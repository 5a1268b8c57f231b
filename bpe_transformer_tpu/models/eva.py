"""EVA chunked linear attention (``attention_kind="eva"``; Zheng, Yuan,
Wang, Kong, *Efficient Attention via Control Variates*, ICLR 2023, as the
EvaByte family runs it).

Positions come in windows of ``W = eva_window`` and chunks of ``C =
eva_chunk``.  With ``q_i, k_j`` the rotated projections, ``d`` the head
width and two learned vectors a head, ``mu`` and ``phi``:

* a complete chunk ``c`` (positions ``cC .. cC + C - 1``) has a **summary
  row**: the key ``k~_c = sum_j softmax_j(k_j . mu) k_j`` and the value
  ``v~_c = sum_j softmax_j((k_j . phi - |k_j|^2 / 2) / sqrt(d)) v_j``, both
  softmaxes over the chunk's ``C`` rows (:func:`chunk_summaries`);
* query ``i`` in window ``w = i // W`` attends, in ONE softmax at scale
  ``1 / sqrt(d)``, to the exact keys of its own window up to itself
  (``wW <= j <= i``) and to the summary rows of every chunk of every
  earlier window (``c < w W / C``).  The open window's own chunks are not
  visible: they become so together, when the window closes.

So what a sequence has to keep grows by one row per ``C`` positions once
the window those positions lie in has closed, and the exact rows of a
closed window are dropped.  Three forms that agree:

* :func:`self_attention` - a whole sequence from position 0, masks written
  out (`transformer.forward`, the tests' oracle inside the package);
* :func:`xla_eva_chunk_attention` - one sequence's chunk of queries over
  its cached rows, summaries first and then the open window, in a loop that
  follows the live keys (`models/decode.EvaRows`, a prefill chunk);
* one row a slot over the same rows under a plain length: no function of
  its own - the cached rows lie so that the dense pool's tick attention
  (`kernels/pallas/decode_attention`) is it.

Scores and the softmaxes' sums are float32 over operands at the activation
width (the published ``mixedp_attn``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.ops.core import linear, merge_heads

NEG_INF = -1e30
#: Keys a step of the chunk's loop scores (a float32 tile of heads x
#: queries x this is live at a time).
EVA_CHUNK_KEY_BLOCK = 512


def init_eva_params(rng: jax.Array, config: ModelConfig, dtype=jnp.float32) -> dict:
    """q, k, v, o as the plain block has them, and the two pooling vectors a
    head by the published init: a normal draw clamped to +-1, times
    ``d_head ** -0.25``."""
    d, width = config.d_model, config.num_heads * config.d_head
    k = jax.random.split(rng, 6)

    def dense(key, d_out, d_in):
        w = jax.random.truncated_normal(key, -3.0, 3.0, (d_out, d_in), jnp.float32)
        return (w * 0.02).astype(dtype)

    def pooling(key):
        w = jax.random.normal(key, (config.num_heads, config.d_head), jnp.float32)
        return (jnp.clip(w, -1.0, 1.0) * config.d_head ** -0.25).astype(dtype)

    return {
        "q_proj": dense(k[0], width, d),
        "k_proj": dense(k[1], width, d),
        "v_proj": dense(k[2], width, d),
        "output_proj": dense(k[3], d, width),
        "eva_mu": pooling(k[4]),
        "eva_phi": pooling(k[5]),
    }


@jax.named_scope("eva_summary")
def chunk_summaries(k: Array, v: Array, mu: Array, phi: Array) -> tuple[Array, Array]:
    """The summary rows of whole chunks: ``k``, ``v`` (..., heads, chunks,
    C, d_head), ``mu``, ``phi`` (heads, d_head) -> ``(k~, v~)`` (..., heads,
    chunks, d_head) at ``k``'s width.  The poolings' logits, softmaxes and
    sums are float32."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    mu = mu.astype(jnp.float32)[:, None, None, :]
    phi = phi.astype(jnp.float32)[:, None, None, :]
    d = k.shape[-1]
    key_w = jax.nn.softmax(jnp.sum(k32 * mu, axis=-1), axis=-1)
    value_logits = (
        jnp.sum(k32 * phi, axis=-1) - 0.5 * jnp.sum(k32 * k32, axis=-1)
    ) * d ** -0.5
    value_w = jax.nn.softmax(value_logits, axis=-1)
    return (
        jnp.sum(key_w[..., None] * k32, axis=-2).astype(k.dtype),
        jnp.sum(value_w[..., None] * v32, axis=-2).astype(v.dtype),
    )


def project_qkv(h: Array, attn: dict, positions: Array, config: ModelConfig):
    """``h`` (batch, rows, d_model) -> rotated ``q``, ``k`` and ``v``
    (batch, heads, rows, d_head); ``positions`` broadcasts against (batch,
    rows).  The plain block's projections and rotation."""
    from bpe_transformer_tpu.models.decode import _project_qkv, _rope_qk

    q, k, v = _project_qkv(h, attn, config)
    return (*_rope_qk(q, k, positions, config), v)


def visibility(positions: Array, n_keys: int, n_chunks: int, config: ModelConfig):
    """The two masks written out, for queries at ``positions`` (rows,) of a
    sequence from position 0: ``(exact, summaries)`` - (rows, n_keys), key
    ``j`` is of the query's own window and not after it; (rows, n_chunks),
    chunk ``c`` lies in an earlier window."""
    window, chunk = config.eva_window, config.eva_chunk
    i = positions[:, None]
    j = jnp.arange(n_keys)[None, :]
    c = jnp.arange(n_chunks)[None, :]
    exact = (j <= i) & (j // window == i // window)
    summaries = (c * chunk) // window < i // window
    return exact, summaries


@jax.named_scope("eva_attn")
def self_attention(h: Array, attn: dict, positions: Array, config: ModelConfig) -> Array:
    """One attention sublayer over whole sequences from position 0: ``h``
    (batch, seq, d_model), ``positions`` (seq,) = ``arange(seq)``.
    Materialized scores over ``seq + seq // C`` keys a query."""
    q, k, v = project_qkv(h, attn, positions, config)
    batch, heads, seq, d = q.shape
    chunk = config.eva_chunk
    n_chunks = -(-seq // chunk)
    pad = ((0, 0), (0, 0), (0, n_chunks * chunk - seq), (0, 0))
    # A trailing part-filled chunk has no summary; its row here is of the
    # last window and so visible to no query.
    k_sum, v_sum = chunk_summaries(
        jnp.pad(k, pad).reshape(batch, heads, n_chunks, chunk, d),
        jnp.pad(v, pad).reshape(batch, heads, n_chunks, chunk, d),
        attn["eva_mu"], attn["eva_phi"],
    )
    exact, summaries = visibility(positions, seq, n_chunks, config)
    keys = jnp.concatenate([k_sum, k], axis=2)
    values = jnp.concatenate([v_sum, v], axis=2)
    visible = jnp.concatenate([summaries, exact], axis=1)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, keys, preferred_element_type=jnp.float32
    ) * d ** -0.5
    scores = jnp.where(visible, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    att = jnp.einsum("bhqk,bhkd->bhqd", probs, values)
    return linear(merge_heads(att), attn["output_proj"])


@jax.named_scope("eva_chunk_attn")
def xla_eva_chunk_attention(
    q: Array, k_rows: Array, v_rows: Array, n_summaries, first, n_rows,
) -> Array:
    """One sequence's chunk of queries against its cached rows: ``q``
    (heads, rows, d_head), row ``r`` the query at position ``first + r`` of
    the open window (``first``, traced, counts from the window's start; the
    first ``n_rows`` rows are real); ``k_rows``, ``v_rows`` (heads, keys,
    d_head) the sequence's rows as its table holds them - ``n_summaries``
    (traced) summary rows, all of them visible to every query, then the
    open window's rows from its start, row ``j`` visible to queries at or
    after it.  Returns (heads, rows, d_head).

    A flash loop in XLA, as `xla_mla_chunk_attention` is: a block of keys is
    scored in float32 and folded into a running softmax, so nothing as
    large as queries x keys x heads is ever held, and the loop runs over
    the ``n_summaries + first + n_rows`` live keys, not the table."""
    heads, rows, d = q.shape
    keys = k_rows.shape[1]
    block = min(EVA_CHUNK_KEY_BLOCK, keys)
    pad = ((0, 0), (0, -keys % block), (0, 0))
    k_rows, v_rows = jnp.pad(k_rows, pad), jnp.pad(v_rows, pad)
    scale = d ** -0.5
    q_at = n_summaries + first + jnp.arange(rows)  # a query's own row

    def step(i, carry):
        m_prev, l_prev, acc = carry
        k_part = jax.lax.dynamic_slice_in_dim(k_rows, i * block, block, axis=1)
        v_part = jax.lax.dynamic_slice_in_dim(v_rows, i * block, block, axis=1)
        s = jnp.einsum(
            "hqd,hkd->hqk", q, k_part, preferred_element_type=jnp.float32
        )
        # Summaries lie before every query's own row: one comparison.
        at = i * block + jnp.arange(block)
        s = jnp.where(at[None, :] <= q_at[:, None], s * scale, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "hqk,hkd->hqd", p.astype(v_part.dtype), v_part,
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    live = n_summaries + first + n_rows
    _, l, acc = jax.lax.fori_loop(
        0, (live + block - 1) // block, step,
        (
            jnp.full((heads, rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, rows, 1), jnp.float32),
            jnp.zeros((heads, rows, d), jnp.float32),
        ),
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
