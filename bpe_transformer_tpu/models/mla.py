"""Multi-head latent attention (MLA): one attention sublayer's weights,
projections and its two forms.

For a hidden state ``h`` (``config`` names in brackets)::

    c_q        = s_q * RMSNorm(W_qa h)              [q_lora_rank]
    q          = W_qb c_q  -> heads x (nope + rope)  [qk_nope/rope_head_dim]
    [c_kv;k_r] = W_kva h                             [kv_lora_rank + rope]
    c          = s_kv * RMSNorm(c_kv)
    [k_nope;v] = W_kvb c   -> heads x (nope + v)     [v_head_dim]

with ``s_q = sqrt(d_model / q_lora_rank)`` and ``s_kv`` likewise where the
config scales its latents - or, with ``q_lora_rank = 0``, the full-rank
query ``q = W_q h`` and no ``c_q`` - RoPE (interleaved pairs) on ``q_rope``
and the one key ``k_r`` all heads share, ``scores = (q_nope . k_nope +
q_rope . k_r) * scale`` and a causal float32 softmax over ``v``.  ``scale``
is ``(nope + rope) ** -0.5``; where the config stretches its positions
(``yarn_factor``: the frequencies of `ops/rope.yarn_inv_freq`, cos and sin
times ``m(mscale) / m(mscale_all_dim)``) it is that times
``m(mscale_all_dim) ** 2`` (:func:`softmax_scale`).

The sublayer comes in two blocks (`ModelConfig.attention_kind`): twice a
layer in the shortcut-connected double layer, once a layer in the
sequential pre-norm block of a ``layer_pattern``.  Nothing here knows which.

**What is cached** is the *latent row* ``[c ; rope(k_r)]``
(``config.latent_width`` values a position, no heads): :func:`latent_rows`.
Every path writes it.  A decode step's one row against a long cache attends
it **absorbed** (`kernels/pallas/mla_attention.py`): the key up-projection
folded into the query, the value's applied to the output, the rows attended
as they are (:func:`absorb_query` / :func:`unabsorb_output` around the
cache's own attention).  Many rows of one sequence (:func:`rows_attention`:
a paged chunk after a cached prefix, a dense cache's prefill, the plain
forward) attend **expanded** - keys and values of every head made from the
rows, inside one kernel - where the bucket is 256 rows or more on the TPU
and the widths are whole lane tiles, and absorbed in an XLA loop elsewhere
(`mla_attention.mla_chunk_path`: the expanded form does 2.2 times fewer
FLOPs from ~170 query rows a key on).  Both are the same sum, the expanded
order the reference's; ``tests/test_longcatflash.py`` holds every path to
it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.ops.core import linear
from bpe_transformer_tpu.ops.rope import (
    apply_rope,
    rope_tables,
    yarn_inv_freq,
    yarn_mscale,
)


def init_mla_params(rng: jax.Array, config: ModelConfig, dtype=jnp.float32) -> dict:
    """One sublayer's tree.  ``kv_b`` is head-major: rows ``h * (nope + v)
    ..`` are head ``h``'s key up-projection then its value's.  The query is
    ``q_a``, ``q_norm``, ``q_b`` through the bottleneck, or ``q_proj``
    alone at full rank (``q_lora_rank = 0``)."""
    d, heads = config.d_model, config.num_heads
    nope, rope, v = config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    if config.q_lora_rank:
        query = {
            "q_a": (config.q_lora_rank, d),
            "q_b": (heads * (nope + rope), config.q_lora_rank),
        }
    else:
        query = {"q_proj": (heads * (nope + rope), d)}
    shapes = {
        **query,
        "kv_a": (config.kv_lora_rank + rope, d),
        "kv_b": (heads * (nope + v), config.kv_lora_rank),
        "output_proj": (d, heads * v),
    }
    keys = jax.random.split(rng, len(shapes))
    params = {
        name: (
            jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * 0.02
        ).astype(dtype)
        for key, (name, shape) in zip(keys, shapes.items())
    }
    if config.q_lora_rank:
        params["q_norm"] = jnp.ones((config.q_lora_rank,), dtype)
    params["kv_norm"] = jnp.ones((config.kv_lora_rank,), dtype)
    return params


# Scores are large here (the scaled latents give raw scores a spread of ~33
# before the softmax scale, 2.4 after) and softmax hands an absolute error of
# a score on as the same relative error of the output, so what feeds a score
# is rounded to the activation width once, not at every step on the way: the
# rotation runs on float32 tables, a latent's norm, weight and scale are one
# float32 expression (PERF.md section 6, PR 33: 1.0% -> 0.8% a sublayer).


def _rope(x, positions, config: ModelConfig):
    rope = config.qk_rope_head_dim
    if config.yarn_factor == 1.0:
        cos, sin = rope_tables(rope, config.context_length, config.rope_theta)
    else:
        # Stretched positions: the frequencies pair by pair, made once on
        # the host, and both tables at YaRN's magnitude.
        cos, sin = rope_tables(
            rope, config.context_length,
            inv_freq=yarn_inv_freq(
                rope, config.rope_theta, config.yarn_factor,
                config.yarn_original_context, config.yarn_beta_fast,
                config.yarn_beta_slow,
            ),
            magnitude=yarn_mscale(config.yarn_factor, config.yarn_mscale)
            / yarn_mscale(config.yarn_factor, config.yarn_mscale_all_dim),
        )
    return apply_rope(x.astype(jnp.float32), positions, cos, sin).astype(x.dtype)


def _scaled_norm(x, weight, scale: float, eps: float):
    """``scale * RMSNorm(x) * weight``, rounded once."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * (weight.astype(jnp.float32) * scale)).astype(x.dtype)


def queries(h: Array, p: dict, positions: Array, config: ModelConfig):
    """``h`` (batch, rows, d_model) -> ``(q_nope (batch, heads, rows, nope),
    q_rope (batch, heads, rows, rope))``, the latter rotated; ``positions``
    broadcasts against (batch, rows)."""
    with jax.named_scope("mla_q"):
        if config.q_lora_rank:
            c_q = _scaled_norm(
                linear(h, p["q_a"]), p["q_norm"], config.q_lora_scale,
                config.norm_eps,
            )
            q = linear(c_q, p["q_b"])
        else:
            q = linear(h, p["q_proj"])
        q = q.reshape(*q.shape[:-1], config.num_heads, config.d_head)
        q = jnp.swapaxes(q, -2, -3)
        nope = config.qk_nope_head_dim
        q_rope = _rope(q[..., nope:], jnp.expand_dims(positions, -2), config)
        return q[..., :nope], q_rope


def latent_rows(h: Array, p: dict, positions: Array, config: ModelConfig) -> Array:
    """``h`` (batch, rows, d_model) -> the rows latent attention caches,
    (batch, rows, latent_width): the normalised, scaled latent and the
    rotated shared key."""
    with jax.named_scope("mla_kv"):
        kv = linear(h, p["kv_a"])
        rank = config.kv_lora_rank
        c = _scaled_norm(
            kv[..., :rank], p["kv_norm"], config.kv_lora_scale, config.norm_eps
        )
        return jnp.concatenate([c, _rope(kv[..., rank:], positions, config)], axis=-1)


def _kv_b(p: dict, config: ModelConfig) -> Array:
    """(heads, nope + v, rank)."""
    return p["kv_b"].reshape(config.num_heads, -1, config.kv_lora_rank)


def softmax_scale(config: ModelConfig) -> float:
    """``d_head ** -0.5``, times ``m(yarn_mscale_all_dim) ** 2`` where the
    positions are stretched (`ops/rope.yarn_mscale`; 1 without a stretch)."""
    scale = config.d_head ** -0.5
    if config.yarn_factor != 1.0:
        scale *= yarn_mscale(config.yarn_factor, config.yarn_mscale_all_dim) ** 2
    return scale


def rows_attention_path(queries: int, config: ModelConfig) -> str:
    """The form ``queries`` rows of one sequence attend in, ``"mla_chunk"``
    (expanded, the kernel) or ``"xla"`` (the absorbed loop):
    `mla_attention.mla_chunk_path` at the config's widths.  One rule for
    :func:`rows_attention` and for the engine's counters."""
    from bpe_transformer_tpu.kernels.pallas.mla_attention import mla_chunk_path

    return mla_chunk_path(
        queries, config.qk_nope_head_dim, config.v_head_dim, config.kv_lora_rank
    )


def rows_attention(
    q_nope, q_rope, rows, p: dict, q_positions, n_keys, config: ModelConfig
) -> Array:
    """One sequence's queries (heads, queries, .) against its latent
    ``rows`` (keys, latent_width, or as a pool pads them: zeros past it)
    from position 0: (queries, heads * v), before the output projection.
    The form is chosen here, from the shapes and the backend
    (`mla_attention.mla_chunk_path`): a bucket of many rows on the TPU
    expands each block of rows into every head's keys and values inside one
    kernel, everything else folds the up-projections into query and output
    and loops over the rows as they lie."""
    from bpe_transformer_tpu.kernels.pallas.mla_attention import (
        mla_chunk_attention,
        xla_mla_chunk_attention,
    )

    if rows_attention_path(q_nope.shape[1], config) == "mla_chunk":
        attend = mla_chunk_attention
    else:
        attend, rows = xla_mla_chunk_attention, rows[:, : config.latent_width]
    out = attend(
        q_nope, q_rope, rows, _kv_b(p, config), q_positions, n_keys,
        scale=softmax_scale(config),
    )
    return jnp.swapaxes(out, 0, 1).reshape(out.shape[1], -1)


def absorb_query(q_nope, q_rope, p: dict, config: ModelConfig) -> Array:
    """(batch, heads, nope) and (batch, heads, rope) of one query row a
    sequence -> the absorbed query (batch, heads, latent_width): a head's
    ``W_k^T q_nope`` beside its ``q_rope``, to be scored against latent rows
    as they are cached."""
    w_k = _kv_b(p, config)[:, : config.qk_nope_head_dim]
    q_lat = jnp.einsum("bhd,hdc->bhc", q_nope, w_k)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def unabsorb_output(o_lat, p: dict, config: ModelConfig) -> Array:
    """The absorbed attention's (batch, heads, rank) output through each
    head's value up-projection: (batch, heads * v)."""
    w_v = _kv_b(p, config)[:, config.qk_nope_head_dim:]
    out = jnp.einsum("bhc,hdc->bhd", o_lat, w_v)
    return out.reshape(out.shape[0], -1)


def self_attention(h: Array, p: dict, positions: Array, config: ModelConfig):
    """The sublayer on whole sequences attending to themselves (the plain
    forward, a dense cache's prefill): ``h`` (batch, rows, d_model),
    ``positions`` (rows,) -> ``((batch, rows, d_model), latent rows (batch,
    rows, latent_width))``.  A sequence at a time."""
    q_nope, q_rope = queries(h, p, positions, config)
    rows = latent_rows(h, p, positions, config)
    n_keys = h.shape[-2]

    def attend(qn, qr, r):
        return rows_attention(qn, qr, r, p, positions, n_keys, config)

    if rows_attention_path(n_keys, config) == "mla_chunk":
        # The kernel copies a sequence's rows from where they lie in HBM:
        # it takes no batch axis.
        att = jax.lax.map(lambda one: attend(*one), (q_nope, q_rope, rows))
    else:
        att = jax.vmap(attend)(q_nope, q_rope, rows)
    return linear(att, p["output_proj"]), rows


def absorbed_attention(h: Array, p: dict, positions: Array, config: ModelConfig, attend):
    """The sublayer for one query row a sequence, absorbed: ``h`` (batch, 1,
    d_model) -> (batch, 1, d_model).  ``attend(q_abs (batch, heads,
    latent_width), new rows (batch, 1, latent_width))`` writes the rows
    where its cache keeps them and returns the attention over the cached
    rows, (batch, heads, rank)."""
    q_nope, q_rope = queries(h, p, positions, config)
    rows = latent_rows(h, p, positions, config)
    q_abs = absorb_query(q_nope[:, :, 0], q_rope[:, :, 0], p, config)
    out = unabsorb_output(attend(q_abs, rows), p, config)
    return linear(out[:, None, :], p["output_proj"])
