"""The plain reference of granite-4.0-h-small (``model_type``
``granitemoehybrid``; "Granite 4.0-H Small 32B-A9B"), written from its
published ``config.json`` (keys in brackets) and the catalog's description
("Mamba-2 (128 heads, d_state 128); GQA NoPE - 40L: 36 mamba + 4 attention",
"72 experts, top-10, 1 shared").

* Norms: ``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``
  [``normalization_function``], a learned weight, no bias.
* Embedding ``x_0 = embedding_multiplier * E[token]``; logits ``(N_f(x_L)
  E^T) / logits_scaling`` [``tie_word_embeddings``].
* **The layer** [``num_hidden_layers``; kind by ``layer_types``]: ``a = x + r
  * Mixer(N1 x)``; ``h = N2 a``; ``y = a + r * (M(h) + S(h))``, ``r`` =
  ``residual_multiplier``.
* **Mamba-2 mixer** on ``u`` (T, hidden): ``mamba_n_heads`` heads of
  ``mamba_d_head`` (inner width ``mamba_expand`` x hidden), state
  ``mamba_d_state``, one group [``mamba_n_groups``]: ``[z ; xBC ; dt] = W_in
  u`` (no bias [``mamba_proj_bias``]); ``xBC_t = silu(b_c + sum_j w_c[:, j]
  xBC_{t-(k-1)+j})``, a depthwise causal convolution of width k =
  ``mamba_d_conv`` with bias [``mamba_conv_bias``], zeros left of the
  sequence's start; ``[x ; B ; C] = xBC`` (``B`` and ``C`` shared by all
  heads); ``dt = softplus(dt + dt_bias)`` a head, no clamp; ``A = -exp(A_log)``;
  **state** ``H_t = exp(dt_t A) H_{t-1} + (dt_t x_t) (x) B_t`` (head_dim x
  state a head, ``H_{-1} = 0``); ``y_t = H_t C_t + D x_t``; ``out = W_out
  RMSNorm(y * silu(z))``, one norm over all inner channels.  **Always the
  recurrence, step by step** (``lax.scan`` over positions); the program's
  chunked form [``mamba_chunk_size``] is the same sum.
* **Attention** (``layer_types[l] == "attention"``): ``num_attention_heads``
  query heads over ``num_key_value_heads`` KV heads of ``hidden_size /
  num_attention_heads``, no biases [``attention_bias``], no positional
  encoding at all [``position_embedding_type`` "nope"], ``scores = (q . k) *
  attention_multiplier``, causal softmax.
* **Routed layer** ``M(h)``: ``l = W_r h`` over the router's ``n_experts``
  outputs (the published ``num_local_experts``, 72); ``T`` = the
  ``num_experts_per_tok`` largest; ``g = softmax(l[T])``; ``M(h) = sum_{e in
  T} g_e E_e(h)``, every expert a SwiGLU of width ``intermediate_size``.  No
  capacity: nothing is dropped.
* **Shared expert** ``S(h)``: one SwiGLU of width
  ``shared_intermediate_size``, ungated, added whole.

**This chip's share.**  ``cfg["num_local_experts"]`` experts are held,
numbered ``expert_offset ..`` of the router's ``n_experts``: the router keeps
all its outputs and its experts per token, gates are normalised over all
chosen experts, and ``M`` sums over ``T`` *and held* only.  What the absent
experts would add is left out and the partial result goes on to the next
layer.  The vocabulary is the slice ``vocab_size`` of the file.

Departures from the published description, all of them: (1) everything
marked *assumed* in the configuration file (no clamp on ``dt``, gate before
the one-group norm, the float32 state, the seeded values of ``A_log``,
``dt_bias``, ``D`` and the convolution); (2) at width the weights stay
bfloat16-valued on the device and are cast to float32 a matrix and an
expert at a time, and attention runs in blocks of queries: the same
arithmetic in an order that fits the chip; (3) the routed sum runs over the
held experts with a gate of zero where one was not chosen, which is the same
sum; (4) :func:`followed_routings` computes single positions again from the
state the full forward left before them - the same recurrence, restarted at
a stored state.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, cache or batching; imports nothing of ``bpe_transformer_tpu``, and
from ``reference_cohere2moe`` the seed's generator, the float8 control's
rounding, the near ties of a routing and the row-block helper.
``quant="fp8"`` is the control of ``correct`` (see ``reference.py``).
"""

from __future__ import annotations

import functools
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference_cohere2moe import (
    HEAD_ROWS,
    QUERY_BLOCK,
    _draw,
    _draw_jit,
    _fake_fp8,
    _Frozen,
    _in_blocks,
    _matmul,
    _mix,
    _swiglu,
    routing_choices,
)

SEQUENCE_SIZES = 1  # served sequences are scored at this many padded lengths
STATE_EVERY = 64    # the full forward keeps a layer's state every so many positions
ROW_BLOCK = 512     # single rows go through a layer in blocks of this many
MAX_ROUTINGS = 8    # routings followed for one served position, at most
NORM_LEAVES = 1 << 20   # the norms' leaf numbers start here, past every matrix's
VALUE_LEAVES = 1 << 21  # ... and the state-space layers' seeded values' here


# ------------------------------------------------------------------ weights


def _uniform(seed, shape):
    """Uniform (0, 1) from a uint32 ``seed``, element i a hash of (seed, i)
    (``reference_cohere2moe._draw``'s generator before its normal)."""
    index = jax.lax.iota(jnp.uint32, math.prod(shape)).reshape(shape)
    bits = _mix(_mix(index + seed * jnp.uint32(0x9E3779B9)) ^ seed)
    return (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24)) + 0.5 / (1 << 24)


def widths(cfg: dict) -> dict:
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    assert inner == cfg["mamba_n_heads"] * cfg["mamba_d_head"] and cfg["mamba_n_groups"] == 1
    return {
        "inner": inner, "channels": inner + 2 * cfg["mamba_d_state"],
        "d_head": cfg["hidden_size"] // cfg["num_attention_heads"],
    }


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def init_weights(seed: int, cfg: dict, dtype=jnp.float32, draw=_draw) -> dict:
    """The benchmark's weights from ``--seed`` in the program's tree layout:
    truncated normal (+-3 sigma) times 0.02 for every matrix and the
    convolution's bias, for every norm 1 + 5 times such a draw (0.7 .. 1.3:
    a norm weight dropped or applied twice moves the logits), and for a
    state-space layer the family's initialisation where a normal draw would
    be degenerate: ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus
    of a log-uniform in (1e-3, 1e-1), ``D = 1``, convolution weights ``U(-1/2,
    1/2)``.  The tied embedding is such a draw over ``embedding_multiplier``,
    so that the stream enters at 0.02 an element (``x_0 = 12 E``) as a
    layer's matrices expect: at 0.02 itself ``12 E[t] . E[t]`` puts every
    position's own input token ten sigma above every other logit, in any
    precision, and no comparison of logits tells bfloat16 from float8.
    Leaves are numbered in tree order (the tied embedding is matrix 0) and
    leaf m is drawn from the seed ``hash(seed, m)``."""
    d, ff, sff = cfg["hidden_size"], cfg["intermediate_size"], cfg["shared_intermediate_size"]
    w = widths(cfg)
    heads, k = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    d_q = cfg["num_attention_heads"] * w["d_head"]
    d_kv = cfg["num_key_value_heads"] * w["d_head"]
    held = cfg["num_local_experts"]
    counters = {"matrix": iter(range(NORM_LEAVES)), "norm": iter(range(NORM_LEAVES, VALUE_LEAVES)),
                "value": iter(range(VALUE_LEAVES, 2 * VALUE_LEAVES))}

    def leaf_seed(kind):
        return jnp.uint32((int(seed) * 1000003 + next(counters[kind]) * 7919 + 12345) % 2**32)

    def dense(*shape):
        return draw(leaf_seed("matrix"), shape, dtype)

    def norm(width):
        return (1.0 + 5.0 * draw(leaf_seed("norm"), (width,), jnp.float32)).astype(dtype)

    def uniform(*shape):
        return _uniform(leaf_seed("value"), shape)

    def mamba():
        dt = jnp.exp(math.log(1e-3) + uniform(heads) * (math.log(1e-1) - math.log(1e-3)))
        return {
            "in_proj": dense(w["inner"] + w["channels"] + heads, d),
            "conv_w": (uniform(w["channels"], k) - 0.5).astype(dtype),
            "conv_b": dense(w["channels"]),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(1.0 + 15.0 * uniform(heads)).astype(dtype),
            "D": jnp.ones((heads,), dtype),
            "norm": norm(w["inner"]),
            "out_proj": dense(d, w["inner"]),
        }

    def attention():
        return {
            "q_proj": dense(d_q, d), "k_proj": dense(d_kv, d),
            "v_proj": dense(d_kv, d), "output_proj": dense(d, d_q),
        }

    embedding = (
        draw(leaf_seed("matrix"), (cfg["vocab_size"], d), jnp.float32)
        / cfg["embedding_multiplier"]
    ).astype(dtype)
    layers = []
    for kind in layer_kinds(cfg):
        mixer = {"ssm": mamba()} if kind == "mamba" else {"attn": attention()}
        layers.append({
            **mixer, "ln1": norm(d), "ln2": norm(d),
            "ffn": {
                "router": dense(cfg["n_experts"], d),
                "w1": dense(held, ff, d), "w2": dense(held, d, ff), "w3": dense(held, ff, d),
                "shared": {"w1": dense(1, sff, d), "w2": dense(1, d, sff), "w3": dense(1, sff, d)},
            },
        })
    return {"token_embeddings": embedding, "layers": layers, "ln_final": norm(d)}


def weights_from_seed(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    """On the device, one small jitted program a matrix shape."""
    return init_weights(seed, cfg, dtype, draw=_draw_jit)


# ------------------------------------------------------------------ forward


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def _f32(p, *names):
    return tuple(p[name].astype(jnp.float32) for name in names)


def mamba_project(u, p, cfg: dict, quant):
    """``u`` (rows, hidden) -> ``(z, xBC before the convolution, dt)``."""
    w = widths(cfg)
    zxbcdt = _matmul(u, p["in_proj"], quant)
    dt = jax.nn.softplus(zxbcdt[:, w["inner"] + w["channels"]:] + p["dt_bias"].astype(jnp.float32))
    return zxbcdt[:, : w["inner"]], zxbcdt[:, w["inner"]: w["inner"] + w["channels"]], dt


def mamba_conv(pre, history, p):
    """xBC rows ``pre`` (rows, channels), each behind its own ``history``
    (rows, k - 1, channels) -> the convolution's activated outputs."""
    conv_w, conv_b = _f32(p, "conv_w", "conv_b")
    window = jnp.concatenate([history, pre[:, None]], axis=1)          # (rows, k, channels)
    return jax.nn.silu(conv_b + jnp.einsum("rjc,cj->rc", window, conv_w))


def behind(pre, k: int):
    """``pre`` with k - 1 rows of zeros before it: row i + j, j < k - 1, is
    the j-th row of position i's history."""
    return jnp.concatenate([jnp.zeros((k - 1, pre.shape[1]), jnp.float32), pre])


def split_xbc(act, cfg: dict, quant):
    """``(x (rows, heads, head_dim), B, C)`` of activated xBC rows."""
    w, n = widths(cfg), cfg["mamba_d_state"]
    x = act[:, : w["inner"]].reshape(-1, cfg["mamba_n_heads"], cfg["mamba_d_head"])
    b, c = act[:, w["inner"]: w["inner"] + n], act[:, w["inner"] + n:]
    if quant == "fp8":
        x, b, c = _fake_fp8(x), _fake_fp8(b), _fake_fp8(c)
    return x, b, c


def state_step(h, x_t, b_t, dt_t, a):
    """``H_t`` from ``H_{t-1}``: one position of the recurrence."""
    return jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * b_t


def mamba_out(y, z, p, cfg: dict, quant):
    gated = _rmsnorm(y.reshape(y.shape[0], -1) * jax.nn.silu(z), p["norm"], cfg["rms_norm_eps"])
    return _matmul(gated, p["out_proj"], quant)


def mamba(u, p, cfg: dict, quant):
    """The mixer over a whole sequence from its start, the recurrence step
    by step: ``((S, hidden), what :func:`row_block_mamba` needs of it)`` -
    the state before every :data:`STATE_EVERY`-th position, xBC before
    (:func:`behind`) and after the convolution and ``dt`` of every position."""
    s, k = u.shape[0], cfg["mamba_d_conv"]
    z, pre, dt = mamba_project(u, p, cfg, quant)
    padded = behind(pre, k)
    act = mamba_conv(pre, jnp.stack([padded[j: j + s] for j in range(k - 1)], axis=1), p)
    x, b, c = split_xbc(act, cfg, quant)
    (a_log, d_skip) = _f32(p, "A_log", "D")
    a = -jnp.exp(a_log)
    every = math.gcd(s, STATE_EVERY)

    def position(h, xs):
        x_t, b_t, c_t, dt_t = xs
        h = state_step(h, x_t, b_t, dt_t, a)
        return h, jnp.einsum("hpn,n->hp", h, c_t) + d_skip[:, None] * x_t

    def stretches(h, xs):
        h_end, y = jax.lax.scan(position, h, xs)
        return h_end, (y, h)  # the state BEFORE the stretch is what is kept

    shaped = tuple(v.reshape(s // every, every, *v.shape[1:]) for v in (x, b, c, dt))
    start = jnp.zeros((cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]), jnp.float32)
    _, (y, states) = jax.lax.scan(stretches, start, shaped)
    y = y.reshape(s, cfg["mamba_n_heads"], cfg["mamba_d_head"])
    return mamba_out(y, z, p, cfg, quant), (states, padded, act, dt)


def attention(h, p, cfg: dict, quant):
    """``h`` (S, hidden) -> ``((S, hidden), (k, v))``: plain causal GQA with
    no positional transform, scores times ``attention_multiplier``; ``k``
    and ``v`` (kv heads, S, head_dim) are what :func:`row_attention` reads."""
    s = h.shape[0]
    heads, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], widths(cfg)["d_head"]

    def split(t, n):
        return t.reshape(s, n, dh).transpose(1, 0, 2)

    q = split(_matmul(h, p["q_proj"], quant), heads)
    k = split(_matmul(h, p["k_proj"], quant), kvh)
    v = split(_matmul(h, p["v_proj"], quant), kvh)
    if quant == "fp8":
        q, k, v = _fake_fp8(q), _fake_fp8(k), _fake_fp8(v)
    qg = q.reshape(kvh, heads // kvh, s, dh)
    block = math.gcd(s, QUERY_BLOCK)
    key_pos = jnp.arange(s)[None, :]

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=2)
        scores = jnp.einsum("kgqd,ktd->kgqt", qb, k) * cfg["attention_multiplier"]
        scores = jnp.where(key_pos <= start + jnp.arange(block)[:, None], scores, -jnp.inf)
        return jnp.einsum("kgqt,ktd->kgqd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))  # (nb, kv, g, block, dh)
    merged = out.transpose(0, 3, 1, 2, 4).reshape(s, heads * dh)
    return _matmul(merged, p["output_proj"], quant), (k, v)


def moe(h, p, cfg: dict, quant, chosen=None):
    """``h`` (S, hidden) -> ``M(h) + S(h)``: this share's routed part plus
    the shared expert whole.  ``chosen`` (S, experts per token) names each
    token's experts in place of the router's own largest; the gates are the
    softmax over whatever is named."""
    held, offset = cfg["num_local_experts"], cfg.get("expert_offset", 0)
    logits = _matmul(h, p["router"], None)  # the router is never rounded
    if chosen is None:
        _, chosen = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    gates = jax.nn.softmax(jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)

    def one_expert(total, xs):
        w1, w2, w3, e = xs
        gate = jnp.sum(jnp.where(chosen == e + offset, gates, 0.0), axis=-1)
        return total + gate[:, None] * _swiglu(h, w1, w2, w3, quant), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h), (p["w1"], p["w2"], p["w3"], jnp.arange(held))
    )
    sh = p["shared"]
    return routed + _swiglu(h, sh["w1"][0], sh["w2"][0], sh["w3"][0], quant)


def block(x, p, cfg: dict, quant):
    """One layer over a whole sequence: ``(y, the mixer's memory)``."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = _rmsnorm(x, p["ln1"], eps)
    mixed, memory = mamba(u, p["ssm"], cfg, quant) if "ssm" in p else attention(u, p["attn"], cfg, quant)
    a = x + r * mixed
    return a + r * moe(_rmsnorm(a, p["ln2"], eps), p["ffn"], cfg, quant), memory


def keep_stretch(states, padded, act, dt, first, n: int, every: int, k: int):
    """Of what :func:`mamba` kept of a whole sequence, the ``n`` positions
    from ``first`` (a traced multiple of ``every``; zeros past the
    sequence's end): one shape whatever the stretch, so one program."""

    def stretch(arr, start, size):
        arr = jnp.concatenate([arr, jnp.zeros((size, *arr.shape[1:]), arr.dtype)])
        return jax.lax.dynamic_slice_in_dim(arr, start, size)

    return {
        "states": stretch(states, first // every, n // every),
        "behind": stretch(padded, first, n + k - 1),
        "act": stretch(act, first, n), "dt": stretch(dt, first, n),
    }


_keep_jit = jax.jit(keep_stretch, static_argnames=("n", "every", "k"))


def _row_tail(x, mixed, p, cfg: dict):
    """``(h, a, router logits)`` of rows whose mixer gave ``mixed``."""
    a = x + cfg["residual_multiplier"] * mixed
    h = _rmsnorm(a, p["ln2"], cfg["rms_norm_eps"])
    return h, a, _matmul(h, p["ffn"]["router"], None)


def row_block_attention(x, positions, p, k_seq, v_seq, cfg: dict):
    """The layer up to its expert layer for single rows: row r is a token at
    ``positions[r]`` of a sequence whose keys and values are ``k_seq``,
    ``v_seq``; it sees those before its position and its own."""
    rows = x.shape[0]
    heads, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], widths(cfg)["d_head"]
    u, attn = _rmsnorm(x, p["ln1"], cfg["rms_norm_eps"]), p["attn"]
    q = _matmul(u, attn["q_proj"], None).reshape(rows, kvh, heads // kvh, dh)
    k = _matmul(u, attn["k_proj"], None).reshape(rows, kvh, dh)
    v = _matmul(u, attn["v_proj"], None).reshape(rows, kvh, dh)
    before = jnp.einsum("rkgd,ktd->rkgt", q, k_seq) * cfg["attention_multiplier"]
    visible = jnp.arange(k_seq.shape[1])[None, :] < positions[:, None]
    before = jnp.where(visible[:, None, None, :], before, -jnp.inf)
    own = jnp.einsum("rkgd,rkd->rkg", q, k) * cfg["attention_multiplier"]
    weights = jax.nn.softmax(jnp.concatenate([before, own[..., None]], axis=-1), axis=-1)
    out = jnp.einsum("rkgt,ktd->rkgd", weights[..., :-1], v_seq) + weights[..., -1:] * v[:, :, None, :]
    return _row_tail(x, _matmul(out.reshape(rows, heads * dh), attn["output_proj"], None), p, cfg)


def row_block_mamba(x, positions, at_step, p, kept, base, cfg: dict, every: int):
    """The layer up to its expert layer for single rows of a state-space
    layer.  ``kept`` is what the full forward left of a stretch of the
    sequence (:func:`keep_stretch`: a state every ``every`` positions, xBC
    before and after the convolution and ``dt`` of every position) and
    ``positions`` the rows' own, counted from the stretch's start.  The
    recurrence runs from the kept state before position ``base`` over the
    full forward's own ``(x, B, dt)``, and before it takes position ``base +
    i`` the rows ``at_step[i]`` (row numbers, ``rows`` where there is none)
    are computed from the state before them and their own input."""
    rows, span, k = x.shape[0], at_step.shape[0], cfg["mamba_d_conv"]
    inner, n = widths(cfg)["inner"], cfg["mamba_d_state"]
    ssm = p["ssm"]
    state = jax.lax.dynamic_index_in_dim(kept["states"], base // every, keepdims=False)
    act = jax.lax.dynamic_slice_in_dim(kept["act"], base, span)
    steps = (
        act[:, :inner].reshape(span, cfg["mamba_n_heads"], -1), act[:, inner: inner + n],
        jax.lax.dynamic_slice_in_dim(kept["dt"], base, span),
    )
    history = kept["behind"][positions[:, None] + jnp.arange(k - 1)]
    u = _rmsnorm(x, p["ln1"], cfg["rms_norm_eps"])
    z, pre, dt = mamba_project(u, ssm, cfg, None)
    x_r, b_r, c_r = split_xbc(mamba_conv(pre, history, ssm), cfg, None)
    (a_log, d_skip) = _f32(ssm, "A_log", "D")
    a = -jnp.exp(a_log)
    c_pad = jnp.concatenate([c_r, jnp.zeros((1, c_r.shape[1]), jnp.float32)])

    def position(h, xs):
        picked, x_t, b_t, dt_t = xs
        seen = jnp.einsum("hpn,kn->khp", h, c_pad[picked])      # C_r . H_{t-1}
        return state_step(h, x_t, b_t, dt_t, a), seen

    _, seen = jax.lax.scan(position, state, (at_step, *steps))  # (steps, per step, heads, head_dim)
    # Row r is entry (i, j) of at_step: invert the table.
    flat = at_step.reshape(-1)
    where = jnp.zeros((rows + 1,), jnp.int32).at[flat].set(jnp.arange(flat.shape[0], dtype=jnp.int32))
    before = seen.reshape(flat.shape[0], *seen.shape[2:])[where[:rows]]
    y = (
        jnp.exp(dt * a)[:, :, None] * before
        + (dt[:, :, None] * x_r) * jnp.sum(b_r * c_r, axis=-1)[:, None, None]
        + d_skip[:, None] * x_r
    )
    return _row_tail(x, mamba_out(y, z, ssm, cfg, None), p, cfg)


def row_block_experts(h, chosen, p, cfg: dict):
    """``M(h) + S(h)`` with each row's experts given."""
    return moe(h, p["ffn"], cfg, None, chosen)


def head(x, w, cfg: dict, quant):
    h = _rmsnorm(x, w["ln_final"], cfg["rms_norm_eps"])
    return _matmul(h, w["token_embeddings"], quant) / cfg["logits_scaling"]


_block_jit = jax.jit(block, static_argnames=("cfg", "quant"))
_row_attention_jit = jax.jit(row_block_attention, static_argnames=("cfg",))
_row_mamba_jit = jax.jit(row_block_mamba, static_argnames=("cfg", "every"))
_row_experts_jit = jax.jit(row_block_experts, static_argnames=("cfg",))
_head_jit = jax.jit(head, static_argnames=("cfg", "quant"))


def _gaps(x, tokens, w, cfg: dict, quant):
    """By how much each row's token lies below the row's best logit."""
    logits = head(x, w, cfg, quant)
    return jnp.max(logits, axis=-1) - jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]


_gaps_jit = jax.jit(_gaps, static_argnames=("cfg", "quant"))
_best_jit = jax.jit(
    lambda x, w, cfg, quant: jnp.argmax(head(x, w, cfg, quant), axis=-1).astype(jnp.int32),
    static_argnames=("cfg", "quant"),
)


def _embed(w, cfg, ids):
    return cfg["embedding_multiplier"] * w["token_embeddings"][jnp.asarray(ids)].astype(jnp.float32)


def hidden_states(
    w: dict, row, cfg: dict, quant: str | None = None, memory: list | None = None,
    keep: tuple = (0, None),
):
    """``(S,)`` token ids -> ``(S, hidden)`` after the last block, a layer at
    a time: one jitted program a layer kind, not one for the model.
    ``memory`` (a list) collects what each layer's mixer left for
    :func:`followed_routings` to compute positions ``keep[0] .. keep[1] - 1``
    again: an attention layer's keys and values, a state-space layer's
    stretch (:func:`keep_stretch`) from the last kept state at or before
    ``keep[0]`` (``first``)."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    lo, hi = keep[0], len(row) if keep[1] is None else keep[1]
    every = math.gcd(len(row), STATE_EVERY)
    first = lo // every * every
    with jax.default_matmul_precision("highest"):
        x = _embed(w, cfg, row)
        for p in w["layers"]:
            x, kept = _block_jit(x, p, cfg=cfg, quant=quant)
            if memory is not None and "ssm" in p:
                # A whole number of row blocks and one block's span past it.
                n = -(-(hi - first) // ROW_BLOCK) * ROW_BLOCK + every + ROW_BLOCK
                kept = {"first": first, "every": every, "stretch": _keep_jit(
                    *kept, np.int32(first), n=n, every=every, k=cfg["mamba_d_conv"]
                )}
            if memory is not None:
                memory.append(kept)
        return x


def forward_logits(w: dict, tokens, cfg: dict, quant: str | None = None):
    """``(B, S)`` token ids -> ``(B, S, V)`` float32 logits."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head_jit(hidden_states(w, row, cfg, quant), w, cfg=cfg, quant=quant)
            for row in np.asarray(tokens)
        ])


# ------------------------------------------------------------------ serving


def _mamba_rows(x, position, p, kept, cfg):
    """:func:`row_block_mamba` over host rows sorted by position, a block of
    :data:`ROW_BLOCK` at a time, each from the nearest state the full forward
    kept before the block's first row.  Every call has the same shapes."""
    every = kept["every"]
    span = every + ROW_BLOCK            # positions a block's rows may lie at, from its base
    outs = []
    for start in range(0, len(x), ROW_BLOCK):
        rows = slice(start, start + ROW_BLOCK)
        pos = position[rows] - kept["first"]   # counted from the kept stretch's start
        pad = ROW_BLOCK - len(pos)
        base = int(pos[0]) // every * every
        # Rows by the step before which they are computed.
        at_step = np.full((span, MAX_ROUTINGS), ROW_BLOCK, np.int32)
        fill = np.zeros(span, np.int32)
        for r, step in enumerate(pos - base):
            at_step[step, fill[step]] = r
            fill[step] += 1
        got = _row_mamba_jit(
            np.pad(x[rows], [(0, pad), (0, 0)]), np.pad(pos, (0, pad)).astype(np.int32),
            at_step, p, kept["stretch"], np.int32(base), cfg=cfg, every=every,
        )
        outs.append([np.asarray(g)[: ROW_BLOCK - pad] for g in got])
    return [np.concatenate(column) for column in zip(*outs)]


def followed_routings(w: dict, cfg: dict, tokens, memory: list, lo: int, hi: int):
    """As ``reference_cohere2moe.followed_routings``: the hidden states after
    the last block of positions ``lo .. hi - 1`` of ``tokens``, once for
    every routing a 16-bit computation may have taken *at that position*
    (its ``routing_choices``, in each layer on the state that the routing so
    far produced): ``(states (rows, hidden), position index of each row)``.
    Earlier positions are what the full forward made of them (``memory``,
    from :func:`hidden_states`): an attention layer's keys and values, a
    state-space layer's state before the position.  A position is followed
    along at most :data:`MAX_ROUTINGS` routings, of which each layer may use
    its share (half of what the next may), nearest ties first in row order."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    near = _Frozen(
        num_experts=cfg["num_local_experts"], expert_offset=cfg.get("expert_offset", 0),
        num_experts_per_tok=cfg["num_experts_per_tok"],
    )
    position = np.arange(lo, hi, dtype=np.int32)
    origin = np.arange(hi - lo)
    layers = len(w["layers"])
    with jax.default_matmul_precision("highest"):
        x = np.asarray(_embed(w, cfg, tokens[lo:hi]))
        for layer, (p, kept) in enumerate(zip(w["layers"], memory)):
            if "ssm" in p:
                h, a, logits = _mamba_rows(x, position, p, kept, cfg)
            else:
                h, a, logits = _in_blocks(
                    functools.partial(_row_attention_jit, cfg=cfg), [x, position], p, *kept,
                    block=ROW_BLOCK,
                )
            cap = max(2, MAX_ROUTINGS >> (layers - 1 - layer))
            followed = np.bincount(origin, minlength=hi - lo)
            parent, chosen = [], []
            for row, sets in enumerate(routing_choices(logits, near)):
                room = max(cap - followed[origin[row]], 0)
                sets = sets[: 1 + room]
                followed[origin[row]] += len(sets) - 1
                parent += [row] * len(sets)
                chosen += sets
            parent = np.asarray(parent)
            (experts,) = _in_blocks(
                functools.partial(_row_experts_jit, cfg=cfg),
                [h[parent], np.asarray(chosen, np.int32)], p, block=ROW_BLOCK,
            )
            x = a[parent] + cfg["residual_multiplier"] * experts
            position, origin = position[parent], origin[parent]
    return x, origin


def served_gaps(seed: int, cfg: dict, sequences: list, *, control: bool = False) -> list:
    """As ``reference_cohere2moe.served_gaps``: for each ``(prompt_ids,
    served_ids)`` one full forward over prompt + served tokens, at each
    served position the gap by which the served token's logit lies below the
    reference's best, and the widest such gap of the sequence - where a
    position's routing is a near tie, the smallest gap over the routings it
    may have taken (top-10 of 72 with half of them held here: which of two
    near-tied experts wins moves the layer's result by a whole gated expert,
    below what a 16-bit computation resolves).  Every served position is
    scored.  Weights are the seed's, rounded to bfloat16 as they are served.
    With ``control=True`` the gaps are read for the token the float8 forward
    puts first at each of the same positions.  Each sequence's numbers go to
    standard error for the record."""
    cfg = _Frozen(cfg)
    w = weights_from_seed(seed, cfg, jnp.bfloat16)
    # One padded length, so one program a layer kind: compiling a program
    # costs more than running it over the padding (causal, and every other
    # operation is a token's own, so what follows the last token changes
    # nothing before it).
    step = -(-cfg["context_length"] // SEQUENCE_SIZES)

    def on_head(fn, states, *more, quant=None):
        with jax.default_matmul_precision("highest"):
            return _in_blocks(
                functools.partial(fn, cfg=cfg, quant=quant), [states, *more], w, block=HEAD_ROWS
            )[0]

    out = []
    for prompt, served in sequences:
        ids = list(prompt) + list(served)
        tokens = np.asarray(ids + [0] * (-len(ids) % step), np.int32)
        lo, hi = len(prompt) - 1, len(ids) - 1
        memory, t0 = [], time.perf_counter()
        jax.block_until_ready(hidden_states(w, tokens, cfg, memory=memory, keep=(lo, hi)))
        t1 = time.perf_counter()
        if control:
            low = np.asarray(hidden_states(w, tokens, cfg, "fp8")[lo:hi])
            chosen = on_head(_best_jit, low, quant="fp8")
        else:
            chosen = np.asarray(ids[lo + 1:hi + 1], np.int32)
        t2 = time.perf_counter()
        rows, origin = followed_routings(w, cfg, tokens, memory, lo, hi)
        del memory
        row_gaps = on_head(_gaps_jit, rows, chosen[origin])
        t3 = time.perf_counter()
        gaps = np.full(hi - lo, np.inf)
        np.minimum.at(gaps, origin, row_gaps)
        # A position's first row is the reference's own routing.
        own = row_gaps[np.unique(origin, return_index=True)[1]]
        one = np.bincount(origin, minlength=hi - lo) == 1
        print(json.dumps({
            "served_gaps": "control" if control else "sound", "prompt": len(prompt),
            "served": len(served), "rows": len(origin), "one_routing_share": float(one.mean()),
            "widest": float(gaps.max()), "mean": float(gaps.mean()),
            "widest_one_routing": float(gaps[one].max()) if one.any() else 0.0,
            "widest_own_routing": float(own.max()), "mean_own_routing": float(own.mean()),
            "forward_s": round(t1 - t0, 2), "rows_s": round(t3 - t2, 2),
        }), file=sys.stderr)
        out.append(float(gaps.max()))
    return out
