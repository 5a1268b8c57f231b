"""The plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (``model_type``
``nemotron_h``), written from its published ``config.json`` (keys in
brackets) and the catalog's description ("Mamba-2 (64 heads, conv4); GQA
32Q/2KV - 52 blocks MEMEM*... (M=Mamba, E=MoE, *=attn x6)", "128 experts,
top-6, 1 shared; relu^2, routed scaling 2.5").

* Norms: ``RMSNorm(x) = x / sqrt(mean(x^2) + layer_norm_epsilon) * g``, a
  learned weight, no bias.  Embedding ``x_0 = E[token]``; logits ``W_head
  N_f(x_L)``, the head untied [``tie_word_embeddings`` false].
* **The layer** [``num_hidden_layers``; kind by the letter of
  ``hybrid_override_pattern``]: **one** pre-norm sublayer, ``x <- x +
  F_k(N_l(x))`` with ``F_k`` the Mamba-2 mixer (``M``), attention (``*``) or
  the expert layer (``E``) - never a mixer and a feed-forward part both.
* **Mamba-2 mixer** on ``u`` (T, hidden): ``mamba_num_heads`` heads of
  ``mamba_head_dim`` (inner width their product; ``expand`` is unused), state
  ``ssm_state_size``, ``n_groups`` groups of ``B`` and ``C``: ``[z ; xBC ;
  dt] = W_in u`` (no bias [``mamba_proj_bias``]); ``xBC_t = silu(b_c + sum_j
  w_c[:, j] xBC_{t-(k-1)+j})``, a depthwise causal convolution of width k =
  ``conv_kernel`` with bias [``use_conv_bias``], zeros left of the sequence's
  start; ``[x ; B ; C] = xBC`` with ``B``, ``C`` (groups, state); ``dt =
  softplus(dt + dt_bias)`` a head, no clamp; ``A = -exp(A_log)``; for head
  ``h`` of group ``g = h // (heads / groups)`` the **state** ``H_t = exp(dt_t
  A) H_{t-1} + (dt_t x_t) (x) B_t[g]`` (head_dim x state, ``H_{-1} = 0``);
  ``y_t = H_t C_t[g] + D x_t``; ``out = W_out GroupRMSNorm(y * silu(z))``,
  the norm over each group's inner channels apart under one weight.
  **Always the recurrence, step by step** (``lax.scan`` over positions); the
  program's chunked form [``chunk_size``] is the same sum.
* **Attention**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``, no biases
  [``attention_bias``], **no positional encoding** (the family's attention
  rotates nothing, though the config carries ``rope_theta``), ``scores = (q .
  k) head_dim^-1/2``, causal softmax.
* **Expert layer**: ``s = sigmoid(W_r u)`` over the router's ``n_experts``
  outputs (the published ``n_routed_experts``, 128); ``T`` = the
  ``num_experts_per_tok`` largest of ``s + b`` (the selection bias; ``n_group
  = topk_group = 1``: no group limit); gates ``g = routed_scaling_factor *
  s[T] / sum(s[T])`` [``norm_topk_prob``]; ``sum_{e in T} g_e E_e(u) +
  S(u)``, every routed expert ``W_down relu(W_up u)^2`` of width
  ``moe_intermediate_size`` [``mlp_hidden_act`` relu2] - two matrices, no
  gate - and the shared expert the same at
  ``moe_shared_expert_intermediate_size``, added whole.  No capacity.

**This chip's share.**  ``cfg["n_routed_experts"]`` experts are held,
numbered ``expert_offset ..`` of the router's ``n_experts``: the router keeps
all its outputs, its bias and its experts per token, gates are normalised
over all chosen experts, and the routed sum runs over ``T`` *and held* only.
What the absent experts would add is left out and the partial result goes on
to the next layer.  The vocabulary is the slice ``vocab_size`` of the file.

Departures from the published description, all of them: (1) everything
marked *assumed* in the configuration file (no rotation, no clamp on ``dt``,
gate before the grouped norm, the float32 state, ``expand`` unused, the
seeded values of ``A_log``, ``dt_bias``, ``D``, the convolution and the
selection bias); (2) at width the weights stay bfloat16-valued on the device
and are cast to float32 a matrix and an expert at a time, and attention runs
in blocks of queries: the same arithmetic in an order that fits the chip;
(3) the routed sum runs over the held experts with a gate of zero where one
was not chosen, which is the same sum; (4) :func:`followed_routings`
computes single positions again from the state the full forward left before
them - the same recurrence, restarted at a stored state.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, cache or batching; imports nothing of ``bpe_transformer_tpu``, from
``reference_cohere2moe`` the seed's generator, the float8 control's rounding,
the near ties of a routing and the row-block helper, and from
``reference_granitehybrid`` what of a state-space layer knows no groups (the
convolution, the kept stretch).  ``quant="fp8"`` is the control of
``correct`` (see ``reference.py``).
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
    routing_choices,
)
from chipbench.reference_granitehybrid import (
    NORM_LEAVES,
    ROW_BLOCK,
    SEQUENCE_SIZES,
    STATE_EVERY,
    VALUE_LEAVES,
    _f32,
    _keep_jit,
    _rmsnorm,
    _uniform,
    behind,
    mamba_conv,
)

#: A selection score moves a quarter as far as its logit at most (the
#: sigmoid's steepest slope): times this, ``routing_choices``' margin on
#: logits holds for them.
SCORE_TO_LOGIT = 4.0
BIAS_SPREAD = 5.0  # the seeded selection bias is this times a 0.02 draw
#: Joint routings followed for one served position.  Six of 128 scores packed
#: into ~1.2 leave the sixth and the seventh 0.010 apart at the median, and a
#: bfloat16 stream moves a score by 0.001 (first expert layer) to 0.0045
#: (fifth), rarely by 0.02: the program routes as the reference's own forward
#: does in 98%, 95%, 85%, 77% and 73% of the positions of the five expert
#: layers, and against that forward alone the widest gap reads 1.0-1.8 (the
#: 13 layers at the published widths on the CPU, counts; my chip runs, PR
#: 42).  Kept after each expert layer are the rows whose summed leads are
#: least.  With 16 of them the widest gap over 1,151 positions read 0.327,
#: with 32 the same 0.327 - and 0.327 with the reference FORCED along the
#: program's own routing at every layer: what is left is no routing but the
#: served context (the states and K/V of earlier positions, a third of which
#: were routed otherwise in some layer) and bfloat16 itself.  PERF.md,
#: section 6, PR 42.
MAX_ROUTINGS = 16
PAIRED = 3  # of a row's nearest single exchanges, those also made two at a time
KINDS = {"M": "ssm", "*": "attn", "E": "ffn"}


# ------------------------------------------------------------------ weights


def widths(cfg: dict) -> dict:
    heads, groups = cfg["mamba_num_heads"], cfg["n_groups"]
    inner = heads * cfg["mamba_head_dim"]
    assert heads % groups == 0 and cfg["n_group"] == cfg["topk_group"] == 1
    return {
        "inner": inner, "bc": groups * cfg["ssm_state_size"],
        "channels": inner + 2 * groups * cfg["ssm_state_size"],
        "per_group": heads // groups,
    }


def layer_kinds(cfg: dict) -> list:
    """``"ssm"``, ``"attn"`` or ``"ffn"`` for each layer that is kept."""
    return [KINDS[k] for k in cfg["hybrid_override_pattern"][: cfg["num_hidden_layers"]]]


def init_weights(seed: int, cfg: dict, dtype=jnp.float32, draw=_draw) -> dict:
    """The benchmark's weights from ``--seed`` in the program's tree layout
    (a layer holds its one sublayer and its one norm): truncated normal (+-3
    sigma) times 0.02 for every matrix and the convolution's bias, for every
    norm 1 + 5 times such a draw (0.7 .. 1.3: a norm weight dropped or
    applied twice moves the logits), for the router's selection bias 5 times
    such a draw (+-0.3 beside scores in 0.2 .. 0.8: it decides a good part
    of the choices), and for a state-space layer the family's initialisation
    where a normal draw would be degenerate: ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of a log-uniform in (``time_step_min``,
    ``time_step_max``), ``D = 1``, convolution weights ``U(-1/2, 1/2)``.
    Leaves are numbered in tree order by kind and leaf m is drawn from the
    seed ``hash(seed, m)``."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    sff = cfg["moe_shared_expert_intermediate_size"]
    w = widths(cfg)
    heads, k = cfg["mamba_num_heads"], cfg["conv_kernel"]
    d_q = cfg["num_attention_heads"] * cfg["head_dim"]
    d_kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    held = cfg["n_routed_experts"]
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
        lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
        dt = jnp.exp(lo + uniform(heads) * (hi - lo))
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

    def experts():
        bias = BIAS_SPREAD * draw(leaf_seed("value"), (cfg["n_experts"],), jnp.float32)
        return {
            "router": dense(cfg["n_experts"], d), "router_bias": bias.astype(dtype),
            "w1": dense(held, ff, d), "w2": dense(held, d, ff),
            "shared": {"w1": dense(1, sff, d), "w2": dense(1, d, sff)},
        }

    embedding = dense(cfg["vocab_size"], d)
    layers = []
    for kind in layer_kinds(cfg):
        if kind == "ffn":
            layers.append({"ln2": norm(d), "ffn": experts()})
        else:
            layers.append({kind: mamba() if kind == "ssm" else attention(), "ln1": norm(d)})
    return {
        "token_embeddings": embedding, "layers": layers, "ln_final": norm(d),
        "lm_head": dense(cfg["vocab_size"], d),
    }


def weights_from_seed(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    """On the device, one small jitted program a matrix shape."""
    return init_weights(seed, cfg, dtype, draw=_draw_jit)


# ------------------------------------------------------------------ forward


def _by_head(v, cfg: dict):
    """``B`` or ``C`` (..., groups, state) -> (..., heads, state): each
    head its group's."""
    return jnp.repeat(v, widths(cfg)["per_group"], axis=-2)


def mamba_project(u, p, cfg: dict, quant):
    """``u`` (rows, hidden) -> ``(z, xBC before the convolution, dt)``."""
    w = widths(cfg)
    zxbcdt = _matmul(u, p["in_proj"], quant)
    dt = jax.nn.softplus(zxbcdt[:, w["inner"] + w["channels"]:] + p["dt_bias"].astype(jnp.float32))
    return zxbcdt[:, : w["inner"]], zxbcdt[:, w["inner"]: w["inner"] + w["channels"]], dt


def split_xbc(act, cfg: dict, quant):
    """``(x (rows, heads, head_dim), B, C (rows, groups, state))`` of
    activated xBC rows."""
    w, groups = widths(cfg), cfg["n_groups"]
    x = act[:, : w["inner"]].reshape(-1, cfg["mamba_num_heads"], cfg["mamba_head_dim"])
    b = act[:, w["inner"]: w["inner"] + w["bc"]].reshape(-1, groups, cfg["ssm_state_size"])
    c = act[:, w["inner"] + w["bc"]:].reshape(-1, groups, cfg["ssm_state_size"])
    if quant == "fp8":
        x, b, c = _fake_fp8(x), _fake_fp8(b), _fake_fp8(c)
    return x, b, c


def state_step(h, x_t, b_t, dt_t, a, cfg: dict):
    """``H_t`` from ``H_{t-1}``: one position of the recurrence, ``b_t``
    (groups, state)."""
    return (
        jnp.exp(dt_t * a)[:, None, None] * h
        + (dt_t[:, None] * x_t)[:, :, None] * _by_head(b_t, cfg)[:, None, :]
    )


def mamba_out(y, z, p, cfg: dict, quant):
    """``y`` (rows, heads, head_dim) gated by ``z``, normalised a group's
    channels at a time, projected."""
    rows, groups = y.shape[0], cfg["n_groups"]
    gated = (y.reshape(rows, -1) * jax.nn.silu(z)).reshape(rows, groups, -1)
    normed = _rmsnorm(gated, p["norm"].reshape(groups, -1), cfg["layer_norm_epsilon"])
    return _matmul(normed.reshape(rows, -1), p["out_proj"], quant)


def mamba(u, p, cfg: dict, quant):
    """The mixer over a whole sequence from its start, the recurrence step
    by step: ``((S, hidden), what :func:`row_block_mamba` needs of it)`` -
    the state before every :data:`STATE_EVERY`-th position, xBC before
    (``behind``) and after the convolution and ``dt`` of every position."""
    s, k = u.shape[0], cfg["conv_kernel"]
    z, pre, dt = mamba_project(u, p, cfg, quant)
    padded = behind(pre, k)
    act = mamba_conv(pre, jnp.stack([padded[j: j + s] for j in range(k - 1)], axis=1), p)
    x, b, c = split_xbc(act, cfg, quant)
    (a_log, d_skip) = _f32(p, "A_log", "D")
    a = -jnp.exp(a_log)
    every = math.gcd(s, STATE_EVERY)

    def position(h, xs):
        x_t, b_t, c_t, dt_t = xs
        h = state_step(h, x_t, b_t, dt_t, a, cfg)
        return h, jnp.einsum("hpn,hn->hp", h, _by_head(c_t, cfg)) + d_skip[:, None] * x_t

    def stretches(h, xs):
        h_end, y = jax.lax.scan(position, h, xs)
        return h_end, (y, h)  # the state BEFORE the stretch is what is kept

    shaped = tuple(v.reshape(s // every, every, *v.shape[1:]) for v in (x, b, c, dt))
    start = jnp.zeros(
        (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]), jnp.float32
    )
    _, (y, states) = jax.lax.scan(stretches, start, shaped)
    y = y.reshape(s, cfg["mamba_num_heads"], cfg["mamba_head_dim"])
    return mamba_out(y, z, p, cfg, quant), (states, padded, act, dt)


def attention(h, p, cfg: dict, quant):
    """``h`` (S, hidden) -> ``((S, hidden), (k, v))``: plain causal GQA with
    no positional transform, scores times ``head_dim ** -0.5``; ``k`` and
    ``v`` (kv heads, S, head_dim) are what :func:`row_block_attention`
    reads."""
    s = h.shape[0]
    heads, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]

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
        scores = jnp.einsum("kgqd,ktd->kgqt", qb, k) * dh ** -0.5
        scores = jnp.where(key_pos <= start + jnp.arange(block)[:, None], scores, -jnp.inf)
        return jnp.einsum("kgqt,ktd->kgqd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))  # (nb, kv, g, block, dh)
    merged = out.transpose(0, 3, 1, 2, 4).reshape(s, heads * dh)
    return _matmul(merged, p["output_proj"], quant), (k, v)


def _relu2(h, w1, w2, quant):
    return _matmul(jnp.square(jax.nn.relu(_matmul(h, w1, quant))), w2, quant)


def selection_scores(h, p):
    """``(s, s + b)``: the router's sigmoid scores in float32, never
    rounded, and what the choice of experts is made by."""
    s = jax.nn.sigmoid(_matmul(h, p["router"], None))
    return s, s + p["router_bias"].astype(jnp.float32)


def moe(h, p, cfg: dict, quant, chosen=None):
    """``h`` (S, hidden) -> this share's routed part plus the shared expert
    whole.  ``chosen`` (S, experts per token) names each token's experts in
    place of the largest of ``s + b``; the gates are the scores of whatever
    is named, normalised and scaled."""
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    scores, biased = selection_scores(h, p)
    if chosen is None:
        _, chosen = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = cfg["routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)

    def one_expert(total, xs):
        w1, w2, e = xs
        gate = jnp.sum(jnp.where(chosen == e + offset, gates, 0.0), axis=-1)
        return total + gate[:, None] * _relu2(h, w1, w2, quant), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h), (p["w1"], p["w2"], jnp.arange(held))
    )
    sh = p["shared"]
    return routed + _relu2(h, sh["w1"][0], sh["w2"][0], quant)


def block(x, p, cfg: dict, quant):
    """One layer - one sublayer - over a whole sequence: ``(y, the mixer's
    memory)``, None of a layer without a mixer."""
    eps = cfg["layer_norm_epsilon"]
    if "ffn" in p:
        return x + moe(_rmsnorm(x, p["ln2"], eps), p["ffn"], cfg, quant), None
    u = _rmsnorm(x, p["ln1"], eps)
    mixed, memory = mamba(u, p["ssm"], cfg, quant) if "ssm" in p else attention(u, p["attn"], cfg, quant)
    return x + mixed, memory


def row_block_attention(x, positions, p, k_seq, v_seq, cfg: dict):
    """An attention layer for single rows: row r is a token at
    ``positions[r]`` of a sequence whose keys and values are ``k_seq``,
    ``v_seq``; it sees those before its position and its own."""
    rows = x.shape[0]
    heads, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    u, attn = _rmsnorm(x, p["ln1"], cfg["layer_norm_epsilon"]), p["attn"]
    q = _matmul(u, attn["q_proj"], None).reshape(rows, kvh, heads // kvh, dh)
    k = _matmul(u, attn["k_proj"], None).reshape(rows, kvh, dh)
    v = _matmul(u, attn["v_proj"], None).reshape(rows, kvh, dh)
    before = jnp.einsum("rkgd,ktd->rkgt", q, k_seq) * dh ** -0.5
    visible = jnp.arange(k_seq.shape[1])[None, :] < positions[:, None]
    before = jnp.where(visible[:, None, None, :], before, -jnp.inf)
    own = jnp.einsum("rkgd,rkd->rkg", q, k) * dh ** -0.5
    weights = jax.nn.softmax(jnp.concatenate([before, own[..., None]], axis=-1), axis=-1)
    out = jnp.einsum("rkgt,ktd->rkgd", weights[..., :-1], v_seq) + weights[..., -1:] * v[:, :, None, :]
    return x + _matmul(out.reshape(rows, heads * dh), attn["output_proj"], None)


def row_block_mamba(x, positions, at_step, p, kept, base, cfg: dict, every: int):
    """A state-space layer for single rows.  ``kept`` is what the full
    forward left of a stretch of the sequence (``keep_stretch``: a state
    every ``every`` positions, xBC before and after the convolution and
    ``dt`` of every position) and ``positions`` the rows' own, counted from
    the stretch's start.  The recurrence runs from the kept state before
    position ``base`` over the full forward's own ``(x, B, dt)``, and before
    it takes position ``base + i`` the rows ``at_step[i]`` (row numbers,
    ``rows`` where there is none) are computed from the state before them
    and their own input."""
    rows, span, k = x.shape[0], at_step.shape[0], cfg["conv_kernel"]
    ssm = p["ssm"]
    state = jax.lax.dynamic_index_in_dim(kept["states"], base // every, keepdims=False)
    x_s, b_s, _ = split_xbc(jax.lax.dynamic_slice_in_dim(kept["act"], base, span), cfg, None)
    steps = (x_s, b_s, jax.lax.dynamic_slice_in_dim(kept["dt"], base, span))
    history = kept["behind"][positions[:, None] + jnp.arange(k - 1)]
    u = _rmsnorm(x, p["ln1"], cfg["layer_norm_epsilon"])
    z, pre, dt = mamba_project(u, ssm, cfg, None)
    x_r, b_r, c_r = split_xbc(mamba_conv(pre, history, ssm), cfg, None)
    (a_log, d_skip) = _f32(ssm, "A_log", "D")
    a = -jnp.exp(a_log)
    c_pad = jnp.concatenate([c_r, jnp.zeros((1, *c_r.shape[1:]), jnp.float32)])

    def position(h, xs):
        picked, x_t, b_t, dt_t = xs
        seen = jnp.einsum("hpn,khn->khp", h, _by_head(c_pad[picked], cfg))  # C_r . H_{t-1}
        return state_step(h, x_t, b_t, dt_t, a, cfg), seen

    _, seen = jax.lax.scan(position, state, (at_step, *steps))  # (steps, per step, heads, head_dim)
    # Row r is entry (i, j) of at_step: invert the table.
    flat = at_step.reshape(-1)
    where = jnp.zeros((rows + 1,), jnp.int32).at[flat].set(jnp.arange(flat.shape[0], dtype=jnp.int32))
    before = seen.reshape(flat.shape[0], *seen.shape[2:])[where[:rows]]
    own = _by_head(jnp.sum(b_r * c_r, axis=-1)[..., None], cfg)         # (rows, heads, 1)
    y = (
        jnp.exp(dt * a)[:, :, None] * before
        + (dt[:, :, None] * x_r) * own
        + d_skip[:, None] * x_r
    )
    return x + mamba_out(y, z, ssm, cfg, None)


def row_block_scores(x, p, cfg: dict):
    """``(u, s + b)`` of rows entering an expert layer."""
    u = _rmsnorm(x, p["ln2"], cfg["layer_norm_epsilon"])
    return u, selection_scores(u, p["ffn"])[1]


def row_block_experts(u, chosen, p, cfg: dict):
    """The expert layer's branch with each row's experts given."""
    return moe(u, p["ffn"], cfg, None, chosen)


def head(x, w, cfg: dict, quant):
    return _matmul(_rmsnorm(x, w["ln_final"], cfg["layer_norm_epsilon"]), w["lm_head"], quant)


_block_jit = jax.jit(block, static_argnames=("cfg", "quant"))
_row_attention_jit = jax.jit(row_block_attention, static_argnames=("cfg",))
_row_mamba_jit = jax.jit(row_block_mamba, static_argnames=("cfg", "every"))
_row_scores_jit = jax.jit(row_block_scores, static_argnames=("cfg",))
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


def _embed(w, ids):
    return w["token_embeddings"][jnp.asarray(ids)].astype(jnp.float32)


def hidden_states(
    w: dict, row, cfg: dict, quant: str | None = None, memory: list | None = None,
    keep: tuple = (0, None),
):
    """``(S,)`` token ids -> ``(S, hidden)`` after the last block, a layer at
    a time: one jitted program a layer kind, not one for the model.
    ``memory`` (a list) collects what each layer's mixer left for
    :func:`followed_routings` to compute positions ``keep[0] .. keep[1] - 1``
    again: an attention layer's keys and values, a state-space layer's
    stretch (``keep_stretch``) from the last kept state at or before
    ``keep[0]`` (``first``), None of an expert layer."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    lo, hi = keep[0], len(row) if keep[1] is None else keep[1]
    every = math.gcd(len(row), STATE_EVERY)
    first = lo // every * every
    with jax.default_matmul_precision("highest"):
        x = _embed(w, row)
        for p in w["layers"]:
            x, kept = _block_jit(x, p, cfg=cfg, quant=quant)
            if memory is not None and "ssm" in p:
                # A whole number of row blocks and one block's span past it.
                n = -(-(hi - first) // ROW_BLOCK) * ROW_BLOCK + every + ROW_BLOCK
                kept = {"first": first, "every": every, "stretch": _keep_jit(
                    *kept, np.int32(first), n=n, every=every, k=cfg["conv_kernel"]
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
        outs.append(np.asarray(got)[: ROW_BLOCK - pad])
    return np.concatenate(outs)


def _routings_by_lead(sets: list, scores) -> list:
    """A row's routings with what each costs: ``[(experts, lead)]``, its own
    first at 0, then ``routing_choices``' single exchanges - the lead is by
    how much the expert given up led the one taken in its place - and, of
    the :data:`PAIRED` nearest of them, every two that touch four different
    experts made together, at the sum of their leads (two near ties in one
    layer flip independently)."""
    own = sets[0]
    singles = []
    for other in sets[1:]:
        (out,), (taken,) = np.setdiff1d(own, other), np.setdiff1d(other, own)
        singles.append((scores[out] - scores[taken], out, taken, other))
    singles.sort(key=lambda single: single[0])
    routings = [(own, 0.0)] + [(other, lead) for lead, _, _, other in singles]
    for i, (lead_a, out_a, in_a, _) in enumerate(singles[:PAIRED]):
        for lead_b, out_b, in_b, _ in singles[i + 1: PAIRED]:
            if out_a != out_b and in_a != in_b:
                both = np.concatenate([np.setdiff1d(own, [out_a, out_b]), [in_a, in_b]])
                routings.append((both, lead_a + lead_b))
    return routings


def followed_routings(w: dict, cfg: dict, tokens, memory: list, lo: int, hi: int):
    """As ``reference_cohere2moe.followed_routings``: the hidden states after
    the last block of positions ``lo .. hi - 1`` of ``tokens``, once for
    every routing a 16-bit computation may have taken *at that position*
    (:func:`_routings_by_lead` on the selection scores, in each expert layer
    on the state that the routing so far produced): ``(states (rows,
    hidden), position index of each row)``.  Earlier positions are what the
    full forward made of them (``memory``, from :func:`hidden_states`): an
    attention layer's keys and values, a state-space layer's state before
    the position.  After every expert layer a position keeps the
    :data:`MAX_ROUTINGS` rows whose leads, summed over the layers so far,
    are least - the most likely joint routings, the reference's own (0)
    first."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    near = _Frozen(
        num_experts=cfg["n_routed_experts"], expert_offset=cfg.get("expert_offset", 0),
        num_experts_per_tok=cfg["num_experts_per_tok"],
    )
    position = np.arange(lo, hi, dtype=np.int32)
    origin = np.arange(hi - lo)
    cost = np.zeros(hi - lo)
    with jax.default_matmul_precision("highest"):
        x = np.asarray(_embed(w, tokens[lo:hi]))
        for p, kept in zip(w["layers"], memory):
            if "ssm" in p:
                x = _mamba_rows(x, position, p, kept, cfg)
                continue
            if "attn" in p:
                (x,) = _in_blocks(
                    functools.partial(_row_attention_jit, cfg=cfg), [x, position], p, *kept,
                    block=ROW_BLOCK,
                )
                continue
            u, biased = _in_blocks(
                functools.partial(_row_scores_jit, cfg=cfg), [x], p, block=ROW_BLOCK
            )
            parent, chosen, summed = [], [], []
            for row, sets in enumerate(routing_choices(SCORE_TO_LOGIT * biased, near)):
                for experts, lead in _routings_by_lead(sets, biased[row]):
                    parent.append(row)
                    chosen.append(experts)
                    summed.append(cost[row] + lead)
            parent, summed = np.asarray(parent), np.asarray(summed)
            # By position, then by summed lead: the first MAX_ROUTINGS of each.
            order = np.lexsort((summed, origin[parent]))
            first = np.searchsorted(origin[parent][order], origin[parent][order])
            order = order[np.arange(len(order)) - first < MAX_ROUTINGS]
            parent, cost = parent[order], summed[order]
            (experts,) = _in_blocks(
                functools.partial(_row_experts_jit, cfg=cfg),
                [u[parent], np.asarray(chosen, np.int32)[order]], p, block=ROW_BLOCK,
            )
            x = x[parent] + experts
            position, origin = position[parent], origin[parent]
    return x, origin


def served_gaps(seed: int, cfg: dict, sequences: list, *, control: bool = False) -> list:
    """For each ``(prompt_ids, served_ids)`` one full forward over prompt +
    served tokens, at each served position the gap by which the served
    token's logit lies below the reference's best - where a position's
    routing is a near tie, the smallest gap over the joint routings it may
    have taken (top-6 of 128 with half of them held here: which of two
    near-tied experts wins moves the layer's result by a whole gated expert,
    below what a 16-bit computation resolves) - and **the mean of these gaps
    over the sequence's served positions**, every one of them scored.  The
    mean and not the widest (``reference_cohere2moe.served_gaps``): a
    position's gap is 0 at 97-98% of the positions of a sound run, but a
    bfloat16 stream routes about a third of the positions otherwise than the
    reference's own forward in some layer, the states and K/V they leave
    behind are the context of every later position, and what that does to
    the widest single gap is no rounding - 0.08-1.13 a sequence over 72
    sequences, against 1.04-1.63 for the float8 control (my chip runs, PR
    42): they overlap.  The means stand 9 times apart (0.0004-0.0089
    against 0.079-0.111).  The widest goes to standard error with the
    sequence's other numbers for the record.  Weights are the seed's,
    rounded to bfloat16 as they are served.  With ``control=True`` the gaps
    are read for the token the float8 forward puts first at each of the same
    positions."""
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
        out.append(float(gaps.mean()))
    return out
