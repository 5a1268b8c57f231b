"""The plain reference of MiMo-V2.5's language model (``model_type``
``mimo_v2``), written from its published ``config.json`` (keys in brackets)
and the catalog's description ("SWA(128) with learnable sink bias; global GQA
- 48L, 5 SWA : 1 global; qk 192 / v 128", "256 experts, top-8, 0 shared").

* Norms: ``RMSNorm(x) = x / sqrt(mean(x^2) + layernorm_epsilon) * g``.
  Embedding ``x_0 = E[token]``; logits ``W_head N_f(x_L)``, the head untied
  [``tie_word_embeddings`` false].
* **The layer**, pre-norm and sequential: ``h = x + Attn_l(N1 x)``, ``y = h +
  FFN_l(N2 h)``.
* **Attention** [``hybrid_layer_pattern[l]``: 0 full, 1 window]:
  ``num_attention_heads`` query heads of ``head_dim`` (192); a full layer has
  ``num_key_value_heads`` (4) K/V heads and rotates at ``rope_theta``, a
  window layer ``swa_num_key_value_heads`` (8) at ``swa_rope_theta`` and sees
  key j from query i iff ``0 <= i - j < sliding_window``; ``q = W_q u``, ``k
  = W_k u`` (kv x 192), ``v = attention_value_scale * W_v u`` (kv x
  ``v_head_dim`` 128), no bias [``attention_bias``]; RoPE rotates the pairs
  ``(2i, 2i + 1)`` of a head's first ``rotary_dim`` values (64 of 192
  [``partial_rotary_factor``]) by ``position * theta^(-2i / rotary_dim)``,
  the other 128 pass; scores ``q . k / sqrt(192)``; query head n reads K/V
  head ``n // (heads / kv)``; output ``W_o concat(heads)`` (64 x 128 ->
  hidden).
* **Sink** [``add_swa_attention_sink_bias`` true,
  ``add_full_attention_sink_bias`` false]: a window layer has one learned
  scalar ``b_h`` a query head that joins the softmax's denominator and
  carries no value, ``p_ij = exp(s_ij - m) / (sum_j' exp(s_ij' - m) + exp(b_h
  - m))`` with ``m`` the maximum over the visible scores and ``b_h`` -
  computed here as a softmax over the scores with ``b_h`` appended as one more
  column, whose weight is dropped.
* **Feed-forward part** [``moe_layer_freq[l]``]: 0 a dense SwiGLU of
  ``intermediate_size`` (layer 0 alone); 1 the expert layer: ``s =
  sigmoid(W_r u)`` over the router's ``n_experts`` outputs (the published
  ``n_routed_experts``, 256); ``T`` = the ``num_experts_per_tok`` largest of
  ``s + b`` (the selection bias [``topk_method`` noaux_tc]; ``n_group =
  topk_group = 1``: no group limit); gates ``g = s[T] / sum(s[T])``
  [``norm_topk_prob``], times 1 [``routed_scaling_factor`` null]; ``sum_{e
  in T} g_e W2_e(silu(W1_e u) * W3_e u)`` at ``moe_intermediate_size``; no
  shared expert [``n_shared_experts`` null].  No capacity.

**This chip's share.**  ``cfg["n_routed_experts"]`` experts are held,
numbered ``expert_offset ..`` of the router's ``n_experts``: the router keeps
all its outputs, its bias and its experts per token, gates are normalised
over all chosen experts, and the routed sum runs over ``T`` *and held* only.
What the absent experts would add is left out and the partial result goes on
to the next layer.  The vocabulary is the slice ``vocab_size`` of the file.

Departures from the published description, all of them: (1) everything
marked *assumed* in the configuration file (the rotated width 64, the pairs
RoPE rotates, the value scale on the value, ``attention_chunk_size`` unused,
the seeded sinks and selection bias, no towers and no multi-token-prediction
layers); (2) at width the weights stay bfloat16-valued on the device and are
cast to float32 a matrix and an expert at a time, attention runs in blocks of
queries, and a window layer's block reads the keys from its first row's
window start to its last row and no others: the same arithmetic in an order
that fits the chip and a 28k-token request; (3) the routed sum runs over the
held experts with a gate of zero where one was not chosen, which is the same
sum; (4) :func:`followed_routings` computes single positions again against
the keys and values the full forward left before them.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, cache or batching; imports nothing of ``bpe_transformer_tpu``, from
``reference_cohere2moe`` the seed's generator, the float8 control's rounding,
the near ties of a routing and the row-block helper, from
``reference_nemotronh`` how joint routings are ranked and kept.
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
    _swiglu,
    routing_choices,
)
from chipbench.reference_nemotronh import (
    MAX_ROUTINGS,
    SCORE_TO_LOGIT,
    _routings_by_lead,
)

SEQUENCE_SIZES = 1  # served sequences are scored at this many padded lengths
ROW_BLOCK = 256     # single rows go through a layer in blocks of this many
DENSE_ROWS = 4096   # ... and a sequence through the dense layer's 16,384 so many
BIAS_SPREAD = 5.0   # the seeded selection bias is this times a 0.02 draw
SINK_SPREAD = 50.0  # the seeded sinks are this times a 0.02 draw (sigma 1)
NORM_LEAVES = 1 << 20   # the norms' leaf numbers start here, past every matrix's
VALUE_LEAVES = 1 << 21  # ... and the sinks' and biases' here


# ------------------------------------------------------------------ weights


def rotary_dim(cfg: dict) -> int:
    """The leading part of a head that rotates: ``partial_rotary_factor x
    head_dim`` (0.334 x 192 = 64.1) cut to an even whole number."""
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2


def group_kind(cfg: dict, windowed: bool) -> tuple:
    """``(K/V heads, theta)`` of the window layers or of the full layers."""
    if windowed:
        return cfg["swa_num_key_value_heads"], cfg["swa_rope_theta"]
    return cfg["num_key_value_heads"], cfg["rope_theta"]


def layer_kind(cfg: dict, layer: int) -> tuple:
    """``(windowed, K/V heads, theta, dense)`` of layer ``layer``."""
    windowed = bool(cfg["hybrid_layer_pattern"][layer])
    return (windowed, *group_kind(cfg, windowed), not cfg["moe_layer_freq"][layer])


def has_sink(cfg: dict, windowed: bool) -> bool:
    return bool(cfg["add_swa_attention_sink_bias" if windowed else "add_full_attention_sink_bias"])


def init_weights(seed: int, cfg: dict, dtype=jnp.float32, draw=_draw) -> dict:
    """The benchmark's weights from ``--seed`` in the program's tree layout:
    truncated normal (+-3 sigma) times 0.02 for every matrix, for every norm
    1 + 5 times such a draw (0.7 .. 1.3), for the router's selection bias 5
    times such a draw (+-0.3 beside scores near 0.5: it decides a good part
    of the choices) and for the sinks 50 times (sigma 1 beside scores near 0:
    a sink takes a share of a window's 128 weights that a fault in it moves).
    Leaves are numbered in tree order by kind and leaf m is drawn from the
    seed ``hash(seed, m)``."""
    d, heads, dh, dv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    held = cfg["n_routed_experts"]
    counters = {"matrix": iter(range(NORM_LEAVES)), "norm": iter(range(NORM_LEAVES, VALUE_LEAVES)),
                "value": iter(range(VALUE_LEAVES, 2 * VALUE_LEAVES))}

    def leaf_seed(kind):
        return jnp.uint32((int(seed) * 1000003 + next(counters[kind]) * 7919 + 12345) % 2**32)

    def dense(*shape):
        return draw(leaf_seed("matrix"), shape, dtype)

    def norm(width):
        return (1.0 + 5.0 * draw(leaf_seed("norm"), (width,), jnp.float32)).astype(dtype)

    def value(spread, *shape):
        return (spread * draw(leaf_seed("value"), shape, jnp.float32)).astype(dtype)

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        windowed, kvh, _, is_dense = layer_kind(cfg, i)
        attn = {
            "q_proj": dense(heads * dh, d), "k_proj": dense(kvh * dh, d),
            "v_proj": dense(kvh * dv, d), "output_proj": dense(d, heads * dv),
        }
        if has_sink(cfg, windowed):
            attn["sink"] = value(SINK_SPREAD, heads)
        if is_dense:
            ff = cfg["intermediate_size"]
            ffn = {"w1": dense(ff, d), "w2": dense(d, ff), "w3": dense(ff, d)}
        else:
            ff = cfg["moe_intermediate_size"]
            ffn = {
                "router": dense(cfg["n_experts"], d),
                "router_bias": value(BIAS_SPREAD, cfg["n_experts"]),
                "w1": dense(held, ff, d), "w2": dense(held, d, ff), "w3": dense(held, ff, d),
            }
        layers.append({"attn": attn, "ln1": norm(d), "ffn": ffn, "ln2": norm(d)})
    return {
        "token_embeddings": dense(cfg["vocab_size"], d), "layers": layers,
        "ln_final": norm(d), "lm_head": dense(cfg["vocab_size"], d),
    }


def weights_from_seed(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    """On the device, one small jitted program a matrix shape."""
    return init_weights(seed, cfg, dtype, draw=_draw_jit)


# ------------------------------------------------------------------ forward


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def _rope(x, theta, positions, rotated: int):
    """Rotate pairs (2i, 2i + 1) of the first ``rotated`` values of the last
    axis by ``position * theta^(-2i / rotated)``; the rest passes.
    ``positions`` broadcasts against ``x.shape[:-1]``."""
    inv = theta ** (-jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    part = x[..., :rotated]
    even, odd = part[..., 0::2], part[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1).reshape(part.shape)
    return jnp.concatenate([turned, x[..., rotated:]], axis=-1)


def _sink_softmax(scores, sink):
    """Softmax over the last axis of ``scores`` with the heads' sink logits
    ``sink`` (broadcast against ``scores[..., :1]``; None: no sink) as one
    more column, whose weight is dropped: it carries no value."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    column = jnp.broadcast_to(sink.astype(jnp.float32), scores.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]


def _project(u, p, cfg: dict, kvh: int, theta, positions, quant):
    """``(q (heads, S, dh), k (kv, S, dh), v (kv, S, dv))``, rotated and the
    values scaled."""
    s = u.shape[0]
    heads, dh, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]

    def split(t, n, width):
        return t.reshape(s, n, width).transpose(1, 0, 2)

    q = split(_matmul(u, p["q_proj"], quant), heads, dh)
    k = split(_matmul(u, p["k_proj"], quant), kvh, dh)
    v = split(_matmul(u, p["v_proj"], quant), kvh, dv) * cfg["attention_value_scale"]
    rotated = rotary_dim(cfg)
    return _rope(q, theta, positions, rotated), _rope(k, theta, positions, rotated), v


def attention(u, p, cfg: dict, windowed: bool, quant):
    """``u`` (S, hidden) -> ``((S, hidden), (k, v))``; ``k`` and ``v`` (kv
    heads, S, width) are what :func:`row_block_attention` reads.  In blocks
    of queries; a window layer's block reads the keys from ``window - 1``
    before its first row to its last row."""
    s = u.shape[0]
    heads, dv = cfg["num_attention_heads"], cfg["v_head_dim"]
    kvh, theta = group_kind(cfg, windowed)
    q, k, v = _project(u, p, cfg, kvh, theta, jnp.arange(s), quant)
    if quant == "fp8":
        q, k, v = _fake_fp8(q), _fake_fp8(k), _fake_fp8(v)
    dh = q.shape[-1]
    qg = q.reshape(kvh, heads // kvh, s, dh)
    sink = p["sink"].reshape(kvh, heads // kvh, 1, 1) if "sink" in p else None
    block = math.gcd(s, QUERY_BLOCK)
    window = cfg["sliding_window"]
    # Keys in reach of a block of queries: all of them, or the window's.
    reach = min(block + window - 1, s) if windowed else s

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=2)
        first = jnp.clip(start + block - reach, 0, s - reach) if windowed else 0
        kb = jax.lax.dynamic_slice_in_dim(k, first, reach, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, first, reach, axis=1)
        scores = jnp.einsum("kgqd,ktd->kgqt", qb, kb) / math.sqrt(dh)
        q_pos = start + jnp.arange(block)[:, None]
        key_pos = first + jnp.arange(reach)[None, :]
        visible = key_pos <= q_pos
        if windowed:
            visible &= q_pos - key_pos < window
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("kgqt,ktd->kgqd", _sink_softmax(scores, sink), vb)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))  # (nb, kv, g, block, dv)
    merged = out.transpose(0, 3, 1, 2, 4).reshape(s, heads * dv)
    return _matmul(merged, p["output_proj"], quant), (k, v)


def selection_scores(u, p):
    """``(s, s + b)``: the router's sigmoid scores in float32, never
    rounded, and what the choice of experts is made by."""
    s = jax.nn.sigmoid(_matmul(u, p["router"], None))
    return s, s + p["router_bias"].astype(jnp.float32)


def moe(u, p, cfg: dict, quant, chosen=None):
    """``u`` (S, hidden) -> this share's routed part.  ``chosen`` (S, experts
    per token) names each token's experts in place of the largest of ``s +
    b``; the gates are the scores of whatever is named, normalised."""
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    scores, biased = selection_scores(u, p)
    if chosen is None:
        _, chosen = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True)  # norm_topk_prob
    if cfg.get("routed_scaling_factor"):
        gates = gates * cfg["routed_scaling_factor"]

    def one_expert(total, xs):
        w1, w2, w3, e = xs
        gate = jnp.sum(jnp.where(chosen == e + offset, gates, 0.0), axis=-1)
        return total + gate[:, None] * _swiglu(u, w1, w2, w3, quant), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u), (p["w1"], p["w2"], p["w3"], jnp.arange(held))
    )
    return routed


def ffn(u, p, cfg: dict, quant, chosen=None):
    """A layer's feed-forward part by its tree: the dense SwiGLU or the
    expert layer."""
    if "router" in p:
        return moe(u, p, cfg, quant, chosen)
    # In blocks of rows: 32k rows of 16,384 float32 values, three times
    # over, would not fit beside the weights.
    block = math.gcd(u.shape[0], DENSE_ROWS)
    out = jax.lax.map(
        lambda rows: _swiglu(rows, p["w1"], p["w2"], p["w3"], quant),
        u.reshape(-1, block, u.shape[-1]),
    )
    return out.reshape(u.shape)


def block(x, p, cfg: dict, windowed: bool, quant):
    """One layer over a whole sequence: ``(y, (keys, values))``."""
    eps = cfg["layernorm_epsilon"]
    attended, memory = attention(_rmsnorm(x, p["ln1"], eps), p["attn"], cfg, windowed, quant)
    h = x + attended
    return h + ffn(_rmsnorm(h, p["ln2"], eps), p["ffn"], cfg, quant), memory


def row_block_attention(x, positions, p, k_seq, v_seq, cfg: dict, windowed: bool):
    """A layer's attention half for single rows: row r is a token at
    ``positions[r]`` of a sequence whose keys and values are ``k_seq``,
    ``v_seq``; it sees those before its position (inside the window) and its
    own.  Returns ``(h = x + Attn, N2 h)``."""
    rows = x.shape[0]
    heads, dv = cfg["num_attention_heads"], cfg["v_head_dim"]
    kvh, theta = group_kind(cfg, windowed)
    eps, attn = cfg["layernorm_epsilon"], p["attn"]
    q, k, v = _project(_rmsnorm(x, p["ln1"], eps), attn, cfg, kvh, theta, positions[None, :], None)
    dh = q.shape[-1]
    q = q.transpose(1, 0, 2).reshape(rows, kvh, heads // kvh, dh)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)           # (rows, kv, width)
    before = jnp.einsum("rkgd,ktd->rkgt", q, k_seq) / math.sqrt(dh)
    key_pos = jnp.arange(k_seq.shape[1])[None, :]
    visible = key_pos < positions[:, None]
    if windowed:
        visible &= positions[:, None] - key_pos < cfg["sliding_window"]
    before = jnp.where(visible[:, None, None, :], before, -jnp.inf)
    own = jnp.einsum("rkgd,rkd->rkg", q, k) / math.sqrt(dh)
    sink = attn["sink"].reshape(1, kvh, heads // kvh, 1) if "sink" in attn else None
    weights = _sink_softmax(jnp.concatenate([before, own[..., None]], axis=-1), sink)
    out = jnp.einsum("rkgt,ktd->rkgd", weights[..., :-1], v_seq) + weights[..., -1:] * v[:, :, None, :]
    h = x + _matmul(out.reshape(rows, heads * dv), attn["output_proj"], None)
    return h, _rmsnorm(h, p["ln2"], eps)


def row_block_scores(u, p):
    return selection_scores(u, p["ffn"])[1]


def row_block_ffn(u, chosen, p, cfg: dict):
    """The feed-forward part with each row's experts given (the dense layer
    takes no notice of them)."""
    return ffn(u, p["ffn"], cfg, None, chosen)


def head(x, w, cfg: dict, quant):
    return _matmul(_rmsnorm(x, w["ln_final"], cfg["layernorm_epsilon"]), w["lm_head"], quant)


_block_jit = jax.jit(block, static_argnames=("cfg", "windowed", "quant"))
_row_attention_jit = jax.jit(row_block_attention, static_argnames=("cfg", "windowed"))
_row_scores_jit = jax.jit(row_block_scores)
_row_ffn_jit = jax.jit(row_block_ffn, static_argnames=("cfg",))
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


def hidden_states(w: dict, row, cfg: dict, quant: str | None = None, memory: list | None = None):
    """``(S,)`` token ids -> ``(S, hidden)`` after the last block, a layer at
    a time: one jitted program a layer kind, not one for the model.
    ``memory`` (a list) collects each layer's ``(keys, values)``."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(w, row)
        for i, p in enumerate(w["layers"]):
            x, kept = _block_jit(x, p, cfg=cfg, windowed=layer_kind(cfg, i)[0], quant=quant)
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


def followed_routings(w: dict, cfg: dict, tokens, memory: list, lo: int, hi: int):
    """As ``reference_nemotronh.followed_routings``: the hidden states after
    the last block of positions ``lo .. hi - 1`` of ``tokens``, once for
    every joint routing a 16-bit computation may have taken *at that
    position* (`_routings_by_lead` on the selection scores, in each expert
    layer on the state that the routing so far produced; after every expert
    layer a position keeps the `MAX_ROUTINGS` rows whose summed leads are
    least, the reference's own first): ``(states (rows, hidden), position
    index of each row)``.  Earlier positions are what the full forward made
    of them (``memory``, from :func:`hidden_states`)."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    near = _Frozen(
        num_experts=cfg["n_routed_experts"], expert_offset=cfg.get("expert_offset", 0),
        num_experts_per_tok=cfg["num_experts_per_tok"],
    )
    top = cfg["num_experts_per_tok"]
    position = np.arange(lo, hi, dtype=np.int32)
    origin = np.arange(hi - lo)
    cost = np.zeros(hi - lo)
    with jax.default_matmul_precision("highest"):
        x = np.asarray(_embed(w, tokens[lo:hi]))
        for i, (p, kept) in enumerate(zip(w["layers"], memory)):
            x, u = _in_blocks(
                functools.partial(_row_attention_jit, cfg=cfg, windowed=layer_kind(cfg, i)[0]),
                [x, position], p, *kept, block=ROW_BLOCK,
            )
            if "router" not in p["ffn"]:
                (out,) = _in_blocks(
                    functools.partial(_row_ffn_jit, cfg=cfg),
                    [u, np.zeros((len(u), top), np.int32)], p, block=ROW_BLOCK,
                )
                x = x + out
                continue
            (biased,) = _in_blocks(_row_scores_jit, [u], p, block=ROW_BLOCK)
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
            (out,) = _in_blocks(
                functools.partial(_row_ffn_jit, cfg=cfg),
                [u[parent], np.asarray(chosen, np.int32)[order]], p, block=ROW_BLOCK,
            )
            x = x[parent] + out
            position, origin = position[parent], origin[parent]
    return x, origin


def served_gaps(seed: int, cfg: dict, sequences: list, *, control: bool = False) -> list:
    """For each ``(prompt_ids, served_ids)`` one full forward over prompt +
    served tokens, at each served position the gap by which the served
    token's logit lies below the reference's best - where a position's
    routing is a near tie, the smallest gap over the joint routings it may
    have taken (top-8 of 256 sigmoid scores under a selection bias: the
    eighth and the ninth lie closer than a 16-bit computation resolves at a
    good part of the positions, and where this share holds either, a whole
    gated expert enters or leaves) - and **the mean of these gaps over the
    sequence's served positions**, every one of them scored, as
    ``reference_nemotronh.served_gaps`` says and for its reason: a position
    routed otherwise than the reference's own forward leaves its K/V behind
    as the context of every later position, which moves the widest single
    gap by what is no rounding, and the mean hardly.  The widest goes to
    standard error with the sequence's other numbers for the record.
    Weights are the seed's, rounded to bfloat16 as they are served.  With
    ``control=True`` the gaps are read for the token the float8 forward puts
    first at each of the same positions."""
    cfg = _Frozen(cfg)
    w = weights_from_seed(seed, cfg, jnp.bfloat16)
    # One padded length, so one program a layer kind: compiling the three
    # kinds of block and the rows' programs for a length costs ~38 s on the
    # v5e, running them over the padding a few seconds (causal, and every
    # other operation is a token's own, so what follows the last token
    # changes nothing before it).
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
        jax.block_until_ready(hidden_states(w, tokens, cfg, memory=memory))
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
