"""The plain reference of the Cohere2-MoE language model
(``command-a-plus-05-2026``, ``model_type: cohere2_moe``), written from its
published ``config.json`` (keys in brackets) and the catalog's description.

Per layer l (0-based), on the hidden state x of width ``hidden_size``:

* ``h = LN(x) = (x - mean) / sqrt(var + layer_norm_eps) * g``, no bias.  One
  norm a block [``use_parallel_block``]: ``x' = x + Attn(h) + MoE(h)``.
* Attention: ``num_attention_heads`` query heads and ``num_key_value_heads``
  KV heads of width ``head_dim``, no biases [``attention_bias``], no QK norm
  [``use_qk_norm``]; query head n reads KV head ``n // (heads / kv_heads)``;
  scores / sqrt(head_dim).  ``layer_types[l] == "sliding_attention"``
  (``(l + 1) % 4 != 0``): interleaved-pair RoPE [``rope_gptj``] on all of
  ``head_dim`` [``rotary_pct`` 1], theta ``rope_theta``; key j visible to
  query i iff ``0 <= i - j < sliding_window``.  ``"full_attention"``: no
  positional transform at all, causal mask.
* MoE [``first_k_dense_replace`` 0: every layer]: ``s = sigmoid(W_r h)``
  [``expert_selection_fn``] over the router's ``n_experts`` outputs (the
  published ``num_experts``, 128); ``T`` = the ``num_experts_per_tok``
  largest; ``g_e = s_e / sum_{t in T} s_t`` [``norm_topk_prob``];
  ``routed = sum_{e in T} g_e * W2_e(silu(W1_e h) * W3_e h)``, every expert
  a SwiGLU of width ``intermediate_size`` [``hidden_act``,
  ``use_gated_activation``]; ``shared = (1 / num_shared_experts) * sum_j
  S_j(h)`` [``shared_expert_combination_strategy`` average], each ``S_j``
  the same SwiGLU; ``MoE(h) = routed + shared``.  No capacity: nothing is
  dropped.
* Logits: ``LN_f(x_L) @ E^T * logit_scale``, ``E`` the tied embedding
  [``tie_word_embeddings``].

**This chip's share.**  ``cfg["num_experts"]`` experts are held, numbered
``expert_offset ..`` of the router's ``n_experts``: the router keeps all its
outputs and its experts per token, gates are normalised over all chosen
experts, and ``routed`` sums over ``T`` *and held* only.  What the absent
experts would add is left out and the partial result goes on to the next
layer.  The vocabulary is the slice ``vocab_size`` of the file.

Departures from the published description, all of them: (1) the vision
tower is out (the catalog gives no config for it); (2) the reading of
``intermediate_size`` as one expert's width and of "average" as the mean of
the shared experts' outputs are the configuration file's ``assumed``;
(3) at width the weights stay bfloat16-valued on the device and are cast to
float32 a layer and an expert at a time, and attention runs in blocks of
queries: the same arithmetic, in an order that fits the chip; (4) the routed
sum runs over the held experts with a gate of zero where an expert was not
chosen, which is the same sum.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, cache or batching; imports nothing of ``bpe_transformer_tpu``.
``quant="fp8"`` is the control of ``correct`` (see ``reference.py``).
"""

from __future__ import annotations

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
FP8_MAX = 448.0
SEQUENCE_SIZES = 4  # served sequences are scored at this many padded lengths
QUERY_BLOCK = 128
HEAD_ROWS = 512  # ... and the head in blocks of this many rows
#: Two router logits closer than this may change places in a 16-bit
#: computation (see :func:`served_gaps`).  Router logits have a spread of 1.3
#: here; bfloat16 activations move one by ~0.01.
ROUTER_MARGIN = 0.1
NEAR = 3  # experts on each side of the top-k's edge tried against each other
MAX_ROUTINGS = 32  # routings followed for one served position, at most


# ------------------------------------------------------------------ weights


def _mix(x):
    """murmur3's 32-bit finalizer: every input bit reaches every output bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _draw(seed, shape, dtype):
    """A matrix of truncated normals (+-3 sigma) times 0.02 from a uint32
    ``seed``: element i is a hash of (seed, i) - a counter-based generator
    of a dozen integer operations an element, the same on every backend and
    in every call.  (jax.random's threefry takes 55 s for this
    configuration's 4.7 B values on the v5e, this 1-16 s: my chip runs,
    PR 28.)"""
    n = math.prod(shape)
    index = jax.lax.iota(jnp.uint32, n).reshape(shape)
    bits = _mix(_mix(index + seed * jnp.uint32(0x9E3779B9)) ^ seed)
    u = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24)) + 0.5 / (1 << 24)
    edge = math.erf(3.0 / math.sqrt(2.0))
    z = math.sqrt(2.0) * jax.lax.erf_inv((2.0 * u - 1.0) * edge)
    return (jnp.clip(z, -3.0, 3.0) * INIT_STD).astype(dtype)


_draw_jit = jax.jit(_draw, static_argnums=(1, 2))


def init_weights(seed: int, cfg: dict, dtype=jnp.float32, draw=_draw) -> dict:
    """The benchmark's weights from ``--seed`` in the program's tree layout:
    truncated normal (+-3 sigma) times 0.02 for every matrix, ones for every
    norm.  Matrix number m of the tree (the tied embedding is 0; block i has
    1 + 11 i ..: q, k, v, o, router, w1, w2, w3 of the held experts, w1, w2,
    w3 of the shared experts) is drawn from the seed ``hash(seed, m)``."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    dh = cfg["head_dim"]
    d_q, d_kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    held, shared = cfg["num_experts"], cfg["num_shared_experts"]
    count = iter(range(1 + 11 * cfg["num_hidden_layers"]))

    def dense(*shape):
        leaf_seed = (int(seed) * 1000003 + next(count) * 7919 + 12345) % 2**32
        return draw(jnp.uint32(leaf_seed), shape, dtype)

    embedding = dense(cfg["vocab_size"], d)
    layers = []
    for _ in range(cfg["num_hidden_layers"]):
        layers.append({
            "attn": {
                "q_proj": dense(d_q, d),
                "k_proj": dense(d_kv, d),
                "v_proj": dense(d_kv, d),
                "output_proj": dense(d, d_q),
            },
            "ln1": jnp.ones((d,), dtype),
            "ffn": {
                "router": dense(cfg["n_experts"], d),
                "w1": dense(held, ff, d),
                "w2": dense(held, d, ff),
                "w3": dense(held, ff, d),
                "shared": {
                    "w1": dense(shared, ff, d),
                    "w2": dense(shared, d, ff),
                    "w3": dense(shared, ff, d),
                },
            },
        })
    return {
        "token_embeddings": embedding,
        "layers": layers,
        "ln_final": jnp.ones((d,), dtype),
    }


def weights_from_seed(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    """On the device, one small jitted program a matrix shape: a single
    program for the whole tree would hold its float32 draws all at once
    (4.7 B parameters at width)."""
    return init_weights(seed, cfg, dtype, draw=_draw_jit)


# ------------------------------------------------------------------ forward


def _fake_fp8(x):
    """Round to float8-e4m3 under a per-row scale."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(x, w, quant):
    """``x @ w.T`` for a ``(d_out, d_in)`` weight, in float32."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum("...i,oi->...o", x, w)


def _layernorm(x, g, eps):
    centered = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    return centered * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, theta, positions):
    """Rotate pairs (2i, 2i+1) of the last axis (``head_dim``) by position *
    theta^(-2i/head_dim); ``positions`` broadcasts against ``x.shape[:-1]``."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1).reshape(x.shape)


def _swiglu(h, w1, w2, w3, quant):
    return _matmul(jax.nn.silu(_matmul(h, w1, quant)) * _matmul(h, w3, quant), w2, quant)


def attention(h, p, cfg: dict, window, rotate, quant):
    """``h`` (S, hidden) -> ``((S, hidden), k, v)``.  ``window`` and
    ``rotate`` (:func:`layer_kind`) are values, not branches, so that one
    compiled program serves both kinds of layer: key j is visible to query
    i iff ``0 <= i - j < window`` (a full layer's is larger than any
    sequence), and q and k are rotated by position iff ``rotate``.  ``k``
    and ``v`` (kv heads, rows, head_dim) are the layer's keys as they were
    attended to and its values, padded with rows nothing sees to
    ``context_length`` (:func:`row_attention` reads them)."""
    s = h.shape[0]
    heads, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]

    def split(t, n):
        return t.reshape(s, n, dh).transpose(1, 0, 2)

    q = split(_matmul(h, p["q_proj"], quant), heads)
    k = split(_matmul(h, p["k_proj"], quant), kvh)
    v = split(_matmul(h, p["v_proj"], quant), kvh)
    q, k = (jnp.where(rotate, _rope(t, cfg["rope_theta"], jnp.arange(s)), t) for t in (q, k))
    if quant == "fp8":
        q, k, v = _fake_fp8(q), _fake_fp8(k), _fake_fp8(v)
    qg = q.reshape(kvh, heads // kvh, s, dh)
    block = math.gcd(s, QUERY_BLOCK)
    key_pos = jnp.arange(s)[None, :]

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, axis=2)
        scores = jnp.einsum("kgqd,ktd->kgqt", qb, k) / math.sqrt(dh)
        q_pos = start + jnp.arange(block)[:, None]
        visible = (key_pos <= q_pos) & (q_pos - key_pos < window)
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("kgqt,ktd->kgqd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one_block, jnp.arange(0, s, block))  # (nb, kv, g, block, dh)
    merged = out.transpose(0, 3, 1, 2, 4).reshape(s, heads * dh)
    unseen = ((0, 0), (0, max(cfg["context_length"] - s, 0)), (0, 0))
    return _matmul(merged, p["output_proj"], quant), jnp.pad(k, unseen), jnp.pad(v, unseen)


def row_attention(h, positions, p, k_seq, v_seq, window, rotate, cfg: dict):
    """:func:`attention` for single rows: row r is a token at
    ``positions[r]`` of a sequence whose earlier keys and values are
    ``k_seq``, ``v_seq`` (what :func:`attention` returned); it sees those
    before its position and its own."""
    rows = h.shape[0]
    heads, kvh, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = _matmul(h, p["q_proj"], None).reshape(rows, heads, dh)
    k = _matmul(h, p["k_proj"], None).reshape(rows, kvh, dh)
    v = _matmul(h, p["v_proj"], None).reshape(rows, kvh, dh)
    q, k = (jnp.where(rotate, _rope(t, cfg["rope_theta"], positions[:, None]), t) for t in (q, k))
    qg = q.reshape(rows, kvh, heads // kvh, dh)
    key_pos = jnp.arange(k_seq.shape[1])[None, :]
    visible = (key_pos < positions[:, None]) & (positions[:, None] - key_pos < window)
    before = jnp.einsum("rkgd,ktd->rkgt", qg, k_seq) / math.sqrt(dh)
    before = jnp.where(visible[:, None, None, :], before, -jnp.inf)
    own = jnp.einsum("rkgd,rkd->rkg", qg, k) / math.sqrt(dh)
    weights = jax.nn.softmax(jnp.concatenate([before, own[..., None]], axis=-1), axis=-1)
    out = jnp.einsum("rkgt,ktd->rkgd", weights[..., :-1], v_seq)
    out = out + weights[..., -1:] * v[:, :, None, :]
    return _matmul(out.reshape(rows, heads * dh), p["output_proj"], None)


def moe(h, p, cfg: dict, quant, chosen=None):
    """``h`` (S, hidden) -> ``((S, hidden), router logits)``: this share's
    routed part plus the shared experts' average.  ``chosen`` (S, experts
    per token) names each token's experts in place of the router's own
    largest; the gates are normalised over them all the same."""
    held, offset = cfg["num_experts"], cfg.get("expert_offset", 0)
    top = cfg["num_experts_per_tok"]
    router_logits = _matmul(h, p["router"], None)  # the router is never rounded
    scores = jax.nn.sigmoid(router_logits)
    if chosen is None:
        chosen_s, chosen_i = jax.lax.top_k(scores, top)
    else:
        chosen_i, chosen_s = chosen, jnp.take_along_axis(scores, chosen, axis=-1)
    gates = chosen_s / jnp.sum(chosen_s, axis=-1, keepdims=True)  # norm_topk_prob

    def one_expert(total, xs):
        w1, w2, w3, e = xs
        gate = jnp.sum(jnp.where(chosen_i == e + offset, gates, 0.0), axis=-1)
        return total + gate[:, None] * _swiglu(h, w1, w2, w3, quant), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h), (p["w1"], p["w2"], p["w3"], jnp.arange(held))
    )

    def one_shared(total, xs):
        return total + _swiglu(h, *xs, quant), None

    sh = p["shared"]
    shared, _ = jax.lax.scan(one_shared, jnp.zeros_like(h), (sh["w1"], sh["w2"], sh["w3"]))
    return routed + shared / cfg["num_shared_experts"], router_logits


def layer_kind(cfg: dict, layer: int):
    """``(window, rotate)`` of layer ``layer`` as :func:`attention` takes
    them: a sliding layer has the window and RoPE, a full layer neither."""
    kind = cfg["layer_types"][layer]
    if kind == "sliding_attention":
        return np.int32(cfg["sliding_window"]), np.bool_(True)
    if kind == "full_attention":
        return np.int32(np.iinfo(np.int32).max), np.bool_(False)
    raise ValueError(f"unknown layer type {kind!r}")


def block(x, p, window, rotate, cfg: dict, quant):
    """One layer: ``(x', keys, values)`` (see :func:`attention`)."""
    h = _layernorm(x, p["ln1"], cfg["layer_norm_eps"])
    attended, k, v = attention(h, p["attn"], cfg, window, rotate, quant)
    return x + attended + moe(h, p["ffn"], cfg, quant)[0], k, v


def row_block_attention(x, positions, p, k_seq, v_seq, window, rotate, cfg: dict):
    """The first half of :func:`block` for single rows (see
    :func:`row_attention`): ``(h, x + Attn(h), router logits)``."""
    h = _layernorm(x, p["ln1"], cfg["layer_norm_eps"])
    attended = row_attention(h, positions, p["attn"], k_seq, v_seq, window, rotate, cfg)
    return h, x + attended, _matmul(h, p["ffn"]["router"], None)


def row_block_experts(h, chosen, p, cfg: dict):
    """The second half: ``MoE(h)`` with each row's experts given."""
    return moe(h, p["ffn"], cfg, None, chosen)[0]


def head(x, w, cfg: dict, quant):
    h = _layernorm(x, w["ln_final"], cfg["layer_norm_eps"])
    return _matmul(h, w["token_embeddings"], quant) * cfg["logit_scale"]


_block_jit = jax.jit(block, static_argnames=("cfg", "quant"))
_row_attention_jit = jax.jit(row_block_attention, static_argnames=("cfg",))
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


class _Frozen(dict):
    """A configuration dict as a static jit argument (equal by content)."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def hidden_states(w: dict, row, cfg: dict, quant: str | None = None, keys: list | None = None):
    """``(S,)`` token ids -> ``(S, hidden)`` after the last block, a layer
    at a time: one jitted program a layer kind, not one for the model.
    ``keys`` (a list) collects each layer's ``(keys, values)``."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    with jax.default_matmul_precision("highest"):
        x = w["token_embeddings"][jnp.asarray(row)].astype(jnp.float32)
        for i, p in enumerate(w["layers"]):
            x, k, v = _block_jit(x, p, *layer_kind(cfg, i), cfg=cfg, quant=quant)
            if keys is not None:
                keys.append((k, v))
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


def routing_choices(router_logits, cfg: dict) -> list:
    """For each row of ``(rows, n_experts)`` float router logits, the expert
    sets a 16-bit computation of the same layer may pick: the reference's
    own largest first, then that set with one chosen expert given up for one
    not chosen, for every such pair among the :data:`NEAR` experts on each
    side of the edge whose logits lie within :data:`ROUTER_MARGIN` and of
    which this share holds either (giving up one absent expert for another
    changes nothing computed here but the gates' sum, by under 1%)."""
    held, offset = cfg["num_experts"], cfg.get("expert_offset", 0)
    top = cfg["num_experts_per_tok"]
    logits = np.asarray(router_logits)
    order = np.argsort(-logits, axis=-1, kind="stable")[:, : top + NEAR]
    out = []
    for row, experts in zip(logits, order):
        own = experts[:top]
        sets = [own]
        for i in range(max(top - NEAR, 0), top):
            for b in experts[top:]:
                a = own[i]
                ours = offset <= a < offset + held or offset <= b < offset + held
                if ours and row[a] - row[b] < ROUTER_MARGIN:
                    sets.append(np.concatenate([own[:i], own[i + 1:], [b]]))
        out.append(sets)
    return out


def _in_blocks(fn, rows: list, *rest, block: int = QUERY_BLOCK):
    """``fn(*blocks of block rows, *rest)`` over host arrays of equal
    length, the last block padded with zeros: one program shape whatever
    the number of rows.  Returns host arrays."""
    n = len(rows[0])
    outs = []
    for start in range(0, n, block):
        part = [a[start: start + block] for a in rows]
        pad = block - len(part[0])
        part = [np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)) for a in part]
        got = fn(*part, *rest)
        got = got if isinstance(got, tuple) else (got,)
        outs.append([np.asarray(g)[: block - pad] for g in got])
    return [np.concatenate(column) for column in zip(*outs)]


def followed_routings(w: dict, cfg: dict, tokens, keys: list, lo: int, hi: int):
    """The hidden states after the last block of positions ``lo .. hi - 1``
    of ``tokens``, once for every routing a 16-bit computation may have
    taken *at that position* (:func:`routing_choices`, in each layer on the
    state that the routing so far produced): ``(states (rows, hidden),
    position index of each row)``.  Earlier positions are what the full
    forward made of them (``keys``, from :func:`hidden_states`)."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    position = np.arange(lo, hi, dtype=np.int32)
    origin = np.arange(hi - lo)
    with jax.default_matmul_precision("highest"):
        x = np.asarray(w["token_embeddings"][jnp.asarray(tokens[lo:hi])].astype(jnp.float32))
        for i, p in enumerate(w["layers"]):
            h, attended, logits = _in_blocks(
                functools.partial(_row_attention_jit, cfg=cfg),
                [x, position], p, *keys[i], *layer_kind(cfg, i),
            )
            followed = np.bincount(origin, minlength=hi - lo)
            parent, chosen = [], []
            for row, sets in enumerate(routing_choices(logits, cfg)):
                room = max(MAX_ROUTINGS - followed[origin[row]], 0)
                sets = sets[: 1 + room]
                followed[origin[row]] += len(sets) - 1
                parent += [row] * len(sets)
                chosen += sets
            parent = np.asarray(parent)
            (experts,) = _in_blocks(
                functools.partial(_row_experts_jit, cfg=cfg),
                [h[parent], np.asarray(chosen, np.int32)], p,
            )
            x, position, origin = attended[parent] + experts, position[parent], origin[parent]
    return x, origin


def served_gaps(seed: int, cfg: dict, sequences: list, *, control: bool = False) -> list:
    """As ``reference.served_gaps``: for each ``(prompt_ids, served_ids)``
    one full forward over prompt + served tokens, at each served position
    the gap by which the served token's logit lies below the reference's
    best, and the widest such gap of the sequence - **where a position's
    routing is a near tie, the smallest gap over the routings it may have
    taken**.  Every served position is scored.  Weights are the seed's,
    rounded to bfloat16 as they are served.  With ``control=True`` the gaps
    are read for the token the float8 forward puts first at each of the same
    positions.

    Why routings are followed: this block picks 8 of 128 experts a token
    and layer, and the 8th and the 9th router logits lie closer than
    :data:`ROUTER_MARGIN` at about one token-layer in three.  Which of the
    two wins is then below what any 16-bit computation resolves, and where
    one of them is held here the winner's whole gated output (a third of the
    expert layer's, measured) enters or leaves the token's hidden state: a
    logit moves by up to 1.0, every time that token recurs.  Sound bfloat16
    runs read 0.0-1.05 as their widest gap against the reference's own
    routing alone (my chip runs, PR 28; KV pages, both kernels and the
    expert layer were checked one by one against this file on the same
    request), which says nothing about precision.  So each served position
    is computed again as a single row (:func:`followed_routings`) under
    every routing within the margin, layer after layer, and is held to the
    one that brings the reference nearest the served token: at a position
    whose routing is decided that is the plain comparison of the dense
    cells, at a near tie a fault has to miss every routing the tie allows.
    Another position's routing reaches this one only through attention,
    which the readings show to be small (PERF.md, section 4).  Each
    sequence's numbers go to standard error for the record: rows followed,
    the share of positions with one routing, the widest gap over those, and
    the widest and mean gap against the reference's own routing."""
    cfg = _Frozen(cfg)
    w = weights_from_seed(seed, cfg, jnp.bfloat16)
    # A few padded lengths, so a few programs: causal, and every other
    # operation is a token's own, so padding behind the last token changes
    # nothing before it.
    step = -(-cfg["context_length"] // SEQUENCE_SIZES)

    def on_head(fn, states, *more, quant=None):
        # The head on served positions alone, in blocks of one size.
        with jax.default_matmul_precision("highest"):
            return _in_blocks(
                functools.partial(fn, cfg=cfg, quant=quant), [states, *more], w, block=HEAD_ROWS
            )[0]

    out = []
    for prompt, served in sequences:
        ids = list(prompt) + list(served)
        tokens = np.asarray(ids + [0] * (-len(ids) % step), np.int32)
        lo, hi = len(prompt) - 1, len(ids) - 1
        keys = []
        hidden_states(w, tokens, cfg, keys=keys)
        if control:
            low = np.asarray(hidden_states(w, tokens, cfg, "fp8")[lo:hi])
            chosen = on_head(_best_jit, low, quant="fp8")
        else:
            chosen = np.asarray(ids[lo + 1:hi + 1], np.int32)
        rows, origin = followed_routings(w, cfg, tokens, keys, lo, hi)
        row_gaps = on_head(_gaps_jit, rows, chosen[origin])
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
        }), file=sys.stderr)
        out.append(float(gaps.max()))
    return out
