"""The plain reference of sarvam-105b's language model (``model_type``
``sarvam_mla``), written from its published ``config.json`` (keys in
brackets) and the catalog's description ("MLA (kv_lora 512, q 192 = 128 nope
+ 64 rope, head_dim 576) - 32L", "128 experts, top-8, 1 shared; aux-free
bias, scaling 2.5").  The attention keys are DeepSeek-V2's latent attention
in the form that family's smaller model uses (no ``q_lora_rank``: a
full-rank query); the block and router keys are the Bailing-MoE-V2 family's.

* Norms: ``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``, eps 1e-6.
  Embedding ``x_0 = E[token]``; logits ``W_head N_f(x_L)``, the head untied
  [``tie_word_embeddings`` false]; no biases.
* **The layer**, pre-norm and sequential: ``h = x + Attn(N1 x)``, ``y = h +
  FFN_l(N2 h)``.
* **Attention** (every layer; ``num_attention_heads`` 64): ``q = W_q u`` ->
  heads x ``q_head_dim`` (192 = ``qk_nope_head_dim`` 128 + ``qk_rope_head_dim``
  64), no query bottleneck; ``[c_kv ; k_r] = W_kva u`` [``kv_lora_rank`` 512
  + 64]; ``c = RMSNorm(c_kv)`` with a learned weight [``use_qk_norm``, as the
  configuration file's ``assumed`` reads it]; ``[k_nope ; v] = W_kvb c`` ->
  heads x (128 + ``v_head_dim`` 128); RoPE on ``q_rope`` and on the one
  ``k_r`` all heads share, interleaved pairs ``(2i, 2i + 1)``; scores
  ``(q_nope . k_nope + q_rope . k_r) * scale``, causal float32 softmax over
  ``v``; output ``W_o concat(heads)``.  Always this *expanded* form: keys
  and values of every head are made from ``c`` and attended as heads (the
  program's tick attends absorbed, which is the same sum).
* **Positions** [``rope_scaling.type`` ``deepseek_yarn``]: pair ``k`` of
  ``rope / 2`` has ``f_k = rope_theta ** (-2k / rope)``; ``dim(r) = rope
  ln(original_max_position_embeddings / (2 pi r)) / (2 ln rope_theta)``,
  ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` (held to
  ``0 .. rope - 1``); ``ramp_k = clip((k - low) / (high - low), 0, 1)``; the
  pair turns at ``f_k (1 - ramp_k) + f_k / factor * ramp_k``
  (:func:`yarn_frequencies`).  cos and sin are multiplied by ``m(mscale) /
  m(mscale_all_dim)``, ``m(s) = 0.1 s ln(factor) + 1``; ``scale = q_head_dim
  ** -0.5 * m(mscale_all_dim) ** 2`` (:func:`softmax_scale`).  At the
  published numbers: ``low`` 10, ``high`` 23, the magnitude 1, the scale
  0.135234.
* **Feed-forward part**: layers below ``first_k_dense_replace`` (layer 0) a
  dense SwiGLU of ``intermediate_size``; the others ``s = sigmoid(W_r u)``
  over the router's ``n_experts`` outputs (the published ``num_experts``,
  128) in float32; ``T`` = the ``num_experts_per_tok`` largest of ``s + b``
  [``moe_router_enable_expert_bias``: the bias joins the choice and not the
  gates]; gates ``g = s[T] / sum(s[T])`` times ``routed_scaling_factor``;
  experts SwiGLU of ``moe_intermediate_size``; plus ``num_shared_experts``
  (1) shared expert of the same width that every token passes, added
  unscaled: ``FFN(u) = 2.5 sum_{e in T} g_e E_e(u) + S(u)``.  No capacity,
  no groups of experts.

**This chip's share.**  ``cfg["num_experts"]`` experts are held, numbered
``expert_offset ..`` of the router's ``n_experts``: the router keeps all its
outputs, its bias and its experts per token, gates are normalised over all
chosen experts, and the routed sum runs over ``T`` *and held* only.  The
shared expert is whole on every chip.  What the absent experts would add is
left out and the partial result goes on to the next layer.  The vocabulary is
the slice ``vocab_size`` of the file.

Departures from the published description, all of them: (1) everything
marked *assumed* in the configuration file (``use_qk_norm`` as the latent's
norm, sigmoid scores, normalised gates, one group of experts, interleaved
pairs, the seeded selection bias, no multi-token-prediction layer); (2) at
width the weights stay bfloat16-valued on the device and are cast to float32
a matrix and an expert at a time; attention runs a group of heads and a
block of queries at a time, the group's keys and values expanded from the
latents where they are attended, and the dense layer's 16,384 in blocks of
rows: the same arithmetic in an order that fits the chip and a 32k-token
request; (3) the routed sum runs over the held experts with a gate of zero
where one was not chosen, which is the same sum; (4)
:func:`followed_routings` computes single positions again against the
latents the full forward left before them.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, cache or batching; imports nothing of ``bpe_transformer_tpu``, from
``reference_cohere2moe`` the seed's generator, the float8 control's rounding,
the near ties of a routing and the row-block helper, from
``reference_nemotronh`` the norm and how joint routings are ranked and kept.
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
    _rmsnorm,
    _routings_by_lead,
)

SEQUENCE_SIZES = 1  # served sequences are scored at this many padded lengths
ROW_BLOCK = 256     # single rows go through a layer in blocks of this many
DENSE_ROWS = 4096   # ... and a sequence through the dense layer's 16,384 so many
HEAD_GROUP = 8      # heads whose keys and values are expanded at a time
BIAS_SPREAD = 5.0   # the seeded selection bias is this times a 0.02 draw
NORM_LEAVES = 1 << 20   # the norms' leaf numbers start here, past every matrix's
VALUE_LEAVES = 1 << 21  # ... and the biases' here


# ---------------------------------------------------------------- positions


def yarn_range(cfg: dict) -> tuple:
    """``(low, high)``: pairs up to ``low`` keep their frequency, pairs from
    ``high`` have it divided by the factor."""
    rope, theta, s = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]

    def dim(turns):
        length = s["original_max_position_embeddings"]
        return rope * math.log(length / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = math.floor(dim(s["beta_fast"])), math.ceil(dim(s["beta_slow"]))
    return max(low, 0), min(high, rope - 1)


def yarn_frequencies(cfg: dict) -> np.ndarray:
    """The rotated pairs' frequencies, ``rope / 2`` float64 values."""
    rope, theta, s = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    low, high = yarn_range(cfg)
    out = []
    for k in range(rope // 2):
        f = theta ** (-2.0 * k / rope)
        ramp = min(max((k - low) / (high - low if high > low else 0.001), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / s["factor"] * ramp)
    return np.asarray(out)


def _m(cfg: dict, key: str) -> float:
    s = cfg["rope_scaling"]
    return 0.1 * s[key] * math.log(s["factor"]) + 1.0 if s["factor"] > 1 else 1.0


def rope_magnitude(cfg: dict) -> float:
    return _m(cfg, "mscale") / _m(cfg, "mscale_all_dim")


def softmax_scale(cfg: dict) -> float:
    return cfg["q_head_dim"] ** -0.5 * _m(cfg, "mscale_all_dim") ** 2


def _rope(x, positions, cfg: dict):
    """Rotate pairs (2i, 2i + 1) of the last axis by ``position *
    frequency_i``, at YaRN's magnitude; ``positions`` broadcasts against
    ``x.shape[:-1]``."""
    inv = jnp.asarray(yarn_frequencies(cfg), jnp.float32)
    ang = jnp.asarray(positions, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang) * rope_magnitude(cfg), jnp.sin(ang) * rope_magnitude(cfg)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1).reshape(x.shape)


# ------------------------------------------------------------------ weights


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"]


def init_weights(seed: int, cfg: dict, dtype=jnp.float32, draw=_draw) -> dict:
    """The benchmark's weights from ``--seed`` in the program's tree layout:
    truncated normal (+-3 sigma) times 0.02 for every matrix, for every norm
    1 + 5 times such a draw (0.7 .. 1.3: a norm weight that the program
    dropped, or applied twice, moves the logits) and for the router's
    selection bias 5 times such a draw (+-0.3 beside scores near 0.5: it
    decides a good part of the choices).  Leaves are numbered in tree order
    by kind and leaf m is drawn from the seed ``hash(seed, m)``."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, held, shared = cfg["kv_lora_rank"], cfg["num_experts"], cfg["num_shared_experts"]
    counters = {"matrix": iter(range(NORM_LEAVES)), "norm": iter(range(NORM_LEAVES, VALUE_LEAVES)),
                "value": iter(range(VALUE_LEAVES, 2 * VALUE_LEAVES))}

    def leaf_seed(kind):
        return jnp.uint32((int(seed) * 1000003 + next(counters[kind]) * 7919 + 12345) % 2**32)

    def dense(*shape):
        return draw(leaf_seed("matrix"), shape, dtype)

    def norm(width):
        return (1.0 + 5.0 * draw(leaf_seed("norm"), (width,), jnp.float32)).astype(dtype)

    def swiglu(*lead, width):
        return {"w1": dense(*lead, width, d), "w2": dense(*lead, d, width),
                "w3": dense(*lead, width, d)}

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        attn = {
            "q_proj": dense(heads * (nope + rope), d), "kv_a": dense(rank + rope, d),
            "kv_b": dense(heads * (nope + vd), rank), "output_proj": dense(d, heads * vd),
            "kv_norm": norm(rank),
        }
        if is_dense(cfg, i):
            ffn = swiglu(width=cfg["intermediate_size"])
        else:
            ff = cfg["moe_intermediate_size"]
            ffn = {
                "router": dense(cfg["n_experts"], d),
                "router_bias": (
                    BIAS_SPREAD * draw(leaf_seed("value"), (cfg["n_experts"],), jnp.float32)
                ),
                **swiglu(held, width=ff), "shared": swiglu(shared, width=ff),
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


def _latents(u, positions, p, cfg: dict, quant):
    """``(c (rows, rank), k_r (rows, rope) rotated)`` of the rows ``u`` at
    ``positions``: what a cache of this attention holds a position."""
    rank = cfg["kv_lora_rank"]
    kv = _matmul(u, p["kv_a"], quant)
    c = _rmsnorm(kv[:, :rank], p["kv_norm"], cfg["rms_norm_eps"])
    return c, _rope(kv[:, rank:], positions, cfg)


def _queries(u, positions, w_group, cfg: dict, quant):
    """A group of heads' queries ``(rows, group, nope + rope)``, the rope
    part rotated; ``w_group`` (group, nope + rope, hidden) is the group's
    part of ``W_q``."""
    nope = cfg["qk_nope_head_dim"]
    q = _matmul(u, w_group.reshape(-1, w_group.shape[-1]), quant)
    q = q.reshape(u.shape[0], w_group.shape[0], -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], positions[:, None], cfg)], -1)
    return _fake_fp8(q) if quant == "fp8" else q


def _by_group(p, cfg: dict) -> tuple:
    """``(W_q, W_kvb)`` a group of `HEAD_GROUP` heads at a time: ``(groups,
    group, nope + rope, hidden)`` and ``(groups, group, nope + v, rank)``."""
    heads = cfg["num_attention_heads"]
    group = math.gcd(heads, HEAD_GROUP)
    return (
        p["q_proj"].reshape(heads // group, group, -1, p["q_proj"].shape[-1]),
        p["kv_b"].reshape(heads // group, group, -1, p["kv_b"].shape[-1]),
    )


def _expand(c, k_r, w_group, cfg: dict, quant):
    """A group of heads' keys ``(keys, group, nope + rope)`` and values
    ``(keys, group, v)`` from latents ``c`` and the shared rotated key
    ``k_r``; ``w_group`` (group, nope + v, rank) is the group's part of
    ``W_kvb``."""
    group, nope = w_group.shape[0], cfg["qk_nope_head_dim"]
    both = _matmul(c, w_group.reshape(-1, w_group.shape[-1]), quant)
    both = both.reshape(c.shape[0], group, -1)
    shared = jnp.broadcast_to(k_r[:, None, :], (k_r.shape[0], group, k_r.shape[-1]))
    k, v = jnp.concatenate([both[..., :nope], shared], -1), both[..., nope:]
    if quant == "fp8":
        k, v = _fake_fp8(k), _fake_fp8(v)
    return k, v


def attention(u, p, cfg: dict, quant):
    """``u`` (S, hidden), a sequence from position 0 -> ``((S, hidden), (c,
    k_r))``; the latents are what :func:`row_block_attention` reads.  A
    group of heads at a time, its keys and values expanded from the latents,
    in blocks of queries."""
    s = u.shape[0]
    heads, vd = cfg["num_attention_heads"], cfg["v_head_dim"]
    positions = jnp.arange(s)
    c, k_r = _latents(u, positions, p, cfg, quant)
    scale, block = softmax_scale(cfg), math.gcd(s, QUERY_BLOCK)

    def one_group(xs):
        wq_g, w_g = xs
        q_g = _queries(u, positions, wq_g, cfg, quant)
        k, v = _expand(c, k_r, w_g, cfg, quant)

        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(q_g, start, block)          # (block, g, .)
            scores = jnp.einsum("qgd,kgd->gqk", qb, k) * scale
            visible = positions[None, :] <= start + jnp.arange(block)[:, None]
            weights = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kgd->qgd", weights, v)

        return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, -1, vd)

    out = jax.lax.map(one_group, _by_group(p, cfg))                       # (G, S, g, v)
    merged = out.transpose(1, 0, 2, 3).reshape(s, heads * vd)
    return _matmul(merged, p["output_proj"], quant), (c, k_r)


def selection_scores(u, p):
    """``(s, s + b)``: the router's sigmoid scores in float32, never
    rounded, and what the choice of experts is made by."""
    s = jax.nn.sigmoid(_matmul(u, p["router"], None))
    return s, s + p["router_bias"].astype(jnp.float32)


def routed(u, p, cfg: dict, quant, chosen=None):
    """``u`` (S, hidden) -> this share's routed part, scaled.  ``chosen``
    (S, experts per token) names each token's experts in place of the
    largest of ``s + b``; the gates are the scores of whatever is named,
    normalised, times the scaling factor."""
    held, offset = cfg["num_experts"], cfg.get("expert_offset", 0)
    scores, biased = selection_scores(u, p)
    if chosen is None:
        _, chosen = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = cfg["routed_scaling_factor"] * picked / jnp.sum(picked, axis=-1, keepdims=True)

    def one_expert(total, xs):
        w1, w2, w3, e = xs
        gate = jnp.sum(jnp.where(chosen == e + offset, gates, 0.0), axis=-1)
        return total + gate[:, None] * _swiglu(u, w1, w2, w3, quant), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u), (p["w1"], p["w2"], p["w3"], jnp.arange(held))
    )
    return out


def shared_experts(u, p, quant):
    """Every shared expert's output, summed: what every token passes
    through, and what every chip computes alike."""
    s = p["shared"]
    return sum(
        _swiglu(u, s["w1"][j], s["w2"][j], s["w3"][j], quant)
        for j in range(s["w1"].shape[0])
    )


def ffn(u, p, cfg: dict, quant, chosen=None):
    """A layer's feed-forward part by its tree: the dense SwiGLU, or the
    routed experts plus the shared one."""
    if "router" in p:
        return routed(u, p, cfg, quant, chosen) + shared_experts(u, p, quant)
    # In blocks of rows: 32k rows of 16,384 float32 values, three times
    # over, would not fit beside the weights.
    block = math.gcd(u.shape[0], DENSE_ROWS)
    out = jax.lax.map(
        lambda rows: _swiglu(rows, p["w1"], p["w2"], p["w3"], quant),
        u.reshape(-1, block, u.shape[-1]),
    )
    return out.reshape(u.shape)


def block(x, p, cfg: dict, quant):
    """One layer over a whole sequence: ``(y, (c, k_r))``."""
    eps = cfg["rms_norm_eps"]
    attended, memory = attention(_rmsnorm(x, p["ln1"], eps), p["attn"], cfg, quant)
    h = x + attended
    return h + ffn(_rmsnorm(h, p["ln2"], eps), p["ffn"], cfg, quant), memory


def row_block_attention(x, positions, p, c_seq, kr_seq, cfg: dict):
    """A layer's attention half for single rows: row r is a token at
    ``positions[r]`` of a sequence whose latents are ``c_seq``, ``kr_seq``;
    it sees those before its position and its own.  Returns ``(h = x +
    Attn, N2 h)``."""
    rows = x.shape[0]
    heads, vd = cfg["num_attention_heads"], cfg["v_head_dim"]
    eps, attn = cfg["rms_norm_eps"], p["attn"]
    u = _rmsnorm(x, p["ln1"], eps)
    c, k_r = _latents(u, positions, attn, cfg, None)
    scale = softmax_scale(cfg)
    before = jnp.arange(c_seq.shape[0])[None, :] < positions[:, None]

    def one_group(xs):
        wq_g, w_g = xs
        q_g = _queries(u, positions, wq_g, cfg, None)
        k_seq, v_seq = _expand(c_seq, kr_seq, w_g, cfg, None)
        k_own, v_own = _expand(c, k_r, w_g, cfg, None)
        earlier = jnp.einsum("qgd,kgd->gqk", q_g, k_seq) * scale
        earlier = jnp.where(before, earlier, -jnp.inf)
        own = jnp.einsum("qgd,qgd->gq", q_g, k_own) * scale
        weights = jax.nn.softmax(jnp.concatenate([earlier, own[..., None]], -1), axis=-1)
        return (
            jnp.einsum("gqk,kgd->qgd", weights[..., :-1], v_seq)
            + weights[..., -1].T[..., None] * v_own
        )

    out = jax.lax.map(one_group, _by_group(attn, cfg))
    out = out.transpose(1, 0, 2, 3).reshape(rows, heads * vd)
    h = x + _matmul(out, attn["output_proj"], None)
    return h, _rmsnorm(h, p["ln2"], eps)


def row_block_scores(u, p):
    return selection_scores(u, p["ffn"])[1]


def row_block_ffn(u, chosen, p, cfg: dict):
    """The feed-forward part with each row's experts given (the dense layer
    takes no notice of them)."""
    return ffn(u, p["ffn"], cfg, None, chosen)


def head(x, w, cfg: dict, quant):
    return _matmul(_rmsnorm(x, w["ln_final"], cfg["rms_norm_eps"]), w["lm_head"], quant)


_block_jit = jax.jit(block, static_argnames=("cfg", "quant"))
_row_attention_jit = jax.jit(row_block_attention, static_argnames=("cfg",))
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
    ``memory`` (a list) collects each layer's latents ``(c, k_r)``."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(w, row)
        for p in w["layers"]:
            x, kept = _block_jit(x, p, cfg=cfg, quant=quant)
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
    """As ``reference_mimov2.followed_routings``: the hidden states after the
    last block of positions ``lo .. hi - 1`` of ``tokens``, once for every
    joint routing a 16-bit computation may have taken *at that position*
    (`_routings_by_lead` on the selection scores, in each expert layer on
    the state that the routing so far produced; after every expert layer a
    position keeps the `MAX_ROUTINGS` rows whose summed leads are least, the
    reference's own first): ``(states (rows, hidden), position index of each
    row)``.  Earlier positions are what the full forward made of them
    (``memory``, from :func:`hidden_states`)."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    top = cfg["num_experts_per_tok"]
    position = np.arange(lo, hi, dtype=np.int32)
    origin = np.arange(hi - lo)
    cost = np.zeros(hi - lo)
    with jax.default_matmul_precision("highest"):
        x = np.asarray(_embed(w, tokens[lo:hi]))
        for p, kept in zip(w["layers"], memory):
            x, u = _in_blocks(
                functools.partial(_row_attention_jit, cfg=cfg),
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
            for row, sets in enumerate(routing_choices(SCORE_TO_LOGIT * biased, cfg)):
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
    """As ``reference_mimov2.served_gaps`` and for its reasons: for each
    ``(prompt_ids, served_ids)`` one full forward over prompt + served
    tokens, at each served position the gap by which the served token's
    logit lies below the reference's best - where a position's routing is a
    near tie (top-8 of 128 sigmoid scores under a selection bias), the
    smallest gap over the joint routings it may have taken - and **the mean
    of these gaps over the sequence's served positions**, every one of them
    scored: a position routed otherwise than the reference's own forward
    leaves its latent row behind as the context of every later position,
    which moves the widest single gap by what is no rounding, and the mean
    hardly.  The widest goes to standard error with the sequence's other
    numbers for the record.  Weights are the seed's, rounded to bfloat16 as
    they are served.  With ``control=True`` the gaps are read for the token
    the float8 forward puts first at each of the same positions."""
    cfg = _Frozen(cfg)
    w = weights_from_seed(seed, cfg, jnp.bfloat16)
    # One padded length, so one program a layer kind (causal, and every
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
