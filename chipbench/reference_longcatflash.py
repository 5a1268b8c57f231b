"""The plain reference of LongCat-Flash-Omni's language model, written from
its published ``config.json`` (keys in brackets) and the catalog's
description ("MLA - 28 double-layers", "512 experts, top-12, 0 shared",
256 zero experts of type identity).  Language model only: the audio and
vision encoders and the codec decoder are out.

* Norms: ``RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``, no bias.
* **MLA sublayer** ``Attn_i(h)``, ``num_attention_heads`` heads, no biases
  [``attention_bias``]: ``c_q = s_q * RMSNorm(W_qa h)`` [``q_lora_rank``],
  ``s_q = sqrt(hidden_size / q_lora_rank)`` [``mla_scale_q_lora``];
  ``q = W_qb c_q`` -> heads x (``qk_nope_head_dim`` + ``qk_rope_head_dim``);
  ``[c_kv ; k_r] = W_kva h`` [``kv_lora_rank`` + rope]; ``c = s_kv *
  RMSNorm(c_kv)``, ``s_kv = sqrt(hidden_size / kv_lora_rank)``
  [``mla_scale_kv_lora``]; ``k_r`` is one key shared by all heads, neither
  normalised nor scaled; ``[k_nope ; v] = W_kvb c`` -> heads x (nope +
  ``v_head_dim``); RoPE on ``q_rope`` and ``k_r`` only, interleaved pairs,
  theta ``rope_theta``, no scaling; ``scores = (q_nope . k_nope + q_rope .
  k_r) / sqrt(nope + rope)``, causal softmax, ``out = W_o concat_h(softmax .
  v)``.  Always this *expanded* form: keys and values of every head are
  computed from ``c`` and attended as heads (the program also has an
  absorbed form, which is the same sum).
* **Dense FFN** ``F_i(h) = W2 (silu(W1 h) * W3 h)`` [``ffn_hidden_size``].
* **Expert layer** ``M(h)``: ``p = softmax(W_r h)`` over ``n_experts +
  zero_expert_num`` outputs (real experts first; router without bias);
  ``T`` = the ``moe_topk`` largest of ``p + b`` (``b`` the selection bias,
  zeros); ``g_e = routed_scaling_factor * p_e`` for ``e`` in ``T``, not
  renormalised; ``M(h) = sum_{e in T, e real} g_e E_e(h) + (sum_{e in T, e
  zero} g_e) h`` [``zero_expert_type`` identity], ``E_e`` a SwiGLU of width
  ``expert_ffn_hidden_size``.  No shared expert, no capacity.
* **The layer** (the shortcut-connected double layer): ``a = x +
  Attn_0(N1 x)``; ``u = N2 a``; ``m = M(u)``; ``b = a + F_0(u)``; ``d = b +
  Attn_1(N3 b)``; ``y = d + F_1(N4 d) + m``.
* Logits ``RMSNorm_f(x_L) @ W_head^T``, head untied.

**This chip's share.**  ``cfg["n_routed_experts"]`` real experts are held,
numbered ``expert_offset ..`` of the router's ``cfg["n_experts"]``: the
router keeps all its outputs and its experts per token, the real part sums
over ``T`` *and held* only, and the zero experts' part is computed whole (a
zero expert has no weights and lives with the token).  What the absent
experts would add is left out and the partial result goes on to the next
layer.  The vocabulary is the slice ``vocab_size`` of the file.

Departures from the published description, all of them: (1) everything
marked *assumed* in the configuration file (the scales' values, interleaved
RoPE, the softmax scale, zeros for the selection bias, unnormalised gates,
the double layer's wiring, the untied head); (2) at width the weights stay
bfloat16-valued on the device and are cast to float32 a matrix and an
expert at a time, attention runs in blocks of queries, and keys and values
are kept between calls as the latent ``c`` and ``k_r`` they are computed
from and expanded where they are attended: the same arithmetic in an order
that fits the chip; (3) the real part runs over the held experts with a
gate of zero where one was not chosen, which is the same sum; (4) sequences
that begin alike are computed once over what they share
(:func:`served_gaps`): causal, so what follows a position changes nothing
at it.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, cache or batching; imports nothing of ``bpe_transformer_tpu``, and
from ``reference_cohere2moe`` the seed's generator, the float8 control's
rounding and the row-block helper.  ``quant="fp8"`` is the control of
``correct`` (see ``reference.py``).
"""

from __future__ import annotations

import functools
import json
import math
import sys

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
    _rope,
    _swiglu,
)

SUFFIX_SIZES = 8  # what follows a shared prefix is scored at this many padded lengths
PREFIX_STEP = 512  # a shared prefix is taken in whole multiples of this
#: Two router logits closer than this may change places in a 16-bit
#: computation (see :func:`served_gaps`).  Router logits have a spread of 1.6
#: here; bfloat16 activations move one by ~0.01.  Half of
#: ``reference_cohere2moe``'s margin: with 768 outputs the edge of the top 12
#: is four times as crowded, and at 0.1 the search followed 19 routings a
#: position for the gaps that 7 give at 0.05 (PERF.md section 6, PR 33).
ROUTER_MARGIN = 0.05
NEAR = 3  # outputs on each side of the top-k's edge tried against each other
MAX_ROUTINGS = 16  # routings followed for one served position, at most
MATRICES_A_LAYER = 20
NORM_LEAVES = 1 << 20  # the norms' leaf numbers start here, past every matrix's


# ------------------------------------------------------------------ weights


def init_weights(seed: int, cfg: dict, dtype=jnp.float32, draw=_draw) -> dict:
    """The benchmark's weights from ``--seed`` in the program's tree layout:
    truncated normal (+-3 sigma) times 0.02 for every matrix, zeros for the
    router's selection bias, and for every norm 1 + 5 times such a draw
    (0.7 .. 1.3: a norm weight that the program dropped, or applied twice,
    moves the logits; all ones would hide it).  Matrix number m of the tree
    (embedding 0, head 1, then 20 a layer: q_a, q_b, kv_a, kv_b, o of each
    attention sublayer, w1, w2, w3 of each dense FFN, the router, w1, w2, w3
    of the held experts) is drawn from the seed ``hash(seed, m)``; norm
    number k (a layer's q and kv norms of each sublayer, then its four, the
    final norm last) from ``hash(seed, NORM_LEAVES + k)``."""
    d, ff, eff = cfg["hidden_size"], cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    heads, rq, rkv = cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    held = cfg["n_routed_experts"]
    outputs = cfg["n_experts"] + cfg["zero_expert_num"]
    count = iter(range(2 + MATRICES_A_LAYER * cfg["num_layers"]))

    norms = iter(range(NORM_LEAVES, NORM_LEAVES + 1 + 8 * cfg["num_layers"]))

    def leaf(number, shape, kind):
        leaf_seed = (int(seed) * 1000003 + number * 7919 + 12345) % 2**32
        return draw(jnp.uint32(leaf_seed), shape, kind)

    def dense(*shape):
        return leaf(next(count), shape, dtype)

    def norm(width):
        return (1.0 + 5.0 * leaf(next(norms), (width,), jnp.float32)).astype(dtype)

    def attention():
        return {
            "q_a": dense(rq, d), "q_b": dense(heads * (nope + rope), rq),
            "kv_a": dense(rkv + rope, d), "kv_b": dense(heads * (nope + vd), rkv),
            "output_proj": dense(d, heads * vd),
            "q_norm": norm(rq), "kv_norm": norm(rkv),
        }

    def swiglu():
        return {"w1": dense(ff, d), "w2": dense(d, ff), "w3": dense(ff, d)}

    embedding, head = dense(cfg["vocab_size"], d), dense(cfg["vocab_size"], d)
    layers = []
    for _ in range(cfg["num_layers"]):
        layers.append({
            "attn": [attention(), attention()],
            "ln": [norm(d) for _ in range(4)],
            "dense_ffn": [swiglu(), swiglu()],
            "ffn": {
                "router": dense(outputs, d),
                "w1": dense(held, eff, d), "w2": dense(held, d, eff),
                "w3": dense(held, eff, d),
                "router_bias": jnp.zeros((outputs,), jnp.float32),
            },
        })
    return {
        "token_embeddings": embedding, "layers": layers,
        "ln_final": norm(d), "lm_head": head,
    }


def weights_from_seed(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    """On the device, one small jitted program a matrix shape."""
    return init_weights(seed, cfg, dtype, draw=_draw_jit)


# ------------------------------------------------------------------ forward


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def attention(h, positions, p, c_before, kr_before, cfg: dict, quant, rows_alone: bool):
    """One MLA sublayer for the rows ``h`` (rows, hidden) at ``positions``:
    ``((rows, hidden), c, k_r)``.  ``c_before``, ``kr_before`` (keys, .) are
    the latents and rotated shared keys of the sequence's positions ``0 ..
    keys - 1``, of which a row sees those before its own position; ``c``,
    ``k_r`` are the rows' own.  The rows are consecutive positions of one
    sequence and see each other causally, or with ``rows_alone`` each is a
    position of its own and sees, of the rows, itself.  Keys and values of
    every head are expanded from the latents here and attended as heads."""
    rows = h.shape[0]
    heads, rq, rkv = cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    d, eps, theta = cfg["hidden_size"], cfg["rms_norm_eps"], cfg["rope_theta"]
    s_q = math.sqrt(d / rq) if cfg["mla_scale_q_lora"] else 1.0
    s_kv = math.sqrt(d / rkv) if cfg["mla_scale_kv_lora"] else 1.0

    c_q = s_q * _rmsnorm(_matmul(h, p["q_a"], quant), p["q_norm"], eps)
    q = _matmul(c_q, p["q_b"], quant).reshape(rows, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta, positions[:, None])], -1)
    kv = _matmul(h, p["kv_a"], quant)
    c = s_kv * _rmsnorm(kv[:, :rkv], p["kv_norm"], eps)
    k_r = _rope(kv[:, rkv:], theta, positions)

    def expand(c_rows, kr_rows):
        both = _matmul(c_rows, p["kv_b"], quant).reshape(-1, heads, nope + vd)
        shared = jnp.broadcast_to(kr_rows[:, None, :], (kr_rows.shape[0], heads, rope))
        return jnp.concatenate([both[..., :nope], shared], -1), both[..., nope:]

    k_before, v_before = expand(c_before, kr_before)
    k_own, v_own = expand(c, k_r)
    if quant == "fp8":
        q, k_before, v_before, k_own, v_own = (
            _fake_fp8(t) for t in (q, k_before, v_before, k_own, v_own)
        )
    scale = 1.0 / math.sqrt(nope + rope)
    before_pos = jnp.arange(c_before.shape[0])
    block = math.gcd(rows, QUERY_BLOCK)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)            # (block, heads, .)
        pos = jax.lax.dynamic_slice_in_dim(positions, start, block)
        s_before = jnp.einsum("qhd,khd->hqk", qb, k_before) * scale
        s_before = jnp.where(before_pos[None, :] < pos[:, None], s_before, -jnp.inf)
        if rows_alone:
            kb = jax.lax.dynamic_slice_in_dim(k_own, start, block)
            vb = jax.lax.dynamic_slice_in_dim(v_own, start, block)
            s_own = jnp.einsum("qhd,qhd->hq", qb, kb)[..., None] * scale
            weights = jax.nn.softmax(jnp.concatenate([s_before, s_own], -1), axis=-1)
            return (
                jnp.einsum("hqk,khd->qhd", weights[..., :-1], v_before)
                + weights[..., -1].T[..., None] * vb
            )
        s_own = jnp.einsum("qhd,khd->hqk", qb, k_own) * scale
        s_own = jnp.where(positions[None, :] <= pos[:, None], s_own, -jnp.inf)
        weights = jax.nn.softmax(jnp.concatenate([s_before, s_own], -1), axis=-1)
        n_before = k_before.shape[0]
        return (
            jnp.einsum("hqk,khd->qhd", weights[..., :n_before], v_before)
            + jnp.einsum("hqk,khd->qhd", weights[..., n_before:], v_own)
        )

    out = jax.lax.map(one_block, jnp.arange(0, rows, block))           # (nb, block, heads, v)
    return _matmul(out.reshape(rows, heads * vd), p["output_proj"], quant), c, k_r


def router_probabilities(h, p):
    """``(p, logits)`` over every router output; the router is never
    rounded."""
    logits = _matmul(h, p["router"], None)
    return jax.nn.softmax(logits, axis=-1), logits


def moe(h, p, cfg: dict, quant, chosen=None):
    """``h`` (rows, hidden) -> ``((rows, hidden), router logits)``: this
    share's real part plus the zero experts' whole.  ``chosen`` (rows,
    experts per token) names each row's outputs in place of the router's own
    largest; the gates are the probabilities of whatever is named."""
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    probs, logits = router_probabilities(h, p)
    if chosen is None:
        _, chosen = jax.lax.top_k(probs + p["router_bias"], cfg["moe_topk"])
    gates = cfg["routed_scaling_factor"] * jnp.take_along_axis(probs, chosen, axis=-1)

    def one_expert(total, xs):
        w1, w2, w3, e = xs
        gate = jnp.sum(jnp.where(chosen == e + offset, gates, 0.0), axis=-1)
        return total + gate[:, None] * _swiglu(h, w1, w2, w3, quant), None

    real, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h), (p["w1"], p["w2"], p["w3"], jnp.arange(held))
    )
    zero_gate = jnp.sum(jnp.where(chosen >= cfg["n_experts"], gates, 0.0), axis=-1)
    return real + zero_gate[:, None] * h, logits


def _before_experts(x, positions, p, before, cfg, quant, rows_alone):
    """The layer but for ``m``: ``(u, y - m, router logits, latents)``."""
    eps, ln, dense = cfg["rms_norm_eps"], p["ln"], p["dense_ffn"]
    (c0, kr0), (c1, kr1) = before
    att, c_a, kr_a = attention(
        _rmsnorm(x, ln[0], eps), positions, p["attn"][0], c0, kr0, cfg, quant, rows_alone
    )
    a = x + att
    u = _rmsnorm(a, ln[1], eps)
    b = a + _swiglu(u, dense[0]["w1"], dense[0]["w2"], dense[0]["w3"], quant)
    att, c_b, kr_b = attention(
        _rmsnorm(b, ln[2], eps), positions, p["attn"][1], c1, kr1, cfg, quant, rows_alone
    )
    d = b + att
    h = _rmsnorm(d, ln[3], eps)
    y = d + _swiglu(h, dense[1]["w1"], dense[1]["w2"], dense[1]["w3"], quant)
    return u, y, _matmul(u, p["ffn"]["router"], None), ((c_a, kr_a), (c_b, kr_b))


def block(x, positions, p, before, cfg: dict, quant):
    """One layer over consecutive positions of a sequence: ``(y, latents)``,
    ``latents`` the two sublayers' ``(c, k_r)`` of these positions."""
    u, y, _, latents = _before_experts(x, positions, p, before, cfg, quant, False)
    return y + moe(u, p["ffn"], cfg, quant)[0], latents


def row_block_before_experts(x, positions, p, before, cfg: dict):
    """The layer but for ``m``, for single rows: ``(u, y - m, router
    logits)``."""
    return _before_experts(x, positions, p, before, cfg, None, True)[:3]


def row_block_experts(u, chosen, p, cfg: dict):
    """``m = M(u)`` with each row's outputs given."""
    return moe(u, p["ffn"], cfg, None, chosen)[0]


def head(x, w, cfg: dict, quant):
    return _matmul(_rmsnorm(x, w["ln_final"], cfg["rms_norm_eps"]), w["lm_head"], quant)


_block_jit = jax.jit(block, static_argnames=("cfg", "quant"))
_row_before_jit = jax.jit(row_block_before_experts, static_argnames=("cfg",))
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


def no_latents(cfg: dict) -> list:
    """The latents of no position, a layer's two sublayers each."""
    empty = (
        jnp.zeros((0, cfg["kv_lora_rank"]), jnp.float32),
        jnp.zeros((0, cfg["qk_rope_head_dim"]), jnp.float32),
    )
    return [(empty, empty)] * cfg["num_layers"]


def hidden_states(w: dict, row, cfg: dict, quant: str | None = None, before: list | None = None):
    """``(S,)`` token ids that follow the positions of ``before`` (a list a
    layer of the two sublayers' ``(c, k_r)``, from an earlier call; default
    none) -> ``((S, hidden)`` after the last block, the latents of ``before``
    and these positions together``)``, a layer at a time: one jitted program
    a length, not one for the model."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    before = no_latents(cfg) if before is None else before
    positions = before[0][0][0].shape[0] + jnp.arange(len(row))
    latents = []
    with jax.default_matmul_precision("highest"):
        x = w["token_embeddings"][jnp.asarray(row)].astype(jnp.float32)
        for p, earlier in zip(w["layers"], before):
            x, own = _block_jit(x, positions, p, earlier, cfg=cfg, quant=quant)
            latents.append(tuple(
                tuple(jnp.concatenate(pair) for pair in zip(old, new))
                for old, new in zip(earlier, own)
            ))
        return x, latents


def forward_logits(w: dict, tokens, cfg: dict, quant: str | None = None):
    """``(B, S)`` token ids -> ``(B, S, V)`` float32 logits."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head_jit(hidden_states(w, row, cfg, quant)[0], w, cfg=cfg, quant=quant)
            for row in np.asarray(tokens)
        ])


# ------------------------------------------------------------------ serving


def routing_choices(router_logits, cfg: dict) -> list:
    """For each row of ``(rows, outputs)`` float router logits, the sets of
    outputs a 16-bit computation of the same layer may pick, as ``(margin,
    set)``: the reference's own largest first (margin -1), then that set
    with one chosen output given up for one not chosen, nearest tie first,
    for every such pair among the :data:`NEAR` outputs on each side of the
    edge whose logits lie within ``ROUTER_MARGIN`` (the margin given) and
    that changes what is computed here: one of the two is a real expert this
    share holds or a zero expert, and not both are zero experts (gates are
    not renormalised, so giving up one absent expert for another changes
    nothing at all, and one zero expert for another only the gates' sum, by
    under a tenth of one gate)."""
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    real, top = cfg["n_experts"], cfg["moe_topk"]
    logits = np.asarray(router_logits)
    order = np.argsort(-logits, axis=-1, kind="stable")[:, : top + NEAR]

    def kind(e):  # 0 absent, 1 held here, 2 zero
        return 2 if e >= real else int(offset <= e < offset + held)

    out = []
    for row, experts in zip(logits, order):
        own = experts[:top]
        swaps = []
        for i in range(max(top - NEAR, 0), top):
            for b in experts[top:]:
                a = own[i]
                margin = float(row[a] - row[b])
                if (kind(a), kind(b)) not in ((0, 0), (2, 2)) and margin < ROUTER_MARGIN:
                    swaps.append((margin, np.concatenate([own[:i], own[i + 1:], [b]])))
        out.append([(-1.0, own)] + sorted(swaps, key=lambda swap: swap[0]))
    return out


def followed_routings(w: dict, cfg: dict, tokens, latents: list, lo: int, hi: int):
    """The hidden states after the last block of positions ``lo .. hi - 1``
    of ``tokens``, once for every routing a 16-bit computation may have
    taken *at that position* (:func:`routing_choices`, in each layer on the
    state that the routing so far produced): ``(states (rows, hidden),
    position index of each row)``.  Earlier positions are what the full
    forward made of them (``latents``, from :func:`hidden_states`).  The
    expert layer joins at the layer's end, so a layer's rows are computed
    once up to there and each routing adds its own ``m``.

    A position is followed along at most :data:`MAX_ROUTINGS` routings, of
    which each layer may use its share (half of what the next may: the last
    layer's ties move the logits most directly and are never crowded out by
    the first's), nearest ties first."""
    cfg = cfg if isinstance(cfg, _Frozen) else _Frozen(cfg)
    position = np.arange(lo, hi, dtype=np.int32)
    origin = np.arange(hi - lo)
    layers = len(w["layers"])
    with jax.default_matmul_precision("highest"):
        x = np.asarray(w["token_embeddings"][jnp.asarray(tokens[lo:hi])].astype(jnp.float32))
        for layer, (p, before) in enumerate(zip(w["layers"], latents)):
            u, y, logits = _in_blocks(
                functools.partial(_row_before_jit, cfg=cfg), [x, position], p, before,
            )
            cap = max(2, MAX_ROUTINGS >> (layers - 1 - layer))
            choices = routing_choices(logits, cfg)
            followed = np.bincount(origin, minlength=hi - lo)
            swaps = sorted(
                (margin, row, k)
                for row, sets in enumerate(choices)
                for k, (margin, _) in enumerate(sets[1:], start=1)
            )
            taken = [[0] for _ in choices]  # a row's own routing first
            for _, row, k in swaps:
                if followed[origin[row]] < cap:
                    followed[origin[row]] += 1
                    taken[row].append(k)
            parent = np.asarray([row for row, ks in enumerate(taken) for _ in ks])
            chosen = np.asarray(
                [choices[row][k][1] for row, ks in enumerate(taken) for k in ks], np.int32
            )
            (m,) = _in_blocks(
                functools.partial(_row_experts_jit, cfg=cfg), [u[parent], chosen], p,
            )
            x, position, origin = y[parent] + m, position[parent], origin[parent]
    return x, origin


def shared_prefix(sequences: list) -> int:
    """Positions every sequence begins alike with, in whole multiples of
    :data:`PREFIX_STEP`, and before every prompt's last token."""
    prompts = [np.asarray(prompt) for prompt, _ in sequences]
    shortest = min(len(prompt) for prompt in prompts)
    alike = np.all([prompt[:shortest] == prompts[0][:shortest] for prompt in prompts], axis=0)
    common = shortest if alike.all() else int(np.argmin(alike))
    return min(common, shortest - 1) // PREFIX_STEP * PREFIX_STEP


def served_gaps(seed: int, cfg: dict, sequences: list, *, control: bool = False) -> list:
    """As ``reference_cohere2moe.served_gaps``: for each ``(prompt_ids,
    served_ids)`` one full forward over prompt + served tokens, at each
    served position the gap by which the served token's logit lies below
    the reference's best, and the widest such gap of the sequence - where a
    position's routing is a near tie, the smallest gap over the routings it
    may have taken.  Every served position is scored.  Weights are the
    seed's, rounded to bfloat16 as they are served.  With ``control=True``
    the gaps are read for the token the float8 forward puts first at each of
    the same positions.

    What the sequences share from position 0 (:func:`shared_prefix`: this
    cell's system prompt) goes through the forward once, and each sequence's
    own positions after it.

    Why routings are followed: top-12 of 768 has about two outputs within
    ``ROUTER_MARGIN`` of the edge on either side, a third of the outputs are
    zero experts and gates are not renormalised, so which of two near-tied
    outputs wins moves the layer's result by a whole gated expert (or a
    whole ``gate x h``) wherever one of them is computed here; that is below
    what any 16-bit computation resolves and says nothing about precision
    (see ``reference_cohere2moe.served_gaps``).  Each sequence's numbers go
    to standard error for the record."""
    cfg = _Frozen(cfg)
    w = weights_from_seed(seed, cfg, jnp.bfloat16)
    step = -(-cfg["context_length"] // SUFFIX_SIZES)
    n_shared = shared_prefix(sequences)
    shared_ids = np.asarray(sequences[0][0][:n_shared], np.int32)

    def on_head(fn, states, *more, quant=None):
        with jax.default_matmul_precision("highest"):
            return _in_blocks(
                functools.partial(fn, cfg=cfg, quant=quant), [states, *more], w, block=HEAD_ROWS
            )[0]

    def prefix_latents(quant):
        return hidden_states(w, shared_ids, cfg, quant)[1] if n_shared else None

    before, before_low = prefix_latents(None), None
    out = []
    for prompt, served in sequences:
        ids = list(prompt) + list(served)
        own = ids[n_shared:]
        tokens = np.asarray(own + [0] * (-len(own) % step), np.int32)
        lo, hi = len(prompt) - 1 - n_shared, len(own) - 1
        latents = hidden_states(w, tokens, cfg, before=before)[1]
        if control:
            if before_low is None:
                before_low = prefix_latents("fp8")
            low = np.asarray(hidden_states(w, tokens, cfg, "fp8", before_low)[0][lo:hi])
            chosen = on_head(_best_jit, low, quant="fp8")
        else:
            chosen = np.asarray(own[lo + 1:hi + 1], np.int32)
        # The rows' earlier positions: the shared ones and the sequence's own.
        whole = np.concatenate([shared_ids, tokens])
        rows, origin = followed_routings(w, cfg, whole, latents, n_shared + lo, n_shared + hi)
        row_gaps = on_head(_gaps_jit, rows, chosen[origin])
        gaps = np.full(hi - lo, np.inf)
        np.minimum.at(gaps, origin, row_gaps)
        # A position's first row is the reference's own routing.
        first = row_gaps[np.unique(origin, return_index=True)[1]]
        one = np.bincount(origin, minlength=hi - lo) == 1
        print(json.dumps({
            "served_gaps": "control" if control else "sound", "prompt": len(prompt),
            "shared": n_shared, "served": len(served), "rows": len(origin),
            "one_routing_share": float(one.mean()),
            "widest": float(gaps.max()), "mean": float(gaps.mean()),
            "widest_one_routing": float(gaps[one].max()) if one.any() else 0.0,
            "widest_own_routing": float(first.max()), "mean_own_routing": float(first.mean()),
        }), file=sys.stderr)
        out.append(float(gaps.max()))
    return out
