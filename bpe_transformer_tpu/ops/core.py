"""Core tensor ops: the building blocks of the transformer, as pure jnp.

Each op mirrors a spec-only component of the reference test contract
(`/root/reference/tests/adapters.py`): linear (M1), embedding (M2), rmsnorm
(M3), silu (M4), swiglu (M5), softmax (M6), scaled-dot-product attention
(M7), multi-head self-attention with/without RoPE (M8).

TPU notes: weights follow the torch ``(d_out, d_in)`` row-major layout so
reference checkpoints map 1:1; matmuls are einsums the XLA TPU backend tiles
onto the MXU; normalization/softmax accumulate in float32 regardless of the
activation dtype (bf16-safe); masks are boolean with True = keep.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from bpe_transformer_tpu.ops.rope import apply_rope, rope_tables

#: Large negative filler for masked attention scores.  Finite (not -inf) so
#: fully-masked rows produce a uniform distribution instead of NaNs.
MASK_VALUE = -1e30


def linear(x: Array, weight: Array) -> Array:
    """``y = x @ W.T`` with torch-layout ``W: (d_out, d_in)``; no bias.

    ``weight`` may also be an int8-quantized dict (``ops/quant.py``, the
    serving path's per-channel weights) — dispatched to the
    dequant-in-register Pallas matmul.  Training params are plain arrays,
    so the hot path is untouched.
    """
    if isinstance(weight, dict):
        from bpe_transformer_tpu.ops.quant import quant_linear

        return quant_linear(x, weight)
    return jnp.einsum("...i,oi->...o", x, weight)


def head_logits(hidden: Array, head_w: Array) -> Array:
    """Vocab projection ``hidden (..., d) @ head_w (vocab, d).T`` in the
    HIDDEN's dtype with float32 accumulation/output.

    The one dtype rule for every logits site (train loss, chunked CE,
    decode sampling): on the bf16 perf path the step's most expensive
    matmul keeps full MXU rate (f32 inputs run the systolic array at ~1/4
    speed on v5e) while the f32 output preserves logsumexp/sampling
    stability; on f32 paths it is bit-identical to an f32 matmul.

    An int8-quantized ``head_w`` dict (serving path) dispatches to the
    dequant-in-register kernel; its accumulator is already f32, so the
    float32-clean logits contract holds unchanged.
    """
    with jax.named_scope("lm_head"):
        if isinstance(head_w, dict):
            from bpe_transformer_tpu.ops.quant import quant_linear

            return quant_linear(hidden, head_w, preserve_f32=True)
        return jax.lax.dot_general(
            hidden, head_w.astype(hidden.dtype),
            (((hidden.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def embedding(weight: Array, token_ids: Array) -> Array:
    """Row gather from ``(vocab_size, d_model)``."""
    return jnp.take(weight, token_ids, axis=0)


def rmsnorm(x: Array, weight: Array, eps: float) -> Array:
    """Root-mean-square norm with affine scale; accumulates in float32.
    ``eps`` is the caller's to give: a model's is `ModelConfig.norm_eps`."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype) * weight


def layernorm(x: Array, weight: Array, eps: float) -> Array:
    """Mean-subtracting layer norm with affine scale and no bias;
    accumulates in float32."""
    x32 = x.astype(jnp.float32)
    centered = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(centered * centered, axis=-1, keepdims=True) + eps)
    return (centered * scale).astype(x.dtype) * weight


def window_causal_mask(seq_len: int, window: int) -> Array:
    """``(seq, seq)`` keep-mask: key j visible to query i iff
    ``0 <= i - j < window``."""
    i = jnp.arange(seq_len)[:, None]
    j = jnp.arange(seq_len)[None, :]
    return (j <= i) & (i - j < window)


def relu2(x: Array) -> Array:
    """``relu(x)^2``, the squared ReLU."""
    return jnp.square(jax.nn.relu(x))


def silu(x: Array) -> Array:
    """``x * sigmoid(x)``."""
    return x * jax.nn.sigmoid(x)


def swiglu(x: Array, w1: Array, w2: Array, w3: Array) -> Array:
    """SwiGLU FFN: ``w2(silu(w1 x) * (w3 x))``.

    ``w1, w3: (d_ff, d_model)``, ``w2: (d_model, d_ff)``.
    """
    return linear(silu(linear(x, w1)) * linear(x, w3), w2)


def softmax(x: Array, axis: int = -1) -> Array:
    """Shift-stabilized softmax along ``axis``; float32 accumulation."""
    x32 = x.astype(jnp.float32)
    shifted = x32 - jax.lax.stop_gradient(x32.max(axis=axis, keepdims=True))
    exp = jnp.exp(shifted)
    return (exp / exp.sum(axis=axis, keepdims=True)).astype(x.dtype)


def scaled_dot_product_attention(
    q: Array,
    k: Array,
    v: Array,
    mask: Array | None = None,
) -> Array:
    """Attention over the last two axes; boolean ``mask`` keeps True entries.

    Shapes: ``q (..., Sq, d)``, ``k (..., Sk, d)``, ``v (..., Sk, dv)``,
    ``mask (..., Sq, Sk)`` broadcastable.
    """
    d_k = q.shape[-1]
    scores = jnp.einsum("...qd,...kd->...qk", q, k) / jnp.sqrt(
        jnp.asarray(d_k, dtype=q.dtype)
    )
    if mask is not None:
        scores = jnp.where(mask, scores, MASK_VALUE)
    weights = softmax(scores, axis=-1)
    return jnp.einsum("...qk,...kv->...qv", weights, v)


def causal_mask(seq_len: int, dtype=bool) -> Array:
    """Lower-triangular ``(seq, seq)`` keep-mask."""
    return jnp.tril(jnp.ones((seq_len, seq_len), dtype=dtype))


def attention_entropy(q: Array, k: Array, causal: bool = True) -> Array:
    """Mean Shannon entropy (nats) of the softmax attention distribution.

    ``q (..., Sq, d)``, ``k (..., Sk, d)`` — the same tensors an
    ``attention_fn`` receives; scores/log-softmax accumulate in float32.
    Averaged over every leading axis and query position: ~0 means the
    heads collapsed onto single keys, ~log(Sk) means uniform (no learned
    structure).  The telemetry dynamics tap (`telemetry.dynamics`) calls
    this on a batch slice — it re-materializes the (Sq, Sk) score matrix,
    which fused attention kernels exist to avoid.
    """
    q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
    scores = jnp.einsum("...qd,...kd->...qk", q32, k32) / jnp.sqrt(
        jnp.asarray(q.shape[-1], jnp.float32)
    )
    if causal:
        scores = jnp.where(
            causal_mask(scores.shape[-1])[: scores.shape[-2]], scores, MASK_VALUE
        )
    logp = jax.nn.log_softmax(scores, axis=-1)
    # exp(logp) is exactly 0 at masked entries, so p * logp contributes -0.0
    # there (never NaN).
    return -jnp.mean(jnp.sum(jnp.exp(logp) * logp, axis=-1))


def split_heads(x: Array, num_heads: int) -> Array:
    """``(..., S, H*dh) -> (..., H, S, dh)`` with head-major row layout.

    Matches the reference weight convention where projection rows are the
    concatenation of per-head blocks (`adapters.py:237-251`).
    """
    *batch, seq, dm = x.shape
    x = x.reshape(*batch, seq, num_heads, dm // num_heads)
    return jnp.moveaxis(x, -2, -3)


def merge_heads(x: Array) -> Array:
    """``(..., H, S, dh) -> (..., S, H*dh)``."""
    x = jnp.moveaxis(x, -3, -2)
    *batch, seq, h, dh = x.shape
    return x.reshape(*batch, seq, h * dh)


def multihead_self_attention(
    x: Array,
    q_w: Array,
    k_w: Array,
    v_w: Array,
    o_w: Array,
    num_heads: int,
    *,
    num_kv_heads: int | None = None,
    positions: Array | None = None,
    rope_theta: float | None = None,
    max_seq_len: int | None = None,
    rope_cos_sin: tuple[Array, Array] | None = None,
    causal: bool = True,
    attention_fn=None,
) -> Array:
    """Causal multi-head self-attention, optionally with RoPE on Q/K.

    All four projections are single fused matmuls over the head-concat
    weight layout.  RoPE (when enabled) is applied per head at
    ``d_head = d_model // num_heads``.  ``attention_fn(q, k, v)`` swaps the
    materialized-scores attention for a fused kernel (e.g. Pallas flash
    attention); the callable owns its own (causal) masking.

    ``num_kv_heads < num_heads`` is grouped-query attention: K/V project to
    fewer heads (``k_w``/``v_w`` have ``num_kv_heads * d_head`` rows) and
    each KV head serves ``num_heads // num_kv_heads`` query heads — the
    projections and the KV cache shrink by that factor while scores/output
    math is unchanged (KV heads broadcast up before the attention call, so
    every ``attention_fn`` works untouched).
    """
    seq_len = x.shape[-2]
    kv_heads = num_kv_heads or num_heads
    q = split_heads(linear(x, q_w), num_heads)
    k = split_heads(linear(x, k_w), kv_heads)
    v = split_heads(linear(x, v_w), kv_heads)

    if rope_cos_sin is not None or rope_theta is not None:
        if positions is None:
            positions = jnp.arange(seq_len)
        if rope_cos_sin is None:
            d_head = q.shape[-1]
            rope_cos_sin = rope_tables(
                d_head, max_seq_len or seq_len, rope_theta, dtype=jnp.float32
            )
        cos, sin = rope_cos_sin
        # positions broadcast over the head axis: (..., S) -> (..., 1, S)
        pos = jnp.expand_dims(positions, axis=-2)
        q = apply_rope(q, pos, cos, sin)
        k = apply_rope(k, pos, cos, sin)

    if kv_heads != num_heads:
        group = num_heads // kv_heads
        k = jnp.repeat(k, group, axis=-3)
        v = jnp.repeat(v, group, axis=-3)

    if attention_fn is not None:
        attended = attention_fn(q, k, v)
    else:
        mask = causal_mask(seq_len) if causal else None
        attended = scaled_dot_product_attention(q, k, v, mask)
    return linear(merge_heads(attended), o_w)
