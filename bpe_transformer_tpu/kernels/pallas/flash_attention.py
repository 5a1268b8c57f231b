"""Flash attention: blockwise online-softmax attention as a Pallas kernel,
optionally with RoPE fused into the Q/K block loads.

TPU-native replacement for materialized S^2 attention (the reference's spec
M7, `/root/reference/tests/adapters.py:92-110`, materializes the full score
matrix; BASELINE.json config 4 demands a fused RoPE+attention kernel at seq
1k/4k/16k).

Kernel structure (classic FlashAttention on the MXU):

* grid ``(batch*heads, S/block_q, S/block_k)`` — the key axis iterates
  fastest; VMEM scratch (f32 accumulator + running max/denominator) persists
  across the key axis so each query block is normalized online, never
  materializing more than a ``(block_q, block_k)`` score tile.
* causal masking happens at block granularity: key blocks strictly above the
  diagonal are predicated off, the diagonal block gets the triangular mask,
  blocks below run unmasked.
* sequence padding to the block size is sound under causal masking (padded
  keys sit above every valid query's diagonal) and padded query rows are
  sliced off on the way out.
* RoPE fusion: Q and K are pre-permuted on the host side from the
  interleaved pair convention ``(x0, x1, x2, x3, ...)`` to a half-split
  layout ``(x0, x2, ... | x1, x3, ...)``.  Attention scores are invariant
  under any fixed permutation of the head dim applied to both Q and K, so
  in-kernel rotation becomes two dense multiply-adds against full-width
  cos/sin tiles (``rot = x * C + swap(x) * S``) with no strided access —
  the rotated Q/K never round-trip through HBM.

The backward pass is the standard FlashAttention-2 split: the forward
additionally emits the per-row logsumexp; the backward recomputes score
tiles in VMEM (never materializing S^2) in two kernels — dK/dV with the
query axis innermost (accumulators live in VMEM scratch per key block) and
dQ with the key axis innermost.  ``delta = rowsum(dO * O)`` is one cheap
elementwise XLA pass.  For the RoPE-fused variant the backward applies the
(orthogonal) rotation to Q/K outside the kernel — elementwise, O(S*d) — and
un-rotates dQ/dK with the transposed rotation, so the O(S^2) part still
never touches HBM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.ops.core import MASK_VALUE as NEG_INF
from bpe_transformer_tpu.ops.core import causal_mask, scaled_dot_product_attention

LANES = 128


def _rotate_half_layout(x, c, s, half: int):
    """RoPE rotation for inputs in the half-split feature layout.

    ``c``/``s`` are full-width cos/sin tiles ``[cos|cos|0]`` / ``[sin|sin|0]``
    so the rotation is ``x * c + swap(x) * s`` with ``swap = [-x2 | x1 | 0]``
    — two dense FMAs, no strided lane access.
    """
    x1 = x[:, :half]
    x2 = x[:, half : 2 * half]
    tail = x[:, 2 * half :]
    swapped = jnp.concatenate([-x2, x1, tail], axis=-1)
    return x * c + swapped * s


def _flash_kernel(
    *refs,
    scale: float, block_q: int, block_k: int, causal: bool, num_k_blocks: int,
    rope_half: int, with_lse: bool,
):
    refs = list(refs)
    if rope_half:
        q_ref, k_ref, v_ref, cq_ref, sq_ref, ck_ref, sk_ref = refs[:7]
        del refs[:7]
    else:
        q_ref, k_ref, v_ref = refs[:3]
        del refs[:3]
    o_ref = refs.pop(0)
    lse_ref = refs.pop(0) if with_lse else None
    acc_ref, m_ref, l_ref = refs[:3]
    qrot_ref = refs[3] if rope_half else None
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        if rope_half:
            # Rotate the query block once per (batch*head, q-block); it is
            # reused across every key block from VMEM scratch.
            qrot_ref[:] = _rotate_half_layout(
                q_ref[0].astype(jnp.float32) * scale,
                cq_ref[:].astype(jnp.float32),
                sq_ref[:].astype(jnp.float32),
                rope_half,
            )

    # Key blocks entirely above the causal diagonal contribute nothing.
    compute = (block_k * ik) <= (block_q * iq + block_q - 1) if causal else True

    @pl.when(compute)
    def _block():
        if rope_half:
            q = qrot_ref[:]
            k = _rotate_half_layout(
                k_ref[0].astype(jnp.float32),
                ck_ref[:].astype(jnp.float32),
                sk_ref[:].astype(jnp.float32),
                rope_half,
            )
        else:
            q = q_ref[0].astype(jnp.float32) * scale
            k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)

        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + iq * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ik * block_k
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0:1], 1e-30)  # fully-masked rows -> 0
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        if with_lse:
            # Per-row logsumexp for the FA-2 backward.  Under the causal
            # mask every row sees at least its diagonal, so l > 0 and the
            # value is finite (padded rows included).
            lse = m_ref[:, 0:1] + jnp.log(denom)
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _xla_attention(q, k, v, causal: bool):
    """Materialized-scores oracle (parity tests + the recompute backward):
    ops.core attention with float32 accumulation."""
    mask = causal_mask(q.shape[-2]) if causal else None
    out = scaled_dot_product_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), mask
    )
    return out.astype(q.dtype)


def _flash_impl(
    q, k, v, causal, block_q, block_k, interpret, cos=None, sin=None,
    return_lse=False,
):
    *batch, s, d = q.shape
    bh = 1
    for dim in batch:
        bh *= dim
    rope = cos is not None
    if rope and (cos.shape != (s, d // 2) or sin.shape != (s, d // 2)):
        raise ValueError(
            f"cos/sin must be position-gathered to shape (seq, d//2) = "
            f"{(s, d // 2)}, got {cos.shape} / {sin.shape}; select rows from "
            "rope_tables(...) by token position before calling"
        )

    block_q = min(block_q, s)
    block_k = min(block_k, s)
    # Pad so BOTH block sizes divide the padded length, or the grid would
    # skip trailing query/key blocks and return garbage rows.
    block = math.lcm(block_q, block_k)
    s_pad = pl.cdiv(s, block) * block
    if s_pad != s and not causal:
        raise ValueError(
            f"non-causal flash attention requires seq ({s}) divisible by the "
            f"block size ({block})"
        )
    d_pad = pl.cdiv(d, LANES) * LANES

    def prep(x):
        x = x.reshape(bh, s, d)
        return jnp.pad(x, ((0, 0), (0, s_pad - s), (0, d_pad - d)))

    if rope:
        half = d // 2
        # Scores are invariant to a fixed feature permutation applied to both
        # Q and K: move from the interleaved pair convention to a half-split
        # layout so the in-kernel rotation needs no strided access.
        to_half = lambda x: jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        q, k = to_half(q), to_half(k)
        # Full-width tiles [cos|cos|0] / [sin|sin|0], padded to (s_pad, d_pad).
        ctile = jnp.pad(
            jnp.concatenate([cos, cos], axis=-1).astype(jnp.float32),
            ((0, s_pad - s), (0, d_pad - d)),
        )
        stile = jnp.pad(
            jnp.concatenate([sin, sin], axis=-1).astype(jnp.float32),
            ((0, s_pad - s), (0, d_pad - d)),
        )

    qp, kp, vp = prep(q), prep(k), prep(v)
    nq = s_pad // block_q
    nk = s_pad // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=1.0 / (d**0.5),  # true head dim, not the lane-padded one
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        num_k_blocks=nk,
        rope_half=(d // 2) if rope else 0,
        with_lse=return_lse,
    )
    qspec = pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM)
    in_specs = [qspec, kspec, kspec]
    operands = [qp, kp, vp]
    scratch = [
        pltpu.VMEM((block_q, d_pad), jnp.float32),  # output accumulator
        pltpu.VMEM((block_q, LANES), jnp.float32),  # running row max
        pltpu.VMEM((block_q, LANES), jnp.float32),  # running denominator
    ]
    if rope:
        tile_q = pl.BlockSpec((block_q, d_pad), lambda b, i, j: (i, 0), memory_space=pltpu.VMEM)
        tile_k = pl.BlockSpec((block_k, d_pad), lambda b, i, j: (j, 0), memory_space=pltpu.VMEM)
        in_specs += [tile_q, tile_q, tile_k, tile_k]
        operands += [ctile, stile, ctile, stile]
        scratch.append(pltpu.VMEM((block_q, d_pad), jnp.float32))  # rotated Q

    out_shape = jax.ShapeDtypeStruct(qp.shape, qp.dtype)
    out_spec = pl.BlockSpec(
        (1, block_q, d_pad), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM
    )
    if return_lse:
        # lse is written lane-broadcast (LANES copies per row) so both the
        # forward store and the backward loads stay plain (8,128)-tiled
        # VMEM traffic — same layout trick as the m/l scratch above.
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((bh, s_pad, LANES), jnp.float32),
        )
        out_spec = (
            out_spec,
            pl.BlockSpec(
                (1, block_q, LANES), lambda b, i, j: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
        )

    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attention_fwd",
    )(*operands)

    if return_lse:
        out, lse = out
        return out[:, :s, :d].reshape(*batch, s, d), lse[:, :, 0]
    return out[:, :s, :d].reshape(*batch, s, d)


# ------------------------------------------------- FlashAttention-2 backward


def _bwd_score_block(q_ref, k_ref, lse_ref, scale, block_q, block_k, causal, i, j):
    """Recompute one (block_q, block_k) probability tile from VMEM refs."""
    qs = q_ref[0].astype(jnp.float32) * scale
    kb = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        qs, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
        s = jnp.where(rows >= cols, s, NEG_INF)
    # exp(NEG_INF - lse) underflows to exactly 0, so masked entries drop out.
    p = jnp.exp(s - lse_ref[0][:, 0:1])
    return qs, p


def _flash_bwd_dkdv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
    dk_ref, dv_ref, dk_acc, dv_acc,
    *, scale, block_q, block_k, causal, num_q_blocks,
):
    """Grid (batch*heads, S/block_k, S/block_q): the query axis iterates
    fastest; dK/dV accumulate in VMEM scratch per key block."""
    j = pl.program_id(1)  # key block
    i = pl.program_id(2)  # query block (innermost)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    compute = (block_k * j) <= (block_q * i + block_q - 1) if causal else True

    @pl.when(compute)
    def _block():
        qs, p = _bwd_score_block(
            q_ref, k_ref, lse_ref, scale, block_q, block_k, causal, i, j
        )
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        # dV += P^T dO ; dS = P * (dO V^T - delta) ; dK += dS^T (Q * scale)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0][:, 0:1])
        dk_acc[:] += jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
    dq_ref, dq_acc,
    *, scale, block_q, block_k, causal, num_k_blocks,
):
    """Grid (batch*heads, S/block_q, S/block_k): the key axis iterates
    fastest; dQ accumulates in VMEM scratch per query block."""
    i = pl.program_id(1)  # query block
    j = pl.program_id(2)  # key block (innermost)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    compute = (block_k * j) <= (block_q * i + block_q - 1) if causal else True

    @pl.when(compute)
    def _block():
        _, p = _bwd_score_block(
            q_ref, k_ref, lse_ref, scale, block_q, block_k, causal, i, j
        )
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0][:, 0:1])
        dq_acc[:] += jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        # S = (Q * scale) K^T, so dQ picks up the remaining scale factor.
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_impl(q, k, v, out, lse, g, causal, block_q, block_k, interpret):
    """Blockwise dQ/dK/dV: two pallas_calls, no S^2 materialization.

    ``lse`` is the forward's per-row logsumexp, shape ``(batch*heads,
    s_pad)`` in the padded sequence length.
    """
    *batch, s, d = q.shape
    bh = 1
    for dim in batch:
        bh *= dim

    block_q = min(block_q, s)
    block_k = min(block_k, s)
    block = math.lcm(block_q, block_k)
    s_pad = pl.cdiv(s, block) * block
    d_pad = pl.cdiv(d, LANES) * LANES
    nq = s_pad // block_q
    nk = s_pad // block_k
    scale = 1.0 / (d**0.5)

    def prep(x):
        x = x.reshape(bh, s, d)
        return jnp.pad(x, ((0, 0), (0, s_pad - s), (0, d_pad - d)))

    qp, kp, vp, dop, outp = prep(q), prep(k), prep(v), prep(g), prep(out)
    # delta = rowsum(dO * O): one elementwise pass, O(S*d).  Padded rows have
    # dO = 0, so their delta is 0 and their dS vanishes.
    delta = jnp.sum(dop.astype(jnp.float32) * outp.astype(jnp.float32), axis=-1)
    # Lane-broadcast the row statistics (see the forward's lse store).
    lane = lambda x: jnp.broadcast_to(x[:, :, None], (bh, s_pad, LANES))
    lse_b, delta_b = lane(lse), lane(delta)

    qspec = lambda im: pl.BlockSpec((1, block_q, d_pad), im, memory_space=pltpu.VMEM)
    kspec = lambda im: pl.BlockSpec((1, block_k, d_pad), im, memory_space=pltpu.VMEM)
    rowspec = lambda im: pl.BlockSpec((1, block_q, LANES), im, memory_space=pltpu.VMEM)

    # dK/dV: grid (bh, nk, nq), query axis innermost.
    by_q = lambda b, j, i: (b, i, 0)
    by_k = lambda b, j, i: (b, j, 0)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkdv_kernel,
            scale=scale, block_q=block_q, block_k=block_k, causal=causal,
            num_q_blocks=nq,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(kp.shape, k.dtype),
            jax.ShapeDtypeStruct(vp.shape, v.dtype),
        ),
        grid=(bh, nk, nq),
        in_specs=[qspec(by_q), qspec(by_q), rowspec(by_q), rowspec(by_q),
                  kspec(by_k), kspec(by_k)],
        out_specs=(kspec(by_k), kspec(by_k)),
        scratch_shapes=[
            pltpu.VMEM((block_k, d_pad), jnp.float32),
            pltpu.VMEM((block_k, d_pad), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkdv",
    )(qp, dop, lse_b, delta_b, kp, vp)

    # dQ: grid (bh, nq, nk), key axis innermost.
    by_q2 = lambda b, i, j: (b, i, 0)
    by_k2 = lambda b, i, j: (b, j, 0)
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel,
            scale=scale, block_q=block_q, block_k=block_k, causal=causal,
            num_k_blocks=nk,
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        grid=(bh, nq, nk),
        in_specs=[qspec(by_q2), qspec(by_q2), rowspec(by_q2), rowspec(by_q2),
                  kspec(by_k2), kspec(by_k2)],
        out_specs=qspec(by_q2),
        scratch_shapes=[pltpu.VMEM((block_q, d_pad), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qp, dop, lse_b, delta_b, kp, vp)

    unpad = lambda x: x[:, :s, :d].reshape(*batch, s, d)
    return unpad(dq), unpad(dk), unpad(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise attention over ``(..., seq, head_dim)`` inputs.

    Leading dims (batch, heads) are arbitrary; seq is padded to the block
    size internally (sound under ``causal=True``); head_dim is zero-padded
    to the 128-lane width and sliced back.
    """
    return _flash_impl(q, k, v, causal, block_q, block_k, interpret)


def flash_attention_for_config(q, k, v, config, *, causal: bool = True) -> jax.Array:
    """Config-driven plain-flash dispatch: block size from
    ``config.flash_block_size``, interpret mode from the backend.  The ONE
    call shared by the training attention (`models/transformer.py`), the
    decode prefill (`models/decode.py`), and future sites — so the call
    signature and interpret-mode policy can't drift between copies."""
    from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

    block = config.flash_block_size
    return flash_attention(q, k, v, causal, block, block, interpret_mode())


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_impl(
        q, k, v, causal, block_q, block_k, interpret, return_lse=True
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd_impl(
        q, k, v, out, lse, g, causal, block_q, block_k, interpret
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------- ring / context-parallel interface


def flash_attention_with_lse(
    q, k, v, causal, block_q, block_k, interpret
) -> tuple[jax.Array, jax.Array]:
    """Forward + per-row logsumexp ``(..., seq)`` — the statistic a
    ring/context-parallel caller needs to merge partial attention outputs
    across visiting K/V shards (log-sum-exp combine).  Forward only; the
    ring caller owns the custom VJP.
    """
    *batch, s, d = q.shape
    out, lse = _flash_impl(
        q, k, v, causal, block_q, block_k, interpret, return_lse=True
    )
    return out, lse[:, :s].reshape(*batch, s)


def flash_attention_block_bwd(
    q, k, v, out, lse, g, causal, block_q, block_k, interpret
):
    """Partial (dq, dk, dv) of ONE visiting K/V block, given the GLOBAL
    forward output and logsumexp.

    With ``lse``/``out`` computed over ALL keys, the recomputed block
    probabilities ``exp(s_blk - lse)`` are the true global attention
    weights of this block, so the returned grads are exactly this block's
    additive contributions (the standard ring-flash backward).  ``q`` and
    ``k``/``v`` must share the (square) shard length, divisible by the
    block sizes.
    """
    *batch, s, d = q.shape
    if s % math.lcm(min(block_q, s), min(block_k, s)):
        raise ValueError(
            f"block backward needs seq ({s}) divisible by the block sizes"
        )
    bh = 1
    for dim in batch:
        bh *= dim
    return _flash_bwd_impl(
        q, k, v, out, lse.reshape(bh, s), g, causal, block_q, block_k, interpret
    )


# ------------------------------------------------- fused RoPE + attention


def _xla_rope_attention(q, k, v, cos, sin, causal: bool):
    """XLA oracle for the fused kernel: interleaved-pair RoPE on Q/K, then
    materialized-scores attention (used for parity tests and the recompute
    backward)."""
    from bpe_transformer_tpu.ops.rope import apply_rope

    positions = jnp.arange(q.shape[-2])
    qr = apply_rope(q.astype(jnp.float32), positions, cos, sin)
    kr = apply_rope(k.astype(jnp.float32), positions, cos, sin)
    return _xla_attention(qr, kr, v.astype(jnp.float32), causal).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_attention_with_rope(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention with RoPE applied to Q/K inside the kernel.

    ``cos``/``sin`` are position-gathered tables of shape ``(seq, d//2)``
    (interleaved-pair convention, `ops.rope.rope_tables` rows selected by
    token position) — the rotated Q/K exist only in VMEM, saving one full
    read+write of Q and K through HBM versus rope-then-attention
    (BASELINE.json config 4: fused RoPE+attention at seq 1k/4k/16k).
    """
    return _flash_impl(q, k, v, causal, block_q, block_k, interpret, cos, sin)


def _flash_rope_fwd(q, k, v, cos, sin, causal, block_q, block_k, interpret):
    out, lse = _flash_impl(
        q, k, v, causal, block_q, block_k, interpret, cos, sin, return_lse=True
    )
    return out, (q, k, v, cos, sin, out, lse)


def _flash_rope_bwd(causal, block_q, block_k, interpret, residuals, g):
    """FA-2 backward through the rotation: RoPE is orthogonal per (position,
    pair), so rotate Q/K forward (elementwise, O(S*d)), run the blockwise
    backward on the rotated values — scores and lse are invariant to the
    layout permutation the forward kernel uses — then apply the transposed
    rotation (angle negated) to dQ/dK.  cos/sin grads are computed exactly
    from the elementwise rotation (they are non-trainable tables in the
    model, but the vjp stays honest)."""
    from bpe_transformer_tpu.ops.rope import apply_rope

    q, k, v, cos, sin, out, lse = residuals
    positions = jnp.arange(q.shape[-2])
    f32 = jnp.float32
    qr = apply_rope(q.astype(f32), positions, cos, sin).astype(q.dtype)
    kr = apply_rope(k.astype(f32), positions, cos, sin).astype(k.dtype)
    dqr, dkr, dv = _flash_bwd_impl(
        qr, kr, v, out, lse, g, causal, block_q, block_k, interpret
    )
    dq = apply_rope(dqr.astype(f32), positions, cos, -sin).astype(q.dtype)
    dk = apply_rope(dkr.astype(f32), positions, cos, -sin).astype(k.dtype)

    def table_grads(x, dxr):
        # x_rot_even = x_e*c - x_o*s ; x_rot_odd = x_e*s + x_o*c  (per pair)
        x, dxr = x.astype(f32), dxr.astype(f32)
        xe, xo = x[..., 0::2], x[..., 1::2]
        ge, go = dxr[..., 0::2], dxr[..., 1::2]
        bdims = tuple(range(x.ndim - 2))
        dcos = jnp.sum(ge * xe + go * xo, axis=bdims)
        dsin = jnp.sum(go * xe - ge * xo, axis=bdims)
        return dcos, dsin

    dcq, dsq = table_grads(q, dqr)
    dck, dsk = table_grads(k, dkr)
    return dq, dk, dv, (dcq + dck).astype(cos.dtype), (dsq + dsk).astype(sin.dtype)


flash_attention_with_rope.defvjp(_flash_rope_fwd, _flash_rope_bwd)
