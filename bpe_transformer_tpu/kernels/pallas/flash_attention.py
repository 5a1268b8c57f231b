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
* the MXU takes q, k, v, dO — and the probabilities / dS of each pair's
  second matmul — in the dtype the caller passed, accumulating in float32
  (``preferred_element_type``); running max, denominator, logsumexp,
  ``delta`` and every accumulator stay float32.  That is the precision of
  the materialized XLA path (`ops/core.py`), whose scores and
  probabilities are the inputs' dtype too.
* blocks carry the head dim at its own width (a block whose last dimension
  is the array's own is legal), so d_head 64 is never padded to 128 lanes
  in HBM, and the row statistics travel as compact ``(bh, 1, S)`` rows.
* causal masking happens at block granularity: key blocks strictly above the
  diagonal are predicated off AND not fetched (their index map stays on the
  last block needed), blocks crossing the diagonal get the triangular mask,
  blocks below run unmasked.
* the tiles come from the shape (`runtime.flash_tiles`), as does the choice
  between this kernel and materialized scores (`runtime.attention_path`);
  `attention_plan` / `flash_attention_for_config` are what the model asks.
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

The backward pass is FlashAttention-2's recompute in ONE kernel: the forward
additionally emits the per-row logsumexp; the backward recomputes each score
tile once in VMEM, transposed (``S^T = K Q^T``, so the row statistics
broadcast along sublanes and four of the five matmuls need no transpose),
with the query axis innermost — dK/dV accumulate in VMEM scratch per key
block, dQ in a whole-sequence float32 scratch written when the head is done.
``delta = rowsum(dO * O)`` is one cheap
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

from bpe_transformer_tpu.kernels.pallas.runtime import (
    attention_path,
    flash_tiles,
    interpret_mode,
    pick_block,
)
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


#: Contraction patterns of the kernels' matmuls: ``A @ B``, ``A @ B.T``
#: and ``A.T @ B`` over 2-D tiles.
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _mxu(a, b, dims):
    """One MXU matmul: operands as given (the callers keep them in the
    inputs' own dtype), float32 accumulation."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _scaled(x, scale: float):
    """``x * scale`` rounded back to ``x``'s dtype (exact for the
    power-of-two scales of d_head 16/64/256)."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _crosses_diagonal(iq, ik, block_q: int, block_k: int):
    """Does tile (iq, ik) hold an entry with key index > query index?"""
    return ik * block_k + block_k - 1 > iq * block_q


def _below_or_on_diagonal(iq, ik, block_q: int, block_k: int):
    """Does tile (iq, ik) hold any entry the causal mask keeps?"""
    return ik * block_k <= iq * block_q + block_q - 1


def _run_tile(step, iq, ik, block_q: int, block_k: int, causal: bool):
    """Run ``step(masked)`` for tile (iq, ik): skipped above the causal
    diagonal, with the triangular mask only where the tile crosses it."""
    if not causal:
        step(False)
        return
    crosses = _crosses_diagonal(iq, ik, block_q, block_k)
    pl.when(crosses & _below_or_on_diagonal(iq, ik, block_q, block_k))(
        lambda: step(True)
    )
    pl.when(jnp.logical_not(crosses))(lambda: step(False))


def _compiler_params(semantics: tuple[str, ...], block_q: int, block_k: int):
    """Grid semantics plus a scoped-VMEM limit sized for the score tiles
    (the 16 MiB default is short of what 1,024-wide tiles keep live)."""
    tile_bytes = 4 * block_q * block_k
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(min(100 << 20, max(32 << 20, 10 * tile_bytes))),
    )


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
    acc_ref, m_ref, l_ref, qs_ref = refs
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        # The scaled (and, fused, rotated) query block is made once per
        # (batch*head, q-block) and reused across every key block.
        if rope_half:
            qs_ref[:] = _rotate_half_layout(
                q_ref[0].astype(jnp.float32) * scale,
                cq_ref[:].astype(jnp.float32),
                sq_ref[:].astype(jnp.float32),
                rope_half,
            ).astype(qs_ref.dtype)
        else:
            qs_ref[:] = _scaled(q_ref[0], scale)

    def _step(masked: bool):
        if rope_half:
            k = _rotate_half_layout(
                k_ref[0].astype(jnp.float32),
                ck_ref[:].astype(jnp.float32),
                sk_ref[:].astype(jnp.float32),
                rope_half,
            ).astype(k_ref.dtype)
        else:
            k = k_ref[0]
        v = v_ref[0]
        s = _mxu(qs_ref[:], k, _NT)  # (block_q, block_k), float32
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + iq * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ik * block_k
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + _mxu(p.astype(v.dtype), v, _NN)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    _run_tile(_step, iq, ik, block_q, block_k, causal)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:], 1e-30)  # fully-masked rows -> 0
        o_ref[0] = (acc_ref[:] / denom[:, 0:1]).astype(o_ref.dtype)
        if with_lse:
            # Per-row logsumexp for the FA-2 backward, stored as a ROW
            # (1, block_q): the statistics travel compact through HBM and
            # the backward kernel broadcasts them along sublanes.  Under
            # the causal mask every row sees at least its diagonal, so
            # l > 0 and the value is finite (padded rows included).
            lse = m_ref[:] + jnp.log(denom)  # lane-broadcast (block_q, LANES)
            lse_ref[0] = jnp.transpose(lse)[0:1, :]


def _xla_attention(q, k, v, causal: bool):
    """Materialized-scores oracle (parity tests + the recompute backward):
    ops.core attention with float32 accumulation."""
    mask = causal_mask(q.shape[-2]) if causal else None
    out = scaled_dot_product_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), mask
    )
    return out.astype(q.dtype)


def _tiling(s: int, block_q: int, block_k: int, causal: bool):
    """``(block_q, block_k, s_pad)``: the tiles clamped to the sequence and
    the length padded so BOTH divide it (or the grid would skip trailing
    query/key blocks and return garbage rows)."""
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    block = math.lcm(block_q, block_k)
    s_pad = pl.cdiv(s, block) * block
    if s_pad != s and not causal:
        raise ValueError(
            f"non-causal flash attention requires seq ({s}) divisible by the "
            f"block size ({block})"
        )
    return block_q, block_k, s_pad


def _flatten(x, bh: int, s_pad: int):
    """``(..., s, d) -> (bh, s_pad, d)``; the head dim keeps its own width
    (a block whose last dimension is the array's own is legal, so d_head 64
    is NOT padded to 128 lanes in HBM)."""
    *_, s, d = x.shape
    x = x.reshape(bh, s, d)
    if s_pad != s:
        x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))
    return x


# The two launchers below are jitted so that a model's layers share ONE
# traced kernel and ONE lowered Mosaic module each: inside an outer trace
# every further call at the same shapes is a cache hit, where a bare
# pallas_call would trace and lower its kernel again (0.12 s a call: 3 s of
# every process start of the 12-layer step, compile cache or not).


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "return_lse"),
)
def _flash_impl(
    q, k, v, causal, block_q, block_k, interpret, cos=None, sin=None,
    return_lse=False,
):
    *batch, s, d = q.shape
    bh = math.prod(batch)
    rope = cos is not None
    if rope and (cos.shape != (s, d // 2) or sin.shape != (s, d // 2)):
        raise ValueError(
            f"cos/sin must be position-gathered to shape (seq, d//2) = "
            f"{(s, d // 2)}, got {cos.shape} / {sin.shape}; select rows from "
            "rope_tables(...) by token position before calling"
        )
    block_q, block_k, s_pad = _tiling(s, block_q, block_k, causal)

    if rope:
        # Scores are invariant to a fixed feature permutation applied to both
        # Q and K: move from the interleaved pair convention to a half-split
        # layout so the in-kernel rotation needs no strided access.
        to_half = lambda x: jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        q, k = to_half(q), to_half(k)
        # Full-width tiles [cos|cos] / [sin|sin], padded to (s_pad, d).
        tile = lambda t: jnp.pad(
            jnp.concatenate([t, t], axis=-1).astype(jnp.float32),
            ((0, s_pad - s), (0, 0)),
        )
        ctile, stile = tile(cos), tile(sin)

    qp, kp, vp = (_flatten(x, bh, s_pad) for x in (q, k, v))
    nq = s_pad // block_q
    nk = s_pad // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=1.0 / (d**0.5),
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        num_k_blocks=nk,
        rope_half=(d // 2) if rope else 0,
        with_lse=return_lse,
    )
    if causal:
        # Above the diagonal the key index stays on the last block the query
        # block needs, so a skipped grid step fetches nothing.
        k_index = lambda b, i, j: (
            b, jnp.minimum(j, (i * block_q + block_q - 1) // block_k), 0
        )
    else:
        k_index = lambda b, i, j: (b, j, 0)
    q_index = lambda b, i, j: (b, i, 0)
    qspec = pl.BlockSpec((1, block_q, d), q_index, memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, block_k, d), k_index, memory_space=pltpu.VMEM)
    in_specs = [qspec, kspec, kspec]
    operands = [qp, kp, vp]
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        pltpu.VMEM((block_q, LANES), jnp.float32),  # running row max
        pltpu.VMEM((block_q, LANES), jnp.float32),  # running denominator
        pltpu.VMEM((block_q, d), q.dtype),  # scaled (rotated) Q block
    ]
    if rope:
        tile_q = pl.BlockSpec((block_q, d), lambda b, i, j: (i, 0), memory_space=pltpu.VMEM)
        tile_k = pl.BlockSpec(
            (block_k, d), lambda b, i, j: k_index(b, i, j)[1:], memory_space=pltpu.VMEM
        )
        in_specs += [tile_q, tile_q, tile_k, tile_k]
        operands += [ctile, stile, ctile, stile]

    out_shape = jax.ShapeDtypeStruct(qp.shape, qp.dtype)
    out_spec = qspec
    if return_lse:
        out_shape = (out_shape, jax.ShapeDtypeStruct((bh, 1, s_pad), jnp.float32))
        out_spec = (
            out_spec,
            pl.BlockSpec(
                (1, 1, block_q), lambda b, i, j: (b, 0, i), memory_space=pltpu.VMEM
            ),
        )

    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"), block_q, block_k
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*operands)

    if return_lse:
        out, lse = out
        return out[:, :s].reshape(*batch, s, d), lse[:, 0]
    return out[:, :s].reshape(*batch, s, d)


# ------------------------------------------------- FlashAttention-2 backward


def _flash_bwd_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
    *, scale, block_q, block_k, causal, num_q_blocks, num_k_blocks,
):
    """dQ, dK and dV of one (batch*head) in one pass over its score tiles.

    Grid ``(batch*heads, S/block_k, S/block_q)``, query axis innermost.
    Every tile is recomputed ONCE, transposed — ``S^T = K Q^T`` of shape
    (block_k, block_q) — so four of the five matmuls are plain ``A @ B`` /
    ``A @ B.T`` and the row statistics (logsumexp, delta) broadcast along
    sublanes from their compact (1, block_q) rows.  dK/dV accumulate per
    key block; dQ accumulates in a whole-sequence float32 scratch (S x d:
    256 KiB at S=1,024) and is written when the head's last tile is done.
    """
    j = pl.program_id(1)  # key block
    i = pl.program_id(2)  # query block (innermost)

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _init_dkdv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _step(masked: bool):
        k, do, v = k_ref[0], do_ref[0], v_ref[0]
        # The scores the forward took its logsumexp from: the scaled q,
        # rounded to the operands' dtype as there (not k * scale, which
        # rounds differently when the scale is no power of two: d_head 128).
        qs = _scaled(q_ref[0], scale)
        s_t = _mxu(k, qs, _NT)  # (block_k, block_q), float32
        if masked:
            keys = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0) + j * block_k
            queries = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1) + i * block_q
            s_t = jnp.where(queries >= keys, s_t, NEG_INF)
        # exp(NEG_INF - lse) underflows to exactly 0, so masked entries drop out.
        p_t = jnp.exp(s_t - lse_ref[0])
        # dV += P^T dO ; dS = P * (dO V^T - delta) ; dK += dS^T (Q * scale) ;
        # dQ += dS K, scaled when the head is done
        dv_acc[:] += _mxu(p_t.astype(do.dtype), do, _NN)
        dp_t = _mxu(v, do, _NT)
        ds_t = (p_t * (dp_t - delta_ref[0])).astype(qs.dtype)
        dk_acc[:] += _mxu(ds_t, qs, _NN)
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        dq_acc[rows, :] += _mxu(ds_t, k, _TN)

    _run_tile(_step, i, j, block_q, block_k, causal)

    @pl.when(i == num_q_blocks - 1)
    def _finalize_dkdv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((j == num_k_blocks - 1) & (i == num_q_blocks - 1))
    def _finalize_dq():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


#: Widest backward tile.  The one-pass backward keeps S^T, P^T, dP^T and
#: dS^T tiles live at once; on the v5e 512 x 512 beat 1,024 x 1,024 there
#: (3.5 ms a layer against 4.1 at gpt2-small-32k's shape) while the forward
#: wants the larger tile.
BWD_MAX_TILE = 512


def _bwd_tiles(block_q: int, block_k: int) -> tuple[int, int]:
    """The backward's tiles for a forward run at ``(block_q, block_k)``: a
    tile over :data:`BWD_MAX_TILE` becomes its largest lane-aligned divisor
    under it (1,024 -> 512, 768 -> 384), or stays whole where that would
    fall under 256 (896 has only 128): a divisor keeps dividing the
    forward's padded length."""
    def cap(block: int) -> int:
        if block <= BWD_MAX_TILE:
            return block
        smaller = pick_block(block, BWD_MAX_TILE, LANES)
        return smaller if smaller >= 256 else block

    return cap(block_q), cap(block_k)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def _flash_bwd_impl(q, k, v, out, lse, g, causal, block_q, block_k, interpret):
    """Blockwise dQ/dK/dV: one pallas_call, no S^2 materialization.

    ``lse`` is the forward's per-row logsumexp, shape ``(batch*heads,
    s_pad)`` in the padded sequence length of the forward's tiles.
    """
    *batch, s, d = q.shape
    bh = math.prod(batch)
    s_pad = _tiling(s, block_q, block_k, causal)[2]
    block_q, block_k = _bwd_tiles(min(block_q, s), min(block_k, s))
    nq = s_pad // block_q
    nk = s_pad // block_k

    qp, kp, vp, dop = (_flatten(x, bh, s_pad) for x in (q, k, v, g))
    # delta = rowsum(dO * O): one elementwise pass, O(S*d).  Padded rows have
    # dO = 0, so their delta is 0 and their dS vanishes.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(bh, 1, s)
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, s_pad - s)))
    lse = lse.reshape(bh, 1, s_pad)

    if causal:
        # Key block j needs query blocks from (j * block_k) // block_q on;
        # before that the query index stays put, so a skipped step fetches
        # nothing.
        q_block = lambda j, i: jnp.maximum(i, (j * block_k) // block_q)
    else:
        q_block = lambda j, i: i
    by_q = lambda b, j, i: (b, q_block(j, i), 0)
    by_k = lambda b, j, i: (b, j, 0)
    stat = lambda b, j, i: (b, 0, q_block(j, i))
    qspec = pl.BlockSpec((1, block_q, d), by_q, memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, block_k, d), by_k, memory_space=pltpu.VMEM)
    rowspec = pl.BlockSpec((1, 1, block_q), stat, memory_space=pltpu.VMEM)
    whole = pl.BlockSpec((1, s_pad, d), lambda b, j, i: (b, 0, 0), memory_space=pltpu.VMEM)

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel,
            scale=1.0 / (d**0.5), block_q=block_q, block_k=block_k,
            causal=causal, num_q_blocks=nq, num_k_blocks=nk,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct(kp.shape, k.dtype),
            jax.ShapeDtypeStruct(vp.shape, v.dtype),
        ),
        grid=(bh, nk, nq),
        in_specs=[qspec, qspec, rowspec, rowspec, kspec, kspec],
        out_specs=(whole, kspec, kspec),
        scratch_shapes=[
            pltpu.VMEM((s_pad, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary"), block_q, block_k
        ),
        interpret=interpret,
        name="flash_attention_bwd",
    )(qp, dop, lse, delta, kp, vp)

    unpad = lambda x: x[:, :s].reshape(*batch, s, d)
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
    size internally (sound under ``causal=True``); head_dim reaches the
    kernels at its own width.
    """
    return _flash_impl(q, k, v, causal, block_q, block_k, interpret)


def attention_plan(config, seq_len: int) -> tuple[str, tuple[int, int]]:
    """``(path, (block_q, block_k))`` of causal self-attention for this
    config at ``seq_len``: ``config.attention_impl`` forces ``"xla"`` or the
    flash kernel (``"flash"``/``"flash_fused"``), ``"auto"`` asks
    :func:`runtime.attention_path` — the shape decides.  The tiles are the
    ones :func:`flash_attention_for_config` runs, whatever the path."""
    if config.attention_impl == "auto":
        path = attention_path(seq_len, config.d_head)
    else:
        path = "xla" if config.attention_impl == "xla" else "flash"
    return path, flash_tiles(seq_len)


def flash_attention_for_config(q, k, v, config, *, causal: bool = True) -> jax.Array:
    """Config-driven plain-flash dispatch: tiles from the operands' shape
    (:func:`runtime.flash_tiles`), interpret mode from the backend.  The ONE
    call shared by the training attention (`models/transformer.py`), the
    decode prefill (`models/decode.py`), and future sites — so the call
    signature and interpret-mode policy can't drift between copies."""
    block_q, block_k = flash_tiles(q.shape[-2])
    return flash_attention(q, k, v, causal, block_q, block_k, interpret_mode())


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
