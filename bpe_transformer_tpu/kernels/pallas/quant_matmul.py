"""Int8-weight matmul with in-register dequantization.

The serving engines store matmul weights as ``{"q": int8 (d_out, d_in),
"scale": f32 (d_out,)}`` (`ops/quant.py`): 1 byte per value in HBM, one
f32 scale per output channel.  This kernel is the read path — the weight
twin of the PR 9 paged decode kernel's KV dequant: each grid step DMAs
one int8 row tile into VMEM, converts it to f32 **in registers**, runs
the dot at f32 accumulation, and applies the per-row scale to the tile's
output columns.  A dequantized copy of the weight never exists in HBM,
so the decode tick's weight stream is the int8 bytes — the ~2x-vs-bf16
cut the quantization exists for.

Because the scale is per OUTPUT channel the matmul factors exactly
(``y[.., o] = scale[o] * sum_i x[.., i] q[o, i]``), so dequantization is
one multiply per output element *after* the reduction — the MXU sees a
plain f32 dot over the converted tile.

Shapes: ``x (m, d_in)`` activations (any dtype; converted to f32 for the
accumulation), ``q (d_out, d_in)`` int8, ``scale (d_out,)`` f32; returns
``(m, d_out)`` **f32** (callers cast down; `head_logits` keeps the f32 —
logits stay float32-clean).  The grid tiles BOTH axes: ``d_out`` row
tiles (the weight stream) and ``m`` row tiles — the same dispatch serves
the 1-token decode tick (m = slots, one tile) and full prefill buckets
(m = bucket length), so the activation block must never assume
decode-sized m or a long bucket would blow the VMEM budget.  TPU note:
the d_out tile is the LANE axis of the output block, so it must be a
multiple of 128 (which also satisfies the int8 weight tile's 32-sublane
alignment) or span the whole axis.  A d_out with no such divisor (683,
1365, 2731, vocab 10,000) runs a ragged last tile — Pallas pads the edge block's reads and drops its
out-of-range writes, and every output column depends only on its own
weight row, so the padding never reaches a kept value.  Interpret mode
runs everywhere else (CPU tests), as with the sibling kernels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.kernels.pallas.runtime import pick_block

SUBLANES = 8
LANES = 128
#: d_out tile ceiling (a (512, d_in) int8 tile plus its f32 conversion
#: stays a few MB of VMEM at every shipped d_in).
BLOCK_N = 512


def _pick_block_n(n: int) -> int:
    """The d_out tile: the largest 128-multiple divisor up to
    :data:`BLOCK_N`; with none (683, 1365, 2731, vocab 10,000) a
    128-multiple tile with a ragged last one (see module docstring)."""
    return pick_block(n, BLOCK_N, LANES) or min(
        BLOCK_N, pl.cdiv(n, LANES) * LANES
    )


def _quant_matmul_kernel(x_ref, q_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (block_m, d_in)
    w = q_ref[...].astype(jnp.float32)          # (block_n, d_in) — in regs
    out = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                            # (block_m, block_n)
    # scale rides as (block_n, 1); transpose to broadcast over rows.
    o_ref[...] = out * s_ref[...].reshape(1, -1)


def quant_matmul(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ (q * scale[:, None]).T`` with the dequant in registers (see
    module docstring).  ``x`` may have any leading shape; returns f32
    ``(*leading, d_out)``."""
    if interpret is None:
        from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

        interpret = interpret_mode()
    *lead, d_in = x.shape
    n, d_in2 = q.shape
    if d_in2 != d_in or scale.shape != (n,):
        raise ValueError(
            f"shape mismatch: x {x.shape}, q {q.shape}, scale {scale.shape}"
        )
    m = 1
    for dim in lead:
        m *= dim
    x2 = x.reshape(m, d_in)
    m_pad = pl.cdiv(max(m, 1), SUBLANES) * SUBLANES
    if m_pad != m:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
    bn = block_n or _pick_block_n(n)
    if bn != n and bn % LANES:
        raise ValueError(
            f"block_n={bn} must be a multiple of {LANES} or the whole "
            f"d_out={n}: it is the lane axis of the output tile, and the "
            "TPU lowering refuses any other width"
        )
    # Tile m too: a full prefill bucket's activations must not ride VMEM
    # whole (m_pad is a SUBLANES multiple, so a divisor always exists).
    bm = pick_block(m_pad, target=256, step=SUBLANES)

    out = pl.pallas_call(
        _quant_matmul_kernel,
        grid=(m_pad // bm, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bm, d_in), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, d_in), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), jnp.float32),
        interpret=interpret,
        name="quant_matmul",
    )(x2, q, scale.reshape(n, 1))
    return out[:m].reshape(*lead, n)
