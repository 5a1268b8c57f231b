"""Grouped (ragged) matmul over the experts a process holds.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies rows
``lhs[offset_e : offset_e + group_sizes[e]]`` by ``rhs[e].T`` for each group
``e`` in turn: the rows of ``lhs`` are sorted by expert and ``rhs`` is the
stack of torch-layout expert weights ``(experts, d_out, d_in)``.  Rows past
``sum(group_sizes)`` belong to no group; what the output holds there is
undefined and the caller never reads it.

On the TPU this is JAX's own Mosaic kernel, megablox ``gmm``
(``jax.experimental.pallas.ops.tpu.megablox``): a grid over row tiles that
visits only the tiles of non-empty groups, so a decode tick streams the
weights of the experts that got a row and no others, while a prefill chunk
gives the same experts full tiles.  Its device events are named after the
jitted wrapper, ``gmm.N`` (three calls a layer); the program's scope around
them is ``block/moe/experts``.  Elsewhere (CPU tests) it is
``jax.lax.ragged_dot``, the same contract in XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.kernels.pallas.runtime import pick_block

#: Row tile of the TPU kernel (a divisor of the row count is picked: 8
#: assignments a token make every served shape a multiple of it).
ROW_TILE = 128


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn): 128 rows, the whole contraction up to 2,048 and 512
    output columns - a 2 MB weight tile, double-buffered, inside the v5e's
    default scoped VMEM."""
    tm = pick_block(m, ROW_TILE, 8) or m
    tk = pick_block(k, 2048, 128) or k
    tn = pick_block(n, 512, 128) or n
    return tm, tk, tn


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``lhs`` (rows, d_in) sorted by group, ``rhs`` (groups, d_out, d_in),
    ``group_sizes`` (groups,) int32 -> (rows, d_out) in ``lhs.dtype``."""
    m, k = lhs.shape
    n = rhs.shape[1]
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_tiling(m, k, n), transpose_rhs=True,
        )
    out = jax.lax.ragged_dot(
        lhs, jnp.swapaxes(rhs, 1, 2), group_sizes,
        preferred_element_type=jnp.float32,
    )
    return out.astype(lhs.dtype)
