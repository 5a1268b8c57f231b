"""Grouped (ragged) matmul over the experts a process holds.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies rows
``lhs[offset_e : offset_e + group_sizes[e]]`` by expert ``e``'s matrix for
each group ``e`` in turn: the rows of ``lhs`` are sorted by expert and
``rhs`` is the stack of the experts' weights, in one of two forms:

* ``transpose_rhs=True`` (the default): the torch layout ``(experts, d_out,
  d_in)``, as every parameter tree, checkpoint and reference holds a
  matrix; rows are multiplied by ``rhs[e].T``.  Every call over a raw tree
  takes it, and every call of a served tree whose contraction width is a
  whole number of 128-lane tiles.
* ``transpose_rhs=False``: ``(experts, d_in, d_out)``, rows multiplied by
  ``rhs[e]`` as it lies.  The served tree holds a down projection so where
  the experts' width is not a whole number of lane tiles (`relaid_rhs`,
  asked by `models/moe.serving_layout`; nemotron's 1,856 = 14.5 x 128):
  the chip rests a ``(experts, d_model, 1856)`` parameter with the 1,856
  second-minor, the transposed call wants it minor, and XLA would lay the
  whole stack out anew before the call, in every launch.

The same rows by the same weights in the same precision either way.  Rows
past ``sum(group_sizes)`` belong to no group; what the output holds there
is undefined and the caller never reads it.

On the TPU this is JAX's own Mosaic kernel, megablox ``gmm``
(``jax.experimental.pallas.ops.tpu.megablox``): a grid over row tiles that
visits only the tiles of non-empty groups, so a decode tick streams the
weights of the experts that got a row and no others, while a prefill chunk
gives the same experts full tiles.  Its device events are named after the
jitted wrapper, ``gmm.N`` (a call a matrix of an expert: three a layer of
SwiGLU experts, two around a squared ReLU); the program's scope around
them is ``block/moe/experts``.  Elsewhere (CPU tests) it is
``jax.lax.ragged_dot``, the same contract in XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.kernels.pallas.runtime import pick_block

#: Lanes of a vector register.  A parameter whose minor dimension is a whole
#: number of them rests on the chip as its shape says; one of 14.5 x 128 the
#: compiler rests with another dimension minor (see `relaid_rhs`).
LANES = 128

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


def relaid_rhs(d_in: int) -> bool:
    """Whether a served tree should hold a stack contracted over ``d_in`` as
    ``(experts, d_in, d_out)`` (``transpose_rhs=False``): where ``d_in`` is
    not a whole number of lane tiles - the width at which the chip rests
    the torch layout ``d_in``-second-minor already and the transposed call
    makes XLA copy the stack in every launch."""
    return d_in % LANES != 0


def grouped_matmul(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
    transpose_rhs: bool = True,
) -> jax.Array:
    """``lhs`` (rows, d_in) sorted by group, ``rhs`` (groups, d_out, d_in) -
    or (groups, d_in, d_out) with ``transpose_rhs=False`` - ``group_sizes``
    (groups,) int32 -> (rows, d_out) in ``lhs.dtype``."""
    m, k = lhs.shape
    n = rhs.shape[1 if transpose_rhs else 2]
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_tiling(m, k, n), transpose_rhs=transpose_rhs,
        )
    out = jax.lax.ragged_dot(
        lhs, jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs, group_sizes,
        preferred_element_type=jnp.float32,
    )
    return out.astype(lhs.dtype)
