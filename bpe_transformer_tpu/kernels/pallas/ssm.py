"""One step of a state-space layer's recurrence for every slot of a tick:
``ssm_state_update``.

For each row ``s`` of the tick and head ``h``, with the state ``H`` (head
channels x state values, float32) of the slot the row belongs to::

    H' = exp(dt[s, h] * a[h]) * H + (dt[s, h] * x[s, h]) (x) b[s, g]
    y[s, h] = H' c[s, g] + d_skip[h] * x[s, h]

``b`` and ``c`` come by group: ``(rows, groups, state values)``, head ``h``
reading group ``g = h // (heads // groups)``; of one group they are ``(rows,
state values)``, shared by all heads.

**The resting layout** (this paragraph is its one description; the pool of
`models/decode.init_recurrent_pool` holds it, :func:`to_resting` and
:func:`from_resting` are its one definition).  A layer's states rest in one
array ``(slots + 1, heads / k, state values, k x head channels)``, a row a
slot and the last row trash: **the channels lie along the lanes**, ``k``
heads side by side in a lane row - ``k = 128 // channels`` where that
divides a group's heads, else 1 (:func:`heads_a_row`; both served
configurations: 64 channels, ``k = 2``, ``(slots + 1, heads / 2, 128,
128)``) - and the state values along the sublanes.  Element ``[s, r, n, j *
channels + p]`` is ``H[p, n]`` of head ``r * k + j``.  What varies a
channel (``x``) is then a lane row **as it lies** in ``(rows, heads x
channels)``, ``y`` leaves as such rows, and the sum over the state values is
a sum of vregs: a head row's step is loads, multiplies, adds and stores, with
one cross-lane step a (row, group) for each of ``b`` and ``c`` - their row
transposed to lie along the sublanes.  `models/ssm.py`'s scan and dense step
keep ``(batch, heads, channels, state values)``; a chunk's one slot passes
through the pair.

``ids`` (rows,) says which row of the array each tick row updates (an idle
or still-prefilling slot's row is sent to trash, so its state is not touched
at all).  The array is updated **in place**: on the TPU the Pallas kernel
takes it aliased to its output and reads and writes each addressed head's
state exactly once (its device events are named ``ssm_state_update``); the
states of rows nobody addresses never move.  Elsewhere (CPU tests) the same
contract over the same layout is a gather, the update and a scatter in XLA
(:func:`xla_ssm_state_update`).

The kernel walks ``(block of head rows, row)``: a block's states ``(head
rows a block, state values, lanes)`` come in as whole ``(8, 128)`` tiles, 2
MB in and 2 MB out a step - which is what sets its time: on the v5e such a
stream moves at 80% of the HBM's peak whatever the body, the blocks or the
buffering are (PERF.md section 6, PR 44).  Everything else is small and
comes as the layers around the call hold it, so XLA relays nothing out:
``x``, ``b``, ``c`` and ``y`` two-dimensional ``(rows, width)``, a tile of 8
tick rows at a time (the step's row is one sublane of it, picked by a mask);
the decay and ``dt`` - one number a (row, head) - as scalars that the kernel
spreads over a head's lanes itself.  Several groups: a block of head rows
holds whole groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.kernels.pallas.runtime import pick_block

#: Lanes of a vreg: what a row of heads fills.
LANES = 128
#: Head rows a grid step: 32 x (128, 128) float32 states are 2 MB, in and out
#: double-buffered 8 MB.
ROW_BLOCK = 32
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def heads_a_row(heads: int, channels: int, groups: int = 1) -> int:
    """``k``: the heads that lie side by side along the lanes."""
    k = max(LANES // channels, 1)
    return k if (heads // groups) % k == 0 else 1


def to_resting(state: jax.Array, groups: int = 1) -> jax.Array:
    """``(batch, heads, channels, state values)`` -> the resting layout
    ``(batch, heads / k, state values, k x channels)``."""
    batch, heads, channels, n = state.shape
    k = heads_a_row(heads, channels, groups)
    rows = state.reshape(batch, heads // k, k, channels, n)
    return rows.transpose(0, 1, 4, 2, 3).reshape(batch, heads // k, n, k * channels)


def from_resting(resting: jax.Array, channels: int) -> jax.Array:
    """The resting layout -> ``(batch, heads, channels, state values)``."""
    batch, head_rows, n, lanes = resting.shape
    k = lanes // channels
    rows = resting.reshape(batch, head_rows, n, k, channels)
    return rows.transpose(0, 1, 3, 4, 2).reshape(batch, head_rows * k, channels, n)


def xla_ssm_state_update(state, ids, x, dt, a, b, c, d_skip):
    """The contract in XLA: ``(y (rows, heads, channels) float32, state)``."""
    rows, heads, channels = x.shape
    head_rows = state.shape[1]
    x32 = x.astype(jnp.float32)
    # What varies along a head row's lanes: the decay, a head's value on its
    # channels' lanes, and dt * x as it lies.
    decay = jnp.broadcast_to(jnp.exp(dt * a)[:, :, None], x32.shape)
    decay, dtx = (v.reshape(rows, head_rows, -1) for v in (decay, dt[:, :, None] * x32))
    b, c = b.astype(jnp.float32), c.astype(jnp.float32)
    if b.ndim == 3:  # each head row its group's rows: (rows, head rows, state)
        b, c = (jnp.repeat(v, head_rows // v.shape[1], axis=1) for v in (b, c))
    else:
        b, c = b[:, None, :], c[:, None, :]
    new = state[ids] * decay[:, :, None, :] + b[..., None] * dtx[:, :, None, :]
    y = jnp.sum(new * c[..., None], axis=2).reshape(x.shape) + d_skip[None, :, None] * x32
    return y, state.at[ids].set(new)


def _kernel(
    ids_ref, decay_ref, dt_ref, x_ref, b_ref, c_ref, h_ref, y_ref, out_ref, *,
    head_rows, rows_a_group, k,
):
    del ids_ref  # the index maps' alone
    n, lanes = h_ref.shape[2:]
    j, s = pl.program_id(0), pl.program_id(1)
    tile = x_ref.shape[0]
    # x, b, c and y come a tile of tick rows at a time, as they lie around
    # the call; this step's row is one sublane of each.
    mine = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) == s % tile
    # The step's first head among the row's scalars (rows x heads, flat).
    first = (s * pl.num_programs(0) + j) * head_rows * k
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def my_row(ref, across):  # (1, width): the tile's other rows are zeroed and summed away
        return jnp.sum(jnp.where(mine, ref[:, across], 0.0), axis=0, keepdims=True)

    def lane_row(ref, r):
        # A head's scalar on its channels' lanes: splats and selects.
        row = jnp.full((1, lanes), ref[first + r * k], jnp.float32)
        for i in range(1, k):
            row = jnp.where(lane >= i * (lanes // k), ref[first + r * k + i], row)
        return row

    def column(ref, g):
        # A group's row laid along the sublanes, every lane equal: the one
        # cross-lane step, shared by the group's head rows.
        return jnp.broadcast_to(my_row(ref, slice(g * n, (g + 1) * n)), (lanes, n)).T

    for r in range(head_rows):
        if r % rows_a_group == 0:  # the block's next group
            b, c = column(b_ref, r // rows_a_group), column(c_ref, r // rows_a_group)
        across = slice(r * lanes, (r + 1) * lanes)
        dtx = lane_row(dt_ref, r) * my_row(x_ref, across)
        new = h_ref[0, r] * lane_row(decay_ref, r) + b * dtx
        out_ref[0, r] = new
        y = jnp.sum(new * c, axis=0, keepdims=True)
        y_ref[:, across] = jnp.where(mine, y, y_ref[:, across])


def _pallas_ssm_state_update(state, ids, x, dt, a, b, c, d_skip, interpret):
    rows, heads, channels = x.shape
    _, head_rows, n, lanes = state.shape
    k = heads // head_rows
    rb = pick_block(head_rows, ROW_BLOCK, 8) or head_rows
    if b.ndim == 2:  # one group: every block of head rows reads the row's b and c
        rows_a_group, in_block = rb, 1
    else:
        rows_a_group = head_rows // b.shape[1]
        if rb % rows_a_group or (rb // rows_a_group * n) % LANES:
            rb = head_rows  # whole groups a block, whole lane tiles: else one block
        in_block = rb // rows_a_group
    # What comes a tick row - x, b, c, and y - stays two-dimensional, (rows,
    # width) as the layers around the call hold it, and moves a tile of tick
    # rows at a time (a block's index changes every ``tile`` steps): the grid
    # walks the blocks of head rows outermost, so a tile is never left and
    # come back to.
    tile = 8 if rows % 8 == 0 else rows
    x32 = x.astype(jnp.float32)
    per_head = pl.BlockSpec((tile, rb * lanes), lambda j, s, ids, *_: (s // tile, j))
    per_row = pl.BlockSpec(
        (tile, in_block * n), lambda j, s, ids, *_: (s // tile, j if b.ndim == 3 else 0)
    )
    states = pl.BlockSpec((1, rb, n, lanes), lambda j, s, ids, *_: (ids[s], j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_kernel, head_rows=rb, rows_a_group=rows_a_group, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # The rows' ids, and what is one number a (row, head): the decay
            # and dt, scalars the kernel spreads over a head's lanes itself.
            num_scalar_prefetch=3,
            grid=(head_rows // rb, rows),
            in_specs=[per_head, per_row, per_row, states],
            out_specs=[per_head, states],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, heads * channels), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # The states (operand 6, the scalars first) are the second output.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="ssm_state_update",
    )(
        ids.astype(jnp.int32), jnp.exp(dt * a).reshape(-1), dt.reshape(-1),
        *(v.astype(jnp.float32).reshape(rows, -1) for v in (x, b, c)), state,
    )
    return y.reshape(x.shape) + d_skip[None, :, None] * x32, state


def ssm_state_update(
    state: jax.Array, ids: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
    b: jax.Array, c: jax.Array, d_skip: jax.Array, *, path: str | None = None,
):
    """``state`` (slots + 1, heads / k, state values, k x channels) float32,
    resting as the module says, updated at rows ``ids`` (rows,) by one step:
    ``x`` (rows, heads, channels),
    ``dt`` (rows, heads) float32 (0 leaves a state as it is), ``a`` and
    ``d_skip`` (heads,) float32, ``b`` and ``c`` (rows, state values), or
    (rows, groups, state values) by group.  Returns ``(y (rows, heads, channels) float32, state)``.  ``path``
    forces ``"pallas"`` (interpret mode off the TPU: parity tests) or
    ``"xla"``; None takes the kernel on the TPU."""
    with jax.named_scope("ssm_state_update"):
        on_tpu = jax.default_backend() == "tpu"
        if path == "xla" or (path is None and not on_tpu):
            return xla_ssm_state_update(state, ids, x, dt, a, b, c, d_skip)
        return _pallas_ssm_state_update(
            state, ids, x, dt, a, b, c, d_skip, interpret=not on_tpu
        )
