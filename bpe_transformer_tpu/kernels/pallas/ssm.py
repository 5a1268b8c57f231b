"""One step of a state-space layer's recurrence for every slot of a tick:
``ssm_state_update``.

For each row ``s`` of the tick and head ``h``, with the state ``H`` (head
channels x state values, float32) of the slot the row belongs to::

    H' = exp(dt[s, h] * a[h]) * H + (dt[s, h] * x[s, h]) (x) b[s, g]
    y[s, h] = H' c[s, g] + d_skip[h] * x[s, h]

``b`` and ``c`` come by group: ``(rows, groups, state values)``, head ``h``
reading group ``g = h // (heads // groups)``; of one group they are ``(rows,
state values)``, shared by all heads, and the kernel is the one it was before
there were groups.

The states rest in one array a layer, ``(slots + 1, heads, head channels,
state values)``, a row a slot and the last row trash; ``ids`` (rows,) says
which row each tick row updates (an idle or still-prefilling slot's row is
sent to trash, so its state is not touched at all).  The array is updated
**in place**: on the TPU the Pallas kernel takes it aliased to its output and
reads and writes each addressed head's state exactly once (its device events
are named ``ssm_state_update``); the states of rows nobody addresses never
move.  Elsewhere (CPU tests) the same contract is a gather, the update and a
scatter in XLA (:func:`xla_ssm_state_update`).

The kernel walks ``(row, block of heads)``: a block's states ``(heads a
block, head channels, state values)`` come in as whole ``(8, 128)`` tiles
with the state values along the lanes, so ``b`` and ``c`` broadcast along
sublanes as they are (several groups: a block of heads holds whole groups,
whose rows of ``b`` and ``c`` come in as one tile, a sublane a group); what
varies along the sublanes (``dt * x``, a channel a sublane) is handed in
head-minor, ``(head channels, heads a block)``, and sliced a column a head,
which the lanes then repeat.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.kernels.pallas.runtime import pick_block

#: Heads a grid step: 64 x (64, 128) float32 states are 2 MB, in and out
#: double-buffered 8 MB.
HEAD_BLOCK = 64
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def xla_ssm_state_update(state, ids, x, dt, a, b, c, d_skip):
    """The contract in XLA: ``(y (rows, heads, channels) float32, state)``."""
    x32 = x.astype(jnp.float32)
    decay = jnp.exp(dt * a)                                    # (rows, heads)
    rows = state[ids]
    b, c = b.astype(jnp.float32), c.astype(jnp.float32)
    by_group = b.ndim == 3
    if by_group:  # each head its group's rows: (rows, heads, state)
        b, c = (jnp.repeat(v, x.shape[1] // v.shape[1], axis=1) for v in (b, c))
    b = b[:, :, None, :] if by_group else b[:, None, None, :]
    new = rows * decay[:, :, None, None] + (dt[:, :, None] * x32)[..., None] * b
    y = jnp.einsum(
        "shpn,shn->shp" if by_group else "shpn,sn->shp", new, c,
        precision=jax.lax.Precision.HIGHEST,
    ) + d_skip[None, :, None] * x32
    return y, state.at[ids].set(new)


def _kernel(
    ids_ref, decay_ref, dtx_ref, b_ref, c_ref, h_ref, y_ref, out_ref, *, heads,
    per_group,
):
    del ids_ref  # the index maps' alone
    if per_group == heads:  # one group
        b, c = b_ref[0], c_ref[0]                              # (1, state)
    for h in range(heads):
        if per_group != heads:  # a sublane a group of the block
            g = h // per_group
            b, c = b_ref[0, g:g + 1], c_ref[0, g:g + 1]
        new = (
            h_ref[0, h] * decay_ref[0, 0, :, h:h + 1]
            + dtx_ref[0, 0, :, h:h + 1] * b
        )                                                      # (channels, state)
        out_ref[0, h] = new
        y_ref[0, 0, :, h:h + 1] = jnp.sum(new * c, axis=-1, keepdims=True)


def _pallas_ssm_state_update(state, ids, x, dt, a, b, c, d_skip, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, channels = x.shape
    n = state.shape[-1]
    hb = pick_block(heads, HEAD_BLOCK, 8) or heads
    if b.ndim == 2:  # one group: every block of heads reads the row's b and c
        per_group, in_block = hb, 1
    else:
        per_group = heads // b.shape[1]
        if hb % per_group or (hb // per_group) % 8:
            hb = heads  # whole groups a block, 8 a tile: else one block
        in_block = hb // per_group
    blocks = heads // hb
    x32 = x.astype(jnp.float32)

    def head_minor(t):  # (rows, heads, channels) -> (rows, blocks, channels, hb)
        return jnp.swapaxes(t.reshape(rows, blocks, hb, channels), 2, 3)

    def by_row(t):  # (rows, [groups,] state) -> (rows, groups, state) float32
        t = t.astype(jnp.float32)
        return t[:, None, :] if t.ndim == 2 else t

    decay = jnp.broadcast_to(jnp.exp(dt * a)[:, :, None], x32.shape)
    per_head = pl.BlockSpec((1, 1, channels, hb), lambda s, j, ids: (s, j, 0, 0))
    if in_block == 1:
        per_row = pl.BlockSpec((1, 1, n), lambda s, j, ids: (s, 0, 0))
    else:
        per_row = pl.BlockSpec((1, in_block, n), lambda s, j, ids: (s, j, 0))
    states = pl.BlockSpec((1, hb, channels, n), lambda s, j, ids: (ids[s], j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb, per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, blocks),
            in_specs=[per_head, per_head, per_row, per_row, states],
            out_specs=[per_head, states],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, blocks, channels, hb), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # The states (operand 5, the ids first) are the second output.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="ssm_state_update",
    )(
        ids.astype(jnp.int32), head_minor(decay), head_minor(dt[:, :, None] * x32),
        by_row(b), by_row(c), state,
    )
    y = jnp.swapaxes(y, 2, 3).reshape(rows, heads, channels)
    return y + d_skip[None, :, None] * x32, state


def ssm_state_update(
    state: jax.Array, ids: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array,
    b: jax.Array, c: jax.Array, d_skip: jax.Array, *, path: str | None = None,
):
    """``state`` (slots + 1, heads, channels, state values) float32 updated
    at rows ``ids`` (rows,) by one step: ``x`` (rows, heads, channels),
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
