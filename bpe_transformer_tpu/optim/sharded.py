"""ZeRO-1 sharded AdamW: each data-parallel replica owns 1/N of the
optimizer state (Xu et al., arXiv:2004.13336 — automatic cross-replica
sharding of the weight update, exactly this repo's dp case).

The update works on the FLAT layout: every param/grad leaf is raveled to
float32 and concatenated into one vector, zero-padded to a multiple of the
data-parallel width ``N`` and viewed as ``(N, shard_len)``.  Per step:

* gradients are **reduce-scattered** along the dp axis (each replica
  receives the summed 1/N shard it owns — one collective moving the same
  bytes as the old all-reduce's reduce half),
* the global clip norm comes from the scattered shards (``psum`` of local
  sum-of-squares — shards tile the full vector, so the norm is exact),
* each replica applies AdamW to its shard only (m/v and the fp32 master
  copy all live in the ``(N, shard_len)`` layout, sharded ``P(axis)``, so
  per-chip optimizer bytes are ~1/N of the replicated state's),
* fresh params are **all-gathered** back to every replica.

The master shard is kept even for fp32 params: slicing this replica's
shard out of the replicated params each step would force a full flat
f32 copy of the params inside the compiled update (the slice offset is
the runtime ``axis_index``, so XLA cannot fold the concatenation away) —
4P of transient HBM traffic per step against 4P/N resident for the
persistent shard.  For bf16 params the master is also the precision
story: updates accumulate in f32 and the bf16 params are its rounded
projection.

Math is identical to :func:`bpe_transformer_tpu.optim.adamw.adamw_update`
applied after a gradient ``pmean`` — same decoupled weight decay, same
bias correction, same clip semantics — just computed where the shard
lives.  The CPU-mesh parity test pins this.

Checkpoint compatibility: :func:`restore_opt_state` adapts any
checkpointed optimizer state to the run's sharding mode — dense ↔ sharded
in either direction, and sharded → sharded across a different dp width —
so a pre-sharding checkpoint resumes into a ZeRO-1 run (and vice versa)
without a conversion tool.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import Array, lax

from bpe_transformer_tpu.optim.adamw import AdamWState, adamw_init


class ShardedAdamWState(NamedTuple):
    """ZeRO-1 optimizer state in the flat ``(n_shards, shard_len)`` layout.

    ``master`` always carries the fp32 master weights (see the module
    docstring for why fp32 params keep one too); ``None`` only appears
    transiently in payloads from checkpoints written before the
    always-master layout — :func:`restore_opt_state` backfills it."""

    step: Array  # scalar int32, replicated
    m: Array  # (n_shards, shard_len) float32 first moment
    v: Array  # (n_shards, shard_len) float32 second moment
    master: Any  # (n_shards, shard_len) float32 master weights


def is_sharded_opt_state(opt_state) -> bool:
    """True for a :class:`ShardedAdamWState` (or an equivalent 4-tuple from
    a checkpoint payload)."""
    if isinstance(opt_state, ShardedAdamWState):
        return True
    return isinstance(opt_state, (tuple, list)) and len(opt_state) == 4


def flat_total(params) -> int:
    """Total element count across every leaf of ``params``."""
    import numpy as np

    return int(sum(np.prod(np.shape(p)) for p in jax.tree_util.tree_leaves(params)))


def shard_len(total: int, n_shards: int) -> int:
    """Per-shard flat length: ``total`` rounded up to a multiple of
    ``n_shards`` (the tail shard is zero-padded), divided by it."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return -(-total // n_shards)


def flatten_cast(tree, dtype, pad_to: int | None = None) -> Array:
    """Ravel every leaf to ``dtype`` and concatenate; zero-pad to
    ``pad_to``.  The bf16 gradient boundary flattens at the narrow width
    so the reduce-scatter moves half the bytes."""
    leaves = jax.tree_util.tree_leaves(tree)
    flat = jnp.concatenate([leaf.astype(dtype).ravel() for leaf in leaves])
    if pad_to is not None and pad_to > flat.size:
        flat = jnp.pad(flat, (0, pad_to - flat.size))
    return flat


def flatten_f32(tree, pad_to: int | None = None) -> Array:
    """Ravel every leaf to float32 and concatenate; zero-pad to ``pad_to``."""
    return flatten_cast(tree, jnp.float32, pad_to)


def unflatten_like(flat: Array, template) -> Any:
    """Inverse of :func:`flatten_f32`: split ``flat`` at the template's
    leaf boundaries, reshape, and cast each piece back to the template
    leaf's dtype.  Padding beyond the template's total is ignored."""
    leaves = jax.tree_util.tree_leaves(template)
    out, offset = [], 0
    for leaf in leaves:
        size = int(leaf.size)
        out.append(
            flat[offset : offset + size].reshape(leaf.shape).astype(leaf.dtype)
        )
        offset += size
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template), out
    )


def sharded_adamw_init(
    params, n_shards: int, mesh=None, axis: str = "data"
) -> ShardedAdamWState:
    """Zero-initialized ZeRO-1 state for ``params`` split ``n_shards`` ways.

    With ``mesh``, the ``(n_shards, shard_len)`` leaves are placed sharded
    ``P(axis)`` so each chip materializes only its own 1/N from step 0 —
    without it they are laid out replicated and the first sharded dispatch
    re-places them.
    """
    total = flat_total(params)
    L = shard_len(total, n_shards)
    state = ShardedAdamWState(
        step=jnp.zeros((), jnp.int32),
        m=jnp.zeros((n_shards, L), jnp.float32),
        v=jnp.zeros((n_shards, L), jnp.float32),
        master=flatten_f32(params, pad_to=n_shards * L).reshape(n_shards, L),
    )
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        state = ShardedAdamWState(
            step=jax.device_put(
                state.step, NamedSharding(mesh, PartitionSpec())
            ),
            m=_place_sharded(state.m, mesh, axis),
            v=_place_sharded(state.v, mesh, axis),
            master=_place_sharded(state.master, mesh, axis),
        )
    return state


@jax.named_scope("optimizer")
def sharded_adamw_update(
    params,
    grads,
    state: ShardedAdamWState,
    lr: float | Array,
    *,
    axis: str,
    n_shards: int,
    betas: tuple[float, float] = (0.9, 0.999),
    eps: float = 1e-8,
    weight_decay: float = 0.01,
    grad_clip_norm: float | None = None,
    clip_eps: float = 1e-6,
    grads_dtype: str = "float32",
):
    """One ZeRO-1 AdamW step INSIDE ``shard_map`` over ``axis``.

    ``params``/``grads`` are the full replicated/per-shard trees (grads are
    the LOCAL gradients — the reduce-scatter here replaces the dp
    ``pmean``); ``state`` leaves arrive as this replica's ``(1, shard_len)``
    block (``in_specs=P(axis)`` on the leading shard dim).  Returns
    ``(new_params, new_state, grad_norm)`` with ``grad_norm`` the global
    pre-clip norm of the MEAN gradients (what the unsharded path reports).

    ``grads_dtype="bfloat16"`` flattens the gradient tree at bf16 so the
    reduce-scatter — the training step's one big collective on this path —
    moves HALF the bytes; the scattered shard widens straight back to
    float32, so the clip norm, moments, and fp32 master math below are
    untouched (only sub-bf16 gradient precision is rounded away, bounded
    by the parity tests).
    """
    b1, b2 = betas
    total = flat_total(params)
    L = int(state.m.shape[-1])

    # Reduce-scatter: one collective hands each replica the summed shard it
    # owns; dividing by N makes it the mean (== pmean semantics).  The
    # flatten happens at the (possibly narrowed) collective width.
    flat_g = flatten_cast(grads, jnp.dtype(grads_dtype), pad_to=n_shards * L)
    g_local = (
        lax.psum_scatter(flat_g, axis, scatter_dimension=0, tiled=True)
        .astype(jnp.float32)
        / n_shards
    )

    # Global clip norm from the shards: they tile the full vector, so the
    # psum of local sums-of-squares IS the full sum (pad contributes 0).
    with jax.named_scope("grad_clip"):
        grad_norm = jnp.sqrt(lax.psum(jnp.sum(jnp.square(g_local)), axis))
        if grad_clip_norm is not None:
            scale = jnp.minimum(1.0, grad_clip_norm / (grad_norm + clip_eps))
            g_local = g_local * scale

    m_local = state.m.reshape(-1)
    v_local = state.v.reshape(-1)
    # The persistent master shard is the fp32 source of truth for this
    # replica's slice of the params (never re-derived from the replicated
    # params — that would cost a full flat f32 copy per step AND, for bf16
    # params, discard the sub-bf16 accumulation).
    p_local = state.master.reshape(-1)

    step = state.step + 1
    t = step.astype(jnp.float32)
    bias1 = 1.0 - b1**t
    bias2 = 1.0 - b2**t
    m_new = b1 * m_local + (1.0 - b1) * g_local
    v_new = b2 * v_local + (1.0 - b2) * jnp.square(g_local)
    m_hat = m_new / bias1
    v_hat = v_new / bias2
    p_new = p_local * (1.0 - lr * weight_decay) - lr * m_hat / (
        jnp.sqrt(v_hat) + eps
    )

    # All-gather the fresh shards back into the replicated param trees.
    flat_new = lax.all_gather(p_new, axis, tiled=True)
    new_params = unflatten_like(flat_new[:total], params)
    new_state = ShardedAdamWState(
        step=step, m=m_new[None], v=v_new[None], master=p_new[None]
    )
    return new_params, new_state, grad_norm


# ------------------------------------------------- checkpoint conversions


def shard_opt_state(
    opt: AdamWState, params, n_shards: int, mesh=None, axis: str = "data"
) -> ShardedAdamWState:
    """Convert a dense :class:`AdamWState` into the ZeRO-1 flat layout
    (legacy-checkpoint resume into a sharded run).  The master starts as
    the fp32 view of the current params — exact for f32 params, and the
    best available truth for bf16 ones (a dense checkpoint never carried
    sub-bf16 precision to begin with)."""
    total = flat_total(params)
    L = shard_len(total, n_shards)
    state = ShardedAdamWState(
        step=jnp.asarray(opt.step, jnp.int32),
        m=flatten_f32(opt.m, pad_to=n_shards * L).reshape(n_shards, L),
        v=flatten_f32(opt.v, pad_to=n_shards * L).reshape(n_shards, L),
        master=flatten_f32(params, pad_to=n_shards * L).reshape(n_shards, L),
    )
    if mesh is not None:
        state = ShardedAdamWState(
            step=state.step,
            m=_place_sharded(state.m, mesh, axis),
            v=_place_sharded(state.v, mesh, axis),
            master=(
                _place_sharded(state.master, mesh, axis)
                if state.master is not None
                else None
            ),
        )
    return state


def _place_sharded(arr, mesh, axis: str):
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(arr, NamedSharding(mesh, PartitionSpec(axis)))


def unshard_opt_state(opt: ShardedAdamWState, params) -> AdamWState:
    """Back to the dense per-leaf layout (sharded checkpoint resumed into
    an unsharded run).  Moments stay float32 like :func:`adamw_init`'s."""
    total = flat_total(params)
    moments_template = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params
    )
    flat_m = jnp.asarray(opt.m).reshape(-1)[:total]
    flat_v = jnp.asarray(opt.v).reshape(-1)[:total]
    return AdamWState(
        step=jnp.asarray(opt.step, jnp.int32),
        m=unflatten_like(flat_m, moments_template),
        v=unflatten_like(flat_v, moments_template),
    )


def restore_opt_state(
    opt_payload,
    params,
    zero1_shards: int | None = None,
    mesh=None,
    axis: str = "data",
):
    """Adapt a checkpointed optimizer payload (or ``None``) to the run's
    optimizer-sharding mode.

    ``opt_payload`` is whatever ``payload["opt_state"]`` unpickled to: a
    dense 3-field :class:`AdamWState`, a 4-field
    :class:`ShardedAdamWState`, or ``None`` (init fresh).
    ``zero1_shards`` is the dp width when the run wants ZeRO-1, ``None``
    for the dense optimizer.  Handles every crossing: dense → sharded
    (pre-sharding checkpoint into a ZeRO-1 run), sharded → dense, and
    sharded → sharded across a DIFFERENT dp width (reshard through the
    flat vector).
    """
    if opt_payload is None:
        if zero1_shards:
            return sharded_adamw_init(params, zero1_shards, mesh=mesh, axis=axis)
        return adamw_init(params)
    if is_sharded_opt_state(opt_payload):
        sharded = ShardedAdamWState(*opt_payload)
        if not zero1_shards:
            return unshard_opt_state(sharded, params)
        if int(sharded.m.shape[0]) != zero1_shards:
            # Saved on N chips, resumed on M: reshard every flat leaf —
            # INCLUDING the fp32 master, whose accumulated sub-bf16
            # precision must survive the width change for the resumed
            # trajectory to match an uninterrupted run — by trimming the
            # old padding and re-padding for the new width.
            total = flat_total(params)
            new_len = shard_len(total, zero1_shards)

            def rewidth(arr):
                flat = jnp.asarray(arr).reshape(-1)[:total]
                return jnp.pad(
                    flat, (0, zero1_shards * new_len - total)
                ).reshape(zero1_shards, new_len)

            sharded = ShardedAdamWState(
                step=jnp.asarray(sharded.step, jnp.int32),
                m=rewidth(sharded.m),
                v=rewidth(sharded.v),
                master=(
                    rewidth(sharded.master)
                    if sharded.master is not None
                    else None
                ),
            )
        if sharded.master is None:
            # Payload from the brief no-master-for-f32 layout: backfill
            # from the params BEFORE placement so the master leaf is born
            # sharded like m/v, never materialized full-size per chip.
            sharded = sharded._replace(
                master=flatten_f32(
                    params,
                    pad_to=int(sharded.m.shape[0]) * int(sharded.m.shape[1]),
                ).reshape(sharded.m.shape)
            )
        if mesh is not None:
            sharded = ShardedAdamWState(
                step=jnp.asarray(sharded.step, jnp.int32),
                m=_place_sharded(jnp.asarray(sharded.m), mesh, axis),
                v=_place_sharded(jnp.asarray(sharded.v), mesh, axis),
                master=_place_sharded(jnp.asarray(sharded.master), mesh, axis),
            )
        return sharded
    dense = AdamWState(*opt_payload)
    if zero1_shards:
        return shard_opt_state(dense, params, zero1_shards, mesh=mesh, axis=axis)
    return dense
