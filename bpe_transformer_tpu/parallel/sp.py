"""Sequence-parallel (context-parallel) training: ring attention in the loop.

Long sequences are sharded along a ``seq`` mesh axis (in addition to the
``data`` batch axis): every chip holds a slice of every sequence, activation
memory scales as O(S / n_seq), and attention runs as the ring schedule from
`parallel.ring_attention` (K/V shards rotating over ICI).  Everything else
in the block (norms, FFN, projections) is token-local, so only attention and
the loss/grad reductions touch collectives:

* attention: ``ppermute`` ring over ``seq``;
* loss and gradients: ``pmean`` over both ``data`` and ``seq``.

This subsystem has no reference counterpart at all (max context there is 16
tokens) — it exists because long-context is first-class in the TPU build.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.transformer import forward
from bpe_transformer_tpu.ops.grad import clip_by_global_norm
from bpe_transformer_tpu.optim.adamw import AdamWState, adamw_update
from bpe_transformer_tpu.optim.schedule import cosine_schedule_jax
from bpe_transformer_tpu.parallel.ring_attention import (
    ring_flash_attention,
    ring_self_attention,
    zigzag_indices,
    zigzag_positions,
    zigzag_ring_flash_attention,
    zigzag_ring_self_attention,
)
from bpe_transformer_tpu.parallel.ulysses import ulysses_attention
from bpe_transformer_tpu.training.train_step import (
    TrainHParams,
    accumulate_grads,
    jit_step,
    scanned_step_fn,
)

P = PartitionSpec

_FLASH_RING_KV_CHUNK_ERROR = (
    'attention_impl="flash" does not honor ring_kv_chunk inside the ring '
    "(the Pallas kernel tiles each visiting shard by flash_block_size "
    'instead); unset ring_kv_chunk or use the XLA ring (attention_impl="xla")'
)


def sp_forward(
    params,
    local_token_ids: jax.Array,
    config: ModelConfig,
    seq_axis: str,
    ulysses: bool = False,
) -> jax.Array:
    """Forward over a local sequence shard; call INSIDE shard_map.

    Positions are global (shard offset + local index) so RoPE sees the true
    token positions; attention is the exact ring schedule over ``seq_axis``
    (or the Ulysses all-to-all head-scatter with ``ulysses=True``).
    """
    s_local = local_token_ids.shape[-1]
    offset = jax.lax.axis_index(seq_axis) * s_local
    positions = offset + jnp.arange(s_local)
    attention_fn = _sp_attention_fn(config, seq_axis, ulysses=ulysses)
    return forward(
        params, local_token_ids, config, positions=positions, attention_fn=attention_fn
    )


def _sp_attention_fn(
    config: ModelConfig,
    seq_axis: str,
    zigzag: bool = False,
    ulysses: bool = False,
):
    """Per-shard attention for the sp schedules, per the config:
    ``ulysses=True`` is the all-to-all head scatter (`parallel/ulysses.py`);
    otherwise ``attention_impl="flash"`` runs the Pallas kernel inside every
    ring shard (ring-flash / zig-zag ring-flash), anything else the XLA
    online-softmax ring (optionally kv-chunked; zig-zag has no chunk knob —
    its sub-blocks are already half-size)."""
    if ulysses:
        return partial(ulysses_attention, axis_name=seq_axis, config=config)
    if config.attention_impl == "flash":
        from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

        if config.ring_kv_chunk:
            raise ValueError(_FLASH_RING_KV_CHUNK_ERROR)
        block = config.flash_block_size
        fn = zigzag_ring_flash_attention if zigzag else ring_flash_attention
        return partial(
            fn,
            axis_name=seq_axis,
            block_q=block,
            block_k=block,
            interpret=interpret_mode(),
        )
    if zigzag:
        return partial(zigzag_ring_self_attention, axis_name=seq_axis)
    return partial(
        ring_self_attention,
        axis_name=seq_axis,
        causal=True,
        kv_chunk=config.ring_kv_chunk,
    )


def make_sp_train_step(
    config: ModelConfig,
    hparams: TrainHParams,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: str = "seq",
    zigzag: bool = False,
    ulysses: bool = False,
    accum_steps: int = 1,
    inner_steps: int = 1,
) -> Callable:
    """Train step over a 2-D (data x seq) mesh: batch split on ``data``,
    every sequence split on ``seq``; params/opt-state replicated.

    ``ulysses=True`` swaps the ring schedule for the all-to-all head
    scatter (`parallel/ulysses.py`): one all_to_all re-partitions Q/K/V to
    head-sharded, dense/flash attention runs over the FULL sequence per
    head slice, and the inverse all_to_all restores sequence sharding.
    Requires ``num_heads`` to be a multiple of the seq axis size;
    contiguous layout
    (mutually exclusive with ``zigzag`` — Ulysses has no load imbalance to
    fix, every device already does identical full-sequence work).

    The global batch must divide the data axis and ``context_length`` must
    divide the seq axis.  With ``zigzag=True`` the causal ring runs the
    balanced striped schedule (~2x less attention work at large mesh sizes);
    feed batches through :func:`shard_sp_batch` with ``zigzag=True`` so the
    on-device layout matches, and note positions/loss are permutation-
    consistent (targets ride the same permutation as inputs).

    ``accum_steps > 1``: gradient accumulation INSIDE the sharded program —
    each chip scans its local microbatch shards (``lax.scan``, so peak
    activation memory stays one microbatch even though sp exists precisely
    because long-context activations are HBM-limited), and the grad/loss
    ``pmean`` over (data, seq) runs ONCE per update, after accumulation.
    Batches become ``(accum_steps, micro_batch, seq)``; feed them through
    :func:`shard_sp_batch` with ``stacked=True``.

    ``inner_steps > 1``: several FULL updates per dispatch (``lax.scan``
    over the whole local update incl. its per-update pmean), amortizing
    host launch latency exactly like the dp/GSPMD scanned steps; batches
    are ``(inner_steps, batch, seq)``, also via ``stacked=True``.  Metrics
    report the last inner update.  Mutually exclusive with accumulation.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if inner_steps < 1:
        raise ValueError(f"inner_steps must be >= 1, got {inner_steps}")
    if accum_steps > 1 and inner_steps > 1:
        raise ValueError("accum_steps and inner_steps cannot both exceed 1")
    if zigzag and ulysses:
        raise ValueError(
            "zigzag and ulysses are mutually exclusive (the all-to-all "
            "schedule has no causal load imbalance to stripe away)"
        )
    n_seq = mesh.shape[seq_axis]
    if ulysses and config.num_heads % n_seq:
        raise ValueError(
            f"ulysses scatters heads over the seq axis: num_heads="
            f"{config.num_heads} must be a multiple of the {seq_axis!r} "
            f"axis size {n_seq} (use the ring schedule otherwise)"
        )
    if zigzag and config.ring_kv_chunk:
        raise ValueError(
            "the zig-zag schedule does not honor ring_kv_chunk (its "
            "sub-blocks are already half-size); use the contiguous ring, or "
            'unset ring_kv_chunk and set attention_impl="flash" for '
            "VMEM-tiled zig-zag"
        )
    if config.attention_impl == "flash" and config.ring_kv_chunk and not ulysses:
        # Same guard lives in _sp_attention_fn (covers sp_forward too);
        # raising here surfaces it at step-construction time.  Ulysses is
        # carved out: it never consumes ring_kv_chunk (its inner attention
        # is full-sequence flash/dense), so a ring-specific error about a
        # knob the selected schedule ignores would only mislead.
        raise ValueError(_FLASH_RING_KV_CHUNK_ERROR)

    def local_step(params, opt_state: AdamWState, x, y):
        def loss_fn(p, x, y):
            # Memory-lean loss on the LOCAL sequence shard (already seq/N
            # long); lm_loss applies the shared clamp/divisibility guard.
            from bpe_transformer_tpu.models.transformer import (
                forward_hidden,
                lm_head_weight,
            )
            from bpe_transformer_tpu.ops.losses import lm_loss

            s_local = x.shape[-1]
            if zigzag:
                positions = zigzag_positions(
                    jax.lax.axis_index(seq_axis), s_local, n_seq
                )
            else:
                offset = jax.lax.axis_index(seq_axis) * s_local
                positions = offset + jnp.arange(s_local)
            attention_fn = _sp_attention_fn(
                config, seq_axis, zigzag=zigzag, ulysses=ulysses
            )
            hidden, aux = forward_hidden(
                p, x, config, positions=positions, attention_fn=attention_fn
            )
            loss = lm_loss(
                hidden, lm_head_weight(p, config), y, config.loss_chunk
            )
            if config.ffn_type == "moe":
                # Load-balance aux per dispatch group (the Switch
                # convention): each shard routes its local tokens and
                # regularizes its own expert loads; the pmean below averages
                # the shard auxes (equal-size shards).
                loss = loss + config.router_aux_weight * aux
            return loss

        grad_fn = jax.value_and_grad(loss_fn)
        if accum_steps > 1:
            loss, grads = accumulate_grads(
                grad_fn, params, x, y, accum_steps, context="sp grad-accum step"
            )
        else:
            loss, grads = grad_fn(params, x, y)
        # Equal-size shards: the global mean is the mean of shard means —
        # ONE collective per update, after any local accumulation.  Under
        # grads_dtype="bfloat16" the tree crosses the (data, seq)
        # all-reduce at half width (train_step._reduce_grads semantics);
        # clip/AdamW below stay f32.
        narrow = jnp.dtype(hparams.grads_dtype)
        if narrow != jnp.float32:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(narrow), grads
            )
        grads = jax.lax.pmean(grads, (data_axis, seq_axis))
        if narrow != jnp.float32:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads
            )
        loss = jax.lax.pmean(loss, (data_axis, seq_axis))

        grads, grad_norm = clip_by_global_norm(grads, hparams.grad_clip_norm)
        lr = cosine_schedule_jax(
            opt_state.step,
            hparams.max_learning_rate,
            hparams.min_learning_rate,
            hparams.warmup_iters,
            hparams.cosine_cycle_iters,
        )
        params, opt_state = adamw_update(
            params, grads, opt_state, lr,
            betas=hparams.betas, eps=hparams.eps,
            weight_decay=hparams.weight_decay,
        )
        metrics = {
            "loss": loss.astype(jnp.float32),
            "lr": lr.astype(jnp.float32),
            "grad_norm": grad_norm,
        }
        return params, opt_state, metrics

    if inner_steps > 1:
        local_step = scanned_step_fn(config, hparams, inner_steps, body=local_step)

    stacked = accum_steps > 1 or inner_steps > 1
    batch_spec = (
        P(None, data_axis, seq_axis) if stacked else P(data_axis, seq_axis)
    )
    mapped = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec, batch_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jit_step(mapped)


def shard_sp_batch(
    batch,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: str = "seq",
    zigzag: bool = False,
    stacked: bool = False,
):
    """Place ``(B, S)`` batch arrays split over (data, seq).

    ``zigzag=True`` permutes the sequence axis into the striped layout
    (shard ``i`` gets global chunks ``(i, 2n-1-i)``) before placement, for
    :func:`make_sp_train_step`'s balanced schedule.  ``stacked=True``
    places ``(accum_steps, micro_batch, S)`` arrays with the leading dim
    unsharded (the grad-accum layout; zigzag permutes the last axis either
    way).
    """
    if zigzag:
        n = mesh.shape[seq_axis]
        perm = zigzag_indices(jax.tree_util.tree_leaves(batch)[0].shape[-1], n)
        batch = jax.tree_util.tree_map(lambda a: a[..., perm], batch)
    spec = P(None, data_axis, seq_axis) if stacked else P(data_axis, seq_axis)
    sharding = NamedSharding(mesh, spec)
    return jax.device_put(batch, sharding)
