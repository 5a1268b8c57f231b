"""Multi-chip train steps: explicit-collective DP and GSPMD FSDP/TP.

Two complementary executions of the same update body
(`training.train_step.train_step_fn`):

* :func:`make_dp_train_step` — ``jax.shard_map`` over a 1-D ``data`` mesh.
  Every chip holds full replicas; the batch is split along ``data``; each
  chip computes local gradients and a single ``lax.pmean`` all-reduce (ICI)
  makes them global before the identical AdamW update runs everywhere.
  This is the BASELINE.json north-star collective, written explicitly.

* :func:`make_gspmd_train_step` — ``jax.jit`` with ``NamedSharding``
  in/out shardings for ``dp`` / ``fsdp`` / ``tp`` / ``fsdp_tp`` strategies
  (specs from `parallel.sharding`).  XLA's SPMD partitioner derives the
  all-gather / reduce-scatter / psum schedule from the annotations — the
  idiomatic TPU path that scales from v4-8 data parallelism to
  GPT-2-medium FSDP on v5p-16 (BASELINE configs 2/3/5).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.optim.adamw import AdamWState
from bpe_transformer_tpu.parallel.sharding import param_shardings
from bpe_transformer_tpu.training.train_step import (
    TrainHParams,
    grad_accum_step_fn,
    jit_step,
    scanned_step_fn,
    train_step_fn,
)

P = PartitionSpec


def partitioned_config(config: ModelConfig, mesh: Mesh | None) -> ModelConfig:
    """``config`` as a program that XLA's SPMD partitioner splits over
    ``mesh`` must see it: a ``jax.jit`` whose operands are sharded over more
    than one device — the GSPMD steps below, the loop's eval forward on a
    sharded batch.  The partitioner cannot split a Mosaic kernel ("Mosaic
    kernels cannot be automatically partitioned"), and the shape that
    ``attention_impl="auto"`` chooses from does not say who partitions the
    program, so "auto" becomes the materialized ``"xla"`` path there: what
    every such program ran before the choice existed.  Bodies under
    ``shard_map`` (dp, pp) are per-device programs and keep "auto"; a
    forced ``"flash"`` is left to fail as loudly as it always has."""
    if mesh is not None and mesh.size > 1 and config.attention_impl == "auto":
        return dataclasses.replace(config, attention_impl="xla")
    return config


def _multi_step_body(
    config: ModelConfig,
    hparams: TrainHParams,
    accum_steps: int,
    inner_steps: int,
    reduce_axis: str | None,
    health: bool = False,
    dynamics: bool = False,
    zero1_shards: int | None = None,
) -> tuple[Callable, bool]:
    """(body, stacked): the per-shard update body for the requested
    accumulation/scan mode, and whether batches carry a leading stacked dim
    (``(accum|inner, micro_batch, seq)`` instead of ``(batch, seq)``).

    ``health`` and ``dynamics`` thread through to the shared update bodies
    (see ``training.train_step.train_step_fn``): the device-side health/
    dynamics stats compile inside the same sharded program, so their
    reductions reuse the step's collectives and nothing new crosses the
    host boundary.

    ``zero1_shards`` swaps the AdamW update for the ZeRO-1 sharded one
    (`optim.sharded`): reduce-scatter grads, shard-local moment update,
    all-gather params — composed with the same accum/inner stacking."""
    if accum_steps > 1 and inner_steps > 1:
        raise ValueError("accum_steps and inner_steps cannot both exceed 1")
    if accum_steps > 1:
        return (
            grad_accum_step_fn(
                config, hparams, accum_steps, reduce_axis, health=health,
                dynamics=dynamics, zero1_shards=zero1_shards,
            ),
            True,
        )
    if inner_steps > 1:
        return (
            scanned_step_fn(
                config, hparams, inner_steps, reduce_axis, health=health,
                dynamics=dynamics, zero1_shards=zero1_shards,
            ),
            True,
        )
    return (
        train_step_fn(
            config, hparams, reduce_axis, health=health, dynamics=dynamics,
            zero1_shards=zero1_shards,
        ),
        False,
    )


def make_dp_train_step(
    config: ModelConfig,
    hparams: TrainHParams,
    mesh: Mesh,
    axis: str = "data",
    accum_steps: int = 1,
    inner_steps: int = 1,
    health: bool = False,
    dynamics: bool = False,
    opt_sharding: str | None = None,
) -> Callable:
    """Data-parallel step with an explicit gradient all-reduce over ``axis``.

    Batch arrays must be sharded (or shardable) along their leading dim;
    params/opt-state are replicated.  The global batch size must divide the
    mesh axis size.

    ``accum_steps > 1``: each chip scans its local microbatches and the
    all-reduce runs ONCE per update (after local accumulation); batches are
    ``(accum_steps, micro_batch, seq)`` with the micro batch split on
    ``axis``.  ``inner_steps > 1``: several full updates per dispatch, each
    with its own all-reduce; batches are ``(inner_steps, batch, seq)``.

    ``opt_sharding="zero1"`` replaces the pmean + replicated AdamW with the
    ZeRO-1 sharded update (`optim.sharded`): reduce-scatter grads along
    ``axis``, shard-local moment/master update, all-gather fresh params.
    The opt state must then be a ``ShardedAdamWState`` (from
    ``sharded_adamw_init``/``restore_opt_state``) whose flat ``(N, L)``
    leaves ride ``P(axis)`` in/out specs — per-chip optimizer bytes ~1/N.
    """
    if opt_sharding not in (None, "zero1"):
        raise ValueError(f"unknown opt_sharding: {opt_sharding!r}")
    n_shards = mesh.shape[axis] if opt_sharding == "zero1" else None
    body, stacked = _multi_step_body(
        config, hparams, accum_steps, inner_steps, reduce_axis=axis,
        health=health, dynamics=dynamics, zero1_shards=n_shards,
    )
    batch_spec = P(None, axis) if stacked else P(axis)
    if n_shards is not None:
        from bpe_transformer_tpu.optim.sharded import ShardedAdamWState

        # The sharded state's (N, L) leaves split their leading dim over
        # the dp axis — each replica's body sees its own (1, L) block.
        opt_spec = ShardedAdamWState(
            step=P(), m=P(axis), v=P(axis), master=P(axis)
        )
    else:
        opt_spec = P()
    # out_specs are pytree PREFIXES: the final P() covers the whole metrics
    # dict, whatever keys (health sub-dicts included) the body emits.
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), opt_spec, batch_spec, batch_spec),
        out_specs=(P(), opt_spec, P()),
        check_vma=False,
    )
    return jit_step(mapped)


def make_gspmd_train_step(
    config: ModelConfig,
    hparams: TrainHParams,
    mesh: Mesh,
    strategy: str = "fsdp",
    example_params=None,
    accum_steps: int = 1,
    inner_steps: int = 1,
    health: bool = False,
    dynamics: bool = False,
    opt_sharding: str | None = None,
) -> Callable:
    """Sharding-annotated jit step; XLA derives the collective schedule.

    ``example_params`` (an abstract or concrete params pytree) is needed to
    build per-leaf shardings.  Returns a step with donated params/opt-state.

    ``accum_steps``/``inner_steps`` compile the accumulation/multi-update
    ``lax.scan`` INSIDE the sharded program (batches gain a leading stacked
    dim, split on ``data`` along their second axis); XLA still derives all
    collectives from the annotations, so FSDP's gather/scatter schedule
    composes with accumulation without any manual communication.

    ``opt_sharding="zero1"`` annotates the AdamW m/v leaves with
    ``zero1_opt_specs`` (each leaf's largest divisible dim split along
    ``data`` on top of the strategy's param spec): the update body is
    UNCHANGED — the in/out sharding constraints alone make XLA keep the
    moments 1/N per chip and derive the reduce-scatter/all-gather around
    the weight update.  A no-op under ``fsdp`` (moments already shard with
    the params).
    """
    if example_params is None:
        raise ValueError("example_params is required to derive shardings")
    if opt_sharding not in (None, "zero1"):
        raise ValueError(f"unknown opt_sharding: {opt_sharding!r}")
    body, stacked = _multi_step_body(
        partitioned_config(config, mesh), hparams, accum_steps, inner_steps,
        reduce_axis=None, health=health, dynamics=dynamics,
    )
    p_sh = param_shardings(example_params, mesh, strategy)
    replicated = NamedSharding(mesh, P())
    if opt_sharding == "zero1":
        from bpe_transformer_tpu.parallel.sharding import zero1_opt_shardings

        moment_sh = zero1_opt_shardings(example_params, mesh, strategy)
    else:
        moment_sh = p_sh
    opt_sh = AdamWState(step=replicated, m=moment_sh, v=moment_sh)
    data_spec = (P(None, "data") if stacked else P("data"))
    batch_sh = (
        NamedSharding(mesh, data_spec) if "data" in mesh.shape else replicated
    )

    # The metrics out-sharding is a pytree PREFIX: one replicated sharding
    # covers the whole dict regardless of which keys (health sub-dicts
    # included) the body emits — all metrics are scalars.
    return jit_step(
        body,
        in_shardings=(p_sh, opt_sh, batch_sh, batch_sh),
        out_shardings=(p_sh, opt_sh, replicated),
    )


def shard_batch(batch, mesh: Mesh, axis: str = "data", stacked: bool = False):
    """Place a host batch on the mesh, split along the data axis.

    ``stacked=True`` places ``(accum|inner, batch, seq)`` arrays with the
    LEADING dim unsharded and the batch dim split on ``axis`` (the
    grad-accum / scanned-step layouts).  On meshes without that axis (e.g.
    pure tensor parallelism) the batch is replicated instead, matching
    make_gspmd_train_step's fallback."""
    if axis in mesh.shape:
        spec = P(None, axis) if stacked else P(axis)
    else:
        spec = P()
    return jax.device_put(batch, NamedSharding(mesh, spec))
