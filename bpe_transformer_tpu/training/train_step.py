"""Jitted train/eval steps: forward, loss, backward, clip, schedule, AdamW.

The whole update is one traced computation (SURVEY §3.4-3.5: the reference
implies but never implements this loop): host touches only batch feed and
metric readback.  Multi-chip variants live in
``bpe_transformer_tpu.parallel.train_step``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.transformer import forward
from bpe_transformer_tpu.ops.grad import clip_by_global_norm
from bpe_transformer_tpu.ops.losses import cross_entropy
from bpe_transformer_tpu.optim.adamw import AdamWState, adamw_update
from bpe_transformer_tpu.optim.schedule import cosine_schedule_jax
from bpe_transformer_tpu.utils.compile_cache import layered_program_options


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    """Optimization hyperparameters (host-side constants baked into the jit)."""

    max_learning_rate: float = 3e-4
    min_learning_rate: float = 3e-5
    warmup_iters: int = 100
    cosine_cycle_iters: int = 10_000
    betas: tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip_norm: float = 1.0
    #: Width of the gradient tree AT THE REDUCTION BOUNDARY (PR 13):
    #: ``"bfloat16"`` rounds gradients to bf16 just before the dp ``pmean``
    #: / ZeRO-1 reduce-scatter, halving the bytes every training collective
    #: moves, then widens back to float32 — clipping, AdamW moments, and
    #: the fp32 master update are unchanged.  Applied uniformly in every
    #: step variant (single-device and GSPMD pay the same round-trip
    #: rounding, so numerics never depend on the execution mode); the only
    #: information lost is sub-bf16 gradient precision, bounded by the
    #: parity tests.  ``"float32"`` (default) is byte-identical to the
    #: historical step.
    grads_dtype: str = "float32"

    def __post_init__(self):
        if self.grads_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f'grads_dtype={self.grads_dtype!r} must be "float32" or '
                '"bfloat16"'
            )


def make_loss_fn(
    config: ModelConfig, with_aux: bool = False, with_stats: bool = False
) -> Callable:
    """``with_aux=True`` returns ``(loss, aux)`` instead of the scalar loss,
    where ``aux`` is the raw MoE load-balance loss (0 for dense FFNs) —
    the health-enabled train step exports it as an expert-balance stat
    (exactly 1.0 at perfectly uniform routing).

    ``with_stats=True`` (dynamics introspection; supersedes ``with_aux``)
    returns ``(loss, (aux, act_stats))`` with the per-layer activation
    statistics from ``forward_hidden_stats`` — same forward, same math,
    plus cheap in-graph reductions."""
    if config.dropless_block:
        raise ValueError(
            "training is not supported for this block (a layer pattern, the "
            "parallel block, LayerNorm, sigmoid routing, shared or held "
            "experts; latent attention, the double layer, zero experts; "
            "state-space layers, whose scan has no backward; chunked linear "
            "attention): it "
            "has no load-balance loss and no backward-tested "
            "path; it is served and checked against its plain forward only "
            "(ROADMAP: what cannot run yet)"
        )
    is_moe = config.ffn_type == "moe"

    if with_stats:
        from bpe_transformer_tpu.models.transformer import (
            forward_hidden_stats,
            lm_head_weight,
        )
        from bpe_transformer_tpu.ops.core import head_logits
        from bpe_transformer_tpu.ops.losses import lm_loss

        def stats_loss_fn(params, x, y):
            hidden, aux, act_stats = forward_hidden_stats(params, x, config)
            head_w = lm_head_weight(params, config)
            if config.loss_chunk:
                loss = lm_loss(hidden, head_w, y, config.loss_chunk)
            else:
                loss = cross_entropy(head_logits(hidden, head_w), y)
            if is_moe:
                loss = loss + config.router_aux_weight * aux
            return loss, (aux, act_stats)

        return stats_loss_fn

    if config.loss_chunk:
        from bpe_transformer_tpu.models.transformer import (
            forward_hidden,
            lm_head_weight,
        )
        from bpe_transformer_tpu.ops.losses import lm_loss

        def loss_fn(params, x, y):
            hidden, aux = forward_hidden(params, x, config)
            loss = lm_loss(
                hidden, lm_head_weight(params, config), y, config.loss_chunk
            )
            if is_moe:
                loss = loss + config.router_aux_weight * aux
            if with_aux:
                return loss, aux
            return loss

    elif is_moe:

        def loss_fn(params, x, y):
            logits, aux = forward(params, x, config, return_aux=True)
            loss = cross_entropy(logits, y) + config.router_aux_weight * aux
            if with_aux:
                return loss, aux
            return loss

    else:

        def loss_fn(params, x, y):
            loss = cross_entropy(forward(params, x, config), y)
            if with_aux:
                return loss, jnp.zeros((), jnp.float32)
            return loss

    return loss_fn


def _reduce_act_stats(act_stats: dict, axis: str) -> dict:
    """Fold per-shard activation stats to global ones under a mapped mesh
    axis: means average, absmax maxes, non-finite counts sum."""
    return {
        "rms": jax.lax.pmean(act_stats["rms"], axis),
        "absmax": jax.lax.pmax(act_stats["absmax"], axis),
        "nonfinite": jax.lax.psum(act_stats["nonfinite"], axis),
        "attn_entropy": jax.lax.pmean(act_stats["attn_entropy"], axis),
    }


def _reduce_grads(grads, reduce_axis: str | None, grads_dtype: str):
    """The gradient-reduction boundary shared by every non-ZeRO step body.

    Under ``grads_dtype="bfloat16"`` the tree is rounded to bf16 just
    before the dp ``pmean`` — the collective moves half the bytes — and
    widened back to float32 for the clip/AdamW math.  The round-trip
    applies even with no mapped axis (single device; GSPMD, where XLA owns
    the collective placement and frequently schedules the derived
    all-reduce on the narrowed values), so one ``grads_dtype`` means one
    set of numerics across execution modes."""
    narrow = jnp.dtype(grads_dtype)
    if narrow != jnp.float32:
        grads = jax.tree_util.tree_map(lambda g: g.astype(narrow), grads)
    if reduce_axis is not None:
        grads = jax.lax.pmean(grads, reduce_axis)
    if narrow != jnp.float32:
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads
        )
    return grads


def _check_zero1(zero1_shards, reduce_axis, health, dynamics, context):
    """Validate a ZeRO-1 request: it needs a mapped dp axis to scatter
    over, and it never materializes the global mean-gradient tree the
    health/dynamics taps read (that tree not existing is the point)."""
    if zero1_shards is None:
        return
    if reduce_axis is None:
        raise ValueError(
            f"{context}: zero1_shards requires a mapped reduce_axis (the "
            "sharded update reduce-scatters gradients over the dp axis)"
        )
    if health or dynamics:
        raise ValueError(
            f"{context}: health/dynamics stats are not supported with the "
            "ZeRO-1 sharded update — they read the global gradient tree, "
            "which the reduce-scatter path deliberately never builds; "
            "drop --health-stats/--dynamics-every or --opt-sharding"
        )


def _zero1_update(params, opt_state, loss, grads, hparams, axis, n_shards):
    """The shared ZeRO-1 tail of a step body: schedule lr, reduce-scatter +
    shard-local AdamW + all-gather (`optim.sharded`), metrics dict.  The
    plain and grad-accum bodies differ only in how ``loss``/``grads`` were
    produced (``loss`` is this shard's local value; the pmean happens
    here)."""
    from bpe_transformer_tpu.optim.sharded import sharded_adamw_update

    loss = jax.lax.pmean(loss, axis)
    lr = cosine_schedule_jax(
        opt_state.step,
        hparams.max_learning_rate,
        hparams.min_learning_rate,
        hparams.warmup_iters,
        hparams.cosine_cycle_iters,
    )
    new_params, opt_state, grad_norm = sharded_adamw_update(
        params,
        grads,
        opt_state,
        lr,
        axis=axis,
        n_shards=n_shards,
        betas=hparams.betas,
        eps=hparams.eps,
        weight_decay=hparams.weight_decay,
        grad_clip_norm=hparams.grad_clip_norm,
        grads_dtype=hparams.grads_dtype,
    )
    metrics = {
        "loss": loss.astype(jnp.float32),
        "lr": lr.astype(jnp.float32),
        "grad_norm": grad_norm,
    }
    return new_params, opt_state, metrics


def train_step_fn(
    config: ModelConfig,
    hparams: TrainHParams,
    reduce_axis: str | None = None,
    health: bool = False,
    dynamics: bool = False,
    zero1_shards: int | None = None,
) -> Callable:
    """The un-jitted update body ``(params, opt_state, x, y) ->
    (params, opt_state, metrics)`` shared by every execution mode.

    ``reduce_axis`` names a mapped mesh axis to pmean loss/grads over —
    that single hook is all data parallelism adds to the update.

    ``health=True`` (opt-in; the default step is unchanged) appends the
    device-side health stats from `telemetry.health` to ``metrics``:
    non-finite loss/grad/param detection, per-layer-group grad/param norms,
    and (MoE) the raw expert load-balance loss as ``moe_aux``.  All extra
    cost is a few reductions inside the same jitted program — the stats
    ride the loop's existing once-per-``log_every`` metric fetch.

    ``dynamics=True`` (opt-in, `telemetry.dynamics`) additionally appends
    ``metrics["dynamics"]``: per-layer grad/param norms, update-to-param
    ratios, per-tensor non-finite localization counts, and per-block
    activation stats tapped from the SAME differentiated forward
    (``forward_hidden_stats``).  Everything stays on device and rides the
    same log-cadence fetch — zero extra host syncs.

    ``zero1_shards`` (with ``reduce_axis``) switches the update to the
    ZeRO-1 sharded optimizer (`optim.sharded`): gradients are
    reduce-scattered instead of pmean'd, each replica updates its 1/N
    shard of AdamW state, and fresh params are all-gathered — ``opt_state``
    is then a :class:`~bpe_transformer_tpu.optim.sharded.ShardedAdamWState`
    whose leaves arrive as this replica's block under ``shard_map``."""
    _check_zero1(zero1_shards, reduce_axis, health, dynamics, "train_step_fn")
    is_moe = config.ffn_type == "moe"
    with_aux = health and is_moe
    loss_fn = make_loss_fn(config, with_aux=with_aux, with_stats=dynamics)

    if zero1_shards is not None:

        def zero1_step(params, opt_state, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
            return _zero1_update(
                params, opt_state, loss, grads, hparams, reduce_axis,
                zero1_shards,
            )

        return zero1_step

    def step(params, opt_state: AdamWState, x, y):
        act_stats = None
        if dynamics:
            (loss, (aux, act_stats)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, x, y)
            moe_aux = aux if with_aux else None
        elif with_aux:
            (loss, moe_aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, x, y
            )
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
            moe_aux = None
        grads = _reduce_grads(grads, reduce_axis, hparams.grads_dtype)
        if reduce_axis is not None:
            loss = jax.lax.pmean(loss, reduce_axis)
            if moe_aux is not None:
                # The exported expert-balance stat must describe GLOBAL
                # routing, not shard 0's micro-batch.
                moe_aux = jax.lax.pmean(moe_aux, reduce_axis)
            if act_stats is not None:
                act_stats = _reduce_act_stats(act_stats, reduce_axis)
        # Dynamics reports the TRUE (pre-clip, post-pmean) gradient
        # magnitudes; the optimizer consumes the clipped tree below.
        raw_grads = grads
        grads, grad_norm = clip_by_global_norm(grads, hparams.grad_clip_norm)
        lr = cosine_schedule_jax(
            opt_state.step,
            hparams.max_learning_rate,
            hparams.min_learning_rate,
            hparams.warmup_iters,
            hparams.cosine_cycle_iters,
        )
        new_params, opt_state = adamw_update(
            params,
            grads,
            opt_state,
            lr,
            betas=hparams.betas,
            eps=hparams.eps,
            weight_decay=hparams.weight_decay,
        )
        metrics = {
            "loss": loss.astype(jnp.float32),
            "lr": lr.astype(jnp.float32),
            "grad_norm": grad_norm,
        }
        if health:
            from bpe_transformer_tpu.telemetry.health import health_metrics

            # Post-update params: optimizer-produced non-finites are caught
            # the same step they appear (before they can be checkpointed).
            metrics["health"] = health_metrics(loss, grads, new_params)
            if moe_aux is not None:
                metrics["health"]["moe_aux"] = moe_aux.astype(jnp.float32)
        if dynamics:
            from bpe_transformer_tpu.telemetry.dynamics import dynamics_metrics

            metrics["dynamics"] = dynamics_metrics(
                raw_grads, params, new_params, act_stats
            )
        return new_params, opt_state, metrics

    return step


def jit_step(step: Callable, **shardings) -> Callable:
    """``jax.jit`` of a train step, params and optimizer state donated: the
    one place every step factory compiles through, here and in `parallel/`
    (``shardings``: the GSPMD step's ``in_shardings``/``out_shardings``).

    On the TPU the step's layers compile as deduplicated calls
    (`utils/compile_cache.layered_program_options`: without it
    gpt2-small-32k's step is a 286 MB executable that takes 71 s to
    compile, against 65 MB and 37 s for the same instructions called; AOT
    for a described v5e, PERF.md §6 PR 27)."""
    return jax.jit(
        step, donate_argnums=(0, 1),
        compiler_options=layered_program_options(), **shardings,
    )


def make_train_step(
    config: ModelConfig,
    hparams: TrainHParams,
    health: bool = False,
    dynamics: bool = False,
) -> Callable:
    """Single-device jitted train step with buffer donation (params and opt
    state update in place in HBM)."""
    return jit_step(
        train_step_fn(config, hparams, health=health, dynamics=dynamics)
    )


def accumulate_grads(grad_fn, params, xs, ys, accum_steps: int, context: str = ""):
    """Scan-accumulated ``(loss, grads)`` over a leading microbatch dim.

    ``grad_fn(params, x, y) -> (loss, grads)`` runs once per microbatch
    inside a ``lax.scan`` (peak activation memory = one microbatch);
    gradients are summed in f32 and averaged, so the result equals a single
    step on the concatenated batch (mean-of-means over equal-size
    microbatches).  Shared by the single-device/dp/GSPMD accumulation body
    (:func:`grad_accum_step_fn`) and the sp ring-attention step
    (`parallel/sp.py`) so the subtle numerics live in exactly one place.
    """
    if xs.ndim != 3 or ys.ndim != 3 or xs.shape[0] != accum_steps:
        raise ValueError(
            f"{context or 'grad-accum step'} wants (accum_steps="
            f"{accum_steps}, micro_batch, seq) token ids, got xs "
            f"{xs.shape} — reshape the batch (training/loop.py does this "
            "for CLI runs)"
        )

    def body(carry, batch):
        loss_sum, grad_sum = carry
        loss, grads = grad_fn(params, batch[0], batch[1])
        grad_sum = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), grad_sum, grads
        )
        return (loss_sum + loss, grad_sum), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    (loss_sum, grad_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), (xs, ys)
    )
    inv = 1.0 / accum_steps
    return loss_sum * inv, jax.tree_util.tree_map(lambda g: g * inv, grad_sum)


def grad_accum_step_fn(
    config: ModelConfig,
    hparams: TrainHParams,
    accum_steps: int,
    reduce_axis: str | None = None,
    health: bool = False,
    dynamics: bool = False,
    zero1_shards: int | None = None,
) -> Callable:
    """Un-jitted accumulation body: one optimizer update from
    ``accum_steps`` microbatch gradients.

    The microbatch loop is a ``lax.scan`` over a leading ``(accum_steps,)``
    batch dim, so peak activation memory is ONE microbatch's forward/backward
    while the effective batch is ``accum_steps x`` larger — the standard way
    to train batch sizes that don't fit HBM on one chip.  Gradients and the
    loss are averaged (identical to a single step on the concatenated batch,
    since the loss is a mean over examples and microbatches are equal-size).

    ``reduce_axis`` pmean-reduces the accumulated grads/loss over a mapped
    mesh axis (the shard_map dp path) — ONE collective per update, after
    the local accumulation, not one per microbatch.

    ``health=True`` appends `telemetry.health` stats to ``metrics`` (as in
    :func:`train_step_fn`; the MoE ``moe_aux`` export is plain-step-only —
    the accumulation scan carries loss+grads, not per-microbatch aux).

    ``dynamics=True`` appends ``metrics["dynamics"]`` computed from the
    ACCUMULATED gradients and the update (per-layer norms, update ratios,
    non-finite localization); activation stats are absent on this path —
    the scan carries loss+grads, not per-microbatch activation taps.

    Signature: ``(params, opt_state, xs, ys) -> (params, opt_state,
    metrics)`` with ``xs/ys: (accum_steps, micro_batch, seq)``.

    ``zero1_shards`` swaps in the ZeRO-1 sharded update (as in
    :func:`train_step_fn`): the locally-ACCUMULATED gradients are
    reduce-scattered — still one collective per optimizer update.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    _check_zero1(
        zero1_shards, reduce_axis, health, dynamics, "grad_accum_step_fn"
    )
    loss_fn = make_loss_fn(config)

    if zero1_shards is not None:

        def zero1_step(params, opt_state, xs, ys):
            loss, grads = accumulate_grads(
                jax.value_and_grad(loss_fn), params, xs, ys, accum_steps
            )
            return _zero1_update(
                params, opt_state, loss, grads, hparams, reduce_axis,
                zero1_shards,
            )

        return zero1_step

    def step(params, opt_state: AdamWState, xs, ys):
        loss, grads = accumulate_grads(
            jax.value_and_grad(loss_fn), params, xs, ys, accum_steps
        )
        grads = _reduce_grads(grads, reduce_axis, hparams.grads_dtype)
        if reduce_axis is not None:
            loss = jax.lax.pmean(loss, reduce_axis)

        raw_grads = grads
        grads, grad_norm = clip_by_global_norm(grads, hparams.grad_clip_norm)
        lr = cosine_schedule_jax(
            opt_state.step,
            hparams.max_learning_rate,
            hparams.min_learning_rate,
            hparams.warmup_iters,
            hparams.cosine_cycle_iters,
        )
        new_params, opt_state = adamw_update(
            params,
            grads,
            opt_state,
            lr,
            betas=hparams.betas,
            eps=hparams.eps,
            weight_decay=hparams.weight_decay,
        )
        metrics = {
            "loss": loss.astype(jnp.float32),
            "lr": lr.astype(jnp.float32),
            "grad_norm": grad_norm,
        }
        if health:
            from bpe_transformer_tpu.telemetry.health import health_metrics

            metrics["health"] = health_metrics(loss, grads, new_params)
        if dynamics:
            from bpe_transformer_tpu.telemetry.dynamics import dynamics_metrics

            metrics["dynamics"] = dynamics_metrics(
                raw_grads, params, new_params, None
            )
        return new_params, opt_state, metrics

    return step


def make_grad_accum_train_step(
    config: ModelConfig,
    hparams: TrainHParams,
    accum_steps: int,
    health: bool = False,
    dynamics: bool = False,
) -> Callable:
    """Single-device jitted wrapper of :func:`grad_accum_step_fn`."""
    return jit_step(
        grad_accum_step_fn(
            config, hparams, accum_steps, health=health, dynamics=dynamics
        )
    )


def scanned_step_fn(
    config: ModelConfig,
    hparams: TrainHParams,
    inner_steps: int,
    reduce_axis: str | None = None,
    body: Callable | None = None,
    health: bool = False,
    dynamics: bool = False,
    zero1_shards: int | None = None,
) -> Callable:
    """Un-jitted body: ``inner_steps`` optimizer updates via ``lax.scan``.

    For small models a single update is microseconds of device work, so
    throughput is bounded by per-dispatch host latency; scanning the update
    body amortizes that launch cost over ``inner_steps`` real updates —
    identical math, one dispatch.

    ``reduce_axis`` threads through to each inner update's gradient pmean
    (the shard_map dp path).  ``body`` overrides the default single-update
    body with a caller-built one (the sp ring-attention step passes its own
    local update) so the scan/last-metrics plumbing lives in one place.

    Signature: ``(params, opt_state, xs, ys) -> (params, opt_state,
    metrics)`` where ``xs``/``ys`` carry a leading ``(inner_steps,)`` batch
    dim and ``metrics`` reports the LAST inner step (one device sync per
    call, like the per-step fn).
    """
    if inner_steps < 1:
        raise ValueError(f"inner_steps must be >= 1, got {inner_steps}")
    if body is None:
        body = train_step_fn(
            config, hparams, reduce_axis, health=health, dynamics=dynamics,
            zero1_shards=zero1_shards,
        )

    def multi(params, opt_state: AdamWState, xs, ys):
        def scan_body(carry, batch):
            p, s = carry
            p, s, metrics = body(p, s, batch[0], batch[1])
            return (p, s), metrics

        (params, opt_state), metrics = jax.lax.scan(
            scan_body, (params, opt_state), (xs, ys)
        )
        last = jax.tree_util.tree_map(lambda a: a[-1], metrics)
        return params, opt_state, last

    return multi


def make_scanned_train_step(
    config: ModelConfig,
    hparams: TrainHParams,
    inner_steps: int,
    health: bool = False,
    dynamics: bool = False,
) -> Callable:
    """Single-device jitted wrapper of :func:`scanned_step_fn`."""
    return jit_step(
        scanned_step_fn(
            config, hparams, inner_steps, health=health, dynamics=dynamics
        )
    )


def make_eval_step(config: ModelConfig) -> Callable:
    """Pure cross-entropy eval (no MoE router aux — that's a training
    regularizer; val_loss stays a log-perplexity comparable across configs).

    Honors ``loss_chunk_size`` so eval fits in the same memory envelope as
    the train step."""

    if config.loss_chunk:
        from bpe_transformer_tpu.models.transformer import (
            forward_hidden,
            lm_head_weight,
        )
        from bpe_transformer_tpu.ops.losses import lm_loss

        def eval_loss(params, x, y):
            hidden, _ = forward_hidden(params, x, config)
            return lm_loss(
                hidden, lm_head_weight(params, config), y, config.loss_chunk
            )

    else:

        def eval_loss(params, x, y):
            logits = forward(params, x, config)
            return cross_entropy(logits, y)

    return jax.jit(eval_loss)
