"""Pipeline parallelism: GPipe-style microbatched stages over a ``pp`` axis.

No reference precedent (SURVEY §2.4 lists PP as absent); built TPU-first:

* the layer stack is split into ``pp`` contiguous stages, one per mesh rank
  along the ``pp`` axis; each rank holds ONLY its stage's block parameters
  (leading stage dim sharded via ``shard_map``);
* activations flow rank -> rank+1 through ``lax.ppermute`` (neighbor
  exchange over ICI) inside a ``lax.scan`` over ``num_micro + pp - 1``
  pipeline ticks — microbatch ``t`` enters stage 0 at tick ``t`` and leaves
  the last stage at tick ``t + pp - 1``;
* the backward pipeline is not hand-written: ``jax.value_and_grad``
  differentiates through the scan + ppermute (the transpose of a ppermute is
  the reverse ppermute), yielding the reverse-order schedule automatically;
* composes with data parallelism on a 2-D ``(data, pp)`` mesh — batch split
  over ``data``, gradients pmean'd over ``data``.

Embeddings / final norm / LM head are replicated on every rank ("shared"):
only rank 0 reads the embedding and only the last rank applies the head, so
their gradients are psum'd over ``pp`` to become global.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.transformer import (
    Params,
    policy_block,
)
from bpe_transformer_tpu.ops.core import embedding, rmsnorm
from bpe_transformer_tpu.ops.rope import rope_tables
from bpe_transformer_tpu.optim.adamw import AdamWState, adamw_init, adamw_update
from bpe_transformer_tpu.optim.schedule import cosine_schedule_jax
from bpe_transformer_tpu.training.train_step import TrainHParams, jit_step

P = PartitionSpec


# ------------------------------------------------------------ param layout


def stack_pipeline_params(params: Params, pp: int) -> dict:
    """Re-layout a transformer param pytree for ``pp`` pipeline stages.

    Returns ``{"stages": ..., "shared": ...}`` where every ``stages`` leaf is
    stacked to ``(pp, layers_per_stage, ...)`` (dim 0 shards over the ``pp``
    mesh axis) and ``shared`` holds the replicated embedding / final norm /
    LM head.
    """
    layers = params["layers"]
    if len(layers) % pp:
        raise ValueError(
            f"num_layers={len(layers)} not divisible by pipeline size {pp}"
        )
    per_stage = len(layers) // pp
    stage_groups = [
        layers[s * per_stage : (s + 1) * per_stage] for s in range(pp)
    ]
    # blocks-within-stage stacked on dim 0, then stages stacked on a new dim 0.
    stages = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *[
            jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *group)
            for group in stage_groups
        ],
    )
    shared = {
        "token_embeddings": params["token_embeddings"],
        "ln_final": params["ln_final"],
    }
    if "lm_head" in params:  # absent under tie_embeddings
        shared["lm_head"] = params["lm_head"]
    return {"stages": stages, "shared": shared}


def unstack_pipeline_params(pp_params: dict) -> Params:
    """Inverse of :func:`stack_pipeline_params` (for checkpoint interop)."""
    stages = pp_params["stages"]
    leaves = jax.tree_util.tree_leaves(stages)
    pp, per_stage = leaves[0].shape[0], leaves[0].shape[1]
    layers = [
        jax.tree_util.tree_map(lambda l: l[s, i], stages)
        for s in range(pp)
        for i in range(per_stage)
    ]
    out = {
        "token_embeddings": pp_params["shared"]["token_embeddings"],
        "layers": layers,
        "ln_final": pp_params["shared"]["ln_final"],
    }
    if "lm_head" in pp_params["shared"]:
        out["lm_head"] = pp_params["shared"]["lm_head"]
    return out


# ------------------------------------------------------------- loss (local)


def _pp_loss_fn(
    config: ModelConfig,
    num_micro: int,
    pp_axis: str,
    pp_size: int,
) -> Callable:
    """Per-rank pipelined forward+loss: ``(pp_params, x, y) -> mean CE``.

    Runs under ``shard_map``; ``pp_params["stages"]`` leaves arrive shaped
    ``(1, layers_per_stage, ...)`` (this rank's stage).
    """

    def loss_fn(pp_params, x, y):
        stages, shared = pp_params["stages"], pp_params["shared"]
        rank = lax.axis_index(pp_axis)
        batch, seq = x.shape
        if batch % num_micro:
            raise ValueError(
                f"per-rank batch {batch} not divisible by "
                f"num_microbatches {num_micro}"
            )
        mb = batch // num_micro
        x_mb = x.reshape(num_micro, mb, seq)
        y_mb = y.reshape(num_micro, mb, seq)

        act_dtype = jnp.dtype(config.activation_dtype)
        positions = jnp.arange(seq)
        rope_cos_sin = None
        if not config.remove_rope:
            cos, sin = rope_tables(
                config.d_head, config.context_length, config.rope_theta
            )
            rope_cos_sin = (cos.astype(act_dtype), sin.astype(act_dtype))

        embed_w = shared["token_embeddings"].astype(act_dtype)
        per_stage = jax.tree_util.tree_leaves(stages)[0].shape[1]

        def apply_stage(act):
            aux_sum = jnp.zeros((), jnp.float32)
            # Graduated remat policy (PR 13): the same policy dispatch as
            # the single-program forward — full/dots_saveable checkpoint
            # the block (in_scan: the tick scan already bars CSE),
            # save_attn keeps the attention kernel's residuals and remats
            # only the FFN tail.  The deprecated remat bool maps to full.
            block = policy_block(config, in_scan=True)
            for i in range(per_stage):
                block_params = jax.tree_util.tree_map(
                    lambda l: l[0, i].astype(act_dtype), stages
                )
                act, aux = block(
                    act, block_params, config, rope_cos_sin, positions, None
                )
                aux_sum = aux_sum + aux
            return act, aux_sum

        def head_loss(act, targets):
            if not config.remove_rmsnorm:
                act = rmsnorm(
                    act, shared["ln_final"].astype(act_dtype), config.norm_eps
                )
            from bpe_transformer_tpu.ops.losses import lm_loss

            head_w = shared.get("lm_head", shared["token_embeddings"])
            return lm_loss(act, head_w, targets, config.loss_chunk)

        fwd_perm = [(i, (i + 1) % pp_size) for i in range(pp_size)]
        ticks = num_micro + pp_size - 1

        def tick(carry, t):
            recv, loss_sum, aux_total = carry
            # Only rank 0 pays for the embedding lookup; other ranks take the
            # ppermute'd activation (lax.cond executes a single branch).
            enter = jnp.clip(t, 0, num_micro - 1)
            act_in = lax.cond(
                rank == 0,
                lambda: embedding(
                    embed_w,
                    lax.dynamic_index_in_dim(x_mb, enter, 0, keepdims=False),
                ).astype(act_dtype),
                lambda: recv,
            )
            act_out, aux = apply_stage(act_in)
            # MoE router aux: count only the ticks where THIS rank holds a
            # real microbatch (warmup/drain ticks process garbage
            # activations whose routing must not leak into the loss or its
            # gradient).  Each rank contributes its own stages' aux to its
            # LOCAL loss — the sum over ranks seeds exactly once per term,
            # same argument as the head loss below.
            valid = (t >= rank) & (t - rank < num_micro)
            aux_total = aux_total + jnp.where(valid, aux, 0.0)

            # Only the last rank pays for the full-vocab head matmul + CE.
            done = t - (pp_size - 1)
            done_idx = jnp.clip(done, 0, num_micro - 1)
            take = (rank == pp_size - 1) & (done >= 0)
            mb_loss = lax.cond(
                take,
                lambda: head_loss(
                    act_out,
                    lax.dynamic_index_in_dim(y_mb, done_idx, 0, keepdims=False),
                ),
                lambda: jnp.zeros((), jnp.float32),
            )
            loss_sum = loss_sum + mb_loss

            recv_next = lax.ppermute(act_out, pp_axis, fwd_perm)
            return (recv_next, loss_sum, aux_total), None

        d = config.d_model
        init = (
            jnp.zeros((mb, seq, d), act_dtype),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        (_, loss_sum, aux_total), _ = lax.scan(tick, init, jnp.arange(ticks))
        # LOCAL loss: CE is nonzero only on the last rank; each rank adds
        # its own stages' router aux.  Deliberately NOT psum'd here —
        # differentiating a psum inside shard_map would seed one cotangent
        # per rank and overcount stage gradients pp times; with local
        # losses the total = sum of rank-local terms, each seeded exactly
        # once, and the ppermute transposes route every rank its true
        # gradient.  The caller psums the VALUE for metrics.
        local = loss_sum
        if config.ffn_type == "moe":
            local = local + config.router_aux_weight * aux_total
        return local / num_micro

    return loss_fn


# --------------------------------------------------------------- train step


def make_pp_train_step(
    config: ModelConfig,
    hparams: TrainHParams,
    mesh: Mesh,
    *,
    num_microbatches: int = 4,
    pp_axis: str = "pp",
    dp_axis: str = "data",
    accum_steps: int = 1,
    inner_steps: int = 1,
) -> Callable:
    """Jitted pipeline(+data)-parallel step over ``mesh``.

    Signature: ``(pp_params, opt_state, x, y) -> (pp_params, opt_state,
    metrics)`` where ``pp_params`` comes from :func:`stack_pipeline_params`
    (placed with :func:`shard_pp_params`) and ``opt_state`` from
    :func:`jax.eval_shape`-compatible :func:`~bpe_transformer_tpu.optim.
    adamw.adamw_init` over it.

    ``accum_steps > 1``: gradient accumulation around the pipeline — each
    accumulation slice runs the FULL GPipe schedule (all
    ``num_microbatches`` ticks), gradients sum in f32 via the shared
    :func:`~bpe_transformer_tpu.training.train_step.accumulate_grads`
    (same numerics as the dp/sp/GSPMD paths) and the optimizer updates
    once.  This stacks a second, memory-motivated microbatching level on
    top of the pipeline's own (which exists to fill the bubble, not to
    shrink activations): peak activation memory is one accum slice's
    pipeline.  Batches become ``(accum_steps, batch, seq)`` — feed through
    ``shard_batch(..., stacked=True)``.

    ``inner_steps > 1``: several FULL updates per dispatch (``lax.scan``
    over the whole update body inside the pipelined program, via the
    shared :func:`~bpe_transformer_tpu.training.train_step.
    scanned_step_fn`); batches ``(inner_steps, batch, seq)``, also
    ``stacked=True``.  Metrics report the last update.
    """
    if pp_axis not in mesh.shape:
        raise ValueError(f"mesh {dict(mesh.shape)} lacks axis {pp_axis!r}")
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if inner_steps < 1:
        raise ValueError(f"inner_steps must be >= 1, got {inner_steps}")
    if accum_steps > 1 and inner_steps > 1:
        raise ValueError("accum_steps and inner_steps cannot both exceed 1")
    pp_size = mesh.shape[pp_axis]
    use_dp = dp_axis in mesh.shape and mesh.shape[dp_axis] > 1
    loss_fn = _pp_loss_fn(config, num_microbatches, pp_axis, pp_size)

    def step(pp_params, opt_state: AdamWState, x, y):
        if accum_steps > 1:
            from bpe_transformer_tpu.training.train_step import accumulate_grads

            local_loss, grads = accumulate_grads(
                jax.value_and_grad(loss_fn), pp_params, x, y, accum_steps,
                context="pp grad-accum step",
            )
        else:
            local_loss, grads = jax.value_and_grad(loss_fn)(pp_params, x, y)
        loss = lax.psum(local_loss, pp_axis)  # loss lives on the last rank
        # Shared params saw real gradients on one rank only (embed on rank 0,
        # head/final-norm on the last): psum over pp makes them global.
        grads["shared"] = lax.psum(grads["shared"], pp_axis)
        if use_dp:
            # The dp gradient all-reduce optionally crosses at bf16
            # (train_step._reduce_grads semantics; the pp-axis psums above
            # are correctness sums of DISJOINT partials and stay f32).
            narrow = jnp.dtype(hparams.grads_dtype)
            if narrow != jnp.float32:
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(narrow), grads
                )
            grads = lax.pmean(grads, dp_axis)
            if narrow != jnp.float32:
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads
                )
            loss = lax.pmean(loss, dp_axis)

        # Global grad-norm: stage grads live on distinct pp ranks (sum their
        # squares across pp); shared grads are identical on every rank.
        stage_sq = sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(grads["stages"])
        )
        shared_sq = sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(grads["shared"])
        )
        global_norm = jnp.sqrt(lax.psum(stage_sq, pp_axis) + shared_sq)
        scale = jnp.minimum(
            1.0, hparams.grad_clip_norm / (global_norm + 1e-6)
        )
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

        lr = cosine_schedule_jax(
            opt_state.step,
            hparams.max_learning_rate,
            hparams.min_learning_rate,
            hparams.warmup_iters,
            hparams.cosine_cycle_iters,
        )
        pp_params_new, opt_state = adamw_update(
            pp_params,
            grads,
            opt_state,
            lr,
            betas=hparams.betas,
            eps=hparams.eps,
            weight_decay=hparams.weight_decay,
        )
        metrics = {"loss": loss, "lr": lr, "grad_norm": global_norm}
        return pp_params_new, opt_state, metrics

    if inner_steps > 1:
        from bpe_transformer_tpu.training.train_step import scanned_step_fn

        step = scanned_step_fn(config, hparams, inner_steps, body=step)

    param_specs = {"stages": P(pp_axis), "shared": P()}
    opt_specs = AdamWState(step=P(), m=param_specs, v=param_specs)
    stacked = accum_steps > 1 or inner_steps > 1
    if use_dp:
        batch_spec = P(None, dp_axis) if stacked else P(dp_axis)
    else:
        batch_spec = P()
    metric_specs = {"loss": P(), "lr": P(), "grad_norm": P()}

    mapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(param_specs, opt_specs, batch_spec, batch_spec),
        out_specs=(param_specs, opt_specs, metric_specs),
        check_vma=False,
    )
    return jit_step(mapped)


def shard_pp_params(pp_params: dict, mesh: Mesh, pp_axis: str = "pp"):
    """Place stacked pipeline params: stages split over ``pp``, shared replicated."""
    stage_sh = NamedSharding(mesh, P(pp_axis))
    repl = NamedSharding(mesh, P())
    return {
        "stages": jax.device_put(pp_params["stages"], stage_sh),
        "shared": jax.device_put(pp_params["shared"], repl),
    }


def init_pp_opt_state(pp_params: dict, mesh: Mesh, pp_axis: str = "pp") -> AdamWState:
    """AdamW state over stacked pipeline params, sharded to match."""
    state = adamw_init(pp_params)
    stage_sh = NamedSharding(mesh, P(pp_axis))
    repl = NamedSharding(mesh, P())

    def place(tree):
        return {
            "stages": jax.device_put(tree["stages"], stage_sh),
            "shared": jax.device_put(tree["shared"], repl),
        }

    return AdamWState(
        step=jax.device_put(state.step, repl),
        m=place(state.m),
        v=place(state.v),
    )
