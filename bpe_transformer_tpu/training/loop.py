"""The training loop: tokenized memmap -> sharded jitted steps -> checkpoints.

The reference has no training loop at all (SURVEY §3.5 — it is implied by
the union of its adapters); this makes it real, TPU-first: one jitted update
(single-chip, explicit-DP, or GSPMD-sharded), host work limited to batch
sampling and metric readback, periodic eval and preemption-safe checkpoints,
and tokens/sec/chip accounting.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
from pathlib import Path

import jax
import numpy as np

from bpe_transformer_tpu.checkpointing import (
    load_checkpoint,
    save_checkpoint,
    save_checkpoint_sharded,
)
from bpe_transformer_tpu.data.dataset import get_batch
from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.transformer import init_params
from bpe_transformer_tpu.optim.adamw import AdamWState, adamw_init
from bpe_transformer_tpu.training.train_step import (
    TrainHParams,
    make_eval_step,
    make_train_step,
)
from bpe_transformer_tpu.telemetry import (
    FlightRecorder,
    MetricsLogger,
    StepTimer,
    Telemetry,
    Watchdog,
    dynamics_record,
    flatten_dynamics,
    flatten_health,
    install_compile_counter,
    install_gc_counter,
    nonfinite_fields,
    run_manifest,
    sample_resources,
    tree_bytes_per_device,
)


@dataclasses.dataclass(frozen=True)
class LoopConfig:
    steps: int = 1000
    batch_size: int = 32
    log_every: int = 50
    eval_every: int = 500
    eval_batches: int = 8
    checkpoint_every: int = 1000
    checkpoint_dir: str | None = None
    #: Optional observability sinks (telemetry.sinks): JSONL file of step
    #: records, and a wandb project (gated import — only used when set).
    #: The JSONL stream is the unified telemetry stream: a run-manifest
    #: header, step records, span/event records, and a footer.
    metrics_jsonl: str | None = None
    wandb_project: str | None = None
    #: Compute device-side health stats inside the jitted step (telemetry.
    #: health: non-finite detection, per-layer-group grad/param norms, MoE
    #: load balance) and log them at every log_every sync.  Opt-in: the
    #: default step is byte-identical to before.  Not supported with
    #: parallel="sp"/"pp" (those strategies build their own update bodies).
    health_stats: bool = False
    #: Emit kind="dynamics" training-introspection records every N steps
    #: (0 = off; telemetry.dynamics): per-layer grad/param norms,
    #: update-to-param ratios, per-block activation RMS/absmax + attention
    #: entropy, and per-tensor non-finite localization.  Everything is
    #: computed INSIDE the jitted step and fetched with the existing
    #: log_every sync — zero additional device→host transfers — so N must
    #: be a multiple of log_every.  Not supported with parallel="sp"/"pp"
    #: (same constraint as health_stats).
    dynamics_every: int = 0
    #: Emit kind="attribution" performance-attribution records every N
    #: steps (0 = off; telemetry.attribution): the measured compute /
    #: collective / host-gap split of wall step time plus, once per run,
    #: XLA cost-model roofline verdicts for the compiled step.  The probe
    #: (a non-donating AOT copy of the update) compiles and runs ONLY at
    #: attribution boundaries — untouched steps pay zero extra host syncs
    #: — so N must be a multiple of log_every.  Not supported with
    #: parallel="sp"/"pp" (same constraint as dynamics_every).
    attribution_every: int = 0
    #: Enable the telemetry watchdog: a background thread flags hung steps
    #: (no metric sync within watchdog_factor x the trailing median step
    #: time), and non-finite states detected at a log boundary follow
    #: watchdog_policy — "raise" (dump state to the telemetry stream, then
    #: raise NonFiniteError), "skip" (record the event and keep going), or
    #: "rollback" (reload the last valid checkpoint, skip the offending
    #: data window, retry under the max_rollbacks/recovery_min_progress
    #: crash-loop budget; requires checkpoint_dir, not supported with
    #: parallel="pp").
    watchdog: bool = False
    watchdog_factor: float = 10.0
    watchdog_policy: str = "raise"
    #: Crash-loop breaker for watchdog_policy="rollback": abort (raise
    #: NonFiniteError) after max_rollbacks rollbacks without at least
    #: recovery_min_progress steps of training between detections — a
    #: failure that is not batch-local must not crash-loop the pod slice.
    max_rollbacks: int = 3
    recovery_min_progress: int = 1
    #: Retention GC: keep only the newest N step_*.ckpt snapshots (None =
    #: keep everything).  The snapshot latest.ckpt points at is never
    #: deleted, quarantined *.corrupt snapshots are left as evidence, and
    #: stranded .tmp/.old crash debris older than the newest snapshot is
    #: reclaimed (resilience/retention.py).
    keep_checkpoints: int | None = None
    seed: int = 0
    #: None -> single device; "dp" -> shard_map psum; "sp" -> context
    #: parallelism (ring attention over a data x seq mesh); "pp" -> GPipe
    #: pipeline stages over a pp axis; "fsdp"/"tp"/"ep" combinations
    #: (e.g. "fsdp_tp", "dp_ep") -> GSPMD with those shardings.
    parallel: str | None = None
    mesh_axes: dict | None = None  # e.g. {"data": 8} or {"data": 4, "model": 2}
    pp_microbatches: int = 4  # pipeline microbatches (parallel="pp")
    #: With parallel="sp": run the balanced zig-zag (striped) ring schedule
    #: (~2x less causal attention work at large seq meshes).
    sp_zigzag: bool = False
    #: With parallel="sp": Ulysses all-to-all head scatter instead of the
    #: ring (num_heads must be a multiple of the seq axis size; see
    #: parallel/ulysses.py).
    sp_ulysses: bool = False
    #: Optimizer updates per XLA dispatch (lax.scan over the update body).
    #: >1 amortizes host launch latency for small models — identical math.
    #: Works single-device and under dp/sp/GSPMD meshes (the scan compiles
    #: inside the sharded program); not with pp, which already amortizes
    #: dispatch over its microbatches.  log/eval/checkpoint cadences must
    #: be multiples.
    inner_steps: int = 1
    #: Optimizer-state sharding across the data-parallel axis.  "zero1"
    #: (with parallel="dp" or a GSPMD strategy) shards AdamW m/v and the
    #: fp32 master weights 1/N per chip (optim/sharded.py,
    #: Xu et al. arXiv:2004.13336): the dp path reduce-scatters gradients,
    #: updates each replica's shard, and all-gathers fresh params; GSPMD
    #: strategies express the same schedule through NamedSharding
    #: annotations on the opt-state leaves.  Not supported with sp/pp, and
    #: (dp path) not combinable with health_stats/dynamics_every — the
    #: sharded update never materializes the global gradient tree those
    #: taps read.
    opt_sharding: str | None = None
    #: Batch prefetch depth (data/dataset.BatchPrefetcher): N batches are
    #: sampled + stacked on a jax-free background thread while the device
    #: runs the current step, so the main thread only pays the
    #: async-enqueued device transfer — the host-sampling share of
    #: host_gap_frac collapses.  Batches stay a pure function of the
    #: iteration, so determinism/resume are unaffected.  0 (the library
    #: default) is the synchronous feed; the CLI defaults to 1.
    prefetch: int = 0
    #: Microbatches per optimizer update (gradient accumulation): each
    #: batch of ``batch_size`` is split into this many sequential
    #: microbatches, capping activation memory at one microbatch while the
    #: update math is identical.  Works single-device and under dp/sp/GSPMD
    #: meshes (one collective per update, after local accumulation — under
    #: sp that's the long-context HBM-relief combo); not with pp, which
    #: already microbatches.  Must divide batch_size (and the microbatch
    #: must divide the data mesh axis); mutually exclusive with
    #: inner_steps > 1.
    grad_accum_steps: int = 1
    #: Overlap checkpoint serialization/IO with training: save() snapshots
    #: to host synchronously and writes in a background thread (at most one
    #: write in flight).  Costs one host-RAM copy of the state per save.
    async_checkpoint: bool = False


def train(
    model_config: ModelConfig,
    hparams: TrainHParams,
    loop: LoopConfig,
    train_data: np.ndarray,
    val_data: np.ndarray | None = None,
    resume_from: str | Path | None = None,
    log_fn=print,
    fault_injector=None,
) -> dict:
    """Run the loop; returns a summary dict (final/eval losses, throughput).

    ``fault_injector`` (resilience.faults.FaultInjector) defaults to the
    ``BT_FAULTS`` env plan — a no-op in production, the chaos harness's
    entry point in tests.  A run stopped by SIGTERM/SIGINT writes an
    emergency checkpoint, emits a ``kind="preemption"`` record, and returns
    with ``summary["preempted"]`` set (the CLI maps it to
    ``EXIT_PREEMPTED``).
    """
    # Imported here, not at module top: parallel.train_step reuses the
    # update body from training.train_step, so a top-level import would be
    # circular through the package __init__s.
    from bpe_transformer_tpu.parallel import (
        make_dp_train_step,
        make_gspmd_train_step,
        make_mesh,
        make_sp_train_step,
        partitioned_config,
        shard_batch,
        shard_params,
        shard_sp_batch,
    )
    from bpe_transformer_tpu.data.dataset import (
        BatchPrefetcher,
        check_dataset_geometry,
    )
    from bpe_transformer_tpu.resilience.faults import FaultInjector
    from bpe_transformer_tpu.resilience.rollback import (
        RollbackBudget,
        RollbackExhausted,
    )
    from bpe_transformer_tpu.resilience.signals import GracefulShutdown
    from bpe_transformer_tpu.telemetry.watchdog import NonFiniteError

    injector = fault_injector if fault_injector is not None else FaultInjector.from_env()

    # The telemetry narrator exists from the first line so setup work is
    # spanned; records are buffered until the sinks exist (attach below).
    telemetry = Telemetry()
    setup_span = telemetry.start_span("setup")
    # Arm the process-wide compile counter before the first trace so every
    # jit cache miss of this run lands in the kind="resources" records.
    install_compile_counter()
    install_gc_counter()

    if loop.health_stats and loop.parallel in ("sp", "pp"):
        raise ValueError(
            f'health_stats is not supported with parallel="{loop.parallel}" '
            "(sp/pp build their own update bodies); drop --health-stats or "
            "use a dp/GSPMD strategy"
        )
    if loop.dynamics_every < 0:
        raise ValueError(
            f"dynamics_every must be >= 0, got {loop.dynamics_every}"
        )
    if loop.dynamics_every:
        if loop.parallel in ("sp", "pp"):
            raise ValueError(
                f'dynamics_every is not supported with parallel='
                f'"{loop.parallel}" (sp/pp build their own update bodies); '
                "drop --dynamics-every or use a dp/GSPMD strategy"
            )
        if loop.dynamics_every % loop.log_every:
            raise ValueError(
                f"dynamics_every={loop.dynamics_every} must be a multiple "
                f"of log_every={loop.log_every} — dynamics records ride "
                "the log-cadence metric fetch (no extra host syncs)"
            )
    if loop.attribution_every < 0:
        raise ValueError(
            f"attribution_every must be >= 0, got {loop.attribution_every}"
        )
    if loop.attribution_every:
        if loop.parallel in ("sp", "pp"):
            raise ValueError(
                f'attribution_every is not supported with parallel='
                f'"{loop.parallel}" (sp/pp build their own update bodies); '
                "drop --attribution-every or use a dp/GSPMD strategy"
            )
        if loop.attribution_every % loop.log_every:
            raise ValueError(
                f"attribution_every={loop.attribution_every} must be a "
                f"multiple of log_every={loop.log_every} — attribution "
                "probes run at log boundaries so untouched steps pay zero "
                "extra host syncs"
            )
    if loop.opt_sharding is not None:
        if loop.opt_sharding != "zero1":
            raise ValueError(
                f"unknown opt_sharding: {loop.opt_sharding!r} (only "
                '"zero1" is implemented)'
            )
        if loop.parallel in (None, "sp", "pp"):
            raise ValueError(
                'opt_sharding="zero1" needs a data-parallel mesh to shard '
                'across — use --parallel dp or a GSPMD strategy (fsdp '
                "already shards its optimizer state with the params)"
            )
        if loop.parallel == "dp" and (loop.health_stats or loop.dynamics_every):
            raise ValueError(
                'opt_sharding="zero1" with parallel="dp" does not support '
                "health_stats/dynamics_every — the reduce-scatter update "
                "never materializes the global gradient tree those taps "
                "read; drop them or use a GSPMD strategy"
            )
    if loop.prefetch < 0:
        raise ValueError(f"prefetch must be >= 0, got {loop.prefetch}")
    if loop.watchdog and loop.watchdog_policy not in Watchdog.POLICIES:
        # Validate BEFORE any sink opens: a bad policy must not leak an open
        # JSONL handle or an unfinished wandb run.
        raise ValueError(
            f"watchdog_policy must be one of {Watchdog.POLICIES}, "
            f"got {loop.watchdog_policy!r}"
        )
    rollback_mode = loop.watchdog and loop.watchdog_policy == "rollback"
    if rollback_mode:
        if loop.checkpoint_dir is None:
            raise ValueError(
                'watchdog_policy="rollback" needs checkpoint_dir — recovery '
                "reloads the last valid snapshot"
            )
        if loop.parallel == "pp":
            raise ValueError(
                'watchdog_policy="rollback" is not supported with '
                'parallel="pp" (checkpoints carry the stacked-stage layout); '
                'use "raise" or "skip"'
            )
        if loop.checkpoint_every % loop.log_every:
            # Detection happens at log boundaries; keeping every checkpoint
            # boundary ON a log boundary guarantees a poisoned-but-not-yet-
            # detected state can never be checkpointed (the rollback path
            # skips the save at the detecting boundary).
            raise ValueError(
                f"checkpoint_every={loop.checkpoint_every} must be a "
                f"multiple of log_every={loop.log_every} under "
                'watchdog_policy="rollback" — checkpoints must land on '
                "detection boundaries so a non-finite state is never saved"
            )
    # Fail on an undersized token file NOW with a geometry message, not as
    # an opaque index error on some later batch (data/dataset.py).
    check_dataset_geometry(
        train_data, model_config.context_length, loop.batch_size,
        name="train_data",
    )
    if val_data is not None:
        check_dataset_geometry(
            val_data, model_config.context_length, loop.batch_size,
            name="val_data",
        )

    mesh = None
    if loop.parallel is not None:
        mesh_axes = loop.mesh_axes
        if mesh_axes is None and loop.parallel == "sp":
            # sp needs a seq axis; default to pure context parallelism.
            mesh_axes = {"data": 1, "seq": len(jax.devices())}
        if mesh_axes is None and loop.parallel == "pp":
            mesh_axes = {"pp": len(jax.devices())}
        mesh = make_mesh(mesh_axes)
        # A strategy whose axis is absent from the mesh would silently
        # degrade to replication — fail loudly instead.
        required_axes = {
            "dp": "data",
            "tp": "model",
            "ep": "expert",
            "fsdp": "data",
            "pp": "pp",
        }
        for token in loop.parallel.split("_"):
            needed = required_axes.get(token)
            if needed is not None and needed not in mesh.shape:
                raise ValueError(
                    f'parallel="{loop.parallel}" requires a mesh with a '
                    f'"{needed}" axis, e.g. --mesh data=2,{needed}=4'
                )
        if loop.opt_sharding == "zero1" and "data" not in mesh.shape:
            # No data axis -> nothing to shard across: zero1 would silently
            # degrade to a replicated optimizer.  Fail loudly instead.
            raise ValueError(
                'opt_sharding="zero1" requires a mesh with a "data" axis '
                "to shard the optimizer state across, e.g. --mesh "
                "data=4,model=2"
            )
        if loop.parallel == "sp":
            seq_size = mesh.shape.get("seq")
            if seq_size is None:
                raise ValueError(
                    'parallel="sp" requires a mesh with a "seq" axis, e.g. '
                    '--mesh data=2,seq=4'
                )
            if model_config.context_length % seq_size:
                raise ValueError(
                    f"context_length {model_config.context_length} must be "
                    f"divisible by the seq mesh axis ({seq_size})"
                )

    def load_state(src: Path):
        """Fallback-aware state restore shared by resume and NaN rollback:
        verify (jax-free checksums) -> load -> ``(params, opt_state,
        iteration, used_path)``.  A corrupt snapshot is quarantined with a
        ``.corrupt`` suffix and the newest prior valid sibling is loaded
        instead of crashing (checkpointing.load_checkpoint_with_fallback)."""
        from bpe_transformer_tpu.checkpointing.checkpoint import (
            load_checkpoint_with_fallback,
            sharded_checkpoint_exists,
        )

        src = Path(src)
        # A directory may be a checkpoints PARENT (resume from its latest
        # snapshot) or a sharded checkpoint itself (has a manifest — or a
        # crash-stranded orphan sibling the loader recovers from).
        if src.is_dir() and not sharded_checkpoint_exists(src):
            src = src / "latest.ckpt"
        gspmd = mesh is not None and loop.parallel not in ("dp", "sp", "pp")

        def loader(path):
            if gspmd and sharded_checkpoint_exists(path):
                # Streaming re-placement: build the target shardings from
                # the ABSTRACT param tree (no init compute) so each leaf
                # lands on its mesh devices as it is read — the full FSDP
                # state is never staged on host in one buffer.
                from bpe_transformer_tpu.checkpointing import (
                    load_checkpoint_sharded,
                )
                from bpe_transformer_tpu.parallel.sharding import param_shardings
                from jax.sharding import NamedSharding, PartitionSpec

                abstract = jax.eval_shape(
                    lambda: init_params(jax.random.PRNGKey(0), model_config)
                )
                pshard = param_shardings(abstract, mesh, loop.parallel)
                moment_sh = pshard
                if loop.opt_sharding == "zero1":
                    from bpe_transformer_tpu.parallel.sharding import (
                        zero1_opt_shardings,
                    )

                    moment_sh = zero1_opt_shardings(
                        abstract, mesh, loop.parallel
                    )
                return load_checkpoint_sharded(
                    path,
                    shardings={
                        "params": pshard,
                        "opt_state": AdamWState(
                            step=NamedSharding(mesh, PartitionSpec()),
                            m=moment_sh,
                            v=moment_sh,
                        ),
                    },
                )
            return load_checkpoint(path)

        payload, used = load_checkpoint_with_fallback(src, loader=loader)
        loaded_params = payload["params"]
        # restore_opt_state adapts whatever the checkpoint holds — a dense
        # AdamWState, a ZeRO-1 ShardedAdamWState (possibly from a different
        # dp width), or nothing — to THIS run's optimizer-sharding mode, so
        # pre-sharding checkpoints resume into sharded runs and vice versa.
        from bpe_transformer_tpu.optim.sharded import restore_opt_state

        zero1_dp = loop.parallel == "dp" and loop.opt_sharding == "zero1"
        loaded_opt = restore_opt_state(
            payload["opt_state"],
            loaded_params,
            zero1_shards=mesh.shape["data"] if zero1_dp else None,
            mesh=mesh if zero1_dp else None,
        )
        return loaded_params, loaded_opt, payload["iteration"], used

    start_iteration = 0
    if resume_from is not None:
        params, opt_state, start_iteration, used_path = load_state(
            Path(resume_from)
        )
        log_fn(f"resumed from {used_path} at iteration {start_iteration}")
    else:
        params = init_params(jax.random.PRNGKey(loop.seed), model_config)
        opt_state = None  # built after placement

    if mesh is not None and loop.parallel not in ("dp", "sp", "pp"):
        params = shard_params(params, mesh, loop.parallel)
    if loop.parallel == "pp":
        from bpe_transformer_tpu.parallel.pp import (
            init_pp_opt_state,
            shard_pp_params,
            stack_pipeline_params,
        )

        pp_size = mesh.shape["pp"]
        # A resumed checkpoint may already carry the stacked pipeline layout;
        # a dense checkpoint (params AND optimizer moments) is re-stacked.
        if "stages" in params:
            n_stages = jax.tree_util.tree_leaves(params["stages"])[0].shape[0]
            if n_stages != pp_size:
                raise ValueError(
                    f"checkpoint has {n_stages} pipeline stages but the mesh "
                    f"pp axis is {pp_size}; resume with --mesh ...,pp={n_stages}"
                )
        if "stages" not in params:
            params = stack_pipeline_params(params, pp_size)
            if opt_state is not None:
                opt_state = AdamWState(
                    step=opt_state.step,
                    m=stack_pipeline_params(opt_state.m, pp_size),
                    v=stack_pipeline_params(opt_state.v, pp_size),
                )
        params = shard_pp_params(params, mesh)
        if opt_state is None:
            opt_state = init_pp_opt_state(params, mesh)
    zero1_dp = loop.parallel == "dp" and loop.opt_sharding == "zero1"
    zero1_gspmd = (
        loop.opt_sharding == "zero1"
        and mesh is not None
        and loop.parallel not in ("dp", "sp", "pp")
    )
    if opt_state is None:
        if zero1_dp:
            from bpe_transformer_tpu.optim.sharded import sharded_adamw_init

            opt_state = sharded_adamw_init(
                params, mesh.shape["data"], mesh=mesh
            )
        else:
            opt_state = adamw_init(params)
    if zero1_gspmd:
        # Commit the moments to their ZeRO-1 shardings up front (1/N per
        # chip from step 0); a resumed dense state gets placed the same
        # way.  No-op for leaves already on the right sharding.
        from bpe_transformer_tpu.parallel.sharding import zero1_opt_shardings

        moment_sh = zero1_opt_shardings(params, mesh, loop.parallel)
        opt_state = AdamWState(
            step=jax.numpy.asarray(opt_state.step),
            m=jax.device_put(opt_state.m, moment_sh),
            v=jax.device_put(opt_state.v, moment_sh),
        )

    stride = loop.inner_steps
    if stride > 1:
        for name, every in (
            ("log_every", loop.log_every),
            ("eval_every", loop.eval_every),
            ("checkpoint_every", loop.checkpoint_every),
        ):
            if every % stride:
                raise ValueError(
                    f"{name}={every} must be a multiple of inner_steps={stride}"
                )

    accum = loop.grad_accum_steps
    if accum > 1:
        if stride > 1:
            raise ValueError(
                "grad_accum_steps and inner_steps cannot both exceed 1"
            )
        if loop.batch_size % accum:
            raise ValueError(
                f"batch_size={loop.batch_size} must divide by "
                f"grad_accum_steps={accum}"
            )
    if mesh is not None and "data" in mesh.shape and (accum > 1 or stride > 1):
        # The sharded step splits the (micro)batch dim over the data axis.
        micro = loop.batch_size // accum if accum > 1 else loop.batch_size
        if micro % mesh.shape["data"]:
            raise ValueError(
                f"microbatch size {micro} must divide by the data mesh axis "
                f"({mesh.shape['data']})"
            )

    # build_step(n) rebuilds the step for a TAIL shorter than inner_steps
    # (the last scan of a run whose total isn't a stride multiple).
    stacked_batches = stride > 1 or accum > 1

    def _mesh_places():
        """(place, place_plain) for shard_batch-based strategies (dp and
        GSPMD): stacked layout for training when accum/inner scan, plain
        (B, S) for eval and 1-step tails."""
        return (
            lambda b: shard_batch(b, mesh, stacked=stacked_batches),
            lambda b: shard_batch(b, mesh),
        )
    health = loop.health_stats
    dynamics = loop.dynamics_every > 0
    if mesh is None:
        def build_step(n=stride):
            if n > 1:
                from bpe_transformer_tpu.training.train_step import (
                    make_scanned_train_step,
                )

                return make_scanned_train_step(
                    model_config, hparams, n, health=health, dynamics=dynamics
                )
            if accum > 1:
                from bpe_transformer_tpu.training.train_step import (
                    make_grad_accum_train_step,
                )

                return make_grad_accum_train_step(
                    model_config, hparams, accum, health=health, dynamics=dynamics
                )
            return make_train_step(
                model_config, hparams, health=health, dynamics=dynamics
            )

        step_fn = build_step()
        place = place_plain = lambda b: b
    elif loop.parallel == "dp":
        def build_step(n=stride):
            return make_dp_train_step(
                model_config, hparams, mesh, accum_steps=accum, inner_steps=n,
                health=health, dynamics=dynamics,
                opt_sharding=loop.opt_sharding,
            )

        step_fn = build_step()
        place, place_plain = _mesh_places()
    elif loop.parallel == "sp":
        def build_step(n=stride):
            return make_sp_train_step(
                model_config, hparams, mesh, zigzag=loop.sp_zigzag,
                ulysses=loop.sp_ulysses,
                accum_steps=accum, inner_steps=n,
            )

        step_fn = build_step()
        place = lambda b: shard_sp_batch(
            b, mesh, zigzag=loop.sp_zigzag, stacked=stacked_batches
        )
        # place_plain feeds build_step(1) at a 1-step inner tail, so it must
        # carry the TRAINING layout (zigzag as configured, unstacked).  The
        # dense eval forward never uses it for sp — run_eval's sp branch
        # places its own batches in global order, without the permutation.
        place_plain = lambda b: shard_sp_batch(b, mesh, zigzag=loop.sp_zigzag)
    elif loop.parallel == "pp":
        from bpe_transformer_tpu.parallel.pp import make_pp_train_step

        def build_step(n=stride):
            return make_pp_train_step(
                model_config, hparams, mesh,
                num_microbatches=loop.pp_microbatches,
                accum_steps=accum, inner_steps=n,
            )

        step_fn = build_step()
        place, place_plain = _mesh_places()
    else:
        def build_step(n=stride):
            return make_gspmd_train_step(
                model_config,
                hparams,
                mesh,
                loop.parallel,
                example_params=params,
                accum_steps=accum,
                inner_steps=n,
                health=health,
                dynamics=dynamics,
                opt_sharding=loop.opt_sharding,
            )

        step_fn = build_step()
        place, place_plain = _mesh_places()

    # GSPMD/pipeline strategies hold device-sharded params; checkpoint those
    # through the streaming directory format.  dp/sp keep replicated params
    # (single-file pickle is fine and keeps file-like compatibility).
    sharded_ckpt = mesh is not None and loop.parallel not in ("dp", "sp")
    async_saver = None
    if loop.async_checkpoint and loop.checkpoint_dir is not None:
        from bpe_transformer_tpu.checkpointing.checkpoint import AsyncCheckpointer

        async_saver = AsyncCheckpointer()

    # Eval batches are sharded over the mesh, so XLA partitions the eval
    # forward: no Mosaic kernel may be in it (`partitioned_config`).
    eval_step = make_eval_step(partitioned_config(model_config, mesh))
    n_chips = len(jax.devices()) if mesh is not None else 1
    tokens_per_step = loop.batch_size * model_config.context_length

    def run_eval() -> float:
        if val_data is None:
            return float("nan")
        handle = telemetry.start_span(
            "eval", step=iteration, batches=loop.eval_batches
        )
        try:
            eval_params = params
            if loop.parallel == "pp":
                # Eval reuses the dense single-program forward; pull the
                # stacked stages back to host, restore the layer-list
                # layout, and upload ONCE so the batch loop below doesn't
                # re-transfer per batch.
                from bpe_transformer_tpu.parallel.pp import unstack_pipeline_params

                eval_params = jax.device_put(
                    unstack_pipeline_params(jax.device_get(params))
                )
            eval_rng = np.random.default_rng(loop.seed + 1)
            losses = []
            for _ in range(loop.eval_batches):
                ex, ey = get_batch(
                    val_data, loop.batch_size, model_config.context_length, eval_rng
                )
                ex, ey = (jax.numpy.asarray(ex), jax.numpy.asarray(ey))
                if loop.parallel == "sp":
                    # Eval runs the DENSE forward, which needs sequences in
                    # global order — place without the zig-zag permutation
                    # even when training uses it.
                    ex, ey = shard_sp_batch((ex, ey), mesh)
                elif loop.parallel != "pp":
                    # Eval batches are plain (B, S) — never the stacked
                    # grad-accum/inner-steps layout the train `place`
                    # expects.
                    ex, ey = place_plain((ex, ey))
                losses.append(float(eval_step(eval_params, ex, ey)))
            return float(np.mean(losses))
        finally:
            # Eval time is not step time: discount it from the throughput
            # window so tokens/sec and step_wall_s describe training steps.
            timer.exclude(handle.end())

    history: list[dict] = []
    from bpe_transformer_tpu.utils.flops import train_step_flops

    timer = StepTimer(
        n_chips=n_chips,
        flops_per_token=train_step_flops(model_config, loop.batch_size)
        / tokens_per_step,
    )
    sinks = MetricsLogger(
        jsonl_path=loop.metrics_jsonl, wandb_project=loop.wandb_project
    )
    # Attach the sinks and write the run-manifest header FIRST, so every
    # JSONL this loop produces is self-describing (config, mesh, versions,
    # git SHA) before any metric lands in it.
    telemetry.attach(sinks.log)
    # The attention path is chosen once per compile, from the shape (and,
    # for a GSPMD step, from who partitions the program): the header (and
    # the summary) say which one this run's step holds.  The sp schedules
    # bring their own attention and are not labelled.
    attention = {}
    if loop.parallel != "sp":
        from bpe_transformer_tpu.kernels.pallas.flash_attention import (
            attention_plan,
        )

        gspmd = mesh is not None and loop.parallel not in ("dp", "pp")
        path, tiles = attention_plan(
            partitioned_config(model_config, mesh) if gspmd else model_config,
            model_config.context_length,
        )
        attention = {
            "attention_path": path,
            "flash_tiles": list(tiles) if path == "flash" else None,
        }
    telemetry.emit(
        run_manifest(
            kind="train",
            model_config=model_config,
            loop_config=loop,
            mesh=mesh,
            parallel=loop.parallel,
            extra={
                "start_iteration": start_iteration, "n_chips": n_chips,
                **attention,
            },
        )
    )
    #: Always-on decision ring (telemetry/flightrecorder.py): rollback,
    #: preemption, and watchdog transitions land here as host-side
    #: bookkeeping (zero extra device syncs — pinned by the fetch-count
    #: test), flushed as a kind="blackbox" dump on watchdog NaN/hang and
    #: at the preemption epilogue.
    recorder = FlightRecorder("train")
    wd = None
    if loop.watchdog:
        wd = Watchdog(
            factor=loop.watchdog_factor,
            steps_per_beat=loop.log_every,
            policy=loop.watchdog_policy,
            telemetry=telemetry,
            recorder=recorder,
        )
        wd.start()

    def wd_pause():
        """Suspend hang detection around a known long phase (compile, eval,
        synchronous checkpoint save); no-op without a watchdog."""
        return wd.pause() if wd is not None else contextlib.nullcontext()
    last_loss = float("nan")
    val_loss = float("nan")
    first_dispatch = True
    prev_sync_iteration = start_iteration
    excluded_steps = 0
    clean_exit = False
    #: Graceful preemption: SIGTERM/SIGINT sets a flag the loop polls each
    #: step boundary (emergency checkpoint + kind="preemption" record +
    #: distinct exit code downstream).  install() is a no-op off the main
    #: thread — the flag then simply never trips.
    stop = GracefulShutdown(recorder=recorder)
    stop.install()
    preempted: str | None = None
    rollback_budget = (
        RollbackBudget(loop.max_rollbacks, loop.recovery_min_progress)
        if rollback_mode
        else None
    )
    #: Built lazily at the FIRST attribution boundary (the probe pays an
    #: AOT compile; a run that never reaches its cadence pays nothing).
    attribution_probe = None
    #: Advanced by each NaN rollback: mixes into the per-iteration batch
    #: seed so the retry samples DIFFERENT data over the replayed window —
    #: "skip the offending batch" without tracking which batch offended.
    #: Zero (the default) preserves the exact historical seeding, so
    #: resume determinism is untouched on runs that never roll back.
    batch_salt = 0

    def batch_rng(it: int) -> np.random.Generator:
        if batch_salt:
            return np.random.default_rng((loop.seed, it, batch_salt))
        return np.random.default_rng((loop.seed, it))

    def make_host_batch(it: int):
        """``(x, y, n, plain)`` for iteration ``it`` — numpy host sampling
        only (memmap gather, stacking, microbatch reshape), a pure function
        of the iteration (and rollback salt), so the jax-free prefetch
        worker can build it while the device runs the current step.  ``n``
        is the number of optimizer updates the batch carries (< stride only
        on the tail scan of a run whose total isn't a stride multiple);
        ``plain`` selects place_plain (the unstacked 1-step layout) at
        placement time.  Device placement stays on the MAIN thread: the
        transfer is an async enqueue once dispatch returns, and a worker
        issuing device ops concurrently with the donating step dispatch can
        abort the CPU runtime."""
        injector.on_batch_read(it)
        if stride > 1:
            n = min(stride, loop.steps - it)
            batches = [
                get_batch(
                    train_data,
                    loop.batch_size,
                    model_config.context_length,
                    batch_rng(it + j),
                )
                for j in range(n)
            ]
            if n == 1:
                # A 1-step tail is a plain step (build_step(1)): feed the
                # unstacked (B, S) layout it expects.
                return batches[0][0], batches[0][1], n, True
            x = np.stack([b[0] for b in batches])
            y = np.stack([b[1] for b in batches])
            return x, y, n, False
        x, y = get_batch(
            train_data, loop.batch_size, model_config.context_length,
            batch_rng(it),
        )
        if accum > 1:  # (B, S) -> (accum, B/accum, S) microbatches
            micro = loop.batch_size // accum
            x = x.reshape(accum, micro, -1)
            y = y.reshape(accum, micro, -1)
        return x, y, 1, False

    #: Lookahead batch feed: while the device runs step i, the worker
    #: thread samples + stacks the batch for step i+n, so the
    #: inter-dispatch host gap shrinks to the async device enqueue
    #: (attribution's host_gap_frac is the needle this moves).
    prefetcher = BatchPrefetcher(make_host_batch, depth=loop.prefetch)

    def save_snapshot(sync: bool = False) -> Path:
        """Write one checkpoint at the current iteration (step file +
        latest pointer + retention GC) — shared by the periodic cadence and
        the preemption emergency path (``sync=True`` bypasses the async
        saver: the process is about to exit)."""
        ckpt_handle = telemetry.start_span(
            "checkpoint",
            step=iteration,
            async_save=async_saver is not None and not sync,
        )
        ckpt_path = Path(loop.checkpoint_dir) / f"step_{iteration:08d}.ckpt"
        latest = Path(loop.checkpoint_dir) / "latest.ckpt"
        state_kwargs = dict(
            params=params,
            opt_state=opt_state,
            iteration=iteration,
            extra={
                "val_loss": None if math.isnan(val_loss) else val_loss,
                "train_loss": None if math.isnan(last_loss) else last_loss,
                # Self-describing checkpoints: eval/generate can recover
                # the architecture without the user re-passing --preset (a
                # mismatched preset crashes deep in RoPE with a shape
                # error).
                "model_config": dataclasses.asdict(model_config),
            },
        )

        def update_latest(ckpt_path=ckpt_path, latest=latest):
            from bpe_transformer_tpu.resilience.integrity import sidecar_path
            from bpe_transformer_tpu.resilience.retention import gc_checkpoints

            # A prior run of the other format may have left latest
            # as a symlink/dir; clear before re-pointing.
            if latest.is_symlink() or latest.exists():
                if latest.is_dir() and not latest.is_symlink():
                    shutil.rmtree(latest)
                else:
                    latest.unlink()
            if sharded_ckpt:
                latest.symlink_to(ckpt_path.name)
            else:
                # latest.ckpt is a byte copy — don't pay device_get
                # + pickle twice.  The checksum sidecar travels with it so
                # the copy is independently verifiable.
                shutil.copyfile(ckpt_path, latest)
                side = sidecar_path(ckpt_path)
                if side.exists():
                    shutil.copyfile(side, sidecar_path(latest))
            if loop.keep_checkpoints:
                gc_checkpoints(
                    Path(loop.checkpoint_dir), loop.keep_checkpoints,
                    log_fn=log_fn,
                )

        # A synchronous multi-GB save is legitimate silence;
        # detection suspends and the deadline re-arms on exit.
        with wd_pause():
            if async_saver is not None and not sync:
                # Device→host snapshot happens now; serialization +
                # IO overlap with the next training steps.
                async_saver.save(
                    ckpt_path,
                    sharded=sharded_ckpt,
                    on_complete=update_latest,
                    **state_kwargs,
                )
            elif sharded_ckpt:
                # GSPMD-sharded states stream shard-by-shard into a
                # checkpoint DIRECTORY — the full tree is never
                # staged on host in one buffer (FSDP-scale
                # requirement).
                save_checkpoint_sharded(ckpt_path, **state_kwargs)
                update_latest()
            else:
                save_checkpoint(ckpt_path, **state_kwargs)
                update_latest()
        # The span covers the synchronous portion (async saves
        # return after the device->host snapshot); discount it from
        # the throughput window — save time is not step time.
        timer.exclude(ckpt_handle.end())
        return ckpt_path

    # finally-close so an interrupt/OOM mid-run still flushes the JSONL
    # handle and finishes the wandb run.
    iteration = start_iteration
    try:
        setup_span.end()
        # Discard the window accumulated since StepTimer construction —
        # sink/manifest/watchdog setup is not step time.
        timer.snapshot()
        while iteration < loop.steps:
            # Chaos hooks (no-ops without a BT_FAULTS plan), then the
            # preemption poll: a SIGTERM/SIGINT that arrived since the last
            # boundary stops the loop HERE — before more compute — and the
            # epilogue below writes the emergency checkpoint.
            injector.at_step(iteration)
            if stop.triggered:
                preempted = stop.signame or "signal"
                break
            # Per-iteration seeding (not one stream advanced per step) so a
            # resumed run samples the SAME batch at the same iteration as an
            # uninterrupted one — preemption-safe determinism (batch_rng
            # folds in the post-rollback salt).  The prefetcher hands back
            # the worker-built host batch when one is ready, else builds it
            # synchronously (first step, post-rollback).
            with telemetry.phase("train/next_batch"):
                hx, hy, n, plain = prefetcher.get(iteration)
            if stride > 1 and n != stride:
                # Tail shorter than the compiled scan length.  The rebuilt
                # step pays a fresh jit compile on dispatch: route it
                # through the same span/exclusion/pause path as the first
                # step so it can't pollute throughput or trip the watchdog.
                step_fn = build_step(n)
                first_dispatch = True
            # Kick off the next batches now (up to the configured depth —
            # schedule() dedups and caps the pipeline): they sample + stack
            # on the worker thread while the device executes this step.
            # Future iterations advance by this dispatch's n, which matches
            # every upcoming boundary — including the shorter tail scan,
            # whose boundary still lands on a stride multiple and whose
            # batch make_host_batch builds correctly because it recomputes
            # its own n = min(stride, steps - it) per iteration.
            for ahead in range(1, loop.prefetch + 1):
                future_it = iteration + ahead * n
                if future_it < loop.steps:
                    prefetcher.schedule(future_it)
            # Device placement (async enqueue) on the main thread only.
            with telemetry.phase("train/next_batch"):
                x, y = (place_plain if plain else place)(
                    (jax.numpy.asarray(hx), jax.numpy.asarray(hy))
                )
            if first_dispatch:
                # The first dispatch of a (re)built step pays the jit
                # compile; span it (with a sync fence so the span measures
                # compile + first step, not just async dispatch), keep it
                # out of the throughput window — logged tokens/sec should
                # be steady state, not compile-dominated — and pause the
                # watchdog (a tail recompile happens with an armed
                # step-time median a long compile would trip).
                handle = telemetry.start_span("compile_first_step", step=iteration)
                with wd_pause():
                    params, opt_state, metrics = step_fn(params, opt_state, x, y)
                    jax.block_until_ready(metrics["loss"])
                timer.exclude(handle.end())
                # Warmup step(s): neither their tokens nor their step count
                # enter the window — excluding only the time would credit
                # tokens against ~zero elapsed and over-report throughput.
                excluded_steps += n
                first_dispatch = False
            else:
                with telemetry.phase("train/step_dispatch"):
                    params, opt_state, metrics = step_fn(
                        params, opt_state, x, y
                    )
                timer.update(tokens_per_step * n)
            iteration += n
            if injector.active:
                # Chaos: a planned NaN lands in the params HERE (a faithful
                # stand-in for a bad-batch overflow) so the log-boundary
                # detection and rollback path below face the real thing.
                params = injector.poison_params(params, iteration)

            is_last = iteration == loop.steps
            if iteration % loop.log_every == 0 or is_last:
                with telemetry.phase("train/sync"):
                    fetched = jax.device_get(metrics)  # the device sync point
                # Host work between the sync and the next dispatch: the
                # device's idle gap under it has this name in a trace.
                with telemetry.phase("train/log"):
                    dyn_flat = None
                    if dynamics:
                        # Already on host — the dynamics pytree rode the fetch
                        # above; flattening costs no device round-trip.
                        dyn_flat = flatten_dynamics(fetched["dynamics"])
                    last_loss = float(fetched["loss"])
                    rates = timer.snapshot()
                    real_steps = iteration - prev_sync_iteration - excluded_steps
                    step_wall_s = rates["window_seconds"] / max(real_steps, 1)
                    prev_sync_iteration = iteration
                    excluded_steps = 0
                    record = {
                        "step": iteration,
                        "loss": last_loss,
                        "lr": float(fetched["lr"]),
                        "grad_norm": float(fetched["grad_norm"]),
                        "tokens_per_sec": rates["tokens_per_sec"],
                        "tokens_per_sec_per_chip": rates["tokens_per_sec_per_chip"],
                        "step_wall_s": step_wall_s,
                        "window_seconds": rates["window_seconds"],
                    }
                    if "mfu" in rates:
                        record["mfu"] = rates["mfu"]
                    if loop.health_stats:
                        record.update(flatten_health(fetched["health"]))
                    if dyn_flat and "first_nonfinite" in dyn_flat:
                        # Localization rides the step record so the watchdog's
                        # nonfinite event (and NonFiniteError message) names
                        # the offending tensor path, not just "loss is NaN".
                        record["nonfinite_path"] = dyn_flat["first_nonfinite"]
                    history.append(record)
                    # The decision ring's heartbeat: values already on the host
                    # from the fetch above (zero extra syncs — the fetch-count
                    # test pins this), coalesced so steady-state logging holds
                    # ONE ring slot and a preemption/NaN dump still shows the
                    # last healthy step alongside the failure events.
                    recorder.record(
                        "step",
                        coalesce=True,
                        step=iteration,
                        loss=last_loss,
                        step_wall_s=round(step_wall_s, 6),
                    )
                    # Through the narrator, not sinks.log directly: emit() holds
                    # the telemetry lock (the watchdog thread writes hang events
                    # through the same JSONL handle) and counts the record for
                    # the footer's record_counts.
                    telemetry.emit(record)
                    if dyn_flat is not None and (
                        iteration % loop.dynamics_every == 0 or is_last
                    ):
                        telemetry.emit(dynamics_record(iteration, dyn_flat))
                    # Resource accounting rides the same once-per-log_every
                    # boundary: sample_resources is sync-free (RSS, live-buffer
                    # metadata, device memory_stats, compile counter), so HBM
                    # headroom and recompile trends cost zero extra host syncs.
                    # params/opt-state bytes are PER-CHIP (shard-shape metadata)
                    # — the number that shows the ZeRO-1 memory win directly.
                    telemetry.emit(
                        sample_resources(
                            step=iteration,
                            params_bytes=tree_bytes_per_device(params),
                            opt_state_bytes=tree_bytes_per_device(opt_state),
                        )
                    )
                    log_fn(
                        f"step {record['step']:>6d}  loss {record['loss']:.4f}  "
                        f"lr {record['lr']:.2e}  gnorm {record['grad_norm']:.3f}  "
                        f"tok/s {record['tokens_per_sec']:,.0f}"
                    )
                if (
                    loop.attribution_every
                    and iteration % loop.attribution_every == 0
                ):
                    # Exact-cadence only (no is_last catch-up like
                    # dynamics): the probe pays a real AOT compile, and a
                    # run whose steps never reach the cadence must pay
                    # nothing — no surprise multi-minute compile at the
                    # final step of a short run.
                    # Performance attribution (telemetry.attribution): a
                    # non-donating AOT copy of the step is fenced-timed to
                    # split this window's wall step time into compute /
                    # collective / host-gap, with the XLA cost-model
                    # roofline riding the first record.  Probe compile and
                    # measure time are excluded from throughput and
                    # watchdog-paused — untouched steps never see it.
                    from bpe_transformer_tpu.telemetry.attribution import (
                        StepProbe,
                    )

                    attr_handle = telemetry.start_span(
                        "attribution_probe",
                        step=iteration,
                        compile_probe=attribution_probe is None,
                    )
                    with wd_pause():
                        if attribution_probe is None:
                            attribution_probe = StepProbe(
                                model_config,
                                hparams,
                                batch_size=loop.batch_size,
                                mesh=mesh,
                                parallel=loop.parallel,
                                accum_steps=accum,
                                inner_steps=stride,
                                seed=loop.seed,
                                opt_sharding=loop.opt_sharding,
                            )
                        attr_record = attribution_probe.attribution_record(
                            params,
                            opt_state,
                            step=iteration,
                            wall_step_s=step_wall_s,
                            t=telemetry.now(),
                        )
                    timer.exclude(attr_handle.end())
                    telemetry.emit(attr_record)
                    log_fn(
                        f"step {iteration:>6d}  attribution: compute "
                        f"{attr_record['compute_frac']:.0%}  collective "
                        + (
                            f"{attr_record['collective_frac']:.0%}"
                            if attr_record["collective_frac"] is not None
                            else "n/a"
                        )
                        + f"  host gap {attr_record['host_gap_frac']:.0%}"
                    )
                if wd is not None:
                    # A window of only warmup steps has no meaningful step
                    # time; beat without a sample rather than seeding the
                    # median with a near-zero artifact.
                    wd.beat(step_wall_s if real_steps > 0 else None)
                bad_fields = nonfinite_fields(record)
                if bad_fields or record.get("nonfinite_path"):
                    # Dump-then-policy: the event (with the full record)
                    # reaches the JSONL before "raise" tears the loop down;
                    # without a watchdog the anomaly is recorded and the
                    # loop continues (legacy behavior, now visible).
                    if wd is not None:
                        wd.on_nonfinite(record, bad_fields)
                    else:
                        telemetry.event(
                            "nonfinite", step=iteration, fields=bad_fields
                        )
                    if rollback_mode:
                        # NaN rollback recovery: reload the last valid
                        # checkpoint, advance the data window past the
                        # offending batches, retry — under the crash-loop
                        # budget (a failure that survives a fresh window is
                        # not batch-local; escalate instead of looping).
                        detect_step = iteration
                        nonfinite_path = record.get("nonfinite_path")
                        try:
                            rollbacks = rollback_budget.note(detect_step)
                        except RollbackExhausted as exc:
                            telemetry.event(
                                "recovery_abort",
                                step=detect_step,
                                rollbacks=rollback_budget.total,
                                error=str(exc),
                            )
                            raise NonFiniteError(
                                str(exc), record=record
                            ) from exc
                        handle = telemetry.start_span(
                            "rollback", step=detect_step
                        )
                        with wd_pause():
                            if async_saver is not None:
                                # A snapshot of the poisoned state must
                                # never land; join before reloading.
                                async_saver.wait()
                            try:
                                params, opt_state, restored, used = (
                                    load_state(Path(loop.checkpoint_dir))
                                )
                            except Exception as exc:  # noqa: BLE001
                                telemetry.event(
                                    "recovery_abort",
                                    step=detect_step,
                                    error=repr(exc),
                                )
                                raise NonFiniteError(
                                    "rollback failed: no valid checkpoint "
                                    f"to restore ({exc}); state dumped to "
                                    "the telemetry stream",
                                    record=record,
                                ) from exc
                            if mesh is not None and loop.parallel not in (
                                "dp", "sp", "pp",
                            ):
                                # A dense fallback snapshot arrives as host
                                # arrays; re-place onto the GSPMD mesh
                                # (no-op for the streaming-loaded case).
                                params = shard_params(
                                    params, mesh, loop.parallel
                                )
                        timer.exclude(handle.end())
                        recorder.record(
                            "rollback",
                            step=detect_step,
                            restored_step=restored,
                            rollbacks=rollbacks,
                            nonfinite_path=nonfinite_path,
                        )
                        batch_salt += 1
                        # Prefetched batches were sampled with the OLD salt
                        # (and for the replayed window): drop them.
                        # reraise=True: a fault a prefetched batch already
                        # consumed (fire-once chaos read faults) surfaces
                        # here instead of vanishing with the pipeline.
                        prefetcher.invalidate(reraise=True)
                        telemetry.emit(
                            {
                                "kind": "recovery",
                                "t": telemetry.now(),
                                "step": detect_step,
                                "restored_step": restored,
                                "rollbacks": rollbacks,
                                "lost_steps": detect_step - restored,
                                **(
                                    {"nonfinite_path": nonfinite_path}
                                    if nonfinite_path
                                    else {}
                                ),
                            }
                        )
                        log_fn(
                            f"rollback #{rollbacks}: non-finite at step "
                            f"{detect_step}"
                            + (
                                f" (localized to {nonfinite_path})"
                                if nonfinite_path
                                else ""
                            )
                            + f"; restored {used} at step {restored}, "
                            "data window advanced"
                        )
                        iteration = restored
                        prev_sync_iteration = iteration
                        excluded_steps = 0
                        # Discard the poisoned window's timings: recovery
                        # time is not step time.
                        timer.snapshot()
                        continue

            if val_data is not None and (
                iteration % loop.eval_every == 0 or is_last
            ):
                # Eval (its first call pays a jit compile) is legitimate
                # silence — detection suspends for the duration and the
                # deadline re-arms on exit, without polluting the step-time
                # history.
                with wd_pause():
                    val_loss = run_eval()
                telemetry.emit({"step": iteration, "val_loss": val_loss})
                log_fn(f"step {iteration:>6d}  val_loss {val_loss:.4f}")

            if loop.checkpoint_dir is not None and (
                iteration % loop.checkpoint_every == 0 or is_last
            ):
                save_snapshot()

        if preempted is not None:
            # Graceful preemption epilogue: an emergency snapshot at the
            # exact stop boundary (so --resume loses zero completed steps),
            # then a kind="preemption" record BEFORE the footer — the
            # stream tells the story even if the slice vanishes next.
            emergency = None
            state_poisoned = False
            if loop.checkpoint_dir is not None:
                if async_saver is not None:
                    async_saver.wait()
                # A SIGTERM can land between a NaN-producing step and the
                # log boundary that would have detected it; an un-checked
                # emergency save would then make the poisoned state the
                # NEWEST snapshot (which rollback-on-resume would restore
                # over and over until its budget died).  The save already
                # pays a full device_get — pay the isfinite pass too and
                # keep the prior clean snapshot as the resume target.
                state_poisoned = any(
                    not bool(np.all(np.isfinite(np.asarray(jax.device_get(leaf)))))
                    for leaf in jax.tree_util.tree_leaves(params)
                )
                if not state_poisoned:
                    emergency = save_snapshot(sync=True)
            telemetry.emit(
                {
                    "kind": "preemption",
                    "t": telemetry.now(),
                    "step": iteration,
                    "signal": preempted,
                    "checkpoint": str(emergency) if emergency else None,
                    **(
                        {"skipped_nonfinite_state": True}
                        if state_poisoned
                        else {}
                    ),
                }
            )
            # SIGTERM epilogue black-box: the decision ring (signal
            # receipt, rollbacks, watchdog transitions) leaves with the
            # stream before the slice vanishes.  Forced: a terminal path
            # never loses its dump to the cooldown.
            recorder.record(
                "preemption",
                step=iteration,
                signal=preempted,
                checkpoint=str(emergency) if emergency else None,
            )
            telemetry.emit(
                recorder.blackbox(
                    "preemption",
                    context={"step": iteration, "signal": preempted},
                    force=True,
                )
            )
            log_fn(
                f"preempted by {preempted} at step {iteration}"
                + (f"; emergency checkpoint {emergency}" if emergency else "")
                + (
                    "; emergency save SKIPPED (non-finite state — prior "
                    "snapshot remains the resume target)"
                    if state_poisoned
                    else ""
                )
            )
        # Preemption is a DELIBERATE shutdown: the stream is complete and
        # footered (the footer's preempted field + the preemption record
        # distinguish it from a finished run).
        clean_exit = True

    finally:
        stop.uninstall()
        prefetcher.close()
        try:
            if async_saver is not None:
                # Join the in-flight write so a finished run always has its
                # final checkpoint (and surface any background write error).
                async_saver.close()
        finally:
            if wd is not None:
                wd.stop()
            # The footer closes the stream either way: clean=False marks a
            # crash/interrupt, and the watchdog verdict (hang/non-finite
            # counts) makes "watchdog-clean" checkable from the JSONL alone.
            telemetry.footer(
                steps=iteration,
                clean=clean_exit,
                watchdog_hang_events=wd.hang_events if wd is not None else 0,
                watchdog_nonfinite_events=(
                    wd.nonfinite_events if wd is not None else 0
                ),
                **({"preempted": preempted} if preempted else {}),
            )
            # Even if the background write failed, flush the metric sinks —
            # the recorded history matters most when the run just crashed.
            sinks.close()
    summary = {
        "steps": loop.steps,
        "final_train_loss": last_loss,
        # None (JSON null) when no eval ran — a NaN literal breaks strict
        # JSON consumers of summary.json / the CLI's summary line.
        "final_val_loss": None if math.isnan(val_loss) else val_loss,
        "history": history,
        **attention,
    }
    if preempted is not None:
        summary["preempted"] = preempted
        summary["stopped_at_step"] = iteration
    if rollback_budget is not None and rollback_budget.total:
        summary["rollbacks"] = rollback_budget.total
    if loop.checkpoint_dir is not None:
        from bpe_transformer_tpu.resilience.integrity import atomic_write_json

        # tmp + os.replace (like the checkpoint writers): a kill during the
        # final write can't leave a truncated summary.json behind.
        atomic_write_json(Path(loop.checkpoint_dir) / "summary.json", summary)
    return summary
