"""Command-line interface: tokenizer training, corpus tokenization, LM
training/eval, and text generation.

The reference ships no CLI at all (SURVEY §5, config/flag system: "No
CLI/argparse anywhere"); this is the framework's real entry point:

    bpe-tpu train-tokenizer --input corpus.txt --vocab-size 10000 --output-dir tok/
    bpe-tpu tokenize --input corpus.txt --tokenizer-dir tok/ --output tokens.bin
    bpe-tpu train --data tokens.bin --val-data val.bin --preset tinystories-4l \
                  --steps 5000 --batch-size 64 --checkpoint-dir ckpt/
    bpe-tpu generate --checkpoint ckpt/latest.ckpt --tokenizer-dir tok/ \
                     --prompt "Once upon a time"
    bpe-tpu serve    --checkpoint ckpt/latest.ckpt --tokenizer-dir tok/ \
                     --slots 8 --port 8000 --metrics-jsonl serve.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

from bpe_transformer_tpu.models import config as model_configs
from bpe_transformer_tpu.models.config import ModelConfig

PRESETS = {
    "ts-test": model_configs.TS_TEST_CONFIG,
    "tinystories-4l": model_configs.TINYSTORIES_4L,
    "tinystories-12l": model_configs.TINYSTORIES_12L,
    "tinystories-moe": model_configs.TINYSTORIES_MOE,
    "gpt2-small-32k": model_configs.GPT2_SMALL_32K,
    "gpt2-medium": model_configs.GPT2_MEDIUM,
}


def _specials(args) -> list[str]:
    """Resolve --special-token: appended values replace the default rather
    than extending it (argparse appends onto list defaults)."""
    return args.special_token if args.special_token else ["<|endoftext|>"]


def _load_model_config(args, stored: dict | None = None) -> ModelConfig:
    """Resolve the architecture: explicit JSON > explicit --preset >
    checkpoint-stored config > the preset default.

    ``stored`` is the ``extra["model_config"]`` dict a training run saves
    into its checkpoints — eval/generate pass it so an existing checkpoint
    describes itself (a preset that mismatches the weights crashes deep in
    RoPE with an opaque shape error).
    """
    if args.model_config:
        return ModelConfig.from_json(args.model_config)
    preset = getattr(args, "preset", None)
    if preset is not None:
        return PRESETS[preset]
    if stored:
        import dataclasses

        # The stored config pins the ARCHITECTURE (what the weights need);
        # backend-specific execution knobs must not leak — a checkpoint
        # trained with Pallas flash attention on TPU would otherwise fail
        # to lower when evaluated on a CPU host.  Explicit --preset /
        # --model-config still selects them deliberately.
        cfg = ModelConfig.from_dict(stored)
        return dataclasses.replace(
            cfg,
            attention_impl="auto",
            ffn_impl="xla",
            decode_attention_impl="auto",
            remat=False,
            remat_policy="none",
            scan_layers=False,
        )
    return PRESETS[getattr(args, "default_preset", "tinystories-4l")]


def _add_mfu_knob_flags(p) -> None:
    """The training-MFU execution knobs (ISSUE 13), shared by ``train``,
    ``warmup --train`` (whose jit-baked programs must match the run they
    warm), and ``profile``: the graduated remat policy, scan-over-layers,
    and the bf16 gradient-collective boundary."""
    p.add_argument(
        "--remat-policy",
        default=None,
        choices=["none", "full", "dots_saveable", "save_attn"],
        help="activation-rematerialization policy for the backward pass: "
        "none (save everything), full (recompute whole blocks — the "
        "deprecated remat:true), dots_saveable (save matmul outputs), "
        "save_attn (keep the flash-attention kernel's FA-2 residuals, "
        "rematerialize the FFN tail — lower peak HBM than none, less "
        "recompute than full); default: the model config's setting",
    )
    p.add_argument(
        "--scan-layers",
        action="store_true",
        help="run the layer stack as one policy-rematerialized lax.scan "
        "over stacked block params: O(1)-in-depth compile time, identical "
        "numerics; param pytree/checkpoints unchanged",
    )
    p.add_argument(
        "--grads-dtype",
        default="float32",
        choices=["float32", "bfloat16"],
        help="gradient width at the reduction boundary: bfloat16 rounds "
        "the grad tree before the dp pmean / ZeRO-1 reduce-scatter "
        "(half the collective bytes; f32 clip/AdamW/master math "
        "unchanged; same rounding applied in every execution mode)",
    )


def _apply_mfu_knobs(model_config: ModelConfig, args) -> ModelConfig:
    """Fold the --remat-policy/--scan-layers flags into the resolved model
    config, with the deprecation note for configs still using the old
    ``remat: bool`` (accepted as remat_policy="full")."""
    import dataclasses

    if model_config.remat and not args.remat_policy:
        print(
            'note: ModelConfig.remat is deprecated — treating remat=true '
            'as remat_policy="full"; set remat_policy (or --remat-policy) '
            "explicitly",
            file=sys.stderr,
        )
    overrides = {}
    if args.remat_policy:
        # The explicit flag wins over (and silences) the deprecated bool.
        overrides.update(remat_policy=args.remat_policy, remat=False)
    if args.scan_layers:
        overrides["scan_layers"] = True
    if overrides:
        model_config = dataclasses.replace(model_config, **overrides)
    return model_config


def cmd_train_tokenizer(args) -> int:
    from bpe_transformer_tpu.tokenization import BPETrainer

    trainer = BPETrainer(
        vocab_size=args.vocab_size, special_tokens=_specials(args)
    )
    trainer.train(args.input, n_workers=args.workers)
    trainer.save_trainer(Path(args.output_dir))
    print(
        f"trained vocab of {len(trainer.vocab)} tokens "
        f"({len(trainer.merges)} merges) -> {args.output_dir}"
    )
    return 0


def _load_tokenizer(tokenizer_dir: str, special_tokens: list[str]):
    from bpe_transformer_tpu.tokenization import BPETokenizer

    d = Path(tokenizer_dir)
    return BPETokenizer.from_files(
        d / "vocab.pkl", d / "merges.pkl", special_tokens=special_tokens
    )


def cmd_tokenize(args) -> int:
    from bpe_transformer_tpu.data import tokenize_to_memmap

    tokenizer = _load_tokenizer(args.tokenizer_dir, _specials(args))
    tokens = tokenize_to_memmap(tokenizer, args.input, args.output, args.dtype)
    print(f"wrote {len(tokens):,} tokens ({args.dtype}) -> {args.output}")
    return 0


def _maybe_profile_trace(logdir: str | None):
    """A ``jax.profiler`` trace context when ``--profile-trace DIR`` was
    given, else a no-op — so command bodies wrap their hot section
    unconditionally."""
    if logdir is None:
        import contextlib

        return contextlib.nullcontext()
    from bpe_transformer_tpu.telemetry import profile_trace

    return profile_trace(logdir)


def cmd_train(args) -> int:
    if args.supervise:
        # Supervised mode: THIS process becomes the jax-free parent — it
        # never imports jax (the child owns the chip) and respawns the
        # actual training child on crash/preemption with auto-resume from
        # the newest valid checkpoint (resilience/supervisor.py).
        from bpe_transformer_tpu.resilience.supervisor import supervise

        if not args.checkpoint_dir:
            print(
                "train --supervise needs --checkpoint-dir (restart-with-"
                "resume is the whole point)",
                file=sys.stderr,
            )
            return 2
        return supervise(
            getattr(args, "_argv", None) or ["train"],
            args.checkpoint_dir,
            max_restarts=args.max_restarts,
            backoff_s=args.restart_backoff,
        )

    from bpe_transformer_tpu.data import load_token_file
    from bpe_transformer_tpu.resilience.signals import EXIT_PREEMPTED
    from bpe_transformer_tpu.training.loop import LoopConfig, train
    from bpe_transformer_tpu.training.train_step import TrainHParams

    # Before anything jit-compiles: repeat starts (supervisor respawns,
    # preemption resumes) then load their XLA programs from disk.  The
    # directory comes from the one rule in utils/compile_cache.py.
    from bpe_transformer_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(args.compile_cache)

    model_config = _apply_mfu_knobs(_load_model_config(args), args)
    hparams = TrainHParams(
        max_learning_rate=args.lr,
        min_learning_rate=args.min_lr if args.min_lr is not None else args.lr / 10,
        warmup_iters=args.warmup,
        cosine_cycle_iters=args.lr_cycle if args.lr_cycle else args.steps,
        weight_decay=args.weight_decay,
        grad_clip_norm=args.grad_clip,
        grads_dtype=args.grads_dtype,
    )
    mesh_axes = None
    if args.mesh:
        mesh_axes = {
            name: int(size)
            for name, size in (part.split("=") for part in args.mesh.split(","))
        }
    loop = LoopConfig(
        steps=args.steps,
        batch_size=args.batch_size,
        log_every=args.log_every,
        eval_every=args.eval_every,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
        metrics_jsonl=args.metrics_jsonl,
        wandb_project=args.wandb_project,
        health_stats=args.health_stats,
        dynamics_every=args.dynamics_every,
        attribution_every=args.attribution_every,
        watchdog=args.watchdog,
        watchdog_factor=args.watchdog_factor,
        watchdog_policy=args.watchdog_policy,
        max_rollbacks=args.max_rollbacks,
        recovery_min_progress=args.recovery_min_progress,
        keep_checkpoints=args.keep_checkpoints,
        seed=args.seed,
        parallel=args.parallel,
        mesh_axes=mesh_axes,
        pp_microbatches=args.pp_microbatches,
        sp_zigzag=args.sp_zigzag,
        sp_ulysses=args.sp_ulysses,
        inner_steps=args.inner_steps,
        grad_accum_steps=args.grad_accum_steps,
        async_checkpoint=args.async_checkpoint,
        opt_sharding=args.opt_sharding,
        prefetch=args.prefetch,
    )
    train_data = load_token_file(args.data, args.dtype)
    val_data = load_token_file(args.val_data, args.dtype) if args.val_data else None
    with _maybe_profile_trace(args.profile_trace):
        summary = train(
            model_config,
            hparams,
            loop,
            train_data,
            val_data,
            resume_from=args.resume,
        )
    print(json.dumps({k: v for k, v in summary.items() if k != "history"}))
    # Distinct exit code for a SIGTERM/SIGINT stop (emergency checkpoint
    # already written): supervisors respawn-with-resume on it instead of
    # treating the run as crashed or finished.
    return EXIT_PREEMPTED if summary.get("preempted") else 0


def _load_inference_state(args, *, need_tokenizer: bool):
    """The checkpoint-restore + config-resolution (+ tokenizer-load)
    sequence every inference command shares (eval / generate / serve):
    returns ``(payload, model_config, tokenizer)`` with the architecture
    taken from the checkpoint's stored config unless overridden (see
    `_load_model_config`).  ``tokenizer`` is None when not requested —
    eval scores token files directly."""
    from bpe_transformer_tpu.checkpointing import load_checkpoint

    payload = load_checkpoint(args.checkpoint)
    model_config = _load_model_config(
        args, stored=payload.get("extra", {}).get("model_config")
    )
    tokenizer = None
    if need_tokenizer:
        tokenizer = _load_tokenizer(args.tokenizer_dir, _specials(args))
    return payload, model_config, tokenizer


def cmd_eval(args) -> int:
    import jax.numpy as jnp

    from bpe_transformer_tpu.data import get_batch, load_token_file
    from bpe_transformer_tpu.training.train_step import make_eval_step

    payload, model_config, _ = _load_inference_state(args, need_tokenizer=False)
    eval_step = make_eval_step(model_config)
    data = load_token_file(args.data, args.dtype)
    rng = np.random.default_rng(args.seed)
    losses = []
    for _ in range(args.batches):
        x, y = get_batch(data, args.batch_size, model_config.context_length, rng)
        losses.append(float(eval_step(payload["params"], jnp.asarray(x), jnp.asarray(y))))
    print(json.dumps({"val_loss": float(np.mean(losses)), "batches": args.batches}))
    return 0


def cmd_generate(args) -> int:
    import dataclasses

    from bpe_transformer_tpu.training.sampling import generate_text

    payload, model_config, tokenizer = _load_inference_state(
        args, need_tokenizer=True
    )
    if args.decode_attention:
        model_config = dataclasses.replace(
            model_config, decode_attention_impl=args.decode_attention
        )
    with _maybe_profile_trace(args.profile_trace):
        text = generate_text(
            payload["params"],
            model_config,
            tokenizer,
            prompt=args.prompt,
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            seed=args.seed,
        )
    print(text)
    return 0


def cmd_serve(args) -> int:
    """Continuous-batching inference: offline batch mode when
    ``--prompts-file`` is given, else the HTTP JSON endpoint."""
    from bpe_transformer_tpu.serving import ServingEngine, make_http_server
    from bpe_transformer_tpu.telemetry import (
        MetricsLogger,
        Telemetry,
        run_manifest,
    )

    if args.prompts_file and not args.output:
        print("serve: --prompts-file needs --output", file=sys.stderr)
        return 2
    # Speculative-decoding flags fail fast BEFORE any accelerator work
    # (PR 9 style): DraftSpec is jax-free, so a malformed draft config or
    # a structurally impossible combination costs milliseconds, not a
    # model load + compile.  The vocab cross-check against the resolved
    # target config runs right after checkpoint-config resolution below.
    draft_spec = None
    if args.speculate:
        if args.speculate < 1:
            print(f"serve: --speculate must be >= 1, got {args.speculate}",
                  file=sys.stderr)
            return 2
        if not args.paged:
            print("serve: --speculate needs --paged (the verify pass "
                  "scores through the paged scatter; the KV rewind lives "
                  "in the block pool)", file=sys.stderr)
            return 2
        if not args.draft_config:
            print("serve: --speculate needs --draft-config (a DraftSpec "
                  "JSON: tiny geometry or truncate_layers)",
                  file=sys.stderr)
            return 2
        from bpe_transformer_tpu.serving.spec.draft import DraftSpec

        try:
            draft_spec = DraftSpec.from_json(args.draft_config)
        except (OSError, ValueError, TypeError) as exc:
            print(f"serve: bad --draft-config: {exc}", file=sys.stderr)
            return 2
    elif args.draft_config:
        print("serve: --draft-config needs --speculate K", file=sys.stderr)
        return 2
    if args.kv_dtype == "int8" and not args.paged:
        print("serve: --kv-dtype int8 needs --paged (the int8 scale pools "
              "live in the block pool)", file=sys.stderr)
        return 2
    if args.role != "both" and not args.paged:
        print(f"serve: --role {args.role} needs --paged (KV migration "
              "payloads are block chains)", file=sys.stderr)
        return 2
    if args.evacuate_to and not args.paged:
        print("serve: --evacuate-to needs --paged (drain evacuation "
              "exports in-flight sessions as KV block chains)",
              file=sys.stderr)
        return 2
    if args.role == "prefill" and args.prompts_file:
        print("serve: --role prefill cannot run offline batch mode (it "
              "never decodes; prefixes stream out over /kv/export)",
              file=sys.stderr)
        return 2
    if args.decode_attention == "paged" and not args.paged:
        print("serve: --decode-attention paged needs --paged (the kernel "
              "reads through the block table)", file=sys.stderr)
        return 2
    # Flag validation is done; before the engine compiles its bucket
    # ladder: a rolling-restart replica warm-starts from the cache instead
    # of re-paying every prefill bucket + decode tick compile.
    from bpe_transformer_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(args.compile_cache)
    payload, model_config, tokenizer = _load_inference_state(
        args, need_tokenizer=True
    )
    if args.decode_attention:
        import dataclasses

        model_config = dataclasses.replace(
            model_config, decode_attention_impl=args.decode_attention
        )
    if args.weight_dtype == "int8" and model_config.ffn_type == "moe":
        # The per-channel quantizer covers dense matmul weights; MoE
        # expert stacks route through the gather dispatch it does not.
        # A config error, not a degraded mode — refuse at startup.
        print("serve: --weight-dtype int8 does not cover MoE expert "
              "stacks; serve this config at the activation width",
              file=sys.stderr)
        return 2
    if draft_spec is not None:
        # Vocab/geometry compatibility against the RESOLVED target config:
        # rejection sampling compares distributions over one shared
        # vocabulary, so a mismatched draft is a configuration error the
        # server must refuse at startup, not a degraded mode.
        try:
            draft_spec.validate_against(model_config)
        except ValueError as exc:
            print(f"serve: {exc}", file=sys.stderr)
            return 2
    stop_id = None
    if tokenizer.special_tokens:
        stop_id = tokenizer.encode(tokenizer.special_tokens[0])[0]

    logger = MetricsLogger(
        jsonl_path=args.metrics_jsonl, max_bytes=args.metrics_max_bytes
    )
    telemetry = Telemetry(sink=logger.log) if args.metrics_jsonl else None
    # Built unconditionally: /statusz serves the manifest even when no
    # metrics JSONL is being written.
    manifest = run_manifest(kind="serve", model_config=model_config)
    if telemetry is not None:
        telemetry.emit(manifest)

    serving = ServingEngine(
        payload["params"],
        model_config,
        tokenizer=tokenizer,
        slots=args.slots,
        max_queue=args.max_queue,
        max_wait_s=args.max_wait,
        default_stop_id=stop_id,
        default_max_new_tokens=args.max_new_tokens,
        telemetry=telemetry,
        manifest=manifest,
        paged=args.paged,
        block_size=args.block_size,
        num_kv_blocks=args.num_kv_blocks,
        prefill_chunk=args.prefill_chunk,
        prefill_token_budget=args.prefill_budget,
        prefix_cache=not args.no_prefix_cache,
        kv_dtype=None if args.kv_dtype == "act" else args.kv_dtype,
        weight_dtype=(
            None if args.weight_dtype == "act" else args.weight_dtype
        ),
        fused_sampling=args.fused_sampling,
        speculate_k=args.speculate,
        draft_spec=draft_spec,
        role=args.role,
        flightrecorder_capacity=args.flightrecorder_capacity,
    )
    try:
        with serving:
            if args.prompts_file:
                results = serving.serve_batch_file(
                    args.prompts_file,
                    args.output,
                    max_new_tokens=args.max_new_tokens,
                    temperature=args.temperature,
                    top_k=args.top_k,
                    top_p=args.top_p,
                    seed=args.seed,
                )
                reasons: dict[str, int] = {}
                for r in results:
                    reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
                print(
                    json.dumps(
                        {
                            "prompts": len(results),
                            "finish_reasons": reasons,
                            "output": args.output,
                            **serving.stats(),
                        }
                    )
                )
                return 0
            server = make_http_server(serving, host=args.host, port=args.port)
            host, port = server.server_address[:2]
            # A service is stopped with SIGTERM (kill, container runtimes):
            # graceful drain — the interrupt gets us out of serve_forever
            # (no new connections), then the engine finishes every queued
            # and in-flight request before close() runs, so preemption
            # never cancels work the engine can still complete and the
            # telemetry stream always ends with a footer.
            import signal

            def _sigterm(signum, frame):
                raise KeyboardInterrupt

            signal.signal(signal.SIGTERM, _sigterm)
            print(
                f"serving on http://{host}:{port}  "
                f"(slots={args.slots}, queue={args.max_queue}, "
                f"role={args.role}; POST /generate /kv/export /kv/import, "
                "GET /healthz /metrics /statusz; "
                "Ctrl-C/SIGTERM drains then stops)",
                flush=True,
            )
            try:
                server.serve_forever()
            except KeyboardInterrupt:
                pass
            finally:
                server.shutdown()
                drained = serving.drain(
                    timeout_s=args.drain_timeout,
                    evacuate_urls=args.evacuate_to,
                )
                print(
                    ("drained cleanly"
                     + (" (sessions evacuated over the wire)"
                        if args.evacuate_to else ""))
                    if drained
                    else f"drain timed out after {args.drain_timeout}s; "
                    "cancelling stragglers",
                    flush=True,
                )
                server.server_close()
            return 0
    finally:
        logger.close()


def cmd_route(args) -> int:
    # Jax-free fleet front (serving/router.py): health-aware balancing
    # over N serve replicas off their /statusz surface — runs on a box
    # with no accelerator runtime.
    from bpe_transformer_tpu.serving.router import main as route_main

    forwarded = []
    for replica in args.replica:
        forwarded += ["--replica", replica]
    forwarded += [
        "--host", args.host,
        "--port", str(args.port),
        "--poll-interval", str(args.poll_interval),
        "--request-timeout", str(args.request_timeout),
        "--connect-timeout", str(args.connect_timeout),
    ]
    if args.prefill_threshold is not None:
        forwarded += ["--prefill-threshold", str(args.prefill_threshold)]
    forwarded += ["--suspect-after", str(args.suspect_after)]
    if args.metrics_jsonl:
        forwarded += ["--metrics-jsonl", args.metrics_jsonl]
    return route_main(forwarded)


def cmd_control(args) -> int:
    # Jax-free self-healing control loop (serving/controller.py): polls
    # the fleet aggregator + router and acts — hot KV rebalancing, tier
    # retuning, elastic capacity — behind a crash-loop breaker.
    from bpe_transformer_tpu.serving.controller import main as control_main

    forwarded = ["--fleet", args.fleet]
    if args.router:
        forwarded += ["--router", args.router]
    forwarded += [
        "--host", args.host,
        "--port", str(args.port),
        "--interval", str(args.interval),
        "--evidence-max-age", str(args.evidence_max_age),
        "--cooldown", str(args.cooldown),
        "--action-timeout", str(args.action_timeout),
        "--action-retries", str(args.action_retries),
        "--max-failures", str(args.max_failures),
        "--rebalance-gap", str(args.rebalance_gap),
        "--scale-sustain", str(args.scale_sustain),
        "--scale-down-idle", str(args.scale_down_idle),
    ]
    for spec in args.spawn or []:
        forwarded += ["--spawn", spec]
    if args.observe_only:
        forwarded.append("--observe-only")
    if args.once:
        forwarded.append("--once")
    if args.metrics_jsonl:
        forwarded += ["--metrics-jsonl", args.metrics_jsonl]
    return control_main(forwarded)


def cmd_fleet(args) -> int:
    # Jax-free fleet aggregator (telemetry/fleet.py): poll N replicas +
    # the router into kind=fleet/slo/alert records and serve the fleet
    # /statusz + /metrics — the observability plane every fleet-level
    # tool (monitor --fleet, report --slo, the compare gate) reads.
    from bpe_transformer_tpu.telemetry.fleet import main as fleet_main

    forwarded = []
    for replica in args.replica:
        forwarded += ["--replica", replica]
    if args.router:
        forwarded += ["--router", args.router]
    forwarded += [
        "--host", args.host,
        "--port", str(args.port),
        "--interval", str(args.interval),
        "--poll-timeout", str(args.poll_timeout),
    ]
    if args.metrics_jsonl:
        forwarded += ["--metrics-jsonl", args.metrics_jsonl]
    if args.slo_config:
        forwarded += ["--slo-config", args.slo_config]
    for window in args.window or []:
        forwarded += ["--window", str(window)]
    if args.once:
        forwarded.append("--once")
    return fleet_main(forwarded)


def cmd_incident(args) -> int:
    # Jax-free postmortem bundler (telemetry/incident.py): sweep every
    # host's flight-recorder page concurrently, correlate the dumps by
    # absolute time_unix (and X-Request-Id with --request), and write one
    # bundle with a wall-clock-ordered cross-replica timeline.
    from bpe_transformer_tpu.telemetry.incident import main as incident_main

    forwarded = []
    for replica in args.replica:
        forwarded += ["--replica", replica]
    if args.router:
        forwarded += ["--router", args.router]
    forwarded += [
        "--timeout", str(args.timeout),
        "--timeline-cap", str(args.timeline_cap),
        "--out", args.out,
    ]
    if args.request:
        forwarded += ["--request", args.request]
    return incident_main(forwarded)


def _enable_warmup_cache(args):
    """The cache directory `warmup` fills (utils/compile_cache.py's rule),
    or None after saying why there is none: warming without a cache would
    compile into thin air."""
    from bpe_transformer_tpu.utils.compile_cache import (
        ENV_VAR,
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache(args.compile_cache)
    if cache_dir is None:
        print(f"warmup: no compile cache to warm — give --compile-cache DIR "
              f"or set {ENV_VAR} (the CPU backend has no default "
              "directory; elsewhere the checkout's could not be created)",
              file=sys.stderr)
    return cache_dir


def _warmup_train(args) -> int:
    """``bpe-tpu warmup --train``: AOT-compile the TRAINING step (+ eval)
    programs into the persistent compile cache — the supervisor respawn
    loop's warm-restart path (ROADMAP item 5 remainder).  A respawned
    ``bpe-tpu train --compile-cache DIR --resume ...`` child then loads
    its update program from disk instead of re-paying the cold compile
    after every preemption or crash.

    The cache key is the LOWERED program, so this mirrors the exact step
    construction ``training/loop.py`` performs for the same flags: same
    ModelConfig, same TrainHParams constants (hyperparameters are baked
    into the jit as Python scalars — a different ``--lr`` is a different
    program), same batch/accum/inner-steps shapes.  Single-device path
    only (the supervisor story); mesh-parallel runs warm on their own
    first step."""
    import jax
    import jax.numpy as jnp

    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim.adamw import adamw_init
    from bpe_transformer_tpu.telemetry.resources import (
        compile_cache_hits,
        install_compile_counter,
    )
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_eval_step,
        make_train_step,
    )

    if args.grad_accum_steps > 1 and args.inner_steps > 1:
        print("warmup: --grad-accum-steps and --inner-steps are mutually "
              "exclusive (as in bpe-tpu train)", file=sys.stderr)
        return 2
    if args.grad_accum_steps > 1 and args.batch_size % args.grad_accum_steps:
        print(f"warmup: --batch-size {args.batch_size} must be a multiple "
              f"of --grad-accum-steps {args.grad_accum_steps}",
              file=sys.stderr)
        return 2

    install_compile_counter()
    cache_dir = _enable_warmup_cache(args)
    if cache_dir is None:
        return 2

    if args.checkpoint:
        payload, model_config, _ = _load_inference_state(
            args, need_tokenizer=False
        )
        params = jax.device_put(payload["params"])
    else:
        # The cache key is the lowered program (shapes/config), not the
        # weights: random init warms the same entries a checkpoint would.
        model_config = _load_model_config(args)
        params = init_params(jax.random.PRNGKey(0), model_config)

    # The MFU knobs change the LOWERED program (remat structure, scanned
    # layer stack, grad-cast boundary), so warming them must mirror the
    # run's flags exactly — same contract as --lr/--batch-size above.
    model_config = _apply_mfu_knobs(model_config, args)
    hparams = TrainHParams(
        max_learning_rate=args.lr,
        min_learning_rate=(
            args.min_lr if args.min_lr is not None else args.lr / 10
        ),
        warmup_iters=args.warmup,
        cosine_cycle_iters=args.lr_cycle if args.lr_cycle else args.steps,
        weight_decay=args.weight_decay,
        grad_clip_norm=args.grad_clip,
        grads_dtype=args.grads_dtype,
    )
    ctx = model_config.context_length
    batch = args.batch_size
    # Eval first: the train step donates params/opt_state, so it runs last.
    eval_step = make_eval_step(model_config)
    dummy = jnp.zeros((batch, ctx), jnp.int32)
    jax.block_until_ready(eval_step(params, dummy, dummy))

    health = args.health_stats
    dynamics = args.dynamics_every > 0
    if args.inner_steps > 1:
        from bpe_transformer_tpu.training.train_step import (
            make_scanned_train_step,
        )

        step = make_scanned_train_step(
            model_config, hparams, args.inner_steps,
            health=health, dynamics=dynamics,
        )
        x = jnp.zeros((args.inner_steps, batch, ctx), jnp.int32)
    elif args.grad_accum_steps > 1:
        from bpe_transformer_tpu.training.train_step import (
            make_grad_accum_train_step,
        )

        step = make_grad_accum_train_step(
            model_config, hparams, args.grad_accum_steps,
            health=health, dynamics=dynamics,
        )
        x = jnp.zeros(
            (args.grad_accum_steps, batch // args.grad_accum_steps, ctx),
            jnp.int32,
        )
    else:
        step = make_train_step(
            model_config, hparams, health=health, dynamics=dynamics
        )
        x = dummy
    opt_state = adamw_init(params)
    new_params, new_opt, metrics = step(params, opt_state, x, x)
    jax.block_until_ready(metrics["loss"])
    del new_params, new_opt

    print(json.dumps({
        "mode": "train",
        "programs_compiled": step._cache_size() + eval_step._cache_size(),
        "batch_size": batch,
        "grad_accum_steps": args.grad_accum_steps,
        "inner_steps": args.inner_steps,
        "health_stats": health,
        "remat_policy": model_config.resolved_remat_policy,
        "scan_layers": model_config.scan_layers,
        "grads_dtype": hparams.grads_dtype,
        "cache_dir": str(cache_dir),
        "cache_hits": compile_cache_hits(),
    }))
    return 0


def cmd_warmup(args) -> int:
    """AOT-compile the serving program ladder into the persistent compile
    cache, so a router-triggered replica restart (or first boot on a fresh
    host sharing the cache dir) reaches traffic without paying the
    20-40 s/program cold compiles — ROADMAP item 5's rolling-deploy
    story: warm the exact programs ``bpe-tpu serve`` with the same
    config/engine knobs will request (``--speculate`` adds the draft
    prefill ladder + propose + verify programs), or — with ``--train`` —
    the training-step programs the supervisor respawn loop resumes
    into."""
    import jax

    from bpe_transformer_tpu.telemetry.resources import (
        compile_cache_hits,
        install_compile_counter,
    )

    if args.train:
        if args.speculate or args.paged or args.role != "both":
            print("warmup: --train warms the training-step programs; it "
                  "composes with serving flags in separate invocations, "
                  "not one", file=sys.stderr)
            return 2
        return _warmup_train(args)

    # Role-scoped warmup (ISSUE 15): a disaggregated node must not pay
    # compile time for programs it never runs — prefill replicas warm
    # chunk buckets + export (no tick), decode replicas warm tick +
    # import (no chunk ladder).
    if args.role != "both" and not args.paged:
        print(f"warmup: --role {args.role} needs --paged", file=sys.stderr)
        return 2
    if args.role == "prefill" and args.speculate:
        print("warmup: --role prefill never ticks; speculation lives on "
              "decode replicas (warm them with --role decode)",
              file=sys.stderr)
        return 2

    # Speculative-decoding fast-fail (PR 9 style): structural checks and
    # the jax-free DraftSpec parse before any model/compile work; the
    # vocab cross-check runs right after config resolution below.
    draft_spec = None
    if args.speculate:
        if not args.paged:
            print("warmup: --speculate needs --paged", file=sys.stderr)
            return 2
        if not args.draft_config:
            print("warmup: --speculate needs --draft-config",
                  file=sys.stderr)
            return 2
        from bpe_transformer_tpu.serving.spec.draft import DraftSpec

        try:
            draft_spec = DraftSpec.from_json(args.draft_config)
        except (OSError, ValueError, TypeError) as exc:
            print(f"warmup: bad --draft-config: {exc}", file=sys.stderr)
            return 2
    elif args.draft_config:
        print("warmup: --draft-config needs --speculate K", file=sys.stderr)
        return 2

    install_compile_counter()
    cache_dir = _enable_warmup_cache(args)
    if cache_dir is None:
        return 2

    if args.checkpoint:
        payload, model_config, _ = _load_inference_state(
            args, need_tokenizer=False
        )
        params = payload["params"]
    else:
        # The cache key is the lowered program (shapes/config), not the
        # weights: random init warms the same entries a checkpoint would.
        from bpe_transformer_tpu.models import init_params

        model_config = _load_model_config(args)
        params = init_params(jax.random.PRNGKey(0), model_config)

    if args.decode_attention:
        import dataclasses

        model_config = dataclasses.replace(
            model_config, decode_attention_impl=args.decode_attention
        )
    if args.weight_dtype in ("int8", "both") and model_config.ffn_type == "moe":
        print("warmup: --weight-dtype int8 does not cover MoE expert "
              "stacks", file=sys.stderr)
        return 2
    if draft_spec is not None:
        try:
            draft_spec.validate_against(model_config)
        except ValueError as exc:
            print(f"warmup: {exc}", file=sys.stderr)
            return 2

    # Weight widths to warm: int8-quantized weights lower to DIFFERENT
    # programs (dequant-in-register matmuls), so a --weight-dtype int8
    # replica restarting against a cache warmed only at the activation
    # width would cold-compile its whole ladder; "both" lands every
    # program (PR 9's kv-dtype pattern).
    weight_dtypes: list[str | None] = {
        "act": [None], "int8": ["int8"], "both": [None, "int8"],
    }[args.weight_dtype]

    factories = []
    kv_dtypes: list[str | None] = [None]
    if args.paged:
        from bpe_transformer_tpu.serving import PagedEngine

        # Warm EVERY pool dtype the fleet may restart with (default both):
        # the int8 and activation-width pools lower to different programs,
        # and a --kv-dtype int8 replica restarting against a cache warmed
        # only at full width would cold-compile its whole ladder.
        kv_dtypes = {
            "act": [None], "int8": ["int8"], "both": [None, "int8"],
        }[args.kv_dtype]
        # ONE kwargs list for both engine classes: a knob added here warms
        # the same ladder serve compiles, spec or not.
        if args.speculate:
            from bpe_transformer_tpu.serving import SpecEngine

            cls: type = SpecEngine
            extra = dict(draft=draft_spec, speculate_k=args.speculate)
        else:
            cls, extra = PagedEngine, {}
        for kv_dtype in kv_dtypes:
            for weight_dtype in weight_dtypes:
                # prefix_cache OFF: warmup's point is compiling every
                # ladder rung, and its repeated dummy prompts would
                # otherwise share a prefix and shrink later rungs' chunks
                # into already-compiled programs.
                factory = (
                    lambda kv_dtype=kv_dtype, weight_dtype=weight_dtype: cls(
                        params, model_config, slots=args.slots,
                        block_size=args.block_size,
                        num_blocks=args.num_kv_blocks,
                        prefill_chunk=args.prefill_chunk,
                        prefix_cache=False, kv_dtype=kv_dtype,
                        weight_dtype=weight_dtype,
                        fused_sampling=args.fused_sampling, **extra,
                    )
                )
                # Migration programs touch only the POOL (no weights), so
                # a both-role warm runs them once per pool width — the
                # later weight-width engines would only re-land identical
                # cache entries.  Spec engines skip it here: their import
                # path is `--role decode`'s job (it additionally warms
                # the draft catch-up ladder).
                factory.warm_migration = (
                    not args.speculate and weight_dtype == weight_dtypes[0]
                )
                factories.append(factory)
    else:
        from bpe_transformer_tpu.serving import SlotPoolEngine

        for weight_dtype in weight_dtypes:
            factories.append(
                lambda weight_dtype=weight_dtype: SlotPoolEngine(
                    params, model_config, slots=args.slots,
                    weight_dtype=weight_dtype,
                    fused_sampling=args.fused_sampling,
                )
            )

    ctx = model_config.context_length
    programs = 0
    buckets = None
    # One engine alive at a time: with --num-kv-blocks sized to the serve
    # config's HBM budget, holding the act-width AND int8 pools resident
    # together would OOM warmup on exactly the machine serve fits on.
    for factory in factories:
        engine = factory()
        if buckets is None:
            buckets = list(engine.buckets)
        if args.role == "decode":
            # Decode-role ladder: tick + the import copy program ONLY —
            # grafts are synthesized host-side (zero KV rows; warmup
            # cares about program shapes), so the chunk ladder never
            # compiles.  Speculative engines import at every draft
            # bucket position, warming the draft catch-up re-prefill
            # ladder + propose + verify alongside.
            from bpe_transformer_tpu.serving.kvpool.migrate import (
                synthetic_decode_payload,
            )

            positions = (
                [min(b, ctx - 2) for b in engine.draft_buckets]
                if args.speculate
                else [min(engine.block_size, ctx - 2)]
            )
            for plen in positions:
                slot = engine.import_slot(
                    synthetic_decode_payload(
                        model_config, block_size=engine.block_size,
                        kv_dtype=engine.kv_dtype, prompt_len=plen,
                        max_new_tokens=2,
                    )
                )
                while engine._active[slot]:
                    engine.tick()
        else:
            # Speculative engines walk the DRAFT prefill ladder (it runs
            # to the full context; chunked prefill splits long rungs into
            # the already-walked chunk buckets), so draft prefill +
            # propose + verify all warm alongside the target chunk
            # programs.  The max_new_tokens budget of 2 still exercises a
            # full spec tick.
            ladder = (
                engine.draft_buckets if args.speculate else engine.buckets
            )
            for bucket in ladder:
                plen = min(bucket, ctx - 2)
                event = engine.admit(
                    [1] * plen, max_new_tokens=2, temperature=0.0
                )
                if args.role == "prefill":
                    # Prefill-role ladder: chunk buckets + the export
                    # extract program; the tick NEVER compiles here.
                    if not event.finished:
                        engine.export_slot(event.slot)
                        engine.release(event.slot)
                    continue
                while not event.finished:
                    events = engine.tick()
                    event = next(e for e in events if e.slot == event.slot)
            if (
                args.role == "both" and args.paged
                and getattr(factory, "warm_migration", False)
            ):
                # A both-role replica may evacuate (export) and accept
                # grafts (import): warm the migration pair too.
                from bpe_transformer_tpu.serving.kvpool.migrate import (
                    synthetic_decode_payload,
                )

                slot = engine.import_slot(
                    synthetic_decode_payload(
                        model_config, block_size=engine.block_size,
                        kv_dtype=engine.kv_dtype,
                        prompt_len=min(engine.block_size, ctx - 2),
                        max_new_tokens=2,
                    )
                )
                engine.export_slot(slot)
                engine.release(slot)
        programs += engine.compiled_programs()
        del engine

    summary = {
        "programs_compiled": programs,
        "buckets": buckets,
        "role": args.role,
        "engine": (
            "spec" if args.speculate else "paged" if args.paged else "dense"
        ),
        "speculate": args.speculate or None,
        "decode_attention": model_config.decode_attention_impl,
        "kv_dtypes": [d or "act" for d in kv_dtypes] if args.paged else None,
        "weight_dtypes": [d or "act" for d in weight_dtypes],
        "fused_sampling": args.fused_sampling,
        "cache_dir": str(cache_dir),
        "cache_hits": compile_cache_hits(),
    }
    print(json.dumps(summary))
    return 0


def cmd_profile(args) -> int:
    """Performance attribution without a training job: the XLA cost-model
    roofline of the compiled train step (and, with ``--serve``, the
    serving bucket ladder), plus the measured compute / collective /
    host-gap split when ``--measure N > 0`` — emitted to stdout and,
    with ``--metrics-jsonl``, as a ``kind="attribution"`` telemetry
    stream ``bpe-tpu report`` renders.  CPU-runnable (degraded: the
    roofline verdicts read ``unknown`` without a TPU peak-table entry)."""
    import jax

    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.telemetry import (
        MetricsLogger,
        Telemetry,
        run_manifest,
    )
    from bpe_transformer_tpu.telemetry.attribution import (
        StepProbe,
        serving_program_costs,
    )
    from bpe_transformer_tpu.training.train_step import TrainHParams
    from bpe_transformer_tpu.utils.flops import (
        peak_flops_per_chip,
        peak_hbm_bytes_per_sec,
    )

    if args.checkpoint:
        payload, model_config, _ = _load_inference_state(
            args, need_tokenizer=False
        )
        params = payload["params"]
    else:
        model_config = _load_model_config(args)
        params = init_params(jax.random.PRNGKey(args.seed), model_config)
    model_config = _apply_mfu_knobs(model_config, args)
    opt_state = adamw_init(params)
    device = jax.devices()[0]

    probe = StepProbe(
        model_config,
        TrainHParams(grads_dtype=args.grads_dtype),
        batch_size=args.batch,
        iters=max(args.measure, 1),
        seed=args.seed,
    )
    rows = list(probe.program_costs(params, opt_state))
    if args.serve:
        rows += serving_program_costs(
            params, model_config, slots=args.slots
        )

    peak_f = peak_flops_per_chip(device.device_kind)
    peak_bw = peak_hbm_bytes_per_sec(device.device_kind)
    header = f"== cost model ({device.device_kind}"
    if peak_f and peak_bw:
        header += (
            f", peak {peak_f / 1e12:,.0f} TF/s / {peak_bw / 1e9:,.0f} GB/s"
            f", ridge {peak_f / peak_bw:,.1f} flops/B"
        )
    print(header + ") ==")
    print(f"  {'program':<18s}{'GFLOPs':>10s}{'MB moved':>10s}"
          f"{'AI f/B':>9s}  verdict")

    def fmt(value, width, scale=1.0, digits=2):
        if value is None:
            return f"{'-':>{width}s}"
        return f"{value / scale:>{width},.{digits}f}"

    for row in rows:
        print(
            f"  {row['name']:<18s}"
            + fmt(row["flops"], 10, 1e9)
            + fmt(row["bytes_accessed"], 10, 2**20, 1)
            + fmt(row["arithmetic_intensity"], 9, 1.0, 1)
            + f"  {row['bound']}"
        )

    record = None
    if args.measure > 0:
        wall = probe.loop_wall_step_s(params, opt_state, iters=args.measure)
        record = probe.attribution_record(
            params, opt_state, step=0, wall_step_s=wall, t=0.0,
            include_programs=True,
        )
        record["programs"] = rows  # include the serving ladder if analyzed
        print(f"== measured split ({args.measure} iters) ==")
        coll = record["collective_frac"]
        print(
            f"  wall {record['wall_step_s'] * 1e3:,.2f} ms/step  "
            f"device {record['device_step_s'] * 1e3:,.2f} ms  "
            f"compute {record['compute_frac']:.0%}  collective "
            + (f"{coll:.0%}" if coll is not None else "n/a")
            + f"  host gap {record['host_gap_frac']:.0%}"
        )

    if args.metrics_jsonl:
        logger = MetricsLogger(jsonl_path=args.metrics_jsonl)
        try:
            telemetry = Telemetry(sink=logger.log)
            telemetry.emit(
                run_manifest(
                    kind="profile",
                    model_config=model_config,
                    extra={"batch": args.batch, "measure": args.measure},
                )
            )
            if record is not None:
                record["t"] = telemetry.now()
                telemetry.emit(record)
            telemetry.footer(clean=True)
        finally:
            logger.close()
        print(f"wrote attribution stream -> {args.metrics_jsonl}")

    if args.json:
        summary = {
            "metric": "attribution",
            "config": args.preset or "custom",
            "batch": args.batch,
            "platform": device.platform,
            "device_kind": device.device_kind,
            "programs": rows,
        }
        if record is not None:
            summary.update(
                {
                    k: record[k]
                    for k in (
                        "wall_step_s", "device_step_s", "compute_frac",
                        "collective_frac", "host_gap_frac",
                        "train_peak_hbm_bytes", "remat_policy",
                        "grads_dtype", "scan_layers",
                    )
                }
            )
        print(json.dumps(summary))
    return 0


def cmd_report(args) -> int:
    # Pure host-side file parsing (telemetry.report imports no jax): safe on
    # a laptop reading a metrics.jsonl pulled off a TPU pod.
    from bpe_transformer_tpu.telemetry.report import main as report_main

    forwarded = [args.metrics]
    if args.compare:
        forwarded += ["--compare", args.compare]
    if args.baseline:
        forwarded += ["--baseline", args.baseline]
    if args.trace:
        forwarded += ["--trace", args.trace]
    if args.slo:
        forwarded.append("--slo")
    forwarded += ["--threshold-pct", str(args.threshold_pct)]
    for pair in args.threshold or []:
        forwarded += ["--threshold", pair]
    return report_main(forwarded)


def cmd_verify_checkpoint(args) -> int:
    # Jax-free fast path (resilience/integrity.py): checksums + manifest
    # shape check only — no unpickling, no array loads, safe on a login
    # host while the pod trains.
    from bpe_transformer_tpu.resilience.integrity import main as verify_main

    forwarded = [args.path]
    if args.json:
        forwarded.append("--json")
    return verify_main(forwarded)


def cmd_monitor(args) -> int:
    # jax-free live view: tail a metrics.jsonl or poll a /metrics endpoint.
    from bpe_transformer_tpu.telemetry.monitor import main as monitor_main

    forwarded = []
    if args.metrics:
        forwarded.append(args.metrics)
    if args.url:
        forwarded += ["--url", args.url]
    if args.fleet:
        forwarded += ["--fleet", args.fleet]
    forwarded += ["--interval", str(args.interval)]
    if args.once:
        forwarded.append("--once")
    if args.plain:
        forwarded.append("--plain")
    return monitor_main(forwarded)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="bpe-tpu", description="TPU-native BPE + transformer LM framework"
    )
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("train-tokenizer", help="train a BPE tokenizer")
    p.add_argument("--input", required=True)
    p.add_argument("--vocab-size", type=int, required=True)
    p.add_argument("--special-token", action="append", default=None,
                   help='repeatable; default: ["<|endoftext|>"]')
    p.add_argument("--output-dir", required=True)
    p.add_argument("--workers", type=int, default=None)
    p.set_defaults(fn=cmd_train_tokenizer)

    p = sub.add_parser("tokenize", help="encode a corpus to a binary token file")
    p.add_argument("--input", required=True)
    p.add_argument("--tokenizer-dir", required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--dtype", default="uint16", choices=["uint16", "uint32"])
    p.add_argument("--special-token", action="append", default=None,
                   help='repeatable; default: ["<|endoftext|>"]')
    p.set_defaults(fn=cmd_tokenize)

    p = sub.add_parser("train", help="pretrain a transformer LM")
    p.add_argument("--data", required=True)
    p.add_argument("--val-data", default=None)
    p.add_argument("--dtype", default="uint16", choices=["uint16", "uint32"])
    p.add_argument("--preset", default="tinystories-4l", choices=sorted(PRESETS))
    p.add_argument("--model-config", default=None, help="JSON config path")
    p.add_argument("--steps", type=int, default=1000)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--min-lr", type=float, default=None)
    p.add_argument("--warmup", type=int, default=100)
    p.add_argument("--lr-cycle", type=int, default=None)
    p.add_argument("--weight-decay", type=float, default=0.01)
    p.add_argument("--grad-clip", type=float, default=1.0)
    p.add_argument("--log-every", type=int, default=50)
    p.add_argument("--eval-every", type=int, default=500)
    p.add_argument("--checkpoint-every", type=int, default=1000)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--metrics-jsonl", default=None,
                   help="append step metrics as JSON lines to this file")
    p.add_argument("--wandb-project", default=None,
                   help="log metrics to this wandb project (requires wandb)")
    p.add_argument(
        "--health-stats",
        action="store_true",
        help="compute device-side health stats inside the jitted step "
        "(non-finite loss/grad/param detection, per-layer-group grad/param "
        "norms, MoE expert balance) and log them every --log-every; opt-in "
        "— the default step is unchanged",
    )
    p.add_argument(
        "--dynamics-every",
        type=int,
        default=0,
        metavar="N",
        help='emit kind="dynamics" training-introspection records every N '
        "steps (0 = off; N must be a multiple of --log-every): per-layer "
        "grad/param norms, update-to-param ratios, activation RMS/absmax + "
        "attention entropy, and NaN/Inf localization by tensor path — "
        "computed inside the jitted step and fetched with the existing "
        "log sync, zero extra host syncs",
    )
    p.add_argument(
        "--attribution-every",
        type=int,
        default=0,
        metavar="N",
        help='emit kind="attribution" performance-attribution records '
        "every N steps (0 = off; N must be a multiple of --log-every): "
        "the measured compute / collective / host-gap split of wall step "
        "time plus one-off XLA cost-model roofline verdicts for the "
        "compiled step — the probe runs only at attribution boundaries, "
        "untouched steps pay zero extra host syncs",
    )
    p.add_argument(
        "--watchdog",
        action="store_true",
        help="flag hung steps (no metric sync within --watchdog-factor x "
        "the trailing median step time) and apply --watchdog-policy to "
        "non-finite states detected at a log boundary",
    )
    p.add_argument("--watchdog-factor", type=float, default=10.0)
    p.add_argument(
        "--watchdog-policy",
        choices=["raise", "skip", "rollback"],
        default="raise",
        help='"raise": dump state to the telemetry stream then stop; '
        '"skip": record the event and keep training; "rollback": reload '
        "the last valid checkpoint, skip the offending data window, and "
        "retry (needs --checkpoint-dir; bounded by --max-rollbacks/"
        "--recovery-min-progress)",
    )
    p.add_argument(
        "--max-rollbacks",
        type=int,
        default=3,
        help="crash-loop breaker for --watchdog-policy rollback: abort "
        "after this many rollbacks without --recovery-min-progress steps "
        "of training between them",
    )
    p.add_argument(
        "--recovery-min-progress",
        type=int,
        default=1,
        metavar="STEPS",
        help="steps of training between rollbacks that reset the "
        "--max-rollbacks counter",
    )
    p.add_argument(
        "--keep-checkpoints",
        type=int,
        default=None,
        metavar="N",
        help="retention GC: keep only the newest N step_*.ckpt snapshots "
        "(latest.ckpt's target is never deleted; *.corrupt quarantines are "
        "kept as evidence; stranded .tmp/.old crash debris is reclaimed)",
    )
    p.add_argument(
        "--supervise",
        action="store_true",
        help="run under a jax-free supervisor parent that respawns a "
        "crashed/preempted child with exponential backoff and auto-resume "
        "from the newest valid checkpoint (needs --checkpoint-dir)",
    )
    p.add_argument(
        "--max-restarts",
        type=int,
        default=5,
        help="supervisor budget: consecutive child failures without "
        "checkpoint progress before giving up (with --supervise)",
    )
    p.add_argument(
        "--restart-backoff",
        type=float,
        default=1.0,
        metavar="SECONDS",
        help="initial supervisor restart backoff, doubled per consecutive "
        "failure (with --supervise; preemptions respawn immediately)",
    )
    p.add_argument(
        "--profile-trace",
        default=None,
        metavar="DIR",
        help="capture a jax.profiler trace of the run under DIR "
        "(view with tensorboard --logdir DIR)",
    )
    p.add_argument("--resume", default=None)
    p.add_argument(
        "--parallel",
        default=None,
        choices=["dp", "sp", "pp", "fsdp", "tp", "fsdp_tp", "ep", "dp_ep", "fsdp_ep"],
        help="multi-chip strategy (default: single device)",
    )
    p.add_argument(
        "--pp-microbatches",
        type=int,
        default=4,
        help="pipeline microbatches per step (with --parallel pp)",
    )
    p.add_argument(
        "--mesh",
        default=None,
        help='mesh axes, e.g. "data=8", "data=4,model=2", "data=2,pp=4"',
    )
    p.add_argument(
        "--sp-ulysses",
        action="store_true",
        help="Ulysses all-to-all head-scatter sequence parallelism instead "
        "of the ring (with --parallel sp; num_heads must be a multiple of "
        "the seq mesh axis size)",
    )
    p.add_argument(
        "--sp-zigzag",
        action="store_true",
        help="balanced zig-zag ring schedule (with --parallel sp)",
    )
    p.add_argument(
        "--inner-steps",
        type=int,
        default=1,
        help="optimizer updates per XLA dispatch (lax.scan; single device)",
    )
    p.add_argument(
        "--opt-sharding",
        choices=["zero1"],
        default=None,
        help="ZeRO-1 optimizer-state sharding across the data axis (with "
        "--parallel dp or a GSPMD strategy): AdamW m/v and the fp32 master "
        "weights live 1/N per chip; the dp path reduce-scatters grads "
        "and all-gathers fresh params instead of the all-reduce",
    )
    p.add_argument(
        "--prefetch",
        type=int,
        default=1,
        metavar="N",
        help="batch prefetch depth: sample + stack the next N batches on a "
        "jax-free background thread while the device runs the current step "
        "(0 = synchronous feed; the device transfer itself is an async "
        "enqueue either way); batches stay a pure function of the "
        "iteration, so determinism/resume are unaffected",
    )
    p.add_argument(
        "--compile-cache",
        default=None,
        metavar="DIR",
        help="JAX's persistent compilation cache directory: "
        "respawns/resumes (and any later run of the same config) load "
        "their XLA programs from disk instead of recompiling.  "
        "JAX_COMPILATION_CACHE_DIR wins when set; with neither, an "
        "accelerator run caches under <checkout>/.scratch/jax_ccache and "
        "a CPU run does not cache",
    )
    p.add_argument(
        "--async-checkpoint",
        action="store_true",
        help="write checkpoints in a background thread (overlaps IO with "
        "training; costs one host-RAM copy of the state per save)",
    )
    p.add_argument(
        "--grad-accum-steps",
        type=int,
        default=1,
        help="microbatches per optimizer update (sequential gradient "
        "accumulation; single device; must divide --batch-size)",
    )
    p.add_argument("--seed", type=int, default=0)
    _add_mfu_knob_flags(p)
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("eval", help="evaluate a checkpoint's loss")
    p.add_argument("--checkpoint", required=True)
    p.add_argument("--data", required=True)
    p.add_argument("--dtype", default="uint16", choices=["uint16", "uint32"])
    # default None: prefer the config stored inside the checkpoint.
    p.add_argument("--preset", default=None, choices=sorted(PRESETS))
    p.add_argument("--model-config", default=None)
    p.add_argument("--batches", type=int, default=16)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--seed", type=int, default=0)
    p.set_defaults(fn=cmd_eval)

    p = sub.add_parser("generate", help="sample text from a checkpoint")
    p.add_argument("--checkpoint", required=True)
    p.add_argument("--tokenizer-dir", required=True)
    # default None: prefer the config stored inside the checkpoint.
    p.add_argument("--preset", default=None, choices=sorted(PRESETS))
    p.add_argument("--model-config", default=None)
    p.add_argument("--prompt", default="")
    p.add_argument("--max-new-tokens", type=int, default=128)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None,
                   help="nucleus sampling: keep the smallest prefix of "
                   "probability mass >= p")
    p.add_argument("--special-token", action="append", default=None,
                   help='repeatable; default: ["<|endoftext|>"]')
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--decode-attention",
        choices=["xla", "pallas"],
        default=None,
        help="decode-step cache attention: pallas = the flash-decoding "
        "kernel (TPU; interpret mode elsewhere); default keeps the "
        "portable xla path",
    )
    p.add_argument(
        "--profile-trace",
        default=None,
        metavar="DIR",
        help="capture a jax.profiler trace of the generation under DIR",
    )
    p.set_defaults(fn=cmd_generate)

    p = sub.add_parser(
        "serve",
        help="continuous-batching inference: HTTP JSON endpoint, or offline "
        "batch mode with --prompts-file/--output",
    )
    p.add_argument("--checkpoint", required=True)
    p.add_argument("--tokenizer-dir", required=True)
    # default None: prefer the config stored inside the checkpoint.
    p.add_argument("--preset", default=None, choices=sorted(PRESETS))
    p.add_argument("--model-config", default=None)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000,
                   help="HTTP port (0: ephemeral)")
    p.add_argument("--slots", type=int, default=8,
                   help="concurrent in-flight generations (KV-cache pool "
                   "capacity)")
    p.add_argument("--max-queue", type=int, default=64,
                   help="admission queue capacity; beyond it requests are "
                   "rejected with 503 (backpressure)")
    p.add_argument("--max-wait", type=float, default=0.0,
                   help="seconds an idle engine may hold admissions to "
                   "batch prefills (bounded extra latency)")
    p.add_argument("--max-new-tokens", type=int, default=128,
                   help="default per-request generation budget")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--prompts-file", default=None,
                   help="offline batch mode: one prompt per line in, "
                   "completions JSONL out (--output); no HTTP server")
    p.add_argument("--output", default=None,
                   help="JSONL results path for --prompts-file")
    p.add_argument("--metrics-jsonl", default=None,
                   help="append serving telemetry (request spans, engine "
                   "records) to this file; summarize with bpe-tpu report")
    p.add_argument("--metrics-max-bytes", type=int, default=None,
                   metavar="BYTES",
                   help="size-based JSONL rotation: when the live file "
                   "would exceed BYTES the writer renames it to .1/.2/... "
                   "(never splitting a record), re-stamps the run manifest "
                   "onto the new segment, and keeps the newest 4 rotated "
                   "segments (older ones are GC'd); default: no rotation")
    p.add_argument("--flightrecorder-capacity", type=int, default=256,
                   metavar="EVENTS",
                   help="flight-recorder ring size: the last N scheduling "
                   "decisions (admit/park/reject/deadline/migration/tick) "
                   "kept host-side for GET /debug/flightrecorder and "
                   "triggered kind=blackbox dumps; memory is capped at N "
                   "events regardless of uptime")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   metavar="SECONDS",
                   help="on Ctrl-C/SIGTERM: stop accepting, then wait up "
                   "to this long for queued + in-flight requests to finish "
                   "before cancelling stragglers (graceful drain)")
    p.add_argument("--evacuate-to", action="append", default=None,
                   metavar="HOST:PORT",
                   help="peer replica base URL for drain evacuation "
                   "(repeatable, with --paged): on Ctrl-C/SIGTERM, "
                   "in-flight sessions are exported over the wire to a "
                   "peer's /kv/import and queued requests replayed on "
                   "its /generate instead of finishing in place — the "
                   "replica vanishes without dropping or delaying work")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="JAX's persistent compilation cache directory: "
                   "restarted replicas load the prefill-bucket/decode "
                   "programs from disk instead of recompiling (pre-warm "
                   "with bpe-tpu warmup); same resolution rule as "
                   "bpe-tpu train --compile-cache")
    p.add_argument("--paged", action="store_true",
                   help="paged KV memory: block-pool cache with radix "
                   "prefix sharing (shared system prompts prefill once) "
                   "and chunked prefill (serving/kvpool/)")
    p.add_argument("--block-size", type=int, default=16,
                   help="KV block size in tokens (with --paged; must "
                   "divide the context length)")
    p.add_argument("--num-kv-blocks", type=int, default=None,
                   help="KV pool capacity in blocks (with --paged; "
                   "default: dense-equivalent slots x context / block)")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   metavar="TOKENS",
                   help="chunked prefill: split long prompts into chunks "
                   "of this many tokens, interleaved with decode ticks "
                   "(with --paged; default: whole-prompt prefill)")
    p.add_argument("--prefill-budget", type=int, default=None,
                   metavar="TOKENS",
                   help="max prefill tokens between consecutive decode "
                   "ticks (with --paged + --prefill-chunk): bounds decode "
                   "p99 under heavy prefill traffic")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable the radix prefix cache (with --paged)")
    p.add_argument("--kv-dtype", choices=("act", "int8"), default="act",
                   help="KV block storage width (with --paged): 'act' "
                   "stores at the activation dtype; 'int8' quantizes "
                   "blocks with per-block-per-head f32 scales — ~2x less "
                   "HBM traffic per token vs bf16 (4x vs f32), 2-4x more "
                   "blocks at fixed memory")
    p.add_argument("--decode-attention",
                   choices=("xla", "pallas", "paged"), default=None,
                   help="force the decode-step attention: 'paged' (with "
                   "--paged) is the block-pool-native flash kernel, which "
                   "reads the blocks the slots hold straight out of the "
                   "pool; 'xla' gathers the tables' rows; 'pallas' is the "
                   "dense cache's flash decode (a block pool takes the "
                   "default); default: chosen from the shape and the "
                   "backend (the kernel on the TPU)")
    p.add_argument("--weight-dtype", choices=("act", "int8"), default="act",
                   help="serving weight storage width: 'int8' quantizes "
                   "the matmul weights per output channel at engine build "
                   "(scales captured once) and every program dequantizes "
                   "in registers — ~2x less weight HBM traffic per decode "
                   "tick vs bf16, bounded logit error; embeddings/norms "
                   "stay at the activation width (MoE configs rejected)")
    p.add_argument("--fused-sampling", action="store_true",
                   help="fuse the decode tick's tail — head projection + "
                   "top-k/top-p filtering + sampling (and the spec-decode "
                   "accept/residual distributions) — into one Pallas "
                   "kernel: logits never reach HBM and the per-tick sort "
                   "chain is gone; greedy output is token-identical to "
                   "the unfused path")
    p.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="speculative decoding (with --paged + "
                   "--draft-config): a small draft model proposes K "
                   "tokens per slot per tick, one batched target verify "
                   "pass scores all of them, and rejection sampling "
                   "accepts a prefix — the sampling distribution is "
                   "provably preserved (greedy output is token-identical "
                   "to non-speculative greedy); each accepted token "
                   "saves a full target decode tick")
    p.add_argument("--draft-config", default=None, metavar="JSON",
                   help="DraftSpec JSON for --speculate: "
                   '{"truncate_layers": N} shares the target\'s first N '
                   "blocks (zero extra weight memory), or a tiny "
                   'geometry {"d_model", "num_layers", "num_heads", '
                   '"d_ff"[, "num_kv_heads", "seed"]}; the vocabulary '
                   "must match the target (validated up front)")
    p.add_argument("--role", choices=("prefill", "decode", "both"),
                   default="both",
                   help="disaggregated-fleet role (with --paged): "
                   "'prefill' runs the chunk machine and streams finished "
                   "prefixes out over POST /kv/export instead of ticking; "
                   "'decode' accepts KV grafts on POST /kv/import and "
                   "runs pure decode ticks (fed only imports it never "
                   "compiles a chunk program); 'both' (default) serves "
                   "everything — pair with bpe-tpu route "
                   "--prefill-threshold for two-tier scheduling")
    p.add_argument("--special-token", action="append", default=None,
                   help='repeatable; default: ["<|endoftext|>"]')
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser(
        "route",
        help="health-aware HTTP router over N serve replicas: weighted "
        "balancing off each replica's /statusz (queue depth, free slots, "
        "free KV blocks), drain/death failover with request replay; "
        "jax-free — runs on a front-end box with no accelerator",
    )
    p.add_argument("--replica", action="append", required=True,
                   metavar="HOST:PORT",
                   help="replica base URL (repeatable)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8100,
                   help="router HTTP port (0: ephemeral)")
    p.add_argument("--poll-interval", type=float, default=1.0,
                   help="seconds between replica health polls")
    p.add_argument("--request-timeout", type=float, default=600.0,
                   help="seconds to wait for a replica's response (a "
                   "timeout is NOT replayed — the generation is still "
                   "running on that replica)")
    p.add_argument("--connect-timeout", type=float, default=5.0,
                   help="seconds to wait for a replica's TCP connect "
                   "before failing over")
    p.add_argument("--prefill-threshold", type=int, default=None,
                   metavar="TOKENS",
                   help="two-tier disaggregated scheduling: prompts of "
                   ">= TOKENS prefill on a --role prefill replica and "
                   "decode on the least-loaded decode replica via KV "
                   "migration; shorter prompts bypass straight to decode "
                   "nodes")
    p.add_argument("--suspect-after", type=int, default=3, metavar="N",
                   help="consecutive connect failures before a replica "
                   "is quarantined as suspect and probed on exponential "
                   "backoff instead of every poll; a successful probe "
                   "clears it (counters in /statusz)")
    p.add_argument("--metrics-jsonl", default=None,
                   help="write the router's trace stream (pick/hop/"
                   "request spans per proxied request) to this JSONL; "
                   "one X-Request-Id trace id joins it to the replicas' "
                   "streams")
    p.set_defaults(fn=cmd_route)

    p = sub.add_parser(
        "control",
        help="self-healing fleet control loop: polls the fleet "
        "aggregator + router and acts — hot KV rebalancing, tier "
        "retuning, elastic capacity — with per-action retries, "
        "hysteresis cooldowns, and a crash-loop breaker; jax-free",
    )
    p.add_argument("--fleet", required=True, metavar="HOST:PORT",
                   help="fleet aggregator base URL (bpe-tpu fleet)")
    p.add_argument("--router", default=None, metavar="HOST:PORT",
                   help="router base URL (enables tier retuning via "
                   "POST /admin/threshold)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8300,
                   help="controller HTTP port (0: ephemeral)")
    p.add_argument("--interval", type=float, default=2.0,
                   help="seconds between control ticks")
    p.add_argument("--evidence-max-age", type=float, default=10.0,
                   help="hold (observe-only) when the aggregator's fleet "
                   "record is older than this")
    p.add_argument("--cooldown", type=float, default=30.0,
                   help="per-(action, target) hysteresis window")
    p.add_argument("--action-timeout", type=float, default=30.0,
                   help="per-attempt actuator timeout")
    p.add_argument("--action-retries", type=int, default=3,
                   help="bounded retries per action (exponential backoff)")
    p.add_argument("--max-failures", type=int, default=5,
                   help="consecutive action failures before the "
                   "crash-loop breaker trips (controller halts)")
    p.add_argument("--rebalance-gap", type=int, default=3,
                   help="queue+slots load gap between hottest and "
                   "coldest replica that triggers a session rebalance")
    p.add_argument("--scale-sustain", type=float, default=10.0,
                   help="seconds a queue_growth/block_exhaustion alert "
                   "must persist before scaling up")
    p.add_argument("--scale-down-idle", type=float, default=120.0,
                   help="seconds of fleet idleness before retiring a "
                   "controller-spawned replica")
    p.add_argument("--spawn", action="append", default=[],
                   metavar="URL=CMD",
                   help="declarable replica slot for elastic capacity: "
                   "base URL + the serve command (repeatable; declare "
                   "the URL to the router/fleet too — it sits suspect "
                   "until spawned)")
    p.add_argument("--observe-only", action="store_true",
                   help="decide and record, never act")
    p.add_argument("--once", action="store_true",
                   help="one control tick, print its records, exit")
    p.add_argument("--metrics-jsonl", default=None,
                   help="write kind=control records to this JSONL")
    p.set_defaults(fn=cmd_control)

    p = sub.add_parser(
        "fleet",
        help="fleet aggregator over N serve replicas + the router: "
        "kind=fleet/slo/alert telemetry, SLO burn rates, anomaly "
        "watchdog, fleet /statusz + /metrics; jax-free",
    )
    p.add_argument("--replica", action="append", required=True,
                   metavar="HOST:PORT",
                   help="replica base URL (repeatable)")
    p.add_argument("--router", default=None, metavar="HOST:PORT",
                   help="router base URL (availability counters)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8200,
                   help="fleet HTTP port (0: ephemeral)")
    p.add_argument("--interval", type=float, default=2.0,
                   help="seconds between fleet sweeps")
    p.add_argument("--poll-timeout", type=float, default=5.0,
                   help="per-host poll timeout in seconds")
    p.add_argument("--metrics-jsonl", default=None,
                   help="write fleet/slo/alert records to this JSONL "
                   "(bpe-tpu report summarizes and gates it)")
    p.add_argument("--slo-config", default=None, metavar="JSON",
                   help="objectives as inline JSON or a JSON file path")
    p.add_argument("--window", action="append", type=float, default=None,
                   metavar="SECONDS",
                   help="SLO evaluation window (repeatable)")
    p.add_argument("--once", action="store_true",
                   help="one sweep, print the fleet record, exit")
    p.set_defaults(fn=cmd_fleet)

    p = sub.add_parser(
        "incident",
        help="postmortem bundler: sweep router + replica flight recorders "
        "(GET /debug/flightrecorder) into one JSONL bundle with a "
        "wall-clock-ordered cross-replica timeline; jax-free — "
        "summarize with bpe-tpu report",
    )
    p.add_argument("--replica", action="append", required=True,
                   metavar="HOST:PORT",
                   help="replica base URL (repeatable)")
    p.add_argument("--router", default=None, metavar="HOST:PORT",
                   help="router base URL (its per-hop ring joins the "
                   "timeline)")
    p.add_argument("--timeout", type=float, default=5.0,
                   help="per-host sweep timeout in seconds (hosts are "
                   "swept concurrently: a dead host costs one timeout)")
    p.add_argument("--request", default=None, metavar="REQUEST_ID",
                   help="narrow the timeline to one X-Request-Id "
                   "(cross-host request correlation)")
    p.add_argument("--timeline-cap", type=int, default=2000,
                   help="max merged timeline entries; overflow is counted "
                   "as timeline_truncated, never dropped silently")
    p.add_argument("--out", default="incident.jsonl",
                   help="bundle path (kind=blackbox dumps + one "
                   "kind=incident summary)")
    p.set_defaults(fn=cmd_incident)

    p = sub.add_parser(
        "warmup",
        help="AOT-compile the serving program ladder (prefill buckets + "
        "decode tick) into a persistent compile cache, so replica "
        "restarts reach traffic without cold XLA compiles",
    )
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="persistent compilation cache directory (shared "
                   "with bpe-tpu serve --compile-cache); "
                   "JAX_COMPILATION_CACHE_DIR wins when set, and on an "
                   "accelerator the default is <checkout>/.scratch/"
                   "jax_ccache")
    p.add_argument("--checkpoint", default=None,
                   help="warm with a real checkpoint's config (default: "
                   "--preset with random init — same programs)")
    p.add_argument("--preset", default=None, choices=sorted(PRESETS))
    p.add_argument("--model-config", default=None, help="JSON config path")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--paged", action="store_true",
                   help="warm the paged engine's chunk/tick programs "
                   "instead of the dense ladder")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-kv-blocks", type=int, default=None)
    p.add_argument("--prefill-chunk", type=int, default=None)
    p.add_argument("--kv-dtype", choices=("act", "int8", "both"),
                   default="both",
                   help="which paged pool dtypes to warm (default both: "
                   "a replica restarting with either --kv-dtype hits the "
                   "cache)")
    p.add_argument("--decode-attention",
                   choices=("xla", "pallas", "paged"), default=None,
                   help="warm this decode-attention ladder (use 'paged' "
                   "for --decode-attention paged replicas)")
    p.add_argument("--weight-dtype", choices=("act", "int8", "both"),
                   default="act",
                   help="which weight storage widths to warm: int8 "
                   "weights lower to different (dequant-in-register) "
                   "programs; 'both' lands every program in the cache so "
                   "a replica restarting with either --weight-dtype hits "
                   "(one engine resident at a time)")
    p.add_argument("--fused-sampling", action="store_true",
                   help="warm the fused sample-in-kernel tick programs "
                   "(serve --fused-sampling replicas)")
    p.add_argument("--role", choices=("prefill", "decode", "both"),
                   default="both",
                   help="warm only this role's ladder (with --paged): "
                   "'prefill' = chunk buckets + the export program, no "
                   "tick; 'decode' = tick + the import copy program via "
                   "synthetic grafts, no chunk ladder; 'both' (default) "
                   "= everything incl. the migration pair — "
                   "disaggregated nodes stop paying compile time for "
                   "programs they never run")
    p.add_argument("--speculate", type=int, default=0, metavar="K",
                   help="warm the speculative-decoding programs (with "
                   "--paged + --draft-config): target chunk ladder + "
                   "verify + draft prefill ladder + propose, exactly "
                   "what serve --speculate K compiles")
    p.add_argument("--draft-config", default=None, metavar="JSON",
                   help="DraftSpec JSON for --speculate (same format as "
                   "serve --draft-config)")
    p.add_argument("--train", action="store_true",
                   help="warm the TRAINING step (+ eval) programs "
                   "instead of a serving ladder — the supervisor respawn "
                   "loop's warm-restart path; mirror the train run's "
                   "--batch-size/--lr/... so the lowered program matches")
    p.add_argument("--batch-size", type=int, default=32,
                   help="(--train) batch size of the run to warm")
    p.add_argument("--steps", type=int, default=1000,
                   help="(--train) --steps of the run to warm (the "
                   "cosine cycle length is baked into the program)")
    p.add_argument("--lr", type=float, default=3e-4,
                   help="(--train) learning rate of the run to warm")
    p.add_argument("--min-lr", type=float, default=None)
    p.add_argument("--warmup", type=int, default=100,
                   help="(--train) LR warmup iters of the run to warm")
    p.add_argument("--lr-cycle", type=int, default=None)
    p.add_argument("--weight-decay", type=float, default=0.01)
    p.add_argument("--grad-clip", type=float, default=1.0)
    p.add_argument("--grad-accum-steps", type=int, default=1,
                   help="(--train) gradient-accumulation microbatches")
    p.add_argument("--inner-steps", type=int, default=1,
                   help="(--train) scanned inner steps per dispatch")
    p.add_argument("--health-stats", action="store_true",
                   help="(--train) warm the health-stats step variant")
    p.add_argument("--dynamics-every", type=int, default=0,
                   help="(--train) warm the dynamics step variant")
    _add_mfu_knob_flags(p)
    p.set_defaults(fn=cmd_warmup, default_preset="tinystories-4l")

    p = sub.add_parser(
        "profile",
        help="performance attribution without a training job: XLA "
        "cost-model roofline of the compiled train step (and serving "
        "bucket ladder with --serve) + the measured compute/collective/"
        "host-gap split; CPU-runnable (cost model only degrades to "
        "'unknown' verdicts)",
    )
    p.add_argument("--preset", default=None, choices=sorted(PRESETS))
    p.add_argument("--model-config", default=None, help="JSON config path")
    p.add_argument("--checkpoint", default=None,
                   help="profile a real checkpoint's weights instead of "
                   "randomly initialized params")
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--measure", type=int, default=10, metavar="ITERS",
                   help="timed iterations for the measured split "
                   "(0 = static cost model only)")
    p.add_argument("--serve", action="store_true",
                   help="also cost-model the serving program ladder "
                   "(one prefill per bucket + the decode tick)")
    p.add_argument("--slots", type=int, default=8,
                   help="slot-pool capacity for --serve analysis")
    p.add_argument("--metrics-jsonl", default=None,
                   help='write a manifest + kind="attribution" telemetry '
                   "stream bpe-tpu report can render")
    p.add_argument("--json", action="store_true",
                   help="print a machine-readable summary line (bench "
                   "queue evidence rows)")
    p.add_argument("--seed", type=int, default=0)
    _add_mfu_knob_flags(p)
    p.set_defaults(fn=cmd_profile, default_preset="tinystories-4l")

    p = sub.add_parser(
        "report",
        help="summarize a telemetry metrics.jsonl (loss/throughput/MFU "
        "stats, span breakdown, anomaly list); no accelerator needed; "
        "--compare/--baseline gate regressions with a nonzero exit",
    )
    p.add_argument("metrics", help="path to a metrics.jsonl telemetry stream")
    p.add_argument("--compare", default=None, metavar="BASELINE_JSONL",
                   help="baseline stream: print per-metric deltas; exit 3 "
                   "on any regression beyond threshold")
    p.add_argument("--baseline", default=None, metavar="BENCH_JSON",
                   help="bench capture JSON (tpu_capture_*.json / "
                   "BENCH_*.json) as the comparison baseline")
    p.add_argument("--trace", default=None, metavar="OUT_JSON",
                   help="export the span stream as Chrome trace-event "
                   "JSON (Perfetto / chrome://tracing); engine/resources "
                   "records become counter tracks")
    p.add_argument("--slo", action="store_true",
                   help="force the SLO section (evaluates default "
                   "objectives over fleet records when no slo records "
                   "exist; graceful notice when the stream has neither)")
    p.add_argument("--threshold-pct", type=float, default=5.0,
                   help="default regression threshold in percent")
    p.add_argument("--threshold", action="append", default=[],
                   metavar="METRIC=PCT",
                   help="per-metric threshold override (repeatable)")
    p.set_defaults(fn=cmd_report)

    p = sub.add_parser(
        "verify-checkpoint",
        help="verify a checkpoint's integrity (CRC32 checksums + manifest "
        "shape check; jax-free, loads no arrays); exit 0 = valid, 1 = "
        "corrupt",
    )
    p.add_argument("path", help="dense .ckpt file or sharded checkpoint dir")
    p.add_argument("--json", action="store_true",
                   help="machine-readable verdict")
    p.set_defaults(fn=cmd_verify_checkpoint)

    p = sub.add_parser(
        "monitor",
        help="live operational view: tail a metrics.jsonl or poll a "
        "running server's /metrics endpoint; no accelerator needed",
    )
    p.add_argument("metrics", nargs="?", default=None,
                   help="telemetry metrics.jsonl to tail")
    p.add_argument("--url", default=None, metavar="HOST:PORT",
                   help="poll http://HOST:PORT/metrics instead of a file")
    p.add_argument("--fleet", default=None, metavar="HOST:PORT",
                   help="poll a bpe-tpu fleet aggregator's /statusz "
                   "instead: replicas online/draining, fleet tok/s, "
                   "worst kv headroom, firing alerts, SLO burn")
    p.add_argument("--interval", type=float, default=2.0,
                   help="refresh interval in seconds (default: 2)")
    p.add_argument("--once", action="store_true",
                   help="render one frame and exit (scripts/smoke tests)")
    p.add_argument("--plain", action="store_true",
                   help="plain stdout frames even on a tty (no curses)")
    p.set_defaults(fn=cmd_monitor)

    return parser


def main(argv: list[str] | None = None) -> int:
    raw_argv = list(argv) if argv is not None else sys.argv[1:]
    args = build_parser().parse_args(raw_argv)
    # The raw argv rides along so `train --supervise` can respawn the exact
    # command as its child (minus the supervisor-only flags).
    args._argv = raw_argv
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
