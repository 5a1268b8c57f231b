"""Per-component timing breakdown of the training step on the real chip.

A whole-step benchmark reports one number for the whole update; this
script decomposes it so an MFU gap can be attributed to a specific stage
(forward, backward, optimizer, attention impl, CE chunking) instead of
guessed at.  Measurement is `telemetry.attribution`'s shared path —
``time_call`` (value-fetch barrier, warm first) for the sub-stage jits and
``StepProbe`` (non-donating AOT step copies + XLA cost analysis) for the
full update — so these bench rows and the loop's ``kind="attribution"``
telemetry records can never disagree about method.  Full-step rows carry
the static roofline verdict (flops, bytes moved, arithmetic intensity,
compute- vs memory-bound) alongside the measured ms.

Rows (one JSON line each, stdout):
    {"stage": "full_step" | "forward" | "value_and_grad" | ..., "ms": N,
     "config": ..., "platform": "tpu", ...}

Refuses to record CPU-fallback numbers: if the accelerator probe fails the
script exits(3) without output (the TPU queue treats that as a retry).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))



def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", default="gpt2-small-32k")
    parser.add_argument("--batch", type=int, default=32)
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument(
        "--decode",
        action="store_true",
        help="decompose the DECODE path instead of training: prefill(+1) "
        "and per-token scan cost, for each decode_attention_impl — the "
        "attribution the gpt2 decode-cell timeouts need (compile vs "
        "prefill vs token loop)",
    )
    parser.add_argument(
        "--remat-policy", default=None,
        choices=["none", "full", "dots_saveable", "save_attn"],
        help="remat policy for the measured step (default: config's)",
    )
    parser.add_argument(
        "--scan-layers", action="store_true",
        help="measure the scan-over-layers step",
    )
    parser.add_argument(
        "--grads-dtype", default="float32",
        choices=["float32", "bfloat16"],
        help="gradient width at the reduction boundary",
    )
    parser.add_argument(
        "--mfu-push", action="store_true",
        help="training-MFU knob matrix (ISSUE 13): one full_step row per "
        "(remat_policy, grads_dtype, scan_layers) combination with "
        "implied tok/s + mfu + peak_hbm_bytes, so `bpe-tpu report "
        "--compare` can diff each knob against a headline capture",
    )
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from bpe_transformer_tpu.utils.chip_probe import require_tpu
    from bpe_transformer_tpu.utils.compile_cache import enable_compile_cache

    # JAX_PLATFORMS=cpu given explicitly is a functional smoke for the
    # script itself; the rows it emits carry platform "cpu".
    require_tpu(Path(__file__).stem)
    cache_dir = enable_compile_cache()

    import bpe_transformer_tpu.models as models
    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.telemetry.attribution import (
        StepProbe,
        time_call,
    )
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_loss_fn,
    )

    name_to_attr = {
        "tinystories-4l": "TINYSTORIES_4L",
        "tinystories-12l": "TINYSTORIES_12L",
        "gpt2-small-32k": "GPT2_SMALL_32K",
        "gpt2-medium": "GPT2_MEDIUM",
    }
    base = getattr(models, name_to_attr[args.config])
    base = dataclasses.replace(
        base, activation_dtype="bfloat16",
        attention_impl="flash" if base.context_length >= 1024 else "xla",
    )
    if args.remat_policy:
        base = dataclasses.replace(
            base, remat_policy=args.remat_policy, remat=False
        )
    if args.scan_layers:
        base = dataclasses.replace(base, scan_layers=True)
    device = jax.devices()[0]
    rng = np.random.default_rng(0)

    def emit(stage: str, ms: float, **extra) -> None:
        print(
            json.dumps(
                {
                    "stage": stage,
                    "ms": round(ms, 3),
                    "config": args.config,
                    "batch": args.batch,
                    "platform": device.platform,
                    **extra,
                }
            ),
            flush=True,
        )

    def step_row(config, grads_dtype: str | None = None) -> tuple[float, dict]:
        # The shared attribution probe: a NON-donating AOT copy of the
        # update (no state threading needed — the loop's buffers stay
        # valid) timed with the same fenced path the telemetry records
        # use, plus the program's XLA cost-model roofline verdict and
        # peak-HBM envelope, labelled with the execution knobs that
        # produced them (the ISSUE 13 attribution contract: every knob's
        # win or regression names its cause).
        params = init_params(jax.random.PRNGKey(0), config)
        opt_state = adamw_init(params)
        hparams = TrainHParams(grads_dtype=grads_dtype or args.grads_dtype)
        probe = StepProbe(
            config, hparams, batch_size=args.batch, iters=args.iters
        )
        cost = probe.program_costs(params, opt_state)[0]
        memory = probe.memory_stats(params, opt_state)
        measured = probe.measure(params, opt_state)
        return measured["device_step_s"] * 1e3, {
            "flops": cost["flops"],
            "bytes_accessed": cost["bytes_accessed"],
            "arithmetic_intensity": cost["arithmetic_intensity"],
            "bound": cost["bound"],
            "peak_hbm_bytes": memory.get("peak_hbm_bytes"),
            "remat_policy": config.resolved_remat_policy,
            "grads_dtype": hparams.grads_dtype,
            "scan_layers": config.scan_layers,
        }

    if args.mfu_push:
        # Training-MFU knob matrix: the graduated remat ladder at f32
        # grads, then the bf16-collective and scan-layers combinations on
        # the selective-recompute point.  Each row carries implied tok/s +
        # mfu so a reader can diff rows without re-deriving geometry.
        from bpe_transformer_tpu.utils.flops import mfu as mfu_of

        matrix = [
            ("none", "float32", False),
            ("dots_saveable", "float32", False),
            ("full", "float32", False),
            ("save_attn", "float32", False),
            ("save_attn", "bfloat16", False),
            ("save_attn", "bfloat16", True),
        ]
        for policy, grads_dtype, scan in matrix:
            cfg = dataclasses.replace(
                base, remat_policy=policy, remat=False, scan_layers=scan
            )
            ms, cost = step_row(cfg, grads_dtype=grads_dtype)
            tokens_per_sec = args.batch * cfg.context_length / (ms / 1e3)
            emit(
                "mfu_push", ms,
                attention=cfg.attention_impl,
                loss_chunk=cfg.loss_chunk,
                tokens_per_sec=round(tokens_per_sec, 1),
                mfu=(
                    round(m, 4)
                    if (m := mfu_of(cfg, args.batch, ms / 1e3,
                                    device.device_kind)) is not None
                    else None
                ),
                **cost,
            )
        return 0

    if args.decode:
        from bench_decode import PROMPT_LEN  # shared geometry: these rows
        # must stay comparable with the decode.jsonl cells they explain

        from bpe_transformer_tpu.models.decode import generate_cached

        params = init_params(jax.random.PRNGKey(0), base)
        prompt = jnp.asarray(
            rng.integers(0, base.vocab_size, size=(args.batch, PROMPT_LEN)),
            jnp.int32,
        )
        key = jax.random.PRNGKey(1)
        n_long = 33  # per-token cost = (t(33) - t(1)) / 32
        # Honesty marker for the compile row: the persistent compile
        # cache means a RETRY measures a warm "compile" — record how many
        # cache entries existed so the row is self-describing.
        ccache_entries = (
            len(list(cache_dir.iterdir())) if cache_dir is not None else 0
        )
        for impl in ("xla", "pallas"):
            cfg_d = dataclasses.replace(base, decode_attention_impl=impl)

            def gen(n, cfg_d=cfg_d):
                return generate_cached(
                    params, prompt, key, config=cfg_d,
                    max_new_tokens=n, temperature=0.0,
                )

            t0 = time.perf_counter()
            jax.device_get(gen(1))  # compile + first run
            emit(
                "decode_compile_plus_first(new=1)",
                (time.perf_counter() - t0) * 1e3,
                dec=impl,
                ccache_entries_at_start=ccache_entries,
            )
            t1 = time_call(lambda: gen(1), iters=args.iters)
            emit("decode_prefill_plus_1", t1, dec=impl, prompt=PROMPT_LEN)
            t_long = time_call(lambda: gen(n_long), iters=max(args.iters // 2, 3))
            emit(
                "decode_per_token",
                (t_long - t1) / (n_long - 1),
                dec=impl,
                measured_new=n_long,
            )
        return 0

    ids = rng.integers(0, base.vocab_size, size=(args.batch, base.context_length))
    x = jnp.asarray(ids)
    y = jnp.asarray(np.roll(ids, -1, axis=1))

    # 1. The full update as shipped.
    ms, cost = step_row(base)
    emit("full_step", ms, attention=base.attention_impl,
         loss_chunk=base.loss_chunk, **cost)

    # 2. Forward-only and grad-only splits (optimizer cost = full - valgrad).
    params = init_params(jax.random.PRNGKey(0), base)
    loss_fn = make_loss_fn(base)
    fwd = jax.jit(loss_fn)
    emit("forward", time_call(fwd, params, x, y, iters=args.iters))
    vg = jax.jit(jax.value_and_grad(loss_fn))
    emit("value_and_grad", time_call(lambda p: vg(p, x, y)[0], params, iters=args.iters))

    # 3. The other forced attention path at this exact shape (the flash
    # kernel's tiles come from the shape: kernels/pallas/runtime.py).
    for attn in ("xla", "flash"):
        if attn == base.attention_impl:
            continue  # already row 1
        ms, cost = step_row(dataclasses.replace(base, attention_impl=attn))
        emit(
            "full_step", ms,
            attention=attn, loss_chunk=base.loss_chunk,
            **cost,
        )

    # 4. CE chunking policy.  loss_chunk_size=None now resolves to the
    # AUTO chunk on these forced-bf16 configs (PR 13), so the full-logits
    # comparison point must be requested explicitly as 0; rows are
    # labelled with the RESOLVED chunk (null = full logits).
    for chunk in (0, 512):
        cfg = dataclasses.replace(base, loss_chunk_size=chunk)
        if cfg.loss_chunk == base.loss_chunk:
            continue  # already row 1
        ms, cost = step_row(cfg)
        emit(
            "full_step", ms,
            attention=base.attention_impl, loss_chunk=cfg.loss_chunk,
            **cost,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
