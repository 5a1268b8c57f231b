"""Sharded-optimizer + step-overlap head-to-head: plain dp vs dp+ZeRO-1.

Runs the SAME short data-parallel training job twice over every local
device — once with the replicated AdamW update (the BENCH baseline
collective), once with ``opt_sharding="zero1"`` + the double-buffered
host→device prefetch — through the real training loop, so each run emits
the production telemetry (attribution splits, per-chip state bytes) this
bench then reads back.  The JSON row it prints is the PR-7 evidence line:

* ``opt_state_bytes`` vs ``opt_state_bytes_plain`` — per-chip AdamW state
  must scale ~1/N along the dp axis,
* ``host_gap_frac`` vs ``host_gap_frac_plain`` — the prefetcher's effect
  on the measured host-gap fraction,
* ``value`` (tokens/sec/chip, zero1 run) vs ``plain_tokens_per_sec_per_chip``
  — the throughput guardrail: sharding the update must not cost speed.

On a single-device backend the dp mesh is 1-wide: the row still measures
the overlap half honestly, while the bytes ratio reads 1.0 (nothing to
shard across — the row says so via ``n_chips``).

    python benchmarks/bench_sharded_opt.py [--config tinystories-4l]
    JAX_PLATFORMS=cpu python benchmarks/bench_sharded_opt.py --steps 8  # smoke
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bpe_transformer_tpu.utils.chip_probe import require_tpu  # noqa: E402
from bpe_transformer_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

import numpy as np

import jax


def stream_summary(path: Path) -> dict:
    """The comparison-relevant numbers out of one run's telemetry stream
    (jax-free parse — same records ``bpe-tpu report`` reads)."""
    steps, resources, attributions = [], [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = record.get("kind")
            if kind == "resources":
                resources.append(record)
            elif kind == "attribution":
                attributions.append(record)
            elif kind is None and "tokens_per_sec_per_chip" in record:
                steps.append(record)
    tps = [r["tokens_per_sec_per_chip"] for r in steps]
    out = {
        "tokens_per_sec_per_chip": (
            round(float(np.mean(tps)), 1) if tps else None
        ),
    }
    if resources:
        out["opt_state_bytes"] = resources[-1].get("opt_state_bytes")
        out["params_bytes"] = resources[-1].get("params_bytes")
    if attributions:
        last = attributions[-1]
        for key in ("compute_frac", "collective_frac", "host_gap_frac"):
            out[key] = last.get(key)
    return out


def run_variant(
    config, hparams, *, steps, batch, mesh_axes, zero1, prefetch, data, out_jsonl
):
    from bpe_transformer_tpu.training.loop import LoopConfig, train

    # The attribution probe fires once, at the mid-run log boundary (it
    # must be a log_every multiple that lands inside the run).
    log_every = max(steps // 4, 1)
    attribution_every = (steps // (2 * log_every)) * log_every or log_every
    loop = LoopConfig(
        steps=steps,
        batch_size=batch,
        log_every=log_every,
        eval_every=10**9,
        checkpoint_every=10**9,
        metrics_jsonl=str(out_jsonl),
        attribution_every=attribution_every,
        parallel="dp",
        mesh_axes=mesh_axes,
        opt_sharding="zero1" if zero1 else None,
        prefetch=prefetch,
        seed=0,
    )
    train(config, hparams, loop, data, log_fn=lambda *_: None)
    return stream_summary(Path(out_jsonl))


def main() -> int:
    require_tpu(Path(__file__).stem)
    enable_compile_cache()
    parser = argparse.ArgumentParser()
    on_accel = jax.default_backend() != "cpu"
    parser.add_argument(
        "--config", default="tinystories-4l",
        choices=["ts-test", "tinystories-4l", "tinystories-12l"],
    )
    parser.add_argument("--steps", type=int, default=60 if on_accel else 8)
    parser.add_argument("--batch", type=int, default=None)
    args = parser.parse_args()

    from bpe_transformer_tpu.models import config as model_configs
    from bpe_transformer_tpu.training.train_step import TrainHParams

    presets = {
        "ts-test": (model_configs.TS_TEST_CONFIG, 8),
        "tinystories-4l": (model_configs.TINYSTORIES_4L, 32),
        "tinystories-12l": (model_configs.TINYSTORIES_12L, 32),
    }
    config, default_batch = presets[args.config]
    batch = args.batch or default_batch
    n_chips = len(jax.devices())
    if batch % n_chips:
        batch = max(batch // n_chips, 1) * n_chips
    mesh_axes = {"data": n_chips}
    hparams = TrainHParams(warmup_iters=5, cosine_cycle_iters=args.steps)

    # Synthetic learnable stream at the config's vocab (same trick as the
    # loop tests): the bench measures throughput/memory, not convergence.
    vocab = min(config.vocab_size, 4096)
    data = np.tile(np.arange(vocab, dtype=np.int32), 200)

    scratch = Path(tempfile.mkdtemp(prefix="bench_sharded_opt_"))
    plain = run_variant(
        config, hparams, steps=args.steps, batch=batch, mesh_axes=mesh_axes,
        zero1=False, prefetch=0, data=data, out_jsonl=scratch / "plain.jsonl",
    )
    zero1 = run_variant(
        config, hparams, steps=args.steps, batch=batch, mesh_axes=mesh_axes,
        zero1=True, prefetch=2, data=data, out_jsonl=scratch / "zero1.jsonl",
    )

    device = jax.devices()[0]
    row = {
        "metric": "sharded_opt",
        "config": args.config,
        "batch": batch,
        "steps": args.steps,
        "n_chips": n_chips,
        # "value" is the headline field capture tooling sorts on: the
        # zero1 run's tokens/sec/chip.
        "value": zero1.get("tokens_per_sec_per_chip"),
        "plain_tokens_per_sec_per_chip": plain.get("tokens_per_sec_per_chip"),
        "opt_state_bytes": zero1.get("opt_state_bytes"),
        "opt_state_bytes_plain": plain.get("opt_state_bytes"),
        "params_bytes": zero1.get("params_bytes"),
        "host_gap_frac": zero1.get("host_gap_frac"),
        "host_gap_frac_plain": plain.get("host_gap_frac"),
        "compute_frac": zero1.get("compute_frac"),
        "collective_frac": zero1.get("collective_frac"),
        "platform": device.platform,
        "device": str(device),
    }
    if row["opt_state_bytes"] and row["opt_state_bytes_plain"]:
        row["opt_bytes_ratio"] = round(
            row["opt_state_bytes"] / row["opt_state_bytes_plain"], 4
        )
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
