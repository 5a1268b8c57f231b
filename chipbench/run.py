"""The benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  It finds ``workloads/<name>.json`` and the configuration that
file names under ``configs/``, refuses to run anywhere but on the TPU with
the cell's number of chips, hands the cell to ``<kind>_cell.py`` and prints
the result as the last line of its standard output.  Everything the program
under test or a library prints goes to standard error; lines of the
benchmark's own (each one JSON object with an ``"info"`` key) go to standard
output before the result, and nothing after it.

With ``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` a part of the window runs under the profiler and the metrics
are the cell's per-layer ones (``layer_metrics/*.json``).
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse
import importlib
import json
import os
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TRACE_ANNOTATION = "chipbench/traced"
#: Host events with these prefixes are the program's own phases (PERF.md,
#: section 3): an idle gap is labelled by one of them where one covers it.
PROGRAM_PHASES = ("serve/", "train/")
#: Traces land here: inside the checkout, git-ignored (``.scratch/``).
SCRATCH = ROOT / ".scratch" / "chipbench"


def process_start_time() -> float:
    """Unix time at which this process was started (Linux: /proc), else the
    time this module was imported."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


def load_json(directory: Path, name: str) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"chipbench: no {path.relative_to(HERE.parent)}")
    return json.loads(path.read_text())


def load_peaks(device_kind: str) -> dict:
    """The device's published peaks.  A device that is not in the table is
    an error, not a default."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in peaks:
        raise SystemExit(f"chipbench: no peaks for device kind {device_kind!r}")
    return peaks[device_kind]


def program_model_config(config: dict):
    """The program's ModelConfig for a configuration file: the preset the
    file names, checked against the file's architecture, so that every
    implementation choice (attention path, remat, loss chunking) stays the
    program's; or, without a preset, the architecture alone."""
    from bpe_transformer_tpu.models.config import ModelConfig
    from bpe_transformer_tpu.training.cli import PRESETS

    arch = {k: config[k] for k in config["architecture_keys"]}
    if config.get("program_preset"):
        preset = PRESETS[config["program_preset"]]
        differs = {
            k: (v, getattr(preset, k)) for k, v in arch.items()
            if getattr(preset, k) != v
        }
        if differs:
            raise SystemExit(
                f"chipbench: preset {config['program_preset']} no longer has "
                f"the configuration's sizes: {differs}"
            )
        return preset
    return ModelConfig(**arch)


class Tracer:
    """The profiler around a part of the window, and its reduction."""

    def __init__(self, directory: Path):
        self.directory = directory
        self._annotation = None
        #: What the traced run paid for its trace, for the "trace_cost" line.
        self.cost = {}

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        # Host TraceMe events label the idle gaps; the Python function
        # tracer would slow the very host path that is being measured.
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(self.directory), profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(TRACE_ANNOTATION)
        self._annotation.__enter__()

    def stop(self) -> None:
        import jax

        self._annotation.__exit__(None, None, None)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.cost["stop_s"] = time.perf_counter() - t0

    def reduce(self) -> dict:
        from chipbench import reduce_trace

        t0 = time.perf_counter()
        events = reduce_trace.read_events(reduce_trace.find_xplane(self.directory))
        t1 = time.perf_counter()
        window = reduce_trace.annotation_window(events, TRACE_ANNOTATION)
        if window is None:
            raise RuntimeError("the trace holds no chipbench/traced annotation")
        out = reduce_trace.reduce(events, window, prefer=PROGRAM_PHASES)
        out["events"], out["window"] = events, window
        self.cost.update(
            read_s=t1 - t0, reduce_s=time.perf_counter() - t1, events=len(events)
        )
        return out


def numeric(stats: dict) -> dict:
    """The plain numbers of a ``stats()`` or ``memory_stats()`` dict."""
    return {
        k: v for k, v in stats.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }


def device_report(devices) -> dict:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    ]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }


def run_cell(
    workload: dict,
    config: dict,
    *,
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    emit,
    expect_platform: str = "tpu",
    control: bool = False,
    t_start: float | None = None,
) -> dict:
    """Run one cell and return the result object.  ``expect_platform`` is
    "tpu" for every run of the command; the rehearsals and tests under
    ``tests/`` pass "cpu" with sizes of their own.  ``control=True`` (only
    ``control.py`` and the tests) also reads the lower-precision control's
    numbers beside the program's."""
    t_start = t_start if t_start is not None else process_start_time()
    import jax

    devices = jax.devices()
    if devices[0].platform != expect_platform or len(devices) != workload["chips"]:
        raise SystemExit(
            f"chipbench: cell {name} needs {workload['chips']} "
            f"{expect_platform} device(s); jax has {len(devices)} x "
            f"{devices[0].platform}"
        )
    if expect_platform == "tpu":
        peaks = load_peaks(devices[0].device_kind)
        # The repo's one compile-cache rule: JAX_COMPILATION_CACHE_DIR if the
        # machine sets it, else <checkout>/.scratch/jax_ccache - a fixed path
        # inside the checkout either way.
        from bpe_transformer_tpu.utils.compile_cache import enable_compile_cache

        emit({"info": "compile_cache", "dir": str(enable_compile_cache())})
    else:
        peaks = {"flops_bf16": float("nan"), "hbm_bytes_per_s": float("nan")}
    SCRATCH.mkdir(parents=True, exist_ok=True)
    phases = {"devices_found": time.time() - t_start}

    def phase(label: str) -> None:
        phases[label] = time.time() - t_start

    cell = importlib.import_module(f"chipbench.{workload['kind']}_cell")
    env = {
        "name": name, "workload": workload, "config": config, "seed": seed,
        "seconds": seconds, "trace": trace, "emit": emit, "t_start": t_start,
        "tracer": Tracer(SCRATCH / f"trace_{name}") if trace else None,
        "reference": importlib.import_module(
            f"chipbench.{config.get('reference', 'reference')}"
        ),
        "counts": importlib.import_module(f"chipbench.{config.get('counts', 'counts')}"),
        "model_config": program_model_config(config),
        "devices": devices, "phase": phase, "control": control, "peaks": peaks,
    }
    out = cell.run(env)
    emit({"info": "setup_phases_s", **phases})
    device = out["device"]
    result = {
        "correct": bool(out["correct"]),
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]),
    }
    if trace:
        from chipbench import layer_metrics

        reduced = out["trace"]
        scalars = {
            **out["scalars"],
            "window_s": reduced["window_s"],
            "peak_flops": peaks["flops_bf16"],
            "peak_bytes_per_s": peaks["hbm_bytes_per_s"],
            "chips": workload["chips"],
        }
        if reduced["busy_s"] > 0:  # nothing ran on a device: nothing to read
            scalars["busy_s"] = reduced["busy_s"]
        ctx = {
            "scalars": scalars, "events": reduced["events"],
            "window": reduced["window"],
            "records": out.get("records", ()),
            "stats_samples": out.get("stats_samples", ()),
        }
        emit({"info": "context", **scalars})
        t0 = time.perf_counter()
        result["metrics"] = layer_metrics.evaluate_all(
            HERE / "layer_metrics", name, ctx, also=workload.get("layer_metrics", ())
        )
        # What the trace cost this run: whether a cell is nearing the run's
        # time limit shows here first.  reduce_s holds the readers too.
        cost = env["tracer"].cost
        cost["reduce_s"] += time.perf_counter() - t0
        emit({"info": "trace_cost", **cost, **out["traced"]})
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
        }
    else:
        result["metrics"] = out["metrics"]
    result["device"] = device
    # What `correct` compared, each number beside its limit: last in the line.
    result["compared"] = {
        row["number"]: {"value": row["value"], "limit": row["limit"]}
        for row in out["compared"]
    }
    return result


def load_cell(name: str) -> tuple[dict, dict]:
    workload = load_json(HERE / "workloads", name)
    return workload, load_json(HERE / "configs", workload["config"])


def cli(argv, load=load_cell, expect_platform: str = "tpu") -> int:
    """The command.  ``load`` and ``expect_platform`` are not options of it:
    they exist for ``tests/`` and the rehearsals, which hand the harness
    tiny sizes on the CPU from Python."""
    t_start = process_start_time()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    # The result is the last line of standard output and nothing follows it:
    # keep the real stdout for the benchmark's own lines and point fd 1 at
    # stderr for everyone else (train()'s log_fn, warnings, C libraries).
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def emit(obj: dict) -> None:
        out.write(json.dumps(obj) + "\n")
        out.flush()

    workload, config = load(args.workload)
    result = run_cell(
        workload, config, name=args.workload, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), emit=emit,
        t_start=t_start, expect_platform=expect_platform,
    )
    for number, row in result["compared"].items():
        print(f"compared {number}: {row['value']} (limit {row['limit']})", file=sys.stderr)
    sys.stderr.flush()
    emit(result)
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(cli(sys.argv[1:]))
