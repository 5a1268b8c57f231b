"""Unified telemetry subsystem: one stream tells the whole story of a run.

Subsumes and extends the old ``utils.metrics`` / ``utils.profiling`` pair
(both kept as re-export shims).  The pieces:

- `sinks` — ``MetricsLogger``: stdout / JSONL / wandb fan-out, the single
  write path every record kind shares;
- `spans` — ``Telemetry``: nested wall-clock spans and point events emitted
  as structured records alongside step metrics;
- `manifest` — ``run_manifest``: the self-describing header record (config,
  mesh, jax/device versions, git SHA, host);
- `health` — device-side health stats computed INSIDE the jitted train step
  (non-finite detection, per-layer-group grad/param norms, MoE load
  balance), fetched with the existing once-per-``log_every`` sync;
- `dynamics` — per-layer training-dynamics introspection (grad/param
  norms, update-to-param ratios, activation stats, NaN/Inf localization),
  same in-graph/zero-extra-sync contract, emitted as ``kind="dynamics"``
  records;
- `attribution` — performance attribution: XLA cost-model roofline
  verdicts per compiled program and the measured compute / collective /
  host-gap split of step time, emitted as ``kind="attribution"`` records
  (``--attribution-every`` / ``bpe-tpu profile``);
- `trace` — Chrome trace-event export of the span stream
  (``bpe-tpu report --trace``, jax-free) + cross-stream per-request
  timeline assembly (``request_timeline``);
- `fleet` — the fleet aggregator (``bpe-tpu fleet``, jax-free): polls N
  replicas + the router into ``kind="fleet"`` records and serves
  fleet-level ``/statusz`` + ``/metrics``;
- `slo` — declarative service-level objectives over the fleet stream:
  rolling-window SLIs and error-budget burn rates (``kind="slo"``);
- `alerts` — the serving anomaly watchdog: edge-triggered rule-based
  detectors over engine/fleet gauges (``kind="alert"``), run inside
  every serving engine and the fleet aggregator;
- `flightrecorder` — ``FlightRecorder``: the always-on bounded ring of
  decision events (admit/park/reject, hops, budget deferrals, rollbacks)
  every control-plane component keeps, flushed as ``kind="blackbox"``
  dumps on alert/watchdog/preemption/manual triggers;
- `incident` — the jax-free ``bpe-tpu incident`` postmortem bundler:
  sweeps router + replica ``/debug/flightrecorder`` pages and writes one
  wall-clock-ordered cross-replica bundle (``kind="incident"``);
- `watchdog` — hung-step detection against the trailing median step time
  plus the "dump state + raise or skip" non-finite policy;
- `timing` — ``StepTimer`` throughput/MFU windows, ``profile_trace``,
  ``time_fn``;
- `report` — the jax-free ``bpe-tpu report`` summarizer.
"""

from bpe_transformer_tpu.telemetry.flightrecorder import FlightRecorder
from bpe_transformer_tpu.telemetry.manifest import git_sha, run_manifest
from bpe_transformer_tpu.telemetry.report import nonfinite_fields
from bpe_transformer_tpu.telemetry.resources import (
    compile_cache_hits,
    compile_events,
    install_compile_counter,
    install_gc_counter,
    record_compile_events,
    sample_resources,
    tree_bytes_per_device,
)
from bpe_transformer_tpu.telemetry.schema import RECORD_SCHEMAS, validate_record
from bpe_transformer_tpu.telemetry.sinks import MetricsLogger
from bpe_transformer_tpu.telemetry.spans import Telemetry
from bpe_transformer_tpu.telemetry.watchdog import NonFiniteError, Watchdog

from bpe_transformer_tpu._lazy import lazy_attrs

#: `health`, `dynamics`, and `timing` import jax at module load; they
#: resolve lazily (PEP 562, shared helper in `_lazy`) so the jax-free
#: members above — most importantly the report tool — stay importable on
#: hosts with no accelerator runtime, matching models/ and training/.
__getattr__ = lazy_attrs(
    __name__,
    {
        "flatten_health": "health",
        "group_norms": "health",
        "health_metrics": "health",
        "nonfinite_count": "health",
        "dynamics_metrics": "dynamics",
        "dynamics_record": "dynamics",
        "flatten_dynamics": "dynamics",
        "StepProbe": "attribution",
        "program_cost": "attribution",
        "roofline": "attribution",
        "serving_program_costs": "attribution",
        "time_call": "attribution",
        "StepTimer": "timing",
        "profile_trace": "timing",
        "time_fn": "timing",
    },
)

__all__ = [
    "FlightRecorder",
    "MetricsLogger",
    "NonFiniteError",
    "RECORD_SCHEMAS",
    "StepProbe",
    "StepTimer",
    "Telemetry",
    "Watchdog",
    "compile_cache_hits",
    "compile_events",
    "dynamics_metrics",
    "dynamics_record",
    "flatten_dynamics",
    "flatten_health",
    "git_sha",
    "group_norms",
    "health_metrics",
    "install_compile_counter",
    "install_gc_counter",
    "nonfinite_count",
    "nonfinite_fields",
    "profile_trace",
    "program_cost",
    "record_compile_events",
    "roofline",
    "run_manifest",
    "sample_resources",
    "serving_program_costs",
    "time_call",
    "time_fn",
    "tree_bytes_per_device",
    "validate_record",
]
