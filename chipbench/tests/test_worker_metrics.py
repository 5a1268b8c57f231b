"""The per-layer metrics that read the program's ``tick`` records (ISSUE
25): the tiny rehearsal of each serve cell, traced, reports every one of
them that lists the cell, and the phases they read add up to the period."""

import json

import pytest

from chipbench import run
from chipbench.tests import tiny

TICK_METRICS = {
    path.stem: json.loads(path.read_text())
    for path in sorted((tiny.BENCH / "layer_metrics").glob("*.json"))
    if json.loads(path.read_text())["reader"].get("select", {}).get("kind") == "tick"
}


def test_the_seven_metrics_are_declared():
    assert set(TICK_METRICS) == {
        "worker.period_ms.mean", "worker.admit_ms.mean", "worker.prefill_ms.mean",
        "tick.dispatch_ms.mean", "tick.wait_ms.mean", "tick.wait_ms.noprefill.p50",
        "worker.deliver_ms.mean",
    }
    declared = json.loads((tiny.BENCH.parent / "BENCHMARK.json").read_text())
    bench = {m["name"]: m for m in declared["per_layer"]}
    e2e = {m["name"]: m for m in declared["end_to_end"]}
    for name, spec in TICK_METRICS.items():
        assert bench[name]["workloads"] == spec["workloads"]
        # A cell reports a metric only where it reports what the metric moves.
        assert set(spec["workloads"]) <= set(e2e[spec["moves"]]["workloads"])


@pytest.mark.parametrize(
    "cell", ["small.serve.decode-heavy", "medium.serve.prefill-heavy"]
)
def test_traced_rehearsal_reports_every_tick_metric_of_the_cell(cell):
    workload, config = tiny.tiny_serve(cell)
    # Long outputs after short prompts: most periods run no prefill chunk,
    # so the metric that selects those periods has something to read.
    workload["traffic"]["prompt_len"].update(lo=3, hi=4)
    workload["traffic"]["output_len"].update(lo=8, hi=10)
    out = run.run_cell(
        workload, config, name=cell, seed=2**31 + 25, seconds=1.0, trace=True,
        emit=lambda o: None, expect_platform="cpu",
    )
    assert out["correct"] is True
    wanted = {n for n, spec in TICK_METRICS.items() if cell in spec["workloads"]}
    assert wanted and wanted <= set(out["metrics"])
    got = {n: out["metrics"][n]["value"] for n in wanted}
    assert all(out["metrics"][n]["unit"] == "ms" for n in wanted)
    assert all(v >= 0 for v in got.values())
    parts = sum(v for n, v in got.items() if n.endswith(".mean") and "period" not in n)
    assert 0 < parts <= got["worker.period_ms.mean"] * (1 + 1e-6)
