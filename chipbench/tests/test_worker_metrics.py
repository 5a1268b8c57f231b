"""The per-layer metrics that read the program's ``tick`` records (ISSUE
25): the tiny rehearsal of each serve cell, traced, reports every one of
them that lists the cell, and the phases they read add up to the period."""

import json

import pytest

from chipbench import run, serve_cell
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
        cells = bench[name]["workloads"]
        assert set(spec["workloads"]) <= set(cells)
        assert all(name in tiny.metrics_of_cell(cell) for cell in cells)
        # A cell reports a metric only where it reports what the metric moves.
        assert set(cells) <= set(e2e[spec["moves"]]["workloads"])


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


@pytest.mark.parametrize("budget, ended_by", [(512, "seconds"), (6, "launches")])
def test_traced_part_is_bounded_and_its_counters_cover_it(monkeypatch, budget, ended_by):
    """The traced part ends at ``trace_seconds`` or at the launch budget;
    the counters are read inside it, so ``d_ticks`` is the ``tick`` records
    of the same stretch; and the run says what its trace cost."""
    monkeypatch.setattr(serve_cell, "TRACE_LAUNCH_BUDGET", budget)
    workload, config = tiny.tiny_serve("small.serve.decode-heavy")
    workload["trace_seconds"] = 2.0
    # A quarter of a second of this traffic finishes a handful of requests:
    # all greedy, so that the check has one to score.
    workload["traffic"]["greedy_every"] = 1
    lines = []
    out = run.run_cell(
        workload, config, name="small.serve.decode-heavy", seed=2**31 + 29,
        seconds=1.0, trace=True, emit=lines.append, expect_platform="cpu",
    )
    assert out["correct"] is True
    info = {line["info"]: line for line in lines if "info" in line}
    cost, context = info["trace_cost"], info["context"]
    assert set(cost) == {
        "info", "stop_s", "read_s", "reduce_s", "events", "launches", "traced_s", "ended_by",
    }
    assert cost["ended_by"] == ended_by and cost["events"] > 0
    assert all(cost[k] >= 0 for k in ("stop_s", "read_s", "reduce_s"))
    if ended_by == "launches":
        assert budget <= cost["launches"] and cost["traced_s"] < 2.0
    else:
        assert cost["launches"] < budget and cost["traced_s"] >= 2.0
    # The harness's clock and the profiler's annotation span the same part.
    assert cost["traced_s"] == context["wall_s"]
    assert abs(context["wall_s"] - context["window_s"]) < 0.1
    # The counters and the records cover the same stretch; a launch is a
    # tick or a chunk of its period.
    assert abs(context["d_ticks"] - info["window"]["tick_records"]) <= 1
    assert 0 < context["d_ticks"] <= cost["launches"] + 1
