"""The trace reduction on synthetic events and on the recorded ones."""

import gzip
import json

import pytest

from chipbench import layer_metrics, reduce_trace as rt
from chipbench.tests.tiny import BENCH

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, name, start, dur, line=None):
    return (plane, line or (rt.OPS_LINE if plane != HOST else "main"), name, start, dur)


SYNTHETIC = [
    ev(HOST, "chipbench/traced", 10.0, 1.0),
    ev(DEV, "while.1", 10.1, 0.4),
    ev(DEV, "fusion.7", 10.1, 0.1),        # nested in the while
    ev(DEV, "fusion.7", 10.3, 0.1),
    ev(DEV, "copy.2", 10.7, 0.1),
    ev(DEV, "fusion.9", 9.95, 0.1),        # straddles the window's start
    ev(DEV, "step", 10.0, 1.0, line="Steps"),  # another grain: not counted
    ev(HOST, "PjitFunction(tick)", 10.5, 0.2),
    ev(HOST, "outer", 10.45, 0.5),
]


def test_union_busy_idle_and_gaps():
    window = rt.annotation_window(SYNTHETIC, "chipbench/traced")
    assert window == (10.0, 11.0)
    out = rt.reduce(SYNTHETIC, window)
    assert out["busy_s"] == pytest.approx(0.05 + 0.4 + 0.1)
    assert out["window_s"] == pytest.approx(1.0)
    ops = dict(out["device_ops"])
    assert ops["fusion.7"] == pytest.approx(0.2) and ops["while.1"] == pytest.approx(0.4)
    gaps = dict(out["idle_gaps"])
    # 10.5-10.7 lies under the inner host event, 10.8-11.0 under the outer one.
    assert gaps["PjitFunction(tick)"] == pytest.approx(0.2)
    assert gaps["outer"] == pytest.approx(0.2, abs=1e-6)
    assert rt.matching_seconds(SYNTHETIC, r"^fusion\.7", window) == pytest.approx(0.2)
    assert rt.matching_seconds(SYNTHETIC, "nothing", window) is None


def test_recorded_trace():
    recorded = sorted((BENCH / "testdata").glob("*.json.gz"))
    assert recorded, "no recorded trace under chipbench/testdata"
    for path in recorded:
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        events = [tuple(e) for e in data["events"]]
        out = rt.reduce(events, tuple(data["window"]))
        assert 0 < out["busy_s"] <= out["window_s"]
        assert len(out["device_ops"]) == 10
        assert data["expect"]["busy_s"] == pytest.approx(out["busy_s"], rel=1e-9)


def test_readers():
    ctx = {
        "scalars": {"busy_s": 2.0, "steps": 4, "zero": 0},
        "records": [{"kind": "span", "name": "prefill", "dur_s": d} for d in (0.1, 0.2, 0.9)],
        "stats_samples": [{"free": 3, "total": 4}, {"free": 1, "total": 4}, {"total": 4}],
        "events": SYNTHETIC, "window": (10.0, 11.0),
    }
    read = layer_metrics.read_metric
    assert read({"kind": "formula", "expr": "1000 * busy_s / steps"}, ctx) == 500.0
    assert read({"kind": "formula", "expr": "busy_s / zero"}, ctx) is None
    assert read({"kind": "formula", "expr": "missing * 2"}, ctx) is None
    spans = {"kind": "records", "select": {"kind": "span", "name": "prefill"}, "field": "dur_s"}
    assert read({**spans, "reduce": "p50", "scale": 1000}, ctx) == 200.0
    assert read({**spans, "reduce": "p95"}, ctx) == 0.9
    assert read({**spans, "select": {"name": "none"}, "reduce": "p50"}, ctx) is None
    pool = {"kind": "stats_samples", "expr": "1 - free / total", "reduce": "max", "scale": 100}
    assert read(pool, ctx) == 75.0
    ops = {"kind": "trace_ops", "pattern": "^copy", "expr": "1000 * ops_s / steps"}
    assert read(ops, ctx) == pytest.approx(25.0)
    with pytest.raises(ValueError):
        read({"kind": "formula", "expr": "__import__('os')"}, ctx)
