"""The trace reduction on synthetic events and on the recorded ones."""

import bisect
import gzip
import json
import time
from collections import defaultdict

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


def idle_gaps_by_lookback(events, window, skip="chipbench/"):
    """The function the sweep replaced (PR 24), kept as its oracle: for each
    gap, back over the host events that start before its midpoint, as far as
    5 s.  Its cost grows with gaps x host events."""
    per_plane = rt.device_events(events, window)
    busy = rt.union(per_plane[sorted(per_plane)[0]])
    edges = [window[0]] + [t for s, e in busy for t in (s, e)] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted(
        (start, start + dur, name)
        for plane, _, name, start, dur in events
        if plane == HOST and dur > 0 and not name.startswith(skip)
    )
    starts = [h[0] for h in host]
    sums = defaultdict(float)
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        best = None
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            s, e, name = host[i]
            if e >= mid and (best is None or e - s < best[0]):
                best = (e - s, name)
            if mid - s > 5.0:
                break
        sums[best[1] if best else "no host event"] += g1 - g0
    return sorted(([k, v] for k, v in sums.items()), key=lambda kv: -kv[1])


def recorded(name):
    with gzip.open(BENCH / "testdata" / f"{name}.json.gz", "rt") as f:
        data = json.load(f)
    return [tuple(e) for e in data["events"]], tuple(data["window"])


def tiled(events, window, seconds, density=1):
    """The recorded stretch played ``density`` times faster and repeated to
    fill ``seconds``: launches, gaps and host events all grow together, as
    they do behind a faster tick."""
    w0, span = window[0], (window[1] - window[0]) / density
    copies = round(seconds / span)
    out = [
        (plane, line, name, w0 + (start - w0) / density + k * span, dur / density)
        for k in range(copies)
        for plane, line, name, start, dur in events
    ]
    return out, (w0, w0 + copies * span)


RECORDED = sorted(
    p.name.removesuffix(".json.gz") for p in (BENCH / "testdata").glob("*.json.gz")
)


@pytest.mark.parametrize("name", RECORDED)
@pytest.mark.parametrize("seconds, density", [(None, 1), (1.5, 1), (0.75, 2)])
def test_sweep_equals_the_lookback_on_recorded_traces(name, seconds, density):
    events, window = recorded(name)
    if seconds is not None:
        events, window = tiled(events, window, seconds, density)
    got = rt.idle_gaps(events, window, top=10**9)
    want = idle_gaps_by_lookback(events, window)
    assert [k for k, _ in got] == [k for k, _ in want] and len(got) > 2
    assert all(abs(a - b) <= 1e-9 for (_, a), (_, b) in zip(got, want))


@pytest.mark.parametrize("prefer", [(), ("serve/", "train/")])
@pytest.mark.parametrize("name", RECORDED)
def test_gaps_add_up_to_the_idle_time(name, prefer):
    """Labels move idle time from one name to another, never in or out."""
    events, window = recorded(name)
    gaps = rt.idle_gaps(events, window, top=10**9, prefer=prefer)
    busy = rt.busy_seconds(events, window)
    idle = window[1] - window[0] - busy[sorted(busy)[0]]
    assert sum(v for _, v in gaps) == pytest.approx(idle, abs=1e-9)
    if prefer and any(name.startswith(prefer) for _, _, name, _, _ in events):
        # A trace recorded since PR 25 has the worker's phases under every
        # gap of the worker's making: they lead the list.
        assert gaps[0][0].startswith(prefer)


#: Device busy 10.0-10.1, 10.2-10.3, ... inside a 1 s window: gaps with the
#: midpoints 10.15, 10.35, 10.55, 10.75 and 10.95.
COMB = [ev(HOST, "chipbench/traced", 10.0, 1.0)] + [
    ev(DEV, f"fusion.{k}", 10.0 + 0.2 * k, 0.1) for k in range(5)
]


@pytest.mark.parametrize(
    "host, prefer, want",
    [
        # Nested host events on two threads: the innermost covers 10.35,
        # the middle one 10.15 and 10.55, the outer one the rest.
        ([("outer", 10.05, 0.93), ("middle", 10.12, 0.5), ("inner", 10.3, 0.1)], (),
         {"inner": 0.1, "middle": 0.2, "outer": 0.2}),
        # A gap no host event covers, and one whose event ended before it.
        ([("early", 10.1, 0.04), ("late", 10.5, 0.3)], (),
         {"no host event": 0.3, "late": 0.2}),
        # An event that started more than 5 s before the midpoints still
        # covers them: the look-back stopped at the first such event it met
        # and never reached this one behind "old".
        ([("ancient", 2.0, 9.5), ("old", 3.0, 0.5), ("now", 10.32, 0.05)], (),
         {"ancient": 0.4, "now": 0.1}),
        # The harness's own annotation never labels a gap.
        ([("chipbench/traced", 10.0, 1.0), ("chipbench/warm", 10.1, 0.1)], (),
         {"no host event": 0.5}),
        # Of two events equally long the later one wins, as before.
        ([("first", 10.1, 0.1), ("second", 10.12, 0.1)], (), {"second": 0.1, "no host event": 0.4}),
        # With the preference the program's phase wins over the runtime's
        # shorter event inside it; the runtime's name stays where no phase is.
        ([("serve/step", 10.1, 0.5), ("serve/tick_wait", 10.12, 0.3),
          ("np.asarray(jax.Array)", 10.13, 0.05), ("ReadSyncFlag", 10.7, 0.1)],
         ("serve/", "train/"),
         {"serve/tick_wait": 0.2, "serve/step": 0.1, "ReadSyncFlag": 0.1, "no host event": 0.1}),
        ([("serve/step", 10.1, 0.5), ("serve/tick_wait", 10.12, 0.3),
          ("np.asarray(jax.Array)", 10.13, 0.05), ("ReadSyncFlag", 10.7, 0.1)], (),
         {"np.asarray(jax.Array)": 0.1, "serve/tick_wait": 0.1, "serve/step": 0.1,
          "ReadSyncFlag": 0.1, "no host event": 0.1}),
    ],
    ids=["nested", "uncovered", "older-than-5s", "own-annotation", "tie", "prefer", "prefer-off"],
)
def test_sweep_on_synthetic_host_events(host, prefer, want):
    events = COMB + [
        ev(HOST, name, start, dur, line=f"thread{i % 2}")
        for i, (name, start, dur) in enumerate(host)
    ]
    got = dict(rt.idle_gaps(events, (10.0, 11.0), prefer=prefer))
    assert got == pytest.approx(want, abs=1e-9)
    if not prefer and "ancient" not in want:
        assert got == pytest.approx(dict(idle_gaps_by_lookback(events, (10.0, 11.0))), abs=1e-9)


@pytest.mark.parametrize("density, limit_s", [(1, 2.0), (4, 5.0), (10, 15.0)])
def test_reduction_cost_is_linear_in_the_trace(density, limit_s):
    """5 s of the serve trace at the recorded density (48 launches, 77 k
    events; the look-back took 20 s there and 79 s at twice it), at four
    times it (PR 26's) and at ten."""
    events, window = tiled(*recorded("small_serve_trace"), 5.1, density)
    assert len(events) >= 77_000 * density
    t0 = time.perf_counter()
    out = rt.reduce(events, window, prefer=("serve/", "train/"))
    assert time.perf_counter() - t0 < limit_s
    assert 0 < out["busy_s"] < out["window_s"] and out["idle_gaps"]
