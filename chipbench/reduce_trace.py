"""From a profiler trace to busy/idle time, per-name sums and idle gaps.

Two parts.  :func:`read_events` is a thin adapter from
``jax.profiler.ProfileData`` (the ``.xplane.pb`` the profiler writes) to
plain tuples ``(plane, line, name, start_s, dur_s)``.  Everything else is a
pure function over such tuples, so it is tested on synthetic events and on
the recorded ones under ``testdata/``.

What a v5e trace looks like (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event per executed HLO
op or fusion (its name is the op's whole HLO line; a ``while`` spans the ops
of its body on the same line) and whose lines ``XLA Modules`` and ``Steps``
hold one event per program launch - the same time at another grain, not to
be counted twice; ``Async XLA Ops`` holds the copies that overlap compute.
An event's only stats are its device offset and duration: no FLOPs, no
category, so names are all there is.  Host threads are the lines of
``/host:CPU``; ``jax.profiler.TraceAnnotation`` and the runtime's own
``TraceMe`` events (``PjitFunction(step)``, ``np.asarray(jax.Array)``) land
there, on the same clock.
"""

from __future__ import annotations

import heapq
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str | Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_events(path: str | Path) -> list[tuple]:
    """Every timed event of the device planes' op lines and of the host
    plane, as ``(plane, line, name, start_s, dur_s)``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    events = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        if not is_device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if is_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                events.append(
                    (plane.name, line.name, short_name(ev.name),
                     ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                )
    return events


def short_name(name: str) -> str:
    """A device op's event name is its whole HLO line; keep the result's
    name (``%fusion.2782 = ...`` -> ``fusion.2782``)."""
    return name.split(" = ", 1)[0].lstrip("%")[:96]


def device_events(events, window=None) -> dict:
    """``{plane: [(start, end, name)]}`` of the device op events, clipped to
    ``window = (t0, t1)`` where given."""
    out = defaultdict(list)
    for plane, line, name, start, dur in events:
        if not DEVICE_PLANE.match(plane) or line != OPS_LINE:
            continue
        end = start + dur
        if window is not None:
            start, end = max(start, window[0]), min(end, window[1])
            if end <= start:
                continue
        out[plane].append((start, end, name))
    return dict(out)


def union(intervals) -> list[tuple]:
    """Merge ``(start, end, ...)`` intervals into disjoint ``(start, end)``."""
    merged = []
    for item in sorted(intervals):
        start, end = item[0], item[1]
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_seconds(events, window=None) -> dict:
    """Per device plane: seconds in which at least one op ran."""
    return {
        plane: sum(e - s for s, e in union(evs))
        for plane, evs in device_events(events, window).items()
    }


def annotation_window(events, name: str):
    """(start, end) of the first host event called ``name`` — the harness
    wraps the traced part of its window in one."""
    for plane, _, ev_name, start, dur in events:
        if plane == HOST_PLANE and ev_name == name:
            return (start, start + dur)
    return None


def op_sums(events, window=None) -> list:
    """``[[name, seconds]]`` of device op time by name, summed over events
    and averaged over the device planes, largest first.  Ops nest (a
    ``while`` holds its body's ops), so the sums can exceed busy time; the
    names are the compiler's, which is all the program gives today."""
    per_plane = device_events(events, window)
    sums = defaultdict(float)
    for evs in per_plane.values():
        for start, end, name in evs:
            sums[name] += end - start
    n = max(len(per_plane), 1)
    return sorted(([k, v / n] for k, v in sums.items()), key=lambda kv: -kv[1])


def matching_seconds(events, pattern: str, window=None) -> float | None:
    """Union of the device time of ops whose name matches ``pattern``,
    averaged over the device planes; None where nothing matches."""
    rx = re.compile(pattern)
    per_plane = device_events(events, window)
    totals = []
    for evs in per_plane.values():
        hit = [(s, e) for s, e, name in evs if rx.search(name)]
        totals.append(sum(e - s for s, e in union(hit)))
    if not totals or not any(totals):
        return None
    return sum(totals) / len(totals)


def idle_gaps(events, window, top: int = 10, skip: str = "chipbench/", prefer=()) -> list:
    """The idle time of the first device plane inside ``window``, labelled
    by what the host was doing: each gap between busy intervals gets the
    name of the shortest host event that covers its midpoint (the innermost
    one; the harness's own ``chipbench/`` annotations do not count), and
    gaps are summed by label.  Where ``prefer`` names prefixes
    (``("serve/", "train/")``: the program's own phases), the shortest
    covering event with such a name wins, and the runtime's names
    (``np.asarray(jax.Array)``) label a gap only where no phase covers it.
    ``[[label, seconds]]``, largest first.

    One sweep: gaps and host events are walked together in time order, the
    host events that have started wait in a heap by duration, and one that
    has ended is dropped when it reaches the top - it cannot cover a later
    midpoint either.  O((G + H) log H) for G gaps and H host events."""
    per_plane = device_events(events, window)
    if not per_plane:
        return []
    busy = union(per_plane[sorted(per_plane)[0]])
    edges = [window[0]] + [t for s, e in busy for t in (s, e)] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = sorted(
        (start, start + dur, name)
        for plane, _, name, start, dur in events
        if plane == HOST_PLANE and dur > 0 and not name.startswith(skip)
    )
    prefer = tuple(prefer)
    # Entries are (duration, -index, end, name): of two events equally long
    # the later in the sorted order wins, as it always has.
    phases, others = [], []
    sums = defaultdict(float)
    nxt = 0
    for g0, g1 in gaps:  # disjoint and in time order, so are their midpoints
        mid = 0.5 * (g0 + g1)
        while nxt < len(host) and host[nxt][0] <= mid:
            s, e, name = host[nxt]
            heap = phases if prefer and name.startswith(prefer) else others
            heapq.heappush(heap, (e - s, -nxt, e, name))
            nxt += 1
        label = "no host event"
        for heap in (phases, others):
            while heap and heap[0][2] < mid:
                heapq.heappop(heap)
            if heap:
                label = heap[0][3]
                break
        sums[label] += g1 - g0
    return sorted(([k, v] for k, v in sums.items()), key=lambda kv: -kv[1])[:top]


def reduce(events, window, prefer=()) -> dict:
    """The numbers a traced run reports: ``busy_s`` (mean over chips),
    ``window_s``, the ten ops with most time and the idle gaps by label
    (``prefer`` as in :func:`idle_gaps`)."""
    busy = busy_seconds(events, window)
    return {
        "busy_s": sum(busy.values()) / len(busy) if busy else 0.0,
        "busy_s_by_plane": busy,
        "window_s": window[1] - window[0],
        "device_ops": op_sums(events, window)[:10],
        "idle_gaps": idle_gaps(events, window, prefer=prefer),
    }


def main(argv=None) -> int:
    """``python3 chipbench/reduce_trace.py <trace_dir> [--save FILE.json.gz
    --first-seconds S]``: print what the trace holds (planes, lines, event
    counts, the reduction over the annotated window) and optionally save the
    events of its first S seconds for ``testdata/``."""
    import argparse
    import collections
    import gzip
    import json

    import jax

    parser = argparse.ArgumentParser()
    parser.add_argument("trace_dir")
    parser.add_argument("--save")
    parser.add_argument("--first-seconds", type=float, default=1.0)
    parser.add_argument("--annotation", default="chipbench/traced")
    args = parser.parse_args(argv)
    path = find_xplane(args.trace_dir)
    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            names = collections.Counter(short_name(ev.name) for ev in line.events)
            if line.name == OPS_LINE:
                first = next(iter(line.events), None)
                if first is not None:
                    print("  stats of one op event:", [(k, str(v)[:60]) for k, v in first.stats])
            print("  line", repr(line.name), sum(names.values()), "events;",
                  [n[:48] for n, _ in names.most_common(4)])
    events = read_events(path)
    window = annotation_window(events, args.annotation)
    print("window", window)
    if window is not None:
        out = reduce(events, window)
        print(json.dumps(out, indent=1))
        if args.save:
            cut = window[0] + args.first_seconds
            kept = [e for e in events if window[0] - 0.01 <= e[3] <= cut]
            with gzip.open(args.save, "wt") as f:
                json.dump({"window": [window[0], cut], "events": kept}, f)
            print("saved", len(kept), "events to", args.save)
    return 0


if __name__ == "__main__":
    import sys
    from pathlib import Path as _Path

    sys.path.insert(0, str(_Path(__file__).resolve().parent.parent))
    sys.exit(main())
