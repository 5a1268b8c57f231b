"""Span/event emitter: nested wall-clock spans as structured JSONL records.

``Telemetry`` is the host-side narrator of a run.  It shares the step
metrics' sink (``MetricsLogger.log``), so one JSONL file carries the whole
story — a run manifest header, step records, span/event records, and a
footer — and ``bpe-tpu report`` can reconstruct the run from that single
file.

Record kinds (step metrics carry no ``kind`` key, preserving the existing
JSONL schema):

- ``{"kind": "span", "name", "path", "t", "dur_s", ...attrs}`` — a closed
  wall-clock span; ``path`` is the ``/``-joined nesting
  (``"setup/resume"``), ``t`` the start offset in seconds since the
  ``Telemetry`` object was created.
- ``{"kind": "event", "name", "t", ...attrs}`` — a point-in-time marker
  (NaN detection, watchdog trips, checkpoint completions).
- ``{"kind": "manifest", ...}`` / ``{"kind": "footer", ...}`` — run header
  and trailer (see `telemetry.manifest` and :meth:`Telemetry.footer`).

Every span is made in one place, :class:`Phase`: a clock pair that is also a
``jax.profiler.TraceAnnotation`` of the same name, so under any profiler
session (``bpe-tpu train --profile-trace``, a benchmark's tracer) the
program's spans sit on the ``/host:CPU`` plane, on the device trace's clock.
This module stays jax-free: the annotation type is looked up in
``sys.modules``, so only a process that already holds jax annotates.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from collections import Counter
from typing import Callable


class Phase:
    """One timed stretch of host work: ``start`` and ``dur_s`` on ``clock``,
    and a profiler annotation called ``name`` over the same stretch.

    ``with Phase("serve/admit", clock) as phase: ...`` then ``phase.dur_s``.
    It emits no record: the caller decides what the seconds feed (a span
    record, a ``tick`` record's field, a counter).  With no profiler session
    the annotation costs one ``TraceMe`` check; in a process without jax it
    is not made at all.  Enter and exit on one thread; one that has exited
    may be entered again.
    """

    __slots__ = ("start", "dur_s", "_name", "_clock", "_annotation")

    def __init__(self, name: str, clock=time.perf_counter):
        self._name = name
        self._clock = clock
        self.dur_s = 0.0

    def __enter__(self) -> "Phase":
        # A fresh annotation each time: one that has ended does not start
        # again in a profiler's session.
        jax = sys.modules.get("jax")
        self._annotation = annotation = (
            jax.profiler.TraceAnnotation(self._name) if jax is not None else None
        )
        if annotation is not None:
            annotation.__enter__()
        self.start = self._clock()
        return self

    def __exit__(self, *exc) -> None:
        self.dur_s = self._clock() - self.start
        if self._annotation is not None:
            self._annotation.__exit__(*exc)


class SpanHandle:
    """An open span; ``end()`` (or the ``Telemetry.span`` context manager)
    closes it and emits the record."""

    def __init__(self, telemetry: "Telemetry", name: str, path: str, attrs: dict):
        self._telemetry = telemetry
        self.name = name
        self.path = path
        self._attrs = attrs
        self._phase = Phase(path, telemetry._clock).__enter__()
        self._start = self._phase.start
        self._closed = False

    def end(self, **extra_attrs) -> float:
        """Close the span; returns its duration in seconds.  Idempotent."""
        if self._closed:
            return 0.0
        self._closed = True
        self._phase.__exit__(None, None, None)
        self._telemetry._close_span(self, self._phase.dur_s, extra_attrs)
        return self._phase.dur_s


class Telemetry:
    """Nested spans + events emitted through a record sink.

    ``sink`` is any ``callable(dict)`` — typically ``MetricsLogger.log`` so
    telemetry lands in the same JSONL as step metrics.  With ``sink=None``
    records are buffered and flushed on :meth:`attach` (the training loop
    starts narrating before its sinks exist); never attached, the buffer is
    simply dropped, so a bare ``Telemetry()`` is a safe no-op emitter.

    Emission is lock-protected: the watchdog thread emits hang events while
    the main thread emits step spans.
    """

    def __init__(self, sink: Callable[[dict], None] | None = None, clock=time.perf_counter):
        self._sink = sink
        self._clock = clock
        self._t0 = clock()
        self._stack: list[str] = []
        self._buffer: list[dict] = []
        self._lock = threading.Lock()
        #: "<kind>:<name>" -> count of records emitted; the footer reports it.
        self.counts: Counter = Counter()

    # ------------------------------------------------------------- plumbing

    def attach(self, sink: Callable[[dict], None]) -> None:
        """Set the sink and flush records emitted before it existed."""
        with self._lock:
            self._sink = sink
            buffered, self._buffer = self._buffer, []
            for record in buffered:
                sink(record)

    def emit(self, record: dict) -> None:
        """Send one record to the sink (or buffer it when none is attached)."""
        key = f"{record.get('kind', 'metric')}:{record.get('name', '')}"
        with self._lock:
            self.counts[key] += 1
            if self._sink is None:
                self._buffer.append(record)
            else:
                self._sink(record)

    def _now(self) -> float:
        return self._clock() - self._t0

    def now(self) -> float:
        """Seconds since this Telemetry was created — the ``t`` axis every
        span/event record shares.  Public so emitters of custom record
        kinds (preemption/recovery in the training loop) stamp the same
        timeline."""
        return round(self._now(), 6)

    # ------------------------------------------------------- span/event API

    def start_span(self, name: str, **attrs) -> SpanHandle:
        """Open a span; close it with ``handle.end()``.  Spans must close in
        LIFO order (they nest)."""
        path = "/".join(self._stack + [name])
        self._stack.append(name)
        return SpanHandle(self, name, path, attrs)

    def _close_span(self, handle: SpanHandle, dur: float, extra_attrs: dict) -> None:
        if self._stack and self._stack[-1] == handle.name:
            self._stack.pop()
        self.emit(
            {
                "kind": "span",
                "name": handle.name,
                "path": handle.path,
                "t": round(handle._start - self._t0, 6),
                "dur_s": round(dur, 6),
                **handle._attrs,
                **extra_attrs,
            }
        )

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """``with telemetry.span("compile"): ...`` — nested wall-clock span."""
        handle = self.start_span(name, **attrs)
        try:
            yield handle
        finally:
            handle.end()

    def phase(self, name: str) -> Phase:
        """``with telemetry.phase("train/sync"): ...`` — a :class:`Phase`
        on this narrator's clock: an annotation in a profiler's trace, no
        record.  For what happens every step, where a span record each
        time would swamp the stream."""
        return Phase(name, self._clock)

    def event(self, name: str, **attrs) -> None:
        """Emit a point-in-time event record."""
        self.emit(
            {"kind": "event", "name": name, "t": round(self._now(), 6), **attrs}
        )

    def footer(self, **attrs) -> None:
        """Emit the run trailer: record counts plus caller attrs (step count,
        watchdog verdict).  A JSONL ending without one signals a crash."""
        self.emit(
            {
                "kind": "footer",
                "t": round(self._now(), 6),
                "record_counts": dict(self.counts),
                **attrs,
            }
        )
