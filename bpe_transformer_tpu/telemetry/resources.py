"""Resource accounting: the two numbers that actually kill TPU jobs.

Production pjit/TPU deployments die to exactly two silent resource leaks —
HBM creep (a growing live-buffer set marching toward ``bytes_limit``) and
recompile storms (a shape leak turning every step into a multi-second XLA
compile).  Neither shows up in loss curves; both are cheap to sample.  This
module turns them into ``kind="resources"`` records in the unified PR-1
telemetry stream:

- **Device memory** — ``jax.local_devices()[*].memory_stats()`` (per-device
  ``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``, summed across
  local devices).  CPU backends return ``None`` from ``memory_stats()``;
  the fields simply stay ``None`` there.
- **Live buffers** — the total bytes of all live ``jax.Array``\\ s on this
  host (`jax.live_arrays`), a backend-independent HBM proxy that works on
  the CPU test platform too.  Metadata-only: no device sync.
- **Host RSS** — ``/proc/self/status`` VmRSS (with a ``getrusage`` peak
  fallback): host-side leaks (tokenizer tables, checkpoint staging copies)
  kill pods just as dead.
- **Compile events** — a process-wide counter fed by ``jax.monitoring``'s
  compile-duration events (every jit cache miss, including the serving
  engine's bucketed prefills) plus :func:`record_compile_events` for code
  that compiles outside jax's event stream.  A counter that keeps climbing
  after warmup is the recompile-storm signature.
- **Collector pauses** — a process-wide count of the interpreter's garbage
  collections and the seconds they took (:func:`install_gc_counter`, one
  ``gc.callbacks`` hook): a collection stops every Python thread of the
  process, whichever thread set it off.

Everything here is **sync-free** (no ``device_get``, no blocking on async
dispatch) so sampling can ride the existing once-per-``log_every`` metric
fetch at zero additional host syncs per step — and **jax-optional**: on a
host without jax the record still carries RSS, so the module stays safe to
import from the jax-free report/monitor tools.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

#: Process-wide compile-event count (monitoring listener + manual records)
#: and the cumulative seconds those compiles took — the latter is what the
#: serving ``/metrics`` compile-time gauge exposes (a recompile storm is
#: visible as a climbing count; how much wall it stole needs the sum).
_compile_events = 0
_compile_time_s = 0.0
_compile_cache_hits = 0
_compile_lock = threading.Lock()
_listener_installed = False

#: The jax.monitoring duration event every backend compile records exactly
#: once (traced-jaxpr and MLIR-lowering events fire alongside it; counting
#: only this one keeps "1 event == 1 XLA compile").  NOTE: on persistent-
#: compilation-cache HITS this event still fires (its duration then
#: measures cache deserialization, not XLA work) — the cache-hit counter
#: below is what distinguishes a warm start from a recompile.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: Fired once per compile request served from the persistent compilation
#: cache (``--compile-cache DIR`` / utils.compile_cache): a restarted
#: process whose hit counter climbs while wall compile time stays flat is
#: warm-starting as designed.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


#: Process-wide collector accounting (:func:`install_gc_counter`): seconds
#: inside collections, their count over all generations, and the oldest
#: generation's apart.  Written by the one thread that collects (a
#: collection holds the interpreter lock from ``start`` to ``stop``).
_gc_pause_s = 0.0
_gc_collections = 0
_gc_gen2_collections = 0
_gc_started = 0.0
_gc_installed = False


def _on_gc(phase: str, info: dict) -> None:
    global _gc_started, _gc_pause_s, _gc_collections, _gc_gen2_collections
    if phase == "start":
        _gc_started = time.perf_counter()
    elif _gc_started:
        _gc_pause_s += time.perf_counter() - _gc_started
        _gc_collections += 1
        _gc_gen2_collections += info.get("generation") == 2
        _gc_started = 0.0


def install_gc_counter() -> None:
    """Hook the interpreter's collector (``gc.callbacks``) into
    :func:`gc_pauses`.  Idempotent; between collections it costs nothing.
    Collections before installation are not counted."""
    global _gc_installed
    with _compile_lock:
        if not _gc_installed:
            gc.callbacks.append(_on_gc)
            _gc_installed = True


def gc_pauses() -> dict:
    """``{"gc_pause_s", "gc_collections", "gc_gen2_collections"}`` of this
    process so far: seconds inside garbage collections (every Python thread
    stands still for them), how many there were, and how many of the oldest
    generation (the long ones)."""
    return {
        "gc_pause_s": _gc_pause_s,
        "gc_collections": _gc_collections,
        "gc_gen2_collections": _gc_gen2_collections,
    }


def record_compile_events(n: int = 1, duration_s: float = 0.0) -> int:
    """Manually add ``n`` compile events (and their wall time) to the
    process-wide counters (for compile paths jax's monitoring stream
    doesn't cover); returns the new event total."""
    global _compile_events, _compile_time_s
    with _compile_lock:
        _compile_events += n
        _compile_time_s += max(duration_s, 0.0)
        return _compile_events


def compile_events() -> int:
    """Process-wide compile-event count so far (see module docstring)."""
    with _compile_lock:
        return _compile_events


def compile_time_s() -> float:
    """Cumulative wall seconds spent in XLA backend compiles so far (fed
    by the same ``jax.monitoring`` duration events as the counter)."""
    with _compile_lock:
        return _compile_time_s


def compile_cache_hits() -> int:
    """Compile requests served from the persistent compilation cache so
    far (0 when the cache is disabled or jax predates the event)."""
    with _compile_lock:
        return _compile_cache_hits


def install_compile_counter() -> bool:
    """Register the ``jax.monitoring`` listener feeding :func:`compile_events`.

    Idempotent; returns whether the listener is installed.  Safe (returns
    False) without jax or on a jax without the monitoring API.  Callers that
    sample resources should install this as early as possible — events
    before installation are simply not counted.
    """
    global _listener_installed
    # Check-and-register under the lock: listeners cannot be unregistered,
    # so two racing first calls (a ServingEngine construction concurrent
    # with a train loop arming the counter) must not both install — every
    # compile would count twice for the process lifetime.
    with _compile_lock:
        if _listener_installed:
            return True
        try:
            import jax.monitoring as monitoring

            def _on_duration(event: str, duration: float, **_kwargs) -> None:
                if event == _COMPILE_EVENT:
                    record_compile_events(1, duration_s=duration)

            monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:
            return False
        try:
            # Best-effort: older jax has no plain-event listener API; the
            # hit counter then just stays 0.
            def _on_event(event: str, **_kwargs) -> None:
                if event == _CACHE_HIT_EVENT:
                    global _compile_cache_hits
                    with _compile_lock:
                        _compile_cache_hits += 1

            monitoring.register_event_listener(_on_event)
        except Exception:
            pass
        _listener_installed = True
        return True


def host_rss_bytes() -> int | None:
    """Current resident set size of this process in bytes (Linux
    ``/proc/self/status`` VmRSS; ``getrusage`` *peak* RSS as a portable
    fallback), or None when neither source exists."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss is KiB on Linux, bytes on macOS.
        return peak_kb if sys.platform == "darwin" else peak_kb * 1024
    except Exception:
        return None


def device_memory_stats() -> dict | None:
    """Summed ``memory_stats()`` across local devices: ``{"bytes_in_use",
    "peak_bytes_in_use", "bytes_limit", "n_devices"}`` plus
    ``"bytes_in_use_per_device"`` (a list — whether sharded state really
    landed on every chip), or None when the backend exposes no stats (CPU)
    or jax is absent.  Metadata-only — never syncs the device."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return None
    totals = {"bytes_in_use": 0, "peak_bytes_in_use": 0, "bytes_limit": 0}
    per_device = []
    n = 0
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        n += 1
        per_device.append(stats.get("bytes_in_use"))
        for key in totals:
            value = stats.get(key)
            if isinstance(value, int):
                totals[key] += value
    if n == 0:
        return None
    totals["n_devices"] = n
    totals["bytes_in_use_per_device"] = per_device
    return totals


def live_buffer_bytes() -> int | None:
    """Total bytes of live ``jax.Array`` buffers on this host (params, opt
    state, caches, stray temporaries) — the backend-independent HBM proxy.
    None without jax."""
    try:
        import jax

        return int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:
        return None


def tree_bytes_per_device(tree) -> int | None:
    """PER-DEVICE bytes of a pytree of arrays — the number that answers
    "how much HBM does this state cost each chip".

    For a sharded ``jax.Array`` the per-device cost is its shard shape
    (``sharding.shard_shape``) times the itemsize — metadata only, no
    device sync — so a ZeRO-1 optimizer state reports ~1/N of its global
    bytes while replicated params report their full size.  Host/numpy
    leaves count their full ``nbytes`` (they cost that much wherever they
    land).  ``None`` when the tree is empty or jax is absent.
    """
    try:
        import jax
        import numpy as np
    except Exception:
        return None
    total = 0
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return None
    for leaf in leaves:
        try:
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and hasattr(sharding, "shard_shape"):
                shape = sharding.shard_shape(leaf.shape)
            else:
                shape = np.shape(leaf)
            itemsize = np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
            total += int(np.prod(shape)) * itemsize
        except Exception:
            # A leaf we can't size (deleted buffer, exotic type) must not
            # take the whole resource record down.
            continue
    return total


def sample_resources(**extra) -> dict:
    """One ``kind="resources"`` record: host RSS, live-buffer bytes, summed
    device-memory stats (None fields on CPU), and the process compile
    counter.  ``extra`` attrs (``step``, ``t``) merge into the record.
    Sync-free — safe at every ``log_every`` boundary."""
    record: dict = {
        "kind": "resources",
        "time_unix": round(time.time(), 3),
        "host_rss_bytes": host_rss_bytes(),
        "live_buffer_bytes": live_buffer_bytes(),
        "compile_events": compile_events(),
        # Cumulative wall seconds in XLA compiles (not schema-required:
        # older streams predate the field) — the /metrics compile-time
        # gauge and the trace counter track read it.
        "compile_time_s": round(compile_time_s(), 3),
        # Persistent-compilation-cache hits (not schema-required): climbs
        # while compile_time_s stays flat on a warm --compile-cache start.
        "compile_cache_hits": compile_cache_hits(),
    }
    # The collector's pauses (not schema-required; 0 until
    # install_gc_counter has run).
    record.update(gc_pauses())
    mem = device_memory_stats()
    record["hbm_bytes_in_use"] = mem["bytes_in_use"] if mem else None
    record["hbm_peak_bytes_in_use"] = mem["peak_bytes_in_use"] if mem else None
    record["hbm_bytes_limit"] = mem["bytes_limit"] if mem else None
    # Not schema-required (older streams predate it): one entry per local
    # device, so a sharded run shows every chip holding its share.
    record["hbm_bytes_in_use_per_device"] = (
        mem["bytes_in_use_per_device"] if mem else None
    )
    record.update(extra)
    return record
