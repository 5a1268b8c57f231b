"""Tracing / timing harness: profiler traces, kernel timing, throughput.

Moved here from ``utils/profiling.py`` (kept as a re-export shim) when
telemetry became its own subsystem.

- :func:`profile_trace` — a ``jax.profiler`` trace context writing a
  TensorBoard-viewable trace (XLA ops, fusion, HBM transfers); exposed on
  the CLI as ``bpe-tpu train/generate --profile-trace DIR``.
- :func:`time_fn` — wall-clock a jitted callable with a compile warmup and a
  per-iteration device-sync fence; the general "is this kernel faster"
  harness.  (``benchmarks/bench_attention.py`` keeps its own amortized-sync
  variant: it syncs once after N dispatches, which suits many-small-kernel
  comparisons.)
- :class:`StepTimer` — windowed tokens/sec(/chip) and MFU accounting for
  training loops.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable

import jax


@contextlib.contextmanager
def profile_trace(logdir: str, create_perfetto_link: bool = False):
    """Capture a ``jax.profiler`` device trace under ``logdir``.

    View with ``tensorboard --logdir <logdir>`` (Profile tab) or the
    generated Perfetto link. On TPU this records per-op device timelines,
    fusion boundaries, and HBM traffic; on CPU it still records XLA host
    ops, so the harness is testable without hardware.
    """
    jax.profiler.start_trace(logdir, create_perfetto_link=create_perfetto_link)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def _sync(value) -> None:
    jax.block_until_ready(value)


def time_fn(
    fn: Callable,
    *args,
    iters: int = 10,
    warmup: int = 2,
    **kwargs,
) -> dict:
    """Time ``fn(*args, **kwargs)`` with compile warmup and device sync.

    Returns ``{"mean_s", "best_s", "iters"}``. ``fn`` should return a jax
    value (or pytree of them) so the sync fence is meaningful.
    """
    for _ in range(warmup):
        _sync(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        start = time.perf_counter()
        _sync(fn(*args, **kwargs))
        times.append(time.perf_counter() - start)
    return {
        "mean_s": sum(times) / len(times),
        "best_s": min(times),
        "iters": iters,
    }


class StepTimer:
    """Windowed throughput counter: tokens/sec, tokens/sec/chip, and MFU.

    ``update(n_tokens)`` after every step; ``snapshot()`` returns the rates
    over the window since the last snapshot and resets it. The training loop
    reads a device metric (its own sync point) before calling ``snapshot``,
    so these rates include real device time, not just dispatch time.

    Pass ``flops_per_token`` (training FLOPs per token, e.g.
    ``flops.train_step_flops(cfg, B) / (B * S)``) to get model-FLOPs
    utilization in the snapshot; it is None when the device's peak FLOPs
    are unknown (CPU, unrecognized TPU generation).
    """

    def __init__(self, n_chips: int = 1, flops_per_token: float | None = None):
        self.n_chips = max(n_chips, 1)
        self.flops_per_token = flops_per_token
        self._peak_flops: float | None = None
        if flops_per_token is not None:
            from bpe_transformer_tpu.utils.flops import peak_flops_per_chip

            self._peak_flops = peak_flops_per_chip(jax.devices()[0].device_kind)
        self._window_start = time.perf_counter()
        self._window_tokens = 0
        self._window_excluded = 0.0
        self.total_tokens = 0

    def update(self, n_tokens: int) -> None:
        self._window_tokens += n_tokens
        self.total_tokens += n_tokens

    def exclude(self, seconds: float) -> None:
        """Discount non-step time (jit compile, eval, a synchronous
        checkpoint save) from the current window, so tokens/sec and the
        derived per-step wall time describe training steps — not whatever
        else the loop did between two log boundaries."""
        self._window_excluded += max(seconds, 0.0)

    def snapshot(self) -> dict:
        now = time.perf_counter()
        elapsed = max(now - self._window_start - self._window_excluded, 1e-9)
        tok_per_sec = self._window_tokens / elapsed
        out = {
            "tokens_per_sec": tok_per_sec,
            "tokens_per_sec_per_chip": tok_per_sec / self.n_chips,
            "window_seconds": elapsed,
            "window_tokens": self._window_tokens,
        }
        if self.flops_per_token is not None and self._peak_flops is not None:
            achieved = tok_per_sec * self.flops_per_token / self.n_chips
            out["mfu"] = achieved / self._peak_flops
        self._window_start = now
        self._window_tokens = 0
        self._window_excluded = 0.0
        return out
