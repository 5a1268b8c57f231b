"""Utilities.  Resolved lazily (PEP 562, like models/ and telemetry/): the
timing and debug helpers import jax, while ``compile_cache.resolve_cache_dir``
and ``chip_probe`` must be importable from a parent process that stays off
the accelerator (``chip_smoke.py``, the bench launchers)."""

from bpe_transformer_tpu._lazy import lazy_attrs

__getattr__ = lazy_attrs(
    __name__,
    {
        "MetricsLogger": "metrics",
        "StepTimer": "profiling",
        "check_finite": "debug",
        "enable_compile_cache": "compile_cache",
        "nan_checks": "debug",
        "profile_trace": "profiling",
        "time_fn": "profiling",
    },
)

__all__ = [
    "MetricsLogger",
    "StepTimer",
    "check_finite",
    "enable_compile_cache",
    "nan_checks",
    "profile_trace",
    "time_fn",
]
