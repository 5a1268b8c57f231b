"""Shared kernel runtime helpers."""

from __future__ import annotations

import jax


def pick_block(n: int, target: int, step: int) -> int:
    """Largest divisor of ``n`` that is a multiple of ``step`` and <=
    ``target``, or 0 when there is none (the caller names its fallback:
    the whole axis, or a ragged last tile)."""
    best = 0
    b = step
    while b <= min(target, n):
        if n % b == 0:
            best = b
        b += step
    return best


def interpret_mode() -> bool:
    """Pallas TPU kernels run in interpret mode on non-TPU backends
    (CPU tests, debugging); compiled Mosaic otherwise."""
    return jax.default_backend() != "tpu"
