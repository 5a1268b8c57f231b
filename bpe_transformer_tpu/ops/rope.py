"""Rotary position embeddings (RoPE), interleaved-pair convention.

Matches the reference contract pinned by `test_rope.npz` (verified to
~5e-7): for each adjacent feature pair ``(x[2k], x[2k+1])`` at position
``p``, rotate by angle ``p * theta^(-2k/d)``.

TPU-first shape discipline: the sin/cos tables are precomputed once for
``max_seq_len`` (host constant, becomes an XLA constant under jit), and
application is a pure elementwise op that XLA fuses into the surrounding
attention matmuls.  The table gather by ``positions`` keeps shapes static so
the whole attention stack stays jit-compatible at any prompt length.

Tables are made in two ways.  From one base: pair ``k`` turns at ``theta **
(-2k / d)``.  Or from frequencies given pair by pair (``inv_freq``), which
is how stretched positions come in: :func:`yarn_inv_freq` (YaRN as the
DeepSeek-V2 family applies it to latent attention's rotated key) leaves the
fast pairs as they are, divides the slow ones by the factor and draws a line
between, and ``magnitude`` scales cos and sin alike.

Reference spec: `/root/reference/tests/adapters.py:187-206` (run_rope),
`bpe_transformer/embeddings/rope.py` (empty placeholder in the reference).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
from jax import Array


def rope_tables(
    d_k: int, max_seq_len: int, theta: float = 10000.0, dtype=jnp.float32,
    *, inv_freq=None, magnitude: float = 1.0,
) -> tuple[Array, Array]:
    """Precompute ``(cos, sin)`` tables of shape ``(max_seq_len, d_k // 2)``:
    pair ``k`` at ``theta ** (-2k / d_k)``, or at ``inv_freq[k]`` where the
    caller gives the ``d_k // 2`` frequencies itself; both tables times
    ``magnitude``."""
    if d_k % 2:
        raise ValueError(f"RoPE feature dim must be even, got {d_k}")
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, d_k, 2, dtype=jnp.float32) / d_k)
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angles = jnp.arange(max_seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    return cos.astype(dtype), sin.astype(dtype)


def yarn_correction_range(
    d_k: int, theta: float, original_len: int, beta_fast: float, beta_slow: float
) -> tuple[int, int]:
    """``(low, high)``: the pairs below ``low`` turn more than ``beta_fast``
    times in ``original_len`` positions, those above ``high`` less than
    ``beta_slow`` times.  The pair that turns ``r`` times is ``d_k ln(
    original_len / (2 pi r)) / (2 ln theta)``; ``low`` is the floor of that
    at ``beta_fast``, ``high`` the ceiling at ``beta_slow``, held to ``0 ..
    d_k - 1`` as the family's code holds them (so a ``high`` past the last
    pair leaves the ramp unfinished inside the head)."""

    def pair(turns: float) -> float:
        return d_k * math.log(original_len / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = math.floor(pair(beta_fast)), math.ceil(pair(beta_slow))
    return max(low, 0), min(high, d_k - 1)


def yarn_inv_freq(
    d_k: int, theta: float, factor: float, original_len: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> np.ndarray:
    """The ``d_k // 2`` frequencies of positions stretched by ``factor``
    (float64, on the host): pair ``k``'s ``f_k = theta ** (-2k / d_k)``
    kept up to ``low``, ``f_k / factor`` from ``high``, and between them
    ``f_k (1 - ramp_k) + f_k / factor * ramp_k`` with ``ramp_k = (k - low)
    / (high - low)`` (:func:`yarn_correction_range`)."""
    low, high = yarn_correction_range(d_k, theta, original_len, beta_fast, beta_slow)
    k = np.arange(d_k // 2, dtype=np.float64)
    freq = theta ** (-2.0 * k / d_k)
    # A range of no width (low == high) ramps over a thousandth of a pair.
    ramp = np.clip((k - low) / max(high - low, 0.001), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / factor * ramp


def yarn_mscale(factor: float, mscale: float) -> float:
    """``m(s) = 0.1 s ln(factor) + 1``, and 1 without a stretch."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(
    x: Array,
    positions: Array,
    cos: Array,
    sin: Array,
) -> Array:
    """Rotate ``x`` (``..., seq, d_k``) by position-dependent angles.

    ``positions`` has shape ``(..., seq)`` (leading dims broadcast against
    ``x``'s batch dims) and indexes into the precomputed tables.
    """
    cos_p = cos[positions]  # (..., seq, d_k//2)
    sin_p = sin[positions]
    x_even = x[..., 0::2]
    x_odd = x[..., 1::2]
    rot_even = x_even * cos_p - x_odd * sin_p
    rot_odd = x_even * sin_p + x_odd * cos_p
    # Re-interleave: stack pairs on a trailing axis and flatten.
    out = jnp.stack([rot_even, rot_odd], axis=-1)
    return out.reshape(x.shape)


def rope(
    x: Array,
    positions: Array,
    *,
    theta: float = 10000.0,
    max_seq_len: int | None = None,
) -> Array:
    """One-shot convenience: build tables and apply (test/reference seam)."""
    if max_seq_len is None:
        max_seq_len = int(positions.max()) + 1
    cos, sin = rope_tables(x.shape[-1], max_seq_len, theta, dtype=x.dtype)
    return apply_rope(x, positions, cos, sin)
