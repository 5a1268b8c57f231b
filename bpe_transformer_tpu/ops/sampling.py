"""Order statistics of a logits row without sorting it: the two cut-offs
of runtime top-k / top-p sampling as threshold searches.

Both filters of the serving sampler are value thresholds with ties kept —
all a sort would yield is one scalar a row.  Map each float32 logit to a
uint32 whose unsigned order is the float order (:func:`okey`), then build
the threshold bit by bit from the top: a candidate prefix is accepted when
enough entries lie at or above it (:func:`topk_threshold`) or when the
softmax mass strictly above it has fallen under the nucleus
(:func:`nucleus_threshold`).  32 vectorized passes over the row, and the
thresholds land on representable values, so the keep sets are the sorted
cut-offs' own (the one caveat: the nucleus mass is summed in another order
than a sorted cumulative sum, so a logit within one ulp of the nucleus
boundary may fall on the other side).

Plain ``jnp``: `serving.engine.filter_logits` runs these under XLA over
``(slots, vocab)`` logits in HBM, and the finalize of the fused Pallas
tail (`kernels/pallas/sample.py`) over a row tile in VMEM.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def okey(x):
    """float -> uint32 whose unsigned integer order equals the float order
    (IEEE sign-flip trick; NaN-free inputs assumed).  ``-0.0`` takes
    ``+0.0``'s key, so key comparisons are float comparisons."""
    x = x.astype(jnp.float32)
    b = lax.bitcast_convert_type(
        jnp.where(x == 0.0, 0.0, x), jnp.uint32
    )
    return jnp.where(
        (b >> jnp.uint32(31)) > 0, ~b, b | jnp.uint32(0x80000000)
    )


def _descend(decide, shape):
    """A uint32 threshold per row, built bit by bit from the top:
    ``decide(t, bit)`` returns ``t`` with ``bit`` (a one-bit mask below
    every bit decided so far) set or left clear.  A real loop, not 32
    copies of its body: under XLA a loop keeps the row-sized operands it
    reads on every pass in fast memory where 32 unrolled passes each read
    them from HBM (PERF.md section 6, PR 34: the probe), and a kernel's
    code stays one pass long."""

    def step(i, t):
        return decide(t, jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))

    return lax.fori_loop(0, 32, step, jnp.zeros(shape, jnp.uint32))


def topk_threshold(keys, kk):
    """Per row, the uint32 key of the ``kk``-th largest entry (ties give
    the shared key): radix descent for the largest ``t`` with
    ``count(keys >= t) >= kk``.  ``keys`` (R, V) uint32, ``kk`` (R, 1)
    int32 in [1, V]."""

    def decide(t, bit):
        cnt = jnp.sum(
            (keys >= (t | bit)).astype(jnp.int32), axis=-1, keepdims=True
        )
        return jnp.where(cnt >= kk, t | bit, t)

    return _descend(decide, kk.shape)


def nucleus_threshold(keys, e, p_mass):
    """Per row, the smallest uint32 ``t`` whose strictly-above mass
    ``sum(e[keys > t])`` is below ``p_mass`` — the value-space nucleus
    cutoff (an entry x is kept iff the mass strictly above it is < p,
    which is exactly the keep rule of a sorted cumulative sum).  ``e``
    must be 0 at already-dropped entries."""

    def decide(t, bit):
        # Max completion with this bit still 0: if even it satisfies the
        # predicate, the minimum does too with bit 0; else the bit is 1.
        trial = t | (bit - jnp.uint32(1))
        g = jnp.sum(jnp.where(keys > trial, e, 0.0), axis=-1, keepdims=True)
        return jnp.where(g < p_mass, t, t | bit)

    return _descend(decide, p_mass.shape)
