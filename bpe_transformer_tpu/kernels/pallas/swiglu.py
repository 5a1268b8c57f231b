"""Fused Pallas SwiGLU FFN kernel.

Computes ``y = (silu(x @ w1.T) * (x @ w3.T)) @ w2.T`` in one kernel — the
gated hidden activation ``(tokens, d_ff)`` never round-trips to HBM (the
extension SURVEY §2.2 M5 anticipates beyond the XLA swiglu).

Tiling: grid ``(token_tiles, ff_tiles)``; each step loads an ``x`` tile and
one ``d_ff`` slice of w1/w3/w2 into VMEM, runs both up-projections + gate on
the MXU/VPU, and accumulates the down-projection into the output tile
(initialized on the first ``ff`` step).  ``d_model`` stays resident per tile.

Backward: closed-form VJP in plain XLA (recomputes the two up-projections —
same rematerialization trade as flash attention's backward).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

BLOCK_M = 256  # token-tile rows
BLOCK_F = 512  # d_ff slice per grid step


def _swiglu_kernel(x_ref, w1_ref, w3_ref, w2_ref, y_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[:] = jnp.zeros_like(y_ref)

    x = x_ref[:]
    up = jax.lax.dot_general(
        x, w1_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    lin = jax.lax.dot_general(
        x, w3_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h = (up * jax.nn.sigmoid(up) * lin).astype(x.dtype)
    y_ref[:] += jax.lax.dot_general(
        h, w2_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(y_ref.dtype)


def _swiglu_impl(x2d, w1, w3, w2, block_m, block_f, interpret):
    m, d = x2d.shape
    ff = w1.shape[0]
    grid = (pl.cdiv(m, block_m), pl.cdiv(ff, block_f))
    return pl.pallas_call(
        _swiglu_kernel,
        out_shape=jax.ShapeDtypeStruct((m, d), x2d.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_f, d), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_f, d), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((d, block_f), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_m, d), lambda i, j: (i, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="swiglu_fused",
    )(x2d, w1, w3, w2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def swiglu_fused(
    x: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    w3: jax.Array,
    block_m: int = BLOCK_M,
    block_f: int = BLOCK_F,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused SwiGLU: ``x (..., d_model)``, ``w1/w3 (d_ff, d_model)``,
    ``w2 (d_model, d_ff)`` -> ``(..., d_model)``.

    Same argument order/layout as ``ops.core.swiglu`` (the XLA baseline and
    parity oracle).  Runs in Pallas interpret mode off-TPU.
    """
    if interpret is None:
        interpret = interpret_mode()
    orig_shape = x.shape
    d = orig_shape[-1]
    ff = w1.shape[0]
    x2d = x.reshape(-1, d)
    m = x2d.shape[0]

    # Pad every tiled dimension up to its block multiple — Pallas blocks must
    # cover the arrays exactly, and zero padding is algebraically inert here
    # (silu(0) * 0 contributes nothing; zero w2 rows produce zero columns).
    pad_m = pl.cdiv(m, block_m) * block_m - m
    pad_f = pl.cdiv(ff, block_f) * block_f - ff
    lane = 128
    pad_d = pl.cdiv(d, lane) * lane - d
    if pad_m or pad_d:
        x2d = jnp.pad(x2d, ((0, pad_m), (0, pad_d)))
    if pad_f or pad_d:
        w1 = jnp.pad(w1, ((0, pad_f), (0, pad_d)))
        w3 = jnp.pad(w3, ((0, pad_f), (0, pad_d)))
        w2 = jnp.pad(w2, ((0, pad_d), (0, pad_f)))
    out = _swiglu_impl(x2d, w1, w3, w2, block_m, block_f, interpret)
    if pad_m or pad_d:
        out = out[:m, :d]
    return out.reshape(orig_shape)


def _swiglu_fwd(x, w1, w2, w3, block_m, block_f, interpret):
    return swiglu_fused(x, w1, w2, w3, block_m, block_f, interpret), (x, w1, w2, w3)


def _swiglu_bwd(block_m, block_f, interpret, residuals, g):
    x, w1, w2, w3 = residuals
    orig_shape = x.shape
    d = orig_shape[-1]
    x2d = x.reshape(-1, d).astype(jnp.float32)
    g2d = g.reshape(-1, d).astype(jnp.float32)
    w1f, w2f, w3f = (w.astype(jnp.float32) for w in (w1, w2, w3))

    up = x2d @ w1f.T          # (m, ff)
    lin = x2d @ w3f.T
    sig = jax.nn.sigmoid(up)
    silu = up * sig
    h = silu * lin            # gated hidden

    gh = g2d @ w2f            # dL/dh, (m, ff)
    d_lin = gh * silu
    d_up = gh * lin * (sig + silu * (1.0 - sig))  # silu' = sig + silu(1-sig)

    dx = (d_up @ w1f + d_lin @ w3f).astype(x.dtype).reshape(orig_shape)
    dw1 = (d_up.T @ x2d).astype(w1.dtype)
    dw3 = (d_lin.T @ x2d).astype(w3.dtype)
    dw2 = (g2d.T @ h).astype(w2.dtype)
    return dx, dw1, dw2, dw3


swiglu_fused.defvjp(_swiglu_fwd, _swiglu_bwd)
