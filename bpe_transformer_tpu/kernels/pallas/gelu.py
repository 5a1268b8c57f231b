"""Pallas GeLU kernel (tanh approximation).

TPU-native equivalent of the reference's only accelerator kernel, the Triton
GeLU (`/root/reference/bpe_transformer/kernels/triton/gelu.py:33-64`), with
the same tanh-approximation constants (sqrt(2/pi) ~ 0.79788456, c=0.044715).

Where the Triton kernel tiles a flat pointer over 1024-thread blocks, the
TPU version tiles a (rows, 128)-lane layout over the VPU: the wrapper pads
and reshapes any input to lane-aligned 2-D tiles, and the kernel body is pure
elementwise VPU work per (ROWS_PER_TILE, 128) block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

LANES = 128
ROWS_PER_TILE = 256  # (256, 128) f32 tile = 128 KB of VMEM per buffer

_SQRT_2_OVER_PI = 0.79788456
_C = 0.044715


def _gelu_kernel(x_ref, y_ref):
    x = x_ref[:]
    inner = _SQRT_2_OVER_PI * (x + _C * x * x * x)
    # tanh via exp, as the reference kernel computes it — but clamped: exp of
    # ~2*44 overflows float32 to inf (NaN after the divide), while tanh has
    # saturated to 1.0 long before that.
    e = jnp.exp(jnp.minimum(2.0 * inner, 30.0))
    tanh = (e - 1.0) / (e + 1.0)
    y_ref[:] = 0.5 * x * (1.0 + tanh)


@jax.custom_jvp
def gelu(x: jax.Array) -> jax.Array:
    """Elementwise tanh-approx GeLU for arrays of any shape/float dtype.

    Differentiable: the backward uses the closed-form derivative in XLA (the
    forward Pallas kernel itself is not traced by autodiff).  On non-TPU
    backends the kernel runs in Pallas interpret mode.
    """
    interpret = interpret_mode()
    original_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]

    tile_elems = ROWS_PER_TILE * LANES
    padded = pl.cdiv(n, tile_elems) * tile_elems
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    tiled = flat.reshape(-1, LANES)
    num_tiles = tiled.shape[0] // ROWS_PER_TILE

    out = pl.pallas_call(
        _gelu_kernel,
        out_shape=jax.ShapeDtypeStruct(tiled.shape, tiled.dtype),
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec(
                (ROWS_PER_TILE, LANES),
                lambda i: (i, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (ROWS_PER_TILE, LANES),
            lambda i: (i, 0),
            memory_space=pltpu.VMEM,
        ),
        interpret=interpret,
        name="gelu",
    )(tiled)

    return out.reshape(-1)[:n].reshape(original_shape)


@gelu.defjvp
def _gelu_jvp(primals, tangents):
    (x,), (dx,) = primals, tangents
    u = _SQRT_2_OVER_PI * (x + _C * x * x * x)
    t = jnp.tanh(u)
    du = _SQRT_2_OVER_PI * (1.0 + 3.0 * _C * x * x)
    grad = 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t * t) * du
    return gelu(x), grad * dx


def gelu_reference(x: jax.Array) -> jax.Array:
    """Plain-XLA tanh-approx GeLU with identical constants (parity oracle)."""
    inner = _SQRT_2_OVER_PI * (x + _C * x * x * x)
    return 0.5 * x * (1.0 + jnp.tanh(inner))
