"""Per-channel int8 weight quantization for the serving path.

At serving batch sizes (batch ≲ slots) the decode tick is
weight-bandwidth-bound: every emitted token pays one full HBM sweep of
the matmul weights (T-REX, arXiv:2503.00322, builds an accelerator
around exactly this "reduce external memory access" bottleneck).  This
module shrinks that sweep ~2x under bf16 (4x under f32) by storing the
matmul weights as int8 with one f32 scale per OUTPUT channel:

    W[o, i]  ~=  q[o, i] * scale[o],      q int8, scale = amax_i|W[o,:]|/127

and dequantizing **in registers** at matmul time (the Pallas kernel in
`kernels/pallas/quant_matmul.py`, the weight twin of the PR 9 paged
decode kernel's KV dequant) — a dequantized f32/bf16 copy of the weight
never exists in HBM.  Because the scale is per output row, the matmul
factors exactly:

    y[..., o] = scale[o] * sum_i x[..., i] * q[o, i]

so the inner product runs over the int8 tile and ONE multiply per output
element applies the scale — no per-element dequant tensor at all.

A quantized weight is a plain dict ``{"q": int8 (d_out, d_in),
"scale": f32 (d_out,)}`` — a pytree, so it flows through jit/scan/vmap
unchanged — and `ops.core.linear` / `ops.core.head_logits` dispatch on
it, which is what lets every serving program (decode tick, chunked
prefill, spec verify, draft propose over a truncated view) run quantized
without a second code path.  Training never constructs one: quantization
happens once, at engine build / ``warmup`` time
(:func:`quantize_params`), on the already-compute-dtype-cast tree.

What is quantized: the attention projections (q/k/v/output), the dense
FFN matrices (w1/w2[/w3]), and the LM head — the tensors a decode tick
streams.  What is NOT: token embeddings (a row *gather*, not a matmul —
int8 rows would quantize activations, not traffic), norm gains (tiny),
and MoE expert stacks (the gather-dispatch layout is not covered;
engines refuse ``weight_dtype="int8"`` for MoE configs up front).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from bpe_transformer_tpu.models.config import ModelConfig

__all__ = [
    "dequantize",
    "is_quantized",
    "quant_linear",
    "quant_linear_xla",
    "quantize_params",
    "quantize_weight",
    "tree_bytes",
]

#: Keys of a quantized-weight dict — the dispatch tag `ops.core.linear`
#: checks.  Kept minimal so the dict stays a transparent pytree.
_QKEYS = frozenset({"q", "scale"})


def is_quantized(w) -> bool:
    """True for a quantized-weight dict (works on tracers too — the check
    is structural, never touches array values)."""
    return isinstance(w, dict) and _QKEYS.issubset(w.keys())


def quantize_weight(w: Array) -> dict:
    """Per-output-channel symmetric int8 quantization of a ``(d_out,
    d_in)`` matmul weight: ``scale[o] = max_i |w[o, i]| / 127`` (f32),
    ``q = round(w / scale)`` clipped to ``[-127, 127]``.  An all-zero row
    keeps scale 0 and dequantizes to exact zeros."""
    if w.ndim != 2:
        raise ValueError(
            f"quantize_weight expects a 2D (d_out, d_in) matrix, got "
            f"{w.shape}"
        )
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=1) / 127.0  # (d_out,)
    safe = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(w32 / safe[:, None]), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


def dequantize(w: dict, dtype=jnp.float32) -> Array:
    """Materialize the approximate weight (tests/debugging only — the
    serving path never calls this)."""
    return (
        w["q"].astype(jnp.float32) * w["scale"][:, None]
    ).astype(dtype)


def quant_linear_xla(x: Array, w: dict) -> Array:
    """XLA reference for the quantized matmul: f32 accumulation over the
    int8 tile, ONE scale multiply per output element, output back at
    ``x``'s dtype.  The Pallas kernel's parity oracle — never a fallback:
    a shape the kernel cannot tile raises at trace time."""
    out = jax.lax.dot_general(
        x.astype(jnp.float32), w["q"].astype(jnp.float32),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (out * w["scale"]).astype(x.dtype)


def quant_linear(x: Array, w: dict, *, preserve_f32: bool = False) -> Array:
    """``y = x @ (q * scale).T`` without materializing the dequantized
    weight: the Pallas kernel streams int8 tiles through VMEM and
    dequantizes in registers (interpret mode off-TPU, like every kernel
    here).  ``preserve_f32=True`` returns the f32 accumulator itself —
    the `head_logits` contract (logits stay float32-clean)."""
    from bpe_transformer_tpu.kernels.pallas.quant_matmul import quant_matmul

    out = quant_matmul(x, w["q"], w["scale"])  # f32
    return out if preserve_f32 else out.astype(x.dtype)


def _quantize_ffn(ffn: dict) -> dict:
    """Quantize a dense FFN param dict (swiglu w1/w2/w3 or silu/gelu
    w1/w2) — every 2D leaf is a matmul weight by construction."""
    return {name: quantize_weight(w) for name, w in ffn.items()}


def quantize_params(params: dict, config: ModelConfig) -> dict:
    """Quantize the serving param tree's matmul weights in place of the
    originals: attention projections, dense FFN matrices, and the
    ``lm_head`` leaf when present.  Embeddings and norm gains pass
    through untouched (see module docstring).  Raises for MoE configs —
    the expert stacks' gather-dispatch layout is not covered."""
    if config.ffn_type == "moe":
        raise ValueError(
            'weight_dtype="int8" does not cover MoE expert stacks; '
            "serve MoE configs at the activation width"
        )
    out = {
        "token_embeddings": params["token_embeddings"],
        "ln_final": params["ln_final"],
        "layers": [
            {
                "attn": {
                    name: quantize_weight(w)
                    for name, w in layer["attn"].items()
                },
                "ln1": layer["ln1"],
                "ln2": layer["ln2"],
                "ffn": _quantize_ffn(layer["ffn"]),
            }
            for layer in params["layers"]
        ],
    }
    if "lm_head" in params:
        out["lm_head"] = quantize_weight(params["lm_head"])
    return out


def tree_bytes(tree) -> int:
    """Resident bytes of every array leaf (quantized dicts count their
    int8 payload + f32 scales — the honest footprint)."""
    return sum(
        int(leaf.size) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "dtype")
    )
