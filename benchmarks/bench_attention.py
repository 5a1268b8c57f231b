"""BASELINE config 4: Pallas fused attention vs XLA baseline at long seq.

Run on a TPU host:  python benchmarks/bench_attention.py
Prints one JSON line per sequence length with both timings and the speedup.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bpe_transformer_tpu.utils.chip_probe import require_tpu  # noqa: E402
from bpe_transformer_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

import numpy as np

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.kernels.pallas.flash_attention import (
    flash_attention,
    flash_attention_with_rope,
)
from bpe_transformer_tpu.ops.core import causal_mask, scaled_dot_product_attention
from bpe_transformer_tpu.ops.rope import apply_rope, rope_tables


def _xla_baseline(q, k, v, causal):
    """The model's OWN attention_impl="xla" math (ops/core.py): compute-
    dtype matmuls, f32 softmax.  The f32-upcast parity oracle
    (kernels/pallas/flash_attention._xla_attention) is NOT a fair speed
    baseline — f32 matmuls run the MXU at ~1/4 rate."""
    mask = causal_mask(q.shape[-2]) if causal else None
    return scaled_dot_product_attention(q, k, v, mask)

BATCH, HEADS, D_HEAD = 1, 8, 64
# Override with e.g. `--seq 16384` to split long runs across invocations;
# `--batch 8 --heads 12` measures a training-shaped grid (the default B=1
# cells are latency-dominated at short seq and noisy between runs).
SEQ_LENS = (1024, 4096, 16384)


def _sync(x) -> float:
    # Fetching one value fences the computation that produced it.
    return float(jax.device_get(x.reshape(-1)[0]))


def _bench(fn, *args, label: str = "", iters: int = 10) -> float | None:
    """Mean seconds/call, or None when the case can't run (e.g. the XLA
    materialized path OOMing at seq 16k — which is the point of flash)."""
    try:
        jitted = jax.jit(fn)
        t_compile = time.perf_counter()
        _sync(jitted(*args))
        print(
            f"  {label}: compiled+first-run in "
            f"{time.perf_counter() - t_compile:.1f}s",
            file=sys.stderr,
            flush=True,
        )
        start = time.perf_counter()
        out = None
        for _ in range(iters):
            out = jitted(*args)
        _sync(out)
        return (time.perf_counter() - start) / iters
    except Exception as exc:  # noqa: BLE001 - report the case as absent
        print(f"case failed: {exc!r}"[:300], file=sys.stderr)
        return None


def _ms(t: float | None):
    return round(t * 1e3, 3) if t is not None else None


def _ratio(a: float | None, b: float | None):
    return round(a / b, 2) if a and b else None




def main() -> int:
    require_tpu(Path(__file__).stem)
    enable_compile_cache()
    seq_lens = SEQ_LENS
    if "--seq" in sys.argv:
        arg = sys.argv[sys.argv.index("--seq") + 1]
        seq_lens = tuple(int(s) for s in arg.split(","))
    batch, heads = BATCH, HEADS
    if "--batch" in sys.argv:
        batch = int(sys.argv[sys.argv.index("--batch") + 1])
    if "--heads" in sys.argv:
        heads = int(sys.argv[sys.argv.index("--heads") + 1])

    rng = np.random.default_rng(0)
    cos, sin = rope_tables(D_HEAD, max(seq_lens))
    on_tpu = jax.default_backend() == "tpu"

    for seq in seq_lens:
        shape = (batch, heads, seq, D_HEAD)
        q, k, v = (
            jnp.asarray(rng.standard_normal(shape), dtype=jnp.bfloat16)
            for _ in range(3)
        )
        pos = jnp.arange(seq)[None, None, :]

        def roped(attn):
            def fn(q, k, v):
                c, s = cos.astype(q.dtype), sin.astype(q.dtype)
                return attn(apply_rope(q, pos, c, s), apply_rope(k, pos, c, s), v)

            return fn

        cos_s, sin_s = cos[:seq], sin[:seq]
        iters = 10 if seq < 16384 else 3
        t_xla = _bench(
            roped(lambda q, k, v: _xla_baseline(q, k, v, True)), q, k, v,
            label=f"xla_fwd@{seq}", iters=iters,
        )
        t_flash = _bench(
            roped(
                lambda q, k, v: flash_attention(q, k, v, True, 512, 512, not on_tpu)
            ),
            q, k, v,
            label=f"flash_fwd@{seq}", iters=iters,
        )
        t_fused = _bench(
            lambda q, k, v: flash_attention_with_rope(
                q, k, v, cos_s, sin_s, True, 512, 512, not on_tpu
            ),
            q, k, v,
            label=f"fused_fwd@{seq}", iters=iters,
        )

        # Backward (training) path: grad of a scalar through attention.
        # The Pallas backward recomputes score blocks in-kernel, so peak
        # memory stays O(S) per row — the XLA backward materializes the
        # (S, S) probability matrix and its cotangent.
        def grad_of(attn):
            g = jax.grad(
                lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )

            # Reduce ALL THREE grads into the timed output: syncing only dq
            # would let jit dead-code-eliminate the XLA path's separate
            # dk/dv einsums while the monolithic Pallas backward kernel
            # still computes everything — biasing the comparison.
            def timed(*a):
                dq, dk, dv = g(*a)
                return (
                    dq.astype(jnp.float32).mean()
                    + dk.astype(jnp.float32).mean()
                    + dv.astype(jnp.float32).mean()
                )

            return timed

        t_xla_bwd = _bench(
            grad_of(roped(lambda q, k, v: _xla_baseline(q, k, v, True))),
            q, k, v,
            label=f"xla_bwd@{seq}", iters=iters,
        )
        t_flash_bwd = _bench(
            grad_of(
                roped(
                    lambda q, k, v: flash_attention(
                        q, k, v, True, 512, 512, not on_tpu
                    )
                )
            ),
            q, k, v,
            label=f"flash_bwd@{seq}", iters=iters,
        )
        t_fused_bwd = _bench(
            grad_of(
                lambda q, k, v: flash_attention_with_rope(
                    q, k, v, cos_s, sin_s, True, 512, 512, not on_tpu
                )
            ),
            q, k, v,
            label=f"fused_bwd@{seq}", iters=iters,
        )
        print(
            json.dumps(
                {
                    "metric": f"rope+causal_attention seq={seq} "
                    f"(B={batch},H={heads},D=64,bf16)",
                    "xla_ms": _ms(t_xla),
                    "pallas_ms": _ms(t_flash),
                    "pallas_fused_rope_ms": _ms(t_fused),
                    "speedup": _ratio(t_xla, t_flash),
                    "speedup_fused": _ratio(t_xla, t_fused),
                    "xla_bwd_ms": _ms(t_xla_bwd),
                    "pallas_bwd_ms": _ms(t_flash_bwd),
                    "pallas_fused_rope_bwd_ms": _ms(t_fused_bwd),
                    "speedup_bwd": _ratio(t_xla_bwd, t_flash_bwd),
                    "speedup_bwd_fused": _ratio(t_xla_bwd, t_fused_bwd),
                    "device": str(jax.devices()[0]),
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
