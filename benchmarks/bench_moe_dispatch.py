"""MoE dispatch formulations head-to-head: dense one-hot einsum vs gather.

Same routing semantics (pinned by tests/test_moe.py equivalence tests);
this measures the cost difference.  The one-hot dispatch/combine einsums
cost ``2·n·e·cap·d`` flops EACH — at training shapes that exceeds the
expert FFN compute itself — while the gather formulation moves rows by
index.

On the TPU the numbers are wall-clock evidence; for a host-CPU run
(relative formulation arithmetic, like the ring-schedule comparison) set
``JAX_PLATFORMS=cpu`` explicitly — without it the script exits rc=3 off
the TPU, producing no output.

    python benchmarks/bench_moe_dispatch.py [--tokens N] [--d D] [--ff F]
    JAX_PLATFORMS=cpu python benchmarks/bench_moe_dispatch.py   # CPU smoke
"""

from __future__ import annotations

import argparse
import dataclasses
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




def main() -> int:
    require_tpu(Path(__file__).stem)
    enable_compile_cache()
    parser = argparse.ArgumentParser()
    # Defaults: the tinystories-moe bench shape on accelerators, a scaled
    # shape (same n/(3*ff) dispatch:FFN flop ratio regime) on host CPU.
    on_accel = jax.default_backend() != "cpu"
    parser.add_argument("--tokens", type=int, default=8192 if on_accel else 2048)
    parser.add_argument("--d", type=int, default=512 if on_accel else 256)
    parser.add_argument("--ff", type=int, default=1365 if on_accel else 683)
    parser.add_argument("--experts", type=int, default=8)
    parser.add_argument("--top-k", type=int, default=2)
    parser.add_argument("--iters", type=int, default=10 if on_accel else 3)
    args = parser.parse_args()

    from bpe_transformer_tpu.models import TS_TEST_CONFIG
    from bpe_transformer_tpu.models.moe import init_moe_params, switch_ffn

    base = dataclasses.replace(
        TS_TEST_CONFIG,
        d_model=args.d,
        d_ff=args.ff,
        ffn_type="moe",
        n_experts=args.experts,
        router_top_k=args.top_k,
    )
    dtype = jnp.bfloat16 if on_accel else jnp.float32
    params = init_moe_params(jax.random.PRNGKey(0), base, dtype=dtype)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((args.tokens, args.d)), dtype=dtype
    )

    def timed(config):
        def loss(p, x):
            out, aux = switch_ffn(x, p, config)
            return jnp.sum(out.astype(jnp.float32) ** 2) + aux

        fn = jax.jit(jax.value_and_grad(loss))
        val, _ = fn(params, x)
        float(jax.device_get(val))  # compile + barrier
        start = time.perf_counter()
        for _ in range(args.iters):
            val, _ = fn(params, x)
        float(jax.device_get(val))
        return (time.perf_counter() - start) / args.iters * 1e3

    t_einsum = timed(dataclasses.replace(base, moe_dispatch="einsum"))
    t_gather = timed(dataclasses.replace(base, moe_dispatch="gather"))
    device = jax.devices()[0]
    print(
        json.dumps(
            {
                "metric": (
                    f"moe switch_ffn fwd+bwd (n={args.tokens}, e={args.experts}, "
                    f"top{args.top_k}, d={args.d}, ff={args.ff}, {np.dtype(dtype).name})"
                ),
                "einsum_ms": round(t_einsum, 3),
                "gather_ms": round(t_gather, 3),
                "speedup": round(t_einsum / t_gather, 2),
                "platform": device.platform,
                "device": str(device),
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
