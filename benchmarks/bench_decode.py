"""Decode-path throughput: KV-cached vs uncached autoregressive sampling.

The reference's contract stops at logits (it ships no sampler at all); this
framework's decode stack is `models/decode.py` (prefill + lax.scan'd
per-token steps over a KV cache, one XLA program per generation) with the
uncached full-forward path of `training/sampling.py` as the baseline.

Run on a TPU host:  python benchmarks/bench_decode.py
Prints one JSON line per (config, batch) with both tokens/sec figures.

`--config tinystories-4l|gpt2-small-32k` and `--batch N` restrict the grid
so long runs can be split across invocations.
"""

from __future__ import annotations

import argparse
import json
import os
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

CONFIGS = {
    "tinystories-4l": "TINYSTORIES_4L",
    "gpt2-small-32k": "GPT2_SMALL_32K",
}
PROMPT_LEN = 64


def make_uncached_step(params, config):
    """One jitted full-forward sample step, built ONCE per config so timed
    iterations hit jax's jit cache (a fresh closure per call would recompile
    every dispatch and the 'uncached' baseline would measure compilation)."""
    from bpe_transformer_tpu.models.decode import _sample_from_logits
    from bpe_transformer_tpu.models.transformer import forward

    @jax.jit
    def step(buf, length, key):
        logits = forward(params, buf, config)[:, length - 1]
        key, sub = jax.random.split(key)
        nxt = _sample_from_logits(logits, sub, 1.0, None, None)
        buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, length))
        return buf, nxt, key

    return step


def _uncached_generate(step, config, prompt, key, max_new_tokens):
    """Full forward over the whole context buffer per emitted token — the
    sliding-window fallback of training/sampling.py, batched, timed as the
    baseline the KV cache is supposed to beat."""
    batch, plen = prompt.shape
    ctx = config.context_length
    buf = jnp.zeros((batch, ctx), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    length = plen
    last = None
    for _ in range(max_new_tokens):
        buf, last, key = step(buf, jnp.asarray(length), key)
        length += 1
    return last


def _time(fn, *args, iters: int, label: str):
    try:
        t0 = time.perf_counter()
        out = fn(*args)  # compile + first run
        jax.block_until_ready(out)
        float(jax.device_get(jnp.asarray(out).reshape(-1)[0]))  # hard barrier
        print(
            f"{label}: compiled+first-run in {time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
        )
        start = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        float(jax.device_get(jnp.asarray(out).reshape(-1)[0]))
        return (time.perf_counter() - start) / iters
    except Exception as exc:  # noqa: BLE001 - report the case as absent
        print(f"{label} failed: {exc!r}"[:300], file=sys.stderr)
        return None




def main() -> int:
    require_tpu(Path(__file__).stem)
    enable_compile_cache()
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", choices=sorted(CONFIGS), default=None)
    parser.add_argument("--batch", type=int, default=None)
    args = parser.parse_args()

    import dataclasses

    import bpe_transformer_tpu.models as models
    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.models.decode import generate_cached

    on_accel = jax.default_backend() != "cpu"
    # BENCH_DECODE_NEW_TOKENS caps the generation length (the gpt2-scale
    # cells timed out at 128: scan-program remote compile + 128 sequential
    # uncached forwards).  NOTE the cached tok/s amortizes the fixed
    # prefill over the generated tokens, so rows at different lengths are
    # not directly comparable — every row records prompt=/new= for that.
    raw_new = os.environ.get(
        "BENCH_DECODE_NEW_TOKENS", "128" if on_accel else "16"
    )
    try:
        new_tokens = int(raw_new)
    except ValueError:
        print(f"invalid BENCH_DECODE_NEW_TOKENS={raw_new!r}", file=sys.stderr)
        return 2
    if new_tokens <= 0:
        print(f"BENCH_DECODE_NEW_TOKENS must be positive, got {raw_new}", file=sys.stderr)
        return 2
    # BENCH_DECODE_ATTN=pallas times the flash-decoding kernel
    # (kernels/pallas/decode_attention.py) on the cached path; rows carry a
    # dec= tag so the two formulations land as distinct evidence.
    decode_attn = os.environ.get("BENCH_DECODE_ATTN", "xla")
    if decode_attn not in ("xla", "pallas"):
        print(f"invalid BENCH_DECODE_ATTN={decode_attn!r}", file=sys.stderr)
        return 2
    # BENCH_DECODE_SKIP_UNCACHED=1: variant cells (e.g. the pallas rows)
    # only need the cached timing — re-running the minutes-long uncached
    # baseline the base cell already measured would burn chip time.
    skip_uncached = os.environ.get("BENCH_DECODE_SKIP_UNCACHED") == "1"
    iters = 3 if on_accel else 1

    names = [args.config] if args.config else sorted(CONFIGS)
    batches = [args.batch] if args.batch else [1, 8]
    measured_any = False
    for name in names:
        # Each preset keeps its own activation dtype (gpt2 presets are bf16:
        # bf16 KV cache + einsums on the cached path, bf16 forward on the
        # uncached baseline — same dtype both sides, so the comparison stays
        # algorithmic).
        config = dataclasses.replace(
            getattr(models, CONFIGS[name]),
            attention_impl="xla",
            decode_attention_impl=decode_attn,
        )
        params = init_params(jax.random.PRNGKey(0), config)
        rng = np.random.default_rng(0)
        for batch in batches:
            prompt = jnp.asarray(
                rng.integers(0, config.vocab_size, size=(batch, PROMPT_LEN)),
                dtype=jnp.int32,
            )
            key = jax.random.PRNGKey(1)

            t_cached = _time(
                lambda: generate_cached(
                    params, prompt, key, config=config,
                    max_new_tokens=new_tokens,
                ),
                iters=iters,
                label=f"cached {name} B={batch}",
            )
            if skip_uncached:
                t_uncached = None
            else:
                uncached_step = make_uncached_step(params, config)
                t_uncached = _time(
                    lambda: _uncached_generate(
                        uncached_step, config, prompt, key, new_tokens
                    ),
                    iters=iters,
                    label=f"uncached {name} B={batch}",
                )

            if t_cached or t_uncached:
                measured_any = True

            def tps(t):
                return round(batch * new_tokens / t, 1) if t else None

            print(
                json.dumps(
                    {
                        "metric": f"decode_tokens_per_sec ({name}, B={batch}, "
                        f"prompt={PROMPT_LEN}, new={new_tokens}, "
                        f"{config.activation_dtype})"
                        + (f" dec={decode_attn}" if decode_attn != "xla" else ""),
                        "kv_cached_tok_per_s": tps(t_cached),
                        "uncached_tok_per_s": tps(t_uncached),
                        "speedup": (
                            round(t_uncached / t_cached, 2)
                            if t_cached and t_uncached
                            else None
                        ),
                        "device": str(jax.devices()[0]),
                        "platform": jax.devices()[0].platform,
                    }
                ),
                flush=True,
            )
    # All timings failed -> nonzero so queue runners retry instead of
    # committing an all-null row and marking the cell done.
    return 0 if measured_any else 4


if __name__ == "__main__":
    sys.exit(main())
