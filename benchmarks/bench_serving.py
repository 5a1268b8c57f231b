"""Serving-engine throughput/latency: closed-loop concurrency sweeps and
an open-loop (target-QPS) load generator.

Two modes, one JSON row per cell:

* **closed loop** (default): for each ``--concurrency`` level the engine
  serves a fixed request load (ragged prompt lengths, shared token
  budget) and reports aggregate generated tokens/sec plus p50/p95/p99
  request latency — the tradeoff curve capacity planning reads.
* **open loop** (``--qps F``): requests arrive on a Poisson schedule at
  the target rate regardless of completions — the arrival process real
  traffic has — with an optional shared system prefix
  (``--shared-prefix-len N`` tokens on ``--shared-prefix-frac`` of
  requests).  Rows carry p50/p95/p99 end-to-end latency, achieved QPS,
  and — for the paged engine — the prefix-cache hit rate and prefill
  compute seconds, so the paged-vs-dense comparison ("prefix sharing
  buys X% of prefill back") is one jax-free diff of two rows.

``--paged`` switches the engine to the block-pool KV cache
(`serving/kvpool/`): radix prefix sharing + chunked prefill
(``--prefill-chunk``/``--prefill-budget``); ``--decode-attention paged``
runs the block-pool-NATIVE flash-decode kernel (no per-tick gather
transient) and ``--kv-dtype int8`` the quantized pool — rows carry
``kv_pool_bytes``/``kv_bytes_per_token`` so the memory-traffic claims
are machine-checkable.  Warmup (compilation of the configured ladder +
tick) happens before timing in both modes, so cells measure steady-state
serving, not XLA.

A third mode, ``--restart``, times restart-to-traffic (ROADMAP item 5):
a serve replica from process spawn to first token THROUGH the router's
rejoin path, cold versus ``bpe-tpu warmup``-warmed compile cache — one
JSON row with ``cold_s``/``warm_s``/``warmup_s``.

One process per chip: the in-process modes import jax in THIS process and
start no child; the subprocess modes (``--restart``, ``--controller*``)
keep this process jax-free — the random-weight checkpoint is written by a
short CPU child (`_write_random_checkpoint`) and the ``bpe-tpu serve``
children own the chip.  Compile caches follow the one rule in
``utils/compile_cache.py`` (no per-run temporary cache directory).

Run on a TPU host:  python benchmarks/bench_serving.py [--qps 8 --paged]
Prints one JSON line per cell.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

import numpy as np  # noqa: E402

CONFIGS = {
    "tinystories-4l": "TINYSTORIES_4L",
    "gpt2-small-32k": "GPT2_SMALL_32K",
}


def _pctl(values, q):
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, max(0, round(q * len(ordered)) - 1))]


def _make_engine(params, config, *, concurrency, n_requests, args):
    from bpe_transformer_tpu.serving import ServingEngine

    draft_spec = None
    if args.speculate:
        from bpe_transformer_tpu.serving import DraftSpec

        draft_spec = DraftSpec(truncate_layers=args.draft_layers)
    return ServingEngine(
        params, config, slots=concurrency, max_queue=n_requests + 1,
        paged=args.paged, block_size=args.block_size,
        prefill_chunk=args.prefill_chunk,
        prefill_token_budget=args.prefill_budget,
        kv_dtype=None if args.kv_dtype == "act" else args.kv_dtype,
        weight_dtype=(
            None if args.weight_dtype == "act" else args.weight_dtype
        ),
        fused_sampling=args.fused_sampling,
        speculate_k=args.speculate, draft_spec=draft_spec,
    )


def _warmup(serving, config):
    """One request per distinct bucket + the tick program, so timed cells
    measure steady-state serving rather than XLA.  Prompts are DISTINCT
    per bucket: identical ones would share a radix-cache prefix on the
    paged engine, shrinking later rungs' chunks into already-compiled
    programs and leaving their cold compile inside the timed cell.
    Returns a post-warmup stats snapshot so row fields can be reported as
    deltas (warmup traffic must not pollute hit-rate/compute evidence)."""
    ctx = config.context_length
    vocab = config.vocab_size
    for b in serving.engine.buckets:
        plen = min(b, ctx - 2)
        serving.generate([(17 * b + i) % vocab for i in range(plen)],
                         max_new_tokens=2, temperature=0.0, timeout=600)
    return serving.stats()


def _parse_prompt_mix(spec: str) -> tuple[int, int, float]:
    """``--prompt-mix SHORT,LONG,LONG_FRAC`` (e.g. ``12,160,0.25``):
    bimodal prompt lengths — the disaggregated-serving workload, where a
    minority of long prompts is exactly what blows a monolithic
    replica's decode p99."""
    parts = spec.split(",")
    if len(parts) != 3:
        raise ValueError(
            f"--prompt-mix wants SHORT,LONG,LONG_FRAC, got {spec!r}"
        )
    short, long_, frac = int(parts[0]), int(parts[1]), float(parts[2])
    if short < 1 or long_ <= short or not 0.0 < frac < 1.0:
        raise ValueError(
            f"--prompt-mix needs 1 <= SHORT < LONG and 0 < LONG_FRAC < 1, "
            f"got {spec!r}"
        )
    return short, long_, frac


def _prompts_mix(rng, config, *, n_requests, new_tokens, short, long_, frac):
    """Bimodal prompts: ``frac`` of requests at ~``long_`` tokens, the
    rest at ~``short`` (±25% jitter so bucket ladders stay honest).
    Returns ``(prompts, is_long flags)``."""
    ctx = config.context_length
    vocab = config.vocab_size
    cap = max(ctx - new_tokens - 1, 2)
    prompts, is_long = [], []
    for _ in range(n_requests):
        lng = rng.random() < frac
        base = long_ if lng else short
        n = int(rng.integers(max(1, (3 * base) // 4), (5 * base) // 4 + 1))
        prompts.append(
            [int(t) for t in rng.integers(0, vocab, size=min(n, cap))]
        )
        is_long.append(lng)
    return prompts, is_long


def _bucket_fields(results, is_long) -> dict:
    """Per-bucket (short/long) and overall request + decode latency
    percentiles — the row evidence `serve_open_disagg` is judged on:
    disaggregation moves SHORT-bucket decode p99, which a monolithic mix
    lets long prefills stall."""
    out: dict = {}
    lat = [r.queue_wait_s + r.prefill_s + r.decode_s for r in results]
    dec = [r.decode_s for r in results]
    out["decode_p50_s"] = round(_pctl(dec, 0.50), 4)
    out["decode_p95_s"] = round(_pctl(dec, 0.95), 4)
    out["decode_p99_s"] = round(_pctl(dec, 0.99), 4)
    for label, flag in (("short", False), ("long", True)):
        sel = [i for i, lng in enumerate(is_long) if lng is flag]
        if not sel:
            continue
        for name, values in (
            ("latency", [lat[i] for i in sel]),
            ("decode", [dec[i] for i in sel]),
        ):
            for q, tag in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
                out[f"{label}_{name}_{tag}_s"] = round(
                    _pctl(values, q), 4
                )
        out[f"{label}_requests"] = len(sel)
    return out


def _prompts(rng, config, *, n_requests, new_tokens,
             shared_prefix_len=0, shared_prefix_frac=0.0):
    """Ragged prompts biased short (serving-shaped); a ``shared_prefix_len``
    system prefix rides the first ``shared_prefix_frac`` fraction of them
    (same tokens every time — the prefix-cache target)."""
    ctx = config.context_length
    vocab = config.vocab_size
    max_suffix = max(min(ctx - new_tokens - shared_prefix_len, 4 * 64), 9)
    prefix = [int(t) for t in rng.integers(0, vocab, size=shared_prefix_len)]
    prompts = []
    for i in range(n_requests):
        n = int(rng.integers(8, max_suffix))
        suffix = [int(t) for t in rng.integers(0, vocab, size=n)]
        if shared_prefix_len and i < shared_prefix_frac * n_requests:
            prompts.append(prefix + suffix)
        else:
            prompts.append(suffix)
    return prompts


def _prefill_compute_s(stats):
    return sum(
        work["seconds"]
        for work in stats.get("prefill_bucket_work", {}).values()
    )


def _paged_row_fields(serving, baseline):
    """Prefix-cache and prefill-compute evidence as DELTAS against the
    post-warmup ``baseline`` snapshot (warmup traffic excluded) —
    None-filled for the dense engine so rows stay diffable."""
    stats = serving.stats()
    hits = misses = rate = None
    if stats.get("prefix_cache_hits") is not None:
        hits = stats["prefix_cache_hits"] - baseline.get(
            "prefix_cache_hits", 0
        )
        misses = stats["prefix_cache_misses"] - baseline.get(
            "prefix_cache_misses", 0
        )
        rate = round(hits / (hits + misses), 6) if hits + misses else None
    out = {
        "engine": stats.get("engine_kind", "dense"),
        "prefill_compute_s": round(
            _prefill_compute_s(stats) - _prefill_compute_s(baseline), 4
        ),
        "prefix_hits": hits,
        "prefix_hit_rate": rate,
        "kv_blocks_free_end": stats.get("kv_blocks_free"),
        # KV-memory economics (ISSUE 9): the int8 win and the paged-native
        # kernel's traffic cut are judged against these row fields.
        "kv_dtype": stats.get("kv_dtype"),
        "kv_pool_bytes": stats.get("kv_pool_bytes"),
        "kv_bytes_per_token": stats.get("kv_bytes_per_token"),
        # Weight-quantization + fused-sampling evidence (ISSUE 11): the
        # per-tick weight sweep (int8 halves it vs bf16), the storage
        # width label, whether the tick tail ran fused, and the analytic
        # roofline's intensity/floor — machine-checkable next to the
        # compiled-program count the bounded-compile claim pins.
        "weight_dtype": stats.get("weight_dtype"),
        "params_bytes": stats.get("params_bytes"),
        "tick_weight_bytes": stats.get("tick_weight_bytes"),
        "fused_sampling": stats.get("fused_sampling"),
        "tick_arithmetic_intensity": (
            (stats.get("decode_roofline") or {}).get("arithmetic_intensity")
        ),
        "tick_projected_s": (
            (stats.get("decode_roofline") or {}).get("projected_tick_s")
        ),
        "decode_p95_s": stats["phase_p95_s"]["decode"],
    }
    if stats.get("spec_k") is not None:
        # Speculative-decoding evidence (ISSUE 10), warmup excluded: the
        # acceptance rate of the timed traffic, tokens emitted per target
        # verify pass (1.0 = non-speculative, k+1 = ceiling), and the
        # draft's share of spec-tick wall — the overhead acceptance pays.
        proposed = stats["spec_proposed_tokens"] - baseline.get(
            "spec_proposed_tokens", 0
        )
        accepted = stats["spec_accepted_tokens"] - baseline.get(
            "spec_accepted_tokens", 0
        )
        steps = stats["spec_target_steps"] - baseline.get(
            "spec_target_steps", 0
        )
        emitted = stats["spec_emitted_tokens"] - baseline.get(
            "spec_emitted_tokens", 0
        )
        draft_s = stats["spec_draft_time_s"] - baseline.get(
            "spec_draft_time_s", 0.0
        )
        tick_s = stats["spec_tick_time_s"] - baseline.get(
            "spec_tick_time_s", 0.0
        )
        out.update({
            "speculate_k": stats["spec_k"],
            "accept_rate": (
                round(accepted / proposed, 6) if proposed else None
            ),
            "tokens_per_target_step": (
                round(emitted / steps, 6) if steps else None
            ),
            "draft_overhead_frac": (
                round(draft_s / tick_s, 6) if tick_s > 0 else None
            ),
            "rewound_tokens": stats["spec_rewound_tokens"] - baseline.get(
                "spec_rewound_tokens", 0
            ),
        })
    return out


def run_cell(params, config, *, concurrency, n_requests, new_tokens, args,
             seed=0):
    """Closed loop: submit everything up front, the scheduler feeds slots."""
    from bpe_transformer_tpu.serving import Request

    rng = np.random.default_rng(seed)
    prompts = _prompts(
        rng, config, n_requests=n_requests, new_tokens=new_tokens,
        shared_prefix_len=args.shared_prefix_len,
        shared_prefix_frac=args.shared_prefix_frac,
    )

    with _make_engine(
        params, config, concurrency=concurrency, n_requests=n_requests,
        args=args,
    ) as serving:
        baseline = _warmup(serving, config)
        t0 = time.perf_counter()
        handles = [
            serving.submit(
                Request(
                    prompt_ids=tuple(p), max_new_tokens=new_tokens,
                    temperature=1.0, top_k=50, seed=i,
                )
            )
            for i, p in enumerate(prompts)
        ]
        results = [h.result(timeout=1800) for h in handles]
        wall = time.perf_counter() - t0
        latencies = [
            r.queue_wait_s + r.prefill_s + r.decode_s for r in results
        ]
        tokens = sum(len(r.token_ids) for r in results)
        compiled = serving.engine.compiled_programs()
        extra = _paged_row_fields(serving, baseline)

    return {
        "wall_s": round(wall, 3),
        "gen_tok_per_s": round(tokens / wall, 1),
        "latency_p50_s": round(_pctl(latencies, 0.50), 4),
        "latency_p95_s": round(_pctl(latencies, 0.95), 4),
        "latency_p99_s": round(_pctl(latencies, 0.99), 4),
        "compiled_programs": compiled,
        "requests": n_requests,
        "new_tokens": new_tokens,
        **extra,
    }


def run_open_loop(params, config, *, concurrency, n_requests, new_tokens,
                  qps, args, seed=0):
    """Open loop: Poisson arrivals at the target QPS — submissions never
    wait for completions, so queueing delay is measured, not hidden."""
    from bpe_transformer_tpu.serving import Request

    rng = np.random.default_rng(seed)
    prompts = _prompts(
        rng, config, n_requests=n_requests, new_tokens=new_tokens,
        shared_prefix_len=args.shared_prefix_len,
        shared_prefix_frac=args.shared_prefix_frac,
    )
    # The shared-prefix requests are interleaved with the rest (real mixes
    # are), not front-loaded: shuffle the submission order.
    order = rng.permutation(n_requests)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=n_requests))

    with _make_engine(
        params, config, concurrency=concurrency, n_requests=n_requests,
        args=args,
    ) as serving:
        baseline = _warmup(serving, config)
        t0 = time.perf_counter()
        handles = []
        for arrival, idx in zip(arrivals, order):
            now = time.perf_counter() - t0
            if now < arrival:
                time.sleep(arrival - now)
            handles.append(
                serving.submit(
                    Request(
                        prompt_ids=tuple(prompts[int(idx)]),
                        max_new_tokens=new_tokens,
                        temperature=1.0, top_k=50, seed=int(idx),
                    )
                )
            )
        results = [h.result(timeout=1800) for h in handles]
        wall = time.perf_counter() - t0
        latencies = [
            r.queue_wait_s + r.prefill_s + r.decode_s for r in results
        ]
        tokens = sum(len(r.token_ids) for r in results)
        compiled = serving.engine.compiled_programs()
        extra = _paged_row_fields(serving, baseline)

    return {
        "wall_s": round(wall, 3),
        "qps_target": qps,
        "qps_achieved": round(n_requests / wall, 3),
        "gen_tok_per_s": round(tokens / wall, 1),
        "latency_p50_s": round(_pctl(latencies, 0.50), 4),
        "latency_p95_s": round(_pctl(latencies, 0.95), 4),
        "latency_p99_s": round(_pctl(latencies, 0.99), 4),
        "compiled_programs": compiled,
        "requests": n_requests,
        "new_tokens": new_tokens,
        "shared_prefix_len": args.shared_prefix_len,
        "shared_prefix_frac": args.shared_prefix_frac,
        **extra,
    }


def run_open_fleet(params, config, *, concurrency, n_requests, new_tokens,
                   qps, args, seed=0):
    """Open-loop Poisson arrivals against a TWO-ENGINE in-process fleet
    (equal engine count either way — the CPU smoke's stand-in for equal
    chips):

    * **monolithic** (default, ``--replicas 2``): requests round-robin
      across N ``role="both"`` engines — every replica's decode ticks
      share a worker loop with long-prompt prefills;
    * **disaggregated** (``--disagg``): one prefill-role engine + one
      decode-role engine wired through the real KV migration path —
      long prompts (>= ``--prefill-threshold``) prefill on the prefill
      engine, export as payload bytes, and graft onto the decode engine
      (`submit_import`); short prompts bypass straight to the decode
      engine.  The decode engine's ticks never wait behind a long
      prefill, which is the whole point: compare ``decode_p99_s`` (and
      the ``short_*`` bucket fields) across the two rows.

    ``--prompt-mix`` supplies the bimodal lengths; rows carry per-bucket
    p50/p95/p99 latency + decode fields.
    """
    import threading

    from bpe_transformer_tpu.serving import Request, ServingEngine

    short, long_, frac = _parse_prompt_mix(args.prompt_mix)
    threshold = args.prefill_threshold or (short + long_) // 2
    rng = np.random.default_rng(seed)
    prompts, is_long = _prompts_mix(
        rng, config, n_requests=n_requests, new_tokens=new_tokens,
        short=short, long_=long_, frac=frac,
    )
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=n_requests))

    def make(role):
        return ServingEngine(
            params, config, slots=concurrency, max_queue=n_requests + 1,
            paged=True, block_size=args.block_size,
            prefill_chunk=args.prefill_chunk,
            prefill_token_budget=args.prefill_budget,
            kv_dtype=None if args.kv_dtype == "act" else args.kv_dtype,
            weight_dtype=(
                None if args.weight_dtype == "act" else args.weight_dtype
            ),
            fused_sampling=args.fused_sampling,
            role=role,
        )

    if args.disagg:
        engines = [make("prefill"), make("decode")]
    else:
        engines = [make("both") for _ in range(args.replicas)]
    for engine in engines:
        engine.start()
    try:
        # Warm every engine's ladder so timed cells measure steady state
        # (the decode engine warms tick+import through a real migration).
        ctx = config.context_length
        vocab = config.vocab_size
        if args.disagg:
            pre, dec = engines
            for b in pre.engine.buckets:
                plen = min(b, ctx - new_tokens - 1)
                r = pre.generate(
                    [(13 * b + i) % vocab for i in range(plen)],
                    max_new_tokens=2, temperature=0.0, migrate=True,
                    timeout=600,
                )
                if r.kv_payload is not None:
                    dec.submit_import(r.kv_payload).result(timeout=600)
            for b in dec.engine.buckets:  # short prompts prefill here
                plen = min(b, ctx - new_tokens - 1)
                dec.generate(
                    [(29 * b + i) % vocab for i in range(plen)],
                    max_new_tokens=2, temperature=0.0, timeout=600,
                )
        else:
            for engine in engines:
                for b in engine.engine.buckets:
                    plen = min(b, ctx - new_tokens - 1)
                    engine.generate(
                        [(17 * b + i) % vocab for i in range(plen)],
                        max_new_tokens=2, temperature=0.0, timeout=600,
                    )

        results: list = [None] * n_requests
        errors: list = []

        def serve_one(i: int, t0: float):
            delay = arrivals[i] - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            req = dict(
                max_new_tokens=new_tokens, temperature=1.0, top_k=50,
                seed=i,
            )
            try:
                if args.disagg:
                    pre, dec = engines
                    if len(prompts[i]) >= threshold:
                        r = pre.generate(
                            prompts[i], migrate=True, timeout=1800, **req
                        )
                        if r.finish_reason == "migrated":
                            r = dec.submit_import(r.kv_payload).result(
                                timeout=1800
                            )
                    else:
                        r = dec.generate(prompts[i], timeout=1800, **req)
                else:
                    r = engines[i % len(engines)].generate(
                        prompts[i], timeout=1800, **req
                    )
                results[i] = r
            except Exception as exc:  # noqa: BLE001 — the row reports it
                errors.append(repr(exc))

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=serve_one, args=(i, t0), daemon=True)
            for i in range(n_requests)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=1900)
        wall = time.perf_counter() - t0
        done = [r for r in results if r is not None]
        flags = [f for r, f in zip(results, is_long) if r is not None]
        tokens = sum(len(r.token_ids) for r in done)
        lat = [r.queue_wait_s + r.prefill_s + r.decode_s for r in done]
        dec_stats = engines[-1].stats()
        migrations = sum(e.stats()["migrations_in"] for e in engines)
    finally:
        for engine in engines:
            engine.close()

    return {
        "wall_s": round(wall, 3),
        "qps_target": qps,
        "qps_achieved": round(len(done) / wall, 3) if wall else None,
        "gen_tok_per_s": round(tokens / wall, 1),
        "latency_p50_s": round(_pctl(lat, 0.50), 4),
        "latency_p95_s": round(_pctl(lat, 0.95), 4),
        "latency_p99_s": round(_pctl(lat, 0.99), 4),
        **_bucket_fields(done, flags),
        "requests": n_requests,
        "completed": len(done),
        "failed": n_requests - len(done),
        "new_tokens": new_tokens,
        "prompt_mix": args.prompt_mix,
        "prefill_threshold": threshold if args.disagg else None,
        "migrations": migrations,
        "engines": len(engines),
        "decode_compiled_programs": dec_stats["compiled_programs"],
    }


def _serve_flags(args) -> list:
    """The engine knobs forwarded to a `bpe-tpu serve` / `bpe-tpu warmup`
    subprocess (restart bench), mirroring what _make_engine builds
    in-process."""
    flags = []
    if args.paged:
        flags += ["--paged", "--block-size", str(args.block_size)]
        if args.prefill_chunk:
            flags += ["--prefill-chunk", str(args.prefill_chunk)]
        if args.kv_dtype != "act":
            flags += ["--kv-dtype", args.kv_dtype]
    if args.decode_attention:
        flags += ["--decode-attention", args.decode_attention]
    if args.weight_dtype != "act":
        flags += ["--weight-dtype", args.weight_dtype]
    if args.fused_sampling:
        flags += ["--fused-sampling"]
    return flags


#: Run by a short child on the CPU backend: the parent of the subprocess
#: modes never imports jax (a parent that touched it would hold the chip
#: its `bpe-tpu serve` children need).
_CHECKPOINT_CHILD = """
import dataclasses, sys
import jax
import bpe_transformer_tpu.models as models
from bpe_transformer_tpu.checkpointing import save_checkpoint
config = getattr(models, sys.argv[2])
save_checkpoint(
    sys.argv[1],
    params=models.init_params(jax.random.PRNGKey(0), config),
    extra={"model_config": dataclasses.asdict(config)},
)
"""


def _write_random_checkpoint(workdir: Path, config_attr: str):
    """Random-weight checkpoint + byte-level tokenizer files under
    ``workdir`` for the subprocess modes; returns ``(ckpt, tok_dir)``."""
    import os
    import pickle
    import subprocess

    ckpt = workdir / "model.ckpt"
    subprocess.run(
        [sys.executable, "-c", _CHECKPOINT_CHILD, str(ckpt), config_attr],
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(REPO_ROOT)},
        check=True, timeout=600,
    )
    tok_dir = workdir / "tok"
    tok_dir.mkdir()
    with open(tok_dir / "vocab.pkl", "wb") as f:
        pickle.dump({i: bytes([i]) for i in range(256)}, f)
    with open(tok_dir / "merges.pkl", "wb") as f:
        pickle.dump([], f)
    return ckpt, tok_dir


def run_restart(args) -> dict:
    """Restart-to-traffic (ROADMAP item 5): time a replica from SPAWN to
    first token served THROUGH the router's rejoin path, cold (empty
    compile cache) vs `bpe-tpu warmup`-warmed — the rolling-deploy number
    a fleet operator actually waits on.  This process stays jax-free (a
    parent that touched jax would hold the chip the child serve needs);
    the router is the in-process jax-free `serving.router.Router` driven
    by hand.  "Cold" is a child with the persistent cache switched off
    (``JAX_ENABLE_COMPILATION_CACHE=false``); "warm" is a child that finds
    what `bpe-tpu warmup` left in the directory the one cache rule
    resolves — no temporary cache directory anywhere."""
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    from bpe_transformer_tpu.serving.router import Router

    workdir = Path(tempfile.mkdtemp(prefix="bpe_restart_"))
    procs: list = []
    try:
        ckpt, tok_dir = _write_random_checkpoint(
            workdir, CONFIGS[args.config]
        )

        import socket

        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()

        child_env = {**os.environ, "PYTHONPATH": str(REPO_ROOT)}
        cold_env = {**child_env, "JAX_ENABLE_COMPILATION_CACHE": "false"}

        base_cmd = [
            sys.executable, "-m", "bpe_transformer_tpu.training.cli",
            "serve",
            "--checkpoint", str(ckpt),
            "--tokenizer-dir", str(tok_dir),
            "--port", str(port),
            "--slots", "2",
            "--max-new-tokens", "4",
        ] + _serve_flags(args)

        def spawn(env):
            proc = subprocess.Popen(
                base_cmd, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL, env=env,
            )
            procs.append(proc)
            return proc

        def time_to_first_token(env, timeout_s=900.0):
            """Spawn the replica and drive the router by hand until a
            generate lands: the router marks the (absent) replica down,
            sees it rejoin via /statusz polls, and the first 200 is
            first-token time — exactly a rolling restart's window."""
            router = Router(
                [f"http://127.0.0.1:{port}"],
                poll_timeout_s=2.0, connect_timeout_s=2.0,
                request_timeout_s=600.0,
            )
            body = json.dumps(
                {"prompt_ids": [5, 6, 7, 8, 9, 10, 11],
                 "max_new_tokens": 4, "temperature": 0.0}
            ).encode()
            t0 = time.perf_counter()
            proc = spawn(env)
            deadline = t0 + timeout_s
            while time.perf_counter() < deadline:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"replica exited rc={proc.returncode} before "
                        "serving"
                    )
                router.poll_once()
                if any(r.available for r in router.replicas):
                    code, _payload = router.handle_generate(body)
                    if code == 200:
                        return time.perf_counter() - t0, proc
                time.sleep(0.2)
            raise RuntimeError(f"no first token within {timeout_s}s")

        def stop(proc):
            proc.send_signal(signal.SIGTERM)  # serve drains gracefully
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)

        cold_s, proc = time_to_first_token(cold_env)
        stop(proc)

        t0 = time.perf_counter()
        warm_proc = subprocess.run(
            [
                sys.executable, "-m", "bpe_transformer_tpu.training.cli",
                "warmup",
                "--checkpoint", str(ckpt),
                "--slots", "2",
            ] + _serve_flags(args)
            + (["--kv-dtype", args.kv_dtype] if args.paged
               and args.kv_dtype == "act" else []),
            capture_output=True, text=True, env=child_env, timeout=1200,
        )
        warmup_s = time.perf_counter() - t0
        if warm_proc.returncode != 0:
            raise RuntimeError(f"warmup failed: {warm_proc.stderr[-500:]}")
        warm_summary = json.loads(
            warm_proc.stdout.strip().splitlines()[-1]
        )

        warm_s, proc = time_to_first_token(child_env)
        stop(proc)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        shutil.rmtree(workdir, ignore_errors=True)

    return {
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "warmup_s": round(warmup_s, 3),
        "speedup": round(cold_s / warm_s, 3) if warm_s else None,
        "programs_warmed": warm_summary.get("programs_compiled"),
        "engine": "paged" if args.paged else "dense",
        "decode_attention": args.decode_attention or "xla",
        "kv_dtype": args.kv_dtype if args.paged else None,
        "weight_dtype": args.weight_dtype,
        "fused_sampling": args.fused_sampling,
    }


#: Diurnal ramp (``--controller``/``--controller-static``): arrival-rate
#: factor on --qps and long-prompt fraction per phase — overnight lull,
#: a long-prompt-heavy peak, then a cooldown.  The shifting mix is what
#: drives the controller's threshold retune; the rate ramp is what
#: drives elastic scale-up.
RAMP_PHASES = (
    ("night", 0.5, 0.10),
    ("peak", 2.0, 0.40),
    ("cool", 1.0, 0.20),
)


def _free_port() -> int:
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


def run_controller_ramp(args) -> dict:
    """Diurnal-ramp fleet bench (ISSUE 20): the same Poisson ramp with a
    shifting prompt mix served by a real subprocess fleet — one
    always-on role=both replica, one prefill-tier replica, and one
    ELASTIC slot — either supervised by the closed-loop controller
    (``--controller``: retune + rebalance + scale-up actually fire) or
    left static (``--controller-static``: the fixed fleet the controller
    row is judged against).  ``--chaos`` additionally SIGKILLs the
    always-on replica mid-decode and blackholes its first ``/kv/import``
    (``BT_FAULTS``), so the row shows what the spawner-respawn +
    suspect-probe + retry-with-idempotency-key stack recovers.

    The parent stays jax-free on CPU (router, aggregator, and controller
    are all pure-stdlib); replicas own the chip.  One JSON row."""
    import shutil
    import tempfile
    import threading

    from bpe_transformer_tpu.models import config as model_configs
    from bpe_transformer_tpu.serving.controller import (
        FleetController,
        ReplicaSpawner,
    )
    from bpe_transformer_tpu.serving.router import (
        Router,
        make_router_http_server,
    )
    from bpe_transformer_tpu.telemetry.fleet import (
        FleetAggregator,
        make_fleet_http_server,
    )

    managed = bool(args.controller)
    config = getattr(model_configs, CONFIGS[args.config])
    new_tokens = min(args.new_tokens, 16)
    n_requests = args.requests or 48
    base_qps = args.qps or 4.0
    if args.prompt_mix:
        short, long_, _ = _parse_prompt_mix(args.prompt_mix)
    else:
        short, long_ = 12, 160
    initial_threshold = args.prefill_threshold or 96

    workdir = Path(tempfile.mkdtemp(prefix="bpe_ramp_"))
    servers: list = []
    spawner = None
    router = None
    fleet = None
    stop = threading.Event()
    try:
        ckpt, tok_dir = _write_random_checkpoint(
            workdir, CONFIGS[args.config]
        )
        env_prefix = ["env", f"PYTHONPATH={REPO_ROOT}"]

        def serve_argv(port, role, extra_env=(), extra=()):
            return (
                env_prefix + list(extra_env) + [
                    sys.executable, "-m",
                    "bpe_transformer_tpu.training.cli", "serve",
                    "--checkpoint", str(ckpt),
                    "--tokenizer-dir", str(tok_dir),
                    "--port", str(port),
                    "--slots", "4",
                    "--max-new-tokens", str(new_tokens),
                    "--paged", "--block-size", str(args.block_size),
                    "--role", role,
                ] + list(extra)
            )

        port_a, port_p, port_e = _free_port(), _free_port(), _free_port()
        url_a = f"http://127.0.0.1:{port_a}"
        url_p = f"http://127.0.0.1:{port_p}"
        url_e = f"http://127.0.0.1:{port_e}"

        chaos_env = ()
        if args.chaos:
            fault_dir = workdir / "faults_a"
            fault_dir.mkdir()
            # Fires once each (once_dir survives the respawn): the
            # always-on replica dies mid-decode and swallows its first
            # /kv/import; the spawner respawns it, the router probes it
            # back in, and the relay's idempotency-keyed retry lands.
            chaos_env = ("BT_FAULTS=" + json.dumps({
                "kill_at_decode_tick": 24,
                "http_blackhole": True,
                "http_fault_path": "/kv/import",
                "once_dir": str(fault_dir),
            }),)

        spawner = ReplicaSpawner([
            (url_a, serve_argv(port_a, "both", extra_env=chaos_env)),
            (url_p, serve_argv(
                port_p, "prefill", extra=("--evacuate-to", url_a),
            )),
            (url_e, serve_argv(
                port_e, "both", extra=("--evacuate-to", url_a),
            )),
        ])
        spawner.spawn()  # always-on decode-capable replica
        spawner.spawn()  # prefill tier; third slot stays elastic

        router = Router(
            [url_a, url_p, url_e],
            poll_interval_s=0.5, poll_timeout_s=2.0,
            connect_timeout_s=2.0, request_timeout_s=600.0,
            prefill_threshold=initial_threshold, suspect_after=3,
            probe_backoff_s=0.5, probe_backoff_max_s=4.0,
        )
        router.start()
        router_port = _free_port()
        router_httpd = make_router_http_server(
            router, port=router_port
        )
        servers.append(router_httpd)
        fleet = FleetAggregator(
            [url_a, url_p, url_e],
            router_url=f"http://127.0.0.1:{router_port}",
            poll_interval_s=1.0, poll_timeout_s=2.0,
        )
        fleet_port = _free_port()
        fleet_httpd = make_fleet_http_server(fleet, port=fleet_port)
        servers.append(fleet_httpd)
        for httpd in servers:
            threading.Thread(
                target=httpd.serve_forever, daemon=True
            ).start()

        controller = None
        if managed:
            controller = FleetController(
                f"http://127.0.0.1:{fleet_port}",
                router_url=f"http://127.0.0.1:{router_port}",
                spawner=spawner,
                poll_timeout_s=2.0, evidence_max_age_s=15.0,
                cooldown_s=10.0, action_timeout_s=120.0,
                scale_sustain_s=4.0, scale_down_idle_s=1e9,
                retune_min_samples=12, rebalance_min_gap=4,
            )

            def ctl_loop():
                while not stop.is_set():
                    try:
                        controller.run_once()
                    except Exception:  # noqa: BLE001 — keep ticking
                        pass
                    stop.wait(1.0)

            threading.Thread(target=ctl_loop, daemon=True).start()

        # Wait for the two always-on replicas to come up (compile-cached
        # spawns after the first pass are fast; a cold first pass pays
        # the ladder once here, outside the timed ramp).
        deadline = time.perf_counter() + 1200
        while time.perf_counter() < deadline:
            router.poll_once()
            if sum(r.available for r in router.replicas) >= 2:
                break
            time.sleep(1.0)
        else:
            raise RuntimeError("always-on replicas never came up")
        fleet.start()

        # Build the ramp: per-phase Poisson arrivals on a shared clock,
        # each request tagged with its phase.
        rng = np.random.default_rng(0)
        per_phase = max(n_requests // len(RAMP_PHASES), 1)
        schedule = []  # (arrival_s, phase_idx, prompt)
        t_cursor = 0.0
        for idx, (_, qps_factor, long_frac) in enumerate(RAMP_PHASES):
            prompts, _flags = _prompts_mix(
                rng, config, n_requests=per_phase,
                new_tokens=new_tokens, short=short, long_=long_,
                frac=long_frac,
            )
            gaps = rng.exponential(
                1.0 / (base_qps * qps_factor), size=per_phase
            )
            for prompt, gap in zip(prompts, gaps):
                t_cursor += float(gap)
                schedule.append((t_cursor, idx, prompt))

        lat: list = [None] * len(schedule)
        codes: list = [None] * len(schedule)

        def serve_one(i, t0):
            arrival, _, prompt = schedule[i]
            delay = arrival - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            body = json.dumps({
                "prompt_ids": prompt, "max_new_tokens": new_tokens,
                "temperature": 1.0, "top_k": 50, "seed": i,
            }).encode()
            t_s = time.perf_counter()
            try:
                code, _payload = router.handle_generate(body)
            except Exception:  # noqa: BLE001 — the row reports it
                code = 599
            codes[i] = code
            if code == 200:
                lat[i] = time.perf_counter() - t_s

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=serve_one, args=(i, t0), daemon=True)
            for i in range(len(schedule))
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=1900)
        wall = time.perf_counter() - t0

        phases_out = []
        for idx, (name, qps_factor, long_frac) in enumerate(RAMP_PHASES):
            sel = [i for i, (_, p, _pr) in enumerate(schedule) if p == idx]
            ok = [lat[i] for i in sel if lat[i] is not None]
            phases_out.append({
                "phase": name,
                "qps": round(base_qps * qps_factor, 3),
                "long_frac": long_frac,
                "requests": len(sel),
                "failed": sum(1 for i in sel if codes[i] != 200),
                "latency_p50_s": (
                    round(_pctl(ok, 0.50), 4) if ok else None
                ),
                "latency_p99_s": (
                    round(_pctl(ok, 0.99), 4) if ok else None
                ),
            })
        done = [v for v in lat if v is not None]

        router_page = router.statusz()
        ctl_fields = {}
        if controller is not None:
            stop.set()
            ctl_page = controller.statusz()
            by_action: dict = {}
            for rec in ctl_page.get("recent") or []:
                if rec.get("outcome") == "ok":
                    key = rec["action"]
                    by_action[key] = by_action.get(key, 0) + 1
            ctl_fields = {
                "controller_actions_ok": ctl_page["actions_ok"],
                "controller_actions_failed": ctl_page["actions_failed"],
                "controller_holds": ctl_page["holds"],
                "controller_breaker": ctl_page["breaker"],
                "scale_ups": by_action.get("scale_up", 0),
                "retunes": by_action.get("retune", 0),
                "rebalances": by_action.get("rebalance", 0),
            }
        row = {
            "mode": "controller" if managed else "static",
            "chaos": bool(args.chaos),
            "wall_s": round(wall, 3),
            "requests": len(schedule),
            "completed": len(done),
            "failed": len(schedule) - len(done),
            "latency_p50_s": (
                round(_pctl(done, 0.50), 4) if done else None
            ),
            "latency_p99_s": (
                round(_pctl(done, 0.99), 4) if done else None
            ),
            "phases": phases_out,
            "prefill_threshold_initial": initial_threshold,
            "prefill_threshold_final": router_page.get(
                "prefill_threshold"
            ),
            "threshold_updates": router_page.get("threshold_updates"),
            "replicas_suspected": router_page.get("suspected_total"),
            "suspect_probes": router_page.get("probes_total"),
            "suspect_recoveries": router_page.get("recoveries_total"),
            "respawns": sum(
                s["restarts"] for s in spawner.snapshot()
            ),
            **ctl_fields,
        }
    finally:
        stop.set()
        if fleet is not None:
            fleet.close()
        if router is not None:
            router.close()
        for httpd in servers:
            httpd.shutdown()
        if spawner is not None:
            spawner.stop_all(timeout_s=60.0)
        shutil.rmtree(workdir, ignore_errors=True)
    return row


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", choices=sorted(CONFIGS), default="tinystories-4l")
    parser.add_argument("--concurrency", type=int, action="append", default=None,
                        help="slot-pool sizes to sweep (repeatable)")
    parser.add_argument("--requests", type=int, default=None,
                        help="requests per cell (default 4x concurrency)")
    parser.add_argument("--new-tokens", type=int, default=64)
    parser.add_argument("--qps", type=float, default=None,
                        help="open-loop mode: Poisson arrivals at this "
                        "target rate (default: closed loop)")
    parser.add_argument("--paged", action="store_true",
                        help="paged block-pool KV engine (radix prefix "
                        "sharing + chunked prefill)")
    parser.add_argument("--block-size", type=int, default=16)
    parser.add_argument("--prefill-chunk", type=int, default=None)
    parser.add_argument("--prefill-budget", type=int, default=None)
    parser.add_argument("--shared-prefix-len", type=int, default=0,
                        help="shared system-prefix length in tokens "
                        "(the prefix-cache target workload)")
    parser.add_argument("--shared-prefix-frac", type=float, default=0.5,
                        help="fraction of requests carrying the shared "
                        "prefix (with --shared-prefix-len)")
    parser.add_argument("--kv-dtype", choices=("act", "int8"),
                        default="act",
                        help="paged KV pool storage width (int8: "
                        "quantized blocks + per-block-per-head scales)")
    parser.add_argument("--decode-attention",
                        choices=("xla", "pallas", "paged"), default=None,
                        help="decode-step attention impl ('paged': the "
                        "block-pool-native flash kernel, no gather "
                        "transient; needs --paged)")
    parser.add_argument("--weight-dtype", choices=("act", "int8"),
                        default="act",
                        help="serving weight storage width (int8: "
                        "per-channel quantized matmul weights, dequant in "
                        "registers — rows carry tick_weight_bytes / "
                        "params_bytes so the ~2x weight-stream cut is "
                        "machine-checkable)")
    parser.add_argument("--fused-sampling", action="store_true",
                        help="fuse head projection + filtering + sampling "
                        "into one Pallas kernel per tick (logits never "
                        "reach HBM)")
    parser.add_argument("--speculate", type=int, default=0, metavar="K",
                        help="speculative decoding (needs --paged): a "
                        "truncated-layer draft proposes K tokens/slot per "
                        "tick, one target verify pass judges them; rows "
                        "carry accept_rate / tokens_per_target_step / "
                        "draft_overhead_frac")
    parser.add_argument("--draft-layers", type=int, default=1,
                        help="draft = the target's first N transformer "
                        "blocks (shared weights, zero extra memory; "
                        "with --speculate)")
    parser.add_argument("--prompt-mix", default=None,
                        metavar="SHORT,LONG,FRAC",
                        help="open-loop bimodal prompt mix (needs --qps + "
                        "--paged), e.g. 12,160,0.25: 25%% of prompts at "
                        "~160 tokens, the rest at ~12 — rows carry "
                        "per-bucket (short/long) p50/p95/p99 latency AND "
                        "decode-latency fields, the disaggregation "
                        "headline evidence")
    parser.add_argument("--replicas", type=int, default=2,
                        help="(with --prompt-mix) monolithic fleet size: "
                        "N role=both engines served round-robin — the "
                        "equal-engine-count baseline --disagg is judged "
                        "against")
    parser.add_argument("--disagg", action="store_true",
                        help="(with --prompt-mix) disaggregated fleet: "
                        "one prefill-role + one decode-role engine wired "
                        "through the real KV migration path — long "
                        "prompts prefill on the prefill engine and graft "
                        "onto the decode engine, short prompts bypass; "
                        "compare decode_p99_s vs the monolithic row")
    parser.add_argument("--prefill-threshold", type=int, default=None,
                        help="(with --disagg) prompt-token threshold for "
                        "the two-tier path (default: midpoint of the "
                        "prompt mix)")
    parser.add_argument("--controller", action="store_true",
                        help="diurnal-ramp fleet mode (ISSUE 20): a "
                        "subprocess fleet (always-on + prefill-tier + "
                        "one elastic slot) under the closed-loop "
                        "controller — retune/rebalance/scale-up fire "
                        "against the shifting mix and rate ramp; one "
                        "row with per-phase p50/p99 + action counts")
    parser.add_argument("--controller-static", action="store_true",
                        help="the same diurnal ramp WITHOUT the "
                        "controller — the static-fleet baseline the "
                        "--controller row is judged against")
    parser.add_argument("--chaos", action="store_true",
                        help="(with --controller) BT_FAULTS chaos: "
                        "SIGKILL the always-on replica mid-decode and "
                        "blackhole its first /kv/import — the row shows "
                        "what respawn + suspect-probe + idempotent "
                        "retry recover")
    parser.add_argument("--restart", action="store_true",
                        help="restart-to-traffic mode: time a replica "
                        "from spawn to first token through the router "
                        "rejoin path, cold vs bpe-tpu-warmup-warmed "
                        "(one row; ignores --concurrency/--qps)")
    args = parser.parse_args()

    if args.decode_attention == "paged" and not args.paged:
        print("--decode-attention paged needs --paged", file=sys.stderr)
        return 2
    if args.kv_dtype == "int8" and not args.paged:
        print("--kv-dtype int8 needs --paged", file=sys.stderr)
        return 2
    if args.speculate and not args.paged:
        print("--speculate needs --paged", file=sys.stderr)
        return 2
    if args.disagg and not args.prompt_mix:
        print("--disagg needs --prompt-mix", file=sys.stderr)
        return 2
    if args.prompt_mix and not (args.controller or args.controller_static) \
            and (args.qps is None or not args.paged):
        print("--prompt-mix needs --qps (open loop) and --paged "
              "(KV migration lives in the block pool)", file=sys.stderr)
        return 2

    if args.chaos and not args.controller:
        print("--chaos needs --controller", file=sys.stderr)
        return 2
    if args.controller and args.controller_static:
        print("--controller and --controller-static are exclusive",
              file=sys.stderr)
        return 2
    if args.controller or args.controller_static:
        cell = run_controller_ramp(args)
        print(json.dumps(
            {
                "metric": f"controller_ramp ({args.config}, "
                f"mode={cell['mode']}"
                + (", chaos" if cell["chaos"] else "") + ")",
                **cell,
                "platform": "subprocess",
            }
        ), flush=True)
        return 0

    if args.restart:
        cell = run_restart(args)
        print(json.dumps(
            {
                "metric": f"restart_to_traffic ({args.config}, "
                f"{cell['engine']}, attn={cell['decode_attention']})",
                **cell,
                "platform": "subprocess",
            }
        ), flush=True)
        return 0

    # In-process modes from here on: THIS process owns the chip and no
    # child is started.
    import dataclasses

    import jax

    import bpe_transformer_tpu.models as models
    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.utils.chip_probe import require_tpu
    from bpe_transformer_tpu.utils.compile_cache import enable_compile_cache

    require_tpu(Path(__file__).stem)
    enable_compile_cache()
    on_accel = jax.default_backend() != "cpu"
    config = dataclasses.replace(
        getattr(models, CONFIGS[args.config]),
        attention_impl="xla",
        decode_attention_impl=args.decode_attention or "xla",
    )
    params = init_params(jax.random.PRNGKey(0), config)
    levels = args.concurrency or ([1, 4, 8] if on_accel else [1, 2])
    new_tokens = args.new_tokens if on_accel else min(args.new_tokens, 8)

    measured_any = False
    for concurrency in levels:
        n_requests = args.requests or 4 * concurrency
        try:
            if args.prompt_mix:
                cell = run_open_fleet(
                    params, config,
                    concurrency=concurrency,
                    n_requests=n_requests,
                    new_tokens=new_tokens,
                    qps=args.qps,
                    args=args,
                )
                mode = f"qps={args.qps},mix={args.prompt_mix}"
            elif args.qps is not None:
                cell = run_open_loop(
                    params, config,
                    concurrency=concurrency,
                    n_requests=n_requests,
                    new_tokens=new_tokens,
                    qps=args.qps,
                    args=args,
                )
                mode = f"qps={args.qps}"
            else:
                cell = run_cell(
                    params, config,
                    concurrency=concurrency,
                    n_requests=n_requests,
                    new_tokens=new_tokens,
                    args=args,
                )
                mode = "closed"
        except Exception as exc:  # noqa: BLE001 - report the cell as absent
            print(f"concurrency={concurrency} failed: {exc!r}"[:300],
                  file=sys.stderr)
            continue
        measured_any = True
        if args.prompt_mix:
            engine = (
                "disagg" if args.disagg else f"mono-x{args.replicas}"
            )
        else:
            engine = "paged" if args.paged else "dense"
        if args.paged and args.kv_dtype != "act":
            engine += f"-{args.kv_dtype}"
        if args.decode_attention:
            engine += f"-{args.decode_attention}"
        if args.weight_dtype != "act":
            engine += "-w8"
        if args.fused_sampling:
            engine += "-fs"
        if args.speculate:
            engine += f"-spec{args.speculate}"
        print(
            json.dumps(
                {
                    "metric": f"serving_tokens_per_sec ({args.config}, "
                    f"slots={concurrency}, req={n_requests}, "
                    f"new={new_tokens}, {engine}, {mode}, "
                    f"{config.activation_dtype})",
                    **cell,
                    "decode_attention": args.decode_attention or "xla",
                    "device": str(jax.devices()[0]),
                    "platform": jax.devices()[0].platform,
                }
            ),
            flush=True,
        )
    return 0 if measured_any else 4


if __name__ == "__main__":
    sys.exit(main())
