"""Performance attribution: XLA cost-model roofline + measured step split.

The first step toward closing an MFU gap is knowing *where the time goes*
before touching any code.  This module answers that in
two complementary ways, both riding the unified telemetry stream as
``kind="attribution"`` records:

1. **Static cost model (roofline).**  Every probe program is AOT-lowered
   (``jax.jit(body).lower(...).compile()``) and its XLA
   ``cost_analysis()`` — flops + bytes accessed — turned into an
   arithmetic intensity (flops/byte) that is classified compute- vs
   memory-bound against the chip's ridge point
   (``peak_flops / peak_hbm_bandwidth``, `utils.flops` peak tables).
   Works on CPU too (XLA:CPU publishes the same counters), so the cost
   model is tier-1-testable; only the *verdict* degrades to ``"unknown"``
   on devices without a peak-table entry.

2. **Measured split.**  Wall step time decomposes into **device-compute**,
   **collective**, and **host-gap** fractions: a non-donating AOT copy of
   the training update is timed with a single fence (device = compute +
   collectives); under explicit DP a collective-free local-shard copy is
   timed the same way (collective = full − local, the Xu et al.
   arXiv:2004.13336 decomposition for the dp weight-update path); the
   host gap is span-derived — the loop's measured wall time per step
   minus the device time.  The three fractions sum to 1.0 by
   construction.

The probe is **opt-in and boundary-only**: it runs at the training loop's
``--attribution-every`` cadence (or under ``bpe-tpu profile``), pays its
one-off compile inside a watchdog-paused, throughput-excluded span, and
adds exactly :data:`StepProbe.FETCHES_PER_MEASURE` host syncs per timed
variant per boundary — untouched steps see zero new syncs (pinned by a
fetch-count test).

`benchmarks/bench_breakdown.py` drives the same helpers
(:func:`time_call`, :func:`program_cost`, :func:`roofline`), so bench rows
and telemetry records share one measurement path.
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import numpy as np

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.utils.flops import (
    peak_flops_per_chip,
    peak_hbm_bytes_per_sec,
)

__all__ = [
    "StepProbe",
    "decode_tick_roofline",
    "program_cost",
    "program_memory",
    "roofline",
    "serving_program_costs",
    "time_call",
]


# ----------------------------------------------------------- measurement

def _fence(out) -> None:
    """Device-sync barrier (as `telemetry/timing.py:_sync`)."""
    jax.block_until_ready(out)


def time_call(fn: Callable, *args, iters: int = 10, warmup: int = 2) -> float:
    """Mean wall milliseconds per call of ``fn(*args)``.

    The shared measurement path of the attribution probe and
    ``bench_breakdown``: ``warmup`` unfenced calls + one fence (absorbs
    compile/first-dispatch), then ``iters`` back-to-back dispatches + one
    fence — exactly two host syncs total, whatever ``iters`` is.
    """
    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    _fence(out)
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _fence(out)
    return (time.perf_counter() - start) / max(iters, 1) * 1e3


# -------------------------------------------------------- XLA cost model

def program_cost(compiled) -> dict:
    """``{"flops", "bytes_accessed"}`` out of an AOT-compiled executable's
    XLA ``cost_analysis()`` (fields are None when the backend publishes no
    counter).  Accepts both the modern single-dict and the legacy
    one-dict-per-partition list shape."""
    try:
        analysis = compiled.cost_analysis()
    except Exception:
        return {"flops": None, "bytes_accessed": None}
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not isinstance(analysis, dict):
        return {"flops": None, "bytes_accessed": None}

    def grab(key):
        value = analysis.get(key)
        return float(value) if isinstance(value, (int, float)) else None

    return {"flops": grab("flops"), "bytes_accessed": grab("bytes accessed")}


def program_memory(compiled) -> dict:
    """Peak-HBM accounting of an AOT-compiled executable from XLA's
    ``memory_analysis()`` (None values when the backend publishes none).

    ``peak_hbm_bytes = temp + arguments + outputs − aliased``: the
    buffer-assignment envelope the program needs live at once.  ``temp``
    alone is where a remat policy's win shows (activation residuals are
    temp buffers); arguments/outputs are the resident state.  For a
    NON-donating probe program this is an upper bound on the live
    (donating) step's peak — params/opt-state are counted once as
    arguments and once as outputs — but the bound is CONSTRUCTED
    identically for every knob setting, so deltas across
    remat/precision/scan configurations (and the ``train_peak_hbm_bytes``
    compare-gate row) attribute real wins, which is what the gate needs.
    """
    try:
        stats = compiled.memory_analysis()
    except Exception:
        stats = None
    if stats is None:
        return {
            "peak_hbm_bytes": None, "temp_bytes": None,
            "argument_bytes": None, "output_bytes": None,
        }

    def grab(name):
        value = getattr(stats, name, None)
        return int(value) if isinstance(value, (int, float)) else None

    temp = grab("temp_size_in_bytes")
    args = grab("argument_size_in_bytes")
    out = grab("output_size_in_bytes")
    alias = grab("alias_size_in_bytes") or 0
    peak = None
    if temp is not None and args is not None and out is not None:
        peak = temp + args + out - alias
    return {
        "peak_hbm_bytes": peak,
        "temp_bytes": temp,
        "argument_bytes": args,
        "output_bytes": out,
    }


def roofline(
    flops: float | None,
    bytes_accessed: float | None,
    device_kind: str,
    name: str = "program",
) -> dict:
    """Classify one compiled program against the device roofline.

    Returns a JSON-ready dict: the raw counters, the arithmetic intensity
    (flops/byte), the device ridge point (``peak_flops / peak_bw``, the
    intensity at which a kernel stops being bandwidth-starved), and a
    ``bound`` verdict — ``"compute-bound"`` / ``"memory-bound"`` /
    ``"unknown"`` (no counters, or no peak-table entry for the device).
    """
    intensity = None
    if flops and bytes_accessed:
        intensity = flops / bytes_accessed
    peak_f = peak_flops_per_chip(device_kind)
    peak_bw = peak_hbm_bytes_per_sec(device_kind)
    ridge = peak_f / peak_bw if peak_f and peak_bw else None
    bound = "unknown"
    if intensity is not None and ridge is not None:
        bound = "compute-bound" if intensity >= ridge else "memory-bound"
    return {
        "name": name,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "arithmetic_intensity": (
            round(intensity, 3) if intensity is not None else None
        ),
        "ridge_flops_per_byte": round(ridge, 3) if ridge is not None else None,
        "bound": bound,
    }


def decode_tick_roofline(
    *,
    flops: float,
    weight_bytes: float,
    kv_bytes: float,
    act_bytes: float,
    device_kind: str,
) -> dict:
    """The serving decode tick's analytic roofline: its HBM byte stream
    decomposed into **weights** (the per-tick sweep of the matmul
    weights — what int8 quantization halves vs bf16), **KV** (the live
    attention read stream — what int8 KV blocks halve), and
    **activations** (transient tensors, estimated), against the chip
    ridge point.

    Unlike :func:`roofline` (which reads XLA's ``cost_analysis`` of a
    compiled program), this is a *first-principles* model from engine
    facts — resident weight bytes, live cache positions, tick FLOPs
    (`utils.flops.decode_tick_flops`) — so the weight/KV split is
    attributable: the compare gate can pin "serving weight bytes per
    tick" directly, and ``projected_tick_s`` (total bytes / peak HBM
    bandwidth) is the memory-bound latency floor the measured tick is
    judged against.  Returns a JSON-ready dict extending the
    :func:`roofline` row with the byte decomposition.
    """
    total = float(weight_bytes) + float(kv_bytes) + float(act_bytes)
    row = roofline(
        flops if flops else None, total if total else None, device_kind,
        name="decode_tick",
    )
    peak_bw = peak_hbm_bytes_per_sec(device_kind)
    row.update(
        {
            "weight_bytes": int(weight_bytes),
            "kv_bytes": int(kv_bytes),
            "act_bytes": int(act_bytes),
            "weight_frac": round(weight_bytes / total, 4) if total else None,
            "projected_tick_s": (
                round(total / peak_bw, 9) if peak_bw and total else None
            ),
        }
    )
    return row


# ------------------------------------------------------------ step probe

class StepProbe:
    """Non-donating AOT copies of the training update used to attribute
    step time and cost-model the compiled programs.

    Built once per run (lazily, at the first attribution boundary) for the
    loop's exact execution mode — single-device, explicit-DP, or GSPMD,
    with the grad-accum / inner-steps stacking the real step uses — on a
    synthetic batch of the real shape.  Not donating means the probe never
    invalidates the loop's live params/opt-state buffers (the price is one
    transient extra copy of the state during a measure, which is why the
    probe is opt-in and boundary-only).

    The collective split is measured only where it is well-defined: under
    ``parallel="dp"`` a collective-free single-shard copy of the same body
    is timed and ``collective = full − local``.  GSPMD strategies
    interleave XLA-scheduled collectives with compute (overlap makes the
    subtraction dishonest there), so they report ``collective_frac=None``
    with compute carrying the whole device time.
    """

    #: Host syncs (jax.device_get) per timed variant per measure() — the
    #: constant the fetch-count acceptance test pins.
    FETCHES_PER_MEASURE = 2

    def __init__(
        self,
        model_config: ModelConfig,
        hparams,
        *,
        batch_size: int,
        mesh=None,
        parallel: str | None = None,
        accum_steps: int = 1,
        inner_steps: int = 1,
        iters: int = 3,
        seed: int = 0,
        opt_sharding: str | None = None,
    ):
        if parallel in ("sp", "pp"):
            raise ValueError(
                f'attribution is not supported with parallel="{parallel}" '
                "(sp/pp build their own update bodies)"
            )
        self.config = model_config
        self.hparams = hparams
        self.batch_size = batch_size
        self.mesh = mesh
        self.parallel = parallel
        self.accum_steps = accum_steps
        self.inner_steps = inner_steps
        self.opt_sharding = opt_sharding
        self.iters = iters
        self._rng = np.random.default_rng(seed)
        self._compiled: dict[str, object] = {}
        self._costs: list[dict] | None = None
        self._memory: dict | None = None
        self._batches: dict[str, tuple] = {}

    # -- internal builders -------------------------------------------------

    def _synth_batch(self, batch: int) -> tuple[np.ndarray, np.ndarray]:
        """Random token ids at the loop's exact batch layout (stacked for
        grad-accum / inner-steps) — timing is data-independent for dense
        configs, and synthetic data keeps the probe decoupled from the
        loop's deterministic batch stream."""
        S = self.config.context_length
        ids = self._rng.integers(0, self.config.vocab_size, size=(batch, S))
        x = ids.astype(np.int32)
        y = np.roll(ids, -1, axis=1).astype(np.int32)
        if self.accum_steps > 1:
            micro = batch // self.accum_steps
            x = x.reshape(self.accum_steps, micro, S)
            y = y.reshape(self.accum_steps, micro, S)
        elif self.inner_steps > 1:
            x = np.broadcast_to(x, (self.inner_steps, *x.shape)).copy()
            y = np.broadcast_to(y, (self.inner_steps, *y.shape)).copy()
        return x, y

    def _bodies(self) -> dict[str, Callable]:
        """``{variant: un-jitted body}`` for this execution mode.  Under
        explicit dp the ``train_step_local`` variant is the SAME body with
        the gradient ``pmean`` dropped — it runs over the same mesh on the
        same sharded batch, so ``full − local`` isolates exactly the
        collective (placement, shapes, and per-chip compute identical)."""
        from bpe_transformer_tpu.parallel.train_step import (
            _multi_step_body,
            partitioned_config,
        )

        # The probe compiles what the loop's step holds: a GSPMD program
        # takes the config its partitioner can split.
        config = self.config
        if self.mesh is not None and self.parallel != "dp":
            config = partitioned_config(config, self.mesh)

        def body(reduce_axis, zero1_shards=None):
            b, _ = _multi_step_body(
                config, self.hparams, self.accum_steps,
                self.inner_steps, reduce_axis=reduce_axis,
                zero1_shards=zero1_shards,
            )
            return b

        if self.mesh is not None and self.parallel == "dp":
            if self.opt_sharding == "zero1":
                # The ZeRO-1 schedule interleaves reduce-scatter / compute /
                # all-gather; a collective-free variant would change the
                # per-chip work, so — like GSPMD — it reports
                # collective_frac=None rather than a made-up number.
                n = self.mesh.shape["data"]
                return {"train_step": body("data", zero1_shards=n)}
            return {
                "train_step": body("data"),
                "train_step_local": body(None),
            }
        # Single device, or a GSPMD strategy: one program.  (XLA schedules
        # GSPMD collectives interleaved with compute — overlap makes a
        # subtraction-based collective split dishonest there, so GSPMD
        # reports collective_frac=None rather than a made-up number.)
        return {"train_step": body(None)}

    def _compile(self, params, opt_state) -> None:
        """AOT-lower + compile every probe variant (once), harvesting each
        program's cost analysis on the way.  Never touches the loop's jit
        caches and never donates."""
        import jax.numpy as jnp

        device_kind = jax.devices()[0].device_kind
        x, y = self._synth_batch(self.batch_size)
        x, y = jnp.asarray(x), jnp.asarray(y)
        if self.mesh is not None:
            from bpe_transformer_tpu.parallel.train_step import shard_batch

            stacked = self.accum_steps > 1 or self.inner_steps > 1
            x, y = shard_batch((x, y), self.mesh, stacked=stacked)
        costs: list[dict] = []
        for name, body in self._bodies().items():
            jitted = (
                self._mesh_jit(body, params, opt_state)
                if self.mesh is not None
                else jax.jit(body)
            )
            compiled = jitted.lower(params, opt_state, x, y).compile()
            self._compiled[name] = compiled
            self._batches[name] = (x, y)
            cost = program_cost(compiled)
            costs.append(
                roofline(
                    cost["flops"], cost["bytes_accessed"], device_kind,
                    name=name,
                )
            )
            if name == "train_step":
                # Peak-HBM accounting of the full update program: the
                # number the remat policy / bf16 boundary / loss chunking
                # move, stamped onto every attribution record so the
                # train_peak_hbm_bytes compare gate can pin it.
                self._memory = program_memory(compiled)
        self._costs = costs

    def _mesh_jit(self, body, params, opt_state):
        """The sharded (non-donating) jit wrapper matching the loop's
        strategy: shard_map for explicit dp, NamedSharding annotations for
        GSPMD."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        stacked = self.accum_steps > 1 or self.inner_steps > 1
        if self.parallel == "dp":
            batch_spec = P(None, "data") if stacked else P("data")
            if self.opt_sharding == "zero1":
                from bpe_transformer_tpu.optim.sharded import ShardedAdamWState

                opt_spec = ShardedAdamWState(
                    step=P(), m=P("data"), v=P("data"), master=P("data")
                )
            else:
                opt_spec = P()
            mapped = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(P(), opt_spec, batch_spec, batch_spec),
                out_specs=(P(), opt_spec, P()),
                check_vma=False,
            )
            return jax.jit(mapped)
        from bpe_transformer_tpu.parallel.sharding import param_shardings

        p_sh = param_shardings(params, self.mesh, self.parallel)
        replicated = NamedSharding(self.mesh, P())
        if self.opt_sharding == "zero1":
            from bpe_transformer_tpu.parallel.sharding import zero1_opt_shardings

            moment_sh = zero1_opt_shardings(params, self.mesh, self.parallel)
        else:
            moment_sh = p_sh
        opt_sh = type(opt_state)(step=replicated, m=moment_sh, v=moment_sh)
        data_spec = P(None, "data") if stacked else P("data")
        batch_sh = (
            NamedSharding(self.mesh, data_spec)
            if "data" in self.mesh.shape
            else replicated
        )
        return jax.jit(
            body,
            in_shardings=(p_sh, opt_sh, batch_sh, batch_sh),
            out_shardings=(p_sh, opt_sh, replicated),
        )

    # -- public API --------------------------------------------------------

    def program_costs(self, params, opt_state) -> list[dict]:
        """Roofline rows (one per probe program), compiling on first use."""
        if self._costs is None:
            self._compile(params, opt_state)
        return self._costs

    def memory_stats(self, params, opt_state) -> dict:
        """:func:`program_memory` of the compiled full-step program
        (``peak_hbm_bytes``/``temp_bytes``/...), compiling on first use —
        the number the remat-policy and loss-chunking knobs move."""
        if self._costs is None:
            self._compile(params, opt_state)
        return dict(self._memory or {})

    def measure(self, params, opt_state) -> dict:
        """Fenced device timings of the probe programs (seconds per
        OPTIMIZER UPDATE — inner-steps scans are divided back out):
        ``{"device_step_s", "compute_s", "collective_s"}`` with
        ``collective_s`` None where not measurable (GSPMD / single device
        reports 0.0)."""
        if self._costs is None:
            self._compile(params, opt_state)
        per_update = 1.0 / max(self.inner_steps, 1)

        def timed(name: str) -> float:
            compiled = self._compiled[name]
            x, y = self._batches[name]
            ms = time_call(
                compiled, params, opt_state, x, y,
                iters=self.iters, warmup=1,
            )
            return ms / 1e3 * per_update

        device_step_s = timed("train_step")
        if self.mesh is None:
            return {
                "device_step_s": device_step_s,
                "compute_s": device_step_s,
                "collective_s": 0.0,
            }
        if "train_step_local" in self._compiled:
            local_s = timed("train_step_local")
            collective_s = max(device_step_s - local_s, 0.0)
            return {
                "device_step_s": device_step_s,
                "compute_s": device_step_s - collective_s,
                "collective_s": collective_s,
            }
        return {
            "device_step_s": device_step_s,
            "compute_s": device_step_s,
            "collective_s": None,
        }

    def loop_wall_step_s(self, params, opt_state, iters: int | None = None) -> float:
        """Wall seconds per optimizer update of a training-shaped mini
        loop: each iteration pays a fresh host batch (numpy sampling +
        device upload) then an async dispatch of the full-step probe, with
        one fence at the end — the ``bpe-tpu profile`` stand-in for the
        real loop's measured wall step time (its host-gap fraction thus
        covers batch feed + dispatch overhead, the same work the loop
        does)."""
        import jax.numpy as jnp

        if self._costs is None:
            self._compile(params, opt_state)
        compiled = self._compiled["train_step"]
        iters = iters if iters is not None else max(self.iters, 3)
        _fence(compiled(params, opt_state, *self._batches["train_step"]))
        start = time.perf_counter()
        out = None
        for _ in range(iters):
            x, y = self._synth_batch(self.batch_size)
            x, y = jnp.asarray(x), jnp.asarray(y)
            if self.mesh is not None:
                from bpe_transformer_tpu.parallel.train_step import shard_batch

                stacked = self.accum_steps > 1 or self.inner_steps > 1
                x, y = shard_batch((x, y), self.mesh, stacked=stacked)
            out = compiled(params, opt_state, x, y)
        _fence(out)
        return (
            (time.perf_counter() - start)
            / max(iters, 1)
            / max(self.inner_steps, 1)
        )

    @property
    def fetches_per_measure(self) -> int:
        """Total host syncs one :meth:`measure` call performs — variants x
        :data:`FETCHES_PER_MEASURE` (the fetch-count test's budget)."""
        n_variants = 2 if (
            self.mesh is not None
            and self.parallel == "dp"
            and self.opt_sharding != "zero1"
        ) else 1
        return n_variants * self.FETCHES_PER_MEASURE

    def attribution_record(
        self,
        params,
        opt_state,
        *,
        step: int,
        wall_step_s: float,
        t: float,
        include_programs: bool | None = None,
    ) -> dict:
        """One ``kind="attribution"`` record: the measured compute /
        collective / host-gap split of ``wall_step_s`` (fractions sum to
        1.0), carrying the static roofline rows on the first record of the
        run (``include_programs`` overrides).

        Every record additionally carries the update program's
        ``train_peak_hbm_bytes`` (:func:`program_memory` of the compiled
        step) and the execution-knob labels that produced it —
        ``remat_policy`` / ``grads_dtype`` / ``scan_layers`` — so a
        peak-memory or MFU move is attributable to the knob that caused
        it instead of read off a dashboard and guessed at."""
        first = self._costs is None
        measured = self.measure(params, opt_state)
        device_s = measured["device_step_s"]
        collective_s = measured["collective_s"]
        compute_s = measured["compute_s"]
        host_gap_s = max(wall_step_s - device_s, 0.0)
        denom = max(wall_step_s, device_s, 1e-12)
        memory = self._memory or {}
        record = {
            "kind": "attribution",
            "t": round(t, 6),
            "step": step,
            "wall_step_s": round(wall_step_s, 6),
            "device_step_s": round(device_s, 6),
            "compute_frac": round(compute_s / denom, 4),
            "collective_frac": (
                round(collective_s / denom, 4)
                if collective_s is not None
                else None
            ),
            "host_gap_frac": round(host_gap_s / denom, 4),
            "probe_iters": self.iters,
            "train_peak_hbm_bytes": memory.get("peak_hbm_bytes"),
            "train_temp_hbm_bytes": memory.get("temp_bytes"),
            "remat_policy": self.config.resolved_remat_policy,
            "grads_dtype": getattr(self.hparams, "grads_dtype", "float32"),
            "scan_layers": self.config.scan_layers,
        }
        if include_programs if include_programs is not None else first:
            record["programs"] = self._costs
        return record


# -------------------------------------------------- serving cost model

def serving_program_costs(
    params,
    config: ModelConfig,
    *,
    slots: int = 8,
    prefill_buckets: tuple[int, ...] | None = None,
) -> list[dict]:
    """Roofline rows for the serving engine's program set: one bucketed
    prefill per bucket plus the batched decode tick — the same closures
    `serving.engine.SlotPoolEngine` jits, AOT-lowered here so profiling a
    bucket ladder never touches (or miscounts) a live engine's bounded
    per-engine compile cache."""
    import functools

    import jax.numpy as jnp

    from bpe_transformer_tpu.models.decode import init_kv_cache
    from bpe_transformer_tpu.models.transformer import lm_head_weight
    from bpe_transformer_tpu.serving.engine import (
        _prefill_program,
        _tick_program,
        default_prefill_buckets,
    )

    if prefill_buckets is None:
        prefill_buckets = default_prefill_buckets(config.context_length)
    device_kind = jax.devices()[0].device_kind
    act_dtype = jnp.dtype(config.activation_dtype)
    lm_head = lm_head_weight(params, config).astype(act_dtype)
    if act_dtype != jnp.float32:
        params = jax.tree_util.tree_map(lambda p: p.astype(act_dtype), params)
    cache = init_kv_cache(config, slots, dtype=act_dtype)
    key = jax.random.PRNGKey(0)

    rows: list[dict] = []
    prefill = functools.partial(_prefill_program, config=config)
    for bucket in prefill_buckets:
        padded = jnp.zeros((1, bucket), jnp.int32)
        compiled = jax.jit(prefill).lower(
            params, lm_head, cache, padded, jnp.int32(bucket),
            jnp.int32(0), key, jnp.float32(1.0), jnp.int32(0),
            jnp.float32(2.0),
        ).compile()
        cost = program_cost(compiled)
        rows.append(
            roofline(
                cost["flops"], cost["bytes_accessed"], device_kind,
                name=f"prefill[{bucket}]",
            )
        )
    tick = functools.partial(_tick_program, config=config)
    compiled = jax.jit(tick).lower(
        params, lm_head, cache,
        jnp.zeros(slots, jnp.int32), jnp.zeros(slots, jnp.int32),
        jnp.ones(slots, bool), jnp.zeros((slots, 2), jnp.uint32),
        jnp.ones(slots, jnp.float32), jnp.zeros(slots, jnp.int32),
        jnp.full(slots, 2.0, jnp.float32),
    ).compile()
    cost = program_cost(compiled)
    rows.append(
        roofline(
            cost["flops"], cost["bytes_accessed"], device_kind,
            name=f"decode_tick[{slots}]",
        )
    )
    return rows
