"""The documented telemetry record schema — one source of truth.

Every record any module in this package emits into the unified JSONL stream
must be one of the kinds below, carrying at least the required fields.  The
table is duplicated (deliberately, as prose) in ``ARCHITECTURE.md`` and
``README.md`` § Observability; ``tools/check_telemetry_schema.py`` — wired
into tier-1 — greps the package for every emitted ``kind`` and fails when
one is missing from this registry, so a new record kind cannot ship
undocumented.

The Chrome trace exporter (``telemetry/trace.py``) additionally assumes
``span`` records carry ``t``/``dur_s`` on the run-relative seconds axis,
``engine`` records share that ``t`` axis, and ``resources`` records carry
absolute ``time_unix`` — declared as ``TRACE_ASSUMPTIONS`` there and
cross-checked against this registry by the same tool.

Jax-free: the report/monitor tools import this on hosts with no
accelerator runtime.
"""

from __future__ import annotations

#: kind -> set of REQUIRED fields.  Step/val metric records carry no
#: ``kind`` key (the pre-telemetry JSONL schema, preserved); they are
#: registered under the pseudo-kind ``"metric"``.
RECORD_SCHEMAS: dict[str, set[str]] = {
    # Run header: config, mesh, versions, git SHA, host (telemetry/manifest.py).
    # A training header additionally carries (optional; absent under
    # ``parallel="sp"``, whose ring schedules bring their own attention)
    # ``attention_path`` — ``"flash"`` or ``"xla"``, the causal
    # self-attention the compiled step holds, forced by the config or chosen
    # from (S, d_head, dtype, backend) — and ``flash_tiles``, the flash
    # kernel's ``[block_q, block_k]`` (null on the xla path); ``bpe-tpu
    # train``'s summary row and ``summary.json`` repeat both.
    "manifest": {"kind", "run_kind", "time_utc", "host"},
    # Closed wall-clock span; ``path`` is the /-joined nesting (spans.py).
    "span": {"kind", "name", "path", "t", "dur_s"},
    # Point-in-time marker: NaN dumps, watchdog trips, worker errors.
    "event": {"kind", "name", "t"},
    # Periodic serving-engine snapshot (serving/server.py).
    "engine": {
        "kind", "t", "active_slots", "queue_depth", "tokens_per_sec",
        "tokens_total", "ticks", "requests_finished", "compiled_programs",
    },
    # One decode tick of the serving worker (serving/server.py): where the
    # tick *period* went.  A period runs from the end of the previous
    # launch's period to the end of this one's, so consecutive records tile
    # the worker's time (``t`` + ``dur_s`` = the next record's ``t``, on
    # the serve/* spans' axis): one record a launch.  The phase fields are
    # the clock pairs of the worker's ``serve/*`` profiler annotations,
    # summed over the period: ``admit_s`` (cancellations, backlog expiry,
    # grafts, the scheduler pop, every admission), ``prefill_s`` over
    # ``chunks`` prefill-chunk calls of ``prefill_tokens`` prompt tokens
    # (the paged engine's worker queues a chunk and reads nothing: dispatch
    # and bookkeeping), ``dispatch_s`` (the tick program's call until it
    # returns), ``wait_s`` (the host blocked on the oldest unread launch:
    # the paged engine's worker queues tick n+1 first and then reads tick n
    # and the first token of each final chunk queued behind it, so this is
    # what of their device time the host's own work had not covered; the
    # dense and the speculative engine read back the tick they launched),
    # ``emit_s`` (arrays to events, of the launches read), ``deliver_s``
    # (tokens to their streams and the finished requests: behind the next
    # launch either way), ``idle_s`` (waiting for work) and ``other_s`` =
    # ``dur_s`` minus the rest, kept explicit.  Optional (ISSUE 38), from
    # a second clock, the worker thread's CPU clock (``time.thread_time``;
    # a read is a system call, so it is read once a period): ``cpu_s``, the
    # thread's CPU seconds over the period (beside ``dur_s``), and
    # ``host_offcpu_s`` = ``dur_s - wait_s - idle_s - cpu_s``, what of the
    # period outside the two phases whose purpose is to be off the CPU the
    # worker was off it - waiting for the interpreter lock, descheduled by
    # the host, or asleep in a runtime call that let go of the lock: time
    # the worker wanted and did not get, not device time (less the little
    # CPU it used while it waited).  Never clamped at 0: a kernel that
    # counts CPU time by its scheduler's tick (the chip machines': 10 ms)
    # hands out multiples of it, one record then reads a tick too much or
    # too little, and only sums and means over many records say anything.
    # ``gc_s``: the seconds of the period the interpreter's collector
    # stopped every thread, whichever thread ran it.  ``batch`` is the tokens the
    # engine emitted in the period (those of the launches it read);
    # ``queue_depth`` the scheduler's at the period's end.  Optional
    # ``overlapped``: 1 where the period's launch was queued while the one
    # before was unread; ``stale_rows``: rows read in it that a launch had
    # computed for a tenant since gone (a finish by stop id, a cancellation
    # or an eviction found one launch late); ``carry_flushes``: times a
    # reader or writer of the decode carry (migration, rewind) had to read
    # unread launches first (all 0 for the engines that do not run ahead).
    # The ``moe_*`` and ``ssm_tick_*`` fields are those of the tick that
    # was READ in the period.  Optional
    # ``moe_rows_local``: the tick's expert assignments that landed on
    # experts held here (grouped paged engine; 0 elsewhere); optional
    # ``moe_zero_assignments``: those that landed on zero-compute experts;
    # optional ``ssm_tick_state_rows``: state-space slot-layers the tick
    # updated (live slots x state-space layers), and ``ssm_chunk_tokens`` /
    # ``ssm_chunk_rows``: real and bucket rows x state-space layers through
    # the scans of the period's prefill chunks - layers counted by kind
    # from the config's own pattern (``ModelConfig.layer_kinds``: a layer
    # is a state-space mixer, an attention mixer, an expert layer, or a
    # mixer and an expert layer both), as the ``moe_*`` fields count the
    # layers that have an expert part and ``attn_kv_positions`` the
    # attention layers alone; optional
    # ``attn_shared_kv_positions`` / ``attn_shared_slots``: key positions x
    # sublayers that tick's slots attended through the latent kernels'
    # shared pass, and the slots on the shared chain (over a latent pool
    # alone); optional ``attn_kv_positions`` / ``attn_summary_kv_positions``:
    # cached rows x layers that tick attended and those of them that are
    # chunk summaries (over a summary-and-window cache alone); optional
    # ``attn_full_kv_positions`` / ``attn_window_kv_positions``: key
    # positions that tick's slots attended in the full-attention layers and
    # - inside the window - in the window layers, each x its group's layers
    # (over rows of keys and values by group alone, `decode.GroupedRows`).
    "tick": {
        "kind", "t", "dur_s", "admit_s", "prefill_s", "chunks",
        "prefill_tokens", "dispatch_s", "wait_s", "emit_s", "deliver_s",
        "idle_s", "other_s", "batch", "queue_depth",
    },
    # Resource accounting sample (telemetry/resources.py): HBM fields are
    # None on backends without memory_stats (CPU), never absent.  Training
    # records additionally carry optional ``params_bytes`` /
    # ``opt_state_bytes`` (PER-CHIP state bytes from shard-shape metadata —
    # the ZeRO-1 optimizer-sharding memory win reads directly off them) and
    # ``compile_time_s``; all three are optional — older streams predate
    # them.  ``hbm_bytes_in_use_per_device`` (PR 22, optional, None on CPU)
    # lists one entry per local device: a sharded run shows every chip
    # holding its share.  ``hbm_peak_bytes_in_use`` is the allocator's
    # high-water mark, which on the v5e runtime does NOT include a
    # program's temporaries (PERF.md §6, PR 22) — a floor, not the peak.
    # Optional ``gc_pause_s`` / ``gc_collections`` / ``gc_gen2_collections``
    # (ISSUE 38): the process's seconds inside garbage collections so far,
    # their count over all generations and the oldest generation's apart
    # (0 until ``install_gc_counter`` has hooked the collector).
    "resources": {
        "kind", "time_unix", "host_rss_bytes", "live_buffer_bytes",
        "compile_events", "hbm_bytes_in_use", "hbm_peak_bytes_in_use",
        "hbm_bytes_limit",
    },
    # Training-dynamics introspection sample (telemetry/dynamics.py),
    # emitted every --dynamics-every steps at the log-cadence fetch.  The
    # payload is flat per-layer keys — grad_norm/param_norm/update_ratio
    # per layer label (``layers.N``, ``token_embeddings``, ...), activation
    # act_rms/act_absmax/attn_entropy per block, nonzero non-finite counts
    # per tensor path (``nonfinite_params/layers.3.ffn.w1``) and a
    # ``first_nonfinite`` localization path — all optional (a grad-accum
    # step has no activation taps; a clean step has no non-finite keys).
    "dynamics": {"kind", "step"},
    # Graceful-preemption marker (resilience/signals + training/loop.py):
    # SIGTERM/SIGINT was caught, the loop stopped at a step boundary, and
    # (when a checkpoint dir is configured) an emergency snapshot was
    # written — ``checkpoint`` carries its path, null when none could be.
    "preemption": {"kind", "t", "step", "signal"},
    # NaN-rollback recovery record (training/loop.py under
    # on_nonfinite="rollback"): the run reloaded ``restored_step``'s
    # checkpoint after a non-finite state at ``step`` and is retrying with
    # the offending data window skipped.  ``rollbacks`` is the running
    # count; optional ``lost_steps`` and the PR-4 ``nonfinite_path``
    # localization ride along.
    "recovery": {"kind", "t", "step", "restored_step", "rollbacks"},
    # Performance-attribution sample (telemetry/attribution.py), emitted
    # every --attribution-every steps (and by ``bpe-tpu profile``): the
    # measured compute / collective / host-gap split of wall step time
    # (fractions sum to ~1.0; ``collective_frac`` is null where the
    # collective is not separable — GSPMD strategies), plus, on the first
    # record of a run, the static XLA cost-model roofline rows under an
    # optional ``programs`` list (name, flops, bytes_accessed,
    # arithmetic_intensity, ridge_flops_per_byte, bound verdict).
    # Records additionally carry the compiled step's peak-HBM envelope and
    # the execution-knob labels that produced it (all optional — older
    # streams predate them): ``train_peak_hbm_bytes`` /
    # ``train_temp_hbm_bytes`` (XLA memory_analysis: temp + args + outputs
    # − aliased of the non-donating probe program; null on backends
    # without the counters) and ``remat_policy`` / ``grads_dtype`` /
    # ``scan_layers`` — so a peak or MFU move is attributable to the knob
    # that caused it.  ``train_peak_hbm_bytes`` feeds the report compare
    # gate (lower), as does the derived ``mfu_compute_ceiling``.
    "attribution": {
        "kind", "t", "step", "wall_step_s", "device_step_s",
        "compute_frac", "collective_frac", "host_gap_frac",
    },
    # Paged-KV pool snapshot (serving/server.py, paged engines only),
    # emitted on the engine-record cadence: block occupancy
    # (``blocks_{total,free,shared}``), radix prefix-cache effectiveness
    # (cumulative token ``prefix_{hits,misses}`` and the derived
    # ``prefix_hit_rate``, null before any lookup), the chunked-prefill
    # backlog (optional ``prefill_pending_tokens``), and the KV-memory
    # economics (optional ``kv_pool_bytes`` — resident pool bytes, scale
    # pools included — and ``kv_bytes_per_token`` — the per-position KV
    # footprint at pool width, the attention read stream's unit, which
    # int8 quantization halves/quarters; both feed the
    # report --baseline regression gate; older streams predate them), and
    # what the compiled pool programs say of themselves (optional, also in
    # ``stats()`` / ``/statusz``; XLA ``memory_analysis()`` of each program
    # the engine has run): ``kv_pool_aliased_bytes`` — ``kv_pool_bytes``
    # less the bytes of the pool that the least-aliasing program does not
    # alias from its donated argument to its output, so EQUAL to
    # ``kv_pool_bytes`` while the pool goes through every program in place
    # and lower by an array's size as soon as one stops being donated —
    # and ``tick_temp_bytes``, the tick (or spec verify) program's
    # temporaries, where a pool-sized layout copy coming back would show.
    # Both null before a program has run.  ``stats()`` also carries the
    # recurrent state's counters (0 without state-space layers):
    # ``ssm_tick_state_rows`` (slot-layers the ticks updated),
    # ``ssm_chunk_tokens`` / ``ssm_chunk_rows`` (real and bucket rows x
    # state-space layers through the chunks' scans), ``ssm_state_resets``
    # (admissions from a zero state) and the gauge ``ssm_state_bytes``
    # (state and conv rows at their grouped widths - the conv rows carry
    # every group's ``B`` and ``C`` - of the state-space layers alone;
    # ``kv_pool_bytes`` stays K and V of the attention layers alone, and a
    # layer without a mixer holds nothing); over a latent pool alone
    # ``attn_shared_kv_positions`` (of ``attn_kv_positions``, those the
    # ticks' slots attended through the shared pass: the chain of blocks
    # several slots' rows start with, attended once for all of them) and
    # ``attn_shared_slots`` (slots on the chain, summed over ticks),
    # ``chunk_attn_pairs`` (visible (query, key) pairs of the chunks x
    # sublayers; ``attn_pairs`` stays the ticks') and
    # ``chunk_attn_kernel_pairs`` (those of launches whose bucket attends
    # in the expanded form's kernel, `mla_attention.mla_chunk_path`); over a
    # summary-and-window cache alone ``attn_summary_kv_positions`` (of the
    # ticks' ``attn_kv_positions``, the rows that are chunk summaries),
    # ``eva_summary_rows`` (summaries written by ticks and chunks, x
    # layers), ``eva_windows_closed`` (a slot's table row laid out anew)
    # and the gauge ``kv_summary_blocks_used`` (there ``attn_pairs`` holds
    # the chunks' pairs too, ``kv_bytes_per_token`` is one cached ROW over
    # the layers - a position's or a chunk's summary - and
    # ``kv_window_blocks_recycled`` counts a closed window's blocks, which
    # the slot keeps for its next window); over rows of keys and values by
    # group alone (`decode.GroupedRows`) the attention's counts by group,
    # each x its group's layers: ``attn_full_kv_positions`` /
    # ``attn_window_kv_positions`` (the ticks': a live slot's context, and
    # what of it lies inside the window), ``chunk_attn_full_kv_positions``
    # / ``chunk_attn_window_kv_positions`` (the chunks': back to a chunk's
    # first row's window start in a window layer) and
    # ``chunk_attn_full_pairs`` / ``chunk_attn_window_pairs`` (the chunks'
    # visible (query, key) pairs), ``chunk_attn_full_key_blocks`` /
    # ``chunk_attn_full_masked_blocks`` (the blocks of keys the chunk
    # kernel's walks fold in the full layers, and those of them under a
    # mask: `sink_attention.chunk_walk`) - there ``attn_pairs`` and
    # ``attn_kv_positions`` hold both groups', ticks and chunks, and
    # ``kv_window_blocks_recycled`` the window group's blocks given back
    # behind the launch that read them; and
    # the two dispatch phases
    # in parts (ISSUE 38; ``paged_engine.LAUNCH_PARTS``), clock seconds
    # summed over every launch: ``launch_tick_{prepare,call,after}_s`` of
    # the ``ticks`` launches - host arithmetic and the argument copies, the
    # jitted call until it returns (argument transfers and the enqueue),
    # the book-keeping behind it - and
    # ``launch_chunk_{key,prepare,call,after}_s`` of the ``chunk_launches``
    # chunks - the request's PRNG key, the padded row and the table row,
    # the jitted call, the book-keeping and a final chunk's radix insert.
    "kvpool": {
        "kind", "t", "blocks_total", "blocks_free", "blocks_shared",
        "prefix_hits", "prefix_misses",
    },
    # Speculative-decoding snapshot (serving/server.py, SpecEngine only),
    # emitted on the engine-record cadence: the fixed window ``k``, the
    # cumulative draft tokens judged (``proposed``) and kept
    # (``accepted``), decode tokens emitted by spec ticks (``emitted``)
    # over ``target_steps`` verify passes, plus the derived
    # ``accept_rate`` (accepted/proposed, null before any tick),
    # ``tokens_per_target_step`` (the "ticks saved" number — 1.0 is
    # non-speculative decode, k+1 the ceiling), ``rewound`` stale KV
    # positions rolled back, and the draft's share of tick wall time
    # (optional ``draft_frac``).  ``accept_rate`` and
    # ``tokens_per_target_step`` feed the report compare gate.
    "spec": {
        "kind", "t", "k", "proposed", "accepted", "emitted", "target_steps",
    },
    # Decode-tick roofline sample (serving/server.py, every engine kind),
    # emitted on the engine-record cadence: the analytic HBM byte split of
    # ONE decode tick at current occupancy — ``weight_bytes`` (the matmul
    # weight sweep int8 weight quantization halves vs bf16), ``kv_bytes``
    # (the live attention stream int8 KV blocks halve), optional
    # ``act_bytes`` (transient estimate; fused sampling shrinks the
    # vocab-sized tail to one gumbel round trip) — plus the tick ``flops``
    # (utils/flops.decode_tick_flops) and the derived
    # ``arithmetic_intensity`` / ``ridge_flops_per_byte`` / ``bound``
    # verdict / ``projected_tick_s`` memory-bound floor (null off-TPU),
    # ``weight_frac``, occupancy (``active_slots``) and the
    # ``weight_dtype`` / ``fused_sampling`` knobs that produced it.
    # ``weight_bytes`` feeds the report compare gate (serve_weight_bytes).
    "roofline": {
        "kind", "t", "weight_bytes", "kv_bytes", "flops",
    },
    # KV-slot migration (serving/server.py, ISSUE 15): one record per KV
    # move in the disaggregated fleet.  ``direction`` is ``export`` (a
    # prefill-role replica streamed a finished prefix out), ``import`` (a
    # decode replica grafted a payload), or ``evacuate`` (a draining
    # replica exported an in-flight session to a peer).  ``bytes`` is the
    # serialized payload size, ``blocks`` the KV blocks moved.  Import
    # records additionally carry the phase split — optional ``export_s``
    # (from the source's meta), ``transfer_s`` (export -> graft wall,
    # wall-clock-derived), ``import_s`` (the graft itself), and their
    # ``total_s`` (the compare gate's migration_p99_s evidence) — plus
    # ``request_id`` so migration hops join the cross-stream request
    # timeline next to the serve/migration_* spans.
    "migration": {"kind", "t", "direction", "bytes", "blocks"},
    # Fleet sweep (telemetry/fleet.py, `bpe-tpu fleet`): one concurrent
    # poll of every replica's /statusz+/metrics (plus the router's
    # counters) merged into fleet-level gauges — online/draining counts,
    # summed queue depth / active slots / token rate, worst-replica
    # ``kv_headroom_frac``, fleet spec ``accept_rate``, cumulative
    # availability counters (``requests_ok``/``requests_failed``, router
    # present only), merged cumulative latency histograms
    # (``hist_total``/``hist_ttfb`` as ``[le, count]`` pairs, le null =
    # +Inf) with the derived ``request_p99_s``/``ttfb_p99_s``, and a
    # ``per_replica`` snapshot table.  All but the required fields are
    # optional/nullable — a dense fleet has no kv gauges, a routerless
    # sweep no availability.
    "fleet": {"kind", "t", "replicas_total", "replicas_online"},
    # SLO evaluation (telemetry/slo.py) over a rolling window of the
    # fleet stream: the objective's ``target`` good-fraction, the
    # window's ``good``/``total`` event deltas and derived ``sli``, and
    # the error-budget ``burn_rate`` = (1-sli)/(1-target) — null when the
    # window saw no traffic.  Latency objectives carry ``threshold_s``.
    # ``burn_rate`` feeds the report compare gate (slo_max_burn_rate).
    "slo": {"kind", "t", "objective", "window_s", "burn_rate"},
    # Serving anomaly watchdog transition (telemetry/alerts.py):
    # edge-triggered — one ``state="firing"`` record when a rule starts
    # firing (with its evidence fields and human ``message``), one
    # ``state="cleared"`` (with ``active_s``) when it stops; persisting
    # conditions emit nothing.  Rules: queue_growth, block_exhaustion
    # (with ``projected_dry_s``), accept_rate_collapse, compile_storm,
    # replica_flap.  ``severity`` is ``page`` | ``warn``.
    "alert": {"kind", "t", "rule", "state"},
    # Fleet control-plane decision (serving/controller.py, `bpe-tpu
    # control`, ISSUE 20): one record per controller action or hold.
    # ``action`` is ``rebalance`` (victim sessions moved via
    # /kv/export -> /kv/import), ``retune`` (router --prefill-threshold
    # adjusted to the live prompt mix), ``scale_up``/``scale_down``
    # (replica spawned/retired through the supervisor machinery), or
    # ``hold`` (the loop degraded to observe-only).  ``outcome`` is
    # ``ok`` | ``failed`` (after bounded retries) | ``observe_only``
    # (decided but not executed: --observe-only, or the named hold
    # reason) | ``held``.  ``breaker`` is the action-budget crash-loop
    # breaker state (``closed`` | ``tripped`` — a tripped controller
    # stops acting until restarted).  ``reason`` says why the decision
    # fired or why the loop is holding (``stale_evidence``,
    # ``partial_sweep``, ``fleet_unreachable``, ``breaker_tripped``);
    # ``target``/``params``/``attempts``/``dur_s`` ride along per action.
    "control": {"kind", "t", "action", "outcome", "breaker"},
    # Flight-recorder black-box dump (telemetry/flightrecorder.py): the
    # always-on decision ring of one ``component`` ("serve" | "route" |
    # "train" | "control"), flushed on a ``trigger`` — ``alert:<rule>``, ``watchdog_hang``,
    # ``nonfinite``, ``preemption``, ``manual`` (POST /debug/dump), or
    # ``sweep`` (the incident tool snapshotting a live ring).  ``events`` is
    # the ring contents oldest-first (each entry: ``event`` name, run-relative
    # ``t``, absolute ``time_unix``, plus the decision's own fields);
    # ``recorded``/``dropped`` are lifetime counters (dropped > 0 means the
    # ring wrapped).  Host-side context rides along per component: queue
    # depth, slot states, kvpool gauges, active alerts + history tail for
    # serving; step/rollback state for training.
    "blackbox": {
        "kind", "t", "time_unix", "component", "trigger", "events",
    },
    # Incident postmortem bundle summary (telemetry/incident.py, `bpe-tpu
    # incident`): one record per assembled bundle.  ``hosts`` is the per-host
    # sweep outcome table (url, online, dumps collected); ``timeline`` is the
    # merged cross-host event list, wall-clock-ordered by absolute
    # ``time_unix`` (each entry stamped with its source ``host``), optionally
    # filtered to one request id and capped (``timeline_truncated`` rides
    # along when capped).
    "incident": {"kind", "time_unix", "hosts", "timeline"},
    # Run trailer: record counts + clean verdict (spans.py Telemetry.footer).
    "footer": {"kind", "t", "record_counts"},
    # Step/val metrics (NO kind key): at least a step number plus one
    # metric value (loss or val_loss in practice).
    "metric": {"step"},
}


def layer_sort_key(label: str):
    """Natural ordering for the per-layer labels of ``dynamics`` records:
    ``layers.2`` before ``layers.10``, block layers before the top-level
    tensors (``lm_head``, ``ln_final``, ``token_embeddings``).  Shared by
    the report and monitor renderers so their tables always agree."""
    parts = label.split(".")
    if parts[0] == "layers" and len(parts) > 1 and parts[1].isdigit():
        return (0, int(parts[1]), label)
    return (1, 0, label)


def record_kind(record: dict) -> str:
    """The schema kind of a record: its ``kind`` field, or ``"metric"``
    for the kind-less step/val records."""
    return record.get("kind", "metric")


def validate_record(record: dict) -> list[str]:
    """Problems with one record against the documented schema (empty list =
    valid): unknown kind, or a required field missing.  Fields may be null
    (e.g. HBM stats on CPU) — required means *present*, not non-null."""
    kind = record_kind(record)
    schema = RECORD_SCHEMAS.get(kind)
    if schema is None:
        return [f"undocumented record kind {kind!r}"]
    missing = sorted(schema - record.keys())
    if missing:
        return [f"kind {kind!r} missing required fields: {', '.join(missing)}"]
    return []
