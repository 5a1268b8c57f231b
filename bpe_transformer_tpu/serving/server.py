"""Serving front end: `Request`/`Result` types, the blocking + streaming
`ServingEngine`, an offline batch mode, and the stdlib HTTP JSON endpoint
behind ``bpe-tpu serve``.

Layering (one thread owns the chip):

* transports (HTTP handler threads, `generate()` callers, the batch runner)
  only touch the `FifoScheduler` and per-request completion events;
* ONE worker thread runs the engine loop — admit queued requests into free
  slots (prefill), run a decode tick across every occupied slot, deliver
  sampled tokens to the per-request streams, retire finished slots — so the
  `SlotPoolEngine` itself never needs a lock;
* backpressure surfaces where it belongs: a full queue raises
  `QueueFullError` at submit time (HTTP 503), never blocking a transport.

Telemetry (PR-1 stream schema): per-request ``serve/queue_wait``,
``serve/prefill``, ``serve/decode`` span records, periodic
``{"kind": "engine"}`` records (active slots, queue depth, tokens/sec), and
the shared manifest/footer — all through one `telemetry.Telemetry`, so
``bpe-tpu report`` summarizes a serving run from the same JSONL it already
reads for training runs.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import queue
import threading
import time
import uuid
from pathlib import Path
from typing import Iterator

import numpy as np

from bpe_transformer_tpu.resilience.faults import FaultInjector
from bpe_transformer_tpu.serving.engine import SlotPoolEngine, TickEvent
from bpe_transformer_tpu.serving.kvpool.migrate import (
    supported_codecs as _supported_codecs,
)
from bpe_transformer_tpu.serving.metrics import ServingMetrics, render_prometheus
from bpe_transformer_tpu.serving.scheduler import (
    FifoScheduler,
    PrefillBudget,
    QueueFullError,
)
from bpe_transformer_tpu.telemetry.alerts import (
    AlertEngine,
    default_serving_rules,
)
from bpe_transformer_tpu.telemetry.flightrecorder import FlightRecorder
from bpe_transformer_tpu.telemetry.resources import (
    gc_pauses,
    install_compile_counter,
    install_gc_counter,
    sample_resources,
)
from bpe_transformer_tpu.telemetry.spans import Phase

__all__ = [
    "Request",
    "Result",
    "RequestHandle",
    "ServingEngine",
    "QueueFullError",
    "DuplicateRequestError",
    "make_http_server",
]

_STREAM_END = object()

#: What a ``tick`` record reads of the state-space layers where the engine,
#: or its cache kind, has none: the one place those zeros come from.
_TICK_COUNTS_OFF = {"ssm_tick_state_rows": 0, "ssm_chunk_tokens": 0, "ssm_chunk_rows": 0}


class DuplicateRequestError(ValueError):
    """A request id already in flight on this replica.  Subclasses
    ValueError for direct ``submit()`` callers, but the HTTP layer maps
    it to a retryable 503, NOT a 400: the canonical producer is a client
    retrying a router 504 with the same echoed X-Request-Id (the id it
    was told to keep for correlation) — that retry must fail over to a
    replica that ISN'T still running the original generation, not be
    judged a client error fleet-wide."""


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request (token-id based; transports tokenize)."""

    prompt_ids: tuple[int, ...]
    max_new_tokens: int = 128
    temperature: float = 1.0
    top_k: int | None = None
    top_p: float | None = None
    seed: int = 0
    stop_id: int | None = None
    #: Seconds the request may wait IN THE QUEUE before it is failed fast
    #: with ``finish_reason="deadline"`` (None: wait indefinitely).
    deadline_s: float | None = None
    #: Optional session key (multi-turn conversations): the fleet router
    #: hashes it to a sticky replica so follow-up turns land where the
    #: session's radix prefix blocks live.  The replica itself only
    #: carries it (request metadata) — affinity is a routing concern.
    session: str | None = None
    #: Disaggregated prefill (ISSUE 15): run the chunk machine, then —
    #: instead of entering decode — export the finished prefix as a KV
    #: migration payload (``Result.kv_payload``, finish_reason
    #: ``"migrated"``).  The ``/kv/export`` endpoint sets this; needs a
    #: paged engine.
    migrate: bool = False
    #: ``migrate`` only: comma list of wire codecs the IMPORTER accepts
    #: (the ``X-KV-Accept`` header on ``/kv/export``) — the export picks
    #: the best locally available one (``migrate.negotiate_codec``).
    #: None = no negotiation happened -> raw, so a pre-negotiation peer
    #: is never handed a frame it cannot open.
    kv_accept: str | None = None
    request_id: str = dataclasses.field(
        default_factory=lambda: uuid.uuid4().hex
    )


@dataclasses.dataclass(frozen=True)
class Result:
    """A finished request: generated ids + why it stopped + phase timings."""

    request_id: str
    token_ids: tuple[int, ...]
    finish_reason: str  # stop | length | deadline | cancelled | error | migrated
    queue_wait_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    #: ``finish_reason == "migrated"`` only: the serialized KV payload
    #: (serving/kvpool/migrate.py) another replica's ``/kv/import`` (or
    #: ``submit_import``) continues the generation from.
    kv_payload: bytes | None = None

    def timings(self) -> dict:
        return {
            "queue_wait_s": round(self.queue_wait_s, 6),
            "prefill_s": round(self.prefill_s, 6),
            "decode_s": round(self.decode_s, 6),
        }


class _Entry:
    """Worker-side state for one submitted request."""

    __slots__ = (
        "request", "tokens", "stream", "done", "result", "slot",
        "t_submit", "t_decode_start", "queue_wait_s", "prefill_s",
        "cancel_requested", "bucket", "t_prefill_start", "programs_before",
        "shared_tokens", "migrated_in", "t_chunks_queued",
    )

    def __init__(self, request: Request, t_submit: float):
        self.request = request
        self.tokens: list[int] = []
        #: The request's tokens on their way to its reader.  A C-level queue:
        #: since the worker runs a launch ahead it seldom blocks, so what a
        #: put and a reader's wake-up take of the interpreter lock is the
        #: worker's own time (PERF.md section 6, PR 37).
        self.stream: queue.SimpleQueue = queue.SimpleQueue()
        self.done = threading.Event()
        self.result: Result | None = None
        self.slot: int | None = None
        self.t_submit = t_submit
        self.t_decode_start = t_submit
        self.queue_wait_s = 0.0
        self.prefill_s = 0.0
        self.cancel_requested = False
        self.bucket: int | None = None  # prefill bucket, set at admission
        self.t_prefill_start = t_submit  # first chunk start (paged engine)
        self.programs_before = 0  # compile counter at admission (paged)
        self.shared_tokens = 0  # prefix-cache-reused prompt tokens (paged)
        self.migrated_in = False  # arrived as a KV graft (ISSUE 15)
        #: When the final chunk was queued with its token left unread (the
        #: paged engine's worker): the prefill lasts until that read.
        self.t_chunks_queued: float | None = None


class RequestHandle:
    """Caller-side view of an in-flight request."""

    def __init__(self, serving: "ServingEngine", entry: _Entry):
        self._serving = serving
        self._entry = entry

    @property
    def request_id(self) -> str:
        return self._entry.request.request_id

    def result(self, timeout: float | None = None) -> Result:
        """Block until the request finishes; raises TimeoutError."""
        if not self._entry.done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not done within {timeout}s"
            )
        return self._entry.result

    def tokens(self) -> Iterator[int]:
        """Stream token ids as the engine emits them (ends at completion)."""
        while True:
            item = self._entry.stream.get()
            if item is _STREAM_END:
                return
            yield item

    def cancel(self) -> None:
        self._serving.cancel(self.request_id)


class ServingEngine:
    """Continuous-batching serving: scheduler + slot pool + worker thread.

    Use as a context manager (or call :meth:`start`/:meth:`close`)::

        with ServingEngine(params, config, slots=8) as serving:
            result = serving.generate([1, 2, 3], max_new_tokens=16)
    """

    def __init__(
        self,
        params,
        config,
        *,
        tokenizer=None,
        slots: int = 8,
        max_queue: int = 64,
        max_wait_s: float = 0.0,
        prefill_buckets: tuple[int, ...] | None = None,
        min_bucket: int = 16,
        default_stop_id: int | None = None,
        default_max_new_tokens: int = 128,
        telemetry=None,
        engine_record_every_s: float = 1.0,
        idle_poll_s: float = 0.02,
        clock=time.monotonic,
        manifest: dict | None = None,
        paged: bool = False,
        block_size: int = 16,
        num_kv_blocks: int | None = None,
        prefill_chunk: int | None = None,
        prefill_token_budget: int | None = None,
        prefix_cache: bool = True,
        kv_dtype: str | None = None,
        weight_dtype: str | None = None,
        fused_sampling: bool = False,
        speculate_k: int = 0,
        draft_spec=None,
        alert_rules=None,
        role: str = "both",
        flightrecorder_capacity: int = 256,
    ):
        # Count XLA compiles (the engine's bucketed prefills included) into
        # the process-wide telemetry.resources counter before the first
        # program builds; and the collector's pauses, which stop the worker
        # whichever thread they fall to.
        install_compile_counter()
        install_gc_counter()
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f'role={role!r} must be "prefill", "decode", or "both"'
            )
        if role != "both" and not paged:
            raise ValueError(
                f"role={role!r} needs paged=True (KV migration lives in "
                "the block pool)"
            )
        if speculate_k and not paged:
            raise ValueError(
                "speculate_k needs paged=True (the verify pass scores "
                "through the paged scatter; the KV rewind lives in the "
                "block pool)"
            )
        if config.has_window_layers and not paged:
            raise ValueError(
                "a config with sliding-window layers is served by the paged "
                "engine (paged=True): its window pool group lives there"
            )
        if config.has_window_layers and (speculate_k or role != "both"):
            raise ValueError(
                "speculative decoding (its KV rewind) and the prefill/decode "
                "roles (KV migration) are not supported over window pool "
                "groups (ROADMAP: what cannot run yet)"
            )
        if config.attention_kind == "mla" and not paged:
            raise ValueError(
                "a config with latent attention is served by the paged "
                "engine (paged=True): its latent pool lives there"
            )
        if config.attention_kind == "mla" and (speculate_k or role != "both"):
            raise ValueError(
                "speculative decoding (a verify pass of several rows a slot) "
                "and the prefill/decode roles (KV migration ships K and V "
                "heads) are not supported over a latent pool (ROADMAP: what "
                "cannot run yet)"
            )
        if config.eva_block and not paged:
            raise ValueError(
                "a config with chunked linear attention is served by the "
                "paged engine (paged=True): its summary-and-window cache "
                "lives there"
            )
        if config.eva_block and (speculate_k or role != "both"):
            raise ValueError(
                "speculative decoding (a verify pass of several rows a slot "
                "would straddle a window's closing) and the prefill/decode "
                "roles (KV migration ships one chain of positions) are not "
                "supported over a summary-and-window cache (ROADMAP: what "
                "cannot run yet)"
            )
        if config.hybrid_block and not paged:
            raise ValueError(
                "a config with state-space layers is served by the paged "
                "engine (paged=True): its recurrent state rows live there"
            )
        if config.hybrid_block and (speculate_k or role != "both"):
            raise ValueError(
                "speculative decoding (its verify pass rewinds) and the "
                "prefill/decode roles (KV migration ships blocks of "
                "positions) are not supported over a recurrent state "
                "(ROADMAP: what cannot run yet)"
            )
        if speculate_k:
            from bpe_transformer_tpu.serving.spec.engine import SpecEngine

            if draft_spec is None:
                raise ValueError(
                    "speculate_k needs a draft_spec (DraftSpec or a "
                    "prebuilt DraftModel)"
                )
            self.engine = SpecEngine(
                params, config, draft=draft_spec, speculate_k=speculate_k,
                slots=slots, block_size=block_size,
                num_blocks=num_kv_blocks,
                prefill_buckets=prefill_buckets, min_bucket=min_bucket,
                prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
                kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                fused_sampling=fused_sampling,
            )
        elif paged:
            from bpe_transformer_tpu.serving.kvpool.paged_engine import (
                PagedEngine,
            )

            self.engine = PagedEngine(
                params, config, slots=slots, block_size=block_size,
                num_blocks=num_kv_blocks,
                prefill_buckets=prefill_buckets, min_bucket=min_bucket,
                prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
                kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                fused_sampling=fused_sampling,
            )
        else:
            self.engine = SlotPoolEngine(
                params, config, slots=slots,
                prefill_buckets=prefill_buckets, min_bucket=min_bucket,
                weight_dtype=weight_dtype, fused_sampling=fused_sampling,
            )
        self.paged = paged
        #: The worker runs the plain paged engine one launch ahead
        #: (`PagedEngine.launch` for tick n+1, then `collect` for tick n).
        #: The dense pool's tick and a speculative one read back what they
        #: launch: theirs is the composed ``tick()``.
        self._runs_ahead = paged and not speculate_k
        # The engine times its tick's dispatch/wait/emit phases on the
        # worker's clock, so they add up with the worker's own phases.
        self.engine.clock = clock
        #: Disaggregated-fleet role (ISSUE 15): ``"prefill"`` replicas run
        #: the chunk machine then stream finished prefixes out over
        #: ``/kv/export`` instead of ticking (plain /generate refused);
        #: ``"decode"`` replicas additionally accept grafts on
        #: ``/kv/import`` and — fed only imports — never compile a chunk
        #: program; ``"both"`` (default) serves everything.
        self.role = role
        #: Speculative decoding active (the engine is a SpecEngine): the
        #: stats/statusz/metrics surfaces grow the acceptance gauges and
        #: the engine-record cadence emits kind="spec" records.
        self.spec = bool(speculate_k)
        #: Always-on decision ring (telemetry/flightrecorder.py): every
        #: admit/park/reject/deadline/finish, migration, rewind, drain and
        #: worker-error decision lands here as host-side bookkeeping (zero
        #: device syncs — pinned by the fetch-count test), flushed as a
        #: kind="blackbox" dump on alert/manual/worker-error triggers.
        self.flightrecorder = FlightRecorder(
            "serve", capacity=flightrecorder_capacity, clock=clock
        )
        if paged:
            # Paged KV rewinds (speculative rejection rollbacks, partial
            # chains) are pool decisions too: the engine tees them in.
            self.engine.recorder = self.flightrecorder
        #: Chunked-prefill fairness (paged only): prefill tokens allowed
        #: between consecutive decode ticks (None = run chunks to
        #: completion, the dense engine's schedule).
        self._prefill_budget = PrefillBudget(
            prefill_token_budget if paged else None,
            recorder=self.flightrecorder,
        )
        #: Admissions parked on KV-block exhaustion (paged): retried in
        #: FIFO order before any newer queue pop, as decode retirements
        #: free blocks.
        self._admit_backlog: list[_Entry] = []
        #: Slots mid-chunked-prefill -> their entries (paged).
        self._prefill_entries: dict[int, _Entry] = {}
        #: Inbound KV grafts awaiting a slot/blocks, FIFO:
        #: ``(entry, payload_dict, payload_bytes_len, recv_unix)`` —
        #: fed by submit_import / adopt_migration (transport threads),
        #: drained by the worker ahead of fresh admissions.
        self._import_queue: collections.deque = collections.deque()
        self._import_lock = threading.Lock()
        #: Drain-evacuation targets: in-process peer ServingEngines the
        #: worker exports every queued + in-flight session to when a
        #: ``drain(evacuate_to=...)`` runs (round-robin).
        self._evacuate_peers: list = []
        self._evacuate_rr = 0
        #: Over-the-wire drain-evacuation targets (ISSUE 20): peer base
        #: URLs — queued requests replay as seeded ``/generate`` calls,
        #: in-flight slots export + relay to a peer's ``/kv/import``; the
        #: relay thread completes the original caller's handle with the
        #: peer's tokens (token-identical: same KV, same RNG state).
        self._evacuate_urls: list[str] = []
        #: Controller-initiated hot rebalancing (``POST /admin/evacuate``):
        #: pending ``(target_url, max_sessions, done_event, out_dict)``
        #: requests the worker consumes at the top of each step.
        self._rebalance_queue: collections.deque = collections.deque()
        self._relays_ok = 0
        self._relays_failed = 0
        self._rebalanced_out = 0
        #: Bounded retry policy for one payload relay (per-attempt HTTP
        #: timeout, exponential backoff between attempts).
        self.relay_attempts = 4
        self.relay_timeout_s = 600.0
        self.relay_backoff_s = 0.2
        #: Wire codec for migration payload exports (v2 frames): a comma
        #: list negotiated against the importer (``negotiate_codec``).
        #: zlib is stdlib, so every same-version peer decodes it.
        self.export_codec = "zstd,zlib"
        #: Fleet chaos harness (ISSUE 20): per-replica BT_FAULTS plan —
        #: no-op (cheap comparisons) unless the env var is set.
        self.faults = FaultInjector.from_env()
        self._decode_ticks = 0
        #: Replay-once idempotency on imports: key -> _Entry, so a retried
        #: ``/kv/import`` (response lost, connection dropped mid-reply)
        #: attaches to the original graft instead of double-grafting.
        self._idem_keys: collections.OrderedDict = collections.OrderedDict()
        self._idem_lock = threading.Lock()
        self.scheduler = FifoScheduler(
            max_queue=max_queue, max_wait_s=max_wait_s, clock=clock
        )
        self.tokenizer = tokenizer
        self.default_stop_id = default_stop_id
        self.default_max_new_tokens = default_max_new_tokens
        self.manifest = manifest
        #: Live counter/histogram aggregate behind /metrics and stats() —
        #: fed from the same measurements the serve/* spans carry.
        self.metrics = ServingMetrics(clock=clock)
        self._telemetry = telemetry
        self._record_every_s = engine_record_every_s
        self._idle_poll_s = idle_poll_s
        self._clock = clock
        self._t0 = clock()
        self._last_record_t = self._t0
        self._last_record_tokens = 0
        #: The open tick period (see :meth:`_open_period`): what the worker
        #: has spent, phase by phase, since the last decode tick's end.
        self._period = self._open_period(self._t0)
        self._entries: dict[str, _Entry] = {}
        self._entries_lock = threading.Lock()
        self._slot_entries: dict[int, _Entry] = {}
        #: The last tick's tokens, each with its request and finish reason,
        #: held until the next program is in the device's queue
        #: (:meth:`_publish`).
        self._unpublished: list[tuple[_Entry, int, str | None]] = []
        #: Per-request trace ring (newest last): the finished requests'
        #: phase timelines behind /statusz "recent_requests" — the same
        #: numbers the serve/* spans carry, queryable from a live server
        #: without tailing the JSONL.
        self._recent: collections.deque = collections.deque(maxlen=32)
        #: Serving anomaly watchdog (telemetry/alerts.py): fed a gauge
        #: sample on the engine-record cadence INDEPENDENT of whether a
        #: telemetry sink exists — /statusz must show active alerts on a
        #: server run without --metrics-jsonl.  Transitions (fire/clear)
        #: are emitted as kind="alert" records when a sink is attached.
        self._alerts = AlertEngine(
            alert_rules
            if alert_rules is not None
            else default_serving_rules()
        )
        self._requests_finished = 0
        self._thread: threading.Thread | None = None
        self._running = False
        self._draining = False
        self._worker_error: BaseException | None = None

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "ServingEngine":
        if self._thread is not None:
            return self
        self._running = True
        self._t0 = self._clock()
        self._last_record_t = self._t0
        self._period = self._open_period(self._t0)
        self._thread = threading.Thread(
            target=self._run, name="serving-engine", daemon=True
        )
        self._thread.start()
        return self

    def drain(
        self, timeout_s: float = 30.0, evacuate_to=None, evacuate_urls=None,
    ) -> bool:
        """Graceful shutdown, phase 1: stop ADMITTING (new submits raise
        ``RuntimeError`` -> HTTP 503) but keep the worker running until
        every queued and in-flight request finishes — the SIGTERM path of
        ``bpe-tpu serve`` (preemption must not cancel work the engine can
        still complete).  Returns True when fully drained, False on
        timeout (the caller's ``close()`` then cancels the stragglers).

        ``evacuate_to`` (ISSUE 15) turns drain into session *evacuation*:
        a list of in-process peer ``ServingEngine`` replicas the worker
        migrates every queued AND in-flight session to — mid-generation
        slots are exported as KV payloads and grafted onto a peer, which
        continues the generation bit-for-bit and completes the original
        caller's handle — so draining a loaded replica finishes in
        payload-transfer time instead of longest-generation time, with
        zero failed requests and zero token divergence.

        ``evacuate_urls`` (ISSUE 20) is the cross-process form: peer base
        URLs.  Queued (never-admitted) requests replay on a peer as
        seeded ``/generate`` calls; in-flight sessions export and relay
        to a peer's ``/kv/import`` with an idempotency key + bounded
        retries — the relay thread completes the original caller's
        handle with the peer's returned tokens, so the caller's open
        connection never notices the replica it was talking to left."""
        if evacuate_to:
            peers = [p for p in evacuate_to if p.accepting_imports()]
            self._evacuate_peers = peers
        if evacuate_urls:
            self._evacuate_urls = [u.rstrip("/") for u in evacuate_urls]
        self._draining = True
        self.flightrecorder.record(
            "drain",
            queue_depth=self.scheduler.depth,
            active_slots=self.engine.active_count,
            evacuating=bool(self._evacuate_peers or self._evacuate_urls),
        )
        if self._telemetry is not None:
            self._telemetry.event(
                "serve_drain",
                queue_depth=self.scheduler.depth,
                active_slots=self.engine.active_count,
                evacuating=bool(self._evacuate_peers or self._evacuate_urls),
            )
        deadline = self._clock() + timeout_s
        while True:
            # The entries registry is the superset of unfinished work:
            # queue depth and active_count both read 0 for a request the
            # worker has popped but not yet slotted (it sits in a
            # multi-second prefill compile exactly when a drain is likely
            # to ask) — _finish() is the only thing that unregisters.
            with self._entries_lock:
                pending = len(self._entries)
            if (
                not pending
                and not self.engine.active_count
                and not self.scheduler.depth
            ):
                return True
            if (
                self._worker_error is not None
                or not self._running
                or self._clock() >= deadline
            ):
                return False
            time.sleep(min(self._idle_poll_s, 0.05))

    def close(self) -> None:
        """Stop the worker; in-flight and queued requests finish as
        ``cancelled``."""
        self._running = False
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self._worker_error is None:
            self._settle()  # what the device had finished is not cancelled
        drain = self.scheduler.pop_ready(self.scheduler.max_queue)
        for qe in drain.admit + drain.expired + drain.cancelled:
            self._finish(qe.item, "cancelled")
        for slot in list(self._slot_entries):
            entry = self._slot_entries.pop(slot)
            self.engine.release(slot)
            self._finish(entry, "cancelled")
        for slot in list(self._prefill_entries):
            entry = self._prefill_entries.pop(slot)
            self.engine.release(slot)
            self._finish(entry, "cancelled")
        for entry in self._admit_backlog:
            self._finish(entry, "cancelled")
        self._admit_backlog = []
        with self._import_lock:
            imports = [item[0] for item in self._import_queue]
            self._import_queue.clear()
        for entry in imports:
            self._finish(entry, "cancelled")
        if self._telemetry is not None:
            self._telemetry.footer(
                clean=self._worker_error is None,
                requests=self._requests_finished,
                ticks=self.engine.ticks,
                tokens=self.engine.tokens_emitted,
            )

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------- transport side

    def submit(self, request: Request) -> RequestHandle:
        """Validate + enqueue; raises `QueueFullError` (backpressure) or
        ``ValueError`` (prompt the context window cannot serve)."""
        if self._worker_error is not None:
            raise RuntimeError(
                "serving engine worker died"
            ) from self._worker_error
        if not self._running:
            raise RuntimeError("serving engine is not running (use start())")
        if self._draining:
            raise RuntimeError(
                "serving engine is draining (shutting down); not accepting "
                "new requests"
            )
        if request.migrate and not self.paged:
            raise ValueError(
                "migrate-at-prefill needs a paged engine (the KV payload "
                "is a block chain)"
            )
        if self.role == "prefill" and not request.migrate:
            # A prefill-role replica never ticks: a plain generate would
            # park in a slot forever.  503 (RuntimeError at the HTTP
            # layer) so a misdirected client fails over, not a 400.
            raise RuntimeError(
                "prefill-role replica serves /kv/export only (finished "
                "prefixes stream out as KV payloads; decode lives on "
                "decode-role replicas)"
            )
        plen = len(request.prompt_ids)
        ctx = self.engine.config.context_length
        if plen < 1:
            raise ValueError("prompt must contain at least one token")
        if plen > ctx - 1:
            raise ValueError(
                f"prompt of {plen} tokens leaves no room to generate in a "
                f"context of {ctx}"
            )
        if request.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {request.max_new_tokens}"
            )
        if self.paged:
            # A request whose worst-case block chain exceeds the whole pool
            # can NEVER be admitted: fail fast at the transport instead of
            # deadlocking the admission backlog.
            need = self.engine.blocks_needed(plen, request.max_new_tokens)
            if need > self.engine.allocator.usable_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks; the pool holds "
                    f"{self.engine.allocator.usable_blocks}"
                )
        entry = _Entry(request, self._clock())
        with self._entries_lock:
            if request.request_id in self._entries:
                # Client-supplied ids (X-Request-Id) key the entries
                # registry and the trace streams: a duplicate in flight
                # would orphan the first caller's completion event.
                raise DuplicateRequestError(
                    f"request id {request.request_id!r} is already in "
                    "flight on this replica"
                )
            self._entries[request.request_id] = entry
        try:
            self.scheduler.submit(
                entry,
                request_id=request.request_id,
                deadline_s=request.deadline_s,
            )
        except BaseException as exc:
            # Any enqueue failure (backpressure, a bad deadline value, ...)
            # must unregister the entry — a leaked entry holds a Queue and
            # an Event forever.
            with self._entries_lock:
                self._entries.pop(request.request_id, None)
            if isinstance(exc, QueueFullError):
                self.metrics.on_reject()
                self.flightrecorder.record(
                    "reject",
                    request_id=request.request_id,
                    queue_depth=self.scheduler.depth,
                )
            raise
        self.metrics.on_submit()
        return RequestHandle(self, entry)

    def generate(
        self,
        prompt_ids,
        *,
        max_new_tokens: int | None = None,
        temperature: float = 1.0,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int = 0,
        stop_id: int | None = None,
        deadline_s: float | None = None,
        session: str | None = None,
        request_id: str | None = None,
        migrate: bool = False,
        kv_accept: str | None = None,
        timeout: float | None = None,
    ) -> Result:
        """Blocking one-call generation.  ``request_id`` adopts a
        caller-supplied trace id (the router's ``X-Request-Id``) so one id
        stitches router hops, serve spans, and engine slot state.
        ``migrate=True`` is the /kv/export path: the result carries the
        finished prefix as a KV payload instead of a full generation."""
        kwargs = {} if request_id is None else {"request_id": request_id}
        handle = self.submit(
            Request(
                prompt_ids=tuple(int(t) for t in prompt_ids),
                max_new_tokens=(
                    self.default_max_new_tokens
                    if max_new_tokens is None
                    else max_new_tokens
                ),
                temperature=temperature,
                top_k=top_k,
                top_p=top_p,
                seed=seed,
                stop_id=self.default_stop_id if stop_id is None else stop_id,
                deadline_s=deadline_s,
                session=session,
                migrate=migrate,
                kv_accept=kv_accept,
                **kwargs,
            )
        )
        return handle.result(timeout)

    # ------------------------------------------------------- KV migration

    def accepting_imports(self) -> bool:
        """Whether this replica can graft KV payloads right now (paged,
        not prefill-role, worker alive, not draining)."""
        return (
            self.paged
            and self.role != "prefill"
            and self._running
            and not self._draining
            and self._worker_error is None
        )

    def submit_import(
        self,
        payload_bytes: bytes,
        *,
        idempotency_key: str | None = None,
    ) -> RequestHandle:
        """Accept a serialized KV migration payload (the ``/kv/import``
        body): validate it against this engine's geometry, register the
        request, and queue the graft for the worker.  The handle resolves
        with the COMPLETE generation — tokens emitted before the
        migration (carried in the payload) plus everything decoded here.

        ``idempotency_key`` (ISSUE 20, the ``X-Idempotency-Key`` header)
        makes the graft exactly-once under retries: a repeated key —
        whether the original graft is queued, decoding, or already
        finished — attaches to the original entry and resolves with ITS
        result instead of grafting a second copy.  The sender keeps one
        key per exported payload across every retry of that transfer.

        Raises ``ValueError`` (bad payload / geometry mismatch -> 400),
        ``QueueFullError`` (backpressure -> 503),
        :class:`DuplicateRequestError`, or ``RuntimeError`` (not
        accepting -> 503)."""
        from bpe_transformer_tpu.serving.kvpool.migrate import (
            payload_from_bytes,
        )

        if self._worker_error is not None:
            raise RuntimeError(
                "serving engine worker died"
            ) from self._worker_error
        if not self._running:
            raise RuntimeError("serving engine is not running (use start())")
        if self._draining:
            raise RuntimeError("serving engine is draining; not accepting")
        if not self.paged:
            raise RuntimeError("KV import needs a paged engine")
        if self.role == "prefill":
            raise RuntimeError(
                "prefill-role replica does not accept KV imports"
            )
        if idempotency_key:
            with self._idem_lock:
                known = self._idem_keys.get(idempotency_key)
            if known is not None:
                return RequestHandle(self, known)
        payload = payload_from_bytes(payload_bytes)
        meta = payload["meta"]
        # Full structural validation at the TRANSPORT: a corrupt payload
        # must 400 here, never reach the worker thread.
        self.engine.validate_import_payload(payload)
        request = Request(
            prompt_ids=tuple(int(t) for t in meta["prompt"]),
            max_new_tokens=max(int(meta["max_new_tokens"]), 1),
            temperature=float(meta["temperature"]),
            seed=int(meta["seed"]),
            stop_id=meta["stop_id"],
            deadline_s=meta.get("deadline_s"),
            session=meta.get("session"),
            request_id=meta.get("request_id") or uuid.uuid4().hex,
        )
        entry = _Entry(request, self._clock())
        self._entry_from_meta(entry, meta)
        if idempotency_key:
            # Claim-or-attach under one lock: a concurrent duplicate that
            # raced past the cheap pre-parse check attaches to whichever
            # entry claimed first — the graft below runs exactly once per
            # key.  The claim survives the entry finishing (bounded LRU),
            # so a retry whose original already completed gets the cached
            # result instead of a second graft.
            with self._idem_lock:
                known = self._idem_keys.get(idempotency_key)
                if known is not None:
                    return RequestHandle(self, known)
                self._idem_keys[idempotency_key] = entry
                while len(self._idem_keys) > 4096:
                    self._idem_keys.popitem(last=False)
        try:
            with self._entries_lock:
                if request.request_id in self._entries:
                    raise DuplicateRequestError(
                        f"request id {request.request_id!r} is already in "
                        "flight on this replica"
                    )
                self._entries[request.request_id] = entry
            try:
                # Capacity check + append under ONE lock hold: each queued
                # item carries a whole decoded KV payload, so a racy check
                # would let concurrent imports blow the memory bound the
                # backpressure exists to enforce.
                with self._import_lock:
                    if len(self._import_queue) >= self.scheduler.max_queue:
                        raise QueueFullError(
                            f"import queue full ({self.scheduler.max_queue})"
                        )
                    self._import_queue.append(
                        (entry, payload, len(payload_bytes), time.time())
                    )
            except BaseException:
                with self._entries_lock:
                    self._entries.pop(request.request_id, None)
                raise
        except BaseException:
            if idempotency_key:
                # A failed graft must not poison the key: the sender's
                # retry (same key) deserves a fresh attempt.
                with self._idem_lock:
                    if self._idem_keys.get(idempotency_key) is entry:
                        del self._idem_keys[idempotency_key]
            raise
        self.metrics.on_submit()
        self.scheduler.notify()
        return RequestHandle(self, entry)

    def adopt_migration(self, entry: _Entry, payload) -> None:
        """In-process drain evacuation, receiving side: adopt a peer's
        live ``_Entry`` (its stream/done handles stay with the original
        caller) and queue its KV payload for grafting.  Called from the
        EVACUATING replica's worker thread.  ``payload`` is either the
        serialized bytes or the already-parsed dict — queued grafts move
        between peers without a pointless reserialize/reparse round
        trip of multi-MB KV rows."""
        from bpe_transformer_tpu.serving.kvpool.migrate import (
            payload_from_bytes,
            payload_nbytes,
        )

        if not self.accepting_imports():
            raise RuntimeError("replica is not accepting imports")
        if isinstance(payload, (bytes, bytearray)):
            nbytes = len(payload)
            payload = payload_from_bytes(payload)
        else:
            nbytes = payload_nbytes(payload)
        self.engine.validate_import_payload(payload)
        with self._entries_lock:
            if entry.request.request_id in self._entries:
                raise DuplicateRequestError(
                    f"request id {entry.request.request_id!r} already in "
                    "flight on the evacuation target"
                )
            self._entries[entry.request.request_id] = entry
        with self._import_lock:
            self._import_queue.append(
                (entry, payload, nbytes, time.time())
            )
        self.scheduler.notify()

    def adopt_entry(self, entry: _Entry) -> None:
        """In-process drain evacuation for NOT-YET-ADMITTED requests: the
        peer's queued entry re-enters this replica's scheduler whole (same
        stream/done handles, same request id)."""
        if not self.accepting_imports():
            raise RuntimeError("replica is not accepting new requests")
        with self._entries_lock:
            if entry.request.request_id in self._entries:
                raise DuplicateRequestError(
                    f"request id {entry.request.request_id!r} already in "
                    "flight on the evacuation target"
                )
            self._entries[entry.request.request_id] = entry
        try:
            self.scheduler.submit(
                entry,
                request_id=entry.request.request_id,
                deadline_s=entry.request.deadline_s,
            )
        except BaseException:
            with self._entries_lock:
                self._entries.pop(entry.request.request_id, None)
            raise
        self.metrics.on_submit()

    @staticmethod
    def _entry_from_meta(entry: _Entry, meta: dict) -> None:
        """Restore the serving-layer request state a payload carries:
        tokens already emitted and the phase timings accrued before the
        migration (so Result timings stay end-to-end)."""
        entry.tokens = [int(t) for t in meta.get("emitted") or []]
        entry.queue_wait_s = float(meta.get("queue_wait_s") or 0.0)
        entry.prefill_s = float(meta.get("prefill_s") or 0.0)
        entry.bucket = meta.get("bucket")
        entry.shared_tokens = int(meta.get("shared_tokens") or 0)
        entry.migrated_in = True

    def stream(self, request: Request) -> Iterator[int]:
        """Submit and yield token ids as they are generated."""
        return self.submit(request).tokens()

    def cancel(self, request_id: str) -> bool:
        """Cancel a queued or in-flight request."""
        if self.scheduler.cancel(request_id):
            return True
        with self._entries_lock:
            entry = self._entries.get(request_id)
        if entry is not None and not entry.done.is_set():
            entry.cancel_requested = True
            return True
        return False

    def decode_roofline(self) -> dict:
        """The decode tick's analytic roofline at CURRENT occupancy
        (`telemetry.attribution.decode_tick_roofline`): per tick, the
        weight sweep is the engine's resident matmul-weight bytes
        (int8-halved under ``weight_dtype="int8"``), the KV stream is the
        live positions times the per-position footprint (int8-halved
        under ``kv_dtype="int8"``), and activations are a documented
        estimate — transient block tensors plus the vocab-sized tail
        (unfused: logits + masked-logits + gumbel round trips; fused:
        only the caller-side gumbel tensor the kernel reads)."""
        import jax

        from bpe_transformer_tpu.telemetry.attribution import (
            decode_tick_roofline,
        )
        from bpe_transformer_tpu.utils.flops import decode_tick_flops

        engine = self.engine
        config = engine.config
        active = engine.active_count
        positions = engine._positions
        live = int(((positions + 1) * engine._active).sum())
        act_itemsize = np.dtype(config.activation_dtype).itemsize
        # ~12 d_model-sized transients per token per block (q/k/v/att/
        # norms/ffn intermediates at d_ff ~ 2.7 d) — an estimate, labeled
        # as such.  The vocab-sized tail: unfused pays ~3 (slots, vocab)
        # f32 round trips (logits, filter_logits' masked copy, the
        # categorical gumbel; the search passes are extra, uncounted);
        # fused still pays ONE — the caller-side gumbel tensor the kernel
        # reads (drawing it in-kernel would delete it; noted, not done) —
        # so fusion shrinks the term 3x, never to zero.
        act_bytes = active * config.num_layers * 12 * config.d_model * (
            act_itemsize
        )
        vocab_trip = 2 * active * config.vocab_size * 4
        act_bytes += vocab_trip if engine.fused_sampling else 3 * vocab_trip
        row = decode_tick_roofline(
            flops=decode_tick_flops(config, active, live),
            weight_bytes=engine.tick_weight_bytes,
            kv_bytes=engine.kv_bytes_per_token * (live + active),
            act_bytes=act_bytes,
            device_kind=jax.devices()[0].device_kind,
        )
        row.update(
            {
                "active_slots": active,
                "live_positions": live,
                "weight_dtype": engine.weight_dtype,
                "fused_sampling": engine.fused_sampling,
            }
        )
        return row

    def stats(self) -> dict:
        """Engine/queue gauges + the live request counters — the same
        aggregate ``GET /metrics`` renders, reachable offline.  A paged
        engine adds the kvpool gauges (block occupancy, prefix-cache
        hit/miss counters, chunked-prefill queue depth)."""
        with self._import_lock:
            import_backlog = len(self._import_queue)
        stats = {
            "engine_kind": (
                "spec" if self.spec else "paged" if self.paged else "dense"
            ),
            "role": self.role,
            "import_backlog": import_backlog,
            "slots": self.engine.n_slots,
            "active_slots": self.engine.active_count,
            "queue_depth": self.scheduler.depth,
            "ticks": self.engine.ticks,
            # Ticks whose sampler ran its top-k / its nucleus search.
            "sample_topk_ticks": self.engine.sample_topk_ticks,
            "sample_topp_ticks": self.engine.sample_topp_ticks,
            "tokens_emitted": self.engine.tokens_emitted,
            "requests_finished": self._requests_finished,
            "compiled_programs": self.engine.compiled_programs(),
            "prefill_buckets": list(self.engine.buckets),
            # Quantized-decode gauges (ISSUE 11): what the weights weigh,
            # at what width, and what one tick streams — plus the
            # analytic tick roofline the report/compare gate reads.
            "weight_dtype": self.engine.weight_dtype,
            "params_bytes": self.engine.params_bytes,
            "tick_weight_bytes": self.engine.tick_weight_bytes,
            "fused_sampling": self.engine.fused_sampling,
            "decode_roofline": self.decode_roofline(),
            "alerts_firing": len(self._alerts.active()),
            **self.metrics.snapshot(),
        }
        # The interpreter's collector, process-wide: every thread stands
        # still for a collection.
        stats.update(gc_pauses())
        if self.paged:
            stats.update(self.engine.gauges())
            stats["block_size"] = self.engine.block_size
            stats["kv_dtype"] = self.engine.kv_dtype
            stats["admit_backlog"] = len(self._admit_backlog)
        return stats

    def statusz(self) -> dict:
        """The ``GET /statusz`` payload: run manifest, uptime, compile
        accounting (per-engine program count + process-wide compile
        events), per-slot state, queue depth, the recent-request trace
        ring (per-request phase timelines), and the last-error ring."""
        resources = sample_resources()
        with self._import_lock:
            import_backlog = len(self._import_queue)
        page = {
            "manifest": self.manifest,
            "uptime_s": round(self.metrics.uptime_s(), 3),
            "engine_kind": (
                "spec" if self.spec else "paged" if self.paged else "dense"
            ),
            # Disaggregated-fleet role (ISSUE 15): the router partitions
            # the fleet off this field — prefill-role replicas take
            # /kv/export only, decode-role replicas take imports.
            "role": self.role,
            "migrations_out": self.metrics.migrations_out,
            "migrations_in": self.metrics.migrations_in,
            "import_backlog": import_backlog,
            # Wire codecs this replica can DECODE (v2 payloads), best
            # first — what a migration sender negotiates against.
            "kv_accept": ",".join(_supported_codecs()),
            # Over-the-wire session moves (ISSUE 20): relayed out OK /
            # failed after retries, and controller-initiated rebalances.
            "relays_ok": self._relays_ok,
            "relays_failed": self._relays_failed,
            "rebalanced_out": self._rebalanced_out,
            # The fleet router reads these to route around a replica that
            # is shutting down (PR-5 drain) or whose worker died, and to
            # weight by free capacity.  Load is reported as OCCUPANCY, not
            # decode activity: a paged slot mid-chunked-prefill is busy,
            # and a block-starved parked admission is queued work — a
            # replica saturated with prefills must not look idle.
            "draining": self._draining,
            "speculate_k": self.engine.k if self.spec else None,
            "weight_dtype": self.engine.weight_dtype,
            "params_bytes": self.engine.params_bytes,
            "fused_sampling": self.engine.fused_sampling,
            "decode_roofline": self.decode_roofline(),
            "compiled_programs": self.engine.compiled_programs(),
            "compile_events": resources["compile_events"],
            "prefill_buckets": list(self.engine.buckets),
            "queue_depth": (
                self.scheduler.depth + len(self._admit_backlog)
                + import_backlog
            ),
            "slots": self.engine.n_slots,
            "active_slots": self.engine.n_slots - self.engine.free_slots,
            "requests_finished": self._requests_finished,
            "worker_alive": self._thread is not None
            and self._worker_error is None,
            "slot_states": self.engine.slot_states(),
            # Newest-last ring of finished request timelines (request_id +
            # queue_wait/prefill/decode + bucket): the per-request trace
            # view, live, without tailing the telemetry JSONL.
            "recent_requests": list(self._recent),
            # Anomaly-watchdog verdicts (telemetry/alerts.py): the
            # currently-firing rules with their evidence — what the fleet
            # aggregator folds and an operator's first question answered.
            "alerts": self._alerts.active(),
            # Last-N firing/cleared transitions with timestamps: an alert
            # that cleared five minutes ago is still the answer to "what
            # happened?" — active() alone forgets it.
            "alert_history": self._alerts.history(16),
            # Decision-ring counters (GET /debug/flightrecorder holds the
            # ring itself; the operator page just shows it is alive).
            "flightrecorder": self.flightrecorder.stats(),
            # Cumulative seconds of the worker per phase of its tick
            # period: waiting on the device, or on its own host?
            "worker_phase_seconds": {
                phase: round(seconds, 6)
                for phase, seconds in self.metrics.worker_phase_seconds.items()
            },
            # The worker thread's CPU seconds, and what of its own phases
            # it was off the CPU: the interpreter lock, the host's scheduler
            # or a sleeping runtime call - not device time.
            "worker_cpu_seconds": round(self.metrics.worker_cpu_seconds, 6),
            "worker_offcpu_seconds": round(
                self.metrics.worker_offcpu_seconds, 6
            ),
            "resources": resources,
            "last_errors": self.metrics.last_errors(),
        }
        if self.paged:
            page["kvpool"] = {
                **self.engine.gauges(),
                "block_size": self.engine.block_size,
                "kv_dtype": self.engine.kv_dtype,
                "admit_backlog": len(self._admit_backlog),
            }
        return page

    def prometheus_metrics(self) -> str:
        """The ``GET /metrics`` body (Prometheus text exposition)."""
        return render_prometheus(
            self.metrics, self.stats(), sample_resources()
        )

    # ------------------------------------------------------------ batch mode

    def run_batch(self, prompts: list, **knobs) -> list[Result]:
        """Offline batch: submit every prompt (waiting out backpressure
        instead of failing) and return results in input order."""
        handles: list[RequestHandle] = []
        for prompt in prompts:
            while True:
                try:
                    handles.append(
                        self.submit(
                            Request(
                                prompt_ids=tuple(int(t) for t in prompt),
                                **{
                                    "max_new_tokens": self.default_max_new_tokens,
                                    "stop_id": self.default_stop_id,
                                    **knobs,
                                },
                            )
                        )
                    )
                    break
                except QueueFullError:
                    time.sleep(0.005)  # the worker is draining the queue
        return [h.result() for h in handles]

    def serve_batch_file(
        self, prompts_path, output_path, **knobs
    ) -> list[Result]:
        """Offline file mode: one prompt per input line -> one JSONL result
        line per prompt (input order), tokenizing/detokenizing with the
        attached tokenizer."""
        if self.tokenizer is None:
            raise ValueError("batch file mode needs a tokenizer")
        lines = [
            ln
            for ln in Path(prompts_path).read_text(
                encoding="utf-8"
            ).splitlines()
            if ln.strip()
        ]
        prompts = [self.tokenizer.encode(ln) for ln in lines]
        results = self.run_batch(prompts, **knobs)
        with open(output_path, "w", encoding="utf-8") as f:
            for text, result in zip(lines, results):
                ids = list(result.token_ids)
                if result.finish_reason == "stop":
                    ids = ids[:-1]  # don't render the stop token itself
                f.write(
                    json.dumps(
                        {
                            "prompt": text,
                            "completion": self.tokenizer.decode(ids),
                            "finish_reason": result.finish_reason,
                            "n_tokens": len(result.token_ids),
                            **result.timings(),
                        }
                    )
                    + "\n"
                )
        return results

    # ---------------------------------------------------------- worker loop

    def _run(self) -> None:
        try:
            while self._running:
                # The outer label: what falls between two phases of an
                # iteration (a tick record's ``other_s``) lies under it.
                with self._phase("step"):
                    worked = self._step()
                if not worked:
                    with self._phase("idle_wait") as idle:
                        self.scheduler.wait_for_work(self._idle_poll_s)
                    self._period["idle_s"] += idle.dur_s
        except BaseException as exc:  # noqa: BLE001 — fail loudly, unblock callers
            self._worker_error = exc
            self._running = False
            try:
                self._settle()  # what the queued launches finished did not fail
            except Exception:  # noqa: BLE001 — the engine itself is what failed
                self._publish()
            self.metrics.record_error(repr(exc), source="worker")
            self.flightrecorder.record("worker_error", error=repr(exc))
            if self._telemetry is not None:
                self._telemetry.event("serve_worker_error", error=repr(exc))
            # A dead worker is a terminal incident: flush the decision ring
            # while the evidence is still warm (force past the cooldown).
            self.blackbox_dump("worker_error", force=True)
            for slot in list(self._slot_entries):
                entry = self._slot_entries.pop(slot)
                self.engine.release(slot)
                self._finish(entry, "error")
            for slot in list(self._prefill_entries):
                entry = self._prefill_entries.pop(slot)
                self._finish(entry, "error")
            for entry in self._admit_backlog:
                self._finish(entry, "error")
            self._admit_backlog = []
            with self._import_lock:
                dead_imports = [item[0] for item in self._import_queue]
                self._import_queue.clear()
            for entry in dead_imports:
                self._finish(entry, "error")
            # Every other registered request must unblock too — queued ones
            # AND ones popped for admission when the step raised: their
            # callers are parked on done.wait() and nothing else will run
            # the queue again.  (_finish is idempotent, so sweeping the
            # registry after the explicit drains is safe.)
            drain = self.scheduler.pop_ready(self.scheduler.max_queue)
            for qe in drain.admit + drain.expired + drain.cancelled:
                self._finish(qe.item, "error")
            with self._entries_lock:
                leftover = list(self._entries.values())
            for entry in leftover:
                self._finish(entry, "error")

    def _step(self) -> bool:
        """One engine-loop iteration: cancellations, admissions, chunked
        prefill under the per-tick token budget (paged), then a decode
        tick.  Returns whether any work happened."""
        worked = False

        # Drain evacuation (ISSUE 15): once draining with peers attached,
        # every queued and in-flight session leaves as a KV payload (or a
        # whole queue entry) before anything else runs this iteration.
        if self._draining and (self._evacuate_peers or self._evacuate_urls):
            self._settle()  # a session leaves with its stream up to date
            worked |= self._evacuate_step()

        # Controller-initiated hot rebalancing (ISSUE 20): export victim
        # sessions and relay them to the requested peer without draining.
        if self._rebalance_queue:
            self._settle()
            worked |= self._rebalance_step()

        with self._phase("admit") as admit:
            worked |= self._admit_step()
        self._period["admit_s"] += admit.dur_s

        worked |= self._advance_prefills()

        launched = self.engine.active_count > 0
        if launched:
            # Chaos hook: SIGKILL-mid-decode fires here, between slots
            # holding live KV and the tick that would advance them — the
            # worst instant a replica can die.
            self._decode_ticks += 1
            self.faults.at_decode_tick(self._decode_ticks)
        if self._runs_ahead and (launched or self.engine.unread):
            # Tick n+1 goes into the device's queue before tick n is read;
            # so does whatever a final chunk of this iteration left unread
            # (its slot's first token).  Only the newest launch stays
            # unread, and not even that one where nothing will follow it.
            self.engine.launch()
            events = []
            while self.engine.unread > int(
                launched and self.engine.active_count > 0
            ):
                events += self.engine.collect()
            self._deliver(events)
            self._publish()  # the device has the next launch to run meanwhile
        elif launched:
            events = self.engine.tick(dispatched=self._publish)
            self._deliver(events)
        else:
            self._publish()  # no program to publish behind
        if launched:
            tick = self.engine.last_tick_s  # (dispatch, wait, emit)
            self.metrics.on_decode_seconds(sum(tick))
            self._close_period(tick, n_events=len(events))
            worked = True
        self._maybe_emit_engine_record()
        return worked

    def _settle(self) -> None:
        """Read every launch the engine has queued and not read, take the
        tokens to their requests and publish them: before a session leaves
        (evacuation, rebalancing), at shutdown and on a worker error."""
        if self._runs_ahead:
            self.engine.flush()
            self._deliver(self.engine.collect())
        self._publish()

    def _admit_step(self) -> bool:
        """The admission part of one iteration (the ``serve/admit`` phase):
        cancellations, backlog expiry, inbound grafts, the scheduler pop and
        every admission.  Returns whether any work happened."""
        worked = False
        # In-flight cancellations retire their slots before the next tick
        # — decoding slots, slots mid-chunked-prefill, and block-starved
        # parked admissions alike.
        for slot, entry in list(self._slot_entries.items()):
            if entry.cancel_requested:
                del self._slot_entries[slot]
                self.engine.release(slot)
                self._finish(entry, "cancelled")
                worked = True
        for slot, entry in list(self._prefill_entries.items()):
            if entry.cancel_requested:
                del self._prefill_entries[slot]
                self.engine.release(slot)
                self._finish(entry, "cancelled")
                worked = True
        if self._admit_backlog:
            now = self._clock()
            kept = []
            for entry in self._admit_backlog:
                deadline = entry.request.deadline_s
                if entry.cancel_requested:
                    self._finish(entry, "cancelled")
                    worked = True
                elif (
                    deadline is not None
                    and now >= entry.t_submit + deadline
                ):
                    # The deadline contract follows the request out of the
                    # scheduler: a block-starved parked admission expires
                    # exactly like a queued one would.
                    self._finish(entry, "deadline")
                    worked = True
                else:
                    kept.append(entry)
            self._admit_backlog = kept

        # Inbound KV grafts land BEFORE fresh admissions: migrated work is
        # the fleet's oldest (it already paid queue wait + prefill on its
        # source replica).
        worked |= self._advance_imports()

        # Admissions: block-starved parked entries retry FIRST, strictly
        # FIFO — while any is parked, newer submissions stay queued so a
        # big request cannot be starved by a stream of small ones.
        while self._admit_backlog and self.engine.free_slots:
            if not self._try_admit(self._admit_backlog[0]):
                break
            self._admit_backlog.pop(0)
            worked = True
        # Pending grafts gate fresh admissions exactly like a parked
        # backlog: admitting newer work would consume the slots/blocks
        # the migrated sessions wait for.
        with self._import_lock:
            imports_pending = bool(self._import_queue)
        n_free = (
            0 if (self._admit_backlog or imports_pending)
            else self.engine.free_slots
        )
        engine_idle = (
            self.engine.active_count == 0 and not self._prefill_entries
        )
        pop = self.scheduler.pop_ready(n_free, engine_idle=engine_idle)
        for qe in pop.cancelled:
            self._finish(qe.item, "cancelled")
            worked = True
        for qe in pop.expired:
            self._finish(qe.item, "deadline")
            worked = True
        for qe in pop.admit:
            # Strict FIFO past a block-starved admission: once one entry
            # parks, everything popped behind it parks too — admitting it
            # would consume the very blocks the parked request waits for.
            if self._admit_backlog or not self._try_admit(qe.item):
                self._admit_backlog.append(qe.item)
            worked = True
        return worked

    # ------------------------------------------------------- tick periods

    def _phase(self, name: str) -> Phase:
        """One worker phase: a clock pair on the worker's clock that is
        also a ``serve/<name>`` annotation in a profiler's trace.  It
        writes no span record; its seconds go into the ``tick`` record."""
        return Phase(f"serve/{name}", self._clock)

    def _open_period(self, t: float, cpu: float = 0.0) -> dict:
        """A tick period runs from the end of one decode tick to the end
        of the next one, so the periods tile the worker's time.  The phases
        around the tick accumulate here as they happen; ``deliver_s`` is the
        publishing of the tick before (:meth:`_publish`).  ``cpu`` is what
        the worker thread's CPU clock reads now: 0 at the thread's start."""
        return {
            "t": t, "admit_s": 0.0, "prefill_s": 0.0, "chunks": 0,
            "prefill_tokens": 0, "idle_s": 0.0, "deliver_s": 0.0,
            "cpu_before": cpu, "gc_before": gc_pauses()["gc_pause_s"],
            "tokens_before": self.engine.tokens_emitted,
            "chunk_counts_before": self._chunk_counts(),
            "carry_before": self._carry_counts(),
        }

    def _carry_counts(self) -> tuple:
        """The engine's ``(ticks_overlapped, tick_stale_rows,
        carry_flushes)`` (zeros for an engine that reads back what it
        launches)."""
        return tuple(
            getattr(self.engine, name, 0)
            for name in ("ticks_overlapped", "tick_stale_rows", "carry_flushes")
        )

    def _chunk_counts(self) -> dict:
        """The running counts of the engine's cache kind of its chunks' work
        (`kvpool/host_cache.py`; {} for an engine or a kind that counts none)."""
        return getattr(self.engine, "chunk_counts", dict)()

    def _close_period(self, tick, n_events: int) -> None:
        """End the period now, at the end of its tick, and account for it
        once: the ``kind="tick"`` record, the cumulative phase seconds of
        ``ServingMetrics`` and the flight recorder's coalesced tick entry
        all carry these same clock pairs."""
        end = self._clock()
        period, self._period = (
            self._period, self._open_period(end, time.thread_time())
        )
        dispatch_s, wait_s, emit_s = tick
        seconds = {
            "admit": period["admit_s"], "prefill": period["prefill_s"],
            "dispatch": dispatch_s, "wait": wait_s, "emit": emit_s,
            "deliver": period["deliver_s"], "idle": period["idle_s"],
        }
        dur_s = end - period["t"]
        # Kept explicit so nothing hides: the engine record, the request
        # spans' emission, a finished prefill's hand-over, loop overhead.
        seconds["other"] = dur_s - sum(seconds.values())
        # The worker thread's CPU seconds over the period - ONE read of its
        # CPU clock a period, here: on the chip machines a read is a system
        # call of 6 us that steps by 10 ms, and two more of them around the
        # tick's call cost 1-3% of a 10 ms period - and what of the period
        # it was neither on the CPU nor where being off it is the purpose
        # (blocked on the device, waiting for work): a wait for the
        # interpreter lock, the host's scheduler, a runtime call asleep with
        # the lock let go.  Time the worker wanted and did not get, less the
        # little CPU it used while it waited; never clamped: where the clock
        # steps, one period's reading says nothing and sums do.
        cpu_s = self._period["cpu_before"] - period["cpu_before"]
        offcpu_s = dur_s - wait_s - period["idle_s"] - cpu_s
        self.metrics.on_worker_period(seconds, cpu_s, offcpu_s)
        # Tick summary, coalesced: consecutive ticks merge into one ring
        # entry (count + refreshed fields) so steady-state decode chatter
        # cannot evict the rare decision events around it.
        self.flightrecorder.record(
            "tick",
            coalesce=True,
            n_events=n_events,
            tick_s=round(dispatch_s + wait_s + emit_s, 6),
            active_slots=self.engine.active_count,
            queue_depth=self.scheduler.depth,
        )
        if self._telemetry is None:
            return
        before = period["chunk_counts_before"]
        chunk_counts = {
            name: now - before[name]
            for name, now in self._chunk_counts().items()
        }
        overlapped, stale_rows, carry_flushes = (
            now - before for now, before in
            zip(self._carry_counts(), period["carry_before"])
        )
        self._telemetry.emit(
            {
                "kind": "tick",
                "t": round(period["t"] - self._t0, 6),
                "dur_s": round(dur_s, 6),
                **{f"{k}_s": round(v, 6) for k, v in seconds.items()},
                "cpu_s": round(cpu_s, 6),
                "host_offcpu_s": round(offcpu_s, 6),
                # The collector's seconds in the period, whichever thread
                # it ran on.
                "gc_s": round(
                    self._period["gc_before"] - period["gc_before"], 6
                ),
                "chunks": period["chunks"],
                "prefill_tokens": period["prefill_tokens"],
                # Tokens the engine emitted in the period: the tick's, and
                # the first token of each prefill that completed in it.
                "batch": self.engine.tokens_emitted - period["tokens_before"],
                "queue_depth": self.scheduler.depth,
                # Whether the period's launch was queued while the one
                # before was unread, the rows it read that a launch had
                # computed for a tenant since gone, and the times a reader
                # of the carry had to read unread launches first.
                "overlapped": overlapped,
                "stale_rows": stale_rows,
                "carry_flushes": carry_flushes,
                # Of the tick that was READ in the period (one launch behind
                # the one it queued, where the worker runs ahead):
                # assignments of its tokens that landed on experts
                # held here and on zero experts (dropless expert layers of
                # the paged engine; 0 elsewhere).
                "moe_rows_local": getattr(
                    self.engine, "last_tick_moe_rows_local", 0
                ),
                "moe_zero_assignments": getattr(
                    self.engine, "last_tick_moe_zero_assignments", 0
                ),
                # State-space slot-layers that tick updated (live slots x
                # state-space layers) and the period's chunks' real and
                # bucket rows x state-space layers: 0 without such layers.
                **_TICK_COUNTS_OFF,
                # What the engine's cache kind counted of that tick at its
                # dispatch (a latent pool: its shared pass; a summary-and-
                # window cache: rows attended and the summaries among them)
                # and of the period's chunks (`kvpool/host_cache.py`).
                **getattr(self.engine, "last_tick_counts", {}),
                **chunk_counts,
            }
        )

    def _try_admit(self, entry: _Entry) -> bool:
        """Admit one popped entry into the engine.  Dense engine: one-shot
        bucketed prefill, always succeeds (the scheduler never over-pops
        slots).  Paged engine: reserve the slot + worst-case block chain
        and queue the prompt's chunks; returns False when the pool is
        block-starved so the caller parks the entry and retries as decode
        retirements free blocks."""
        request = entry.request
        t0 = self._clock()
        if self.paged:
            from bpe_transformer_tpu.serving.kvpool.blocks import (
                NoFreeBlocksError,
            )

            entry.programs_before = self.engine.compiled_programs()
            try:
                slot = self.engine.begin(
                    request.prompt_ids,
                    max_new_tokens=request.max_new_tokens,
                    temperature=request.temperature,
                    top_k=request.top_k,
                    top_p=request.top_p,
                    seed=request.seed,
                    stop_id=request.stop_id,
                    request_id=request.request_id,
                )
            except NoFreeBlocksError:
                # Coalesced: the backlog head retries every step while the
                # pool stays dry — one ring entry per parked request, with
                # a retry count, not one per retry.
                self.flightrecorder.record(
                    "park",
                    coalesce=True,
                    request_id=request.request_id,
                    prompt_len=len(request.prompt_ids),
                    backlog=len(self._admit_backlog),
                )
                return False
            entry.queue_wait_s = t0 - entry.t_submit
            self._span(
                "queue_wait", entry.t_submit, entry.queue_wait_s, request
            )
            entry.slot = slot
            entry.bucket = self.engine.slot_bucket(slot)
            entry.shared_tokens = self.engine.slot_shared_len(slot)
            entry.t_prefill_start = t0
            entry.prefill_s = 0.0
            self._prefill_entries[slot] = entry
            self.flightrecorder.record(
                "admit",
                request_id=request.request_id,
                slot=slot,
                prompt_len=len(request.prompt_ids),
                queue_wait_s=round(entry.queue_wait_s, 6),
                shared_tokens=entry.shared_tokens or None,
            )
            return True

        entry.queue_wait_s = t0 - entry.t_submit
        entry.bucket = self.engine.bucket_for(len(request.prompt_ids))
        programs_before = self.engine.compiled_programs()
        event = self.engine.admit(
            request.prompt_ids,
            max_new_tokens=request.max_new_tokens,
            temperature=request.temperature,
            top_k=request.top_k,
            top_p=request.top_p,
            seed=request.seed,
            stop_id=request.stop_id,
            request_id=request.request_id,
        )
        now = self._clock()
        entry.prefill_s = now - t0
        entry.t_decode_start = now
        entry.slot = event.slot
        self.metrics.on_prefill(
            entry.bucket,
            len(request.prompt_ids),
            entry.prefill_s,
            # A bucket's first admission pays its XLA compile — keep that
            # wall out of the bucket's steady-state throughput gauge.
            compiled=self.engine.compiled_programs() > programs_before,
        )
        self._span("queue_wait", entry.t_submit, entry.queue_wait_s, request)
        self._span("prefill", t0, entry.prefill_s, request)
        # Time to first token: wait + prefill, observed request-level for
        # the ttfb SLO histogram (never as a span — see metrics.phases).
        self.metrics.observe_phase(
            "ttfb", entry.queue_wait_s + entry.prefill_s
        )
        self.flightrecorder.record(
            "admit",
            request_id=request.request_id,
            slot=event.slot,
            prompt_len=len(request.prompt_ids),
            bucket=entry.bucket,
            queue_wait_s=round(entry.queue_wait_s, 6),
        )
        entry.tokens.append(event.token)
        entry.stream.put(event.token)
        if event.finished:
            self._finish(entry, event.finished)
        else:
            self._slot_entries[event.slot] = entry
        return True

    def _advance_imports(self) -> bool:
        """Graft queued KV payloads into the engine, FIFO.  A graft that
        cannot land yet (no free slot, block-starved pool) stays queued
        and retries as retirements free capacity — the import twin of the
        parked-admission backlog."""
        from bpe_transformer_tpu.serving.kvpool.blocks import (
            NoFreeBlocksError,
        )

        worked = False
        while True:
            with self._import_lock:
                if not self._import_queue:
                    return worked
                entry, payload, nbytes, recv_unix = self._import_queue[0]
            if entry.cancel_requested:
                with self._import_lock:
                    self._import_queue.popleft()
                self._finish(entry, "cancelled")
                worked = True
                continue
            deadline = entry.request.deadline_s
            if (
                deadline is not None
                and self._clock() >= entry.t_submit + deadline
            ):
                # The deadline contract follows the request through a
                # migration: a graft parked past its budget expires like
                # a queued admission would (t_submit = graft receipt).
                with self._import_lock:
                    self._import_queue.popleft()
                self._finish(entry, "deadline")
                worked = True
                continue
            if not self.engine.free_slots:
                return worked
            t0 = self._clock()
            try:
                slot = self.engine.import_slot(payload)
            except NoFreeBlocksError:
                return worked  # pool dry: retry as decode frees blocks
            with self._import_lock:
                self._import_queue.popleft()
            import_s = self._clock() - t0
            meta = payload["meta"]
            entry.slot = slot
            now = self._clock()
            if meta.get("decoding"):
                # Backdated by the decode seconds already accrued on the
                # exporting replica: the final Result.decode_s (and its
                # closing span) stays end-to-end across the migration.
                entry.t_decode_start = now - float(
                    meta.get("decode_s") or 0.0
                )
                self._slot_entries[slot] = entry
            else:
                entry.t_prefill_start = now
                entry.programs_before = self.engine.compiled_programs()
                self._prefill_entries[slot] = entry
            self.metrics.on_migration("in", nbytes)
            exported_unix = meta.get("exported_unix")
            transfer_s = (
                max(recv_unix - exported_unix, 0.0)
                if isinstance(exported_unix, (int, float))
                else None
            )
            export_s = meta.get("export_s")
            total_s = import_s + (transfer_s or 0.0) + (export_s or 0.0)
            self._span(
                "migration_import", t0, import_s, entry.request
            )
            self.metrics.observe_phase("migration", total_s)
            self._emit_migration(
                direction="import",
                request_id=entry.request.request_id,
                bytes=nbytes,
                blocks=int(meta["n_blocks"]),
                export_s=export_s,
                transfer_s=transfer_s,
                import_s=round(import_s, 6),
                total_s=round(total_s, 6),
                decoding=bool(meta.get("decoding")),
            )
            worked = True

    def _export_entry(
        self, entry: _Entry, slot: int, codec: str = "raw"
    ) -> tuple[bytes, int]:
        """Export ``slot`` (holding ``entry``'s generation) as payload
        bytes, with the serving-layer continuation state — emitted tokens,
        token history (the speculative importer's draft re-prefill input),
        accrued phase timings — folded into the meta.  Releases the slot.
        Returns ``(payload_bytes, n_blocks)``."""
        from bpe_transformer_tpu.serving.kvpool.migrate import (
            payload_to_bytes,
        )

        t0 = self._clock()
        # Decode seconds accrued HERE ride the meta so the importer can
        # backdate its decode clock — Result.decode_s and the total SLO
        # histogram stay end-to-end across the migration.
        decode_accrued = (
            t0 - entry.t_decode_start
            if slot in self._slot_entries or self.engine._active[slot]
            else 0.0
        )
        payload = self.engine.export_slot(
            slot,
            {
                "emitted": [int(t) for t in entry.tokens],
                "history": [
                    int(t) for t in entry.request.prompt_ids
                ] + [int(t) for t in entry.tokens],
                "queue_wait_s": round(entry.queue_wait_s, 6),
                "prefill_s": round(entry.prefill_s, 6),
                "decode_s": round(max(decode_accrued, 0.0), 6),
                "bucket": entry.bucket,
                "shared_tokens": entry.shared_tokens,
                "deadline_s": entry.request.deadline_s,
                "session": entry.request.session,
                "exported_unix": time.time(),
            },
        )
        self.engine.release(slot)
        # The device-extract wall rides the meta so the IMPORT side's
        # migration record carries the full export/transfer/import split
        # (serialization + HTTP land in transfer_s via exported_unix).
        payload["meta"]["export_s"] = round(self._clock() - t0, 6)
        # Chaos hook: truncate/bit-flip the bytes in flight (fires once) —
        # the importer's CRC/length checks must 400 the graft.
        data = self.faults.on_export_payload(
            payload_to_bytes(payload, codec=codec)
        )
        return data, int(payload["meta"]["n_blocks"])

    def _complete_migration_export(self, entry: _Entry, slot: int) -> None:
        """Prefill-role handoff: the finished prefix (first token already
        sampled and delivered) leaves as a KV payload; the request
        finishes here as ``"migrated"`` with the payload on its result."""
        from bpe_transformer_tpu.serving.kvpool.migrate import (
            negotiate_codec,
        )

        t0 = self._clock()
        data, blocks = self._export_entry(
            entry, slot, codec=negotiate_codec(entry.request.kv_accept)
        )
        export_s = self._clock() - t0
        self.metrics.on_migration("out", len(data))
        self._span("migration_export", t0, export_s, entry.request)
        self._emit_migration(
            direction="export",
            request_id=entry.request.request_id,
            bytes=len(data),
            blocks=blocks,
            export_s=round(export_s, 6),
        )
        self._finish(entry, "migrated", kv_payload=data)

    def _evacuate_step(self) -> bool:
        """Move every queued + in-flight session to an evacuation peer
        (round-robin): queued entries re-enter the peer's scheduler whole;
        in-flight slots (decoding AND mid-prefill) export as KV payloads
        the peer grafts and continues bit-for-bit.  The original callers'
        handles complete from the peer — zero failed requests.

        Peers are either in-process ``ServingEngine`` objects (entries
        move whole, payload dicts skip the bytes codec) or — when only
        ``_evacuate_urls`` is set — remote replicas: queued requests
        replay as seeded ``/generate`` calls and exported sessions relay
        to ``/kv/import`` from background threads (the worker must not
        block on a peer's decode), each under one idempotency key across
        its bounded retries."""
        from bpe_transformer_tpu.serving.kvpool.migrate import (
            negotiate_codec,
            payload_to_bytes,
        )

        peers = [p for p in self._evacuate_peers if p.accepting_imports()]
        urls = list(self._evacuate_urls)
        if not peers and not urls:
            self._evacuate_peers = []
            return False
        wire = not peers
        wire_codec = negotiate_codec(self.export_codec)

        def next_peer():
            self._evacuate_rr += 1
            return peers[self._evacuate_rr % len(peers)]

        worked = False
        # Not-yet-admitted work first (cheap: no KV moves) — the queue,
        # then block-starved parked admissions and queued grafts.
        pop = self.scheduler.pop_ready(self.scheduler.max_queue)
        for qe in pop.cancelled:
            self._finish(qe.item, "cancelled")
        for qe in pop.expired:
            self._finish(qe.item, "deadline")
        moved_entries = list(self._admit_backlog)
        self._admit_backlog = []
        with self._import_lock:
            moved_imports = list(self._import_queue)
            self._import_queue.clear()
        for qe in pop.admit:
            moved_entries.append(qe.item)
        for entry in moved_entries:
            if wire:
                # Nothing emitted yet: a seeded /generate replay on the
                # peer is token-identical.  The entry stays registered
                # until the relay thread finishes it (drain waits on the
                # registry).
                self._relay_entry_thread(entry, None, urls, "evacuate")
                worked = True
                continue
            with self._entries_lock:
                self._entries.pop(entry.request.request_id, None)
            try:
                next_peer().adopt_entry(entry)
            except (RuntimeError, ValueError) as exc:
                self.metrics.record_error(repr(exc), source="evacuate")
                self._finish(entry, "error")
            worked = True
        for entry, payload, nbytes, _recv in moved_imports:
            if wire:
                data = payload_to_bytes(payload, codec=wire_codec)
                self._relay_entry_thread(entry, data, urls, "evacuate")
                worked = True
                continue
            with self._entries_lock:
                self._entries.pop(entry.request.request_id, None)
            try:
                # Already parsed: hand the dict over directly (the bytes
                # codec is for the HTTP transport, not in-process moves).
                next_peer().adopt_migration(entry, payload)
            except (RuntimeError, ValueError) as exc:
                self.metrics.record_error(repr(exc), source="evacuate")
                self._finish(entry, "error")
            worked = True

        # In-flight sessions: export + graft.  The entry object itself
        # moves — its stream/done handles keep serving the original
        # caller from the peer's worker (in-process) or complete with the
        # peer's returned tokens (over the wire).
        in_flight = list(self._prefill_entries.items()) + list(
            self._slot_entries.items()
        )
        for slot, entry in in_flight:
            self._prefill_entries.pop(slot, None)
            self._slot_entries.pop(slot, None)
            t0 = self._clock()
            data, blocks = self._export_entry(
                entry, slot, codec=wire_codec if wire else "raw"
            )
            export_s = self._clock() - t0
            entry.slot = None
            self.metrics.on_migration("out", len(data))
            self._span("migration_export", t0, export_s, entry.request)
            self._emit_migration(
                direction="evacuate",
                request_id=entry.request.request_id,
                bytes=len(data),
                blocks=blocks,
                export_s=round(export_s, 6),
            )
            if wire:
                self._relay_entry_thread(entry, data, urls, "evacuate")
                worked = True
                continue
            with self._entries_lock:
                self._entries.pop(entry.request.request_id, None)
            try:
                next_peer().adopt_migration(entry, data)
            except (RuntimeError, ValueError) as exc:
                self.metrics.record_error(repr(exc), source="evacuate")
                self._finish(entry, "error")
            worked = True
        if worked and self._telemetry is not None:
            self._telemetry.event(
                "serve_evacuate",
                sessions=len(in_flight),
                queued=len(moved_entries) + len(moved_imports),
                peers=len(peers) or len(urls),
                wire=wire,
            )
        return worked

    # ------------------------------------- over-the-wire relay (ISSUE 20)

    def _relay_entry_thread(self, entry, data, urls, direction) -> None:
        threading.Thread(
            target=self._relay_entry,
            args=(entry, data, urls, direction),
            name="kv-relay",
            daemon=True,
        ).start()

    def _relay_entry(self, entry, data, urls, direction) -> None:
        """Move one session to a peer over HTTP and complete the original
        caller's handle with the peer's result.  ``data=None`` replays a
        never-admitted request as a seeded ``/generate`` (token-identical:
        nothing was emitted yet); otherwise ``data`` is an exported KV
        payload POSTed to ``/kv/import`` under ONE idempotency key held
        across every retry — the receiver grafts exactly once even when a
        response is lost mid-reply.  Connect/read failures rotate to the
        next peer URL with exponential backoff; a 400 is permanent (the
        payload itself is bad — retrying the same bytes cannot help)."""
        import urllib.error
        import urllib.request

        idem_key = uuid.uuid4().hex
        rid = entry.request.request_id
        t0 = self._clock()
        result = None
        last_exc: Exception | None = None
        for attempt in range(self.relay_attempts):
            url = urls[attempt % len(urls)]
            try:
                if data is None:
                    req = entry.request
                    body = json.dumps(
                        {
                            "prompt_ids": list(req.prompt_ids),
                            "max_new_tokens": req.max_new_tokens,
                            "temperature": req.temperature,
                            "top_k": req.top_k,
                            "top_p": req.top_p,
                            "seed": req.seed,
                            "stop_id": req.stop_id,
                            "deadline_s": req.deadline_s,
                            "session": req.session,
                        }
                    ).encode("utf-8")
                    http_req = urllib.request.Request(
                        url + "/generate",
                        data=body,
                        headers={
                            "Content-Type": "application/json",
                            "X-Request-Id": rid,
                        },
                    )
                else:
                    http_req = urllib.request.Request(
                        url + "/kv/import",
                        data=data,
                        headers={
                            "Content-Type": "application/octet-stream",
                            "X-Request-Id": rid,
                            "X-Idempotency-Key": idem_key,
                        },
                    )
                with urllib.request.urlopen(
                    http_req, timeout=self.relay_timeout_s
                ) as resp:
                    result = json.loads(resp.read())
                break
            except urllib.error.HTTPError as exc:
                last_exc = exc
                if exc.code == 400:
                    break
            except (OSError, ValueError) as exc:
                last_exc = exc
            if attempt + 1 < self.relay_attempts:
                time.sleep(self.relay_backoff_s * (2 ** attempt))
        transfer_s = self._clock() - t0
        if result is None:
            self._relays_failed += 1
            self.metrics.record_error(
                f"relay failed: {last_exc!r}",
                source="relay",
                request_id=rid,
            )
            self.flightrecorder.record(
                "relay_failed",
                request_id=rid,
                direction=direction,
                error=repr(last_exc),
            )
            self._finish(entry, "error")
            return
        # Peer token_ids = tokens emitted before the move + everything it
        # decoded; stream only the suffix so the caller sees no repeats.
        all_tokens = [int(t) for t in result.get("token_ids", [])]
        for tok in all_tokens[len(entry.tokens):]:
            entry.tokens.append(tok)
            entry.stream.put(tok)
        self._relays_ok += 1
        self._emit_migration(
            direction=f"{direction}_relay",
            request_id=rid,
            bytes=len(data) if data is not None else 0,
            transfer_s=round(transfer_s, 6),
            total_s=round(transfer_s, 6),
        )
        self._finish(entry, result.get("finish_reason") or "stop")

    def request_rebalance(
        self,
        target_url: str,
        max_sessions: int = 1,
        timeout_s: float = 30.0,
    ) -> dict:
        """Transport side of ``POST /admin/evacuate`` (controller hot
        rebalancing): ask the worker to export up to ``max_sessions``
        decoding sessions and relay them to ``target_url``'s
        ``/kv/import``.  Blocks until the exports happen (the relays
        complete asynchronously; each original caller's handle resolves
        with the peer's tokens).  Returns ``{"moved", "request_ids",
        "target"}``."""
        if not self.paged:
            raise RuntimeError("rebalancing needs a paged engine")
        if self._worker_error is not None:
            raise RuntimeError(
                "serving engine worker died"
            ) from self._worker_error
        if not self._running:
            raise RuntimeError("serving engine is not running")
        done = threading.Event()
        out: dict = {}
        self._rebalance_queue.append(
            (target_url.rstrip("/"), max(1, int(max_sessions)), done, out)
        )
        self.scheduler.notify()
        if not done.wait(timeout_s):
            raise TimeoutError("rebalance request not picked up by worker")
        return out

    def _rebalance_step(self) -> bool:
        """Worker side: export the requested victim sessions and hand them
        to relay threads.  Victims are the decoding slots with the most
        budget remaining — the sessions that gain the most from moving to
        a less loaded replica (and whose KV is cheapest per remaining
        token to have shipped)."""
        from bpe_transformer_tpu.serving.kvpool.migrate import (
            negotiate_codec,
        )

        worked = False
        codec = negotiate_codec(self.export_codec)
        while self._rebalance_queue:
            target, n, done, out = self._rebalance_queue.popleft()
            victims = sorted(
                self._slot_entries.items(),
                key=lambda kv: (
                    kv[1].request.max_new_tokens - len(kv[1].tokens)
                ),
                reverse=True,
            )[:n]
            moved = []
            for slot, entry in victims:
                self._slot_entries.pop(slot, None)
                t0 = self._clock()
                data, blocks = self._export_entry(entry, slot, codec=codec)
                export_s = self._clock() - t0
                entry.slot = None
                self.metrics.on_migration("out", len(data))
                self._span("migration_export", t0, export_s, entry.request)
                self._emit_migration(
                    direction="rebalance",
                    request_id=entry.request.request_id,
                    bytes=len(data),
                    blocks=blocks,
                    export_s=round(export_s, 6),
                )
                self._relay_entry_thread(entry, data, [target], "rebalance")
                moved.append(entry.request.request_id)
                self._rebalanced_out += 1
                worked = True
            out.update(moved=len(moved), request_ids=moved, target=target)
            self.flightrecorder.record(
                "rebalance", target=target, moved=len(moved)
            )
            done.set()
        return worked

    def _emit_migration(self, **fields) -> None:
        """One ``kind="migration"`` record (bytes, blocks, phase split) —
        the telemetry spine's view of each KV move."""
        # Tee into the decision ring BEFORE the sink guard: the flight
        # recorder must see every KV move even on a server run without
        # --metrics-jsonl.
        self.flightrecorder.record(
            "migration", **{k: v for k, v in fields.items() if v is not None}
        )
        if self._telemetry is None:
            return
        self._telemetry.emit(
            {
                "kind": "migration",
                "t": round(self._clock() - self._t0, 6),
                "time_unix": round(time.time(), 6),
                **{k: v for k, v in fields.items() if v is not None},
            }
        )

    def _advance_prefills(self) -> bool:
        """Run pending prefill chunks (paged engine) under the per-tick
        token budget, oldest admission first.  A completed prefill
        delivers its first token and moves the slot to the decode set —
        the paged twin of the dense admission's tail."""
        if not self.paged or not self._prefill_entries:
            return False
        worked = False
        budget = self._prefill_budget
        budget.start_tick()
        for slot in list(self.engine.pending_prefills()):
            entry = self._prefill_entries.get(slot)
            if entry is None:
                continue
            while True:
                chunk_tokens = self.engine.next_chunk_tokens(slot)
                if not budget.admits(chunk_tokens):
                    return worked  # budget spent: decode tick runs next
                delivered = self._period["deliver_s"]
                with self._phase("prefill_chunk") as chunk:
                    if self._runs_ahead and not entry.request.migrate:
                        # Queued and not read: a final chunk's token comes
                        # with the tick before it, behind the next launch.
                        event = None
                        queued = self.engine.launch_chunk(slot)
                    else:
                        # A prefix that leaves as a KV payload is no row of
                        # a tick here: read at once, nothing follows.
                        queued = False
                        event = self.engine.prefill_step(
                            slot, dispatched=self._publish
                        )
                entry.prefill_s += chunk.dur_s
                # A publish behind the chunk is the period's deliver.
                self._period["prefill_s"] += chunk.dur_s - (
                    self._period["deliver_s"] - delivered
                )
                self._period["chunks"] += 1
                self._period["prefill_tokens"] += chunk_tokens
                budget.spend(chunk_tokens)
                worked = True
                if queued:
                    entry.t_chunks_queued = chunk.start + chunk.dur_s
                    break
                if event is not None:
                    self._complete_prefill(entry, event)
                    break
        return worked

    def _complete_prefill(self, entry: _Entry, event: TickEvent) -> None:
        request = entry.request
        del self._prefill_entries[entry.slot]
        if entry.t_chunks_queued is not None:
            # The final chunk was queued with its token unread: the prefill
            # lasted until this read.
            entry.prefill_s += self._clock() - entry.t_chunks_queued
        self.metrics.on_prefill(
            entry.bucket,
            # COMPUTED prompt tokens: the prefix-cache-shared prefix paid
            # no compute, so it stays out of the throughput accounting.
            len(request.prompt_ids) - entry.shared_tokens,
            entry.prefill_s,
            compiled=self.engine.compiled_programs() > entry.programs_before,
        )
        self._span(
            "prefill", entry.t_prefill_start, entry.prefill_s, request
        )
        self.metrics.observe_phase(
            "ttfb", entry.queue_wait_s + entry.prefill_s
        )
        entry.t_decode_start = self._clock()
        entry.tokens.append(event.token)
        entry.stream.put(event.token)
        if event.finished:
            self._finish(entry, event.finished)
        elif entry.request.migrate:
            # Disaggregated prefill handoff (ISSUE 15): the finished
            # prefix (first token included) leaves as a KV payload
            # instead of entering this replica's decode set.
            self._complete_migration_export(entry, event.slot)
        else:
            self._slot_entries[event.slot] = entry

    def _deliver(self, events: list[TickEvent]) -> None:
        """Take a tick's events to their requests, now, while a slot still
        names its request; the streams get them in :meth:`_publish`.  A
        slot still among the prefills names a request whose final chunk was
        queued with its token unread: this is that token (the engine hands
        no row to a slot's next tenant)."""
        first = 0
        for event in events:
            if event.slot in self._prefill_entries:
                self._complete_prefill(self._prefill_entries[event.slot], event)
                first += 1
                continue
            entry = self._slot_entries.get(event.slot)
            if entry is None:
                continue  # released between admit and tick (cancellation)
            entry.tokens.append(event.token)
            if event.finished:
                del self._slot_entries[event.slot]
            self._unpublished.append((entry, event.token, event.finished))
        self.metrics.on_decode_tokens(len(events) - first)

    def _publish(self) -> None:
        """Put the held tokens on their requests' streams and finish the
        requests that ended (the ``serve/deliver`` phase).  Every reader
        that wakes wants the interpreter lock, so the worker does this when
        it has nothing to launch: the engines call it once their next
        program is in the device's queue, and the worker itself wherever
        none follows.  At 64 readers the same puts between a tick and the
        next launch cost the device 4 ms a tick (PERF.md section 6, PR 33)."""
        if not self._unpublished:
            return
        held, self._unpublished = self._unpublished, []
        with self._phase("deliver") as deliver:
            for entry, token, finished in held:
                entry.stream.put(token)
                if finished:
                    self._finish(entry, finished)
        self._period["deliver_s"] += deliver.dur_s

    def _finish(
        self, entry: _Entry, reason: str, kv_payload: bytes | None = None
    ) -> None:
        if entry.done.is_set():
            return
        now = self._clock()
        decode_s = (
            now - entry.t_decode_start
            if entry.slot is not None and reason != "migrated"
            else 0.0
        )
        if entry.slot is not None and reason != "migrated":
            self._span("decode", entry.t_decode_start, decode_s, entry.request)
        elif reason in ("deadline", "cancelled") and not entry.migrated_in:
            # Never admitted: the whole life was queue wait.
            entry.queue_wait_s = now - entry.t_submit
            self._span("queue_wait", entry.t_submit, entry.queue_wait_s,
                       entry.request)
        entry.result = Result(
            request_id=entry.request.request_id,
            token_ids=tuple(entry.tokens),
            finish_reason=reason,
            queue_wait_s=entry.queue_wait_s,
            prefill_s=entry.prefill_s,
            decode_s=decode_s,
            kv_payload=kv_payload,
        )
        self._requests_finished += 1
        self.metrics.on_finish(reason)
        # Deadline expiries are first-class incident evidence (the park ->
        # deadline chain IS a block-exhaustion story); ordinary completions
        # ride along as "finish" so the ring shows request turnover.
        self.flightrecorder.record(
            "deadline" if reason == "deadline" else "finish",
            request_id=entry.request.request_id,
            reason=reason if reason != "deadline" else None,
            n_tokens=len(entry.tokens) or None,
            slot=entry.slot,
        )
        # Whole-request latency for the total SLO histogram (request-level
        # only — a total SPAN would double-count in the report's
        # per-request phase assembly).
        self.metrics.observe_phase(
            "total", entry.queue_wait_s + entry.prefill_s + decode_s
        )
        # Per-request trace: the finished timeline joins the /statusz ring.
        # Same numbers as the serve/* spans and Result.timings() — one
        # measurement, three surfaces.
        self._recent.append(
            {
                "request_id": entry.request.request_id,
                "finish_reason": reason,
                "n_tokens": len(entry.tokens),
                "prompt_len": len(entry.request.prompt_ids),
                "bucket": entry.bucket,
                "slot": entry.slot,
                "t_submit": round(entry.t_submit - self._t0, 6),
                "queue_wait_s": round(entry.queue_wait_s, 6),
                "prefill_s": round(entry.prefill_s, 6),
                "decode_s": round(decode_s, 6),
            }
        )
        with self._entries_lock:
            self._entries.pop(entry.request.request_id, None)
        entry.stream.put(_STREAM_END)
        entry.done.set()

    # ------------------------------------------------------------ telemetry

    def _span(self, name: str, start: float, dur: float, request: Request):
        """Emit one request-phase span record.  Spans are emitted directly
        (not via Telemetry's nesting stack — concurrent requests interleave,
        so LIFO nesting does not apply).  The same duration feeds the live
        /metrics histogram, so the scrape and the stream always agree."""
        self.metrics.observe_phase(name, dur)
        if self._telemetry is None:
            return
        self._telemetry.emit(
            {
                "kind": "span",
                "name": name,
                "path": f"serve/{name}",
                "t": round(start - self._t0, 6),
                "dur_s": round(dur, 6),
                "request_id": request.request_id,
                # Absolute span START time: every stream has its own t
                # epoch, so cross-stream request assembly (router lanes
                # joining these lanes in telemetry/trace.request_timeline)
                # orders hops by wall clock.  Spans are emitted at phase
                # end, so start = now - dur.
                "time_unix": round(time.time() - dur, 6),
            }
        )

    def _feed_alerts(self, t: float, resources: dict | None) -> None:
        """One watchdog sample on the engine-record cadence; transitions
        go to the telemetry stream when one is attached (the active set
        is always queryable via /statusz regardless)."""
        sample: dict = {
            "queue_depth": self.scheduler.depth + len(self._admit_backlog),
            "active_slots": self.engine.active_count,
        }
        if resources is not None:
            sample["compile_events"] = resources.get("compile_events")
        if self.paged:
            gauges = self.engine.gauges()
            sample["kv_blocks_free"] = gauges.get("kv_blocks_free")
            sample["kv_blocks_total"] = gauges.get("kv_blocks_total")
            if self.spec:
                sample["spec_accept_rate"] = gauges.get("spec_accept_rate")
                sample["spec_proposed"] = gauges.get("spec_proposed_tokens")
        for transition in self._alerts.feed(sample, round(t, 6)):
            self.flightrecorder.record(
                "alert",
                rule=transition.get("rule"),
                state=transition.get("state"),
                severity=transition.get("severity"),
            )
            if self._telemetry is not None:
                self._telemetry.emit(transition)
            if transition.get("state") == "firing":
                # An alert edge is THE black-box trigger: flush the ring
                # (with the alert itself as its newest entry) while the
                # decisions that led here are still in it.  The recorder's
                # cooldown de-dupes a storm of edges into one dump.
                self.blackbox_dump(f"alert:{transition.get('rule')}")

    def blackbox_dump(self, trigger: str, force: bool = False) -> dict | None:
        """Flush the decision ring as a ``kind="blackbox"`` record with the
        host-side operational context an incident needs (queue/slot/kvpool
        state, active alerts + history tail) attached; emitted into the
        telemetry stream when a sink is attached, always retained on the
        recorder for ``GET /debug/flightrecorder``.  Returns the dump, or
        None while the post-dump cooldown holds (``force=True`` bypasses —
        the POST /debug/dump and terminal worker-error paths).

        Everything gathered here is host-side bookkeeping (slot_states and
        kvpool gauges are plain dict reads) — no device syncs, matching the
        recording path's fetch-count contract."""
        context: dict = {
            "queue_depth": self.scheduler.depth + len(self._admit_backlog),
            "active_slots": self.engine.active_count,
            "draining": self._draining,
            "requests_finished": self._requests_finished,
            "slot_states": self.engine.slot_states(),
            "alerts": self._alerts.active(),
            "alert_history": self._alerts.history(16),
        }
        if self.paged:
            context["kvpool"] = {
                **self.engine.gauges(),
                "admit_backlog": len(self._admit_backlog),
            }
        dump = self.flightrecorder.blackbox(
            trigger, context=context, force=force
        )
        if dump is not None and self._telemetry is not None:
            self._telemetry.emit(dump)
        return dump

    def _maybe_emit_engine_record(self) -> None:
        now = self._clock()
        elapsed = now - self._last_record_t
        if elapsed < self._record_every_s:
            return
        # Milliseconds of host work once a second, between a tick's deliver
        # and the next dispatch: annotated so the device's idle gap under it
        # has a name; its seconds stay in the tick record's ``other_s``.
        with self._phase("engine_record"):
            self._emit_engine_record(now, elapsed)

    def _emit_engine_record(self, now: float, elapsed: float) -> None:
        # Sampled UNCONDITIONALLY (sync-free, jax-optional — see
        # telemetry/resources.py): the compile-storm rule must see the
        # compile counter even on a server run without --metrics-jsonl.
        resources = sample_resources(t=round(now - self._t0, 6))
        # The watchdog samples BEFORE the idle short-circuit: an idle
        # engine is exactly when a queue-growth alert must clear.
        self._feed_alerts(now - self._t0, resources)
        if self._telemetry is None:
            self._last_record_t = now
            return
        tokens = self.engine.tokens_emitted
        # A fully idle engine stays silent (no tokens since the last record
        # and nothing in flight) — an idle server must not grow its JSONL.
        if (
            tokens == self._last_record_tokens
            and not self.engine.active_count
            and not self.scheduler.depth
        ):
            self._last_record_t = now
            return
        self._telemetry.emit(
            {
                "kind": "engine",
                "t": round(now - self._t0, 6),
                "active_slots": self.engine.active_count,
                "queue_depth": self.scheduler.depth,
                "tokens_per_sec": round(
                    (tokens - self._last_record_tokens) / max(elapsed, 1e-9), 3
                ),
                "tokens_total": tokens,
                "ticks": self.engine.ticks,
                "requests_finished": self._requests_finished,
                "compiled_programs": self.engine.compiled_programs(),
            }
        )
        # Resource accounting rides the same cadence: HBM/RSS/compile
        # trends of a serving process are as load-bearing as tokens/sec.
        # (The sample was taken once above — the watchdog's compile-storm
        # rule and this record must read the same numbers.)
        self._telemetry.emit(resources)
        # Decode-tick roofline on the same cadence (every engine kind):
        # the weight/KV/activation byte split of one tick at current
        # occupancy vs the chip ridge point — the record the report's
        # roofline section and the serve_weight_bytes compare-gate row
        # read (ISSUE 11).
        roof = self.decode_roofline()
        self._telemetry.emit(
            {
                "kind": "roofline",
                "t": round(now - self._t0, 6),
                "weight_bytes": roof["weight_bytes"],
                "kv_bytes": roof["kv_bytes"],
                "act_bytes": roof["act_bytes"],
                "flops": roof["flops"],
                "arithmetic_intensity": roof["arithmetic_intensity"],
                "ridge_flops_per_byte": roof["ridge_flops_per_byte"],
                "bound": roof["bound"],
                "projected_tick_s": roof["projected_tick_s"],
                "weight_frac": roof["weight_frac"],
                "active_slots": roof["active_slots"],
                "weight_dtype": roof["weight_dtype"],
                "fused_sampling": roof["fused_sampling"],
            }
        )
        if self.paged:
            # Paged-pool accounting on the same cadence: block occupancy,
            # prefix-cache effectiveness, chunked-prefill backlog — the
            # numbers `report`'s kvpool section and the router's health
            # weighting read.
            gauges = self.engine.gauges()
            self._telemetry.emit(
                {
                    "kind": "kvpool",
                    "t": round(now - self._t0, 6),
                    "blocks_total": gauges["kv_blocks_total"],
                    "blocks_free": gauges["kv_blocks_free"],
                    "blocks_shared": gauges["kv_blocks_shared"],
                    "prefix_hits": gauges["prefix_cache_hits"],
                    "prefix_misses": gauges["prefix_cache_misses"],
                    "prefix_hit_rate": gauges["prefix_hit_rate"],
                    "prefill_pending_tokens": gauges[
                        "prefill_pending_tokens"
                    ],
                    # KV-memory economics (ISSUE 9): resident pool bytes
                    # (int8 quarters f32 at fixed block count) and the
                    # per-token KV write footprint — the report/compare
                    # gate's KV-memory regression rows.
                    "kv_pool_bytes": gauges["kv_pool_bytes"],
                    "kv_bytes_per_token": gauges["kv_bytes_per_token"],
                    # The compiled programs' own account: is the pool
                    # still updated in place, and what the tick holds
                    # beside it (ISSUE 30).
                    "kv_pool_aliased_bytes": gauges["kv_pool_aliased_bytes"],
                    "tick_temp_bytes": gauges["tick_temp_bytes"],
                }
            )
            if self.spec:
                # Speculative-decoding acceptance on the same cadence: the
                # accept rate and emitted-tokens-per-verify-pass the
                # report/monitor/compare surfaces read (ISSUE 10).
                self._telemetry.emit(
                    {
                        "kind": "spec",
                        "t": round(now - self._t0, 6),
                        "k": gauges["spec_k"],
                        "proposed": gauges["spec_proposed_tokens"],
                        "accepted": gauges["spec_accepted_tokens"],
                        "emitted": self.engine.spec_emitted,
                        "target_steps": gauges["spec_target_steps"],
                        "accept_rate": gauges["spec_accept_rate"],
                        "tokens_per_target_step": gauges[
                            "spec_tokens_per_target_step"
                        ],
                        "rewound": gauges["spec_rewound_tokens"],
                        "draft_frac": gauges["spec_draft_frac"],
                    }
                )
        self._last_record_t = now
        self._last_record_tokens = tokens


# ------------------------------------------------------------------ HTTP

def make_http_server(
    serving: ServingEngine, host: str = "127.0.0.1", port: int = 8000
):
    """A `ThreadingHTTPServer` exposing the serving engine as JSON-over-HTTP
    (stdlib only — no web framework dependency):

    * ``POST /generate`` — body ``{"prompt": str | "prompt_ids": [int],
      "max_new_tokens"?, "temperature"?, "top_k"?, "top_p"?, "seed"?,
      "stop_id"?, "deadline_s"?}`` -> ``{"completion"?, "token_ids",
      "finish_reason", "timings", "request_id"}``; 400 on bad input, 503
      when the admission queue is full (backpressure).  An inbound
      ``X-Request-Id`` header is adopted as the request's trace id and
      echoed back on EVERY response (errors included) — the fleet
      tracing contract (ISSUE 12).
    * ``GET /healthz`` — engine/queue stats (JSON).
    * ``GET /metrics`` — Prometheus text exposition: request/token
      counters, queue depth, slot occupancy, per-phase latency
      histograms, compile + HBM/RSS accounting (`serving/metrics.py`).
    * ``GET /statusz`` — JSON operator page: run manifest, uptime,
      compile counters, per-slot state, recent per-request phase
      timelines, last-error ring buffer.
    * ``POST /kv/export`` (ISSUE 15) — a /generate-shaped body, served by
      the chunk machine only: the finished prefix (first token sampled)
      returns as a binary KV migration payload
      (``application/octet-stream``) instead of being decoded here — the
      disaggregated router moves it to a decode replica's ``/kv/import``.
      When the first token already finishes the request (stop id, budget
      1), the normal JSON result returns instead.
    * ``POST /kv/import`` (ISSUE 15) — body is a ``/kv/export`` payload;
      the replica grafts it and decodes to completion, answering with the
      standard /generate JSON (token ids = tokens emitted before the
      migration + everything decoded here; greedy and seeded sampling are
      token-identical to an unmigrated run).  400 on a geometry/dtype
      mismatch, 503 on backpressure.

    * ``POST /admin/evacuate`` (ISSUE 20) — controller-initiated hot
      rebalancing: body ``{"target": url, "max_sessions"?}`` exports
      victim sessions and relays them to the target's ``/kv/import``
      (idempotency-keyed, bounded retries); the original callers' open
      requests complete with the target's tokens.
    * ``GET /debug/flightrecorder`` — the live decision ring + retained
      black-box dumps (``bpe-tpu incident`` sweeps this across the fleet).
    * ``POST /debug/dump`` — force a black-box flush now; answers with
      the ``kind="blackbox"`` dump.

    ``port=0`` binds an ephemeral port (tests); the caller owns
    ``serve_forever()`` / ``shutdown()``.
    """
    import socket
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        # Bounded request read + quiet logs: serving telemetry is the
        # observable surface, not stderr.
        def log_message(self, *args):  # noqa: D102
            pass

        def _fault_gate(self) -> bool:
            """Chaos hook (BT_FAULTS): a blackholed path drops the
            connection with no response — what a partitioned peer looks
            like from the caller's side.  Delays sleep inline inside
            ``on_http_request``."""
            if serving.faults.on_http_request(self.path) == "blackhole":
                try:
                    self.connection.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                self.close_connection = True
                return True
            return False

        def _reply(
            self, code: int, payload: dict, request_id: str | None = None
        ) -> None:
            self._reply_text(
                code, json.dumps(payload), "application/json",
                request_id=request_id,
            )

        def _reply_text(
            self,
            code: int,
            text: str,
            content_type: str,
            request_id: str | None = None,
        ) -> None:
            body = text.encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            if request_id is not None:
                # Echoed on EVERY /generate response — 503 backpressure
                # and 400s included — so a client can hand the id to an
                # operator and the operator can find the request in the
                # trace streams (or prove it never reached the engine).
                self.send_header("X-Request-Id", request_id)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802 (stdlib API)
            if self._fault_gate():
                return
            path = self.path.split("?", 1)[0]
            if path == "/healthz":
                return self._reply(200, {"ok": True, **serving.stats()})
            if path == "/metrics":
                return self._reply_text(
                    200,
                    serving.prometheus_metrics(),
                    "text/plain; version=0.0.4; charset=utf-8",
                )
            if path == "/statusz":
                return self._reply(200, serving.statusz())
            if path == "/debug/flightrecorder":
                # The live decision ring + retained black-box dumps — what
                # `bpe-tpu incident` sweeps across the fleet.
                return self._reply(200, serving.flightrecorder.debug_page())
            return self._reply(404, {"error": "unknown path"})

        def _reply_payload(self, data: bytes, request_id: str) -> None:
            """A binary KV migration payload (/kv/export success)."""
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("X-Request-Id", request_id)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_POST(self):  # noqa: N802 (stdlib API)
            if self._fault_gate():
                return
            if self.path == "/kv/import":
                return self._kv_import()
            if self.path == "/admin/evacuate":
                return self._admin_evacuate()
            if self.path == "/debug/dump":
                # Operator-initiated black-box flush: always dumps (force
                # past the cooldown) and answers with the dump itself.
                dump = serving.blackbox_dump("manual", force=True)
                return self._reply(200, dump)
            if self.path not in ("/generate", "/kv/export"):
                return self._reply(404, {"error": "unknown path"})
            migrate = self.path == "/kv/export"
            # Trace-id adoption: an inbound X-Request-Id (minted by the
            # fleet router, or sent by a client directly) becomes THE
            # request_id tagging this request's serve/* spans and engine
            # slot state — one id stitches router -> replica -> engine.
            # Absent, one is minted here so the echo below always holds.
            trace_id = (self.headers.get("X-Request-Id") or "").strip()
            trace_id = trace_id[:128] or uuid.uuid4().hex
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("body must be a JSON object")
                prompt_ids = body.get("prompt_ids")
                if prompt_ids is None:
                    prompt = body.get("prompt")
                    if prompt is None:
                        raise ValueError("need 'prompt' or 'prompt_ids'")
                    if serving.tokenizer is None:
                        raise ValueError(
                            "'prompt' needs a tokenizer; send 'prompt_ids'"
                        )
                    prompt_ids = serving.tokenizer.encode(prompt)
                result = serving.generate(
                    prompt_ids,
                    max_new_tokens=body.get("max_new_tokens"),
                    temperature=float(body.get("temperature", 1.0)),
                    top_k=body.get("top_k"),
                    top_p=body.get("top_p"),
                    seed=int(body.get("seed", 0)),
                    stop_id=body.get("stop_id"),
                    deadline_s=body.get("deadline_s"),
                    session=body.get("session"),
                    request_id=trace_id,
                    migrate=migrate,
                    # Codec negotiation (ISSUE 20): the importer-to-be
                    # says what v2 frames it can open; the export picks
                    # the best one both sides share (absent: raw).
                    kv_accept=(
                        self.headers.get("X-KV-Accept") if migrate else None
                    ),
                )
            except (QueueFullError, DuplicateRequestError) as exc:
                # Both are "this replica can't take THIS request right
                # now": 503 so the router fails over instead of judging
                # the caller (a duplicate id means OUR copy is still
                # running — a peer can serve the retry).
                return self._reply(
                    503, {"error": str(exc), "request_id": trace_id},
                    request_id=trace_id,
                )
            except (ValueError, TypeError, json.JSONDecodeError) as exc:
                return self._reply(
                    400, {"error": str(exc), "request_id": trace_id},
                    request_id=trace_id,
                )
            except RuntimeError as exc:
                # Engine not running / worker died / draining: a JSON 503
                # beats the stdlib handler's closed socket.
                return self._reply(
                    503, {"error": str(exc), "request_id": trace_id},
                    request_id=trace_id,
                )
            if result.finish_reason == "migrated":
                return self._reply_payload(
                    result.kv_payload, result.request_id
                )
            payload = {
                "request_id": result.request_id,
                "token_ids": list(result.token_ids),
                "finish_reason": result.finish_reason,
                "timings": result.timings(),
            }
            if serving.tokenizer is not None:
                ids = list(result.token_ids)
                if result.finish_reason == "stop":
                    ids = ids[:-1]  # the stop token itself isn't prose
                payload["completion"] = serving.tokenizer.decode(ids)
            self._reply(200, payload, request_id=result.request_id)

        def _kv_import(self):
            """POST /kv/import: graft a KV payload, decode to completion,
            answer with the standard generate JSON."""
            trace_id = (self.headers.get("X-Request-Id") or "").strip()
            trace_id = trace_id[:128] or None
            try:
                length = int(self.headers.get("Content-Length", 0))
                data = self.rfile.read(length)
                idem = (
                    self.headers.get("X-Idempotency-Key") or ""
                ).strip()[:128] or None
                handle = serving.submit_import(data, idempotency_key=idem)
                result = handle.result()
            except (QueueFullError, DuplicateRequestError) as exc:
                return self._reply(
                    503, {"error": str(exc)}, request_id=trace_id
                )
            except (ValueError, TypeError, KeyError, IndexError) as exc:
                # KeyError/IndexError: a JSON-valid but structurally
                # corrupt payload header (missing meta keys, bogus array
                # manifest) — the caller's bad payload, never a replica
                # fault (a dropped connection here would make the router
                # mark healthy decode replicas down and replay the same
                # corrupt bytes across the pool).
                return self._reply(
                    400, {"error": f"bad payload: {exc!r}"},
                    request_id=trace_id,
                )
            except RuntimeError as exc:
                return self._reply(
                    503, {"error": str(exc)}, request_id=trace_id
                )
            payload = {
                "request_id": result.request_id,
                "token_ids": list(result.token_ids),
                "finish_reason": result.finish_reason,
                "timings": result.timings(),
            }
            if serving.tokenizer is not None:
                ids = list(result.token_ids)
                if result.finish_reason == "stop":
                    ids = ids[:-1]
                payload["completion"] = serving.tokenizer.decode(ids)
            self._reply(200, payload, request_id=result.request_id)

        def _admin_evacuate(self):
            """POST /admin/evacuate: controller-initiated hot rebalancing
            — body ``{"target": base_url, "max_sessions"?, "timeout_s"?}``
            exports victim sessions and relays them to the target's
            ``/kv/import``.  Answers with the moved request ids once the
            exports happen (relays complete asynchronously)."""
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("body must be a JSON object")
                target = body.get("target")
                if not target or not isinstance(target, str):
                    raise ValueError("need 'target' (peer base URL)")
                out = serving.request_rebalance(
                    target,
                    max_sessions=int(body.get("max_sessions", 1)),
                    timeout_s=float(body.get("timeout_s", 30.0)),
                )
            except (ValueError, TypeError, json.JSONDecodeError) as exc:
                return self._reply(400, {"error": str(exc)})
            except (RuntimeError, TimeoutError) as exc:
                return self._reply(503, {"error": str(exc)})
            return self._reply(200, out)

    return ThreadingHTTPServer((host, port), Handler)
