"""Paged continuous-batching engine: the slot-pool contract on block-pool
KV memory, with radix prefix sharing and chunked prefill.

Drop-in peer of `serving.engine.SlotPoolEngine` (same admit/tick/release
lifecycle, same ``TickEvent`` vocabulary, same one-jitted-tick and
bounded-compile-count guarantees), with three new behaviors:

* **paged KV** — the cache is a flat pool of ``block_size``-token blocks
  (`models/decode.init_kv_pool`: per layer K and V arrays ``(num_blocks,
  block_size, kv_heads * d_head)``, block-major rows); each slot owns a
  *chain of block ids* in a block table that the decode tick and chunk
  prefill read through (gather) and write through (scatter).  How a pool
  is laid out, written and attended is its *cache kind*'s business
  (`models/decode.py`: `DenseRows`, `GroupedPages` for a config with
  sliding-window layers, `LatentRows` for latent attention, `RecurrentRows`
  where state-space layers keep a recurrent state a slot beside the K/V of
  the attention layers and a layer without a mixer keeps nothing, `EvaRows`
  for a summary-and-window cache); the two programs here are one forward
  over whichever the config has, and what the HOST must know of the kind
  is its host half's (`kvpool/host_cache.py`, ``PagedEngine.cache``).  Pool
  capacity is a knob (``num_blocks``) decoupled from ``slots *
  context_length``.  **One pool is alive at a time and no program copies
  it:** it rests on the device in the layout the programs index, and every
  program that updates it (tick, chunk, verify, copy-block, inject-block)
  takes it donated and hands it back (:meth:`PagedEngine._in_place`
  rebinds ``_pool`` from the program's output before anything else can
  read it); ``stats()`` says so from the compiled programs themselves
  (``kv_pool_aliased_bytes``, ``tick_temp_bytes``).  A program that raises
  after it was handed the pool has lost it: the next use of the pool
  fails ("Array has been deleted"), loudly;
* **radix prefix sharing** — prompts consult the `RadixPrefixCache`
  before computing: matched full blocks are reference-counted into the
  slot's table and prefill starts at the first unmatched position, so a
  shared system prompt is computed once per fleet replica, not once per
  request.  Token-identical to the dense engine by construction: K/V at
  a position is a pure function of the token prefix, and shared blocks
  are frozen (copy-on-write, never rewritten);
* **int8 KV blocks** (``kv_dtype="int8"``) — the pool stores quantized
  K/V with per-block-per-head f32 scales in parallel scale pools;
  writers quantize at scatter time (decode: rescale-on-grow, prefill:
  per-block scatter-max — `models/decode.py`), readers dequantize on
  gather (XLA path) or in registers (the paged-native kernel).  Per-token
  HBM traffic drops ~2x vs bf16 / 4x vs f32, and the freed bytes raise
  the block count at fixed memory;
* **chunked prefill** — prefill is a resumable state machine
  (:meth:`begin` / :meth:`prefill_step`): each step runs ONE
  ``prefill_chunk``-token chunk, so the serving worker can interleave
  decode ticks between a long prompt's chunks and decode p99 stays
  bounded under heavy prefill traffic (the worker owns the per-tick
  token budget — `serving.scheduler.PrefillBudget`).

Compile count: one program per chunk bucket + one tick, asserted by
:meth:`compiled_programs` exactly like the dense engine.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.decode import (
    cache_kind,
    chunk_cache,
    init_paged_pool,
    paged_forward,
    slot_cache,
)
from bpe_transformer_tpu.models.moe import W2_RELAID
from bpe_transformer_tpu.ops.quant import tree_bytes
from bpe_transformer_tpu.serving.engine import (
    TOP_K_DISABLED,
    TOP_P_DISABLED,
    SlotPoolEngine,
    TickEvent,
    default_prefill_buckets,
    filters_asked,
    gumbel_rows,
    next_token_logits,
    prepare_serving_weights,
    sample_tokens,
)
from bpe_transformer_tpu.serving.kvpool.blocks import BlockAllocator, NoFreeBlocksError
from bpe_transformer_tpu.serving.kvpool.host_cache import HOST_HALVES
from bpe_transformer_tpu.serving.kvpool.radix import RadixPrefixCache
from bpe_transformer_tpu.telemetry.spans import Phase
from bpe_transformer_tpu.utils.compile_cache import layered_program_options

__all__ = ["PagedEngine", "PagedSlotInfo", "NoFreeBlocksError"]


#: The two dispatch phases in parts, ``{program: (phase, parts)}``: each part
#: is a ``serve/<phase>/<part>`` annotation nested in its phase
#: (``serve/tick_dispatch`` is :meth:`PagedEngine.launch`'s own,
#: ``serve/prefill_chunk`` the serving worker's around
#: :meth:`PagedEngine.launch_chunk`), and its seconds, summed over every
#: launch, are the gauge ``launch_<program>_<part>_s``.  ``call`` is the
#: jitted call until it returns: argument transfers and the enqueue.
LAUNCH_PARTS = {
    "tick": ("tick_dispatch", ("prepare", "call", "after")),
    "chunk": ("prefill_chunk", ("key", "prepare", "call", "after")),
}


class _Part(Phase):
    """A part of a dispatch phase: a :class:`Phase` entered once a launch
    that sums its seconds, ``total_s``.  Made once an engine (a fresh one a
    launch costs the launch twice as much) and handed the engine's clock of
    the day as it is entered: ``with part.at(clock): ...``."""

    __slots__ = ("total_s",)

    def __init__(self, name: str):
        super().__init__(name)
        self.total_s = 0.0

    def at(self, clock) -> "_Part":
        self._clock = clock
        return self

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        self.total_s += self.dur_s


def _chunk_program(
    params, lm_head, pool, moe_pending, table_row, chunk, start, chunk_len,
    key, temp, top_k, top_p, carry, slot, final, *, config: ModelConfig,
    block_size: int,
):
    """One chunk-bucket-shaped prefill step + first-token sampling.  The
    sampled token/key are meaningful only for a prompt's FINAL chunk (the
    host passes the request key there and ignores the outputs earlier),
    so key handling stays byte-identical to the dense prefill program.

    The pool is donated and comes back updated.  ``moe_pending`` holds the
    routing counts of the chunks since the last tick where the cache kind
    carries any (None where it does not: no argument, no output); the
    chunk's own are added and the next tick hands them to the host, never
    this program.

    ``carry`` is the decode carry ``(tokens, positions, keys)`` as the
    launch before left it on the device.  A ``final`` chunk writes its
    ``slot``'s entry - the first token, the prompt's length, the split key
    - and so makes the slot a row of the next tick with no read in
    between; any other chunk hands the carry on as it came.  It rides
    through both programs in launch order, as ``moe_pending`` does, and is
    never donated: the launch before still holds the array the host has
    yet to read."""
    bucket = chunk.shape[1]
    cache = chunk_cache(
        config, table_row, start, chunk_len, bucket, block_size=block_size
    )
    logits, pool, counts = paged_forward(
        params, chunk, pool, cache, config, lm_head,
        row=jnp.clip(chunk_len - 1, 0, bucket - 1),
    )
    with jax.named_scope("key_split"):
        key, sub = jax.random.split(key)
    tok = sample_tokens(
        next_token_logits(logits, config), sub[None], temp[None],
        top_k[None], top_p[None],
    )[0]
    with jax.named_scope("carry_write"):
        tokens, positions, keys = carry
        carry = (
            tokens.at[slot].set(jnp.where(final, tok, tokens[slot])),
            positions.at[slot].set(
                jnp.where(final, start + chunk_len, positions[slot])
            ),
            keys.at[slot].set(jnp.where(final, key, keys[slot])),
        )
    return tok, carry, pool, None if counts is None else moe_pending + counts


def _tick_program(
    params, lm_head, pool, moe_pending, tables, tokens, positions, active,
    keys, temps, top_ks, top_ps, *, config: ModelConfig, block_size: int,
    fused: bool = False,
):
    """One engine tick over the paged pool — sampling identical to the
    dense `_tick_program`, decode reads/writes through the block table.
    ``fused=True`` runs the head projection + filter + sample tail as ONE
    Pallas kernel (see the dense twin's docstring).

    Where the cache kind carries routing counts, the last output is
    ``(counts since the last tick - the chunks' and its own -, its own, the
    next moe_pending)``: they come back with the tokens, in the same read,
    and the host keeps the running totals (int64; a device int32 would wrap
    within a day of this traffic).  The next ``moe_pending``, zeros, is a
    program's output like a chunk's, so that neither program ever sees a
    second kind of argument there (a host array would be one, and a compile
    in the middle of serving).  None where the kind carries none."""
    with jax.named_scope("key_split"):
        split = jax.vmap(jax.random.split)(keys)
        keys_next, subs = split[:, 0], split[:, 1]
    cache = slot_cache(config, tables, positions, active, block_size=block_size)
    out, pool, counts = paged_forward(
        params, tokens[:, None], pool, cache, config, lm_head, row=0,
        return_hidden=fused,
    )
    if fused:
        from bpe_transformer_tpu.kernels.pallas.sample import (
            fused_head_sample,
        )

        gumbel = gumbel_rows(subs, config.vocab_size)
        nxt = fused_head_sample(out, lm_head, temps, top_ks, top_ps, gumbel)
    else:
        # A vacant slot goes in as a greedy row: it asks for no search.
        nxt = sample_tokens(
            next_token_logits(out, config), subs,
            jnp.where(active, temps, 0.0), top_ks, top_ps,
        )
    nxt = jnp.where(active, nxt, tokens)
    keys_next = jnp.where(active[:, None], keys_next, keys)
    positions = jnp.where(active, positions + 1, positions)
    moe = None
    if counts is not None:
        since = moe_pending + counts
        moe = (since, counts, jnp.zeros_like(since))
    return nxt, positions, keys_next, pool, moe


def _copy_block_program(pool, src, dst):
    """Copy one block's rows (K/V and, for int8 pools, their scale rows)
    from pool block ``src`` to ``dst`` — the device half of a
    copy-on-write rewind (`PagedEngine.rewind`).  ``src``/``dst`` are
    traced scalars, so every copy shares one compiled program; the pool is
    donated, so one block moves and nothing else."""
    return [
        {name: arr.at[dst].set(arr[src]) for name, arr in layer.items()}
        for layer in pool
    ]


def _extract_block_program(pool, src):
    """Read one block's rows out of the pool (K/V + int8 scale rows) —
    the device half of :meth:`PagedEngine.export_slot`.  ``src`` is a
    traced scalar, so every block of every export shares ONE compiled
    program regardless of chain length."""
    return [{name: arr[src] for name, arr in layer.items()} for layer in pool]


def _inject_block_program(pool, rows, dst):
    """Write one migrated block's rows into the pool at block ``dst`` —
    the device half of :meth:`PagedEngine.import_slot` (the
    `_copy_block_program` idiom with host-supplied rows).  ``dst`` is a
    traced scalar and ``rows`` mirrors the pool's per-layer dict
    structure (one block of each array, as the pool holds it), so every
    grafted block shares ONE compiled program; the pool is donated."""
    return [
        {name: arr.at[dst].set(row[name]) for name, arr in layer.items()}
        for layer, row in zip(pool, rows)
    ]


def _blocks_to_wire(blocks: np.ndarray, kv_heads: int) -> np.ndarray:
    """K or V blocks as the pool holds them, ``(n, block_size, kv_heads *
    d_head)``, in the migration wire's heads-major form ``(n, kv_heads,
    block_size, d_head)`` (`kvpool/migrate.py`): one slot's blocks, on the
    host."""
    n, block_size, width = blocks.shape
    return np.ascontiguousarray(
        blocks.reshape(n, block_size, kv_heads, width // kv_heads)
        .transpose(0, 2, 1, 3)
    )


def _blocks_from_wire(blocks: np.ndarray) -> np.ndarray:
    """The inverse of :func:`_blocks_to_wire`."""
    n, kv_heads, block_size, d_head = blocks.shape
    return np.ascontiguousarray(
        blocks.transpose(0, 2, 1, 3).reshape(n, block_size, kv_heads * d_head)
    )


@dataclasses.dataclass
class PagedSlotInfo:
    """Host-side bookkeeping for one occupied slot (prefill + decode)."""

    prompt: np.ndarray  # int32 prompt ids (owned copy)
    prompt_len: int
    bucket: int  # the first computed chunk's program bucket (metrics)
    max_new_tokens: int  # effective: clamped to the context window
    stop_id: int | None
    seed: int
    temp_enc: np.float32
    top_k_enc: np.int32
    top_p_enc: np.float32
    block_ids: list  # every block this slot holds a reference on
    shared_len: int  # tokens reused from the prefix cache (block-aligned)
    next_pos: int  # prefill cursor: first position not yet computed
    generated: int = 0
    #: The serving request (= fleet trace id) occupying this slot — slot
    #: metadata for /statusz and cross-replica tracing, like the dense
    #: engine's SlotInfo.request_id.
    request_id: str | None = None


@dataclasses.dataclass
class _Launch:
    """One program in the device's queue whose result the host has yet to
    read: a decode tick, or a prompt's final chunk."""

    tokens: jax.Array  # a tick's token a slot; a final chunk's one token
    #: ``(slot, tenant)`` of every row the host will emit: the slots live at
    #: dispatch and who held each (a final chunk: its one slot).
    rows: tuple
    #: A tick's routing counts ``(since the last tick, its own)``, and what
    #: its cache kind counted of it at dispatch (`host_cache`'s
    #: ``before_tick``): the ``tick`` record's fields of that kind.
    moe: tuple | None = None
    counts: dict = dataclasses.field(default_factory=dict)
    first: bool = False  # a final chunk: the token is its slot's first


class PagedEngine:
    """Paged-KV continuous-batching engine (see module docstring).

    Single-threaded like the dense engine: one caller drives
    :meth:`begin`/:meth:`prefill_step`/:meth:`tick`/:meth:`release` (or
    the :meth:`admit` convenience that runs a whole prefill at once).

    **The decode carry lives on the device.**  ``tokens``, ``positions`` and
    ``keys`` of one launch are the next launch's arguments as the device
    arrays they came back as (``_carry``); a prompt's final chunk writes its
    slot's entry there.  So no program's result has to be read before the
    next program is queued: :meth:`launch` queues a tick and starts the copy
    of its tokens to the host, :meth:`collect` reads the oldest unread
    launch, and :meth:`tick` is the one after the other.  A caller that
    runs one launch ahead (the serving worker: ``launch()`` for tick n+1,
    then ``collect()`` for tick n) gets the same tokens in the same order.
    The host keeps its own ``_positions`` by arithmetic - a dispatched live
    slot advances by one - and reads back tokens only.

    *Finishing one launch late.*  A finish by length is a count the host
    has at dispatch: such a slot is not live in the next launch.  A finish
    by ``stop_id``, a cancellation and an eviction are found after the next
    launch was queued with the slot live; that row is stale.  Every launch
    records who held each live slot (``_tenant``, bumped at every
    :meth:`release`), and :meth:`collect` emits a row only to the tenant
    that launched it.  On the device a stale row is harmless: it writes
    position p+1 of the old chain through the table captured at dispatch,
    inside the chain :meth:`blocks_needed` reserved and beyond the prompt's
    full blocks (all the radix cache shares); a block freed and allocated
    again is overwritten by the new tenant's chunk, which is queued later
    and so runs later; a recurrent state row is reset by the next tenant's
    first chunk; a window group's recycled block is read by launch n
    before launch n+1 writes it, by device order; and the slot's entry of
    the carry is rewritten by the next tenant's final chunk before a tick
    runs it.  (Where the cache kind counts routing, a stale row's tokens
    are in the counts: the fixed-shape tick computed them.)

    Every host-side reader or writer of the carry outside the two halves
    - :meth:`rewind`, :meth:`export_slot`, :meth:`import_slot`, a
    speculative tick - goes through :meth:`read_carry` /
    :meth:`write_carry`, which :meth:`flush` first.
    """

    #: Optional flight recorder (telemetry/flightrecorder.py), attached by
    #: the serving engine: KV rewinds (speculative rejections, host-side
    #: truncations) are pool decisions the incident ring should show.
    recorder = None
    #: Seconds ``(dispatch, wait, emit)`` since the last :meth:`launch`
    #: began: that launch's dispatch — the ``_tick_jit`` call until it
    #: returns (argument transfer and enqueue) —, and of every launch read
    #: since then the host's blocked time on it and the Python loop from
    #: array to events — handed to the serving worker as plain data for
    #: its ``tick`` record.  Each is also a ``serve/tick_*`` annotation in
    #: a profiler's trace.
    last_tick_s = (0.0, 0.0, 0.0)

    def __init__(
        self,
        params,
        config: ModelConfig,
        *,
        slots: int = 8,
        block_size: int = 16,
        num_blocks: int | None = None,
        prefill_buckets: tuple[int, ...] | None = None,
        min_bucket: int = 16,
        prefill_chunk: int | None = None,
        prefix_cache: bool = True,
        kv_dtype: str | None = None,
        weight_dtype: str | None = None,
        fused_sampling: bool = False,
    ):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f'kv_dtype={kv_dtype!r} must be None (activation width) '
                'or "int8"'
            )
        ctx = config.context_length
        if block_size < 1 or ctx % block_size:
            raise ValueError(
                f"block_size={block_size} must divide "
                f"context_length={ctx}"
            )
        if config.dropless_block and weight_dtype is not None:
            raise ValueError(
                "weight_dtype quantizes the dense block's weight tree "
                "(ops/quant.py); this config's parallel block or double "
                "layer, its held, shared and zero experts are served at the "
                "activation width only"
            )
        #: The cache kind's host half, found here and nowhere else: what
        #: the kind refuses, its tables and chains, what a launch lays out
        #: and counts are asked of this object, never of the config.
        self.cache = HOST_HALVES[cache_kind(config)](
            config, slots=slots, block_size=block_size,
            prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
            kv_dtype=kv_dtype is not None, fused_sampling=fused_sampling,
        )
        self.config = config
        self.n_slots = slots
        self.block_size = block_size
        self.blocks_per_slot = self.cache.blocks_per_slot
        self.max_chain = self.cache.max_chain
        if prefill_chunk is None:
            prefill_chunk = self.cache.default_prefill_chunk
        if prefill_chunk < 1 or (
            prefill_chunk < ctx and prefill_chunk % block_size
        ):
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be a positive "
                f"multiple of block_size={block_size} (chunks after the "
                "first must start block-aligned)"
            )
        self.prefill_chunk = min(prefill_chunk, ctx)

        if prefill_buckets is None:
            prefill_buckets = default_prefill_buckets(ctx, min_bucket)
        ladder = tuple(sorted(set(prefill_buckets)))
        if not ladder or ladder[-1] > ctx:
            raise ValueError(
                f"prefill buckets {ladder} must be non-empty and <= "
                f"context_length={ctx}"
            )
        if ladder[-1] < ctx:
            ladder = ladder + (ctx,)
        # Chunk program shapes: the bucket ladder capped at the chunk size
        # (a chunk is never longer than prefill_chunk, so larger buckets
        # would never compile anyway — the compile bound only shrinks).
        chunk_ladder = tuple(b for b in ladder if b < self.prefill_chunk)
        self.buckets = chunk_ladder + (self.prefill_chunk,)

        # Pool capacity: default exactly the dense slot pool's (every slot
        # can hold a full context) + the reserved trash block; prefix
        # sharing makes the same capacity serve MORE concurrent work.
        if num_blocks is None:
            num_blocks = slots * self.max_chain + 1
        self.allocator = BlockAllocator(num_blocks, block_size)
        self.prefix_cache = (
            RadixPrefixCache(self.allocator) if prefix_cache else None
        )

        act_dtype = jnp.dtype(config.activation_dtype)
        # Compute-dtype cast + optional per-channel int8 quantization:
        # every program (chunk prefill, tick, spec verify) then streams
        # 1-byte weights and dequantizes in registers.
        (
            self._params, self._lm_head, self.weight_dtype,
            self.params_bytes, self.tick_weight_bytes,
        ) = prepare_serving_weights(params, config, weight_dtype)
        #: Expert layers whose down projection this engine holds relaid
        #: (`models/moe.serving_layout`): the ``moe_relaid_layers`` gauge.
        self.moe_relaid_layers = sum(
            W2_RELAID in layer.get("ffn", ()) for layer in self._params["layers"]
        )
        self.fused_sampling = bool(fused_sampling)
        self._pool = init_paged_pool(
            config, num_blocks, block_size, act_dtype, kv_dtype=kv_dtype,
            **self.cache.pool_keywords,
        )
        #: "int8" for quantized pools, else the activation dtype name —
        #: the /statusz + stats() label.
        self.kv_dtype = kv_dtype or str(act_dtype)
        #: How the tick's rows attend: the cache kind's choice.
        self.tick_attention_path = cache_kind(config).attention_path(
            config, True, self.blocks_per_slot, self._first_attention_entry()
        )
        self.cache.settle(self._pool, self.tick_attention_path, self.buckets)
        #: Resident bytes of the whole KV pool (scale pools included):
        #: int8 quarters the f32 pool (halves bf16) at fixed block count -
        #: or, held fixed, buys 2-4x the blocks.  What the kind keeps a
        #: slot and not a position (state-space layers' state rows) is
        #: counted apart.
        self.kv_pool_bytes = tree_bytes(self._pool) - self.cache.state_bytes
        self.kv_bytes_per_token = self.cache.kv_bytes_per_token(
            1 if kv_dtype == "int8" else act_dtype.itemsize
        )
        #: Key positions the ticks' live slots held, and key positions
        #: their tables address (every slot's whole row, what a gather
        #: through the table reads): `tick_live_key_share`.
        self.tick_live_keys = 0
        self.tick_table_keys = 0
        #: What the cache kind counted of the last tick read, and its
        #: running counts of the chunks' work: the ``tick`` record's.
        self.last_tick_counts = self.cache.no_tick
        self.chunk_counts = self.cache.chunk_counts
        #: Routing counts of the dropless expert layers, summed over layers:
        #: [tokens routed, assignments on held experts, non-empty expert
        #: groups, assignments on zero experts] (the device's vector ends at
        #: three where the config has no zero experts: the fourth reads 0).
        #: The totals are kept here; the device carries only the chunks'
        #: counts since the last tick, which the tick hands over with its
        #: own.
        self.moe_counts = np.zeros(4, np.int64)
        self.last_tick_moe_rows_local = 0
        self.last_tick_moe_zero_assignments = 0
        self._moe_pending = cache_kind(config).zero_counts(config)
        #: The decode carry ``(tokens, positions, keys)``, on the device.
        self._carry = jax.device_put((
            np.zeros(slots, np.int32), np.zeros(slots, np.int32),
            np.zeros((slots, 2), np.uint32),
        ))
        #: The host's own positions, kept by arithmetic.
        self._positions = np.zeros(slots, np.int32)
        #: The slots the next launch runs.
        self._active = np.zeros(slots, bool)
        #: Tokens a slot may still launch: a finish by length is known at
        #: dispatch, and takes the slot out of the next launch.
        self._budget = np.zeros(slots, np.int64)
        #: Who holds a slot: bumped at every release.
        self._tenant = np.zeros(slots, np.int64)
        #: Launches the host has yet to read, oldest first, and the events
        #: a :meth:`flush` read on a caller's behalf, held for the next
        #: :meth:`collect`.
        self._unread: collections.deque = collections.deque()
        self._held: list[TickEvent] = []
        self._temps = np.zeros(slots, np.float32)
        self._top_ks = np.full(slots, TOP_K_DISABLED, np.int32)
        self._top_ps = np.full(slots, TOP_P_DISABLED, np.float32)
        self._slots: list[PagedSlotInfo | None] = [None] * slots
        self._prefilling: list[int] = []  # slots mid-prefill, begin order

        # Per-engine jit closures: compiled_programs() is an exact
        # per-engine compile counter, as in the dense engine.  The pool
        # (argument 2) is donated: both programs update it in place.  With
        # one pool alive the chip has memory to spare, and XLA then writes
        # every layer's code out: ask for the layers as calls
        # (`layered_program_options`), as the train step does - where the
        # cache kind says so.
        options = layered_program_options() if self.cache.layers_as_calls else None
        self._chunk_jit = jax.jit(
            functools.partial(
                _chunk_program, config=config, block_size=block_size
            ),
            donate_argnums=(2,),
            compiler_options=options,
        )
        self._tick_jit = jax.jit(
            functools.partial(
                _tick_program, config=config, block_size=block_size,
                fused=self.fused_sampling,
            ),
            donate_argnums=(2,),
            compiler_options=options,
        )
        # Copy-on-write block copy (rewind into a shared block): compiled
        # only the first time a CoW rewind actually runs.  Per-engine
        # partial for the same reason as the migration jits below — a
        # bare ``jax.jit(fn)`` shares one cache across engines (keyed by
        # function identity), which would make compiled_programs() read
        # ANOTHER engine's CoW compile as this engine's.
        self._copy_jit = jax.jit(
            functools.partial(_copy_block_program), donate_argnums=(0,)
        )
        # KV migration halves (ISSUE 15): per-block extract (export) and
        # inject (import) — each compiled only when a migration runs, and
        # ONCE regardless of chain length (traced block ids).  Wrapped in
        # per-engine partials so compiled_programs() stays an exact
        # per-engine counter (bare ``jax.jit(fn)`` wrappers share one
        # cache across engines, keyed by function identity).
        self._extract_jit = jax.jit(
            functools.partial(_extract_block_program)
        )
        self._inject_jit = jax.jit(
            functools.partial(_inject_block_program), donate_argnums=(0,)
        )
        #: ``{program: (aliased bytes, temporary bytes)}`` from XLA's
        #: ``memory_analysis()`` of each pool program this engine has run
        #: (:meth:`_in_place`), and the pool's own size on the device.
        self._program_memory: dict = {}
        self._pool_device_bytes = sum(
            arr.on_device_size_in_bytes()
            for arr in jax.tree_util.tree_leaves(self._pool)
        )

        self.ticks = 0
        #: Ticks queued while the one before was unread, rows a launch
        #: computed for a tenant that had left before they were read, and
        #: the times a reader or writer of the carry had to read unread
        #: launches first.
        self.ticks_overlapped = 0
        self.tick_stale_rows = 0
        self.carry_flushes = 0
        #: Ticks in which a live sampled slot asked for top-k / for top-p:
        #: how often each of the sampler's searches ran.
        self.sample_topk_ticks = 0
        self.sample_topp_ticks = 0
        self.tokens_emitted = 0
        #: Chunks queued, and the parts of the two dispatch phases
        #: (`LAUNCH_PARTS`).
        self.chunk_launches = 0
        self._parts = {
            (program, part): _Part(f"serve/{phase}/{part}")
            for program, (phase, parts) in LAUNCH_PARTS.items()
            for part in parts
        }
        #: The clock of the tick phases; the serving worker sets its own.
        self.clock = time.monotonic

    # ------------------------------------------------------------- queries

    @property
    def active_count(self) -> int:
        return int(self._active.sum())

    @property
    def free_slots(self) -> int:
        return sum(1 for info in self._slots if info is None)

    @property
    def unread(self) -> int:
        """Launches whose events :meth:`collect` has yet to hand over: those
        the host has not read, and as one more what a :meth:`flush` read
        and holds."""
        return len(self._unread) + bool(self._held)

    def compiled_programs(self) -> int:
        """XLA programs compiled by this engine so far — bounded by
        ``len(self.buckets) + 1`` (one chunk program per bucket + the
        tick), plus one more once a copy-on-write :meth:`rewind` has
        run, and one each for the migration extract/inject programs once
        an :meth:`export_slot`/:meth:`import_slot` has run (a pure
        decode-role replica therefore stays within tick + inject — the
        chunk ladder never compiles there)."""
        return (
            self._chunk_jit._cache_size()
            + self._tick_jit._cache_size()
            + self._copy_jit._cache_size()
            + self._extract_jit._cache_size()
            + self._inject_jit._cache_size()
        )

    def _first_attention_entry(self):
        """The pool entry of the first attention (sub)layer."""
        first = next(
            layer for layer in range(self.config.num_layers)
            if self.config.layer_mixer(layer) == "attn"
        )
        return self._pool[first * self.config.attn_sublayers]

    def bucket_for(self, length: int) -> int:
        """The smallest chunk bucket holding ``length`` tokens (lengths
        beyond the chunk size run as multiple chunks of the largest)."""
        for b in self.buckets:
            if length <= b:
                return b
        return self.buckets[-1]

    def slot_bucket(self, slot: int) -> int | None:
        """The slot's first computed chunk bucket (metrics labeling)."""
        info = self._slots[slot]
        return None if info is None else info.bucket

    def slot_shared_len(self, slot: int) -> int:
        """Prompt tokens the slot reused from the prefix cache."""
        info = self._slots[slot]
        return 0 if info is None else info.shared_len

    def pending_prefills(self) -> tuple[int, ...]:
        """Slots with prefill chunks still to run, in begin order."""
        return tuple(self._prefilling)

    def prefill_remaining(self, slot: int) -> int:
        info = self._slots[slot]
        if info is None:
            return 0
        return info.prompt_len - info.next_pos

    def next_chunk_tokens(self, slot: int) -> int:
        """The token cost of the next :meth:`prefill_step` on ``slot``
        (what the serving worker charges against its per-tick budget)."""
        return min(self.prefill_chunk, self.prefill_remaining(slot))

    def pending_prefill_tokens(self) -> int:
        return sum(self.prefill_remaining(s) for s in self._prefilling)

    def gauges(self) -> dict:
        """The kvpool operational gauges (/metrics + kind="kvpool")."""
        out = self.allocator.gauges()
        if self.prefix_cache is not None:
            out.update(self.prefix_cache.gauges())
        else:
            out.update(
                {
                    "prefix_cache_hits": 0,
                    "prefix_cache_misses": 0,
                    "prefix_hit_rate": None,
                    "prefix_cache_nodes": 0,
                }
            )
        # The groups by name.  One group: the full group is the pool.
        out["kv_full_blocks_total"] = self.allocator.usable_blocks
        out["kv_full_blocks_free"] = self.allocator.free_count
        out.update(self.cache.gauges())
        out["tick_attention_path"] = self.tick_attention_path
        out["tick_live_key_share"] = (
            100.0 * self.tick_live_keys / self.tick_table_keys
            if self.tick_table_keys else None
        )
        out["moe_tokens_routed"] = int(self.moe_counts[0])
        out["moe_rows_local"] = int(self.moe_counts[1])
        out["moe_expert_groups"] = int(self.moe_counts[2])
        out["moe_zero_assignments"] = int(self.moe_counts[3])
        out["moe_relaid_layers"] = self.moe_relaid_layers
        out["prefill_pending_tokens"] = self.pending_prefill_tokens()
        out["prefill_pending_slots"] = len(self._prefilling)
        out["ticks_overlapped"] = self.ticks_overlapped
        out["tick_stale_rows"] = self.tick_stale_rows
        out["carry_flushes"] = self.carry_flushes
        out["chunk_launches"] = self.chunk_launches
        for (program, part), timed in self._parts.items():
            out[f"launch_{program}_{part}_s"] = timed.total_s
        out["kv_pool_bytes"] = self.kv_pool_bytes
        out["kv_bytes_per_token"] = self.kv_bytes_per_token
        # From the compiled programs themselves (`_in_place`): the pool's
        # bytes less what the least-aliasing program run so far does NOT
        # alias - kv_pool_bytes while every program takes the whole pool
        # donated - and the tick's temporaries, where a pool-sized layout
        # copy would show.  None before a program has run.
        memory = self._program_memory
        out["kv_pool_aliased_bytes"] = None
        if memory:
            aliased = min(aliased for aliased, _ in memory.values())
            out["kv_pool_aliased_bytes"] = max(
                self.kv_pool_bytes
                - max(self._pool_device_bytes - aliased, 0), 0
            )
        out["tick_temp_bytes"] = memory["tick"][1] if "tick" in memory else None
        return out

    def slot_states(self) -> list[dict]:
        """Per-slot occupancy snapshot (the ``/statusz`` view), extended
        with paged-memory facts: blocks held, shared-prefix tokens, and
        prefill progress for slots still chunking."""
        states: list[dict] = []
        for slot in range(self.n_slots):
            info = self._slots[slot]
            if info is None:
                states.append({"slot": slot, "active": False})
                continue
            states.append(
                {
                    "slot": slot,
                    "active": bool(self._active[slot]),
                    "position": int(self._positions[slot]),
                    "prompt_len": info.prompt_len,
                    "bucket": info.bucket,
                    "generated": info.generated,
                    "max_new_tokens": info.max_new_tokens,
                    "blocks": len(info.block_ids),
                    "shared_prefix_tokens": info.shared_len,
                    "prefill_pos": info.next_pos,
                    "request_id": info.request_id,
                }
            )
        return states

    # ------------------------------------------------------------ lifecycle

    def _in_place(self, program: str, jit_fn, *args, pool_at: int = -1):
        """Run a program that takes the pool donated and returns it updated
        as its only output or as output ``pool_at`` (the chunk and tick
        programs' last is the routing counts); ``_pool`` is rebound
        before anything else can read the buffers the program consumed.  The
        first run of each ``program`` also notes what XLA says of its
        memory, before the call while its arguments are live: the call's
        lowering is this one, so the program is compiled once."""
        if program not in self._program_memory:
            analysis = jit_fn.lower(*args).compile().memory_analysis()
            self._program_memory[program] = (
                int(analysis.alias_size_in_bytes),
                int(analysis.temp_size_in_bytes),
            )
        out = jit_fn(*args)
        self._pool = out[pool_at] if isinstance(out, tuple) else out
        return out

    def _validate(self, prompt: np.ndarray, max_new_tokens: int) -> None:
        plen = prompt.shape[0]
        ctx = self.config.context_length
        if plen < 1:
            raise ValueError("prompt must contain at least one token")
        if plen > ctx - 1:
            raise ValueError(
                f"prompt of {plen} tokens leaves no room to generate in a "
                f"context of {ctx}"
            )
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case block reservation for one request (before any
        prefix-cache credit): every position the request may ever write."""
        ctx = self.config.context_length
        eff = min(max_new_tokens, ctx - prompt_len)
        return self.cache.blocks_needed(min(prompt_len + eff, ctx))

    def _alloc_blocks(self, n: int) -> list:
        """Allocate ``n`` fresh blocks, evicting prefix-cache LRU leaves to
        cover a shortfall first (the same discipline :meth:`begin` applies
        to admissions); raises :class:`NoFreeBlocksError` when the pool
        cannot cover it even then."""
        shortfall = n - self.allocator.free_count
        if shortfall > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict(shortfall)
        return self.allocator.alloc(n)

    def extend_blocks(self, slot: int, upto_len: int) -> None:
        """Grow ``slot``'s block chain to cover ``upto_len`` token
        positions (speculative-decoding scratch: the verify pass writes a
        few positions beyond the admission's worst-case reservation, and
        :meth:`rewind` returns whatever the acceptance didn't keep).
        Raises :class:`NoFreeBlocksError` when the pool is dry — the
        caller shrinks its speculation window instead of parking."""
        self.cache.refuse("extend_blocks")
        info = self._slots[slot]
        if info is None:
            raise ValueError(f"slot {slot} is not occupied")
        need = -(-min(upto_len, self.config.context_length) // self.block_size)
        extra = need - len(info.block_ids)
        if extra <= 0:
            return
        fresh = self._alloc_blocks(extra)
        start = len(info.block_ids)
        info.block_ids.extend(fresh)
        self.cache.tables[slot, start: start + len(fresh)] = fresh

    def rewind(
        self, slot: int, new_len: int, *, keep_blocks: int | None = None
    ) -> dict:
        """Roll ``slot``'s written-KV frontier back to ``new_len`` tokens:
        positions ``0 .. new_len-1`` stay valid, everything beyond is
        abandoned (speculative-decoding rejection, or any host-side
        re-scoring that truncates a sequence).

        * **frontier rollback within a block** is pure bookkeeping — the
          abandoned rows stay in the pool but every reader masks keys by
          the slot's position, so they are invisible until overwritten;
        * **block release across boundaries** — chain blocks wholly beyond
          the frontier are deref'd (returned to the pool when this was the
          last reference).  ``keep_blocks`` floors the chain length:
          mid-flight callers pass their admission-time reservation so a
          rewind can never give away blocks the request still needs to
          finish (only speculative scratch beyond it is released);
        * **copy-on-write** — if the block the NEXT write lands in is
          shared (radix-indexed, or referenced by another slot), it is
          replaced by a fresh device copy and the shared copy is never
          mutated.  The copy may evict prefix-cache leaves and raises
          :class:`NoFreeBlocksError` when the pool cannot supply the
          replacement block;
        * **int8 pools** — block scales are monotone within an occupancy:
          a rewound row's magnitude stays folded into its block's scale
          until the block is fully vacated (the next write at offset 0
          resets it).  Valid rows keep their values (they were rescaled by
          ``old/new`` whenever the scale grew); writes after the rewind
          quantize against the possibly-inflated scale, so their precision
          is bounded by it — the cost of per-block scales, documented
          rather than repaired.

        Returns ``{"released": n_blocks, "cow": bool}``.  The caller owns
        position/sampling state (:meth:`write_carry`) — this is a KV-memory
        primitive.  Unread launches are read first (:meth:`flush`): what
        they emitted is part of the frontier the caller rolls back from.
        """
        self.cache.refuse("rewind")
        self.flush()
        info = self._slots[slot]
        if info is None:
            raise ValueError(f"slot {slot} is not occupied")
        if slot in self._prefilling:
            raise ValueError(f"slot {slot} is mid-prefill; cannot rewind")
        if new_len < 0 or new_len > self.config.context_length:
            raise ValueError(
                f"new_len={new_len} outside [0, "
                f"{self.config.context_length}]"
            )
        if new_len < int(self._positions[slot]):
            self.cache.refuse("rewind_below_frontier")
        bs = self.block_size
        needed = -(-new_len // bs)
        floor = max(needed, keep_blocks or 0)
        released = 0
        if floor < len(info.block_ids):
            dropped = info.block_ids[floor:]
            info.block_ids = info.block_ids[:floor]
            self.allocator.deref(dropped)
            released = len(dropped)
            self.cache.tables[slot, floor:] = 0
        # The block the next write lands in must be exclusively owned:
        # rewinding into a radix-shared region would otherwise scribble
        # over blocks other chains still read.
        cow = False
        idx = new_len // bs
        if idx < len(info.block_ids):
            shared = info.block_ids[idx]
            if self.allocator.refcount(shared) > 1:
                fresh = self._alloc_blocks(1)[0]
                self._in_place(
                    "copy_block", self._copy_jit, self._pool,
                    np.int32(shared), np.int32(fresh),
                )
                self.allocator.deref([shared])
                info.block_ids[idx] = fresh
                self.cache.tables[slot, idx] = fresh
                cow = True
        info.shared_len = min(info.shared_len, new_len)
        if self.recorder is not None:
            # Coalesced per slot: spec verify passes rewind every tick —
            # one ring entry per slot's run of rewinds, host-side only.
            self.recorder.record(
                "rewind",
                coalesce=True,
                request_id=info.request_id,
                slot=slot,
                new_len=new_len,
                released=released or None,
                cow=cow or None,
            )
        return {"released": released, "cow": cow}

    # ------------------------------------------------------------ migration

    def export_slot(self, slot: int, extra_meta: dict | None = None) -> dict:
        """Serialize ``slot`` into a self-describing migration payload
        (ISSUE 15): the slot's pool rows (per block, through one compiled
        extract program; int8 pools ship their per-block-per-head scale
        rows alongside) plus everything needed to continue the generation
        bit-for-bit on another replica — the prompt, the prefill frontier
        (mid-prefill exports allowed), and, for finished prefixes, the
        full decode state including the RNG key, so greedy AND seeded
        sampling round-trip token-identically.

        Strictly read-only: refcounts, the radix index, and every pool row
        are untouched — a radix-shared source block is never mutated (or
        released) by exporting a slot that references it.  The caller owns
        releasing the slot once the payload has landed.  ``extra_meta``
        (serving-layer fields: emitted tokens, timings, the token history
        a speculative importer re-prefills its draft from) is merged into
        the payload meta.
        """
        self.cache.refuse("export_slot")
        tokens, positions, keys = self.read_carry()
        info = self._slots[slot]
        if info is None:
            raise ValueError(f"slot {slot} is not occupied")
        decoding = bool(self._active[slot])
        if not decoding and slot not in self._prefilling:
            raise ValueError(f"slot {slot} has no exportable state")
        # Ship only WRITTEN blocks: the chain holds the admission's
        # worst-case reservation, but rows beyond the written frontier
        # (decode: positions < position; mid-prefill: < next_pos) are
        # recycled garbage the importer re-reserves locally — shipping
        # them would inflate the transfer (the disaggregated path's
        # dominant cost) with bytes nobody reads.
        frontier = int(positions[slot]) if decoding else info.next_pos
        n_written = -(-frontier // self.block_size)
        ids = info.block_ids[:n_written]
        per_block = [
            jax.tree_util.tree_map(
                np.asarray, self._extract_jit(self._pool, np.int32(bid))
            )
            for bid in ids
        ]
        # One slot's blocks, stacked on the host and turned from the
        # pool's rows into the wire's heads-major blocks (scale rows are
        # the same in both).
        kv_heads = self.config.num_kv_heads or self.config.num_heads
        layers = []
        for li, layer in enumerate(self._pool):
            shipped = {}
            for name, arr in layer.items():
                blocks = np.stack(
                    [blk[li][name] for blk in per_block]
                ) if per_block else np.zeros((0,) + arr.shape[1:], arr.dtype)
                shipped[name] = (
                    _blocks_to_wire(blocks, kv_heads) if name in ("k", "v")
                    else blocks
                )
            layers.append(shipped)
        meta = {
            "format": 1,
            "block_size": self.block_size,
            "kv_dtype": self.kv_dtype,
            "num_layers": self.config.num_layers,
            "kv_heads": kv_heads,
            "d_head": self.config.d_head,
            "context_length": self.config.context_length,
            "n_blocks": len(ids),
            "prompt": [int(t) for t in info.prompt],
            "prompt_len": info.prompt_len,
            "next_pos": info.next_pos,
            "decoding": decoding,
            "generated": info.generated,
            "max_new_tokens": info.max_new_tokens,
            "stop_id": info.stop_id,
            "seed": info.seed,
            "temperature": float(info.temp_enc),
            "top_k": int(info.top_k_enc),
            "top_p": float(info.top_p_enc),
            "token": int(tokens[slot]),
            "position": int(positions[slot]),
            "key": [int(k) for k in keys[slot]],
            "request_id": info.request_id,
        }
        if extra_meta:
            meta.update(extra_meta)
        return {"meta": meta, "layers": layers}

    def validate_import_meta(self, meta: dict) -> None:
        """Reject a payload this engine cannot graft — geometry or pool
        dtype mismatch is a configuration error, caught before any block
        is allocated (HTTP 400, not a half-grafted slot)."""
        self.cache.refuse("import_slot")
        if meta.get("format") != 1:
            raise ValueError(
                f"unsupported payload format {meta.get('format')!r}"
            )
        kv_heads = self.config.num_kv_heads or self.config.num_heads
        expect = {
            "block_size": self.block_size,
            "kv_dtype": self.kv_dtype,
            "num_layers": self.config.num_layers,
            "kv_heads": kv_heads,
            "d_head": self.config.d_head,
            "context_length": self.config.context_length,
        }
        for key, want in expect.items():
            got = meta.get(key)
            if got != want:
                raise ValueError(
                    f"payload {key}={got!r} does not match this engine's "
                    f"{want!r}"
                )
        if meta["n_blocks"] > self.blocks_per_slot:
            raise ValueError(
                f"payload carries {meta['n_blocks']} blocks; a slot here "
                f"holds at most {self.blocks_per_slot}"
            )
        need = max(
            meta["n_blocks"],
            self.blocks_needed(meta["prompt_len"], meta["max_new_tokens"]),
        )
        if need > self.allocator.usable_blocks:
            # Could NEVER land (parking would deadlock the import queue).
            raise ValueError(
                f"grafting needs {need} KV blocks; the pool holds "
                f"{self.allocator.usable_blocks}"
            )
        if not meta["decoding"] and meta["next_pos"] % self.block_size:
            raise ValueError(
                f"mid-prefill frontier {meta['next_pos']} is not "
                f"block-aligned (block_size={self.block_size})"
            )

    def validate_import_payload(self, payload: dict) -> None:
        """:meth:`validate_import_meta` plus a STRUCTURAL check of the
        shipped arrays against the meta — a payload whose header parses
        but whose rows are inconsistent (wrong shape/dtype, missing
        scale arrays, short block dimension) must fail at the transport
        (HTTP 400) rather than inside the worker thread, where the
        resulting inject error would kill the replica and leak the
        freshly allocated chain."""
        meta = payload["meta"]
        self.validate_import_meta(meta)
        layers = payload["layers"]
        if len(layers) != self.config.num_layers:
            raise ValueError(
                f"payload ships {len(layers)} layers; this engine has "
                f"{self.config.num_layers}"
            )
        names = set(self._pool[0])
        n = int(meta["n_blocks"])
        kv_heads = self.config.num_kv_heads or self.config.num_heads
        wire_block = (kv_heads, self.block_size, self.config.d_head)
        for li, (layer, pool_layer) in enumerate(zip(layers, self._pool)):
            if set(layer) != names:
                raise ValueError(
                    f"payload layer {li} arrays {sorted(layer)} do not "
                    f"match the pool's {sorted(names)}"
                )
            for name, arr in layer.items():
                # K/V ride the wire as heads-major blocks, scales as rows.
                want_shape = (n,) + (
                    wire_block if name in ("k", "v") else (kv_heads,)
                )
                want_dtype = pool_layer[name].dtype
                arr = np.asarray(arr)
                if tuple(arr.shape) != want_shape or arr.dtype != want_dtype:
                    raise ValueError(
                        f"payload layer {li} array {name!r} is "
                        f"{arr.dtype}{tuple(arr.shape)}; this pool wants "
                        f"{want_dtype}{want_shape}"
                    )

    def import_slot(self, payload: dict) -> int:
        """Graft a migration payload into this pool: fresh blocks
        allocated (prefix-cache LRU leaves evicted to cover a shortfall,
        :class:`NoFreeBlocksError` raised when the pool still cannot —
        the caller parks and retries), rows scattered via one compiled
        per-block inject program, and the generation state restored so
        the next :meth:`tick` (or :meth:`prefill_step`, for mid-prefill
        payloads) continues bit-for-bit.  A finished prefix's full prompt
        blocks are indexed into the radix cache, so migrated sessions
        seed prefix sharing on their new home.  Returns the slot."""
        meta = payload["meta"]
        self.validate_import_payload(payload)
        free = [s for s in range(self.n_slots) if self._slots[s] is None]
        if not free:
            raise RuntimeError("no free slot")
        slot = free[0]
        # The payload ships only WRITTEN blocks; the rest of the
        # admission's worst-case reservation is re-reserved locally
        # (fresh blocks, no inject — their rows get written by this
        # replica's own chunks/ticks).
        n = int(meta["n_blocks"])
        chain = max(
            n,
            self.blocks_needed(
                int(meta["prompt_len"]), int(meta["max_new_tokens"])
            ),
        )
        fresh = self._alloc_blocks(chain)
        self.cache.admit(slot, fresh)
        # The wire's heads-major blocks become the pool's rows on the host
        # (one slot's blocks), then land through the inject program.
        at_rest = [
            {
                name: _blocks_from_wire(np.asarray(arr)) if name in ("k", "v")
                else np.asarray(arr)
                for name, arr in layer.items()
            }
            for layer in payload["layers"]
        ]
        for i, dst in enumerate(fresh[:n]):
            rows = [
                {name: arr[i] for name, arr in layer.items()}
                for layer in at_rest
            ]
            self._in_place(
                "inject_block", self._inject_jit, self._pool, rows,
                np.int32(dst),
            )

        prompt = np.asarray(meta["prompt"], np.int32)
        plen = int(meta["prompt_len"])
        info = PagedSlotInfo(
            prompt=prompt,
            prompt_len=plen,
            bucket=self.bucket_for(min(plen, self.prefill_chunk)),
            max_new_tokens=int(meta["max_new_tokens"]),
            stop_id=meta["stop_id"],
            seed=int(meta["seed"]),
            temp_enc=np.float32(meta["temperature"]),
            top_k_enc=np.int32(meta["top_k"]),
            top_p_enc=np.float32(meta["top_p"]),
            block_ids=fresh,
            shared_len=0,
            next_pos=int(meta["next_pos"]),
            generated=int(meta["generated"]),
            request_id=meta.get("request_id"),
        )
        self._slots[slot] = info
        if meta["decoding"]:
            tokens, positions, keys = self.read_carry()
            tokens[slot] = int(meta["token"])
            positions[slot] = int(meta["position"])
            keys[slot] = np.asarray(meta["key"], np.uint32)
            self.write_carry(tokens, positions, keys)
            self._temps[slot] = info.temp_enc
            self._top_ks[slot] = info.top_k_enc
            self._top_ps[slot] = info.top_p_enc
            self._budget[slot] = info.max_new_tokens - info.generated
            self._active[slot] = True
            if self.prefix_cache is not None:
                full = plen // self.block_size
                if full:
                    self.prefix_cache.insert(
                        [int(t) for t in prompt[: full * self.block_size]],
                        fresh[:full],
                    )
        else:
            self._prefilling.append(slot)
        return slot

    def begin(
        self,
        prompt_ids,
        *,
        max_new_tokens: int,
        temperature: float = 1.0,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int = 0,
        stop_id: int | None = None,
        request_id: str | None = None,
    ) -> int:
        """Reserve a slot + its worst-case block chain (prefix-cache blocks
        reused by reference) and queue the prompt for chunked prefill.
        Raises ``RuntimeError`` when no slot is free,
        :class:`NoFreeBlocksError` when the pool (after cache eviction)
        cannot cover the reservation — the caller parks the admission and
        retries as decode retirements free blocks."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        self._validate(prompt, max_new_tokens)
        plen = int(prompt.shape[0])
        free = [s for s in range(self.n_slots) if self._slots[s] is None]
        if not free:
            raise RuntimeError("no free slot")
        slot = free[0]

        need = self.blocks_needed(plen, max_new_tokens)
        if need > self.allocator.usable_blocks:
            raise ValueError(
                f"request needs {need} KV blocks; the pool holds "
                f"{self.allocator.usable_blocks}"
            )
        matched: list[int] = []
        if self.prefix_cache is not None:
            matched = self.prefix_cache.match([int(t) for t in prompt])
        try:
            fresh = self._alloc_blocks(need - len(matched))
        except NoFreeBlocksError:
            if matched:
                self.allocator.deref(matched)
            raise
        block_ids = matched + fresh
        try:
            self.cache.admit(slot, block_ids)
        except NoFreeBlocksError:
            self.allocator.deref(fresh)
            raise

        shared_len = len(matched) * self.block_size
        if self.prefix_cache is not None:
            # Charged only now that the admission proceeds: a parked
            # (block-starved) request re-matches on every retry and must
            # not inflate the hit/miss counters.
            self.prefix_cache.charge(plen, shared_len)
        ctx = self.config.context_length
        info = PagedSlotInfo(
            prompt=prompt,
            prompt_len=plen,
            bucket=self.bucket_for(min(plen - shared_len, self.prefill_chunk)),
            max_new_tokens=min(max_new_tokens, ctx - plen),
            stop_id=stop_id,
            seed=seed,
            temp_enc=np.float32(temperature),
            top_k_enc=np.int32(TOP_K_DISABLED if top_k is None else top_k),
            top_p_enc=np.float32(TOP_P_DISABLED if top_p is None else top_p),
            block_ids=block_ids,
            shared_len=shared_len,
            next_pos=shared_len,
            request_id=request_id,
        )
        self._slots[slot] = info
        self._prefilling.append(slot)
        return slot

    # ------------------------------------------------------ the decode carry

    def flush(self) -> None:
        """Read every unread launch now, out of the two halves' order, and
        hold its events for the next :meth:`collect`: what a reader or
        writer of the carry, or of the memory an unread launch may still
        write, does first."""
        if self._unread:
            self.carry_flushes += 1
            self._hold_unread()

    def _hold_unread(self, keep: int = 0) -> None:
        """Read all but the newest ``keep`` unread launches and hold their
        events for the next :meth:`collect`."""
        while len(self._unread) > keep:
            self._held += self._read(self._unread.popleft())

    def read_carry(self) -> tuple:
        """The carry ``(tokens, positions, keys)`` on the host, as writable
        copies, with nothing unread behind it."""
        self.flush()
        return tuple(np.array(part) for part in self._carry)

    def write_carry(self, tokens, positions, keys) -> None:
        """Replace the carry (and the host's own positions) from host
        arrays; the device gets copies no one else holds."""
        self.flush()
        self._positions = np.array(positions, np.int32)
        self._carry = jax.device_put((
            np.array(tokens, np.int32), self._positions.copy(),
            np.array(keys, np.uint32),
        ))

    def launch_chunk(self, slot: int) -> bool:
        """Queue ONE prefill chunk for ``slot`` and read nothing; returns
        whether it was the prompt's final chunk.  A final chunk samples the
        request's first token and writes the slot's entry of the carry on
        the device, so the slot is a row of the next :meth:`launch`; the
        prompt's full blocks are indexed into the prefix cache; the token is
        the next unread launch's to hand over (:meth:`collect`)."""
        info = self._slots[slot]
        if info is None or slot not in self._prefilling:
            raise ValueError(f"slot {slot} has no pending prefill")
        # Key discipline = dense prefill: the request key is split ONCE, on
        # the final chunk; earlier chunks get a throwaway key and their
        # sampled token/key outputs are discarded.
        with self._parts["chunk", "key"].at(self.clock):
            key_in = jax.random.PRNGKey(info.seed)
        with self._parts["chunk", "prepare"].at(self.clock):
            plen = info.prompt_len
            chunk_len = min(self.prefill_chunk, plen - info.next_pos)
            bucket = self.bucket_for(chunk_len)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :chunk_len] = info.prompt[
                info.next_pos: info.next_pos + chunk_len
            ]
            final = info.next_pos + chunk_len == plen
            self.cache.before_chunk(slot, info.next_pos, chunk_len, bucket)
            args = (
                self._params, self._lm_head, self._pool, self._moe_pending,
                self.cache.table_rows(slot), padded, np.int32(info.next_pos),
                np.int32(chunk_len), key_in, info.temp_enc, info.top_k_enc,
                info.top_p_enc, self._carry, np.int32(slot), np.bool_(final),
            )
        with self._parts["chunk", "call"].at(self.clock):
            tok, self._carry, _, self._moe_pending = self._in_place(
                f"chunk_{bucket}", self._chunk_jit, *args, pool_at=-2
            )
            del args  # the old carry and the copies go here, as they did
        with self._parts["chunk", "after"].at(self.clock):
            self.chunk_launches += 1
            info.next_pos += chunk_len
            if not final:
                return False

            tok.copy_to_host_async()
            self._unread.append(
                _Launch(tok, ((slot, int(self._tenant[slot])),), first=True)
            )
            self._prefilling.remove(slot)
            self._positions[slot] = plen
            self._temps[slot] = info.temp_enc
            self._top_ks[slot] = info.top_k_enc
            self._top_ps[slot] = info.top_p_enc
            # The first token is one of the budget: a request for one token
            # is never a row of a tick.
            self._budget[slot] = info.max_new_tokens - 1
            self._active[slot] = self._budget[slot] > 0
            if self.prefix_cache is not None:
                full = plen // self.block_size
                if full:
                    self.prefix_cache.insert(
                        [int(t) for t in info.prompt[: full * self.block_size]],
                        info.block_ids[:full],
                    )
            return True

    def prefill_step(self, slot: int, dispatched=None) -> TickEvent | None:
        """Run ONE prefill chunk for ``slot``: :meth:`launch_chunk`, and
        after a final chunk the read of its token at once.  Returns ``None``
        while chunks remain, and on the final chunk the admission
        :class:`TickEvent` (exactly the dense engine's ``admit`` result).
        ``dispatched`` is called once the chunk's program is in the device's
        queue, before the final chunk's token is waited on (as `tick`'s).
        Launches that were unread before it are read first, their events
        held for the next :meth:`collect`."""
        final = self.launch_chunk(slot)
        if dispatched is not None:
            dispatched()
        if not final:
            return None
        self._hold_unread(keep=1)
        (event,) = self._read(self._unread.popleft())
        return event

    def admit(
        self,
        prompt_ids,
        *,
        max_new_tokens: int,
        temperature: float = 1.0,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int = 0,
        stop_id: int | None = None,
        request_id: str | None = None,
    ) -> TickEvent:
        """Dense-engine-compatible admission: begin + run every prefill
        chunk back to back (no decode interleaving).  The serving worker
        drives chunks itself for budget-interleaved scheduling; tests and
        offline batch use this."""
        slot = self.begin(
            prompt_ids,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            seed=seed,
            stop_id=stop_id,
            request_id=request_id,
        )
        while True:
            event = self.prefill_step(slot)
            if event is not None:
                return event

    def launch(self) -> bool:
        """Queue one batched decode step across every live slot and read
        nothing: the carry goes in as the device arrays the launch before
        left, comes back as device arrays, and the copy of the tokens to the
        host starts here.  Returns whether there was a live slot to launch
        for.  What the host counts of a tick - positions, attention pairs,
        a finish by length - it counts here, from its own arithmetic."""
        if not self._active.any():
            return False
        with Phase("serve/tick_dispatch", self.clock) as dispatch:
            with self._parts["tick", "prepare"].at(self.clock):
                live = np.flatnonzero(self._active)
                seen, counted = self.cache.before_tick(
                    live, self._positions, self._active
                )
                self.tick_live_keys += int(seen.sum())
                self.tick_table_keys += self.cache.tables.size * self.block_size
                asked = filters_asked(
                    self._active, self._temps, self._top_ks, self._top_ps
                )
                self.sample_topk_ticks += asked[0]
                self.sample_topp_ticks += asked[1]
                tokens, positions, keys = self._carry
                # The host's arrays go in as copies: this launch may still
                # be waiting when an admission or a release next rewrites
                # them.
                args = (
                    self._params, self._lm_head, self._pool,
                    self._moe_pending, self.cache.table_rows(), tokens, positions,
                    self._active.copy(), keys, self._temps.copy(),
                    self._top_ks.copy(), self._top_ps.copy(),
                )
            with self._parts["tick", "call"].at(self.clock):
                tokens, positions, keys, _, moe = self._in_place(
                    "tick", self._tick_jit, *args, pool_at=-2
                )
                del args  # the copies go with the call, as they did
            with self._parts["tick", "after"].at(self.clock):
                self._carry = (tokens, positions, keys)
                tokens.copy_to_host_async()
                if moe is not None:
                    # They come to the host with the tokens.
                    *moe, self._moe_pending = moe
                    for counts in moe:
                        counts.copy_to_host_async()
                self.ticks_overlapped += any(
                    not unread.first for unread in self._unread
                )
                self._unread.append(_Launch(
                    tokens,
                    tuple(zip(live.tolist(), self._tenant[live].tolist())),
                    moe, counted,
                ))
                self.ticks += 1
                self._positions[live] += 1
                self._budget[live] -= 1
                self._active[live[self._budget[live] <= 0]] = False
        self.last_tick_s = (dispatch.dur_s, 0.0, 0.0)
        return True

    def _read(self, launch: _Launch) -> list[TickEvent]:
        """Read one launch's tokens (the host blocks here if the device has
        yet to finish it) and turn its rows into events: each row to the
        tenant that launched it, a row whose tenant has left to no one."""
        with Phase("serve/tick_wait", self.clock) as wait:
            tokens = np.asarray(launch.tokens).reshape(-1).tolist()
            if launch.moe is not None:
                moe_since, moe_tick = map(np.asarray, launch.moe)
                self.moe_counts[: moe_since.size] += moe_since
                self.last_tick_moe_rows_local = int(moe_tick[1])
                self.last_tick_moe_zero_assignments = int(moe_tick[3:].sum())
        events: list[TickEvent] = []
        with Phase("serve/tick_emit", self.clock) as emit:
            if not launch.first:
                self.last_tick_counts = launch.counts
            held = self._tenant.tolist()  # a release bumps its own slot only
            for slot, tenant in launch.rows:
                if held[slot] != tenant:
                    self.tick_stale_rows += 1
                    continue
                info = self._slots[slot]
                token = tokens[0 if launch.first else slot]
                info.generated += 1
                self.tokens_emitted += 1
                finished = SlotPoolEngine._finish_reason(info, token)
                if finished:
                    self.release(slot)
                events.append(
                    TickEvent(slot=slot, token=token, finished=finished)
                )
        dispatch_s, wait_s, emit_s = self.last_tick_s
        self.last_tick_s = (
            dispatch_s, wait_s + wait.dur_s, emit_s + emit.dur_s
        )
        return events

    def collect(self) -> list[TickEvent]:
        """The events of the oldest unread launch - a tick's, or the first
        token of a final chunk - or, before any of those, what a
        :meth:`flush` read and holds.  The last tick's routing counts and
        state rows (``last_tick_*``) are then those of the tick read
        here."""
        if self._held:
            events, self._held = self._held, []
            return events
        return self._read(self._unread.popleft()) if self._unread else []

    def tick(self, dispatched=None) -> list[TickEvent]:
        """One batched decode step across every occupied slot — semantics
        identical to the dense engine's tick, ``dispatched`` included:
        :meth:`launch`, then :meth:`collect` until nothing is unread."""
        self.launch()
        if dispatched is not None:
            dispatched()
        events = self.collect()
        while self.unread:
            events += self.collect()
        return events

    def release(self, slot: int) -> None:
        """Free a slot: drop its block references (blocks still indexed by
        the prefix cache survive for future hits), clear its table row.  A
        recurrent state needs no free: the slot's next tenant starts from
        zeros."""
        info = self._slots[slot]
        self._active[slot] = False
        self._slots[slot] = None
        # A row an unread launch computed for this tenant is no one's now.
        self._tenant[slot] += 1
        if slot in self._prefilling:
            self._prefilling.remove(slot)
        if info is not None and info.block_ids:
            self.allocator.deref(info.block_ids)
        self.cache.release(slot)
