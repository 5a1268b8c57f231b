"""The host half of a cache kind.  `models/decode.py` has a class a kind: how a
pool is laid out, written and attended on the device.  Here is that class's
companion, what the HOST must know to serve the kind: the geometry of a slot's
table row, the blocks a request holds, a second group's chains, the rows to lay
out anew before a launch, what the kind refuses, and what a launch counts of
its attention and state from the positions alone (no device read).
`PagedEngine` finds a config's companion once (``HOST_HALVES[cache_kind(config)]``)
and calls it; it reads no flag to learn which kind it serves.  A new kind is one
class there and one here.  The base, :class:`HostDenseRows`, is one chain of
blocks a slot; each other class overrides only what differs."""

from __future__ import annotations

import numpy as np

from bpe_transformer_tpu.kernels.pallas import mla_attention, sink_attention
from bpe_transformer_tpu.models import mla
from bpe_transformer_tpu.models.decode import (
    DenseRows, EvaRows, GroupedPages, GroupedRows, LatentRows, RecurrentRows,
    eva_table_geometry,
)
from bpe_transformer_tpu.ops.quant import tree_bytes
from bpe_transformer_tpu.serving.kvpool.blocks import (
    BlockAllocator, GrowingWindowChain, WindowChain,
)

__all__ = ["HOST_HALVES"]

# What every kind but the dense one cannot take: the operations written for
# one chain of K/V blocks of positions, as a refusal names them.
_CHAIN_OPERATIONS = {
    "extend_blocks": "extend_blocks (speculative scratch)",
    "export_slot": "KV migration (export_slot)",
    "import_slot": "KV migration (import_slot)",
}


class HostDenseRows:
    """`DenseRows`' host half: one chain of blocks a slot, a table row its
    chain from position 0, nothing refused."""

    #: What a refusal calls the kind, and why it cannot take an operation
    #: written for one chain of K/V blocks of positions.
    over = why = None
    #: ``{name: the refusal's words for it}`` of the engine's constructor options
    #: and of `PagedEngine`'s / `SpecEngine`'s chain operations it cannot take.
    refused_options: dict = {}
    refused_operations: dict = {}
    #: Whether its programs compile their layers as calls (`utils/compile_cache.
    #: layered_program_options`), and the keywords `models/decode.init_paged_pool`
    #: wants beyond the full group's size.
    layers_as_calls = True
    pool_keywords: dict = {}
    #: Bytes of the pool that are a slot's state, counted apart from the KV
    #: pool: a slot's share of them does not grow with its context.
    state_bytes = 0
    #: A tick's own counts before any tick was read, and the kind's running
    #: counters that are gauges under their own names.
    no_tick: dict = {}
    counters: tuple = ("attn_pairs", "attn_kv_positions")

    def __init__(self, config, *, slots: int, block_size: int, prefill_chunk, **asked):
        """``asked``: whether the caller asks for ``prefix_cache``, an int8
        ``kv_dtype``, ``fused_sampling``; ``prefill_chunk`` as it was passed."""
        for option, what in self.refused_options.items():
            if asked[option]:
                raise ValueError(
                    f"{what} is not supported over {self.over} "
                    "(ROADMAP: what cannot run yet); pass it off"
                )
        self.config, self.slots, self.block_size = config, slots, block_size
        #: Attention sublayers that read the cache a tick: a layer's one, or
        #: the double layer's two, over the layers that keep K/V a position
        #: (`ModelConfig.layer_kinds`).
        self.attn_sublayers = config.attn_layers * config.attn_sublayers
        #: The table's width, the blocks the longest request holds at once,
        #: and the engine's ``prefill_chunk`` where the caller names none.
        self.blocks_per_slot = self.max_chain = config.context_length // block_size
        self.default_prefill_chunk = config.context_length
        self._lay_out(prefill_chunk)
        self.tables = np.zeros((slots, self.blocks_per_slot), np.int32)
        #: What the attention of the ticks (and, over window pool groups and a
        #: summary-and-window cache, of the chunks) needs, summed over layers:
        #: visible (query, key) pairs and distinct KV positions to stream.
        self.attn_pairs = 0
        self.attn_kv_positions = 0

    def _lay_out(self, prefill_chunk) -> None:
        """The kind's own geometry, second group and per-slot state, and the multiples
        it holds ``block_size`` and ``prefill_chunk`` (as the caller passed it) to."""

    def settle(self, pool, tick_attention_path: str, buckets: tuple) -> None:
        """The pool as `init_paged_pool` made it, how a tick's rows attend over
        it, and the chunk programs' shapes as the engine cut its ladder."""

    def kv_bytes_per_token(self, itemsize: int) -> int:
        """KV footprint per token POSITION at pool width across all layers
        (k + v) - the unit of the attention READ stream, which scales with
        context and dominates the decode tick's HBM traffic; this is the
        knob int8 halves (vs bf16).  NOT a write-traffic counter: int8's
        decode scatter is a whole-block rescale RMW (~block_size rows,
        bounded at one block per slot per layer), amortized small against
        the context-sized read."""
        config = self.config
        kv_heads = config.num_kv_heads or config.num_heads
        return 2 * config.attn_layers * kv_heads * config.d_head * itemsize

    def refuse(self, operation: str) -> None:
        """Raise if the kind cannot take ``operation``."""
        what = self.refused_operations.get(operation)
        if what is not None:
            raise NotImplementedError(
                f"{what} is not supported over {self.over}: {self.why} "
                "(ROADMAP: what cannot run yet)"
            )

    def blocks_needed(self, span: int) -> int:
        """Blocks a request of ``span`` positions holds at its longest."""
        return -(-span // self.block_size)  # ceil

    def admit(self, slot: int, block_ids: list) -> None:
        """``slot``'s new tenant holds ``block_ids``, its whole reservation:
        its table row, a second group's chain, the kind's per-slot state.  A
        :class:`NoFreeBlocksError` leaves nothing held here (the caller gives
        ``block_ids`` back)."""
        self.tables[slot, : len(block_ids)] = block_ids
        self.tables[slot, len(block_ids):] = 0

    def release(self, slot: int) -> None:
        self.tables[slot, :] = 0

    def table_rows(self, slot: int | None = None):
        """The block tables as the device half takes them: every slot's rows
        (a tick), or one slot's (a chunk) - a COPY either way.  No launch
        is read back before the host goes on: its program may still be
        waiting when the host next rewrites a row (an admission, a release,
        a window row recycling in place), and the CPU backend reads a numpy
        argument that happens to lie 64-byte aligned where it lies, without
        copying it: a chunk then attended through the next chunk's row
        (ROADMAP D11).  The copy belongs to its launch alone."""
        return self._pick(self.tables, slot)

    @staticmethod
    def _pick(array: np.ndarray, slot: int | None) -> np.ndarray:
        return array.copy() if slot is None else array[slot].copy()

    def before_chunk(self, slot: int, start: int, chunk_len: int, bucket: int) -> None:
        """``slot``'s chunk of ``chunk_len`` rows from ``start``, in the program of
        ``bucket`` rows, is about to be queued: rows to advance or lay out anew."""

    def before_tick(self, live: np.ndarray, positions: np.ndarray, active: np.ndarray):
        """A tick over the ``live`` slots is about to be queued, each slot's one
        row at ``positions[slot]``: rows to advance or lay out anew.  Returns each
        live slot's key count and the tick's own counts (the ``tick`` record's)."""
        seen = positions[live].astype(np.int64) + 1
        self._count_tick(self.attn_sublayers * int(seen.sum()))
        return seen, self.no_tick

    def _count_tick(self, keys_read: int) -> None:
        # One query a live slot: pairs and KV positions are alike.
        self.attn_pairs += keys_read
        self.attn_kv_positions += keys_read

    def _chunk_pairs(self, rows: int, before: int) -> int:
        """Visible (query, key) pairs of a chunk of ``rows`` queries after
        ``before`` cached rows - all of those and its own causal half - over
        the attention sublayers."""
        return self.attn_sublayers * (rows * before + rows * (rows + 1) // 2)

    def chunk_counts(self) -> dict:
        """Running counts of the chunks' work: the ``tick`` record's, by period."""
        return {}

    def gauges(self) -> dict:
        """The kind's gauges.  What reads 0 off its kind reads it here."""
        off = (
            "kv_window_blocks_total", "kv_window_blocks_free",
            "kv_window_blocks_recycled", "ssm_tick_state_rows", "ssm_chunk_tokens",
            "ssm_chunk_rows", "ssm_state_resets", "ssm_state_bytes",
        )
        counted = {name: getattr(self, name) for name in self.counters}
        return {**dict.fromkeys(off, 0), **counted}


class HostGroupedPages(HostDenseRows):
    """`GroupedPages`' host half: a config with sliding-window layers keeps a
    window group beside the full one, with an allocator of its own and a
    :class:`WindowChain` a slot.  The group is a reservation, not a knob: a
    chain holds at most window + one chunk of positions, and every slot can
    hold that much.  A slot's window row starts at its chain's first live
    block, whose first position is the slot's base."""

    over = "window pool groups"
    why = (
        "a recycled window block cannot be rolled back, copied or shipped as "
        "part of a whole chain"
    )
    refused_options = {
        "prefix_cache": "prefix_cache=True (the radix cache shares whole chains; "
                        "a window group recycles its blocks)",
        "kv_dtype": 'kv_dtype="int8"',
        "fused_sampling": "fused_sampling",
    }
    refused_operations = {
        **_CHAIN_OPERATIONS, "rewind": "rewind",
        "speculate": "speculative decoding (its verify pass rewinds)",
    }
    # Its programs compile as they did (ROADMAP D10 measures that on the
    # chip before it changes).
    layers_as_calls = False

    def _lay_out(self, prefill_chunk) -> None:
        config, block_size, slots = self.config, self.block_size, self.slots
        ctx, window = config.context_length, config.sliding_window
        chunk = min(prefill_chunk or ctx, ctx)
        if window % block_size or chunk % block_size:
            raise ValueError(
                f"sliding_window={window} and prefill_chunk={chunk} must "
                f"be multiples of block_size={block_size}"
            )
        self.window_cap = min((window + chunk) // block_size, self.blocks_per_slot)
        blocks = self._window_group_blocks()
        self.pool_keywords = {"num_window_blocks": blocks}
        self.window_allocator = BlockAllocator(blocks, block_size)
        self.chains: list[WindowChain | None] = [None] * slots
        self.window_tables = np.zeros((slots, max(self.window_cap, 1)), np.int32)
        self.window_base = np.zeros(slots, np.int32)
        self.window_recycled = 0
        self._window_layers = sum(
            config.layer_window(layer) is not None for layer in range(config.num_layers)
        )

    def _window_group_blocks(self) -> int:
        """The window group's size, its trash block included: every slot's
        reservation of window + one chunk."""
        return self.slots * self.window_cap + 1

    def _new_chain(self, need: int):
        """The window chain of a request whose full chain is ``need`` blocks."""
        return WindowChain(self.window_allocator, self.window_cap, need)

    def admit(self, slot: int, block_ids: list) -> None:
        self.chains[slot] = self._new_chain(len(block_ids))
        self._write_window_row(slot)
        super().admit(slot, block_ids)

    def release(self, slot: int) -> None:
        super().release(slot)
        if self.chains[slot] is not None:
            self.chains[slot].release()
            self.chains[slot] = None
            self.window_tables[slot, :] = 0
            self.window_base[slot] = 0

    def table_rows(self, slot: int | None = None):
        return {
            "full": self._pick(self.tables, slot),
            "window": self._pick(self.window_tables, slot),
            "window_base": self._pick(self.window_base, slot),
        }

    def before_chunk(self, slot: int, start: int, chunk_len: int, bucket: int) -> None:
        # The chunk's first query reads back to start - window + 1.
        self.advance_window(slot, start - self.config.sliding_window + 1)
        self.count_attention(start, start + chunk_len)

    def before_tick(self, live, positions, active):
        window = self.config.sliding_window
        for slot in live:
            self.advance_window(int(slot), int(positions[slot]) - window + 1)
        seen = positions[live].astype(np.int64) + 1
        # A window layer's positions are capped by its window.
        self._count_tick(
            (self.attn_sublayers - self._window_layers) * int(seen.sum())
            + self._window_layers * int(np.minimum(seen, window).sum())
        )
        return seen, self.no_tick

    def advance_window(self, slot: int, lo_pos: int) -> None:
        """Recycle ``slot``'s window blocks that lie wholly below
        ``lo_pos`` (positions no later query of the slot reads)."""
        recycled = self.chains[slot].advance(lo_pos)
        if recycled:
            self.window_recycled += recycled
            self._write_window_row(slot)

    def _write_window_row(self, slot: int) -> None:
        chain = self.chains[slot]
        row = self.window_tables[slot]
        row[:] = 0
        row[: len(chain.ids)] = chain.ids
        self.window_base[slot] = chain.first * self.block_size

    def attention_needs(self, start: int, end: int) -> tuple[int, int, int, int]:
        """What the attention of queries ``start .. end - 1`` of one slot
        needs: ``(visible pairs of the full layers, of the window layers,
        key positions of the full layers, of the window layers)``, each
        over its group's layers (plain integers)."""
        window = self.config.sliding_window
        full_layers = self.attn_sublayers - self._window_layers
        full_pairs = (end * (end + 1) - start * (start + 1)) // 2
        # Queries from position window - 1 on see exactly window keys.
        capped = max(end - max(start, window - 1), 0)
        uncapped_end = end - capped
        window_pairs = (
            uncapped_end * (uncapped_end + 1) - start * (start + 1)
        ) // 2 + capped * window
        return (
            full_layers * full_pairs, self._window_layers * window_pairs,
            full_layers * end,
            self._window_layers * (end - max(start - window + 1, 0)),
        )

    def count_attention(self, start: int, end: int) -> tuple[int, int, int, int]:
        """Add what the attention of queries ``start .. end - 1`` of one
        slot needs, over the layers of both kinds; returns it by group."""
        needs = self.attention_needs(start, end)
        self.attn_pairs += needs[0] + needs[1]
        self.attn_kv_positions += needs[2] + needs[3]
        return needs

    def gauges(self) -> dict:
        return {
            **super().gauges(),
            "kv_window_blocks_total": self.window_allocator.usable_blocks,
            "kv_window_blocks_free": self.window_allocator.free_count,
            "kv_window_blocks_recycled": self.window_recycled,
        }


class HostGroupedRows(HostGroupedPages):
    """`GroupedRows`' host half: `HostGroupedPages`' two groups and window
    rows, over a window group that is **not** a reservation a slot.  A window
    far shorter than a chunk (128 positions under chunks of 2,048) makes
    ``window + chunk`` blocks a slot seventeen windows' worth for every slot
    at once, of which a slot between its launches needs one: the blocks back
    from its next query's window start.  So a slot's chain is taken block by
    block as its launches reach them and cut back **behind the launch that
    read them** - at the next launch of any slot, which the device runs after
    it - and the group holds ``window // block_size + 1`` blocks a slot and
    one chunk's beside them.  Attention is counted by group."""

    #: Key positions the ticks' slots attended, and the chunks' (a chunk
    #: reads what lies before it back to its first row's window start, and
    #: its own rows), each x its group's layers; the visible (query, key)
    #: pairs of the chunks likewise.
    attn_full_kv_positions = attn_window_kv_positions = 0
    chunk_attn_full_kv_positions = chunk_attn_window_kv_positions = 0
    chunk_attn_full_pairs = chunk_attn_window_pairs = 0
    #: The blocks of keys the chunk kernel's walks fold in the full layers
    #: (a K/V head's, every query block of the launch's bucket, x the full
    #: layers), and those of them folded under a mask - an edge crosses them
    #: (`sink_attention.chunk_walk`, the arithmetic the kernel walks by).
    chunk_attn_full_key_blocks = chunk_attn_full_masked_blocks = 0
    counters = HostDenseRows.counters + (
        "attn_full_kv_positions", "attn_window_kv_positions",
        "chunk_attn_full_kv_positions", "chunk_attn_window_kv_positions",
        "chunk_attn_full_pairs", "chunk_attn_window_pairs",
        "chunk_attn_full_key_blocks", "chunk_attn_full_masked_blocks",
    )
    no_tick = {"attn_full_kv_positions": 0, "attn_window_kv_positions": 0}

    def _lay_out(self, prefill_chunk) -> None:
        super()._lay_out(prefill_chunk)
        #: Where a slot's next query's window starts, once the launch that
        #: read further back is queued: blocks below it are dead.
        self._cut_at: dict[int, int] = {}

    def _window_group_blocks(self) -> int:
        window_blocks = self.config.sliding_window // self.block_size + 1
        return self.slots * window_blocks + self.window_cap + 1

    def _new_chain(self, need: int):
        return GrowingWindowChain(self.window_allocator)

    def kv_bytes_per_token(self, itemsize: int) -> int:
        config = self.config
        return sum(
            config.layer_kv_heads(layer) * (config.d_head + config.value_dim)
            for layer in range(config.num_layers)
        ) * itemsize

    def release(self, slot: int) -> None:
        super().release(slot)
        self._cut_at.pop(slot, None)

    def _cut_back(self) -> None:
        """Free what the launches queued so far have read for the last time."""
        for slot, lo_pos in self._cut_at.items():
            self.advance_window(slot, lo_pos)
        self._cut_at.clear()

    def _reach(self, slot: int, lo_pos: int, last_pos: int) -> None:
        """``slot``'s chain from the block of ``lo_pos`` to that of ``last_pos``."""
        self.advance_window(slot, lo_pos)
        if self.chains[slot].reach(lo_pos, last_pos):
            self._write_window_row(slot)

    def before_chunk(self, slot: int, start: int, chunk_len: int, bucket: int) -> None:
        window, end = self.config.sliding_window, start + chunk_len
        self._cut_back()
        self._reach(slot, start - window + 1, end - 1)
        self._cut_at[slot] = end - window + 1
        full_pairs, window_pairs, full_keys, window_keys = self.count_attention(start, end)
        self.chunk_attn_full_pairs += full_pairs
        self.chunk_attn_window_pairs += window_pairs
        self.chunk_attn_full_kv_positions += full_keys
        self.chunk_attn_window_kv_positions += window_keys
        visited, masked = sink_attention.chunk_walk_blocks(
            start, bucket, self.blocks_per_slot * self.block_size, None
        )
        full_layers = self.attn_sublayers - self._window_layers
        self.chunk_attn_full_key_blocks += full_layers * visited
        self.chunk_attn_full_masked_blocks += full_layers * masked

    def before_tick(self, live, positions, active):
        window = self.config.sliding_window
        self._cut_back()
        for slot in live:
            at = int(positions[slot])
            self._reach(int(slot), at - window + 1, at)
        seen = positions[live].astype(np.int64) + 1
        counts = {
            "attn_full_kv_positions": (
                (self.attn_sublayers - self._window_layers) * int(seen.sum())
            ),
            "attn_window_kv_positions": (
                self._window_layers * int(np.minimum(seen, window).sum())
            ),
        }
        self.attn_full_kv_positions += counts["attn_full_kv_positions"]
        self.attn_window_kv_positions += counts["attn_window_kv_positions"]
        self._count_tick(sum(counts.values()))
        return seen, counts


class HostLatentRows(HostDenseRows):
    """`LatentRows`' host half: one chain a slot, as the dense kind has (so
    the radix prefix cache carries over), one latent row a position and
    sublayer and no K and V."""

    over = "a latent pool"
    why = (
        "the migration wire ships K and V blocks of heads, and a verify pass "
        "has no latent form"
    )
    refused_options = {
        "kv_dtype": 'kv_dtype="int8" (latent rows have no heads to scale by)',
        "fused_sampling": "fused_sampling",
    }
    refused_operations = {
        **_CHAIN_OPERATIONS, "speculate": "speculative decoding (its verify pass)",
    }
    no_tick = {"attn_shared_kv_positions": 0, "attn_shared_slots": 0}

    #: Under the tick's kernels: key positions the ticks' slots attended
    #: through the shared pass - the chain of blocks several slots' rows
    #: start with, attended once for all of them - x sublayers (the unit of
    #: ``attn_kv_positions``), and the slots on the chain summed over ticks.
    #: The program's own rule (`mla_attention.shared_prefix`) on the host's
    #: tables and positions.
    attn_shared_kv_positions = attn_shared_slots = 0
    _shares_chain = False
    #: The chunks: the visible (query, key) pairs their attention needs x
    #: sublayers, and those of them whose launch's bucket attends in the
    #: expanded form's kernel (`mla.rows_attention_path`, the rule
    #: `mla.rows_attention` asks).  Not in ``attn_pairs``, which counts a
    #: latent pool's ticks alone.
    chunk_attn_pairs = chunk_attn_kernel_pairs = 0
    counters = HostDenseRows.counters + (
        "attn_shared_kv_positions", "attn_shared_slots", "chunk_attn_pairs",
        "chunk_attn_kernel_pairs",
    )

    def settle(self, pool, tick_attention_path: str, buckets: tuple) -> None:
        self._shares_chain = tick_attention_path == "mla_paged"
        self._chunk_kernel_buckets = frozenset(
            bucket for bucket in buckets
            if mla.rows_attention_path(bucket, self.config) == "mla_chunk"
        )

    def kv_bytes_per_token(self, itemsize: int) -> int:
        return self.attn_sublayers * self.config.latent_width * itemsize

    def before_chunk(self, slot: int, start: int, chunk_len: int, bucket: int) -> None:
        pairs = self._chunk_pairs(chunk_len, start)
        self.chunk_attn_pairs += pairs
        if bucket in self._chunk_kernel_buckets:
            self.chunk_attn_kernel_pairs += pairs

    def before_tick(self, live, positions, active):
        seen, counts = super().before_tick(live, positions, active)
        if self._shares_chain:
            shared, _ = mla_attention.shared_prefix(
                self.tables, np.where(active, positions + 1, 0), self.block_size, xp=np,
            )
            counts = {
                "attn_shared_kv_positions": (
                    self.attn_sublayers * self.block_size * int(shared.sum())
                ),
                "attn_shared_slots": int(np.count_nonzero(shared)),
            }
            self.attn_shared_kv_positions += counts["attn_shared_kv_positions"]
            self.attn_shared_slots += counts["attn_shared_slots"]
        return seen, counts


class HostRecurrentRows(HostDenseRows):
    """`RecurrentRows`' host half: the dense chain for the K/V blocks of the
    attention layers, and beside it the state-space layers' recurrent state,
    a row a slot (which layer is which - and which has no cache of any kind -
    is the config's pattern of kinds, `ModelConfig.layer_kinds`).  A state
    needs no free: the slot's next tenant starts from zeros."""

    over = "a recurrent state"
    why = (
        "it is the state after a slot's last token and of no earlier one, and "
        "the migration wire ships blocks of positions"
    )
    refused_options = {
        "prefix_cache": "prefix_cache=True (a shared chain of blocks says nothing "
                        "of the recurrent state at its end)",
        "kv_dtype": 'kv_dtype="int8"',
        "fused_sampling": "fused_sampling",
    }
    refused_operations = {
        **_CHAIN_OPERATIONS,
        "rewind_below_frontier": "rewind below the written frontier",
        "speculate": "speculative decoding (its verify pass rewinds)",
    }
    no_tick = {"ssm_tick_state_rows": 0}

    #: Slot-layers the ticks updated (live slots x state-space layers), real
    #: and bucket rows x state-space layers through the chunks' scans, and
    #: admissions that started from a zero state.
    ssm_tick_state_rows = ssm_chunk_tokens = ssm_chunk_rows = ssm_state_resets = 0
    counters = HostDenseRows.counters + (
        "ssm_tick_state_rows", "ssm_chunk_tokens", "ssm_chunk_rows", "ssm_state_resets",
    )

    def _lay_out(self, prefill_chunk) -> None:
        self._ssm_layers = self.config.ssm_layers
        self.pool_keywords = {"slots": self.slots}

    def settle(self, pool, tick_attention_path: str, buckets: tuple) -> None:
        self.state_bytes = tree_bytes([entry for entry in pool if "ssm" in entry])

    def table_rows(self, slot: int | None = None):
        rows = super().table_rows(slot)
        # A chunk addresses its slot's state rows by the slot's id.
        return rows if slot is None else {"blocks": rows, "slot": np.int32(slot)}

    def before_chunk(self, slot: int, start: int, chunk_len: int, bucket: int) -> None:
        self.ssm_chunk_tokens += self._ssm_layers * chunk_len
        self.ssm_chunk_rows += self._ssm_layers * bucket
        # The chunk program starts a chunk at position 0 from zeros.
        self.ssm_state_resets += int(start == 0)

    def before_tick(self, live, positions, active):
        seen, _ = super().before_tick(live, positions, active)
        rows = self._ssm_layers * len(live)
        self.ssm_tick_state_rows += rows
        return seen, {"ssm_tick_state_rows": rows}

    def chunk_counts(self) -> dict:
        tokens, rows = self.ssm_chunk_tokens, self.ssm_chunk_rows
        return {"ssm_chunk_tokens": tokens, "ssm_chunk_rows": rows}

    def gauges(self) -> dict:
        return {**super().gauges(), "ssm_state_bytes": self.state_bytes}


class HostEvaRows(HostDenseRows):
    """`EvaRows`' host half, a summary-and-window cache over the dense pool:
    a slot holds its open window's blocks (``window_blocks[slot]``, the
    leading ones of its chain) and a block of summaries for every
    ``block_size`` blocks of the windows it lives to close, a window's in a
    row.  Its table row is laid out anew at every closing
    (:meth:`enter_window`; ``window[slot]`` is the window the row is laid out
    for, -1: not yet)."""

    over = "a summary-and-window cache"
    why = (
        "a closed window's exact rows are gone, so nothing rolls back across a "
        "closing, and the migration wire ships one chain of positions"
    )
    refused_options = {
        "prefix_cache": "prefix_cache=True (a cached chain's closed windows have no "
                        "exact rows left for a prompt that ends inside them)",
        "kv_dtype": 'kv_dtype="int8" (a summary row shares no block scale with '
                    "the rows it pools)",
        "fused_sampling": "fused_sampling (the fused tail projects the head's whole "
                          "width; generation samples prediction head 0)",
    }
    refused_operations = {**_CHAIN_OPERATIONS, "rewind": "rewind"}
    no_tick = {"attn_kv_positions": 0, "attn_summary_kv_positions": 0}

    #: Of the ticks' ``attn_kv_positions`` those that are summary rows;
    #: summary rows written by ticks and chunks, x layers; windows closed (a
    #: table row laid out anew); a closed window's blocks, which hold the
    #: next window's rows.  A chunk's attention adds to ``attn_pairs`` alone.
    attn_summary_kv_positions = eva_summary_rows = eva_windows_closed = 0
    window_recycled = 0
    counters = HostDenseRows.counters + (
        "attn_summary_kv_positions", "eva_summary_rows", "eva_windows_closed",
    )

    def _lay_out(self, prefill_chunk) -> None:
        config = self.config
        per_window, window_blocks, self.blocks_per_slot = eva_table_geometry(
            config, self.block_size
        )
        self._eva_blocks = (per_window, window_blocks)
        # A window and the summary blocks of every window but the last (the
        # table also has room for the open window's pending summaries).
        self.max_chain = self.blocks_per_slot - per_window
        self.default_prefill_chunk = config.eva_window
        if prefill_chunk is None:
            prefill_chunk = config.eva_window
        if config.eva_window % min(prefill_chunk, config.context_length):
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must divide eva_window="
                f"{config.eva_window}: a chunk lies inside one window"
            )
        self._chains: list = [None] * self.slots
        self.window_blocks = [0] * self.slots
        self.window = [-1] * self.slots

    def settle(self, pool, tick_attention_path: str, buckets: tuple) -> None:
        if any(b % self.block_size for b in buckets):
            raise ValueError(
                f"prefill buckets {buckets} must be multiples of "
                f"block_size={self.block_size}: a chunk's whole blocks are "
                "summarised from its rows"
            )

    def blocks_needed(self, span: int) -> int:
        return sum(self.chain(span))

    def chain(self, span: int) -> tuple[int, int]:
        """``(window blocks, summary blocks)`` of a request of ``span``
        positions: the blocks of its longest window, and a window's summary
        blocks for every window it lives to close."""
        per_window, window_blocks = self._eva_blocks
        return (
            min(-(-span // self.block_size), window_blocks),
            (span - 1) // self.config.eva_window * per_window,
        )

    def admit(self, slot: int, block_ids: list) -> None:
        # A chain longer than a window's blocks holds a whole window: the
        # rest are summary blocks (:meth:`chain`).
        self._chains[slot], self.window[slot] = block_ids, -1
        self.window_blocks[slot] = min(len(block_ids), self._eva_blocks[1])
        self.enter_window(slot, 0)

    def release(self, slot: int) -> None:
        super().release(slot)
        self._chains[slot] = None

    def enter_window(self, slot: int, position: int) -> None:
        """Lay ``slot``'s table row out for the window ``position`` lies in:
        the summary blocks of the windows before it, the window's blocks -
        the same blocks window after window: a closed window's exact rows
        are dropped, and counted as recycled - and the blocks its own
        summaries are written to (trash where the request ends before the
        window closes)."""
        window = position // self.config.eva_window
        if window == self.window[slot]:
            return
        per_window, window_blocks = self._eva_blocks
        held, block_ids = self.window_blocks[slot], self._chains[slot]
        summaries = block_ids[held:]
        row = self.tables[slot]
        row[:] = 0
        visible = window * per_window
        row[:visible] = summaries[:visible]
        row[visible: visible + held] = block_ids[:held]
        pending = summaries[visible: visible + per_window]
        at = visible + window_blocks
        row[at: at + len(pending)] = pending
        if self.window[slot] >= 0:
            self.eva_windows_closed += window - self.window[slot]
            self.window_recycled += held
        self.window[slot] = window

    def before_chunk(self, slot: int, start: int, chunk_len: int, bucket: int) -> None:
        config = self.config
        self.enter_window(slot, start)
        width = config.eva_window
        summaries = start // width * config.eva_chunks_per_window
        self.attn_pairs += self._chunk_pairs(chunk_len, summaries + start % width)
        self.eva_summary_rows += self.attn_sublayers * (chunk_len // config.eva_chunk)

    def before_tick(self, live, positions, active):
        # The summaries of the windows a slot has closed, then its open
        # window up to the row: what `EvaRows` attends.
        config = self.config
        width, per_chunk = config.eva_window, config.eva_chunk
        for slot in live:
            self.enter_window(int(slot), int(positions[slot]))
        at = positions[live].astype(np.int64)
        summaries = at // width * config.eva_chunks_per_window
        seen = summaries + at % width + 1
        self.eva_summary_rows += self.attn_sublayers * int(
            np.count_nonzero(at % per_chunk == per_chunk - 1)
        )
        counts = {
            "attn_kv_positions": self.attn_sublayers * int(seen.sum()),
            "attn_summary_kv_positions": self.attn_sublayers * int(summaries.sum()),
        }
        self.attn_summary_kv_positions += counts["attn_summary_kv_positions"]
        self._count_tick(counts["attn_kv_positions"])
        return seen, counts

    def gauges(self) -> dict:
        return {
            **super().gauges(),
            "kv_window_blocks_recycled": self.window_recycled,
            "kv_summary_blocks_used": sum(
                len(block_ids) - held
                for block_ids, held in zip(self._chains, self.window_blocks)
                if block_ids is not None
            ),
        }


#: A cache kind's host half by its device half (`models/decode.cache_kind`).
HOST_HALVES = {
    DenseRows: HostDenseRows, GroupedPages: HostGroupedPages,
    GroupedRows: HostGroupedRows,
    LatentRows: HostLatentRows, RecurrentRows: HostRecurrentRows, EvaRows: HostEvaRows,
}
