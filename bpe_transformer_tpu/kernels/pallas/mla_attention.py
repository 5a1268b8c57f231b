"""Latent attention (MLA) against cached latent rows.

What latent attention caches is one row a position and sublayer,
``[c ; k_r]``: ``rank`` values of the normalised key-value latent and the
``rope`` values of the one rotated key all heads share
(`models/mla.py`).  Two forms of the same attention read it:

* **absorbed** (:func:`mla_paged_attention`, :func:`xla_mla_rows_attention`):
  the key up-projection is folded into the query, so a head's query is
  ``[W_k,h^T q_nope,h ; q_rope,h]``, as wide as a cached row, every head
  reads the *same* row for its score (MQA with one KV "head"), the row's
  first ``rank`` values are also the value row, and the value up-projection
  is applied to the ``rank``-wide output afterwards.  A cached position
  costs its own bytes and nothing is expanded: the form of a decode tick,
  one query row a slot against thousands of positions.
* **expanded** (:func:`mla_chunk_attention`): keys and values of every head
  are expanded from the latent rows and attended as plain heads.  A (query,
  key) pair costs ``2 x (192 + 128)`` FLOPs a head so against ``2 x (576 +
  512)`` absorbed, and a key costs ``2 x 512 x 256`` a head once to expand:
  the form of many query rows, and the reference's
  (``chipbench/reference_longcatflash.py``).

On the TPU the tick's form is two Pallas kernels that copy blocks through
the block table straight out of the pool, as
`decode_attention.paged_decode_attention` does for K/V heads, under one
softmax state.  The radix prefix cache puts the *same physical blocks* at
the head of many slots' table rows (a system prompt: 8,192 of a slot's
~9,300 keys in the benchmark's cell), so the first kernel
(``mla_paged_attention_shared.N``) attends that chain **once** for all the
slots that hold it - their heads' query rows side by side in tiles of
`MLA_SHARED_TILE_ROWS`, whole MXU tiles, the keys read once a tile and not
once a slot - and hands on the unnormalised state ``(m, l, acc)`` of every
(slot, head); the second (``mla_paged_attention.N``) walks each slot's own
blocks from there, one slot a grid step, and normalises.  Which blocks are
shared is read from the tables a tick (:func:`shared_prefix`: no flag, no
knob); with nothing shared the first kernel walks no group and the second
is the whole walk.  Exact attention in another order (Hydragen, Juravsky et
al. 2024; FlashInfer's cascade attention).  Elsewhere the tick's form is a
gather and a masked softmax in XLA.

Many query rows of one sequence (a chunk that resumes after a cached prefix,
a dense cache's prefill, the plain forward) take either form, chosen from
the shapes and the backend in one place (:func:`mla_chunk_path`, asked by
`models/mla.rows_attention`).  A bucket of `MLA_CHUNK_MIN_ROWS` rows or more
on the TPU, at widths of whole lane tiles, attends **expanded** inside one
Pallas kernel (``mla_chunk_attention.N``).  Its grid is the heads: a step
holds one head's up-projection and the whole bucket's query rows of that
head, and walks the blocks of `MLA_CHUNK_KERNEL_KEYS` latent rows that any
of its rows sees in a loop *inside* the step (:func:`chunk_walk`, from the
positions and the live key count).  A trip copies its block out of HBM into
one of two buffers while the block before it is computed on, makes the
head's keys and values of the block in VMEM - **once a head**, whatever the
bucket's rows - and folds them into the float32 softmax state of each tile
of `MLA_CHUNK_TILE_ROWS` query rows in turn: bare where the tile sees the
whole block, under the mask where its edge crosses the block, not at all
where it sees none of it.  Neither K, V nor a score tile reaches HBM, and a
block past the chunk's last position costs nothing: no copy, no step, no
branch.  Everything else (every other backend, unaligned test shapes, a
short chunk) runs :func:`xla_mla_chunk_attention`, the **absorbed** flash
loop in XLA whose trip count follows the last position.  On the v5e, one
sublayer after 8,192 cached positions (64 heads, bfloat16; PERF.md section
6, PR 50; the loop at 1,024 rows is PR 41's reading):

    query rows            128     256     512     1,024   2,048
    absorbed loop, ms     1.12    2.35    5.92    15.84   -
    expanded kernel       1.51    1.64    2.37     3.87   6.95

and 2,048 rows from position 0 1.10 ms, after 30,000 positions 22.68
(chains of 32,768 rows under 2,048 query rows, of 16,384 under fewer).  A
trip over 2,048 rows takes 5.7 us where its 1,792 matrix pushes need 4.8
(16 cycles a push on each of four MXUs): 84%.  By the
static schedule a trip's expansion is 963 bundles for 256 pushes and an
unmasked fold of 512 rows x 512 keys 1,603 for 384, so most of what is
left is in the products themselves: the rotated key's 64 dead lanes of 256
and the expansion.  The absorbed loop's product is the tick's shared
pass's, whose own kernel holds 58-60% (PR 39), so an absorbed kernel could
not have caught up: the FLOPs had to go.  (PR 33 had read the two forms as
two XLA programs, 15.9 ms absorbed against 18.3-21.3 expanded: expanded *in
XLA* writes 64 heads' K and V and float32 score tiles to HBM.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.kernels.pallas.decode_attention import NEG_INF

#: Keys a step of the tick's kernel copies and computes on: contexts are
#: thousands of positions long here, and a step's fixed cost is spread over
#: its keys (`runtime.PAGED_GROUP_KEYS` is 256 for contexts of a thousand).
#: On the v5e, 64 slots of ~9,300 keys: 2.82 ms a call at 256, 2.41 at 512,
#: 2.21 at 1,024 (PERF.md section 6, PR 33).
MLA_GROUP_KEYS = 1024
#: Query rows a step of the shared pass holds against a group of keys: the
#: heads of 16 slots at 64 heads.  The keys are the MXU's stationary operand
#: and the query rows stream past them, so a step's rate grows with its
#: rows.  On the v5e, 64 slots on a chain of 8,192 keys: the pass 0.938 ms
#: at 256 rows, 0.705-0.721 at 512, 0.616-0.637 at 1,024, 0.705-0.711 at
#: 2,048 (its FLOPs need 0.37; PERF.md section 6, PR 39).
MLA_SHARED_TILE_ROWS = 1024
#: A tile's scores and probabilities are 10 MB at 1,024 x 1,024, beside
#: its queries, its state and two groups of keys: over the default 16 MiB.
MLA_SHARED_VMEM_BYTES = 64 * 1024 * 1024
#: Fewest slots on one chain for which the shared pass is taken: a tile's
#: step costs the same whatever the number of member rows in it (0.159 ms
#: for a chain of 8,192 keys) and a member saves ~0.029 ms of its own walk.
#: On the v5e, 64 slots of ~9,300 keys, both kernels together: 4 members
#: 2.197 ms against 2.155 with the pass off, 6 members 2.143, 8 members
#: 2.082, 16 members 1.850, all 64 0.914-0.941 (PERF.md section 6, PR 39).
MLA_SHARED_MIN_SLOTS = 6
#: Lanes behind a softmax state's accumulator for its maximum and its sum.
_STATE_LANES = 128
#: Keys a step of the chunk's absorbed loop scores and folds into its
#: softmax (`xla_mla_chunk_attention`).
MLA_CHUNK_KEY_BLOCK = 1024
#: Latent rows a trip of the chunk kernel's walk copies, expands into a
#: head's keys and values and folds, and query rows of a tile, which meets
#: the expanded block bare, under the mask or not at all.  A (tile, block)
#: pair is the grain of the walk, so both are as small as the fold stays
#: efficient: by the static schedule a fold of 512 x 512 is 1,603 bundles,
#: of 512 x 1,024 3,448, of 1,024 x 512 3,349, of 256 x 256 614.  On the
#: v5e, 2,048 rows after 8,192 positions / 1,024 rows after 8,192 (PERF.md
#: section 6, PR 50), keys x rows: 512 x 512 7.23 / 3.83 ms a call, 1,024 x
#: 512 7.46 / 3.92, 512 x 1,024 7.44 / 3.92, 1,024 x 1,024 7.53 / 3.94, 512
#: x 256 7.86 / 4.11, 256 x 512 9.61 / 5.04.
MLA_CHUNK_KERNEL_KEYS = 512
MLA_CHUNK_TILE_ROWS = 512
#: Fewest query rows for which a chunk attends in the expanded form: by
#: FLOPs the forms break even at ~170 rows a key.  On the v5e after 8,192
#: positions, kernel against loop: 128 rows 1.51 against 1.12 ms, 256 rows
#: 1.64 against 2.35, 512 rows 2.37 against 5.92 (PERF.md section 6, PR 50).
MLA_CHUNK_MIN_ROWS = 256


# ------------------------------------------------------------ absorbed, XLA


@jax.named_scope("mla_attn")
def xla_mla_rows_attention(q_abs, rows, visible, *, rank: int, scale: float):
    """Absorbed attention over latent rows as they lie: ``q_abs`` (batch,
    heads, width), ``rows`` (batch, keys, width), ``visible`` (batch, keys)
    -> (batch, heads, rank) in ``q_abs.dtype``.  Float32 scores and
    softmax, probabilities back at the rows' width."""
    scores = jnp.einsum(
        "bhw,bkw->bhk", q_abs, rows, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    return jnp.einsum(
        "bhk,bkc->bhc", probs, rows[..., :rank],
        preferred_element_type=jnp.float32,
    ).astype(q_abs.dtype)


# ------------------------------------------------ the chain the slots share


def shared_prefix(tables, key_counts, block_size: int, xp=jnp):
    """Which leading blocks of their table rows a tick's slots share:
    ``(shared_blocks (slots,), reference slot)``.

    The reference is the first slot of the largest family of slots that
    start with the same block (not "the first live slot": one slot with a
    private copy of the prefix would defeat that).  ``shared_blocks[s]`` is
    the leading run of slot ``s``'s row that equals the reference's, held
    to the whole blocks its ``key_counts[s]`` reaches (so every position of
    the run is one the slot attends; 0 for an idle slot and a slot
    mid-prefill, whose count is 0) and to the chain's length, the longest
    run that at least two slots hold; all zeros where fewer than
    `MLA_SHARED_MIN_SLOTS` slots share.  One rule for the program
    (``xp=jnp``, on the tick's own arguments) and for the host's counters
    (``xp=np``, on the host's tables and positions)."""
    slots, blocks = tables.shape
    counts = xp.broadcast_to(xp.reshape(key_counts, (-1,)), (slots,))
    whole = counts // block_size
    sees = whole > 0
    first = tables[:, 0]
    family = (first[:, None] == first[None, :]) & sees[:, None] & sees[None, :]
    reference = xp.argmax(family.sum(axis=1))
    differs = tables != tables[reference][None, :]
    run = xp.where(differs.any(axis=1), xp.argmax(differs, axis=1), blocks)
    run = xp.minimum(run, whole)
    ids = xp.arange(slots)
    chain = xp.max(xp.where(ids == xp.argmax(run), 0, run))
    shared = xp.minimum(run, chain)
    enough = (shared > 0).sum() >= MLA_SHARED_MIN_SLOTS
    return xp.where(enough, shared, 0).astype(xp.int32), reference


def shared_split(tables, key_counts, block_size: int) -> dict:
    """What both kernels need of :func:`shared_prefix` (a tick's sublayers
    each ask with the same tables and counts, and XLA keeps one of the equal
    computations: the tick compiled for the v5e holds the same instructions
    as one that is handed the split, 5,694 at 4 sublayers; PR 39):
    ``shared`` (slots,) blocks and ``chain`` (blocks a slot,) the
    reference's row; ``order`` (slots,) the slots in descending order of
    ``shared`` (members next to each other, the longest first, so that a
    tile of query rows is full and its walk ends with its first slot's);
    ``state_at`` (slots,) the place in that order whose state the own pass
    fetches for a slot - its own where it shares, else (read by no one)
    the one the slot before it fetched, so nothing is fetched; ``own``
    (slots,) the keys a slot attends past its run and
    ``live_from`` (slots + 1,), the first slot from s on that holds one,
    ``slots`` where none does."""
    tables = jnp.asarray(tables, jnp.int32)
    slots = tables.shape[0]
    counts = jnp.broadcast_to(
        jnp.asarray(key_counts, jnp.int32).reshape(-1), (slots,)
    )
    shared, reference = shared_prefix(tables, counts, block_size)
    ids = jnp.arange(slots, dtype=jnp.int32)
    ahead = (shared[None, :] > shared[:, None]) | (
        (shared[None, :] == shared[:, None]) & (ids[None, :] < ids[:, None])
    )
    place = ahead.sum(axis=1, dtype=jnp.int32)
    sharer = jax.lax.cummax(jnp.where(shared > 0, ids, -1))
    own = counts - shared * block_size
    live_from = jnp.append(
        jax.lax.cummin(jnp.where(own > 0, ids, slots), reverse=True),
        jnp.int32(slots),
    )
    return {
        "shared": shared, "chain": tables[reference],
        "order": jnp.zeros_like(ids).at[place].set(ids),
        "state_at": jnp.where(sharer >= 0, place[sharer], 0), "own": own,
        "live_from": live_from,
    }


# --------------------------------------------------------- absorbed, kernels


def _group_copies(pool_hbm, buf, sems, block_of, live, b, go, block_size):
    """Start (``go``) or await the copies of ``live`` pool blocks, block
    ``i`` of them ``block_of(i)``, into buffer ``b``."""

    def one(i, carry):
        block = block_of(i) if go else 0
        rows = pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
        copy = pltpu.make_async_copy(
            pool_hbm.at[block], buf.at[b, rows], sems.at[b]
        )
        copy.start() if go else copy.wait()
        return carry

    jax.lax.fori_loop(0, live, one, 0)


def _fold_group(q, kv, visible, state, *, scale: float, rank: int):
    """One group of keys into a running softmax: ``q`` (rows, width), ``kv``
    (keys, width) whose first ``rank`` lanes are the values, ``visible``
    (rows, keys) or None where every key is, ``state`` = ``(m, l, acc)``;
    returns the new state."""
    m_prev, l_prev, acc = state
    s = jax.lax.dot_general(
        q, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                       # (rows, keys)
    if visible is not None:
        s = jnp.where(visible, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jax.lax.dot_general(
        p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc


def _mla_shared_kernel(
    chain_ref, shared_ref, _walking_ref, q_ref, pool_hbm, state_ref, buf, sems,
    turn, m_run, l_run, *, scale: float, block_size: int, group_blocks: int,
    tile_slots: int, heads_pad: int, rank: int,
):
    """The shared pass: one tile of ``tile_slots`` slots' query rows a grid
    step (slots in descending order of their shared blocks, ``shared_ref``,
    so a tile walks as far as its first slot shares and a tile of
    non-members walks nothing; ``_walking_ref`` is the block specs' alone),
    inside it a loop over the chain's groups of
    ``group_blocks`` blocks, copied through the reference's row
    ``chain_ref`` into one of two buffers while the other is computed on; a
    tile's last group starts the copies of the next tile's first.  A row
    sees the keys below its own slot's run.  Leaves the unnormalised state
    of the rows that share in ``state_ref``: the accumulator in its first
    ``rank`` lanes, the running maximum and sum in the two lanes after
    them (`_STATE_LANES` wide in all); what it leaves for a row that
    shares nothing (in a tile that walked: keys all masked, each at a
    probability of one; in one that did not: nothing written) is read by
    no one."""
    tile = pl.program_id(0)
    group_keys = group_blocks * block_size
    rows = tile_slots * heads_pad

    def blocks_of(t):          # ``shared_ref`` ends with a tile of zeros
        return shared_ref[t * tile_slots]

    def copies(t, group, b, go):
        first = group * group_blocks
        live = jnp.minimum(blocks_of(t) - first, group_blocks)
        _group_copies(
            pool_hbm, buf, sems, lambda i: chain_ref[first + i], live, b, go,
            block_size,
        )

    @pl.when(tile == 0)
    def _open():
        # Rows no copy has reached are multiplied by a probability of
        # exactly zero: they must hold numbers.
        buf[...] = jnp.zeros_like(buf)
        turn[0] = 0

        @pl.when(blocks_of(0) > 0)
        def _():
            copies(0, 0, 0, True)

    groups = pl.cdiv(blocks_of(tile), group_blocks)

    @pl.when(groups > 0)
    def _walk():
        q = q_ref[...]                              # (rows, width)
        row_slot = (
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads_pad
        )
        limit = jnp.zeros((rows, 1), jnp.int32)
        for j in range(tile_slots):
            limit = jnp.where(
                row_slot == j, shared_ref[tile * tile_slots + j] * block_size,
                limit,
            )
        # Below the run of the tile's last slot, its shortest, every row
        # sees every key.
        all_see = shared_ref[tile * tile_slots + tile_slots - 1] * block_size
        acc_ref = state_ref.at[:, :rank]
        fold = functools.partial(_fold_group, scale=scale, rank=rank)
        m_run[...] = jnp.full_like(m_run, NEG_INF)
        l_run[...] = jnp.zeros_like(l_run)
        acc_ref[...] = jnp.zeros((rows, rank), jnp.float32)

        def group_step(g, carry):
            b = turn[0]
            last = g + 1 == groups

            @pl.when(
                jnp.logical_or(jnp.logical_not(last), blocks_of(tile + 1) > 0)
            )
            def _():
                copies(
                    jnp.where(last, tile + 1, tile), jnp.where(last, 0, g + 1),
                    1 - b, True,
                )

            copies(tile, g, b, False)
            kv = buf[b]                             # (group_keys, width)
            state = (m_run[...], l_run[...], acc_ref[...])

            def masked():
                cols = jax.lax.broadcasted_iota(
                    jnp.int32, (rows, group_keys), 1
                ) + g * group_keys
                return fold(q, kv, cols < limit, state)

            m_run[...], l_run[...], acc_ref[...] = jax.lax.cond(
                (g + 1) * group_keys <= all_see,
                lambda: fold(q, kv, None, state), masked,
            )
            turn[0] = 1 - b
            return carry

        jax.lax.fori_loop(0, groups, group_step, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _STATE_LANES), 1)
        state_ref[:, rank:] = jnp.where(
            lane == 0, m_run[...], jnp.where(lane == 1, l_run[...], 0.0)
        )


def _mla_paged_kernel(
    tables_ref, counts_ref, starts_ref, live_from_ref, state_at_ref, q_ref,
    state_ref, pool_hbm, o_ref, buf, sems, turn, *, scale: float,
    block_size: int, group_blocks: int, slots: int, rank: int,
):
    """The own pass: one slot a grid step; inside it a loop over the groups
    of ``group_blocks`` pool blocks of the slot's own keys - ``counts_ref``
    of them, from block ``starts_ref`` of its row on (trip count from the
    count) - that starts at the state the shared pass left for the slot
    (``state_ref``, as `_mla_shared_kernel` lays it out; the empty state
    for a slot that shares nothing, ``starts_ref`` 0, whose block nobody
    wrote) and normalises at its end.  The pool stays in HBM: each live
    block of a group is copied through the block table into one of two VMEM
    buffers while the other buffer's group is computed on, and a slot's
    last group starts the copies of the next live slot's first.  A group is
    read once: ``(heads, width) x (keys, width)`` gives every head's scores,
    and the same buffer's first ``rank`` lanes are the values of ``(heads,
    keys) x (keys, rank)``."""
    slot = pl.program_id(0)
    group_keys = group_blocks * block_size

    def copies(s, group, b, go):
        """Start (``go``) or await the copies of group ``group`` of slot
        ``s``'s own keys into buffer ``b``: its live blocks and no others."""
        ahead = group * group_blocks
        live = jnp.minimum(
            pl.cdiv(counts_ref[s], block_size) - ahead, group_blocks
        )
        first = starts_ref[s] + ahead
        _group_copies(
            pool_hbm, buf, sems, lambda i: tables_ref[s, first + i], live, b,
            go, block_size,
        )

    @pl.when(slot == 0)
    def _open():
        # Rows no copy has reached are multiplied by a probability of
        # exactly zero: they must hold numbers.
        buf[...] = jnp.zeros_like(buf)
        turn[0] = 0

        @pl.when(live_from_ref[0] < slots)
        def _():
            copies(live_from_ref[0], 0, 0, True)

    count = counts_ref[slot]
    groups = pl.cdiv(count, group_keys)
    q = q_ref[0]                                    # (heads_pad, width)

    def group_step(g, state):
        b = turn[0]
        last = g + 1 == groups
        nxt_slot = jnp.where(last, live_from_ref[slot + 1], slot)

        @pl.when(nxt_slot < slots)
        def _():
            copies(nxt_slot, jnp.where(last, 0, g + 1), 1 - b, True)

        copies(slot, g, b, False)
        kv = buf[b]                                 # (group_keys, width)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], group_keys), 1
        ) + g * group_keys
        turn[0] = 1 - b
        return _fold_group(q, kv, cols < count, state, scale=scale, rank=rank)

    shares = starts_ref[slot] > 0
    state = state_ref[0]
    _, l, acc = jax.lax.fori_loop(
        0, groups, group_step,
        (
            jnp.where(shares, state[:, rank:rank + 1], NEG_INF),
            jnp.where(shares, state[:, rank + 1:rank + 2], 0.0),
            jnp.where(shares, state[:, :rank], 0.0),
        ),
    )
    # A slot with no keys walks no group: zeros over the guard, finite.
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def _mla_paged_impl(q_abs, pool, tables, key_counts, rank, scale, interpret):
    slots, num_heads, width = q_abs.shape
    _, block_size, _ = pool.shape
    split = shared_split(tables, key_counts, block_size)
    nbs = tables.shape[1]
    group = max(1, min(MLA_GROUP_KEYS // block_size, nbs))
    # Whole sublane tiles at the rows' width (16 rows of bfloat16).
    heads_pad = pl.cdiv(num_heads, 16) * 16
    q_rows = jnp.pad(q_abs, ((0, 0), (0, heads_pad - num_heads), (0, 0)))
    buffers = [
        pltpu.VMEM((2, group * block_size, width), pool.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((1,), jnp.int32),               # the buffer in turn
    ]

    # The shared pass, over the slots in descending order of their runs.
    tile_slots = max(1, min(MLA_SHARED_TILE_ROWS // heads_pad, slots))
    tiles = pl.cdiv(slots, tile_slots)
    places = tiles * tile_slots
    tile_rows = tile_slots * heads_pad
    q_placed = jnp.pad(
        q_rows[split["order"]], ((0, places - slots), (0, 0), (0, 0))
    ).reshape(places * heads_pad, width)
    # A tile of zeros more: the last tile asks what the next one shares.
    shared_placed = jnp.pad(
        split["shared"][split["order"]], (0, places - slots + tile_slots)
    )

    # A tile that walks nothing reads and writes nothing: the tiles past
    # the last that walks (the order is descending) all take the block of
    # the first of them, fetched once and written back once.
    walking = jnp.minimum(
        jnp.sum(shared_placed[: places : tile_slots] > 0, dtype=jnp.int32),
        tiles - 1,
    )

    def at_tile(lanes):
        return pl.BlockSpec(
            (tile_rows, lanes),
            lambda t, chain, shared, walking: (jnp.minimum(t, walking[0]), 0),
            memory_space=pltpu.VMEM,
        )

    state = pl.pallas_call(
        functools.partial(
            _mla_shared_kernel, scale=scale, block_size=block_size,
            group_blocks=group, tile_slots=tile_slots, heads_pad=heads_pad,
            rank=rank,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles,),
            in_specs=[at_tile(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=at_tile(rank + _STATE_LANES),
            scratch_shapes=buffers + [
                pltpu.VMEM((tile_rows, 1), jnp.float32),   # running maximum
                pltpu.VMEM((tile_rows, 1), jnp.float32),   # running sum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (places * heads_pad, rank + _STATE_LANES), jnp.float32
        ),
        # The buffers and the turn are carried from tile to tile.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=MLA_SHARED_VMEM_BYTES,
        ),
        interpret=interpret,
        name="mla_paged_attention_shared",
    )(split["chain"], shared_placed, walking.reshape(1), q_placed, pool)

    # The own pass: each slot's keys past its run.
    def at_slot(*block, placed=False):
        return pl.BlockSpec(
            (1, *block),
            lambda s, *refs: (refs[4][s] if placed else s,) + (0,) * len(block),
            memory_space=pltpu.VMEM,
        )

    out = pl.pallas_call(
        functools.partial(
            _mla_paged_kernel, scale=scale, block_size=block_size,
            group_blocks=group, slots=slots, rank=rank,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(slots,),
            in_specs=[
                at_slot(heads_pad, width),
                at_slot(heads_pad, rank + _STATE_LANES, placed=True),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=at_slot(heads_pad, rank),
            scratch_shapes=buffers,
        ),
        out_shape=jax.ShapeDtypeStruct((slots, heads_pad, rank), jnp.float32),
        # The buffers and the turn are carried from slot to slot.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="mla_paged_attention",
    )(
        jnp.asarray(tables, jnp.int32), split["own"], split["shared"],
        split["live_from"], split["state_at"], q_rows,
        state.reshape(places, heads_pad, rank + _STATE_LANES), pool,
    )
    return out[:, :num_heads].astype(q_abs.dtype)


def mla_paged_path(
    block_size: int, width: int, rank: int, backend: str | None = None
) -> str:
    """``"mla_paged"``, the kernel, on the TPU where the pool's blocks are
    whole sublane tiles and the value part of a row whole lane tiles;
    ``"xla"``, gathered rows, elsewhere (unaligned test shapes, every other
    backend, where the kernel would run in interpret mode)."""
    backend = backend or jax.default_backend()
    if backend == "tpu" and block_size % 16 == 0 and rank % 128 == 0 and width % 128 == 0:
        return "mla_paged"
    return "xla"


def mla_paged_attention(
    q_abs: jax.Array,
    pool: jax.Array,
    tables: jax.Array,
    key_counts: jax.Array,
    *,
    rank: int,
    scale: float,
    path: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One decode step of absorbed latent attention read straight out of
    the latent block pool, and only the blocks the slots hold - a chain of
    blocks that several slots share once for all of them.

    ``q_abs`` (slots, heads, latent width) are the absorbed queries,
    ``pool`` (num_blocks, block_size, width) one sublayer's latent rows,
    padded with zeros to whole lane tiles (`models/decode.init_latent_pool`;
    the queries are padded alike here, so the padding adds nothing to a
    score), ``tables`` (slots, blocks_per_slot) each slot's chain of block
    ids, ``key_counts`` (slots,) how many positions of its chain a slot
    attends to (``position + 1`` for the token just written, 0 for an idle
    slot: it copies nothing and yields zeros).  Returns (slots, heads,
    rank): each head's softmax-weighted sum of the rows' first ``rank``
    values.  ``path`` forces ``"mla_paged"`` (parity tests: interpret mode
    off the TPU) or ``"xla"``."""
    _, block_size, width = pool.shape
    if q_abs.shape[-1] > width or tables.shape[0] != q_abs.shape[0]:
        raise ValueError(
            f"shape mismatch: q_abs {q_abs.shape}, pool {pool.shape}, "
            f"tables {tables.shape}"
        )
    q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, width - q_abs.shape[-1])))
    path = path or mla_paged_path(block_size, width, rank)
    with jax.named_scope("mla_attn"):
        if path == "mla_paged":
            if interpret is None:
                from bpe_transformer_tpu.kernels.pallas.runtime import (
                    interpret_mode,
                )

                interpret = interpret_mode()
            return _mla_paged_impl(
                q_abs, pool, tables, key_counts, rank, float(scale), interpret
            )
        with jax.named_scope("pool_gather"):
            rows = pool[tables].reshape(q_abs.shape[0], -1, width)
        visible = jnp.arange(rows.shape[1]) < jnp.maximum(
            jnp.reshape(key_counts, (-1, 1)), 1
        )
    return xla_mla_rows_attention(q_abs, rows, visible, rank=rank, scale=scale)


# -------------------------------------------------- many query rows, XLA


@jax.named_scope("mla_chunk_attn")
def xla_mla_chunk_attention(
    q_nope, q_rope, rows, kv_b, q_positions, n_keys, *, scale: float
):
    """Absorbed attention of one sequence's queries against its latent
    rows: ``q_nope`` (heads, queries, nope), ``q_rope`` (heads, queries,
    rope), ``rows`` (keys, rank + rope) the sequence's cached rows from
    position 0, ``kv_b`` (heads, nope + v, rank) the key-value
    up-projection, ``q_positions`` (queries,) each query's position (it
    sees keys ``0 .. position``), ``n_keys`` how many leading rows any query
    sees (traced: the loop runs ``ceil(n_keys / MLA_CHUNK_KEY_BLOCK)``
    times).  Returns (heads, queries, v).

    A flash loop in XLA: the key up-projection is folded into the queries
    once, a block of rows is scored as it lies in float32 and folded into a
    running softmax, and the value up-projection is applied once after the
    loop - so nothing as large as queries x keys x heads is ever held and
    work follows the live keys, not the table."""
    heads, queries, nope = q_nope.shape
    keys = rows.shape[0]
    rank = kv_b.shape[-1]
    block = min(MLA_CHUNK_KEY_BLOCK, keys)
    # Whole blocks: rows past the last position are masked like any other.
    rows = jnp.pad(rows, ((0, -keys % block), (0, 0)))
    q_abs = jnp.concatenate(
        [jnp.einsum("hqd,hdc->hqc", q_nope, kv_b[:, :nope]), q_rope], axis=-1
    )

    def step(i, carry):
        m_prev, l_prev, acc = carry
        part = jax.lax.dynamic_slice_in_dim(rows, i * block, block)
        s = jnp.einsum(
            "hqw,kw->hqk", q_abs, part, preferred_element_type=jnp.float32
        )
        key_pos = i * block + jnp.arange(block)
        s = jnp.where(
            key_pos[None, :] <= q_positions[:, None], s * scale, NEG_INF
        )
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "hqk,kc->hqc", p.astype(part.dtype), part[:, :rank],
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(
        0, (n_keys + block - 1) // block, step,
        (
            jnp.full((heads, queries, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, queries, 1), jnp.float32),
            jnp.zeros((heads, queries, rank), jnp.float32),
        ),
    )
    out = (acc / jnp.maximum(l, 1e-30)).astype(q_nope.dtype)
    return jnp.einsum("hqc,hdc->hqd", out, kv_b[:, nope:])


# ------------------------------------------- many query rows, expanded, kernel


def chunk_walk(lo, hi, n_keys, block: int, minimum=min):
    """``(clear, end)``: of the blocks of ``block`` keys from position 0 on,
    the ones that query rows at positions ``lo .. hi`` (their least and
    their greatest) walk when ``n_keys`` leading keys are live.  Blocks ``0
    .. clear - 1`` lie wholly at or under ``lo``: every row sees every key
    of them, and they take no mask.  Blocks ``clear .. end - 1`` hold a key
    that some row sees and one that some row does not - an edge crosses them
    - and are folded under the mask.  No row sees a key of a block from
    ``end`` on: it is neither copied, expanded nor computed.  ``clear <=
    end``.

    One definition for the kernel, which walks a tile of query rows by it
    (on traced arrays: ``minimum`` is then `jnp.minimum`), and for a test's
    count of the pairs themselves (on integers)."""
    end = (minimum(hi + 1, n_keys) + block - 1) // block
    return minimum((lo + 1) // block, end), end


def _mla_chunk_kernel(
    clear_ref, end_ref, q_ref, pos_ref, rows_hbm, w_ref, o_ref, buf, sems,
    k_ref, v_ref, m_ref, l_ref, acc_ref, *, scale: float, rank: int,
    nope: int, block: int, tile_rows: int,
):
    """One head against the whole bucket of query rows a grid step, and the
    walk over the blocks of ``block`` keys inside it, as far as the last
    block any tile of ``tile_rows`` rows sees (``clear_ref``, ``end_ref``:
    each tile's `chunk_walk`).  A trip of the walk awaits the copy of its
    block of latent rows (``rows_hbm`` stays in HBM; two buffers, the next
    block's copy - or, behind a head's last block, the next head's first -
    started before the block is computed on), expands the block into this
    head's keys and values **once**, in VMEM (``k_ref``, ``v_ref``), and
    folds them into the softmax state of each tile in turn: with no mask
    where the tile's every row sees the block's every key, under the mask
    where the tile's edge crosses the block, and not at all where none of
    its rows sees any of it.  The running maximum and sum lie a row's value
    in every lane of ``m_ref`` / ``l_ref``, so that a reduction's column
    meets the state with one broadcast and the state meets the accumulator
    with none."""
    head, heads = pl.program_id(0), pl.num_programs(0)
    tiles = q_ref.shape[1] // tile_rows
    lanes = m_ref.shape[1]
    end = functools.reduce(jnp.maximum, [end_ref[t] for t in range(tiles)])

    def copy(b, slot):
        at = pl.ds(pl.multiple_of(b * block, block), block)
        return pltpu.make_async_copy(rows_hbm.at[at], buf.at[slot], sems.at[slot])

    @pl.when(jnp.logical_and(head == 0, end > 0))
    def _first():
        copy(0, 0).start()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(t, b, masked: bool):
        """The expanded block ``b`` into tile ``t``'s state."""
        rows = pl.ds(pl.multiple_of(t * tile_rows, tile_rows), tile_rows)
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(
            q_ref[0, rows, :], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # (tile_rows, block)
        if masked:
            key_at = b * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1
            )
            seen = key_at <= pltpu.repeat(pos_ref[rows, :], block // lanes, axis=1)
            s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - pltpu.repeat(m_new, block // lanes, axis=1))
        l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
            p, axis=-1, keepdims=True
        )
        acc_ref[rows, :] = acc_ref[rows, :] * pltpu.repeat(
            alpha, acc_ref.shape[1] // lanes, axis=1
        ) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[rows, :] = m_new

    def trip(b, _):
        slot = jax.lax.rem(head * end + b, 2)
        copy(b, slot).wait()
        more = b + 1 < end

        @pl.when(jnp.logical_or(more, head + 1 < heads))
        def _():
            copy(jnp.where(more, b + 1, 0), 1 - slot).start()

        rows = buf[slot]                            # (block, rank + rope')
        kv = jax.lax.dot_general(
            rows[:, :rank], w_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(rows.dtype)                        # (block, nope + v)
        # A head's key: its own ``nope`` values beside the one rotated key
        # all heads share, as the query's two parts lie.
        k_ref[:, :nope] = kv[:, :nope]
        k_ref[:, nope:] = rows[:, rank:]
        v_ref[...] = kv[:, nope:]

        def tile(t, _):
            clear = clear_ref[t]

            @pl.when(b < clear)
            def _():
                fold(t, b, False)

            @pl.when(jnp.logical_and(b >= clear, b < end_ref[t]))
            def _():
                fold(t, b, True)

        jax.lax.fori_loop(0, tiles, tile, None)

    jax.lax.fori_loop(0, end, trip, None)
    # No live key (``n_keys`` 0) walks no block: zeros over the guard.
    o_ref[0] = (
        acc_ref[...] / pltpu.repeat(
            jnp.maximum(l_ref[...], 1e-30), acc_ref.shape[1] // lanes, axis=1
        )
    ).astype(o_ref.dtype)


def chunk_tiles(queries: int, keys: int) -> tuple[int, int]:
    """``(query rows a tile, keys a block)`` of the chunk kernel for a bucket
    of ``queries`` rows over a chain of ``keys`` rows: the module's
    constants, held to the bucket and to the chain in whole lane tiles."""
    return (
        min(MLA_CHUNK_TILE_ROWS, queries),
        min(MLA_CHUNK_KERNEL_KEYS, -(-keys // 128) * 128),
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _mla_chunk_impl(q, rows, kv_b, q_positions, n_keys, scale, interpret):
    heads, queries, q_width = q.shape
    keys, width = rows.shape
    rank = kv_b.shape[-1]
    nope = q_width - (width - rank)
    v = kv_b.shape[1] - nope
    tile_rows, block = chunk_tiles(queries, keys)
    # A row's running maximum and sum fill a lane tile (or what of one
    # divides a value), and so does its position.
    lanes = math.gcd(v, _STATE_LANES)
    positions = q_positions.astype(jnp.int32)
    by_tile = positions.reshape(queries // tile_rows, tile_rows)
    clear, end = chunk_walk(
        by_tile.min(axis=1), by_tile.max(axis=1), jnp.asarray(n_keys, jnp.int32),
        block, jnp.minimum,
    )

    def whole(*shape):
        return pl.BlockSpec(shape, lambda h, *_: (0,) * len(shape))

    def at_head(*shape):
        return pl.BlockSpec((1, *shape), lambda h, *_: (h,) + (0,) * len(shape))

    return pl.pallas_call(
        functools.partial(
            _mla_chunk_kernel, scale=scale, rank=rank, nope=nope, block=block,
            tile_rows=tile_rows,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(heads,),
            in_specs=[
                at_head(queries, q_width), whole(queries, lanes),
                pl.BlockSpec(memory_space=pl.ANY), at_head(nope + v, rank),
            ],
            out_specs=at_head(queries, v),
            scratch_shapes=[
                pltpu.VMEM((2, block, width), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((block, q_width), rows.dtype),   # a head's keys
                pltpu.VMEM((block, v), rows.dtype),         # and its values
                pltpu.VMEM((queries, lanes), jnp.float32),  # running maximum
                pltpu.VMEM((queries, lanes), jnp.float32),  # running sum
                pltpu.VMEM((queries, v), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((heads, queries, v), q.dtype),
        # A head's last block starts the copy of the next head's first.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=MLA_SHARED_VMEM_BYTES,
        ),
        interpret=interpret,
        name="mla_chunk_attention",
    )(
        clear, end, q, jnp.broadcast_to(positions[:, None], (queries, lanes)),
        rows, kv_b,
    )


def mla_chunk_path(
    queries: int, nope: int, v: int, rank: int, backend: str | None = None
) -> str:
    """``"mla_chunk"``, the expanded form in the kernel, on the TPU for a
    bucket of `MLA_CHUNK_MIN_ROWS` query rows or more in whole tiles, where
    a head's key, its value and the latent are whole lane tiles (the rotated
    key is padded to them, as the pool pads it); ``"xla"``, the absorbed
    loop, elsewhere (every other backend, where the kernel would run in
    interpret mode; unaligned test shapes; a short chunk, whose keys'
    expansion would cost more than the absorbed form's wider rows)."""
    backend = backend or jax.default_backend()
    tile_rows = min(MLA_CHUNK_TILE_ROWS, queries)
    if (
        backend == "tpu" and queries >= MLA_CHUNK_MIN_ROWS
        and tile_rows % 128 == 0 and queries % tile_rows == 0
        and nope % 128 == 0 and v % 128 == 0 and rank % 128 == 0
    ):
        return "mla_chunk"
    return "xla"


@jax.named_scope("mla_chunk_attn")
def mla_chunk_attention(
    q_nope, q_rope, rows, kv_b, q_positions, n_keys, *, scale: float,
    interpret: bool | None = None,
):
    """`xla_mla_chunk_attention`'s contract in the **expanded** form, one
    Pallas kernel: every head's keys and values of a block of latent rows
    are made from the rows where they are attended, in VMEM, and neither
    they nor a score reach HBM.  ``rows`` may come as a pool pads them
    (zeros up to whole lane tiles past ``rank + rope``); the queries' shapes
    are `mla_chunk_path`'s to admit."""
    rank, rope = kv_b.shape[-1], q_rope.shape[-1]
    lanes = -(-rope // 128) * 128
    keys = rows.shape[0]
    _, block = chunk_tiles(q_nope.shape[1], keys)
    # Whole blocks of whole lane tiles: a row past the last position is
    # masked like any other, and a padded lane meets a zero of the query.
    rows = jnp.pad(
        rows, ((0, -keys % block), (0, max(rank + lanes - rows.shape[1], 0)))
    )[:, : rank + lanes]
    q = jnp.concatenate(
        [q_nope, jnp.pad(q_rope, ((0, 0), (0, 0), (0, lanes - rope)))], axis=-1
    )
    if interpret is None:
        from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

        interpret = interpret_mode()
    return _mla_chunk_impl(
        q, rows, kv_b, q_positions, n_keys, float(scale), interpret
    )
