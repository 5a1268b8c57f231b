"""Attention over paged rows whose key is wider than their value, from a
window's start on and with a sink in the softmax: the attention of the
`models/decode.GroupedRows` cache kind, tick and chunk.

A pool of that kind holds, a layer, ``(blocks, block_size, kv_heads * (d_key
+ d_value))`` rows: a position's keys, every K/V head's side by side, and
behind them its values - no value padded to the key's width.  The layer's
group (full or window) says how many K/V heads that is.  The mathematics, for
a query at index ``i`` of its slot's row of keys (a window group's row starts
at the slot's first live block, so indices are relative):

    s_ij = q_i . k_j * scale           for  max(i - window + 1, 0) <= j <= i
    m_i  = max(max_j s_ij, b_h)
    o_i  = sum_j exp(s_ij - m_i) v_j / (sum_j exp(s_ij - m_i) + exp(b_h - m_i))

``b_h`` is the query head's sink logit: it joins the denominator and carries
no value.  Without a sink the term is absent; without a window ``j`` runs from
0.

Two kernels on the TPU, each called under the name of its group so that
device events tell the groups apart:

* :func:`sink_paged_attention` (``sink_paged_attention_full`` /
  ``_window``) - the tick: one query a slot straight out of the pool, the
  blocks a slot holds inside what it may see and no others (a window group's
  row begins at the window's first block: the host recycles what lies below).
  `decode_attention.paged_decode_attention`'s schedule - a slot a grid step, a
  group of blocks copied through the table while the group before it is
  computed on, the heads side by side against block-diagonal queries - with
  one copy a block (keys and values lie in one row), a first visible key and
  the sink as the softmax's starting state.
* :func:`sink_chunk_attention` (``sink_chunk_attention_full`` / ``_window``)
  - the chunk: one slot's rows against its gathered chain, flash accumulation
  over blocks of keys, so no (chunk x context) score reaches HBM.  A grid
  step is a block of query rows of one K/V head, whose keys and values lie in
  VMEM (fetched once a head); the walk over blocks of keys is a loop INSIDE
  the step, bounded by :func:`chunk_walk` from the chunk's position: the
  blocks every row of the query block sees whole are folded with no mask -
  matmul, scale, max / exp / sum, rescale, matmul - the one or two the
  diagonal crosses (and, under a window, its lower edge) under the mask, and
  a block no row sees costs nothing: no step, no copy, no branch.  A window
  layer's chain is window + chunk long and its walk two or three blocks.

Elsewhere (the CPU's tests) the same contracts are a gather and a masked
softmax in XLA (:func:`xla_sink_attention`); ``interpret=True`` runs the
kernels there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.kernels.pallas.decode_attention import (
    SUBLANES,
    _head_owner,
)
from bpe_transformer_tpu.ops.core import MASK_VALUE as NEG_INF

#: Keys a step of the tick's kernel copies and computes on.
PAGED_GROUP_KEYS = 256
#: The chunk kernel's blocks.  `CHUNK_QUERY_ROWS`: query rows of a grid step,
#: every query head of a K/V head at once (16 x 128 rows of scores at
#: 16-to-1) - and the keys of a window layer's block, so that a walk reads
#: little outside the window.  `CHUNK_KEYS`: keys of a full layer's block,
#: one trip of the loop inside the step (2,048 x 256 float32 scores: of the
#: blocks of 128 rows timed on the v5e at 256, 512 and 1,024 keys the
#: fastest, PERF.md section 6, PR 47).
CHUNK_QUERY_ROWS = 128
CHUNK_KEYS = 256
CHUNK_VMEM_LIMIT_BYTES = 96 * 1024 * 1024
#: What a K/V head's keys and values held in VMEM may take, one of the two
#: buffers the pipeline keeps: a third of the limit each, and the last third
#: for a step's scores and its state.
CHUNK_HELD_BYTES = CHUNK_VMEM_LIMIT_BYTES // 3


def sink_logits(sink, heads: int):
    """The sink as the kernels take it: float32 ``(heads,)``, `NEG_INF`
    (a term of exactly zero) where the layer has none."""
    if sink is None:
        return jnp.full((heads,), NEG_INF, jnp.float32)
    return sink.astype(jnp.float32)


# ------------------------------------------------------------------ XLA


def xla_sink_attention(q, k, v, q_at, *, window=None, sink=None, first=None):
    """The mathematics above with materialized scores.  ``q`` (batch, rows,
    heads, d_key); ``k`` (batch, keys, kv_heads, d_key); ``v`` (batch, keys,
    kv_heads, d_value); ``q_at`` (batch, rows) each query's own index among
    the keys (below 0: the row sees nothing and yields zeros); ``first``
    (batch, rows) a first visible key where the caller counts the window
    itself.  Returns (batch, rows, heads, d_value)."""
    batch, rows, heads, d_key = q.shape
    kv_heads = k.shape[2]
    qg = q.reshape(batch, rows, kv_heads, heads // kv_heads, d_key)
    scores = jnp.einsum(
        "brkgd,bjkd->bkgrj", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * d_key**-0.5
    key_at = jnp.arange(k.shape[1])[None, None, :]
    visible = key_at <= q_at[..., None]
    if window is not None:
        visible &= q_at[..., None] - key_at < window
    if first is not None:
        visible &= key_at >= first[..., None]
    scores = jnp.where(visible[:, None, None], scores, NEG_INF)
    top = jnp.max(scores, axis=-1, keepdims=True)
    b = sink_logits(sink, heads).reshape(1, kv_heads, heads // kv_heads, 1, 1)
    top = jnp.maximum(top, b)
    weights = jnp.where(visible[:, None, None], jnp.exp(scores - top), 0.0)
    total = jnp.sum(weights, axis=-1, keepdims=True) + jnp.exp(b - top)
    out = jnp.einsum(
        "bkgrj,bjkd->brkgd", weights / jnp.maximum(total, 1e-30),
        v.astype(jnp.float32),
    )
    return out.reshape(batch, rows, heads, v.shape[-1]).astype(q.dtype)


def split_rows(rows, kv_heads: int, d_key: int):
    """Pool rows ``(..., kv_heads * (d_key + d_value))`` as ``(keys (...,
    kv_heads, d_key), values (..., kv_heads, d_value))``."""
    k_width = kv_heads * d_key
    k, v = rows[..., :k_width], rows[..., k_width:]
    return (
        k.reshape(*k.shape[:-1], kv_heads, d_key),
        v.reshape(*v.shape[:-1], kv_heads, v.shape[-1] // kv_heads),
    )


# ----------------------------------------------------------------- tick


def _paged_kernel(
    tables_ref, counts_ref, firsts_ref, live_from_ref, q_ref, pick_ref,
    sink_ref, kv_hbm, o_ref, kv_buf, sems, turn, *, scale: float,
    block_size: int, group_blocks: int, slots: int, heads_per_kv: int,
    k_width: int,
):
    """`decode_attention._paged_decode_kernel` over rows of keys and values:
    one slot a grid step, a loop over the slot's live groups of
    ``group_blocks`` pool blocks, each live block one copy through the table
    into the buffer that is not being computed on, the last group of a slot
    starting the next live slot's first.  Keys below ``firsts_ref[slot]``
    are masked; the running maximum and denominator start from the sink."""
    slot = pl.program_id(0)
    group_keys = group_blocks * block_size

    def copies(s, group, buf, go):
        first = group * group_blocks
        live = jnp.minimum(
            pl.cdiv(counts_ref[s], block_size) - first, group_blocks
        )

        def one(i, carry):
            block = tables_ref[s, first + i] if go else 0
            rows = pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
            copy = pltpu.make_async_copy(
                kv_hbm.at[block], kv_buf.at[buf, rows], sems.at[buf]
            )
            copy.start() if go else copy.wait()
            return carry

        jax.lax.fori_loop(0, live, one, 0)

    @pl.when(slot == 0)
    def _open():
        # Rows no copy has reached are multiplied by a probability of
        # exactly zero: they must hold numbers.
        kv_buf[...] = jnp.zeros_like(kv_buf)
        turn[0] = 0

        @pl.when(live_from_ref[0] < slots)
        def _():
            copies(live_from_ref[0], 0, 0, True)

    count = counts_ref[slot]
    first_key = firsts_ref[slot]
    groups = pl.cdiv(count, group_keys)
    q = q_ref[0]                                    # (heads_pad, k_width)
    heads_pad = q.shape[0]
    v_width = kv_buf.shape[-1] - k_width

    def group_step(g, carry):
        m_prev, l_prev, acc = carry
        buf = turn[0]
        last = g + 1 == groups
        nxt_slot = jnp.where(last, live_from_ref[slot + 1], slot)

        @pl.when(nxt_slot < slots)
        def _():
            copies(nxt_slot, jnp.where(last, 0, g + 1), 1 - buf, True)

        copies(slot, g, buf, False)
        k = kv_buf[buf, :, :k_width]                # (group_keys, k_width)
        v = kv_buf[buf, :, k_width:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (heads_pad, group_keys)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + g * group_keys
        seen = (cols < count) & (cols >= first_key)
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        turn[0] = 1 - buf
        return m_new, l_new, acc

    sink = sink_ref[...]                            # (heads_pad, 1)
    _, l, acc = jax.lax.fori_loop(
        0, groups, group_step,
        (
            sink,
            jnp.where(sink > 0.5 * NEG_INF, 1.0, 0.0),
            jnp.zeros((heads_pad, v_width), jnp.float32),
        ),
    )
    # A slot with no keys walks no group: zeros over the guard, finite.
    out = acc / jnp.maximum(l, 1e-30)
    for j in range(heads_per_kv):
        o_ref[0, j:j + 1, :] = jnp.sum(
            out * pick_ref[j], axis=0, keepdims=True
        ).astype(o_ref.dtype)


def paged_group_blocks(block_size: int, blocks_per_slot: int) -> int:
    return max(1, min(PAGED_GROUP_KEYS // block_size, blocks_per_slot))


@functools.partial(
    jax.jit, static_argnames=("kv_heads", "d_key", "name", "interpret")
)
def _paged_impl(
    q, pool, tables, key_counts, first_keys, sink, *, kv_heads, d_key, name,
    interpret,
):
    slots, num_heads, _ = q.shape
    _, block_size, width = pool.shape
    k_width = kv_heads * d_key
    v_width = width - k_width
    d_value = v_width // kv_heads
    per_kv = num_heads // kv_heads
    group = paged_group_blocks(block_size, tables.shape[1])
    heads_pad = pl.cdiv(num_heads, 2 * SUBLANES) * 2 * SUBLANES

    owner = _head_owner(num_heads, kv_heads, heads_pad)
    q_rows = jnp.einsum(
        "shd,hk->shkd",
        jnp.pad(q, ((0, 0), (0, heads_pad - num_heads), (0, 0))),
        owner.astype(q.dtype),
    ).reshape(slots, heads_pad, k_width)
    # pick[j, h, lane]: head h is the j-th of its kv head, and the lane is
    # that kv head's.
    pick = (
        jnp.repeat(owner, d_value, axis=1)[None]
        & (jnp.arange(heads_pad) % per_kv == jnp.arange(per_kv)[:, None])[
            :, :, None
        ]
    ).astype(jnp.float32)
    sink_rows = jnp.pad(
        sink_logits(sink, num_heads), (0, heads_pad - num_heads),
        constant_values=NEG_INF,
    )[:, None]

    counts = jnp.asarray(key_counts, jnp.int32)
    firsts = jnp.asarray(first_keys, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    # live_from[s]: the first slot from s on that holds a key, ``slots``
    # where none does (entry ``slots`` too).
    index = jnp.where(counts > 0, jnp.arange(slots, dtype=jnp.int32), slots)
    live_from = jnp.append(
        jax.lax.cummin(index, reverse=True), jnp.int32(slots)
    )

    def at_slot(*block):
        return pl.BlockSpec(
            (1, *block), lambda s, *_: (s,) + (0,) * len(block),
            memory_space=pltpu.VMEM,
        )

    def whole(*shape):
        return pl.BlockSpec(
            shape, lambda s, *_: (0,) * len(shape), memory_space=pltpu.VMEM
        )

    kernel = functools.partial(
        _paged_kernel, scale=d_key**-0.5, block_size=block_size,
        group_blocks=group, slots=slots, heads_per_kv=per_kv, k_width=k_width,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slots,),
            in_specs=[
                at_slot(heads_pad, k_width), whole(per_kv, heads_pad, v_width),
                whole(heads_pad, 1), pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=at_slot(per_kv, v_width),
            scratch_shapes=[
                pltpu.VMEM((2, group * block_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),       # the buffer in turn
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, per_kv, v_width), jnp.float32),
        # The buffers and the turn are carried from slot to slot.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name=name,
    )(tables, counts, firsts, live_from, q_rows, pick, sink_rows, pool)
    # out[s, j, k * d + i] is element i of head k * per_kv + j.
    out = jnp.swapaxes(out.reshape(slots, per_kv, kv_heads, d_value), 1, 2)
    return out.reshape(slots, num_heads, d_value).astype(q.dtype)


def sink_paged_attention(
    q, pool, tables, key_counts, first_keys, sink=None, *, kv_heads: int,
    window: bool, interpret: bool | None = None,
):
    """The tick's attention straight out of a `GroupedRows` pool array.

    ``q`` (slots, heads, d_key); ``pool`` (blocks, block_size, kv_heads *
    (d_key + d_value)); ``tables`` (slots, blocks a row) the slots' rows of
    block ids; ``key_counts`` (slots,) how many keys of its row a slot
    attends to, its own included (0: idle - nothing copied, zeros out);
    ``first_keys`` (slots,) the first of them it may see (a window's start
    inside the row's first block); ``sink`` (heads,) float32 or None.
    ``window`` names the call for the device's events and nothing else:
    which keys are seen is the counts' and the firsts' to say.  Returns
    (slots, heads, d_value)."""
    if interpret is None:
        from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

        interpret = interpret_mode()
    slots, num_heads, d_key = q.shape
    if (
        pool.ndim != 3 or tables.ndim != 2 or tables.shape[0] != slots
        or num_heads % kv_heads or pool.shape[2] <= kv_heads * d_key
        or (pool.shape[2] - kv_heads * d_key) % kv_heads
    ):
        raise ValueError(
            f"shape mismatch: q {q.shape}, pool {pool.shape} (blocks, "
            f"block_size, kv_heads={kv_heads} x (d_key + d_value)), tables "
            f"{tables.shape}"
        )
    return _paged_impl(
        q, pool, tables, key_counts, first_keys, sink, kv_heads=kv_heads,
        d_key=d_key, interpret=interpret,
        name="sink_paged_attention_" + ("window" if window else "full"),
    )


def sink_paged_path(block_size: int, width: int, k_width: int) -> str:
    """``"sink_paged"`` on the TPU where a row's keys and its values are
    whole lane tiles and a block whole sublane tiles, else ``"xla"``
    (gathered rows; always on the CPU)."""
    if (
        jax.default_backend() == "tpu" and width % 128 == 0
        and k_width % 128 == 0 and block_size % 16 == 0
    ):
        return "sink_paged"
    return "xla"


# ---------------------------------------------------------------- chunk


def _chunk_kernel(
    at_ref, q_ref, sink_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
    scale: float, window: int | None, tq: int, tk: int, key_blocks: int,
    held_blocks: int, guard: bool,
):
    """One block of ``tq`` query rows of every query head of one K/V head a
    grid step, against the ``held_blocks`` blocks of ``tk`` keys of the
    head's chain that lie in VMEM (the whole chain where it fits; else the
    grid's last axis moves over its parts, under one softmax state).  The
    walk over key blocks is here, not in the grid: `chunk_walk` gives, from
    the chunk's position, the blocks an edge crosses below (a window's lower
    edge), the ``clear`` blocks every row sees whole, and the blocks an edge
    crosses above (the diagonal).  A clear block is folded with no mask at
    all; a block outside the walk costs nothing.  Flash accumulation from
    the sink's state.  ``guard``: a row may meet a block of which it sees
    nothing while its running maximum is still `NEG_INF` (a window and no
    sink), so the probabilities are masked too."""
    i, part = pl.program_id(1), pl.program_id(2)
    first, clear_lo, clear_hi, end = chunk_walk(
        at_ref[0], i, tq, tk, window, key_blocks, jnp.minimum, jnp.maximum
    )
    group, lanes = q_ref.shape[1], m_ref.shape[1]
    held_from = part * held_blocks

    @pl.when(part == 0)
    def _start():
        sink = sink_ref[0]                          # (group * tq, lanes)
        m_ref[...] = sink
        l_ref[...] = jnp.where(sink > 0.5 * NEG_INF, 1.0, 0.0)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(block, edge: bool):
        q = q_ref[0].reshape(group * tq, q_ref.shape[-1])
        at = pl.ds(pl.multiple_of((block - held_from) * tk, tk), tk)
        k, v = k_ref[0, at, :], v_ref[0, at, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (group * tq, tk)
        if edge:
            row = jax.lax.broadcasted_iota(jnp.int32, (group * tq, 1), 0)
            q_at = at_ref[0] + i * tq + jax.lax.rem(row, tq)
            key_at = block * tk + jax.lax.broadcasted_iota(
                jnp.int32, (1, tk), 1
            )
            seen = key_at <= q_at
            if window is not None:
                seen &= q_at - key_at < window
            s = jnp.where(seen, s, NEG_INF)
        # The state lies a row's value in every lane: a reduction's column
        # meets it with one broadcast, and it meets the accumulator with none.
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - pltpu.repeat(m_new, tk // lanes, axis=1))
        if edge and guard:
            p = jnp.where(seen, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * pltpu.repeat(
            alpha, acc_ref.shape[1] // lanes, axis=1
        ) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    def walk(lo, hi, edge: bool):
        """Fold blocks ``lo .. hi - 1``, those of them that are held."""
        jax.lax.fori_loop(
            jnp.maximum(lo, held_from),
            jnp.minimum(hi, held_from + held_blocks),
            lambda block, _: fold(block, edge), None,
        )

    if window is not None:
        walk(first, clear_lo, True)
    walk(clear_lo, clear_hi, False)
    walk(clear_hi, end, True)

    @pl.when(part == pl.num_programs(2) - 1)
    def _end():
        out = acc_ref[...] / pltpu.repeat(
            jnp.maximum(l_ref[...], 1e-30), acc_ref.shape[1] // lanes, axis=1
        )
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def chunk_walk(
    q_at0, i, tq: int, tk: int, window, key_blocks: int, minimum=min,
    maximum=max,
):
    """``(first, clear_lo, clear_hi, end)``: the blocks of ``tk`` keys that
    query block ``i`` of a chunk whose first row sits at ``q_at0`` walks.
    ``first .. end - 1`` are the blocks that hold a key any of its rows
    sees, inside the chain: from its first row's window start (block 0
    without a window) to its last row's own key.  ``clear_lo .. clear_hi -
    1`` of them lie wholly inside what EVERY row sees - up to the first
    row's own key and, under a window, from the last row's window start -
    and take no mask; the others, below and above, are crossed by an edge.
    ``first <= clear_lo <= clear_hi <= end``.

    One definition for both sides: the host counts with it on integers
    (`chunk_walk_blocks`), the kernel derives its loops' bounds from it on
    traced scalars (``minimum`` / ``maximum`` are then `jnp`'s)."""
    top = q_at0 + i * tq                            # the first row's own key
    bottom = top + tq - 1                           # the last row's
    end = minimum(bottom // tk + 1, key_blocks)
    clear_hi = minimum((top + 1) // tk, end)
    if window is None:
        return 0, 0, clear_hi, end
    first = minimum(maximum(top - window + 1, 0) // tk, end)
    reach = maximum(bottom - window + 1, 0)         # the last row's window start
    clear_lo = minimum(maximum((reach + tk - 1) // tk, first), end)
    return first, clear_lo, maximum(clear_hi, clear_lo), end


def chunk_tiles(rows: int, keys: int, window) -> tuple[int, int]:
    """``(query rows, keys)`` of a block of the chunk kernel: a window layer
    takes blocks of keys as small as its blocks of queries, so that a walk
    reads little outside the window."""
    tq = math.gcd(rows, CHUNK_QUERY_ROWS)
    return tq, math.gcd(keys, CHUNK_KEYS if window is None else CHUNK_QUERY_ROWS)


def chunk_held_blocks(key_blocks: int, block_bytes: int) -> int:
    """How many of a chain's ``key_blocks`` blocks a grid step of the chunk
    kernel holds in VMEM (a block of one K/V head's keys and values takes
    ``block_bytes`` there): all of them where they fit `CHUNK_HELD_BYTES`,
    else the most that do and divide the chain."""
    fit = min(max(CHUNK_HELD_BYTES // block_bytes, 1), key_blocks)
    return max(n for n in range(1, fit + 1) if key_blocks % n == 0)


def chunk_walk_blocks(start: int, rows: int, keys: int, window) -> tuple[int, int]:
    """``(visited, masked)``: the key blocks the kernel's walks fold for a
    chunk of ``rows`` query rows (the bucket: rows of padding walk like any
    other) from position ``start`` over a chain of ``keys``, and those of
    them folded under a mask.  A K/V head's count: every head walks alike."""
    tq, tk = chunk_tiles(rows, keys, window)
    visited = masked = 0
    for i in range(rows // tq):
        first, clear_lo, clear_hi, end = chunk_walk(start, i, tq, tk, window, keys // tk)
        visited += end - first
        masked += end - first - (clear_hi - clear_lo)
    return visited, masked


@functools.partial(jax.jit, static_argnames=("window", "name", "interpret"))
def _chunk_impl(q, k, v, q_at0, sink, *, window, name, interpret):
    rows, heads, d_key = q.shape
    keys, kv_heads, d_value = v.shape
    group = heads // kv_heads
    tq, tk = chunk_tiles(rows, keys, window)
    key_blocks = keys // tk
    # In VMEM a key and a value take whole lane tiles.
    held_lanes = sum(-(-d // 128) * 128 for d in (d_key, d_value))
    held = chunk_held_blocks(key_blocks, tk * held_lanes * k.dtype.itemsize)
    # (kv, group, rows, d) and (kv, keys, d): a K/V head's own rows together.
    qg = jnp.transpose(q.reshape(rows, kv_heads, group, d_key), (1, 2, 0, 3))
    kg, vg = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)
    # A row's running maximum and denominator fill a lane tile (or what of
    # one divides a block of keys and a value), and so does its sink.
    state_lanes = math.gcd(tk, d_value, 128)
    sink_rows = jnp.broadcast_to(
        jnp.repeat(
            sink_logits(sink, heads).reshape(kv_heads, group), tq, axis=1
        )[..., None],
        (kv_heads, group * tq, state_lanes),
    )

    def held_part(g, i, part, at):
        """The part of the chain a step holds: its own, or the nearest one
        its walk reaches (no new copy for a part it does not)."""
        first, _, _, end = chunk_walk(
            at[0], i, tq, tk, window, key_blocks, jnp.minimum, jnp.maximum
        )
        return g, jnp.clip(part, first // held, (end - 1) // held), 0

    kernel = functools.partial(
        _chunk_kernel, scale=d_key**-0.5, window=window, tq=tq, tk=tk,
        key_blocks=key_blocks, held_blocks=held,
        guard=window is not None and sink is None,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kv_heads, rows // tq, key_blocks // held),
            in_specs=[
                pl.BlockSpec(
                    (1, group, tq, d_key), lambda g, i, part, at: (g, 0, i, 0)
                ),
                pl.BlockSpec(
                    (1, group * tq, state_lanes),
                    lambda g, i, part, at: (g, 0, 0),
                ),
                pl.BlockSpec((1, held * tk, d_key), held_part),
                pl.BlockSpec((1, held * tk, d_value), held_part),
            ],
            out_specs=pl.BlockSpec(
                (1, group, tq, d_value), lambda g, i, part, at: (g, 0, i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((group * tq, state_lanes), jnp.float32),  # running max
                pltpu.VMEM((group * tq, state_lanes), jnp.float32),  # denominator
                pltpu.VMEM((group * tq, d_value), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((kv_heads, group, rows, d_value), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )(jnp.reshape(q_at0, (1,)).astype(jnp.int32), qg, sink_rows, kg, vg)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(rows, heads, d_value)


def sink_chunk_attention(
    q, k, v, q_at0, sink=None, *, window: int | None,
    interpret: bool | None = None,
):
    """One slot's chunk against its gathered chain.  ``q`` (rows, heads,
    d_key), query ``r`` at index ``q_at0 + r`` (traced) of the chain's keys
    ``k`` (keys, kv_heads, d_key) / ``v`` (keys, kv_heads, d_value), its own
    key included; ``window`` the layer's (None: causal from the chain's
    first key); ``sink`` (heads,) float32 or None.  Rows of padding are
    computed like any other (what they see is finite and not read).
    Returns (rows, heads, d_value)."""
    if interpret is None:
        from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

        interpret = interpret_mode()
    return _chunk_impl(
        q, k, v, q_at0, sink, window=window, interpret=interpret,
        name="sink_chunk_attention_" + ("full" if window is None else "window"),
    )


def sink_chunk_path(rows: int, keys: int, window) -> str:
    """``"sink_chunk"`` on the TPU where the kernel's tiles are whole (query
    rows a multiple of 16, keys of 128), else ``"xla"`` (materialized
    scores; always on the CPU)."""
    tq, tk = chunk_tiles(rows, keys, window)
    if jax.default_backend() == "tpu" and tq % 16 == 0 and tk % 128 == 0:
        return "sink_chunk"
    return "xla"
