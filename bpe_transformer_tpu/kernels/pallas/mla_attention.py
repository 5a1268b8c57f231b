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
* **expanded**: keys and values of every head are expanded from the latent
  rows and attended as plain heads.  A (query, key) pair costs ``2 x (192 +
  128)`` FLOPs a head so against ``2 x (576 + 512)`` absorbed; it is the
  reference's form (``chipbench/reference_longcatflash.py``) and nowhere in
  the program.

On the TPU the tick's form is a Pallas kernel that walks each slot's live
blocks through the block table straight out of the pool, as
`decode_attention.paged_decode_attention` does for K/V heads (device events
``mla_paged_attention.N``); elsewhere it is a gather and a masked softmax
in XLA.  Many query rows of one sequence (:func:`xla_mla_chunk_attention`:
a chunk that resumes after a cached prefix, a dense cache's prefill, the
plain forward) run in XLA on every backend, a loop over key blocks whose
trip count follows the last position, absorbed too: on the v5e 15.9 ms
against 18.3-21.3 expanded for 1,024 rows after 8,192 positions, for all
its 2.4 times the FLOPs - 64 heads' keys of 192 make small matrix
products, one shared row of 576 a large one (PERF.md section 6, PR 33).
"""

from __future__ import annotations

import functools

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
#: Keys a step of the chunk's loop scores and folds into its softmax.
MLA_CHUNK_KEY_BLOCK = 1024


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


# --------------------------------------------------------- absorbed, kernel


def _mla_paged_kernel(
    tables_ref, counts_ref, live_from_ref, q_ref, pool_hbm, o_ref, buf, sems,
    turn, *, scale: float, block_size: int, group_blocks: int, slots: int,
    rank: int,
):
    """One slot a grid step; inside it a loop over the slot's live groups of
    ``group_blocks`` pool blocks (trip count from the slot's key count).
    The pool stays in HBM: each live block of a group is copied through the
    block table into one of two VMEM buffers while the other buffer's group
    is computed on, and a slot's last group starts the copies of the next
    live slot's first.  A group is read once: ``(heads, width) x (keys,
    width)`` gives every head's scores, and the same buffer's first
    ``rank`` lanes are the values of ``(heads, keys) x (keys, rank)``."""
    slot = pl.program_id(0)
    group_keys = group_blocks * block_size

    def copies(s, group, b, go):
        """Start (``go``) or await the copies of group ``group`` of slot
        ``s`` into buffer ``b``: its live blocks and no others."""
        first = group * group_blocks
        live = jnp.minimum(
            pl.cdiv(counts_ref[s], block_size) - first, group_blocks
        )

        def one(i, carry):
            block = tables_ref[s, first + i] if go else 0
            rows = pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
            copy = pltpu.make_async_copy(
                pool_hbm.at[block], buf.at[b, rows], sems.at[b]
            )
            copy.start() if go else copy.wait()
            return carry

        jax.lax.fori_loop(0, live, one, 0)

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
    heads_pad = q.shape[0]

    def group_step(g, carry):
        m_prev, l_prev, acc = carry
        b = turn[0]
        last = g + 1 == groups
        nxt_slot = jnp.where(last, live_from_ref[slot + 1], slot)

        @pl.when(nxt_slot < slots)
        def _():
            copies(nxt_slot, jnp.where(last, 0, g + 1), 1 - b, True)

        copies(slot, g, b, False)
        kv = buf[b]                                 # (group_keys, width)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (heads_pad, group_keys)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + g * group_keys
        s = jnp.where(cols < count, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        turn[0] = 1 - b
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(
        0, groups, group_step,
        (
            jnp.full((heads_pad, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads_pad, 1), jnp.float32),
            jnp.zeros((heads_pad, rank), jnp.float32),
        ),
    )
    # A slot with no keys walks no group: zeros over the guard, finite.
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def _mla_paged_impl(q_abs, pool, tables, key_counts, rank, scale, interpret):
    slots, num_heads, width = q_abs.shape
    _, block_size, _ = pool.shape
    nbs = tables.shape[1]
    group = max(1, min(MLA_GROUP_KEYS // block_size, nbs))
    # Whole sublane tiles at the rows' width (16 rows of bfloat16).
    heads_pad = pl.cdiv(num_heads, 16) * 16
    q_rows = jnp.pad(q_abs, ((0, 0), (0, heads_pad - num_heads), (0, 0)))
    counts = jnp.broadcast_to(
        jnp.asarray(key_counts, jnp.int32).reshape(-1), (slots,)
    )
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

    out = pl.pallas_call(
        functools.partial(
            _mla_paged_kernel, scale=scale, block_size=block_size,
            group_blocks=group, slots=slots, rank=rank,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[
                at_slot(heads_pad, width), pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=at_slot(heads_pad, rank),
            scratch_shapes=[
                pltpu.VMEM((2, group * block_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),       # the buffer in turn
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, heads_pad, rank), jnp.float32),
        # The buffers and the turn are carried from slot to slot.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="mla_paged_attention",
    )(jnp.asarray(tables, jnp.int32), counts, live_from, q_rows, pool)
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
    the latent block pool, and only the blocks the slots hold.

    ``q_abs`` (slots, heads, latent width) are the absorbed queries,
    ``pool`` (num_blocks, block_size, width) one sublayer's latent rows,
    padded with zeros to whole lane tiles (`models/decode.init_latent_pool`;
    the queries are padded alike here, so the padding adds nothing to a
    score), ``tables`` (slots, blocks_per_slot) each slot's chain of block
    ids, ``key_counts`` (slots,) how many positions of its chain a slot
    attends to (``position + 1`` for the token just written, 0 for an idle
    slot: it copies nothing and yields zeros).  Returns (slots, heads, rank): each head's softmax-weighted sum
    of the rows' first ``rank`` values.  ``path`` forces ``"mla_paged"``
    (parity tests: interpret mode off the TPU) or ``"xla"``."""
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
