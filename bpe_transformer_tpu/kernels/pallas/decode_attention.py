"""Single-query (decode-step) attention against the KV cache as a Pallas
kernel.

The decode hot loop attends ONE new token per sequence against the whole
cached context (`models/decode.py:decode_step`) — a capability the
reference never implements (its contract stops at training logits,
`/root/reference/tests/adapters.py:282-361`).  Per token the XLA path
materializes a (B, KV, G, 1, ctx) score tensor, runs a separate f32
softmax pass, then a second contraction — three HBM round trips over
score-sized intermediates for what is fundamentally a bandwidth-bound
streaming reduction over the cache.  This kernel is the flash-decoding
formulation: the cache is streamed block-by-block through VMEM exactly
once, scores never leave VMEM, and the online-softmax accumulator
(`kernels/pallas/flash_attention.py`'s, specialized to a single query
position) produces the normalized output in the same pass.

Shapes (GQA-native — queries arrive grouped per KV head so the kernel
reads the COMPACT cache, preserving decode's GQA bandwidth win):

* ``q``        (batch, num_heads, d_head)     — the one new token's queries,
                                                RoPE already applied
* ``k_cache``  (batch, kv_heads, ctx, d_head) — written positions <= pos
* ``v_cache``  (batch, kv_heads, ctx, d_head)
* ``pos``      scalar int32 (traced)          — attend to cache[0..pos];
               or (batch,) for per-sequence frontiers (serving slot pool)
* returns      (batch, num_heads, d_head)

Grid ``(batch*kv_heads, ctx/block_k)``, key axis innermost; ``pos`` rides
scalar prefetch (SMEM) so the causal frontier is a traced value — the
generation loop's ``lax.scan`` carries it — while the program stays a
single compilation.  Key blocks entirely beyond ``pos`` are predicated
off AND their K/V index maps clamp to the frontier block, so the dead
tail of the cache is neither computed on nor fetched (the pipeline elides
the repeated-block DMAs) — early decode steps stream only the live
prefix.

The kernel is forward-only by design: decoding is inference.  Training
gradients flow through the training attention paths (flash/ring), never
through this one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.ops.core import MASK_VALUE as NEG_INF

LANES = 128
SUBLANES = 8


def _decode_kernel(
    pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, block_k: int, num_k_blocks: int, kv_heads: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # One frontier per batch row (grid axis 0 walks batch-major over
    # batch*kv_heads): a scalar pos is pre-broadcast to (batch,) by the
    # caller, so the per-sequence ragged case costs nothing extra.
    pos = pos_ref[pl.program_id(0) // kv_heads]
    # Blocks whose first key index is beyond the causal frontier contribute
    # nothing (pos >= 0 always leaves block 0 live, so l > 0 at finalize).
    @pl.when(j * block_k <= pos)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale  # (G_pad, d)
        k = k_ref[0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G_pad, block_k)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
        s = jnp.where(cols <= pos, s, NEG_INF)

        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        # eps guards the division only; padded (zero) query rows score 0
        # everywhere visible and emit a harmless uniform average of v —
        # the caller's out[:, :group] slice discards them.
        denom = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@jax.named_scope("decode_attn")
def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array | int,
    *,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """One decode step of attention: ``softmax(q k^T / sqrt(d)) v`` over
    cache positions ``<= pos``, streamed blockwise (see module docstring).

    ``interpret=None`` resolves via ``runtime.interpret_mode()`` (compiled
    Mosaic on TPU, interpreter elsewhere), like the sibling kernels.
    """
    if interpret is None:
        from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

        interpret = interpret_mode()
    batch, num_heads, d = q.shape
    b2, kv_heads, ctx, d2 = k_cache.shape
    if (b2, d2) != (batch, d) or v_cache.shape != k_cache.shape:
        raise ValueError(
            f"shape mismatch: q {q.shape}, k_cache {k_cache.shape}, "
            f"v_cache {v_cache.shape}"
        )
    if num_heads % kv_heads:
        raise ValueError(
            f"num_heads={num_heads} not divisible by kv_heads={kv_heads}"
        )
    group = num_heads // kv_heads
    # Shrink block_k (sublane-aligned) to a divisor of ctx when one exists,
    # so the no-copy fast path below covers every aligned context — e.g.
    # ctx=384 runs at block 128 instead of padding to 512.  Contexts with
    # no multiple-of-8 divisor (ragged test shapes) take the explicit
    # padded fallback with a sublane-aligned block.
    bk = min(block_k, ctx) - (min(block_k, ctx) % SUBLANES)
    while bk >= SUBLANES and ctx % bk:
        bk -= SUBLANES
    if bk >= SUBLANES:
        block_k = bk
    else:
        block_k = min(block_k, pl.cdiv(ctx, SUBLANES) * SUBLANES)

    # The CACHE is never copied: its head dim passes through the BlockSpec
    # at the true width (XLA's TPU layout already lane-pads the minor dim
    # physically, so block reads at d < 128 move the same tiles) and the
    # context axis is blocked in place.  A per-step jnp.pad of the whole
    # cache would materialize a padded HBM copy of every layer's cache on
    # every generated token — timing the copy, not the kernel (review r5).
    # Only the per-step operands are padded: the one-token query tile
    # (rows to the sublane width — padded G rows normalize against the eps
    # denominator and are sliced off) and, for ragged standalone contexts
    # only, the cache's trailing partial block (decode.py caches are always
    # context_length, a multiple of any shipped block_k).
    g_pad = pl.cdiv(group, SUBLANES) * SUBLANES
    ctx_pad = pl.cdiv(ctx, block_k) * block_k
    nk = ctx_pad // block_k
    bkv = batch * kv_heads

    qg = q.reshape(batch, kv_heads, group, d).reshape(bkv, group, d)
    qg = jnp.pad(qg, ((0, 0), (0, g_pad - group), (0, 0)))
    prep = lambda c: (
        c.reshape(bkv, ctx, d)
        if ctx_pad == ctx
        else jnp.pad(c.reshape(bkv, ctx, d), ((0, 0), (0, ctx_pad - ctx), (0, 0)))
    )
    kp, vp = prep(k_cache), prep(v_cache)
    # Scalar and per-batch frontiers share one program: broadcast to
    # (batch,) so the prefetch array's shape never varies.
    pos_arr = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (batch,)
    )

    kernel = functools.partial(
        _decode_kernel,
        scale=1.0 / (d**0.5),  # true head dim, not the lane-padded one
        block_k=block_k,
        num_k_blocks=nk,
        kv_heads=kv_heads,
    )
    # Scalar-prefetch index maps receive the scalar ref as a trailing arg.
    # The K/V index CLAMPS to the causal frontier's block: grid steps beyond
    # ``pos`` are compute-predicated off in the kernel, and re-requesting
    # the frontier block instead of a dead one lets the pipeline elide the
    # DMA (same block index -> no refetch) — early decode steps would
    # otherwise stream the entire dead tail of the cache every token.
    qspec = pl.BlockSpec(
        (1, g_pad, d), lambda b, j, p: (b, 0, 0), memory_space=pltpu.VMEM
    )
    kvspec = pl.BlockSpec(
        (1, block_k, d),
        lambda b, j, p: (b, jnp.minimum(j, p[b // kv_heads] // block_k), 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bkv, nk),
        in_specs=[qspec, kvspec, kvspec],
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((g_pad, d), jnp.float32),      # output accumulator
            pltpu.VMEM((g_pad, LANES), jnp.float32),  # running row max
            pltpu.VMEM((g_pad, LANES), jnp.float32),  # running denominator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bkv, g_pad, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(pos_arr, qg, kp, vp)
    return out[:, :group, :].reshape(batch, num_heads, d)


def _paged_decode_kernel(
    tables_ref, pos_ref, q_ref, k_ref, v_ref, *refs,
    scale: float, block_size: int, num_blocks_per_slot: int, kv_heads: int,
    d_head: int, quantized: bool,
):
    """Block-table flash decode: grid axis 1 walks a slot's KV BLOCKS (the
    block table was already consumed by the BlockSpec index maps, so
    ``k_ref``/``v_ref`` hold one pool block each: ``block_size`` rows, every
    kv head's ``d_head`` lanes side by side) with the same online softmax as
    :func:`_decode_kernel`, one kv head after the other."""
    if quantized:
        kscale_ref, vscale_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    slot = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    pos = pos_ref[slot]

    @pl.when(j * block_size <= pos)
    def _block():
        k_rows = k_ref[0].astype(jnp.float32)  # (block_size, kv * d)
        v_rows = v_ref[0].astype(jnp.float32)
        if quantized:
            # Per-block-per-head dequant IN REGISTERS.  The scale tile is
            # the 8-row group of the (num_blocks, kv_heads) f32 pool that
            # holds this block (a 1-row tile is not a legal TPU block);
            # the block's row and a head's column are selected by mask
            # (dynamic sublane/lane indexing is not a TPU vector
            # primitive).  Rows of a ragged last group are never selected.
            blk = tables_ref[slot, jnp.minimum(j, pos // block_size)]
            shape = (SUBLANES, kv_heads)
            in_row = (
                jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                == blk % SUBLANES
            )
            head_col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        for head in range(kv_heads):
            lanes = slice(head * d_head, (head + 1) * d_head)
            q = q_ref[0, head].astype(jnp.float32) * scale  # (G_pad, d)
            k = k_rows[:, lanes]                            # (block_size, d)
            v = v_rows[:, lanes]
            if quantized:
                pick = in_row & (head_col == head)
                k = k * jnp.sum(jnp.where(pick, kscale_ref[...], 0.0))
                v = v * jnp.sum(jnp.where(pick, vscale_ref[...], 0.0))
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (G_pad, block_size)
            cols = (
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                + j * block_size
            )
            s = jnp.where(cols <= pos, s, NEG_INF)

            m_prev = m_ref[head, :, 0:1]
            l_prev = l_ref[head, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[head] = acc_ref[head] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[head] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[head] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == num_blocks_per_slot - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@jax.named_scope("decode_attn")
def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    tables: jax.Array,
    pos: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged-NATIVE flash decode: one decode step of attention read straight
    out of the KV block pool — no contiguous per-slot gather ever exists.

    The serving block pool (`models/decode.init_kv_pool`) stores KV as
    ``(num_blocks, block_size, kv_heads * d_head)`` — block-major rows, a
    row's heads side by side along the lanes; each slot's cache is a chain
    of block ids in ``tables`` ``(slots, blocks_per_slot)``.  Where
    `gather_paged_rows` materializes a ``(slots, blocks_per_slot*block_size)``
    transient per layer per tick before any kernel runs, here the grid is
    ``(slots, blocks_per_slot)`` and the BLOCK TABLE IS CONSUMED INSIDE THE
    K/V BlockSpec INDEX MAPS: ``tables``/``pos`` ride scalar prefetch
    (SMEM), so grid step ``(s, j)`` DMAs pool block ``tables[s, min(j,
    pos[s] // block_size)]`` — every kv head's rows, one contiguous
    ``block_size * kv_heads * d_head`` stretch — directly into VMEM, and the
    kernel walks the heads.  HBM traffic per tick drops to one streaming
    read of the LIVE blocks — the gather's extra write+read round trip of
    the whole transient is gone, and (as in :func:`decode_attention`)
    blocks beyond the causal frontier clamp to the frontier block so their
    DMAs are elided.

    ``k_scale``/``v_scale`` ``(num_blocks, kv_heads)`` f32 must be given
    exactly when the pool is int8-quantized (per-block-per-head scales, the
    serving pool's ``kv_dtype="int8"`` layout); the kernel dequantizes each
    block in registers, so the HBM side of the stream stays 1 byte/value.
    What the v5e compiler refuses is a 1-row scale tile, so the scales ride
    as 8-row groups (see the kernel); the pool shapes it accepts are
    compiled in ``tests/test_chip_compile.py``.

    ``pos`` is the per-slot causal frontier ``(slots,)`` (scalar broadcast
    accepted).  Returns ``(slots, num_heads, d_head)`` like
    :func:`decode_attention`.
    """
    if interpret is None:
        from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

        interpret = interpret_mode()
    slots, num_heads, d = q.shape
    if k_pool.ndim != 3 or v_pool.shape != k_pool.shape or k_pool.shape[2] % d:
        raise ValueError(
            f"shape mismatch: q {q.shape}, k_pool {k_pool.shape}, "
            f"v_pool {v_pool.shape} (pools are (num_blocks, block_size, "
            "kv_heads * d_head))"
        )
    num_blocks, block_size, width = k_pool.shape
    kv_heads = width // d
    if tables.ndim != 2 or tables.shape[0] != slots:
        raise ValueError(
            f"tables {tables.shape} must be (slots={slots}, blocks_per_slot)"
        )
    if num_heads % kv_heads:
        raise ValueError(
            f"num_heads={num_heads} not divisible by kv_heads={kv_heads}"
        )
    quantized = k_scale is not None
    if quantized != (v_scale is not None) or (
        quantized != (k_pool.dtype == jnp.int8)
    ):
        raise ValueError(
            "k_scale/v_scale must both be given exactly for int8 pools"
        )
    if quantized and k_scale.shape != (num_blocks, kv_heads):
        raise ValueError(
            f"k_scale {k_scale.shape} must be (num_blocks={num_blocks}, "
            f"kv_heads={kv_heads})"
        )
    group = num_heads // kv_heads
    g_pad = pl.cdiv(group, SUBLANES) * SUBLANES
    nbs = tables.shape[1]

    qg = q.reshape(slots, kv_heads, group, d)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (slots,))
    tables = jnp.asarray(tables, jnp.int32)

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=1.0 / (d**0.5),
        block_size=block_size,
        num_blocks_per_slot=nbs,
        kv_heads=kv_heads,
        d_head=d,
        quantized=quantized,
    )
    # Index maps receive the scalar-prefetch refs as trailing args: the
    # block-table lookup happens HERE, steering each grid step's DMA to
    # its pool block.  Steps beyond the frontier clamp to the frontier
    # block (same id -> the pipeline elides the refetch) and are
    # compute-predicated off in the kernel, exactly like the dense kernel.
    qspec = pl.BlockSpec(
        (1, kv_heads, g_pad, d), lambda s, j, t, p: (s, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )

    def block_id(s, j, t, p):
        return t[s, jnp.minimum(j, p[s] // block_size)]

    kvspec = pl.BlockSpec(
        (1, block_size, width),
        lambda s, j, t, p: (block_id(s, j, t, p), 0, 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [qspec, kvspec, kvspec]
    inputs = [qg, k_pool, v_pool]
    if quantized:
        # A (1, kv_heads) row is not a legal TPU block (sublane dim must
        # be a multiple of 8 or the whole axis): DMA the 8-row group that
        # holds the block's row; the kernel selects the row by mask.
        sspec = pl.BlockSpec(
            (SUBLANES, kv_heads),
            lambda s, j, t, p: (block_id(s, j, t, p) // SUBLANES, 0),
            memory_space=pltpu.VMEM,
        )
        in_specs += [sspec, sspec]
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, nbs),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((kv_heads, g_pad, d), jnp.float32),      # accumulator
            pltpu.VMEM((kv_heads, g_pad, LANES), jnp.float32),  # running max
            pltpu.VMEM((kv_heads, g_pad, LANES), jnp.float32),  # denominator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, kv_heads, g_pad, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables, pos_arr, *inputs)
    return out[:, :, :group, :].reshape(slots, num_heads, d)


@jax.named_scope("decode_attn")
def xla_rows_attention(q, k_rows, v_rows, visible):
    """Materialized-scores attention over KV kept AS THE POOL HOLDS IT:
    ``k_rows``/``v_rows`` ``(batch, keys, kv_heads * d_head)``, a key's
    heads side by side along the lanes (`models/decode.gather_paged_rows`).
    ``q`` is ``(batch, heads, queries, d_head)``, ``visible`` ``(batch,
    queries, keys)``; returns ``(batch, heads, queries, d_head)``.

    The same mathematics as :func:`xla_decode_attention` (f32 scores and
    softmax, probabilities back at the query's width), arranged so that
    the rows are never split into heads: on the TPU a ``(..., 768)`` array
    and its ``(..., 12, 64)`` view are different tilings, and at serving
    batch the gathered rows are as large as the pool, so splitting them
    costs a pool-sized copy per K and V per layer.  Instead the QUERIES
    are laid out block-diagonally - column ``(head, query)`` of ``q_cols``
    holds that query in the lanes of its kv head and zeros elsewhere - so
    ``k_rows @ q_cols`` is every head's scores in one batched matmul over
    the rows' own layout (the zeros add nothing), and ``probs @ v_rows``
    gives every head's output in every kv head's lanes, of which each head
    keeps its own.  The wasted products (``kv_heads`` times the scores'
    and the outputs') are activation-sized; the rows are read once each.
    """
    batch, heads, queries, d = q.shape
    keys, width = k_rows.shape[1:]
    kv_heads = width // d
    # owner[h, k]: query head h reads kv head k (GQA groups are contiguous).
    owner = (
        jnp.arange(heads)[:, None] // (heads // kv_heads)
        == jnp.arange(kv_heads)[None, :]
    ).astype(q.dtype)
    q_cols = jnp.einsum("bhqd,hk->bkdhq", q, owner).reshape(
        batch, width, heads * queries
    )
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    scores = jnp.einsum("bcj,bjn->bnc", k_rows, q_cols) * scale
    scores = scores.reshape(batch, heads, queries, keys)
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum(
        "bnc,bcj->bnj", probs.reshape(batch, heads * queries, keys), v_rows
    ).reshape(batch, heads, queries, kv_heads, d)
    return jnp.einsum("bhqkd,hk->bhqd", out, owner)


@jax.named_scope("decode_attn")
def xla_decode_attention(q, k_cache, v_cache, pos, window: int | None = None):
    """Materialized-scores formulation: the grouped einsum straight against
    the compact GQA cache (the per-token hot path reads only
    ``kv_heads * ctx`` values — no head expansion), f32 scores + softmax.
    This IS `models/decode.py:decode_step`'s xla attention (that path calls
    here — single implementation) and the kernel's parity oracle.
    ``window`` keeps only the keys ``pos - window < j <= pos`` (a
    sliding-window layer reading a full dense cache).
    """
    batch, num_heads, d = q.shape
    kv_heads, ctx = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(batch, kv_heads, num_heads // kv_heads, 1, d)
    # f32 scale promotes the scores out of bf16 before masking/softmax,
    # matching the kernel's f32 score accumulation.
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    scores = jnp.einsum("bkgqd,bkcd->bkgqc", qg, k_cache) * scale
    # pos is a scalar (whole batch at one depth) or (batch,) — per-sequence
    # causal frontiers for the serving engine's ragged slot pool.
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        visible = (jnp.arange(ctx) <= pos)[None, None, None, None, :]
    else:
        visible = (jnp.arange(ctx)[None, :] <= pos[:, None])[
            :, None, None, None, :
        ]
    if window is not None:
        behind = jnp.reshape(pos, (-1, 1)) - jnp.arange(ctx)[None, :] < window
        visible = visible & behind[:, None, None, None, :]
    scores = jnp.where(visible, scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    att = jnp.einsum("bkgqc,bkcd->bkgqd", probs, v_cache)
    return att.reshape(batch, num_heads, d)
