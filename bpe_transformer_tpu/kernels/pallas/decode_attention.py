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
    scale: float | None = None,
) -> jax.Array:
    """One decode step of attention: ``softmax(q k^T / sqrt(d)) v`` over
    cache positions ``<= pos``, streamed blockwise (see module docstring).
    ``scale`` (here and in every function below) replaces ``1 / sqrt(d)``
    where a config multiplies its scores by something else.

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
        # true head dim, not the lane-padded one
        scale=1.0 / (d**0.5) if scale is None else scale,
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


def _head_owner(heads: int, kv_heads: int, rows: int | None = None):
    """``owner[h, k]``: query head ``h`` reads kv head ``k`` (GQA groups are
    contiguous); bool ``(rows or heads, kv_heads)``, rows past ``heads``
    (padding) own nothing."""
    h = jnp.arange(rows or heads)[:, None]
    return (h // (heads // kv_heads) == jnp.arange(kv_heads)[None, :]) & (
        h < heads
    )


def _paged_decode_kernel(
    tables_ref, counts_ref, live_from_ref, q_ref, pick_ref, k_hbm, v_hbm,
    *refs, scale: float, block_size: int, group_blocks: int, slots: int,
    heads_per_kv: int, quantized: bool,
):
    """One slot a grid step; inside it a loop over the slot's live *groups*
    of ``group_blocks`` pool blocks (trip count from the slot's key count).
    ``k_hbm``/``v_hbm`` are the whole pools, left in HBM: each live block of
    a group is copied through the block table into one of two VMEM buffers
    while the other buffer's group is computed on, and the last group of a
    slot starts the copies of the next live slot's first group, so the
    stream never waits on a slot's edge.

    A group is computed as it lies, heads side by side: ``q_ref`` holds the
    slot's queries block-diagonally (row ``h`` is head ``h`` in the lanes of
    its kv head, zeros elsewhere), so one ``(heads, width) x (keys, width)``
    contraction gives every head's scores and one ``(heads, keys) x (keys,
    width)`` product every head's output in every kv head's lanes;
    ``pick_ref`` keeps each head's own lanes at the end.  An int8 pool's
    per-block-per-head scales multiply the scores and the probabilities
    (row ``h`` only ever keeps lanes of one kv head, so the scale of that
    head's block is a factor of the whole row entry), which is the
    dequantization, done in registers on ``(heads, keys)`` values."""
    if quantized:
        ks_ref, vs_ref, spread_ref, o_ref, k_buf, v_buf, sems, turn = refs
    else:
        o_ref, k_buf, v_buf, sems, turn = refs
    slot = pl.program_id(0)
    group_keys = group_blocks * block_size

    def copies(s, group, buf, go):
        """Start (``go``) or await the copies of group ``group`` of slot
        ``s`` into buffer ``buf``: its live blocks and no others."""
        first = group * group_blocks
        live = jnp.minimum(
            pl.cdiv(counts_ref[s], block_size) - first, group_blocks
        )

        def one(i, carry):
            block = tables_ref[s, first + i] if go else 0
            rows = pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
            for hbm, vmem, sem in (
                (k_hbm, k_buf, sems.at[buf, 0]), (v_hbm, v_buf, sems.at[buf, 1])
            ):
                copy = pltpu.make_async_copy(
                    hbm.at[block], vmem.at[buf, rows], sem
                )
                copy.start() if go else copy.wait()
            return carry

        jax.lax.fori_loop(0, live, one, 0)

    @pl.when(slot == 0)
    def _open():
        if not quantized:
            # Rows no copy has reached are multiplied by a probability of
            # exactly zero: they must hold numbers.
            k_buf[...] = jnp.zeros_like(k_buf)
            v_buf[...] = jnp.zeros_like(v_buf)
        turn[0] = 0

        @pl.when(live_from_ref[0] < slots)
        def _():
            copies(live_from_ref[0], 0, 0, True)

    count = counts_ref[slot]
    groups = pl.cdiv(count, group_keys)
    q = q_ref[0]                                    # (heads_pad, width)
    heads_pad, width = q.shape

    def per_key(scales_ref, g):
        """A group's block scales ``(heads_pad, group_blocks)`` spread over
        the keys of their blocks, exactly (a 0/1 matrix at full precision)."""
        return jax.lax.dot_general(
            scales_ref[0, g], spread_ref[...], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    def group_step(g, carry):
        m_prev, l_prev, acc = carry
        buf = turn[0]
        last = g + 1 == groups
        nxt_slot = jnp.where(last, live_from_ref[slot + 1], slot)

        @pl.when(nxt_slot < slots)
        def _():
            copies(nxt_slot, jnp.where(last, 0, g + 1), 1 - buf, True)

        copies(slot, g, buf, False)
        k = k_buf[buf]                              # (group_keys, width)
        v = v_buf[buf]
        if quantized:
            k = k.astype(jnp.float32).astype(q.dtype)
            v = v.astype(jnp.float32).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                   # (heads_pad, group_keys)
        if quantized:
            s = s * per_key(ks_ref, g)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + g * group_keys
        s = jnp.where(cols < count, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * per_key(vs_ref, g)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        turn[0] = 1 - buf
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(
        0, groups, group_step,
        (
            jnp.full((heads_pad, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads_pad, 1), jnp.float32),
            jnp.zeros((heads_pad, width), jnp.float32),
        ),
    )
    # A slot with no keys walks no group: zeros over the guard, finite.
    out = acc / jnp.maximum(l, 1e-30)
    for j in range(heads_per_kv):
        o_ref[0, j:j + 1, :] = jnp.sum(
            out * pick_ref[j], axis=0, keepdims=True
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def _paged_decode_impl(
    q, k_pool, v_pool, tables, key_counts, k_scale, v_scale, interpret,
    scale=None,
):
    from bpe_transformer_tpu.kernels.pallas.runtime import paged_group_blocks

    slots, num_heads, d = q.shape
    _, block_size, width = k_pool.shape
    kv_heads = width // d
    per_kv = num_heads // kv_heads
    nbs = tables.shape[1]
    quantized = k_scale is not None
    group = min(
        paged_group_blocks(block_size, width, k_pool.dtype.itemsize), nbs
    )
    group_keys = group * block_size
    heads_pad = pl.cdiv(num_heads, 2 * SUBLANES) * 2 * SUBLANES

    head_kv = jnp.arange(heads_pad) // per_kv
    owner = _head_owner(num_heads, kv_heads, heads_pad)
    q_rows = jnp.einsum(
        "shd,hk->shkd",
        jnp.pad(q, ((0, 0), (0, heads_pad - num_heads), (0, 0))),
        owner.astype(q.dtype),
    ).reshape(slots, heads_pad, width)
    # pick[j, h, lane]: head h is the j-th of its kv head, and the lane is
    # that kv head's.
    own_lanes = jnp.repeat(owner, d, axis=1)
    pick = (
        own_lanes[None]
        & (jnp.arange(heads_pad) % per_kv == jnp.arange(per_kv)[:, None])[
            :, :, None
        ]
    ).astype(jnp.float32)

    counts = jnp.broadcast_to(
        jnp.asarray(key_counts, jnp.int32).reshape(-1), (slots,)
    )
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

    in_specs = [
        at_slot(heads_pad, width), whole(per_kv, heads_pad, width),
        pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [q_rows, pick, k_pool, v_pool]
    if quantized:
        # The scale of head h's kv head in each block of a slot's chain,
        # a group of blocks a row: (slots, groups, heads_pad, group).
        groups = pl.cdiv(nbs, group)

        def by_group(scale):
            rows = jnp.swapaxes(scale[tables][:, :, head_kv], 1, 2)
            rows = jnp.pad(rows, ((0, 0), (0, 0), (0, groups * group - nbs)))
            return jnp.swapaxes(
                rows.reshape(slots, heads_pad, groups, group), 1, 2
            )

        # spread[i, key]: key lies in block i of its group.
        spread = (
            jnp.arange(group)[:, None] == jnp.arange(group_keys)[None, :] // block_size
        ).astype(jnp.float32)
        in_specs += [
            at_slot(groups, heads_pad, group), at_slot(groups, heads_pad, group),
            whole(group, group_keys),
        ]
        inputs += [by_group(k_scale), by_group(v_scale), spread]

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=1.0 / (d**0.5) if scale is None else scale,
        block_size=block_size,
        group_blocks=group,
        slots=slots,
        heads_per_kv=per_kv,
        quantized=quantized,
    )
    buffers = pltpu.VMEM((2, group_keys, width), k_pool.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=in_specs,
            out_specs=at_slot(per_kv, width),
            scratch_shapes=[
                buffers, buffers,
                pltpu.SemaphoreType.DMA((2, 2)),   # [buffer, K | V]
                pltpu.SMEM((1,), jnp.int32),       # the buffer in turn
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, per_kv, width), jnp.float32),
        # The buffers and the turn are carried from slot to slot.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables, counts, live_from, *inputs)
    # out[s, j, k * d + i] is element i of head k * per_kv + j.
    out = jnp.swapaxes(out.reshape(slots, per_kv, kv_heads, d), 1, 2)
    return out.reshape(slots, num_heads, d).astype(q.dtype)


@jax.named_scope("decode_attn")
def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    tables: jax.Array,
    key_counts: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Paged-NATIVE flash decode: one decode step of attention read straight
    out of the KV block pool, and only the blocks the slots hold.

    The serving block pool (`models/decode.init_kv_pool`) stores KV as
    ``(num_blocks, block_size, kv_heads * d_head)`` - block-major rows, a
    row's heads side by side along the lanes; each slot's cache is a chain
    of block ids in ``tables`` ``(slots, blocks_per_slot)``.  Where
    `gather_paged_rows` materializes ``(slots, blocks_per_slot * block_size)``
    rows a layer a tick whatever the slots hold, here the pools stay in HBM
    and the kernel (:func:`_paged_decode_kernel`) walks each slot's chain a
    *group* of blocks at a time - `runtime.paged_group_blocks` of them,
    256 keys' worth where the buffers allow - copying a group's live blocks
    through the table into VMEM while the group before it is computed on.
    ``key_counts`` ``(slots,)`` (a scalar is broadcast) says how many keys
    of its chain a slot attends to, ``position + 1`` for the token just
    written; work and HBM traffic follow it: a slot at 0 (idle) copies
    nothing, walks no group and yields zeros.  ``tables`` and the counts
    ride scalar prefetch, so one compiled program serves every state.

    ``k_scale``/``v_scale`` ``(num_blocks, kv_heads)`` f32 must be given
    exactly when the pool is int8-quantized (per-block-per-head scales, the
    serving pool's ``kv_dtype="int8"`` layout); the HBM side of the stream
    stays 1 byte a value, the scales are gathered through the table
    (activation-sized) and applied inside the kernel.  The pool shapes the
    v5e compiler accepts are compiled in ``tests/test_chip_compile.py``.

    Returns ``(slots, num_heads, d_head)`` like :func:`decode_attention`.
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
    num_blocks, _, width = k_pool.shape
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
    return _paged_decode_impl(
        q, k_pool, v_pool, tables, key_counts, k_scale, v_scale, interpret,
        scale,
    )


@jax.named_scope("decode_attn")
def xla_rows_attention(q, k_rows, v_rows, visible, scale: float | None = None):
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
    owner = _head_owner(heads, kv_heads).astype(q.dtype)
    q_cols = jnp.einsum("bhqd,hk->bkdhq", q, owner).reshape(
        batch, width, heads * queries
    )
    scale = (
        1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32)) if scale is None
        else jnp.float32(scale)
    )
    scores = jnp.einsum("bcj,bjn->bnc", k_rows, q_cols) * scale
    scores = scores.reshape(batch, heads, queries, keys)
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum(
        "bnc,bcj->bnj", probs.reshape(batch, heads * queries, keys), v_rows
    ).reshape(batch, heads, queries, kv_heads, d)
    return jnp.einsum("bhqkd,hk->bhqd", out, owner)


@jax.named_scope("decode_attn")
def xla_decode_attention(
    q, k_cache, v_cache, pos, window: int | None = None,
    scale: float | None = None,
):
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
    scale = (
        1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32)) if scale is None
        else jnp.float32(scale)
    )
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
