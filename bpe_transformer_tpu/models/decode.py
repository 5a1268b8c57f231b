"""KV-cached autoregressive decoding.

A capability the reference never implements (its contract stops at training
logits, `/root/reference/tests/adapters.py:282-361`); built TPU-first so
sampling is O(1) per token instead of re-running the full forward:

* the cache is a static-shape pytree — per layer ``(batch, heads,
  context_length, d_head)`` K and V buffers — so prefill + every decode step
  compile once (``lax.dynamic_update_slice`` writes, no shape growth);
* prefill runs the blocks over the whole prompt at once (MXU-friendly) while
  recording K/V; each decode step projects exactly one token and attends
  against the cache under a position mask;
* the token loop is a ``lax.scan`` inside ONE jit, so generation launches a
  single XLA program regardless of ``max_new_tokens``.

Weights use the same param pytree as training — no export/conversion step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import Array, lax

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.transformer import Params, lm_head_weight
from bpe_transformer_tpu.ops.core import (
    embedding,
    head_logits,
    layernorm,
    linear,
    merge_heads,
    rmsnorm,
    split_heads,
)
from bpe_transformer_tpu.ops.rope import apply_rope, rope_tables

KVCache = list  # [{"k": (B, H, ctx, dh), "v": (B, H, ctx, dh)} per layer]


def init_kv_cache(config: ModelConfig, batch: int, dtype=jnp.float32) -> KVCache:
    # GQA stores only num_kv_heads — the cache (decode's HBM footprint)
    # shrinks by the query-group factor.
    kv_heads = config.num_kv_heads or config.num_heads
    shape = (batch, kv_heads, config.context_length, config.d_head)
    return [
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for _ in range(config.num_layers)
    ]


def _rope_qk(q, k, positions, config, layer: int = 0):
    if not config.layer_rope(layer):
        return q, k
    cos, sin = rope_tables(config.d_head, config.context_length, config.rope_theta)
    # Keep the compute dtype (bf16 decode must not promote to f32 here).
    cos, sin = cos.astype(q.dtype), sin.astype(q.dtype)
    pos = jnp.expand_dims(positions, axis=-2)  # broadcast over heads
    return apply_rope(q, pos, cos, sin), apply_rope(k, pos, cos, sin)


def _ffn_decode(x, ffn, config, valid=None, tally=None):
    """The block's FFN as it is served.  A MoE layer is the dropless one
    (`models/moe.dropless_moe`): a decode step's few tokens and a prefill's
    many are routed alike and nothing is dropped, whatever capacity the
    training forward runs at.  ``valid`` leaves rows out of the expert
    computation; ``tally`` (a list) collects the layer's routing counts."""
    if config.ffn_type == "moe":
        from bpe_transformer_tpu.models.moe import dropless_moe

        out, counts = dropless_moe(x, ffn, config, valid=valid)
        if tally is not None:
            tally.append(counts)
        return out
    from bpe_transformer_tpu.models.transformer import _ffn

    return _ffn(x, ffn, config)[0]


def _block_apply(x, block_params, config, attend, valid=None, tally=None):
    """One block around a caller-supplied ``attend(h) -> attention output``.

    Mirrors `transformer_block_aux` (models/transformer.py): pre-norm by
    default, post-norm under the ablation flag, both branches from one norm
    under ``parallel_block``.
    """
    if config.parallel_block:
        h = _norm(x, block_params["ln1"], config)
        with jax.named_scope("block/attn"):
            a = attend(h)
        with jax.named_scope("block/ffn"):
            return x + a + _ffn_decode(h, block_params["ffn"], config, valid, tally)
    if config.use_post_norm:
        with jax.named_scope("block/attn"):
            x = _norm(x + attend(x), block_params["ln1"], config)
        with jax.named_scope("block/ffn"):
            f = _ffn_decode(x, block_params["ffn"], config, valid, tally)
            return _norm(x + f, block_params["ln2"], config)
    with jax.named_scope("block/attn"):
        h = _norm(x, block_params["ln1"], config)
        x = x + attend(h)
    with jax.named_scope("block/ffn"):
        h = _norm(x, block_params["ln2"], config)
        return x + _ffn_decode(h, block_params["ffn"], config, valid, tally)


def _norm(x, w, config):
    if config.remove_rmsnorm:
        return x
    return layernorm(x, w) if config.norm_type == "layernorm" else rmsnorm(x, w)


def _embed(params, token_ids):
    with jax.named_scope("embed"):
        return embedding(params["token_embeddings"], token_ids)


def _final_norm(x, params, config):
    with jax.named_scope("final_norm"):
        return _norm(x, params["ln_final"], config)


def _project_qkv(h, attn, config):
    kv_heads = config.num_kv_heads or config.num_heads
    q = split_heads(linear(h, attn["q_proj"]), config.num_heads)
    k = split_heads(linear(h, attn["k_proj"]), kv_heads)
    v = split_heads(linear(h, attn["v_proj"]), kv_heads)
    return q, k, v


def _expand_kv(x, config):
    """Broadcast cached KV heads up to the query heads (GQA no-op for MHA)."""
    kv_heads = config.num_kv_heads or config.num_heads
    if kv_heads == config.num_heads:
        return x
    return jnp.repeat(x, config.num_heads // kv_heads, axis=1)


def prefill(
    params: Params,
    token_ids: Array,
    config: ModelConfig,
    cache: KVCache,
    lm_head: Array | None = None,
    last_pos: Array | None = None,
) -> tuple[Array, KVCache]:
    """Run the prompt through the model, filling the cache.

    ``token_ids``: (batch, prompt_len).  Returns logits of the LAST prompt
    position ``(batch, vocab)`` and the filled cache.  ``lm_head`` overrides
    the head weight — generate_cached passes a weight pre-cast to the
    compute dtype once, outside the token loop (head_logits accumulates in
    f32 either way, so logits stay float32-clean).

    ``last_pos`` (batch,) selects WHICH position's logits to return per
    sequence (default: the last).  The serving engine pads ragged prompts up
    to a shared bucket length so one program serves every prompt in the
    bucket; causal masking keeps positions ``<= last_pos`` untouched by the
    padding, and the padded cache rows are overwritten by decode before any
    step can attend to them.
    """
    batch, plen = token_ids.shape
    positions = jnp.arange(plen)
    x = _embed(params, token_ids)
    # Long prompts take the flash kernel (forced by the config, or chosen
    # from the prompt's shape under "auto"): the materialized path needs an
    # O(plen^2) score buffer per layer, which is exactly the memory wall
    # the training side removes with flash attention.  RoPE is already
    # applied outside (decode owns per-position tables), so both "flash"
    # and "flash_fused" map to the plain flash kernel here.
    from bpe_transformer_tpu.kernels.pallas.flash_attention import (
        attention_plan,
        flash_attention_for_config,
    )

    # A window layer's band mask has no flash kernel: a config with window
    # layers prefills its dense cache with materialized scores throughout.
    use_flash = (
        attention_plan(config, plen)[0] == "flash"
        and not config.has_window_layers
    )
    if not use_flash:
        scale = 1.0 / jnp.sqrt(jnp.asarray(config.d_head, jnp.float32))
        causal = jnp.tril(jnp.ones((plen, plen), bool))

    new_cache = []
    for layer, (block_params, layer_cache) in enumerate(
        zip(params["layers"], cache)
    ):
        window = config.layer_window(layer)
        if not use_flash:
            mask = causal
            if window is not None:
                from bpe_transformer_tpu.ops.core import window_causal_mask

                mask = window_causal_mask(plen, window)

        def attend(
            h, block_params=block_params, layer_cache=layer_cache,
            layer=layer, mask=None if use_flash else mask,
        ):
            q, k, v = _project_qkv(h, block_params["attn"], config)
            q, k = _rope_qk(q, k, positions, config, layer)
            new_cache.append(
                {
                    "k": lax.dynamic_update_slice(layer_cache["k"], k, (0, 0, 0, 0)),
                    "v": lax.dynamic_update_slice(layer_cache["v"], v, (0, 0, 0, 0)),
                }
            )
            k, v = _expand_kv(k, config), _expand_kv(v, config)
            if use_flash:
                att = merge_heads(flash_attention_for_config(q, k, v, config))
                return linear(att, block_params["attn"]["output_proj"])
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            scores = jnp.where(mask, scores, -jnp.inf)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
                h.dtype
            )
            att = merge_heads(jnp.einsum("bhqk,bhkd->bhqd", probs, v))
            return linear(att, block_params["attn"]["output_proj"])

        x = _block_apply(x, block_params, config, attend)

    x = _final_norm(x, params, config)
    head = lm_head_weight(params, config) if lm_head is None else lm_head
    # head_logits: activation-dtype matmul, f32 accumulation — the
    # head read (decode's per-token bandwidth bottleneck alongside the
    # cache) happens at the compute width, logits stay f32-clean.
    if last_pos is None:
        last = x[:, -1]
    else:
        idx = jnp.reshape(last_pos, (-1, 1, 1))
        last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
    logits = head_logits(last, head)
    return logits, new_cache


def _cache_write(buf: Array, new: Array, pos: Array) -> Array:
    """Write ``new`` (B, H, s, dh) into ``buf`` at sequence position ``pos``
    — scalar ``pos`` writes the whole batch at one offset (the classic
    generation loop); a ``(B,)`` vector writes each sequence at its own
    position (the serving engine's slots sit at ragged depths)."""
    if jnp.ndim(pos) == 0:
        return lax.dynamic_update_slice(buf, new, (0, 0, pos, 0))
    return jax.vmap(
        lambda b, n, p: lax.dynamic_update_slice(b, n, (0, p, 0))
    )(buf, new, pos)


def decode_step(
    params: Params,
    token: Array,
    pos: Array,
    cache: KVCache,
    config: ModelConfig,
    lm_head: Array | None = None,
    active: Array | None = None,
    return_hidden: bool = False,
) -> tuple[Array, KVCache]:
    """One cached decode step.

    ``token``: (batch,) ids of the token AT position ``pos`` — a scalar
    (whole batch at one depth, the classic generation loop) or a ``(batch,)``
    vector (each sequence at its own depth, the serving engine's slot pool);
    returns logits ``(batch, vocab)`` for each token's position and the
    updated cache.  ``lm_head`` as in :func:`prefill`.

    ``active`` (batch,) bool gates the cache write per sequence: inactive
    slots keep their cache rows untouched (their logits are still computed —
    the program shape is batch-static — but the caller discards them).

    ``return_hidden=True`` skips the head projection and returns the
    final-norm hidden state ``(batch, d_model)`` instead of logits — the
    fused sample-in-kernel tick (`kernels/pallas/sample.py`) owns the
    projection then, so logits never materialize in HBM.
    """
    x = _embed(params, token[:, None])  # (B, 1, d)
    positions = pos[None] if jnp.ndim(pos) == 0 else pos[:, None]  # (1,)|(B,1)

    new_cache = []
    for layer, (block_params, layer_cache) in enumerate(
        zip(params["layers"], cache)
    ):
        window = config.layer_window(layer)

        def attend(
            h, block_params=block_params, layer_cache=layer_cache,
            layer=layer, window=window,
        ):
            q, k, v = _project_qkv(h, block_params["attn"], config)
            q, k = _rope_qk(q, k, positions, config, layer)
            k_cache = _cache_write(layer_cache["k"], k, pos)
            v_cache = _cache_write(layer_cache["v"], v, pos)
            if active is not None:
                keep = active[:, None, None, None]
                k_cache = jnp.where(keep, k_cache, layer_cache["k"])
                v_cache = jnp.where(keep, v_cache, layer_cache["v"])
            new_cache.append({"k": k_cache, "v": v_cache})
            # Both impls read the COMPACT GQA cache — the per-token hot path
            # reads only num_kv_heads * ctx bytes; expanding heads here
            # would forfeit GQA's decode-bandwidth win.  "paged" names the
            # block-pool-native kernel; the dense cache has no block table,
            # so it degrades to the contiguous flash-decoding kernel here.
            if window is not None:
                # The window is a mask over the full dense cache (the
                # streamed kernel has no lower frontier).
                from bpe_transformer_tpu.kernels.pallas.decode_attention import (
                    xla_decode_attention,
                )

                att = xla_decode_attention(
                    q[:, :, 0], k_cache, v_cache, pos, window=window
                )
            elif config.decode_attention_impl in ("pallas", "paged"):
                # Flash-decoding kernel: the cache streams through VMEM
                # once, scores never reach HBM
                # (kernels/pallas/decode_attention.py; parity pinned by
                # tests/test_kernels.py + tests/test_decode.py).
                from bpe_transformer_tpu.kernels.pallas.decode_attention import (
                    decode_attention,
                )

                att = decode_attention(q[:, :, 0], k_cache, v_cache, pos)
            else:
                # Materialized grouped einsum — the same single
                # implementation the kernel parity tests pin against.
                from bpe_transformer_tpu.kernels.pallas.decode_attention import (
                    xla_decode_attention,
                )

                att = xla_decode_attention(q[:, :, 0], k_cache, v_cache, pos)
            att = merge_heads(att[:, :, None, :])
            return linear(att, block_params["attn"]["output_proj"])

        x = _block_apply(x, block_params, config, attend)

    x = _final_norm(x, params, config)
    if return_hidden:
        return x[:, 0], new_cache
    head = lm_head_weight(params, config) if lm_head is None else lm_head
    logits = head_logits(x[:, 0], head)
    return logits, new_cache


# --------------------------------------------------------- paged KV memory
#
# The serving kvpool layer (serving/kvpool/) replaces the dense per-slot
# cache rows with a flat pool of fixed-size blocks; these are the device
# programs that read/write KV *through a block table* instead of a
# contiguous row.  Both live here (not in serving/) because they are the
# paged twins of prefill/decode_step above and share every building block.


def init_kv_pool(
    config: ModelConfig,
    num_blocks: int,
    block_size: int,
    dtype=jnp.float32,
    kv_dtype: str | None = None,
) -> KVCache:
    """A paged KV pool: per layer K and V block arrays ``(num_blocks,
    block_size, kv_heads * d_head)`` - block-major rows, one row a token
    position, its heads side by side along the lanes (head ``h`` is lanes
    ``h * d_head .. (h + 1) * d_head``).  Block 0 is the serving layer's
    trash block (masked writes are steered to it); a request's cache is a
    chain of block ids, not a row index.

    The shape is the layout: it is the one the v5e compiler takes in and
    hands back as it rests on the device, ``{2,1,0}`` in whole ``(8, 128)``
    tiles with no padding, so a program that scatters rows into the pool
    (``.at[block_ids, offsets]``) and gathers blocks out of it
    (``buf[tables]``) with the pool donated updates it in place and holds no
    copy of a pool-sized array (`tests/test_chip_compile.py` reads the
    compiled text).  Any four-dimensional shape with ``d_head`` = 64 as its
    minor dimension rests with the block axis folded into the tile
    (``{0,3,2,1}``), which no scatter or gather indexes: every program then
    re-lays the whole pool out on the way in and again on the way out.

    ``kv_dtype="int8"`` stores quantized K/V at one byte per value, in the
    same shape, with per-block-per-head f32 scales in parallel
    ``k_scale``/``v_scale`` pools ``(num_blocks, kv_heads)`` — HBM traffic
    per decoded token drops ~2x vs bf16 (4x vs f32) and the freed bytes buy
    more blocks at fixed memory.  A block's scale covers its whole
    ``(block_size, d_head)`` tile of that head; writers keep it valid by
    rescale-on-grow (see :func:`_quantize_decode_row`).  ``kv_dtype=None``
    stores at ``dtype`` (the activation width) with no scale pools.
    """
    if kv_dtype not in (None, "int8"):
        raise ValueError(f'kv_dtype={kv_dtype!r} must be None or "int8"')
    kv_heads = config.num_kv_heads or config.num_heads
    shape = (num_blocks, block_size, kv_heads * config.d_head)
    store = jnp.int8 if kv_dtype == "int8" else dtype
    layers: KVCache = []
    for _ in range(config.num_layers):
        layer = {"k": jnp.zeros(shape, store), "v": jnp.zeros(shape, store)}
        if kv_dtype == "int8":
            layer["k_scale"] = jnp.zeros((num_blocks, kv_heads), jnp.float32)
            layer["v_scale"] = jnp.zeros((num_blocks, kv_heads), jnp.float32)
        layers.append(layer)
    return layers


@jax.named_scope("pool_gather")
def gather_paged_rows(
    buf: Array, tables: Array, scale: Array | None = None, dtype=None
) -> Array:
    """Materialize contiguous per-slot KV from the pool through the block
    table, AS THE POOL HOLDS IT: ``buf`` (num_blocks, block_size, kv_heads *
    d_head) gathered by ``tables`` (slots, blocks_per_slot) -> (slots,
    blocks_per_slot * block_size, kv_heads * d_head), a key position a row.

    This one gather is the XLA read path of the paged pool: the decode
    tick and the verify pass attend over the rows as they are
    (`xla_rows_attention`), so nothing as large as the gathered chains is
    ever re-laid out.  The buffer is transient (one layer at a time) —
    only the block pool is resident, which is where paging's memory win
    lives.

    An int8 pool passes its per-block-per-head ``scale`` pool
    ``(num_blocks, kv_heads)`` and the ``dtype`` to dequantize to: the
    scales are gathered through the same table and spread over their
    head's lanes (the paged-native kernel dequantizes in registers without
    ever materializing this buffer).
    """
    gathered = buf[tables]  # (S, nb, bs, kv * dh)
    s, nb, bs, width = gathered.shape
    if scale is not None:
        lanes = jnp.repeat(scale[tables], width // scale.shape[1], axis=-1)
        gathered = (
            gathered.astype(jnp.float32) * lanes[:, :, None, :]
        ).astype(dtype)
    return gathered.reshape(s, nb * bs, width)


def gather_paged_kv(
    buf: Array, tables: Array, kv_heads: int, scale: Array | None = None,
    dtype=None,
) -> Array:
    """:func:`gather_paged_rows` with the heads split out: (slots, kv_heads,
    blocks_per_slot * block_size, d_head), layout-identical to the dense
    cache, for the readers that want that (chunked prefill's one slot, the
    contiguous Pallas flash-decoding kernel).  The split is a transpose of
    the gathered transient, never of the pool."""
    rows = gather_paged_rows(buf, tables, scale, dtype)
    s, keys, width = rows.shape
    with jax.named_scope("pool_gather"):
        return jnp.transpose(
            rows.reshape(s, keys, kv_heads, width // kv_heads), (0, 2, 1, 3)
        )


def _pool_rows(rows: Array, dtype) -> Array:
    """``(..., kv_heads, d_head)`` K or V rows as the pool holds them:
    ``(..., kv_heads * d_head)`` at the pool's width."""
    return rows.reshape(*rows.shape[:-2], -1).astype(dtype)


@jax.named_scope("pool_write")
def _quantize_decode_row(
    pool_arr: Array, scale_arr: Array, new_row: Array, write_ids, offsets
) -> tuple[Array, Array]:
    """Scatter one new KV row per slot into an int8 block pool, keeping the
    per-block-per-head scale sound under incremental writes.

    ``new_row`` (slots, kv_heads, d_head) lands at ``(write_ids[s],
    offsets[s])``, one pool row.  The block scale grows monotonically within
    one occupancy: ``offset == 0`` starts a FRESH block (blocks are recycled
    without zeroing, so the previous owner's scale must not leak) and
    resets the base scale to 0; otherwise the new row's absmax is folded
    in and — when the scale grew — the block's already-written int8 rows
    are rescaled by ``old/new`` (<= 1, so values stay in range; the
    precision given up on old rows is the cost of per-block rather than
    per-token scales).  One block per slot is touched — activation-sized
    work, no pool-wide traffic.
    """
    slots, kv_heads, d_head = new_row.shape
    blk = pool_arr[write_ids].astype(jnp.float32)       # (S, bs, kv * d)
    blk = blk.reshape(slots, -1, kv_heads, d_head)      # (S, bs, kv, d)
    s_old = scale_arr[write_ids]                        # (S, kv)
    s_base = jnp.where(offsets[:, None] == 0, 0.0, s_old)
    amax = jnp.max(jnp.abs(new_row.astype(jnp.float32)), axis=-1)  # (S, kv)
    s_new = jnp.maximum(s_base, amax / 127.0)
    safe = jnp.maximum(s_new, 1e-30)
    # factor 0 on fresh blocks zeroes the recycled garbage rows too.
    factor = s_base / safe
    blk = jnp.round(blk * factor[:, None, :, None])
    row_q = jnp.clip(
        jnp.round(new_row.astype(jnp.float32) / safe[:, :, None]), -127, 127
    )
    sel = (
        jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
        == offsets[:, None, None, None]
    )
    blk = jnp.where(sel, row_q[:, None, :, :], blk)
    return (
        pool_arr.at[write_ids].set(_pool_rows(blk, jnp.int8)),
        scale_arr.at[write_ids].set(s_new),
    )


def paged_decode_step(
    params: Params,
    token: Array,
    pos: Array,
    pool: KVCache,
    tables: Array,
    config: ModelConfig,
    lm_head: Array | None = None,
    active: Array | None = None,
    return_hidden: bool = False,
    *,
    block_size: int,
) -> tuple[Array, KVCache]:
    """One cached decode step against the paged pool — the block-table twin
    of :func:`decode_step` (``return_hidden`` as there: the fused
    sample-in-kernel tick takes the final-norm hidden state and owns the
    head projection).

    ``token``/``pos``/``active``: per-slot ``(slots,)`` vectors as in the
    serving slot pool.  ``tables`` (slots, blocks_per_slot) int32 maps each
    slot's logical block index to a pool block id (0 = trash).  The new
    K/V is scattered into the pool at ``(tables[slot, pos // block_size],
    pos % block_size)`` — inactive slots scatter to the trash block, so one
    compiled program serves every occupancy pattern (int8 pools quantize
    the row at scatter time, :func:`_quantize_decode_row`).  Attention then
    honors ``config.decode_attention_impl``: ``"paged"`` runs the
    paged-NATIVE flash kernel straight against the pool (the block table is
    consumed inside the kernel's index maps — no contiguous transient);
    ``"xla"`` gathers the slots' rows (:func:`gather_paged_rows`,
    dequantizing on gather for int8 pools) and attends over them as they
    are (`xla_rows_attention`); ``"pallas"`` splits the heads out of the
    gathered rows for the contiguous flash-decoding kernel.
    """
    x = _embed(params, token[:, None])  # (S, 1, d)
    positions = pos[:, None]
    block_col = (pos // block_size).astype(jnp.int32)
    offsets = (pos % block_size).astype(jnp.int32)
    write_ids = jnp.take_along_axis(tables, block_col[:, None], axis=1)[:, 0]
    if active is not None:
        write_ids = jnp.where(active, write_ids, 0)
    quantized = "k_scale" in pool[0]
    kv_heads = config.num_kv_heads or config.num_heads
    # (S, 1, keys): key j visible to slot s iff j <= pos[s].
    visible = (
        jnp.arange(tables.shape[1] * block_size)[None, None, :]
        <= pos[:, None, None]
    )

    new_pool = []
    for block_params, layer_pool in zip(params["layers"], pool):

        def attend(h, block_params=block_params, layer_pool=layer_pool):
            q, k, v = _project_qkv(h, block_params["attn"], config)
            q, k = _rope_qk(q, k, positions, config)
            # Scatter the one new token's K/V into each slot's frontier
            # block (advanced-index scatter: (S,) block ids x (S,) offsets
            # address (S, kv_heads * d_head) rows).
            k_scale = v_scale = None
            if quantized:
                k_pool, k_scale = _quantize_decode_row(
                    layer_pool["k"], layer_pool["k_scale"],
                    k[:, :, 0, :], write_ids, offsets,
                )
                v_pool, v_scale = _quantize_decode_row(
                    layer_pool["v"], layer_pool["v_scale"],
                    v[:, :, 0, :], write_ids, offsets,
                )
                new_pool.append(
                    {"k": k_pool, "v": v_pool,
                     "k_scale": k_scale, "v_scale": v_scale}
                )
            else:
                # Explicit cast to the pool width: jax 0.9 deprecates the
                # implicit one (an f32 row into a bf16 pool).
                with jax.named_scope("pool_write"):
                    k_pool = layer_pool["k"].at[write_ids, offsets].set(
                        _pool_rows(k[:, :, 0, :], layer_pool["k"].dtype)
                    )
                    v_pool = layer_pool["v"].at[write_ids, offsets].set(
                        _pool_rows(v[:, :, 0, :], layer_pool["v"].dtype)
                    )
                new_pool.append({"k": k_pool, "v": v_pool})
            if config.decode_attention_impl == "paged":
                from bpe_transformer_tpu.kernels.pallas.decode_attention import (
                    paged_decode_attention,
                )

                att = paged_decode_attention(
                    q[:, :, 0], k_pool, v_pool, tables, pos,
                    k_scale=k_scale, v_scale=v_scale,
                )[:, :, None, :]
            elif config.decode_attention_impl == "pallas":
                # The contiguous kernel wants the dense cache's layout.
                from bpe_transformer_tpu.kernels.pallas.decode_attention import (
                    decode_attention,
                )

                att = decode_attention(
                    q[:, :, 0],
                    gather_paged_kv(k_pool, tables, kv_heads, k_scale, h.dtype),
                    gather_paged_kv(v_pool, tables, kv_heads, v_scale, h.dtype),
                    pos,
                )[:, :, None, :]
            else:
                from bpe_transformer_tpu.kernels.pallas.decode_attention import (
                    xla_rows_attention,
                )

                att = xla_rows_attention(
                    q,
                    gather_paged_rows(k_pool, tables, k_scale, h.dtype),
                    gather_paged_rows(v_pool, tables, v_scale, h.dtype),
                    visible,
                )
            return linear(merge_heads(att), block_params["attn"]["output_proj"])

        x = _block_apply(x, block_params, config, attend)

    x = _final_norm(x, params, config)
    if return_hidden:
        return x[:, 0], new_pool
    head = lm_head_weight(params, config) if lm_head is None else lm_head
    logits = head_logits(x[:, 0], head)
    return logits, new_pool


def paged_chunk_prefill(
    params: Params,
    chunk_tokens: Array,
    start: Array,
    chunk_len: Array,
    table_row: Array,
    pool: KVCache,
    config: ModelConfig,
    lm_head: Array | None = None,
    *,
    block_size: int,
) -> tuple[Array, KVCache]:
    """Prefill ONE chunk of one slot's prompt into the paged pool.

    ``chunk_tokens`` (1, chunk_bucket) is the chunk padded to its program
    bucket; ``start`` (traced scalar) its first absolute position;
    ``chunk_len`` (traced) the real token count; ``table_row``
    (blocks_per_slot,) the slot's block chain.  The chunk's K/V is
    scattered straight into the pool per position (padded tail positions
    steer to the trash block), then the chunk's queries attend to the
    slot's FULL gathered cache under the causal mask ``key_pos <= start +
    row`` — which is what lets a chunk resume after a radix-cache-shared
    prefix (positions < start were written by an earlier request's
    prefill) and is also how long prompts prefill incrementally, chunk by
    chunk, between decode ticks.

    Returns logits at the chunk's last real position (the serving layer
    samples the first token from the FINAL chunk's logits and discards the
    others) and the updated pool.  Non-final chunks must have ``chunk_len
    % block_size == 0`` so the next chunk starts block-aligned.

    Attention here is the materialized-scores formulation (transient
    O(chunk x context) score buffer) regardless of ``attention_impl`` —
    the chunk-vs-whole-cache shape has no flash kernel yet.

    int8 pools: chunks always start block-aligned (the radix-shared prefix
    is whole blocks; non-final chunks are block multiples), so every block
    this chunk touches is freshly owned — its per-block scale is RESET to
    the max over the chunk's rows in that block (a scatter-max after a
    scatter-zero; the recycled block's leftover scale never leaks), then
    the rows quantize against it.  A final partial block's scale keeps
    growing under decode's rescale-on-grow writes.
    """
    _, cb = chunk_tokens.shape
    ctx = config.context_length
    nb = table_row.shape[0]
    positions = start + jnp.arange(cb)
    # Padded tail rows may index past the RoPE/context tables: clamp them
    # (their outputs are discarded; their pool writes go to trash below).
    safe_positions = jnp.clip(positions, 0, ctx - 1)
    in_chunk = jnp.arange(cb) < chunk_len
    idx_in_table = jnp.clip(safe_positions // block_size, 0, nb - 1)
    write_ids = jnp.where(in_chunk, table_row[idx_in_table], 0)
    offsets = safe_positions % block_size
    quantized = "k_scale" in pool[0]
    kv_heads = config.num_kv_heads or config.num_heads

    x = _embed(params, chunk_tokens)
    scale = 1.0 / jnp.sqrt(jnp.asarray(config.d_head, jnp.float32))
    # (cb, ctx) causal frontier: key j visible to chunk row i iff j <= start+i.
    mask = (
        jnp.arange(nb * block_size)[None, :] <= (start + jnp.arange(cb))[:, None]
    )

    @jax.named_scope("pool_write")
    def _quant_chunk_rows(pool_arr, scale_arr, rows):
        """Per-block scatter of this chunk's (cb, kv, d) rows: reset the
        written blocks' scales, scatter-max the rows' absmax in, quantize
        each row against its block's fresh scale."""
        amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)  # (cb, kv)
        amax = jnp.where(in_chunk[:, None], amax, 0.0)
        scales = scale_arr.at[write_ids, :].set(0.0)
        scales = scales.at[write_ids, :].max(amax / 127.0)
        per_row = jnp.maximum(scales[write_ids], 1e-30)  # (cb, kv)
        rows_q = jnp.clip(
            jnp.round(rows.astype(jnp.float32) / per_row[..., None]),
            -127, 127,
        )
        return (
            pool_arr.at[write_ids, offsets].set(_pool_rows(rows_q, jnp.int8)),
            scales,
        )

    new_pool = []
    for block_params, layer_pool in zip(params["layers"], pool):

        def attend(h, block_params=block_params, layer_pool=layer_pool):
            q, k, v = _project_qkv(h, block_params["attn"], config)
            q, k = _rope_qk(q, k, safe_positions, config)
            k_scale = v_scale = None
            if quantized:
                k_pool, k_scale = _quant_chunk_rows(
                    layer_pool["k"], layer_pool["k_scale"],
                    jnp.transpose(k[0], (1, 0, 2)),
                )
                v_pool, v_scale = _quant_chunk_rows(
                    layer_pool["v"], layer_pool["v_scale"],
                    jnp.transpose(v[0], (1, 0, 2)),
                )
                new_pool.append(
                    {"k": k_pool, "v": v_pool,
                     "k_scale": k_scale, "v_scale": v_scale}
                )
            else:
                with jax.named_scope("pool_write"):
                    k_pool = layer_pool["k"].at[write_ids, offsets].set(
                        _pool_rows(
                            jnp.transpose(k[0], (1, 0, 2)),
                            layer_pool["k"].dtype,
                        )
                    )
                    v_pool = layer_pool["v"].at[write_ids, offsets].set(
                        _pool_rows(
                            jnp.transpose(v[0], (1, 0, 2)),
                            layer_pool["v"].dtype,
                        )
                    )
                new_pool.append({"k": k_pool, "v": v_pool})
            # One slot's chain, heads split out: an activation-sized
            # transpose (the scores below are per head).
            k_cache = gather_paged_kv(
                k_pool, table_row[None], kv_heads, k_scale, h.dtype
            )
            v_cache = gather_paged_kv(
                v_pool, table_row[None], kv_heads, v_scale, h.dtype
            )
            with jax.named_scope("chunk_attn"):
                k_full = _expand_kv(k_cache, config)
                v_full = _expand_kv(v_cache, config)
                scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_full) * scale
                scores = jnp.where(mask[None, None], scores, -jnp.inf)
                probs = jax.nn.softmax(
                    scores.astype(jnp.float32), axis=-1
                ).astype(h.dtype)
                att = merge_heads(
                    jnp.einsum("bhqk,bhkd->bhqd", probs, v_full)
                )
            return linear(att, block_params["attn"]["output_proj"])

        x = _block_apply(x, block_params, config, attend)

    x = _final_norm(x, params, config)
    head = lm_head_weight(params, config) if lm_head is None else lm_head
    idx = jnp.reshape(jnp.clip(chunk_len - 1, 0, cb - 1), (1, 1, 1))
    last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
    return head_logits(last, head), new_pool


def paged_verify_step(
    params: Params,
    tokens: Array,
    positions: Array,
    rooms: Array,
    pool: KVCache,
    tables: Array,
    config: ModelConfig,
    lm_head: Array | None = None,
    active: Array | None = None,
    return_hidden: bool = False,
    *,
    block_size: int,
) -> tuple[Array, KVCache]:
    """Batched multi-position scoring pass — the speculative-decoding
    verify program's forward (`serving/spec/`), generalizing
    :func:`paged_decode_step` from one token per slot to ``K+1``.

    ``tokens`` (slots, K+1): each slot's not-yet-written last token followed
    by its K draft proposals; ``positions`` (slots,) the absolute position
    of ``tokens[:, 0]``; ``rooms`` (slots,) how many PROPOSAL rows are real
    for this slot (rows ``0..rooms[s]`` are written/scored; beyond that the
    scatter steers to the trash block and the outputs are host-ignored —
    one fixed-``K`` program serves every per-slot headroom).  All K+1
    tokens' K/V scatter into the pool through the block table exactly as a
    chunk prefill would (a K-length chunk IS a scoring pass), then every
    row attends to the slot's full gathered rows under the causal frontier
    ``key_pos <= positions + row`` (`xla_rows_attention`, as the tick's).  Returns logits ``(slots, K+1, vocab)``
    — row ``j`` is the target distribution for position ``positions+j+1``
    — and the updated pool.

    The serving layer rolls the written frontier back over rejected rows
    afterwards (`PagedEngine.rewind`): positions beyond the accepted
    prefix hold stale K/V that the mask keeps invisible until the next
    verify overwrites them.

    int8 pools quantize rows SEQUENTIALLY via a ``lax.scan`` over the K+1
    rows with the decode-row quantizer (`_quantize_decode_row`), preserving
    its rescale-on-grow semantics: rows land mid-block next to earlier
    valid rows, so the chunk-prefill scale RESET would corrupt them.  The
    pass's readers then see each block's FINAL scale (plain ticks see the
    scale as of their own step), so int8 verify logits match K+1 plain
    ticks within quantization error, not bitwise — the act-width path is
    exact.  Attention is the materialized-scores formulation (as in
    :func:`paged_chunk_prefill`): the chunk-vs-whole-cache shape has no
    flash kernel, and ``decode_attention_impl`` only governs the 1-token
    tick.
    """
    s, k1 = tokens.shape
    ctx = config.context_length
    nb = tables.shape[1]
    pos_j = positions[:, None] + jnp.arange(k1)[None, :]  # (S, K+1)
    safe_pos = jnp.clip(pos_j, 0, ctx - 1)
    valid = (jnp.arange(k1)[None, :] <= rooms[:, None]) & (pos_j <= ctx - 1)
    if active is not None:
        valid = valid & active[:, None]
    idx = jnp.clip(safe_pos // block_size, 0, nb - 1)
    write_ids = jnp.where(valid, jnp.take_along_axis(tables, idx, axis=1), 0)
    offsets = safe_pos % block_size
    quantized = "k_scale" in pool[0]

    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        xla_rows_attention,
    )

    x = _embed(params, tokens)  # (S, K+1, d)
    # (S, K+1, ctx) causal frontier: key j visible to row i iff j <= pos_i.
    mask = jnp.arange(nb * block_size)[None, None, :] <= pos_j[:, :, None]

    def _quant_verify_rows(pool_arr, scale_arr, rows):
        """Sequential per-row int8 scatter (rows (S, K+1, kv, d)): each row
        applies the decode quantizer against the scale state the previous
        row left — the same write order as K+1 plain decode ticks."""

        def step(carry, inp):
            arr, sc = carry
            row, ids, off = inp
            return _quantize_decode_row(arr, sc, row, ids, off), None

        (pool_arr, scale_arr), _ = jax.lax.scan(
            step,
            (pool_arr, scale_arr),
            (
                jnp.swapaxes(rows, 0, 1),
                jnp.swapaxes(write_ids, 0, 1),
                jnp.swapaxes(offsets, 0, 1),
            ),
        )
        return pool_arr, scale_arr

    new_pool = []
    for block_params, layer_pool in zip(params["layers"], pool):

        def attend(h, block_params=block_params, layer_pool=layer_pool):
            q, k, v = _project_qkv(h, block_params["attn"], config)
            q, k = _rope_qk(q, k, safe_pos, config)
            k_rows = jnp.swapaxes(k, 1, 2)  # (S, K+1, kv, d)
            v_rows = jnp.swapaxes(v, 1, 2)
            k_scale = v_scale = None
            if quantized:
                k_pool, k_scale = _quant_verify_rows(
                    layer_pool["k"], layer_pool["k_scale"], k_rows
                )
                v_pool, v_scale = _quant_verify_rows(
                    layer_pool["v"], layer_pool["v_scale"], v_rows
                )
                new_pool.append(
                    {"k": k_pool, "v": v_pool,
                     "k_scale": k_scale, "v_scale": v_scale}
                )
            else:
                k_pool = layer_pool["k"].at[write_ids, offsets].set(
                    _pool_rows(k_rows, layer_pool["k"].dtype)
                )
                v_pool = layer_pool["v"].at[write_ids, offsets].set(
                    _pool_rows(v_rows, layer_pool["v"].dtype)
                )
                new_pool.append({"k": k_pool, "v": v_pool})
            # Every slot's chain: as large as the pool, so attended as rows.
            att = xla_rows_attention(
                q,
                gather_paged_rows(k_pool, tables, k_scale, h.dtype),
                gather_paged_rows(v_pool, tables, v_scale, h.dtype),
                mask,
            )
            return linear(merge_heads(att), block_params["attn"]["output_proj"])

        x = _block_apply(x, block_params, config, attend)

    x = _final_norm(x, params, config)
    if return_hidden:
        return x, new_pool
    head = lm_head_weight(params, config) if lm_head is None else lm_head
    return head_logits(x, head), new_pool


# ----------------------------------------------- grouped pools (two kinds)
#
# A config whose layers differ in kind (sliding-window and full attention)
# keeps two pool groups: a full layer's pool holds a slot's whole chain, a
# window layer's only the pages still inside the window.  Both use the page
# layout of `kernels/pallas/ragged_attention.py` - ``(pages, page_size,
# 2 * kv_heads, d_head)``, K and V of a head side by side - which the
# device's default tiling holds as is, so the programs donate the pool and
# update it in place with no copy at their edges.  ``tables`` is a dict:
# ``"full"`` and ``"window"`` page rows per slot, and ``"window_base"``, the
# absolute position of the first row entry of the window group (rows there
# start at the slot's first live page; full rows start at position 0).


def init_grouped_kv_pool(
    config: ModelConfig, num_full_blocks: int, num_window_blocks: int,
    block_size: int, dtype=jnp.float32,
) -> list:
    """One page array a layer, sized by its group.  Page 0 of every array is
    the trash page."""
    kv_heads = config.num_kv_heads or config.num_heads
    return [
        jnp.zeros(
            (
                num_full_blocks if config.layer_window(layer) is None
                else num_window_blocks,
                block_size, 2 * kv_heads, config.d_head,
            ),
            dtype,
        )
        for layer in range(config.num_layers)
    ]


def _group_rows(tables: dict, config: ModelConfig, layer: int):
    """``(page rows, base position, window)`` of the layer's group."""
    window = config.layer_window(layer)
    if window is None:
        return tables["full"], 0, None
    return tables["window"], tables["window_base"], window


@jax.named_scope("pool_write")
def _write_pages(pages, k, v, page_ids, offsets):
    """Scatter one K and one V row a token, ``(tokens, kv_heads, d_head)``
    each, to ``pages[page_ids, offsets]``: a whole (2 * kv_heads, d_head)
    tile a token."""
    tokens, kv_heads, d_head = k.shape
    rows = jnp.stack([k, v], axis=2).reshape(tokens, 2 * kv_heads, d_head)
    return pages.at[page_ids, offsets].set(rows.astype(pages.dtype))


def _attn_scope(window):
    return jax.named_scope("attn_window" if window is not None else "attn_full")


def grouped_decode_step(
    params: Params,
    token: Array,
    pos: Array,
    pool: list,
    tables: dict,
    config: ModelConfig,
    lm_head: Array | None = None,
    active: Array | None = None,
    *,
    block_size: int,
) -> tuple[Array, list, Array]:
    """:func:`paged_decode_step` over the grouped pools: one new token a
    slot, written to its group's page and attended from there by the ragged
    paged kernel, which reads the pages a slot holds and no others.  Returns
    ``(logits, pool, moe_counts)``; ``moe_counts`` sums the layers'
    ``dropless_moe`` counts (zeros without a MoE layer), idle slots left
    out."""
    from bpe_transformer_tpu.kernels.pallas.ragged_attention import (
        ragged_paged_attention,
    )

    slots = token.shape[0]
    x = _embed(params, token[:, None])  # (S, 1, d)
    positions = pos[:, None]
    live = jnp.ones((slots,), bool) if active is None else active
    cu_q_lens = jnp.arange(slots + 1, dtype=jnp.int32)
    num_seqs = jnp.full((1,), slots, jnp.int32)
    tally: list = []
    new_pool = []
    for layer, (block_params, pages) in enumerate(zip(params["layers"], pool)):
        rows, base, window = _group_rows(tables, config, layer)
        rel = (pos - base).astype(jnp.int32)
        page_ids = jnp.take_along_axis(rows, (rel // block_size)[:, None], axis=1)[:, 0]
        page_ids = jnp.where(live, page_ids, 0)
        kv_lens = jnp.where(live, rel + 1, 1).astype(jnp.int32)

        def attend(
            h, block_params=block_params, pages=pages, layer=layer, rows=rows,
            window=window, rel=rel, page_ids=page_ids, kv_lens=kv_lens,
        ):
            q, k, v = _project_qkv(h, block_params["attn"], config)
            q, k = _rope_qk(q, k, positions, config, layer)
            pages = _write_pages(
                pages, k[:, :, 0], v[:, :, 0], page_ids, rel % block_size
            )
            new_pool.append(pages)
            with _attn_scope(window):
                att = ragged_paged_attention(
                    q[:, :, 0], pages, kv_lens, rows, cu_q_lens, num_seqs,
                    window=window, one_query_per_seq=True,
                )
            att = att.reshape(slots, 1, -1)
            return linear(att, block_params["attn"]["output_proj"])

        x = _block_apply(x, block_params, config, attend, valid=live, tally=tally)

    x = _final_norm(x, params, config)
    head = lm_head_weight(params, config) if lm_head is None else lm_head
    return head_logits(x[:, 0], head), new_pool, _sum_counts(tally)


def grouped_chunk_prefill(
    params: Params,
    chunk_tokens: Array,
    start: Array,
    chunk_len: Array,
    table_rows: dict,
    pool: list,
    config: ModelConfig,
    lm_head: Array | None = None,
    *,
    block_size: int,
) -> tuple[Array, list, Array]:
    """:func:`paged_chunk_prefill` over the grouped pools: the chunk's K/V
    goes to its group's pages, then its queries attend to the slot's pages
    through the ragged paged kernel - causal in a full layer, inside the
    window in a window layer, whose row must reach back to ``start -
    window + 1``.  ``table_rows`` holds one slot's rows.  Returns
    ``(last real position's logits, pool, moe_counts)``."""
    from bpe_transformer_tpu.kernels.pallas.ragged_attention import (
        ragged_paged_attention,
    )

    _, cb = chunk_tokens.shape
    positions = start + jnp.arange(cb)
    safe_positions = jnp.clip(positions, 0, config.context_length - 1)
    in_chunk = jnp.arange(cb) < chunk_len
    cu_q_lens = jnp.stack([0, chunk_len]).astype(jnp.int32)
    num_seqs = jnp.ones((1,), jnp.int32)
    x = _embed(params, chunk_tokens)
    tally: list = []
    new_pool = []
    for layer, (block_params, pages) in enumerate(zip(params["layers"], pool)):
        row, base, window = _group_rows(table_rows, config, layer)
        rel = (safe_positions - base).astype(jnp.int32)
        idx = jnp.clip(rel // block_size, 0, row.shape[0] - 1)
        page_ids = jnp.where(in_chunk, row[idx], 0)
        kv_lens = jnp.reshape(start + chunk_len - base, (1,)).astype(jnp.int32)

        def attend(
            h, block_params=block_params, pages=pages, layer=layer, row=row,
            window=window, rel=rel, page_ids=page_ids, kv_lens=kv_lens,
        ):
            q, k, v = _project_qkv(h, block_params["attn"], config)
            q, k = _rope_qk(q, k, safe_positions, config, layer)
            pages = _write_pages(
                pages, jnp.swapaxes(k[0], 0, 1), jnp.swapaxes(v[0], 0, 1),
                page_ids, rel % block_size,
            )
            new_pool.append(pages)
            with _attn_scope(window):
                att = ragged_paged_attention(
                    jnp.swapaxes(q[0], 0, 1), pages, kv_lens, row[None],
                    cu_q_lens, num_seqs, window=window, one_query_per_seq=False,
                )
            # Padded rows are not computed by the kernel: whatever it left
            # there must not reach the next layer's K/V.
            att = jnp.where(in_chunk[:, None, None], att, 0)
            return linear(att.reshape(1, cb, -1), block_params["attn"]["output_proj"])

        x = _block_apply(
            x, block_params, config, attend, valid=in_chunk[None], tally=tally
        )

    x = _final_norm(x, params, config)
    head = lm_head_weight(params, config) if lm_head is None else lm_head
    idx = jnp.reshape(jnp.clip(chunk_len - 1, 0, cb - 1), (1, 1, 1))
    last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
    return head_logits(last, head), new_pool, _sum_counts(tally)


def _sum_counts(tally: list) -> Array:
    if not tally:
        return jnp.zeros((3,), jnp.int32)
    return jnp.sum(jnp.stack(tally), axis=0)


def _sample_from_logits(
    logits, key, temperature: float, top_k: int | None, top_p: float | None = None
):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None:
        # lax.top_k is O(V log k) vs a full O(V log V) sort for one
        # threshold — this runs once per generated token inside the scan.
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # Nucleus sampling: keep the smallest prob-descending prefix whose
        # mass reaches top_p (the first token is always kept).
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p  # mass BEFORE each token
        # The most likely token is always kept (also guards top_p <= 0,
        # which would otherwise mask EVERY logit).
        keep = keep.at[..., 0].set(True)
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1)
        logits = jnp.where(logits < cutoff[..., None], -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


@partial(
    jax.jit,
    static_argnames=(
        "config", "max_new_tokens", "temperature", "top_k", "top_p", "stop_id"
    ),
)
def generate_cached(
    params: Params,
    prompt_ids: Array,
    key: Array,
    *,
    config: ModelConfig,
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    stop_id: int | None = None,
) -> Array:
    """Sample ``(batch, max_new_tokens)`` continuations in one XLA program.

    ``prompt_ids``: (batch, prompt_len) with ``prompt_len + max_new_tokens
    <= context_length`` (the cache is sized to the context window).

    ``stop_id``: once a sequence samples this id, every subsequent token is
    pinned to ``stop_id`` inside the scan (the program shape stays static —
    stopping cannot shrink the scan), so the host can truncate at the FIRST
    occurrence and agree exactly with the early-exiting sliding-window path.
    """
    batch, plen = prompt_ids.shape
    if plen + max_new_tokens > config.context_length:
        raise ValueError(
            f"prompt ({plen}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"context_length ({config.context_length})"
        )
    # Honor the config's compute dtype (mirrors forward(): params cast once,
    # activations and the KV cache follow).  The LM head is pre-cast to the
    # SAME compute dtype — _head_logits accumulates in f32, so logits stay
    # float32-clean while the head read (the per-token bandwidth bottleneck
    # alongside the cache) happens at the compute width.
    act_dtype = jnp.dtype(config.activation_dtype)
    lm_head = lm_head_weight(params, config).astype(act_dtype)
    if act_dtype != jnp.float32:
        params = jax.tree_util.tree_map(lambda p: p.astype(act_dtype), params)
    cache = init_kv_cache(config, batch, dtype=act_dtype)
    logits, cache = prefill(params, prompt_ids, config, cache, lm_head=lm_head)
    key, sub = jax.random.split(key)
    first = _sample_from_logits(logits, sub, temperature, top_k, top_p)
    # -1 never matches a sampled id (ids are >= 0), so stop_id=None keeps
    # the pinning select a no-op without a second trace path.
    sid = -1 if stop_id is None else stop_id
    done = first == sid

    def step(carry, _):
        token, pos, cache, key, done = carry
        logits, cache = decode_step(
            params, token, pos, cache, config, lm_head=lm_head
        )
        key, sub = jax.random.split(key)
        nxt = _sample_from_logits(logits, sub, temperature, top_k, top_p)
        nxt = jnp.where(done, sid, nxt)
        return (nxt, pos + 1, cache, key, done | (nxt == sid)), nxt

    if max_new_tokens == 1:
        return first[:, None]
    _, rest = lax.scan(
        step,
        (first, jnp.asarray(plen), cache, key, done),
        None,
        length=max_new_tokens - 1,
    )
    return jnp.concatenate([first[:, None], rest.T], axis=1)
