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
    if config.eva_block:
        raise NotImplementedError(
            "chunked linear attention has no dense cache of a row a position: "
            "it is served over the paged engine's summary-and-window cache "
            "(`EvaRows`), and its whole-sequence form is `transformer.forward`"
        )
    if config.attention_kind == "mla":
        # Latent attention: per layer and sublayer one buffer of latent rows
        # (batch, 1, context_length, latent_width) - no heads.
        shape = (batch, 1, config.context_length, config.latent_width)
        return [
            [jnp.zeros(shape, dtype) for _ in range(config.attn_sublayers)]
            for _ in range(config.num_layers)
        ]
    # GQA stores only num_kv_heads — the cache (decode's HBM footprint)
    # shrinks by the query-group factor.
    kv_heads = config.num_kv_heads or config.num_heads
    shape = (batch, kv_heads, config.context_length, config.d_head)
    if config.hybrid_block:
        # A layer's entry by its mixer: K and V rows, a state-space layer's
        # recurrent state, whatever the context (`models/ssm.py`), nothing.
        from bpe_transformer_tpu.models.ssm import init_ssm_state

        def entry(mixer):
            if mixer == "ssm":
                return init_ssm_state(config, batch, dtype)
            return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)} if mixer else {}

        return [entry(config.layer_mixer(i)) for i in range(config.num_layers)]
    return [
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for _ in range(config.num_layers)
    ]


def _softmax_scale(config):
    """What the materialized scores are multiplied by: ``d_head ** -0.5``,
    or the config's own multiplier."""
    if config.attention_multiplier is None:
        return 1.0 / jnp.sqrt(jnp.asarray(config.d_head, jnp.float32))
    return jnp.float32(config.attention_multiplier)


def _rope_qk(q, k, positions, config, layer: int = 0):
    if not config.layer_rope(layer):
        return q, k
    cos, sin = rope_tables(
        config.rope_dim, config.context_length, config.layer_rope_theta(layer)
    )
    # Keep the compute dtype (bf16 decode must not promote to f32 here).
    cos, sin = cos.astype(q.dtype), sin.astype(q.dtype)
    pos = jnp.expand_dims(positions, axis=-2)  # broadcast over heads
    if config.rope_dim != config.d_head:
        # The head's leading part rotates, the rest passes.
        def rotate(x):
            part = apply_rope(x[..., : config.rope_dim], pos, cos, sin)
            return jnp.concatenate([part, x[..., config.rope_dim:]], axis=-1)

        return rotate(q), rotate(k)
    return apply_rope(q, pos, cos, sin), apply_rope(k, pos, cos, sin)


def _ffn_decode(x, ffn, config, valid=None, tally=None):
    """The block's FFN as it is served.  A MoE layer is the dropless one
    (`models/moe.dropless_moe`): a decode step's few tokens and a prefill's
    many are routed alike and nothing is dropped, whatever capacity the
    training forward runs at.  ``valid`` leaves rows out of the expert
    computation; ``tally`` (a list) collects the layer's routing counts."""
    if config.ffn_type == "moe" and "router" not in ffn:
        # A dense layer beside expert layers (`config.DENSE_FFN_KIND`).
        from bpe_transformer_tpu.ops.core import swiglu

        with jax.named_scope("dense"):
            return swiglu(x, ffn["w1"], ffn["w2"], ffn["w3"])
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
    under ``parallel_block``; under ``hybrid_block`` ``a = x + r * Mixer(N1
    x)``, ``y = a + r * (M(N2 a) + S(N2 a))`` with the residual multiplier
    ``r``, ``attend`` being the layer's mixer - each of the two sublayers
    where the layer's tree has it (a layer of one sublayer has one norm and
    one branch; ``attend`` is not called without a mixer; latent attention
    outside the double layer is a mixer here like any other).  Under
    ``double_layer`` it is the
    shortcut-connected double layer, whose ``attend(h, sublayer)`` is called
    for each of its two attention sublayers: with norms ``N1 .. N4``, dense
    FFNs ``F_0, F_1`` and the expert layer ``M``, ``a = x + Attn_0(N1 x)``,
    ``u = N2 a``, ``m = M(u)``, ``b = a + F_0(u)``, ``d = b + Attn_1(N3
    b)``, ``y = d + F_1(N4 d) + m`` - the expert layer reads the first
    sublayer's normalised stream and joins at the layer's end.
    """
    if config.double_layer:
        from bpe_transformer_tpu.ops.core import swiglu

        ln, dense = block_params["ln"], block_params["dense_ffn"]
        with jax.named_scope("block/attn"):
            a = x + attend(_norm(x, ln[0], config), 0)
        with jax.named_scope("block/ffn"):
            u = _norm(a, ln[1], config)
            m = _ffn_decode(u, block_params["ffn"], config, valid, tally)
            with jax.named_scope("dense"):
                b = a + swiglu(u, dense[0]["w1"], dense[0]["w2"], dense[0]["w3"])
        with jax.named_scope("block/attn"):
            d = b + attend(_norm(b, ln[2], config), 1)
        with jax.named_scope("block/ffn"), jax.named_scope("dense"):
            h = _norm(d, ln[3], config)
            return d + swiglu(h, dense[1]["w1"], dense[1]["w2"], dense[1]["w3"]) + m
    if config.eva_block:
        # Sequential and pre-norm, the stream and both adds in float32 over
        # branches at the activation width (the published fp32_skip_add).
        act = jnp.dtype(config.activation_dtype)
        x = x.astype(jnp.float32)
        with jax.named_scope("block/attn"):
            x = x + attend(_norm(x, block_params["ln1"], config).astype(act))
        with jax.named_scope("block/ffn"):
            h = _norm(x, block_params["ln2"], config).astype(act)
            return x + _ffn_decode(h, block_params["ffn"], config, valid, tally)
    if config.hybrid_block:
        # Sequential and pre-norm; the layer's mixer (attention, or the
        # state-space mixer where the tree has "ssm") and the expert layer
        # with its shared expert each join times the residual multiplier.
        # The tree says which of the two the layer has (`layer_kinds`).
        r = config.residual_multiplier
        if "ln1" in block_params:
            with jax.named_scope("block/ssm" if "ssm" in block_params else "block/attn"):
                x = x + r * attend(_norm(x, block_params["ln1"], config))
        if "ffn" in block_params:
            with jax.named_scope("block/ffn"):
                h = _norm(x, block_params["ln2"], config)
                x = x + r * _ffn_decode(h, block_params["ffn"], config, valid, tally)
        return x
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
    if config.norm_unit_offset:
        # The offset is added at float32, as `transformer._maybe_norm` says.
        return rmsnorm(x, 1.0 + w.astype(jnp.float32), config.norm_eps)
    norm = layernorm if config.norm_type == "layernorm" else rmsnorm
    return norm(x, w, config.norm_eps)


def _embed(params, token_ids, config):
    with jax.named_scope("embed"):
        x = embedding(params["token_embeddings"], token_ids)
        if config.embedding_multiplier != 1.0:
            x = x * config.embedding_multiplier
        return x


def _logits(x, head, config):
    """Float32 logits of the final-norm rows ``x``."""
    logits = head_logits(x, head)
    if config.logits_scaling != 1.0:
        logits = logits / config.logits_scaling
    return logits


def _final_norm(x, params, config):
    with jax.named_scope("final_norm"):
        x = _norm(x, params["ln_final"], config)
        # A float32 residual stream ends here.
        return x.astype(config.activation_dtype) if config.eva_block else x


def _project_qkv(h, attn, config, layer: int | None = None):
    kv_heads = config.layer_kv_heads(layer)
    q = split_heads(linear(h, attn["q_proj"]), config.num_heads)
    k = split_heads(linear(h, attn["k_proj"]), kv_heads)
    v = split_heads(linear(h, attn["v_proj"]), kv_heads)
    if config.attention_value_scale != 1.0:
        v = v * jnp.asarray(config.attention_value_scale, v.dtype)
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
    x = _embed(params, token_ids, config)
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
        and config.attention_multiplier is None  # the kernel's scale is d_head's
    )
    if config.hybrid_block and last_pos is not None:
        raise NotImplementedError(
            "a recurrent state has no padded prefill: the rows behind "
            "last_pos would enter it (the paged engine's chunks mask them)"
        )
    if not use_flash:
        scale = _softmax_scale(config)
        causal = jnp.tril(jnp.ones((plen, plen), bool))

    new_cache = []
    for layer, (block_params, layer_cache) in enumerate(
        zip(params["layers"], cache)
    ):
        if config.attention_kind == "mla":
            layer_new: list = []

            def attend_latent(
                h, sub=0, block_params=block_params, layer_cache=layer_cache,
                layer_new=layer_new,
            ):
                from bpe_transformer_tpu.models.mla import self_attention

                out, rows = self_attention(
                    h, _latent_sublayer(block_params["attn"], sub, config),
                    positions, config,
                )
                layer_new.append(
                    lax.dynamic_update_slice(
                        layer_cache[sub],
                        rows[:, None].astype(layer_cache[sub].dtype),
                        (0, 0, 0, 0),
                    )
                )
                return out

            x = _block_apply(x, block_params, config, attend_latent)
            new_cache.append(layer_new)
            continue
        if config.layer_mixer(layer) is None:  # no cache of any kind
            x = _block_apply(x, block_params, config, None)
            new_cache.append(layer_cache)
            continue
        if config.layer_is_ssm(layer):
            from bpe_transformer_tpu.models.ssm import mamba2

            def mixer(h, block_params=block_params):
                out, state = mamba2(h, block_params["ssm"], config)
                new_cache.append(state)
                return out

            x = _block_apply(x, block_params, config, mixer)
            continue
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
    return _logits(last, head, config), new_cache


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


def _latent_sublayer(attn, sublayer: int, config):
    """One latent-attention sublayer's tree: the double layer holds a list
    of two, the sequential block the one tree itself."""
    return attn[sublayer] if config.double_layer else attn


def _latent_decode_attention(
    h, sub=0, *, attn, config, positions, pos, active, layer_cache, layer_new
):
    """One latent-attention sublayer of :func:`decode_step`: the new row
    into the dense latent cache, the query absorbed against it."""
    from bpe_transformer_tpu.kernels.pallas.mla_attention import (
        xla_mla_rows_attention,
    )
    from bpe_transformer_tpu.models import mla

    def attend(q_abs, rows):
        old = layer_cache[sub]
        buf = _cache_write(old, rows[:, None].astype(old.dtype), pos)
        if active is not None:
            buf = jnp.where(active[:, None, None, None], buf, old)
        layer_new.append(buf)
        visible = jnp.arange(buf.shape[2])[None, :] <= jnp.reshape(pos, (-1, 1))
        return xla_mla_rows_attention(
            q_abs, buf[:, 0], visible, rank=config.kv_lora_rank,
            scale=mla.softmax_scale(config),
        )

    return mla.absorbed_attention(
        h, _latent_sublayer(attn, sub, config), positions, config, attend
    )


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
    x = _embed(params, token[:, None], config)  # (B, 1, d)
    positions = pos[None] if jnp.ndim(pos) == 0 else pos[:, None]  # (1,)|(B,1)

    new_cache = []
    for layer, (block_params, layer_cache) in enumerate(
        zip(params["layers"], cache)
    ):
        if config.attention_kind == "mla":
            layer_new: list = []
            x = _block_apply(
                x, block_params, config,
                partial(
                    _latent_decode_attention, attn=block_params["attn"],
                    config=config, positions=positions, pos=pos, active=active,
                    layer_cache=layer_cache, layer_new=layer_new,
                ),
            )
            new_cache.append(layer_new)
            continue
        if config.layer_mixer(layer) is None:  # no cache of any kind
            x = _block_apply(x, block_params, config, None)
            new_cache.append(layer_cache)
            continue
        if config.layer_is_ssm(layer):
            from bpe_transformer_tpu.models.ssm import mamba2_step

            def mixer(h, block_params=block_params, layer_cache=layer_cache):
                out, state = mamba2_step(
                    h[:, 0], block_params["ssm"], config, layer_cache, active
                )
                new_cache.append(state)
                return out[:, None]

            x = _block_apply(x, block_params, config, mixer)
            continue
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
                    q[:, :, 0], k_cache, v_cache, pos, window=window,
                    scale=config.attention_multiplier,
                )
            elif config.decode_attention_impl in ("pallas", "paged"):
                # Flash-decoding kernel: the cache streams through VMEM
                # once, scores never reach HBM
                # (kernels/pallas/decode_attention.py; parity pinned by
                # tests/test_kernels.py + tests/test_decode.py).
                from bpe_transformer_tpu.kernels.pallas.decode_attention import (
                    decode_attention,
                )

                att = decode_attention(
                    q[:, :, 0], k_cache, v_cache, pos,
                    scale=config.attention_multiplier,
                )
            else:
                # Materialized grouped einsum — the same single
                # implementation the kernel parity tests pin against.
                from bpe_transformer_tpu.kernels.pallas.decode_attention import (
                    xla_decode_attention,
                )

                att = xla_decode_attention(
                    q[:, :, 0], k_cache, v_cache, pos,
                    scale=config.attention_multiplier,
                )
            att = merge_heads(att[:, :, None, :])
            return linear(att, block_params["attn"]["output_proj"])

        x = _block_apply(x, block_params, config, attend)

    x = _final_norm(x, params, config)
    if return_hidden:
        return x[:, 0], new_cache
    head = lm_head_weight(params, config) if lm_head is None else lm_head
    return _logits(x[:, 0], head, config), new_cache


# --------------------------------------------------------- paged KV memory
#
# The serving kvpool layer (serving/kvpool/) replaces the dense per-slot
# cache rows with a flat pool of fixed-size blocks; what follows reads and
# writes KV *through a block table* instead of a contiguous row: the pools,
# the cache kinds that own their formats, and the one forward over them
# (here, not in serving/: the paged twin of prefill/decode_step above,
# sharing every building block).


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

    This one gather is the XLA read path of the paged pool: the verify
    pass, and the decode tick where the paged-native kernel is not chosen
    (`DenseRows.attention_path`), attend over the rows as they are
    (`xla_rows_attention`), so nothing as large as the gathered chains is
    ever re-laid out.  It reads every slot's whole table row whatever the
    slot holds.  The buffer is transient (one layer at a time) — only the
    block pool is resident, which is where paging's memory win lives.

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
    cache, for the reader that wants that (chunked prefill's one slot).
    The split is a transpose of the gathered transient, never of the
    pool."""
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


@jax.named_scope("pool_write")
def _quantize_chunk_rows(
    pool_arr: Array, scale_arr: Array, rows: Array, write_ids, offsets, valid
) -> tuple[Array, Array]:
    """Scatter one slot's chunk of rows ``(rows, kv_heads, d_head)`` into an
    int8 block pool whose blocks the chunk owns FRESH (chunks start
    block-aligned: the radix-shared prefix is whole blocks, non-final chunks
    are block multiples): each written block's scale is RESET to the max
    over the chunk's rows in that block (a scatter-max after a scatter-zero;
    the recycled block's leftover scale never leaks), then the rows quantize
    against it.  A final partial block's scale keeps growing under
    :func:`_quantize_decode_row`."""
    amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1)  # (rows, kv)
    amax = jnp.where(valid[:, None], amax, 0.0)
    scales = scale_arr.at[write_ids, :].set(0.0)
    scales = scales.at[write_ids, :].max(amax / 127.0)
    per_row = jnp.maximum(scales[write_ids], 1e-30)  # (rows, kv)
    rows_q = jnp.clip(
        jnp.round(rows.astype(jnp.float32) / per_row[..., None]), -127, 127
    )
    return (
        pool_arr.at[write_ids, offsets].set(_pool_rows(rows_q, jnp.int8)),
        scales,
    )


# ------------------------------------------------------------ cache kinds
#
# One forward (:func:`paged_forward`) serves every program that reads and
# writes KV through a block table: a decode tick is ``(slots, 1)`` tokens, a
# speculative verify pass ``(slots, K + 1)``, a prefill chunk ``(1, chunk
# bucket)``.  What differs between pools is a *cache kind*: the one place
# that knows how a pool is laid out.  A kind provides
#
# * init          the pool's arrays (`init_kv_pool`, `init_grouped_kv_pool`,
#                  chosen by `init_paged_pool`);
# * addresses     computed at construction, once a program, from where each
#                  row goes (``positions``), the block tables and which rows
#                  are real (``valid``): block ids, offsets, visibility;
# * ``write``      one layer's new K/V rows into its pool arrays;
# * ``attend``     the queries against the layer's pool, ``(slots, rows,
#                  heads * d_head)`` out;
# * ``ffn_rows`` / ``tally`` / ``counts``   the rows a dropless expert layer
#                  routes, where its counts are collected and what the
#                  program hands back (None where the kind carries none:
#                  its programs then have no such output).
#
# ``positions`` and ``valid`` come as the program has them and are used as
# they come, never reshaped (the ``(n, 1)`` form of the same arithmetic
# compiles into other fusions than the programs were measured with):
# ``(slots,)`` for one row a slot, against ``tables`` (slots, blocks);
# ``(slots, rows)`` for several; and with ``chunk`` (its first position and
# the count of its real rows) ``(rows,)`` of ONE slot, whose ``tables`` are
# its own rows ``(blocks,)``.
#
# Latent attention's cache is a third kind (`LatentRows`, further down): it
# has no K/V heads to write or attend, so it provides the whole sublayer
# (``attention``) in place of ``write`` and ``attend``.  A recurrent state
# beside the K/V of a few attention layers is a fourth (`RecurrentRows`):
# it provides the state-space layers whole (``mixer``) and is `DenseRows`
# for the rest.


def _clamped(x, hi: int, rows: int):
    """``x``, positions or block columns of ``rows`` rows a slot, held to
    ``0 .. hi`` for indexing the RoPE tables or a block table.  Further
    rows of a verify pass and a chunk's padded tail may lie past the context
    or the table (their writes go to trash, their outputs nowhere); a
    one-row step never does - the engine retires a slot at the context's
    end - and is indexed as it is."""
    return x if rows == 1 else jnp.clip(x, 0, hi)


def _token_rows(x, chunk: bool, one_row: bool):
    """``(slots, heads, rows, d_head)`` as its addresses come: ``(rows,
    heads, d_head)`` of a chunk's one slot, ``(slots, heads, d_head)`` for
    one row a slot, else ``(slots, rows, heads, d_head)``."""
    if chunk:
        return jnp.swapaxes(x[0], 0, 1)
    return x[:, :, 0] if one_row else jnp.swapaxes(x, 1, 2)


def _block_addresses(tables, at, valid, block_size: int, rows: int):
    """``(block ids, offsets)`` of the rows at table-relative positions
    ``at``: ``tables[slot, at // block_size]``, the trash block 0 where
    ``valid`` is False."""
    col = _clamped(at // block_size, tables.shape[-1] - 1, rows).astype(jnp.int32)
    if tables.ndim == 1:
        ids = tables[col]
    elif at.ndim == 1:
        ids = jnp.take_along_axis(tables, col[:, None], axis=1)[:, 0]
    else:
        ids = jnp.take_along_axis(tables, col, axis=1)
    if valid is not None:
        ids = jnp.where(valid, ids, 0)
    return ids, (at % block_size).astype(jnp.int32)


def _activation_width_only(kv_dtype, holder: str) -> None:
    if kv_dtype is not None:
        raise ValueError(f"{holder} at the activation width")


class DenseRows:
    """`init_kv_pool`'s pool: per layer K and V rows ``(blocks, block,
    kv_heads * d_head)``, at the activation width or int8 with per-block
    scales.  ``tables`` maps a slot's logical block to a pool block id (0 =
    trash: rows that are not ``valid`` are written there, so one program
    serves every occupancy).

    A chunk's rows begin blocks it owns fresh: an int8 pool resets their
    scales (:func:`_quantize_chunk_rows`), and its queries attend to the
    slot's gathered chain by per-head materialized scores (an O(chunk x
    context) transient: the chunk-vs-whole-cache shape has no flash kernel).
    Otherwise rows land mid-block beside earlier ones: int8 rows
    rescale-on-grow one after another (:func:`_quantize_decode_row`, the
    write order of as many plain ticks; the readers of a several-row pass
    see each block's FINAL scale, so its int8 logits match plain ticks
    within quantization error, not bitwise).

    How the rows of a tick or a verify pass attend is a choice of the shape
    and the backend (:meth:`attention_path`): the one-row tick on the TPU
    reads the pool in place through the paged-native kernel, which copies
    the blocks a slot holds and no others (idle slots none); elsewhere, and
    for several rows a slot, every slot's whole table is gathered, as large
    as the pool, and attended as rows (`xla_rows_attention`).
    ``config.decode_attention_impl`` forces ``"xla"`` or ``"paged"`` on the
    one-row step (parity tests); no other step asks it."""

    #: Every row goes through the FFN, and a MoE layer's counts are dropped.
    ffn_rows = tally = None

    def __init__(self, config, tables, positions, valid, block_size, chunk=None):
        self.config, self.tables, self.valid = config, tables, valid
        self.chunk = chunk is not None
        self.one_row = positions.ndim == 1 and not self.chunk
        rows = 1 if self.one_row else positions.shape[-1]
        at = _clamped(positions, config.context_length - 1, rows)
        self.positions = positions
        self.rope_positions = at[:, None] if self.one_row else at
        self.write_ids, self.offsets = _block_addresses(
            tables, at, valid, block_size, rows
        )
        # (slots, rows, keys): key j visible to a row iff j <= its position.
        visible = jnp.arange(tables.shape[-1] * block_size) <= positions[..., None]
        self.visible = (
            visible[None] if self.chunk else visible[:, None] if self.one_row
            else visible
        )

    def write(self, layer, layer_pool, k, v):
        """``k``/``v`` (slots, kv_heads, rows, d_head) to ``(write_ids,
        offsets)``: an advanced-index scatter of ``kv_heads * d_head`` wide
        rows, cast to the pool's width."""
        out = {}
        for name, new in (("k", k), ("v", v)):
            rows = _token_rows(new, self.chunk, self.one_row)
            arr = layer_pool[name]
            if f"{name}_scale" in layer_pool:
                out[name], out[f"{name}_scale"] = self._write_int8(
                    arr, layer_pool[f"{name}_scale"], rows
                )
            else:
                with jax.named_scope("pool_write"):
                    out[name] = arr.at[self.write_ids, self.offsets].set(
                        _pool_rows(rows, arr.dtype)
                    )
        return out

    def _write_int8(self, arr, scale, rows):
        at = self.write_ids, self.offsets
        if self.chunk:
            return _quantize_chunk_rows(arr, scale, rows, *at, self.valid)
        if self.one_row:
            return _quantize_decode_row(arr, scale, rows, *at)
        return lax.scan(
            lambda carry, row: (_quantize_decode_row(*carry, *row), None),
            (arr, scale), tuple(jnp.swapaxes(a, 0, 1) for a in (rows, *at)),
        )[0]

    @staticmethod
    def attention_path(config, one_row: bool, blocks_per_slot: int, layer_pool) -> str:
        """``"paged"`` or ``"xla"``: how the rows of a tick (``one_row``) or
        a verify pass attend over this pool.
        `runtime.decode_attention_path` chooses from the shape and the
        backend unless the config forces the one-row step's path (the dense
        cache's ``"pallas"`` kernel has no block table: here it is the
        choice too)."""
        from bpe_transformer_tpu.kernels.pallas.runtime import (
            decode_attention_path,
        )

        if one_row and config.decode_attention_impl in ("xla", "paged"):
            return config.decode_attention_impl
        _, block_size, width = layer_pool["k"].shape
        return decode_attention_path(
            one_row, blocks_per_slot, block_size, width,
            layer_pool["k"].dtype.itemsize,
        )

    def attend(self, layer, q, layer_pool):
        from bpe_transformer_tpu.kernels.pallas.decode_attention import (
            paged_decode_attention,
            xla_rows_attention,
        )

        config, tables = self.config, self.tables
        kv_heads = config.num_kv_heads or config.num_heads
        k_pool, v_pool = layer_pool["k"], layer_pool["v"]
        k_scale, v_scale = layer_pool.get("k_scale"), layer_pool.get("v_scale")
        if self.chunk:
            # One slot's chain, heads split out: an activation-sized
            # transpose (the scores are per head).
            k_cache = gather_paged_kv(k_pool, tables[None], kv_heads, k_scale, q.dtype)
            v_cache = gather_paged_kv(v_pool, tables[None], kv_heads, v_scale, q.dtype)
            with jax.named_scope("chunk_attn"):
                scale = _softmax_scale(config)
                scores = jnp.einsum(
                    "bhqd,bhkd->bhqk", q, _expand_kv(k_cache, config)
                ) * scale
                scores = jnp.where(self.visible[:, None], scores, -jnp.inf)
                probs = jax.nn.softmax(
                    scores.astype(jnp.float32), axis=-1
                ).astype(q.dtype)
                att = jnp.einsum(
                    "bhqk,bhkd->bhqd", probs, _expand_kv(v_cache, config)
                )
        elif self.attention_path(
            config, self.one_row, tables.shape[-1], layer_pool
        ) == "paged":
            # Straight out of the pool: each slot's live blocks, a group a
            # step; a slot that is not valid holds no key and copies none.
            key_counts = self.positions + 1
            if self.valid is not None:
                key_counts = jnp.where(self.valid, key_counts, 0)
            att = paged_decode_attention(
                q[:, :, 0], k_pool, v_pool, tables, key_counts,
                k_scale=k_scale, v_scale=v_scale,
                scale=config.attention_multiplier,
            )[:, :, None, :]
        else:
            att = xla_rows_attention(
                q,
                gather_paged_rows(k_pool, tables, k_scale, q.dtype),
                gather_paged_rows(v_pool, tables, v_scale, q.dtype),
                self.visible, scale=config.attention_multiplier,
            )
        return merge_heads(att)

    @staticmethod
    def init_pool(config, num_blocks, block_size, dtype, *, kv_dtype=None, **_):
        """The kind's pool: of `init_paged_pool`'s keywords a kind takes its own."""
        return init_kv_pool(config, num_blocks, block_size, dtype, kv_dtype=kv_dtype)

    @staticmethod
    def zero_counts(config=None):
        """No routing counts ride along."""
        return None

    counts = zero_counts


# A config whose layers differ in kind (sliding-window and full attention)
# keeps two pool groups: a full layer's pool holds a slot's whole chain, a
# window layer's only the pages still inside the window.  Where the two
# kinds differ in their mask alone - a period of window layers, every layer
# the same K/V heads of one width - both groups use the page layout of
# `kernels/pallas/ragged_attention.py`, JAX's ragged paged kernel: ``(pages,
# page_size, 2 * kv_heads, d_head)``, K and V of a head side by side - which
# the device's default tiling holds as is, so the programs donate the pool
# and update it in place with no copy at their edges (`GroupedPages`).  Where
# they differ in the shape of what they cache (K/V heads by group, a value
# narrower than its key), each layer keeps rows of its own width and
# `kernels/pallas/sink_attention.py` reads them (`GroupedRows`, further down).


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


class _RoutingCounts:
    """What a kind that carries the dropless expert layers' routing counts
    shares: ``tally``, the list the forward collects them in, is the
    kind's own."""

    @staticmethod
    def zero_counts(config):
        """[tokens routed, assignments on held experts, non-empty expert
        groups] and, where the config has zero experts, [assignments on
        them], int32: what a program is handed and hands back
        (`moe.dropless_moe`'s counts)."""
        return jnp.zeros((4 if config.n_zero_experts else 3,), jnp.int32)

    def counts(self):
        """The tally summed over the layers (zeros without a MoE layer)."""
        if not self.tally:
            return self.zero_counts(self.config)
        return jnp.sum(jnp.stack(self.tally), axis=0)


class GroupedPages(_RoutingCounts):
    """`init_grouped_kv_pool`'s pool: both groups in the one page layout of
    JAX's ragged paged kernel, one ``kv_heads`` and one ``d_head`` for K and
    V of every layer.  ``tables`` is a dict: ``"full"`` and
    ``"window"`` page rows, and ``"window_base"``, the absolute position of
    the first row entry of the window group (rows there start at the slot's
    first live page; full rows start at position 0).  A layer's new K/V
    goes to its group's pages and is attended from there by the ragged
    paged kernel, which reads the pages a slot holds and no others - causal
    in a full layer, inside the window in a window layer, whose row must
    reach back to the first query's ``position - window + 1``.  Either one
    row a slot (a tick) or one slot's chunk.
    Routing counts of the dropless expert layers ride along, rows that are
    not ``valid`` left out."""

    def __init__(self, config, tables, positions, valid, block_size, chunk=None):
        if positions.ndim != 1:
            raise NotImplementedError(
                "several rows a slot (a verify pass) over window pool groups"
            )
        self.config, self.chunk = config, chunk is not None
        #: The layers' ``dropless_moe`` counts, as the forward collects them.
        self.tally: list = []
        tokens = positions.shape[0]
        self.ffn_rows = jnp.ones((tokens,), bool) if valid is None else valid
        if self.chunk:
            rows = tokens
            start, chunk_len = chunk
            self.cu_q_lens = jnp.stack([0, chunk_len]).astype(jnp.int32)
            self.num_seqs = jnp.ones((1,), jnp.int32)
        else:
            rows = 1
            self.cu_q_lens = jnp.arange(tokens + 1, dtype=jnp.int32)
            self.num_seqs = jnp.full((1,), tokens, jnp.int32)
        at = _clamped(positions, config.context_length - 1, rows)
        self.rope_positions = at if self.chunk else at[:, None]

        def addresses(window):
            """``(page rows, page ids, offsets, kv_lens)`` of a layer: its
            group's row entries start at absolute position ``base``."""
            page_rows, base = (
                (tables["full"], 0) if window is None
                else (tables["window"], tables["window_base"])
            )
            rel = (at - base).astype(jnp.int32)
            if self.chunk:
                kv_lens = jnp.reshape(start + chunk_len - base, (1,))
            else:
                kv_lens = jnp.where(self.ffn_rows, rel + 1, 1)
            return (
                page_rows if page_rows.ndim == 2 else page_rows[None],
                *_block_addresses(page_rows, rel, self.ffn_rows, block_size, rows),
                kv_lens.astype(jnp.int32),
            )

        # A layer each, not a group each: XLA merges the equal ones, and
        # fuses these few integers otherwise when they come merged.
        self.layers = [
            addresses(config.layer_window(layer))
            for layer in range(config.num_layers)
        ]

    @staticmethod
    def init_pool(
        config, num_blocks, block_size, dtype, *, kv_dtype=None,
        num_window_blocks=0, **_,
    ):
        _activation_width_only(kv_dtype, "window pool groups hold K/V")
        return init_grouped_kv_pool(
            config, num_blocks, num_window_blocks, block_size, dtype
        )

    @staticmethod
    def attention_path(config, one_row: bool, blocks_per_slot: int, layer_pool) -> str:
        """``"ragged"``, JAX's ragged paged kernel, on the TPU; ``"xla"``,
        its stand-in, elsewhere (`kernels/pallas/ragged_attention.py`)."""
        return "ragged" if jax.default_backend() == "tpu" else "xla"

    @jax.named_scope("pool_write")
    def write(self, layer, pages, k, v):
        """One K and one V row a token to ``pages[page_ids, offsets]``: a
        whole ``(2 * kv_heads, d_head)`` tile a token."""
        _, page_ids, offsets, _ = self.layers[layer]
        k, v = (_token_rows(x, self.chunk, True) for x in (k, v))
        tokens, kv_heads, d_head = k.shape
        tiles = jnp.stack([k, v], axis=2).reshape(tokens, 2 * kv_heads, d_head)
        return pages.at[page_ids, offsets].set(tiles.astype(pages.dtype))

    def attend(self, layer, q, pages):
        from bpe_transformer_tpu.kernels.pallas.ragged_attention import (
            ragged_paged_attention,
        )

        window = self.config.layer_window(layer)
        page_rows, _, _, kv_lens = self.layers[layer]
        slots, heads, rows, d_head = q.shape
        with jax.named_scope("attn_window" if window is not None else "attn_full"):
            att = ragged_paged_attention(
                _token_rows(q, self.chunk, True), pages, kv_lens,
                page_rows, self.cu_q_lens,
                self.num_seqs, window=window, one_query_per_seq=not self.chunk,
            )
        if self.chunk:
            # Padded rows are not computed by the kernel: whatever it left
            # there must not reach the next layer's K/V.
            att = jnp.where(self.ffn_rows[:, None, None], att, 0)
        return att.reshape(slots, rows, heads * d_head)


# Attention layers that differ by kind in the SHAPE of what they cache (K/V
# heads by group, a key wider than its value) keep the two groups of
# `GroupedPages` over rows laid out as `DenseRows`' are: a position's keys,
# every K/V head's side by side along the lanes, and behind them its values,
# each at its own width - ``(blocks, block_size, kv_heads * (d_head +
# value_dim))`` with the layer's own ``kv_heads``.  JAX's ragged kernel
# wants K and V of one width interleaved by head; these rows are read by the
# kernels of `kernels/pallas/sink_attention.py`.


def init_grouped_row_pool(
    config: ModelConfig, num_full_blocks: int, num_window_blocks: int,
    block_size: int, dtype=jnp.float32,
) -> list:
    """One array of rows a layer, sized by its group and its K/V heads.
    Block 0 of every array is the trash block."""
    return [
        jnp.zeros(
            (
                num_full_blocks if config.layer_window(layer) is None
                else num_window_blocks,
                block_size,
                config.layer_kv_heads(layer) * (config.d_head + config.value_dim),
            ),
            dtype,
        )
        for layer in range(config.num_layers)
    ]


class GroupedRows(_RoutingCounts):
    """`init_grouped_row_pool`'s pool.  ``tables`` is `GroupedPages`' dict
    (``"full"`` and ``"window"`` rows of block ids and ``"window_base"``, the
    absolute position of the window row's first entry).  A layer's new keys
    and values go to its group's blocks as one row a position and are
    attended from there under the layer's own mask and sink
    (`kernels/pallas/sink_attention.py`): a tick's one row a slot straight
    out of the pool, the blocks the slot holds inside what it may see and no
    others; a chunk's rows against the slot's gathered chain - a window
    layer's chain is window + chunk long whatever the context - by flash
    accumulation.  On the CPU both are a gather and a masked softmax.
    Either one row a slot (a tick) or one slot's chunk.  Routing counts
    ride along as in `GroupedPages`."""

    def __init__(self, config, tables, positions, valid, block_size, chunk=None):
        if positions.ndim != 1:
            raise NotImplementedError(
                "several rows a slot (a verify pass) over window pool groups"
            )
        self.config, self.chunk = config, chunk
        self.tally: list = []
        tokens = positions.shape[0]
        self.ffn_rows = jnp.ones((tokens,), bool) if valid is None else valid
        rows = tokens if chunk is not None else 1
        at = _clamped(positions, config.context_length - 1, rows)
        self.rope_positions = at if chunk is not None else at[:, None]
        window = config.sliding_window

        def addresses(windowed: bool):
            """``(block rows, block ids, offsets, index of each row's own
            key in its block row, the first key it sees)`` of a layer."""
            block_rows, base = (
                (tables["window"], tables["window_base"]) if windowed
                else (tables["full"], 0)
            )
            rel = (at - base).astype(jnp.int32)
            first = (
                jnp.maximum(rel - window + 1, 0) if windowed
                else jnp.zeros_like(rel)
            )
            return (
                block_rows,
                *_block_addresses(block_rows, rel, self.ffn_rows, block_size, rows),
                rel, first,
            )

        groups = {kind: addresses(kind) for kind in (False, True)}
        self.layers = [
            groups[config.layer_window(layer) is not None]
            for layer in range(config.num_layers)
        ]

    @staticmethod
    def init_pool(
        config, num_blocks, block_size, dtype, *, kv_dtype=None,
        num_window_blocks=0, **_,
    ):
        _activation_width_only(kv_dtype, "window pool groups hold K/V")
        return init_grouped_row_pool(
            config, num_blocks, num_window_blocks, block_size, dtype
        )

    @staticmethod
    def attention_path(config, one_row: bool, blocks_per_slot: int, layer_pool) -> str:
        """``"sink_paged"``, the tick's kernel, on the TPU at whole tiles;
        ``"xla"`` elsewhere (`sink_attention.sink_paged_path`)."""
        from bpe_transformer_tpu.kernels.pallas.sink_attention import (
            sink_paged_path,
        )

        _, block_size, width = layer_pool.shape
        kv_heads = width // (config.d_head + config.value_dim)
        return sink_paged_path(block_size, width, kv_heads * config.d_head)

    @jax.named_scope("pool_write")
    def write(self, layer, rows_pool, k, v):
        """A position's keys and, behind them, its values: one row."""
        _, block_ids, offsets, _, _ = self.layers[layer]
        k, v = (_token_rows(x, self.chunk is not None, True) for x in (k, v))
        rows = jnp.concatenate(
            [k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1)], axis=-1
        )
        return rows_pool.at[block_ids, offsets].set(rows.astype(rows_pool.dtype))

    def attend(self, layer, q, rows_pool, sink=None):
        from bpe_transformer_tpu.kernels.pallas import sink_attention

        config = self.config
        window = config.layer_window(layer)
        kv_heads = config.layer_kv_heads(layer)
        block_rows, _, _, rel, first = self.layers[layer]
        slots, _, rows, d_head = q.shape
        width = rows_pool.shape[-1]
        with jax.named_scope("attn_window" if window is not None else "attn_full"):
            if self.chunk is None:
                counts = jnp.where(self.ffn_rows, rel + 1, 0)
                if self.attention_path(config, True, 0, rows_pool) == "sink_paged":
                    att = sink_attention.sink_paged_attention(
                        q[:, :, 0], rows_pool, block_rows, counts, first, sink,
                        kv_heads=kv_heads, window=window is not None,
                    )
                else:
                    with jax.named_scope("pool_gather"):
                        chains = rows_pool[block_rows].reshape(slots, -1, width)
                    k, v = sink_attention.split_rows(chains, kv_heads, d_head)
                    att = sink_attention.xla_sink_attention(
                        jnp.swapaxes(q, 1, 2), k, v, (counts - 1)[:, None],
                        sink=sink, first=first[:, None],
                    )[:, 0]
                return att.reshape(slots, 1, -1)
            with jax.named_scope("pool_gather"):
                chain = rows_pool[block_rows].reshape(-1, width)
                k, v = sink_attention.split_rows(chain, kv_heads, d_head)
            queries = jnp.swapaxes(q[0], 0, 1)       # (rows, heads, d_head)
            if sink_attention.sink_chunk_path(rows, chain.shape[0], window) == "sink_chunk":
                att = sink_attention.sink_chunk_attention(
                    queries, k, v, rel[0], sink, window=window
                )
            else:
                att = sink_attention.xla_sink_attention(
                    queries[None], k[None], v[None], rel[None], window=window,
                    sink=sink,
                )[0]
            # What a padded row computed must not reach the next layer's K/V.
            att = jnp.where(self.ffn_rows[:, None, None], att, 0)
            return att.reshape(1, rows, -1)


# Latent attention caches one row a position and attention sublayer, the
# normalised latent beside the rotated shared key, with no heads
# (`models/mla.py`): a pool array a sublayer, a layer's side by side in the
# pool's list (sublayer ``j`` of layer ``l`` is entry ``l * sublayers + j``).


def init_latent_pool(
    config: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.float32
) -> list:
    """``{"c": (num_blocks, block_size, row width)}`` for each attention
    sublayer of each layer.  Block 0 is the trash block, as in every pool.

    A row is ``latent_width`` values padded with zeros to whole 128-lane
    tiles (576 -> 640): the device lays a row out in whole tiles whatever
    its shape says, and the v5e compiler slices a block out of the pool only
    along whole tiles (`tests/test_chip_compile.py`)."""
    shape = (num_blocks, block_size, -(-config.latent_width // 128) * 128)
    return [
        {"c": jnp.zeros(shape, dtype)}
        for _ in range(config.num_layers * config.attn_sublayers)
    ]


class LatentRows(_RoutingCounts):
    """`init_latent_pool`'s pool: one chain of blocks a slot, as `DenseRows`
    has (so the radix prefix cache, whose bookkeeping is block ids, shares
    whole frozen blocks of latent rows between slots), rows of
    ``latent_width`` values.  A tick's one row a slot attends in the
    absorbed form straight out of the pool (`mla_paged_attention`: on the
    TPU two kernels, the chain of blocks that the tick's slots share
    attended once for all of them and each slot's own blocks after it -
    which blocks those are it reads from the tables, `shared_prefix` - and
    gathered rows elsewhere); a chunk's rows attend over the slot's
    gathered chain, the prefix an earlier request wrote included, as far as
    the chunk's last position - a bucket of 256 rows or more on the TPU in
    the expanded form inside one kernel, each block of rows up-projected
    where it is attended, anything else in the absorbed loop
    (`mla.rows_attention` chooses; `kernels/pallas/mla_attention.py` says
    why, with both forms' times).  Several rows a
    slot (a verify pass) have no form here.  Routing counts of the expert
    layers ride along as in `GroupedPages`."""

    def __init__(self, config, tables, positions, valid, block_size, chunk=None):
        if positions.ndim != 1:
            raise NotImplementedError(
                "several rows a slot (a verify pass) over a latent pool"
            )
        self.config, self.tables, self.chunk = config, tables, chunk
        self.tally: list = []
        tokens = positions.shape[0]
        self.ffn_rows = jnp.ones((tokens,), bool) if valid is None else valid
        rows = tokens if chunk is not None else 1
        at = _clamped(positions, config.context_length - 1, rows)
        self.positions = positions
        self.rope_positions = at if chunk is not None else at[:, None]
        self.write_ids, self.offsets = _block_addresses(
            tables, at, valid, block_size, rows
        )

    @staticmethod
    def init_pool(config, num_blocks, block_size, dtype, *, kv_dtype=None, **_):
        _activation_width_only(kv_dtype, "a latent pool holds its rows")
        return init_latent_pool(config, num_blocks, block_size, dtype)

    @staticmethod
    def attention_path(config, one_row: bool, blocks_per_slot: int, layer_pool) -> str:
        """``"mla_paged"``, the kernels, or ``"xla"``: how a tick's rows
        attend (`mla_attention.mla_paged_path`)."""
        from bpe_transformer_tpu.kernels.pallas.mla_attention import (
            mla_paged_path,
        )

        _, block_size, width = layer_pool["c"].shape
        return mla_paged_path(block_size, width, config.kv_lora_rank)

    def attention(self, h, attn, layer_pool, new_pool):
        """One sublayer: ``h`` (slots, rows, d_model) -> the same shape; the
        new latent rows go to ``(write_ids, offsets)`` of the sublayer's
        pool array, appended to ``new_pool`` updated."""
        from bpe_transformer_tpu.kernels.pallas.mla_attention import (
            mla_paged_attention,
        )
        from bpe_transformer_tpu.models import mla

        config, pool_arr = self.config, layer_pool["c"]

        def write(rows):
            with jax.named_scope("pool_write"):
                pad = pool_arr.shape[-1] - rows.shape[-1]
                written = pool_arr.at[self.write_ids, self.offsets].set(
                    jnp.pad(rows, ((0, 0), (0, pad))).astype(pool_arr.dtype)
                )
            new_pool.append({"c": written})
            return written

        if self.chunk is None:
            def attend(q_abs, rows):
                key_counts = jnp.where(self.ffn_rows, self.positions + 1, 0)
                return mla_paged_attention(
                    q_abs, write(rows[:, 0]), self.tables, key_counts,
                    rank=config.kv_lora_rank, scale=mla.softmax_scale(config),
                )

            return mla.absorbed_attention(
                h, attn, self.rope_positions, config, attend
            )
        start, chunk_len = self.chunk
        q_nope, q_rope = mla.queries(h, attn, self.rope_positions, config)
        rows = mla.latent_rows(h, attn, self.rope_positions, config)
        written = write(rows[0])
        with jax.named_scope("pool_gather"):
            chain = written[self.tables].reshape(-1, written.shape[-1])
        att = mla.rows_attention(
            q_nope[0], q_rope[0], chain, attn, self.positions,
            start + chunk_len, config,
        )
        return linear(att[None], attn["output_proj"])


# A state-space layer keeps no rows of positions: one recurrent state a
# sequence, whatever its length (`models/ssm.py`).  Its pool entry is a row
# a SLOT - ``{"ssm": (slots + 1, ...) float32 in the resting layout of
# `kernels/pallas/ssm.py` (the channels along the lanes: that module's
# docstring describes it), "conv": (slots + 1, k - 1, channels)}``, the last
# row trash, as block 0 is of the pools of positions - beside the K and V
# pools of the config's attention layers, which are `DenseRows`' own, and the
# empty entries of the layers that have no mixer and so no cache of any kind.


def init_recurrent_pool(
    config: ModelConfig, num_blocks: int, block_size: int, slots: int,
    dtype=jnp.float32,
) -> list:
    """A layer's entry by its mixer (`ModelConfig.layer_mixer`):
    `init_kv_pool`'s K and V rows for an attention layer, zeroed state rows
    for a state-space layer, an empty entry for a layer without one."""
    from bpe_transformer_tpu.kernels.pallas.ssm import to_resting
    from bpe_transformer_tpu.models.ssm import init_ssm_state

    kv_heads = config.num_kv_heads or config.num_heads
    shape = (num_blocks, block_size, kv_heads * config.d_head)

    def rows_a_slot():  # `init_ssm_state`'s rows, the states where they rest
        state = init_ssm_state(config, slots + 1, dtype)
        return {**state, "ssm": to_resting(state["ssm"], config.ssm_groups)}

    def entry(mixer):
        if mixer == "ssm":  # zeros at the shapes alone: nothing is relaid
            shapes = jax.eval_shape(rows_a_slot)
            return {name: jnp.zeros(s.shape, s.dtype) for name, s in shapes.items()}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)} if mixer else {}

    return [entry(config.layer_mixer(i)) for i in range(config.num_layers)]


class RecurrentRows(_RoutingCounts):
    """`init_recurrent_pool`'s pool.  The attention layers are `DenseRows`'
    by composition (``write``, ``attend``: a chain of blocks a slot, the
    paged-native kernel on the tick); a state-space layer is provided whole
    (:meth:`mixer`), its state addressed **by slot id**:

    * a tick's row ``s`` is slot ``s``; rows that are not ``valid`` (idle
      slots, slots still prefilling) are sent to the trash row, so a tick
      never touches their state (`kernels/pallas/ssm.ssm_state_update`
      updates the addressed rows in place, where they rest: that module
      describes the layout), and keep their conv rows;
    * a chunk is one slot's, ``tables = {"blocks": its table row, "slot":
      its id}``: it starts from the slot's state, **or from zeros where it
      starts at position 0** (an admission: nothing of the slot's last
      tenant is read), runs the chunked scan over its bucket with the
      padded rows masked out of state and conv rows, and leaves the state
      after its last real row for the prompt's next chunk or first tick
      (the scan's ``(heads, channels, state values)`` is one slot's
      relayout from and to the pool's, `from_resting` / `to_resting`).

    Several rows a slot (a verify pass) would need the state of every row
    to roll back to: no form here.  Routing counts ride along as in
    `GroupedPages`."""

    def __init__(self, config, tables, positions, valid, block_size, chunk=None):
        if positions.ndim != 1:
            raise NotImplementedError(
                "several rows a slot (a verify pass) over a recurrent state"
            )
        self.config, self.chunk = config, chunk
        self.tally: list = []
        tokens = positions.shape[0]
        self.ffn_rows = jnp.ones((tokens,), bool) if valid is None else valid
        if chunk is not None:
            tables, self.slot = tables["blocks"], tables["slot"]
        else:
            self.state_ids = jnp.where(self.ffn_rows, jnp.arange(tokens), tokens)
        self.rows = DenseRows(config, tables, positions, valid, block_size, chunk)
        self.rope_positions = self.rows.rope_positions

    @staticmethod
    def init_pool(config, num_blocks, block_size, dtype, *, kv_dtype=None, slots=0, **_):
        _activation_width_only(kv_dtype, "a recurrent pool holds K/V")
        return init_recurrent_pool(config, num_blocks, block_size, slots, dtype)

    @staticmethod
    def attention_path(config, one_row: bool, blocks_per_slot: int, layer_pool) -> str:
        return DenseRows.attention_path(config, one_row, blocks_per_slot, layer_pool)

    def write(self, layer, layer_pool, k, v):
        return self.rows.write(layer, layer_pool, k, v)

    def attend(self, layer, q, layer_pool):
        return self.rows.attend(layer, q, layer_pool)

    def mixer(self, h, ssm, layer_pool, new_pool):
        """One state-space layer: ``h`` (slots, rows, d_model) -> the same
        shape; the layer's state rows, updated, are appended to
        ``new_pool``."""
        from bpe_transformer_tpu.kernels.pallas.ssm import (
            from_resting,
            ssm_state_update,
            to_resting,
        )
        from bpe_transformer_tpu.models import ssm as mamba

        config = self.config
        if self.chunk is None:
            slots = h.shape[0]
            z, x, b, c, dt, a, conv = mamba.step_inputs(
                h[:, 0], ssm, config, layer_pool["conv"][:slots], self.ffn_rows
            )
            y, states = ssm_state_update(
                layer_pool["ssm"], self.state_ids, x, dt, a, b, c,
                ssm["D"].astype(jnp.float32),
            )
            with jax.named_scope("pool_write"):
                conv = layer_pool["conv"].at[:slots].set(conv)
            new_pool.append({"ssm": states, "conv": conv})
            return mamba.step_output(y, z, ssm, config)[:, None]
        start, _ = self.chunk
        with jax.named_scope("pool_gather"):
            state = {
                name: jnp.where(
                    start == 0, 0,
                    lax.dynamic_index_in_dim(arr, self.slot, 0, keepdims=True),
                ).astype(arr.dtype)
                for name, arr in layer_pool.items()
            }
            state["ssm"] = from_resting(state["ssm"], config.ssm_head_dim)
        out, state = mamba.mamba2(h, ssm, config, state, self.ffn_rows[None])
        with jax.named_scope("pool_write"):
            state["ssm"] = to_resting(state["ssm"], config.ssm_groups)
            new_pool.append({
                name: lax.dynamic_update_slice_in_dim(
                    arr, state[name].astype(arr.dtype), self.slot, 0
                )
                for name, arr in layer_pool.items()
            })
        return out


# Chunked linear attention (`models/eva.py`) keeps the exact K/V of a
# sequence's open window and one summary row for every chunk of the windows
# it has closed.  Its pool is `init_kv_pool`'s - a row is K and V of
# ``heads * d_head`` whether it is a position's or a chunk's summary - and a
# block is a chunk (``block_size == eva_chunk``), so a summary is a block's
# summary and a block of summaries those of ``block_size`` blocks.


def eva_table_geometry(config: ModelConfig, block_size: int) -> tuple[int, int, int]:
    """``(summary blocks a window, window blocks, table width)`` of a slot's
    table row over a summary-and-window cache: the summary blocks of every
    closed window, then the open window's blocks, then the open window's
    own summary blocks, written and not yet visible - ``windows *
    summary_blocks + window_blocks`` entries at the longest context."""
    window, chunk = config.eva_window, config.eva_chunk
    if block_size != chunk or (window // chunk) % block_size:
        raise ValueError(
            f"a summary-and-window cache keeps a chunk a block and whole "
            f"blocks of summaries a window: block_size={block_size} must "
            f"equal eva_chunk={chunk} and its square divide "
            f"eva_window={window}"
        )
    per_window = window // chunk // block_size
    return per_window, window // block_size, (
        config.context_length // window * per_window + window // block_size
    )


class EvaRows:
    """A summary-and-window cache over `init_kv_pool`'s pool.  A slot's
    table row is **its visible summary blocks followed by its open window's
    blocks** (`eva_table_geometry`; the serving engine rewrites the row each
    time a window closes), so the rows a query attends - every summary of
    every closed window, then its own window up to itself - are the leading
    ``key_count`` rows of the row's chain: the position at ``r`` of window
    ``w`` is chain row ``at = w * chunks_per_window + r``, and attends
    ``at + 1`` rows.

    * A tick's one row a slot is written at ``at`` and attends under that
      plain length, so it is the dense pool's tick - `DenseRows` over chain
      rows in the place of positions, by composition: its write, and its
      two paths to attend (the paged-native kernel on the TPU, gathered
      rows elsewhere).  The row
      that completes a chunk (``r % chunk == chunk - 1``) also reads its
      block back whole and writes the block's summary
      (`eva.chunk_summaries`) among the open window's pending summaries,
      ``window`` rows past the window's start; every other row's summary,
      computed alike (one program), goes to the trash block.
    * A chunk is one slot's, block-aligned and inside one window (the
      engine cuts prompts so): its rows are written, its whole blocks
      summarised from the rows in hand, and its queries attend over the
      slot's gathered chain in `eva.xla_eva_chunk_attention`'s loop.

    Several rows a slot (a verify pass) would straddle a window's closing:
    no form here.  No routing counts ride along."""

    ffn_rows = tally = None

    def __init__(self, config, tables, positions, valid, block_size, chunk=None):
        if positions.ndim != 1:
            raise NotImplementedError(
                "several rows a slot (a verify pass) over a summary-and-"
                "window cache"
            )
        eva_table_geometry(config, block_size)
        self.config, self.tables, self.chunk = config, tables, chunk
        window, per_chunk = config.eva_window, config.eva_chunk
        rows = 1 if chunk is None else positions.shape[0]
        pos = _clamped(positions, config.context_length - 1, rows)
        self.rope_positions = pos[:, None] if chunk is None else pos
        # Chain rows: the closed windows' summaries, then the open window.
        self.n_summaries = pos // window * config.eva_chunks_per_window
        #: The dense kind over chain rows in the place of positions: where
        #: a row is written, and how a tick's row attends.
        self.rows = DenseRows(
            config, tables, self.n_summaries + pos % window, valid, block_size,
            chunk,
        )
        # Where the summaries go: the pending rows lie a window past the
        # open window's first row.
        if chunk is None:
            closes = pos % per_chunk == per_chunk - 1
            summary_at = self.n_summaries + window + pos % window // per_chunk
            summary_valid = closes if valid is None else closes & valid
        else:
            start, chunk_len = chunk
            whole = jnp.arange(rows // per_chunk)
            summary_at = (
                start // window * config.eva_chunks_per_window + window
                + start % window // per_chunk + whole
            )
            summary_valid = (whole + 1) * per_chunk <= chunk_len
        self.summary_ids, self.summary_offsets = _block_addresses(
            tables, summary_at, summary_valid, block_size, summary_at.shape[0]
        )

    @staticmethod
    def init_pool(config, num_blocks, block_size, dtype, *, kv_dtype=None, **_):
        _activation_width_only(kv_dtype, "a summary-and-window pool holds its rows")
        return init_kv_pool(config, num_blocks, block_size, dtype)

    @staticmethod
    def attention_path(config, one_row: bool, blocks_per_slot: int, layer_pool) -> str:
        return DenseRows.attention_path(config, one_row, blocks_per_slot, layer_pool)

    def attention(self, h, attn, layer, layer_pool, new_pool):
        """One sublayer: ``h`` (slots, rows, d_model) -> the same shape; the
        layer's K and V arrays, the new rows and summaries written, are
        appended to ``new_pool``."""
        from bpe_transformer_tpu.models import eva

        config = self.config
        q, k, v = eva.project_qkv(h, attn, self.rope_positions, config)
        written = self.rows.write(layer, layer_pool, k, v)
        heads, d_head, per_chunk = config.num_heads, config.d_head, config.eva_chunk
        if self.chunk is None:
            # The block the row landed in, whole, as the pool now holds it.
            with jax.named_scope("pool_gather"):
                k_blocks, v_blocks = (
                    jnp.swapaxes(
                        written[name][self.rows.write_ids].reshape(
                            -1, per_chunk, heads, d_head
                        ), 1, 2,
                    )[:, :, None]
                    for name in ("k", "v")
                )
        else:
            k_blocks, v_blocks = (
                x[0].reshape(heads, -1, per_chunk, d_head) for x in (k, v)
            )
        k_sum, v_sum = eva.chunk_summaries(
            k_blocks, v_blocks, attn["eva_mu"], attn["eva_phi"]
        )
        if self.chunk is not None:  # (heads, chunks, d_head) -> a row a chunk
            k_sum, v_sum = (jnp.swapaxes(x, 0, 1) for x in (k_sum, v_sum))
        for name, rows in (("k", k_sum), ("v", v_sum)):
            with jax.named_scope("pool_write"):
                written[name] = written[name].at[
                    self.summary_ids, self.summary_offsets
                ].set(rows.reshape(rows.shape[0], -1).astype(q.dtype))
        new_pool.append(written)

        if self.chunk is None:
            # A plain length over the chain: the dense kind's tick.
            with jax.named_scope("eva_attn"):
                att = self.rows.attend(layer, q, written)
        else:
            start, chunk_len = self.chunk
            k_rows, v_rows = (
                gather_paged_kv(written[name], self.tables[None], heads)[0]
                for name in ("k", "v")
            )
            att = merge_heads(eva.xla_eva_chunk_attention(
                q[0], k_rows, v_rows, self.n_summaries[0],
                start % config.eva_window, chunk_len,
            )[None])
        return linear(att, attn["output_proj"])

    zero_counts = counts = staticmethod(DenseRows.zero_counts)


def cache_kind(config: ModelConfig):
    if config.attention_kind == "mla":
        return LatentRows
    if config.eva_block:
        return EvaRows
    if config.has_window_layers:
        # However the window layers were spelt (a period or a pattern's
        # letters): pages while the two kinds differ in their mask alone,
        # rows once they differ in the shape of what they cache.
        return GroupedRows if config.split_attention else GroupedPages
    return RecurrentRows if config.hybrid_block else DenseRows


def init_paged_pool(
    config: ModelConfig, num_blocks: int, block_size: int, dtype=jnp.float32,
    *, kv_dtype: str | None = None, num_window_blocks: int = 0,
    slots: int = 0,
):
    """The pool of the config's cache kind (its ``init_pool``).
    ``num_window_blocks`` sizes the window group where the kind has one,
    ``slots`` the state rows of a recurrent one."""
    return cache_kind(config).init_pool(
        config, num_blocks, block_size, dtype, kv_dtype=kv_dtype,
        num_window_blocks=num_window_blocks, slots=slots,
    )


def slot_cache(config, tables, positions, valid=None, *, block_size: int):
    """The cache as a decode tick (``positions`` (slots,): one row a slot)
    or a verify pass ((slots, K + 1)) addresses it: the token at absolute
    position ``positions[s]`` / ``positions[s, j]`` is a row of slot ``s``;
    rows where ``valid`` is False (idle slots, proposals beyond a slot's
    room) are written to trash."""
    return cache_kind(config)(config, tables, positions, valid, block_size)


def chunk_cache(
    config, table_row, start, chunk_len, bucket: int, *, block_size: int
):
    """The cache as ONE slot's prefill chunk addresses it: ``bucket`` rows
    from absolute position ``start`` (traced), the first ``chunk_len``
    (traced) real, through the slot's own ``table_row`` (one row of each
    table).  Non-final chunks must have ``chunk_len % block_size == 0`` so
    the next chunk starts block-aligned; a chunk may resume after a
    radix-shared prefix (positions < start were written by an earlier
    request's prefill)."""
    return cache_kind(config)(
        config, table_row, start + jnp.arange(bucket),
        jnp.arange(bucket) < chunk_len, block_size, (start, chunk_len),
    )


def _cached_attention(
    h, sublayer=0, *, attn, config, cache, layer, layer_pool, new_pool, ssm=None
):
    """One attention sublayer - or, where the layer's tree has ``ssm``, its
    state-space mixer - over the paged pool; ``layer_pool`` is the layer's
    entries of the pool's list, one a sublayer."""
    if config.attention_kind == "mla":
        return cache.attention(
            h, _latent_sublayer(attn, sublayer, config), layer_pool[sublayer],
            new_pool,
        )
    (layer_pool,) = layer_pool
    if config.eva_block:
        return cache.attention(h, attn, layer, layer_pool, new_pool)
    if ssm is not None:
        return cache.mixer(h, ssm, layer_pool, new_pool)
    q, k, v = _project_qkv(h, attn, config, layer)
    q, k = _rope_qk(q, k, cache.rope_positions, config, layer)
    layer_pool = cache.write(layer, layer_pool, k, v)
    new_pool.append(layer_pool)
    if "sink" in attn:
        att = cache.attend(layer, q, layer_pool, sink=attn["sink"])
    else:
        att = cache.attend(layer, q, layer_pool)
    return linear(att, attn["output_proj"])


def paged_forward(
    params: Params,
    tokens: Array,
    pool: list,
    cache,
    config: ModelConfig,
    lm_head: Array | None = None,
    *,
    row=None,
    return_hidden: bool = False,
):
    """``tokens`` (slots, rows) through the model over a paged pool: each
    layer's new K/V is written where ``cache`` (a cache kind, built by
    :func:`slot_cache` or :func:`chunk_cache`) says and attended from
    there.  The block-table twin of :func:`prefill` and :func:`decode_step`:
    a decode tick is ``rows = 1``, a verify pass ``rows = K + 1`` (row ``j``
    is the target distribution for position ``positions + j + 1``; the
    serving layer rolls the frontier back over rejected rows afterwards,
    `PagedEngine.rewind`, and the mask keeps them invisible until
    overwritten), a chunk ``slots = 1``.

    Returns ``(logits, pool, counts)``: float32 logits of every row
    ``(slots, rows, vocab)``, or ``(slots, vocab)`` of row ``row`` - an int
    for all slots alike, or (slots,) indices (a chunk's last real row); the
    final-norm hidden state in their place under ``return_hidden`` (the
    fused sample-in-kernel tail owns the head projection then, so logits
    never materialize in HBM); the updated pool; and the kind's routing
    counts (None for a kind that carries none)."""
    x = _embed(params, tokens, config)
    new_pool: list = []
    per_layer = config.attn_sublayers
    for layer, block_params in enumerate(params["layers"]):
        layer_pool = pool[layer * per_layer: (layer + 1) * per_layer]
        if config.layer_mixer(layer) is None:
            new_pool.extend(layer_pool)  # its empty entry, as it came
        x = _block_apply(
            x, block_params, config,
            partial(
                _cached_attention, attn=block_params.get("attn"), config=config,
                cache=cache, layer=layer, new_pool=new_pool,
                ssm=block_params.get("ssm"), layer_pool=layer_pool,
            ),
            valid=cache.ffn_rows, tally=cache.tally,
        )
    x = _final_norm(x, params, config)
    if isinstance(row, int):
        x = x[:, row]
    elif row is not None:
        x = jnp.take_along_axis(x, jnp.reshape(row, (-1, 1, 1)), axis=1)[:, 0]
    if not return_hidden:
        head = lm_head_weight(params, config) if lm_head is None else lm_head
        x = _logits(x, head, config)
    return x, new_pool, cache.counts()


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
