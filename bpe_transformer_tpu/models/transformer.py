"""Transformer language model: param pytree + pure jitted forward.

Architecture (the reference's tested contract, `/root/reference/tests/
adapters.py:209-361`): token embeddings -> N pre-norm blocks
(RMSNorm -> causal MHA with RoPE -> residual; RMSNorm -> SwiGLU -> residual)
-> final RMSNorm -> untied LM head.

TPU-first design: parameters are a plain nested dict of arrays (a pytree —
no module system), the forward pass is a pure function traced once under
``jax.jit``, blocks rematerialize under a graduated policy
(``ModelConfig.remat_policy`` -> :func:`policy_block`: none / full /
dots_saveable / save_attn, trading FLOPs for HBM at four operating
points), the layer stack optionally runs as one ``lax.scan``
(``scan_layers`` — O(1)-in-depth compile time), and activations can run
in bfloat16 while norms/softmax/loss accumulate in float32.  The
torch-style flat state-dict key schema (`adapters.py:307-353`) is
supported bidirectionally so reference checkpoints map 1:1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.ops.core import (
    embedding,
    head_logits,
    layernorm,
    linear,
    multihead_self_attention,
    rmsnorm,
    scaled_dot_product_attention,
    silu,
    swiglu,
)
from bpe_transformer_tpu.ops.rope import rope_tables

Params = dict


# --------------------------------------------------------------------- init


def init_params(
    rng: jax.Array, config: ModelConfig, dtype=jnp.float32
) -> Params:
    """Initialize a parameter pytree (truncated-normal projections, unit norms)."""

    def dense(key, d_out, d_in, std=0.02):
        return (
            jax.random.truncated_normal(key, -3.0, 3.0, (d_out, d_in), jnp.float32)
            * std
        ).astype(dtype)

    d, ff, v = config.d_model, config.d_ff, config.vocab_size
    # A norm's weight at rest: one, or the offset from it.
    unit = jnp.zeros if config.norm_unit_offset else jnp.ones
    # GQA: K/V project to num_kv_heads * d_head rows (== d for plain MHA);
    # Q to num_heads * d_head (== d unless the config sets head_dim).
    d_q = config.num_heads * config.d_head
    keys = jax.random.split(rng, 2 + config.num_layers)
    layers = []
    for i in range(config.num_layers):
        k = jax.random.split(keys[2 + i], 7)
        if config.double_layer:
            from bpe_transformer_tpu.models.mla import init_mla_params
            from bpe_transformer_tpu.models.moe import init_moe_params

            # Two attention sublayers, two dense FFNs, four norms and the
            # expert layer (`models/decode._block_apply`).
            layers.append(
                {
                    "attn": [init_mla_params(k[j], config, dtype) for j in (0, 1)],
                    "ln": [jnp.ones((d,), dtype) for _ in range(4)],
                    "dense_ffn": [
                        {
                            "w1": dense(kk[0], ff, d),
                            "w2": dense(kk[1], d, ff),
                            "w3": dense(kk[2], ff, d),
                        }
                        for kk in (jax.random.split(k[j], 3) for j in (2, 3))
                    ],
                    "ffn": init_moe_params(k[4], config, dtype),
                }
            )
            continue
        # The layer's sublayers by its kind (`ModelConfig.layer_kinds`): a
        # mixer under ln1, the feed-forward part under ln2 (under ln1 too
        # in the parallel block), or one of the two alone.
        layer = {}
        if config.layer_is_ssm(i):
            from bpe_transformer_tpu.models.ssm import init_ssm_params

            layer["ssm"] = init_ssm_params(k[0], config, dtype)
        elif config.eva_block:
            from bpe_transformer_tpu.models.eva import init_eva_params

            layer["attn"] = init_eva_params(k[0], config, dtype)
        elif config.attention_kind == "mla":
            # Latent attention in the sequential block: one sublayer a layer.
            from bpe_transformer_tpu.models.mla import init_mla_params

            layer["attn"] = init_mla_params(k[0], config, dtype)
        elif config.layer_mixer(i):
            # K/V heads by the layer's kind, a value at its own width, and
            # the sink's logit a query head where the kind has one (for a
            # config whose layers are alike: d_kv twice and d_q again).
            kv_heads = config.layer_kv_heads(i)
            layer["attn"] = {
                "q_proj": dense(k[0], d_q, d),
                "k_proj": dense(k[1], kv_heads * config.d_head, d),
                "v_proj": dense(k[2], kv_heads * config.value_dim, d),
                "output_proj": dense(k[3], d, config.num_heads * config.value_dim),
            }
            if config.layer_sink(i):
                layer["attn"]["sink"] = jnp.zeros((config.num_heads,), jnp.float32)
        if config.layer_mixer(i):
            layer["ln1"] = unit((d,), dtype)
        if config.layer_has_ffn(i):
            if config.ffn_type == "moe" and not config.layer_ffn_is_dense(i):
                from bpe_transformer_tpu.models.moe import init_moe_params

                layer["ffn"] = init_moe_params(k[4], config, dtype)
            else:
                layer["ffn"] = {
                    "w1": dense(k[4], ff, d),
                    "w2": dense(k[5], d, ff),
                    "w3": dense(k[6], ff, d),
                }
            if not config.parallel_block:  # one norm a block otherwise
                layer["ln2"] = unit((d,), dtype)
        layers.append(layer)
    params = {
        "token_embeddings": dense(keys[0], v, d),
        "layers": layers,
        "ln_final": unit((d,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = dense(keys[1], config.head_width, d)
    return params


def lm_head_weight(params: Params, config: ModelConfig) -> Array:
    """The vocab-projection matrix: the embedding itself when tied."""
    if config.tie_embeddings:
        return params["token_embeddings"]
    return params["lm_head"]


# ------------------------------------------------------------------ forward


def _ffn(
    x: Array, ffn_params: dict, config: ModelConfig
) -> tuple[Array, Array]:
    """FFN dispatch; returns ``(output, aux_loss)`` (aux is 0 except MoE)."""
    zero = jnp.zeros((), jnp.float32)
    if config.ffn_type in (None, "swiglu"):
        # int8-quantized serving weights (dict leaves, ops/quant.py) take
        # the plain composition below — each linear dispatches to the
        # dequant-in-register quant matmul; the fused swiglu kernel reads
        # raw arrays.
        if config.ffn_impl == "pallas" and not isinstance(
            ffn_params["w1"], dict
        ):
            from bpe_transformer_tpu.kernels.pallas.swiglu import swiglu_fused

            return (
                swiglu_fused(
                    x, ffn_params["w1"], ffn_params["w2"], ffn_params["w3"]
                ),
                zero,
            )
        return swiglu(x, ffn_params["w1"], ffn_params["w2"], ffn_params["w3"]), zero
    if config.ffn_type == "silu":
        return linear(silu(linear(x, ffn_params["w1"])), ffn_params["w2"]), zero
    if config.ffn_type == "gelu":
        from bpe_transformer_tpu.kernels.pallas.gelu import gelu

        return linear(gelu(linear(x, ffn_params["w1"])), ffn_params["w2"]), zero
    if config.ffn_type == "moe":
        from bpe_transformer_tpu.models.moe import dropless_moe, switch_ffn

        if config.dropless_block:
            # Shared or held experts, sigmoid routing: the served layer is
            # the only one there is (no capacity, no aux loss, no training).
            return dropless_moe(x, ffn_params, config)[0], zero
        return switch_ffn(x, ffn_params, config)
    raise ValueError(f"unknown ffn_type: {config.ffn_type!r}")


def _maybe_norm(x: Array, weight: Array, config: ModelConfig) -> Array:
    if config.remove_rmsnorm:
        return x
    if config.norm_type == "layernorm":
        return layernorm(x, weight, config.norm_eps)
    if config.norm_unit_offset:
        # The offset is added at float32: one plus a 16-bit weight is not a
        # 16-bit number.
        return rmsnorm(x, 1.0 + weight.astype(jnp.float32), config.norm_eps)
    return rmsnorm(x, weight, config.norm_eps)


def _patterned_block(
    x: Array,
    block_params: dict,
    config: ModelConfig,
    layer: int,
    rope_cos_sin: tuple[Array, Array] | None,
    positions: Array,
) -> Array:
    """One block of a config with per-layer attention kinds: window layers
    mask keys outside ``0 <= i - j < sliding_window`` (materialized scores:
    this is the plain forward the serving paths are checked against), full
    layers are causal and rotate q and k only under
    ``rope_on_full_layers``; the block is parallel (one norm, both branches
    from it: `ModelConfig` refuses such a config without ``parallel_block``)."""
    from bpe_transformer_tpu.ops.core import window_causal_mask

    window = config.layer_window(layer)
    attention_fn = None
    if window is not None:
        mask = window_causal_mask(x.shape[-2], window)
        attention_fn = lambda q, k, v: scaled_dot_product_attention(q, k, v, mask)

    def attend(h):
        scope = "attn_window" if window is not None else "attn_full"
        with jax.named_scope("block/attn"), jax.named_scope(scope):
            return multihead_self_attention(
                h,
                block_params["attn"]["q_proj"],
                block_params["attn"]["k_proj"],
                block_params["attn"]["v_proj"],
                block_params["attn"]["output_proj"],
                config.num_heads,
                num_kv_heads=config.num_kv_heads,
                positions=positions,
                rope_cos_sin=rope_cos_sin if config.layer_rope(layer) else None,
                causal=True,
                attention_fn=attention_fn,
            )

    h = _maybe_norm(x, block_params["ln1"], config)
    with jax.named_scope("block/ffn"):
        f, _ = _ffn(h, block_params["ffn"], config)
    return x + attend(h) + f


def _attention(
    x: Array,
    attn_params: dict,
    config: ModelConfig,
    rope_cos_sin: tuple[Array, Array] | None,
    positions: Array,
    attention_fn=None,
    entropy_tap: dict | None = None,
) -> Array:
    if attention_fn is None and config.attention_impl in ("auto", "flash"):
        from bpe_transformer_tpu.kernels.pallas.flash_attention import (
            attention_plan,
            flash_attention_for_config,
        )

        # "auto": the shape decides; "xla" leaves attention_fn None, the
        # materialized path inside multihead_self_attention.
        if attention_plan(config, x.shape[-2])[0] == "flash":
            attention_fn = lambda q, k, v: flash_attention_for_config(
                q, k, v, config
            )
    elif attention_fn is None and config.attention_impl == "flash_fused":
        from bpe_transformer_tpu.kernels.pallas.flash_attention import (
            flash_attention_for_config,
            flash_attention_with_rope,
        )
        from bpe_transformer_tpu.kernels.pallas.runtime import (
            flash_tiles,
            interpret_mode,
        )

        if rope_cos_sin is None:
            raise ValueError("attention_impl='flash_fused' requires RoPE enabled")
        if positions.ndim != 1:
            # Validate BEFORE the crossover branch so the contract doesn't
            # silently depend on sequence length.
            raise ValueError(
                "attention_impl='flash_fused' shares one cos/sin tile across "
                f"the batch, so positions must be 1-D, got {positions.shape}; "
                "use attention_impl='flash' for per-example positions"
            )
        if x.shape[-2] < config.flash_fused_min_seq:
            # Below the measured crossover the in-kernel RoPE recompute
            # costs more than it saves: dispatch the plain flash kernel
            # with RoPE applied outside (identical numerics).
            attention_fn = lambda q, k, v: flash_attention_for_config(
                q, k, v, config
            )
        else:
            # RoPE moves inside the kernel: gather the tables at the true
            # token positions here, hand MHA a rope-free path.
            cos, sin = rope_cos_sin
            cos_p, sin_p = cos[positions], sin[positions]
            rope_cos_sin = None
            block_q, block_k = flash_tiles(x.shape[-2])
            attention_fn = lambda q, k, v: flash_attention_with_rope(
                q, k, v, cos_p, sin_p, True, block_q, block_k, interpret_mode()
            )
    elif attention_fn is None and config.attention_impl != "xla":
        raise ValueError(f"unknown attention_impl: {config.attention_impl!r}")
    if entropy_tap is not None:
        # Dynamics introspection (telemetry.dynamics): record the mean
        # attention entropy of this layer from the q/k handed to the
        # attention callable — post-RoPE for the xla/flash paths, pre-RoPE
        # under flash_fused above the crossover (where RoPE lives inside
        # the kernel; the entropy is then of the un-rotated scores — an
        # indicator, not an exact value).  Sampled from batch element 0:
        # the tap re-materializes an (S, S) score matrix, and one example
        # is plenty for a collapse/uniformity diagnostic.
        from bpe_transformer_tpu.ops.core import (
            attention_entropy,
            causal_mask,
            scaled_dot_product_attention,
        )

        inner = attention_fn

        def tapped(q, k, v, _inner=inner):
            q_s = q[:1] if q.ndim > 3 else q
            k_s = k[:1] if k.ndim > 3 else k
            entropy_tap["attn_entropy"] = attention_entropy(q_s, k_s)
            if _inner is not None:
                return _inner(q, k, v)
            return scaled_dot_product_attention(
                q, k, v, causal_mask(q.shape[-2])
            )

        attention_fn = tapped
    return multihead_self_attention(
        x,
        attn_params["q_proj"],
        attn_params["k_proj"],
        attn_params["v_proj"],
        attn_params["output_proj"],
        config.num_heads,
        num_kv_heads=config.num_kv_heads,
        positions=positions,
        rope_cos_sin=rope_cos_sin,
        causal=True,
        attention_fn=attention_fn,
    )


def _attn_half(
    x: Array,
    block_params: dict,
    config: ModelConfig,
    rope_cos_sin: tuple[Array, Array] | None,
    positions: Array,
    attention_fn=None,
    entropy_tap: dict | None = None,
) -> Array:
    """The residual attention half of one block: ``x + attn(norm(x))``
    pre-norm, ``norm(x + attn(x))`` post-norm.

    The attention output is tagged :func:`jax.ad_checkpoint.checkpoint_name`
    (``"flash_attn_out"``) so remat policies can address it by name; under
    ``remat_policy="save_attn"`` this half runs OUTSIDE the checkpointed
    region, so the flash kernel's custom-vjp residuals (q/k/v, output,
    logsumexp — the FA-2 statistics the kernel already emits) stay saved
    and the O(S^2 d) attention never recomputes on the backward.
    """
    from jax.ad_checkpoint import checkpoint_name

    with jax.named_scope("block/attn"):
        h = x if config.use_post_norm else _maybe_norm(
            x, block_params["ln1"], config
        )
        attn_out = checkpoint_name(
            _attention(
                h, block_params["attn"], config, rope_cos_sin, positions,
                attention_fn, entropy_tap,
            ),
            "flash_attn_out",
        )
        if config.use_post_norm:
            return _maybe_norm(x + attn_out, block_params["ln1"], config)
        return x + attn_out


def _ffn_half(
    x: Array, block_params: dict, config: ModelConfig
) -> tuple[Array, Array]:
    """The residual FFN half of one block; returns ``(x, aux_loss)``.
    Cheap flops, heavy memory (the ``d_ff`` expansion) — the part
    ``remat_policy="save_attn"`` rematerializes."""
    with jax.named_scope("block/ffn"):
        if config.use_post_norm:
            f, aux = _ffn(x, block_params["ffn"], config)
            return _maybe_norm(x + f, block_params["ln2"], config), aux
        h = _maybe_norm(x, block_params["ln2"], config)
        f, aux = _ffn(h, block_params["ffn"], config)
        return x + f, aux


def transformer_block_aux(
    x: Array,
    block_params: dict,
    config: ModelConfig,
    rope_cos_sin: tuple[Array, Array] | None,
    positions: Array,
    attention_fn=None,
    entropy_tap: dict | None = None,
) -> tuple[Array, Array]:
    """One block; returns ``(x, aux_loss)`` (aux nonzero only for MoE FFNs).

    Pre-norm by default, post-norm under the ablation flag.
    ``attention_fn(q, k, v)`` overrides the config-selected attention (used
    by the sequence-parallel path to substitute ring attention).
    ``entropy_tap`` (a dict, dynamics introspection) receives this layer's
    mean attention entropy under ``"attn_entropy"``.
    """
    x = _attn_half(
        x, block_params, config, rope_cos_sin, positions, attention_fn,
        entropy_tap,
    )
    return _ffn_half(x, block_params, config)


def _block_save_attn(
    x: Array,
    block_params: dict,
    config: ModelConfig,
    rope_cos_sin: tuple[Array, Array] | None,
    positions: Array,
    attention_fn=None,
    entropy_tap: dict | None = None,
) -> tuple[Array, Array]:
    """One block under ``remat_policy="save_attn"`` (selective activation
    recomputation, Korthikanti et al. / arXiv:2302.01107 §recompute):

    * the attention half runs at the ambient level — the flash kernel's
      custom-vjp keeps its FA-2 residuals (q/k/v, tagged output,
      logsumexp), so the flops-dense attention is computed exactly once;
    * the FFN half (ln2 + FFN + residual) is ``jax.checkpoint``'d — its
      ``(B, T, d_ff)`` expansion intermediates, the block's memory bulk,
      are dropped and rematerialized on the backward.

    Peak activation memory lands strictly between ``full`` and ``none``;
    recompute flops strictly below ``full``/``dots_saveable`` (both re-run
    the opaque kernel).  Numerics are identical to the plain block.
    """
    x = _attn_half(
        x, block_params, config, rope_cos_sin, positions, attention_fn,
        entropy_tap,
    )
    tail = jax.checkpoint(_ffn_half, static_argnums=(2,))
    return tail(x, block_params, config)


def policy_block(
    config: ModelConfig, with_stats: bool = False, in_scan: bool = False
):
    """The remat-policy-wrapped block callable for ``config``.

    Dispatches on ``config.resolved_remat_policy`` (the graduated dial;
    ``remat: bool`` back-compat included):

    * ``none`` — the plain block;
    * ``full`` — ``jax.checkpoint`` around the whole block, save nothing;
    * ``dots_saveable`` — block checkpoint saving matmul outputs
      (``jax.checkpoint_policies.dots_saveable``);
    * ``save_attn`` — :func:`_block_save_attn` (remat lives INSIDE the
      block: wrapping it whole would drag the kernel back into the region).

    ``with_stats=True`` returns the dynamics-instrumented variant
    (``(x, aux, stats)`` instead of ``(x, aux)``).  ``in_scan=True`` drops
    the checkpoint CSE barrier (documented safe under ``lax.scan``, where
    the scan structure already prevents forward/backward merging) — used
    by ``scan_layers`` and the pipeline tick scan.

    Shared by ``forward_hidden``/``forward_hidden_stats`` and
    ``parallel/pp.py`` so the policy semantics cannot drift between the
    single-program and pipelined forwards.
    """
    policy_name = config.resolved_remat_policy
    if with_stats:
        base = _block_with_stats
    elif policy_name == "save_attn":
        base = _block_save_attn
    else:
        base = transformer_block_aux
    if policy_name in ("none", "save_attn"):
        # save_attn self-checkpoints its FFN tail (the stats variant
        # dispatches internally); nothing to wrap here.
        return base
    pol = (
        jax.checkpoint_policies.dots_saveable
        if policy_name == "dots_saveable"
        else None
    )
    return jax.checkpoint(
        base, static_argnums=(2, 5), policy=pol, prevent_cse=not in_scan
    )


def transformer_block(
    x: Array,
    block_params: dict,
    config: ModelConfig,
    rope_cos_sin: tuple[Array, Array] | None,
    positions: Array,
    attention_fn=None,
) -> Array:
    """One block (aux-loss-free view of :func:`transformer_block_aux`)."""
    return transformer_block_aux(
        x, block_params, config, rope_cos_sin, positions, attention_fn
    )[0]


def _forward_prologue(
    params: Params,
    token_ids: Array,
    config: ModelConfig,
    positions: Array | None,
):
    """Shared entry of the forward passes: seq validation, default
    positions, mixed-precision weight cast, embedding lookup, RoPE tables.
    Returns ``(x, compute_params, rope_cos_sin, positions)``."""
    seq_len = token_ids.shape[-1]
    if seq_len > config.context_length:
        raise ValueError(
            f"sequence length {seq_len} exceeds context_length "
            f"{config.context_length} (RoPE tables are sized to the context)"
        )
    if positions is None:
        positions = jnp.arange(seq_len)

    act_dtype = jnp.dtype(config.activation_dtype)
    # Mixed precision: master params may be float32 while compute runs in
    # ``activation_dtype`` — cast the weights entering matmuls so bf16
    # actually reaches the MXU.  Norm weights stay in the compute dtype too;
    # rmsnorm internally accumulates in float32 either way.
    compute_params = params
    if act_dtype != jnp.float32:
        compute_params = jax.tree_util.tree_map(
            lambda p: p.astype(act_dtype), params
        )

    with jax.named_scope("embed"):
        x = embedding(
            compute_params["token_embeddings"], token_ids
        ).astype(act_dtype)
        if config.embedding_multiplier != 1.0:
            x = x * config.embedding_multiplier

    rope_cos_sin = None
    if not config.remove_rope:
        cos, sin = rope_tables(
            config.d_head, config.context_length, config.rope_theta
        )
        rope_cos_sin = (cos.astype(act_dtype), sin.astype(act_dtype))
    return x, compute_params, rope_cos_sin, positions


def forward_hidden(
    params: Params,
    token_ids: Array,
    config: ModelConfig,
    positions: Array | None = None,
    attention_fn=None,
) -> tuple[Array, Array]:
    """Final-norm hidden states ``(batch, seq, d_model)`` + summed MoE aux.

    Everything in :func:`forward` except the LM head — the seam for
    memory-lean losses that stream the vocab projection in chunks instead of
    materializing ``(batch, seq, vocab)`` logits.
    """
    x, compute_params, rope_cos_sin, positions = _forward_prologue(
        params, token_ids, config, positions
    )

    aux_total = jnp.zeros((), jnp.float32)
    if config.dropless_block:
        if attention_fn is not None:
            raise ValueError(
                "an attention_fn override replaces every layer's attention; "
                "this config's layers differ in kind"
            )
    if config.double_layer:
        # The served arrangement is the only one there is; latent attention
        # runs over the sequence's own rows (`models/mla.py`).
        from bpe_transformer_tpu.models.decode import _block_apply
        from bpe_transformer_tpu.models.mla import self_attention

        for block_params in compute_params["layers"]:
            x = _block_apply(
                x, block_params, config,
                lambda h, sub, attn=block_params["attn"]: self_attention(
                    h, attn[sub], positions, config
                )[0],
            )
    elif config.eva_block:
        # The served arrangement again, over the whole sequence with the
        # two masks written out (`models/eva.py`).
        from bpe_transformer_tpu.models.decode import _block_apply
        from bpe_transformer_tpu.models.eva import self_attention

        for block_params in compute_params["layers"]:
            x = _block_apply(
                x, block_params, config,
                lambda h, attn=block_params["attn"]: self_attention(
                    h, attn, positions, config
                ),
            )
    elif config.hybrid_block:
        # The served arrangement again; a layer's mixer is the state-space
        # scan over the whole sequence, latent attention, or plain causal
        # attention under the config's own score multiplier.
        from bpe_transformer_tpu.models.decode import _block_apply

        for layer, block_params in enumerate(compute_params["layers"]):
            x = _block_apply(
                x, block_params, config,
                lambda h, p=block_params, layer=layer: _hybrid_mixer(
                    h, p, config, positions, layer
                ),
            )
    elif config.dropless_block:
        for layer, block_params in enumerate(compute_params["layers"]):
            x = _patterned_block(
                x, block_params, config, layer, rope_cos_sin, positions
            )
    elif config.scan_layers:
        x, aux_total = _scan_blocks(
            x, aux_total, compute_params["layers"], config, rope_cos_sin,
            positions, attention_fn,
        )
    else:
        block = policy_block(config)
        for block_params in compute_params["layers"]:
            x, aux = block(
                x, block_params, config, rope_cos_sin, positions, attention_fn
            )
            aux_total = aux_total + aux

    return _final_norm(x, compute_params, config), aux_total


def _hybrid_mixer(
    h: Array, block_params: dict, config: ModelConfig, positions, layer: int = 0
) -> Array:
    """A hybrid block's mixer over a whole sequence from its start."""
    if "ssm" in block_params:
        from bpe_transformer_tpu.models.ssm import mamba2

        return mamba2(h, block_params["ssm"], config)[0]
    if config.attention_kind == "mla":
        # Latent attention over the sequence's own rows (`models/mla.py`).
        from bpe_transformer_tpu.models.mla import self_attention

        return self_attention(h, block_params["attn"], positions, config)[0]
    if config.has_window_layers:
        # Window and full layers by kind, as the paged forward projects,
        # rotates and attends them, the whole sequence its own chain.
        from bpe_transformer_tpu.kernels.pallas.sink_attention import (
            xla_sink_attention,
        )
        from bpe_transformer_tpu.models.decode import _project_qkv, _rope_qk

        attn = block_params["attn"]
        q, k, v = _project_qkv(h, attn, config, layer)
        q, k = _rope_qk(q, k, positions, config, layer)
        q, k, v = (jnp.swapaxes(x, -3, -2) for x in (q, k, v))
        lead = q.shape[:-3]
        q, k, v = (x.reshape(-1, *x.shape[-3:]) for x in (q, k, v))
        scope = "attn_full" if config.layer_window(layer) is None else "attn_window"
        with jax.named_scope(scope):
            att = xla_sink_attention(
                q, k, v, jnp.broadcast_to(positions, q.shape[:2]),
                window=config.layer_window(layer), sink=attn.get("sink"),
            )
        return linear(att.reshape(*lead, att.shape[1], -1), attn["output_proj"])
    def attention_fn(q, k, v):
        # Materialized causal scores under the config's own multiplier.
        scores = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32)
        scores = jnp.where(
            jnp.tril(jnp.ones(scores.shape[-2:], bool)),
            scores * config.attention_scale, -jnp.inf,
        )
        weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("...qk,...kv->...qv", weights, v)

    attn = block_params["attn"]
    return multihead_self_attention(
        h, attn["q_proj"], attn["k_proj"], attn["v_proj"], attn["output_proj"],
        config.num_heads, num_kv_heads=config.num_kv_heads, positions=positions,
        rope_cos_sin=None, causal=True, attention_fn=attention_fn,
    )


def _final_norm(x: Array, compute_params: Params, config: ModelConfig) -> Array:
    with jax.named_scope("final_norm"):
        x = _maybe_norm(x, compute_params["ln_final"], config)
        # A float32 residual stream ends here.
        return x.astype(config.activation_dtype) if config.eva_block else x


def _scan_blocks(
    x: Array,
    aux_total: Array,
    layers: list,
    config: ModelConfig,
    rope_cos_sin,
    positions: Array,
    attention_fn=None,
    with_stats: bool = False,
):
    """Run the layer stack as ONE ``lax.scan`` over stacked block params
    (``config.scan_layers``): the jaxpr contains a single
    (policy-rematerialized) block body whatever ``num_layers`` is, so
    compile time is O(1) in depth — the pjit-era trainer formulation
    (arXiv:2204.06514).

    The at-rest pytree keeps its per-layer list layout; the stack happens
    here, inside the traced step.  Under bf16 activation configs the
    prologue's mixed-precision cast already copies every leaf, so stacking
    adds no extra HBM beyond layout; f32 configs pay one transient stacked
    copy of the block params (and XLA's gradient of the stack is the
    per-layer slice, so grads land back in the list layout unchanged).

    ``with_stats=True`` scans the dynamics-instrumented block and returns
    ``(x, aux_total, act_stats)`` with the per-layer stats stacked by the
    scan itself.
    """
    block = policy_block(config, with_stats=with_stats, in_scan=True)
    stacked = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *layers)

    if with_stats:
        def body(carry, layer_params):
            h, aux = carry
            h, a, stats = block(
                h, layer_params, config, rope_cos_sin, positions, attention_fn
            )
            return (h, aux + a), stats

        (x, aux_total), act_stats = jax.lax.scan(
            body, (x, aux_total), stacked
        )
        return x, aux_total, act_stats

    def body(carry, layer_params):
        h, aux = carry
        h, a = block(
            h, layer_params, config, rope_cos_sin, positions, attention_fn
        )
        return (h, aux + a), None

    (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), stacked)
    return x, aux_total


def _block_with_stats(
    x: Array,
    block_params: dict,
    config: ModelConfig,
    rope_cos_sin: tuple[Array, Array] | None,
    positions: Array,
    attention_fn=None,
) -> tuple[Array, Array, dict]:
    """One block + its activation statistics (dynamics introspection).

    The stats are part of the RETURN value (not a side channel), so the
    function stays pure and composes with ``jax.checkpoint`` — under remat
    the tap simply recomputes with the block in the backward pass.
    Dispatches the ``save_attn`` block structure internally so the stats
    variant honors the same remat policy as the plain forward.
    """
    tap: dict = {}
    base = (
        _block_save_attn
        if config.resolved_remat_policy == "save_attn"
        else transformer_block_aux
    )
    x, aux = base(
        x, block_params, config, rope_cos_sin, positions, attention_fn, tap
    )
    x32 = x.astype(jnp.float32)
    stats = {
        "rms": jnp.sqrt(jnp.mean(jnp.square(x32))),
        "absmax": jnp.max(jnp.abs(x32)),
        "nonfinite": jnp.sum(~jnp.isfinite(x)).astype(jnp.int32),
        "attn_entropy": tap.get("attn_entropy", jnp.zeros((), jnp.float32)),
    }
    return x, aux, stats


def forward_hidden_stats(
    params: Params,
    token_ids: Array,
    config: ModelConfig,
    positions: Array | None = None,
    attention_fn=None,
) -> tuple[Array, Array, dict]:
    """:func:`forward_hidden` + per-block activation statistics.

    Returns ``(hidden, aux_total, act_stats)`` where ``act_stats`` stacks
    one scalar per layer: ``{"rms": (L,), "absmax": (L,), "nonfinite":
    (L,) i32, "attn_entropy": (L,)}`` — block-output RMS/absmax/non-finite
    counts plus the mean attention entropy (sampled from batch element 0).
    The stats are ordinary traced scalars, so the dynamics-enabled train
    step gets them from the SAME forward it differentiates — no second
    pass, no host syncs (`telemetry.dynamics`).  Honors the graduated
    ``config.remat_policy`` (and ``scan_layers``) like
    :func:`forward_hidden`.
    """
    x, compute_params, rope_cos_sin, positions = _forward_prologue(
        params, token_ids, config, positions
    )

    aux_total = jnp.zeros((), jnp.float32)
    if config.scan_layers:
        x, aux_total, act_stats = _scan_blocks(
            x, aux_total, compute_params["layers"], config, rope_cos_sin,
            positions, attention_fn, with_stats=True,
        )
        return _final_norm(x, compute_params, config), aux_total, act_stats

    block = policy_block(config, with_stats=True)
    per_layer: list[dict] = []
    for block_params in compute_params["layers"]:
        x, aux, stats = block(
            x, block_params, config, rope_cos_sin, positions, attention_fn
        )
        aux_total = aux_total + aux
        per_layer.append(stats)
    act_stats = {
        key: jnp.stack([stats[key] for stats in per_layer])
        for key in per_layer[0]
    }

    return _final_norm(x, compute_params, config), aux_total, act_stats


def forward(
    params: Params,
    token_ids: Array,
    config: ModelConfig,
    positions: Array | None = None,
    attention_fn=None,
    return_aux: bool = False,
) -> Array:
    """Logits ``(batch, seq, vocab)`` for ``token_ids (batch, seq)``.

    ``seq`` may be anything up to ``config.context_length`` (truncated-input
    behavior pinned by `test_transformer_lm_truncated_input`).

    ``return_aux=True`` additionally returns the summed auxiliary
    (load-balance) loss of MoE layers: ``(logits, aux)``.
    """
    x, aux_total = forward_hidden(params, token_ids, config, positions, attention_fn)
    # LM head: activation-dtype matmul, f32 accumulation (ops/core.py
    # head_logits — f32 logits for stable loss/sampling at full MXU rate).
    logits = head_logits(x, lm_head_weight(params, config))
    if config.logits_scaling != 1.0:
        logits = logits / config.logits_scaling
    if return_aux:
        return logits, aux_total
    return logits


# ------------------------------------------------- torch state-dict interop


def params_from_state_dict(
    state_dict: dict, num_layers: int, tied: bool = False
) -> Params:
    """Build the param pytree from flat torch-style keys (numpy/jnp values).

    Key schema: `adapters.py:307-353` (``token_embeddings.weight``,
    ``layers.{i}.attn.{q,k,v,output}_proj.weight``, ``layers.{i}.ln{1,2}.weight``,
    ``layers.{i}.ffn.w{1,2,3}.weight``, ``ln_final.weight``, ``lm_head.weight``).

    ``tied=True`` loads a ``tie_embeddings`` export (no ``lm_head.weight``);
    by default a missing head key fails fast here rather than as a distant
    KeyError at the first forward.
    """

    def get(key):
        return jnp.asarray(state_dict[key])

    head = {} if tied else {"lm_head": get("lm_head.weight")}

    layers = []
    for i in range(num_layers):
        p = f"layers.{i}."
        layers.append(
            {
                "attn": {
                    "q_proj": get(p + "attn.q_proj.weight"),
                    "k_proj": get(p + "attn.k_proj.weight"),
                    "v_proj": get(p + "attn.v_proj.weight"),
                    "output_proj": get(p + "attn.output_proj.weight"),
                },
                "ln1": get(p + "ln1.weight"),
                "ln2": get(p + "ln2.weight"),
                "ffn": {
                    "w1": get(p + "ffn.w1.weight"),
                    "w2": get(p + "ffn.w2.weight"),
                    "w3": get(p + "ffn.w3.weight"),
                },
            }
        )
    return {
        "token_embeddings": get("token_embeddings.weight"),
        "layers": layers,
        "ln_final": get("ln_final.weight"),
        **head,
    }


def state_dict_from_params(params: Params) -> dict:
    """Flatten the param pytree back to the torch-style key schema."""
    out = {
        "token_embeddings.weight": params["token_embeddings"],
        "ln_final.weight": params["ln_final"],
    }
    if "lm_head" in params:  # absent under tie_embeddings
        out["lm_head.weight"] = params["lm_head"]
    for i, layer in enumerate(params["layers"]):
        p = f"layers.{i}."
        out[p + "attn.q_proj.weight"] = layer["attn"]["q_proj"]
        out[p + "attn.k_proj.weight"] = layer["attn"]["k_proj"]
        out[p + "attn.v_proj.weight"] = layer["attn"]["v_proj"]
        out[p + "attn.output_proj.weight"] = layer["attn"]["output_proj"]
        out[p + "ln1.weight"] = layer["ln1"]
        out[p + "ln2.weight"] = layer["ln2"]
        out[p + "ffn.w1.weight"] = layer["ffn"]["w1"]
        out[p + "ffn.w2.weight"] = layer["ffn"]["w2"]
        out[p + "ffn.w3.weight"] = layer["ffn"]["w3"]
    return out
