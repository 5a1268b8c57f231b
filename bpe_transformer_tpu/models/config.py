"""Typed model/training configuration.

The model schema is a superset of the reference's JSON config fixture
(`/root/reference/tests/fixtures/ts_tests/model_config.json:1-13`), including
its ablation flags, so reference configs load unchanged via
:meth:`ModelConfig.from_json`.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any


#: What a letter of ``ModelConfig.layer_pattern`` puts in a layer: ``(its
#: mixer, whether the feed-forward part follows)``.
LAYER_KINDS = {
    "M": ("ssm", False), "*": ("attn", False), "E": (None, True),
    "m": ("ssm", True), "a": ("attn", True), "w": ("attn", True),
    "A": ("attn", True),
}
#: The letter whose attention sees a sliding window (every other attention
#: layer sees everything before it), and the one whose feed-forward part is
#: the dense SwiGLU of ``d_ff`` whatever ``ffn_type`` says of the other
#: layers (a leading dense layer before expert layers).
WINDOW_KIND = "w"
DENSE_FFN_KIND = "A"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    context_length: int
    d_model: int
    num_layers: int
    num_heads: int
    d_ff: int
    rope_theta: float = 10000.0
    #: Grouped-query attention: K/V heads (None -> num_heads, i.e. MHA).
    #: Must divide num_heads; shrinks KV projections and the decode cache
    #: by num_heads // num_kv_heads.
    num_kv_heads: int | None = None
    #: Tie the LM head to the token embedding matrix (no separate lm_head
    #: parameter; the reference contract's untied schema stays the default).
    tie_embeddings: bool = False
    # Ablation flags (reference schema; defaults = the tested architecture).
    remove_rmsnorm: bool = False
    use_post_norm: bool = False
    remove_rope: bool = False
    # None -> SwiGLU; "silu"/"gelu" -> 2-matrix FFN; "moe" -> routed experts
    ffn_type: str | None = None
    # MoE knobs (used when ffn_type == "moe").
    n_experts: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    #: Experts per token: 1 = Switch routing, 2 = GShard-style top-2 (gates
    #: renormalized over the chosen experts).
    router_top_k: int = 1
    #: Expert dispatch formulation.  "einsum" builds dense one-hot
    #: dispatch/combine tensors (GShard-style; under an expert-sharded mesh
    #: GSPMD turns them into all-to-alls).  "gather" routes tokens to expert
    #: slots by index (identical assignments/gates) — the dense einsums cost
    #: 2·n·e·cap·d flops EACH, which at bench shapes exceeds the expert FFN
    #: compute itself, while gathers move only e·cap·d values.
    moe_dispatch: str = "einsum"
    # TPU execution knobs (not part of the reference schema).
    activation_dtype: str = "float32"  # "bfloat16" for the perf path
    #: DEPRECATED (PR 13): the all-or-nothing remat switch.  ``remat=True``
    #: is accepted as an alias for ``remat_policy="full"`` so old configs,
    #: checkpoints, and bench captures keep loading; new code should set
    #: ``remat_policy``.  Setting BOTH (``remat=True`` with a non-full
    #: ``remat_policy``) is a contradiction and fails validation.
    remat: bool = False
    #: Graduated activation-rematerialization policy for the backward pass
    #: (the training-MFU memory/flops dial; `models/transformer.py`):
    #:
    #: * ``"none"``  — save every intermediate (max memory, zero recompute);
    #: * ``"full"``  — ``jax.checkpoint`` each block saving only its input
    #:   (min memory; the whole block, flash-attention kernel included,
    #:   recomputes on the backward — the old ``remat=True``);
    #: * ``"dots_saveable"`` — block remat that SAVES matmul outputs
    #:   (``jax.checkpoint_policies.dots_saveable``): only cheap
    #:   elementwise/norm work recomputes, but the Pallas flash-attention
    #:   kernel is an opaque custom-vjp call the policy cannot see inside,
    #:   so its forward still re-runs;
    #: * ``"save_attn"`` — selective recompute (Korthikanti et al.): the
    #:   flash-attention call runs OUTSIDE the remat region, so the
    #:   backward reuses the FA-2 residuals the kernel already emits
    #:   (q/k/v, output, logsumexp — tagged ``checkpoint_name``) and the
    #:   O(S^2 d) attention never recomputes, while the memory-heavy,
    #:   cheap-flops FFN tail (ln2 + FFN + residual) rematerializes.
    #:   Peak HBM sits strictly below ``none``; recompute flops strictly
    #:   below ``full``/``dots_saveable``.
    remat_policy: str = "none"
    #: Stack the per-block parameters and run the layer stack as ONE
    #: policy-rematerialized ``lax.scan`` over blocks (training forward
    #: only; decode keeps its per-layer programs).  Compile time becomes
    #: O(1) in depth — the pjit-era trainer formulation (arXiv:2204.06514).
    #: The at-rest param pytree is unchanged (checkpoints, state-dict
    #: interop, ZeRO-1 flat layout all untouched); the stack happens inside
    #: the traced step and rides the mixed-precision cast's existing copy
    #: on bf16 configs.  Requires num_layers >= 1 and homogeneous blocks
    #: (always true for this architecture).
    scan_layers: bool = False
    #: Causal self-attention of the training forward, eval and the dense
    #: engine's prefill.  ``"auto"`` (the default; presets set nothing): the
    #: shape decides — `kernels.pallas.runtime.attention_path` picks the
    #: Pallas flash kernel where materialized S x S scores cost more than
    #: they save (on the TPU, S >= 512 that 128-lane tiles divide, d_head
    #: >= 64, bfloat16 and float32 alike), the materialized XLA path
    #: elsewhere, and `runtime.flash_tiles` picks the kernel's tiles.  A
    #: program that XLA's SPMD partitioner splits (the GSPMD steps, eval on
    #: a sharded batch) cannot hold a Mosaic kernel and takes "auto" as
    #: "xla" (`parallel.train_step.partitioned_config`).  The other values
    #: FORCE a path (tests, benchmarks, `parallel/sp.py`, which only runs
    #: ring-flash when told to): ``"xla"`` (materialized) | ``"flash"``
    #: (Pallas) | ``"flash_fused"`` (RoPE in-kernel).
    attention_impl: str = "auto"
    # "xla" | "pallas" (fused SwiGLU kernel; swiglu FFNs only)
    ffn_impl: str = "xla"
    #: Decode-step attention against the KV cache.  ``"auto"``, the
    #: default: the paged serving engine's one-row tick over a dense block
    #: pool takes what `kernels.pallas.runtime.decode_attention_path`
    #: chooses from the shape and the backend - on the TPU the paged-native
    #: kernel, which reads the blocks the slots hold straight out of the
    #: pool, elsewhere gathered rows under XLA; the dense cache's
    #: `decode_step` takes "xla".  The other values FORCE a path (parity
    #: tests, benchmarks): ``"xla"`` (materialized scores) | ``"paged"``
    #: (the paged-native kernel; the dense cache has no block table and
    #: treats it as "pallas") | ``"pallas"`` (the contiguous flash-decoding
    #: kernel of the dense cache, kernels/pallas/decode_attention.py; a
    #: block pool has no such kernel and takes it as "auto").
    #: Inference-only - the training attention path is attention_impl.
    decode_attention_impl: str = "auto"
    #: q/k tile of the ring-flash schedules (`parallel/sp.py`, the only
    #: reader: its per-device shards are not the sequence, and tiny in
    #: tests).  Every other flash call takes its tiles from the shape
    #: (`kernels.pallas.runtime.flash_tiles`).
    flash_block_size: int = 256
    #: attention_impl="flash_fused" auto-falls-back to the plain flash
    #: kernel (RoPE outside) below this sequence length: the in-kernel RoPE
    #: rematerialization only pays off once the sequence is long enough
    #: (builder capture benchmarks/captures/attention.jsonl; no ledger
    #: number yet).  Set to 0 to force the fused kernel at every length.
    flash_fused_min_seq: int = 2048
    # Sequence-chunked LM loss: cap peak logits memory at
    # O(batch * chunk * vocab) instead of O(batch * seq * vocab).
    # None -> AUTO: bfloat16 training configs default to chunking (the f32
    # (B, T, V) logits buffer is exactly the peak-memory spike the remat
    # policy fights; see ``loss_chunk``), float32 configs materialize full
    # logits.  0 -> force full logits.  N -> chunk N (must divide the
    # sequence; `ops.losses.lm_loss` falls back when it doesn't).
    loss_chunk_size: int | None = None
    # ---- per-layer attention kinds, the parallel block and the dropless
    # expert layer (the Cohere2-MoE family; every default is the block
    # above, so existing configs and checkpoints load unchanged) ----------
    #: Width of one attention head where it is not ``d_model // num_heads``
    #: (128 heads x 128 over a hidden size of 4,096).
    head_dim: int | None = None
    #: Sliding-window attention: key j is visible to query i iff
    #: ``0 <= i - j < sliding_window``.  Which layers are window layers is
    #: `layer_kinds`' to say (``"w"``): a ``layer_pattern`` names
    #: them one by one, or a period does - layers ``l`` with ``(l + 1) %
    #: sliding_window_pattern != 0`` are window layers and every
    #: ``sliding_window_pattern``-th layer attends to everything; with the
    #: default pattern of 1 every layer is a full layer.
    sliding_window: int | None = None
    sliding_window_pattern: int = 1
    #: Whether full-attention layers rotate q and k.  False: no positional
    #: transform at all on them (window layers keep RoPE).
    rope_on_full_layers: bool = True
    #: "rmsnorm" | "layernorm" (mean-subtracting, no bias).
    norm_type: str = "rmsnorm"
    #: What every norm adds to the mean square (or the variance) under the
    #: root: the block's norms, the final norm, latent attention's norms of
    #: its latents and a state-space mixer's gated norm.
    norm_eps: float = 1e-5
    #: One norm a block and both branches from it:
    #: ``x + attn(norm(x)) + ffn(norm(x))``.
    parallel_block: bool = False
    #: Router scores of the MoE layer: "softmax" over all experts (Switch /
    #: GShard) or "sigmoid" per expert; with ``router_top_k > 1`` the chosen
    #: gates are renormalized to sum to one either way.
    moe_router: str = "softmax"
    #: Shared experts every token passes through (same SwiGLU width as a
    #: routed expert); their outputs are averaged and added to the routed
    #: part.
    n_shared_experts: int = 0
    #: Expert parallelism without the exchange: this process holds experts
    #: ``expert_offset .. expert_offset + experts_held - 1`` of the
    #: ``n_experts`` the router scores, and computes their part of the
    #: result only (None = all of them).  Serving only.
    experts_held: int | None = None
    expert_offset: int = 0
    # ---- latent attention, the shortcut-connected double layer and
    # zero-compute experts (the LongCat-Flash family; every default is the
    # block above) ---------------------------------------------------------
    #: "mha": q/k/v heads (``num_kv_heads`` makes it GQA).  "mla": latent
    #: attention - queries through a ``q_lora_rank`` bottleneck (0: a
    #: full-rank query projection, no bottleneck), keys and
    #: values expanded from one cached row a position of ``kv_lora_rank``
    #: latent values and one ``qk_rope_head_dim`` rotated key shared by all
    #: heads; a head's query and key are ``qk_nope_head_dim +
    #: qk_rope_head_dim`` wide (only the latter rotated), its value
    #: ``v_head_dim`` (`models/mla.py`).  Latent attention comes in two
    #: blocks: without a ``layer_pattern`` in the shortcut-connected double
    #: layer (`double_layer`), under a ``layer_pattern`` of ``"a"`` and
    #: ``"A"`` layers in the sequential pre-norm block (`hybrid_block`), one
    #: attention sublayer a layer.
    attention_kind: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: Scale the normalised query / key-value latents by ``sqrt(d_model /
    #: rank)`` (`q_lora_scale`, `kv_lora_scale`).
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    #: Latent attention's positions stretched past the length the model was
    #: trained at (YaRN as the DeepSeek-V2 family applies it, ``rope_scaling
    #: .type = "deepseek_yarn"``; `ops/rope.yarn_inv_freq`): a factor of 1
    #: is no scaling.  Pairs that turn more than ``yarn_beta_fast`` times in
    #: ``yarn_original_context`` positions keep their frequency, pairs that
    #: turn less than ``yarn_beta_slow`` times have it divided by
    #: ``yarn_factor``, a line between; cos and sin are multiplied by
    #: ``m(yarn_mscale) / m(yarn_mscale_all_dim)`` and the softmax scale by
    #: ``m(yarn_mscale_all_dim) ** 2``, ``m(s) = 0.1 s ln(factor) + 1``
    #: (`models/mla.softmax_scale`).
    yarn_factor: float = 1.0
    yarn_original_context: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    #: Width of one routed expert where it is not ``d_ff``.
    expert_d_ff: int | None = None
    #: Router outputs past ``n_experts`` that name no weights: a token's
    #: assignment to one returns ``gate * input`` and costs nothing.
    n_zero_experts: int = 0
    #: False: the chosen gates are the scores as they are (times
    #: ``routed_scaling_factor``), not renormalised to sum to one.
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    #: The router's tree holds ``router_bias`` (router outputs,), added to
    #: the scores for the choice of experts and not to the gates.
    router_bias: bool = False
    # ---- layers that differ in kind by a pattern - state-space mixers
    # beside attention layers, each with the feed-forward part or one
    # sublayer a layer - the four multipliers, a shared expert of its own
    # width and an expert's activation (every default is the block above) --
    #: The kind of every layer, a letter a layer (`LAYER_KINDS`, `layer_kinds`):
    #: ``"M"`` a Mamba-2 state-space mixer alone (`models/ssm.py`: no cache
    #: of positions but one recurrent state a sequence), ``"*"`` attention
    #: alone, ``"E"`` the feed-forward part alone (no mixer, so no cache of
    #: any kind) - layers of one pre-norm sublayer each - and ``"m"`` / ``"a"``
    #: a state-space / attention mixer followed by the feed-forward part;
    #: ``"w"`` is ``"a"`` under the sliding window, and ``"A"`` is ``"a"``
    #: with the dense SwiGLU of ``d_ff`` as its feed-forward part where the
    #: others' is the expert layer (`WINDOW_KIND`, `DENSE_FFN_KIND`).
    #: None, the default, with a period of 0: every layer attends and feeds
    #: forward.  The block is the sequential pre-norm one (`hybrid_block`).
    layer_pattern: str | None = None
    #: The periodic pattern in two numbers: with a period ``p > 0`` layer
    #: ``i`` is ``"a"`` where ``i % p == attn_layer_offset`` and ``"m"``
    #: elsewhere (`layer_kinds` spells it out; not beside ``layer_pattern``).
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    #: The Mamba-2 mixer: ``ssm_heads`` heads of ``ssm_head_dim`` channels
    #: (inner width their product), a state of ``ssm_state`` values a
    #: channel, ``B`` and ``C`` in ``ssm_groups`` groups (head ``h`` reads
    #: those of group ``h // (ssm_heads // ssm_groups)``, and the gated norm
    #: runs over each group's channels apart; 1: shared by all heads), a
    #: depthwise causal convolution ``ssm_conv`` wide, the scan in chunks of
    #: ``ssm_chunk`` positions.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    #: ``x_0 = embedding_multiplier * E[token]``; every branch joins the
    #: stream times ``residual_multiplier``; attention scores are ``(q . k)
    #: * attention_multiplier`` (None: ``d_head ** -0.5``); logits are
    #: divided by ``logits_scaling``.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None
    logits_scaling: float = 1.0
    #: Width of a shared expert where it is not a routed expert's.
    shared_d_ff: int | None = None
    # ---- attention layers that differ in more than their mask, by kind
    # (window or full; a ``layer_pattern`` with window layers, every default
    # the block above): K/V heads, the rotation's base, a sink in the
    # softmax; and for both kinds a value narrower than the key, a rotation
    # of a part of the head, a scale on the values ------------------------
    #: K/V heads of the window layers where they are not ``num_kv_heads``.
    window_kv_heads: int | None = None
    #: RoPE's base in the window layers where it is not ``rope_theta``.
    window_rope_theta: float | None = None
    # Outside latent attention ``v_head_dim`` (above) is the width of a
    # head's value where it is not the key's (0: ``d_head``), and
    # ``qk_rope_head_dim`` the leading part of a head that RoPE rotates (0:
    # all of it; the rest passes unrotated).
    #: A learned scalar a query head that joins the softmax's denominator
    #: and carries no value (the tree's ``attn["sink"]``, float32): ``p_ij
    #: = exp(s_ij - m) / (sum_j' exp(s_ij' - m) + exp(b_h - m))`` - in the
    #: window layers; the full layers have none.
    sink_on_window_layers: bool = False
    #: The values are multiplied by this before the weighted sum.
    attention_value_scale: float = 1.0
    #: What an expert of the dropless layer computes, routed and shared
    #: alike: ``"swiglu"`` ``w2 (silu(w1 u) * w3 u)``, three matrices, or
    #: ``"relu2"`` ``w2 relu(w1 u)^2``, two (its tree has no ``w3``).
    expert_activation: str = "swiglu"
    # ---- chunked linear attention over a window, the unit-offset norm and
    # several prediction heads (the EvaByte family; every default is the
    # block above) ---------------------------------------------------------
    #: ``attention_kind="eva"`` (`models/eva.py`): a query attends exactly
    #: to the keys of its own window of ``eva_window`` positions up to
    #: itself, and to one summary row for every chunk of ``eva_chunk``
    #: positions of every earlier window, in one softmax.  The window is a
    #: multiple of the chunk and the context of the window.
    eva_window: int = 0
    eva_chunk: int = 0
    #: ``norm(x) = x / rms(x) * (1 + g)``: the norms' weights are offsets
    #: from one.
    norm_unit_offset: bool = False
    #: Prediction heads: the head's width is ``vocab_size *
    #: num_pred_heads``, head-major; head 0 is the next token and what
    #: generation samples, head ``j`` the token ``j`` further on.
    num_pred_heads: int = 1
    # Sequence-parallel ring attention: sub-chunk each visiting K/V shard
    # so per-device score memory is O(S_local * chunk) instead of
    # O(S_local^2).  Must divide the local shard length.  None -> one full
    # block per ring step.
    ring_kv_chunk: int | None = None

    #: Default sequence chunk of the AUTO loss-chunking policy (bf16
    #: configs; clamped to the context length).
    AUTO_LOSS_CHUNK = 256

    @property
    def d_head(self) -> int:
        """Width of a head's query and key (the softmax scale's)."""
        if self.attention_kind == "mla":
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.num_heads

    @property
    def rope_dim(self) -> int:
        """Width of what RoPE rotates: the whole head, its leading
        ``qk_rope_head_dim`` values, or latent attention's rope part."""
        return self.qk_rope_head_dim or self.d_head

    @property
    def value_dim(self) -> int:
        """Width of a head's value (and of its part of the output)."""
        return self.v_head_dim or self.d_head

    @property
    def latent_width(self) -> int:
        """Values latent attention caches a position and sublayer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def q_lora_scale(self) -> float:
        return (self.d_model / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def kv_lora_scale(self) -> float:
        return (self.d_model / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0

    @property
    def eva_block(self) -> bool:
        """Chunked linear attention (`models/eva.py`): the sequential
        pre-norm block with the residual stream carried in float32 over
        activations of ``activation_dtype`` (the published
        ``fp32_skip_add``; `models/decode._block_apply`).  Derived, as
        `double_layer` is: the one configuration with this attention adds
        so."""
        return self.attention_kind == "eva"

    @property
    def eva_chunks_per_window(self) -> int:
        return self.eva_window // self.eva_chunk

    @property
    def head_width(self) -> int:
        """Outputs of the head: every prediction head's ``vocab_size``."""
        return self.vocab_size * self.num_pred_heads

    @property
    def double_layer(self) -> bool:
        """The shortcut-connected double layer: two attention sublayers, two
        dense SwiGLU FFNs of width ``d_ff`` and one expert layer (``ffn_type
        = "moe"``) in a layer; the expert layer reads the first sublayer's
        normalised stream and joins at the layer's end
        (`models/decode._block_apply`).  Derived, and no field: it is
        latent attention outside a ``layer_pattern``.  The one other block
        with latent attention is the sequential one, which names its layers
        by a pattern anyway (a leading dense layer is a letter of it), so
        the pattern's absence says everything a flag would, and a
        configuration file written before the second block - attention_kind
        "mla" and nothing more - goes on meaning this layer."""
        return self.attention_kind == "mla" and not self.hybrid_block

    @property
    def attn_sublayers(self) -> int:
        """Attention sublayers, and so cache arrays, a layer."""
        return 2 if self.double_layer else 1

    @property
    def moe_d_ff(self) -> int:
        return self.d_ff if self.expert_d_ff is None else self.expert_d_ff

    @property
    def shared_ff(self) -> int:
        return self.moe_d_ff if self.shared_d_ff is None else self.shared_d_ff

    @property
    def hybrid_block(self) -> bool:
        """Layers that differ in kind (`layer_kinds`): the sequential
        pre-norm block with each layer's sublayers by its kind - a
        state-space or attention mixer, the feed-forward part, or both - the
        multipliers and an added shared expert
        (`models/decode._block_apply`)."""
        return self.layer_pattern is not None or self.attn_layer_period > 0

    @property
    def layer_kinds(self) -> str:
        """The kind of every layer, a letter of `LAYER_KINDS` a layer: the
        pattern itself, the period and offset spelt out, or every layer
        ``"a"``."""
        if self.layer_pattern is not None:
            return self.layer_pattern
        if self.attn_layer_period > 0:
            return "".join(
                "a" if i % self.attn_layer_period == self.attn_layer_offset else "m"
                for i in range(self.num_layers)
            )
        if self.sliding_window is not None and self.sliding_window_pattern > 1:
            return "".join(
                "w" if (i + 1) % self.sliding_window_pattern else "a"
                for i in range(self.num_layers)
            )
        return "a" * self.num_layers

    def layer_mixer(self, layer: int) -> str | None:
        """Layer ``layer``'s (0-based) mixer: ``"ssm"``, ``"attn"`` or None."""
        return LAYER_KINDS[self.layer_kinds[layer]][0]

    def layer_has_ffn(self, layer: int) -> bool:
        return LAYER_KINDS[self.layer_kinds[layer]][1]

    def layer_ffn_is_dense(self, layer: int) -> bool:
        """Whether layer ``layer``'s feed-forward part is the dense SwiGLU
        of ``d_ff`` although the config's is the expert layer."""
        return self.layer_kinds[layer] == DENSE_FFN_KIND

    def layer_is_ssm(self, layer: int) -> bool:
        """Whether layer ``layer`` (0-based) is a state-space layer."""
        return self.layer_mixer(layer) == "ssm"

    def _layers_with(self, mixer) -> int:
        return sum(LAYER_KINDS[kind][0] == mixer for kind in self.layer_kinds)

    @property
    def ssm_layers(self) -> int:
        """Layers that keep a recurrent state a sequence."""
        return self._layers_with("ssm")

    @property
    def attn_layers(self) -> int:
        """Layers that keep K/V (or latent rows) a position."""
        return self._layers_with("attn")

    @property
    def ssm_inner(self) -> int:
        """Channels of the mixer's inner stream."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_channels(self) -> int:
        """Channels through the convolution: the inner stream and every
        group's ``B`` and ``C``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def attention_scale(self) -> float:
        """What attention scores are multiplied by."""
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        return self.d_head ** -0.5

    @property
    def router_outputs(self) -> int:
        return self.n_experts + self.n_zero_experts

    @property
    def local_experts(self) -> int:
        """Experts whose weights this process holds."""
        return self.n_experts if self.experts_held is None else self.experts_held

    @property
    def has_window_layers(self) -> bool:
        return WINDOW_KIND in self.layer_kinds

    def layer_window(self, layer: int) -> int | None:
        """The sliding window of layer ``layer`` (0-based), None for a
        full-attention layer."""
        if self.layer_kinds[layer] == WINDOW_KIND:
            return self.sliding_window
        return None

    def layer_kv_heads(self, layer: int | None = None) -> int:
        """K/V heads of layer ``layer``: the window layers' own where the
        config gives them, else ``num_kv_heads`` (None: a full layer's)."""
        if (
            layer is not None and self.window_kv_heads is not None
            and self.layer_window(layer) is not None
        ):
            return self.window_kv_heads
        return self.num_kv_heads or self.num_heads

    def layer_rope_theta(self, layer: int) -> float:
        if self.window_rope_theta is not None and self.layer_window(layer) is not None:
            return self.window_rope_theta
        return self.rope_theta

    def layer_sink(self, layer: int) -> bool:
        """Whether layer ``layer``'s softmax has the learned sink."""
        return self.sink_on_window_layers and self.layer_window(layer) is not None

    @property
    def split_attention(self) -> bool:
        """Attention layers that differ by kind in the shape of what they
        cache, a value narrower than the key, a partial rotation, a sink or
        a value scale: what only `models/decode.GroupedRows` and the
        kernels of `kernels/pallas/sink_attention.py` serve."""
        return (
            self.window_kv_heads is not None or self.window_rope_theta is not None
            or self.sink_on_window_layers
            or self.attention_value_scale != 1.0
            or (self.attention_kind != "mla" and bool(self.v_head_dim or self.qk_rope_head_dim))
        )

    def layer_rope(self, layer: int) -> bool:
        """Whether layer ``layer`` rotates q and k."""
        if self.remove_rope:
            return False
        return self.rope_on_full_layers or self.layer_window(layer) is not None

    @property
    def dropless_block(self) -> bool:
        """True for what only the serving paths and the plain forward run
        (no training step, no ``scan_layers``, no int8 weights): a layer
        pattern, a parallel block, LayerNorm, shared or held experts, latent
        attention, the double layer, zero experts and their router, layers
        by a pattern of kinds (state-space mixers, one sublayer a layer), an
        expert activation of its own, chunked linear attention with its
        unit-offset norm and prediction heads."""
        return (
            self.latent_block
            or self.hybrid_block
            or self.eva_block
            or self.norm_unit_offset
            or self.num_pred_heads != 1
            or self.has_window_layers
            or self.parallel_block
            or self.norm_type != "rmsnorm"
            or not self.rope_on_full_layers
            or self.n_shared_experts > 0
            or self.experts_held is not None
            or self.moe_router != "softmax"
            or self.head_dim is not None
            or self.expert_activation != "swiglu"
        )

    @property
    def latent_block(self) -> bool:
        """Latent attention (in either of its blocks), or any of the double
        layer's expert layer's departures."""
        return (
            self.attention_kind == "mla"
            or self.expert_d_ff is not None
            or self.n_zero_experts > 0
            or not self.norm_topk_prob
            or self.routed_scaling_factor != 1.0
            or self.router_bias
        )

    @property
    def resolved_remat_policy(self) -> str:
        """The effective remat policy: ``remat_policy``, with the
        deprecated ``remat: bool`` accepted as ``"full"``."""
        if self.remat and self.remat_policy == "none":
            return "full"
        return self.remat_policy

    @property
    def loss_chunk(self) -> int | None:
        """The effective loss chunk size: explicit N, ``0`` -> None (full
        logits), ``None`` -> auto — bfloat16 training configs whose
        context exceeds :data:`AUTO_LOSS_CHUNK` chunk at that size, so the
        compiled step never materializes the f32 ``(B, T, V)`` logits
        tensor.  Shorter contexts (the chunk would BE the sequence — no
        buffer shrinks) and float32 configs keep full logits."""
        if self.loss_chunk_size is not None:
            return self.loss_chunk_size or None
        if (
            self.activation_dtype == "bfloat16"
            and self.context_length > self.AUTO_LOSS_CHUNK
        ):
            return self.AUTO_LOSS_CHUNK
        return None

    def __post_init__(self):
        if self.head_dim is not None and self.head_dim < 1:
            raise ValueError(f"head_dim={self.head_dim} must be positive")
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(
                f"sliding_window={self.sliding_window} must be positive"
            )
        if self.sliding_window_pattern < 1:
            raise ValueError(
                f"sliding_window_pattern={self.sliding_window_pattern} must "
                "be >= 1"
            )
        if self.norm_type not in ("rmsnorm", "layernorm"):
            raise ValueError(
                f'norm_type={self.norm_type!r} must be "rmsnorm" or "layernorm"'
            )
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(
                f'moe_router={self.moe_router!r} must be "softmax" or "sigmoid"'
            )
        if self.n_shared_experts and self.ffn_type != "moe":
            raise ValueError('n_shared_experts needs ffn_type="moe"')
        if self.experts_held is not None and not (
            self.ffn_type == "moe"
            and 1 <= self.experts_held
            and 0 <= self.expert_offset
            and self.expert_offset + self.experts_held <= self.n_experts
        ):
            raise ValueError(
                f"experts_held={self.experts_held} at expert_offset="
                f"{self.expert_offset} must name experts of a MoE layer with "
                f"n_experts={self.n_experts}"
            )
        if self.attention_kind not in ("mha", "mla", "eva"):
            raise ValueError(
                f'attention_kind={self.attention_kind!r} must be "mha", '
                '"mla" or "eva"'
            )
        if self.eva_block:
            if (
                self.eva_chunk < 1 or self.eva_window < self.eva_chunk
                or self.eva_window % self.eva_chunk
                or self.context_length % self.eva_window
            ):
                raise ValueError(
                    f'attention_kind="eva" needs eva_chunk={self.eva_chunk} >= '
                    f"1 dividing eva_window={self.eva_window}, which divides "
                    f"context_length={self.context_length}"
                )
            if (
                self.sliding_window is not None or self.hybrid_block
                or self.num_kv_heads not in (None, self.num_heads)
                or self.parallel_block or self.use_post_norm
                or self.remove_rmsnorm or self.remove_rope
                or self.norm_type != "rmsnorm"
                or self.ffn_type not in (None, "swiglu")
                or self.num_pred_heads < 1 or self.tie_embeddings
            ):
                raise ValueError(
                    "chunked linear attention summarises every K/V head's "
                    "rotated keys by window and comes in the sequential "
                    "pre-norm RMSNorm block with a dense SwiGLU and an untied "
                    "head: sliding_window, state-space layers "
                    "(layer_pattern, attn_layer_period), num_kv_heads < num_heads, "
                    "parallel_block, use_post_norm, remove_rmsnorm, "
                    "remove_rope, LayerNorm, another ffn_type and "
                    "tie_embeddings contradict it"
                )
        elif (
            self.eva_window or self.eva_chunk or self.norm_unit_offset
            or self.num_pred_heads != 1
        ):
            raise ValueError(
                "eva_window, eva_chunk, norm_unit_offset and num_pred_heads "
                'are chunked linear attention\'s (attention_kind="eva"): no '
                "other block applies them"
            )
        mla_dims = (
            self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim,
        )
        if self.attention_kind == "mla":
            if (
                min(mla_dims[1:]) < 1 or self.q_lora_rank < 0
                or self.qk_rope_head_dim % 2
            ):
                raise ValueError(
                    'attention_kind="mla" needs positive kv_lora_rank, '
                    "qk_nope_head_dim, v_head_dim, an even qk_rope_head_dim "
                    "and a q_lora_rank that is positive or 0, a full-rank "
                    f"query (got {mla_dims})"
                )
            if self.mla_scale_q_lora and not self.q_lora_rank:
                raise ValueError(
                    "mla_scale_q_lora scales the query's latent, and "
                    "q_lora_rank=0 (a full-rank query) has none"
                )
            if (
                self.num_kv_heads is not None or self.head_dim is not None
                or self.sliding_window is not None or self.remove_rope
                or self.remove_rmsnorm
            ):
                raise ValueError(
                    "latent attention has no K/V heads, one head width of its "
                    "own and no window layers: num_kv_heads, head_dim, "
                    "sliding_window, remove_rope and remove_rmsnorm contradict it"
                )
            if self.hybrid_block and (
                set(self.layer_kinds) - {"a", DENSE_FFN_KIND}
                or self.attention_multiplier is not None
            ):
                raise ValueError(
                    "latent attention under a layer_pattern is the sequential "
                    'block of "a" and "A" layers - every layer attends and '
                    "feeds forward: a latent pool has an array a layer - at "
                    f"its own softmax scale (got {self.layer_kinds!r}, "
                    f"attention_multiplier={self.attention_multiplier})"
                )
        elif (
            any(mla_dims[:3]) or self.mla_scale_q_lora or self.mla_scale_kv_lora
            or (any(mla_dims[3:]) and not self.has_window_layers)
        ):
            raise ValueError(
                "q_lora_rank .. v_head_dim and the lora scales are latent "
                'attention\'s (attention_kind="mla"); outside it a '
                "layer_pattern with window layers alone takes a "
                "qk_rope_head_dim and a v_head_dim"
            )
        if self.yarn_factor == 1.0:
            if any(
                getattr(self, name) != getattr(type(self), name)
                for name in (
                    "yarn_original_context", "yarn_beta_fast", "yarn_beta_slow",
                    "yarn_mscale", "yarn_mscale_all_dim",
                )
            ):
                raise ValueError(
                    "yarn_original_context .. yarn_mscale_all_dim stretch "
                    "positions by yarn_factor, which is 1 (no scaling)"
                )
        elif (
            self.attention_kind != "mla" or self.yarn_factor < 1.0
            or self.yarn_original_context < 1
            or not 0 < self.yarn_beta_slow < self.yarn_beta_fast
        ):
            raise ValueError(
                f"yarn_factor={self.yarn_factor} stretches latent attention's "
                'positions (attention_kind="mla"; no other attention reads '
                "it): it needs a factor >= 1, a positive "
                "yarn_original_context and 0 < yarn_beta_slow < yarn_beta_fast"
            )
        if self.norm_eps <= 0:
            raise ValueError(f"norm_eps={self.norm_eps} must be positive")
        if self.double_layer and (
            self.ffn_type != "moe" or self.parallel_block or self.use_post_norm
            or self.norm_type != "rmsnorm"
        ):
            raise ValueError(
                'the double layer is pre-norm RMSNorm around an expert layer '
                '(ffn_type="moe"); parallel_block, use_post_norm and '
                "LayerNorm contradict it"
            )
        if self.ffn_type != "moe" and (
            self.expert_d_ff is not None or self.n_zero_experts
            or self.router_bias
        ):
            raise ValueError(
                'expert_d_ff, n_zero_experts and router_bias need ffn_type="moe"'
            )
        if self.n_zero_experts < 0 or (
            self.expert_d_ff is not None and self.expert_d_ff < 1
        ):
            raise ValueError(
                f"n_zero_experts={self.n_zero_experts} must be >= 0 and "
                f"expert_d_ff={self.expert_d_ff} positive"
            )
        if self.parallel_block and self.use_post_norm:
            raise ValueError("parallel_block has one pre-norm; use_post_norm contradicts it")
        ssm_dims = (self.ssm_heads, self.ssm_head_dim, self.ssm_state)
        if self.hybrid_block:
            if self.layer_pattern is None:
                if not 0 <= self.attn_layer_offset < self.attn_layer_period:
                    raise ValueError(
                        f"attn_layer_offset={self.attn_layer_offset} must lie in "
                        f"[0, attn_layer_period={self.attn_layer_period})"
                    )
            elif self.attn_layer_period or self.attn_layer_offset:
                raise ValueError(
                    "layer_pattern names every layer's kind: attn_layer_period "
                    "and attn_layer_offset beside it say the same twice"
                )
            elif (
                len(self.layer_pattern) != self.num_layers
                or set(self.layer_pattern) - set(LAYER_KINDS)
            ):
                raise ValueError(
                    f"layer_pattern={self.layer_pattern!r} must name each of "
                    f"num_layers={self.num_layers} layers by one of "
                    f"{''.join(LAYER_KINDS)!r}"
                )
            if self.ssm_layers == 0:
                if any(ssm_dims) or self.ssm_groups != 1:
                    raise ValueError(
                        "ssm_heads .. ssm_groups are the state-space layers' "
                        f"and layer_pattern={self.layer_pattern!r} has none"
                    )
            elif min(ssm_dims) < 1 or self.ssm_conv < 2 or self.ssm_chunk < 1:
                raise ValueError(
                    "state-space layers need positive ssm_heads, ssm_head_dim, "
                    f"ssm_state and ssm_chunk and ssm_conv >= 2 (got {ssm_dims}, "
                    f"{self.ssm_conv}, {self.ssm_chunk})"
                )
            if self.ssm_layers and (
                self.ssm_groups < 1 or self.ssm_heads % self.ssm_groups
            ):
                raise ValueError(
                    f"ssm_groups={self.ssm_groups} must divide "
                    f"ssm_heads={self.ssm_heads}"
                )
            if (
                (self.sliding_window is not None and not self.has_window_layers)
                or self.parallel_block or self.use_post_norm
                or self.remove_rmsnorm or self.norm_type != "rmsnorm"
            ):
                raise ValueError(
                    "layers by a pattern of kinds come in the sequential "
                    "pre-norm RMSNorm block, state-space mixers beside plain "
                    "attention layers, or latent attention in every layer: "
                    "sliding_window, parallel_block, use_post_norm, "
                    "remove_rmsnorm and LayerNorm contradict them"
                )
            if self.has_window_layers and (
                self.sliding_window is None or self.sliding_window_pattern != 1
                or self.ssm_layers or "*" in self.layer_kinds
                or not self.rope_on_full_layers
            ):
                raise ValueError(
                    f"layer_pattern={self.layer_pattern!r} names window "
                    "layers: it needs a sliding_window, says itself which "
                    "layers are windowed (sliding_window_pattern stays 1), "
                    "rotates every layer, and no cache kind holds a window "
                    "group beside a recurrent state or a layer of attention "
                    "alone"
                )
            if DENSE_FFN_KIND in self.layer_kinds and self.ffn_type != "moe":
                raise ValueError(
                    f"layer_pattern={self.layer_pattern!r} names layers whose "
                    'feed-forward part is dense beside expert layers: ffn_type="moe"'
                )
        elif (
            self.attn_layer_period < 0 or self.attn_layer_offset
            or any(ssm_dims) or self.ssm_groups != 1
        ):
            raise ValueError(
                "attn_layer_offset and ssm_heads .. ssm_groups are the hybrid "
                "block's (layer_pattern, or attn_layer_period > 0)"
            )
        elif (
            self.embedding_multiplier != 1.0 or self.residual_multiplier != 1.0
            or self.attention_multiplier is not None
            or self.logits_scaling != 1.0 or self.shared_d_ff is not None
            or self.expert_activation != "swiglu"
        ):
            raise ValueError(
                "the multipliers, shared_d_ff and expert_activation are the "
                "hybrid block's (layer_pattern, or attn_layer_period > 0): no "
                "other block applies them"
            )
        if self.split_attention:
            if not (self.layer_pattern is not None and self.has_window_layers):
                raise ValueError(
                    "window_kv_heads, window_rope_theta, the sink, "
                    "attention_value_scale and, outside latent attention, "
                    "qk_rope_head_dim and v_head_dim are a layer_pattern's "
                    "with window layers: no other block's cache holds them"
                )
            if self.qk_rope_head_dim % 2 or not (
                0 <= self.qk_rope_head_dim <= self.d_head
            ) or self.v_head_dim < 0 or self.attention_multiplier is not None:
                raise ValueError(
                    f"qk_rope_head_dim={self.qk_rope_head_dim} must be even "
                    f"and at most d_head={self.d_head}, v_head_dim="
                    f"{self.v_head_dim} not negative, and the scores' scale "
                    "d_head ** -0.5 (no attention_multiplier)"
                )
            if self.window_kv_heads is not None and (
                self.window_kv_heads < 1 or self.num_heads % self.window_kv_heads
            ):
                raise ValueError(
                    f"window_kv_heads={self.window_kv_heads} must divide "
                    f"num_heads={self.num_heads}"
                )
        if self.expert_activation not in ("swiglu", "relu2") or (
            self.expert_activation != "swiglu" and self.ffn_type != "moe"
        ):
            raise ValueError(
                f"expert_activation={self.expert_activation!r} must be "
                '"swiglu" or "relu2", the latter of an expert layer '
                '(ffn_type="moe")'
            )
        if self.shared_d_ff is not None and not (
            self.n_shared_experts and self.shared_d_ff >= 1
        ):
            raise ValueError("shared_d_ff is the positive width of n_shared_experts > 0")
        if self.dropless_block and not (
            self.parallel_block or self.double_layer or self.hybrid_block
            or self.eva_block
        ):
            raise ValueError(
                "a layer pattern, LayerNorm, head_dim, sigmoid routing, shared "
                "or held experts run in the parallel block only (zero "
                "experts, unnormalised gates and an expert width of its own "
                "in the double layer too): no configuration has them in a "
                "sequential block"
            )
        if self.scan_layers and self.dropless_block:
            raise ValueError(
                "scan_layers runs homogeneous training blocks; a layer "
                "pattern, state-space layers, the parallel block, the double "
                "layer, chunked linear attention, LayerNorm, "
                "shared, held or zero experts are served and not trained "
                "(ROADMAP: what cannot run yet)"
            )
        if self.head_dim is None and self.d_model % self.num_heads:
            raise ValueError(
                f"d_model={self.d_model} not divisible by num_heads={self.num_heads}"
            )
        if self.num_kv_heads is not None and (
            self.num_kv_heads < 1 or self.num_heads % self.num_kv_heads
        ):
            raise ValueError(
                f"num_kv_heads={self.num_kv_heads} must divide "
                f"num_heads={self.num_heads}"
            )
        if self.ffn_type == "moe" and self.n_experts < 1:
            raise ValueError(
                'ffn_type="moe" requires n_experts >= 1 (got '
                f"{self.n_experts}); set n_experts in the model config"
            )
        if self.moe_dispatch not in ("einsum", "gather"):
            raise ValueError(
                f'moe_dispatch={self.moe_dispatch!r} must be "einsum" or "gather"'
            )
        if self.decode_attention_impl not in ("auto", "xla", "pallas", "paged"):
            raise ValueError(
                f"decode_attention_impl={self.decode_attention_impl!r} "
                'must be "auto", "xla", "pallas" or "paged"'
            )
        if self.ffn_type == "moe" and not (
            1 <= self.router_top_k <= self.router_outputs
        ):
            raise ValueError(
                f"router_top_k={self.router_top_k} must be in "
                f"[1, router outputs={self.router_outputs}]"
            )
        if self.remat_policy not in (
            "none", "full", "dots_saveable", "save_attn"
        ):
            raise ValueError(
                f"remat_policy={self.remat_policy!r} must be one of "
                '"none", "full", "dots_saveable", "save_attn"'
            )
        if self.remat and self.remat_policy not in ("none", "full"):
            raise ValueError(
                f"remat=True (deprecated alias for remat_policy=\"full\") "
                f"contradicts remat_policy={self.remat_policy!r}; drop the "
                "bool and set only remat_policy"
            )
        if self.loss_chunk_size is not None and self.loss_chunk_size < 0:
            raise ValueError(
                f"loss_chunk_size={self.loss_chunk_size} must be None "
                "(auto), 0 (full logits), or a positive chunk"
            )

    @classmethod
    def from_dict(cls, raw: dict) -> "ModelConfig":
        """Build from a plain dict, ignoring unknown keys (reference JSON
        schema compatibility; also the checkpoint-stored config)."""
        known = {f.name for f in dataclasses.fields(cls)}
        coerced = {k: v for k, v in raw.items() if k in known}
        # json round-trips tuples as lists; frozen dataclasses need hashables.
        for k, v in coerced.items():
            if isinstance(v, list):
                coerced[k] = tuple(v)
        return cls(**coerced)

    @classmethod
    def from_json(cls, path: str | Path) -> "ModelConfig":
        with open(path) as f:
            raw: dict[str, Any] = json.load(f)
        return cls.from_dict(raw)

    def to_json(self, path: str | Path) -> None:
        payload = dataclasses.asdict(self)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)


#: The reference test fixture architecture (model_config.json).
TS_TEST_CONFIG = ModelConfig(
    vocab_size=10_000,
    context_length=16,
    d_model=64,
    num_layers=3,
    num_heads=4,
    d_ff=128,
    rope_theta=10000.0,
)

#: BASELINE.json config 1: TinyStories 4L/256d single-chip model.
TINYSTORIES_4L = ModelConfig(
    vocab_size=10_000,
    context_length=256,
    d_model=256,
    num_layers=4,
    num_heads=8,
    d_ff=683,
    rope_theta=10000.0,
)

#: BASELINE.json config 2: TinyStories 12L/512d data-parallel model.
TINYSTORIES_12L = ModelConfig(
    vocab_size=10_000,
    context_length=512,
    d_model=512,
    num_layers=12,
    num_heads=8,
    d_ff=1365,
    rope_theta=10000.0,
)

#: BASELINE.json config 3: GPT-2-small-class model with 32k vocab.
GPT2_SMALL_32K = ModelConfig(
    vocab_size=32_000,
    context_length=1024,
    d_model=768,
    num_layers=12,
    num_heads=12,
    d_ff=2048,
    rope_theta=10000.0,
    activation_dtype="bfloat16",
    loss_chunk_size=256,
)

#: Sparse counterpart of TINYSTORIES_12L: 8-expert top-2 MoE FFNs with the
#: same d_model/attention; train with an ep strategy (dp_ep/fsdp_ep) so the
#: expert stacks shard over the expert mesh axis.
TINYSTORIES_MOE = ModelConfig(
    vocab_size=10_000,
    context_length=512,
    d_model=512,
    num_layers=12,
    num_heads=8,
    d_ff=1365,
    rope_theta=10000.0,
    ffn_type="moe",
    n_experts=8,
    router_top_k=2,
    capacity_factor=1.25,
    # Builder capture 2026-08-02 (benchmarks/captures/
    # tpu_capture_tinystories-moe*.json): gather beat einsum — the dense
    # dispatch/combine einsums cost more than the expert FFN itself at this
    # shape.  Identical routing; einsum stays selectable.
    moe_dispatch="gather",
)

#: BASELINE.json config 5: GPT-2-medium-class model (FSDP target).
GPT2_MEDIUM = ModelConfig(
    vocab_size=32_000,
    context_length=1024,
    d_model=1024,
    num_layers=24,
    num_heads=16,
    d_ff=2731,
    rope_theta=10000.0,
    activation_dtype="bfloat16",
    # Selective recompute (PR 13): strictly less recompute than the old
    # remat=True at a peak-HBM point that still fits the FSDP target.
    remat_policy="save_attn",
    loss_chunk_size=256,
)
