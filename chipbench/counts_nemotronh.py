"""What the NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 configuration *requires*,
computed from shapes (see ``counts.py``): FLOPs and bytes of this chip's
share.

``cfg`` is the dict of ``configs/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16.json``.
A layer is one sublayer (the letter of ``hybrid_override_pattern``): a
Mamba-2 mixer's ``in_proj`` and ``out_proj`` and the recurrence itself, an
attention layer's q, k, v and o, or an expert layer's router over all
``n_experts``, its shared expert whole and, of the routed experts, what this
chip holds: a token's ``num_experts_per_tok`` choices land on a held expert
with probability ``n_routed_experts / n_experts``, so on average ``6 x 64 /
128 = 3`` experts a token.  Every layer has one norm.  Attention pairs count
for the attention layers alone (2 layers in 13).
"""

from __future__ import annotations


def _kinds(cfg: dict) -> str:
    return cfg["hybrid_override_pattern"][: cfg["num_hidden_layers"]]


def mamba_layers(cfg: dict) -> int:
    return _kinds(cfg).count("M")


def attention_layers(cfg: dict) -> int:
    return _kinds(cfg).count("*")


def expert_layers(cfg: dict) -> int:
    return _kinds(cfg).count("E")


def ssm_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def ssm_conv_channels(cfg: dict) -> int:
    return ssm_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mamba_matmul_params(cfg: dict) -> int:
    """in_proj (gate, conv channels, dt) and out_proj: what a token
    multiplies through."""
    d, inner = cfg["hidden_size"], ssm_inner(cfg)
    return d * (inner + ssm_conv_channels(cfg) + cfg["mamba_num_heads"]) + inner * d


def mamba_params(cfg: dict) -> int:
    """One Mamba-2 mixer: the two projections, the convolution and its
    bias, dt_bias / A_log / D, the gated norm."""
    ch, heads = ssm_conv_channels(cfg), cfg["mamba_num_heads"]
    return mamba_matmul_params(cfg) + ch * cfg["conv_kernel"] + ch + 3 * heads + ssm_inner(cfg)


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one attention layer."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * dh + 2 * d * cfg["num_key_value_heads"] * dh


def expert_params(cfg: dict) -> int:
    """One routed expert: two matrices around the squared ReLU."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix and its selection bias."""
    return cfg["n_experts"] * (cfg["hidden_size"] + 1)


def layer_params_outside_experts(cfg: dict, kind: str) -> int:
    """A layer's one sublayer, the routed experts left out, and its norm."""
    sublayer = {
        "M": mamba_params(cfg), "*": attention_params(cfg),
        "E": shared_params(cfg) + router_params(cfg),
    }[kind]
    return sublayer + cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    """The head's slice; the embedding's is as large again (untied)."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def params_held(cfg: dict) -> int:
    return (
        sum(layer_params_outside_experts(cfg, kind) for kind in _kinds(cfg))
        + expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
        + 2 * head_params(cfg) + cfg["hidden_size"]
    )


def matmul_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of every weight a decode tick streams when every held expert
    gets a row (192 slots x 6 / 128 = 9 rows an expert: every one): all
    that is held but the embedding, which is a row gather."""
    return itemsize * (params_held(cfg) - head_params(cfg))


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one cached position: the attention layers alone."""
    return 2 * attention_layers(cfg) * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def state_bytes_per_slot(cfg: dict, itemsize: int = 2) -> int:
    """What a slot keeps whatever its context: a float32 state a Mamba
    layer and its k - 1 conv rows at the activation width."""
    state = 4 * ssm_inner(cfg) * cfg["ssm_state_size"]
    conv = itemsize * (cfg["conv_kernel"] - 1) * ssm_conv_channels(cfg)
    return mamba_layers(cfg) * (state + conv)


def held_experts_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["n_experts"]


def recurrence_flops_per_token(cfg: dict) -> float:
    """One position of one Mamba layer's recurrence: decay and input into
    the state (3 an element) and the state against C (2 an element)."""
    return 5.0 * ssm_inner(cfg) * cfg["ssm_state_size"]


def forward_flops(cfg: dict, n_tokens: int, sum_keys: int, n_head_tokens: int) -> float:
    """FLOPs a forward pass of this chip's share requires for ``n_tokens``
    positions whose attention layers see ``sum_keys`` keys in total, with
    the head applied at ``n_head_tokens`` of them."""
    per_token = (
        mamba_layers(cfg) * mamba_matmul_params(cfg)
        + attention_layers(cfg) * attention_params(cfg)
        + expert_layers(cfg) * (
            shared_params(cfg) + cfg["n_experts"] * cfg["hidden_size"]
            + held_experts_per_token(cfg) * expert_params(cfg)
        )
    )
    blocks = 2.0 * per_token * n_tokens
    recurrence = mamba_layers(cfg) * recurrence_flops_per_token(cfg) * n_tokens
    # QK^T and AV: 2 * heads * head_dim each per (query, key) pair.
    d_attn = cfg["num_attention_heads"] * cfg["head_dim"]
    attention = 4.0 * d_attn * attention_layers(cfg) * sum_keys
    return blocks + recurrence + attention + 2.0 * head_params(cfg) * n_head_tokens


# The tick's kernels and the expert layer's grouped matmul: what
# ``layer_metrics/nemotron.ssm_state_update_roofline.json``,
# ``nemotron.paged_decode_attention_roofline.json`` and
# ``nemotron.gmm_roofline.json`` spell out in numbers.


def ssm_state_update_bytes(cfg: dict, slot_layers: int) -> float:
    """A slot-layer's float32 state, read once and written once."""
    return 2.0 * 4 * ssm_inner(cfg) * cfg["ssm_state_size"] * slot_layers


def paged_decode_attention_bytes(cfg: dict, kv_positions: int, itemsize: int = 2) -> float:
    """K and V of a cached position of one attention layer, read once
    (``kv_positions`` counts positions x attention layers)."""
    return float(2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize * kv_positions)


def gmm_flops(cfg: dict, rows: int) -> float:
    """A row of the grouped matmul: two matrices of one routed expert."""
    return 2.0 * expert_params(cfg) * rows


def gmm_bytes(cfg: dict, groups: int, itemsize: int = 2) -> float:
    """A non-empty expert group streams its two matrices."""
    return float(itemsize * expert_params(cfg) * groups)
