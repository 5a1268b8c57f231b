"""What the granite-4.0-h-small configuration *requires*, computed from
shapes (see ``counts.py``): FLOPs and bytes of this chip's share.

``cfg`` is the dict of ``configs/granite-4.0-h-small.json``.  Counted per
layer: its mixer - a Mamba-2 layer's ``in_proj`` and ``out_proj`` and the
recurrence itself, or an attention layer's q, k, v and o - the router over
all ``n_experts``, the shared expert whole, and of the routed experts what
this chip holds: a token's ``num_experts_per_tok`` choices land on a held
expert with probability ``num_local_experts / n_experts``, so on average
``10 x 36 / 72 = 5`` experts a token.  Attention pairs count for the
attention layers alone (1 layer in 10).
"""

from __future__ import annotations


def _kinds(cfg: dict) -> list:
    return cfg["layer_types"][: cfg["num_hidden_layers"]]


def mamba_layers(cfg: dict) -> int:
    return sum(1 for kind in _kinds(cfg) if kind == "mamba")


def attention_layers(cfg: dict) -> int:
    return sum(1 for kind in _kinds(cfg) if kind == "attention")


def ssm_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def ssm_conv_channels(cfg: dict) -> int:
    return ssm_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mamba_params(cfg: dict) -> int:
    """One Mamba-2 mixer: in_proj (gate, conv channels, dt), the
    convolution and its bias, dt_bias / A_log / D, the gated norm, out_proj."""
    d, inner, ch = cfg["hidden_size"], ssm_inner(cfg), ssm_conv_channels(cfg)
    heads = cfg["mamba_n_heads"]
    return (
        d * (inner + ch + heads) + ch * cfg["mamba_d_conv"] + ch + 3 * heads
        + inner + inner * d
    )


def mamba_matmul_params(cfg: dict) -> int:
    """in_proj and out_proj: what a token multiplies through."""
    d, inner = cfg["hidden_size"], ssm_inner(cfg)
    return d * (inner + ssm_conv_channels(cfg) + cfg["mamba_n_heads"]) + inner * d


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one attention layer."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    return 2 * d * cfg["num_attention_heads"] * dh + 2 * d * cfg["num_key_value_heads"] * dh


def expert_params(cfg: dict) -> int:
    """One routed SwiGLU expert."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["n_experts"] * cfg["hidden_size"]


def layer_params_outside_experts(cfg: dict, kind: str) -> int:
    """A layer's mixer, shared expert, router and two norms."""
    mixer = mamba_params(cfg) if kind == "mamba" else attention_params(cfg)
    return mixer + shared_params(cfg) + router_params(cfg) + 2 * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    """The tied embedding / head slice."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def params_held(cfg: dict) -> int:
    return (
        sum(layer_params_outside_experts(cfg, kind) for kind in _kinds(cfg))
        + cfg["num_hidden_layers"] * cfg["num_local_experts"] * expert_params(cfg)
        + head_params(cfg)
    )


def matmul_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of every weight held, each held expert once: what a decode tick
    streams when every held expert gets a row (96 slots x 10 / 72 = 13 rows
    an expert: every one)."""
    return itemsize * params_held(cfg)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one cached position: the attention layers alone."""
    return 2 * attention_layers(cfg) * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def state_bytes_per_slot(cfg: dict, itemsize: int = 2) -> int:
    """What a slot keeps whatever its context: a float32 state a Mamba
    layer and its k - 1 conv rows at the activation width."""
    state = 4 * ssm_inner(cfg) * cfg["mamba_d_state"]
    conv = itemsize * (cfg["mamba_d_conv"] - 1) * ssm_conv_channels(cfg)
    return mamba_layers(cfg) * (state + conv)


def held_experts_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["num_local_experts"] / cfg["n_experts"]


def recurrence_flops_per_token(cfg: dict) -> float:
    """One position of one Mamba layer's recurrence: decay and input into
    the state (3 an element) and the state against C (2 an element)."""
    return 5.0 * ssm_inner(cfg) * cfg["mamba_d_state"]


def forward_flops(cfg: dict, n_tokens: int, sum_keys: int, n_head_tokens: int) -> float:
    """FLOPs a forward pass of this chip's share requires for ``n_tokens``
    positions whose attention layers see ``sum_keys`` keys in total, with
    the head applied at ``n_head_tokens`` of them."""
    per_token = (
        mamba_layers(cfg) * mamba_matmul_params(cfg)
        + attention_layers(cfg) * attention_params(cfg)
        + cfg["num_hidden_layers"] * (
            shared_params(cfg) + router_params(cfg)
            + held_experts_per_token(cfg) * expert_params(cfg)
        )
    )
    blocks = 2.0 * per_token * n_tokens
    recurrence = mamba_layers(cfg) * recurrence_flops_per_token(cfg) * n_tokens
    # QK^T and AV: 2 * heads * head_dim each per (query, key) pair.
    d_attn = cfg["num_attention_heads"] * head_dim(cfg)
    attention = 4.0 * d_attn * attention_layers(cfg) * sum_keys
    return blocks + recurrence + attention + 2.0 * head_params(cfg) * n_head_tokens


# The tick's kernel (``ssm_state_update``) and the expert layer's grouped
# matmul: what ``layer_metrics/ssm_state_update_roofline.json`` and
# ``granite.gmm_roofline.json`` spell out in numbers.


def ssm_state_update_bytes(cfg: dict, slot_layers: int) -> float:
    """A slot-layer's float32 state, read once and written once."""
    return 2.0 * 4 * ssm_inner(cfg) * cfg["mamba_d_state"] * slot_layers


def gmm_flops(cfg: dict, rows: int) -> float:
    """A row of the grouped matmul: three matrices of one routed expert."""
    return 2.0 * expert_params(cfg) * rows


def gmm_bytes(cfg: dict, groups: int, itemsize: int = 2) -> float:
    """A non-empty expert group streams its three matrices."""
    return float(itemsize * expert_params(cfg) * groups)
