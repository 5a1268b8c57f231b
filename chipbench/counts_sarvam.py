"""What the sarvam-105b configuration *requires*, computed from shapes (see
``counts.py``): FLOPs and bytes of this chip's share.

``cfg`` is the dict of ``configs/sarvam-105b.json``.  Counted per layer: the
latent attention (``q``, ``kv_a``, ``kv_b``, ``o``: a full-rank query, no
bottleneck), the dense SwiGLU in the leading ``first_k_dense_replace``
layers, and in the others the router over all its outputs, the shared expert
every token passes, and of the routed experts what this chip holds: a
token's ``num_experts_per_tok`` choices land on a held expert with
probability ``num_experts / n_experts``, so on average ``8 x 32 / 128 = 2``
experts a token.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """One latent-attention layer: W_q, W_kva, W_kvb and W_o."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    return (
        d * heads * (nope + rope) + d * (rank + rope)
        + rank * heads * (nope + v) + heads * v * d
    )


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert; a shared expert is as wide."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["n_experts"] * cfg["hidden_size"]


def dense_layers(cfg: dict) -> int:
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def dense_layer_params(cfg: dict) -> int:
    """A leading layer: attention and the dense SwiGLU."""
    return attention_params(cfg) + dense_ffn_params(cfg)


def layer_params_outside_experts(cfg: dict) -> int:
    """An expert layer but for its routed experts: attention, the router
    and the shared expert(s)."""
    return (
        attention_params(cfg) + router_params(cfg)
        + cfg["num_shared_experts"] * expert_params(cfg)
    )


def head_params(cfg: dict) -> int:
    """The embedding slice and the untied head slice."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def params_held(cfg: dict) -> int:
    """Matmul weights on this chip (``num_experts`` routed experts a layer;
    the norms' vectors and the selection biases are left out)."""
    return (
        dense_layers(cfg) * dense_layer_params(cfg)
        + expert_layers(cfg) * (
            layer_params_outside_experts(cfg) + cfg["num_experts"] * expert_params(cfg)
        )
        + head_params(cfg)
    )


def matmul_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of every weight held, each held expert once (see
    ``counts_cohere2moe.matmul_weight_bytes``); the embedding slice is held
    and not streamed by a tick."""
    return itemsize * params_held(cfg)


def latent_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def kv_bytes_per_position(cfg: dict, itemsize: int = 2) -> int:
    """One layer's latent row of a cached position, as the config counts
    it."""
    return latent_width(cfg) * itemsize


def kv_bytes_per_position_held(cfg: dict, itemsize: int = 2) -> int:
    """The same row as the device holds it: padded to whole 128-lane tiles
    (576 -> 640)."""
    return -(-latent_width(cfg) // 128) * 128 * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """The latent rows of one cached position over all layers."""
    return cfg["num_hidden_layers"] * kv_bytes_per_position(cfg, itemsize)


def held_experts_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["n_experts"]


def forward_flops(cfg: dict, n_tokens: int, sum_keys: int, n_head_tokens: int) -> float:
    """FLOPs a forward pass of this chip's share requires for ``n_tokens``
    positions whose attention layers see ``sum_keys`` keys in total each,
    with the head applied at ``n_head_tokens`` of them.  Attention is
    counted in the expanded form, ``2 x heads x (nope + rope + v)`` a pair."""
    per_token = (
        dense_layers(cfg) * dense_layer_params(cfg)
        + expert_layers(cfg) * (
            layer_params_outside_experts(cfg)
            + held_experts_per_token(cfg) * expert_params(cfg)
        )
    )
    attention = cfg["num_hidden_layers"] * mla_chunk_attention_flops(cfg, sum_keys)
    head = 2.0 * cfg["vocab_size"] * cfg["hidden_size"] * n_head_tokens
    return 2.0 * per_token * n_tokens + attention + head


# The kernels: what ``layer_metrics/sarvam.*_roofline.json`` spell out in
# numbers.


def mla_chunk_attention_flops(cfg: dict, pairs: int) -> float:
    """A visible (query, key) pair of one layer, all heads, in the expanded
    form: the score over a head's key of ``nope + rope``, the value sum over
    its ``v`` values."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    ) * pairs


def mla_paged_attention_flops(cfg: dict, pairs: int) -> float:
    """A (query, key) pair of one layer, all heads, absorbed: the score over
    a whole latent row and the value sum over its latent part."""
    return 2.0 * cfg["num_attention_heads"] * (latent_width(cfg) + cfg["kv_lora_rank"]) * pairs


def mla_paged_attention_bytes(cfg: dict, kv_positions: int, itemsize: int = 2) -> float:
    """A cached position of one layer is one padded row, copied once by each
    slot that holds it (for scores and values alike)."""
    return float(kv_bytes_per_position_held(cfg, itemsize) * kv_positions)


def gmm_flops(cfg: dict, rows: int) -> float:
    """A row of the grouped matmul: three matrices of one routed expert."""
    return 2.0 * expert_params(cfg) * rows


def gmm_bytes(cfg: dict, groups: int, itemsize: int = 2) -> float:
    """A non-empty expert group streams its three matrices."""
    return float(itemsize * expert_params(cfg) * groups)
