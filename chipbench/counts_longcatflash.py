"""What the LongCat-Flash-Omni configuration *requires*, computed from
shapes (see ``counts.py``): FLOPs and bytes of this chip's share.

``cfg`` is the dict of ``configs/LongCat-Flash-Omni.json``.  Counted per
layer: two MLA sublayers (``q_a``, ``q_b``, ``kv_a``, ``kv_b``, ``o``), two
dense SwiGLU FFNs, the router over all its outputs (real and zero experts),
and of the real experts what this chip holds: a token's ``moe_topk``
choices land on a held expert with probability ``n_routed_experts /
(n_experts + zero_expert_num)``, so on average ``12 x 16 / 768 = 0.25``
experts a token.  A zero expert costs no FLOPs and no bytes.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """One MLA sublayer: q_a, q_b, kv_a, kv_b and o."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (
        d * rq + rq * heads * (nope + rope) + d * (rkv + rope)
        + rkv * heads * (nope + v) + heads * v * d
    )


def dense_params(cfg: dict) -> int:
    """One dense SwiGLU FFN."""
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg: dict) -> int:
    """One real expert."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_outputs(cfg: dict) -> int:
    return cfg["n_experts"] + cfg["zero_expert_num"]


def router_params(cfg: dict) -> int:
    return router_outputs(cfg) * cfg["hidden_size"]


def layer_params_outside_experts(cfg: dict) -> int:
    return 2 * attention_params(cfg) + 2 * dense_params(cfg) + router_params(cfg)


def layer_params_held(cfg: dict) -> int:
    """Matmul weights of one layer on this chip."""
    return layer_params_outside_experts(cfg) + cfg["n_routed_experts"] * expert_params(cfg)


def head_params(cfg: dict) -> int:
    """The embedding slice and the untied head slice."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def params_held(cfg: dict) -> int:
    return cfg["num_layers"] * layer_params_held(cfg) + head_params(cfg)


def matmul_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of every weight held, each held expert once (see
    ``counts_cohere2moe.matmul_weight_bytes``); the embedding slice is held
    and not streamed by a tick."""
    return itemsize * params_held(cfg)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """The latent rows of one cached position: one a sublayer, two
    sublayers a layer."""
    return 2 * cfg["num_layers"] * latent_width(cfg) * itemsize


def latent_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def held_experts_per_token(cfg: dict) -> float:
    return cfg["moe_topk"] * cfg["n_routed_experts"] / router_outputs(cfg)


def forward_flops(cfg: dict, n_tokens: int, sum_keys: int, n_head_tokens: int) -> float:
    """FLOPs a forward pass of this chip's share requires for ``n_tokens``
    positions whose attention sublayers see ``sum_keys`` keys in total each,
    with the head applied at ``n_head_tokens`` of them.  Attention is counted
    in the expanded form, ``2 x heads x (nope + rope + v)`` a pair."""
    per_token = (
        layer_params_outside_experts(cfg)
        + held_experts_per_token(cfg) * expert_params(cfg)
    )
    blocks = 2.0 * cfg["num_layers"] * per_token * n_tokens
    pair = 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )
    attention = 2 * cfg["num_layers"] * pair * sum_keys
    return blocks + attention + cfg["vocab_size"] * cfg["hidden_size"] * 2.0 * n_head_tokens


# The tick's kernel (``mla_paged_attention``, absorbed form): what
# ``layer_metrics/mla_paged_attention_roofline.json`` spells out in numbers.


def mla_paged_attention_flops(cfg: dict, pairs: int) -> float:
    """A (query, key) pair of one sublayer, all heads: the absorbed score
    over a whole latent row and the value sum over its latent part."""
    return 2.0 * cfg["num_attention_heads"] * (latent_width(cfg) + cfg["kv_lora_rank"]) * pairs


def mla_paged_attention_bytes(cfg: dict, kv_positions: int, itemsize: int = 2) -> float:
    """A cached position of one sublayer is one latent row, read once a slot
    that holds it (for scores and values alike)."""
    return float(itemsize * latent_width(cfg) * kv_positions)


def gmm_flops(cfg: dict, rows: int) -> float:
    """A row of the grouped matmul: three matrices of one real expert."""
    return 2.0 * expert_params(cfg) * rows


def gmm_bytes(cfg: dict, groups: int, itemsize: int = 2) -> float:
    """A non-empty expert group streams its three matrices."""
    return float(itemsize * expert_params(cfg) * groups)
