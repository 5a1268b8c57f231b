"""What the Cohere2-MoE configuration *requires*, computed from shapes (see
``counts.py``): FLOPs and bytes of this chip's share.

``cfg`` is the dict of ``configs/command-a-plus-05-2026.json``.  Counted per
layer: q and o (``hidden x heads x head_dim`` each), k and v, the router
over all ``n_experts``, the shared experts whole, and of the routed experts
what this chip holds: a token's ``num_experts_per_tok`` choices land on a
held expert with probability ``num_experts / n_experts``, so on average
``8 x 16 / 128 = 1`` expert a token.  Window layers are counted at
``min(keys, sliding_window)`` keys a query.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * dh + 2 * d * cfg["num_key_value_heads"] * dh


def expert_params(cfg: dict) -> int:
    """One SwiGLU expert, routed or shared."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["n_experts"] * cfg["hidden_size"]


def layer_params_held(cfg: dict) -> int:
    """Matmul weights of one layer on this chip: attention, router, the
    shared experts and the ``num_experts`` routed experts held."""
    return (
        attention_params(cfg) + router_params(cfg)
        + (cfg["num_shared_experts"] + cfg["num_experts"]) * expert_params(cfg)
    )


def head_params(cfg: dict) -> int:
    """The tied embedding / head slice."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def params_held(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * layer_params_held(cfg) + head_params(cfg)


def matmul_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of every weight held, each held expert once: what a decode tick
    streams when every held expert gets a row.  A tick that misses an expert
    streams less (``moe.rows_per_expert.mean`` and ``d_moe_expert_groups``
    say how many it reached)."""
    return itemsize * params_held(cfg)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one cached position over all layers (a window layer keeps
    a position only while it is inside the window)."""
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def _window_layers(cfg: dict) -> int:
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return sum(1 for kind in kinds if kind == "sliding_attention")


def window_keys(cfg: dict, n_tokens: int, sum_keys: int) -> int:
    """Keys a window layer's queries see, given what a full layer's see:
    one decoded token at context c (``n_tokens == 1``, ``sum_keys == c``)
    sees ``min(c, window)``; a from-zero prefill of n tokens (``sum_keys ==
    n (n + 1) / 2``) sees ``sum_p min(p + 1, window)``."""
    w = cfg["sliding_window"]
    if n_tokens == 1:
        return min(sum_keys, w)
    if sum_keys != n_tokens * (n_tokens + 1) // 2:
        raise ValueError("window_keys counts one decoded token or a from-zero prefill")
    full = min(n_tokens, w)
    return full * (full + 1) // 2 + (n_tokens - full) * w


def forward_flops(cfg: dict, n_tokens: int, sum_keys: int, n_head_tokens: int) -> float:
    """FLOPs a forward pass of this chip's share requires for ``n_tokens``
    positions whose full-attention layers see ``sum_keys`` keys in total,
    with the head applied at ``n_head_tokens`` of them."""
    layers = cfg["num_hidden_layers"]
    window_layers = _window_layers(cfg)
    routed_here = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["n_experts"]
    per_token = (
        attention_params(cfg) + router_params(cfg)
        + (cfg["num_shared_experts"] + routed_here) * expert_params(cfg)
    )
    blocks = 2.0 * layers * per_token * n_tokens
    # QK^T and AV: 2 * heads * head_dim each per (query, key) pair.
    d_attn = cfg["num_attention_heads"] * cfg["head_dim"]
    keys = (layers - window_layers) * sum_keys + window_layers * window_keys(
        cfg, n_tokens, sum_keys
    )
    return blocks + 4.0 * d_attn * keys + 2.0 * head_params(cfg) * n_head_tokens
