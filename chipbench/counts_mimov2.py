"""What the MiMo-V2.5 configuration *requires*, computed from shapes (see
``counts.py``): FLOPs and bytes of this chip's share.

``cfg`` is the dict of ``configs/MiMo-V2.5.json``.  A layer's kind is read
from ``hybrid_layer_pattern`` (0 full attention, 1 window) and
``moe_layer_freq`` (0 the dense SwiGLU, 1 the expert layer) over the layers
that are kept.  Counted per layer: q (``hidden x heads x head_dim``), k and v
at the layer's own K/V heads (``head_dim`` and ``v_head_dim`` wide), o
(``heads x v_head_dim x hidden``); the dense SwiGLU or the router over all
``n_experts`` and, of the routed experts, what this chip holds: a token's
``num_experts_per_tok`` choices land on a held expert with probability
``n_routed_experts / n_experts``, so on average ``8 x 16 / 256 = 0.5`` an
expert layer.  Window layers are counted at ``min(keys, sliding_window)`` keys
a query.
"""

from __future__ import annotations


def _kinds(cfg: dict) -> list:
    """``(windowed, dense)`` of each layer that is kept."""
    n = cfg["num_hidden_layers"]
    return list(zip(
        (bool(k) for k in cfg["hybrid_layer_pattern"][:n]),
        (not k for k in cfg["moe_layer_freq"][:n]),
    ))


def full_layers(cfg: dict) -> int:
    return sum(not windowed for windowed, _ in _kinds(cfg))


def window_layers(cfg: dict) -> int:
    return sum(windowed for windowed, _ in _kinds(cfg))


def expert_layers(cfg: dict) -> int:
    return sum(not dense for _, dense in _kinds(cfg))


def kv_heads(cfg: dict, windowed: bool) -> int:
    return cfg["swa_num_key_value_heads"] if windowed else cfg["num_key_value_heads"]


def attention_params(cfg: dict, windowed: bool) -> int:
    """q, k, v and o of one layer of the kind (sinks apart)."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dh, dv, kv = cfg["head_dim"], cfg["v_head_dim"], kv_heads(cfg, windowed)
    return d * heads * dh + d * kv * dh + d * kv * dv + heads * dv * d


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed SwiGLU expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["n_experts"] * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    """The embedding's slice and the head's, untied."""
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]


def params_held(cfg: dict) -> int:
    """Matmul weights on this chip (norms, sinks and biases left out)."""
    total = head_params(cfg)
    for windowed, dense in _kinds(cfg):
        total += attention_params(cfg, windowed)
        total += dense_ffn_params(cfg) if dense else (
            router_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg)
        )
    return total


def matmul_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of every weight a decode tick streams when every held expert
    gets a row: all that is held but the embedding, which is a row gather."""
    return itemsize * (params_held(cfg) - head_params(cfg) // 2)


def kv_bytes_per_position(cfg: dict, windowed: bool, itemsize: int = 2) -> int:
    """Keys and values of one cached position of one layer of the kind: K
    at ``head_dim``, V at ``v_head_dim``, no padding (2,560 B full, 5,120 B
    window)."""
    return kv_heads(cfg, windowed) * (cfg["head_dim"] + cfg["v_head_dim"]) * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One cached position over all layers (a window layer keeps a position
    only while it is inside the window)."""
    return (
        full_layers(cfg) * kv_bytes_per_position(cfg, False, itemsize)
        + window_layers(cfg) * kv_bytes_per_position(cfg, True, itemsize)
    )


def held_experts_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["n_experts"]


def window_keys(cfg: dict, n_tokens: int, sum_keys: int) -> int:
    """Keys a window layer's queries see, given what a full layer's see
    (``counts_cohere2moe.window_keys``)."""
    w = cfg["sliding_window"]
    if n_tokens == 1:
        return min(sum_keys, w)
    if sum_keys != n_tokens * (n_tokens + 1) // 2:
        raise ValueError("window_keys counts one decoded token or a from-zero prefill")
    full = min(n_tokens, w)
    return full * (full + 1) // 2 + (n_tokens - full) * w


def pair_flops(cfg: dict) -> float:
    """A visible (query, key) pair of one layer: QK^T at ``head_dim`` and AV
    at ``v_head_dim``, 2 FLOPs a multiply-add, every query head."""
    return 2.0 * cfg["num_attention_heads"] * (cfg["head_dim"] + cfg["v_head_dim"])


def forward_flops(cfg: dict, n_tokens: int, sum_keys: int, n_head_tokens: int) -> float:
    """FLOPs a forward pass of this chip's share requires for ``n_tokens``
    positions whose full-attention layers see ``sum_keys`` keys in total,
    with the head applied at ``n_head_tokens`` of them."""
    per_token = 0.0
    for windowed, dense in _kinds(cfg):
        per_token += attention_params(cfg, windowed)
        per_token += dense_ffn_params(cfg) if dense else (
            router_params(cfg) + held_experts_per_token(cfg) * expert_params(cfg)
        )
    keys = full_layers(cfg) * sum_keys + window_layers(cfg) * window_keys(
        cfg, n_tokens, sum_keys
    )
    return (
        2.0 * per_token * n_tokens + pair_flops(cfg) * keys
        + head_params(cfg) * n_head_tokens  # 2 x the head's half of head_params
    )


# The new kernels and the expert layer's grouped matmul: what
# ``layer_metrics/mimo.attn_full_roofline.json``, ``mimo.attn_window_roofline
# .json``, ``mimo.sink_chunk_attention_roofline.json`` and ``mimo.gmm_roofline.json``
# spell out in numbers.


def paged_attention_bytes(cfg: dict, kv_positions: int, windowed: bool) -> float:
    """A cached position of one layer of the kind, read once by the slot
    that holds it (``kv_positions`` counts positions x layers of the kind)."""
    return float(kv_bytes_per_position(cfg, windowed) * kv_positions)


def chunk_attention_flops(cfg: dict, pairs: int) -> float:
    """The chunk kernel's visible pairs (pairs x layers, both kinds)."""
    return pair_flops(cfg) * pairs


def gmm_flops(cfg: dict, rows: int) -> float:
    """A row of the grouped matmul: three matrices of one routed expert."""
    return 2.0 * expert_params(cfg) * rows


def gmm_bytes(cfg: dict, groups: int, itemsize: int = 2) -> float:
    """A non-empty expert group streams its three matrices."""
    return float(itemsize * expert_params(cfg) * groups)
