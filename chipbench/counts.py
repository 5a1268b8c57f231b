"""What the algorithm *requires*, computed from shapes: FLOPs and bytes.

Every share of a peak that the benchmark reports divides one of these by a
measured time and a number from ``peaks.json``.  They count what the causal
forward (and, for training, backward) pass needs and nothing the program
chooses to do on top: recomputation under remat is not counted, the
embedding lookup is a gather (0 FLOPs), and attention is counted causally
(position p attends to p + 1 keys), not as the full S x S square.

``cfg`` is the dict of a ``configs/<name>.json`` file.  A configuration
with another block brings its own counts module and names it in its file
(``"counts": "<module>"``).
"""

from __future__ import annotations


def _kv_heads(cfg: dict) -> int:
    return cfg.get("num_kv_heads") or cfg["num_heads"]


def _d_head(cfg: dict) -> int:
    return cfg["d_model"] // cfg["num_heads"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block's matmuls: q, k, v, o and the three SwiGLU
    matrices."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    d_kv = _kv_heads(cfg) * _d_head(cfg)
    return 2 * d * d + 2 * d * d_kv + 3 * d * ff


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["d_model"]


def matmul_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of the weights every decode tick has to stream: all blocks and
    the LM head (the embedding is a row gather, not a stream)."""
    return itemsize * (
        cfg["num_layers"] * layer_matmul_params(cfg) + head_params(cfg)
    )


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one cached position over all layers."""
    return 2 * cfg["num_layers"] * _kv_heads(cfg) * _d_head(cfg) * itemsize


def forward_flops(
    cfg: dict, n_tokens: int, sum_keys: int, n_head_tokens: int
) -> float:
    """FLOPs a forward pass requires for ``n_tokens`` token positions that
    attend to ``sum_keys`` keys in total (position p sees p + 1), with the
    LM head applied at ``n_head_tokens`` of them."""
    d = cfg["num_heads"] * _d_head(cfg)
    blocks = 2.0 * cfg["num_layers"] * layer_matmul_params(cfg) * n_tokens
    # QK^T and AV: 2 * d each per (query, key) pair per layer.
    attention = 4.0 * d * cfg["num_layers"] * sum_keys
    head = 2.0 * head_params(cfg) * n_head_tokens
    return blocks + attention + head


def train_flops_per_token(cfg: dict, seq_len: int | None = None) -> float:
    """Forward + backward (2x forward) of the causal LM at ``seq_len``,
    per trained token."""
    s = seq_len or cfg["context_length"]
    sum_keys = s * (s + 1) // 2
    return 3.0 * forward_flops(cfg, s, sum_keys, s) / s


def serve_flops(cfg: dict, requests) -> float:
    """FLOPs required by ``requests`` = iterable of (prompt_len, n_out):
    the prompt prefilled (head at its last position only) and
    ``n_out - 1`` further tokens decoded, each seeing all before it."""
    total = 0.0
    for prompt_len, n_out in requests:
        n = prompt_len + max(n_out - 1, 0)
        total += forward_flops(cfg, n, n * (n + 1) // 2, max(n_out, 0))
    return total
