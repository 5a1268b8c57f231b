"""What the EvaByte configuration *requires*, computed from shapes (see
``counts.py``): FLOPs and bytes of this chip's share, stage 0 of a four-stage
pipeline - ``num_hidden_layers`` whole layers, the embedding and the head.

``cfg`` is the dict of ``configs/EvaByte.json``.  A query at position ``i``
attends ``rows_attended(cfg, i)`` rows: one summary for every chunk of every
window before its own, then its own window up to itself - not ``i + 1``.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg: dict) -> int:
    """q, k, v, o, the three SwiGLU matrices, two norms, mu and phi."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * d * d + 3 * d * ff + 2 * d + 2 * cfg["num_attention_heads"] * head_dim(cfg)


def layer_matmul_params(cfg: dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * d * d + 3 * d * ff


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    """Every prediction head: ``num_pred_heads x vocab_size`` outputs."""
    return cfg["num_pred_heads"] * cfg["vocab_size"] * cfg["hidden_size"]


def params_held(cfg: dict) -> int:
    return (
        cfg["num_hidden_layers"] * layer_params(cfg) + embedding_params(cfg)
        + head_params(cfg) + cfg["hidden_size"]
    )


def matmul_weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of the weights every decode tick streams: all layers' matrices
    and the head (the embedding is a row gather)."""
    return itemsize * (
        cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_params(cfg)
    )


def row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one cached row of one layer - a position's or a chunk's
    summary: ``2 x heads x head_dim`` values."""
    return 2 * cfg["num_attention_heads"] * head_dim(cfg) * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One cached row over all layers held.  A position costs a row while
    its window is open and a ``chunk_size``-th of one once it has closed."""
    return cfg["num_hidden_layers"] * row_bytes(cfg, itemsize)


def chunks_per_window(cfg: dict) -> int:
    return cfg["window_size"] // cfg["chunk_size"]


def summaries_visible(cfg: dict, position: int) -> int:
    """Summary rows the query at ``position`` attends: every chunk of every
    earlier window."""
    return position // cfg["window_size"] * chunks_per_window(cfg)


def rows_attended(cfg: dict, position: int) -> int:
    return summaries_visible(cfg, position) + position % cfg["window_size"] + 1


def rows_held(cfg: dict, context: int) -> int:
    """Rows a sequence of ``context`` positions keeps: the summaries of its
    closed windows, the open window's pending ones and its exact rows."""
    last = context - 1
    pending = last % cfg["window_size"] // cfg["chunk_size"]
    return rows_attended(cfg, last) + pending


def prompt_pairs(cfg: dict, n: int) -> int:
    """(query, row) pairs of a prompt of ``n`` positions from position 0."""
    width, per_window = cfg["window_size"], chunks_per_window(cfg)
    whole, rest = divmod(n, width)
    # Window w: width queries, each w * per_window summaries, and the
    # causal triangle of the window.
    pairs = per_window * width * whole * (whole - 1) // 2 + whole * width * (width + 1) // 2
    return pairs + rest * whole * per_window + rest * (rest + 1) // 2


def forward_flops(cfg: dict, n_tokens: int, sum_keys: int, n_head_tokens: int) -> float:
    """FLOPs a forward pass of this chip's share requires for ``n_tokens``
    positions, the head (all prediction heads) applied at ``n_head_tokens``.
    ``sum_keys`` is what a causal model would attend - the harness's own
    count: a decoded token's ``position + 1``, a prompt's ``n (n + 1) /
    2`` - and is turned into the rows this attention attends."""
    if n_tokens == 1:
        pairs = rows_attended(cfg, sum_keys - 1)
    elif sum_keys == n_tokens * (n_tokens + 1) // 2:
        pairs = prompt_pairs(cfg, n_tokens)
    else:
        raise ValueError("neither one decoded position nor a prompt from position 0")
    layers = cfg["num_hidden_layers"]
    blocks = 2.0 * layers * layer_matmul_params(cfg) * n_tokens
    # QK^T and AV: 2 x heads x head_dim each per (query, row) pair per layer.
    attention = 4.0 * cfg["hidden_size"] * layers * pairs
    # A summary: two poolings' logits and two weighted sums over its rows.
    summaries = 8.0 * cfg["hidden_size"] * layers * (n_tokens // cfg["chunk_size"]) * cfg["chunk_size"]
    return blocks + attention + summaries + 2.0 * head_params(cfg) * n_head_tokens


# The tick's attention is the dense pool's paged kernel over this cache's
# rows (``layer_metrics/eva_attention_roofline.json`` spells it out in
# numbers): bandwidth-bound, a row streamed once by the slot that holds it.


def eva_attention_bytes(cfg: dict, rows_x_layers: int, itemsize: int = 2) -> float:
    return float(row_bytes(cfg, itemsize) * rows_x_layers)


def eva_attention_flops(cfg: dict, rows_x_layers: int) -> float:
    return 4.0 * cfg["hidden_size"] * rows_x_layers
