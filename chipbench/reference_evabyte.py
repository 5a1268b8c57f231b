"""The plain reference of EvaByte (``model_type`` ``evabyte``,
``attention_class`` ``eva``; "EvaByte 6.5B", a byte-level dense model with
"EVA chunked linearized attention"), written from its published
``config.json`` (keys in brackets) and EVA's equations (Zheng, Yuan, Wang,
Kong, *Efficient Attention via Control Variates*, ICLR 2023).

* Norms: ``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + g)``
  [``norm_add_unit_offset``], a learned offset, no bias.
* Embedding ``x_0 = E[byte]`` [``vocab_size`` 320: 256 bytes, 64 specials];
  logits ``norm_f(x_L) W_head^T``, ``num_pred_heads x vocab_size`` of them,
  head-major: head 0 is the next byte [``tie_word_embeddings`` false].
* **The layer** [``num_hidden_layers``]: ``x <- x + W_o o(norm_1 x)``, ``x <-
  x + W_2 (silu(W_1 h) * W_3 h)`` with ``h = norm_2 x`` [``hidden_act``
  silu, ``intermediate_size``]; no biases [``attention_bias``].
* **EVA attention**, ``W`` = ``window_size``, ``C`` = ``chunk_size``, ``d`` =
  ``hidden_size / num_attention_heads``, per head ``h`` with its two learned
  vectors ``mu_h`` (``adaptive_mu_k``) and ``phi_h`` (``adaptive_phi``):
  ``q_i, k_j`` are the projections rotated (RoPE, ``rope_theta``) at their
  absolute positions, ``v_j`` the value projection.

  - chunk ``c`` holds positions ``cC .. cC + C - 1``; its **key summary** is
    ``k~_c = sum_j softmax_j(k_j . mu_h) k_j`` and its **value summary**
    ``v~_c = sum_j softmax_j((k_j . phi_h - |k_j|^2 / 2) / sqrt(d)) v_j``,
    both softmaxes over the chunk's ``C`` rows;
  - query ``i`` lies in window ``w = i // W``; with ``A_i = {j : wW <= j <=
    i}`` and ``B_i = {c : c < w W / C}``,
    ``o_i = (sum_{A_i} e^{q_i.k_j / sqrt(d)} v_j + sum_{B_i} e^{q_i.k~_c /
    sqrt(d)} v~_c) / (sum_{A_i} e^{q_i.k_j / sqrt(d)} + sum_{B_i} e^{q_i.k~_c
    / sqrt(d)})``: one softmax over the exact keys of the query's own window
    and the summaries of every chunk of every earlier window.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
cache, no kernels, no batching.  It imports nothing of
``bpe_transformer_tpu`` and takes nothing the program has made.  A long
sequence is computed **a window of queries at a time** (``window_forward``:
one program of one shape whatever the window, the layers under a
``lax.scan``, every slice inside it): a window's queries need their own
window's keys and the summaries of the windows before, which are carried
from window to window in a buffer a layer.  Both masks are written out as
comparisons of absolute indices.

Departures and what is assumed (the catalog has the config and no
modelling code; ``configs/EvaByte.json`` lists them under ``assumed``): the
key pooling's logits carry no ``1 / sqrt(d)`` and the value pooling's do;
the head's outputs lie head-major; RoPE rotates adjacent pairs ``(2i, 2i +
1)`` - the program's convention; a half-split convention is the same model
under a fixed permutation of each head's q and k columns, which seeded
weights absorb.

``quant="fp8"`` is the control, as in ``reference.py``: every matmul operand
and q, k, v rounded to float8-e4m3 under a per-row scale.
"""

from __future__ import annotations

import functools
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0  # largest finite float8_e4m3fn
LAYER_KEYS = 9  # q, k, v, o, w1, w2, w3, mu, phi


class _Frozen(dict):
    """A configuration dict a jitted function can take as a static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True, default=str))


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# ------------------------------------------------------------------ weights


def _dense(key, shape, std, dtype):
    w = jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
    return (w * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _layer_weights(key, cfg, dtype):
    d, ff, std = cfg["hidden_size"], cfg["intermediate_size"], cfg["init_std"]
    heads, dh = cfg["num_attention_heads"], head_dim(cfg)
    k = jax.random.split(key, LAYER_KEYS)

    def pooling(key):
        w = jax.random.normal(key, (heads, dh), jnp.float32)
        return (jnp.clip(w, -1.0, 1.0) * dh ** -0.25).astype(dtype)

    return {
        "attn": {
            "q_proj": _dense(k[0], (d, d), std, dtype),
            "k_proj": _dense(k[1], (d, d), std, dtype),
            "v_proj": _dense(k[2], (d, d), std, dtype),
            "output_proj": _dense(k[3], (d, d), std, dtype),
            "eva_mu": pooling(k[7]),
            "eva_phi": pooling(k[8]),
        },
        "ln1": jnp.zeros((d,), dtype),
        "ln2": jnp.zeros((d,), dtype),
        "ffn": {
            "w1": _dense(k[4], (ff, d), std, dtype),
            "w2": _dense(k[5], (d, ff), std, dtype),
            "w3": _dense(k[6], (ff, d), std, dtype),
        },
    }


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def _outer_weights(k_embed, k_head, cfg, dtype):
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg["init_std"]
    return {
        "token_embeddings": _dense(k_embed, (v, d), std, dtype),
        "ln_final": jnp.zeros((d,), dtype),
        "lm_head": _dense(k_head, (v * cfg["num_pred_heads"], d), std, dtype),
    }


def weights_from_seed(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    """The benchmark's weights from ``--seed``, in the tree the program
    takes: every matrix a truncated normal (+-3 sigma) times ``init_std``,
    ``mu`` and ``phi`` by the published init (a normal draw clamped to +-1,
    times ``d ** -0.25``), every norm's offset 0.  The key splits into 2 +
    L: the embedding, the head, then a layer each, split into nine.  Drawn
    in float32 and rounded once to ``dtype``; one program a layer, the same
    for every layer and seed."""
    cfg = _Frozen(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 + cfg["num_hidden_layers"])
    dtype = jnp.dtype(dtype)
    out = _outer_weights(keys[0], keys[1], cfg, dtype)
    out["layers"] = [
        _layer_weights(keys[2 + i], cfg, dtype)
        for i in range(cfg["num_hidden_layers"])
    ]
    return out


# ------------------------------------------------------------------ forward


def _fake_fp8(x):
    """Round to float8-e4m3 under a per-row scale."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(x, w, quant):
    """``x @ w.T`` for a ``(d_out, d_in)`` weight."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum("...i,oi->...o", x, w)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + g.astype(jnp.float32)
    )


def _rope(x, positions, theta):
    """Rotate pairs (2i, 2i + 1) of the last axis of ``(heads, rows, d)`` by
    ``positions[row] * theta ** (-2i / d)``."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    ).reshape(x.shape)


def chunk_summaries(k, v, mu, phi):
    """``k``, ``v`` (heads, chunks, C, d) -> ``(k~, v~)`` (heads, chunks, d):
    the two poolings over each chunk's C rows."""
    d = k.shape[-1]
    key_w = jax.nn.softmax(jnp.einsum("hcjd,hd->hcj", k, mu), axis=-1)
    value_w = jax.nn.softmax(
        (jnp.einsum("hcjd,hd->hcj", k, phi) - 0.5 * jnp.sum(k * k, axis=-1))
        / math.sqrt(d),
        axis=-1,
    )
    return (
        jnp.einsum("hcj,hcjd->hcd", key_w, k),
        jnp.einsum("hcj,hcjd->hcd", value_w, v),
    )


def _layer(x, p, summaries, window, cfg, quant):
    """One layer over the positions of window ``window`` (traced): ``x`` (W,
    hidden); ``summaries`` = (k~, v~) buffers (heads, all chunks, d) of this
    layer holding the earlier windows' rows.  Returns the window's output
    and the buffers with this window's summaries written in."""
    width, per_chunk = cfg["window_size"], cfg["chunk_size"]
    heads, dh, eps = cfg["num_attention_heads"], head_dim(cfg), cfg["rms_norm_eps"]
    attn = p["attn"]
    positions = window * width + jnp.arange(width)
    a = _norm(x, p["ln1"], eps)

    def split(t):
        return t.reshape(width, heads, dh).transpose(1, 0, 2)

    q = _rope(split(_matmul(a, attn["q_proj"], quant)), positions, cfg["rope_theta"])
    k = _rope(split(_matmul(a, attn["k_proj"], quant)), positions, cfg["rope_theta"])
    v = split(_matmul(a, attn["v_proj"], quant))
    if quant == "fp8":
        q, k, v = _fake_fp8(q), _fake_fp8(k), _fake_fp8(v)
    chunks = width // per_chunk
    k_sum, v_sum = chunk_summaries(
        k.reshape(heads, chunks, per_chunk, dh), v.reshape(heads, chunks, per_chunk, dh),
        attn["eva_mu"].astype(jnp.float32), attn["eva_phi"].astype(jnp.float32),
    )
    k_all, v_all = summaries
    # A_i: key j of the query's own window, not after it.
    j = positions[None, :]
    i = positions[:, None]
    in_a = (window * width <= j) & (j <= i)
    # B_i: chunk c of an earlier window (the buffer holds chunk c at row c).
    c = jnp.arange(k_all.shape[1])[None, :]
    in_b = jnp.broadcast_to(c < window * chunks, (width, k_all.shape[1]))
    scores = jnp.concatenate(
        [jnp.einsum("hqd,hkd->hqk", q, k), jnp.einsum("hqd,hcd->hqc", q, k_all)],
        axis=-1,
    ) / math.sqrt(dh)
    scores = jnp.where(jnp.concatenate([in_a, in_b], axis=-1), scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", weights, jnp.concatenate([v, v_all], axis=1))
    x = x + _matmul(o.transpose(1, 0, 2).reshape(width, heads * dh), attn["output_proj"], quant)
    f = _norm(x, p["ln2"], eps)
    gate = jax.nn.silu(_matmul(f, p["ffn"]["w1"], quant)) * _matmul(f, p["ffn"]["w3"], quant)
    x = x + _matmul(gate, p["ffn"]["w2"], quant)
    at = (0, window * chunks, 0)
    return x, (
        jax.lax.dynamic_update_slice(k_all, k_sum, at),
        jax.lax.dynamic_update_slice(v_all, v_sum, at),
    )


@functools.partial(jax.jit, static_argnames=("cfg", "quant"), donate_argnums=(3,))
def window_forward(stacked, outer, tokens, summaries, window, cfg, quant=None):
    """``tokens`` (W,), the ids at the positions of window ``window``
    (traced) -> their final-norm hidden states (W, hidden), and the layers'
    summary buffers ``(k~, v~)`` (layers, heads, all chunks, d) with this
    window's rows written.  ``stacked`` is the layers' weights, a leading
    layer axis on each."""
    with jax.default_matmul_precision("highest"):
        x = outer["token_embeddings"].astype(jnp.float32)[tokens]

        def body(x, per_layer):
            p, summaries = per_layer
            x, summaries = _layer(x, p, summaries, window, cfg, quant)
            return x, summaries

        x, summaries = jax.lax.scan(body, x, (stacked, summaries))
        return _norm(x, outer["ln_final"], cfg["rms_norm_eps"]), summaries


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def head_logits(hidden, outer, cfg, quant=None):
    """Final-norm rows (rows, hidden) -> float32 logits (rows, heads *
    vocab), head-major."""
    with jax.default_matmul_precision("highest"):
        return _matmul(hidden, outer["lm_head"], quant)


@jax.jit
def stack_layers(layers):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def empty_summaries(cfg: dict):
    chunks = cfg["max_position_embeddings"] // cfg["chunk_size"]
    shape = (cfg["num_hidden_layers"], cfg["num_attention_heads"], chunks, head_dim(cfg))
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def windows_of(w: dict, tokens, cfg: dict, quant: str | None = None):
    """The final-norm hidden states of ``tokens`` (n,), a window (W, hidden)
    at a time, in order.  The sequence is padded with id 0 to whole
    windows: nothing behind a position changes it."""
    cfg = _Frozen(cfg)
    width = cfg["window_size"]
    tokens = np.asarray(tokens, np.int32)
    padded = np.zeros(-(-len(tokens) // width) * width, np.int32)
    padded[: len(tokens)] = tokens
    stacked = stack_layers(w["layers"])
    outer = {name: w[name] for name in ("token_embeddings", "ln_final", "lm_head")}
    summaries = empty_summaries(cfg)
    for window in range(len(padded) // width):
        hidden, summaries = window_forward(
            stacked, outer, jnp.asarray(padded[window * width: (window + 1) * width]),
            summaries, jnp.int32(window), cfg, quant,
        )
        yield hidden, outer


def forward_logits(w: dict, tokens, cfg: dict, quant: str | None = None) -> np.ndarray:
    """``(n,)`` ids -> ``(n, num_pred_heads * vocab_size)`` float32 logits of
    every position and prediction head (the tests' oracle; ``served_gaps``
    reads head 0 a window at a time)."""
    cfg = _Frozen(cfg)
    rows = [
        np.asarray(head_logits(hidden, outer, cfg, quant))
        for hidden, outer in windows_of(w, tokens, cfg, quant)
    ]
    return np.concatenate(rows)[: len(tokens)]


# ------------------------------------------------------------------ serving


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _head0(hidden, outer, chosen, cfg, quant=None):
    """Of prediction head 0 at every row: the gap by which ``chosen``'s
    logit lies below the best, and the best's id."""
    logits = head_logits(hidden, outer, cfg, quant)[:, : cfg["vocab_size"]]
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - picked, jnp.argmax(logits, axis=-1)


def served_gaps(seed: int, cfg: dict, sequences: list, *, control: bool = False) -> list:
    """For each ``(prompt_ids, served_ids)``: one full forward over prompt +
    served tokens, a window at a time, and at each served position the gap
    by which the served byte's logit (prediction head 0) lies below the
    reference's best.  Returns the widest gap of each sequence.

    Weights are the seed's, rounded to bfloat16 as they are served.  With
    ``control=True`` the served bytes are ignored: at each of the same
    positions the gap is read for the byte the float8 forward puts first.
    Each sequence's numbers go to standard error for the record."""
    cfg = _Frozen(cfg)
    width = cfg["window_size"]
    w = weights_from_seed(seed, cfg, jnp.bfloat16)
    out = []
    for prompt, served in sequences:
        ids = np.asarray(list(prompt) + list(served), np.int32)
        fed, nxt = ids[:-1], ids[1:]
        padded = -(-len(fed) // width) * width
        chosen = np.zeros(padded, np.int32)
        chosen[: len(nxt)] = nxt
        t0 = time.perf_counter()
        if control:
            chosen = np.concatenate([
                np.asarray(_head0(
                    hidden, outer, jnp.zeros(width, jnp.int32), cfg, "fp8"
                )[1])
                for hidden, outer in windows_of(w, fed, cfg, "fp8")
            ]).astype(np.int32)
        gaps = np.concatenate([
            np.asarray(_head0(
                hidden, outer,
                jnp.asarray(chosen[i * width: (i + 1) * width]), cfg,
            )[0])
            for i, (hidden, outer) in enumerate(windows_of(w, fed, cfg))
        ])
        scored = gaps[len(prompt) - 1: len(fed)]
        print(json.dumps({
            "served_gaps": "control" if control else "sound",
            "prompt": len(prompt), "served": len(served),
            "windows": padded // width, "widest": float(scored.max()),
            "mean": float(scored.mean()),
            "forward_s": round(time.perf_counter() - t0, 2),
        }), file=sys.stderr)
        out.append(float(scored.max()))
    return out
