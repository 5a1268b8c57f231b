"""The plain reference of the repo's decoder block, written from its
equations: token embedding, pre-norm RMSNorm (eps 1e-5), interleaved RoPE
(theta from the configuration), causal multi-head attention, SwiGLU, untied
LM head, mean cross-entropy, global-norm clipping and AdamW under a linear
warm-up / cosine schedule.

Float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no batching tricks.  It imports nothing of
``bpe_transformer_tpu`` and takes nothing the program has made: weights come
from :func:`init_weights` (the benchmark's weights from ``--seed``), inputs
from the benchmark's own generators.

``quant="fp8"`` is the *control* of "How correct is decided": the same
mathematics with every matmul operand rounded to float8-e4m3 under a
per-row scale (straight-through in the backward pass) — the nearest
precision below the bfloat16 the configurations state.  It has to come out
as *not* correct; ``chipbench/control.py`` reads it on the chip.

``cfg`` is the dict of a ``configs/<name>.json`` file.  A configuration
with another block brings its own module with these functions and names it
in its file (``"reference": "<module>"``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-5
INIT_STD = 0.02
FP8_MAX = 448.0  # largest finite float8_e4m3fn
PAD_TO = 256  # served sequences are scored at lengths padded to this


# ------------------------------------------------------------------ weights


def init_weights(key, cfg: dict, dtype=jnp.float32) -> dict:
    """The benchmark's weights from ``key = jax.random.PRNGKey(seed)``:
    truncated normal (+-3 sigma) times 0.02 for every matrix, ones for every
    norm.  Key derivation: the key split into 2 + L; key 0 the embedding,
    key 1 the head, key 2 + i split into 7 for block i (q, k, v, o, w1, w2,
    w3).  Drawn in float32 and rounded once to ``dtype``.  The key is an
    argument, not a constant, so one compiled program serves every seed."""
    d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    d_head = d // cfg["num_heads"]
    d_kv = (cfg.get("num_kv_heads") or cfg["num_heads"]) * d_head

    def dense(key, d_out, d_in):
        w = jax.random.truncated_normal(key, -3.0, 3.0, (d_out, d_in), jnp.float32)
        return (w * INIT_STD).astype(dtype)

    keys = jax.random.split(key, 2 + cfg["num_layers"])
    layers = []
    for i in range(cfg["num_layers"]):
        k = jax.random.split(keys[2 + i], 7)
        layers.append(
            {
                "attn": {
                    "q_proj": dense(k[0], d, d),
                    "k_proj": dense(k[1], d_kv, d),
                    "v_proj": dense(k[2], d_kv, d),
                    "output_proj": dense(k[3], d, d),
                },
                "ln1": jnp.ones((d,), dtype),
                "ln2": jnp.ones((d,), dtype),
                "ffn": {
                    "w1": dense(k[4], ff, d),
                    "w2": dense(k[5], d, ff),
                    "w3": dense(k[6], ff, d),
                },
            }
        )
    return {
        "token_embeddings": dense(keys[0], v, d),
        "layers": layers,
        "ln_final": jnp.ones((d,), dtype),
        "lm_head": dense(keys[1], v, d),
    }


def weights_from_seed(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    """One jitted call on the device."""
    return jax.jit(lambda key: init_weights(key, cfg, dtype))(jax.random.PRNGKey(seed))


def leaf_names(tree) -> list[str]:
    return [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


# ------------------------------------------------------------------ forward


def _fake_fp8(x):
    """Round to float8-e4m3 under a per-row scale; identity gradient."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _matmul(x, w, quant):
    """``x @ w.T`` for a ``(d_out, d_in)`` weight."""
    if quant == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum("...i,oi->...o", x, w)


def _rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * g


def _rope(x, theta):
    """Rotate pairs (2i, 2i+1) of the last axis of ``(..., S, d_head)`` by
    position * theta^(-2i/d_head)."""
    s, dh = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape)


def _block(x, p, cfg, quant):
    b, s, d = x.shape
    h = cfg["num_heads"]
    kvh = cfg.get("num_kv_heads") or h
    dh = d // h
    a = _rmsnorm(x, p["ln1"])

    def heads(t, n):
        return t.reshape(b, s, n, dh).transpose(0, 2, 1, 3)

    q = _rope(heads(_matmul(a, p["attn"]["q_proj"], quant), h), cfg["rope_theta"])
    k = _rope(heads(_matmul(a, p["attn"]["k_proj"], quant), kvh), cfg["rope_theta"])
    v = heads(_matmul(a, p["attn"]["v_proj"], quant), kvh)
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
    if quant == "fp8":
        q, k, v = _fake_fp8(q), _fake_fp8(k), _fake_fp8(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attended = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    merged = attended.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + _matmul(merged, p["attn"]["output_proj"], quant)
    f = _rmsnorm(x, p["ln2"])
    gate = jax.nn.silu(_matmul(f, p["ffn"]["w1"], quant)) * _matmul(f, p["ffn"]["w3"], quant)
    return x + _matmul(gate, p["ffn"]["w2"], quant)


def forward_logits(w: dict, tokens, cfg: dict, quant: str | None = None):
    """``(B, S)`` token ids -> ``(B, S, V)`` float32 logits."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        x = w["token_embeddings"][tokens]
        for p in w["layers"]:
            # Checkpointed per block so the backward pass of a row block
            # holds one block's intermediates, not twelve.
            x = jax.checkpoint(lambda x, p: _block(x, p, cfg, quant))(x, p)
        return _matmul(_rmsnorm(x, w["ln_final"]), w["lm_head"], quant)


def lm_loss(w: dict, x, y, cfg: dict, quant: str | None = None):
    """Mean next-token cross-entropy over every position of every row."""
    logits = forward_logits(w, x, cfg, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


# ----------------------------------------------------------------- training


def schedule(it, hp: dict):
    """Learning rate of optimizer step ``it`` (0-based): linear warm-up,
    then cosine to the minimum at ``cosine_cycle_iters``."""
    it = jnp.asarray(it, jnp.float32)
    hi, lo = hp["max_learning_rate"], hp["min_learning_rate"]
    warm, cycle = hp["warmup_iters"], hp["cosine_cycle_iters"]
    progress = (it - warm) / (cycle - warm)
    cos = lo + 0.5 * (1.0 + jnp.cos(jnp.pi * progress)) * (hi - lo)
    out = jnp.where(it < warm, it / warm * hi, cos)
    return jnp.where(it > cycle, lo, out)


def _leaf_norms(tree):
    return jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
         for a in jax.tree_util.tree_leaves(tree)]
    )


def reference_train(
    seed: int,
    cfg: dict,
    hp: dict,
    batches: list,
    *,
    quant: str | None = None,
    rows_per_block: int = 8,
) -> dict:
    """Follow the first ``len(batches)`` optimizer steps from the seed's
    weights.  ``batches`` = [(x, y)] of ``(B, S)`` ints.  Gradients are
    accumulated over blocks of rows so the float32 pass fits beside nothing
    else.  Returns per-step losses, the per-leaf norms of the first
    gradient *as the optimizer gets it* (after clipping), and the per-leaf
    norms of the parameters' change after the last step."""
    b1, b2 = hp["betas"]
    tree = jax.tree_util
    w0 = weights_from_seed(seed, cfg)
    grad_fn = jax.jit(
        jax.value_and_grad(lambda w, x, y: lm_loss(w, x, y, cfg, quant))
    )

    @jax.jit
    def update(w, m, v, g, step):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in tree.tree_leaves(g)))
        clip = jnp.minimum(1.0, hp["grad_clip_norm"] / (norm + 1e-6))
        g = tree.tree_map(lambda a: a * clip, g)
        lr = schedule(step, hp)
        t = (step + 1).astype(jnp.float32)
        m = tree.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tree.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)

        def leaf(p, m, v):
            m_hat, v_hat = m / (1 - b1**t), v / (1 - b2**t)
            return p * (1 - lr * hp["weight_decay"]) - lr * m_hat / (
                jnp.sqrt(v_hat) + hp["eps"]
            )

        return tree.tree_map(leaf, w, m, v), m, v, _leaf_norms(g)

    w = w0
    zeros = tree.tree_map(jnp.zeros_like, w0)
    m, v = zeros, zeros
    losses, first_grad = [], None
    for step, (x, y) in enumerate(batches):
        x, y = np.asarray(x), np.asarray(y)
        n_blocks = x.shape[0] // rows_per_block
        if n_blocks * rows_per_block != x.shape[0]:
            raise ValueError(f"{x.shape[0]} rows do not divide into blocks of {rows_per_block}")
        loss_sum, g_sum = 0.0, zeros
        for i in range(n_blocks):
            rows = slice(i * rows_per_block, (i + 1) * rows_per_block)
            loss, g = grad_fn(w, jnp.asarray(x[rows]), jnp.asarray(y[rows]))
            loss_sum = loss_sum + loss
            g_sum = tree.tree_map(jnp.add, g_sum, g)
        g = tree.tree_map(lambda a: a / n_blocks, g_sum)
        losses.append(float(loss_sum / n_blocks))
        w, m, v, g_norms = update(w, m, v, g, jnp.asarray(step, jnp.int32))
        if first_grad is None:
            first_grad = np.asarray(g_norms)
    change = np.asarray(
        jax.jit(lambda a, b: _leaf_norms(tree.tree_map(jnp.subtract, a, b)))(w, w0)
    )
    return {
        "losses": losses,
        "first_grad_leaf_norms": first_grad,
        "change_leaf_norms": change,
        "leaf_names": leaf_names(w0),
    }


# ------------------------------------------------------------------ serving


def served_gaps(seed: int, cfg: dict, sequences: list, *, control: bool = False) -> list:
    """For each ``(prompt_ids, served_ids)``: one full forward over prompt +
    served tokens, and at each served position the gap by which the served
    token's logit lies below the reference's best.  Returns the widest gap
    of each sequence.

    Weights are the seed's, rounded to bfloat16 as they are served.  With
    ``control=True`` the served tokens are ignored: at each of the same
    positions the gap is read for the token the float8 forward puts first."""
    w = weights_from_seed(seed, cfg, jnp.bfloat16)

    @jax.jit
    def gaps(w, tokens):
        logits = forward_logits(w, tokens[None, :-1], cfg)[0]
        if control:
            chosen = jnp.argmax(forward_logits(w, tokens[None, :-1], cfg, "fp8")[0], axis=-1)
        else:
            chosen = tokens[1:]
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    out = []
    for prompt, served in sequences:
        ids = list(prompt) + list(served)
        # Causal: padding behind the last token changes nothing before it,
        # and a few padded lengths mean a few compiles.
        padded = -(-len(ids) // PAD_TO) * PAD_TO
        tokens = jnp.asarray(ids + [0] * (padded - len(ids)), jnp.int32)
        g = np.asarray(gaps(w, tokens))
        out.append(float(g[len(prompt) - 1:len(ids) - 1].max()))
    return out
