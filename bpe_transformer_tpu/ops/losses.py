"""Training losses as pure XLA ops (optax-free).

Reference contract: `run_cross_entropy` (`/root/reference/tests/
adapters.py:440-455`) — mean cross-entropy over examples, stable at 1000x
logit scale (pinned by `test_nn_utils.py:27-59`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array, lax
from jax.scipy.special import logsumexp

from bpe_transformer_tpu.ops.core import head_logits


@jax.named_scope("loss")
def cross_entropy(logits: Array, targets: Array) -> Array:
    """Mean negative log-likelihood of ``targets`` under ``logits``.

    ``logits: (..., vocab)``, ``targets: (...)`` integer class ids.  Uses
    logsumexp (float32 accumulation) so arbitrarily scaled logits stay
    finite.
    """
    logits32 = logits.astype(jnp.float32)
    target_logit = jnp.take_along_axis(
        logits32, targets[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    nll = logsumexp(logits32, axis=-1) - target_logit
    return nll.mean()


@jax.named_scope("loss")
def chunked_lm_cross_entropy(
    hidden: Array,
    lm_head_w: Array,
    targets: Array,
    chunk_size: int,
) -> Array:
    """Mean LM cross-entropy WITHOUT materializing full logits.

    ``hidden: (batch, seq, d_model)``, ``lm_head_w: (vocab, d_model)``,
    ``targets: (batch, seq)``.  The sequence axis is processed in
    ``chunk_size`` slices inside a ``lax.map``; each chunk projects to the
    vocab, reduces to its NLL, and is rematerialized on the backward pass —
    peak activation memory drops from ``O(seq * vocab)`` to
    ``O(chunk * vocab)``, the enabling trick for 32k-vocab configs at long
    context.  Numerically identical to
    ``cross_entropy(hidden @ lm_head.T, targets)``.
    """
    batch, seq, d = hidden.shape
    if seq % chunk_size:
        raise ValueError(
            f"seq {seq} not divisible by loss chunk_size {chunk_size}"
        )
    n_chunks = seq // chunk_size
    h = hidden.reshape(batch, n_chunks, chunk_size, d).swapaxes(0, 1)
    t = targets.reshape(batch, n_chunks, chunk_size).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_nll(args):
        hc, tc = args  # (batch, chunk, d), (batch, chunk)
        # head_logits: activation-dtype matmul, f32 accumulation — full MXU
        # rate on the bf16 path, f32 logsumexp stability either way.
        logits = head_logits(hc, lm_head_w)
        target_logit = jnp.take_along_axis(
            logits, tc[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        return (logsumexp(logits, axis=-1) - target_logit).sum()

    total = lax.map(chunk_nll, (h, t)).sum()
    return total / (batch * seq)


def lm_loss(
    hidden: Array,
    lm_head_w: Array,
    targets: Array,
    chunk_size: int | None,
) -> Array:
    """LM cross-entropy from final hidden states, chunking when possible.

    The one shared guard for every loss path (single-device train/eval,
    pipeline head loss, sequence-parallel shards): clamp ``chunk_size`` to
    the actual sequence — callers may evaluate truncated inputs — and fall
    back to full logits when the chunk doesn't divide it.
    """
    seq = hidden.shape[-2]
    chunk = min(chunk_size, seq) if chunk_size else None
    if chunk and seq % chunk == 0:
        return chunked_lm_cross_entropy(hidden, lm_head_w, targets, chunk)
    return cross_entropy(head_logits(hidden, lm_head_w), targets)
