"""Training losses as pure XLA ops (optax-free).

Reference contract: `run_cross_entropy` (`/root/reference/tests/
adapters.py:440-455`) — mean cross-entropy over examples, stable at 1000x
logit scale (pinned by `test_nn_utils.py:27-59`).
"""

from __future__ import annotations

import functools

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


def _split_chunks(hidden: Array, targets: Array, chunk_size: int):
    """Chunk-major views: ``(n_chunks, batch, chunk, d)`` and its targets."""
    batch, seq, d = hidden.shape
    n_chunks = seq // chunk_size
    h = hidden.reshape(batch, n_chunks, chunk_size, d).swapaxes(0, 1)
    t = targets.reshape(batch, n_chunks, chunk_size).swapaxes(0, 1)
    return h, t


def _chunk_terms(hc: Array, tc: Array, head_w: Array):
    """One chunk's logits reduced: its summed NLL, ``exp(logits - max)`` and
    that exponential's sum over the vocabulary (`logsumexp`'s own steps, so
    the gradient below can be made of its parts)."""
    # head_logits: activation-dtype matmul, f32 accumulation — full MXU
    # rate on the bf16 path, f32 logsumexp stability either way.
    logits = head_logits(hc, head_w)
    target_logit = jnp.take_along_axis(
        logits, tc[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    top = logits.max(axis=-1)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    weights = jnp.exp(logits - top[..., None])
    total = weights.sum(axis=-1)
    nll = (jnp.log(total) + top - target_logit).sum()
    return nll, weights, total


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunked_nll(
    hidden: Array, lm_head_w: Array, targets: Array, chunk_size: int
) -> Array:
    """The loss alone (evaluation, any call outside a ``grad``): a chunk's
    logits, their reduction, the sum.  No gradient work."""
    batch, seq, _ = hidden.shape
    nll = lax.map(
        lambda chunk: _chunk_terms(*chunk, lm_head_w)[0],
        _split_chunks(hidden, targets, chunk_size),
    )
    return nll.sum() / (batch * seq)


def _chunked_nll_fwd(hidden, lm_head_w, targets, chunk_size):
    """Loss AND gradients in one loop, each chunk's logits made once.

    ``dlogits = (softmax(logits) - onehot(target)) / N`` needs nothing the
    loss's own pass does not hold, so the chunk's two gradient products are
    made while its logits are live.  Operand and result dtypes are those of
    the transposes XLA derives for `_chunk_terms` (on the bf16 path: float32
    ``dlogits`` against bfloat16 operands, float32 out, each chunk's share
    rounded to the activation dtype as the cotangent of `head_logits`' cast
    is, the head's accumulated in float32).
    """
    batch, seq, _ = hidden.shape
    inv_n = 1.0 / (batch * seq)
    head_w = lm_head_w.astype(hidden.dtype)

    def chunk_step(dw, chunk):
        hc, tc = chunk  # (batch, chunk, d), (batch, chunk)
        nll, weights, total = _chunk_terms(hc, tc, head_w)
        dlogits = weights * (inv_n / total)[..., None]
        vocab_ids = lax.broadcasted_iota(jnp.int32, dlogits.shape, 2)
        dlogits = jnp.where(
            vocab_ids == tc[..., None].astype(jnp.int32),
            dlogits - inv_n,
            dlogits,
        )
        dh_c = lax.dot_general(
            dlogits, head_w, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(hc.dtype)
        dw_c = lax.dot_general(
            dlogits, hc, (((0, 1), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(head_w.dtype)
        return dw + dw_c.astype(dw.dtype), (nll, dh_c)

    dw, (nll, dh) = lax.scan(
        chunk_step,
        jnp.zeros(lm_head_w.shape, jnp.float32),
        _split_chunks(hidden, targets, chunk_size),
    )
    dh = dh.swapaxes(0, 1).reshape(hidden.shape)
    loss = nll.sum() / (batch * seq)
    return loss, (dh, dw.astype(lm_head_w.dtype))


def _chunked_nll_bwd(chunk_size, residuals, g):
    dh, dw = residuals
    return (g * dh).astype(dh.dtype), (g * dw).astype(dw.dtype), None


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


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
    ``chunk_size`` slices inside one loop; each chunk projects to the
    vocab and reduces to its NLL, and its logits die with the iteration —
    peak activation memory drops from ``O(seq * vocab)`` to
    ``O(chunk * vocab)``, the enabling trick for 32k-vocab configs at long
    context.  Numerically identical to
    ``cross_entropy(hidden @ lm_head.T, targets)``.

    Under a ``grad`` the same loop also makes the gradients (a
    ``custom_vjp``): what is kept for the backward pass is the hidden
    states' gradient (``hidden``'s shape and dtype) and the head's
    (``lm_head_w``'s), which the backward rule only scales by its
    cotangent — no chunk's logits are computed twice.  Outside a ``grad``
    (``eval_loss``) the loop holds the loss's ops alone, so an evaluation
    pays for no gradient.
    """
    seq = hidden.shape[1]
    if seq % chunk_size:
        raise ValueError(
            f"seq {seq} not divisible by loss chunk_size {chunk_size}"
        )
    return _chunked_nll(hidden, lm_head_w, targets, chunk_size)


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
