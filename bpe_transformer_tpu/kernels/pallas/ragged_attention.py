"""Attention of new queries against paged K/V, window or full: the
attention of `models/decode.GroupedPages`' tick and chunk programs - the
grouped pool whose two groups share one shape: every layer the same K/V
heads, a value as wide as its key, no sink (a period of window layers over
plain GQA).  Groups that differ in shape keep rows of keys and values
(`models/decode.GroupedRows`) and are read by
`kernels/pallas/sink_attention.py`.

Pages are ``(num_pages, page_size, 2 * kv_heads, d_head)`` with K and V of
one KV head side by side on the head axis (K even, V odd): one page of one
position range is one contiguous tile row, so the pool needs no re-layout at
a program's edge.  A sequence is a row of page ids, its length ``kv_len``
(keys ``0 .. kv_len - 1`` of that row, the new tokens' own included) and a
run of ``q_len`` query rows that are its *last* ``q_len`` positions.  Query
at sequence position i sees key j iff ``0 <= i - j`` (``< window`` too where
a window is given) - relative positions only, so a window group hands in a
page row that *starts* at its first live page and a ``kv_len`` counted from
there.

On the TPU this is JAX's own Mosaic kernel
``jax.experimental.pallas.ops.tpu.ragged_paged_attention`` (device events
``ragged_paged_attention_kernel``): manual double-buffered page DMAs, flash
accumulation, mixed prefill and decode in one call.  It walks a sequence's
pages from the row's first entry to ``kv_len`` and no further, which is why
the callers compact window rows.  Elsewhere (CPU tests) the same contract is
a gather and a masked softmax in XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.ops.core import MASK_VALUE

#: Pages per flash block and queries per block of the TPU kernel: 512 keys a
#: step; 8 query rows where every sequence brings one (a decode tick
#: computes the whole query block for each sequence), 32 for a chunk.
KV_PAGES_PER_BLOCK = 32
DECODE_QUERIES_PER_BLOCK = 8
CHUNK_QUERIES_PER_BLOCK = 32
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


def ragged_paged_attention(
    q: jax.Array,
    kv_pages: jax.Array,
    kv_lens: jax.Array,
    page_rows: jax.Array,
    cu_q_lens: jax.Array,
    num_seqs: jax.Array,
    *,
    window: int | None,
    one_query_per_seq: bool,
) -> jax.Array:
    """``q`` (tokens, heads, d_head) -> (tokens, heads, d_head).

    ``kv_lens`` (seqs,), ``page_rows`` (seqs, pages_per_seq), ``cu_q_lens``
    (seqs + 1,) cumulative query counts, ``num_seqs`` (1,) - all int32.
    ``one_query_per_seq`` says the call is a decode tick (token s belongs
    to sequence s); otherwise it is one sequence's chunk."""
    d_head = q.shape[-1]
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
            ragged_paged_attention as kernel,
        )

        return kernel(
            q, kv_pages, kv_lens, page_rows, cu_q_lens, num_seqs,
            sm_scale=d_head**-0.5, sliding_window=window,
            num_kv_pages_per_block=min(KV_PAGES_PER_BLOCK, page_rows.shape[1]),
            num_queries_per_block=min(
                q.shape[0],
                DECODE_QUERIES_PER_BLOCK if one_query_per_seq
                else CHUNK_QUERIES_PER_BLOCK,
            ),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        )
    return _xla_ragged_paged_attention(
        q, kv_pages, kv_lens, page_rows, cu_q_lens, window, one_query_per_seq
    )


def _xla_ragged_paged_attention(
    q, kv_pages, kv_lens, page_rows, cu_q_lens, window, one_query_per_seq
):
    tokens, heads, d_head = q.shape
    page_size = kv_pages.shape[1]
    kv_heads = kv_pages.shape[2] // 2
    if one_query_per_seq:
        seq_of = jnp.arange(tokens)
        q_pos = kv_lens - 1  # the sequence's last position
    else:
        seq_of = jnp.zeros((tokens,), jnp.int32)
        q_len = cu_q_lens[1] - cu_q_lens[0]
        q_pos = kv_lens[0] - q_len + jnp.arange(tokens)
    gathered = kv_pages[page_rows[seq_of]]  # (T, pages, page, 2kv, d)
    keys = gathered.reshape(tokens, -1, 2 * kv_heads, d_head)
    k, v = keys[:, :, 0::2], keys[:, :, 1::2]  # (T, K, kv, d)
    qg = q.reshape(tokens, kv_heads, heads // kv_heads, d_head)
    scores = jnp.einsum(
        "tkgd,tjkd->tkgj", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * d_head**-0.5
    key_pos = jnp.arange(keys.shape[1])[None, :]
    visible = key_pos <= q_pos[:, None]
    if window is not None:
        visible &= q_pos[:, None] - key_pos < window
    scores = jnp.where(visible[:, None, None, :], scores, MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tkgj,tjkd->tkgd", probs, v.astype(jnp.float32))
    return out.reshape(tokens, heads, d_head).astype(q.dtype)
