"""Fused final-projection + sampling: the decode tick's tail as ONE kernel.

The unfused tick tail is a chain: head matmul -> (slots, vocab) f32
logits to HBM -> `filter_logits` (the top-k and nucleus threshold
searches, each a few dozen passes over the logits in HBM) -> masked
logits to HBM -> `jax.random.categorical` (gumbel noise + argmax) —
several vocab-sized HBM round trips to emit ONE token per slot.  This
kernel collapses the whole tail: the head streams through VMEM once per
row tile (int8 weights dequantize in registers, `ops/quant.py` layout),
logits accumulate in a VMEM scratch and never reach HBM, and the
filtering + sampling run in the same program.

**Sort-free exact filtering.**  Runtime top-k/top-p need order
statistics (the k-th largest logit; the nucleus cutoff).  Both come from
the 32-step radix descents over order-preserving uint32 keys of
`ops/sampling.py` — the one implementation `serving.engine.filter_logits`
runs under XLA too — here over the VMEM-resident logits of a row tile.
The thresholds are EXACT (they land on representable key values), so the
keep sets match a sorted cut-off's bit for bit (the only fp caveat: the
nucleus mass comparison sums in a different order than a sorted cumsum,
so a logit sitting within one ulp of the nucleus boundary can flip —
measure-zero for real logits).

**Sampling.**  ``jax.random.categorical(key, masked)`` IS
``argmax(masked + gumbel(key, shape))`` — so the caller draws the gumbel
noise from the very key the unfused path would hand to ``categorical``
and passes it in; the kernel adds it to the masked logits and takes the
argmax (first occurrence, matching ``jnp.argmax``).  Greedy rows
(temp 0) take the raw-logits argmax, exactly as `sample_tokens`.

Two entry points share the machinery:

* :func:`fused_head_sample` — the tick tail: one token per row.
* :func:`fused_verify_head` — the speculative-decoding verify tail
  (`serving/spec/engine.py`): per scored row, the greedy token, the
  filtered target probability of the judged draft token (the ``p(d)`` of
  the Leviathan accept rule), and a residual-distribution sample
  (``max(p − q, 0)`` normalized, the rejection bonus) — so the verify
  program's only vocab-sized tensors outside the kernel are the draft's
  own ``q`` (which the propose program materialized anyway) and the
  gumbel noise.

Forward-only inference kernels, like the decode-attention siblings.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bpe_transformer_tpu.kernels.pallas.runtime import pick_block
from bpe_transformer_tpu.ops.core import MASK_VALUE as NEG_INF
from bpe_transformer_tpu.ops.sampling import (
    nucleus_threshold,
    okey,
    topk_threshold,
)

SUBLANES = 8
LANE = 128


def _pick_block_v(v: int, target: int = 2048) -> tuple[int, int]:
    """``(vocab tile, padded vocab)``.  The tile is a multiple of 128 —
    Mosaic must prove the dynamic-offset scratch store lane-aligned — and
    the scratch spans a whole number of tiles.  A vocabulary with an
    aligned divisor (32,000 -> 1,280) tiles exactly; any other (10,000)
    takes ``target``-wide tiles over a scratch padded up to the next tile
    boundary: the last head tile is ragged (Pallas pads the read) and the
    finalize masks the padding columns to ``NEG_INF`` before any use."""
    bv = pick_block(v, target, LANE) or min(target, pl.cdiv(v, LANE) * LANE)
    return bv, pl.cdiv(v, bv) * bv


def _pick_block_r(r_pad: int, v_pad: int) -> int:
    """Row tile: every vocab-sized operand, the scratch and the finalize's
    temporaries live at this many rows, and the 64 radix passes run over
    all of them — so rows shrink as the vocabulary grows (32 rows up to
    8k, 8 rows at 32k; 24 rows at 32k overflowed the v5e's 16 MB scoped
    VMEM while the passes were unrolled)."""
    rows = 32 * 8192 // v_pad // SUBLANES * SUBLANES
    return pick_block(r_pad, min(32, max(SUBLANES, rows)), SUBLANES)


def _argmax_first(x):
    """Row-wise argmax, first occurrence (``jnp.argmax`` semantics)."""
    v = x.shape[-1]
    m = jnp.max(x, axis=-1, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.min(jnp.where(x == m, iota, v), axis=-1, keepdims=True)


def _filter_rows(logits, temps, top_ks, top_ps, vocab):
    """The `filter_logits` keep-set + masked logits for (R, V_pad) rows
    with per-row runtime knobs, sort-free (see module docstring).  Columns
    at and beyond ``vocab`` are scratch padding: forced to ``NEG_INF``
    here, they rank below every real logit, carry zero softmax mass, and
    never win an argmax.  Returns ``(masked, keep, e_kept, greedy)``: the
    -inf-masked scaled logits, the boolean keep set, the kept entries'
    ``exp(x - rowmax)`` weights (softmax numerators), and the raw-logits
    argmax."""
    if logits.shape[-1] != vocab:
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(cols < vocab, logits, NEG_INF)
    greedy = _argmax_first(logits)
    scaled = logits / jnp.maximum(temps, 1e-6)
    keys = okey(scaled)

    kk_raw = top_ks.astype(jnp.int32)
    kk = jnp.where(kk_raw > 0, jnp.clip(kk_raw, 1, vocab), vocab)
    tk = topk_threshold(keys, kk)
    keep_k = keys >= tk
    masked1 = jnp.where(keep_k, scaled, NEG_INF)

    m2 = jnp.max(masked1, axis=-1, keepdims=True)
    e = jnp.where(keep_k, jnp.exp(masked1 - m2), 0.0)
    z = jnp.sum(e, axis=-1, keepdims=True)
    tp = nucleus_threshold(keys, e, top_ps * z)
    # The max (and its value-ties) always survives, as in filter_logits'
    # keep[..., 0] = True — value-based masking keeps every tie.
    keep = keep_k & ((keys >= tp) | (masked1 == m2))
    masked = jnp.where(keep, masked1, NEG_INF)
    return masked, keep, jnp.where(keep, e, 0.0), greedy


def _accumulate_logits(x_ref, h_ref, s_ref, acc_ref, *, block_v, quantized):
    """Grid step ``(i, j)``: head tile ``j``'s logit columns for row tile
    ``i`` into the scratch.  int8 tiles dequantize in registers —
    per-output-channel scale applied AFTER the f32-accumulated dot, so
    the weight bytes that cross HBM are the int8 payload."""
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)           # (block_r, d)
    h = h_ref[...].astype(jnp.float32)           # (block_v, d)
    out = jax.lax.dot_general(
        x, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                             # (block_r, block_v)
    if quantized:
        out = out * s_ref[...].reshape(1, -1)
    acc_ref[:, pl.ds(j * block_v, block_v)] = out


def _sample_kernel(
    x_ref, h_ref, *refs, block_v, num_v_blocks, quantized, vocab,
):
    if quantized:
        s_ref, knobs_ref, g_ref, tok_ref, acc_ref = refs
    else:
        s_ref = None
        knobs_ref, g_ref, tok_ref, acc_ref = refs
    _accumulate_logits(
        x_ref, h_ref, s_ref, acc_ref, block_v=block_v, quantized=quantized
    )

    @pl.when(pl.program_id(1) == num_v_blocks - 1)
    def _finalize():
        logits = acc_ref[...]
        temps = knobs_ref[:, 0:1]
        masked, _, _, greedy = _filter_rows(
            logits, temps, knobs_ref[:, 1:2], knobs_ref[:, 2:3], vocab
        )
        sampled = _argmax_first(masked + g_ref[...])
        tok_ref[...] = jnp.where(temps > 0.0, sampled, greedy).astype(
            jnp.int32
        )


def _verify_kernel(
    x_ref, h_ref, *refs, block_v, num_v_blocks, quantized, vocab,
):
    if quantized:
        (s_ref, knobs_ref, judge_ref, q_ref, g_ref,
         greedy_ref, pd_ref, bonus_ref, acc_ref) = refs
    else:
        s_ref = None
        (knobs_ref, judge_ref, q_ref, g_ref,
         greedy_ref, pd_ref, bonus_ref, acc_ref) = refs
    _accumulate_logits(
        x_ref, h_ref, s_ref, acc_ref, block_v=block_v, quantized=quantized
    )

    @pl.when(pl.program_id(1) == num_v_blocks - 1)
    def _finalize():
        logits = acc_ref[...]
        temps = knobs_ref[:, 0:1]
        _, _, e_kept, greedy = _filter_rows(
            logits, temps, knobs_ref[:, 1:2], knobs_ref[:, 2:3], vocab
        )
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        # Filtered target distribution p: softmax over the keep set for
        # sampled rows, the EXACT raw-argmax one-hot for greedy rows (the
        # Leviathan rule then collapses to argmax agreement).
        p_soft = e_kept / jnp.maximum(
            jnp.sum(e_kept, axis=-1, keepdims=True), 1e-30
        )
        onehot = (iota == greedy).astype(jnp.float32)
        p = jnp.where(temps > 0.0, p_soft, onehot)
        pd_ref[...] = jnp.sum(
            jnp.where(iota == judge_ref[...], p, 0.0),
            axis=-1, keepdims=True,
        )
        # Residual max(p - q, 0) with the all-mass-gone fallback to p
        # itself; the bonus/correction token is its gumbel-argmax sample
        # (sampled rows) or plain argmax (greedy rows) — exactly the
        # `_spec_verify_program` math, one sample per candidate row.
        res = jnp.maximum(p - q_ref[...].astype(jnp.float32), 0.0)
        has_mass = jnp.sum(res, axis=-1, keepdims=True) > 0
        res = jnp.where(has_mass, res, p)
        logres = jnp.where(res > 0, jnp.log(jnp.maximum(res, 1e-38)), NEG_INF)
        bonus_s = _argmax_first(logres + g_ref[...])
        bonus_g = _argmax_first(res)
        greedy_ref[...] = greedy.astype(jnp.int32)
        bonus_ref[...] = jnp.where(temps > 0.0, bonus_s, bonus_g).astype(
            jnp.int32
        )


def _head_operands(head, v, d):
    """Normalize the head argument: a raw ``(V, d)`` array or the int8
    quantized dict — returns ``(inputs, in_specs, quantized)`` for the
    blocked head tile (+ per-row scale tile when quantized)."""
    quantized = isinstance(head, dict)
    if quantized:
        q, scale = head["q"], head["scale"]
        if q.shape != (v, d) or scale.shape != (v,):
            raise ValueError(
                f"quantized head q {q.shape} / scale {scale.shape} must be "
                f"({v}, {d}) / ({v},)"
            )
        return [q, scale.reshape(v, 1)], quantized
    if head.shape != (v, d):
        raise ValueError(f"head {head.shape} must be ({v}, {d})")
    return [head], quantized


def _run(kernel_body, hidden, head, knobs, extra_inputs, out_shapes,
         *, vocab, interpret, name):
    """Shared pallas_call assembly for both entry points: grid =
    ``(row tiles, vocab tiles)`` with the vocab axis innermost, so each
    row tile's logits fully accumulate in the ``(block_r, vocab)``
    scratch before its finalize fires, then the next row tile reuses the
    scratch (the grid iterates sequentially, last axis fastest — the
    decode-attention kernels' accumulator pattern).  Row-tiling bounds
    VMEM: the scratch and every vocab-sized per-row operand (gumbel, the
    verify ``q``) live at ``block_r`` rows, not the full batch — the
    spec verify's rows = slots·(K+1) must not ride whole."""
    if interpret is None:
        from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode

        interpret = interpret_mode()
    r, d = hidden.shape
    r_pad = pl.cdiv(r, SUBLANES) * SUBLANES
    pad = lambda a: (
        a if a.shape[0] == r_pad
        else jnp.pad(a, ((0, r_pad - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))
    )
    head_inputs, quantized = _head_operands(head, vocab, d)
    bv, v_pad = _pick_block_v(vocab)
    nv = v_pad // bv
    br = _pick_block_r(r_pad, v_pad)

    rowspec = lambda minor: pl.BlockSpec(
        (br, minor), lambda i, j: (i, 0), memory_space=pltpu.VMEM
    )
    in_specs = [rowspec(d)]
    in_specs.append(
        pl.BlockSpec((bv, d), lambda i, j: (j, 0), memory_space=pltpu.VMEM)
    )
    if quantized:
        in_specs.append(
            pl.BlockSpec((bv, 1), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM)
        )
    in_specs.append(rowspec(knobs.shape[1]))
    inputs = [pad(hidden), *head_inputs, pad(knobs)]
    for arr in extra_inputs:
        if arr.shape[1] == vocab and v_pad != vocab:
            # Vocab-sized per-row operands (gumbel, the verify q) meet the
            # padded scratch elementwise; their padding columns only ever
            # touch masked logits.
            arr = jnp.pad(arr, ((0, 0), (0, v_pad - vocab)))
        inputs.append(pad(arr))
        in_specs.append(rowspec(arr.shape[1]))

    kernel = functools.partial(
        kernel_body, block_v=bv, num_v_blocks=nv, quantized=quantized,
        vocab=vocab,
    )
    outs = pl.pallas_call(
        kernel,
        grid=(r_pad // br, nv),
        in_specs=in_specs,
        out_specs=[rowspec(1) for _ in out_shapes],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, 1), dt) for dt in out_shapes
        ],
        scratch_shapes=[pltpu.VMEM((br, v_pad), jnp.float32)],
        interpret=interpret,
        name=name,
    )(*inputs)
    return [o[:r, 0] for o in outs]


def fused_head_sample(
    hidden: jax.Array,
    head,
    temps: jax.Array,
    top_ks: jax.Array,
    top_ps: jax.Array,
    gumbel: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused tick tail: ``hidden (rows, d)`` -> sampled token ids
    ``(rows,)`` int32 under per-row runtime knobs.

    ``head`` is the LM head — a ``(vocab, d)`` array or the int8
    quantized dict.  ``gumbel (rows, vocab)`` is the caller's noise,
    drawn from the same key the unfused path would give
    ``jax.random.categorical`` (which is literally gumbel + argmax), so
    fused and unfused sampling agree token-for-token whenever the logits
    agree bitwise; greedy rows (temp 0) are argmax and agree always.
    """
    rows, _ = hidden.shape
    vocab = gumbel.shape[-1]
    knobs = jnp.stack(
        [
            temps.astype(jnp.float32),
            top_ks.astype(jnp.float32),
            top_ps.astype(jnp.float32),
        ],
        axis=1,
    )
    (tok,) = _run(
        _sample_kernel, hidden, head, knobs,
        [gumbel.astype(jnp.float32)], [jnp.int32],
        vocab=vocab, interpret=interpret, name="fused_head_sample",
    )
    return tok


def fused_verify_head(
    hidden: jax.Array,
    head,
    temps: jax.Array,
    top_ks: jax.Array,
    top_ps: jax.Array,
    judge_tokens: jax.Array,
    q_probs: jax.Array,
    gumbel: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The speculative-verify tail for ``hidden (rows, d)`` scored rows
    (rows = slots * (K+1), row-major): returns ``(greedy, p_d, bonus)``
    each ``(rows,)`` — the raw-argmax token, the filtered target
    probability of ``judge_tokens`` (the accept rule's ``p(d)``; greedy
    rows handle it outside via argmax agreement), and a sample from the
    residual ``max(p − q_probs, 0)`` (fallback ``p``).  ``q_probs``/
    ``gumbel`` are ``(rows, vocab)``; all knobs per row.
    """
    rows, _ = hidden.shape
    vocab = q_probs.shape[-1]
    knobs = jnp.stack(
        [
            temps.astype(jnp.float32),
            top_ks.astype(jnp.float32),
            top_ps.astype(jnp.float32),
        ],
        axis=1,
    )
    greedy, p_d, bonus = _run(
        _verify_kernel, hidden, head, knobs,
        [
            judge_tokens.astype(jnp.int32).reshape(rows, 1),
            q_probs.astype(jnp.float32),
            gumbel.astype(jnp.float32),
        ],
        [jnp.int32, jnp.float32, jnp.int32],
        vocab=vocab, interpret=interpret, name="fused_verify_head",
    )
    return greedy, p_d, bonus
