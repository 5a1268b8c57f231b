"""Draft model for speculative decoding: config, parameters, and the
propose/prefill device programs.

A draft is a SMALL transformer sharing the target's tokenizer/vocab (and
context window) that guesses K tokens per slot per tick; the target then
scores all of them in ONE batched verify pass (`serving/spec/engine.py`),
so every accepted guess saves a full target decode tick — and each target
tick is a full HBM sweep of the KV pool, which is exactly what decode
spends its time on.

Two ways to get a draft (`DraftSpec`):

* **tiny geometry** — its own ``d_model``/``num_layers``/``num_heads``/
  ``d_ff``, separately initialized (``seed``); train it however you like
  and load its params, or serve with random init for plumbing tests;
* **truncated-layer view** (``truncate_layers: N``) — the target's first
  N transformer blocks plus its embedding/head, *sharing the target's
  parameter arrays* (zero extra weight memory).  Early layers of a depth-
  trained LM are a serviceable next-token guesser, and the shared
  embedding guarantees the vocabularies agree by construction.

`DraftSpec` itself is jax-free (the CLI validates ``--draft-config``
before any accelerator work — a vocab mismatch must fail fast with
rc 2); `DraftModel` and the device programs import jax lazily.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from bpe_transformer_tpu.models.config import ModelConfig

__all__ = ["DraftSpec", "DraftModel"]


@dataclasses.dataclass(frozen=True)
class DraftSpec:
    """Declarative draft-model description (``--draft-config`` JSON).

    Exactly one of ``truncate_layers`` or the geometry fields
    (``d_model``/``num_layers``/``num_heads``/``d_ff``) selects the draft.
    ``vocab_size``, when given, is cross-checked against the target —
    rejection sampling compares distributions over the SAME vocabulary, so
    a mismatch is a configuration error, not a degraded mode.
    """

    truncate_layers: int | None = None
    d_model: int | None = None
    num_layers: int | None = None
    num_heads: int | None = None
    d_ff: int | None = None
    num_kv_heads: int | None = None
    vocab_size: int | None = None
    seed: int = 0

    @classmethod
    def from_dict(cls, raw: dict) -> "DraftSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(raw) - known)
        if unknown:
            raise ValueError(
                f"draft config has unknown key(s): {', '.join(unknown)}"
            )
        return cls(**raw)

    @classmethod
    def from_json(cls, path: str | Path) -> "DraftSpec":
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def validate_against(self, target: ModelConfig) -> None:
        """Raise ``ValueError`` for a draft the target can never verify:
        vocab mismatch (the acceptance rule is undefined across different
        vocabularies) or a truncation deeper than the target."""
        if self.vocab_size is not None and self.vocab_size != target.vocab_size:
            raise ValueError(
                f"draft vocab_size={self.vocab_size} != target "
                f"vocab_size={target.vocab_size}: speculative verification "
                "compares distributions over one shared vocabulary"
            )
        if self.truncate_layers is not None:
            if not 1 <= self.truncate_layers <= target.num_layers:
                raise ValueError(
                    f"truncate_layers={self.truncate_layers} must be in "
                    f"[1, {target.num_layers}] (the target's depth)"
                )
            if any(
                getattr(self, f) is not None
                for f in ("d_model", "num_layers", "num_heads", "d_ff")
            ):
                raise ValueError(
                    "give truncate_layers OR a draft geometry, not both"
                )
        else:
            missing = [
                f
                for f in ("d_model", "num_layers", "num_heads", "d_ff")
                if getattr(self, f) is None
            ]
            if missing:
                raise ValueError(
                    "draft geometry incomplete: missing "
                    + ", ".join(missing)
                    + " (or set truncate_layers)"
                )

    def resolve(self, target: ModelConfig) -> ModelConfig:
        """The draft's full :class:`ModelConfig`: shares the target's
        vocab/context/RoPE/activation dtype, forces the portable xla
        execution paths (the draft is small — kernel wins are target-side),
        and never pages (its KV is a dense per-slot cache)."""
        self.validate_against(target)
        common = dict(
            attention_impl="xla",
            ffn_impl="xla",
            decode_attention_impl="xla",
            remat=False,
        )
        if self.truncate_layers is not None:
            return dataclasses.replace(
                target, num_layers=self.truncate_layers, **common
            )
        return ModelConfig(
            vocab_size=target.vocab_size,
            context_length=target.context_length,
            d_model=self.d_model,
            num_layers=self.num_layers,
            num_heads=self.num_heads,
            d_ff=self.d_ff,
            num_kv_heads=self.num_kv_heads,
            rope_theta=target.rope_theta,
            tie_embeddings=False,
            activation_dtype=target.activation_dtype,
            **common,
        )


class DraftModel:
    """A ready-to-run draft: resolved config + parameter pytree + the
    compute-dtype LM head, built from a :class:`DraftSpec` against the
    target's params/config.

    Truncated drafts VIEW the target's arrays (the ``layers`` list is
    sliced, nothing is copied); geometry drafts initialize their own
    params from ``spec.seed`` — callers with a trained draft checkpoint
    pass its params via ``params=``.
    """

    def __init__(self, target_params, target_config: ModelConfig,
                 spec: DraftSpec, params=None):
        import jax
        import jax.numpy as jnp

        from bpe_transformer_tpu.models.transformer import (
            init_params,
            lm_head_weight,
        )

        from bpe_transformer_tpu.ops.quant import is_quantized

        self.spec = spec
        self.config = spec.resolve(target_config)
        self.truncated = spec.truncate_layers is not None
        if params is None:
            if self.truncated:
                params = dict(target_params)
                params["layers"] = list(
                    target_params["layers"][: spec.truncate_layers]
                )
            else:
                params = init_params(
                    jax.random.PRNGKey(spec.seed), self.config
                )
        act_dtype = jnp.dtype(self.config.activation_dtype)
        head = lm_head_weight(params, self.config)
        # int8-quantized weights (ops/quant.py dicts — a truncated view of
        # an engine built with weight_dtype="int8") pass through whole:
        # the draft's decode programs dispatch them through the same
        # dequant-in-register matmul the target uses, so a truncated
        # draft stays a zero-copy view of the quantized tree.
        self.lm_head = head if is_quantized(head) else head.astype(act_dtype)
        # Cast only when a leaf NEEDS it: an already-cast tree passes
        # through UNTOUCHED (same containers, same arrays), so a
        # truncated view built from the serving engine's compute-dtype
        # params (`SpecEngine` passes those) keeps sharing the target's
        # arrays even off float32.  Quantized dicts are opaque leaves
        # here — int8 payloads and f32 scales are already at their
        # storage widths and must never be "cast".
        if any(
            leaf.dtype != act_dtype
            for leaf in jax.tree_util.tree_leaves(params, is_leaf=is_quantized)
            if not is_quantized(leaf)
        ):
            params = jax.tree_util.tree_map(
                lambda p: (
                    p
                    if is_quantized(p) or p.dtype == act_dtype
                    else p.astype(act_dtype)
                ),
                params,
                is_leaf=is_quantized,
            )
        self.params = params
        #: EXTRA draft weight bytes: leaves not shared with the target's
        #: arrays (by identity) — 0 for a fully-shared truncated view, the
        #: real footprint for geometry drafts or a dtype-cast copy.
        target_leaf_ids = {
            id(leaf) for leaf in jax.tree_util.tree_leaves(target_params)
        }
        self.param_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(params)
            if id(leaf) not in target_leaf_ids
        )


def _propose_program(
    params, lm_head, cache, tokens, positions, active, keys, temps,
    top_ks, top_ps, *, config: ModelConfig, k: int,
):
    """ONE compiled program proposing K draft tokens per slot.

    A ``lax.scan`` of K dense decode steps over the draft's own KV cache:
    step j feeds the previous token (step 1: the slot's not-yet-written
    last target token) at its position, writes the draft KV row, and
    samples ``d_j`` from the knob-filtered draft distribution ``q_j``
    (greedy slots take the raw argmax and ``q_j`` is its exact one-hot).
    A final extra decode step writes ``d_K``'s KV row — without it, a
    fully-accepted window would leave a one-position hole in the draft
    cache that the next propose would read as zeros.

    Returns ``(draft_tokens (S, K), draft_probs (S, K, V), cache, keys)``.
    ``draft_probs`` is the distribution each token was actually sampled
    from — the ``q`` of the Leviathan acceptance rule; it stays on device
    and feeds the verify program directly.  Stale cache rows beyond a
    later-rejected prefix need no cleanup: draft attention masks keys by
    position, and the next propose overwrites them.
    """
    import jax
    import jax.numpy as jnp

    from bpe_transformer_tpu.models.decode import decode_step
    from bpe_transformer_tpu.serving.engine import (
        TOP_K_DISABLED,
        TOP_P_DISABLED,
        filter_logits,
    )

    vocab = config.vocab_size
    # A vacant slot asks for no search.
    top_ks = jnp.where(active, top_ks, TOP_K_DISABLED)
    top_ps = jnp.where(active, top_ps, TOP_P_DISABLED)

    def body(carry, _):
        tok, pos, cache, keys = carry
        logits, cache = decode_step(
            params, tok, pos, cache, config, lm_head=lm_head, active=active
        )
        masked = filter_logits(logits, temps, top_ks, top_ps)
        probs = jax.nn.softmax(masked, axis=-1)
        greedy = jnp.argmax(logits, axis=-1)
        onehot = jax.nn.one_hot(greedy, vocab, dtype=probs.dtype)
        split = jax.vmap(jax.random.split)(keys)
        keys_next, subs = split[:, 0], split[:, 1]
        sampled = jax.vmap(jax.random.categorical)(subs, masked)
        d = jnp.where(temps > 0.0, sampled, greedy)
        q_row = jnp.where((temps > 0.0)[:, None], probs, onehot)
        d = jnp.where(active, d, tok)
        keys_next = jnp.where(active[:, None], keys_next, keys)
        pos_next = jnp.where(active, pos + 1, pos)
        return (d, pos_next, cache, keys_next), (d, q_row)

    (last_tok, last_pos, cache, keys), (ds, qs) = jax.lax.scan(
        body, (tokens, positions, cache, keys), None, length=k
    )
    # Write d_K's KV row (logits discarded): the draft cache must cover
    # every proposed position so an all-accepted window leaves no gap.
    _, cache = decode_step(
        params, last_tok, last_pos, cache, config, lm_head=lm_head,
        active=active,
    )
    draft_tokens = jnp.transpose(ds, (1, 0))
    draft_probs = jnp.transpose(qs, (1, 0, 2))
    return draft_tokens, draft_probs, cache, keys


def _draft_prefill_program(
    params, lm_head, cache, padded, length, slot, *, config: ModelConfig
):
    """Fill slot ``slot``'s DRAFT cache rows from the (bucket-padded)
    prompt — the draft twin of the dense engine's prefill, minus the
    sampling (the target's prefill owns the first token; the draft only
    needs its KV state to start proposing).  The draft always prefills
    the WHOLE prompt: its dense cache has no radix sharing, and the
    draft forward is small enough that recomputing a shared prefix is
    cheaper than plumbing block bookkeeping into a second cache."""
    import jax.numpy as jnp
    from jax import lax

    from bpe_transformer_tpu.models.decode import init_kv_cache, prefill

    fresh = init_kv_cache(config, 1, dtype=cache[0]["k"].dtype)
    _, filled = prefill(
        params, padded, config, fresh, lm_head=lm_head,
        last_pos=jnp.reshape(length - 1, (1,)),
    )
    return [
        {
            "k": lax.dynamic_update_slice(c["k"], f["k"], (slot, 0, 0, 0)),
            "v": lax.dynamic_update_slice(c["v"], f["v"], (slot, 0, 0, 0)),
        }
        for c, f in zip(cache, filled)
    ]
