"""Slot-pool continuous-batching engine: many in-flight generations, one
compiled decode program.

Production TPU serving lives or dies on chip saturation: a single-request
decode step is a tiny matvec that leaves the MXU idle, and recompiling per
prompt shape stalls the pipeline for seconds at a time.  This engine fixes
both with a **fixed-capacity slot pool**:

* the KV cache is one batched pytree — ``slots x context_length`` per layer
  (`models/decode.init_kv_cache`) — and every engine tick runs ONE jitted
  ``decode_step`` across all slots at their own positions (the per-slot
  ``pos`` vector + ``active`` mask generalization of `models/decode.py`),
  sampling each slot with independent RNG/temperature/top-k/top-p **at
  runtime** (no sampling knob is a static argument, so knob changes never
  recompile);
* prefill pads each prompt up to a **power-of-two length bucket** and runs
  a per-bucket program that writes the slot's cache rows and samples the
  first token — the engine compiles at most ``len(buckets) + 1`` XLA
  programs total (one per bucket + the tick), asserted by
  :meth:`SlotPoolEngine.compiled_programs`;
* slots retire on stop-id / max-tokens and are immediately re-admittable:
  a fresh prefill overwrites the slot's whole cache row, so no cross-request
  state survives.

The engine is single-threaded by design (the serving layer's worker loop
owns it); queueing, deadlines, and transport live in `serving.scheduler`
and `serving.server`.

MoE note: expert capacity inside a tick is batch-shaped (all slots' tokens
route together), so under capacity pressure slots are not perfectly
independent — the same caveat as batched `generate_cached`, and a no-op for
drop-free configs.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.decode import decode_step, init_kv_cache, prefill
from bpe_transformer_tpu.models.transformer import lm_head_weight
from bpe_transformer_tpu.ops.sampling import (
    nucleus_threshold,
    okey,
    topk_threshold,
)
from bpe_transformer_tpu.telemetry.spans import Phase

#: Runtime encodings for "knob disabled" — the knobs are values the
#: sampler reads, so every slot shares one program regardless of which
#: knobs are in play.
TOP_K_DISABLED = 0
TOP_P_DISABLED = 2.0


def prepare_serving_weights(params, config: ModelConfig, weight_dtype):
    """The weight pipeline every serving engine runs at build time: cast
    the tree + LM head to the compute dtype (mirrors ``generate_cached``),
    then — under ``weight_dtype="int8"`` — quantize the matmul weights
    per output channel (`ops/quant.py`), so every program the engine
    compiles streams 1-byte weights and dequantizes in registers.  An
    expert layer's tree comes back in its serving layout
    (`models/moe.serving_layout`: a down projection whose contraction width
    is not a whole number of lane tiles laid out once, here, as the grouped
    matmul reads it, under a name of its own; the same bytes, so both byte
    counts are what they were), every other tree leaf for leaf as it came.

    Returns ``(params, lm_head, label, params_bytes, tick_weight_bytes)``:
    the (possibly quantized) tree and head copy, the ``weight_dtype``
    gauge label ("int8" or the activation dtype name), resident weight
    bytes, and the bytes ONE decode tick actually streams (block stack +
    final norm + the head copy; the embedding row gather and the tree's
    unused ``lm_head`` leaf stay out — they are resident, not per-tick
    traffic).
    """
    if weight_dtype not in (None, "int8"):
        raise ValueError(
            f'weight_dtype={weight_dtype!r} must be None (activation '
            'width) or "int8"'
        )
    from bpe_transformer_tpu.ops.quant import (
        quantize_params,
        quantize_weight,
        tree_bytes,
    )

    act_dtype = jnp.dtype(config.activation_dtype)
    lm_head = lm_head_weight(params, config).astype(act_dtype)
    if act_dtype != jnp.float32:
        params = jax.tree_util.tree_map(lambda p: p.astype(act_dtype), params)
    if weight_dtype == "int8":
        params = quantize_params(params, config)
        lm_head = quantize_weight(lm_head)
    elif config.ffn_type == "moe":
        from bpe_transformer_tpu.models.moe import serving_layout

        # A layer at a time, over containers of our own: where the cast
        # made the leaves ours too, a stack's torch layout goes as soon as
        # its relaid form exists.
        layers = list(params["layers"])
        params = {**params, "layers": layers}
        for i, layer in enumerate(layers):
            if "ffn" in layer:
                layers[i] = {**layer, "ffn": serving_layout(layer["ffn"])}
    label = "int8" if weight_dtype == "int8" else str(act_dtype)
    params_bytes = tree_bytes(params) + tree_bytes(lm_head)
    tick_weight_bytes = (
        tree_bytes(params["layers"])
        + tree_bytes(params["ln_final"])
        + tree_bytes(lm_head)
    )
    return params, lm_head, label, params_bytes, tick_weight_bytes


def gumbel_rows(keys, vocab: int):
    """Per-row gumbel noise ``(rows, vocab)`` from per-row RNG keys —
    the noise ``jax.random.categorical`` would draw internally from the
    same keys, precomputed so the fused sample kernel
    (`kernels/pallas/sample.py`) can take its argmax in-program and stay
    token-identical to the unfused sampler."""
    return jax.vmap(
        lambda k: jax.random.gumbel(k, (vocab,), jnp.float32)
    )(keys)


def default_prefill_buckets(
    context_length: int, min_bucket: int = 16
) -> tuple[int, ...]:
    """Power-of-two prompt-length buckets up to (and always including) the
    context length — the bounded set of prefill program shapes."""
    buckets: list[int] = []
    b = min_bucket
    while b < context_length:
        buckets.append(b)
        b *= 2
    buckets.append(context_length)
    return tuple(buckets)


def filter_logits(logits, temps, top_ks, top_ps):
    """Temperature-scale + top-k/top-p mask ``(batch, vocab)`` logits with
    RUNTIME ``(batch,)`` knobs — the filtering half of :func:`sample_tokens`:
    the scaled logits with the dropped entries at ``-inf``.

    Split out so the speculative-decoding accept/resample math
    (`serving/spec/`) can reach the *modified distribution* itself
    (``softmax`` of this return value), not just a sample from it: the
    Leviathan acceptance rule must compare draft and target probabilities
    under exactly the knobs the sampler would have applied.

    Both filters are value thresholds with ties kept, found without a sort
    (`ops/sampling.py`), and each search runs only where a row of the
    batch asks for its filter: ``top_ks <= 0`` and ``top_ps >= 1`` are
    "off", and a row with a filter off keeps everything whatever the other
    rows ask for.  ``top_p == 1.0`` exactly is off too: the sorted
    cumulative sum this replaced (until PR 34) dropped the tail whose mass
    float32 rounds away under 1.0.  The predicates read the knobs alone,
    never the temperatures, so a row's result does not depend on its
    neighbours.
    """
    vocab = logits.shape[-1]
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    k_on, p_on = (top_ks > 0)[:, None], (top_ps < 1.0)[:, None]
    # The threshold key that keeps a whole row.
    keep_all = jnp.zeros((logits.shape[0], 1), jnp.uint32)
    # Each search makes its own keys: keys made once out here and handed to
    # the two `cond`s are an operand the loops read from HBM on every pass,
    # 0.95 ms a 128-row tick against 0.33 (PERF.md section 6, PR 34).

    # top-k: keep everything >= the k-th largest (ties included, matching
    # the static sampler), k clipped to the vocabulary.
    with jax.named_scope("sample/top_k"):

        def kth_largest():
            kk = jnp.clip(top_ks, 1, vocab)[:, None]
            return jnp.where(k_on, topk_threshold(okey(scaled), kk), keep_all)

        tk = lax.cond(jnp.any(k_on), kth_largest, lambda: keep_all)

    # top-p over the top-k survivors' softmax (renormalized over them, as
    # the static sampler does by masking before nucleus): an entry stays
    # while the mass strictly above it is under top_p; the row's maximum
    # always stays.
    with jax.named_scope("sample/top_p"):

        def nucleus():
            keys = okey(scaled)
            top = jnp.max(scaled, axis=-1, keepdims=True)
            e = jnp.where(keys >= tk, jnp.exp(scaled - top), 0.0)
            z = jnp.sum(e, axis=-1, keepdims=True)
            tp = nucleus_threshold(keys, e, top_ps[:, None] * z)
            return jnp.where(p_on, jnp.minimum(tp, okey(top)), keep_all)

        tp = lax.cond(jnp.any(p_on), nucleus, lambda: keep_all)

    return jnp.where(okey(scaled) >= jnp.maximum(tk, tp), scaled, -jnp.inf)


def next_token_logits(logits, config: ModelConfig):
    """What generation samples from: the head's logits, or prediction head
    0's ``vocab_size`` of them where the head has several (head-major:
    `ModelConfig.num_pred_heads`)."""
    if config.num_pred_heads == 1:
        return logits
    return logits[..., : config.vocab_size]


def sample_tokens(logits, keys, temps, top_ks, top_ps):
    """Per-row sampling with RUNTIME knobs: ``temps`` (0 = greedy),
    ``top_ks`` (0 = disabled), ``top_ps`` (>= 1 disabled).

    Mirrors `models/decode._sample_from_logits` semantics per row — scale by
    temperature, top-k threshold with ties kept, then nucleus filtering on
    the top-k-renormalized distribution (:func:`filter_logits`) — but with
    every knob a traced ``(batch,)`` vector, so one compiled program serves
    any knob mix.  A greedy row's token is the raw ``argmax`` and never
    reads its filtered row, so its knobs go to the filter as "off": a
    batch of greedy rows runs neither search.
    """
    with jax.named_scope("sample/draw"):
        greedy = jnp.argmax(logits, axis=-1)
    sampling = temps > 0.0
    masked = filter_logits(
        logits, temps,
        jnp.where(sampling, top_ks, TOP_K_DISABLED),
        jnp.where(sampling, top_ps, TOP_P_DISABLED),
    )
    with jax.named_scope("sample/draw"):
        sampled = jax.vmap(jax.random.categorical)(keys, masked)
        return jnp.where(sampling, sampled, greedy)


def filters_asked(active, temps, top_ks, top_ps) -> tuple[bool, bool]:
    """``(top-k, top-p)``: whether a live sampled slot asks for that filter
    - what the two searches of the tick's sampler hang on (the tick program
    hands a vacant slot over as greedy), read on the host from the arrays
    the engine is about to hand the program."""
    sampled = active & (temps > 0.0)
    return (
        bool((sampled & (top_ks > 0)).any()),
        bool((sampled & (top_ps < 1.0)).any()),
    )


def _prefill_program(
    params, lm_head, cache, padded, length, slot, key, temp, top_k, top_p,
    *, config: ModelConfig,
):
    """One bucket-shaped prefill: fill slot ``slot``'s cache rows from the
    padded prompt, return the first sampled token.  ``length``/``slot`` and
    every sampling knob are traced, so the program count is exactly the
    bucket count."""
    fresh = init_kv_cache(config, 1, dtype=cache[0]["k"].dtype)
    logits, filled = prefill(
        params, padded, config, fresh, lm_head=lm_head,
        last_pos=jnp.reshape(length - 1, (1,)),
    )
    # Replace the slot's ENTIRE cache row (zeros beyond the bucket): no
    # stale state from the previous occupant survives re-admission.
    new_cache = [
        {
            "k": lax.dynamic_update_slice(c["k"], f["k"], (slot, 0, 0, 0)),
            "v": lax.dynamic_update_slice(c["v"], f["v"], (slot, 0, 0, 0)),
        }
        for c, f in zip(cache, filled)
    ]
    with jax.named_scope("key_split"):
        key, sub = jax.random.split(key)
    tok = sample_tokens(
        logits, sub[None], temp[None], top_k[None], top_p[None]
    )[0]
    return tok, key, new_cache


def _tick_program(
    params, lm_head, cache, tokens, positions, active, keys, temps,
    top_ks, top_ps, *, config: ModelConfig, fused: bool = False,
):
    """One engine tick: batched decode step at per-slot positions, per-slot
    runtime sampling, inactive slots frozen (cache write masked, position
    held, token passed through).

    ``fused=True`` runs the tick's tail — head projection + filtering +
    sampling — as ONE Pallas kernel (`kernels/pallas/sample.py`): the
    decode step returns the final-norm hidden state, the caller-side
    gumbel noise replaces ``categorical``'s internal draw from the same
    keys, and (slots, vocab) logits never reach HBM.  Greedy output is
    token-identical to the unfused path; sampled output is too whenever
    the kernel's logits match the XLA matmul bitwise.
    """
    with jax.named_scope("key_split"):
        split = jax.vmap(jax.random.split)(keys)
        keys_next, subs = split[:, 0], split[:, 1]
    if fused:
        from bpe_transformer_tpu.kernels.pallas.sample import (
            fused_head_sample,
        )

        hidden, cache = decode_step(
            params, tokens, positions, cache, config, lm_head=lm_head,
            active=active, return_hidden=True,
        )
        gumbel = gumbel_rows(subs, config.vocab_size)
        nxt = fused_head_sample(
            hidden, lm_head, temps, top_ks, top_ps, gumbel
        )
    else:
        logits, cache = decode_step(
            params, tokens, positions, cache, config, lm_head=lm_head,
            active=active,
        )
        # A vacant slot goes in as a greedy row: it asks for no search.
        nxt = sample_tokens(
            logits, subs, jnp.where(active, temps, 0.0), top_ks, top_ps
        )
    nxt = jnp.where(active, nxt, tokens)
    keys_next = jnp.where(active[:, None], keys_next, keys)
    positions = jnp.where(active, positions + 1, positions)
    return nxt, positions, keys_next, cache


@dataclasses.dataclass
class SlotInfo:
    """Host-side bookkeeping for one occupied slot."""

    prompt_len: int
    bucket: int
    max_new_tokens: int  # effective: clamped to the context window
    stop_id: int | None
    generated: int = 0  # includes the prefill-sampled first token
    #: The serving request (= fleet trace id) occupying this slot, so a
    #: /statusz slot table answers "whose request is pinning slot 3" and a
    #: cross-replica trace can name the slot a hop landed on.
    request_id: str | None = None


@dataclasses.dataclass(frozen=True)
class TickEvent:
    """One slot's output from a tick (or admission): the sampled token and,
    when the slot retired, why (``"stop"`` | ``"length"``)."""

    slot: int
    token: int
    finished: str | None = None


class SlotPoolEngine:
    """Fixed-capacity continuous-batching engine over a batched KV cache.

    Single-threaded: exactly one caller (the serving worker loop) may call
    :meth:`admit` / :meth:`tick` / :meth:`release`.
    """

    #: Seconds of the last tick's ``(dispatch, wait, emit)`` phases: see
    #: the paged twin, `kvpool.paged_engine.PagedEngine.last_tick_s`.
    last_tick_s = (0.0, 0.0, 0.0)

    def __init__(
        self,
        params,
        config: ModelConfig,
        *,
        slots: int = 8,
        prefill_buckets: tuple[int, ...] | None = None,
        min_bucket: int = 16,
        weight_dtype: str | None = None,
        fused_sampling: bool = False,
    ):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if config.attention_kind == "mla":
            raise ValueError(
                "the dense slot pool keeps K and V heads a slot; a config "
                "with latent attention is served by the paged engine "
                "(ROADMAP: what cannot run yet)"
            )
        if config.eva_block:
            raise ValueError(
                "the dense slot pool keeps a row a position; a config with "
                "chunked linear attention is served by the paged engine over "
                "its summary-and-window cache (ROADMAP: what cannot run yet)"
            )
        if config.hybrid_block:
            raise ValueError(
                "the dense slot pool prefills a padded bucket, whose padding "
                "would enter a recurrent state; a config with state-space "
                "layers is served by the paged engine (ROADMAP: what cannot "
                "run yet)"
            )
        self.config = config
        self.n_slots = slots
        ctx = config.context_length
        if prefill_buckets is None:
            prefill_buckets = default_prefill_buckets(ctx, min_bucket)
        buckets = tuple(sorted(set(prefill_buckets)))
        if not buckets or buckets[-1] > ctx:
            raise ValueError(
                f"prefill buckets {buckets} must be non-empty and <= "
                f"context_length={ctx}"
            )
        if buckets[-1] < ctx:
            buckets = buckets + (ctx,)
        self.buckets = buckets

        # Params/head cast once to the compute dtype (mirrors
        # generate_cached), then optionally int8-quantized per output
        # channel — every program this engine compiles streams 1-byte
        # weights then; the cache lives at the activation width.
        act_dtype = jnp.dtype(config.activation_dtype)
        (
            self._params, self._lm_head, self.weight_dtype,
            self.params_bytes, self.tick_weight_bytes,
        ) = prepare_serving_weights(params, config, weight_dtype)
        self.fused_sampling = bool(fused_sampling)
        self._cache = init_kv_cache(config, slots, dtype=act_dtype)
        kv_heads = config.num_kv_heads or config.num_heads
        #: KV footprint per token position across layers (k + v) at the
        #: cache width — the decode-tick attention read stream's unit
        #: (the dense twin of the paged engine's gauge; feeds the
        #: decode-tick roofline).
        self.kv_bytes_per_token = (
            2 * config.num_layers * kv_heads * config.d_head
            * act_dtype.itemsize
        )

        # Per-slot sampling/position state is host-side numpy: tiny (N,)
        # vectors shipped with each dispatch; only the cache stays resident.
        self._tokens = np.zeros(slots, np.int32)
        self._positions = np.zeros(slots, np.int32)
        self._active = np.zeros(slots, bool)
        self._keys = np.zeros((slots, 2), np.uint32)
        self._temps = np.zeros(slots, np.float32)
        self._top_ks = np.full(slots, TOP_K_DISABLED, np.int32)
        self._top_ps = np.full(slots, TOP_P_DISABLED, np.float32)
        self._slots: list[SlotInfo | None] = [None] * slots

        # Per-engine jit closures (NOT module-level): each engine owns its
        # compile cache, so compiled_programs() is an exact per-engine
        # compile counter — the bounded-compilation guarantee is testable.
        self._prefill_jit = jax.jit(
            functools.partial(_prefill_program, config=config)
        )
        self._tick_jit = jax.jit(
            functools.partial(
                _tick_program, config=config, fused=self.fused_sampling
            )
        )

        self.ticks = 0
        #: Ticks in which a live sampled slot asked for top-k / for top-p:
        #: how often each of the sampler's searches ran.
        self.sample_topk_ticks = 0
        self.sample_topp_ticks = 0
        self.tokens_emitted = 0
        #: The clock of the tick phases; the serving worker sets its own.
        self.clock = time.monotonic

    # ------------------------------------------------------------- queries

    @property
    def active_count(self) -> int:
        return int(self._active.sum())

    @property
    def free_slots(self) -> int:
        return self.n_slots - self.active_count

    def compiled_programs(self) -> int:
        """XLA programs compiled by this engine so far — bounded by
        ``len(self.buckets) + 1`` (one prefill per bucket + one tick)."""
        return self._prefill_jit._cache_size() + self._tick_jit._cache_size()

    def slot_states(self) -> list[dict]:
        """Per-slot occupancy snapshot (the ``/statusz`` view): position,
        prompt length / bucket, tokens generated vs budget for occupied
        slots; ``{"active": False}`` for vacant ones.  Host-side metadata
        only — never touches the device."""
        states: list[dict] = []
        for slot in range(self.n_slots):
            info = self._slots[slot]
            if not self._active[slot] or info is None:
                states.append({"slot": slot, "active": False})
                continue
            states.append(
                {
                    "slot": slot,
                    "active": True,
                    "position": int(self._positions[slot]),
                    "prompt_len": info.prompt_len,
                    "bucket": info.bucket,
                    "generated": info.generated,
                    "max_new_tokens": info.max_new_tokens,
                    "request_id": info.request_id,
                }
            )
        return states

    def bucket_for(self, prompt_len: int) -> int:
        """The smallest bucket holding ``prompt_len`` (prompts are padded up
        to it so prefill shapes come from a bounded set)."""
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds the largest bucket "
            f"{self.buckets[-1]}"
        )

    # ------------------------------------------------------------ lifecycle

    def admit(
        self,
        prompt_ids,
        *,
        max_new_tokens: int,
        temperature: float = 1.0,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int = 0,
        stop_id: int | None = None,
        request_id: str | None = None,
    ) -> TickEvent:
        """Prefill a free slot with ``prompt_ids`` and sample the first
        token.  Returns the admission :class:`TickEvent` (slot, first token,
        and a finish reason when one token already completes the request).
        Raises ``RuntimeError`` when no slot is free and ``ValueError`` for
        prompts the context window cannot serve.  ``request_id`` is carried
        as slot metadata only (the /statusz slot table + fleet tracing)."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        plen = prompt.shape[0]
        ctx = self.config.context_length
        if plen < 1:
            raise ValueError("prompt must contain at least one token")
        if plen > ctx - 1:
            raise ValueError(
                f"prompt of {plen} tokens leaves no room to generate in a "
                f"context of {ctx}"
            )
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        free = np.flatnonzero(~self._active)
        if free.size == 0:
            raise RuntimeError("no free slot")
        slot = int(free[0])

        bucket = self.bucket_for(plen)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = prompt
        temp_enc = np.float32(temperature)
        top_k_enc = np.int32(TOP_K_DISABLED if top_k is None else top_k)
        top_p_enc = np.float32(TOP_P_DISABLED if top_p is None else top_p)

        tok, key, self._cache = self._prefill_jit(
            self._params, self._lm_head, self._cache, padded,
            np.int32(plen), np.int32(slot), jax.random.PRNGKey(seed),
            temp_enc, top_k_enc, top_p_enc,
        )
        token = int(tok)
        self._tokens[slot] = token
        self._positions[slot] = plen
        self._keys[slot] = np.asarray(key)
        self._temps[slot] = temp_enc
        self._top_ks[slot] = top_k_enc
        self._top_ps[slot] = top_p_enc
        info = SlotInfo(
            prompt_len=plen,
            bucket=bucket,
            max_new_tokens=min(max_new_tokens, ctx - plen),
            stop_id=stop_id,
            generated=1,
            request_id=request_id,
        )
        self._slots[slot] = info
        self._active[slot] = True
        self.tokens_emitted += 1

        finished = self._finish_reason(info, token)
        if finished:
            self.release(slot)
        return TickEvent(slot=slot, token=token, finished=finished)

    def tick(self, dispatched=None) -> list[TickEvent]:
        """One batched decode step across every occupied slot: returns each
        active slot's sampled token, retiring slots that hit their stop id
        or token budget.  ``dispatched``, where given, is called once the
        tick's program is in the device's queue and before the host waits
        on its tokens: host work done there costs the device nothing."""
        if not self._active.any():
            return []
        with Phase("serve/tick_dispatch", self.clock) as dispatch:
            asked = filters_asked(
                self._active, self._temps, self._top_ks, self._top_ps
            )
            self.sample_topk_ticks += asked[0]
            self.sample_topp_ticks += asked[1]
            tokens, positions, keys, self._cache = self._tick_jit(
                self._params, self._lm_head, self._cache, self._tokens,
                self._positions, self._active, self._keys, self._temps,
                self._top_ks, self._top_ps,
            )
        if dispatched is not None:
            dispatched()
        with Phase("serve/tick_wait", self.clock) as wait:
            tokens = np.asarray(tokens)
            self._tokens = tokens.copy()
            self._positions = np.asarray(positions).copy()
            self._keys = np.asarray(keys).copy()
        self.ticks += 1

        events: list[TickEvent] = []
        with Phase("serve/tick_emit", self.clock) as emit:
            for slot in np.flatnonzero(self._active):
                slot = int(slot)
                info = self._slots[slot]
                token = int(tokens[slot])
                info.generated += 1
                self.tokens_emitted += 1
                finished = self._finish_reason(info, token)
                if finished:
                    self.release(slot)
                events.append(
                    TickEvent(slot=slot, token=token, finished=finished)
                )
        self.last_tick_s = (dispatch.dur_s, wait.dur_s, emit.dur_s)
        return events

    def release(self, slot: int) -> None:
        """Free a slot (normal retirement or cancellation).  The cache row
        is left as-is — the next admission's prefill overwrites it whole."""
        self._active[slot] = False
        self._slots[slot] = None

    @staticmethod
    def _finish_reason(info: SlotInfo, token: int) -> str | None:
        if info.stop_id is not None and token == info.stop_id:
            return "stop"
        if info.generated >= info.max_new_tokens:
            return "length"
        return None
