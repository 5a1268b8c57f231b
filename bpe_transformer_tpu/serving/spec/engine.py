"""Speculative decoding on the paged engine: draft-propose, batched
target verify, Leviathan rejection sampling, KV rewind.

Decode is memory-bound — every target tick sweeps the whole KV pool
through HBM to emit ONE token per slot.  `SpecEngine` cuts the *number*
of ticks: a small `DraftModel` guesses K tokens per slot (its own dense
KV, a few percent of the target's bytes), then ONE target pass scores
all K+1 positions through the same paged scatter + masked attention a
chunk prefill uses (`models/decode.paged_forward`, K+1 rows a slot), and
rejection sampling accepts a per-slot variable prefix.  Each tick emits
between 1 token (first guess rejected — the tick degenerates to a plain
decode step plus the cheap draft) and K+1 tokens (all accepted + the bonus),
so the HBM sweeps per emitted token drop by the acceptance rate.

**Distribution preservation** (Leviathan et al.): with target
distribution ``p`` and draft distribution ``q`` (both AFTER the slot's
temperature/top-k/top-p filtering — `serving.engine.filter_logits`),
draft token ``d ~ q`` is accepted iff ``u·q(d) < p(d)`` with
``u ~ U[0,1)``; on rejection the emitted token is drawn from
``normalize(max(p − q, 0))``.  Accepted-or-resampled, the emitted token
is distributed exactly ``p`` — speculation changes latency, never the
sampling law.  Greedy slots (temp 0) make both sides exact one-hots, so
the rule collapses to "accept while the target argmax agrees, then emit
the target argmax": greedy speculative decode is TOKEN-IDENTICAL to
non-speculative greedy (pinned by the parity suite, like the PR 8
dense/paged pins).

**KV discipline**: verify writes K/V for every scored position; rejected
rows become stale.  The engine rolls the frontier back with
`PagedEngine.rewind` — bookkeeping within a block, real block release
across boundaries (verify may write past the admission's worst-case
reservation into scratch blocks `extend_blocks` grabs per tick), and
copy-on-write if the frontier block is shared.  Stale rows are invisible
by masking until the next verify overwrites them.

**Compile bound** (fixed K): target chunk ladder + ONE verify program +
draft prefill ladder + ONE propose program — asserted by tests exactly
like the dense/paged engines' bounds.  The plain tick program never
compiles (every spec tick IS a verify).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.decode import (
    cache_kind,
    paged_forward,
    slot_cache,
)
from bpe_transformer_tpu.serving.engine import (
    TOP_K_DISABLED,
    TOP_P_DISABLED,
    SlotPoolEngine,
    TickEvent,
    default_prefill_buckets,
    filter_logits,
    filters_asked,
)
from bpe_transformer_tpu.serving.kvpool.blocks import NoFreeBlocksError
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine
from bpe_transformer_tpu.serving.spec.draft import (
    DraftModel,
    DraftSpec,
    _draft_prefill_program,
    _propose_program,
)
from bpe_transformer_tpu.telemetry.spans import Phase
from bpe_transformer_tpu.utils.compile_cache import layered_program_options

__all__ = ["SpecEngine"]


def _spec_verify_program(
    params, lm_head, pool, tables, base_tokens, draft_tokens, draft_probs,
    positions, rooms, active, keys, temps, top_ks, top_ps,
    *, config: ModelConfig, block_size: int, fused: bool = False,
):
    """One speculative tick's target half: score K+1 positions, run the
    acceptance rule, sample the bonus/correction token — all on device,
    so the host fetches only ``(out_tokens, n_emit)`` per slot.

    Row ``j`` of the verify logits is the target distribution for
    position ``positions+j+1``; rows ``0..K-1`` judge draft tokens
    ``d_1..d_K`` and row ``n_acc`` supplies the bonus (all judged rows
    accepted) or the rejection resample.  ``rooms`` caps per-slot
    speculation (context edge / block-starved scratch) inside the one
    fixed-K program.  Returns ``(out_tokens (S, K+1), n_emit (S,),
    keys, pool)`` — ``out_tokens[:n_emit]`` are the tick's emissions.

    ``fused=True`` moves the whole vocab-sized tail — head projection,
    `filter_logits`, the filtered probabilities ``p(d)`` the accept rule
    reads, and the residual ``max(p − q, 0)`` bonus sample — into ONE
    Pallas kernel (`kernels/pallas/sample.py::fused_verify_head`): the
    (S·(K+1), vocab) logits never reach HBM and the per-row sort chain
    is gone; what remains outside is O(S·K) acceptance bookkeeping.
    The residual is sampled for EVERY candidate row (cheap vector math
    against per-row gumbel noise) and row ``n_acc``'s sample is selected
    — each row's draw is an independent categorical from that row's
    residual law, so the emitted distribution is unchanged; greedy
    output is token-identical to the unfused program.
    """
    s, k = draft_tokens.shape
    k1 = k + 1
    vocab = config.vocab_size
    tokens = jnp.concatenate([base_tokens[:, None], draft_tokens], axis=1)
    # Rows 0..rooms[s] of slot s are written and scored; beyond that (and
    # past the context, and in idle slots) the scatter steers to the trash
    # block and the outputs are host-ignored: one fixed-K program serves
    # every per-slot headroom.
    pos_j = positions[:, None] + jnp.arange(k1)[None, :]
    valid = (
        (jnp.arange(k1)[None, :] <= rooms[:, None])
        & (pos_j <= config.context_length - 1)
        & active[:, None]
    )
    cache = slot_cache(config, tables, pos_j, valid, block_size=block_size)

    split = jax.vmap(lambda kk: jax.random.split(kk, 3))(keys)
    keys_next, u_keys, b_keys = split[:, 0], split[:, 1], split[:, 2]
    u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(u_keys)
    judged = jnp.arange(k)[None, :] < rooms[:, None]
    q = draft_probs  # (S, K, V)
    q_d = jnp.take_along_axis(q, draft_tokens[..., None], axis=-1)[..., 0]
    # Rows at/after the judged window verify against NO draft mass: row
    # n_acc == min(rooms, k) is the all-accepted bonus row, whose
    # distribution is p itself (q treated as 0 there).
    lim = jnp.minimum(rooms, k)
    q_pad = jnp.concatenate(
        [q, jnp.zeros((s, 1, vocab), q.dtype)], axis=1
    )
    q_pad = jnp.where(
        (jnp.arange(k1)[None, :] < lim[:, None])[..., None], q_pad, 0.0
    )

    if fused:
        from bpe_transformer_tpu.kernels.pallas.sample import (
            fused_verify_head,
        )
        from bpe_transformer_tpu.serving.engine import gumbel_rows

        hidden, pool, _ = paged_forward(
            params, tokens, pool, cache, config, lm_head, return_hidden=True
        )  # (S, K+1, d)
        rep = lambda a: jnp.repeat(a, k1, axis=0)  # noqa: E731
        judge = jnp.concatenate(
            [draft_tokens, jnp.zeros((s, 1), draft_tokens.dtype)], axis=1
        )
        gumbel = jax.vmap(
            lambda kk: jax.random.gumbel(kk, (k1, vocab), jnp.float32)
        )(b_keys)
        greedy, p_d_soft, bonus_rows = fused_verify_head(
            hidden.reshape(s * k1, -1), lm_head,
            rep(temps), rep(top_ks), rep(top_ps),
            judge.reshape(-1), q_pad.reshape(s * k1, vocab),
            gumbel.reshape(s * k1, vocab),
        )
        greedy = greedy.reshape(s, k1)
        # Greedy rows' p is an exact one-hot: p(d) is argmax agreement.
        p_d_full = jnp.where(
            (temps > 0.0)[:, None],
            p_d_soft.reshape(s, k1),
            (greedy == judge).astype(jnp.float32),
        )
        p_d = p_d_full[:, :k]
        accept = (u * q_d < p_d) & judged
        n_acc = jnp.sum(
            jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
        )
        bonus = jnp.take_along_axis(
            bonus_rows.reshape(s, k1), n_acc[:, None], axis=1
        )[:, 0]
    else:
        logits, pool, _ = paged_forward(
            params, tokens, pool, cache, config, lm_head
        )

        # Target distribution per row under the slot's runtime knobs;
        # greedy rows are EXACT one-hots (argmax of the raw logits), so
        # greedy acceptance is an integer comparison, not a float
        # threshold.
        flat = logits.reshape(s * k1, vocab)
        rep = lambda a: jnp.repeat(a, k1, axis=0)  # noqa: E731
        # A vacant slot's rows ask for no search.
        filt = filter_logits(
            flat, rep(temps),
            rep(jnp.where(active, top_ks, TOP_K_DISABLED)),
            rep(jnp.where(active, top_ps, TOP_P_DISABLED)),
        )
        p_soft = jax.nn.softmax(filt, axis=-1).reshape(s, k1, vocab)
        greedy_tok = jnp.argmax(logits, axis=-1)  # (S, K+1)
        p_greedy = jax.nn.one_hot(greedy_tok, vocab, dtype=p_soft.dtype)
        p = jnp.where((temps > 0.0)[:, None, None], p_soft, p_greedy)

        p_d = jnp.take_along_axis(
            p[:, :k], draft_tokens[..., None], axis=-1
        )[..., 0]
        # Leviathan: accept d iff u*q(d) < p(d).  Greedy: q_d == 1 and
        # p_d is 0/1, so this is exactly "target argmax == draft token".
        accept = (u * q_d.astype(p.dtype) < p_d) & judged
        n_acc = jnp.sum(
            jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
        )

        # Bonus row: the residual max(p - q, 0) at the first rejection, p
        # itself when every judged row accepted (row n_acc is then the
        # first unjudged position — q_pad is zeroed there, so one formula
        # covers both; a free extra token per fully-accepted window).
        row = n_acc[:, None, None]
        p_row = jnp.take_along_axis(p, row, axis=1)[:, 0]
        q_row = jnp.take_along_axis(
            q_pad.astype(p.dtype), row, axis=1
        )[:, 0]
        residual = jnp.maximum(p_row - q_row, 0.0)
        # p == q exactly would accept with probability 1, so a rejection
        # implies positive residual mass; the fallback guards rounding.
        has_mass = jnp.sum(residual, axis=-1, keepdims=True) > 0
        residual = jnp.where(has_mass, residual, p_row)
        res_logits = jnp.where(residual > 0, jnp.log(residual), -jnp.inf)
        bonus_sampled = jax.vmap(jax.random.categorical)(b_keys, res_logits)
        bonus = jnp.where(
            temps > 0.0, bonus_sampled, jnp.argmax(residual, axis=-1)
        )

    iota = jnp.arange(k1)[None, :]
    d_pad = jnp.concatenate([draft_tokens, draft_tokens[:, -1:]], axis=1)
    out = jnp.where(iota < n_acc[:, None], d_pad, bonus[:, None])
    n_emit = jnp.where(active, n_acc + 1, 0)
    out = jnp.where(active[:, None], out, base_tokens[:, None])
    keys_next = jnp.where(active[:, None], keys_next, keys)
    return out, n_emit, keys_next, pool


class SpecEngine(PagedEngine):
    """Speculative paged engine: the PagedEngine contract (begin /
    prefill_step / tick / release, ``TickEvent`` vocabulary, bounded
    compiles) where one :meth:`tick` may emit SEVERAL tokens per slot —
    events for one slot appear in emission order, ``finished`` on the
    last, exactly what the serving worker's delivery loop already
    handles.

    ``draft`` is a :class:`DraftSpec` (resolved against the target here)
    or a prebuilt :class:`DraftModel`; ``speculate_k`` fixes the window
    (one compiled propose + verify program each).
    """

    def __init__(
        self,
        params,
        config: ModelConfig,
        *,
        draft,
        speculate_k: int,
        min_bucket: int = 16,
        **paged_kwargs,
    ):
        if speculate_k < 1:
            raise ValueError(
                f"speculate_k must be >= 1, got {speculate_k}"
            )
        super().__init__(params, config, min_bucket=min_bucket, **paged_kwargs)
        self.cache.refuse("speculate")
        # This engine's tick is the verify pass: several rows a slot.
        self.tick_attention_path = cache_kind(config).attention_path(
            config, False, self.blocks_per_slot, self._pool[0]
        )
        if isinstance(draft, DraftSpec):
            # Build the draft from the engine's COMPUTE-DTYPE params: a
            # truncated view then shares the very arrays the target runs
            # on (zero extra weight bytes even off float32 — DraftModel's
            # cast passes already-cast leaves through untouched).
            draft = DraftModel(self._params, config, draft)
        if draft.config.vocab_size != config.vocab_size:
            raise ValueError(
                f"draft vocab_size={draft.config.vocab_size} != target "
                f"{config.vocab_size}"
            )
        if draft.config.context_length != config.context_length:
            raise ValueError(
                f"draft context_length={draft.config.context_length} != "
                f"target {config.context_length}"
            )
        self.draft = draft
        self.k = speculate_k

        from bpe_transformer_tpu.models.decode import init_kv_cache

        act_dtype = jnp.dtype(draft.config.activation_dtype)
        self._draft_cache = init_kv_cache(
            draft.config, self.n_slots, dtype=act_dtype
        )
        self._draft_keys = np.zeros((self.n_slots, 2), np.uint32)
        #: Draft prompts prefill whole (no radix sharing in the dense
        #: draft cache), so the draft ladder runs to the full context even
        #: when the target ladder is chunk-capped.
        self._draft_buckets = default_prefill_buckets(
            config.context_length, min_bucket
        )
        self._propose_jit = jax.jit(
            functools.partial(
                _propose_program, config=draft.config, k=speculate_k
            )
        )
        self._draft_prefill_jit = jax.jit(
            functools.partial(_draft_prefill_program, config=draft.config)
        )
        self._verify_jit = jax.jit(
            functools.partial(
                _spec_verify_program, config=config,
                block_size=self.block_size, fused=self.fused_sampling,
            ),
            # The pool and the layers, as in the base engine's tick.
            donate_argnums=(2,),
            compiler_options=layered_program_options(),
        )

        # Acceptance telemetry (cumulative; the serving layer snapshots
        # them into kind="spec" records, /statusz, and /metrics).
        self.spec_proposed = 0   # draft tokens actually judged (<= K/tick)
        self.spec_accepted = 0   # judged tokens the target kept
        self.spec_emitted = 0    # decode tokens emitted by spec ticks
        #: Per-SLOT verify participations: one per active slot per tick —
        #: the non-speculative engine would have paid one decode tick per
        #: unit, so emitted/target_steps IS the "ticks saved" ratio
        #: (1.0 = no win, k+1 = ceiling), independent of batch width.
        self.spec_target_steps = 0
        self.spec_rewound = 0    # stale positions rolled back
        self.draft_time_s = 0.0  # wall inside the draft propose
        self.tick_time_s = 0.0   # wall of whole spec ticks

    # ------------------------------------------------------------- queries

    @property
    def draft_buckets(self) -> tuple:
        """The draft prefill bucket ladder (runs to the full context: the
        dense draft cache has no radix sharing, so draft prompts always
        prefill whole).  ``bpe-tpu warmup`` iterates this to warm every
        draft rung."""
        return tuple(self._draft_buckets)

    def compiled_programs(self) -> int:
        """Bounded by ``len(buckets) + 1`` (chunk ladder + verify) ``+
        len(draft_buckets) + 1`` (draft prefill ladder + propose) — the
        plain tick program never compiles on the spec path (+1 more once
        a copy-on-write rewind has run, as in the base engine)."""
        return (
            super().compiled_programs()
            + self._propose_jit._cache_size()
            + self._draft_prefill_jit._cache_size()
            + self._verify_jit._cache_size()
        )

    def spec_gauges(self) -> dict:
        """The speculative-decoding operational gauges: acceptance rate,
        emitted tokens per target verify pass (the "ticks saved" number),
        and the draft's share of tick wall time."""
        proposed, accepted = self.spec_proposed, self.spec_accepted
        return {
            "spec_k": self.k,
            "spec_proposed_tokens": proposed,
            "spec_accepted_tokens": accepted,
            "spec_emitted_tokens": self.spec_emitted,
            "spec_target_steps": self.spec_target_steps,
            "spec_accept_rate": (
                round(accepted / proposed, 6) if proposed else None
            ),
            "spec_tokens_per_target_step": (
                round(self.spec_emitted / self.spec_target_steps, 6)
                if self.spec_target_steps
                else None
            ),
            "spec_rewound_tokens": self.spec_rewound,
            "spec_draft_time_s": round(self.draft_time_s, 6),
            "spec_tick_time_s": round(self.tick_time_s, 6),
            "spec_draft_frac": (
                round(self.draft_time_s / self.tick_time_s, 6)
                if self.tick_time_s > 0
                else None
            ),
        }

    def gauges(self) -> dict:
        out = super().gauges()
        out.update(self.spec_gauges())
        return out

    # ------------------------------------------------------------ migration

    def export_slot(self, slot: int, extra_meta: dict | None = None) -> dict:
        """Base payload + the slot's draft RNG key, so a speculative
        importer's proposal chain continues where this replica's left
        off (greedy migration is exact regardless — accepted tokens are
        always the target argmax chain)."""
        extra = dict(extra_meta or {})
        if self._active[slot]:
            extra.setdefault(
                "draft_key", [int(k) for k in self._draft_keys[slot]]
            )
        return super().export_slot(slot, extra)

    def import_slot(self, payload: dict) -> int:
        """Graft + draft catch-up: the dense draft cache is NOT shipped
        (a few percent of the target's bytes, but rebuildable) — the
        draft re-prefills from the grafted prefix's token history
        (``meta["history"]``: prompt + every emitted token), exactly the
        catch-up a fresh admission's final chunk performs.  K/V at a
        position is a pure function of the token prefix, so the draft's
        proposals resume from equivalent state; greedy output stays
        token-identical to the un-migrated generation by the acceptance
        rule (the emitted chain is the target argmax chain either way).
        """
        meta = payload["meta"]
        if meta.get("decoding") and meta.get("history") is None:
            raise ValueError(
                "speculative import needs meta['history'] (prompt + "
                "emitted tokens) to re-prefill the draft cache"
            )
        slot = super().import_slot(payload)
        if meta["decoding"]:
            history = [int(t) for t in meta["history"]]
            pos = int(meta["position"])
            bucket = self._draft_bucket_for(pos)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :pos] = history[:pos]
            self._draft_cache = self._draft_prefill_jit(
                self.draft.params, self.draft.lm_head, self._draft_cache,
                padded, np.int32(pos), np.int32(slot),
            )
            draft_key = meta.get("draft_key")
            self._draft_keys[slot] = (
                np.asarray(draft_key, np.uint32)
                if draft_key is not None
                else np.asarray(
                    jax.random.PRNGKey(int(meta["seed"]) ^ 0x5BEC)
                )
            )
        return slot

    # ------------------------------------------------------------ lifecycle

    def _draft_bucket_for(self, length: int) -> int:
        for b in self._draft_buckets:
            if length <= b:
                return b
        return self._draft_buckets[-1]

    def prefill_step(self, slot: int, dispatched=None) -> TickEvent | None:
        event = super().prefill_step(slot, dispatched)
        if event is None or event.finished:
            return event
        # Final chunk landed and the slot decodes on: bring the draft's
        # cache up to the same token history (whole prompt, one bucketed
        # pass) and seed its independent sampling chain.
        info = self._slots[slot]
        plen = info.prompt_len
        bucket = self._draft_bucket_for(plen)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = info.prompt
        self._draft_cache = self._draft_prefill_jit(
            self.draft.params, self.draft.lm_head, self._draft_cache,
            padded, np.int32(plen), np.int32(slot),
        )
        self._draft_keys[slot] = np.asarray(
            jax.random.PRNGKey(info.seed ^ 0x5BEC)
        )
        return event

    def launch(self) -> bool:
        raise NotImplementedError(
            "a speculative tick reads the carry on the host between its "
            "draft and its verify program, so it does not split into a "
            "launch and a collect: call tick()"
        )

    def tick(self, dispatched=None) -> list[TickEvent]:
        """One speculative tick (:meth:`_spec_tick`).  Its draft and verify
        programs each sync, so the worker's tick record gets the whole
        tick as its wait phase and no dispatch or emit share, and
        ``dispatched`` has no program to run behind: it is called first."""
        if dispatched is not None:
            dispatched()
        with Phase("serve/tick_wait", self.clock) as wait:
            events = self._spec_tick()
        self.last_tick_s = (0.0, wait.dur_s, 0.0)
        return events

    def _spec_tick(self) -> list[TickEvent]:
        """Draft-propose K, target-verify K+1, accept/resample, emit
        1..K+1 tokens per slot, rewind the rejected tail.  Event contract:
        per-slot events in emission order, ``finished`` set on the slot's
        last event."""
        if not self._active.any():
            return []
        t0 = time.perf_counter()
        # The carry on the host (the base engine keeps it on the device):
        # headroom, acceptance and rewind below are host decisions.
        tokens, positions, keys = self.read_carry()
        d_toks, d_probs, self._draft_cache, d_keys = self._propose_jit(
            self.draft.params, self.draft.lm_head, self._draft_cache,
            tokens, positions, self._active, self._draft_keys,
            self._temps, self._top_ks, self._top_ps,
        )
        jax.block_until_ready(d_toks)
        t_draft = time.perf_counter()
        self._draft_keys = np.asarray(d_keys).copy()

        # Per-slot speculation headroom: the context edge, then whatever
        # scratch blocks the pool can spare beyond the admission's
        # reservation (block-starved slots shrink their window instead of
        # stalling — the base reservation always backs room >= 1).
        ctx = self.config.context_length
        rooms = np.zeros(self.n_slots, np.int32)
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            info = self._slots[slot]
            p = int(positions[slot])
            room = min(self.k, ctx - 1 - p)
            try:
                self.extend_blocks(slot, p + room + 1)
            except NoFreeBlocksError:
                backed = len(info.block_ids) * self.block_size
                room = min(room, backed - 1 - p)
            rooms[slot] = room

        asked = filters_asked(
            self._active, self._temps, self._top_ks, self._top_ps
        )
        self.sample_topk_ticks += asked[0]
        self.sample_topp_ticks += asked[1]
        # The spec engine's tick program: the pool goes through it donated.
        out, n_emit, keys, _ = self._in_place(
            "tick", self._verify_jit,
            self._params, self._lm_head, self._pool, self.cache.tables,
            tokens, d_toks, d_probs, positions, rooms,
            self._active, keys, self._temps, self._top_ks,
            self._top_ps,
        )
        out = np.asarray(out)
        n_emit = np.asarray(n_emit)
        keys = np.asarray(keys)
        self.ticks += 1

        events: list[TickEvent] = []
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            info = self._slots[slot]
            p = int(positions[slot])
            room = int(rooms[slot])
            emit = int(n_emit[slot])
            self.spec_proposed += room
            self.spec_accepted += emit - 1
            self.spec_target_steps += 1
            emitted = 0
            finished = None
            for j in range(emit):
                token = int(out[slot, j])
                info.generated += 1
                self.tokens_emitted += 1
                self.spec_emitted += 1
                emitted += 1
                finished = SlotPoolEngine._finish_reason(info, token)
                events.append(
                    TickEvent(slot=slot, token=token, finished=finished)
                )
                if finished:
                    break
            new_p = p + emitted
            tokens[slot] = int(out[slot, emitted - 1])
            positions[slot] = new_p
            if finished:
                self.release(slot)
            else:
                # Valid KV now ends at the last emitted token; everything
                # verify wrote beyond it (rejected guesses, truncated
                # tail) rolls back — scratch blocks past the admission
                # reservation return to the pool.
                self.spec_rewound += max(0, p + room + 1 - new_p)
                self.rewind(
                    slot, new_p,
                    keep_blocks=self.blocks_needed(
                        info.prompt_len, info.max_new_tokens
                    ),
                )
        self.write_carry(tokens, positions, keys)
        now = time.perf_counter()
        self.draft_time_s += t_draft - t0
        self.tick_time_s += now - t0
        return events
