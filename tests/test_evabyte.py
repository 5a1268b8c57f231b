"""EvaByte's block at a small size on the CPU: EVA chunked linear attention
in its three forms (whole sequence, one slot's chunk, one row a slot) and
the paged engine over the summary-and-window cache - chunks that end a
window, ticks that end one, a prompt whose last window is part-filled, ticks
alone from position 0, another slot prefilling between the ticks, a slot's
next tenant - against ``chipbench/reference_evabyte.py`` on seeded float32
weights, **all logits of all prediction heads**; the visibility rule; the
recycling of a closed window's blocks; the counters; and every refusal of
what cannot run yet.

Tolerances.  Everything here runs in float32 against a float32 reference of
logits about 1.4 wide: the forms differ in the order of their sums (a running
softmax against a materialized one, a summary pooled from a block read back
against one pooled from rows in hand), which reads 5e-7 to 3e-6, so ``TOL =
2e-5`` leaves an order of magnitude of room and lies two orders under what a
bfloat16 computation reads (3e-3 and more, `test_a_bfloat16_forward_fails`),
three under float8."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bpe_transformer_tpu.models import eva  # noqa: E402
from bpe_transformer_tpu.models.config import TS_TEST_CONFIG, ModelConfig  # noqa: E402
from bpe_transformer_tpu.models.decode import (  # noqa: E402
    EvaRows,
    cache_kind,
    chunk_cache,
    eva_table_geometry,
    init_kv_cache,
    paged_forward,
    slot_cache,
)
from bpe_transformer_tpu.models.transformer import forward, init_params  # noqa: E402
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine  # noqa: E402
from chipbench import reference_evabyte as ref  # noqa: E402

TOL = 2e-5
WINDOW, CHUNK, VOCAB, HEADS = 32, 4, 40, 2

#: Hidden 64, 4 heads of 16, SwiGLU of 96, 2 layers, 2 prediction heads of 40,
#: windows of 32 in chunks of 4, a context of 4 windows.
CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "intermediate_size": 96, "vocab_size": VOCAB,
    "num_pred_heads": HEADS, "window_size": WINDOW, "chunk_size": CHUNK,
    "rope_theta": 100000.0, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 4 * WINDOW, "init_std": 0.05,
}


def program_cfg(**more) -> ModelConfig:
    args = dict(
        vocab_size=VOCAB, context_length=CFG["max_position_embeddings"],
        d_model=64, num_layers=2, num_heads=4, d_ff=96, rope_theta=100000.0,
        attention_kind="eva", eva_window=WINDOW, eva_chunk=CHUNK,
        norm_unit_offset=True, num_pred_heads=HEADS,
    )
    args.update(more)
    return ModelConfig(**args)


def weights(seed=3) -> dict:
    """The reference's seeded weights with the norms' offsets drawn too (the
    benchmark leaves them at the published 0): a missing ``1 +`` shows."""
    w = ref.weights_from_seed(seed, CFG)
    key = jax.random.PRNGKey(seed + 100)
    for i, layer in enumerate(w["layers"]):
        for j, name in enumerate(("ln1", "ln2")):
            layer[name] = 0.1 * jax.random.normal(jax.random.fold_in(key, 2 * i + j), (64,))
    w["ln_final"] = 0.1 * jax.random.normal(jax.random.fold_in(key, 99), (64,))
    return w


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n)


def small_engine(seed=3, **more) -> PagedEngine:
    args = dict(slots=3, block_size=CHUNK, prefill_chunk=16, prefill_buckets=(8, 16),
                prefix_cache=False)
    args.update(more)
    return PagedEngine(weights(seed), program_cfg(), **args)


def widest(ours, theirs) -> float:
    return float(np.max(np.abs(np.asarray(ours) - np.asarray(theirs))))


# ------------------------------------------------------ the whole sequence


@pytest.mark.parametrize("length", [80, 100, 128])
def test_forward_matches_reference(length):
    """2.5, 3.1 and 4 windows: every position, both prediction heads."""
    w, tokens = weights(), tokens_of(length)
    ours = forward(w, jnp.asarray(tokens)[None], program_cfg())[0]
    theirs = ref.forward_logits(w, tokens, CFG)
    assert ours.shape == theirs.shape == (length, HEADS * VOCAB)
    assert widest(ours, theirs) < TOL


def test_a_bfloat16_forward_fails():
    """The tolerance is tight enough: the same forward at the bfloat16 the
    configuration serves in is two orders past it, and float8 three."""
    w, tokens = weights(), tokens_of(100)
    theirs = ref.forward_logits(w, tokens, CFG)
    low = forward(w, jnp.asarray(tokens)[None], program_cfg(activation_dtype="bfloat16"))[0]
    assert widest(low, theirs) > 50 * TOL
    assert widest(ref.forward_logits(w, tokens, CFG, "fp8"), theirs) > 500 * TOL


def test_init_params_has_the_reference_tree():
    ours = init_params(jax.random.PRNGKey(0), program_cfg())
    theirs = ref.weights_from_seed(3, CFG)
    shapes = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)  # noqa: E731
    assert shapes(ours) == shapes(theirs)
    assert ours["lm_head"].shape == (HEADS * VOCAB, 64)
    bound = 16 ** -0.25
    for tree in (ours, theirs):
        attn = tree["layers"][1]["attn"]
        for name in ("eva_mu", "eva_phi"):
            assert float(jnp.max(jnp.abs(attn[name]))) <= bound + 1e-6
            assert float(jnp.std(attn[name])) > 0.3 * bound
        assert not np.any(np.asarray(tree["ln_final"]))  # offsets from one


def test_chunk_summaries_are_the_references_two_poolings():
    key = jax.random.split(jax.random.PRNGKey(1), 4)
    k, v = (jax.random.normal(kk, (4, 5, CHUNK, 16)) for kk in key[:2])
    mu, phi = (jax.random.normal(kk, (4, 16)) for kk in key[2:])
    for ours, theirs in zip(eva.chunk_summaries(k, v, mu, phi), ref.chunk_summaries(k, v, mu, phi)):
        assert widest(ours, theirs) < 1e-6
    # The key pooling's logits carry no 1 / sqrt(d), the value pooling's do,
    # and its |k|^2 / 2: a single huge key takes the whole value pooling's
    # weight away from itself.
    big = k.at[:, :, 0].multiply(30.0)
    _, v_sum = eva.chunk_summaries(big, v, mu, phi)
    rest = eva.chunk_summaries(big[:, :, 1:], v[:, :, 1:], mu, phi)[1]
    assert widest(v_sum, rest) < 1e-5


# --------------------------------------------------- the visibility rule


def brute_masks(n):
    exact = np.zeros((n, n), bool)
    summaries = np.zeros((n, -(-n // CHUNK)), bool)
    for i in range(n):
        w = i // WINDOW
        for j in range(n):
            exact[i, j] = w * WINDOW <= j <= i
        for c in range(summaries.shape[1]):
            summaries[i, c] = c < w * WINDOW // CHUNK
    return exact, summaries


def test_masks_are_the_two_sets_written_out():
    n = 3 * WINDOW + 5
    exact, summaries = eva.visibility(jnp.arange(n), n, -(-n // CHUNK), program_cfg())
    want_exact, want_summaries = brute_masks(n)
    assert np.array_equal(np.asarray(exact), want_exact)
    assert np.array_equal(np.asarray(summaries), want_summaries)
    # A summary is invisible until its window closes: the last query of a
    # window sees none of that window's chunks, the next query all of them.
    per_window = WINDOW // CHUNK
    assert want_summaries[WINDOW - 1].sum() == 0
    assert want_summaries[WINDOW].sum() == per_window
    assert want_summaries[2 * WINDOW - 1].sum() == per_window


def attention_case(n=2 * WINDOW + 8):
    config = program_cfg()
    attn = weights()["layers"][0]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, n, 64))
    return config, attn, h


def chunk_form(config, attn, h, first_window=2, k_edit=None):
    """The queries of window ``first_window`` (the last, part-filled one)
    through `xla_eva_chunk_attention`, from summary rows and that window's
    own rows alone.  ``k_edit(k_summaries)`` may replace summary rows."""
    n = h.shape[1]
    q, k, v = eva.project_qkv(h, attn, jnp.arange(n), config)
    lo = first_window * WINDOW
    chunks = lo // CHUNK
    k_sum, v_sum = eva.chunk_summaries(
        k[0, :, :lo].reshape(4, chunks, CHUNK, 16), v[0, :, :lo].reshape(4, chunks, CHUNK, 16),
        attn["eva_mu"], attn["eva_phi"],
    )
    if k_edit is not None:
        k_sum, v_sum = k_edit(k_sum, v_sum)
    pad = jnp.zeros((4, WINDOW - (n - lo), 16))
    k_rows = jnp.concatenate([k_sum, k[0, :, lo:], pad], axis=1)
    v_rows = jnp.concatenate([v_sum, v[0, :, lo:], pad], axis=1)
    att = eva.xla_eva_chunk_attention(q[0, :, lo:], k_rows, v_rows, chunks, 0, n - lo)
    return eva.linear(eva.merge_heads(att[None]), attn["output_proj"])[0]


def test_the_chunk_form_needs_no_exact_row_of_a_closed_window():
    """The chunk form is handed the summaries and the open window only, and
    gives what the whole-sequence form gives: a closed window's exact rows
    are not an input of a later query."""
    config, attn, h = attention_case()
    whole = eva.self_attention(h, attn, jnp.arange(h.shape[1]), config)[0]
    assert widest(chunk_form(config, attn, h), whole[2 * WINDOW:]) < 1e-5


def test_an_earlier_windows_key_reaches_a_later_query_through_its_summary_alone():
    """Perturb the input at one position of window 0.  Queries of window 2
    change - and by exactly what replacing that one chunk's summary row
    changes: every other row the chunk form is handed is as it was."""
    config, attn, h = attention_case()
    at = 9  # chunk 2 of window 0
    moved = h.at[0, at].add(3.0)
    n = h.shape[1]
    before = eva.self_attention(h, attn, jnp.arange(n), config)[0, 2 * WINDOW:]
    after = eva.self_attention(moved, attn, jnp.arange(n), config)[0, 2 * WINDOW:]
    assert widest(before, after) > 1e-3

    _, k2, v2 = eva.project_qkv(moved, attn, jnp.arange(n), config)
    c = at // CHUNK
    new_k, new_v = eva.chunk_summaries(
        k2[0, :, c * CHUNK:(c + 1) * CHUNK][:, None], v2[0, :, c * CHUNK:(c + 1) * CHUNK][:, None],
        attn["eva_mu"], attn["eva_phi"],
    )

    def one_row(k_sum, v_sum):
        return k_sum.at[:, c].set(new_k[:, 0]), v_sum.at[:, c].set(new_v[:, 0])

    assert widest(chunk_form(config, attn, h, k_edit=one_row), after) < 1e-5
    # Inside its own window the key is attended exactly, not pooled.
    own = eva.self_attention(moved, attn, jnp.arange(n), config)[0, at:WINDOW]
    assert widest(own, eva.self_attention(h, attn, jnp.arange(n), config)[0, at:WINDOW]) > 1e-3


# ------------------------------------------- the paged engine's two programs


def _all_logits(params, lm_head, pool, cache, tokens, *, config):
    return paged_forward(params, tokens, pool, cache, config, lm_head)[:2]


def forced_tick(eng, slot, token, position):
    """One teacher-forced tick of ``slot`` alone, its table row laid out as
    `launch` lays it out: the float32 logits of every prediction head."""
    eng.cache.enter_window(slot, position)
    tok = np.zeros((eng.n_slots, 1), np.int32)
    pos = np.zeros(eng.n_slots, np.int32)
    active = np.zeros(eng.n_slots, bool)
    tok[slot], pos[slot], active[slot] = token, position, True
    cache = slot_cache(
        eng.config, jnp.asarray(eng.cache.table_rows()), jnp.asarray(pos),
        jnp.asarray(active), block_size=eng.block_size,
    )
    logits, eng._pool = _all_logits(
        eng._params, eng._lm_head, eng._pool, cache, jnp.asarray(tok), config=eng.config
    )
    return logits[slot, 0]


def begin(eng, prompt, new=8):
    return eng.begin(prompt, max_new_tokens=new, temperature=0.0)


def served_logit_error(eng, tokens, plen, between=lambda t: None, full=None):
    """Prefill ``tokens[:plen]`` in the engine's own chunks, then
    teacher-forced ticks to the end: the widest difference of a tick's
    logits (every head) from the reference's full forward, and the slot."""
    if full is None:
        full = ref.forward_logits(weights(), tokens, CFG)
    slot = begin(eng, tokens[:plen], len(tokens) - plen + 1)
    while eng.prefill_step(slot) is None:
        pass
    worst = 0.0
    for t in range(plen, len(tokens)):
        between(t)
        worst = max(worst, widest(forced_tick(eng, slot, tokens[t], t), full[t]))
    return worst, slot


@pytest.mark.parametrize("plen", [70, 64, 95, 33])
def test_paged_chunks_and_ticks_match_reference(plen):
    """Chunks of 16 (two to a window) and then ticks to position 109, the
    window at 96 closing in decode: a prompt whose last window is
    part-filled and whose last chunk is (70: a bucket of 8 holding 6 rows,
    a block half written, which a tick completes and summarises from the
    pool), a chunk that ends a window (64: the first tick opens the next),
    a tick that ends one (95 is written by a chunk, 96 by the first tick
    after the tick-less closing; 33: a tick closes window 1 at 63 -> 64),
    while ANOTHER slot is admitted and prefills between the ticks."""
    eng = small_engine()
    assert cache_kind(eng.config) is EvaRows
    tokens, other = tokens_of(110, 2), tokens_of(41, 3)
    steps = iter(["begin", "chunk", None, "chunk", None, "chunk"])

    def another_slot_prefills(t, state={}):
        step = next(steps, None)
        if step == "begin":
            state["slot"] = begin(eng, other)
        elif step == "chunk":
            eng.prefill_step(state["slot"])

    worst, slot = served_logit_error(eng, tokens, plen, another_slot_prefills)
    assert worst < TOL
    assert not eng.pending_prefills()
    assert eng.cache.window[slot] == 3


def test_ticks_alone_from_position_0():
    """A prompt of one byte, then 100 ticks: every summary is a tick's,
    pooled from its block read back out of the pool."""
    eng = small_engine()
    tokens = tokens_of(101, 4)
    worst, _ = served_logit_error(eng, tokens, 1)
    assert worst < TOL


def test_every_row_of_a_chunk_matches_reference():
    """The chunk program hands back its last row; here every row of every
    chunk, both heads, through the engine's tables as `launch_chunk` lays
    them out - the third chunk starts window 1, the last holds 6 rows."""
    eng = small_engine()
    tokens = tokens_of(70, 5)
    full = ref.forward_logits(weights(), tokens, CFG)
    slot = begin(eng, tokens)
    for start in range(0, 70, 16):
        n = min(16, 70 - start)
        eng.cache.enter_window(slot, start)
        padded = np.zeros((1, 16), np.int32)
        padded[0, :n] = tokens[start:start + n]
        cache = chunk_cache(
            eng.config, jnp.asarray(eng.cache.table_rows(slot)), jnp.int32(start),
            jnp.int32(n), 16, block_size=CHUNK,
        )
        logits, eng._pool = _all_logits(
            eng._params, eng._lm_head, eng._pool, cache, jnp.asarray(padded),
            config=eng.config,
        )
        assert widest(logits[0, :n], full[start:start + n]) < TOL


def test_the_paged_kernel_is_the_ticks_attention_in_interpret_mode():
    """The dense pool's paged-native kernel over this kind's table and key
    counts (what the chip runs), interpreted, against gathered rows."""
    tokens = tokens_of(90, 6)
    full = ref.forward_logits(weights(), tokens, CFG)
    eng = PagedEngine(
        weights(), program_cfg(decode_attention_impl="paged"), slots=2,
        block_size=CHUNK, prefill_chunk=16, prefill_buckets=(8, 16), prefix_cache=False,
    )
    assert eng.tick_attention_path == "paged"
    worst, _ = served_logit_error(eng, tokens, 60, full=full)
    assert worst < TOL


def test_pending_summaries_are_invisible_until_their_window_closes():
    """Poison the rows the open window's summaries are written to (finite:
    a masked row's weight is an exact 0, and 0 x NaN is not).  The ticks of
    that window read as the reference; the first tick of the next window
    attends them."""
    eng = small_engine()
    tokens = tokens_of(70, 7)
    full = ref.forward_logits(weights(), tokens, CFG)
    slot = begin(eng, tokens[:40], 40)
    while eng.prefill_step(slot) is None:
        pass
    per_window, window_blocks, _ = eva_table_geometry(eng.config, CHUNK)
    row = eng.cache.tables[slot]
    pending = row[per_window + window_blocks: 2 * per_window + window_blocks]
    assert pending.all()
    # Chunks 0 and 1 of window 1 (positions 32-39) are summarised already.
    eng._pool = [
        {name: arr.at[pending[0], :2].set(50.0) for name, arr in layer.items()}
        for layer in eng._pool
    ]
    for t in range(40, 2 * WINDOW):
        assert widest(forced_tick(eng, slot, tokens[t], t), full[t]) < TOL
    assert widest(forced_tick(eng, slot, tokens[64], 64), full[64]) > 0.1


# ----------------------------------------- blocks, counters and the real loop


def test_a_request_holds_one_window_and_a_summary_block_per_sixteen_blocks():
    eng = small_engine()
    per_window, window_blocks, width = eva_table_geometry(eng.config, CHUNK)
    assert (per_window, window_blocks, width) == (2, 8, 4 * 2 + 8)
    assert eng.blocks_per_slot == width and eng.max_chain == 8 + 3 * 2
    # span 20: five blocks of one window, none closes; span 33: a window
    # and one closing; the whole context: a window and three closings.
    assert eng.blocks_needed(12, 8) == 5
    assert eng.blocks_needed(25, 8) == 8 + 2
    assert eng.blocks_needed(100, 28) == 8 + 3 * 2
    free = eng.allocator.free_count
    slot = begin(eng, tokens_of(70), 30)
    info = eng._slots[slot]
    assert (eng.cache.window_blocks[slot], len(info.block_ids)) == (8, 8 + 3 * 2)
    row = eng.cache.tables[slot]
    assert list(row[:8]) == info.block_ids[:8] and list(row[8:10]) == info.block_ids[8:10]
    assert not row[10:].any()
    eng.release(slot)
    assert eng.allocator.free_count == free and not eng.cache.tables[slot].any()


def test_engine_serves_greedy_tokens_the_reference_puts_first():
    """The real loop, one launch ahead of its reads: `launch` lays the rows
    out as windows close; windows close in prefill (32, 64) and in decode
    (96); the counters are the arithmetic of what was attended."""
    eng = small_engine()
    prompt = tokens_of(70, 8)
    event = eng.admit(prompt, max_new_tokens=35, temperature=0.0)
    served = [event.token]
    eng.launch()
    while eng.unread:
        eng.launch()
        served += [e.token for e in eng.collect()]
    assert len(served) == 35
    fed = np.concatenate([prompt, served[:-1]])
    logits = ref.forward_logits(weights(), fed, CFG)[69:, :VOCAB]
    gaps = logits.max(-1) - logits[np.arange(35), served]
    assert float(gaps.max()) < TOL

    stats = eng.gauges()
    layers, per_window = 2, WINDOW // CHUNK
    ticked = np.arange(70, 104)  # the positions the 34 ticks wrote
    summaries = ticked // WINDOW * per_window
    assert stats["attn_kv_positions"] == layers * int((summaries + ticked % WINDOW + 1).sum())
    assert stats["attn_summary_kv_positions"] == layers * int(summaries.sum())
    chunks = [(0, 16), (16, 16), (32, 16), (48, 16), (64, 6)]
    pairs = sum(
        n * (s // WINDOW * per_window + s % WINDOW) + n * (n + 1) // 2 for s, n in chunks
    )
    assert stats["attn_pairs"] == stats["attn_kv_positions"] + layers * pairs
    # Whole chunks the chunks wrote (4 + 4 + 4 + 4 + 1) and the ticks closed.
    assert stats["eva_summary_rows"] == layers * (17 + int((ticked % CHUNK == CHUNK - 1).sum()))
    assert stats["eva_windows_closed"] == 3
    assert stats["kv_window_blocks_recycled"] == 3 * 8
    assert stats["kv_summary_blocks_used"] == 0  # released at the finish
    assert eng.last_tick_counts == {
        "attn_kv_positions": layers * (3 * per_window + 103 % WINDOW + 1),
        "attn_summary_kv_positions": layers * 3 * per_window,
    }


def test_a_slots_next_tenant_serves_as_a_fresh_engine_does():
    """Slot 0 serves a request across two closings and is released; its
    next tenant's rows - summary blocks, window blocks, the table row - owe
    nothing to it."""
    eng = small_engine(slots=1)
    first = tokens_of(80, 9)
    eng.admit(first[:50], max_new_tokens=30, temperature=0.0)
    while eng.tick():
        pass
    assert eng.free_slots == 1
    tokens = tokens_of(75, 10)
    worst, slot = served_logit_error(eng, tokens, 37)
    assert slot == 0 and worst < TOL


def test_no_program_compiles_after_the_warm_up():
    eng = small_engine()
    for n in (8, 16):
        eng.admit(tokens_of(n), max_new_tokens=2, temperature=0.0)
        while eng.tick():
            pass
    warm = eng.compiled_programs()
    assert warm == len(eng.buckets) + 1
    eng.admit(tokens_of(77, 11), max_new_tokens=30, temperature=1.0, top_k=5, seed=1)
    while eng.tick():
        pass
    assert eng.compiled_programs() == warm


def test_the_tick_record_carries_the_ticks_own_rows():
    from bpe_transformer_tpu.serving.server import Request, ServingEngine
    from bpe_transformer_tpu.telemetry.spans import Telemetry

    records = []
    engine = ServingEngine(
        weights(), program_cfg(), telemetry=Telemetry(sink=records.append), paged=True,
        slots=2, block_size=CHUNK, prefill_chunk=16, prefill_buckets=(8, 16),
        prefix_cache=False,
    )
    engine.start()
    try:
        result = engine.submit(
            Request(prompt_ids=tuple(tokens_of(40).tolist()), max_new_tokens=6, temperature=0.0)
        ).result(timeout=300)
        stats = engine.stats()
    finally:
        engine.close()
    assert len(result.token_ids) == 6 and all(0 <= t < VOCAB for t in result.token_ids)
    ticks = [r for r in records if r.get("kind") == "tick" and "attn_summary_kv_positions" in r]
    assert ticks and ticks[-1]["attn_summary_kv_positions"] == 2 * (WINDOW // CHUNK)
    assert ticks[-1]["attn_kv_positions"] > ticks[-1]["attn_summary_kv_positions"]
    for name in ("attn_summary_kv_positions", "eva_summary_rows", "eva_windows_closed",
                 "kv_summary_blocks_used", "kv_pool_bytes", "kv_bytes_per_token"):
        assert name in stats, name


def test_served_gaps_read_zero_on_the_references_own_tokens_and_the_control_does_not():
    """``served_gaps`` at the seed's own weights (norm offsets 0, as the
    benchmark serves them): the reference's own greedy bytes read 0 at every
    served position; the float8 control's bytes do not."""
    prompt = tokens_of(50, 12).tolist()
    # bfloat16 weights, as served: score the bytes of that model.
    w16 = ref.weights_from_seed(3, CFG, jnp.bfloat16)
    served16 = []
    for _ in range(20):
        logits = ref.forward_logits(w16, np.asarray(prompt + served16), CFG)
        served16.append(int(logits[-1, :VOCAB].argmax()))
    sound = ref.served_gaps(3, CFG, [(prompt, served16)])
    control = ref.served_gaps(3, CFG, [(prompt, served16)], control=True)
    assert sound == [0.0]
    assert control[0] > 1e-3


# ------------------------------------------------------------ the refusals

REFUSED_AT_CONSTRUCTION = {
    "prefix_cache": (dict(prefix_cache=True), "closed windows have no exact rows"),
    "int8_kv": (dict(kv_dtype="int8"), "shares no block scale"),
    "fused_sampling": (dict(fused_sampling=True), "samples prediction head 0"),
    "int8_weights": (dict(weight_dtype="int8"), "activation width only"),
    "block_is_not_a_chunk": (dict(block_size=8), "must equal eva_chunk"),
    "chunk_straddles_a_window": (dict(prefill_chunk=24, prefill_buckets=(8,)), "inside one window"),
    "bucket_off_the_blocks": (dict(prefill_buckets=(6, 16)), "multiples of block_size"),
}


@pytest.mark.parametrize("name", REFUSED_AT_CONSTRUCTION)
def test_engine_refuses_at_construction(name):
    more, message = REFUSED_AT_CONSTRUCTION[name]
    with pytest.raises(ValueError, match=message):
        small_engine(**more)


@pytest.mark.parametrize("what", ["extend_blocks", "export_slot", "import_slot", "rewind"])
def test_engine_refuses_scratch_migration_and_rewind(what):
    eng = small_engine()
    slot = eng.admit(tokens_of(20), max_new_tokens=4, temperature=0.0).slot
    call = {
        "extend_blocks": lambda: eng.extend_blocks(slot, 40),
        "export_slot": lambda: eng.export_slot(slot),
        "import_slot": lambda: eng.import_slot({"meta": {}, "layers": []}),
        "rewind": lambda: eng.rewind(slot, 10),
    }[what]
    with pytest.raises(NotImplementedError, match="summary-and-window cache"):
        call()


@pytest.mark.parametrize(
    "more, message",
    [
        (dict(paged=False), "served by the paged engine"),
        (dict(paged=True, speculate_k=2), "straddle a window's closing"),
        (dict(paged=True, role="decode"), "one chain of positions"),
    ],
)
def test_serving_engine_refuses(more, message):
    from bpe_transformer_tpu.serving.server import ServingEngine

    with pytest.raises(ValueError, match=message):
        ServingEngine(weights(), program_cfg(), prefix_cache=False, **more)


def test_spec_and_slot_pool_engines_the_dense_cache_and_a_verify_pass_refuse():
    from bpe_transformer_tpu.serving.engine import SlotPoolEngine
    from bpe_transformer_tpu.serving.spec.engine import DraftSpec, SpecEngine

    config = program_cfg()
    with pytest.raises(ValueError, match="summary-and-window cache"):
        SlotPoolEngine(weights(), config)
    with pytest.raises((ValueError, NotImplementedError)):
        SpecEngine(
            weights(), config, draft=DraftSpec(truncate_layers=1), speculate_k=2,
            block_size=CHUNK, prefix_cache=False,
        )
    with pytest.raises(NotImplementedError, match="no dense cache"):
        init_kv_cache(config, 1)
    with pytest.raises(NotImplementedError, match="several rows a slot"):
        slot_cache(
            config, jnp.zeros((2, 16), jnp.int32), jnp.zeros((2, 3), jnp.int32),
            block_size=CHUNK,
        )


def test_scan_layers_and_training_are_refused():
    from bpe_transformer_tpu.training.train_step import make_loss_fn

    with pytest.raises(ValueError, match="scan_layers"):
        program_cfg(scan_layers=True)
    with pytest.raises(ValueError, match="training is not supported"):
        make_loss_fn(program_cfg())


@pytest.mark.parametrize(
    "change, message",
    [
        (dict(sliding_window=16), "contradict"),
        (dict(attention_kind="mla"), "eva_window"),
        (dict(attn_layer_period=2, ssm_heads=2, ssm_head_dim=4, ssm_state=4), "contradict"),
        (dict(num_kv_heads=2), "contradict it"),
        (dict(parallel_block=True), "contradict it"),
        (dict(tie_embeddings=True), "contradict it"),
        (dict(ffn_type="moe", n_experts=2), "contradict it"),
        (dict(eva_chunk=5), "dividing eva_window"),
        (dict(eva_window=48), "divides"),
        (dict(num_pred_heads=0), "contradict it"),
    ],
)
def test_config_refuses_contradictions(change, message):
    with pytest.raises(ValueError, match=message):
        program_cfg(**change)


@pytest.mark.parametrize("field", ["eva_window", "norm_unit_offset", "num_pred_heads"])
def test_the_new_fields_are_this_blocks_alone(field):
    value = {"eva_window": 32, "norm_unit_offset": True, "num_pred_heads": 2}[field]
    with pytest.raises(ValueError, match="chunked linear attention's"):
        dataclasses.replace(TS_TEST_CONFIG, **{field: value})


def test_config_properties_and_defaults():
    config = program_cfg()
    assert config.eva_block and config.dropless_block and not config.latent_block
    assert config.head_width == HEADS * VOCAB and config.eva_chunks_per_window == 8
    assert config.d_head == 16 and config.attn_sublayers == 1
    plain = TS_TEST_CONFIG
    assert not plain.eva_block and plain.head_width == plain.vocab_size
    assert (plain.eva_window, plain.eva_chunk, plain.num_pred_heads) == (0, 0, 1)
    assert not plain.norm_unit_offset and not plain.dropless_block


def test_the_chip_side_check_of_every_head_reads_rounding_error_here():
    """`tools/check_evabyte_heads.py` (what a builder runs at the published
    width, where ``correct`` scores head 0 alone) at this file's size in
    float32: both heads agree with the reference across a closing in
    prefill and one in the ticks, and the float8 control does not."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    import check_evabyte_heads

    out = check_evabyte_heads.read(
        CFG, program_cfg(), 7, 70, 40, chunk=16, dtype=jnp.float32
    )
    assert (out["windows_closed_in_prefill"], out["windows_closed_in_ticks"]) == (2, 1)
    assert len(out["program_widest_by_head"]) == HEADS
    assert max(out["program_widest_by_head"]) < TOL
    assert min(out["control_widest_by_head"]) > 100 * TOL
