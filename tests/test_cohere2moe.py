"""The Cohere2-MoE block (`command-a-plus-05-2026`) at a small size on the
CPU: the plain forward, the dense-cache path and the paged two-group engine
against ``chipbench/reference_cohere2moe.py`` on seeded float32 weights; the
share test that ties a chip's experts to the whole layer; the window pool
group's allocator; and every refusal of what cannot run yet."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bpe_transformer_tpu.models.config import TS_TEST_CONFIG, ModelConfig  # noqa: E402
from bpe_transformer_tpu.models.decode import (  # noqa: E402
    decode_step,
    init_kv_cache,
    paged_forward,
    prefill,
    slot_cache,
)
from bpe_transformer_tpu.models.moe import dropless_moe  # noqa: E402
from bpe_transformer_tpu.models.transformer import forward, init_params  # noqa: E402
from bpe_transformer_tpu.serving.kvpool.blocks import (  # noqa: E402
    BlockAllocator,
    NoFreeBlocksError,
    WindowChain,
)
from bpe_transformer_tpu.serving.kvpool.host_cache import HostDenseRows  # noqa: E402
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine  # noqa: E402
from chipbench import reference_cohere2moe as ref  # noqa: E402

WINDOW = 6


def reference_cfg(held=8, offset=0, layers=8) -> dict:
    """Two periods of the pattern, GQA (4 query heads on 2 KV heads), head
    width 16 over a hidden size of 32, 8 experts top-2, 2 shared."""
    return {
        "hidden_size": 32, "intermediate_size": 16, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": layers, "num_experts": held, "n_experts": 8,
        "expert_offset": offset, "num_experts_per_tok": 2,
        "num_shared_experts": 2,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
        "sliding_window": WINDOW, "rope_theta": 50000, "layer_norm_eps": 1e-5,
        "logit_scale": 1, "vocab_size": 64, "context_length": 32,
    }


def program_cfg(c: dict, **more) -> ModelConfig:
    return ModelConfig(
        vocab_size=c["vocab_size"], context_length=32, d_model=c["hidden_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        head_dim=c["head_dim"], rope_theta=c["rope_theta"],
        sliding_window=c["sliding_window"], sliding_window_pattern=4,
        rope_on_full_layers=False, norm_type="layernorm", parallel_block=True,
        tie_embeddings=True, ffn_type="moe", moe_router="sigmoid",
        n_experts=c["n_experts"], router_top_k=c["num_experts_per_tok"],
        n_shared_experts=c["num_shared_experts"], experts_held=c["num_experts"],
        expert_offset=c["expert_offset"], **more,
    )


SHARES = {"held_all": (8, 0), "held_share": (2, 2)}


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_reference(share):
    """Window (6) shorter than the sequence (20), two periods, GQA."""
    c = reference_cfg(*SHARES[share])
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 20))
    ours = forward(w, jnp.asarray(tokens), program_cfg(c))
    theirs = ref.forward_logits(w, tokens, c)
    assert float(jnp.max(jnp.abs(ours - theirs))) < 2e-6


# --------------------------------------- how the reference scores a served run


def test_routing_choices_follow_near_ties_of_held_experts():
    c = reference_cfg(2, 2)  # holds experts 2 and 3 of 8, two a token
    logits = np.asarray([
        [3.0, 2.0, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0],   # decided
        [3.0, 0.0, 2.0, 1.95, -1.0, -1.0, -2.0, -3.0],  # 2 against 3: both held
        [3.0, 2.0, 0.0, -1.0, 1.95, -1.0, -2.0, -3.0],  # 1 against 4: neither held
        [3.0, 2.95, 2.9, 0.0, -1.0, -1.0, -2.0, -3.0],  # 0, 1 against 2 (held)
    ], np.float32)
    sets = [[sorted(s.tolist()) for s in row] for row in ref.routing_choices(logits, c)]
    assert sets[0] == [[0, 1]]
    assert sets[1] == [[0, 2], [0, 3]]
    assert sets[2] == [[0, 1]]
    assert sets[3] == [[0, 1], [1, 2], [0, 2]]


def test_rows_of_decided_positions_equal_the_full_forward(monkeypatch):
    """With no near tie to follow, every position is one row and equals the
    full forward: single-row attention against the sequence's keys."""
    monkeypatch.setattr(ref, "ROUTER_MARGIN", 0.0)
    c = reference_cfg(2, 2)
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(4).integers(0, 64, 24).astype(np.int32)
    keys = []
    full = ref.hidden_states(w, tokens, c, keys=keys)
    rows, origin = ref.followed_routings(w, c, tokens, keys, 5, 24)
    assert origin.tolist() == list(range(19))
    assert float(np.max(np.abs(rows - np.asarray(full[5:24])))) < 2e-5


def test_a_routing_taken_at_a_near_tie_is_among_the_rows(monkeypatch):
    """A program that gives up a chosen expert for its neighbour at one
    position of layer 0 ends in a state the full forward never reaches; the
    rows followed for that position hold it."""
    monkeypatch.setattr(ref, "ROUTER_MARGIN", 10.0)  # every edge is a tie
    c = ref._Frozen(reference_cfg(2, 2, layers=4))
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(5).integers(0, 64, 16).astype(np.int32)
    at = 15
    with jax.default_matmul_precision("highest"):
        x = w["token_embeddings"][jnp.asarray(tokens)].astype(jnp.float32)
        for i, p in enumerate(w["layers"]):
            h = ref._layernorm(x, p["ln1"], c["layer_norm_eps"])
            attended, _, _ = ref.attention(h, p["attn"], c, *ref.layer_kind(c, i), None)
            chosen = None
            if i == 0:
                _, logits = ref.moe(h, p["ffn"], c, None)
                sets = ref.routing_choices(logits, c)
                assert len(sets[at]) > 1
                chosen = np.stack([s[0] for s in sets])
                chosen[at] = sets[at][1]
                chosen = jnp.asarray(chosen, jnp.int32)
            x = x + attended + ref.moe(h, p["ffn"], c, None, chosen)[0]
    taken = np.asarray(x[at])
    keys = []
    own = np.asarray(ref.hidden_states(w, tokens, c, keys=keys)[at])
    rows, origin = ref.followed_routings(w, c, tokens, keys, at, at + 1)
    assert 1 < len(rows) <= ref.MAX_ROUTINGS + 3 * ref.NEAR
    moved = float(np.max(np.abs(own - taken)))
    assert moved > 1e-4
    assert min(float(np.max(np.abs(row - taken))) for row in rows) < moved / 50
    assert float(np.max(np.abs(rows[0] - own))) < moved / 50  # the reference's own first


def test_served_gaps_of_the_references_own_greedy_tokens():
    c = reference_cfg(2, 2, layers=4)
    w = ref.weights_from_seed(11, c, jnp.bfloat16)
    prompt = np.random.default_rng(6).integers(0, 64, 9).tolist()
    ids = list(prompt)
    for _ in range(5):
        ids.append(int(jnp.argmax(ref.forward_logits(w, np.asarray([ids]), c)[0, -1])))
    (gap,) = ref.served_gaps(11, c, [(prompt, ids[9:])])
    assert gap < 1e-4
    wrong = [(t + 1) % 64 for t in ids[9:]]
    (gap,) = ref.served_gaps(11, c, [(prompt, wrong)])
    assert gap > 1e-3
    assert all(g >= 0 for g in ref.served_gaps(11, c, [(prompt, ids[9:])], control=True))


def test_no_program_compiles_after_the_warm_up():
    """One request a bucket, alone, is the cell's warm-up; a chunk straight
    after a chunk (two prompts admitted in one period) and a tick after
    either then run the programs that are there (the routing counts the
    device carries are a program's output every time, never a host array)."""
    eng = small_engine(reference_cfg(2, 2, layers=4))
    rng = np.random.default_rng(0)

    def begin(n):
        return eng.begin(rng.integers(0, 64, n), max_new_tokens=6, temperature=0.0)

    for n in (2, 4):
        slot = begin(n)
        while eng.prefill_step(slot) is None:
            pass
        eng.tick(), eng.tick(), eng.release(slot)
    warm = eng.compiled_programs()
    assert warm == len(eng.buckets) + 1
    first, second = begin(4), begin(2)
    eng.prefill_step(first), eng.prefill_step(second)
    eng.tick(), eng.tick()
    assert eng.compiled_programs() == warm
    assert eng.gauges()["moe_tokens_routed"] == 4 * (2 + 4 + 4 + 2 + 2 + 2 + 2 + 2)


def test_attention_counters_count_pairs_and_positions():
    c = reference_cfg(2, 2, layers=4)  # three window layers, one full
    eng = small_engine(c)

    def brute(start, end):
        pairs = sum(3 * min(q + 1, WINDOW) + q + 1 for q in range(start, end))
        reach = 3 * (end - max(start - WINDOW + 1, 0)) + end
        return pairs, reach

    for start, end in [(0, 4), (3, 9), (8, 12), (20, 21)]:
        before = eng.cache.attn_pairs, eng.cache.attn_kv_positions
        eng.cache.count_attention(start, end)
        got = eng.cache.attn_pairs - before[0], eng.cache.attn_kv_positions - before[1]
        assert got == brute(start, end)


def test_init_params_has_the_reference_tree():
    c = reference_cfg(2, 2)
    ours = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), program_cfg(c)))
    theirs = jax.eval_shape(lambda: ref.init_weights(0, c))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(ours) == shapes(theirs)


def test_dense_cache_matches_reference():
    """prefill + decode_step over the dense cache: the window is a mask."""
    c = reference_cfg(2, 2)
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(1).integers(0, 64, (2, 24))
    full = ref.forward_logits(w, tokens, c)
    logits, cache = prefill(w, jnp.asarray(tokens[:, :10]), pc, init_kv_cache(pc, 2))
    worst = float(jnp.max(jnp.abs(logits - full[:, 9])))
    for t in range(10, 24):
        logits, cache = decode_step(w, jnp.asarray(tokens[:, t]), jnp.asarray(t), cache, pc)
        worst = max(worst, float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert worst < 2e-6


def small_engine(c, **more) -> PagedEngine:
    args = dict(
        slots=3, block_size=2, prefill_chunk=4, prefill_buckets=(2, 4),
        prefix_cache=False,
    )
    args.update(more)
    return PagedEngine(ref.weights_from_seed(3, c), program_cfg(c), **args)


def aligned64(array: np.ndarray) -> np.ndarray:
    """A copy of ``array`` that starts on a 64-byte boundary: the CPU backend
    reads such a jit argument where it lies, without copying it."""
    raw = np.zeros(array.nbytes + 64, np.uint8)
    start = (-raw.ctypes.data) % 64
    out = raw[start:start + array.nbytes].view(array.dtype).reshape(array.shape)
    out[...] = array
    return out


def test_a_chunk_is_handed_its_own_rows():
    """`HostGroupedPages._write_window_row` rewrites a slot's row in place while the last
    chunk's program may still be waiting: what a chunk program is handed
    shares no memory with the engine's tables."""
    eng = small_engine(reference_cfg(2, 2, layers=4))
    for slot in range(eng.n_slots):
        for handed in eng.cache.table_rows(slot).values():
            for table in (eng.cache.tables, eng.cache.window_tables, eng.cache.window_base):
                assert not np.shares_memory(handed, table)
    one_group = PagedEngine(
        init_params(jax.random.PRNGKey(0), TS_TEST_CONFIG), TS_TEST_CONFIG,
        slots=2, block_size=4, prefix_cache=False,
    )
    assert not np.shares_memory(one_group.cache.table_rows(1), one_group.cache.tables)


def test_paged_two_groups_match_reference_logits_past_the_window():
    """Prefill in chunks of 4 and teacher-forced decode through the paged
    two-group pools, 30 positions against a window of 6 and blocks of 2:
    logits (not tokens) against the reference's full forward, with window
    blocks recycled mid-request.  The host's tables lie 64-byte aligned,
    where the CPU backend reads a jit argument in place: a chunk that was
    handed a view of its row attended through the next chunk's (ROADMAP
    D11: 0.29 here, one run in five where the alignment was chance)."""
    c = reference_cfg(2, 2)
    eng = small_engine(c)
    eng.cache.tables = aligned64(eng.cache.tables)
    eng.cache.window_tables = aligned64(eng.cache.window_tables)
    pc, w = eng.config, ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(2).integers(0, 64, 30)
    full = ref.forward_logits(w, tokens[None], c)[0]
    plen = 13
    slot = eng.begin(tokens[:plen], max_new_tokens=17, temperature=0.0)
    while eng.prefill_step(slot) is None:
        pass
    assert eng.cache.window_recycled > 0  # recycled while still prefilling
    chain = eng.cache.chains[slot]
    worst, longest = 0.0, len(chain.ids)
    active = np.zeros(eng.n_slots, bool)
    active[slot] = True
    for t in range(plen, 30):
        eng.cache.advance_window(slot, t - WINDOW + 1)
        longest = max(longest, len(chain.ids))
        tok = np.zeros(eng.n_slots, np.int32)
        pos = np.zeros(eng.n_slots, np.int32)
        tok[slot], pos[slot] = tokens[t], t
        cache = slot_cache(
            pc, eng.cache.table_rows(), jnp.asarray(pos), jnp.asarray(active),
            block_size=2,
        )
        logits, eng._pool, _ = paged_forward(
            eng._params, jnp.asarray(tok)[:, None], eng._pool, cache, pc,
            eng._lm_head, row=0,
        )
        worst = max(worst, float(jnp.max(jnp.abs(logits[slot] - full[t]))))
    assert worst < 2e-6
    assert longest <= eng.cache.window_cap == (WINDOW + 4) // 2
    assert chain.first > 0 and eng.cache.window_base[slot] == chain.first * 2


def test_engine_serves_greedy_tokens_the_reference_puts_first():
    """Three slots at ragged depths through admit/tick, the way the worker
    drives the engine; counters move and every block comes back."""
    c = reference_cfg(2, 2)
    eng = small_engine(c)
    w = ref.weights_from_seed(3, c)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n) for n in (13, 5, 9)]
    seqs = [list(p) for p in prompts]
    for seq, prompt in zip(seqs, prompts):
        seq.append(eng.admit(prompt, max_new_tokens=12, temperature=0.0).token)
    while eng.active_count:
        for event in eng.tick():
            seqs[event.slot].append(event.token)
    for prompt, seq in zip(prompts, seqs):
        assert len(seq) == len(prompt) + 12
        full = ref.forward_logits(w, np.asarray(seq)[None], c)[0]
        for i in range(len(prompt) - 1, len(seq) - 1):
            assert float(full[i].max() - full[i, seq[i + 1]]) < 1e-5
    gauges = eng.gauges()
    assert gauges["kv_window_blocks_recycled"] > 0
    assert gauges["kv_window_blocks_free"] == gauges["kv_window_blocks_total"]
    assert gauges["kv_full_blocks_free"] == gauges["kv_full_blocks_total"]
    assert gauges["moe_tokens_routed"] > 0
    assert 0 < gauges["moe_rows_local"] <= 2 * gauges["moe_tokens_routed"]
    assert 0 < gauges["moe_expert_groups"] <= gauges["moe_rows_local"]
    assert eng.last_tick_moe_rows_local >= 0


def test_all_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts of all 4 shares of 2 experts, plus
    the shared part once, equal the uncut reference layer."""
    uncut = reference_cfg(8, 0, layers=1)
    w = ref.weights_from_seed(7, uncut)["layers"][0]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(1), (11, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(h, w, uncut, None)
    nobody = jnp.zeros((11,), bool)
    total = None
    for offset in range(0, 8, 2):
        share = {
            **w, "w1": w["w1"][offset:offset + 2],
            "w2": w["w2"][offset:offset + 2], "w3": w["w3"][offset:offset + 2],
        }
        cfg = program_cfg(reference_cfg(2, offset, layers=1))
        out, counts = dropless_moe(h, share, cfg)
        shared_alone, _ = dropless_moe(h, share, cfg, valid=nobody)
        total = out - shared_alone if total is None else total + out - shared_alone
        assert int(counts[0]) == 11 and int(counts[1]) <= 22
    assert float(jnp.max(jnp.abs(total + shared_alone - want))) < 1e-6


def test_dropless_moe_counts_and_valid_rows():
    cfg = program_cfg(reference_cfg(8, 0, layers=1))
    w = ref.weights_from_seed(7, reference_cfg(8, 0, layers=1))["layers"][0]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(2), (9, 32), jnp.float32)
    out, counts = dropless_moe(h, w, cfg)
    assert [int(v) for v in counts[:2]] == [9, 18]  # all held: every assignment local
    valid = jnp.arange(9) < 4
    cut, counts = dropless_moe(h, w, cfg, valid=valid)
    assert [int(v) for v in counts[:2]] == [4, 8]
    assert float(jnp.max(jnp.abs(cut[:4] - out[:4]))) < 1e-6


# ------------------------------------------------------- window pool group


def test_window_chain_never_exceeds_window_plus_chunk():
    alloc = BlockAllocator(100, 4)
    window, chunk = 16, 8
    cap = (window + chunk) // 4
    chain = WindowChain(alloc, cap, need=25)  # a request of 100 positions
    assert len(chain.ids) == cap and alloc.free_count == 99 - cap
    longest = 0
    for start in range(0, 64, chunk):  # prefill chunks
        chain.advance(start - window + 1)
        assert chain.covers(max(start - window + 1, 0))
        assert chain.covers(start + chunk - 1)
        longest = max(longest, len(chain.ids))
    for pos in range(64, 100):  # decode
        chain.advance(pos - window + 1)
        assert chain.covers(max(pos - window + 1, 0)) and chain.covers(pos)
        longest = max(longest, len(chain.ids))
    assert longest == cap
    assert chain.recycled == chain.first > 0


def test_recycled_blocks_return_to_the_free_list():
    alloc = BlockAllocator(12, 4)
    chain = WindowChain(alloc, cap=4, need=10)
    held = list(chain.ids)
    assert alloc.free_count == 11 - 4
    # A second request cannot be admitted beside it ...
    with pytest.raises(NoFreeBlocksError):
        WindowChain(alloc, cap=8, need=8)
    assert chain.advance(2 * 4) == 2  # two blocks wholly below position 8
    assert all(alloc.refcount(b) == 0 or b in chain.ids for b in held[:2])
    assert len(chain.ids) == 4 and alloc.free_count == 11 - 4
    chain.release()
    assert alloc.free_count == 11 and chain.ids == []


def test_short_request_never_recycles():
    alloc = BlockAllocator(50, 4)
    chain = WindowChain(alloc, cap=6, need=3)
    for pos in range(12):
        assert chain.advance(pos - 16 + 1) == 0 and chain.covers(pos)
    assert chain.first == 0 and len(chain.ids) == 3


def test_one_group_case_is_todays_engine():
    """No window layers: no window group, the pool and programs of before."""
    cfg = dataclasses.replace(TS_TEST_CONFIG, vocab_size=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = PagedEngine(params, cfg, slots=2, block_size=4, prefix_cache=False)
    assert type(eng.cache) is HostDenseRows  # no window group, no second allocator
    assert isinstance(eng._pool[0], dict) and set(eng._pool[0]) == {"k", "v"}
    first = eng.admit(np.arange(5), max_new_tokens=4, temperature=0.0)
    tokens = [first.token] + [e.token for _ in range(3) for e in eng.tick()]
    want = list(np.arange(5))
    for _ in range(4):
        want.append(int(jnp.argmax(forward(params, jnp.asarray([want]), cfg)[0, -1])))
    assert tokens == want[5:]
    gauges = eng.gauges()
    assert gauges["kv_full_blocks_total"] == gauges["kv_blocks_total"]
    assert gauges["kv_full_blocks_free"] == gauges["kv_blocks_free"]
    assert gauges["kv_window_blocks_total"] == 0 == gauges["kv_window_blocks_recycled"]
    assert gauges["moe_rows_local"] == 0


# ---------------------------------------------------------------- refusals


@pytest.mark.parametrize(
    "more",
    [
        dict(prefix_cache=True), dict(kv_dtype="int8"), dict(weight_dtype="int8"),
        dict(fused_sampling=True), dict(block_size=4),  # 6 % 4: window not aligned
    ],
    ids=["prefix_cache", "kv_int8", "weight_int8", "fused_sampling", "unaligned_window"],
)
def test_engine_refuses_at_construction(more):
    with pytest.raises(ValueError):
        small_engine(reference_cfg(2, 2), **more)


@pytest.mark.parametrize("what", ["rewind", "extend_blocks", "export_slot", "import_slot"])
def test_engine_refuses_rollback_and_migration(what):
    eng = small_engine(reference_cfg(2, 2))
    call = {
        "rewind": lambda: eng.rewind(0, 0),
        "extend_blocks": lambda: eng.extend_blocks(0, 8),
        "export_slot": lambda: eng.export_slot(0),
        "import_slot": lambda: eng.validate_import_meta({"format": 1}),
    }[what]
    with pytest.raises(NotImplementedError, match="window pool groups"):
        call()


@pytest.mark.parametrize(
    "more",
    [dict(paged=False), dict(speculate_k=2), dict(role="prefill"), dict(role="decode")],
    ids=["dense_engine", "speculation", "prefill_role", "decode_role"],
)
def test_serving_engine_refuses(more):
    from bpe_transformer_tpu.serving.server import ServingEngine

    c = reference_cfg(2, 2)
    args = dict(paged=True, prefix_cache=False, block_size=2, prefill_chunk=4)
    args.update(more)
    with pytest.raises(ValueError, match="window"):
        ServingEngine(ref.weights_from_seed(3, c), program_cfg(c), **args)


def test_spec_engine_refuses():
    from bpe_transformer_tpu.serving.spec.draft import DraftSpec
    from bpe_transformer_tpu.serving.spec.engine import SpecEngine

    c = reference_cfg(2, 2)
    with pytest.raises(NotImplementedError, match="window pool groups"):
        SpecEngine(
            ref.weights_from_seed(3, c), program_cfg(c), draft=DraftSpec(),
            speculate_k=2, block_size=2, prefill_chunk=4, prefix_cache=False,
        )


def test_scan_layers_and_training_are_refused():
    with pytest.raises(ValueError, match="scan_layers"):
        program_cfg(reference_cfg(2, 2), scan_layers=True)
    from bpe_transformer_tpu.training.train_step import make_loss_fn

    with pytest.raises(ValueError, match="training is not supported"):
        make_loss_fn(program_cfg(reference_cfg(2, 2)))


def test_config_validation():
    base = reference_cfg(2, 2)
    with pytest.raises(ValueError, match="experts_held"):
        program_cfg({**base, "num_experts": 4, "expert_offset": 6})
    with pytest.raises(ValueError, match="norm_type"):
        dataclasses.replace(TS_TEST_CONFIG, norm_type="batchnorm")
    with pytest.raises(ValueError, match="parallel block only"):
        dataclasses.replace(TS_TEST_CONFIG, norm_type="layernorm")
    cfg = program_cfg(base)
    assert cfg.d_head == 16 and cfg.local_experts == 2
    assert [cfg.layer_window(layer) for layer in range(4)] == [6, 6, 6, None]
    assert [cfg.layer_rope(layer) for layer in range(4)] == [True, True, True, False]
    assert not TS_TEST_CONFIG.dropless_block and TS_TEST_CONFIG.layer_window(0) is None


# ------------------------------- every MoE configuration is served dropless


@pytest.mark.parametrize(
    "moe",
    [
        dict(n_experts=4, router_top_k=1, capacity_factor=1.25),
        dict(n_experts=4, router_top_k=2, capacity_factor=1.25),
        # The training forward's capacity (ceil(8 * 16 / 64) = 2) is below
        # the batch: the served layer has none to run out of.
        dict(n_experts=64, router_top_k=1, capacity_factor=1.0, context_length=16),
    ],
    ids=["top1_default_capacity", "top2_default_capacity", "capacity_below_batch"],
)
def test_softmax_moe_is_served_dropless(moe):
    """The capacity-dropping softmax MoE of training is served by the
    dropless layer: prefill + decode steps equal the drop-free full forward
    whatever ``capacity_factor`` says (was: a capacity derived from
    ``context_length`` in decode._ffn_decode)."""
    cfg = dataclasses.replace(TS_TEST_CONFIG, vocab_size=64, ffn_type="moe", **moe)
    nodrop = dataclasses.replace(cfg, capacity_factor=100.0)
    params = init_params(jax.random.PRNGKey(3), cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 64, (8, 10)), jnp.int32)
    full = forward(params, ids, nodrop)
    logits, cache = prefill(params, ids[:, :4], cfg, init_kv_cache(cfg, 8))
    worst = float(jnp.max(jnp.abs(logits - full[:, 3])))
    for t in range(4, 10):
        logits, cache = decode_step(params, ids[:, t], jnp.asarray(t), cache, cfg)
        worst = max(worst, float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert worst < 1e-5
