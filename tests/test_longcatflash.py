"""The LongCat-Flash block (`LongCat-Flash-Omni`'s language model) at a
small size on the CPU: the plain forward, the dense latent cache and the
paged engine over a latent pool (chunks expanded, ticks absorbed, a resume
after a radix-shared prefix) against ``chipbench/reference_longcatflash.py``
on seeded float32 weights; the share test that ties a chip's experts and the
zero experts to the whole layer; the counts; and every refusal of what
cannot run yet."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bpe_transformer_tpu.kernels.pallas import mla_attention  # noqa: E402
from bpe_transformer_tpu.models.config import TS_TEST_CONFIG, ModelConfig  # noqa: E402
from bpe_transformer_tpu.models.decode import (  # noqa: E402
    LatentRows,
    cache_kind,
    decode_step,
    init_kv_cache,
    paged_forward,
    prefill,
    slot_cache,
)
from bpe_transformer_tpu.models.moe import dropless_moe, route  # noqa: E402
from bpe_transformer_tpu.models.transformer import forward, init_params  # noqa: E402
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine  # noqa: E402
from chipbench import reference_longcatflash as ref  # noqa: E402

REAL, ZERO, TOP = 16, 8, 4


def reference_cfg(held=REAL, offset=0, layers=2) -> dict:
    """Hidden 64, 4 heads of 8 + 4 / 8, lora ranks 16 / 8, dense 128, 16
    real experts of width 32 and 8 zero experts, 4 a token."""
    return {
        "hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
        "num_layers": layers, "num_attention_heads": 4, "q_lora_rank": 16,
        "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
        "v_head_dim": 8, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "n_routed_experts": held, "n_experts": REAL,
        "expert_offset": offset, "zero_expert_num": ZERO, "moe_topk": TOP,
        "rms_norm_eps": 1e-5, "rope_theta": 10000000, "vocab_size": 64,
        "context_length": 64,
    }


def program_cfg(c: dict, **more) -> ModelConfig:
    return ModelConfig(
        vocab_size=c["vocab_size"], context_length=c["context_length"],
        d_model=c["hidden_size"], num_layers=c["num_layers"],
        num_heads=c["num_attention_heads"], d_ff=c["ffn_hidden_size"],
        rope_theta=c["rope_theta"], attention_kind="mla",
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        mla_scale_q_lora=True, mla_scale_kv_lora=True,
        ffn_type="moe", expert_d_ff=c["expert_ffn_hidden_size"],
        n_experts=c["n_experts"], n_zero_experts=c["zero_expert_num"],
        router_top_k=c["moe_topk"], norm_topk_prob=False,
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        router_bias=True, experts_held=c["n_routed_experts"],
        expert_offset=c["expert_offset"], **more,
    )


def small_engine(c: dict, seed=3, **more) -> PagedEngine:
    args = dict(slots=3, block_size=4, prefill_chunk=8, prefill_buckets=(4, 8))
    args.update(more)
    return PagedEngine(ref.weights_from_seed(seed, c), program_cfg(c), **args)


SHARES = {"held_all": (REAL, 0), "held_share": (4, 4)}


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_reference(share):
    c = reference_cfg(*SHARES[share])
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 20))
    ours = forward(w, jnp.asarray(tokens), program_cfg(c))
    theirs = ref.forward_logits(w, tokens, c)
    assert float(jnp.max(jnp.abs(ours - theirs))) < 1e-5


def test_init_params_has_the_reference_tree():
    c = reference_cfg(4, 4)
    ours = init_params(jax.random.PRNGKey(0), program_cfg(c))
    theirs = ref.weights_from_seed(3, c)
    shapes = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)  # noqa: E731
    assert shapes(ours) == shapes(theirs)


def test_dense_cache_matches_reference():
    """Prefill (many rows) then decode_step token by token (one row)."""
    c = reference_cfg(4, 4)
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(1).integers(0, 64, (2, 24))
    full = ref.forward_logits(w, tokens, c)
    logits, cache = prefill(w, jnp.asarray(tokens[:, :9]), pc, init_kv_cache(pc, 2))
    worst = float(jnp.max(jnp.abs(logits - full[:, 8])))
    for t in range(9, 24):
        logits, cache = decode_step(w, jnp.asarray(tokens[:, t]), jnp.asarray(t), cache, pc)
        worst = max(worst, float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert worst < 1e-5


# ------------------------------------------------- the paged engine's paths


def served_logit_error(eng, c, tokens, plen, seed=3):
    """Prefill ``tokens[:plen]`` in the engine's chunks, then teacher-forced
    ticks to the end: the widest difference of a tick's logits from the
    reference's full forward, and the slot."""
    pc = eng.config
    full = ref.forward_logits(ref.weights_from_seed(seed, c), tokens[None], c)[0]
    slot = eng.begin(tokens[:plen], max_new_tokens=len(tokens) - plen, temperature=0.0)
    while eng.prefill_step(slot) is None:
        pass
    worst = 0.0
    active = np.zeros(eng.n_slots, bool)
    active[slot] = True
    for t in range(plen, len(tokens)):
        tok = np.zeros(eng.n_slots, np.int32)
        pos = np.zeros(eng.n_slots, np.int32)
        tok[slot], pos[slot] = tokens[t], t
        cache = slot_cache(
            pc, eng.cache.table_rows(), jnp.asarray(pos), jnp.asarray(active),
            block_size=eng.block_size,
        )
        logits, eng._pool, _ = paged_forward(
            eng._params, jnp.asarray(tok)[:, None], eng._pool, cache, pc,
            eng._lm_head, row=0,
        )
        worst = max(worst, float(jnp.max(jnp.abs(logits[slot] - full[t]))))
    return worst, slot


@pytest.mark.parametrize("tick_path", ["xla", "mla_paged"])
def test_paged_chunks_and_ticks_match_reference(tick_path, monkeypatch):
    """A prompt of 11 in chunks of two bucket sizes (8, then 3 in the bucket
    of 4), then 19 ticks (gathered rows under XLA, or the kernel in
    interpret mode), all absorbed, against the reference's expanded form."""
    monkeypatch.setattr(mla_attention, "mla_paged_path", lambda *a, **k: tick_path)
    c = reference_cfg(4, 4)
    eng = small_engine(c)
    assert cache_kind(eng.config) is LatentRows
    assert eng.tick_attention_path == tick_path
    tokens = np.random.default_rng(2).integers(0, 64, 30)
    worst, _ = served_logit_error(eng, c, tokens, 11)
    assert worst < 1e-5
    # Latent rows of 12 values, padded to a whole lane tile.
    assert len(eng._pool) == 2 * 2 and eng._pool[0]["c"].shape[1:] == (4, 128)


def test_resume_after_a_radix_shared_prefix_equals_the_request_served_cold():
    """The second request shares the first's two whole prompt blocks: its
    chunk resumes at position 8 over latent rows the first one wrote."""
    c = reference_cfg(4, 4)
    eng = small_engine(c, prefix_cache=True)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 64, 9)
    first = np.concatenate([shared, rng.integers(0, 64, 12)])
    second = np.concatenate([shared, rng.integers(0, 64, 15)])
    worst, slot = served_logit_error(eng, c, first, 12)
    assert worst < 1e-5 and eng.slot_shared_len(slot) == 0
    worst, slot = served_logit_error(eng, c, second, 14)
    assert eng.slot_shared_len(slot) == 8
    assert worst < 1e-5
    gauges = eng.gauges()
    assert gauges["prefix_cache_hits"] == 8 and gauges["prefix_cache_misses"] == 12 + 6


def test_engine_serves_greedy_tokens_the_reference_puts_first():
    """Three slots at ragged depths through admit/tick, the way the worker
    drives the engine; counters move and every block comes back."""
    c = reference_cfg(4, 4)
    eng = small_engine(c, prefix_cache=False)
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
    assert gauges["kv_blocks_free"] == gauges["kv_blocks_total"]
    routed = gauges["moe_tokens_routed"]
    assert routed == 2 * (13 + 5 + 9 + 3 * 11)  # two layers
    assert 0 < gauges["moe_rows_local"] < TOP * routed
    assert 0 < gauges["moe_zero_assignments"] < TOP * routed
    assert 0 < gauges["moe_expert_groups"] <= gauges["moe_rows_local"]
    assert eng.last_tick_moe_zero_assignments >= 0
    # A pair is one (query, key) of one sublayer: ticks only, 4 sublayers.
    ticks = sum(sum(range(n + 1, n + 12)) for n in (13, 5, 9))
    assert gauges["attn_pairs"] == gauges["attn_kv_positions"] == 4 * ticks
    assert gauges["kv_bytes_per_token"] == 4 * 12 * 4
    assert gauges["kv_pool_bytes"] == 4 * eng.allocator.num_blocks * 4 * 128 * 4


def test_no_program_compiles_after_the_warm_up():
    """One request a bucket is the cell's warm-up; chunks after chunks, a
    chunk that resumes after a shared prefix and ticks after either then
    run the programs that are there."""
    eng = small_engine(reference_cfg(4, 4), prefix_cache=True)
    rng = np.random.default_rng(0)

    def begin(prompt):
        return eng.begin(prompt, max_new_tokens=6, temperature=0.0)

    for n in (3, 7):
        slot = begin(rng.integers(0, 64, n))
        while eng.prefill_step(slot) is None:
            pass
        eng.tick(), eng.tick(), eng.release(slot)
    warm = eng.compiled_programs()
    assert warm == len(eng.buckets) + 1
    shared = rng.integers(0, 64, 8)
    slots = []
    for n in (5, 2):  # the second finds the first's two blocks in the cache
        slots.append(begin(np.concatenate([shared, rng.integers(0, 64, n)])))
        while eng.prefill_step(slots[-1]) is None:
            pass
    eng.tick(), eng.tick()
    assert eng.slot_shared_len(slots[1]) == 8
    assert eng.compiled_programs() == warm


def test_the_kernel_reads_what_the_gathered_rows_read():
    """`mla_paged_attention` in interpret mode against its XLA stand-in:
    ragged key counts, an idle slot, tables in any order."""
    rng = np.random.default_rng(7)
    slots, heads, width, rank, block, blocks = 5, 4, 12, 8, 4, 6
    pool = jnp.asarray(rng.normal(size=(40, block, width)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(slots, heads, width)), jnp.float32)
    tables = jnp.asarray(rng.permutation(39)[: slots * blocks].reshape(slots, blocks) + 1)
    counts = jnp.asarray([1, 24, 0, 9, 17], jnp.int32)
    want = mla_attention.mla_paged_attention(
        q, pool, tables, counts, rank=rank, scale=0.3, path="xla"
    )
    got = mla_attention.mla_paged_attention(
        q, pool, tables, counts, rank=rank, scale=0.3, path="mla_paged"
    )
    live = np.asarray(counts) > 0
    assert float(jnp.max(jnp.abs(got - want)[live])) < 1e-5
    assert float(jnp.max(jnp.abs(got[~live]))) == 0.0


# ------------------------------------- the chain of blocks the slots share

#: One block pool for every case: 6 slots of up to 8 blocks of 4 rows.
CHAIN = [1, 2, 3, 4, 5]  # the blocks a shared prefix lies in


def _rows(*own, shared=5, chain=CHAIN):
    """A table row: ``shared`` blocks of the chain, then its own."""
    row = list(chain[:shared]) + list(own)
    return row + [0] * (8 - len(row))


SHARED_CASES = {
    # name: (rows of the table, key counts, the rule's shared blocks)
    "every_slot_shares": (
        [_rows(10, 11), _rows(12), _rows(13, 14, 15), _rows(16), _rows(17), _rows(18)],
        [26, 21, 32, 22, 23, 24], [5, 5, 5, 5, 5, 5],
    ),
    "none_shares": (
        [_rows(10 + 7 * s, 11 + 7 * s, 12 + 7 * s, shared=0) for s in range(6)],
        [9, 12, 5, 1, 10, 11], [0] * 6,
    ),
    # Slots 1 and 4 prefilled the same tokens before the cache held them
    # (`private_copies` writes their blocks): other ids, no run.
    "private_copies": (
        [_rows(10), _rows(11, chain=[20, 21, 22, 23, 24]), _rows(12, 13),
         _rows(14), _rows(15, chain=[30, 31, 32, 33, 34]), _rows(16)],
        [22, 23, 27, 21, 24, 22], [5, 0, 5, 5, 0, 5],
    ),
    # Groups are 2 blocks here: chains of 5 and 3 blocks end inside one,
    # and a fork of the radix tree leaves slot 3 on the first 3 alone.
    "a_chain_that_ends_inside_a_group": (
        [_rows(10), _rows(11, 12), _rows(13), _rows(14, 15, 16, shared=3),
         _rows(17), _rows(18)],
        [23, 26, 21, 22, 24, 22], [5, 5, 5, 3, 5, 5],
    ),
    "a_member_with_no_keys_of_its_own": (
        [_rows(10), _rows(), _rows(11), _rows(), _rows(12), _rows(13)],
        [22, 20, 23, 20, 21, 24], [5, 5, 5, 5, 5, 5],
    ),
    # Slot 1 idles (an empty row), slot 3 is mid-prefill after a cache hit
    # (the chain is in its row, its count is 0).
    "idle_and_prefilling_slots_among_members": (
        [_rows(10), [0] * 8, _rows(11), _rows(12), _rows(13), _rows(14)],
        [22, 0, 23, 0, 21, 24], [5, 0, 5, 0, 5, 5],
    ),
    # Slot 2's row runs with the reference's for 5 blocks and its count
    # reaches into the third: the two whole blocks it sees are shared.
    "a_row_that_matches_past_its_count": (
        [_rows(10), _rows(11), _rows(), _rows(12), _rows(13), _rows(14)],
        [22, 23, 10, 21, 24, 22], [5, 5, 2, 5, 5, 5],
    ),
    # Two slots on a chain are fewer than `MLA_SHARED_MIN_SLOTS` (3 here).
    "too_few_members": (
        [_rows(10), _rows(11)] + [
            _rows(12 + 7 * s, 13 + 7 * s, 14 + 7 * s, shared=0) for s in range(4)
        ],
        [22, 23, 9, 12, 5, 10], [0] * 6,
    ),
    # The largest family decides the reference, not the first live slot.
    "the_first_slot_holds_a_private_copy": (
        [_rows(10, chain=[20, 21, 22, 23, 24]), _rows(11), _rows(12), _rows(13),
         _rows(14), _rows(15)],
        [22, 23, 21, 24, 22, 23], [0, 5, 5, 5, 5, 5],
    ),
}


@pytest.fixture
def small_groups(monkeypatch):
    """Groups of 2 blocks and tiles of 2 slots' heads, so 6 slots and 5
    shared blocks make several tiles and several groups; the pass is taken
    from 3 slots on a chain."""
    monkeypatch.setattr(mla_attention, "MLA_GROUP_KEYS", 8)
    monkeypatch.setattr(mla_attention, "MLA_SHARED_TILE_ROWS", 32)
    monkeypatch.setattr(mla_attention, "MLA_SHARED_MIN_SLOTS", 3)
    mla_attention._mla_paged_impl.clear_cache()
    yield
    mla_attention._mla_paged_impl.clear_cache()


@pytest.mark.parametrize("case", SHARED_CASES)
def test_the_shared_pass_and_the_own_pass_read_what_the_gathered_rows_read(
    case, small_groups
):
    """The two kernels (interpret mode) against `xla_mla_rows_attention`
    over the gathered rows, and the rule's two forms against each other."""
    rows, counts, want_shared = SHARED_CASES[case]
    rng = np.random.default_rng(11)
    heads, width, rank, block = 4, 12, 8, 4
    pool = rng.normal(size=(60, block, width)).astype(np.float32)
    for copy in ([20, 21, 22, 23, 24], [30, 31, 32, 33, 34]):
        pool[copy] = pool[CHAIN]  # private copies of the same tokens
    pool = jnp.asarray(pool)
    q = jnp.asarray(rng.normal(size=(len(rows), heads, width)), jnp.float32)
    tables, counts = np.asarray(rows, np.int32), np.asarray(counts, np.int32)

    shared, reference = mla_attention.shared_prefix(tables, counts, block, xp=np)
    assert shared.tolist() == want_shared
    traced, traced_reference = jax.jit(
        lambda t, c: mla_attention.shared_prefix(t, c, block)
    )(tables, counts)
    assert traced.tolist() == want_shared and int(traced_reference) == reference

    gathered = pool[tables].reshape(len(rows), -1, width)
    visible = jnp.arange(gathered.shape[1]) < counts[:, None]
    want = mla_attention.xla_mla_rows_attention(
        q, gathered, visible, rank=rank, scale=0.3
    )
    got = mla_attention.mla_paged_attention(
        q, pool, jnp.asarray(tables), jnp.asarray(counts), rank=rank,
        scale=0.3, path="mla_paged",
    )
    live = counts > 0
    assert float(jnp.max(jnp.abs(got - want)[live])) < 1e-5
    assert float(jnp.max(jnp.abs(got[~live]), initial=0.0)) == 0.0


@pytest.mark.parametrize("members, taken", [(5, False), (6, True)])
def test_the_shared_pass_is_taken_from_six_members(members, taken):
    """The constant as the package ships it (no monkeypatch): five slots on
    a chain run today's walk alone, six take the shared pass - in both of
    the rule's forms."""
    assert mla_attention.MLA_SHARED_MIN_SLOTS == 6
    rows = [_rows(10 + s) for s in range(members)] + [
        _rows(40 + 3 * s, 41 + 3 * s, shared=0) for s in range(8 - members)
    ]
    tables = np.asarray(rows, np.int32)
    counts = np.full(8, 22, np.int32)
    want = [5 * taken] * members + [0] * (8 - members)
    shared, _ = mla_attention.shared_prefix(tables, counts, 4, xp=np)
    assert shared.tolist() == want
    traced, _ = jax.jit(lambda t, c: mla_attention.shared_prefix(t, c, 4))(
        tables, counts
    )
    assert traced.tolist() == want


# ------------------------------------ a chunk's rows in the expanded form

#: Toy widths that keep `mla_chunk_path`'s lane rule: 2 heads of 128 + 64 /
#: 128 over a latent of 128.
CHUNK_HEADS, CHUNK_NOPE, CHUNK_ROPE, CHUNK_V, CHUNK_RANK = 2, 128, 64, 128, 128
#: name: (first position, key positions any query sees); the kernel's key
#: blocks are `MLA_CHUNK_KERNEL_KEYS` = 512 as shipped (the absorbed loop's
#: `MLA_CHUNK_KEY_BLOCK` = 1,024), the table holds 3,072 rows.
CHUNK_CASES = {
    "from_position_0": (0, None),
    "ends_inside_a_key_block": (None, 1500),
    "ends_at_a_key_block": (None, 2048),
    "ends_one_past_a_key_block": (None, 2049),
    "padded_rows_past_the_chunk": (1100, None),
}


def _expanded_reference(q_nope, q_rope, rows, kv_b, positions, scale):
    """Every head's keys and values from the rows, a masked softmax over
    all of them at once: float32, nothing blocked."""
    nope, rank = q_nope.shape[-1], kv_b.shape[-1]
    k_nope = jnp.einsum("kc,hdc->hkd", rows[:, :rank], kv_b[:, :nope])
    v = jnp.einsum("kc,hdc->hkd", rows[:, :rank], kv_b[:, nope:])
    scores = (
        jnp.einsum("hqd,hkd->hqk", q_nope, k_nope)
        + jnp.einsum("hqr,kr->hqk", q_rope, rows[:, rank:])
    ) * scale
    visible = jnp.arange(rows.shape[0])[None, :] <= positions[:, None]
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", probs, v)


@pytest.mark.parametrize("case", CHUNK_CASES)
@pytest.mark.parametrize("queries", [256, 512, 1024])
def test_the_chunk_kernel_attends_what_the_loop_and_the_expanded_form_attend(
    queries, case
):
    """`mla_chunk_attention` (interpret mode) against the absorbed loop and
    against a plain float32 expanded reference, on the chunk's own rows;
    a padded row past them comes out finite and is no one's."""
    assert mla_attention.MLA_CHUNK_KERNEL_KEYS == 512
    assert mla_attention.MLA_CHUNK_KEY_BLOCK == 1024
    start, n_keys = CHUNK_CASES[case]
    padded = 57 if case == "padded_rows_past_the_chunk" else 0
    chunk_len = queries - padded
    start = n_keys - chunk_len if start is None else start
    n_keys = start + chunk_len
    rng = np.random.default_rng(queries + len(case))

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q_nope = draw(CHUNK_HEADS, queries, CHUNK_NOPE)
    q_rope = draw(CHUNK_HEADS, queries, CHUNK_ROPE)
    rows = draw(3072, CHUNK_RANK + CHUNK_ROPE)
    kv_b = draw(CHUNK_HEADS, CHUNK_NOPE + CHUNK_V, CHUNK_RANK) * 0.1
    positions = start + jnp.arange(queries)
    args = (q_nope, q_rope, rows, kv_b, positions, n_keys)
    got = mla_attention.mla_chunk_attention(*args, scale=0.07, interpret=True)
    loop = mla_attention.xla_mla_chunk_attention(*args, scale=0.07)
    plain = _expanded_reference(q_nope, q_rope, rows, kv_b, positions, 0.07)
    assert got.shape == (CHUNK_HEADS, queries, CHUNK_V)
    assert float(jnp.max(jnp.abs(got - loop)[:, :chunk_len])) < 2e-5
    assert float(jnp.max(jnp.abs(got - plain)[:, :chunk_len])) < 2e-5
    assert bool(jnp.isfinite(got).all())
    # Rows as a pool pads them (zeros to whole lane tiles) read the same.
    wide = jnp.pad(rows, ((0, 0), (0, 64)))
    again = mla_attention.mla_chunk_attention(
        q_nope, q_rope, wide, kv_b, positions, n_keys, scale=0.07, interpret=True
    )
    assert bool(jnp.array_equal(again, got))


@pytest.mark.parametrize(
    "backend, queries, widths, want",
    [
        ("tpu", 1024, (128, 128, 512), "mla_chunk"),
        ("tpu", 256, (128, 128, 512), "mla_chunk"),
        ("tpu", 2048, (128, 128, 512), "mla_chunk"),
        ("cpu", 1024, (128, 128, 512), "xla"),
        ("gpu", 1024, (128, 128, 512), "xla"),
        ("tpu", 128, (128, 128, 512), "xla"),     # under the row threshold
        ("tpu", 1536, (128, 128, 512), "mla_chunk"),  # three tiles of 512
        ("tpu", 1280, (128, 128, 512), "xla"),    # no whole tiles of 512
        ("tpu", 1024, (8, 8, 8), "xla"),          # this file's test widths
        ("tpu", 1024, (128, 64, 512), "xla"),
        ("tpu", 1024, (128, 128, 192), "xla"),
    ],
)
def test_the_chunk_takes_the_kernel_on_the_tpu_from_256_aligned_rows(
    backend, queries, widths, want
):
    """The constants as the package ships them; the rule both ways."""
    assert mla_attention.MLA_CHUNK_MIN_ROWS == 256
    assert mla_attention.MLA_CHUNK_TILE_ROWS == 512
    assert mla_attention.mla_chunk_path(queries, *widths, backend=backend) == want
    if backend == "cpu":  # the backend here, asked of jax
        assert mla_attention.mla_chunk_path(queries, *widths) == want


#: A prompt of 11 is two chunks: 8 rows from position 0 in the bucket of 8,
#: then 3 rows after 8 in the bucket of 4; 2 double layers = 4 sublayers.
TWO_CHUNKS = 4 * (8 * 9 // 2), 4 * (3 * 8 + 3 * 4 // 2)
CHUNK_RULES = {
    "loop": (None, 0),
    "kernel": (lambda *a, **k: "mla_chunk", sum(TWO_CHUNKS)),
    "kernel_from_8_rows": (
        lambda queries, *a, **k: "mla_chunk" if queries >= 8 else "xla",
        TWO_CHUNKS[0],
    ),
}


@pytest.mark.parametrize("rule", CHUNK_RULES)
def test_a_latent_chunks_pairs_equal_a_count_by_hand(rule, monkeypatch):
    """`chunk_attn_pairs` / `chunk_attn_kernel_pairs` for a two-chunk
    prompt, the chunks served by the form the rule names (the kernel in
    interpret mode) against the reference; `attn_pairs` stays the ticks'."""
    path, kernel_pairs = CHUNK_RULES[rule]
    if path is not None:
        monkeypatch.setattr(mla_attention, "mla_chunk_path", path)
    c = reference_cfg(4, 4)
    eng = small_engine(c)
    tokens = np.random.default_rng(2).integers(0, 64, 14)
    worst, _ = served_logit_error(eng, c, tokens, 11)
    assert worst < 1e-5
    gauges = eng.gauges()
    assert gauges["chunk_attn_pairs"] == sum(TWO_CHUNKS)
    assert gauges["chunk_attn_kernel_pairs"] == kernel_pairs
    # `served_logit_error` forces its ticks past the engine: none counted.
    assert gauges["attn_pairs"] == 0 and eng.chunk_launches == 2


def test_no_chunk_pairs_off_the_latent_kind():
    config = dataclasses.replace(TS_TEST_CONFIG, vocab_size=64, context_length=32)
    eng = PagedEngine(
        init_params(jax.random.PRNGKey(0), config), config, slots=2,
        block_size=4, prefill_chunk=8, prefill_buckets=(4, 8),
    )
    eng.admit(np.arange(11) % 64, max_new_tokens=2, temperature=0.0)
    eng.tick()
    gauges = eng.gauges()
    assert not {"chunk_attn_pairs", "chunk_attn_kernel_pairs"} & set(gauges)
    assert gauges["attn_pairs"] > 0


def _serve_after_one_prefix(monkeypatch, rule):
    """Four greedy requests behind one 9-token prefix (two whole blocks)
    through the kernels in interpret mode, `shared_prefix` replaced by
    ``rule``: every request's tokens, the engine's gauges, and the shared
    blocks of every tick as the program itself computed them."""
    monkeypatch.setattr(mla_attention, "mla_paged_path", lambda *a, **k: "mla_paged")
    monkeypatch.setattr(mla_attention, "MLA_SHARED_MIN_SLOTS", 3)
    # The kernels' launcher is jitted on its own and reads the rule and the
    # constant when it is traced.
    mla_attention._mla_paged_impl.clear_cache()
    in_program = []

    def recording(tables, key_counts, block_size, xp=jnp):
        shared, reference = rule(tables, key_counts, block_size, xp=xp)
        if xp is jnp:
            jax.debug.callback(lambda v: in_program.append(np.asarray(v)), shared)
        return shared, reference

    monkeypatch.setattr(mla_attention, "shared_prefix", recording)
    c = reference_cfg(4, 4)
    eng = small_engine(c, slots=4, prefix_cache=True)
    assert eng.tick_attention_path == "mla_paged"
    rng = np.random.default_rng(9)
    prefix = rng.integers(0, 64, 9)
    prompts = [np.concatenate([prefix, rng.integers(0, 64, n)]) for n in (3, 6, 2, 5)]
    served = {}
    for prompt, new in zip(prompts, (7, 5, 9, 6)):
        event = eng.admit(prompt, max_new_tokens=new, temperature=0.0)
        served[event.slot] = [event.token]
    assert [eng.slot_shared_len(s) for s in sorted(served)] == [0, 8, 8, 8]
    while eng.active_count:
        for event in eng.tick():
            served[event.slot].append(event.token)
    jax.effects_barrier()
    return served, {**eng.gauges(), "ticks": eng.ticks}, in_program


def test_the_shared_pass_serves_the_tokens_of_the_walk_alone(monkeypatch):
    """The same requests with the rule as it is and with a rule that finds
    no chain (every slot walks its whole row, as before there was a shared
    pass) are served the same tokens; and what the host counted of the
    shared pass is what the program's own rule found, tick by tick."""
    rule = mla_attention.shared_prefix

    def no_chain(tables, key_counts, block_size, xp=jnp):
        shared, reference = rule(tables, key_counts, block_size, xp=xp)
        return shared * 0, reference

    try:
        served, gauges, in_program = _serve_after_one_prefix(monkeypatch, rule)
        alone, gauges_alone, none = _serve_after_one_prefix(monkeypatch, no_chain)
    finally:
        mla_attention._mla_paged_impl.clear_cache()
    assert served == alone and len(served) == 4
    # Every sublayer of a tick asks the rule (4 here).
    assert len(in_program) == 4 * gauges["ticks"] == 4 * gauges_alone["ticks"]
    assert len(none) == len(in_program)
    assert all(not shared.any() for shared in none)
    assert gauges_alone["attn_shared_kv_positions"] == 0
    assert gauges_alone["attn_shared_slots"] == 0
    # Blocks of 4: the prefix's two blocks in every live row while at least
    # `MLA_SHARED_MIN_SLOTS` (3 here) of the requests live.
    assert gauges["attn_shared_kv_positions"] == 4 * sum(
        int(shared.sum()) for shared in in_program
    )
    assert 4 * gauges["attn_shared_slots"] == sum(
        int((shared > 0).sum()) for shared in in_program
    )
    assert {int(v) for shared in in_program for v in shared} == {0, 2}
    assert 0 < gauges["attn_shared_kv_positions"] < gauges["attn_kv_positions"]
    assert gauges["attn_kv_positions"] == gauges_alone["attn_kv_positions"]


@pytest.mark.parametrize("pool", ["latent", "dense"])
def test_the_tick_record_carries_the_shared_pass_over_a_latent_pool_alone(pool):
    """A served request's `tick` records hold the shared pass's two counts
    over a latent pool (0 here: gathered rows on the CPU) and leave them
    out over any other."""
    from bpe_transformer_tpu.serving.server import Request, ServingEngine
    from bpe_transformer_tpu.telemetry.schema import validate_record
    from bpe_transformer_tpu.telemetry.spans import Telemetry

    if pool == "latent":
        c = reference_cfg(4, 4)
        weights, config = ref.weights_from_seed(3, c), program_cfg(c)
    else:
        config = dataclasses.replace(TS_TEST_CONFIG, vocab_size=64, context_length=32)
        weights = init_params(jax.random.PRNGKey(0), config)
    records = []
    with ServingEngine(
        weights, config, slots=2, min_bucket=8, paged=True, block_size=4,
        prefill_chunk=8, telemetry=Telemetry(sink=records.append),
    ) as serving:
        request = Request(prompt_ids=(5, 6, 7), max_new_tokens=4, temperature=0.0)
        serving.submit(request).result(timeout=300)
    ticks = [r for r in records if r.get("kind") == "tick"]
    assert ticks
    for record in ticks:
        validate_record(record)
        held = {"attn_shared_kv_positions", "attn_shared_slots"} & set(record)
        assert len(held) == (2 if pool == "latent" else 0)
        assert not any(record[key] for key in held)


# ------------------------------------------- the expert layer and its shares


def layer_weights(seed=7):
    return ref.weights_from_seed(seed, reference_cfg(layers=1))["layers"][0]["ffn"]


def test_all_shares_add_up_to_the_uncut_layer():
    """The share test: the real parts of all 4 shares of 4 experts, plus the
    zero experts' part once, equal the uncut reference layer."""
    uncut = reference_cfg(layers=1)
    w = layer_weights()
    h = jax.random.normal(jax.random.PRNGKey(1), (11, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(h, w, uncut, None)
    top_i, gates = route(h, w["router"], program_cfg(uncut), w["router_bias"])
    zero_part = jnp.sum(jnp.where(top_i >= REAL, gates, 0.0), axis=-1)[:, None] * h
    assert float(jnp.max(jnp.abs(zero_part))) > 0.01
    real = jnp.zeros_like(h)
    for offset in range(0, REAL, 4):
        share = {**w, **{k: w[k][offset:offset + 4] for k in ("w1", "w2", "w3")}}
        out, counts = dropless_moe(h, share, program_cfg(reference_cfg(4, offset, layers=1)))
        real = real + out - zero_part
        assert int(counts[0]) == 11 and int(counts[1]) + int(counts[3]) <= TOP * 11
    assert float(jnp.max(jnp.abs(real + zero_part - want))) < 1e-5


def test_a_token_on_zero_experts_alone_leaves_the_grouped_matmul_no_row():
    """A selection bias that puts every zero expert first: no assignment is
    held, no group computed, and the layer returns ``(sum g) h``."""
    cfg = program_cfg(reference_cfg(4, 4, layers=1))
    w = layer_weights()
    w = {**w, **{k: w[k][4:8] for k in ("w1", "w2", "w3")},
         "router_bias": jnp.where(jnp.arange(REAL + ZERO) >= REAL, 10.0, 0.0)}
    h = jax.random.normal(jax.random.PRNGKey(2), (9, 64), jnp.float32)
    out, counts = dropless_moe(h, w, cfg)
    assert [int(v) for v in counts] == [9, 0, 0, TOP * 9]
    probs = jax.nn.softmax(h @ w["router"].T, axis=-1)
    gate = 6.0 * jnp.sum(jax.lax.top_k(probs[:, REAL:], TOP)[0], axis=-1)
    assert float(jnp.max(jnp.abs(out - gate[:, None] * h))) < 1e-6


@pytest.mark.parametrize("rows_valid", [9, 4], ids=["all_rows", "valid_rows"])
def test_counts_equal_a_count_by_hand(rows_valid):
    cfg = program_cfg(reference_cfg(4, 4, layers=1))
    w = layer_weights()
    w = {**w, **{k: w[k][4:8] for k in ("w1", "w2", "w3")}}
    h = jax.random.normal(jax.random.PRNGKey(3), (9, 64), jnp.float32)
    valid = jnp.arange(9) < rows_valid
    out, counts = dropless_moe(h, w, cfg, valid=valid)
    top_i = np.asarray(route(h, w["router"], cfg, w["router_bias"])[0])[:rows_valid]
    held = (top_i >= 4) & (top_i < 8)
    assert [int(v) for v in counts] == [
        rows_valid, int(held.sum()), len(np.unique(top_i[held])),
        int((top_i >= REAL).sum()),
    ]
    whole, _ = dropless_moe(h, w, cfg)
    assert float(jnp.max(jnp.abs(out[:rows_valid] - whole[:rows_valid]))) < 1e-6
    assert not np.asarray(out[rows_valid:]).any()


# --------------------------------------- how the reference scores a served run


def test_routing_choices_follow_near_ties_of_what_is_computed_here():
    c = {**reference_cfg(2, 2), "n_experts": 5, "zero_expert_num": 3, "moe_topk": 2}
    # Outputs 0-4 real (2 and 3 held), 5-7 zero.
    logits = np.asarray([
        [3.0, 2.0, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0],   # decided
        [3.0, 2.0, 0.0, -1.0, 1.95, -1.0, -2.0, -3.0],  # 1 against 4: both absent
        [3.0, 0.0, 2.0, -1.0, 1.95, -1.0, -2.0, -3.0],  # 2 (held) against 4
        [3.0, 0.0, -1.0, -1.0, 2.0, 1.95, -2.0, -3.0],  # 4 (absent) against 5 (zero)
        [3.0, 0.0, -1.0, -1.0, -1.0, 2.0, 1.95, -3.0],  # 5 against 6: both zero
        [3.0, 0.0, -1.0, 1.95, -1.0, 2.0, -2.0, -3.0],  # 5 (zero) against 3 (held)
    ], np.float32)
    sets = [[sorted(s.tolist()) for _, s in row] for row in ref.routing_choices(logits, c)]
    assert sets == [
        [[0, 1]], [[0, 1]], [[0, 2], [0, 4]], [[0, 4], [0, 5]], [[0, 5]],
        [[0, 5], [0, 3]],
    ]


def test_rows_of_decided_positions_equal_the_full_forward(monkeypatch):
    """With no near tie to follow, every position is one row and equals the
    full forward - computed whole, or after a prefix computed before it."""
    monkeypatch.setattr(ref, "ROUTER_MARGIN", 0.0)
    c = reference_cfg(4, 4)
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(4).integers(0, 64, 24).astype(np.int32)
    full, latents = ref.hidden_states(w, tokens, c)
    _, before = ref.hidden_states(w, tokens[:8], c)
    resumed, again = ref.hidden_states(w, tokens[8:], c, before=before)
    assert float(jnp.max(jnp.abs(resumed - full[8:]))) < 1e-5
    assert float(jnp.max(jnp.abs(again[1][1][0] - latents[1][1][0]))) < 1e-5
    rows, origin = ref.followed_routings(w, c, tokens, latents, 5, 24)
    assert origin.tolist() == list(range(19))
    assert float(np.max(np.abs(rows - np.asarray(full[5:24])))) < 2e-5


def test_served_gaps_of_the_references_own_greedy_tokens(monkeypatch):
    """Two sequences that share their first 8 positions: the prefix goes
    through once; the reference's own tokens read no gap, others do."""
    monkeypatch.setattr(ref, "PREFIX_STEP", 4)
    c = reference_cfg(4, 4)
    w = ref.weights_from_seed(11, c, jnp.bfloat16)
    rng = np.random.default_rng(6)
    shared = rng.integers(0, 64, 9).tolist()
    sequences = []
    for extra in (2, 5):
        prompt = shared + rng.integers(0, 64, extra).tolist()
        ids = list(prompt)
        for _ in range(5):
            ids.append(int(jnp.argmax(ref.forward_logits(w, np.asarray([ids]), c)[0, -1])))
        sequences.append((prompt, ids[len(prompt):]))
    assert ref.shared_prefix(sequences) == 8
    assert max(ref.served_gaps(11, c, sequences)) < 1e-4
    wrong = [(p, [(t + 1) % 64 for t in served]) for p, served in sequences]
    assert min(ref.served_gaps(11, c, wrong)) > 1e-3
    assert all(g >= 0 for g in ref.served_gaps(11, c, sequences, control=True))


# ---------------------------------------------------------------- refusals


@pytest.mark.parametrize(
    "more",
    [dict(kv_dtype="int8"), dict(weight_dtype="int8"), dict(fused_sampling=True)],
    ids=["kv_int8", "weight_int8", "fused_sampling"],
)
def test_engine_refuses_at_construction(more):
    with pytest.raises(ValueError, match="latent pool|double layer"):
        small_engine(reference_cfg(4, 4), **more)


@pytest.mark.parametrize("what", ["extend_blocks", "export_slot", "import_slot"])
def test_engine_refuses_speculation_scratch_and_migration(what):
    eng = small_engine(reference_cfg(4, 4))
    call = {
        "extend_blocks": lambda: eng.extend_blocks(0, 8),
        "export_slot": lambda: eng.export_slot(0),
        "import_slot": lambda: eng.validate_import_meta({"format": 1}),
    }[what]
    with pytest.raises(NotImplementedError, match="latent pool"):
        call()


@pytest.mark.parametrize(
    "more",
    [dict(paged=False), dict(speculate_k=2), dict(role="prefill"), dict(role="decode")],
    ids=["dense_engine", "speculation", "prefill_role", "decode_role"],
)
def test_serving_engine_refuses(more):
    from bpe_transformer_tpu.serving.server import ServingEngine

    c = reference_cfg(4, 4)
    args = dict(paged=True, block_size=4, prefill_chunk=8)
    args.update(more)
    with pytest.raises(ValueError, match="latent"):
        ServingEngine(ref.weights_from_seed(3, c), program_cfg(c), **args)


def test_spec_and_slot_pool_engines_refuse():
    from bpe_transformer_tpu.serving.engine import SlotPoolEngine
    from bpe_transformer_tpu.serving.spec.draft import DraftSpec
    from bpe_transformer_tpu.serving.spec.engine import SpecEngine

    c = reference_cfg(4, 4)
    w = ref.weights_from_seed(3, c)
    with pytest.raises(NotImplementedError, match="latent pool"):
        SpecEngine(w, program_cfg(c), draft=DraftSpec(), speculate_k=2,
                   block_size=4, prefill_chunk=8)
    with pytest.raises(ValueError, match="latent attention"):
        SlotPoolEngine(w, program_cfg(c))
    with pytest.raises(NotImplementedError, match="several rows a slot"):
        slot_cache(program_cfg(c), jnp.zeros((3, 16), jnp.int32),
                   jnp.zeros((3, 2), jnp.int32), block_size=4)


def test_scan_layers_and_training_are_refused():
    with pytest.raises(ValueError, match="scan_layers"):
        program_cfg(reference_cfg(4, 4), scan_layers=True)
    from bpe_transformer_tpu.training.train_step import make_loss_fn

    with pytest.raises(ValueError, match="training is not supported"):
        make_loss_fn(program_cfg(reference_cfg(4, 4)))


@pytest.mark.parametrize(
    "change, message",
    [
        (dict(attention_kind="mha"), "are latent"),
        (dict(kv_lora_rank=0), "needs positive"),
        (dict(qk_rope_head_dim=3), "even"),
        (dict(num_kv_heads=2), "no K/V heads"),
        (dict(parallel_block=True), "contradict"),
        (dict(ffn_type=None, experts_held=None), "expert layer|ffn_type"),
    ],
    ids=["mla_alone", "no_rank", "odd_rope", "kv_heads", "parallel", "no_experts"],
)
def test_config_refuses_contradictions(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(program_cfg(reference_cfg(4, 4)), **change)


def test_config_properties_and_defaults():
    cfg = program_cfg(reference_cfg(4, 4))
    assert (cfg.d_head, cfg.rope_dim, cfg.latent_width) == (12, 4, 12)
    assert (cfg.q_lora_scale, cfg.kv_lora_scale) == (2.0, 8 ** 0.5)
    assert (cfg.attn_sublayers, cfg.moe_d_ff, cfg.router_outputs) == (2, 32, 24)
    assert cfg.dropless_block and cfg.local_experts == 4
    plain = TS_TEST_CONFIG
    assert not plain.latent_block and not plain.dropless_block
    assert (plain.attn_sublayers, plain.rope_dim, plain.moe_d_ff) == (1, plain.d_head, plain.d_ff)
    for field, message in [("n_zero_experts", "ffn_type"), ("q_lora_rank", "latent")]:
        with pytest.raises(ValueError, match=message):
            dataclasses.replace(plain, **{field: 4})
    with pytest.raises(ValueError, match="parallel block only"):
        dataclasses.replace(plain, ffn_type="moe", n_experts=4, norm_topk_prob=False)
