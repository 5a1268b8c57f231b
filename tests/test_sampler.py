"""The serving sampler's two filters against the sorting form they
replaced (ISSUE 34).

`filter_logits` finds the k-th largest logit and the nucleus cut-off by
threshold searches (`ops/sampling.py`).  The body it had until PR 34 — two
full-vocabulary sorts — lives on here as the oracle: the keep sets and the
masked values must equal it over every knob mix one batch can hold, and
`sample_tokens` must draw the same tokens from the same keys.

Two stated differences, both the contract's and none the oracle's merit:
``top_p >= 1`` is "off" exactly (a sorted float32 cumulative sum reaches
1.0 a few entries early and drops that tail at ``top_p == 1``), and the
nucleus mass is summed in another order, so an entry whose mass-before
lies within float32 rounding of ``top_p`` may fall on either side.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bpe_transformer_tpu.serving import engine
from bpe_transformer_tpu.serving.engine import (
    TOP_P_DISABLED,
    filter_logits,
    sample_tokens,
)


def sorted_filter(logits, temps, top_ks, top_ps):
    """`filter_logits` as it was: one sort for the k-th largest value, a
    second over the survivors for the cumulative mass."""
    vocab = logits.shape[-1]
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    k_idx = jnp.where(top_ks > 0, jnp.clip(top_ks, 1, vocab), vocab) - 1
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)
    sorted_m = jnp.sort(masked, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_m, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]  # mass BEFORE each token
    keep = keep.at[:, 0].set(True)  # the argmax always survives
    cutoff = jnp.min(jnp.where(keep, sorted_m, jnp.inf), axis=-1)
    return jnp.where(masked < cutoff[:, None], -jnp.inf, masked)


def sorted_sample(logits, keys, temps, top_ks, top_ps):
    """`sample_tokens` over the oracle's filter."""
    greedy = jnp.argmax(logits, axis=-1)
    masked = sorted_filter(logits, temps, top_ks, top_ps)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    return jnp.where(temps > 0.0, sampled, greedy)


def oracle_knobs(top_ps):
    """``top_p >= 1`` is off by contract (module docstring)."""
    return jnp.where(top_ps >= 1.0, TOP_P_DISABLED, top_ps)


def near_the_nucleus_edge(logits, temps, top_ks, top_ps, tol=2e-6):
    """Entries whose softmax mass strictly above them (over the top-k
    survivors, in float64) lies within ``tol`` of their row's ``top_p``:
    where float32 summation order may decide."""
    scaled = np.asarray(
        logits / jnp.maximum(temps, 1e-6)[:, None], np.float64
    )
    after_k = np.asarray(
        sorted_filter(logits, temps, top_ks, jnp.full_like(top_ps, 2.0))
    )
    kept = np.isfinite(after_k) | (after_k == np.inf)
    e = np.where(kept, np.exp(scaled - scaled.max(-1, keepdims=True)), 0.0)
    order = np.argsort(-scaled, axis=-1, kind="stable")
    e_sorted = np.take_along_axis(e, order, -1)
    s_sorted = np.take_along_axis(scaled, order, -1)
    before = np.cumsum(e_sorted, -1) - e_sorted
    # Ties share the mass above their first member.
    first = np.concatenate(
        [np.ones((len(scaled), 1), bool), s_sorted[:, 1:] != s_sorted[:, :-1]],
        axis=-1,
    )
    idx = np.maximum.accumulate(
        np.where(first, np.arange(scaled.shape[-1]), 0), axis=-1
    )
    above = np.take_along_axis(before, idx, -1) / e.sum(-1, keepdims=True)
    near_sorted = np.abs(above - np.asarray(top_ps, np.float64)[:, None]) < tol
    near = np.zeros_like(near_sorted)
    np.put_along_axis(near, order, near_sorted, -1)
    return near


K_CASES = (0, 1, 50, "vocab", "beyond")
P_CASES = (0.0, 0.5, 0.9, 1.0, 2.0)
T_CASES = (0.0, 0.7, 1.0, 1.3)


def knob_batch(vocab, dtype, seed):
    """One batch holding every (k, p) pair at rotating temperatures,
    greedy rows among them, a row of heavy ties and two rows that already
    hold ``-inf``."""
    rng = np.random.default_rng(seed)
    ks = [
        {"vocab": vocab, "beyond": vocab + 7}.get(k, k) for k in K_CASES
    ]
    rows = [(k, p) for k in ks for p in P_CASES]
    temps = [T_CASES[i % len(T_CASES)] for i in range(len(rows))]
    # The tie row (few distinct values) and the -inf rows, at k=50/p=0.9
    # and k=0/p=0.5.
    rows += [(50, 0.9), (50, 0.9), (0, 0.5)]
    temps += [1.0, 0.7, 1.0]
    logits = rng.normal(0.0, 2.5, (len(rows), vocab)).astype(np.float32)
    logits[-3] = np.round(logits[-3])
    logits[-2, rng.random(vocab) < 0.5] = -np.inf
    logits[-1, rng.random(vocab) < 0.999] = -np.inf
    return (
        jnp.asarray(logits).astype(dtype),
        jnp.asarray(temps, jnp.float32),
        jnp.asarray([k for k, _ in rows], jnp.int32),
        jnp.asarray([p for _, p in rows], jnp.float32),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab", [10_000, 32_000])
def test_filter_and_tokens_equal_the_sorted_oracle(vocab, dtype):
    logits, temps, top_ks, top_ps = knob_batch(vocab, dtype, seed=vocab)
    got = np.asarray(jax.jit(filter_logits)(logits, temps, top_ks, top_ps))
    want = np.asarray(
        jax.jit(sorted_filter)(logits, temps, top_ks, oracle_knobs(top_ps))
    )
    assert got.dtype == want.dtype
    differ = got != want
    near = near_the_nucleus_edge(logits, temps, top_ks, top_ps)
    assert not (differ & ~near).any(), np.argwhere(differ & ~near)[:8]
    assert differ.sum() <= 2  # and rare at that
    # The row's maximum always survives; a kept value is the scaled logit.
    scaled = np.asarray(logits / jnp.maximum(temps, 1e-6)[:, None])
    assert (got.max(-1) == scaled.max(-1)).all()
    assert ((got == scaled) | (got == -np.inf)).all()

    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(len(temps)) + 11)
    tokens = np.asarray(
        jax.jit(sample_tokens)(logits, keys, temps, top_ks, top_ps)
    )
    oracle = np.asarray(
        jax.jit(sorted_sample)(logits, keys, temps, top_ks, oracle_knobs(top_ps))
    )
    rows_equal = ~differ.any(-1)
    np.testing.assert_array_equal(tokens[rows_equal], oracle[rows_equal])
    greedy = np.asarray(temps) == 0
    np.testing.assert_array_equal(
        tokens[greedy], np.asarray(jnp.argmax(logits, -1))[greedy]
    )


def _kept(row):
    return set(np.flatnonzero(np.asarray(row) > -np.inf).tolist())


@pytest.mark.parametrize(
    "values,top_k,top_p,kept",
    [
        # The tie at the k-th value is kept whole.
        ([5.0, 4.0, 4.0, 4.0, 1.0, 0.0], 2, 2.0, {0, 1, 2, 3}),
        ([5.0, 4.0, 4.0, 4.0, 1.0, 0.0], 4, 2.0, {0, 1, 2, 3}),
        ([5.0, 4.0, 4.0, 4.0, 1.0, 0.0], 5, 2.0, {0, 1, 2, 3, 4}),
        # Signed zeros are one value.
        ([1.0, 0.0, -0.0, -1.0], 2, 2.0, {0, 1, 2}),
        ([1.0, -0.0, 0.0, -1.0], 2, 2.0, {0, 1, 2}),
        # Nucleus: probabilities 0.4, 0.2, 0.2, 0.2 - the mass above the
        # tie is 0.4, so 0.5 keeps all of it and 0.4 none of it.
        (np.log([0.4, 0.2, 0.2, 0.2]), 0, 0.5, {0, 1, 2, 3}),
        (np.log([0.4, 0.2, 0.2, 0.2]), 0, 0.4, {0}),
        (np.log([0.4, 0.2, 0.2, 0.2]), 0, 0.0, {0}),
        # A tie for the maximum survives top_p 0 and top_k 1 whole.
        ([3.0, 3.0, 1.0], 1, 0.0, {0, 1}),
        # top-k first, nucleus over its survivors: 0.5 / 0.25 / 0.25
        # renormalised over the top two is 2/3, 1/3.
        (np.log([0.5, 0.25, 0.125, 0.125]), 2, 0.6, {0}),
        (np.log([0.5, 0.25, 0.125, 0.125]), 2, 0.7, {0, 1}),
        # Entries that are already -inf stay dropped and count for nothing.
        ([2.0, -np.inf, 1.0, -np.inf], 3, 2.0, {0, 2}),
        ([2.0, -np.inf, 1.0, -np.inf], 0, 2.0, {0, 2}),
    ],
)
def test_ties_zeros_and_dropped_entries(values, top_k, top_p, kept):
    logits = jnp.asarray([values], jnp.float32)
    knobs = (
        jnp.ones(1), jnp.asarray([top_k], jnp.int32),
        jnp.asarray([top_p], jnp.float32),
    )
    got = filter_logits(logits, *knobs)
    assert _kept(got[0]) == kept
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(sorted_filter(logits, *knobs))
    )


@pytest.mark.parametrize(
    "mix", ["all_greedy", "top_p_off", "top_k_off", "both_off"]
)
def test_a_filter_no_row_asks_for_changes_nothing(monkeypatch, mix):
    """Each search stands under a `lax.cond` on its knob; taken or not, a
    row whose filter is off keeps everything, so the result is one."""
    rows, vocab = 6, 1_000
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(0, 2.5, (rows, vocab)), jnp.float32)
    temps = jnp.asarray([1.0, 0.7, 1.3, 1.0, 0.5, 1.0])
    top_ks = jnp.asarray([50, 1, 0, 7, 999, 2000], jnp.int32)
    top_ps = jnp.asarray([0.9, 2.0, 0.5, 1.0, 0.0, 0.3], jnp.float32)
    if mix == "all_greedy":
        temps = jnp.zeros(rows)
    if mix in ("top_p_off", "both_off"):
        top_ps = jnp.asarray([2.0, 1.0, 1.5, 1.0, 2.0, 2.0], jnp.float32)
    if mix in ("top_k_off", "both_off"):
        top_ks = jnp.asarray([0, -1, 0, 0, -5, 0], jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(rows))

    skipped = (
        np.asarray(filter_logits(logits, temps, top_ks, top_ps)),
        np.asarray(sample_tokens(logits, keys, temps, top_ks, top_ps)),
    )
    # How many searches a traced program may skip: every `cond` of the
    # engine module, counted and then forced to its search.
    conds = []

    def searched(pred, search, skip):
        conds.append(bool(pred))
        return search()

    monkeypatch.setattr(engine.lax, "cond", searched)
    forced = (
        np.asarray(filter_logits(logits, temps, top_ks, top_ps)),
        np.asarray(sample_tokens(logits, keys, temps, top_ks, top_ps)),
    )
    monkeypatch.undo()
    np.testing.assert_array_equal(skipped[0], forced[0])
    np.testing.assert_array_equal(skipped[1], forced[1])
    # filter_logits reads the knobs alone; sample_tokens hands a greedy
    # row's over as off.
    k_on, p_on = mix in ("all_greedy", "top_p_off"), mix in ("all_greedy", "top_k_off")
    assert conds[:2] == [k_on, p_on]
    if mix == "all_greedy":
        assert conds[2:] == [False, False]
        np.testing.assert_array_equal(
            skipped[1], np.asarray(jnp.argmax(logits, -1))
        )
    want = sorted_filter(logits, temps, top_ks, oracle_knobs(top_ps))
    np.testing.assert_array_equal(skipped[0], np.asarray(want))


def test_a_rows_result_does_not_depend_on_its_neighbours():
    """`serving/spec/` takes ``softmax(filter_logits(...))`` row by row:
    the same row gives the same values alone, beside rows with both
    filters on, and beside greedy rows."""
    rng = np.random.default_rng(9)
    row = jnp.asarray(rng.normal(0, 2.5, (1, 2_000)), jnp.float32)
    others = jnp.asarray(rng.normal(0, 2.5, (3, 2_000)), jnp.float32)
    alone = filter_logits(
        row, jnp.ones(1), jnp.zeros(1, jnp.int32), jnp.full(1, 2.0)
    )
    beside = filter_logits(
        jnp.concatenate([row, others]),
        jnp.asarray([1.0, 1.0, 0.0, 0.7]),
        jnp.asarray([0, 40, 3, 0], jnp.int32),
        jnp.asarray([2.0, 0.9, 0.2, 0.5]),
    )
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(beside[0]))
    np.testing.assert_array_equal(np.asarray(alone[0]), np.asarray(row[0]))


# ------------------------------------------------ how often each search ran


def _tiny_model():
    from bpe_transformer_tpu.models.config import ModelConfig
    from bpe_transformer_tpu.models.transformer import init_params

    config = ModelConfig(
        vocab_size=512, context_length=32, d_model=32, num_layers=2,
        num_heads=2, d_ff=64, rope_theta=10000.0,
    )
    return config, init_params(jax.random.PRNGKey(0), config)


@pytest.mark.parametrize("kind", ["dense", "paged", "spec"])
def test_stats_count_the_ticks_each_filter_was_asked_for(kind):
    """`sample_topk_ticks` / `sample_topp_ticks`: ticks in which a live
    sampled slot asked for that filter, counted on the host beside
    `ticks`; a greedy slot asks for none whatever its knobs, and neither
    do the knobs a finished request leaves in its slot."""
    from bpe_transformer_tpu.serving import ServingEngine

    config, params = _tiny_model()
    options = {} if kind == "dense" else dict(paged=True, block_size=8)
    if kind == "spec":
        from bpe_transformer_tpu.serving.spec.draft import DraftSpec

        options.update(speculate_k=2, draft_spec=DraftSpec(truncate_layers=1))
    serving = ServingEngine(params, config, slots=2, min_bucket=8, **options)
    eng = serving.engine

    def run(**knobs):
        before = serving.stats()
        eng.admit([1, 2, 3], max_new_tokens=6, seed=1, **knobs)
        while eng.active_count:
            eng.tick()
        after = serving.stats()
        return tuple(
            after[k] - before[k]
            for k in ("ticks", "sample_topk_ticks", "sample_topp_ticks")
        )

    ticks, top_k, top_p = run(temperature=1.0, top_k=5)
    assert ticks > 0 and (top_k, top_p) == (ticks, 0)
    ticks, top_k, top_p = run(temperature=0.8, top_p=0.9)
    assert ticks > 0 and (top_k, top_p) == (0, ticks)
    ticks, top_k, top_p = run(temperature=1.0, top_k=5, top_p=0.5)
    assert (top_k, top_p) == (ticks, ticks)
    # A greedy request never reads its filtered row; top_p 1 is off.
    assert run(temperature=0.0, top_k=5, top_p=0.5)[1:] == (0, 0)
    assert run(temperature=1.0, top_p=1.0)[1:] == (0, 0)
    assert run(temperature=1.0)[1:] == (0, 0)
    # The sampled requests' knobs are still in the vacated slots' rows.
    assert not eng._active.any() and (eng._temps > 0).any()


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_a_vacant_slot_asks_the_tick_for_no_search(monkeypatch, kind):
    """The tick program hands a vacant slot to the sampler as a greedy
    row, so the knobs a request left in its slot start no search: slot 0
    is live and greedy, slots 1 and 2 are vacant with both filters on."""
    from bpe_transformer_tpu.models.decode import init_kv_cache

    fn, args = _tiny_programs()["tick"]
    args = list(args)
    if kind == "dense":
        config, params = _tiny_model()
        fn = functools.partial(engine._tick_program, config=config)
        args = [params, args[1], init_kv_cache(config, 3), *args[5:]]
    active, temps = len(args) - 5, len(args) - 3
    args[active] = np.array([True, False, False])
    args[temps] = np.array([0.0, 1.0, 0.7], np.float32)
    asked = []
    real = engine.filter_logits

    def spy(logits, temps, top_ks, top_ps):
        asked.append((np.asarray(top_ks), np.asarray(top_ps)))
        return real(logits, temps, top_ks, top_ps)

    monkeypatch.setattr(engine, "filter_logits", spy)
    fn(*args)  # not jitted: the spy sees the values
    ((top_ks, top_ps),) = asked
    assert not (top_ks > 0).any() and (top_ps >= 1).all()
    args[active] = np.ones(3, bool)
    fn(*args)
    np.testing.assert_array_equal(asked[1][0] > 0, [False, True, True])
    np.testing.assert_array_equal(asked[1][1] < 1, [False, True, True])


# ------------------------------------------------- no sort in a program


def _tiny_programs():
    """The paged engine's tick and one chunk program at a tiny size, with
    live arguments, as `PagedEngine` jits them."""
    from bpe_transformer_tpu.models.decode import init_kv_pool
    from bpe_transformer_tpu.models.transformer import lm_head_weight
    from bpe_transformer_tpu.serving.kvpool import paged_engine as pe

    config, params = _tiny_model()
    head = lm_head_weight(params, config)
    slots, bs = 3, 8
    nbs = config.context_length // bs
    pool = init_kv_pool(config, slots * nbs + 1, bs)
    tables = np.arange(1, slots * nbs + 1, dtype=np.int32).reshape(slots, nbs)
    knobs = (
        np.ones(slots, np.float32), np.full(slots, 50, np.int32),
        np.full(slots, 0.9, np.float32),
    )
    tick = functools.partial(pe._tick_program, config=config, block_size=bs)
    chunk = functools.partial(pe._chunk_program, config=config, block_size=bs)
    return {
        "tick": (tick, (
            params, head, pool, None, tables, np.zeros(slots, np.int32),
            np.full(slots, 12, np.int32), np.ones(slots, bool),
            np.zeros((slots, 2), np.uint32), *knobs,
        )),
        "chunk": (chunk, (
            params, head, pool, None, tables[0], np.zeros((1, 16), np.int32),
            np.int32(0), np.int32(9), np.zeros(2, np.uint32),
            np.float32(1.0), np.int32(50), np.float32(0.9),
            # The decode carry, the chunk's slot, and that it is final.
            (np.zeros(slots, np.int32), np.zeros(slots, np.int32),
             np.zeros((slots, 2), np.uint32)),
            np.int32(0), np.bool_(True),
        )),
    }


@pytest.mark.parametrize("name", ["tick", "chunk"])
def test_serving_programs_hold_no_sort(name):
    """A `jnp.sort` back in `sample_tokens` fails here and not in a ledger
    line: the compiled tick and chunk of a dense configuration hold no
    sort at all (the v5e compile at the small cell's shape:
    tests/test_chip_compile.py)."""
    import re

    fn, args = _tiny_programs()[name]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "sample/top_k" in text and "sample/top_p" in text
    assert re.findall(r"\bsort\(", text) == []
    # What the assertion would catch: the oracle's program holds two.
    oracle = jax.jit(sorted_filter).lower(
        jnp.zeros((3, 512)), jnp.ones(3), jnp.ones(3, jnp.int32), jnp.ones(3)
    )
    assert len(re.findall(r"\bsort\(", oracle.compile().as_text())) >= 2
