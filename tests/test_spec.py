"""Speculative decoding (`serving/spec/`): draft propose, batched target
verify, Leviathan rejection sampling, KV rewind.

The correctness bar (ISSUE 10): **greedy speculative decode is
token-identical to non-speculative greedy** — across plain, shared-prefix
and chunked-prefill scenarios — because greedy acceptance collapses to
"accept while the target argmax agrees".  Sampled decoding is pinned
statistically: the emitted-token distribution must match the target's
knob-filtered softmax (Leviathan's distribution-preservation theorem),
within sampling noise.  Compile counts stay bounded (chunk ladder +
verify + draft ladder + propose; the plain tick program never compiles),
and the acceptance gauges flow engine -> stats -> /statusz -> /metrics ->
report -> compare gate.
"""

import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from bpe_transformer_tpu.models import TS_TEST_CONFIG, init_params
from bpe_transformer_tpu.serving import ServingEngine
from bpe_transformer_tpu.serving.engine import SlotPoolEngine
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine
from bpe_transformer_tpu.serving.spec.draft import DraftModel, DraftSpec
from bpe_transformer_tpu.serving.spec.engine import SpecEngine

pytestmark = pytest.mark.serving

REPO = Path(__file__).resolve().parent.parent

CFG = dataclasses.replace(TS_TEST_CONFIG, vocab_size=128, context_length=32)

DRAFT = DraftSpec(truncate_layers=1)


@pytest.fixture(scope="module")
def setup():
    params = init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(0)
    prompts = [
        [int(t) for t in rng.integers(0, CFG.vocab_size, size=n)]
        for n in (3, 7, 12, 19)
    ]
    return params, prompts


@pytest.fixture(scope="module")
def dense_engine(setup):
    params, _ = setup
    return SlotPoolEngine(params, CFG, slots=2, min_bucket=8)


@pytest.fixture(scope="module")
def spec_engine(setup):
    # Shared across the parity/bounded-compile/gauge tests: per-engine jit
    # caches make engines the expensive resource in this module (same
    # policy as test_kvpool/test_serving).
    params, _ = setup
    return SpecEngine(
        params, CFG, draft=DRAFT, speculate_k=3, slots=2, block_size=8,
        min_bucket=8,
    )


@pytest.fixture(scope="module")
def chunked_spec_engine(setup):
    params, _ = setup
    return SpecEngine(
        params, CFG, draft=DRAFT, speculate_k=2, slots=2, block_size=8,
        min_bucket=8, prefill_chunk=8,
    )


def _run(engine, prompt, **knobs):
    event = engine.admit(prompt, **knobs)
    out = [event.token]
    slot = event.slot
    while not event.finished:
        events = engine.tick()
        mine = [e for e in events if e.slot == slot]
        out.extend(e.token for e in mine)
        event = mine[-1]
    return out


# ------------------------------------------------------------ DraftSpec


def test_draft_spec_validation_rejects_bad_configs():
    with pytest.raises(ValueError, match="vocab_size"):
        DraftSpec(truncate_layers=1, vocab_size=999).validate_against(CFG)
    with pytest.raises(ValueError, match="truncate_layers"):
        DraftSpec(truncate_layers=CFG.num_layers + 1).validate_against(CFG)
    with pytest.raises(ValueError, match="not both"):
        DraftSpec(truncate_layers=1, d_model=16).validate_against(CFG)
    with pytest.raises(ValueError, match="incomplete"):
        DraftSpec(d_model=16, num_layers=1).validate_against(CFG)
    with pytest.raises(ValueError, match="unknown key"):
        DraftSpec.from_dict({"truncate_layers": 1, "nope": 2})
    # A matching explicit vocab and a full geometry both pass.
    DraftSpec(truncate_layers=1, vocab_size=CFG.vocab_size).validate_against(
        CFG
    )
    DraftSpec(d_model=16, num_layers=1, num_heads=2, d_ff=32).validate_against(
        CFG
    )


def test_draft_model_truncated_view_shares_target_arrays(setup):
    params, _ = setup
    draft = DraftModel(params, CFG, DraftSpec(truncate_layers=1))
    assert draft.config.num_layers == 1
    assert draft.config.vocab_size == CFG.vocab_size
    # Zero extra weight memory: the layer list is a slice of the target's.
    assert draft.param_bytes == 0
    assert draft.params["layers"][0] is params["layers"][0]
    assert len(draft.params["layers"]) == 1


def test_draft_model_geometry_initializes_own_params(setup):
    params, _ = setup
    spec = DraftSpec(d_model=16, num_layers=1, num_heads=2, d_ff=32, seed=7)
    draft = DraftModel(params, CFG, spec)
    assert draft.config.d_model == 16
    assert draft.param_bytes > 0
    assert draft.config.context_length == CFG.context_length


def test_spec_engine_rejects_mismatched_draft(setup):
    params, _ = setup
    with pytest.raises(ValueError, match="vocab"):
        SpecEngine(
            params, CFG, draft=DraftSpec(truncate_layers=1, vocab_size=64),
            speculate_k=2, slots=1, block_size=8,
        )
    with pytest.raises(ValueError, match="speculate_k"):
        SpecEngine(
            params, CFG, draft=DRAFT, speculate_k=0, slots=1, block_size=8
        )


# ------------------------------------------------------- greedy parity


def test_greedy_parity_with_dense_engine(setup, dense_engine, spec_engine):
    """ACCEPTANCE (ISSUE 10): greedy speculative decode is token-identical
    to non-speculative greedy — the Leviathan rule at temp 0 collapses to
    "accept while the target argmax agrees, then emit the target argmax",
    so speculation changes tick count, never tokens."""
    _, prompts = setup
    for prompt in prompts:
        assert _run(spec_engine, prompt, max_new_tokens=10,
                    temperature=0.0) == \
            _run(dense_engine, prompt, max_new_tokens=10, temperature=0.0), \
            f"spec/dense greedy divergence for prompt {prompt}"
    # Speculation actually sped something up: fewer target steps than
    # emitted tokens (acceptance > 0 for a self-drafted model).
    gauges = spec_engine.spec_gauges()
    assert gauges["spec_accept_rate"] is not None
    assert gauges["spec_tokens_per_target_step"] > 1.0


def test_greedy_parity_through_shared_prefix(setup, dense_engine,
                                             spec_engine):
    """Radix-shared prompt blocks + verify-pass writes + rewind stay
    token-identical: rewinding must copy-on-write rather than scribble
    over blocks the cache still indexes."""
    _, prompts = setup
    base = prompts[3]
    first = base + [15, 16]
    second = base + [19, 11, 12]
    assert _run(spec_engine, first, max_new_tokens=8, temperature=0.0) == \
        _run(dense_engine, first, max_new_tokens=8, temperature=0.0)
    slot = spec_engine.begin(second, max_new_tokens=8, temperature=0.0)
    assert spec_engine.slot_shared_len(slot) == 16
    event = spec_engine.prefill_step(slot)
    while event is None:
        event = spec_engine.prefill_step(slot)
    out = [event.token]
    while not event.finished:
        mine = [e for e in spec_engine.tick() if e.slot == slot]
        out.extend(e.token for e in mine)
        event = mine[-1]
    assert out == _run(dense_engine, second, max_new_tokens=8,
                       temperature=0.0)


def test_greedy_parity_chunked_prefill(setup, dense_engine,
                                       chunked_spec_engine):
    """Chunked prefill (the same machinery the verify pass generalizes)
    composes with speculation: long prompts split into chunks, then the
    spec ticks take over — tokens unchanged."""
    _, prompts = setup
    for prompt in (prompts[2], prompts[3]):
        assert _run(chunked_spec_engine, prompt, max_new_tokens=8,
                    temperature=0.0) == \
            _run(dense_engine, prompt, max_new_tokens=8, temperature=0.0)


def test_greedy_parity_batched_slots(setup, dense_engine, spec_engine):
    """Two slots decoding together (per-slot variable acceptance inside
    one fixed-K verify program) match their solo dense runs."""
    _, prompts = setup
    expected = {
        0: _run(dense_engine, prompts[0], max_new_tokens=6, temperature=0.0),
        1: _run(dense_engine, prompts[1], max_new_tokens=6, temperature=0.0),
    }
    ev0 = spec_engine.admit(prompts[0], max_new_tokens=6, temperature=0.0)
    ev1 = spec_engine.admit(prompts[1], max_new_tokens=6, temperature=0.0)
    outs = {ev0.slot: [ev0.token], ev1.slot: [ev1.token]}
    done = {ev0.slot: ev0.finished, ev1.slot: ev1.finished}
    by_slot = {ev0.slot: 0, ev1.slot: 1}
    while not all(done.values()):
        for e in spec_engine.tick():
            outs[e.slot].append(e.token)
            if e.finished:
                done[e.slot] = True
    for slot, idx in by_slot.items():
        assert outs[slot] == expected[idx], f"slot {slot} diverged"


# --------------------------------------------------- sampling behavior


def _filtered_softmax(params, tokens, *, top_k):
    """The target's next-token distribution after ``tokens``, under the
    same runtime knob filtering the serving sampler applies — the ``p`` of
    the Leviathan theorem."""
    import jax.numpy as jnp

    from bpe_transformer_tpu.models.decode import init_kv_cache, prefill
    from bpe_transformer_tpu.serving.engine import filter_logits

    bucket = 8 if len(tokens) <= 8 else 16
    padded = np.zeros((1, bucket), np.int32)
    padded[0, : len(tokens)] = tokens
    logits, _ = prefill(
        params, jnp.asarray(padded), CFG,
        init_kv_cache(CFG, 1, dtype=jnp.float32),
        last_pos=jnp.asarray([len(tokens) - 1]),
    )
    filt = filter_logits(
        np.asarray(logits, np.float32),
        np.asarray([1.0], np.float32),
        np.asarray([top_k], np.int32),
        np.asarray([2.0], np.float32),  # top-p disabled (>= 1)
    )
    p = np.exp(filt[0] - filt[0].max())
    return p / p.sum()


def test_sampled_distribution_preserved(setup):
    """Leviathan distribution preservation, measured: with temp 1 +
    top-k 4, the spec path's second-token draw matches the target's
    knob-filtered conditional softmax within sampling noise.  The draft
    proposes from a DIFFERENT distribution (1 of 3 layers), so acceptance
    is partial — exactly the regime the accept/residual math must keep
    unbiased in.  Token 0 comes from the prefill sampler (dense-identical
    by construction); token 1 is the first draw through accept/resample,
    so we histogram t1 CONDITIONED on the most frequent t0 and compare to
    p(.|t0)."""
    params, prompts = setup
    prompt = prompts[1]
    engine = SpecEngine(
        params, CFG, draft=DRAFT, speculate_k=2, slots=1, block_size=8,
        min_bucket=8,
    )
    n = 400
    pairs: dict = {}
    for seed in range(n):
        out = _run(
            engine, prompt, max_new_tokens=2, temperature=1.0, top_k=4,
            seed=seed,
        )
        pairs.setdefault(out[0], []).append(out[1])
    t0, draws = max(pairs.items(), key=lambda kv: len(kv[1]))
    assert len(draws) >= 50, "top-4 sampling should concentrate first tokens"
    ref = _filtered_softmax(params, prompt + [t0], top_k=4)
    emp = np.zeros(CFG.vocab_size)
    for t1 in draws:
        emp[t1] += 1
    emp /= emp.sum()
    tv = 0.5 * np.abs(emp - ref).sum()
    # TV noise floor for a 4-support distribution at >=50 draws is
    # ~sqrt(k/n) ≈ 0.15-0.3; a BROKEN acceptance rule (e.g. emitting the
    # draft's distribution, which comes from a different model) moves TV
    # by O(1).
    assert tv < 0.30, (
        f"spec-path draw diverges from target distribution: TV={tv:.3f} "
        f"(support {np.flatnonzero(ref > 0).tolist()}, n={len(draws)})"
    )
    # Sampling path exercised the acceptance/bonus machinery.
    g = engine.spec_gauges()
    assert g["spec_proposed_tokens"] > 0
    assert 0.0 <= g["spec_accept_rate"] <= 1.0


def test_sampled_generation_respects_stop_and_length(setup, spec_engine):
    _, prompts = setup
    out = _run(
        spec_engine, prompts[0], max_new_tokens=5, temperature=0.9,
        top_k=8, seed=11,
    )
    assert len(out) == 5
    assert all(0 <= t < CFG.vocab_size for t in out)
    # stop_id: the emission loop must break INSIDE a multi-token window —
    # the stop token is the generation's last, nothing after it leaks out.
    greedy = _run(spec_engine, prompts[0], max_new_tokens=6,
                  temperature=0.0)
    stop_id = greedy[2]
    stop = _run(
        spec_engine, prompts[0], max_new_tokens=20, temperature=0.0,
        stop_id=stop_id,
    )
    assert stop == greedy[: greedy.index(stop_id) + 1]


def test_greedy_spec_equals_the_paged_engine_one_launch_ahead(setup, spec_engine):
    """ISSUE 37: the plain paged engine keeps its decode carry on the
    device and may run one launch ahead; a speculative tick reads the carry
    on the host through the same owner (`read_carry` / `write_carry`) and
    does not split.  Greedy tokens agree across the two, and the carry the
    speculative engine writes back is the one its host arithmetic says."""
    params, prompts = setup
    plain = PagedEngine(params, CFG, slots=2, block_size=8, min_bucket=8)
    slot = plain.begin(prompts[2], max_new_tokens=9, temperature=0.0)
    while not plain.launch_chunk(slot):
        pass
    ahead = []
    while plain.unread:
        launched = plain.launch()
        while plain.unread > int(launched and plain.active_count > 0):
            ahead += [e.token for e in plain.collect()]
    assert plain.ticks_overlapped > 0
    assert ahead == _run(spec_engine, prompts[2], max_new_tokens=9,
                         temperature=0.0)
    with pytest.raises(NotImplementedError, match="tick\\(\\)"):
        spec_engine.launch()
    event = spec_engine.admit(prompts[2], max_new_tokens=9, temperature=0.0)
    spec_engine.tick()
    tokens, positions, _ = spec_engine.read_carry()
    assert np.array_equal(positions, spec_engine._positions)
    generated = spec_engine._slots[event.slot].generated
    assert int(positions[event.slot]) == len(prompts[2]) + generated - 1
    assert int(tokens[event.slot]) == ahead[generated - 1]
    spec_engine.release(event.slot)


# ------------------------------------------------------- compile bound


def test_bounded_compile_and_no_plain_tick(setup, dense_engine, spec_engine,
                                           chunked_spec_engine):
    """ACCEPTANCE (ISSUE 10): compile count stays within the ladder bound
    + draft ladder + propose + verify (+1 once a CoW rewind ran), and the
    plain decode-tick program NEVER compiles on the spec path — every
    spec tick is a verify pass."""
    for engine in (spec_engine, chunked_spec_engine):
        bound = (
            len(engine.buckets)          # target chunk ladder
            + 1                          # verify
            + len(engine.draft_buckets)  # draft prefill ladder
            + 1                          # propose
            + engine._copy_jit._cache_size()  # CoW copy, if any ran
        )
        assert engine.compiled_programs() <= bound, (
            f"{engine.compiled_programs()} programs > bound {bound}"
        )
        assert engine._tick_jit._cache_size() == 0, (
            "the plain tick compiled on the spec path"
        )
        assert engine._verify_jit._cache_size() == 1
        assert engine._propose_jit._cache_size() == 1


# -------------------------------------------- block-starved speculation


def test_speculation_window_shrinks_when_pool_is_dry(setup):
    """A block-starved slot shrinks its speculation window (rooms < K)
    instead of stalling or raising: the admission-time reservation always
    backs at least one decode position."""
    params, prompts = setup
    # Pool sized to the admission reservation EXACTLY: prompt 12 tokens +
    # 4 new = 16 positions = 2 blocks (+1 trash).  Verify scratch beyond
    # the reservation is never available.
    engine = SpecEngine(
        params, CFG, draft=DRAFT, speculate_k=3, slots=1, block_size=8,
        min_bucket=8, num_blocks=3, prefix_cache=False,
    )
    dense = SlotPoolEngine(params, CFG, slots=1, min_bucket=8)
    out = _run(engine, prompts[2], max_new_tokens=4, temperature=0.0)
    assert out == _run(dense, prompts[2], max_new_tokens=4, temperature=0.0)
    # The pool gave back everything on release.
    assert engine.allocator.free_count == engine.allocator.usable_blocks


def test_int8_spec_generation_stays_coherent(setup):
    """int8 pools under the sequential verify quantizer + rewind-then-
    regrow: generation completes, rewinds happen, and the acceptance
    gauges stay sane.  (Token-level int8 parity with the plain int8
    engine is NOT promised — the verify pass quantizes K+1 rows against
    final block scales, plain ticks against per-step scales; both are
    within quantization error of the fp path.)"""
    params, prompts = setup
    engine = SpecEngine(
        params, CFG, draft=DRAFT, speculate_k=2, slots=1, block_size=8,
        min_bucket=8, kv_dtype="int8",
    )
    out = _run(engine, prompts[1], max_new_tokens=10, temperature=0.0)
    assert len(out) == 10
    assert all(0 <= t < CFG.vocab_size for t in out)
    g = engine.spec_gauges()
    assert g["spec_rewound_tokens"] >= 0
    assert g["spec_accept_rate"] is not None
    # fp greedy reference: int8 may flip near-ties but must stay close —
    # the first couple of tokens ride large logit margins in practice.
    fp = SpecEngine(
        params, CFG, draft=DRAFT, speculate_k=2, slots=1, block_size=8,
        min_bucket=8,
    )
    fp_out = _run(fp, prompts[1], max_new_tokens=10, temperature=0.0)
    assert out[0] == fp_out[0], "int8 diverged at the very first token"
    # Block scales stayed finite and non-negative (rewound rows fold into
    # the scale until the block is vacated — documented semantics).
    for layer in engine._pool:
        k_scale = np.asarray(layer["k_scale"])
        assert np.isfinite(k_scale).all() and (k_scale >= 0).all()


# --------------------------------------------------- serving + telemetry


def test_serving_engine_spec_end_to_end(setup, tmp_path):
    """ACCEPTANCE (ISSUE 10): the gauges flow end to end — engine stats ->
    /statusz payload -> Prometheus exposition -> kind="spec" records ->
    report section -> compare-gate metrics — and greedy generations match
    the non-speculative paged serving engine."""
    from bpe_transformer_tpu.telemetry import MetricsLogger, Telemetry
    from bpe_transformer_tpu.telemetry.monitor import (
        fold_prometheus,
        parse_prometheus,
        render_frame,
    )
    from bpe_transformer_tpu.telemetry.report import (
        extract_compare_metrics,
        render_report,
        summarize,
    )

    params, prompts = setup
    jsonl = tmp_path / "serve_spec.jsonl"
    logger = MetricsLogger(jsonl_path=str(jsonl))
    telemetry = Telemetry(sink=logger.log)

    with ServingEngine(
        params, CFG, slots=2, paged=True, block_size=8,
        speculate_k=2, draft_spec=DRAFT, telemetry=telemetry,
        engine_record_every_s=0.0,
    ) as serving:
        results = [
            serving.generate(p, max_new_tokens=8, temperature=0.0)
            for p in prompts[:3]
        ]
    logger.close()

    with ServingEngine(
        params, CFG, slots=2, paged=True, block_size=8
    ) as plain:
        plain_results = [
            plain.generate(p, max_new_tokens=8, temperature=0.0)
            for p in prompts[:3]
        ]
    for r, pr in zip(results, plain_results):
        assert r.token_ids == pr.token_ids
        assert r.finish_reason == pr.finish_reason

    # stats(): engine kind + the acceptance gauges.
    with ServingEngine(
        params, CFG, slots=2, paged=True, block_size=8,
        speculate_k=2, draft_spec=DRAFT,
    ) as serving:
        serving.generate(prompts[0], max_new_tokens=6, temperature=0.0)
        stats = serving.stats()
        assert stats["engine_kind"] == "spec"
        assert stats["spec_k"] == 2
        assert stats["spec_accept_rate"] is not None
        assert stats["spec_tokens_per_target_step"] >= 1.0
        page = serving.statusz()
        assert page["engine_kind"] == "spec"
        assert page["speculate_k"] == 2
        assert page["kvpool"]["spec_accept_rate"] == \
            stats["spec_accept_rate"]
        text = serving.prometheus_metrics()
    state = fold_prometheus(parse_prometheus(text))
    assert state["spec_k"] == 2
    assert "spec_accept_rate" in state
    assert "spec_tokens_per_target_step" in state
    assert "spec" in render_frame(state, "test")

    # The JSONL stream carries kind="spec" records the report renders and
    # the compare gate extracts.
    records = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    spec_records = [r for r in records if r.get("kind") == "spec"]
    assert spec_records, "no kind='spec' records on the engine cadence"
    for r in spec_records:
        assert r["k"] == 2
        assert r["proposed"] >= r["accepted"]
    report = render_report(records)
    assert "speculative decoding" in report
    metrics = extract_compare_metrics(summarize(records))
    assert "accept_rate" in metrics
    assert metrics["accept_rate"][1] == "higher"
    assert "tokens_per_target_step" in metrics


def test_serving_engine_speculate_requires_paged_and_draft(setup):
    params, _ = setup
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(params, CFG, speculate_k=2, draft_spec=DRAFT)
    with pytest.raises(ValueError, match="draft_spec"):
        ServingEngine(params, CFG, paged=True, speculate_k=2)


# ------------------------------------------------- fixture-pinned surfaces


def test_spec_fixture_pins_report_monitor_compare():
    """tests/fixtures/spec_tiny.jsonl is the pinned wire format: report
    section, monitor fold, and the compare gate must keep reading it
    (schema check #5 keeps the kind covered)."""
    from bpe_transformer_tpu.telemetry.monitor import fold_records, render_frame
    from bpe_transformer_tpu.telemetry.report import (
        compare_metrics,
        extract_compare_metrics,
        render_report,
        summarize,
    )

    records = [
        json.loads(ln)
        for ln in (REPO / "tests/fixtures/spec_tiny.jsonl")
        .read_text().splitlines()
    ]
    summary = summarize(records)
    assert summary["spec"]["accept_rate"] == 0.625
    assert summary["spec"]["tokens_per_target_step"] == 3.5
    report = render_report(records)
    assert "== speculative decoding (2 samples) ==" in report
    assert "accept rate 62.5%" in report

    state = fold_records(records)
    assert state["spec_accept_rate"] == 0.625
    frame = render_frame(state, "test")
    assert "spec   k 4  accept 62%" in frame

    metrics = extract_compare_metrics(summary)
    regressed = dict(metrics)
    regressed["accept_rate"] = (0.3, "higher")
    rows, regressions = compare_metrics(metrics, regressed)
    assert "accept_rate" in regressions
    rows, regressions = compare_metrics(metrics, metrics)
    assert not regressions


# ----------------------------------------------------------- CLI fast-fail


def _cli(args, **env_extra):
    import os

    return subprocess.run(
        [sys.executable, "-m", "bpe_transformer_tpu.training.cli"] + args,
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(REPO), **env_extra},
        cwd=str(REPO),
    )


@pytest.mark.slow
def test_cli_speculate_fast_fail_rc2(tmp_path):
    """ACCEPTANCE (satellite): serve/warmup reject impossible --speculate
    combinations up front with rc 2 — structural errors before any model
    load, vocab mismatch right after config resolution (never a deep
    shape error mid-compile)."""
    draft = tmp_path / "draft.json"
    draft.write_text(json.dumps({"truncate_layers": 1}))
    bad_vocab = tmp_path / "bad_vocab.json"
    bad_vocab.write_text(json.dumps(
        {"d_model": 16, "num_layers": 1, "num_heads": 2, "d_ff": 32,
         "vocab_size": 17}
    ))
    bad_keys = tmp_path / "bad_keys.json"
    bad_keys.write_text(json.dumps({"truncate_layers": 1, "bogus": True}))

    # Structural failures never touch the (nonexistent) checkpoint.
    proc = _cli(["serve", "--checkpoint", "/nonexistent",
                 "--tokenizer-dir", "/nonexistent", "--speculate", "2"])
    assert proc.returncode == 2 and "--paged" in proc.stderr
    proc = _cli(["serve", "--checkpoint", "/nonexistent",
                 "--tokenizer-dir", "/nonexistent", "--paged",
                 "--speculate", "2"])
    assert proc.returncode == 2 and "--draft-config" in proc.stderr
    proc = _cli(["serve", "--checkpoint", "/nonexistent",
                 "--tokenizer-dir", "/nonexistent", "--paged",
                 "--speculate", "2", "--draft-config", str(bad_keys)])
    assert proc.returncode == 2 and "unknown key" in proc.stderr
    proc = _cli(["serve", "--checkpoint", "/nonexistent",
                 "--tokenizer-dir", "/nonexistent", "--paged",
                 "--draft-config", str(draft)])
    assert proc.returncode == 2 and "--speculate" in proc.stderr

    # Vocab mismatch: config resolution happens, engines never build.
    proc = _cli(["warmup", "--compile-cache", str(tmp_path / "cc"),
                 "--preset", "ts-test", "--paged", "--speculate", "2",
                 "--draft-config", str(bad_vocab)])
    assert proc.returncode == 2 and "vocab_size" in proc.stderr
    proc = _cli(["warmup", "--compile-cache", str(tmp_path / "cc"),
                 "--paged", "--speculate", "2"])
    assert proc.returncode == 2 and "--draft-config" in proc.stderr


@pytest.mark.slow
def test_warmup_spec_cli_two_process_cache_hits(tmp_path):
    """`bpe-tpu warmup --speculate` AOT-compiles the spec ladder (chunk +
    verify + draft prefill + propose) into the persistent cache; a second
    process restarts warm."""
    draft = tmp_path / "draft.json"
    draft.write_text(json.dumps({"truncate_layers": 1}))
    cache_dir = tmp_path / "xla_cache"

    def run():
        proc = _cli([
            "warmup", "--compile-cache", str(cache_dir),
            "--preset", "ts-test", "--paged", "--block-size", "8",
            "--slots", "2", "--kv-dtype", "act",
            "--speculate", "3", "--draft-config", str(draft),
        ], XLA_FLAGS="")
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = run()
    assert cold["engine"] == "spec" and cold["speculate"] == 3
    assert cold["cache_hits"] == 0
    # chunk ladder + verify + draft ladder + propose, one kv dtype.
    assert cold["programs_compiled"] <= 2 * (len(cold["buckets"]) + 1)
    warm = run()
    assert warm["cache_hits"] > 0
