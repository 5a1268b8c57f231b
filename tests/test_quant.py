"""int8 weight-quantized decode + fused sample-in-kernel (ISSUE 11).

Pins the three legs of the quantized-compute decode path:

* **per-channel int8 weights** (`ops/quant.py` + the dequant-in-register
  Pallas matmul): exact kernel-vs-XLA-reference parity, bounded
  dequantization error, the quantize-tree structure contract
  (embeddings/norms untouched, MoE refused), and the ~2x/4x weight-byte
  cut asserted via tree bytes (the PR 9 pool-bytes pin pattern);
* **fused sampling** (`kernels/pallas/sample.py`): the fused
  projection+filter+sample kernel is token-identical to the unfused
  `sample_tokens` chain across runtime knob mixes (the gumbel noise IS
  what `jax.random.categorical` draws), engine-level greedy AND sampled
  parity fused-vs-unfused, the spec-verify kernel against the
  `_spec_verify_program` reference math, and greedy spec parity on the
  fully quantized+fused path;
* **quality gates** (PR 9 style): quantized-vs-f32 decode logit
  max-abs-error bound, a greedy long-decode smoke, bounded-compile
  assertions (the quantized/fused ladder adds no unbounded programs),
  and the serving stats/statusz/metrics/roofline surfaces.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.decode import decode_step, init_kv_cache
from bpe_transformer_tpu.models.transformer import init_params, lm_head_weight
from bpe_transformer_tpu.ops.core import head_logits, linear
from bpe_transformer_tpu.ops.quant import (
    dequantize,
    is_quantized,
    quant_linear,
    quant_linear_xla,
    quantize_params,
    quantize_weight,
    tree_bytes,
)

REPO = Path(__file__).resolve().parent.parent

CFG = ModelConfig(
    vocab_size=128, context_length=64, d_model=32, num_layers=2,
    num_heads=4, d_ff=48,
)
CFG_GQA = ModelConfig(
    vocab_size=96, context_length=32, d_model=32, num_layers=2,
    num_heads=4, num_kv_heads=2, d_ff=40,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _drain(engine, prompts, *, temp=0.0, top_k=None, top_p=None,
           max_new_tokens=8):
    toks = {}
    for i, p in enumerate(prompts):
        ev = engine.admit(
            p, max_new_tokens=max_new_tokens, temperature=temp,
            top_k=top_k, top_p=top_p, seed=11 + i,
        )
        toks.setdefault(ev.slot, []).append(ev.token)
    while engine.active_count:
        for ev in engine.tick():
            toks.setdefault(ev.slot, []).append(ev.token)
    return toks


# ------------------------------------------------------------ quantization


def test_quantize_weight_layout_and_error_bound():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(83, 64)).astype(np.float32)) * 0.1
    wq = quantize_weight(w)
    assert is_quantized(wq)
    assert wq["q"].dtype == jnp.int8 and wq["q"].shape == w.shape
    assert wq["scale"].dtype == jnp.float32 and wq["scale"].shape == (83,)
    # Per-channel symmetric quantization: error <= scale/2 per channel.
    err = jnp.abs(dequantize(wq) - w)
    assert float(jnp.max(err - wq["scale"][:, None] / 2)) <= 1e-7
    # An all-zero row dequantizes to exact zeros (scale 0, no NaN).
    w0 = quantize_weight(w.at[5].set(0.0))
    assert float(jnp.abs(dequantize(w0)[5]).max()) == 0.0


@pytest.mark.parametrize(
    "shape", [(8, 683, 256), (3, 97, 64), (1, 40, 32), (2, 4100, 16)]
)
def test_quant_matmul_kernel_matches_xla_reference(shape):
    """The Pallas dequant-in-register matmul equals the XLA reference to
    f32 rounding on every block layout: a d_out with no 128-multiple
    divisor runs 128-multiple tiles with a ragged last one (683, 4100) or
    one padded tile (97, 40), so the dot's summation order differs from
    XLA's whole-axis one — the bound is a few ulp of the output's scale
    (measured <= 8e-7 of it, and within 2x of XLA's own error against an
    f64 product)."""
    m, o, i = shape
    rng = np.random.default_rng(1)
    wq = quantize_weight(
        jnp.asarray(rng.normal(size=(o, i)).astype(np.float32))
    )
    x = jnp.asarray(rng.normal(size=(m, i)).astype(np.float32))
    ref = np.asarray(quant_linear_xla(x, wq))
    np.testing.assert_allclose(
        np.asarray(quant_linear(x, wq)), ref,
        rtol=0, atol=2e-6 * float(np.abs(ref).max()),
    )


def test_linear_and_head_dispatch_on_quantized_dicts():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(96, 64)).astype(np.float32))
    wq = quantize_weight(w)
    x = jnp.asarray(rng.normal(size=(2, 3, 64))).astype(jnp.bfloat16)
    out = linear(x, wq)
    assert out.shape == (2, 3, 96) and out.dtype == jnp.bfloat16
    logits = head_logits(x, wq)
    # head_logits contract: logits stay float32-clean under quantization.
    assert logits.dtype == jnp.float32
    ref = head_logits(x.astype(jnp.float32), w)
    assert float(jnp.max(jnp.abs(logits - ref))) < 0.2


def test_quantize_params_structure_and_bytes(params):
    qparams = quantize_params(params, CFG)
    # Embeddings and norm gains pass through IDENTICALLY (same arrays).
    assert qparams["token_embeddings"] is params["token_embeddings"]
    assert qparams["ln_final"] is params["ln_final"]
    layer = qparams["layers"][0]
    assert layer["ln1"] is params["layers"][0]["ln1"]
    for name in ("q_proj", "k_proj", "v_proj", "output_proj"):
        assert is_quantized(layer["attn"][name])
    for name in ("w1", "w2", "w3"):
        assert is_quantized(layer["ffn"][name])
    assert is_quantized(qparams["lm_head"])
    # The matmul-weight bytes shrink ~4x vs f32 (scale overhead included).
    dense = tree_bytes(params["layers"]) + tree_bytes(params["lm_head"])
    quant = tree_bytes(qparams["layers"]) + tree_bytes(qparams["lm_head"])
    assert quant < 0.30 * dense
    # MoE expert stacks are NOT covered: refuse loudly.
    moe_cfg = ModelConfig(
        vocab_size=64, context_length=16, d_model=16, num_layers=1,
        num_heads=2, d_ff=32, ffn_type="moe", n_experts=2,
    )
    with pytest.raises(ValueError, match="[Mm]o[Ee]"):
        quantize_params(init_params(jax.random.PRNGKey(1), moe_cfg), moe_cfg)


def test_quantized_decode_logit_error_bound(params):
    """QUALITY GATE: int8-weight decode logits stay within a documented
    max-abs-error bound of the f32 path (PR 9's kv-int8 pattern)."""
    qparams = quantize_params(params, CFG)
    lm_head = lm_head_weight(params, CFG)
    q_head = quantize_weight(lm_head)
    cache = init_kv_cache(CFG, 3)
    token = jnp.asarray([5, 9, 77], jnp.int32)
    pos = jnp.zeros(3, jnp.int32)
    ref, _ = decode_step(params, token, pos, cache, CFG, lm_head=lm_head)
    got, _ = decode_step(qparams, token, pos, cache, CFG, lm_head=q_head)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < 0.15, f"int8-weight logit error {err} over bound"
    assert err > 0  # the paths genuinely differ — the bound is load-bearing


# ---------------------------------------------------------- fused sampling


def _knob_rows():
    temps = jnp.asarray([0.0, 1.0, 0.7, 1.3, 1.0, 0.5], jnp.float32)
    top_ks = jnp.asarray([0, 0, 5, 1, 40, 0], jnp.int32)
    top_ps = jnp.asarray([2.0, 0.9, 2.0, 0.5, 0.3, 0.0], jnp.float32)
    return temps, top_ks, top_ps


@pytest.mark.parametrize("quantized", [False, True])
def test_fused_head_sample_token_identical_to_unfused(quantized):
    """ACCEPTANCE: the fused projection+filter+sample kernel emits the
    SAME tokens as the unfused head_logits -> filter_logits ->
    categorical chain across greedy/temp/top-k/top-p knob mixes — the
    gumbel noise is exactly what categorical would draw from the same
    keys."""
    from bpe_transformer_tpu.kernels.pallas.sample import fused_head_sample
    from bpe_transformer_tpu.serving.engine import gumbel_rows, sample_tokens

    rng = np.random.default_rng(3)
    s, d, v = 6, 64, 257  # odd vocab: whole-V block path
    hidden = jnp.asarray(rng.normal(size=(s, d)).astype(np.float32))
    head = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32)) * 0.3
    if quantized:
        head = quantize_weight(head)
    temps, top_ks, top_ps = _knob_rows()
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(s))
    ref = sample_tokens(
        head_logits(hidden, head), keys, temps, top_ks, top_ps
    )
    tok = fused_head_sample(
        hidden, head, temps, top_ks, top_ps, gumbel_rows(keys, v)
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(tok))


def test_fused_verify_head_matches_reference_math():
    """The spec-verify kernel's three outputs (greedy, p(d), residual
    bonus sample) equal the `_spec_verify_program` reference math
    computed in plain jnp on the same logits/noise."""
    from bpe_transformer_tpu.kernels.pallas.sample import fused_verify_head
    from bpe_transformer_tpu.serving.engine import filter_logits

    rng = np.random.default_rng(4)
    s, k1, d, v = 3, 4, 32, 101
    r = s * k1
    hidden = jnp.asarray(rng.normal(size=(r, d)).astype(np.float32))
    head = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32)) * 0.3
    temps = jnp.repeat(jnp.asarray([0.0, 1.0, 0.8], jnp.float32), k1)
    ks = jnp.repeat(jnp.asarray([0, 7, 0], jnp.int32), k1)
    ps = jnp.repeat(jnp.asarray([2.0, 0.8, 2.0], jnp.float32), k1)
    judge = jnp.asarray(rng.integers(0, v, size=(r,)), jnp.int32)
    q = jax.nn.softmax(
        jnp.asarray(rng.normal(size=(r, v)).astype(np.float32)), axis=-1
    )
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(r))
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)

    greedy, p_d, bonus = fused_verify_head(
        hidden, head, temps, ks, ps, judge, q, gumbel
    )
    logits = head_logits(hidden, head)
    g_ref = jnp.argmax(logits, axis=-1)
    p_soft = jax.nn.softmax(filter_logits(logits, temps, ks, ps), axis=-1)
    p = jnp.where(
        (temps > 0)[:, None], p_soft, jax.nn.one_hot(g_ref, v)
    )
    pd_ref = jnp.take_along_axis(p, judge[:, None], axis=-1)[:, 0]
    res = jnp.maximum(p - q, 0.0)
    res = jnp.where(jnp.sum(res, -1, keepdims=True) > 0, res, p)
    logres = jnp.where(res > 0, jnp.log(res), -jnp.inf)
    bonus_ref = jnp.where(
        temps > 0,
        jnp.argmax(logres + gumbel, axis=-1),
        jnp.argmax(res, axis=-1),
    )
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(g_ref))
    np.testing.assert_allclose(
        np.asarray(p_d), np.asarray(pd_ref), rtol=0, atol=2e-6
    )
    np.testing.assert_array_equal(np.asarray(bonus), np.asarray(bonus_ref))


# ------------------------------------------------------- engine-level pins


#: The flagship combination (paged + int8) stays tier-1; the other
#: engine/width combinations run in the full matrix (870s-budget
#: discipline, PR 9 precedent — tier-1 keeps one end-to-end pin per
#: claim, the sweep stays behind `slow`).
@pytest.mark.parametrize(
    "weight_dtype",
    [pytest.param(None, marks=pytest.mark.slow), "int8"],
)
@pytest.mark.parametrize(
    "paged",
    [pytest.param(False, marks=pytest.mark.slow), True],
)
def test_engine_greedy_fused_identical_to_unfused(params, paged, weight_dtype):
    """ACCEPTANCE: greedy decode with fused sampling is token-identical
    to the unfused path — on both engines, at both weight widths."""
    from bpe_transformer_tpu.serving.engine import SlotPoolEngine
    from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine

    def build(fused):
        if paged:
            return PagedEngine(
                params, CFG, slots=3, block_size=8,
                weight_dtype=weight_dtype, fused_sampling=fused,
            )
        return SlotPoolEngine(
            params, CFG, slots=3, weight_dtype=weight_dtype,
            fused_sampling=fused,
        )

    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11] * 12]
    assert _drain(build(False), prompts) == _drain(build(True), prompts)


@pytest.mark.slow
def test_engine_sampled_fused_matches_unfused_on_cpu(params):
    """On CPU the kernel's logits match the XLA matmul bitwise, so even
    the SAMPLED path is token-identical fused-vs-unfused (the stronger
    form of distribution preservation; on hardware only greedy is
    pinned)."""
    from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine

    prompts = [[1, 2, 3], [7], [5, 5, 5, 5]]
    a = _drain(
        PagedEngine(params, CFG, slots=3, block_size=8),
        prompts, temp=0.9, top_k=9, top_p=0.85,
    )
    b = _drain(
        PagedEngine(params, CFG, slots=3, block_size=8,
                    fused_sampling=True),
        prompts, temp=0.9, top_k=9, top_p=0.85,
    )
    assert a == b


@pytest.mark.slow
def test_quantized_greedy_long_decode_smoke(params):
    """QUALITY GATE: a long greedy decode on int8 weights emits valid
    tokens end to end and tracks the f32 path closely (the per-step
    logit error bound keeps argmax flips rare at this scale)."""
    from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine

    prompts = [[3, 1, 4, 1, 5]]
    ref = _drain(
        PagedEngine(params, CFG, slots=1, block_size=8),
        prompts, max_new_tokens=48,
    )
    got = _drain(
        PagedEngine(params, CFG, slots=1, block_size=8,
                    weight_dtype="int8", fused_sampling=True),
        prompts, max_new_tokens=48,
    )
    (ref_toks,), (got_toks,) = ref.values(), got.values()
    assert len(got_toks) == 48
    assert all(0 <= t < CFG.vocab_size for t in got_toks)
    agree = sum(a == b for a, b in zip(ref_toks, got_toks)) / 48
    assert agree >= 0.8, f"int8 greedy drifted: {agree:.0%} agreement"


@pytest.mark.slow
def test_bounded_compile_quantized_fused_ladder(params):
    """QUALITY GATE: the quantized+fused ladder adds no unbounded
    programs — still one chunk program per bucket + one tick."""
    from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine

    engine = PagedEngine(
        params, CFG, slots=3, block_size=8, weight_dtype="int8",
        fused_sampling=True, prefill_buckets=(8, 16),
    )
    _drain(engine, [[1] * 5, [2] * 12, [3] * 3], max_new_tokens=6)
    _drain(engine, [[4] * 9, [5] * 2], max_new_tokens=6)
    assert engine.compiled_programs() <= len(engine.buckets) + 1


@pytest.mark.slow
def test_spec_greedy_parity_on_quantized_fused_path(params):
    """ACCEPTANCE: the spec-decode greedy parity suite's core pin holds
    on the quantized path — SpecEngine with int8 weights + fused verify
    emits exactly the non-speculative quantized engine's greedy tokens
    (the truncated draft shares the quantized tree, zero extra bytes)."""
    from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine
    from bpe_transformer_tpu.serving.spec.draft import DraftSpec
    from bpe_transformer_tpu.serving.spec.engine import SpecEngine

    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11] * 12]
    base = _drain(
        PagedEngine(params, CFG, slots=3, block_size=8,
                    weight_dtype="int8"),
        prompts, max_new_tokens=10,
    )
    spec = SpecEngine(
        params, CFG, draft=DraftSpec(truncate_layers=1), speculate_k=3,
        slots=3, block_size=8, weight_dtype="int8", fused_sampling=True,
    )
    assert _drain(spec, prompts, max_new_tokens=10) == base
    assert spec.draft.param_bytes == 0  # still a zero-copy quantized view
    # Sampled smoke on the same engine: runs, valid tokens, gauges move.
    out = _drain(spec, prompts, temp=0.9, top_k=20, max_new_tokens=6)
    assert all(0 <= t < CFG.vocab_size for ts in out.values() for t in ts)
    assert spec.spec_target_steps > 0


# --------------------------------------------------- serving-layer gauges


def test_serving_stats_statusz_metrics_and_roofline(params):
    """Telemetry satellites: params_bytes / weight_dtype / tick bytes on
    stats() + /statusz + /metrics, and the analytic decode-tick roofline
    wired end to end with the int8 ratio visible."""
    from bpe_transformer_tpu.serving.server import ServingEngine

    act = ServingEngine(params, CFG, slots=2, paged=True, block_size=8)
    q = ServingEngine(
        params, CFG, slots=2, paged=True, block_size=8,
        weight_dtype="int8", fused_sampling=True,
    )
    try:
        sa, sq = act.stats(), q.stats()
        assert sa["weight_dtype"] == "float32" and sq["weight_dtype"] == "int8"
        # ACCEPTANCE: ~2x+ lower weight bytes per tick (4x vs f32 minus
        # scale overhead), pinned via tree bytes like PR 9's pool pin.
        ratio = sq["tick_weight_bytes"] / sa["tick_weight_bytes"]
        assert ratio < 0.45, ratio
        assert sq["params_bytes"] < sa["params_bytes"]
        assert sq["fused_sampling"] is True
        roof = sq["decode_roofline"]
        assert roof["weight_bytes"] == sq["tick_weight_bytes"]
        assert roof["weight_dtype"] == "int8"
        assert roof["kv_bytes"] == 0  # no active slots yet
        zz = q.statusz()
        assert zz["weight_dtype"] == "int8"
        assert zz["decode_roofline"]["fused_sampling"] is True
        prom = q.prometheus_metrics()
        for needle in (
            'bpe_tpu_params_bytes{weight_dtype="int8"}',
            "bpe_tpu_decode_tick_weight_bytes",
            "bpe_tpu_decode_tick_kv_bytes",
        ):
            assert needle in prom, needle
    finally:
        act.close()
        q.close()


def test_roofline_records_emitted_and_schema_valid(params):
    """The kind="roofline" record rides the engine cadence and validates
    against the registered schema (check #5's fixture pins the wire
    format; this pins the live emitter)."""
    from bpe_transformer_tpu.serving.server import ServingEngine
    from bpe_transformer_tpu.telemetry import Telemetry
    from bpe_transformer_tpu.telemetry.schema import validate_record

    records = []
    tel = Telemetry(sink=records.append)
    s = ServingEngine(
        params, CFG, slots=2, paged=True, block_size=8,
        weight_dtype="int8", telemetry=tel, engine_record_every_s=0.0,
    )
    with s:
        s.generate([1, 2, 3], max_new_tokens=6, temperature=0.0,
                   timeout=120)
    roofs = [r for r in records if r.get("kind") == "roofline"]
    assert roofs, [r.get("kind") for r in records]
    for rec in roofs:
        assert not validate_record(rec)
    assert roofs[0]["weight_dtype"] == "int8"
    assert roofs[0]["weight_bytes"] == s.engine.tick_weight_bytes


def test_decode_tick_roofline_math():
    from bpe_transformer_tpu.telemetry.attribution import decode_tick_roofline
    from bpe_transformer_tpu.utils.flops import (
        decode_tick_flops,
        matmul_param_count,
    )

    flops = decode_tick_flops(CFG, 4, 100)
    assert flops == 2.0 * matmul_param_count(CFG) * 4 + (
        4.0 * CFG.num_layers * CFG.d_model * 100
    )
    row = decode_tick_roofline(
        flops=flops, weight_bytes=1000, kv_bytes=500, act_bytes=100,
        device_kind="TPU v5e",
    )
    assert row["bytes_accessed"] == 1600
    assert row["weight_frac"] == 0.625
    assert row["bound"] == "memory-bound"  # AI ~124 under the ~241 ridge
    assert row["projected_tick_s"] is not None
    tiny = decode_tick_roofline(
        flops=flops, weight_bytes=100, kv_bytes=50, act_bytes=10,
        device_kind="TPU v5e",
    )
    assert tiny["bound"] == "compute-bound"  # tiny bytes, big flops
    cpu = decode_tick_roofline(
        flops=flops, weight_bytes=1000, kv_bytes=0, act_bytes=0,
        device_kind="cpu",
    )
    assert cpu["bound"] == "unknown" and cpu["projected_tick_s"] is None


def test_roofline_fixture_pins_report_and_compare_gate():
    """tests/fixtures/roofline_tiny.jsonl is the pinned wire format:
    the report section and the serve_weight_bytes compare-gate row must
    keep reading it."""
    from bpe_transformer_tpu.telemetry.report import (
        compare_metrics,
        extract_compare_metrics,
        render_report,
        summarize,
    )

    records = [
        json.loads(ln)
        for ln in (REPO / "tests/fixtures/roofline_tiny.jsonl")
        .read_text().splitlines()
    ]
    summary = summarize(records)
    assert summary["roofline"]["weight_bytes"] == 13159424
    assert summary["roofline"]["weight_dtype"] == "int8"
    assert summary["roofline"]["bound"] == "memory-bound"
    report = render_report(records)
    assert "== decode roofline (2 samples) ==" in report
    assert "tick weights 13159424 B (int8)" in report

    metrics = extract_compare_metrics(summary)
    assert metrics["serve_weight_bytes"] == (13159424.0, "lower")
    # Weight bytes growing back against an int8 baseline is a gated
    # regression (the quantization win lost).
    bloated = dict(metrics)
    bloated["serve_weight_bytes"] = (26318848.0, "lower")
    _, regressions = compare_metrics(metrics, bloated)
    assert "serve_weight_bytes" in regressions
    _, regressions = compare_metrics(metrics, metrics)
    assert not regressions


@pytest.mark.slow
def test_cli_weight_dtype_rc2_validation(tmp_path):
    """rc-2 validation (PR 9 pattern): --weight-dtype int8 on an MoE
    config is a configuration error the CLI refuses up front — the
    per-channel quantizer does not cover expert stacks."""
    import os
    import subprocess
    import sys as _sys

    moe_cfg = tmp_path / "moe.json"
    moe_cfg.write_text(json.dumps({
        "vocab_size": 64, "context_length": 16, "d_model": 16,
        "num_layers": 1, "num_heads": 2, "d_ff": 32,
        "ffn_type": "moe", "n_experts": 2,
    }))
    proc = subprocess.run(
        [
            _sys.executable, "-m", "bpe_transformer_tpu.training.cli",
            "warmup", "--compile-cache", str(tmp_path / "cc"),
            "--model-config", str(moe_cfg), "--paged",
            "--weight-dtype", "int8",
        ],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)},
        cwd=str(REPO),
    )
    assert proc.returncode == 2
    assert "MoE" in proc.stderr


# ----------------------------------------------------------- tooling guard


def test_tier1_budget_tool_log_mode(tmp_path):
    """The tier-1 budget guard (tools/check_tier1_budget.py) passes a
    within-budget pytest log, fails an over-budget one, and fails loudly
    on a log with no summary trailer (an interrupted/killed run must not
    read as green)."""
    import sys
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_tier1_budget as tool
    finally:
        sys.path.pop(0)

    ok = tmp_path / "ok.log"
    ok.write_text("...\n== 398 passed, 27 deselected in 612.34s ==\n")
    assert tool.main([str(ok)]) == 0
    over = tmp_path / "over.log"
    over.write_text("== 430 passed in 845.10s ==\n")
    assert tool.main([str(over)]) == 1
    assert tool.main([str(over), "--budget", "900"]) == 0
    truncated = tmp_path / "killed.log"
    truncated.write_text("...F....\n")  # killed mid-run: no trailer
    assert tool.main([str(truncated)]) == 1
