"""Unified telemetry subsystem: spans, watchdog, manifests, health stats,
and the `bpe-tpu report` summarizer — all CPU-testable.

The fast tier-1 anchor for the observability layer: everything here runs in
seconds under JAX_PLATFORMS=cpu (the integration tests train a byte-level
2-layer model for a handful of steps).
"""

import json
import math
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bpe_transformer_tpu.models import ModelConfig
from bpe_transformer_tpu.telemetry import (
    NonFiniteError,
    Telemetry,
    Watchdog,
    flatten_health,
    git_sha,
    group_norms,
    health_metrics,
    nonfinite_count,
    nonfinite_fields,
    run_manifest,
)
from bpe_transformer_tpu.telemetry.health import group_of
from bpe_transformer_tpu.telemetry.report import (
    load_records,
    render_report,
    summarize,
)

TINY = ModelConfig(
    vocab_size=128,
    context_length=16,
    d_model=32,
    num_layers=2,
    num_heads=2,
    d_ff=64,
)


# --------------------------------------------------------------- span/event


def test_spans_nest_and_emit_structured_records():
    records = []
    t = Telemetry(sink=records.append)
    with t.span("setup"):
        with t.span("resume", path_hint="x"):
            pass
        t.event("checkpoint_loaded", step=5)
    kinds = [r["kind"] for r in records]
    assert kinds == ["span", "event", "span"]  # inner span closes first
    inner, event, outer = records
    assert inner["path"] == "setup/resume" and inner["name"] == "resume"
    assert inner["path_hint"] == "x"  # attrs pass through
    assert outer["path"] == "setup"
    assert outer["dur_s"] >= inner["dur_s"] >= 0
    assert event["name"] == "checkpoint_loaded" and event["step"] == 5
    assert event["t"] >= 0


def test_span_handle_end_is_idempotent_and_returns_duration():
    records = []
    t = Telemetry(sink=records.append)
    handle = t.start_span("compile")
    dur = handle.end(cache_hit=False)
    assert dur >= 0
    assert handle.end() == 0.0  # second close: no duplicate record
    assert len(records) == 1
    assert records[0]["cache_hit"] is False


def test_buffering_flushes_on_attach_and_bare_telemetry_is_noop():
    t = Telemetry()  # no sink: records buffer
    t.event("early", n=1)
    with t.span("setup"):
        pass
    records = []
    t.attach(records.append)
    assert [r["name"] for r in records] == ["early", "setup"]
    t.event("late")  # post-attach records flow straight through
    assert records[-1]["name"] == "late"
    Telemetry().event("dropped")  # never attached: silently dropped


def test_footer_reports_record_counts():
    records = []
    t = Telemetry(sink=records.append)
    t.event("nonfinite")
    t.event("nonfinite")
    t.footer(steps=100, clean=True)
    footer = records[-1]
    assert footer["kind"] == "footer"
    assert footer["clean"] is True and footer["steps"] == 100
    assert footer["record_counts"]["event:nonfinite"] == 2


# ----------------------------------------------------------------- watchdog


def _fake_clock(now):
    return lambda: now[0]


def test_watchdog_flags_hang_once_per_gap_and_rearms_on_beat():
    now = [0.0]
    records = []
    hangs = []
    wd = Watchdog(
        factor=4.0,
        min_history=3,
        min_timeout_s=0.0,
        telemetry=Telemetry(sink=records.append),
        on_hang=hangs.append,
        clock=_fake_clock(now),
    )
    assert wd.check() is False  # no history yet: cannot judge
    for _ in range(3):
        wd.beat(1.0)
    assert wd.hang_timeout_s() == pytest.approx(4.0)
    now[0] = 3.0
    assert wd.check() is False  # within deadline
    now[0] = 10.0
    assert wd.check() is True
    assert wd.check() is False  # once per silent gap
    assert wd.hang_events == 1
    assert hangs and hangs[0] == pytest.approx(10.0)
    event = records[-1]
    assert event["name"] == "watchdog_hang"
    assert event["silent_s"] == pytest.approx(10.0)
    wd.beat(1.0)  # new beat re-arms detection
    now[0] = 30.0
    assert wd.check() is True
    assert wd.hang_events == 2


def test_watchdog_median_resists_one_slow_step_and_floors_timeout():
    now = [0.0]
    wd = Watchdog(factor=2.0, min_history=3, min_timeout_s=5.0, clock=_fake_clock(now))
    for step_s in (0.01, 0.01, 0.01, 100.0):
        wd.beat(step_s)
    # Median 0.01 -> 2x median is 0.02, floored to min_timeout_s.
    assert wd.hang_timeout_s() == pytest.approx(5.0)


def test_watchdog_pause_suspends_detection_and_rearms():
    now = [0.0]
    wd = Watchdog(factor=2.0, min_history=3, min_timeout_s=0.0, clock=_fake_clock(now))
    for _ in range(3):
        wd.beat(1.0)
    with wd.pause():
        now[0] = 100.0  # way past the 2s deadline: legitimate long phase
        assert wd.check() is False
    assert wd.hang_events == 0
    # Exit re-armed the deadline from the pause's end, not the last beat.
    now[0] = 101.0
    assert wd.check() is False
    now[0] = 110.0
    assert wd.check() is True


def test_watchdog_nonfinite_policy_raise_dumps_then_raises():
    records = []
    wd = Watchdog(policy="raise", telemetry=Telemetry(sink=records.append))
    bad = {"step": 7, "loss": float("nan")}
    with pytest.raises(NonFiniteError, match="step 7"):
        wd.on_nonfinite(bad, ["loss"])
    # The evidence reached the stream BEFORE the raise.
    assert records[-1]["name"] == "nonfinite"
    assert records[-1]["record"]["step"] == 7
    assert wd.nonfinite_events == 1


def test_watchdog_nonfinite_policy_skip_records_and_continues():
    records = []
    wd = Watchdog(policy="skip", telemetry=Telemetry(sink=records.append))
    wd.on_nonfinite({"step": 3}, ["grad_norm/attn"])
    assert wd.nonfinite_events == 1
    assert records[-1]["fields"] == ["grad_norm/attn"]


def test_watchdog_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        Watchdog(policy="explode")


def test_watchdog_thread_lifecycle():
    wd = Watchdog(poll_interval_s=0.01)
    with wd:
        assert wd._thread is not None
    assert wd._thread is None  # stop() joined it
    wd.stop()  # idempotent


# ----------------------------------------------------------------- manifest


def test_run_manifest_is_json_serializable_and_self_describing():
    m = run_manifest(
        kind="train",
        model_config=TINY,
        loop_config={"steps": 10},
        parallel="dp",
        extra={"n_chips": 8},
    )
    json.dumps(m)  # must round-trip as one JSON record
    assert m["kind"] == "manifest" and m["run_kind"] == "train"
    assert m["model_config"]["d_model"] == 32
    assert m["loop_config"] == {"steps": 10}
    assert m["parallel"] == "dp" and m["n_chips"] == 8
    assert m["jax_version"]  # backend reachable in tests
    assert m["devices"]["platform"] == "cpu"
    assert m["host"] and m["python"]


def test_git_sha_inside_and_outside_a_checkout(tmp_path):
    sha = git_sha()
    assert sha is None or len(sha.split("-")[0]) == 40
    assert git_sha(cwd=tmp_path) is None  # not a checkout: None, no raise


def test_attach_manifest_never_loses_the_payload(monkeypatch):
    from bpe_transformer_tpu.telemetry import manifest as manifest_mod

    payload = manifest_mod.attach_manifest({"tok_s": 1.0}, kind="bench")
    assert payload["manifest"]["run_kind"] == "bench"

    def boom(**kw):
        raise RuntimeError("no backend")

    monkeypatch.setattr(manifest_mod, "run_manifest", boom)
    payload = manifest_mod.attach_manifest({"tok_s": 1.0}, kind="bench")
    assert payload == {"tok_s": 1.0}  # un-annotated, not raised


# ------------------------------------------------------- device-side health


def test_group_of_buckets_canonical_layer_groups():
    assert group_of("['layers'][0]['attn']['wq']") == "attn"
    assert group_of("['layers'][0]['ffn']['w1']") == "ffn"
    assert group_of("['token_embeddings']") == "embed"
    assert group_of("['lm_head']") == "head"
    assert group_of("['layers'][0]['ln1']") == "norm"
    assert group_of("['something_else']") == "other"


def test_group_norms_and_nonfinite_count():
    tree = {
        "attn": {"w": jnp.full((4,), 3.0)},
        "ffn": {"w": jnp.array([4.0, float("inf")])},
    }
    norms = group_norms(tree)
    assert norms["attn"] == pytest.approx(6.0)  # sqrt(4 * 9)
    assert int(nonfinite_count(tree)) == 1
    # bf16 leaves accumulate in f32: no overflow at moderate norms.
    big = {"attn": jnp.full((1024,), 300.0, dtype=jnp.bfloat16)}
    assert math.isfinite(float(group_norms(big)["attn"]))


def test_flatten_health_produces_flat_jsonl_keys():
    health = health_metrics(
        jnp.float32(2.5),
        {"attn": jnp.ones(3)},
        {"attn": jnp.ones(3), "lm_head": jnp.full(2, float("nan"))},
    )
    flat = flatten_health({**health, "moe_aux": jnp.float32(1.25)})
    assert flat["nonfinite_loss"] == 0
    assert flat["nonfinite_params"] == 2
    assert flat["grad_norm/attn"] == pytest.approx(math.sqrt(3.0))
    assert math.isnan(flat["param_norm/head"])
    assert flat["moe_aux"] == pytest.approx(1.25)


def test_health_enabled_train_step_exports_group_norms():
    import jax

    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_train_step,
    )

    params = init_params(jax.random.PRNGKey(0), TINY)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, TINY.vocab_size, size=(4, TINY.context_length))
    x, y = jnp.asarray(ids), jnp.asarray(np.roll(ids, -1, axis=1))

    # Default step: metrics unchanged (no health key, no extra cost).
    _, _, metrics = make_train_step(TINY, TrainHParams())(params, opt_state, x, y)
    assert "health" not in metrics

    params = init_params(jax.random.PRNGKey(0), TINY)
    step = make_train_step(TINY, TrainHParams(), health=True)
    _, _, metrics = step(params, adamw_init(params), x, y)
    flat = flatten_health(jax.device_get(metrics["health"]))
    assert flat["nonfinite_loss"] == 0
    assert flat["nonfinite_grads"] == 0 and flat["nonfinite_params"] == 0
    for group in ("attn", "ffn", "embed", "head", "norm"):
        assert flat[f"grad_norm/{group}"] >= 0
        assert flat[f"param_norm/{group}"] > 0


def test_health_enabled_moe_step_exports_expert_balance():
    import dataclasses

    import jax

    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_train_step,
    )

    moe = dataclasses.replace(TINY, ffn_type="moe", n_experts=4)
    params = init_params(jax.random.PRNGKey(0), moe)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, moe.vocab_size, size=(4, moe.context_length))
    x, y = jnp.asarray(ids), jnp.asarray(np.roll(ids, -1, axis=1))
    step = make_train_step(moe, TrainHParams(), health=True)
    _, _, metrics = step(params, adamw_init(params), x, y)
    moe_aux = float(metrics["health"]["moe_aux"])
    # Switch-style load-balance loss: 1.0 at uniform routing, and bounded
    # by n_experts (all traffic on one expert).
    assert 0.5 <= moe_aux <= moe.n_experts + 0.5


# ------------------------------------------------------------------- report


def test_nonfinite_fields_flags_counts_and_nonfinite_values():
    assert nonfinite_fields({"loss": 2.0, "grad_norm/attn": 1.0}) == []
    assert nonfinite_fields({"nonfinite_grads": 3}) == ["nonfinite_grads"]
    assert nonfinite_fields({"loss": float("nan")}) == ["loss"]
    # The global grad_norm every run logs is value-checked even without
    # --health-stats: an Inf grad norm must trip the watchdog policy.
    assert nonfinite_fields({"grad_norm": float("inf")}) == ["grad_norm"]
    assert nonfinite_fields({"param_norm/ffn": float("inf")}) == ["param_norm/ffn"]


def test_load_records_skips_corrupt_lines(tmp_path):
    path = tmp_path / "m.jsonl"
    path.write_text('{"step": 1}\nnot json\n\n{"step": 2}\n{"truncat')
    assert load_records(path) == [{"step": 1}, {"step": 2}]
    assert load_records(tmp_path / "missing.jsonl") == []


def _stream(tmp_path, records):
    path = tmp_path / "metrics.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    return path


def test_summarize_detects_anomalies(tmp_path):
    records = [
        {"kind": "manifest", "run_kind": "train", "git_sha": "abc"},
        {"step": 1, "loss": 3.0, "tokens_per_sec": 100.0},
        {"step": 2, "loss": 9.0},  # 3x spike
        {"step": 3, "loss": float("nan"), "nonfinite_grads": 2},
        {"step": 3, "val_loss": float("nan")},
        {"kind": "event", "name": "nonfinite", "t": 1.0, "step": 3},
        {"kind": "span", "name": "setup", "path": "setup", "t": 0.0, "dur_s": 1.5},
        # no footer: the run crashed
    ]
    s = summarize(load_records(_stream(tmp_path, records)))
    assert s["manifest"]["git_sha"] == "abc"
    assert s["steps"]["n"] == 3 and s["steps"]["step_range"] == [1, 3]
    assert s["spans"]["setup"]["total_s"] == pytest.approx(1.5)
    text = " | ".join(s["anomalies"])
    assert "non-finite state at step 3" in text
    assert "non-finite val_loss at step 3" in text
    assert "loss spike at step 2" in text
    assert "nonfinite event at step 3" in text
    assert "no footer" in text


def test_report_renders_clean_run(tmp_path):
    records = [
        run_manifest(kind="train", model_config=TINY),
        {"kind": "span", "name": "setup", "path": "setup", "t": 0.0, "dur_s": 0.8},
        {"step": 10, "loss": 3.0, "lr": 1e-4, "grad_norm": 0.5,
         "tokens_per_sec": 1000.0, "step_wall_s": 0.01, "mfu": 0.2,
         "grad_norm/attn": 0.3},
        {"step": 20, "loss": 2.5, "lr": 1e-4, "grad_norm": 0.4,
         "tokens_per_sec": 1200.0, "step_wall_s": 0.009, "mfu": 0.25,
         "grad_norm/attn": 0.2},
        {"step": 20, "val_loss": 2.6},
        {"kind": "footer", "t": 2.0, "clean": True, "record_counts": {}},
    ]
    text = render_report(load_records(_stream(tmp_path, records)))
    assert "== run manifest ==" in text and "kind=train" in text
    assert "steps 10..20" in text and "loss 3 -> 2.5" in text
    assert "val_loss" in text
    assert "tokens/sec" in text and "mfu" in text
    assert "setup" in text
    assert "grad_norm/attn" in text
    assert "anomalies (0)" in text and "clean footer" in text


def test_report_uses_latest_manifest_on_resumed_stream(tmp_path):
    records = [
        {"kind": "manifest", "run_kind": "train", "git_sha": "old0000"},
        {"step": 1, "loss": 3.0},
        {"kind": "footer", "t": 1.0, "clean": True, "record_counts": {}},
        {"kind": "manifest", "run_kind": "train", "git_sha": "new1111"},
        {"step": 2, "loss": 2.5},
        {"kind": "footer", "t": 2.0, "clean": True, "record_counts": {}},
    ]
    s = summarize(load_records(_stream(tmp_path, records)))
    # Latest manifest wins (matches summarize_captures.py); the render
    # flags that the stream holds multiple segments.
    assert s["manifest"]["git_sha"] == "new1111" and s["n_manifests"] == 2
    assert "latest of 2 manifests" in render_report(load_records(_stream(tmp_path, records)))


def test_report_cli_exit_codes(tmp_path, capsys):
    from bpe_transformer_tpu.telemetry.report import main as report_main

    assert report_main([]) == 2  # usage
    assert report_main([str(tmp_path / "missing.jsonl")]) == 1
    path = _stream(tmp_path, [{"step": 1, "loss": 2.0}])
    assert report_main([str(path)]) == 0
    assert "steps 1..1" in capsys.readouterr().out


def test_report_importable_without_jax(tmp_path):
    """The report tool must run on hosts with no accelerator runtime (a
    laptop summarizing a capture pulled off a pod): importing it — and the
    jax-free telemetry members — must not import jax."""
    import subprocess
    import sys as _sys
    from pathlib import Path

    script = (
        "import sys\n"
        "sys.modules['jax'] = None\n"  # any `import jax` now raises
        "from bpe_transformer_tpu.telemetry.report import summarize\n"
        "from bpe_transformer_tpu.telemetry.monitor import fold_records\n"
        "from bpe_transformer_tpu.telemetry import (\n"
        "    MetricsLogger, Telemetry, Watchdog, nonfinite_fields,\n"
        "    run_manifest, sample_resources, validate_record)\n"
        "assert 'jax_version' not in run_manifest(kind='offline')\n"
        "record = sample_resources()\n"  # degrades: RSS only, null device fields
        "assert record['host_rss_bytes'] and record['hbm_bytes_in_use'] is None\n"
        "assert validate_record(record) == []\n"
        "print('ok')\n"
    )
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [_sys.executable, "-c", script],
        capture_output=True, text=True,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(repo)},
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"


# ------------------------------------------------------ resources (PR 3)


def test_sample_resources_record_shape_and_rss():
    from bpe_transformer_tpu.telemetry import sample_resources, validate_record

    record = sample_resources(step=7)
    assert record["kind"] == "resources" and record["step"] == 7
    assert validate_record(record) == []
    # Host RSS must be real on Linux CI; live buffers are an int (possibly
    # 0); CPU backends carry null HBM fields, but the KEYS are pinned.
    assert record["host_rss_bytes"] > 1024 * 1024
    assert isinstance(record["live_buffer_bytes"], int)
    assert isinstance(record["compile_events"], int)
    for key in ("hbm_bytes_in_use", "hbm_peak_bytes_in_use", "hbm_bytes_limit"):
        assert key in record


def test_compile_counter_counts_fresh_jit_compiles():
    import jax
    import jax.numpy as jnp

    from bpe_transformer_tpu.telemetry import (
        compile_events,
        install_compile_counter,
        record_compile_events,
    )

    assert install_compile_counter() is True
    assert install_compile_counter() is True  # idempotent
    before = compile_events()

    @jax.jit
    def f(x, c):
        return x * c

    f(jnp.ones(3), 2.0)  # fresh program: one compile event
    first = compile_events()
    assert first >= before + 1
    f(jnp.ones(3), 3.0)  # cache hit: no new event
    assert compile_events() == first
    f(jnp.ones((2, 2)), 2.0)  # new shape: recompile
    assert compile_events() >= first + 1
    assert record_compile_events(2) == compile_events()


def test_validate_record_flags_unknown_and_missing():
    from bpe_transformer_tpu.telemetry import validate_record

    assert validate_record({"step": 3, "loss": 1.0}) == []
    assert validate_record(
        {"kind": "span", "name": "x", "path": "x", "t": 0.0, "dur_s": 0.1}
    ) == []
    assert "undocumented" in validate_record({"kind": "mystery"})[0]
    assert "missing required" in validate_record({"kind": "span", "name": "x"})[0]


def test_telemetry_schema_tool_is_clean():
    """tools/check_telemetry_schema.py (the tier-1 gate): every kind
    emitted in the package is documented, the docs tables are current, and
    the committed fixtures validate."""
    import subprocess
    import sys as _sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [_sys.executable, str(repo / "tools" / "check_telemetry_schema.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "telemetry schema clean" in proc.stdout


# ------------------------------------------------- compare / regression gate


def test_compare_metrics_directions_and_thresholds():
    from bpe_transformer_tpu.telemetry.report import compare_metrics

    base = {
        "tokens_per_sec_mean": (1000.0, "higher"),
        "loss_last": (2.0, "lower"),
        "step_wall_s_mean": (0.01, "lower"),
    }
    cur = {
        "tokens_per_sec_mean": (900.0, "higher"),   # -10%: regression
        "loss_last": (1.8, "lower"),                # -10%: improvement
        "step_wall_s_mean": (0.0102, "lower"),      # +2%: within threshold
        "mfu_mean": (0.3, "higher"),                # not in baseline: skipped
    }
    rows, regressions = compare_metrics(base, cur, default_threshold_pct=5.0)
    verdicts = {r["metric"]: r["verdict"] for r in rows}
    assert verdicts == {
        "loss_last": "improved",
        "tokens_per_sec_mean": "regressed",
        "step_wall_s_mean": "ok",
    }
    assert regressions == ["tokens_per_sec_mean"]
    # A per-metric threshold override can waive the gate.
    _, regressions = compare_metrics(
        base, cur, default_threshold_pct=5.0,
        thresholds={"tokens_per_sec_mean": 15.0},
    )
    assert regressions == []


FIXTURES = Path(__file__).resolve().parent / "fixtures"


def test_report_compare_fixture_pair_gates_regression(capsys):
    """ACCEPTANCE: the committed fixture pair encodes a known throughput/
    MFU/HBM regression; `bpe-tpu report --compare` exits 3 on it, 0 in the
    improving direction, and 0 when thresholds waive it."""
    from bpe_transformer_tpu.telemetry.report import main as report_main

    base = str(FIXTURES / "compare_base.jsonl")
    regressed = str(FIXTURES / "compare_regressed.jsonl")
    assert report_main([regressed, "--compare", base]) == 3
    out = capsys.readouterr().out
    assert "== compare vs" in out and "regressed" in out
    assert "tokens_per_sec_mean" in out and "hbm_peak_bytes" in out

    # The improving direction passes the gate (deltas flagged "improved").
    assert report_main([base, "--compare", regressed]) == 0
    assert "improved" in capsys.readouterr().out

    # Thresholds are configurable: wide enough, the same pair passes.
    assert report_main(
        [regressed, "--compare", base, "--threshold-pct", "50"]
    ) == 0
    # ...and a bad per-metric threshold is a usage error, not a silent skip.
    assert report_main(
        [regressed, "--compare", base, "--threshold", "typo_metric=5"]
    ) == 2


def test_report_baseline_capture_gate(tmp_path, capsys):
    """--baseline gates a stream against a bench capture JSON (and a
    capture against a previous capture)."""
    from bpe_transformer_tpu.telemetry.report import main as report_main

    capture = tmp_path / "tpu_capture_test.json"
    capture.write_text(json.dumps(
        {"metric": "tok/s", "value": 1500000.0, "mfu": 0.28,
         "platform": "tpu", "final_val_loss": 2.7}
    ))
    regressed = str(FIXTURES / "compare_regressed.jsonl")
    assert report_main([regressed, "--baseline", str(capture)]) == 3
    assert "regressed" in capsys.readouterr().out

    slower = tmp_path / "tpu_capture_prev.json"
    slower.write_text(json.dumps(
        {"metric": "tok/s", "value": 1000000.0, "mfu": 0.2, "platform": "tpu"}
    ))
    assert report_main([str(capture), "--baseline", str(slower)]) == 0
    out = capsys.readouterr().out
    assert "== bench capture" in out and "improved" in out
    assert report_main([str(slower), "--baseline", str(capture)]) == 3


def test_report_graceful_on_empty_and_manifest_less(tmp_path, capsys):
    """Satellite: an empty (or corrupt-only) stream exits 1 with a clear
    message — never a traceback — and a manifest-less stream still renders
    with an explicit '(no manifest record)' line."""
    from bpe_transformer_tpu.telemetry.report import main as report_main

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert report_main([str(empty)]) == 1
    err = capsys.readouterr().err
    assert "no readable records" in err and "Traceback" not in err

    corrupt = tmp_path / "corrupt.jsonl"
    corrupt.write_text("not json at all\n{truncat")
    assert report_main([str(corrupt)]) == 1

    manifestless = tmp_path / "manifestless.jsonl"
    manifestless.write_text(json.dumps({"step": 1, "loss": 2.0}) + "\n")
    assert report_main([str(manifestless)]) == 0
    assert "(no manifest record)" in capsys.readouterr().out


# ------------------------------------------------------------------ monitor


def test_monitor_fold_records_builds_operational_state():
    from bpe_transformer_tpu.telemetry.monitor import fold_records, render_frame

    state = fold_records([
        {"kind": "manifest", "run_kind": "train",
         "devices": {"count": 8, "kind": "cpu"}},
        {"step": 10, "loss": 3.0, "tokens_per_sec": 500.0, "mfu": 0.1},
        {"step": 20, "loss": 2.5, "tokens_per_sec": 600.0, "mfu": 0.12},
        {"kind": "resources", "time_unix": 0.0, "host_rss_bytes": 2**30,
         "live_buffer_bytes": 2**20, "compile_events": 4,
         "hbm_bytes_in_use": None, "hbm_peak_bytes_in_use": None,
         "hbm_bytes_limit": None},
        {"kind": "event", "name": "watchdog_hang", "t": 5.0},
        {"kind": "footer", "t": 9.0, "clean": True, "record_counts": {}},
    ])
    assert state["step"] == 20 and state["loss"] == 2.5
    assert state["host_rss_bytes"] == 2**30
    assert "hbm_bytes_in_use" not in state  # null never overwrites
    assert state["anomalies"] == 1 and state["last_anomaly"] == "watchdog_hang"
    assert state["footer_clean"] is True
    frame = render_frame(state, "test.jsonl")
    assert "step 20" in frame and "loss 2.5" in frame
    assert "rss 1,024.0 MiB" in frame
    assert "anomalies 1" in frame and "cleanly" in frame
    # Incremental fold continues from prior state (the tail path).
    state2 = fold_records([{"step": 30, "loss": 2.4}], state)
    assert state2["step"] == 30 and state2["anomalies"] == 1


def test_monitor_prometheus_roundtrip():
    """render_prometheus -> parse_prometheus -> fold_prometheus closes the
    loop: the monitor reconstructs serve state from a real scrape body."""
    from bpe_transformer_tpu.serving.metrics import (
        ServingMetrics,
        render_prometheus,
    )
    from bpe_transformer_tpu.telemetry.monitor import (
        fold_prometheus,
        parse_prometheus,
        render_frame,
    )

    m = ServingMetrics()
    m.on_submit(); m.on_submit(); m.on_reject()
    m.on_finish("length"); m.on_finish("stop")
    m.observe_phase("decode", 0.2)
    m.observe_phase("queue_wait", 0.004)
    text = render_prometheus(
        m,
        {"queue_depth": 1, "active_slots": 2, "slots": 4, "ticks": 9,
         "tokens_emitted": 55, "compiled_programs": 3},
        {"compile_events": 7, "host_rss_bytes": 2**20,
         "live_buffer_bytes": None, "hbm_bytes_in_use": None,
         "hbm_peak_bytes_in_use": None, "hbm_bytes_limit": None},
    )
    state = fold_prometheus(parse_prometheus(text))
    assert state["requests_finished"] == 2
    assert state["requests_rejected"] == 1
    assert state["queue_depth"] == 1 and state["slots"] == 4
    assert state["tokens_total"] == 55
    assert state["compile_events"] == 7
    assert "hbm_bytes_in_use" not in state  # null gauges never rendered
    frame = render_frame(state, "http://x/metrics")
    assert "slots 2/4" in frame and "queue 1" in frame and "rejected 1" in frame


def test_monitor_histogram_consistency():
    from bpe_transformer_tpu.serving.metrics import LatencyHistogram

    h = LatencyHistogram(buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    cumulative = h.cumulative()
    assert [c for _, c in cumulative] == [1, 2, 3, 4]
    assert cumulative[-1][0] == math.inf and cumulative[-1][1] == h.count == 4
    assert h.sum == pytest.approx(5.555)
    assert h.percentile(0.5) == 0.1
    assert h.percentile(1.0) == 1.0  # +Inf clamps to the last finite bound
    h.observe(float("nan"))  # ignored, not corrupted
    assert h.count == 4


def test_monitor_cli_once_smoke(tmp_path):
    """Satellite: `bpe-tpu monitor <stream> --once` renders one frame and
    exits 0 in a non-tty subprocess, without jax importable."""
    import subprocess
    import sys as _sys

    repo = Path(__file__).resolve().parent.parent
    fixture = repo / "tests" / "fixtures" / "serving_tiny.jsonl"
    proc = subprocess.run(
        [
            _sys.executable, "-c",
            "import sys; sys.modules['jax'] = None\n"
            "from bpe_transformer_tpu.telemetry.monitor import main\n"
            f"sys.exit(main([{str(fixture)!r}, '--once']))",
        ],
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(repo)},
    )
    assert proc.returncode == 0, proc.stderr
    assert "bpe-tpu monitor" in proc.stdout
    assert "requests 3" in proc.stdout

    # Usage errors are crisp: no source, or two sources.
    from bpe_transformer_tpu.telemetry.monitor import main as monitor_main

    assert monitor_main([]) == 2
    assert monitor_main(["x.jsonl", "--url", "host:1"]) == 2
    assert monitor_main([str(tmp_path / "missing.jsonl")]) == 1


def test_monitor_url_mode_against_live_endpoint(tmp_path):
    """--url mode: the monitor scrapes a real HTTP /metrics endpoint (a
    stub server rendering ServingMetrics) and folds it into a frame."""
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    from bpe_transformer_tpu.serving.metrics import (
        ServingMetrics,
        render_prometheus,
    )
    from bpe_transformer_tpu.telemetry.monitor import UrlSource

    m = ServingMetrics()
    m.on_submit()
    m.on_finish("length")
    m.observe_phase("decode", 0.1)
    body = render_prometheus(
        m, {"queue_depth": 0, "active_slots": 0, "slots": 2, "ticks": 3,
            "tokens_emitted": 12, "compiled_programs": 2},
    )

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            data = body.encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    server = HTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        source = UrlSource(f"127.0.0.1:{server.server_address[1]}")
        state = source.refresh()
        assert state["requests_finished"] == 1
        assert state["tokens_total"] == 12
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)


# -------------------------------------------------- loop integration (CPU)


HP = dict(
    max_learning_rate=1e-3,
    min_learning_rate=1e-4,
    warmup_iters=2,
    cosine_cycle_iters=50,
)


@pytest.fixture(scope="module")
def byte_data():
    text = b"the quick brown fox. " * 2000
    return np.frombuffer(text, dtype=np.uint8).astype(np.uint16)


@pytest.mark.parametrize(
    "attention_impl,path,tiles",
    [("auto", "xla", None), ("flash", "flash", [16, 16])],
)
def test_train_manifest_and_summary_name_the_attention_path(
    tmp_path, byte_data, attention_impl, path, tiles
):
    """The attention path is chosen once per compile, so its "hit share" is
    a label: the run-manifest header and the summary row both say which
    path the step holds and, on the flash path, the kernel's tiles.  (On
    the CPU "auto" always materializes; gpt2-small-32k's shape resolving to
    flash on the TPU is pinned in tests/test_kernels.py.)"""
    import dataclasses

    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    jsonl = tmp_path / "metrics.jsonl"
    loop = LoopConfig(
        steps=2, batch_size=4, log_every=2, eval_every=100,
        checkpoint_every=100, metrics_jsonl=str(jsonl),
    )
    summary = train(
        dataclasses.replace(TINY, attention_impl=attention_impl),
        TrainHParams(**HP), loop, byte_data, log_fn=lambda *_: None,
    )
    manifest = load_records(jsonl)[0]
    assert manifest["kind"] == "manifest"
    for record in (manifest, summary):
        assert record["attention_path"] == path
        assert record["flash_tiles"] == tiles
    assert np.isfinite(summary["final_train_loss"])


def test_train_emits_unified_stream_and_report_reads_it(tmp_path, byte_data):
    """The acceptance run: health stats + spans + watchdog on a short CPU
    training run produce one self-describing JSONL — manifest header, span
    records, per-layer-group grad norms, watchdog-clean footer — that
    `bpe-tpu report` summarizes."""
    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    jsonl = tmp_path / "metrics.jsonl"
    loop = LoopConfig(
        steps=8,
        batch_size=8,
        log_every=4,
        eval_every=8,
        eval_batches=1,
        checkpoint_every=100,
        metrics_jsonl=str(jsonl),
        health_stats=True,
        watchdog=True,
    )
    summary = train(
        TINY, TrainHParams(**HP), loop, byte_data, byte_data,
        log_fn=lambda *_: None,
    )
    assert np.isfinite(summary["final_train_loss"])
    records = load_records(jsonl)

    manifest = records[0]
    assert manifest["kind"] == "manifest" and manifest["run_kind"] == "train"
    assert manifest["model_config"]["d_model"] == TINY.d_model
    assert manifest["loop_config"]["health_stats"] is True

    spans = {r["path"] for r in records if r.get("kind") == "span"}
    assert {"setup", "compile_first_step"} <= spans
    assert any(p.startswith("eval") for p in spans)

    steps = [r for r in records if "kind" not in r and "loss" in r]
    assert [r["step"] for r in steps] == [4, 8]
    for r in steps:
        assert r["nonfinite_loss"] == 0
        assert r["grad_norm/attn"] > 0 and r["param_norm/ffn"] > 0
        assert r["tokens_per_sec"] > 0 and r["step_wall_s"] > 0

    # ACCEPTANCE (PR 3): the run emits kind="resources" records at every
    # log boundary with non-null host RSS (HBM fields null on CPU), at
    # zero extra host syncs — they ride the existing metric fetch.
    resources = [r for r in records if r.get("kind") == "resources"]
    assert [r["step"] for r in resources] == [4, 8]
    for r in resources:
        assert r["host_rss_bytes"] > 0
        assert isinstance(r["compile_events"], int) and r["compile_events"] >= 1
        assert "hbm_bytes_in_use" in r and "live_buffer_bytes" in r

    footer = records[-1]
    assert footer["kind"] == "footer" and footer["clean"] is True
    assert footer["watchdog_hang_events"] == 0
    assert footer["watchdog_nonfinite_events"] == 0
    # Step and val records flow through the narrator too, so the footer's
    # record_counts cross-checks the WHOLE stream (truncation detection):
    # 2 step records + 1 val record, all under the default "metric:" key.
    assert footer["record_counts"]["metric:"] == 3

    text = render_report(records)
    assert "anomalies (0)" in text and "grad_norm/attn" in text


def test_nan_injection_fires_watchdog_raise_policy(tmp_path, byte_data):
    """Synthetic NaN: an absurd LR overflows the params within a step or
    two; the health stats surface it at the next log boundary and the
    watchdog's "raise" policy dumps the record then stops the run."""
    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    jsonl = tmp_path / "metrics.jsonl"
    loop = LoopConfig(
        steps=12,
        batch_size=8,
        log_every=2,
        eval_every=100,
        checkpoint_every=100,
        metrics_jsonl=str(jsonl),
        health_stats=True,
        watchdog=True,
        watchdog_policy="raise",
    )
    hot = TrainHParams(
        max_learning_rate=1e30, min_learning_rate=1e30,
        warmup_iters=0, cosine_cycle_iters=50,
    )
    with pytest.raises(NonFiniteError):
        train(TINY, hot, loop, byte_data, log_fn=lambda *_: None)
    records = load_records(jsonl)
    events = [r for r in records if r.get("kind") == "event"]
    assert any(e["name"] == "nonfinite" for e in events)
    # The dump carries the offending record, and the footer is unclean.
    dump = next(e for e in events if e["name"] == "nonfinite")
    assert dump["fields"] and dump["record"]["step"] == dump["step"]
    footer = records[-1]
    assert footer["kind"] == "footer" and footer["clean"] is False
    assert footer["watchdog_nonfinite_events"] == 1
    # The report surfaces the whole story from the file alone.
    text = render_report(records)
    assert "nonfinite event" in text and "unclean" in text


def test_nan_injection_skip_policy_keeps_training(tmp_path, byte_data):
    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    jsonl = tmp_path / "metrics.jsonl"
    loop = LoopConfig(
        steps=6,
        batch_size=8,
        log_every=2,
        eval_every=100,
        checkpoint_every=100,
        metrics_jsonl=str(jsonl),
        health_stats=True,
        watchdog=True,
        watchdog_policy="skip",
    )
    hot = TrainHParams(
        max_learning_rate=1e30, min_learning_rate=1e30,
        warmup_iters=0, cosine_cycle_iters=50,
    )
    train(TINY, hot, loop, byte_data, log_fn=lambda *_: None)  # must not raise
    records = load_records(jsonl)
    footer = records[-1]
    assert footer["kind"] == "footer" and footer["clean"] is True
    assert footer["watchdog_nonfinite_events"] >= 1


# ----------------------------------------------- dynamics introspection


def test_dynamics_paths_labels_and_localization():
    """Pure helpers: tensor paths, layer labels, and the params -> act ->
    grads localization priority in flatten_dynamics."""
    import jax

    from bpe_transformer_tpu.telemetry.dynamics import (
        dynamics_metrics,
        flatten_dynamics,
        layer_label,
        per_layer_norms,
    )

    assert layer_label("layers.3.attn.q_proj") == "layers.3"
    assert layer_label("token_embeddings") == "token_embeddings"

    params = {
        "layers": [
            {"ffn": {"w1": jnp.ones((2, 2))}},
            {"ffn": {"w1": jnp.full((2, 2), float("nan"))}},
        ],
        "lm_head": jnp.ones((3,)),
    }
    norms = per_layer_norms(params)
    assert set(norms) == {"layers.0", "layers.1", "lm_head"}
    assert norms["layers.0"] == pytest.approx(2.0)

    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    clean = jax.tree_util.tree_map(jnp.ones_like, params)
    dyn = jax.device_get(dynamics_metrics(grads, params, clean))
    flat = flatten_dynamics(dyn)
    # The NaN lives in the step's INPUT params; only nonzero counts emit.
    assert flat["nonfinite_params/layers.1.ffn.w1"] == 4
    assert flat["first_nonfinite"] == "params/layers.1.ffn.w1"
    assert not any(k.startswith("nonfinite_grads/") for k in flat)
    assert flat["update_ratio/layers.0"] >= 0

    # Clean trees carry no localization keys at all.
    flat_clean = flatten_dynamics(
        jax.device_get(dynamics_metrics(grads, clean, clean))
    )
    assert "first_nonfinite" not in flat_clean
    assert not any(k.startswith("nonfinite_") for k in flat_clean)

    # Activation localization outranks gradients (the finite-params,
    # overflowing-activation scenario) but not params.
    act = {
        "rms": jnp.ones((2,)),
        "absmax": jnp.ones((2,)),
        "nonfinite": jnp.array([0, 7], jnp.int32),
        "attn_entropy": jnp.ones((2,)),
    }
    bad_grads = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, float("inf")), params
    )
    flat_act = flatten_dynamics(
        jax.device_get(dynamics_metrics(bad_grads, clean, clean, act))
    )
    assert flat_act["first_nonfinite"] == "act/layers.1"
    assert flat_act["act_nonfinite/layers.1"] == 7
    assert flat_act["attn_entropy/layers.0"] == pytest.approx(1.0)


def test_dynamics_enabled_train_step_exports_per_layer_stats():
    import jax

    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.telemetry.dynamics import flatten_dynamics
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_train_step,
    )

    params = init_params(jax.random.PRNGKey(0), TINY)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, TINY.vocab_size, size=(4, TINY.context_length))
    x, y = jnp.asarray(ids), jnp.asarray(np.roll(ids, -1, axis=1))

    # Default step: no dynamics key, metrics unchanged.
    _, _, metrics = make_train_step(TINY, TrainHParams())(
        params, adamw_init(params), x, y
    )
    assert "dynamics" not in metrics

    params = init_params(jax.random.PRNGKey(0), TINY)
    step = make_train_step(TINY, TrainHParams(), dynamics=True)
    _, _, metrics = step(params, adamw_init(params), x, y)
    flat = flatten_dynamics(jax.device_get(metrics["dynamics"]))
    for layer in ("layers.0", "layers.1", "token_embeddings", "lm_head"):
        assert flat[f"grad_norm/{layer}"] > 0
        assert flat[f"param_norm/{layer}"] > 0
        assert flat[f"update_ratio/{layer}"] >= 0
    for i in range(TINY.num_layers):
        assert math.isfinite(flat[f"act_rms/layers.{i}"])
        assert flat[f"act_absmax/layers.{i}"] > 0
        # Causal softmax entropy over a 16-token context: strictly inside
        # (0, log 16].
        assert 0 < flat[f"attn_entropy/layers.{i}"] <= math.log(16) + 1e-5
    assert "first_nonfinite" not in flat  # clean run


@pytest.mark.slow
def test_dynamics_rides_scanned_and_grad_accum_variants():
    import jax

    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.telemetry.dynamics import flatten_dynamics
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_grad_accum_train_step,
        make_scanned_train_step,
    )

    hp = TrainHParams(warmup_iters=0)
    params = init_params(jax.random.PRNGKey(0), TINY)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, TINY.vocab_size, size=(2, 4, TINY.context_length))
    xs, ys = jnp.asarray(ids), jnp.asarray(np.roll(ids, -1, axis=2))

    step = make_scanned_train_step(TINY, hp, 2, dynamics=True)
    _, _, metrics = step(params, adamw_init(params), xs, ys)
    flat = flatten_dynamics(jax.device_get(metrics["dynamics"]))
    assert flat["grad_norm/layers.1"] > 0
    assert flat["attn_entropy/layers.0"] > 0  # act taps ride the scan body

    params = init_params(jax.random.PRNGKey(0), TINY)
    step = make_grad_accum_train_step(TINY, hp, 2, dynamics=True)
    _, _, metrics = step(params, adamw_init(params), xs, ys)
    flat = flatten_dynamics(jax.device_get(metrics["dynamics"]))
    assert flat["grad_norm/layers.1"] > 0
    assert flat["update_ratio/layers.0"] > 0
    # The accumulation scan carries loss+grads, not activation taps.
    assert not any(k.startswith(("act_rms/", "attn_entropy/")) for k in flat)


def test_dynamics_record_validates_against_schema():
    from bpe_transformer_tpu.telemetry import validate_record
    from bpe_transformer_tpu.telemetry.dynamics import dynamics_record

    record = dynamics_record(
        50, {"grad_norm/layers.0": 0.5, "first_nonfinite": "params/x"}
    )
    assert record["kind"] == "dynamics" and record["step"] == 50
    assert validate_record(record) == []
    assert validate_record({"kind": "dynamics"})  # step is required


def test_dynamics_every_validation():
    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    data = np.zeros(10_000, np.uint16)
    loop = LoopConfig(steps=2, batch_size=8, log_every=2, dynamics_every=3)
    with pytest.raises(ValueError, match="multiple of log_every"):
        train(TINY, TrainHParams(**HP), loop, data)
    loop = LoopConfig(
        steps=2, batch_size=8, parallel="sp", dynamics_every=2, log_every=2
    )
    with pytest.raises(ValueError, match="dynamics_every"):
        train(TINY, TrainHParams(**HP), loop, data)
    with pytest.raises(ValueError, match=">= 0"):
        train(
            TINY, TrainHParams(**HP),
            LoopConfig(steps=2, batch_size=8, dynamics_every=-1), data,
        )


def test_health_stats_rejected_for_sp_and_pp():
    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    loop = LoopConfig(steps=2, batch_size=8, parallel="sp", health_stats=True)
    with pytest.raises(ValueError, match="health_stats"):
        train(TINY, TrainHParams(**HP), loop, np.zeros(10_000, np.uint16))


def test_bad_watchdog_policy_rejected_before_sinks_open(tmp_path):
    """An invalid policy must fail fast — before the metrics JSONL (or a
    wandb run) is opened, so nothing leaks."""
    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    jsonl = tmp_path / "metrics.jsonl"
    loop = LoopConfig(
        steps=2, batch_size=8, metrics_jsonl=str(jsonl),
        watchdog=True, watchdog_policy="warn",
    )
    with pytest.raises(ValueError, match="watchdog_policy"):
        train(TINY, TrainHParams(**HP), loop, np.zeros(10_000, np.uint16))
    assert not jsonl.exists()


# ------------------------------------------- dynamics: loop integration


def _counting_train(monkeypatch, byte_data, tmp_path, dynamics_every):
    """Run a short training with jax.device_get / block_until_ready call
    counting; returns (records, counts)."""
    import jax

    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    counts = {"device_get": 0, "block_until_ready": 0}
    real_get, real_block = jax.device_get, jax.block_until_ready

    def counting_get(x):
        counts["device_get"] += 1
        return real_get(x)

    def counting_block(x):
        counts["block_until_ready"] += 1
        return real_block(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    jsonl = tmp_path / f"dyn_{dynamics_every}.jsonl"
    loop = LoopConfig(
        steps=8,
        batch_size=8,
        log_every=4,
        eval_every=100,
        checkpoint_every=100,
        metrics_jsonl=str(jsonl),
        dynamics_every=dynamics_every,
    )
    train(TINY, TrainHParams(**HP), loop, byte_data, log_fn=lambda *_: None)
    monkeypatch.setattr(jax, "device_get", real_get)
    monkeypatch.setattr(jax, "block_until_ready", real_block)
    return load_records(jsonl), counts


def test_dynamics_loop_emits_records_at_zero_extra_fetches(
    monkeypatch, tmp_path, byte_data
):
    """ACCEPTANCE: with --dynamics-every the stream gains kind="dynamics"
    records at the dynamics cadence — and the number of device fetches /
    sync barriers is IDENTICAL to a run with the flag off (the dynamics
    pytree rides the existing log-cadence fetch)."""
    from bpe_transformer_tpu.telemetry import validate_record

    records_off, counts_off = _counting_train(
        monkeypatch, byte_data, tmp_path, dynamics_every=0
    )
    records_on, counts_on = _counting_train(
        monkeypatch, byte_data, tmp_path, dynamics_every=4
    )
    assert counts_on == counts_off  # zero additional device→host syncs

    dynamics = [r for r in records_on if r.get("kind") == "dynamics"]
    assert [r["step"] for r in dynamics] == [4, 8]
    for r in dynamics:
        assert validate_record(r) == []
        assert r["grad_norm/layers.0"] > 0
        assert r["attn_entropy/layers.1"] > 0
        assert "first_nonfinite" not in r  # clean run

    # Flag off: no dynamics records, and the step records carry no
    # dynamics-derived keys — the schema is byte-identical to before.
    assert not [r for r in records_off if r.get("kind") == "dynamics"]
    steps_off = [r for r in records_off if "kind" not in r and "loss" in r]
    dyn_prefixes = (
        "update_ratio/", "act_rms/", "act_absmax/", "attn_entropy/",
        "nonfinite_params/", "nonfinite_grads/", "act_nonfinite/",
    )
    for r in steps_off:
        assert not any(k.startswith(dyn_prefixes) for k in r)
        assert "nonfinite_path" not in r


def test_dynamics_localizes_nan_seeded_layer(tmp_path, byte_data):
    """ACCEPTANCE: a run whose params are seeded with a NaN in layer 1's
    ffn.w1 produces a watchdog nonfinite event AND a report callout naming
    that tensor path — the documented forensic workflow (resume from a
    checkpoint at --dynamics-every 1 --log-every 1)."""
    import jax

    from bpe_transformer_tpu.checkpointing import save_checkpoint
    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    params = init_params(jax.random.PRNGKey(0), TINY)
    w1 = np.asarray(params["layers"][1]["ffn"]["w1"]).copy()
    w1[0, 0] = np.nan
    params["layers"][1]["ffn"]["w1"] = jnp.asarray(w1)
    ckpt = tmp_path / "nan.ckpt"
    save_checkpoint(ckpt, params=params, opt_state=adamw_init(params), iteration=0)

    jsonl = tmp_path / "nan.jsonl"
    loop = LoopConfig(
        steps=4,
        batch_size=8,
        log_every=1,
        eval_every=100,
        checkpoint_every=100,
        metrics_jsonl=str(jsonl),
        dynamics_every=1,
        watchdog=True,
        watchdog_policy="raise",
    )
    with pytest.raises(NonFiniteError, match=r"params/layers\.1\.ffn\.w1"):
        train(
            TINY, TrainHParams(**HP), loop, byte_data,
            resume_from=ckpt, log_fn=lambda *_: None,
        )
    records = load_records(jsonl)
    event = next(
        r for r in records if r.get("kind") == "event" and r["name"] == "nonfinite"
    )
    assert event["path"] == "params/layers.1.ffn.w1"
    dynamics = [r for r in records if r.get("kind") == "dynamics"]
    assert dynamics[0]["first_nonfinite"] == "params/layers.1.ffn.w1"
    assert dynamics[0]["nonfinite_params/layers.1.ffn.w1"] == 1
    text = render_report(records)
    assert "localized to params/layers.1.ffn.w1" in text


# ------------------------------------- dynamics: fixture, report, monitor


def test_report_dynamics_fixture_pins_section_and_compare(capsys):
    """The committed dynamics_tiny.jsonl pins the report Dynamics section
    (per-layer table + localization callout) and still feeds the --compare
    gate; a stream with NO dynamics records renders no section and exits
    cleanly."""
    from bpe_transformer_tpu.telemetry.report import main as report_main

    fixture = str(FIXTURES / "dynamics_tiny.jsonl")
    assert report_main([fixture]) == 0
    out = capsys.readouterr().out
    assert "== dynamics (2 records, steps 50..100) ==" in out
    assert "layers.0" in out and "layers.1" in out
    assert "! first non-finite: params/layers.1.ffn.w1 at step 100" in out
    assert "nonfinite event at step 100 localized to params/layers.1.ffn.w1" in out

    # Self-compare: shared metrics, zero delta, exit 0.
    assert report_main([fixture, "--compare", fixture]) == 0
    assert "no regressions" in capsys.readouterr().out

    # A dynamics-free stream: clean exit, no Dynamics section.
    plain = str(FIXTURES / "telemetry_tiny.jsonl")
    assert report_main([plain]) == 0
    assert "== dynamics" not in capsys.readouterr().out


def test_monitor_once_renders_dynamics_table(tmp_path):
    """Satellite: `bpe-tpu monitor <dynamics stream> --once` renders the
    per-layer table without jax importable."""
    import subprocess
    import sys as _sys

    repo = Path(__file__).resolve().parent.parent
    fixture = repo / "tests" / "fixtures" / "dynamics_tiny.jsonl"
    proc = subprocess.run(
        [
            _sys.executable, "-c",
            "import sys; sys.modules['jax'] = None\n"
            "from bpe_transformer_tpu.telemetry.monitor import main\n"
            f"sys.exit(main([{str(fixture)!r}, '--once']))",
        ],
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(repo)},
    )
    assert proc.returncode == 0, proc.stderr
    assert "per-layer introspection (step 100)" in proc.stdout
    assert "layers.0" in proc.stdout and "token_embeddings" in proc.stdout
    assert "nonfinite params/layers.1.ffn.w1" in proc.stdout or "anomalies" in proc.stdout


# --------------------------------------------------- chrome trace export


def test_trace_events_spans_and_counters():
    from bpe_transformer_tpu.telemetry.trace import trace_events

    records = [
        {"kind": "manifest", "run_kind": "train",
         "time_utc": "2026-08-03T00:00:00+00:00"},
        {"kind": "span", "name": "setup", "path": "setup", "t": 0.0,
         "dur_s": 1.0},
        {"kind": "span", "name": "resume", "path": "setup/resume", "t": 0.2,
         "dur_s": 0.5, "step": 3},
        {"kind": "engine", "t": 2.0, "active_slots": 3, "queue_depth": 1,
         "tokens_per_sec": 500.0, "tokens_total": 10, "ticks": 5,
         "requests_finished": 2, "compiled_programs": 4},
        {"kind": "resources", "time_unix": 1785542402.5,
         "host_rss_bytes": 2**30, "live_buffer_bytes": None,
         "compile_events": 7, "hbm_bytes_in_use": None,
         "hbm_peak_bytes_in_use": None, "hbm_bytes_limit": None},
    ]
    events = trace_events(records)
    spans = [e for e in events if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["setup", "resume"]
    # Distinct paths get distinct named lanes; attrs ride through as args.
    assert spans[0]["tid"] != spans[1]["tid"]
    assert spans[1]["args"] == {"step": 3}
    assert spans[1]["ts"] == pytest.approx(0.2e6) and spans[1]["dur"] == pytest.approx(0.5e6)
    names = {
        e["args"]["name"]
        for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {"setup", "setup/resume"} <= names

    counters = {e["name"]: e for e in events if e["ph"] == "C"}
    assert counters["engine"]["args"]["tokens_per_sec"] == 500.0
    assert counters["engine"]["ts"] == pytest.approx(2e6)
    # resources re-based against the manifest's time_utc: the fixture
    # sample is 2.5 s after the 2026-08-03T00:00:00+00:00 epoch... which is
    # seconds-since-epoch arithmetic — just pin non-negativity and args.
    assert counters["resources"]["ts"] >= 0
    assert counters["resources"]["args"] == {
        "host_rss_bytes": 2**30, "compile_events": 7,
    }


def test_report_trace_cli_writes_chrome_trace(tmp_path, capsys):
    from bpe_transformer_tpu.telemetry.report import main as report_main

    fixture = str(FIXTURES / "dynamics_tiny.jsonl")
    out = tmp_path / "trace.json"
    assert report_main([fixture, "--trace", str(out)]) == 0
    assert "wrote" in capsys.readouterr().out
    payload = json.loads(out.read_text())
    assert payload["traceEvents"]
    kinds = {e["ph"] for e in payload["traceEvents"]}
    assert "X" in kinds and "C" in kinds

    # --trace on a bench capture (not a stream) is a crisp usage error.
    capture = tmp_path / "cap.json"
    capture.write_text(json.dumps({"metric": "tok/s", "value": 1.0}))
    assert report_main([str(capture), "--trace", str(tmp_path / "t.json")]) == 2


# ------------------------------------------- attribution: cost model, probe


def test_time_call_and_program_cost_cpu_smoke():
    """The shared measurement path (telemetry.attribution): XLA
    cost_analysis of an AOT-compiled program yields positive flops/bytes
    on CPU too (the cost model is tier-1-testable), and time_call returns
    a positive mean ms."""
    import jax

    from bpe_transformer_tpu.telemetry.attribution import (
        program_cost,
        time_call,
    )

    def f(a, b):
        return (a @ b).sum()

    x = jnp.ones((64, 128))
    y = jnp.ones((128, 32))
    compiled = jax.jit(f).lower(x, y).compile()
    cost = program_cost(compiled)
    assert cost["flops"] and cost["flops"] > 0
    assert cost["bytes_accessed"] and cost["bytes_accessed"] > 0
    assert time_call(compiled, x, y, iters=2, warmup=1) > 0


def test_roofline_verdicts_and_unknown_device():
    from bpe_transformer_tpu.telemetry.attribution import roofline

    # TPU v4: peak 275 TF/s over 1228 GB/s -> ridge ~223.9 flops/byte.
    high = roofline(1e12, 1e9, "TPU v4", name="matmul")  # AI 1000
    low = roofline(1e9, 1e9, "TPU v4", name="gather")  # AI 1
    assert high["bound"] == "compute-bound"
    assert low["bound"] == "memory-bound"
    assert high["ridge_flops_per_byte"] == pytest.approx(223.9, abs=0.1)
    # No peak-table entry (CPU): intensity still reported, verdict honest.
    unknown = roofline(1e12, 1e9, "cpu")
    assert unknown["bound"] == "unknown"
    assert unknown["arithmetic_intensity"] == 1000.0
    # Degenerate counters: no crash, no fake verdict.
    assert roofline(None, None, "TPU v4")["bound"] == "unknown"


def test_peak_tables_and_warn_once_on_unknown_kind():
    import warnings

    from bpe_transformer_tpu.utils import flops as flops_mod

    assert flops_mod.peak_flops_per_chip("TPU v5p") == 459e12
    assert flops_mod.peak_flops_per_chip("TPU v6e") == 918e12
    assert flops_mod.peak_hbm_bytes_per_sec("TPU v4") == 1228e9
    # Unknown TPU generation: None + exactly ONE warning per kind (a
    # silent None quietly disables MFU/roofline for the whole run).
    flops_mod._warned_unknown_kinds.discard("TPU v99")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert flops_mod.peak_flops_per_chip("TPU v99") is None
        assert flops_mod.peak_flops_per_chip("TPU v99") is None
    assert len([w for w in caught if "TPU v99" in str(w.message)]) == 1
    # CPU/GPU backends are not TPU generations — no warning noise there.
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert flops_mod.peak_flops_per_chip("cpu") is None
    assert not caught


def test_attribution_every_validation():
    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    data = np.zeros(10_000, np.uint16)
    with pytest.raises(ValueError, match="attribution_every"):
        train(
            TINY, TrainHParams(**HP),
            LoopConfig(steps=2, batch_size=8, attribution_every=-1),
            data,
        )
    with pytest.raises(ValueError, match="multiple of log_every"):
        train(
            TINY, TrainHParams(**HP),
            LoopConfig(
                steps=4, batch_size=8, log_every=4, attribution_every=3
            ),
            data,
        )


def _counting_attr_train(monkeypatch, byte_data, tmp_path, attribution_every):
    """Like _counting_train, parameterized on attribution_every."""
    import jax

    from bpe_transformer_tpu.training import LoopConfig, TrainHParams, train

    counts = {"device_get": 0, "block_until_ready": 0}
    real_get, real_block = jax.device_get, jax.block_until_ready

    def counting_get(x):
        counts["device_get"] += 1
        return real_get(x)

    def counting_block(x):
        counts["block_until_ready"] += 1
        return real_block(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    jsonl = tmp_path / f"attr_{attribution_every}.jsonl"
    loop = LoopConfig(
        steps=8,
        batch_size=8,
        log_every=4,
        eval_every=100,
        checkpoint_every=100,
        metrics_jsonl=str(jsonl),
        attribution_every=attribution_every,
    )
    train(TINY, TrainHParams(**HP), loop, byte_data, log_fn=lambda *_: None)
    monkeypatch.setattr(jax, "device_get", real_get)
    monkeypatch.setattr(jax, "block_until_ready", real_block)
    return load_records(jsonl), counts


@pytest.mark.slow
def test_attribution_loop_emits_records_at_bounded_fetch_cost(
    monkeypatch, tmp_path, byte_data
):
    """ACCEPTANCE: --attribution-every emits kind="attribution" records
    whose compute+collective+host fractions sum to ~1.0 — and the ONLY
    extra host syncs vs a plain run are the probe's own fenced timings at
    the single attribution boundary (StepProbe.FETCHES_PER_MEASURE per
    timed variant); untouched steps pay zero."""
    from bpe_transformer_tpu.telemetry import validate_record
    from bpe_transformer_tpu.telemetry.attribution import StepProbe

    records_off, counts_off = _counting_attr_train(
        monkeypatch, byte_data, tmp_path, attribution_every=0
    )
    records_on, counts_on = _counting_attr_train(
        monkeypatch, byte_data, tmp_path, attribution_every=8
    )
    # One boundary (step 8), one single-device variant -> exactly
    # FETCHES_PER_MEASURE extra value fetches; no extra sync barriers.
    assert counts_on["device_get"] == (
        counts_off["device_get"] + StepProbe.FETCHES_PER_MEASURE
    )
    assert counts_on["block_until_ready"] == counts_off["block_until_ready"]

    attributions = [
        r for r in records_on if r.get("kind") == "attribution"
    ]
    assert [r["step"] for r in attributions] == [8]
    record = attributions[0]
    assert validate_record(record) == []
    total = (
        record["compute_frac"]
        + (record["collective_frac"] or 0.0)
        + record["host_gap_frac"]
    )
    assert total == pytest.approx(1.0, abs=0.02)
    assert record["device_step_s"] > 0
    # Single device: the collective split is exactly zero, not null.
    assert record["collective_frac"] == 0.0
    # The first record carries the static cost-model rows.
    programs = record["programs"]
    assert programs and programs[0]["name"] == "train_step"
    assert programs[0]["flops"] > 0
    assert programs[0]["bound"] in (
        "compute-bound", "memory-bound", "unknown"
    )
    # The probe's compile+measure time is spanned (and thus excluded from
    # the throughput window by the loop).
    assert any(
        r.get("kind") == "span" and r.get("name") == "attribution_probe"
        for r in records_on
    )
    # Flag off: no attribution records at all.
    assert not [r for r in records_off if r.get("kind") == "attribution"]


# ------------------------------- attribution: fixture, report, monitor, trace


def test_report_attribution_fixture_pins_section_and_compare(
    tmp_path, capsys
):
    """The committed attribution_tiny.jsonl pins the report's attribution
    section (step-time split, MFU ceiling, per-program roofline verdicts)
    and feeds the --compare gate: a stream whose collective_frac grew
    regresses with exit 3."""
    from bpe_transformer_tpu.telemetry.report import main as report_main

    fixture = str(FIXTURES / "attribution_tiny.jsonl")
    assert report_main([fixture]) == 0
    out = capsys.readouterr().out
    assert "== attribution (2 records, steps 50..100) ==" in out
    assert "compute 64.0%" in out
    assert "collective 10.5%" in out
    assert "host gap 25.5%" in out
    assert "mfu 0.13 -> 0.197 ceiling" in out
    assert "train_step" in out and "compute-bound" in out
    assert "decode_tick[8]" in out and "memory-bound" in out

    # Self-compare: shared metrics (incl. the new fraction gates), exit 0.
    assert report_main([fixture, "--compare", fixture]) == 0
    out = capsys.readouterr().out
    assert "collective_frac" in out and "host_gap_frac" in out
    assert "no regressions" in out

    # A stream whose collective fraction doubled: gate trips (exit 3).
    regressed = tmp_path / "attr_regressed.jsonl"
    regressed.write_text(
        Path(fixture).read_text()
        .replace('"collective_frac": 0.11', '"collective_frac": 0.3')
        .replace('"collective_frac": 0.1,', '"collective_frac": 0.28,')
    )
    assert report_main([str(regressed), "--compare", fixture]) == 3
    assert "collective_frac" in capsys.readouterr().out


def test_monitor_folds_attribution_into_live_state():
    from bpe_transformer_tpu.telemetry.monitor import (
        fold_records,
        render_frame,
    )

    records = load_records(FIXTURES / "attribution_tiny.jsonl")
    state = fold_records(records)
    assert state["compute_frac"] == 0.66  # latest record wins
    assert state["collective_frac"] == 0.1
    assert state["host_gap_frac"] == 0.24
    assert state["attribution_step"] == 100
    assert state["bound_verdict"] == "train_step compute-bound"
    frame = render_frame(state, "fixture")
    assert "attr" in frame
    assert "compute 66%" in frame
    assert "[train_step compute-bound]" in frame


def test_trace_attribution_counters_and_request_lanes(tmp_path):
    """The Chrome trace export grows an attribution counter track, and
    serving spans carrying a request_id land in per-request lanes (one
    queue->prefill->decode timeline per request)."""
    from bpe_transformer_tpu.telemetry.trace import trace_events

    events = trace_events(load_records(FIXTURES / "attribution_tiny.jsonl"))
    counters = [
        e for e in events if e.get("ph") == "C" and e["name"] == "attribution"
    ]
    assert len(counters) == 2
    assert counters[0]["args"]["compute_frac"] == 0.62
    assert counters[-1]["args"]["host_gap_frac"] == 0.24

    events = trace_events(load_records(FIXTURES / "serving_tiny.jsonl"))
    lanes = {
        e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert "request/req-a" in lanes and "request/req-b" in lanes
    # All three phases of req-a share its lane (a per-request timeline).
    tid_by_lane = {
        e["args"]["name"]: e["tid"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    req_a_spans = [
        e
        for e in events
        if e.get("ph") == "X" and e.get("tid") == tid_by_lane["request/req-a"]
    ]
    assert {e["name"] for e in req_a_spans} == {
        "queue_wait", "prefill", "decode"
    }

    # Lane cap: a long serving stream must not explode into one Perfetto
    # row per request — beyond _MAX_REQUEST_LANES distinct ids the spans
    # fall back to the shared phase lanes.
    from bpe_transformer_tpu.telemetry.trace import _MAX_REQUEST_LANES

    many = [
        {"kind": "span", "name": "decode", "path": "serve/decode",
         "t": i * 0.01, "dur_s": 0.005, "request_id": f"req-{i:04d}"}
        for i in range(_MAX_REQUEST_LANES + 20)
    ]
    lanes = {
        e["args"]["name"]
        for e in trace_events(many)
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    req_lanes = {l for l in lanes if l.startswith("request/")}
    assert len(req_lanes) == _MAX_REQUEST_LANES
    assert "serve/decode" in lanes  # overflow kept the shared phase lane


def test_report_serving_total_p99_and_dominant_phase(capsys):
    """The serving section attributes tail latency to a phase: total
    request p50/p95/p99 assembled from the request_id-tagged spans, with
    the slow tail's dominant phase named."""
    from bpe_transformer_tpu.telemetry.report import main as report_main

    records = load_records(FIXTURES / "serving_tiny.jsonl")
    serving = summarize(records)["serving"]
    assert serving["requests_traced"] == 3
    assert serving["total"]["p99_s"] is not None
    assert serving["slow_dominant_phase"] == "decode"
    assert serving["phases"]["decode"]["p99_s"] is not None

    assert report_main([str(FIXTURES / "serving_tiny.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "slow tail dominated by decode" in out


@pytest.mark.slow
def test_profile_cli_smoke(tmp_path, capsys):
    """ACCEPTANCE (CPU degraded mode): bpe-tpu profile runs the cost model
    + measured split end to end on CPU, writes a schema-valid attribution
    stream, and the report renders its section."""
    from bpe_transformer_tpu.telemetry import validate_record
    from bpe_transformer_tpu.telemetry.report import main as report_main
    from bpe_transformer_tpu.training.cli import main as cli_main

    stream = tmp_path / "profile.jsonl"
    rc = cli_main(
        [
            "profile", "--preset", "ts-test", "--batch", "2",
            "--measure", "1", "--serve", "--slots", "2",
            "--metrics-jsonl", str(stream), "--json",
        ]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "== cost model" in out and "train_step" in out
    assert "prefill[16]" in out and "decode_tick[2]" in out
    assert "== measured split" in out

    records = load_records(stream)
    attribution = next(
        r for r in records if r.get("kind") == "attribution"
    )
    assert validate_record(attribution) == []
    total = (
        attribution["compute_frac"]
        + (attribution["collective_frac"] or 0.0)
        + attribution["host_gap_frac"]
    )
    assert total == pytest.approx(1.0, abs=0.02)
    # The stream is a real telemetry stream: manifest + footer + report.
    assert any(r.get("kind") == "manifest" for r in records)
    assert any(r.get("kind") == "footer" for r in records)
    assert report_main([str(stream)]) == 0
    assert "== attribution" in capsys.readouterr().out
