"""``granite.serve.chat-short`` (ISSUE 36): the configuration file against
the catalog's numbers and the issue's arithmetic, the counts, the traffic,
a CPU rehearsal of the cell at tiny sizes through ``run_cell``, the four
new metric files, and that every file the benchmark had is as it was."""

import json
import subprocess

import pytest

from chipbench import counts_granitehybrid as counts
from chipbench import layer_metrics, run
from chipbench.tests.tiny import BENCH, metrics_of_cell

CELL = "granite.serve.chat-short"
PARENT = "bb6b2e84b105133db8ac1adf3d29a9c24876b681"
NEW_METRICS = [
    "ssm.chunk_fill_share.mean", "ssm_state_update_roofline", "granite.gmm_roofline",
    "ssm.state_share.tick",
]
NEW_FILES = {
    "chipbench/configs/granite-4.0-h-small.json", f"chipbench/workloads/{CELL}.json",
    "chipbench/reference_granitehybrid.py", "chipbench/counts_granitehybrid.py",
    "chipbench/tests/test_granitehybrid_cell.py",
    *(f"chipbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_key_value_heads": 8,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
}


def load():
    return run.load_cell(CELL)


def tiny_cell():
    """The cell's files cut to a hidden size of 64: 8 state-space heads of
    16 with a state of 16, chunks of 8; 4 attention heads over 2 KV heads;
    12 experts of 16 of which 6 are held, 3 a token, a shared expert of 32;
    one period of 3 layers, attention second; blocks of 4, chunks of 8."""
    workload, config = load()
    config.update(
        hidden_size=64, d_model=64, intermediate_size=16, d_ff=16, expert_d_ff=16,
        shared_intermediate_size=32, shared_d_ff=32, num_attention_heads=4, num_heads=4,
        num_key_value_heads=2, num_kv_heads=2, mamba_n_heads=8, ssm_heads=8,
        mamba_d_head=16, ssm_head_dim=16, mamba_d_state=16, ssm_state=16,
        mamba_chunk_size=8, ssm_chunk=8, num_local_experts=6, experts_held=6,
        n_experts=12, num_experts_per_tok=3, router_top_k=3, num_hidden_layers=3,
        num_layers=3, layer_types=["mamba", "attention", "mamba"], attn_layer_period=3,
        attn_layer_offset=1, vocab_size=512, context_length=64,
        activation_dtype="float32",
    )
    workload["serve"]["engine"].update(
        slots=4, block_size=4, prefill_chunk=8, prefill_token_budget=8,
        prefill_buckets=[4, 8], num_kv_blocks=None,
    )
    workload["serve"].update(warm_buckets=[4, 8], ramp_s=0.5)
    workload["traffic"]["arrival"].update(clients=4, stagger_s=0.2)
    workload["traffic"]["prompt_len"].update(lo=3, hi=24)
    workload["traffic"]["output_len"].update(lo=4, hi=20)
    # Every second request greedy: four clients finish a handful of requests
    # in the twin's seconds, and `correct` needs a greedy one among them.
    workload["traffic"].update(max_total=60, n_sizes=16, closed_plan=64, greedy_every=2)
    workload["trace_seconds"] = 1.0
    # The limit's tiny twin: float32 against float32 reads rounding error.
    workload["correct"]["served_logit_gap"] = 1e-5
    return workload, config


def test_counts_pin_the_issues_numbers():
    _, cfg = load()
    assert counts.mamba_params(cfg) == pytest.approx(102.29e6, rel=1e-4)
    assert counts.attention_params(cfg) == pytest.approx(41.94e6, rel=1e-4)
    assert counts.shared_params(cfg) == pytest.approx(18.87e6, rel=1e-3)
    assert counts.router_params(cfg) == pytest.approx(0.29e6, rel=2e-2)
    assert counts.layer_params_outside_experts(cfg, "mamba") == pytest.approx(121.46e6, rel=1e-4)
    assert counts.layer_params_outside_experts(cfg, "attention") == pytest.approx(61.12e6, rel=1e-4)
    assert counts.expert_params(cfg) == pytest.approx(9.437e6, rel=1e-4)
    assert (counts.mamba_layers(cfg), counts.attention_layers(cfg)) == (9, 1)
    assert counts.params_held(cfg) == pytest.approx(4.757e9, rel=1e-3)
    assert counts.matmul_weight_bytes(cfg) == pytest.approx(9.51e9, rel=1e-3)
    assert counts.kv_bytes_per_token(cfg) == 4096
    assert counts.state_bytes_per_slot(cfg) == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert counts.state_bytes_per_slot(cfg) == pytest.approx(37.75e6 + 0.46e6, rel=1e-3)
    assert counts.held_experts_per_token(cfg) == 5
    # Published model from the same arithmetic: 32.2B, 8.8B active.
    outside = (36 * counts.layer_params_outside_experts(cfg, "mamba")
               + 4 * counts.layer_params_outside_experts(cfg, "attention"))
    assert outside / 40 == pytest.approx(115.4e6, rel=1e-3)
    assert outside + 40 * 72 * counts.expert_params(cfg) + 100352 * 4096 == pytest.approx(32.2e9, rel=2e-3)
    assert outside + 40 * 10 * counts.expert_params(cfg) + 100352 * 4096 == pytest.approx(8.8e9, rel=3e-3)
    # The kernels' functions say what the metric files spell out.
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert "8388608 * d_ssm_tick_state_rows" in specs["ssm_state_update_roofline"]["reader"]["expr"]
    assert counts.ssm_state_update_bytes(cfg, 3) == 8388608 * 3
    assert "6 * 4096 * 768 * d_moe_rows_local" in specs["granite.gmm_roofline"]["reader"]["expr"]
    assert counts.gmm_flops(cfg, 5) == counts.gmm_bytes(cfg, 5) == 6 * 4096 * 768 * 5
    # A decoded token at context c: one attention layer of 32 heads x 128.
    near, far = (counts.forward_flops(cfg, 1, c, 1) for c in (300, 3000))
    assert far - near == pytest.approx(4 * 32 * 128 * 2700)


def test_configuration_file_holds_the_published_numbers():
    _, cfg = load()
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["layer_types"] == (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size", "context_length"]
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"], cfg["vocab_size"]) == (10, 36, 50176)
    assert cfg["published"]["num_hidden_layers"] == 40 and cfg["published"]["num_local_experts"] == 72
    assert cfg["published"]["vocab_size"] == 100352 and "2 chips share each layer" in cfg["deployment"]
    assert "8 chips" in cfg["deployment"]
    assert {"intermediate_size", "time_step_limit", "gated_norm", "state_precision",
            "seeded_values", "shared_expert", "routing", "precision"} <= set(cfg["assumed"])
    # The program's names repeat the published widths.
    model = run.program_model_config(cfg)
    assert (model.d_model, model.num_heads, model.num_kv_heads, model.d_head) == (4096, 32, 8, 128)
    assert (model.ssm_heads, model.ssm_head_dim, model.ssm_state) == (128, 64, 128)
    assert (model.ssm_inner, model.ssm_conv_channels, model.ssm_conv, model.ssm_chunk) == (8192, 8448, 4, 256)
    assert [i for i in range(10) if not model.layer_is_ssm(i)] == [5]
    assert (model.moe_d_ff, model.shared_ff, model.router_outputs) == (768, 1536, 72)
    assert (model.n_experts, model.router_top_k, model.local_experts) == (72, 10, 36)
    assert (model.embedding_multiplier, model.residual_multiplier) == (12, 0.22)
    assert (model.attention_scale, model.logits_scaling) == (1 / 128, 16)
    assert model.hybrid_block and model.tie_embeddings and not model.layer_rope(5)
    declared = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in declared["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = [w for w in declared["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    listed = {m["name"] for m in declared["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == metrics_of_cell(CELL)
    judged = {m["name"] for m in declared["end_to_end"] if CELL in m.get("workloads", ())}
    assert judged | {"setup_s"} == set(json.loads(
        (BENCH / "workloads" / f"{CELL}.json").read_text())["end_to_end"])


def test_traffic_is_the_issues():
    workload, _ = load()
    from chipbench import traffic

    sizes = traffic.request_sizes(workload["traffic"])
    assert len(sizes) == 32 and sizes[:, 0].min() >= 128 and sizes[:, 0].max() <= 2048
    assert sizes[:, 1].min() >= 128 and sizes[:, 1].max() <= 1024
    assert (sizes.sum(axis=1) <= 3072).all()
    plan = traffic.plan_requests(workload["traffic"], 50176, 2**31 + 36, 60.0)
    assert len(plan) == 1024 and sum(p.greedy for p in plan) == 128  # every eighth
    assert len({p.prompt_ids[:64] for p in plan[:40]}) == 40           # no shared prefix
    assert workload["trace_seconds"] == 3.0
    assert (workload["serve"]["ramp_s"], workload["traffic"]["arrival"]["stagger_s"]) == (20.0, 12.0)
    engine = workload["serve"]["engine"]
    assert (engine["slots"], workload["traffic"]["arrival"]["clients"]) == (96, 96)
    assert engine["prefix_cache"] is False and engine["num_kv_blocks"] == 12289
    assert engine["prefill_buckets"] == workload["serve"]["warm_buckets"] == [256, 512, 1024]
    # 2,048 positions a slot: every slot at the mean request (1,016) twice
    # over, and the 96 largest of three blocks of sizes at once.
    per_request = -(-sizes.sum(axis=1) // 16)
    assert engine["num_kv_blocks"] - 1 >= 2 * 96 * per_request.mean()
    assert engine["num_kv_blocks"] - 1 >= 3 * per_request.sum()


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_rehearsal_through_run_cell(trace):
    workload, config = tiny_cell()
    out = run.run_cell(
        workload, config, name=CELL, seed=2**31 + 36, seconds=2.5, trace=trace,
        emit=lambda o: None, expect_platform="cpu",
    )
    assert out["correct"] is True and out["failed"] == 0
    if not trace:
        assert set(out["metrics"]) == {"setup_s", "serve.out_tok_s", "serve.tpot_ms.p95"}
        return
    # Every counter metric reports; the kernel's shares need device events,
    # which a CPU trace has none of (covered below).
    for name in ["ssm.chunk_fill_share.mean", "moe.rows_per_expert.mean", "kvpool.used_share.peak"]:
        assert out["metrics"][name]["value"] > 0, name
    assert 30 < out["metrics"]["ssm.chunk_fill_share.mean"]["value"] <= 100
    assert "ssm_state_update_roofline" not in out["metrics"]
    assert set(workload["layer_metrics"]) <= set(out["metrics"]) | {"device.idle_share.serve"}


def test_the_float8_control_fails_the_limits_tiny_twin(monkeypatch):
    """At tiny widths in float32 a sound run reads rounding error; the
    float8 control reads whole logits (of a model whose logits are divided
    by 16: small ones).  The near ties' margin is cut with the router's
    spread (0.02 x sqrt(hidden): 0.16 here, 1.28 at width): at the margin
    of the published width every tiny position would be a tie of 16
    routings, one of which always suits the control's token."""
    from chipbench import control, reference_cohere2moe

    monkeypatch.setattr(reference_cohere2moe, "ROUTER_MARGIN", 0.1 / 8)
    out = control.read(lambda name: tiny_cell(), CELL, [2**31 + 37], 4.0,
                       expect_platform="cpu", log=lambda line: None)
    sound = out["sound_largest"]["served_logit_widest_gap"]
    low = out["control_smallest"]["served_logit_widest_gap"]
    assert out["correct"] == [True] and sound < 1e-5 < low, (sound, low)


def test_kernel_shares_read_their_kernels_events_and_nothing_on_the_parent():
    plane, line = "/device:TPU:0", "XLA Ops"
    events = [
        (plane, line, "%gmm.3 = bf16[960,768]{1,0} custom-call(%fusion.9, %gmm.1)", 1.0, 0.004),
        (plane, line, "%fusion.9 = bf16[960,4096]{1,0} fusion(%gmm.1)", 1.004, 0.5),
        (plane, line, "%ssm_state_update.2 = (f32[96,2,64,64], f32[97,128,64,128]) custom-call()", 2.0, 0.002),
    ]
    scalars = {
        "d_moe_rows_local": 4800.0, "d_moe_expert_groups": 360.0,
        "d_ssm_tick_state_rows": 96.0, "d_ssm_chunk_tokens": 6210.0,
        "d_ssm_chunk_rows": 9216.0, "peak_flops": 197e12, "peak_bytes_per_s": 819e9,
        "window_s": 2.0, "wall_s": 2.0, "busy_s": 0.5,
    }
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert set(NEW_METRICS) <= set(specs)
    ctx = {"scalars": scalars, "events": events, "window": (0.0, 10.0)}
    read = lambda name, c=ctx: layer_metrics.read_metric(specs[name]["reader"], c)  # noqa: E731
    assert read("granite.gmm_roofline") == pytest.approx(100 * (6 * 4096 * 768 * 360 / 819e9) / 0.004)
    assert read("ssm_state_update_roofline") == pytest.approx(100 * (8388608 * 96 / 819e9) / 0.002)
    assert read("ssm.state_share.tick") == pytest.approx(100 * 0.002 / 0.5)
    assert read("ssm.chunk_fill_share.mean") == pytest.approx(100 * 6210 / 9216)
    # A program without the counters (the parent) or without the kernel:
    # nothing to read, no error.
    bare = {"scalars": {k: v for k, v in scalars.items() if not k.startswith("d_")},
            "events": events[:2], "window": (0.0, 10.0)}
    assert all(read(name, bare) is None for name in NEW_METRICS)
    assert read("ssm_state_update_roofline", {**ctx, "events": events[:2]}) is None


def test_no_file_the_benchmark_had_has_changed():
    """Add-as-data: against the parent commit, ``chipbench/`` only gains
    files, and ``BENCHMARK.json`` only entries at the ends of its lists."""
    root = BENCH.parent

    def git(*args):
        return subprocess.run(
            ["git", *args], cwd=root, capture_output=True, text=True, check=True
        ).stdout

    try:
        git("cat-file", "-e", PARENT)
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history here (an unpacked archive)")
    changed = git("diff", "--name-status", PARENT, "--", "chipbench").split("\n")
    assert [line for line in changed if line and not line.startswith("A")] == []
    untracked = set(git("ls-files", "--others", "--exclude-standard", "chipbench").split())
    added = {line.split("\t")[1] for line in changed if line} | untracked
    assert added == NEW_FILES
    before = json.loads(git("show", f"{PARENT}:BENCHMARK.json"))
    after = json.loads((root / "BENCHMARK.json").read_text())
    assert {k: after[k] for k in ("command", "paths", "run_seconds")} == {
        k: before[k] for k in ("command", "paths", "run_seconds")
    }
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        old, new = before[section], after[section]
        for was, now in zip(old, new):
            lists = was.get("workloads", []), now.get("workloads", [])
            assert now == {**was, **({"workloads": lists[1]} if "workloads" in was else {})}
            assert lists[1][: len(lists[0])] == lists[0] and set(lists[1][len(lists[0]):]) <= {CELL}
        assert all(CELL in e.get("workloads", [CELL]) or e["name"] in (CELL, "granite-4.0-h-small")
                   for e in new[len(old):])
