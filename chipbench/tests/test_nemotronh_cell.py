"""``nemotron.serve.reasoning`` (ISSUE 42): the configuration file against
the catalog's numbers and the issue's arithmetic, the counts, the traffic,
a CPU rehearsal of the cell at tiny sizes through ``run_cell``, the four
new metric files, and that every file the benchmark had is as it was."""

import json
import subprocess

import pytest

from chipbench import counts_nemotronh as counts
from chipbench import layer_metrics, run
from chipbench.tests.tiny import BENCH, metrics_of_cell

CELL = "nemotron.serve.reasoning"
NAME = "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
PARENT = "3f30e37b00f828e54bd6fd9a7130d0348dd741bb"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
NEW_METRICS = [
    "nemotron.gmm_roofline", "nemotron.ssm_state_update_roofline",
    "nemotron.paged_decode_attention_roofline", "nemotron.expert_share.tick",
]
NEW_FILES = {
    f"chipbench/configs/{NAME}.json", f"chipbench/workloads/{CELL}.json",
    "chipbench/reference_nemotronh.py", "chipbench/counts_nemotronh.py",
    "chipbench/tests/test_nemotronh_cell.py",
    *(f"chipbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
#: The catalog's ``config`` of the model, less the three keys the cut changes.
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688, "hybrid_override_pattern": PATTERN,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
}


def load():
    return run.load_cell(CELL)


def tiny_cell():
    """The cell's files cut to a hidden size of 64: 8 state-space heads of
    16 in 4 groups with a state of 16, chunks of 8; 4 attention heads of 16
    over 2 KV heads; 8 experts of 16 of which 4 are held, 3 a token, a
    shared expert of 32; the 7 layers MEM*EME; blocks of 4, chunks of 8."""
    workload, config = load()
    config.update(
        hidden_size=64, d_model=64, moe_intermediate_size=16, d_ff=16, expert_d_ff=16,
        moe_shared_expert_intermediate_size=32, shared_d_ff=32, num_attention_heads=4,
        num_heads=4, num_key_value_heads=2, num_kv_heads=2, head_dim=16,
        mamba_num_heads=8, ssm_heads=8, mamba_head_dim=16, ssm_head_dim=16,
        ssm_state_size=16, ssm_state=16, n_groups=4, ssm_groups=4, chunk_size=8,
        ssm_chunk=8, n_routed_experts=4, experts_held=4, n_experts=8,
        num_experts_per_tok=3, router_top_k=3, num_hidden_layers=7, num_layers=7,
        hybrid_override_pattern="MEM*EME", layer_pattern="MEM*EME", vocab_size=512,
        context_length=64, activation_dtype="float32",
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
    # The limit's tiny twin.  The twin is served from the harness's
    # bfloat16-valued weights: an embedding row enters the stream as
    # bfloat16 and, with no multiplier on it, the stream stays at that width
    # whatever activation_dtype says - so a sound run reads bfloat16's
    # rounding (a sequence's mean gap up to 2.7e-4 over 3 seeds) and the
    # float8 control 4.1e-3 - 9.6e-3; logits are ~0.5 wide.
    workload["correct"]["served_logit_gap"] = 1e-3
    return workload, config


def test_counts_pin_the_issues_numbers():
    _, cfg = load()
    assert counts.mamba_params(cfg) == pytest.approx(38.74e6, rel=1e-4)
    assert counts.attention_params(cfg) == pytest.approx(23.40e6, rel=2e-4)
    assert counts.layer_params_outside_experts(cfg, "E") == pytest.approx(20.30e6, rel=2e-4)
    assert counts.expert_params(cfg) == 2 * 2688 * 1856 == pytest.approx(9.978e6, rel=1e-4)
    assert (counts.mamba_layers(cfg), counts.expert_layers(cfg), counts.attention_layers(cfg)) == (6, 5, 2)
    assert counts.params_held(cfg) == pytest.approx(3.926e9, rel=1e-4)
    assert 2 * counts.params_held(cfg) == pytest.approx(7.85e9, rel=1e-3)
    assert counts.kv_bytes_per_token(cfg) == 2048
    assert counts.state_bytes_per_slot(cfg) == 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert counts.state_bytes_per_slot(cfg) == pytest.approx(12.58e6 + 0.22e6, rel=1e-3)
    assert counts.ssm_conv_channels(cfg) == 6144 == 4096 + 2 * 8 * 128
    assert counts.held_experts_per_token(cfg) == 3
    # Published model from the same arithmetic: 31.58B, 3.58B active.
    whole = {**cfg, "num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072}
    assert (counts.mamba_layers(whole), counts.expert_layers(whole), counts.attention_layers(whole)) == (23, 23, 6)
    assert counts.params_held(whole) == pytest.approx(31.58e9, rel=1e-4)
    active = counts.params_held(whole) - 23 * (128 - 6) * counts.expert_params(whole)
    assert active == pytest.approx(3.58e9, rel=1e-3)
    # All 128 experts and the whole vocabulary at this depth: no room for a cache.
    uncut = {**cfg, "n_routed_experts": 128, "vocab_size": 131072}
    assert 2 * counts.params_held(uncut) == pytest.approx(14.9e9, rel=5e-3)
    # The issue's memory plan: weights, 193 rows of state, 36,864 blocks.
    plan = 2 * counts.params_held(cfg) + 193 * counts.state_bytes_per_slot(cfg) + 36864 * 16 * 2048
    assert plan == pytest.approx(11.5e9, rel=5e-3)
    # The kernels' functions say what the metric files spell out.
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert "4194304 * d_ssm_tick_state_rows" in specs["nemotron.ssm_state_update_roofline"]["reader"]["expr"]
    assert counts.ssm_state_update_bytes(cfg, 3) == 4194304 * 3
    assert "4 * 2688 * 1856 * d_moe_rows_local" in specs["nemotron.gmm_roofline"]["reader"]["expr"]
    assert counts.gmm_flops(cfg, 5) == counts.gmm_bytes(cfg, 5) == 4 * 2688 * 1856 * 5
    assert "1024 * d_attn_kv_positions" in specs["nemotron.paged_decode_attention_roofline"]["reader"]["expr"]
    assert counts.paged_decode_attention_bytes(cfg, 7) == 1024 * 7
    # A decoded token at context c: two attention layers of 32 heads x 128.
    near, far = (counts.forward_flops(cfg, 1, c, 1) for c in (300, 3000))
    assert far - near == pytest.approx(4 * 32 * 128 * 2 * 2700)


def test_configuration_file_holds_the_published_numbers():
    _, cfg = load()
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size", "context_length"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (13, 64, 65536)
    assert cfg["published"]["num_hidden_layers"] == 52 and cfg["published"]["n_routed_experts"] == 128
    assert cfg["published"]["vocab_size"] == 131072 and cfg["published"]["context_length"] == 262144
    assert "8 chips" in cfg["deployment"] and "2 chips that share each layer" in cfg["deployment"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert {"rope_theta", "time_step_limit", "gated_norm", "state_precision", "expand",
            "seeded_values", "shared_expert", "routing", "layer", "precision"} <= set(cfg["assumed"])
    for word in ("A_log", "dt_bias", "D = 1", "convolution", "selection bias"):
        assert word in cfg["assumed"]["seeded_values"], word
    assert "no rotation" in cfg["assumed"]["rope_theta"] and "no clamp" in cfg["assumed"]["time_step_limit"]
    # The program's names repeat the published widths.
    model = run.program_model_config(cfg)
    assert (model.d_model, model.num_heads, model.num_kv_heads, model.d_head) == (2688, 32, 2, 128)
    assert (model.ssm_heads, model.ssm_head_dim, model.ssm_state, model.ssm_groups) == (64, 64, 128, 8)
    assert (model.ssm_inner, model.ssm_conv_channels, model.ssm_conv, model.ssm_chunk) == (4096, 6144, 4, 128)
    assert model.layer_kinds == PATTERN[:13] == "MEMEM*EMEMEM*"
    assert (model.ssm_layers, model.attn_layers) == (6, 2)
    assert (model.moe_d_ff, model.shared_ff, model.router_outputs) == (1856, 3712, 128)
    assert (model.n_experts, model.router_top_k, model.local_experts) == (128, 6, 64)
    assert (model.moe_router, model.router_bias, model.norm_topk_prob) == ("sigmoid", True, True)
    assert (model.routed_scaling_factor, model.expert_activation) == (2.5, "relu2")
    assert model.attention_scale == 128 ** -0.5 and model.residual_multiplier == 1.0
    assert model.hybrid_block and not model.tie_embeddings and not model.layer_rope(5)
    declared = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in declared["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = [w for w in declared["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["traffic"] == "serve.reasoning"
    listed = {m["name"] for m in declared["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == metrics_of_cell(CELL) and set(NEW_METRICS) <= listed
    judged = {m["name"] for m in declared["end_to_end"] if CELL in m.get("workloads", ())}
    assert judged | {"setup_s"} == set(json.loads(
        (BENCH / "workloads" / f"{CELL}.json").read_text())["end_to_end"])


def test_traffic_is_the_issues():
    workload, _ = load()
    from chipbench import traffic

    sizes = traffic.request_sizes(workload["traffic"])
    assert len(sizes) == 32 and sizes[:, 0].min() >= 128 and sizes[:, 0].max() <= 1024
    assert sizes[:, 1].min() >= 512 and sizes[:, 1].max() <= 2048
    assert (sizes.sum(axis=1) <= 3072).all()
    # The set's means stand near the distributions' (431 and 1,108).
    assert sizes[:, 0].mean() == pytest.approx(431, rel=0.05)
    assert sizes[:, 1].mean() == pytest.approx(1108, rel=0.05)
    plan = traffic.plan_requests(workload["traffic"], 65536, 2**31 + 42, 60.0)
    assert len(plan) == 2048 and sum(p.greedy for p in plan) == 512    # every fourth
    assert len({p.prompt_ids[:64] for p in plan[:40]}) == 40           # no shared prefix
    assert all(p.greedy or (p.temperature, p.top_k) == (1.0, 50) for p in plan)
    assert workload["trace_seconds"] == 3.0
    assert (workload["serve"]["ramp_s"], workload["traffic"]["arrival"]["stagger_s"]) == (24.0, 16.0)
    engine = workload["serve"]["engine"]
    assert (engine["slots"], workload["traffic"]["arrival"]["clients"]) == (192, 192)
    assert engine["prefix_cache"] is False and engine["num_kv_blocks"] == 36865 == 192 * 192 + 1
    assert (engine["block_size"], engine["prefill_chunk"], engine["prefill_token_budget"]) == (16, 1024, 1024)
    assert engine["prefill_buckets"] == workload["serve"]["warm_buckets"] == [256, 512, 1024]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_rehearsal_through_run_cell(trace):
    workload, config = tiny_cell()
    out = run.run_cell(
        workload, config, name=CELL, seed=2**31 + 42, seconds=2.5, trace=trace,
        emit=lambda o: None, expect_platform="cpu",
    )
    assert out["correct"] is True and out["failed"] == 0
    if not trace:
        assert set(out["metrics"]) == {"setup_s", "serve.out_tok_s", "serve.tpot_ms.p95"}
        return
    # Every counter metric reports; the kernels' shares need device events,
    # which a CPU trace has none of (covered below).
    for name in ["ssm.chunk_fill_share.mean", "moe.rows_per_expert.mean", "kvpool.used_share.peak"]:
        assert out["metrics"][name]["value"] > 0, name
    assert 30 < out["metrics"]["ssm.chunk_fill_share.mean"]["value"] <= 100
    assert not set(NEW_METRICS) & set(out["metrics"])
    assert set(workload["layer_metrics"]) <= set(out["metrics"]) | {
        "device.idle_share.serve", "ssm.state_share.tick",
    }


def test_the_float8_control_fails_the_limits_tiny_twin(monkeypatch):
    """At tiny widths a sound run reads bfloat16's rounding error (see
    ``tiny_cell``); the float8 control reads four times the limit or more (both
    the widest of the sequences' MEAN gaps, the cell's number).
    The near ties' margin is cut with
    the router's spread (0.02 x sqrt(hidden): 0.16 here, 1.04 at width): at
    the margin of the published width every tiny position would be a tie of
    many routings, one of which always suits the control's token."""
    from chipbench import control, reference_cohere2moe

    monkeypatch.setattr(reference_cohere2moe, "ROUTER_MARGIN", 0.1 / 8)
    out = control.read(lambda name: tiny_cell(), CELL, [2**31 + 43], 4.0,
                       expect_platform="cpu", log=lambda line: None)
    sound = out["sound_largest"]["served_logit_widest_gap"]
    low = out["control_smallest"]["served_logit_widest_gap"]
    assert out["correct"] == [True] and sound < 1e-3 < low / 3, (sound, low)


def test_kernel_shares_read_their_kernels_events_and_nothing_on_the_parent():
    plane, line = "/device:TPU:0", "XLA Ops"
    events = [
        (plane, line, "%gmm.3 = bf16[1152,1856]{1,0} custom-call(%fusion.9, %gmm.1)", 1.0, 0.004),
        (plane, line, "%fusion.9 = bf16[1152,2688]{1,0} fusion(%gmm.1)", 1.004, 0.5),
        (plane, line, "%ssm_state_update.2 = (f32[192,1,64,64], f32[193,64,64,128]) custom-call()", 2.0, 0.002),
        (plane, line, "%paged_decode_attention.5 = bf16[192,32,128]{2,1,0} custom-call()", 3.0, 0.001),
    ]
    scalars = {
        "d_moe_rows_local": 2880.0, "d_moe_expert_groups": 320.0,
        "d_ssm_tick_state_rows": 1152.0, "d_attn_kv_positions": 400000.0,
        "peak_flops": 197e12, "peak_bytes_per_s": 819e9,
        "window_s": 2.0, "wall_s": 2.0, "busy_s": 0.5,
    }
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert set(NEW_METRICS) <= set(specs)
    ctx = {"scalars": scalars, "events": events, "window": (0.0, 10.0)}
    read = lambda name, c=ctx: layer_metrics.read_metric(specs[name]["reader"], c)  # noqa: E731
    assert read("nemotron.gmm_roofline") == pytest.approx(100 * (4 * 2688 * 1856 * 320 / 819e9) / 0.004)
    assert read("nemotron.ssm_state_update_roofline") == pytest.approx(100 * (4194304 * 1152 / 819e9) / 0.002)
    assert read("nemotron.paged_decode_attention_roofline") == pytest.approx(100 * (1024 * 400000 / 819e9) / 0.001)
    assert read("nemotron.expert_share.tick") == pytest.approx(100 * 0.004 / 0.5)
    # A program without the counters (the parent) or without the kernels:
    # nothing to read, no error.
    bare = {"scalars": {k: v for k, v in scalars.items() if not k.startswith("d_")},
            "events": events[1:2], "window": (0.0, 10.0)}
    assert all(read(name, bare) is None for name in NEW_METRICS)
    for name, spec in specs.items():
        if name in NEW_METRICS:
            assert spec["workloads"] == [CELL] and spec["unit"] == "%"


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
        assert all(CELL in e.get("workloads", [CELL]) or e["name"] in (CELL, NAME)
                   for e in new[len(old):])
    assert len(after["configs"]) == len(before["configs"]) + 1
    assert len(after["workloads"]) == len(before["workloads"]) + 1
    assert [m["name"] for m in after["per_layer"][len(before["per_layer"]):]] == NEW_METRICS
