"""``mimo.serve.long-context`` (ISSUE 46): the configuration file against the
catalog's numbers and the issue's arithmetic, the counts, the traffic, a CPU
rehearsal of the cell at tiny sizes through ``run_cell``, the five new metric
files, and that every file the benchmark had is as it was."""

import json
import subprocess

import pytest

from chipbench import counts_mimov2 as counts
from chipbench import layer_metrics, run
from chipbench.tests.tiny import BENCH, metrics_of_cell

CELL = "mimo.serve.long-context"
NAME = "MiMo-V2.5"
PARENT = "bf1d3a6cca8868af0e565b8e95660b2aad10e612"
NEW_METRICS = [
    "mimo.attn_full_roofline", "mimo.attn_window_roofline",
    "mimo.sink_chunk_attention_roofline", "mimo.gmm_roofline",
    "mimo.attention_share.tick",
]
NEW_FILES = {
    f"chipbench/configs/{NAME}.json", f"chipbench/workloads/{CELL}.json",
    "chipbench/reference_mimov2.py", "chipbench/counts_mimov2.py",
    "chipbench/tests/test_mimov2_cell.py",
    *(f"chipbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
WINDOWED = [0] + ([1] * 4 + [0]) + ([1] * 5 + [0]) * 7
#: The catalog's ``config`` of the model, less the three keys the cut changes.
CATALOG = {
    "attention_bias": False, "attention_chunk_size": 128, "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv", "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192, "swa_v_head_dim": 128,
    "head_dim": 192, "hidden_act": "silu", "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": WINDOWED, "intermediate_size": 16384,
    "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576, "model_type": "mimo_v2",
    "moe_intermediate_size": 2048, "moe_layer_freq": [0] + [1] * 47, "n_group": 1,
    "n_shared_experts": None, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"}, "rope_theta": 10000000,
    "routed_scaling_factor": None, "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
}


def load():
    return run.load_cell(CELL)


def tiny_cell():
    """The cell's files cut to a hidden size of 64: 8 query heads of 24 over
    2 (full) and 4 (window) K/V heads, values of 16, 8 of 24 rotated, a
    window of 8; a dense layer of 96; 16 experts of 16 of which 4 are held, 4
    a token; the 7 layers as published; blocks of 4, chunks of 16 (two
    windows long)."""
    workload, config = load()
    config.update(
        hidden_size=64, d_model=64, intermediate_size=96, d_ff=96,
        moe_intermediate_size=16, expert_d_ff=16, num_attention_heads=8, num_heads=8,
        num_key_value_heads=2, num_kv_heads=2, swa_num_key_value_heads=4,
        window_kv_heads=4, head_dim=24, swa_head_dim=24, v_head_dim=16,
        swa_v_head_dim=16, qk_rope_head_dim=8, sliding_window=8, sliding_window_size=8,
        n_routed_experts=4, experts_held=4, n_experts=16, num_experts_per_tok=4,
        router_top_k=4, vocab_size=512, context_length=128, activation_dtype="float32",
    )
    workload["serve"]["engine"].update(
        slots=4, block_size=4, prefill_chunk=16, prefill_token_budget=16,
        prefill_buckets=[8, 16], num_kv_blocks=None,
    )
    workload["serve"].update(warm_buckets=[8, 16], ramp_s=0.5)
    workload["traffic"]["arrival"].update(clients=4, stagger_s=0.2)
    workload["traffic"]["prompt_len"].update(lo=10, hi=60)
    workload["traffic"]["output_len"].update(lo=4, hi=30)
    workload["traffic"].update(max_total=120, n_sizes=16, closed_plan=64, greedy_every=2)
    workload["trace_seconds"] = 1.0
    # The limit's tiny twin: the twin is served at float32 from the harness's
    # bfloat16-valued weights, so a sound run reads float32's error (0.0 in
    # three seeds) and the float8 control 2.5e-3.
    workload["correct"]["served_logit_gap"] = 5e-4
    return workload, config


def test_counts_pin_the_issues_numbers():
    _, cfg = load()
    assert counts.attention_params(cfg, False) == 89_128_960
    assert counts.attention_params(cfg, True) == 94_371_840
    assert counts.dense_ffn_params(cfg) == 201_326_592
    assert counts.router_params(cfg) == 1_048_576 and counts.expert_params(cfg) == 25_165_824
    assert counts.head_params(cfg) == 2 * 19072 * 4096 == pytest.approx(156.2e6, rel=1e-3)
    assert (counts.full_layers(cfg), counts.window_layers(cfg), counts.expert_layers(cfg)) == (2, 5, 6)
    assert counts.params_held(cfg) == pytest.approx(3.430e9, rel=1e-4)
    assert 2 * counts.params_held(cfg) == pytest.approx(6.86e9, rel=1e-3)
    assert counts.kv_bytes_per_position(cfg, False) == 2560
    assert counts.kv_bytes_per_position(cfg, True) == 5120
    assert counts.kv_bytes_per_token(cfg) == 2 * 2560 + 5 * 5120
    # What `GroupedPages` would hold: K and V of 8 heads of 192 in all 7 layers.
    assert 7 * 2 * 8 * 192 * 2 == 43008
    assert counts.held_experts_per_token(cfg) == 0.5
    # Published model from the same arithmetic: 308.8B.
    whole = {**cfg, "num_hidden_layers": 48, "n_routed_experts": 256, "vocab_size": 152576}
    assert (counts.full_layers(whole), counts.window_layers(whole), counts.expert_layers(whole)) == (9, 39, 47)
    assert counts.params_held(whole) == pytest.approx(308.8e9, rel=1e-4)
    # The memory plan: weights, 73,728 full blocks, the window group that is
    # no reservation (64 x 9 + 136 blocks of 16 positions in 5 layers).
    engine = load()[0]["serve"]["engine"]
    full = (engine["num_kv_blocks"] - 1) * 16 * 2 * 2560
    window = (64 * 9 + 136 + 1) * 16 * 5 * 5120
    assert (engine["num_kv_blocks"] - 1) * 16 == pytest.approx(1.18e6, rel=1e-2)
    assert full == pytest.approx(6.04e9, rel=1e-3) and window == pytest.approx(0.292e9, rel=1e-2)
    assert 2 * counts.params_held(cfg) + full + window == pytest.approx(13.19e9, rel=1e-3)
    # The kernels' functions say what the metric files spell out.
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert "2560 * d_attn_full_kv_positions" in specs["mimo.attn_full_roofline"]["reader"]["expr"]
    assert counts.paged_attention_bytes(cfg, 7, False) == 2560 * 7
    assert "5120 * d_attn_window_kv_positions" in specs["mimo.attn_window_roofline"]["reader"]["expr"]
    assert counts.paged_attention_bytes(cfg, 7, True) == 5120 * 7
    assert "40960 * (d_chunk_attn_full_pairs + d_chunk_attn_window_pairs)" in (
        specs["mimo.sink_chunk_attention_roofline"]["reader"]["expr"])
    assert counts.chunk_attention_flops(cfg, 3) == 40960 * 3 == 2 * 64 * (192 + 128) * 3
    assert "6 * 4096 * 2048 * d_moe_rows_local" in specs["mimo.gmm_roofline"]["reader"]["expr"]
    assert counts.gmm_flops(cfg, 5) == counts.gmm_bytes(cfg, 5) == 6 * 4096 * 2048 * 5
    # A decoded token at context c: two full layers of 64 heads x (192 + 128),
    # five window layers that see 128 keys whatever the context.
    near, far = (counts.forward_flops(cfg, 1, c, 1) for c in (3000, 23000))
    assert far - near == pytest.approx(2 * 64 * 320 * 2 * 20000)


def test_configuration_file_holds_the_published_numbers():
    _, cfg = load()
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["source"] == "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size", "context_length"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (7, 16, 19072)
    assert cfg["published"]["num_hidden_layers"] == 48 and cfg["published"]["n_routed_experts"] == 256
    assert cfg["published"]["vocab_size"] == 152576 and cfg["published"]["context_length"] == 1048576
    assert "16 chips share each layer" in cfg["deployment"] and "6.86 GB" in cfg["deployment"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert {"partial_rotary_factor", "rope_pairs", "attention_value_scale", "attention_chunk_size",
            "fused_qkv", "sink", "routing", "towers_and_mtp", "precision", "seeded_values"} <= set(cfg["assumed"])
    assert "64" in cfg["assumed"]["partial_rotary_factor"] and "unused" in cfg["assumed"]["attention_chunk_size"]
    # The program's names repeat the published widths.
    model = run.program_model_config(cfg)
    assert (model.d_model, model.num_heads, model.d_head, model.value_dim, model.rope_dim) == (4096, 64, 192, 128, 64)
    assert (model.layer_kv_heads(0), model.layer_kv_heads(1)) == (4, 8)
    assert (model.layer_rope_theta(0), model.layer_rope_theta(1)) == (1e7, 1e4)
    assert model.layer_kinds == "Awwwwaw" and model.sliding_window == 128
    assert [bool(model.layer_window(i)) for i in range(7)] == [bool(k) for k in WINDOWED[:7]]
    assert [model.layer_ffn_is_dense(i) for i in range(7)] == [not k for k in cfg["moe_layer_freq"][:7]]
    assert model.attn_layers == 7 and model.layer_kinds.count("w") == 5
    # The sink: published on the window layers alone, which is all the
    # program has a field for.
    assert cfg["add_swa_attention_sink_bias"] is True
    assert cfg["add_full_attention_sink_bias"] is False
    assert [model.layer_sink(i) for i in range(7)] == [bool(k) for k in WINDOWED[:7]]
    assert model.attention_value_scale == 0.707 and model.d_ff == 16384
    assert (model.moe_d_ff, model.router_outputs, model.router_top_k, model.local_experts) == (2048, 256, 8, 16)
    assert (model.moe_router, model.router_bias, model.norm_topk_prob) == ("sigmoid", True, True)
    assert model.routed_scaling_factor == 1.0 and model.n_shared_experts == 0
    assert model.hybrid_block and not model.tie_embeddings and model.context_length == 32768
    declared = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert len(declared["workloads"]) == 9
    entry = [c for c in declared["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = [w for w in declared["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["traffic"] == "serve.long-context"
    listed = {m["name"] for m in declared["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == metrics_of_cell(CELL) and set(NEW_METRICS) <= listed
    judged = {m["name"] for m in declared["end_to_end"] if CELL in m.get("workloads", ())}
    assert judged | {"setup_s"} == set(json.loads(
        (BENCH / "workloads" / f"{CELL}.json").read_text())["end_to_end"])


def test_traffic_is_the_issues():
    workload, _ = load()
    from chipbench import traffic

    sizes = traffic.request_sizes(workload["traffic"])
    assert len(sizes) == 32 and sizes[:, 0].min() >= 4096 and sizes[:, 0].max() <= 24576
    assert sizes[:, 1].min() >= 512 and sizes[:, 1].max() <= 2048
    assert (sizes.sum(axis=1) <= 26624).all()
    # The set's means stand near the distributions' (11.4k and 1.1k).
    assert sizes[:, 0].mean() == pytest.approx(11429, rel=0.05)
    assert sizes[:, 1].mean() == pytest.approx(1108, rel=0.08)
    plan = traffic.plan_requests(workload["traffic"], 19072, 2**31 + 42, 60.0)
    assert len(plan) == 512 and sum(p.greedy for p in plan) == 64      # every eighth
    assert len({p.prompt_ids[:64] for p in plan[:40]}) == 40           # no shared prefix
    assert all(p.greedy or (p.temperature, p.top_k) == (1.0, 50) for p in plan)
    assert workload["trace_seconds"] == 3.0
    # The ramp belongs to this schedule (sizes_seed 46, closed_plan 512): at
    # 54 s both edges of a 40 s window fall where a shift of the trajectory
    # moves the rate least, and the first generation's last greedy request
    # ends ~6 s into a traced part's ~12 (PERF.md section 6, PR 46).  Another
    # sizes_seed or stagger needs the ramp read again.
    assert workload["serve"]["ramp_s"] == 54.0
    assert (workload["traffic"]["sizes_seed"], workload["traffic"]["closed_plan"]) == (46, 512)
    assert workload["traffic"]["arrival"] == {"kind": "closed", "clients": 64, "stagger_s": 16.0}
    engine = workload["serve"]["engine"]
    assert engine["slots"] == 64 and engine["prefix_cache"] is False
    assert engine["num_kv_blocks"] == 73729 == 64 * 1152 + 1
    assert (engine["block_size"], engine["prefill_chunk"], engine["prefill_token_budget"]) == (16, 2048, 2048)
    assert engine["prefill_buckets"] == workload["serve"]["warm_buckets"] == [512, 1024, 2048]


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
    for name in ["kvpool.full_used_share.peak", "kvpool.window_used_share.peak",
                 "moe.rows_per_expert.mean"]:
        assert out["metrics"][name]["value"] > 0, name
    assert not set(NEW_METRICS) & set(out["metrics"])
    assert set(workload["layer_metrics"]) <= set(out["metrics"]) | {"device.idle_share.serve"}


def test_the_float8_control_fails_the_limits_tiny_twin(monkeypatch):
    """At tiny widths a sound run reads float32's error (see ``tiny_cell``);
    the float8 control reads three times the limit or more (both the widest
    of the sequences' MEAN gaps, the cell's number).  The near ties' margin
    is cut with the router's spread, as the other expert cells' twins cut
    it."""
    from chipbench import control, reference_cohere2moe

    monkeypatch.setattr(reference_cohere2moe, "ROUTER_MARGIN", 0.1 / 8)
    out = control.read(lambda name: tiny_cell(), CELL, [2**31 + 43], 4.0,
                       expect_platform="cpu", log=lambda line: None)
    sound = out["sound_largest"]["served_logit_widest_gap"]
    low = out["control_smallest"]["served_logit_widest_gap"]
    assert out["correct"] == [True] and sound < 5e-4 < low / 3, (sound, low)


def test_kernel_shares_read_their_kernels_events_and_nothing_on_the_parent():
    plane, line = "/device:TPU:0", "XLA Ops"
    events = [
        (plane, line, "%gmm.3 = bf16[512,2048]{1,0} custom-call(%fusion.9, %gmm.1)", 1.0, 0.004),
        (plane, line, "%fusion.9 = bf16[64,4096]{1,0} fusion(%gmm.1)", 1.004, 0.5),
        (plane, line, "%sink_paged_attention_full.2 = f32[64,16,512]{2,1,0} custom-call()", 2.0, 0.003),
        (plane, line, "%sink_paged_attention_window.5 = f32[64,8,1024]{2,1,0} custom-call()", 3.0, 0.001),
        (plane, line, "%sink_chunk_attention_full.7 = bf16[4,16,2048,128]{3,2,1,0} custom-call()", 4.0, 0.02),
        (plane, line, "%sink_chunk_attention_window.8 = bf16[8,8,2048,128]{3,2,1,0} custom-call()", 5.0, 0.002),
    ]
    scalars = {
        "d_moe_rows_local": 2880.0, "d_moe_expert_groups": 320.0,
        "d_attn_full_kv_positions": 1.5e6, "d_attn_window_kv_positions": 40000.0,
        "d_chunk_attn_full_pairs": 4e7, "d_chunk_attn_window_pairs": 1e6,
        "peak_flops": 197e12, "peak_bytes_per_s": 819e9,
        "window_s": 2.0, "wall_s": 2.0, "busy_s": 0.5,
    }
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert set(NEW_METRICS) <= set(specs)
    ctx = {"scalars": scalars, "events": events, "window": (0.0, 10.0)}
    read = lambda name, c=ctx: layer_metrics.read_metric(specs[name]["reader"], c)  # noqa: E731
    assert read("mimo.gmm_roofline") == pytest.approx(100 * (6 * 4096 * 2048 * 320 / 819e9) / 0.004)
    assert read("mimo.attn_full_roofline") == pytest.approx(100 * (2560 * 1.5e6 / 819e9) / 0.003)
    assert read("mimo.attn_window_roofline") == pytest.approx(100 * (5120 * 40000 / 819e9) / 0.001)
    assert read("mimo.sink_chunk_attention_roofline") == pytest.approx(
        100 * (40960 * 4.1e7 / 197e12) / 0.022)
    assert read("mimo.attention_share.tick") == pytest.approx(100 * 0.004 / 0.5)
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
