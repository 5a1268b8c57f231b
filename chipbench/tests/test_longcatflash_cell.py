"""``longcat.serve.agent-shared`` (ISSUE 33): the configuration file against
the catalog's numbers and the issue's arithmetic, the counts, the traffic,
a CPU rehearsal of the cell at tiny sizes through ``run_cell``, the four
new metric files, and that every file the benchmark had is as it was."""

import json
import subprocess

import pytest

from chipbench import counts_longcatflash as counts
from chipbench import layer_metrics, run
from chipbench.tests.tiny import BENCH, metrics_of_cell

CELL = "longcat.serve.agent-shared"
PARENT = "d7e2af5ffe1a7b8ca6f6f2462b943233f52e229b"
NEW_METRICS = [
    "moe.zero_share.mean", "kvpool.prefix_hit_share.mean",
    "mla_paged_attention_roofline", "longcat.gmm_roofline",
]
NEW_FILES = {
    "chipbench/configs/LongCat-Flash-Omni.json", f"chipbench/workloads/{CELL}.json",
    "chipbench/reference_longcatflash.py", "chipbench/counts_longcatflash.py",
    "chipbench/tests/test_longcatflash_cell.py",
    *(f"chipbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
CATALOG = {
    "attention_bias": False, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
}


def load():
    return run.load_cell(CELL)


def tiny_cell():
    """The cell's files cut to a hidden size of 64: 4 heads of 8 + 4 / 8,
    lora ranks 16 / 8, 16 real experts of which 4 are held and 8 zero
    experts, 4 a token; blocks of 4, chunks of 8, a shared prefix of 8."""
    workload, config = load()
    config.update(
        hidden_size=64, d_model=64, ffn_hidden_size=128, d_ff=128,
        expert_ffn_hidden_size=32, expert_d_ff=32, num_attention_heads=4,
        num_heads=4, q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, n_routed_experts=4, experts_held=4,
        n_experts=16, zero_expert_num=8, n_zero_experts=8, moe_topk=4,
        router_top_k=4, num_layers=2, vocab_size=64, context_length=64,
        activation_dtype="float32",
    )
    workload["serve"]["engine"].update(
        slots=4, block_size=4, prefill_chunk=8, prefill_token_budget=8,
        prefill_buckets=[4, 8], num_kv_blocks=None,
    )
    workload["serve"].update(warm_buckets=[4, 8], ramp_s=0.5)
    workload["traffic"]["arrival"].update(clients=4, stagger_s=0.2)
    workload["traffic"]["prompt_len"].update(lo=10, hi=24)
    workload["traffic"]["output_len"].update(lo=4, hi=20)
    # Every second request greedy: four clients finish a handful of requests
    # in the twin's seconds, and `correct` needs a greedy one among them.
    workload["traffic"].update(
        max_total=60, n_sizes=16, closed_plan=64, greedy_every=2,
        shared_prefix={"share": 1.0, "len": 8},
    )
    workload["trace_seconds"] = 1.0
    return workload, config


def test_counts_pin_the_issues_numbers():
    _, cfg = load()
    assert counts.attention_params(cfg) == pytest.approx(90.57e6, rel=1e-4)
    assert counts.dense_params(cfg) == pytest.approx(226.49e6, rel=1e-4)
    assert counts.router_params(cfg) == pytest.approx(4.72e6, rel=1e-3)
    assert counts.layer_params_outside_experts(cfg) == pytest.approx(638.9e6, rel=1e-4)
    assert counts.expert_params(cfg) == pytest.approx(37.75e6, rel=1e-4)
    assert counts.params_held(cfg) == pytest.approx(5.173e9, rel=1e-4)
    assert counts.matmul_weight_bytes(cfg) == pytest.approx(10.35e9, rel=1e-3)
    assert counts.kv_bytes_per_token(cfg) == 9216  # 8 sublayers x 1,152 B
    assert counts.held_experts_per_token(cfg) == 0.25
    # Published model from the same arithmetic: 560B, 27.9B active at 8 real.
    layer = counts.layer_params_outside_experts(cfg)
    assert 28 * (layer + 512 * counts.expert_params(cfg)) + 2 * 131072 * 6144 == pytest.approx(
        560.7e9, rel=1e-3
    )
    assert 28 * (layer + 8 * counts.expert_params(cfg)) + 2 * 131072 * 6144 == pytest.approx(
        27.9e9, rel=2e-3
    )
    # The kernels' functions say what the metric files spell out.
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert f"2 * 64 * (576 + 512) * d_attn_pairs" in specs["mla_paged_attention_roofline"]["reader"]["expr"]
    assert counts.mla_paged_attention_flops(cfg, 3) == 2 * 64 * (576 + 512) * 3
    assert counts.mla_paged_attention_bytes(cfg, 3) == 1152 * 3
    assert counts.gmm_flops(cfg, 5) == counts.gmm_bytes(cfg, 5) == 6 * 6144 * 2048 * 5
    # A decoded token at context c: 8 sublayers of 64 heads x 320 a pair.
    near, far = (counts.forward_flops(cfg, 1, c, 1) for c in (3000, 10000))
    assert far - near == pytest.approx(8 * 2 * 64 * 320 * 7000)


def test_configuration_file_holds_the_published_numbers():
    _, cfg = load()
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size", "context_length"]
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (4, 16, 16384)
    assert cfg["published"]["num_layers"] == 28 and cfg["published"]["n_routed_experts"] == 512
    assert cfg["published"]["vocab_size"] == 131072 and "32 chips share each layer" in cfg["deployment"]
    assert {"language_model_only", "rope", "softmax_scale", "router_bias", "norm_topk_prob",
            "double_layer", "lm_head", "precision"} <= set(cfg["assumed"])
    # The program's names repeat the published widths.
    model = run.program_model_config(cfg)
    assert (model.d_model, model.num_heads, model.d_head, model.v_head_dim) == (6144, 64, 192, 128)
    assert (model.q_lora_rank, model.kv_lora_rank, model.latent_width) == (1536, 512, 576)
    assert (model.q_lora_scale, round(model.kv_lora_scale, 4)) == (2.0, 3.4641)
    assert (model.d_ff, model.moe_d_ff, model.router_outputs) == (12288, 2048, 768)
    assert (model.n_experts, model.router_top_k, model.local_experts) == (512, 12, 16)
    assert model.double_layer and not model.norm_topk_prob and model.routed_scaling_factor == 6
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
    assert len(sizes) == 32 and sizes[:, 0].min() >= 8320 and sizes[:, 0].max() <= 9216
    assert sizes[:, 1].min() >= 256 and sizes[:, 1].max() <= 1024
    plan = traffic.plan_requests(workload["traffic"], 16384, 2**31 + 33, 60.0)
    assert len(plan) == 512 and sum(p.greedy for p in plan) == 64  # every eighth
    assert workload["trace_seconds"] == 2.0
    assert (workload["serve"]["ramp_s"], workload["traffic"]["arrival"]["stagger_s"]) == (24.0, 16.0)
    assert len({p.prompt_ids[:8192] for p in plan[:40]}) == 1  # one system prompt
    assert len({p.prompt_ids[8192:8320] for p in plan[:40]}) == 40
    engine = workload["serve"]["engine"]
    assert (engine["slots"], workload["traffic"]["arrival"]["clients"]) == (64, 64)
    assert engine["prefix_cache"] is True and engine["num_kv_blocks"] == 20481
    # The shared prefix once and every slot's own blocks at their largest.
    assert engine["num_kv_blocks"] - 1 >= 512 + 64 * -(-(1024 + 1024) // 16)


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_rehearsal_through_run_cell(trace):
    workload, config = tiny_cell()
    out = run.run_cell(
        workload, config, name=CELL, seed=2**31 + 33, seconds=2.5, trace=trace,
        emit=lambda o: None, expect_platform="cpu",
    )
    assert out["correct"] is True and out["failed"] == 0
    if not trace:
        assert set(out["metrics"]) == {"setup_s", "serve.out_tok_s", "serve.tpot_ms.p95"}
        return
    # Every counter metric reports; the two kernels' shares need device
    # events, which a CPU trace has none of (covered below).
    for name in NEW_METRICS[:2] + ["moe.rows_per_expert.mean", "kvpool.used_share.peak"]:
        assert out["metrics"][name]["value"] > 0, name
    assert 10 < out["metrics"]["moe.zero_share.mean"]["value"] < 60
    assert 20 < out["metrics"]["kvpool.prefix_hit_share.mean"]["value"] < 90
    assert "mla_paged_attention_roofline" not in out["metrics"]
    assert set(workload["layer_metrics"]) <= set(out["metrics"]) | {"device.idle_share.serve"}


def test_the_float8_control_fails_the_limits_tiny_twin():
    """At tiny widths in float32 a sound run reads rounding error; the
    float8 control reads whole logits."""
    from chipbench import control

    out = control.read(lambda name: tiny_cell(), CELL, [2**31 + 34], 4.0,
                       expect_platform="cpu", log=lambda line: None)
    sound = out["sound_largest"]["served_logit_widest_gap"]
    low = out["control_smallest"]["served_logit_widest_gap"]
    assert out["correct"] == [True] and sound < 1e-3 < low


def test_kernel_shares_read_their_kernels_events_and_nothing_on_the_parent():
    plane, line = "/device:TPU:0", "XLA Ops"
    events = [
        (plane, line, "%gmm.3 = bf16[768,2048]{1,0} custom-call(%fusion.9, %gmm.1)", 1.0, 0.004),
        (plane, line, "%fusion.9 = bf16[768,6144]{1,0} fusion(%gmm.1)", 1.004, 0.5),
        (plane, line, "%mla_paged_attention.2 = f32[64,64,512]{2,1,0} custom-call()", 2.0, 0.002),
    ]
    scalars = {
        "d_moe_rows_local": 256.0, "d_moe_expert_groups": 16.0, "d_attn_pairs": 4e6,
        "d_attn_kv_positions": 4e6, "d_moe_zero_assignments": 1000.0,
        "d_moe_tokens_routed": 250.0, "d_prefix_cache_hits": 930.0,
        "d_prefix_cache_misses": 70.0, "peak_flops": 197e12, "peak_bytes_per_s": 819e9,
        "window_s": 2.0, "wall_s": 2.0,
    }
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert set(NEW_METRICS) <= set(specs)
    ctx = {"scalars": scalars, "events": events, "window": (0.0, 10.0)}
    read = lambda name, c=ctx: layer_metrics.read_metric(specs[name]["reader"], c)  # noqa: E731
    assert read("longcat.gmm_roofline") == pytest.approx(100 * (6 * 6144 * 2048 * 16 / 819e9) / 0.004)
    assert read("mla_paged_attention_roofline") == pytest.approx(100 * (1152 * 4e6 / 819e9) / 0.002)
    assert read("moe.zero_share.mean") == pytest.approx(100 / 3)
    assert read("kvpool.prefix_hit_share.mean") == pytest.approx(93.0)
    # A program without the counters (the parent) or without the kernels:
    # nothing to read, no error.
    bare = {"scalars": {k: v for k, v in scalars.items() if not k.startswith("d_")},
            "events": events, "window": (0.0, 10.0)}
    assert all(read(name, bare) is None for name in NEW_METRICS)
    assert read("mla_paged_attention_roofline", {**ctx, "events": events[:2]}) is None


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
        assert all(CELL in e.get("workloads", [CELL]) or e["name"] in (CELL, "LongCat-Flash-Omni")
                   for e in new[len(old):])
