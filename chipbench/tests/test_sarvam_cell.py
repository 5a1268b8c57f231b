"""``sarvam.serve.doc-qa`` (ISSUE 49): the configuration file against the
catalog's numbers and the issue's arithmetic, the counts, the traffic, a CPU
rehearsal of the cell at tiny sizes through ``run_cell``, the four new metric
files, and that every file the benchmark had is as it was."""

import json
import subprocess

import pytest

from chipbench import counts_sarvam as counts
from chipbench import layer_metrics, run
from chipbench.tests.tiny import BENCH, metrics_of_cell

CELL = "sarvam.serve.doc-qa"
NAME = "sarvam-105b"
PARENT = "d5def1ab43a4c9d237c195ad0f579ed8650ffeab"
NEW_METRICS = [
    "sarvam.mla_chunk_attention_roofline", "sarvam.mla_paged_attention_roofline",
    "sarvam.gmm_roofline", "sarvam.chunk_attention_share.busy",
]
NEW_FILES = {
    f"chipbench/configs/{NAME}.json", f"chipbench/workloads/{CELL}.json",
    "chipbench/reference_sarvam.py", "chipbench/counts_sarvam.py",
    "chipbench/tests/test_sarvam_cell.py",
    *(f"chipbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
YARN = {
    "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
    "original_max_position_embeddings": 4096, "type": "deepseek_yarn",
}
#: The catalog's ``config`` of the model, less the three keys the cut changes.
CATALOG = {
    "attn_implementation": None, "default_theta": 10000, "first_k_dense_replace": 1,
    "head_dim": 576, "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8, "num_shared_experts": 1,
    "q_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": YARN, "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
}


def load():
    return run.load_cell(CELL)


def tiny_cell():
    """The cell's files cut to a hidden size of 64: 4 heads of 16 + 8 / 16
    over a latent of 32, positions stretched by 8 past an original 16, a
    dense layer of 96 then four expert layers of 16 experts of 16 (4 held,
    4 a token) and a shared one; blocks of 4, chunks of 16."""
    workload, config = load()
    config.update(
        hidden_size=64, d_model=64, intermediate_size=96, d_ff=96,
        moe_intermediate_size=16, expert_d_ff=16, num_attention_heads=4, num_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        q_head_dim=24, head_dim=40, rope_theta=100.0,
        rope_scaling={**YARN, "factor": 8, "original_max_position_embeddings": 16,
                      "beta_fast": 4, "beta_slow": 0.25},
        yarn_factor=8.0, yarn_original_context=16, yarn_beta_fast=4.0, yarn_beta_slow=0.25,
        num_experts=4, experts_held=4, n_experts=16, num_experts_per_tok=4,
        router_top_k=4, vocab_size=512, context_length=128, activation_dtype="float32",
    )
    workload["serve"]["engine"].update(
        slots=4, block_size=4, prefill_chunk=16, prefill_token_budget=16,
        prefill_buckets=[8, 16], num_kv_blocks=None,
    )
    workload["serve"].update(warm_buckets=[8, 16], ramp_s=0.5)
    workload["traffic"]["arrival"].update(clients=4, stagger_s=0.2)
    workload["traffic"]["prompt_len"].update(lo=18, hi=90)
    workload["traffic"]["output_len"].update(lo=4, hi=24)
    workload["traffic"].update(max_total=120, n_sizes=16, closed_plan=64, greedy_every=2)
    workload["trace_seconds"] = 1.0
    # The limit's tiny twin: the twin is served at float32 from the harness's
    # bfloat16-valued weights, so a sound run reads float32's error.
    workload["correct"]["served_logit_gap"] = 5e-4
    return workload, config


def test_counts_pin_the_issues_numbers():
    _, cfg = load()
    assert counts.attention_params(cfg) == 50_331_648 + 2_359_296 + 8_388_608 + 33_554_432
    assert counts.attention_params(cfg) == 94_633_984
    assert counts.dense_ffn_params(cfg) == 201_326_592
    assert counts.router_params(cfg) == 524_288 and counts.expert_params(cfg) == 25_165_824
    assert counts.dense_layer_params(cfg) == 295_960_576
    assert counts.layer_params_outside_experts(cfg) == 120_324_096
    assert counts.head_params(cfg) == 2 * 65536 * 4096
    assert (counts.dense_layers(cfg), counts.expert_layers(cfg)) == (1, 4)
    assert counts.params_held(cfg) == 4_535_353_344
    assert counts.matmul_weight_bytes(cfg) == pytest.approx(9.07e9, rel=1e-3)
    # The published model from the same arithmetic: the published 105B.
    whole = {**cfg, "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert counts.params_held(whole) == 106_031_480_832
    assert counts.head_params(whole) == 2_147_483_648
    # Active a token: 8 of 128 experts - 10.27B in the blocks (the "~10B
    # class" of described_as), 11.34B with the head's 1.07B.
    active = counts.params_held({**whole, "num_experts": 8}) - counts.head_params(whole)
    assert active == pytest.approx(10.267e9, rel=1e-3)
    assert active + counts.head_params(whole) // 2 == pytest.approx(11.34e9, rel=1e-3)
    # A cached position: 1,152 B a layer, 5,760 B over the five; 1,280 and
    # 6,400 as the device pads a row; K and V of 64 heads would be 40,960.
    assert counts.kv_bytes_per_position(cfg) == 1152 == cfg["head_dim"] * 2
    assert counts.kv_bytes_per_token(cfg) == 5760
    assert counts.kv_bytes_per_position_held(cfg) == 1280
    assert 64 * (192 + 128) * 2 == 40960
    assert counts.held_experts_per_token(cfg) == 2.0
    # The memory plan: weights and 700,000 positions of latent rows.
    engine = load()[0]["serve"]["engine"]
    positions = (engine["num_kv_blocks"] - 1) * 16
    pool = engine["num_kv_blocks"] * 16 * 5 * 1280
    assert positions == 700_000 and pool == pytest.approx(4.48e9, rel=1e-3)
    assert counts.matmul_weight_bytes(cfg) + pool == pytest.approx(13.55e9, rel=1e-3)
    # A prompt token's matrices: 1.96 GFLOP (attention 0.95, layer 0's FFN
    # 0.40, four expert layers at 2 held experts and the shared one 0.61).
    assert counts.forward_flops(cfg, 1, 0, 0) == pytest.approx(1.957e9, rel=1e-3)
    assert 2 * 5 * counts.attention_params(cfg) == pytest.approx(0.946e9, rel=1e-3)
    assert 2 * counts.dense_ffn_params(cfg) == pytest.approx(0.403e9, rel=1e-3)
    assert 2 * 4 * (524_288 + 3 * 25_165_824) == pytest.approx(0.608e9, rel=1e-3)
    near, far = (counts.forward_flops(cfg, 1, c, 1) for c in (4000, 24000))
    assert far - near == pytest.approx(2 * 64 * 320 * 5 * 20000)
    assert 5 * counts.mla_chunk_attention_flops(cfg, 1) == 204_800  # "205 kFLOP" a pair
    # The kernels' functions say what the metric files spell out.
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    expr = {name: specs[name]["reader"]["expr"] for name in NEW_METRICS}
    assert "2 * 64 * (192 + 128) * d_chunk_attn_kernel_pairs" in expr[NEW_METRICS[0]]
    assert counts.mla_chunk_attention_flops(cfg, 3) == 2 * 64 * (192 + 128) * 3
    assert "1280 * d_attn_kv_positions" in expr[NEW_METRICS[1]]
    assert "2 * 64 * (576 + 512) * d_attn_pairs" in expr[NEW_METRICS[1]]
    assert counts.mla_paged_attention_bytes(cfg, 7) == 1280 * 7
    assert counts.mla_paged_attention_flops(cfg, 7) == 2 * 64 * (576 + 512) * 7
    assert "6 * 4096 * 2048 * d_moe_rows_local" in expr[NEW_METRICS[2]]
    assert counts.gmm_flops(cfg, 5) == counts.gmm_bytes(cfg, 5) == 6 * 4096 * 2048 * 5


def test_configuration_file_holds_the_published_numbers():
    _, cfg = load()
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["source"] == "https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json"
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "context_length"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 32, 65536)
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["num_experts"]) == (32, 128)
    assert (published["vocab_size"], published["context_length"]) == (262144, 131072)
    assert "106,031,480,832" in published["parameters"]
    assert "4 chips" in cfg["deployment"] and "9.07 GB" in cfg["deployment"]
    assert "4,535,353,344" in cfg["deployment"] and "1,152 B" in cfg["deployment"]
    assert "32, 64 and 96" in cfg["reduced_how"]["num_experts"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert {"use_qk_norm", "scoring", "norm_topk_prob", "expert_groups", "shared_expert",
            "rope_pairs", "yarn", "head_dim", "seeded_values", "mtp", "lm_head", "precision",
            "program_keys"} <= set(cfg["assumed"])
    assert "512 latent values" in cfg["assumed"]["use_qk_norm"]
    assert "0.135234" in cfg["assumed"]["yarn"]
    # The program's names repeat the published widths; no width is cut.
    model = run.program_model_config(cfg)
    assert (model.d_model, model.num_heads, model.d_head, model.value_dim) == (4096, 64, 192, 128)
    assert (model.qk_nope_head_dim, model.rope_dim, model.kv_lora_rank) == (128, 64, 512)
    assert model.q_lora_rank == 0 and model.latent_width == 576 == cfg["head_dim"]
    assert model.d_head == cfg["q_head_dim"]
    assert model.layer_kinds == "Aaaaa" and model.hybrid_block and not model.double_layer
    assert (model.attn_sublayers, model.attn_layers) == (1, 5)
    assert [model.layer_ffn_is_dense(i) for i in range(5)] == [True] + [False] * 4
    assert model.norm_eps == 1e-6 == cfg["rms_norm_eps"]
    scaling = cfg["rope_scaling"]
    assert (model.yarn_factor, model.yarn_original_context) == (
        scaling["factor"], scaling["original_max_position_embeddings"])
    assert (model.yarn_beta_fast, model.yarn_beta_slow) == (scaling["beta_fast"], scaling["beta_slow"])
    assert (model.yarn_mscale, model.yarn_mscale_all_dim) == (
        scaling["mscale"], scaling["mscale_all_dim"])
    assert model.rope_theta == cfg["rope_theta"] and model.d_ff == 16384
    assert (model.moe_d_ff, model.shared_ff, model.router_outputs) == (2048, 2048, 128)
    assert (model.router_top_k, model.local_experts, model.expert_offset) == (8, 32, 0)
    assert (model.moe_router, model.router_bias, model.norm_topk_prob) == ("sigmoid", True, True)
    assert model.routed_scaling_factor == 2.5 and model.n_shared_experts == 1
    assert not model.tie_embeddings and model.context_length == 32768
    from bpe_transformer_tpu.models import mla
    from chipbench import reference_sarvam

    assert mla.softmax_scale(model) == pytest.approx(0.135234, rel=1e-5)
    assert reference_sarvam.softmax_scale(cfg) == pytest.approx(0.135234, rel=1e-5)
    assert reference_sarvam.yarn_range(cfg) == (10, 23)
    # The same file serves the other three shares.
    for offset in (32, 64, 96):
        assert run.program_model_config({**cfg, "expert_offset": offset}).expert_offset == offset
    declared = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert len(declared["workloads"]) == 10
    assert all(w["chips"] == 1 for w in declared["workloads"])
    entry = [c for c in declared["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert len(entry["why"]) <= 200 and "sarvam_mla" in entry["why"]
    cell = [w for w in declared["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and cell["traffic"] == "serve.doc-qa"
    listed = {m["name"] for m in declared["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == metrics_of_cell(CELL) and set(NEW_METRICS) <= listed
    judged = {m["name"] for m in declared["end_to_end"] if CELL in m.get("workloads", ())}
    assert judged | {"setup_s"} == set(json.loads(
        (BENCH / "workloads" / f"{CELL}.json").read_text())["end_to_end"])


def test_traffic_is_the_issues():
    workload, _ = load()
    from chipbench import traffic

    sizes = traffic.request_sizes(workload["traffic"])
    assert len(sizes) == 32 and sizes[:, 0].min() >= 4096 and sizes[:, 0].max() <= 32000
    assert sizes[:, 1].min() >= 64 and sizes[:, 1].max() <= 384
    assert (sizes.sum(axis=1) <= 32768).all()
    # The set's means stand near the distributions' (13.6k and ~180), and a
    # third of the prompts pass 16k tokens.
    assert sizes[:, 0].mean() == pytest.approx(13600, rel=0.05)
    assert sizes[:, 1].mean() == pytest.approx(180, rel=0.05)
    assert (sizes[:, 0] > 16384).sum() == 11
    plan = traffic.plan_requests(workload["traffic"], 65536, 2**31 + 49, 60.0)
    assert len(plan) == 512 and sum(p.greedy for p in plan) == 64      # every eighth
    assert len({p.prompt_ids[:64] for p in plan[:40]}) == 40           # no shared prefix
    assert all(p.greedy or (p.temperature, p.top_k) == (1.0, 50) for p in plan)
    assert max(max(p.prompt_ids) for p in plan[:8]) < 65536
    assert workload["trace_seconds"] == 3.0
    # The ramp belongs to this schedule (sizes_seed 49, closed_plan 512): the
    # rate is flat from ~50 s after the first client joins, and the greedy
    # request of index 55 (the 31,722-token prompt) ends ~56.4 s in - inside a
    # traced part from 55 s, stalled by the profiler or not (PERF.md section
    # 6, PR 49).  Another sizes_seed or stagger needs the ramp read again.
    assert workload["serve"]["ramp_s"] == 55.0
    assert (workload["traffic"]["sizes_seed"], workload["traffic"]["closed_plan"]) == (49, 512)
    assert workload["traffic"]["arrival"] == {"kind": "closed", "clients": 32, "stagger_s": 16.0}
    assert workload["traffic"]["shared_prefix"] == {"share": 0.0, "len": 0}
    engine = workload["serve"]["engine"]
    assert engine["slots"] == 32 and engine["prefix_cache"] is False
    assert engine["num_kv_blocks"] == 43751
    assert (engine["block_size"], engine["prefill_chunk"], engine["prefill_token_budget"]) == (16, 2048, 2048)
    assert engine["prefill_buckets"] == workload["serve"]["warm_buckets"] == [512, 1024, 2048]
    assert workload["end_to_end"] == ["setup_s", "serve.out_tok_s", "serve.tpot_ms.p95"]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_rehearsal_through_run_cell(trace):
    workload, config = tiny_cell()
    out = run.run_cell(
        workload, config, name=CELL, seed=2**31 + 49, seconds=2.5, trace=trace,
        emit=lambda o: None, expect_platform="cpu",
    )
    assert out["correct"] is True and out["failed"] == 0
    if not trace:
        assert set(out["metrics"]) == {"setup_s", "serve.out_tok_s", "serve.tpot_ms.p95"}
        return
    # Every counter metric reports; the kernels' shares need device events,
    # which a CPU trace has none of (covered below).
    for name in ["kvpool.used_share.peak", "moe.rows_per_expert.mean"]:
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
    out = control.read(lambda name: tiny_cell(), CELL, [2**31 + 50], 4.0,
                       expect_platform="cpu", log=lambda line: None)
    sound = out["sound_largest"]["served_logit_widest_gap"]
    low = out["control_smallest"]["served_logit_widest_gap"]
    assert out["correct"] == [True] and sound < 5e-4 < low / 3, (sound, low)


def test_kernel_shares_read_their_kernels_events_and_nothing_on_the_parent():
    plane, line = "/device:TPU:0", "XLA Ops"
    events = [
        (plane, line, "%gmm.3 = bf16[16384,2048]{1,0} custom-call(%fusion.9, %gmm.1)", 1.0, 0.004),
        (plane, line, "%fusion.9 = bf16[64,4096]{1,0} fusion(%gmm.1)", 1.004, 0.5),
        (plane, line, "%mla_paged_attention.2 = f32[32,64,512]{2,1,0} custom-call()", 2.0, 0.003),
        (plane, line, "%mla_paged_attention_shared.5 = f32[32,64,512]{2,1,0} custom-call()", 3.0, 0.001),
        (plane, line, "%mla_chunk_attention.7 = bf16[64,2048,128]{2,1,0} custom-call()", 4.0, 0.02),
    ]
    scalars = {
        "d_moe_rows_local": 40000.0, "d_moe_expert_groups": 320.0,
        "d_attn_pairs": 2.0e6, "d_attn_kv_positions": 2.0e6, "d_chunk_attn_kernel_pairs": 6e7,
        "peak_flops": 197e12, "peak_bytes_per_s": 819e9,
        "window_s": 2.0, "wall_s": 2.0, "busy_s": 0.5,
    }
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert set(NEW_METRICS) <= set(specs)
    ctx = {"scalars": scalars, "events": events, "window": (0.0, 10.0)}
    read = lambda name, c=ctx: layer_metrics.read_metric(specs[name]["reader"], c)  # noqa: E731
    # The rows' FLOPs are the larger need here (125 rows a group on average
    # would not be; 40,000 rows over 320 groups are).
    flops, streamed = 6 * 4096 * 2048 * 40000 / 197e12, 6 * 4096 * 2048 * 320 / 819e9
    assert flops < streamed
    assert read("sarvam.gmm_roofline") == pytest.approx(100 * streamed / 0.004)
    assert read("sarvam.mla_paged_attention_roofline") == pytest.approx(
        100 * (1280 * 2.0e6 / 819e9) / 0.004)
    assert read("sarvam.mla_chunk_attention_roofline") == pytest.approx(
        100 * (40960 * 6e7 / 197e12) / 0.02)
    assert read("sarvam.chunk_attention_share.busy") == pytest.approx(100 * 0.02 / 0.5)
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
