"""``cmdaplus.serve.long-mixed`` (ISSUE 28): the configuration file against
the catalog's numbers and the issue's arithmetic, the counts, and a CPU
rehearsal of the cell at tiny sizes through ``run_cell``."""

import json

import pytest

from chipbench import counts_cohere2moe as counts
from chipbench import layer_metrics, run
from chipbench.tests.tiny import BENCH

CELL = "cmdaplus.serve.long-mixed"
NEW_METRICS = [
    "moe.rows_per_expert.mean", "moe.local_share.mean",
    "kvpool.window_used_share.peak", "kvpool.full_used_share.peak",
    "gmm_roofline", "ragged_paged_attention_roofline",
]


def load():
    return run.load_cell(CELL)


def tiny_cell():
    """The cell's files cut to a hidden size of 32: 4 heads of 16 on 2 KV
    heads, 8 experts of which 2 are held, window 8, blocks of 2, chunks of
    8."""
    workload, config = load()
    config.update(
        hidden_size=32, d_model=32, intermediate_size=16, d_ff=16, head_dim=16,
        num_attention_heads=4, num_heads=4, num_key_value_heads=2, num_kv_heads=2,
        num_experts=2, experts_held=2, n_experts=8, num_experts_per_tok=2,
        router_top_k=2, num_shared_experts=2, n_shared_experts=2,
        sliding_window=8, vocab_size=64, context_length=64,
        activation_dtype="float32",
    )
    workload["serve"]["engine"].update(
        slots=4, block_size=2, prefill_chunk=8, prefill_token_budget=8,
        prefill_buckets=[4, 8], num_kv_blocks=None,
    )
    workload["serve"].update(warm_buckets=[4, 8], ramp_s=0.5)
    workload["traffic"]["arrival"].update(clients=4, stagger_s=0.2)
    workload["traffic"]["prompt_len"].update(lo=6, hi=30)
    workload["traffic"]["output_len"].update(lo=4, hi=20)
    workload["traffic"].update(max_total=60, n_sizes=16, closed_plan=64)
    workload["trace_seconds"] = 1.0
    return workload, config


def test_counts_pin_the_issues_numbers():
    _, cfg = load()
    assert counts.layer_params_held(cfg) == pytest.approx(1149.77e6, rel=1e-5)
    assert counts.params_held(cfg) == pytest.approx(4.733e9, rel=1e-4)
    assert counts.matmul_weight_bytes(cfg) == pytest.approx(9.47e9, rel=1e-3)
    assert counts.kv_bytes_per_token(cfg) == 16384  # 4 layers x 4,096 B
    assert counts.attention_params(cfg) == pytest.approx(142.6e6, rel=1e-3)
    assert counts.expert_params(cfg) == pytest.approx(50.33e6, rel=1e-3)
    # Published model from the same arithmetic: 218B, 25B active.
    layer = counts.attention_params(cfg) + counts.router_params(cfg) + 132 * counts.expert_params(cfg)
    assert 32 * layer + 262144 * 4096 == pytest.approx(218.3e9, rel=1e-3)


def test_forward_flops_caps_window_layers_at_the_window():
    _, cfg = load()
    d_attn, w = 128 * 128, cfg["sliding_window"]
    # One decoded token: three window layers see min(c, 4096), one sees c.
    near, far = (counts.forward_flops(cfg, 1, c, 1) for c in (3000, 10000))
    assert far - near == pytest.approx(4.0 * d_attn * ((10000 - 3000) + 3 * (w - 3000)))
    assert counts.window_keys(cfg, 1, 10000) == w
    # A from-zero prefill of n tokens: sum_p min(p + 1, 4096).
    n = 6000
    assert counts.window_keys(cfg, n, n * (n + 1) // 2) == sum(min(p + 1, w) for p in range(n))
    # Routed experts at 8 x 16 / 128 = 1 expert a token, shared whole.
    per_token = counts.forward_flops(cfg, 2, 3, 0) - counts.forward_flops(cfg, 1, 1, 0)
    attention = 4.0 * d_attn * 4 * 2
    assert per_token - attention == pytest.approx(
        2.0 * 4 * (counts.attention_params(cfg) + counts.router_params(cfg) + 5 * counts.expert_params(cfg))
    )


def test_configuration_file_holds_the_published_numbers():
    _, cfg = load()
    row = {
        "hidden_size": 4096, "num_attention_heads": 128, "num_key_value_heads": 8,
        "head_dim": 128, "intermediate_size": 4096, "num_experts_per_tok": 8,
        "num_shared_experts": 4, "sliding_window": 4096, "rope_theta": 50000,
        "max_position_embeddings": 200000, "layer_norm_eps": 1e-5,
    }
    assert {k: cfg[k] for k in row} == row
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "context_length"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 32768)
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    # The program's names repeat the published widths.
    model = run.program_model_config(cfg)
    assert (model.d_model, model.num_heads, model.num_kv_heads, model.d_head) == (4096, 128, 8, 128)
    assert (model.n_experts, model.router_top_k, model.local_experts) == (128, 8, 16)
    assert [model.layer_window(i) for i in range(4)] == [4096, 4096, 4096, None]
    declared = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in declared["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"] and len(entry["source"]) <= 200


def test_traffic_is_the_issues():
    workload, _ = load()
    from chipbench import traffic

    sizes = traffic.request_sizes(workload["traffic"])
    assert len(sizes) == 32 and sizes[:, 0].min() >= 1024 and sizes[:, 0].max() <= 12288
    assert sizes[:, 1].min() >= 128 and sizes[:, 1].max() <= 512
    assert int((sizes[:, 0] > 4096).sum()) == 14  # 44% pass the window
    engine = workload["serve"]["engine"]
    assert (engine["slots"], workload["traffic"]["arrival"]["clients"]) == (32, 32)
    assert (engine["block_size"], engine["prefill_chunk"]) == (16, 2048)
    # Two block-shuffled sets in flight at once at the very most.
    assert engine["num_kv_blocks"] - 1 >= 2 * sum(sorted(-(-sizes.sum(1) // 16))[-16:])


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_rehearsal_through_run_cell(trace):
    workload, config = tiny_cell()
    out = run.run_cell(
        workload, config, name=CELL, seed=2**31 + 28, seconds=2.5, trace=trace,
        emit=lambda o: None, expect_platform="cpu",
    )
    assert out["correct"] is True and out["failed"] == 0
    if not trace:
        assert set(out["metrics"]) == {"setup_s", "serve.out_tok_s", "serve.tpot_ms.p95"}
        return
    # Every counter metric reports; the two kernels' shares need device
    # events, which a CPU trace has none of (covered below).
    for name in NEW_METRICS[:4]:
        assert out["metrics"][name]["value"] > 0, name
    assert out["metrics"]["moe.local_share.mean"]["value"] <= 100
    assert "gmm_roofline" not in out["metrics"]
    assert set(workload["layer_metrics"]) <= set(out["metrics"]) | {"device.idle_share.serve"}


def test_kernel_shares_read_their_kernels_events_and_nothing_on_the_parent():
    plane, line = "/device:TPU:0", "XLA Ops"
    events = [
        (plane, line, "%gmm.3 = bf16[256,4096]{1,0} custom-call(%fusion.9, %gmm.1)", 1.0, 0.004),
        (plane, line, "%fusion.9 = bf16[256,4096]{1,0} fusion(%gmm.1)", 1.004, 0.5),
        (plane, line, "%ragged_paged_attention_kernel.2 = bf16[32,128,128]{2,1,0} custom-call()", 2.0, 0.002),
    ]
    scalars = {
        "d_moe_rows_local": 256.0, "d_moe_expert_groups": 16.0, "d_attn_pairs": 4e6,
        "d_attn_kv_positions": 4e5, "peak_flops": 197e12, "peak_bytes_per_s": 819e9,
        "window_s": 2.0, "wall_s": 4.0,
    }
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert set(NEW_METRICS) <= set(specs)
    ctx = {"scalars": scalars, "events": events, "window": (0.0, 10.0)}
    gmm = layer_metrics.read_metric(specs["gmm_roofline"]["reader"], ctx)
    assert gmm == pytest.approx(100 * (6 * 4096**2 * 16 / 819e9) * 0.5 / 0.004)
    attn = layer_metrics.read_metric(specs["ragged_paged_attention_roofline"]["reader"], ctx)
    assert attn == pytest.approx(100 * (4096 * 4e5 / 819e9) * 0.5 / 0.002)
    # A program without the counters (the parent) or without the kernels:
    # nothing to read, no error.
    bare = {"scalars": {k: v for k, v in scalars.items() if not k.startswith("d_")},
            "events": events, "window": (0.0, 10.0)}
    assert all(layer_metrics.read_metric(specs[n]["reader"], bare) is None for n in NEW_METRICS)
    assert layer_metrics.read_metric(specs["gmm_roofline"]["reader"], {**ctx, "events": events[1:2]}) is None
