"""``evabyte.serve.long-doc`` (ISSUE 40): the configuration file against the
catalog's numbers and the issue's arithmetic, the counts, the traffic and the
pool's size, a CPU rehearsal of the cell at tiny sizes through ``run_cell``,
the three new metric files, that every metric the cell reports lists it, and
that every file the benchmark had is as it was."""

import json
import subprocess

import pytest

from chipbench import counts_evabyte as counts
from chipbench import layer_metrics, run
from chipbench.tests.tiny import BENCH, metrics_of_cell

CELL = "evabyte.serve.long-doc"
PARENT = "dc46042f7d2742fcc2efef4560a5af6cc354ec7f"
NEW_METRICS = [
    "eva.summary_key_share.mean", "eva_attention_roofline", "eva.attention_share.tick",
]
NEW_FILES = {
    "chipbench/configs/EvaByte.json", f"chipbench/workloads/{CELL}.json",
    "chipbench/reference_evabyte.py", "chipbench/counts_evabyte.py",
    "chipbench/tests/test_evabyte_cell.py",
    *(f"chipbench/layer_metrics/{name}.json" for name in NEW_METRICS),
}
CATALOG = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False,
    "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096,
    "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
    "intermediate_size": 11008, "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32, "num_chunks": None,
    "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False,
    "vocab_size": 320, "window_size": 2048,
}


def load():
    return run.load_cell(CELL)


def tiny_cell():
    """The cell's files cut to a hidden size of 64: 4 heads of 16, SwiGLU of
    96, 2 layers, 2 prediction heads of 40 bytes, windows of 32 in chunks of
    4 = blocks of 4, a context of 8 windows; 4 slots, chunks of 16."""
    workload, config = load()
    config.update(
        hidden_size=64, d_model=64, intermediate_size=96, d_ff=96,
        num_attention_heads=4, num_heads=4, num_key_value_heads=4,
        num_hidden_layers=2, num_layers=2, vocab_size=40, num_pred_heads=2,
        window_size=32, eva_window=32, chunk_size=4, eva_chunk=4,
        max_position_embeddings=256, context_length=256, init_std=0.05,
        activation_dtype="float32",
    )
    workload["serve"]["engine"].update(
        slots=4, max_queue=8, block_size=4, prefill_chunk=16, prefill_token_budget=16,
        prefill_buckets=[8, 16], num_kv_blocks=None,
    )
    workload["serve"].update(warm_buckets=[8, 16], ramp_s=0.5)
    workload["traffic"]["arrival"].update(clients=4, stagger_s=0.2)
    workload["traffic"]["prompt_len"].update(lo=40, hi=150)
    workload["traffic"]["output_len"].update(lo=8, hi=40)
    workload["traffic"].update(max_total=200, n_sizes=16, closed_plan=64, greedy_every=2)
    workload["trace_seconds"] = 1.0
    # The limit's tiny twin: float32 against float32 reads rounding error.
    workload["correct"]["served_logit_gap"] = 2e-5
    return workload, config


def test_counts_pin_the_issues_numbers():
    _, cfg = load()
    assert counts.layer_params(cfg) == 202_391_552
    assert counts.embedding_params(cfg) == 320 * 4096 == 1_310_720
    assert counts.head_params(cfg) == 4096 * 2560 == 10_485_760
    assert counts.params_held(cfg) == pytest.approx(1.631e9, rel=1e-3)
    assert 2 * counts.params_held(cfg) == pytest.approx(3.26e9, rel=2e-3)
    assert 32 * counts.layer_params(cfg) + 1_310_720 + 10_485_760 == pytest.approx(6.488e9, rel=1e-4)
    assert counts.row_bytes(cfg) == 16_384 and counts.kv_bytes_per_token(cfg) == 131_072
    # At 26.6k bytes of context: 12 closed windows' summaries, the open
    # window's pending ones and its exact rows; K/V of every position 3.49 GB.
    assert counts.summaries_visible(cfg, 26_623) == 12 * 128
    assert counts.rows_attended(cfg, 26_623) == 12 * 128 + 2048
    assert counts.rows_held(cfg, 26_624) == 12 * 128 + 127 + 2048
    assert 26_624 * 131_072 == pytest.approx(3.49e9, rel=1e-3)
    # A tick's rows: what the issue's sizing line uses (~1,730 at ~12k).
    assert counts.rows_attended(cfg, 11_999) == 5 * 128 + 11_999 % 2048 + 1
    # Pairs of a prompt are the sum of its positions' rows.
    for n in (1, 2047, 2048, 2049, 5000):
        assert counts.prompt_pairs(cfg, n) == sum(counts.rows_attended(cfg, i) for i in range(n))
    near, far = (counts.forward_flops(cfg, 1, c, 1) for c in (2048 + 300, 2048 + 1300))
    assert far - near == pytest.approx(4 * 4096 * 8 * 1000)
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert "16384 * d_attn_kv_positions" in specs["eva_attention_roofline"]["reader"]["expr"]
    assert counts.eva_attention_bytes(cfg, 7) == 16384 * 7
    # Bandwidth-bound at the chip's peaks: bytes take longer than FLOPs.
    assert counts.eva_attention_bytes(cfg, 1) / 819e9 > counts.eva_attention_flops(cfg, 1) / 197e12


def test_configuration_file_holds_the_published_numbers():
    _, cfg = load()
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 8
    assert cfg["published"]["num_hidden_layers"] == 32 and "4 chips" in cfg["deployment"]
    assert {"key_pooling_logits", "head_layout", "pooling_init", "rope_convention",
            "seeded_values", "precision"} <= set(cfg["assumed"])
    # No implementation choice: every program key is a published width's name.
    assert not {"decode_attention_impl", "attention_impl", "ffn_impl"} & set(cfg)
    model = run.program_model_config(cfg)
    assert (model.d_model, model.num_heads, model.d_head, model.d_ff) == (4096, 32, 128, 11008)
    assert (model.eva_window, model.eva_chunk, model.context_length) == (2048, 16, 32768)
    assert (model.vocab_size, model.num_pred_heads, model.head_width) == (320, 8, 2560)
    assert model.eva_block and model.norm_unit_offset and not model.tie_embeddings
    assert (model.num_layers, model.rope_theta) == (8, 100000)
    declared = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in declared["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = [w for w in declared["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and cell["traffic"] == "serve.long-doc"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # Every metric the cell reports lists it, and no other does.
    listed = {m["name"] for m in declared["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == metrics_of_cell(CELL)
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
    # Every prompt closes 2-12 windows before its first tick.
    assert 2 <= (sizes[:, 0] // 2048).min() and (sizes[:, 0] // 2048).max() <= 12
    plan = traffic.plan_requests(workload["traffic"], 320, 2**31 + 40, 70.0)
    assert len(plan) == 1024 and sum(p.greedy for p in plan) == 256  # every fourth
    assert all(0 <= t < 320 for t in plan[0].prompt_ids)
    assert len({p.prompt_ids[:64] for p in plan[:40]}) == 40           # no shared prefix
    assert workload["trace_seconds"] == 3.0
    assert (workload["serve"]["ramp_s"], workload["traffic"]["arrival"]["stagger_s"]) == (24.0, 16.0)
    engine = workload["serve"]["engine"]
    assert (engine["slots"], workload["traffic"]["arrival"]["clients"]) == (32, 32)
    assert engine["prefix_cache"] is False and engine["block_size"] == 16
    assert (engine["prefill_chunk"], engine["prefill_token_budget"]) == (2048, 2048)
    assert engine["prefill_buckets"] == workload["serve"]["warm_buckets"] == [512, 1024, 2048]
    assert workload["traffic"]["sampling"] == {"temperature": 1.0, "top_k": 50}
    # No admission waits for a block: a slot holds a window (128 blocks)
    # and 8 blocks a window it lives to close; the pool holds the 32
    # largest of two blocks of the schedule at once.
    closed = (sizes.sum(axis=1) - 1) // 2048
    worst = sorted(list(closed) * 2)[-32:]
    assert engine["num_kv_blocks"] - 1 == 32 * 128 + 8 * sum(worst) == 5824
    assert (engine["num_kv_blocks"] - 1) * 16 * 131_072 == pytest.approx(12.21e9, rel=1e-3)


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_rehearsal_through_run_cell(trace):
    workload, config = tiny_cell()
    out = run.run_cell(
        workload, config, name=CELL, seed=2**31 + 40, seconds=4.0, trace=trace,
        emit=lambda o: None, expect_platform="cpu",
    )
    assert out["correct"] is True and out["failed"] == 0
    if not trace:
        assert set(out["metrics"]) == {"setup_s", "serve.out_tok_s", "serve.tpot_ms.p95"}
        return
    assert 0 < out["metrics"]["eva.summary_key_share.mean"]["value"] < 100
    assert out["metrics"]["kvpool.used_share.peak"]["value"] > 0
    assert "eva_attention_roofline" not in out["metrics"]  # no device events on the CPU
    assert set(workload["layer_metrics"]) <= set(out["metrics"]) | {"device.idle_share.serve"}


def test_the_float8_control_fails_the_limits_tiny_twin():
    from chipbench import control

    out = control.read(lambda name: tiny_cell(), CELL, [2**31 + 41], 4.0,
                       expect_platform="cpu", log=lambda line: None)
    sound = out["sound_largest"]["served_logit_widest_gap"]
    low = out["control_smallest"]["served_logit_widest_gap"]
    assert out["correct"] == [True] and sound < 2e-5 < low, (sound, low)


def test_kernel_shares_read_their_kernels_events_and_nothing_on_the_parent():
    plane, line = "/device:TPU:0", "XLA Ops"
    events = [
        (plane, line, "%paged_decode_attention.4 = f32[32,1,4096]{2,1,0} custom-call(%a, %b)", 1.0, 0.002),
        (plane, line, "%fusion.9 = bf16[32,4096]{1,0} fusion(%x)", 1.004, 0.01),
    ]
    scalars = {
        "d_attn_kv_positions": 8 * 24 * 1700.0, "d_attn_summary_kv_positions": 8 * 24 * 640.0,
        "peak_flops": 197e12, "peak_bytes_per_s": 819e9, "window_s": 2.0, "wall_s": 2.0,
        "busy_s": 0.5,
    }
    specs = layer_metrics.load_metrics(BENCH / "layer_metrics", CELL)
    assert set(NEW_METRICS) <= set(specs)
    ctx = {"scalars": scalars, "events": events, "window": (0.0, 10.0)}
    read = lambda name, c=ctx: layer_metrics.read_metric(specs[name]["reader"], c)  # noqa: E731
    assert read("eva_attention_roofline") == pytest.approx(
        100 * (16384 * 8 * 24 * 1700 / 819e9) / 0.002
    )
    assert read("eva.attention_share.tick") == pytest.approx(100 * 0.002 / 0.5)
    assert read("eva.summary_key_share.mean") == pytest.approx(100 * 640 / 1700)
    # A program without the counters (the parent) or without the kernel:
    # nothing to read, no error.
    bare = {"scalars": {k: v for k, v in scalars.items() if not k.startswith("d_")},
            "events": events[1:], "window": (0.0, 10.0)}
    assert all(read(name, bare) is None for name in NEW_METRICS)


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
        assert all(CELL in e.get("workloads", [CELL]) or e["name"] in (CELL, "EvaByte")
                   for e in new[len(old):])
