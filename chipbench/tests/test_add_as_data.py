"""A cell, a configuration and a per-layer metric added as new files are
found by name, with no edit to ``run.py`` or to any file that is there."""

import json
import shutil

from chipbench import run
from chipbench.tests import tiny


def test_new_cell_config_and_metric_are_found_by_name(tmp_path, monkeypatch):
    bench = tmp_path / "chipbench"
    for sub in ("workloads", "configs", "layer_metrics"):
        shutil.copytree(tiny.BENCH / sub, bench / sub)
    # A new configuration: its file of sizes (no preset of the program).
    config = {**tiny.TINY_CONFIG, "name": "tiny-new", "source": "test", "reduced": []}
    (bench / "configs" / "tiny-new.json").write_text(json.dumps(config))
    # A new cell on it: a traffic mix that is only data.
    workload, _ = tiny.tiny_serve("medium.serve.prefill-heavy")
    workload["config"] = "tiny-new"
    workload["traffic"]["output_len"] = {"dist": "fixed", "lo": 3, "hi": 3}
    workload["layer_metrics"] = ["engine.tick_ms.mean"]  # one that is there
    (bench / "workloads" / "tiny.new-cell.json").write_text(json.dumps(workload))
    # A new per-layer metric: one file with a declarative reader.
    (bench / "layer_metrics" / "engine.requests_per_tick.json").write_text(json.dumps({
        "layer": "engine decode tick", "unit": "requests", "better": "higher",
        "moves": "serve.out_tok_s", "source": "program_counter",
        "workloads": ["tiny.new-cell"],
        "reader": {"kind": "formula", "expr": "d_requests_finished / d_ticks"},
    }))
    monkeypatch.setattr(run, "HERE", bench)
    wl, cfg = run.load_cell("tiny.new-cell")
    assert cfg["name"] == "tiny-new"
    out = run.run_cell(wl, cfg, name="tiny.new-cell", seed=9, seconds=1.0,
                       trace=True, emit=lambda o: None, expect_platform="cpu")
    assert out["correct"] is True
    assert out["metrics"]["engine.requests_per_tick"]["unit"] == "requests"
    assert "engine.tick_ms.mean" in out["metrics"]
    assert "sched.queue_wait_ms.p95" not in out["metrics"]  # lists other cells
