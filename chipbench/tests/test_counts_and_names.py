"""The counts against the numbers of ISSUE 24, the peaks table, and the
names and units of BENCHMARK.json against the contract's alphabet."""

import json
import re

import pytest

from chipbench import counts
from chipbench.tests.tiny import BENCH, metrics_of_cell

ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize(
    "name, flops, kv_bytes",
    [("gpt2-small-32k", 714e6, 36864), ("gpt2-medium", 2.16e9, 98304)],
)
def test_counts_match_the_issue(name, flops, kv_bytes):
    cfg = config(name)
    assert counts.train_flops_per_token(cfg) == pytest.approx(flops, rel=2e-3)
    assert counts.kv_bytes_per_token(cfg) == kv_bytes


def test_weight_bytes_and_causal_attention():
    cfg = config("gpt2-small-32k")
    assert counts.matmul_weight_bytes(cfg) == pytest.approx(0.219e9, rel=2e-3)
    # Causal, not the full square: a full S x S count is 8% higher.
    s = cfg["context_length"]
    full = 3 * counts.forward_flops(cfg, s, s * s, s) / s
    assert 1.06 < full / counts.train_flops_per_token(cfg) < 1.09
    # One decoded token at position p needs what position p of a prefill does.
    assert counts.serve_flops(cfg, [(10, 1)]) == counts.forward_flops(cfg, 10, 55, 1)


def test_peaks_table():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert (v5e["flops_bf16"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert "Google Cloud" in v5e["source"]


def test_benchmark_json_names_units_and_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    for cfg in bench["configs"]:
        assert NAME.match(cfg["name"]) and (ROOT / cfg["file"]).is_file()
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert "setup_s" in e2e and len(e2e) <= 5
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
        assert (BENCH / "workloads" / f"{cell['name']}.json").is_file()
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(metric["name"]), metric["name"]
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert set(metric.get("workloads", ())) <= cells
    for metric in bench["per_layer"]:
        assert metric["moves"] in e2e
        spec = json.loads((BENCH / "layer_metrics" / f"{metric['name']}.json").read_text())
        for key in ("layer", "unit", "better", "moves", "source"):
            assert spec[key] == metric[key], (metric["name"], key)
        # Declared for a cell = read there: the metric's file lists the cell
        # or the cell's own file takes the metric up.
        assert set(spec["workloads"]) <= set(metric["workloads"])
        assert all(metric["name"] in metrics_of_cell(c) for c in metric["workloads"])
