"""Tiny sizes for the CPU: TS_TEST_CONFIG's numbers as a configuration
file's dict, and the cells' workload files cut down to them."""

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def metrics_of_cell(cell: str) -> set:
    """The per-layer metrics the harness evaluates in a cell, by its own
    rule: a metric file lists the cell, or the cell's workload file takes
    the metric up under ``layer_metrics``."""
    from chipbench import layer_metrics

    workload = json.loads((BENCH / "workloads" / f"{cell}.json").read_text())
    return set(layer_metrics.load_metrics(
        BENCH / "layer_metrics", cell, also=workload.get("layer_metrics", ())
    ))

TINY_CONFIG = {
    "vocab_size": 10000, "context_length": 16, "d_model": 64, "num_layers": 3,
    "num_heads": 4, "d_ff": 128, "rope_theta": 10000.0,
    "activation_dtype": "float32",
    "architecture_keys": [
        "vocab_size", "context_length", "d_model", "num_layers", "num_heads",
        "d_ff", "rope_theta", "activation_dtype",
    ],
}


def tiny_train(activation_dtype="float32"):
    wl = json.loads((BENCH / "workloads" / "small.train.json").read_text())
    wl["train"].update(batch_size=4, log_every=5)
    wl["data"]["n_tokens"] = 20000
    wl["trace_seconds"] = 1.0
    wl["correct"]["reference_rows_per_block"] = 2
    return wl, {**TINY_CONFIG, "activation_dtype": activation_dtype}


def tiny_serve(name="small.serve.decode-heavy"):
    wl = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    wl["serve"]["engine"].update(slots=4, block_size=4, prefill_buckets=[8, 12])
    wl["serve"].update(warm_buckets=[8, 12], ramp_s=0.5)
    if wl["traffic"]["arrival"]["kind"] == "closed":
        wl["traffic"]["arrival"]["clients"] = 4
    else:
        wl["traffic"]["arrival"]["rate"] = 20.0
    wl["traffic"]["prompt_len"].update(lo=3, hi=8)
    wl["traffic"]["output_len"].update(lo=2, hi=7)
    wl["traffic"].update(max_total=15, n_sizes=16)
    wl["trace_seconds"] = 1.0
    return wl, dict(TINY_CONFIG)
