"""The command: fails off the chip, and its last line is the contract's."""

import json
import os
import subprocess
import sys

import pytest

from chipbench.tests.tiny import BENCH

ROOT = BENCH.parent
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def run(script, *args):
    return subprocess.run(
        [sys.executable, str(script), *args], cwd=ROOT, env=ENV,
        capture_output=True, text=True, timeout=900,
    )


def test_exits_nonzero_on_the_cpu_and_prints_no_result():
    done = run(BENCH / "run.py", "--workload", "small.train", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
    assert "needs 1 tpu device" in done.stderr


def test_unknown_cell_exits_nonzero():
    done = run(BENCH / "run.py", "--workload", "nope", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and '"correct"' not in done.stdout


@pytest.mark.parametrize(
    "cell, trace, e2e",
    [
        ("small.train", "0", {"setup_s", "train.tok_s_chip"}),
        ("small.serve.decode-heavy", "0",
         {"setup_s", "serve.out_tok_s", "serve.tpot_ms.p95"}),
        ("medium.serve.prefill-heavy", "1", None),
    ],
)
def test_last_line_is_the_result_and_nothing_follows(cell, trace, e2e):
    done = run(BENCH / "tests" / "drive_tiny.py", "--workload", cell,
               "--seed", str(2**31 + 77), "--seconds", "1.5", "--trace", trace)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    # Every line of stdout is the benchmark's own JSON; the program's
    # narration (train()'s log lines among it) went to stderr.
    parsed = [json.loads(line) for line in lines]
    assert all("info" in obj for obj in parsed[:-1])
    result = parsed[-1]
    assert set(result) == KEYS | ({"breakdown"} if trace == "1" else set())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for value in result["metrics"].values():
        assert set(value) == {"value", "unit"} and isinstance(value["value"], float)
    if e2e is not None:
        assert set(result["metrics"]) == e2e
    else:
        # On the CPU no device plane exists, so the trace readers find
        # nothing and are left out; spans and counters are read.
        assert {"sched.queue_wait_ms.p95", "engine.tick_ms.mean"} <= set(result["metrics"])
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert {"busy_s", "window_s"} <= set(result["device"])
    compared = [o for o in parsed if o.get("info") == "correct"][0]["compared"]
    assert all({"number", "value", "limit", "ok"} <= set(row) for row in compared)
    # The same numbers come last in the result's line and end standard error.
    assert list(result)[-1] == "compared"
    assert result["compared"] == {
        row["number"]: {"value": row["value"], "limit": row["limit"]} for row in compared
    }
    tail = done.stderr.strip().splitlines()[-len(compared):]
    assert [line.split()[1].rstrip(":") for line in tail] == [r["number"] for r in compared]
