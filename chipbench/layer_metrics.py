"""Per-layer metrics as data: one ``layer_metrics/<name>.json`` per metric.

A file gives ``layer``, ``unit``, ``better``, ``moves``, ``workloads`` (the
same fields as its ``BENCHMARK.json`` entry) and a declarative ``reader``.
The harness hands every reader the same *context* of a traced run and
implements four kinds of reader once:

``formula``
    ``{"kind": "formula", "expr": "1000 * busy_s / steps"}`` — arithmetic
    (+ - * / parentheses, ``min``/``max``) over the context's scalars.
``records``
    ``{"kind": "records", "select": {"kind": "span", "name": "prefill"},
    "field": "dur_s", "reduce": "p50", "scale": 1000}`` — a field of the
    program's telemetry records inside the window, reduced by ``p50``,
    ``p95``, ``mean``, ``max`` or ``sum``.
``stats_samples``
    ``{"kind": "stats_samples", "expr": "1 - kv_blocks_free /
    kv_blocks_total", "reduce": "max", "scale": 100}`` — a formula over each
    sample of ``ServingEngine.stats()`` taken in the window, reduced.
``trace_ops``
    ``{"kind": "trace_ops", "pattern": "fusion", "expr": "1000 * ops_s /
    steps"}`` — device time of trace ops whose name matches, as ``ops_s``,
    then a formula.

Context scalars (where the run has them): ``window_s`` and ``busy_s`` (the
traced part of the window; busy is the union of device-op intervals,
averaged over chips), ``wall_s`` (harness clock over the same part),
``steps`` in it (train), ``d_<key>`` for the change of every numeric
``stats()`` key over it (serve: ``d_ticks``, ``d_tokens_emitted``, ...), the counts of ``counts.py`` the cell computes
(``flops_required``, ``weight_bytes``), and ``peak_flops`` /
``peak_bytes_per_s`` from ``peaks.json`` for the device the run is on.

A reader that finds nothing to read — a name the context lacks, no record
selected, a zero divisor — returns None and the harness leaves the metric
out of the line.  A later PR adds a metric by adding one file.
"""

from __future__ import annotations

import ast
import json
import operator
import statistics
from pathlib import Path

_BIN = {
    ast.Add: operator.add, ast.Sub: operator.sub,
    ast.Mult: operator.mul, ast.Div: operator.truediv,
}
_CALLS = {"min": min, "max": max}


class Missing(Exception):
    """The context lacks what the reader needs."""


def evaluate(expr: str, names: dict) -> float:
    """Arithmetic over ``names``; raises Missing for an unknown name or a
    zero divisor, ValueError for anything that is not arithmetic."""

    def walk(node):
        if isinstance(node, ast.Expression):
            return walk(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
            return node.value
        if isinstance(node, ast.Name):
            value = names.get(node.id)
            if not isinstance(value, (int, float)) or isinstance(value, bool):
                raise Missing(node.id)
            return value
        if isinstance(node, ast.BinOp) and type(node.op) in _BIN:
            left, right = walk(node.left), walk(node.right)
            if isinstance(node.op, ast.Div) and right == 0:
                raise Missing("zero divisor")
            return _BIN[type(node.op)](left, right)
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -walk(node.operand)
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id in _CALLS
            and not node.keywords
        ):
            return _CALLS[node.func.id](*[walk(a) for a in node.args])
        raise ValueError(f"not arithmetic: {ast.dump(node)}")

    value = float(walk(ast.parse(expr, mode="eval")))
    if value != value:
        raise Missing("not a number")
    return value


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def reduce_values(values, how: str) -> float:
    if not values:
        raise Missing("no values")
    if how == "mean":
        return statistics.fmean(values)
    if how == "max":
        return max(values)
    if how == "sum":
        return float(sum(values))
    if how.startswith("p") and how[1:].isdigit():
        return percentile(values, float(how[1:]))
    raise ValueError(f"unknown reduction {how!r}")


def read_metric(reader: dict, ctx: dict):
    """The metric's value from the run's context, or None."""
    try:
        kind = reader["kind"]
        scale = reader.get("scale", 1)
        if kind == "formula":
            return scale * evaluate(reader["expr"], ctx["scalars"])
        if kind == "records":
            select = reader["select"]
            values = [
                r[reader["field"]]
                for r in ctx.get("records", ())
                if all(r.get(k) == v for k, v in select.items())
                and isinstance(r.get(reader["field"]), (int, float))
            ]
            return scale * reduce_values(values, reader["reduce"])
        if kind == "stats_samples":
            values = []
            for sample in ctx.get("stats_samples", ()):
                try:
                    values.append(evaluate(reader["expr"], sample))
                except Missing:
                    continue
            return scale * reduce_values(values, reader["reduce"])
        if kind == "trace_ops":
            from chipbench import reduce_trace

            ops_s = reduce_trace.matching_seconds(
                ctx.get("events", ()), reader["pattern"], ctx.get("window")
            )
            if ops_s is None:
                return None
            return scale * evaluate(
                reader["expr"], {**ctx["scalars"], "ops_s": ops_s}
            )
        raise ValueError(f"unknown reader kind {kind!r}")
    except Missing:
        return None


def load_metrics(directory: Path, workload: str, also=()) -> dict:
    """``{name: spec}`` of every metric file that lists ``workload``, and of
    those the workload's own file names under ``layer_metrics`` (how a cell
    added later takes up metrics that are already there)."""
    out = {}
    for path in sorted(directory.glob("*.json")):
        spec = json.loads(path.read_text())
        if workload in spec.get("workloads", ()) or path.stem in also:
            out[path.stem] = spec
    return out


def evaluate_all(directory: Path, workload: str, ctx: dict, also=()) -> dict:
    """``{name: {"value", "unit"}}`` for every metric of the cell that finds
    something to read."""
    out = {}
    for name, spec in load_metrics(directory, workload, also).items():
        value = read_metric(spec["reader"], ctx)
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out
