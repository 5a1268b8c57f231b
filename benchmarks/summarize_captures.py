"""One-screen summary of every persisted TPU capture.

Reads benchmarks/captures/*.json (the `tpu_capture_*.json` per-config
captures of the bench.py deleted in PR 22, northstar.json) and the
attention/decode/breakdown/moe-dispatch JSONL files, and prints a compact
table per group — what's measured, when, and at what knobs.  Pure
host-side file reads: safe to run any time (no jax).

    python benchmarks/summarize_captures.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

CAP = Path(__file__).resolve().parent / "captures"


def _rows(path: Path):
    try:
        with path.open() as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        yield json.loads(line)
                    except json.JSONDecodeError:
                        pass
    except OSError:
        return


def _manifest_line(m: dict | None) -> str | None:
    """Compact provenance from a telemetry run-manifest record (the
    ``kind="manifest"`` header every training/benchmark stream now writes,
    also embedded as ``"manifest"`` in the per-config and northstar captures)."""
    if not m:
        return None
    parts = [f"git={str(m.get('git_sha'))[:12]}"]
    if m.get("jax_version"):
        parts.append(f"jax={m['jax_version']}")
    devices = m.get("devices") or {}
    if devices:
        parts.append(
            f"{devices.get('count', '?')}x{devices.get('kind', '?')}"
            f" ({devices.get('platform', '?')})"
        )
    if m.get("mesh"):
        parts.append(f"mesh={m['mesh']}")
    if m.get("parallel"):
        parts.append(f"parallel={m['parallel']}")
    if m.get("host"):
        parts.append(f"host={m['host']}")
    return "  ".join(parts)


def main() -> int:
    if not CAP.exists():
        print("no captures directory", file=sys.stderr)
        return 1

    print("== per-config captures (tokens/sec/chip) ==")
    for p in sorted(CAP.glob("tpu_capture_*.json")):
        try:
            c = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError) as exc:
            print(f"  {p.name}: unreadable ({exc!r})")
            continue
        knobs = [f"att={c.get('attention_impl', '?')}"]
        for key in ("ffn_impl", "moe_dispatch"):
            if c.get(key) not in (None, "xla", "einsum"):
                knobs.append(f"{key}={c[key]}")
        policy = c.get("remat_policy") or ("full" if c.get("remat") else None)
        if policy and policy != "none":
            knobs.append(f"remat={policy}")
        if c.get("scan_layers"):
            knobs.append("scan_layers")
        if c.get("grads_dtype") not in (None, "float32"):
            knobs.append(f"grads={c['grads_dtype']}")
        print(
            f"  {p.name[12:-5]:28s} {c.get('value') or 0:>12,.0f} tok/s"
            f"  mfu={c.get('mfu')}  vs_torch={c.get('vs_baseline')}"
            f"  B={c.get('batch')} steps={c.get('measure_steps')}"
            # `or '?'` not a .get default: the key can be present with a JSON
            # null, and None[:16] would kill the whole summary.
            f"  @{(c.get('captured_at_utc') or '?')[:16]}  [{', '.join(knobs)}]"
        )
        provenance = _manifest_line(c.get("manifest"))
        if provenance:
            print(f"    {provenance}")

    ns = CAP / "northstar.json"
    print("== north star ==")
    if ns.exists():
        try:
            c = json.loads(ns.read_text())
            print(
                f"  platform={c.get('platform')}  "
                f"val jax={c['final_val_loss']['jax']:.4f} vs "
                f"torch={c['final_val_loss']['torch_cpu']:.4f}  "
                f"reached={c.get('reached_reference')}  "
                f"speedup={c.get('speedup')}x  @{(c.get('captured_at_utc') or '?')[:16]}"
            )
            provenance = _manifest_line(c.get("manifest"))
            if provenance:
                print(f"    {provenance}")
        except (OSError, json.JSONDecodeError, KeyError, TypeError) as exc:
            print(f"  unreadable ({exc!r})")
    else:
        print("  (not yet captured — torch half lives in northstar_torch.json)")

    for name, keys in (
        ("attention.jsonl", ("metric", "speedup", "speedup_bwd")),
        ("decode.jsonl", ("metric", "speedup")),
        ("moe_dispatch.jsonl", ("metric", "speedup")),
        ("breakdown.jsonl", ("stage", "ms", "config")),
        (
            "host_tokenization.jsonl",
            (
                "stage",
                "engine",
                "n_workers",
                "pretokens_per_s",
                "tokens_per_s",
                "speedup",
                # The trailing summary row carries the grid's provenance —
                # whether these are real multicore rows or a collapsed
                # single-core grid.
                "usable_cores",
                "captured_at_utc",
            ),
        ),
    ):
        path = CAP / name
        rows = list(_rows(path))
        # Unified-telemetry streams open with a run-manifest header (and may
        # close with a footer): surface the provenance once, keep the data
        # rows as before.
        manifests = [r for r in rows if r.get("kind") == "manifest"]
        rows = [r for r in rows if r.get("kind") not in ("manifest", "footer")]
        print(f"== {name} ({len(rows)} rows) ==")
        if manifests:
            print(f"    {_manifest_line(manifests[-1])}")
        # 20, not 12: a full multicore host-tokenization grid is 14+ rows
        # and truncating it would cut the python-engine rows the
        # native-vs-python comparison needs (review r5).
        for r in rows[-20:]:
            print("  " + "  ".join(f"{k}={r.get(k)}" for k in keys if k in r))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # `... | head` closing early is fine
        sys.exit(0)
