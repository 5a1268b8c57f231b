#!/usr/bin/env python
"""Gate: every telemetry record kind emitted anywhere in the package must be
documented in ``telemetry/schema.py`` (and its prose table in the docs).

Three checks, all static/jax-free (wired into tier-1 via
``tests/test_telemetry.py``, runnable standalone):

1. **Source sweep** — grep ``bpe_transformer_tpu/`` (every subpackage: the
   ``resilience/`` emitters' preemption/recovery kinds included, plus
   ``chip_smoke.py``, ``benchmarks/`` and ``tools/``) for every
   ``"kind": "..."`` / ``kind="..."`` literal an emitter writes; each must
   be a key of ``RECORD_SCHEMAS``.  A new record kind cannot ship
   undocumented.
2. **Docs sweep** — every documented kind must appear in the
   ``ARCHITECTURE.md`` and ``README.md`` record-kind tables.
3. **Fixture validation** — every record in the committed
   ``tests/fixtures/*.jsonl`` streams must validate against its kind's
   required-field schema (the fixtures are the pinned wire format).
4. **Trace-exporter assumptions** — every field ``telemetry/trace.py``
   reads (its ``TRACE_ASSUMPTIONS``) must be a required field of the
   corresponding kind, so a schema change cannot silently break the
   Chrome trace export.
5. **Fixture coverage** — every registered kind must appear in at least
   one committed ``tests/fixtures/*.jsonl`` stream (the pinned wire
   format): a kind nobody pins is a kind whose renderers regress
   silently.  (``metric`` is exempt from the literal-kind grep — it is
   the pseudo-kind of the kind-less step records, matched by a bare
   ``"step"`` + ``"loss"`` record instead.)

Exit 0 when clean; 1 with one line per violation otherwise.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from bpe_transformer_tpu.telemetry.schema import (  # noqa: E402
    RECORD_SCHEMAS,
    validate_record,
)

#: ``{"kind": "span"}`` / ``dict(...)["kind"] = "x"`` string-literal record
#: kinds.  Kwarg spellings (``run_manifest(kind="serve")``) are deliberately
#: NOT swept: in this codebase they name run kinds (train/serve/bench), not
#: record kinds — every record-kind emitter writes the dict-literal form.
_KIND_DICT = re.compile(r'["\']kind["\']\s*:\s*["\'](\w+)["\']')


def emitted_kinds() -> dict[str, list[str]]:
    """record kind -> source locations that emit it."""
    kinds: dict[str, list[str]] = {}
    roots = [REPO / "bpe_transformer_tpu", REPO / "benchmarks", REPO / "tools"]
    files = [p for root in roots for p in sorted(root.rglob("*.py"))]
    files += [REPO / "chip_smoke.py"]
    for path in files:
        if path == Path(__file__).resolve():
            continue
        try:
            text = path.read_text(encoding="utf-8")
        except OSError:
            continue
        for match in _KIND_DICT.finditer(text):
            line = text[: match.start()].count("\n") + 1
            kinds.setdefault(match.group(1), []).append(
                f"{path.relative_to(REPO)}:{line}"
            )
    return kinds


def check_source() -> list[str]:
    problems = []
    for kind, where in sorted(emitted_kinds().items()):
        if kind not in RECORD_SCHEMAS:
            problems.append(
                f"undocumented record kind {kind!r} emitted at "
                f"{', '.join(where[:3])} — add it to "
                "bpe_transformer_tpu/telemetry/schema.py and the docs tables"
            )
    return problems


def check_docs() -> list[str]:
    problems = []
    for doc in ("ARCHITECTURE.md", "README.md"):
        try:
            text = (REPO / doc).read_text(encoding="utf-8")
        except OSError:
            problems.append(f"{doc} missing — the schema table lives there")
            continue
        for kind in RECORD_SCHEMAS:
            if f"`{kind}`" not in text and f'"{kind}"' not in text:
                problems.append(
                    f"{doc} does not document record kind {kind!r} "
                    "(record-kind table out of date)"
                )
    return problems


def check_fixtures() -> list[str]:
    problems = []
    for path in sorted((REPO / "tests" / "fixtures").glob("*.jsonl")):
        for lineno, line in enumerate(
            path.read_text(encoding="utf-8").splitlines(), start=1
        ):
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except json.JSONDecodeError:
                problems.append(f"{path.name}:{lineno}: unparseable JSON")
                continue
            if not isinstance(record, dict):
                problems.append(f"{path.name}:{lineno}: not a JSON object")
                continue
            for problem in validate_record(record):
                problems.append(f"{path.name}:{lineno}: {problem}")
    return problems


def check_fixture_coverage() -> list[str]:
    """Every registered record kind is exercised by a committed fixture."""
    from bpe_transformer_tpu.telemetry.schema import record_kind

    seen: set[str] = set()
    for path in sorted((REPO / "tests" / "fixtures").glob("*.jsonl")):
        for line in path.read_text(encoding="utf-8").splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(record, dict):
                seen.add(record_kind(record))
    problems = []
    for kind in RECORD_SCHEMAS:
        if kind not in seen:
            problems.append(
                f"record kind {kind!r} appears in no tests/fixtures/*.jsonl "
                "stream — add a fixture record so its renderers are pinned"
            )
    return problems


def check_trace_assumptions() -> list[str]:
    from bpe_transformer_tpu.telemetry.trace import TRACE_ASSUMPTIONS

    problems = []
    for kind, fields in sorted(TRACE_ASSUMPTIONS.items()):
        schema = RECORD_SCHEMAS.get(kind)
        if schema is None:
            problems.append(
                f"trace exporter assumes record kind {kind!r}, which is "
                "not in the schema registry"
            )
            continue
        missing = sorted(fields - schema)
        if missing:
            problems.append(
                f"trace exporter reads {kind!r} field(s) "
                f"{', '.join(missing)} that the schema does not require — "
                "align telemetry/trace.py TRACE_ASSUMPTIONS with "
                "telemetry/schema.py"
            )
    return problems


def main() -> int:
    problems = (
        check_source()
        + check_docs()
        + check_fixtures()
        + check_trace_assumptions()
        + check_fixture_coverage()
    )
    for problem in problems:
        print(f"telemetry-schema: {problem}", file=sys.stderr)
    if not problems:
        kinds = ", ".join(sorted(RECORD_SCHEMAS))
        print(f"telemetry schema clean ({kinds})")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
