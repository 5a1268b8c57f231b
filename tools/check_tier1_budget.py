#!/usr/bin/env python
"""Guard: the tier-1 (``-m 'not slow'``) suite must stay inside its wall
budget.

The driver gives tier-1 a hard timeout (1,470 s with ``-n 6``
as of PR 22 — the commands it really runs are in
``/root/TESTS_LAST_RUN.json``; ROADMAP's "Tier-1 verify" line is older);
PR 9 already had to sweep 27 heavy tests behind ``slow`` to fit it, and
every PR since has grown the suite.  A suite that silently creeps past
the budget doesn't fail gracefully — it gets KILLED mid-run and reports
whatever happened to finish.  This tool makes the creep loud *before*
that happens, two jax-free ways:

* **Log mode** (default, given a pytest log file): parse the summary
  trailer (``... passed ... in 612.34s``) of a finished tier-1 run —
  e.g. the ``/tmp/_t1.log`` the ROADMAP verify command tees — and fail
  when the measured wall exceeds ``--budget`` (default 800 s; a whole
  run took 89-214 s at PR 22, so that is a wide margin under the kill).
* **Count mode** (``--collect``): run ``pytest --collect-only -q -m 'not
  slow'`` and fail when the tier-1 test COUNT exceeds ``--max-tests``
  (default: :data:`DEFAULT_MAX_TESTS`).  A proxy, not a measurement — but it runs in seconds,
  so it can gate a commit that adds a pile of unmarked tests without
  re-running the suite.  When the ceiling is hit legitimately (cheap
  tests), raise it here *in the same commit* that adds them — the point
  is that growth is a decision, not an accident.

Exit 0 within budget; 1 over budget (or unparseable log); 2 usage.
"""

from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: Wall budget for a finished tier-1 run (seconds) — well under the
#: driver's 1,470 s timeout, with margin for runner variance.
DEFAULT_BUDGET_S = 800.0

#: Tier-1 test-count ceiling for --collect mode.  ~430 tests ran in
#: ~640 s at PR 10 on a 2-cpu runner (~1.5 s/test amortized); the ceiling
#: keeps headroom while catching a silent 20%+ jump.  Raised 520 -> 545
#: in PR 13 (deliberately, per the policy above) for the 11 tier-1
#: MFU-push tests (tests/test_mfu_push.py — remat-policy parity/ordering,
#: bf16 collective bytes, donation audit, peak-HBM gate).  Raised
#: 545 -> 570 in PR 17 for the self-healing control plane
#: (tests/test_controller.py decide/breaker/spawner pins, migration wire
#: v2 CRC+codec, router suspect quarantine, serving fault hooks, import
#: idempotency); its heavy fleet chaos e2e is marked slow.  Raised
#: 570 -> 630 in PR 22 (627 collected) for the v5e AOT kernel compiles
#: (tests/test_chip_compile.py, 34 cases, ~1-5 s each) and the chip_smoke
#: / compile-cache contract tests (tests/test_chip_smoke.py, 40 cases, no
#: model); tests/test_bench_capture.py (13) went with bench.py.  Raised
#: 630 -> 710 in PR 25 (700 collected) for tests/test_worker_phases.py (73
#: cases in 21 s: one parametrised case per worker phase, profiler event
#: and named scope, so each counts; the whole tier-1 run took 169 s).
#: Raised 710 -> 770 in PR 27 (754 collected) for the attention path/tile
#: table (tests/test_kernels.py: one parametrised row per preset shape and
#: per side of the line, 19 cases in 2 s), the bf16 kernel parity and
#: backward-tile cases, the run-manifest label, the AOT flash compiles at
#: the picked and the unaligned tiles, and the multi-chip lowerings of
#: "auto" attention for the described v5e:2x2 (5 cases, 1 s each).
#: 793 at PR 28 (whole run 228 s): the Cohere2-MoE block against its
#: reference, the window pool group's allocator, every refusal (31 cases,
#: 105 s in one process) and 8 AOT compiles of its two kernels (33 s).
#: Raised 820 -> 845 in PR 30 (828 collected, 29 added): the dense pool in
#: place - every pool program's aliasing from its own memory analysis, at
#: both pool widths, over an audited begin-to-release lifecycle (one case a
#: program), the compiler option its layered programs are jitted with
#: (tests/test_kvpool.py, 18 cases in 25 s), the parent's wire fixtures,
#: attention over pool rows against per-head attention
#: (tests/test_kernels.py, 4 cases), and the tick, chunk, copy-block and
#: inject-block programs compiled for the described v5e at gpt2-small-32k
#: widths and two layers, the tick once more without the option for the
#: executable's size (tests/test_chip_compile.py, 7 cases, 17-24 s a whole
#: program).  Raised 845 -> 880 in PR 32 (861 collected, 29 added; the
#: whole run 285 s with six workers): the paged-native kernel over ragged
#: key counts at the cells' head shapes and both pool widths, the table of
#: `decode_attention_path` and of the group size (tests/test_kernels.py, 19
#: cases in 27 s), a tick with idle slots against the XLA path and the new
#: `stats()` counters (tests/test_kvpool.py, 4 cases), and the kernel and
#: the 128-slot tick compiled for the described v5e (tests/
#: test_chip_compile.py, 6 cases, 1-10 s each).  Raised 880 -> 940 in PR 33
#: (907 collected, 46 added): the LongCat-Flash block against its reference
#: on every path, the share test, the counts and each refusal
#: (tests/test_longcatflash.py, 36 cases in about 100 s), and the latent
#: tick kernel, the grouped matmul at its widths and the two latent pool
#: programs compiled for the described v5e (tests/test_chip_compile.py, 10
#: cases, 1-12 s each).  Raised 940 -> 975 in PR 34 (942 collected, 33
#: added): the sort-free sampler against the sorting body as its oracle,
#: the `cond`s taken either way, the counters and a vacant slot's row
#: (tests/test_sampler.py, 29 cases in about 40 s), and the tick, a chunk
#: and the verify pass compiled for the described v5e with no sort and no
#: key crossing into a branch (tests/test_chip_compile.py, 4 cases and one
#: assertion in the latent programs', 2-8 s each).  Raised 975 -> 1040 in
#: PR 36 (1,004 collected, 62 added): the Granite-4.0-H block against its
#: reference on every path - the mixer's three forms, the dense cache, paged
#: chunks and ticks with a slot mid-prefill, a slot's next tenant, idle
#: slots bit for bit - the share test, the multipliers, the counters and
#: each refusal (tests/test_granitehybrid.py, 51 cases in about 165 s in
#: one process), and the state-update kernel, the grouped matmul and the
#: paged decode kernel at its widths and the two recurrent pool programs
#: compiled for the described v5e (tests/test_chip_compile.py, 11 cases,
#: 1-8 s each).  Raised 1040 -> 1075 in PR 38 (1,048 collected, 19 added):
#: the two dispatch phases in parts - each part an event inside its phase
#: under a profiler session, the parts adding up in every period - the
#: worker's off-CPU seconds, a forced collection in its period, a held
#: interpreter lock showing as off-CPU seconds (tests/test_worker_phases.py,
#: 15 cases in about 20 s) and the same parts from every cache kind
#: (tests/test_launch_ahead.py, 4 cases, 3-7 s each).  Raised 1075 -> 1150
#: in PR 40 (1,123 collected, 57 added): EvaByte's block against its
#: reference on every path - the whole sequence, paged chunks and ticks
#: across window closings with a slot mid-prefill, ticks alone, every row of
#: a chunk, the paged kernel interpreted, a slot's next tenant - the
#: visibility rule, the blocks and counters and each refusal
#: (tests/test_evabyte.py, 53 cases in about 100 s in one process), and the
#: paged decode kernel at its widths and the tick and two chunk programs
#: over the summary-and-window cache compiled for the described v5e
#: (tests/test_chip_compile.py, 4 cases, 3-9 s each); the whole run 468 s
#: with six workers.  Raised 1150 -> 1200 in PR 41 (1,157 collected, 33
#: added): the latent chunk's expanded kernel in interpret mode against the
#: absorbed loop and a plain float32 reference by bucket, end of the keys
#: and padding, the rule that chooses the form, the engine's two chunk
#: counters by hand (tests/test_longcatflash.py, 29 cases in about 50 s in
#: one process), and the kernel at the published widths compiled for the
#: described v5e (tests/test_chip_compile.py, 4 cases, 2-4 s each).
#: Raised 1200 -> 1275 in PR 42 (1,231 collected, 74 added): the block of
#: one-sublayer layers against its reference on every path - the grouped
#: mixer's three forms, the grouped state-update kernel interpreted at four
#: head layouts, the dense cache, paged chunks and ticks, the counters by
#: the pattern's own layers, the share test, nine parts of the block each
#: left out, every refusal with its message (tests/test_nemotronh.py, 61
#: cases in about 90 s with six workers), and the grouped kernel, the grouped
#: matmul and the paged decode kernel at its widths, the two pool programs
#: and the carry compiled for the described v5e (tests/test_chip_compile.py,
#: 13 cases, 1-10 s each); the whole run 558 s with six workers.
#: Raised 1275 -> 1325 in PR 44 (1,294 collected, 36 added): the state-update
#: kernel over the resting layout, interpreted and by its XLA twin, against
#: the recurrence written out in the old layout - six head layouts and two
#: tiles of rows, eight by group (tests/test_granitehybrid.py 11 more,
#: tests/test_nemotronh.py 12 more, a second or two each), the to / from pair
#: a bijection at six shapes, a chunk's end state read back through the pool
#: (8 cases), and the kernel at five shapes the packing does not fit compiled
#: for the described v5e (tests/test_chip_compile.py, 1-2 s each); the whole
#: run 573 s with six workers.
#: Raised 1325 -> 1400 in PR 46 (1,369 collected, 69 added): MiMo-V2.5's
#: block against its reference - the forward on two shares, prefill then
#: teacher-forced ticks through the two groups of rows at three prompt
#: lengths, each mechanism (the sink, two rotations' bases, the rotated
#: part, the value scale) left out in the forward and in the engine, the
#: share against the whole, the window group that is no reservation, the
#: counters by group, the two kernels in interpret mode (ten cases), the
#: cache kind by shape and not by spelling, every refusal
#: (tests/test_mimov2.py, 61 cases in about 75 s with six workers)
#: and the two kernels and the cell's tick and chunk programs compiled for
#: the described v5e (tests/test_chip_compile.py, 8 cases, 1-20 s each).
#: Raised 1400 -> 1450 in PR 47 (1,407 collected, 38 added): the chunk
#: kernel's walk inside the step - its interpreted cases 6 -> 30 (five
#: windows by sink by three geometries: a chunk of four query blocks, a
#: bucket under one block, a chain held in parts; a second each), the walk's
#: four integers against the pairs themselves and against their traced twin
#: at twelve geometries (tests/test_mimov2.py) and the 1,024 bucket compiled
#: for the described v5e (tests/test_chip_compile.py, 2 cases); the whole
#: run 607 s with six workers.
#: Raised 1450 -> 1525 in PR 49 (1,475 collected, 59 added): sarvam-105b's
#: block against its reference - YaRN's range, frequencies and scale at the
#: published numbers, the forward on two shares, the dense latent cache,
#: prefill then teacher-forced ticks through the latent pool at three
#: prompt lengths by both tick paths, eleven mechanisms each left out in
#: the forward and five of them in the engine, the radix cache over one sublayer a
#: layer, the counters by hand, the share against the whole, the chunk
#: kernel interpreted under the config's scale, every refusal
#: (tests/test_sarvam.py, 49 cases in about 125 s in one process) and the
#: two kernels at a 32k-row table, the grouped matmul at its widths and the
#: cell's tick and chunk programs compiled for the described v5e
#: (tests/test_chip_compile.py, 10 cases, 1-10 s each).
DEFAULT_MAX_TESTS = 1525

#: Pytest summary trailer: "== 398 passed, 27 deselected in 612.34s =="
#: (also plain "in 612.34s (0:10:12)" forms).
_TRAILER = re.compile(r"\bin\s+(\d+(?:\.\d+)?)s\b")
_COUNTS = re.compile(r"(\d+)\s+(passed|failed|errors?|skipped)")


def check_log(path: Path, budget_s: float) -> int:
    try:
        text = path.read_text(encoding="utf-8", errors="replace")
    except OSError as exc:
        print(f"tier1-budget: cannot read {path}: {exc}", file=sys.stderr)
        return 1
    wall = None
    counts: dict[str, int] = {}
    for line in text.splitlines():
        m = _TRAILER.search(line)
        if m and _COUNTS.search(line):
            wall = float(m.group(1))
            counts = {k: int(n) for n, k in _COUNTS.findall(line)}
    if wall is None:
        print(
            f"tier1-budget: no pytest summary trailer in {path} "
            "(run interrupted or not a pytest log?)",
            file=sys.stderr,
        )
        return 1
    verdict = "within" if wall <= budget_s else "OVER"
    print(
        f"tier1 wall {wall:.1f}s — {verdict} budget {budget_s:.0f}s "
        f"({', '.join(f'{v} {k}' for k, v in counts.items()) or 'no counts'})"
    )
    if wall > budget_s:
        print(
            "tier1-budget: the 'not slow' suite is over budget — move "
            "heavy tests behind the slow marker (PR 9 precedent) before "
            "the driver's timeout starts killing runs",
            file=sys.stderr,
        )
        return 1
    return 0


def check_collect(max_tests: int) -> int:
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest", "tests/", "-q",
            "--collect-only", "-m", "not slow",
            "--continue-on-collection-errors",
            "-p", "no:cacheprovider",
        ],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=600,
    )
    m = re.search(
        r"(\d+)(?:/\d+)? tests? (?:collected|selected)",
        proc.stdout + proc.stderr,
    )
    if m is None:
        # "N deselected, M selected" / "N tests collected" variants.
        m = re.search(r"(\d+) selected", proc.stdout + proc.stderr)
    if m is None:
        print(
            "tier1-budget: could not parse collected-test count from "
            "pytest --collect-only output",
            file=sys.stderr,
        )
        print(proc.stdout[-2000:], file=sys.stderr)
        return 1
    n = int(m.group(1))
    verdict = "within" if n <= max_tests else "OVER"
    print(f"tier1 collects {n} tests — {verdict} ceiling {max_tests}")
    if n > max_tests:
        print(
            "tier1-budget: tier-1 test count jumped past the ceiling — "
            "either mark the new heavy tests slow, or raise "
            "DEFAULT_MAX_TESTS in this tool in the same commit (growth "
            "should be a decision, not an accident)",
            file=sys.stderr,
        )
        return 1
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "log", nargs="?", default=None,
        help="pytest log of a finished tier-1 run (e.g. /tmp/_t1.log)",
    )
    parser.add_argument("--budget", type=float, default=DEFAULT_BUDGET_S,
                        help="wall budget in seconds for log mode")
    parser.add_argument("--collect", action="store_true",
                        help="count tier-1 tests via pytest --collect-only "
                        "instead of parsing a log")
    parser.add_argument("--max-tests", type=int, default=DEFAULT_MAX_TESTS,
                        help="test-count ceiling for --collect mode")
    args = parser.parse_args(argv)
    if args.collect:
        return check_collect(args.max_tests)
    if not args.log:
        parser.print_usage(sys.stderr)
        print(
            "tier1-budget: give a pytest log path, or --collect",
            file=sys.stderr,
        )
        return 2
    return check_log(Path(args.log), args.budget)


if __name__ == "__main__":
    sys.exit(main())
