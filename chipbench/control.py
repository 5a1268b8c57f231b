"""Read the limits of ``correct``: the program's numbers and the control's.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Run on the chip by the builder of a cell, never by the benchmark's own
runs.  For each seed it drives the cell as the command does (a short window
at the cell's own load) and, beside every number ``correct`` compares, reads
the same number from the *control*: the reference computed in float8-e4m3
(``reference.py``, ``quant="fp8"``), the nearest precision below the
bfloat16 the configurations state.  A limit belongs above the largest sound
reading and below the smallest control reading (PERF.md gives both for each
limit).  The last line is one JSON object with every reading.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def read(load, name, seeds, seconds, expect_platform="tpu", log=print) -> dict:
    from chipbench import run

    sound, control = {}, {}

    def emit(obj):
        target = {"correct": sound, "control": control}.get(obj.get("info"))
        if target is not None:
            for row in obj["compared"]:
                target.setdefault(row["number"], []).append(row["value"])
        log(json.dumps(obj)[:2000])

    results = []
    for seed in seeds:
        workload, config = load(name)
        result = run.run_cell(
            workload, config, name=name, seed=seed, seconds=seconds,
            trace=False, emit=emit, expect_platform=expect_platform,
            control=True,
        )
        results.append(result["correct"])
    return {
        "workload": name, "seeds": list(seeds), "correct": results,
        "sound_largest": {k: max(v) for k, v in sound.items()},
        "control_smallest": {k: min(v) for k, v in control.items()},
        "sound": sound, "control": control,
    }


def main(argv=None) -> int:
    from chipbench import run

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = read(run.load_cell, args.workload, seeds, args.seconds,
               log=lambda line: print(line, file=sys.stderr))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
