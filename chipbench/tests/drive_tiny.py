"""The command's own code path (``run.cli``) at tiny sizes on the CPU:
``python3 chipbench/tests/drive_tiny.py --workload <cell> --seed ...``.
Used by the tests through a subprocess, so that what reaches the real
standard output can be read."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import run
from chipbench.tests import tiny


def load(name):
    if name == "small.train":
        return tiny.tiny_train()
    return tiny.tiny_serve(name)


if __name__ == "__main__":
    sys.exit(run.cli(sys.argv[1:], load=load, expect_platform="cpu"))
