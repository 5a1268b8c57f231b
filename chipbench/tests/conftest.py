"""Run by hand: ``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q``
(not part of ``tests/``, whose collection is budgeted)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
