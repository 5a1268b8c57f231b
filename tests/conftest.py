"""Shared test configuration.

Must run before anything imports jax: forces the CPU platform with 8 virtual
devices so multi-chip sharding (data-parallel psum, FSDP partitioning) is
exercised without TPU hardware — the TPU-native analogue of a fake
distributed backend.
"""

import os
from pathlib import Path

# Force CPU for the test suite regardless of ambient configuration: numeric
# parity tolerances assume f32 host matmuls, and the virtual 8-device mesh
# only exists on the host platform.  (The chip is checked by chip_smoke.py,
# through the chip tool — never by this suite.)
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

REPO_ROOT = Path(__file__).resolve().parent.parent


def load_script_module(name: str, relpath: str):
    """Import a top-level script (chip_smoke.py, benchmarks/*.py) as a module
    under a test-private name — the shared loader for script-unit tests so
    the 5-line spec boilerplate isn't copied per file."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(name, REPO_ROOT / relpath)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

#: The upstream reference checkout (read-only).  Tests that pin numerics or
#: token ids against its fixtures/snapshots skip gracefully when absent.
REFERENCE_ROOT = Path("/root/reference")
REFERENCE_FIXTURES = REFERENCE_ROOT / "tests" / "fixtures"
REFERENCE_SNAPSHOTS = REFERENCE_ROOT / "tests" / "_snapshots"

requires_reference = pytest.mark.skipif(
    not REFERENCE_FIXTURES.is_dir(),
    reason="reference checkout with fixtures not mounted",
)


@pytest.fixture(scope="session")
def reference_fixtures() -> Path:
    if not REFERENCE_FIXTURES.is_dir():
        pytest.skip("reference fixtures not available")
    return REFERENCE_FIXTURES


@pytest.fixture(scope="session")
def reference_snapshots() -> Path:
    if not REFERENCE_SNAPSHOTS.is_dir():
        pytest.skip("reference snapshots not available")
    return REFERENCE_SNAPSHOTS


@pytest.fixture(scope="session")
def tiny_corpus(tmp_path_factory) -> Path:
    """A small synthetic training corpus with document separators."""
    lines = []
    words = [
        "the", "quick", "brown", "fox", "jumps", "over", "lazy", "dog",
        "pack", "my", "box", "with", "five", "dozen", "liquor", "jugs",
        "sphinx", "of", "black", "quartz", "judge", "vow",
    ]
    for i in range(400):
        line = " ".join(words[(i + j) % len(words)] for j in range(12))
        lines.append(line + ("." if i % 3 else "!"))
        if i % 25 == 24:
            lines.append("<|endoftext|>")
    path = tmp_path_factory.mktemp("corpus") / "tiny_corpus.txt"
    path.write_text("\n".join(lines) + "\n", encoding="utf-8")
    return path
