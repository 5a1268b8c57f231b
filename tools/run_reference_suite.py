"""Run the reference repo's own test files VERBATIM against this framework.

The reference's compatibility seam is that its tests import only
`tests/adapters.py` (`/root/reference/tests/test_model.py:6-18`; the
assignment design, `/root/reference/tests/README.md`).  This runner stages
the reference suite with every test file, conftest, snapshot, and fixture
**byte-identical** (symlinked read-only), swapping in exactly one file —
`tests/adapters.py`, re-exporting `bpe_transformer_tpu.compat.adapters` —
which is the swap the suite was designed for.

Environment shims live in an OUTER conftest (rootdir level, ours), never in
the reference files:
  * `tiktoken.get_encoding("gpt2")` downloads its vocab from the network;
    this container has no egress, so the shim rebuilds the identical
    encoding offline from the reference's own fixture artifacts
    (`gpt2_vocab.json`, 50,257 entries) — same ids, same regex, same
    special token.
  * Tests whose fixtures are the repo's missing large blobs
    (`/root/reference/.MISSING_LARGE_BLOBS`: `ts_tests/model.pt`,
    `tinystories_sample_5M.txt`) are SKIPPED with an explicit reason —
    nobody, including the reference itself, can run those from this mount.

Usage:
    python tools/run_reference_suite.py [extra pytest args]

Exit code is pytest's.  A summary line (collected/passed/skipped) prints at
the end; PARITY.md records the certified result.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
REF_TESTS = Path("/root/reference/tests")

ADAPTERS_SHIM = '''\
"""The one swapped file: the reference suite's designed seam.

Everything else in this staged tree is a byte-identical symlink into
/root/reference/tests; this module re-exports the framework's adapter
implementations (bpe_transformer_tpu/compat/adapters.py) under the import
path the reference tests use (`from .adapters import ...`).
"""

from bpe_transformer_tpu.compat.adapters import *  # noqa: F401,F403
'''

OUTER_CONFTEST = '''\
"""Environment shims for running the reference suite offline (ours; the
reference's own tests/conftest.py is staged unmodified next to the tests).

1. tiktoken.get_encoding("gpt2") normally downloads the GPT-2 vocab; this
   container has no egress.  Rebuild the identical encoding from the
   reference's committed fixture artifacts instead (same trick as the
   framework's own tests/test_tokenizer.py).
2. Skip tests whose fixtures are the repo's missing large blobs
   (.MISSING_LARGE_BLOBS) — unrunnable from this mount by anyone.
"""

import pytest

_OFFLINE_ENCODINGS = {}


def _install_offline_tiktoken():
    import tiktoken

    from bpe_transformer_tpu.tokenization.gpt2 import load_gpt2_vocab

    real_get_encoding = tiktoken.get_encoding

    def offline_get_encoding(name):
        if name != "gpt2":
            return real_get_encoding(name)
        if "gpt2" not in _OFFLINE_ENCODINGS:
            vocab = load_gpt2_vocab(
                "/root/reference/tests/fixtures/gpt2_vocab.json"
            )
            mergeable = {
                tok: idx for idx, tok in vocab.items() if tok != b"<|endoftext|>"
            }
            _OFFLINE_ENCODINGS["gpt2"] = tiktoken.Encoding(
                name="gpt2",
                pat_str=(
                    r"""'(?:[sdmt]|ll|ve|re)| ?\\p{L}+| ?\\p{N}+|"""
                    r""" ?[^\\s\\p{L}\\p{N}]+|\\s+(?!\\S)|\\s+"""
                ),
                mergeable_ranks=mergeable,
                special_tokens={"<|endoftext|>": 50256},
            )
        return _OFFLINE_ENCODINGS["gpt2"]

    tiktoken.get_encoding = offline_get_encoding


_install_offline_tiktoken()

#: Tests that read tinystories_sample_5M.txt by path (the ts_state_dict
#: model.pt dependents are caught by fixture name instead).
_5M_TESTS = {
    "test_train_bpe_special_tokens",
    "test_encode_iterable_memory_usage",
    "test_encode_memory_usage",
}


def pytest_collection_modifyitems(config, items):
    skip_blob = pytest.mark.skip(
        reason="fixture is a missing large blob (see "
        "/root/reference/.MISSING_LARGE_BLOBS); unrunnable from "
        "this mount by the reference itself"
    )
    for item in items:
        if "ts_state_dict" in getattr(item, "fixturenames", ()):
            item.add_marker(skip_blob)
        elif item.name.split("[")[0] in _5M_TESTS:
            item.add_marker(skip_blob)
'''


def stage() -> Path:
    """Build a fresh staging tree; a per-run tempdir, so concurrent
    invocations (the in-suite certification test vs a manual run) can never
    rmtree each other's tree mid-run."""
    stage_root = Path(tempfile.mkdtemp(prefix="refsuite-"))
    tests = stage_root / "tests"
    tests.mkdir(parents=True)
    (stage_root / "conftest.py").write_text(OUTER_CONFTEST)
    for entry in REF_TESTS.iterdir():
        if entry.name == "adapters.py":
            continue  # the designed swap point
        if entry.name == "__pycache__":
            continue
        (tests / entry.name).symlink_to(entry)
    (tests / "adapters.py").write_text(ADAPTERS_SHIM)
    return stage_root


def main() -> int:
    stage_root = stage()
    cmd = [
        sys.executable,
        "-m",
        "pytest",
        "tests/",
        "-q",
        "-p",
        "no:cacheprovider",  # rootdir may be torn down between runs
        *sys.argv[1:],
    ]
    env = dict(os.environ)
    # The reference suite is torch-vs-adapter numerics on the host — force
    # the CPU backend UNCONDITIONALLY: a TPU has no role in this parity
    # run, and the suite must not take a chip another process needs.
    env["JAX_PLATFORMS"] = "cpu"
    print(
        f"running reference suite: {' '.join(cmd)} (cwd={stage_root})",
        file=sys.stderr,
    )
    try:
        return subprocess.call(cmd, cwd=stage_root, env=env)
    finally:
        shutil.rmtree(stage_root, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
