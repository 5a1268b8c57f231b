"""Unit tests for benchmarks/bench_tokenization.usable_cores — the gate of
the armed multi-worker capture trap.  A wrong answer either
keeps the trap disarmed forever on a real multicore host or fires it with a
fantasy grid on a quota-throttled one, so the affinity ∧ cgroup-quota logic
gets direct tests."""

import pytest

from conftest import load_script_module


@pytest.fixture()
def tok_bench():
    return load_script_module(
        "bench_tok_under_test", "benchmarks/bench_tokenization.py"
    )


def _fake_cgroup(monkeypatch, mod, content):
    real_path = mod.Path

    class FakePath(type(real_path())):
        def read_text(self, *a, **k):
            if str(self) == "/sys/fs/cgroup/cpu.max":
                if isinstance(content, Exception):
                    raise content
                return content
            return super().read_text(*a, **k)

    monkeypatch.setattr(mod, "Path", FakePath)


@pytest.mark.parametrize(
    "affinity,cpu_max,expected",
    [
        (16, "max 100000", 16),        # no quota -> affinity rules
        (16, "400000 100000", 4),      # 4-CPU quota caps affinity
        (16, "50000 100000", 1),       # sub-core quota floors at 1
        (2, "800000 100000", 2),       # affinity below the quota rules
        (16, "garbage", 16),           # unparseable -> affinity fallback
        (16, OSError("no cgroup"), 16),  # cgroup v1 host -> fallback
    ],
)
def test_usable_cores(monkeypatch, tok_bench, affinity, cpu_max, expected):
    monkeypatch.setattr(
        tok_bench.os, "sched_getaffinity", lambda _: set(range(affinity))
    )
    _fake_cgroup(monkeypatch, tok_bench, cpu_max)
    assert tok_bench.usable_cores() == expected
