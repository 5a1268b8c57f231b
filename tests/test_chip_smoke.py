"""The chip_smoke.py contract and the one compile-cache rule — cheap pins,
no model, no server, no training run.

* the last stdout line is built by ONE function with exactly the keys the
  driver reads;
* off the chip the script exits non-zero and never says ``"ok": true``;
* `utils/compile_cache.resolve_cache_dir`: env > flag > checkout default,
  off on the CPU with neither, never a moving path;
* a parent that starts chip-owning children never imports jax
  (`chip_smoke.py`, the subprocess modes of `benchmarks/bench_serving.py`).
"""

import ast
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import REPO_ROOT, load_script_module
from bpe_transformer_tpu.utils import chip_probe, compile_cache


@pytest.fixture(scope="module")
def smoke():
    return load_script_module("chip_smoke_under_test", "chip_smoke.py")


DEVICES = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


# ------------------------------------------------------- the last line


def test_final_line_has_exactly_the_contract_keys(smoke):
    line = smoke.final_line(DEVICES)
    assert "\n" not in line
    parsed = json.loads(line)
    assert set(parsed) == {"ok", "device"}
    assert set(parsed["device"]) == {"platform", "kind", "count"}
    assert parsed == {"ok": True, "device": DEVICES}
    assert json.loads(json.dumps(parsed)) == parsed  # round-trips


def test_final_line_drops_anything_else_in_the_device_record(smoke):
    noisy = {**DEVICES, "count": 4, "coords": [0, 0], "hbm": 1 << 34}
    parsed = json.loads(smoke.final_line(noisy))
    assert parsed["device"] == {**DEVICES, "count": 4}


def test_final_line_is_only_reachable_through_main(smoke):
    """One builder, one caller: nothing but main() prints the ok line, and
    main() hard-codes the platform it accepts."""
    tree = ast.parse((REPO_ROOT / "chip_smoke.py").read_text())
    callers = [
        fn.name
        for fn in ast.walk(tree) if isinstance(fn, ast.FunctionDef)
        for call in ast.walk(fn)
        if isinstance(call, ast.Call)
        and getattr(call.func, "id", None) == "final_line"
    ]
    assert callers == ["main"]
    main_src = ast.get_source_segment(
        (REPO_ROOT / "chip_smoke.py").read_text(),
        next(n for n in tree.body
             if isinstance(n, ast.FunctionDef) and n.name == "main"),
    )
    assert 'run_one_chip("tpu", True' in main_src
    assert 'run_four_chips("tpu"' in main_src
    # No option lets the command name another platform.
    assert "--platform" not in main_src and "--expect" not in main_src


def test_smoke_on_cpu_exits_nonzero_and_never_says_ok():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "expected 'tpu'" in proc.stderr


def test_smoke_alone_in_a_directory_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_bytes(
        (REPO_ROOT / "chip_smoke.py").read_bytes()
    )
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


def _stream(devices=DEVICES, clean=True, loss=2.0):
    return [
        {"kind": "manifest", "devices": devices, "start_iteration": 0},
        {"kind": "span", "name": "compile_first_step", "dur_s": 1.0},
        {"step": 1, "loss": 3.0, "step_wall_s": 0.1},
        {"step": 2, "loss": loss, "step_wall_s": 0.1},
        {"kind": "resources", "compile_events": 1, "compile_time_s": 1.0,
         "compile_cache_hits": 0, "hbm_peak_bytes_in_use": None,
         "params_bytes": 4, "opt_state_bytes": 8},
        {"kind": "footer", "clean": clean},
    ]


def test_train_facts_accepts_a_good_stream(smoke):
    facts = smoke._train_facts(_stream(), DEVICES)
    assert facts["steps"] == [1, 2] and facts["losses"] == [3.0, 2.0]


@pytest.mark.parametrize(
    "stream, why",
    [
        (_stream(devices=None), "devices"),
        (_stream(devices={**DEVICES, "platform": "cpu"}), "devices"),
        (_stream(clean=False), "clean footer"),
        (_stream(loss=float("nan")), "non-finite"),
        (_stream()[:-1], "clean footer"),
    ],
    ids=["no-devices", "other-platform", "unclean-footer", "nan-loss",
         "no-footer"],
)
def test_train_facts_refuses(smoke, stream, why):
    with pytest.raises(smoke.SmokeFailure, match=why):
        smoke._train_facts(stream, DEVICES)


def test_probe_refuses_the_wrong_platform_before_any_model_work(capsys):
    assert chip_probe.main(["--expect-platform", "tpu"]) == 3
    record = json.loads(capsys.readouterr().out)
    assert record["devices"]["platform"] == "cpu"
    assert "interpret_mode" not in record  # stopped before the kernels


@pytest.mark.parametrize(
    "backend, env, exits",
    [("tpu", None, False), ("cpu", "cpu", False), ("cpu", None, True),
     ("gpu", None, True)],
    ids=["tpu", "cpu-explicit", "cpu-silent-fallback", "other-accelerator"],
)
def test_require_tpu(monkeypatch, backend, env, exits):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    if exits:
        with pytest.raises(SystemExit) as info:
            chip_probe.require_tpu("bench_x")
        assert info.value.code == 3
    else:
        chip_probe.require_tpu("bench_x")


# ------------------------------------------------ the compile-cache rule


@pytest.mark.parametrize(
    "env, flag, backend, expected",
    [
        ("/some/dir", "other/", "tpu", Path("/some/dir")),
        ("/some/dir", None, "tpu", Path("/some/dir")),
        ("/some/dir", "other/", "cpu", Path("/some/dir")),
        (None, "other/", "tpu", Path("other/")),
        (None, "other/", "cpu", Path("other/")),
        (None, None, "tpu", REPO_ROOT / ".scratch" / "jax_ccache"),
        (None, None, "cpu", None),
    ],
    ids=["env-beats-flag", "env-alone", "env-on-cpu", "flag", "flag-on-cpu",
         "checkout-default", "cpu-stays-off"],
)
def test_resolve_cache_dir(monkeypatch, env, flag, backend, expected):
    if env is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env)
    assert compile_cache.resolve_cache_dir(flag, backend=backend) == expected


def test_default_cache_dir_never_moves():
    default = compile_cache.DEFAULT_CACHE_DIR
    assert default == REPO_ROOT / ".scratch" / "jax_ccache"
    ignored = (REPO_ROOT / ".gitignore").read_text().split()
    assert ".scratch/" in ignored
    source = (
        REPO_ROOT / "bpe_transformer_tpu" / "utils" / "compile_cache.py"
    ).read_text()
    for moving in ("tempfile", "mkdtemp", "getpid", "time."):
        assert moving not in source


@pytest.mark.parametrize("env", ["/some/dir", None], ids=["env", "no-env"])
def test_enable_sets_no_directory_when_the_variable_is_set(
    monkeypatch, tmp_path, env
):
    """With JAX_COMPILATION_CACHE_DIR set, jax reads it itself and no code
    sets another directory — `--compile-cache other/` included."""
    import jax

    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: updates.__setitem__(key, value)
    )
    monkeypatch.setattr(Path, "mkdir", lambda self, **kwargs: None)
    if env is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env)
    other = tmp_path / "other"
    resolved = compile_cache.enable_compile_cache(other)
    if env is None:
        assert resolved == other
        assert updates["jax_compilation_cache_dir"] == str(other)
    else:
        assert resolved == Path(env)
        assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


@pytest.mark.parametrize("named", [False, True], ids=["default", "flag"])
def test_unwritable_cache_directory(monkeypatch, tmp_path, named):
    """The checkout default that cannot be created (an installed package)
    warns and runs uncached; a directory the user named fails loudly."""
    import jax

    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: updates.__setitem__(key, value)
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("")  # mkdir under a regular file: NotADirectoryError
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", blocker / "ccache")
    if named:
        with pytest.raises(OSError):
            compile_cache.enable_compile_cache(blocker / "named")
    else:
        with pytest.warns(RuntimeWarning, match="compile cache off"):
            assert compile_cache.enable_compile_cache() is None
    assert updates == {}


def test_no_other_code_sets_a_cache_directory():
    """The rule lives in utils/compile_cache.py and nowhere else: no other
    file updates the jax option or writes the variable (reading it, or
    naming it in a help string, is fine)."""
    import re

    sets_it = re.compile(
        r"jax_compilation_cache_dir"
        r"|setdefault\(\s*[\"']JAX_COMPILATION_CACHE_DIR"
        r"|\[[\"']JAX_COMPILATION_CACHE_DIR[\"']\]\s*="
        r"|JAX_COMPILATION_CACHE_DIR="
    )
    files = [
        REPO_ROOT / "chip_smoke.py",
        REPO_ROOT / "__graft_entry__.py",
        # the one test file that could set it for the whole session
        REPO_ROOT / "tests" / "conftest.py",
    ]
    for root in ("bpe_transformer_tpu", "benchmarks", "tools", "examples"):
        files += sorted((REPO_ROOT / root).rglob("*.py"))
        files += sorted((REPO_ROOT / root).rglob("*.sh"))
    offenders = [
        str(path.relative_to(REPO_ROOT)) for path in files
        if path.name != "compile_cache.py"
        and sets_it.search(path.read_text())
    ]
    assert offenders == []


def test_warmup_takes_its_directory_from_the_rule():
    from bpe_transformer_tpu.training.cli import build_parser

    args = build_parser().parse_args(["warmup", "--train"])
    assert args.compile_cache is None


# ------------------------------------------------- jax-free parents


def _jax_imports(node) -> list[int]:
    lines = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Import):
            names = [alias.name for alias in sub.names]
        elif isinstance(sub, ast.ImportFrom):
            names = [sub.module or ""]
        else:
            continue
        if any(name == "jax" or name.startswith("jax.") for name in names):
            lines.append(sub.lineno)
    return lines


def _module_level(tree) -> ast.Module:
    """The statements that run at import (function and class bodies may
    import jax lazily; importing the module must not)."""
    return ast.Module(
        body=[n for n in tree.body
              if not isinstance(n, (ast.FunctionDef, ast.ClassDef))],
        type_ignores=[],
    )


def test_chip_smoke_never_imports_jax():
    tree = ast.parse((REPO_ROOT / "chip_smoke.py").read_text())
    assert _jax_imports(tree) == []


@pytest.mark.parametrize(
    "module",
    [
        "bpe_transformer_tpu/__init__.py",
        "bpe_transformer_tpu/_lazy.py",
        "bpe_transformer_tpu/utils/__init__.py",
        "bpe_transformer_tpu/utils/compile_cache.py",
        "bpe_transformer_tpu/models/__init__.py",
        "bpe_transformer_tpu/models/config.py",
        "bpe_transformer_tpu/native/__init__.py",
        "bpe_transformer_tpu/native/engine.py",
    ],
)
def test_what_the_smoke_parent_imports_is_jax_free_at_import(module):
    tree = ast.parse((REPO_ROOT / module).read_text())
    assert _jax_imports(_module_level(tree)) == []


def test_bench_serving_subprocess_parents_never_import_jax():
    tree = ast.parse((REPO_ROOT / "benchmarks/bench_serving.py").read_text())
    assert _jax_imports(_module_level(tree)) == []
    functions = {
        n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)
    }
    for name in ("run_restart", "run_controller_ramp",
                 "_write_random_checkpoint"):
        assert _jax_imports(functions[name]) == [], name
    # main() starts children only before it imports jax.
    main = functions["main"]
    first_jax = min(_jax_imports(main))
    for call in ast.walk(main):
        if isinstance(call, ast.Call) and getattr(call.func, "id", "") in (
            "run_restart", "run_controller_ramp"
        ):
            assert call.lineno < first_jax
