"""Regression tests for benchmarks/northstar.py's jax phase.  Runs the real
phase_jax on CPU at a 4-step protocol against a temp torch-reference
artifact, covering the self-describing capture fields, the
exhausted-checkpoint cleanup, the mismatched-checkpoint discard, and the
legacy /tmp checkpoint migration — the paths a chip call exercises with no
chance to debug."""

import json

import pytest

from conftest import REPO_ROOT, load_script_module


@pytest.fixture()
def northstar(monkeypatch, tmp_path):
    monkeypatch.setenv("NORTHSTAR_STEPS", "4")
    monkeypatch.syspath_prepend(str(REPO_ROOT / "benchmarks"))
    mod = load_script_module("northstar_under_test", "benchmarks/northstar.py")
    assert mod.STEPS == 4
    mod.EVAL_EVERY = 2
    mod.TORCH_JSON = tmp_path / "torch.json"
    mod.CAPTURE = tmp_path / "northstar.json"
    mod.CAPTURE_NATIVE = tmp_path / "northstar_native.json"
    mod.CKPT = tmp_path / "scratch" / "ckpt.pkl"
    mod.LEGACY_CKPT = tmp_path / "legacy" / "ckpt.pkl"
    mod.TORCH_JSON.write_text(
        json.dumps(
            {
                "steps": 4,
                "final_val_loss": 9.0,
                "tokens_per_sec": 100.0,
                "config": "smoke",
            }
        )
    )
    return mod


@pytest.mark.slow
def test_phase_jax_capture_is_self_describing(northstar):
    assert northstar.phase_jax(allow_cpu=True) == 0
    cap = json.loads(northstar.CAPTURE.read_text())
    assert cap["reference_tolerance"] == northstar.VAL_TOLERANCE
    assert cap["val_loss_delta_vs_torch"] == pytest.approx(
        cap["final_val_loss"]["jax"] - 9.0, abs=1e-3
    )
    assert cap["steps"] == 4 and cap["platform"] == "cpu"
    # The exhausted checkpoint is cleared so a deliberate re-run is fresh.
    assert not northstar.CKPT.exists()


@pytest.mark.slow
def test_phase_jax_discards_mismatched_checkpoint(northstar):
    from bpe_transformer_tpu.checkpointing import save_checkpoint
    import numpy as np

    # A checkpoint claiming a different platform/protocol must not seed the
    # run: phase_jax discards it and trains from scratch to completion.
    northstar.CKPT.parent.mkdir(parents=True)
    save_checkpoint(
        northstar.CKPT,
        params={"w": np.zeros(1)},
        opt_state=None,
        iteration=99,
        extra={"curve": [], "train_s": 0.0, "platform": "tpu", "steps": 4},
    )
    assert northstar.phase_jax(allow_cpu=True) == 0
    cap = json.loads(northstar.CAPTURE.read_text())
    assert len(cap["curve"]) == 2  # evals at steps 2 and 4: a FULL fresh run


@pytest.mark.slow
def test_phase_jax_native_variant_matches_parity_math(northstar):
    """The native variant (scanned dispatch) must produce the SAME update
    math as the per-step parity loop: on CPU both run at full f32 precision,
    so the two curves agree to float tolerance.  Also pins the native
    artifact's self-description (variant, steps_per_dispatch, own capture
    file, own checkpoint name)."""
    assert northstar.phase_jax(allow_cpu=True) == 0
    assert northstar.phase_jax(allow_cpu=True, variant="native") == 0
    parity = json.loads(northstar.CAPTURE.read_text())
    native = json.loads(northstar.CAPTURE_NATIVE.read_text())
    assert native["variant"] == "native"
    assert native["steps_per_dispatch"] == northstar.EVAL_EVERY
    assert parity.get("variant", "parity") == "parity"
    # Same protocol, same init, same batches; CPU runs both at true f32 —
    # the scan changes dispatch, not numerics.
    for p_pt, n_pt in zip(parity["curve"], native["curve"]):
        assert p_pt["step"] == n_pt["step"]
        assert n_pt["val_loss"] == pytest.approx(p_pt["val_loss"], abs=1e-4)
    assert not (northstar.CKPT.parent / f"native_{northstar.CKPT.name}").exists()


@pytest.mark.slow
def test_phase_jax_migrates_legacy_tmp_checkpoint(northstar):
    from bpe_transformer_tpu.checkpointing import save_checkpoint
    import numpy as np

    # A legacy checkpoint moves to the new location, then (being
    # platform-mismatched here) is discarded through the normal guard —
    # proving the migration itself ran.
    northstar.LEGACY_CKPT.parent.mkdir(parents=True)
    save_checkpoint(
        northstar.LEGACY_CKPT,
        params={"w": np.zeros(1)},
        opt_state=None,
        iteration=99,
        extra={"curve": [], "train_s": 0.0, "platform": "tpu", "steps": 4},
    )
    assert northstar.phase_jax(allow_cpu=True) == 0
    assert not northstar.LEGACY_CKPT.exists()  # migrated away
    assert json.loads(northstar.CAPTURE.read_text())["steps"] == 4
