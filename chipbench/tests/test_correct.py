"""``correct``: the reference agrees with the program in float32, a
bfloat16 program fails a float32-tight limit, the float8 control reads
several times what a sound run reads, and a timed path broken underneath
comes out as not correct."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, reference, run
from chipbench.tests import tiny

ARCH = {k: tiny.TINY_CONFIG[k] for k in tiny.TINY_CONFIG["architecture_keys"]}


def quiet(_):
    pass


def test_reference_agrees_with_the_programs_forward_in_float32():
    from bpe_transformer_tpu.models.config import ModelConfig
    from bpe_transformer_tpu.models.transformer import forward, init_params

    config = ModelConfig(**ARCH)
    params = init_params(jax.random.PRNGKey(11), config)
    weights = reference.weights_from_seed(11, tiny.TINY_CONFIG)
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(weights)):
        assert jnp.array_equal(a, b)
    x = np.random.default_rng(0).integers(0, 10000, (4, 16))
    gap = jnp.abs(forward(params, jnp.asarray(x), config)
                  - reference.forward_logits(weights, jnp.asarray(x), tiny.TINY_CONFIG))
    assert float(gap.max()) < 1e-5


def cell(kind, **limits):
    wl, cfg = tiny.tiny_train() if kind == "train" else tiny.tiny_serve()
    wl["correct"].update(limits)
    return wl, cfg


TIGHT = dict(loss_abs_gap=1e-5, first_grad_rel_gap=1e-5, param_change_rel_gap=1e-4)


def test_float32_program_passes_float32_tight_limits_bfloat16_fails_them():
    wl, cfg = cell("train", **TIGHT)
    ok = run.run_cell(wl, cfg, name="small.train", seed=3, seconds=1.0,
                      trace=False, emit=quiet, expect_platform="cpu")
    assert ok["correct"] is True
    low = run.run_cell(wl, {**cfg, "activation_dtype": "bfloat16"}, name="small.train",
                       seed=3, seconds=1.0, trace=False, emit=quiet,
                       expect_platform="cpu")
    assert low["correct"] is False


def test_float8_control_reads_well_above_a_bfloat16_run():
    def load(_):
        return tiny.tiny_train("bfloat16")

    out = control.read(load, "small.train", [5, 6, 7], 1.0, "cpu", log=quiet)
    sound = out["sound_largest"]["first_grad_worst_leaf_rel_gap"]
    low = out["control_smallest"]["first_grad_worst_leaf_rel_gap"]
    assert low > 3 * sound


def test_served_control_gap_is_well_above_the_programs():
    out = control.read(lambda n: tiny.tiny_serve(n), "small.serve.decode-heavy",
                       [5, 6, 7], 1.0, "cpu", log=quiet)
    sound = out["sound_largest"]["served_logit_widest_gap"]
    assert out["control_smallest"]["served_logit_widest_gap"] > max(3 * sound, 1e-3)


def test_a_step_that_returns_its_parameters_unchanged_is_not_correct(monkeypatch):
    from bpe_transformer_tpu.training import train_step

    real = train_step.adamw_update

    def frozen(params, grads, state, lr, **kw):
        _, new_state = real(params, grads, state, lr, **kw)
        return params, new_state

    monkeypatch.setattr(train_step, "adamw_update", frozen)
    wl, cfg = cell("train", loss_abs_gap=1e-2, first_grad_rel_gap=0.05,
                   param_change_rel_gap=0.05)
    seen = []
    out = run.run_cell(wl, cfg, name="small.train", seed=4, seconds=1.0,
                       trace=False, emit=seen.append, expect_platform="cpu")
    assert out["correct"] is False
    rows = [o for o in seen if o.get("info") == "correct"][0]["compared"]
    change = [r for r in rows if r["number"].startswith("param_change")][0]
    assert change["value"] == pytest.approx(1.0, abs=1e-3) and not change["ok"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from bpe_transformer_tpu.serving.kvpool import paged_engine

    real = paged_engine.sample_tokens
    monkeypatch.setattr(
        paged_engine, "sample_tokens",
        lambda logits, *a, **k: (real(logits, *a, **k) + 1) % logits.shape[-1],
    )
    wl, cfg = cell("serve", served_logit_gap=1e-3)
    out = run.run_cell(wl, cfg, name="small.serve.decode-heavy", seed=4,
                       seconds=1.0, trace=False, emit=quiet, expect_platform="cpu")
    assert out["correct"] is False
