"""Pallas kernels (interpret mode on CPU) + ring attention parity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.kernels.pallas.flash_attention import (
    _xla_attention,
    _xla_rope_attention,
    flash_attention,
    flash_attention_with_rope,
)
from bpe_transformer_tpu.kernels.pallas.gelu import gelu, gelu_reference
from bpe_transformer_tpu.parallel import make_mesh
from bpe_transformer_tpu.parallel.ring_attention import make_ring_attention


# ------------------------------------------------------------------- gelu


@pytest.mark.parametrize("shape", [(7,), (33, 17), (2, 3, 130)])
def test_gelu_matches_reference_formula(shape):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 3)
    out = gelu(x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(gelu_reference(x)), atol=1e-6
    )


@pytest.mark.slow
def test_gelu_matches_torch_tanh_gelu():
    import torch
    import torch.nn.functional as F

    x = np.linspace(-5, 5, 257, dtype=np.float32)
    ours = np.asarray(gelu(jnp.asarray(x)))
    theirs = F.gelu(torch.from_numpy(x), approximate="tanh").numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-5)


# -------------------------------------------------------- flash attention


@pytest.mark.parametrize(
    "batch,heads,seq,d,causal",
    [
        (2, 2, 128, 64, True),
        (1, 4, 256, 64, True),
        (2, 2, 128, 64, False),
        (1, 1, 200, 32, True),  # seq not divisible by block, odd head dim
    ],
)
def test_flash_attention_matches_xla(batch, heads, seq, d, causal):
    rng = np.random.default_rng(1)
    mk = lambda: jnp.asarray(
        rng.standard_normal((batch, heads, seq, d)).astype(np.float32)
    )
    q, k, v = mk(), mk(), mk()
    out = flash_attention(q, k, v, causal, 128, 128, True)
    expected = _xla_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


def test_flash_attention_gradients_match_xla():
    rng = np.random.default_rng(2)
    shape = (1, 2, 128, 32)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for _ in range(3)
    )

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, True, 128, 128, True).sum()

    def loss_xla(q, k, v):
        return _xla_attention(q, k, v, True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize(
    "shape,block_q,block_k,causal",
    [
        ((1, 2, 200, 32), 64, 32, True),   # ragged seq, unequal blocks
        ((2, 1, 96, 16), 32, 96, True),    # block_k > block_q, lcm padding
        ((1, 1, 128, 64), 64, 64, False),  # non-causal backward
    ],
)
@pytest.mark.slow
def test_flash_backward_blockwise_parity(shape, block_q, block_k, causal):
    """The FA-2 Pallas backward (dQ/dK/dV kernels, no S^2 materialization)
    matches the materialized-scores XLA vjp across padding/blocking shapes."""
    rng = np.random.default_rng(11)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for _ in range(3)
    )
    ct = jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, block_q, block_k, True) * ct).sum()

    def loss_xla(q, k, v):
        return (_xla_attention(q, k, v, causal) * ct).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_flash_backward_bf16_grad_dtype():
    rng = np.random.default_rng(12)
    shape = (1, 2, 128, 64)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape), dtype=jnp.bfloat16)
        for _ in range(3)
    )
    grads = jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, 128, 128, True)
        .astype(jnp.float32)
        .sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    f32 = lambda t: tuple(np.asarray(x, dtype=np.float32) for x in t)
    expected = jax.grad(
        lambda q, k, v: _xla_attention(q, k, v, True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(f32(grads), f32(expected)):
        assert a.dtype == np.float32
        np.testing.assert_allclose(a, b, atol=5e-2)
    for g in grads:
        assert g.dtype == jnp.bfloat16


@pytest.mark.slow
def test_fused_rope_table_gradients_match_xla():
    """cos/sin table grads of the fused kernel's vjp match the XLA oracle
    (tables are non-trainable in the model, but the vjp stays honest)."""
    from bpe_transformer_tpu.ops.rope import rope_tables

    rng = np.random.default_rng(13)
    shape = (1, 2, 64, 32)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for _ in range(3)
    )
    cos, sin = rope_tables(shape[-1], shape[-2])

    g_fused = jax.grad(
        lambda c, s: flash_attention_with_rope(q, k, v, c, s, True, 32, 32, True).sum(),
        argnums=(0, 1),
    )(cos, sin)
    g_xla = jax.grad(
        lambda c, s: _xla_rope_attention(q, k, v, c, s, True).sum(),
        argnums=(0, 1),
    )(cos, sin)
    for a, b in zip(g_fused, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(3)
    shape = (1, 2, 128, 64)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape), dtype=jnp.bfloat16)
        for _ in range(3)
    )
    out = flash_attention(q, k, v, True, 128, 128, True)
    expected = _xla_attention(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(expected, dtype=np.float32),
        atol=3e-2,
    )


@pytest.mark.parametrize("seq,d_head", [(256, 64), (1024, 64), (512, 128)])
def test_flash_attention_bf16_within_the_xla_paths_own_error(seq, d_head):
    """bf16 operands on the MXU, float32 statistics: at the tiles the shape
    picks, the kernel's forward and gradients on bf16 inputs sit within the
    error the model's materialized XLA path (bf16 scores and probabilities,
    float32 softmax) itself holds against a float32 reference.  d_head 128
    has a scale that is no power of two: the forward and the backward must
    round the same scaled operand, or the recomputed scores leave the
    forward's logsumexp."""
    from bpe_transformer_tpu.kernels.pallas.runtime import flash_tiles
    from bpe_transformer_tpu.ops.core import (
        causal_mask,
        scaled_dot_product_attention,
    )

    rng = np.random.default_rng(7)
    shape = (1, 2, seq, d_head)
    q, k, v, w = (
        jnp.asarray(rng.standard_normal(shape), dtype=jnp.bfloat16)
        for _ in range(4)
    )
    block_q, block_k = flash_tiles(seq)
    f32 = lambda x: x.astype(jnp.float32)
    materialized = lambda q, k, v: scaled_dot_product_attention(
        q, k, v, causal_mask(seq)
    )
    flash = lambda q, k, v: flash_attention(q, k, v, True, block_q, block_k, True)

    def out_and_grads(fn, *qkv):
        loss = lambda q, k, v: jnp.sum(f32(fn(q, k, v)) * f32(w))
        out = fn(*qkv)
        grads = jax.grad(loss, argnums=(0, 1, 2))(*qkv)
        return [np.asarray(f32(x)) for x in (out, *grads)]

    want = out_and_grads(materialized, f32(q), f32(k), f32(v))
    xla = out_and_grads(materialized, q, k, v)
    got = out_and_grads(flash, q, k, v)
    assert flash(q, k, v).dtype == jnp.bfloat16
    gap = lambda a, b: np.linalg.norm(a - b) / np.linalg.norm(b)
    for name, g, x, ref in zip(("out", "dq", "dk", "dv"), got, xla, want):
        assert gap(g, ref) <= 1.05 * gap(x, ref) + 1e-4, (
            name, gap(g, ref), gap(x, ref)
        )


# ------------------------------------------------ fused RoPE + attention


@pytest.mark.parametrize(
    "batch,heads,seq,d",
    [
        (2, 4, 48, 64),   # seq not divisible by block
        (1, 2, 128, 32),
        (1, 1, 200, 16),  # small head dim, ragged seq
    ],
)
def test_fused_rope_flash_attention_matches_xla(batch, heads, seq, d):
    from bpe_transformer_tpu.ops.rope import rope_tables

    rng = np.random.default_rng(6)
    mk = lambda: jnp.asarray(
        rng.standard_normal((batch, heads, seq, d)).astype(np.float32)
    )
    q, k, v = mk(), mk(), mk()
    cos, sin = rope_tables(d, seq)
    out = flash_attention_with_rope(q, k, v, cos, sin, True, 32, 16, True)
    expected = _xla_rope_attention(q, k, v, cos, sin, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


@pytest.mark.slow
def test_fused_rope_flash_attention_gradients_match_xla():
    from bpe_transformer_tpu.ops.rope import rope_tables

    rng = np.random.default_rng(7)
    shape = (1, 2, 96, 32)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for _ in range(3)
    )
    cos, sin = rope_tables(shape[-1], shape[-2])

    def loss_fused(q, k, v):
        return flash_attention_with_rope(q, k, v, cos, sin, True, 32, 32, True).sum()

    def loss_xla(q, k, v):
        return _xla_rope_attention(q, k, v, cos, sin, True).sum()

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fused, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.slow
def test_model_fused_flash_attention_matches_xla_impl():
    import dataclasses

    from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params

    cfg = dataclasses.replace(TS_TEST_CONFIG, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.random.default_rng(8).integers(0, 512, size=(2, 16)))
    base = forward(params, ids, cfg)
    # min_seq=0 forces the FUSED kernel even at this tiny seq (the default
    # crossover would auto-fall-back to plain flash below 2048).
    fused_cfg = dataclasses.replace(
        cfg, attention_impl="flash_fused", flash_fused_min_seq=0
    )
    fused = forward(params, ids, fused_cfg)
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(fused), atol=2e-4, rtol=1e-3
    )


@pytest.mark.slow  # 870s tier-1 budget (PR 11 sweep; ISSUE 11 tooling guard) — runs in the full matrix
def test_flash_fused_crossover_dispatch(monkeypatch):
    """Below flash_fused_min_seq the model must run the PLAIN flash kernel
    (RoPE outside) — the fused kernel loses at short seq on-chip (r2 bench:
    2.330 vs 2.168 ms at 1k) — and must call the fused kernel at/above the
    threshold."""
    import dataclasses
    import importlib

    # `pallas/__init__` re-exports a FUNCTION named flash_attention that
    # shadows the submodule on `import ... as` attribute resolution; go
    # through importlib to get the actual module.
    fa = importlib.import_module(
        "bpe_transformer_tpu.kernels.pallas.flash_attention"
    )
    from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params

    cfg = dataclasses.replace(
        TS_TEST_CONFIG, vocab_size=512, attention_impl="flash_fused"
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.random.default_rng(9).integers(0, 512, size=(2, 16)))

    calls = []
    real = fa.flash_attention_with_rope
    monkeypatch.setattr(
        fa,
        "flash_attention_with_rope",
        lambda *a, **k: calls.append(1) or real(*a, **k),
    )
    forward(params, ids, cfg)  # seq 16 < 2048: plain-flash fallback
    assert not calls, "fused kernel invoked below the crossover"

    forced = dataclasses.replace(cfg, flash_fused_min_seq=0)
    forward(params, ids, forced)
    assert calls, "fused kernel not invoked when forced"


# ---------------------------------------------------------- ring attention


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.slow
def test_ring_attention_matches_full(causal):
    mesh = make_mesh({"data": 8})
    rng = np.random.default_rng(4)
    shape = (2, 2, 8 * 16, 32)  # seq 128 split 8 ways -> 16 per device
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for _ in range(3)
    )
    ring = make_ring_attention(mesh, "data", causal)
    out = ring(q, k, v)
    expected = _xla_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


@pytest.mark.slow
def test_ring_attention_gradients_flow():
    mesh = make_mesh({"data": 8})
    rng = np.random.default_rng(5)
    shape = (1, 2, 64, 16)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for _ in range(3)
    )
    ring = make_ring_attention(mesh, "data", True)

    g_ring = jax.grad(lambda q_: ring(q_, k, v).sum())(q)
    g_full = jax.grad(lambda q_: _xla_attention(q_, k, v, True).sum())(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full), atol=2e-5)


# ------------------------------------------------- model kernel integration


def test_model_flash_attention_matches_xla_impl():
    import dataclasses

    from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params

    cfg = dataclasses.replace(TS_TEST_CONFIG, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, size=(2, 16)))
    base = forward(params, ids, cfg)
    flash_cfg = dataclasses.replace(cfg, attention_impl="flash")
    flashed = forward(params, ids, flash_cfg)
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(flashed), atol=2e-4, rtol=1e-3
    )


#: (seq, d_head, dtype, backend) -> (path, forward tiles).  Every preset's
#: training shape is a row; the rest pin the line and the tile rule.
ATTENTION_TABLE = [
    # presets: ts-test, tinystories-4l, tinystories-12l / -moe,
    # gpt2-small-32k, gpt2-medium
    (16, 16, "float32", "tpu", "xla", (16, 16)),
    (256, 32, "float32", "tpu", "xla", (256, 256)),
    (512, 64, "float32", "tpu", "flash", (512, 512)),
    (1024, 64, "bfloat16", "tpu", "flash", (1024, 1024)),
    # the line: S >= 512 and d_head >= 64, either dtype, on the TPU only
    (256, 64, "bfloat16", "tpu", "xla", (256, 256)),
    (384, 64, "bfloat16", "tpu", "xla", (384, 384)),
    (512, 64, "bfloat16", "tpu", "flash", (512, 512)),
    (512, 32, "bfloat16", "tpu", "xla", (512, 512)),
    (1024, 64, "float32", "tpu", "flash", (1024, 1024)),
    (1024, 128, "bfloat16", "tpu", "flash", (1024, 1024)),
    (1024, 64, "bfloat16", "cpu", "xla", (1024, 1024)),
    (1024, 64, "bfloat16", "gpu", "xla", (1024, 1024)),
    # tiles: the largest 128-multiple divisor up to 1,024; a sequence that
    # has none goes to XLA, and a forced flash pads it to 256-wide tiles
    # (one tile under 256 positions)
    (2048, 64, "bfloat16", "tpu", "flash", (1024, 1024)),
    (1536, 64, "bfloat16", "tpu", "flash", (768, 768)),
    (4096, 128, "bfloat16", "tpu", "flash", (1024, 1024)),
    (1000, 64, "bfloat16", "tpu", "xla", (256, 256)),
    (600, 64, "bfloat16", "tpu", "xla", (256, 256)),
    (640, 64, "bfloat16", "tpu", "flash", (640, 640)),
    (200, 64, "float32", "tpu", "xla", (200, 200)),
]


@pytest.mark.parametrize("seq,d_head,dtype,backend,path,tiles", ATTENTION_TABLE)
def test_attention_path_and_tiles_from_the_shape(
    seq, d_head, dtype, backend, path, tiles
):
    from bpe_transformer_tpu.kernels.pallas.runtime import (
        attention_path,
        flash_tiles,
    )

    del dtype  # names the preset's row; neither choice asks it
    assert attention_path(seq, d_head, backend) == path
    assert flash_tiles(seq) == tiles


@pytest.mark.parametrize(
    "forward,backward",
    [
        ((1024, 1024), (512, 512)),  # halved: four score tiles live at once
        ((768, 768), (384, 384)),
        ((896, 896), (896, 896)),  # only 128 divides it lane-aligned: whole
        ((512, 256), (512, 256)),
        ((200, 128), (200, 128)),
    ],
)
def test_flash_backward_tiles_divide_the_forwards(forward, backward):
    from bpe_transformer_tpu.kernels.pallas.flash_attention import _bwd_tiles

    assert _bwd_tiles(*forward) == backward
    assert all(f % b == 0 for f, b in zip(forward, backward))


def test_attention_table_holds_every_presets_shape(monkeypatch):
    """The table above is not allowed to miss a preset, and
    `attention_plan` (what `_attention`, `decode.prefill` and the run
    manifest ask) agrees with it: forced values force, "auto" chooses."""
    import dataclasses

    from bpe_transformer_tpu.kernels.pallas.flash_attention import attention_plan
    from bpe_transformer_tpu.training.cli import PRESETS

    rows = {(s, d, dt, b): (p, t) for s, d, dt, b, p, t in ATTENTION_TABLE}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name, cfg in PRESETS.items():
        key = (cfg.context_length, cfg.d_head, cfg.activation_dtype, "tpu")
        assert key in rows, name
        assert cfg.attention_impl == "auto", name
        assert attention_plan(cfg, cfg.context_length) == rows[key], name
        for forced, path in (("xla", "xla"), ("flash", "flash"), ("flash_fused", "flash")):
            plan = attention_plan(
                dataclasses.replace(cfg, attention_impl=forced), cfg.context_length
            )
            assert plan == (path, rows[key][1]), (name, forced)


@pytest.mark.parametrize("preset", ["ts-test", "tinystories-4l"])
def test_auto_attention_below_the_line_is_the_materialized_step(
    preset, monkeypatch
):
    """Below the line "auto" hands `multihead_self_attention` no
    attention_fn, so the step lowers to exactly what the forced-"xla"
    config (the default before the choice existed) lowers to — steered to
    the TPU's branch of the predicate, since the CPU's always materializes."""
    import dataclasses

    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.training.cli import PRESETS
    from bpe_transformer_tpu.training.train_step import make_loss_fn

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = PRESETS[preset]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    ids = jax.ShapeDtypeStruct((2, cfg.context_length), jnp.int32)

    def lowered(config):
        fn = jax.jit(jax.value_and_grad(make_loss_fn(config)))
        return fn.lower(params, ids, ids).as_text()

    auto = lowered(cfg)
    assert auto == lowered(dataclasses.replace(cfg, attention_impl="xla"))
    assert "tpu_custom_call" not in auto


def test_model_gelu_ffn_trains():
    import dataclasses

    from bpe_transformer_tpu.models import TS_TEST_CONFIG, init_params
    from bpe_transformer_tpu.training import TrainHParams, make_train_step
    from bpe_transformer_tpu.optim import adamw_init

    cfg = dataclasses.replace(TS_TEST_CONFIG, vocab_size=512, ffn_type="gelu")
    params = init_params(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg, TrainHParams(warmup_iters=1, cosine_cycle_iters=5))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 512, size=(4, 16)))
    y = jnp.asarray(rng.integers(0, 512, size=(4, 16)))
    params, _, metrics = step(params, adamw_init(params), x, y)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))


def test_gelu_large_inputs_finite():
    """exp-based tanh must not overflow: gelu(11) == 11, not NaN."""
    x = jnp.asarray([11.0, 50.0, 1000.0, -1000.0], dtype=jnp.float32)
    out = np.asarray(gelu(x))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[:3], np.asarray(x[:3]), rtol=1e-6)
    assert out[3] == 0.0


def test_flash_attention_asymmetric_blocks():
    """seq not divisible by block_q alone must still produce every row."""
    rng = np.random.default_rng(7)
    shape = (1, 2, 100, 32)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape).astype(np.float32)) for _ in range(3)
    )
    out = flash_attention(q, k, v, True, 64, 256, True)
    expected = _xla_attention(q, k, v, True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=2e-5)


# --------------------------------------------------------------- fused swiglu


def test_fused_swiglu_matches_xla():
    from bpe_transformer_tpu.kernels.pallas.swiglu import swiglu_fused
    from bpe_transformer_tpu.ops.core import swiglu

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 24, 64)).astype(np.float32))
    w1 = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32) * 0.05)
    w2 = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32) * 0.05)
    w3 = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32) * 0.05)

    got = swiglu_fused(x, w1, w2, w3, 16, 32, True)
    want = swiglu(x, w1, w2, w3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_swiglu_gradients_match_xla():
    import jax

    from bpe_transformer_tpu.kernels.pallas.swiglu import swiglu_fused
    from bpe_transformer_tpu.ops.core import swiglu

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    w1 = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32) * 0.05)
    w2 = jnp.asarray(rng.normal(size=(32, 64)).astype(np.float32) * 0.05)
    w3 = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32) * 0.05)

    loss_fused = lambda *a: swiglu_fused(*a, 8, 16, True).sum()
    loss_xla = lambda *a: swiglu(*a).sum()
    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, w1, w2, w3)
    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2, 3))(x, w1, w2, w3)
    for a, b in zip(g_fused, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_model_fused_swiglu_matches_xla_impl():
    import dataclasses

    import jax

    from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params

    cfg_xla = dataclasses.replace(TS_TEST_CONFIG, vocab_size=256)
    cfg_pallas = dataclasses.replace(cfg_xla, ffn_impl="pallas")
    params = init_params(jax.random.PRNGKey(0), cfg_xla)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 256, size=(2, cfg_xla.context_length)))
    a = forward(params, ids, cfg_xla)
    b = forward(params, ids, cfg_pallas)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ------------------------------------------------------ decode attention


@pytest.mark.parametrize(
    "batch,heads,kv_heads,ctx,d,pos",
    [
        (2, 4, 4, 128, 64, 100),   # MHA
        (2, 8, 2, 256, 64, 0),     # GQA, frontier at the first position
        (1, 4, 1, 200, 48, 199),   # MQA, ragged ctx + odd head dim, full cache
        (3, 6, 3, 512, 64, 17),    # frontier inside the first block
    ],
)
def test_decode_attention_matches_xla(batch, heads, kv_heads, ctx, d, pos):
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        decode_attention,
        xla_decode_attention,
    )

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((batch, heads, d)).astype(np.float32))
    mk = lambda: jnp.asarray(
        rng.standard_normal((batch, kv_heads, ctx, d)).astype(np.float32)
    )
    k, v = mk(), mk()
    out = decode_attention(q, k, v, pos, interpret=True)
    ref = xla_decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_decode_attention_traced_pos_single_compile():
    """pos rides scalar prefetch: one jitted program serves every frontier
    (the generation loop's lax.scan carries pos as a traced value)."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        decode_attention,
        xla_decode_attention,
    )

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 8, 64)).astype(np.float32))
    mk = lambda: jnp.asarray(
        rng.standard_normal((2, 4, 256, 64)).astype(np.float32)
    )
    k, v = mk(), mk()
    f = jax.jit(lambda q, k, v, p: decode_attention(q, k, v, p, interpret=True))
    for pos in (0, 100, 255):
        np.testing.assert_allclose(
            np.asarray(f(q, k, v, jnp.int32(pos))),
            np.asarray(xla_decode_attention(q, k, v, pos)),
            atol=2e-5,
            err_msg=f"pos {pos}",
        )


def test_decode_attention_bf16():
    """bf16 cache/queries (the decode perf path): f32 accumulation inside,
    output close to the f32 oracle at bf16 tolerance."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        decode_attention,
        xla_decode_attention,
    )

    rng = np.random.default_rng(4)
    q32 = rng.standard_normal((2, 4, 64)).astype(np.float32)
    k32 = rng.standard_normal((2, 4, 128, 64)).astype(np.float32)
    v32 = rng.standard_normal((2, 4, 128, 64)).astype(np.float32)
    out = decode_attention(
        jnp.asarray(q32, jnp.bfloat16),
        jnp.asarray(k32, jnp.bfloat16),
        jnp.asarray(v32, jnp.bfloat16),
        64,
        interpret=True,
    )
    assert out.dtype == jnp.bfloat16
    ref = xla_decode_attention(
        jnp.asarray(q32), jnp.asarray(k32), jnp.asarray(v32), 64
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2
    )


def test_decode_attention_rejects_bad_shapes():
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        decode_attention,
    )

    q = jnp.zeros((2, 5, 64))
    kv = jnp.zeros((2, 2, 128, 64))
    with pytest.raises(ValueError, match="not divisible"):
        decode_attention(q, kv, kv, 0, interpret=True)
    with pytest.raises(ValueError, match="shape mismatch"):
        decode_attention(jnp.zeros((2, 4, 32)), kv, kv, 0, interpret=True)


def test_decode_attention_gpt2_shape():
    """The queued device cell's geometry (gpt2-small: H=12, d_head=64,
    ctx=1024, bf16): parity at several causal frontiers, one jitted program."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        decode_attention,
        xla_decode_attention,
    )

    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 12, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 12, 1024, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 12, 1024, 64)), jnp.bfloat16)
    f = jax.jit(lambda q, k, v, p: decode_attention(q, k, v, p, interpret=True))
    for pos in (63, 512, 1023):
        out = f(q, k, v, jnp.int32(pos))
        ref = xla_decode_attention(q, k, v, pos)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, err_msg=f"pos {pos}",
        )


# ------------------------------------------------ paged-native flash decode


def _paged_pool(rng, num_blocks, kv_heads, block_size, d, dtype=np.float32):
    """A pool array as `init_kv_pool` shapes it: block-major rows, a row's
    heads side by side."""
    return jnp.asarray(
        rng.standard_normal((num_blocks, block_size, kv_heads * d)).astype(
            dtype
        )
    )


def _per_head(scale, d):
    """(num_blocks, kv_heads) block scales spread over the pool's shape."""
    return jnp.repeat(scale, d, axis=1)[:, None, :]


@pytest.mark.parametrize(
    "slots,heads,kv_heads,block_size,nbs,d",
    [
        (3, 8, 4, 8, 4, 16),    # GQA, the serving test shape
        (2, 4, 4, 16, 4, 64),   # MHA, production-ish block
        (1, 6, 1, 8, 8, 48),    # MQA, deep chain + odd head dim
    ],
)
def test_paged_decode_attention_matches_gathered_xla(
    slots, heads, kv_heads, block_size, nbs, d
):
    """The paged-NATIVE kernel (pool blocks copied through the table, a
    slot's live ones) equals the gather-then-attend reference at ragged
    per-slot key counts."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        paged_decode_attention,
        xla_decode_attention,
    )
    from bpe_transformer_tpu.models.decode import gather_paged_kv

    rng = np.random.default_rng(7)
    num_blocks = slots * nbs + 1
    k_pool = _paged_pool(rng, num_blocks, kv_heads, block_size, d)
    v_pool = _paged_pool(rng, num_blocks, kv_heads, block_size, d)
    # Distinct non-trash blocks per slot, deliberately shuffled: the
    # kernel must follow the table, not pool order.
    perm = rng.permutation(np.arange(1, num_blocks))
    tables = jnp.asarray(perm.reshape(slots, nbs), jnp.int32)
    ctx = nbs * block_size
    pos = jnp.asarray(
        [0, ctx - 1, ctx // 2][:slots] + [3] * max(0, slots - 3), jnp.int32
    )[:slots]
    q = jnp.asarray(rng.standard_normal((slots, heads, d)).astype(np.float32))

    out = paged_decode_attention(q, k_pool, v_pool, tables, pos + 1,
                                 interpret=True)
    ref = xla_decode_attention(
        q, gather_paged_kv(k_pool, tables, kv_heads),
        gather_paged_kv(v_pool, tables, kv_heads), pos,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _quantized_pool(rng, num_blocks, kv_heads, block_size, d):
    """``(int8 pool, per-block-per-head scales)`` of random rows."""
    rows = _paged_pool(rng, num_blocks, kv_heads, block_size, d)
    scale = jnp.asarray(
        (np.abs(rng.standard_normal((num_blocks, kv_heads))) / 40 + 0.01)
        .astype(np.float32)
    )
    quantized = jnp.clip(jnp.round(rows / _per_head(scale, d)), -127, 127)
    return quantized.astype(jnp.int8), scale


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize(
    "heads,kv_heads,d",
    [(12, 12, 64), (16, 16, 64), (8, 2, 64)],
    ids=["small-12x64", "medium-16x64", "gqa-8over2"],
)
def test_paged_decode_attention_walks_ragged_chains(heads, kv_heads, d, kv_dtype):
    """One call over slots whose chains end everywhere a walk by groups can
    go wrong - no key at all (an idle slot: zeros, nothing copied), one key,
    one short of a block's edge, exactly on it, one group and a key, the
    whole table - at the cells' head shapes and a GQA one, over the
    activation-width pool and the int8 one: the XLA rows path's numbers
    (`xla_rows_attention` over `gather_paged_rows`, which is what the tick
    computes where the kernel is not chosen)."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        paged_decode_attention,
        xla_rows_attention,
    )
    from bpe_transformer_tpu.kernels.pallas.runtime import paged_group_blocks
    from bpe_transformer_tpu.models.decode import gather_paged_rows

    rng = np.random.default_rng(heads * 10 + kv_heads)
    block_size, width = 16, kv_heads * d
    act = jnp.bfloat16
    group = paged_group_blocks(block_size, width, 1 if kv_dtype == "int8" else 2)
    nbs = group + 4
    group_keys = group * block_size
    counts = jnp.asarray(
        [0, 1, 3 * block_size - 1, 3 * block_size, group_keys + 1,
         nbs * block_size],
        jnp.int32,
    )
    slots = counts.shape[0]
    num_blocks = slots * nbs + 1
    tables = jnp.asarray(
        rng.permutation(np.arange(1, num_blocks)).reshape(slots, nbs), jnp.int32
    )
    q = jnp.asarray(rng.standard_normal((slots, heads, d)), act)
    if kv_dtype == "int8":
        k_pool, k_scale = _quantized_pool(rng, num_blocks, kv_heads, block_size, d)
        v_pool, v_scale = _quantized_pool(rng, num_blocks, kv_heads, block_size, d)
    else:
        k_pool = _paged_pool(rng, num_blocks, kv_heads, block_size, d).astype(act)
        v_pool = _paged_pool(rng, num_blocks, kv_heads, block_size, d).astype(act)
        k_scale = v_scale = None

    out = paged_decode_attention(
        q, k_pool, v_pool, tables, counts, k_scale=k_scale, v_scale=v_scale,
        interpret=True,
    )
    assert out.shape == (slots, heads, d) and out.dtype == act
    visible = jnp.arange(nbs * block_size)[None, None, :] < counts[:, None, None]
    ref = xla_rows_attention(
        q[:, :, None],
        gather_paged_rows(k_pool, tables, k_scale, act),
        gather_paged_rows(v_pool, tables, v_scale, act),
        visible,
    )[:, :, 0]
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert not out[0].any()  # the idle slot: zeros, whatever its table says
    # Both sides round probabilities and outputs to bfloat16.
    np.testing.assert_allclose(out[1:], ref[1:], atol=4e-2)


@pytest.mark.parametrize(
    "one_row,blocks_per_slot,block_size,width,itemsize,backend,expected",
    [
        (True, 64, 16, 768, 2, "tpu", "paged"),    # small.serve.decode-heavy
        (True, 64, 16, 1024, 2, "tpu", "paged"),   # medium.serve.prefill-heavy
        (True, 64, 16, 768, 1, "tpu", "paged"),    # the int8 pool
        (False, 64, 16, 768, 2, "tpu", "xla"),     # a verify pass
        (True, 64, 16, 768, 2, "cpu", "xla"),      # interpret mode elsewhere
        (True, 4, 16, 768, 2, "tpu", "xla"),       # a table under one group
        (True, 64, 8, 768, 2, "tpu", "xla"),       # blocks under a bf16 tile
        (True, 64, 16, 192, 2, "tpu", "xla"),      # rows off the lane width
    ],
)
def test_decode_attention_path_follows_shape_and_backend(
    one_row, blocks_per_slot, block_size, width, itemsize, backend, expected
):
    from bpe_transformer_tpu.kernels.pallas.runtime import decode_attention_path

    assert decode_attention_path(
        one_row, blocks_per_slot, block_size, width, itemsize, backend
    ) == expected


@pytest.mark.parametrize(
    "block_size,width,itemsize,expected",
    [
        (16, 768, 2, 16),     # gpt2-small-32k: 256 keys, 4 x 384 KB
        (16, 1024, 2, 16),    # gpt2-medium: 4 x 512 KB
        (128, 1024, 2, 2),    # 256 keys are two blocks
        (16, 8192, 4, 2),     # wide float32 rows: the buffers' 4 MB bound
        (512, 8192, 4, 1),    # never under one block
    ],
)
def test_paged_group_blocks(block_size, width, itemsize, expected):
    from bpe_transformer_tpu.kernels.pallas.runtime import paged_group_blocks

    assert paged_group_blocks(block_size, width, itemsize) == expected


@pytest.mark.parametrize(
    "batch,heads,kv_heads,queries,d",
    [
        (3, 8, 4, 1, 16),   # GQA decode tick: one query a slot
        (2, 4, 4, 1, 64),   # MHA at a production head width
        (2, 6, 2, 3, 8),    # the verify pass: K+1 queries a slot
        (1, 6, 1, 2, 48),   # MQA, odd head width
    ],
)
def test_rows_attention_matches_per_head_attention(
    batch, heads, kv_heads, queries, d
):
    """`xla_rows_attention` over KV kept as pool rows (heads side by side
    along the lanes, never split) equals plain per-head attention over the
    same keys: the block-diagonal queries add only zeros."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        xla_decode_attention,
        xla_rows_attention,
    )

    rng = np.random.default_rng(batch * 100 + heads)
    keys = 24
    k_rows = jnp.asarray(
        rng.standard_normal((batch, keys, kv_heads * d)).astype(np.float32)
    )
    v_rows = jnp.asarray(
        rng.standard_normal((batch, keys, kv_heads * d)).astype(np.float32)
    )
    q = jnp.asarray(
        rng.standard_normal((batch, heads, queries, d)).astype(np.float32)
    )
    pos = jnp.asarray(rng.integers(0, keys - queries, batch), jnp.int32)
    # Query j of slot b sees keys up to pos[b] + j.
    frontier = pos[:, None] + jnp.arange(queries)[None, :]
    visible = jnp.arange(keys)[None, None, :] <= frontier[:, :, None]
    out = xla_rows_attention(q, k_rows, v_rows, visible)
    assert out.shape == (batch, heads, queries, d)

    def split(rows):  # (B, keys, kv * d) -> the dense cache's (B, kv, keys, d)
        return jnp.transpose(
            rows.reshape(batch, keys, kv_heads, d), (0, 2, 1, 3)
        )

    for j in range(queries):
        ref = xla_decode_attention(
            q[:, :, j], split(k_rows), split(v_rows), frontier[:, j]
        )
        np.testing.assert_allclose(
            np.asarray(out[:, :, j]), np.asarray(ref), atol=2e-5
        )


def test_paged_decode_attention_int8_matches_dequant_reference():
    """int8 blocks + per-block-per-head scales: the kernel's in-register
    dequant equals attention over the explicitly dequantized gathered
    cache (same numbers, no transient)."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        paged_decode_attention,
        xla_decode_attention,
    )
    from bpe_transformer_tpu.models.decode import gather_paged_kv

    rng = np.random.default_rng(11)
    slots, heads, kv_heads, block_size, nbs, d = 2, 8, 4, 8, 4, 16
    num_blocks = slots * nbs + 1
    kq, k_scale = _quantized_pool(rng, num_blocks, kv_heads, block_size, d)
    vq, v_scale = _quantized_pool(rng, num_blocks, kv_heads, block_size, d)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, num_blocks)).reshape(slots, nbs),
        jnp.int32,
    )
    pos = jnp.asarray([9, 31], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, heads, d)).astype(np.float32))

    out = paged_decode_attention(
        q, kq, vq, tables, pos + 1, k_scale=k_scale, v_scale=v_scale,
        interpret=True,
    )
    kd = kq.astype(jnp.float32) * _per_head(k_scale, d)
    vd = vq.astype(jnp.float32) * _per_head(v_scale, d)
    ref = xla_decode_attention(
        q, gather_paged_kv(kd, tables, kv_heads),
        gather_paged_kv(vd, tables, kv_heads), pos,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_decode_attention_single_compile_across_state():
    """tables and key counts ride scalar prefetch: one jitted program
    serves every table layout and chain length (the paged tick's
    bounded-compile contract)."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        paged_decode_attention,
        xla_decode_attention,
    )
    from bpe_transformer_tpu.models.decode import gather_paged_kv

    rng = np.random.default_rng(3)
    slots, heads, kv_heads, block_size, nbs, d = 2, 4, 2, 8, 4, 16
    num_blocks = slots * nbs + 1
    k_pool = _paged_pool(rng, num_blocks, kv_heads, block_size, d)
    v_pool = _paged_pool(rng, num_blocks, kv_heads, block_size, d)
    f = jax.jit(
        lambda q, k, v, t, p: paged_decode_attention(
            q, k, v, t, p, interpret=True
        )
    )
    q = jnp.asarray(rng.standard_normal((slots, heads, d)).astype(np.float32))
    for seed in (0, 1, 2):
        r2 = np.random.default_rng(seed)
        tables = jnp.asarray(
            r2.permutation(np.arange(1, num_blocks)).reshape(slots, nbs),
            jnp.int32,
        )
        pos = jnp.asarray(r2.integers(0, nbs * block_size, slots), jnp.int32)
        out = f(q, k_pool, v_pool, tables, pos + 1)
        ref = xla_decode_attention(
            q, gather_paged_kv(k_pool, tables, kv_heads),
            gather_paged_kv(v_pool, tables, kv_heads), pos,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
    assert f._cache_size() == 1


def test_paged_decode_attention_rejects_bad_shapes():
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        paged_decode_attention,
    )

    q = jnp.zeros((2, 4, 16))
    pool = jnp.zeros((9, 8, 2 * 16))
    tables = jnp.zeros((2, 4), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="tables"):
        paged_decode_attention(q, pool, pool, jnp.zeros((3, 4), jnp.int32),
                               pos, interpret=True)
    with pytest.raises(ValueError, match="shape mismatch"):
        paged_decode_attention(q, pool, jnp.zeros((9, 8, 2 * 8)), tables,
                               pos, interpret=True)
    # The heads-major blocks of the migration wire are not the pool's form.
    with pytest.raises(ValueError, match="shape mismatch"):
        old_form = jnp.zeros((9, 2, 8, 16))
        paged_decode_attention(q, old_form, old_form, tables, pos,
                               interpret=True)
    with pytest.raises(ValueError, match="int8"):
        paged_decode_attention(q, pool, pool, tables, pos,
                               k_scale=jnp.zeros((9, 2)), interpret=True)
    with pytest.raises(ValueError, match="not divisible"):
        paged_decode_attention(jnp.zeros((2, 5, 16)), pool, pool, tables,
                               pos, interpret=True)
