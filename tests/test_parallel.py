"""Multi-chip execution on the virtual 8-device CPU mesh.

The TPU-native analogue of testing a distributed backend without a cluster
(SURVEY §4, TPU-build additions): data-parallel psum steps and FSDP/TP
GSPMD steps must compile, run, and agree numerically with the single-device
step.

Tier-1 keeps the cheap surface (mesh/spec/validation checks, sharded
forward, the Ulysses attention-parity smoke); the full train-step parity
matrix (dp/sp/pp/ulysses x grad-accum/inner-steps) runs real 8-device
training per case — 10-80 s each on the CPU mesh — and lives behind the
``slow`` marker to keep the suite inside its wall-clock budget.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params
from bpe_transformer_tpu.optim import adamw_init
from bpe_transformer_tpu.parallel import (
    make_dp_train_step,
    make_gspmd_train_step,
    make_mesh,
    param_specs,
    shard_batch,
    shard_params,
)
from bpe_transformer_tpu.training.train_step import (
    TrainHParams,
    make_train_step,
)

CFG = dataclasses.replace(TS_TEST_CONFIG, vocab_size=512)
HP = TrainHParams(warmup_iters=2, cosine_cycle_iters=10)


@pytest.mark.parametrize(
    "devices,impl,want",
    [
        (None, "auto", "auto"),  # no mesh: the shape decides
        (1, "auto", "auto"),  # a one-device mesh partitions nothing
        (8, "auto", "xla"),  # XLA's partitioner cannot split a Mosaic kernel
        (8, "xla", "xla"),
        (8, "flash", "flash"),  # a forced path stays forced
    ],
)
def test_partitioned_config_resolves_auto_attention(devices, impl, want):
    from bpe_transformer_tpu.parallel import partitioned_config

    mesh = None
    if devices:
        mesh = make_mesh({"data": devices}, devices=jax.devices()[:devices])
    config = dataclasses.replace(CFG, attention_impl=impl)
    resolved = partitioned_config(config, mesh)
    assert resolved.attention_impl == want
    assert dataclasses.replace(resolved, attention_impl=impl) == config


def _setup(seed=0):
    params = init_params(jax.random.PRNGKey(seed), CFG)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(seed)
    x = rng.integers(0, CFG.vocab_size, size=(16, CFG.context_length))
    y = rng.integers(0, CFG.vocab_size, size=(16, CFG.context_length))
    return params, opt_state, jnp.asarray(x), jnp.asarray(y)


def test_shard_map_modern_api_is_what_the_installed_jax_exports():
    """The parallel strategies call `jax.shard_map(check_vma=...)` and
    `jax.lax.axis_size` directly (no shim since PR 22): the installed jax
    must export both, with the semantics the strategies rely on."""
    mesh = make_mesh({"data": 8})
    mapped = jax.shard_map(
        lambda x: jax.lax.psum(x, "data") + jax.lax.axis_size("data"),
        mesh=mesh,
        in_specs=PartitionSpec("data"),
        out_specs=PartitionSpec("data"),
        check_vma=False,
    )
    out = np.asarray(mapped(jnp.ones(8, jnp.int32)))
    np.testing.assert_array_equal(out, np.full(8, 16))  # psum 8 + size 8


def test_eight_virtual_devices_present():
    assert len(jax.devices()) == 8


@pytest.mark.slow
def test_dp_step_matches_single_device():
    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 8})
    params2, opt_state2, x2, y2 = _setup()
    dp_step = make_dp_train_step(CFG, HP, mesh)
    x2, y2 = shard_batch((x2, y2), mesh)
    p2, s2, m2 = dp_step(params2, opt_state2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        p2,
    )


@pytest.mark.parametrize("strategy,axes", [
    ("dp", {"data": 8}),
    ("fsdp", {"data": 8}),
    ("fsdp_tp", {"data": 4, "model": 2}),
    ("tp", {"data": 2, "model": 4}),
])
@pytest.mark.slow
def test_gspmd_step_matches_single_device(strategy, axes):
    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh(axes)
    params2, opt_state2, x2, y2 = _setup()
    params2 = shard_params(params2, mesh, strategy)
    opt_state2 = adamw_init(params2)
    step = make_gspmd_train_step(CFG, HP, mesh, strategy, example_params=params2)
    x2, y2 = shard_batch((x2, y2), mesh)
    p2, s2, m2 = step(params2, opt_state2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    # spot-check a couple of weight tensors after gathering
    np.testing.assert_allclose(
        np.asarray(p1["lm_head"]), np.asarray(jax.device_get(p2["lm_head"])),
        atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(p1["layers"][0]["ffn"]["w1"]),
        np.asarray(jax.device_get(p2["layers"][0]["ffn"]["w1"])),
        atol=1e-5,
    )


def test_fsdp_actually_shards_parameters():
    mesh = make_mesh({"data": 8})
    params = init_params(jax.random.PRNGKey(0), CFG)
    sharded = shard_params(params, mesh, "fsdp")
    emb = sharded["token_embeddings"]
    # Each device must hold 1/8th of the embedding rows.
    shard_shapes = {s.data.shape for s in emb.addressable_shards}
    assert shard_shapes == {(CFG.vocab_size // 8, CFG.d_model)}
    # Tiny norm vectors stay replicated.
    ln = sharded["ln_final"]
    assert {s.data.shape for s in ln.addressable_shards} == {(CFG.d_model,)}


def test_tp_specs_split_heads_and_ffn():
    mesh = make_mesh({"data": 2, "model": 4})
    params = init_params(jax.random.PRNGKey(0), CFG)
    specs = param_specs(params, mesh, "tp")
    attn = specs["layers"][0]["attn"]
    assert attn["q_proj"] == PartitionSpec("model", None)
    assert attn["output_proj"] == PartitionSpec(None, "model")
    ffn = specs["layers"][0]["ffn"]
    assert ffn["w1"] == PartitionSpec("model", None)
    assert ffn["w2"] == PartitionSpec(None, "model")


def test_dp_forward_inference_sharded():
    """Plain forward under a sharded batch: XLA partitions it with no code
    changes (activation sharding follows the batch)."""
    mesh = make_mesh({"data": 8})
    params = init_params(jax.random.PRNGKey(0), CFG)
    x = jnp.zeros((16, 8), dtype=jnp.int32)
    xs = shard_batch(x, mesh)
    logits = jax.jit(lambda p, t: forward(p, t, CFG))(params, xs)
    assert logits.shape == (16, 8, CFG.vocab_size)


@pytest.mark.slow
def test_sp_step_matches_single_device():
    """Context-parallel (ring attention) training step == single-device step."""
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "seq": 4})
    params2, opt_state2, x2, y2 = _setup()
    step = make_sp_train_step(CFG, HP, mesh)
    x2, y2 = shard_sp_batch((x2, y2), mesh)
    p2, s2, m2 = step(params2, opt_state2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        p2,
    )


@pytest.mark.parametrize("zigzag", [False, True], ids=["ring", "zigzag"])
@pytest.mark.slow
def test_sp_grad_accum_matches_full_batch_step(zigzag):
    """Gradient accumulation INSIDE the sp (ring attention) program: each
    chip scans its local microbatch shards, one pmean over (data, seq) per
    update, and the result equals the single-device full-batch update —
    the long-context HBM-relief combo."""
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    accum = 2
    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "seq": 4})
    params2, opt_state2, x2, y2 = _setup()
    micro = x2.shape[0] // accum
    x2 = x2.reshape(accum, micro, -1)
    y2 = y2.reshape(accum, micro, -1)
    step = make_sp_train_step(CFG, HP, mesh, zigzag=zigzag, accum_steps=accum)
    x2, y2 = shard_sp_batch((x2, y2), mesh, zigzag=zigzag, stacked=True)
    p2, s2, m2 = step(params2, opt_state2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        p2,
    )


@pytest.mark.slow
def test_sp_inner_steps_match_sequential_sp_steps():
    """inner_steps under the sp mesh: one scanned dispatch of 3 full updates
    (each with its own pmean) equals 3 sequential sp steps."""
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    mesh = make_mesh({"data": 2, "seq": 4})
    params, opt_state, x, y = _setup()
    seq_step = make_sp_train_step(CFG, HP, mesh)
    xp, yp = shard_sp_batch((x, y), mesh)
    p1, s1 = params, opt_state
    for _ in range(3):
        p1, s1, m1 = seq_step(p1, s1, xp, yp)

    params2, opt_state2, x2, y2 = _setup()
    scan_step = make_sp_train_step(CFG, HP, mesh, inner_steps=3)
    xs = jnp.broadcast_to(x2, (3, *x2.shape))
    ys = jnp.broadcast_to(y2, (3, *y2.shape))
    xs, ys = shard_sp_batch((xs, ys), mesh, stacked=True)
    p2, s2, m2 = scan_step(params2, opt_state2, xs, ys)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        p2,
    )


@pytest.mark.slow
def test_sp_forward_matches_full_forward():
    from bpe_transformer_tpu.parallel import sp_forward
    from functools import partial
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({"seq": 8})
    params = init_params(jax.random.PRNGKey(0), CFG)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, CFG.vocab_size, size=(2, CFG.context_length))
    )
    full = forward(params, ids, CFG)

    mapped = jax.shard_map(
        partial(sp_forward, config=CFG, seq_axis="seq"),
        mesh=mesh,
        in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    )
    sharded = mapped(params, ids)
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(sharded), atol=2e-4, rtol=1e-3
    )


# ------------------------------------------------------------ pipeline (pp)


@pytest.mark.slow
def test_pp_step_matches_single_device():
    """GPipe pipeline (4 stages) + dp must reproduce the single-device update."""
    from bpe_transformer_tpu.parallel.pp import (
        init_pp_opt_state,
        make_pp_train_step,
        shard_pp_params,
        stack_pipeline_params,
        unstack_pipeline_params,
    )

    cfg = dataclasses.replace(CFG, num_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))

    single = make_train_step(cfg, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "pp": 4})
    params2 = init_params(jax.random.PRNGKey(0), cfg)
    pp_params = shard_pp_params(stack_pipeline_params(params2, 4), mesh)
    pp_opt = init_pp_opt_state(pp_params, mesh)
    step = make_pp_train_step(cfg, HP, mesh, num_microbatches=4)
    x2, y2 = shard_batch((x, y), mesh)
    p2, s2, m2 = step(pp_params, pp_opt, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    np.testing.assert_allclose(
        float(m1["grad_norm"]), float(m2["grad_norm"]), rtol=1e-4
    )
    restored = unstack_pipeline_params(jax.device_get(p2))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        p1,
        restored,
    )


def _sp_first_step(config, x, y):
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    mesh = make_mesh({"data": 2, "seq": 4})
    params = init_params(jax.random.PRNGKey(0), config)
    step = make_sp_train_step(config, HP, mesh)
    return step(params, adamw_init(params), *shard_sp_batch((x, y), mesh))


def _pp_first_step(config, x, y):
    from bpe_transformer_tpu.parallel.pp import (
        init_pp_opt_state,
        make_pp_train_step,
        shard_pp_params,
        stack_pipeline_params,
    )

    config = dataclasses.replace(config, num_layers=4)
    mesh = make_mesh({"data": 2, "pp": 4})
    params = shard_pp_params(
        stack_pipeline_params(init_params(jax.random.PRNGKey(0), config), 4), mesh
    )
    step = make_pp_train_step(config, HP, mesh, num_microbatches=4)
    return step(params, init_pp_opt_state(params, mesh), *shard_batch((x, y), mesh))


@pytest.mark.parametrize(
    "first_step,chunk", [(_sp_first_step, 2), (_pp_first_step, 4)], ids=["sp", "pp"]
)
def test_chunked_loss_under_sp_and_pp(first_step, chunk):
    """The chunked loss - a ``custom_vjp`` whose forward rule makes the
    gradients - inside the sequence-parallel ``shard_map`` (a shard's 4
    positions in chunks of 2) and inside the pipeline's head stage (a
    ``cond`` in the ticks' ``scan``): loss and gradient (AdamW's first
    moment after one step) are the full-logits step's."""
    _, _, x, y = _setup()
    _, full_state, full = first_step(dataclasses.replace(CFG, loss_chunk_size=0), x, y)
    _, state, got = first_step(dataclasses.replace(CFG, loss_chunk_size=chunk), x, y)
    np.testing.assert_allclose(float(got["loss"]), float(full["loss"]), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-8
        ),
        state.m,
        full_state.m,
    )


@pytest.mark.slow
def test_pp_grad_accum_matches_full_batch_step():
    """Gradient accumulation AROUND the pipeline: each accumulation slice
    runs the full GPipe schedule, gradients sum in f32 through the shared
    accumulate_grads, and one update equals the single-device full-batch
    step (closes the last pp NotImplementedError)."""
    from bpe_transformer_tpu.parallel.pp import (
        init_pp_opt_state,
        make_pp_train_step,
        shard_pp_params,
        stack_pipeline_params,
        unstack_pipeline_params,
    )

    accum = 2
    cfg = dataclasses.replace(CFG, num_layers=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))

    single = make_train_step(cfg, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "pp": 4})
    params2 = init_params(jax.random.PRNGKey(0), cfg)
    pp_params = shard_pp_params(stack_pipeline_params(params2, 4), mesh)
    pp_opt = init_pp_opt_state(pp_params, mesh)
    step = make_pp_train_step(
        cfg, HP, mesh, num_microbatches=2, accum_steps=accum
    )
    micro = x.shape[0] // accum
    xs = x.reshape(accum, micro, -1)
    ys = y.reshape(accum, micro, -1)
    xs, ys = shard_batch((xs, ys), mesh, stacked=True)
    p2, s2, m2 = step(pp_params, pp_opt, xs, ys)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    np.testing.assert_allclose(
        float(m1["grad_norm"]), float(m2["grad_norm"]), rtol=1e-4
    )
    restored = unstack_pipeline_params(jax.device_get(p2))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        p1,
        restored,
    )


@pytest.mark.slow
def test_pp_inner_steps_match_sequential_pp_steps():
    """inner_steps under pp: one scanned dispatch of 3 full pipelined
    updates equals 3 sequential pp steps."""
    from bpe_transformer_tpu.parallel.pp import (
        init_pp_opt_state,
        make_pp_train_step,
        shard_pp_params,
        stack_pipeline_params,
    )

    cfg = dataclasses.replace(CFG, num_layers=4)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, cfg.context_length)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, cfg.context_length)))
    mesh = make_mesh({"data": 2, "pp": 4})

    def fresh():
        params = init_params(jax.random.PRNGKey(2), cfg)
        pp_params = shard_pp_params(stack_pipeline_params(params, 4), mesh)
        return pp_params, init_pp_opt_state(pp_params, mesh)

    seq_step = make_pp_train_step(cfg, HP, mesh, num_microbatches=2)
    p1, s1 = fresh()
    xp, yp = shard_batch((x, y), mesh)
    for _ in range(3):
        p1, s1, m1 = seq_step(p1, s1, xp, yp)

    scan_step = make_pp_train_step(
        cfg, HP, mesh, num_microbatches=2, inner_steps=3
    )
    p2, s2 = fresh()
    xs = jnp.broadcast_to(x, (3, *x.shape))
    ys = jnp.broadcast_to(y, (3, *y.shape))
    xs, ys = shard_batch((xs, ys), mesh, stacked=True)
    p2, s2, m2 = scan_step(p2, s2, xs, ys)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        jax.device_get(p1),
        jax.device_get(p2),
    )


def test_pp_accum_and_inner_both_raise():
    from bpe_transformer_tpu.parallel.pp import make_pp_train_step

    mesh = make_mesh({"data": 2, "pp": 4})
    with pytest.raises(ValueError, match="cannot both exceed 1"):
        make_pp_train_step(CFG, HP, mesh, accum_steps=2, inner_steps=2)


def test_pp_stack_unstack_roundtrip():
    from bpe_transformer_tpu.parallel.pp import (
        stack_pipeline_params,
        unstack_pipeline_params,
    )

    cfg = dataclasses.replace(CFG, num_layers=4)
    params = init_params(jax.random.PRNGKey(1), cfg)
    restored = unstack_pipeline_params(stack_pipeline_params(params, 2))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params,
        restored,
    )


def test_hybrid_mesh_degenerate_and_validation():
    from bpe_transformer_tpu.parallel import make_hybrid_mesh

    # dcn all-1 degenerates to a plain ICI mesh.
    mesh = make_hybrid_mesh({"data": 4, "model": 2})
    assert dict(mesh.shape) == {"data": 4, "model": 2}

    with pytest.raises(ValueError, match="not present"):
        make_hybrid_mesh({"data": 8}, {"model": 2})
    with pytest.raises(ValueError, match="needs"):
        make_hybrid_mesh({"data": 8}, {"data": 2})


# ------------------------------------------------- zig-zag ring attention


@pytest.mark.slow
def test_zigzag_ring_attention_matches_xla_and_ring():
    """Balanced zig-zag schedule == materialized causal attention == the
    contiguous ring, after the layout permutation round-trip."""
    from functools import partial

    from bpe_transformer_tpu.ops.core import causal_mask, scaled_dot_product_attention
    from bpe_transformer_tpu.parallel.ring_attention import (
        ring_self_attention,
        zigzag_indices,
        zigzag_inverse_indices,
        zigzag_ring_self_attention,
    )

    n = 8
    B, H, S, D = 2, 2, 64, 16
    mesh = make_mesh({"seq": n})
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, S, D)).astype(np.float32))
        for _ in range(3)
    )
    expected = scaled_dot_product_attention(q, k, v, causal_mask(S))

    spec = PartitionSpec(None, None, "seq", None)
    ring = jax.shard_map(
        partial(ring_self_attention, axis_name="seq", causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )
    np.testing.assert_allclose(np.asarray(ring(q, k, v)), np.asarray(expected), atol=1e-5)

    perm = zigzag_indices(S, n)
    inv = zigzag_inverse_indices(S, n)
    zig = jax.shard_map(
        partial(zigzag_ring_self_attention, axis_name="seq"),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )
    out_zig = zig(q[..., perm, :], k[..., perm, :], v[..., perm, :])[..., inv, :]
    np.testing.assert_allclose(np.asarray(out_zig), np.asarray(expected), atol=1e-5)


@pytest.mark.slow
def test_ring_attention_bf16_inputs_match_f32_reference():
    """The compute-dtype matmul rule (bf16 inputs, f32 accumulation) must
    track the f32 oracle within bf16 tolerance for BOTH XLA ring schedules.
    All other ring tests run f32, where preferred_element_type is a no-op —
    this is the only coverage of the precision-affecting path."""
    from functools import partial

    from bpe_transformer_tpu.ops.core import causal_mask, scaled_dot_product_attention
    from bpe_transformer_tpu.parallel.ring_attention import (
        ring_self_attention,
        zigzag_indices,
        zigzag_inverse_indices,
        zigzag_ring_self_attention,
    )

    n = 8
    B, H, S, D = 2, 2, 64, 16
    mesh = make_mesh({"seq": n})
    rng = np.random.default_rng(1)
    q32, k32, v32 = (
        jnp.asarray(rng.standard_normal((B, H, S, D)).astype(np.float32))
        for _ in range(3)
    )
    expected = scaled_dot_product_attention(q32, k32, v32, causal_mask(S))
    q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))

    spec = PartitionSpec(None, None, "seq", None)
    ring = jax.shard_map(
        partial(ring_self_attention, axis_name="seq", causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )
    out = ring(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected), atol=0.03
    )

    perm = zigzag_indices(S, n)
    inv = zigzag_inverse_indices(S, n)
    zig = jax.shard_map(
        partial(zigzag_ring_self_attention, axis_name="seq"),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )
    out_zig = zig(q[..., perm, :], k[..., perm, :], v[..., perm, :])[..., inv, :]
    np.testing.assert_allclose(
        np.asarray(out_zig, np.float32), np.asarray(expected), atol=0.03
    )


def test_zigzag_positions_cover_sequence():
    from bpe_transformer_tpu.parallel.ring_attention import (
        zigzag_indices,
        zigzag_positions,
    )

    n, S = 4, 64
    all_pos = jnp.concatenate(
        [zigzag_positions(i, S // n, n) for i in range(n)]
    )
    assert sorted(np.asarray(all_pos).tolist()) == list(range(S))
    # positions agree with the layout permutation
    np.testing.assert_array_equal(np.asarray(all_pos), np.asarray(zigzag_indices(S, n)))


@pytest.mark.slow
def test_sp_zigzag_step_matches_single_device():
    """Zig-zag context-parallel step == single-device step: the permutation
    is transparent to the loss (targets ride the same layout)."""
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "seq": 4})
    params2, opt_state2, x2, y2 = _setup()
    step = make_sp_train_step(CFG, HP, mesh, zigzag=True)
    x2, y2 = shard_sp_batch((x2, y2), mesh, zigzag=True)
    p2, s2, m2 = step(params2, opt_state2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        p2,
    )




def test_sp_flash_with_ring_kv_chunk_raises():
    """attention_impl="flash" ignores ring_kv_chunk inside the ring (the
    Pallas kernel tiles by flash_block_size); the combination must fail
    loudly instead of silently dropping the knob."""
    from bpe_transformer_tpu.parallel import make_sp_train_step

    mesh = make_mesh({"data": 2, "seq": 4})
    cfg = dataclasses.replace(CFG, attention_impl="flash", ring_kv_chunk=4)
    with pytest.raises(ValueError, match="ring_kv_chunk"):
        make_sp_train_step(cfg, HP, mesh)


@pytest.mark.slow
def test_dp_grad_accum_matches_full_batch_step():
    """Gradient accumulation under the explicit-collective dp mesh: scanning
    2 microbatches per chip then one all-reduced update equals the
    single-device full-batch step."""
    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 8})
    params2, opt_state2, x2, y2 = _setup()
    accum = 2
    micro = x2.shape[0] // accum  # 8, divides the data axis
    x2 = x2.reshape(accum, micro, -1)
    y2 = y2.reshape(accum, micro, -1)
    step = make_dp_train_step(CFG, HP, mesh, accum_steps=accum)
    x2, y2 = shard_batch((x2, y2), mesh, stacked=True)
    p2, s2, m2 = step(params2, opt_state2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        p2,
    )


@pytest.mark.parametrize("strategy,axes,accum", [
    ("fsdp", {"data": 8}, 2),  # micro=8 divides data=8
    ("fsdp_tp", {"data": 4, "model": 2}, 4),  # micro=4 divides data=4
])
@pytest.mark.slow
def test_gspmd_grad_accum_matches_full_batch_step(strategy, axes, accum):
    """Gradient accumulation compiled INSIDE the GSPMD program: the
    accumulation scan composes with XLA-derived FSDP collectives and equals
    the single-device full-batch update."""
    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh(axes)
    params2, opt_state2, x2, y2 = _setup()
    params2 = shard_params(params2, mesh, strategy)
    opt_state2 = adamw_init(params2)
    micro = x2.shape[0] // accum
    x2 = x2.reshape(accum, micro, -1)
    y2 = y2.reshape(accum, micro, -1)
    step = make_gspmd_train_step(
        CFG, HP, mesh, strategy, example_params=params2, accum_steps=accum
    )
    x2, y2 = shard_batch((x2, y2), mesh, stacked=True)
    p2, s2, m2 = step(params2, opt_state2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(p1["lm_head"]), np.asarray(jax.device_get(p2["lm_head"])),
        atol=1e-5,
    )


@pytest.mark.slow
def test_dp_inner_steps_match_sequential_dp_steps():
    """inner_steps under the dp mesh: one scanned dispatch of 3 updates
    equals 3 sequential dp steps."""
    mesh = make_mesh({"data": 8})
    params, opt_state, x, y = _setup()
    seq_step = make_dp_train_step(CFG, HP, mesh)
    xp, yp = shard_batch((x, y), mesh)
    p1, s1 = params, opt_state
    for _ in range(3):
        p1, s1, m1 = seq_step(p1, s1, xp, yp)

    params2, opt_state2, x2, y2 = _setup()
    scan_step = make_dp_train_step(CFG, HP, mesh, inner_steps=3)
    xs = jnp.broadcast_to(x2, (3, *x2.shape))
    ys = jnp.broadcast_to(y2, (3, *y2.shape))
    xs, ys = shard_batch((xs, ys), mesh, stacked=True)
    p2, s2, m2 = scan_step(params2, opt_state2, xs, ys)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        p2,
    )


# ------------------------------------------------- ulysses (all-to-all sp)


def test_ulysses_attention_matches_dense():
    """The all-to-all head scatter reproduces dense causal attention: one
    all_to_all to head-sharded, full-seq attention, inverse all_to_all."""
    from functools import partial

    from bpe_transformer_tpu.ops.core import causal_mask, scaled_dot_product_attention
    from bpe_transformer_tpu.parallel.ulysses import ulysses_attention

    mesh = make_mesh({"data": 2, "seq": 4})
    rng = np.random.default_rng(0)
    B, H, S, D = 2, 8, 32, 16
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, S, D)).astype(np.float32))
        for _ in range(3)
    )
    dense = scaled_dot_product_attention(q, k, v, causal_mask(S))

    spec = PartitionSpec("data", None, "seq")
    mapped = jax.shard_map(
        partial(ulysses_attention, axis_name="seq"),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    out = mapped(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)


@pytest.mark.parametrize(
    "num_heads,kv_heads",
    [(4, None), (4, 2), (8, 4)],
    ids=["mha", "gqa_expanded", "gqa_compact"],
)
@pytest.mark.slow
def test_sp_ulysses_step_matches_single_device(num_heads, kv_heads):
    """A full train step under the Ulysses schedule equals the single-device
    update (gradients flow through the all_to_alls — their transpose is the
    inverse all_to_all).  gqa_expanded: kv_heads (2) does not divide the seq
    axis (4), so K/V ship expanded; gqa_compact: kv_heads (4) does, so the
    compact slice/re-expand path runs — including its BACKWARD, which relies
    on the repeat-VJP summing each group so the sliced duplicates' zero
    cotangents wash out."""
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    cfg = dataclasses.replace(CFG, num_heads=num_heads, num_kv_heads=kv_heads)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))

    single = make_train_step(cfg, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "seq": 4})
    params2 = init_params(jax.random.PRNGKey(0), cfg)
    step = make_sp_train_step(cfg, HP, mesh, ulysses=True)
    xp, yp = shard_sp_batch((x, y), mesh)
    p2, s2, m2 = step(params2, adamw_init(params2), xp, yp)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        jax.device_get(p2),
    )


@pytest.mark.slow
def test_sp_ulysses_forward_matches_full_forward():
    from functools import partial

    from bpe_transformer_tpu.parallel import sp_forward

    mesh = make_mesh({"data": 2, "seq": 4})
    params = init_params(jax.random.PRNGKey(1), CFG)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(4, CFG.context_length)))
    dense = forward(params, ids, CFG)

    mapped = jax.shard_map(
        partial(sp_forward, config=CFG, seq_axis="seq", ulysses=True),
        mesh=mesh,
        in_specs=(PartitionSpec(), PartitionSpec("data", "seq")),
        out_specs=PartitionSpec("data", "seq", None),
        check_vma=False,
    )
    out = mapped(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=3e-5)


def test_sp_ulysses_validation():
    from bpe_transformer_tpu.parallel import make_sp_train_step

    mesh = make_mesh({"data": 2, "seq": 4})
    with pytest.raises(ValueError, match="mutually exclusive"):
        make_sp_train_step(CFG, HP, mesh, zigzag=True, ulysses=True)
    cfg3 = dataclasses.replace(CFG, num_heads=2, d_model=32)
    with pytest.raises(ValueError, match="must be a multiple"):
        make_sp_train_step(cfg3, HP, mesh, ulysses=True)


@pytest.mark.slow
def test_sp_ulysses_gqa_compact_kv_path():
    """When kv_heads also divides the seq axis the K/V all_to_alls ship the
    COMPACT kv heads (group× less communication); numerics must match the
    dense forward exactly like the expanded path."""
    from functools import partial

    from bpe_transformer_tpu.parallel import sp_forward

    cfg = dataclasses.replace(CFG, num_heads=8, d_model=64, num_kv_heads=4)
    mesh = make_mesh({"data": 2, "seq": 4})
    params = init_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(4, cfg.context_length)))
    dense = forward(params, ids, cfg)

    mapped = jax.shard_map(
        partial(sp_forward, config=cfg, seq_axis="seq", ulysses=True),
        mesh=mesh,
        in_specs=(PartitionSpec(), PartitionSpec("data", "seq")),
        out_specs=PartitionSpec("data", "seq", None),
        check_vma=False,
    )
    out = mapped(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=3e-5)


@pytest.mark.slow
def test_sp_ulysses_grad_accum_matches_full_batch_step():
    """Ulysses composes with gradient accumulation (the schedule-independent
    accumulate_grads scan): equals the single-device full-batch update."""
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    accum = 2
    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "seq": 4})
    params2, opt_state2, x2, y2 = _setup()
    micro = x2.shape[0] // accum
    x2 = x2.reshape(accum, micro, -1)
    y2 = y2.reshape(accum, micro, -1)
    step = make_sp_train_step(CFG, HP, mesh, ulysses=True, accum_steps=accum)
    x2, y2 = shard_sp_batch((x2, y2), mesh, stacked=True)
    p2, s2, m2 = step(params2, opt_state2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        p1,
        p2,
    )


@pytest.mark.slow
def test_sp_ulysses_flash_inner_attention_matches_xla():
    """attention_impl="flash" routes Ulysses' full-sequence inner attention
    through the Pallas kernel (interpret mode on CPU): step parity vs the
    single-device update still holds."""
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    cfg = dataclasses.replace(CFG, attention_impl="flash")
    params, opt_state, x, y = _setup()
    single = make_train_step(CFG, HP)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "seq": 4})
    params2, opt_state2, x2, y2 = _setup()
    step = make_sp_train_step(cfg, HP, mesh, ulysses=True)
    xp, yp = shard_sp_batch((x2, y2), mesh)
    p2, s2, m2 = step(params2, opt_state2, xp, yp)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        p1,
        p2,
    )
