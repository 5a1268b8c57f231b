"""Mixture-of-experts FFN: routing numerics, capacity, and expert parallelism.

The MoE layer has no reference precedent; these tests pin its semantics the
same way the reference pins dense ops — against a transparent per-token
reference implementation — and validate the expert-parallel (GSPMD) step on
the virtual 8-device mesh.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params
from bpe_transformer_tpu.models.moe import expert_capacity, init_moe_params, switch_ffn
from bpe_transformer_tpu.optim import adamw_init
from bpe_transformer_tpu.parallel import make_mesh, make_gspmd_train_step, shard_batch, shard_params
from bpe_transformer_tpu.training.train_step import TrainHParams, make_train_step

MOE_CFG = dataclasses.replace(
    TS_TEST_CONFIG,
    vocab_size=512,
    ffn_type="moe",
    n_experts=4,
    capacity_factor=2.0,
)


def _reference_switch(tokens, params, cap):
    """Per-token numpy reference: route to argmax expert, drop beyond cap."""
    router = np.asarray(params["router"], np.float32)
    w1, w2, w3 = (np.asarray(params[k], np.float32) for k in ("w1", "w2", "w3"))
    logits = tokens @ router.T
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    idx = probs.argmax(-1)
    out = np.zeros_like(tokens)
    counts = {e: 0 for e in range(router.shape[0])}
    for n in range(tokens.shape[0]):
        e = int(idx[n])
        if counts[e] >= cap:
            continue
        counts[e] += 1
        x = tokens[n]
        h = (x @ w1[e].T) / (1 + np.exp(-(x @ w1[e].T))) * (x @ w3[e].T)
        out[n] = probs[n, e] * (h @ w2[e].T)
    return out


def test_switch_ffn_matches_per_token_reference():
    cfg = dataclasses.replace(MOE_CFG, capacity_factor=100.0)  # no drops
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 5, cfg.d_model)).astype(np.float32))

    out, aux = switch_ffn(x, params, cfg)
    tokens = np.asarray(x, np.float32).reshape(-1, cfg.d_model)
    ref = _reference_switch(tokens, params, cap=10**9)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.d_model), ref, atol=1e-5
    )
    assert float(aux) > 0.0


@pytest.mark.parametrize("top_k", [1, 2])
def test_gather_dispatch_matches_einsum(top_k):
    """moe_dispatch="gather" is the same routing function as "einsum":
    identical assignments, positions, gates, and drops — outputs and aux
    must agree (incl. under capacity pressure) and so must gradients."""
    cfg = dataclasses.replace(
        MOE_CFG, router_top_k=top_k, capacity_factor=0.5
    )  # tight capacity: drops exercised
    cfg_g = dataclasses.replace(cfg, moe_dispatch="gather")
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)).astype(np.float32))

    # Compiled (config static), not eager op-by-op dispatch.
    ffn = jax.jit(switch_ffn, static_argnums=2)
    out_e, aux_e = ffn(x, params, cfg)
    out_g, aux_g = ffn(x, params, cfg_g)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_e), atol=1e-5)
    np.testing.assert_allclose(float(aux_g), float(aux_e), rtol=1e-6)

    def loss(p, c):
        o, a = switch_ffn(x, p, c)
        return jnp.sum(o**2) + a

    grad = jax.jit(jax.grad(loss), static_argnums=1)
    g_e = grad(params, cfg)
    g_g = grad(params, cfg_g)
    for k in g_e:
        np.testing.assert_allclose(
            np.asarray(g_g[k]), np.asarray(g_e[k]), atol=1e-4
        )


def test_switch_ffn_respects_capacity():
    cfg = dataclasses.replace(MOE_CFG, capacity_factor=0.5)
    params = init_moe_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    n_tok = 32
    x = jnp.asarray(rng.normal(size=(1, n_tok, cfg.d_model)).astype(np.float32))
    cap = expert_capacity(n_tok, cfg.n_experts, cfg.capacity_factor)

    out, _ = switch_ffn(x, params, cfg)
    ref = _reference_switch(
        np.asarray(x, np.float32).reshape(-1, cfg.d_model), params, cap
    )
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.d_model), ref, atol=1e-5
    )
    # Overflow tokens exist and produce exactly-zero output rows.
    dropped = np.all(ref == 0.0, axis=-1)
    assert dropped.any()


def _reference_topk(tokens, params, cap, k):
    """Per-token numpy reference for top-k routing: gates renormalized over
    the chosen experts, capacity filled rank-major (all first choices queue
    before any second choice)."""
    router = np.asarray(params["router"], np.float32)
    w1, w2, w3 = (np.asarray(params[kk], np.float32) for kk in ("w1", "w2", "w3"))
    logits = tokens @ router.T
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    topk_probs = np.take_along_axis(probs, order, axis=-1)
    gates = topk_probs / topk_probs.sum(-1, keepdims=True)
    out = np.zeros_like(tokens)
    counts = {e: 0 for e in range(router.shape[0])}
    for rank in range(k):
        for n in range(tokens.shape[0]):
            e = int(order[n, rank])
            if counts[e] >= cap:
                continue
            counts[e] += 1
            x = tokens[n]
            h = (x @ w1[e].T) / (1 + np.exp(-(x @ w1[e].T))) * (x @ w3[e].T)
            out[n] += gates[n, rank] * (h @ w2[e].T)
    return out


def test_top2_ffn_matches_per_token_reference():
    cfg = dataclasses.replace(MOE_CFG, router_top_k=2, capacity_factor=100.0)
    params = init_moe_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(6, 5, cfg.d_model)).astype(np.float32))

    out, aux = switch_ffn(x, params, cfg)
    tokens = np.asarray(x, np.float32).reshape(-1, cfg.d_model)
    ref = _reference_topk(tokens, params, cap=10**9, k=2)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.d_model), ref, atol=1e-5
    )
    assert float(aux) > 0.0


def test_top2_capacity_fills_rank_major():
    """With tight capacity, every token's first choice outranks any token's
    second choice — pinned against the rank-major numpy reference."""
    cfg = dataclasses.replace(MOE_CFG, router_top_k=2, capacity_factor=0.75)
    params = init_moe_params(jax.random.PRNGKey(4), cfg)
    rng = np.random.default_rng(4)
    n_tok = 32
    x = jnp.asarray(rng.normal(size=(1, n_tok, cfg.d_model)).astype(np.float32))
    cap = expert_capacity(n_tok, cfg.n_experts, cfg.capacity_factor)

    out, _ = switch_ffn(x, params, cfg)
    ref = _reference_topk(
        np.asarray(x, np.float32).reshape(-1, cfg.d_model), params, cap, k=2
    )
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, cfg.d_model), ref, atol=1e-5
    )


def test_uniform_router_aux_is_near_one():
    """With a zero router every expert gets probability 1/E; aux -> ~1."""
    cfg = MOE_CFG
    params = init_moe_params(jax.random.PRNGKey(2), cfg)
    params = dict(params, router=jnp.zeros_like(params["router"]))
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)).astype(np.float32))
    _, aux = switch_ffn(x, params, cfg)
    assert float(aux) == pytest.approx(1.0, abs=1e-5)


def test_moe_lm_trains():
    """Full LM with MoE FFNs: loss (incl. aux) decreases over a few steps."""
    cfg = MOE_CFG
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    step = make_train_step(cfg, TrainHParams(warmup_iters=1, cosine_cycle_iters=50))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(8, cfg.context_length))
    x = jnp.asarray(ids)
    y = jnp.asarray(np.roll(ids, -1, axis=1))
    losses = []
    for _ in range(8):
        params, opt_state, metrics = step(params, opt_state, x, y)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("dispatch", ["einsum", "gather"])
@pytest.mark.slow
def test_ep_step_matches_single_device(dispatch):
    """dp_ep GSPMD step on a (data, expert) mesh reproduces the single-device
    update (routing and capacity drops are deterministic) — for BOTH dispatch
    formulations (gather must stay mesh-compilable, not just fast)."""
    cfg = dataclasses.replace(MOE_CFG, moe_dispatch=dispatch)
    hp = TrainHParams(warmup_iters=2, cosine_cycle_iters=10)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))

    single = make_train_step(cfg, hp)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "expert": 4})
    params2 = init_params(jax.random.PRNGKey(0), cfg)
    params2 = shard_params(params2, mesh, "dp_ep")
    opt2 = adamw_init(params2)
    step = make_gspmd_train_step(cfg, hp, mesh, "dp_ep", example_params=params2)
    x2, y2 = shard_batch((x, y), mesh)
    p2, s2, m2 = step(params2, opt2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        p1,
        jax.device_get(p2),
    )


@pytest.mark.slow
def test_sp_moe_step_matches_single_device():
    """Context-parallel (ring attention) step with MoE FFNs == single-device
    step.  Capacity is generous so per-shard routing has no drops.  The aux
    weight is zeroed: the load-balance loss is computed per dispatch group
    (the Switch convention), so under sp it averages shard-local products
    rather than reproducing the global product — expert compute and the task
    loss must still match the single-device step exactly."""
    from bpe_transformer_tpu.parallel import make_sp_train_step, shard_sp_batch

    cfg = dataclasses.replace(
        MOE_CFG, capacity_factor=16.0, router_aux_weight=0.0
    )
    hp = TrainHParams(warmup_iters=2, cosine_cycle_iters=10)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(4, cfg.context_length)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(4, cfg.context_length)))

    single = make_train_step(cfg, hp)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "seq": 4})
    params2 = init_params(jax.random.PRNGKey(0), cfg)
    opt2 = adamw_init(params2)
    step = make_sp_train_step(cfg, hp, mesh)
    x2, y2 = shard_sp_batch((x, y), mesh)
    p2, s2, m2 = step(params2, opt2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        p1,
        jax.device_get(p2),
    )


@pytest.mark.slow
def test_sp_moe_loop_trains(tmp_path):
    """The training loop accepts parallel="sp" with an MoE config (the hole
    closed in round 2) and the loss decreases."""
    from bpe_transformer_tpu.training.loop import LoopConfig, train

    cfg = dataclasses.replace(MOE_CFG, capacity_factor=4.0, router_top_k=2)
    # Learnable data (uniform-random tokens sit at the entropy floor already):
    # a repeating ramp makes next-token prediction reducible within steps.
    data = np.tile(np.arange(cfg.vocab_size, dtype=np.int32), 40)
    summary = train(
        cfg,
        TrainHParams(warmup_iters=2, cosine_cycle_iters=30),
        LoopConfig(
            steps=12,
            batch_size=8,
            log_every=4,
            eval_every=1000,
            checkpoint_every=1000,
            parallel="sp",
            mesh_axes={"data": 2, "seq": 4},
        ),
        train_data=data,
        log_fn=lambda *_: None,
    )
    assert summary["history"][-1]["loss"] < summary["history"][0]["loss"]


def test_moe_expert_weights_sharded_on_expert_axis():
    from bpe_transformer_tpu.parallel import param_specs
    from jax.sharding import PartitionSpec as P

    cfg = MOE_CFG
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh({"data": 2, "expert": 4})
    specs = param_specs(params, mesh, "dp_ep")
    ffn = specs["layers"][0]["ffn"]
    assert ffn["w1"][0] == "expert"
    assert ffn["router"][0] == "expert"
    assert all(axis is None for axis in specs["layers"][0]["attn"]["q_proj"])


@pytest.mark.slow
def test_pp_moe_step_matches_single_device():
    """GPipe pipeline step with MoE FFNs == single-device step (aux weight
    zeroed for exact parity: the pp aux is per-microbatch/per-dispatch-group
    like sp; generous capacity so routing has no drops)."""
    from bpe_transformer_tpu.parallel.pp import (
        init_pp_opt_state,
        make_pp_train_step,
        shard_pp_params,
        stack_pipeline_params,
        unstack_pipeline_params,
    )

    cfg = dataclasses.replace(
        MOE_CFG,
        num_layers=4,
        capacity_factor=64.0,
        router_aux_weight=0.0,
    )
    hp = TrainHParams(warmup_iters=2, cosine_cycle_iters=10)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, cfg.context_length)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, cfg.context_length)))

    single = make_train_step(cfg, hp)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "pp": 4})
    pp_params = stack_pipeline_params(init_params(jax.random.PRNGKey(0), cfg), 4)
    pp_params = shard_pp_params(pp_params, mesh)
    opt2 = init_pp_opt_state(pp_params, mesh)
    step = make_pp_train_step(cfg, hp, mesh, num_microbatches=4)
    p2, s2, m2 = step(pp_params, opt2, x, y)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        p1,
        unstack_pipeline_params(jax.device_get(p2)),
    )


@pytest.mark.slow
def test_pp_moe_loop_trains():
    """The training loop accepts parallel="pp" with an MoE config (the
    second composition hole closed in round 2) and the loss decreases with
    the router aux ACTIVE."""
    from bpe_transformer_tpu.training.loop import LoopConfig, train

    cfg = dataclasses.replace(
        MOE_CFG, num_layers=4, capacity_factor=4.0, router_top_k=2
    )
    data = np.tile(np.arange(cfg.vocab_size, dtype=np.int32), 40)
    summary = train(
        cfg,
        TrainHParams(warmup_iters=2, cosine_cycle_iters=30),
        LoopConfig(
            steps=12,
            batch_size=8,
            log_every=4,
            eval_every=1000,
            checkpoint_every=1000,
            parallel="pp",
            mesh_axes={"data": 2, "pp": 4},
            pp_microbatches=4,
        ),
        train_data=data,
        log_fn=lambda *_: None,
    )
    assert summary["history"][-1]["loss"] < summary["history"][0]["loss"]


@pytest.mark.slow
def test_fsdp_ep_step_matches_single_device():
    """fsdp_ep: dense params sharded ZeRO-style over data while expert
    stacks shard over the expert axis — the full CLI strategy matrix row."""
    cfg = MOE_CFG
    hp = TrainHParams(warmup_iters=2, cosine_cycle_iters=10)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw_init(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(16, cfg.context_length)))

    single = make_train_step(cfg, hp)
    p1, s1, m1 = single(params, opt_state, x, y)

    mesh = make_mesh({"data": 2, "expert": 4})
    params2 = init_params(jax.random.PRNGKey(0), cfg)
    params2 = shard_params(params2, mesh, "fsdp_ep")
    opt2 = adamw_init(params2)
    step = make_gspmd_train_step(cfg, hp, mesh, "fsdp_ep", example_params=params2)
    x2, y2 = shard_batch((x, y), mesh)
    p2, s2, m2 = step(params2, opt2, x2, y2)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        p1,
        jax.device_get(p2),
    )
