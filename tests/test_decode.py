"""KV-cached decoding: numerics vs the full forward, and sampler integration."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params
from bpe_transformer_tpu.models.decode import (
    decode_step,
    generate_cached,
    init_kv_cache,
    prefill,
)

CFG = dataclasses.replace(TS_TEST_CONFIG, vocab_size=512, context_length=32)

# Compiled, not eager: an eager call dispatches (and compiles) every op of
# the model one by one, and these tests call the three functions dozens of
# times.  ModelConfig is a frozen dataclass, so it rides as a static arg.
forward = jax.jit(
    forward, static_argnums=(2,), static_argnames=("config", "return_aux")
)
prefill = jax.jit(prefill, static_argnums=(2,), static_argnames=("config",))
decode_step = jax.jit(
    decode_step, static_argnums=(4,),
    static_argnames=("config", "return_hidden"),
)


@pytest.fixture(scope="module")
def setup():
    params = init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(2, 12)), jnp.int32)
    return params, ids


def _stepwise_decode_parity(
    params, ids, cfg, ref, prefill_len, atol=1e-4, lm_head=None,
    cache_dtype=jnp.float32,
):
    """Shared parity scaffold: prefill then token-by-token decode_step,
    asserting logits against ``ref`` (a (B, S, V) full-forward run) at the
    prefill boundary and every subsequent position.  Returns the final
    (logits, cache) for any extra per-test assertions."""
    cache = init_kv_cache(cfg, ids.shape[0], dtype=cache_dtype)
    logits, cache = prefill(
        params, ids[:, :prefill_len], cfg, cache, lm_head=lm_head
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref[:, prefill_len - 1]), atol=atol
    )
    for p in range(prefill_len, ids.shape[1]):
        logits, cache = decode_step(
            params, ids[:, p], jnp.asarray(p), cache, cfg, lm_head=lm_head
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref[:, p]), atol=atol,
            err_msg=f"position {p}",
        )
    return logits, cache


def test_prefill_matches_forward(setup):
    params, ids = setup
    full = forward(params, ids, CFG)  # (B, S, V)
    cache = init_kv_cache(CFG, ids.shape[0])
    logits, _ = prefill(params, ids, CFG, cache)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, -1]), atol=1e-4
    )


def test_decode_step_matches_forward(setup):
    """Feeding tokens one by one through the cache reproduces the full
    forward's logits at every position."""
    params, ids = setup
    _stepwise_decode_parity(params, ids, CFG, forward(params, ids, CFG), 4)


@pytest.mark.slow
def test_generate_cached_greedy_matches_uncached(setup):
    """temperature=0: the cached sampler and the sliding-window sampler must
    produce identical token sequences."""
    from bpe_transformer_tpu.training.sampling import generate_ids

    params, ids = setup
    prompt = [int(t) for t in np.asarray(ids[0, :5])]
    cached = generate_ids(params, CFG, prompt, max_new_tokens=10, temperature=0.0)

    out = generate_cached(
        params,
        jnp.asarray([prompt], jnp.int32),
        jax.random.PRNGKey(0),
        config=CFG,
        max_new_tokens=10,
        temperature=0.0,
    )
    assert cached == [int(t) for t in np.asarray(out[0])]

    # And against the explicit full-forward argmax loop.
    seq = list(prompt)
    for _ in range(10):
        logits = forward(params, jnp.asarray([seq], jnp.int32), CFG)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert cached == seq[len(prompt):]


@pytest.mark.parametrize(
    "variant",
    [
        dict(use_post_norm=True),
        dict(ffn_type="moe", n_experts=4, capacity_factor=64.0),
        dict(
            ffn_type="moe",
            n_experts=4,
            router_top_k=2,
            capacity_factor=64.0,
            use_post_norm=True,
        ),
    ],
    ids=["post_norm", "moe_top1", "moe_top2_post_norm"],
)
@pytest.mark.slow
def test_cached_decode_parity_block_variants(variant):
    """Round-2 coverage: the cached path handles post-norm and MoE blocks
    (capacity generous so per-call routing has no drops) with logits parity
    at every position and greedy-token parity."""
    cfg = dataclasses.replace(CFG, **variant)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 12)), jnp.int32)

    _stepwise_decode_parity(params, ids, cfg, forward(params, ids, cfg), 4)

    # Greedy generation: cached sampler == explicit full-forward argmax loop.
    prompt = [int(t) for t in np.asarray(ids[0, :5])]
    cached = generate_cached(
        params,
        jnp.asarray([prompt], jnp.int32),
        jax.random.PRNGKey(0),
        config=cfg,
        max_new_tokens=8,
        temperature=0.0,
    )
    seq = list(prompt)
    for _ in range(8):
        lg = forward(params, jnp.asarray([seq], jnp.int32), cfg)
        seq.append(int(jnp.argmax(lg[0, -1])))
    assert [int(t) for t in np.asarray(cached[0])] == seq[len(prompt):]


def test_generate_cached_shapes_and_range(setup):
    params, _ = setup
    out = generate_cached(
        params,
        jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
        jax.random.PRNGKey(1),
        config=CFG,
        max_new_tokens=7,
        temperature=1.0,
        top_k=20,
    )
    assert out.shape == (2, 7)
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) < CFG.vocab_size))


def test_generate_cached_context_overflow_raises(setup):
    params, _ = setup
    with pytest.raises(ValueError, match="exceeds"):
        generate_cached(
            params,
            jnp.asarray([[1] * 30], jnp.int32),
            jax.random.PRNGKey(0),
            config=CFG,
            max_new_tokens=10,
        )


def test_sampler_long_generation_falls_back(setup):
    """Generation past the context window still works (sliding window)."""
    from bpe_transformer_tpu.training.sampling import generate_ids

    params, _ = setup
    out = generate_ids(
        params, CFG, [1, 2, 3], max_new_tokens=40, temperature=0.0
    )
    assert len(out) == 40


def test_top_p_sampling_masks_tail(setup):
    """top_p keeps only the nucleus: with a peaked distribution and small p,
    sampling must always return the argmax; samples stay in vocab range."""
    import jax

    from bpe_transformer_tpu.models.decode import _sample_from_logits

    logits = jnp.log(
        jnp.asarray([[0.6, 0.25, 0.1, 0.04, 0.01]], jnp.float32)
    )
    for seed in range(8):
        tok = _sample_from_logits(
            logits, jax.random.PRNGKey(seed), temperature=1.0,
            top_k=None, top_p=0.5,
        )
        assert int(tok[0]) == 0  # only the 0.6 token is in the 0.5 nucleus

    # p large enough to admit the top two: both appear, the tail never does.
    seen = set()
    for seed in range(40):
        tok = _sample_from_logits(
            logits, jax.random.PRNGKey(seed), temperature=1.0,
            top_k=None, top_p=0.85,
        )
        seen.add(int(tok[0]))
    assert seen == {0, 1}

    # Degenerate p never masks everything: p=0 reduces to greedy.
    for seed in range(4):
        tok = _sample_from_logits(
            logits, jax.random.PRNGKey(seed), temperature=1.0,
            top_k=None, top_p=0.0,
        )
        assert int(tok[0]) == 0

    # End-to-end through the cached sampler.
    params, _ = setup
    out = generate_cached(
        params,
        jnp.asarray([[1, 2, 3]], jnp.int32),
        jax.random.PRNGKey(0),
        config=CFG,
        max_new_tokens=5,
        temperature=1.0,
        top_p=0.9,
    )
    assert out.shape == (1, 5)
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) < CFG.vocab_size))


@pytest.mark.slow  # 870s tier-1 budget (PR 11 sweep; ISSUE 11 tooling guard) — runs in the full matrix
def test_moe_decode_default_capacity_no_drops():
    """At the DEFAULT capacity_factor the cached path drops nothing: serving
    takes the dropless expert layer (decode._ffn_decode ->
    moe.dropless_moe; no capacity at all), so the whole cached chain
    reproduces a drop-free full forward exactly."""
    cfg = dataclasses.replace(
        CFG, ffn_type="moe", n_experts=4, capacity_factor=1.25
    )
    nodrop = dataclasses.replace(cfg, capacity_factor=100.0)
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 12)), jnp.int32)

    # Drop-free oracle: the default-capacity cached chain must match it.
    _stepwise_decode_parity(params, ids, cfg, forward(params, ids, nodrop), 4)


@pytest.mark.slow
def test_moe_decode_step_dropfree_with_degenerate_capacity():
    """Even when the training forward's expert capacity is below the batch
    size (many experts, tiny context), single-token decode steps stay
    drop-free: the served layer is dropless whatever capacity_factor says."""
    cfg = dataclasses.replace(
        CFG,
        context_length=16,
        ffn_type="moe",
        n_experts=64,
        capacity_factor=1.0,  # full-length cap = ceil(8*16/64) = 2 < B=8
    )
    nodrop = dataclasses.replace(cfg, capacity_factor=100.0)
    params = init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(5)
    B = 8
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, 10)), jnp.int32)

    _stepwise_decode_parity(params, ids, cfg, forward(params, ids, nodrop), 2)


@pytest.mark.slow  # 870s tier-1 budget (PR 11 sweep; ISSUE 11 tooling guard) — runs in the full matrix
def test_bf16_cached_decode_close_to_bf16_forward():
    """The cached path honors activation_dtype: under bf16 the whole chain
    (params cast once, bf16 KV cache, bf16 einsums, f32 softmax/logits)
    tracks the bf16 full forward closely — the gpt2 presets are bf16, so
    they must get the O(1)-per-token path, not the sliding-window fallback."""
    cfg = dataclasses.replace(CFG, activation_dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 12)), jnp.int32)

    ref = forward(params, ids, cfg)  # bf16 compute, f32 logits

    from bpe_transformer_tpu.models.transformer import lm_head_weight

    act = jnp.bfloat16
    head = lm_head_weight(params, cfg).astype(jnp.float32)  # master, f32
    cast = jax.tree_util.tree_map(lambda p: p.astype(act), params)
    logits, cache = _stepwise_decode_parity(
        cast, ids, cfg, ref, 4, atol=0.1, lm_head=head, cache_dtype=act
    )
    assert logits.dtype == jnp.float32
    assert cache[0]["k"].dtype == act


def test_generate_ids_bf16_uses_cached_fast_path(monkeypatch):
    """generate_ids routes bf16 configs through generate_cached now."""
    from bpe_transformer_tpu.models import decode as decode_mod
    from bpe_transformer_tpu.training.sampling import generate_ids

    cfg = dataclasses.replace(CFG, activation_dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(1), cfg)

    calls = []
    real = decode_mod.generate_cached
    monkeypatch.setattr(
        decode_mod,
        "generate_cached",
        lambda *a, **k: calls.append(1) or real(*a, **k),
    )
    out = generate_ids(params, cfg, [1, 2, 3], max_new_tokens=6, temperature=0.5)
    assert calls, "bf16 config took the slow sliding-window path"
    assert len(out) == 6 and all(0 <= t < cfg.vocab_size for t in out)


@pytest.mark.slow  # 870s tier-1 budget (PR 11 sweep; ISSUE 11 tooling guard) — runs in the full matrix
def test_pallas_decode_attention_impl_matches_xla(setup):
    """decode_attention_impl="pallas" (flash-decoding kernel) reproduces the
    grouped-einsum decode path: same greedy tokens end-to-end and matching
    step logits (kernel parity itself is pinned in tests/test_kernels.py)."""
    params, ids = setup
    cfg_pallas = dataclasses.replace(CFG, decode_attention_impl="pallas")

    _stepwise_decode_parity(params, ids, cfg_pallas, forward(params, ids, CFG), 4)

    prompt = ids[:, :5]
    a = generate_cached(
        params, prompt, jax.random.PRNGKey(0), config=CFG,
        max_new_tokens=8, temperature=0.0,
    )
    b = generate_cached(
        params, prompt, jax.random.PRNGKey(0), config=cfg_pallas,
        max_new_tokens=8, temperature=0.0,
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_pallas_decode_attention_impl_gqa():
    """The kernel path reads the COMPACT GQA cache (no head expansion):
    per-step logits match the full forward on a grouped-query config."""
    gqa = dataclasses.replace(
        CFG, num_kv_heads=2, decode_attention_impl="pallas"
    )
    params = init_params(jax.random.PRNGKey(1), gqa)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, gqa.vocab_size, size=(2, 10)), jnp.int32)
    _stepwise_decode_parity(params, ids, gqa, forward(params, ids, gqa), 3)


def test_prefill_flash_matches_xla(setup):
    """attention_impl="flash" routes the prefill through the Pallas flash
    kernel (no O(plen^2) score buffer); logits match the materialized path
    and greedy generation is identical end-to-end."""
    params, ids = setup
    cfg_flash = dataclasses.replace(CFG, attention_impl="flash")

    cache = init_kv_cache(CFG, ids.shape[0])
    logits_xla, cache_xla = prefill(params, ids, CFG, cache)
    cache = init_kv_cache(cfg_flash, ids.shape[0])
    logits_fl, cache_fl = prefill(params, ids, cfg_flash, cache)
    np.testing.assert_allclose(
        np.asarray(logits_fl), np.asarray(logits_xla), atol=2e-4
    )
    # The cache contents are impl-independent (written before attention).
    for lx, lf in zip(cache_xla, cache_fl):
        np.testing.assert_allclose(np.asarray(lx["k"]), np.asarray(lf["k"]), atol=1e-6)

    prompt = ids[:, :5]
    a = generate_cached(
        params, prompt, jax.random.PRNGKey(0), config=CFG,
        max_new_tokens=8, temperature=0.0,
    )
    b = generate_cached(
        params, prompt, jax.random.PRNGKey(0), config=cfg_flash,
        max_new_tokens=8, temperature=0.0,
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sample_from_logits_edge_cases():
    """Sampler edge cases: temperature=0 (greedy argmax), top_k=1, the
    top_p mass boundary, and combined top_k+top_p filtering."""
    from bpe_transformer_tpu.models.decode import _sample_from_logits

    probs = [0.6, 0.25, 0.1, 0.04, 0.01]
    logits = jnp.log(jnp.asarray([probs], jnp.float32))

    # temperature=0: exact greedy, RNG-independent.
    for seed in range(4):
        tok = _sample_from_logits(
            logits, jax.random.PRNGKey(seed), temperature=0.0, top_k=None
        )
        assert int(tok[0]) == 0

    # top_k=1: only the argmax survives at ANY temperature.
    for seed in range(8):
        tok = _sample_from_logits(
            logits, jax.random.PRNGKey(seed), temperature=2.0, top_k=1
        )
        assert int(tok[0]) == 0

    def support(top_k, top_p, n=40):
        seen = set()
        for seed in range(n):
            tok = _sample_from_logits(
                logits, jax.random.PRNGKey(seed), temperature=1.0,
                top_k=top_k, top_p=top_p,
            )
            seen.add(int(tok[0]))
        return seen

    # top_p mass boundary: "mass BEFORE the token < p" means p exactly at
    # the leading probability excludes the runner-up; a hair above admits
    # it (the cumulative 0.6 is no longer < 0.6, but IS < 0.61).
    assert support(None, 0.6) == {0}
    assert support(None, 0.61) == {0, 1}

    # Combined: top_p acts on the top_k-RENORMALIZED distribution.  With
    # top_k=2 the two survivors renormalize to ~{0.706, 0.294}; p=0.4 cuts
    # the runner-up there, p=0.99 keeps exactly the top-k pair.
    assert support(2, 0.4) == {0}
    assert support(2, 0.99) == {0, 1}


@pytest.mark.slow  # 870s tier-1 budget (PR 11 sweep; ISSUE 11 tooling guard) — runs in the full matrix
def test_generate_cached_stop_id_pins_and_truncates(setup):
    """Satellite: the KV-cached fast path honors stop_id — post-stop tokens
    are pinned to stop_id inside the scan, and generate_ids' host-side
    truncation makes cached and sliding-window generation agree on stopped
    sequences."""
    from bpe_transformer_tpu.training.sampling import generate_ids

    params, ids = setup
    prompt = [int(t) for t in np.asarray(ids[0, :5])]
    free_run = generate_ids(params, CFG, prompt, max_new_tokens=10, temperature=0.0)
    sid = free_run[4]
    first = free_run.index(sid)

    # The raw cached program: stop at the first occurrence, then pinned.
    out = generate_cached(
        params,
        jnp.asarray([prompt], jnp.int32),
        jax.random.PRNGKey(0),
        config=CFG,
        max_new_tokens=10,
        temperature=0.0,
        stop_id=int(sid),
    )
    out = [int(t) for t in np.asarray(out[0])]
    assert out[first] == sid
    assert out[: first + 1] == free_run[: first + 1]
    assert all(t == sid for t in out[first:]), "post-stop tokens not pinned"

    # generate_ids (fast path) truncates to ... + [stop_id], agreeing with
    # the sliding-window path's early exit semantics.
    stopped = generate_ids(
        params, CFG, prompt, max_new_tokens=10, temperature=0.0,
        stop_id=int(sid),
    )
    assert stopped == free_run[: first + 1]

    # And with a stop_id that never fires, output is unchanged.
    never = generate_ids(
        params, CFG, prompt, max_new_tokens=10, temperature=0.0,
        stop_id=CFG.vocab_size + 7,
    )
    assert never == free_run


def test_decode_step_vector_positions_match_scalar(setup):
    """The per-slot generalization: a (B,) position vector with an active
    mask reproduces the scalar-pos logits for each row at its own depth,
    and inactive rows leave their cache untouched."""
    params, ids = setup
    full = forward(params, ids, CFG)

    # Two sequences prefixed to DIFFERENT lengths inside one batched cache.
    plens = [4, 7]
    cache = init_kv_cache(CFG, 2)
    for row, plen in enumerate(plens):
        row_cache = init_kv_cache(CFG, 1)
        _, row_cache = prefill(params, ids[row : row + 1, :plen], CFG, row_cache)
        cache = [
            {
                "k": layer["k"].at[row].set(filled["k"][0]),
                "v": layer["v"].at[row].set(filled["v"][0]),
            }
            for layer, filled in zip(cache, row_cache)
        ]

    pos = jnp.asarray(plens)
    tokens = jnp.stack([ids[0, plens[0]], ids[1, plens[1]]])

    # Both rows active at ragged depths: each row's logits match the full
    # forward at ITS position.
    logits, new_cache = decode_step(
        params, tokens, pos, cache, CFG, active=jnp.asarray([True, True])
    )
    for row, plen in enumerate(plens):
        np.testing.assert_allclose(
            np.asarray(logits[row]), np.asarray(full[row, plen]), atol=1e-4,
            err_msg=f"row {row} at pos {plen}",
        )
    assert not np.array_equal(
        np.asarray(new_cache[0]["k"][1]), np.asarray(cache[0]["k"][1])
    )

    # Inactive rows freeze: row 1's cache is bit-identical after the step
    # (its logits are computed but discarded by the engine).
    _, masked_cache = decode_step(
        params, tokens, pos, cache, CFG, active=jnp.asarray([True, False])
    )
    assert not np.array_equal(
        np.asarray(masked_cache[0]["k"][0]), np.asarray(cache[0]["k"][0])
    )
    np.testing.assert_array_equal(
        np.asarray(masked_cache[0]["k"][1]), np.asarray(cache[0]["k"][1])
    )


def test_vector_pos_pallas_matches_xla(setup):
    """The flash-decoding kernel accepts per-batch causal frontiers: same
    outputs as the grouped-einsum path at ragged positions."""
    from bpe_transformer_tpu.kernels.pallas.decode_attention import (
        decode_attention,
        xla_decode_attention,
    )

    rng = np.random.default_rng(11)
    B, H, KV, ctx, d = 3, 4, 2, 32, 8
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, KV, ctx, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KV, ctx, d)), jnp.float32)
    pos = jnp.asarray([3, 17, 31])
    ref = xla_decode_attention(q, k, v, pos)
    out = decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # Scalar pos still matches (the pre-generalization contract).
    np.testing.assert_allclose(
        np.asarray(decode_attention(q, k, v, 9)),
        np.asarray(xla_decode_attention(q, k, v, 9)),
        atol=2e-5,
    )


def test_top_k_threshold_matches_sort_formulation():
    """lax.top_k thresholding is equivalent to the previous full-sort kth
    selection (ties included: everything >= the k-th largest survives)."""
    from bpe_transformer_tpu.models.decode import _sample_from_logits

    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
    # Inject ties at the boundary to pin tie behavior.
    logits = logits.at[:, 10].set(logits[:, 3])
    for k in (1, 5, 64):
        kth_sort = jnp.sort(logits, axis=-1)[..., -k][..., None]
        kth_topk = jax.lax.top_k(logits, k)[0][..., -1:]
        np.testing.assert_allclose(np.asarray(kth_sort), np.asarray(kth_topk))
    # And the sampler still runs with top_k through the jitted path.
    out = _sample_from_logits(logits, jax.random.PRNGKey(0), 1.0, 5)
    assert out.shape == (4,)


def test_generate_cached_with_tp_sharded_params():
    """Multi-chip INFERENCE with no decode-specific sharding code: GSPMD
    propagates the tensor-parallel parameter shardings through prefill, the
    KV cache, and the scanned token loop, reproducing the single-device
    greedy tokens exactly."""
    from bpe_transformer_tpu.parallel import make_mesh, shard_params

    cfg = dataclasses.replace(TS_TEST_CONFIG, vocab_size=512, context_length=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 8)), jnp.int32)
    ref = generate_cached(
        params, prompt, jax.random.PRNGKey(1), config=cfg,
        max_new_tokens=6, temperature=0.0,
    )

    mesh = make_mesh({"data": 2, "model": 4})
    sharded = shard_params(params, mesh, "tp")
    out = generate_cached(
        sharded, prompt, jax.random.PRNGKey(1), config=cfg,
        max_new_tokens=6, temperature=0.0,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.slow
def test_pallas_decode_attention_impl_moe_block():
    """The flash-decoding kernel composes with MoE blocks (attention is
    FFN-independent, but the integration deserves its own pin): per-step
    logits match the full forward on a routed-FFN config."""
    cfg = dataclasses.replace(
        CFG,
        ffn_type="moe",
        n_experts=4,
        capacity_factor=64.0,
        decode_attention_impl="pallas",
    )
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 10)), jnp.int32)
    _stepwise_decode_parity(params, ids, cfg, forward(params, ids, cfg), 3)
