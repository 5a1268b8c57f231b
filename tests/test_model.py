"""Transformer block/LM vs an independent torch oracle + contract checks.

The reference's trained-weights fixture (`ts_tests/model.pt`) is absent from
the mounted checkout (.MISSING_LARGE_BLOBS), so full-LM snapshot parity is
unverifiable; instead an independent torch implementation of the pinned
architecture (pre-norm RMSNorm / RoPE / causal MHA / SwiGLU, head-concat
weight layout per `adapters.py:209-361`) serves as the oracle on random
weights drawn in the reference state-dict schema.
"""

import numpy as np
import pytest
import torch
import torch.nn.functional as F

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.models import (
    TS_TEST_CONFIG,
    ModelConfig,
    forward,
    init_params,
    params_from_state_dict,
    state_dict_from_params,
    transformer_block,
)
from bpe_transformer_tpu.ops import rope_tables

# ------------------------------------------------------ torch oracle


def torch_rope(x, positions, theta):
    d = x.shape[-1]
    inv = theta ** (-torch.arange(0, d, 2, dtype=torch.float32) / d)
    ang = positions.float()[:, None] * inv[None, :]
    cos, sin = torch.cos(ang), torch.sin(ang)
    xe, xo = x[..., 0::2], x[..., 1::2]
    out = torch.empty_like(x)
    out[..., 0::2] = xe * cos - xo * sin
    out[..., 1::2] = xe * sin + xo * cos
    return out


def torch_mha(x, qw, kw, vw, ow, n_heads, theta=None):
    b, s, d = x.shape
    dh = d // n_heads
    split = lambda t: (x @ t.T).view(b, s, n_heads, dh).transpose(1, 2)
    q, k, v = split(qw), split(kw), split(vw)
    if theta is not None:
        pos = torch.arange(s)
        q = torch_rope(q, pos, theta)
        k = torch_rope(k, pos, theta)
    scores = q @ k.transpose(-1, -2) / dh**0.5
    mask = torch.tril(torch.ones(s, s, dtype=torch.bool))
    scores = scores.masked_fill(~mask, float("-inf"))
    out = (F.softmax(scores, dim=-1) @ v).transpose(1, 2).reshape(b, s, d)
    return out @ ow.T


def torch_rmsnorm(x, w):
    return x * torch.rsqrt(x.pow(2).mean(-1, keepdim=True) + 1e-5) * w


def torch_block(x, w, n_heads, theta):
    h = torch_rmsnorm(x, w["ln1.weight"])
    x = x + torch_mha(
        h,
        w["attn.q_proj.weight"],
        w["attn.k_proj.weight"],
        w["attn.v_proj.weight"],
        w["attn.output_proj.weight"],
        n_heads,
        theta,
    )
    h = torch_rmsnorm(x, w["ln2.weight"])
    ffn = (
        F.silu(h @ w["ffn.w1.weight"].T) * (h @ w["ffn.w3.weight"].T)
    ) @ w["ffn.w2.weight"].T
    return x + ffn


def torch_lm(indices, sd, cfg: ModelConfig):
    x = F.embedding(indices, sd["token_embeddings.weight"])
    for i in range(cfg.num_layers):
        w = {k[len(f"layers.{i}.") :]: v for k, v in sd.items() if k.startswith(f"layers.{i}.")}
        x = torch_block(x, w, cfg.num_heads, cfg.rope_theta)
    x = torch_rmsnorm(x, sd["ln_final.weight"])
    return x @ sd["lm_head.weight"].T


def random_state_dict(cfg: ModelConfig, seed=0):
    g = torch.Generator().manual_seed(seed)
    rand = lambda *s: torch.randn(*s, generator=g) * 0.05
    sd = {
        "token_embeddings.weight": rand(cfg.vocab_size, cfg.d_model),
        "ln_final.weight": 1 + 0.1 * rand(cfg.d_model),
        "lm_head.weight": rand(cfg.vocab_size, cfg.d_model),
    }
    for i in range(cfg.num_layers):
        p = f"layers.{i}."
        sd[p + "attn.q_proj.weight"] = rand(cfg.d_model, cfg.d_model)
        sd[p + "attn.k_proj.weight"] = rand(cfg.d_model, cfg.d_model)
        sd[p + "attn.v_proj.weight"] = rand(cfg.d_model, cfg.d_model)
        sd[p + "attn.output_proj.weight"] = rand(cfg.d_model, cfg.d_model)
        sd[p + "ln1.weight"] = 1 + 0.1 * rand(cfg.d_model)
        sd[p + "ln2.weight"] = 1 + 0.1 * rand(cfg.d_model)
        sd[p + "ffn.w1.weight"] = rand(cfg.d_ff, cfg.d_model)
        sd[p + "ffn.w2.weight"] = rand(cfg.d_model, cfg.d_ff)
        sd[p + "ffn.w3.weight"] = rand(cfg.d_ff, cfg.d_model)
    return sd


CFG = TS_TEST_CONFIG


@pytest.fixture(scope="module")
def oracle_setup():
    sd = random_state_dict(CFG)
    params = params_from_state_dict({k: v.numpy() for k, v in sd.items()}, CFG.num_layers)
    g = torch.Generator().manual_seed(42)
    indices = torch.randint(0, CFG.vocab_size, (4, 12), generator=g)
    return sd, params, indices


def test_block_matches_torch_oracle(oracle_setup):
    sd, params, _ = oracle_setup
    g = torch.Generator().manual_seed(7)
    x = torch.randn(4, 12, CFG.d_model, generator=g)
    expected = torch_block(
        x, {k[len("layers.0.") :]: v for k, v in sd.items() if k.startswith("layers.0.")},
        CFG.num_heads, CFG.rope_theta,
    )
    cos, sin = rope_tables(CFG.d_head, CFG.context_length, CFG.rope_theta)
    actual = transformer_block(
        jnp.asarray(x.numpy()),
        params["layers"][0],
        CFG,
        (cos, sin),
        jnp.arange(12),
    )
    np.testing.assert_allclose(
        np.asarray(actual), expected.numpy(), atol=2e-5, rtol=1e-4
    )


def test_lm_matches_torch_oracle(oracle_setup):
    sd, params, indices = oracle_setup
    expected = torch_lm(indices, sd, CFG)
    actual = forward(params, jnp.asarray(indices.numpy()), CFG)
    np.testing.assert_allclose(
        np.asarray(actual), expected.numpy(), atol=1e-4, rtol=1e-2
    )


def test_lm_truncated_input(oracle_setup):
    sd, params, indices = oracle_setup
    truncated = indices[:, :6]
    expected = torch_lm(truncated, sd, CFG)
    actual = forward(params, jnp.asarray(truncated.numpy()), CFG)
    np.testing.assert_allclose(
        np.asarray(actual), expected.numpy(), atol=1e-4, rtol=1e-2
    )


def test_state_dict_roundtrip(oracle_setup):
    _, params, _ = oracle_setup
    flat = state_dict_from_params(params)
    rebuilt = params_from_state_dict(flat, CFG.num_layers)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params,
        rebuilt,
    )


def test_init_params_shapes():
    params = init_params(jax.random.PRNGKey(0), CFG)
    assert params["token_embeddings"].shape == (CFG.vocab_size, CFG.d_model)
    assert len(params["layers"]) == CFG.num_layers
    assert params["layers"][0]["ffn"]["w1"].shape == (CFG.d_ff, CFG.d_model)
    logits = forward(params, jnp.zeros((2, 8), dtype=jnp.int32), CFG)
    assert logits.shape == (2, 8, CFG.vocab_size)
    assert logits.dtype == jnp.float32


def test_remat_forward_is_identical(oracle_setup):
    _, params, indices = oracle_setup
    import dataclasses

    remat_cfg = dataclasses.replace(CFG, remat=True)
    base = forward(params, jnp.asarray(indices.numpy()), CFG)
    remat = forward(params, jnp.asarray(indices.numpy()), remat_cfg)
    np.testing.assert_allclose(np.asarray(base), np.asarray(remat), atol=1e-6)


def test_bfloat16_activation_path_runs():
    import dataclasses

    cfg = dataclasses.replace(CFG, activation_dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    logits = forward(params, jnp.zeros((2, 8), dtype=jnp.int32), cfg)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_ablation_flags_change_output(oracle_setup):
    import dataclasses

    _, params, indices = oracle_setup
    ids = jnp.asarray(indices.numpy())
    base = np.asarray(forward(params, ids, CFG))
    for flag in ("remove_rmsnorm", "use_post_norm", "remove_rope"):
        cfg = dataclasses.replace(CFG, **{flag: True})
        alt = np.asarray(forward(params, ids, cfg))
        assert not np.allclose(alt, base), flag


def test_config_json_roundtrip(tmp_path, reference_fixtures):
    cfg = ModelConfig.from_json(
        reference_fixtures / "ts_tests" / "model_config.json"
    )
    assert cfg == TS_TEST_CONFIG
    cfg.to_json(tmp_path / "cfg.json")
    assert ModelConfig.from_json(tmp_path / "cfg.json") == cfg


# ------------------------------------------------ grouped-query attention


def test_gqa_equals_mha_with_repeated_kv_weights():
    """A GQA forward == an MHA forward whose K/V weights repeat each KV
    head's block once per query group (the defining GQA identity)."""
    import dataclasses

    from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params

    cfg_gqa = dataclasses.replace(
        TS_TEST_CONFIG, vocab_size=256, num_kv_heads=2
    )  # 4 query heads, 2 KV heads
    cfg_mha = dataclasses.replace(TS_TEST_CONFIG, vocab_size=256)
    params = init_params(jax.random.PRNGKey(0), cfg_gqa)

    def repeat_kv(w):  # (kv*dh, d) -> (H*dh, d), each head block doubled
        dh = cfg_gqa.d_head
        blocks = [w[i * dh : (i + 1) * dh] for i in range(cfg_gqa.num_kv_heads)]
        group = cfg_gqa.num_heads // cfg_gqa.num_kv_heads
        return jnp.concatenate([b for blk in blocks for b in [blk] * group])

    mha_params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    mha_params["layers"] = [
        {
            **layer,
            "attn": {
                **layer["attn"],
                "k_proj": repeat_kv(layer["attn"]["k_proj"]),
                "v_proj": repeat_kv(layer["attn"]["v_proj"]),
            },
        }
        for layer in params["layers"]
    ]

    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, size=(2, 12)), jnp.int32
    )
    out_gqa = forward(params, ids, cfg_gqa)
    out_mha = forward(mha_params, ids, cfg_mha)
    np.testing.assert_allclose(
        np.asarray(out_gqa), np.asarray(out_mha), atol=1e-5
    )


def _greedy_reference(params, cfg, prompt, n_new):
    """The full-forward argmax loop cached decode is compared with, as ONE
    compiled program: the growing sequence is right-padded to the context
    (causal attention never looks at what follows), where an eager forward
    per length would re-trace every op n_new times."""
    from bpe_transformer_tpu.models import forward

    fwd = jax.jit(lambda p, x: forward(p, x, cfg))
    seq = list(prompt)
    for _ in range(n_new):
        padded = seq + [0] * (cfg.context_length - len(seq))
        logits = fwd(params, jnp.asarray([padded], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[len(prompt):]


def test_gqa_cached_decode_parity_and_cache_shape():
    """GQA: the KV cache holds only num_kv_heads, and cached greedy decode
    matches the full-forward argmax loop."""
    import dataclasses

    from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params
    from bpe_transformer_tpu.models.decode import generate_cached, init_kv_cache

    cfg = dataclasses.replace(
        TS_TEST_CONFIG, vocab_size=256, context_length=32, num_kv_heads=1
    )
    cache = init_kv_cache(cfg, batch=2)
    assert cache[0]["k"].shape == (2, 1, 32, cfg.d_head)

    params = init_params(jax.random.PRNGKey(1), cfg)
    prompt = [3, 1, 4, 1, 5]
    out = generate_cached(
        params,
        jnp.asarray([prompt], jnp.int32),
        jax.random.PRNGKey(0),
        config=cfg,
        max_new_tokens=8,
        temperature=0.0,
    )
    assert [int(t) for t in np.asarray(out[0])] == _greedy_reference(
        params, cfg, prompt, 8
    )


def test_tied_embeddings_share_head():
    """tie_embeddings: no lm_head parameter; logits use the embedding
    matrix; training moves the tied matrix; cached decode matches the full
    forward."""
    import dataclasses

    from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params
    from bpe_transformer_tpu.models.decode import generate_cached
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.training.train_step import TrainHParams, make_train_step

    cfg = dataclasses.replace(
        TS_TEST_CONFIG, vocab_size=256, context_length=32, tie_embeddings=True
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in params

    # State-dict export/import stays an inverse pair without the head key.
    from bpe_transformer_tpu.models.transformer import (
        params_from_state_dict,
        state_dict_from_params,
    )

    sd = state_dict_from_params(params)
    assert "lm_head.weight" not in sd
    back = params_from_state_dict(sd, cfg.num_layers, tied=True)
    assert "lm_head" not in back
    # Untied load of a tied export fails FAST at the missing key.
    with pytest.raises(KeyError):
        params_from_state_dict(sd, cfg.num_layers)

    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, size=(2, 8)), jnp.int32
    )
    logits = forward(params, ids, cfg)
    assert logits.shape == (2, 8, 256)

    # Cached decode greedy parity (before training: the train step donates
    # and deletes the param buffers).
    prompt = [1, 2, 3, 4]
    out = generate_cached(
        params, jnp.asarray([prompt], jnp.int32), jax.random.PRNGKey(0),
        config=cfg, max_new_tokens=6, temperature=0.0,
    )
    assert [int(t) for t in np.asarray(out[0])] == _greedy_reference(
        params, cfg, prompt, 6
    )

    # Chunked-loss path exercises lm_head_weight too.
    cfg_chunk = dataclasses.replace(cfg, loss_chunk_size=8)
    step = make_train_step(cfg_chunk, TrainHParams(warmup_iters=1, cosine_cycle_iters=20))
    opt = adamw_init(params)
    p, s, m0 = step(params, opt, ids, jnp.roll(ids, -1, axis=1))
    for _ in range(5):
        p, s, m = step(p, s, ids, jnp.roll(ids, -1, axis=1))
    assert float(m["loss"]) < float(m0["loss"])
