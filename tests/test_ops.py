"""Core-op numerics: reference snapshots where derivable, torch oracles else."""

import numpy as np
import pytest
import torch
import torch.nn.functional as F

import jax.numpy as jnp

from bpe_transformer_tpu.ops import (
    clip_by_global_norm,
    cross_entropy,
    embedding,
    linear,
    rmsnorm,
    rope,
    scaled_dot_product_attention,
    silu,
    softmax,
    swiglu,
)


def _t2n(t):
    return t.detach().cpu().numpy()


# ----------------------------------------------------------- torch oracles


def test_linear_matches_torch():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((128, 64), dtype=np.float32)
    x = rng.standard_normal((4, 12, 64), dtype=np.float32)
    expected = _t2n(torch.from_numpy(x) @ torch.from_numpy(w).T)
    np.testing.assert_allclose(
        np.asarray(linear(jnp.asarray(x), jnp.asarray(w))), expected, atol=1e-5
    )


def test_embedding_matches_torch():
    rng = np.random.default_rng(1)
    table = rng.standard_normal((100, 16), dtype=np.float32)
    ids = rng.integers(0, 100, size=(4, 7))
    expected = _t2n(F.embedding(torch.from_numpy(ids), torch.from_numpy(table)))
    np.testing.assert_allclose(
        np.asarray(embedding(jnp.asarray(table), jnp.asarray(ids))), expected
    )


def test_silu_matches_torch():
    x = np.linspace(-6, 6, 101, dtype=np.float32).reshape(1, -1)
    expected = _t2n(F.silu(torch.from_numpy(x)))
    np.testing.assert_allclose(np.asarray(silu(jnp.asarray(x))), expected, atol=1e-6)


def test_softmax_matches_torch_and_is_overflow_safe():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((3, 5)).astype(np.float32)
    expected = _t2n(F.softmax(torch.from_numpy(x), dim=-1))
    np.testing.assert_allclose(
        np.asarray(softmax(jnp.asarray(x), axis=-1)), expected, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(softmax(jnp.asarray(x) + 100.0, axis=-1)), expected, atol=1e-6
    )
    # other axes too
    expected0 = _t2n(F.softmax(torch.from_numpy(x), dim=0))
    np.testing.assert_allclose(
        np.asarray(softmax(jnp.asarray(x), axis=0)), expected0, atol=1e-6
    )


def test_rmsnorm_matches_torch():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((4, 12, 64)).astype(np.float32)
    w = rng.standard_normal(64).astype(np.float32)
    xt = torch.from_numpy(x)
    expected = _t2n(
        xt * torch.rsqrt(xt.pow(2).mean(-1, keepdim=True) + 1e-5) * torch.from_numpy(w)
    )
    np.testing.assert_allclose(
        np.asarray(rmsnorm(jnp.asarray(x), jnp.asarray(w), eps=1e-5)),
        expected,
        atol=1e-6,
    )


def test_swiglu_matches_torch():
    rng = np.random.default_rng(4)
    d_model, d_ff = 64, 128
    x = rng.standard_normal((4, 12, d_model)).astype(np.float32)
    w1 = rng.standard_normal((d_ff, d_model)).astype(np.float32) * 0.1
    w2 = rng.standard_normal((d_model, d_ff)).astype(np.float32) * 0.1
    w3 = rng.standard_normal((d_ff, d_model)).astype(np.float32) * 0.1
    xt = torch.from_numpy(x)
    expected = _t2n(
        (F.silu(xt @ torch.from_numpy(w1).T) * (xt @ torch.from_numpy(w3).T))
        @ torch.from_numpy(w2).T
    )
    actual = np.asarray(
        swiglu(jnp.asarray(x), jnp.asarray(w1), jnp.asarray(w2), jnp.asarray(w3))
    )
    np.testing.assert_allclose(actual, expected, atol=1e-5)


def test_cross_entropy_matches_torch_and_is_overflow_safe():
    rng = np.random.default_rng(5)
    logits = rng.random((8, 5)).astype(np.float32)
    targets = rng.integers(0, 5, size=8)
    expected = _t2n(
        F.cross_entropy(torch.from_numpy(logits), torch.from_numpy(targets))
    )
    np.testing.assert_allclose(
        np.asarray(cross_entropy(jnp.asarray(logits), jnp.asarray(targets))),
        expected,
        atol=1e-4,
    )
    big = logits * 1000.0
    expected_big = _t2n(
        F.cross_entropy(torch.from_numpy(big), torch.from_numpy(targets))
    )
    np.testing.assert_allclose(
        np.asarray(cross_entropy(jnp.asarray(big), jnp.asarray(targets))),
        expected_big,
        atol=1e-4,
    )


def test_gradient_clipping_matches_torch():
    rng = np.random.default_rng(6)
    grads = {
        "a": rng.standard_normal((5, 5)).astype(np.float32),
        "b": {"c": rng.standard_normal(7).astype(np.float32)},
    }
    max_norm = 1e-2
    params_t = [
        torch.nn.Parameter(torch.zeros(5, 5)),
        torch.nn.Parameter(torch.zeros(7)),
    ]
    params_t[0].grad = torch.from_numpy(grads["a"].copy())
    params_t[1].grad = torch.from_numpy(grads["b"]["c"].copy())
    torch.nn.utils.clip_grad_norm_(params_t, max_norm)

    clipped, norm = clip_by_global_norm(
        {"a": jnp.asarray(grads["a"]), "b": {"c": jnp.asarray(grads["b"]["c"])}},
        max_norm,
    )
    np.testing.assert_allclose(
        np.asarray(clipped["a"]), _t2n(params_t[0].grad), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(clipped["b"]["c"]), _t2n(params_t[1].grad), atol=1e-6
    )
    assert float(norm) > max_norm  # this fixture definitely clips


def test_gradient_clipping_noop_below_budget():
    g = {"a": jnp.asarray(np.full((2, 2), 1e-4, dtype=np.float32))}
    clipped, _ = clip_by_global_norm(g, max_norm=10.0)
    np.testing.assert_allclose(np.asarray(clipped["a"]), np.asarray(g["a"]))


# --------------------------------------------- reference snapshot parity


def _seeded_qkvm():
    torch.manual_seed(1)
    q = torch.randn(4, 12, 64)
    torch.manual_seed(2)
    k = torch.randn(4, 16, 64)
    torch.manual_seed(3)
    v = torch.randn(4, 16, 64)
    torch.manual_seed(5)
    mask = torch.randn(4, 12, 16) > 0.5
    return q, k, v, mask


def test_sdpa_matches_reference_snapshot(reference_snapshots):
    expected = dict(np.load(reference_snapshots / "test_scaled_dot_product_attention.npz"))[
        "array"
    ]
    q, k, v, mask = _seeded_qkvm()
    actual = scaled_dot_product_attention(
        jnp.asarray(_t2n(q)), jnp.asarray(_t2n(k)), jnp.asarray(_t2n(v)),
        jnp.asarray(_t2n(mask)),
    )
    np.testing.assert_allclose(np.asarray(actual), expected, atol=1e-6, rtol=1e-4)


def test_sdpa_4d_matches_reference_snapshot(reference_snapshots):
    expected = dict(
        np.load(reference_snapshots / "test_4d_scaled_dot_product_attention.npz")
    )["array"]
    q, k, v, mask = _seeded_qkvm()
    reshape = lambda t, s: jnp.asarray(_t2n(t)).reshape(s)
    actual = scaled_dot_product_attention(
        reshape(q, (2, 2, 12, 64)),
        reshape(k, (2, 2, 16, 64)),
        reshape(v, (2, 2, 16, 64)),
        jnp.asarray(_t2n(mask)).reshape(2, 2, 12, 16),
    )
    np.testing.assert_allclose(np.asarray(actual), expected, atol=1e-6, rtol=1e-4)


def test_rope_matches_reference_snapshot(reference_snapshots):
    expected = dict(np.load(reference_snapshots / "test_rope.npz"))["array"]
    torch.manual_seed(4)
    x = torch.randn(4, 12, 64)
    actual = rope(
        jnp.asarray(_t2n(x)), jnp.arange(12), theta=10000.0, max_seq_len=12
    )
    np.testing.assert_allclose(np.asarray(actual), expected, atol=1e-6, rtol=1e-4)


def test_sdpa_fully_masked_rows_are_finite():
    q, k, v, _ = _seeded_qkvm()
    mask = jnp.zeros((4, 12, 16), dtype=bool)  # everything masked
    out = scaled_dot_product_attention(
        jnp.asarray(_t2n(q)), jnp.asarray(_t2n(k)), jnp.asarray(_t2n(v)), mask
    )
    assert np.isfinite(np.asarray(out)).all()


def _loss_case(hidden_dtype=np.float32, head_dtype=np.float32):
    """``(hidden, head, targets)`` of the chunked-loss tests: 2 x 16 tokens,
    d 8, vocab 50."""
    rng = np.random.default_rng(0)
    b, s, d, v = 2, 16, 8, 50
    hidden = jnp.asarray(rng.normal(size=(b, s, d)), hidden_dtype)
    head = jnp.asarray(rng.normal(size=(v, d)), head_dtype)
    targets = jnp.asarray(rng.integers(0, v, size=(b, s)))
    return hidden, head, targets


def _checkpointed_chunk_loss(hidden, lm_head_w, targets, chunk_size):
    """The chunked loss as it was until ISSUE 48, kept as the plain
    reference: a chunk's logits under ``jax.checkpoint`` inside a
    ``lax.map``, its gradients XLA's own transposes."""
    import jax
    from jax import lax
    from jax.scipy.special import logsumexp

    from bpe_transformer_tpu.ops.core import head_logits

    batch, seq, d = hidden.shape
    n_chunks = seq // chunk_size
    h = hidden.reshape(batch, n_chunks, chunk_size, d).swapaxes(0, 1)
    t = targets.reshape(batch, n_chunks, chunk_size).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_nll(args):
        hc, tc = args
        logits = head_logits(hc, lm_head_w)
        target_logit = jnp.take_along_axis(
            logits, tc[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        return (logsumexp(logits, axis=-1) - target_logit).sum()

    return lax.map(chunk_nll, (h, t)).sum() / (batch * seq)


def _equations(jaxpr, *primitives):
    """Every equation of a jaxpr and of the jaxprs its equations hold, or
    those of the named primitives alone."""
    for eqn in jaxpr.eqns:
        if not primitives or eqn.primitive.name in primitives:
            yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, *primitives)


@pytest.mark.parametrize("chunk", [4, 16], ids=["chunk4", "chunk_is_seq"])
def test_chunked_lm_cross_entropy_matches_full(chunk):
    """Chunked loss == full-logits loss, in value AND gradients."""
    import jax

    from bpe_transformer_tpu.ops.losses import chunked_lm_cross_entropy, cross_entropy

    hidden, head, targets = _loss_case()
    full = lambda h, w: cross_entropy(h @ w.T, targets)
    chunked = lambda h, w: chunked_lm_cross_entropy(h, w, targets, chunk_size=chunk)

    np.testing.assert_allclose(
        float(chunked(hidden, head)), float(full(hidden, head)), rtol=1e-6
    )
    g_full = jax.grad(full, argnums=(0, 1))(hidden, head)
    g_chunk = jax.grad(chunked, argnums=(0, 1))(hidden, head)
    for a, c in zip(g_full, g_chunk):
        np.testing.assert_allclose(np.asarray(c), np.asarray(a), atol=1e-5)


def test_chunked_lm_cross_entropy_refuses_a_chunk_that_does_not_divide():
    from bpe_transformer_tpu.ops.losses import chunked_lm_cross_entropy

    hidden, head, targets = _loss_case()
    with pytest.raises(ValueError, match="divisible"):
        chunked_lm_cross_entropy(hidden, head, targets, chunk_size=5)


def test_chunked_lm_cross_entropy_gradients_are_the_checkpointed_loops():
    """On the training path's dtypes (bfloat16 hidden states, a float32
    head) the gradients made in the forward loop are, bit for bit on the
    CPU, those XLA transposed out of the checkpointed loop: same operand
    dtypes, same accumulation, same casts."""
    import jax

    from bpe_transformer_tpu.ops.losses import chunked_lm_cross_entropy

    hidden, head, targets = _loss_case(hidden_dtype=jnp.bfloat16)
    grads = lambda loss: jax.jit(
        jax.value_and_grad(lambda h, w: loss(h, w, targets, 4), argnums=(0, 1))
    )(hidden, head)
    value, (dh, dw) = grads(chunked_lm_cross_entropy)
    want, (want_dh, want_dw) = grads(_checkpointed_chunk_loss)
    np.testing.assert_allclose(float(value), float(want), rtol=1e-6)
    assert (dh.dtype, dw.dtype) == (jnp.bfloat16, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(dh, np.float32), np.asarray(want_dh, np.float32)
    )
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(want_dw))


def test_chunked_lm_cross_entropy_scales_with_its_cotangent():
    """The backward rule is a scaling: under ``3 * loss + aux`` the loss's
    share of both gradients is three times the gradient of the loss."""
    import jax

    from bpe_transformer_tpu.ops.losses import chunked_lm_cross_entropy

    hidden, head, targets = _loss_case()
    loss = lambda h, w: chunked_lm_cross_entropy(h, w, targets, 4)
    aux = lambda h, w: jnp.sum(h * h) + jnp.sum(w)
    both = lambda h, w: 3.0 * loss(h, w) + aux(h, w)
    g_loss, g_aux, g_both = (
        jax.grad(f, argnums=(0, 1))(hidden, head) for f in (loss, aux, both)
    )
    for plain, other, got in zip(g_loss, g_aux, g_both):
        np.testing.assert_allclose(
            np.asarray(got), 3.0 * np.asarray(plain) + np.asarray(other),
            rtol=1e-5, atol=1e-6,
        )


def test_chunked_lm_cross_entropy_outside_a_grad_makes_no_gradient():
    """The eval path: one logits-shaped product a chunk, and no equation
    whose result has the hidden states' or the head's shape."""
    import jax

    from bpe_transformer_tpu.ops.losses import chunked_lm_cross_entropy

    hidden, head, targets = _loss_case()
    jaxpr = jax.make_jaxpr(
        lambda h, w: chunked_lm_cross_entropy(h, w, targets, 4)
    )(hidden, head).jaxpr
    (_loop,) = _equations(jaxpr, "scan", "while")
    (dot,) = _equations(jaxpr, "dot_general")
    assert dot.outvars[0].aval.shape == (2, 4, 50)
    made = {v.aval.shape for e in _equations(jaxpr) for v in e.outvars}
    assert head.shape not in made and hidden.shape not in made


def test_chunked_lm_cross_entropy_under_a_grad_is_one_loop_of_three_products():
    """Loss and gradients come out of ONE loop whose body holds exactly
    three products - the logits, ``dlogits @ W`` and ``dlogits^T @ h`` -
    so no chunk's logits are computed twice."""
    import jax

    from bpe_transformer_tpu.ops.losses import chunked_lm_cross_entropy

    hidden, head, targets = _loss_case(hidden_dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        jax.value_and_grad(
            lambda h, w: chunked_lm_cross_entropy(h, w, targets, 4), argnums=(0, 1)
        )
    )(hidden, head).jaxpr
    (loop,) = _equations(jaxpr, "scan", "while")
    assert loop.params["length"] == 4
    dots = list(_equations(jaxpr, "dot_general"))
    assert sorted(e.outvars[0].aval.shape for e in dots) == [
        (2, 4, 8), (2, 4, 50), (50, 8),
    ]
    assert dots == list(_equations(loop.params["jaxpr"].jaxpr, "dot_general"))


def test_head_logits_dtype_rule():
    """head_logits: matmul in the hidden's dtype, f32 accumulation/output.

    f32 inputs must be bit-identical to a plain f32 matmul; bf16 inputs
    must produce f32 logits close to the f32 oracle (the head weight is
    read at bf16, so tolerance is bf16-level).
    """
    from bpe_transformer_tpu.ops.core import head_logits

    rng = np.random.default_rng(0)
    hidden32 = jnp.asarray(rng.normal(size=(2, 16, 8)).astype(np.float32))
    head32 = jnp.asarray(rng.normal(size=(50, 8)).astype(np.float32))

    oracle = hidden32 @ head32.T
    exact = head_logits(hidden32, head32)
    assert exact.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(exact), np.asarray(oracle))

    mixed = head_logits(hidden32.astype(jnp.bfloat16), head32)
    assert mixed.dtype == jnp.float32  # accumulation/output stay f32
    np.testing.assert_allclose(
        np.asarray(mixed), np.asarray(oracle), rtol=0.05, atol=0.1
    )
