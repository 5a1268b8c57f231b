"""sarvam-105b's block at a small size on the CPU: latent attention in the
sequential block (`LatentRows` at one sublayer a layer) - the plain forward,
the dense latent cache and the paged engine's chunks and ticks against
``chipbench/reference_sarvam.py`` on seeded float32 weights, at contexts past
the (small) original length so the ramp and the stretched pairs are in use;
YaRN's frequencies and the softmax scale at the published numbers; each
mechanism the configuration brings with a control that leaves it out; the
share test that ties a chip's experts to the whole layer; the kernels in
interpret mode under the config's scale; and every refusal."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bpe_transformer_tpu.kernels.pallas import mla_attention  # noqa: E402
from bpe_transformer_tpu.models import mla, moe  # noqa: E402
from bpe_transformer_tpu.models.config import ModelConfig  # noqa: E402
from bpe_transformer_tpu.models.decode import (  # noqa: E402
    LatentRows,
    cache_kind,
    decode_step,
    init_kv_cache,
    paged_forward,
    prefill,
    slot_cache,
)
from bpe_transformer_tpu.models.moe import dropless_moe  # noqa: E402
from bpe_transformer_tpu.models.transformer import forward, init_params  # noqa: E402
from bpe_transformer_tpu.ops.rope import (  # noqa: E402
    rope_tables,
    yarn_correction_range,
    yarn_inv_freq,
    yarn_mscale,
)
from bpe_transformer_tpu.serving.kvpool import host_cache  # noqa: E402
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine  # noqa: E402
from chipbench import reference_sarvam as ref  # noqa: E402

CONFIGS = Path(__file__).resolve().parents[1] / "chipbench" / "configs"
#: ``rope_scaling`` as published, and at the small size: 4 pairs at base
#: 100, original length 16, so that pair 0 keeps its frequency, pairs 1 and
#: 2 lie on the ramp and pair 3 is divided by 8 - and every sequence here
#: passes 16 positions.
PUBLISHED_YARN = {
    "type": "deepseek_yarn", "factor": 40, "original_max_position_embeddings": 4096,
    "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
}
SMALL_YARN = {
    "type": "deepseek_yarn", "factor": 8, "original_max_position_embeddings": 16,
    "beta_fast": 4, "beta_slow": 0.25, "mscale": 1, "mscale_all_dim": 1,
}


def reference_cfg(held=4, offset=0, layers=3) -> dict:
    """Hidden 32, 4 heads of 8 + 8 / 8 over a latent of 16, a dense layer of
    48 then expert layers: 16 experts of 16, 4 a token, of which ``held``
    are here, and one shared expert."""
    return {
        "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
        "num_hidden_layers": layers, "num_attention_heads": 4, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8, "q_head_dim": 16,
        "rms_norm_eps": 1e-6, "rope_theta": 100.0, "rope_scaling": dict(SMALL_YARN),
        "first_k_dense_replace": 1, "num_experts": held, "n_experts": 16,
        "expert_offset": offset, "num_experts_per_tok": 4, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "vocab_size": 64, "context_length": 64,
    }


def yarn_fields(scaling: dict) -> dict:
    return dict(
        yarn_factor=float(scaling["factor"]),
        yarn_original_context=scaling["original_max_position_embeddings"],
        yarn_beta_fast=float(scaling["beta_fast"]), yarn_beta_slow=float(scaling["beta_slow"]),
        yarn_mscale=float(scaling["mscale"]),
        yarn_mscale_all_dim=float(scaling["mscale_all_dim"]),
    )


def program_cfg(c: dict, **more) -> ModelConfig:
    dense = c["first_k_dense_replace"]
    args = dict(
        vocab_size=c["vocab_size"], context_length=c["context_length"],
        d_model=c["hidden_size"], num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"], d_ff=c["intermediate_size"],
        rope_theta=c["rope_theta"], attention_kind="mla",
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        layer_pattern="A" * dense + "a" * (c["num_hidden_layers"] - dense),
        norm_eps=c["rms_norm_eps"], **yarn_fields(c["rope_scaling"]),
        ffn_type="moe", moe_router="sigmoid", n_experts=c["n_experts"],
        router_top_k=c["num_experts_per_tok"], expert_d_ff=c["moe_intermediate_size"],
        router_bias=True, routed_scaling_factor=c["routed_scaling_factor"],
        n_shared_experts=c["num_shared_experts"], experts_held=c["num_experts"],
        expert_offset=c["expert_offset"],
    )
    args.update(more)
    return ModelConfig(**args)


def small_engine(c, weights=None, config=None, **more) -> PagedEngine:
    args = dict(slots=3, block_size=4, prefill_chunk=8, prefill_buckets=(4, 8))
    args.update(more)
    weights = ref.weights_from_seed(3, c) if weights is None else weights
    return PagedEngine(weights, config or program_cfg(c), **args)


def served_logits(eng, tokens, plen):
    """Prefill ``tokens[:plen]`` in the engine's chunks, then teacher-forced
    ticks to the end: float32 logits of positions ``plen ..``, each through
    `paged_forward` as a tick runs it, and the slot."""
    pc = eng.config
    slot = eng.begin(tokens[:plen], max_new_tokens=len(tokens) - plen, temperature=0.0)
    while eng.prefill_step(slot) is None:
        pass
    active = np.zeros(eng.n_slots, bool)
    active[slot] = True
    out = []
    for t in range(plen, len(tokens)):
        tok = np.zeros(eng.n_slots, np.int32)
        pos = np.zeros(eng.n_slots, np.int32)
        tok[slot], pos[slot] = tokens[t], t
        cache = slot_cache(
            pc, eng.cache.table_rows(), jnp.asarray(pos), jnp.asarray(active),
            block_size=eng.block_size,
        )
        logits, eng._pool, _ = paged_forward(
            eng._params, jnp.asarray(tok)[:, None], eng._pool, cache, pc,
            eng._lm_head, row=0,
        )
        out.append(np.asarray(logits[slot]))
    return np.stack(out), slot


# ----------------------------------------------- the configuration's fields


def test_latent_attention_comes_in_two_blocks():
    """Under a layer pattern it is the sequential block, one sublayer a
    layer; without one - LongCat-Flash-Omni's file as it is, which hands
    `ModelConfig` only its ``architecture_keys`` - it goes on meaning the
    double layer."""
    pc = program_cfg(reference_cfg())
    assert pc.layer_kinds == "Aaa" and pc.hybrid_block and not pc.double_layer
    assert (pc.attn_sublayers, pc.attn_layers, pc.latent_width) == (1, 3, 24)
    assert pc.layer_ffn_is_dense(0) and not pc.layer_ffn_is_dense(1)
    assert pc.dropless_block and cache_kind(pc) is LatentRows
    longcat = json.loads((CONFIGS / "LongCat-Flash-Omni.json").read_text())
    theirs = ModelConfig(**{k: longcat[k] for k in longcat["architecture_keys"]})
    assert theirs.double_layer and theirs.attn_sublayers == 2 and not theirs.hybrid_block
    assert theirs.norm_eps == 1e-5 and theirs.yarn_factor == 1.0
    assert mla.softmax_scale(theirs) == 192 ** -0.5


def test_yarn_at_the_published_numbers():
    """``low`` 10, ``high`` 23; pairs 0-10 as published, pairs 23-31 divided
    by 40, a line between; the tables' magnitude 1.0; the scale 0.135234."""
    s = PUBLISHED_YARN
    assert yarn_correction_range(64, 10000, 4096, 32, 1) == (10, 23)
    got = yarn_inv_freq(64, 10000, 40, 4096, 32, 1)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert got.shape == (32,) and got[0] == 1.0
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-15)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-15)
    np.testing.assert_allclose(got[31], 10000.0 ** (-62 / 64) / 40, rtol=1e-15)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        got[11:23], plain[11:23] * (1 - ramp) + plain[11:23] / 40 * ramp, rtol=1e-12
    )
    assert (np.diff(got) < 0).all()
    published = {**reference_cfg(), "qk_rope_head_dim": 64, "rope_theta": 10000,
                 "rope_scaling": s, "q_head_dim": 192}
    assert ref.yarn_range(published) == (10, 23)
    np.testing.assert_allclose(ref.yarn_frequencies(published), got, rtol=1e-12)
    m = yarn_mscale(40, 1)
    assert abs(m - 1.36889) < 1e-5 and yarn_mscale(40, 0) == 1.0
    assert ref.rope_magnitude(published) == 1.0
    pc = program_cfg(
        reference_cfg(), qk_nope_head_dim=128, qk_rope_head_dim=64, **yarn_fields(s)
    )
    assert abs(mla.softmax_scale(pc) - 0.135234) < 1e-6
    assert abs(mla.softmax_scale(pc) / 192 ** -0.5 - 1.874) < 1e-3
    assert abs(ref.softmax_scale(published) - mla.softmax_scale(pc)) < 1e-12


def test_yarn_tables_are_the_stretched_angles():
    """`rope_tables` from frequencies given pair by pair, at a magnitude."""
    freq = yarn_inv_freq(8, 100.0, 8, 16, 4, 0.25)
    np.testing.assert_allclose(freq, ref.yarn_frequencies(reference_cfg()), rtol=1e-12)
    assert yarn_correction_range(8, 100.0, 16, 4, 0.25) == (0, 3)
    cos, sin = rope_tables(8, 40, inv_freq=freq, magnitude=1.5)
    angles = np.arange(40)[:, None] * freq[None, :]
    np.testing.assert_allclose(cos, 1.5 * np.cos(angles), atol=2e-6)
    np.testing.assert_allclose(sin, 1.5 * np.sin(angles), atol=2e-6)
    plain_cos, _ = rope_tables(8, 40, 100.0)
    assert float(jnp.max(jnp.abs(cos / 1.5 - plain_cos))) > 0.5


CONTRADICTIONS = {
    "a_state_space_layer": (dict(layer_pattern="Ama"), "every layer attends and feeds forward"),
    "attention_alone": (dict(layer_pattern="A*a"), "every layer attends and feeds forward"),
    "a_multiplier": (dict(attention_multiplier=0.1), "its own softmax scale"),
    "a_scaled_query_latent": (dict(mla_scale_q_lora=True), "full-rank query"),
    "a_negative_rank": (dict(q_lora_rank=-1), "positive or 0"),
    "yarn_parts_alone": (dict(yarn_factor=1.0), "which is 1"),
    "a_shrinking_factor": (dict(yarn_factor=0.5), "a factor >= 1"),
    "no_original_length": (dict(yarn_original_context=0), "yarn_original_context"),
    "betas_in_disorder": (dict(yarn_beta_slow=8.0), "yarn_beta_slow < yarn_beta_fast"),
    "no_epsilon": (dict(norm_eps=0.0), "norm_eps"),
    "kv_heads": (dict(num_kv_heads=2), "no K/V heads"),
}


@pytest.mark.parametrize("case", CONTRADICTIONS)
def test_config_refuses(case):
    more, message = CONTRADICTIONS[case]
    with pytest.raises(ValueError, match=message):
        program_cfg(reference_cfg(), **more)


def test_yarn_outside_latent_attention_is_refused():
    with pytest.raises(ValueError, match="stretches latent attention's positions"):
        ModelConfig(
            vocab_size=64, context_length=64, d_model=32, num_layers=2, num_heads=4,
            d_ff=48, **yarn_fields(SMALL_YARN),
        )


def test_init_params_has_the_reference_tree_and_a_full_rank_query():
    c = reference_cfg()
    ours = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), program_cfg(c)))
    theirs = jax.eval_shape(lambda: ref.init_weights(0, c))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(ours) == shapes(theirs)
    for layer in ours["layers"]:
        assert sorted(layer["attn"]) == ["kv_a", "kv_b", "kv_norm", "output_proj", "q_proj"]
        assert layer["attn"]["q_proj"].shape == (4 * 16, 32)
    assert "router" not in ours["layers"][0]["ffn"]
    assert ours["layers"][0]["ffn"]["w1"].shape == (48, 32)
    assert all(layer["ffn"]["shared"]["w1"].shape == (1, 16, 32) for layer in ours["layers"][1:])
    # The bottleneck's tree is what it was.
    narrow = jax.eval_shape(lambda: mla.init_mla_params(
        jax.random.PRNGKey(0), program_cfg(c, q_lora_rank=12)
    ))
    assert narrow["q_a"].shape == (12, 32) and narrow["q_norm"].shape == (12,)
    assert "q_proj" not in narrow


# ------------------------------------------------- against the reference


SHARES = {"held_all": (16, 0), "held_share": (4, 4)}


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_reference(share):
    """Sequences of 40 against an original length of 16: positions 16-39 lie
    where only the stretched frequencies place them."""
    c = reference_cfg(*SHARES[share])
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 40))
    ours = forward(w, jnp.asarray(tokens), program_cfg(c))
    theirs = ref.forward_logits(w, tokens, c)
    assert float(jnp.max(jnp.abs(theirs))) > 0.1
    assert float(jnp.max(jnp.abs(ours - theirs))) < 2e-6


def _small_latents(w):
    """The tree with ``kv_a`` a hundredth as large: the latent's mean square
    falls to ~1e-6, where the norm's epsilon is a good part of the root."""
    layers = [
        {**layer, "attn": {**layer["attn"], "kv_a": layer["attn"]["kv_a"] * 0.01}}
        for layer in w["layers"]
    ]
    return {**w, "layers": layers}


def _without(w, name):
    """The tree without leaf ``name`` of every expert layer's ``ffn``."""
    layers = [
        {**layer, "ffn": {k: v for k, v in layer["ffn"].items() if k != name}}
        for layer in w["layers"]
    ]
    return {**w, "layers": layers}


def mechanism_left_out(name, pc, w, monkeypatch):
    """``(program config, weights)`` of a program without one mechanism."""
    if name == "positions not stretched":
        plain = dict(yarn_factor=1.0, yarn_original_context=0, yarn_beta_fast=32.0,
                     yarn_beta_slow=1.0, yarn_mscale=1.0, yarn_mscale_all_dim=0.0)
        monkeypatch.setattr(mla, "softmax_scale", lambda config: ref.softmax_scale(
            reference_cfg()))
        return dataclasses.replace(pc, **plain), w
    if name == "every pair divided by the factor":
        monkeypatch.setattr(mla, "yarn_inv_freq", lambda d, theta, factor, *a: (
            theta ** (-2.0 * np.arange(d // 2) / d) / factor))
        return pc, w
    if name == "the scale without its 1.874":
        monkeypatch.setattr(mla, "softmax_scale", lambda config: config.d_head ** -0.5)
        return pc, w
    if name == "the latent not normalised":
        monkeypatch.setattr(mla, "_scaled_norm", lambda x, weight, scale, eps: x)
        return pc, w
    if name == "the latent's norm at 1e-5":
        norm = mla._scaled_norm
        monkeypatch.setattr(mla, "_scaled_norm", lambda x, weight, scale, eps: norm(
            x, weight, scale, 1e-5))
        return pc, w
    if name == "every norm at 1e-5":
        return dataclasses.replace(pc, norm_eps=1e-5), w
    if name == "layer 0 routed like the others":
        layers = list(w["layers"])
        layers[0] = {**layers[0], "ffn": layers[1]["ffn"]}
        return pc, {**w, "layers": layers}
    if name == "no selection bias":
        return dataclasses.replace(pc, router_bias=False), _without(w, "router_bias")
    if name == "the bias in the gates":
        def route(tokens, router, config, bias=None):
            scores = jax.nn.sigmoid(tokens.astype(jnp.float32) @ router.T) + bias
            top_s, top_i = jax.lax.top_k(scores, config.router_top_k)
            gates = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
            return top_i, gates * config.routed_scaling_factor

        monkeypatch.setattr(moe, "route", route)
        return pc, w
    if name == "gates not scaled by 2.5":
        return dataclasses.replace(pc, routed_scaling_factor=1.0), w
    if name == "no shared expert":
        return dataclasses.replace(pc, n_shared_experts=0), _without(w, "shared")
    raise KeyError(name)


#: name: whether the weights' ``kv_a`` is scaled down first (an epsilon
#: shows only beside a mean square of its own size).
MECHANISMS = {
    "positions not stretched": False, "every pair divided by the factor": False,
    "the scale without its 1.874": False, "the latent not normalised": False,
    "the latent's norm at 1e-5": True, "every norm at 1e-5": False,
    "layer 0 routed like the others": False, "no selection bias": False,
    "the bias in the gates": False, "gates not scaled by 2.5": False,
    "no shared expert": False,
}


@pytest.mark.parametrize("name", MECHANISMS)
def test_forward_without_a_mechanism_leaves_the_reference(name, monkeypatch):
    """Each mechanism moves the logits by fifty times the agreement of the
    program that has it (2e-6) or more."""
    c = reference_cfg()
    w = ref.weights_from_seed(3, c)
    if MECHANISMS[name]:
        w = _small_latents(w)
    tokens = np.random.default_rng(0).integers(0, 64, (1, 40))
    theirs = ref.forward_logits(w, tokens, c)
    whole = forward(w, jnp.asarray(tokens), program_cfg(c))
    assert float(jnp.max(jnp.abs(whole - theirs))) < 2e-6
    pc, tree = mechanism_left_out(name, program_cfg(c), w, monkeypatch)
    ours = forward(tree, jnp.asarray(tokens), pc)
    assert float(jnp.max(jnp.abs(ours - theirs))) > 1e-4


def test_layer_zero_is_the_dense_ffn_and_routes_nothing():
    """Layer 0 runs under ``block/ffn/dense`` and appends nothing to the
    tally: the counts are the two expert layers'."""
    c = reference_cfg()
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    tables = jnp.asarray(np.arange(1, 17, dtype=np.int32).reshape(1, 16))
    pool = LatentRows.init_pool(pc, 17, 4, jnp.float32)
    cache = slot_cache(pc, tables, jnp.asarray([0]), block_size=4)
    _, _, counts = paged_forward(w, jnp.asarray([[5]]), pool, cache, pc, row=0)
    assert len(cache.tally) == 2 and int(counts[0]) == 2
    text = jax.jit(
        lambda w, pool: paged_forward(w, jnp.asarray([[5]]), pool, cache, pc, row=0)[0]
    ).lower(w, pool).as_text(debug_info=True)
    assert "block/ffn/dense" in text and "block/moe/shared" in text
    assert "mla_q" in text and "mla_kv" in text


def test_dense_cache_matches_reference():
    """Prefill (many rows, expanded or the loop) then `decode_step` token by
    token (one row, absorbed) over the dense latent cache."""
    c = reference_cfg()
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(1).integers(0, 64, (2, 30))
    full = ref.forward_logits(w, tokens, c)
    logits, cache = prefill(w, jnp.asarray(tokens[:, :19]), pc, init_kv_cache(pc, 2))
    worst = float(jnp.max(jnp.abs(logits - full[:, 18])))
    for t in range(19, 30):
        logits, cache = decode_step(w, jnp.asarray(tokens[:, t]), jnp.asarray(t), cache, pc)
        worst = max(worst, float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert worst < 2e-6


# ------------------------------------------------- the paged engine's paths


@pytest.mark.parametrize("tick_path", ["xla", "mla_paged"])
@pytest.mark.parametrize("plen", [3, 13, 22])
def test_paged_chunks_and_ticks_match_reference(plen, tick_path, monkeypatch):
    """A prompt in chunks of 8 (its tail in the bucket of 4 or 8), then ticks
    to position 39 (gathered rows under XLA, or the kernel in interpret
    mode), all absorbed, against the reference's expanded form: chunks and
    ticks both pass the original length of 16."""
    monkeypatch.setattr(mla_attention, "mla_paged_path", lambda *a, **k: tick_path)
    c = reference_cfg()
    eng = small_engine(c)
    assert cache_kind(eng.config) is LatentRows
    assert isinstance(eng.cache, host_cache.HostLatentRows)
    assert eng.tick_attention_path == tick_path
    tokens = np.random.default_rng(2).integers(0, 64, 40)
    full = ref.forward_logits(ref.weights_from_seed(3, c), tokens[None], c)[0]
    got, _ = served_logits(eng, tokens, plen)
    assert float(np.max(np.abs(got - np.asarray(full[plen:])))) < 1e-5
    # One array a layer: rows of 24 values, padded to a whole lane tile.
    assert len(eng._pool) == 3 and eng._pool[0]["c"].shape[1:] == (4, 128)


#: The mechanisms the engine reaches by a path of its own (positions and
#: the scale through the cache's rows and the tick's absorbed form, the
#: latent's norm into the pool, the tree's say on layer 0, the shared
#: expert under padded rows); the others are `dropless_moe`'s either way.
PAGED_MECHANISMS = [
    "positions not stretched", "the scale without its 1.874",
    "the latent's norm at 1e-5", "layer 0 routed like the others",
    "no shared expert",
]


@pytest.mark.parametrize("name", PAGED_MECHANISMS)
def test_paged_path_without_a_mechanism_leaves_the_reference(name, monkeypatch):
    c = reference_cfg()
    w = ref.weights_from_seed(3, c)
    if MECHANISMS[name]:
        w = _small_latents(w)
    tokens = np.random.default_rng(2).integers(0, 64, 40)
    full = np.asarray(ref.forward_logits(w, tokens[None], c)[0])
    pc, tree = mechanism_left_out(name, program_cfg(c), w, monkeypatch)
    got, _ = served_logits(small_engine(c, tree, pc), tokens, 21)
    assert float(np.max(np.abs(got - full[21:]))) > 1e-4


def test_resume_after_a_radix_shared_prefix_equals_the_request_served_cold():
    """The radix prefix cache over one sublayer a layer: the second request
    shares the first's two whole prompt blocks and resumes at position 8."""
    c = reference_cfg()
    eng = small_engine(c, prefix_cache=True)
    w = ref.weights_from_seed(3, c)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 64, 9)
    first = np.concatenate([shared, rng.integers(0, 64, 21)])
    second = np.concatenate([shared, rng.integers(0, 64, 25)])
    for tokens, plen, hit in ((first, 12, 0), (second, 14, 8)):
        got, slot = served_logits(eng, tokens, plen)
        full = np.asarray(ref.forward_logits(w, tokens[None], c)[0])
        assert eng.slot_shared_len(slot) == hit
        assert float(np.max(np.abs(got - full[plen:]))) < 1e-5
    assert eng.gauges()["prefix_cache_hits"] == 8


def test_engine_serves_greedy_tokens_and_counts_what_it_did():
    """Three slots at ragged depths through admit/tick, the way the worker
    drives the engine: the reference puts every served token first, the
    counters are a count by hand, and every block comes back."""
    c = reference_cfg()
    eng = small_engine(c, prefix_cache=False)
    w = ref.weights_from_seed(3, c)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n) for n in (19, 5, 9)]
    seqs = [list(p) for p in prompts]
    for seq, prompt in zip(seqs, prompts):
        seq.append(eng.admit(prompt, max_new_tokens=12, temperature=0.0).token)
    while eng.active_count:
        for event in eng.tick():
            seqs[event.slot].append(event.token)
    for prompt, seq in zip(prompts, seqs):
        assert len(seq) == len(prompt) + 12
        full = ref.forward_logits(w, np.asarray(seq)[None], c)[0]
        for i in range(len(prompt) - 1, len(seq) - 1):
            assert float(full[i].max() - full[i, seq[i + 1]]) < 1e-5
    gauges = eng.gauges()
    assert gauges["kv_blocks_free"] == gauges["kv_blocks_total"]
    routed = gauges["moe_tokens_routed"]
    assert routed == 2 * (19 + 5 + 9 + 3 * 11)  # the two layers that route
    assert 0 < gauges["moe_rows_local"] < 4 * routed
    assert 0 < gauges["moe_expert_groups"] <= gauges["moe_rows_local"]
    # A pair is one (query, key) of one sublayer: three sublayers, one a layer.
    ticks = sum(sum(range(n + 1, n + 12)) for n in (19, 5, 9))
    assert gauges["attn_pairs"] == gauges["attn_kv_positions"] == 3 * ticks
    chunks = sum(n * (n + 1) // 2 for n in (19, 5, 9))
    assert gauges["chunk_attn_pairs"] == 3 * chunks
    assert gauges["chunk_attn_kernel_pairs"] == 0  # no bucket of 256 rows here
    # 24 values a position a layer as the config counts them, 128 as the
    # device holds them.
    assert gauges["kv_bytes_per_token"] == 3 * 24 * 4
    assert gauges["kv_pool_bytes"] == 3 * eng.allocator.num_blocks * 4 * 128 * 4


def test_no_program_compiles_after_the_warm_up():
    eng = small_engine(reference_cfg(), prefix_cache=False)
    rng = np.random.default_rng(0)
    for n in (3, 7):
        slot = eng.begin(rng.integers(0, 64, n), max_new_tokens=6, temperature=0.0)
        while eng.prefill_step(slot) is None:
            pass
        eng.tick(), eng.tick(), eng.release(slot)
    warm = eng.compiled_programs()
    assert warm == len(eng.buckets) + 1
    slot = eng.begin(rng.integers(0, 64, 29), max_new_tokens=6, temperature=0.0)
    while eng.prefill_step(slot) is None:
        pass
    eng.tick(), eng.tick()
    assert eng.compiled_programs() == warm


# ------------------------------------------------ the share and the whole


def test_all_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts of all 4 shares of 4 experts (the
    small size's offsets 0, 4, 8, 12 for the file's 0, 32, 64, 96), with the
    shared expert - which every chip computes alike - counted once, equal
    the uncut reference's expert layer."""
    uncut = reference_cfg(16, 0)
    w = ref.weights_from_seed(7, uncut)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(1), (11, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(h, w, uncut, None)
        shared = ref.shared_experts(h, w, None)
    total, rows = shared, 0
    for offset in range(0, 16, 4):
        share = {**w, **{m: w[m][offset:offset + 4] for m in ("w1", "w2", "w3")}}
        cfg = program_cfg(reference_cfg(4, offset))
        out, counts = dropless_moe(h, share, cfg)
        with jax.default_matmul_precision("highest"):
            theirs = ref.ffn(h, share, reference_cfg(4, offset), None)
        assert float(jnp.max(jnp.abs(out - theirs))) < 1e-7
        total, rows = total + (out - shared), rows + int(counts[1])
        assert int(counts[0]) == 11
    assert rows == 11 * 4  # every assignment lands on exactly one share
    size = float(jnp.max(jnp.abs(want)))
    assert size > 1e-3 and float(jnp.max(jnp.abs(total - want))) < 1e-4 * size
    assert float(jnp.max(jnp.abs(shared))) > 0.05 * size  # the shared part counts


def test_the_selection_bias_chooses_and_does_not_weigh():
    """Seeded so that the bias changes the chosen experts of a good share of
    tokens; the gates are the scores of the chosen, normalised, times 2.5."""
    c = reference_cfg(16, 0)
    w = ref.weights_from_seed(7, c)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
    scores, biased = ref.selection_scores(h, w)
    by_score = np.sort(np.asarray(jax.lax.top_k(scores, 4)[1]), axis=-1)
    by_bias = np.sort(np.asarray(jax.lax.top_k(biased, 4)[1]), axis=-1)
    assert (by_score != by_bias).any(axis=-1).mean() > 0.2
    top_i, gates = moe.route(h, w["router"], program_cfg(c), w["router_bias"])
    assert (np.sort(np.asarray(top_i), axis=-1) == by_bias).all()
    picked = np.take_along_axis(np.asarray(scores), np.asarray(top_i), axis=-1)
    np.testing.assert_allclose(
        gates, 2.5 * picked / picked.sum(axis=-1, keepdims=True), rtol=1e-6
    )


# ------------------------------------------ the kernels in interpret mode


def test_the_chunk_kernel_attends_expanded_under_the_configs_scale():
    """`mla_chunk_attention` (interpret mode) at whole lane tiles - the
    expanded chunk - against the reference's own expansion, 256 query rows
    after 300 cached positions, under YaRN's softmax scale."""
    cfg = {**reference_cfg(), "num_attention_heads": 2, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 128, "q_head_dim": 192}
    scale = ref.softmax_scale(cfg)
    rng = np.random.default_rng(11)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    queries, keys, start = 256, 1024, 300
    q = draw(queries, 2, 192)
    c, k_r, kv_b = draw(keys, 128), draw(keys, 64), draw(2, 256, 128) * 0.1
    positions = start + jnp.arange(queries)
    got = mla_attention.mla_chunk_attention(
        jnp.swapaxes(q[..., :128], 0, 1), jnp.swapaxes(q[..., 128:], 0, 1),
        jnp.concatenate([c, k_r], axis=-1), kv_b, positions, start + queries,
        scale=scale, interpret=True,
    )
    with jax.default_matmul_precision("highest"):
        k, v = ref._expand(c, k_r, kv_b, cfg, None)
        scores = jnp.einsum("qgd,kgd->gqk", q, k) * scale
        visible = jnp.arange(keys)[None, :] <= positions[:, None]
        want = jnp.einsum(
            "gqk,kgd->gqd", jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1), v
        )
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    other = mla_attention.mla_chunk_attention(
        jnp.swapaxes(q[..., :128], 0, 1), jnp.swapaxes(q[..., 128:], 0, 1),
        jnp.concatenate([c, k_r], axis=-1), kv_b, positions, start + queries,
        scale=192 ** -0.5, interpret=True,
    )
    assert float(jnp.max(jnp.abs(other - want))) > 1e-3


#: name: (the chunk's first position, rows of the bucket that are padding).
#: The chain is 4,096 rows long and every case's ``n_keys`` short of it.
WALK_CASES = {
    "from_position_0": (0, 0),
    "inside_a_block": (300, 0),
    "on_a_blocks_edge": (1024, 0),
    "padding_rows_past_n_keys": (812, 200),
}


@pytest.mark.parametrize("case", WALK_CASES)
@pytest.mark.parametrize("queries", [512, 1024, 2048])
def test_the_chunk_kernels_walk_is_the_pairs_themselves(queries, case):
    """`mla_attention.chunk_walk`, tile by tile of every bucket of the cell,
    against the (query, key) pairs: a block under ``clear`` is seen whole by
    every row, a block from ``clear`` to ``end`` is crossed by an edge, no
    row of the chunk sees a key from ``end`` on - so the blocks as the kernel
    folds them, the mask on the crossed ones alone, are the pairs, and their
    count is the host's `chunk_attn_kernel_pairs`."""
    start, padding = WALK_CASES[case]
    chain = 4096
    tile_rows, block = mla_attention.chunk_tiles(queries, chain)
    assert (tile_rows, block) == (512, 512)
    chunk_len = queries - padding
    positions = start + np.arange(queries)
    n_keys = start + chunk_len
    sees = np.arange(chain)[None, :] <= positions[:, None]
    block_of = np.arange(chain) // block
    pairs = 0
    for t in range(queries // tile_rows):
        rows = slice(t * tile_rows, (t + 1) * tile_rows)
        lo, hi = int(positions[rows].min()), int(positions[rows].max())
        clear, end = mla_attention.chunk_walk(lo, hi, n_keys, block)
        assert 0 <= clear <= end <= -(-n_keys // block)
        traced = mla_attention.chunk_walk(
            jnp.int32(lo), jnp.int32(hi), jnp.int32(n_keys), block, jnp.minimum
        )
        assert (int(traced[0]), int(traced[1])) == (clear, end)
        tile, real = sees[rows], positions[rows] < n_keys
        assert tile[:, block_of < clear].all()
        for b in range(clear, end):
            crossed = tile[:, block_of == b]
            assert crossed.any() and not crossed.all()
        assert not tile[real][:, block_of >= end].any()
        folded = (block_of < clear) | ((block_of < end) & tile)
        assert np.array_equal(folded[real], tile[real])
        pairs += int(folded[real].sum())
    assert pairs == chunk_len * start + chunk_len * (chunk_len + 1) // 2


@pytest.mark.parametrize("order", ["as_the_engine_pads", "rows_out_of_order"])
def test_the_chunk_kernel_at_2048_ragged_rows_from_inside_a_block(order):
    """`mla_chunk_attention` (interpret mode) against the absorbed loop at
    the cell's largest bucket: 2,048 rows of which 1,847 are the chunk's,
    from position 812 (inside a key block) over a chain of 4,096 rows, so
    ``n_keys`` is short of the chain and the padding rows' positions run
    past it; and with the rows in another order, so that every tile's walk
    reaches from the first block to the last."""
    heads, nope, rope, v, rank = 2, 128, 64, 128, 128
    queries, chunk_len, start, chain = 2048, 1847, 812, 4096
    rng = np.random.default_rng(50)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q_nope, q_rope = draw(heads, queries, nope), draw(heads, queries, rope)
    rows, kv_b = draw(chain, rank + rope), draw(heads, nope + v, rank) * 0.1
    positions = start + np.arange(queries)
    if order == "rows_out_of_order":
        positions = rng.permutation(positions)
    real = positions < start + chunk_len
    args = (q_nope, q_rope, rows, kv_b, jnp.asarray(positions), start + chunk_len)
    got = mla_attention.mla_chunk_attention(*args, scale=0.07, interpret=True)
    loop = mla_attention.xla_mla_chunk_attention(*args, scale=0.07)
    assert real.sum() == chunk_len and bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - loop)[:, real])) < 2e-5


# ---------------------------------------------------------------- refusals


def test_what_the_kind_cannot_serve_is_refused():
    c = reference_cfg()
    with pytest.raises(ValueError, match="latent"):
        small_engine(c, kv_dtype="int8")
    eng = small_engine(c)
    for operation in ("extend_blocks", "export_slot", "import_slot", "speculate"):
        with pytest.raises(NotImplementedError, match="a latent pool"):
            eng.cache.refuse(operation)
    with pytest.raises(ValueError, match="scan_layers"):
        program_cfg(c, scan_layers=True)
    from bpe_transformer_tpu.serving.server import ServingEngine
    from bpe_transformer_tpu.training.train_step import make_loss_fn

    with pytest.raises(ValueError, match="training is not supported"):
        make_loss_fn(program_cfg(c))
    with pytest.raises(ValueError, match="paged=True"):
        ServingEngine(ref.weights_from_seed(3, c), program_cfg(c), paged=False)
