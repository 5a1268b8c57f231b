"""MiMo-V2.5's block at a small size on the CPU: the plain forward and the
paged two-group engine (`GroupedRows`: rows of keys and values by group)
against ``chipbench/reference_mimov2.py`` on seeded float32 weights; each
mechanism the configuration brings - the sink, the partial rotation at two
bases, the value scale, the leading dense layer, the groups' shapes - with a
control that leaves it out; the share test that ties a chip's experts to the
whole layer; the kernels in interpret mode against their XLA stand-in; the
window group that is no reservation; and every refusal."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bpe_transformer_tpu.kernels.pallas import sink_attention  # noqa: E402
from bpe_transformer_tpu.models.config import ModelConfig  # noqa: E402
from bpe_transformer_tpu.models.decode import (  # noqa: E402
    GroupedPages,
    GroupedRows,
    RecurrentRows,
    cache_kind,
    paged_forward,
    slot_cache,
)
from bpe_transformer_tpu.models.moe import dropless_moe  # noqa: E402
from bpe_transformer_tpu.models.transformer import forward, init_params  # noqa: E402
from bpe_transformer_tpu.serving.kvpool import host_cache  # noqa: E402
from bpe_transformer_tpu.serving.kvpool.blocks import (  # noqa: E402
    BlockAllocator,
    GrowingWindowChain,
    NoFreeBlocksError,
)
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine  # noqa: E402
from chipbench import reference_mimov2 as ref  # noqa: E402

WINDOW = 8
PUBLISHED_WINDOWED = [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0]
PUBLISHED_EXPERTS = [0] + [1] * 11


def reference_cfg(held=4, offset=0, layers=7) -> dict:
    """The published pattern's first layers (F W W W W F W, layer 0 dense),
    8 query heads of 24 over 2 (full) and 4 (window) K/V heads, values of
    16, 8 of a head's 24 values rotated, 16 experts top-4 of which 4 held."""
    return {
        "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 16,
        "head_dim": 24, "v_head_dim": 16, "num_attention_heads": 8,
        "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
        "num_hidden_layers": layers, "n_routed_experts": held, "n_experts": 16,
        "expert_offset": offset, "num_experts_per_tok": 4,
        "hybrid_layer_pattern": PUBLISHED_WINDOWED, "moe_layer_freq": PUBLISHED_EXPERTS,
        "sliding_window": WINDOW, "rope_theta": 1e7, "swa_rope_theta": 1e4,
        "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
        "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
        "layernorm_epsilon": 1e-5, "routed_scaling_factor": None,
        "vocab_size": 64, "context_length": 64,
    }


def pattern_of(c: dict) -> str:
    letters = {(0, 0): "A", (0, 1): "a", (1, 1): "w"}
    kinds = zip(c["hybrid_layer_pattern"], c["moe_layer_freq"])
    return "".join(letters[kind] for kind in kinds)[: c["num_hidden_layers"]]


def program_cfg(c: dict, **more) -> ModelConfig:
    args = dict(
        vocab_size=c["vocab_size"], context_length=c["context_length"],
        d_model=c["hidden_size"], num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        window_kv_heads=c["swa_num_key_value_heads"], d_ff=c["intermediate_size"],
        head_dim=c["head_dim"], v_head_dim=c["v_head_dim"],
        qk_rope_head_dim=ref.rotary_dim(c), rope_theta=c["rope_theta"],
        window_rope_theta=c["swa_rope_theta"], sliding_window=c["sliding_window"],
        layer_pattern=pattern_of(c), sink_on_window_layers=True,
        attention_value_scale=c["attention_value_scale"], ffn_type="moe",
        moe_router="sigmoid", n_experts=c["n_experts"],
        router_top_k=c["num_experts_per_tok"], expert_d_ff=c["moe_intermediate_size"],
        router_bias=True, experts_held=c["n_routed_experts"],
        expert_offset=c["expert_offset"],
    )
    args.update(more)
    return ModelConfig(**args)


def small_engine(c, weights=None, config=None, **more) -> PagedEngine:
    args = dict(
        slots=3, block_size=2, prefill_chunk=4, prefill_buckets=(2, 4),
        prefix_cache=False,
    )
    args.update(more)
    weights = ref.weights_from_seed(3, c) if weights is None else weights
    return PagedEngine(weights, config or program_cfg(c), **args)


def served_logits(eng, tokens, plen):
    """Prefill ``tokens[:plen]`` in the engine's chunks, then teacher-forced
    ticks to the end: float32 logits of positions ``plen - 1 ..``, the first
    from the final chunk, the rest through `paged_forward` as a tick runs it."""
    pc = eng.config
    slot = eng.begin(tokens[:plen], max_new_tokens=len(tokens) - plen + 1, temperature=0.0)
    while eng.prefill_step(slot) is None:
        pass
    active = np.zeros(eng.n_slots, bool)
    active[slot] = True
    out = []
    for t in range(plen, len(tokens)):
        tok = np.zeros(eng.n_slots, np.int32)
        pos = np.zeros(eng.n_slots, np.int32)
        tok[slot], pos[slot] = tokens[t], t
        eng.cache.before_tick(np.flatnonzero(active), pos, active)
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


def test_layer_kinds_spell_the_published_lists():
    pc = program_cfg(reference_cfg())
    assert pc.layer_kinds == "Awwwwaw" and pc.hybrid_block and pc.has_window_layers
    assert [pc.layer_window(i) for i in range(7)] == [None, 8, 8, 8, 8, None, 8]
    assert [pc.layer_kv_heads(i) for i in range(7)] == [2, 4, 4, 4, 4, 2, 4]
    assert [pc.layer_rope_theta(i) for i in range(7)] == [1e7] + [1e4] * 4 + [1e7, 1e4]
    assert [pc.layer_sink(i) for i in range(7)] == [False] + [True] * 4 + [False, True]
    assert [pc.layer_ffn_is_dense(i) for i in range(7)] == [True] + [False] * 6
    assert (pc.d_head, pc.value_dim, pc.rope_dim) == (24, 16, 8)
    assert (pc.attn_layers, pc.ssm_layers) == (7, 0)
    assert cache_kind(pc) is GroupedRows and pc.split_attention


def test_a_period_of_window_layers_is_spelt_through_layer_kinds():
    """`sliding_window_pattern` is a way to write the letters, as granite's
    period is: three window layers of four, the kind that was."""
    periodic = ModelConfig(
        vocab_size=64, context_length=32, d_model=32, num_layers=8, num_heads=4,
        num_kv_heads=2, d_ff=16, head_dim=16, sliding_window=6,
        sliding_window_pattern=4, rope_on_full_layers=False,
        norm_type="layernorm", parallel_block=True, tie_embeddings=True,
    )
    assert periodic.layer_kinds == "wwwawwwa" and not periodic.hybrid_block
    assert [periodic.layer_window(i) for i in (0, 3, 4, 7)] == [6, None, 6, None]
    assert cache_kind(periodic) is GroupedPages and not periodic.split_attention
    plain = dataclasses.replace(periodic, sliding_window_pattern=1)
    assert plain.layer_kinds == "a" * 8 and not plain.has_window_layers


UNIFORM = {
    "dense": dict(layer_pattern="awwa"),
    "leading_dense_before_experts": dict(
        layer_pattern="Awwa", ffn_type="moe", n_experts=4, router_top_k=2, expert_d_ff=16),
}


@pytest.mark.parametrize("case", UNIFORM)
def test_the_cache_kind_follows_the_shapes_not_the_spelling(case):
    """Window layers named by a pattern's letters whose two kinds differ in
    their mask alone keep the pages a period of them keeps (`GroupedPages`,
    a reservation a slot): rows by group are for shapes that differ."""
    pc = ModelConfig(
        vocab_size=64, context_length=64, d_model=32, num_layers=4, num_heads=4,
        num_kv_heads=2, d_ff=48, head_dim=16, sliding_window=8, **UNIFORM[case],
    )
    assert pc.hybrid_block and pc.has_window_layers and not pc.split_attention
    assert cache_kind(pc) is GroupedPages
    assert cache_kind(dataclasses.replace(pc, window_kv_heads=4)) is GroupedRows
    w = init_params(jax.random.PRNGKey(0), pc)
    eng = small_engine(None, weights=w, config=pc)
    assert type(eng.cache) is host_cache.HostGroupedPages
    prompt = np.random.default_rng(5).integers(0, 64, 21)
    seq = [*prompt, eng.admit(prompt, max_new_tokens=14, temperature=0.0).token]
    while eng.active_count:
        seq.extend(event.token for event in eng.tick())
    full = forward(w, jnp.asarray(seq)[None], pc)[0]
    assert len(seq) == 35
    for i in range(len(prompt) - 1, len(seq) - 1):
        assert float(full[i].max() - full[i, seq[i + 1]]) < 1e-5


CONTRADICTIONS = {
    "window letters without a window": dict(sliding_window=None),
    "a period beside the letters": dict(sliding_window_pattern=4),
    "a layer of attention alone": dict(layer_pattern="Awwww*w"),
    "a window layer with a dense part (no letter)": dict(layer_pattern="AWwwwaw"),
    "state-space layers beside a window group": dict(
        layer_pattern="Awwwwmw", ssm_heads=2, ssm_head_dim=8, ssm_state=4),
    "state-space widths without such a layer": dict(ssm_heads=2),
    "full layers that do not rotate": dict(rope_on_full_layers=False),
    "an odd rotated width": dict(qk_rope_head_dim=7),
    "a rotated width past the head": dict(qk_rope_head_dim=26),
    "window K/V heads that do not divide": dict(window_kv_heads=3),
    "a score multiplier": dict(attention_multiplier=0.5),
    "a dense layer among dense layers": dict(ffn_type=None, n_experts=0,
        router_bias=False, experts_held=None, expert_d_ff=None, moe_router="softmax"),
}


@pytest.mark.parametrize("case", CONTRADICTIONS)
def test_config_refuses(case):
    with pytest.raises(ValueError):
        program_cfg(reference_cfg(), **CONTRADICTIONS[case])


@pytest.mark.parametrize("field", [
    dict(window_kv_heads=2), dict(window_rope_theta=1e4),
    dict(sink_on_window_layers=True),
    dict(attention_value_scale=0.5), dict(v_head_dim=8), dict(qk_rope_head_dim=4),
], ids=lambda f: next(iter(f)))
def test_the_new_fields_belong_to_a_pattern_with_window_layers(field):
    base = dict(vocab_size=64, context_length=32, d_model=32, num_layers=2,
                num_heads=4, d_ff=16)
    with pytest.raises(ValueError, match="layer_pattern|latent"):
        ModelConfig(**base, **field)
    with pytest.raises(ValueError, match="layer_pattern|latent"):  # no window layer
        ModelConfig(**base, layer_pattern="aa", **field)


def test_init_params_has_the_reference_tree():
    c = reference_cfg()
    ours = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), program_cfg(c)))
    theirs = jax.eval_shape(lambda: ref.init_weights(0, c))
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(ours) == shapes(theirs)
    layers = ours["layers"]
    assert "router" not in layers[0]["ffn"] and layers[0]["ffn"]["w1"].shape == (48, 32)
    assert all("router" in layer["ffn"] for layer in layers[1:])
    assert ["sink" in layer["attn"] for layer in layers] == [False] + [True] * 4 + [False, True]
    assert layers[0]["attn"]["k_proj"].shape == (2 * 24, 32)
    assert layers[1]["attn"]["k_proj"].shape == (4 * 24, 32)
    assert layers[1]["attn"]["v_proj"].shape == (4 * 16, 32)
    assert layers[1]["attn"]["output_proj"].shape == (32, 8 * 16)


# ------------------------------------------------- against the reference


SHARES = {"held_all": (16, 0), "held_share": (4, 4)}


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_reference(share):
    """Window (8) five times over in a sequence of 40, both groups, layer 0
    dense."""
    c = reference_cfg(*SHARES[share])
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 40))
    ours = forward(w, jnp.asarray(tokens), program_cfg(c))
    theirs = ref.forward_logits(w, tokens, c)
    assert float(jnp.max(jnp.abs(theirs))) > 0.1
    assert float(jnp.max(jnp.abs(ours - theirs))) < 2e-6


def mechanism_left_out(name, c, w):
    """``(program config, weights)`` of a program without one mechanism."""
    pc = program_cfg(c)
    if name == "sink dropped":
        layers = [
            {**layer, "attn": {k: v for k, v in layer["attn"].items() if k != "sink"}}
            for layer in w["layers"]
        ]
        return dataclasses.replace(pc, sink_on_window_layers=False), {**w, "layers": layers}
    if name == "window layers at the full layers' theta":
        return dataclasses.replace(pc, window_rope_theta=None), w
    if name == "full layers at the window layers' theta":
        return dataclasses.replace(pc, rope_theta=1e4), w
    if name == "the whole head rotated":
        return dataclasses.replace(pc, qk_rope_head_dim=0), w
    if name == "no value scale":
        return dataclasses.replace(pc, attention_value_scale=1.0), w
    raise KeyError(name)


MECHANISMS = [
    "sink dropped", "window layers at the full layers' theta",
    "full layers at the window layers' theta", "the whole head rotated",
    "no value scale",
]


@pytest.mark.parametrize("name", MECHANISMS)
def test_forward_without_a_mechanism_leaves_the_reference(name):
    """Each mechanism moves the logits by fifty times the agreement of the
    program that has it (2e-6, above) or more: the rotations least, since
    seeded scores are small beside the softmax's range."""
    c = reference_cfg()
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (1, 40))
    theirs = ref.forward_logits(w, tokens, c)
    pc, tree = mechanism_left_out(name, c, w)
    ours = forward(tree, jnp.asarray(tokens), pc)
    assert float(jnp.max(jnp.abs(ours - theirs))) > 1e-4


def test_a_sink_with_a_value_row_is_not_the_sink():
    """The sink joins the denominator and carries no value: a key of zeros
    with score ``b_h`` and a value row of ones gives another output."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 6, 8, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 6, 4, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 6, 4, 16)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    at = jnp.arange(6)[None]
    got = sink_attention.xla_sink_attention(q, k, v, at, window=4, sink=sink)
    bare = sink_attention.xla_sink_attention(q, k, v, at, window=4)
    # By hand, head 0 at the last row: keys 2 .. 5 and the sink's column.
    s = (q[0, 5, 0] @ k[0, 2:6, 0].T) * 24 ** -0.5
    weights = jax.nn.softmax(jnp.append(s, sink[0]))
    assert float(jnp.max(jnp.abs(weights[:4] @ v[0, 2:6, 0] - got[0, 5, 0]))) < 1e-6
    with_row = weights[:4] @ v[0, 2:6, 0] + weights[4] * jnp.ones(16)
    assert float(jnp.max(jnp.abs(with_row - got[0, 5, 0]))) > 1e-2
    assert float(jnp.max(jnp.abs(bare - got))) > 1e-2
    assert float(jnp.sum(weights[:4])) < 1.0


def test_rotation_turns_the_leading_part_and_passes_the_rest():
    from bpe_transformer_tpu.models.decode import _rope_qk

    pc = program_cfg(reference_cfg())
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 2, 5, 24)), jnp.float32)
    positions = jnp.arange(3, 8)
    full, _ = _rope_qk(x, x, positions, pc, layer=0)
    window, _ = _rope_qk(x, x, positions, pc, layer=1)
    for turned, theta in ((full, 1e7), (window, 1e4)):
        assert jnp.array_equal(turned[..., 8:], x[..., 8:])
        want = ref._rope(x, theta, positions, 8)
        assert float(jnp.max(jnp.abs(turned - want))) < 1e-6
    assert float(jnp.max(jnp.abs(full[..., :8] - window[..., :8]))) > 1e-2


def test_layer_zero_is_the_dense_ffn_and_routes_nothing():
    """Layer 0's feed-forward part is the SwiGLU of 48 under scope
    ``block/ffn/dense``; the 6 other layers route."""
    c = reference_cfg()
    eng = small_engine(c)
    prompt = np.arange(1, 10)
    eng.admit(prompt, max_new_tokens=3, temperature=0.0)
    while eng.active_count:
        eng.tick()
    # The chunks' counts ride the ticks': six routing layers a token.
    routed = eng.gauges()["moe_tokens_routed"]
    assert routed >= 6 * (len(prompt) + 1) and routed % 6 == 0
    w = ref.weights_from_seed(3, c)
    text = jax.jit(lambda t: forward(w, t, program_cfg(c))).lower(
        jnp.zeros((1, 8), jnp.int32)
    ).as_text(debug_info=True)
    assert "block/ffn/dense" in text and "attn_window" in text and "attn_full" in text


# ------------------------------------------------------ the paged engine


def test_the_two_groups_pool_shapes():
    """A row is K at the key's width and, behind it, V at the value's, by
    the layer's own K/V heads: no value padded to the key's width."""
    eng = small_engine(reference_cfg())
    full, window = 2 * (24 + 16), 4 * (24 + 16)
    widths = [arr.shape[-1] for arr in eng._pool]
    assert widths == [full, window, window, window, window, full, window]
    full_blocks = eng.allocator.num_blocks
    window_blocks = eng.cache.window_allocator.num_blocks
    assert [arr.shape[0] for arr in eng._pool] == [
        full_blocks if i in (0, 5) else window_blocks for i in range(7)
    ]
    # The window group: window // block + 1 blocks a slot and one chunk's.
    assert window_blocks == 3 * (WINDOW // 2 + 1) + (WINDOW + 4) // 2 + 1
    itemsize = eng._pool[0].dtype.itemsize
    assert eng.kv_bytes_per_token == (2 * full + 5 * window) * itemsize
    assert eng.kv_pool_bytes == sum(arr.nbytes for arr in eng._pool)
    assert eng.tick_attention_path == "xla"  # the CPU's stand-in


@pytest.mark.parametrize("plen", [3, 13, 22])
def test_paged_groups_match_reference_logits_past_the_window(plen):
    """Chunks of 4 against a window of 8 and blocks of 2 (a chunk straddles
    the window's edge from the third on; a prompt of 3 ends mid-chunk), then
    teacher-forced ticks to position 45 - the window five times over:
    logits against the reference's whole forward."""
    c = reference_cfg()
    eng = small_engine(c)
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(2).integers(0, 64, 46)
    full = np.asarray(ref.forward_logits(w, tokens[None], c)[0])
    got, slot = served_logits(eng, tokens, plen)
    assert float(np.max(np.abs(got - full[plen:46]))) < 2e-6
    chain = eng.cache.chains[slot]
    assert chain.first > 0 and eng.cache.window_base[slot] == chain.first * 2
    assert len(chain.ids) <= WINDOW // 2 + 1
    assert eng.cache.window_recycled > 0


@pytest.mark.parametrize("name", MECHANISMS)
def test_paged_groups_without_a_mechanism_leave_the_reference(name):
    c = reference_cfg()
    w = ref.weights_from_seed(3, c)
    pc, tree = mechanism_left_out(name, c, w)
    eng = small_engine(c, weights=tree, config=pc)
    tokens = np.random.default_rng(2).integers(0, 64, 30)
    full = np.asarray(ref.forward_logits(w, tokens[None], c)[0])
    got, _ = served_logits(eng, tokens, 13)
    assert float(np.max(np.abs(got - full[13:30]))) > 1e-4


def force_kernels(monkeypatch):
    """The engine's tick and chunk take the Pallas kernels (interpret mode
    on the CPU) in place of the XLA stand-in."""
    monkeypatch.setattr(sink_attention, "sink_paged_path", lambda *a: "sink_paged")
    monkeypatch.setattr(sink_attention, "sink_chunk_path", lambda *a: "sink_chunk")


def test_engine_serves_greedy_tokens_through_the_kernels(monkeypatch):
    """Three slots at ragged depths through admit/tick, the way the worker
    drives the engine, the attention in the kernels; counters move and every
    block of both groups comes back."""
    force_kernels(monkeypatch)
    c = reference_cfg()
    eng = small_engine(c)
    assert eng.tick_attention_path == "sink_paged"
    w = ref.weights_from_seed(3, c)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n) for n in (21, 5, 13)]
    seqs = [list(p) for p in prompts]
    for seq, prompt in zip(seqs, prompts):
        seq.append(eng.admit(prompt, max_new_tokens=14, temperature=0.0).token)
    while eng.active_count:
        for event in eng.tick():
            seqs[event.slot].append(event.token)
    for prompt, seq in zip(prompts, seqs):
        assert len(seq) == len(prompt) + 14
        full = ref.forward_logits(w, np.asarray(seq)[None], c)[0]
        for i in range(len(prompt) - 1, len(seq) - 1):
            assert float(full[i].max() - full[i, seq[i + 1]]) < 1e-5
    gauges = eng.gauges()
    assert gauges["kv_window_blocks_recycled"] > 0
    assert gauges["kv_window_blocks_free"] == gauges["kv_window_blocks_total"]
    assert gauges["kv_full_blocks_free"] == gauges["kv_full_blocks_total"]
    assert 0 < gauges["moe_rows_local"] <= 4 * gauges["moe_tokens_routed"]
    assert gauges["attn_full_kv_positions"] > gauges["attn_window_kv_positions"] > 0


def test_no_program_compiles_after_the_warm_up():
    eng = small_engine(reference_cfg())
    rng = np.random.default_rng(0)

    def begin(n):
        return eng.begin(rng.integers(0, 64, n), max_new_tokens=6, temperature=0.0)

    for n in (2, 4):
        slot = begin(n)
        while eng.prefill_step(slot) is None:
            pass
        eng.tick(), eng.tick(), eng.release(slot)
    warm = eng.compiled_programs()
    assert warm == len(eng.buckets) + 1
    first, second = begin(11), begin(2)
    while eng.prefill_step(first) is None:
        pass
    eng.prefill_step(second)
    eng.tick(), eng.tick()
    assert eng.compiled_programs() == warm


def test_attention_counters_count_by_group(monkeypatch):
    # Blocks of 2 query rows by 8 keys: a prompt's chunks of 4 start inside
    # a block of keys as well as on its edge.
    monkeypatch.setattr(sink_attention, "CHUNK_QUERY_ROWS", 2)
    monkeypatch.setattr(sink_attention, "CHUNK_KEYS", 8)
    c = reference_cfg()  # two full layers, five window layers
    eng = small_engine(c)
    cache = eng.cache
    slot = eng.begin(np.arange(1, 20), max_new_tokens=4, temperature=0.0)
    keys = cache.blocks_per_slot * cache.block_size
    assert sink_attention.chunk_tiles(4, keys, None) == (2, 8)

    def brute(start, end):
        full_pairs = sum(q + 1 for q in range(start, end))
        window_pairs = sum(min(q + 1, WINDOW) for q in range(start, end))
        return (2 * full_pairs, 5 * window_pairs, 2 * end,
                5 * (end - max(start - WINDOW + 1, 0)))

    def brute_walk(start, bucket):
        """The full layers' walks, pair by pair: every query block of the
        launch's bucket (rows of padding too) over the blocks of keys that
        hold a pair one of its rows sees, and those of them that also hold
        a pair it does not."""
        visited = masked = 0
        for first_row in range(start, start + bucket, 2):
            for block in range(0, keys, 8):
                seen = [k <= q for q in (first_row, first_row + 1)
                        for k in range(block, block + 8)]
                visited += any(seen)
                masked += any(seen) and not all(seen)
        return 2 * visited, 2 * masked

    names = ("chunk_attn_full_pairs", "chunk_attn_window_pairs",
             "chunk_attn_full_kv_positions", "chunk_attn_window_kv_positions")
    walks = ("chunk_attn_full_key_blocks", "chunk_attn_full_masked_blocks")
    for start, length in [(0, 4), (4, 4), (8, 4), (12, 3)]:
        before = [getattr(cache, n) for n in names + walks]
        cache.before_chunk(slot, start, length, 4)
        got = tuple(getattr(cache, n) - b for n, b in zip(names + walks, before))
        assert got == brute(start, start + length) + brute_walk(start, 4)
    assert 0 < cache.chunk_attn_full_masked_blocks < cache.chunk_attn_full_key_blocks
    positions = np.array([14, 0, 0], np.int32)
    active = np.array([True, False, False])
    seen, counts = cache.before_tick(np.array([slot]), positions, active)
    assert counts == {"attn_full_kv_positions": 2 * 15, "attn_window_kv_positions": 5 * 8}
    assert cache.attn_kv_positions == cache.attn_pairs - sum(
        getattr(cache, n) for n in names[:2]
    ) + sum(getattr(cache, n) for n in names[2:])
    gauges = cache.gauges()
    assert all(gauges[n] == getattr(cache, n) for n in names + walks)


def test_the_window_group_is_no_reservation():
    """A slot holds the blocks back from its next query's window start and
    no more once the launch that read further back is queued: the blocks of
    a chunk return at the next launch of ANY slot, and a group of window //
    block + 1 blocks a slot and one chunk's never runs out."""
    window, block, chunk = 16, 4, 32
    c = {**reference_cfg(), "sliding_window": window, "context_length": 256}
    eng = small_engine(c, slots=4, block_size=block, prefill_chunk=chunk,
                       prefill_buckets=(chunk,))
    cache = eng.cache
    total = cache.window_allocator.usable_blocks
    assert total == 4 * (window // block + 1) + (window + chunk) // block
    for slot in range(4):
        cache.admit(slot, eng.allocator.alloc(64))
    held = lambda s: len(cache.chains[s].ids)  # noqa: E731
    # Four prompts of 200, a chunk each in turn: each chunk's blocks are cut
    # back behind its launch, when the next slot's chunk is laid out.
    for start in range(0, 192, chunk):
        for slot in range(4):
            cache.before_chunk(slot, start, chunk, chunk)
            assert held(slot) <= (window + chunk) // block
            others = [held(s) for s in range(4) if s != slot]
            assert max(others) <= window // block + 1
            chain = cache.chains[slot]
            assert chain.first * block <= max(start - window + 1, 0)
            assert (chain.first + held(slot)) * block >= start + chunk
    positions = np.full(4, 192, np.int32)
    live, active = np.arange(4), np.ones(4, bool)
    for step in range(40):
        cache.before_tick(live, positions + step, active)
        assert all(held(s) <= window // block + 1 for s in range(4))
        for s in range(4):
            at = 192 + step
            first = cache.chains[s].first
            assert first * block <= at - window + 1 < (first + 1) * block
            assert cache.window_base[s] == first * block
    assert cache.window_recycled > 0
    for slot in range(4):
        cache.release(slot)
    assert cache.window_allocator.free_count == total


def test_growing_window_chain_takes_and_gives_back():
    alloc = BlockAllocator(12, 4)  # 11 usable blocks of 4 positions
    chain = GrowingWindowChain(alloc)
    assert chain.reach(-15, 31) and (chain.first, len(chain.ids)) == (0, 8)
    assert not chain.reach(0, 31) and alloc.free_count == 3
    assert chain.advance(16) == 4 and (chain.first, len(chain.ids)) == (4, 4)
    assert chain.advance(16) == 0 and alloc.free_count == 7
    with pytest.raises(NoFreeBlocksError):
        chain.reach(16, 100)
    assert (chain.first, len(chain.ids)) == (4, 4)  # nothing taken on failure
    assert chain.advance(1000) == 4 and chain.ids == [] and alloc.free_count == 11
    # Nothing live: the chain starts again at the window, not at block 4.
    assert chain.reach(90, 101) and (chain.first, len(chain.ids)) == (22, 4)
    chain.release()
    assert alloc.free_count == 11 and chain.ids == []


# ------------------------------------------------ the share and the whole


def test_all_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts of all 4 shares of 4 experts equal
    the uncut reference's expert layer (no shared expert to add once)."""
    uncut = reference_cfg(16, 0, layers=2)
    w = ref.weights_from_seed(7, uncut)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(1), (11, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(h, w, uncut, None)
    total, rows = 0.0, 0
    for offset in range(0, 16, 4):
        share = {**w, **{m: w[m][offset:offset + 4] for m in ("w1", "w2", "w3")}}
        cfg = program_cfg(reference_cfg(4, offset, layers=2))
        out, counts = dropless_moe(h, share, cfg)
        with jax.default_matmul_precision("highest"):
            theirs = ref.moe(h, share, reference_cfg(4, offset, layers=2), None)
        assert float(jnp.max(jnp.abs(out - theirs))) < 1e-8
        total, rows = total + out, rows + int(counts[1])
        assert int(counts[0]) == 11
    assert rows == 11 * 4  # every assignment lands on exactly one share
    size = float(jnp.max(jnp.abs(want)))
    assert size > 1e-4 and float(jnp.max(jnp.abs(total - want))) < 1e-4 * size


def test_the_selection_bias_chooses_and_does_not_weigh():
    c = reference_cfg(16, 0, layers=2)
    w = ref.weights_from_seed(7, c)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
    scores, biased = ref.selection_scores(h, w)
    by_score = np.sort(np.asarray(jax.lax.top_k(scores, 4)[1]), axis=-1)
    by_bias = np.sort(np.asarray(jax.lax.top_k(biased, 4)[1]), axis=-1)
    assert (by_score != by_bias).any(axis=-1).mean() > 0.2
    without = {k: v for k, v in w.items() if k != "router_bias"}
    cfg = program_cfg(c)
    out, _ = dropless_moe(h, w, cfg)
    bare, _ = dropless_moe(h, without, dataclasses.replace(cfg, router_bias=False))
    assert float(jnp.max(jnp.abs(out - bare))) > 1e-4


# ------------------------------------------ the kernels in interpret mode


@pytest.mark.parametrize("kv_heads", [2, 4], ids=["full4to1", "window2to1"])
@pytest.mark.parametrize("sunk", [False, True], ids=["nosink", "sink"])
def test_paged_kernel_matches_the_stand_in(kv_heads, sunk):
    rng = np.random.default_rng(0)
    slots, heads, dk, dv, bs, nb, blocks = 5, 8, 24, 16, 4, 70, 90
    pool = jnp.asarray(rng.normal(size=(blocks, bs, kv_heads * (dk + dv))), jnp.float32)
    tables = jnp.asarray(rng.integers(1, blocks, (slots, nb)), jnp.int32)
    # An idle slot, a partial block, several groups of 256 keys, a window's
    # start inside the first block.
    counts = jnp.asarray([0, 7, 277, 20, 33], jnp.int32)
    firsts = jnp.asarray([0, 0, 0, 2, 3], jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, heads, dk)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(heads,)), jnp.float32) if sunk else None
    got = sink_attention.sink_paged_attention(
        q, pool, tables, counts, firsts, sink, kv_heads=kv_heads, window=sunk,
        interpret=True,
    )
    rows = pool[tables].reshape(slots, nb * bs, -1)
    k, v = sink_attention.split_rows(rows, kv_heads, dk)
    want = sink_attention.xla_sink_attention(
        q[:, None], k, v, (counts - 1)[:, None], sink=sink, first=firsts[:, None]
    )[:, 0]
    assert got.shape == (slots, heads, dv)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    assert float(jnp.max(jnp.abs(got[0]))) == 0.0  # the idle slot: zeros


#: rows, the chunk's positions, bytes of a head's keys and values a step holds.
CHUNK_GEOMETRIES = {
    # Four query blocks of 8: at the chain's start (no clear block before the
    # first), off the key blocks' grid (40) and off the query blocks' (43),
    # and ending in the chain's last block.
    "chunk32": (32, (0, 40, 43, 64), None),
    # A bucket of fewer rows than a query block takes.
    "bucket4": (4, (0, 6, 92), None),
    # A chain too long to hold: the grid's last axis moves over three parts.
    "parts": (32, (0, 43, 64), 2 * 16 * 256 * 4),
}


@pytest.mark.parametrize("geometry", CHUNK_GEOMETRIES)
@pytest.mark.parametrize(
    "window", [None, 3, 5, 20, 40],
    ids=["full", "window3", "window5", "window20", "window40"],
)
@pytest.mark.parametrize("sunk", [False, True], ids=["nosink", "sink"])
def test_chunk_kernel_matches_the_stand_in(monkeypatch, window, sunk, geometry):
    """Blocks of 8 rows by 16 keys (8 under a window): blocks on the
    diagonal, blocks a window's lower edge crosses, wholly visible ones
    between them (a window of 20 or 40 keys has some, one narrower than a
    query block none) and blocks no row sees."""
    rows, starts, held_bytes = CHUNK_GEOMETRIES[geometry]
    monkeypatch.setattr(sink_attention, "CHUNK_QUERY_ROWS", 8)
    monkeypatch.setattr(sink_attention, "CHUNK_KEYS", 16)
    if held_bytes is not None:
        monkeypatch.setattr(sink_attention, "CHUNK_HELD_BYTES", held_bytes)
        tk = sink_attention.chunk_tiles(rows, 96, window)[1]
        assert sink_attention.chunk_held_blocks(96 // tk, tk * 256 * 4) == 32 // tk
    sink_attention._chunk_impl.clear_cache()
    rng = np.random.default_rng(1)
    keys, heads, kv, dk, dv = 96, 8, 2, 24, 16
    q = jnp.asarray(rng.normal(size=(rows, heads, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(keys, kv, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(keys, kv, dv)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(heads,)), jnp.float32) if sunk else None
    for at0 in starts:
        got = sink_attention.sink_chunk_attention(
            q, k, v, jnp.int32(at0), sink, window=window, interpret=True
        )
        want = sink_attention.xla_sink_attention(
            q[None], k[None], v[None], (at0 + jnp.arange(rows))[None],
            window=window, sink=sink,
        )[0]
        assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    sink_attention._chunk_impl.clear_cache()


@pytest.mark.parametrize("tq,tk", [(4, 8), (8, 4), (4, 4)])
@pytest.mark.parametrize("window", [None, 3, 8, 20])
def test_chunk_walk_is_the_blocks_a_query_block_sees(window, tq, tk):
    """`chunk_walk` against the pairs themselves, for every position of a
    chunk of three query blocks over a chain of six blocks of keys (the last
    positions run past the chain's end, as rows of padding may): the walk is
    exactly the blocks that hold a visible pair, the clear blocks exactly
    those that hold no other, and the kernel's arithmetic - the same function on traced
    scalars - gives the host's four integers."""
    key_blocks, blocks = 6, 3
    starts = np.arange(key_blocks * tk, dtype=np.int32)
    traced = jax.jit(jax.vmap(jax.vmap(
        lambda at, i: jnp.stack(sink_attention.chunk_walk(
            at, i, tq, tk, window, key_blocks, jnp.minimum, jnp.maximum
        )),
        in_axes=(None, 0),
    ), in_axes=(0, None)))(starts, jnp.arange(blocks, dtype=jnp.int32))
    for at0 in starts:
        for i in range(blocks):
            first, clear_lo, clear_hi, end = walk = sink_attention.chunk_walk(
                int(at0), i, tq, tk, window, key_blocks
            )
            assert all(isinstance(n, int) for n in walk)
            assert list(traced[at0, i]) == list(walk)
            assert 0 <= first <= clear_lo <= clear_hi <= end <= key_blocks
            rows = range(at0 + i * tq, at0 + (i + 1) * tq)
            for block in range(key_blocks):
                seen = [
                    k <= r and (window is None or r - k < window)
                    for r in rows for k in range(block * tk, (block + 1) * tk)
                ]
                assert (first <= block < end) == any(seen)
                assert (clear_lo <= block < clear_hi) == all(seen)


def test_chunk_tiles_walk_what_a_window_can_see():
    # The cell's shapes.  A full layer: blocks of 128 query rows (x 16 query
    # heads a K/V head) by 256 keys, a head's whole chain of 32,768 keys held
    # in VMEM (128 blocks of 256 x (256 + 128) lanes x 2 B), so the grid is (4
    # K/V heads, 16 query blocks, 1) and the walk is the kernel's own.  At
    # position 12,288 the first query block folds 48 clear blocks and one on
    # the diagonal, the last 55 and one; nothing of the chain's other 72.
    tiles, walk = sink_attention.chunk_tiles, sink_attention.chunk_walk
    assert tiles(2048, 32768, None) == (128, 256)
    assert tiles(512, 32768, None) == (128, 256)
    assert sink_attention.chunk_held_blocks(128, 256 * 384 * 2) == 128
    assert walk(12288, 0, 128, 256, None, 128) == (0, 0, 48, 49)
    assert walk(12288, 15, 128, 256, None, 128) == (0, 0, 55, 56)
    assert walk(0, 0, 128, 256, None, 128) == (0, 0, 0, 1)
    assert sink_attention.chunk_walk_blocks(12288, 2048, 32768, None) == (840, 16)
    # A chain eight times as long is held an eighth at a time.
    assert sink_attention.chunk_held_blocks(1024, 256 * 384 * 2) == 128
    # A window layer: blocks of 128 keys, a chain of window + chunk whatever
    # the context; a query block walks the two blocks of 128 its window of
    # 128 touches (three off the blocks' grid), each under a mask.
    assert tiles(2048, 2176, 128) == (128, 128)
    assert tiles(512, 2176, 128) == (128, 128)
    assert sink_attention.chunk_held_blocks(17, 128 * 384 * 2) == 17
    assert walk(128, 0, 128, 128, 128, 17) == (0, 1, 1, 2)
    assert walk(128, 15, 128, 128, 128, 17) == (15, 16, 16, 17)
    assert walk(130, 3, 128, 128, 128, 17) == (3, 5, 5, 6)
    assert sink_attention.chunk_walk_blocks(128, 2048, 2176, 128) == (32, 32)
    assert sink_attention.paged_group_blocks(16, 2048) == 16
    assert sink_attention.paged_group_blocks(16, 136) == 16
    assert sink_attention.sink_paged_path(16, 1280, 768) == "xla"  # the CPU
    assert sink_attention.sink_chunk_path(2048, 32768, None) == "xla"


# ---------------------------------------------------------------- refusals


def test_what_the_kind_cannot_serve_is_refused():
    c = reference_cfg()
    w, pc = ref.weights_from_seed(3, c), program_cfg(c)
    assert isinstance(small_engine(c).cache, host_cache.HostGroupedRows)
    assert cache_kind(pc) is not RecurrentRows
    for option, words in (
        (dict(prefix_cache=True), "prefix_cache=True"),
        (dict(kv_dtype="int8"), 'kv_dtype="int8"'),
        (dict(fused_sampling=True), "fused_sampling"),
    ):
        with pytest.raises(ValueError, match="window pool groups") as err:
            small_engine(c, **option)
        assert words in str(err.value)
    with pytest.raises(ValueError, match="weight_dtype"):
        small_engine(c, weight_dtype="int8")
    eng = small_engine(c)
    slot = eng.begin(np.arange(1, 6), max_new_tokens=4, temperature=0.0)
    for call in (
        lambda: eng.rewind(slot, 2), lambda: eng.extend_blocks(slot, 1),
        lambda: eng.export_slot(slot),
    ):
        with pytest.raises(NotImplementedError, match="window pool groups"):
            call()
    with pytest.raises(NotImplementedError, match="several rows a slot"):
        slot_cache(pc, eng.cache.table_rows(), jnp.zeros((3, 2), jnp.int32), block_size=2)
    from bpe_transformer_tpu.serving.server import ServingEngine

    with pytest.raises(ValueError, match="paged=True"):
        ServingEngine(w, pc, paged=False)
    with pytest.raises(ValueError, match="window pool groups"):
        ServingEngine(w, pc, paged=True, prefix_cache=False, speculate_k=2)
    with pytest.raises(ValueError, match="scan_layers"):
        dataclasses.replace(pc, scan_layers=True)
    from bpe_transformer_tpu.training.train_step import make_loss_fn

    with pytest.raises(Exception, match="serv|dropless|train"):
        make_loss_fn(pc)
