"""The Nemotron-H block (`NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`) at a small
size on the CPU: layers of one sublayer each by a pattern, the state-space
mixer's three forms with ``B`` and ``C`` by group, the dense cache and the
paged engine over state rows, K/V blocks and layers with no cache at all
(chunks of two bucket sizes, ticks, another slot mid-prefill, a slot's next
tenant) against ``chipbench/reference_nemotronh.py`` - which computes the
recurrence step by step - on seeded float32 weights; the share test that
ties a chip's experts and the shared expert to the whole layer; what each
departure of the block moves; the counters; and every refusal.

Tolerances: program and reference are both float32 here, so what is left
between them is the order of sums - the chunked scan's masked products
against the recurrence step by step, a grouped matmul against a loop over
experts: 5e-7 to 7e-7 of the widest logit (``apart``) as read here.
:data:`TOL`, 2e-5 of it, is thirty times that, and what any part of the
block moves when it is left out is fifty times :data:`TOL` or more
(``test_what_the_block_adds_moves_the_logits``: 1e-3 the least).  Single
mixers and the expert layer are compared in absolute terms at 1e-5 (values
of order one).
"""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from test_granitehybrid import (  # noqa: E402
    apart,
    begin,
    forced_tick,
    update_case,
    updated_where_it_rests,
    written_out_update,
)

from bpe_transformer_tpu.kernels.pallas import ssm as ssm_kernel  # noqa: E402
from bpe_transformer_tpu.models import moe, ssm  # noqa: E402
from bpe_transformer_tpu.models.config import LAYER_KINDS, ModelConfig  # noqa: E402
from bpe_transformer_tpu.models.decode import (  # noqa: E402
    RecurrentRows,
    cache_kind,
    decode_step,
    init_kv_cache,
    prefill,
    slot_cache,
)
from bpe_transformer_tpu.models.transformer import forward, init_params  # noqa: E402
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine  # noqa: E402
from chipbench import reference_nemotronh as ref  # noqa: E402

EXPERTS, TOP, PATTERN = 8, 3, "MEM*EME"
TOL = 2e-5       # program against reference, of the widest logit
LEFT_OUT_MOVES = 5e-4  # ... and the least a part of the block has to move them by
KINDS = [{"M": "ssm", "*": "attn", "E": "ffn"}[k] for k in PATTERN]


def reference_cfg(held=EXPERTS, offset=0, layers=len(PATTERN), **more) -> dict:
    """Hidden 64; 8 state-space heads of 16 in 4 groups with a state of 16,
    chunks of 8; 4 attention heads of 16 over 2 KV heads; 8 experts of width
    16, 3 a token, a shared expert of 32; seven layers of one sublayer each,
    three Mamba-2, three expert and one attention layer."""
    return {
        "hidden_size": 64, "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": layers,
        "hybrid_override_pattern": PATTERN * 2, "n_routed_experts": held,
        "n_experts": EXPERTS, "expert_offset": offset, "num_experts_per_tok": TOP,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "mamba_num_heads": 8, "mamba_head_dim": 16,
        "ssm_state_size": 16, "n_groups": 4, "conv_kernel": 4, "chunk_size": 8,
        "layer_norm_epsilon": 1e-5, "time_step_min": 1e-3, "time_step_max": 0.1,
        "vocab_size": 64, "context_length": 64, **more,
    }


def program_cfg(c: dict, **more) -> ModelConfig:
    args = dict(
        vocab_size=c["vocab_size"], context_length=c["context_length"],
        d_model=c["hidden_size"], num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["moe_intermediate_size"], remove_rope=True,
        tie_embeddings=False,
        layer_pattern=c["hybrid_override_pattern"][: c["num_hidden_layers"]],
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"], ssm_groups=c["n_groups"],
        ssm_conv=c["conv_kernel"], ssm_chunk=c["chunk_size"], ffn_type="moe",
        expert_activation="relu2", expert_d_ff=c["moe_intermediate_size"],
        shared_d_ff=c["moe_shared_expert_intermediate_size"],
        n_experts=c["n_experts"], router_top_k=c["num_experts_per_tok"],
        n_shared_experts=1, moe_router="sigmoid", router_bias=True,
        norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=c["routed_scaling_factor"],
        experts_held=c["n_routed_experts"], expert_offset=c["expert_offset"],
    )
    args.update(more)
    return ModelConfig(**args)


def small_engine(c: dict, seed=3, **more) -> PagedEngine:
    args = dict(slots=3, block_size=4, prefill_chunk=8, prefill_buckets=(4, 8),
                prefix_cache=False)
    args.update(more)
    return PagedEngine(ref.weights_from_seed(seed, c), program_cfg(c), **args)


SHARES = {"held_all": (EXPERTS, 0), "held_share": (4, 4)}


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_reference(share):
    c = reference_cfg(*SHARES[share])
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 20))
    ours = forward(w, jnp.asarray(tokens), program_cfg(c))
    theirs = ref.forward_logits(w, tokens, c)
    assert apart(ours, theirs) < TOL


def test_init_params_has_the_reference_tree_a_sublayer_a_layer():
    c = reference_cfg(4, 4)
    ours = init_params(jax.random.PRNGKey(0), program_cfg(c))
    theirs = ref.weights_from_seed(3, c)
    shapes = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)  # noqa: E731
    assert shapes(ours) == shapes(theirs)
    # One sublayer and one norm a layer; two matrices an expert, no w3.
    want = {"ssm": {"ssm", "ln1"}, "attn": {"attn", "ln1"}, "ffn": {"ffn", "ln2"}}
    assert [set(layer) for layer in ours["layers"]] == [want[k] for k in KINDS]
    experts = ours["layers"][1]["ffn"]
    assert set(experts) == {"router", "router_bias", "w1", "w2", "shared"}
    assert set(experts["shared"]) == {"w1", "w2"}
    assert ours["layers"][0]["ssm"]["in_proj"].shape == (128 + (128 + 2 * 4 * 16) + 8, 64)


def test_the_period_and_offset_are_a_pattern_spelt_out():
    """One representation: a periodic config's kinds are the pattern of
    ``m`` and ``a`` it spells, and the same config given as that pattern is
    the same program (trees and logits bit for bit)."""
    from test_granitehybrid import program_cfg as granite_cfg
    from test_granitehybrid import reference_cfg as granite_reference_cfg

    periodic = granite_cfg(granite_reference_cfg(6, 6))
    assert periodic.layer_kinds == "mam" and periodic.layer_pattern is None
    spelt = dataclasses.replace(
        periodic, attn_layer_period=0, attn_layer_offset=0, layer_pattern="mam"
    )
    assert [spelt.layer_mixer(i) for i in range(3)] == ["ssm", "attn", "ssm"]
    assert (spelt.ssm_layers, spelt.attn_layers) == (periodic.ssm_layers, periodic.attn_layers) == (2, 1)
    key = jax.random.PRNGKey(4)
    a, b = init_params(key, periodic), init_params(key, spelt)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(lambda x, y: bool(jnp.all(x == y)), a, b))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 64, (1, 12)))
    assert bool(jnp.all(forward(a, tokens, periodic) == forward(a, tokens, spelt)))


# ------------------------------------------------- the mixer's three forms


def mixer_case(rows=21):
    c = reference_cfg()
    p = ref.weights_from_seed(5, c)["layers"][0]["ssm"]
    u = jax.random.normal(jax.random.PRNGKey(2), (1, rows, 64), jnp.float32)
    return c, program_cfg(c), p, u


def test_whole_sequence_scan_matches_the_references_recurrence():
    c, pc, p, u = mixer_case()
    with jax.default_matmul_precision("highest"):
        want, _ = ref.mamba(u[0], p, ref._Frozen(c), None)
    got, state = ssm.mamba2(u, p, pc)
    assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-5
    assert state["ssm"].dtype == jnp.float32 and state["ssm"].shape == (1, 8, 16, 16)
    assert state["conv"].shape == (1, 3, 128 + 2 * 4 * 16)


@pytest.mark.parametrize("cut", [2, 5, 8, 13, 16])
def test_a_carried_chunk_continues_the_whole_sequence(cut):
    """Split at a boundary of the scan's chunk (8, 16) and off it, shorter
    than the convolution (2) and longer."""
    _, pc, p, u = mixer_case()
    whole, end = ssm.mamba2(u, p, pc)
    first, carried = ssm.mamba2(u[:, :cut], p, pc)
    second, last = ssm.mamba2(u[:, cut:], p, pc, carried)
    assert float(jnp.max(jnp.abs(jnp.concatenate([first, second], 1) - whole))) < 1e-5
    for name in ("ssm", "conv"):
        assert float(jnp.max(jnp.abs(last[name] - end[name]))) < 1e-5


def test_step_by_step_matches_the_whole_sequence():
    _, pc, p, u = mixer_case()
    whole, end = ssm.mamba2(u, p, pc)
    state, outs = ssm.init_ssm_state(pc, 1), []
    for t in range(u.shape[1]):
        out, state = ssm.mamba2_step(u[:, t], p, pc, state)
        outs.append(out)
    assert float(jnp.max(jnp.abs(jnp.stack(outs, 1) - whole))) < 1e-5
    for name in ("ssm", "conv"):
        assert float(jnp.max(jnp.abs(state[name] - end[name]))) < 1e-5


@pytest.mark.parametrize("real", [0, 5, 12])
def test_rows_that_are_not_valid_leave_the_state_alone(real):
    _, pc, p, u = mixer_case(12)
    before = ssm.mamba2(u[:, :7] * 0.5, p, pc)[1]
    want_out, want = (
        ssm.mamba2(u[:, :real], p, pc, before) if real else (u[:, :0], before)
    )
    got_out, got = ssm.mamba2(u, p, pc, before, jnp.arange(12)[None] < real)
    assert float(jnp.max(jnp.abs(got_out[:, :real] - want_out), initial=0.0)) < 1e-5
    for name in ("ssm", "conv"):
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) < 1e-6


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize(
    "heads, groups, channels, k, tiles",
    [(64, 8, 64, 2, 0), (64, 8, 64, 2, 2), (128, 16, 64, 2, 0), (128, 16, 64, 2, 2),
     (16, 2, 64, 2, 0), (16, 16, 64, 1, 0), (64, 8, 8, 1, 0), (32, 2, 8, 16, 0)],
    ids=[
        "8_groups_one_block", "8_groups_two_tiles_of_rows", "two_blocks_of_8_groups",
        "two_blocks_two_tiles_of_rows", "2_groups", "a_head_a_group",
        "narrow_heads_of_8_groups", "sixteen_heads_a_row_2_groups",
    ],
)
def test_the_grouped_kernel_updates_what_the_xla_update_updates(path, heads, groups, channels, k, tiles):
    """`ssm_state_update` with ``B`` and ``C`` by group over the resting
    layout - the XLA stand-in, and the kernel in interpret mode - against the
    recurrence written out over ``(heads, channels, state values)``: rows in
    any order, two rows sent to trash, the rest of the states untouched bit
    for bit; and a head reads its own group's rows (every group's ``B`` and
    ``C`` differ; no row of heads straddles two groups)."""
    n = 16 if channels == 64 else 128
    case = update_case(heads, channels, n, groups, tiles=tiles)
    state, ids, x, dt, a, b, c, d_skip = case
    assert ssm_kernel.heads_a_row(heads, channels, groups) == k
    assert ssm_kernel.to_resting(state, groups).shape == (len(state), heads // k, n, k * channels)
    want_y, want = written_out_update(*case)
    got_y, got = updated_where_it_rests(path, *case)
    assert float(jnp.max(jnp.abs(got_y - want_y))) < 1e-4
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # A head at a time under its group's B and C.
    per_group = heads // groups
    for h in (0, per_group - 1, heads - 1):
        g = h // per_group
        one_y, one = written_out_update(
            state[:, h:h + 1], ids, x[:, h:h + 1], dt[:, h:h + 1], a[h:h + 1],
            b[:, g], c[:, g], d_skip[h:h + 1],
        )
        assert float(jnp.max(jnp.abs(one_y[:, 0] - got_y[:, h]))) < 1e-4
        assert float(jnp.max(jnp.abs(one[:, 0] - got[:, h]))) < 1e-5


# --------------------------------------------------------- the dense cache


def test_dense_cache_matches_reference():
    """Prefill (the chunked scan) then decode_step token by token (one step
    a sequence); a layer without a mixer has an empty entry."""
    c = reference_cfg(4, 4)
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(1).integers(0, 64, (2, 24))
    full = ref.forward_logits(w, tokens, c)
    cache = init_kv_cache(pc, 2)
    assert [sorted(layer) for layer in cache[:4]] == [["conv", "ssm"], [], ["conv", "ssm"], ["k", "v"]]
    logits, cache = prefill(w, jnp.asarray(tokens[:, :9]), pc, cache)
    worst = apart(logits, full[:, 8])
    step = jax.jit(functools.partial(decode_step, config=pc))
    for t in range(9, 24):
        logits, cache = step(w, jnp.asarray(tokens[:, t]), jnp.asarray(t), cache)
        worst = max(worst, apart(logits, full[:, t]))
    assert worst < TOL


# ------------------------------------------------- the paged engine's paths


def served_logit_error(eng, c, tokens, plen, between=lambda t: None, seed=3):
    """Prefill ``tokens[:plen]`` in the engine's chunks, then teacher-forced
    ticks to the end (``between(t)`` runs before the tick at ``t``): the
    widest difference of a tick's logits from the reference's full forward
    (``apart``), and the slot."""
    full = ref.forward_logits(ref.weights_from_seed(seed, c), tokens[None], c)[0]
    slot = begin(eng, tokens[:plen], len(tokens) - plen)
    while eng.prefill_step(slot) is None:
        pass
    worst = 0.0
    for t in range(plen, len(tokens)):
        between(t)
        worst = max(worst, apart(forced_tick(eng, slot, tokens[t], t), full[t]))
    return worst, slot


@pytest.mark.parametrize("update", ["xla", "pallas"])
def test_paged_chunks_and_ticks_match_reference(update, monkeypatch):
    """A prompt of 11 in chunks of two bucket sizes (8, then 3 in the bucket
    of 4: the carried scan, the second chunk off the scan's chunk boundary),
    then 19 ticks (the XLA update, or the grouped kernel in interpret mode)
    while ANOTHER slot is admitted and prefills its two chunks between them."""
    monkeypatch.setattr(
        ssm_kernel, "ssm_state_update",
        functools.partial(ssm_kernel.ssm_state_update, path=update),
    )
    c = reference_cfg(4, 4)
    eng = small_engine(c)
    assert cache_kind(eng.config) is RecurrentRows
    tokens = np.random.default_rng(2).integers(0, 64, 30)
    other = np.random.default_rng(3).integers(0, 64, 13)
    steps = iter(["begin", "chunk", None, "chunk", None])

    def another_slot_prefills(t, state={}):
        step = next(steps, None)
        if step == "begin":
            state["slot"] = begin(eng, other)
        elif step == "chunk":
            eng.prefill_step(state["slot"])

    worst, slot = served_logit_error(eng, c, tokens, 11, another_slot_prefills)
    assert worst < TOL
    assert not eng.pending_prefills() and eng.active_count == 2
    # The other slot, prefilled between this one's ticks, serves as alone.
    full = ref.forward_logits(ref.weights_from_seed(3, c), np.append(other, 7)[None], c)[0]
    assert apart(forced_tick(eng, 1 - slot, 7, 13), full[13]) < TOL
    # State rows a slot (and trash), K/V blocks, and nothing for a layer
    # without a mixer.
    want = {"ssm": ["conv", "ssm"], "attn": ["k", "v"], "ffn": []}
    assert [sorted(entry) for entry in eng._pool] == [want[k] for k in KINDS]
    # ... where they rest: the state values, then the channels along the
    # lanes - a group's 2 heads do not fill a row with 8, so a head a row.
    assert eng._pool[0]["ssm"].shape == (3 + 1, 8 // 1, 16, 1 * 16)
    assert eng._pool[0]["ssm"].dtype == jnp.float32
    assert eng._pool[0]["conv"].shape == (3 + 1, 3, 128 + 2 * 4 * 16)


def test_a_slots_next_tenant_serves_as_a_fresh_engine_does():
    c = reference_cfg(4, 4)
    rng = np.random.default_rng(4)
    first, second = rng.integers(0, 64, 26), rng.integers(0, 64, 22)
    used, fresh = small_engine(c), small_engine(c)
    _, slot = served_logit_error(used, c, first, 10)
    used.release(slot)
    worst_used, again = served_logit_error(used, c, second, 6)
    worst_fresh, _ = served_logit_error(fresh, c, second, 6)
    assert again == slot and worst_used < TOL and worst_used == worst_fresh
    assert used.gauges()["ssm_state_resets"] == 2


def test_engine_serves_greedy_tokens_and_counts_by_the_pattern():
    """Three slots at ragged depths through admit/tick, the way the worker
    drives the engine; every counter equals a count by hand over the
    pattern's own layers: 3 state-space, 1 attention, 3 expert layers."""
    c = reference_cfg(4, 4)
    eng = small_engine(c)
    w = ref.weights_from_seed(3, c)
    rng = np.random.default_rng(5)
    lengths = (13, 5, 9)
    prompts = [rng.integers(0, 64, n) for n in lengths]
    seqs = [list(p) for p in prompts]
    for seq, prompt in zip(seqs, prompts):
        seq.append(eng.admit(prompt, max_new_tokens=12, temperature=0.0).token)
    while eng.active_count:
        for event in eng.tick():
            seqs[event.slot].append(event.token)
    cfg = ref._Frozen(c)
    for prompt, seq in zip(prompts, seqs):
        assert len(seq) == len(prompt) + 12
        # The served token is the reference's first under the routing the
        # reference itself takes, or under one of its near ties.
        padded = np.asarray(seq + [0] * (32 - len(seq)), np.int32)
        memory = []
        ref.hidden_states(w, padded, cfg, memory=memory, keep=(len(prompt) - 1, len(seq) - 1))
        rows, origin = ref.followed_routings(w, cfg, padded, memory, len(prompt) - 1, len(seq) - 1)
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(ref.head(jnp.asarray(rows), w, cfg, None))
        served = np.asarray(seq[len(prompt):])[origin]
        gaps = np.full(12, np.inf)
        np.minimum.at(gaps, origin, logits.max(-1) - logits[np.arange(len(origin)), served])
        assert gaps.max() < 1e-6
    gauges = eng.gauges()
    assert gauges["kv_blocks_free"] == gauges["kv_blocks_total"]
    ssm_layers, attn_layers, expert_layers = 3, 1, 3
    assert (eng.config.ssm_layers, eng.config.attn_layers) == (ssm_layers, attn_layers)
    # Chunks: 13 = 8 + 5 (buckets 8, 8), 5 (bucket 8), 9 = 8 + 1 (8, 4).
    assert gauges["ssm_chunk_tokens"] == ssm_layers * (13 + 5 + 9)
    assert gauges["ssm_chunk_rows"] == ssm_layers * (8 + 8 + 8 + 8 + 4)
    assert gauges["ssm_state_resets"] == 3
    assert gauges["ssm_tick_state_rows"] == ssm_layers * 3 * 11
    assert eng.last_tick_counts == {"ssm_tick_state_rows": ssm_layers * 3}
    # The grouped widths: conv rows of 128 + 2 x 4 x 16 channels.
    assert gauges["ssm_state_bytes"] == ssm_layers * 4 * (8 * 16 * 16 + 3 * 256) * 4
    ticks = sum(sum(range(n + 1, n + 12)) for n in lengths)
    assert gauges["attn_pairs"] == gauges["attn_kv_positions"] == attn_layers * ticks
    assert gauges["kv_bytes_per_token"] == attn_layers * 2 * 2 * 16 * 4
    assert gauges["kv_pool_bytes"] == attn_layers * 2 * eng.allocator.num_blocks * 4 * 32 * 4
    assert gauges["moe_tokens_routed"] == expert_layers * (13 + 5 + 9 + 3 * 11)
    assert 0 < gauges["moe_rows_local"] < TOP * gauges["moe_tokens_routed"]


def test_no_program_compiles_after_the_warm_up():
    eng = small_engine(reference_cfg(4, 4))
    rng = np.random.default_rng(0)
    for n in (3, 7):
        slot = begin(eng, rng.integers(0, 64, n))
        while eng.prefill_step(slot) is None:
            pass
        eng.tick(), eng.tick(), eng.release(slot)
    warm = eng.compiled_programs()
    assert warm == len(eng.buckets) + 1
    slots = [begin(eng, rng.integers(0, 64, n)) for n in (13, 2, 20)]
    for slot in slots:
        while eng.prefill_step(slot) is None:
            eng.tick()
    eng.tick(), eng.tick()
    assert eng.compiled_programs() == warm
    gauges = eng.gauges()
    assert gauges["kv_pool_aliased_bytes"] == gauges["kv_pool_bytes"]


# ------------------------------------------- the expert layer and its shares


def test_both_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test: the routed parts of both shares of 4 experts plus the
    shared expert counted once equal the uncut reference layer."""
    uncut = reference_cfg(layers=2)
    w = ref.weights_from_seed(7, uncut)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(1), (11, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(h, w, uncut, None)
        shared = ref._relu2(h, w["shared"]["w1"][0], w["shared"]["w2"][0], None)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    routed = jnp.zeros_like(h)
    for offset in (0, 4):
        share = {**w, **{k: w[k][offset:offset + 4] for k in ("w1", "w2")}}
        out, counts = moe.dropless_moe(h, share, program_cfg(reference_cfg(4, offset, layers=2)))
        routed = routed + out - shared
        assert int(counts[0]) == 11 and 0 < int(counts[1]) < TOP * 11
    assert float(jnp.max(jnp.abs(routed + shared - want))) < 1e-5


def _shared_bc(split):
    def first_groups(xbc, config):
        x, b, c = split(xbc, config)
        return x, jnp.broadcast_to(b[..., :1, :], b.shape), jnp.broadcast_to(c[..., :1, :], c.shape)

    return first_groups


LEFT_OUT = {
    "groups_of_b_and_c": lambda mp: mp.setattr(ssm, "_split_xbc", _shared_bc(ssm._split_xbc)),
    "norm_by_group": lambda mp: mp.setattr(
        ssm, "_gate_out", lambda y, z, p, groups, eps, fn=ssm._gate_out: fn(y, z, p, 1, eps)
    ),
    "the_square": lambda mp: mp.setattr(moe, "relu2", jax.nn.relu),
    "relu_for_silu": lambda mp: mp.setattr(moe, "relu2", lambda x: jnp.square(jax.nn.silu(x))),
    "selection_bias": "zero_bias",
    "scaling_2.5": dict(routed_scaling_factor=1.0),
    "gate_norm": dict(norm_topk_prob=False),
    "sigmoid": dict(moe_router="softmax"),
    "attention_scale": dict(attention_multiplier=1.0),
}


@pytest.mark.parametrize("what", LEFT_OUT)
def test_what_the_block_adds_moves_the_logits(what, monkeypatch):
    """Each part of the block left out of the PROGRAM (a head reading group
    0's ``B`` and ``C``, one norm over all inner channels, a plain ReLU, a
    SiLU squared, no selection bias, no scaling by 2.5, gates not
    normalised, softmax scores, scores not scaled by ``d_head ** -0.5``):
    the logits leave the reference's by twenty-five times the tolerance of
    the tests above, or more."""
    c = reference_cfg(4, 4)
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (1, 20))
    theirs = ref.forward_logits(w, tokens, c)
    change, ours_w, more = LEFT_OUT[what], w, {}
    if change == "zero_bias":
        ours_w = jax.tree_util.tree_map_with_path(
            lambda path, a: a * 0 if "router_bias" in str(path) else a, w
        )
    elif isinstance(change, dict):
        more = change
    else:
        change(monkeypatch)
    ours = forward(ours_w, jnp.asarray(tokens), program_cfg(c, **more))
    assert apart(ours, theirs) > LEFT_OUT_MOVES


def test_a_layer_is_one_sublayer():
    """``x + F(norm(x))`` and no more: a layer's output less its input is
    its one branch - a state-space layer adds no expert branch, an expert
    layer no mixer's (`_block_apply` reads the layer's tree)."""
    from bpe_transformer_tpu.models.decode import _block_apply, _norm

    c = reference_cfg(4, 4)
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 9, 64), jnp.float32)
    m = w["layers"][0]
    mixed = lambda h: ssm.mamba2(h, m["ssm"], pc)[0]  # noqa: E731
    assert bool(jnp.all(_block_apply(x, m, pc, mixed) == x + mixed(_norm(x, m["ln1"], pc))))
    e = w["layers"][1]
    routed = moe.dropless_moe(_norm(x, e["ln2"], pc), e["ffn"], pc)[0]
    assert bool(jnp.all(_block_apply(x, e, pc, None) == x + routed))


# ------------------------------------------------- the reference's own parts


def test_rows_of_decided_positions_equal_the_full_forward():
    c = ref._Frozen(reference_cfg(4, 4))
    w = ref.weights_from_seed(3, c)
    ids = np.random.default_rng(8).integers(0, 64, 48).astype(np.int32)
    memory = []
    states = ref.hidden_states(w, ids, c, memory=memory, keep=(19, 45))
    rows, origin = ref.followed_routings(w, c, ids, memory, 19, 45)
    first = np.unique(origin, return_index=True)[1]
    assert float(np.max(np.abs(rows[first] - np.asarray(states[19:45])))) < 1e-5
    assert [m is None for m in memory] == [k == "ffn" for k in KINDS]
    assert memory[0]["first"] == 16 and memory[0]["stretch"]["states"].shape[1:] == (8, 16, 16)


def test_served_gaps_of_the_references_own_greedy_tokens(monkeypatch):
    monkeypatch.setattr(
        ref, "weights_from_seed", lambda seed, cfg, dtype=None: ref.init_weights(seed, cfg)
    )
    c = reference_cfg(4, 4)
    w = ref.init_weights(11, c)
    rng = np.random.default_rng(9)
    seq = list(rng.integers(0, 64, 7))
    for _ in range(6):
        padded = np.asarray(seq + [0] * (32 - len(seq)))[None]
        seq.append(int(jnp.argmax(ref.forward_logits(w, padded, c)[0, len(seq) - 1])))
    sequences = [(seq[:7], seq[7:])]
    assert max(ref.served_gaps(11, c, sequences)) < 1e-7
    wrong = [(prompt, [(t + 1) % 64 for t in served]) for prompt, served in sequences]
    assert min(ref.served_gaps(11, c, wrong)) > 1e-5
    assert all(g >= 0 for g in ref.served_gaps(11, c, sequences, control=True))


def test_the_seeded_selection_bias_decides_choices():
    """The bias the reference seeds is no decoration: with it a good part of
    the tokens choose other experts than their scores alone would."""
    c = reference_cfg(layers=2)
    p = ref.weights_from_seed(3, c)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(5), (64, 64), jnp.float32)
    scores, biased = ref.selection_scores(h, p)
    plain = np.sort(np.asarray(jax.lax.top_k(scores, TOP)[1]), -1)
    chosen = np.sort(np.asarray(jax.lax.top_k(biased, TOP)[1]), -1)
    assert 0.2 < float(np.mean(np.any(plain != chosen, -1)))
    ids, gates = moe.route(h, p["router"], program_cfg(c), p["router_bias"])
    assert np.array_equal(np.sort(np.asarray(ids), -1), chosen)
    assert float(jnp.max(jnp.abs(jnp.sum(gates, -1) - 2.5))) < 1e-5


# ---------------------------------------------------------------- refusals


@pytest.mark.parametrize(
    "more, message",
    [
        (dict(prefix_cache=True), "prefix_cache=True .* is not supported over a recurrent state"),
        (dict(kv_dtype="int8"), 'kv_dtype="int8" is not supported over a recurrent state'),
        (dict(weight_dtype="int8"), "weight_dtype quantizes the dense block's weight tree"),
        (dict(fused_sampling=True), "fused_sampling is not supported over a recurrent state"),
    ],
    ids=["prefix_cache", "kv_int8", "weight_int8", "fused_sampling"],
)
def test_engine_refuses_at_construction(more, message):
    with pytest.raises(ValueError, match=message):
        small_engine(reference_cfg(4, 4), **more)


@pytest.mark.parametrize("what", ["extend_blocks", "export_slot", "import_slot", "rewind"])
def test_engine_refuses_scratch_migration_and_rewind(what):
    eng = small_engine(reference_cfg(4, 4))
    slot = begin(eng, np.arange(6))
    while eng.prefill_step(slot) is None:
        pass
    call = {
        "extend_blocks": lambda: eng.extend_blocks(slot, 8),
        "export_slot": lambda: eng.export_slot(slot),
        "import_slot": lambda: eng.validate_import_meta({"format": 1}),
        "rewind": lambda: eng.rewind(slot, 3),
    }[what]
    with pytest.raises(NotImplementedError, match="a recurrent state: it is the state after"):
        call()


@pytest.mark.parametrize(
    "more, message",
    [
        (dict(paged=False), "state-space layers is served by the paged engine"),
        (dict(speculate_k=2), "not supported over a recurrent state"),
        (dict(role="prefill"), "not supported over a recurrent state"),
        (dict(role="decode"), "not supported over a recurrent state"),
    ],
    ids=["dense_engine", "speculation", "prefill_role", "decode_role"],
)
def test_serving_engine_refuses(more, message):
    from bpe_transformer_tpu.serving.server import ServingEngine

    c = reference_cfg(4, 4)
    args = dict(paged=True, block_size=4, prefill_chunk=8, prefix_cache=False)
    args.update(more)
    with pytest.raises(ValueError, match=message):
        ServingEngine(ref.weights_from_seed(3, c), program_cfg(c), **args)


def test_a_verify_pass_padded_prefill_and_training_are_refused():
    c = reference_cfg(4, 4)
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    with pytest.raises(NotImplementedError, match="several rows a slot"):
        slot_cache(pc, jnp.zeros((3, 16), jnp.int32), jnp.zeros((3, 2), jnp.int32),
                   block_size=4)
    with pytest.raises(NotImplementedError, match="padded prefill"):
        prefill(w, jnp.zeros((1, 8), jnp.int32), pc, init_kv_cache(pc, 1),
                last_pos=jnp.asarray([4]))
    with pytest.raises(ValueError, match="scan_layers"):
        program_cfg(c, scan_layers=True)
    from bpe_transformer_tpu.training.train_step import make_loss_fn

    with pytest.raises(ValueError, match="training is not supported"):
        make_loss_fn(pc)


@pytest.mark.parametrize(
    "change, message",
    [
        (dict(layer_pattern="MEM*EM"), "must name each of num_layers=7"),
        (dict(layer_pattern="MEM*EMx"), "by one of 'M\\*EmawA'"),
        (dict(attn_layer_period=3), "say the same twice"),
        (dict(ssm_groups=3), "ssm_groups=3 must divide ssm_heads=8"),
        (dict(ssm_groups=0), "must divide"),
        (dict(expert_activation="gelu"), 'must be "swiglu" or "relu2"'),
        (dict(ffn_type=None, n_shared_experts=0, shared_d_ff=None, experts_held=None,
              expert_d_ff=None, router_bias=False), 'relu2", the latter of an expert layer'),
        (dict(sliding_window=8), "contradict"),
        (dict(parallel_block=True), "contradict"),
    ],
    ids=["short_pattern", "unknown_letter", "pattern_and_period", "groups_do_not_divide",
         "no_groups", "unknown_activation", "relu2_without_experts", "window", "parallel"],
)
def test_config_refuses_contradictions(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(program_cfg(reference_cfg(4, 4)), **change)


def test_config_properties_and_defaults():
    cfg = program_cfg(reference_cfg(4, 4))
    assert cfg.layer_kinds == PATTERN and set(PATTERN) <= set(LAYER_KINDS)
    assert [cfg.layer_mixer(i) for i in range(7)] == ["ssm", None, "ssm", "attn", None, "ssm", None]
    assert [cfg.layer_has_ffn(i) for i in range(7)] == [k == "ffn" for k in KINDS]
    assert (cfg.ssm_layers, cfg.attn_layers, cfg.ssm_inner, cfg.ssm_conv_channels) == (3, 1, 128, 256)
    assert (cfg.attention_scale, cfg.shared_ff, cfg.moe_d_ff) == (0.25, 32, 16)
    assert cfg.hybrid_block and cfg.dropless_block and cfg.local_experts == 4
    # Every default is the block that was: one group, SwiGLU experts, a
    # mixer and a feed-forward part in every layer.
    from bpe_transformer_tpu.models.config import TS_TEST_CONFIG as plain

    assert (plain.ssm_groups, plain.expert_activation, plain.layer_pattern) == (1, "swiglu", None)
    assert plain.layer_kinds == "a" * plain.num_layers and plain.attn_layers == plain.num_layers
    assert not plain.hybrid_block and not plain.dropless_block and plain.ssm_layers == 0
    for field, value in [("ssm_groups", 2), ("expert_activation", "relu2")]:
        with pytest.raises(ValueError, match="hybrid block's"):
            dataclasses.replace(plain, **{field: value})


# ------------------------------------------- the served tree's down projection


def _relaid_case(held, offset, seed=7, rows=23):
    c = reference_cfg(held, offset, layers=2)
    raw = ref.weights_from_seed(seed, c)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (rows, 64), jnp.float32)
    return h, raw, moe.serving_layout(raw), program_cfg(c)


@pytest.mark.parametrize("share", SHARES)
@pytest.mark.parametrize("real", [None, 0, 9, 23])
def test_the_relaid_down_projection_is_the_same_layer(share, real):
    """`dropless_moe` over a served tree - the down projection held as
    ``(experts, d_ff, d_model)`` under `moe.W2_RELAID` - equals
    `dropless_moe` over the torch-layout tree exactly, outputs and counts:
    every row routed, only the first ``real`` rows ``valid``, all experts
    held and a share of them at an ``expert_offset``."""
    h, raw, served, config = _relaid_case(*SHARES[share])
    assert "w2" not in served and set(served) - set(raw) == {moe.W2_RELAID}
    assert served[moe.W2_RELAID].shape == (config.local_experts, 16, 64)
    assert all(served[k] is raw[k] for k in raw if k != "w2")
    valid = None if real is None else jnp.arange(h.shape[0]) < real
    want, want_counts = moe.dropless_moe(h, raw, config, valid=valid)
    got, got_counts = moe.dropless_moe(h, served, config, valid=valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_counts), np.asarray(want_counts))
    assert int(want_counts[1]) > 0 or real == 0
    assert float(jnp.max(jnp.abs(want))) > 1e-3


def test_grouped_matmul_reads_either_form_of_its_weights():
    from bpe_transformer_tpu.kernels.pallas.grouped_matmul import grouped_matmul, relaid_rhs

    rng = np.random.default_rng(2)
    lhs = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((4, 64, 16)), jnp.float32)
    sizes = jnp.asarray([5, 0, 12, 3], jnp.int32)  # rows past 20: no group
    want = grouped_matmul(lhs, rhs, sizes)
    got = grouped_matmul(lhs, jnp.swapaxes(rhs, 1, 2), sizes, transpose_rhs=False)
    assert got.shape == want.shape == (24, 64)
    np.testing.assert_array_equal(np.asarray(got[:20]), np.asarray(want[:20]))
    # The rule by width: whole lane tiles keep the torch layout.
    assert [relaid_rhs(n) for n in (1856, 16, 768, 2048, 4096)] == [True, True, False, False, False]


def _published(name, **cut):
    import json

    path = Path(__file__).resolve().parents[1] / f"chipbench/configs/{name}.json"
    file = json.loads(path.read_text())
    return ModelConfig(**{**{k: file[k] for k in file["architecture_keys"]}, **cut})


def _pipeline_shapes(config):
    """The raw tree's and the weight pipeline's result's leaves, by path, as
    shapes alone (published widths: nothing is allocated)."""
    from bpe_transformer_tpu.serving.engine import prepare_serving_weights

    raw = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config))
    counted = []

    def pipeline(params):
        served, lm_head, _, *byte_counts = prepare_serving_weights(params, config, None)
        counted[:] = byte_counts
        return served, lm_head

    served, _ = jax.eval_shape(pipeline, raw)
    paths = lambda tree: {  # noqa: E731
        jax.tree_util.keystr(path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }
    return paths(raw), paths(served), raw, tuple(counted)


def _gpt2_small():
    from bpe_transformer_tpu.models.config import GPT2_SMALL_32K

    return dataclasses.replace(GPT2_SMALL_32K, num_layers=2)


OTHER_TREES = {
    "granite": lambda: _published(
        "granite-4.0-h-small", num_layers=2, attn_layer_period=2, attn_layer_offset=1
    ),
    "cmdaplus": lambda: _published("command-a-plus-05-2026"),
    "longcat": lambda: _published("LongCat-Flash-Omni", num_layers=1),
    "evabyte": lambda: _published("EvaByte", num_layers=2),
    "gpt2-small": _gpt2_small,
}


@pytest.mark.parametrize("cell", OTHER_TREES)
def test_the_weight_pipeline_leaves_the_other_trees_as_they_are(cell):
    """Expert widths of whole lane tiles (768, 4,096, 2,048) and trees
    without an expert layer pass `prepare_serving_weights` leaf for leaf:
    the same paths, the same shapes."""
    before, after, _, _ = _pipeline_shapes(OTHER_TREES[cell]())
    assert after == before
    assert not any(moe.W2_RELAID in path for path in after)


def test_the_weight_pipeline_relays_nemotrons_down_projections():
    """At the published widths and the cell's 13 layers the served tree
    holds ``w2_relaid`` (held, 1,856, 2,688) - the shape ``w1`` has - where
    the raw tree holds ``w2`` (held, 2,688, 1,856), in each of the five
    expert layers; every other leaf is as it came - the shared expert's
    ``w2`` too - and both byte counts are the torch-layout tree's."""
    from bpe_transformer_tpu.ops.quant import tree_bytes

    config = _published("NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    before, after, raw, (params_bytes, tick_weight_bytes) = _pipeline_shapes(config)
    moved = {path for path in before if path.endswith("['ffn']['w2']")}
    assert len(moved) == config.layer_pattern.count("E") == 5
    assert all(before[p] == (64, 2688, 1856) for p in moved)
    relaid = {p.replace("['w2']", f"['{moe.W2_RELAID}']") for p in moved}
    assert set(after) == set(before) - moved | relaid
    assert all(after[p] == (64, 1856, 2688) for p in relaid)
    assert all(after[p] == before[p] for p in set(after) - relaid)
    assert before["['layers'][1]['ffn']['shared']['w2']"] == (1, 2688, 3712)
    assert after["['layers'][1]['ffn']['w1']"] == (64, 1856, 2688)
    half = lambda tree: tree_bytes(tree) // 2  # noqa: E731  float32 -> bfloat16
    assert params_bytes == half(raw) + half(raw["lm_head"])
    assert tick_weight_bytes == (
        half(raw["layers"]) + half(raw["ln_final"]) + half(raw["lm_head"])
    )


def test_the_pipeline_does_not_touch_the_callers_tree():
    from bpe_transformer_tpu.serving.engine import prepare_serving_weights

    c = reference_cfg(4, 4)
    raw = ref.weights_from_seed(3, c)
    layers = list(raw["layers"])
    ffns = [dict(layer["ffn"]) for layer in layers if "ffn" in layer]
    served = prepare_serving_weights(raw, program_cfg(c), None)[0]
    assert raw["layers"] == layers and all(a is b for a, b in zip(raw["layers"], layers))
    kept = [layer["ffn"] for layer in raw["layers"] if "ffn" in layer]
    assert [set(f) for f in kept] == [set(f) for f in ffns]
    assert all(f[k] is g[k] for f, g in zip(kept, ffns) for k in g)
    assert all(
        moe.W2_RELAID in layer["ffn"] for layer in served["layers"] if "ffn" in layer
    )


def test_the_engine_says_how_many_layers_it_holds_relaid():
    """``moe_relaid_layers``: every expert layer of the pattern here (a
    width of 16 is no whole lane tile), none where the experts' width is
    one or the tree has no expert layer."""
    eng = small_engine(reference_cfg(4, 4))
    assert eng.gauges()["moe_relaid_layers"] == PATTERN.count("E") == 3
    wide = reference_cfg(4, 4, moe_intermediate_size=128)
    assert small_engine(wide).gauges()["moe_relaid_layers"] == 0
    from bpe_transformer_tpu.models.config import TS_TEST_CONFIG

    dense = dataclasses.replace(TS_TEST_CONFIG, num_layers=1, context_length=32)
    plain = PagedEngine(init_params(jax.random.PRNGKey(0), dense), dense, slots=2, block_size=4)
    assert plain.gauges()["moe_relaid_layers"] == 0
