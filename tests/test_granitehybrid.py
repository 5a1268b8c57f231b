"""The Granite-4.0-H block (`granite-4.0-h-small`) at a small size on the
CPU: the plain forward, the state-space mixer's three forms, the dense cache
and the paged engine over a recurrent state beside K/V blocks (chunks of two
bucket sizes, ticks, another slot mid-prefill, a slot's next tenant) against
``chipbench/reference_granitehybrid.py`` - which computes the recurrence step
by step - on seeded float32 weights; the share test that ties a chip's
experts and the shared expert to the whole layer; the multipliers; the
counters; and every refusal of what cannot run yet."""

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bpe_transformer_tpu.kernels.pallas import ssm as ssm_kernel  # noqa: E402
from bpe_transformer_tpu.models import ssm  # noqa: E402
from bpe_transformer_tpu.models.config import TS_TEST_CONFIG, ModelConfig  # noqa: E402
from bpe_transformer_tpu.models.decode import (  # noqa: E402
    RecurrentRows,
    cache_kind,
    decode_step,
    init_kv_cache,
    paged_forward,
    prefill,
    slot_cache,
)
from bpe_transformer_tpu.models.moe import dropless_moe  # noqa: E402
from bpe_transformer_tpu.models.transformer import forward, init_params  # noqa: E402
from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine  # noqa: E402
from chipbench import reference_granitehybrid as ref  # noqa: E402

EXPERTS, TOP, LAYERS = 12, 3, 3
MULTIPLIERS = {
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 4.0, "logits_scaling": 16,
}


def reference_cfg(held=EXPERTS, offset=0, layers=LAYERS, **more) -> dict:
    """Hidden 64; 8 state-space heads of 16 with a state of 16, chunks of 8;
    4 attention heads over 2 KV heads of 16; 12 experts of width 16, 3 a
    token, a shared expert of 32; one period of three layers, the attention
    layer second.  (The attention multiplier is large where the model's is
    small: at these widths scores of a few tenths leave every softmax nearly
    flat, and the test that drops the multiplier would see nothing.)"""
    return {
        "hidden_size": 64, "intermediate_size": 16, "shared_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": layers, "layer_types": ["mamba", "attention", "mamba"] * 4,
        "num_local_experts": held, "n_experts": EXPERTS, "expert_offset": offset,
        "num_experts_per_tok": TOP, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_chunk_size": 8, "rms_norm_eps": 1e-5, "vocab_size": 64,
        "context_length": 64, **MULTIPLIERS, **more,
    }


def program_cfg(c: dict, **more) -> ModelConfig:
    args = dict(
        vocab_size=c["vocab_size"], context_length=c["context_length"],
        d_model=c["hidden_size"], num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], remove_rope=True, tie_embeddings=True,
        attn_layer_period=3, attn_layer_offset=1, ssm_heads=c["mamba_n_heads"],
        ssm_head_dim=c["mamba_d_head"], ssm_state=c["mamba_d_state"],
        ssm_conv=c["mamba_d_conv"], ssm_chunk=c["mamba_chunk_size"],
        embedding_multiplier=c["embedding_multiplier"],
        residual_multiplier=c["residual_multiplier"],
        attention_multiplier=c["attention_multiplier"],
        logits_scaling=c["logits_scaling"], ffn_type="moe",
        expert_d_ff=c["intermediate_size"], shared_d_ff=c["shared_intermediate_size"],
        n_experts=c["n_experts"], router_top_k=c["num_experts_per_tok"],
        n_shared_experts=1, experts_held=c["num_local_experts"],
        expert_offset=c["expert_offset"],
    )
    args.update(more)
    return ModelConfig(**args)


def small_engine(c: dict, seed=3, **more) -> PagedEngine:
    args = dict(slots=3, block_size=4, prefill_chunk=8, prefill_buckets=(4, 8),
                prefix_cache=False)
    args.update(more)
    return PagedEngine(ref.weights_from_seed(seed, c), program_cfg(c), **args)


SHARES = {"held_all": (EXPERTS, 0), "held_share": (6, 6)}


def apart(ours, theirs) -> float:
    """The widest difference as a share of the reference's largest value:
    this model's logits are a few thousandths (an embedding drawn over 12, a
    head divided by 16), so an absolute tolerance would say little."""
    return float(jnp.max(jnp.abs(ours - theirs)) / jnp.max(jnp.abs(theirs)))


@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_reference(share):
    c = reference_cfg(*SHARES[share])
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 20))
    ours = forward(w, jnp.asarray(tokens), program_cfg(c))
    theirs = ref.forward_logits(w, tokens, c)
    assert apart(ours, theirs) < 1e-4


def test_init_params_has_the_reference_tree():
    c = reference_cfg(6, 6)
    ours = init_params(jax.random.PRNGKey(0), program_cfg(c))
    theirs = ref.weights_from_seed(3, c)
    shapes = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)  # noqa: E731
    assert shapes(ours) == shapes(theirs)
    assert ["ssm" in layer for layer in ours["layers"]] == [True, False, True]


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
    assert state["conv"].shape == (1, 3, 128 + 2 * 16)


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


@pytest.mark.parametrize("real", [0, 2, 5, 12])
def test_rows_that_are_not_valid_leave_the_state_alone(real):
    """A bucket of 12 rows with ``real`` of them real: state and conv rows
    are those after the real rows alone (none: as they came)."""
    _, pc, p, u = mixer_case(12)
    before = ssm.mamba2(u[:, :7] * 0.5, p, pc)[1]
    want_out, want = (
        ssm.mamba2(u[:, :real], p, pc, before) if real else (u[:, :0], before)
    )
    got_out, got = ssm.mamba2(u, p, pc, before, jnp.arange(12)[None] < real)
    assert float(jnp.max(jnp.abs(got_out[:, :real] - want_out), initial=0.0)) < 1e-5
    for name in ("ssm", "conv"):
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) < 1e-6
    # One row a sequence: an idle row's state and conv rows, bit for bit.
    idle = jnp.asarray([False])
    _, kept = ssm.mamba2_step(u[:, 0], p, pc, before, idle)
    assert all(bool(jnp.all(kept[name] == before[name])) for name in before)


def written_out_update(state, ids, x, dt, a, b, c, d_skip):
    """The step as `kernels/pallas/ssm.py` writes it out, over states that
    lie ``(slots + 1, heads, channels, state values)``: what both paths over
    the resting layout are held to."""
    x32, b, c = (v.astype(jnp.float32) for v in (x, b, c))
    if b.ndim == 3:  # each head its group's rows
        b, c = (jnp.repeat(v, x.shape[1] // v.shape[1], axis=1) for v in (b, c))
    else:
        b, c = (jnp.broadcast_to(v[:, None], (v.shape[0], x.shape[1], v.shape[1])) for v in (b, c))
    new = (
        state[ids] * jnp.exp(dt * a)[:, :, None, None]
        + (dt[:, :, None] * x32)[..., None] * b[:, :, None, :]
    )
    y = jnp.einsum("shpn,shn->shp", new, c, precision=jax.lax.Precision.HIGHEST)
    return y + d_skip[None, :, None] * x32, state.at[ids].set(new)


def update_case(heads, channels, n, groups=1, slots=6, tiles=0):
    """Rows in any order, two of them sent to trash with ``dt = 0``: five
    rows over 6 slots, or ``tiles`` whole tiles of 8 rows over 6 slots a
    tile (the first tile's five rows again in each)."""
    rng = np.random.default_rng(7)
    ids, live = np.asarray([3, slots, 0, slots, 5]), np.asarray([1, 0, 1, 0, 1])
    if tiles:  # slots 1, 2 and 4 of every tile's six stay unaddressed
        at = [t * slots + np.asarray([3, 0, 5, 3, 0, 5, 3, 0]) for t in range(tiles)]
        ids = np.concatenate([np.where(np.arange(8) < 3, a, tiles * slots) for a in at])
        live, slots = (ids < tiles * slots).astype(int), tiles * slots
    rows = len(ids)
    by_row = (rows, n) if groups == 1 else (rows, groups, n)
    state = jnp.asarray(rng.normal(size=(slots + 1, heads, channels, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(rows, heads, channels)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (rows, heads)), jnp.float32)
    dt = dt * jnp.asarray(live, jnp.float32)[:, None]  # trash rows: dt = 0
    a = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=by_row), jnp.float32) for _ in range(2))
    d_skip = jnp.asarray(rng.normal(size=heads), jnp.float32)
    return state, jnp.asarray(ids, jnp.int32), x, dt, a, b, c, d_skip


def updated_where_it_rests(path, state, ids, x, dt, a, b, c, d_skip):
    """`ssm_state_update` over the resting layout, its states handed back as
    they were given; the rows nobody addressed rest bit for bit."""
    groups = 1 if b.ndim == 2 else b.shape[1]
    rests = ssm_kernel.to_resting(state, groups)
    y, got = ssm_kernel.ssm_state_update(rests, ids, x, dt, a, b, c, d_skip, path=path)
    assert got.shape == rests.shape and got.dtype == jnp.float32
    for row in (1, 2, 4):
        assert bool(jnp.all(got[row] == rests[row]))
    return y, ssm_kernel.from_resting(got, x.shape[2])


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize(
    "heads, channels, n, k, tiles",
    [(16, 64, 16, 2, 0), (128, 64, 16, 2, 0), (128, 64, 16, 2, 2), (5, 64, 16, 1, 0),
     (16, 8, 128, 16, 0), (4, 128, 16, 1, 0)],
    ids=["two_heads_a_row", "two_blocks_of_rows", "two_blocks_two_tiles_of_rows", "odd_heads",
         "sixteen_heads_a_row", "a_head_fills_a_row"],
)
def test_the_kernel_updates_what_the_xla_update_updates(path, heads, channels, n, k, tiles):
    """`ssm_state_update` over the resting layout - the XLA stand-in, and
    the kernel in interpret mode - against the recurrence written out over
    ``(heads, channels, state values)``: rows in any order, two rows sent to
    trash, the rest of the states untouched bit for bit; five rows (one
    tile of them), or two tiles of 8 (a row is then one sublane of its
    tile's ``x``, ``b``, ``c`` and ``y``)."""
    case = update_case(heads, channels, n, tiles=tiles)
    slots = case[0].shape[0]
    assert ssm_kernel.heads_a_row(heads, channels) == k
    assert ssm_kernel.to_resting(case[0]).shape == (slots, heads // k, n, k * channels)
    want_y, want = written_out_update(*case)
    got_y, got = updated_where_it_rests(path, *case)
    assert float(jnp.max(jnp.abs(got_y - want_y))) < 1e-4
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(got[3] - case[0][3]))) > 0.1


@pytest.mark.parametrize(
    "heads, channels, groups, k",
    [(128, 64, 1, 2), (64, 64, 8, 2), (8, 16, 1, 8), (8, 16, 4, 1), (6, 64, 6, 1), (3, 256, 1, 1)],
    ids=["granite", "nemotron", "tiny_granite", "tiny_nemotron", "a_head_a_group", "wider_than_a_row"],
)
def test_to_and_from_the_resting_layout_is_a_bijection(heads, channels, groups, k):
    """Every element has one place: there and back is the identity both
    ways, ``k`` heads lie side by side along the lanes, and element ``[s, r,
    n, j * channels + p]`` is ``H[p, n]`` of head ``r * k + j``."""
    n = 8
    state = jnp.arange(2 * heads * channels * n, dtype=jnp.float32).reshape(2, heads, channels, n)
    rests = ssm_kernel.to_resting(state, groups)
    assert rests.shape == (2, heads // k, n, k * channels)
    assert bool(jnp.all(ssm_kernel.from_resting(rests, channels) == state))
    assert bool(jnp.all(ssm_kernel.to_resting(ssm_kernel.from_resting(rests, channels), groups) == rests))
    r, j, p, i = heads // k - 1, k - 1, channels - 2, 3
    assert float(rests[1, r, i, j * channels + p]) == float(state[1, r * k + j, p, i])


@pytest.mark.parametrize("start", [0, 8], ids=["an_admission", "a_carried_chunk"])
def test_a_chunks_end_state_rests_in_the_pool_as_the_scan_left_it(start):
    """`RecurrentRows.mixer` over one slot's chunk (5 real rows in a bucket
    of 8): the state the pool then holds for the slot, read back through
    `from_resting`, is `chunked_scan`'s end state (from zeros where the chunk
    starts at 0, else from what the slot held), and no other row moved."""
    from bpe_transformer_tpu.models.decode import chunk_cache, init_recurrent_pool

    _, pc, p, u = mixer_case(8)
    rng = np.random.default_rng(11)
    layer_pool = init_recurrent_pool(pc, 9, 4, 3)[0]
    layer_pool = {
        name: jnp.asarray(rng.normal(size=arr.shape), arr.dtype)
        for name, arr in layer_pool.items()
    }
    assert layer_pool["ssm"].shape == (3 + 1, 1, 16, 8 * 16)
    held = {
        "ssm": ssm_kernel.from_resting(layer_pool["ssm"][1:2], 16),
        "conv": layer_pool["conv"][1:2],
    }
    cache = chunk_cache(
        pc, {"blocks": jnp.zeros(16, jnp.int32), "slot": jnp.int32(1)},
        jnp.int32(start), jnp.int32(5), 8, block_size=4,
    )
    new_pool = []
    got_out = cache.mixer(u, p, layer_pool, new_pool)
    want_out, want = ssm.mamba2(
        u, p, pc, held if start else None, jnp.arange(8)[None] < 5
    )
    assert float(jnp.max(jnp.abs(got_out[:, :5] - want_out[:, :5]))) < 1e-5
    (now,) = new_pool
    assert now["ssm"].shape == layer_pool["ssm"].shape
    assert float(jnp.max(jnp.abs(ssm_kernel.from_resting(now["ssm"][1:2], 16) - want["ssm"]))) < 1e-6
    assert float(jnp.max(jnp.abs(now["conv"][1:2] - want["conv"]))) < 1e-6
    for row in (0, 2, 3):
        assert all(bool(jnp.all(now[name][row] == layer_pool[name][row])) for name in now)


# --------------------------------------------------------- the dense cache


def test_dense_cache_matches_reference():
    """Prefill (the chunked scan) then decode_step token by token (one step
    a sequence), the state in the cache's tree."""
    c = reference_cfg(6, 6)
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(1).integers(0, 64, (2, 24))
    full = ref.forward_logits(w, tokens, c)
    cache = init_kv_cache(pc, 2)
    assert [sorted(layer) for layer in cache[:2]] == [["conv", "ssm"], ["k", "v"]]
    logits, cache = prefill(w, jnp.asarray(tokens[:, :9]), pc, cache)
    worst = apart(logits, full[:, 8])
    step = jax.jit(functools.partial(decode_step, config=pc))
    for t in range(9, 24):
        logits, cache = step(w, jnp.asarray(tokens[:, t]), jnp.asarray(t), cache)
        worst = max(worst, apart(logits, full[:, t]))
    assert worst < 1e-4


# ------------------------------------------------- the paged engine's paths


@functools.partial(jax.jit, static_argnames=("config", "block_size"))
def _tick_logits(params, lm_head, pool, tables, tok, pos, active, *, config, block_size):
    cache = slot_cache(config, tables, pos, active, block_size=block_size)
    return paged_forward(params, tok[:, None], pool, cache, config, lm_head, row=0)[:2]


def forced_tick(eng, slot, token, position):
    """One teacher-forced tick of ``slot`` alone: its float32 logits."""
    tok = np.zeros(eng.n_slots, np.int32)
    pos = np.zeros(eng.n_slots, np.int32)
    active = np.zeros(eng.n_slots, bool)
    tok[slot], pos[slot], active[slot] = token, position, True
    logits, eng._pool = _tick_logits(
        eng._params, eng._lm_head, eng._pool, eng.cache.table_rows(), tok, pos, active,
        config=eng.config, block_size=eng.block_size,
    )
    return logits[slot]


def begin(eng, prompt, new=8):
    return eng.begin(prompt, max_new_tokens=new, temperature=0.0)


def served_logit_error(eng, c, tokens, plen, between=lambda t: None, seed=3):
    """Prefill ``tokens[:plen]`` in the engine's chunks, then teacher-forced
    ticks to the end (``between(t)`` runs before the tick at ``t``): the
    widest difference of a tick's logits from the reference's full forward
    (:func:`apart`), and the slot."""
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
    then 19 ticks (the XLA update, or the kernel in interpret mode) while
    ANOTHER slot is admitted and prefills its two chunks between them."""
    monkeypatch.setattr(
        ssm_kernel, "ssm_state_update",
        functools.partial(ssm_kernel.ssm_state_update, path=update),
    )
    c = reference_cfg(6, 6)
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
    assert worst < 1e-4
    assert not eng.pending_prefills() and eng.active_count == 2
    # The other slot, prefilled between this one's ticks, serves as alone.
    full = ref.forward_logits(ref.weights_from_seed(3, c), np.append(other, 7)[None], c)[0]
    assert apart(forced_tick(eng, 1 - slot, 7, 13), full[13]) < 1e-4
    # State rows a slot (and trash) beside K/V blocks of the attention layers.
    kinds = [sorted(entry) for entry in eng._pool]
    assert kinds == [["conv", "ssm"], ["k", "v"], ["conv", "ssm"]]
    # ... where they rest: 8 heads of 16 channels side by side along the lanes.
    assert eng._pool[0]["ssm"].shape == (3 + 1, 1, 16, 8 * 16)
    assert eng._pool[0]["ssm"].dtype == jnp.float32
    assert eng._pool[0]["conv"].shape == (3 + 1, 3, 160)


def test_a_slots_next_tenant_serves_as_a_fresh_engine_does():
    """No state leaks: slot 0 serves one request, is released, and serves a
    second exactly as an engine that never saw the first."""
    c = reference_cfg(6, 6)
    rng = np.random.default_rng(4)
    first, second = rng.integers(0, 64, 26), rng.integers(0, 64, 22)
    used, fresh = small_engine(c), small_engine(c)
    _, slot = served_logit_error(used, c, first, 10)
    used.release(slot)
    worst_used, again = served_logit_error(used, c, second, 6)
    worst_fresh, _ = served_logit_error(fresh, c, second, 6)
    assert again == slot and worst_used < 1e-4 and worst_used == worst_fresh
    assert used.gauges()["ssm_state_resets"] == 2


def test_a_tick_leaves_idle_and_prefilling_slots_states_bit_for_bit():
    c = reference_cfg(6, 6)
    eng = small_engine(c)
    rng = np.random.default_rng(6)
    done = begin(eng, rng.integers(0, 64, 5))            # slot 0: will tick
    while eng.prefill_step(done) is None:
        pass
    mid = begin(eng, rng.integers(0, 64, 14))            # slot 1: one chunk of two
    assert eng.prefill_step(mid) is None and eng.pending_prefills() == (mid,)
    before = jax.tree_util.tree_map(np.asarray, eng._pool)  # the pool is donated
    eng.tick()
    changed = [
        [bool(np.any(np.asarray(now[name][row]) != was[name][row])) for row in range(4)]
        for was, now in zip(before, eng._pool) if "ssm" in was for name in ("ssm", "conv")
    ]
    # Row 0 moved in every state-space layer; rows 1 (mid-prefill), 2 (idle)
    # and 3 (trash) did not.
    assert changed == [[True, False, False, False]] * 4
    # ... and the mid-prefill slot's second chunk carries on from its first.
    assert eng.prefill_step(mid) is not None


def test_engine_serves_greedy_tokens_the_reference_puts_first():
    """Three slots at ragged depths through admit/tick, the way the worker
    drives the engine; the counters equal a count by hand."""
    c = reference_cfg(6, 6)
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
    for prompt, seq in zip(prompts, seqs):
        assert len(seq) == len(prompt) + 12
        full = ref.forward_logits(w, np.asarray(seq)[None], c)[0]
        for i in range(len(prompt) - 1, len(seq) - 1):
            assert float(full[i].max() - full[i, seq[i + 1]]) < 1e-7
    gauges = eng.gauges()
    assert gauges["kv_blocks_free"] == gauges["kv_blocks_total"]
    ssm_layers, attn_layers = 2, 1
    # Chunks: 13 = 8 + 5 (buckets 8, 8), 5 (bucket 8), 9 = 8 + 1 (8, 4).
    assert gauges["ssm_chunk_tokens"] == ssm_layers * (13 + 5 + 9)
    assert gauges["ssm_chunk_rows"] == ssm_layers * (8 + 8 + 8 + 8 + 4)
    assert gauges["ssm_state_resets"] == 3
    assert gauges["ssm_tick_state_rows"] == ssm_layers * 3 * 11
    assert eng.last_tick_counts == {"ssm_tick_state_rows": ssm_layers * 3}
    assert gauges["ssm_state_bytes"] == ssm_layers * 4 * (8 * 16 * 16 + 3 * 160) * 4
    # Attention is counted for the attention layers alone, K/V bytes too.
    ticks = sum(sum(range(n + 1, n + 12)) for n in lengths)
    assert gauges["attn_pairs"] == gauges["attn_kv_positions"] == attn_layers * ticks
    assert gauges["kv_bytes_per_token"] == attn_layers * 2 * 2 * 16 * 4
    assert gauges["kv_pool_bytes"] == attn_layers * 2 * eng.allocator.num_blocks * 4 * 32 * 4
    assert gauges["moe_tokens_routed"] == LAYERS * (13 + 5 + 9 + 3 * 11)


def test_no_program_compiles_after_the_warm_up():
    eng = small_engine(reference_cfg(6, 6))
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
    """The share test: the routed parts of both shares of 6 experts plus the
    shared expert counted once equal the uncut reference layer."""
    uncut = reference_cfg(layers=1)
    w = ref.weights_from_seed(7, uncut)["layers"][0]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(1), (11, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(h, w, uncut, None)
        shared = ref._swiglu(h, *(w["shared"][k][0] for k in ("w1", "w2", "w3")), None)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3
    routed = jnp.zeros_like(h)
    for offset in (0, 6):
        share = {**w, **{k: w[k][offset:offset + 6] for k in ("w1", "w2", "w3")}}
        out, counts = dropless_moe(h, share, program_cfg(reference_cfg(6, offset, layers=1)))
        routed = routed + out - shared
        assert int(counts[0]) == 11 and 0 < int(counts[1]) < TOP * 11
    assert float(jnp.max(jnp.abs(routed + shared - want))) < 1e-5


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_moves_the_output(name):
    """Dropped (set to what the plain block has), the logits differ from
    the reference's by far more than rounding."""
    c = reference_cfg(6, 6)
    w = ref.weights_from_seed(3, c)
    tokens = np.random.default_rng(0).integers(0, 64, (1, 20))
    theirs = ref.forward_logits(w, tokens, c)
    plain = {"attention_multiplier": None}.get(name, 1.0)
    ours = forward(w, jnp.asarray(tokens), program_cfg(c, **{name: plain}))
    assert apart(ours, theirs) > 1e-2


# ------------------------------------------------- the reference's own parts


def test_rows_of_decided_positions_equal_the_full_forward():
    """`followed_routings` restarts the recurrence at a kept state: a
    position's first row (the reference's own routing) is the full
    forward's."""
    c = ref._Frozen(reference_cfg(6, 6))
    w = ref.weights_from_seed(3, c)
    ids = np.random.default_rng(8).integers(0, 64, 48).astype(np.int32)
    memory = []
    states = ref.hidden_states(w, ids, c, memory=memory, keep=(19, 45))
    rows, origin = ref.followed_routings(w, c, ids, memory, 19, 45)
    first = np.unique(origin, return_index=True)[1]
    assert float(np.max(np.abs(rows[first] - np.asarray(states[19:45])))) < 1e-5
    assert memory[0]["first"] == 16 and memory[0]["stretch"]["states"].shape[1:] == (8, 16, 16)


def test_served_gaps_of_the_references_own_greedy_tokens(monkeypatch):
    monkeypatch.setattr(
        ref, "weights_from_seed", lambda seed, cfg, dtype=None: ref.init_weights(seed, cfg)
    )
    c = reference_cfg(6, 6)
    w = ref.init_weights(11, c)
    rng = np.random.default_rng(9)
    sequences = []
    for n in (7,):
        seq = list(rng.integers(0, 64, n))
        for _ in range(6):
            padded = np.asarray(seq + [0] * (32 - len(seq)))[None]
            seq.append(int(jnp.argmax(ref.forward_logits(w, padded, c)[0, len(seq) - 1])))
        sequences.append((seq[:n], seq[n:]))
    assert max(ref.served_gaps(11, c, sequences)) < 1e-7
    wrong = [(prompt, [(t + 1) % 64 for t in served]) for prompt, served in sequences]
    assert min(ref.served_gaps(11, c, wrong)) > 1e-5
    assert all(g >= 0 for g in ref.served_gaps(11, c, sequences, control=True))


# ---------------------------------------------------------------- refusals


@pytest.mark.parametrize(
    "more",
    [dict(prefix_cache=True), dict(kv_dtype="int8"), dict(weight_dtype="int8"),
     dict(fused_sampling=True)],
    ids=["prefix_cache", "kv_int8", "weight_int8", "fused_sampling"],
)
def test_engine_refuses_at_construction(more):
    with pytest.raises(ValueError, match="recurrent state|weight_dtype"):
        small_engine(reference_cfg(6, 6), **more)


@pytest.mark.parametrize("what", ["extend_blocks", "export_slot", "import_slot", "rewind"])
def test_engine_refuses_scratch_migration_and_rewind(what):
    eng = small_engine(reference_cfg(6, 6))
    slot = begin(eng, np.arange(6))
    while eng.prefill_step(slot) is None:
        pass
    call = {
        "extend_blocks": lambda: eng.extend_blocks(slot, 8),
        "export_slot": lambda: eng.export_slot(slot),
        "import_slot": lambda: eng.validate_import_meta({"format": 1}),
        "rewind": lambda: eng.rewind(slot, 3),
    }[what]
    with pytest.raises(NotImplementedError, match="recurrent state"):
        call()


@pytest.mark.parametrize(
    "more",
    [dict(paged=False), dict(speculate_k=2), dict(role="prefill"), dict(role="decode")],
    ids=["dense_engine", "speculation", "prefill_role", "decode_role"],
)
def test_serving_engine_refuses(more):
    from bpe_transformer_tpu.serving.server import ServingEngine

    c = reference_cfg(6, 6)
    args = dict(paged=True, block_size=4, prefill_chunk=8, prefix_cache=False)
    args.update(more)
    with pytest.raises(ValueError, match="state-space|recurrent state"):
        ServingEngine(ref.weights_from_seed(3, c), program_cfg(c), **args)


def test_spec_and_slot_pool_engines_and_padded_prefill_refuse():
    from bpe_transformer_tpu.serving.engine import SlotPoolEngine
    from bpe_transformer_tpu.serving.spec.draft import DraftSpec
    from bpe_transformer_tpu.serving.spec.engine import SpecEngine

    c = reference_cfg(6, 6)
    pc, w = program_cfg(c), ref.weights_from_seed(3, c)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        SpecEngine(w, pc, draft=DraftSpec(), speculate_k=2, block_size=4,
                   prefill_chunk=8, prefix_cache=False)
    with pytest.raises(ValueError, match="state-space"):
        SlotPoolEngine(w, pc)
    with pytest.raises(NotImplementedError, match="several rows a slot"):
        slot_cache(pc, jnp.zeros((3, 16), jnp.int32), jnp.zeros((3, 2), jnp.int32),
                   block_size=4)
    with pytest.raises(NotImplementedError, match="padded prefill"):
        prefill(w, jnp.zeros((1, 8), jnp.int32), pc, init_kv_cache(pc, 1),
                last_pos=jnp.asarray([4]))


def test_scan_layers_and_training_are_refused():
    with pytest.raises(ValueError, match="scan_layers"):
        program_cfg(reference_cfg(6, 6), scan_layers=True)
    from bpe_transformer_tpu.training.train_step import make_loss_fn

    with pytest.raises(ValueError, match="training is not supported"):
        make_loss_fn(program_cfg(reference_cfg(6, 6)))


@pytest.mark.parametrize(
    "change, message",
    [
        (dict(sliding_window=8), "contradict"),
        (dict(parallel_block=True), "contradict"),
        (dict(norm_type="layernorm"), "contradict"),
        (dict(attn_layer_offset=3), "must lie in"),
        (dict(ssm_state=0), "need positive"),
        (dict(attn_layer_period=0, attn_layer_offset=0), "hybrid block's"),
        (dict(shared_d_ff=32, n_shared_experts=0), "shared_d_ff"),
    ],
    ids=["window", "parallel", "layernorm", "offset", "no_state", "ssm_alone", "shared_width"],
)
def test_config_refuses_contradictions(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(program_cfg(reference_cfg(6, 6)), **change)


def test_config_properties_and_defaults():
    cfg = program_cfg(reference_cfg(6, 6))
    assert [cfg.layer_is_ssm(i) for i in range(3)] == [True, False, True]
    assert (cfg.ssm_layers, cfg.ssm_inner, cfg.ssm_conv_channels) == (2, 128, 160)
    assert (cfg.attention_scale, cfg.shared_ff, cfg.moe_d_ff) == (4.0, 32, 16)
    assert cfg.hybrid_block and cfg.dropless_block and cfg.local_experts == 6
    plain = TS_TEST_CONFIG
    assert not plain.hybrid_block and not plain.dropless_block and plain.ssm_layers == 0
    assert not any(plain.layer_is_ssm(i) for i in range(plain.num_layers))
    assert plain.attention_scale == plain.d_head ** -0.5 and plain.shared_ff == plain.d_ff
    # 64, PR 40's four, PR 42's three, PR 46's four (attention layers by
    # kind), PR 49's seven (the norms' epsilon, six of stretched positions)
    assert len(dataclasses.fields(ModelConfig)) == 82
    for field, value in [("ssm_heads", 4), ("residual_multiplier", 0.5), ("logits_scaling", 2.0)]:
        with pytest.raises(ValueError, match="hybrid block's"):
            dataclasses.replace(plain, **{field: value})
