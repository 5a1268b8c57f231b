"""The paged engine one launch ahead (ISSUE 37): the decode carry lives on
the device, `PagedEngine.launch` queues tick n+1 before `collect` reads tick
n, and a prompt's final chunk leaves its first token unread behind the next
launch.  Over all four cache kinds at tiny sizes on the CPU: the same
requests give every request the same tokens in either order, a row a launch
computed for a tenant that has since left reaches no one, and neither half
that queues a program reads anything from the device."""

import contextlib
import dataclasses

import jax
import numpy as np
import pytest

from bpe_transformer_tpu.models.config import TS_TEST_CONFIG
from bpe_transformer_tpu.models.transformer import init_params
from bpe_transformer_tpu.serving.kvpool.host_cache import (
    HostGroupedPages,
    HostRecurrentRows,
)
from bpe_transformer_tpu.serving.kvpool.paged_engine import (
    LAUNCH_PARTS,
    PagedEngine,
)
from tests import test_cohere2moe as cohere
from tests import test_granitehybrid as granite
from tests import test_longcatflash as longcat

DENSE = dataclasses.replace(TS_TEST_CONFIG, vocab_size=64, context_length=32)


def dense_engine(**more) -> PagedEngine:
    args = dict(slots=2, block_size=4, prefill_chunk=8, prefill_buckets=(4, 8))
    args.update(more)
    return PagedEngine(init_params(jax.random.PRNGKey(0), DENSE), DENSE, **args)


#: One engine of each cache kind: `DenseRows` and `LatentRows` with the radix
#: prefix cache, `GroupedPages` with a window (6) that the requests outgrow,
#: `RecurrentRows` with a state row a slot.  Two slots, so slots are reused.
KINDS = {
    "dense": lambda: dense_engine(),
    "grouped": lambda: cohere.small_engine(
        cohere.reference_cfg(2, 2, layers=4), slots=2, block_size=2,
        prefill_chunk=8, prefill_buckets=(4, 8),
    ),
    "latent": lambda: longcat.small_engine(longcat.reference_cfg(4, 4), slots=2),
    "recurrent": lambda: granite.small_engine(granite.reference_cfg(6, 6), slots=2),
}


def requests_for(stop_id=None) -> list[dict]:
    """Six requests for two slots (vocabulary 64, context >= 32): greedy and
    sampled rows, a prompt of two chunks, one that shares two full blocks
    with an earlier one (a radix hit where the kind has the cache), a long
    decode that outgrows a window of 6, a finish by ``stop_id`` and one at
    the first token."""
    shared = [7, 8, 9, 10, 11, 12, 13, 14]
    return [
        dict(prompt_ids=shared + [3], max_new_tokens=6, temperature=0.0),
        dict(prompt_ids=[20, 21, 22, 23, 24], max_new_tokens=12,
             temperature=1.0, top_k=8, seed=1, stop_id=stop_id),
        dict(prompt_ids=shared + [30, 31, 32], max_new_tokens=5,
             temperature=0.9, top_p=0.9, seed=2),
        dict(prompt_ids=list(range(40, 51)), max_new_tokens=9, temperature=0.0),
        dict(prompt_ids=[5, 6, 4], max_new_tokens=12, temperature=1.0, seed=4),
        dict(prompt_ids=[50, 51, 52, 53, 54, 55], max_new_tokens=1,
             temperature=1.0, seed=5),
    ]


def drive(engine, requests, ahead: bool, cancel=None):
    """The requests through ``engine`` the way the serving worker drives it:
    cancellations, admissions while a slot is free, a chunk of every pending
    prompt, a tick.  ``ahead`` runs one launch ahead (`launch_chunk`,
    `launch`, then `collect` for all but the newest launch); otherwise every
    program is read at once (`prefill_step`, `tick`).  ``cancel`` maps a
    request to the number of tokens after which the host cancels it.
    Returns each request's tokens and finish reason."""
    cancel = cancel or {}
    out = {i: [] for i in range(len(requests))}
    reason, slot_of, waiting = {}, {}, list(range(len(requests)))

    def take(events):
        for event in events:
            i = slot_of[event.slot]  # a stale row would name the wrong one
            out[i].append(event.token)
            if event.finished:
                reason[i] = event.finished
                del slot_of[event.slot]

    while waiting or slot_of or engine.unread:
        for slot, i in list(slot_of.items()):
            if i in cancel and len(out[i]) >= cancel[i]:
                engine.release(slot)
                reason[i] = "cancelled"
                del slot_of[slot]
        while waiting and engine.free_slots:
            i = waiting.pop(0)
            slot_of[engine.begin(**requests[i])] = i
        for slot in engine.pending_prefills():
            if ahead:
                engine.launch_chunk(slot)
            else:
                event = engine.prefill_step(slot)
                take([] if event is None else [event])
        if ahead:
            launched = engine.launch()
            while engine.unread > int(launched and engine.active_count > 0):
                take(engine.collect())
        else:
            take(engine.tick())
    return out, reason


@pytest.mark.parametrize("kind", KINDS)
def test_one_launch_ahead_gives_every_request_the_same_tokens(kind):
    probe = KINDS[kind]()
    alone = {}
    for i, request in enumerate(requests_for()):
        event = probe.admit(**request)
        alone[i] = [event.token]
        while not event.finished:
            (event,) = probe.tick()
            alone[i].append(event.token)
    # A token the sampled request draws becomes its stop id: the finish is
    # found when the launch is read, one launch after the slot ran on.
    stop_id = alone[1][3]
    requests = requests_for(stop_id)
    alone[1] = alone[1][: alone[1].index(stop_id) + 1]
    cancel = {3: 3}

    sync, ahead = KINDS[kind](), KINDS[kind]()
    want, want_reason = drive(sync, requests, ahead=False, cancel=cancel)
    got, got_reason = drive(ahead, requests, ahead=True, cancel=cancel)

    assert got_reason == want_reason == {
        0: "length", 1: "stop", 2: "length", 3: "cancelled", 4: "length",
        5: "length",
    }
    for i in range(len(requests)):
        if i in cancel:  # one launch ahead the host may see one token more
            assert got[i][:3] == want[i] == alone[i][:3]
        else:
            # Bit for bit, and what a fresh engine gives the request alone:
            # no next tenant of a slot received a stale token.
            assert got[i] == want[i] == alone[i], i
    # The mechanism engaged: launches ran ahead, the stop and the
    # cancellation each left a row to no one, and no one cut in.
    assert ahead.ticks_overlapped > 0 and ahead.tick_stale_rows >= 2
    assert ahead.carry_flushes == 0
    assert sync.ticks_overlapped == sync.tick_stale_rows == 0
    assert ahead.tokens_emitted == sum(len(t) for t in got.values())
    # The host's own arithmetic is the device's, and nothing is held.
    assert np.array_equal(ahead.read_carry()[1], ahead._positions)
    assert ahead.carry_flushes == 0  # nothing was unread
    for engine in (sync, ahead):
        assert engine.free_slots == engine.n_slots and not engine.unread
        assert not engine._active.any()
    gauges = ahead.gauges()
    if ahead.prefix_cache is not None:
        assert gauges["prefix_cache_hits"] >= 8  # the shared two blocks
    if isinstance(ahead.cache, HostGroupedPages):
        # Window blocks were recycled at a launch while the launch before,
        # which reads them, was unread (a stale row's launch recycles too:
        # a few more than the other order).
        assert gauges["kv_window_blocks_recycled"] >= (
            sync.gauges()["kv_window_blocks_recycled"]
        ) > 0
    if isinstance(ahead.cache, HostRecurrentRows):
        assert gauges["ssm_state_resets"] == len(requests)


def launch_seconds(engine, program: str) -> dict:
    """``{part: seconds}`` of a program's launches so far."""
    gauges = engine.gauges()
    return {
        part: gauges[f"launch_{program}_{part}_s"]
        for part in LAUNCH_PARTS[program][1]
    }


@pytest.mark.parametrize("kind", KINDS)
def test_every_cache_kind_times_its_launches_in_the_same_parts(kind):
    """Whatever the cache kind, a tick's dispatch is timed in three parts
    and a chunk's in four under the same keys of `gauges()`; a tick's three
    add up to its dispatch."""
    engine = KINDS[kind]()
    timed = {
        f"launch_{program}_{part}_s"
        for program, (_, parts) in LAUNCH_PARTS.items() for part in parts
    }
    gauges = engine.gauges()
    assert {key for key in gauges if key.startswith("launch_")} == timed
    assert gauges["chunk_launches"] == 0 and not any(gauges[k] for k in timed)

    slot = engine.begin(
        prompt_ids=list(range(40, 51)), max_new_tokens=5, temperature=0.0
    )
    while slot in engine.pending_prefills():  # 8 tokens, then 3
        engine.launch_chunk(slot)
    assert engine.gauges()["chunk_launches"] == 2
    between = []
    for _ in range(4):
        was = launch_seconds(engine, "tick")
        assert engine.launch()
        now = launch_seconds(engine, "tick")
        parts_s = sum(now[part] - was[part] for part in now)
        dispatch_s = engine.last_tick_s[0]
        assert parts_s <= dispatch_s
        between.append(dispatch_s - parts_s - 0.05 * dispatch_s)
    # Between the parts: two clock reads a part (the host may have had the
    # thread off its core in one of the four).
    assert sorted(between)[-2] <= 250e-6
    engine.flush()
    gauges = engine.gauges()
    assert all(gauges[key] > 0 for key in timed)


@pytest.mark.parametrize("kind", KINDS)
def test_every_cache_kind_hands_out_the_same_gauges(kind):
    """`gauges()` has the same keys over every cache kind, but for the two
    counts of the latent tick's shared pass and the two of a latent chunk's
    pairs, which the latent kind alone carries (0 here: on the CPU its
    ticks take gathered rows, and nothing has been prefilled)."""
    gauges = KINDS[kind]().gauges()
    latent_alone = {
        "attn_shared_kv_positions", "attn_shared_slots", "chunk_attn_pairs",
        "chunk_attn_kernel_pairs",
    }
    assert set(gauges) - latent_alone == set(dense_engine().gauges())
    assert (set(gauges) & latent_alone == latent_alone) == (kind == "latent")
    assert not any(gauges.get(key) for key in latent_alone)


def test_tick_is_launch_then_collect():
    """`tick()` is `launch()` then `collect()`: two engines, one driven by
    the composition and one by its halves, step for step."""
    whole, halves = dense_engine(), dense_engine()
    requests = requests_for()[:2]
    for engine in (whole, halves):
        for request in requests:
            assert not engine.admit(**request).finished
    for _ in range(4):
        want = whole.tick()
        assert halves.launch() and halves.unread == 1
        assert halves.collect() == want and halves.unread == 0
    assert whole.ticks == halves.ticks == 4
    assert whole.ticks_overlapped == halves.ticks_overlapped == 0
    assert halves.collect() == [] and halves.carry_flushes == 0


# ------------------------------------------------ no read before the launch


@contextlib.contextmanager
def device_reads(monkeypatch):
    """Every read of a device array by the host inside the block, as a
    list: the transfer guard where the backend honours it (the TPU), and an
    instrumented read everywhere (on the CPU a device array is host memory
    and the guard lets everything through)."""
    from jax._src import array as jax_array

    reads = []
    real_value = jax_array.ArrayImpl._value
    real_asarray, real_array = np.asarray, np.array

    def value(self):
        reads.append(("value", self.shape))
        return real_value.fget(self)

    def asarray(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            reads.append(("asarray", a.shape))
        return real_asarray(a, *args, **kwargs)

    def array(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            reads.append(("array", a.shape))
        return real_array(a, *args, **kwargs)

    with monkeypatch.context() as patch:
        patch.setattr(jax_array.ArrayImpl, "_value", property(value))
        patch.setattr(np, "asarray", asarray)
        patch.setattr(np, "array", array)
        with jax.transfer_guard_device_to_host("disallow"):
            yield reads


def test_the_halves_that_queue_a_program_read_nothing(monkeypatch):
    """On the worker's path a final chunk (`launch_chunk`) and the `launch`
    half of a tick perform no device-to-host read; `collect` is where the
    host reads, and only the tokens."""
    engine = dense_engine()
    first, second = requests_for()[:2]
    engine.admit(**first)  # both programs have run once: nothing compiles
    engine.tick()
    slot = engine.begin(**second)
    with device_reads(monkeypatch) as reads:
        assert engine.launch_chunk(slot)  # five tokens: the final chunk
        assert engine.launch() and engine.launch()
    assert reads == [] and engine.unread == 3
    with device_reads(monkeypatch) as reads:
        (event,) = engine.collect()
    assert event.slot == slot and reads == [("asarray", ())]
    with device_reads(monkeypatch) as reads:
        assert len(engine.collect()) == 2
    assert reads == [("asarray", (2,))]


def test_counters_over_a_steady_run(monkeypatch):
    """Over a steady run one launch ahead every launch but the first finds
    the one before it unread; a reader of the carry (here `export_slot`)
    flushes, and the launch after it finds nothing unread."""
    engine = dense_engine(prefix_cache=False)
    slots = [engine.begin(prompt_ids=[1 + s, 2, 3], max_new_tokens=20,
                          temperature=1.0, seed=s) for s in range(2)]
    for slot in slots:
        engine.launch_chunk(slot)
    tokens = {slot: [] for slot in slots}

    def step():
        launched = engine.launch()
        while engine.unread > int(launched):
            for event in engine.collect():
                tokens[event.slot].append(event.token)

    for _ in range(6):
        step()
    assert engine.ticks == 6 and engine.carry_flushes == 0
    assert engine.ticks_overlapped == engine.ticks - 1 - engine.carry_flushes
    payload = engine.export_slot(slots[0])  # reads the carry: a flush
    assert engine.carry_flushes == 1 and engine.unread == 1  # held for collect
    for _ in range(4):
        step()
    assert engine.ticks == 10
    assert engine.ticks_overlapped == engine.ticks - 1 - engine.carry_flushes
    # What the flush read was not lost: every launch gave each slot a token,
    # and the payload says where the slot stood after the sixth.
    assert [len(t) for t in tokens.values()] == [1 + 9, 1 + 9]
    meta = payload["meta"]
    assert meta["generated"] == 1 + 6 and meta["position"] == 3 + 6
    assert meta["token"] == tokens[slots[0]][6]
    assert np.array_equal(engine.read_carry()[1], engine._positions)
