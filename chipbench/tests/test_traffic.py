"""The one traffic generator: same seed same requests, the stated ranges,
the same set of sizes for every seed, a Poisson schedule at the fixed rate."""

import json

import numpy as np

from chipbench import traffic
from chipbench.tests.tiny import BENCH


def mix(name):
    return json.loads((BENCH / "workloads" / f"{name}.json").read_text())["traffic"]


def test_same_seed_same_requests_other_seed_same_sizes():
    spec = mix("small.serve.decode-heavy")
    a = traffic.plan_requests(spec, 32000, 7, 10.0)
    b = traffic.plan_requests(spec, 32000, 7, 10.0)
    c = traffic.plan_requests(spec, 32000, 8, 10.0)
    assert a == b and a != c
    sizes = lambda plan: [(len(p.prompt_ids), p.max_new_tokens) for p in plan]
    # The schedule is the cell's: the same sizes in the same order for every
    # seed; the contents are the seed's.
    assert sizes(a) == sizes(c)
    assert a[0].prompt_ids != c[0].prompt_ids and a[0].seed != c[0].seed
    # Any stretch of a few blocks offers the same work.
    block = spec["n_sizes"]
    assert sorted(sizes(a[block:3 * block])) == sorted(sizes(a[5 * block:7 * block]))


def test_length_ranges_and_greedy_share():
    spec = mix("small.serve.decode-heavy")
    plan = traffic.plan_requests(spec, 32000, 1, 10.0)
    prompts = [len(p.prompt_ids) for p in plan]
    outs = [p.max_new_tokens for p in plan]
    assert min(prompts) >= 32 and max(prompts) <= 256
    assert min(outs) >= 64 and max(outs) <= 512
    assert max(a + b for a, b in zip(prompts, outs)) <= spec["max_total"]
    assert all(p.due_s is None for p in plan)
    greedy = [p for p in plan if p.greedy]
    assert len(greedy) == len(plan) // spec["greedy_every"]
    assert all(p.temperature == 0.0 and p.top_k is None for p in greedy)
    assert all(0 <= t < 32000 for p in plan[:50] for t in p.prompt_ids)


def test_poisson_schedule_has_the_cells_rate():
    spec = mix("medium.serve.prefill-heavy")
    rate = spec["arrival"]["rate"]
    plan = traffic.plan_requests(spec, 32000, 3, 400.0)
    due = np.array([p.due_s for p in plan])
    assert np.all(np.diff(due) > 0)
    assert abs(len(due) / due[-1] - rate) / rate < 0.02
    # Every seed offers the same arrivals.
    other = np.array([p.due_s for p in traffic.plan_requests(spec, 32000, 4, 400.0)])
    assert np.allclose(due, other)


def test_training_tokens_have_structure():
    data = {"n_tokens": 50000, "zipf_exponent": 1.0}
    a = traffic.training_tokens(data, 32000, 5)
    assert np.array_equal(a, traffic.training_tokens(data, 32000, 5))
    assert a.dtype == np.uint16 and a.max() < 32000
    habit = a[:-1] % 3 == 0
    follows = a[1:][habit] == (a[:-1][habit].astype(np.int64) * 7 + 1) % 32000
    assert follows.mean() > 0.6
