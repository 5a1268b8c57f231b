"""One general traffic generator, driven by a workload file's ``traffic``
and ``data`` parameters.  A later PR adds a mix by adding a data file.

Serving: the *schedule* belongs to the cell, the contents to the seed.  The
set of request sizes (and, in an open loop, of arrival gaps) is drawn once
from ``sizes_seed`` in the workload file - ``n_sizes`` of them, a few dozen
- and offered block after block, each block the whole set in an order of
its own, also from ``sizes_seed``.  So every ``--seed`` offers the same
sizes at the same times, and every stretch a few blocks long the same work;
the run's seed draws the token ids and the per-request sampling seeds (and
the weights).  A cell whose runs replayed different schedules spread by 6%
in tokens/s and 30% in the first token's tail (my chip run, PR 24): the
seed was changing the work.  The
arrival and length draws follow ``benchmarks/bench_serving.py``, which
PERF.md lists for a later PR to delete.

Training: a token array with Zipf unigrams and a bigram habit, as
``chip_smoke.py:phase_data`` builds with words, so the loss can fall.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request of the plan.  ``due_s`` is None in a closed loop."""

    index: int
    prompt_ids: tuple
    max_new_tokens: int
    temperature: float
    top_k: int | None
    seed: int
    due_s: float | None
    greedy: bool


def _draw_lengths(rng, spec: dict, n: int) -> np.ndarray:
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "loguniform":
        return np.exp(rng.uniform(math.log(lo), math.log(hi + 1), n)).astype(int).clip(lo, hi)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "fixed":
        return np.full(n, lo)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def request_sizes(traffic: dict) -> np.ndarray:
    """The fixed set of ``(prompt_len, output_len)`` every seed offers."""
    rng = np.random.default_rng(traffic["sizes_seed"])
    n = traffic["n_sizes"]
    prompts = _draw_lengths(rng, traffic["prompt_len"], n)
    outputs = _draw_lengths(rng, traffic["output_len"], n)
    cap = traffic.get("max_total")
    if cap:
        outputs = np.minimum(outputs, cap - prompts).clip(1)
    return np.stack([prompts, outputs], axis=1)


def _blocks(rng, block: int, n: int) -> np.ndarray:
    """``n`` indices into a set of ``block``: permutation after permutation."""
    reps = -(-n // block)
    return np.concatenate([rng.permutation(block) for _ in range(reps)])[:n]


def plan_requests(traffic: dict, vocab_size: int, seed: int, horizon_s: float) -> list:
    """The run's requests in offer order.  Open loop: enough to cover
    ``horizon_s`` at the fixed rate; closed loop: ``closed_plan`` of them
    (the clients take the next one as they finish; it cycles after that)."""
    sizes = request_sizes(traffic)
    rng = np.random.default_rng([seed, 1])
    schedule = np.random.default_rng(traffic["sizes_seed"] + 2)
    arrival = traffic["arrival"]
    if arrival["kind"] == "poisson":
        n = max(8, int(math.ceil(arrival["rate"] * horizon_s * 1.25)))
        # The same gaps at the same places for every seed, block after block.
        gaps = np.random.default_rng(traffic["sizes_seed"] + 1).exponential(
            1.0 / arrival["rate"], len(sizes)
        )
        gaps *= 1.0 / (arrival["rate"] * gaps.mean())  # the set's rate is the cell's
        due = np.cumsum(gaps[_blocks(schedule, len(gaps), n)]).tolist()
    elif arrival["kind"] == "closed":
        n = traffic.get("closed_plan", 4096)
        due = [None] * n
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    order = _blocks(schedule, len(sizes), n)
    sampling = traffic["sampling"]
    greedy_every = traffic.get("greedy_every", 0)
    prefix = traffic.get("shared_prefix", {"share": 0.0, "len": 0})
    shared = rng.integers(0, vocab_size, prefix["len"]).tolist()
    plan = []
    for i in range(n):
        prompt_len, out_len = (int(v) for v in sizes[order[i]])
        ids = rng.integers(0, vocab_size, prompt_len).tolist()
        if prefix["len"] and rng.random() < prefix["share"]:
            ids = (shared + ids)[:prompt_len]
        greedy = bool(greedy_every) and i % greedy_every == greedy_every - 1
        plan.append(
            Planned(
                index=i,
                prompt_ids=tuple(ids),
                max_new_tokens=out_len,
                temperature=0.0 if greedy else sampling["temperature"],
                top_k=None if greedy else sampling.get("top_k"),
                seed=int(rng.integers(0, 2**31 - 1)),
                due_s=due[i],
                greedy=greedy,
            )
        )
    return plan


def training_tokens(data: dict, vocab_size: int, seed: int) -> np.ndarray:
    """``n_tokens`` ids: Zipf-weighted unigrams, and after every token whose
    id divides by three, a fixed successor — structure a model picks up in a
    few dozen steps."""
    rng = np.random.default_rng([seed, 2])
    n = data["n_tokens"]
    weights = 1.0 / (np.arange(vocab_size) + 1.0) ** data.get("zipf_exponent", 1.0)
    # Which id gets which rank changes with the seed; the law does not.
    ids = rng.permutation(vocab_size)
    tokens = ids[
        np.searchsorted(np.cumsum(weights / weights.sum()), rng.random(n)).clip(0, vocab_size - 1)
    ]
    habit = tokens[:-1] % 3 == 0
    tokens[1:][habit] = (tokens[:-1][habit] * 7 + 1) % vocab_size
    return tokens.astype(np.uint16 if vocab_size <= 65536 else np.int32)
