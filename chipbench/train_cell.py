"""A training cell: ``training.loop.train()`` called the way ``bpe-tpu
train`` calls it, clocked from outside.

``train()`` is one function, so the harness watches it through the two
doors it has: ``log_fn`` (called at every device sync of the loop, which is
where the window opens, is clocked and closes) and ``fault_injector`` (the
chaos hooks, called at every step boundary; the probe keeps them off and
uses the call to read the loop's live state for the check).  The state
itself is read from the loop's frame - its locals ``params``, ``opt_state``,
``metrics``, ``hx``, ``hy``, ``step_fn``, ``record`` - because ``train()``
hands it out no other way; a rename there breaks this file loudly (KeyError)
and is listed in PERF.md as what a ``tracing`` PR should replace by a hook.

The window is stopped by the loop's own cooperative stop: SIGTERM to this
process at the closing sync (no checkpoint directory, so nothing is saved).

``correct`` follows the one compiled step with its state - the object the
window then runs - through its first three optimizer steps and compares
with the float32 reference (``reference.reference_train``) after the state
is freed: each step's loss, the first gradient as the optimizer gets it
(from AdamW's first moment after one step) and the parameters' change after
the three, both by the worst leaf.
"""

from __future__ import annotations

import gc
import math
import re
import signal
import sys
import time

import numpy as np

STEP_LINE = re.compile(r"^step\s+\d+\s+loss")
CHECK_STEPS = 3


def worst_leaf_gap(got: np.ndarray, want: np.ndarray) -> tuple[float, int]:
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    floor = float(np.median(want))
    gaps = np.abs(got - want) / np.maximum(want, floor)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), worst


def compare(losses, first_grad, change, ref: dict, limits: dict) -> list:
    """``[(number, value, limit)]``: what is compared, beside its limit."""
    out = [
        (f"loss_step{i + 1}_abs_gap", abs(got - want), limits["loss_abs_gap"])
        for i, (got, want) in enumerate(zip(losses, ref["losses"]))
    ]
    gap, _ = worst_leaf_gap(np.asarray(first_grad), ref["first_grad_leaf_norms"])
    out.append(("first_grad_worst_leaf_rel_gap", gap, limits["first_grad_rel_gap"]))
    gap, _ = worst_leaf_gap(np.asarray(change), ref["change_leaf_norms"])
    out.append(("param_change_worst_leaf_rel_gap", gap, limits["param_change_rel_gap"]))
    return out


class Probe:
    """``fault_injector`` and ``log_fn`` of one ``train()`` call."""

    active = False  # the chaos hooks stay off: poison_params is never called

    def __init__(self, env: dict, hp: dict, log_every: int):
        self.env = env
        self.hp = hp
        self.trace_seconds = env["workload"].get("trace_seconds", 4.0)
        self.open_at_step = -(-CHECK_STEPS // log_every) * log_every  # first sync past the check
        self.batches, self.losses = [], []
        self.first_grad = self.change = self.step_fn = self.abstract = None
        self.syncs = []  # (harness clock, step, loss, the loop's step_wall_s)
        self.t_open = self.step_open = self.t_close = self.step_close = None

    # -- fault_injector ---------------------------------------------------

    def on_batch_read(self, it: int) -> None:
        pass

    def at_step(self, it: int) -> None:
        """Top of loop iteration ``it``: step ``it`` has been dispatched, its
        outputs are the loop's ``params``/``opt_state``/``metrics``.  All
        that is read here is enqueued behind it; nothing syncs."""
        if not 1 <= it <= CHECK_STEPS:
            return
        import jax
        import jax.numpy as jnp

        live = sys._getframe(1).f_locals
        self.env["phase"](f"step_{it}_dispatched")
        self.batches.append((np.array(live["hx"]), np.array(live["hy"])))
        self.losses.append(live["metrics"]["loss"])
        norms = lambda tree: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
             for a in jax.tree_util.tree_leaves(tree)]
        )
        if it == 1:
            # m_1 = (1 - beta1) * g_1, g_1 as the optimizer got it.
            b1 = self.hp["betas"][0]
            self.first_grad = jax.jit(lambda m: norms(m) / (1.0 - b1))(
                live["opt_state"].m
            )
            self.step_fn = live["step_fn"]
            self.abstract = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                (live["params"], live["opt_state"], live["x"], live["y"]),
            )
        if it == CHECK_STEPS:
            # Against the seed's weights drawn again leaf by leaf inside the
            # program, so no second copy of the parameters is ever held.
            cfg = self.env["config"]
            init = self.env["reference"].init_weights
            self.change = jax.jit(
                lambda p, key: norms(jax.tree_util.tree_map(jnp.subtract, p, init(key, cfg)))
            )(live["params"], jax.random.PRNGKey(self.env["seed"]))

    # -- log_fn -----------------------------------------------------------

    def log(self, line: str) -> None:
        now = time.perf_counter()
        print(line, file=sys.stderr)
        if not STEP_LINE.match(line):
            return
        record = sys._getframe(1).f_locals["record"]
        step = record["step"]
        self.syncs.append((now, step, record["loss"], record["step_wall_s"]))
        tracer = self.env["tracer"]
        if self.t_open is None:
            if step >= self.open_at_step:
                if tracer is not None:
                    tracer.start()
                    now = time.perf_counter()
                self.t_open, self.step_open = now, step
            return
        if self.t_close is not None:
            return
        elapsed = now - self.t_open
        interval = now - self.syncs[-2][0]
        target = self.trace_seconds if tracer is not None else self.env["seconds"]
        if elapsed + 0.5 * interval >= target:
            if tracer is not None:
                tracer.stop()
            self.t_close, self.step_close = now, step
            signal.raise_signal(signal.SIGTERM)  # the loop's cooperative stop


def program_peak_bytes(probe: Probe, emit) -> int:
    """What the compiled step needs while it runs: arguments + temporaries +
    outputs that are not aliased to arguments.  The runtime's
    ``peak_bytes_in_use`` misses a step's temporaries (PERF.md, PR 22), so
    the step is asked itself - compiled once more, from the cache."""
    t0 = time.perf_counter()
    analysis = probe.step_fn.lower(*probe.abstract).compile().memory_analysis()
    total = (
        analysis.argument_size_in_bytes + analysis.temp_size_in_bytes
        + analysis.output_size_in_bytes - analysis.alias_size_in_bytes
    )
    emit({
        "info": "step_memory_analysis",
        "argument_bytes": analysis.argument_size_in_bytes,
        "temp_bytes": analysis.temp_size_in_bytes,
        "output_bytes": analysis.output_size_in_bytes,
        "alias_bytes": analysis.alias_size_in_bytes,
        "total_bytes": total, "seconds": time.perf_counter() - t0,
    })
    return int(total)


def run(env: dict) -> dict:
    import jax

    from bpe_transformer_tpu.training.loop import LoopConfig, train
    from bpe_transformer_tpu.training.train_step import TrainHParams
    from chipbench import traffic
    from chipbench.run import device_report, numeric

    workload, config, seed, emit = env["workload"], env["config"], env["seed"], env["emit"]
    spec = workload["train"]
    limits = workload["correct"]
    model_config = env["model_config"]
    seq = model_config.context_length
    hp = dict(spec["hparams"])
    hparams = TrainHParams(**{**hp, "betas": tuple(hp["betas"])})
    loop = LoopConfig(
        steps=10**9, batch_size=spec["batch_size"], log_every=spec["log_every"],
        eval_every=10**9, checkpoint_every=10**9, checkpoint_dir=None,
        seed=seed, prefetch=spec["prefetch"], parallel=spec.get("parallel"),
        mesh_axes=spec.get("mesh_axes"),
    )
    data = traffic.training_tokens(workload["data"], config["vocab_size"], seed)
    env["phase"]("data_made")
    probe = Probe(env, hp, spec["log_every"])
    train(model_config, hparams, loop, data, None, log_fn=probe.log, fault_injector=probe)
    if probe.t_close is None:
        raise RuntimeError("train() returned before the window closed")

    device = device_report(env["devices"])
    losses = [float(v) for v in jax.device_get(probe.losses)]
    first_grad = np.asarray(probe.first_grad)
    change = np.asarray(probe.change)
    emit({"info": "memory_stats", **numeric(env["devices"][0].memory_stats() or {})})
    device["memory_peak_bytes"] = max(
        device["memory_peak_bytes"], program_peak_bytes(probe, emit)
    )

    steps = probe.step_close - probe.step_open
    wall_s = probe.t_close - probe.t_open
    tokens = steps * spec["batch_size"] * seq
    chips = workload["chips"]
    window_losses = [l for t, s, l, _ in probe.syncs if s >= probe.step_open]
    finite = [math.isfinite(l) for l in window_losses]
    flops_per_token = env["counts"].train_flops_per_token(config, seq)
    step_walls = sorted(w for t, s, l, w in probe.syncs if s > probe.step_open)
    rate = tokens / wall_s / chips
    emit({
        "info": "window", "steps": steps, "wall_s": wall_s, "syncs": len(window_losses) - 1,
        "tokens": tokens, "first_loss": window_losses[0], "last_loss": window_losses[-1],
        "loop_step_wall_s_median": step_walls[len(step_walls) // 2] if step_walls else None,
        "flops_per_token": flops_per_token,
    })

    # The reference runs now: the program's state is gone with train()'s frame.
    probe.step_fn = probe.abstract = None
    gc.collect()
    t0 = time.perf_counter()
    rows = limits["reference_rows_per_block"]
    ref = env["reference"].reference_train(seed, config, hp, probe.batches, rows_per_block=rows)
    rows_differ = all(
        len({row.tobytes() for row in x}) == len(x) and np.array_equal(x[:, 1:], y[:, :-1])
        for x, y in probe.batches
    )
    _, grad_leaf = worst_leaf_gap(first_grad, ref["first_grad_leaf_norms"])
    _, change_leaf = worst_leaf_gap(change, ref["change_leaf_norms"])
    if env["control"]:
        low = env["reference"].reference_train(
            seed, config, hp, probe.batches, quant="fp8", rows_per_block=rows
        )
        emit({"info": "control", "compared": [
            {"number": n, "value": v, "limit": l, "fails": v > l}
            for n, v, l in compare(
                low["losses"], low["first_grad_leaf_norms"],
                low["change_leaf_norms"], ref, limits,
            )
        ]})
    compared = [
        {"number": n, "value": v, "limit": l, "ok": v <= l}
        for n, v, l in compare(losses, first_grad, change, ref, limits)
    ]
    correct = (
        all(row["ok"] for row in compared)
        and all(finite) and window_losses[-1] < window_losses[0] and rows_differ
    )
    emit({
        "info": "correct", "compared": compared,
        "program_losses": losses, "reference_losses": ref["losses"],
        "first_grad_worst_leaf": ref["leaf_names"][grad_leaf],
        "param_change_worst_leaf": ref["leaf_names"][change_leaf],
        "window_losses_finite": all(finite),
        "window_loss_fell": window_losses[-1] < window_losses[0],
        "rows_differ_and_targets_shifted": rows_differ,
        "reference_seconds": time.perf_counter() - t0,
    })

    out = {
        "correct": correct, "attempted": steps, "failed": finite[1:].count(False),
        "device": device, "compared": compared,
    }
    setup_s = (time.time() - env["t_start"]) - (time.perf_counter() - probe.t_open)
    if env["trace"]:
        out["trace"] = env["tracer"].reduce()
        out["traced"] = {"launches": steps, "traced_s": wall_s, "ended_by": "seconds"}
        out["scalars"] = {
            "wall_s": wall_s, "steps": steps,
            "flops_required": flops_per_token * tokens / chips,
        }
    else:
        mfu = rate * flops_per_token / env["peaks"]["flops_bf16"]
        emit({"info": "mfu", "value": mfu if mfu == mfu else None})
        out["metrics"] = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "train.tok_s_chip": {"value": rate, "unit": "tokens/s/chip"},
        }
    return out

