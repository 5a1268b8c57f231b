"""A serving cell: ``serving.ServingEngine`` built the way ``bpe-tpu serve
--paged`` builds it, in this process, driven from the client's side.

No HTTP, no tokenizer, no checkpoint: weights are made on the device from
the seed in one jitted call, in the type they are served in, and handed to
the engine.  The program's telemetry stream is read through
``Telemetry(sink=...)``; its counters through ``ServingEngine.stats()``.

Client side means this file's own clock on its own threads: one reader per
request over ``RequestHandle.tokens()`` stamps every token as it arrives.
Time to first token counts from when the request was *due* (open loop) or
*sent* (closed loop).

``correct``: after the window has closed and the engine is freed, a sample
of the greedy requests the window finished (drawn from the seed, the
longest among them) is scored by the float32 reference in one full forward
each; the widest gap by which a served token's logit lies below the
reference's best must stay under the cell's limit.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from chipbench.layer_metrics import percentile

#: The traced part of a window ends at the workload's ``trace_seconds`` or
#: once the worker has launched this many programs (a tick, and each chunk
#: of its period), whichever comes first: what the profiler collects, and so
#: what stopping it, reading and reducing the trace cost, grows with the
#: launches and not with the seconds.  No accepted cell comes near it (48
#: launches in 5 s, 76 in 2 s); it is there for the faster tick.
TRACE_LAUNCH_BUDGET = 512


class Load:
    """The clients of one run and what they saw."""

    def __init__(self, engine, plan, traffic_spec: dict):
        self.engine = engine
        self.plan = plan
        self.arrival = traffic_spec["arrival"]
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.next_index = 0
        self.done = []  # finished, failed or cancelled request records
        self.inflight = {}
        self.threads = []
        clients = self.arrival.get("clients", 0)
        # Closed loop, set-up only: each client's first request is cut to a
        # random part of its output so the window sees a steady mix and not
        # the echo of a common start.  Part of the cell's schedule, like the
        # order of sizes: the same for every seed.
        self.first_cut = np.random.default_rng(
            traffic_spec["sizes_seed"] + 3
        ).uniform(0.05, 1.0, clients)

    # -- one request ------------------------------------------------------

    def _request(self, planned, n_out: int, t_ref: float) -> None:
        from bpe_transformer_tpu.serving.server import Request

        rec = {
            "index": planned.index, "t_ref": t_ref, "stamps": [], "tokens": [],
            "greedy": planned.greedy, "prompt": planned.prompt_ids,
            "n_out": n_out, "failed": False, "finish": None,
        }
        request = Request(
            prompt_ids=planned.prompt_ids, max_new_tokens=n_out,
            temperature=planned.temperature, top_k=planned.top_k,
            seed=planned.seed,
        )
        try:
            rec["t_sent"] = time.perf_counter()
            handle = self.engine.submit(request)
        except Exception as exc:  # refused: queue full, engine down
            rec["failed"], rec["finish"] = True, f"refused: {exc!r}"
            with self.lock:
                self.done.append(rec)
            self.stop.wait(0.05)  # a refused closed-loop client does not spin
            return
        with self.lock:
            self.inflight[planned.index] = handle
        for token in handle.tokens():
            rec["stamps"].append(time.perf_counter())
            rec["tokens"].append(token)
        result = handle.result()
        rec["finish"] = result.finish_reason
        rec["failed"] = result.finish_reason in ("error", "deadline")
        rec["program_s"] = (result.queue_wait_s, result.prefill_s, result.decode_s)
        with self.lock:
            self.inflight.pop(planned.index, None)
            self.done.append(rec)

    # -- arrivals ---------------------------------------------------------

    def _take(self):
        with self.lock:
            planned = self.plan[self.next_index % len(self.plan)]
            self.next_index += 1
        return planned

    def _closed_client(self, cid: int) -> None:
        first = True
        # Set-up only: clients join one by one, so the first prompts do not
        # queue behind each other for longer than the ramp lasts.
        if self.stop.wait(self.arrival.get("stagger_s", 0.0) * cid / self.arrival["clients"]):
            return
        while not self.stop.is_set():
            planned = self._take()
            n_out = planned.max_new_tokens
            if first:
                n_out, first = max(1, int(n_out * self.first_cut[cid])), False
            self._request(planned, n_out, time.perf_counter())

    def _dispatcher(self) -> None:
        t0 = time.perf_counter()
        for planned in self.plan:
            due = t0 + planned.due_s
            if self.stop.wait(max(0.0, due - time.perf_counter())):
                return
            thread = threading.Thread(
                target=self._request, args=(planned, planned.max_new_tokens, due),
                daemon=True,
            )
            thread.start()
            self.threads.append(thread)

    def start(self) -> None:
        if self.arrival["kind"] == "closed":
            targets = [
                threading.Thread(target=self._closed_client, args=(c,), daemon=True)
                for c in range(self.arrival["clients"])
            ]
        else:
            targets = [threading.Thread(target=self._dispatcher, daemon=True)]
        for thread in targets:
            thread.start()
        self.threads.extend(targets)

    def finish(self) -> None:
        """No new requests; cancel what is in flight; wait for every thread."""
        self.stop.set()
        with self.lock:
            handles = list(self.inflight.values())
        for handle in handles:
            handle.cancel()
        for thread in list(self.threads):
            thread.join(timeout=60)
        alive = [t for t in self.threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"{len(alive)} client threads did not end")


def warm_up(engine, buckets, vocab_size: int, seed: int) -> None:
    """One greedy request per prefill bucket the cell's prompts reach: the
    chunk program of each bucket and the tick compile (or load from the
    cache) here, and nowhere in the window."""
    from bpe_transformer_tpu.serving.server import Request

    rng = np.random.default_rng([seed, 4])
    for bucket in buckets:
        prompt = tuple(rng.integers(0, vocab_size, bucket).tolist())
        engine.submit(
            Request(prompt_ids=prompt, max_new_tokens=3, temperature=0.0, seed=0)
        ).result(timeout=1500)


def window_metrics(done, t_open: float, t_close: float) -> dict:
    """The end-to-end numbers of the window from the clients' stamps."""
    tokens_in = sum(
        1 for r in done for t in r["stamps"] if t_open <= t <= t_close
    )
    ttft, tpot, finished = [], [], []
    for r in done:
        if r["failed"] and t_open <= r["t_ref"] <= t_close:
            ttft.append(float("inf"))
        if not r["stamps"]:
            continue
        if t_open <= r["stamps"][0] <= t_close:
            ttft.append(r["stamps"][0] - r["t_ref"])
        if r["finish"] == "length" and t_open <= r["stamps"][-1] <= t_close:
            finished.append(r)
            if len(r["stamps"]) > 1:
                tpot.append((r["stamps"][-1] - r["stamps"][0]) / (len(r["stamps"]) - 1))
    attempted = [r for r in done if t_open <= r["t_ref"] <= t_close]
    # Where a stall shows: the tokens of each second of the window, and the
    # longest time in it with no token to any client.
    by_second = [0] * (int(t_close - t_open) + 1)
    stamps = sorted(t for r in done for t in r["stamps"] if t_open <= t <= t_close)
    for t in stamps:
        by_second[int(t - t_open)] += 1
    edges = [t_open, *stamps, t_close]
    return {
        "tokens_by_second": by_second,
        "longest_silence_s": max(b - a for a, b in zip(edges, edges[1:])),
        "tokens_in_window": tokens_in, "ttft": ttft, "tpot": tpot,
        "finished": finished, "attempted": len(attempted),
        "failed": sum(1 for r in attempted if r["failed"]),
    }


def required_flops(counts, config, done, t_open, t_close) -> float:
    """FLOPs the harness's own requests required inside the window: a
    prompt counts where its first token fell, a decoded token where it was
    stamped."""
    total = 0.0
    for r in done:
        n_prompt = len(r["prompt"])
        if r["stamps"] and t_open <= r["stamps"][0] <= t_close:
            total += counts.forward_flops(
                config, n_prompt, n_prompt * (n_prompt + 1) // 2, 1
            )
        for k, t in enumerate(r["stamps"][1:], start=1):
            if t_open <= t <= t_close:
                total += counts.forward_flops(config, 1, n_prompt + k, 1)
    return total


def run(env: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from bpe_transformer_tpu.serving.server import ServingEngine
    from bpe_transformer_tpu.telemetry.spans import Telemetry
    from chipbench import traffic
    from chipbench.run import device_report, numeric

    workload, config, seed, emit = env["workload"], env["config"], env["seed"], env["emit"]
    spec, mix, limits = workload["serve"], workload["traffic"], workload["correct"]
    tracer = env["tracer"]
    seconds = workload.get("trace_seconds", 5.0) if tracer is not None else env["seconds"]

    params = env["reference"].weights_from_seed(seed, config, jnp.bfloat16)
    env["phase"]("weights_made")
    records = []
    telemetry = Telemetry(sink=lambda r: records.append((time.perf_counter(), r)))
    engine_args = dict(spec["engine"])
    if "prefill_buckets" in engine_args:
        engine_args["prefill_buckets"] = tuple(engine_args["prefill_buckets"])
    engine = ServingEngine(
        params, env["model_config"], telemetry=telemetry, paged=True, **engine_args
    )
    del params
    engine.start()
    env["phase"]("engine_built")
    warm_up(engine, spec["warm_buckets"], config["vocab_size"], seed)
    env["phase"]("warmed_up")
    horizon = spec["ramp_s"] + seconds + 5.0
    plan = traffic.plan_requests(mix, config["vocab_size"], seed, horizon)
    load = Load(engine, plan, mix)
    load.start()
    time.sleep(spec["ramp_s"])

    # A traced part is the profiler's annotation and nothing else: counters,
    # clock, records and samples are all taken inside start() .. stop(),
    # which themselves take seconds while the engine keeps serving.
    if tracer is not None:
        tracer.start()
    stats_open = numeric(engine.stats())
    t_open = time.perf_counter()
    samples = []
    if tracer is not None:
        seen, launches, ended_by = len(records), 0, "seconds"
        while time.perf_counter() - t_open < seconds:
            time.sleep(0.25)
            samples.append(numeric(engine.stats()))
            upto = len(records)
            launches += sum(
                1 + r.get("chunks", 0) for _, r in records[seen:upto]
                if r.get("kind") == "tick"
            )
            seen = upto
            if launches >= TRACE_LAUNCH_BUDGET:
                ended_by = "launches"
                break
    else:
        time.sleep(seconds)
    t_close = time.perf_counter()
    stats_close = engine.stats()
    if tracer is not None:
        tracer.stop()
    # The requests the check samples from, and those counted as attempted
    # and failed, are a traced run's up to here: the engine serves on while
    # the profiler stops, and the traced part alone finishes too few.
    t_served = time.perf_counter() if tracer is not None else t_close
    load.finish()
    device = device_report(env["devices"])
    emit({"info": "memory_stats", **numeric(env["devices"][0].memory_stats() or {})})
    engine.close()

    done = load.done
    win = window_metrics(done, t_open, t_served)
    wall_s = t_close - t_open
    deltas = {
        f"d_{k}": stats_close[k] - v
        for k, v in stats_open.items() if k in numeric(stats_close)
    }
    compiles = deltas.get("d_compiled_programs", 0)
    late = [r["t_sent"] - r["t_ref"] for r in done if "t_sent" in r]
    emit({
        "info": "window", "wall_s": wall_s, "served_s": t_served - t_open,
        "requests_done": len(done),
        "finished_in_window": len(win["finished"]), "ttft_samples": len(win["ttft"]),
        "tpot_samples": len(win["tpot"]), "tokens_in_window": win["tokens_in_window"],
        "ttft_ms_p50": 1000 * percentile(win["ttft"], 50) if win["ttft"] else None,
        "tpot_ms_p50": 1000 * percentile(win["tpot"], 50) if win["tpot"] else None,
        "generator_late_ms_p95": 1000 * percentile(late, 95) if late else None,
        "generator_late_ms_max": 1000 * max(late) if late else None,
        "compiles_in_window": compiles, "ticks": deltas.get("d_ticks"),
        "tick_records": sum(
            1 for t, r in records if r.get("kind") == "tick" and t_open <= t <= t_close
        ),
        "engine_tokens": deltas.get("d_tokens_emitted"),
        "queue_depth_close": stats_close.get("queue_depth"),
        "active_slots_close": stats_close.get("active_slots"),
        "finish_reasons": stats_close.get("finish_reasons"),
        "longest_silence_ms": 1000 * win["longest_silence_s"],
        "tokens_by_second": win["tokens_by_second"],
    })
    if compiles:
        raise RuntimeError(f"{compiles} program(s) compiled inside the window")

    # The reference runs now: engine, pool and weights are freed.
    del engine, load
    gc.collect()
    t0 = time.perf_counter()
    greedy = sorted(
        (r for r in win["finished"] if r["greedy"]), key=lambda r: -len(r["tokens"])
    )
    picks = greedy[:1]
    rest = greedy[1:]
    order = np.random.default_rng([seed, 5]).permutation(len(rest))
    picks += [rest[i] for i in order[: limits["sample_requests"] - 1]]
    sequences = [(r["prompt"], r["tokens"]) for r in picks]
    gaps = env["reference"].served_gaps(seed, config, sequences) if picks else []
    if env["control"] and picks:
        low = env["reference"].served_gaps(seed, config, sequences, control=True)
        emit({"info": "control", "compared": [
            {"number": "served_logit_widest_gap", "value": max(low),
             "limit": limits["served_logit_gap"],
             "fails": max(low) > limits["served_logit_gap"]},
        ], "gap_by_request": low})
    lengths_ok = all(
        len(r["tokens"]) == r["n_out"]
        and all(0 <= t < config["vocab_size"] for t in r["tokens"])
        for r in win["finished"]
    )
    widest = max(gaps) if gaps else float("inf")
    correct = widest <= limits["served_logit_gap"] and lengths_ok and win["failed"] == 0
    compared = [
        {"number": "served_logit_widest_gap", "value": widest if gaps else None,
         "limit": limits["served_logit_gap"], "ok": widest <= limits["served_logit_gap"]},
        {"number": "requests_failed", "value": win["failed"], "limit": 0,
         "ok": win["failed"] == 0},
    ]
    emit({
        "info": "correct", "compared": compared,
        "requests_scored": len(picks),
        "served_tokens_scored": sum(len(r["tokens"]) for r in picks),
        "gap_by_request": gaps, "lengths_ok": lengths_ok,
        "reference_seconds": time.perf_counter() - t0,
    })

    out = {
        "correct": correct, "attempted": win["attempted"], "failed": win["failed"],
        "device": device, "compared": compared,
    }
    if tracer is not None:
        out["trace"] = tracer.reduce()
        out["traced"] = {"launches": launches, "traced_s": wall_s, "ended_by": ended_by}
        out["records"] = [r for t, r in records if t_open <= t <= t_close]
        out["stats_samples"] = samples
        out["scalars"] = {
            "wall_s": wall_s, **deltas,
            "flops_required": required_flops(env["counts"], config, done, t_open, t_close),
            "weight_bytes": env["counts"].matmul_weight_bytes(config),
        }
    else:
        def p95_ms(values):
            value = percentile(values, 95) if values else float("inf")
            return 1000 * value if value != float("inf") else 1e12

        setup_s = (time.time() - env["t_start"]) - (time.perf_counter() - t_open)
        metrics = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "serve.out_tok_s": {"value": win["tokens_in_window"] / wall_s, "unit": "tokens/s"},
            "serve.ttft_ms.p95": {"value": p95_ms(win["ttft"]), "unit": "ms"},
            "serve.tpot_ms.p95": {"value": p95_ms(win["tpot"]), "unit": "ms"},
        }
        # The cell's file says which of them it is judged by (BENCHMARK.json
        # lists the same cells under each metric's "workloads").
        out["metrics"] = {k: metrics[k] for k in workload["end_to_end"]}
    return out
