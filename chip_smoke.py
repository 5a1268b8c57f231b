#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run with no arguments on a machine with one TPU chip::

    python3 chip_smoke.py

It drives the normal entry points (``bpe-tpu``, i.e. ``python -m
bpe_transformer_tpu.training.cli``) once, at the full width AND depth of the
``gpt2-small-32k`` preset (d_model 768, 12 layers, 12 heads, d_ff 2048,
vocab 32,000, context 1,024, bf16 activations), weights random from a seed:

1. **probe** — a child reports the run manifest's device record, the
   jax/jaxlib/libtpu versions, ``interpret_mode()``, and the number of Mosaic
   kernels in the lowered train step.  Off the chip the script stops HERE,
   seconds in, before any model work.
2. **data** — a corpus generated from ``--seed``; ``train-tokenizer`` and
   ``tokenize`` build the token file (no network, no ``/root/reference``).
3. **train** — ``bpe-tpu train`` with ``attention_impl="flash"`` (through
   ``--model-config``; the train CLI has no attention flag): a few optimizer
   steps at sequence 1,024, loss finite and lower at the end, a checkpoint.
4. **resume** — ``--resume`` from that checkpoint for one more step.
5. **serve** — ``bpe-tpu serve --paged --decode-attention paged`` (bf16
   block pool) on ``--port 0``: ``/healthz``, greedy and seeded ``/generate``
   (one repeated with the same seed: token ids identical; two concurrent),
   ``/statusz``, then SIGTERM: exit code 0 and a clean footer.

``--four-chips`` (run by the builder; needs a host with four chips) runs
ONLY the sharded path and what it is compared with: ``gpt2-medium`` trained
single-device at batch 4 (the reference), ``--parallel fsdp --mesh data=4``
at batch 4 (losses must agree step by step) and at batch 16 — the size one
chip cannot hold — with a checkpoint saved and resumed, and the state shown
to be spread over the four chips from the runs' own ``kind="resources"``
records.

One process per chip: THIS process never imports jax; the children run one
after the other, their stdout/stderr go to files under
``.scratch/chip_smoke/``.  Every phase prints one JSON line; any failed
check raises, the script exits non-zero, and nothing is caught to carry on.
The LAST stdout line — written only when every phase passed on a TPU — is
exactly ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORKDIR = ROOT / ".scratch" / "chip_smoke"
CLI = [sys.executable, "-m", "bpe_transformer_tpu.training.cli"]
PROBE = [sys.executable, "-m", "bpe_transformer_tpu.utils.chip_probe"]
#: |fsdp loss - single-device loss| <= LOSS_RTOL * |loss|, every step: bf16
#: activations and a different gradient-reduction order, nothing else.
LOSS_RTOL = 2e-3


class SmokeFailure(RuntimeError):
    """A phase's check failed; the script exits non-zero."""


def final_line(devices: dict) -> str:
    """THE last stdout line: two top-level keys, three inside ``device``,
    nothing else (timings, versions and counts go on earlier lines)."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": devices["platform"],
            "kind": devices["kind"],
            "count": devices["count"],
        },
    })


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


# ------------------------------------------------------------ children


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _tail(path: Path, n: int = 4000) -> str:
    try:
        return path.read_text(errors="replace")[-n:]
    except OSError:
        return ""


def run_child(name: str, argv: list[str], timeout: float) -> Path:
    """Run one child to completion, stdout/stderr to files; a non-zero exit
    (or a timeout) fails the smoke with the tail of its stderr.  Returns
    the stdout file."""
    out, err = WORKDIR / f"{name}.out", WORKDIR / f"{name}.err"
    t0 = time.monotonic()
    with open(out, "w") as fo, open(err, "w") as fe:
        try:
            proc = subprocess.run(
                argv, cwd=ROOT, env=child_env(), stdout=fo, stderr=fe,
                timeout=timeout,
            )
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{name}: no exit within {timeout:.0f}s\n{_tail(err)}"
            ) from None
    if proc.returncode != 0:
        raise SmokeFailure(
            f"{name}: exit code {proc.returncode}\n{_tail(out, 1000)}\n"
            f"{_tail(err)}"
        )
    print(f"[chip_smoke] {name}: ok in {time.monotonic() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return out


def read_jsonl(path: Path) -> list[dict]:
    return [json.loads(line) for line in path.read_text().splitlines() if line]


# -------------------------------------------------------------- phases


def phase_probe(
    expect_platform: str, expect_count: int, require_kernels: bool,
    config_path: Path | None = None, batch_size: int = 8,
) -> dict:
    """The device check, first and cheap; returns the device record the
    last line is built from."""
    argv = PROBE + ["--expect-platform", expect_platform]
    if config_path is not None:
        argv += ["--lower-train", str(config_path),
                 "--batch-size", str(batch_size)]
    record = json.loads(run_child("probe", argv, 300).read_text())
    devices = record.get("devices")
    check(devices, "probe: the run manifest has no `devices` record")
    check(devices["platform"] == expect_platform,
          f"probe: platform {devices['platform']!r} != {expect_platform!r}")
    check(devices["count"] == expect_count,
          f"probe: {devices['count']} devices, this path needs {expect_count}")
    if require_kernels:
        check(record["interpret_mode"] is False,
              "probe: Pallas kernels would run in interpret mode")
        if config_path is not None:
            check(record["train_step_tpu_custom_calls"] > 0,
                  "probe: no tpu_custom_call in the lowered train step — "
                  "the flash kernel is not on the path")
    emit("probe", **record)
    return devices


def phase_data(seed: int, n_docs: int, tokenizer_vocab: int) -> dict:
    """Corpus from the seed -> tokenizer -> token file, through the CLI."""
    from bpe_transformer_tpu.native import engine as native

    rng = random.Random(seed)
    letters = "abcdefghijklmnopqrstuvwxyz"
    words = [
        "".join(rng.choice(letters) for _ in range(rng.randint(2, 9)))
        for _ in range(3000)
    ]
    # Zipf-ish unigram weights + a word-to-word habit, so a model can learn
    # something in a handful of steps.
    weights = [1.0 / (rank + 1) for rank in range(len(words))]
    corpus = WORKDIR / "corpus.txt"
    with open(corpus, "w", encoding="utf-8") as f:
        for _ in range(n_docs):
            for _ in range(rng.randint(4, 12)):
                picks = rng.choices(range(len(words)), weights, k=rng.randint(5, 16))
                sentence = []
                for idx in picks:
                    sentence.append(words[idx])
                    if idx % 3 == 0:
                        sentence.append(words[(idx * 7 + 1) % len(words)])
                f.write(" ".join(sentence).capitalize() + ". ")
            f.write("\n<|endoftext|>\n")
    tok_dir, tokens = WORKDIR / "tok", WORKDIR / "tokens.bin"
    t0 = time.monotonic()
    run_child("train_tokenizer", CLI + [
        "train-tokenizer", "--input", str(corpus),
        "--vocab-size", str(tokenizer_vocab), "--output-dir", str(tok_dir),
    ], 600)
    run_child("tokenize", CLI + [
        "tokenize", "--input", str(corpus), "--tokenizer-dir", str(tok_dir),
        "--output", str(tokens),
    ], 600)
    n_tokens = tokens.stat().st_size // 2  # uint16
    emit(
        "data", seed=seed, corpus_bytes=corpus.stat().st_size,
        tokenizer_vocab=tokenizer_vocab, n_tokens=n_tokens,
        # The .so is built from native/src on first use (git ignores
        # native/_build); without a toolchain the Python path is taken.
        tokenizer_path="native (built from native/src)"
        if native.is_available() else "pure Python (no native build)",
        seconds=round(time.monotonic() - t0, 1),
    )
    return {"tok_dir": tok_dir, "tokens": tokens, "words": words}


def _train_facts(records: list[dict], devices: dict) -> dict:
    """Checks every train child must pass + the facts worth printing."""
    manifest = records[0]
    check(manifest.get("kind") == "manifest", "train: no manifest header")
    check(manifest.get("devices") == devices,
          f"train: manifest devices {manifest.get('devices')} != probe's "
          f"{devices}")
    steps = [r for r in records if "loss" in r and "step" in r]
    losses = [r["loss"] for r in steps]
    check(losses and all(math.isfinite(x) for x in losses),
          f"train: non-finite or missing losses {losses}")
    footer = records[-1]
    check(footer.get("kind") == "footer" and footer.get("clean") is True,
          f"train: stream does not end in a clean footer: {footer}")
    resources = [r for r in records if r.get("kind") == "resources"]
    spans = {r["name"]: r["dur_s"] for r in records if r.get("kind") == "span"}
    last = resources[-1]
    walls = sorted(r["step_wall_s"] for r in steps[1:]) or [None]
    return {
        "start_iteration": manifest.get("start_iteration"),
        "steps": [r["step"] for r in steps],
        "losses": losses,
        "compile_first_step_s": spans.get("compile_first_step"),
        "step_wall_s_median": walls[len(walls) // 2],
        "compile_events": last["compile_events"],
        "compile_time_s": last["compile_time_s"],
        "cache_hits": last["compile_cache_hits"],
        "hbm_peak_bytes": last["hbm_peak_bytes_in_use"],
        "hbm_in_use_per_device": last.get("hbm_bytes_in_use_per_device"),
        "params_bytes_per_chip": last["params_bytes"],
        "opt_state_bytes_per_chip": last["opt_state_bytes"],
        "mesh": manifest.get("mesh"),
    }


def run_train(
    name: str, devices: dict, tokens: Path, model_args: list[str],
    steps: int, batch_size: int, seed: int, extra: list[str] = (),
) -> dict:
    metrics = WORKDIR / f"{name}.jsonl"
    run_child(name, CLI + [
        "train", "--data", str(tokens), *model_args,
        "--steps", str(steps), "--batch-size", str(batch_size),
        "--seed", str(seed), "--log-every", "1",
        # Hyperparameters are baked into the compiled step: every run of a
        # shape passes the same ones, so a later run hits the cache.
        "--lr", "1e-3", "--warmup", "0", "--lr-cycle", "1000",
        "--metrics-jsonl", str(metrics), *extra,
    ], 900)
    return _train_facts(read_jsonl(metrics), devices)


def phase_train_resume(
    devices: dict, tokens: Path, config_path: Path, steps: int,
    batch_size: int, seed: int,
) -> Path:
    ckpt = WORKDIR / "ckpt"
    model_args = ["--model-config", str(config_path)]
    facts = run_train(
        "train", devices, tokens, model_args, steps, batch_size, seed,
        ["--checkpoint-dir", str(ckpt), "--checkpoint-every", str(steps)],
    )
    check(facts["steps"] == list(range(1, steps + 1)) and steps >= 3,
          f"train: expected steps 1..{steps}, logged {facts['steps']}")
    check(facts["losses"][-1] < facts["losses"][0],
          f"train: loss did not fall: {facts['losses']}")
    check((ckpt / "latest.ckpt").exists(), "train: no checkpoint written")
    emit("train", batch_size=batch_size, **facts)

    facts = run_train(
        "resume", devices, tokens, model_args, steps + 1, batch_size, seed,
        ["--checkpoint-dir", str(ckpt), "--checkpoint-every", str(steps + 1),
         "--resume", str(ckpt)],
    )
    check(facts["start_iteration"] == steps and facts["steps"] == [steps + 1],
          f"resume: expected to continue at {steps}, got {facts}")
    emit("resume", batch_size=batch_size, **facts)
    return ckpt / "latest.ckpt"


def _http(url: str, body: dict | None = None, timeout: float = 600) -> dict:
    request = urllib.request.Request(
        url,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(request, timeout=timeout) as response:
        check(response.status == 200, f"{url}: HTTP {response.status}")
        return json.loads(response.read())


def phase_serve(
    devices: dict, checkpoint: Path, tok_dir: Path, words: list[str],
    vocab_size: int, block_size: int, max_new_tokens: int,
    long_prompt_words: int,
) -> None:
    """`bpe-tpu serve` as a child on an ephemeral port: requests, then
    SIGTERM and a clean exit."""
    out, err = WORKDIR / "serve.out", WORKDIR / "serve.err"
    metrics = WORKDIR / "serve.jsonl"
    t0 = time.monotonic()
    with open(out, "w") as fo, open(err, "w") as fe:
        proc = subprocess.Popen(CLI + [
            "serve", "--checkpoint", str(checkpoint),
            "--tokenizer-dir", str(tok_dir), "--port", "0",
            "--paged", "--decode-attention", "paged",
            "--block-size", str(block_size), "--slots", "4",
            "--max-new-tokens", str(max_new_tokens),
            "--metrics-jsonl", str(metrics),
        ], cwd=ROOT, env=child_env(), stdout=fo, stderr=fe)
    try:
        url = None
        while url is None:
            check(proc.poll() is None,
                  f"serve: exited with {proc.returncode} before serving\n"
                  f"{_tail(err)}")
            check(time.monotonic() - t0 < 600, "serve: no banner in 600s")
            for line in out.read_text().splitlines():
                if line.startswith("serving on http://"):
                    url = line.split()[2]
            time.sleep(0.5)
        startup_s = time.monotonic() - t0

        health = _http(url + "/healthz", timeout=30)
        check(health.get("ok") is True, f"serve: /healthz says {health}")

        def generate(n_words: int, offset: int, **knobs) -> dict:
            prompt = " ".join(words[offset:offset + n_words]).capitalize()
            sent = time.monotonic()
            reply = _http(url + "/generate", {"prompt": prompt, **knobs})
            ids = reply["token_ids"]
            check(ids and all(0 <= i < vocab_size for i in ids),
                  f"serve: bad token ids {ids}")
            reply["_seconds"] = round(time.monotonic() - sent, 3)
            return reply

        replies = [generate(6, 0, temperature=0)]             # greedy, cold
        sampled = dict(temperature=0.8, top_k=40, top_p=0.95, seed=1234)
        first = generate(8, 10, **sampled)
        again = generate(8, 10, **sampled)
        check(first["token_ids"] == again["token_ids"],
              "serve: the same seed gave different tokens: "
              f"{first['token_ids']} vs {again['token_ids']}")
        replies += [first, again]
        # Two at once (batched into one tick) + a longer prompt (another
        # prefill bucket).
        concurrent: list = [None, None]

        def worker(i: int) -> None:
            try:
                concurrent[i] = generate(5 + i, 30 + 10 * i, temperature=0.7,
                                         seed=i)
            except Exception as exc:  # re-raised on the main thread below
                concurrent[i] = exc

        threads = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        for result in concurrent:
            if isinstance(result, Exception):
                raise result
        replies += concurrent
        replies.append(generate(long_prompt_words, 100, temperature=0))

        status = _http(url + "/statusz", timeout=30)
        check(status["manifest"].get("devices") == devices,
              f"serve: manifest devices {status['manifest'].get('devices')}"
              f" != probe's {devices}")
        check(status["requests_finished"] == len(replies)
              and status["worker_alive"] and not status["last_errors"],
              f"serve: /statusz unhealthy: finished "
              f"{status['requests_finished']}/{len(replies)}, errors "
              f"{status['last_errors']}")
        check(status["engine_kind"] == "paged"
              and status["kvpool"]["kv_dtype"] != "int8",
              f"serve: not the paged bf16 engine: {status['engine_kind']}")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("serve: no exit 120s after SIGTERM") from None
        check(rc == 0, f"serve: exit code {rc} after SIGTERM\n{_tail(err)}")
        check("drained cleanly" in out.read_text(),
              "serve: no 'drained cleanly' line")
        footer = read_jsonl(metrics)[-1]
        check(footer.get("kind") == "footer" and footer.get("clean") is True,
              f"serve: stream does not end in a clean footer: {footer}")
        emit(
            "serve", startup_s=round(startup_s, 1), requests=len(replies),
            tokens_generated=sum(len(r["token_ids"]) for r in replies),
            request_seconds=[r["_seconds"] for r in replies],
            repeated_seed_identical=True, exit_code=rc,
            compiled_programs=status["compiled_programs"],
            compile_events=status["compile_events"],
            compile_time_s=status["resources"]["compile_time_s"],
            cache_hits=status["resources"]["compile_cache_hits"],
            hbm_peak_bytes=status["resources"]["hbm_peak_bytes_in_use"],
            kv_dtype=status["kvpool"]["kv_dtype"], block_size=block_size,
            decode_attention="paged",
        )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# ---------------------------------------------------------- whole runs


def start(seed: int) -> None:
    from bpe_transformer_tpu.utils.compile_cache import resolve_cache_dir

    shutil.rmtree(WORKDIR, ignore_errors=True)
    WORKDIR.mkdir(parents=True)
    emit("start", seed=seed, workdir=str(WORKDIR),
         compile_cache=str(resolve_cache_dir(None, backend="tpu")),
         compile_cache_from_env="JAX_COMPILATION_CACHE_DIR" in os.environ)


def run_one_chip(
    expect_platform: str, require_kernels: bool, model: dict, seed: int,
    steps: int = 6, batch_size: int = 8, n_docs: int = 4000,
    tokenizer_vocab: int = 2048, block_size: int = 32,
    max_new_tokens: int = 16, long_prompt_words: int = 60,
) -> dict:
    """train -> checkpoint -> resume -> serve on one chip; returns the
    device record.  ``model`` is a ModelConfig dict (main() passes the
    gpt2-small-32k preset with flash attention)."""
    start(seed)
    config_path = WORKDIR / "model_config.json"
    config_path.write_text(json.dumps(model))
    devices = phase_probe(
        expect_platform, 1, require_kernels, config_path, batch_size
    )
    data = phase_data(seed, n_docs, tokenizer_vocab)
    checkpoint = phase_train_resume(
        devices, data["tokens"], config_path, steps, batch_size, seed
    )
    phase_serve(
        devices, checkpoint, data["tok_dir"], data["words"],
        model["vocab_size"], block_size, max_new_tokens, long_prompt_words,
    )
    return devices


def run_four_chips(
    expect_platform: str, model_args: list[str], seed: int,
    steps: int = 4, small_batch: int = 4, big_batch: int = 16,
    n_docs: int = 4000, tokenizer_vocab: int = 2048,
) -> dict:
    """The sharded path and its single-device reference, nothing else."""
    start(seed)
    devices = phase_probe(expect_platform, 4, require_kernels=False)
    tokens = phase_data(seed, n_docs, tokenizer_vocab)["tokens"]
    fsdp = ["--parallel", "fsdp", "--mesh", "data=4"]

    ref = run_train(f"ref_b{small_batch}", devices, tokens, model_args,
                    steps, small_batch, seed)
    emit("reference_single_device", batch_size=small_batch, **ref)
    got = run_train(f"fsdp_b{small_batch}", devices, tokens, model_args,
                    steps, small_batch, seed, fsdp)
    worst = max(
        abs(a - b) / abs(a) for a, b in zip(ref["losses"], got["losses"])
    )
    check(len(got["losses"]) == len(ref["losses"]) == steps
          and worst <= LOSS_RTOL,
          f"fsdp: losses {got['losses']} differ from the single-device "
          f"reference {ref['losses']} by {worst:.2e} > {LOSS_RTOL}")
    emit("fsdp_matches_reference", batch_size=small_batch,
         max_rel_loss_diff=worst, rtol=LOSS_RTOL, **got)

    ckpt = WORKDIR / "ckpt_fsdp"
    big = run_train(
        f"fsdp_b{big_batch}", devices, tokens, model_args, steps,
        big_batch, seed,
        fsdp + ["--checkpoint-dir", str(ckpt),
                "--checkpoint-every", str(steps)],
    )
    check(big["losses"][-1] < big["losses"][0],
          f"fsdp B={big_batch}: loss did not fall: {big['losses']}")
    # The state is really spread: per-chip bytes from shard shapes, and
    # every device holding memory (the allocator's own count, where the
    # backend reports one — the CPU rehearsal has none).
    for key in ("params_bytes_per_chip", "opt_state_bytes_per_chip"):
        ratio = big[key] / ref[key]
        check(0.2 <= ratio <= 0.3,
              f"fsdp: {key} is {ratio:.3f} of the single-device run's, "
              "expected about 1/4")
    per_device = big["hbm_in_use_per_device"]
    if expect_platform != "cpu":
        check(per_device and len(per_device) == 4
              and min(per_device) > 0.5 * max(per_device),
              f"fsdp: device memory is not spread: {per_device}")
    emit("fsdp_train", batch_size=big_batch, **big,
         params_ratio_vs_single=round(
             big["params_bytes_per_chip"] / ref["params_bytes_per_chip"], 4),
         opt_state_ratio_vs_single=round(
             big["opt_state_bytes_per_chip"] / ref["opt_state_bytes_per_chip"],
             4))

    resumed = run_train(
        "fsdp_resume", devices, tokens, model_args, steps + 1, big_batch,
        seed,
        fsdp + ["--checkpoint-dir", str(ckpt),
                "--checkpoint-every", str(steps + 1), "--resume", str(ckpt)],
    )
    check(resumed["start_iteration"] == steps
          and resumed["steps"] == [steps + 1],
          f"fsdp resume: expected to continue at {steps}, got {resumed}")
    emit("fsdp_resume", batch_size=big_batch, **resumed)
    return devices


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run ONLY the four-chip path (gpt2-medium, fsdp over data=4) "
        "and its single-device reference; needs a host with four chips",
    )
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    if not (ROOT / "bpe_transformer_tpu").is_dir():
        print("chip_smoke: the bpe_transformer_tpu package is not next to "
              "this script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    t0 = time.monotonic()
    if args.four_chips:
        devices = run_four_chips("tpu", ["--preset", "gpt2-medium"], args.seed)
    else:
        import dataclasses

        from bpe_transformer_tpu.models.config import GPT2_SMALL_32K

        model = dataclasses.asdict(
            dataclasses.replace(GPT2_SMALL_32K, attention_impl="flash")
        )
        devices = run_one_chip("tpu", True, model, args.seed)
    check("jax" not in sys.modules,
          "chip_smoke: the parent imported jax (it must stay off the chip)")
    emit("done", seconds=round(time.monotonic() - t0, 1))
    print(final_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SmokeFailure as failure:
        print(f"chip_smoke: FAILED — {failure}", file=sys.stderr, flush=True)
        code = 1
    sys.exit(code)
