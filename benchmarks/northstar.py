"""BASELINE north star, demonstrated in ONE on-chip run.

Target (BASELINE.json): the TinyStories 4-layer LM reaches the PyTorch-CPU
reference validation loss at >= 10x its tokens/sec.  Prior rounds proved the
two halves separately — throughput on the chip and loss parity at
toy shape on CPU (val_parity.py).  This script closes the loop at the REAL
config-1 shape (`TINYSTORIES_4L`: vocab 10k, seq 256, 4L/256d) with the
training run itself on the accelerator.

Protocol (LR-matched, identical on both substrates — val_parity.py's):
same BPE-tokenized corpus, same train/val split, same pre-drawn batch
schedule, same init (the JAX init copied into torch), same warmup+cosine
AdamW schedule (`TrainHParams` defaults).  The torch side is the
reference-architecture step from ``val_parity.make_torch_lm`` (defined by
`/root/reference/tests/adapters.py:282-361`; the reference ships no loop).

Corpus: BASELINE config 1 names `tinystories_sample.txt`, but the mounted
copy is 3.7 KB and the 5 MB sample is a missing blob
(`/root/reference/.MISSING_LARGE_BLOBS`); `corpus.en` (130 KB) is the
largest text the reference ships, so it is the corpus here — recorded in
the artifact, as in val_parity.py.

Phases (so a chip call only pays for the accelerator part):
  --phase data    tokenize the corpus at vocab 10k; cache to
                  benchmarks/northstar_tokens.npz (deterministic, committed)
  --phase torch   the torch-CPU reference run; writes
                  benchmarks/northstar_torch.json (curve, final val loss,
                  tokens/sec).  Runs offline, no accelerator needed.
  --phase jax     the accelerator run.  Checkpoints every eval to the
                  repo-local gitignored scratch (.scratch/northstar_ckpt.pkl,
                  NORTHSTAR_CKPT overrides) so an interrupted run
                  RESUMES instead of restarting; on completion writes
                  benchmarks/captures/northstar.json with both final val
                  losses, both tokens/sec, and the speedup.
  (default)       data + torch if their artifacts are missing, then jax.

Numerics: both sides train in f32; the JAX run pins
``jax.default_matmul_precision("highest")`` so the TPU trajectory tracks the
torch-f32 oracle (TPU's default f32 matmul rounds through bf16 passes and
would drift over hundreds of steps).  Even at highest precision the tiny
model clears the 10x bar by orders of magnitude — this run is the
convergence evidence, not a speed measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


SEQ = 256
BATCH = 16
VOCAB = 10_000
#: NORTHSTAR_STEPS is a smoke-test override; the artifacts record the value
#: used, and phase_jax refuses a torch reference run at a different length.
STEPS = int(os.environ.get("NORTHSTAR_STEPS", "200"))
EVAL_EVERY = 25
VAL_FRACTION = 0.1
SPECIAL = "<|endoftext|>"
CORPUS = "/root/reference/tests/fixtures/corpus.en"

TOKENS_NPZ = REPO / "benchmarks" / "northstar_tokens.npz"
TORCH_JSON = REPO / "benchmarks" / "northstar_torch.json"
CAPTURE = REPO / "benchmarks" / "captures" / "northstar.json"
#: The native-precision variant writes its own artifact: the parity run
#: (matmul precision=highest, per-step dispatch) is the convergence oracle;
#: the native run (TPU-default f32 matmuls, EVAL_EVERY steps per scanned
#: dispatch) is the same protocol at the precision/dispatch the framework
#: actually trains at, and is the run that demonstrates BOTH north-star
#: clauses — reference val loss AND >=10x tokens/sec — in one run.
CAPTURE_NATIVE = REPO / "benchmarks" / "captures" / "northstar_native.json"
#: Resume checkpoint lives in the repo's gitignored scratch, not /tmp, so
#: mid-run progress survives with the checkout.  Legacy /tmp checkpoints
#: are migrated in phase_jax so an in-flight resume survives this path
#: change.
CKPT = Path(
    os.environ.get("NORTHSTAR_CKPT", str(REPO / ".scratch" / "northstar_ckpt.pkl"))
)
LEGACY_CKPT = Path("/tmp/tpu_results/northstar_ckpt.pkl")
#: Val-loss slack for the reached_reference verdict: two independent f32
#: trajectories (torch-CPU vs TPU at matmul precision=highest) drift a few
#: centinats over 200 steps; recorded in the artifact so the claim is
#: self-describing.
VAL_TOLERANCE = 0.02


def _write_json(path: Path, payload: dict) -> None:
    """tmp + os.replace: a kill landing mid-write must not leave a torn
    artifact."""
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(payload, indent=2) + "\n")
    os.replace(tmp, path)


def phase_data() -> np.ndarray:
    """Tokenize the corpus (vocab 10k BPE trained on it) and cache the ids.

    Deterministic — the BPE trainer's tie-breaking is pinned by the
    reference's own snapshot tests — so the cache is just a time saver for
    the accelerator window, not a correctness requirement.
    """
    if TOKENS_NPZ.exists():
        return np.load(TOKENS_NPZ)["tokens"]
    from bpe_transformer_tpu import BPETokenizer, train_bpe

    corpus = Path(CORPUS)
    vocab, merges = train_bpe(str(corpus), VOCAB, [SPECIAL])
    tok = BPETokenizer(vocab, merges, [SPECIAL])
    ids = tok.encode(corpus.read_text(encoding="utf-8", errors="ignore"))
    tokens = np.asarray(ids, dtype=np.int32)
    np.savez_compressed(TOKENS_NPZ, tokens=tokens, vocab_size=len(vocab))
    print(f"tokenized {corpus.name}: {len(tokens)} tokens, "
          f"{len(vocab)} vocab entries", file=sys.stderr)
    return tokens


def split_tokens(tokens: np.ndarray):
    n_val = max(int(len(tokens) * VAL_FRACTION), SEQ + 1)
    return tokens[:-n_val], tokens[-n_val:]


def batch_schedule(n_tokens: int) -> np.ndarray:
    """All start indices drawn up front from one seed — a resumed run at
    step k sees exactly the batches the uninterrupted run would have."""
    rng = np.random.default_rng(0)
    return rng.integers(0, n_tokens - SEQ - 1, size=(STEPS, BATCH))


def gather_batch(tokens: np.ndarray, starts: np.ndarray):
    x = np.stack([tokens[s : s + SEQ] for s in starts])
    y = np.stack([tokens[s + 1 : s + SEQ + 1] for s in starts])
    return x.astype(np.int64), y.astype(np.int64)


def val_batches(val_toks: np.ndarray):
    n = (len(val_toks) - 1) // SEQ
    for i in range(min(n, 8)):
        s = i * SEQ
        yield (
            val_toks[s : s + SEQ][None, :].astype(np.int64),
            val_toks[s + 1 : s + SEQ + 1][None, :].astype(np.int64),
        )


def model_config():
    import dataclasses

    from bpe_transformer_tpu.models import TINYSTORIES_4L

    assert TINYSTORIES_4L.vocab_size == VOCAB
    assert TINYSTORIES_4L.context_length == SEQ
    return dataclasses.replace(TINYSTORIES_4L)


def init_params_np():
    """The shared starting point: JAX's deterministic init (threefry is
    platform-independent), fetched to host numpy for the torch loader."""
    import jax

    from bpe_transformer_tpu.models import init_params

    params = init_params(jax.random.PRNGKey(0), model_config())
    return jax.tree_util.tree_map(np.asarray, params)


def phase_torch() -> dict:
    if TORCH_JSON.exists():
        return json.loads(TORCH_JSON.read_text())
    import torch

    from benchmarks.val_parity import (
        _load_jax_params_into_torch,
        make_torch_lm,
    )

    tokens = phase_data()
    train_toks, val_toks = split_tokens(tokens)
    schedule = batch_schedule(len(train_toks))
    cfg = model_config()
    model, train_step, eval_loss = make_torch_lm(cfg)
    _load_jax_params_into_torch(model, init_params_np())

    def val_loss():
        losses = [
            eval_loss(torch.from_numpy(x), torch.from_numpy(y))
            for x, y in val_batches(val_toks)
        ]
        return sum(losses) / len(losses)

    curve = []
    start = time.perf_counter()
    train_s = 0.0
    for i in range(STEPS):
        x, y = gather_batch(train_toks, schedule[i])
        t0 = time.perf_counter()
        loss = train_step(torch.from_numpy(x), torch.from_numpy(y))
        train_s += time.perf_counter() - t0
        if (i + 1) % EVAL_EVERY == 0 or i == STEPS - 1:
            curve.append({"step": i + 1, "train_loss": loss, "val_loss": val_loss()})
            print(f"torch step {i + 1}: {curve[-1]}", file=sys.stderr)
    result = {
        "config": "TINYSTORIES_4L (vocab 10k, seq 256), batch 16",
        "corpus": CORPUS,
        "steps": STEPS,
        "curve": curve,
        "final_val_loss": curve[-1]["val_loss"],
        # tokens/sec over train-step time only (evals excluded on both
        # sides — the comparison is the training step, the reference's
        # contract surface).
        "tokens_per_sec": round(STEPS * BATCH * SEQ / train_s, 1),
        "wall_s": round(time.perf_counter() - start, 1),
    }
    _write_json(TORCH_JSON, result)
    print(f"torch reference: final val {result['final_val_loss']:.4f}, "
          f"{result['tokens_per_sec']:,.0f} tok/s", file=sys.stderr)
    return result


def phase_jax(allow_cpu: bool, variant: str = "parity") -> int:
    """One accelerator run of the shared protocol.

    ``variant="parity"``: f32 at matmul precision=highest, one dispatch per
    step — the trajectory tracks the torch-f32 oracle; the convergence claim.
    ``variant="native"``: TPU-default f32 matmul precision (single-pass bf16
    MXU) with the EVAL_EVERY steps between evals folded into ONE scanned
    dispatch (`make_scanned_train_step` — identical update math; the LR
    schedule rides opt_state.step, so scanning changes nothing numerically
    beyond the matmul rounding).  Same corpus/split/schedule/init; the run
    that shows val-loss AND the >=10x clause together, at the precision the
    framework actually trains at.
    """
    if variant not in ("parity", "native"):
        raise ValueError(f"unknown variant {variant!r}")
    native = variant == "native"
    capture_path = CAPTURE_NATIVE if native else CAPTURE
    ckpt_path = CKPT.with_name(f"native_{CKPT.name}") if native else CKPT
    from bpe_transformer_tpu.utils.chip_probe import require_tpu
    from bpe_transformer_tpu.utils.compile_cache import enable_compile_cache

    if not allow_cpu:
        require_tpu("northstar")
    enable_compile_cache()
    torch_ref = json.loads(TORCH_JSON.read_text())
    if torch_ref["steps"] != STEPS:
        raise SystemExit(
            f"torch reference ran {torch_ref['steps']} steps but this run "
            f"wants {STEPS}; delete {TORCH_JSON} or match NORTHSTAR_STEPS"
        )
    if native and STEPS % EVAL_EVERY:
        raise SystemExit(
            f"native variant scans {EVAL_EVERY} steps per dispatch; "
            f"NORTHSTAR_STEPS={STEPS} must be a multiple of it"
        )

    import contextlib

    import jax
    import jax.numpy as jnp

    from bpe_transformer_tpu.checkpointing import load_checkpoint, save_checkpoint
    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim import adamw_init
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_eval_step,
        make_scanned_train_step,
        make_train_step,
    )

    tokens = phase_data()
    train_toks, val_toks = split_tokens(tokens)
    schedule = batch_schedule(len(train_toks))
    cfg = model_config()
    device = jax.devices()[0]

    precision_ctx = (
        contextlib.nullcontext()
        if native
        else jax.default_matmul_precision("highest")
    )
    with precision_ctx:
        if native:
            step = make_scanned_train_step(cfg, TrainHParams(), EVAL_EVERY)
        else:
            step = make_train_step(cfg, TrainHParams())
        ev = make_eval_step(cfg)

        if not native and not ckpt_path.exists() and LEGACY_CKPT.exists():
            import shutil  # move, not rename: /tmp and the repo can be
                           # different filesystems (rename would EXDEV)
            ckpt_path.parent.mkdir(parents=True, exist_ok=True)
            shutil.move(str(LEGACY_CKPT), str(ckpt_path))
            print(f"migrated legacy checkpoint {LEGACY_CKPT} -> {ckpt_path}", file=sys.stderr)
        if ckpt_path.exists():
            payload = load_checkpoint(ckpt_path)
            ckpt_platform = payload["extra"].get("platform")
            ckpt_steps = payload["extra"].get("steps")
            ckpt_variant = payload["extra"].get("variant", "parity")
            if (
                ckpt_platform != device.platform
                or ckpt_steps != STEPS
                or ckpt_variant != variant
            ):
                # An interrupted --allow-cpu smoke must not seed the real
                # on-chip run (the capture would claim a trajectory trained
                # mostly on the wrong substrate), and a checkpoint from a
                # different-length protocol must not shortcut this one (a
                # stale iteration >= STEPS would skip training entirely and
                # write an inconsistent artifact); restart from scratch.
                print(
                    f"checkpoint is platform={ckpt_platform!r} steps={ckpt_steps!r} "
                    f"variant={ckpt_variant!r}; this run is "
                    f"platform={device.platform!r} steps={STEPS} variant={variant!r}; "
                    "discarding and starting fresh",
                    file=sys.stderr,
                )
                ckpt_path.unlink()
                payload = None
        else:
            payload = None
        if payload is not None:
            params, opt_state = payload["params"], payload["opt_state"]
            start_step = payload["iteration"]
            curve = payload["extra"]["curve"]
            train_s = payload["extra"]["train_s"]
            print(f"resuming from step {start_step}", file=sys.stderr)
        else:
            params = init_params(jax.random.PRNGKey(0), cfg)
            opt_state = adamw_init(params)
            start_step, curve, train_s = 0, [], 0.0

        def val_loss():
            losses = [
                float(ev(params, jnp.asarray(x), jnp.asarray(y)))
                for x, y in val_batches(val_toks)
            ]
            return sum(losses) / len(losses)

        def checkpoint(done_step: int) -> None:
            ckpt_path.parent.mkdir(parents=True, exist_ok=True)
            save_checkpoint(
                ckpt_path,
                params=params,
                opt_state=opt_state,
                iteration=done_step,
                extra={
                    "curve": curve,
                    "train_s": train_s,
                    "platform": device.platform,
                    "steps": STEPS,
                    "variant": variant,
                },
            )

        if native:
            # AOT-compile the scanned step OUTSIDE the timed loop (the torch
            # side pays no compile, so compile time must not pollute the
            # tokens/sec comparison).  lower() +
            # compile() never executes, so no donation or update happens.
            batch_aval = jax.ShapeDtypeStruct((EVAL_EVERY, BATCH, SEQ), jnp.int32)
            step = step.lower(params, opt_state, batch_aval, batch_aval).compile()
            # One dispatch per eval block: the EVAL_EVERY pre-drawn batches
            # are stacked (inner, B, S) and scanned on-device.  A resumed
            # run restarts at the block boundary its checkpoint recorded.
            for block_start in range(start_step, STEPS, EVAL_EVERY):
                xs, ys = zip(
                    *(
                        gather_batch(train_toks, schedule[i])
                        for i in range(block_start, block_start + EVAL_EVERY)
                    )
                )
                xs, ys = np.stack(xs), np.stack(ys)
                t0 = time.perf_counter()
                params, opt_state, m = step(
                    params, opt_state, jnp.asarray(xs), jnp.asarray(ys)
                )
                loss = float(jax.device_get(m["loss"]))  # execution barrier
                train_s += time.perf_counter() - t0
                done = block_start + EVAL_EVERY
                curve.append({"step": done, "train_loss": loss, "val_loss": val_loss()})
                print(f"jax step {done}: {curve[-1]}", file=sys.stderr)
                checkpoint(done)
        else:
            for i in range(start_step, STEPS):
                x, y = gather_batch(train_toks, schedule[i])
                t0 = time.perf_counter()
                params, opt_state, m = step(params, opt_state, jnp.asarray(x), jnp.asarray(y))
                loss = float(jax.device_get(m["loss"]))  # execution barrier
                train_s += time.perf_counter() - t0
                if (i + 1) % EVAL_EVERY == 0 or i == STEPS - 1:
                    curve.append({"step": i + 1, "train_loss": loss, "val_loss": val_loss()})
                    print(f"jax step {i + 1}: {curve[-1]}", file=sys.stderr)
                    checkpoint(i + 1)

    jax_tps = STEPS * BATCH * SEQ / train_s
    final_val = curve[-1]["val_loss"]
    result = {
        "metric": "north star: reference val loss on-accel at >=10x torch-CPU tok/s",
        "config": torch_ref["config"],
        "corpus": CORPUS,
        "steps": STEPS,
        "platform": device.platform,
        "device": str(device),
        "variant": variant,
        "precision": (
            "f32, TPU-default matmul precision (single-pass bf16 MXU), "
            f"{EVAL_EVERY} steps per scanned dispatch"
            if native
            else "f32, matmul precision=highest (parity with the torch-f32 oracle)"
        ),
        "steps_per_dispatch": EVAL_EVERY if native else 1,
        "curve": curve,
        "final_val_loss": {"jax": final_val, "torch_cpu": torch_ref["final_val_loss"]},
        "reached_reference": final_val <= torch_ref["final_val_loss"] + VAL_TOLERANCE,
        "reference_tolerance": VAL_TOLERANCE,
        "val_loss_delta_vs_torch": round(final_val - torch_ref["final_val_loss"], 4),
        "tokens_per_sec": {
            "jax": round(jax_tps, 1),
            "torch_cpu": torch_ref["tokens_per_sec"],
        },
        "speedup": round(jax_tps / torch_ref["tokens_per_sec"], 2),
        "captured_at_utc": time.strftime("%Y-%m-%dT%H:%M:%S+00:00", time.gmtime()),
    }
    # Self-describing artifact: embed the run manifest (git SHA, jax/device
    # versions, host) — best-effort down to the import, never at the cost
    # of the measurement.
    try:
        from bpe_transformer_tpu.telemetry.manifest import attach_manifest

        attach_manifest(
            result, kind="northstar", model_config=cfg, extra={"variant": variant}
        )
    except Exception as exc:
        print(f"manifest attach failed: {exc!r}", file=sys.stderr)
    capture_path.parent.mkdir(parents=True, exist_ok=True)
    _write_json(capture_path, result)
    print(json.dumps({k: result[k] for k in (
        "platform", "variant", "final_val_loss", "reached_reference", "speedup")}))
    # The measurement is COMPLETE either way — the artifact records the
    # verdict honestly — exit 0, and clear the exhausted checkpoint so a
    # deliberate re-run starts fresh.
    ckpt_path.unlink(missing_ok=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--phase", choices=["data", "torch", "jax"], default=None)
    ap.add_argument(
        "--variant", choices=["parity", "native"], default="parity",
        help="parity: matmul precision=highest, per-step dispatch (tracks "
        "the torch-f32 oracle).  native: TPU-default precision, "
        "EVAL_EVERY steps per scanned dispatch — the honest-throughput "
        "run; writes northstar_native.json",
    )
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="let --phase jax run on host CPU (smoke testing only; the "
        "capture then records platform=cpu)",
    )
    args = ap.parse_args()
    if args.phase == "data":
        phase_data()
        return 0
    if args.phase == "torch":
        phase_torch()
        return 0
    if args.phase == "jax":
        return phase_jax(args.allow_cpu, args.variant)
    phase_torch()  # runs data implicitly; both cached
    return phase_jax(args.allow_cpu, args.variant)


if __name__ == "__main__":
    sys.exit(main())
