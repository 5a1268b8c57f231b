"""``python -m bpe_transformer_tpu.utils.chip_probe``: what this process
sees of the accelerator, as one JSON line — the child `chip_smoke.py`
starts first, because its own process must stay off JAX (one process per
chip).

Always reports the run manifest's ``devices`` record, the jax / jaxlib /
libtpu versions and ``kernels.pallas.runtime.interpret_mode()``.  With
``--lower-train CONFIG.json`` it also lowers (never compiles or runs) the
single-device train step for that config on abstract shapes and counts the
Mosaic custom calls in it — the proof that ``attention_impl="flash"``
really put the kernel into the step the trainer is about to compile.  With
``--expect-platform P`` it exits 3 right after the device record when the
platform is another one, before any model code is traced.

:func:`require_tpu` is the same refusal for the bench scripts, in-process.
"""

from __future__ import annotations

import argparse
import json
import sys
from importlib import metadata


def require_tpu(script: str) -> None:
    """For bench scripts, called once jax is imported and before anything
    is measured: a number taken off the chip is not a device metric, so
    exit 3 unless the backend is the TPU.  ``JAX_PLATFORMS=cpu`` given
    explicitly is the one way to rehearse a script's control flow on the
    CPU — its rows then say ``platform: cpu``."""
    import os

    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu":
        print(f"{script}: JAX_PLATFORMS=cpu given — a rehearsal, not a "
              "measurement", file=sys.stderr)
        return
    print(f"{script}: backend is {platform!r}, not a TPU; refusing to "
          "measure (set JAX_PLATFORMS=cpu to rehearse the control flow)",
          file=sys.stderr)
    raise SystemExit(3)


def _version(dist: str) -> str | None:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return None


def lower_train_step(config_path: str, batch_size: int) -> dict:
    """Lower the `training.train_step.make_train_step` program for the
    config on ShapeDtypeStructs (no device memory) and count its kernels."""
    import jax
    import jax.numpy as jnp

    from bpe_transformer_tpu.models import ModelConfig, init_params
    from bpe_transformer_tpu.optim.adamw import adamw_init
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_train_step,
    )

    config = ModelConfig.from_json(config_path)
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), config)
    )
    opt_state = jax.eval_shape(adamw_init, params)
    tokens = jax.ShapeDtypeStruct(
        (batch_size, config.context_length), jnp.int32
    )
    lowered = make_train_step(config, TrainHParams()).lower(
        params, opt_state, tokens, tokens
    )
    return {
        "attention_impl": config.attention_impl,
        "train_step_tpu_custom_calls": lowered.as_text().count(
            "tpu_custom_call"
        ),
    }


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="chip_probe", description=__doc__)
    parser.add_argument("--expect-platform", default=None)
    parser.add_argument("--lower-train", default=None, metavar="CONFIG.json")
    parser.add_argument("--batch-size", type=int, default=8)
    args = parser.parse_args(argv)

    from bpe_transformer_tpu.kernels.pallas.runtime import interpret_mode
    from bpe_transformer_tpu.telemetry.manifest import run_manifest

    record = {
        "devices": run_manifest(kind="probe").get("devices"),
        "jax": _version("jax"),
        "jaxlib": _version("jaxlib"),
        "libtpu": _version("libtpu"),
    }
    platform = (record["devices"] or {}).get("platform")
    if args.expect_platform and platform != args.expect_platform:
        print(json.dumps(record), flush=True)
        print(f"chip_probe: platform is {platform!r}, expected "
              f"{args.expect_platform!r}", file=sys.stderr)
        return 3
    record["interpret_mode"] = interpret_mode()
    if args.lower_train:
        record.update(lower_train_step(args.lower_train, args.batch_size))
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
