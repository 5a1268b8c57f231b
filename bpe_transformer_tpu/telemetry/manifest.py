"""Run manifests: the self-describing header record of every telemetry stream.

A capture JSON or metrics JSONL found weeks later must answer "what code, on
what hardware, at what config produced this?" without the shell history that
launched it.  ``run_manifest`` collects exactly that — config dicts, mesh
layout, jax/device versions, git SHA, host — as one JSON-serializable dict
with ``kind="manifest"``, logged first into every stream
(``training/loop.py``, ``benchmarks/northstar.py``) and embedded in
bench captures.

Everything here degrades gracefully: no git checkout, no jax backend, or no
mesh just omits those fields rather than failing the run it describes.
"""

from __future__ import annotations

import dataclasses
import platform
import socket
import subprocess
import sys
import time
from pathlib import Path


def git_sha(cwd: str | Path | None = None) -> str | None:
    """The current commit SHA (with ``-dirty`` suffix when the tree has
    uncommitted changes), or None outside a git checkout."""
    if cwd is None:
        cwd = Path(__file__).resolve().parent
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=cwd, capture_output=True, text=True, timeout=5,
        )
        if sha.returncode != 0:
            return None
    except (OSError, subprocess.SubprocessError):
        return None
    try:
        dirty = subprocess.run(
            ["git", "status", "--porcelain"],
            cwd=cwd, capture_output=True, text=True, timeout=5,
        )
        suffix = "-dirty" if dirty.returncode == 0 and dirty.stdout.strip() else ""
    except (OSError, subprocess.SubprocessError):
        # The dirty check is best-effort decoration — a slow `git status`
        # (large tree, cold NFS) must not discard the SHA already in hand.
        suffix = ""
    return sha.stdout.strip() + suffix


def _config_dict(config) -> dict | None:
    if config is None:
        return None
    if dataclasses.is_dataclass(config) and not isinstance(config, type):
        return dataclasses.asdict(config)
    if isinstance(config, dict):
        return dict(config)
    return {"repr": repr(config)}


def host_manifest(kind: str) -> dict:
    """The header record WITHOUT the jax/device probe: for jax-free
    emitters (the fleet router and aggregator) that must never initialize
    an accelerator backend as a side effect of describing themselves —
    ``run_manifest`` would touch ``jax.devices()`` whenever jax happens to
    be installed, and a front-end box colocated with a chip must not grab
    it just to write a stream header."""
    return {
        "kind": "manifest",
        "run_kind": kind,
        "time_utc": time.strftime("%Y-%m-%dT%H:%M:%S+00:00", time.gmtime()),
        "host": socket.gethostname(),
        "python": platform.python_version(),
        "argv": list(sys.argv),
        "git_sha": git_sha(),
    }


def run_manifest(
    kind: str = "train",
    model_config=None,
    loop_config=None,
    mesh=None,
    parallel: str | None = None,
    extra: dict | None = None,
) -> dict:
    """Build the header record.  ``mesh`` is a ``jax.sharding.Mesh`` (its
    axis-name -> size layout is recorded); configs may be dataclasses or
    dicts.  Device/jax fields are best-effort — absent when no backend is
    reachable (e.g. the report tool or a replay path)."""
    record: dict = host_manifest(kind)
    try:
        from bpe_transformer_tpu import __version__

        record["package_version"] = __version__
    except Exception:
        pass
    try:
        import jax

        record["jax_version"] = jax.__version__
        devices = jax.devices()
        record["devices"] = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
    except Exception:
        # No jax / no backend: the manifest still describes the host run.
        pass
    if mesh is not None:
        try:
            record["mesh"] = {name: int(size) for name, size in mesh.shape.items()}
        except Exception:
            record["mesh"] = {"repr": repr(mesh)}
    if parallel is not None:
        record["parallel"] = parallel
    if model_config is not None:
        record["model_config"] = _config_dict(model_config)
    if loop_config is not None:
        record["loop_config"] = _config_dict(loop_config)
    if extra:
        record.update(extra)
    return record


def attach_manifest(payload: dict, kind: str, **kwargs) -> dict:
    """Best-effort: embed ``run_manifest(kind, **kwargs)`` as
    ``payload["manifest"]``.  Capture payloads (northstar.py)
    share one contract here: manifest trouble must never lose the
    measurement — on any failure the payload is returned un-annotated and
    the error goes to stderr."""
    try:
        payload["manifest"] = run_manifest(kind=kind, **kwargs)
    except Exception as exc:
        print(f"manifest attach failed: {exc!r}", file=sys.stderr)
    return payload
