"""Speculative decoding subsystem (draft propose → target verify → KV
rewind) riding the paged serving engine.

- `draft`  — `DraftSpec` (jax-free declarative config: tiny geometry or a
  truncated-layer view of the target) + `DraftModel` + the draft-side
  device programs (K-step propose scan, bucketed draft prefill);
- `engine` — `SpecEngine`: the PagedEngine contract where one tick emits
  1..K+1 tokens per slot via one batched target verify pass
  (`models/decode.paged_forward`, K+1 rows a slot) and Leviathan
  rejection sampling, with the rejected tail rolled back through
  `PagedEngine.rewind`.

`DraftSpec` imports no jax — the CLI validates ``--draft-config`` (vocab
compatibility, geometry completeness) before any accelerator work.
"""

from bpe_transformer_tpu._lazy import lazy_attrs

__getattr__ = lazy_attrs(
    __name__,
    {
        "DraftSpec": "draft",
        "DraftModel": "draft",
        "SpecEngine": "engine",
    },
)

__all__ = ["DraftModel", "DraftSpec", "SpecEngine"]
