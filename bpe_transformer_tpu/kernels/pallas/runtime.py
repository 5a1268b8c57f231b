"""Shared kernel runtime helpers."""

from __future__ import annotations

import jax


def pick_block(n: int, target: int, step: int) -> int:
    """Largest divisor of ``n`` that is a multiple of ``step`` and <=
    ``target``, or 0 when there is none (the caller names its fallback:
    the whole axis, or a ragged last tile)."""
    best = 0
    b = step
    while b <= min(target, n):
        if n % b == 0:
            best = b
        b += step
    return best


#: Shortest sequence at which the flash kernels beat materialized scores on
#: the TPU, and the narrowest head they were timed at there (v5e, PERF.md
#: §6 PR 27: at S=256 / d_head 32 XLA wins 0.46 ms against 0.76, at S=512 /
#: d_head 64 flash wins 1.73 against 1.91 in bf16 and 2.45 against 4.06 in
#: float32, at S=1,024 6.5 against 11.8).
FLASH_MIN_SEQ = 512
FLASH_MIN_D_HEAD = 64

#: Widest flash tile.  Grid steps cost more than masked-out work at these
#: sizes: on the v5e 1,024 x 1,024 beat every smaller forward tile at
#: S=1,024 (2.36 ms a layer against 3.71 at 512 x 512 and 6.20 at 256).
FLASH_MAX_TILE = 1024


def flash_tiles(seq_len: int) -> tuple[int, int]:
    """``(block_q, block_k)`` of the flash-attention forward for causal
    self-attention over ``seq_len`` positions: the largest divisor of the
    sequence that is a multiple of the 128-lane width and at most
    :data:`FLASH_MAX_TILE` — the whole sequence up to 1,024.  A sequence
    no such tile divides (a forced path only: :func:`attention_path` sends
    it to XLA) keeps the 256 of before the choice existed, the sequence
    padded to a multiple of it, or runs as one tile under 256 positions.
    The backward holds five score tiles live where the forward holds two
    and caps its own tiles at 512 (`flash_attention._bwd_tiles`).  Neither
    d_head (64) nor the dtype (bf16, float32) moved the best tile in the
    timings, so neither is asked."""
    block = pick_block(seq_len, FLASH_MAX_TILE, 128) or min(256, seq_len)
    return block, block


def attention_path(seq_len: int, d_head: int, backend: str | None = None) -> str:
    """``"flash"`` or ``"xla"`` for causal self-attention at this shape:
    the Pallas flash kernels where writing S x S scores to HBM costs more
    than it saves — on the TPU, from :data:`FLASH_MIN_SEQ` positions that
    128-lane tiles divide, at heads at least :data:`FLASH_MIN_D_HEAD` wide,
    in bfloat16 and float32 alike — and materialized XLA attention
    elsewhere (short or unaligned sequences, e.g. a raw prompt length;
    narrow heads, untimed; every other backend, where the kernels would run
    in interpret mode).  What the shape cannot say — that XLA's SPMD
    partitioner will split the program, which no Mosaic kernel survives —
    the caller that builds such a program says by forcing ``"xla"``
    (`parallel.train_step.partitioned_config`)."""
    backend = backend or jax.default_backend()
    if (
        backend == "tpu"
        and seq_len >= FLASH_MIN_SEQ
        and seq_len % 128 == 0
        and d_head >= FLASH_MIN_D_HEAD
    ):
        return "flash"
    return "xla"


#: Keys a step of the paged decode kernel copies and computes on, and what
#: its four VMEM buffers (K and V, two each) may hold together.
PAGED_GROUP_KEYS = 256
PAGED_BUFFER_BYTES = 4 * 1024 * 1024


def paged_group_blocks(block_size: int, width: int, itemsize: int) -> int:
    """Pool blocks to a step of `paged_decode_attention`: enough for
    :data:`PAGED_GROUP_KEYS` keys (a step's fixed cost is spread over them,
    and a chain is walked in whole groups, so a larger group computes on
    more dead rows of short chains), fewer where four buffers of
    ``width``-wide rows would pass :data:`PAGED_BUFFER_BYTES`; at least
    one."""
    fit = PAGED_BUFFER_BYTES // (4 * block_size * width * itemsize)
    return max(1, min(PAGED_GROUP_KEYS // block_size, fit))


def decode_attention_path(
    one_row: bool, blocks_per_slot: int, block_size: int, width: int,
    itemsize: int, backend: str | None = None,
) -> str:
    """``"paged"`` or ``"xla"`` for a step's attention over a dense block
    pool (`models/decode.DenseRows`): the paged-native kernel, which reads
    the blocks the slots hold and no others, on the TPU for one row a slot
    (the decode tick) where the pool's rows are whole lane tiles, its
    blocks whole sublane tiles at either width, and the table at least one
    group wide; gathered rows under XLA elsewhere - several rows a slot (a
    verify pass), tables shorter than a group (tiny contexts), unaligned
    test shapes, and every other backend, where the kernel would run in
    interpret mode."""
    backend = backend or jax.default_backend()
    if (
        backend == "tpu"
        and one_row
        and width % 128 == 0
        and block_size % 16 == 0
        and blocks_per_slot >= paged_group_blocks(block_size, width, itemsize)
    ):
        return "paged"
    return "xla"


def interpret_mode() -> bool:
    """Pallas TPU kernels run in interpret mode on non-TPU backends
    (CPU tests, debugging); compiled Mosaic otherwise."""
    return jax.default_backend() != "tpu"
