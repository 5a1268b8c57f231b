"""Every prediction head of EvaByte at the published width, on the chip.

    python3 tools/check_evabyte_heads.py --seed 2147485350

The benchmark's ``correct`` scores prediction head 0 alone: the tick and
chunk programs return sampled bytes, so XLA is free to drop the other seven
heads' columns there.  This reads what it cannot: a prompt prefilled by
``PagedEngine``'s own chunks (bfloat16, the cell's configuration and seeded
weights), then teacher-forced ticks through ``paged_forward`` with **all**
``num_pred_heads x vocab_size`` float32 logits kept, across a window's
closing, against ``chipbench/reference_evabyte.py``'s full forward - and,
beside it, the reference's own float8 forward against its float32 one (the
control).  Per head, the widest absolute difference of a logit.  A wrong
head layout or wrong head weights read as wide as the logits themselves.
Run by the builder, outside any timed window; the last line is one JSON
object.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def read(config: dict, model_config, seed: int, prompt_len: int, ticks: int,
         *, chunk: int | None = None, dtype=None) -> dict:
    """``config`` is the configuration's file, ``model_config`` the program's
    ``ModelConfig`` of it.  The prompt's ``prompt_len`` ids and the
    ``ticks`` forced ones are drawn from ``seed``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bpe_transformer_tpu.models.decode import paged_forward, slot_cache
    from bpe_transformer_tpu.serving.kvpool.paged_engine import PagedEngine
    from chipbench import reference_evabyte as ref

    dtype = jnp.bfloat16 if dtype is None else dtype
    vocab, heads = config["vocab_size"], config["num_pred_heads"]
    width = config["window_size"]
    chunk = width if chunk is None else chunk
    tokens = np.random.default_rng(seed).integers(0, vocab, prompt_len + ticks)
    weights = ref.weights_from_seed(seed, config, dtype)
    windows = -(-len(tokens) // width)
    block = config["chunk_size"]
    eng = PagedEngine(
        weights, model_config, slots=2, block_size=block,
        prefill_chunk=chunk, prefill_buckets=(chunk,), prefix_cache=False,
        num_blocks=2 * (width // block + windows * (width // block // block)) + 1,
    )

    @functools.partial(jax.jit, donate_argnums=(1,))
    def all_logits(params, pool, tables, pos, active, tok, lm_head):
        cache = slot_cache(model_config, tables, pos, active, block_size=block)
        return paged_forward(params, tok, pool, cache, model_config, lm_head)[:2]

    slot = eng.begin(tokens[:prompt_len], max_new_tokens=ticks + 1, temperature=0.0)
    while eng.prefill_step(slot) is None:
        pass
    eng.flush()
    ours = []
    for position in range(prompt_len, len(tokens)):
        # One teacher-forced tick of the slot alone, its table row laid out
        # as `launch` lays it out.
        eng.cache.enter_window(slot, position)
        tok = np.zeros((eng.n_slots, 1), np.int32)
        pos = np.zeros(eng.n_slots, np.int32)
        active = np.zeros(eng.n_slots, bool)
        tok[slot], pos[slot], active[slot] = tokens[position], position, True
        logits, eng._pool = all_logits(
            eng._params, eng._pool, eng.cache.table_rows(), pos, active, tok, eng._lm_head
        )
        ours.append(np.asarray(logits[slot, 0], np.float32))
    ours = np.stack(ours).reshape(ticks, heads, vocab)
    del eng

    def theirs(quant):
        rows = ref.forward_logits(weights, tokens, config, quant)[prompt_len:]
        return rows.reshape(ticks, heads, vocab)

    sound = theirs(None)
    by_head = lambda a, b: [float(x) for x in np.abs(a - b).max(axis=(0, 2))]  # noqa: E731
    return {
        "seed": seed, "prompt": prompt_len, "ticks": ticks,
        "windows_closed_in_prefill": prompt_len // width,
        "windows_closed_in_ticks": (len(tokens) - 1) // width - (prompt_len - 1) // width,
        "logit_width": float(sound.max() - sound.min()),
        "program_widest_by_head": by_head(ours, sound),
        "control_widest_by_head": by_head(theirs("fp8"), sound),
        "device": jax.devices()[0].platform,
    }


def main(argv=None) -> int:
    from chipbench import run

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--prompt", type=int, default=6100)
    parser.add_argument("--ticks", type=int, default=64)
    args = parser.parse_args(argv)
    _, config = run.load_cell("evabyte.serve.long-doc")
    out = read(config, run.program_model_config(config), args.seed, args.prompt, args.ticks)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
