"""Autoregressive sampling from a trained LM.

A capability the reference never implements (its contract stops at logits);
included so the framework is usable end-to-end: tokenize a prompt, decode
with temperature/top-k sampling, detokenize.

Implementation: generations that fit the context window run the KV-cached
one-XLA-program path (``models/decode.generate_cached``, honoring the
config's activation dtype); longer generations fall back to fixed-shape
sliding-window decode — the prompt lives in a ``context_length`` buffer and
every step re-runs the jitted forward on the full buffer, reading the logit
row at the current position (causal masking makes the padding beyond it
irrelevant).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.transformer import forward


@partial(jax.jit, static_argnames=("config", "temperature", "top_k", "top_p"))
def _sample_step(params, buf, length, key, *, config, temperature, top_k, top_p):
    from bpe_transformer_tpu.models.decode import _sample_from_logits

    logits = forward(params, buf[None, :], config)[0, length - 1]
    return _sample_from_logits(logits, key, temperature, top_k, top_p)


def generate_ids(
    params,
    config: ModelConfig,
    prompt_ids: list[int],
    max_new_tokens: int = 128,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    seed: int = 0,
    stop_id: int | None = None,
) -> list[int]:
    """Sample token ids continuing ``prompt_ids`` (sliding-window context)."""
    ctx = config.context_length
    prompt = list(prompt_ids)[-ctx:]
    if not prompt:
        raise ValueError("prompt must contain at least one token")

    if len(prompt) + max_new_tokens <= ctx:
        # KV-cached fast path: O(1) work per token, one XLA program for the
        # whole generation (models/decode.py); honors activation_dtype (bf16
        # cache/compute for the bf16 presets).  Safe for MoE configs too:
        # decode derives expert capacity from context_length (see
        # decode._ffn_decode), so its few-token calls never drop tokens —
        # cached and uncached sampling can differ only in the case where the
        # uncached full forward would itself drop tokens at max length.
        from bpe_transformer_tpu.models.decode import generate_cached

        ids = generate_cached(
            params,
            jnp.asarray([prompt], dtype=jnp.int32),
            jax.random.PRNGKey(seed),
            config=config,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            stop_id=stop_id,
        )
        # Post-stop tokens are pinned to stop_id inside the scan, so
        # truncating at the first occurrence reproduces the sliding-window
        # path's early exit exactly.
        out = [int(t) for t in np.asarray(ids[0])]
        if stop_id is not None and stop_id in out:
            out = out[: out.index(stop_id) + 1]
        return out

    # Sliding-window fallback (prompt + continuation exceed the context
    # window): full forward per token.
    if config.decode_attention_impl not in ("auto", "xla"):
        import sys

        print(
            "generate_ids: generation exceeds the context window, taking "
            "the sliding-window path — decode_attention_impl="
            f"{config.decode_attention_impl!r} only applies to the "
            "KV-cached path (shorten max_new_tokens to fit the window to "
            "use it)",
            file=sys.stderr,
        )
    buf = np.zeros(ctx, dtype=np.int32)
    buf[: len(prompt)] = prompt
    length = len(prompt)
    key = jax.random.PRNGKey(seed)

    out: list[int] = []
    buf_dev = jnp.asarray(buf)
    for _ in range(max_new_tokens):
        key, sub = jax.random.split(key)
        next_id = int(
            _sample_step(
                params,
                buf_dev,
                length,
                sub,
                config=config,
                temperature=temperature,
                top_k=top_k,
                top_p=top_p,
            )
        )
        out.append(next_id)
        if stop_id is not None and next_id == stop_id:
            break
        if length < ctx:
            buf_dev = buf_dev.at[length].set(next_id)
            length += 1
        else:
            buf_dev = jnp.roll(buf_dev, -1).at[ctx - 1].set(next_id)
    return out


def generate_text(
    params,
    config: ModelConfig,
    tokenizer,
    prompt: str = "",
    max_new_tokens: int = 128,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    seed: int = 0,
) -> str:
    """Encode ``prompt``, sample a continuation, return prompt + decode."""
    prompt_ids = tokenizer.encode(prompt) if prompt else [0]
    stop_id = None
    specials = getattr(tokenizer, "special_tokens", None) or []
    if specials:
        stop_id = tokenizer.encode(specials[0])[0]
    new_ids = generate_ids(
        params,
        config,
        prompt_ids,
        max_new_tokens=max_new_tokens,
        temperature=temperature,
        top_k=top_k,
        top_p=top_p,
        seed=seed,
        stop_id=stop_id,
    )
    return prompt + tokenizer.decode(new_ids)
