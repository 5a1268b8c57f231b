"""Compatibility seam: the torch-shaped reference adapters.

The reference's test suite never imports implementation modules — only the
21 adapter functions in its ``tests/adapters.py``
(`/root/reference/tests/adapters.py`).  ``compat.adapters`` implements that
full surface backed by this framework's JAX ops/models/optim/data/
serialization, converting ``torch.Tensor`` <-> ``jnp.ndarray`` only at the
boundary, so the reference (CS336-derived) suite runs green against the
TPU-native core.

The adapter names resolve lazily (PEP 562): ``adapters`` imports torch,
which jax-only processes must not pay for.
"""

_ADAPTER_NAMES = (
    "get_adamw_cls",
    "get_tokenizer",
    "run_cross_entropy",
    "run_embedding",
    "run_get_batch",
    "run_get_lr_cosine_schedule",
    "run_gradient_clipping",
    "run_linear",
    "run_load_checkpoint",
    "run_multihead_self_attention",
    "run_multihead_self_attention_with_rope",
    "run_rmsnorm",
    "run_rope",
    "run_save_checkpoint",
    "run_scaled_dot_product_attention",
    "run_silu",
    "run_softmax",
    "run_swiglu",
    "run_train_bpe",
    "run_transformer_block",
    "run_transformer_lm",
)


def __getattr__(name: str):
    if name in _ADAPTER_NAMES:
        import importlib

        module = importlib.import_module("bpe_transformer_tpu.compat.adapters")
        value = getattr(module, name)
        globals()[name] = value  # cache: resolve once per process
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = list(_ADAPTER_NAMES)
