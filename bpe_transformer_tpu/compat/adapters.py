"""The 21 reference adapter functions, backed by the JAX core.

Signature contract: `/root/reference/tests/adapters.py` (the CS336-derived
suite's only import surface).  torch tensors are converted to jnp at entry
and back at exit; all math runs in this framework's ops/models/optim/data/
checkpointing modules.
"""

from __future__ import annotations

import os
from collections.abc import Iterable
from typing import IO, Any, BinaryIO

import numpy as np
import torch

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.models.transformer import forward as lm_forward
from bpe_transformer_tpu.models.transformer import (
    params_from_state_dict,
    transformer_block,
)
from bpe_transformer_tpu.ops import (
    clip_by_global_norm,
    cross_entropy,
    embedding,
    linear,
    multihead_self_attention,
    rmsnorm,
    rope,
    rope_tables,
    scaled_dot_product_attention,
    silu,
    softmax,
    swiglu,
)
from bpe_transformer_tpu.optim.adamw import adamw_init, adamw_update
from bpe_transformer_tpu.optim.schedule import cosine_schedule
from bpe_transformer_tpu.tokenization import BPETokenizer, train_bpe


def _j(t: torch.Tensor) -> jnp.ndarray:
    return jnp.asarray(t.detach().cpu().numpy())


def _t(a) -> torch.Tensor:
    return torch.from_numpy(np.asarray(a))


# ----------------------------------------------------------- model ops


def run_linear(d_in, d_out, weights, in_features) -> torch.Tensor:
    return _t(linear(_j(in_features), _j(weights)))


def run_embedding(vocab_size, d_model, weights, token_ids) -> torch.Tensor:
    return _t(embedding(_j(weights), _j(token_ids)))


def run_swiglu(d_model, d_ff, w1_weight, w2_weight, w3_weight, in_features) -> torch.Tensor:
    return _t(swiglu(_j(in_features), _j(w1_weight), _j(w2_weight), _j(w3_weight)))


def run_scaled_dot_product_attention(Q, K, V, mask=None) -> torch.Tensor:
    jmask = _j(mask) if mask is not None else None
    return _t(scaled_dot_product_attention(_j(Q), _j(K), _j(V), jmask))


def run_multihead_self_attention(
    d_model, num_heads, q_proj_weight, k_proj_weight, v_proj_weight,
    o_proj_weight, in_features,
) -> torch.Tensor:
    return _t(
        multihead_self_attention(
            _j(in_features),
            _j(q_proj_weight), _j(k_proj_weight), _j(v_proj_weight),
            _j(o_proj_weight),
            num_heads,
            causal=True,
        )
    )


def run_multihead_self_attention_with_rope(
    d_model, num_heads, max_seq_len, theta,
    q_proj_weight, k_proj_weight, v_proj_weight, o_proj_weight,
    in_features, token_positions=None,
) -> torch.Tensor:
    positions = _j(token_positions) if token_positions is not None else None
    return _t(
        multihead_self_attention(
            _j(in_features),
            _j(q_proj_weight), _j(k_proj_weight), _j(v_proj_weight),
            _j(o_proj_weight),
            num_heads,
            positions=positions,
            rope_theta=theta,
            max_seq_len=max_seq_len,
            causal=True,
        )
    )


def run_rope(d_k, theta, max_seq_len, in_query_or_key, token_positions) -> torch.Tensor:
    return _t(
        rope(_j(in_query_or_key), _j(token_positions), theta=theta, max_seq_len=max_seq_len)
    )


def run_transformer_block(
    d_model, num_heads, d_ff, max_seq_len, theta, weights, in_features
) -> torch.Tensor:
    config = ModelConfig(
        vocab_size=1,  # unused by a single block
        context_length=max_seq_len,
        d_model=d_model,
        num_layers=1,
        num_heads=num_heads,
        d_ff=d_ff,
        rope_theta=theta,
    )
    prefixed = {f"layers.0.{k}": _j(v) for k, v in weights.items()}
    params = params_from_state_dict(
        prefixed | {"token_embeddings.weight": jnp.zeros((1, d_model)),
                    "ln_final.weight": jnp.ones(d_model),
                    "lm_head.weight": jnp.zeros((1, d_model))},
        num_layers=1,
    )
    x = _j(in_features)
    seq_len = x.shape[-2]
    cos, sin = rope_tables(d_model // num_heads, max_seq_len, theta)
    out = transformer_block(
        x, params["layers"][0], config, (cos, sin), jnp.arange(seq_len)
    )
    return _t(out)


def run_transformer_lm(
    vocab_size, context_length, d_model, num_layers, num_heads, d_ff,
    rope_theta, weights, in_indices,
) -> torch.Tensor:
    config = ModelConfig(
        vocab_size=vocab_size,
        context_length=context_length,
        d_model=d_model,
        num_layers=num_layers,
        num_heads=num_heads,
        d_ff=d_ff,
        rope_theta=rope_theta,
    )
    params = params_from_state_dict(
        {k: _j(v) for k, v in weights.items()}, num_layers
    )
    return _t(lm_forward(params, _j(in_indices), config))


def run_rmsnorm(d_model, eps, weights, in_features) -> torch.Tensor:
    return _t(rmsnorm(_j(in_features), _j(weights), eps=eps))


def run_silu(in_features) -> torch.Tensor:
    return _t(silu(_j(in_features)))


def run_softmax(in_features, dim) -> torch.Tensor:
    return _t(softmax(_j(in_features), axis=dim))


# ------------------------------------------------------------- training


def run_cross_entropy(inputs, targets) -> torch.Tensor:
    return _t(cross_entropy(_j(inputs), _j(targets)))


def run_gradient_clipping(parameters: Iterable[torch.nn.Parameter], max_l2_norm: float) -> None:
    params = [p for p in parameters if p.grad is not None]
    grads = {i: _j(p.grad) for i, p in enumerate(params)}
    clipped, _ = clip_by_global_norm(grads, max_l2_norm)
    for i, p in enumerate(params):
        p.grad.copy_(_t(clipped[i]).to(p.grad.dtype))


class _JaxBackedAdamW(torch.optim.Optimizer):
    """torch-Optimizer facade over the pure-JAX AdamW update.

    Gradients cross to jnp, `optim.adamw.adamw_update` computes the step,
    and parameters/moments cross back — torch autograd drives, XLA updates.
    """

    def __init__(self, params, lr=1e-3, weight_decay=0.01, betas=(0.9, 0.999), eps=1e-8):
        defaults = dict(lr=lr, weight_decay=weight_decay, betas=betas, eps=eps)
        super().__init__(params, defaults)

    @torch.no_grad()
    def step(self, closure=None):
        loss = closure() if closure is not None else None
        for group in self.param_groups:
            for p in group["params"]:
                if p.grad is None:
                    continue
                state = self.state[p]
                if not state:
                    state["step"] = torch.zeros((), dtype=torch.int32)
                    state["exp_avg"] = torch.zeros_like(p, dtype=torch.float32)
                    state["exp_avg_sq"] = torch.zeros_like(p, dtype=torch.float32)

                from bpe_transformer_tpu.optim.adamw import AdamWState

                jax_state = AdamWState(
                    step=jnp.asarray(state["step"].numpy()),
                    m=_j(state["exp_avg"]),
                    v=_j(state["exp_avg_sq"]),
                )
                new_p, new_state = adamw_update(
                    _j(p),
                    _j(p.grad),
                    jax_state,
                    lr=group["lr"],
                    betas=tuple(group["betas"]),
                    eps=group["eps"],
                    weight_decay=group["weight_decay"],
                )
                p.copy_(_t(new_p).to(p.dtype))
                state["step"] = _t(new_state.step)
                state["exp_avg"] = _t(new_state.m)
                state["exp_avg_sq"] = _t(new_state.v)
        return loss


def get_adamw_cls() -> Any:
    return _JaxBackedAdamW


def run_get_lr_cosine_schedule(
    it, max_learning_rate, min_learning_rate, warmup_iters, cosine_cycle_iters
):
    return cosine_schedule(
        it, max_learning_rate, min_learning_rate, warmup_iters, cosine_cycle_iters
    )


# ------------------------------------------------------------------ data


def run_get_batch(dataset, batch_size, context_length, device) -> tuple[torch.Tensor, torch.Tensor]:
    from bpe_transformer_tpu.data.dataset import get_batch

    # Validate the device eagerly (invalid ordinals must raise).
    torch.empty(0, device=device)
    x, y = get_batch(np.asarray(dataset), batch_size, context_length)
    return (
        torch.from_numpy(x).long().to(device),
        torch.from_numpy(y).long().to(device),
    )


# -------------------------------------------------------- serialization


def _tree_to_numpy(obj):
    if torch.is_tensor(obj):
        return obj.detach().cpu().numpy()
    if isinstance(obj, dict):
        return {k: _tree_to_numpy(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_numpy(v) for v in obj)
    return obj


def _tree_to_torch(obj):
    if isinstance(obj, np.ndarray):
        return torch.from_numpy(obj.copy())
    if isinstance(obj, dict):
        return {k: _tree_to_torch(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_torch(v) for v in obj)
    return obj


def run_save_checkpoint(
    model: torch.nn.Module,
    optimizer: torch.optim.Optimizer,
    iteration: int,
    out: str | os.PathLike | BinaryIO | IO[bytes],
):
    from bpe_transformer_tpu.checkpointing import save_checkpoint

    save_checkpoint(
        out,
        params=_tree_to_numpy(dict(model.state_dict())),
        opt_state=None,
        iteration=iteration,
        extra={"torch_optimizer_state": _tree_to_numpy(optimizer.state_dict())},
    )


def run_load_checkpoint(
    src: str | os.PathLike | BinaryIO | IO[bytes],
    model: torch.nn.Module,
    optimizer: torch.optim.Optimizer,
) -> int:
    from bpe_transformer_tpu.checkpointing import load_checkpoint

    payload = load_checkpoint(src)
    model.load_state_dict(_tree_to_torch(payload["params"]))
    optimizer.load_state_dict(_tree_to_torch(payload["extra"]["torch_optimizer_state"]))
    return payload["iteration"]


# --------------------------------------------------------- tokenization


def get_tokenizer(
    vocab: dict[int, bytes],
    merges: list[tuple[bytes, bytes]],
    special_tokens: list[str] | None = None,
) -> Any:
    return BPETokenizer(vocab=vocab, merges=merges, special_tokens=special_tokens)


def run_train_bpe(
    input_path: str | os.PathLike,
    vocab_size: int,
    special_tokens: list[str],
    **kwargs,
) -> tuple[dict[int, bytes], list[tuple[bytes, bytes]]]:
    return train_bpe(
        input_path=input_path, vocab_size=vocab_size, special_tokens=special_tokens
    )
