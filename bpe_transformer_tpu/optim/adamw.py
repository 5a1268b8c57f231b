"""AdamW as a pure XLA update function (optax-free).

Reference contract: `get_adamw_cls` (`/root/reference/tests/adapters.py:
470-474`) pinned by `test_optimizer.py:7-49` to match torch's AdamW within
1e-4 after 1000 steps.  We use torch's decoupled ordering: weight decay
multiplies the parameter before the Adam step is subtracted.

State is a pytree mirroring the parameter structure (first/second moments)
plus a scalar step count, so it shards with the parameters under any
``NamedSharding`` and checkpoints like any other pytree.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import Array


class AdamWState(NamedTuple):
    step: Array  # scalar int32
    m: Any  # first moment, same pytree as params
    v: Any  # second moment, same pytree as params


def adamw_init(params) -> AdamWState:
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        m=jax.tree_util.tree_map(zeros, params),
        v=jax.tree_util.tree_map(zeros, params),
    )


@jax.named_scope("optimizer")
def adamw_update(
    params,
    grads,
    state: AdamWState,
    lr: float | Array,
    *,
    betas: tuple[float, float] = (0.9, 0.999),
    eps: float = 1e-8,
    weight_decay: float = 0.01,
):
    """One AdamW step; returns ``(new_params, new_state)``.

    ``lr`` may be a traced scalar (schedule value) — no recompilation per
    step.  Moments accumulate in float32 even for bf16 params.
    """
    b1, b2 = betas
    step = state.step + 1
    t = step.astype(jnp.float32)
    bias1 = 1.0 - b1**t
    bias2 = 1.0 - b2**t

    def leaf_update(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m_new = b1 * m + (1.0 - b1) * g32
        v_new = b2 * v + (1.0 - b2) * jnp.square(g32)
        m_hat = m_new / bias1
        v_hat = v_new / bias2
        p32 = p.astype(jnp.float32)
        p_new = p32 * (1.0 - lr * weight_decay) - lr * m_hat / (
            jnp.sqrt(v_hat) + eps
        )
        return p_new.astype(p.dtype), m_new, v_new

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    out = [leaf_update(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_params, AdamWState(step=step, m=new_m, v=new_v)
