"""Gradient utilities: global-norm clipping over parameter pytrees.

Reference contract: `run_gradient_clipping` (`/root/reference/tests/
adapters.py:458-467`) — combined L2 over all grads, scale applied when the
norm exceeds the budget, matching ``torch.nn.utils.clip_grad_norm_``
(eps 1e-6 in the denominator).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array


def global_norm(tree) -> Array:
    """L2 norm over every array in a pytree, accumulated in float32."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in leaves)
    )


@jax.named_scope("grad_clip")
def clip_by_global_norm(grads, max_norm: float, eps: float = 1e-6):
    """Scale ``grads`` so their combined L2 norm is at most ``max_norm``.

    Returns ``(clipped_grads, pre_clip_norm)``.  The scale factor
    ``max_norm / (norm + eps)`` is only applied when the norm exceeds the
    budget — identical semantics to torch's ``clip_grad_norm_``.
    """
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + eps))
    return jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype), grads), norm
