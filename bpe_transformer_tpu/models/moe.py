"""Mixture-of-experts FFN (top-1 Switch / top-k GShard routing) with expert
parallelism.

No reference precedent (SURVEY §2.4 lists EP as absent); built TPU-first:
expert weights are stacked on a leading ``(n_experts, ...)`` dim and expert
compute is a single batched einsum over all experts — no per-expert Python
loops, fully static shapes.  Two dispatch formulations share identical
routing semantics (``ModelConfig.moe_dispatch``):

* ``"einsum"`` (default): dense one-hot dispatch/combine tensors in the
  GShard style; under an expert-sharded mesh GSPMD turns the dispatch
  einsums into all-to-alls over ICI.
* ``"gather"``: tokens reach their expert slots by row gather/scatter of
  indices — the dense einsums cost ``2·n·e·cap·d`` flops each (more than
  the expert FFN itself at training shapes), gathers move only the rows.

Semantics (Switch Transformer, Fedus et al. 2021; GShard, Lepikhin et al.
2020 — both public):

* each token routes to its ``router_top_k`` highest-probability experts;
  with k=1 the gate is the raw softmax prob (Switch), with k>1 gates are
  renormalized over the chosen experts (GShard top-2);
* per-expert capacity ``ceil(capacity_factor * tokens / n_experts)``;
  overflow tokens are dropped (their FFN output is zero, the residual
  connection carries them through);
* load-balance auxiliary loss ``n_experts * sum_e f_e * P_e`` (f = fraction
  of tokens dispatched to e, P = mean router probability of e) encourages
  uniform routing; added to the training loss with ``router_aux_weight``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import Array

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.ops.core import relu2, silu


def init_moe_params(rng: jax.Array, config: ModelConfig, dtype=jnp.float32) -> dict:
    """Stacked expert weights + router for one MoE FFN layer: the router
    over all its outputs (``n_experts`` and the zero experts after them),
    the stacks of the experts held here (``config.local_experts``), a
    ``"shared"`` stack where the config has shared experts (of the routed
    width, or ``shared_d_ff``) and a ``"router_bias"`` of zeros where it has
    one.  An expert is ``w1``, ``w2`` and - the SwiGLU's third matrix, which
    ``expert_activation="relu2"`` has not - ``w3``."""
    e, d, ff = config.router_outputs, config.d_model, config.moe_d_ff
    held, shared = config.local_experts, config.n_shared_experts

    def dense(key, shape, std=0.02):
        return (
            jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * std
        ).astype(dtype)

    def experts(keys, count, width):
        stack = {
            "w1": dense(keys[0], (count, width, d)),
            "w2": dense(keys[1], (count, d, width)),
        }
        if config.expert_activation == "swiglu":
            stack["w3"] = dense(keys[2], (count, width, d))
        return stack

    k = jax.random.split(rng, 4)
    params = {"router": dense(k[0], (e, d)), **experts(k[1:], held, ff)}
    if config.router_bias:
        params["router_bias"] = jnp.zeros((e,), jnp.float32)
    if shared:
        ks = jax.random.split(jax.random.fold_in(rng, 1), 3)
        params["shared"] = experts(ks, shared, config.shared_ff)
    return params


def expert_capacity(n_tokens: int, n_experts: int, capacity_factor: float) -> int:
    return max(1, math.ceil(capacity_factor * n_tokens / n_experts))


def switch_ffn(
    x: Array, moe_params: dict, config: ModelConfig
) -> tuple[Array, Array]:
    """Top-k routed SwiGLU experts.  Returns ``(output, aux_loss)``.

    ``router_top_k == 1`` is Switch routing (gate = raw softmax prob of the
    winning expert); ``k > 1`` is GShard-style top-k (gates renormalized over
    the chosen experts).  Capacity fills rank-major — every token's first
    choice is queued before any token's second choice — so a congested
    expert sheds low-priority assignments first.

    This is the training layer.  Serving (`models/decode.py`) takes
    :func:`dropless_moe`, which has no capacity to run out of.

    ``x``: (..., d_model); routing flattens all leading dims into one token
    axis (static shape under jit).
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    n = math.prod(orig_shape[:-1])
    tokens = x.reshape(n, d)
    e = config.n_experts
    top_k = config.router_top_k
    cap = expert_capacity(n, e, config.capacity_factor)

    # Router in float32 for stable softmax/argmax.
    logits = jnp.einsum(
        "nd,ed->ne", tokens.astype(jnp.float32), moe_params["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (n, e)
    topk_probs, topk_idx = jax.lax.top_k(probs, top_k)  # (n, k)
    if top_k == 1:
        gates = topk_probs  # Switch: raw winning probability
    else:
        gates = topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)

    assign = jax.nn.one_hot(topk_idx.T, e, dtype=jnp.float32)  # (k, n, e)
    # Queue position of each (rank, token) assignment within its expert,
    # rank-major: flatten (k, n) so all rank-0 rows precede rank-1 rows.
    flat = assign.reshape(top_k * n, e)
    pos = jnp.cumsum(flat, axis=0) * flat - flat  # 0-based, 0 elsewhere
    keep = flat * (pos < cap)  # drop overflow assignments

    compute_dtype = tokens.dtype
    if config.moe_dispatch == "gather":
        # Index-routed dispatch: identical assignments/positions/gates, but
        # tokens reach their expert slots by row gather instead of the dense
        # (n, e, cap) one-hot einsums, whose 2·n·e·cap·d flops EACH rival
        # the expert FFN compute itself at training shapes.
        kn = top_k * n
        # Row i of `flat` is (rank i // n, token i % n); its assigned expert
        # and queue position live in that row's single nonzero column.
        expert_of_row = topk_idx.T.reshape(kn)
        pos_of_row = jnp.sum(pos, axis=1).astype(jnp.int32)
        kept = jnp.sum(keep, axis=1) > 0
        src_token = (jnp.arange(kn, dtype=jnp.int32) % n)
        # Flat slot index; dropped assignments land on a sentinel slot past
        # the real e*cap range.
        dest = jnp.where(kept, expert_of_row * cap + pos_of_row, e * cap)
        # slot -> source token (sentinel n = out of bounds, reads a zero
        # row below).  Kept destinations are unique by construction (cumsum
        # queueing), so the scatter is collision-free over real slots.
        slot_src = (
            jnp.full((e * cap + 1,), n, jnp.int32).at[dest].set(src_token)
        )
        # mode="fill": empty slots (index n, out of bounds) read zeros.
        # Deliberately NOT a concat-of-a-zero-row + clamped take: gathering
        # from a concatenation of a batch-sharded operand miscompiles under
        # the GSPMD partitioner (wrong rows near the shard boundary —
        # tests/test_moe.py::test_ep_step_matches_single_device[gather]),
        # while an OOB-fill gather partitions correctly.
        expert_in = jnp.take(
            tokens, slot_src[: e * cap], axis=0, mode="fill", fill_value=0
        ).reshape(e, cap, d)
    else:
        dispatch = (
            keep[:, :, None]
            * jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
        ).reshape(top_k, n, e, cap)
        combine = gates.T[:, :, None, None] * dispatch  # (k, n, e, cap)
        # A token holds at most one slot per expert, so summing ranks is
        # exact.
        dispatch = jnp.sum(dispatch, axis=0)  # (n, e, cap)
        combine = jnp.sum(combine, axis=0)  # (n, e, cap)
        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(compute_dtype), tokens)

    # Expert SwiGLU, batched over the expert dim.
    up = jnp.einsum("ecd,efd->ecf", expert_in, moe_params["w1"])
    lin = jnp.einsum("ecd,efd->ecf", expert_in, moe_params["w3"])
    h = silu(up) * lin
    expert_out = jnp.einsum("ecf,edf->ecd", h, moe_params["w2"])

    if config.moe_dispatch == "gather":
        # Dropped assignments carry the sentinel dest e*cap: out of bounds,
        # filled with zeros (same no-concat rule as the dispatch gather).
        out_rows = jnp.take(
            expert_out.reshape(e * cap, d), dest, axis=0,
            mode="fill", fill_value=0,
        )  # (k·n, d)
        gates_flat = (gates.T.reshape(kn) * jnp.sum(keep, axis=1)).astype(
            compute_dtype
        )
        out = jnp.sum(
            (out_rows * gates_flat[:, None]).reshape(top_k, n, d), axis=0
        )
    else:
        out = jnp.einsum("nec,ecd->nd", combine.astype(compute_dtype), expert_out)

    # Load-balance loss over the *pre-capacity* first-choice assignments
    # (the Switch definition; ranks >= 1 follow the same router so the
    # gradient signal is unchanged).
    frac_tokens = jnp.mean(assign[0], axis=0)  # (e,)
    frac_probs = jnp.mean(probs, axis=0)  # (e,)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    return out.reshape(orig_shape), aux


# ------------------------------------------------------------ dropless path

#: The name under which a served tree holds an expert layer's down
#: projection as ``(experts, d_ff, d_model)`` in place of the torch-layout
#: ``"w2"`` (`serving_layout`).
W2_RELAID = "w2_relaid"


def serving_layout(moe_params: dict) -> dict:
    """One expert layer's tree as a serving engine holds it: the routed
    experts' down projection ``"w2"`` ``(experts, d_model, d_ff)`` laid out
    once as `W2_RELAID` ``(experts, d_ff, d_model)`` where the grouped
    matmul reads that form without a copy
    (`kernels/pallas/grouped_matmul.relaid_rhs`: an expert width that is not
    a whole number of lane tiles), every other leaf - and every other tree,
    whole - as it is.  `dropless_moe` takes whichever the tree holds."""
    from bpe_transformer_tpu.kernels.pallas.grouped_matmul import relaid_rhs

    w2 = moe_params.get("w2")
    if w2 is None or w2.ndim != 3 or not relaid_rhs(w2.shape[-1]):
        return moe_params
    out = {name: leaf for name, leaf in moe_params.items() if name != "w2"}
    out[W2_RELAID] = jnp.swapaxes(w2, 1, 2)
    return out


def route(
    tokens: Array, router: Array, config: ModelConfig, bias: Array | None = None
) -> tuple[Array, Array]:
    """Scores over all the router's outputs in float32, the
    ``router_top_k`` largest - of ``scores + bias`` where the router has a
    selection ``bias`` - and their gates: ``(expert ids (n, k), gates (n,
    k))``.  Softmax top-1 keeps the raw probability (Switch), as does every
    choice without ``norm_topk_prob``; every other case renormalizes the
    chosen scores to sum to one.  Gates are scaled by
    ``routed_scaling_factor``."""
    logits = jnp.einsum(
        "nd,ed->ne", tokens.astype(jnp.float32), router.astype(jnp.float32)
    )
    if config.moe_router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top_s, top_i = jax.lax.top_k(scores, config.router_top_k)
    else:
        _, top_i = jax.lax.top_k(scores + bias, config.router_top_k)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if config.norm_topk_prob and not (
        config.moe_router == "softmax" and config.router_top_k == 1
    ):
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    if config.routed_scaling_factor != 1.0:
        top_s = top_s * config.routed_scaling_factor
    return top_i, top_s


def dropless_moe(
    x: Array, moe_params: dict, config: ModelConfig, valid: Array | None = None
) -> tuple[Array, Array]:
    """The served expert layer: nothing is dropped and there is no
    capacity.  Returns ``(output, counts)``.

    Every token is routed over all ``n_experts``; the assignments that land
    on the experts held here (``expert_offset .. + local_experts``) are
    sorted by expert and go through one grouped matmul per matrix of an
    expert - three of a SwiGLU, two around a squared ReLU
    (``expert_activation``; `kernels/pallas/grouped_matmul.py`; the down
    projection as the torch-layout ``"w2"`` of a raw tree or as a served
    tree's `W2_RELAID`, the same product either way) - which
    visits only experts that got a row; each token's output is the
    gate-weighted sum of its held experts' results.  What the absent experts would add is left out: with
    ``experts_held=None`` that is nothing, with a share it is the other
    processes' part of an expert-parallel layer.  Shared experts, where the
    config has them, see every token and are averaged and added (one shared
    expert is added whole: its average is itself), at their own width where
    the config gives one (``shared_d_ff``).

    ``valid`` (tokens,) bool leaves rows out of the expert computation
    altogether (padded chunk rows, idle slots); their output is the shared
    part alone.  Shapes are static: the sorted buffer has a row for every
    assignment there could be, ``tokens * router_top_k``.

    Router outputs from ``n_experts`` on are **zero experts**
    (``n_zero_experts``): they have no weights and live with the token, so
    every process computes their part whole - the token's gates on them,
    summed, times the layer's input - and their assignments never enter the
    sort or the grouped matmul, whose rows so vary by token.

    ``counts`` is int32 ``[tokens routed, assignments held here, non-empty
    expert groups computed]`` and, where the config has zero experts,
    ``[assignments on zero experts]`` - the ``moe_*`` counters of
    ``stats()`` (a config without them compiles to the programs it had).
    """
    from bpe_transformer_tpu.kernels.pallas.grouped_matmul import grouped_matmul

    orig_shape = x.shape
    d = orig_shape[-1]
    n = math.prod(orig_shape[:-1])
    tokens = x.reshape(n, d)
    top_k, held = config.router_top_k, config.local_experts
    kn = n * top_k

    with jax.named_scope("block/moe/router"):
        top_i, gates = route(
            tokens, moe_params["router"], config, moe_params.get("router_bias")
        )
        # A zero expert's id lies past every held expert's: never local.
        local = top_i - config.expert_offset
        is_local = (local >= 0) & (local < held)
        is_zero = top_i >= config.n_experts if config.n_zero_experts else None
        if valid is not None:
            is_local &= valid.reshape(n)[:, None]
            if is_zero is not None:
                is_zero &= valid.reshape(n)[:, None]
        # Row r of the flat assignment list is (token r // k, rank r % k);
        # assignments that are not ours sort behind every held expert.
        key = jnp.where(is_local, local, held).reshape(kn)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.zeros((held,), jnp.int32).at[key].add(1, mode="drop")
        rows_local = jnp.sum(group_sizes)
        routed = n if valid is None else jnp.sum(valid)
        counts = [
            jnp.asarray(routed, jnp.int32), rows_local,
            jnp.sum(group_sizes > 0).astype(jnp.int32),
        ]
        if is_zero is not None:
            counts.append(jnp.sum(is_zero).astype(jnp.int32))
        counts = jnp.stack(counts)

    with jax.named_scope("block/moe/experts"):
        sorted_in = jnp.take(tokens, order // top_k, axis=0)  # (kn, d)
        up = grouped_matmul(sorted_in, moe_params["w1"], group_sizes)
        if config.expert_activation == "relu2":
            hidden = relu2(up)
        else:
            lin = grouped_matmul(sorted_in, moe_params["w3"], group_sizes)
            hidden = silu(up) * lin
        if W2_RELAID in moe_params:  # a served tree: `serving_layout`
            sorted_out = grouped_matmul(
                hidden, moe_params[W2_RELAID], group_sizes, transpose_rhs=False
            )
        else:
            sorted_out = grouped_matmul(hidden, moe_params["w2"], group_sizes)
        # Back to assignment order.  Rows past rows_local were not computed
        # (their memory is whatever it was): selected out, never scaled.
        sorted_row = jnp.zeros((kn,), jnp.int32).at[order].set(
            jnp.arange(kn, dtype=jnp.int32)
        )
        picked = jnp.take(sorted_out, sorted_row, axis=0).astype(jnp.float32)
        picked = jnp.where(is_local.reshape(kn, 1), picked * gates.reshape(kn, 1), 0.0)
        out = jnp.sum(picked.reshape(n, top_k, d), axis=1).astype(tokens.dtype)

    if config.n_zero_experts:
        with jax.named_scope("block/moe/zero"):
            zero_gate = jnp.sum(jnp.where(is_zero, gates, 0.0), axis=-1)
            out = out + (
                zero_gate[:, None] * tokens.astype(jnp.float32)
            ).astype(tokens.dtype)

    if config.n_shared_experts:
        with jax.named_scope("block/moe/shared"):
            shared = moe_params["shared"]
            up = jnp.einsum("nd,jfd->njf", tokens, shared["w1"])
            if config.expert_activation == "relu2":
                hidden = relu2(up)
            else:
                lin = jnp.einsum("nd,jfd->njf", tokens, shared["w3"])
                hidden = silu(up) * lin
            both = jnp.einsum("njf,jdf->nd", hidden, shared["w2"])
            out = out + (both / config.n_shared_experts).astype(tokens.dtype)
    return out.reshape(orig_shape), counts
