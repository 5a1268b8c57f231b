"""The Mamba-2 state-space mixer: one layer's weights and its three forms.

For the normalised stream ``u`` (``config`` names in brackets)::

    [z ; xBC ; dt] = W_in u             [ssm_inner + ssm_conv_channels + ssm_heads]
    xBC_t = silu(b_c + sum_j w_c[:, j] * xBC_{t - (k - 1) + j})   [ssm_conv = k]
    [x ; B ; C] = xBC                   [heads x ssm_head_dim ; groups x ssm_state, twice]
    dt = softplus(dt + dt_bias)         a head;   A = -exp(A_log)
    H_t = exp(dt_t A) H_{t-1} + (dt_t x_t) (x) B_t[g]  [a head: head_dim x ssm_state]
    y_t = H_t C_t[g] + D x_t
    out = W_out RMSNorm_g(y * silu(z))  [a norm over each group's inner channels]

``B`` and ``C`` come in ``ssm_groups`` groups: head ``h`` reads those of group
``g = h // (heads // groups)``, and the gated norm runs over each group's
channels apart under one weight of ``ssm_inner``.  With one group - the
default - they are shared by all heads, there is one norm over all inner
channels, and ``B``, ``C`` carry no group axis anywhere below (the programs
of a one-group config are what they were before there were groups).  The
convolution is depthwise and causal, zeros left of the sequence's start.
**What a sequence keeps** between calls is ``{"ssm": H (heads, head_dim,
ssm_state) float32, "conv": the last k - 1 pre-activation xBC rows}`` - it
does not grow with the context.

Three forms that agree (``tests/test_granitehybrid.py``, with groups
``tests/test_nemotronh.py``): :func:`mamba2` over
a whole sequence, chunked (``ssm_chunk`` positions at a time the recurrence
is a masked matrix product, between chunks a carried state); the same from a
carried state, leaving one (a prefill chunk); :func:`mamba2_step`, one
position a sequence (a decode tick: `kernels/pallas/ssm.ssm_state_update`).
Rows that are not ``valid`` leave the state as it was (``dt = 0``: decay 1, no
input) and do not enter the conv rows, so a bucket's padding and an idle
slot need no second program.  The state, ``dt`` and the decay are float32
whatever the activations are.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import Array

from bpe_transformer_tpu.models.config import ModelConfig
from bpe_transformer_tpu.ops.core import linear, rmsnorm, silu

HIGHEST = jax.lax.Precision.HIGHEST


def init_ssm_params(rng: jax.Array, config: ModelConfig, dtype=jnp.float32) -> dict:
    """One layer's tree.  Matrices as everywhere (truncated normal x 0.02);
    the values a normal draw would leave degenerate follow the family's
    initialisation: ``A_log = log U(1, 16)``, ``dt_bias`` the inverse
    softplus of a log-uniform in (1e-3, 1e-1), ``D = 1``, conv weights
    ``U(-1/2, 1/2)`` (``k ** -0.5`` at width 4)."""
    d, inner, ch = config.d_model, config.ssm_inner, config.ssm_conv_channels
    heads, k = config.ssm_heads, config.ssm_conv
    keys = jax.random.split(rng, 5)

    def dense(key, shape):
        return (
            jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * 0.02
        ).astype(dtype)

    dt = jnp.exp(
        jax.random.uniform(keys[3], (heads,), jnp.float32, math.log(1e-3), math.log(1e-1))
    )
    bound = k ** -0.5
    return {
        "in_proj": dense(keys[0], (inner + ch + heads, d)),
        "conv_w": jax.random.uniform(keys[1], (ch, k), jnp.float32, -bound, bound).astype(dtype),
        "conv_b": jnp.zeros((ch,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(keys[4], (heads,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "D": jnp.ones((heads,), dtype),
        "norm": jnp.ones((inner,), dtype),
        "out_proj": dense(keys[2], (d, inner)),
    }


def init_ssm_state(config: ModelConfig, batch: int, dtype=jnp.float32) -> dict:
    """The state of ``batch`` sequences at their start: zeros."""
    return {
        "ssm": jnp.zeros(
            (batch, config.ssm_heads, config.ssm_head_dim, config.ssm_state), jnp.float32
        ),
        "conv": jnp.zeros((batch, config.ssm_conv - 1, config.ssm_conv_channels), dtype),
    }


def _project(u, p, config):
    """``(z, xBC before the convolution, dt before softplus)``."""
    inner, ch = config.ssm_inner, config.ssm_conv_channels
    with jax.named_scope("block/ssm/in_proj"):
        zxbcdt = linear(u, p["in_proj"])
    return zxbcdt[..., :inner], zxbcdt[..., inner:inner + ch], zxbcdt[..., inner + ch:]


def _convolve(window, p, rows: int):
    """``window`` (..., rows + k - 1, channels), each row behind its k - 1
    predecessors -> the convolution's ``rows`` activated outputs."""
    k = p["conv_w"].shape[1]
    w = p["conv_w"].astype(jnp.float32)
    acc = p["conv_b"].astype(jnp.float32)
    for j in range(k):
        acc = acc + window[..., j:j + rows, :].astype(jnp.float32) * w[:, j]
    return silu(acc).astype(window.dtype)


def _step_sizes(dt_raw, p, valid):
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return dt if valid is None else jnp.where(valid[..., None], dt, 0.0)


def _split_xbc(xbc, config):
    """``(x (..., heads, channels), B, C)``: ``B`` and ``C`` (..., state
    values) of one group, (..., groups, state values) of several."""
    inner, groups = config.ssm_inner, config.ssm_groups
    n = groups * config.ssm_state
    x = xbc[..., :inner].reshape(*xbc.shape[:-1], config.ssm_heads, config.ssm_head_dim)
    b, c = xbc[..., inner:inner + n], xbc[..., inner + n:]
    if groups > 1:
        b, c = (v.reshape(*v.shape[:-1], groups, config.ssm_state) for v in (b, c))
    return x, b, c


def _gate_out(y, z, p, groups: int, eps: float):
    """``y`` (..., inner) float32 gated by ``z``, normalised a group of
    channels at a time, projected."""
    with jax.named_scope("block/ssm/gate_norm"):
        g = y.astype(z.dtype) * silu(z)
        if groups == 1:
            g = rmsnorm(g, p["norm"], eps)
        else:
            by_group = g.reshape(*g.shape[:-1], groups, -1)
            g = rmsnorm(by_group, p["norm"].reshape(groups, -1), eps).reshape(g.shape)
    with jax.named_scope("block/ssm/out_proj"):
        return linear(g, p["out_proj"])


def chunked_scan(x, dt, a, b, c, state, chunk: int):
    """The recurrence over ``x`` (batch, T, heads, channels) with steps
    ``dt`` (batch, T, heads) float32, ``a`` (heads,), ``b`` and ``c``
    (batch, T, state values), from ``state`` (batch, heads, channels, state
    values) float32: ``(y (batch, T, heads, channels) float32, end state)``,
    without the skip term.  Inside a chunk ``Y = ((C B^T) o L)(dt * X)``
    with ``L_ts = exp(sum_{s < r <= t} dt_r a)``; a chunk's end state is
    ``(prod decay) H + sum_s (prod_{r > s} decay_r) dt_s x_s (x) B_s``; the
    incoming state adds ``C_t (prod_{r <= t} decay_r) H``.

    ``b`` and ``c`` of several groups (batch, T, groups, state values): the
    heads of a group are a recurrence of their own under the group's ``B``
    and ``C``, so the one-group scan is mapped over the groups."""
    if b.ndim == 4:
        groups = b.shape[2]

        def by_group(v, axis):  # heads -> (groups, heads a group), groups first
            split = v.reshape(*v.shape[:axis], groups, -1, *v.shape[axis + 1:])
            return jnp.moveaxis(split, axis, 0)

        y, state = jax.vmap(
            lambda x, dt, a, b, c, state: chunked_scan(x, dt, a, b, c, state, chunk)
        )(
            by_group(x, 2), by_group(dt, 2), by_group(a, 0),
            jnp.moveaxis(b, 2, 0), jnp.moveaxis(c, 2, 0), by_group(state, 1),
        )
        y, state = jnp.moveaxis(y, 0, 2), jnp.moveaxis(state, 0, 1)
        return y.reshape(x.shape), state.reshape(state.shape[0], -1, *state.shape[3:])
    batch, t, heads, channels = x.shape
    size = min(chunk, t)
    pad = -t % size
    if pad:  # steps of 0 change nothing
        x, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in (x, b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    chunks = (t + pad) // size
    act = x.dtype
    x, b, c, dt = (v.reshape(batch, chunks, size, *v.shape[2:]) for v in (x, b, c, dt))
    log_decay = jnp.cumsum(dt * a, axis=2)                     # (B, c, l, h)
    dtx = dt[..., None] * x.astype(jnp.float32)                # (B, c, l, h, p)
    # Within the chunk.
    cb = jnp.einsum("bcln,bcsn->bcls", c, b, preferred_element_type=jnp.float32)
    span = log_decay[:, :, :, None, :] - log_decay[:, :, None, :, :]   # (B, c, l, s, h)
    causal = jnp.tril(jnp.ones((size, size), bool))[None, None, :, :, None]
    weights = cb[..., None] * jnp.exp(jnp.where(causal, span, -jnp.inf))
    y = jnp.einsum(
        "bclsh,bcshp->bclhp", weights.astype(act), dtx.astype(act),
        preferred_element_type=jnp.float32,
    )
    # Each chunk's own contribution to its end state, then chunk to chunk.
    to_end = jnp.exp(log_decay[:, :, -1:, :] - log_decay)      # (B, c, l, h)
    own = jnp.einsum(
        "bcshp,bcsn->bchpn", dtx * to_end[..., None], b.astype(jnp.float32),
        precision=HIGHEST,
    )
    whole = jnp.exp(log_decay[:, :, -1, :])                    # (B, c, h)

    def carry(h, xs):
        own_c, whole_c = xs
        return h * whole_c[:, :, None, None] + own_c, h

    state, before = jax.lax.scan(
        carry, state, (jnp.swapaxes(own, 0, 1), jnp.swapaxes(whole, 0, 1))
    )
    y = y + jnp.einsum(
        "bcln,cbhpn->bclhp", c.astype(jnp.float32), before, precision=HIGHEST
    ) * jnp.exp(log_decay)[..., None]
    return y.reshape(batch, chunks * size, heads, channels)[:, :t], state


def mamba2(
    u: Array, p: dict, config: ModelConfig, state: dict | None = None,
    valid: Array | None = None,
) -> tuple[Array, dict]:
    """``u`` (batch, T, d_model) -> ``((batch, T, d_model), state)``, from
    ``state`` (None: a sequence's start) and leaving the state after the
    last valid row.  ``valid`` (batch, T) bool, None for all, is a prefix of
    each sequence's rows (a bucket's padding comes last)."""
    batch, t, _ = u.shape
    if state is None:
        state = init_ssm_state(config, batch, u.dtype)
    z, xbc_pre, dt_raw = _project(u, p, config)
    with jax.named_scope("block/ssm/conv"):
        window = jnp.concatenate([state["conv"].astype(u.dtype), xbc_pre], axis=1)
        xbc = _convolve(window, p, t)
        # The k - 1 rows behind the next position: rows n - (k - 1) .. n - 1
        # of the sequence so far, n valid rows into the window.
        count = jnp.full((batch,), t) if valid is None else jnp.sum(valid, axis=1)
        conv = jax.vmap(
            lambda w, n: jax.lax.dynamic_slice_in_dim(w, n, config.ssm_conv - 1, axis=0)
        )(window, count)
    with jax.named_scope("block/ssm/scan"):
        x, b, c = _split_xbc(xbc, config)
        dt = _step_sizes(dt_raw, p, valid)
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        y, ssm = chunked_scan(x, dt, a, b, c, state["ssm"], config.ssm_chunk)
        y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    out = _gate_out(
        y.reshape(batch, t, config.ssm_inner), z, p, config.ssm_groups, config.norm_eps
    )
    return out, {"ssm": ssm, "conv": conv.astype(state["conv"].dtype)}


def step_inputs(u: Array, p: dict, config: ModelConfig, conv: Array, valid: Array | None):
    """One row a sequence, ``u`` (rows, d_model) behind its ``conv`` rows
    (rows, k - 1, channels): ``(z, x (rows, heads, channels), b, c (as
    `_split_xbc` gives them), dt (rows, heads) float32, a, the next conv rows)``; rows that are not
    ``valid`` get ``dt = 0`` and keep their conv rows."""
    z, xbc_pre, dt_raw = _project(u, p, config)
    with jax.named_scope("block/ssm/conv"):
        window = jnp.concatenate([conv.astype(u.dtype), xbc_pre[:, None]], axis=1)
        xbc = _convolve(window, p, 1)[:, 0]
        moved = window[:, 1:].astype(conv.dtype)
        if valid is not None:
            moved = jnp.where(valid[:, None, None], moved, conv)
    x, b, c = _split_xbc(xbc, config)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    return z, x, b, c, _step_sizes(dt_raw, p, valid), a, moved


def step_output(y: Array, z: Array, p: dict, config: ModelConfig) -> Array:
    """``y`` (rows, heads, channels) float32 -> (rows, d_model)."""
    return _gate_out(
        y.reshape(y.shape[0], config.ssm_inner), z, p, config.ssm_groups, config.norm_eps
    )


def mamba2_step(
    u: Array, p: dict, config: ModelConfig, state: dict, valid: Array | None = None
) -> tuple[Array, dict]:
    """One position a sequence: ``u`` (batch, d_model) and the batch's
    ``state`` -> ``((batch, d_model), state)``."""
    from bpe_transformer_tpu.kernels.pallas.ssm import (
        from_resting,
        to_resting,
        xla_ssm_state_update,
    )

    z, x, b, c, dt, a, conv = step_inputs(u, p, config, state["conv"], valid)
    y, ssm = xla_ssm_state_update(
        to_resting(state["ssm"], config.ssm_groups), jnp.arange(u.shape[0]), x, dt,
        a, b, c, p["D"].astype(jnp.float32),
    )
    ssm = from_resting(ssm, config.ssm_head_dim)
    return step_output(y, z, p, config), {"ssm": ssm, "conv": conv}
