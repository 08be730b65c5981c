"""Mamba-2 state-space recurrence and its short causal convolution, plain
``jax.numpy``: the two things a state-space layer keeps a row in place of keys
and values.

**The recurrence.** Per head ``h`` with ``A_h < 0``, a step size ``dt_t > 0``
from the token, an input ``x_t`` ``[P]``, and ``B_t``, ``C_t`` ``[N]`` shared
by the ``H / G`` heads of a group: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
B_t`` (``[P, N]``, float32) and ``y_t = S_t C_t + D x_t``. Unlike
``ops/linear_attention`` the decay is the token's, not a constant of the head,
and the input is scaled by the same ``dt_t``. Two forms over one state:

- :func:`step` — one token a row from the row's state, the recurrence itself;
- :func:`chunked` — a block of positions from a state, ``chunk`` at a time:
  with ``a_t = dt_t A`` and ``cum`` its running sum inside a chunk, the
  decay-masked product ``((C B^T) * exp(cum_i - cum_j)) (dt x)`` for ``j <=
  i``, the carried state read through ``exp(cum_i)``, and the state leaving as
  ``exp(cum_last) S + sum_j exp(cum_last - cum_j) dt_j x_j (x) B_j``. Every
  exponent is a difference that is ``<= 0``: nothing overflows.

**The convolution** is depthwise and causal over ``K`` positions, so a row
carries its last ``K - 1`` inputs (the TAIL): :func:`conv_step` and
:func:`conv_block`.

A block may be padded (a 16-token bucket past the real prompt): ``valid_len``
says how many of its positions are real; a position past it has ``dt = 0`` —
it neither decays nor feeds the state — and does not enter the tail. A row of
a decode step that is not ``live`` (an idle slot, a slot still filling) keeps
its state and its tail bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def step(x, dt, a, b, c, d, state, live=None):
    """x ``[B, H, P]``, dt ``[B, H]`` (after the softplus), a, d ``[H]``, b, c
    ``[B, G, N]``, state ``[B, H, P, N]`` float32 -> (y ``[B, H, P]`` float32,
    the new state). ``live`` ``[B]`` bool: a row that is not keeps its state
    unchanged (its output is garbage nobody reads)."""
    rows, heads, p = x.shape
    groups, n = b.shape[1:]
    x, dt, b, c = (v.astype(F32) for v in (x, dt, b, c))
    # heads side by side under their group: B and C broadcast, never repeated
    s = state.reshape(rows, groups, heads // groups, p, n)
    decay = jnp.exp(dt * a.astype(F32)).reshape(rows, groups, -1, 1, 1)
    fed = (dt[..., None] * x).reshape(rows, groups, -1, p, 1) * b[:, :, None, None, :]
    new = decay * s + fed
    y = jnp.sum(new * c[:, :, None, None, :], axis=-1).reshape(rows, heads, p)
    y = y + d.astype(F32)[None, :, None] * x
    new = new.reshape(state.shape)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return y, new


def chunked(x, dt, a, b, c, d, state, valid_len=None, chunk: int = 128):
    """x ``[B, T, H, P]``, dt ``[B, T, H]``, b, c ``[B, T, G, N]`` from
    ``state`` ``[B, H, P, N]`` float32 -> (y ``[B, T, H, P]`` float32, the
    state after the block). ``valid_len`` ``[B]``: the real positions of each
    row's block (all ``T`` when None)."""
    rows, t, heads, p = x.shape
    groups, n = b.shape[2:]
    per = heads // groups
    size = min(chunk, t)
    pad = -t % size
    x, dt, b, c = (v.astype(F32) for v in (x, dt, b, c))
    if valid_len is not None:
        real = jnp.arange(t)[None, :] < jnp.asarray(valid_len).reshape(rows, 1)
        dt = jnp.where(real[:, :, None], dt, 0.0)
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    count = (t + pad) // size

    def blocks(v):  # [B, T, ...] -> [N, B, size, ...]
        return jnp.moveaxis(v.reshape(rows, count, size, *v.shape[2:]), 1, 0)

    a = a.astype(F32)
    causal = jnp.arange(size)[:, None] >= jnp.arange(size)[None, :]
    hi = jax.lax.Precision.HIGHEST  # the state lives on: no single-pass products here

    def one(s, xs):
        xc, dtc, bc, cc = xs  # [B, L, H, P], [B, L, H], [B, L, G, N] x2
        cum = jnp.cumsum(dtc * a, axis=1)  # [B, L, H], <= 0 and falling
        fed = (dtc[..., None] * xc).reshape(rows, size, groups, per, p)
        gap = cum[:, :, None, :] - cum[:, None, :, :]  # [B, i, j, H]: <= 0 where j <= i
        lam = jnp.where(causal[None, :, :, None], jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
        scores = jnp.einsum("bign,bjgn->bijg", cc, bc, precision=hi)
        weights = scores[..., None] * lam.reshape(rows, size, size, groups, per)
        intra = jnp.einsum("bijgh,bjghp->bighp", weights, fed, precision=hi)
        sg = s.reshape(rows, groups, per, p, n)
        inter = jnp.einsum("bign,bghpn->bighp", cc, sg, precision=hi) * jnp.exp(cum).reshape(
            rows, size, groups, per, 1)
        # what each position still weighs at the chunk's end: exp(cum_last - cum_j)
        left = jnp.exp(cum[:, -1:, :] - cum).reshape(rows, size, groups, per, 1)
        sg = (jnp.exp(cum[:, -1]).reshape(rows, groups, per, 1, 1) * sg
              + jnp.einsum("bjghp,bjgn->bghpn", fed * left, bc, precision=hi))
        return sg.reshape(s.shape), (intra + inter).reshape(rows, size, heads, p)

    state, y = jax.lax.scan(one, state, (blocks(x), blocks(dt), blocks(b), blocks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(rows, t + pad, heads, p)
    return (y + d.astype(F32)[None, None, :, None] * x)[:, :t], state


def conv_step(x, tail, w, bias=None, live=None):
    """One position of the depthwise causal convolution. x ``[B, C]``, tail
    ``[B, K - 1, C]`` (the row's last inputs, oldest first), w ``[C, K]``
    (``w[:, K - 1]`` weighs the newest input, torch ``Conv1d``'s layout without
    its middle axis), bias ``[C]`` -> (y ``[B, C]`` float32, the new tail). A
    row that is not ``live`` keeps its tail."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)  # [B, K, C]
    y = jnp.einsum("bkc,ck->bc", window.astype(F32), w.astype(F32))
    if bias is not None:
        y = y + bias.astype(F32)
    new = window[:, 1:]
    if live is not None:
        new = jnp.where(live[:, None, None], new, tail)
    return y, new


def conv_block(x, tail, w, bias=None, valid_len=None):
    """A block of positions after ``tail``. x ``[B, T, C]`` -> (y ``[B, T, C]``
    float32, the tail after the block's ``valid_len`` real positions — all
    ``T`` when None; fewer than ``K - 1`` of them keep the older inputs
    behind)."""
    rows, t, _ = x.shape
    k = w.shape[1]
    run = jnp.concatenate([tail, x.astype(tail.dtype)], axis=1)  # [B, K - 1 + T, C]
    w32 = w.astype(F32)
    y = sum(run[:, j: j + t].astype(F32) * w32[:, j] for j in range(k))
    if bias is not None:
        y = y + bias.astype(F32)
    if valid_len is None:
        return y, run[:, t:]
    ends = jnp.asarray(valid_len, jnp.int32).reshape(rows)
    new = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, k - 1, axis=0))(run, ends)
    return y, new
