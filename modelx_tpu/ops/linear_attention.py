"""Linear attention with a per-head decay (lightning attention), plain
``jax.numpy``: the running sum a layer keeps in place of keys and values.

Per head with decay ``lam = exp(-s)``: ``S_t = lam S_{t-1} + k_t v_t^T``
(``[D, D]``, float32) and ``o_t = S_t^T q_t * scale``. Two forms of it:

- :func:`step` — one token a row from the row's state, the recurrence itself;
- :func:`chunked` — a block of positions from a state, chunk by chunk: inside
  a chunk the decay-masked product ``((Q K^T) * Lam) V`` with ``Lam_ij =
  lam^(i - j)`` for ``j <= i``, across chunks the state, ``diag(lam^(i+1)) Q
  S``; the state leaves as ``lam^C S + sum_i lam^(C-1-i) k_i v_i^T``.

Every power of ``lam`` is taken from a difference of positions, never as a
quotient of two powers: ``lam^-C`` overflows for the fast heads.

A block may be padded (a 16-token bucket past the real prompt): ``valid_len``
says how many of its positions are real, and a position past it neither
decays nor feeds the state. A row of a decode step that is not live (an idle
slot, a slot still filling) keeps its state bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 256


def decay_slopes(heads: int) -> np.ndarray:
    """``s_h = 2^(-8 (h + 1) / heads)``: head 0 forgets within a few
    positions, the last head over hundreds."""
    return (2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)).astype(np.float32)


def step(q, k, v, slopes, state, live=None, scale: float = 1.0):
    """q, k, v ``[B, H, D]``, state ``[B, H, D, D]`` float32 -> (o ``[B, H,
    D]`` float32, the new state). ``live`` ``[B]`` bool: a row that is not
    keeps its state unchanged (its output is garbage nobody reads)."""
    lam = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None, None]
    f32 = lambda x: x.astype(jnp.float32)
    new = lam * state + f32(k)[..., :, None] * f32(v)[..., None, :]
    out = jnp.einsum("bhd,bhde->bhe", f32(q), new) * scale
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, state)
    return out, new


def chunked(q, k, v, slopes, state, valid_len=None, scale: float = 1.0, chunk: int = CHUNK):
    """q, k, v ``[B, T, H, D]`` from ``state`` ``[B, H, D, D]`` float32 -> (o
    ``[B, T, H, D]`` float32, the state after the block). ``valid_len`` ``[B]``:
    the real positions of each row's block (all ``T`` when None)."""
    b, t, h, d = q.shape
    c = min(chunk, t)
    pad = -t % c
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v))
    n = (t + pad) // c
    real = jnp.arange(t + pad)[None, :] < (
        jnp.full((b, 1), t) if valid_len is None else jnp.asarray(valid_len).reshape(b, 1))
    slopes = jnp.asarray(slopes, jnp.float32)

    def blocks(x):  # [B, T, ...] -> [N, B, c, ...]
        return jnp.moveaxis(x.reshape(b, n, c, *x.shape[2:]), 1, 0)

    def one(s, xs):
        qc, kc, vc, m = xs  # [B, c, H, D] x3, [B, c] bool
        seen = jnp.cumsum(m, axis=1).astype(jnp.float32)  # real positions up to and with i
        total = seen[:, -1]
        # Lam_ij = lam^(seen_i - seen_j) for j <= i and j real; the exponent is >= 0
        gap = seen[:, :, None] - seen[:, None, :]  # [B, c, c]
        causal = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])[None] & m[:, None, :]
        lam = jnp.where(causal[:, None], jnp.exp(-slopes[None, :, None, None]
                                                 * jnp.maximum(gap, 0.0)[:, None]), 0.0)
        scores = jnp.einsum("bihd,bjhd->bhij", qc, kc,
                            preferred_element_type=jnp.float32) * lam
        intra = jnp.einsum("bhij,bjhd->bihd", scores.astype(vc.dtype), vc,
                           preferred_element_type=jnp.float32)
        carried = jnp.exp(-slopes[None, None, :] * seen[:, :, None])  # lam^(i+1): [B, c, H]
        inter = jnp.einsum("bihd,bhde->bihe", qc.astype(jnp.float32), s) * carried[..., None]
        # what each real key still weighs at the chunk's end: lam^(total - seen_j)
        left = jnp.where(m[:, :, None], jnp.exp(
            -slopes[None, None, :] * (total[:, None] - seen)[:, :, None]), 0.0)
        kw = kc.astype(jnp.float32) * left[..., None]
        s = (jnp.exp(-slopes[None, :] * total[:, None])[..., None, None] * s
             + jnp.einsum("bjhd,bjhe->bhde", kw, vc.astype(jnp.float32)))
        return s, (intra + inter) * scale

    state, out = jax.lax.scan(one, state, (blocks(q), blocks(k), blocks(v), blocks(real)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h, d)
    return out[:, :t], state
