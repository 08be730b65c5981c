"""A learned selector over a position-addressed cache: index scores, an exact
top-k of positions, and a gather of the chosen lines.

DeepSeek sparse attention (DSA) puts a second, small network beside a latent
attention layer — the *lightning indexer* — that scores every cached position
for a query and keeps the ``k`` best; the attention proper then sees those
positions alone, the same set for every head. The indexer has a cache leaf of
its own, one key of ``d`` lanes a position, and per token ``H`` query heads of
``d`` lanes and ``H`` weights:

    I(t, s) = sum_j w_j(t) * relu(q_j(t) . k(s))        s <= t, in float32
    S_t     = every s <= t while t + 1 <= k, else the k positions of largest
              I(t, s), a tie going to the lower s

Two forms, as ``ops.latent_attention`` has two of its attention:

- a decode step (one query a row): :func:`step_scores` over the whole leaf,
  :func:`select` (``lax.top_k`` on the masked float32 scores: exact, and its
  tie rule is the one above), :func:`gather_lines` of the chosen positions'
  lines into ``[B, k, W]``, over which the caller runs the absorbed attention
  with lengths ``min(context, k)`` — the rows a short context does not fill lie
  behind the length;
- a block of prompt positions: :func:`block_scores` folds heads and key blocks
  so that no ``[H, S, L]`` array exists, and :func:`selection_mask` turns them
  into the ``[B, S, L]`` mask of ``S_t`` under which the caller's dense
  attention runs (``ops.latent_attention.expanded(selected=...)``);
  :func:`block_selection` is the two a query tile at a time. The k-th
  largest score of a row comes from :func:`kth_largest`, which bisects the
  float's bit pattern in 32 compare-and-count passes — no sort of ``[S, L]``.

Nothing here approximates: ``lax.approx_max_k`` or a sampled threshold would be
a different model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")
# key positions one fold of ``block_scores`` scores, and the query heads it
# takes at a time: [B, S, HEAD_BLOCK, KEY_BLOCK] float32 is what is live
KEY_BLOCK = 1024
HEAD_BLOCK = 16
# query positions whose scores and mask ``block_selection`` holds at a time
QUERY_TILE = 1024


def step_scores(q, weights, keys):
    """One query a row. q ``[B, H, d]``, weights ``[B, H]`` (float32), keys
    ``[B, L, d]`` (the index leaf). Returns ``I`` ``[B, L]`` in float32, of
    every position the leaf has — the caller's lengths say which exist."""
    dots = jnp.einsum("bhd,bld->bhl", q, keys, preferred_element_type=jnp.float32)
    return jnp.einsum("bhl,bh->bl", jax.nn.relu(dots), weights.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def select(scores, lengths, k: int):
    """The positions of the ``k`` largest ``scores`` ``[B, L]`` among each
    row's first ``lengths`` ``[B]``, best first, a tie to the lower position
    (``lax.top_k``'s rule). Returns ``[B, min(k, L)]`` int32; where a row holds
    fewer than ``k`` positions its own come first, all of them, and what
    follows lies behind ``min(lengths, k)``."""
    visible = jnp.arange(scores.shape[1])[None, :] < lengths[:, None]
    _, idx = jax.lax.top_k(jnp.where(visible, _one_zero(scores), NEG_INF),
                           min(k, scores.shape[1]))
    return idx.astype(jnp.int32)


def _one_zero(x):
    """-0.0 -> 0.0: the two are one score (``top_k`` and the bit patterns
    order them; the equations do not)."""
    return jnp.where(x == 0, jnp.zeros_like(x), x)


def gather_lines(cache, idx):
    """cache ``[B, L, W]``, idx ``[B, k]`` -> the chosen lines ``[B, k, W]``."""
    return jnp.take_along_axis(cache, idx[:, :, None], axis=1)


def block_scores(q, weights, keys):
    """A block of query positions. q ``[B, S, H, d]``, weights ``[B, S, H]``,
    keys ``[B, L, d]``. Returns ``I`` ``[B, S, L]`` float32, a key block and a
    group of heads at a time (a ``lax.map`` over the one, a loop over the
    other)."""
    b, s, h, d = q.shape
    length = keys.shape[1]
    kb = length if length <= KEY_BLOCK or length % KEY_BLOCK else KEY_BLOCK
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 else h
    weights = weights.astype(jnp.float32)

    def one_block(block):  # [B, kb, d]
        def heads(i, acc):
            qh = jax.lax.dynamic_slice_in_dim(q, i * hb, hb, axis=2)
            wh = jax.lax.dynamic_slice_in_dim(weights, i * hb, hb, axis=2)
            dots = jnp.einsum("bshd,bkd->bshk", qh, block, preferred_element_type=jnp.float32)
            return acc + jnp.einsum("bshk,bsh->bsk", jax.nn.relu(dots), wh,
                                    preferred_element_type=jnp.float32)
        return jax.lax.fori_loop(0, h // hb, heads, jnp.zeros((b, s, kb), jnp.float32))

    blocks = jnp.moveaxis(keys.reshape(b, length // kb, kb, d), 1, 0)
    out = jax.lax.map(one_block, blocks)  # [L / kb, B, S, kb]
    return jnp.moveaxis(out, 0, 2).reshape(b, s, length)


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order (-0.0 below
    0.0; a score is never NaN)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(x, k: int):
    """The ``k``-th largest value of each row of ``x`` ``[..., L]`` (float32,
    ``1 <= k <= L``), exactly and without a sort: the largest bit pattern that
    at least ``k`` of the row's reach, found a bit a pass from the top."""
    bits = _ordered_bits(x)
    found = jnp.zeros(x.shape[:-1], jnp.uint32)

    def one_bit(i, found):
        trial = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(bits >= trial[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, found)

    found = jax.lax.fori_loop(0, 32, one_bit, found)
    back = jnp.where(found >> 31 == 1, found & jnp.uint32((1 << 31) - 1), ~found)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


def selection_mask(scores, query_positions, k: int):
    """scores ``[B, S, L]`` float32, query_positions ``[B, S]`` (absolute) ->
    bool ``[B, S, L]``: ``S_t`` of every query — every position it may see
    while there are at most ``k``, else the ``k`` of largest score, a tie at
    the k-th going to the lower positions."""
    length = scores.shape[-1]
    visible = jnp.arange(length)[None, None, :] <= query_positions[:, :, None]
    if k >= length:
        return visible
    masked = jnp.where(visible, _one_zero(scores), NEG_INF)
    kth = kth_largest(masked, k)[..., None]
    above = masked > kth
    ties = (masked == kth) & visible
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    # a query with at most k positions to see: the k-th largest is its least score or
    # -inf, and what is chosen is all it may see
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room))


def block_selection(q, weights, keys, query_positions, k: int):
    """:func:`block_scores` then :func:`selection_mask`, ``QUERY_TILE`` queries at
    a time (``S`` halved until it fits, while it is even), so that the float32
    scores of one tile are live and the ``[B, S, L]`` that remains is the
    mask. Scopes ``dsa.score`` / ``dsa.select`` name the two in a trace."""
    b, s = q.shape[:2]
    size = s
    while size > QUERY_TILE and size % 2 == 0:
        size //= 2

    def one_tile(args):
        qt, wt, pt = args
        with jax.named_scope("dsa.score"):
            scores = block_scores(qt, wt, keys)
        with jax.named_scope("dsa.select"):
            return selection_mask(scores, pt, k)

    split = lambda x: jnp.moveaxis(x.reshape(b, s // size, size, *x.shape[2:]), 1, 0)  # noqa: E731
    masks = jax.lax.map(one_tile, (split(q), split(weights), split(query_positions)))
    return jnp.moveaxis(masks, 0, 1).reshape(b, s, keys.shape[1])
