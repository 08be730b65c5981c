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

- a decode step (one query a row): :func:`step_scores`, :func:`select`,
  :func:`gather_lines` of the chosen positions' lines into ``[B, k, W]``, over
  which the caller runs the absorbed attention with lengths ``min(context, k)``
  — the rows a short context does not fill lie behind the length. The step has
  TWO lowerings of one selection, and which runs is read off the inputs
  (:func:`takes_kernel`: where the absorbed attention's kernel runs over the
  same leaf — the TPU backend, one device, or its name — and chunks of 128
  positions tile the blocks and ``k``; no flag):

  - everywhere else, the scores of the WHOLE leaf as one ``[B, L]`` fusion and
    ``lax.top_k`` on them under the rows' lengths (:func:`select_reference`:
    exact, best first, its tie rule the one above — and a sort of 32,768 a row
    on the TPU, 0.39 ms a layer beside 0.18 for the scores; PERF.md, PR 50);
  - there, :func:`score_kernel` — the flat grid of
    ``latent_attention.decode_kernel`` over the (row, key block) pairs that
    exist, so a key a row does not hold is never read — writes the scores in
    chunks ``[B, L / 128, 128]``, and :func:`select` of chunks is
    :func:`select_ascending`: :func:`chosen_mask`, one Pallas kernel over
    groups of rows that keeps a group's scores in VMEM and finds the k-th
    largest by :func:`kth_largest`'s bisection of the ordered bit pattern (32
    compare-and-count passes) and, among the scores that tie with it, the
    last position kept by 15 more — no sort, no cumulative sum — then
    :func:`compact`, which turns the mask into ``[B, k]`` positions in
    ASCENDING order by compares, sums and one one-hot product (no scatter,
    no search). The SET is ``lax.top_k``'s, tie for tie; the attention does
    not ask for an order. Why the mask is a kernel when :func:`selection_mask`
    is the same mask in XLA (PERF.md, PR 51, measured in the cell): its
    ``cumsum`` over a row's 32,768 positions is 0.15 ms a layer (−9.5 % of
    the tokens a second); with the ties counted chunk by chunk instead it is
    0.8 % behind, and its 32 loop passes a layer are 500 more device
    operations a step — a trace of 8 s then outlasts the benchmark's wait
    for the profiler and the traced run loses its counters.
- a block of prompt positions: :func:`block_scores` folds heads and key blocks
  so that no ``[H, S, L]`` array exists, and :func:`selection_mask` turns them
  into the ``[B, S, L]`` mask of ``S_t`` under which the caller's dense
  attention runs (``ops.latent_attention.expanded(selected=...)``);
  :func:`block_selection` is the two a query tile at a time. The k-th
  largest score of a row comes from :func:`kth_largest`, which bisects the
  float's bit pattern in 32 compare-and-count passes — no sort of ``[S, L]``.

The gather stays XLA's (14 ns a line): Mosaic's DMA takes no single line of a
leaf tiled by eight positions, and the eight lines around each chosen one are
as many bytes as a third of the leaf (PERF.md, PR 51).

Nothing here approximates: ``lax.approx_max_k`` or a sampled threshold would be
a different model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from modelx_tpu.ops import latent_attention as latent_ops

NEG_INF = float("-inf")
INT_MIN = -(2**31)
# positions a chunk of the sort-free selection holds: a row's scores are
# ``[L / LANES, LANES]``, a chunk a sublane
LANES = latent_ops.LANES
# VMEM a step of the mask kernel may hold (of a v5e core's 16 MiB scoped limit)
MASK_VMEM_BYTES = 12 * 2**20
# key positions one fold of ``block_scores`` scores, and the query heads it
# takes at a time: [B, S, HEAD_BLOCK, KEY_BLOCK] float32 is what is live
KEY_BLOCK = 1024
HEAD_BLOCK = 16
# query positions whose scores and mask ``block_selection`` holds at a time
QUERY_TILE = 1024


def takes_kernel(cache_shape: tuple, rank: int, k: int, impl: str = "auto",
                 mesh=None) -> tuple[int, bool]:
    """(the scoring kernel's key block or 0, interpret) for a decode step's
    selection of ``k`` among the positions of a line leaf ``cache_shape`` ``[B,
    L, W]`` (its index leaf has the same ``B`` and ``L``): the kernels run
    exactly where the absorbed attention's runs over that leaf
    (``latent_attention.absorbed_takes_kernel``: the backend, the mesh, or the
    name), chunks of ``LANES`` positions tile its blocks and ``k``, and a row
    fits the mask kernel's VMEM (:func:`mask_group`). ``interpret`` reaches
    :func:`step_scores` alone: :func:`select` keeps the three arguments a
    harness wraps it with and reads it off the backend — the two differ only
    where a TPU is asked for ``"ragged+interpret"`` (the mask then compiles)."""
    block, interpret = latent_ops.absorbed_takes_kernel(cache_shape, rank, impl, mesh)
    tiles = block % LANES == 0 and k % LANES == 0 and mask_group(cache_shape[0], cache_shape[1])
    return (block, interpret) if tiles else (0, False)


def step_scores(q, weights, keys, lengths=None, *, block: int = 0, interpret: bool = False):
    """One query a row. q ``[B, H, d]``, weights ``[B, H]`` (float32), keys
    ``[B, L, d]`` (the index leaf). Returns ``I`` in float32 — ``[B, L]``, of
    every position the leaf has (the caller's lengths say which exist); or,
    with a ``block`` (:func:`takes_kernel`), from :func:`score_kernel` in
    chunks ``[B, L / LANES, LANES]``, of the key blocks up to each row's
    ``lengths`` and undefined past them."""
    if block:
        return score_kernel(q, weights, keys, lengths, block=block, interpret=interpret)
    dots = jnp.einsum("bhd,bld->bhl", q, keys, preferred_element_type=jnp.float32)
    return jnp.einsum("bhl,bh->bl", jax.nn.relu(dots), weights.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _score_kernel(row_ref, block_ref, q_ref, w_ref, keys_ref, o_ref):
    """One (row, key block) step of :func:`score_kernel`. q_ref [H, d], w_ref
    [H, LANES] (a head's weight in every lane), keys_ref [block, d], o_ref
    [block / LANES, LANES]: a chunk of positions a sublane."""
    del row_ref, block_ref
    dots = jax.lax.dot_general(q_ref[...], keys_ref[...], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # [H, block]
    w = w_ref[...]
    for c in range(o_ref.shape[0]):
        chunk = dots[:, c * LANES: (c + 1) * LANES]
        o_ref[c: c + 1, :] = jnp.sum(jnp.maximum(chunk, 0.0) * w, axis=0, keepdims=True)


def score_kernel(q, weights, keys, lengths, *, block: int, interpret: bool = False):
    """:func:`step_scores` as a Pallas kernel, each row over its own context
    only: the flat grid of ``latent_attention.decode_kernel`` over the (row,
    key block) pairs that exist, a step one block of ``block`` keys against the
    row's heads (operands as they are, float32 products), relu, the heads'
    weighted sum in float32 on the vector unit. Blocks past a row's
    ``lengths`` are neither read nor written. Returns ``[B, L / LANES, LANES]``."""
    b, h, d = q.shape
    cache_len = keys.shape[1]
    if not block or cache_len % block or block % LANES:
        raise ValueError(f"no block of {block} keys in chunks of {LANES} tiles {cache_len}")
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, cache_len)
    row_of, block_of, steps = latent_ops.block_table(lengths, block, b * (cache_len // block))
    wide = jnp.broadcast_to(weights.astype(jnp.float32)[:, :, None], (b, h, LANES))
    per_row = lambda lanes: pl.BlockSpec(  # noqa: E731
        (None, h, lanes), lambda s, row_of, block_of: (row_of[s], 0, 0))
    per_block = lambda rows, lanes: pl.BlockSpec(  # noqa: E731
        (None, rows, lanes), lambda s, row_of, block_of: (row_of[s], block_of[s], 0))
    return pl.pallas_call(
        _score_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,),
            in_specs=[per_row(d), per_row(LANES), per_block(block, d)],
            out_specs=per_block(block // LANES, LANES)),
        out_shape=jax.ShapeDtypeStruct((b, cache_len // LANES, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dsa_step_scores",
    )(row_of, block_of, q, wide, keys)


def select(scores, lengths, k: int):
    """The positions of the ``k`` largest ``scores`` among each row's first
    ``lengths`` ``[B]``, a tie to the lower position (``lax.top_k``'s rule), as
    ``[B, min(k, L)]`` int32; where a row holds fewer than ``k`` positions its
    own come first, all of them, and what follows lies in range behind
    ``min(lengths, k)``. The SET is one; the order and the lowering follow the
    scores' form: ``[B, L]`` is :func:`select_reference` (a sort, best first),
    the chunks ``[B, L / LANES, LANES]`` that :func:`score_kernel` writes are
    :func:`select_ascending` (no sort, ascending)."""
    if scores.ndim == 3:
        return select_ascending(scores, lengths, k)
    return select_reference(scores, lengths, k)


def select_reference(scores, lengths, k: int):
    """:func:`select` of ``[B, L]`` by ``lax.top_k`` on the masked float32
    scores, best first: a sort on the TPU."""
    visible = jnp.arange(scores.shape[1])[None, :] < lengths[:, None]
    _, idx = jax.lax.top_k(jnp.where(visible, _one_zero(scores), NEG_INF),
                           min(k, scores.shape[1]))
    return idx.astype(jnp.int32)


def select_ascending(scores, lengths, k: int):
    """:func:`select` of chunks ``[B, L / LANES, LANES]`` (``k < L``) without a
    sort: :func:`chosen_mask` then :func:`compact`, positions ascending."""
    # whoever has the chunks asked for kernels (``takes_kernel``); Mosaic compiles for a
    # TPU alone, so anywhere else that was by name, interpreted
    return compact(chosen_mask(scores, lengths, k, interpret=jax.default_backend() != "tpu"), k)


def _mask_kernel(len_ref, s_ref, o_ref, key_ref, count_ref, *, k: int):
    """One group of rows of :func:`chosen_mask`. s_ref [G, C, LANES] float32;
    key_ref the same in int32: a score's bit pattern in the integers' SIGNED
    order (-0.0 is 0.0, a position past the row's length below every score);
    count_ref [G, LANES] float32 carries a pass's counts from the rows' sublane
    sums to one lane sum. The k-th largest key a bit a pass from the top, as
    :func:`kth_largest`; then of the keys that tie with it the lowest
    positions that fill ``k``, their last a bit a pass the same way. The rows
    of a group go through a pass together: one row's 47 passes in a chain
    would wait 47 times for a reduction."""
    g, chunks, lanes = s_ref.shape
    first = pl.program_id(0) * g
    pos = (jax.lax.broadcasted_iota(jnp.int32, (chunks, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, (chunks, lanes), 1))
    for r in range(g):
        x = s_ref[r]
        bits = pltpu.bitcast(jnp.where(x == 0.0, 0.0, x), jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(2**31 - 1), bits)
        key_ref[r] = jnp.where(pos < len_ref[first + r], key, INT_MIN)

    def count(hit):
        """[G, 1] float32 (exact to 2**24): positions of each row where ``hit(r)``."""
        for r in range(g):
            count_ref[r: r + 1, :] = jnp.sum(hit(r).astype(jnp.float32), axis=0, keepdims=True)
        return jnp.sum(count_ref[...], axis=1, keepdims=True)

    def row(x, r):  # [G, 1] -> row r's, over its chunks
        return jnp.broadcast_to(x[r: r + 1, :], (chunks, lanes))

    def score_bit(i, found):
        # ``found`` in the UNSIGNED order of ``_ordered_bits``; ``^ INT_MIN`` is the signed key
        trial = found | jnp.left_shift(jnp.int32(1), 31 - i)
        enough = count(lambda r: key_ref[r] >= row(trial ^ INT_MIN, r)) >= k
        return jnp.where(enough, trial, found)

    kth = jax.lax.fori_loop(0, 32, score_bit, jnp.zeros((g, 1), jnp.int32)) ^ INT_MIN
    above = lambda r: key_ref[r] > row(kth, r)  # noqa: E731
    ties = lambda r: (key_ref[r] == row(kth, r)) & (pos < len_ref[first + r])  # noqa: E731
    room = k - count(above)  # 1 or more: fewer than k lie above the k-th largest
    bits = (chunks * lanes - 1).bit_length()

    def position_bit(i, found):
        # the largest position that fewer than ``room`` ties lie below: the last tie kept
        trial = found | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        short = count(lambda r: ties(r) & (pos < row(trial, r))) < room
        return jnp.where(short, trial, found)

    last = jax.lax.fori_loop(0, bits, position_bit, jnp.zeros((g, 1), jnp.int32))
    for r in range(g):
        o_ref[r] = (above(r) | (ties(r) & (pos <= row(last, r)))).astype(o_ref.dtype)


def mask_group(rows: int, positions: int) -> int:
    """Rows a step of :func:`chosen_mask` takes: the most that divide ``rows``
    and fit ``MASK_VMEM_BYTES`` at 16 bytes a position (the float32 scores and
    the bfloat16 mask in two buffers each, the int32 keys); 0 where one row
    does not."""
    fit = MASK_VMEM_BYTES // (16 * positions)
    return max((g for g in range(1, min(rows, fit) + 1) if rows % g == 0), default=0)


def chosen_mask(scores, lengths, k: int, *, interpret: bool = False):
    """scores ``[B, C, LANES]`` float32 (a row's positions in chunks), lengths
    ``[B]`` -> ``[B, C, LANES]`` bfloat16, 1 at the ``min(k, lengths)``
    positions :func:`select` means and 0 elsewhere: one Pallas kernel, a grid
    over groups of :func:`mask_group` rows, a group's scores resident in VMEM
    for all its compare-and-count passes."""
    b, chunks, lanes = scores.shape
    group = mask_group(b, chunks * lanes)
    if not group:
        raise ValueError(f"a row of {chunks * lanes} positions is over {MASK_VMEM_BYTES} bytes")
    rows = pl.BlockSpec((group, chunks, lanes), lambda i, lens: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_mask_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b // group,), in_specs=[rows], out_specs=rows,
            scratch_shapes=[pltpu.VMEM((group, chunks, lanes), jnp.int32),
                            pltpu.VMEM((group, lanes), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, chunks, lanes), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dsa_chosen_mask",
    )(jnp.clip(lengths.astype(jnp.int32), 0, chunks * lanes), scores.astype(jnp.float32))


def compact(mask, k: int):
    """mask ``[B, C, LANES]`` (0 / 1, bfloat16) -> the positions of its ones,
    ascending, ``[B, k]`` int32; past a row's ones, positions in range. Dense:
    no scatter, no sort, no search — slot ``j``'s chunk is the number of chunks
    that end at or before ``j`` (``latent_attention.block_table``'s compare and
    sum), the chunk's running count comes by a one-hot product (0 / 1 and
    counts to ``LANES`` are exact in bfloat16, the sums float32), its lane is
    the number of lanes whose running count is at most ``j``'s place in the chunk."""
    b, chunks, lanes = mask.shape
    upto = (jnp.arange(lanes)[:, None] <= jnp.arange(lanes)[None, :]).astype(mask.dtype)
    running = jnp.einsum("bcl,lm->bcm", mask, upto, preferred_element_type=jnp.float32)
    counts = running[..., -1]  # [B, C]
    ends = jnp.cumsum(counts, axis=1)
    slot = jnp.arange(k, dtype=jnp.float32)
    before = ends[:, None, :] <= slot[None, :, None]  # [B, k, C]
    chunk = jnp.minimum(jnp.sum(before, axis=2, dtype=jnp.int32), chunks - 1)
    place = slot[None, :] - jnp.sum(jnp.where(before, counts[:, None, :], 0.0), axis=2)
    onehot = (chunk[:, :, None] == jnp.arange(chunks)[None, None, :]).astype(mask.dtype)
    mine = jnp.einsum("bkc,bcl->bkl", onehot, running.astype(mask.dtype),
                      preferred_element_type=jnp.float32)  # [B, k, LANES]
    lane = jnp.sum(mine <= place[:, :, None], axis=2, dtype=jnp.int32)
    return jnp.minimum(chunk * lanes + lane, chunks * lanes - 1)


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
