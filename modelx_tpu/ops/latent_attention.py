"""Latent (MLA) attention over ONE cache of compressed lines.

A latent-attention layer caches, a position, one line ``[c | k_pe | 0..]``:
the normed compressed key-value ``c`` (``rank`` lanes), the one roped key all
heads share (``rope`` lanes), zeros up to whole 128-lane tiles (:func:`line_width`
— 576 becomes 640: a ``[slots, L, 576]`` leaf the TPU compiler lays with the
POSITIONS minor, so a position's line is strided over 576 rows and a kernel's
operand is re-laid whole; 512 + 64 as two leaves pads the 64 to a tile all the
same; PERF.md, PR 43). Per-head keys and values exist only as ``W_kvb`` applied
to ``c``, and two forms of one attention read the lines:

- :func:`expanded` — a block of query positions (a prompt, a prefill piece):
  keys and values are EXPANDED from the lines a key block at a time
  (``[kb, H, dn + dv]``, never the whole context), scores and the running
  softmax a query tile at a time, as ``ops.attention._merge_block`` folds
  blocks; compute-bound, ``2 H (dn + dr + dv)`` FLOP a query-key pair.
- :func:`absorbed` — one query a row (a decode step): ``W_uk`` is multiplied
  into the query and ``W_uv`` into the output by the caller, so a row's query
  is ``[q_nope W_uk | q_pe | 0..]`` of a line's width, the scores are ``q .
  line`` and the values are the line's first ``rank`` lanes: each line is read
  ONCE as key and value, no per-head key or value of a cached position ever
  exists. ``2 H (width + rank)`` FLOP for ``2 width`` bytes a row-position: on
  the v5e's ridge.

Who runs :func:`absorbed`'s kernel (:func:`decode_kernel`,
``latent_decode_attention`` in a trace) is read off the inputs
(:func:`absorbed_takes_kernel`): whole lane tiles, a cache the block cuts in two
or more, the TPU backend, one device. Everything else contracts the whole cache
in ``jnp`` under a mask (:func:`absorbed_reference`, the kernel's reference in
the tests). The kernel is this file's own (PR 47): its grid is FLAT over the
(row, block) pairs that exist — ``row_of`` and ``block_of`` as prefetched
scalars from a cumulative sum of the rows' block counts, the grid's one bound a
traced scalar — so a block a row does not hold costs no step, and a step does
what one shared line needs and no more: no head comparison, the position mask
in a row's last block alone. It shares nothing with the ragged decode kernel of
``ops.attention`` but the online-softmax recurrence: that one serves eight KV
heads a line behind a head mask and wants a short block, this one wants the
longest fold the bytes read past a row's context allow (PERF.md, PR 47).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from modelx_tpu.ops import attention as attn_ops

NEG_INF = attn_ops.NEG_INF
LANES = 128
# positions a block of the absorbed kernel holds: 2,560 KB of lines, double
# buffered, and [H, 2048] float32 scores in VMEM. The copy of the lines is the
# kernel's floor (alone it runs at 0.91 of the HBM peak, and the scores and the
# softmax hide behind it whole); the second product's wait for the softmax adds
# 0.33-0.41 us a block whatever its length, so the block is as long as what a
# row reads past its context allows: at most one block, 1,024 positions a row
# in the mean, 5 % of a 21 k context (my chip runs, PR 47: 1,024 reads a call
# 10 % slower, 4,096 would read 10 % past)
ABSORBED_BLOCK = 2048
# positions whose keys and values one step of ``expanded`` expands
EXPAND_BLOCK = 1024
# bytes of float32 scores one query tile of ``expanded`` may hold
SCORE_BYTES = 2**28


def line_width(rank: int, rope: int) -> int:
    """Lanes of a cached line: ``rank + rope`` rounded up to whole tiles."""
    return -(-(rank + rope) // LANES) * LANES


def absorbed_block(cache_len: int) -> int:
    """Positions a block of the absorbed kernel holds for a cache of
    ``cache_len``: ``ABSORBED_BLOCK``, halved until it cuts the cache in two
    blocks or more; 0 where none does."""
    block = ABSORBED_BLOCK
    while block and (cache_len % block or cache_len < 2 * block):
        block //= 2
    return block


def absorbed_takes_kernel(cache_shape: tuple, rank: int, impl: str = "auto",
                          mesh=None) -> tuple[int, bool]:
    """(the kernel's block or 0, interpret) for one decode step over a cache
    ``[B, L, W]`` — from shapes, the backend and the mesh alone, so that the
    engine's counters can ask without tracing (a stored program is never
    traced). ``impl`` ``"ragged"`` / ``"ragged+interpret"`` asks for the kernel
    by name wherever a block tiles the cache."""
    name, _, flag = impl.partition("+")
    block = absorbed_block(cache_shape[1])
    if name == "ragged":
        return block, flag == "interpret"
    ok = (block >= attn_ops.RAGGED_MIN_BLOCK and cache_shape[2] % LANES == 0
          and rank % LANES == 0 and jax.default_backend() == "tpu"
          and (mesh is None or mesh.size == 1))
    return (block if ok else 0), False


def absorbed(q, cache, offsets, scale: float, rank: int, *, impl: str = "auto", mesh=None):
    """One decode step in the absorbed form. q ``[B, H, W]`` (the absorbed
    query, the roped part, zeros), cache ``[B, L, W]`` holding every row's
    lines up to and including position ``offsets`` ``[B]``. Returns the
    attention-weighted sum of the lines' first ``rank`` lanes, ``[B, H, rank]``
    in q's dtype (the caller applies ``W_uv``)."""
    block, interpret = absorbed_takes_kernel(cache.shape, rank, impl, mesh)
    attn_ops.note_choice("latent" if block else "latent_reference", 1, cache.shape[1], mesh)
    if block:
        return decode_kernel(q, cache, offsets + 1, scale, rank, block=block,
                             interpret=interpret)
    return absorbed_reference(q, cache, offsets, scale, rank)


def absorbed_reference(q, cache, offsets, scale: float, rank: int):
    """:func:`absorbed` in ``jnp``: the whole cache contracted under a mask."""
    scores = jnp.einsum("bhw,blw->bhl", q, cache, preferred_element_type=jnp.float32) * scale
    visible = jnp.arange(cache.shape[1])[None, :] <= offsets[:, None]
    probs = jax.nn.softmax(jnp.where(visible[:, None, :], scores, NEG_INF), axis=-1)
    return jnp.einsum("bhl,blc->bhc", probs.astype(cache.dtype), cache[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def block_table(lengths, block: int, steps: int):
    """The kernel's schedule for rows of ``lengths`` [B] (1 or more each):
    ``row_of`` and ``block_of`` [steps] int32 — step ``s`` folds block
    ``block_of[s]`` of row ``row_of[s]``, a row's blocks one after another —
    and the number of steps that exist, ``sum(ceil(lengths / block))``. Entries
    past that count are never visited."""
    blocks = -(-lengths // block)
    ends = jnp.cumsum(blocks)
    step = jnp.arange(steps, dtype=jnp.int32)
    # the rows that end at or before a step: a compare and a sum (a
    # ``searchsorted`` is a loop on the TPU and cost 0.14 ms a call, PR 47)
    row_of = jnp.minimum(jnp.sum(ends[None, :] <= step[:, None], axis=1), lengths.shape[0] - 1)
    block_of = step - (ends - blocks)[row_of]
    return row_of.astype(jnp.int32), block_of.astype(jnp.int32), ends[-1].astype(jnp.int32)


def _decode_kernel(row_ref, block_ref, len_ref, q_ref, lines_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, block: int, rank: int, sm_scale: float):
    """One (row, block) step of :func:`decode_kernel`. q_ref [H, W]: every head
    of the row; lines_ref [block, W]: one block of the row's lines, key and
    value at once — copied once, contracted twice. The online-softmax state
    (m, l, acc) lives in scratch across a row's blocks: reset at its first,
    written out at its last, the only one that can hold a position past the
    row's length."""
    step = pl.program_id(0)
    row, j = row_ref[step], block_ref[step]
    length = len_ref[row]
    last = (length - 1) // block

    def fold(visible):
        s = jax.lax.dot_general(
            q_ref[...], lines_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, block]
        if visible is not None:
            s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(lines_ref.dtype), lines_ref[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < last)
    def _():
        fold(None)

    @pl.when(j == last)
    def _():
        # the last block holds position ``last * block`` < length, so the
        # running max is real even where it is the row's only block
        fold(jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) < length - j * block)
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def decode_kernel(q, cache, lengths, scale: float, rank: int, *, block: int,
                  interpret: bool = False):
    """:func:`absorbed` as a Pallas kernel, each row over its own context only.
    q [B, H, W], cache [B, L, W] as the engine keeps it, ``lengths`` [B] the
    positions each row holds (clipped to 1..L: an idle row folds one block).
    Row i folds ``ceil(lengths[i] / block)`` blocks of ``block`` positions with
    an online softmax — operands as they are, f32 scores, statistics and
    accumulator — and the grid has exactly that many steps (:func:`block_table`),
    the input pipeline's index maps reading row and block from the table.
    Returns [B, H, rank] in q's dtype."""
    b, h, width = q.shape
    cache_len = cache.shape[1]
    if not block or cache_len % block:
        raise ValueError(f"no block of {block} positions tiles a cache of {cache_len}")
    rows = -(-h // attn_ops.FLASH_ROW_TILE) * attn_ops.FLASH_ROW_TILE
    if rows != h:  # a packed bf16 tile is 16 rows; a pad row's query is zero
        q = jnp.pad(q, ((0, 0), (0, rows - h), (0, 0)))
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, cache_len)
    row_of, block_of, steps = block_table(lengths, block, b * (cache_len // block))
    per_row = lambda lanes: pl.BlockSpec(  # noqa: E731
        (None, rows, lanes), lambda s, row_of, block_of, lens: (row_of[s], 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block=block, rank=rank, sm_scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[per_row(width),
                      pl.BlockSpec((None, block, width),
                                   lambda s, row_of, block_of, lens: (row_of[s], block_of[s], 0))],
            out_specs=per_row(rank),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attention",
    )(row_of, block_of, lengths, q, cache)
    return out[:, :h]


def positions_read(cache_shape: tuple, rank: int, lengths, impl: str = "auto", mesh=None):
    """Positions whose lines :func:`absorbed` reads for rows of ``lengths``
    [B] (a row that holds nothing: 0): whole blocks up to each row's context
    where the kernel runs, the whole cache where it does not."""
    block, _ = absorbed_takes_kernel(cache_shape, rank, impl, mesh)
    if block:
        return jnp.where(lengths > 0, jnp.minimum(-(-lengths // block) * block, cache_shape[1]), 0)
    return jnp.where(lengths > 0, cache_shape[1], 0)


def _query_tile(b: int, h: int, s: int, kb: int) -> int:
    """Query positions a tile of :func:`expanded` holds: a power of two that
    divides ``s`` and keeps ``[b, h, tile, kb]`` float32 scores within
    ``SCORE_BYTES`` (16 at least)."""
    tile = s
    while tile > 16 and tile % 2 == 0 and b * h * tile * kb * 4 > SCORE_BYTES:
        tile //= 2
    return tile


def expanded(q_nope, q_pe, cache, offset, w_kvb, scale: float, rank: int, *,
             key_block: int = EXPAND_BLOCK, selected=None):
    """A block of query positions against the lines, keys and values expanded
    a key block at a time. q_nope ``[B, S, H, dn]``, q_pe ``[B, S, H, dr]``
    (roped), cache ``[B, L, W]`` already holding the block's own lines at
    ``offset`` (a scalar, or one start a row), w_kvb ``[H, dn + dv, rank]``.
    Causal by absolute position; ``selected`` (bool ``[B, S, L]``, a learned
    selector's choice of positions a query, ``ops/index_select.py``) hides what
    it leaves out as well; a query that may see nothing of the first key blocks
    folds them at the finite ``NEG_INF``, and its first real maximum wipes
    that. Returns ``[B, S, H, dv]`` in q's dtype.

    Key blocks past the last query are not visited (the loop's bound follows
    ``offset``); the first block holds position 0, which every query sees, so
    every running maximum is real from the first step on."""
    b, s, h, dn = q_nope.shape
    dr, cache_len = q_pe.shape[-1], cache.shape[1]
    dv = w_kvb.shape[1] - dn
    kb = cache_len if cache_len <= key_block else math.gcd(cache_len, key_block)
    tile = _query_tile(b, h, s, kb)
    tiles = s // tile
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    # [tiles, B, tile, H, d]: the tiles one after another (``lax.map``), so that
    # one tile's scores are live at a time
    split = lambda x: jnp.moveaxis(x.reshape(b, tiles, tile, *x.shape[2:]), 1, 0)  # noqa: E731
    qn, qp = split(q_nope), split(q_pe)
    starts = jnp.arange(tiles, dtype=jnp.int32) * tile

    def fold(i, carry):
        lines = jax.lax.dynamic_slice_in_dim(cache, i * kb, kb, axis=1)
        kv = jnp.einsum("bkc,hdc->bkhd", lines[..., :rank], w_kvb,
                        preferred_element_type=jnp.float32).astype(cache.dtype)
        k_nope, v, k_pe = kv[..., :dn], kv[..., dn:], lines[..., rank: rank + dr]
        kpos = i * kb + jnp.arange(kb)
        chosen = () if selected is None else (
            split(jax.lax.dynamic_slice_in_dim(selected, i * kb, kb, axis=2)),)

        def one_tile(args):
            q_n, q_p, start, acc, m_prev, l_prev, *keep = args
            scores = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_nope,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bqhd,bkd->bhqk", q_p, k_pe,
                                   preferred_element_type=jnp.float32)) * scale
            qpos = offset[:, None] + start + jnp.arange(tile)[None, :]  # [B, tile]
            visible = kpos[None, None, :] <= qpos[:, :, None]  # [B, tile, kb]
            if keep:
                visible = visible & keep[0]
            scores = jnp.where(visible[:, None], scores, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new[..., None])
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        return jax.lax.map(one_tile, (qn, qp, starts, *carry, *chosen))

    carry = (jnp.zeros((tiles, b, h, tile, dv), jnp.float32),
             jnp.full((tiles, b, h, tile), NEG_INF, jnp.float32),
             jnp.zeros((tiles, b, h, tile), jnp.float32))
    blocks = jnp.minimum((jnp.max(offset) + s + kb - 1) // kb, cache_len // kb)
    acc, _, l = jax.lax.fori_loop(0, blocks, fold, carry)
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [tiles, B, H, tile, dv]
    out = jnp.moveaxis(out, 0, 1).transpose(0, 1, 3, 2, 4).reshape(b, s, h, dv)
    return out.astype(q_nope.dtype)
