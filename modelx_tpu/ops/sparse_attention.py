"""Block-sparse attention with a learned selection (the MiniCPM4 form), plain
``jax.numpy``: a query attends only the key blocks a cheap pass over
COMPRESSED keys picks for its KV head.

With stride ``s``, compressed key ``i`` is the mean of the keys at positions
``[s i, s i + 2 s)``. The cache keeps them in an INDEX ``[.., L / s, Hkv, D]``
whose entry ``j`` is compressed key ``j - 1`` — the window ending at position
``s (j + 1)``, complete once that many positions are written, so that a block
of positions starting at a multiple of ``s`` owns the entries of the same
range (entry 0 is never valid). A query with context ``n`` sees entry ``j``
when ``j >= 1`` and ``s (j + 1) <= n``.

Selection (:func:`select_blocks`), one per KV head: softmax of the head's
queries over the entries they see, summed over the query group; a block's
score is the largest over the entries whose positions touch it (entries ``r
b .. r b + r``, ``r = block / s``); the first ``init_blocks`` blocks and the
``window_size / block`` blocks ending at the query's own are forced; the
``topk`` highest are taken, ties to the lower index. A query whose context
is below ``dense_len`` attends all of its positions instead.

Two attentions over the selection. A one-token step reads the selected blocks
and nothing else of the row, in one of two ways to fetch the same operands:
:func:`decode_attention` GATHERS them with ``jnp.take`` (the reference: the
CPU, a mesh, narrow heads), :func:`decode_attention_kernel` copies each (row,
KV head)'s blocks from the cache as it lies into VMEM, once, with the block
indices as prefetched scalars (:func:`decode_takes_kernel` says which, from
what it can observe). :func:`prefill_attention` walks a block of queries over
the row's key tiles with a running softmax under the selected-block mask
(dense arithmetic, no ``[queries, heads, keys]`` array over a row).

Keys and values lie as ``[B, L, Hkv * D]``, a position's KV heads side by side
in one row: a block of positions is then one contiguous run of the array as
the chip tiles it, and taking blocks is a gather of whole runs. As ``[B, L,
Hkv, D]`` with 2 KV heads the compiler lays the heads outermost, and the
reshape that a gather of blocks needs copied every leaf in every step (my chip
run, PR 35: 23 of a 45 ms step).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of a packed bf16 tile: what a block is a multiple of and a query group is padded to
from modelx_tpu.ops.attention import FLASH_ROW_TILE as ROW_TILE

NEG = -1e30
Q_TILE, K_TILE = 256, 1024


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    kernel_size: int = 32
    kernel_stride: int = 16
    init_blocks: int = 1
    block_size: int = 64
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self) -> None:
        if self.kernel_size != 2 * self.kernel_stride:
            raise ValueError("sparse attention: kernel_size other than 2 x kernel_stride is "
                             "not implemented")
        if self.block_size % self.kernel_stride or 16 % self.kernel_stride:
            raise ValueError("sparse attention: kernel_stride must divide block_size and the "
                             "16-token prompt bucket")

    @property
    def entries_per_block(self) -> int:
        return self.block_size // self.kernel_stride


def compress(k_ext, spec: SparseSpec):
    """Index entries of a run of positions. ``k_ext`` ``[B, s + C, Hkv, D]``:
    the ``s`` positions before the run, then its ``C`` (a multiple of ``s``)
    -> ``[B, C / s, Hkv, D]``, entry m the mean of ``k_ext[m s : m s + 2 s]``."""
    b, n, hkv, d = k_ext.shape
    s = spec.kernel_stride
    halves = k_ext.astype(jnp.float32).reshape(b, n // s, s, hkv, d).sum(axis=2)
    return ((halves[:, :-1] + halves[:, 1:]) / (2 * s)).astype(k_ext.dtype)


def select_blocks(q, index, context, spec: SparseSpec, blocks: int):
    """q ``[B, Q, H, D]``, index ``[B, J, Hkv, D]``, context ``[B, Q]`` (each
    query's position + 1) -> the selected blocks ``[B, Q, Hkv, K]`` of
    ``blocks``, ``K = min(topk, blocks)``, highest score first."""
    b, nq, h, d = q.shape
    j, hkv = index.shape[1], index.shape[2]
    s, r = spec.kernel_stride, spec.entries_per_block
    qg = q.reshape(b, nq, hkv, h // hkv, d)
    logits = jnp.einsum("bqhgd,bjhd->bqhgj", qg, index,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    at = jnp.arange(j)
    seen = (at[None, None, :] >= 1) & (s * (at[None, None, :] + 1) <= context[:, :, None])
    seen = seen[:, :, None, None, :]
    probs = jax.nn.softmax(jnp.where(seen, logits, NEG), axis=-1)
    summed = jnp.where(seen[:, :, :, 0], jnp.sum(jnp.where(seen, probs, 0.0), axis=3), -jnp.inf)
    # block b's score: the largest over entries r b .. r b + r
    need = r * blocks + r
    summed = jnp.pad(summed, ((0, 0),) * 3 + ((0, max(need - j, 0)),),
                     constant_values=-jnp.inf)[..., :need]
    score = summed[..., 0: r * blocks: r]
    for e in range(1, r + 1):
        score = jnp.maximum(score, summed[..., e: e + r * blocks: r])
    own = ((context - 1) // spec.block_size)[:, :, None, None]
    blk = jnp.arange(blocks)[None, None, None, :]
    forced = (blk < spec.init_blocks) | (blk > own - spec.window_size // spec.block_size)
    score = jnp.where(blk > own, -jnp.inf, jnp.where(forced, jnp.inf, score))
    return jax.lax.top_k(score, min(spec.topk, blocks))[1]


def decode_attention(q, k_cache, v_cache, chosen, position, spec: SparseSpec):
    """One query a row over its selected blocks, gathered. q ``[B, H, D]``,
    caches ``[B, L, Hkv * D]`` (``L`` a multiple of the block), chosen ``[B,
    Hkv, K]``, position ``[B]`` -> ``[B, H, D]`` float32. A selected block is
    read whole — its positions' rows hold every KV head, so a head's gather
    brings the other heads' lanes with it — and positions past the query's
    are masked."""
    b, h, d = q.shape
    length = k_cache.shape[1]
    hkv = k_cache.shape[2] // d
    size = spec.block_size
    blocks = length // size
    rows = jnp.arange(b)[:, None, None] * blocks + chosen  # [B, Hkv, K]: all in bounds

    def gather(cache):  # -> [B, Hkv, K, size, Hkv * D]
        return jnp.take(cache.reshape(b * blocks, size, hkv * d), rows, axis=0, mode="clip")

    kg, vg = gather(k_cache), gather(v_cache)
    qg = q.reshape(b, hkv, h // hkv, d)
    at = chosen[..., None] * size + jnp.arange(size)  # [B, Hkv, K, size]
    visible = at <= position[:, None, None, None]
    outs = []
    for i in range(hkv):  # a head's own lanes of the blocks gathered for it
        k_i, v_i = kg[:, i, :, :, i * d: (i + 1) * d], vg[:, i, :, :, i * d: (i + 1) * d]
        logits = jnp.einsum("bgd,bkpd->bgkp", qg[:, i], k_i,
                            preferred_element_type=jnp.float32) / math.sqrt(d)
        shape = logits.shape
        probs = jax.nn.softmax(jnp.where(visible[:, i, None], logits, NEG)
                               .reshape(*shape[:2], -1), axis=-1)
        outs.append(jnp.einsum("bgkp,bkpd->bgd", probs.reshape(shape).astype(v_i.dtype), v_i,
                               preferred_element_type=jnp.float32))
    return jnp.stack(outs, axis=1).reshape(b, h, d)


def decode_takes_kernel(q, k_cache, spec: SparseSpec, impl: str = "auto", mesh=None):
    """Whether a one-token step over these operands takes
    :func:`decode_attention_kernel`, and whether interpreted -> (take,
    interpret). Read off what the code can observe, no flag: the TPU backend,
    one device (a bare Mosaic call cannot be partitioned), heads of a multiple
    of 128 lanes (a head's lanes of a row are then whole tiles), blocks of a
    multiple of the bf16 tile's 16 rows. ``impl`` ``"sparse"``
    (``"sparse+interpret"`` on the CPU) asks for the kernel by name; any other
    name leaves the choice here."""
    name, _, flag = impl.partition("+")
    if name == "sparse":
        return True, flag == "interpret"
    d = q.shape[-1]
    return (jax.default_backend() == "tpu" and (mesh is None or mesh.size == 1)
            and d % 128 == 0 and spec.block_size % ROW_TILE == 0
            and k_cache.ndim == 3 and k_cache.shape[2] % d == 0), False


def _sparse_decode_kernel(chosen_ref, position_ref, q_ref, at_ref, k_hbm, v_hbm, o_ref,
                          k_buf, v_buf, sems, *, kv_heads: int, size: int, sm_scale: float,
                          run: tuple[int, int] | None):
    """One (row, KV head) program of :func:`decode_attention_kernel`.

    k_hbm / v_hbm: the caches as they lie, ``[B, L, Hkv * D]``, never staged
    whole. ``chosen_ref`` ``[B * Hkv * K]`` in scalar memory names the pair's
    blocks; each is copied — the head's own ``D`` lanes of ``size`` rows — to
    its place in ``k_buf`` / ``v_buf`` ``[2, K * size, D]``. ``run`` ``(first,
    count)``: where the selection's entries ``first .. first + count`` name
    blocks that lie side by side in the row (the window ending at the query's
    own, which ``select_blocks`` returns first and in order) they are one
    copy, checked pair by pair; any other selection copies block by block.
    The copies of pair ``n + 1`` are started before pair ``n`` is computed
    (the grid runs in order on one core), so only the first pair's are waited
    for cold. Then the present arithmetic: the group's queries ``[G, D]``
    against the ``K * size`` positions at once, float32 logits, ``at_ref``
    (the positions' places in the row) masked past the row's own, one softmax,
    probabilities x V."""
    n, pairs = pl.program_id(0), pl.num_programs(0)
    d = k_buf.shape[-1]
    k_blocks = k_buf.shape[1] // size
    slot = n % 2

    def transfer(pair, slot, wait: bool):
        """Start, or wait for, every copy of ``pair`` into ``slot``."""
        row, head = pair // kv_heads, pair % kv_heads
        base = pair * k_blocks
        lanes = pl.ds(pl.multiple_of(head * d, d), d)

        def copy(j, blocks=1):
            first = pl.multiple_of(chosen_ref[base + j] * size, size)
            into = pl.ds(pl.multiple_of(j * size, size), blocks * size)
            for i, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                dma = pltpu.make_async_copy(hbm.at[row, pl.ds(first, blocks * size), lanes],
                                            buf.at[slot, into], sems.at[slot, i])
                if wait:
                    dma.wait()
                else:
                    dma.start()

        def each(lo, hi):
            if hi > lo:
                jax.lax.fori_loop(lo, hi, lambda j, _: copy(j), None)

        if run is None:
            return each(0, k_blocks)
        lo, count = run
        each(0, lo)
        each(lo + count, k_blocks)
        first = chosen_ref[base + lo]
        side_by_side = jax.lax.fori_loop(
            1, count, lambda j, ok: ok & (chosen_ref[base + lo + j] == first + j), True)
        pl.when(side_by_side)(lambda: copy(lo, count))
        pl.when(jnp.logical_not(side_by_side))(lambda: each(lo, lo + count))

    @pl.when(n == 0)
    def _():
        transfer(0, 0, wait=False)

    @pl.when(n + 1 < pairs)
    def _():
        transfer(n + 1, 1 - slot, wait=False)

    transfer(n, slot, wait=True)
    logits = jax.lax.dot_general(q_ref[...], k_buf[slot], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * sm_scale  # [G, K * size]
    logits = jnp.where(at_ref[...] <= position_ref[n // kv_heads], logits, NEG)
    p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = p / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[...] = jax.lax.dot_general(probs.astype(v_buf.dtype), v_buf[slot],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)


def decode_attention_kernel(q, k_cache, v_cache, chosen, position, spec: SparseSpec, *,
                            interpret: bool = False):
    """:func:`decode_attention` with another way to fetch its operands: same
    inputs, same selection, same precision (operands as they are, float32
    logits and accumulation, one softmax over the ``K * block`` positions),
    ``[B, H, D]`` float32 out — algebraically the reference, not bit-identical
    to it. A Pallas kernel over the (row, KV head) pairs reads each selected
    block ONCE, the head's own lanes only, from the cache where it lies; the
    gather reads every block with all KV heads' lanes, writes the copy to HBM
    and reads it back. Nothing but the output (and ``at``, 4 bytes a position
    attended) passes through HBM."""
    b, h, d = q.shape
    hkv = k_cache.shape[2] // d
    size, k_blocks = spec.block_size, chosen.shape[-1]
    group = h // hkv
    rows = -(-group // ROW_TILE) * ROW_TILE
    qg = q.reshape(b * hkv, group, d)
    if rows != group:  # pad rows attend like any other and are cut away
        qg = jnp.pad(qg, ((0, 0), (0, rows - group), (0, 0)))
    at = (chosen[..., None] * size + jnp.arange(size)).astype(jnp.int32)
    window = spec.window_size // size
    run = (spec.init_blocks, window) if 1 < window <= k_blocks - spec.init_blocks else None
    per_pair = lambda *shape: pl.BlockSpec((None, *shape), lambda n, *_: (n, 0, 0))  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, kv_heads=hkv, size=size,
                          sm_scale=1.0 / math.sqrt(d), run=run),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b * hkv,),
            in_specs=[per_pair(rows, d), per_pair(1, k_blocks * size),
                      pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_pair(rows, d),
            scratch_shapes=[pltpu.VMEM((2, k_blocks * size, d), k_cache.dtype),
                            pltpu.VMEM((2, k_blocks * size, d), v_cache.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((b * hkv, rows, d), jnp.float32),
        # a pair's program starts the next pair's copies: the grid runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret, name="sparse_decode_attention",
    )(chosen.reshape(-1).astype(jnp.int32), position.astype(jnp.int32), qg,
      at.reshape(b * hkv, 1, k_blocks * size), k_cache, v_cache)
    return out[:, :group].reshape(b, h, d)


def prefill_attention(q, k_row, v_row, index, start, spec: SparseSpec,
                      q_tile: int = Q_TILE, k_tile: int = K_TILE):
    """A block of queries at positions ``start ..`` over a row that already
    holds their keys. q ``[B, C, H, D]``, rows ``[B, L, Hkv * D]`` (or ``[B, L,
    Hkv, D]``), index ``[B, J, Hkv, D]``, ``start`` a scalar -> ``[B, C, H,
    D]`` float32. Query tile
    by query tile: the selection of the tile's sparse queries (skipped where
    every context is below ``dense_len``), then the key tiles up to the
    tile's last position under a running softmax, a key visible when it is
    causal and — for a sparse query — its block is selected."""
    b, c, h, d = q.shape
    length, hkv = k_row.shape[1], index.shape[2]
    k_row, v_row = k_row.reshape(b, length, hkv * d), v_row.reshape(b, length, hkv * d)
    size = spec.block_size
    k_tile = -(-min(k_tile, length) // size) * size
    pad_l = -length % k_tile
    if pad_l:  # keys past every query: the causal mask hides them
        k_row, v_row = (jnp.pad(x, ((0, 0), (0, pad_l), (0, 0))) for x in (k_row, v_row))
    length += pad_l
    blocks, per_tile = length // size, k_tile // size
    qt = min(q_tile, c)
    pad_q = -c % qt
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    tiles = (c + pad_q) // qt
    group = h // hkv
    start = jnp.asarray(start, jnp.int32)
    scale = 1.0 / math.sqrt(d)

    def tile(xs):
        q_t, first = xs  # [B, qt, H, D], the tile's first position
        pos = first + jnp.arange(qt)  # [qt]
        dense = pos + 1 < spec.dense_len

        def select(_):
            chosen = select_blocks(q_t, index, jnp.broadcast_to(pos + 1, (b, qt)), spec, blocks)
            return (chosen[..., None] == jnp.arange(blocks)).any(axis=-2)  # [B, qt, Hkv, blocks]

        picked = jax.lax.cond(jnp.all(dense), lambda _: jnp.zeros((b, qt, hkv, blocks), bool),
                              select, None)
        qg = q_t.reshape(b, qt, hkv, group, d)

        def keys(i, carry):
            m, l, acc = carry
            k_t, v_t = (jax.lax.dynamic_slice_in_dim(x, i * k_tile, k_tile, axis=1)
                        .reshape(b, k_tile, hkv, d) for x in (k_row, v_row))
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_t,
                                preferred_element_type=jnp.float32) * scale
            at = i * k_tile + jnp.arange(k_tile)
            mine = jax.lax.dynamic_slice_in_dim(picked, i * per_tile, per_tile, axis=3)
            mine = jnp.repeat(mine, size, axis=3) | dense[None, :, None, None]  # [B,qt,Hkv,kt]
            visible = mine.transpose(0, 2, 1, 3)[:, :, None] & (
                at[None, :] <= pos[:, None])[None, None, None]
            m_new = jnp.maximum(m, jnp.max(jnp.where(visible, logits, NEG), axis=-1))
            p = jnp.where(visible, jnp.exp(logits - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_t.dtype), v_t, preferred_element_type=jnp.float32)
            return m_new, l * alpha + jnp.sum(p, axis=-1), acc

        init = (jnp.full((b, hkv, group, qt), NEG, jnp.float32),
                jnp.zeros((b, hkv, group, qt), jnp.float32),
                jnp.zeros((b, hkv, group, qt, d), jnp.float32))
        upto = jnp.minimum((first + qt + k_tile - 1) // k_tile, length // k_tile)
        _, l, acc = jax.lax.fori_loop(0, upto, keys, init)
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, Hkv, G, qt, D]
        return out.transpose(0, 3, 1, 2, 4).reshape(b, qt, h, d)

    firsts = start + qt * jnp.arange(tiles)
    out = jax.lax.map(tile, (jnp.moveaxis(q.reshape(b, tiles, qt, h, d), 1, 0), firsts))
    return jnp.moveaxis(out, 0, 1).reshape(b, tiles * qt, h, d)[:, :c]
