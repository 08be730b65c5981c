"""Attention: reference implementation, a Pallas TPU flash kernel, and ring
attention for sequence/context parallelism.

TPU-first design notes (pallas_guide.md):

- the flash kernel tiles q into VMEM blocks and streams k/v blocks,
  carrying the online-softmax (m, l, acc) state so HBM traffic is O(n)
  per q block instead of materializing the n×n score matrix;
- block sizes are multiples of the (8/16, 128) tile constraints, and the
  matmuls are shaped to land on the 128×128 MXU in fp32 accumulation;
- ring attention (long-context, first-class per the build brief) shards
  the sequence across the ``sp`` mesh axis with `shard_map`; each step
  computes local flash statistics against the resident k/v block and
  `ppermute`s k/v around the ring, so peak memory per device is
  O(seq/sp_devices) and comms ride ICI neighbor links.

All three paths compute the same math; tests cross-check them (on the CPU
they ask for the pallas kernel's interpret mode by name — nothing selects
it for them).

Nothing here substitutes one implementation for another behind the
caller's back: ``flash_attention`` always runs the kernel (padding ragged
lengths up to the block), compiles it for the backend it is on, and under a
multi-device mesh wraps it in ``shard_map`` — a bare Mosaic call cannot be
partitioned by GSPMD. Which implementation a model's forward compiled with
is recorded at trace time by :func:`note_choice`.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from modelx_tpu.utils import trace

NEG_INF = -1e30
FLASH_BLOCK = 128  # q and k block: one MXU tile edge
# rows per packed bf16 sublane tile: Mosaic refuses a block (and the k-loop's
# dynamic slice) whose row count is not a multiple of the tile — "cannot
# statically prove that index in dimension 1 is a multiple of 8" for a
# 5-token /v1/forward, which interpret mode never shows
FLASH_ROW_TILE = 16
# (position, KV head) pairs one step of the ragged decode kernel contracts:
# 256 positions of 8 KV heads. On the v5e 256, 512 and 1024 positions read a
# whole cache at the same 0.91 of the HBM peak; rows at a third of the cache
# cost 0.655, 0.673 and 0.799 ms a layer — a shorter block reads less past a
# row's length, and a skipped grid step costs 0.27 us (PERF.md, PR 34)
RAGGED_COLUMNS = 2048
# fewest positions a block may hold where nobody asked for the kernel by name
RAGGED_MIN_BLOCK = 128
# most bytes of keys and values one ring may hold for the ring decode kernel,
# whose block is the whole ring: compiled for a described v5e under 72 query
# heads, 4.26 MB (1,040 positions of 8 KV heads of 128 in bf16) fits Mosaic's
# own VMEM limit — both double-buffered beside the f32 logits — and 8.45 MB
# (2,064) does not. Laguna's 528 positions are 2.16 MB
RING_BLOCK_BYTES = 9 << 19
# the block of a ``[B, L, Hkv * D]`` leaf (a position's heads in one line) is
# what four KV heads' would be: RAGGED_COLUMNS // 4 = 512 positions, 0.79 MB of
# 768-wide keys — a line is one column there, whatever heads it holds
FLAT_KV_HEADS = 4


# -- reference (jnp) ----------------------------------------------------------


def attention_reference(q, k, v, causal: bool = True, q_offset=0,
                        scale: float | None = None, logit_softcap: float = 0.0,
                        window: int = 0, key_positions=None, sinks=None):
    """Plain softmax(QK^T * scale)V. Shapes: [B, H, S, D] (kv may have fewer
    heads than q — GQA — as long as H % Hkv == 0). ``q_offset`` positions the
    queries for cached decode: a scalar for uniform batches, or a [B] vector
    for ragged ones (each row decoding from its own prompt length).

    ``scale`` defaults to 1/sqrt(head_dim); gemma2-style attention passes
    query_pre_attn_scalar**-0.5 instead. ``logit_softcap`` > 0 applies
    cap * tanh(logits / cap) BEFORE masking (the gemma2 convention).
    ``window`` > 0 limits each query to its last ``window`` keys (sliding
    window attention; needs ``causal``). ``key_positions`` ([B, K] int, needs
    ``causal``) gives each key's absolute position where index and position
    differ — a ring written at ``position mod K`` — and a negative entry
    marks a key that holds nothing yet. The values may be narrower or wider
    than the keys (``v`` ``[B, Hkv, K, Dv]``: the output is ``[B, H, S, Dv]``).
    ``sinks`` ``[H]``: one learned logit a query head that joins the softmax's
    denominator and carries no value — ``a_j = exp(s_j) / (exp(sink) + sum_i
    exp(s_i))``; it is neither scaled nor masked."""
    b, hq, qlen, d = q.shape
    qk, pv = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
    if k.shape[1] != hq:
        # GQA: fold the query heads over their KV head (head h reads KV head
        # h // G, what a repeat along axis 1 means) and contract against k/v
        # as they lie — unfolding a decode cache G-fold, per layer and step,
        # moved more bytes than the weights did
        q = q.reshape(b, k.shape[1], hq // k.shape[1], qlen, d)
        qk, pv = "bhgqd,bhkd->bhgqk", "bhgqk,bhkd->bhgqd"
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum(qk, q, k, preferred_element_type=jnp.float32) * scale
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    if causal:
        off = jnp.asarray(q_offset)
        qpos = jnp.arange(qlen)[:, None] + (
            jax.lax.expand_dims(off, range(1, logits.ndim)) if off.ndim else off
        )  # [Q,K] or [B,1,(1,)Q,K]
        if key_positions is None:
            kpos = jnp.arange(k.shape[2])[None, :]
            visible = kpos <= qpos
        else:  # [B, K] -> [B, 1, (1,) 1, K] against qpos [B, 1, (1,) Q, 1]
            kpos = jax.lax.expand_dims(key_positions, range(1, logits.ndim - 1))
            visible = (kpos <= qpos) & (kpos >= 0)
        if window > 0:  # keys qpos-window < kpos <= qpos stay visible
            visible = visible & (kpos > qpos - window)
        logits = jnp.where(visible, logits, NEG_INF)
    if sinks is not None:
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(logits.shape[1:-2] + (1, 1)),
            logits.shape[:-1] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([logits, sink], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(pv, probs.astype(v.dtype), v).reshape(b, hq, qlen, v.shape[-1])


def _repeat_kv_heads(q, k, v):
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return q, k, v


# -- pallas flash kernel ------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, *rest, block_k: int, causal: bool,
                  sm_scale: float, logit_softcap: float = 0.0, window: int = 0,
                  kv_len: int = 0):
    """One (batch*head, q-block) program: online softmax over k/v blocks.

    q_ref: [block_q, d], k_ref: [seq_k, d], v_ref: [seq_k, dv], o_ref:
    [block_q, dv]. With a sink (``rest`` = sink_ref [1, 1], o_ref) the online
    softmax starts from the head's sink logit — (m, l, acc) = (sink, 1, 0) in
    place of (-inf, 0, 0): mass in the denominator that carries no value.
    ``logit_softcap`` > 0 tanh-caps the scaled scores before masking and
    ``window`` > 0 limits each query to its last ``window`` keys (gemma2);
    both default off, preserving the plain flash semantics. ``kv_len`` > 0
    says only the first ``kv_len`` keys are real (the rest is block
    padding) and masks the tail.
    """
    *sink_ref, o_ref = rest
    block_q, dv = o_ref.shape
    seq_k = k_ref.shape[0]
    q_idx = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32) * sm_scale

    def body(start_k, carry):
        acc, m_prev, l_prev = carry
        k_blk = k_ref[pl.ds(start_k * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(start_k * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        kpos = start_k * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if causal:
            qpos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            visible = kpos <= qpos
            if window > 0:
                visible = visible & (kpos > qpos - window)
            s = jnp.where(visible, s, NEG_INF)
        if kv_len:
            # padded keys sit in the LAST block behind real ones, so every
            # row's running max is real by then and exp() zeroes them
            s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # multiply by the visibility mask after exp when a block can be
        # fully masked (window mode): exp(NEG_INF - NEG_INF) = 1 otherwise
        p = jnp.exp(s - m_new[:, None])
        if causal and window > 0:
            p = jnp.where(visible, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc, m_new, l_new

    num_k = seq_k // block_k
    lo = 0
    if causal:
        # skip fully-masked k blocks beyond this q block: exact ceiling of
        # the last visible key over block_k. (The previous floor-based form
        # computed ZERO blocks for early q blocks whenever block_k >
        # block_q, silently zeroing those output rows.)
        num_k = jnp.minimum(num_k, ((q_idx + 1) * block_q + block_k - 1) // block_k)
        if window > 0:
            # ...and the fully-below-window blocks before it: the earliest
            # key any query in this block can see is q_idx*bq - window + 1
            lo = jnp.maximum(0, (q_idx * block_q - window + 1) // block_k)
    acc0 = jnp.zeros((block_q, dv), jnp.float32)
    if sink_ref:
        m0 = jnp.broadcast_to(sink_ref[0][...].reshape(1), (block_q,))
        l0 = jnp.ones((block_q,), jnp.float32)
    else:
        m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
    acc, _m, l = jax.lax.fori_loop(lo, num_k, body, (acc0, m0, l0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_blocks(seq: int, block: int = FLASH_BLOCK) -> tuple[int, int]:
    """(block, padded length) the kernel uses for a ``seq``-long axis: one
    tile-aligned block when the axis is short, otherwise ``block`` with the
    length rounded up to a multiple of it."""
    block = min(block, -(-seq // FLASH_ROW_TILE) * FLASH_ROW_TILE)
    return block, -(-seq // block) * block


def _flash_local(q, k, v, sinks=None, *, causal, block_q, block_k, interpret, scale,
                 logit_softcap, window):
    """The kernel on ONE device's share: q [B, H, Sq, D], k [B, Hkv, Sk, D], v
    [B, Hkv, Sk, Dv], ``sinks`` [H] or None (a sink logit a query head).
    Ragged lengths are padded up to the block (padded keys masked in the
    kernel, padded query rows sliced off) — never handed to another
    implementation."""
    q, k, v = _repeat_kv_heads(q, k, v)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, pq = flash_blocks(sq, block_q)
    block_k, pk = flash_blocks(sk, block_k)
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)

    dv = v.shape[-1]

    def rows(x, s, padded):
        x = x.reshape(b * h, s, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, padded - s), (0, 0))) if padded != s else x

    sink_spec, sink_arg = [], []
    if sinks is not None:
        sink_spec = [pl.BlockSpec((None, 1, 1), lambda i, j: (i, 0, 0))]
        sink_arg = [jnp.tile(sinks.astype(jnp.float32), b).reshape(b * h, 1, 1)]
    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                          sm_scale=sm_scale, logit_softcap=logit_softcap,
                          window=window, kv_len=sk if pk != sk else 0),
        grid=(b * h, pq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, pk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, pk, dv), lambda i, j: (i, 0, 0)),
            *sink_spec,
        ],
        out_specs=pl.BlockSpec((None, block_q, dv), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, pq, dv), q.dtype),
        interpret=interpret,
    )(rows(q, sq, pq), rows(k, sk, pk), rows(v, sk, pk), *sink_arg)
    return out[:, :sq].reshape(b, h, sq, dv)


def _axes_dividing(mesh: Mesh, names: tuple[str, ...], dim: int):
    """The mesh axes among ``names`` (size > 1) whose product divides
    ``dim`` — as a PartitionSpec entry (None when there are none)."""
    kept = tuple(a for a in names if a in mesh.axis_names and mesh.shape[a] > 1)
    if not kept or dim % math.prod(mesh.shape[a] for a in kept):
        return None
    return kept if len(kept) > 1 else kept[0]


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret", "scale", "logit_softcap",
    "window", "mesh"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = FLASH_BLOCK,
                    block_k: int = FLASH_BLOCK, interpret: bool = False,
                    scale: float | None = None, logit_softcap: float = 0.0,
                    window: int = 0, mesh: Mesh | None = None, sinks=None):
    """Flash attention via pallas. q/k/v: [B, H, S, D] (GQA allowed; the
    values' width may differ from the keys'). ``sinks`` [H]: a sink logit a
    query head, the online softmax's starting state (:func:`attention_reference`).

    The kernel compiles for the backend it runs on; ``interpret=True`` is
    for callers on the CPU that ask for it (tests, the virtual-device dry
    run) and is never chosen here. ``scale``/``logit_softcap``/``window``
    mirror attention_reference — the gemma2 prefill rides the MXU kernel
    with its own semantics.

    Under a ``mesh`` of more than one device the call is wrapped in
    ``shard_map`` — batch over dp/fsdp, heads over tp, wherever they divide
    — because GSPMD cannot partition a Mosaic kernel by itself; an axis
    that does not divide leaves that dimension replicated.
    """
    local = functools.partial(
        _flash_local, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, scale=scale, logit_softcap=logit_softcap,
        window=window)
    if mesh is None or mesh.size == 1:
        return local(q, k, v, sinks)
    if sinks is not None:
        raise ValueError("attention sinks under a mesh are not implemented")
    batch = _axes_dividing(mesh, ("dp", "fsdp"), q.shape[0])
    heads = _axes_dividing(mesh, ("tp",), k.shape[1])
    if heads is None and _axes_dividing(mesh, ("tp",), q.shape[1]) is not None:
        # fewer kv heads than tp shards: repeat them first so both sides split
        q, k, v = _repeat_kv_heads(q, k, v)
        heads = "tp"
    spec = P(batch, heads, None, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


# -- ragged decode attention (pallas) -----------------------------------------


def ragged_block(cache_len: int, kv_heads: int) -> int:
    """Positions one block of the ragged decode kernel holds for a cache of
    ``cache_len`` positions and ``kv_heads`` heads: ``RAGGED_COLUMNS //
    kv_heads``, halved until it cuts ``cache_len`` into two blocks or more;
    0 where no block does."""
    block = RAGGED_COLUMNS // kv_heads
    while block and (cache_len % block or cache_len < 2 * block):
        block //= 2
    return block


def _fold_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, sm_scale: float, visible):
    """One block of a row's cache folded into its online-softmax state: the
    arithmetic both decode kernels share. q_ref [rows, d]: every query head of
    the row. k_ref / v_ref [columns, d]: the block as it lies, a line a
    (position, KV head) pair. One contraction gives every query head against
    every pair — operands as they are, f32 logits — ``visible()`` [rows,
    columns] says which of them count, and (m, l, acc), f32 in scratch, take
    the block in; ``p`` meets the values in their own dtype."""
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale  # [rows, columns]
    s = jnp.where(visible(), s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _start_state(sink_ref, m_ref, l_ref, acc_ref):
    """A row's online-softmax state before its first block: (-inf, 0, 0), or
    with sinks ``(sink, 1, 0)`` — each query head's sink logit already in the
    denominator, with no value behind it (sink_ref: a list of none or one ref
    ``[rows, 1]``)."""
    if sink_ref:
        m_ref[...] = sink_ref[0][...]
        l_ref[...] = jnp.ones_like(l_ref)
    else:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _ragged_decode_kernel(len_ref, q_ref, k_ref, v_ref, row_head_ref, col_head_ref,
                          col_pos_ref, *rest, block: int, sm_scale: float):
    """One (row, KV block) program of :func:`decode_attention`.

    q_ref [rows, d]: every query head of the row. k_ref / v_ref [block *
    kv_heads, d]: one block of the row's cache as it lies, a line a (position,
    KV head) pair. One contraction gives every query head against every pair;
    ``row_head == col_head`` keeps a head's own KV head (what a repeat of the
    KV heads means) and ``col_pos`` the positions below the row's length. The
    MXU's time here is the loading of the block's tiles, which the lines that
    are masked away share: folding each group onto its KV head would load the
    same tiles and need the heads picked apart first. The online-softmax state
    (m, l, acc) lives in scratch across the row's blocks; a block past the
    row's last does nothing (its index map pointed at the last one, so nothing
    was copied for it either). ``rest``: (sink_ref,) o_ref, m_ref, l_ref,
    acc_ref (:func:`_start_state`)."""
    *sink_ref, o_ref, m_ref, l_ref, acc_ref = rest
    row, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[row]
    last = (length - 1) // block

    @pl.when(j == 0)
    def _():
        _start_state(sink_ref, m_ref, l_ref, acc_ref)

    @pl.when(j <= last)
    def _():
        # block j <= last holds position j * block < length under every KV
        # head, so each real row's running max is real from its first block
        _fold_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, sm_scale, lambda: (
            row_head_ref[...] == col_head_ref[...]) & (col_pos_ref[...] < length - j * block))

    @pl.when(j == last)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _head_rows(q, hkv: int, block: int, flat: bool = False):
    """What both decode kernels make of q [B, 1, H, D] over blocks of ``block``
    positions of ``hkv`` KV heads: q as [B, rows, D], H padded to whole packed
    bf16 tiles of 16 rows; ``rows``; and the three index vectors of a block's
    mask — each row's KV head [rows, 1] (a pad row matches none), each
    column's KV head and its position in the block [1, block * hkv].

    ``flat``: the cache keeps a position's KV heads side by side in ONE line
    ``[B, L, hkv * D]``. q then goes ``[B, rows, hkv * D]``, each head's query
    in its own KV head's lanes and zero in the others', so that one contraction
    over the whole line is the head against its own KV head; a column is a
    position, and no head is masked."""
    b, _, hq, d = q.shape
    rows = -(-hq // FLASH_ROW_TILE) * FLASH_ROW_TILE
    q = q.reshape(b, hq, d)
    row_head = np.full((rows, 1), -1, np.int32)
    if flat:
        q = (q[:, :, None, :] * jnp.asarray(_own_kv_head(hq, hkv), q.dtype)[None, :, :, None]
             ).reshape(b, hq, hkv * d)
        row_head[:hq, 0] = 0
        col = np.arange(block, dtype=np.int32)[None]
        heads = (row_head, np.zeros_like(col), col)
    else:
        row_head[:hq, 0] = np.arange(hq) // (hq // hkv)
        col = np.arange(block * hkv, dtype=np.int32)[None]
        heads = (row_head, col % hkv, col // hkv)
    if rows != hq:  # a packed bf16 tile is 16 rows; pad rows match no KV head
        q = jnp.pad(q, ((0, 0), (0, rows - hq), (0, 0)))
    return q, rows, heads


def _own_kv_head(hq: int, hkv: int) -> np.ndarray:
    """[hq, hkv] 0/1: query head h reads KV head ``h // (hq / hkv)``."""
    return (np.arange(hq)[:, None] // (hq // hkv) == np.arange(hkv)[None, :]).astype(np.float32)


def _kv_lines(q, k_cache, v_cache):
    """How a decode kernel sees its caches: (flat, hkv, line width of the keys,
    of the values, lines a position). ``[B, L, Hkv, D]`` leaves are read as
    lines of one (position, KV head) pair (:func:`_as_lines`); ``[B, L, Hkv *
    D]`` leaves (``flat``) as they are, a line a position. The values' width is
    their own (``Dv``)."""
    d = q.shape[-1]
    if k_cache.ndim == 3:
        return True, k_cache.shape[2] // d, k_cache.shape[2], v_cache.shape[2], 1
    return False, k_cache.shape[2], d, v_cache.shape[3], k_cache.shape[2]


def _as_lines(cache):
    """A cache leaf as ``[B, lines, width]``: the same bytes, no copy."""
    return cache if cache.ndim == 3 else cache.reshape(
        cache.shape[0], cache.shape[1] * cache.shape[2], cache.shape[3])


def _pick_heads(out, hq: int, hkv: int, flat: bool):
    """A decode kernel's output ``[B, rows, width]`` as ``[B, 1, H, Dv]``: the
    real rows, and of a ``flat`` cache's line each head's own KV head's lanes
    (exact: a sum of one value and zeros)."""
    b = out.shape[0]
    out = out[:, :hq]
    if flat:
        out = jnp.einsum("bhgd,hg->bhd", out.reshape(b, hq, hkv, -1),
                         jnp.asarray(_own_kv_head(hq, hkv), out.dtype))
    return out.reshape(b, 1, hq, -1)


def _sink_rows(sinks, rows: int):
    """``sinks`` [H] as the kernels' operand ``[rows, 1]`` float32 (pad rows 0)."""
    return jnp.pad(sinks.astype(jnp.float32), (0, rows - sinks.shape[0]))[:, None]


def decode_attention(q, k_cache, v_cache, lengths, scale: float | None = None, *,
                     block: int = 0, interpret: bool = False, sinks=None):
    """One decode step's attention, each row over its own context only.

    q [B, 1, H, D]; k_cache [B, L, Hkv, D] and v_cache [B, L, Hkv, Dv] as the
    engine keeps them (no transpose, no copy: ``[B, L * Hkv, D]`` is the same
    bytes), or ``[B, L, Hkv * D]`` / ``[B, L, Hkv * Dv]``, a position's heads
    side by side in one line (:func:`_head_rows`, ``flat`` — what a leaf whose
    head is not whole lane tiles, 192 say, is kept as); ``lengths``
    [B] int, the positions each row holds (``cache_offset + 1``, clipped to
    1..L). Returns [B, 1, H, Dv] in q's dtype. Row i reads ``ceil(lengths[i] /
    block)`` blocks of ``block`` positions and folds them with an online
    softmax — operands as they are, f32 logits, statistics and accumulator —
    the last one masked by position; what lies past it is neither copied nor
    computed (the grid spans all ``L / block`` blocks, the index map holds at
    the row's last). ``block`` 0 takes :func:`ragged_block`'s. ``sinks`` [H]:
    a sink logit a query head, the softmax's starting state. Algebraically
    the softmax of :func:`attention_reference`, not bit-identical to it."""
    b, _, hq, d = q.shape
    cache_len = k_cache.shape[1]
    flat, hkv, wk, wv, lines = _kv_lines(q, k_cache, v_cache)
    block = block or ragged_block(cache_len, FLAT_KV_HEADS if flat else hkv)
    if not block or cache_len % block:
        raise ValueError(f"no block of {block} positions tiles a cache of {cache_len}")
    cols = block * lines
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, cache_len)
    q, rows, heads = _head_rows(q, hkv, block, flat)

    def kv_index(i, j, lens):
        return i, jnp.minimum(j, (lens[i] - 1) // block), 0

    per_row = lambda w: pl.BlockSpec((None, rows, w), lambda i, j, lens: (i, 0, 0))  # noqa: E731
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, j, lens: (0, 0))
    sink = [] if sinks is None else [_sink_rows(sinks, rows)]
    out = pl.pallas_call(
        functools.partial(_ragged_decode_kernel, block=block,
                          sm_scale=scale if scale is not None else 1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, cache_len // block),
            in_specs=[per_row(wk), pl.BlockSpec((None, cols, wk), kv_index),
                      pl.BlockSpec((None, cols, wv), kv_index),
                      whole(rows, 1), whole(1, cols), whole(1, cols),
                      *[whole(rows, 1) for _ in sink]],
            out_specs=per_row(wv),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, wv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows, wv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="ragged_decode_attention",
    )(lengths, q, _as_lines(k_cache), _as_lines(v_cache), *heads, *sink)
    return _pick_heads(out, hq, hkv, flat)


def ring_key_positions(offsets, length: int):
    """[B, length] int: the absolute position each index of a ring of
    ``length`` holds for a query at ``offsets`` [B], position p written at ``p
    mod length`` — index r holds the newest position <= the query's that is
    congruent to r, and one that would be negative holds nothing yet. Index r
    is ``(offset - r) mod length`` positions old: what
    :func:`_ring_decode_kernel` masks by, from the same two numbers."""
    return offsets[:, None] - (offsets[:, None] - jnp.arange(length)[None, :]) % length


def _ring_decode_kernel(off_ref, q_ref, k_ref, v_ref, row_head_ref, col_head_ref,
                        col_pos_ref, *rest, length: int, window: int, sm_scale: float):
    """One row of :func:`ring_decode_attention`: the whole ring is the row's
    one block, folded by :func:`_fold_block` into a fresh state. Ring index r
    is ``age = (offset - r) mod length`` positions old; it counts iff ``age <
    window`` (inside the window) and ``age <= offset`` (it has been written:
    ``key_positions >= 0``). The modulo is taken once, on the scalar.
    ``rest``: (sink_ref,) o_ref, m_ref, l_ref, acc_ref."""
    *sink_ref, o_ref, m_ref, l_ref, acc_ref = rest
    offset = off_ref[pl.program_id(0)]
    newest = jax.lax.rem(offset, length)  # the ring index of the query's own position
    _start_state(sink_ref, m_ref, l_ref, acc_ref)

    def visible():
        age = newest - col_pos_ref[...]
        age = jnp.where(age < 0, age + length, age)  # [1, columns]
        return (row_head_ref[...] == col_head_ref[...]) & (
            (age < window) & (age <= offset))

    _fold_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, sm_scale, visible)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def ring_decode_attention(q, k_ring, v_ring, offsets, window: int,
                          scale: float | None = None, *, interpret: bool = False,
                          sinks=None):
    """One decode step's sliding-window attention over ring caches, each ring
    read once where it lies.

    q [B, 1, H, D] at positions ``offsets`` [B]; k_ring [B, L, Hkv, D] and
    v_ring [B, L, Hkv, Dv] (or both ``flat``, :func:`decode_attention`),
    position p at index ``p mod L``, the query's own already written (viewed
    ``[B, L * Hkv, D]``: the same bytes, no transpose, no copy). A grid step a
    row, the row's whole ring its one block — a ring is full after L positions,
    there is nothing to skip, and 528 = 16 x 33 has no power-of-two block for
    :func:`ragged_block` to find — masked by each index's age
    (:func:`_ring_decode_kernel`): :func:`attention_reference` under ``window``
    and :func:`ring_key_positions`, algebraically, not bit for bit. ``sinks``
    [H]: a sink logit a query head. Returns [B, 1, H, Dv] in q's dtype."""
    b, _, hq, d = q.shape
    length = k_ring.shape[1]
    flat, hkv, wk, wv, lines = _kv_lines(q, k_ring, v_ring)
    q, rows, heads = _head_rows(q, hkv, length, flat)
    cols = length * lines
    per_row = lambda n, w: pl.BlockSpec((None, n, w), lambda i, offs: (i, 0, 0))  # noqa: E731
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, offs: (0, 0))  # noqa: E731
    sink = [] if sinks is None else [_sink_rows(sinks, rows)]
    out = pl.pallas_call(
        functools.partial(_ring_decode_kernel, length=length, window=window,
                          sm_scale=scale if scale is not None else 1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b,),
            in_specs=[per_row(rows, wk), per_row(cols, wk), per_row(cols, wv),
                      whole(rows, 1), whole(1, cols), whole(1, cols),
                      *[whole(rows, 1) for _ in sink]],
            out_specs=per_row(rows, wv),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, wv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, rows, wv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret, name="ring_decode_attention",
    )(offsets.astype(jnp.int32), q, _as_lines(k_ring), _as_lines(v_ring), *heads, *sink)
    return _pick_heads(out, hq, hkv, flat)


_ragged_calls = threading.local()


@contextlib.contextmanager
def ragged_calls():
    """Collects ``(block, cache length)`` of every call that
    :func:`cached_attention` hands to the ragged kernel while a step is
    traced inside: what the engine counts its KV reads from."""
    outer, calls = getattr(_ragged_calls, "calls", None), []
    _ragged_calls.calls = calls
    try:
        yield calls
    finally:
        _ragged_calls.calls = outer


def kv_positions(calls: list, lengths):
    """[2] int32 for one decode step over rows of ``lengths`` [B]: the
    positions the ``calls``' blocks cover (``ceil(length / block) * block`` a
    row and call) and the positions their caches hold."""
    read = sum(jnp.sum(jnp.minimum(-(-lengths // block) * block, cache_len))
               for block, cache_len in calls)
    return jnp.stack([read, lengths.shape[0] * sum(n for _, n in calls)]).astype(jnp.int32)


def decode_block(cache_shape: tuple, itemsize: int = 2, *, ring: bool = False,
                 impl: str = "auto", mesh: Mesh | None = None) -> int:
    """Positions a block of the Pallas kernel that takes a decode step — one
    query a row at an offset a row, no ``logit_softcap`` — over a cache ``[B,
    L, Hkv, D]``; 0 where the step is :func:`attention_reference`'s. From
    shapes, the backend and the mesh alone, so that the engine's layout can
    ask it of its leaves without tracing (a stored program is never traced).

    Plain causal attention over a dense cache (:func:`decode_attention`):
    :func:`ragged_block`'s block where it is ``RAGGED_MIN_BLOCK`` positions or
    more. A window over a ring, ``ring`` (:func:`ring_decode_attention`): the
    whole ring, where its keys and values fit ``RING_BLOCK_BYTES``. Both ask
    for heads of a multiple of 128, whole tiles of KV heads, the TPU backend
    and one device (a bare Mosaic call cannot be partitioned); ``impl``
    ``"ragged"`` (``"ragged+interpret"`` on the CPU) asks for the kernel by
    name wherever it can run at all. A leaf ``[B, L, W]`` keeps a position's
    heads side by side in one line (``flat``, :func:`decode_attention`): the
    same two kernels, lines of a multiple of 128 lanes in place of the heads'
    two conditions, blocks of :data:`FLAT_KV_HEADS`' positions."""
    if len(cache_shape) == 4:
        cache_len, hkv, d = cache_shape[1:]
        whole_tiles, line_bytes = d % 128 == 0 and hkv % 8 == 0, hkv * d * itemsize
    else:
        (cache_len, width), hkv = cache_shape[1:], FLAT_KV_HEADS
        whole_tiles, line_bytes = width % 128 == 0, width * itemsize
    if ring:
        block, fits = cache_len, 2 * cache_len * line_bytes <= RING_BLOCK_BYTES
    else:
        block = ragged_block(cache_len, hkv)
        fits = block >= RAGGED_MIN_BLOCK
    if impl.partition("+")[0] == "ragged":
        return block
    if (fits and whole_tiles and jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)):
        return block
    return 0


def cached_attention(q, k_cache, v_cache, q_offset, *, impl: str = "auto",
                     mesh: Mesh | None = None, scale: float | None = None,
                     logit_softcap: float = 0.0, window: int = 0, ring: bool = False,
                     sinks=None):
    """Causal attention of q [B, S, H, D] (positions ``q_offset`` onwards)
    against a KV cache [B, L, Hkv, D] that already holds their keys and
    values. ``ring``: the cache is a ring under ``window`` — position p at
    index ``p mod L``, one query a row. Returns [B, S, H, D]; the pick is
    recorded (:func:`note_choice`).

    Who takes a kernel is read off the inputs (:func:`decode_block`): one
    query a row, a per-row offset vector, no ``logit_softcap``, and either
    plain causal attention over a dense cache — the ragged kernel,
    :func:`decode_attention`, each row's blocks up to its own context — or a
    window over a ring, :func:`ring_decode_attention`, each ring once where it
    lies. Everything else — a window over a dense cache, an admission's
    prefill, a mesh, the CPU — is :func:`attention_reference` as before, a
    ring's ``key_positions`` from :func:`ring_key_positions`. ``impl``
    ``"ragged"`` (``"ragged+interpret"`` on the CPU) asks for the kernels by
    name wherever they can run at all; any other name leaves the choice here.
    The caches may be ``flat`` (``[B, L, Hkv * D]``, :func:`decode_attention`),
    the values of a width of their own, and ``sinks`` [H] a sink logit a query
    head: every form takes all three."""
    (b, qlen, hq, d), cache_len = q.shape, k_cache.shape[1]
    hkv = k_cache.shape[2] if k_cache.ndim == 4 else k_cache.shape[2] // d
    if ring and (qlen != 1 or not window):  # static: fails clearly at trace time
        raise ValueError(f"a ring cache decodes one token a step under a window "
                         f"(got {qlen} under {window})")
    block = 0
    # a window is the ring kernel's over a ring, nobody's over a dense cache
    if qlen == 1 and jnp.ndim(q_offset) == 1 and not logit_softcap and (ring or not window):
        block = decode_block(k_cache.shape, k_cache.dtype.itemsize, ring=ring, impl=impl,
                             mesh=mesh)
    kernel = "reference" if not block else "ring" if ring else "ragged"
    note_choice(kernel, qlen, cache_len, mesh, group=hq // hkv)
    interpret = impl.partition("+")[2] == "interpret"
    if kernel == "ring":
        return ring_decode_attention(q, k_cache, v_cache, q_offset, window, scale,
                                     interpret=interpret, sinks=sinks)
    if kernel == "ragged":
        calls = getattr(_ragged_calls, "calls", None)
        if calls is not None:
            calls.append((block, cache_len))
        return decode_attention(q, k_cache, v_cache, q_offset + 1, scale, block=block,
                                interpret=interpret, sinks=sinks)
    key_positions = None
    if ring:
        key_positions = ring_key_positions(
            jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,)), cache_len)
    t = lambda x: x.transpose(0, 2, 1, 3)
    if k_cache.ndim == 3:  # a position's heads side by side: the same numbers, by head
        k_cache = k_cache.reshape(b, cache_len, hkv, d)
        v_cache = v_cache.reshape(b, cache_len, hkv, -1)
    return t(attention_reference(
        t(q), t(k_cache), t(v_cache), causal=True, q_offset=q_offset, scale=scale,
        logit_softcap=logit_softcap, window=window, key_positions=key_positions, sinks=sinks))


# keys a step of :func:`blocked_attention` holds: 64 query heads of a piece of
# 2,048 positions against 512 keys are 268 MB of float32 logits
BLOCKED_KEYS = 512


def blocked_attention(q, k, v, q_offset, *, window: int = 0, sinks=None,
                      key_positions=None, scale: float | None = None,
                      block_k: int = BLOCKED_KEYS):
    """Causal attention of a BLOCK of queries q [B, S, H, D] (positions
    ``q_offset`` onwards; a scalar or [B]) against keys and values that hold
    their own already — k [B, K, Hkv, D], v [B, K, Hkv, Dv] or ``flat`` — a key
    block at a time under an online softmax, so that a prompt piece of 2,048
    positions over a cache of 32,768 never holds ``[H, S, K]`` logits (17 GB
    in float32): what :func:`attention_reference` computes, in ``jax.numpy``.
    Key index is position unless ``key_positions`` [B, K] says otherwise (a
    negative entry holds nothing); then every block is visited, else only
    those that hold a key some query sees (below the last query, inside the
    first one's ``window``). ``sinks`` [H]: the softmax's starting state.
    Returns [B, S, H, Dv]."""
    b, qlen, hq, d = q.shape
    n_keys = k.shape[1]
    hkv = k.shape[2] if k.ndim == 4 else k.shape[2] // d
    dv = v.shape[-1] if v.ndim == 4 else v.shape[2] // hkv
    group = hq // hkv
    bk = min(block_k, -(-n_keys // FLASH_ROW_TILE) * FLASH_ROW_TILE)
    padded = -(-n_keys // bk) * bk
    if padded != n_keys:
        pad = lambda x: jnp.pad(x, ((0, 0), (0, padded - n_keys)) + ((0, 0),) * (x.ndim - 2))
        k, v = pad(k), pad(v)
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)
    offset = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))
    qpos = (offset[:, None] + jnp.arange(qlen)[None, :])[:, None, None, :, None]  # [B,1,1,S,1]
    q5 = q.reshape(b, qlen, hkv, group, d).transpose(0, 2, 3, 1, 4)  # [B, Hkv, G, S, D]
    n_blocks, first = padded // bk, 0
    if key_positions is None:
        n_blocks = jnp.minimum(n_blocks, (jnp.max(offset) + qlen + bk - 1) // bk)
        if window > 0:
            first = jnp.maximum(0, (jnp.min(offset) - window + 1) // bk)
    elif padded != n_keys:
        key_positions = jnp.pad(key_positions, ((0, 0), (0, padded - n_keys)),
                                constant_values=-1)

    def fold(i, carry):
        acc, m_prev, l_prev = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, i * bk, bk, axis=1).reshape(b, bk, hkv, d)
        v_blk = jax.lax.dynamic_slice_in_dim(v, i * bk, bk, axis=1).reshape(b, bk, hkv, dv)
        s = jnp.einsum("bhgsd,bjhd->bhgsj", q5, k_blk,
                       preferred_element_type=jnp.float32) * sm_scale
        if key_positions is None:
            index = i * bk + jnp.arange(bk)
            kpos = jnp.where(index < n_keys, index, -1)[None, None, None, None, :]
        else:
            kpos = jax.lax.dynamic_slice_in_dim(key_positions, i * bk, bk, axis=1)[
                :, None, None, None, :]
        visible = (kpos <= qpos) & (kpos >= 0)
        if window > 0:
            visible = visible & (kpos > qpos - window)
        s = jnp.where(visible, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        # a query may see nothing of a block: exp(NEG_INF - NEG_INF) = 1 otherwise
        p = jnp.where(visible, jnp.exp(s - m_new[..., None]), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgsj,bjhd->bhgsd", p.astype(v.dtype), v_blk, preferred_element_type=jnp.float32)
        return acc, m_new, l_prev * alpha + jnp.sum(p, axis=-1)

    stat = (b, hkv, group, qlen)
    if sinks is None:
        m0, l0 = jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32)
    else:
        m0 = jnp.broadcast_to(sinks.astype(jnp.float32).reshape(1, hkv, group, 1), stat)
        l0 = jnp.ones(stat, jnp.float32)
    acc, _m, l = jax.lax.fori_loop(
        first, n_blocks, fold, (jnp.zeros(stat + (dv,), jnp.float32), m0, l0))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, qlen, hq, dv)


def ring_context_attention(q, k_ctx, v_ctx, k, v, start, q_offset, window: int, *,
                           sinks=None, scale: float | None = None):
    """A prompt piece over a window layer whose cache is a ring: q [B, S, H, D]
    at positions ``q_offset`` onwards, its own keys and values k / v [B, S, ...],
    and the slot's last R positions k_ctx / v_ctx [B, R, ...] IN POSITION ORDER
    from ``start`` on (``q_offset - R``: dl/kv_layout.LayerKindKV.view unrolls
    the ring; a negative position holds nothing). Attends ``[context ++ piece]``
    under the window, the causal mask and the sinks
    (:func:`blocked_attention`), and returns (out [B, S, H, Dv], the last R
    positions of the two together, in order from ``q_offset + S - R`` on):
    what the ring keeps of them. Leaves of either form, ``flat`` or by head."""
    b, r, s = q.shape[0], k_ctx.shape[1], k.shape[1]
    keys, values = jnp.concatenate([k_ctx, k], axis=1), jnp.concatenate([v_ctx, v], axis=1)
    first = lambda at, n: jnp.broadcast_to(  # noqa: E731
        jnp.asarray(at, jnp.int32), (b,))[:, None] + jnp.arange(n)[None, :]
    out = blocked_attention(
        q, keys, values, q_offset, window=window, sinks=sinks, scale=scale,
        key_positions=jnp.concatenate([first(start, r), first(q_offset, s)], axis=1))
    return out, (keys[:, s:], values[:, s:])


def note_choice(impl: str, sq: int, sk: int, mesh: Mesh | None = None,
                group: int = 1) -> None:
    """Record — at TRACE time, once per attention call site — which
    implementation a forward compiled with. The record is a zero-length span
    whose name carries the decision (``attention.flash[144x144]+pad[256x256]``),
    so ``/v1/trace`` (and ``MODELX_TRACE=1`` logs) show what ``impl="auto"``
    chose for each length without a new surface. ``group`` is query heads per
    KV head: the reference contracts them grouped (``+gqa4``) and the ragged
    and ring decode kernels mask each onto its own KV head; every other
    implementation repeats the KV heads."""
    name = f"attention.{impl}[{sq}x{sk}]"
    if impl in ("reference", "ragged", "ring") and group > 1:
        name += f"+gqa{group}"
    if impl == "flash":
        pq, pk = flash_blocks(sq)[1], flash_blocks(sk)[1]
        if (pq, pk) != (sq, sk):
            name += f"+pad[{pq}x{pk}]"
        if mesh is not None and mesh.size > 1:
            name += "+shard_map"
    with trace.span(name):
        pass


# -- ring attention (sequence parallelism) ------------------------------------


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal: bool = True,
                   block_k: int = 0):
    """Ring attention over a sequence-sharded mesh axis.

    q/k/v: [B, H, S, D] *globally*; S is sharded over ``axis``. Each device
    holds S/n local tokens, computes flash statistics against its resident
    k/v shard, then rotates k/v around the ring with ppermute (n-1 hops),
    merging online-softmax partials — numerically identical to full
    attention but with O(S/n) memory and neighbor-only ICI traffic.
    """
    n = mesh.shape[axis]
    bk = block_k or RING_BLOCK_K

    def local_fn(q_blk, k_blk, v_blk):
        idx = jax.lax.axis_index(axis)
        s_local = q_blk.shape[2]
        q_start = idx * s_local

        def step(i, carry):
            acc, m_prev, l_prev, k_cur, v_cur = carry
            src = jax.lax.rem(idx - i + n, n)  # whose kv block we hold now
            k_start = src * s_local

            def merge(args):
                acc, m_prev, l_prev = args
                return _merge_block(
                    q_blk, k_cur, v_cur, acc, m_prev, l_prev,
                    q_offset=q_start, k_offset=k_start, causal=causal,
                    block_k=bk,
                )

            if causal:
                # a hop whose whole k/v block sits after this device's last
                # query is fully masked: skip its matmuls entirely (on
                # average half the hops)
                needed = k_start <= q_start + s_local - 1
                acc, m_prev, l_prev = jax.lax.cond(
                    needed, merge, lambda args: args, (acc, m_prev, l_prev)
                )
            else:
                acc, m_prev, l_prev = merge((acc, m_prev, l_prev))
            perm = [(j, (j + 1) % n) for j in range(n)]
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return acc, m_prev, l_prev, k_nxt, v_nxt

        b, h, _s, d = q_blk.shape
        hq = q_blk.shape[1]
        acc0 = jnp.zeros((b, hq, s_local, d), jnp.float32)
        m0 = jnp.full((b, hq, s_local), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hq, s_local), jnp.float32)
        acc, m, l, _k, _v = jax.lax.fori_loop(
            0, n, step, (acc0, m0, l0, k_blk, v_blk), unroll=False
        )
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_blk.dtype)

    spec = P(None, None, axis, None)
    return shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp", causal: bool = True,
                      interpret: bool = False):
    """Ulysses/DeepSpeed-style sequence parallelism via all-to-all.

    q/k/v: [B, H, S, D] globally, S sharded over ``axis``. Two all-to-alls
    re-shard from sequence-parallel to *head*-parallel: each device then
    holds H/n heads with the FULL sequence, runs the local flash kernel
    (no ring steps, no online-softmax merging across devices), and a final
    all-to-all restores sequence sharding. Versus ring attention the comm
    volume is O(S·D·H/n) per device in two dense all-to-alls that ride ICI
    all at once instead of n-1 neighbor hops — better when n is small and
    heads divide evenly; ring wins on memory for very long S. Requires
    H % n == 0 (kv heads are repeated first when GQA heads don't divide).
    """
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(f"ulysses needs heads % {axis}={n} == 0, got {q.shape[1]}")
    hkv = k.shape[1]
    if hkv % n:
        # GQA heads don't divide the axis: repeat kv only up to lcm(Hkv, n)
        # — the minimal count that shards evenly; the local flash kernel
        # finishes any remaining per-device repeat without moving bytes
        rep = ((n * hkv) // math.gcd(n, hkv)) // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)

    def local_fn(q_blk, k_blk, v_blk):
        # [B, H, S/n, D] -> [B, H/n, S, D]: split heads, gather sequence
        to_heads = lambda x: jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)
        out = flash_attention(
            to_heads(q_blk), to_heads(k_blk), to_heads(v_blk), causal=causal,
            interpret=interpret,
        )
        # [B, H/n, S, D] -> [B, H, S/n, D]
        return jax.lax.all_to_all(out, axis, split_axis=2, concat_axis=1, tiled=True)

    spec = P(None, None, axis, None)
    return shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


RING_BLOCK_K = 512


def _merge_block(q, k, v, acc, m_prev, l_prev, q_offset, k_offset, causal,
                 block_k: int = RING_BLOCK_K):
    """Merge one k/v block into running flash statistics. All [B,H,S,D].

    The block is consumed in ``block_k``-key chunks with the online-softmax
    carried across chunks: peak activation memory is O(s_q x block_k), not
    O(s_q x s_k) — materializing the whole per-hop score matrix would put
    the O((S/n)^2) cost ring attention exists to avoid right back."""
    q32, k32, v32 = (x.astype(jnp.float32) for x in _repeat_kv_heads(q, k, v))
    scale = 1.0 / math.sqrt(q.shape[-1])
    q32 = q32 * scale
    s_k = k32.shape[2]
    bk = min(block_k, s_k)
    if s_k % bk:
        bk = s_k  # odd block sizes: one chunk (correctness over tiling)
    qpos = q_offset + jnp.arange(q.shape[2])[:, None]

    def chunk(i, carry):
        acc, m_prev, l_prev = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k32, i * bk, bk, axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(v32, i * bk, bk, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_blk, preferred_element_type=jnp.float32)
        if causal:
            kpos = k_offset + i * bk + jnp.arange(bk)[None, :]
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk, preferred_element_type=jnp.float32
        )
        return acc_new, m_new, l_new

    # The loop bound stays STATIC even though the diagonal hop wastes some
    # fully-masked chunks: a traced bound (offsets come off axis_index)
    # makes fori_loop non-reverse-differentiable, and ring attention must
    # train (sp meshes run this under value_and_grad). The outer per-hop
    # lax.cond skip already removes the fully-masked hops, which is where
    # the bulk of the wasted work was.
    return jax.lax.fori_loop(0, s_k // bk, chunk, (acc, m_prev, l_prev))
