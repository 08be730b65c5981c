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

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from modelx_tpu.utils import trace

NEG_INF = -1e30
FLASH_BLOCK = 128  # q and k block: one MXU tile edge
# rows per packed bf16 sublane tile: Mosaic refuses a block (and the k-loop's
# dynamic slice) whose row count is not a multiple of the tile — "cannot
# statically prove that index in dimension 1 is a multiple of 8" for a
# 5-token /v1/forward, which interpret mode never shows
FLASH_ROW_TILE = 16


# -- reference (jnp) ----------------------------------------------------------


def attention_reference(q, k, v, causal: bool = True, q_offset=0,
                        scale: float | None = None, logit_softcap: float = 0.0,
                        window: int = 0, key_positions=None):
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
    marks a key that holds nothing yet."""
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
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(pv, probs.astype(v.dtype), v).reshape(b, hq, qlen, d)


def _repeat_kv_heads(q, k, v):
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return q, k, v


# -- pallas flash kernel ------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  sm_scale: float, logit_softcap: float = 0.0, window: int = 0,
                  kv_len: int = 0):
    """One (batch*head, q-block) program: online softmax over k/v blocks.

    q_ref: [block_q, d], k_ref/v_ref: [seq_k, d], o_ref: [block_q, d].
    ``logit_softcap`` > 0 tanh-caps the scaled scores before masking and
    ``window`` > 0 limits each query to its last ``window`` keys (gemma2);
    both default off, preserving the plain flash semantics. ``kv_len`` > 0
    says only the first ``kv_len`` keys are real (the rest is block
    padding) and masks the tail.
    """
    block_q, d = q_ref.shape
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
    acc0 = jnp.zeros((block_q, d), jnp.float32)
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


def _flash_local(q, k, v, *, causal, block_q, block_k, interpret, scale,
                 logit_softcap, window):
    """The kernel on ONE device's share: q [B, H, Sq, D], k/v [B, Hkv, Sk, D].
    Ragged lengths are padded up to the block (padded keys masked in the
    kernel, padded query rows sliced off) — never handed to another
    implementation."""
    q, k, v = _repeat_kv_heads(q, k, v)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q, pq = flash_blocks(sq, block_q)
    block_k, pk = flash_blocks(sk, block_k)
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def rows(x, s, padded):
        x = x.reshape(b * h, s, d)
        return jnp.pad(x, ((0, 0), (0, padded - s), (0, 0))) if padded != s else x

    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                          sm_scale=sm_scale, logit_softcap=logit_softcap,
                          window=window, kv_len=sk if pk != sk else 0),
        grid=(b * h, pq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, pk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, pk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, pq, d), q.dtype),
        interpret=interpret,
    )(rows(q, sq, pq), rows(k, sk, pk), rows(v, sk, pk))
    return out[:, :sq].reshape(b, h, sq, d)


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
                    window: int = 0, mesh: Mesh | None = None):
    """Flash attention via pallas. q/k/v: [B, H, S, D] (GQA allowed).

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
        return local(q, k, v)
    batch = _axes_dividing(mesh, ("dp", "fsdp"), q.shape[0])
    heads = _axes_dividing(mesh, ("tp",), k.shape[1])
    if heads is None and _axes_dividing(mesh, ("tp",), q.shape[1]) is not None:
        # fewer kv heads than tp shards: repeat them first so both sides split
        q, k, v = _repeat_kv_heads(q, k, v)
        heads = "tp"
    spec = P(batch, heads, None, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def note_choice(impl: str, sq: int, sk: int, mesh: Mesh | None = None,
                group: int = 1) -> None:
    """Record — at TRACE time, once per attention call site — which
    implementation a forward compiled with. The record is a zero-length span
    whose name carries the decision (``attention.flash[144x144]+pad[256x256]``),
    so ``/v1/trace`` (and ``MODELX_TRACE=1`` logs) show what ``impl="auto"``
    chose for each length without a new surface. ``group`` is query heads per
    KV head: the reference contracts them grouped (``+gqa4``), every other
    implementation repeats the KV heads."""
    name = f"attention.{impl}[{sq}x{sk}]"
    if impl == "reference" and group > 1:
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
