"""How the continuous engine's KV state lies on the device.

``dl/continuous.py`` schedules: slots, admissions, fills, dispatches. Where
a slot's keys and values live is decided here, behind three classes with one
interface, and the engine never asks which it got:

- ``DenseKV``: one ``[max_slots, max_len]`` cache per leaf; a slot's rows
  are its own, so a reservation always succeeds and a release does nothing.
- ``PagedKV``: a POOL of fixed-size pages (``[num_pages, page_size]`` per
  leaf) plus a host-managed block table ``[max_slots, max_len/page_size]``,
  so HBM scales with LIVE tokens instead of ``max_slots x max_len``. A row
  reserves the pages its span needs and returns them at retirement. Page 0
  is a TRASH page no slot owns: idle table entries point there, so idle
  rows' writes land harmlessly and their reads sit beyond the causal
  horizon (the dense engine's idle-row trick, relocated). The table is a
  traced input, never a shape: one program serves every page assignment.
- ``LayerKindKV``: a cache per layer KIND, for a family whose layers differ
  (``Family.layer_kind_decode_fns``): full-attention layers keep
  ``[max_slots, max_len]``, sliding-window layers a RING of the window plus
  one 16-token bucket a slot, written at ``position mod ring`` and masked by
  absolute position, sparse-attention layers an INDEX of compressed keys (one
  row per ``max_len / n`` positions) beside their keys and values, and
  linear-attention layers a STATE a slot with no position axis at all, and
  latent-attention layers one compressed LINE a position — so HBM
  holds what each kind can ever attend to, not ``max_len`` for all. What a
  leaf kind cannot give is refused when the engine is built, with a message
  that names the kind: a ring no view of an overwritten prefix (so no prefix
  cache), a state no cut at a token (so no prefix cache, no speculative
  verify, no pages). A prefill piece needs of a ring only what the ring still
  holds: the slot's last positions, handed over in position order.

Three kinds of method. Device state (``new_state``, ``abstract_state``).
Host bookkeeping (``fits``/``reserve``/``release``/``never_holds``/``reset``)
plus the builders of WHERE — the one argument a program takes to find a
slot's rows (``at``, ``at_many``, ``all_slots``): a slot index for
``DenseKV``, which so adds no argument to any program; the slot with its
table row for ``PagedKV``. And the traced primitives that unpack it
(``step``, ``put``, ``put_many``, ``view``, ``put_piece``): the only lines
in which the engine's programs differ by layout. A scratch cache — what an
admission prefills into, what the prefix cache stores — is a dense
``[k, bucket]`` tree for both.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np


class Refused(ValueError):
    """An engine option this family's layout cannot serve. Raised when the
    engine is built — at a pod's start-up, where it ends the load — never
    swallowed into a lazy retry, never silently wrong."""


def build(server, fwd, init_cache, stats: dict, *, max_slots: int,
          max_len: int, chunk_size: int, page_size: int, max_live_tokens: int,
          paged_attention: str, prefix_cache=None, prefill_chunk: int = 0,
          speculative_k: int = 0):
    """The layout the engine's arguments ask for. ``fwd`` / ``init_cache``
    are the family's cached forward and scratch-cache constructor; ``stats``
    is the engine's counter dict (the pool reports its occupancy there)."""
    if paged_attention not in ("gather", "in-place"):
        raise ValueError(f"unknown paged_attention mode {paged_attention!r}")
    if server.family.layer_kind_decode_fns is not None:
        return LayerKindKV(server, fwd, init_cache, stats, max_slots, max_len,
                           asked={"page_size": page_size, "prefix_cache": prefix_cache,
                                  "prefill_chunk": prefill_chunk,
                                  "speculative_k": speculative_k})
    if page_size <= 0:
        return DenseKV(fwd, init_cache, server.mesh, stats, max_slots, max_len)
    return PagedKV(server, fwd, init_cache, stats, max_slots, max_len,
                   chunk_size, page_size, max_live_tokens, paged_attention)


class DenseKV:
    """``[max_slots, max_len]`` per leaf. WHERE is the slot index (a vector
    of them for a batched admission, nothing for "every slot")."""

    # the dim of a leaf that dp may split: its slots
    _batch_dim = 0

    # rows ``ride`` adds below the slots' for the layout's own counters
    counter_rows = 0

    def __init__(self, fwd, init_cache, mesh, stats: dict, max_slots: int,
                 max_len: int) -> None:
        self.fwd, self.init_cache, self.mesh, self.stats = fwd, init_cache, mesh, stats
        self.max_slots, self.max_len = max_slots, max_len

    # -- device state ---------------------------------------------------------

    def _zeros(self):
        return self.init_cache(self.max_slots, self.max_len)

    def sharding(self, shape):
        """Where one leaf lives on the serving mesh (None on a single
        device — the dp=1 engine stays byte-identical to before). Dense
        caches shard slots over dp and kv heads over tp; the paged pool
        shards kv heads over tp only, because its leading dim is a global
        page index no axis may split."""
        if self.mesh.size <= 1:
            return None
        from modelx_tpu.dl.sharding import cache_sharding

        return cache_sharding(self.mesh, shape, batch_dim=self._batch_dim,
                              head_dim=len(shape) - 2)

    def new_state(self):
        """The KV state, zeroed, laid out on the serving mesh with an
        explicit GSPMD layout before the first program closes over it.
        Every program the engine compiles inherits these input layouts, so
        decode math runs tensor-parallel instead of congealing on device 0."""
        cache = self._zeros()
        if self.mesh.size <= 1:
            return cache
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, self.sharding(leaf.shape)), cache)

    def abstract_state(self):
        """The state as a dispatch meets it — committed to the mesh, as the
        admit program returned it, not as ``jnp.zeros`` left it — without
        allocating it (``chunk_warmer`` lowers against this)."""
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=self.sharding(x.shape) or replicated(self.mesh)),
            jax.eval_shape(self._zeros))

    def describe(self) -> tuple:
        """What of this layout shapes the engine's programs: key material
        for the node's executable store (dl/aot_cache.py)."""
        return (type(self).__name__, self.max_slots, self.max_len)

    # -- host bookkeeping -----------------------------------------------------

    def reset(self) -> None:
        """Every slot empty again (a supervised restart)."""

    def fits(self, tokens: int) -> bool:
        """Could a new row reserve ``tokens`` positions now?"""
        return True

    def reserve(self, slot: int, tokens: int) -> bool:
        """Grow ``slot``'s reservation to cover ``tokens`` positions; all
        or nothing. False = short: the caller waits for retirements."""
        return True

    def release(self, slot: int) -> None:
        """A retired row's reservation goes back."""

    def never_holds(self, tokens: int) -> str:
        """Why a row of ``tokens`` <= max_len positions could NEVER be
        reserved, for the error a submit raises ("" = it could)."""
        return ""

    def count_sweep(self, offsets: np.ndarray, n_steps: int) -> None:
        """Account one decode dispatch over rows at ``offsets``."""

    def block_kwargs(self, valid_len=None, last_idx=None) -> dict:
        """What the family's cached forward is told of a block of prompt
        positions beyond the cache and the offset — its rows' real lengths,
        given as such or as the last real index; None of both = the whole
        block: nothing, where every leaf is addressed by position (a padded
        bucket's tail is overwritten)."""
        return {}

    def step_kwargs(self, offsets, steps) -> dict:
        """What it is told of a decode step over all slots: nothing, where an
        idle slot's writes are harmless (``_chunk_impl``)."""
        return {}

    def _row_written(self) -> list:
        """The leaves (as shapes) a decode step writes one new position a row
        into, through ``ops.kv_write.write_rows``: here every one."""
        return jax.tree_util.tree_leaves(jax.eval_shape(self._zeros))

    @functools.cached_property
    def row_writes(self) -> tuple[int, int]:
        """(leaves whose per-row write is the kernel's, leaves written a row
        at a time) of ONE decode step over all slots. ``write_rows``' rule is
        static — shapes, the backend, the mesh — so the layout asks it of its
        own leaves: a chunk program loaded from the executable store is never
        traced, and a record made while tracing would miss it."""
        from modelx_tpu.ops import kv_write

        hows = [kv_write.lowering(leaf.shape, (leaf.shape[0], 1, *leaf.shape[2:]), 1, self.mesh)
                for leaf in self._row_written()]
        return hows.count("kernel"), len(hows)

    def landed(self, toks: np.ndarray) -> None:
        """A chunk's token block ``[.., steps + 1]`` has reached the host:
        what ``ride`` added to it is read here. Two rows below everything else
        are the steps' KV positions read and cached by the layers that took
        the ragged decode kernel (``attn_kv_positions_read`` / ``_cached``); a
        program none of whose layers did sends none, and the counters do not
        exist. Nothing rides for the cache writes: ``kv_write_rows_kernel`` /
        ``kv_write_rows`` grow by steps x slots x :attr:`row_writes`, and do
        not exist where no leaf's write takes the kernel."""
        stats = self.stats
        if toks.shape[0] > self.max_slots + self.counter_rows:
            grown = toks[-2:, :-1].astype(np.int64).sum(axis=1)
            for key, n in zip(("attn_kv_positions_read", "attn_kv_positions_cached"), grown):
                stats[key] = stats.get(key, 0) + int(n)
        if self.row_writes[0]:
            rows = (toks.shape[1] - 1) * self.max_slots
            for key, leaves in zip(("kv_write_rows_kernel", "kv_write_rows"), self.row_writes):
                stats[key] = stats.get(key, 0) + rows * leaves

    def all_slots(self) -> tuple:
        return ()

    def at(self, slot: int, filled: int = 0, piece_len: int = 0):
        """WHERE for one slot; ``piece_len`` > 0 names the rows a prefill
        piece landing at ``filled`` writes."""
        return jnp.int32(slot)

    def at_many(self, slots: np.ndarray):
        """WHERE for a batched admission (``max_slots`` = a pad row)."""
        return jnp.asarray(slots)

    # -- traced primitives ----------------------------------------------------

    @staticmethod
    def slot_of(where):
        return where

    def step(self, params, block, cache, offsets):
        """One cached forward over ALL slots, each row at its own offset."""
        return self.fwd(params, block, kv_cache=cache, cache_offset=offsets)

    def ride(self, cache, block, kv_read=None):
        """What goes home with a chunk's token block ``[max_slots, n + 1]`` —
        rows appended below the slots', so that device-side counters cost no
        host sync of their own. Of the state, nothing here; ``kv_read``
        ``[n, 2]`` (each step's KV positions read and cached where layers
        took the ragged decode kernel, else None) becomes two rows, a step a
        column."""
        if kv_read is None:
            return block
        return jnp.concatenate([block, jnp.pad(kv_read.T, ((0, 0), (0, 1)))], axis=0)

    def put(self, cache, small, where):
        """Write a scratch cache to the front of one slot."""
        return jax.tree_util.tree_map(
            lambda big, little: jax.lax.dynamic_update_slice(
                big, little, (where,) + (0,) * (big.ndim - 1)),
            cache, small)

    def put_many(self, cache, small, where):
        """Write row i of a scratch cache to the front of slot ``where[i]``
        (pad rows carry an out-of-bounds slot: ``mode="drop"`` discards)."""
        return jax.tree_util.tree_map(
            lambda big, lit: big.at[where, : lit.shape[1]].set(lit, mode="drop"),
            cache, small)

    def view(self, cache, where, length: int):
        """A dense ``[1, length]`` copy of the front of one slot."""
        return jax.tree_util.tree_map(
            lambda big: jax.lax.dynamic_slice(
                big, (where,) + (0,) * (big.ndim - 1),
                (1, length) + big.shape[2:]),
            cache)

    # a piece's forward ran on the slot's whole row: write the row back
    put_piece = put


class PagedKV(DenseKV):
    """``[num_pages, page_size]`` pools and a block table. WHERE is
    ``(slot, table row)`` — plus the touched table entries and the first
    one's token offset for a prefill piece — and the whole table for "every
    slot"."""

    _batch_dim = -1

    def __init__(self, server, fwd, init_cache, stats: dict, max_slots: int,
                 max_len: int, chunk_size: int, page_size: int,
                 max_live_tokens: int, paged_attention: str) -> None:
        super().__init__(fwd, init_cache, server.mesh, stats, max_slots, max_len)
        if max_len % page_size:
            raise ValueError(
                f"max_len {max_len} must be a multiple of page_size {page_size}")
        self.page_size = ps = int(page_size)
        budget = int(max_live_tokens) or max(
            max_len + chunk_size + ps, max_slots * max_len // 4)
        self.num_pages = 1 + -(-budget // ps)  # +1: trash
        # chunk attention: "gather" (default) rebuilds a dense view per
        # step — bit-identical logits to every other decode path, so the
        # engine's cross-engine token-exactness guarantee holds
        # unconditionally; "in-place" reads the page pools directly
        # (ops/paged_attention.py, per-step transient = one page block —
        # the long-context/HBM-bound deployment shape) at the cost of
        # blockwise-softmax numerics: greedy matches in practice, sampled
        # rows can flip at bf16 near-boundaries (measured on v5e). The
        # operator picks the trade (--kv-attention).
        self.fwd_paged = None
        if paged_attention == "in-place":
            family = server.family
            if family.paged_decode_fns is not None:
                self.fwd_paged = family.paged_decode_fns(server.cfg, mesh=server.mesh)
            else:
                # an operator asking for in-place did so for the HBM budget;
                # a silent fallback would surface only as an OOM later
                logging.getLogger("modelx.serve").warning(
                    "--kv-attention in-place: family %s has no paged decode; "
                    "falling back to the dense-gather chunk (higher per-step "
                    "transient HBM)", family.name)
        stats["page_size"] = ps
        stats["pages_total"] = self.num_pages - 1  # excl. trash
        stats["paged_attention"] = "gather" if self.fwd_paged is None else "in-place"
        self.reset()

    def describe(self) -> tuple:
        return (*super().describe(), self.page_size, self.num_pages,
                self.fwd_paged is not None)

    def _zeros(self):
        return jax.tree_util.tree_map(
            lambda leaf: jnp.zeros(
                (self.num_pages, self.page_size) + leaf.shape[2:], leaf.dtype),
            self.init_cache(1, self.page_size))

    def _row_written(self) -> list:
        return []  # a pool is written page by page (``write_token_kv``)

    # -- host bookkeeping -----------------------------------------------------

    def reset(self) -> None:
        self._free_pages = list(range(1, self.num_pages))
        self._table = np.zeros(
            (self.max_slots, self.max_len // self.page_size), np.int32)
        self._row_pages: dict[int, list[int]] = {}  # slot -> owned pages
        self.stats["pages_free"] = len(self._free_pages)

    def _pages(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    def fits(self, tokens: int) -> bool:
        return self._pages(tokens) <= len(self._free_pages)

    def reserve(self, slot: int, tokens: int) -> bool:
        need = self._pages(tokens)
        pages = self._row_pages.get(slot, [])
        if need - len(pages) > len(self._free_pages):
            return False
        self._row_pages[slot] = pages
        for j in range(len(pages), need):
            pg = self._free_pages.pop()
            pages.append(pg)
            self._table[slot, j] = pg
        self.stats["pages_free"] = len(self._free_pages)
        return True

    def release(self, slot: int) -> None:
        # table zeroing points the slot's entries back at the trash page;
        # a program possibly still in flight dispatched with a SNAPSHOT of
        # the table, so reuse stays data-ordered
        self._free_pages.extend(self._row_pages.pop(slot, ()))
        self._table[slot, :] = 0
        self.stats["pages_free"] = len(self._free_pages)

    def never_holds(self, tokens: int) -> str:
        if self._pages(tokens) <= self.num_pages - 1:
            return ""
        return ("needs more pages than the engine's pool holds "
                f"({self.num_pages - 1} x {self.page_size} tokens)")

    def count_sweep(self, offsets: np.ndarray, n_steps: int) -> None:
        # ragged paged sweep: the in-place kernel stops at the batch's
        # actual max page (ops/paged_attention), so the interesting number
        # is how much of the static table width a dispatch really walks
        pps = self._table.shape[1]
        blocks = min(pps, (int(offsets.max()) + n_steps) // self.page_size + 1)
        self.stats["pages_swept"] = self.stats.get("pages_swept", 0) + blocks
        self.stats["pages_swept_possible"] = (
            self.stats.get("pages_swept_possible", 0) + pps)

    # .copy(): jax zero-copy-aliases host numpy buffers and transfers
    # lazily, while the engine goes on reserving and releasing — every
    # dispatch gets a private snapshot of the table nobody mutates

    def all_slots(self) -> tuple:
        return (jnp.asarray(self._table.copy()),)

    def at(self, slot: int, filled: int = 0, piece_len: int = 0):
        where = (jnp.int32(slot), jnp.asarray(self._table[slot].copy()))
        if piece_len:
            # [filled, filled + piece_len) spans at most piece_len/ps + 1
            # pages (all reserved); the touched count is static per
            # (bucket, alignment) pair
            ps = self.page_size
            first, last = filled // ps, (filled + piece_len - 1) // ps
            where += (jnp.asarray(self._table[slot, first: last + 1].copy()),
                      jnp.int32(first * ps))
        return where

    def at_many(self, slots: np.ndarray):
        rows = np.zeros((len(slots), self._table.shape[1]), np.int32)
        real = slots < self.max_slots  # pad rows keep the trash page
        rows[real] = self._table[slots[real]]
        return jnp.asarray(slots), jnp.asarray(rows)

    # -- traced primitives ----------------------------------------------------

    @staticmethod
    def slot_of(where):
        return where[0]

    def step(self, params, block, pool, offsets, table):
        if self.fwd_paged is not None and block.shape[1] == 1:
            # the family forward scatters this step's k/v into the pools
            # and attends over them IN PLACE (single-token steps only)
            return self.fwd_paged(params, block, kv_cache=pool,
                                  cache_offset=offsets, table=table)
        # gather every slot's pages into a dense [max_slots, max_len] view
        # (a TRANSIENT the scheduler frees layer by layer — the persistent
        # state is only the pool), run the family forward against it
        # unchanged, then scatter the rows each slot wrote back into their
        # pages
        from modelx_tpu.ops.paged_attention import write_token_kv

        dense = jax.tree_util.tree_map(
            lambda p: p[table].reshape(self.max_slots, self.max_len, *p.shape[2:]),
            pool)
        logits, dense = self.fwd(params, block, kv_cache=dense, cache_offset=offsets)

        def put_back(p, d):
            for j in range(block.shape[1]):
                rows = jax.vmap(
                    lambda row, o: jax.lax.dynamic_slice_in_dim(row, o, 1, axis=0)
                )(d, offsets + j)  # [slots, 1, ...] — the row each slot wrote
                p = write_token_kv(p, rows, table, offsets + j)
            return p

        return logits, jax.tree_util.tree_map(put_back, pool, dense)

    def put(self, pool, small, where):
        # page by page: the scratch's length is static, so the write
        # unrolls to ceil(length/page_size) dynamic_update_slices. The
        # final block may be a partial page: the page's tail stays junk
        # past every query position until decode overwrites it
        ps, table_row = self.page_size, where[1]

        def write(out, little):
            for j in range(0, little.shape[1], ps):
                blk = jax.lax.slice_in_dim(
                    little, j, min(j + ps, little.shape[1]), axis=1)
                out = jax.lax.dynamic_update_slice(
                    out, blk, (table_row[j // ps],) + (0,) * (out.ndim - 1))
            return out

        return jax.tree_util.tree_map(write, pool, small)

    def put_many(self, pool, small, where):
        # same bucket means the same page count, so every page column
        # scatters all rows at once
        ps, rows = self.page_size, where[1]

        def write(out, little):
            for j in range(0, little.shape[1], ps):
                w = min(j + ps, little.shape[1]) - j
                blk = jax.lax.slice_in_dim(little, j, j + w, axis=1)
                out = out.at[rows[:, j // ps], :w].set(blk)
            return out

        return jax.tree_util.tree_map(write, pool, small)

    def view(self, pool, where, length: int):
        # gather only the span's pages (``length`` is static, so the page
        # count is too; unreserved entries point at trash)
        n_pg = self._pages(length)
        return jax.tree_util.tree_map(
            lambda p: p[where[1][:n_pg]].reshape(
                1, n_pg * self.page_size, *p.shape[2:])[:, :length],
            pool)

    def put_piece(self, pool, dense, where):
        # write back ONLY the pages the piece touched: scattering the
        # slot's whole max_len span per piece would pay ~max_len/piece x
        # the useful copy traffic on exactly the long-context shapes
        # chunked prefill targets
        ps, (_slot, _row, touched, start) = self.page_size, where

        def write(out, d):
            for j in range(touched.shape[0]):
                blk = jax.lax.dynamic_slice_in_dim(d, start + j * ps, ps, axis=1)
                out = jax.lax.dynamic_update_slice(
                    out, blk, (touched[j],) + (0,) * (out.ndim - 1))
            return out

        return jax.tree_util.tree_map(write, pool, dense)


class LayerKindKV(DenseKV):
    """A cache per layer kind. The family's ``layer_kind_decode_fns`` gives
    the forward over it, its constructor and each leaf's kind: ``"full"``
    leaves are ``[max_slots, max_len]`` as in ``DenseKV``; ``"window"``
    leaves are rings ``[max_slots, ring]``, position p at index ``p mod
    ring``; an ``"index"`` leaf ``[max_slots, max_len / n]`` holds one row per
    n positions (a sparse layer's compressed keys); a ``"state"`` leaf
    ``[max_slots, ...]`` has no position axis (a linear-attention layer's
    running sum, or a state-space layer's two — its recurrence's state and its
    convolution's tail: ``put`` copies a whole state, ``view`` / ``put_piece``
    hand the slot's state to a prefill piece and take it back); a ``"latent"`` leaf
    ``[max_slots, max_len, W]`` holds one compressed line a position in place
    of per-head keys and values (latent attention: laid and addressed as a
    full leaf — it carries ``--prefill-chunk`` — but written a row at a time
    by the family itself, and a kind of its own so that what no test holds
    over it yet is refused by name); a ``"counter"``
    leaf is a small vector the decode step adds to, which goes home with each
    chunk's tokens (``ride`` / ``landed``).

    A scratch cache (an admission's prefill) has the family's own leaves at
    the prompt's bucket; what of it survives on a window layer is its last
    ``ring`` positions, rolled to their ring indices. The ring is one
    16-bucket longer than the window, so the bucket's padding past the real
    prompt displaces only positions no later query can see, and the mask by
    absolute position hides the padding itself until decode overwrites it
    (models/laguna.ring_len). A long prompt lands in pieces: a piece sees a
    ring as its slot's last ``ring`` positions in position order (``view``),
    the family's forward attends those and the piece together and hands back
    the last ``ring`` of them, which ``put_piece`` rolls to their indices — the
    last piece's padded tail displaces what the bucket's slack covers, as an
    admission's does. A state has no such slack — what enters it
    stays — so a layout with states tells the family's forward how many of a
    block's positions are real (``block_kwargs``) and which rows of a decode
    step are live (``step_kwargs``): an idle or filling slot's state is left
    bit for bit.

    Every slot's rows are its own, so a reservation always succeeds; what is
    counted is what the live reservations hold, by kind."""

    # engine option -> (the leaf kinds that cannot carry it, what is refused, why)
    REFUSED = {
        "page_size": (("window", "state", "latent"), "--kv-page-size",
                      {"window": "a ring is not paged", "state": "a state has no pages",
                       "latent": "the page pool's in-place decode reads per-head keys and "
                                 "values, and a latent line has neither"}),
        "prefix_cache": (("window", "state", "latent"), "--prefix-cache, and with it the KV "
                         "store's bundles and resume from stored KV",
                         {"window": "a ring cannot give back a prefix it has overwritten",
                          "state": "a state cannot be cut at a token",
                          "latent": "a suffix landing at an unbucketed offset over stored "
                                    "latent lines is held by no test yet"}),
        "speculative_k": (("window", "state", "latent"), "--speculative-k",
                          {"window": "a verify block writes several ring positions a step",
                           "state": "a state cannot drop the tokens a verify rejects",
                           "latent": "a verify block is neither the one-token absorbed form "
                                     "nor a prompt block at one offset, and no test holds it"}),
    }

    @classmethod
    def refuse(cls, family: str, kinds=("full", "window"), **asked) -> None:
        """Raise ``Refused`` naming every engine option in ``asked`` that is
        set and that a layout with leaves of ``kinds`` does not carry, each
        with the leaf kind that is the reason."""
        bad = []
        for option, value in asked.items():
            if option not in cls.REFUSED:
                continue  # every leaf kind carries it (``prefill_chunk``)
            cannot, what, why = cls.REFUSED[option]
            reasons = [f"{why[k]} ({k!r} leaves)" for k in cannot if k in kinds]
            if value and reasons:
                bad.append(f"{what} ({'; '.join(reasons)})")
        if bad:
            raise Refused(
                f"family {family} keeps a cache per layer kind "
                f"({', '.join(sorted(set(kinds) - {'counter'}))} leaves), which does not "
                f"carry: {'; '.join(bad)}")

    def __init__(self, server, fwd, init_cache, stats: dict, max_slots: int,
                 max_len: int, asked: dict | None = None) -> None:
        super().__init__(fwd, init_cache, server.mesh, stats, max_slots, max_len)
        fns = server.family.layer_kind_decode_fns(server.cfg, mesh=server.mesh)
        self.fwd_kinds, self.init_state, self.kinds = fns["fwd"], fns["init_state"], fns["kinds"]
        have = set(self.kinds.values())
        self.refuse(server.family.name, tuple(have), **(asked or {}))
        self.has_state = "state" in have
        # counter leaf -> (the stats block it feeds, its entries' names)
        self.counters: dict[str, tuple[str, tuple]] = fns.get("counters", {})
        self.counter_rows = sum(len(names) for _, names in self.counters.values())
        # the name the family's forward asks the decode kernels by (a test's)
        self.attention_impl: str = fns.get("attention_impl", "auto")
        shapes = jax.eval_shape(self._zeros)
        rings = {shapes[n].shape[1] for n, kind in self.kinds.items() if kind == "window"}
        self.ring = rings.pop() if rings else max_len
        self.has_rings = "window" in have

        def bytes_of(kind: str) -> int:
            return sum(int(np.prod(shapes[n].shape)) * shapes[n].dtype.itemsize
                       for n, k in self.kinds.items() if k == kind)

        stats["kv"] = {"bytes_full": bytes_of("full"), "bytes_window": bytes_of("window"),
                       "window_positions": self.ring, "positions_full": 0,
                       "positions_window": 0}
        if have & {"index", "state"}:
            stats["kv"].update(bytes_index=bytes_of("index"), bytes_state=bytes_of("state"),
                               states_live=0)
        if "latent" in have:
            stats["kv"]["bytes_latent"] = bytes_of("latent")
        for block, gauges in fns.get("gauges", {}).items():
            stats[block] = dict(gauges)
        self._last: dict[str, np.ndarray] = {}
        self.reset()

    def describe(self) -> tuple:
        return (*super().describe(), self.ring, tuple(sorted(self.kinds.items())))

    def _zeros(self):
        return self.init_state(self.max_slots, self.max_len)

    def _row_written(self) -> list:
        shapes = jax.eval_shape(self._zeros)
        return [shapes[name] for name, kind in self.kinds.items() if kind in ("full", "window")]

    def sharding(self, shape):
        if len(shape) < 3:  # a counter leaf: every device holds it whole
            return None if self.mesh.size <= 1 else replicated(self.mesh)
        return super().sharding(shape)

    # -- host bookkeeping -----------------------------------------------------

    def reset(self) -> None:
        self._held: dict[int, int] = {}  # slot -> positions reserved
        self._count()

    def _count(self) -> None:
        kv = self.stats["kv"]
        kv["positions_full"] = sum(self._held.values())
        kv["positions_window"] = sum(min(t, self.ring) for t in self._held.values())
        if "states_live" in kv:
            kv["states_live"] = len(self._held)

    def fits(self, tokens: int) -> bool:
        return tokens <= self.max_len

    def reserve(self, slot: int, tokens: int) -> bool:
        if tokens > self.max_len:
            return False
        self._held[slot] = max(tokens, self._held.get(slot, 0))
        self._count()
        return True

    def release(self, slot: int) -> None:
        self._held.pop(slot, None)
        self._count()

    @functools.cached_property
    def ring_reads(self) -> tuple[int, int]:
        """(window layers whose decode step reads its ring in the ring decode
        kernel, window layers) — asked of the ring leaves as :attr:`row_writes`
        is of the written ones: ``ops.attention.decode_block``'s rule is
        static, and the engine's step is one token a row at an offset a row."""
        from modelx_tpu.ops import attention

        shapes = jax.eval_shape(self._zeros)
        rings = [shapes[name] for name, kind in self.kinds.items() if kind == "window"]
        kernel = [leaf for leaf in rings if attention.decode_block(
            leaf.shape, leaf.dtype.itemsize, ring=True, impl=self.attention_impl,
            mesh=self.mesh)]
        return len(kernel) // 2, len(rings) // 2  # a layer's ring is two leaves

    def landed(self, toks: np.ndarray) -> None:
        """Beside ``DenseKV``'s: ``attn_ring_kernel_calls`` / ``attn_ring_calls``
        grow by steps x :attr:`ring_reads` (nothing rides for them either; they
        do not exist where no ring takes the kernel), then the counter leaves."""
        super().landed(toks)
        if self.ring_reads[0]:
            for key, layers in zip(("attn_ring_kernel_calls", "attn_ring_calls"),
                                   self.ring_reads):
                self.stats[key] = self.stats.get(key, 0) + (toks.shape[1] - 1) * layers
        # the counters wrap at 32 bits on the device: what is added here is
        # each one's growth since the block before, taken modulo 2**32
        row = self.max_slots
        for leaf, (block, names) in self.counters.items():
            now = toks[row: row + len(names), 0].astype(np.uint32)
            grown = now - self._last.get(leaf, np.zeros(len(names), np.uint32))
            self._last[leaf] = now
            row += len(names)
            into = self.stats.setdefault(block, {})
            for key, value in zip(names, grown):
                into[key] = into.get(key, 0) + int(value)

    # -- traced primitives ----------------------------------------------------

    def block_kwargs(self, valid_len=None, last_idx=None) -> dict:
        if not self.has_state:
            return {}
        return {"valid_len": valid_len if last_idx is None else last_idx + 1}

    def step_kwargs(self, offsets, steps) -> dict:
        # from what the program carries: an idle slot sits at offset 0, a
        # filling one at its frontier with no step taken yet
        return {"live": (offsets > 0) & (steps > 0)} if self.has_state else {}

    def step(self, params, block, cache, offsets, **told):
        return self.fwd_kinds(params, block, kv_cache=cache, cache_offset=offsets, **told)

    def ride(self, cache, block, kv_read=None):
        rows = [jnp.broadcast_to(cache[leaf][:, None], (len(names), block.shape[1]))
                for leaf, (_, names) in self.counters.items()]
        return super().ride(cache, jnp.concatenate([block, *rows], axis=0), kv_read)

    def at(self, slot: int, filled: int = 0, piece_len: int = 0):
        """WHERE for one slot; for a prefill piece over rings also where the
        piece starts and where it ends: ``view`` unrolls a ring from the one,
        ``put_piece`` rolls the piece's last positions back in from the other."""
        if not (piece_len and self.has_rings):
            return jnp.int32(slot)
        self.stats["kv_ring_pieces"] = self.stats.get("kv_ring_pieces", 0) + 1
        return jnp.int32(slot), jnp.int32(filled), jnp.int32(filled + piece_len)

    @staticmethod
    def slot_of(where):
        return where[0] if isinstance(where, tuple) else where

    def _ring_rows(self, little):
        """The part of a dense scratch leaf ``[k, S, ...]`` a ring keeps, laid
        out by ring index: all of it when it fits, else its last ``ring``
        positions, position p rolled to ``p mod ring``."""
        s, r = little.shape[1], self.ring
        if s <= r:
            return little
        return jnp.roll(little[:, s - r:], (s - r) % r, axis=1)

    def _merge(self, cache, small, write):
        out = dict(cache)
        for name, little in small.items():
            if self.kinds.get(name) == "window":
                little = self._ring_rows(little)
            out[name] = write(cache[name], little)
        return out

    def put(self, cache, small, where):
        return self._merge(cache, small, lambda big, little: jax.lax.dynamic_update_slice(
            big, little, (where,) + (0,) * (big.ndim - 1)))

    def put_many(self, cache, small, where):
        return self._merge(cache, small, lambda big, lit: big.at[
            where, : lit.shape[1]].set(lit, mode="drop"))

    def view(self, cache, where, length: int):
        """The slot's own leaves as a ``[1, ...]`` cache for a prefill piece:
        the front ``length`` positions of a full leaf, as many rows of an
        index as cover them, a state whole; the counters stay behind. A ring
        is handed over UNROLLED — the slot's last ``ring`` positions in
        position order, index j holding position ``ring_start + j`` with
        ``ring_start = filled - ring`` a leaf of its own beside them (a
        negative position holds nothing): what of the prompt a ring still has
        is all a window layer's piece can see (``where`` from :meth:`at`)."""
        if self.has_rings and not isinstance(where, tuple):
            raise Refused("a cache per layer kind with rings gives no dense view of a slot "
                          "(prefix cache)")
        slot = self.slot_of(where)

        def front(big, kind):
            if kind in ("state", "window"):
                size = (1,) + big.shape[1:]
            else:
                size = (1, big.shape[1] * length // self.max_len) + big.shape[2:]
            mine = jax.lax.dynamic_slice(big, (slot,) + (0,) * (big.ndim - 1), size)
            # index j of the view is ring index (filled + j) mod ring
            return jnp.roll(mine, -(where[1] % self.ring), axis=1) if kind == "window" else mine

        row = {name: front(cache[name], kind) for name, kind in self.kinds.items()
               if kind != "counter"}
        if self.has_rings:
            row["ring_start"] = where[1] - self.ring
        return row

    def put_piece(self, cache, row, where):
        """Write a piece's row back. The family's forward hands a ring's leaf
        back as the last ``ring`` positions up to the piece's end, in position
        order: index j goes to ring index ``(end + j) mod ring``."""
        if not self.has_rings:
            return self.put(cache, row, where)
        slot, _, end = where
        out = dict(cache)
        for name, little in row.items():
            if name not in self.kinds:
                continue  # ``ring_start``: the view's, not the state's
            if self.kinds[name] == "window":
                little = jnp.roll(little, end % self.ring, axis=1)
            out[name] = jax.lax.dynamic_update_slice(
                cache[name], little, (slot,) + (0,) * (little.ndim - 1))
        return out


def replicated(mesh):
    """The sharding of a small array every device holds whole."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())
