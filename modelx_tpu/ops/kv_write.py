"""Writing new keys and values into a KV cache leaf: the one place the rule
lives (:func:`write_rows`), for every family that keeps ``[B, L, ...]`` leaves.

Two lowerings of one copy. A block of positions at one start for all rows (an
admission's prefill into its scratch cache, a uniform batch) is one
``dynamic_update_slice``. A start a row — the engine's decode step, each slot
at its own depth — is ``jax.vmap(dynamic_update_slice)``, which lowers to a
scatter; the TPU compiler runs a scatter as a serial ``while`` of one 2 KB
update a trip (2.7 us a trip on the v5e, 64 or 32 trips a leaf, ten or eight
leaves a step: 8 and 4 % of a 19 and a 17 ms step — PERF.md, PR 41) while
the memory sits idle. :func:`write_rows_kernel` is the same copy as one
Pallas call a leaf: the rows' starts as prefetched scalars, the cache left
where it lies and aliased to the output, each row's new line one asynchronous
copy from VMEM straight to its place — all started, then all waited (2.5 us a
leaf of 64 rows where the scatter took 171). Bit for bit the scatter's
result, ``dynamic_update_slice``'s clamp included.

A leaf that keeps a position's heads side by side in ONE line (``[B, L, W]``:
MiMo-V2-Flash's 192-wide keys are not whole lane tiles a head) has no such
run: a line is one row of a packed ``(16, 128)`` tile, and the chip's compiler
refuses a one-row copy into it. :func:`write_lines_kernel` is the same write
as a read-modify-write of the row's aligned group of 16 positions — the
group's block in, the one line replaced, the block out, a grid step a row, the
leaf aliased to the output — where the scatter's ``while`` made 32 trips a
leaf, fourteen leaves a step, and a traced run of it so many operations that
the profile call outlasted the run (PERF.md, PR 54).

Who takes the kernel is read off the inputs (:func:`lowering`), as
``ops.attention.cached_attention`` does: no flag, no option, no model's name.
The pick is recorded at trace time (``kv_write.kernel[64x4096]`` /
``kv_write.scatter[...]`` in ``/v1/trace``), and the engine counts the rows
either lowering wrote from the same rule (``dl/kv_layout``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from modelx_tpu.utils import trace

# a position's line [Hkv, D] is whole tiles of the leaf where the KV heads are
# a multiple of a tile's 8 sublanes and a head of its 128 lanes: one row's new
# line is then one contiguous run of the leaf as the chip lays it
LINE_SUBLANES, LINE_LANES = 8, 128
# positions of one packed bf16 tile: the group a flat leaf's line is rewritten in
LINE_GROUP = 16
# the narrowest line a position of a flat ``[B, L, W]`` leaf the kernel takes:
# MiMo-V2-Flash's are 512 to 1,536 lanes. MiniCPM-SALA's and Nemotron-H's 256
# keep the scatter until their cells have been measured with it (ROADMAP R4)
LINE_MIN_LANES = 512


def lowering(cache_shape: tuple, new_shape: tuple, index_ndim: int, mesh=None) -> str:
    """``"kernel"`` or ``"scatter"`` for a write of ``new`` into ``cache`` at
    ``index`` — from shapes, the backend and the mesh alone, so that the
    engine can ask the same question of its leaves without tracing a program
    (one loaded from the executable store is never traced). The kernel: one
    new position a row at a start a row, a leaf ``[B, L, Hkv, D]`` whose line
    is whole tiles, the TPU backend, one device (a bare Mosaic call cannot be
    partitioned); or a leaf ``[B, L, W]`` of whole groups of ``LINE_GROUP``
    positions whose line is whole lane tiles and ``LINE_MIN_LANES`` at least
    (:func:`write_lines_kernel`). Everything else — a scalar start, an
    admission's block of positions, Phi-3's heads of 96, a ``[B, L, 256]``
    leaf, a mesh, the CPU — is the scatter (or the single slice), lowered as
    ever."""
    if not (index_ndim == 1 and new_shape[1] == 1 and jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)):
        return "scatter"
    if (len(cache_shape) == 4 and cache_shape[2] % LINE_SUBLANES == 0
            and cache_shape[3] % LINE_LANES == 0):
        return "kernel"
    if (len(cache_shape) == 3 and cache_shape[1] % LINE_GROUP == 0
            and cache_shape[2] % LINE_LANES == 0 and cache_shape[2] >= LINE_MIN_LANES):
        return "kernel"
    return "scatter"


def write_rows(cache, new, index, mesh=None):
    """Write ``new`` ``[B, S, ...]`` into ``cache`` ``[B, L, ...]`` at
    ``index``: a scalar (every row at the same start) or ``[B]`` (one start a
    row). A start is ``dynamic_update_slice``'s: a negative one counts from
    the end, and it is clamped so that the block lies inside the leaf (idle
    slots carry offsets too). Returns the updated cache."""
    if jnp.ndim(index) == 0:
        return jax.lax.dynamic_update_slice(
            cache, new, (0, index) + (0,) * (cache.ndim - 2))
    how = lowering(cache.shape, new.shape, 1, mesh)
    with trace.span(f"kv_write.{how}[{cache.shape[0]}x{cache.shape[1]}]"):
        pass  # at TRACE time, once a call site: which lowering a program compiled with
    if how == "kernel":
        # off the TPU only a test that steers ``lowering`` comes here
        return write_rows_kernel(cache, new, index,
                                 interpret=jax.default_backend() != "tpu")
    zeros = (0,) * (cache.ndim - 2)
    return jax.vmap(lambda c, u, o: jax.lax.dynamic_update_slice(c, u, (o,) + zeros))(
        cache, new, index)


def _write_rows_kernel(index_ref, new_ref, cache_hbm, out_hbm, sem, *, length: int):
    """index_ref ``[B]`` in scalar memory; new_ref ``[B, 1, Hkv, D]`` whole in
    VMEM; out_hbm the cache where it lies (``cache_hbm`` is the same buffer:
    the call aliases them). One copy a row, all in flight at once: what bounds
    such a kernel is the count of descriptors, not their 2 KB."""
    del cache_hbm
    rows = new_ref.shape[0]

    def copy(b):
        # ``dynamic_update_slice``'s own rule: a negative start counts from the
        # end, then the start is clamped so that the line lies inside the leaf
        at = _start(index_ref[b], length)
        return pltpu.make_async_copy(new_ref.at[b], out_hbm.at[b, pl.ds(at, 1)], sem)

    def start(b, _):
        copy(b).start()

    def wait(b, _):
        # a wait takes the copy's size from its descriptor, not its place
        pltpu.make_async_copy(new_ref.at[0], out_hbm.at[0, pl.ds(0, 1)], sem).wait()

    jax.lax.fori_loop(0, rows, start, None)
    jax.lax.fori_loop(0, rows, wait, None)


def _start(at, length: int):
    """``dynamic_update_slice``'s own rule for one position: a negative start
    counts from the end, then it is clamped into the leaf."""
    return jnp.clip(jnp.where(at < 0, at + length, at), 0, length - 1)


def _write_lines_kernel(index_ref, new_ref, cache_ref, out_ref, *, length: int):
    """One row of :func:`write_lines_kernel`: cache_ref / out_ref ``[LINE_GROUP,
    W]``, the aligned group of positions that holds the row's start (the same
    block of the same buffer: the call aliases them); new_ref ``[1, W]``
    replaces the one line, the others go back as they came."""
    row = jax.lax.rem(_start(index_ref[pl.program_id(0)], length), LINE_GROUP)
    old = cache_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, old.shape, 0)
    out_ref[...] = jnp.where(rows == row, jnp.broadcast_to(new_ref[...], old.shape), old)


def write_lines_kernel(cache, new, index, *, interpret: bool = False):
    """:func:`write_rows` for one new position a row into a leaf that keeps a
    position in ONE line, ``cache`` ``[B, L, W]``, ``new`` ``[B, 1, W]``,
    ``index`` ``[B]``: a grid step a row reads the ``LINE_GROUP`` positions
    around the row's start (found by the prefetched starts), replaces the one
    line and writes the group back; ``cache`` is donated to the output, and
    nothing else of it moves. Bit for bit the scatter's result."""
    length, width = cache.shape[1:]
    group = lambda i, idx: (i, _start(idx[i], length) // LINE_GROUP, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_write_lines_kernel, length=length),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(cache.shape[0],),
            in_specs=[pl.BlockSpec((None, 1, width), lambda i, idx: (i, 0, 0)),
                      pl.BlockSpec((None, LINE_GROUP, width), group)],
            out_specs=pl.BlockSpec((None, LINE_GROUP, width), group)),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},  # operands: index, new, cache
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret, name="kv_write_rows",
    )(index.astype(jnp.int32), new, cache)


def write_rows_kernel(cache, new, index, *, interpret: bool = False):
    """:func:`write_rows` for one new position a row, ``cache`` ``[B, L, Hkv,
    D]``, ``new`` ``[B, 1, Hkv, D]``, ``index`` ``[B]``, as a Pallas kernel
    that writes in place: ``cache`` is donated to the call's output, nothing
    of it is read, and only the ``B`` lines move. A leaf ``[B, L, W]`` goes to
    :func:`write_lines_kernel`."""
    if cache.ndim == 3:
        return write_lines_kernel(cache, new, index, interpret=interpret)
    return pl.pallas_call(
        functools.partial(_write_rows_kernel, length=cache.shape[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(new.shape, lambda i, idx: (0, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},  # operands: index, new, cache
        interpret=interpret, name="kv_write_rows",
    )(index.astype(jnp.int32), new, cache)
