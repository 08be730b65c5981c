#!/usr/bin/env python
"""Time the pieces of the learned selection (``ops/index_select.py``) on the
chip, alone, at the ``deepseek-v3.2-exp-ep16-d5.sparsedoc`` cell's shapes: 16
rows over a 32 k cache, 64 index heads of 128, 2,048 of the positions kept,
640-lane lines, contexts of 17-26 k. One JSON line a piece; ``ms`` is one call's
time: the host's clock around ``--calls`` programs that end in
``block_until_ready``, each ``INNER`` calls of the piece one after another in
a loop on the device (a program a call reads 0.2 ms for ANY piece: the host's
dispatch, PR 51), every call's small integer operand hung on the call before
it so that the compiler keeps them apart and in order. Nothing here is an
end-to-end number. Refuses to run without a TPU.

The decode step's pieces come twice: as every backend but one TPU device runs
them (``step_scores``, ``select_top_k``: a sort) and as the kernels do
(``step_scores_ragged``, ``select_threshold`` + ``select_compact`` =
``select_kernel``); ``score_select_gather_attend`` is the path that ships,
``..._reference`` the sort's. ``gather_copies_alone`` is what is left of an
EXPERIMENT (PERF.md, PR 51) — the absorbed attention fed by single-line copies
from the leaf where it lies, which Mosaic refuses to compile: its copies with
no attention behind them, against ``gather_lines`` + ``absorbed_over_selected``.

    chiprun -- python3 scripts/bench_index_select.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# calls of a piece one program loops over on the device (a tenth for a prompt block's pieces)
INNER = 40


def gather_copies_alone(cache, idx, *, lines_a_step: int, group: int, interpret: bool = False):
    """What is left of experiment (c), the gather fused into the attention
    (PERF.md, PR 51): the COPIES ALONE of the lines ``idx`` ``[B, k]`` names of
    ``cache`` ``[B, L, W]``, from the leaf where it lies into a double-buffered
    block of ``lines_a_step`` — the next step's copies start before this step's
    wait — with no attention behind them. A copy is the ``group`` lines that
    hold the chosen one and start at a multiple of ``group``: Mosaic refuses a
    slice of ONE line of a leaf tiled by eight positions, which is why the
    fused kernel never compiled. Returns each row's last block ``[B, n, W]``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, width = cache.shape
    n, per_row = lines_a_step, idx.shape[1] // lines_a_step

    def kernel(idx_ref, cache_hbm, o_ref, buf, sem):
        step, steps = pl.program_id(0), pl.num_programs(0)
        slot = step % 2

        def start(of, into):
            row, first = of // per_row, of * n

            def eight(i, _):  # the loop's own steps are most of a copy's issue: eight a turn
                for j in (i * 8 + u for u in range(8)):
                    at = idx_ref[first + j]
                    src = cache_hbm.at[row, pl.ds(pl.multiple_of(at // group * group, group),
                                                  group)]
                    dst = buf.at[into, pl.ds(pl.multiple_of(j * group, group), group)]
                    pltpu.make_async_copy(src, dst, sem.at[into]).start()

            jax.lax.fori_loop(0, n // 8, eight, None)

        @pl.when(step == 0)
        def _():
            start(0, 0)

        @pl.when(step + 1 < steps)
        def _():
            start(step + 1, 1 - slot)

        # one wait for the block's bytes: the semaphore counts what the n copies brought
        pltpu.make_async_copy(cache_hbm.at[step // per_row, pl.ds(0, n * group)], buf.at[slot],
                              sem.at[slot]).wait()

        @pl.when(step % per_row == per_row - 1)
        def _():
            o_ref[...] = buf[slot, :n]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * per_row,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, n, width), lambda s, idx: (s // per_row, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, n * group, width), cache.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, n, width), cache.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret, name="gather_copies_alone",
    )(idx.reshape(-1).astype(jnp.int32), cache)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=32768)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--piece", type=int, default=2048)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--allow-cpu", action="store_true", help="a rehearsal: times mean nothing")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from modelx_tpu.ops import index_select as sel
    from modelx_tpu.ops import latent_attention as latent

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(json.dumps({"error": f"no TPU: {dev.platform}"}))
        return 2
    b, length, k, s = args.rows, args.cache_len, args.topk, args.piece
    heads, d, width, rank, attn_heads = 64, 128, 640, 512, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16
    q = jax.random.normal(keys[0], (b, heads, d), bf)
    w = jax.random.normal(keys[1], (b, heads), jnp.float32)
    index = jax.random.normal(keys[2], (b, length, d), bf)
    lines = jax.random.normal(keys[3], (b, length, width), bf)
    q_cat = jax.random.normal(keys[4], (b, attn_heads, width), bf)
    lengths = jnp.asarray(np.random.default_rng(0).integers(17000, 26000, b), jnp.int32)
    lengths = jnp.minimum(lengths, length)
    qs = jax.random.normal(keys[5], (1, s, heads, d), bf)
    ws = jax.random.normal(keys[6], (1, s, heads), jnp.float32)

    inner = INNER

    def timed(name, fn, *inputs, **extra):
        """``fn(*inputs)`` timed; the LAST operand (small, or whole numbers that
        adding a zero leaves as they are) is what each call of the loop takes
        from the call before it — an operand no call changes is hoisted out."""
        out = jax.block_until_ready(jax.jit(fn)(*inputs))

        def looped(*ins):
            def again(_, carry):
                held, _ = carry
                result = fn(*ins[:-1], held)
                first = jax.tree.leaves(result)[0]
                # zero, but the compiler cannot know: the next call waits for this one; the
                # whole result is carried out of the loop, so none of it is dead
                zero = jnp.minimum(jnp.abs(first.reshape(-1)[0]).astype(jnp.float32), 0.0)
                return held + zero.astype(held.dtype), result
            return jax.lax.fori_loop(0, inner, again,
                                     (ins[-1], jax.tree.map(jnp.zeros_like, out)))[1]

        many = jax.jit(looped)
        t0 = time.perf_counter()
        jax.block_until_ready(many(*inputs))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.calls):
            last = many(*inputs)
        jax.block_until_ready(last)
        ms = (time.perf_counter() - t0) / (args.calls * inner) * 1e3
        print(json.dumps({"piece": name, "ms": round(ms, 4), "compile_s": round(compile_s, 2),
                          "device": dev.device_kind, **extra}), flush=True)
        return out

    block, interpret = sel.takes_kernel(lines.shape, rank, k)
    if args.allow_cpu and not block:
        block, interpret = latent.absorbed_block(length), True
    scale = 0.1352

    scores = timed("step_scores", lambda qi, ki, wi: sel.step_scores(qi, wi, ki), q, index, w)
    idx = timed("select_top_k", lambda x, n: sel.select_reference(x, n, k), scores, lengths)
    timed("kth_largest_rows", lambda x, n: sel.kth_largest(
        jnp.where(jnp.arange(length)[None] < n[:, None], x, sel.NEG_INF), k), scores, lengths)
    chunks = timed("step_scores_ragged",
                   lambda qi, wi, ki, n: sel.step_scores(qi, wi, ki, n, block=block,
                                                         interpret=interpret),
                   q, w, index, lengths, block=block)
    mask = timed("select_threshold", lambda x, n: sel.chosen_mask(x, n, k, interpret=interpret),
                 chunks, lengths)
    timed("select_threshold_cumsum",  # the prompt block's form of one query a row
          lambda x, n: sel.selection_mask(x.reshape(b, 1, length), n[:, None] - 1, k),
          chunks, lengths)
    timed("select_compact", lambda m: sel.compact(m, k), mask)
    up = timed("select_kernel", lambda x, n: sel.select_ascending(x, n, k), chunks, lengths)
    same = [sorted(a[:n]) == sorted(b_[:n]) for a, b_, n in zip(
        np.asarray(up).tolist(),
        np.asarray(sel.select_reference(chunks.reshape(b, length), lengths, k)).tolist(),
        np.minimum(np.asarray(lengths), k).tolist())]
    print(json.dumps({"piece": "select_kernel_is_the_sort", "rows": int(sum(same)), "of": b}),
          flush=True)
    got = timed("gather_lines", sel.gather_lines, lines, idx)
    timed("gather_lines_ascending", sel.gather_lines, lines, up)
    kept = jnp.minimum(lengths, k)
    timed("absorbed_over_selected",
          lambda qc, g, n: latent.absorbed(qc, g, n - 1, scale, rank), q_cat, got, kept)
    timed("absorbed_dense", lambda qc, g, n: latent.absorbed(qc, g, n - 1, scale, rank),
          q_cat, lines, lengths)
    for group, lines_a_step in ((8, min(256, k)), (16, min(128, k))):
        timed("gather_copies_alone",
              lambda c, i: gather_copies_alone(c, i, lines_a_step=lines_a_step, group=group,
                                               interpret=interpret),
              lines, idx, copies=b * k, lines_a_copy=group,
              mb=round(b * k * group * width * 2 / 1e6, 1))

    def whole(kernel: bool):
        def run(qi, wi, keys_, lines_, qc, n):
            found = sel.step_scores(qi, wi, keys_, n, block=block if kernel else 0,
                                    interpret=interpret)
            chosen = sel.select(found, n, k)
            return latent.absorbed(qc, sel.gather_lines(lines_, chosen), jnp.minimum(n, k) - 1,
                                   scale, rank)
        return run

    timed("score_select_gather_attend_reference", whole(False), q, w, index, lines, q_cat, lengths)
    timed("score_select_gather_attend", whole(True), q, w, index, lines, q_cat, lengths)

    inner = max(INNER // 10, 1)  # a piece's pieces take tens of ms
    block = timed("block_scores", lambda qi, ki, wi: sel.block_scores(qi, wi, ki), qs, index[:1],
                  ws, queries=s)
    qpos = (length - s + jnp.arange(s))[None, :]
    timed("selection_mask", lambda x, p: sel.selection_mask(x, p, k), block, qpos, queries=s)
    timed("block_top_k_values", lambda x, p: jax.lax.top_k(
        jnp.where(jnp.arange(length)[None, None] <= p[:, :, None], x, sel.NEG_INF), k)[0][..., -1],
        block, qpos, queries=s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
