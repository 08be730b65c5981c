#!/usr/bin/env python
"""Time the pieces of the learned selection (``ops/index_select.py``) on the
chip, alone, at the ``deepseek-v3.2-exp-ep16-d5.sparsedoc`` cell's shapes: 16
rows over a 32 k cache, 64 index heads of 128, 2,048 of the positions kept,
640-lane lines. One JSON line a piece; ``ms`` is one call's time by the host's
clock around ``--calls`` calls that end in ``block_until_ready``. Nothing here
is an end-to-end number. Refuses to run without a TPU.

    chiprun -- python3 scripts/bench_index_select.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=32768)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--piece", type=int, default=2048)
    ap.add_argument("--calls", type=int, default=20)
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

    def timed(name, fn, *inputs, **extra):
        fn = jax.jit(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*inputs))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*inputs)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.calls * 1e3
        print(json.dumps({"piece": name, "ms": round(ms, 4), "compile_s": round(compile_s, 2),
                          "device": dev.device_kind, **extra}), flush=True)
        return out

    scores = timed("step_scores", sel.step_scores, q, w, index)
    idx = timed("select_top_k", lambda x, n: sel.select(x, n, k), scores, lengths)
    timed("kth_largest_rows", lambda x: sel.kth_largest(x, k), scores)
    got = timed("gather_lines", sel.gather_lines, lines, idx)
    kept = jnp.minimum(lengths, k)
    timed("absorbed_over_selected",
          lambda qc, g, n: latent.absorbed(qc, g, n - 1, 0.1352, rank), q_cat, got, kept)
    timed("absorbed_dense", lambda qc, g, n: latent.absorbed(qc, g, n - 1, 0.1352, rank),
          q_cat, lines, lengths)

    def whole(qi, wi, keys_, lines_, qc, n):
        chosen = sel.select(sel.step_scores(qi, wi, keys_), n, k)
        return latent.absorbed(qc, sel.gather_lines(lines_, chosen), jnp.minimum(n, k) - 1,
                               0.1352, rank)

    timed("score_select_gather_attend", whole, q, w, index, lines, q_cat, lengths)

    block = timed("block_scores", sel.block_scores, qs, ws, index[:1], queries=s)
    qpos = (length - s + jnp.arange(s))[None, :]
    timed("selection_mask", lambda x, p: sel.selection_mask(x, p, k), block, qpos, queries=s)
    timed("block_top_k_values", lambda x: jax.lax.top_k(x, k)[0][..., -1], block, queries=s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
