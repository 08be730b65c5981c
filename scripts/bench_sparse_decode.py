#!/usr/bin/env python
"""Time the sparse layers' one-token attention on the chip, alone: the gather
form (``ops/sparse_attention.decode_attention``) against the kernel
(``decode_attention_kernel``) at the ``minicpm-sala-d12.longctx`` cell's
widths — 32 rows, 32 query / 2 KV heads of 128, rows of 32,768 positions, 64
blocks of 64 a KV head — with ``select_blocks``' own selections over random
compressed keys (block 0, the 32 blocks ending at the row's own, then 31
earlier ones by score) at contexts of 17-22 k, inside a ``lax.scan`` as the
engine's chunk program holds it. One JSON line a case; nothing here is an
end-to-end number.

    chiprun -- python3 scripts/bench_sparse_decode.py

``ms`` is one call's device time by the host's clock (a scan of ``--steps``
calls, divided); ``gbps`` the bytes the algorithm needs (each selected block's
own lanes, keys and values, once: 134 MB) over it. Refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS, HEADS, KV_HEADS, HEAD_DIM, CACHE_LEN = 32, 32, 2, 128, 32768


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from modelx_tpu.ops import sparse_attention as sparse

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 2
    spec = sparse.SparseSpec()
    rng = np.random.default_rng(args.seed)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q = jax.random.normal(keys[0], (ROWS, HEADS, HEAD_DIM), jnp.bfloat16)
    k = jax.random.normal(keys[1], (ROWS, CACHE_LEN, KV_HEADS * HEAD_DIM), jnp.bfloat16)
    v = jax.random.normal(keys[2], (ROWS, CACHE_LEN, KV_HEADS * HEAD_DIM), jnp.bfloat16)
    positions = rng.integers(17000, 22000, ROWS).astype(np.int32)
    index = jax.random.normal(
        keys[3], (ROWS, CACHE_LEN // spec.kernel_stride, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    chosen = np.asarray(sparse.select_blocks(
        q[:, None], index, jnp.asarray(positions + 1)[:, None], spec,
        CACHE_LEN // spec.block_size)[:, 0])
    needed = 2 * ROWS * KV_HEADS * spec.topk * spec.block_size * HEAD_DIM * 2
    cases = {"score_order": chosen, "index_order": np.sort(chosen, axis=-1),
             "one_block": np.zeros_like(chosen)}

    def timed(fn, chosen, positions):
        @jax.jit
        def run(q, k, v):
            def body(carry, _):
                # each call's query hangs on the one before, and its selection on
                # the query (by nothing: no value is that large), or the compiler
                # lifts the gather, which reads no query, out of the loop
                q, nothing = carry
                out = fn(q, k, v, chosen + nothing, positions, spec)
                q = (q + out * 1e-3).astype(q.dtype)
                return (q, nothing + (q[0, 0, 0] > 1e30).astype(jnp.int32)), None
            return jax.lax.scan(body, (q, jnp.int32(0)), None, length=args.steps)[0][0]

        run(q, k, v).block_until_ready()
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            run(q, k, v).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / args.steps * 1e3

    for case, picked in cases.items():
        picked, at = jnp.asarray(picked), jnp.asarray(positions)
        want = sparse.decode_attention(q, k, v, picked, at, spec)
        for impl, fn in (("gather", sparse.decode_attention),
                         ("kernel", sparse.decode_attention_kernel)):
            err = float(jnp.abs(fn(q, k, v, picked, at, spec) - want).max())
            ms = timed(fn, picked, at)
            print(json.dumps({"case": case, "impl": impl, "ms": round(ms, 4),
                              "gbps": round(needed / ms / 1e6, 1),
                              "peak_share": round(needed / ms / 1e6 / 819, 4),
                              "max_abs_err": err, "device_kind": device.device_kind}),
                  flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
