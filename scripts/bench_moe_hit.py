#!/usr/bin/env python
"""Time the expert layer's routed sum at a decode step on the chip, alone: the
einsums over every held expert (``ops/moe.every_expert``: ``moe_share_ffn``'s
form everywhere but a decode step on one TPU device) against the kernel that reads only the
hit ones (``ops/moe.hit_experts``), at the two cells that take it —
``laguna-s-2.1-ep2-d5.reason``: 64 rows, 128 held experts of ``[1024, 3072]``;
``deepseek-v2-ep8-d5.longdoc``: 32 rows, 20 held experts of ``[1536, 5120]`` —
with every held expert hit and with the cells' own hit counts (112 and 14),
inside a ``lax.scan`` as the engine's chunk program holds it. One JSON line a
case; nothing here is an end-to-end number.

    chiprun -- python3 scripts/bench_moe_hit.py

``ms`` is one call's device time by the host's clock (a scan of ``--steps``
calls, divided); every operand hangs on the scan's carry (the rows on the sum
the step before gave, the combine weights on the rows), so that the compiler
can lift nothing out of the loop. ``gbps`` is the bytes of the HIT experts'
three matrices — what the algorithm needs, whatever the program read — over
it, and ``peak_share`` that over the chip's 819 GB/s. Refuses to run without a
TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# rows, held experts, F, D, the cell's distinct experts hit a step
CELLS = {"laguna-s-2.1-ep2-d5.reason": (64, 128, 1024, 3072, 112),
         "deepseek-v2-ep8-d5.longdoc": (32, 20, 1536, 5120, 14)}
HBM_GBPS = 819.0  # benchmark/peaks.json, TPU v5 lite


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--block-mib", type=int, nargs="*", default=[],
                    help="also time the kernel with blocks of at most this many MiB")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from modelx_tpu.ops import moe

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 2

    def timed(fn, t, mask, weights):
        def run(t, mask, *weights):
            def body(t, _):
                here = mask * (1.0 + jnp.abs(t[:, :1]).astype(jnp.float32))
                out = fn(t, here, *weights)
                return (t * 0.5 + out.astype(t.dtype) * 0.01).astype(t.dtype), None
            return jax.lax.scan(body, t, None, length=args.steps)[0]

        jitted = jax.jit(run)
        jax.block_until_ready(jitted(t, mask, *weights))
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(t, mask, *weights))
            best = min(best, time.perf_counter() - t0)
        return best / args.steps

    rng = np.random.default_rng(args.seed)
    default_block = moe.BLOCK_BYTES
    for cell, (rows, e, f, d, cell_hit) in CELLS.items():
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        t = jax.random.normal(keys[0], (rows, d), jnp.bfloat16)
        w_gate = (jax.random.normal(keys[1], (e, f, d), jnp.bfloat16) * d ** -0.5)
        w_up = (jax.random.normal(keys[2], (e, f, d), jnp.bfloat16) * d ** -0.5)
        w_down = (jax.random.normal(keys[3], (e, d, f), jnp.bfloat16) * f ** -0.5)
        weights = (w_gate, w_up, w_down)
        for n_hit in (e, cell_hit):
            mask = np.zeros((rows, e), np.float32)
            for expert in rng.choice(e, n_hit, replace=False):  # a few rows an expert
                mask[rng.choice(rows, 2, replace=False), expert] = rng.uniform(0.1, 0.5, 2)
            mask = jnp.asarray(mask)
            want = jax.jit(moe.every_expert)(t, mask, *weights)
            needed = n_hit * 3 * f * d * 2
            cases = [("einsum", moe.every_expert, None), ("kernel", moe.hit_experts, default_block)]
            cases += [("kernel", moe.hit_experts, mib << 20) for mib in args.block_mib]
            for impl, fn, block in cases:
                moe.BLOCK_BYTES = block or default_block
                line = {"cell": cell, "rows": rows, "held": e, "hit": n_hit, "impl": impl,
                        "block_mib": block and block >> 20}
                try:
                    got = jax.jit(lambda *a, fn=fn: fn(*a))(t, mask, *weights)  # a new trace a block size
                    line["max_abs_diff"] = float(jnp.max(jnp.abs(got - want)))
                    line["max_abs"] = float(jnp.max(jnp.abs(want)))
                    per = timed(fn, t, mask, weights)
                    line.update(ms=round(per * 1e3, 4), gbps=round(needed / per / 1e9, 1),
                                peak_share=round(needed / per / 1e9 / HBM_GBPS, 4))
                except Exception as err:  # a block plan the compiler refuses: say so, go on
                    line["error"] = f"{type(err).__name__}: {str(err)[:300]}"
                line["device_kind"] = device.device_kind
                print(json.dumps(line), flush=True)
        moe.BLOCK_BYTES = default_block
        del weights, w_gate, w_up, w_down
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
