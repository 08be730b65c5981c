#!/usr/bin/env python
"""Time the decode step's per-row cache write on the chip, alone: the scatter
(``jax.vmap(dynamic_update_slice)``, what every family wrote before PR 41),
the same updates written out row by row (``minicpm_sala._write_rows``' form)
and the kernel (``ops/kv_write.write_rows_kernel``), at the leaves of the two
cells that take it — ``laguna-s-2.1-ep2-d5.reason``'s full layers ``[64, 4096,
8, 128]`` and rings ``[64, 528, 8, 128]``, ``mixtral-8x7b-d4.decode``'s ``[32,
2048, 8, 128]`` — inside a ``lax.scan`` whose carry is the donated leaf, as
the engine's chunk program holds it. One JSON line a case; nothing here is an
end-to-end number.

    chiprun -- python3 scripts/bench_kv_write.py

``us`` is one write's device time by the host's clock: a scan of ``--steps``
steps, divided, less the same scan with no write in it (``loop_us``: the
step's own reads, which hang every operand on the carry — the new line on the
line the step before wrote, the starts on the step — so that the compiler can
lift nothing out of the loop). Refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LEAVES = {"laguna_full_layer": (64, 4096), "laguna_ring": (64, 528), "mixtral": (32, 2048)}
KV_HEADS, HEAD_DIM = 8, 128


def timed(write, cache, new, index, steps: int, reps: int) -> float:
    """Seconds a step of a scan that ``write``s (cache, new, index) -> cache;
    ``write`` None: the loop alone."""
    import jax

    length = cache.shape[1]

    def run(cache, new, index):
        def body(carry, _):
            cache, new, index = carry
            if write is not None:
                cache = write(cache, new, index)
            # the next line hangs on what row 0 holds where it was just written
            line = jax.lax.dynamic_slice_in_dim(cache[0], index[0], 1)
            new = (new * 0.5 + line[None]).astype(new.dtype)
            return (cache, new, (index + 1) % length), None
        return jax.lax.scan(body, (cache, new, index), None, length=steps)[0]

    fn = jax.jit(run, donate_argnums=(0,))
    cache, new, index = fn(cache, new, index)
    jax.block_until_ready(cache)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        cache, new, index = fn(cache, new, index)
        jax.block_until_ready(cache)
        best = min(best, time.perf_counter() - t0)
    return best / steps


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=256)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from modelx_tpu.ops import kv_write

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 2

    def scatter(cache, new, index):
        return jax.vmap(lambda c, u, o: jax.lax.dynamic_update_slice(c, u, (o, 0, 0)))(
            cache, new, index)

    def by_row(cache, new, index):
        for b in range(cache.shape[0]):
            cache = jax.lax.dynamic_update_slice(cache, new[b:b + 1], (b, index[b], 0, 0))
        return cache

    impls = {"scatter": scatter, "by_row": by_row, "kernel": kv_write.write_rows_kernel}
    rng = np.random.default_rng(args.seed)
    for leaf, (rows, length) in LEAVES.items():
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
        shape = (rows, length, KV_HEADS, HEAD_DIM)
        make = lambda: jax.random.normal(keys[0], shape, jnp.bfloat16)  # noqa: E731
        new = jax.random.normal(keys[1], (rows, 1, KV_HEADS, HEAD_DIM), jnp.bfloat16)
        index = jnp.asarray(rng.integers(0, length, rows).astype(np.int32))
        want = scatter(make(), new, index)
        loop = timed(None, make(), new, index, args.steps, args.reps)
        for impl, write in impls.items():
            same = bool(jnp.array_equal(jax.jit(write)(make(), new, index), want))
            per = timed(write, make(), new, index, args.steps, args.reps)
            print(json.dumps({
                "leaf": leaf, "shape": list(shape), "impl": impl,
                "us": round((per - loop) * 1e6, 2), "us_a_row": round((per - loop) * 1e6 / rows, 3),
                "loop_us": round(loop * 1e6, 2), "same_as_scatter": same,
                "device_kind": device.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
