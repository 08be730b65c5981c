#!/usr/bin/env python
"""Time the ragged decode-attention kernel against ``attention_reference`` on
the chip, alone: one layer's cached attention at a cell's shapes, rows at the
contexts the cell's traffic gives, inside a ``lax.scan`` as the engine's chunk
program holds it. One JSON line a case; nothing here is an end-to-end number.

    chiprun -- python3 scripts/bench_decode_attention.py
    chiprun -- python3 scripts/bench_decode_attention.py --blocks 256,512,1024

``ms`` is one call's device time by the host's clock (a scan of ``--steps``
calls, divided); ``gbps`` the bytes of the blocks the rows' contexts reach (the
reference: of the whole cache) over it. Refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (rows, query heads, KV heads, head dim, cache length, live rows, prompt
# range, output range): the decode cells' shapes and closed-loop traffic
SHAPES = {
    "laguna-full": (64, 48, 8, 128, 4096, 57, (64, 256), (1024, 3072)),
    "mixtral": (32, 32, 8, 128, 2048, 31, (64, 256), (256, 512)),
}


def contexts(rng, rows, live, prompts, outputs, cache_len):
    """Each live row somewhere along its own request; idle rows at 1."""
    import numpy as np

    lengths = np.ones(rows, np.int32)
    out = rng.integers(outputs[0], outputs[1] + 1, live)
    lengths[:live] = rng.integers(prompts[0], prompts[1] + 1, live) + (
        rng.random(live) * out).astype(np.int32)
    return np.minimum(lengths, cache_len)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from modelx_tpu.ops import attention as attn

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    t = lambda x: x.transpose(0, 2, 1, 3)

    def timed(fn, q, k, v, lengths):
        @jax.jit
        def run(q, k, v, lengths):
            def body(q, _):  # each call's query hangs on the one before
                out = fn(q, k, v, lengths)
                return (q + out * 1e-3).astype(q.dtype), None
            return jax.lax.scan(body, q, None, length=args.steps)[0]

        run(q, k, v, lengths).block_until_ready()
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            run(q, k, v, lengths).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / args.steps * 1e3

    for name, (rows, hq, hkv, d, cache_len, live, prompts, outputs) in SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        q = jax.random.normal(keys[0], (rows, 1, hq, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (rows, cache_len, hkv, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (rows, cache_len, hkv, d), jnp.bfloat16)
        position_bytes = 2 * hkv * d * 2  # K and V, bf16
        cases = {
            "traffic": contexts(rng, rows, live, prompts, outputs, cache_len),
            "full": np.full(rows, cache_len, np.int32),
            "one": np.ones(rows, np.int32),
        }
        for case, lengths in cases.items():
            lens = jnp.asarray(lengths)
            ref = lambda q, k, v, n: t(attn.attention_reference(
                t(q), t(k), t(v), causal=True, q_offset=n - 1))
            want = ref(q, k, v, lens).astype(jnp.float32)
            ms = timed(ref, q, k, v, lens)
            line = {"shape": name, "case": case, "impl": "reference", "ms": round(ms, 4),
                    "mean_context": float(lengths.mean()),
                    "gbps": round(rows * cache_len * position_bytes / ms / 1e6, 1),
                    "device_kind": device.device_kind}
            print(json.dumps(line), flush=True)
            for block in (int(b) for b in args.blocks.split(",")):
                if cache_len % block or cache_len < 2 * block:
                    continue
                fn = lambda q, k, v, n: attn.decode_attention(q, k, v, n, block=block)
                err = float(jnp.abs(fn(q, k, v, lens).astype(jnp.float32) - want).max())
                ms = timed(fn, q, k, v, lens)
                read = int((-(-lengths // block) * block).sum())
                print(json.dumps({**line, "impl": f"ragged[{block}]", "ms": round(ms, 4),
                                  "gbps": round(read * position_bytes / ms / 1e6, 1),
                                  "read_share": round(read / (rows * cache_len), 4),
                                  "max_abs_err": err}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
