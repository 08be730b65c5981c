#!/usr/bin/env python
"""Time the decode-attention kernels against their ``jnp`` forms on the chip,
alone: one layer's cached attention at a cell's shapes, rows at the contexts
the cell's traffic gives, inside a ``lax.scan`` as the engine's chunk program
holds it. One JSON line a case; nothing here is an end-to-end number.

    chiprun -- python3 scripts/bench_decode_attention.py
    chiprun -- python3 scripts/bench_decode_attention.py --blocks 256,512,1024
    chiprun -- python3 scripts/bench_decode_attention.py --shapes deepseek-latent \
        --blocks 1024,2048 --parent .parent

``ms`` is one call's device time by the host's clock (a scan of ``--steps``
calls, divided); ``gbps`` the bytes of the blocks the rows' contexts reach (the
reference: of the whole cache) over it. The ``deepseek-latent`` shape is the
latent decode kernel (``ops.latent_attention.decode_kernel``) against
``absorbed_reference``; the ``laguna-ring`` shape is the window layers' kernel
(``ops.attention.ring_decode_attention``: 64 rings of 528 positions under 72
query heads) against ``attention_reference`` under the ring's ``key_positions``
— rings, query and offsets ALL on the scan's carry, each step writing its new
line first as the engine's does (or the compiler lifts the reference's
transposes out of the loop), ``hbm_share`` both leaves' bytes over the HBM
peak. For the latent shape ``hbm_share`` is the bytes of the 640-lane lines its
blocks read over the HBM peak, ``mxu_share`` the operations
``benchmark.bytes_deepseek_v2.latent_attention_step`` counts for the rows'
contexts over the bf16 peak; ``--parent DIR`` adds the kernel of a checkout
that still widens the ragged one (``decode_attention(value_lanes=)``, PRs
43–46) as a line. Refuses to run without a TPU.
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


# rows, heads, line width, value lanes, cache length, contexts: the ``.longdoc``
# cell's one layer (32 rows that all decode 17-24 k into a 32 k cache)
LATENT = {"deepseek-latent": (32, 128, 640, 512, 32768, (17 * 1024, 24 * 1024))}


# rows, query heads, KV heads, head dim, ring length, window: ``.reason``'s three
# window layers (a ring is the window plus one 16-token bucket)
RINGS = {"laguna-ring": (64, 72, 8, 128, 528, 512)}


def bench_ring(args, name, device, rng) -> None:
    import jax
    import jax.numpy as jnp

    from modelx_tpu.ops import attention as attn
    from modelx_tpu.ops.kv_write import write_rows

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        peak = json.load(f)[device.device_kind]["hbm_bytes_per_s"]
    rows, hq, hkv, d, length, window = RINGS[name]
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    q = jax.random.normal(keys[0], (rows, 1, hq, d), jnp.bfloat16)
    k, v = (jax.random.normal(key, (rows, length, hkv, d), jnp.bfloat16) for key in keys[1:])
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731

    def reference(q, k, v, offsets):
        return t(attn.attention_reference(
            t(q), t(k), t(v), causal=True, q_offset=offsets, window=window,
            key_positions=attn.ring_key_positions(offsets, length)))

    def kernel(q, k, v, offsets):
        return attn.ring_decode_attention(q, k, v, offsets, window)

    def chunk(fn):
        """``steps`` decode steps of one window layer: the new line written,
        then the ring attended, everything on the carry."""
        def step(carry, _):
            q, k, v, offsets = carry
            k = write_rows(k, q[:, :, :hkv], offsets % length)
            v = write_rows(v, q[:, :, hkv:2 * hkv], offsets % length)
            out = fn(q, k, v, offsets)
            return ((q + out * 1e-3).astype(q.dtype), k, v, offsets + 1), None
        return jax.jit(lambda *carry: jax.lax.scan(step, carry, None, length=args.steps)[0],
                       donate_argnums=(1, 2))

    # offsets below the window, between window and ring, and past several wraps
    cases = {"traffic": rng.integers(64, 3328, rows), "filling": rng.integers(0, window, rows),
             "idle": [0] * rows}
    for case, offsets in cases.items():
        offsets = jnp.asarray(offsets, jnp.int32)
        want = jax.jit(reference)(q, k, v, offsets).astype(jnp.float32)
        err = float(jnp.abs(jax.jit(kernel)(q, k, v, offsets).astype(jnp.float32) - want).max())
        for impl, fn in (("reference", reference), ("ring_kernel", kernel)):
            run, best = chunk(fn), float("inf")
            carry = run(q, k + 0, v + 0, offsets)
            for _ in range(args.reps):
                t0 = time.perf_counter()
                carry = run(*carry)
                jax.block_until_ready(carry)
                best = min(best, time.perf_counter() - t0)
            ms = best / args.steps * 1e3
            line = {"shape": name, "case": case, "impl": impl, "ms": round(ms, 4),
                    "hbm_share": round(2 * k.size * 2 / (ms * 1e-3) / peak, 4),
                    "device_kind": device.device_kind}
            if impl == "ring_kernel":
                line["max_abs_err"] = err
            print(json.dumps(line), flush=True)


def timed(fn, carry, operands, steps, reps):
    """ms a call of ``fn(carry, *operands) -> carry's shape``: a scan of
    ``steps`` calls, each one's first operand hanging on the one before."""
    import jax

    @jax.jit
    def run(carry, *operands):
        def body(carry, _):
            return (carry + fn(carry, *operands) * 1e-3).astype(carry.dtype), None
        return jax.lax.scan(body, carry, None, length=steps)[0]

    run(carry, *operands).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run(carry, *operands).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / steps * 1e3


def parents_kernel(root):
    """``decode_attention`` of the checkout at ``root``, which takes
    ``value_lanes``: the latent kernel as PRs 43-46 ran it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "parents_attention", os.path.join(root, "modelx_tpu", "ops", "attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.decode_attention


def bench_latent(args, name, device, rng) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import bytes_deepseek_v2
    from modelx_tpu.ops import latent_attention as latent

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)[device.device_kind]
    rows, heads, width, rank, cache_len, (lo, hi) = LATENT[name]
    cfg = {"num_attention_heads": heads, "kv_lora_rank": rank, "qk_rope_head_dim": 64}
    scale = 0.1147
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 2)
    q = jax.random.normal(keys[0], (rows, heads, width), jnp.bfloat16)
    cache = jax.random.normal(keys[1], (rows, cache_len, width), jnp.bfloat16)
    pad = lambda out: jnp.pad(out, ((0, 0), (0, 0), (0, width - rank)))  # noqa: E731
    impls = {}
    for block in (int(b) for b in args.blocks.split(",")):
        if cache_len % block or cache_len < 2 * block:
            continue
        impls[f"kernel[{block}]"] = (block, lambda q, c, n, block=block: latent.decode_kernel(
            q, c, n, scale, rank, block=block))
    if args.parent:
        old = parents_kernel(args.parent)
        impls["parent[1024]"] = (1024, lambda q, c, n: old(
            q[:, None], c.reshape(rows, cache_len, 1, width), None, n, scale, block=1024,
            value_lanes=rank)[:, 0])
    ref = lambda q, c, n: latent.absorbed_reference(q, c, n - 1, scale, rank)  # noqa: E731
    cases = {"traffic": rng.integers(lo, hi + 1, rows).astype(np.int32),
             "full": np.full(rows, cache_len, np.int32), "one": np.ones(rows, np.int32)}
    for case, lengths in cases.items():
        lens = jnp.asarray(lengths)
        want = jax.jit(ref)(q, cache, lens).astype(jnp.float32)
        need = bytes_deepseek_v2.latent_attention_step(cfg, rows, float(lengths.mean()))
        line = {"shape": name, "case": case, "mean_context": float(lengths.mean()),
                "device_kind": device.device_kind}

        def shares(ms, positions):
            return {"ms": round(ms, 4),
                    "hbm_share": round(positions * width * 2 / (ms * 1e-3)
                                       / peaks["hbm_bytes_per_s"], 4),
                    "mxu_share": round(need["flops"] / (ms * 1e-3) / peaks["bf16_flops"], 4)}

        ms = timed(lambda q, c, n: pad(ref(q, c, n)), q, (cache, lens), args.steps, args.reps)
        print(json.dumps({**line, "impl": "jnp", **shares(ms, rows * cache_len)}), flush=True)
        for impl, (block, fn) in impls.items():
            err = float(jnp.abs(jax.jit(fn)(q, cache, lens).astype(jnp.float32) - want).max())
            ms = timed(lambda q, c, n: pad(fn(q, c, n)), q, (cache, lens), args.steps, args.reps)
            steps = int((-(-lengths // block)).sum())
            print(json.dumps({**line, "impl": impl, **shares(ms, steps * block), "steps": steps,
                              "read_share": round(steps * block / int(lengths.sum()), 4),
                              "max_abs_err": err}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shapes", default=",".join([*SHAPES, *LATENT, *RINGS]))
    ap.add_argument("--parent", default="", help="a checkout whose decode_attention takes "
                    "value_lanes: its latent kernel becomes a line of the latent shape")
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

    shapes = args.shapes.split(",")
    for name in shapes:
        if name in LATENT:
            bench_latent(args, name, device, rng)
        if name in RINGS:
            bench_ring(args, name, device, rng)
    for name, (rows, hq, hkv, d, cache_len, live, prompts, outputs) in SHAPES.items():
        if name not in shapes:
            continue
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
            ms = timed(ref, q, (k, v, lens), args.steps, args.reps)
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
                ms = timed(fn, q, (k, v, lens), args.steps, args.reps)
                read = int((-(-lengths // block) * block).sum())
                print(json.dumps({**line, "impl": f"ragged[{block}]", "ms": round(ms, 4),
                                  "gbps": round(read * position_bytes / ms / 1e6, 1),
                                  "read_share": round(read / (rows * cache_len), 4),
                                  "max_abs_err": err}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
