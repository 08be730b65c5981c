"""Seeded bf16 checkpoints in the published (HF) layout, written fast.

The last shard (final norm and output head, 0.2-0.26 GB) is made from
``--seed``, so every logit and every token depends on it; the layers and the
embedding are made from ``BODY_SEED`` and written once per checkout. Writing
all 7.6-12 GB anew for every seed cost 21-28 s of set-up a run and, worse,
left the machine's file system busy for a minute afterwards: deploys that
followed took 51-81 s where they take 43-45 s on a quiet disk (chip runs,
PR 24).

``checkpoints/<family>.py`` gives the tensor names and shapes; this file
fills them. Every matrix is uniform with variance 1/fan_in (the scale of
``models/llama.init_params``), norms are ones. The values come from 16-bit
random indices into a table of 65,536 evenly spaced bf16 values: two passes
over memory per tensor, so that the write and not the generator sets the
pace (chip_smoke's float32 route wrote 0.27 GB/s, PERF.md section 5). The
safetensors container is written directly, tensor by tensor, without a
second copy of a shard in memory.
"""

from __future__ import annotations

import concurrent.futures
import importlib.util
import json
import math
import os
import shutil
import struct
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHUNK = 1 << 24  # elements generated at a time (32 MiB of bf16)
BODY_SEED = 24   # every shard but the last


def family_module(family: str):
    path = os.path.join(HERE, "checkpoints", f"{family}.py")
    spec = importlib.util.spec_from_file_location(f"bm_checkpoint_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


WIDTH = {"BF16": 2, "F32": 4}  # F32 is for CPU rehearsals: XLA:CPU lacks some bf16 dots


def nbytes(shards, dtype: str = "BF16") -> int:
    return WIDTH[dtype] * sum(math.prod(shape) for shard in shards for _, shape in shard)


def _table(fan_in: int, dtype: str):
    """65,536 values evenly spaced in [-a, a), where a*a/3 = 1/fan_in, as
    bf16 bit patterns (or as float32)."""
    import ml_dtypes
    import numpy as np

    a = math.sqrt(3.0 / fan_in)
    grid = (((np.arange(65536, dtype=np.float64) + 0.5) / 32768.0 - 1.0) * a).astype(np.float32)
    return grid if dtype == "F32" else grid.astype(ml_dtypes.bfloat16).view(np.uint16)


class Cancelled(Exception):
    """The run found out that it cannot go on; the write stops at once."""


def _write_shard(path: str, tensors, seed: int, base_index: int, tables: dict,
                 dtype: str, cancelled) -> None:
    import numpy as np

    header, offset = {}, 0
    for name, shape in tensors:
        n = WIDTH[dtype] * math.prod(shape)
        header[name] = {"dtype": dtype, "shape": list(shape),
                        "data_offsets": [offset, offset + n]}
        offset += n
    hjson = json.dumps(header, separators=(",", ":")).encode()
    hjson += b" " * ((8 - len(hjson) % 8) % 8)
    one = np.float32(1.0) if dtype == "F32" else np.uint16(0x3F80)  # 0x3F80 is bf16 1.0
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(hjson)))
        f.write(hjson)
        for j, (name, shape) in enumerate(tensors):
            count = math.prod(shape)
            if cancelled is not None and cancelled.is_set():
                raise Cancelled(path)
            if name.endswith("norm.weight"):
                f.write(np.full(count, one).tobytes())
                continue
            table = tables[shape[-1]]
            rng = np.random.Generator(np.random.SFC64([seed, base_index + j]))
            for start in range(0, count, CHUNK):
                idx = rng.integers(0, 65536, size=min(CHUNK, count - start), dtype=np.uint16)
                f.write(memoryview(table[idx]))


def ensure(parent_dir: str, config_name: str, family: str, sizes: dict, hf_config: dict,
           seed: int, dtype: str = "BF16", cancelled=None) -> tuple[str, int, float]:
    """The checkpoint of (configuration, seed) under ``parent_dir``, written
    if it is not there: all of it, or only the last shard where the body is
    there and the seed is another. One checkpoint a configuration is kept.
    Returns (model dir, bytes of tensor data, seconds spent writing)."""
    mod = family_module(family)
    shards = mod.shards(sizes)
    total = nbytes(shards, dtype)
    model_dir = os.path.join(parent_dir, config_name)
    marker = os.path.join(model_dir, ".written.json")
    body = {"config": config_name, "bytes": total, "dtype": dtype, "body_seed": BODY_SEED}
    want = dict(body, seed=seed)
    try:
        with open(marker) as f:
            have = json.load(f)
    except (OSError, ValueError):
        have = {}
    if have == want:
        return model_dir, total, 0.0
    last = len(shards) - 1
    if {k: have.get(k) for k in body} == body:
        todo = [last]  # the body is there: only the head follows the seed
    else:
        shutil.rmtree(model_dir, ignore_errors=True)
        os.makedirs(model_dir)
        todo = list(range(len(shards)))
    if os.path.exists(marker):
        os.remove(marker)
    t0 = time.monotonic()
    fan_ins = {shape[-1] for shard in shards for name, shape in shard
               if not name.endswith("norm.weight")}
    tables = {k: _table(k, dtype) for k in fan_ins}
    bases = [sum(len(s) for s in shards[:i]) for i in range(len(shards))]

    def write(i: int) -> None:
        _write_shard(os.path.join(model_dir, f"model-{i + 1:05d}-of-{len(shards):05d}.safetensors"),
                     shards[i], seed if i == last else BODY_SEED, bases[i], tables, dtype,
                     cancelled)

    with concurrent.futures.ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(write, todo))
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(hf_config, f, indent=1)
    with open(marker, "w") as f:
        json.dump(want, f)
    return model_dir, total, time.monotonic() - t0
