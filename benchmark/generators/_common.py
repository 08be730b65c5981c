"""What the three generators share: a fixed SET of sizes, reordered by seed.

The sizes and gaps of a mix are drawn once from the mix's own ``shape_seed``;
``--seed`` only permutes them and draws the token ids. Every seed therefore
offers the same work in another order, so that runs with different seeds
differ no more than two runs of one seed.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.stats import pad16


def lognormal_lengths(rng, n: int, spec: dict) -> list[int]:
    """n lengths, lognormal with the given median and sigma, clipped."""
    xs = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return [int(x) for x in np.clip(np.rint(xs), spec["min"], spec["max"])]


def uniform_lengths(rng, n: int, spec: dict) -> list[int]:
    return [int(x) for x in rng.integers(spec["min"], spec["max"] + 1, n)]


def lengths(rng, n: int, spec: dict) -> list[int]:
    kind = spec.get("dist", "uniform")
    if kind == "lognormal":
        return lognormal_lengths(rng, n, spec)
    if kind == "uniform":
        return uniform_lengths(rng, n, spec)
    if kind == "fixed":
        return [int(spec["value"])] * n
    raise ValueError(f"unknown length distribution {kind!r}")


def clip_output(prompt_len: int, out_len: int, max_seq_len: int, overrun: int) -> int:
    """The engine admits a request when pad16(prompt) + max_new_tokens +
    overrun <= max_seq_len (dl/continuous.py ``_validate``; overrun is one
    chunk, ``--stream-chunk-size`` = 8 by default). The output is clipped so
    that no request is refused."""
    return max(1, min(out_len, max_seq_len - overrun - pad16(prompt_len)))


def prompt_tokens(rng, n: int, vocab: int) -> list[int]:
    return [int(t) for t in rng.integers(1, vocab, n)]
