"""Deploys back to back: each is ``modelx dl`` into an empty volume, a new
pod on it, and one streamed greedy request. The same request every time, so
that every deploy's tokens can be compared."""

from __future__ import annotations

import numpy as np

from . import _common as c


def schedule(seed: int, p: dict, vocab: int, seconds: float, max_seq_len: int) -> dict:
    rng = np.random.default_rng([seed, 3])
    pl = p["prompt_tokens"]
    return {"mode": "deploy",
            "request": {"prompt": c.prompt_tokens(rng, pl, vocab),
                        "max_new_tokens": c.clip_output(pl, p["new_tokens"], max_seq_len,
                                                        p["overrun"])}}
