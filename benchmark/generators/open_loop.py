"""Open loop: independent users. Requests are due at Poisson instants at a
fixed rate, whatever the server does; latency counts from the due instant."""

from __future__ import annotations

import numpy as np

from . import _common as c


def schedule(seed: int, p: dict, vocab: int, seconds: float, max_seq_len: int) -> dict:
    n = max(1, round(p["rate_rps"] * seconds))
    shape = np.random.default_rng([p["shape_seed"], n])
    gaps = shape.exponential(1.0, n)
    gaps *= (seconds * n / (n + 1)) / gaps.sum()  # the last one is due inside the window
    prompts = c.lengths(shape, n, p["prompt"])
    outs = c.lengths(shape, n, p["output"])
    rng = np.random.default_rng([seed, 1])
    gaps = gaps[rng.permutation(n)]
    order = rng.permutation(n)
    due = np.cumsum(gaps)
    requests = []
    for i in range(n):
        pl, ol = prompts[order[i]], outs[order[i]]
        requests.append({"due_s": float(due[i]), "prompt": c.prompt_tokens(rng, pl, vocab),
                         "max_new_tokens": c.clip_output(pl, ol, max_seq_len, p["overrun"])})
    return {"mode": "open", "requests": requests, "drain_s": p.get("drain_s", 30.0)}
