"""Closed loop: callers that each wait for their reply. ``clients`` clients,
each with its own list of requests; a client sends its next when the last
one ends. Clients start ``stagger_s`` apart and the window opens
``lead_in_s`` after the first, in steady state."""

from __future__ import annotations

import numpy as np

from . import _common as c


def schedule(seed: int, p: dict, vocab: int, seconds: float, max_seq_len: int) -> dict:
    k, per = p["clients"], p["requests_per_client"]
    n = k * per
    shape = np.random.default_rng([p["shape_seed"], n])
    prompts = c.lengths(shape, n, p["prompt"])
    outs = c.lengths(shape, n, p["output"])
    rng = np.random.default_rng([seed, 2])
    order = rng.permutation(n)
    clients = []
    for ci in range(k):
        reqs = []
        for j in range(per):
            pl, ol = prompts[order[ci * per + j]], outs[order[ci * per + j]]
            reqs.append({"prompt": c.prompt_tokens(rng, pl, vocab),
                         "max_new_tokens": c.clip_output(pl, ol, max_seq_len, p["overrun"])})
        clients.append(reqs)
    return {"mode": "closed", "clients": clients, "lead_in_s": p["lead_in_s"],
            "stagger_s": p["stagger_s"]}
