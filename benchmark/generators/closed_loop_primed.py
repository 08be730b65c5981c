"""Closed loop whose clients each start with one short ``prime`` request. As
``closed_loop`` — ``clients`` clients, each with its own list of requests,
the next sent when the last one ends, ``stagger_s`` apart, the window opening
``lead_in_s`` after the first — but a client's FIRST request is the mix's
``prime`` one (``prompt_tokens``, ``new_tokens``), which ends inside the
lead-in. A mix whose real requests outlast the window (a long document and
thousands of output tokens) then still has completed requests to check, while
the long ones are cut by the window's end; ``requests_per_client`` counts the
prime one."""

from __future__ import annotations

import numpy as np

from . import _common as c
from . import closed_loop


def schedule(seed: int, p: dict, vocab: int, seconds: float, max_seq_len: int) -> dict:
    sched = closed_loop.schedule(seed, dict(p, requests_per_client=p["requests_per_client"] - 1),
                                 vocab, seconds, max_seq_len)
    prime = p["prime"]
    new = c.clip_output(prime["prompt_tokens"], prime["new_tokens"], max_seq_len, p["overrun"])
    rng = np.random.default_rng([seed, 3])
    for reqs in sched["clients"]:
        reqs.insert(0, {"prompt": c.prompt_tokens(rng, prime["prompt_tokens"], vocab),
                        "max_new_tokens": new})
    return sched
