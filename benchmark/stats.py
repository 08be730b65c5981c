"""The arithmetic of the end-to-end metrics: percentiles, TTFT, TPOT, rates.

Kept with the benchmark so that no later PR can change how a number is made.
"""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks — what
    ``numpy.percentile`` gives by default, without numpy."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: list[float]) -> float:
    return percentile(values, 50.0)


def ttft_ms(due_s: float, token_times: list[float]) -> float | None:
    """From the instant the request was DUE to its first streamed line."""
    return (token_times[0] - due_s) * 1e3 if token_times else None


def tpot_ms(token_times: list[float]) -> float | None:
    """(t_last - t_first) / (n - 1): robust to tokens delivered in groups.
    A request with one token has no gap and gives None."""
    n = len(token_times)
    if n < 2:
        return None
    return (token_times[-1] - token_times[0]) / (n - 1) * 1e3


def tokens_in_window(all_times: list[list[float]], t0: float, t1: float) -> int:
    return sum(1 for times in all_times for t in times if t0 <= t <= t1)


def pad16(n: int) -> int:
    """The engine's admit bucket (models/decode.py SEQ_BUCKET = 16)."""
    return -(-n // 16) * 16
