"""Device time of one decode step, in ms, from the trace alone.

The engine names each chunk program for its static dispatch depth
(``jit__chunk_impl_d4``: one run scans 4 x ``chunk_size`` steps), so the
events of the ``XLA Modules`` line say by themselves how many steps ran:
step = seconds / sum(runs x chunk_size x depth). No counter read around the
profile call enters, so nothing depends on the profiler's start and stop
lining up with the engine's counters (``decode_step_ms`` read 0.86-0.99 of
the roofline where the trace gave 0.80, PERF.md section 7). A program whose
modules carry no depth — one older than the naming — gives ``None``.
"""

import re

DEPTH = re.compile(r"chunk_impl_d(\d+)")


def steps_and_seconds(sources: dict, params: dict):
    trace = sources.get("trace") or {}
    steps = seconds = 0.0
    for name, m in trace.get("modules", {}).items():
        hit = DEPTH.search(name)
        if hit:
            steps += m["count"] * params["chunk_size"] * int(hit.group(1))
            seconds += m["seconds"]
    return steps, seconds


def read(sources: dict, params: dict):
    steps, seconds = steps_and_seconds(sources, params)
    if not steps or not seconds:
        return None
    return seconds / steps * 1e3
