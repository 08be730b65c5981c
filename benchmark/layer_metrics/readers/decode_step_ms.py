"""Device time of one decode step, in ms.

The trace gives the device seconds the engine's chunk program ran and how
often it ran (events of the ``XLA Modules`` line whose name matches
``module_regex``). One run of the program is ``chunk_size`` steps times its
dispatch depth, which the trace does not name; the engine's ``chunks`` and
``dispatches`` counters, read just before and just after the profile call,
give the mean depth over that span. step = seconds / (runs x chunk_size x
mean depth). The two spans are not the same instants (the profiler takes a
moment to start and to stop), so this holds where the mix of depths is steady
across the call, as it is in a saturated cell. Rates over the span are not
used: the profiler slows the host while it starts and stops.
"""

import re

from . import engine_delta_ratio


def read(sources: dict, params: dict):
    trace = sources.get("trace") or {}
    span = sources.get("trace_span") or {}
    pattern = re.compile(params["module_regex"])
    hit = [m for name, m in trace.get("modules", {}).items() if pattern.search(name)]
    seconds, runs = sum(m["seconds"] for m in hit), sum(m["count"] for m in hit)
    depth = engine_delta_ratio.read(span, {"numerator": "chunks", "denominator": "dispatches"})
    if not seconds or not runs or not depth:
        return None
    return seconds / (runs * params["chunk_size"] * depth) * 1e3
