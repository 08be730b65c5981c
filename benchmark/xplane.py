#!/usr/bin/env python3
"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

    python benchmark/xplane.py <trace dir or .xplane.pb>        # prints one JSON object
    python benchmark/xplane.py <...> --describe                 # planes, lines, sample events

Run as a child under ``JAX_PLATFORMS=cpu`` after the pod has stopped: reading
needs jax (``jax.profiler.ProfileData``) but no chip.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
operation and whose line ``XLA Modules`` has one event per executed program,
named ``<jit name>(<fingerprint>)``; host threads are lines of ``/host:CPU``.
Busy time is the union of the ``XLA Ops`` intervals of a chip; the window is
the span from the first to the last event of any plane. Where a trace has no
device plane (a CPU rehearsal), events that carry an ``hlo_op`` stat stand in
for device operations, so that the arithmetic can be rehearsed; such a
reduction says ``"device_planes": 0`` and is never reported as a device's.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_HOST_EVENT_NS = 20_000   # host events shorter than this name no gap
GAPS_ATTRIBUTED = 400        # the longest gaps, attributed to host work
TOP = 10
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU)")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Length of the union of [start, end) intervals (ns in, seconds out) and
    the merged intervals themselves."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, [(s, e) for s, e in merged]


def gaps_of(merged: list[tuple[float, float]], t0: float, t1: float) -> list[tuple[float, float]]:
    """The complement of merged busy intervals inside [t0, t1]."""
    out, cur = [], t0
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def op_name(event_name: str) -> str:
    """A TPU trace names an operation by its whole HLO line, thousands of
    characters for a loop: ``%fusion.5 = bf16[8,32]{...} fusion(...)`` ->
    ``fusion.5 bf16[8,32]``; a tuple-shaped result keeps the name alone."""
    m = re.match(r"%?([\w.\-]+) = (\(?)([\w\[\],]*)", event_name)
    if not m:
        return event_name[:80]
    return m.group(1) if m.group(2) or not m.group(3) else f"{m.group(1)} {m.group(3)[:48]}"


def module_name(event_name: str) -> str:
    """``jit__chunk_impl(1234567)`` -> ``jit__chunk_impl``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def attribute_gaps(gaps, host_events) -> dict[str, float]:
    """Seconds of device idleness by what the host was doing: each of the
    longest gaps goes to the SHORTEST host event that covers at least 80 % of
    it (the deepest frame or annotation that spans the gap), else to the host
    event that overlaps it most, else to ``(no host event)``."""
    import numpy as np

    by: dict[str, float] = {}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:GAPS_ATTRIBUTED]
    if host_events:
        hs = np.array([h[0] for h in host_events])
        he = np.array([h[1] for h in host_events])
        hd = he - hs
    for s, e in longest:
        name = "(no host event)"
        if host_events:
            ov = np.minimum(he, e) - np.maximum(hs, s)
            cover = ov >= 0.8 * (e - s)
            if cover.any():
                idx = np.where(cover)[0]
                name = host_events[int(idx[np.argmin(hd[idx])])][2]
            elif (ov > 0).any():
                name = host_events[int(np.argmax(ov))][2]
        by[name] = by.get(name, 0.0) + (e - s) / 1e9
    return by


def reduce_planes(planes) -> dict:
    """``planes``: [(plane name, [(line name, [(event name, start_ns,
    duration_ns, stats dict)])])] — the shape ``load`` returns and the tests
    build by hand."""
    t_min, t_max = float("inf"), float("-inf")
    device = [(n, lines) for n, lines in planes if DEVICE_PLANE.match(n)]
    host_events: list[tuple[float, float, str]] = []
    for name, lines in planes:
        for lname, events in lines:
            for ename, start, dur, _ in events:
                t_min, t_max = min(t_min, start), max(t_max, start + dur)
                if name.startswith("/host:") and dur >= MIN_HOST_EVENT_NS:
                    host_events.append((start, start + dur, ename))
    if t_max <= t_min:
        return {"window_s": 0.0, "busy_s": 0.0, "device_planes": len(device)}
    per_plane_ops: list[list] = []
    modules: dict[str, dict] = {}

    def add_module(name: str, dur: float) -> None:
        m = modules.setdefault(name, {"seconds": 0.0, "count": 0})
        m["seconds"] += dur / 1e9
        m["count"] += 1

    if device:
        for _, lines in device:
            by_line = dict(lines)
            ops = by_line.get(OPS_LINE) or by_line.get(MODULES_LINE) or []
            per_plane_ops.append(ops)
            for ename, _, dur, _ in by_line.get(MODULES_LINE, []):
                add_module(module_name(ename), dur)
    else:
        ops = [ev for _, lines in planes for _, events in lines for ev in events
               if "hlo_op" in ev[3]]
        per_plane_ops.append(ops)
        for ev in ops:
            add_module(str(ev[3].get("hlo_module", "?")), ev[2])
        # the stand-in operations are host events too: they name no gap
        op_names = {ev[0] for ev in ops}
        host_events = [h for h in host_events if h[2] not in op_names]
    busy, op_seconds, all_gaps = [], {}, []
    for ops in per_plane_ops:
        b, merged = union_seconds([(s, s + d) for _, s, d, _ in ops])
        busy.append(b)
        all_gaps += gaps_of(merged, t_min, t_max)
        for ename, _, dur, _ in ops:
            short = op_name(ename)
            op_seconds[short] = op_seconds.get(short, 0.0) + dur / 1e9
    n = len(per_plane_ops)
    idle = attribute_gaps(all_gaps, host_events)
    top = lambda d: [[k, v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    window_s = (t_max - t_min) / 1e9
    busy_s = sum(busy) / n
    return {
        "window_s": window_s, "busy_s": busy_s, "idle_share": 1.0 - busy_s / window_s,
        "device_planes": len(device),
        "modules": {k: {"seconds": v["seconds"] / n, "count": v["count"]}
                    for k, v in modules.items()},
        "device_ops": top(op_seconds), "idle_gaps": top(idle),
        "longest_gap_s": max((e - s for s, e in all_gaps), default=0.0) / 1e9,
    }


def load(path: str, with_stats: bool = False):
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    # event stats are read only where they are needed: with no device plane
    # (a CPU rehearsal), to find the stand-in operations
    on_device = any(DEVICE_PLANE.match(plane.name) for plane in data.planes)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, float(e.start_ns), float(e.duration_ns),
                                       {} if on_device and not with_stats else dict(e.stats))
                                      for e in line.events]))
        planes.append((plane.name, lines))
    return planes


def describe(planes) -> dict:
    """What a person looks at before trusting the reduction."""
    out = {}
    for name, lines in planes:
        out[name] = {lname: {"events": len(ev),
                             "sample": [[e[0], e[2], {k: str(v)[:60] for k, v in
                                                      list(e[3].items())[:6]}]
                                        for e in ev[:4]]}
                     for lname, ev in lines[:40]}
    return out


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    planes = load(argv[0], with_stats="--describe" in argv)
    print(json.dumps(describe(planes) if "--describe" in argv else reduce_planes(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
