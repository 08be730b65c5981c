#!/usr/bin/env python
"""Every device operation of a kept trace, not the ten the benchmark's
reduction keeps: per program its runs and device time, how much of it the
trace holds under a ``while`` event, and the operations by time with their
time per occurrence — inside the loops, and under no loop's event.

    # in one chiprun call: the machine and its .xplane.pb go when the call ends
    python3 benchmark/run.py --workload <cell> --seed <n> --seconds 45 --trace 1
    JAX_PLATFORMS=cpu python3 scripts/trace_ops.py .cache/benchmark/trace/<cell> \\
        [--once 'bf16[64,50176]'] [--top 30] [--json chiprun_out/ops.json]

A traced window cuts the program runs at its two edges: their operations are
in the trace, their ``while`` event is not, and their module event counts as
a run like any other. So "module time minus while time" is not time spent
outside the scan, and runs x depth x chunk size is more steps than ran
(PERF.md section 5, PR 34). ``--once`` names an operation that runs once a
step (the head's product): its occurrences are the steps the trace holds.
Needs jax to read the file, no chip.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def kind(hlo: str) -> str:
    m = re.search(r"[\]})] ([a-z][\w\-]*)\(", hlo)
    return m.group(1) if m else "?"


def programs(planes) -> dict:
    """{program: {runs, module_s, while_s, ops: {name: [seconds, count,
    count under no while event]}}} of the first device plane."""
    from benchmark import xplane

    lines = next((dict(ls) for name, ls in planes if xplane.DEVICE_PLANE.match(name)), None)
    if lines is None:
        return {}
    ops = lines[xplane.OPS_LINE]
    mods = sorted(lines[xplane.MODULES_LINE], key=lambda e: e[1])
    starts = [m[1] for m in mods]
    is_while = lambda name: re.match(r"%?while", name) is not None
    # loops nest (a ragged cache write is a loop inside the scan): the outermost count
    _, whiles = xplane.union_seconds([(s, s + d) for n, s, d, _ in ops if is_while(n)])
    w_starts = [w[0] for w in whiles]

    def within(edges_start, spans, t):
        i = bisect.bisect_right(edges_start, t) - 1
        return i if i >= 0 and t < spans[i][1] else None

    mod_spans = [(m[1], m[1] + m[2]) for m in mods]
    out: dict = {}
    for m in mods:
        p = out.setdefault(xplane.module_name(m[0]),
                           {"runs": 0, "module_s": 0.0, "while_s": 0.0, "ops": {}})
        p["runs"] += 1
        p["module_s"] += m[2] / 1e9
    for ws, we in whiles:
        i = within(starts, mod_spans, ws)
        if i is not None:
            out[xplane.module_name(mods[i][0])]["while_s"] += (we - ws) / 1e9
    for name, s, d, _ in ops:
        i = within(starts, mod_spans, s)
        if i is None or is_while(name):
            continue
        e = out[xplane.module_name(mods[i][0])]["ops"].setdefault(
            f"{xplane.op_name(name)} {kind(name)}", [0.0, 0, 0])
        e[0] += d / 1e9
        e[1] += 1
        e[2] += within(w_starts, whiles, s) is None
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or .xplane.pb")
    ap.add_argument("--once", default="", help="part of the name of a once-a-step operation")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--json", default="", help="also write everything here")
    args = ap.parse_args()
    from benchmark import xplane

    progs = programs(xplane.load(args.trace))
    if not progs:
        print("no device plane in this trace (a CPU rehearsal has none)", file=sys.stderr)
        return 1
    if args.json:
        with open(args.json, "w") as f:
            json.dump(progs, f)
    for name, p in sorted(progs.items(), key=lambda kv: -kv[1]["module_s"]):
        n = p["runs"]
        print(f"{name}: {n} runs, {p['module_s'] / n * 1e3:.2f} ms a run, "
              f"{p['while_s'] / n * 1e3:.2f} ms of it under a while event")
        steps = sum(c for k, (_, c, _) in p["ops"].items() if args.once and args.once in k)
        if steps:
            bare = sum(b for k, (_, _, b) in p["ops"].items() if args.once in k)
            print(f"  {steps} steps by `{args.once}` ({bare} under no while event): "
                  f"{p['module_s'] / steps * 1e3:.3f} ms a step")
        for k, (s, c, b) in sorted(p["ops"].items(), key=lambda kv: -kv[1][0])[:args.top]:
            per_step = f"  x{c / steps:6.2f} a step" if steps else ""
            print(f"  {k[:72]:72s} {s / c * 1e3:9.4f} ms x {c:6d} ({b} bare){per_step}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
