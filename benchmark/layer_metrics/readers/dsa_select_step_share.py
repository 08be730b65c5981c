"""Share of the decode steps' device time that the learned selection takes:
the device seconds of the operations that score the index keys, choose the
positions and gather their lines, over the seconds of the chunk programs'
runs, both from the traced window (a run the window cuts is cut in both).

The runner's reduction keeps ten operations by time, and the selection is a
dozen smaller ones a layer, so this reader goes back to the kept
``.xplane.pb`` itself (``.cache/benchmark/trace/<cell>``, which the runner
leaves until its next traced run) in a child under ``JAX_PLATFORMS=cpu`` —
the runner's process never imports jax — and adds up the operations of the
first device plane whose HLO line matches one of the metric's ``match``
patterns. The patterns are the operations' own names and result shapes as a
kept trace of the cell shows them (``scripts/trace_ops.py``; written into the
metric's file with the run they were read off), since a fusion's event carries
its HLO line and not the ``dsa.*`` scope it was traced under. A trace without
a device plane, without such operations or without a chunk program gives
``None``."""

import functools
import json
import os
import re
import subprocess
import sys

from . import decode_step_ms_named

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def matching(trace_dir: str, patterns: list[str]) -> dict:
    """{pattern: [device seconds, events] of the operations it matches} over
    the first device plane; run in the child."""
    sys.path.insert(0, ROOT)
    from benchmark import xplane

    planes = xplane.load(trace_dir)
    lines = next((dict(ls) for name, ls in planes if xplane.DEVICE_PLANE.match(name)), None)
    if lines is None:
        return {}
    found = {p: [0.0, 0] for p in patterns}
    compiled = [(p, re.compile(p)) for p in patterns]
    for name, _, dur, _ in lines.get(xplane.OPS_LINE) or []:
        for p, rx in compiled:
            if rx.search(name):
                found[p][0] += dur / 1e9
                found[p][1] += 1
                break
    return found


@functools.lru_cache(maxsize=8)
def kept_operations(cell: str, patterns: tuple[str, ...], stamp: float) -> dict | None:
    """:func:`matching` over the cell's kept trace, in a child under
    ``JAX_PLATFORMS=cpu`` (``stamp``, the trace's time of writing, keys the
    answer: two metrics that ask the same of one run open the file once). Why
    it gives ``None`` goes to stderr: a metric that falls silent says so."""
    trace_dir = os.path.join(ROOT, ".cache", "benchmark", "trace", cell)
    argv = [sys.executable, "-m", __name__ if __name__ != "__main__" else __spec__.name,
            trace_dir, json.dumps(list(patterns))]
    try:
        out = subprocess.run(argv, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                             capture_output=True, text=True, timeout=900)
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (OSError, ValueError, IndexError, subprocess.TimeoutExpired) as e:
        tail = getattr(e, "stderr", None) or (out.stderr if "out" in locals() else "")
        print(f"{__name__}: no reading of {trace_dir}: {e!r} {str(tail)[-400:]}", file=sys.stderr)
        return None


def operations(sources: dict, patterns: list[str]) -> dict | None:
    """{pattern: [seconds, events]} of the traced run's kept trace, or None
    where there is no device trace to open."""
    trace_dir = os.path.join(ROOT, ".cache", "benchmark", "trace", sources["cell"])
    if not (sources.get("trace") or {}).get("device_planes") or not os.path.isdir(trace_dir):
        return None
    return kept_operations(sources["cell"], tuple(patterns), os.path.getmtime(trace_dir))


def read(sources: dict, params: dict):
    _, seconds = decode_step_ms_named.steps_and_seconds(sources, params)
    found = operations(sources, params["match"]) if seconds else None
    if not found:
        return None
    missing = [p for p in params["match"] if not found.get(p, [0])[0]]
    if missing:  # part of the selection is out of sight: no share that leaves out work
        print(f"{__name__}: no operation matches {missing}", file=sys.stderr)
        return None
    return sum(s for s, _ in found.values()) / seconds


if __name__ == "__main__":
    print(json.dumps(matching(sys.argv[1], json.loads(sys.argv[2]))))
