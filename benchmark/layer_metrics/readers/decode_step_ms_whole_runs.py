"""Device time of one decode step, in ms, from the chunk program's WHOLE runs.

``decode_step_ms_named`` divides the chunk modules' seconds by module events x
8 x depth and so takes a run that the traced window cuts at an edge for a whole
one: it reads 3-16 % short (PERF.md section 7) and a share built on it as much
high. Here each depth's module is read by itself: ``seconds / (events x
chunk_size x depth)``. A run cut at an edge gives its module seconds short of
its steps, never over, so every module reads AT MOST the true step and a
module with no cut run reads it exactly: the step is the LARGEST of the
modules' readings. The window has two edges and the engine three depths, so
where all three ran one module at least is whole; where fewer ran, the reading
is short by at most the cut runs' share of the best module's events.

The program's own step counter (``ssm.steps_all`` / slots) cannot stand under
the trace's seconds: ``trace_span``'s two ``/metrics`` reads lie around the
whole profile call, whose stop outlasts the load (88 s around an 8 s window,
1,776 steps counted where the window held about 550: my chip run, PR 46).
Counters over the span give RATIOS (live rows, contexts and experts read a
step, in ``decode_hbm_share_nemotron_h``), never a rate. An untraced run, or a
program whose modules carry no depth, gives ``None``."""

from .decode_step_ms_named import DEPTH


def read(sources: dict, params: dict):
    modules = (sources.get("trace") or {}).get("modules", {})
    per_step = [m["seconds"] / (m["count"] * params["chunk_size"] * int(hit.group(1)))
                for name, m in modules.items() if (hit := DEPTH.search(name)) and m.get("count")]
    return max(per_step) * 1e3 if per_step else None
