"""Share of the HBM roofline a MiniCPM-SALA decode step reaches: the bytes one
step must move (benchmark/bytes_minicpm_sala.py: the weights once, each live
row's lightning states read and written, its compressed keys and the keys and
values of the positions its sparse layers attend) over the chip's peak bytes
per second, over the step's device time as the trace alone gives it
(``decode_step_ms_named``). Memory is the bound named.

Live rows, contexts and the positions attended are the PROGRAM'S OWN COUNTS
over the traced span (``trace_span``: ``/metrics`` just before and just after
the profile call), not the schedule's means: live rows from the engine's pad
counters, the mean context and the positions read a row a sparse layer from
``continuous.sparse.{positions_cached, positions_read}`` ÷ ``steps_all``. A
program without the counters (the parent of the PR that added them) gives
``None``.

Which way the cuts bias it: ``decode_step_ms_named`` counts a run of the chunk
program that the traced window cuts at an edge as a whole run of 8 x depth
steps, so the step reads SHORT and this share HIGH — by up to two runs in the
window, about a tenth over 8 s at depth 4 (PERF.md section 7). The sparse
counters ride home under the token blocks, so they lag the pad counters by the
programs in flight at both ends of the span alike; they enter only as ratios
of one another."""

from benchmark import bytes_minicpm_sala

from . import decode_step_ms_named, metrics_path

SPAN = {"before": "trace_span.metrics_before", "after": "trace_span.metrics_after"}


def grown(sources: dict, path: str):
    return metrics_path.total(sources, SPAN, ["{model}.continuous." + path])


def read(sources: dict, params: dict):
    step_ms = decode_step_ms_named.read(sources, params)
    rows, pad = grown(sources, "decode_rows"), grown(sources, "decode_pad_rows")
    steps = grown(sources, "sparse.steps_all")
    cached, got = grown(sources, "sparse.positions_cached"), grown(sources, "sparse.positions_read")
    if step_ms is None or not rows or pad is None or not steps or cached is None or got is None:
        return None
    if not sources.get("peaks"):
        return None
    need = bytes_minicpm_sala.decode_step_bytes(
        sources["config"], live_rows=sources["max_slots"] * (1.0 - pad / rows),
        mean_context=cached / steps, positions_read=got / steps)
    return need["total"] / sources["peaks"]["hbm_bytes_per_s"] / (step_ms / 1e3)
