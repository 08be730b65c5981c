"""Share of the HBM roofline a DeepSeek-V2 decode step reaches: the bytes one
step must read (benchmark/bytes_deepseek_v2.py: the weights once — of the held
experts those a live row is expected to hit — and each live row's latent lines
up to its context) over the chip's peak bytes per second, over the step's
device time as the trace alone gives it (``decode_step_ms_named``). Memory is
the bound named; the latent attention itself sits on the ridge
(``mla.attn_roofline_share``).

Live rows and contexts are the PROGRAM'S OWN COUNTS over the traced span
(``trace_span``: ``/metrics`` just before and just after the profile call):
live rows from the engine's pad counters, the mean context from
``continuous.mla.positions_cached`` ÷ ``steps_all``. A program without the
counters (the parent of the PR that added them) gives ``None``.

Which way the cuts bias it: ``decode_step_ms_named`` counts a run of the chunk
program that the traced window cuts at an edge as a whole run of 8 x depth
steps, so the step reads SHORT and this share HIGH — by up to two runs in the
window (PERF.md section 7). The counters ride home under the token blocks and
enter only as a ratio of one another."""

from benchmark import bytes_deepseek_v2

from . import decode_step_ms_named, metrics_path

SPAN = {"before": "trace_span.metrics_before", "after": "trace_span.metrics_after"}


def grown(sources: dict, path: str):
    return metrics_path.total(sources, SPAN, ["{model}.continuous." + path])


def live_rows_and_context(sources: dict):
    """(live rows a step, mean context a live row) over the traced span, or
    None where the program has no such counters."""
    rows, pad = grown(sources, "decode_rows"), grown(sources, "decode_pad_rows")
    steps, cached = grown(sources, "mla.steps_all"), grown(sources, "mla.positions_cached")
    if not rows or pad is None or not steps or cached is None:
        return None
    return sources["max_slots"] * (1.0 - pad / rows), cached / steps


def read(sources: dict, params: dict):
    step_ms = decode_step_ms_named.read(sources, params)
    found = live_rows_and_context(sources)
    if step_ms is None or found is None or not sources.get("peaks"):
        return None
    need = bytes_deepseek_v2.decode_step_bytes(sources["config"], *found)
    return need["total"] / sources["peaks"]["hbm_bytes_per_s"] / (step_ms / 1e3)
