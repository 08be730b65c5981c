"""Share of the HBM roofline a Nemotron-H decode step reaches: the bytes one
step must move (benchmark/bytes_nemotron_h.py: the weights once — of the held
experts those the step read — each live row's Mamba states and convolution
tails read and written, its keys and values up to its context) over the chip's
peak bytes per second, over the step's device time as the chunk program's
whole runs give it (``decode_step_ms_whole_runs``). Memory is the bound named.

Live rows, contexts and the experts read are the PROGRAM'S OWN COUNTS over the
traced span (``trace_span``: ``/metrics`` just before and just after the
profile call): live rows a step ``ssm.steps_live`` ÷ steps, the mean context
``ssm.positions_live`` ÷ ``ssm.steps_live``, experts read a layer
``moe.experts_read`` ÷ (expert layers x steps), steps ``ssm.steps_all`` ÷
slots. A program without the counters (the parent of the PR that added them)
gives ``None``."""

from benchmark import bytes_nemotron_h

from . import decode_step_ms_whole_runs, metrics_path

SPAN = {"before": "trace_span.metrics_before", "after": "trace_span.metrics_after"}


def grown(sources: dict, path: str):
    return metrics_path.total(sources, SPAN, ["{model}.continuous." + path])


def read(sources: dict, params: dict):
    step_ms = decode_step_ms_whole_runs.read(sources, params)
    steps = (grown(sources, "ssm.steps_all") or 0) / (sources.get("max_slots") or 1)
    live, held = grown(sources, "ssm.steps_live"), grown(sources, "ssm.positions_live")
    read_, layers = grown(sources, "moe.experts_read"), metrics_path.lookup(
        metrics_path.lookup_dump(sources, SPAN["after"]) or {},
        sources.get("model", "default") + ".continuous.moe.sparse_layers")
    if step_ms is None or not steps or not live or held is None or read_ is None or not layers:
        return None
    if not sources.get("peaks"):
        return None
    need = bytes_nemotron_h.decode_step_bytes(
        sources["config"], live_rows=live / steps, mean_context=held / live,
        experts_read=read_ / (layers * steps))
    return need["total"] / sources["peaks"]["hbm_bytes_per_s"] / (step_ms / 1e3)
