"""Share of the HBM roofline a Laguna decode step reaches: the bytes one
step must read (benchmark/bytes_laguna.py, from shapes and layer kinds; live
rows from the engine's pad counters, mean context from the cell's own
schedule) over the chip's peak bytes per second, over the step's device time
as the trace alone gives it (``decode_step_ms_named``). Memory is the bound
named. With XLA alone this is the expert layer's roofline share: the held
experts are most of what a step reads."""

from benchmark import bytes_laguna

from . import decode_step_ms_named, engine_delta_ratio


def read(sources: dict, params: dict):
    step_ms = decode_step_ms_named.read(sources, params)
    pad = engine_delta_ratio.read(sources, {"numerator": "decode_pad_rows",
                                            "denominator": "decode_rows"})
    means = sources.get("schedule_means")
    if step_ms is None or pad is None or not means or not sources.get("peaks"):
        return None
    need = bytes_laguna.decode_step_bytes(
        sources["config"], live_rows=sources["max_slots"] * (1.0 - pad),
        mean_context=means["prompt"] + means["output"] / 2.0)
    return need["total"] / sources["peaks"]["hbm_bytes_per_s"] / (step_ms / 1e3)
