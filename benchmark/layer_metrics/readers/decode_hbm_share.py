"""Share of the HBM roofline a decode step reaches: the bytes one step must
read (benchmark/bytes_model.py, from shapes; live rows from the engine's pad
counters, mean context from the cell's own schedule) over the chip's peak
bytes per second, over the step's device time. Memory is the bound named."""

from benchmark import bytes_model

from . import decode_step_ms, engine_delta_ratio


def read(sources: dict, params: dict):
    step_ms = decode_step_ms.read(sources, params)
    pad = engine_delta_ratio.read(sources, {"numerator": "decode_pad_rows",
                                            "denominator": "decode_rows"})
    means = sources.get("schedule_means")
    if step_ms is None or pad is None or not means or not sources.get("peaks"):
        return None
    slots = sources["max_slots"]
    need = bytes_model.decode_step_bytes(
        sources["config"], live_rows=slots * (1.0 - pad),
        mean_context=means["prompt"] + means["output"] / 2.0)
    return need["total"] / sources["peaks"]["hbm_bytes_per_s"] / (step_ms / 1e3)
