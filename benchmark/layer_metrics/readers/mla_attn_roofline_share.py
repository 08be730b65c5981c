"""Share of its roofline the latent attention of a decode step reaches: what
the roofs ask for one step's latent attention over all layers —
max(operations ÷ the chip's bf16 peak, bytes ÷ its HBM peak), from
``bytes_deepseek_v2.latent_attention_step`` at the live rows and the mean
context the program's own counters give over the traced span — over the device
seconds a step the trace gives the operation.

The reduction keeps ten operations by name and result shape; the absorbed
kernel is a custom call a layer, named for the kernel
(``latent_decode_attention.51``), whose result is ``bf16[slots, heads,
kv_lora_rank]``. Where fewer such operations than the model has layers are
among the kept ones, part of the time is out of sight and the reader gives
``None`` rather than a share that leaves out work (then by hand,
``scripts/trace_ops.py``). Steps are counted as ``decode_step_ms_named`` counts
them, with its known bias (a run cut at the window's edge counts whole: the
time a step reads short, this share high, by up to two runs in the window)."""

import re

from benchmark import bytes_deepseek_v2

from . import decode_hbm_share_deepseek_v2, decode_step_ms_named, metrics_path


def read(sources: dict, params: dict):
    trace, peaks = sources.get("trace") or {}, sources.get("peaks")
    steps, _ = decode_step_ms_named.steps_and_seconds(sources, params)
    found = decode_hbm_share_deepseek_v2.live_rows_and_context(sources)
    gauges = metrics_path.lookup_dump(
        sources, "trace_span.metrics_after." + sources.get("model", "default") + ".continuous.mla")
    if not steps or found is None or not peaks or not gauges:
        return None
    shape = f"bf16[{sources['max_slots']},{gauges['heads']},{gauges['kv_lora_rank']}]"
    kept = [seconds for name, seconds in trace.get("device_ops", [])
            if re.match(r"(latent_decode_attention|custom-call)[.\d]* ", name)
            and name.endswith(shape)]
    if len(kept) < gauges["layers"]:
        return None
    one = bytes_deepseek_v2.latent_attention_step(sources["config"], *found)
    roof_s = gauges["layers"] * max(one["flops"] / peaks["bf16_flops"],
                                    one["bytes"] / peaks["hbm_bytes_per_s"])
    return roof_s / (sum(kept) / steps)
