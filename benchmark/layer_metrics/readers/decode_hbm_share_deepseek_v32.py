"""Share of the HBM roofline a DeepSeek-V3.2-Exp decode step reaches: the bytes
one step must read (benchmark/bytes_deepseek_v32.py: the weights once — of the
held experts those the step read — each live row's index keys up to its context
and the latent lines its selection kept) over the chip's peak bytes per second,
over the step's device time by the window's own count of steps
(``decode_step_ms_counted``: this cell's engine runs one depth). Memory is the bound named: what the selection
costs beyond its bytes — a sort, a gather of single lines — is why this share
reads low, and ``dsa.select_step_share`` says how much of the step it is.

Live rows, contexts, lines kept and experts read are the PROGRAM'S OWN COUNTS
over the traced span (``trace_span``: ``/metrics`` just before and just after
the profile call), each a ratio of two counters that ride home together: live
rows a step from the engine's pad counters, the mean context
``dsa.positions_scored`` ÷ ``dsa.steps_all``, lines a row
``dsa.lines_selected`` ÷ ``dsa.steps_all``, experts read a layer a step
``moe.experts_read`` ÷ ``moe.assignments`` x the assignments a step makes a
layer (slots x ``num_experts_per_tok``: idle slots route too). A program
without the counters gives ``None``."""

from benchmark import bytes_deepseek_v32

from . import decode_step_ms_counted
from .decode_hbm_share_deepseek_v2 import grown  # a counter's growth over the traced span


def counted(sources: dict):
    """(live rows a step, mean context, lines kept a row, experts read a layer
    a step) over the traced span, or None where a counter is missing."""
    rows, pad = grown(sources, "decode_rows"), grown(sources, "decode_pad_rows")
    steps, scored = grown(sources, "dsa.steps_all"), grown(sources, "dsa.positions_scored")
    kept = grown(sources, "dsa.lines_selected")
    read_, made = grown(sources, "moe.experts_read"), grown(sources, "moe.assignments")
    if not rows or pad is None or not steps or scored is None or kept is None:
        return None
    if read_ is None or not made:
        return None
    per_layer_step = sources["max_slots"] * sources["config"]["num_experts_per_tok"]
    return (sources["max_slots"] * (1.0 - pad / rows), scored / steps, kept / steps,
            read_ / made * per_layer_step)


def read(sources: dict, params: dict):
    step_ms = decode_step_ms_counted.read(sources, params)
    found = counted(sources)
    if step_ms is None or found is None or not sources.get("peaks"):
        return None
    live, context, kept, experts = found
    need = bytes_deepseek_v32.decode_step_bytes(
        sources["config"], live, context, experts_read=experts, lines_selected=kept)
    return need["total"] / sources["peaks"]["hbm_bytes_per_s"] / (step_ms / 1e3)
