"""Share of the HBM roofline a MiMo-V2-Flash decode step reaches: the bytes one
step must read (benchmark/bytes_mimo_v2.py: the weights once — of the held
experts those the program counted as hit — each live row's keys and values
up to its context on the full layers, and its rings) over the chip's peak
bytes per second, over the step's device time. Memory is the bound named.

The step's time is the chunk programs' device seconds in the traced window
over the steps the window holds, COUNTED: the full layers' decode kernel runs
once a full layer a step (``params["op"]``, its events in the kept
``.xplane.pb`` — ``attn_full_roofline_share.calls``), so a run the window cuts
gives the steps it ran (``decode_step_ms_named`` takes it for a whole one and
reads the step 3-16 % short, a share above 1).

Live rows and contexts are the PROGRAM'S OWN COUNTS over the traced span
(``trace_span``): live rows from the engine's pad counters; the mean context
from ``continuous.attn_kv_positions_read`` — the positions the full layers'
decode kernel read, summed over rows, layers and steps, each row's rounded up
to the kernel's block of 512: high by 256 positions a row on average, 1 % at
22 k — over full layers x slots x steps (``chunks`` x ``chunk_size``); the
held experts hit a layer a step from ``continuous.moe.experts_hit`` over the
expert layers and the same steps. A program without the counters, or an
untraced run, gives ``None``."""

from benchmark import bytes_mimo_v2

from . import decode_step_ms_named
from .decode_hbm_share_deepseek_v2 import grown


def experts_hit(sources: dict, params: dict):
    """Held experts hit an expert layer a step over the traced span, or None."""
    hit, chunks = grown(sources, "moe.experts_hit"), grown(sources, "chunks")
    layers = sum(1 for x in sources["config"]["moe_layer_freq"] if x)
    return hit / (layers * chunks * params["chunk_size"]) if hit is not None and chunks else None


def live_rows_and_context(sources: dict, params: dict):
    """(live rows a step, mean context a row, positions read a step) over the
    traced span, or None where the program has no such counters."""
    rows, pad = grown(sources, "decode_rows"), grown(sources, "decode_pad_rows")
    read, chunks = grown(sources, "attn_kv_positions_read"), grown(sources, "chunks")
    if not rows or pad is None or not read or not chunks:
        return None
    slots, steps = sources["max_slots"], chunks * params["chunk_size"]
    layers = len(bytes_mimo_v2.full_layers(sources["config"]))
    return slots * (1.0 - pad / rows), read / (layers * slots * steps), read / steps


def read(sources: dict, params: dict):
    from . import attn_full_roofline_share

    _, seconds = decode_step_ms_named.steps_and_seconds(sources, params)
    if not seconds or not sources.get("peaks"):
        return None
    found, hit = live_rows_and_context(sources, params), experts_hit(sources, params)
    kept = attn_full_roofline_share.calls(sources, params) if (
        found is not None and hit is not None) else None
    if kept is None:
        return None
    steps = kept[1] / len(bytes_mimo_v2.full_layers(sources["config"]))
    need = bytes_mimo_v2.decode_step_bytes(sources["config"], found[0], found[1], hit)
    return need["total"] / sources["peaks"]["hbm_bytes_per_s"] / (seconds / steps)
