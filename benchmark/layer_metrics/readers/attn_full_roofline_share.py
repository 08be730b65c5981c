"""Share of the HBM roofline the full-attention layers' decode kernel reaches:
the bytes of keys and values behind the positions the engine says a call read
(``continuous.attn_kv_positions_read`` over the traced span — summed over rows,
full layers and steps — over the span's steps and the full layers; a position
is ``bytes_mimo_v2.line_values`` values, KV heads x (192 + 128)) over the
chip's peak bytes per second, over the device seconds a CALL of the operations
named ``params["op"]`` takes in the traced window (``ragged_decode_attention``:
one custom call a full layer a step). The count lives here and reads the same
work whatever implements it; the blocks a row reads past its context are in it
(they are read), so it is the kernel's own efficiency, not the algorithm's.

Seconds and calls are both COUNTED in the kept ``.xplane.pb``
(``dsa_select_step_share.operations``: a child of its own under
``JAX_PLATFORMS=cpu``), so a run of the chunk program that the window cuts
gives the calls it made and their seconds: ``decode_step_ms_named``'s bias (a
cut run taken for a whole one, a step 3-16 % short) would read this share
above 1. An untraced run, a trace without the operation, or a program without
the counters gives ``None``."""

from benchmark import bytes_mimo_v2

from . import decode_hbm_share_mimo_v2, dsa_select_step_share


def calls(sources: dict, params: dict):
    """(device seconds, events) of the operations named ``params["op"]`` in the
    kept trace, or None."""
    pattern = "^%?" + params["op"] + r"[.\d]* = "
    found = dsa_select_step_share.operations(sources, [pattern]) or {}
    seconds, events = found.get(pattern, (0.0, 0))
    return (seconds, events) if events else None


def read(sources: dict, params: dict):
    peaks = sources.get("peaks")
    found = decode_hbm_share_mimo_v2.live_rows_and_context(sources, params)
    kept = calls(sources, params) if found is not None and peaks else None
    if kept is None:
        return None
    layers = len(bytes_mimo_v2.full_layers(sources["config"]))
    roof_s = bytes_mimo_v2.full_attention_bytes(sources["config"], found[2] / layers) / peaks[
        "hbm_bytes_per_s"]
    return roof_s / (kept[0] / kept[1])
