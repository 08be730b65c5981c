"""Device time of one decode step, in ms: the chunk programs' device seconds in
the traced window over the steps the window holds, COUNTED — the events of an
operation that runs once a step (``once``: the head's product, by its result
shape as a kept trace shows it).

``decode_step_ms_named`` takes a run the window cuts at an edge for a whole
one (3-16 % short); ``decode_step_ms_whole_runs`` is exact where the engine
ran several depths and one module has no cut run, but a cell whose engine
runs ONE depth has one module, cut runs and all (`.sparsedoc`: 11.907, 11.914
and 12.504 ms on three traced runs of one program whose step is 12.989, my
chip runs, PR 50). Here seconds and steps are cut by the same window: a cut
run gives the steps it ran and their seconds. The count comes from the kept
``.xplane.pb`` (``dsa_select_step_share.operations``: a child of its own, once
a run). An untraced run, a trace without the operation, or a program whose
modules carry no depth gives ``None``."""

from . import decode_step_ms_named, dsa_select_step_share


def read(sources: dict, params: dict):
    _, seconds = decode_step_ms_named.steps_and_seconds(sources, params)
    found = dsa_select_step_share.operations(sources, [params["once"]]) if seconds else None
    steps = (found or {}).get(params["once"], [0, 0])[1]
    return seconds / steps * 1e3 if steps else None
