"""Share of the traced device seconds that fell to other programs than the named ones.

The reduced trace (benchmark/xplane.py) holds every XLA module's seconds under
its name (``jit__chunk_impl_d4``, ``jit__admit_nosmall``, ``jit__piece_impl``,
...). The result is the seconds of the modules whose name does NOT contain
``excluding``, over the seconds of all of them: in [0, 1] by construction. A
trace that holds no module gives ``None``.
"""


def read(sources: dict, params: dict):
    modules = (sources.get("trace") or {}).get("modules") or {}
    seconds = sum(m["seconds"] for m in modules.values())
    if not seconds:
        return None
    return sum(m["seconds"] for name, m in modules.items()
               if params["excluding"] not in name) / seconds
