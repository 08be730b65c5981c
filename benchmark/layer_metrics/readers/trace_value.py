"""A value of the reduced trace (benchmark/xplane.py), e.g. ``idle_share``."""


def read(sources: dict, params: dict):
    trace = sources.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return trace.get(params["key"])
