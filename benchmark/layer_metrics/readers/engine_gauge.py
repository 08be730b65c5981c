"""A value of the engine's ``/metrics`` ``continuous`` block as it stands at
the window's end (``boundary_host_ms_p50`` covers the last 512 boundaries)."""


def read(sources: dict, params: dict):
    after = sources.get("metrics_after") or {}
    return after.get(sources.get("model", "default"), {}).get("continuous", {}).get(params["key"])
