"""Ratio of two cumulative engine counters' growth over the window."""


def delta(sources: dict, key: str, before: str = "metrics_before", after: str = "metrics_after"):
    model = sources.get("model", "default")
    a = (sources.get(after) or {}).get(model, {}).get("continuous", {}).get(key)
    b = (sources.get(before) or {}).get(model, {}).get("continuous", {}).get(key, 0)
    return None if a is None else a - b


def read(sources: dict, params: dict):
    num, den = delta(sources, params["numerator"]), delta(sources, params["denominator"])
    if num is None or not den:
        return None
    return num / den
