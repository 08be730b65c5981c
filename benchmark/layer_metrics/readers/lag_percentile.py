"""How late requests left the load generator against their schedule."""

from benchmark import stats


def read(sources: dict, params: dict):
    lags = sources.get("lags_ms")
    return stats.percentile(lags, params["q"]) if lags else None
