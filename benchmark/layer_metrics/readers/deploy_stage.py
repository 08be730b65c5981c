"""Median over the run's deploys of one stage time (or count) of a deploy."""

from benchmark import stats


def read(sources: dict, params: dict):
    values = [d[params["stage"]] for d in sources.get("deploys", [])
              if d.get(params["stage"]) is not None]
    return stats.median(values) if values else None
