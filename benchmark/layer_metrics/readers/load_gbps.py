"""Checkpoint bytes over the pod's own ``load_seconds`` (volume -> HBM),
median over the run's deploys, in GB/s."""

from benchmark import stats


def read(sources: dict, params: dict):
    rates = [d["load_bytes"] / d["load_seconds"] / 1e9 for d in sources.get("deploys", [])
             if d.get("load_bytes") and d.get("load_seconds")]
    return stats.median(rates) if rates else None
