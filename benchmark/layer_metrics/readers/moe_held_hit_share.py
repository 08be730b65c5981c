"""Share of the held experts a decode step hits: the engine's
``moe.experts_hit`` (distinct held experts hit, summed over sparse layers and
steps) over held experts x sparse layers x decode steps, all as growth over
the window. The counters ride the token read-back, so they lag the dispatch
counters by the programs in flight — a few chunks in thousands. A program
without the counters gives ``None``."""

from . import engine_delta_ratio


def read(sources: dict, params: dict):
    model = sources.get("model", "default")
    before = ((sources.get("metrics_before") or {}).get(model, {}).get("continuous", {})
              .get("moe", {}))
    after = ((sources.get("metrics_after") or {}).get(model, {}).get("continuous", {})
             .get("moe", {}))
    chunks = engine_delta_ratio.delta(sources, "chunks")
    if "experts_hit" not in after or not chunks:
        return None
    hit = after["experts_hit"] - before.get("experts_hit", 0)
    steps = chunks * params["chunk_size"]
    return hit / (after["held_experts"] * after["sparse_layers"] * steps)
