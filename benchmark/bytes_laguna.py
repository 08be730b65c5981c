"""Bytes one decode step of a Laguna configuration must read from HBM, from
shapes alone: the yardstick of ``model.decode_hbm_share.reason``.

What the algorithm needs, not what the program happens to read. Per layer,
by its kind: the attention matrices at THAT layer's head count (q, o and the
per-head gate grow with it; k and v do not); on a dense layer the MLP; on a
sparse layer the router at its published width, the shared expert, and the
held experts that at least one live row routes to — the expected number of
distinct ones when each row picks ``top_k`` of the published experts
uniformly; every live row's keys and values, up to its context on a
full-attention layer and up to ``min(context, sliding_window)`` on a
sliding one. Once per step the output head over the vocabulary held.
Embedding rows, norms and activations are left out (under 0.1 %).
"""

from __future__ import annotations


def expected_held_hit(held: int, published: int, top_k: int, rows: float) -> float:
    """Expected distinct held experts chosen by ``rows`` tokens that each
    pick ``top_k`` of ``published`` experts uniformly."""
    return held * (1.0 - (1.0 - top_k / published) ** max(rows, 0.0))


def decode_step_bytes(cfg: dict, live_rows: float, mean_context: float,
                      dtype_bytes: int = 2) -> dict:
    e, hd, kvh = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    held = cfg["num_experts"]
    published = (cfg.get("expert_share") or {}).get("published", held)
    hit = expected_held_hit(held, published, cfg["num_experts_per_tok"], live_rows)
    expert = 3 * e * cfg["moe_intermediate_size"]
    parts = {"attention": 0.0, "dense_mlp": 0.0, "router": 0.0, "shared_expert": 0.0,
             "experts": 0.0, "kv_full": 0.0, "kv_window": 0.0}
    for i in range(cfg["num_hidden_layers"]):
        heads = cfg["num_attention_heads_per_layer"][i]
        parts["attention"] += 2 * heads * hd * e + 2 * kvh * hd * e + heads * e
        if cfg["mlp_layer_types"][i] == "dense":
            parts["dense_mlp"] += 3 * e * cfg["intermediate_size"]
        else:
            parts["router"] += published * e
            parts["shared_expert"] += 3 * e * cfg["shared_expert_intermediate_size"]
            parts["experts"] += hit * expert
        if cfg["layer_types"][i] == "sliding_attention":
            parts["kv_window"] += 2 * kvh * hd * live_rows * min(mean_context,
                                                                 cfg["sliding_window"])
        else:
            parts["kv_full"] += 2 * kvh * hd * live_rows * mean_context
    parts["head"] = cfg["vocab_size"] * e
    parts = {k: v * dtype_bytes for k, v in parts.items()}
    parts["total"] = sum(parts.values())
    parts["experts_hit_per_layer"] = hit
    return parts
