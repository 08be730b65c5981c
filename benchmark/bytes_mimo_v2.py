"""Bytes one decode step of a MiMo-V2-Flash configuration must read from HBM,
and the bytes of its full-attention layers' caches alone, from shapes: the
yardsticks of ``model.decode_hbm_share.longcode`` and
``attn.full_roofline_share.longcode``.

What the algorithm needs, not what the program happens to read. Per layer, by
its kind (``hybrid_layer_pattern``): the attention matrices at THAT kind's
head counts and widths (keys ``head_dim`` wide, values ``v_head_dim``) and, on
a window layer, its sinks; on a dense layer (``moe_layer_freq`` 0) the MLP; on
an expert layer the router at its published width with its choice bias and the
held experts that at least one live row routes to — the number the program
counted where it is given (``experts_hit``: sigmoid scores under a choice bias
concentrate the choice, 7.2 of 16 a layer a step where uniform picks give
10.2), else the expected number of distinct ones when each row picks
``num_experts_per_tok`` of the published experts uniformly; every live row's keys and values: up to its context on a
full layer, ``KV heads x (192 + 128)`` values a position, and on a window
layer the ring as the engine keeps it, ``sliding_window + 16`` indices of the
window layers' KV heads (a decode step reads a ring whole: its 16 indices of
slack are the program's, and 11 % of 0.12 GB). Once per step the output head
over the vocabulary held. Embedding rows, norms and activations are left out
(under 0.1 %).
"""

from __future__ import annotations

from benchmark.bytes_laguna import expected_held_hit

RING_SLACK = 16  # models/mimo_v2.ring_len: the window plus one 16-token bucket


def kind(cfg: dict, layer: int) -> dict:
    """One layer's head counts and widths, from the config's own keys."""
    pre = "swa_" if cfg["hybrid_layer_pattern"][layer] else ""
    return {"window": bool(pre), "heads": cfg[pre + "num_attention_heads"],
            "kv_heads": cfg[pre + "num_key_value_heads"], "d": cfg[pre + "head_dim"],
            "dv": cfg[pre + "v_head_dim"]}


def line_values(cfg: dict, layer: int) -> int:
    """Values a position of one layer's cache holds: every KV head's key and value."""
    k = kind(cfg, layer)
    return k["kv_heads"] * (k["d"] + k["dv"])


def full_layers(cfg: dict) -> list[int]:
    return [i for i in range(cfg["num_hidden_layers"]) if not cfg["hybrid_layer_pattern"][i]]


def decode_step_bytes(cfg: dict, live_rows: float, mean_context: float,
                      experts_hit: float | None = None, dtype_bytes: int = 2) -> dict:
    e = cfg["hidden_size"]
    held = cfg["n_routed_experts"]
    published = (cfg.get("expert_share") or {}).get("published", held)
    hit = experts_hit if experts_hit is not None else expected_held_hit(
        held, published, cfg["num_experts_per_tok"], live_rows)
    expert = 3 * e * cfg["moe_intermediate_size"]
    ring = cfg["sliding_window"] + RING_SLACK
    parts = {"attention": 0.0, "dense_mlp": 0.0, "router": 0.0, "experts": 0.0,
             "kv_full": 0.0, "kv_window": 0.0}
    for i in range(cfg["num_hidden_layers"]):
        k = kind(cfg, i)
        parts["attention"] += (k["heads"] * k["d"] * e + k["kv_heads"] * (k["d"] + k["dv"]) * e
                               + k["heads"] * k["dv"] * e)
        if k["window"] and cfg.get("add_swa_attention_sink_bias"):
            parts["attention"] += k["heads"]
        if not cfg["moe_layer_freq"][i]:
            parts["dense_mlp"] += 3 * e * cfg["intermediate_size"]
        else:
            parts["router"] += published * e + published
            parts["experts"] += hit * expert
        if k["window"]:
            parts["kv_window"] += line_values(cfg, i) * live_rows * min(mean_context, ring)
        else:
            parts["kv_full"] += line_values(cfg, i) * live_rows * mean_context
    parts["head"] = cfg["vocab_size"] * e
    parts = {k: v * dtype_bytes for k, v in parts.items()}
    parts["total"] = sum(parts.values())
    parts["experts_hit_per_layer"] = hit
    return parts


def full_attention_bytes(cfg: dict, positions: float, dtype_bytes: int = 2) -> float:
    """Bytes of keys and values behind ``positions`` cache positions of the
    full layers (all of them alike: the count is summed over layers and rows,
    as the engine's ``attn_kv_positions_read`` is)."""
    return positions * line_values(cfg, full_layers(cfg)[0]) * dtype_bytes
