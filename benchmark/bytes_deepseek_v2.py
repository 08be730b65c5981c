"""Bytes one decode step of a DeepSeek-V2 configuration must read from HBM,
and the operations and bytes of its latent attention, from shapes alone: the
yardsticks of ``model.decode_hbm_share.longdoc`` and
``mla.attn_roofline_share.longdoc``.

What the algorithm needs, not what the program happens to read. Per layer the
attention matrices (the low-rank query pair, the compressed key-value
projection, its up-projection — which the absorbed form reads whole, as
``W_uk`` and ``W_uv`` — and the output); on a dense layer the MLP; on a sparse
layer the router at its published width, the shared experts, and the held
experts that at least one live row routes to — the expected number of distinct
ones when each row picks ``num_experts_per_tok`` of the published experts
uniformly (group-limited routing keeps that marginal); every live row's LATENT
LINES up to its context: ``kv_lora_rank + qk_rope_head_dim`` values a position
a layer, read once (the zeros that pad a cached line to whole lane tiles, and
the positions a block reads past a row's context, are the program's, not the
algorithm's). Once per step the output head over the vocabulary held.
Embedding rows, norms and activations are left out (under 0.1 %).
"""

from __future__ import annotations

from benchmark.bytes_laguna import expected_held_hit


def line_values(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_step_bytes(cfg: dict, live_rows: float, mean_context: float,
                      dtype_bytes: int = 2) -> dict:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    held = cfg["n_routed_experts"]
    published = (cfg.get("expert_share") or {}).get("published", held)
    hit = expected_held_hit(held, published, cfg["num_experts_per_tok"], live_rows)
    expert = 3 * e * cfg["moe_intermediate_size"]
    attention = (cfg["q_lora_rank"] * e
                 + h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) * cfg["q_lora_rank"]
                 + line_values(cfg) * e
                 + h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) * cfg["kv_lora_rank"]
                 + h * cfg["v_head_dim"] * e)
    parts = {"attention": 0.0, "dense_mlp": 0.0, "router": 0.0, "shared_experts": 0.0,
             "experts": 0.0, "latent_lines": 0.0}
    for i in range(cfg["num_hidden_layers"]):
        parts["attention"] += attention
        if i < cfg["first_k_dense_replace"]:
            parts["dense_mlp"] += 3 * e * cfg["intermediate_size"]
        else:
            parts["router"] += published * e
            parts["shared_experts"] += cfg["n_shared_experts"] * expert
            parts["experts"] += hit * expert
        parts["latent_lines"] += line_values(cfg) * live_rows * mean_context
    parts["head"] = cfg["vocab_size"] * e
    parts = {k: v * dtype_bytes for k, v in parts.items()}
    parts["total"] = sum(parts.values())
    parts["experts_hit_per_layer"] = hit
    return parts


def latent_attention_step(cfg: dict, live_rows: float, mean_context: float,
                          dtype_bytes: int = 2) -> dict:
    """Operations and bytes of ONE layer's latent attention in ONE decode step
    in the absorbed form: a row-position costs a score over the line
    (``2 H (rank + rope)``) and a weighted sum of its latent part
    (``2 H rank``), and its line's bytes once."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    pairs = live_rows * mean_context
    return {"flops": 2.0 * h * (line_values(cfg) + r) * pairs,
            "bytes": float(line_values(cfg) * dtype_bytes) * pairs}
