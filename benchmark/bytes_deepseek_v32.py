"""Bytes one decode step of a DeepSeek-V3.2-Exp configuration must read from
HBM, from shapes alone: the yardstick of ``model.decode_hbm_share.sparsedoc``.

What the algorithm needs, not what the program happens to read. Once a step,
per layer, the attention matrices (the low-rank query pair, the compressed
key-value projection, its up-projection, the output) and the indexer's (its
query projection from the query latent, its key projection with the LayerNorm,
its per-head weights); on a dense layer the MLP; on a sparse layer the router
at its published width with its choice bias, the shared expert, and of the
held experts those the step READ: ``experts_read`` a layer from the program's
own counter (every held one where the routed sum is the einsums, the hit ones
where the kernel skips the others) or, where none is given, the expected number
of distinct held experts hit when each live row picks ``num_experts_per_tok``
of the published experts uniformly. Per live row and layer, the INDEX KEYS up
to its context (``index_head_dim`` values a position: the indexer scores every
position it holds) and the LATENT LINES the selection keeps: ``min(context,
index_topk)`` lines of ``kv_lora_rank + qk_rope_head_dim`` values, read once
as key and value (``lines_selected`` a row where the counter is given). The
zeros that pad a cached line to whole lane tiles, the copy a gather makes and
reads again, and positions read past a row's context are the program's, not
the algorithm's. Once per step the output head over the vocabulary held.
Embedding rows, norms and activations are left out (under 0.1 %).
"""

from __future__ import annotations

from benchmark.bytes_deepseek_v2 import line_values
from benchmark.bytes_laguna import expected_held_hit


def decode_step_bytes(cfg: dict, live_rows: float, mean_context: float,
                      experts_read: float | None = None, lines_selected: float | None = None,
                      dtype_bytes: int = 2) -> dict:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    held = cfg["n_routed_experts"]
    published = (cfg.get("expert_share") or {}).get("published", held)
    if experts_read is None:
        experts_read = expected_held_hit(held, published, cfg["num_experts_per_tok"], live_rows)
    if lines_selected is None:
        lines_selected = min(mean_context, cfg["index_topk"])
    expert = 3 * e * cfg["moe_intermediate_size"]
    attention = (cfg["q_lora_rank"] * e
                 + h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) * cfg["q_lora_rank"]
                 + line_values(cfg) * e
                 + h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) * cfg["kv_lora_rank"]
                 + h * cfg["v_head_dim"] * e)
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    indexer = hi * di * cfg["q_lora_rank"] + di * e + 2 * di + hi * e
    parts = {"attention": 0.0, "indexer": 0.0, "dense_mlp": 0.0, "router": 0.0,
             "shared_experts": 0.0, "experts": 0.0, "index_keys": 0.0, "latent_lines": 0.0}
    for i in range(cfg["num_hidden_layers"]):
        parts["attention"] += attention
        parts["indexer"] += indexer
        if i < cfg["first_k_dense_replace"]:
            parts["dense_mlp"] += 3 * e * cfg["intermediate_size"]
        else:
            parts["router"] += published * e + published
            parts["shared_experts"] += cfg["n_shared_experts"] * expert
            parts["experts"] += experts_read * expert
        parts["index_keys"] += di * live_rows * mean_context
        parts["latent_lines"] += line_values(cfg) * live_rows * lines_selected
    parts["head"] = cfg["vocab_size"] * e
    parts = {k: v * dtype_bytes for k, v in parts.items()}
    parts["total"] = sum(parts.values())
    parts["experts_read_per_layer"] = experts_read
    return parts
