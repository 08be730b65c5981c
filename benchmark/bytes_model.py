"""Bytes one decode step must read from HBM, from shapes alone.

The yardstick of ``model.decode_hbm_share.*``: what the algorithm needs, not
what the program happens to read. Per step and per layer: the attention
projections, the experts that at least one of the live rows routes to (the
expected number of distinct experts under uniform routing; dense models have
one "expert" that is always hit), the router, and every live row's keys and
values up to its current length; once per step the output head. Embedding
rows, norms and activations are left out (under 0.1 %).
"""

from __future__ import annotations


def expected_experts_hit(num_experts: int, top_k: int, rows: float) -> float:
    """Expected distinct experts chosen by ``rows`` tokens picking ``top_k``
    of ``num_experts`` uniformly."""
    if num_experts <= 1:
        return 1.0
    return num_experts * (1.0 - (1.0 - top_k / num_experts) ** max(rows, 0.0))


def decode_step_bytes(cfg: dict, live_rows: float, mean_context: float,
                      dtype_bytes: int = 2) -> dict:
    e, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head_dim = cfg.get("head_dim") or e // heads
    experts = cfg.get("num_local_experts", 1)
    top_k = cfg.get("num_experts_per_tok", 1)
    attn = (heads * head_dim * e) * 2 + (kv_heads * head_dim * e) * 2
    hit = expected_experts_hit(experts, top_k, live_rows)
    ffn = 3 * e * f * hit + (experts * e if experts > 1 else 0)
    kv = 2 * kv_heads * head_dim * live_rows * mean_context
    head = v * e
    parts = {"attention": layers * attn * dtype_bytes, "ffn": layers * ffn * dtype_bytes,
             "kv": layers * kv * dtype_bytes, "head": head * dtype_bytes}
    parts["total"] = sum(parts.values())
    parts["experts_hit"] = hit
    return parts
