"""Bytes one decode step of a Nemotron-H configuration must move through HBM,
from shapes alone: the yardstick of ``model.decode_hbm_share.agent``.

Once a step, every matrix of every layer held and the output head (the
embedding's rows, the norms and the activations are left out: under 0.1 %) —
of an expert layer's held experts those the step READ: ``experts_read`` a
layer, from the program's own counter (every held one where the routed sum is
the einsums, the hit ones where a kernel skips the others); where no counter
is given, the expected number of distinct held experts hit when each live row
picks ``num_experts_per_tok`` of the published experts uniformly. Per live row
and ``M`` layer the row's state, ``[heads, head_dim, state]`` float32, READ AND
WRITTEN — the recurrence replaces all of it every step — and its convolution
tail, ``conv_kernel - 1`` lines of ``I + 2 G S`` values, read and written. Per
live row and ``*`` layer its keys and values up to its context.
"""

from __future__ import annotations

from benchmark.bytes_laguna import expected_held_hit


def decode_step_bytes(cfg: dict, live_rows: float, mean_context: float,
                      experts_read: float | None = None, dtype_bytes: int = 2) -> dict:
    e = cfg["hidden_size"]
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    inner = heads * hd
    conv = inner + 2 * groups * n
    held = cfg["n_routed_experts"]
    published = (cfg.get("expert_share") or {}).get("published", held)
    if experts_read is None:
        experts_read = expected_held_hit(held, published, cfg["num_experts_per_tok"], live_rows)
    lat = cfg.get("moe_latent_size") or e
    q, kv = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
    parts = {"mamba_weights": 0.0, "state": 0.0, "conv_tail": 0.0, "router": 0.0,
             "latent_projections": 0.0, "shared_expert": 0.0, "experts": 0.0,
             "attention_weights": 0.0, "kv": 0.0, "dense_mlp": 0.0}
    for kind in cfg["hybrid_override_pattern"]:
        if kind == "M":
            parts["mamba_weights"] += ((inner + conv + heads) * e + e * inner + conv * (k + 1)
                                       + 3 * heads + inner) * dtype_bytes
            parts["state"] += 2 * live_rows * heads * hd * n * 4  # float32, read and written
            parts["conv_tail"] += 2 * live_rows * (k - 1) * conv * dtype_bytes
        elif kind == "E":
            parts["router"] += (published * e + published) * dtype_bytes
            if cfg.get("moe_latent_size"):
                parts["latent_projections"] += 2 * lat * e * dtype_bytes
            parts["shared_expert"] += (2 * e * cfg["n_shared_experts"]
                                       * cfg["moe_shared_expert_intermediate_size"] * dtype_bytes)
            parts["experts"] += experts_read * 2 * lat * cfg["moe_intermediate_size"] * dtype_bytes
        elif kind == "*":
            parts["attention_weights"] += (2 * q * e + 2 * kv * e) * dtype_bytes
            parts["kv"] += 2 * kv * live_rows * mean_context * dtype_bytes
        else:
            parts["dense_mlp"] += 2 * e * cfg["intermediate_size"] * dtype_bytes
    parts["head"] = cfg["vocab_size"] * e * dtype_bytes
    parts["total"] = sum(parts.values())
    parts["experts_read_per_layer"] = experts_read
    return parts
