"""Bytes one decode step of a MiniCPM-SALA configuration must move through
HBM, from shapes alone: the yardstick of ``model.decode_hbm_share.longctx``.

What the algorithm needs, not what the program happens to read. Once a step,
every matrix of every layer held and the output head (the embedding's rows,
the norms and the activations are left out: under 0.1 %). Per live row and
``lightning-attn`` layer the row's state, ``[heads, d, d]`` float32, READ AND
WRITTEN: the recurrence replaces all of it every step. Per live row and
``minicpm4`` layer: the compressed keys the row's context holds (one of
``num_key_value_heads x head_dim`` every ``kernel_stride`` positions: the
selection scores all of them), and the keys and values of the positions
attended — below ``dense_len`` the whole context, from there on the selected
blocks, ``min(topk x block_size, the context's blocks)`` positions.
"""

from __future__ import annotations


def positions_attended(cfg: dict, context: float) -> float:
    """Positions whose keys and values a ``minicpm4`` layer reads for one
    query with ``context`` positions behind it (its own included)."""
    sc = cfg["sparse_config"]
    if context < sc["dense_len"]:
        return context
    block = sc["block_size"]
    return min(sc["topk"] * block, -(-context // block) * block)


def decode_step_bytes(cfg: dict, live_rows: float, mean_context: float,
                      positions_read: float | None = None, dtype_bytes: int = 2) -> dict:
    """``positions_read``: positions attended a row a sparse layer, where the
    program's counters give it; else :func:`positions_attended` of the mean
    context."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    if positions_read is None:
        positions_read = positions_attended(cfg, mean_context)
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    parts = {"attention_weights": 0.0, "mlp_weights": 0.0, "state": 0.0, "kv_attended": 0.0,
             "compressed_keys": 0.0}
    for mixer in cfg["mixer_types"]:
        parts["mlp_weights"] += 3 * e * f * dtype_bytes
        if mixer == "lightning-attn":
            q, kw = lh * ld, cfg["lightning_nkv"] * ld
            parts["state"] += 2 * live_rows * lh * ld * ld * 4  # float32, read and written
        else:
            q, kw = cfg["num_attention_heads"] * cfg["head_dim"], kv
            parts["kv_attended"] += 2 * live_rows * positions_read * kv * dtype_bytes
            parts["compressed_keys"] += (live_rows * mean_context
                                         / cfg["sparse_config"]["kernel_stride"]
                                         * kv * dtype_bytes)
        parts["attention_weights"] += (3 * q * e + 2 * kw * e) * dtype_bytes  # q, o, gate; k, v
    parts["head"] = cfg["vocab_size"] * e * dtype_bytes
    parts["total"] = sum(parts.values())
    return parts
