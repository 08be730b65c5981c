"""MiniCPM-SALA tensor names and shapes in the MiniCPM lineage's layout: per
layer ``self_attn.{q,k,v,o}_proj``, the full-width output gate ``o_gate``, the
per-head ``q_norm`` / ``k_norm``, on a ``lightning-attn`` layer the output norm
``self_attn.norm``; ``mlp.{gate,up,down}_proj``. A ``minicpm4`` layer's k and v
are ``num_key_value_heads`` wide, a lightning layer's ``lightning_nkv``. The
layers held keep their PUBLISHED indices (``layer_share.first`` onwards). One
layer to a shard; the last shard, which ``--seed`` makes, is the final norm and
the output head."""


def shards(c: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    e, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    first = (c.get("layer_share") or {}).get("first", 0)
    out = [[("model.embed_tokens.weight", (v, e))]]
    for i in range(c["num_hidden_layers"]):
        p = f"model.layers.{first + i}."
        lightning = c["mixer_types"][i] == "lightning-attn"
        if lightning:
            hd = c["lightning_head_dim"]
            q, kv = c["lightning_nh"] * hd, c["lightning_nkv"] * hd
        else:
            hd = c["head_dim"]
            q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
        layer = [
            (p + "self_attn.q_proj.weight", (q, e)), (p + "self_attn.k_proj.weight", (kv, e)),
            (p + "self_attn.v_proj.weight", (kv, e)), (p + "self_attn.o_proj.weight", (e, q)),
            (p + "self_attn.o_gate.weight", (q, e)),
            (p + "self_attn.q_norm.weight", (hd,)), (p + "self_attn.k_norm.weight", (hd,)),
            (p + "input_layernorm.weight", (e,)),
            (p + "post_attention_layernorm.weight", (e,)),
            (p + "mlp.gate_proj.weight", (f, e)), (p + "mlp.up_proj.weight", (f, e)),
            (p + "mlp.down_proj.weight", (e, f)),
        ]
        if lightning:
            layer.append((p + "self_attn.norm.weight", (q,)))
        out.append(layer)
    out.append([("model.norm.weight", (e,)), ("lm_head.weight", (v, e))])
    return out
