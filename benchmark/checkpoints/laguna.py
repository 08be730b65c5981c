"""Laguna tensor names and shapes as a push holds them: per-expert
``mlp.experts.<i>.{gate,up,down}_proj.weight`` for the experts the
configuration holds (``num_experts`` of the ``expert_share``'s published
count, indexed from its ``first``), under a router of the PUBLISHED width —
which the loader folds into stacked experts. Per layer its own head count
(``num_attention_heads_per_layer``) and a per-head gate ``g_proj``; the
leading layers dense (``mlp_layer_types``). One layer to a shard; the last
shard, which ``--seed`` makes, is the final norm and the output head."""


def shards(c: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    e, v, hd = c["hidden_size"], c["vocab_size"], c["head_dim"]
    kv = c["num_key_value_heads"] * hd
    f, fs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    held = c["num_experts"]
    share = c.get("expert_share") or {}
    published, first = share.get("published", held), share.get("first", 0)
    out = [[("model.embed_tokens.weight", (v, e))]]
    for i in range(c["num_hidden_layers"]):
        p, h = f"model.layers.{i}.", c["num_attention_heads_per_layer"][i]
        layer = [
            (p + "self_attn.q_proj.weight", (h * hd, e)), (p + "self_attn.k_proj.weight", (kv, e)),
            (p + "self_attn.v_proj.weight", (kv, e)), (p + "self_attn.g_proj.weight", (h, e)),
            (p + "self_attn.o_proj.weight", (e, h * hd)),
            (p + "input_layernorm.weight", (e,)),
            (p + "post_attention_layernorm.weight", (e,)),
        ]
        if c["mlp_layer_types"][i] == "dense":
            d = c["intermediate_size"]
            layer += [(p + "mlp.gate_proj.weight", (d, e)), (p + "mlp.up_proj.weight", (d, e)),
                      (p + "mlp.down_proj.weight", (e, d))]
        else:
            layer += [(p + "mlp.gate.weight", (published, e)),
                      (p + "mlp.shared_expert.gate_proj.weight", (fs, e)),
                      (p + "mlp.shared_expert.up_proj.weight", (fs, e)),
                      (p + "mlp.shared_expert.down_proj.weight", (e, fs))]
            for x in range(first, first + held):
                px = p + f"mlp.experts.{x}."
                layer += [(px + "gate_proj.weight", (f, e)), (px + "up_proj.weight", (f, e)),
                          (px + "down_proj.weight", (e, f))]
        out.append(layer)
    out.append([("model.norm.weight", (e,)), ("lm_head.weight", (v, e))])
    return out
