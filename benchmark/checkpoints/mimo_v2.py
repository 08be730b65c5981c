"""MiMo-V2-Flash tensor names and shapes as a push holds them: per-expert
``mlp.experts.<i>.{gate,up,down}_proj.weight`` for the experts the
configuration holds (``n_routed_experts`` of the ``expert_share``'s published
count, indexed from its ``first``) under a router and a choice bias of the
PUBLISHED width — which the loader folds into stacked experts. A layer is a
window layer where ``hybrid_layer_pattern`` says 1 (the ``swa_`` head counts
and widths, and one sink a query head) and a full layer where 0; keys are
``head_dim`` wide and values ``v_head_dim``; the FFN is dense where
``moe_layer_freq`` says 0. One layer to a shard; the last shard, which
``--seed`` makes, is the final norm and the output head."""


def shards(c: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    e, v, f = c["hidden_size"], c["vocab_size"], c["moe_intermediate_size"]
    held = c["n_routed_experts"]
    share = c.get("expert_share") or {}
    published, first = share.get("published", held), share.get("first", 0)
    out = [[("model.embed_tokens.weight", (v, e))]]
    for i in range(c["num_hidden_layers"]):
        p, pre = f"model.layers.{i}.", "swa_" if c["hybrid_layer_pattern"][i] else ""
        h, kv = c[pre + "num_attention_heads"], c[pre + "num_key_value_heads"]
        d, dv = c[pre + "head_dim"], c[pre + "v_head_dim"]
        layer = [
            (p + "self_attn.q_proj.weight", (h * d, e)), (p + "self_attn.k_proj.weight", (kv * d, e)),
            (p + "self_attn.v_proj.weight", (kv * dv, e)),
            (p + "self_attn.o_proj.weight", (e, h * dv)),
            (p + "input_layernorm.weight", (e,)),
            (p + "post_attention_layernorm.weight", (e,)),
        ]
        if c.get("add_swa_attention_sink_bias" if pre else "add_full_attention_sink_bias"):
            layer.append((p + "self_attn.attention_sink_bias", (h,)))
        if not c["moe_layer_freq"][i]:
            w = c["intermediate_size"]
            layer += [(p + "mlp.gate_proj.weight", (w, e)), (p + "mlp.up_proj.weight", (w, e)),
                      (p + "mlp.down_proj.weight", (e, w))]
        else:
            layer += [(p + "mlp.gate.weight", (published, e)),
                      (p + "mlp.gate.e_score_correction_bias", (published,))]
            for x in range(first, first + held):
                px = p + f"mlp.experts.{x}."
                layer += [(px + "gate_proj.weight", (f, e)), (px + "up_proj.weight", (f, e)),
                          (px + "down_proj.weight", (e, f))]
        out.append(layer)
    out.append([("model.norm.weight", (e,)), ("lm_head.weight", (v, e))])
    return out
