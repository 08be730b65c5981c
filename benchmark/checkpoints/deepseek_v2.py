"""DeepSeek-V2 tensor names and shapes as a push holds them: the low-rank
query pair ``self_attn.{q_a_proj, q_a_layernorm, q_b_proj}``, the compressed
key-value line ``kv_a_proj_with_mqa`` (``kv_lora_rank + qk_rope_head_dim``
rows), its norm and its up-projection ``kv_b_proj``, ``o_proj``; per-expert
``mlp.experts.<i>.{gate,up,down}_proj.weight`` for the experts the
configuration holds (``n_routed_experts`` of the ``expert_share``'s published
count, indexed from its ``first``), under a router ``mlp.gate`` of the
PUBLISHED width — which the loader folds into stacked experts — and the shared
experts as one SwiGLU of width ``n_shared_experts * moe_intermediate_size``;
the first ``first_k_dense_replace`` layers dense. One layer to a shard; the
last shard, which ``--seed`` makes, is the final norm and the output head."""


def shards(c: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    e, v, h = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    ql, r = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    f, fs = c["moe_intermediate_size"], c["n_shared_experts"] * c["moe_intermediate_size"]
    held = c["n_routed_experts"]
    share = c.get("expert_share") or {}
    published, first = share.get("published", held), share.get("first", 0)
    out = [[("model.embed_tokens.weight", (v, e))]]
    for i in range(c["num_hidden_layers"]):
        p = f"model.layers.{i}."
        layer = [
            (p + "self_attn.q_a_proj.weight", (ql, e)),
            (p + "self_attn.q_a_layernorm.weight", (ql,)),
            (p + "self_attn.q_b_proj.weight", (h * (dn + dr), ql)),
            (p + "self_attn.kv_a_proj_with_mqa.weight", (r + dr, e)),
            (p + "self_attn.kv_a_layernorm.weight", (r,)),
            (p + "self_attn.kv_b_proj.weight", (h * (dn + dv), r)),
            (p + "self_attn.o_proj.weight", (e, h * dv)),
            (p + "input_layernorm.weight", (e,)),
            (p + "post_attention_layernorm.weight", (e,)),
        ]
        if i < c["first_k_dense_replace"]:
            d = c["intermediate_size"]
            layer += [(p + "mlp.gate_proj.weight", (d, e)), (p + "mlp.up_proj.weight", (d, e)),
                      (p + "mlp.down_proj.weight", (e, d))]
        else:
            layer += [(p + "mlp.gate.weight", (published, e)),
                      (p + "mlp.shared_experts.gate_proj.weight", (fs, e)),
                      (p + "mlp.shared_experts.up_proj.weight", (fs, e)),
                      (p + "mlp.shared_experts.down_proj.weight", (e, fs))]
            for x in range(first, first + held):
                px = p + f"mlp.experts.{x}."
                layer += [(px + "gate_proj.weight", (f, e)), (px + "up_proj.weight", (f, e)),
                          (px + "down_proj.weight", (e, f))]
        out.append(layer)
    out.append([("model.norm.weight", (e,)), ("lm_head.weight", (v, e))])
    return out
