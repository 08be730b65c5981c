"""DeepSeek-V3.2-Exp tensor names and shapes as a push holds them: DeepSeek-V2's
latent attention (``self_attn.{q_a_proj, q_a_layernorm, q_b_proj,
kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}``), beside it the
lightning indexer ``self_attn.indexer.{wq_b, wk, k_norm.weight, k_norm.bias,
weights_proj}`` (queries from the query latent, one LayerNormed key a
position, one weight a head); per-expert
``mlp.experts.<i>.{gate,up,down}_proj.weight`` for the experts the
configuration holds (``n_routed_experts`` of the ``expert_share``'s published
count, indexed from its ``first``) under a router ``mlp.gate.weight`` of the
PUBLISHED width with its ``mlp.gate.e_score_correction_bias`` — the loader
folds the experts into stacked ones — and the shared expert as one SwiGLU of
width ``n_shared_experts * moe_intermediate_size``; the first
``first_k_dense_replace`` layers dense. The multi-token-prediction layer is
not written. One layer to a shard; the last shard, which ``--seed`` makes, is
the final norm and the output head."""


def shards(c: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    e, v, h = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    ql, r = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    hi, di = c["index_n_heads"], c["index_head_dim"]
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
            (p + "self_attn.indexer.wq_b.weight", (hi * di, ql)),
            (p + "self_attn.indexer.wk.weight", (di, e)),
            (p + "self_attn.indexer.k_norm.weight", (di,)),
            (p + "self_attn.indexer.k_norm.bias", (di,)),
            (p + "self_attn.indexer.weights_proj.weight", (hi, e)),
            (p + "input_layernorm.weight", (e,)),
            (p + "post_attention_layernorm.weight", (e,)),
        ]
        if i < c["first_k_dense_replace"]:
            d = c["intermediate_size"]
            layer += [(p + "mlp.gate_proj.weight", (d, e)), (p + "mlp.up_proj.weight", (d, e)),
                      (p + "mlp.down_proj.weight", (e, d))]
        else:
            layer += [(p + "mlp.gate.weight", (published, e)),
                      (p + "mlp.gate.e_score_correction_bias", (published,)),
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
