"""Mixtral tensor names and shapes in the stock HF layout
(``block_sparse_moe.experts.<i>.w{1,2,3}.weight``), which the loader folds
into stacked experts — what a user's push holds. One layer to a shard."""


def shards(c: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    e, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    head_dim = c.get("head_dim") or e // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * head_dim, c["num_key_value_heads"] * head_dim
    out = [[("model.embed_tokens.weight", (v, e))]]
    for i in range(c["num_hidden_layers"]):
        p = f"model.layers.{i}."
        layer = [
            (p + "self_attn.q_proj.weight", (q, e)), (p + "self_attn.k_proj.weight", (kv, e)),
            (p + "self_attn.v_proj.weight", (kv, e)), (p + "self_attn.o_proj.weight", (e, q)),
            (p + "block_sparse_moe.gate.weight", (c["num_local_experts"], e)),
            (p + "input_layernorm.weight", (e,)),
            (p + "post_attention_layernorm.weight", (e,)),
        ]
        for x in range(c["num_local_experts"]):
            px = p + f"block_sparse_moe.experts.{x}."
            layer += [(px + "w1.weight", (f, e)), (px + "w2.weight", (e, f)),
                      (px + "w3.weight", (f, e))]
        out.append(layer)
    out.append([("model.norm.weight", (e,)), ("lm_head.weight", (v, e))])
    return out
