"""Phi-3 tensor names and shapes as published (fused ``qkv_proj`` and
``gate_up_proj``, [out_features, in_features]), four layers to a shard."""


def shards(c: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    e, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    head_dim = e // c["num_attention_heads"]
    qkv = (c["num_attention_heads"] + 2 * c["num_key_value_heads"]) * head_dim
    out = [[("model.embed_tokens.weight", (v, e))]]
    for i in range(c["num_hidden_layers"]):
        if i % 4 == 0:
            out.append([])
        p = f"model.layers.{i}."
        out[-1] += [
            (p + "self_attn.qkv_proj.weight", (qkv, e)),
            (p + "self_attn.o_proj.weight", (e, c["num_attention_heads"] * head_dim)),
            (p + "mlp.gate_up_proj.weight", (2 * f, e)),
            (p + "mlp.down_proj.weight", (e, f)),
            (p + "input_layernorm.weight", (e,)),
            (p + "post_attention_layernorm.weight", (e,)),
        ]
    out.append([("model.norm.weight", (e,)), ("lm_head.weight", (v, e))])
    return out
