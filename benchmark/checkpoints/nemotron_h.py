"""Nemotron-H tensor names and shapes in the published layout: per layer
``backbone.layers.N.norm.weight`` and ONE mixer by ``hybrid_override_pattern``
— ``M``: ``mixer.in_proj`` (gate, convolved channels and step sizes side by
side: ``2 I + 2 G S + H`` rows), the depthwise ``mixer.conv1d`` (torch's
``[channels, 1, kernel]``) with its bias, the per-head ``dt_bias``, ``A_log``
and ``D``, the gated norm ``mixer.norm`` and ``mixer.out_proj``; ``E``: the
router ``mixer.gate`` at its PUBLISHED width with its choice bias, the latent
projections ``fc1_latent_proj`` / ``fc2_latent_proj``, per-expert
``mixer.experts.<i>.{up,down}_proj.weight`` for the experts the configuration
holds (``n_routed_experts`` of the ``expert_share``'s published count, indexed
from its ``first``; the loader folds them into stacked experts) in the latent
width, and the shared expert on the hidden state; ``*``: ``mixer.{q,k,v,o}_proj``;
``-``: ``mixer.{up,down}_proj``. One layer to a shard; the last shard, which
``--seed`` makes, is the final norm ``norm_f`` and the output head."""


def shards(c: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    e, v = c["hidden_size"], c["vocab_size"]
    heads, groups, n = c["mamba_num_heads"], c["n_groups"], c["ssm_state_size"]
    inner = heads * c["mamba_head_dim"]
    conv = inner + 2 * groups * n
    held = c["n_routed_experts"]
    share = c.get("expert_share") or {}
    published, first = share.get("published", held), share.get("first", 0)
    f, lat = c["moe_intermediate_size"], c.get("moe_latent_size") or e
    fs = c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"]
    q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    out = [[("backbone.embeddings.weight", (v, e))]]
    for i, kind in enumerate(c["hybrid_override_pattern"]):
        p = f"backbone.layers.{i}."
        layer = [(p + "norm.weight", (e,))]
        if kind == "M":
            layer += [(p + "mixer.in_proj.weight", (inner + conv + heads, e)),
                      (p + "mixer.conv1d.weight", (conv, 1, c["conv_kernel"])),
                      (p + "mixer.conv1d.bias", (conv,)),
                      (p + "mixer.dt_bias", (heads,)), (p + "mixer.A_log", (heads,)),
                      (p + "mixer.D", (heads,)), (p + "mixer.norm.weight", (inner,)),
                      (p + "mixer.out_proj.weight", (e, inner))]
        elif kind == "E":
            layer += [(p + "mixer.gate.weight", (published, e)),
                      (p + "mixer.gate.e_score_correction_bias", (published,)),
                      (p + "mixer.shared_experts.up_proj.weight", (fs, e)),
                      (p + "mixer.shared_experts.down_proj.weight", (e, fs))]
            if c.get("moe_latent_size"):
                layer += [(p + "mixer.fc1_latent_proj.weight", (lat, e)),
                          (p + "mixer.fc2_latent_proj.weight", (e, lat))]
            for x in range(first, first + held):
                px = p + f"mixer.experts.{x}."
                layer += [(px + "up_proj.weight", (f, lat)), (px + "down_proj.weight", (lat, f))]
        elif kind == "*":
            layer += [(p + "mixer.q_proj.weight", (q, e)), (p + "mixer.k_proj.weight", (kv, e)),
                      (p + "mixer.v_proj.weight", (kv, e)), (p + "mixer.o_proj.weight", (e, q))]
        else:
            d = c["intermediate_size"]
            layer += [(p + "mixer.up_proj.weight", (d, e)), (p + "mixer.down_proj.weight", (e, d))]
        out.append(layer)
    out.append([("backbone.norm_f.weight", (e,)), ("lm_head.weight", (v, e))])
    return out
