"""Shard-layout annotations: tensor-name -> PartitionSpec rules.

The manifest's ``modelx.shard.spec`` annotation carries a JSON list of
``[regex, partition_spec]`` rules (first match wins), where partition_spec is
a list with one entry per tensor dimension: an axis name ("tp"), a list of
axis names, or null for replicated. This is the registry-storable form of a
GSPMD layout — the t5x/maxtext logical-axis-rules idea flattened onto
checkpoint tensor names.

Default rule sets for the model families live here too, so a checkpoint
pushed without annotations still loads sharded.
"""

from __future__ import annotations

import json
import logging
import re
from typing import TYPE_CHECKING, Sequence

if TYPE_CHECKING:  # jax is imported lazily: the rule tables and the
    # encode/decode/infer_family half of this module must stay importable
    # from jax-free contexts (the client-side push annotates manifests
    # with these rules without ever touching a device)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

Rules = list[tuple[str, list]]


def encode_rules(rules: Rules) -> str:
    return json.dumps([[pattern, spec] for pattern, spec in rules])


def decode_rules(payload: str) -> Rules:
    return [(pattern, spec) for pattern, spec in json.loads(payload)]


def spec_for(name: str, rules: Rules) -> PartitionSpec:
    """First-match-wins lookup of a tensor's PartitionSpec."""
    from jax.sharding import PartitionSpec

    for pattern, spec in rules:
        if re.search(pattern, name):
            return PartitionSpec(*[tuple(s) if isinstance(s, list) else s for s in spec])
    return PartitionSpec()


def clean_spec(spec: PartitionSpec, mesh: Mesh) -> PartitionSpec:
    """Drop axis names the mesh doesn't have (e.g. tp rules on a dp-only mesh)."""
    from jax.sharding import PartitionSpec

    cleaned = []
    for entry in spec:
        if entry is None:
            cleaned.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            cleaned.append(kept if kept else None)
        else:
            cleaned.append(entry if entry in mesh.axis_names else None)
    return PartitionSpec(*cleaned)


def sharding_for(name: str, rules: Rules, mesh: Mesh) -> NamedSharding:
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, clean_spec(spec_for(name, rules), mesh))


def cache_sharding(mesh: Mesh, shape: Sequence[int], batch_dim: int = 0,
                   head_dim: int = 2) -> NamedSharding:
    """NamedSharding for one KV-cache leaf: slots over dp, kv heads over
    tp — each axis applied only when the mesh has it AND its size divides
    the dimension (GQA head counts and tiny test models routinely don't
    divide; an indivisible dim replicates rather than erroring). Pass
    ``batch_dim=-1`` for pooled/paged leaves whose leading dim is a global
    page index no axis may split."""
    from jax.sharding import NamedSharding, PartitionSpec

    spec: list = [None] * len(shape)

    def _assign(axis: str, dim: int) -> None:
        size = dict(mesh.shape).get(axis, 1)
        if 0 <= dim < len(shape) and size > 1 and shape[dim] % size == 0:
            spec[dim] = axis

    _assign("dp", batch_dim)
    _assign("tp", head_dim)
    return NamedSharding(mesh, PartitionSpec(*spec))


# -- default rule sets --------------------------------------------------------

# Llama-family (HF safetensors names). Megatron-style: attention q/k/v and
# ffn up/gate column-parallel (shard dim 0, the output features), o_proj and
# down_proj row-parallel (shard dim 1), embeddings sharded over vocab.
LLAMA_RULES: Rules = [
    (r"embed_tokens\.weight$", ["tp", None]),
    (r"lm_head\.weight$", ["tp", None]),
    (r"(q|k|v)_proj\.weight$", ["tp", None]),
    (r"o_proj\.weight$", [None, "tp"]),
    (r"(gate|up)_proj\.weight$", ["tp", None]),
    (r"down_proj\.weight$", [None, "tp"]),
    (r"norm\.weight$", [None]),
    (r".*", []),
]

# Llama with FSDP: every weight additionally shards its non-tp dimension
# over the ``fsdp`` axis (ZeRO-3 / scaling-book "fully sharded" layout);
# XLA all-gathers params just-in-time per layer and reduce-scatters grads.
# The embedding shards VOCAB over both axes (hidden replicated): an
# fsdp-sharded hidden dim would make the lookup's output hidden-sharded,
# and resharding that to the batch-sharded activation layout is an
# involuntary full rematerialization in the SPMD partitioner; the
# vocab-parallel table lowers to masked-gather + psum instead and is just
# as fully sharded.
LLAMA_FSDP_RULES: Rules = [
    (r"embed_tokens\.weight$", [["tp", "fsdp"], None]),
    (r"lm_head\.weight$", ["tp", "fsdp"]),
    (r"(q|k|v)_proj\.weight$", ["tp", "fsdp"]),
    (r"o_proj\.weight$", ["fsdp", "tp"]),
    (r"(gate|up)_proj\.weight$", ["tp", "fsdp"]),
    (r"down_proj\.weight$", ["fsdp", "tp"]),
    (r"norm\.weight$", [None]),
    (r".*", []),
]

# Qwen2 (HF names): llama's layout plus q/k/v input biases, which split
# with their column-parallel weights' output features (dim 0 over tp).
QWEN2_RULES: Rules = [
    (r"(q|k|v)_proj\.bias$", ["tp"]),
    *LLAMA_RULES,
]

# Gemma2 (HF names): llama's projection layout; the extra sandwich norms
# (pre/post_feedforward_layernorm) are 1-D and replicate via the norm rule.
GEMMA2_RULES: Rules = LLAMA_RULES

# Phi-3 (HF names): llama with FUSED qkv_proj / gate_up_proj. The fused
# tensors shard their output rows over tp like their unfused counterparts;
# the forward's in-jit q/k/v (gate/up) slices cross shard boundaries when
# the sub-block sizes don't divide by tp, and GSPMD inserts the reshard —
# correct everywhere, optimal when tp divides each sub-block.
PHI3_RULES: Rules = [
    (r"embed_tokens\.weight$", ["tp", None]),
    (r"lm_head\.weight$", ["tp", None]),
    (r"qkv_proj\.weight$", ["tp", None]),
    (r"o_proj\.weight$", [None, "tp"]),
    (r"gate_up_proj\.weight$", ["tp", None]),
    (r"down_proj\.weight$", [None, "tp"]),
    (r"norm\.weight$", [None]),
    (r".*", []),
]

# GPT-2 (HF names; Conv1D weights are [in, out] so column-parallel = dim 1).
GPT2_RULES: Rules = [
    (r"wte\.weight$", ["tp", None]),
    (r"wpe\.weight$", [None, None]),
    (r"c_attn\.weight$", [None, "tp"]),
    (r"c_attn\.bias$", ["tp"]),
    (r"attn\.c_proj\.weight$", ["tp", None]),
    (r"c_fc\.weight$", [None, "tp"]),
    (r"c_fc\.bias$", ["tp"]),
    (r"mlp\.c_proj\.weight$", ["tp", None]),
    (r".*", []),
]

# BERT (HF names).
BERT_RULES: Rules = [
    (r"word_embeddings\.weight$", ["tp", None]),
    (r"(query|key|value)\.weight$", ["tp", None]),
    (r"(query|key|value)\.bias$", ["tp"]),
    (r"attention\.output\.dense\.weight$", [None, "tp"]),
    (r"intermediate\.dense\.weight$", ["tp", None]),
    (r"intermediate\.dense\.bias$", ["tp"]),
    (r"output\.dense\.weight$", [None, "tp"]),
    (r".*", []),
]

# Mixtral (llama attention + stacked-expert MoE FFN; models/mixtral.py).
# Expert axis over ep, expert ffn features over tp within each expert.
MIXTRAL_RULES: Rules = [
    (r"embed_tokens\.weight$", ["tp", None]),
    (r"lm_head\.weight$", ["tp", None]),
    (r"(q|k|v)_proj\.weight$", ["tp", None]),
    (r"o_proj\.weight$", [None, "tp"]),
    (r"block_sparse_moe\.gate\.weight$", [None, None]),
    (r"experts\.(w1|w3)\.weight$", ["ep", "tp", None]),
    (r"experts\.w2\.weight$", ["ep", None, "tp"]),
    (r"norm\.weight$", [None]),
    (r".*", []),
]

# Laguna (models/laguna.py): per-head gate rows split with their heads, the
# router replicated at its published width, stacked experts over ep with
# their features over tp; the dense MLP and the shared expert fall to the
# llama projection rules below them.
LAGUNA_RULES: Rules = [
    (r"embed_tokens\.weight$", ["tp", None]),
    (r"lm_head\.weight$", ["tp", None]),
    (r"(q|k|v|g)_proj\.weight$", ["tp", None]),
    (r"o_proj\.weight$", [None, "tp"]),
    (r"mlp\.gate\.weight$", [None, None]),
    (r"experts\.(gate|up)_proj\.weight$", ["ep", "tp", None]),
    (r"experts\.down_proj\.weight$", ["ep", None, "tp"]),
    (r"(gate|up)_proj\.weight$", ["tp", None]),
    (r"down_proj\.weight$", [None, "tp"]),
    (r"norm\.weight$", [None]),
    (r".*", []),
]

# MiniCPM-SALA (models/minicpm_sala.py): the full-width output gate splits
# with the heads it gates, like q/k/v; the per-head q/k norms and the output
# norm are replicated; the MLP falls to the llama projection rules.
MINICPM_SALA_RULES: Rules = [
    (r"embed_tokens\.weight$", ["tp", None]),
    (r"lm_head\.weight$", ["tp", None]),
    (r"(q|k|v)_proj\.weight$", ["tp", None]),
    (r"o_gate\.weight$", ["tp", None]),
    (r"o_proj\.weight$", [None, "tp"]),
    (r"(gate|up)_proj\.weight$", ["tp", None]),
    (r"down_proj\.weight$", [None, "tp"]),
    (r"norm\.weight$", [None]),
    (r".*", []),
]

# DeepSeek-V2 (models/deepseek_v2.py): the low-rank pairs' down-projections
# (q_a, kv_a) and their norms replicated — a latent line has one "KV head" —
# the up-projections and the output split by head, the router replicated at
# its published width, stacked experts over ep with their features over tp;
# the dense MLP and the shared experts (``mlp.shared_experts.*``, which the
# stacked experts' patterns must not catch) fall to the llama projection rules.
DEEPSEEK_V2_RULES: Rules = [
    (r"embed_tokens\.weight$", ["tp", None]),
    (r"lm_head\.weight$", ["tp", None]),
    (r"(q_a_proj|kv_a_proj_with_mqa)\.weight$", [None, None]),
    (r"(q_b|kv_b)_proj\.weight$", ["tp", None]),
    (r"o_proj\.weight$", [None, "tp"]),
    (r"indexer\.(wq_b|wk|weights_proj)\.weight$", [None, None]),
    (r"indexer\.k_norm\.(weight|bias)$", [None]),
    (r"mlp\.gate\.weight$", [None, None]),
    (r"mlp\.gate\.e_score_correction_bias$", [None]),
    (r"mlp\.experts\.(gate|up)_proj\.weight$", ["ep", "tp", None]),
    (r"mlp\.experts\.down_proj\.weight$", ["ep", None, "tp"]),
    (r"(gate|up)_proj\.weight$", ["tp", None]),
    (r"down_proj\.weight$", [None, "tp"]),
    (r"norm\.weight$", [None]),
    (r".*", []),
]

# MiMo-V2-Flash (models/mimo_v2.py): attention by head (keys of 192 and values
# of 128 split with their heads), the sinks with the query heads they belong
# to; the router at its published width and its choice bias replicated; the
# experts as Laguna's.
MIMO_V2_RULES: Rules = [
    (r"embed_tokens\.weight$", ["tp", None]),
    (r"lm_head\.weight$", ["tp", None]),
    (r"(q|k|v)_proj\.weight$", ["tp", None]),
    (r"o_proj\.weight$", [None, "tp"]),
    (r"attention_sink_bias$", ["tp"]),
    (r"mlp\.gate\.weight$", [None, None]),
    (r"mlp\.gate\.e_score_correction_bias$", [None]),
    (r"experts\.(gate|up)_proj\.weight$", ["ep", "tp", None]),
    (r"experts\.down_proj\.weight$", ["ep", None, "tp"]),
    (r"(gate|up)_proj\.weight$", ["tp", None]),
    (r"down_proj\.weight$", [None, "tp"]),
    (r"norm\.weight$", [None]),
    (r".*", []),
]

# Nemotron-H (models/nemotron_h.py): a Mamba layer's fused input projection
# is three runs of rows (gate, convolved channels, step sizes) that a split of
# its rows would cut across, so the Mamba projections, the convolution and the
# per-head vectors are replicated (the deployment runs them data-parallel);
# attention by head; the router at its published width, its choice bias and
# the latent projections replicated; stacked experts over ep with their
# features over tp; the shared expert and a dense layer's MLP (which the
# stacked experts' patterns must not catch) by feature.
NEMOTRON_H_RULES: Rules = [
    (r"embeddings\.weight$", ["tp", None]),
    (r"lm_head\.weight$", ["tp", None]),
    (r"mixer\.(in_proj|out_proj)\.weight$", [None, None]),
    (r"mixer\.(q|k|v)_proj\.weight$", ["tp", None]),
    (r"mixer\.o_proj\.weight$", [None, "tp"]),
    (r"mixer\.gate\.weight$", [None, None]),
    (r"fc[12]_latent_proj\.weight$", [None, None]),
    (r"mixer\.experts\.up_proj\.weight$", ["ep", "tp", None]),
    (r"mixer\.experts\.down_proj\.weight$", ["ep", None, "tp"]),
    (r"up_proj\.weight$", ["tp", None]),
    (r"down_proj\.weight$", [None, "tp"]),
    (r"norm(_f)?\.weight$", [None]),
    (r".*", []),
]

DEFAULT_RULES: dict[str, Rules] = {
    "llama": LLAMA_RULES,
    "qwen2": QWEN2_RULES,
    "gemma2": GEMMA2_RULES,
    "phi3": PHI3_RULES,
    "gpt2": GPT2_RULES,
    "bert": BERT_RULES,
    "mixtral": MIXTRAL_RULES,
    "laguna": LAGUNA_RULES,
    "minicpm_sala": MINICPM_SALA_RULES,
    "deepseek_v2": DEEPSEEK_V2_RULES,
    "nemotron_h": NEMOTRON_H_RULES,
    "mimo_v2": MIMO_V2_RULES,
}


def rules_for_family(family: str) -> Rules:
    return DEFAULT_RULES.get(family, [(r".*", [])])


logger = logging.getLogger("modelx.dl")


def infer_family(tensor_names: Sequence[str]) -> str:
    names = list(tensor_names)
    joined = "\n".join(names)
    if "block_sparse_moe" in joined:
        return "mixtral"
    if "mixer.in_proj" in joined or "mixer.experts" in joined:
        return "nemotron_h"  # one mixer a layer: state-space, experts or attention
    if "self_attn.kv_a_proj_with_mqa" in joined:
        return "deepseek_v2"  # one compressed key-value line a position (latent attention)
    if "self_attn.attention_sink_bias" in joined:
        return "mimo_v2"  # a learned sink a query head on the window layers
    if "self_attn.g_proj" in joined:
        return "laguna"  # per-head output gate beside q/k/v/o
    if "self_attn.o_gate" in joined:
        return "minicpm_sala"  # full-width output gate, q/k norms, no rope on some layers
    if "pre_feedforward_layernorm" in joined:
        # llama layout + sandwich norms: gemma2 — but gemma3 ALSO carries
        # them, adding per-head q_norm/k_norm attention norms (and a
        # different rope/window schedule) that gemma2's math doesn't have;
        # running gemma3 through the gemma2 branch would decode garbage
        # while the extra norm tensors load silently replicated. Fail
        # loudly instead of matching (families.detect raises on "").
        if "q_norm" in joined or "k_norm" in joined:
            logger.warning(
                "checkpoint has gemma2-style sandwich norms AND q_norm/"
                "k_norm attention-norm tensors (gemma3?): refusing the "
                "gemma2 family match — these layer tensors are not part "
                "of any supported architecture"
            )
            return ""
        return "gemma2"
    if "qkv_proj" in joined:
        return "phi3"  # llama layout with fused qkv/gate_up projections
    if "q_proj.bias" in joined:
        return "qwen2"  # llama layout + qkv biases
    if "q_proj" in joined or "gate_proj" in joined:
        return "llama"
    if "c_attn" in joined or "wte" in joined:
        return "gpt2"
    if "word_embeddings" in joined:
        return "bert"
    return ""
