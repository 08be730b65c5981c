"""Model-family registry for the serving path: checkpoint tensor names ->
(config inference, partition rules, forward/generate adapters).

The reference stores models without understanding them; the TPU serving
sidecar has to *execute* them, so each supported family contributes:

- ``infer_config(params)``: recover the architecture from tensor shapes
  (no config.json required — the checkpoint is self-describing);
- ``rules``: GSPMD partition rules (dl/sharding.py);
- ``forward(params, tokens, cfg, mesh)`` -> logits/features;
- ``generate`` (causal families only).

``detect(params)`` picks the family from tensor names, mirroring
dl/sharding.infer_family but over loaded params.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Any, Callable

import jax
import numpy as np

from modelx_tpu.dl.sharding import (
    BERT_RULES,
    DEEPSEEK_V2_RULES,
    GEMMA2_RULES,
    GPT2_RULES,
    LAGUNA_RULES,
    MINICPM_SALA_RULES,
    PHI3_RULES,
    LLAMA_RULES,
    MIXTRAL_RULES,
    NEMOTRON_H_RULES,
    QWEN2_RULES,
    Rules,
    infer_family,
)


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    rules: Rules
    infer_config: Callable[[dict], Any]
    forward: Callable[..., jax.Array]  # (params, tokens, cfg, mesh) -> logits
    generate: Callable[..., jax.Array] | None = None  # causal LMs only
    # ragged-batch decode (params, prompt, row_lens, cfg, mesh, max_new_tokens)
    # -> generated [B, max_new]; cached-decode families only — the serving
    # batcher uses it to coalesce concurrent generate requests
    generate_ragged: Callable[..., jax.Array] | None = None
    # (cfg, mesh) -> (forward-with-cache, init_kv_cache) for streaming decode
    # (models/decode.ChunkedDecoder); cached-decode families only
    decode_fns: Callable[..., tuple] | None = None
    # (cfg, mesh) -> forward over PAGED kv pools (kv_cache = page pools +
    # a block table; ops/paged_attention.py reads them in place) — the
    # continuous engine's fast paged chunk path; None = the engine falls
    # back to its generic dense-gather chunk for this family
    paged_decode_fns: Callable[..., Callable] | None = None
    # (sidecar config.json dict, abstract params) -> cfg, for a family whose
    # architecture leaves no trace in tensor shapes (layer kinds, per-layer
    # head counts, rope parameters, top-k, the experts held of those
    # published): ``infer_config`` then only says so
    config_from_sidecar: Callable[[dict, dict], Any] | None = None
    # (cfg, mesh) -> {"fwd": forward over a cache PER LAYER KIND,
    # "init_state": (slots, max_len) -> that state, "kinds": leaf -> "full" /
    # "window" / "counter", "counters", "gauges"} — the continuous engine
    # then keeps full layers [slots, max_len], window layers as rings, a
    # sparse layer's index of compressed keys ("index"), a linear-attention
    # layer's state ("state", no position axis) and a latent-attention layer's
    # one compressed line a position ("latent") (dl/kv_layout.LayerKindKV);
    # None = every layer's cache is alike
    layer_kind_decode_fns: Callable[..., dict] | None = None


def _shape(params: dict, name: str) -> tuple[int, ...]:
    return tuple(params[name].shape)


def _act_dtype(params: dict, name: str):
    """Activation dtype for a checkpoint: the (float) dtype of its embedding
    weight. A config whose dtype disagrees with the params breaks the cached
    decode path — the KV cache allocates cfg.dtype while k/v arrive in the
    params' compute dtype, and dynamic_update_slice rejects the mismatch.
    Non-float storage (e.g. int8 weight-only quant) computes in bfloat16."""
    import jax.numpy as jnp

    dt = params[name].dtype
    # jnp.issubdtype understands the extended float types (bfloat16 etc.)
    return dt if jnp.issubdtype(dt, jnp.floating) else jnp.bfloat16


# -- llama --------------------------------------------------------------------


def infer_llama_config(params: dict):
    """Recover the architecture from checkpoint tensor shapes."""
    from modelx_tpu.models import llama

    vocab, hidden = _shape(params, "model.embed_tokens.weight")
    layers = 0
    while f"model.layers.{layers}.self_attn.q_proj.weight" in params:
        layers += 1
    q = _shape(params, "model.layers.0.self_attn.q_proj.weight")[0]
    kv = _shape(params, "model.layers.0.self_attn.k_proj.weight")[0]
    inter = _shape(params, "model.layers.0.mlp.gate_proj.weight")[0]
    # head_dim heuristics: big models use 128 (llama/mistral/qwen2-7B+)
    # unless that would leave fewer than 2 kv heads. kv=128 is genuinely
    # ambiguous — MQA-128 (32 q heads x 1 kv head, e.g. q=4096) vs
    # qwen2-0.5B (14 x 64, 2 kv heads, q=896) — so 128 also wins when the
    # checkpoint is clearly big (q//128 >= 8, the pre-qwen2 rule), which
    # keeps MQA llama checkpoints correct while 0.5B-class models (q//128
    # == 7) fall to 64
    if q % 128 == 0 and kv % 128 == 0 and (kv // 128 >= 2 or q // 128 >= 8):
        head_dim = 128
    elif q % 64 == 0 and kv % 64 == 0 and kv // 64 >= 2:
        head_dim = 64
    else:
        head_dim = max(q // 32, 32)
    if hidden <= 512:  # toy checkpoints
        head_dim = 32
    return llama.LlamaConfig(
        vocab_size=vocab,
        hidden_size=hidden,
        intermediate_size=inter,
        num_layers=layers,
        num_heads=q // head_dim,
        num_kv_heads=kv // head_dim,
        head_dim=head_dim,
        tie_embeddings="lm_head.weight" not in params,
        dtype=_act_dtype(params, "model.embed_tokens.weight"),
    )


def _llama_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import llama

    return llama.forward(params, tokens, cfg, mesh=mesh)[0]


def _llama_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import llama

    return llama.greedy_generate(params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh)


def _llama_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                            max_new_tokens=16, **sampling):
    from modelx_tpu.models import llama

    return llama.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


def _llama_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import llama

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
        return llama.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh
        )

    return fwd, (lambda b, max_len: llama.init_kv_cache(cfg, b, max_len))


def _llama_paged_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import llama

    def fwd(p, t, kv_cache, cache_offset, table, mesh=mesh):
        return llama.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset,
            mesh=mesh, paged_table=table,
        )

    return fwd


# -- mixtral ------------------------------------------------------------------


def infer_mixtral_config(params: dict):
    from modelx_tpu.models import mixtral

    vocab, hidden = _shape(params, "model.embed_tokens.weight")
    layers = 0
    while f"model.layers.{layers}.self_attn.q_proj.weight" in params:
        layers += 1
    q = _shape(params, "model.layers.0.self_attn.q_proj.weight")[0]
    kv = _shape(params, "model.layers.0.self_attn.k_proj.weight")[0]
    w1 = "model.layers.0.block_sparse_moe.experts.w1.weight"
    num_experts, inter, _ = _shape(params, w1)
    head_dim = 128 if q % 128 == 0 and q // 128 >= 8 else max(q // 32, 32)
    if hidden <= 512:
        head_dim = 32
    return mixtral.MixtralConfig(
        vocab_size=vocab,
        hidden_size=hidden,
        intermediate_size=inter,
        num_layers=layers,
        num_heads=q // head_dim,
        num_kv_heads=kv // head_dim,
        head_dim=head_dim,
        num_experts=num_experts,
        dtype=_act_dtype(params, "model.embed_tokens.weight"),
    )


def _mixtral_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import mixtral

    return mixtral.forward(params, tokens, cfg, mesh=mesh)[0]


def _mixtral_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import mixtral

    return mixtral.greedy_generate(
        params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh
    )


def _mixtral_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                            max_new_tokens=16, **sampling):
    from modelx_tpu.models import mixtral

    return mixtral.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


def _mixtral_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import mixtral

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
        return mixtral.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh
        )

    return fwd, (lambda b, max_len: mixtral.init_kv_cache(cfg, b, max_len))


def _mixtral_paged_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import mixtral

    def fwd(p, t, kv_cache, cache_offset, table, mesh=mesh):
        return mixtral.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset,
            mesh=mesh, paged_table=table,
        )

    return fwd


# -- laguna -------------------------------------------------------------------


def infer_laguna_config(params: dict):
    raise ValueError(
        "a laguna checkpoint's layer kinds, per-layer head counts, rope "
        "parameters, top-k and expert share leave no trace in tensor shapes: "
        "its config.json must lie beside the weights")


def laguna_config_from_sidecar(sidecar: dict, params: dict):
    from modelx_tpu.models import laguna

    return laguna.config_from_hf(
        sidecar, dtype=_act_dtype(params, "model.embed_tokens.weight"))


def _laguna_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import laguna

    return laguna.forward(params, tokens, cfg, mesh=mesh)[0]


def _laguna_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import laguna

    return laguna.greedy_generate(params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh)


def _laguna_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                            max_new_tokens=16, **sampling):
    from modelx_tpu.models import laguna

    return laguna.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


def _laguna_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import laguna

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
        return laguna.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh
        )

    return fwd, (lambda b, max_len: laguna.init_kv_cache(cfg, b, max_len))


def _laguna_layer_kind_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import laguna

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
        return laguna.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh,
            ring=True,
        )

    return {
        "fwd": fwd,
        "init_state": lambda slots, max_len: laguna.init_layer_state(cfg, slots, max_len),
        "kinds": laguna.cache_kinds(cfg),
        # what the decode step counts of its expert layers, over ALL slots
        # (idle ones route too), and what those counts are shares of
        "counters": {"moe_counts": ("moe", laguna.MOE_COUNTERS)},
        "gauges": {"moe": {"held_experts": cfg.expert_count,
                           "published_experts": cfg.num_experts,
                           "sparse_layers": cfg.mlp_layer_types.count("sparse")}},
    }


# -- gpt2 ---------------------------------------------------------------------


def infer_gpt2_config(params: dict):
    from modelx_tpu.models import gpt2

    vocab, hidden = _shape(params, "wte.weight")
    n_pos = _shape(params, "wpe.weight")[0]
    layers = 0
    while f"h.{layers}.attn.c_attn.weight" in params:
        layers += 1
    # head count: standard gpt2 uses hidden/64 heads
    num_heads = max(hidden // 64, 1)
    if hidden <= 128:  # toy checkpoints
        num_heads = 4
    return gpt2.GPT2Config(
        vocab_size=vocab, n_positions=n_pos, hidden_size=hidden,
        num_layers=layers, num_heads=num_heads,
        dtype=_act_dtype(params, "wte.weight"),
    )


def infer_qwen2_config(params: dict):
    """Qwen2 = llama's decoder with qkv input biases; same inference plus
    the bias flag and qwen2's constants (rms eps 1e-6, rope theta 1e6 —
    every released Qwen2/2.5 uses these; shapes can't reveal them)."""
    cfg = infer_llama_config(params)
    return dataclasses.replace(cfg, qkv_bias=True, rms_eps=1e-6,
                               rope_theta=1_000_000.0)


# -- phi3 ---------------------------------------------------------------------


def infer_phi3_config(params: dict):
    """Phi-3 fused shapes: qkv rows = q + 2*kv with q == hidden in every
    released dense variant (mini 32x96, medium 40x128). head_dim: medium's
    GQA (kv != hidden rows) means 128; mini's MHA means hidden/32 = 96.
    Returns a llama.LlamaConfig — the module reuses llama's decoder.
    rope_theta=10000 is the 4k variants' value; the 128k variants need
    longrope scaling shapes can't reveal — apply_sidecar_config checks the
    pulled config.json and refuses those instead of mis-serving them."""
    from modelx_tpu.models import llama

    vocab, hidden = _shape(params, "model.embed_tokens.weight")
    layers = 0
    while f"model.layers.{layers}.self_attn.qkv_proj.weight" in params:
        layers += 1
    qkv_rows = _shape(params, "model.layers.0.self_attn.qkv_proj.weight")[0]
    inter = _shape(params, "model.layers.0.mlp.gate_up_proj.weight")[0] // 2
    kv_rows = (qkv_rows - hidden) // 2
    if hidden <= 512:  # toy checkpoints: 4 q heads by convention
        head_dim = max(hidden // 4, 8)
    elif kv_rows != hidden:  # GQA (phi-3-medium): 128 everywhere released
        head_dim = 128
    else:  # MHA (phi-3-mini): 32 heads of hidden/32
        head_dim = hidden // 32
    return llama.LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_layers=layers, num_heads=hidden // head_dim,
        num_kv_heads=kv_rows // head_dim, head_dim=head_dim,
        rope_theta=10000.0, rms_eps=1e-5, tie_embeddings=False,
        dtype=_act_dtype(params, "model.embed_tokens.weight"),
    )


def _phi3_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import phi3

    return phi3.forward(params, tokens, cfg, mesh=mesh)[0]


def _phi3_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import phi3

    return phi3.greedy_generate(params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh)


def _phi3_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                          max_new_tokens=16, **sampling):
    from modelx_tpu.models import phi3

    return phi3.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


def _phi3_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import phi3

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
        return phi3.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh
        )

    return fwd, (lambda b, max_len: phi3.init_kv_cache(cfg, b, max_len))


def _phi3_paged_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import phi3

    def fwd(p, t, kv_cache, cache_offset, table, mesh=mesh):
        return phi3.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset,
            mesh=mesh, paged_table=table,
        )

    return fwd


# -- gemma2 -------------------------------------------------------------------


def infer_gemma2_config(params: dict):
    """Gemma2 shapes are llama-like; head_dim is 256 in every released
    checkpoint except 27b (hidden 4608, head_dim 128, query_pre_attn_scalar
    hidden/heads = 144 instead of head_dim). Softcaps and the 4096 sliding
    window are architecture constants shapes can't reveal."""
    from modelx_tpu.models import gemma2

    vocab, hidden = _shape(params, "model.embed_tokens.weight")
    layers = 0
    while f"model.layers.{layers}.self_attn.q_proj.weight" in params:
        layers += 1
    q = _shape(params, "model.layers.0.self_attn.q_proj.weight")[0]
    kv = _shape(params, "model.layers.0.self_attn.k_proj.weight")[0]
    inter = _shape(params, "model.layers.0.mlp.gate_proj.weight")[0]
    if hidden <= 512:  # toy checkpoints
        head_dim = 32
        qpas = float(head_dim)
        window = 16
    elif hidden >= 4608:  # gemma2-27b
        head_dim = 128
        qpas = float(hidden // (q // head_dim))
        window = 4096
    else:  # 2b / 9b
        head_dim = 256
        qpas = 256.0
        window = 4096
    return gemma2.Gemma2Config(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=inter,
        num_layers=layers, num_heads=q // head_dim,
        num_kv_heads=kv // head_dim, head_dim=head_dim,
        query_pre_attn_scalar=qpas, sliding_window=window,
        dtype=_act_dtype(params, "model.embed_tokens.weight"),
    )


def _gemma2_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import gemma2

    return gemma2.forward(params, tokens, cfg, mesh=mesh)[0]


def _gemma2_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import gemma2

    return gemma2.greedy_generate(params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh)


def _gemma2_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                            max_new_tokens=16, **sampling):
    from modelx_tpu.models import gemma2

    return gemma2.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


def _gemma2_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import gemma2

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
        return gemma2.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh
        )

    return fwd, (lambda b, max_len: gemma2.init_kv_cache(cfg, b, max_len))


def _gemma2_paged_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import gemma2

    def fwd(p, t, kv_cache, cache_offset, table, mesh=mesh):
        return gemma2.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset,
            mesh=mesh, paged_table=table,
        )

    return fwd


def _gpt2_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import gpt2

    return gpt2.forward(params, tokens, cfg)[0]


def _gpt2_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import gpt2

    return gpt2.greedy_generate(params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh)


def _gpt2_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                          max_new_tokens=16, **sampling):
    from modelx_tpu.models import gpt2

    return gpt2.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


def _gpt2_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import gpt2

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
        return gpt2.forward(p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset)

    return fwd, (lambda b, max_len: gpt2.init_kv_cache(cfg, b, max_len))


def _gpt2_paged_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import gpt2

    def fwd(p, t, kv_cache, cache_offset, table, mesh=mesh):
        return gpt2.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset,
            paged_table=table,
        )

    return fwd


# -- minicpm_sala ---------------------------------------------------------------


def infer_minicpm_sala_config(params: dict):
    raise ValueError(
        "a minicpm_sala checkpoint's mixer types, sparse settings, muP scales "
        "and published depth leave no trace in tensor shapes: its config.json "
        "must lie beside the weights")


def minicpm_sala_config_from_sidecar(sidecar: dict, params: dict):
    from modelx_tpu.models import minicpm_sala

    return minicpm_sala.config_from_hf(
        sidecar, dtype=_act_dtype(params, "model.embed_tokens.weight"))


def _minicpm_sala_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import minicpm_sala

    return minicpm_sala.forward(params, tokens, cfg, mesh=mesh)[0]


def _minicpm_sala_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import minicpm_sala

    return minicpm_sala.greedy_generate(
        params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh)


def _minicpm_sala_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                                  max_new_tokens=16, **sampling):
    from modelx_tpu.models import minicpm_sala

    return minicpm_sala.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


_UNTOLD = object()


def _state_decode_fns(family, cfg, mesh):
    """``decode_fns`` of a family (its module) some of whose layers keep a
    STATE in place of keys and values: a padded bucket's tail would enter the
    states for good, so a caller that lands a block of prompt positions says
    how many of them are real (None = all), as the continuous engine does."""
    name = family.__name__.rpartition(".")[2]

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh, valid_len=_UNTOLD, live=None):
        if t.shape[1] > 1 and valid_len is _UNTOLD:
            raise ValueError(
                f"{name}: a block of prompt positions needs its rows' real "
                "lengths (a state keeps what a padded tail adds): this family "
                "streams through --continuous-batch")
        return family.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh,
            valid_len=None if valid_len is _UNTOLD else valid_len, live=live)

    return fwd, (lambda b, max_len: family.init_kv_cache(cfg, b, max_len))


def _minicpm_sala_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import minicpm_sala

    return _state_decode_fns(minicpm_sala, cfg, mesh)


def _minicpm_sala_layer_kind_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import minicpm_sala

    fwd, _ = _minicpm_sala_decode_fns(cfg, mesh)
    sparse_layers = cfg.mixer_types.count(minicpm_sala.SPARSE)
    return {
        "fwd": fwd,
        "init_state": lambda slots, max_len: minicpm_sala.init_layer_state(cfg, slots, max_len),
        "kinds": minicpm_sala.cache_kinds(cfg),
        # what the decode step counts of its sparse layers, over the LIVE rows
        "counters": {"sparse_counts": ("sparse", minicpm_sala.SPARSE_COUNTERS)},
        "gauges": {"sparse": {"sparse_layers": sparse_layers,
                              "linear_layers": cfg.num_layers - sparse_layers,
                              "block_size": cfg.sparse.block_size, "topk": cfg.sparse.topk,
                              "dense_len": cfg.sparse.dense_len}},
    }


# -- deepseek_v2 ----------------------------------------------------------------


def infer_deepseek_v2_config(params: dict):
    raise ValueError(
        "a deepseek_v2 checkpoint's head sizes, routing groups, rope scaling and "
        "expert share leave no trace in tensor shapes: its config.json must lie "
        "beside the weights")


def deepseek_v2_config_from_sidecar(sidecar: dict, params: dict):
    from modelx_tpu.models import deepseek_v2

    return deepseek_v2.config_from_hf(
        sidecar, dtype=_act_dtype(params, "model.embed_tokens.weight"))


def _deepseek_v2_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import deepseek_v2

    return deepseek_v2.forward(params, tokens, cfg, mesh=mesh)[0]


def _deepseek_v2_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import deepseek_v2

    return deepseek_v2.greedy_generate(
        params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh)


def _deepseek_v2_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                                 max_new_tokens=16, **sampling):
    from modelx_tpu.models import deepseek_v2

    return deepseek_v2.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


def _deepseek_v2_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import deepseek_v2

    def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
        return deepseek_v2.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh
        )

    return fwd, (lambda b, max_len: deepseek_v2.init_kv_cache(cfg, b, max_len))


def _deepseek_v2_layer_kind_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import deepseek_v2

    fwd, _ = _deepseek_v2_decode_fns(cfg, mesh)
    return {
        "fwd": fwd,
        "init_state": lambda slots, max_len: deepseek_v2.init_layer_state(cfg, slots, max_len),
        "kinds": deepseek_v2.cache_kinds(cfg),
        # what the decode step counts: of its expert layers over ALL slots (idle
        # ones route too), of its latent layers over the rows that hold a context
        "counters": {"moe_counts": ("moe", deepseek_v2.MOE_COUNTERS),
                     "mla_counts": ("mla", deepseek_v2.MLA_COUNTERS)},
        "gauges": {"moe": {"held_experts": cfg.expert_count,
                           "published_experts": cfg.num_experts,
                           "sparse_layers": cfg.num_layers - cfg.first_k_dense_replace,
                           "groups": cfg.n_group, "groups_kept": cfg.topk_group},
                   "mla": {"layers": cfg.num_layers, "heads": cfg.num_heads,
                           "kv_lora_rank": cfg.kv_lora_rank,
                           "rope_dim": cfg.qk_rope_head_dim,
                           "line_width": cfg.line_width}},
    }


# -- nemotron_h -----------------------------------------------------------------


def infer_nemotron_h_config(params: dict):
    raise ValueError(
        "a nemotron_h checkpoint's layer pattern, Mamba head and group counts, "
        "routing and expert share leave no trace in tensor shapes: its "
        "config.json must lie beside the weights")


def nemotron_h_config_from_sidecar(sidecar: dict, params: dict):
    from modelx_tpu.models import nemotron_h

    return nemotron_h.config_from_hf(
        sidecar, dtype=_act_dtype(params, "backbone.embeddings.weight"))


def _nemotron_h_forward(params, tokens, cfg, mesh=None):
    from modelx_tpu.models import nemotron_h

    return nemotron_h.forward(params, tokens, cfg, mesh=mesh)[0]


def _nemotron_h_generate(params, tokens, cfg, mesh=None, max_new_tokens=16):
    from modelx_tpu.models import nemotron_h

    return nemotron_h.greedy_generate(
        params, tokens, cfg, max_new_tokens=max_new_tokens, mesh=mesh)


def _nemotron_h_generate_ragged(params, tokens, row_lens, cfg, mesh=None,
                                max_new_tokens=16, **sampling):
    from modelx_tpu.models import nemotron_h

    return nemotron_h.ragged_greedy_generate(
        params, tokens, row_lens, cfg, max_new_tokens=max_new_tokens, mesh=mesh,
        **sampling,
    )


def _nemotron_h_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import nemotron_h

    return _state_decode_fns(nemotron_h, cfg, mesh)


def _nemotron_h_layer_kind_decode_fns(cfg, mesh=None):
    from modelx_tpu.models import nemotron_h

    fwd, _ = _nemotron_h_decode_fns(cfg, mesh)
    return {
        "fwd": fwd,
        "init_state": lambda slots, max_len: nemotron_h.init_layer_state(cfg, slots, max_len),
        "kinds": nemotron_h.cache_kinds(cfg),
        # what the decode step counts: of its expert layers over ALL slots (idle
        # ones route too); of its rows, live ones and all, once a step
        "counters": {"moe_counts": ("moe", nemotron_h.MOE_COUNTERS),
                     "ssm_counts": ("ssm", nemotron_h.SSM_COUNTERS)},
        "gauges": {"moe": {"held_experts": cfg.expert_count,
                           "published_experts": cfg.num_experts,
                           "sparse_layers": cfg.pattern.count(nemotron_h.EXPERTS),
                           "latent_size": cfg.moe_latent_size},
                   "ssm": {"layers": cfg.pattern.count(nemotron_h.MAMBA),
                           "heads": cfg.mamba_heads, "head_dim": cfg.mamba_head_dim,
                           "state_size": cfg.ssm_state_size, "groups": cfg.n_groups,
                           "conv_kernel": cfg.conv_kernel}},
    }


# -- bert ---------------------------------------------------------------------


def infer_bert_config(params: dict):
    from modelx_tpu.models import bert

    vocab, hidden = _shape(params, "bert.embeddings.word_embeddings.weight")
    max_pos = _shape(params, "bert.embeddings.position_embeddings.weight")[0]
    type_vocab = _shape(params, "bert.embeddings.token_type_embeddings.weight")[0]
    layers = 0
    while f"bert.encoder.layer.{layers}.attention.self.query.weight" in params:
        layers += 1
    inter = _shape(params, "bert.encoder.layer.0.intermediate.dense.weight")[0]
    num_heads = max(hidden // 64, 1)
    if hidden <= 128:
        num_heads = 4
    return bert.BertConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_heads=num_heads, intermediate_size=inter,
        max_position_embeddings=max_pos, type_vocab_size=type_vocab,
        dtype=_act_dtype(params, "bert.embeddings.word_embeddings.weight"),
    )


def _bert_forward(params, tokens, cfg, mesh=None):
    """Returns the sequence output [B,S,E] (encoder family: 'logits' are
    features, argmax over E is not meaningful but harmless for probes)."""
    from modelx_tpu.models import bert

    return bert.forward(params, tokens, cfg)[0]


FAMILIES: dict[str, Family] = {
    "llama": Family("llama", LLAMA_RULES, infer_llama_config, _llama_forward,
                    _llama_generate, _llama_generate_ragged, _llama_decode_fns,
                    _llama_paged_decode_fns),
    # same decoder implementation as llama — the bias params flow through
    # the param dict, so every llama entry point serves qwen2 unchanged
    "qwen2": Family("qwen2", QWEN2_RULES, infer_qwen2_config, _llama_forward,
                    _llama_generate, _llama_generate_ragged, _llama_decode_fns,
                    _llama_paged_decode_fns),
    "phi3": Family("phi3", PHI3_RULES, infer_phi3_config, _phi3_forward,
                  _phi3_generate, _phi3_generate_ragged, _phi3_decode_fns,
                  _phi3_paged_decode_fns),
    "gemma2": Family("gemma2", GEMMA2_RULES, infer_gemma2_config,
                     _gemma2_forward, _gemma2_generate,
                     _gemma2_generate_ragged, _gemma2_decode_fns,
                     _gemma2_paged_decode_fns),
    "mixtral": Family("mixtral", MIXTRAL_RULES, infer_mixtral_config, _mixtral_forward,
                      _mixtral_generate, _mixtral_generate_ragged, _mixtral_decode_fns,
                      _mixtral_paged_decode_fns),
    "laguna": Family("laguna", LAGUNA_RULES, infer_laguna_config, _laguna_forward,
                     _laguna_generate, _laguna_generate_ragged, _laguna_decode_fns,
                     config_from_sidecar=laguna_config_from_sidecar,
                     layer_kind_decode_fns=_laguna_layer_kind_decode_fns),
    "minicpm_sala": Family("minicpm_sala", MINICPM_SALA_RULES, infer_minicpm_sala_config,
                           _minicpm_sala_forward, _minicpm_sala_generate,
                           _minicpm_sala_generate_ragged, _minicpm_sala_decode_fns,
                           config_from_sidecar=minicpm_sala_config_from_sidecar,
                           layer_kind_decode_fns=_minicpm_sala_layer_kind_decode_fns),
    "deepseek_v2": Family("deepseek_v2", DEEPSEEK_V2_RULES, infer_deepseek_v2_config,
                          _deepseek_v2_forward, _deepseek_v2_generate,
                          _deepseek_v2_generate_ragged, _deepseek_v2_decode_fns,
                          config_from_sidecar=deepseek_v2_config_from_sidecar,
                          layer_kind_decode_fns=_deepseek_v2_layer_kind_decode_fns),
    "nemotron_h": Family("nemotron_h", NEMOTRON_H_RULES, infer_nemotron_h_config,
                         _nemotron_h_forward, _nemotron_h_generate,
                         _nemotron_h_generate_ragged, _nemotron_h_decode_fns,
                         config_from_sidecar=nemotron_h_config_from_sidecar,
                         layer_kind_decode_fns=_nemotron_h_layer_kind_decode_fns),
    "gpt2": Family("gpt2", GPT2_RULES, infer_gpt2_config, _gpt2_forward,
                   _gpt2_generate, _gpt2_generate_ragged, _gpt2_decode_fns,
                   _gpt2_paged_decode_fns),
    "bert": Family("bert", BERT_RULES, infer_bert_config, _bert_forward, None),
}


logger = logging.getLogger("modelx.serve")


def sidecar_config(model_dir: str) -> dict | None:
    """The checkpoint's pulled ``config.json`` (the HF sidecar), if any.
    Shape inference recovers the architecture but NOT the RoPE parameters —
    rope_theta and rope_scaling leave no trace in tensor shapes."""
    try:
        with open(os.path.join(model_dir, "config.json")) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return None
    return raw if isinstance(raw, dict) else None


# rope_scaling schemes that reshape position encoding at EVERY position:
# serving them with plain RoPE is wrong from token 0, so they refuse.
# Other schemes (llama3 / linear / dynamic-ntk) match plain RoPE inside
# the original context window — those warn (degraded long-context) but
# keep previously-deployable checkpoints loadable.
_ROPE_SCALING_REFUSED = ("longrope", "su", "yarn")


def config_for(family: Family, params: dict, model_dir: str):
    """The config of the checkpoint under ``model_dir``: from tensor shapes,
    reconciled with the pulled ``config.json`` (rope_theta overrides apply;
    unimplemented rope_scaling refuses before the weights stream) — or,
    for a family whose shapes cannot say, from ``config.json`` alone."""
    sidecar = sidecar_config(model_dir)
    if family.config_from_sidecar is not None:
        if sidecar is None:
            family.infer_config(params)  # raises, saying why
        return family.config_from_sidecar(sidecar, params)
    cfg = family.infer_config(params)
    if sidecar is not None:
        cfg = apply_sidecar_config(cfg, sidecar, family.name)
    return cfg


def apply_sidecar_config(cfg, sidecar: dict, family_name: str):
    """Reconcile a shape-inferred config with the checkpoint's config.json.

    ``rope_scaling`` is not implemented by this runtime. Schemes that
    change the encoding at every position (longrope/su/yarn — e.g. the
    phi-3-*-128k family) would decode garbage from the first token, so
    those checkpoints are REFUSED instead of silently mis-served
    (infer_phi3_config assumes the unscaled rope_theta=10000 of every 4k
    dense phi-3); window-extension schemes (llama3, linear, dynamic) warn
    and serve, correct within the pre-scaling window. A differing
    ``rope_theta`` is safe to honor: the sidecar's value replaces the
    inferred default, with a warning so the override is visible in logs."""
    scaling = sidecar.get("rope_scaling")
    if scaling:
        stype = (
            scaling.get("type") or scaling.get("rope_type")
            if isinstance(scaling, dict) else scaling
        )
        if not isinstance(stype, str) or stype.lower() in _ROPE_SCALING_REFUSED:
            raise ValueError(
                f"{family_name} checkpoint's config.json declares "
                f"rope_scaling ({stype!r}); this runtime implements "
                "unscaled RoPE only — refusing to mis-serve a long-context "
                "checkpoint (e.g. phi-3-*-128k)"
            )
        logger.warning(
            "%s config.json declares rope_scaling %r: not implemented — "
            "serving is exact only within the pre-scaling context window",
            family_name, stype,
        )
    theta = sidecar.get("rope_theta")
    if theta is not None and hasattr(cfg, "rope_theta"):
        try:
            theta = float(theta)
        except (TypeError, ValueError):
            logger.warning(
                "%s config.json rope_theta=%r is not numeric; keeping the "
                "inferred %s", family_name, theta, cfg.rope_theta,
            )
            return cfg
        if theta != float(cfg.rope_theta):
            logger.warning(
                "%s config.json rope_theta=%s overrides the shape-inferred %s",
                family_name, theta, cfg.rope_theta,
            )
            cfg = dataclasses.replace(cfg, rope_theta=theta)
    return cfg


def detect(tensor_names) -> Family:
    """Family from tensor names; raises for unrecognized checkpoints."""
    name = infer_family(list(tensor_names))
    if not name or name not in FAMILIES:
        raise ValueError(
            f"cannot determine model family from tensors ({list(tensor_names)[:4]}...); "
            f"supported: {sorted(FAMILIES)}"
        )
    return FAMILIES[name]


def abstract_params(infos: dict, rules: Rules | None = None, mesh=None,
                    quantize: str | None = None) -> dict:
    """ShapeDtypeStructs for a checkpoint known only by its header/manifest
    tensor index — everything config inference and AOT compilation need,
    before a single weight byte arrives. ``infos`` values need ``shape`` and
    either ``np_dtype()`` (st.TensorInfo) or ``dtype``. With rules+mesh the
    structs carry the placement shardings, so the compiled program matches
    the arrays the loader will deliver. ``quantize="int8"`` mirrors the
    loader's weight-only quantization: eligible 2-D weights become QTensor
    pytrees of structs (int8 data + f32 per-channel scale), so quantized
    deploys AOT-compile while their (halved) bytes stream — int8 TTFT pays
    max(load, compile), not the sum."""
    from modelx_tpu.dl.sharding import sharding_for

    if quantize not in (None, "int8"):
        raise ValueError(f"unsupported quantize mode {quantize!r}")
    if quantize:
        import numpy as np

        from modelx_tpu.ops import quant as qt
        from jax.sharding import NamedSharding, PartitionSpec

    out = {}
    for name, info in infos.items():
        dt = info.np_dtype() if hasattr(info, "np_dtype") else info.dtype
        sharding = sharding_for(name, rules, mesh) if rules is not None and mesh is not None else None
        shape = tuple(info.shape)
        if (
            quantize == "int8"
            and getattr(info, "members", None) is None
            and len(shape) == 2
            and qt.DEFAULT_ELIGIBLE.search(name) is not None
        ):
            # must mirror loader._quantized exactly: a mismatch compiles a
            # program the delivered params can't call
            scale_sharding = None
            if sharding is not None:
                spec = sharding.spec
                scale_sharding = NamedSharding(
                    mesh, PartitionSpec(spec[0] if len(spec) else None)
                )
            out[name] = qt.QTensor(
                q=jax.ShapeDtypeStruct(shape, np.int8, sharding=sharding),
                scale=jax.ShapeDtypeStruct((shape[0],), np.float32, sharding=scale_sharding),
            )
        else:
            out[name] = jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return out


def forward_program_key(family: Family, cfg, mode: str, token_shape: tuple,
                        mesh, param_sds: dict) -> str:
    """The aot_cache key for one (family, cfg, mode, shape, mesh, params)
    program — the single source of key truth shared by precompile_forward,
    precompile_score and the program-store bundler (dl/program_store.py), so
    a published bundle's artifact names always match what a warm boot asks
    the cache for."""
    from modelx_tpu.dl import aot_cache

    return aot_cache.cache_key(
        family.name, cfg, mode, token_shape,
        tuple(mesh.shape.items()) if mesh is not None else None,
        aot_cache.describe_sds(param_sds),
    )


def precompile_forward(family: Family, cfg, param_sds: dict, token_shape: tuple,
                       mesh=None, mode: str = "forward", cache_dir: str = ""):
    """AOT-compile the prefill forward for one token shape from abstract
    params — the weights do not need to exist yet, so a deploy overlaps this
    with the loader's byte streaming and the first request (or first token)
    meets an already-compiled program. Returns the compiled executable;
    call it with (params, tokens) of exactly these shapes/shardings.
    ``mode``: "forward" (logits), "argmax_all" (per-position argmax — the
    serve forward route), "argmax_last" (first decoded token — TTFT).
    ``cache_dir`` reuses a serialized export across processes (dl/aot_cache)
    so a warm pod start skips tracing+lowering entirely."""
    import jax.numpy as jnp

    if mode == "argmax_all":
        def fn(p, t):
            return jnp.argmax(family.forward(p, t, cfg, mesh=mesh), axis=-1)
    elif mode == "argmax_last":
        def fn(p, t):
            return jnp.argmax(family.forward(p, t, cfg, mesh=mesh)[:, -1, :], axis=-1)
    else:
        def fn(p, t):
            return family.forward(p, t, cfg, mesh=mesh)

    tok = jax.ShapeDtypeStruct(token_shape, jnp.int32)
    if cache_dir:
        from modelx_tpu.dl import aot_cache

        key = forward_program_key(family, cfg, mode, token_shape, mesh, param_sds)
        return aot_cache.load_or_compile(fn, (param_sds, tok), cache_dir, key)
    return jax.jit(fn).lower(param_sds, tok).compile()


def precompile_score(family: Family, cfg, param_sds: dict, token_shape: tuple,
                     top_k: int = 0, mesh=None, cache_dir: str = ""):
    """AOT-compile the scoring program (per-token logprobs of the given
    continuations, optional top-k alternatives) for one padded token shape.
    Body must stay identical to what serve.score_logprobs_rows historically
    jitted inline — routing it through here lets the export ride the aot
    cache and the program-store bundle like the forward ladder does.
    Call the result with (params, tokens) of exactly ``token_shape``."""
    import jax.numpy as jnp

    k = int(top_k)

    def fn(params, toks):
        logits = family.forward(params, toks, cfg, mesh=mesh)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)  # [B, Lb, V]
        nxt = jnp.concatenate(
            [toks[:, 1:], jnp.zeros((toks.shape[0], 1), jnp.int32)],
            axis=1,
        )
        chosen = jnp.take_along_axis(
            lp, nxt[..., None], axis=-1
        )[..., 0]  # position j scores token j+1
        if k:
            top_lp, top_id = jax.lax.top_k(lp, k)
            return chosen, top_id, top_lp
        return chosen, None, None

    tok = jax.ShapeDtypeStruct(token_shape, jnp.int32)
    if cache_dir:
        from modelx_tpu.dl import aot_cache

        key = forward_program_key(
            family, cfg, f"score:{int(top_k)}", token_shape, mesh, param_sds
        )
        return aot_cache.load_or_compile(fn, (param_sds, tok), cache_dir, key)
    return jax.jit(fn).lower(param_sds, tok).compile()
