"""Phi-3-family decoder (mini / medium dense variants).

Phi-3 is llama's architecture with FUSED projections in the checkpoint:
``self_attn.qkv_proj.weight`` packs [q | k | v] rows and
``mlp.gate_up_proj.weight`` packs [gate | up] — everything else (RMSNorm,
rope theta 1e4, SwiGLU product, untied lm_head, GQA) is the llama decoder
verbatim. So this module is deliberately thin: the forward SLICES the
fused tensors inside the traced function (an XLA slice is a view — no
copy, and GSPMD repartitions it as needed) and delegates each block to
``llama.decoder_layer``, inheriting the flash/ring attention dispatch,
the cached and RAGGED decode paths, and the in-place PAGED decode the
continuous engine's ``--kv-attention in-place`` uses.

Config reuses ``llama.LlamaConfig`` — phi-3's hyperparameters map onto it
exactly; only the checkpoint tensor naming differs.

No reference counterpart (the reference stores checkpoints without
executing them; pkg/client is model-agnostic).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from modelx_tpu.models import llama
from modelx_tpu.models.llama import LlamaConfig

def param_shapes(cfg: LlamaConfig) -> dict[str, tuple[int, ...]]:
    e, q = cfg.hidden_size, cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    f = cfg.intermediate_size
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_size, e),
        "model.norm.weight": (e,),
        "lm_head.weight": (cfg.vocab_size, e),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        shapes.update({
            p + "self_attn.qkv_proj.weight": (q + 2 * kv, e),
            p + "self_attn.o_proj.weight": (e, q),
            p + "mlp.gate_up_proj.weight": (2 * f, e),
            p + "mlp.down_proj.weight": (e, f),
            p + "input_layernorm.weight": (e,),
            p + "post_attention_layernorm.weight": (e,),
        })
    return shapes


def init_params(cfg: LlamaConfig, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    import math

    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    keys = jax.random.split(key, len(shapes))
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.endswith("norm.weight"):
            params[name] = jnp.ones(shape, dtype)
        else:
            params[name] = (
                jax.random.normal(k, shape) / math.sqrt(shape[-1])
            ).astype(dtype)
    return params


def _slice_rows(w, lo: int, hi: int):
    """Row-slice a weight OR an int8 QTensor: per-output-row scales slice
    with the rows, so a fused quantized tensor un-fuses exactly."""
    from modelx_tpu.ops.quant import QTensor

    if isinstance(w, QTensor):
        return QTensor(w.q[lo:hi], w.scale[lo:hi])
    return w[lo:hi]


def _as_llama_params(params: dict, cfg: LlamaConfig) -> dict:
    """Translate a fused phi3 checkpoint into llama's param vocabulary.
    The slices are traced XLA ops (views), not host copies — this runs
    inside the jitted forward."""
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    f = cfg.intermediate_size
    out = {
        k: params[k]
        for k in ("model.embed_tokens.weight", "model.norm.weight",
                  "lm_head.weight")
        if k in params
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        qkv = params[p + "self_attn.qkv_proj.weight"]
        gu = params[p + "mlp.gate_up_proj.weight"]
        out[p + "self_attn.q_proj.weight"] = _slice_rows(qkv, 0, qd)
        out[p + "self_attn.k_proj.weight"] = _slice_rows(qkv, qd, qd + kvd)
        out[p + "self_attn.v_proj.weight"] = _slice_rows(qkv, qd + kvd, qd + 2 * kvd)
        out[p + "mlp.gate_proj.weight"] = _slice_rows(gu, 0, f)
        out[p + "mlp.up_proj.weight"] = _slice_rows(gu, f, 2 * f)
        for suffix in ("self_attn.o_proj.weight", "mlp.down_proj.weight",
                       "input_layernorm.weight",
                       "post_attention_layernorm.weight"):
            out[p + suffix] = params[p + suffix]
    return out


def forward(
    params: dict[str, jax.Array],
    tokens: jax.Array,
    cfg: LlamaConfig,
    positions: jax.Array | None = None,
    kv_cache: dict | None = None,
    cache_offset: int | jax.Array = 0,
    mesh: Mesh | None = None,
    attention_impl: str = "auto",
    paged_table: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """llama.forward over the un-fused param views: one translation, full
    inheritance of llama's prefill/cached/ragged/paged paths."""
    return llama.forward(
        _as_llama_params(params, cfg), tokens, cfg, positions=positions,
        kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh,
        attention_impl=attention_impl, paged_table=paged_table,
    )


init_kv_cache = llama.init_kv_cache
