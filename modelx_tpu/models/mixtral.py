"""Mixtral-family sparse-MoE decoder (llama attention + MoE FFN).

Params are a flat dict keyed by HF safetensors names, with one deviation:
the per-expert FFN weights are *stacked* along a leading E axis —

    model.layers.N.block_sparse_moe.gate.weight        [E, D]
    model.layers.N.block_sparse_moe.experts.w1.weight  [E, F, D]   (gate)
    model.layers.N.block_sparse_moe.experts.w2.weight  [E, D, F]   (down)
    model.layers.N.block_sparse_moe.experts.w3.weight  [E, F, D]   (up)

— because a stacked E axis is what expert parallelism shards
(MIXTRAL_RULES: E over ``ep``, F over ``tp``). ``from_hf_state_dict``
folds HF's ``experts.<i>.w1.weight`` tensors into this layout.

Reference parity: the reference registry has no model code (SURVEY §2.2);
this family exists for the TPU serve/train path, exercising the ``ep``
mesh axis end-to-end (ops/moe.py).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from modelx_tpu.models import llama
from modelx_tpu.ops import moe as moe_ops
from modelx_tpu.ops.nn import linear as _linear


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 0.0  # <=0: drop-free (exact Mixtral math)
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @classmethod
    def mixtral_8x7b(cls) -> "MixtralConfig":
        return cls()

    @classmethod
    def tiny(cls, vocab_size: int = 512) -> "MixtralConfig":
        return cls(
            vocab_size=vocab_size, hidden_size=128, intermediate_size=256,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
            num_experts=4, top_k=2, rope_theta=10000.0,
        )


def param_shapes(cfg: MixtralConfig) -> dict[str, tuple[int, ...]]:
    e, q = cfg.hidden_size, cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    f, ne = cfg.intermediate_size, cfg.num_experts
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_size, e),
        "model.norm.weight": (e,),
        "lm_head.weight": (cfg.vocab_size, e),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        shapes.update(
            {
                p + "self_attn.q_proj.weight": (q, e),
                p + "self_attn.k_proj.weight": (kv, e),
                p + "self_attn.v_proj.weight": (kv, e),
                p + "self_attn.o_proj.weight": (e, q),
                p + "block_sparse_moe.gate.weight": (ne, e),
                p + "block_sparse_moe.experts.w1.weight": (ne, f, e),
                p + "block_sparse_moe.experts.w2.weight": (ne, e, f),
                p + "block_sparse_moe.experts.w3.weight": (ne, f, e),
                p + "input_layernorm.weight": (e,),
                p + "post_attention_layernorm.weight": (e,),
            }
        )
    return shapes


def init_params(cfg: MixtralConfig, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    keys = jax.random.split(key, len(shapes))
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.endswith("norm.weight"):
            params[name] = jnp.ones(shape, dtype)
        else:
            fan_in = shape[-1]
            params[name] = (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)
    return params


_HF_EXPERT = re.compile(
    r"^(model\.layers\.\d+\.block_sparse_moe\.experts)\.(\d+)\.(w[123])\.weight$"
)


def from_hf_state_dict(sd: Mapping[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Fold HF Mixtral names (experts.<i>.wN.weight) into stacked tensors."""
    out: dict[str, np.ndarray] = {}
    experts: dict[str, dict[int, np.ndarray]] = {}
    for name, value in sd.items():
        m = _HF_EXPERT.match(name)
        if m:
            experts.setdefault(f"{m.group(1)}.{m.group(3)}.weight", {})[int(m.group(2))] = np.asarray(value)
        else:
            out[name] = np.asarray(value)
    for name, parts in experts.items():
        out[name] = np.stack([parts[i] for i in range(len(parts))])
    return out


def forward(
    params: dict[str, jax.Array],
    tokens: jax.Array,
    cfg: MixtralConfig,
    positions: jax.Array | None = None,
    kv_cache: dict | None = None,
    cache_offset: int | jax.Array = 0,
    mesh: Mesh | None = None,
    attention_impl: str = "auto",
    paged_table: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """Returns (logits [B,S,V], updated kv_cache). Same contract as
    llama.forward (paged_table included — MoE serving gets the in-place
    paged decode too); the FFN is the sparse-MoE block (ops/moe.py)."""
    ctx = llama.ShardingCtx(mesh)
    acfg = llama.LlamaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps,
        dtype=cfg.dtype,
    )
    b, s = tokens.shape
    if positions is None:
        off = jnp.asarray(cache_offset if kv_cache is not None else 0)
        positions = jnp.arange(s)[None, :] + (off[:, None] if off.ndim else off)
        positions = jnp.broadcast_to(positions, (b, s))

    x = jnp.take(params["model.embed_tokens.weight"], tokens, axis=0).astype(cfg.dtype)
    x = ctx.constrain(x, "dp", "sp", None)

    new_cache: dict | None = {} if kv_cache is not None else None
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        lp = {
            suffix: params[p + suffix]
            for suffix in llama.LAYER_PARAM_SUFFIXES
            if not suffix.startswith("mlp.")
        }

        def moe_fn(h, p=p):
            return moe_ops.moe_ffn(
                h,
                params[p + "block_sparse_moe.gate.weight"],
                params[p + "block_sparse_moe.experts.w1.weight"],
                params[p + "block_sparse_moe.experts.w2.weight"],
                params[p + "block_sparse_moe.experts.w3.weight"],
                top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                constrain=ctx.constrain,
            )

        cache = (kv_cache[f"k{i}"], kv_cache[f"v{i}"]) if kv_cache is not None else None
        x, updated = llama.decoder_layer(
            lp, x, positions, acfg, ctx, cache=cache, cache_offset=cache_offset,
            mesh=mesh, attention_impl=attention_impl, mlp_fn=moe_fn,
            paged_table=paged_table,
        )
        if updated is not None:
            new_cache[f"k{i}"], new_cache[f"v{i}"] = updated

    x = llama._rms_norm(x, params["model.norm.weight"], cfg.rms_eps)
    logits = _linear(x, params["lm_head.weight"])
    return ctx.constrain(logits, "dp", "sp", None), new_cache


def init_kv_cache(cfg: MixtralConfig, batch: int, max_len: int, dtype=None) -> dict:
    dtype = dtype or cfg.dtype
    cache = {}
    for i in range(cfg.num_layers):
        shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        cache[f"k{i}"] = jnp.zeros(shape, dtype)
        cache[f"v{i}"] = jnp.zeros(shape, dtype)
    return cache
