"""GPT-2 family (BASELINE config #1 checkpoints are GPT-2 125M safetensors).

Params keyed by HF safetensors names (``wte.weight``, ``h.N.attn.c_attn.weight``,
...). HF GPT-2 uses Conv1D layers whose weights are stored [in, out] — note
the transposed layout vs llama's [out, in] Linear. Sharding rules:
dl/sharding.py GPT2_RULES.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from modelx_tpu.ops import attention as attn_ops
from modelx_tpu.ops.kv_write import write_rows
from modelx_tpu.ops.nn import conv1d as _conv1d
from modelx_tpu.ops.nn import layer_norm as _layer_norm


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.float32

    @classmethod
    def gpt2_125m(cls) -> "GPT2Config":
        return cls()

    @classmethod
    def tiny(cls) -> "GPT2Config":
        return cls(vocab_size=256, n_positions=64, hidden_size=64, num_layers=2, num_heads=4)


def param_shapes(cfg: GPT2Config) -> dict[str, tuple[int, ...]]:
    e = cfg.hidden_size
    shapes: dict[str, tuple[int, ...]] = {
        "wte.weight": (cfg.vocab_size, e),
        "wpe.weight": (cfg.n_positions, e),
        "ln_f.weight": (e,),
        "ln_f.bias": (e,),
    }
    for i in range(cfg.num_layers):
        p = f"h.{i}."
        shapes.update(
            {
                p + "ln_1.weight": (e,),
                p + "ln_1.bias": (e,),
                p + "attn.c_attn.weight": (e, 3 * e),  # Conv1D: [in, out]
                p + "attn.c_attn.bias": (3 * e,),
                p + "attn.c_proj.weight": (e, e),
                p + "attn.c_proj.bias": (e,),
                p + "ln_2.weight": (e,),
                p + "ln_2.bias": (e,),
                p + "mlp.c_fc.weight": (e, 4 * e),
                p + "mlp.c_fc.bias": (4 * e,),
                p + "mlp.c_proj.weight": (4 * e, e),
                p + "mlp.c_proj.bias": (e,),
            }
        )
    return shapes


def init_params(cfg: GPT2Config, key: jax.Array) -> dict[str, jax.Array]:
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.endswith(".bias") or "ln_" in name:
            params[name] = (
                jnp.zeros(shape, cfg.dtype) if name.endswith(".bias") else jnp.ones(shape, cfg.dtype)
            )
        else:
            params[name] = (jax.random.normal(k, shape) * 0.02).astype(cfg.dtype)
    return params


def forward(
    params: dict[str, jax.Array],
    tokens: jax.Array,
    cfg: GPT2Config,
    positions: jax.Array | None = None,
    kv_cache: dict | None = None,
    cache_offset: int | jax.Array = 0,
    mesh=None,
    paged_table: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """Returns (logits [B, S, V], updated kv_cache or None) — the same
    cached-decode contract as llama.forward, so the shared decode module
    (scan decode, ragged batching, streaming, speculation) serves GPT-2
    unchanged. Prefill: kv_cache=None. Decode: pass the cache and offset
    (scalar, or [B] for ragged rows). With ``paged_table``, kv_cache holds
    page pools and attention reads them in place (single-token decode, the
    continuous engine's --kv-attention in-place path)."""
    b, s = tokens.shape
    if positions is None:
        off = jnp.asarray(cache_offset if kv_cache is not None else 0)
        positions = jnp.arange(s)[None, :] + (off[:, None] if off.ndim else off)
        positions = jnp.broadcast_to(positions, (b, s))
    x = jnp.take(params["wte.weight"], tokens, axis=0) + jnp.take(
        params["wpe.weight"], positions, axis=0
    )
    x = x.astype(cfg.dtype)
    head_dim = cfg.hidden_size // cfg.num_heads
    new_cache: dict | None = {} if kv_cache is not None else None
    for i in range(cfg.num_layers):
        p = f"h.{i}."
        h = _layer_norm(x, params[p + "ln_1.weight"], params[p + "ln_1.bias"], cfg.layer_norm_eps)
        qkv = _conv1d(h, params[p + "attn.c_attn.weight"], params[p + "attn.c_attn.bias"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, cfg.num_heads, head_dim)
        k = k.reshape(b, s, cfg.num_heads, head_dim)
        v = v.reshape(b, s, cfg.num_heads, head_dim)
        if kv_cache is not None and paged_table is not None:
            from modelx_tpu.ops.paged_attention import paged_attention, write_token_kv

            if s != 1:  # static shape: fails clearly at trace time
                raise ValueError(
                    f"paged decode is single-token only (got seq len {s})"
                )
            ck = write_token_kv(kv_cache[f"k{i}"], k, paged_table, cache_offset)
            cv = write_token_kv(kv_cache[f"v{i}"], v, paged_table, cache_offset)
            new_cache[f"k{i}"], new_cache[f"v{i}"] = ck, cv
            out = paged_attention(
                q[:, 0], ck, cv, paged_table, cache_offset + 1
            )[:, None]
        else:
            if kv_cache is not None:
                # a ragged batch appends each row at its own position
                ck = write_rows(kv_cache[f"k{i}"], k, cache_offset, mesh)
                cv = write_rows(kv_cache[f"v{i}"], v, cache_offset, mesh)
                new_cache[f"k{i}"], new_cache[f"v{i}"] = ck, cv
                k_att, v_att = ck, cv
                q_offset = cache_offset
            else:
                k_att, v_att, q_offset = k, v, 0
            out = attn_ops.attention_reference(
                q.transpose(0, 2, 1, 3),
                k_att.transpose(0, 2, 1, 3),
                v_att.transpose(0, 2, 1, 3),
                causal=True,
                q_offset=q_offset,
            )
            out = out.transpose(0, 2, 1, 3)
        out = out.reshape(b, s, cfg.hidden_size)
        x = x + _conv1d(out, params[p + "attn.c_proj.weight"], params[p + "attn.c_proj.bias"])
        h = _layer_norm(x, params[p + "ln_2.weight"], params[p + "ln_2.bias"], cfg.layer_norm_eps)
        h = jax.nn.gelu(_conv1d(h, params[p + "mlp.c_fc.weight"], params[p + "mlp.c_fc.bias"]), approximate=True)
        x = x + _conv1d(h, params[p + "mlp.c_proj.weight"], params[p + "mlp.c_proj.bias"])
    x = _layer_norm(x, params["ln_f.weight"], params["ln_f.bias"], cfg.layer_norm_eps)
    logits = jax.lax.dot_general(
        x, params["wte.weight"], (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    return logits, new_cache


def check_context(cfg: GPT2Config, last_pos: int) -> None:
    """Positions past the learned wpe table CLAMP inside jit and return
    plausible garbage; the generate loops (dl/families) ask this first and
    refuse up front instead. The bound is on positions actually decoded —
    bucketed paths deliberately over-allocate CACHE beyond prompt+max_new,
    which is harmless."""
    if last_pos > cfg.n_positions:
        raise ValueError(
            f"prompt + max_new_tokens needs {last_pos} positions, but this "
            f"gpt2 has n_positions={cfg.n_positions} — exceeds the model's "
            "position context"
        )


def init_kv_cache(cfg: GPT2Config, batch: int, max_len: int, dtype=None) -> dict:
    dtype = dtype or cfg.dtype
    head_dim = cfg.hidden_size // cfg.num_heads
    return {
        f"{kind}{i}": jnp.zeros((batch, max_len, cfg.num_heads, head_dim), dtype)
        for i in range(cfg.num_layers)
        for kind in ("k", "v")
    }
