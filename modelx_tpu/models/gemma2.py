"""Gemma2-family decoder.

Same pure-functional shape as models/llama.py (flat HF-named param dict,
static-shape KV cache, mesh-aware sharding constraints), with the gemma2
architectural deltas implemented to match HF `Gemma2ForCausalLM` exactly:

- RMSNorm stores ``w`` and scales by ``(1 + w)``, multiplying in float32
  BEFORE the cast back (checkpoint norm weights are zeros-centered);
- embeddings are scaled by ``sqrt(hidden_size)`` (cast to the compute
  dtype first, matching HF's normalizer tensor);
- FOUR norms per layer: attention and FFN outputs are each re-normalized
  before their residual add;
- GeGLU FFN: ``down(gelu_tanh(gate(x)) * up(x))``;
- attention scales by ``query_pre_attn_scalar**-0.5`` (not head_dim),
  softcaps attention logits at ``attn_logit_softcap`` and final logits at
  ``final_logit_softcap``;
- every EVEN layer uses sliding-window attention (window 4096 in released
  checkpoints), odd layers attend globally;
- embeddings are always tied (no lm_head.weight in checkpoints).

Both hot attention paths carry the gemma2 semantics natively: prefill on
TPU rides the pallas flash kernel (scale/softcap/window live inside the
online-softmax loop, with window-aware k-block skipping — long-context
prefill does O(S * window) work on the sliding layers instead of O(S^2)),
and the continuous engine's ``--kv-attention in-place`` paged decode
reads the page pools directly (ops/paged_attention carries the same
kwargs). Cached dense decode and CPU tests use the reference path.

No reference counterpart (kubegems/modelx stores checkpoints without
executing them); family surface mirrors `pkg/client` model-agnosticism.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from modelx_tpu.models.llama import ShardingCtx, _rope
from modelx_tpu.ops import attention as attn_ops
from modelx_tpu.ops.kv_write import write_rows
from modelx_tpu.ops.nn import linear as _linear


@dataclasses.dataclass(frozen=True)
class Gemma2Config:
    vocab_size: int = 256000
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_layers: int = 26
    num_heads: int = 8
    num_kv_heads: int = 4
    head_dim: int = 256
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    query_pre_attn_scalar: float = 256.0
    attn_logit_softcap: float = 50.0
    final_logit_softcap: float = 30.0
    sliding_window: int = 4096
    dtype: Any = jnp.bfloat16

    @classmethod
    def gemma2_2b(cls) -> "Gemma2Config":
        return cls()

    @classmethod
    def gemma2_9b(cls) -> "Gemma2Config":
        return cls(hidden_size=3584, intermediate_size=14336, num_layers=42,
                   num_heads=16, num_kv_heads=8)

    @classmethod
    def tiny(cls, vocab_size: int = 512) -> "Gemma2Config":
        """Test/dry-run config: real structure (incl. a sliding window small
        enough for short tests to actually exercise), toy sizes."""
        return cls(
            vocab_size=vocab_size, hidden_size=128, intermediate_size=256,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
            query_pre_attn_scalar=32.0, sliding_window=16,
        )


# -- params -------------------------------------------------------------------

LAYER_PARAM_SUFFIXES = (
    "self_attn.q_proj.weight",
    "self_attn.k_proj.weight",
    "self_attn.v_proj.weight",
    "self_attn.o_proj.weight",
    "mlp.gate_proj.weight",
    "mlp.up_proj.weight",
    "mlp.down_proj.weight",
    "input_layernorm.weight",
    "post_attention_layernorm.weight",
    "pre_feedforward_layernorm.weight",
    "post_feedforward_layernorm.weight",
)


def param_shapes(cfg: Gemma2Config) -> dict[str, tuple[int, ...]]:
    """HF layout: linear weights are [out_features, in_features]; embeddings
    tied (no lm_head)."""
    e, q = cfg.hidden_size, cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    f = cfg.intermediate_size
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_size, e),
        "model.norm.weight": (e,),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        shapes.update({
            p + "self_attn.q_proj.weight": (q, e),
            p + "self_attn.k_proj.weight": (kv, e),
            p + "self_attn.v_proj.weight": (kv, e),
            p + "self_attn.o_proj.weight": (e, q),
            p + "mlp.gate_proj.weight": (f, e),
            p + "mlp.up_proj.weight": (f, e),
            p + "mlp.down_proj.weight": (e, f),
            p + "input_layernorm.weight": (e,),
            p + "post_attention_layernorm.weight": (e,),
            p + "pre_feedforward_layernorm.weight": (e,),
            p + "post_feedforward_layernorm.weight": (e,),
        })
    return shapes


def init_params(cfg: Gemma2Config, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    keys = jax.random.split(key, len(shapes))
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.endswith("norm.weight"):
            # gemma2 norms scale by (1 + w): the stored weight is
            # zeros-centered, and init must match or parity tests would
            # silently test the llama convention
            params[name] = jnp.zeros(shape, dtype)
        else:
            fan_in = shape[-1]
            params[name] = (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)
    return params


# -- forward ------------------------------------------------------------------


def _rms_norm(x, weight, eps: float):
    """Gemma2 convention: norm AND the (1 + w) scale both in float32, cast
    back after (HF PR 29402 — differs from llama's cast-then-scale)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def _attend(q, k, v, cfg: Gemma2Config, q_offset, window: int,
            prefill: bool = False, mesh: "Mesh | None" = None):
    """[B,S,H,D] in/out; gemma2's scale + softcap (+ sliding window on even
    layers). Prefill on TPU rides the pallas flash kernel (it carries the
    same scale/softcap/window semantics, with window-aware block skipping);
    cached decode uses the reference path (per-row q_offset vectors), and
    so do sequence-parallel meshes — the pallas kernel doesn't model sp
    partitioning (ring attention doesn't model softcap/window yet), while
    XLA partitions the reference einsums under the sp constraints."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kwargs = dict(scale=cfg.query_pre_attn_scalar ** -0.5,
                  logit_softcap=cfg.attn_logit_softcap, window=window)
    sp_active = (mesh is not None and "sp" in mesh.axis_names
                 and mesh.shape["sp"] > 1)
    flash = prefill and not sp_active and jax.default_backend() == "tpu"
    attn_ops.note_choice("flash" if flash else "reference",
                         qt.shape[2], kt.shape[2], mesh,
                         group=qt.shape[1] // kt.shape[1])
    if flash:
        out = attn_ops.flash_attention(qt, kt, vt, causal=True, mesh=mesh, **kwargs)
    else:
        out = attn_ops.attention_reference(
            qt, kt, vt, causal=True, q_offset=q_offset, **kwargs
        )
    return out.transpose(0, 2, 1, 3)


def decoder_layer(
    lp: dict[str, jax.Array],
    x: jax.Array,
    positions: jax.Array,
    cfg: Gemma2Config,
    ctx: ShardingCtx,
    layer_idx: int,
    cache: tuple[jax.Array, jax.Array] | None = None,
    cache_offset: int | jax.Array = 0,
    paged_table: jax.Array | None = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """One gemma2 block: sandwich norms around both halves; even layers
    slide their attention window. ``paged_table`` switches the cached path
    to PAGED layout (page pools + block table, single-token steps), like
    llama's decoder_layer."""
    b, s = x.shape[:2]
    window = cfg.sliding_window if layer_idx % 2 == 0 else 0
    h = _rms_norm(x, lp["input_layernorm.weight"], cfg.rms_eps)
    q = _linear(h, lp["self_attn.q_proj.weight"])
    k = _linear(h, lp["self_attn.k_proj.weight"])
    v = _linear(h, lp["self_attn.v_proj.weight"])
    q = ctx.constrain(q.reshape(b, s, cfg.num_heads, cfg.head_dim), "dp", "sp", "tp", None)
    k = ctx.constrain(k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim), "dp", "sp", "tp", None)
    v = ctx.constrain(v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim), "dp", "sp", "tp", None)
    q = ctx.constrain(_rope(q, positions, cfg.rope_theta), "dp", "sp", "tp", None)
    k = ctx.constrain(_rope(k, positions, cfg.rope_theta), "dp", "sp", "tp", None)

    new_cache: tuple[jax.Array, jax.Array] | None = None
    if cache is not None and paged_table is not None:
        from modelx_tpu.ops.paged_attention import paged_attention, write_token_kv

        if s != 1:  # static shape: fails clearly at trace time
            raise ValueError(
                f"paged decode is single-token only (got seq len {s}); "
                "multi-token blocks (spec verify) take the dense path"
            )
        ck, cv = cache  # pools [P, ps, Hkv, D]
        ck = write_token_kv(ck, k, paged_table, cache_offset)
        cv = write_token_kv(cv, v, paged_table, cache_offset)
        new_cache = (ck, cv)
        attn_out = paged_attention(
            q[:, 0], ck, cv, paged_table, cache_offset + 1,
            scale=cfg.query_pre_attn_scalar ** -0.5,
            logit_softcap=cfg.attn_logit_softcap, window=window,
        )[:, None]  # [B, 1, Hq, D]
    elif cache is not None:
        ck, cv = cache
        ck = write_rows(ck, k, cache_offset, ctx.mesh)
        cv = write_rows(cv, v, cache_offset, ctx.mesh)
        new_cache = (ck, cv)
        attn_out = _attend(q, ck, cv, cfg, q_offset=cache_offset, window=window)
    else:
        attn_out = _attend(q, k, v, cfg, q_offset=0, window=window,
                           prefill=True, mesh=ctx.mesh)

    attn_out = attn_out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    attn_out = _linear(attn_out, lp["self_attn.o_proj.weight"])
    x = x + _rms_norm(attn_out, lp["post_attention_layernorm.weight"], cfg.rms_eps)
    x = ctx.constrain(x, "dp", "sp", None)

    h = _rms_norm(x, lp["pre_feedforward_layernorm.weight"], cfg.rms_eps)
    gate = _linear(h, lp["mlp.gate_proj.weight"])
    up = _linear(h, lp["mlp.up_proj.weight"])
    ff = ctx.constrain(jax.nn.gelu(gate, approximate=True) * up, "dp", "sp", "tp")
    ff = _linear(ff, lp["mlp.down_proj.weight"])
    x = x + _rms_norm(ff, lp["post_feedforward_layernorm.weight"], cfg.rms_eps)
    return ctx.constrain(x, "dp", "sp", None), new_cache


def forward(
    params: dict[str, jax.Array],
    tokens: jax.Array,
    cfg: Gemma2Config,
    positions: jax.Array | None = None,
    kv_cache: dict | None = None,
    cache_offset: int | jax.Array = 0,
    mesh: Mesh | None = None,
    paged_table: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """Returns (logits [B,S,V], updated kv_cache). Prefill: kv_cache=None;
    decode: pass the cache and offset with tokens [B, 1]. With
    ``paged_table``, kv_cache holds PAGE POOLS read in place."""
    ctx = ShardingCtx(mesh)
    b, s = tokens.shape
    if positions is None:
        off = jnp.asarray(cache_offset if kv_cache is not None else 0)
        positions = jnp.arange(s)[None, :] + (off[:, None] if off.ndim else off)
        positions = jnp.broadcast_to(positions, (b, s))

    x = jnp.take(params["model.embed_tokens.weight"], tokens, axis=0).astype(cfg.dtype)
    # HF casts the sqrt(hidden) normalizer to the compute dtype BEFORE the
    # multiply — replicate so bf16 runs stay bit-comparable
    x = x * jnp.asarray(math.sqrt(cfg.hidden_size), cfg.dtype)
    x = ctx.constrain(x, "dp", "sp", None)

    new_cache: dict | None = {} if kv_cache is not None else None
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        lp = {suffix: params[p + suffix] for suffix in LAYER_PARAM_SUFFIXES}
        cache = (kv_cache[f"k{i}"], kv_cache[f"v{i}"]) if kv_cache is not None else None
        x, updated = decoder_layer(
            lp, x, positions, cfg, ctx, i, cache=cache, cache_offset=cache_offset,
            paged_table=paged_table,
        )
        if updated is not None:
            new_cache[f"k{i}"], new_cache[f"v{i}"] = updated

    x = _rms_norm(x, params["model.norm.weight"], cfg.rms_eps)
    logits = _linear(x, params["model.embed_tokens.weight"])  # tied head
    if cfg.final_logit_softcap > 0.0:
        cap = cfg.final_logit_softcap
        logits = (cap * jnp.tanh(logits.astype(jnp.float32) / cap)).astype(logits.dtype)
    return ctx.constrain(logits, "dp", "sp", None), new_cache


# -- kv cache + decode --------------------------------------------------------


def init_kv_cache(cfg: Gemma2Config, batch: int, max_len: int, dtype=None) -> dict:
    dtype = dtype or cfg.dtype
    cache = {}
    for i in range(cfg.num_layers):
        shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        cache[f"k{i}"] = jnp.zeros(shape, dtype)
        cache[f"v{i}"] = jnp.zeros(shape, dtype)
    return cache
