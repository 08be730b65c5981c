"""Llama-family decoder (the flagship serve/train model).

Pure-functional JAX: params are a flat dict keyed by HF safetensors names
("model.layers.N.self_attn.q_proj.weight", ...) so checkpoints pulled from
the registry load directly onto a mesh (dl/loader.py + dl/sharding.py
LLAMA_RULES) with no renaming.

TPU-first choices:

- everything runs in bfloat16 with fp32 accumulation in the matmuls
  (preferred_element_type) — MXU-native;
- attention dispatches to the pallas flash kernel on TPU, ring attention
  when a sequence-parallel axis is present, reference jnp otherwise;
- activation shardings are asserted with with_sharding_constraint using the
  standard megatron layout: batch over dp, sequence over sp, heads/ffn over
  tp — XLA inserts the all-reduces (psum over tp after o_proj/down_proj)
  itself, which is exactly the GSPMD contract (scaling-book recipe);
- no data-dependent Python control flow in the forward; decode uses a
  static-shape KV cache updated with dynamic_update_slice.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from modelx_tpu.ops import attention as attn_ops
from modelx_tpu.ops.kv_write import write_rows
from modelx_tpu.ops.nn import linear as _linear


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    # Qwen2-style attention input biases on q/k/v (the only architectural
    # delta between the llama and qwen2 families; same decoder otherwise)
    qkv_bias: bool = False
    dtype: Any = jnp.bfloat16

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        return cls(
            hidden_size=8192, intermediate_size=28672, num_layers=80,
            num_heads=64, num_kv_heads=8,
        )

    @classmethod
    def tiny(cls, vocab_size: int = 512) -> "LlamaConfig":
        """Test/dry-run config: real structure, toy sizes."""
        return cls(
            vocab_size=vocab_size, hidden_size=128, intermediate_size=256,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
            rope_theta=10000.0,
        )


# -- params -------------------------------------------------------------------


def param_names(cfg: LlamaConfig) -> list[str]:
    names = ["model.embed_tokens.weight", "model.norm.weight"]
    if not cfg.tie_embeddings:
        names.append("lm_head.weight")
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        names += [
            p + "self_attn.q_proj.weight",
            p + "self_attn.k_proj.weight",
            p + "self_attn.v_proj.weight",
            p + "self_attn.o_proj.weight",
            p + "mlp.gate_proj.weight",
            p + "mlp.up_proj.weight",
            p + "mlp.down_proj.weight",
            p + "input_layernorm.weight",
            p + "post_attention_layernorm.weight",
        ]
        if cfg.qkv_bias:
            names += [p + s for s in BIAS_SUFFIXES]
    return names


def param_shapes(cfg: LlamaConfig) -> dict[str, tuple[int, ...]]:
    """HF layout: linear weights are [out_features, in_features]."""
    e, q = cfg.hidden_size, cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    f = cfg.intermediate_size
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_size, e),
        "model.norm.weight": (e,),
    }
    if not cfg.tie_embeddings:
        shapes["lm_head.weight"] = (cfg.vocab_size, e)
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        shapes.update(
            {
                p + "self_attn.q_proj.weight": (q, e),
                p + "self_attn.k_proj.weight": (kv, e),
                p + "self_attn.v_proj.weight": (kv, e),
                p + "self_attn.o_proj.weight": (e, q),
                p + "mlp.gate_proj.weight": (f, e),
                p + "mlp.up_proj.weight": (f, e),
                p + "mlp.down_proj.weight": (e, f),
                p + "input_layernorm.weight": (e,),
                p + "post_attention_layernorm.weight": (e,),
            }
        )
        if cfg.qkv_bias:
            shapes[p + "self_attn.q_proj.bias"] = (q,)
            shapes[p + "self_attn.k_proj.bias"] = (kv,)
            shapes[p + "self_attn.v_proj.bias"] = (kv,)
    return shapes


def init_params(cfg: LlamaConfig, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    keys = jax.random.split(key, len(shapes))
    for (name, shape), k in zip(sorted(shapes.items()), keys):
        if name.endswith("layernorm.weight") or name.endswith("norm.weight"):
            params[name] = jnp.ones(shape, dtype)
        elif name.endswith(".bias"):
            # small random biases (not zeros): parity tests must catch a
            # forward that forgets to add them
            params[name] = (jax.random.normal(k, shape) * 0.05).astype(dtype)
        else:
            fan_in = shape[-1]
            params[name] = (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)
    return params


# -- forward ------------------------------------------------------------------


def _rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def _rope(x, positions, theta: float):
    """Rotary embeddings. x: [B, S, H, D], positions: [B, S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Activation-sharding constraints; None mesh = no constraints."""

    mesh: Mesh | None = None

    def constrain(self, x, *spec):
        if self.mesh is None:
            return x
        names = set(self.mesh.axis_names)
        cleaned = []
        for dim, s in zip(x.shape, spec):
            # "dp" means the batch dimension: fsdp ranks consume their own
            # batch slice too (ZeRO data parallelism), so the batch shards
            # over every data-ish axis present
            cand = ("dp", "fsdp") if s == "dp" else (s,)
            kept = tuple(a for a in cand if a in names)
            # drop axes the mesh lacks or that don't divide the dim (e.g. GQA
            # kv heads smaller than tp)
            if kept and dim % math.prod(self.mesh.shape[a] for a in kept) == 0:
                cleaned.append(kept if len(kept) > 1 else kept[0])
            else:
                cleaned.append(None)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, P(*cleaned)))


def decoder_layer(
    lp: dict[str, jax.Array],
    x: jax.Array,
    positions: jax.Array,
    cfg: LlamaConfig,
    ctx: "ShardingCtx",
    cache: tuple[jax.Array, jax.Array] | None = None,
    cache_offset: int | jax.Array = 0,
    mesh: Mesh | None = None,
    attention_impl: str = "auto",
    mlp_fn=None,
    paged_table: jax.Array | None = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """One transformer block. ``lp`` holds the layer's params keyed by the
    unprefixed HF suffix ("self_attn.q_proj.weight", ...). Returns
    (x, updated (k,v) cache or None).

    ``mlp_fn(h)`` replaces the dense SwiGLU FFN when given (the post-norm
    hidden states go in, the FFN output comes out) — Mixtral passes its
    sparse-MoE block here so the attention half stays shared.

    ``paged_table`` switches the cached-decode path to PAGED layout: the
    cache leaves are page pools [P, page_size, Hkv, D], the table maps each
    row to its pages, and attention reads the pool in place
    (ops/paged_attention.py) — single-token steps only (s == 1), the shape
    the continuous engine's chunk scan drives.

    Which attention runs, by what the call observes. No cache: ``_attend``
    (flash on a TPU, ring under an sp axis, else the reference). A dense
    cache ``[B, L, Hkv, D]``: the new keys and values are written
    (``ops.kv_write.write_rows``: a per-row start is a scatter, or on one TPU
    device where a position's line is whole tiles a kernel that copies each
    row's line to its place), then ``ops.attention.cached_attention`` — one token a
    row at per-row offsets, head_dim a multiple of 128, KV heads a multiple
    of 8, ``L`` two blocks or more, on one TPU device takes the ragged kernel
    that reads each row's KV blocks up to its own context (the engine's decode
    step for llama-3 or Mixtral shapes); an admission's prefill into its
    scratch cache, a scalar offset, head_dim 96 (every Phi-3 program), the
    CPU and a mesh of several devices keep ``attention_reference`` over all
    ``L`` positions, lowered as before. ``attention_impl`` ``"ragged"``
    (``"ragged+interpret"`` on the CPU) asks for the kernel by name."""
    b, s = x.shape[:2]
    h = _rms_norm(x, lp["input_layernorm.weight"], cfg.rms_eps)
    q = _linear(h, lp["self_attn.q_proj.weight"], lp.get("self_attn.q_proj.bias"))
    k = _linear(h, lp["self_attn.k_proj.weight"], lp.get("self_attn.k_proj.bias"))
    v = _linear(h, lp["self_attn.v_proj.weight"], lp.get("self_attn.v_proj.bias"))
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
            q[:, 0], ck, cv, paged_table, cache_offset + 1
        )[:, None]  # [B, 1, Hq, D]
    elif cache is not None:
        ck, cv = cache
        # a ragged batch appends each row at its own position
        ck = write_rows(ck, k, cache_offset, mesh)
        cv = write_rows(cv, v, cache_offset, mesh)
        new_cache = (ck, cv)
        attn_out = attn_ops.cached_attention(q, ck, cv, cache_offset, impl=attention_impl,
                                             mesh=mesh)
    else:
        attn_out = _attend(q, k, v, cfg, causal=True, mesh=mesh, impl=attention_impl)

    attn_out = attn_out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    x = x + _linear(attn_out, lp["self_attn.o_proj.weight"])
    x = ctx.constrain(x, "dp", "sp", None)

    h = _rms_norm(x, lp["post_attention_layernorm.weight"], cfg.rms_eps)
    if mlp_fn is not None:
        x = x + mlp_fn(h)
    else:
        gate = _linear(h, lp["mlp.gate_proj.weight"])
        up = _linear(h, lp["mlp.up_proj.weight"])
        ff = ctx.constrain(jax.nn.silu(gate) * up, "dp", "sp", "tp")
        x = x + _linear(ff, lp["mlp.down_proj.weight"])
    return ctx.constrain(x, "dp", "sp", None), new_cache


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
)

# optional per-layer params (qwen2's qkv biases); present iff cfg.qkv_bias
BIAS_SUFFIXES = (
    "self_attn.q_proj.bias",
    "self_attn.k_proj.bias",
    "self_attn.v_proj.bias",
)


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
    """Returns (logits [B,S,V], updated kv_cache).

    Prefill: kv_cache=None. Decode: pass the cache and the current offset;
    tokens is [B, 1]. With ``paged_table``, kv_cache holds PAGE POOLS and
    attention reads them in place (see decoder_layer; single-token decode).
    """
    ctx = ShardingCtx(mesh)
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
        lp = {suffix: params[p + suffix] for suffix in LAYER_PARAM_SUFFIXES}
        for suffix in BIAS_SUFFIXES:
            if p + suffix in params:
                lp[suffix] = params[p + suffix]
        cache = (kv_cache[f"k{i}"], kv_cache[f"v{i}"]) if kv_cache is not None else None
        x, updated = decoder_layer(
            lp, x, positions, cfg, ctx, cache=cache, cache_offset=cache_offset,
            mesh=mesh, attention_impl=attention_impl, paged_table=paged_table,
        )
        if updated is not None:
            new_cache[f"k{i}"], new_cache[f"v{i}"] = updated

    x = _rms_norm(x, params["model.norm.weight"], cfg.rms_eps)
    head = params.get("lm_head.weight", params["model.embed_tokens.weight"])
    logits = _linear(x, head)
    return ctx.constrain(logits, "dp", "sp", None), new_cache


def _attend(q, k, v, cfg: LlamaConfig, causal: bool, mesh, impl: str):
    """The cache-less forward's attention. q: [B,S,H,D], k/v: [B,S(,kv)...].
    Transposes to [B,H,S,D] and picks the implementation. ``"auto"`` picks from what it can observe
    (an sp axis -> ring; the TPU backend -> the pallas kernel; else the jnp
    reference) and the pick is recorded (``attn_ops.note_choice``). A
    ``"+interpret"`` suffix (``"flash+interpret"``) runs the kernel in
    pallas interpret mode: callers on the CPU ask for it by name."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    impl, _, flag = impl.partition("+")
    if impl in ("auto", "ragged"):  # "ragged" names the cached decode's kernel only
        if mesh is not None and "sp" in mesh.axis_names and mesh.shape["sp"] > 1:
            impl = "ring"
        elif jax.default_backend() == "tpu":
            impl = "flash"
        else:
            impl = "reference"
    attn_ops.note_choice(impl, qt.shape[2], kt.shape[2], mesh,
                         group=qt.shape[1] // kt.shape[1])
    interpret = flag == "interpret"
    if impl == "ring":
        out = attn_ops.ring_attention(qt, kt, vt, mesh, axis="sp", causal=causal)
    elif impl == "ulysses":
        out = attn_ops.ulysses_attention(qt, kt, vt, mesh, axis="sp", causal=causal,
                                         interpret=interpret)
    elif impl == "flash":
        out = attn_ops.flash_attention(qt, kt, vt, causal=causal, mesh=mesh,
                                       interpret=interpret)
    else:
        out = attn_ops.attention_reference(qt, kt, vt, causal=causal)
    return out.transpose(0, 2, 1, 3)


# -- kv cache + greedy decode -------------------------------------------------


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=None) -> dict:
    dtype = dtype or cfg.dtype
    cache = {}
    for i in range(cfg.num_layers):
        shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        cache[f"k{i}"] = jnp.zeros(shape, dtype)
        cache[f"v{i}"] = jnp.zeros(shape, dtype)
    return cache
