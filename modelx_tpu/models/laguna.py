"""Laguna-family decoder: layers that differ in kind.

One model, per layer its own attention kind (``full_attention`` or
``sliding_attention`` with a window), its own query-head count, its own
rope (YaRN on half of each head for the full layers, plain full-head rope
for the sliding ones), a per-head sigmoid gate on the attention output, and
an FFN that is dense on the leading layers and a sparse expert layer with a
shared expert after them (ops/moe.moe_share_ffn). The plain float32
reference of the same equations is ``models/laguna_reference.py``.

Params are a flat dict keyed by the checkpoint's names, the experts stacked
along a leading axis (the loader folds ``experts.<i>.*``):

    model.layers.N.self_attn.{q,k,v,o,g}_proj.weight
    model.layers.N.mlp.{gate,up,down}_proj.weight              dense layers
    model.layers.N.mlp.gate.weight                    [E_pub, D]   router
    model.layers.N.mlp.experts.{gate,up}_proj.weight  [E_held, F, D]
    model.layers.N.mlp.experts.down_proj.weight       [E_held, D, F]
    model.layers.N.mlp.shared_expert.{gate,up,down}_proj.weight

**The config comes from ``config.json``, never from tensor shapes**
(:func:`config_from_hf`): layer kinds, head counts, rope parameters, top-k
and the router's published width leave no trace in shapes. **The share
key**: a checkpoint may hold only some of the routed experts.
``num_experts`` then counts the experts held and ``"expert_share":
{"published": 256, "first": 0}`` says which of how many: the router keeps
its published width, routing and normalisation run over all of them, and
only the held experts' part of the sum is computed. What the absent experts
would add is left out — nothing here stands in for other chips.

Three shapes of KV state, one forward:

- none (``/v1/forward``): one pass, flash attention on a TPU;
- a dense ``[B, L]`` cache for every layer (an admission's scratch, the
  plain generate paths): window layers mask a window over it;
- the engine's state, a cache per layer kind (``ring=True``,
  dl/kv_layout.LayerKindKV): full layers ``[slots, max_len]``, window layers
  a ring of :func:`ring_len` positions written at ``position mod ring`` and
  masked by absolute position. Single-token steps only.

Served through the continuous engine this family **refuses at start-up**
what a ring — its ``"window"`` leaves, not the layout as such — cannot give:
``--prefix-cache`` (and with it the KV store's bundles and resume from stored
KV: a ring cannot give back a prefix it has overwritten), ``--speculative-k``
(a verify block writes several ring positions a step) and ``--kv-page-size``
(a ring is not paged); ``--prefill-chunk`` it carries: a piece sees a ring as
its slot's last positions in position order
(dl/kv_layout.LayerKindKV.refuse names the option, the leaf kind, the reason). Rope types other than ``default`` and
``yarn``, a gating other than ``per-head``, router soft-capping and router
weights applied on the input are refused when the config is read.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from modelx_tpu.models.decode import SEQ_BUCKET
from modelx_tpu.models.llama import ShardingCtx, _rms_norm
from modelx_tpu.ops import attention as attn_ops
from modelx_tpu.ops import moe as moe_ops
from modelx_tpu.ops.kv_write import write_rows
from modelx_tpu.ops.nn import linear as _linear
from modelx_tpu.ops.rope import yarn_inv_freq, yarn_mscale

FULL, SLIDING = "full_attention", "sliding_attention"
# the engine's counters of the expert layers, in the order the decode step
# accumulates them (dl/kv_layout.LayerKindKV reads them back with the tokens)
MOE_COUNTERS = ("assignments", "assignments_held", "experts_hit", "experts_read")


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One layer kind's rotary embedding, as ``rope_parameters`` gives it."""

    theta: float = 10000.0
    rope_type: str = "default"  # or "yarn"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None  # yarn: 0.1 ln(factor) + 1 when absent


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288  # the dense layers' MLP
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    layer_types: tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING)
    mlp_layer_types: tuple[str, ...] = ("dense", "sparse", "sparse", "sparse")
    num_heads_per_layer: tuple[int, ...] = (48, 72, 72, 72)
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    num_experts: int = 256  # the router's published width
    expert_first: int = 0  # the experts held here: first .. first + count
    expert_count: int = 256
    top_k: int = 10
    norm_topk_prob: bool = True
    routed_scale: float = 2.5
    rope_full: RopeSpec = RopeSpec(
        theta=500000.0, rope_type="yarn", partial_rotary_factor=0.5, factor=128.0,
        original_max_position_embeddings=8192, attention_factor=1.4852030263919618)
    rope_sliding: RopeSpec = RopeSpec()
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> tuple[int, int]:
        return self.expert_first, self.expert_count

    def window(self, layer: int) -> int:
        return self.sliding_window if self.layer_types[layer] == SLIDING else 0

    def rope(self, layer: int) -> RopeSpec:
        return self.rope_sliding if self.layer_types[layer] == SLIDING else self.rope_full

    @classmethod
    def tiny(cls, vocab_size: int = 512, **over) -> "LagunaConfig":
        """Test config: every mechanism at toy sizes — five layers (full +
        dense, three sliding, full), two head counts, window 16, 16 experts
        top-4 with a shared one, YaRN on half a head."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
            mlp_layer_types=("dense",) + ("sparse",) * 4,
            num_heads_per_layer=(4, 6, 6, 6, 4), num_kv_heads=2, head_dim=16,
            sliding_window=16, num_experts=16, expert_first=0, expert_count=16, top_k=4,
            rope_full=RopeSpec(theta=500000.0, rope_type="yarn", partial_rotary_factor=0.5,
                               factor=8.0, original_max_position_embeddings=32),
            dtype=jnp.float32)
        base.update(over)
        return cls(**base)


def _rope_spec(raw: Mapping) -> RopeSpec:
    kind = str(raw.get("rope_type") or raw.get("type") or "default").lower()
    if kind not in ("default", "yarn"):
        raise ValueError(f"laguna: rope_type {kind!r} is not implemented (default, yarn)")
    return RopeSpec(
        theta=float(raw.get("rope_theta", 10000.0)), rope_type=kind,
        partial_rotary_factor=float(raw.get("partial_rotary_factor", 1.0)),
        factor=float(raw.get("factor", 1.0)),
        original_max_position_embeddings=int(raw.get("original_max_position_embeddings", 0)),
        beta_fast=float(raw.get("beta_fast", 32.0)), beta_slow=float(raw.get("beta_slow", 1.0)),
        attention_factor=(float(raw["attention_factor"])
                          if raw.get("attention_factor") is not None else None))


def config_from_hf(raw: Mapping, dtype=jnp.bfloat16) -> LagunaConfig:
    """The config of a checkpoint from its ``config.json``. Raises for what
    this family does not implement rather than serving something else."""
    n = int(raw["num_hidden_layers"])
    layer_types = tuple(raw["layer_types"])[:n]
    mlp_types = tuple(raw.get("mlp_layer_types") or (
        "dense" if i in set(raw.get("mlp_only_layers", ())) else "sparse" for i in range(n)))[:n]
    heads = tuple(int(h) for h in raw.get("num_attention_heads_per_layer")
                  or [raw["num_attention_heads"]] * n)[:n]
    if not (len(layer_types) == len(mlp_types) == len(heads) == n):
        raise ValueError(f"laguna: per-layer lists are shorter than num_hidden_layers={n}")
    bad = set(layer_types) - {FULL, SLIDING}
    if bad:
        raise ValueError(f"laguna: unknown layer type(s) {sorted(bad)}")
    if raw.get("gating", "per-head") != "per-head":
        raise ValueError(f"laguna: gating {raw.get('gating')!r} is not implemented (per-head)")
    if raw.get("moe_router_logit_softcapping"):
        raise ValueError("laguna: router logit soft-capping is not implemented")
    if raw.get("moe_apply_router_weight_on_input"):
        raise ValueError("laguna: router weights on the expert input are not implemented")
    if int(raw.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("laguna: decoder_sparse_step other than 1 is not implemented")
    if raw.get("attention_bias"):
        raise ValueError("laguna: attention biases are not implemented")
    held = int(raw["num_experts"])
    share = raw.get("expert_share") or {}
    published, first = int(share.get("published", held)), int(share.get("first", 0))
    if first < 0 or first + held > published:
        raise ValueError(f"laguna: expert_share holds {first}..{first + held} "
                         f"of {published} published experts")
    ropes = raw.get("rope_parameters") or {}
    return LagunaConfig(
        vocab_size=int(raw["vocab_size"]), hidden_size=int(raw["hidden_size"]),
        intermediate_size=int(raw["intermediate_size"]),
        moe_intermediate_size=int(raw["moe_intermediate_size"]),
        shared_expert_intermediate_size=int(raw.get("shared_expert_intermediate_size", 0)),
        layer_types=layer_types, mlp_layer_types=mlp_types, num_heads_per_layer=heads,
        num_kv_heads=int(raw["num_key_value_heads"]), head_dim=int(raw["head_dim"]),
        sliding_window=int(raw.get("sliding_window") or 0),
        num_experts=published, expert_first=first, expert_count=held,
        top_k=int(raw["num_experts_per_tok"]),
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        routed_scale=float(raw.get("moe_routed_scaling_factor", 1.0)),
        rope_full=_rope_spec(ropes.get(FULL, {})), rope_sliding=_rope_spec(ropes.get(SLIDING, {})),
        rms_eps=float(raw.get("rms_norm_eps", 1e-6)), dtype=dtype)


def to_hf_config(cfg: LagunaConfig) -> dict:
    """The ``config.json`` that :func:`config_from_hf` reads back as ``cfg``
    (test checkpoints, and the reference, which reads the architecture from
    this and not from ``cfg``)."""

    def rope(spec: RopeSpec) -> dict:
        out = {"rope_type": spec.rope_type, "rope_theta": spec.theta,
               "partial_rotary_factor": spec.partial_rotary_factor}
        if spec.rope_type == "yarn":
            out.update(factor=spec.factor, beta_fast=spec.beta_fast, beta_slow=spec.beta_slow,
                       original_max_position_embeddings=spec.original_max_position_embeddings,
                       attention_factor=spec.attention_factor)
        return out

    return {
        "model_type": "laguna", "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads_per_layer[0],
        "num_attention_heads_per_layer": list(cfg.num_heads_per_layer),
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.rms_eps, "num_experts": cfg.expert_count,
        "expert_share": {"published": cfg.num_experts, "first": cfg.expert_first},
        "num_experts_per_tok": cfg.top_k, "moe_intermediate_size": cfg.moe_intermediate_size,
        "shared_expert_intermediate_size": cfg.shared_expert_intermediate_size,
        "norm_topk_prob": cfg.norm_topk_prob, "moe_routed_scaling_factor": cfg.routed_scale,
        "gating": "per-head", "sliding_window": cfg.sliding_window,
        "layer_types": list(cfg.layer_types), "mlp_layer_types": list(cfg.mlp_layer_types),
        "rope_parameters": {FULL: rope(cfg.rope_full), SLIDING: rope(cfg.rope_sliding)},
        "tie_word_embeddings": False,
    }


# -- params -------------------------------------------------------------------


def param_shapes(cfg: LagunaConfig) -> dict[str, tuple[int, ...]]:
    """Stacked-expert layout, linear weights [out, in]."""
    e, kv = cfg.hidden_size, cfg.num_kv_heads * cfg.head_dim
    f, fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_size, e),
        "model.norm.weight": (e,),
        "lm_head.weight": (cfg.vocab_size, e),
    }
    for i in range(cfg.num_layers):
        p, h = f"model.layers.{i}.", cfg.num_heads_per_layer[i]
        shapes.update({
            p + "self_attn.q_proj.weight": (h * cfg.head_dim, e),
            p + "self_attn.k_proj.weight": (kv, e),
            p + "self_attn.v_proj.weight": (kv, e),
            p + "self_attn.g_proj.weight": (h, e),
            p + "self_attn.o_proj.weight": (e, h * cfg.head_dim),
            p + "input_layernorm.weight": (e,),
            p + "post_attention_layernorm.weight": (e,),
        })
        if cfg.mlp_layer_types[i] == "dense":
            shapes.update({
                p + "mlp.gate_proj.weight": (cfg.intermediate_size, e),
                p + "mlp.up_proj.weight": (cfg.intermediate_size, e),
                p + "mlp.down_proj.weight": (e, cfg.intermediate_size),
            })
            continue
        shapes.update({
            p + "mlp.gate.weight": (cfg.num_experts, e),
            p + "mlp.experts.gate_proj.weight": (cfg.expert_count, f, e),
            p + "mlp.experts.up_proj.weight": (cfg.expert_count, f, e),
            p + "mlp.experts.down_proj.weight": (cfg.expert_count, e, f),
        })
        if fs:
            shapes.update({
                p + "mlp.shared_expert.gate_proj.weight": (fs, e),
                p + "mlp.shared_expert.up_proj.weight": (fs, e),
                p + "mlp.shared_expert.down_proj.weight": (e, fs),
            })
    return shapes


def init_params(cfg: LagunaConfig, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    for (name, shape), k in zip(sorted(shapes.items()), jax.random.split(key, len(shapes))):
        if name.endswith("norm.weight"):
            params[name] = jnp.ones(shape, dtype)
        else:
            params[name] = (jax.random.normal(k, shape) / math.sqrt(shape[-1])).astype(dtype)
    return params


def to_hf_state_dict(params: Mapping[str, Any], first: int = 0) -> dict[str, np.ndarray]:
    """Unstack the experts into the checkpoint's per-expert names
    (``experts.<first + j>.*``) — what a push holds."""
    out: dict[str, np.ndarray] = {}
    for name, value in params.items():
        if ".mlp.experts." in name:
            head, tail = name.split(".mlp.experts.")
            for j, w in enumerate(np.asarray(value)):
                out[f"{head}.mlp.experts.{first + j}.{tail}"] = w
        else:
            out[name] = np.asarray(value)
    return out


# -- rope ---------------------------------------------------------------------


def rope_frequencies(spec: RopeSpec, head_dim: int) -> tuple[np.ndarray, float, int]:
    """(inverse frequencies [rotated/2], the factor on cos and sin, rotated
    dims) of one layer kind. ``yarn`` follows HF ``_compute_yarn_parameters``:
    interpolated and extrapolated frequencies blended by a linear ramp
    between the dimensions that turn ``beta_fast`` and ``beta_slow`` times
    over the original context (``ops/rope.yarn_inv_freq``, which DeepSeek-V2's
    family shares)."""
    dim = int(head_dim * spec.partial_rotary_factor)
    if spec.rope_type == "default":
        pos_freqs = spec.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        return (1.0 / pos_freqs).astype(np.float32), 1.0, dim

    inv_freq = yarn_inv_freq(spec.theta, dim, spec.factor, spec.original_max_position_embeddings,
                             spec.beta_fast, spec.beta_slow)
    factor = spec.attention_factor
    if factor is None:
        factor = yarn_mscale(spec.factor)
    return inv_freq.astype(np.float32), float(factor), dim


def apply_rope(x, positions, spec: RopeSpec):
    """Rotate-half rope on the first ``partial_rotary_factor`` of each head,
    the rest passed through. x: [B, S, H, D]; positions: [B, S]."""
    inv_freq, factor, dim = rope_frequencies(spec, x.shape[-1])
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    x1, x2 = jnp.split(x[..., :dim].astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., dim:]], axis=-1)


# -- kv state -----------------------------------------------------------------


def ring_len(cfg: LagunaConfig) -> int:
    """Positions a window layer's ring holds a slot: the window plus one
    16-token bucket. An admission writes its prompt's whole 16-bucket, so up
    to 15 positions past the real prompt hold padding; with a bucket of
    slack what they displace lies outside every later query's window, and
    the mask by absolute position hides them until decode overwrites them."""
    return cfg.sliding_window + SEQ_BUCKET


def init_kv_cache(cfg: LagunaConfig, batch: int, max_len: int, dtype=None) -> dict:
    """A dense ``[batch, max_len]`` cache for every layer."""
    dtype = dtype or cfg.dtype
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {f"{kv}{i}": jnp.zeros(shape, dtype) for i in range(cfg.num_layers) for kv in "kv"}


def published(cfg: LagunaConfig) -> dict:
    """What a pod's /metrics names of this family: the counter leaves the
    decode step accumulates (leaf -> (stats block, its entries' names)) — of
    its expert layers, over ALL slots (idle ones route too) — and the gauges
    those counts are shares of."""
    return {
        "counters": {"moe_counts": ("moe", MOE_COUNTERS)},
        "gauges": {"moe": {"held_experts": cfg.expert_count,
                           "published_experts": cfg.num_experts,
                           "sparse_layers": cfg.mlp_layer_types.count("sparse")}},
    }


def cache_kinds(cfg: LagunaConfig) -> dict[str, str]:
    """Leaf name -> ``"full"`` / ``"window"`` / ``"counter"`` of the engine's
    state (:func:`init_layer_state`)."""
    kinds = {f"{kv}{i}": "window" if cfg.window(i) else "full"
             for i in range(cfg.num_layers) for kv in "kv"}
    kinds["moe_counts"] = "counter"
    return kinds


def init_layer_state(cfg: LagunaConfig, slots: int, max_len: int, dtype=None) -> dict:
    """The engine's state: full layers ``[slots, max_len]``, window layers a
    ring of ``min(ring_len, max_len)`` positions, and the expert layers'
    counters (:data:`MOE_COUNTERS`, wrapping int32)."""
    dtype = dtype or cfg.dtype
    ring = min(ring_len(cfg), max_len)
    state = {name: jnp.zeros((slots, ring if kind == "window" else max_len,
                              cfg.num_kv_heads, cfg.head_dim), dtype)
             for name, kind in cache_kinds(cfg).items() if kind != "counter"}
    state["moe_counts"] = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    return state


# -- forward ------------------------------------------------------------------


def _attention(q, k, v, cfg: LagunaConfig, layer: int, ctx: ShardingCtx, cache,
               cache_offset, ring: bool, attention_impl: str, ring_start=None):
    """q [B,S,H,D], k/v [B,S,Hkv,D] after rope -> ([B,S,H,D], new cache).

    No cache: flash on a TPU, else the reference. A cache: the new keys and
    values are written (``ops.kv_write.write_rows``, full layers and rings
    alike), then ``ops.attention.cached_attention`` picks by what
    it observes — a decode step (one token a row at per-row offsets, 128-wide
    heads, on one TPU device) takes a kernel: a full layer's over ``[slots,
    max_len]`` the ragged one, which reads each row's KV blocks up to its own
    context, a window layer's over its ring the ring one, which reads each
    ring once where it lies and masks by each index's age; a window over a
    dense cache, an admission's prefill and the CPU keep
    ``attention_reference``. ``attention_impl`` ``"ragged"``
    (``"ragged+interpret"`` on the CPU) asks for the kernels by name.
    ``ring_start`` (a prefill piece over the engine's state): a window layer's
    cache is its slot's last positions in position order from there on, and
    the piece attends those and itself
    (``ops.attention.ring_context_attention``)."""
    window = cfg.window(layer)
    t = lambda x: x.transpose(0, 2, 1, 3)
    if cache is None:
        impl, _, flag = attention_impl.partition("+")
        if impl in ("auto", "ragged"):  # "ragged" names the cached decode's kernel only
            impl = "flash" if jax.default_backend() == "tpu" else "reference"
        attn_ops.note_choice(impl, q.shape[1], k.shape[1], ctx.mesh,
                             group=q.shape[2] // k.shape[2])
        if impl == "flash":
            out = attn_ops.flash_attention(t(q), t(k), t(v), causal=True, window=window,
                                           mesh=ctx.mesh, interpret=flag == "interpret")
        else:
            out = attn_ops.attention_reference(t(q), t(k), t(v), causal=True, window=window)
        return t(out), None
    ck, cv = cache
    if window and ring_start is not None:
        return attn_ops.ring_context_attention(q, ck, cv, k, v, ring_start, cache_offset,
                                               window)
    rings = ring and bool(window)  # a full layer's leaf is dense under ``ring`` too
    if rings:  # one token a step: ``cached_attention`` refuses a longer block
        length = ck.shape[1]
        offset = jnp.broadcast_to(jnp.asarray(cache_offset, jnp.int32), (q.shape[0],))
        ck = write_rows(ck, k, offset % length, ctx.mesh)
        cv = write_rows(cv, v, offset % length, ctx.mesh)
    else:
        ck = write_rows(ck, k, cache_offset, ctx.mesh)
        cv = write_rows(cv, v, cache_offset, ctx.mesh)
    out = attn_ops.cached_attention(q, ck, cv, cache_offset, impl=attention_impl,
                                    mesh=ctx.mesh, window=window, ring=rings)
    return out, (ck, cv)


def decoder_layer(params, p: str, x, positions, cfg: LagunaConfig, layer: int,
                  ctx: ShardingCtx, cache=None, cache_offset=0, ring: bool = False,
                  attention_impl: str = "auto", ring_start=None):
    """One block. Returns (x, updated (k, v) or None, the expert layer's
    counts or None)."""
    b, s = x.shape[:2]
    heads, hd = cfg.num_heads_per_layer[layer], cfg.head_dim
    kind = "window" if cfg.window(layer) else "full"
    with jax.named_scope(f"laguna.attn.{kind}"):
        u = _rms_norm(x, params[p + "input_layernorm.weight"], cfg.rms_eps)
        q = _linear(u, params[p + "self_attn.q_proj.weight"]).reshape(b, s, heads, hd)
        k = _linear(u, params[p + "self_attn.k_proj.weight"]).reshape(b, s, cfg.num_kv_heads, hd)
        v = _linear(u, params[p + "self_attn.v_proj.weight"]).reshape(b, s, cfg.num_kv_heads, hd)
        gate = jax.nn.sigmoid(_linear(u, params[p + "self_attn.g_proj.weight"])
                              .astype(jnp.float32))  # [B, S, H]
        q = ctx.constrain(apply_rope(q, positions, cfg.rope(layer)), "dp", "sp", "tp", None)
        k = ctx.constrain(apply_rope(k, positions, cfg.rope(layer)), "dp", "sp", "tp", None)
        v = ctx.constrain(v, "dp", "sp", "tp", None)
        attn, new_cache = _attention(q, k, v, cfg, layer, ctx, cache, cache_offset, ring,
                                     attention_impl, ring_start)
        # the per-head gate: one scalar a query head, outside the contraction
        attn = (attn.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
        x = x + _linear(attn.reshape(b, s, heads * hd), params[p + "self_attn.o_proj.weight"])
        x = ctx.constrain(x, "dp", "sp", None)
    m = _rms_norm(x, params[p + "post_attention_layernorm.weight"], cfg.rms_eps)
    if cfg.mlp_layer_types[layer] == "dense":
        with jax.named_scope("laguna.mlp.dense"):
            ff = jax.nn.silu(_linear(m, params[p + "mlp.gate_proj.weight"])) * _linear(
                m, params[p + "mlp.up_proj.weight"])
            ff = ctx.constrain(ff, "dp", "sp", "tp")
            return ctx.constrain(x + _linear(ff, params[p + "mlp.down_proj.weight"]),
                                 "dp", "sp", None), new_cache, None
    shared = None
    if cfg.shared_expert_intermediate_size:
        shared = tuple(params[p + f"mlp.shared_expert.{w}_proj.weight"]
                       for w in ("gate", "up", "down"))
    y, counts = moe_ops.moe_share_ffn(
        m, params[p + "mlp.gate.weight"], params[p + "mlp.experts.gate_proj.weight"],
        params[p + "mlp.experts.up_proj.weight"], params[p + "mlp.experts.down_proj.weight"],
        top_k=cfg.top_k, held=cfg.held, renormalize=cfg.norm_topk_prob,
        routed_scale=cfg.routed_scale, shared=shared, constrain=ctx.constrain,
        scopes=("laguna.moe.routed", "laguna.moe.shared"), mesh=ctx.mesh)
    return ctx.constrain(x + y, "dp", "sp", None), new_cache, counts


def forward(params, tokens, cfg: LagunaConfig, positions=None, kv_cache: dict | None = None,
            cache_offset: int | jax.Array = 0, mesh: Mesh | None = None,
            attention_impl: str = "auto", ring: bool = False):
    """Returns (logits [B,S,V], updated kv_cache). ``kv_cache`` None: one
    cache-less pass. A dense cache for every layer (:func:`init_kv_cache`):
    prefill and decode as the other families do them; with a ``ring_start``
    leaf beside the layers' (a prefill piece over the engine's state,
    dl/kv_layout.LayerKindKV.view) a window layer's leaves are its slot's last
    positions in position order from there on, and come back as the last of
    those and the block's together. ``ring=True``: the engine's per-kind state (:func:`init_layer_state`), one token a step;
    its ``moe_counts`` leaf grows by what the step's expert layers counted."""
    ctx = ShardingCtx(mesh)
    b, s = tokens.shape
    if positions is None:
        off = jnp.asarray(cache_offset if kv_cache is not None else 0)
        positions = jnp.arange(s)[None, :] + (off[:, None] if off.ndim else off)
        positions = jnp.broadcast_to(positions, (b, s))
    x = jnp.take(params["model.embed_tokens.weight"], tokens, axis=0).astype(cfg.dtype)
    x = ctx.constrain(x, "dp", "sp", None)
    new_cache: dict | None = {} if kv_cache is not None else None
    counted = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    for i in range(cfg.num_layers):
        cache = (kv_cache[f"k{i}"], kv_cache[f"v{i}"]) if kv_cache is not None else None
        x, updated, counts = decoder_layer(
            params, f"model.layers.{i}.", x, positions, cfg, i, ctx, cache=cache,
            cache_offset=cache_offset, ring=ring, attention_impl=attention_impl,
            ring_start=kv_cache.get("ring_start") if kv_cache is not None else None)
        if updated is not None:
            new_cache[f"k{i}"], new_cache[f"v{i}"] = updated
        if counts is not None:
            counted = counted + counts
    if kv_cache is not None and "moe_counts" in kv_cache:
        new_cache["moe_counts"] = kv_cache["moe_counts"] + counted
    x = _rms_norm(x, params["model.norm.weight"], cfg.rms_eps)
    logits = _linear(x, params["lm_head.weight"])
    return ctx.constrain(logits, "dp", "sp", None), new_cache
