"""MiMo-V2-Flash-family decoder: window layers with a learned sink beside
full layers, keys wider than values, and a KV-head count a layer kind.

Per layer (``hybrid_layer_pattern``): a FULL layer attends causally over
everything through ``num_key_value_heads`` KV heads; a WINDOW layer over its
last ``sliding_window`` positions (the query's own included) through
``swa_num_key_value_heads``, with one learned logit a query head — the sink —
in the softmax's denominator and no value behind it. Keys and queries are
``head_dim`` wide (192), values ``v_head_dim`` (128), scaled by
``attention_value_scale`` before they are cached; rope turns the first
``int(head_dim * partial_rotary_factor)`` lanes by halves, with a base a
layer kind. The FFN is one dense SwiGLU on the layers ``moe_layer_freq`` marks
0 and elsewhere ``noaux_tc`` routing without groups — sigmoid scores, a bias
that only chooses, top-k renormalised — over SwiGLU experts with no shared
one (ops/moe.moe_share_ffn). The plain float32 reference of the same equations
is ``models/mimo_v2_reference.py``, which lists what the published
``config.json`` leaves open and the reading taken here.

Params are a flat dict keyed by the checkpoint's names, the experts stacked
along a leading axis (the loader folds ``experts.<i>.*``):

    model.layers.N.self_attn.{q,k,v,o}_proj.weight
    model.layers.N.self_attn.attention_sink_bias        [H]     window layers
    model.layers.N.mlp.{gate,up,down}_proj.weight               dense layers
    model.layers.N.mlp.gate.weight                      [E_pub, D]   router
    model.layers.N.mlp.gate.e_score_correction_bias     [E_pub]
    model.layers.N.mlp.experts.{gate,up}_proj.weight    [E_held, F, D]
    model.layers.N.mlp.experts.down_proj.weight         [E_held, D, F]

**The config comes from ``config.json``** (:func:`config_from_hf`). **The share
key** is Laguna's and DeepSeek's: ``n_routed_experts`` counts the experts held
and ``"expert_share": {"published": 256, "first": 0}`` says which of how many;
the router keeps its published width and nothing stands in for other chips.

**Every cache leaf keeps a position's KV heads side by side in one line** —
``k<i>`` ``[B, L, Hkv * 192]``, ``v<i>`` ``[B, L, Hkv * 128]``: 768 and 512
lanes on a full layer, 1,536 and 1,024 on a window layer. A ``[.., Hkv, 192]``
leaf is not whole lane tiles; the chip would pad each head to 256 lanes (a
third more cache than the model has) or lay positions innermost, which no
kernel reads in place. The decode kernels read a line as it lies
(ops/attention.decode_attention, ``flat``).

Three shapes of KV state, one forward, as Laguna's: none; a dense ``[B, L]``
cache for every layer; the engine's state a layer kind (``ring=True``,
dl/kv_layout.LayerKindKV), window layers a ring of :func:`ring_len` positions.
A long prompt lands in pieces (``--prefill-chunk``): a piece's cache then
holds, for a window layer, the slot's last ``ring`` positions in position
order from ``ring_start`` on (``kv_cache["ring_start"]``), the layer attends
``[those ++ the piece]`` and hands back the last ``ring`` of them. What a ring
cannot carry — ``--prefix-cache``, ``--speculative-k``, ``--kv-page-size`` —
is refused at start-up by name (dl/kv_layout.LayerKindKV.refuse).
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
from modelx_tpu.models.laguna import to_hf_state_dict  # noqa: F401  (the same per-expert names)
from modelx_tpu.models.llama import ShardingCtx, _rms_norm
from modelx_tpu.ops import attention as attn_ops
from modelx_tpu.ops import moe as moe_ops
from modelx_tpu.ops.kv_write import write_rows
from modelx_tpu.ops.nn import linear as _linear

# the engine's counters, in the order the decode step accumulates them
# (dl/kv_layout.LayerKindKV reads them back with the tokens)
MOE_COUNTERS = ("assignments", "assignments_held", "experts_hit", "experts_read")
# window-layer attention calls of the decode steps that carried their sinks
ATTN_COUNTERS = ("sink_calls",)
# a block of more tokens than MOE_TOKENS runs its expert layer MOE_CHUNK at a time
MOE_TOKENS, MOE_CHUNK = 4096, 1024


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384  # the dense layers' MLP
    moe_intermediate_size: int = 2048
    window_layers: tuple[bool, ...] = (False, True, True, True, True, False)
    sparse_layers: tuple[bool, ...] = (False, True, True, True, True, True)
    num_heads: int = 64
    num_kv_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_heads: int = 64
    swa_num_kv_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    sink_window: bool = True  # add_swa_attention_sink_bias
    sink_full: bool = False  # add_full_attention_sink_bias
    value_scale: float = 0.707
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5_000_000.0
    swa_rope_theta: float = 10_000.0
    num_experts: int = 256  # the router's published width
    expert_first: int = 0  # the experts held here: first .. first + count
    expert_count: int = 256
    top_k: int = 8
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.window_layers)

    @property
    def held(self) -> tuple[int, int]:
        return self.expert_first, self.expert_count

    def window(self, layer: int) -> int:
        return self.sliding_window if self.window_layers[layer] else 0

    def heads(self, layer: int) -> tuple[int, int, int, int]:
        """(query heads, KV heads, key width, value width) of one layer."""
        if self.window_layers[layer]:
            return (self.swa_num_heads, self.swa_num_kv_heads, self.swa_head_dim,
                    self.swa_v_head_dim)
        return self.num_heads, self.num_kv_heads, self.head_dim, self.v_head_dim

    def sinks(self, layer: int) -> bool:
        return self.sink_window if self.window_layers[layer] else self.sink_full

    def theta(self, layer: int) -> float:
        return self.swa_rope_theta if self.window_layers[layer] else self.rope_theta

    @classmethod
    def tiny(cls, vocab_size: int = 256, **over) -> "MimoV2Config":
        """Test config: every mechanism at toy sizes — five layers (full +
        dense, three window, full), 2 and 4 KV heads under 8 query heads, keys
        of 24 over values of 16, rope on 8 lanes, window 16, 16 experts top-4."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32,
            window_layers=(False, True, True, True, False),
            sparse_layers=(False, True, True, True, True),
            num_heads=8, num_kv_heads=2, head_dim=24, v_head_dim=16,
            swa_num_heads=8, swa_num_kv_heads=4, swa_head_dim=24, swa_v_head_dim=16,
            sliding_window=16, num_experts=16, expert_first=0, expert_count=16, top_k=4,
            dtype=jnp.float32)
        base.update(over)
        return cls(**base)


def config_from_hf(raw: Mapping, dtype=jnp.bfloat16) -> MimoV2Config:
    """The config of a checkpoint from its ``config.json``. Raises for what
    this family does not implement rather than serving something else."""
    n = int(raw["num_hidden_layers"])
    pattern = tuple(bool(int(x)) for x in raw["hybrid_layer_pattern"])[:n]
    sparse = tuple(bool(int(x)) for x in raw["moe_layer_freq"])[:n]
    if not len(pattern) == len(sparse) == n:
        raise ValueError(f"mimo_v2: per-layer lists are shorter than num_hidden_layers={n}")
    if raw.get("scoring_func", "sigmoid") != "sigmoid" or raw.get(
            "topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError("mimo_v2: routing other than noaux_tc over sigmoid scores is not "
                         "implemented")
    if int(raw.get("n_group") or 1) != 1 or int(raw.get("topk_group") or 1) != 1:
        raise ValueError("mimo_v2: group-limited routing is not implemented (n_group 1)")
    if raw.get("n_shared_experts"):
        raise ValueError("mimo_v2: shared experts are not implemented (the source has none)")
    if raw.get("attention_bias"):
        raise ValueError("mimo_v2: attention biases are not implemented")
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError(f"mimo_v2: hidden_act {raw.get('hidden_act')!r} is not implemented")
    if raw.get("rope_scaling"):
        raise ValueError("mimo_v2: rope scaling is not implemented")
    window = int(raw.get("sliding_window") or raw.get("sliding_window_size") or 0)
    if any(pattern) and not window:
        raise ValueError("mimo_v2: window layers without a sliding_window")
    held = int(raw["n_routed_experts"])
    share = raw.get("expert_share") or {}
    published, first = int(share.get("published", held)), int(share.get("first", 0))
    if first < 0 or first + held > published:
        raise ValueError(f"mimo_v2: expert_share holds {first}..{first + held} "
                         f"of {published} published experts")
    heads, kv, d = (int(raw["num_attention_heads"]), int(raw["num_key_value_heads"]),
                    int(raw["head_dim"]))
    dv = int(raw.get("v_head_dim", d))
    scale = raw.get("routed_scaling_factor")
    return MimoV2Config(
        vocab_size=int(raw["vocab_size"]), hidden_size=int(raw["hidden_size"]),
        intermediate_size=int(raw["intermediate_size"]),
        moe_intermediate_size=int(raw["moe_intermediate_size"]),
        window_layers=pattern, sparse_layers=sparse,
        num_heads=heads, num_kv_heads=kv, head_dim=d, v_head_dim=dv,
        swa_num_heads=int(raw.get("swa_num_attention_heads", heads)),
        swa_num_kv_heads=int(raw.get("swa_num_key_value_heads", kv)),
        swa_head_dim=int(raw.get("swa_head_dim", d)),
        swa_v_head_dim=int(raw.get("swa_v_head_dim", dv)),
        sliding_window=window,
        sink_window=bool(raw.get("add_swa_attention_sink_bias", False)),
        sink_full=bool(raw.get("add_full_attention_sink_bias", False)),
        value_scale=float(raw.get("attention_value_scale") or 1.0),
        partial_rotary_factor=float(raw.get("partial_rotary_factor", 1.0)),
        rope_theta=float(raw.get("rope_theta", 10000.0)),
        swa_rope_theta=float(raw.get("swa_rope_theta", raw.get("rope_theta", 10000.0))),
        num_experts=published, expert_first=first, expert_count=held,
        top_k=int(raw["num_experts_per_tok"]),
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        routed_scale=float(scale) if scale is not None else 1.0,
        rms_eps=float(raw.get("layernorm_epsilon", 1e-5)), dtype=dtype)


def to_hf_config(cfg: MimoV2Config) -> dict:
    """The ``config.json`` that :func:`config_from_hf` reads back as ``cfg``
    (test checkpoints, and the reference, which reads the architecture from
    this and not from ``cfg``)."""
    return {
        "model_type": "mimo_v2_flash", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "hybrid_layer_pattern": [int(w) for w in cfg.window_layers],
        "moe_layer_freq": [int(s) for s in cfg.sparse_layers],
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "v_head_dim": cfg.v_head_dim,
        "swa_num_attention_heads": cfg.swa_num_heads,
        "swa_num_key_value_heads": cfg.swa_num_kv_heads,
        "swa_head_dim": cfg.swa_head_dim, "swa_v_head_dim": cfg.swa_v_head_dim,
        "sliding_window": cfg.sliding_window, "sliding_window_size": cfg.sliding_window,
        "add_swa_attention_sink_bias": cfg.sink_window,
        "add_full_attention_sink_bias": cfg.sink_full,
        "attention_value_scale": cfg.value_scale,
        "partial_rotary_factor": cfg.partial_rotary_factor,
        "rope_theta": cfg.rope_theta, "swa_rope_theta": cfg.swa_rope_theta,
        "layernorm_epsilon": cfg.rms_eps, "hidden_act": "silu", "attention_bias": False,
        "n_routed_experts": cfg.expert_count,
        "expert_share": {"published": cfg.num_experts, "first": cfg.expert_first},
        "n_shared_experts": None, "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": None if cfg.routed_scale == 1.0 else cfg.routed_scale,
        "tie_word_embeddings": False,
    }


# -- params -------------------------------------------------------------------


def param_shapes(cfg: MimoV2Config) -> dict[str, tuple[int, ...]]:
    """Stacked-expert layout, linear weights [out, in]."""
    e, f = cfg.hidden_size, cfg.moe_intermediate_size
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_size, e),
        "model.norm.weight": (e,),
        "lm_head.weight": (cfg.vocab_size, e),
    }
    for i in range(cfg.num_layers):
        p, (h, kv, d, dv) = f"model.layers.{i}.", cfg.heads(i)
        shapes.update({
            p + "self_attn.q_proj.weight": (h * d, e),
            p + "self_attn.k_proj.weight": (kv * d, e),
            p + "self_attn.v_proj.weight": (kv * dv, e),
            p + "self_attn.o_proj.weight": (e, h * dv),
            p + "input_layernorm.weight": (e,),
            p + "post_attention_layernorm.weight": (e,),
        })
        if cfg.sinks(i):
            shapes[p + "self_attn.attention_sink_bias"] = (h,)
        if not cfg.sparse_layers[i]:
            shapes.update({
                p + "mlp.gate_proj.weight": (cfg.intermediate_size, e),
                p + "mlp.up_proj.weight": (cfg.intermediate_size, e),
                p + "mlp.down_proj.weight": (e, cfg.intermediate_size),
            })
            continue
        shapes.update({
            p + "mlp.gate.weight": (cfg.num_experts, e),
            p + "mlp.gate.e_score_correction_bias": (cfg.num_experts,),
            p + "mlp.experts.gate_proj.weight": (cfg.expert_count, f, e),
            p + "mlp.experts.up_proj.weight": (cfg.expert_count, f, e),
            p + "mlp.experts.down_proj.weight": (cfg.expert_count, e, f),
        })
    return shapes


def init_params(cfg: MimoV2Config, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    """Seeded weights: norms 1, sinks and choice biases of the size of the
    scores they sit beside (a sink of 0 would still take mass; a bias of 0
    would choose nothing), linear weights ``N(0, 1 / fan_in)``."""
    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    for (name, shape), k in zip(sorted(shapes.items()), jax.random.split(key, len(shapes))):
        if name.endswith("norm.weight"):
            params[name] = jnp.ones(shape, dtype)
        elif name.endswith("attention_sink_bias"):
            params[name] = jax.random.normal(k, shape).astype(dtype)
        elif name.endswith("e_score_correction_bias"):
            params[name] = (0.1 * jax.random.normal(k, shape)).astype(dtype)
        else:
            params[name] = (jax.random.normal(k, shape) / math.sqrt(shape[-1])).astype(dtype)
    return params


# -- rope ---------------------------------------------------------------------


def rotary_dims(cfg: MimoV2Config, head_dim: int) -> int:
    return int(head_dim * cfg.partial_rotary_factor)


def apply_rope(x, positions, theta: float, dim: int):
    """Rotate-half rope on the first ``dim`` lanes of each head, the rest
    passed through. x: [B, S, H, D]; positions: [B, S]."""
    inv_freq = (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x[..., :dim].astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., dim:]], axis=-1)


# -- kv state -----------------------------------------------------------------


def ring_len(cfg: MimoV2Config) -> int:
    """Positions a window layer's ring holds a slot: the window plus one
    16-token bucket (models/laguna.ring_len has the reason)."""
    return cfg.sliding_window + SEQ_BUCKET


def _leaves(cfg: MimoV2Config, batch: int, length, dtype) -> dict:
    """``k<i>`` / ``v<i>`` ``[batch, length(i), Hkv * width]``: a position's KV
    heads side by side in one line."""
    out = {}
    for i in range(cfg.num_layers):
        _, kv, d, dv = cfg.heads(i)
        out[f"k{i}"] = jnp.zeros((batch, length(i), kv * d), dtype)
        out[f"v{i}"] = jnp.zeros((batch, length(i), kv * dv), dtype)
    return out


def init_kv_cache(cfg: MimoV2Config, batch: int, max_len: int, dtype=None) -> dict:
    """A dense ``[batch, max_len]`` cache for every layer."""
    return _leaves(cfg, batch, lambda i: max_len, dtype or cfg.dtype)


def published(cfg: MimoV2Config) -> dict:
    """What a pod's /metrics names of this family: the counter leaves the
    decode step accumulates (leaf -> (stats block, its entries' names)) and the
    gauges those counts are shares of."""
    return {
        "counters": {"moe_counts": ("moe", MOE_COUNTERS), "attn_counts": ("attn", ATTN_COUNTERS)},
        "gauges": {"moe": {"held_experts": cfg.expert_count,
                           "published_experts": cfg.num_experts,
                           "sparse_layers": sum(cfg.sparse_layers)},
                   "attn": {"window_layers": sum(cfg.window_layers),
                            "sink_layers": sum(cfg.sinks(i) for i in range(cfg.num_layers))}},
    }


def cache_kinds(cfg: MimoV2Config) -> dict[str, str]:
    """Leaf name -> ``"full"`` / ``"window"`` / ``"counter"`` of the engine's
    state (:func:`init_layer_state`)."""
    kinds = {f"{kv}{i}": "window" if cfg.window(i) else "full"
             for i in range(cfg.num_layers) for kv in "kv"}
    kinds["moe_counts"] = kinds["attn_counts"] = "counter"
    return kinds


def init_layer_state(cfg: MimoV2Config, slots: int, max_len: int, dtype=None) -> dict:
    """The engine's state: full layers ``[slots, max_len]``, window layers a
    ring of ``min(ring_len, max_len)`` positions, and the counters."""
    ring = min(ring_len(cfg), max_len)
    state = _leaves(cfg, slots, lambda i: ring if cfg.window(i) else max_len,
                    dtype or cfg.dtype)
    state["moe_counts"] = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    state["attn_counts"] = jnp.zeros((len(ATTN_COUNTERS),), jnp.int32)
    return state


# -- forward ------------------------------------------------------------------


def _attention(q, k, v, cfg: MimoV2Config, layer: int, ctx: ShardingCtx, cache,
               cache_offset, ring: bool, ring_start, sinks, attention_impl: str):
    """q [B,S,H,D], k [B,S,Hkv,D], v [B,S,Hkv,Dv] after rope and the value
    scale -> ([B,S,H,Dv], new cache).

    No cache: flash on a TPU, else the reference. A cache (``flat`` leaves):
    a decode step writes its line (``ops.kv_write.write_rows``; a ring at
    ``offset mod ring``) and ``ops.attention.cached_attention`` picks by what it
    observes — on one TPU device the ragged kernel over a full layer's
    ``[slots, max_len]``, the ring kernel over a window layer's ring, both with
    the sinks as their softmax's starting state. A block of prompt positions
    is ``ops.attention.blocked_attention``, a key block at a time: over the
    dense cache it has just been written into, or — a prefill piece over the
    engine's state, ``ring_start`` given — over a window layer's last ``ring``
    positions and the piece itself (``ops.attention.ring_context_attention``)."""
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
                                           mesh=ctx.mesh, interpret=flag == "interpret",
                                           sinks=sinks)
        else:
            out = attn_ops.attention_reference(t(q), t(k), t(v), causal=True, window=window,
                                               sinks=sinks)
        return t(out), None
    ck, cv = cache
    b, s = q.shape[:2]
    k, v = k.reshape(b, s, -1), v.reshape(b, s, -1)
    if window and ring_start is not None:
        return attn_ops.ring_context_attention(q, ck, cv, k, v, ring_start, cache_offset,
                                               window, sinks=sinks)
    rings = ring and bool(window)  # a full layer's leaf is dense under ``ring`` too
    if rings:  # one token a step: ``cached_attention`` refuses a longer block
        offset = jnp.broadcast_to(jnp.asarray(cache_offset, jnp.int32), (b,))
        ck = write_rows(ck, k, offset % ck.shape[1], ctx.mesh)
        cv = write_rows(cv, v, offset % cv.shape[1], ctx.mesh)
    else:
        ck = write_rows(ck, k, cache_offset, ctx.mesh)
        cv = write_rows(cv, v, cache_offset, ctx.mesh)
    if s == 1:
        out = attn_ops.cached_attention(q, ck, cv, cache_offset, impl=attention_impl,
                                        mesh=ctx.mesh, window=window, ring=rings, sinks=sinks)
    else:
        out = attn_ops.blocked_attention(q, ck, cv, cache_offset, window=window, sinks=sinks)
    return out, (ck, cv)


def decoder_layer(params, p: str, x, positions, cfg: MimoV2Config, layer: int,
                  ctx: ShardingCtx, cache=None, cache_offset=0, ring: bool = False,
                  ring_start=None, attention_impl: str = "auto"):
    """One block. Returns (x, updated (k, v) or None, the expert layer's
    counts or None)."""
    b, s = x.shape[:2]
    heads, kv, d, dv = cfg.heads(layer)
    kind = "window" if cfg.window(layer) else "full"
    with jax.named_scope(f"mimo_v2.attn.{kind}"):
        u = _rms_norm(x, params[p + "input_layernorm.weight"], cfg.rms_eps)
        q = _linear(u, params[p + "self_attn.q_proj.weight"]).reshape(b, s, heads, d)
        k = _linear(u, params[p + "self_attn.k_proj.weight"]).reshape(b, s, kv, d)
        v = _linear(u, params[p + "self_attn.v_proj.weight"]).reshape(b, s, kv, dv)
        v = (v.astype(jnp.float32) * cfg.value_scale).astype(x.dtype)  # before the cache
        rot, theta = rotary_dims(cfg, d), cfg.theta(layer)
        q = ctx.constrain(apply_rope(q, positions, theta, rot), "dp", "sp", "tp", None)
        k = ctx.constrain(apply_rope(k, positions, theta, rot), "dp", "sp", "tp", None)
        v = ctx.constrain(v, "dp", "sp", "tp", None)
        sinks = params[p + "self_attn.attention_sink_bias"] if cfg.sinks(layer) else None
        attn, new_cache = _attention(q, k, v, cfg, layer, ctx, cache, cache_offset, ring,
                                     ring_start, sinks, attention_impl)
        x = x + _linear(attn.reshape(b, s, heads * dv), params[p + "self_attn.o_proj.weight"])
        x = ctx.constrain(x, "dp", "sp", None)
    m = _rms_norm(x, params[p + "post_attention_layernorm.weight"], cfg.rms_eps)
    if not cfg.sparse_layers[layer]:
        with jax.named_scope("mimo_v2.mlp.dense"):
            ff = jax.nn.silu(_linear(m, params[p + "mlp.gate_proj.weight"])) * _linear(
                m, params[p + "mlp.up_proj.weight"])
            ff = ctx.constrain(ff, "dp", "sp", "tp")
            return ctx.constrain(x + _linear(ff, params[p + "mlp.down_proj.weight"]),
                                 "dp", "sp", None), new_cache, None

    def experts(tokens):
        return moe_ops.moe_share_ffn(
            tokens, params[p + "mlp.gate.weight"], params[p + "mlp.experts.gate_proj.weight"],
            params[p + "mlp.experts.up_proj.weight"],
            params[p + "mlp.experts.down_proj.weight"],
            top_k=cfg.top_k, held=cfg.held, renormalize=cfg.norm_topk_prob,
            routed_scale=cfg.routed_scale, constrain=ctx.constrain, scoring="sigmoid",
            choice_bias=params[p + "mlp.gate.e_score_correction_bias"],
            scopes=("mimo_v2.moe",), mesh=ctx.mesh)

    if b * s <= MOE_TOKENS:
        y, counts = experts(m)
    else:
        # every held expert runs on every token ([E_held, T, F] activations,
        # three of them): a long cache-less forward goes MOE_CHUNK tokens at a
        # time, one chunk live, padded with zero tokens that are cut off again
        e = m.shape[-1]
        flat = jnp.pad(m.reshape(b * s, e), ((0, -(b * s) % MOE_CHUNK), (0, 0)))
        ys, counts = jax.lax.map(experts, flat.reshape(-1, 1, MOE_CHUNK, e))
        y, counts = ys.reshape(-1, e)[: b * s].reshape(b, s, e), jnp.sum(counts, axis=0)
    return ctx.constrain(x + y, "dp", "sp", None), new_cache, counts


def forward(params, tokens, cfg: MimoV2Config, positions=None, kv_cache: dict | None = None,
            cache_offset: int | jax.Array = 0, mesh: Mesh | None = None,
            attention_impl: str = "auto", ring: bool = False):
    """Returns (logits [B,S,V], updated kv_cache). ``kv_cache`` None: one
    cache-less pass. A dense cache for every layer (:func:`init_kv_cache`):
    prefill and decode as the other families do them; with a ``ring_start``
    leaf beside the layers' (a prefill piece over the engine's state,
    dl/kv_layout.LayerKindKV.view) the window layers' leaves are their slots'
    last positions in position order from there on, and come back as the
    last of those and the block's together. ``ring=True``: the
    engine's per-kind state (:func:`init_layer_state`), one token a step; its
    counter leaves grow by what the step counted."""
    ctx = ShardingCtx(mesh)
    b, s = tokens.shape
    if positions is None:
        off = jnp.asarray(cache_offset if kv_cache is not None else 0)
        positions = jnp.arange(s)[None, :] + (off[:, None] if off.ndim else off)
        positions = jnp.broadcast_to(positions, (b, s))
    x = jnp.take(params["model.embed_tokens.weight"], tokens, axis=0).astype(cfg.dtype)
    x = ctx.constrain(x, "dp", "sp", None)
    new_cache: dict | None = {} if kv_cache is not None else None
    ring_start = kv_cache.get("ring_start") if kv_cache is not None else None
    counted = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    for i in range(cfg.num_layers):
        cache = (kv_cache[f"k{i}"], kv_cache[f"v{i}"]) if kv_cache is not None else None
        x, updated, counts = decoder_layer(
            params, f"model.layers.{i}.", x, positions, cfg, i, ctx, cache=cache,
            cache_offset=cache_offset, ring=ring, ring_start=ring_start,
            attention_impl=attention_impl)
        if updated is not None:
            new_cache[f"k{i}"], new_cache[f"v{i}"] = updated
        if counts is not None:
            counted = counted + counts
    if kv_cache is not None and "moe_counts" in kv_cache:
        new_cache["moe_counts"] = kv_cache["moe_counts"] + counted
        sunk = sum(cfg.sinks(i) and bool(cfg.window(i)) for i in range(cfg.num_layers))
        new_cache["attn_counts"] = kv_cache["attn_counts"] + jnp.int32(sunk)
    x = _rms_norm(x, params["model.norm.weight"], cfg.rms_eps)
    logits = _linear(x, params["lm_head.weight"])
    return ctx.constrain(logits, "dp", "sp", None), new_cache
