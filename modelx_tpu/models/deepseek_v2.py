"""The DeepSeek-V2 / V3 / V3.2 decoder: latent (MLA) attention beside
group-routed experts, and — V3.2 — a learned selector over the latent cache.

Every layer's attention is multi-head LATENT attention: queries through a
low-rank pair (``q_a_proj`` -> norm -> ``q_b_proj``), keys and values through
ONE compressed line a position — ``kv_a_proj_with_mqa`` gives ``kv_lora_rank``
values that ``kv_b_proj`` would expand into every head's key and value, and
``qk_rope_head_dim`` more that are the one roped key all heads share. The cache
holds that line (normed, roped) and nothing else (ops/latent_attention.py): a
block of prompt positions EXPANDS keys and values from the lines a key block
at a time; a decode step ABSORBS the up-projections into the query and the
output and reads each line once as key and value. The first
``first_k_dense_replace`` layers' FFN is dense, the others' a sparse expert
layer with group-limited routing and shared experts (ops/moe.moe_share_ffn).
The equations, and every departure from the published modelling code, are
written out in the plain float32 reference, ``models/deepseek_v2_reference.py``.

``model_type`` ``deepseek_v3`` / ``deepseek_v32`` are the same layer with two
additions, each read from ``config.json`` and absent where it is not there.
**The router** (``scoring_func`` ``sigmoid``, ``topk_method`` ``noaux_tc``):
sigmoid scores, a bias that only chooses (``mlp.gate.e_score_correction_bias``),
a group's score the sum of its two best biased scores
(``ops/moe.route_topk``: sigmoid, a bias and groups together), and the routed scale applied
after the renormalisation too. **The lightning indexer** (``index_topk`` in the
config; DeepSeek sparse attention): beside the latent line every layer caches
one index key a position (leaf ``i<i>`` ``[B, L, index_head_dim]``), scores
every cached position against the token's ``index_n_heads`` index queries
(projected from the QUERY latent) and keeps the ``index_topk`` best — the same
set for all heads. A decode step gathers those positions' lines and runs the
absorbed attention over them (ops/index_select.py); a block of prompt
positions runs the expanded form under the selection's mask; a cache of at
most ``index_topk`` positions is V2's attention unchanged (the keys are
written all the same). Reference: ``models/deepseek_v32_reference.py``.

Params are a flat dict keyed by the checkpoint's names, the experts stacked
along a leading axis (the loader folds ``experts.<i>.*``):

    model.layers.N.self_attn.q_a_proj.weight            [q_lora, D]
    model.layers.N.self_attn.q_a_layernorm.weight       [q_lora]
    model.layers.N.self_attn.q_b_proj.weight            [H (dn + dr), q_lora]
    model.layers.N.self_attn.kv_a_proj_with_mqa.weight  [r + dr, D]
    model.layers.N.self_attn.kv_a_layernorm.weight      [r]
    model.layers.N.self_attn.kv_b_proj.weight           [H (dn + dv), r]
    model.layers.N.self_attn.o_proj.weight              [D, H dv]
    model.layers.N.mlp.{gate,up,down}_proj.weight                dense layers
    model.layers.N.mlp.gate.weight                      [E_pub, D]   router
    model.layers.N.mlp.experts.{gate,up}_proj.weight    [E_held, F, D]
    model.layers.N.mlp.experts.down_proj.weight         [E_held, D, F]
    model.layers.N.mlp.shared_experts.{gate,up,down}_proj.weight  width n_shared F
    model.layers.N.mlp.gate.e_score_correction_bias     [E_pub]      noaux_tc
    model.layers.N.self_attn.indexer.wq_b.weight        [Hi di, q_lora]   indexer
    model.layers.N.self_attn.indexer.wk.weight          [di, D]
    model.layers.N.self_attn.indexer.k_norm.{weight,bias}  [di]
    model.layers.N.self_attn.indexer.weights_proj.weight   [Hi, D]

**The config comes from ``config.json``, never from tensor shapes**
(:func:`config_from_hf`). **The share key**: ``n_routed_experts`` counts the
experts held and ``"expert_share": {"published": 160, "first": 0}`` says which
of how many (as Laguna's): the router keeps its published width, routing and
its groups run over all of them, only the held experts' part of the sum is
computed, and nothing stands in for the others.

The cache (``init_kv_cache``; the engine's, ``init_layer_state``, adds the
counters): per layer ONE leaf ``c<i>`` ``[B, L, W]``, ``W`` = ``kv_lora_rank +
qk_rope_head_dim`` rounded up to whole 128-lane tiles (576 -> 640). Every leaf
is addressed by position, so the continuous engine carries ``--prefill-chunk``
over it (dl/kv_layout.LayerKindKV, kind ``"latent"``); ``--prefix-cache``,
``--kv-page-size`` and ``--speculative-k`` are refused at load, by name. With
an indexer a layer has a second leaf ``i<i>`` ``[B, L, index_head_dim]``, kind
``"index"`` at one row a position: addressed, viewed and landed like ``c<i>``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

# the same share of experts, counted and unstacked the same way
from modelx_tpu.models.laguna import MOE_COUNTERS, to_hf_state_dict  # noqa: F401
from modelx_tpu.models.llama import ShardingCtx, _rms_norm
from modelx_tpu.ops import index_select as select_ops
from modelx_tpu.ops import latent_attention as latent_ops
from modelx_tpu.ops import moe as moe_ops
from modelx_tpu.ops.nn import layer_norm as _layer_norm, linear as _linear
from modelx_tpu.ops.rope import yarn_inv_freq, yarn_mscale
from modelx_tpu.utils import trace

# the engine's counters, in the order the decode step accumulates them
# (dl/kv_layout.LayerKindKV reads them back with the tokens). The expert
# layers' are Laguna's ``MOE_COUNTERS``, over ALL slots (idle ones route too);
# the latent layers', over the rows that hold a context (offset > 0: an idle
# slot sits at 0) and all layers: positions whose lines the step read (whole
# blocks up to a row's context in the kernel, the whole cache elsewhere),
# positions the rows hold (their contexts: what the algorithm needs), row-steps
# that took the absorbed form, row-steps in all
MLA_COUNTERS = ("positions_read", "positions_cached", "steps_absorbed", "steps_all")
# the indexer's, over the same rows and all layers: positions whose index keys
# a step scored (the rows' contexts), lines the selection kept (min(context,
# index_topk) a row), row-steps whose context exceeded index_topk (the
# selection chose), row-steps in all, and of the selecting ones those whose
# selection ran the kernels (ops/index_select.takes_kernel: all of them or none)
DSA_COUNTERS = ("positions_scored", "lines_selected", "steps_selecting", "steps_all",
                "steps_kernel")
# the indexer's LayerNorm (the published inference code's default; not in config.json)
INDEX_NORM_EPS = 1e-6
# tokens one call of the expert layer takes whole, and the chunk a longer block
# goes in (a prefill piece of 2,048 is whole; a probe's 8,256-token forward is not)
MOE_TOKENS, MOE_CHUNK = 4096, 1024


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288  # the dense layers' MLP
    moe_intermediate_size: int = 1536
    num_layers: int = 60
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    num_experts: int = 160  # the router's published width
    expert_first: int = 0  # the experts held here: first .. first + count
    expert_count: int = 160
    n_shared_experts: int = 2
    top_k: int = 6
    n_group: int = 8  # 0: plain top-k over all experts
    topk_group: int = 3
    norm_topk_prob: bool = False
    routed_scale: float = 16.0
    rope_theta: float = 10000.0
    rope_factor: float = 40.0  # YaRN; 1.0: plain rope
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    scoring: str = "softmax"  # "sigmoid": noaux_tc, with a choice bias a layer
    index_topk: int = 0  # 0: no indexer, every cached line is attended to
    index_heads: int = 0
    index_dim: int = 0

    @property
    def model_type(self) -> str:
        """What ``config.json`` calls it: the indexer is V3.2's, ``noaux_tc`` V3's."""
        return "deepseek_v32" if self.index_topk else "deepseek_v3" if self.noaux else "deepseek_v2"

    @property
    def noaux(self) -> bool:
        """``topk_method`` ``noaux_tc``: sigmoid scores, the choice bias, a
        group's score the sum of its two best, the scale after the norm."""
        return self.scoring == "sigmoid"

    @property
    def combine_scale(self) -> float:
        """V2 scales the routed weights OR renormalises them; V3 does both."""
        return self.routed_scale if self.noaux or not self.norm_topk_prob else 1.0

    @property
    def held(self) -> tuple[int, int]:
        return self.expert_first, self.expert_count

    @property
    def groups(self) -> tuple[int, int] | None:
        return (self.n_group, self.topk_group) if self.n_group else None

    @property
    def line_width(self) -> int:
        return latent_ops.line_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def softmax_scale(self) -> float:
        """``(dn + dr)^-0.5`` times the square of YaRN's factor over all
        dimensions: DeepSeek's ``mscale`` enters the scale, not the rotation."""
        return ((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
                * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2)

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    @classmethod
    def tiny(cls, vocab_size: int = 256, **over) -> "DeepseekV2Config":
        """Test config: every mechanism at toy sizes — three layers (dense,
        two sparse), 4 heads of 16 + 8 lanes over a latent of 32, 16 experts
        in 4 groups of which 2, top-3 unnormalised times 4, two shared
        experts, YaRN by 4 over an original context of 32."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_layers=3, num_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            first_k_dense_replace=1, num_experts=16, expert_first=0, expert_count=16,
            n_shared_experts=2, top_k=3, n_group=4, topk_group=2, routed_scale=4.0,
            rope_factor=4.0, rope_original_max=32, dtype=jnp.float32)
        base.update(over)
        return cls(**base)

    @classmethod
    def tiny_v32(cls, vocab_size: int = 256, **over) -> "DeepseekV2Config":
        """:meth:`tiny` as a V3.2: ``noaux_tc`` routing over the same 16
        experts, normed and scaled, one shared expert, and an indexer of 4
        heads of 16 lanes that keeps 8 positions."""
        base = dict(scoring="sigmoid", norm_topk_prob=True,
                    routed_scale=2.5, n_shared_experts=1, rope_mscale=1.0,
                    rope_mscale_all_dim=1.0, index_topk=8, index_heads=4, index_dim=16)
        base.update(over)
        return cls.tiny(vocab_size, **base)


def config_from_hf(raw: Mapping, dtype=jnp.bfloat16) -> DeepseekV2Config:
    """The config of a checkpoint from its ``config.json``. Raises for what
    this family does not implement rather than serving something else."""
    if not raw.get("q_lora_rank"):
        raise ValueError("deepseek_v2: queries without the low-rank pair (q_lora_rank null) "
                         "are not implemented")
    if int(raw.get("moe_layer_freq", 1)) != 1:
        raise ValueError("deepseek_v2: moe_layer_freq other than 1 is not implemented")
    model_type = raw.get("model_type", "deepseek_v2")
    if model_type not in ("deepseek_v2", "deepseek_v3", "deepseek_v32"):
        raise ValueError(f"deepseek_v2: model_type {model_type!r} is not implemented")
    scoring, method = raw.get("scoring_func", "softmax"), raw.get("topk_method", "greedy")
    if (scoring, method) not in (("softmax", "greedy"), ("softmax", "group_limited_greedy"),
                                 ("sigmoid", "noaux_tc")):
        raise ValueError(
            f"deepseek_v2: scoring_func {scoring!r} with topk_method {method!r} is not "
            "implemented (softmax with greedy / group_limited_greedy, sigmoid with noaux_tc)")
    index_topk = int(raw.get("index_topk") or 0)
    if (model_type == "deepseek_v32") != bool(index_topk):
        raise ValueError(f"deepseek_v2: model_type {model_type!r} with index_topk "
                         f"{raw.get('index_topk')!r} is not implemented: the indexer is "
                         "deepseek_v32's, and a deepseek_v32 without index_topk has none")
    if index_topk and int(raw["index_head_dim"]) < int(raw["qk_rope_head_dim"]):
        raise ValueError("deepseek_v2: index_head_dim below qk_rope_head_dim is not implemented")
    if raw.get("attention_bias"):
        raise ValueError("deepseek_v2: attention biases are not implemented")
    if raw.get("tie_word_embeddings"):
        raise ValueError("deepseek_v2: a tied output head is not implemented")
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError(f"deepseek_v2: hidden_act {raw['hidden_act']!r} is not implemented")
    held = int(raw["n_routed_experts"])
    share = raw.get("expert_share") or {}
    published, first = int(share.get("published", held)), int(share.get("first", 0))
    if first < 0 or first + held > published:
        raise ValueError(f"deepseek_v2: expert_share holds {first}..{first + held} "
                         f"of {published} published experts")
    scaling = raw.get("rope_scaling") or {}
    kind = (scaling.get("type") or scaling.get("rope_type") or "yarn") if scaling else None
    if kind not in (None, "yarn"):
        raise ValueError(f"deepseek_v2: rope_scaling type {kind!r} is not implemented (yarn)")
    grouped = method != "greedy"
    return DeepseekV2Config(
        scoring=scoring, index_topk=index_topk,
        index_heads=int(raw.get("index_n_heads") or 0) if index_topk else 0,
        index_dim=int(raw.get("index_head_dim") or 0) if index_topk else 0,
        vocab_size=int(raw["vocab_size"]), hidden_size=int(raw["hidden_size"]),
        intermediate_size=int(raw["intermediate_size"]),
        moe_intermediate_size=int(raw["moe_intermediate_size"]),
        num_layers=int(raw["num_hidden_layers"]), num_heads=int(raw["num_attention_heads"]),
        q_lora_rank=int(raw["q_lora_rank"]), kv_lora_rank=int(raw["kv_lora_rank"]),
        qk_nope_head_dim=int(raw["qk_nope_head_dim"]),
        qk_rope_head_dim=int(raw["qk_rope_head_dim"]), v_head_dim=int(raw["v_head_dim"]),
        first_k_dense_replace=int(raw.get("first_k_dense_replace", 0)),
        num_experts=published, expert_first=first, expert_count=held,
        n_shared_experts=int(raw.get("n_shared_experts") or 0),
        top_k=int(raw["num_experts_per_tok"]),
        n_group=int(raw["n_group"]) if grouped else 0,
        topk_group=int(raw["topk_group"]) if grouped else 0,
        norm_topk_prob=bool(raw.get("norm_topk_prob", False)),
        routed_scale=float(raw.get("routed_scaling_factor", 1.0)),
        rope_theta=float(raw.get("rope_theta", 10000.0)),
        rope_factor=float(scaling.get("factor", 1.0)),
        rope_original_max=int(scaling.get("original_max_position_embeddings", 0)),
        rope_beta_fast=float(scaling.get("beta_fast", 32.0)),
        rope_beta_slow=float(scaling.get("beta_slow", 1.0)),
        rope_mscale=float(scaling.get("mscale", 1.0)),
        rope_mscale_all_dim=float(scaling.get("mscale_all_dim", 0.0)),
        rms_eps=float(raw.get("rms_norm_eps", 1e-6)), dtype=dtype)


def to_hf_config(cfg: DeepseekV2Config) -> dict:
    """The ``config.json`` that :func:`config_from_hf` reads back as ``cfg``
    (test checkpoints, and the reference, which reads the architecture from
    this and not from ``cfg``)."""
    method = "noaux_tc" if cfg.noaux else "group_limited_greedy" if cfg.n_group else "greedy"
    out = {
        "model_type": cfg.model_type, "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_hidden_layers": cfg.num_layers, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_heads, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "first_k_dense_replace": cfg.first_k_dense_replace, "moe_layer_freq": 1,
        "n_routed_experts": cfg.expert_count,
        "expert_share": {"published": cfg.num_experts, "first": cfg.expert_first},
        "n_shared_experts": cfg.n_shared_experts, "num_experts_per_tok": cfg.top_k,
        "topk_method": method,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group, "scoring_func": cfg.scoring,
        "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scale,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
    }
    if cfg.rope_factor != 1.0:
        out["rope_scaling"] = {
            "type": "yarn", "factor": cfg.rope_factor,
            "original_max_position_embeddings": cfg.rope_original_max,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale, "mscale_all_dim": cfg.rope_mscale_all_dim}
    if cfg.index_topk:
        out.update(index_topk=cfg.index_topk, index_n_heads=cfg.index_heads,
                   index_head_dim=cfg.index_dim)
    return out


# -- params -------------------------------------------------------------------


def param_shapes(cfg: DeepseekV2Config) -> dict[str, tuple[int, ...]]:
    """Stacked-expert layout, linear weights [out, in]."""
    e, h = cfg.hidden_size, cfg.num_heads
    f, fs = cfg.moe_intermediate_size, cfg.n_shared_experts * cfg.moe_intermediate_size
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_size, e),
        "model.norm.weight": (e,),
        "lm_head.weight": (cfg.vocab_size, e),
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        shapes.update({
            p + "self_attn.q_a_proj.weight": (cfg.q_lora_rank, e),
            p + "self_attn.q_a_layernorm.weight": (cfg.q_lora_rank,),
            p + "self_attn.q_b_proj.weight": (h * dq, cfg.q_lora_rank),
            p + "self_attn.kv_a_proj_with_mqa.weight": (
                cfg.kv_lora_rank + cfg.qk_rope_head_dim, e),
            p + "self_attn.kv_a_layernorm.weight": (cfg.kv_lora_rank,),
            p + "self_attn.kv_b_proj.weight": (
                h * (cfg.qk_nope_head_dim + cfg.v_head_dim), cfg.kv_lora_rank),
            p + "self_attn.o_proj.weight": (e, h * cfg.v_head_dim),
            p + "input_layernorm.weight": (e,),
            p + "post_attention_layernorm.weight": (e,),
        })
        if cfg.index_topk:
            x = p + "self_attn.indexer."
            shapes.update({
                x + "wq_b.weight": (cfg.index_heads * cfg.index_dim, cfg.q_lora_rank),
                x + "wk.weight": (cfg.index_dim, e),
                x + "k_norm.weight": (cfg.index_dim,), x + "k_norm.bias": (cfg.index_dim,),
                x + "weights_proj.weight": (cfg.index_heads, e),
            })
        if cfg.is_dense(i):
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
        if cfg.noaux:
            shapes[p + "mlp.gate.e_score_correction_bias"] = (cfg.num_experts,)
        if fs:
            shapes.update({
                p + "mlp.shared_experts.gate_proj.weight": (fs, e),
                p + "mlp.shared_experts.up_proj.weight": (fs, e),
                p + "mlp.shared_experts.down_proj.weight": (e, fs),
            })
    return shapes


def init_params(cfg: DeepseekV2Config, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    for (name, shape), k in zip(sorted(shapes.items()), jax.random.split(key, len(shapes))):
        if name.endswith(("_layernorm.weight", "model.norm.weight")) and "_a_layernorm" not in name:
            params[name] = jnp.ones(shape, dtype)
        elif name.endswith("norm.weight"):  # the low-rank pairs' norms: not all ones
            params[name] = (1.0 + 0.1 * jax.random.normal(k, shape)).astype(dtype)
        else:
            params[name] = (jax.random.normal(k, shape) / math.sqrt(shape[-1])).astype(dtype)
    return params


# -- rope ---------------------------------------------------------------------


def rope_frequencies(cfg: DeepseekV2Config) -> tuple[np.ndarray, float]:
    """(inverse frequencies [dr / 2], the factor on cos and sin) of the rope
    lanes: YaRN's blend (``ops/rope.yarn_inv_freq``, shared with Laguna); the
    rotation's own factor is ``m(factor, mscale) / m(factor, mscale_all_dim)``
    — 1.0 where the two are equal, as published."""
    dim = cfg.qk_rope_head_dim
    if cfg.rope_factor == 1.0:
        pos_freqs = cfg.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    inv = yarn_inv_freq(cfg.rope_theta, dim, cfg.rope_factor, cfg.rope_original_max,
                        cfg.rope_beta_fast, cfg.rope_beta_slow)
    on_cos = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
              / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return inv.astype(np.float32), float(on_cos)


def apply_rope(x, positions, cfg: DeepseekV2Config, interleaved: bool = True):
    """x ``[B, S, H, dr]``, positions ``[B, S]``: the published permutation
    of the lanes from interleaved pairs to halves, then rotate-half.
    ``interleaved`` False (the indexer's queries and keys): the lanes come in
    halves already, lane ``i`` with lane ``dr / 2 + i``."""
    inv_freq, factor = rope_frequencies(cfg)
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = (x32[..., 0::2], x32[..., 1::2]) if interleaved else jnp.split(x32, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# -- kv state -----------------------------------------------------------------


def published(cfg: DeepseekV2Config) -> dict:
    """What a pod's /metrics names of this family: the counter leaves the
    decode step accumulates (leaf -> (stats block, its entries' names)) — of
    its expert layers over ALL slots (idle ones route too), of its latent
    layers over the rows that hold a context — and the gauges beside them."""
    out = {
        "counters": {"moe_counts": ("moe", MOE_COUNTERS),
                     "mla_counts": ("mla", MLA_COUNTERS)},
        "gauges": {"moe": {"held_experts": cfg.expert_count,
                           "published_experts": cfg.num_experts,
                           "sparse_layers": cfg.num_layers - cfg.first_k_dense_replace,
                           "groups": cfg.n_group, "groups_kept": cfg.topk_group},
                   "mla": {"layers": cfg.num_layers, "heads": cfg.num_heads,
                           "kv_lora_rank": cfg.kv_lora_rank,
                           "rope_dim": cfg.qk_rope_head_dim,
                           "line_width": cfg.line_width}},
    }
    if cfg.index_topk:
        # the index leaves' bytes are the layout's to say: ``kv.bytes_index``
        out["counters"]["dsa_counts"] = ("dsa", DSA_COUNTERS)
        out["gauges"]["dsa"] = {"layers": cfg.num_layers, "index_topk": cfg.index_topk,
                                "index_heads": cfg.index_heads, "index_dim": cfg.index_dim}
    return out


def cache_kinds(cfg: DeepseekV2Config) -> dict[str, str]:
    """Leaf name -> its kind in the engine's state (dl/kv_layout.LayerKindKV):
    a ``"latent"`` line a position a layer, with an indexer its ``"index"``
    key a position beside it, the ``"counter"`` vectors."""
    kinds = {f"c{i}": "latent" for i in range(cfg.num_layers)}
    kinds.update(moe_counts="counter", mla_counts="counter")
    if cfg.index_topk:
        kinds.update({f"i{i}": "index" for i in range(cfg.num_layers)}, dsa_counts="counter")
    return kinds


def init_kv_cache(cfg: DeepseekV2Config, batch: int, max_len: int, dtype=None) -> dict:
    """The cache of ``batch`` rows of ``max_len`` positions: a layer, one leaf
    ``[batch, max_len, line_width]``, and where the layers have an indexer a
    second, ``i<i>`` ``[batch, max_len, index_dim]``."""
    dtype = dtype or cfg.dtype
    cache = {f"c{i}": jnp.zeros((batch, max_len, cfg.line_width), dtype)
             for i in range(cfg.num_layers)}
    if cfg.index_topk:
        cache.update({f"i{i}": jnp.zeros((batch, max_len, cfg.index_dim), dtype)
                      for i in range(cfg.num_layers)})
    return cache


def init_layer_state(cfg: DeepseekV2Config, slots: int, max_len: int, dtype=None) -> dict:
    """The engine's state: :func:`init_kv_cache` over the slots, and the
    counters (:data:`MOE_COUNTERS`, :data:`MLA_COUNTERS`, with an indexer
    :data:`DSA_COUNTERS`; wrapping int32)."""
    state = init_kv_cache(cfg, slots, max_len, dtype)
    state["moe_counts"] = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    state["mla_counts"] = jnp.zeros((len(MLA_COUNTERS),), jnp.int32)
    if cfg.index_topk:
        state["dsa_counts"] = jnp.zeros((len(DSA_COUNTERS),), jnp.int32)
    return state


# -- forward ------------------------------------------------------------------


def _write_lines(cache, lines, index):
    """Write ``lines`` [B, S, W] into ``cache`` [B, L, W] at ``index`` (a
    scalar, or one start per row — then the rows one after another, each an
    update in place: as one scatter the compiler wants a ``[slots, max_len,
    W]`` leaf in another layout and copies it whole, PERF.md PR 35)."""
    if jnp.ndim(index) == 0:
        return jax.lax.dynamic_update_slice(cache, lines, (0, index, 0))
    for i in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(cache, lines[i: i + 1], (i, index[i], 0))
    return cache


def _index(params, p: str, u, qa, positions, cfg: DeepseekV2Config):
    """The indexer's projections of a block: (queries ``[B, S, Hi, di]``,
    weights ``[B, S, Hi]`` float32, keys ``[B, S, di]``) — queries from the
    QUERY latent ``qa``, keys and weights from the layer's normed input; in
    queries and keys the first ``qk_rope_head_dim`` lanes are roped (in halves)."""
    b, s = u.shape[:2]
    x, dr = p + "self_attn.indexer.", cfg.qk_rope_head_dim
    q = _linear(qa, params[x + "wq_b.weight"]).reshape(b, s, cfg.index_heads, cfg.index_dim)
    q = jnp.concatenate([apply_rope(q[..., :dr], positions, cfg, False), q[..., dr:]], axis=-1)
    k = _layer_norm(_linear(u, params[x + "wk.weight"]), params[x + "k_norm.weight"],
                    params[x + "k_norm.bias"], INDEX_NORM_EPS)
    k = jnp.concatenate(
        [apply_rope(k[:, :, None, :dr], positions, cfg, False)[:, :, 0], k[..., dr:]], axis=-1)
    w = jax.lax.dot_general(u, params[x + "weights_proj.weight"], (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return q, w * (cfg.index_heads ** -0.5 * cfg.index_dim ** -0.5), k


def _attention(params, p: str, u, positions, cfg: DeepseekV2Config, ctx: ShardingCtx, cache,
               cache_offset, attention_impl: str, index_cache=None):
    """u [B, S, D] (normed) -> (the heads' outputs [B, S, H * dv], the
    updated leaf or None, the updated index leaf or None, the step's MLA
    counts or None, its DSA counts or None)."""
    b, s = u.shape[:2]
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    width = cfg.line_width
    with jax.named_scope("dsv2.attn.q"):
        qa = _rms_norm(_linear(u, params[p + "self_attn.q_a_proj.weight"]),
                       params[p + "self_attn.q_a_layernorm.weight"], cfg.rms_eps)
        q = _linear(qa, params[p + "self_attn.q_b_proj.weight"]).reshape(b, s, h, dn + dr)
        q = ctx.constrain(q, "dp", "sp", "tp", None)
        q_nope, q_pe = q[..., :dn], apply_rope(q[..., dn:], positions, cfg)
    with jax.named_scope("dsv2.attn.latent"):
        kva = _linear(u, params[p + "self_attn.kv_a_proj_with_mqa.weight"])  # [B, S, r + dr]
        c = _rms_norm(kva[..., :r], params[p + "self_attn.kv_a_layernorm.weight"], cfg.rms_eps)
        k_pe = apply_rope(kva[..., None, r:], positions, cfg)[:, :, 0]
        lines = jnp.concatenate(
            [c, k_pe, jnp.zeros((b, s, width - r - dr), c.dtype)], axis=-1)
        if cache is None:
            # cache-less: the sequence's own lines, padded to whole key blocks
            # (positions past the sequence lie after every query)
            new_cache, offset = None, 0
            kb = latent_ops.EXPAND_BLOCK
            pad = ((0, 0), (0, -s % kb if s > kb else 0), (0, 0))
            rows = jnp.pad(lines, pad)
        else:
            offset = cache_offset
            rows = new_cache = _write_lines(cache, lines.astype(cache.dtype), offset)
    new_index = index_rows = None
    if cfg.index_topk:
        with jax.named_scope("dsa.index"):
            q_idx, w_idx, k_idx = _index(params, p, u, qa, positions, cfg)
            if cache is None:
                index_rows = jnp.pad(k_idx, pad)
            else:
                index_rows = new_index = _write_lines(
                    index_cache, k_idx.astype(index_cache.dtype), offset)
    # a cache of at most index_topk positions: the selection is every position
    selecting = cfg.index_topk and rows.shape[1] > cfg.index_topk
    # [H, dn + dv, r]: head h's W_uk (rows :dn) and W_uv (rows dn:)
    w_kvb = params[p + "self_attn.kv_b_proj.weight"].reshape(h, dn + dv, r)
    counts = dsa = None
    absorbed = cache is not None and s == 1 and attention_impl != "expanded"
    if absorbed:
        offsets = contexts = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
        with jax.named_scope("dsv2.attn.absorb"):
            q_lat = jnp.einsum("bhd,hdc->bhc", q_nope[:, 0], w_kvb[:, :dn],
                               preferred_element_type=jnp.float32).astype(q.dtype)
            q_cat = jnp.concatenate(
                [q_lat, q_pe[:, 0], jnp.zeros((b, h, width - r - dr), q.dtype)], axis=-1)
        key_block = 0
        if selecting:
            # the best index_topk of each row's context, their lines gathered:
            # the absorbed form then reads min(context, index_topk) lines a row
            # (the span: at TRACE time, once a layer — a program that selects says so)
            key_block, interpret = select_ops.takes_kernel(
                rows.shape, r, cfg.index_topk, attention_impl, ctx.mesh)
            with trace.span(f"dsa.select[{b}x{rows.shape[1]}->{cfg.index_topk}]"):
                with jax.named_scope("dsa.score"):
                    scores = select_ops.step_scores(q_idx[:, 0], w_idx[:, 0], index_rows,
                                                    offsets + 1, block=key_block,
                                                    interpret=interpret)
                with jax.named_scope("dsa.select"):
                    # through the module: a harness wraps this to see what a step chose
                    chosen = select_ops.select(scores, offsets + 1, cfg.index_topk)
                with jax.named_scope("dsa.gather"):
                    rows = select_ops.gather_lines(rows, chosen)
            offsets = jnp.minimum(offsets, cfg.index_topk - 1)
        with jax.named_scope("dsa.attend" if selecting else "dsv2.attn.attend"):
            o_lat = latent_ops.absorbed(q_cat, rows, offsets, cfg.softmax_scale, r,
                                        impl=attention_impl, mesh=ctx.mesh)
        with jax.named_scope("dsv2.attn.absorb"):
            o = jnp.einsum("bhc,hdc->bhd", o_lat, w_kvb[:, dn:],
                           preferred_element_type=jnp.float32).astype(q.dtype)[:, None]
        holds = contexts > 0
        lengths = jnp.where(holds, contexts + 1, 0)
        # lines the attention may see: a row's context, or what was gathered of it
        kept = jnp.where(holds, offsets + 1, 0) if selecting else lengths
        counts = jnp.stack([
            jnp.sum(latent_ops.positions_read(rows.shape, r, kept, attention_impl, ctx.mesh)),
            jnp.sum(lengths), jnp.sum(holds), jnp.sum(holds)]).astype(jnp.int32)
        if cfg.index_topk:
            choosing = jnp.sum(lengths > cfg.index_topk)
            dsa = jnp.stack([
                jnp.sum(lengths), jnp.sum(kept), choosing, jnp.sum(holds),
                choosing if key_block else 0]).astype(jnp.int32)
    else:
        selected = None
        if selecting:
            selected = select_ops.block_selection(q_idx, w_idx, index_rows, positions,
                                                  cfg.index_topk)
        with jax.named_scope("dsv2.attn.attend"):
            o = latent_ops.expanded(q_nope, q_pe, rows, offset, w_kvb, cfg.softmax_scale, r,
                                    selected=selected)
    return o.reshape(b, s, h * dv), new_cache, new_index, counts, dsa


def decoder_layer(params, p: str, x, positions, cfg: DeepseekV2Config, layer: int,
                  ctx: ShardingCtx, cache=None, cache_offset=0, attention_impl: str = "auto",
                  index_cache=None):
    """One block. Returns (x, the updated leaves (line, index key) or Nones,
    the counts (the expert layer's, a decode step's MLA and DSA) or Nones)."""
    u = _rms_norm(x, params[p + "input_layernorm.weight"], cfg.rms_eps)
    o, new_cache, new_index, mla, dsa = _attention(
        params, p, u, positions, cfg, ctx, cache, cache_offset, attention_impl, index_cache)
    leaves = (new_cache, new_index)
    with jax.named_scope("dsv2.attn.out"):
        x = ctx.constrain(x + _linear(o, params[p + "self_attn.o_proj.weight"]),
                          "dp", "sp", None)
    m = _rms_norm(x, params[p + "post_attention_layernorm.weight"], cfg.rms_eps)
    if cfg.is_dense(layer):
        with jax.named_scope("dsv2.mlp.dense"):
            ff = jax.nn.silu(_linear(m, params[p + "mlp.gate_proj.weight"])) * _linear(
                m, params[p + "mlp.up_proj.weight"])
            ff = ctx.constrain(ff, "dp", "sp", "tp")
            return ctx.constrain(x + _linear(ff, params[p + "mlp.down_proj.weight"]),
                                 "dp", "sp", None), leaves, (None, mla, dsa)
    shared = None
    if cfg.n_shared_experts:
        shared = tuple(params[p + f"mlp.shared_experts.{w}_proj.weight"]
                       for w in ("gate", "up", "down"))

    noaux = {}
    if cfg.noaux:
        noaux = dict(scoring="sigmoid",
                     choice_bias=params[p + "mlp.gate.e_score_correction_bias"])

    def experts(tokens):
        return moe_ops.moe_share_ffn(
            tokens, params[p + "mlp.gate.weight"], params[p + "mlp.experts.gate_proj.weight"],
            params[p + "mlp.experts.up_proj.weight"],
            params[p + "mlp.experts.down_proj.weight"],
            top_k=cfg.top_k, held=cfg.held, renormalize=cfg.norm_topk_prob,
            routed_scale=cfg.combine_scale, shared=shared,
            constrain=ctx.constrain, groups=cfg.groups, mesh=ctx.mesh,
            scopes=("dsv2.moe.routed", "dsv2.moe.shared", "dsv2.moe.route"), **noaux)

    b, s, d = m.shape
    if b * s <= MOE_TOKENS:
        y, counts = experts(m)
    else:
        # every held expert runs on every token ([E_held, T, F] activations,
        # three of them): a long cache-less forward goes MOE_CHUNK tokens at a
        # time, one chunk live, padded with zero tokens that are cut off again
        flat = jnp.pad(m.reshape(b * s, d), ((0, -(b * s) % MOE_CHUNK), (0, 0)))
        ys, counts = jax.lax.map(experts, flat.reshape(-1, 1, MOE_CHUNK, d))
        y, counts = ys.reshape(-1, d)[: b * s].reshape(b, s, d), jnp.sum(counts, axis=0)
    return ctx.constrain(x + y, "dp", "sp", None), leaves, (counts, mla, dsa)


def forward(params, tokens, cfg: DeepseekV2Config, positions=None,
            kv_cache: dict | None = None, cache_offset: int | jax.Array = 0,
            mesh: Mesh | None = None, attention_impl: str = "auto"):
    """Returns (logits [B,S,V], updated kv_cache). ``kv_cache`` None: one
    cache-less pass. Else (:func:`init_kv_cache` / :func:`init_layer_state`) a
    block of positions at ``cache_offset`` in the expanded form, or — one
    token a row — a decode step at per-row offsets in the absorbed form
    (``attention_impl`` ``"expanded"`` keeps the expanded form for it: what a
    test holds the absorbed one against; ``"ragged+interpret"`` asks for the
    absorbed kernel on the CPU). The ``moe_counts`` / ``mla_counts`` leaves
    grow by what the step counted."""
    ctx = ShardingCtx(mesh)
    b, s = tokens.shape
    if positions is None:
        off = jnp.asarray(cache_offset if kv_cache is not None else 0)
        positions = jnp.arange(s)[None, :] + (off[:, None] if off.ndim else off)
        positions = jnp.broadcast_to(positions, (b, s))
    x = jnp.take(params["model.embed_tokens.weight"], tokens, axis=0).astype(cfg.dtype)
    x = ctx.constrain(x, "dp", "sp", None)
    new_cache: dict | None = {} if kv_cache is not None else None
    leaves = ("moe_counts", "mla_counts") + (("dsa_counts",) if cfg.index_topk else ())
    counted = [jnp.zeros((len(names),), jnp.int32)
               for names in (MOE_COUNTERS, MLA_COUNTERS, DSA_COUNTERS)[: len(leaves)]]
    for i in range(cfg.num_layers):
        cache = kv_cache[f"c{i}"] if kv_cache is not None else None
        index = kv_cache[f"i{i}"] if kv_cache is not None and cfg.index_topk else None
        x, updated, counts = decoder_layer(
            params, f"model.layers.{i}.", x, positions, cfg, i, ctx, cache=cache,
            cache_offset=cache_offset, attention_impl=attention_impl, index_cache=index)
        for name, leaf in zip((f"c{i}", f"i{i}"), updated):
            if leaf is not None:
                new_cache[name] = leaf
        counted = [have if add is None else have + add for have, add in zip(counted, counts)]
    if kv_cache is not None:
        for leaf, grown in zip(leaves, counted):
            if leaf in kv_cache:
                new_cache[leaf] = kv_cache[leaf] + grown
    x = _rms_norm(x, params["model.norm.weight"], cfg.rms_eps)
    logits = _linear(x, params["lm_head.weight"])
    return ctx.constrain(logits, "dp", "sp", None), new_cache
