"""Nemotron-H decoder: Mamba-2 state-space layers beside a latent expert layer.

A layer is ONE mixer behind one RMS norm and one residual — ``x <- x +
mixer(N(x))`` — and ``hybrid_override_pattern`` says which, a character a
layer: ``M`` a Mamba-2 state-space mixer (ops/ssm.py: a short depthwise causal
convolution, then a recurrence whose whole past is one ``[heads, head_dim,
state]`` float32 STATE a row and the convolution's last ``kernel - 1`` inputs,
the TAIL), ``E`` sparse experts of two matrices with a squared relu between
them, run in a latent width between a down- and an up-projection that every
token passes, beside a shared expert on the hidden state
(ops/moe.moe_share_ffn), ``*`` grouped-query attention without rotary
embedding, ``-`` a dense squared-relu MLP. The equations, and every departure
from the published modelling code, are written out in the plain float32
reference, ``models/nemotron_h_reference.py``.

Params are a flat dict keyed by the checkpoint's names, the experts stacked
along a leading axis (the loader folds ``experts.<i>.*``):

    backbone.embeddings.weight                      [V, D]
    backbone.layers.N.norm.weight                   [D]
    backbone.layers.N.mixer.in_proj.weight          [2 I + 2 G S + H, D]   M
    backbone.layers.N.mixer.conv1d.{weight,bias}    [I + 2 G S, 1, K], [I + 2 G S]
    backbone.layers.N.mixer.{dt_bias,A_log,D}       [H]
    backbone.layers.N.mixer.norm.weight             [I]
    backbone.layers.N.mixer.out_proj.weight         [D, I]
    backbone.layers.N.mixer.gate.weight             [E_pub, D]   router     E
    backbone.layers.N.mixer.gate.e_score_correction_bias  [E_pub]
    backbone.layers.N.mixer.fc{1,2}_latent_proj.weight    [L, D], [D, L]
    backbone.layers.N.mixer.experts.up_proj.weight  [E_held, F, L]
    backbone.layers.N.mixer.experts.down_proj.weight  [E_held, L, F]
    backbone.layers.N.mixer.shared_experts.{up,down}_proj.weight
    backbone.layers.N.mixer.{q,k,v,o}_proj.weight                           *
    backbone.layers.N.mixer.{up,down}_proj.weight                           -
    backbone.norm_f.weight, lm_head.weight

(``I`` = ``mamba_num_heads x mamba_head_dim``, ``G`` = ``n_groups``, ``S`` =
``ssm_state_size``, ``L`` = ``moe_latent_size``.)

**The config comes from ``config.json``, never from tensor shapes**
(:func:`config_from_hf`). **The share key**: ``n_routed_experts`` counts the
experts held and ``"expert_share": {"published": 512, "first": 0}`` says which
of how many (as Laguna's): the router keeps its published width, routing runs
over all of them, only the held experts' part of the latent sum is computed
and up-projected, and nothing stands in for the others.

Three forms of each layer over one set of equations: cache-less
(``/v1/forward``: the Mamba layers chunk by chunk from a zero state); a block
of positions over a cache (an admission's scratch) — ``valid_len`` says how
many of the block's positions are real, and a padded bucket's tail enters
neither state nor convolution tail; one token a row over the engine's state —
``live`` marks the rows that decode, the others keep state and tail bit for
bit.

The cache (``init_kv_cache``; the engine's, ``init_layer_state``, adds the
counters): per ``M`` layer ``s<i>`` ``[B, H, P, S]`` float32 and ``t<i>``
``[B, K - 1, I + 2 G S]``, both of kind ``"state"`` — no position axis, which
is why the continuous engine refuses for this family what cuts a row at a
token: ``--prefix-cache``, ``--speculative-k``, ``--kv-page-size``
(dl/kv_layout.LayerKindKV); per ``*`` layer ``k<i>``, ``v<i>`` ``[B, L, Hkv *
d]`` (a position's KV heads side by side, as MiniCPM-SALA's: two KV heads as an
axis of their own make the TPU compiler re-lay the leaf); ``E`` and ``-``
layers cache nothing.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from modelx_tpu.models.laguna import MOE_COUNTERS
from modelx_tpu.models.llama import ShardingCtx, _rms_norm
# keys and values laid and written as MiniCPM-SALA's sparse layers' are
from modelx_tpu.models.minicpm_sala import _flat, _write_rows
from modelx_tpu.ops import attention as attn_ops
from modelx_tpu.ops import moe as moe_ops
from modelx_tpu.ops import ssm as ssm_ops
from modelx_tpu.ops.nn import linear as _linear

MAMBA, EXPERTS, ATTENTION, DENSE = "M", "E", "*", "-"
# the engine's counter of the rows, once a decode step (not a layer): row-steps
# of live rows, row-steps of all rows — so ``steps_all`` over the slots is the
# number of decode steps — and the positions the live rows hold, their contexts
# (dl/kv_layout.LayerKindKV reads them back with the tokens)
SSM_COUNTERS = ("steps_live", "steps_all", "positions_live")
# tokens one call of the expert layer takes whole, and the chunk a longer block
# goes in (as DeepSeek-V2's: every held expert runs on every token)
MOE_TOKENS, MOE_CHUNK = 4096, 1024


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = "MEMEMEM*EME"  # one character a layer
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    conv_bias: bool = True
    intermediate_size: int = 2688  # the ``-`` layers' MLP
    num_experts: int = 512  # the router's published width
    expert_first: int = 0  # the experts held here: first .. first + count
    expert_count: int = 512
    top_k: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024  # 0: the experts read the hidden state itself
    shared_intermediate_size: int = 5376  # 0: no shared expert
    norm_topk_prob: bool = True
    routed_scale: float = 5.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def held(self) -> tuple[int, int]:
        return self.expert_first, self.expert_count

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def expert_width(self) -> int:
        """The width the experts read and write."""
        return self.moe_latent_size or self.hidden_size

    @classmethod
    def tiny(cls, vocab_size: int = 256, **over) -> "NemotronHConfig":
        """Test config: every mechanism at toy sizes — five layers (Mamba,
        experts, Mamba, attention, experts), 8 Mamba heads of 4 over a state
        of 8 in 2 groups, chunks of 8 positions, 2 KV heads under 4 query
        heads, 16 experts top-3 in a latent of 16 beside a shared one."""
        base = dict(
            vocab_size=vocab_size, hidden_size=32, pattern="MEM*E", num_heads=4, num_kv_heads=2,
            head_dim=8, mamba_heads=8, mamba_head_dim=4, ssm_state_size=8, n_groups=2,
            conv_kernel=4, chunk_size=8, intermediate_size=48, num_experts=16, expert_first=0,
            expert_count=16, top_k=3, moe_intermediate_size=24, moe_latent_size=16,
            shared_intermediate_size=40, routed_scale=2.5, dtype=jnp.float32)
        base.update(over)
        return cls(**base)


_REFUSED_FLAGS = {
    "mamba_proj_bias": "biases on the Mamba projections",
    "attention_bias": "attention biases",
    "mlp_bias": "MLP biases",
    "use_bias": "biases on the projections",
    "moe_shared_expert_overlap": "the shared expert overlapped with the exchange",
    "residual_in_fp32": "a float32 residual stream",
    "tie_word_embeddings": "a tied output head",
}


def config_from_hf(raw: Mapping, dtype=jnp.bfloat16) -> NemotronHConfig:
    """The config of a checkpoint from its ``config.json``. Raises for what
    this family does not implement rather than serving something else."""
    pattern = str(raw["hybrid_override_pattern"])
    n = int(raw["num_hidden_layers"])
    if len(pattern) != n:
        raise ValueError(f"nemotron_h: hybrid_override_pattern lists {len(pattern)} layers, "
                         f"num_hidden_layers is {n}")
    bad = set(pattern) - {MAMBA, EXPERTS, ATTENTION, DENSE}
    if bad:
        raise ValueError(f"nemotron_h: unknown layer kind(s) {sorted(bad)} in "
                         "hybrid_override_pattern")
    for key, what in _REFUSED_FLAGS.items():
        if raw.get(key):
            raise ValueError(f"nemotron_h: {what} ({key}) are not implemented")
    if int(raw.get("n_group", 1)) != 1 or int(raw.get("topk_group", 1)) != 1:
        raise ValueError("nemotron_h: group-limited routing (n_group, topk_group other than 1) "
                         "is not implemented")
    if raw.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(f"nemotron_h: mamba_hidden_act {raw['mamba_hidden_act']!r} is not "
                         "implemented (silu)")
    if raw.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError(f"nemotron_h: mlp_hidden_act {raw['mlp_hidden_act']!r} is not "
                         "implemented (relu2)")
    heads, groups = int(raw["mamba_num_heads"]), int(raw["n_groups"])
    if heads % groups:
        raise ValueError(f"nemotron_h: {heads} Mamba heads do not fall into {groups} groups")
    held = int(raw.get("n_routed_experts", 0))
    share = raw.get("expert_share") or {}
    published, first = int(share.get("published", held)), int(share.get("first", 0))
    if first < 0 or first + held > published:
        raise ValueError(f"nemotron_h: expert_share holds {first}..{first + held} "
                         f"of {published} published experts")
    q_heads = int(raw["num_attention_heads"])
    return NemotronHConfig(
        vocab_size=int(raw["vocab_size"]), hidden_size=int(raw["hidden_size"]), pattern=pattern,
        num_heads=q_heads, num_kv_heads=int(raw["num_key_value_heads"]),
        head_dim=int(raw.get("head_dim") or raw["hidden_size"] // q_heads),
        mamba_heads=heads, mamba_head_dim=int(raw["mamba_head_dim"]),
        ssm_state_size=int(raw["ssm_state_size"]), n_groups=groups,
        conv_kernel=int(raw["conv_kernel"]), chunk_size=int(raw.get("chunk_size", 128)),
        conv_bias=bool(raw.get("use_conv_bias", True)),
        intermediate_size=int(raw.get("intermediate_size", 0)),
        num_experts=published, expert_first=first, expert_count=held,
        top_k=int(raw.get("num_experts_per_tok", 0)),
        moe_intermediate_size=int(raw.get("moe_intermediate_size", 0)),
        moe_latent_size=int(raw.get("moe_latent_size") or 0),
        shared_intermediate_size=int(raw.get("n_shared_experts") or 0)
        * int(raw.get("moe_shared_expert_intermediate_size") or 0),
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        routed_scale=float(raw.get("routed_scaling_factor", 1.0)),
        rms_eps=float(raw.get("layer_norm_epsilon", raw.get("norm_eps", 1e-5))), dtype=dtype)


def to_hf_config(cfg: NemotronHConfig) -> dict:
    """The ``config.json`` that :func:`config_from_hf` reads back as ``cfg``
    (test checkpoints, and the reference, which reads the architecture from
    this and not from ``cfg``)."""
    return {
        "model_type": "nemotron_h", "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers, "hybrid_override_pattern": cfg.pattern,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "mamba_num_heads": cfg.mamba_heads,
        "mamba_head_dim": cfg.mamba_head_dim, "ssm_state_size": cfg.ssm_state_size,
        "n_groups": cfg.n_groups, "conv_kernel": cfg.conv_kernel, "chunk_size": cfg.chunk_size,
        "use_conv_bias": cfg.conv_bias, "intermediate_size": cfg.intermediate_size,
        "n_routed_experts": cfg.expert_count,
        "expert_share": {"published": cfg.num_experts, "first": cfg.expert_first},
        "num_experts_per_tok": cfg.top_k, "moe_intermediate_size": cfg.moe_intermediate_size,
        "moe_latent_size": cfg.moe_latent_size or None,
        "n_shared_experts": 1 if cfg.shared_intermediate_size else 0,
        "moe_shared_expert_intermediate_size": cfg.shared_intermediate_size,
        "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scale,
        "n_group": 1, "topk_group": 1, "layer_norm_epsilon": cfg.rms_eps,
        "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2", "mamba_proj_bias": False,
        "attention_bias": False, "mlp_bias": False, "use_bias": False,
        "moe_shared_expert_overlap": False, "residual_in_fp32": False,
        "tie_word_embeddings": False,
    }


# -- params -------------------------------------------------------------------


def param_shapes(cfg: NemotronHConfig) -> dict[str, tuple[int, ...]]:
    """Stacked-expert layout, linear weights [out, in]."""
    e, w = cfg.hidden_size, cfg.expert_width
    shapes: dict[str, tuple[int, ...]] = {
        "backbone.embeddings.weight": (cfg.vocab_size, e),
        "backbone.norm_f.weight": (e,),
        "lm_head.weight": (cfg.vocab_size, e),
    }
    for i, kind in enumerate(cfg.pattern):
        p = f"backbone.layers.{i}."
        shapes[p + "norm.weight"] = (e,)
        if kind == MAMBA:
            shapes.update({
                p + "mixer.in_proj.weight": (cfg.mamba_inner + cfg.conv_dim + cfg.mamba_heads, e),
                p + "mixer.conv1d.weight": (cfg.conv_dim, 1, cfg.conv_kernel),
                p + "mixer.dt_bias": (cfg.mamba_heads,), p + "mixer.A_log": (cfg.mamba_heads,),
                p + "mixer.D": (cfg.mamba_heads,), p + "mixer.norm.weight": (cfg.mamba_inner,),
                p + "mixer.out_proj.weight": (e, cfg.mamba_inner),
            })
            if cfg.conv_bias:
                shapes[p + "mixer.conv1d.bias"] = (cfg.conv_dim,)
        elif kind == EXPERTS:
            f = cfg.moe_intermediate_size
            shapes.update({
                p + "mixer.gate.weight": (cfg.num_experts, e),
                p + "mixer.gate.e_score_correction_bias": (cfg.num_experts,),
                p + "mixer.experts.up_proj.weight": (cfg.expert_count, f, w),
                p + "mixer.experts.down_proj.weight": (cfg.expert_count, w, f),
            })
            if cfg.moe_latent_size:
                shapes[p + "mixer.fc1_latent_proj.weight"] = (w, e)
                shapes[p + "mixer.fc2_latent_proj.weight"] = (e, w)
            if cfg.shared_intermediate_size:
                shapes[p + "mixer.shared_experts.up_proj.weight"] = (
                    cfg.shared_intermediate_size, e)
                shapes[p + "mixer.shared_experts.down_proj.weight"] = (
                    e, cfg.shared_intermediate_size)
        elif kind == ATTENTION:
            q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
            shapes.update({
                p + "mixer.q_proj.weight": (q, e), p + "mixer.k_proj.weight": (kv, e),
                p + "mixer.v_proj.weight": (kv, e), p + "mixer.o_proj.weight": (e, q),
            })
        else:
            shapes[p + "mixer.up_proj.weight"] = (cfg.intermediate_size, e)
            shapes[p + "mixer.down_proj.weight"] = (e, cfg.intermediate_size)
    return shapes


def init_params(cfg: NemotronHConfig, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    for (name, shape), k in zip(sorted(shapes.items()), jax.random.split(key, len(shapes))):
        if ".mixer." not in name and name.endswith(("norm.weight", "norm_f.weight")):
            params[name] = jnp.ones(shape, dtype)
        elif name.endswith("mixer.norm.weight"):  # the gated norm's: not all ones
            params[name] = (1.0 + 0.1 * jax.random.normal(k, shape)).astype(dtype)
        elif name.endswith("A_log"):  # A = -exp(A_log) in -(1 .. 4): states that forget
            params[name] = jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=4.0)
                                   ).astype(dtype)
        elif name.endswith(("dt_bias", "mixer.D", "e_score_correction_bias")):
            params[name] = (0.2 * jax.random.normal(k, shape)).astype(dtype)
        else:
            params[name] = (jax.random.normal(k, shape) / math.sqrt(shape[-1])).astype(dtype)
    return params


def to_hf_state_dict(params: Mapping[str, Any], first: int = 0) -> dict[str, np.ndarray]:
    """Unstack the experts into the checkpoint's per-expert names
    (``experts.<first + j>.*``) — what a push holds."""
    out: dict[str, np.ndarray] = {}
    for name, value in params.items():
        if ".mixer.experts." in name:
            head, tail = name.split(".mixer.experts.")
            for j, w in enumerate(np.asarray(value)):
                out[f"{head}.mixer.experts.{first + j}.{tail}"] = w
        else:
            out[name] = np.asarray(value)
    return out


# -- kv state -----------------------------------------------------------------


_LEAVES = {MAMBA: ("s", "t"), ATTENTION: ("k", "v"), EXPERTS: (), DENSE: ()}


def published(cfg: NemotronHConfig) -> dict:
    """What a pod's /metrics names of this family: the counter leaves the
    decode step accumulates (leaf -> (stats block, its entries' names)) — of
    its expert layers over ALL slots (idle ones route too); of its rows, live
    ones and all, once a step — and the gauges beside them."""
    return {
        "counters": {"moe_counts": ("moe", MOE_COUNTERS),
                     "ssm_counts": ("ssm", SSM_COUNTERS)},
        "gauges": {"moe": {"held_experts": cfg.expert_count,
                           "published_experts": cfg.num_experts,
                           "sparse_layers": cfg.pattern.count(EXPERTS),
                           "latent_size": cfg.moe_latent_size},
                   "ssm": {"layers": cfg.pattern.count(MAMBA),
                           "heads": cfg.mamba_heads, "head_dim": cfg.mamba_head_dim,
                           "state_size": cfg.ssm_state_size, "groups": cfg.n_groups,
                           "conv_kernel": cfg.conv_kernel}},
    }


def cache_kinds(cfg: NemotronHConfig) -> dict[str, str]:
    """Leaf name -> its kind in the engine's state (dl/kv_layout.LayerKindKV):
    a Mamba layer's two ``"state"`` leaves (the recurrence's state and the
    convolution's tail), an attention layer's ``"full"`` keys and values, the
    two ``"counter"`` vectors."""
    kinds: dict[str, str] = {}
    for i, kind in enumerate(cfg.pattern):
        leaf_kind = "state" if kind == MAMBA else "full"
        kinds.update({f"{leaf}{i}": leaf_kind for leaf in _LEAVES[kind]})
    kinds.update(moe_counts="counter", ssm_counts="counter")
    return kinds


def init_kv_cache(cfg: NemotronHConfig, batch: int, max_len: int, dtype=None) -> dict:
    """The cache of ``batch`` rows of ``max_len`` positions: the Mamba layers'
    states (float32) and tails, the attention layers' keys and values."""
    dtype = dtype or cfg.dtype
    cache = {}
    for i, kind in enumerate(cfg.pattern):
        if kind == MAMBA:
            cache[f"s{i}"] = jnp.zeros((batch, cfg.mamba_heads, cfg.mamba_head_dim,
                                        cfg.ssm_state_size), jnp.float32)
            cache[f"t{i}"] = jnp.zeros((batch, cfg.conv_kernel - 1, cfg.conv_dim), dtype)
        elif kind == ATTENTION:
            for leaf in "kv":
                cache[f"{leaf}{i}"] = jnp.zeros(
                    (batch, max_len, cfg.num_kv_heads * cfg.head_dim), dtype)
    return cache


def init_layer_state(cfg: NemotronHConfig, slots: int, max_len: int, dtype=None) -> dict:
    """The engine's state: :func:`init_kv_cache` over the slots, and the
    counters (:data:`MOE_COUNTERS`, :data:`SSM_COUNTERS`, wrapping int32)."""
    state = init_kv_cache(cfg, slots, max_len, dtype)
    state["moe_counts"] = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    state["ssm_counts"] = jnp.zeros((len(SSM_COUNTERS),), jnp.int32)
    return state


# -- forward ------------------------------------------------------------------


def _gated_norm(y, z, weight, groups: int, eps: float):
    """``N_groups(y * silu(z))``: the RMS statistic over each of ``groups``
    runs of the width by itself, one weight over the whole width. float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shape) * weight.astype(jnp.float32)


def _mamba(params, p: str, u, cfg: NemotronHConfig, ctx: ShardingCtx, cache, offset,
           valid_len, live):
    """u [B, S, D] (normed) -> (y [B, S, D], the new (state, tail) or None)."""
    b, s = u.shape[:2]
    inner, heads, hd = cfg.mamba_inner, cfg.mamba_heads, cfg.mamba_head_dim
    gn = cfg.n_groups * cfg.ssm_state_size
    with jax.named_scope("nemh.mamba.in_proj"):
        zxd = _linear(u, params[p + "mixer.in_proj.weight"])
        z, xbc, dt = zxd[..., :inner], zxd[..., inner: inner + cfg.conv_dim], zxd[
            ..., inner + cfg.conv_dim:]
    conv_w = params[p + "mixer.conv1d.weight"][:, 0, :]
    conv_b = params.get(p + "mixer.conv1d.bias")
    stepping = cache is not None and s == 1
    if cache is None:
        state = jnp.zeros((b, heads, hd, cfg.ssm_state_size), jnp.float32)
        tail = jnp.zeros((b, cfg.conv_kernel - 1, cfg.conv_dim), u.dtype)
    else:
        state, tail = cache
        if not stepping:  # a block at position 0 starts a row: what the slot held is not its past
            fresh = jnp.asarray(offset) == 0
            state = jnp.where(fresh, 0.0, state)
            tail = jnp.where(fresh, jnp.zeros_like(tail), tail)
    with jax.named_scope("nemh.mamba.conv"):
        if stepping:
            xbc, tail = ssm_ops.conv_step(xbc[:, 0], tail, conv_w, conv_b, live=live)
            xbc = xbc[:, None]
        else:
            xbc, tail = ssm_ops.conv_block(xbc, tail, conv_w, conv_b, valid_len=valid_len)
        xbc = jax.nn.silu(xbc)
    x = xbc[..., :inner].reshape(b, s, heads, hd)
    bm = xbc[..., inner: inner + gn].reshape(b, s, cfg.n_groups, cfg.ssm_state_size)
    cm = xbc[..., inner + gn:].reshape(b, s, cfg.n_groups, cfg.ssm_state_size)
    f32 = jnp.float32
    dt = jax.nn.softplus(dt.astype(f32) + params[p + "mixer.dt_bias"].astype(f32))
    a = -jnp.exp(params[p + "mixer.A_log"].astype(f32))
    d = params[p + "mixer.D"]
    if stepping:
        with jax.named_scope("nemh.mamba.step"):
            y, state = ssm_ops.step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, state, live=live)
            y = y[:, None]
    else:
        with jax.named_scope("nemh.mamba.scan"):
            y, state = ssm_ops.chunked(x, dt, a, bm, cm, d, state, valid_len=valid_len,
                                       chunk=cfg.chunk_size)
    with jax.named_scope("nemh.mamba.norm"):
        y = _gated_norm(y.reshape(b, s, inner), z, params[p + "mixer.norm.weight"],
                        cfg.n_groups, cfg.rms_eps).astype(u.dtype)
        y = ctx.constrain(y, "dp", "sp", "tp")
    with jax.named_scope("nemh.mamba.out"):
        out = _linear(y, params[p + "mixer.out_proj.weight"])
    return out, (None if cache is None else (state, tail))


def _attend_rows(q, ck, cv, lengths, kv_heads: int):
    """A decode step's attention over keys and values as the cache lays them.
    q ``[B, H, d]``, ck / cv ``[B, L, Hkv * d]``, lengths ``[B]`` (a row's
    context, its new position included) -> ``[B, H, d]`` float32. Each KV head
    is a run of whole lane tiles of the leaf's last axis, taken as a slice:
    ``[B, L, Hkv, d]`` as an axis of its own is another layout to the TPU
    compiler, which then copies both leaves whole every step (a compile for a
    described v5e, PR 46, as PR 35 found for MiniCPM-SALA)."""
    b, heads, d = q.shape
    qg = q.reshape(b, kv_heads, heads // kv_heads, d)
    seen = jnp.arange(ck.shape[1])[None, None, :] < lengths[:, None, None]
    outs = []
    for j in range(kv_heads):
        k, v = ck[:, :, j * d: (j + 1) * d], cv[:, :, j * d: (j + 1) * d]
        scores = jnp.einsum("bgd,bld->bgl", qg[:, j], k,
                            preferred_element_type=jnp.float32) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, attn_ops.NEG_INF), axis=-1)
        outs.append(jnp.einsum("bgl,bld->bgd", probs.astype(v.dtype), v,
                               preferred_element_type=jnp.float32))
    return jnp.stack(outs, axis=1).reshape(b, heads, d)


def _attention(params, p: str, u, cfg: NemotronHConfig, ctx: ShardingCtx, cache, offset,
               attention_impl: str):
    """u [B, S, D] (normed) -> (y [B, S, D], the new (k, v) or None). No
    rotary embedding: position reaches this layer through the Mamba layers."""
    b, s = u.shape[:2]
    heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _linear(u, params[p + "mixer.q_proj.weight"]).reshape(b, s, heads, hd)
    k = _linear(u, params[p + "mixer.k_proj.weight"]).reshape(b, s, kvh, hd)
    v = _linear(u, params[p + "mixer.v_proj.weight"]).reshape(b, s, kvh, hd)
    q = ctx.constrain(q, "dp", "sp", "tp", None)
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    if cache is None:
        impl, _, flag = attention_impl.partition("+")
        if impl in ("auto", "ragged"):
            impl = "flash" if jax.default_backend() == "tpu" else "reference"
        attn_ops.note_choice(impl, s, s, ctx.mesh, group=heads // kvh)
        if impl == "flash":
            o = t(attn_ops.flash_attention(t(q), t(k), t(v), causal=True, mesh=ctx.mesh,
                                           interpret=flag == "interpret"))
        else:
            o = t(attn_ops.attention_reference(t(q), t(k), t(v), causal=True))
        new = None
    else:
        ck, cv = cache
        by_row = jnp.ndim(offset) != 0
        ck = _write_rows(ck, _flat(k), offset, by_row=by_row)
        cv = _write_rows(cv, _flat(v), offset, by_row=by_row)
        if by_row and s == 1:
            o = _attend_rows(q[:, 0], ck, cv, offset + 1, kvh)[:, None]
        else:  # an admission's block over its scratch: one bucket long
            rows = lambda c: c.reshape(b, c.shape[1], kvh, hd)  # noqa: E731
            o = attn_ops.cached_attention(q, rows(ck), rows(cv), offset, impl=attention_impl,
                                          mesh=ctx.mesh)
        new = (ck, cv)
    return _linear(o.reshape(b, s, heads * hd).astype(u.dtype),
                   params[p + "mixer.o_proj.weight"]), new


def _experts(params, p: str, u, cfg: NemotronHConfig, ctx: ShardingCtx):
    """u [B, S, D] (normed) -> (y [B, S, D], the layer's counts)."""
    shared = latent = None
    if cfg.shared_intermediate_size:
        shared = (None, params[p + "mixer.shared_experts.up_proj.weight"],
                  params[p + "mixer.shared_experts.down_proj.weight"])
    if cfg.moe_latent_size:
        latent = (params[p + "mixer.fc1_latent_proj.weight"],
                  params[p + "mixer.fc2_latent_proj.weight"])

    def experts(tokens):
        return moe_ops.moe_share_ffn(
            tokens, params[p + "mixer.gate.weight"], None,
            params[p + "mixer.experts.up_proj.weight"],
            params[p + "mixer.experts.down_proj.weight"],
            top_k=cfg.top_k, held=cfg.held, renormalize=cfg.norm_topk_prob,
            routed_scale=cfg.routed_scale, shared=shared, constrain=ctx.constrain, mesh=ctx.mesh,
            scoring="sigmoid", choice_bias=params[p + "mixer.gate.e_score_correction_bias"],
            form="relu2", latent=latent,
            scopes=("nemh.moe.routed", "nemh.moe.shared", "nemh.moe.route", "nemh.moe.down",
                    "nemh.moe.up"))

    b, s, d = u.shape
    if b * s <= MOE_TOKENS:
        return experts(u)
    # every held expert runs on every token ([E_held, T, F] activations): a long
    # cache-less forward goes MOE_CHUNK tokens at a time, padded with zero tokens
    flat = jnp.pad(u.reshape(b * s, d), ((0, -(b * s) % MOE_CHUNK), (0, 0)))
    ys, counts = jax.lax.map(experts, flat.reshape(-1, 1, MOE_CHUNK, d))
    return ys.reshape(-1, d)[: b * s].reshape(b, s, d), jnp.sum(counts, axis=0)


def decoder_layer(params, x, cfg: NemotronHConfig, layer: int, ctx: ShardingCtx, cache=None,
                  cache_offset=0, valid_len=None, live=None, attention_impl: str = "auto"):
    """One block: ``x + mixer(N(x))``. ``cache``: None, or the layer's leaves
    (``(state, tail)`` of a Mamba layer, ``(k, v)`` of an attention layer,
    ``()`` of the others). Returns (x, the updated leaves or None, the expert
    layer's counts or None)."""
    p = f"backbone.layers.{layer}."
    kind = cfg.pattern[layer]
    u = _rms_norm(x, params[p + "norm.weight"], cfg.rms_eps)
    new_cache = counts = None
    if kind == MAMBA:
        y, new_cache = _mamba(params, p, u, cfg, ctx, cache, cache_offset, valid_len, live)
    elif kind == ATTENTION:
        with jax.named_scope("nemh.attn"):
            y, new_cache = _attention(params, p, u, cfg, ctx, cache, cache_offset, attention_impl)
    elif kind == EXPERTS:
        y, counts = _experts(params, p, u, cfg, ctx)
    else:
        with jax.named_scope("nemh.mlp.dense"):
            ff = jnp.square(jax.nn.relu(_linear(u, params[p + "mixer.up_proj.weight"])))
            y = _linear(ctx.constrain(ff, "dp", "sp", "tp"), params[p + "mixer.down_proj.weight"])
    return ctx.constrain(x + y.astype(x.dtype), "dp", "sp", None), new_cache, counts


def forward(params, tokens, cfg: NemotronHConfig, kv_cache: dict | None = None,
            cache_offset: int | jax.Array = 0, mesh: Mesh | None = None,
            attention_impl: str = "auto", valid_len=None, live=None):
    """Returns (logits [B,S,V], updated kv_cache). ``kv_cache`` None: one
    cache-less pass. Else (:func:`init_kv_cache` / :func:`init_layer_state`) a
    block of positions at a scalar ``cache_offset`` — ``valid_len`` [B] its
    real positions, all of them when None — or, one token a row, a decode step
    at per-row offsets, of which ``live`` [B] marks the rows that decode (all
    when None); the ``moe_counts`` / ``ssm_counts`` leaves grow by what the
    step counted."""
    ctx = ShardingCtx(mesh)
    b, s = tokens.shape
    if kv_cache is not None and s > 1 and jnp.ndim(cache_offset) != 0:
        raise ValueError("nemotron_h: a block of positions lands at one offset for all rows")
    x = jnp.take(params["backbone.embeddings.weight"], tokens, axis=0).astype(cfg.dtype)
    x = ctx.constrain(x, "dp", "sp", None)
    new_cache: dict | None = {} if kv_cache is not None else None
    moe_counted = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    for i, kind in enumerate(cfg.pattern):
        names = [f"{leaf}{i}" for leaf in _LEAVES[kind]]
        cache = tuple(kv_cache[n] for n in names) if kv_cache is not None else None
        x, updated, counts = decoder_layer(
            params, x, cfg, i, ctx, cache=cache, cache_offset=cache_offset, valid_len=valid_len,
            live=live, attention_impl=attention_impl)
        if updated is not None:
            new_cache.update(zip(names, updated))
        if counts is not None:
            moe_counted = moe_counted + counts
    if kv_cache is not None:
        if "moe_counts" in kv_cache:
            new_cache["moe_counts"] = kv_cache["moe_counts"] + moe_counted
        if "ssm_counts" in kv_cache:
            stepped = jnp.zeros((len(SSM_COUNTERS),), jnp.int32)
            if s == 1:
                decoding = jnp.ones((b,), bool) if live is None else live
                held = jnp.where(decoding, jnp.asarray(cache_offset, jnp.int32) + 1, 0)
                stepped = jnp.stack([jnp.sum(decoding), b, jnp.sum(held)]).astype(jnp.int32)
            new_cache["ssm_counts"] = kv_cache["ssm_counts"] + stepped
    x = _rms_norm(x, params["backbone.norm_f.weight"], cfg.rms_eps)
    logits = _linear(x, params["lm_head.weight"])
    return ctx.constrain(logits, "dp", "sp", None), new_cache
