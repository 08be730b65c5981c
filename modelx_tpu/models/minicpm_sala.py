"""MiniCPM-SALA decoder: linear-attention layers beside block-sparse ones.

Each layer is one of two mixers (``mixer_types``): ``lightning-attn`` — linear
attention with a per-head decay, whose whole past is one ``[heads, d, d]``
float32 STATE a row (ops/linear_attention.py) — or ``minicpm4`` — grouped-query
attention without rope that, from ``dense_len`` positions of context on,
attends only the ``topk`` key blocks a pass over compressed keys selects
(ops/sparse_attention.py). Per-head RMS norms on q and k, a full-width sigmoid
gate on each mixer's output, muP scales on the embedding, the residual and the
logits. The equations, and every assumption behind them, are written out in
the plain float32 reference, ``models/minicpm_sala_reference.py``.

Params are a flat dict keyed by the checkpoint's names:

    model.layers.N.self_attn.{q,k,v,o}_proj.weight, .o_gate.weight  [D, D]
    model.layers.N.self_attn.{q,k}_norm.weight                      [d]
    model.layers.N.self_attn.norm.weight       [D]   lightning layers only
    model.layers.N.mlp.{gate,up,down}_proj.weight
    model.layers.N.{input,post_attention}_layernorm.weight

**The config comes from ``config.json``, never from tensor shapes**
(:func:`config_from_hf`): the keys read are ``mixer_types``, ``sparse_config``
(the MiniCPM4 family's values where absent), the muP scales ``scale_emb``,
``scale_depth``, ``dim_model_base``, and **the share key** ``"layer_share":
{"published": 32, "first": 9}`` — a checkpoint may hold a run of the published
layers that is not a prefix of them: ``num_hidden_layers`` then counts the
layers held, they keep their published names (``model.layers.9`` ..), and the
residual scale ``scale_depth / sqrt(published)`` uses the published depth.

Three forms of each layer over one set of equations:

- cache-less (``/v1/forward``): the lightning layers chunk by chunk from a zero
  state, the sparse layers query tile by query tile over the sequence's own
  keys;
- a block of positions over a cache (an admission's scratch, a prefill piece
  over the slot's row): reads the row's state and earlier keys, writes both
  back, with the compressed keys of the windows the block completes;
  ``valid_len`` says how many of the block's positions are real (a padded
  bucket's tail must not enter a state);
- one token a row over the engine's state: a lightning step reads and writes
  the row's state, a sparse step appends its key and value, every
  ``kernel_stride``-th position a compressed key, and below ``dense_len``
  attends its row densely, from there on reads the selected blocks alone — on
  one TPU device in a kernel that copies each block's own lanes once, elsewhere
  by a gather (``sparse_ops.decode_takes_kernel``). ``live`` marks the rows
  that decode: the others keep their state bit for bit.

The cache (``init_kv_cache``; the engine's, ``init_layer_state``, adds the
counters): per sparse layer ``k<i>``, ``v<i>`` ``[B, L, Hkv * d]`` (a position's
KV heads side by side, so that a block of positions is one contiguous run:
ops/sparse_attention.py) and the index ``c<i>`` ``[B, L / stride, Hkv, d]``; per lightning layer ``s<i>`` ``[B, H, d,
d]`` float32 — no position axis, which is why this family serves long contexts
at all, and why the continuous engine refuses for it what cuts a row at a
token: ``--prefix-cache``, ``--speculative-k``, ``--kv-page-size``
(dl/kv_layout.LayerKindKV). ``--prefill-chunk`` is carried.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from modelx_tpu.models.llama import ShardingCtx, _rms_norm, _rope
from modelx_tpu.ops import attention as attn_ops
from modelx_tpu.ops import linear_attention as linear_ops
from modelx_tpu.ops import sparse_attention as sparse_ops
from modelx_tpu.ops.nn import linear as _linear
from modelx_tpu.ops.sparse_attention import SparseSpec

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# the engine's counters of the sparse layers, in the order the decode step
# accumulates them, over the sparse layers and the live rows
# (dl/kv_layout.LayerKindKV reads them back with the tokens): positions whose
# keys were read, positions the rows hold, row-steps that took the selection,
# row-steps in all, row-steps whose selected blocks the kernel read
# (ops/sparse_attention.decode_attention_kernel; 0 where the gather ran)
SPARSE_COUNTERS = ("positions_read", "positions_cached", "steps_sparse", "steps_all",
                   "steps_kernel")


@dataclasses.dataclass(frozen=True)
class SalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    mixer_types: tuple[str, ...] = (SPARSE,) + (LIGHTNING,) * 3
    num_heads: int = 32  # the sparse layers'
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    qk_norm: bool = True
    attn_use_rope: bool = False
    lightning_use_rope: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    attn_use_output_gate: bool = True
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    published_layers: int = 32
    first_layer: int = 0
    sparse: SparseSpec = SparseSpec()
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    def prefix(self, layer: int) -> str:
        return f"model.layers.{self.first_layer + layer}."

    @classmethod
    def tiny(cls, vocab_size: int = 256, **over) -> "SalaConfig":
        """Test config: every mechanism at toy sizes — three layers of a
        "published" eight (sparse, lightning, lightning), 2 KV heads under 4
        query heads, blocks of 8 positions, top-3 with one initial block and a
        window of two, selection from 32 positions of context on."""
        base = dict(
            vocab_size=vocab_size, hidden_size=32, intermediate_size=64,
            mixer_types=(SPARSE, LIGHTNING, LIGHTNING), num_heads=4, num_kv_heads=2,
            head_dim=8, lightning_heads=4, lightning_head_dim=8, dim_model_base=8,
            published_layers=8, first_layer=2,
            sparse=SparseSpec(kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8,
                              window_size=16, topk=3, dense_len=32),
            dtype=jnp.float32)
        base.update(over)
        return cls(**base)


def config_from_hf(raw: Mapping, dtype=jnp.bfloat16) -> SalaConfig:
    """The config of a checkpoint from its ``config.json``. Raises for what
    this family does not implement rather than serving something else."""
    n = int(raw["num_hidden_layers"])
    mixers = tuple(raw["mixer_types"])
    if len(mixers) != n:
        raise ValueError(f"minicpm_sala: mixer_types lists {len(mixers)} layers, "
                         f"num_hidden_layers is {n}")
    bad = set(mixers) - {LIGHTNING, SPARSE}
    if bad:
        raise ValueError(f"minicpm_sala: unknown mixer type(s) {sorted(bad)}")
    share = raw.get("layer_share") or {}
    published, first = int(share.get("published", n)), int(share.get("first", 0))
    if first < 0 or first + n > published:
        raise ValueError(f"minicpm_sala: layer_share holds {first}..{first + n} "
                         f"of {published} published layers")
    if int(raw.get("lightning_nkv", raw["lightning_nh"])) != int(raw["lightning_nh"]):
        raise ValueError("minicpm_sala: lightning_nkv other than lightning_nh is not implemented")
    if raw.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
        raise ValueError(f"minicpm_sala: lightning_scale {raw['lightning_scale']!r} is not "
                         "implemented (1/sqrt(d))")
    if raw.get("attention_bias"):
        raise ValueError("minicpm_sala: attention biases are not implemented")
    if raw.get("tie_word_embeddings"):
        raise ValueError("minicpm_sala: a tied output head is not implemented")
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError(f"minicpm_sala: hidden_act {raw['hidden_act']!r} is not implemented")
    heads = int(raw["num_attention_heads"])
    return SalaConfig(
        vocab_size=int(raw["vocab_size"]), hidden_size=int(raw["hidden_size"]),
        intermediate_size=int(raw["intermediate_size"]), mixer_types=mixers,
        num_heads=heads, num_kv_heads=int(raw["num_key_value_heads"]),
        head_dim=int(raw.get("head_dim") or raw["hidden_size"] // heads),
        lightning_heads=int(raw["lightning_nh"]),
        lightning_head_dim=int(raw["lightning_head_dim"]),
        rope_theta=float(raw.get("rope_theta", 10000.0)), qk_norm=bool(raw.get("qk_norm", True)),
        attn_use_rope=bool(raw.get("attn_use_rope", False)),
        lightning_use_rope=bool(raw.get("lightning_use_rope", True)),
        use_output_gate=bool(raw.get("use_output_gate", True)),
        use_output_norm=bool(raw.get("use_output_norm", True)),
        attn_use_output_gate=bool(raw.get("attn_use_output_gate", True)),
        scale_emb=float(raw.get("scale_emb", 1.0)), scale_depth=float(raw.get("scale_depth", 1.0)),
        dim_model_base=int(raw.get("dim_model_base", raw["hidden_size"])),
        published_layers=published, first_layer=first,
        sparse=SparseSpec(**(raw.get("sparse_config") or {})),
        rms_eps=float(raw.get("rms_norm_eps", 1e-6)), dtype=dtype)


def to_hf_config(cfg: SalaConfig) -> dict:
    """The ``config.json`` that :func:`config_from_hf` reads back as ``cfg``
    (test checkpoints, and the reference, which reads the architecture from
    this and not from ``cfg``)."""
    return {
        "model_type": "minicpm_sala", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers, "mixer_types": list(cfg.mixer_types),
        "layer_share": {"published": cfg.published_layers, "first": cfg.first_layer},
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim, "lightning_nh": cfg.lightning_heads,
        "lightning_nkv": cfg.lightning_heads, "lightning_head_dim": cfg.lightning_head_dim,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": cfg.lightning_use_rope,
        "attn_use_rope": cfg.attn_use_rope, "qk_norm": cfg.qk_norm,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps, "hidden_act": "silu",
        "scale_emb": cfg.scale_emb, "scale_depth": cfg.scale_depth,
        "dim_model_base": cfg.dim_model_base, "use_output_gate": cfg.use_output_gate,
        "use_output_norm": cfg.use_output_norm, "attn_use_output_gate": cfg.attn_use_output_gate,
        "attention_bias": False, "tie_word_embeddings": False,
        "sparse_config": dataclasses.asdict(cfg.sparse),
    }


# -- params -------------------------------------------------------------------


def param_shapes(cfg: SalaConfig) -> dict[str, tuple[int, ...]]:
    """Linear weights [out, in]."""
    e, f = cfg.hidden_size, cfg.intermediate_size
    shapes: dict[str, tuple[int, ...]] = {
        "model.embed_tokens.weight": (cfg.vocab_size, e),
        "model.norm.weight": (e,),
        "lm_head.weight": (cfg.vocab_size, e),
    }
    for i, mixer in enumerate(cfg.mixer_types):
        p = cfg.prefix(i)
        if mixer == LIGHTNING:
            q = kv = cfg.lightning_heads * cfg.lightning_head_dim
            hd = cfg.lightning_head_dim
            if cfg.use_output_norm:
                shapes[p + "self_attn.norm.weight"] = (q,)
            gated = cfg.use_output_gate
        else:
            q, kv, hd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim, cfg.head_dim
            gated = cfg.attn_use_output_gate
        shapes.update({
            p + "self_attn.q_proj.weight": (q, e), p + "self_attn.k_proj.weight": (kv, e),
            p + "self_attn.v_proj.weight": (kv, e), p + "self_attn.o_proj.weight": (e, q),
            p + "input_layernorm.weight": (e,), p + "post_attention_layernorm.weight": (e,),
            p + "mlp.gate_proj.weight": (f, e), p + "mlp.up_proj.weight": (f, e),
            p + "mlp.down_proj.weight": (e, f),
        })
        if gated:
            shapes[p + "self_attn.o_gate.weight"] = (q, e)
        if cfg.qk_norm:
            shapes[p + "self_attn.q_norm.weight"] = (hd,)
            shapes[p + "self_attn.k_norm.weight"] = (hd,)
    return shapes


def init_params(cfg: SalaConfig, key: jax.Array, dtype=None) -> dict[str, jax.Array]:
    dtype = dtype or cfg.dtype
    shapes = param_shapes(cfg)
    params: dict[str, jax.Array] = {}
    for (name, shape), k in zip(sorted(shapes.items()), jax.random.split(key, len(shapes))):
        if name.endswith(("layernorm.weight", "model.norm.weight")):
            params[name] = jnp.ones(shape, dtype)
        elif name.endswith("norm.weight"):  # the learned q/k/output norms: not all ones
            params[name] = (1.0 + 0.1 * jax.random.normal(k, shape)).astype(dtype)
        else:
            params[name] = (jax.random.normal(k, shape) / math.sqrt(shape[-1])).astype(dtype)
    return params


# -- kv state -----------------------------------------------------------------


def published(cfg: SalaConfig) -> dict:
    """What a pod's /metrics names of this family: the counter leaf the decode
    step accumulates (leaf -> (stats block, its entries' names)) — of its
    sparse layers, over the LIVE rows — and the gauges beside it."""
    sparse_layers = cfg.mixer_types.count(SPARSE)
    return {
        "counters": {"sparse_counts": ("sparse", SPARSE_COUNTERS)},
        "gauges": {"sparse": {"sparse_layers": sparse_layers,
                              "linear_layers": cfg.num_layers - sparse_layers,
                              "block_size": cfg.sparse.block_size, "topk": cfg.sparse.topk,
                              "dense_len": cfg.sparse.dense_len}},
    }


def cache_kinds(cfg: SalaConfig) -> dict[str, str]:
    """Leaf name -> its kind in the engine's state (dl/kv_layout.LayerKindKV):
    ``"full"`` keys and values, the ``"index"`` of compressed keys, a
    lightning layer's ``"state"``, the ``"counter"`` vector."""
    kinds: dict[str, str] = {}
    for i, mixer in enumerate(cfg.mixer_types):
        if mixer == LIGHTNING:
            kinds[f"s{i}"] = "state"
        else:
            kinds.update({f"k{i}": "full", f"v{i}": "full", f"c{i}": "index"})
    kinds["sparse_counts"] = "counter"
    return kinds


def init_kv_cache(cfg: SalaConfig, batch: int, max_len: int, dtype=None) -> dict:
    """The cache of ``batch`` rows of ``max_len`` positions (a multiple of the
    16-token bucket): keys, values and index of the sparse layers, the
    lightning layers' states."""
    dtype = dtype or cfg.dtype
    stride = cfg.sparse.kernel_stride
    if max_len % stride:
        raise ValueError(f"minicpm_sala: a cache of {max_len} positions is not a multiple of "
                         f"the compressed keys' stride {stride}")
    cache = {}
    for name, kind in cache_kinds(cfg).items():
        if kind == "state":
            cache[name] = jnp.zeros((batch, cfg.lightning_heads, cfg.lightning_head_dim,
                                     cfg.lightning_head_dim), jnp.float32)
        elif kind == "index":
            cache[name] = jnp.zeros((batch, max_len // stride, cfg.num_kv_heads, cfg.head_dim),
                                    dtype)
        elif kind == "full":
            cache[name] = jnp.zeros((batch, max_len, cfg.num_kv_heads * cfg.head_dim), dtype)
    return cache


def init_layer_state(cfg: SalaConfig, slots: int, max_len: int, dtype=None) -> dict:
    """The engine's state: :func:`init_kv_cache` over the slots, and the
    sparse layers' counters (:data:`SPARSE_COUNTERS`, wrapping int32)."""
    if max_len % cfg.sparse.block_size:
        raise ValueError(f"minicpm_sala: --max-seq-len {max_len} must be a multiple of the "
                         f"sparse block ({cfg.sparse.block_size} positions)")
    state = init_kv_cache(cfg, slots, max_len, dtype)
    state["sparse_counts"] = jnp.zeros((len(SPARSE_COUNTERS),), jnp.int32)
    return state


# -- forward ------------------------------------------------------------------


def _write_rows(cache, new, index, by_row: bool = False):
    """Write ``new`` [B, S, ...] into ``cache`` [B, L, ...] at ``index`` (a
    scalar, or one start per row). ``by_row``: the rows one after another,
    each an update in place — as one scatter the compiler wants a ``[slots,
    max_len]`` leaf in another layout and copies it whole, twice a step (my
    chip run, PR 35); written as a loop it ends the TPU compiler with an
    internal error, so the updates are written out."""
    rest = (0,) * (cache.ndim - 2)
    if jnp.ndim(index) == 0:
        return jax.lax.dynamic_update_slice(cache, new, (0, index) + rest)
    if not by_row:
        return jax.vmap(lambda c, u, o: jax.lax.dynamic_update_slice(c, u, (o,) + rest))(
            cache, new, index)
    for i in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(cache, new[i: i + 1], (i, index[i]) + rest)
    return cache


def _flat(x):
    """[B, S, Hkv, d] -> [B, S, Hkv * d]: a position's KV heads side by side,
    as the cache keeps them."""
    return x.reshape(*x.shape[:2], -1)


def _qkv(params, p: str, u, heads: int, kv_heads: int, hd: int, cfg: SalaConfig, positions,
         use_rope: bool, ctx: ShardingCtx):
    b, s = u.shape[:2]
    q = _linear(u, params[p + "self_attn.q_proj.weight"]).reshape(b, s, heads, hd)
    k = _linear(u, params[p + "self_attn.k_proj.weight"]).reshape(b, s, kv_heads, hd)
    v = _linear(u, params[p + "self_attn.v_proj.weight"]).reshape(b, s, kv_heads, hd)
    if cfg.qk_norm:
        q = _rms_norm(q, params[p + "self_attn.q_norm.weight"], cfg.rms_eps)
        k = _rms_norm(k, params[p + "self_attn.k_norm.weight"], cfg.rms_eps)
    if use_rope:
        q, k = _rope(q, positions, cfg.rope_theta), _rope(k, positions, cfg.rope_theta)
    return (ctx.constrain(q, "dp", "sp", "tp", None), ctx.constrain(k, "dp", "sp", "tp", None),
            ctx.constrain(v, "dp", "sp", "tp", None))


def _lightning(params, p: str, u, positions, cfg: SalaConfig, ctx: ShardingCtx, state,
               offset, valid_len, live):
    """u [B, S, D] (normed) -> (y [B, S, D], the new state or None)."""
    b, s = u.shape[:2]
    heads, hd = cfg.lightning_heads, cfg.lightning_head_dim
    q, k, v = _qkv(params, p, u, heads, heads, hd, cfg, positions, cfg.lightning_use_rope, ctx)
    slopes, scale = linear_ops.decay_slopes(heads), 1.0 / math.sqrt(hd)
    if state is not None and s == 1:
        with jax.named_scope("sala.linear.step"):
            o, new = linear_ops.step(q[:, 0], k[:, 0], v[:, 0], slopes, state, live=live,
                                     scale=scale)
            o = o[:, None]
    else:
        with jax.named_scope("sala.linear.prefill"):
            if state is None:
                start = jnp.zeros((b, heads, hd, hd), jnp.float32)
            else:  # a block at position 0 starts a row: what the slot held before is not its past
                start = jnp.where(jnp.asarray(offset) == 0, 0.0, state)
            o, new = linear_ops.chunked(q, k, v, slopes, start, valid_len=valid_len, scale=scale)
    o = o.reshape(b, s, heads * hd)
    if cfg.use_output_norm:
        o = _rms_norm(o, params[p + "self_attn.norm.weight"].astype(jnp.float32), cfg.rms_eps)
    if cfg.use_output_gate:
        o = o * jax.nn.sigmoid(_linear(u, params[p + "self_attn.o_gate.weight"])
                               .astype(jnp.float32))
    y = _linear(o.astype(u.dtype), params[p + "self_attn.o_proj.weight"])
    return y, (new if state is not None else None)


def _sparse_step(q, k, v, cache, offset, live, cfg: SalaConfig, ctx: ShardingCtx,
                 attention_impl: str):
    """One token a row over the engine's rows. q [B,1,H,d], k/v [B,1,Hkv,d],
    cache (k, v [B,L,Hkv*d], index), offset [B] -> (o [B,1,H,d] float32, the
    new cache, the step's counts)."""
    spec = cfg.sparse
    ck, cv, index = cache
    b = q.shape[0]
    s = spec.kernel_stride
    ck, cv = (_write_rows(c, _flat(x), offset, by_row=True) for c, x in ((ck, k), (cv, v)))
    context = offset + 1
    with jax.named_scope("sala.sparse.compress"):
        # the window ending at this position, where it ends one: entry j of the index
        due = (context % s == 0) & (context >= 2 * s)
        if live is not None:
            due = due & live
        j = jnp.maximum(context // s - 1, 0)
        # where it is due the window is two whole stride-long runs of the row: taken
        # as such (a slice at any position makes the compiler re-lay the whole leaf)
        runs = ck.shape[1] // s
        at = jnp.arange(b)[:, None] * runs + jnp.maximum(context // s - 2, 0)[:, None]
        window = jnp.take(ck.reshape(b * runs, s, -1), at + jnp.arange(2), axis=0, mode="clip")
        entry = jnp.mean(window.astype(jnp.float32), axis=(1, 2)).astype(index.dtype)
        entry = entry.reshape(b, 1, *index.shape[2:])
        old = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, 1, axis=0))(index, j)
        index = _write_rows(index, jnp.where(due[:, None, None, None], entry, old), j)
    decoding = jnp.ones((b,), bool) if live is None else live
    dense_row = context < spec.dense_len
    dense_len = min(spec.dense_len, ck.shape[1])

    def dense(_):
        with jax.named_scope("sala.attn.dense"):
            # the rows' fronts, taken as whole blocks for the same reason
            size = spec.block_size
            at = (jnp.arange(b)[:, None] * (ck.shape[1] // size)
                  + jnp.arange(-(-dense_len // size))).reshape(-1)

            def front(c):
                got = jnp.take(c.reshape(-1, size, c.shape[-1]), at, axis=0, mode="clip")
                return got.reshape(b, -1, cfg.num_kv_heads, cfg.head_dim)[:, :dense_len]

            return attn_ops.cached_attention(q, front(ck), front(cv), offset, impl=attention_impl,
                                             mesh=ctx.mesh).astype(jnp.float32)

    # the dense branch reads dense_len positions of EVERY row: it runs only in
    # a step in which some live row's context is still below dense_len
    o_dense = jax.lax.cond(jnp.any(dense_row & decoding), dense,
                           lambda _: jnp.zeros(q.shape, jnp.float32), None)
    blocks = ck.shape[1] // spec.block_size
    with jax.named_scope("sala.sparse.select"):
        chosen = sparse_ops.select_blocks(q, index, context[:, None], spec, blocks)[:, 0]
    # one algorithm, two ways to fetch its operands: which is read off the inputs
    kernel, interpret = sparse_ops.decode_takes_kernel(q, ck, spec, attention_impl, ctx.mesh)
    with jax.named_scope("sala.sparse.attend"):
        if kernel:
            o_sparse = sparse_ops.decode_attention_kernel(q[:, 0], ck, cv, chosen, offset, spec,
                                                          interpret=interpret)
        else:
            o_sparse = sparse_ops.decode_attention(q[:, 0], ck, cv, chosen, offset, spec)
        o_sparse = o_sparse[:, None]
    o = jnp.where(dense_row[:, None, None, None], o_dense, o_sparse)
    took = decoding & ~dense_row
    read = jnp.where(took, chosen.shape[-1] * spec.block_size, jnp.where(decoding, context, 0))
    steps_sparse = jnp.sum(took)
    counts = jnp.stack([jnp.sum(read), jnp.sum(jnp.where(decoding, context, 0)), steps_sparse,
                        jnp.sum(decoding), steps_sparse if kernel else 0]).astype(jnp.int32)
    return o, (ck, cv, index), counts


def _sparse_block(q, k, v, cache, offset, cfg: SalaConfig):
    """A block of positions at a scalar ``offset``: over the cache's row once
    the block's keys, values and compressed keys are written, or — no cache —
    over the block itself. -> (o [B,S,H,d] float32, the new cache or None)."""
    spec = cfg.sparse
    s = spec.kernel_stride
    if cache is None:
        pad = -k.shape[1] % s
        ck, cv = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (k, v))
        with jax.named_scope("sala.sparse.compress"):
            index = sparse_ops.compress(jnp.pad(ck, ((0, 0), (s, 0), (0, 0), (0, 0))), spec)
        new = None
    else:
        ck, cv, index = cache
        ck, cv = _write_rows(ck, _flat(k), offset), _write_rows(cv, _flat(v), offset)
        with jax.named_scope("sala.sparse.compress"):
            # (a block that ends inside a window leaves that entry incomplete: no
            # context sees it before the decode step that completes it rewrites it)
            before = jax.lax.dynamic_slice_in_dim(ck, jnp.maximum(offset - s, 0), s, axis=1)
            before = before.reshape(k.shape[0], s, *k.shape[2:])
            run = jnp.pad(k, ((0, 0), (0, -k.shape[1] % s), (0, 0), (0, 0)))
            entries = sparse_ops.compress(jnp.concatenate([before, run], axis=1), spec)
            index = _write_rows(index, entries, offset // s)
        new = (ck, cv, index)
    with jax.named_scope("sala.sparse.attend"):
        o = sparse_ops.prefill_attention(q, ck, cv, index, offset, spec)
    return o, new


def decoder_layer(params, x, positions, cfg: SalaConfig, layer: int, ctx: ShardingCtx,
                  cache=None, cache_offset=0, valid_len=None, live=None,
                  attention_impl: str = "auto"):
    """One block. ``cache``: None, or the layer's leaves — ``(state,)`` of a
    lightning layer, ``(k, v, index)`` of a sparse one. Returns (x, the
    updated leaves or None, a sparse step's counts or None)."""
    p = cfg.prefix(layer)
    b, s = x.shape[:2]
    u = _rms_norm(x, params[p + "input_layernorm.weight"], cfg.rms_eps)
    counts = None
    if cfg.mixer_types[layer] == LIGHTNING:
        y, state = _lightning(params, p, u, positions, cfg, ctx,
                              None if cache is None else cache[0], cache_offset, valid_len, live)
        new_cache = None if cache is None else (state,)
    else:
        q, k, v = _qkv(params, p, u, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg,
                       positions, cfg.attn_use_rope, ctx)
        if cache is not None and s == 1:
            offset = jnp.broadcast_to(jnp.asarray(cache_offset, jnp.int32), (b,))
            o, new_cache, counts = _sparse_step(q, k, v, cache, offset, live, cfg, ctx,
                                                attention_impl)
        else:
            o, new_cache = _sparse_block(q, k, v, cache, cache_offset if cache is not None else 0,
                                         cfg)
        o = o.reshape(b, s, cfg.num_heads * cfg.head_dim)
        if cfg.attn_use_output_gate:
            o = o * jax.nn.sigmoid(_linear(u, params[p + "self_attn.o_gate.weight"])
                                   .astype(jnp.float32))
        y = _linear(o.astype(x.dtype), params[p + "self_attn.o_proj.weight"])
    x = ctx.constrain(x + (cfg.residual_scale * y.astype(jnp.float32)).astype(x.dtype),
                      "dp", "sp", None)
    with jax.named_scope("sala.mlp"):
        m = _rms_norm(x, params[p + "post_attention_layernorm.weight"], cfg.rms_eps)
        ff = jax.nn.silu(_linear(m, params[p + "mlp.gate_proj.weight"])) * _linear(
            m, params[p + "mlp.up_proj.weight"])
        ff = ctx.constrain(ff, "dp", "sp", "tp")
        y = _linear(ff, params[p + "mlp.down_proj.weight"])
    x = x + (cfg.residual_scale * y.astype(jnp.float32)).astype(x.dtype)
    return ctx.constrain(x, "dp", "sp", None), new_cache, counts


_LEAVES = {LIGHTNING: ("s",), SPARSE: ("k", "v", "c")}


def forward(params, tokens, cfg: SalaConfig, positions=None, kv_cache: dict | None = None,
            cache_offset: int | jax.Array = 0, mesh: Mesh | None = None,
            attention_impl: str = "auto", valid_len=None, live=None):
    """Returns (logits [B,S,V], updated kv_cache). ``kv_cache`` None: one
    cache-less pass. Else (:func:`init_kv_cache` / :func:`init_layer_state`) a
    block of positions at a scalar ``cache_offset`` — ``valid_len`` [B] its
    real positions, all of them when None — or, one token a row, a decode step
    at per-row offsets, of which ``live`` [B] marks the rows that decode (all
    when None); a ``sparse_counts`` leaf grows by what the step's sparse
    layers counted."""
    ctx = ShardingCtx(mesh)
    b, s = tokens.shape
    if kv_cache is not None and s > 1 and jnp.ndim(cache_offset) != 0:
        raise ValueError("minicpm_sala: a block of positions lands at one offset for all rows")
    if positions is None:
        off = jnp.asarray(cache_offset if kv_cache is not None else 0)
        positions = jnp.arange(s)[None, :] + (off[:, None] if off.ndim else off)
        positions = jnp.broadcast_to(positions, (b, s))
    x = jnp.take(params["model.embed_tokens.weight"], tokens, axis=0).astype(jnp.float32)
    x = ctx.constrain((x * cfg.scale_emb).astype(cfg.dtype), "dp", "sp", None)
    new_cache: dict | None = {} if kv_cache is not None else None
    counted = jnp.zeros((len(SPARSE_COUNTERS),), jnp.int32)
    for i, mixer in enumerate(cfg.mixer_types):
        names = [f"{leaf}{i}" for leaf in _LEAVES[mixer]]
        cache = tuple(kv_cache[n] for n in names) if kv_cache is not None else None
        x, updated, counts = decoder_layer(
            params, x, positions, cfg, i, ctx, cache=cache, cache_offset=cache_offset,
            valid_len=valid_len, live=live, attention_impl=attention_impl)
        if updated is not None:
            new_cache.update(zip(names, updated))
        if counts is not None:
            counted = counted + counts
    if kv_cache is not None and "sparse_counts" in kv_cache:
        new_cache["sparse_counts"] = kv_cache["sparse_counts"] + counted
    x = _rms_norm(x, params["model.norm.weight"], cfg.rms_eps)
    x = (x.astype(jnp.float32) / (cfg.hidden_size / cfg.dim_model_base)).astype(x.dtype)
    logits = _linear(x, params["lm_head.weight"])
    return ctx.constrain(logits, "dp", "sp", None), new_cache
