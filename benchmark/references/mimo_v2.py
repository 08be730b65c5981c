"""Plain float32 reference of the MiMo-V2-Flash decoder: the layer equations
in straightforward ``jax.numpy``, one sequence at a time, with no cache, no
kernels and no batching. It depends on nothing but jax and numpy, reads the
architecture from the checkpoint's ``config.json`` itself, and takes the
weights under the checkpoint's own per-expert names. What the program
(``models/mimo_v2.py``) computes is held against this.

``N(x) = x / sqrt(mean(x^2) + eps) * w``, ``eps = layernorm_epsilon``. Layer
l, input ``x [T, D]``; the layer is a WINDOW layer where
``hybrid_layer_pattern[l] == 1`` and a FULL layer where 0:

- ``u = N_in(x)``; ``q = u Wq^T -> [T, H, d]``, ``k = u Wk^T -> [T, Hkv, d]``,
  ``v = attention_value_scale * (u Wv^T) -> [T, Hkv, dv]``. ``H, Hkv, d, dv``
  are ``num_attention_heads, num_key_value_heads, head_dim, v_head_dim`` on a
  full layer and the ``swa_`` keys on a window layer (64, 4 | 8, 192, 128). No
  biases.
- Rope on q and k, rotate-half, on the first ``int(d * partial_rotary_factor)``
  lanes of each head (64 of 192), the rest passed through; base ``rope_theta``
  on full layers, ``swa_rope_theta`` on window layers.
- Scores ``s_tj = q_h(t) . k_{h // (H/Hkv)}(j) / sqrt(d)`` over ``j <= t`` and,
  on window layers, ``t - j < sliding_window``. Full layer: ``a = softmax(s)``.
  Window layer with ``add_swa_attention_sink_bias``, sink ``b_h`` a query
  head: ``a_tj = exp(s_tj) / (exp(b_h) + sum_i exp(s_ti))`` — the sink takes
  mass and gives no value. ``h = x + concat_h(a v) Wo^T``.
- ``m = N_post(h)``. ``moe_layer_freq[l] == 0``: ``y = h + (silu(m Wgate^T) *
  m Wup^T) Wdown^T`` of width ``intermediate_size``. Else ``g = sigmoid(m
  Wr^T)`` over the router's published width, ``S`` = the ``num_experts_per_tok``
  largest of ``g + e_score_correction_bias``, ``w_e = g_e / sum_S g``
  (``norm_topk_prob``) times ``routed_scaling_factor`` (null: 1), ``y = h +
  sum_{e in S and held} w_e E_e(m)``, each expert a SwiGLU of
  ``moe_intermediate_size``; no shared expert.
- After the last layer ``N_final`` and the untied head over the vocabulary held.

**The share.** ``n_routed_experts`` counts the experts the checkpoint holds and
``expert_share = {"published": P, "first": f}`` says they are experts ``f .. f
+ n_routed_experts`` of ``P``: routing runs over all ``P``, only the held
experts' terms are summed, and the vocabulary is the rows the checkpoint
holds. Without the key the checkpoint is whole.

**Assumed, because the published config does not say — each a possible
departure from the released model:** (a) ``attention_value_scale`` multiplies
``v`` as it is projected, before any cache (after the softmax it would be the
same number); (b) rope is rotate-half on the FIRST ``int(d * 0.334) = 64``
lanes; (c) the sink joins the softmax as one more logit, unscaled and never
masked, and contributes no value; (d) the window counts the query's own
position: ``0 <= t - j < 128``; (e) no q/k norm (the config has no key for
one); (f) ``attention_chunk_size`` 128 is a kernel's block, not chunked
attention; (g) tensor names follow the DeepSeek-V3 / HF lineage
(``self_attn.{q,k,v,o}_proj``, ``self_attn.attention_sink_bias [H]``,
``mlp.gate.weight``, ``mlp.gate.e_score_correction_bias``,
``mlp.experts.<i>.{gate,up,down}_proj``, ``mlp.{gate,up,down}_proj`` on dense
layers); (h) the multi-token-prediction layers the model card speaks of are
left out (the config gives them no shape); (i) bfloat16 where the published
checkpoint may be FP8.

``cast`` (a function of one array) is applied to every weight as it is used
and ``cast_activations`` to every layer's output: the identity by default; a
comparison's control passes a rounding to show that its tolerances would
catch a lower precision. ``drop_sinks`` and ``value_scale`` plant a fault for
the same purpose. ``head_block`` bounds the ``[heads, T, T]`` scores held at a
time; ``dense_experts`` computes every held expert on every token and weighs
it (the same sum; no shape depends on the routing, so the pass can be traced
once on an accelerator).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _same(x):
    return x


class Weights:
    """Weights by name, float32 at the moment of use."""

    def __init__(self, params, cast=_same) -> None:
        self.params, self.cast = params, cast

    def __call__(self, name: str):
        return self.cast(jnp.asarray(np.asarray(self.params[name]).astype(np.float32)))


def rms_norm(x, w, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_shape(raw: dict, layer: int) -> dict:
    """One layer's kind and sizes, from the config's own keys."""
    window = bool(raw["hybrid_layer_pattern"][layer])
    pre = "swa_" if window else ""
    return {
        "window": int(raw.get("sliding_window") or raw["sliding_window_size"]) if window else 0,
        "heads": int(raw[pre + "num_attention_heads"]),
        "kv_heads": int(raw[pre + "num_key_value_heads"]),
        "d": int(raw[pre + "head_dim"]), "dv": int(raw[pre + "v_head_dim"]),
        "theta": float(raw["swa_rope_theta" if window else "rope_theta"]),
        "sinks": bool(raw.get("add_swa_attention_sink_bias" if window
                              else "add_full_attention_sink_bias", False)),
    }


def rope(x, theta: float, dim: int):
    """x: [T, H, d], positions 0..T-1; rotate-half on the first ``dim`` lanes."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[:, None, :]
    xr, rest = x[..., :dim], x[..., dim:]
    half = jnp.concatenate([-xr[..., dim // 2:], xr[..., : dim // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


def attention(w: Weights, p: str, raw: dict, layer: int, x, head_block: int = 0,
              drop_sinks: bool = False, value_scale: float | None = None):
    t = x.shape[0]
    shape = layer_shape(raw, layer)
    heads, hkv, d, dv = shape["heads"], shape["kv_heads"], shape["d"], shape["dv"]
    u = rms_norm(x, w(p + "input_layernorm.weight"), raw["layernorm_epsilon"])
    q = (u @ w(p + "self_attn.q_proj.weight").T).reshape(t, heads, d)
    k = (u @ w(p + "self_attn.k_proj.weight").T).reshape(t, hkv, d)
    scale = float(raw.get("attention_value_scale") or 1.0) if value_scale is None else value_scale
    v = scale * (u @ w(p + "self_attn.v_proj.weight").T).reshape(t, hkv, dv)
    rotated = int(d * float(raw.get("partial_rotary_factor", 1.0)))
    q, k = rope(q, shape["theta"], rotated), rope(k, shape["theta"], rotated)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    visible = j <= i
    if shape["window"]:
        visible = visible & (i - j < shape["window"])
    sinks = None
    if shape["sinks"] and not drop_sinks:
        sinks = w(p + "self_attn.attention_sink_bias")  # [H]
    group, step, out = heads // hkv, head_block or heads, []
    for h0 in range(0, heads, step):
        hs = np.arange(h0, min(h0 + step, heads))
        kh, vh = k[:, hs // group], v[:, hs // group]  # head h reads KV head h // group
        scores = jnp.einsum("thd,jhd->htj", q[:, hs], kh) / math.sqrt(d)
        scores = jnp.where(visible[None], scores, -jnp.inf)
        if sinks is None:
            probs = jax.nn.softmax(scores, axis=-1)
        else:  # the sink: one more logit in the denominator, no value behind it
            sink = jnp.broadcast_to(sinks[hs][:, None, None], (len(hs), t, 1))
            probs = jax.nn.softmax(jnp.concatenate([scores, sink], -1), axis=-1)[..., :-1]
        out.append(jnp.einsum("htj,jhd->thd", probs, vh))
    a = jnp.concatenate(out, axis=1)
    return x + a.reshape(t, heads * dv) @ w(p + "self_attn.o_proj.weight").T


def swiglu(w: Weights, p: str, m):
    return (jax.nn.silu(m @ w(p + "gate_proj.weight").T) * (m @ w(p + "up_proj.weight").T)
            ) @ w(p + "down_proj.weight").T


def held_experts(raw: dict) -> tuple[int, int, int]:
    """(first held, how many held, published)."""
    held = int(raw["n_routed_experts"])
    share = raw.get("expert_share") or {}
    return int(share.get("first", 0)), held, int(share.get("published", held))


def routing(w: Weights, p: str, raw: dict, m):
    """Combine weights [T, published]: zero off the chosen k. ``noaux_tc``
    with one group: the bias chooses, the unbiased scores weigh."""
    scores = jax.nn.sigmoid(m @ w(p + "mlp.gate.weight").T)
    _, idx = jax.lax.top_k(scores + w(p + "mlp.gate.e_score_correction_bias"),
                           int(raw["num_experts_per_tok"]))
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if raw.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    if raw.get("routed_scaling_factor") is not None:
        vals = vals * float(raw["routed_scaling_factor"])
    return jnp.zeros_like(scores).at[jnp.arange(m.shape[0])[:, None], idx].set(vals)


def routed_experts(w: Weights, p: str, raw: dict, m, dense_experts: bool = False):
    """The held experts' part of the routed sum, expert by expert, each on
    the tokens that chose it (``dense_experts``: on every token, weighed)."""
    first, held, _ = held_experts(raw)
    combine = routing(w, p, raw, m)
    out = jnp.zeros_like(m)
    if dense_experts:
        for e in range(first, first + held):
            out = out + swiglu(w, f"{p}mlp.experts.{e}.", m) * combine[:, e][:, None]
        return out
    combine = np.asarray(combine)
    for e in range(first, first + held):
        rows = np.nonzero(combine[:, e])[0]
        if rows.size:
            y = swiglu(w, f"{p}mlp.experts.{e}.", m[rows])
            out = out.at[rows].add(y * jnp.asarray(combine[rows, e])[:, None])
    return out


def mlp(w: Weights, p: str, raw: dict, layer: int, h, dense_experts: bool = False):
    m = rms_norm(h, w(p + "post_attention_layernorm.weight"), raw["layernorm_epsilon"])
    if not raw["moe_layer_freq"][layer]:
        return h + swiglu(w, p + "mlp.", m)
    return h + routed_experts(w, p, raw, m, dense_experts)


def forward(params, raw: dict, tokens, cast=_same, cast_activations=_same,
            positions=None, head_block: int = 0, dense_experts: bool = False,
            drop_sinks: bool = False, value_scale: float | None = None):
    """Logits [T, vocabulary held] in float32 of one sequence ``tokens``
    [T]; ``positions`` keeps only those rows of the last norm and the head."""
    w = Weights(params, cast)
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[jnp.asarray(tokens)]
        for i in range(int(raw["num_hidden_layers"])):
            p = f"model.layers.{i}."
            x = attention(w, p, raw, i, x, head_block, drop_sinks, value_scale)
            x = cast_activations(mlp(w, p, raw, i, x, dense_experts))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, w("model.norm.weight"), raw["layernorm_epsilon"])
        return (x @ w("lm_head.weight").T).astype(F32)
