"""Plain float32 reference of the Laguna decoder: the layer equations in
straightforward ``jax.numpy``, one sequence at a time, with no cache, no
kernels and no batching. It depends on nothing but jax and numpy, reads the
architecture from the checkpoint's ``config.json`` itself, and takes the
weights under the checkpoint's own per-expert names. What the program
(``models/laguna.py``) computes is held against this.

``N(x) = x / sqrt(mean(x^2) + eps) * w``. Layer l, input ``x [T, D]``,
``H = num_attention_heads_per_layer[l]``, ``Hkv`` KV heads of size ``d``:

- ``u = N_in(x)``; ``q = u Wq^T -> [T,H,d]``, ``k, v -> [T,Hkv,d]``,
  ``g = sigmoid(u Wg^T) -> [T,H]``. No biases.
- Rope on q and k, rotate-half, on the first ``r d`` dimensions of each head,
  the rest passed through. ``full_attention``: YaRN inverse frequencies as HF
  ``_compute_yarn_parameters``, cos and sin times ``attention_factor``.
  ``sliding_attention``: plain rope.
- Scores ``q_h . k_{h // (H/Hkv)} / sqrt(d)`` over ``j <= t`` and, on sliding
  layers, ``t - j < window``; softmax; ``o = concat_h(g_h a_h) Wo^T``;
  ``h = x + o``.
- ``m = N_post(h)``. Dense layers: ``y = h + (silu(m Wgate^T) * m Wup^T)
  Wdown^T``. Sparse layers: ``z = m Wr^T`` over the router's published width,
  ``p = softmax(z)``, ``S`` = the ``k`` largest, ``w_e = scale * p_e /
  sum_{S} p`` (``norm_topk_prob``), ``y = h + sum_{e in S and held} w_e
  E_e(m) + E_shared(m)``, each expert a SwiGLU.
- After the last layer ``N_final`` and the untied head over the vocabulary
  held.

**The share.** ``num_experts`` counts the experts the checkpoint holds and
``expert_share = {"published": P, "first": f}`` says they are experts ``f ..
f + num_experts`` of ``P``: routing runs over all ``P``, only the held
experts' terms are summed, and the vocabulary is the rows the checkpoint
holds. Without the key the checkpoint is whole.

**Assumed, because the published config does not say — each a possible
departure from the released model:** (a) router scores are a softmax over all
router logits, no sigmoid and no bias correction; (b) ``gating: per-head`` is
a sigmoid of a linear map of the normed layer input, one scalar per query
head, on the head's attention output before ``Wo``; (c) no q/k norm; (d) the
shared expert is added ungated; (e) tensor names follow the Qwen-MoE lineage
(``self_attn.{q,k,v,o,g}_proj``, ``mlp.gate``, ``mlp.experts.<i>.{gate,up,
down}_proj``, ``mlp.shared_expert.*``, ``mlp.{gate,up,down}_proj`` on dense
layers).

``cast`` (a function of one array) is applied to every weight as it is used
and ``cast_activations`` to every layer's output: the identity by default;
the comparison's control passes a rounding to 8 bits to show that its
tolerances would catch a lower precision.
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


def inverse_frequencies(rope: dict, head_dim: int):
    """(inv_freq [rotated / 2], factor on cos and sin, rotated dims)."""
    dim = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    base = float(rope.get("rope_theta", 10000.0))
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return 1.0 / pos_freqs, 1.0, dim
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")
    factor, original = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(rope.get("beta_slow", 1)))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv, float(scale), dim


def rope(x, rope_cfg: dict):
    """x: [T, H, d], positions 0..T-1."""
    inv, scale, dim = inverse_frequencies(rope_cfg, x.shape[-1])
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[:, None, :] * scale
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[:, None, :] * scale
    xr, rest = x[..., :dim], x[..., dim:]
    half = jnp.concatenate([-xr[..., dim // 2:], xr[..., : dim // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


def attention(w: Weights, p: str, raw: dict, layer: int, x):
    t = x.shape[0]
    heads = raw["num_attention_heads_per_layer"][layer]
    hkv, d = raw["num_key_value_heads"], raw["head_dim"]
    kind = raw["layer_types"][layer]
    u = rms_norm(x, w(p + "input_layernorm.weight"), raw["rms_norm_eps"])
    q = (u @ w(p + "self_attn.q_proj.weight").T).reshape(t, heads, d)
    k = (u @ w(p + "self_attn.k_proj.weight").T).reshape(t, hkv, d)
    v = (u @ w(p + "self_attn.v_proj.weight").T).reshape(t, hkv, d)
    gate = jax.nn.sigmoid(u @ w(p + "self_attn.g_proj.weight").T)  # [T, H]
    q, k = rope(q, raw["rope_parameters"][kind]), rope(k, raw["rope_parameters"][kind])
    group = heads // hkv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)  # head h reads h // group
    scores = jnp.einsum("thd,jhd->htj", q, k) / math.sqrt(d)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    visible = j <= i
    if kind == "sliding_attention":
        visible = visible & (i - j < raw["sliding_window"])
    probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("htj,jhd->thd", probs, v) * gate[:, :, None]
    return x + a.reshape(t, heads * d) @ w(p + "self_attn.o_proj.weight").T


def swiglu(w: Weights, p: str, m):
    return (jax.nn.silu(m @ w(p + "gate_proj.weight").T) * (m @ w(p + "up_proj.weight").T)
            ) @ w(p + "down_proj.weight").T


def held_experts(raw: dict) -> tuple[int, int, int]:
    """(first held, how many held, published)."""
    held = int(raw["num_experts"])
    share = raw.get("expert_share") or {}
    return int(share.get("first", 0)), held, int(share.get("published", held))


def routing(w: Weights, p: str, raw: dict, m):
    """Combine weights [T, published]: zero off the chosen k."""
    probs = jax.nn.softmax(m @ w(p + "mlp.gate.weight").T, axis=-1)
    vals, idx = jax.lax.top_k(probs, int(raw["num_experts_per_tok"]))
    if raw.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    vals = vals * float(raw.get("moe_routed_scaling_factor", 1.0))
    return jnp.zeros_like(probs).at[jnp.arange(m.shape[0])[:, None], idx].set(vals)


def routed_experts(w: Weights, p: str, raw: dict, m):
    """The held experts' part of the routed sum, expert by expert, each on
    the tokens that chose it."""
    first, held, _ = held_experts(raw)
    combine = np.asarray(routing(w, p, raw, m))
    out = jnp.zeros_like(m)
    for e in range(first, first + held):
        rows = np.nonzero(combine[:, e])[0]
        if rows.size:
            y = swiglu(w, f"{p}mlp.experts.{e}.", m[rows])
            out = out.at[rows].add(y * jnp.asarray(combine[rows, e])[:, None])
    return out


def mlp(w: Weights, p: str, raw: dict, layer: int, h):
    m = rms_norm(h, w(p + "post_attention_layernorm.weight"), raw["rms_norm_eps"])
    if raw["mlp_layer_types"][layer] == "dense":
        return h + swiglu(w, p + "mlp.", m)
    y = routed_experts(w, p, raw, m)
    if raw.get("shared_expert_intermediate_size"):
        y = y + swiglu(w, p + "mlp.shared_expert.", m)
    return h + y


def forward(params, raw: dict, tokens, cast=_same, cast_activations=_same,
            positions=None):
    """Logits [T, vocabulary held] in float32 of one sequence ``tokens``
    [T]; ``positions`` keeps only those rows of the last norm and the head."""
    w = Weights(params, cast)
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[jnp.asarray(tokens)]
        for i in range(int(raw["num_hidden_layers"])):
            p = f"model.layers.{i}."
            x = cast_activations(mlp(w, p, raw, i, attention(w, p, raw, i, x)))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, w("model.norm.weight"), raw["rms_norm_eps"])
        return (x @ w("lm_head.weight").T).astype(F32)
