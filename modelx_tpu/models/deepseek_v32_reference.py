"""Plain float32 reference of the DeepSeek-V3.2-Exp decoder (``model_type``
``deepseek_v32``; without ``index_topk`` it is DeepSeek-V3): the layer
equations in straightforward ``jax.numpy``, one sequence at a time, with no
cache, no absorption of the up-projections, no kernels and no batching — keys
and values are EXPANDED for every position, and the selection is a plain sort,
query by query. It depends on nothing but jax and numpy, reads the architecture
from the checkpoint's ``config.json`` itself, and takes the weights under the
checkpoint's own per-expert names. What the program (``models/deepseek_v2.py``,
which serves V2, V3 and V3.2 as one module) computes is held against this.

``N(x) = x / sqrt(mean(x^2) + eps) * w``; ``LN(x) = (x - mean) / sqrt(var +
1e-6) * w + b``. Layer l, input ``x [T, D]``, ``H`` heads, ``dn`` =
``qk_nope_head_dim``, ``dr`` = ``qk_rope_head_dim``, ``dv`` = ``v_head_dim``,
``r`` = ``kv_lora_rank``; ``u = N_in(x)``:

- **Latent attention.** ``c_q = N_qa(u Wqa^T)``; ``q = c_q Wqb^T -> [T, H, dn +
  dr]`` = ``[q_nope | q_pe]``. ``u Wkva^T -> [T, r + dr]`` = ``[c | k_pe]``;
  ``N_kva(c) Wkvb^T -> [T, H, dn + dv]`` = ``[k_nope | v]``. Rope on ``q_pe``
  (every head) and ``k_pe`` (ONE key all heads share): lanes permuted from
  interleaved pairs to halves, then rotate-half; YaRN's inverse frequencies;
  cos and sin scaled by ``m(factor, mscale) / m(factor, mscale_all_dim)`` (1
  as published), the softmax scale ``(dn + dr)^-0.5 * m(factor,
  mscale_all_dim)^2`` with ``m(s, a) = 0.1 a ln s + 1``.
- **The lightning indexer** (``index_n_heads`` = ``Hi`` heads of
  ``index_head_dim`` = ``di``, ``index_topk`` = ``k``): ``q^I = c_q Wiq^T ->
  [T, Hi, di]`` (from the QUERY latent); ``k^I = LN(u Wik^T) -> [T, di]``; in
  both the FIRST ``dr`` lanes are roped — already in halves (lane ``i`` with
  lane ``dr/2 + i``), rotate-half, the same frequencies — the others are not;
  ``w = u Wiw^T * Hi^-0.5 * di^-0.5 -> [T, Hi]``. ``I(t, s) = sum_j w_j(t)
  relu(q^I_j(t) . k^I(s))`` for ``s <= t``. ``S_t`` = every ``s <= t`` while
  ``t + 1 <= k``, else the ``k`` positions of largest ``I(t, s)``, a tie
  going to the lower ``s`` (a stable sort).
- ``o_h(t) = sum_{s in S_t} softmax_{s in S_t}(scale * q_h(t) . k_h(s))
  v_h(s)`` with ``k_h = [k_nope | rope(k_pe)]`` — the same ``S_t`` for every
  head; ``h = x + concat_h(o_h) Wo^T``.
- ``m = N_post(h)``. Layer ``l < first_k_dense_replace``: ``y = h + (silu(m
  Wgate^T) * m Wup^T) Wdown^T``. Else ``s = sigmoid(m Wr^T)`` over the router's
  published width ``E``; ``c = s + b`` (``e_score_correction_bias``: it only
  chooses); ``n_group`` groups of ``E / n_group`` neighbours, a group's score
  the SUM of its two largest ``c``; the ``topk_group`` best groups stay; the
  ``num_experts_per_tok`` largest ``c`` inside them are chosen; ``g = s_chosen
  / sum(s_chosen) * routed_scaling_factor`` (``norm_topk_prob``; the scale is
  applied either way); ``y = h + sum_{e chosen and held} g_e E_e(m) +
  E_shared(m)``, each expert a SwiGLU, the shared one of width
  ``n_shared_experts * moe_intermediate_size``.
- After the last layer ``N_final`` and the untied head over the vocabulary
  held.

**The share.** ``n_routed_experts`` counts the experts the checkpoint holds
and ``expert_share = {"published": P, "first": f}`` says they are experts ``f
.. f + n_routed_experts`` of ``P``: routing — bias, groups and norm included —
runs over all ``P``, only the held experts' terms are summed, and the
vocabulary is the rows the checkpoint holds. Without the key the checkpoint is
whole.

**Departures from the published code** (``inference/model.py`` of the source
repository), each also under ``assumed`` in the benchmark's configuration file:
(a) the checkpoint is FP8 and the published indexer quantises its queries and
keys to FP8; here every weight is what the checkpoint file holds (bf16 in the
benchmark) and all arithmetic is float32; (b) the published indexer rotates
queries and keys by a Hadamard matrix before quantising them — the rotation is
orthogonal, changes no product in exact arithmetic and serves the FP8 product:
it is left out; (c) rope layouts — interleaved pairs in the attention, halves
in the indexer, as the published inference code has them after its correction —
the indexer's tensor names and its LayerNorm's ``1e-6`` are not in
``config.json``; (d) ties, between positions, experts or groups, go to the
lower index (``torch.topk`` leaves them unspecified); a dropped group's expert
is never chosen (the published mask would let one through when fewer than k
biased scores are positive); (e) the multi-token-prediction layer
(``num_nextn_predict_layers``) is not loaded: it changes no logit of the main
model; (f) ``ep_size``, ``max_position_embeddings`` are read by nothing;
``moe_layer_freq`` other than 1, attention biases, a tied head, ``q_lora_rank``
null are refused.

``cast`` (a function of one array) is applied to every weight as it is used:
the identity by default; the comparison's control passes a rounding to 8 bits
to show that its tolerances would catch a lower precision. ``cast_activations``
is applied to every ACTIVATION a program in a narrower type would round — each
projection's, norm's and rotation's output, the attention's weights and its
output, the gated product, each residual sum, the logits; scores, index
scores, the indexer's weights and the router's scores stay float32, as sums
of float32 products do — the identity by default; a rounding to bfloat16
makes these equations compute as the configuration states (the comparison's
witness: what the drift of that type alone does to the selection). ``head_block`` computes the attention that many
heads at a time and ``query_block`` the index scores that many queries at a
time, so that a long sequence fits a host. ``forward(..., selected=out)``
appends each layer's ``S_t`` as a bool ``[T, T]`` array to the list ``out``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
INDEX_NORM_EPS = 1e-6


def _same(x):
    return x


class Weights:
    """Weights by name, float32 at the moment of use."""

    def __init__(self, params, cast=_same, act=_same) -> None:
        self.params, self.cast, self.act = params, cast, act

    def __call__(self, name: str):
        return self.cast(jnp.asarray(np.asarray(self.params[name]).astype(np.float32)))

    def linear(self, x, name: str):
        """``x W^T``, as an activation."""
        return self.act(x @ self(name).T)


def rms_norm(x, w, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps: float = INDEX_NORM_EPS):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred / jnp.sqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * w + b


def mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def inverse_frequencies(raw: dict):
    """(inv_freq [dr / 2], the factor on cos and sin, the factor on the
    softmax scale) from ``rope_theta`` and ``rope_scaling``."""
    dim, base = int(raw["qk_rope_head_dim"]), float(raw.get("rope_theta", 10000.0))
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scaling = raw.get("rope_scaling")
    if not scaling:
        return 1.0 / pos_freqs, 1.0, 1.0
    kind = scaling.get("type") or scaling.get("rope_type")
    if kind != "yarn":
        raise ValueError(f"rope_scaling type {kind!r}")
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(correction_dim(float(scaling.get("beta_slow", 1)))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)
    all_dim = mscale(factor, float(scaling.get("mscale_all_dim", 0.0)))
    on_cos = mscale(factor, float(scaling.get("mscale", 1.0))) / all_dim
    return inv, on_cos, all_dim * all_dim


def rope(x, raw: dict, interleaved: bool):
    """x: [T, H, dr], positions 0..T-1. ``interleaved``: the lanes come as
    pairs (2i, 2i + 1) and are first permuted to halves; else they are in
    halves already. Then rotate-half."""
    inv, on_cos, _ = inverse_frequencies(raw)
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[:, None, :] * on_cos
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[:, None, :] * on_cos
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def selection(w: Weights, p: str, raw: dict, u, c_q, query_block: int = 0):
    """``S_t`` of every position as a bool ``[T, T]`` (row t: the positions
    query t attends to) by a stable sort of ``I(t, .)``, query by query."""
    t = u.shape[0]
    k = int(raw["index_topk"])
    visible = np.tril(np.ones((t, t), bool))
    if t <= k:
        return visible
    heads, dim, dr = int(raw["index_n_heads"]), int(raw["index_head_dim"]), int(
        raw["qk_rope_head_dim"])
    x = p + "self_attn.indexer."
    q = w.linear(c_q, x + "wq_b.weight").reshape(t, heads, dim)
    q = jnp.concatenate([w.act(rope(q[..., :dr], raw, False)), q[..., dr:]], -1)
    key = w.act(layer_norm(w.linear(u, x + "wk.weight"), w(x + "k_norm.weight"),
                           w(x + "k_norm.bias")))
    key = jnp.concatenate([w.act(rope(key[:, None, :dr], raw, False))[:, 0], key[:, dr:]], -1)
    weight = (u @ w(x + "weights_proj.weight").T) * (heads ** -0.5 * dim ** -0.5)
    out = visible.copy()
    step = query_block or t
    for start in range(0, t, step):
        rows = slice(start, min(start + step, t))
        scores = np.asarray(jnp.einsum(
            "thj,th->tj", jax.nn.relu(jnp.einsum("thd,jd->thj", q[rows], key)), weight[rows]))
        for i, row in enumerate(scores, start):
            if i + 1 > k:
                # the k largest of positions 0..i, the lower position first among equals
                order = np.argsort(-row[: i + 1], kind="stable")[:k]
                out[i] = False
                out[i, order] = True
    return out


def attention(w: Weights, p: str, raw: dict, x, head_block: int = 0, query_block: int = 0,
              selected: list | None = None):
    t, heads = x.shape[0], int(raw["num_attention_heads"])
    dn, dr, dv = (int(raw[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    r, eps = int(raw["kv_lora_rank"]), raw["rms_norm_eps"]
    u = w.act(rms_norm(x, w(p + "input_layernorm.weight"), eps))
    c_q = w.act(rms_norm(w.linear(u, p + "self_attn.q_a_proj.weight"),
                         w(p + "self_attn.q_a_layernorm.weight"), eps))
    q = w.linear(c_q, p + "self_attn.q_b_proj.weight").reshape(t, heads, dn + dr)
    kva = w.linear(u, p + "self_attn.kv_a_proj_with_mqa.weight")  # [T, r + dr]
    c = w.act(rms_norm(kva[:, :r], w(p + "self_attn.kv_a_layernorm.weight"), eps))
    kv = w.linear(c, p + "self_attn.kv_b_proj.weight").reshape(t, heads, dn + dv)
    k_pe = w.act(rope(kva[:, None, r:], raw, True))  # [T, 1, dr]: one key for all heads
    q = jnp.concatenate([q[..., :dn], w.act(rope(q[..., dn:], raw, True))], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (t, heads, dr))], -1)
    v = kv[..., dn:]
    scale = (dn + dr) ** -0.5 * inverse_frequencies(raw)[2]
    if raw.get("index_topk"):
        seen = selection(w, p, raw, u, c_q, query_block)
    else:
        seen = np.tril(np.ones((t, t), bool))
    if selected is not None:
        selected.append(seen)
    seen = jnp.asarray(seen)
    outs = []
    step = head_block or heads
    for h in range(0, heads, step):
        scores = jnp.einsum("thd,jhd->htj", q[:, h: h + step], k[:, h: h + step]) * scale
        probs = w.act(jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1))
        outs.append(jnp.einsum("htj,jhd->thd", probs, v[:, h: h + step]))
    a = w.act(jnp.concatenate(outs, axis=1))
    return w.act(x + w.linear(a.reshape(t, heads * dv), p + "self_attn.o_proj.weight"))


def swiglu(w: Weights, p: str, m):
    return w.linear(w.act(jax.nn.silu(w.linear(m, p + "gate_proj.weight"))
                          * w.linear(m, p + "up_proj.weight")), p + "down_proj.weight")


def held_experts(raw: dict) -> tuple[int, int, int]:
    """(first held, how many held, published)."""
    held = int(raw["n_routed_experts"])
    share = raw.get("expert_share") or {}
    return int(share.get("first", 0)), held, int(share.get("published", held))


def routing(w: Weights, p: str, raw: dict, m):
    """Combine weights [T, published] (numpy): zero off the chosen k. Written
    as the loop the equations describe, token by token."""
    if raw.get("scoring_func") != "sigmoid" or raw.get("topk_method") != "noaux_tc":
        raise ValueError(f"scoring_func {raw.get('scoring_func')!r} with topk_method "
                         f"{raw.get('topk_method')!r}")
    scores = np.asarray(jax.nn.sigmoid(m @ w(p + "mlp.gate.weight").T))
    biased = scores + np.asarray(w(p + "mlp.gate.e_score_correction_bias"))
    t, e = scores.shape
    groups, kept, k = int(raw["n_group"]), int(raw["topk_group"]), int(raw["num_experts_per_tok"])
    size = e // groups
    out = np.zeros_like(scores)
    for i in range(t):
        of_group = [np.sort(biased[i, g * size: (g + 1) * size])[-2:].sum()
                    for g in range(groups)]
        stay = np.argsort(-np.asarray(of_group), kind="stable")[:kept]
        left = np.full(e, -np.inf, scores.dtype)
        for g in stay:
            left[g * size: (g + 1) * size] = biased[i, g * size: (g + 1) * size]
        chosen = np.argsort(-left, kind="stable")[:k]
        gates = scores[i, chosen]
        if raw.get("norm_topk_prob", False):
            gates = gates / gates.sum()
        out[i, chosen] = gates * float(raw.get("routed_scaling_factor", 1.0))
    return out


def routed_experts(w: Weights, p: str, raw: dict, m):
    """The held experts' part of the routed sum, expert by expert, each on
    the tokens that chose it."""
    first, held, _ = held_experts(raw)
    combine = routing(w, p, raw, m)
    out = jnp.zeros_like(m)
    for e in range(first, first + held):
        rows = np.nonzero(combine[:, e])[0]
        if rows.size:
            y = swiglu(w, f"{p}mlp.experts.{e}.", m[rows])
            out = out.at[rows].add(y * jnp.asarray(combine[rows, e])[:, None])
    return out


def is_dense(raw: dict, layer: int) -> bool:
    if int(raw.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq other than 1")
    return layer < int(raw.get("first_k_dense_replace", 0))


def mlp(w: Weights, p: str, raw: dict, layer: int, h):
    m = w.act(rms_norm(h, w(p + "post_attention_layernorm.weight"), raw["rms_norm_eps"]))
    if is_dense(raw, layer):
        return w.act(h + swiglu(w, p + "mlp.", m))
    y = routed_experts(w, p, raw, m)
    if raw.get("n_shared_experts"):
        y = y + swiglu(w, p + "mlp.shared_experts.", m)
    return w.act(h + w.act(y))


def forward(params, raw: dict, tokens, cast=_same, cast_activations=_same,
            positions=None, head_block: int = 0, query_block: int = 0,
            selected: list | None = None):
    """Logits [T, vocabulary held] in float32 of one sequence ``tokens``
    [T]; ``positions`` keeps only those rows of the last norm and the head;
    ``selected`` (a list) receives each layer's ``S_t`` as a bool [T, T]."""
    if raw.get("attention_bias") or raw.get("tie_word_embeddings") or not raw.get("q_lora_rank"):
        raise ValueError("attention biases, a tied head, q_lora_rank null")
    w = Weights(params, cast, cast_activations)
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[jnp.asarray(tokens)]
        for i in range(int(raw["num_hidden_layers"])):
            p = f"model.layers.{i}."
            x = mlp(w, p, raw, i, attention(w, p, raw, x, head_block, query_block, selected))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = w.act(rms_norm(x, w("model.norm.weight"), raw["rms_norm_eps"]))
        return w.linear(x, "lm_head.weight").astype(F32)
