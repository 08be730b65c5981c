"""Plain float32 reference of the DeepSeek-V2 decoder: the layer equations in
straightforward ``jax.numpy``, one sequence at a time, with no cache, no
absorption of the up-projections, no kernels and no batching. It depends on
nothing but jax and numpy, reads the architecture from the checkpoint's
``config.json`` itself, and takes the weights under the checkpoint's own
per-expert names. What the program (``models/deepseek_v2.py``) computes is
held against this.

``N(x) = x / sqrt(mean(x^2) + eps) * w``. Layer l, input ``x [T, D]``, ``H``
heads, ``dn`` = ``qk_nope_head_dim``, ``dr`` = ``qk_rope_head_dim``, ``dv`` =
``v_head_dim``, ``r`` = ``kv_lora_rank``:

- ``u = N_in(x)``. Queries: ``q = N_qa(u Wqa^T) Wqb^T -> [T, H, dn + dr]`` =
  ``[q_nope | q_pe]``. Latent: ``u Wkva^T -> [T, r + dr]`` = ``[c | k_pe]``;
  ``N_kva(c) Wkvb^T -> [T, H, dn + dv]`` = ``[k_nope | v]``. No biases.
- Rope on ``q_pe`` (every head) and ``k_pe`` (ONE key shared by all heads):
  the lanes are first permuted from interleaved pairs to halves (lane ``2i``
  to ``i``, lane ``2i + 1`` to ``dr/2 + i``), then rotate-half. Inverse
  frequencies are YaRN's (interpolated and extrapolated frequencies blended
  by a linear ramp between the dimensions that turn ``beta_fast`` and
  ``beta_slow`` times over the original context); cos and sin are scaled by
  ``m(factor, mscale) / m(factor, mscale_all_dim)`` with ``m(s, a) = 0.1 a ln
  s + 1``.
- ``k = [k_nope | rope(k_pe)]``, ``q = [q_nope | rope(q_pe)]``; scores ``q_h .
  k_h * (dn + dr)^-0.5 * m(factor, mscale_all_dim)^2`` over ``j <= t``;
  softmax in float32; ``o = concat_h(P_h v_h) Wo^T``; ``h = x + o``.
- ``m = N_post(h)``. Layer ``l < first_k_dense_replace``: ``y = h +
  (silu(m Wgate^T) * m Wup^T) Wdown^T``. Else: ``s = softmax(m Wg^T)`` over the
  router's published width ``E``; the experts fall into ``n_group`` groups of
  ``E / n_group`` neighbours; a group's score is the largest ``s`` in it; the
  ``topk_group`` best groups are kept, the scores of the others set to zero;
  ``S`` = the ``num_experts_per_tok`` largest of what is left; ``w_e =
  routed_scaling_factor * s_e`` (``norm_topk_prob`` false: not renormalised;
  true: ``s_e / sum_S s``, unscaled); ``y = h + sum_{e in S and held} w_e
  E_e(m) + E_shared(m)``, each expert a SwiGLU, the shared one of width
  ``n_shared_experts * moe_intermediate_size``.
- After the last layer ``N_final`` and the untied head over the vocabulary
  held.

**The share.** ``n_routed_experts`` counts the experts the checkpoint holds
and ``expert_share = {"published": P, "first": f}`` says they are experts ``f
.. f + n_routed_experts`` of ``P``: routing — groups included — runs over all
``P``, only the held experts' terms are summed, and the vocabulary is the rows
the checkpoint holds. Without the key the checkpoint is whole.

**Departures from the published modelling code** (``modeling_deepseek.py`` of
the source repository), each also under ``assumed`` in the benchmark's
configuration file: (a) the published code computes attention in the
checkpoint's dtype with a float32 softmax; here everything is float32; (b) the
router's scores are computed from float32 inputs as published, and a tie
between two experts or two groups goes to the lower index (``top_k``'s rule;
the published ``torch.topk`` leaves ties unspecified); (c) the published code
masks dropped groups' scores to 0.0 and so could choose a dropped expert when
fewer than k scores are positive — a softmax never gives that, and here a
dropped expert is never chosen; (d) ``moe_layer_freq`` other than 1,
``scoring_func`` other than ``softmax``, ``topk_method`` other than
``group_limited_greedy`` / ``greedy`` and attention biases are refused; (e)
``ep_size``, ``aux_loss_alpha``, ``seq_aux``, ``pretraining_tp`` are read by
nothing (training or deployment plumbing).

``cast`` (a function of one array) is applied to every weight as it is used
and ``cast_activations`` to every layer's output: the identity by default; the
comparison's control passes a rounding to 8 bits to show that its tolerances
would catch a lower precision. ``head_block`` computes the attention that many
heads at a time, so that ``[H, T, T]`` scores of a long sequence fit.
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


def rope(x, raw: dict):
    """x: [T, H, dr], positions 0..T-1: interleaved pairs to halves, then
    rotate-half."""
    inv, on_cos, _ = inverse_frequencies(raw)
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[:, None, :] * on_cos
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[:, None, :] * on_cos
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def attention(w: Weights, p: str, raw: dict, x, head_block: int = 0):
    t, heads = x.shape[0], int(raw["num_attention_heads"])
    dn, dr, dv = (int(raw[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    r, eps = int(raw["kv_lora_rank"]), raw["rms_norm_eps"]
    u = rms_norm(x, w(p + "input_layernorm.weight"), eps)
    qa = rms_norm(u @ w(p + "self_attn.q_a_proj.weight").T,
                  w(p + "self_attn.q_a_layernorm.weight"), eps)
    q = (qa @ w(p + "self_attn.q_b_proj.weight").T).reshape(t, heads, dn + dr)
    kva = u @ w(p + "self_attn.kv_a_proj_with_mqa.weight").T  # [T, r + dr]
    c = rms_norm(kva[:, :r], w(p + "self_attn.kv_a_layernorm.weight"), eps)
    kv = (c @ w(p + "self_attn.kv_b_proj.weight").T).reshape(t, heads, dn + dv)
    k_pe = rope(kva[:, None, r:], raw)  # [T, 1, dr]: one key for all heads
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], raw)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (t, heads, dr))], -1)
    v = kv[..., dn:]
    scale = (dn + dr) ** -0.5 * inverse_frequencies(raw)[2]
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    outs = []
    step = head_block or heads
    for h in range(0, heads, step):
        scores = jnp.einsum("thd,jhd->htj", q[:, h: h + step], k[:, h: h + step]) * scale
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("htj,jhd->thd", probs, v[:, h: h + step]))
    a = jnp.concatenate(outs, axis=1)
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
    """Combine weights [T, published]: zero off the chosen k."""
    if raw.get("scoring_func", "softmax") != "softmax":
        raise ValueError(f"scoring_func {raw['scoring_func']!r}")
    scores = jax.nn.softmax(m @ w(p + "mlp.gate.weight").T, axis=-1)
    t, e = scores.shape
    left = scores
    method = raw.get("topk_method", "greedy")
    if method == "group_limited_greedy":
        groups, kept = int(raw["n_group"]), int(raw["topk_group"])
        best = jnp.max(scores.reshape(t, groups, e // groups), axis=-1)
        _, chosen = jax.lax.top_k(best, kept)
        keep = jnp.zeros((t, groups), bool).at[jnp.arange(t)[:, None], chosen].set(True)
        left = jnp.where(jnp.repeat(keep, e // groups, axis=1), scores, -1.0)
    elif method != "greedy":
        raise ValueError(f"topk_method {method!r}")
    vals, idx = jax.lax.top_k(left, int(raw["num_experts_per_tok"]))
    if raw.get("norm_topk_prob", False):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    else:
        vals = vals * float(raw.get("routed_scaling_factor", 1.0))
    return jnp.zeros_like(scores).at[jnp.arange(t)[:, None], idx].set(vals)


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


def is_dense(raw: dict, layer: int) -> bool:
    if int(raw.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq other than 1")
    return layer < int(raw.get("first_k_dense_replace", 0))


def mlp(w: Weights, p: str, raw: dict, layer: int, h):
    m = rms_norm(h, w(p + "post_attention_layernorm.weight"), raw["rms_norm_eps"])
    if is_dense(raw, layer):
        return h + swiglu(w, p + "mlp.", m)
    y = routed_experts(w, p, raw, m)
    if raw.get("n_shared_experts"):
        y = y + swiglu(w, p + "mlp.shared_experts.", m)
    return h + y


def forward(params, raw: dict, tokens, cast=_same, cast_activations=_same,
            positions=None, head_block: int = 0):
    """Logits [T, vocabulary held] in float32 of one sequence ``tokens``
    [T]; ``positions`` keeps only those rows of the last norm and the head."""
    w = Weights(params, cast)
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[jnp.asarray(tokens)]
        for i in range(int(raw["num_hidden_layers"])):
            p = f"model.layers.{i}."
            x = cast_activations(mlp(w, p, raw, i, attention(w, p, raw, x, head_block)))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, w("model.norm.weight"), raw["rms_norm_eps"])
        return (x @ w("lm_head.weight").T).astype(F32)
