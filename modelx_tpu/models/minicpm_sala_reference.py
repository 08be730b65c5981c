"""Plain float32 reference of the MiniCPM-SALA decoder: the layer equations in
straightforward ``jax.numpy``, one sequence at a time, with no cache, no
kernel, no chunking and no batching. It depends on nothing but jax and numpy,
reads the architecture from the checkpoint's ``config.json`` itself, and takes
the weights under the checkpoint's own names. What the program
(``models/minicpm_sala.py``) computes is held against this.

``N(x, w) = x / sqrt(mean(x^2) + eps) * w`` (eps ``rms_norm_eps``). ``x^`` is
``N`` of a sub-block's input by its layer norm.

- **Model.** ``h0 = scale_emb * E[token]``. Each of a layer's two sub-blocks
  adds ``(scale_depth / sqrt(L_pub)) * y`` to the residual, ``L_pub`` the
  PUBLISHED depth (``layer_share.published``; ``num_hidden_layers`` where the
  key is absent), not the number of layers held. FFN: ``W_down(silu(W_gate x^)
  * W_up x^)``. Logits: ``W_head (N(h) / (hidden_size / dim_model_base))``,
  head untied. A checkpoint that holds layers ``f .. f + n`` of the published
  ones (``layer_share.first = f``) names them ``model.layers.<f + i>``.
- **``lightning-attn`` layer.** ``q, k, v = W x^`` as ``lightning_nh`` heads of
  ``lightning_head_dim``; per-head ``N`` with a learned ``[d]`` weight on q and
  on k (``qk_norm``); rotate-half rope over the whole head, ``rope_theta``
  (``lightning_use_rope``); per head h with ``lam = exp(-s_h)``, ``s_h =
  2^(-8 (h + 1) / H)``: ``o_t = sum_{j <= t} lam^(t - j) (q_t . k_j) v_j /
  sqrt(d)`` — the explicit O(T^2) decay-masked product, equal to the recurrence
  ``S_t = lam S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t / sqrt(d)``; ``o <-
  N_hidden(concat_h o_t)`` with a learned weight (``use_output_norm``), times
  ``sigmoid(W_g x^)`` (``use_output_gate``); ``y = W_o o``.
- **``minicpm4`` layer.** q ``num_attention_heads`` heads, k and v
  ``num_key_value_heads`` heads of ``head_dim``; per-head ``N`` on q and k; no
  rope (``attn_use_rope`` false). A query at position t with context ``n = t +
  1``, for KV head h and its query heads ``G_h``:
  ``n < dense_len``: causal softmax attention over positions ``0..t``, scale
  ``1/sqrt(d)``. Else: compressed keys ``C_h[i] = mean(K_h[s i : s i + w])``
  (``kernel_stride`` s = 16, ``kernel_size`` w = 32) for every i with ``s i + w
  <= n``; ``p_g = softmax_i(q_g . C_h[i] / sqrt(d))``; ``s_h[i] = sum_{g in
  G_h} p_g[i]``; block j = positions ``B j .. B j + B - 1`` (``block_size`` B =
  64); ``b_h[j] = max s_h[i]`` over the compressed keys whose w positions touch
  block j; the first ``init_blocks`` blocks and the ``window_size / B`` blocks
  ending at the query's own are forced; the ``topk`` highest ``b_h`` are
  selected (ties to the lower index); ``o_g`` = causal softmax attention over
  the positions ``<= t`` of the selected blocks only. Then ``o <- o *
  sigmoid(W_g x^)`` (``attn_use_output_gate``), ``y = W_o o``.

**Assumed, because the published config does not say — each a possible
departure from the released model:** (a) ``sparse_config`` = ``{kernel_size 32,
kernel_stride 16, init_blocks 1, block_size 64, window_size 2048, topk 64,
dense_len 8192}``, the MiniCPM4 family's published values (the config has no
such key; its description confirms top-64 blocks); (b) the dense/sparse switch
is by the QUERY'S OWN context, so that a prompt landed in pieces, a cached
decode step and a cache-less forward agree position by position (the published
code switches on the length of the call); (c) the selection's softmax is exact
over the compressed keys (the published kernel approximates its normaliser
from a coarser pooling); (d) decay slopes ``s_h = 2^(-8 (h + 1) / H)``, the
same in every layer; (e) the output norm is over all hidden channels, both
gates are ``[hidden, hidden]`` on ``x^``, no biases; (f) tensor names in the
MiniCPM lineage (``self_attn.{q,k,v,o}_proj``, ``q_norm``, ``k_norm``,
``o_gate``, ``norm``, ``mlp.{gate,up,down}_proj``); (g) seeded weights.

``cast`` (a function of one array) is applied to every weight as it is used
and ``cast_activations`` to every layer's output: the identity by default;
the comparison's control passes a rounding to 8 bits to show that its
tolerances would catch a lower precision.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "init_blocks": 1, "block_size": 64,
                   "window_size": 2048, "topk": 64, "dense_len": 8192}


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


def layer_share(raw: dict) -> tuple[int, int]:
    """(first layer held, published depth)."""
    share = raw.get("layer_share") or {}
    return int(share.get("first", 0)), int(share.get("published", raw["num_hidden_layers"]))


def rope(x, theta: float):
    """x: [T, H, d], positions 0..T-1, rotate-half over the whole head."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


@functools.lru_cache(maxsize=32)
def decay_mask(t: int, heads: int, h: int):
    """``lam_h^(i - j)`` for ``j <= i``, else 0, as ``[T, T]`` float32: from ``i -
    j`` in float64, never as a quotient of two powers. The same in every layer
    (assumption d), so a sequence's masks are made once, a head at a time."""
    slope = 2.0 ** (-8.0 * (h + 1) / heads)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    return jnp.asarray(np.where(j <= i, np.exp(-slope * np.maximum(i - j, 0)), 0.0), F32)


def lightning(w: Weights, p: str, raw: dict, u):
    """u [T, D] (normed) -> the layer's y [T, D]."""
    t, heads, d = u.shape[0], int(raw["lightning_nh"]), int(raw["lightning_head_dim"])
    eps = raw["rms_norm_eps"]
    q = (u @ w(p + "self_attn.q_proj.weight").T).reshape(t, heads, d)
    k = (u @ w(p + "self_attn.k_proj.weight").T).reshape(t, heads, d)
    v = (u @ w(p + "self_attn.v_proj.weight").T).reshape(t, heads, d)
    q = rms_norm(q, w(p + "self_attn.q_norm.weight"), eps)
    k = rms_norm(k, w(p + "self_attn.k_norm.weight"), eps)
    if raw.get("lightning_use_rope", True):
        q, k = rope(q, float(raw["rope_theta"])), rope(k, float(raw["rope_theta"]))
    outs = []
    for h in range(heads):  # head by head, so that a long sequence's [T, T] fits
        scores = (q[:, h] @ k[:, h].T) * decay_mask(t, heads, h)
        outs.append(scores @ v[:, h] / math.sqrt(d))
    o = jnp.stack(outs, axis=1)
    o = o.reshape(t, heads * d)
    if raw.get("use_output_norm", True):
        o = rms_norm(o, w(p + "self_attn.norm.weight"), eps)
    if raw.get("use_output_gate", True):
        o = o * jax.nn.sigmoid(u @ w(p + "self_attn.o_gate.weight").T)
    return o @ w(p + "self_attn.o_proj.weight").T


def compressed_keys(k_head, sc: dict):
    """Every compressed key of one KV head: C[i] = mean(K[s i : s i + w]),
    for the i whose w positions the sequence holds. k_head [T, d] -> [count, d]."""
    size, stride = sc["kernel_size"], sc["kernel_stride"]
    count = (k_head.shape[0] - size) // stride + 1 if k_head.shape[0] >= size else 0
    if not count:
        return jnp.zeros((0, k_head.shape[-1]), F32)
    at = stride * np.arange(count)[:, None] + np.arange(size)[None, :]
    return jnp.mean(k_head[at], axis=1)


def selected_blocks(q_t, comp, t: int, sc: dict) -> np.ndarray:
    """The blocks one KV head's queries at position ``t`` attend. q_t [G, d]
    (the head's query group), comp the head's compressed keys
    (:func:`compressed_keys`) -> sorted block indices."""
    size, stride, block = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    n, d = t + 1, q_t.shape[-1]
    own = t // block
    count = (n - size) // stride + 1 if n >= size else 0
    scores = np.full(own + 1, -np.inf)
    if count:
        probs = jax.nn.softmax(q_t @ comp[:count].T / math.sqrt(d), axis=-1)  # [G, count]
        s = np.asarray(jnp.sum(probs, axis=0), np.float64)
        for i in range(count):  # the blocks key i's positions touch
            for j in range(stride * i // block, min((stride * i + size - 1) // block, own) + 1):
                scores[j] = max(scores[j], s[i])
    forced = set(range(min(sc["init_blocks"], own + 1)))
    forced |= set(range(max(own - sc["window_size"] // block + 1, 0), own + 1))
    for j in forced:
        scores[j] = np.inf
    order = sorted(range(own + 1), key=lambda j: (-scores[j], j))  # ties to the lower index
    return np.sort(np.asarray(order[: sc["topk"]], np.int64))


def sparse(w: Weights, p: str, raw: dict, u):
    """u [T, D] (normed) -> the layer's y [T, D], position by position."""
    t_all, heads, hkv, d = (u.shape[0], int(raw["num_attention_heads"]),
                            int(raw["num_key_value_heads"]), int(raw["head_dim"]))
    sc = dict(SPARSE_DEFAULTS, **(raw.get("sparse_config") or {}))
    eps, group, block = raw["rms_norm_eps"], heads // hkv, sc["block_size"]
    q = (u @ w(p + "self_attn.q_proj.weight").T).reshape(t_all, heads, d)
    k = (u @ w(p + "self_attn.k_proj.weight").T).reshape(t_all, hkv, d)
    v = (u @ w(p + "self_attn.v_proj.weight").T).reshape(t_all, hkv, d)
    q = rms_norm(q, w(p + "self_attn.q_norm.weight"), eps)
    k = rms_norm(k, w(p + "self_attn.k_norm.weight"), eps)
    if raw.get("attn_use_rope", False):
        q, k = rope(q, float(raw["rope_theta"])), rope(k, float(raw["rope_theta"]))
    # positions whose context is below dense_len: plain causal attention, a
    # query head at a time so that a long sequence's [T, T] fits
    dense_t = min(t_all, sc["dense_len"] - 1)
    causal = np.arange(dense_t)[None, :] <= np.arange(dense_t)[:, None]
    dense = []
    for g in range(heads):
        scores = q[:dense_t, g] @ k[:dense_t, g // group].T / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        dense.append(probs @ v[:dense_t, g // group])
    rows = list(jnp.stack(dense, axis=1).reshape(dense_t, heads * d)) if dense_t else []
    comp = [compressed_keys(k[:, h], sc) for h in range(hkv)]
    for t in range(dense_t, t_all):  # the others, position by position
        out = []
        for h in range(hkv):
            qg = q[t, h * group: (h + 1) * group]  # [G, d]
            blocks = selected_blocks(qg, comp[h], t, sc)
            keep = (blocks[:, None] * block + np.arange(block)[None, :]).reshape(-1)
            keep = keep[keep <= t]
            probs = jax.nn.softmax(qg @ k[keep, h].T / math.sqrt(d), axis=-1)
            out.append(probs @ v[keep, h])  # [G, d]
        rows.append(jnp.concatenate(out, axis=0).reshape(heads * d))
    o = jnp.stack(rows)
    if raw.get("attn_use_output_gate", True):
        o = o * jax.nn.sigmoid(u @ w(p + "self_attn.o_gate.weight").T)
    return o @ w(p + "self_attn.o_proj.weight").T


def mlp(w: Weights, p: str, m):
    return (jax.nn.silu(m @ w(p + "mlp.gate_proj.weight").T) * (m @ w(p + "mlp.up_proj.weight").T)
            ) @ w(p + "mlp.down_proj.weight").T


def forward(params, raw: dict, tokens, cast=_same, cast_activations=_same, positions=None):
    """Logits [T, vocabulary] in float32 of one sequence ``tokens`` [T];
    ``positions`` keeps only those rows of the last norm and the head."""
    w = Weights(params, cast)
    first, published = layer_share(raw)
    residual = float(raw.get("scale_depth", 1.0)) / math.sqrt(published)
    eps = raw["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = w("model.embed_tokens.weight")[jnp.asarray(tokens)] * float(raw.get("scale_emb", 1.0))
        for i in range(int(raw["num_hidden_layers"])):
            p = f"model.layers.{first + i}."
            u = rms_norm(x, w(p + "input_layernorm.weight"), eps)
            mixer = lightning if raw["mixer_types"][i] == LIGHTNING else sparse
            x = x + residual * mixer(w, p, raw, u)
            m = rms_norm(x, w(p + "post_attention_layernorm.weight"), eps)
            x = cast_activations(x + residual * mlp(w, p, m))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, w("model.norm.weight"), eps)
        x = x / (float(raw["hidden_size"]) / float(raw.get("dim_model_base", raw["hidden_size"])))
        return (x @ w("lm_head.weight").T).astype(F32)
