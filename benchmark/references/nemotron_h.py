"""Plain float32 reference of the Nemotron-H decoder: the layer equations in
straightforward ``jax.numpy``, one sequence at a time, the state-space
recurrence TOKEN BY TOKEN, with no cache, no chunked scan, no kernels and no
batching. It depends on nothing but jax and numpy, reads the architecture from
the checkpoint's ``config.json`` itself, and takes the weights under the
checkpoint's own per-expert names. What the program (``models/nemotron_h.py``)
computes is held against this.

``N(x) = x / sqrt(mean(x^2) + eps) * w`` (``eps`` = ``layer_norm_epsilon``).
Layer l, input ``x [T, D]``: ``u = N_l(x)``, ``x <- x + mixer_l(u)``, the mixer
by the l-th character of ``hybrid_override_pattern``:

- ``M``, Mamba-2. ``[z | xBC | dt] = u W_in^T`` of widths ``I | I + 2 G S | H``
  (``I`` = ``mamba_num_heads x mamba_head_dim``, ``G`` = ``n_groups``, ``S`` =
  ``ssm_state_size``, ``H`` = ``mamba_num_heads``). ``xBC_t <- silu(b + sum_k
  w[:, k] xBC_{t - (K - 1) + k})``: depthwise, causal, ``K`` = ``conv_kernel``,
  zeros before the sequence. ``xBC = [x | B | C]``: ``x`` ``[H, P]``, ``B``,
  ``C`` ``[G, S]``, head ``h`` reading group ``h // (H / G)``. ``dt_h =
  softplus(dt_h + dt_bias_h)``, ``A_h = -exp(A_log_h)``; from ``S_h = 0``
  ``[P, S]``: ``S_h <- exp(dt_h A_h) S_h + dt_h x_h (x) B_g``, ``y_h = S_h C_g +
  D_h x_h``. Then ``y <- N_groups(y * silu(z))`` — the RMS statistic over each
  of the ``G`` runs of ``I / G`` values by itself, one weight of width ``I`` —
  and ``out = y W_out^T``.
- ``E``, the latent expert layer. ``s = sigmoid(u W_r^T)`` over the router's
  published width; the ``num_experts_per_tok`` largest of ``s + b`` are chosen
  (``b`` = ``e_score_correction_bias``: it only chooses); ``w = s_chosen``,
  divided by their sum (``norm_topk_prob``), times ``routed_scaling_factor``.
  ``l = u W_down^T`` (``fc1_latent_proj``, to ``moe_latent_size``); ``r =
  sum_{e chosen and held} w_e W2_e relu(W1_e l)^2``; ``out = r W_up^T``
  (``fc2_latent_proj``) ``+ W2_s relu(W1_s u)^2``, the shared expert on the
  hidden state. Without ``moe_latent_size`` the experts read ``u`` itself.
- ``*``, attention: ``num_attention_heads`` query and ``num_key_value_heads``
  KV heads of ``head_dim``, no bias, scale ``head_dim^-0.5``, causal, softmax in
  float32, NO rotary embedding; ``out = concat_h(P_h v_h) W_o^T``.
- ``-``, a dense MLP: ``out = W_down relu(W_up u)^2``.

After the last layer ``N_f`` and the untied head over the vocabulary held.

**The share.** ``n_routed_experts`` counts the experts the checkpoint holds
and ``expert_share = {"published": P, "first": f}`` says they are experts ``f
.. f + n_routed_experts`` of ``P``: routing runs over all ``P``, only the held
experts' terms are summed (in the latent width, then up-projected: what the
absent experts would add is another chip's), and the vocabulary is the rows
the checkpoint holds. Without the key the checkpoint is whole.

**Departures from the published modelling code** (``modeling_nemotron_h.py``
of the source repository), each also under ``assumed`` in the benchmark's
configuration file: (a) the router reads the hidden state ``u``, not the
latent — the published ``NemotronHMOE`` routes before it projects; (b) the
attention layers apply no rotary embedding, as the published ``nemotron_h``
attention has none: ``rope_theta`` and ``partial_rotary_factor`` are carried
and read by nothing; (c) the multi-token-prediction layer
(``num_nextn_predict_layers``, ``mtp_hybrid_override_pattern``) is not loaded:
it changes no logit of the main model; (d) ``dt`` is not clamped after the
softplus (the published ``time_step_limit`` is ``(0, inf)``);
``time_step_min``, ``time_step_max`` and ``time_step_floor`` initialise
``dt_bias`` and are read by nothing; (e) a tie between two experts goes to the
lower index (``top_k``'s rule; ``torch.topk`` leaves ties unspecified), and the
published ``+ 1e-20`` under the normalising sum is left out (a sum of sigmoids
is never zero); (f) everything is float32, where the published code computes
in the checkpoint's dtype with a float32 state, router and softmax; (g)
``n_group`` / ``topk_group`` other than 1, ``mamba_proj_bias``,
``attention_bias``, ``mlp_bias``, ``use_bias``, ``moe_shared_expert_overlap``
and ``residual_in_fp32`` true, and activations other than ``silu`` / ``relu2``
are refused; ``expand``, ``rescale_prenorm_residual``, ``num_logits_to_keep``,
``use_mamba_kernels``, ``sliding_window``, ``max_position_embeddings`` and
``chunk_size`` (how a kernel cuts the scan, not what it computes) are read by
nothing.

``cast`` (a function of one array) is applied to every weight as it is used
and ``cast_activations`` to every layer's output: the identity by default; the
comparison's control passes a rounding to 8 bits to show that its tolerances
would catch a lower precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
REFUSED = ("mamba_proj_bias", "attention_bias", "mlp_bias", "use_bias",
           "moe_shared_expert_overlap", "residual_in_fp32", "tie_word_embeddings")


def _same(x):
    return x


class Weights:
    """Weights by name, float32 at the moment of use."""

    def __init__(self, params, cast=_same) -> None:
        self.params, self.cast = params, cast

    def __call__(self, name: str):
        return self.cast(jnp.asarray(np.asarray(self.params[name]).astype(np.float32)))


def check(raw: dict) -> None:
    for key in REFUSED:
        if raw.get(key):
            raise ValueError(f"{key} is not implemented")
    if int(raw.get("n_group", 1)) != 1 or int(raw.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing is not implemented")
    if raw.get("mamba_hidden_act", "silu") != "silu" or raw.get("mlp_hidden_act",
                                                                "relu2") != "relu2":
        raise ValueError("activations other than silu / relu2 are not implemented")


def rms_norm(x, w, eps: float):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def eps_of(raw: dict) -> float:
    return float(raw.get("layer_norm_epsilon", raw.get("norm_eps", 1e-5)))


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba(w: Weights, p: str, raw: dict, u):
    t = u.shape[0]
    heads, hd = int(raw["mamba_num_heads"]), int(raw["mamba_head_dim"])
    groups, n, k = int(raw["n_groups"]), int(raw["ssm_state_size"]), int(raw["conv_kernel"])
    inner, gn = heads * hd, groups * n
    zxd = u @ w(p + "mixer.in_proj.weight").T
    z, xbc, dt = zxd[:, :inner], zxd[:, inner: inner + inner + 2 * gn], zxd[:, 2 * inner + 2 * gn:]
    conv_w = w(p + "mixer.conv1d.weight")[:, 0, :]  # [C, K]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc], axis=0)
    conv = sum(padded[j: j + t] * conv_w[:, j] for j in range(k))
    if raw.get("use_conv_bias", True):
        conv = conv + w(p + "mixer.conv1d.bias")
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, heads, hd)
    b = jnp.repeat(xbc[:, inner: inner + gn].reshape(t, groups, n), heads // groups, axis=1)
    c = jnp.repeat(xbc[:, inner + gn:].reshape(t, groups, n), heads // groups, axis=1)
    dt = jax.nn.softplus(dt + w(p + "mixer.dt_bias"))  # [T, H]
    a = -jnp.exp(w(p + "mixer.A_log"))
    d = w(p + "mixer.D")

    def token(state, xs):  # the recurrence itself, one position at a time
        x_t, b_t, c_t, dt_t = xs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, hd, n), F32), (x, b, c, dt))
    g = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, groups, inner // groups)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps_of(raw))
    return (g.reshape(t, inner) * w(p + "mixer.norm.weight")) @ w(p + "mixer.out_proj.weight").T


def attention(w: Weights, p: str, raw: dict, u):
    t, heads, kvh = u.shape[0], int(raw["num_attention_heads"]), int(raw["num_key_value_heads"])
    hd = int(raw.get("head_dim") or int(raw["hidden_size"]) // heads)
    q = (u @ w(p + "mixer.q_proj.weight").T).reshape(t, heads, hd)
    k = jnp.repeat((u @ w(p + "mixer.k_proj.weight").T).reshape(t, kvh, hd), heads // kvh, axis=1)
    v = jnp.repeat((u @ w(p + "mixer.v_proj.weight").T).reshape(t, kvh, hd), heads // kvh, axis=1)
    scores = jnp.einsum("thd,jhd->htj", q, k) * hd ** -0.5
    visible = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("htj,jhd->thd", probs, v).reshape(t, heads * hd) @ w(
        p + "mixer.o_proj.weight").T


def held_experts(raw: dict) -> tuple[int, int, int]:
    """(first held, how many held, published)."""
    held = int(raw["n_routed_experts"])
    share = raw.get("expert_share") or {}
    return int(share.get("first", 0)), held, int(share.get("published", held))


def routing(w: Weights, p: str, raw: dict, u):
    """Combine weights [T, published]: zero off the chosen k."""
    scores = jax.nn.sigmoid(u @ w(p + "mixer.gate.weight").T)
    _, idx = jax.lax.top_k(scores + w(p + "mixer.gate.e_score_correction_bias"),
                           int(raw["num_experts_per_tok"]))
    vals = jnp.take_along_axis(scores, idx, axis=-1)
    if raw.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    vals = vals * float(raw.get("routed_scaling_factor", 1.0))
    return jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], idx].set(vals)


def routed_latent(w: Weights, p: str, raw: dict, u):
    """The held experts' part of the routed sum, in the experts' own width,
    expert by expert, each on the tokens that chose it."""
    first, held, _ = held_experts(raw)
    combine = np.asarray(routing(w, p, raw, u))
    lat = u @ w(p + "mixer.fc1_latent_proj.weight").T if raw.get("moe_latent_size") else u
    out = jnp.zeros_like(lat)
    for e in range(first, first + held):
        rows = np.nonzero(combine[:, e])[0]
        if rows.size:
            px = f"{p}mixer.experts.{e}."
            y = relu2(lat[rows] @ w(px + "up_proj.weight").T) @ w(px + "down_proj.weight").T
            out = out.at[rows].add(y * jnp.asarray(combine[rows, e])[:, None])
    return out


def experts(w: Weights, p: str, raw: dict, u):
    out = routed_latent(w, p, raw, u)
    if raw.get("moe_latent_size"):
        out = out @ w(p + "mixer.fc2_latent_proj.weight").T
    if raw.get("n_shared_experts"):
        out = out + relu2(u @ w(p + "mixer.shared_experts.up_proj.weight").T) @ w(
            p + "mixer.shared_experts.down_proj.weight").T
    return out


def dense(w: Weights, p: str, raw: dict, u):
    return relu2(u @ w(p + "mixer.up_proj.weight").T) @ w(p + "mixer.down_proj.weight").T


MIXERS = {"M": mamba, "E": experts, "*": attention, "-": dense}


def layer(w: Weights, raw: dict, i: int, x):
    p = f"backbone.layers.{i}."
    u = rms_norm(x, w(p + "norm.weight"), eps_of(raw))
    return x + MIXERS[raw["hybrid_override_pattern"][i]](w, p, raw, u)


def forward(params, raw: dict, tokens, cast=_same, cast_activations=_same, positions=None):
    """Logits [T, vocabulary held] in float32 of one sequence ``tokens``
    [T]; ``positions`` keeps only those rows of the last norm and the head."""
    check(raw)
    w = Weights(params, cast)
    with jax.default_matmul_precision("highest"):
        x = w("backbone.embeddings.weight")[jnp.asarray(tokens)]
        for i in range(int(raw["num_hidden_layers"])):
            x = cast_activations(layer(w, raw, i, x))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rms_norm(x, w("backbone.norm_f.weight"), eps_of(raw))
        return (x @ w("lm_head.weight").T).astype(F32)
