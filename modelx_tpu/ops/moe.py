"""Mixture-of-experts ops: top-k router + capacity-based expert dispatch.

TPU-first design (GShard/Switch recipe, the GSPMD-native MoE formulation):
expert weights are *stacked* along a leading E axis sharded over the ``ep``
mesh axis; token->expert dispatch is expressed as dense one-hot einsums with
a fixed per-expert capacity C, so every shape is static and XLA lowers the
dispatch/combine einsums to all-to-alls over ``ep`` while keeping each
expert's FFN matmuls local to its shard (and further tp-sharded within it).
No data-dependent control flow, no gather/scatter with dynamic shapes.

With ``capacity_factor`` large enough that C >= S*k/E at the observed
routing (tests use drop-free capacity), the math is exactly Mixtral's
renormalized top-k MoE; under pressure, overflow tokens are dropped
(combine weight 0) which is the standard capacity trade.

Reference parity note: the reference registry (kubegems/modelx) has no
models at all (SURVEY §2.2); this module exists for the TPU serving/training
path the build brief makes first-class.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from modelx_tpu.ops.nn import linear as _linear


def router_topk(router_logits: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Mixtral-style routing: softmax over experts, take top-k, renormalize.

    router_logits: [..., E]. Returns (probs [..., E] with zeros off the
    top-k and the top-k entries renormalized to sum 1, mask [..., E]).
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_vals, _ = jax.lax.top_k(probs, k)
    threshold = top_vals[..., k - 1 : k]
    mask = (probs >= threshold).astype(probs.dtype)
    # ties could admit >k experts; keep the formulation dense and renormalize
    kept = probs * mask
    return kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-9), mask


def expert_capacity(seq: int, num_experts: int, k: int, capacity_factor: float) -> int:
    """Static per-expert token budget C."""
    c = int(capacity_factor * seq * k / num_experts + 0.5)
    return max(1, min(seq, c))


def moe_ffn(
    x: jax.Array,
    gate_w: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    w3: jax.Array,
    *,
    top_k: int,
    capacity_factor: float = 0.0,
    constrain=None,
) -> jax.Array:
    """Sparse MoE FFN (SwiGLU experts), dense-dispatch formulation.

    x: [B, S, D]; gate_w: [E, D] (router, torch Linear layout);
    w1/w3: [E, F, D] (gate/up), w2: [E, D, F] (down) — stacked expert
    weights, E sharded over ``ep`` and F over ``tp`` by MIXTRAL_RULES.
    capacity_factor <= 0 means drop-free (C = S, exact Mixtral math).
    ``constrain(x, *axes)`` is ShardingCtx.constrain or None.
    """
    b, s, d = x.shape
    e = gate_w.shape[0]
    c = s if capacity_factor <= 0 else expert_capacity(s, e, top_k, capacity_factor)
    cons = constrain if constrain is not None else (lambda arr, *spec: arr)

    router_logits = jax.lax.dot_general(
        x, gate_w, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [B, S, E]
    probs, mask = router_topk(router_logits, top_k)

    # position of each token within its expert's capacity buffer
    pos = jnp.cumsum(mask, axis=1) * mask - 1.0  # [B, S, E], -1 where unrouted
    in_cap = (pos >= 0) & (pos < c)
    combine = jnp.where(in_cap, probs, 0.0)  # [B, S, E]
    # one-hot over the capacity slot: [B, S, E, C]
    slot = jax.nn.one_hot(jnp.where(in_cap, pos, -1).astype(jnp.int32), c, dtype=x.dtype)
    dispatch = slot * mask.astype(x.dtype)[..., None]

    # scatter tokens to expert buffers: [E, B, C, D] — the all-to-all edge
    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x, preferred_element_type=jnp.float32).astype(x.dtype)
    expert_in = cons(expert_in, "ep", "dp", None, None)

    # per-expert SwiGLU, batched over E (local to each ep shard, tp inside)
    gate = jnp.einsum("ebcd,efd->ebcf", expert_in, w1, preferred_element_type=jnp.float32).astype(x.dtype)
    up = jnp.einsum("ebcd,efd->ebcf", expert_in, w3, preferred_element_type=jnp.float32).astype(x.dtype)
    h = cons(jax.nn.silu(gate) * up, "ep", "dp", None, "tp")
    expert_out = jnp.einsum("ebcf,edf->ebcd", h, w2, preferred_element_type=jnp.float32).astype(x.dtype)
    expert_out = cons(expert_out, "ep", "dp", None, None)

    # gather back with the combine weights: [B, S, D]
    out = jnp.einsum(
        "bsec,ebcd->bsd", (combine[..., None] * slot).astype(x.dtype), expert_out,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    return cons(out, "dp", "sp", None)


def route_topk(router_logits: jax.Array, k: int, *, renormalize: bool = True,
               scale: float = 1.0, groups: tuple[int, int] | None = None) -> jax.Array:
    """Exactly-k routing over the router's whole width: softmax over all E
    logits in float32, the k largest, their probabilities divided by their
    own sum (``renormalize``, HF ``norm_topk_prob``) and multiplied by
    ``scale`` (``moe_routed_scaling_factor``). router_logits: [T, E].
    Returns the combine weights [T, E], zero off the k chosen. Unlike
    :func:`router_topk` a tie never admits a (k+1)-th expert.

    ``groups`` ``(n_group, topk_group)`` is the group-limited routing of a
    deployment that keeps each group on one device (HF ``topk_method``
    ``group_limited_greedy``): the E experts are ``n_group`` runs of ``E /
    n_group`` neighbours (expert e lies in group ``e // (E / n_group)``), a
    group's score is the LARGEST probability among its experts, only the
    ``topk_group`` best groups stay eligible (a tie between groups goes to the
    lower index, as ``top_k``'s does), and the k experts are the best inside
    them — a token whose best expert lies in a dropped group does without it.
    The probabilities are those of the softmax over all E either way."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    eligible = probs
    if groups is not None:
        n_group, topk_group = groups
        t, e = probs.shape
        if e % n_group or not 0 < topk_group <= n_group:
            raise ValueError(f"{e} experts do not fall into {n_group} groups of which "
                             f"{topk_group} are kept")
        best = jnp.max(probs.reshape(t, n_group, e // n_group), axis=-1)  # [T, n_group]
        _, kept = jax.lax.top_k(best, topk_group)
        keep = jnp.zeros((t, n_group), bool).at[jnp.arange(t)[:, None], kept].set(True)
        # an ineligible expert sorts below every probability, zero included
        eligible = jnp.where(jnp.repeat(keep, e // n_group, axis=1), probs, -1.0)
    vals, idx = jax.lax.top_k(eligible, k)
    if renormalize:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    rows = jnp.arange(probs.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(vals * scale)


def moe_share_ffn(
    x: jax.Array,
    router_w: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int,
    held: tuple[int, int] | None = None,
    renormalize: bool = True,
    routed_scale: float = 1.0,
    shared: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    constrain=None,
    scopes: tuple[str, ...] = ("moe.routed", "moe.shared"),
    groups: tuple[int, int] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """An expert layer that is told which experts it holds.

    x: [B, S, D]; router_w: [E_pub, D], the router at its PUBLISHED width;
    w_gate / w_up: [E_held, F, D], w_down: [E_held, D, F] — the stacked
    SwiGLU experts this device holds, experts ``first .. first + count`` of
    the published ``E_pub`` (``held = (first, count)``; None = all of them).
    Routing is over all ``E_pub`` experts: top-k of the full softmax,
    normalised over all k chosen, times ``routed_scale``; ``groups`` limits
    the choice to the best groups of neighbours (:func:`route_topk`) — the
    held run of experts is then usually one of them. Only the held
    experts' part of the sum is computed — what the absent experts would add
    is another device's, and nothing here stands in for it. ``shared``
    (gate, up, down in torch Linear layout) is an always-on SwiGLU expert
    added ungated beside the routed sum.

    Drop-free and exact: every held expert runs on every token and the
    combine weight (zero where the router did not choose it) picks its part,
    so no capacity, sort or dynamic shape is involved. At decode the layer is
    bound by reading the held experts' weights, which this formulation reads
    once each; at prefill it spends E_held/k times the arithmetic a grouped
    product over sorted tokens would (ROADMAP R1).

    Returns (out [B, S, D], counts int32 [3]): token-expert pairs routed,
    those that landed on a held expert, and distinct held experts hit.
    """
    b, s, d = x.shape
    e_pub = router_w.shape[0]
    first, count = held if held is not None else (0, e_pub)
    if count != w_gate.shape[0] or first < 0 or first + count > e_pub:
        raise ValueError(
            f"held experts {first}..{first + count} of {e_pub} do not match the "
            f"{w_gate.shape[0]} stacked expert weights given")
    cons = constrain if constrain is not None else (lambda arr, *spec: arr)
    t = x.reshape(b * s, d)
    f32 = jnp.float32
    # a third scope, where given, names the routing apart from the experts' products
    with jax.named_scope(scopes[2] if len(scopes) > 2 else scopes[0]):
        logits = jax.lax.dot_general(t, router_w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)  # [T, E_pub]
        combine = route_topk(logits, top_k, renormalize=renormalize, scale=routed_scale,
                             groups=groups)
        here = jax.lax.slice_in_dim(combine, first, first + count, axis=1)  # [T, E_held]
        hit = here > 0
        counts = jnp.stack([jnp.int32(b * s * top_k), jnp.sum(hit, dtype=jnp.int32),
                            jnp.sum(jnp.any(hit, axis=0), dtype=jnp.int32)])
    with jax.named_scope(scopes[0]):
        g = jnp.einsum("td,efd->etf", t, w_gate, preferred_element_type=f32).astype(x.dtype)
        u = jnp.einsum("td,efd->etf", t, w_up, preferred_element_type=f32).astype(x.dtype)
        # the combine weight goes on the hidden activation, so that the down
        # projection contracts experts and features at once ([T, E*F] x
        # [E*F, D]) and no [E, T, D] block of per-expert outputs exists
        h = (jax.nn.silu(g) * u).astype(f32) * here.T[:, :, None]
        h = cons(h.astype(x.dtype), "ep", None, "tp")
        out = jnp.einsum("etf,edf->td", h, w_down, preferred_element_type=f32)
    if shared is not None:
        with jax.named_scope(scopes[1]):
            sg, su, sd = shared
            hs = jax.nn.silu(_linear(t, sg)) * _linear(t, su)
            out = out + jax.lax.dot_general(hs, sd, (((1,), (1,)), ((), ())),
                                            preferred_element_type=f32)
    return cons(out.astype(x.dtype).reshape(b, s, d), "dp", "sp", None), counts


def load_balancing_loss(router_logits: jax.Array, mask: jax.Array) -> jax.Array:
    """Switch-style auxiliary load-balancing loss: E * sum_e f_e * p_e,
    where f_e = fraction of tokens routed to expert e, p_e = mean router
    probability. router_logits/mask: [..., E]."""
    e = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    probs = probs.reshape(-1, e)
    frac = mask.reshape(-1, e).astype(jnp.float32)
    return e * jnp.sum(jnp.mean(frac, 0) * jnp.mean(probs, 0))
