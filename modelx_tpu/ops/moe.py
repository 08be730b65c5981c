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

A device that holds a SHARE of a layer's experts runs
:func:`moe_share_ffn` instead: routing over the published width (softmax
scores, or sigmoid ones with a bias that only chooses), the held experts' part
of the sum only — gated SwiGLU experts or two matrices with a squared relu
between them, in the hidden width or in a latent one between a down- and an
up-projection. Its routed sum has two lowerings of one
algorithm whose cost differs with the shape (:func:`lowering`: no flag, no
option, no model's name): thousands of rows hit every held expert and are
bound by arithmetic — the dense einsums, every expert on every token; a decode
step's handful of rows is bound by reading the experts' weights, of which some
no row chose — :func:`hit_experts`, a Pallas kernel that takes the hit
experts' indices as prefetched scalars and reads those experts only (what a
decode step reads: ``counts[3]`` of them, the hit ones, where the einsums read
every held one).

Reference parity note: the reference registry (kubegems/modelx) has no
models at all (SURVEY §2.2); this module exists for the TPU serving/training
path the build brief makes first-class.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from modelx_tpu.ops.nn import linear as _linear
from modelx_tpu.utils import trace

# a matrix of an expert is whole tiles where both its sides are multiples of a
# tile's 128 lanes (and so of its sublanes); the rows of a step, of 8 sublanes
LANES, SUBLANES = 128, 8
# rows x [F, D] in bf16 is T FLOP a byte of weights: under the v5e's ridge (197
# TFLOP/s over 819 GB/s = 240) the product waits for the weights, and the
# kernel's row blocks and float32 sum [T, D] fit in VMEM beside them
ROWS_MAX = 256
# the kernel's blocks: the largest run of a matrix's rows under this many bytes.
# Three operands, double-buffered: six such blocks lie in VMEM (of 128 MiB)
BLOCK_BYTES = 8 << 20


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
               scale: float = 1.0, groups: tuple[int, int] | None = None,
               scoring: str = "softmax", choice_bias: jax.Array | None = None) -> jax.Array:
    """Exactly-k routing over the router's whole width: softmax over all E
    logits in float32, the k largest, their probabilities divided by their
    own sum (``renormalize``, HF ``norm_topk_prob``) and multiplied by
    ``scale`` (``moe_routed_scaling_factor``). router_logits: [T, E].
    Returns the combine weights [T, E], zero off the k chosen. Unlike
    :func:`router_topk` a tie never admits a (k+1)-th expert (the lower index
    wins, ``top_k``'s rule).

    ``scoring`` ``"sigmoid"`` scores each expert by itself (``sigmoid`` of its
    logit, in float32) in place of the softmax over all. ``choice_bias``
    ``[E]`` (HF ``e_score_correction_bias``) is added to the scores for the
    CHOICE of the k alone: the combine weights are the unbiased scores of the
    chosen, so a bias moves which experts a token gets and never how much of
    them.

    ``groups`` ``(n_group, topk_group)`` is the group-limited routing of a
    deployment that keeps each group on one device (HF ``topk_method``
    ``group_limited_greedy``): the E experts are ``n_group`` runs of ``E /
    n_group`` neighbours (expert e lies in group ``e // (E / n_group)``), a
    group's score is the LARGEST probability among its experts, only the
    ``topk_group`` best groups stay eligible (a tie between groups goes to the
    lower index, as ``top_k``'s does), and the k experts are the best inside
    them — a token whose best expert lies in a dropped group does without it.
    The probabilities are those of the softmax over all E either way.
    Sigmoid scores with a choice bias group otherwise (HF ``topk_method``
    ``noaux_tc``): a group's score is the SUM of its two largest BIASED
    scores, and the k are the largest biased scores inside the groups kept.
    Softmax with a bias, or sigmoid without one, is nobody's grouping: refused."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring {scoring!r} is neither softmax nor sigmoid")
    if scoring == "softmax":
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    else:
        probs = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    eligible = probs if choice_bias is None else probs + choice_bias.astype(jnp.float32)
    if groups is not None:
        noaux = scoring == "sigmoid" and choice_bias is not None
        if not noaux and (scoring != "softmax" or choice_bias is not None):
            raise ValueError("group-limited routing is implemented over plain softmax scores "
                             "(a group's best expert) and over sigmoid scores with a choice "
                             "bias (the sum of a group's two best)")
        n_group, topk_group = groups
        t, e = probs.shape
        if e % n_group or not 0 < topk_group <= n_group:
            raise ValueError(f"{e} experts do not fall into {n_group} groups of which "
                             f"{topk_group} are kept")
        if noaux:
            best = jnp.sum(jax.lax.top_k(
                eligible.reshape(t, n_group, e // n_group), 2)[0], axis=-1)
        else:
            best = jnp.max(probs.reshape(t, n_group, e // n_group), axis=-1)  # [T, n_group]
        _, kept = jax.lax.top_k(best, topk_group)
        keep = jnp.zeros((t, n_group), bool).at[jnp.arange(t)[:, None], kept].set(True)
        # an ineligible expert sorts below every score: a probability is zero
        # at least, a biased score anything
        eligible = jnp.where(jnp.repeat(keep, e // n_group, axis=1), eligible,
                             -jnp.inf if noaux else -1.0)
    vals, idx = jax.lax.top_k(eligible, k)
    if choice_bias is not None:
        vals = jnp.take_along_axis(probs, idx, axis=-1)
    if renormalize:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    rows = jnp.arange(probs.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(vals * scale)


def lowering(x_shape: tuple, w_shape: tuple, mesh=None) -> str:
    """``"kernel"`` or ``"einsum"`` for the routed part of
    :func:`moe_share_ffn` on ``x`` ``[B, S, D]`` over stacked experts
    ``[E_held, F, D]`` — from shapes, the backend and the mesh alone, as
    ``ops.kv_write.lowering``. The kernel (:func:`hit_experts`): a decode step
    (``S == 1``) of at most :data:`ROWS_MAX` rows (bound by reading the
    experts, of which some are unhit), ``D`` and ``F`` whole lane tiles and
    the rows whole sublane tiles, the TPU backend, one device (a bare Mosaic
    call cannot be partitioned). Everything else — an admission, a prefill
    piece, a teacher-forced forward, hundreds or thousands of rows that hit
    every expert and are bound by arithmetic, a mesh, the CPU — is the
    einsums, lowered as ever."""
    (b, s, d), f = x_shape, w_shape[1]
    if (s == 1 and b <= ROWS_MAX and b % SUBLANES == 0 and d % LANES == 0 and f % LANES == 0
            and jax.default_backend() == "tpu" and (mesh is None or mesh.size == 1)):
        return "kernel"
    return "einsum"


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
    mesh=None,
    scoring: str = "softmax",
    choice_bias: jax.Array | None = None,
    form: str = "swiglu",
    latent: tuple[jax.Array, jax.Array] | None = None,
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

    Three static arguments say what kind of layer it is (the defaults are the
    layer above). ``scoring`` / ``choice_bias``: the router's scores
    (:func:`route_topk`: ``"sigmoid"``, and a bias that only chooses; with
    ``groups`` the two together group as ``noaux_tc``).
    ``form`` ``"relu2"``: an expert is TWO matrices with a squared relu
    between them, ``w_down relu(w_up x)^2`` — ``w_gate`` is None, and so is
    ``shared``'s gate. ``latent`` ``(down [L, D], up [D, L])``: the experts
    live in a width ``L`` of their own — every token goes through ``down``
    before them and the routed sum through ``up`` after them (``w_up``
    ``[E_held, F, L]``, ``w_down`` ``[E_held, L, F]``), while the router and
    the shared expert read the hidden state itself. The chip up-projects its
    own partial sum; scopes four and five name the two projections.

    Drop-free and exact, with no capacity, sort or dynamic shape, in two
    lowerings of one sum (:func:`lowering` picks, from the shapes, the backend
    and ``mesh``). The einsums run every held expert on every token and the
    combine weight (zero where the router did not choose it) picks its part:
    at prefill that spends E_held/k times the arithmetic a grouped product
    over sorted tokens would (ROADMAP R1). A decode step on one TPU device is
    bound by reading the experts' weights and takes :func:`hit_experts`, which
    reads only the held experts some row of the step chose: one no row chose
    has weight zero on every token and adds exactly zero.

    Returns (out [B, S, D], counts int32 [4]): token-expert pairs routed,
    those that landed on a held expert, distinct held experts hit, and held
    experts whose weights the step read (the hit ones in the kernel, all of
    them in the einsums).
    """
    b, s, d = x.shape
    e_pub = router_w.shape[0]
    first, count = held if held is not None else (0, e_pub)
    if count != w_up.shape[0] or first < 0 or first + count > e_pub:
        raise ValueError(
            f"held experts {first}..{first + count} of {e_pub} do not match the "
            f"{w_up.shape[0]} stacked expert weights given")
    if form not in ("swiglu", "relu2") or (w_gate is None) != (form == "relu2"):
        raise ValueError(f"expert form {form!r} with{'out' if w_gate is None else ''} a gate")
    cons = constrain if constrain is not None else (lambda arr, *spec: arr)
    t = x.reshape(b * s, d)
    f32 = jnp.float32
    # the width the experts read is their matrices': a latent's, else the tokens' own
    how = lowering((b, s, w_up.shape[2]), w_up.shape, mesh)
    with trace.span(f"moe.{how}[{b * s}x{count}]"):
        pass  # at TRACE time, once a call site: which lowering a program compiled with
    # a third scope, where given, names the routing apart from the experts' products
    with jax.named_scope(scopes[2] if len(scopes) > 2 else scopes[0]):
        logits = jax.lax.dot_general(t, router_w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)  # [T, E_pub]
        combine = route_topk(logits, top_k, renormalize=renormalize, scale=routed_scale,
                             groups=groups, scoring=scoring, choice_bias=choice_bias)
        here = jax.lax.slice_in_dim(combine, first, first + count, axis=1)  # [T, E_held]
        hit = here > 0
        n_hit = jnp.sum(jnp.any(hit, axis=0), dtype=jnp.int32)
        counts = jnp.stack([jnp.int32(b * s * top_k), jnp.sum(hit, dtype=jnp.int32), n_hit,
                            n_hit if how == "kernel" else jnp.int32(count)])
    tokens = t
    if latent is not None:
        with jax.named_scope(scopes[3] if len(scopes) > 3 else scopes[0]):
            tokens = _linear(t, latent[0])  # [T, L]
    with jax.named_scope(scopes[0]):
        if how == "kernel":
            # off the TPU only a test that steers ``lowering`` comes here
            out = hit_experts(tokens, here, w_gate, w_up, w_down,
                              interpret=jax.default_backend() != "tpu")
        else:
            out = every_expert(tokens, here, w_gate, w_up, w_down, cons)
    if latent is not None:
        with jax.named_scope(scopes[4] if len(scopes) > 4 else scopes[0]):
            out = jax.lax.dot_general(out.astype(x.dtype), latent[1], (((1,), (1,)), ((), ())),
                                      preferred_element_type=f32)
    if shared is not None:
        with jax.named_scope(scopes[1]):
            sg, su, sd = shared
            if form == "relu2":
                hs = jnp.square(jax.nn.relu(_linear(t, su)))
            else:
                hs = jax.nn.silu(_linear(t, sg)) * _linear(t, su)
            out = out + jax.lax.dot_general(hs, sd, (((1,), (1,)), ((), ())),
                                            preferred_element_type=f32)
    return cons(out.astype(x.dtype).reshape(b, s, d), "dp", "sp", None), counts


def every_expert(t, here, w_gate, w_up, w_down, constrain=None):
    """The routed sum of :func:`moe_share_ffn` as dense einsums: every held
    expert on every token, the combine weights ``here`` ``[T, E_held]`` (zero
    where a row did not choose an expert) picking its part. Operands as
    :func:`hit_experts`'; ``w_gate`` None: experts of two matrices with a
    squared relu between them. Returns ``[T, D]`` float32."""
    f32 = jnp.float32
    if w_gate is not None:
        g = jnp.einsum("td,efd->etf", t, w_gate, preferred_element_type=f32).astype(t.dtype)
    u = jnp.einsum("td,efd->etf", t, w_up, preferred_element_type=f32).astype(t.dtype)
    act = jnp.square(jax.nn.relu(u)) if w_gate is None else jax.nn.silu(g) * u
    # the combine weight goes on the hidden activation, so that the down
    # projection contracts experts and features at once ([T, E*F] x
    # [E*F, D]) and no [E, T, D] block of per-expert outputs exists
    h = (act.astype(f32) * here.T[:, :, None]).astype(t.dtype)
    if constrain is not None:
        h = constrain(h, "ep", None, "tp")
    return jnp.einsum("etf,edf->td", h, w_down, preferred_element_type=f32)


def _chunks(rows: int, row_bytes: int) -> int:
    """Into how many equal runs of whole lane tiles a matrix's ``rows`` go so
    that one run is at most :data:`BLOCK_BYTES` (or one tile of rows)."""
    tiles = max(rows // LANES, 1)
    return next(n for n in range(1, tiles + 1)
                if n == tiles or tiles % n == 0 and rows // n * row_bytes <= BLOCK_BYTES)


def _hit_experts_kernel(ids_ref, n_ref, x_ref, here_ref, *refs, n_f: int, n_d: int,
                        gated: bool):
    """Grid ``(place, step)``: place ``i`` is the ``i``-th hit expert
    (``ids_ref[i]``), its steps the ``n_f`` runs of the gate's rows (a gated
    expert's only), the ``n_f`` of the up's, the ``n_d`` of the down's — one
    block of weights a step, the next one on its way meanwhile. ``out_ref``
    ``[T, D]`` float32 stays in VMEM over the whole grid and is written home
    once."""
    if gated:
        gate_ref, up_ref, down_ref, out_ref, g_ref, h_ref = refs
    else:
        up_ref, down_ref, out_ref, h_ref = refs
    place, step = pl.program_id(0), pl.program_id(1)
    live = place < n_ref[0]
    nt = (((1,), (1,)), ((), ()))  # rows x [N, K]: both contract their last axis
    fc, dc = h_ref.shape[1] // n_f, out_ref.shape[1] // n_d
    ups = n_f if gated else 0  # the step the up's runs start at
    f32 = jnp.float32

    @pl.when((place == 0) & (step == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    for c in range(n_f):
        if gated:
            @pl.when(live & (step == c))
            def _(c=c):
                g = jax.lax.dot_general(x_ref[...], gate_ref[0], nt, preferred_element_type=f32)
                g_ref[:, c * fc:(c + 1) * fc] = g.astype(g_ref.dtype)

        @pl.when(live & (step == ups + c))
        def _(c=c):
            u = jax.lax.dot_general(x_ref[...], up_ref[0], nt, preferred_element_type=f32)
            # the expert's column of the combine weights, picked by a mask: [T, 1]
            lane = jax.lax.broadcasted_iota(jnp.int32, here_ref.shape, 1)
            w = jnp.sum(jnp.where(lane == ids_ref[place], here_ref[...], 0.0), axis=1,
                        keepdims=True)
            # the einsums' roundings, one an operation: g, u, silu(g), their product
            # (two matrices: u, relu(u), its square)
            dtype = h_ref.dtype
            if gated:
                act = jax.nn.silu(g_ref[:, c * fc:(c + 1) * fc].astype(f32)).astype(dtype)
                act = (act.astype(f32) * u.astype(dtype).astype(f32)).astype(dtype)
            else:
                act = jax.nn.relu(u.astype(dtype)).astype(f32)
                act = (act * act).astype(dtype)
            h_ref[:, c * fc:(c + 1) * fc] = (act.astype(f32) * w).astype(dtype)

    for c in range(n_d):
        @pl.when(live & (step == ups + n_f + c))
        def _(c=c):
            out_ref[:, c * dc:(c + 1) * dc] += jax.lax.dot_general(
                h_ref[...], down_ref[0], nt, preferred_element_type=f32)


def hit_experts(t, here, w_gate, w_up, w_down, *, interpret: bool = False):
    """The routed sum of :func:`moe_share_ffn` over the held experts some row
    chose, as one Pallas kernel. t ``[T, D]``; here ``[T, E_held]`` float32,
    the combine weights (zero where a row did not choose an expert); w_gate /
    w_up ``[E_held, F, D]``, w_down ``[E_held, D, F]``. Returns ``[T, D]``
    float32: sum over experts of ``(silu(t g^T) * (t u^T) * here[:, e]) d^T``
    — ``w_gate`` None: of ``(relu(t u^T)^2 * here[:, e]) d^T``, experts of two
    matrices — bf16 operands and float32 accumulation as the einsums have
    them, the experts' parts added in float32 in the order of their indices.

    The hit experts' indices go first in a list of ``E_held`` places (the
    tail repeats the last one) and reach the kernel as prefetched scalars
    beside their count: the weights' index maps read a place's expert from
    the list, so a place past the count asks for the block that is already
    there — no copy — and ``pl.when`` skips its arithmetic. Blocks are runs
    of a matrix's rows, contiguous in HBM, of up to :data:`BLOCK_BYTES`,
    double-buffered by the pipeline; each operand's index moves one step
    before the step that needs it, so one copy is in flight at any time."""
    rows, d = t.shape
    e, f = w_up.shape[:2]
    item = w_up.dtype.itemsize
    gated = w_gate is not None
    n_f, n_d = _chunks(f, d * item), _chunks(d, f * item)
    ups = n_f if gated else 0
    steps = ups + n_f + n_d
    hit = jnp.any(here > 0, axis=0)
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    ids = jnp.where(jnp.arange(e) < n_hit, order, order[jnp.maximum(n_hit - 1, 0)])

    def block(first_step: int, n: int):
        """The index map of an operand used at steps ``first_step ..
        first_step + n`` of each place: before them it stays where the place
        before left it (at place 0, where it will start), so that its copy
        for this place is asked for at the step before the first use; past the
        live places, where the last live step left it."""
        def index(place, step, ids, n_hit):
            step = jnp.where(place < n_hit[0], step, steps - 1)
            early = step < first_step
            expert = ids[jnp.where(early, jnp.maximum(place - 1, 0), place)]
            chunk = jnp.where(early, jnp.where(place == 0, 0, n - 1),
                              jnp.minimum(step - first_step, n - 1))
            return expert, chunk, 0
        return index

    whole = lambda place, step, ids, n_hit: (0, 0)  # noqa: E731
    block_bytes = max(f // n_f * d, d // n_d * f) * item
    weights = [pl.BlockSpec((1, f // n_f, d), block(ups, n_f)),
               pl.BlockSpec((1, d // n_d, f), block(ups + n_f, n_d))]
    if gated:
        weights.insert(0, pl.BlockSpec((1, f // n_f, d), block(0, n_f)))
    return pl.pallas_call(
        functools.partial(_hit_experts_kernel, n_f=n_f, n_d=n_d, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(e, steps),
            in_specs=[pl.BlockSpec((rows, d), whole), pl.BlockSpec((rows, e), whole), *weights],
            out_specs=pl.BlockSpec((rows, d), whole),
            scratch_shapes=[pltpu.VMEM((rows, f), t.dtype)] * (2 if gated else 1)),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=6 * block_bytes + (16 << 20)),
        interpret=interpret, name="moe_hit_experts",
    )(ids, n_hit[None], t, here.astype(jnp.float32),
      *((w_gate, w_up, w_down) if gated else (w_up, w_down)))


def load_balancing_loss(router_logits: jax.Array, mask: jax.Array) -> jax.Array:
    """Switch-style auxiliary load-balancing loss: E * sum_e f_e * p_e,
    where f_e = fraction of tokens routed to expert e, p_e = mean router
    probability. router_logits/mask: [..., E]."""
    e = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    probs = probs.reshape(-1, e)
    frac = mask.reshape(-1, e).astype(jnp.float32)
    return e * jnp.sum(jnp.mean(frac, 0) * jnp.mean(probs, 0))
