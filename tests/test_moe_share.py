"""The expert layer that is told which experts it holds (ops/moe.moe_share_ffn),
against the plain reference layer (models/laguna_reference.py)."""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.continuous import ContinuousBatcher
from modelx_tpu.dl.serve import ModelServer
from modelx_tpu.models import laguna, laguna_reference as reference
from modelx_tpu.ops import moe
from modelx_tpu.parallel.mesh import make_mesh

P = "model.layers.1."


@pytest.fixture(scope="module")
def layer():
    """One sparse layer of the tiny config, its input, and the reference's
    uncut answer for the whole layer (routed sum + shared expert)."""
    cfg = laguna.LagunaConfig.tiny(vocab_size=64)
    params = laguna.init_params(cfg, jax.random.PRNGKey(3))
    raw = laguna.to_hf_config(cfg)
    m = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.hidden_size), jnp.float32)
    w = reference.Weights(laguna.to_hf_state_dict(params))
    flat = m.reshape(-1, cfg.hidden_size)
    with jax.default_matmul_precision("highest"):
        whole = reference.routed_experts(w, P, raw, flat) + reference.swiglu(
            w, P + "mlp.shared_expert.", flat)
    return cfg, params, raw, m, np.asarray(whole).reshape(m.shape)


def share(cfg, params, m, first, count, with_shared):
    sl = slice(first, first + count)
    shared = tuple(params[P + f"mlp.shared_expert.{n}_proj.weight"]
                   for n in ("gate", "up", "down")) if with_shared else None
    return moe.moe_share_ffn(
        m, params[P + "mlp.gate.weight"], params[P + "mlp.experts.gate_proj.weight"][sl],
        params[P + "mlp.experts.up_proj.weight"][sl], params[P + "mlp.experts.down_proj.weight"][sl],
        top_k=cfg.top_k, held=(first, count), routed_scale=cfg.routed_scale, shared=shared)


@pytest.mark.parametrize("cuts", [[(0, 16)], [(0, 8), (8, 8)], [(0, 4), (4, 4), (8, 8)],
                                  [(0, 1), (1, 15)]])
def test_the_shares_parts_add_up_to_the_uncut_reference_layer(layer, cuts):
    """THE share test: what every share's held experts give, with the shared
    expert — which every chip computes alike — counted once, is the whole
    layer as the reference computes it uncut."""
    cfg, params, _, m, whole = layer
    total = sum(share(cfg, params, m, first, count, with_shared=i == 0)[0]
                for i, (first, count) in enumerate(cuts))
    np.testing.assert_allclose(np.asarray(total), whole, atol=2e-5, rtol=1e-5)


def test_a_share_alone_is_not_the_layer(layer):
    """The guard of the test above: half the experts give another answer."""
    cfg, params, _, m, whole = layer
    half = np.asarray(share(cfg, params, m, 0, 8, with_shared=True)[0])
    assert np.abs(half - whole).max() > 1e-2


def test_the_counters_count_pairs_routed_pairs_held_and_experts_hit(layer):
    cfg, params, raw, m, _ = layer
    w = reference.Weights(laguna.to_hf_state_dict(params))
    combine = np.asarray(reference.routing(w, P, raw, m.reshape(-1, cfg.hidden_size)))
    tokens = combine.shape[0]
    assert ((combine > 0).sum(-1) == cfg.top_k).all()
    for first, count in [(0, 16), (0, 8), (8, 8), (5, 3)]:
        counts = np.asarray(share(cfg, params, m, first, count, with_shared=False)[1])
        here = combine[:, first:first + count] > 0
        # off a decode step on one TPU device every held expert's weights are read
        assert counts.tolist() == [tokens * cfg.top_k, int(here.sum()), int(here.any(0).sum()),
                                   count]


@pytest.mark.parametrize("renormalize,scale", [(True, 2.5), (True, 1.0), (False, 1.0)])
def test_route_topk_takes_exactly_k_and_normalises_over_all_k(renormalize, scale):
    logits = jax.random.normal(jax.random.PRNGKey(0), (50, 32))
    logits = logits.at[:, 1].set(logits[:, 0])  # a tie must not admit a (k+1)-th expert
    got = np.asarray(moe.route_topk(logits, 4, renormalize=renormalize, scale=scale))
    probs = np.asarray(jax.nn.softmax(logits, -1))
    assert ((got > 0).sum(-1) == 4).all()
    chosen = np.sort(np.where(got > 0, probs, 0), -1)[:, -4:]
    np.testing.assert_allclose(chosen, np.sort(probs, -1)[:, -4:], rtol=1e-6)
    want = scale if renormalize else chosen.sum(-1) * scale
    np.testing.assert_allclose(got.sum(-1), want, rtol=1e-5)


@pytest.mark.parametrize("held", [(0, 7), (9, 8), (-1, 8)])
def test_held_experts_must_match_the_weights_given(layer, held):
    cfg, params, _, m, _ = layer
    with pytest.raises(ValueError, match="held experts"):
        moe.moe_share_ffn(
            m, params[P + "mlp.gate.weight"], params[P + "mlp.experts.gate_proj.weight"][:8],
            params[P + "mlp.experts.up_proj.weight"][:8],
            params[P + "mlp.experts.down_proj.weight"][:8], top_k=cfg.top_k, held=held)


def test_mixtrals_layer_is_the_one_it_was():
    """ops/moe.moe_ffn is not routed through the new function: drop-free it
    still equals a dense top-2 mixture written out by hand."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 5, 16).astype(np.float32))
    gate, w1, w3 = (jnp.asarray(rng.randn(*s).astype(np.float32) / 4)
                    for s in [(4, 16), (4, 24, 16), (4, 24, 16)])
    w2 = jnp.asarray(rng.randn(4, 16, 24).astype(np.float32) / 4)
    got = moe.moe_ffn(x, gate, w1, w2, w3, top_k=2)
    probs = jax.nn.softmax(x @ gate.T, -1)
    top = jax.lax.top_k(probs, 2)[1]
    want = jnp.zeros_like(x)
    for e in range(4):
        y = (jax.nn.silu(x @ w1[e].T) * (x @ w3[e].T)) @ w2[e].T
        wgt = jnp.where((top == e).any(-1), probs[..., e], 0.0) / jnp.take_along_axis(
            probs, top, -1).sum(-1)
        want = want + y * wgt[..., None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- group-limited routing (route_topk's ``groups``) --------------------------------


def grouped_by_hand(probs: np.ndarray, k: int, n_group: int, topk_group: int, scale: float):
    """The published rule, a token at a time, written out: a group's score is
    its best expert's; the ``topk_group`` best groups stay (ties to the lower
    index); the k best experts inside them (ties to the lower index) get
    ``scale`` times their own probability, not renormalised."""
    t, e = probs.shape
    size, out = e // n_group, np.zeros_like(probs)
    for row in range(t):
        best = [probs[row, g * size:(g + 1) * size].max() for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-best[g], g))[:topk_group]
        eligible = [x for g in kept for x in range(g * size, (g + 1) * size)]
        for x in sorted(eligible, key=lambda x: (-probs[row, x], x))[:k]:
            out[row, x] = scale * probs[row, x]
    return out


def test_group_limited_routing_is_the_loop_written_out():
    logits = np.array(jax.random.normal(jax.random.PRNGKey(7), (64, 16)) * 2.0)
    logits[0, 5] = logits[0, 9]  # two experts of different groups tie
    logits[1, :] = 0.0  # every expert and every group ties: the lowest indices win
    # token 3: groups 1 and 2 hold the two best experts; expert 0, the third best of all,
    # lies in group 0, which is dropped; group 3 holds the next three
    logits[3] = np.array([3.0, 0, 0, 0, 4.0, 0, 0, 0, 5.0, 0, 0, 0, 2.0, 2.9, 2.8, 2.7])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    got = np.asarray(moe.route_topk(jnp.asarray(logits), 3, renormalize=False, scale=4.0,
                                    groups=(4, 2)))
    np.testing.assert_allclose(got, grouped_by_hand(probs, 3, 4, 2, 4.0), rtol=1e-6)
    assert ((got > 0).sum(-1) == 3).all()
    # token 3: groups 2 and 1 are kept (5.0, 4.0); expert 0 (3.0), the third best of all,
    # lies in dropped group 0 and is done without; the third pick comes from a kept group
    assert got[3, 8] > 0 and got[3, 4] > 0 and got[3, 0] == 0 and got[3, 12:].sum() == 0
    assert set(np.nonzero(got[1])[0]) == {0, 1, 2}
    # without groups the same token takes expert 0
    plain = np.asarray(moe.route_topk(jnp.asarray(logits), 3, renormalize=False, scale=4.0))
    assert plain[3, 0] > 0


def noaux_by_hand(logits: np.ndarray, bias: np.ndarray, k: int, n_group: int, topk_group: int,
                  scale: float):
    """``noaux_tc`` a token at a time, from the equations: s = sigmoid(logit),
    c = s + bias; a group's score the sum of its two largest c; the
    ``topk_group`` best groups stay; the k largest c inside them are chosen
    (ties to the lower index, both times); g = s / sum(s of the chosen) x scale."""
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    c = s + bias
    t, e = s.shape
    size, out = e // n_group, np.zeros_like(s)
    for row in range(t):
        score = [np.sort(c[row, g * size:(g + 1) * size])[-2:].sum() for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-score[g], g))[:topk_group]
        eligible = [x for g in kept for x in range(g * size, (g + 1) * size)]
        chosen = sorted(eligible, key=lambda x: (-c[row, x], x))[:k]
        total = sum(s[row, x] for x in chosen)
        for x in chosen:
            out[row, x] = s[row, x] / total * scale
    return out


def test_sigmoid_bias_and_groups_by_the_sum_of_two_is_the_loop_written_out():
    logits = np.array(jax.random.normal(jax.random.PRNGKey(11), (64, 32)) * 2.0)
    bias = np.array(jax.random.normal(jax.random.PRNGKey(12), (32,)) * 0.4)
    logits[1, :] = 0.0  # every expert's score ties: the bias alone chooses
    # token 2: group 0 holds the single best expert and nothing else, groups 1 and 2 two
    # good ones each: by the sum of two, group 0 is dropped and its best expert with it
    logits[2] = -6.0
    logits[2, 0], logits[2, 8:10], logits[2, 16:18] = 6.0, 3.0, 2.5
    got = np.asarray(moe.route_topk(jnp.asarray(logits), 3, renormalize=True, scale=2.5,
                                    groups=(4, 2), scoring="sigmoid",
                                    choice_bias=jnp.asarray(bias)))
    want = noaux_by_hand(logits, bias, 3, 4, 2, 2.5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    assert ((got > 0).sum(-1) == 3).all()
    np.testing.assert_allclose(got.sum(-1), 2.5, rtol=1e-5)
    flat = np.asarray(moe.route_topk(jnp.asarray(logits), 3, renormalize=True, scale=2.5,
                                     groups=(4, 2), scoring="sigmoid",
                                     choice_bias=jnp.zeros(32)))
    np.testing.assert_allclose(flat, noaux_by_hand(logits, np.zeros(32), 3, 4, 2, 2.5),
                               rtol=1e-5, atol=1e-7)
    assert flat[2, 0] == 0 and flat[2, 8] > 0 and flat[2, 16] > 0
    # a group's best expert alone (V2's rule) would have kept group 0
    assert (1 / (1 + np.exp(-logits[2]))).reshape(4, 8).max(-1).argmax() == 0
    # the bias moves which experts a token gets and never how much of them
    chosen = got[5] > 0
    s5 = 1 / (1 + np.exp(-logits[5]))
    np.testing.assert_allclose(got[5, chosen], s5[chosen] / s5[chosen].sum() * 2.5, rtol=1e-5)


def test_the_old_routings_are_what_they_were_and_half_a_noaux_is_refused():
    """Softmax groups by the best expert and sigmoid with a bias and no
    groups: the same numbers as before sigmoid scores could be grouped;
    groups over sigmoid scores WITHOUT a bias, or over softmax WITH one, are
    nobody's routing and still refused."""
    logits = jnp.asarray(np.array(jax.random.normal(jax.random.PRNGKey(7), (32, 16)) * 2.0))
    bias = jnp.asarray(np.linspace(-0.2, 0.2, 16, dtype=np.float32))
    probs = np.asarray(jax.nn.softmax(logits, -1))
    grouped = np.asarray(moe.route_topk(logits, 3, renormalize=False, scale=4.0, groups=(4, 2)))
    np.testing.assert_allclose(grouped, grouped_by_hand(probs, 3, 4, 2, 4.0), rtol=1e-6)
    plain = np.asarray(moe.route_topk(logits, 3, scoring="sigmoid", choice_bias=bias))
    s = np.asarray(jax.nn.sigmoid(logits))
    for row in range(32):
        chosen = sorted(range(16), key=lambda x: (-(s[row, x] + float(bias[x])), x))[:3]
        want = np.zeros(16)
        want[chosen] = s[row, chosen] / s[row, chosen].sum()
        np.testing.assert_allclose(plain[row], want, rtol=1e-5)
    for kw in (dict(scoring="sigmoid"), dict(choice_bias=bias)):
        with pytest.raises(ValueError, match="group-limited routing"):
            moe.route_topk(logits, 3, groups=(4, 2), **kw)
    # the unchanged calls trace to the programs they did: one top_k without groups, two with
    text = str(jax.make_jaxpr(lambda x: moe.route_topk(x, 3, groups=(4, 2)))(logits))
    assert text.count(" top_k[") == 2 and "logistic" not in text


def test_groups_that_do_not_tile_the_experts_are_refused():
    logits = jnp.zeros((2, 10))
    with pytest.raises(ValueError, match="groups"):
        moe.route_topk(logits, 2, groups=(4, 2))
    with pytest.raises(ValueError, match="groups"):
        moe.route_topk(logits, 2, groups=(5, 6))


def test_the_share_layer_passes_the_groups_on_and_names_its_routing_scope(layer):
    """``moe_share_ffn(groups=...)`` routes as ``route_topk`` does, and a third
    scope names the routing apart from the experts' products; without it the
    two-scope callers' programs are what they were."""
    cfg, params, _, m, _ = layer
    sl = slice(0, 8)
    args = (m, params[P + "mlp.gate.weight"], params[P + "mlp.experts.gate_proj.weight"][sl],
            params[P + "mlp.experts.up_proj.weight"][sl], params[P + "mlp.experts.down_proj.weight"][sl])
    kw = dict(top_k=cfg.top_k, held=(0, 8), renormalize=False, routed_scale=2.0)
    grouped, counts = moe.moe_share_ffn(*args, groups=(4, 2), **kw)
    plain, _ = moe.moe_share_ffn(*args, **kw)
    assert np.abs(np.asarray(grouped) - np.asarray(plain)).max() > 1e-3
    assert int(counts[0]) == m.shape[0] * m.shape[1] * cfg.top_k
    text = jax.jit(lambda *a: moe.moe_share_ffn(*a, groups=(4, 2), scopes=("x.routed", "x.shared", "x.route"), **kw)
                   ).lower(*args).as_text(debug_info=True)
    assert "x.route/" in text and "x.routed/" in text


# -- a decode step reads only the hit experts (hit_experts, ISSUE 44) ----------------
# On the CPU the kernel runs in Pallas's interpret mode: values, the counters, the scan,
# and which callers the rule leaves on the einsums; never a time.


def parents_layer(x, router_w, w_gate, w_up, w_down, *, top_k, held=None, renormalize=True,
                  routed_scale=1.0, shared=None, constrain=None, groups=None,
                  scopes=("moe.routed", "moe.shared"), mesh=None):
    """What ``moe_share_ffn`` was at the parent commit, line for line (its
    check of ``held`` apart; ``mesh`` is taken and not looked at), with the
    fourth counter its einsums earn: every held expert read."""
    from modelx_tpu.ops.nn import linear

    b, s, d = x.shape
    first, count = held if held is not None else (0, router_w.shape[0])
    cons = constrain if constrain is not None else (lambda arr, *spec: arr)
    t = x.reshape(b * s, d)
    f32 = jnp.float32
    with jax.named_scope(scopes[2] if len(scopes) > 2 else scopes[0]):
        logits = jax.lax.dot_general(t, router_w, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)
        combine = moe.route_topk(logits, top_k, renormalize=renormalize, scale=routed_scale,
                                 groups=groups)
        here = jax.lax.slice_in_dim(combine, first, first + count, axis=1)
        hit = here > 0
        n_hit = jnp.sum(jnp.any(hit, axis=0), dtype=jnp.int32)
        counts = jnp.stack([jnp.int32(b * s * top_k), jnp.sum(hit, dtype=jnp.int32), n_hit,
                            jnp.int32(count)])
    with jax.named_scope(scopes[0]):
        g = jnp.einsum("td,efd->etf", t, w_gate, preferred_element_type=f32).astype(x.dtype)
        u = jnp.einsum("td,efd->etf", t, w_up, preferred_element_type=f32).astype(x.dtype)
        h = (jax.nn.silu(g) * u).astype(f32) * here.T[:, :, None]
        h = cons(h.astype(x.dtype), "ep", None, "tp")
        out = jnp.einsum("etf,edf->td", h, w_down, preferred_element_type=f32)
    if shared is not None:
        with jax.named_scope(scopes[1]):
            sg, su, sd = shared
            hs = jax.nn.silu(linear(t, sg)) * linear(t, su)
            out = out + jax.lax.dot_general(hs, sd, (((1,), (1,)), ((), ())),
                                            preferred_element_type=f32)
    return cons(out.astype(x.dtype).reshape(b, s, d), "dp", "sp", None), counts


# the two cells' shapes cut small and lane-whole: rows, held experts, F, D
# (laguna-s-2.1-ep2-d5.reason 64 x 128 x 1024 x 3072; deepseek-v2-ep8-d5.longdoc 32 x 20 x
# 1536 x 5120, whose matrices go in two blocks each: here under a budget that cuts them so)
CUT = {"reason": (16, 12, 128, 384, None), "longdoc": (8, 5, 256, 640, 256 * 640 * 2 // 2)}
HITS = {"all": lambda e: list(range(e)), "some": lambda e: [1, e - 2, e // 2],
        "one": lambda e: [e - 1], "none": lambda e: []}


def routed_operands(cell: str, hits: str, dtype, seed=0):
    rows, e, f, d, _ = CUT[cell]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    t = jax.random.normal(keys[0], (rows, d), jnp.float32).astype(dtype)
    w_gate = (jax.random.normal(keys[1], (e, f, d)) * d ** -0.5).astype(dtype)
    w_up = (jax.random.normal(keys[2], (e, f, d)) * d ** -0.5).astype(dtype)
    w_down = (jax.random.normal(keys[3], (e, d, f)) * f ** -0.5).astype(dtype)
    chosen = np.zeros((rows, e), bool)
    rng = np.random.default_rng(seed)
    for expert in HITS[hits](e):
        chosen[rng.choice(rows, 3, replace=False), expert] = True
    here = jnp.where(chosen, jax.random.uniform(keys[4], (rows, e), minval=0.05), 0.0)
    return t, here, w_gate, w_up, w_down


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hits", HITS)
@pytest.mark.parametrize("cell", CUT)
def test_the_kernel_gives_the_einsums_sum(monkeypatch, cell, hits, dtype):
    """Every held expert hit, some unhit, ONE hit, none at all (the routed
    sum is then exactly zero): the kernel's float32 sum is the einsums' to the
    tolerance of float32 accumulation in another order (bf16: of one rounding
    of the hidden activation, which the kernel takes from a float32 silu)."""
    if CUT[cell][4]:
        monkeypatch.setattr(moe, "BLOCK_BYTES", CUT[cell][4] * (2 if dtype == "float32" else 1))
    args = routed_operands(cell, hits, jnp.dtype(dtype))
    rows, e, f, d, _ = CUT[cell]
    item = jnp.dtype(dtype).itemsize
    assert (moe._chunks(f, d * item), moe._chunks(d, f * item)) == (
        (2, 5) if cell == "longdoc" else (1, 1))
    got, want = moe.hit_experts(*args, interpret=True), moe.every_expert(*args)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    if hits == "none":
        assert not np.asarray(got).any() and not np.asarray(want).any()
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hits", HITS)
def test_the_kernel_gives_the_einsums_sum_for_experts_of_two_matrices(hits, dtype):
    """``w_gate`` None: up, a squared relu, down (PR 46) — the same places,
    two steps a place where a gated expert takes three."""
    t, here, _, w_up, w_down = routed_operands("reason", hits, jnp.dtype(dtype))
    got = moe.hit_experts(t, here, None, w_up, w_down, interpret=True)
    want = moe.every_expert(t, here, None, w_up, w_down)
    gated = moe.every_expert(t, here, w_up, w_up, w_down)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    if hits == "none":
        assert not np.asarray(got).any() and not np.asarray(want).any()
    else:
        assert np.abs(np.asarray(want) - np.asarray(gated)).max() > 1e-2  # another function
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


def test_the_kernel_inside_a_scan_whose_carry_is_donated_state():
    """As the chunk program holds it: the rows and a counter leaf are the
    scan's carry, donated; each step routes the rows it was left, sums the
    hit experts and counts them."""
    t, here, *weights = routed_operands("reason", "some", jnp.float32)
    pattern = here > 0

    def chunk(routed, t, counted):
        def step(carry, i):
            t, counted = carry
            # another three experts a step, the weights hung on the rows
            now = jnp.where(jnp.roll(pattern, i, axis=1), 0.1 + jnp.abs(t[:, :1]), 0.0)
            out = routed(t, now, *weights)
            hit = jnp.sum(jnp.any(now > 0, axis=0), dtype=jnp.int32)
            return ((t * 0.5 + out).astype(t.dtype), counted + hit), out[:, 0]
        return jax.lax.scan(step, (t, counted), jnp.arange(6))

    kernel = lambda *a: moe.hit_experts(*a, interpret=True)  # noqa: E731
    want = chunk(moe.every_expert, t, jnp.zeros((), jnp.int32))
    got = jax.jit(lambda *a: chunk(kernel, *a), donate_argnums=(0, 1))(
        t + 0, jnp.zeros((), jnp.int32))
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
    assert int(got[0][1]) == 18


def forced(x_shape, w_shape, mesh=None):
    """The rule with everything but the tiling and the backend: what a tiny
    model on the CPU needs to reach the kernel (interpreted)."""
    return "kernel" if x_shape[1] == 1 and (mesh is None or mesh.size == 1) else "einsum"


def test_experts_read_is_the_hit_experts_with_the_kernel_and_every_held_one_without(
        layer, monkeypatch):
    """The fourth counter, computed IN the step: ``experts_hit`` where the
    kernel ran, the held count where the einsums did; the layer's answer is
    the same either way. A share no row routes to (held experts whose router
    rows are zeroed against rows that all score above zero elsewhere) gives
    the shared expert alone."""
    cfg, params, _, m, _ = layer
    step = m.reshape(-1, 1, cfg.hidden_size)  # 18 rows, one token each: a decode step
    want, counts = share(cfg, params, step, 4, 8, with_shared=True)
    assert counts.tolist()[2:] == [int(counts[2]), 8] and 0 < int(counts[2]) <= 8
    monkeypatch.setattr(moe, "lowering", forced)
    got, kernel_counts = share(cfg, params, step, 4, 8, with_shared=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    assert kernel_counts.tolist() == counts.tolist()[:3] + [int(counts[2])]
    # no held expert hit: the router prefers every other expert on every row
    router = jnp.abs(params[P + "mlp.gate.weight"]).at[4:12].multiply(-1.0)
    unhit = dict(params, **{P + "mlp.gate.weight": router})
    alone, none = share(cfg, unhit, jnp.abs(step), 4, 8, with_shared=True)
    assert none.tolist() == [18 * cfg.top_k, 0, 0, 0]
    shared_only = share(cfg, unhit, jnp.abs(step), 4, 8, with_shared=False)[0]
    assert not np.asarray(shared_only).any() and np.abs(np.asarray(alone)).max() > 1e-3


def test_the_rule_picks_the_kernel_for_the_two_cells_decode_steps_on_one_tpu_device(monkeypatch):
    cells = [((64, 1, 3072), (128, 1024, 3072)), ((32, 1, 5120), (20, 1536, 5120))]
    assert [moe.lowering(*c) for c in cells] == ["einsum", "einsum"]  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [moe.lowering(*c) for c in cells] == ["kernel", "kernel"]
    one = make_mesh("dp=1", jax.devices()[:1])
    assert [moe.lowering(*c, one) for c in cells] == ["kernel", "kernel"]
    # up to the ridge's rows, where the step stops waiting for the weights
    # a latent expert layer is asked at the width its experts read (PR 46)
    assert moe.lowering((64, 1, 1024), (128, 2688, 1024)) == "kernel"
    assert moe.lowering((256, 1, 3072), (128, 1024, 3072)) == "kernel"
    assert moe.lowering((512, 1, 3072), (128, 1024, 3072)) == "einsum"


NOT_THE_KERNEL = {
    "an_admission": ((1, 16, 256), False),
    "a_prefill_piece": ((1, 2048, 256), False),
    "a_teacher_forced_forward": ((2, 9, 256), False),
    "a_width_that_is_not_whole_tiles": ((8, 1, 192), False),
    "rows_that_are_not_whole_tiles": ((3, 1, 256), False),
    "more_rows_than_the_ridge": ((264, 1, 256), False),
    "a_mesh_of_two_devices": ((8, 1, 256), True),
}


@pytest.mark.parametrize("case", NOT_THE_KERNEL)
def test_every_other_caller_traces_the_parents_primitives_exactly(monkeypatch, case):
    """With the backend steered to a TPU — the one condition a CPU run cannot
    meet — each shape the rule leaves out traces to the same jaxpr, equation
    for equation, as the parent's lines (with the counter they earn)."""
    (b, s, d), meshed = NOT_THE_KERNEL[case]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh("dp=2", jax.devices()[:2]) if meshed else None
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    args = (sds(b, s, d), sds(16, d), sds(6, 128, d), sds(6, 128, d), sds(6, d, 128))
    kw = dict(top_k=3, held=(4, 6), routed_scale=2.5,
              shared=(sds(128, d), sds(128, d), sds(d, 128)))
    assert moe.lowering((b, s, d), (6, 128, d), mesh) == "einsum"
    got = jax.make_jaxpr(lambda *a: moe.moe_share_ffn(*a[:5], **dict(kw, shared=a[5:]), mesh=mesh))(
        *args, *kw["shared"])
    want = jax.make_jaxpr(lambda *a: parents_layer(*a[:5], **dict(kw, shared=a[5:])))(
        *args, *kw["shared"])
    assert "pallas_call" not in str(got) and str(got) == str(want)
    # the guard: the same call one condition nearer IS the kernel
    if case == "a_mesh_of_two_devices":
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda *a: moe.moe_share_ffn(*a[:5], **dict(kw, shared=a[5:])))(*args, *kw["shared"]))


def test_the_pick_is_recorded_at_trace_time():
    """Beside ``kv_write.*`` in ``/v1/trace``: a zero-length span a call
    site, named for the lowering and rows x held experts."""
    from modelx_tpu.utils.trace import tracer

    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    args = (sds(7, 1, 24), sds(9, 24), sds(5, 8, 24), sds(5, 8, 24), sds(5, 24, 8))
    jax.make_jaxpr(lambda *a: moe.moe_share_ffn(*a, top_k=2, held=(2, 5)))(*args)
    assert tracer().summary("moe.")["moe.einsum[7x5]"]["count"] == 1


# -- the engines, either lowering -------------------------------------------------------


def laguna_dir(path):
    cfg = laguna.LagunaConfig.tiny(vocab_size=64)
    params = laguna.init_params(cfg, jax.random.PRNGKey(0))
    st.write_safetensors(str(path / "model.safetensors"),
                         laguna.to_hf_state_dict(params, first=cfg.expert_first))
    (path / "config.json").write_text(json.dumps(laguna.to_hf_config(cfg)))
    return cfg.mlp_layer_types.count("sparse"), cfg.expert_count


def deepseek_v2_dir(path):
    from modelx_tpu.models import deepseek_v2 as ds

    # a held share (experts 4..12 of 16: groups 1 and 2 of 4), as the cell's chip holds one
    cfg = dataclasses.replace(ds.DeepseekV2Config.tiny(vocab_size=64), expert_first=4,
                              expert_count=8)
    params = ds.init_params(cfg, jax.random.PRNGKey(0))
    st.write_safetensors(str(path / "model.safetensors"),
                         ds.to_hf_state_dict(params, first=cfg.expert_first))
    (path / "config.json").write_text(json.dumps(ds.to_hf_config(cfg)))
    return cfg.num_layers - cfg.first_k_dense_replace, cfg.expert_count


@pytest.mark.parametrize("family,write", [("laguna", laguna_dir),
                                          ("deepseek_v2", deepseek_v2_dir)])
def test_a_tiny_engine_gives_the_same_greedy_tokens_with_either_lowering(
        tmp_path, monkeypatch, family, write):
    """Prompts through the engine's admit and chunk programs: the einsums'
    tokens, then the kernel's from a new engine (its programs trace anew).
    The engine that took the kernel read exactly the experts its steps hit;
    the one that did not, every held expert of every sparse layer a step."""
    layers, held = write(tmp_path)
    server = ModelServer(str(tmp_path), mesh_spec="dp=1", dtype="float32", max_seq_len=64)
    server.load()
    assert server.family.name == family
    prompts = np.random.default_rng(1).integers(1, 60, (3, 9)).astype(np.int32)

    def run():
        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4)
        try:
            return np.asarray(cb.generate(prompts, max_new_tokens=20)), dict(cb.stats["moe"])
        finally:
            cb.close()

    want, plain = run()
    assert plain["experts_read"] % (layers * held * 4) == 0  # whole chunks of four steps
    assert 0 < plain["experts_hit"] < plain["experts_read"]
    monkeypatch.setattr(moe, "lowering", forced)
    got, stats = run()
    np.testing.assert_array_equal(got, want)
    assert stats["experts_read"] == stats["experts_hit"] == plain["experts_hit"]
    assert stats["assignments_held"] == plain["assignments_held"]


# -- MiMo-V2-Flash's layer: sigmoid scores, a choice bias, no groups, no shared expert ---------


@pytest.fixture(scope="module")
def mimo_layer():
    """One expert layer of a 256-expert router at small widths (top-8, as
    published), its input, and the reference's uncut answer."""
    from modelx_tpu.models import mimo_v2, mimo_v2_reference

    cfg = mimo_v2.MimoV2Config.tiny(vocab_size=64, num_experts=256, expert_count=256, top_k=8,
                                    hidden_size=32, moe_intermediate_size=16)
    params = mimo_v2.init_params(cfg, jax.random.PRNGKey(5))
    raw = mimo_v2.to_hf_config(cfg)
    m = jax.random.normal(jax.random.PRNGKey(6), (2, 9, cfg.hidden_size), jnp.float32)
    w = mimo_v2_reference.Weights(mimo_v2.to_hf_state_dict(params))
    with jax.default_matmul_precision("highest"):
        whole = mimo_v2_reference.routed_experts(w, P, raw, m.reshape(-1, cfg.hidden_size))
    return cfg, params, m, np.asarray(whole).reshape(m.shape)


def test_the_sixteen_shares_of_a_256_expert_layer_add_up_to_the_uncut_reference(mimo_layer):
    """One chip of sixteen holds 16 experts of 256 under the router's 256
    outputs and its choice bias: the sixteen chips' parts are the whole layer
    (no shared expert to count once), and the held assignments add up to all."""
    cfg, params, m, whole = mimo_layer
    total, held = 0.0, 0
    for chip in range(16):
        sl = slice(16 * chip, 16 * chip + 16)
        part, counts = moe.moe_share_ffn(
            m, params[P + "mlp.gate.weight"], params[P + "mlp.experts.gate_proj.weight"][sl],
            params[P + "mlp.experts.up_proj.weight"][sl],
            params[P + "mlp.experts.down_proj.weight"][sl], top_k=cfg.top_k,
            held=(16 * chip, 16), renormalize=True, routed_scale=1.0, scoring="sigmoid",
            choice_bias=params[P + "mlp.gate.e_score_correction_bias"])
        total, held = total + part, held + int(counts[1])
        assert int(counts[0]) == 18 * 8
    np.testing.assert_allclose(np.asarray(total), whole, atol=2e-5, rtol=1e-5)
    assert held == 18 * 8  # every assignment lands on exactly one chip
