"""The expert layer that is told which experts it holds (ops/moe.moe_share_ffn),
against the plain reference layer (models/laguna_reference.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.models import laguna, laguna_reference as reference
from modelx_tpu.ops import moe

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
        assert counts.tolist() == [tokens * cfg.top_k, int(here.sum()), int(here.any(0).sum())]


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
