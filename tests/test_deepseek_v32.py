"""DeepSeek-V3.2-Exp (``models/deepseek_v2.py`` with an indexer and the
``noaux_tc`` router) against its float32 reference, tiny and on the CPU: seeded
weights, logits and not tokens, ``index_topk`` 8 with contexts below it, at it
and well above it. Everything here computes in float32, so each tolerance is
float32 rounding through three layers (about 6e-6 of logits with a standard
deviation of 1; the limits leave a factor of ten) — and a selection that kept
other positions than the reference's moves a logit by whole units, which the
fixture shows against the same weights without an indexer."""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.models import deepseek_v2 as ds
from modelx_tpu.models import deepseek_v2_reference as v2_reference
from modelx_tpu.models import deepseek_v32_reference as reference
from modelx_tpu.ops import index_select as select_ops
from modelx_tpu.ops import moe as moe_ops

TOL = 1e-4  # float32 rounding, three layers; another selection reads whole units
TOPK = 8


@pytest.fixture(scope="module")
def model():
    cfg = ds.DeepseekV2Config.tiny_v32()
    assert cfg.index_topk == TOPK
    params = ds.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 48)).astype(np.int32)
    raw = ds.to_hf_config(cfg)
    chosen: list = []
    want = np.stack([np.asarray(reference.forward(ds.to_hf_state_dict(params), raw, row,
                                                  selected=chosen))
                     for row in tokens])
    return cfg, params, tokens, raw, want, chosen


def test_the_config_reads_back_and_names_what_it_refuses(model):
    cfg, _, _, raw, _, _ = model
    assert ds.config_from_hf(raw, dtype=jnp.float32) == cfg
    assert json.loads(json.dumps(raw)) == raw
    assert (raw["model_type"], raw["scoring_func"], raw["topk_method"]) == (
        "deepseek_v32", "sigmoid", "noaux_tc")
    assert (raw["index_topk"], raw["index_n_heads"], raw["index_head_dim"]) == (8, 4, 16)
    for key, value, message in [
        ("model_type", "deepseek_v4", "model_type"),
        ("scoring_func", "softmax", "scoring_func 'softmax' with topk_method 'noaux_tc'"),
        ("topk_method", "group_limited_greedy", "scoring_func 'sigmoid' with topk_method"),
        ("index_topk", None, "a deepseek_v32 without index_topk"),
        ("model_type", "deepseek_v3", "model_type 'deepseek_v3' with index_topk 8"),
        ("index_head_dim", 4, "index_head_dim"),
        ("q_lora_rank", None, "q_lora_rank"), ("moe_layer_freq", 2, "moe_layer_freq"),
        ("attention_bias", True, "biases"), ("tie_word_embeddings", True, "tied"),
        ("rope_scaling", {"type": "linear", "factor": 2.0}, "rope_scaling"),
        ("expert_share", {"published": 8, "first": 0}, "expert_share"),
    ]:
        with pytest.raises(ValueError, match=message):
            ds.config_from_hf(dict(raw, **{key: value}))
    # a deepseek_v3 is the same router without the indexer, and reads back too
    v3 = dataclasses.replace(cfg, index_topk=0, index_heads=0, index_dim=0)
    assert v3.model_type == "deepseek_v3" and ds.DeepseekV2Config.tiny().model_type == "deepseek_v2"
    assert ds.config_from_hf(ds.to_hf_config(v3), dtype=jnp.float32) == v3
    assert "index_topk" not in ds.to_hf_config(v3)
    assert not any("indexer" in name for name in ds.param_shapes(v3))
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in ds.param_shapes(v3)


def test_the_published_scale_weights_and_shapes():
    """V3.2's numbers: the softmax scale with ``mscale_all_dim`` 1, the
    combine scale applied after the norm, the two leaves a layer."""
    with open("benchmark/configs/deepseek-v3.2-exp-ep16-d5.json") as f:
        cfg = ds.config_from_hf(json.load(f))
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    assert cfg.softmax_scale == pytest.approx(0.1352, abs=1e-4)
    assert ds.rope_frequencies(cfg)[1] == 1.0
    assert cfg.noaux and cfg.norm_topk_prob and cfg.combine_scale == 2.5
    assert (cfg.n_group, cfg.topk_group, cfg.top_k, cfg.held) == (8, 4, 8, (0, 16))
    assert (cfg.index_topk, cfg.index_heads, cfg.index_dim) == (2048, 64, 128)
    shapes = ds.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 4_635_518_208
    assert shapes["model.layers.0.self_attn.indexer.wq_b.weight"] == (64 * 128, 1536)
    assert shapes["model.layers.1.mlp.gate.e_score_correction_bias"] == (256,)
    state = jax.eval_shape(lambda: ds.init_layer_state(cfg, 16, 32768))
    assert state["c4"].shape == (16, 32768, 640) and state["i4"].shape == (16, 32768, 128)
    kinds = ds.cache_kinds(cfg)
    assert kinds["c0"] == "latent" and kinds["i0"] == "index" and kinds["dsa_counts"] == "counter"
    assert ds.published(cfg)["counters"]["dsa_counts"] == ("dsa", ds.DSA_COUNTERS)
    # V2 keeps its one leaf a layer and its two counters
    v2 = ds.DeepseekV2Config.tiny()
    assert set(ds.cache_kinds(v2).values()) == {"latent", "counter"}
    assert "dsa_counts" not in ds.published(v2)["counters"]


def test_the_reference_selects_and_the_selection_matters(model):
    """Below ``index_topk`` every earlier position, then exactly 8 — and the
    same weights without the indexer give other logits by whole units."""
    cfg, params, tokens, raw, want, chosen = model
    assert len(chosen) == 2 * cfg.num_layers
    for seen in chosen:
        assert seen.sum(-1).tolist() == [min(t + 1, TOPK) for t in range(48)]
        assert not np.triu(seen, 1).any()
    assert any(not seen[40, 33:41].all() for seen in chosen)  # not simply the last 8
    dense = dict(raw)
    dense.pop("index_topk")
    other = np.asarray(reference.forward(ds.to_hf_state_dict(params), dense, tokens[0]))
    assert np.abs(other[:TOPK] - want[0, :TOPK]).max() < TOL  # the same model up to 8 positions
    assert np.abs(other - want[0]).max() > 1.0


def test_the_cacheless_forward_is_the_reference(model):
    cfg, params, tokens, _, want, _ = model
    logits, cache = ds.forward(params, jnp.asarray(tokens), cfg)
    assert cache is None
    assert np.abs(np.asarray(logits) - want).max() < TOL


@pytest.mark.parametrize("impl", ["auto", "expanded"])
def test_prefill_then_decode_through_both_leaves_is_the_references_full_forward(model, impl):
    """Ragged rows side by side: row 0 decodes from position 5 — below
    ``index_topk``, through it (7 -> 8 positions) and past it — row 1 from 20,
    well above it, in the same steps; each step writes the line AND the index
    key, scores the row's keys, keeps 8 and attends over their gathered lines
    (``expanded``: the prompt block's form under the selection's mask)."""
    cfg, params, tokens, _, want, _ = model
    starts = np.array([5, 20], np.int32)
    cache = ds.init_layer_state(cfg, 2, 64)
    for row, start in enumerate(starts):  # each row's prompt lands alone, as an admission does
        one = ds.init_kv_cache(cfg, 1, int(start))
        logits, one = ds.forward(params, jnp.asarray(tokens[row: row + 1, :start]), cfg,
                                 kv_cache=one, cache_offset=0)
        assert np.abs(np.asarray(logits[0]) - want[row, :start]).max() < TOL
        assert set(one) == {f"{kind}{i}" for kind in "ci" for i in range(cfg.num_layers)}
        for name, leaf in one.items():
            cache[name] = cache[name].at[row, :start].set(leaf[0])
    for step in range(12):
        at = starts + step
        tok = jnp.asarray(tokens[np.arange(2), at][:, None])
        logits, cache = ds.forward(params, tok, cfg, kv_cache=cache,
                                   cache_offset=jnp.asarray(at), attention_impl=impl)
        assert np.abs(np.asarray(logits[:, 0]) - want[np.arange(2), at]).max() < TOL
    if impl == "expanded":
        return
    # what the steps counted, over three layers: contexts 6..17 and 21..32
    contexts = [int(s) + 1 + k for s in starts for k in range(12)]
    scored, kept, selecting, steps, by_kernel = np.asarray(cache["dsa_counts"])
    assert (scored, steps) == (3 * sum(contexts), 3 * 24)
    assert by_kernel == 0  # no chunk of 128 positions tiles these toy caches: the sort
    assert kept == 3 * sum(min(c, TOPK) for c in contexts)
    assert selecting == 3 * sum(c > TOPK for c in contexts)
    read, cached, absorbed, all_steps = np.asarray(cache["mla_counts"])
    assert (cached, absorbed, all_steps) == (3 * sum(contexts), 3 * 24, 3 * 24)
    assert read == 3 * 24 * TOPK  # the jnp form contracts the 8 gathered lines whole


def test_a_prompt_landed_in_pieces_is_the_prompt_landed_whole(model):
    """Three pieces of 16 (the second and third select among what landed
    before them and their own) leave the same two leaves a layer and give the
    same logits as one block of 48."""
    cfg, params, tokens, _, want, _ = model
    whole = ds.init_kv_cache(cfg, 1, 64)
    _, whole = ds.forward(params, jnp.asarray(tokens[:1]), cfg, kv_cache=whole, cache_offset=0)
    pieces = ds.init_kv_cache(cfg, 1, 64)
    for start in (0, 16, 32):
        logits, pieces = ds.forward(params, jnp.asarray(tokens[:1, start: start + 16]), cfg,
                                    kv_cache=pieces, cache_offset=jnp.int32(start))
        assert np.abs(np.asarray(logits[0]) - want[0, start: start + 16]).max() < TOL
    assert set(whole) == set(pieces) and "i2" in whole
    for name in whole:
        assert np.abs(np.asarray(whole[name]) - np.asarray(pieces[name])).max() < 1e-5


def test_a_padded_bucket_enters_neither_leaf_of_the_rows_that_decode(model):
    """A prompt of 11 lands in a bucket of 16 (five padding tokens behind it):
    the real positions' logits are the reference's, and once the row decodes
    from 11 its steps overwrite the padding's lines and index keys before any
    query can select them — decode follows the reference as if they had never
    been written."""
    cfg, params, tokens, _, want, _ = model
    cache = ds.init_layer_state(cfg, 1, 64)
    padded = np.concatenate([tokens[0, :11], np.full(5, 7, np.int32)])[None]
    logits, cache = ds.forward(params, jnp.asarray(padded), cfg, kv_cache=cache, cache_offset=0)
    assert np.abs(np.asarray(logits[0, :11]) - want[0, :11]).max() < TOL
    assert np.abs(np.asarray(cache["i1"][0, 11:16])).max() > 0  # the padding did write
    for at in range(11, 30):
        logits, cache = ds.forward(params, jnp.asarray(tokens[:1, at: at + 1]), cfg,
                                   kv_cache=cache, cache_offset=jnp.asarray([at]))
        assert np.abs(np.asarray(logits[0, 0]) - want[0, at]).max() < TOL


def _plain_selection(scores: np.ndarray, length: int, k: int) -> list[int]:
    """The k best of the first ``length`` scores, the lower position first
    among equals: a stable sort."""
    return sorted(range(length), key=lambda i: (-scores[i], i))[:k]


def test_the_selection_is_a_plain_sort_ties_included():
    """``select`` (a decode step's) and ``selection_mask`` (a prompt block's)
    against a stable sort, on scores drawn from nine values so that ties cross
    the k-th place in nearly every row, signed zeros among them."""
    rng = np.random.default_rng(3)
    scores = rng.integers(-4, 5, (4, 6, 40)).astype(np.float32)
    scores[0, 0, :6] = [0.0, -0.0, 0.0, -0.0, 1.0, -1.0]
    lengths = np.array([3, 8, 9, 40], np.int32)
    got = np.asarray(select_ops.select(jnp.asarray(scores[:, 0]), jnp.asarray(lengths), 8))
    for row, n in enumerate(lengths):
        want = _plain_selection(scores[row, 0], int(n), 8)
        # the SET is the contract (a kernel's order is ascending, the sort's best first)
        assert sorted(got[row, : len(want)].tolist()) == sorted(want)
        # a short row's own positions come first, all of them; what follows is in range
        assert set(got[row, : len(want)].tolist()) <= set(range(int(n)))
        assert 0 <= got[row].min() and got[row].max() < 40
    qpos = np.stack([np.arange(6) + off for off in (0, 5, 20, 34)])
    mask = np.asarray(select_ops.selection_mask(jnp.asarray(scores), jnp.asarray(qpos), 8))
    for b in range(4):
        for s in range(6):
            want = np.zeros(40, bool)
            want[_plain_selection(scores[b, s], int(qpos[b, s]) + 1, 8)] = True
            assert (mask[b, s] == want).all(), (b, s)
    for k in (1, 7, 40):
        kth = np.asarray(select_ops.kth_largest(jnp.asarray(scores), k))
        assert (kth == np.sort(scores, -1)[..., ::-1][..., k - 1]).all()


def test_the_block_scores_are_the_step_scores_and_the_equation(monkeypatch):
    # folds of 16 keys, 2 heads and 2 queries, so that the toy sizes run every loop
    monkeypatch.setattr(select_ops, "KEY_BLOCK", 16)
    monkeypatch.setattr(select_ops, "HEAD_BLOCK", 2)
    monkeypatch.setattr(select_ops, "QUERY_TILE", 2)
    rng = np.random.default_rng(5)
    q = rng.standard_normal((2, 5, 4, 16)).astype(np.float32)
    w = rng.standard_normal((2, 5, 4)).astype(np.float32)
    keys = rng.standard_normal((2, 64, 16)).astype(np.float32)
    want = np.einsum("bshk,bsh->bsk", np.maximum(np.einsum("bshd,bkd->bshk", q, keys), 0), w)
    block = select_ops.block_scores(jnp.asarray(q), jnp.asarray(w), jnp.asarray(keys))
    assert np.abs(np.asarray(block) - want).max() < 1e-4
    step = select_ops.step_scores(jnp.asarray(q[:, 0]), jnp.asarray(w[:, 0]), jnp.asarray(keys))
    assert np.abs(np.asarray(step) - want[:, 0]).max() < 1e-4
    lines = rng.standard_normal((2, 64, 24)).astype(np.float32)
    idx = np.array([[3, 0, 63], [5, 5, 1]], np.int32)
    got = np.asarray(select_ops.gather_lines(jnp.asarray(lines), jnp.asarray(idx)))
    assert (got == np.stack([lines[0, idx[0]], lines[1, idx[1]]])).all()
    # a block's selection in query tiles is the selection in one
    qpos = jnp.asarray(np.stack([np.arange(5) + 40, np.arange(5) + 9]))
    whole = select_ops.selection_mask(jnp.asarray(want), qpos, 8)
    q8 = jnp.asarray(np.concatenate([q, q[:, :3]], 1))
    w8 = jnp.asarray(np.concatenate([w, w[:, :3]], 1))
    p8 = jnp.concatenate([qpos, qpos[:, :3]], 1)
    tiled = select_ops.block_selection(q8, w8, jnp.asarray(keys), p8, 8)
    assert (np.asarray(tiled[:, :5]) == np.asarray(whole)).all()


def test_the_router_is_the_loop_written_from_the_equations(model):
    """``route_topk`` with sigmoid scores, a choice bias and groups scored by
    the sum of their two best, against the reference's token-by-token loop —
    with a bias large enough to move the choice."""
    cfg, params, _, raw, _, _ = model
    p = "model.layers.1."
    m = jax.random.normal(jax.random.PRNGKey(7), (40, cfg.hidden_size))
    hf = dict(ds.to_hf_state_dict(params))
    bias = np.asarray(jax.random.normal(jax.random.PRNGKey(8), (cfg.num_experts,))) * 0.3
    hf[p + "mlp.gate.e_score_correction_bias"] = bias
    want = reference.routing(reference.Weights(hf), p, raw, m)
    logits = m @ params[p + "mlp.gate.weight"].T
    got = moe_ops.route_topk(logits, cfg.top_k, renormalize=True, scale=cfg.routed_scale,
                             groups=cfg.groups, scoring="sigmoid", choice_bias=jnp.asarray(bias))
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    assert ((np.asarray(got) > 0).sum(-1) == cfg.top_k).all()
    np.testing.assert_allclose(np.asarray(got).sum(-1), cfg.routed_scale, rtol=1e-5)
    unbiased = moe_ops.route_topk(logits, cfg.top_k, groups=cfg.groups, scoring="sigmoid",
                                  choice_bias=jnp.zeros_like(bias))
    assert ((np.asarray(unbiased) > 0) != (np.asarray(got) > 0)).any()  # the bias chose
    # every chosen expert lies in one of the two groups kept
    groups_hit = (np.asarray(got).reshape(40, cfg.n_group, -1) > 0).any(-1).sum(-1)
    assert (groups_hit <= cfg.topk_group).all()


def test_the_shares_add_up_to_the_uncut_layer(model):
    """Four chips, four experts each (a group each): the routed parts of
    shares 0-3, plus what every chip computes alike (the shared expert)
    counted once, are the uncut reference's expert layer."""
    cfg, params, _, raw, _, _ = model
    p = "model.layers.1."
    m = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size))
    hf = ds.to_hf_state_dict(params)
    w = reference.Weights(hf)
    whole = reference.routed_experts(w, p, raw, m) + reference.swiglu(w, p + "mlp.shared_experts.", m)
    shared = tuple(params[p + f"mlp.shared_experts.{x}_proj.weight"] for x in ("gate", "up", "down"))
    total, hits = 0.0, 0
    for share in range(4):
        held = (4 * share, 4)
        experts = [params[p + f"mlp.experts.{x}_proj.weight"][held[0]: held[0] + 4]
                   for x in ("gate", "up", "down")]
        out, counts = moe_ops.moe_share_ffn(
            m[None], params[p + "mlp.gate.weight"], *experts, top_k=cfg.top_k, held=held,
            renormalize=True, routed_scale=cfg.combine_scale, groups=cfg.groups,
            scoring="sigmoid",
            choice_bias=params[p + "mlp.gate.e_score_correction_bias"],
            shared=shared if share == 0 else None)
        total, hits = total + out[0], hits + int(counts[1])
        part = reference.routed_experts(
            w, p, dict(raw, n_routed_experts=4, expert_share={"published": 16, "first": held[0]}), m)
        if share == 0:
            part = part + reference.swiglu(w, p + "mlp.shared_experts.", m)
        assert np.abs(np.asarray(out[0]) - np.asarray(part)).max() < TOL
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < TOL
    assert hits == 24 * cfg.top_k  # every assignment landed on exactly one share


def test_a_held_share_runs_end_to_end_against_the_reference_given_the_same_share(model):
    cfg, params, tokens, _, _, _ = model
    held = dataclasses.replace(cfg, expert_first=4, expert_count=4)  # group 1 of 4
    cut = {k: (v[4:8] if ".mlp.experts." in k else v) for k, v in params.items()}
    logits, _ = ds.forward(cut, jnp.asarray(tokens[:1]), held)
    raw = ds.to_hf_config(held)
    assert raw["n_routed_experts"] == 4 and raw["expert_share"] == {"published": 16, "first": 4}
    want = reference.forward(ds.to_hf_state_dict(cut, first=4), raw, tokens[0])
    assert np.abs(np.asarray(logits[0]) - np.asarray(want)).max() < TOL


def test_deepseek_v2_is_what_it_was():
    """The module serves V2 unchanged: its reference, one leaf a layer, the
    softmax router; and a V2 decode step traces no selection."""
    cfg = ds.DeepseekV2Config.tiny()
    params = ds.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size, (1, 24)).astype(np.int32)
    want = np.asarray(v2_reference.forward(ds.to_hf_state_dict(params), ds.to_hf_config(cfg),
                                           tokens[0]))
    cache = ds.init_layer_state(cfg, 1, 32)
    logits, cache = ds.forward(params, jnp.asarray(tokens[:, :16]), cfg, kv_cache=cache,
                               cache_offset=0)
    assert np.abs(np.asarray(logits[0]) - want[:16]).max() < TOL
    assert set(cache) == {"c0", "c1", "c2", "moe_counts", "mla_counts"}
    step = jax.make_jaxpr(lambda c: ds.forward(
        params, jnp.asarray(tokens[:, 16:17]), cfg, kv_cache=c,
        cache_offset=jnp.asarray([16])))(cache)
    # two top_k a sparse layer, the router's (groups, then experts): no selection is traced
    assert str(step).count(" top_k[") == 2 * (cfg.num_layers - cfg.first_k_dense_replace)


def test_the_tolerance_refuses_bfloat16(model):
    cfg, params, tokens, _, want, _ = model
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    cast = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    logits, _ = ds.forward(cast, jnp.asarray(tokens), low)
    assert np.abs(np.asarray(logits, np.float32) - want).max() > 100 * TOL
