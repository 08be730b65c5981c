"""The deepseek_v2 family against its float32 reference, tiny and on the CPU:
seeded weights, logits and not tokens. Everything here computes in float32, so
each tolerance is float32 rounding through three layers (about 6e-6 of logits
with a standard deviation of 1; the limits leave a factor of ten) — bfloat16
for float32 moves a logit by 2e-2 and fails every one of them, which the last
test shows."""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.models import deepseek_v2 as ds
from modelx_tpu.models import deepseek_v2_reference as reference
from modelx_tpu.ops import latent_attention as latent_ops

TOL = 1e-4  # float32 rounding, three layers; bfloat16 reads 2e-2 and more


@pytest.fixture(scope="module")
def model():
    cfg = ds.DeepseekV2Config.tiny()
    params = ds.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 48)).astype(np.int32)
    raw = ds.to_hf_config(cfg)
    want = np.stack([np.asarray(reference.forward(ds.to_hf_state_dict(params), raw, row))
                     for row in tokens])
    return cfg, params, tokens, raw, want


def test_the_config_reads_back_and_names_what_it_refuses(model):
    cfg, _, _, raw, _ = model
    assert ds.config_from_hf(raw, dtype=jnp.float32) == cfg
    assert json.loads(json.dumps(raw)) == raw
    for key, value, message in [
        ("q_lora_rank", None, "q_lora_rank"), ("moe_layer_freq", 2, "moe_layer_freq"),
        ("scoring_func", "sigmoid", "scoring_func"), ("topk_method", "noaux_tc", "topk_method"),
        ("attention_bias", True, "biases"), ("tie_word_embeddings", True, "tied"),
        ("rope_scaling", {"type": "linear", "factor": 2.0}, "rope_scaling"),
        ("expert_share", {"published": 8, "first": 0}, "expert_share"),
    ]:
        with pytest.raises(ValueError, match=message):
            ds.config_from_hf(dict(raw, **{key: value}))


def test_the_published_softmax_scale_and_rotation_factor():
    """DeepSeek's YaRN: ``mscale`` enters the softmax scale, squared, and the
    rotation's own factor is mscale / mscale_all_dim = 1.0 as published."""
    cfg = ds.DeepseekV2Config()
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    assert cfg.softmax_scale == pytest.approx(0.1147, abs=1e-4)
    inv, on_cos = ds.rope_frequencies(cfg)
    assert on_cos == 1.0 and inv.shape == (32,)
    ref_inv, ref_cos, ref_scale = reference.inverse_frequencies(ds.to_hf_config(cfg))
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_cos == 1.0 and ref_scale * 192 ** -0.5 == pytest.approx(cfg.softmax_scale)
    assert cfg.line_width == 640 and ds.DeepseekV2Config.tiny().line_width == 128


def test_the_cacheless_forward_is_the_reference(model):
    cfg, params, tokens, _, want = model
    logits, cache = ds.forward(params, jnp.asarray(tokens), cfg)
    assert cache is None
    assert np.abs(np.asarray(logits) - want).max() < TOL


@pytest.mark.parametrize("impl", ["auto", "ragged+interpret"])
def test_prefill_then_decode_through_the_cache_is_the_references_full_forward(model, impl):
    """Rows at DIFFERENT depths: row 0 decodes from position 16, row 1 from
    32, in the same steps, each in the absorbed form over its own lines —
    ``ragged+interpret`` through the kernel that reads a row's blocks up to
    its own context (a cache of 64 is two blocks of 32)."""
    cfg, params, tokens, _, want = model
    starts = np.array([16, 32], np.int32)
    cache = ds.init_layer_state(cfg, 2, 64)
    for row, start in enumerate(starts):  # each row's prompt lands alone, as an admission does
        one = ds.init_kv_cache(cfg, 1, int(start))
        logits, one = ds.forward(params, jnp.asarray(tokens[row: row + 1, :start]), cfg,
                                 kv_cache=one, cache_offset=0)
        assert np.abs(np.asarray(logits[0]) - want[row, :start]).max() < TOL
        for name, leaf in one.items():
            cache[name] = cache[name].at[row, :start].set(leaf[0])
    for step in range(12):
        at = starts + step
        tok = jnp.asarray(tokens[np.arange(2), at][:, None])
        logits, cache = ds.forward(params, tok, cfg, kv_cache=cache,
                                   cache_offset=jnp.asarray(at), attention_impl=impl)
        got = np.asarray(logits[:, 0])
        assert np.abs(got - want[np.arange(2), at]).max() < TOL
    # what the steps counted: both rows hold a context, three latent layers, the
    # absorbed form every time; contexts 17..28 and 33..44
    held = 3 * sum(int(s) + 1 + k for s in starts for k in range(12))
    read, cached, absorbed, steps = np.asarray(cache["mla_counts"])
    assert (cached, absorbed, steps) == (held, 3 * 2 * 12, 3 * 2 * 12)
    if impl == "auto":  # the jnp form contracts the whole cache
        assert read == 3 * 2 * 12 * 64
    else:  # whole blocks of 32 up to each row's context
        assert read == 3 * sum(-(-(int(s) + 1 + k) // 32) * 32 for s in starts for k in range(12))
    assert np.asarray(cache["moe_counts"])[0] == 2 * 12 * 2 * cfg.top_k  # rows x steps x sparse layers


def test_the_absorbed_form_is_the_expanded_form_on_the_same_cache(model):
    """One decode step a row over one cache, three ways: absorbed in ``jnp``,
    absorbed in the kernel (interpreted), expanded as a prompt block would be.
    The same softmax over the same lines: float32 rounding apart."""
    cfg, params, tokens, _, _ = model
    cache = ds.init_kv_cache(cfg, 2, 64)
    _, cache = ds.forward(params, jnp.asarray(tokens[:, :40]), cfg, kv_cache=cache, cache_offset=0)
    tok, at = jnp.asarray(tokens[:, 40:41]), jnp.asarray([40, 40], jnp.int32)
    got = {impl: np.asarray(ds.forward(params, tok, cfg, kv_cache=cache, cache_offset=at,
                                       attention_impl=impl)[0])
           for impl in ("auto", "ragged+interpret", "expanded")}
    assert np.abs(got["auto"] - got["expanded"]).max() < TOL
    assert np.abs(got["ragged+interpret"] - got["expanded"]).max() < TOL
    assert np.abs(got["auto"]).max() > 0.5  # and they are not all zero


def test_the_ops_agree_at_rows_of_different_depths():
    """ops/latent_attention alone: ``absorbed`` (both lowerings) against
    ``expanded`` with one query a row at per-row offsets, and against the
    softmax written out."""
    rng = np.random.default_rng(1)
    b, h, dn, dr, dv, r, length = 3, 4, 16, 8, 16, 32, 128
    width = latent_ops.line_width(r, dr)
    cache = np.zeros((b, length, width), np.float32)
    cache[..., : r + dr] = rng.standard_normal((b, length, r + dr))
    w_kvb = rng.standard_normal((h, dn + dv, r)).astype(np.float32) / np.sqrt(r)
    q_nope = rng.standard_normal((b, 1, h, dn)).astype(np.float32)
    q_pe = rng.standard_normal((b, 1, h, dr)).astype(np.float32)
    offsets = jnp.asarray([5, 64, 127], jnp.int32)
    scale = 0.2
    want = latent_ops.expanded(jnp.asarray(q_nope), jnp.asarray(q_pe), jnp.asarray(cache),
                               offsets, jnp.asarray(w_kvb), scale, r, key_block=32)
    q_lat = np.einsum("bhd,hdc->bhc", q_nope[:, 0], w_kvb[:, :dn])
    q_cat = np.concatenate([q_lat, q_pe[:, 0], np.zeros((b, h, width - r - dr), np.float32)], -1)
    for impl in ("auto", "ragged+interpret"):
        o_lat = latent_ops.absorbed(jnp.asarray(q_cat), jnp.asarray(cache), offsets, scale, r,
                                    impl=impl)
        got = np.einsum("bhc,hdc->bhd", np.asarray(o_lat), w_kvb[:, dn:])
        assert np.abs(got - np.asarray(want[:, 0])).max() < 1e-5, impl
    # written out: per row, per head, keys and values expanded over the context
    for row, off in enumerate(np.asarray(offsets)):
        lines = cache[row, : off + 1]
        kv = np.einsum("kc,hdc->khd", lines[:, :r], w_kvb)
        s = (np.einsum("hd,khd->hk", q_nope[row, 0], kv[..., :dn])
             + np.einsum("hd,kd->hk", q_pe[row, 0], lines[:, r: r + dr])) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        assert np.abs(np.einsum("hk,khd->hd", p, kv[..., dn:]) - np.asarray(want[row, 0])).max() < 1e-5
    assert latent_ops.absorbed_takes_kernel(cache.shape, r) == (0, False)  # the CPU, narrow lanes
    assert latent_ops.absorbed_takes_kernel((32, 32768, 640), 512, "ragged")[0] == 2048


def test_a_prompt_landed_in_pieces_is_the_prompt_landed_whole(model):
    """Three pieces of 16 over the row's own lines (a piece expands the keys
    and values of what landed before it, a key block at a time) leave the
    same lines and give the same logits as one block of 48."""
    cfg, params, tokens, _, want = model
    whole = ds.init_kv_cache(cfg, 1, 64)
    _, whole = ds.forward(params, jnp.asarray(tokens[:1]), cfg, kv_cache=whole, cache_offset=0)
    pieces = ds.init_kv_cache(cfg, 1, 64)
    for start in (0, 16, 32):
        logits, pieces = ds.forward(params, jnp.asarray(tokens[:1, start: start + 16]), cfg,
                                    kv_cache=pieces, cache_offset=jnp.int32(start))
        assert np.abs(np.asarray(logits[0]) - want[0, start: start + 16]).max() < TOL
    for name in whole:
        assert np.abs(np.asarray(whole[name]) - np.asarray(pieces[name])).max() < 1e-5


def test_a_long_block_goes_through_the_experts_in_chunks(model, monkeypatch):
    cfg, params, tokens, _, want = model
    monkeypatch.setattr(ds, "MOE_TOKENS", 32)
    monkeypatch.setattr(ds, "MOE_CHUNK", 20)  # 96 tokens: five chunks, the last padded
    logits, _ = ds.forward(params, jnp.asarray(tokens), cfg)
    assert np.abs(np.asarray(logits) - want).max() < TOL


def test_the_shares_add_up_to_the_uncut_layer(model):
    """Four chips, a group of four experts each: the routed parts of shares
    0-3, plus what every chip computes alike (the shared experts) counted
    once, are the uncut reference's expert layer."""
    cfg, params, _, raw, _ = model
    p = "model.layers.1."
    m = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.hidden_size))
    hf = ds.to_hf_state_dict(params)
    w = reference.Weights(hf)
    whole = reference.routed_experts(w, p, raw, m) + reference.swiglu(w, p + "mlp.shared_experts.", m)
    from modelx_tpu.ops import moe as moe_ops

    shared = tuple(params[p + f"mlp.shared_experts.{x}_proj.weight"] for x in ("gate", "up", "down"))
    total, hits = 0.0, 0
    for share in range(4):
        held = (4 * share, 4)
        experts = [params[p + f"mlp.experts.{x}_proj.weight"][held[0]: held[0] + 4]
                   for x in ("gate", "up", "down")]
        out, counts = moe_ops.moe_share_ffn(
            m[None], params[p + "mlp.gate.weight"], *experts, top_k=cfg.top_k, held=held,
            renormalize=False, routed_scale=cfg.routed_scale, groups=cfg.groups,
            shared=shared if share == 0 else None)
        total, hits = total + out[0], hits + int(counts[1])
        # and the reference, given the same share, gives the same part
        part = reference.routed_experts(
            w, p, dict(raw, n_routed_experts=4, expert_share={"published": 16, "first": held[0]}), m)
        if share == 0:
            part = part + reference.swiglu(w, p + "mlp.shared_experts.", m)
        assert np.abs(np.asarray(out[0]) - np.asarray(part)).max() < TOL
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < TOL
    assert hits == 24 * cfg.top_k  # every assignment landed on exactly one share


def test_a_held_share_runs_end_to_end_against_the_reference_given_the_same_share(model):
    cfg, params, tokens, _, _ = model
    held = dataclasses.replace(cfg, expert_first=4, expert_count=4)  # group 1 of 4
    cut = {k: (v[4:8] if ".mlp.experts." in k else v) for k, v in params.items()}
    logits, _ = ds.forward(cut, jnp.asarray(tokens[:1]), held)
    raw = ds.to_hf_config(held)
    assert raw["n_routed_experts"] == 4 and raw["expert_share"] == {"published": 16, "first": 4}
    want = reference.forward(ds.to_hf_state_dict(cut, first=4), raw, tokens[0])
    assert np.abs(np.asarray(logits[0]) - np.asarray(want)).max() < TOL


def test_the_tolerance_refuses_bfloat16(model):
    """The same forward with bfloat16 weights and activations: every limit
    above would fail by two orders of magnitude."""
    cfg, params, tokens, _, want = model
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    cast = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    logits, _ = ds.forward(cast, jnp.asarray(tokens), low)
    assert np.abs(np.asarray(logits, np.float32) - want).max() > 100 * TOL
