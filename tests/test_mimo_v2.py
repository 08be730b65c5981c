"""The mimo_v2 family (models/mimo_v2.py) on the normal serving path, held
against the plain float32 reference (models/mimo_v2_reference.py) at a small
size on the CPU: seeded random weights, keys of 24 over values of 16, 2 KV
heads on the full layers and 4 on the window layers, window 16 with a sink a
query head, a ring of 32, 16 experts top-4 under sigmoid scores and a choice
bias."""

import dataclasses
import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import kv_layout
from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.continuous import ContinuousBatcher
from modelx_tpu.dl.families import FAMILIES, detect
from modelx_tpu.dl.serve import ModelServer
from modelx_tpu.dl.sharding import MIMO_V2_RULES, spec_for
from modelx_tpu.models import mimo_v2, mimo_v2_reference as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, MAX_LEN, SLOTS = 96, 128, 4


def write_checkpoint(path, cfg, seed=0):
    params = mimo_v2.init_params(cfg, jax.random.PRNGKey(seed))
    hf = mimo_v2.to_hf_state_dict(params, first=cfg.expert_first)
    st.write_safetensors(str(path / "model.safetensors"), hf)
    raw = mimo_v2.to_hf_config(cfg)
    (path / "config.json").write_text(json.dumps(raw))
    return params, hf, raw


def served(path, cfg, seed):
    _, hf, raw = write_checkpoint(path, cfg, seed)
    srv = ModelServer(str(path), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN)
    srv.load()
    return srv, hf, raw


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    """A whole checkpoint (all 16 experts), loaded through ModelServer."""
    return served(tmp_path_factory.mktemp("mimo_whole"),
                  mimo_v2.MimoV2Config.tiny(vocab_size=VOCAB), 0)


@pytest.fixture(scope="module")
def half(tmp_path_factory):
    """A checkpoint that holds experts 8-15 of 16 under the full router."""
    return served(tmp_path_factory.mktemp("mimo_half"), mimo_v2.MimoV2Config.tiny(
        vocab_size=VOCAB, expert_first=8, expert_count=8), 1)


def ref_logits(hf, raw, seq, positions=None, **how):
    return np.asarray(reference.forward(hf, raw, list(seq), positions=positions, **how))


def below_the_maximum(hf, raw, prompt, out):
    """How far the reference's logit of each engine token lies below its maximum."""
    seq = np.concatenate([prompt, out])
    logits = ref_logits(hf, raw, seq, positions=list(range(len(prompt) - 1, len(seq) - 1)))
    return logits.max(-1) - logits[np.arange(len(out)), out]


# -- config -------------------------------------------------------------------


def test_the_config_is_read_from_config_json_and_round_trips():
    cfg = mimo_v2.MimoV2Config.tiny(vocab_size=VOCAB, expert_first=4, expert_count=8)
    raw = mimo_v2.to_hf_config(cfg)
    assert raw["model_type"] == "mimo_v2_flash" and raw["n_routed_experts"] == 8
    assert mimo_v2.config_from_hf(json.loads(json.dumps(raw)), dtype=jnp.float32) == cfg


def test_the_benchmarks_configuration_reads_as_published():
    """Every width of the source under its own key: 64 query heads over 4 and
    8 KV heads, keys of 192 over values of 128, window 128 with sinks on the
    window layers only, top-8 of a router of 256 of which 16 are held."""
    with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2-flash-ep16-d7.json")) as f:
        raw = json.load(f)
    cfg = mimo_v2.config_from_hf(raw)
    assert cfg.window_layers == (False, True, True, True, True, False, True)
    assert cfg.sparse_layers == (False,) + (True,) * 6
    assert cfg.heads(0) == (64, 4, 192, 128) and cfg.heads(1) == (64, 8, 192, 128)
    assert (cfg.sliding_window, cfg.sinks(0), cfg.sinks(1)) == (128, False, True)
    assert (cfg.theta(0), cfg.theta(1)) == (5_000_000.0, 10_000.0)
    assert mimo_v2.rotary_dims(cfg, 192) == 64 and cfg.value_scale == 0.707
    assert (cfg.num_experts, cfg.held, cfg.top_k, cfg.routed_scale) == (256, (0, 16), 8, 1.0)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.moe_intermediate_size) == (4096, 16384, 2048)
    assert cfg.rms_eps == 1e-5 and mimo_v2.ring_len(cfg) == 144
    state = jax.eval_shape(lambda: mimo_v2.init_layer_state(cfg, 32, 32768))
    assert state["k0"].shape == (32, 32768, 768) and state["v0"].shape == (32, 32768, 512)
    assert state["k1"].shape == (32, 144, 1536) and state["v1"].shape == (32, 144, 1024)


@pytest.mark.parametrize("change,message", [
    ({"scoring_func": "softmax"}, "noaux_tc over sigmoid"),
    ({"topk_method": "greedy"}, "noaux_tc over sigmoid"),
    ({"n_group": 8, "topk_group": 4}, "group-limited"),
    ({"n_shared_experts": 1}, "shared experts"),
    ({"attention_bias": True}, "attention biases"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"rope_scaling": {"type": "yarn"}}, "rope scaling"),
    ({"expert_share": {"published": 16, "first": 12}}, "expert_share"),
    ({"hybrid_layer_pattern": [0, 1]}, "shorter than num_hidden_layers"),
])
def test_what_the_family_does_not_implement_is_refused_when_the_config_is_read(change, message):
    raw = mimo_v2.to_hf_config(mimo_v2.MimoV2Config.tiny(vocab_size=VOCAB))
    with pytest.raises(ValueError, match=message):
        mimo_v2.config_from_hf(dict(raw, **change))


def test_tensor_names_pick_the_family_and_its_rules():
    cfg = mimo_v2.MimoV2Config.tiny(vocab_size=VOCAB)
    names = list(mimo_v2.to_hf_state_dict(
        {k: np.zeros(v, np.float32) for k, v in mimo_v2.param_shapes(cfg).items()}))
    assert detect(names).name == "mimo_v2"
    assert "model.layers.1.self_attn.attention_sink_bias" in names
    assert "model.layers.0.self_attn.attention_sink_bias" not in names  # a full layer has none
    assert "model.layers.1.mlp.experts.15.down_proj.weight" in names
    P = jax.sharding.PartitionSpec
    assert spec_for("model.layers.1.self_attn.attention_sink_bias", MIMO_V2_RULES) == P("tp")
    assert spec_for("model.layers.1.mlp.gate.e_score_correction_bias", MIMO_V2_RULES) == P(None)
    assert spec_for("model.layers.1.mlp.experts.gate_proj.weight", MIMO_V2_RULES) == P(
        "ep", "tp", None)
    assert spec_for("model.layers.0.mlp.gate_proj.weight", MIMO_V2_RULES) == P("tp", None)


def test_rope_turns_the_first_lanes_by_halves_and_passes_the_rest():
    cfg = mimo_v2.MimoV2Config.tiny()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 3, 24)), jnp.float32)
    rot = mimo_v2.rotary_dims(cfg, 24)
    assert rot == 8
    got = mimo_v2.apply_rope(x, jnp.arange(9)[None], cfg.swa_rope_theta, rot)
    want = reference.rope(x[0], cfg.swa_rope_theta, rot)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])


# -- against the reference -----------------------------------------------------


def test_the_cacheless_forward_gives_the_references_logits(whole):
    srv, hf, raw = whole
    seq = np.random.default_rng(2).integers(1, VOCAB, 50)  # past the window of 16
    got = np.asarray(FAMILIES["mimo_v2"].forward(srv.params, jnp.asarray(seq[None]), srv.cfg))[0]
    np.testing.assert_allclose(got, ref_logits(hf, raw, seq), rtol=2e-4, atol=2e-4)


def test_the_sinks_and_the_value_scale_are_in_the_references_logits(whole):
    """The planted faults of the chip comparison move the logits here too:
    the reference without its sinks, or with ``v`` unscaled, is another model."""
    srv, hf, raw = whole
    seq = np.random.default_rng(3).integers(1, VOCAB, 40)
    right = ref_logits(hf, raw, seq)
    for fault in ({"drop_sinks": True}, {"value_scale": 1.0}):
        assert np.abs(ref_logits(hf, raw, seq, **fault) - right).max() > 1e-2, fault
    np.testing.assert_allclose(ref_logits(hf, raw, seq, head_block=3, dense_experts=True), right,
                               rtol=1e-4, atol=1e-4)  # the same sum, held otherwise


def test_the_flash_kernel_takes_wider_keys_and_sinks(whole):
    srv, hf, raw = whole
    seq = np.random.default_rng(4).integers(1, VOCAB, 40)
    got, _ = mimo_v2.forward(srv.params, jnp.asarray(seq[None]), srv.cfg,
                             attention_impl="flash+interpret")
    np.testing.assert_allclose(np.asarray(got)[0], ref_logits(hf, raw, seq), rtol=5e-4, atol=5e-4)


def test_a_half_held_checkpoint_folds_and_gives_the_references_logits(half):
    srv, hf, raw = half
    assert srv.params["model.layers.1.mlp.experts.up_proj.weight"].shape[0] == 8
    assert srv.params["model.layers.1.mlp.gate.weight"].shape[0] == 16
    seq = np.random.default_rng(5).integers(1, VOCAB, 30)
    got = np.asarray(FAMILIES["mimo_v2"].forward(srv.params, jnp.asarray(seq[None]), srv.cfg))[0]
    np.testing.assert_allclose(got, ref_logits(hf, raw, seq), rtol=2e-4, atol=2e-4)


def test_decode_through_a_dense_cache_follows_the_reference(whole):
    srv, hf, raw = whole
    prompt = np.random.default_rng(3).integers(1, VOCAB, (1, 20))
    out = np.asarray(FAMILIES["mimo_v2"].generate(
        srv.params, jnp.asarray(prompt), srv.cfg, max_new_tokens=30))[0, -30:]
    assert below_the_maximum(hf, raw, prompt[0], out).max() < 1e-3


@pytest.fixture(scope="module")
def engine(half):
    cb = ContinuousBatcher(half[0], max_slots=SLOTS, chunk_size=4)
    yield cb
    cb.close()


@pytest.fixture(scope="module")
def piece_engine(half):
    cb = ContinuousBatcher(half[0], max_slots=SLOTS, chunk_size=4, prefill_chunk=16)
    yield cb
    cb.close()


@pytest.mark.parametrize("prompt_len,new", [(5, 60), (16, 40), (40, 70), (33, 20), (70, 40)])
def test_prefill_then_decode_through_the_engine_follows_the_reference(half, engine, prompt_len, new):
    """Window 16, ring 32: prompts shorter and longer than the window and
    than the ring, outputs that wrap the ring more than once, through leaves
    of two KV-head counts and two widths in one ``LayerKindKV`` state."""
    _, hf, raw = half
    assert isinstance(engine.kv, kv_layout.LayerKindKV) and engine.kv.ring == 32
    prompt = np.random.default_rng(prompt_len).integers(1, VOCAB, (1, prompt_len))
    out = np.asarray(engine.generate(prompt, max_new_tokens=new))[0][-new:]
    assert below_the_maximum(hf, raw, prompt[0], out).max() < 1e-3


@pytest.mark.parametrize("prompt_len,new", [(17, 40), (40, 70), (70, 40), (100, 8)])
def test_a_prompt_landed_in_pieces_equals_the_same_prompt_in_one_piece(
        half, engine, piece_engine, prompt_len, new):
    """``--prefill-chunk 16`` over window 16, ring 32: two to seven pieces,
    the last one padded, prompts past the ring's wrap — each piece sees its
    slot's rings unrolled and the sinks, and the tokens are those of the
    prompt admitted whole and the reference's argmax."""
    _, hf, raw = half
    prompt = np.random.default_rng(prompt_len).integers(1, VOCAB, (1, prompt_len))
    before = piece_engine.snapshot().get("kv_ring_pieces", 0)
    out = np.asarray(piece_engine.generate(prompt, max_new_tokens=new))[0][-new:]
    assert piece_engine.snapshot()["kv_ring_pieces"] - before == -(-prompt_len // 16)
    np.testing.assert_array_equal(out, np.asarray(engine.generate(
        prompt, max_new_tokens=new))[0][-new:])
    assert below_the_maximum(hf, raw, prompt[0], out).max() < 1e-3


@pytest.fixture(scope="module")
def kernel_engine(half):
    """The same engine with both decode kernels asked for by name (interpreted)."""
    srv = half[0]
    family = dataclasses.replace(FAMILIES["mimo_v2"], layer_kind_decode_fns=lambda cfg, mesh=None: {
        **FAMILIES["mimo_v2"].layer_kind_decode_fns(cfg, mesh=mesh),
        "fwd": lambda p, t, kv_cache, cache_offset, mesh=mesh, **told: mimo_v2.forward(
            p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset, mesh=mesh, ring=True,
            attention_impl="ragged+interpret", **told),
        "attention_impl": "ragged+interpret"})
    shim = type("Srv", (), {})()
    shim.__dict__.update(srv.__dict__)
    shim.family = family
    cb = ContinuousBatcher(shim, max_slots=SLOTS, chunk_size=4, prefill_chunk=16)
    yield cb
    cb.close()


def test_rows_at_different_depths_through_both_kernels_follow_the_reference(half, kernel_engine):
    """Four requests of different lengths at once — one landed in pieces —
    with the ragged kernel on the full layers' lines of 2 x 24 and the ring
    kernel, sinks as its starting state, on the window layers' of 4 x 24."""
    _, hf, raw = half
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, VOCAB, (1, n)) for n in (3, 18, 35, 50)]
    outs: list = [None] * 4

    def run(i):
        outs[i] = np.asarray(kernel_engine.generate(prompts[i], max_new_tokens=45))[0][-45:]

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for prompt, out in zip(prompts, outs):
        assert below_the_maximum(hf, raw, prompt[0], out).max() < 1e-3
    snap = kernel_engine.snapshot()
    assert snap["attn_ring_kernel_calls"] == snap["attn_ring_calls"] > 0
    assert snap["attn"]["sink_calls"] == snap["attn_ring_calls"]  # every ring call carried its sinks
    assert 0 < snap["attn_kv_positions_read"] <= snap["attn_kv_positions_cached"]


def test_the_engine_counts_its_expert_layers_its_sinks_and_its_caches_by_kind(half, engine):
    srv = half[0]
    engine.generate(np.ones((1, 8), np.int32), max_new_tokens=12)
    snap = engine.snapshot()
    moe, kv, attn = snap["moe"], snap["kv"], snap["attn"]
    assert (moe["held_experts"], moe["published_experts"], moe["sparse_layers"]) == (8, 16, 4)
    assert (attn["window_layers"], attn["sink_layers"]) == (3, 3)
    assert attn["sink_calls"] % 3 == 0 and attn["sink_calls"] > 0  # three window layers a step
    assert moe["assignments"] % (SLOTS * srv.cfg.top_k * 4) == 0
    assert 0 < moe["assignments_held"] < moe["assignments"]
    # float32 lines: 2 x (24 + 16) on two full layers, 4 x (24 + 16) on three rings of 32
    assert kv["bytes_full"] == SLOTS * MAX_LEN * 2 * (2 * 40) * 4
    assert kv["bytes_window"] == SLOTS * 32 * 3 * (4 * 40) * 4
    assert kv["window_positions"] == 32


@pytest.mark.parametrize("option,message", [
    ({"page_size": 16}, "--kv-page-size"),
    ({"prefix_cache": object()}, "--prefix-cache"),
    ({"speculative_k": 4}, "--speculative-k"),
])
def test_what_a_ring_cannot_carry_is_refused_with_its_name(half, option, message):
    with pytest.raises(kv_layout.Refused, match=message):
        ContinuousBatcher(half[0], max_slots=SLOTS, chunk_size=4, allocate=False,
                          prefill_chunk=16, **option)


def test_the_benchmarks_copy_of_the_reference_is_the_repos():
    with open(os.path.join(ROOT, "modelx_tpu", "models", "mimo_v2_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "references", "mimo_v2.py")) as f:
        assert f.read() == ours
