"""The laguna family (models/laguna.py) on the normal serving path, held
against the plain float32 reference (models/laguna_reference.py) at a small
size on the CPU: seeded random weights, window 16, a ring of 32."""

import base64
import dataclasses
import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import kv_layout
from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.continuous import ContinuousBatcher
from modelx_tpu.dl.families import FAMILIES, detect
from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
from modelx_tpu.dl.sharding import LAGUNA_RULES, spec_for
from modelx_tpu.models import laguna, laguna_reference as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, MAX_LEN, SLOTS = 96, 128, 4


def write_checkpoint(path, cfg, seed=0):
    params = laguna.init_params(cfg, jax.random.PRNGKey(seed))
    hf = laguna.to_hf_state_dict(params, first=cfg.expert_first)
    st.write_safetensors(str(path / "model.safetensors"), hf)
    raw = laguna.to_hf_config(cfg)
    (path / "config.json").write_text(json.dumps(raw))
    return params, hf, raw


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    """A whole checkpoint (all 16 experts), loaded through ModelServer."""
    d = tmp_path_factory.mktemp("laguna_whole")
    cfg = laguna.LagunaConfig.tiny(vocab_size=VOCAB)
    params, hf, raw = write_checkpoint(d, cfg)
    srv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN)
    srv.load()
    return srv, hf, raw


@pytest.fixture(scope="module")
def half(tmp_path_factory):
    """A checkpoint that holds experts 8-15 of 16 under the full router."""
    d = tmp_path_factory.mktemp("laguna_half")
    cfg = laguna.LagunaConfig.tiny(vocab_size=VOCAB, expert_first=8, expert_count=8)
    params, hf, raw = write_checkpoint(d, cfg, seed=1)
    srv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN)
    srv.load()
    return srv, hf, raw, d


def ref_logits(hf, raw, seq, positions=None):
    return np.asarray(reference.forward(hf, raw, list(seq), positions=positions))


# -- config -------------------------------------------------------------------


def test_the_config_is_read_from_config_json_and_round_trips():
    cfg = laguna.LagunaConfig.tiny(vocab_size=VOCAB, expert_first=4, expert_count=8)
    assert laguna.config_from_hf(laguna.to_hf_config(cfg), dtype=jnp.float32) == cfg
    assert cfg.num_heads_per_layer == (4, 6, 6, 6, 4) and cfg.window(1) == 16 and not cfg.window(0)


def test_the_published_config_reads_as_published():
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna-s-2.1-ep2-d5.json")) as f:
        cfg = laguna.config_from_hf(json.load(f))
    assert (cfg.num_experts, cfg.held, cfg.top_k, cfg.routed_scale) == (256, (0, 128), 10, 2.5)
    assert cfg.num_heads_per_layer == (48, 72, 72, 72, 48) and cfg.mlp_layer_types[0] == "dense"
    assert cfg.rope_full.rope_type == "yarn" and cfg.rope_full.partial_rotary_factor == 0.5
    assert cfg.rope_sliding.theta == 10000.0 and laguna.ring_len(cfg) == 528
    shapes = laguna.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 5_572_076_544  # ISSUE 33's table
    assert shapes["model.layers.1.mlp.gate.weight"] == (256, 3072)
    assert shapes["model.layers.1.mlp.experts.gate_proj.weight"] == (128, 1024, 3072)


@pytest.mark.parametrize("change,message", [
    ({"gating": "per-channel"}, "gating"),
    ({"moe_router_logit_softcapping": 30.0}, "soft-capping"),
    ({"moe_apply_router_weight_on_input": True}, "on the expert input"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"attention_bias": True}, "biases"),
    ({"expert_share": {"published": 16, "first": 12}}, "expert_share"),
    ({"layer_types": ["full_attention", "linear_attention"] * 3}, "unknown layer type"),
    ({"rope_parameters": {"full_attention": {"rope_type": "longrope"}}}, "rope_type"),
])
def test_what_the_family_does_not_implement_is_refused_when_the_config_is_read(change, message):
    raw = dict(laguna.to_hf_config(laguna.LagunaConfig.tiny(vocab_size=VOCAB)), **change)
    with pytest.raises(ValueError, match=message):
        laguna.config_from_hf(raw)


def test_without_config_json_the_family_says_why(tmp_path):
    cfg = laguna.LagunaConfig.tiny(vocab_size=VOCAB)
    write_checkpoint(tmp_path, cfg)
    os.remove(tmp_path / "config.json")
    with pytest.raises(ValueError, match="config.json must lie beside"):
        ModelServer(str(tmp_path), mesh_spec="dp=1", dtype="float32").load()


def test_tensor_names_pick_the_family_and_its_rules():
    names = list(laguna.to_hf_state_dict(
        laguna.init_params(laguna.LagunaConfig.tiny(vocab_size=8), jax.random.PRNGKey(0))))
    assert detect(names).name == "laguna"
    assert spec_for("model.layers.1.mlp.experts.up_proj.weight", LAGUNA_RULES) == ("ep", "tp", None)
    assert spec_for("model.layers.1.mlp.experts.down_proj.weight", LAGUNA_RULES) == ("ep", None, "tp")
    assert spec_for("model.layers.1.mlp.gate.weight", LAGUNA_RULES) == (None, None)
    assert spec_for("model.layers.1.mlp.shared_expert.up_proj.weight", LAGUNA_RULES) == ("tp", None)
    assert spec_for("model.layers.1.self_attn.g_proj.weight", LAGUNA_RULES) == ("tp", None)


# -- rope ---------------------------------------------------------------------


@pytest.mark.parametrize("kind", [laguna.FULL, laguna.SLIDING])
def test_rope_matches_the_references(kind):
    """YaRN on half of each head with its attention factor, and plain rope
    on the whole head, against the reference's own arithmetic."""
    cfg = laguna.LagunaConfig.tiny()
    raw = laguna.to_hf_config(cfg)["rope_parameters"][kind]
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 3, cfg.head_dim), jnp.float32)
    spec = cfg.rope_full if kind == laguna.FULL else cfg.rope_sliding
    got = laguna.apply_rope(x[None], jnp.arange(40)[None], spec)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(reference.rope(x, raw)), atol=2e-5)
    rotated = int(cfg.head_dim * spec.partial_rotary_factor)
    np.testing.assert_array_equal(np.asarray(got[..., rotated:]), np.asarray(x[..., rotated:]))
    assert rotated == (8 if kind == laguna.FULL else 16)


def test_yarn_blends_interpolated_and_extrapolated_frequencies():
    spec = laguna.LagunaConfig().rope_full  # the published parameters
    inv, factor, dim = laguna.rope_frequencies(spec, 128)
    plain = 1.0 / spec.theta ** (np.arange(0, 64, 2) / 64)
    assert dim == 64 and factor == pytest.approx(1.4852030263919618)
    assert inv[0] == pytest.approx(plain[0])  # the fastest dimension extrapolates
    assert inv[-1] == pytest.approx(plain[-1] / 128)  # the slowest interpolates
    assert np.all(np.diff(inv) < 0)
    ref_inv, ref_factor, _ = reference.inverse_frequencies(
        laguna.to_hf_config(laguna.LagunaConfig())["rope_parameters"][laguna.FULL], 128)
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_factor == pytest.approx(factor)


# -- forward against the reference ----------------------------------------------


def test_the_cacheless_forward_gives_the_references_logits(whole):
    srv, hf, raw = whole
    toks = np.random.default_rng(0).integers(1, VOCAB, (2, 40))
    got = np.asarray(laguna.forward(srv.params, jnp.asarray(toks), srv.cfg)[0])
    for b in range(2):
        np.testing.assert_allclose(got[b], ref_logits(hf, raw, toks[b]), atol=2e-4)


def test_layers_with_other_head_counts_give_the_references_logits(tmp_path):
    """Head counts by layer are the config's, not a constant: swap them."""
    cfg = laguna.LagunaConfig.tiny(vocab_size=VOCAB, num_heads_per_layer=(6, 2, 4, 8, 2))
    params, hf, raw = write_checkpoint(tmp_path, cfg, seed=5)
    assert params["model.layers.3.self_attn.q_proj.weight"].shape[0] == 8 * cfg.head_dim
    assert params["model.layers.1.self_attn.g_proj.weight"].shape[0] == 2
    toks = np.random.default_rng(1).integers(1, VOCAB, (1, 24))
    got = np.asarray(laguna.forward(params, jnp.asarray(toks), cfg)[0])
    np.testing.assert_allclose(got[0], ref_logits(hf, raw, toks[0]), atol=2e-4)


def test_a_half_held_checkpoint_folds_and_gives_the_references_logits(half):
    """The loader's fold of experts 8-15 under a router of 16, the bytes it
    reports, and the forward of the share against the reference's."""
    srv, hf, raw, d = half
    assert srv.family.name == "laguna" and srv.cfg.held == (8, 8) and srv.cfg.num_experts == 16
    w = srv.params["model.layers.2.mlp.experts.down_proj.weight"]
    assert w.shape == (8, srv.cfg.hidden_size, srv.cfg.moe_intermediate_size)
    np.testing.assert_array_equal(np.asarray(w[3]), hf["model.layers.2.mlp.experts.11.down_proj.weight"])
    assert srv.params["model.layers.2.mlp.gate.weight"].shape == (16, srv.cfg.hidden_size)
    assert srv.stats["load_bytes"] == sum(v.nbytes for v in hf.values())
    toks = np.random.default_rng(2).integers(1, VOCAB, (1, 30))
    got = np.asarray(laguna.forward(srv.params, jnp.asarray(toks), srv.cfg)[0])
    np.testing.assert_allclose(got[0], ref_logits(hf, raw, toks[0]), atol=2e-4)


def test_decode_through_a_dense_cache_follows_the_reference(whole):
    srv, hf, raw = whole
    prompt = np.random.default_rng(3).integers(1, VOCAB, (1, 20))
    out = np.asarray(FAMILIES["laguna"].generate(
        srv.params, jnp.asarray(prompt), srv.cfg, max_new_tokens=30))
    seq = np.concatenate([prompt[0], out[0, -30:]])
    assert (ref_logits(hf, raw, seq).argmax(-1)[19:-1] == seq[20:]).all()


@pytest.fixture(scope="module")
def engine(half):
    cb = ContinuousBatcher(half[0], max_slots=SLOTS, chunk_size=4)
    yield cb
    cb.close()


@pytest.mark.parametrize("prompt_len,new", [(5, 60), (16, 40), (40, 70), (33, 20), (70, 40)])
def test_prefill_then_decode_through_the_engine_follows_the_reference(half, engine, prompt_len, new):
    """Window 16, ring 32: prompts shorter and longer than the window and
    than the ring, outputs that wrap the ring more than once. Every token
    the engine emits is the reference's argmax of the full forward, and the
    reference's logit of it is its maximum to float32 rounding."""
    _, hf, raw, _ = half
    assert isinstance(engine.kv, kv_layout.LayerKindKV) and engine.kv.ring == 32
    prompt = np.random.default_rng(prompt_len).integers(1, VOCAB, (1, prompt_len))
    out = np.asarray(engine.generate(prompt, max_new_tokens=new))[0][-new:]
    seq = np.concatenate([prompt[0], out])
    logits = ref_logits(hf, raw, seq, positions=list(range(prompt_len - 1, len(seq) - 1)))
    below = logits.max(-1) - logits[np.arange(new), out]
    assert below.max() < 1e-3, (int(below.argmax()), float(below.max()))


@pytest.mark.parametrize("which", ["engine", "ragged_engine"])
def test_rows_at_different_depths_share_the_rings(half, which, request):
    """Four requests of different lengths at once: each row's ring is its
    own, whatever the others' offsets are — under the reference, and with the
    ring kernel named (one call, rows at their own depths, idle slots at 0)."""
    engine = request.getfixturevalue(which)
    _, hf, raw, _ = half
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, VOCAB, (1, n)) for n in (3, 18, 35, 50)]
    outs: list = [None] * 4

    def run(i):
        outs[i] = np.asarray(engine.generate(prompts[i], max_new_tokens=45))[0][-45:]

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for prompt, out in zip(prompts, outs):
        seq = np.concatenate([prompt[0], out])
        want = ref_logits(hf, raw, seq).argmax(-1)[prompt.shape[1] - 1:-1]
        np.testing.assert_array_equal(out, want)


def test_the_engine_counts_its_expert_layers_and_its_caches_by_kind(half, engine):
    srv = half[0]
    engine.generate(np.ones((1, 8), np.int32), max_new_tokens=12)
    snap = engine.snapshot()
    moe, kv = snap["moe"], snap["kv"]
    assert (moe["held_experts"], moe["published_experts"], moe["sparse_layers"]) == (8, 16, 4)
    steps = snap["chunks"] * engine.chunk_size
    # every slot routes in every step (idle ones too): top-4 of 16 a sparse layer
    assert moe["assignments"] % (SLOTS * srv.cfg.top_k * 4) == 0
    assert 0 < moe["assignments"] <= steps * SLOTS * srv.cfg.top_k * 4
    assert 0.25 < moe["assignments_held"] / moe["assignments"] < 0.75
    assert 0 < moe["experts_hit"] <= moe["assignments"] // (SLOTS * srv.cfg.top_k) * 8
    leaf = SLOTS * srv.cfg.num_kv_heads * srv.cfg.head_dim * 4  # float32 here
    assert kv == {"bytes_full": 2 * 2 * MAX_LEN * leaf, "bytes_window": 3 * 2 * 32 * leaf,
                  "window_positions": 32, "positions_full": 0, "positions_window": 0}


@pytest.fixture(scope="module")
def ragged_engine(half):
    """The engine with the decode kernels asked for by name (pallas interpret
    mode): the two full layers read two 64-position blocks of their
    128-position caches at most in the ragged kernel, the three window layers
    their rings of 32 whole in the ring kernel. The family says what name its
    forward asks by (``attention_impl``), so that the layout's count of ring
    reads — from the rule, never from a trace — follows it."""
    import copy

    srv = half[0]

    def by_name(cfg, mesh=None):
        fns = srv.family.layer_kind_decode_fns(cfg, mesh=mesh)

        def fwd(p, t, kv_cache, cache_offset, mesh=mesh):
            return laguna.forward(p, t, cfg, kv_cache=kv_cache, cache_offset=cache_offset,
                                  mesh=mesh, ring=True, attention_impl="ragged+interpret")
        return {**fns, "fwd": fwd, "attention_impl": "ragged+interpret"}

    named = copy.copy(srv)
    named.family = dataclasses.replace(srv.family, layer_kind_decode_fns=by_name)
    cb = ContinuousBatcher(named, max_slots=SLOTS, chunk_size=4)
    yield cb
    cb.close()


@pytest.mark.parametrize("prompt_len,new", [(5, 60), (16, 40), (40, 70), (33, 20), (70, 40)])
def test_the_ragged_kernel_on_the_full_layers_follows_the_reference(half, ragged_engine,
                                                                    prompt_len, new):
    """As the engine's own test above, kernels named: every token is the
    float32 reference's argmax of the full forward, to rounding — through
    contexts that end in a full layer's first block and in its second, and
    through rings not yet full, full, and wrapped more than once."""
    _, hf, raw, _ = half
    prompt = np.random.default_rng(prompt_len).integers(1, VOCAB, (1, prompt_len))
    out = np.asarray(ragged_engine.generate(prompt, max_new_tokens=new))[0][-new:]
    seq = np.concatenate([prompt[0], out])
    logits = ref_logits(hf, raw, seq, positions=list(range(prompt_len - 1, len(seq) - 1)))
    below = logits.max(-1) - logits[np.arange(new), out]
    assert below.max() < 1e-3, (int(below.argmax()), float(below.max()))


def test_the_ragged_engine_counts_its_full_layers_reads_beside_the_expert_counts(
        half, ragged_engine, engine):
    ragged_engine.generate(np.ones((1, 8), np.int32), max_new_tokens=12)
    snap = ragged_engine.snapshot()
    steps = snap["chunks"] * ragged_engine.chunk_size
    # the two full layers of five; the rings' reads are whole by nature and
    # have a count of their own: every step, each of the three in the kernel
    assert snap["attn_ring_kernel_calls"] == snap["attn_ring_calls"] == steps * 3
    assert snap["attn_kv_positions_cached"] == steps * SLOTS * MAX_LEN * 2
    assert steps * SLOTS * 64 * 2 <= snap["attn_kv_positions_read"] < (
        snap["attn_kv_positions_cached"])
    assert snap["moe"]["assignments"] % (SLOTS * half[0].cfg.top_k * 4) == 0
    assert snap["moe"]["assignments"] > 0
    assert not any(k.startswith("attn_") for k in engine.snapshot())


def test_an_idle_slots_offset_is_held_at_zero_on_a_cache_per_layer_kind(engine):
    """As ``tests/test_continuous.py`` holds for the dense and the paged
    layout: a slot without a row starts every dispatch at offset 0."""
    seen, chunk_args = [], engine._chunk_args

    def recording(filtered):
        seen.append(engine._offsets[engine._free].copy())
        return chunk_args(filtered)

    engine._chunk_args = recording
    try:
        engine.generate(np.ones((1, 8), np.int32), max_new_tokens=60)
    finally:
        del engine._chunk_args
    assert len(seen) >= 3 and all(len(idle) >= SLOTS - 1 for idle in seen)
    assert not np.concatenate(seen).any()
    assert not engine._offsets.any()


def test_the_chunk_programs_name_carries_its_depth(engine):
    """A device trace must say how many steps a run of the program made."""
    args = (engine.server.params, engine._cache, engine._tok, *engine._chunk_args(False))
    for n_steps, name in [(4, "_chunk_impl_d1"), (16, "_chunk_impl_d4"), (6, "_chunk_impl_s6")]:
        jaxpr = jax.make_jaxpr(lambda *a, n=n_steps: engine._chunk_prog.jit(*a, n_steps=n))(*args)
        assert jaxpr.eqns[0].params["name"] == name


# -- what is refused -------------------------------------------------------------


@pytest.mark.parametrize("option,message", [
    ({"page_size": 16}, "--kv-page-size"),
    ({"prefix_cache": object()}, "--prefix-cache"),
    ({"prefix_cache": object(), "prefill_chunk": 32}, "--prefix-cache"),
    ({"speculative_k": 4}, "--speculative-k"),
])
def test_an_engine_option_the_layout_cannot_serve_is_refused_with_its_name(half, option, message):
    with pytest.raises(kv_layout.Refused, match=message):
        ContinuousBatcher(half[0], max_slots=SLOTS, chunk_size=4, allocate=False, **option)


@pytest.fixture(scope="module")
def piece_engine(half):
    cb = ContinuousBatcher(half[0], max_slots=SLOTS, chunk_size=4, prefill_chunk=16)
    yield cb
    cb.close()


@pytest.mark.parametrize("prompt_len,new", [(17, 40), (40, 70), (70, 40), (100, 8)])
def test_a_prompt_landed_in_pieces_over_the_rings_follows_the_reference(
        half, engine, piece_engine, prompt_len, new):
    """``--prefill-chunk 16`` over window 16, ring 32: a piece sees its slot's
    ring unrolled, attends it and itself, and hands back the last 32 positions
    (dl/kv_layout.LayerKindKV.view / put_piece) — two to seven pieces, the last
    one padded, prompts past the ring's wrap. Every token is the reference's
    argmax and the one the same prompt gives landed in one piece."""
    _, hf, raw, _ = half
    prompt = np.random.default_rng(prompt_len).integers(1, VOCAB, (1, prompt_len))
    before = piece_engine.snapshot().get("kv_ring_pieces", 0)
    out = np.asarray(piece_engine.generate(prompt, max_new_tokens=new))[0][-new:]
    assert piece_engine.snapshot()["kv_ring_pieces"] - before == -(-prompt_len // 16)
    whole = np.asarray(engine.generate(prompt, max_new_tokens=new))[0][-new:]
    np.testing.assert_array_equal(out, whole)
    seq = np.concatenate([prompt[0], out])
    logits = ref_logits(hf, raw, seq, positions=list(range(prompt_len - 1, len(seq) - 1)))
    below = logits.max(-1) - logits[np.arange(new), out]
    assert below.max() < 1e-3, (int(below.argmax()), float(below.max()))


@pytest.mark.parametrize("flags,message", [
    ({"kv_page_size": 16}, "--kv-page-size"),
    ({"kv_page_size": 16, "prefill_chunk": 32}, "--kv-page-size"),
])
def test_a_refused_option_ends_the_load_of_a_continuous_pod(half, flags, message):
    """At start-up, not at the first request: ``engine_at_load`` lets every
    other failure wait for a retry, this one it raises."""
    srv = ModelServer(str(half[3]), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN)
    sset = ServerSet({"default": srv}, continuous_batch=True, max_slots=SLOTS, **flags)
    with pytest.raises(RuntimeError, match=message):
        sset.load_all()


def test_a_prefix_cache_is_refused_at_the_load_too(half):
    srv = ModelServer(str(half[3]), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN,
                      prefix_cache_size=4)
    sset = ServerSet({"default": srv}, continuous_batch=True, max_slots=SLOTS)
    with pytest.raises(RuntimeError, match="--prefix-cache"):
        sset.load_all()


@pytest.mark.parametrize("flags,limit", [
    ({}, 1024),                                                   # the plain paths compile per value
    ({"continuous_batch": True}, 4096),                           # the engine's span is the bound
    ({"continuous_batch": True, "max_new_tokens_limit": 64}, 64),  # an operator's cap stands
])
def test_the_engine_bounds_max_new_tokens_by_the_slots_span(flags, limit):
    """A reasoning job asks for thousands of tokens: under --continuous-batch
    the default cap is --max-seq-len (the engine validates the span and
    compiles nothing per value), elsewhere the 1024 it was."""
    srv = ModelServer("/nonexistent", mesh_spec="dp=1", dtype="float32", max_seq_len=4096)
    assert ServerSet({"default": srv}, **flags).max_new_tokens_limit == limit


# -- the served surface ----------------------------------------------------------


def post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", path, body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def test_v1_forward_returns_the_logits_of_named_positions(whole):
    """The optional field of ``/v1/forward``: float32 logits where asked,
    against the reference; without it the answer is what it was."""
    srv, hf, raw = whole
    httpd = serve(ServerSet({"default": srv}), listen="127.0.0.1:0")
    port = httpd.server_address[1]
    try:
        toks = np.random.default_rng(4).integers(1, VOCAB, (2, 24)).tolist()
        status, plain = post(port, "/v1/forward", {"tokens": toks})
        assert status == 200 and set(plain) == {"logits_argmax"}
        status, body = post(port, "/v1/forward", {"tokens": toks, "logits_at": [0, 7, 23]})
        assert status == 200 and body["logits_argmax"] == plain["logits_argmax"]
        got = body["logits"]
        assert (got["dtype"], got["shape"], got["positions"]) == ("float32", [2, 3, VOCAB], [0, 7, 23])
        logits = np.frombuffer(base64.b64decode(got["b64"]), np.float32).reshape(got["shape"])
        for b in range(2):
            np.testing.assert_allclose(logits[b], ref_logits(hf, raw, toks[b], [0, 7, 23]), atol=2e-4)
        assert logits.argmax(-1)[:, 1].tolist() == [row[7] for row in plain["logits_argmax"]]
        for bad in ([24], [-1], [], "x"):
            status, body = post(port, "/v1/forward", {"tokens": toks, "logits_at": bad})
            assert status == 400 and "logits_at" in body["error"]
    finally:
        httpd.shutdown()


def test_the_benchmarks_copy_of_the_reference_is_the_repos():
    with open(os.path.join(ROOT, "modelx_tpu", "models", "laguna_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "references", "laguna.py")) as f:
        assert f.read() == ours


# -- a pod that ends with the process that started it ----------------------------


def test_exit_with_parent_is_an_option_and_off_by_default():
    from modelx_tpu.dl.serve_main import main

    (opt,) = [p for p in main.params if p.name == "exit_with_parent"]
    assert opt.default is False and "--exit-with-parent" in opt.opts


def test_a_child_that_watches_its_parent_is_gone_after_the_parent_is_killed(tmp_path):
    """SIGKILL the parent — no handler, no ``finally`` runs — and the child
    that asked to end with it is gone; one that did not ask lives on."""
    child = ("import sys, time; sys.path.insert(0, %r); "
             "from modelx_tpu.dl.serve_main import exit_when_parent_is_gone; "
             "{watch}; print('up', flush=True); time.sleep(120)" % ROOT)
    parent = (
        "import subprocess, sys, time\n"
        "kids = [subprocess.Popen([sys.executable, '-c', sys.argv[i]], stdout=subprocess.PIPE)"
        " for i in (1, 2)]\n"
        "for k in kids: k.stdout.readline()\n"
        "print(*[k.pid for k in kids], flush=True)\n"
        "time.sleep(120)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", parent,
                          child.format(watch="exit_when_parent_is_gone(0.05)"),
                          child.format(watch="pass")], stdout=subprocess.PIPE, env=env)
    watcher = bystander = None
    try:
        watcher, bystander = map(int, p.stdout.readline().split())
        os.kill(p.pid, signal.SIGKILL)
        p.wait(10)

        def alive(pid):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                return False
            with open(f"/proc/{pid}/stat") as f:  # a zombie no longer runs
                return f.read().rsplit(")", 1)[1].split()[0] != "Z"

        deadline = time.monotonic() + 10
        while alive(watcher) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not alive(watcher), "the watching child outlived its parent"
        assert alive(bystander), "the control child should not have noticed"
    finally:
        for pid in (p.pid, watcher, bystander):
            try:
                if pid:
                    os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
