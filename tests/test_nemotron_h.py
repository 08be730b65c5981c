"""The nemotron_h family (models/nemotron_h.py) on the normal serving path,
held against the plain float32 reference (models/nemotron_h_reference.py) at a
small size on the CPU: seeded random weights, Mamba-2 heads of 4 over a state
of 8 in 2 groups, chunks of 8 positions, 16 sigmoid-routed relu2 experts top-3
in a latent of 16 beside a shared one, 2 KV heads under 4 query heads. Logits,
not tokens; every tolerance is float32 rounding of sums a few dozen terms long
(1e-4 absolute on logits whose standard deviation is about 1), except where
said."""

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
from modelx_tpu.dl.sharding import NEMOTRON_H_RULES, spec_for
from modelx_tpu.models import nemotron_h as nh, nemotron_h_reference as reference
from modelx_tpu.ops import moe, ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, MAX_LEN, SLOTS = 96, 128, 4
ATOL = 1e-4
# the first period of the published pattern, and one layer kind at a time
PATTERNS = {"mamba": "M", "experts": "E", "attention": "*", "dense": "-",
            "eleven_layers": "MEMEMEM*EME"}


@pytest.fixture(autouse=True, scope="module")
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def model_of(pattern: str, seed=0, **over):
    cfg = nh.NemotronHConfig.tiny(vocab_size=VOCAB, pattern=pattern, **over)
    params = nh.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params, nh.to_hf_state_dict(params, cfg.expert_first), nh.to_hf_config(cfg)


def ref_logits(hf, raw, seq, positions=None):
    return np.asarray(reference.forward(hf, raw, list(seq), positions=positions))


# -- ops/ssm ----------------------------------------------------------------------


def ssm_inputs(t, rows=2, heads=8, p=4, groups=2, n=8, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    dt = jax.nn.softplus(f(rows, t, heads))
    a = -jnp.exp(0.5 * f(heads))
    return f(rows, t, heads, p), dt, a, f(rows, t, groups, n), f(rows, t, groups, n), f(heads)


def stepped(x, dt, a, b, c, d, state, upto=None):
    """``ssm.step`` iterated over a block's positions, a row stopping at its
    own ``upto``."""
    ys = []
    for i in range(x.shape[1]):
        live = None if upto is None else jnp.asarray(upto) > i
        y, state = ssm.step(x[:, i], dt[:, i], a, b[:, i], c[:, i], d, state, live=live)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("chunk", [4, 7, 23, 64])
def test_the_chunked_scan_is_the_step_iterated(chunk):
    x, dt, a, b, c, d = ssm_inputs(37)
    start = jnp.asarray(np.random.default_rng(1).standard_normal((2, 8, 4, 8)), jnp.float32)
    want_y, want_s = stepped(x, dt, a, b, c, d, start)
    got_y, got_s = ssm.chunked(x, dt, a, b, c, d, start, chunk=chunk)
    np.testing.assert_allclose(got_y, want_y, atol=ATOL)
    np.testing.assert_allclose(got_s, want_s, atol=ATOL)


def test_a_padded_bucket_enters_neither_the_state_nor_the_outputs_before_it():
    """A 32-position bucket of which 21 and 32 positions are real: the state
    is the one the real positions leave, whatever the padding holds."""
    x, dt, a, b, c, d = ssm_inputs(32, seed=3)
    valid = jnp.asarray([21, 32])
    zero = jnp.zeros((2, 8, 4, 8), jnp.float32)
    want_y, want_s = stepped(x, dt, a, b, c, d, zero, upto=valid)
    got_y, got_s = ssm.chunked(x, dt, a, b, c, d, zero, valid_len=valid, chunk=8)
    np.testing.assert_allclose(got_s, want_s, atol=ATOL)
    np.testing.assert_allclose(got_y[0, :21], want_y[0, :21], atol=ATOL)
    np.testing.assert_allclose(got_y[1], want_y[1], atol=ATOL)


def test_a_fast_head_never_overflows_over_a_long_chunk():
    x, dt, a, b, c, d = ssm_inputs(256, rows=1, seed=5)
    y, s = ssm.chunked(x, 20.0 * dt, 50.0 * a, b, c, d, jnp.zeros((1, 8, 4, 8)), chunk=256)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(s)).all()


def test_a_row_that_is_not_live_keeps_its_state_and_its_tail_bit_for_bit():
    x, dt, a, b, c, d = ssm_inputs(1, rows=3, seed=7)
    rng = np.random.default_rng(8)
    state = jnp.asarray(rng.standard_normal((3, 8, 4, 8)), jnp.float32)
    tail = jnp.asarray(rng.standard_normal((3, 3, 6)), jnp.float32)
    live = jnp.asarray([True, False, True])
    _, new = ssm.step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], d, state, live=live)
    _, new_tail = ssm.conv_step(jnp.ones((3, 6)), tail, jnp.ones((6, 4)), None, live=live)
    np.testing.assert_array_equal(np.asarray(new)[1], np.asarray(state)[1])
    np.testing.assert_array_equal(np.asarray(new_tail)[1], np.asarray(tail)[1])
    assert not np.array_equal(np.asarray(new)[0], np.asarray(state)[0])
    np.testing.assert_array_equal(np.asarray(new_tail)[0, :2], np.asarray(tail)[0, 1:])


@pytest.mark.parametrize("valid", [None, [11, 2], [0, 16]])
def test_the_convolution_in_blocks_is_the_convolution_a_position_at_a_time(valid):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 16, 6)), jnp.float32)
    w, bias = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32), jnp.arange(6.0)
    tail = jnp.asarray(rng.standard_normal((2, 3, 6)), jnp.float32)
    upto = [16, 16] if valid is None else valid
    want, t = [], tail
    for i in range(16):
        y, t = ssm.conv_step(x[:, i], t, w, bias, live=jnp.asarray(upto) > i)
        want.append(y)
    got, got_tail = ssm.conv_block(x, tail, w, bias,
                                   valid_len=None if valid is None else jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(got_tail), np.asarray(t))
    for row, n in enumerate(upto):
        np.testing.assert_allclose(np.asarray(got)[row, :n], np.stack(want, 1)[row, :n], atol=1e-5)


# -- the config -----------------------------------------------------------------------


def published() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", "nemotron-3-super-ep4-d11.json")) as f:
        return json.load(f)


def test_the_config_is_read_from_config_json_and_round_trips():
    cfg = nh.NemotronHConfig.tiny(vocab_size=VOCAB, expert_first=4, expert_count=8)
    assert nh.config_from_hf(json.loads(json.dumps(nh.to_hf_config(cfg))), dtype=jnp.float32) == cfg


def test_the_benchmarks_configuration_reads_as_published():
    cfg = nh.config_from_hf(published())
    assert cfg.pattern == "MEMEMEM*EME" and cfg.num_layers == 11
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state_size, cfg.n_groups) == (128, 64, 128, 8)
    assert (cfg.mamba_inner, cfg.conv_dim, cfg.conv_kernel, cfg.chunk_size) == (8192, 10240, 4, 128)
    assert (cfg.num_experts, cfg.held, cfg.top_k) == (512, (0, 128), 22)
    assert (cfg.moe_intermediate_size, cfg.moe_latent_size, cfg.shared_intermediate_size) == (
        2688, 1024, 5376)
    assert cfg.norm_topk_prob and cfg.routed_scale == 5.0 and cfg.rms_eps == 1e-5
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size) == (32, 2, 128, 32768)
    shapes = nh.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 4_648_163_712  # ISSUE 46's reckoning


@pytest.mark.parametrize("change,message", [
    ({"n_group": 8, "topk_group": 4}, "group-limited routing"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"moe_shared_expert_overlap": True}, "moe_shared_expert_overlap"),
    ({"residual_in_fp32": True}, "residual_in_fp32"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"hybrid_override_pattern": "MEMXE"}, "unknown layer kind"),
    ({"hybrid_override_pattern": "MEM"}, "lists 3 layers"),
    ({"expert_share": {"published": 16, "first": 12}}, "expert_share holds 12..28"),
])
def test_what_the_family_does_not_implement_is_refused_when_the_config_is_read(change, message):
    raw = dict(nh.to_hf_config(nh.NemotronHConfig.tiny()), **change)
    with pytest.raises(ValueError, match=message):
        nh.config_from_hf(raw)


def test_without_config_json_the_family_says_why(tmp_path):
    _, params, _, _ = model_of("ME")
    st.write_safetensors(str(tmp_path / "model.safetensors"),
                         {k: np.asarray(v) for k, v in params.items()})
    with pytest.raises(Exception, match="config.json"):
        ModelServer(str(tmp_path), mesh_spec="dp=1", dtype="float32").load()


def test_tensor_names_pick_the_family_and_its_rules():
    cfg, _, hf, _ = model_of("MEM*E-")
    assert detect(list(hf)).name == "nemotron_h"
    assert detect(["backbone.layers.0.mixer.in_proj.weight"]).name == "nemotron_h"
    p = "backbone.layers.1.mixer."
    assert spec_for(p + "experts.up_proj.weight", NEMOTRON_H_RULES) == ("ep", "tp", None)
    assert spec_for(p + "experts.down_proj.weight", NEMOTRON_H_RULES) == ("ep", None, "tp")
    assert spec_for(p + "shared_experts.up_proj.weight", NEMOTRON_H_RULES) == ("tp", None)
    assert spec_for(p + "gate.weight", NEMOTRON_H_RULES) == (None, None)
    assert spec_for(p + "gate.e_score_correction_bias", NEMOTRON_H_RULES) == ()
    assert spec_for("backbone.layers.0.mixer.in_proj.weight", NEMOTRON_H_RULES) == (None, None)
    assert spec_for("backbone.layers.0.mixer.A_log", NEMOTRON_H_RULES) == ()
    assert spec_for("backbone.layers.3.mixer.k_proj.weight", NEMOTRON_H_RULES) == ("tp", None)
    assert spec_for("backbone.norm_f.weight", NEMOTRON_H_RULES) == (None,)


# -- the model against the reference -----------------------------------------------------


@pytest.mark.parametrize("name", PATTERNS)
def test_the_cacheless_forward_gives_the_references_logits(name):
    cfg, params, hf, raw = model_of(PATTERNS[name])
    toks = np.random.default_rng(1).integers(1, VOCAB, (2, 29))
    got, _ = nh.forward(params, jnp.asarray(toks), cfg)
    for row in range(2):
        np.testing.assert_allclose(np.asarray(got)[row], ref_logits(hf, raw, toks[row]), atol=ATOL)


@pytest.mark.parametrize("name", PATTERNS)
def test_prefill_then_decode_through_the_cache_is_the_references_full_forward(name):
    """A padded bucket (32 positions, 21 and 27 real) lands in a scratch with
    ``valid_len``, then each row decodes from its own offset."""
    cfg, params, hf, raw = model_of(PATTERNS[name])
    rng = np.random.default_rng(4)
    lens, new = [21, 27], 12
    seqs = [rng.integers(1, VOCAB, n + new) for n in lens]
    want = [ref_logits(hf, raw, s) for s in seqs]
    caches = []
    for n, seq in zip(lens, seqs):  # an admission lands one bucket at one offset
        block = np.zeros((1, 32), np.int64)
        block[0, :n] = seq[:n]
        logits, cache = nh.forward(params, jnp.asarray(block), cfg,
                                   kv_cache=nh.init_kv_cache(cfg, 1, MAX_LEN), cache_offset=0,
                                   valid_len=jnp.asarray([n]))
        np.testing.assert_allclose(np.asarray(logits)[0, :n], want[len(caches)][:n], atol=ATOL)
        caches.append(cache)
    cache = {k: jnp.concatenate([c[k] for c in caches]) for k in caches[0]}
    for i in range(new):
        toks = jnp.asarray([[seqs[0][lens[0] + i]], [seqs[1][lens[1] + i]]])
        logits, cache = nh.forward(params, toks, cfg, kv_cache=cache,
                                   cache_offset=jnp.asarray([lens[0] + i, lens[1] + i]))
        for row in range(2):
            np.testing.assert_allclose(np.asarray(logits)[row, 0], want[row][lens[row] + i],
                                       atol=ATOL)


def test_a_held_share_runs_end_to_end_against_the_reference_given_the_same_share():
    cfg, params, hf, raw = model_of("MEM*E", expert_first=8, expert_count=4)
    assert "backbone.layers.1.mixer.experts.8.up_proj.weight" in hf
    assert "backbone.layers.1.mixer.experts.0.up_proj.weight" not in hf
    toks = np.random.default_rng(6).integers(1, VOCAB, (1, 33))
    got, _ = nh.forward(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(np.asarray(got)[0], ref_logits(hf, raw, toks[0]), atol=ATOL)


def test_the_four_shares_up_projected_plus_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TEST: four chips hold experts 0-3, 4-7, 8-11, 12-15 of 16.
    Each computes its own experts' part of the latent sum and up-projects it;
    the parts, with the shared expert counted once, add up to what the uncut
    reference gives for the whole layer."""
    cfg, params, hf, raw = model_of("E", seed=3)
    p = "backbone.layers.0.mixer."
    u = jnp.asarray(np.random.default_rng(5).standard_normal((1, 19, cfg.hidden_size)),
                    jnp.float32)
    want = np.asarray(reference.experts(reference.Weights(hf), "backbone.layers.0.", raw, u[0]))

    def part(first, count, shared):
        return np.asarray(moe.moe_share_ffn(
            u, params[p + "gate.weight"], None,
            params[p + "experts.up_proj.weight"][first: first + count],
            params[p + "experts.down_proj.weight"][first: first + count],
            top_k=cfg.top_k, held=(first, count), renormalize=True, routed_scale=cfg.routed_scale,
            shared=shared, scoring="sigmoid", form="relu2",
            choice_bias=params[p + "gate.e_score_correction_bias"],
            latent=(params[p + "fc1_latent_proj.weight"], params[p + "fc2_latent_proj.weight"]))[0])

    shared = (None, params[p + "shared_experts.up_proj.weight"],
              params[p + "shared_experts.down_proj.weight"])
    parts = [part(first, 4, None) for first in (0, 4, 8, 12)]
    once = part(0, 4, shared) - parts[0]  # the shared expert: what every chip computes alike
    np.testing.assert_allclose(sum(parts)[0] + once[0], want, atol=ATOL)
    assert np.abs(parts[0][0] - want).max() > 0.05  # a share alone is not the layer


def test_a_long_block_goes_through_the_experts_in_chunks(monkeypatch):
    cfg, params, hf, raw = model_of("ME")
    monkeypatch.setattr(nh, "MOE_TOKENS", 16)
    monkeypatch.setattr(nh, "MOE_CHUNK", 8)
    toks = np.random.default_rng(2).integers(1, VOCAB, (1, 27))
    got, _ = nh.forward(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(np.asarray(got)[0], ref_logits(hf, raw, toks[0]), atol=ATOL)


# -- sigmoid routing -------------------------------------------------------------------


def test_sigmoid_routing_chooses_by_the_biased_score_and_weighs_by_the_unbiased():
    """Expert 3 has the largest score but a bias that drops it below three
    others; experts 0 and 1 tie (the lower index wins the last place): the
    choice follows ``s + b``, the weights are ``s`` of the chosen over their
    sum, times the scale."""
    logits = jnp.asarray([[0.5, 0.5, 1.0, 3.0, -1.0, 0.2]])
    bias = jnp.asarray([0.0, 0.0, 0.0, -0.9, 0.0, 0.5])
    got = np.asarray(moe.route_topk(logits, 3, scale=5.0, scoring="sigmoid", choice_bias=bias))
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits[0])))
    # s + b: 0.622, 0.622, 0.731, 0.053, 0.269, 1.050 -> experts 5, 2 and, of the tie, 0
    want = np.zeros(6)
    want[[5, 2, 0]] = s[[5, 2, 0]] / s[[5, 2, 0]].sum() * 5.0
    np.testing.assert_allclose(got[0], want, atol=1e-6)
    plain = np.asarray(moe.route_topk(logits, 3, scale=5.0, scoring="sigmoid"))
    assert plain[0, 3] > 0 and plain[0, 5] == 0  # without the bias the largest score is taken
    assert (np.count_nonzero(got, axis=1) == 3).all()
    with pytest.raises(ValueError, match="group-limited"):
        moe.route_topk(logits, 2, groups=(2, 1), scoring="sigmoid")
    with pytest.raises(ValueError, match="neither softmax nor sigmoid"):
        moe.route_topk(logits, 2, scoring="tanh")


def test_a_decode_step_reads_the_hit_experts_alone_where_the_rule_says_kernel(monkeypatch):
    """The latent layer's decode step with ``moe.lowering`` steered to the
    kernel (interpreted here; the rule picks it on one TPU device at whole
    tiles): the layer's answer is the einsums', and ``experts_read`` is
    ``experts_hit`` where the einsums read every held one."""
    cfg, params, _, _ = model_of("E", expert_first=4, expert_count=8)
    u = jnp.asarray(np.random.default_rng(3).standard_normal((6, 1, cfg.hidden_size)),
                    jnp.float32)
    ctx = nh.ShardingCtx(None)
    want, counts = nh._experts(params, "backbone.layers.0.", u, cfg, ctx)
    assert counts.tolist()[2:] == [int(counts[2]), 8] and 0 < int(counts[2]) < 8
    monkeypatch.setattr(moe, "lowering", lambda x, w, mesh=None: "kernel" if x[1] == 1 else "einsum")
    got, kernel_counts = nh._experts(params, "backbone.layers.0.", u, cfg, ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    assert kernel_counts.tolist() == counts.tolist()[:3] + [int(counts[2])]


def test_the_expert_form_and_the_gate_must_agree():
    cfg, params, _, _ = model_of("E")
    p = "backbone.layers.0.mixer."
    u = jnp.zeros((1, 4, cfg.hidden_size))
    with pytest.raises(ValueError, match="relu2"):
        moe.moe_share_ffn(u, params[p + "gate.weight"], params[p + "experts.up_proj.weight"],
                          params[p + "experts.up_proj.weight"],
                          params[p + "experts.down_proj.weight"], top_k=3, form="relu2")


# -- the engine ---------------------------------------------------------------------------


def write_checkpoint(path, pattern="MEM*E", seed=0, **over):
    cfg, _, hf, raw = model_of(pattern, seed=seed, **over)
    st.write_safetensors(str(path / "model.safetensors"), hf)
    (path / "config.json").write_text(json.dumps(raw))
    return cfg, hf, raw


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Experts 4-11 of 16 under per-expert names, loaded through ModelServer
    (the loader folds them)."""
    d = tmp_path_factory.mktemp("nemh")
    cfg, hf, raw = write_checkpoint(d, expert_first=4, expert_count=8)
    srv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN)
    srv.load()
    return srv, hf, raw, cfg


@pytest.fixture(scope="module", params=[0, 16], ids=["admit", "pieces16"])
def engine(served, request):
    cb = ContinuousBatcher(served[0], max_slots=SLOTS, chunk_size=4, prefill_chunk=request.param)
    yield cb
    cb.close()


def follows_the_reference(hf, raw, prompt, out):
    """Every token is the reference's argmax of the full forward, and the
    reference's logit of it is its maximum to float32 rounding."""
    seq = np.concatenate([prompt, out])
    logits = ref_logits(hf, raw, seq, positions=list(range(len(prompt) - 1, len(seq) - 1)))
    below = logits.max(-1) - logits[np.arange(len(out)), out]
    assert below.max() < 1e-3, (int(below.argmax()), float(below.max()))


def test_the_loader_folds_the_held_experts_and_the_family_is_told_its_share(served):
    srv, hf, _, cfg = served
    assert srv.family.name == "nemotron_h" and srv.cfg == cfg and cfg.held == (4, 8)
    assert srv.params["backbone.layers.1.mixer.experts.up_proj.weight"].shape == (8, 24, 16)
    np.testing.assert_array_equal(
        np.asarray(srv.params["backbone.layers.1.mixer.experts.down_proj.weight"])[2],
        hf["backbone.layers.1.mixer.experts.6.down_proj.weight"])


@pytest.mark.parametrize("prompt_len,new", [(5, 40), (24, 50), (77, 30)])
def test_prefill_then_decode_through_the_engine_follows_the_reference(served, engine, prompt_len, new):
    """Through the admit program (a padded bucket told its real length), and
    in pieces of 16 through the piece programs (a slot's state and tail go to
    a piece and come back)."""
    _, hf, raw, _ = served
    assert isinstance(engine.kv, kv_layout.LayerKindKV) and engine.kv.has_state
    prompt = np.random.default_rng(prompt_len).integers(1, VOCAB, (1, prompt_len))
    out = np.asarray(engine.generate(prompt, max_new_tokens=new))[0][-new:]
    follows_the_reference(hf, raw, prompt[0], out)


def test_rows_at_different_depths_keep_their_own_states_and_a_reused_slot_starts_anew(served, engine):
    """Six requests over four slots: two slots are used twice, and the second
    row starts from its own prompt's state and tail, not from what the slot
    held."""
    _, hf, raw, _ = served
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, VOCAB, (1, n)) for n in (3, 18, 35, 50, 44, 9)]
    outs: list = [None] * len(prompts)

    def run(i):
        outs[i] = np.asarray(engine.generate(prompts[i], max_new_tokens=30))[0][-30:]

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for prompt, out in zip(prompts, outs):
        follows_the_reference(hf, raw, prompt[0], out)


def test_an_idle_or_filling_slots_state_and_tail_are_untouched_by_the_others_decode(served):
    """The chunk program runs over ALL slots. Slot 0 decodes; slot 1 is idle
    (offset 0) with a marked state; slot 2 is in the middle of a fill (offset
    at its frontier, no step taken): after 8 steps the states and tails of
    slots 1 to 3 are bit for bit what they were, and slot 0's are not."""
    cb = ContinuousBatcher(served[0], max_slots=SLOTS, chunk_size=4, prefill_chunk=16)
    try:
        cb.generate(np.ones((1, 20), np.int32), max_new_tokens=4)  # builds the state
        rng = np.random.default_rng(11)
        cache = {name: (leaf if cb.kv.kinds[name] == "counter" else
                        jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype))
                 for name, leaf in cb._cache.items()}
        before = {k: np.asarray(v) for k, v in cache.items()}
        offsets = jnp.asarray([20, 0, 32, 0], jnp.int32)
        steps = jnp.asarray([5, 9, 0, 0], jnp.int32)  # an idle slot's steps drift; its offset is 0
        told = cb.kv.step_kwargs(offsets, steps)
        np.testing.assert_array_equal(np.asarray(told["live"]), [True, False, False, False])
        args = (served[0].params, cache, jnp.ones((SLOTS, 1), jnp.int32), offsets, steps,
                jnp.zeros(SLOTS, jnp.float32), None, None, jnp.zeros(SLOTS, jnp.int32))
        after, _, _ = jax.jit(cb._chunk_impl, static_argnames="n_steps")(*args, n_steps=8)
        states = [n for n, kind in cb.kv.kinds.items() if kind == "state"]
        assert sorted(states) == ["s0", "s2", "t0", "t2"]
        for name in states:
            got = np.asarray(after[name])
            np.testing.assert_array_equal(got[1:], before[name][1:], err_msg=name)
            assert not np.array_equal(got[0], before[name][0])
        grown = np.asarray(after["ssm_counts"]) - before["ssm_counts"]
        # one live row; every slot, every step; the live row's contexts, 21 to 28
        assert grown.tolist() == [8, 8 * SLOTS, sum(range(21, 29))]
        moe_grown = np.asarray(after["moe_counts"]) - before["moe_counts"]
        assert moe_grown[0] == 8 * SLOTS * 3 * 2  # idle slots route too: two layers, top-3
    finally:
        cb.close()


def test_the_engine_counts_its_states_its_steps_and_its_experts(served, engine):
    _, _, _, cfg = served
    engine.generate(np.ones((1, 9), np.int32), max_new_tokens=12)
    stats = engine.stats
    kv = stats["kv"]
    # two Mamba layers: a float32 state [8, 4, 8] and a float32 tail [3, 64] a slot
    assert kv["bytes_state"] == 2 * SLOTS * (8 * 4 * 8 + 3 * 64) * 4
    assert kv["bytes_full"] == 2 * SLOTS * MAX_LEN * 2 * 8 * 4 and kv["bytes_window"] == 0
    assert stats["ssm"]["steps_all"] % SLOTS == 0 and 0 < stats["ssm"]["steps_live"] <= stats[
        "ssm"]["steps_all"]
    assert {k: stats["ssm"][k] for k in ("layers", "heads", "head_dim", "state_size", "groups",
                                         "conv_kernel")} == {
        "layers": 2, "heads": 8, "head_dim": 4, "state_size": 8, "groups": 2, "conv_kernel": 4}
    moe_stats = stats["moe"]
    assert (moe_stats["held_experts"], moe_stats["published_experts"], moe_stats["sparse_layers"],
            moe_stats["latent_size"]) == (8, 16, 2, 16)
    assert 0 < moe_stats["assignments_held"] < moe_stats["assignments"]
    assert moe_stats["experts_read"] >= moe_stats["experts_hit"] > 0  # the einsums read every held one


@pytest.mark.parametrize("option,message", [
    ({"page_size": 16}, "--kv-page-size"),
    ({"prefix_cache": 4}, "--prefix-cache"),
    ({"speculative_k": 2}, "--speculative-k"),
])
def test_an_engine_option_a_state_cannot_carry_is_refused_with_its_reason(served, option, message):
    with pytest.raises(kv_layout.Refused, match=message) as refused:
        ContinuousBatcher(served[0], max_slots=SLOTS, chunk_size=4, **option)
    assert "'state' leaves" in str(refused.value)


def test_a_block_of_prompt_positions_must_say_how_many_are_real(served):
    srv = served[0]
    fwd, init_cache = srv.family.decode_fns(srv.cfg, mesh=srv.mesh)
    with pytest.raises(ValueError, match="real lengths"):
        fwd(srv.params, jnp.ones((1, 16), jnp.int32), init_cache(1, 32), 0)


def test_decode_through_the_plain_generate_loop_follows_the_reference(served):
    srv, hf, raw, cfg = served
    prompt = np.random.default_rng(3).integers(1, VOCAB, (1, 13))
    out = np.asarray(FAMILIES["nemotron_h"].generate_ragged(
        srv.params, jnp.asarray(prompt), jnp.asarray([13]), cfg, max_new_tokens=10))[0][-10:]
    follows_the_reference(hf, raw, prompt[0], out)


def test_the_benchmarks_copy_of_the_reference_is_the_repos():
    with open(os.path.join(ROOT, "modelx_tpu", "models", "nemotron_h_reference.py")) as a, \
            open(os.path.join(ROOT, "benchmark", "references", "nemotron_h.py")) as b:
        assert a.read() == b.read()
