"""The minicpm_sala family (models/minicpm_sala.py) on the normal serving path,
held against the plain float32 reference (models/minicpm_sala_reference.py) at
a small size on the CPU: seeded random weights, three layers of a "published"
eight (sparse, lightning, lightning), blocks of 8 positions, top-3, the
selection from 32 positions of context on. Logits, not tokens; every
tolerance is float32 rounding of sums a few dozen terms long (1e-4 absolute
on logits whose standard deviation is 0.2-0.3), except where said."""

import json
import math
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
from modelx_tpu.dl.serve import ModelServer, ServerSet
from modelx_tpu.dl.sharding import MINICPM_SALA_RULES, spec_for
from modelx_tpu.models import minicpm_sala as sala, minicpm_sala_reference as reference
from modelx_tpu.ops import linear_attention as linear_ops
from modelx_tpu.ops import sparse_attention as sparse_ops
from modelx_tpu.ops.sparse_attention import SparseSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, MAX_LEN, SLOTS = 96, 128, 4
ATOL = 1e-4
SPEC = SparseSpec(kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8, window_size=16,
                  topk=3, dense_len=32)


@pytest.fixture(autouse=True, scope="module")
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def write_checkpoint(path, cfg, seed=0):
    params = {k: np.asarray(v) for k, v in sala.init_params(cfg, jax.random.PRNGKey(seed)).items()}
    st.write_safetensors(str(path / "model.safetensors"), params)
    raw = sala.to_hf_config(cfg)
    (path / "config.json").write_text(json.dumps(raw))
    return params, raw


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Layers 2-4 of a "published" eight, loaded through ModelServer."""
    d = tmp_path_factory.mktemp("sala")
    cfg = sala.SalaConfig.tiny(vocab_size=VOCAB)
    params, raw = write_checkpoint(d, cfg)
    srv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN)
    srv.load()
    return srv, params, raw, d


def ref_logits(params, raw, seq, positions=None):
    return np.asarray(reference.forward(params, raw, list(seq), positions=positions))


# -- ops/linear_attention -------------------------------------------------------


def quadratic(q, k, v, slopes, scale):
    """The reference's O(T^2) form on arrays [T, H, D] (float64)."""
    t = q.shape[0]
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    out = np.zeros(q.shape)
    for h in range(q.shape[1]):
        decay = np.where(j <= i, np.exp(-slopes[h] * np.maximum(i - j, 0)), 0.0)
        out[:, h] = ((q[:, h] @ k[:, h].T) * decay) @ v[:, h] * scale
    return out


def qkv(t, heads=4, d=8, seed=0, batch=1):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((batch, t, heads, d)).astype(np.float32) for _ in range(3)]


@pytest.mark.parametrize("chunk", [4, 7, 23, 64])
def test_chunkwise_form_is_the_recurrence_is_the_quadratic_form(chunk):
    """Chunks that do and do not divide the 23 positions, one chunk, and a
    chunk longer than the block."""
    q, k, v = qkv(23)
    slopes, scale = linear_ops.decay_slopes(4), 1 / math.sqrt(8)
    zero = jnp.zeros((1, 4, 8, 8), jnp.float32)
    out, state = linear_ops.chunked(q, k, v, slopes, zero, scale=scale, chunk=chunk)
    want = quadratic(*(x[0].astype(np.float64) for x in (q, k, v)), slopes, scale)
    np.testing.assert_allclose(np.asarray(out)[0], want, atol=ATOL)
    s, steps = zero, []
    for t in range(23):
        o, s = linear_ops.step(q[:, t], k[:, t], v[:, t], slopes, s, scale=scale)
        steps.append(np.asarray(o)[0])
    np.testing.assert_allclose(np.stack(steps), want, atol=ATOL)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s), atol=ATOL)


def test_the_fast_heads_never_overflow_over_a_long_chunk():
    """Head 0 of 32 forgets with lam = exp(-0.84): lam^-256 is 1e93. Every
    power is taken from a difference of positions, so nothing is infinite."""
    q, k, v = qkv(300, heads=32, d=4, seed=1)
    out, state = linear_ops.chunked(q, k, v, linear_ops.decay_slopes(32),
                                    jnp.zeros((1, 32, 4, 4), jnp.float32), chunk=256)
    assert np.isfinite(np.asarray(out)).all() and np.isfinite(np.asarray(state)).all()


@pytest.mark.parametrize("pieces", [[16, 16, 5], [32, 5], [5, 32], [37]])
def test_a_prompt_landed_in_pieces_leaves_the_state_of_one_pass(pieces):
    """Each piece in its 16-token bucket, the padded tail told by
    ``valid_len``: a position past it neither decays nor feeds the state."""
    q, k, v = qkv(37, seed=2)
    slopes = linear_ops.decay_slopes(4)
    zero = jnp.zeros((1, 4, 8, 8), jnp.float32)
    whole_out, whole = linear_ops.chunked(q, k, v, slopes, zero, chunk=8)
    state, at, outs = zero, 0, []
    for take in pieces:
        pad = -take % 16
        blk = [np.pad(x[:, at: at + take], ((0, 0), (0, pad), (0, 0), (0, 0)),
                      constant_values=7.0) for x in (q, k, v)]
        out, state = linear_ops.chunked(*blk, slopes, state, valid_len=jnp.asarray([take]), chunk=8)
        outs.append(np.asarray(out)[:, :take])
        at += take
    np.testing.assert_allclose(np.asarray(state), np.asarray(whole), atol=ATOL)
    np.testing.assert_allclose(np.concatenate(outs, axis=1), np.asarray(whole_out), atol=ATOL)


def test_a_row_that_is_not_live_keeps_its_state_bit_for_bit():
    q, k, v = qkv(1, batch=3, seed=3)
    state = jnp.asarray(np.random.default_rng(4).standard_normal((3, 4, 8, 8)), jnp.float32)
    live = jnp.asarray([True, False, True])
    _, new = linear_ops.step(q[:, 0], k[:, 0], v[:, 0], linear_ops.decay_slopes(4), state, live=live)
    np.testing.assert_array_equal(np.asarray(new)[1], np.asarray(state)[1])
    assert not np.array_equal(np.asarray(new)[0], np.asarray(state)[0])


# -- ops/sparse_attention -------------------------------------------------------


def dense_attention(q, k, v):
    """Causal softmax attention, q [T, H, D] over k, v [T, Hkv, D] (float64)."""
    t, h, d = q.shape
    group = h // k.shape[1]
    out = np.zeros(q.shape)
    for g in range(h):
        s = q[:, g] @ k[:, g // group].T / math.sqrt(d)
        s = np.where(np.arange(t)[None, :] <= np.arange(t)[:, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, g] = (p / p.sum(-1, keepdims=True)) @ v[:, g // group]
    return out


def sparse_inputs(t, seed=0, heads=4, hkv=2, d=8):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((1, t, heads, d)).astype(np.float32)
    k = rng.standard_normal((1, t, hkv, d)).astype(np.float32)
    v = rng.standard_normal((1, t, hkv, d)).astype(np.float32)
    return q, k, v


def index_of(k, spec):
    s = spec.kernel_stride
    return sparse_ops.compress(np.pad(k, ((0, 0), (s, 0), (0, 0), (0, 0))), spec)


def test_the_index_holds_the_mean_of_each_window_where_the_window_ends():
    _, k, _ = sparse_inputs(64)
    index = np.asarray(index_of(k, SPEC))
    comp = np.asarray(reference.compressed_keys(jnp.asarray(k[0, :, 1]),
                                                {"kernel_size": 4, "kernel_stride": 2}))
    assert index.shape == (1, 32, 2, 8) and comp.shape == (31, 8)
    np.testing.assert_allclose(index[0, 1:, 1], comp, atol=1e-6)  # entry j is compressed key j - 1


@pytest.mark.parametrize("t", [40, 64, 72])
def test_with_topk_at_least_the_blocks_the_layer_is_dense_attention(t):
    q, k, v = sparse_inputs(t, seed=t)
    spec = SparseSpec(kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8, window_size=16,
                      topk=64, dense_len=16)
    got = sparse_ops.prefill_attention(q, k, v, index_of(k, spec), 0, spec, q_tile=16, k_tile=24)
    want = dense_attention(*(x[0].astype(np.float64) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got)[0], want, atol=ATOL)
    # and the one-token step's gather, at the last position
    pad = -t % 8
    kc, vc = (np.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (k, v))
    chosen = sparse_ops.select_blocks(q[:, -1:], index_of(kc, spec), jnp.asarray([[t]]), spec,
                                      kc.shape[1] // 8)[:, 0]
    flat = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
    one = sparse_ops.decode_attention(q[:, -1], flat(kc), flat(vc), chosen, jnp.asarray([t - 1]), spec)
    np.testing.assert_allclose(np.asarray(one)[0], want[-1], atol=ATOL)


def test_below_dense_len_the_layer_is_dense_attention():
    q, k, v = sparse_inputs(31, seed=5)
    got = sparse_ops.prefill_attention(q, k, v, index_of(np.pad(k, ((0, 0), (0, 1), (0, 0), (0, 0))),
                                                         SPEC), 0, SPEC)
    want = dense_attention(*(x[0].astype(np.float64) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got)[0], want, atol=ATOL)


def test_the_selection_is_the_references_and_holds_the_forced_blocks():
    """Every position from ``dense_len`` on, both KV heads: the selected set
    equals the reference's, and block 0 and the two blocks ending at the
    query's own are in it."""
    t = 96
    q, k, _ = sparse_inputs(t, seed=6)
    index = index_of(k, SPEC)
    context = jnp.arange(1, t + 1)[None, :]
    chosen = np.asarray(sparse_ops.select_blocks(q, index, context, SPEC, t // 8))
    sc = {"kernel_size": 4, "kernel_stride": 2, "init_blocks": 1, "block_size": 8,
          "window_size": 16, "topk": 3}
    for h in range(2):
        comp = reference.compressed_keys(jnp.asarray(k[0, :, h]), sc)
        for pos in range(31, t):
            got = set(chosen[0, pos, h].tolist())
            want = reference.selected_blocks(jnp.asarray(q[0, pos, 2 * h: 2 * h + 2]), comp, pos, sc)
            assert got == set(want.tolist()), (h, pos)
            assert {0, pos // 8, pos // 8 - 1} <= got


def test_a_hand_made_case_chooses_the_block_the_query_points_at_and_leaves_its_neighbour():
    """Keys along e0 in block 3, along e1 in block 5, small elsewhere; a
    query along e0 at position 95 (block 11): top-4 = block 0 and blocks 10,
    11 forced, then block 3 — and block 5 is left out. Its output is then
    attention over those four blocks only."""
    spec = SparseSpec(kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8, window_size=16,
                      topk=4, dense_len=32)
    rng = np.random.default_rng(7)
    k = 0.01 * rng.standard_normal((1, 96, 1, 8)).astype(np.float32)
    k[0, 24:32, 0, 0] = 4.0
    k[0, 40:48, 0, 1] = 4.0
    v = rng.standard_normal((1, 96, 1, 8)).astype(np.float32)
    q = np.zeros((1, 1, 2, 8), np.float32)
    q[..., 0] = 3.0
    index = index_of(k, spec)
    chosen = np.asarray(sparse_ops.select_blocks(q, index, jnp.asarray([[96]]), spec, 12))[0, 0, 0]
    assert set(chosen.tolist()) == {0, 3, 10, 11} and 5 not in chosen
    got = sparse_ops.decode_attention(q[:, 0], k[:, :, 0], v[:, :, 0], jnp.asarray(chosen)[None, None],
                                      jnp.asarray([95]), spec)
    keep = np.concatenate([np.arange(8 * b, 8 * b + 8) for b in (0, 3, 10, 11)])
    s = (q[0, 0] @ k[0, keep, 0].T) / math.sqrt(8)
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(got)[0], (p / p.sum(-1, keepdims=True)) @ v[0, keep, 0],
                               atol=ATOL)


def test_a_stride_the_cache_cannot_keep_is_refused():
    with pytest.raises(ValueError, match="kernel_size"):
        SparseSpec(kernel_size=48, kernel_stride=16)
    with pytest.raises(ValueError, match="kernel_stride"):
        SparseSpec(kernel_size=12, kernel_stride=6, block_size=12)


# -- config ---------------------------------------------------------------------


def test_the_config_is_read_from_config_json_and_round_trips():
    cfg = sala.SalaConfig.tiny(vocab_size=VOCAB)
    assert sala.config_from_hf(sala.to_hf_config(cfg), dtype=jnp.float32) == cfg
    assert cfg.prefix(0) == "model.layers.2." and cfg.residual_scale == 1.4 / math.sqrt(8)


def test_the_published_config_reads_as_published():
    with open(os.path.join(ROOT, "benchmark", "configs", "minicpm-sala-d12.json")) as f:
        cfg = sala.config_from_hf(json.load(f))
    assert cfg.mixer_types.count(sala.SPARSE) == 3 and cfg.mixer_types.count(sala.LIGHTNING) == 9
    assert [cfg.first_layer + i for i, m in enumerate(cfg.mixer_types) if m == sala.SPARSE] == [9, 16, 17]
    assert (cfg.published_layers, cfg.residual_scale) == (32, 1.4 / math.sqrt(32))
    assert (cfg.scale_emb, cfg.hidden_size / cfg.dim_model_base) == (12.0, 16.0)
    assert cfg.sparse == SparseSpec() and (cfg.sparse.topk, cfg.sparse.dense_len) == (64, 8192)
    shapes = sala.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 3_930_008_576  # ISSUE 35's arithmetic
    assert shapes["model.layers.9.self_attn.k_proj.weight"] == (256, 4096)
    assert shapes["model.layers.10.self_attn.k_proj.weight"] == (4096, 4096)
    assert "model.layers.8.self_attn.q_proj.weight" not in shapes
    state = jax.eval_shape(lambda: sala.init_layer_state(cfg, 32, 32768))
    assert state["s1"].shape == (32, 32, 128, 128) and state["s1"].dtype == jnp.float32
    assert state["c0"].shape == (32, 2048, 2, 128) and state["k0"].shape == (32, 32768, 256)


@pytest.mark.parametrize("change,message", [
    ({"mixer_types": ["minicpm4", "mamba", "lightning-attn"]}, "unknown mixer type"),
    ({"mixer_types": ["minicpm4"]}, "mixer_types lists 1 layers"),
    ({"layer_share": {"published": 4, "first": 2}}, "layer_share"),
    ({"lightning_nkv": 2}, "lightning_nkv"),
    ({"lightning_scale": "1/d"}, "lightning_scale"),
    ({"attention_bias": True}, "biases"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_what_the_family_does_not_implement_is_refused_when_the_config_is_read(change, message):
    raw = dict(sala.to_hf_config(sala.SalaConfig.tiny(vocab_size=VOCAB)), **change)
    with pytest.raises(ValueError, match=message):
        sala.config_from_hf(raw)


def test_without_config_json_the_family_says_why(tmp_path):
    write_checkpoint(tmp_path, sala.SalaConfig.tiny(vocab_size=VOCAB))
    os.remove(tmp_path / "config.json")
    with pytest.raises(ValueError, match="config.json must lie beside"):
        ModelServer(str(tmp_path), mesh_spec="dp=1", dtype="float32").load()


def test_tensor_names_pick_the_family_and_its_rules():
    names = list(sala.param_shapes(sala.SalaConfig.tiny(vocab_size=8)))
    assert detect(names).name == "minicpm_sala"
    assert spec_for("model.layers.9.self_attn.o_gate.weight", MINICPM_SALA_RULES) == ("tp", None)
    assert spec_for("model.layers.9.self_attn.q_norm.weight", MINICPM_SALA_RULES) == (None,)


# -- the family against the reference ---------------------------------------------


def test_the_cacheless_forward_gives_the_references_logits(served):
    """100 positions: 31 below ``dense_len``, 69 through the selection."""
    srv, params, raw, _ = served
    assert srv.family.name == "minicpm_sala" and srv.cfg.first_layer == 2
    toks = np.random.default_rng(0).integers(1, VOCAB, (1, 100))
    got = np.asarray(srv.family.forward(srv.params, jnp.asarray(toks), srv.cfg))
    np.testing.assert_allclose(got[0], ref_logits(params, raw, toks[0]), atol=ATOL)


def test_the_mup_scales_use_the_published_depth(served):
    """Three layers of a published eight: the reference reads the depth from
    ``layer_share``; read as three of three, its logits are others."""
    srv, params, raw, _ = served
    toks = np.random.default_rng(1).integers(1, VOCAB, 12)
    want = ref_logits(params, raw, toks)
    got = np.asarray(srv.family.forward(srv.params, jnp.asarray(toks)[None], srv.cfg))[0]
    np.testing.assert_allclose(got, want, atol=ATOL)
    prefix = dict(raw, layer_share={"published": 3, "first": 2})
    assert np.abs(ref_logits(params, prefix, toks) - want).max() > 100 * ATOL


@pytest.mark.parametrize("piece", [None, 16, 32])
@pytest.mark.parametrize("prompt_len", [24, 48, 77])
def test_prefill_then_decode_over_a_cache_gives_the_references_logits(served, piece, prompt_len):
    """The prompt as one block or in pieces of 16 and 32 (each in its 16-token
    bucket), then one token a step to position 100: prompts that end below
    ``dense_len`` = 32, so that decode crosses it, and past it."""
    srv, params, raw, _ = served
    cfg, total = srv.cfg, 100
    toks = np.random.default_rng(prompt_len).integers(1, VOCAB, total)
    cache, outs, at = sala.init_layer_state(cfg, 1, MAX_LEN), [], 0
    while at < prompt_len:
        take = min(piece or prompt_len, prompt_len - at)
        blk = np.zeros(-(-take // 16) * 16, np.int64)
        blk[:take] = toks[at: at + take]
        logits, cache = sala.forward(srv.params, jnp.asarray(blk)[None], cfg, kv_cache=cache,
                                     cache_offset=jnp.int32(at), valid_len=jnp.asarray([take]))
        outs.append(np.asarray(logits)[0, :take])
        at += take
    for t in range(prompt_len, total):
        logits, cache = sala.forward(srv.params, jnp.asarray(toks[t: t + 1])[None], cfg,
                                     kv_cache=cache, cache_offset=jnp.asarray([t], jnp.int32))
        outs.append(np.asarray(logits)[0])
    np.testing.assert_allclose(np.concatenate(outs), ref_logits(params, raw, toks), atol=ATOL)
    read, cached, took, steps, by_kernel = np.asarray(cache["sparse_counts"])
    assert by_kernel == 0  # the CPU gathers
    assert steps == total - prompt_len and took == total - max(prompt_len, 31)
    assert cached == sum(range(prompt_len + 1, total + 1))
    assert read == sum(t if t < 32 else 24 for t in range(prompt_len + 1, total + 1))


def test_a_block_of_prompt_positions_must_say_how_many_are_real(served):
    srv = served[0]
    fwd, init = srv.family.decode_fns(srv.cfg)
    with pytest.raises(ValueError, match="real lengths"):
        fwd(srv.params, jnp.ones((1, 16), jnp.int32), init(1, 32), 0)


def test_decode_through_the_plain_generate_loop_follows_the_reference(served):
    srv, params, raw, _ = served
    prompt = np.random.default_rng(3).integers(1, VOCAB, (1, 20))
    out = np.asarray(FAMILIES["minicpm_sala"].generate(
        srv.params, jnp.asarray(prompt), srv.cfg, max_new_tokens=30))
    seq = np.concatenate([prompt[0], out[0, -30:]])
    logits = ref_logits(params, raw, seq)[19:-1]
    assert (logits.max(-1) - logits[np.arange(30), seq[20:]]).max() < 1e-3


# -- the engine -------------------------------------------------------------------


@pytest.fixture(scope="module", params=[0, 16, 32], ids=["admit", "pieces16", "pieces32"])
def engine(served, request):
    cb = ContinuousBatcher(served[0], max_slots=SLOTS, chunk_size=4, prefill_chunk=request.param)
    yield cb
    cb.close()


def follows_the_reference(params, raw, prompt, out):
    """Every token is the reference's argmax of the full forward, and the
    reference's logit of it is its maximum to float32 rounding."""
    seq = np.concatenate([prompt, out])
    logits = ref_logits(params, raw, seq, positions=list(range(len(prompt) - 1, len(seq) - 1)))
    below = logits.max(-1) - logits[np.arange(len(out)), out]
    assert below.max() < 1e-3, (int(below.argmax()), float(below.max()))


@pytest.mark.parametrize("prompt_len,new", [(5, 60), (24, 50), (40, 70), (77, 40)])
def test_prefill_then_decode_through_the_engine_follows_the_reference(served, engine, prompt_len, new):
    """Through the admit program, and in pieces of 16 and 32 through the
    piece programs: contexts on both sides of ``dense_len`` = 32."""
    _, params, raw, _ = served
    assert isinstance(engine.kv, kv_layout.LayerKindKV) and engine.kv.has_state
    prompt = np.random.default_rng(prompt_len).integers(1, VOCAB, (1, prompt_len))
    out = np.asarray(engine.generate(prompt, max_new_tokens=new))[0][-new:]
    follows_the_reference(params, raw, prompt[0], out)


def test_rows_at_different_depths_keep_their_own_states_and_a_reused_slot_starts_anew(served, engine):
    """Six requests over four slots: two slots are used twice, and the second
    row starts from its own prompt's state, not from what the slot held."""
    _, params, raw, _ = served
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, VOCAB, (1, n)) for n in (3, 18, 35, 50, 44, 9)]
    outs: list = [None] * len(prompts)

    def run(i):
        outs[i] = np.asarray(engine.generate(prompts[i], max_new_tokens=45))[0][-45:]

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for prompt, out in zip(prompts, outs):
        follows_the_reference(params, raw, prompt[0], out)


def test_an_idle_or_filling_slots_state_is_untouched_by_the_others_decode(served):
    """The chunk program runs over ALL slots. Slot 0 decodes; slot 1 is idle
    (offset 0) with a marked state; slot 2 is in the middle of a fill
    (offset at its frontier, no step taken): after 8 steps the states and
    compressed keys of slots 1 and 2 are bit for bit what they were, and
    slot 0's are not."""
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
        for name, kind in cb.kv.kinds.items():
            if kind in ("state", "index"):
                got = np.asarray(after[name])
                np.testing.assert_array_equal(got[1:], before[name][1:], err_msg=name)
                if kind == "state":
                    assert not np.array_equal(got[0], before[name][0])
        counts = np.asarray(after["sparse_counts"]) - before["sparse_counts"]
        assert counts[3] == 8 and counts[1] == sum(range(21, 29))  # one live row, one sparse layer
    finally:
        cb.close()


def test_the_engine_counts_its_states_its_index_and_its_sparse_steps(served, engine):
    srv = served[0]
    engine.generate(np.ones((1, 40), np.int32), max_new_tokens=12)
    snap = engine.snapshot()
    kv, sparse = snap["kv"], snap["sparse"]
    leaf = SLOTS * srv.cfg.num_kv_heads * srv.cfg.head_dim * 4  # float32 here
    assert kv["bytes_full"] == 2 * MAX_LEN * leaf and kv["bytes_index"] == MAX_LEN // 2 * leaf
    assert kv["bytes_state"] == 2 * SLOTS * 4 * 8 * 8 * 4 and kv["bytes_window"] == 0
    assert kv["states_live"] == 0  # the row has retired
    assert (sparse["sparse_layers"], sparse["linear_layers"], sparse["topk"]) == (1, 2, 3)
    assert 0 < sparse["steps_sparse"] <= sparse["steps_all"]
    assert sparse["positions_read"] < sparse["positions_cached"]
    if engine.prefill_chunk:
        assert snap["fill"]["pieces"] >= 2 and snap["fill"]["tokens"] >= 40
    else:
        assert "fill" not in snap


def test_reservations_count_states_and_a_release_gives_them_back(served):
    cb = ContinuousBatcher(served[0], max_slots=SLOTS, chunk_size=4, allocate=False)
    kv = cb.kv
    assert kv.reserve(1, 40) and kv.reserve(3, 128) and not kv.reserve(2, 129)
    assert (kv.stats["kv"]["states_live"], kv.stats["kv"]["positions_full"]) == (2, 168)
    kv.release(1)
    assert (kv.stats["kv"]["states_live"], kv.stats["kv"]["positions_full"]) == (1, 128)
    kv.reset()
    assert kv.stats["kv"]["states_live"] == 0
    assert kv.describe()[-1] == tuple(sorted(sala.cache_kinds(served[0].cfg).items()))


# -- what is refused, and what is carried -------------------------------------------


@pytest.mark.parametrize("option,message", [
    ({"page_size": 16}, "--kv-page-size.*a state has no pages"),
    ({"prefix_cache": object()}, "--prefix-cache.*a state cannot be cut at a token"),
    ({"speculative_k": 4}, "--speculative-k.*a state cannot drop"),
])
def test_an_engine_option_a_state_cannot_carry_is_refused_with_its_reason(served, option, message):
    with pytest.raises(kv_layout.Refused, match=message):
        ContinuousBatcher(served[0], max_slots=SLOTS, chunk_size=4, allocate=False, **option)


def test_chunked_prefill_is_carried_by_a_layout_without_rings(served):
    cb = ContinuousBatcher(served[0], max_slots=SLOTS, chunk_size=4, allocate=False,
                           prefill_chunk=32)
    assert cb.prefill_chunk == 32 and "window" not in cb.kv.kinds.values()


def test_the_refusals_name_the_leaf_kind_that_is_the_reason():
    with pytest.raises(kv_layout.Refused) as ring:
        kv_layout.LayerKindKV.refuse("laguna", ("full", "window", "counter"), page_size=16,
                                     prefix_cache=None, prefill_chunk=32, speculative_k=0)
    assert "--kv-page-size" in str(ring.value) and "'window' leaves" in str(ring.value)
    assert "'state'" not in str(ring.value)
    # chunked prefill is carried by every leaf kind, a ring too since PR 54
    assert "--prefill-chunk" not in str(ring.value)
    kv_layout.LayerKindKV.refuse("laguna", ("full", "window", "counter"), prefill_chunk=32)
    kv_layout.LayerKindKV.refuse("minicpm_sala", ("full", "index", "state"), prefill_chunk=32)


@pytest.mark.parametrize("server_flags,set_flags,message", [
    ({}, {"kv_page_size": 16}, "--kv-page-size"),
    ({"speculative_k": 2}, {}, "--speculative-k"),
    ({"prefix_cache_size": 4}, {}, "--prefix-cache"),
])
def test_a_refused_option_ends_the_load_of_a_continuous_pod(served, server_flags, set_flags, message):
    """At start-up, not at the first request."""
    srv = ModelServer(str(served[3]), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN,
                      **server_flags)
    sset = ServerSet({"default": srv}, continuous_batch=True, max_slots=SLOTS, **set_flags)
    with pytest.raises(RuntimeError, match=message):
        sset.load_all()


def test_a_continuous_pod_with_prefill_chunk_loads_and_builds_its_engine(served):
    srv = ModelServer(str(served[3]), mesh_spec="dp=1", dtype="float32", max_seq_len=MAX_LEN)
    sset = ServerSet({"default": srv}, continuous_batch=True, max_slots=SLOTS, prefill_chunk=32)
    sset.load_all()
    try:
        engine = sset.engine_for(srv, 1, 0.0)
        assert engine is not None and engine.prefill_chunk == 32 and engine.kv.has_state
    finally:
        sset._drop_engine(srv.name)


def test_the_benchmarks_copy_of_the_reference_is_the_repos():
    with open(os.path.join(ROOT, "modelx_tpu", "models", "minicpm_sala_reference.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "references", "minicpm_sala.py")) as f:
        assert f.read() == ours
