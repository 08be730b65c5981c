"""Flagship model + ops tests on the virtual 8-device CPU mesh."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl.families import FAMILIES
from modelx_tpu.dl.sharding import LLAMA_RULES
from modelx_tpu.models import llama
from modelx_tpu.models.train import (
    batch_sharding,
    cross_entropy_loss,
    make_optimizer,
    make_train_step,
    shard_params,
)
from modelx_tpu.ops import attention as attn
from modelx_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def cfg():
    return llama.LlamaConfig.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens(cfg):
    rng = np.random.RandomState(1)
    return jnp.array(rng.randint(0, cfg.vocab_size, (2, 16)), jnp.int32)


class TestAttentionOps:
    def setup_method(self):
        rng = np.random.RandomState(0)
        self.q = jnp.array(rng.rand(2, 4, 128, 32), jnp.float32)
        self.k = jnp.array(rng.rand(2, 4, 128, 32), jnp.float32)
        self.v = jnp.array(rng.rand(2, 4, 128, 32), jnp.float32)

    def test_flash_matches_reference(self):
        ref = attn.attention_reference(self.q, self.k, self.v)
        fl = attn.flash_attention(self.q, self.k, self.v, block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=2e-6)

    def test_flash_noncausal(self):
        ref = attn.attention_reference(self.q, self.k, self.v, causal=False)
        fl = attn.flash_attention(self.q, self.k, self.v, causal=False, block_q=64, block_k=64,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=2e-6)

    def test_flash_block_k_larger_than_block_q(self):
        """block_k > block_q: the causal k-block cap must be an exact
        ceiling — the floor form computed ZERO visible blocks for early q
        blocks and returned all-zero rows."""
        ref = attn.attention_reference(self.q, self.k, self.v)
        fl = attn.flash_attention(self.q, self.k, self.v, block_q=32, block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=2e-6)
        assert np.abs(np.asarray(fl)).sum() > 0

    def test_gqa(self):
        kv = self.k[:, :2], self.v[:, :2]
        ref = attn.attention_reference(self.q, *kv)
        fl = attn.flash_attention(self.q, *kv, block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=2e-6)

    @pytest.mark.parametrize("sq,causal,padded", [
        (144, False, 256),  # only kv_len masks the padded keys here
        (5, True, 16),      # below one block: up to the row tile
    ])
    def test_flash_ragged_pads_to_block(self, sq, causal, padded):
        """Lengths that are not a multiple of the block — 16-bucketed ones
        above it (144, 160, ...) and raw /v1/forward lengths below the row
        tile (5) — stay IN the kernel, padded up (padded keys masked),
        instead of being handed to the reference or to a compiler that
        refuses the unaligned block."""
        rng = np.random.RandomState(3)
        q, k, v = (jnp.array(rng.rand(1, 4, sq, 32), jnp.float32) for _ in range(3))
        ref = attn.attention_reference(q, k[:, :2], v[:, :2], causal=causal)
        text = attn.flash_attention.lower(
            q, k[:, :2], v[:, :2], causal=causal, interpret=True).as_text()
        assert f"{padded}x32" in text  # the padded shape is in the program
        fl = attn.flash_attention(q, k[:, :2], v[:, :2], causal=causal, interpret=True)
        assert fl.shape == q.shape
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=2e-6)

    def test_flash_blocks(self):
        assert attn.flash_blocks(16) == (16, 16)
        assert attn.flash_blocks(5) == (16, 16)
        assert attn.flash_blocks(40) == (48, 48)
        assert attn.flash_blocks(128) == (128, 128)
        assert attn.flash_blocks(144) == (128, 256)
        assert attn.flash_blocks(2048) == (128, 2048)

    @pytest.mark.parametrize("spec,batch,kv_heads", [
        ("dp=1,tp=4", 1, 2),   # fewer kv heads than tp: repeated, then split
        ("dp=2,tp=2", 2, 2),   # batch over dp AND heads over tp (kv heads divide)
        ("dp=4", 1, 2),        # the default dp=N mesh, batch 1: replicated
    ])
    def test_flash_under_mesh_is_shard_mapped(self, spec, batch, kv_heads):
        """A bare pallas_call cannot be partitioned by GSPMD (on the chip:
        'Mosaic kernels cannot be automatically partitioned'); under a
        multi-device mesh the kernel runs inside shard_map and still
        matches the reference."""
        mesh = make_mesh(spec, devices=jax.devices()[:4])
        rng = np.random.RandomState(4)
        q = jnp.array(rng.rand(batch, 4, 128, 32), jnp.float32)
        k = jnp.array(rng.rand(batch, kv_heads, 128, 32), jnp.float32)
        v = jnp.array(rng.rand(batch, kv_heads, 128, 32), jnp.float32)
        ref = attn.attention_reference(q, k, v)
        fl = attn.flash_attention(q, k, v, mesh=mesh, interpret=True)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=2e-6)
        jaxpr = str(jax.make_jaxpr(functools.partial(
            attn.flash_attention, mesh=mesh, interpret=True))(q, k, v))
        assert "shard_map" in jaxpr

    def test_flash_never_interprets_on_its_own(self):
        """interpret is the caller's explicit ask: on a backend that cannot
        compile the kernel the call FAILS rather than quietly running the
        interpreter (or the reference) in its place."""
        with pytest.raises(Exception, match="(?i)interpret|pallas|mosaic|cpu"):
            jax.block_until_ready(
                attn.flash_attention(self.q, self.k, self.v, block_q=64, block_k=64))

    def test_note_choice_names_the_decision(self):
        from modelx_tpu.utils import trace

        mesh = make_mesh("dp=1,tp=4", devices=jax.devices()[:4])
        count = lambda: {p: a["count"] for p, a in
                         trace.tracer().summary("attention.").items()}
        before = count()
        attn.note_choice("flash", 512, 512)
        attn.note_choice("flash", 144, 144, mesh)
        attn.note_choice("reference", 16, 16)
        attn.note_choice("reference", 1, 2048, group=4)  # cached GQA decode
        attn.note_choice("flash", 256, 256, group=4)  # the kernel repeats heads
        got = {p: n - before.get(p, 0) for p, n in count().items()}
        assert {p for p, n in got.items() if n} == {
            "attention.flash[512x512]",
            "attention.flash[144x144]+pad[256x256]+shard_map",
            "attention.reference[16x16]",
            "attention.reference[1x2048]+gqa4",
            "attention.flash[256x256]"}

    def test_ring_matches_reference(self):
        mesh = make_mesh("sp=8")
        ref = attn.attention_reference(self.q, self.k, self.v)
        rg = attn.ring_attention(self.q, self.k, self.v, mesh, axis="sp")
        np.testing.assert_allclose(np.asarray(rg), np.asarray(ref), atol=2e-6)

    def test_ring_noncausal(self):
        mesh = make_mesh("sp=4", devices=jax.devices()[:4])
        ref = attn.attention_reference(self.q, self.k, self.v, causal=False)
        rg = attn.ring_attention(self.q, self.k, self.v, mesh, axis="sp", causal=False)
        np.testing.assert_allclose(np.asarray(rg), np.asarray(ref), atol=2e-6)

    def test_ring_chunked_inner_loop(self):
        """The per-hop merge streams k/v in block_k chunks (bounded memory);
        multi-chunk online softmax must still match the dense reference."""
        mesh = make_mesh("sp=2", devices=jax.devices()[:2])
        ref = attn.attention_reference(self.q, self.k, self.v)
        rg = attn.ring_attention(self.q, self.k, self.v, mesh, axis="sp", block_k=16)
        np.testing.assert_allclose(np.asarray(rg), np.asarray(ref), atol=2e-6)
        # non-causal too (no cond-skip path)
        ref = attn.attention_reference(self.q, self.k, self.v, causal=False)
        rg = attn.ring_attention(
            self.q, self.k, self.v, mesh, axis="sp", causal=False, block_k=16
        )
        np.testing.assert_allclose(np.asarray(rg), np.asarray(ref), atol=2e-6)

    def test_ring_is_reverse_differentiable(self):
        """sp-mesh training runs ring attention under value_and_grad; the
        chunked merge must stay AD-compatible (a traced fori_loop bound
        would raise 'Reverse-mode differentiation does not work...')."""
        mesh = make_mesh("sp=2", devices=jax.devices()[:2])

        def loss(q):
            return jnp.sum(attn.ring_attention(q, self.k, self.v, mesh, axis="sp"))

        g = jax.grad(loss)(self.q)
        assert np.isfinite(np.asarray(g)).all()

    def test_ulysses_matches_reference(self):
        mesh = make_mesh("sp=4", devices=jax.devices()[:4])
        ref = attn.attention_reference(self.q, self.k, self.v)
        ul = attn.ulysses_attention(self.q, self.k, self.v, mesh, axis="sp", interpret=True)
        np.testing.assert_allclose(np.asarray(ul), np.asarray(ref), atol=2e-6)

    def test_ulysses_noncausal(self):
        mesh = make_mesh("sp=4", devices=jax.devices()[:4])
        ref = attn.attention_reference(self.q, self.k, self.v, causal=False)
        ul = attn.ulysses_attention(self.q, self.k, self.v, mesh, axis="sp", causal=False, interpret=True)
        np.testing.assert_allclose(np.asarray(ul), np.asarray(ref), atol=2e-6)

    def test_ulysses_gqa_repeats_heads(self):
        mesh = make_mesh("sp=4", devices=jax.devices()[:4])
        kv = self.k[:, :2], self.v[:, :2]  # 2 kv heads don't divide sp=4
        ref = attn.attention_reference(self.q, *kv)
        ul = attn.ulysses_attention(self.q, *kv, mesh=mesh, axis="sp", interpret=True)
        np.testing.assert_allclose(np.asarray(ul), np.asarray(ref), atol=2e-6)

    def test_ulysses_gqa_partial_repeat(self):
        """Hq=8, Hkv=2, sp=4: kv repeats only to lcm(2,4)=4 heads; the local
        flash kernel finishes the per-device repeat."""
        rng = np.random.RandomState(7)
        q8 = jnp.array(rng.rand(2, 8, 128, 32), jnp.float32)
        k2 = jnp.array(rng.rand(2, 2, 128, 32), jnp.float32)
        v2 = jnp.array(rng.rand(2, 2, 128, 32), jnp.float32)
        mesh = make_mesh("sp=4", devices=jax.devices()[:4])
        ref = attn.attention_reference(q8, k2, v2)
        ul = attn.ulysses_attention(q8, k2, v2, mesh, axis="sp", interpret=True)
        np.testing.assert_allclose(np.asarray(ul), np.asarray(ref), atol=2e-6)

    def test_ulysses_head_mismatch_raises(self):
        mesh = make_mesh("sp=8")
        q = self.q[:, :4]  # 4 heads, sp=8
        with pytest.raises(ValueError, match="heads"):
            attn.ulysses_attention(q, self.k[:, :4], self.v[:, :4], mesh, axis="sp", interpret=True)


class TestLlama:
    def test_param_shapes_match_init(self, cfg, params):
        shapes = llama.param_shapes(cfg)
        assert set(shapes) == set(params)
        for name, shape in shapes.items():
            assert params[name].shape == shape, name

    def test_forward_shape(self, cfg, params, tokens):
        logits, cache = llama.forward(params, tokens, cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert cache is None

    def test_tp_sharded_forward_matches(self, cfg, params, tokens):
        base, _ = llama.forward(params, tokens, cfg)
        mesh = make_mesh("dp=2,tp=4")
        sp = shard_params(params, LLAMA_RULES, mesh)
        f = jax.jit(lambda p, t: llama.forward(p, t, cfg, mesh=mesh)[0])
        sharded = f(sp, jax.device_put(tokens, batch_sharding(mesh)))
        np.testing.assert_allclose(
            np.asarray(sharded, np.float32), np.asarray(base, np.float32), atol=1e-1
        )

    def test_sp_ring_forward_matches(self, cfg, params, tokens):
        base, _ = llama.forward(params, tokens, cfg)
        mesh = make_mesh("dp=2,sp=2,tp=2")
        sp = shard_params(params, LLAMA_RULES, mesh)
        f = jax.jit(lambda p, t: llama.forward(p, t, cfg, mesh=mesh)[0])
        sharded = f(sp, jax.device_put(tokens, batch_sharding(mesh)))
        np.testing.assert_allclose(
            np.asarray(sharded, np.float32), np.asarray(base, np.float32), atol=1e-1
        )

    def test_kv_cache_decode_matches_full_forward(self, cfg, params, tokens):
        """Prefill+decode must agree with teacher-forced full forward."""
        full, _ = llama.forward(params, tokens, cfg)
        cache = llama.init_kv_cache(cfg, 2, 16)
        logits_p, cache = llama.forward(params, tokens[:, :8], cfg, kv_cache=cache, cache_offset=0)
        np.testing.assert_allclose(
            np.asarray(logits_p, np.float32), np.asarray(full[:, :8], np.float32), atol=5e-2
        )
        # decode position 8 with the cache: must match full forward position 8
        step_logits, cache = llama.forward(
            params, tokens[:, 8:9], cfg, kv_cache=cache, cache_offset=8
        )
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0], np.float32),
            np.asarray(full[:, 8], np.float32),
            atol=5e-2,
        )

    def test_greedy_generate(self, cfg, params, tokens):
        out = FAMILIES["llama"].generate(params, tokens[:, :8], cfg, max_new_tokens=4)
        assert out.shape == (2, 12)
        full, _ = llama.forward(params, tokens[:, :8], cfg)
        np.testing.assert_array_equal(
            np.asarray(out[:, 8]), np.asarray(jnp.argmax(full[:, -1], axis=-1))
        )

    def test_loader_roundtrip_into_model(self, cfg, params, tmp_path):
        """Checkpoint -> safetensors -> registry-style load -> identical logits.
        The core promise: registry checkpoints drop into the model unchanged."""
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors

        path = str(tmp_path / "ckpt.safetensors")
        st.write_safetensors(path, {k: np.asarray(v) for k, v in params.items()})
        mesh = make_mesh("dp=2,tp=4")
        loaded, _ = load_safetensors(LocalFileSource(path), mesh, LLAMA_RULES)
        tokens = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        base, _ = llama.forward(params, tokens, cfg)
        via_registry, _ = llama.forward(loaded, tokens, cfg, mesh=mesh)
        np.testing.assert_allclose(
            np.asarray(via_registry, np.float32), np.asarray(base, np.float32), atol=1e-1
        )


class TestTrainStep:
    def test_loss_decreases(self, cfg, params, tokens):
        mesh = make_mesh("dp=2,tp=4")
        sp = shard_params(params, LLAMA_RULES, mesh)
        opt = make_optimizer(lr=1e-2)
        step = jax.jit(make_train_step(cfg, opt, mesh=mesh))
        opt_state = opt.init(sp)
        batch = {
            "tokens": jax.device_put(tokens, batch_sharding(mesh)),
            "targets": jax.device_put(jnp.roll(tokens, -1, axis=1), batch_sharding(mesh)),
        }
        losses = []
        for _ in range(5):
            sp, opt_state, loss = step(sp, opt_state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses

    def test_cross_entropy_sanity(self):
        logits = jnp.zeros((1, 2, 4))
        targets = jnp.array([[0, 1]], jnp.int32)
        assert abs(float(cross_entropy_loss(logits, targets)) - np.log(4)) < 1e-5


class TestRaggedDecode:
    """Ragged batched generation (models/decode.ragged_greedy_generate):
    right-padded rows decoding from per-row offsets must reproduce each
    row's UNBATCHED generation exactly — the correctness bar for the
    serving batcher's generate coalescing."""

    def _f32_cfg(self):
        import dataclasses

        return dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)

    # ~9 s; ragged exactness stays pinned by the sampling-independence test
    @pytest.mark.slow
    def test_matches_unbatched_rows(self):
        cfg = self._f32_cfg()
        params = llama.init_params(cfg, jax.random.PRNGKey(3))
        rng = np.random.RandomState(7)
        lens = [3, 7, 12, 12, 1]
        new = 6
        S = max(lens)
        prompts = [jnp.array(rng.randint(1, cfg.vocab_size, (1, n)), jnp.int32) for n in lens]
        batch = np.zeros((len(lens), S), np.int32)
        for i, p in enumerate(prompts):
            batch[i, : lens[i]] = np.asarray(p[0])
        got = FAMILIES["llama"].generate_ragged(
            params, jnp.asarray(batch), jnp.asarray(lens), cfg, max_new_tokens=new
        )
        assert got.shape == (len(lens), new)
        for i, p in enumerate(prompts):
            solo = FAMILIES["llama"].generate(params, p, cfg, max_new_tokens=new)
            np.testing.assert_array_equal(
                np.asarray(got[i]), np.asarray(solo[0, lens[i]:]), err_msg=f"row {i}"
            )

    def test_uniform_lengths_degenerate_to_plain(self):
        cfg = self._f32_cfg()
        params = llama.init_params(cfg, jax.random.PRNGKey(4))
        rng = np.random.RandomState(8)
        prompt = jnp.array(rng.randint(1, cfg.vocab_size, (3, 9)), jnp.int32)
        new = 5
        got = FAMILIES["llama"].generate_ragged(
            params, prompt, jnp.full((3,), 9, jnp.int32), cfg, max_new_tokens=new
        )
        plain = FAMILIES["llama"].generate(params, prompt, cfg, max_new_tokens=new)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain[:, 9:]))

    def test_zero_new_tokens(self):
        cfg = self._f32_cfg()
        params = llama.init_params(cfg, jax.random.PRNGKey(5))
        out = FAMILIES["llama"].generate_ragged(
            params, jnp.ones((2, 4), jnp.int32), jnp.array([2, 4]), cfg, max_new_tokens=0
        )
        assert out.shape == (2, 0)

    # ~7 s (mixtral compile); moe ops have their own tier-1 coverage
    @pytest.mark.slow
    def test_mixtral_ragged_matches_unbatched(self):
        import dataclasses

        from modelx_tpu.models import mixtral

        cfg = dataclasses.replace(mixtral.MixtralConfig.tiny(), dtype=jnp.float32)
        params = mixtral.init_params(cfg, jax.random.PRNGKey(6))
        rng = np.random.RandomState(9)
        lens = [2, 5]
        S, new = max(lens), 4
        batch = np.zeros((2, S), np.int32)
        prompts = []
        for i, n in enumerate(lens):
            p = rng.randint(1, cfg.vocab_size, (1, n)).astype(np.int32)
            prompts.append(jnp.asarray(p))
            batch[i, :n] = p[0]
        got = FAMILIES["mixtral"].generate_ragged(
            params, jnp.asarray(batch), jnp.asarray(lens), cfg, max_new_tokens=new
        )
        for i, p in enumerate(prompts):
            solo = FAMILIES["mixtral"].generate(params, p, cfg, max_new_tokens=new)
            np.testing.assert_array_equal(
                np.asarray(got[i]), np.asarray(solo[0, lens[i]:]), err_msg=f"row {i}"
            )


class TestSampling:
    """ops/sampling.sample: per-row temperature/top-k/top-p controls inside
    one program, deterministic per (seed, step) stream."""

    def _logits(self, b=4, v=50):
        rng = np.random.RandomState(11)
        return jnp.asarray(rng.randn(b, v) * 3, jnp.float32)

    def test_zero_temperature_rows_are_greedy(self):
        from modelx_tpu.ops import sampling

        lg = self._logits()
        out = sampling.sample(
            lg, jax.random.PRNGKey(0),
            temperature=jnp.array([0.0, 1.0, 0.0, 2.0]),
            top_k=jnp.zeros(4, jnp.int32), top_p=jnp.ones(4),
            seeds=jnp.arange(4), step=0,
        )
        greedy = jnp.argmax(lg, axis=-1)
        assert out[0] == greedy[0] and out[2] == greedy[2]

    def test_top_k_one_is_greedy_at_any_temperature(self):
        from modelx_tpu.ops import sampling

        lg = self._logits()
        out = sampling.sample(
            lg, jax.random.PRNGKey(0),
            temperature=jnp.full(4, 5.0), top_k=jnp.ones(4, jnp.int32),
            top_p=jnp.ones(4), seeds=jnp.arange(4), step=3,
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.argmax(lg, -1)))

    def test_tiny_top_p_is_greedy(self):
        from modelx_tpu.ops import sampling

        lg = self._logits()
        out = sampling.sample(
            lg, jax.random.PRNGKey(0),
            temperature=jnp.full(4, 5.0), top_k=jnp.zeros(4, jnp.int32),
            top_p=jnp.full(4, 1e-6), seeds=jnp.arange(4), step=1,
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.argmax(lg, -1)))

    def test_deterministic_per_seed_and_step(self):
        from modelx_tpu.ops import sampling

        lg = self._logits()
        kw = dict(temperature=jnp.full(4, 1.0), top_k=jnp.zeros(4, jnp.int32),
                  top_p=jnp.ones(4), seeds=jnp.full(4, 7))
        a = sampling.sample(lg, jax.random.PRNGKey(0), step=2, **kw)
        b = sampling.sample(lg, jax.random.PRNGKey(0), step=2, **kw)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = sampling.sample(lg, jax.random.PRNGKey(0), step=3, **kw)
        assert not np.array_equal(np.asarray(a), np.asarray(c))  # stream advances

    def test_sampled_tokens_respect_top_k_support(self):
        from modelx_tpu.ops import sampling

        lg = self._logits(b=2, v=100)
        k = 5
        allowed = np.argsort(-np.asarray(lg), axis=-1)[:, :k]
        for step in range(6):
            out = np.asarray(sampling.sample(
                lg, jax.random.PRNGKey(1),
                temperature=jnp.full(2, 3.0), top_k=jnp.full(2, k, jnp.int32),
                top_p=jnp.ones(2), seeds=jnp.arange(2), step=step,
            ))
            for b in range(2):
                assert out[b] in allowed[b], (step, b)


class TestRaggedSampling:
    def test_sampled_row_independent_of_batch_neighbors(self):
        """A sampled row's output depends only on its own prompt, seed and
        controls — not on what else got coalesced into the batch."""
        import dataclasses

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(12))
        rng = np.random.RandomState(13)
        prompt = rng.randint(1, cfg.vocab_size, (1, 5)).astype(np.int32)
        kw = dict(max_new_tokens=6, temperature=jnp.array([0.9]),
                  top_k=jnp.array([0], jnp.int32), top_p=jnp.array([1.0]),
                  seeds=jnp.array([42], jnp.int32))
        solo = FAMILIES["llama"].generate_ragged(
            params, jnp.asarray(prompt), jnp.array([5]), cfg, **kw)
        # same row inside a 3-row ragged batch with different neighbors
        other = rng.randint(1, cfg.vocab_size, (2, 9)).astype(np.int32)
        batch = np.zeros((3, 9), np.int32)
        batch[0, :5] = prompt[0]
        batch[1:] = other
        out = FAMILIES["llama"].generate_ragged(
            params, jnp.asarray(batch), jnp.array([5, 9, 9]), cfg,
            max_new_tokens=6,
            temperature=jnp.array([0.9, 0.0, 1.5]),
            top_k=jnp.array([0, 0, 3], jnp.int32),
            top_p=jnp.array([1.0, 1.0, 0.9]),
            seeds=jnp.array([42, 0, 7], jnp.int32),
        )
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(solo[0]))
        # the greedy row matches plain greedy decoding
        greedy = FAMILIES["llama"].generate_ragged(
            params, jnp.asarray(other), jnp.array([9, 9]), cfg, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(greedy[0]))
