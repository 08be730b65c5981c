"""Gemma2 family (models/gemma2.py): HF parity, detection/inference,
cached decode exactness, and serving integration.

Gemma2's deltas vs llama — (1+w) RMSNorm with f32 scaling, sqrt(hidden)
embedding scale, sandwich norms, GeGLU, query_pre_attn_scalar attention
scale, attn/final logit softcaps, alternating sliding-window layers, tied
embeddings — are each the kind of silent-wrongness bug a generate smoke
test can't catch, so the oracle is HF `Gemma2ForCausalLM` itself on a
prompt LONGER than the tiny config's sliding window (both layer types
exercised with real masking differences)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import families as fam
from modelx_tpu.dl.families import FAMILIES
from modelx_tpu.parallel.mesh import make_mesh

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


TINY = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
            query_pre_attn_scalar=8.0, sliding_window=6)


def _tiny_cfg(**over):
    from modelx_tpu.models import gemma2

    return gemma2.Gemma2Config(dtype=jnp.float32, **{**TINY, **over})


def _hf_tiny():
    hf_cfg = transformers.Gemma2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, query_pre_attn_scalar=8, sliding_window=6,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-6,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        attention_dropout=0.0, tie_word_embeddings=True,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    return transformers.Gemma2ForCausalLM(hf_cfg).eval()


class TestHFParity:
    # ~9 s of compile: the sliding-window parity leg rides the slow set
    # (tier-1 wall-time budget); basic HF parity + the serving e2e stay
    @pytest.mark.slow
    def test_matches_huggingface_past_the_window(self, tmp_path):
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.sharding import GEMMA2_RULES
        from modelx_tpu.models import gemma2

        hf = _hf_tiny()
        # 12 tokens > sliding_window 6: the even (sliding) layer's mask
        # genuinely differs from the odd (global) layer's
        rng = np.random.RandomState(3)
        tokens = rng.randint(1, 128, (2, 12)).astype(np.int64)
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()

        sd = {k: v.numpy() for k, v in hf.state_dict().items()
              if "rotary_emb" not in k and k != "lm_head.weight"}
        path = str(tmp_path / "gemma2.safetensors")
        st.write_safetensors(path, sd)
        mesh = make_mesh("tp=2", devices=jax.devices()[:2])
        params, _ = load_safetensors(LocalFileSource(path), mesh, GEMMA2_RULES)

        got, _ = gemma2.forward(params, jnp.asarray(tokens, jnp.int32), _tiny_cfg())
        np.testing.assert_allclose(np.asarray(got), want, atol=3e-4, rtol=3e-4)

    def test_sliding_window_changes_logits(self):
        """Sanity that the window is live: widening it past the sequence
        must change long-context logits (if not, the mask was never
        applied and parity only held by luck)."""
        from modelx_tpu.models import gemma2

        cfg = _tiny_cfg()
        params = gemma2.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(5)
        tokens = jnp.asarray(rng.randint(1, 128, (1, 12)), jnp.int32)
        with_window, _ = gemma2.forward(params, tokens, cfg)
        no_window, _ = gemma2.forward(
            params, tokens, dataclasses.replace(cfg, sliding_window=64))
        assert not np.allclose(np.asarray(with_window), np.asarray(no_window))


class TestDetectionInference:
    def test_detected_and_inferred(self):
        from modelx_tpu.dl.sharding import infer_family
        from modelx_tpu.models import gemma2

        cfg = gemma2.Gemma2Config.tiny(vocab_size=64)
        params = gemma2.init_params(cfg, jax.random.PRNGKey(0))
        assert infer_family(list(params)) == "gemma2"
        family = fam.detect(list(params))
        icfg = family.infer_config(params)
        assert icfg.num_layers == cfg.num_layers
        assert icfg.head_dim == cfg.head_dim
        assert icfg.num_heads == cfg.num_heads
        assert icfg.attn_logit_softcap == 50.0

    def test_real_shape_inference(self):
        """2b/9b infer head_dim 256; 27b (hidden 4608) infers 128 with
        query_pre_attn_scalar 144 — same-shaped q/kv as 9b, disambiguated
        by hidden size."""
        import ml_dtypes

        def probe(hidden, q, kv, inter, vocab=256000):
            shapes = {
                "model.embed_tokens.weight": (vocab, hidden),
                "model.layers.0.self_attn.q_proj.weight": (q, hidden),
                "model.layers.0.self_attn.k_proj.weight": (kv, hidden),
                "model.layers.0.mlp.gate_proj.weight": (inter, hidden),
            }
            params = {k: jax.ShapeDtypeStruct(v, ml_dtypes.bfloat16)
                      for k, v in shapes.items()}
            return fam.infer_gemma2_config(params)

        c2b = probe(2304, 2048, 1024, 9216)
        assert (c2b.head_dim, c2b.num_heads, c2b.num_kv_heads) == (256, 8, 4)
        assert c2b.query_pre_attn_scalar == 256.0
        c9b = probe(3584, 4096, 2048, 14336)
        assert (c9b.head_dim, c9b.num_heads, c9b.num_kv_heads) == (256, 16, 8)
        c27b = probe(4608, 4096, 2048, 36864)
        assert (c27b.head_dim, c27b.num_heads, c27b.num_kv_heads) == (128, 32, 16)
        assert c27b.query_pre_attn_scalar == 144.0


class TestDecode:
    # heaviest single tier-1 test (~21 s of compiled-exactness); slow set
    @pytest.mark.slow
    def test_kv_cache_decode_matches_full_forward(self):
        """Prefill + single-token cached steps must reproduce the full
        forward's last-position logits at every step — including steps past
        the sliding window (the cached path masks from q_offset)."""
        from modelx_tpu.models import gemma2

        cfg = _tiny_cfg()
        params = gemma2.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.RandomState(7)
        seq = rng.randint(1, 128, (1, 11)).astype(np.int32)
        prompt_len = 3

        cache = gemma2.init_kv_cache(cfg, 1, 16)
        logits, cache = gemma2.forward(
            params, jnp.asarray(seq[:, :prompt_len]), cfg,
            kv_cache=cache, cache_offset=0,
        )
        for pos in range(prompt_len, seq.shape[1]):
            full, _ = gemma2.forward(params, jnp.asarray(seq[:, :pos]), cfg)
            np.testing.assert_allclose(
                np.asarray(logits[:, -1]), np.asarray(full[:, -1]),
                atol=2e-4, rtol=2e-4,
            )
            logits, cache = gemma2.forward(
                params, jnp.asarray(seq[:, pos:pos + 1]), cfg,
                kv_cache=cache, cache_offset=pos,
            )

    # tier-1 wall (ISSUE 16): TestServing::test_paged_in_place_engine_exact keeps gemma2 tier-1
    @pytest.mark.slow
    def test_greedy_generate_matches_naive(self):
        from modelx_tpu.models import gemma2

        cfg = _tiny_cfg()
        params = gemma2.init_params(cfg, jax.random.PRNGKey(2))
        prompt = jnp.asarray([[5, 9, 2]], jnp.int32)
        got = FAMILIES["gemma2"].generate(params, prompt, cfg, max_new_tokens=9)
        # naive: full re-forward per step
        toks = prompt
        for _ in range(9):
            logits, _ = gemma2.forward(params, toks, cfg)
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            toks = jnp.concatenate([toks, nxt.astype(jnp.int32)], axis=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(toks))


class TestFlashGemma2Semantics:
    @pytest.mark.parametrize("window", [0, 12, 48])
    def test_flash_softcap_window_matches_reference(self, window):
        """The pallas kernel (interpret mode on CPU) with scale/softcap/
        window must match attention_reference — the contract gemma2's TPU
        prefill rides. Blocked shapes (block 16 over seq 64) exercise the
        window-aware lower block skip and the all-masked-block exp fix."""
        from modelx_tpu.ops.attention import attention_reference, flash_attention

        rng = np.random.RandomState(1)
        B, H, S, D = 2, 4, 64, 16
        q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, H // 2, S, D), jnp.float32)  # GQA
        v = jnp.asarray(rng.randn(B, H // 2, S, D), jnp.float32)
        kw = dict(scale=32.0 ** -0.5, logit_softcap=50.0, window=window)
        ref = attention_reference(q, k, v, causal=True, **kw)
        got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestPagedAttentionGemma2Semantics:
    def test_softcap_window_matches_reference(self):
        """paged_attention with scale/softcap/window must match
        attention_reference given the SAME kwargs over the equivalent
        dense cache — the contract the gemma2 in-place decode rides."""
        from modelx_tpu.ops.attention import attention_reference
        from modelx_tpu.ops.paged_attention import paged_attention

        rng = np.random.RandomState(0)
        S, Hq, Hkv, D, ps, pps = 3, 4, 2, 16, 8, 6
        max_len = ps * pps
        P = 1 + S * pps
        lengths = np.array([5, 17, 44], np.int32)
        dense_k = rng.randn(S, max_len, Hkv, D).astype(np.float32)
        dense_v = rng.randn(S, max_len, Hkv, D).astype(np.float32)
        pool_k = np.zeros((P, ps, Hkv, D), np.float32)
        pool_v = np.zeros((P, ps, Hkv, D), np.float32)
        table = np.zeros((S, pps), np.int32)
        pid = 1
        for s in range(S):
            for j in range(pps):
                table[s, j] = pid
                pool_k[pid] = dense_k[s, j * ps:(j + 1) * ps]
                pool_v[pid] = dense_v[s, j * ps:(j + 1) * ps]
                pid += 1
        q = rng.randn(S, Hq, D).astype(np.float32)
        kw = dict(scale=32.0 ** -0.5, logit_softcap=50.0, window=12)
        ref = attention_reference(
            jnp.asarray(q)[:, :, None, :],
            jnp.asarray(dense_k).transpose(0, 2, 1, 3),
            jnp.asarray(dense_v).transpose(0, 2, 1, 3),
            causal=True, q_offset=jnp.asarray(lengths - 1), **kw,
        )[:, :, 0, :]
        got = paged_attention(
            jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(table), jnp.asarray(lengths), **kw,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestServing:
    def test_serves_end_to_end_with_continuous_engine(self, tmp_path):
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.continuous import ContinuousBatcher
        from modelx_tpu.dl.serve import ModelServer
        from modelx_tpu.models import gemma2

        cfg = gemma2.Gemma2Config.tiny(vocab_size=64)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = gemma2.init_params(cfg, jax.random.PRNGKey(3))
        d = tmp_path / "g2"
        d.mkdir()
        st.write_safetensors(
            str(d / "model.safetensors"), {k: np.asarray(v) for k, v in params.items()}
        )
        server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32",
                             max_seq_len=96, name="g2")
        server.load()
        assert server.family.name == "gemma2"
        prompt = np.asarray([[1, 2, 3]], np.int32)
        got = server.generate(prompt, max_new_tokens=6)
        # the server path must agree with the module's own decode; note the
        # inferred config (not the constructor's) drives serving, so this
        # also pins tiny-shape inference to the tiny() constants
        icfg = server.family.infer_config(params)
        want = FAMILIES["gemma2"].generate(params, jnp.asarray(prompt), icfg, max_new_tokens=6)
        np.testing.assert_array_equal(got, np.asarray(want))

        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        try:
            np.testing.assert_array_equal(
                cb.generate(prompt, max_new_tokens=6), got)
        finally:
            cb.close()

    def test_paged_in_place_engine_exact(self, tmp_path):
        """--kv-attention in-place wires gemma2's pool-reading forward
        (softcap + sliding window in the paged op) and must stay
        token-exact on the f32 fixture, past a page boundary AND past the
        tiny config's sliding window."""
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.continuous import ContinuousBatcher
        from modelx_tpu.dl.serve import ModelServer
        from modelx_tpu.models import gemma2

        cfg = dataclasses.replace(gemma2.Gemma2Config.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = gemma2.init_params(cfg, jax.random.PRNGKey(4))
        d = tmp_path / "g2p"
        d.mkdir()
        st.write_safetensors(
            str(d / "model.safetensors"), {k: np.asarray(v) for k, v in params.items()}
        )
        server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32",
                             max_seq_len=96, name="g2p")
        server.load()
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4, page_size=16,
                               paged_attention="in-place")
        try:
            assert cb.kv.fwd_paged is not None  # gemma2 wires the paged fwd
            t = np.array([[5, 9, 2]], np.int32)
            # 28 new tokens: crosses page boundaries (ps 16) and decodes
            # past sliding_window 16, so the windowed layer's paged mask
            # does real work
            np.testing.assert_array_equal(
                cb.generate(t, max_new_tokens=28),
                server.generate(t, max_new_tokens=28),
            )
        finally:
            cb.close()
