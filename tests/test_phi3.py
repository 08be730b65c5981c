"""Phi-3 family (models/phi3.py): HF parity, detection/inference, decode
exactness, serving integration.

Phi-3 is llama with FUSED qkv_proj/gate_up_proj checkpoint tensors; the
forward un-fuses them with in-jit slices and delegates to llama's decoder
layer, so the oracle is HF `Phi3ForCausalLM` (wrong slice boundaries or a
swapped gate/up half would silently produce plausible-looking garbage)."""

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


def _tiny_cfg():
    from modelx_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=10000.0,
        rms_eps=1e-5, tie_embeddings=False, dtype=jnp.float32,
    )


class TestHFParity:
    def test_matches_huggingface(self, tmp_path):
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors
        from modelx_tpu.dl.sharding import PHI3_RULES
        from modelx_tpu.models import phi3

        hf_cfg = transformers.Phi3Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
            attention_dropout=0.0, resid_pdrop=0.0, embd_pdrop=0.0,
            tie_word_embeddings=False, pad_token_id=0,
        )
        torch.manual_seed(0)
        hf = transformers.Phi3ForCausalLM(hf_cfg).eval()
        rng = np.random.RandomState(3)
        tokens = rng.randint(1, 128, (2, 9)).astype(np.int64)
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()

        sd = {k: v.numpy() for k, v in hf.state_dict().items()
              if "rotary_emb" not in k}
        path = str(tmp_path / "phi3.safetensors")
        st.write_safetensors(path, sd)
        mesh = make_mesh("tp=2", devices=jax.devices()[:2])
        params, _ = load_safetensors(LocalFileSource(path), mesh, PHI3_RULES)

        got, _ = phi3.forward(params, jnp.asarray(tokens, jnp.int32), _tiny_cfg())
        np.testing.assert_allclose(np.asarray(got), want, atol=3e-4, rtol=3e-4)


class TestDetectionInference:
    def test_detected_and_inferred(self):
        from modelx_tpu.dl.sharding import infer_family
        from modelx_tpu.models import phi3

        cfg = _tiny_cfg()
        params = phi3.init_params(cfg, jax.random.PRNGKey(0))
        assert any(k.endswith("qkv_proj.weight") for k in params)
        assert infer_family(list(params)) == "phi3"
        family = fam.detect(list(params))
        icfg = family.infer_config(params)
        assert icfg.num_layers == cfg.num_layers
        assert icfg.head_dim == cfg.head_dim
        assert (icfg.num_heads, icfg.num_kv_heads) == (4, 2)
        assert not icfg.tie_embeddings

    def test_real_shape_inference(self):
        """mini (MHA, 32x96) and medium (GQA, 40x128) from fused shapes."""
        import ml_dtypes

        def probe(hidden, qkv_rows, inter2, vocab=32064):
            shapes = {
                "model.embed_tokens.weight": (vocab, hidden),
                "lm_head.weight": (vocab, hidden),
                "model.layers.0.self_attn.qkv_proj.weight": (qkv_rows, hidden),
                "model.layers.0.mlp.gate_up_proj.weight": (inter2, hidden),
            }
            params = {k: jax.ShapeDtypeStruct(v, ml_dtypes.bfloat16)
                      for k, v in shapes.items()}
            return fam.infer_phi3_config(params)

        mini = probe(3072, 3 * 3072, 2 * 8192)  # phi-3-mini: MHA
        assert (mini.head_dim, mini.num_heads, mini.num_kv_heads) == (96, 32, 32)
        assert mini.intermediate_size == 8192
        med = probe(5120, 5120 + 2 * 1280, 2 * 17920)  # phi-3-medium: GQA
        assert (med.head_dim, med.num_heads, med.num_kv_heads) == (128, 40, 10)


class TestDecode:
    # ~9 s compiled-exactness; the llama-shaped decode contract is also
    # covered per-family in tier-1 — this variant rides the slow set
    @pytest.mark.slow
    def test_kv_cache_decode_matches_full_forward(self):
        from modelx_tpu.models import phi3

        cfg = _tiny_cfg()
        params = phi3.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.RandomState(7)
        seq = rng.randint(1, 128, (1, 9)).astype(np.int32)
        prompt_len = 3
        cache = phi3.init_kv_cache(cfg, 1, 16)
        logits, cache = phi3.forward(
            params, jnp.asarray(seq[:, :prompt_len]), cfg,
            kv_cache=cache, cache_offset=0,
        )
        for pos in range(prompt_len, seq.shape[1]):
            full, _ = phi3.forward(params, jnp.asarray(seq[:, :pos]), cfg)
            np.testing.assert_allclose(
                np.asarray(logits[:, -1]), np.asarray(full[:, -1]),
                atol=2e-4, rtol=2e-4,
            )
            logits, cache = phi3.forward(
                params, jnp.asarray(seq[:, pos:pos + 1]), cfg,
                kv_cache=cache, cache_offset=pos,
            )


class TestServing:
    @pytest.fixture()
    def served(self, tmp_path):
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.serve import ModelServer
        from modelx_tpu.models import phi3

        cfg = dataclasses.replace(_tiny_cfg(), vocab_size=64)
        params = phi3.init_params(cfg, jax.random.PRNGKey(2))
        d = tmp_path / "p3"
        d.mkdir()
        st.write_safetensors(
            str(d / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32",
                             max_seq_len=96, name="p3")
        server.load()
        return server, params

    @pytest.mark.slow  # tier-1 wall: HF parity stays tier-1; generic serve e2e covers the engine
    def test_serves_end_to_end_with_continuous_engine(self, served):
        from modelx_tpu.dl.continuous import ContinuousBatcher
        from modelx_tpu.models import phi3

        server, params = served
        assert server.family.name == "phi3"
        prompt = np.asarray([[1, 2, 3]], np.int32)
        got = server.generate(prompt, max_new_tokens=6)
        icfg = server.family.infer_config(params)
        want = FAMILIES["phi3"].generate(params, jnp.asarray(prompt), icfg,
                                    max_new_tokens=6)
        np.testing.assert_array_equal(got, np.asarray(want))
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        try:
            np.testing.assert_array_equal(
                cb.generate(prompt, max_new_tokens=6), got)
        finally:
            cb.close()

    def test_int8_quantized_fused_weights_serve(self, served, tmp_path):
        """--quantize int8 must quantize the FUSED qkv/gate_up tensors (the
        eligibility regex names them explicitly) and the un-fusing slices
        must carry the per-row scales — a plain slice of a QTensor was a
        crash, mismatched scales would be silent garbage."""
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.serve import ModelServer
        from modelx_tpu.models import phi3
        from modelx_tpu.ops.quant import QTensor

        server, params = served
        d = tmp_path / "p3q"
        d.mkdir()
        st.write_safetensors(
            str(d / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        qsrv = ModelServer(str(d), mesh_spec="dp=1", dtype="float32",
                           max_seq_len=96, name="p3q", quantize="int8")
        qsrv.load()
        assert any(
            isinstance(v, QTensor) and k.endswith("qkv_proj.weight")
            for k, v in qsrv.params.items()
        ), "fused qkv_proj was not quantized"
        prompt = np.asarray([[1, 2, 3]], np.int32)
        got = qsrv.generate(prompt, max_new_tokens=6)
        # int8 is lossy: check agreement with the full-precision decode on
        # the FIRST token only if they happen to agree is too strict — the
        # real assertions are (a) it runs and (b) output is in-vocab
        assert got.shape == (1, 9)
        assert int(got.max()) < 64 and int(got.min()) >= 0

    def test_paged_in_place_engine_exact(self, served):
        """phi3 inherits llama's pool-reading paged decode through the
        delegated decoder layer; exact past page boundaries."""
        from modelx_tpu.dl.continuous import ContinuousBatcher

        server, _params = served
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4, page_size=16,
                               paged_attention="in-place")
        try:
            assert cb.kv.fwd_paged is not None
            t = np.array([[5, 9, 2]], np.int32)
            np.testing.assert_array_equal(
                cb.generate(t, max_new_tokens=28),
                server.generate(t, max_new_tokens=28),
            )
        finally:
            cb.close()
