"""GPT-2 and BERT family tests, including numerical parity against the
HuggingFace reference implementations (torch CPU) through the full
checkpoint->safetensors->loader->forward path."""

import dataclasses
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import families as fam
from modelx_tpu.dl.families import FAMILIES
from modelx_tpu.dl.sharding import BERT_RULES, GPT2_RULES
from modelx_tpu.models import bert, gpt2
from modelx_tpu.parallel.mesh import make_mesh

transformers = pytest.importorskip("transformers")
import torch  # noqa: E402  (cpu build, baked in)


class TestGPT2:
    def test_shapes_and_forward(self):
        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        assert set(params) == set(gpt2.param_shapes(cfg))
        tokens = jnp.array([[1, 2, 3, 4, 5]], jnp.int32)
        logits, cache = gpt2.forward(params, tokens, cfg)
        assert logits.shape == (1, 5, cfg.vocab_size)
        assert cache is None

    def test_matches_huggingface(self, tmp_path):
        hf_cfg = transformers.GPT2Config(
            vocab_size=128, n_positions=32, n_embd=32, n_layer=2, n_head=2,
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
        )
        torch.manual_seed(0)
        hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
        tokens = np.array([[3, 14, 15, 92, 65, 35]], np.int64)
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()

        # export -> safetensors -> our loader -> our forward
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors

        sd = {
            k.removeprefix("transformer."): v.numpy()
            for k, v in hf.state_dict().items()
            if not k.endswith(".attn.bias") and k != "lm_head.weight"
        }
        path = str(tmp_path / "gpt2.safetensors")
        st.write_safetensors(path, sd)
        mesh = make_mesh("tp=2", devices=jax.devices()[:2])
        params, _ = load_safetensors(LocalFileSource(path), mesh, GPT2_RULES)

        cfg = gpt2.GPT2Config(vocab_size=128, n_positions=32, hidden_size=32, num_layers=2, num_heads=2)
        got = np.asarray(gpt2.forward(params, jnp.asarray(tokens, jnp.int32), cfg)[0])
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)

    # ~10 s compiled-exactness; HF parity + engine tests keep gpt2 covered
    @pytest.mark.slow
    def test_kv_cache_decode_matches_full_forward(self):
        """Cached decode (prefill + per-token steps) must equal argmax over
        repeated full forwards — the llama/mixtral decode contract, now on
        GPT-2 through the shared decode module."""
        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jnp.array([[5, 6, 7, 5, 6]], jnp.int32)
        n = 8
        naive = prompt
        for _ in range(n):
            logits, _ = gpt2.forward(params, naive, cfg)
            naive = jnp.concatenate(
                [naive, jnp.argmax(logits[:, -1:, :], axis=-1).astype(naive.dtype)], axis=1
            )
        cached = FAMILIES["gpt2"].generate(params, prompt, cfg, max_new_tokens=n)
        np.testing.assert_array_equal(np.asarray(cached), np.asarray(naive))

    def test_ragged_decode_matches_unbatched(self):
        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
        rows = [[3, 14, 15], [9, 2, 6, 5, 3]]
        n = 6
        want = [
            np.asarray(FAMILIES["gpt2"].generate(
                params, jnp.asarray([r], jnp.int32), cfg, max_new_tokens=n
            ))[0, len(r):]
            for r in rows
        ]
        s = max(len(r) for r in rows)
        padded = np.zeros((2, s), np.int32)
        for i, r in enumerate(rows):
            padded[i, :len(r)] = r
        got = FAMILIES["gpt2"].generate_ragged(
            params, jnp.asarray(padded), np.asarray([len(r) for r in rows], np.int32),
            cfg, max_new_tokens=n,
        )
        for i in range(2):
            np.testing.assert_array_equal(np.asarray(got)[i], want[i])


class TestBert:
    def test_shapes_and_forward(self):
        cfg = bert.BertConfig.tiny()
        params = bert.init_params(cfg, jax.random.PRNGKey(0))
        assert set(params) == set(bert.param_shapes(cfg))
        tokens = jnp.array([[1, 2, 3, 4]], jnp.int32)
        seq, pooled = bert.forward(params, tokens, cfg)
        assert seq.shape == (1, 4, cfg.hidden_size)
        assert pooled.shape == (1, cfg.hidden_size)

    def test_matches_huggingface(self, tmp_path):
        hf_cfg = transformers.BertConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
            intermediate_size=64, max_position_embeddings=32,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        )
        torch.manual_seed(0)
        hf = transformers.BertModel(hf_cfg).eval()
        tokens = np.array([[5, 9, 33, 101]], np.int64)
        with torch.no_grad():
            out = hf(torch.tensor(tokens))
            want_seq = out.last_hidden_state.numpy()
            want_pooled = out.pooler_output.numpy()

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors

        sd = {
            "bert." + k: v.numpy()
            for k, v in hf.state_dict().items()
            if "position_ids" not in k
        }
        path = str(tmp_path / "bert.safetensors")
        st.write_safetensors(path, sd)
        mesh = make_mesh("tp=2", devices=jax.devices()[:2])
        params, _ = load_safetensors(LocalFileSource(path), mesh, BERT_RULES)

        cfg = bert.BertConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_position_embeddings=32,
        )
        got_seq, got_pooled = bert.forward(params, jnp.asarray(tokens, jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(got_seq), want_seq, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(got_pooled), want_pooled, atol=2e-4, rtol=2e-4)


class TestLlamaHFParity:
    def test_matches_huggingface(self, tmp_path):
        from modelx_tpu.dl.sharding import LLAMA_RULES
        from modelx_tpu.models import llama

        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
            attention_dropout=0.0, tie_word_embeddings=False,
        )
        torch.manual_seed(0)
        hf = transformers.LlamaForCausalLM(hf_cfg).eval()
        tokens = np.array([[3, 14, 15, 92, 65]], np.int64)
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors

        sd = {k: v.numpy() for k, v in hf.state_dict().items() if "rotary_emb" not in k}
        path = str(tmp_path / "llama.safetensors")
        st.write_safetensors(path, sd)
        mesh = make_mesh("tp=2", devices=jax.devices()[:2])
        params, _ = load_safetensors(LocalFileSource(path), mesh, LLAMA_RULES)

        cfg = llama.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=10000.0,
            dtype=jnp.float32,
        )
        got, _ = llama.forward(params, jnp.asarray(tokens, jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)


class TestQwen2:
    def test_detected_and_inferred(self):
        from modelx_tpu.dl.sharding import infer_family
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  qkv_bias=True, dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        assert any(k.endswith("q_proj.bias") for k in params)
        assert infer_family(list(params)) == "qwen2"
        family = fam.detect(list(params))
        icfg = family.infer_config(params)
        assert icfg.qkv_bias and icfg.rms_eps == 1e-6
        assert icfg.rope_theta == 1_000_000.0

    def test_head_dim_inference_qwen2_0p5b_shapes(self):
        """Qwen2-0.5B: 14 heads x 64 with 2 kv heads. head_dim=128 would
        'fit' (7 x 1) but garble attention; the kv>=2-heads rule must pick
        64."""
        import ml_dtypes

        shapes = {
            "model.embed_tokens.weight": (151936, 896),
            "model.layers.0.self_attn.q_proj.weight": (896, 896),
            "model.layers.0.self_attn.k_proj.weight": (128, 896),
            "model.layers.0.mlp.gate_proj.weight": (4864, 896),
        }
        params = {k: jax.ShapeDtypeStruct(v, ml_dtypes.bfloat16) for k, v in shapes.items()}
        cfg = fam.infer_llama_config(params)
        assert (cfg.head_dim, cfg.num_heads, cfg.num_kv_heads) == (64, 14, 2)
        # llama3-8b shapes still infer 128 (32 heads, 8 kv)
        shapes = {
            "model.embed_tokens.weight": (128256, 4096),
            "model.layers.0.self_attn.q_proj.weight": (4096, 4096),
            "model.layers.0.self_attn.k_proj.weight": (1024, 4096),
            "model.layers.0.mlp.gate_proj.weight": (14336, 4096),
        }
        params = {k: jax.ShapeDtypeStruct(v, ml_dtypes.bfloat16) for k, v in shapes.items()}
        cfg = fam.infer_llama_config(params)
        assert (cfg.head_dim, cfg.num_heads, cfg.num_kv_heads) == (128, 32, 8)

    def test_biases_affect_forward(self):
        """A forward that ignored the biases would match the stripped dict;
        it must not."""
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  qkv_bias=True, dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.array([[1, 2, 3]], jnp.int32)
        with_bias, _ = llama.forward(params, tokens, cfg)
        stripped = {k: v for k, v in params.items() if not k.endswith(".bias")}
        without, _ = llama.forward(stripped, tokens, cfg)
        assert not np.allclose(np.asarray(with_bias), np.asarray(without))

    def test_matches_huggingface(self, tmp_path):
        from modelx_tpu.dl.sharding import QWEN2_RULES
        from modelx_tpu.models import llama

        hf_cfg = transformers.Qwen2Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-6,
            attention_dropout=0.0, tie_word_embeddings=False,
        )
        torch.manual_seed(0)
        hf = transformers.Qwen2ForCausalLM(hf_cfg).eval()
        tokens = np.array([[3, 14, 15, 92, 65]], np.int64)
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors

        sd = {k: v.numpy() for k, v in hf.state_dict().items() if "rotary_emb" not in k}
        path = str(tmp_path / "qwen2.safetensors")
        st.write_safetensors(path, sd)
        mesh = make_mesh("tp=2", devices=jax.devices()[:2])
        params, _ = load_safetensors(LocalFileSource(path), mesh, QWEN2_RULES)
        # biases landed tp-sharded like their weights' output features
        qb = params["model.layers.0.self_attn.q_proj.bias"]
        assert {s.data.shape for s in qb.addressable_shards} == {(16,)}

        cfg = llama.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=10000.0,
            rms_eps=1e-6, qkv_bias=True, dtype=jnp.float32,
        )
        got, _ = llama.forward(params, jnp.asarray(tokens, jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)

    def test_serves_end_to_end(self, tmp_path):
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.serve import ModelServer
        from modelx_tpu.models import llama

        # constants must match what family inference assumes for qwen2
        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(vocab_size=64), qkv_bias=True,
            dtype=jnp.float32, rope_theta=1_000_000.0, rms_eps=1e-6,
        )
        params = llama.init_params(cfg, jax.random.PRNGKey(1))
        d = tmp_path / "qwen"
        d.mkdir()
        st.write_safetensors(
            str(d / "model.safetensors"), {k: np.asarray(v) for k, v in params.items()}
        )
        server = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", name="q")
        server.load()
        assert server.family.name == "qwen2"
        prompt = np.asarray([[1, 2, 3]], np.int32)
        got = server.generate(prompt, max_new_tokens=4)
        want = FAMILIES["llama"].generate(params, jnp.asarray(prompt), cfg, max_new_tokens=4)
        np.testing.assert_array_equal(got, np.asarray(want))


class TestMixtral:
    def test_shapes_and_forward(self):
        from modelx_tpu.models import mixtral

        cfg = mixtral.MixtralConfig.tiny(vocab_size=128)
        params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
        assert set(params) == set(mixtral.param_shapes(cfg))
        tokens = jnp.array([[1, 2, 3, 4, 5]], jnp.int32)
        logits, _ = mixtral.forward(params, tokens, cfg)
        assert logits.shape == (1, 5, cfg.vocab_size)

    def test_matches_huggingface(self, tmp_path):
        from modelx_tpu.dl.sharding import MIXTRAL_RULES
        from modelx_tpu.models import mixtral

        hf_cfg = transformers.MixtralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            num_local_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
            attention_dropout=0.0, tie_word_embeddings=False,
        )
        torch.manual_seed(0)
        hf = transformers.MixtralForCausalLM(hf_cfg).eval()
        tokens = np.array([[3, 14, 15, 92, 65]], np.int64)
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.numpy()

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors

        # stock HF per-expert layout on disk — the loader's expert-fusion
        # pre-pass must assemble the ep-sharded stacked tensors itself
        sd = {k: v.numpy() for k, v in hf.state_dict().items() if "rotary_emb" not in k}
        path = str(tmp_path / "mixtral.safetensors")
        st.write_safetensors(path, sd)
        mesh = make_mesh("ep=2,tp=2", devices=jax.devices()[:4])
        params, _ = load_safetensors(LocalFileSource(path), mesh, MIXTRAL_RULES)
        assert "model.layers.0.block_sparse_moe.experts.w1.weight" in params
        stacked_host = mixtral.from_hf_state_dict(sd)
        np.testing.assert_array_equal(
            np.asarray(params["model.layers.1.block_sparse_moe.experts.w2.weight"]),
            stacked_host["model.layers.1.block_sparse_moe.experts.w2.weight"],
        )

        cfg = mixtral.MixtralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=2,
            num_heads=4, num_kv_heads=2, head_dim=8, num_experts=4, top_k=2,
            rope_theta=10000.0, dtype=jnp.float32,
        )
        got, _ = mixtral.forward(params, jnp.asarray(tokens, jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(got), want, atol=3e-4, rtol=3e-4)

    def test_ep_sharded_matches_unsharded(self):
        from modelx_tpu.dl.sharding import MIXTRAL_RULES, sharding_for
        from modelx_tpu.models import mixtral

        cfg = mixtral.MixtralConfig.tiny(vocab_size=64)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = mixtral.init_params(cfg, jax.random.PRNGKey(1))
        tokens = jnp.array([[7, 3, 9, 1, 4, 2, 8, 6]], jnp.int32)
        want, _ = mixtral.forward(params, tokens, cfg)

        mesh = make_mesh("dp=1,ep=4,tp=2")
        sharded = {
            name: jax.device_put(v, sharding_for(name, MIXTRAL_RULES, mesh))
            for name, v in params.items()
        }
        got, _ = jax.jit(
            lambda p, t: mixtral.forward(p, t, cfg, mesh=mesh)
        )(sharded, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)

    def test_kv_cache_decode_matches_full_forward(self):
        from modelx_tpu.models import mixtral

        cfg = mixtral.MixtralConfig.tiny(vocab_size=64)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = mixtral.init_params(cfg, jax.random.PRNGKey(2))
        tokens = jnp.array([[5, 11, 23, 42]], jnp.int32)
        full, _ = mixtral.forward(params, tokens, cfg)

        cache = mixtral.init_kv_cache(cfg, 1, 8, dtype=jnp.float32)
        logits, cache = mixtral.forward(params, tokens[:, :3], cfg, kv_cache=cache, cache_offset=0)
        step, cache = mixtral.forward(params, tokens[:, 3:4], cfg, kv_cache=cache, cache_offset=3)
        np.testing.assert_allclose(
            np.asarray(step[:, 0]), np.asarray(full[:, 3]), atol=1e-4, rtol=1e-4
        )

    def test_load_balancing_loss(self):
        from modelx_tpu.ops import moe as moe_ops

        # uniform router probs (1/E each): loss = E * sum_e frac_e * (1/E)
        # = sum_e frac_e = k exactly, for ANY mask that routes each token to
        # k experts — the balanced floor of the Switch loss.
        logits = jnp.zeros((2, 16, 4))
        mask = jnp.zeros((2, 16, 4)).at[..., :2].set(1.0)
        loss = moe_ops.load_balancing_loss(logits, mask)
        np.testing.assert_allclose(float(loss), 2.0, rtol=1e-6)

        # skewed routing (all tokens to expert 0) must cost more than balanced
        skew_logits = jnp.zeros((2, 16, 4)).at[..., 0].set(10.0)
        _, skew_mask = moe_ops.router_topk(skew_logits, 1)
        balanced = moe_ops.load_balancing_loss(jnp.zeros((2, 16, 4)), jnp.eye(4)[jnp.arange(32).reshape(2, 16) % 4])
        skewed = moe_ops.load_balancing_loss(skew_logits, skew_mask)
        assert float(skewed) > float(balanced)


class TestMixtralGenerate:
    def test_cached_decode_matches_naive(self):
        """greedy_generate (KV cache + scan) must equal full re-forward."""
        from modelx_tpu.models import mixtral

        cfg = dataclasses.replace(mixtral.MixtralConfig.tiny(vocab_size=64), dtype=jnp.float32)
        params = mixtral.init_params(cfg, jax.random.PRNGKey(5))
        prompt = jnp.array([[3, 9, 12, 7]], jnp.int32)
        out = FAMILIES["mixtral"].generate(params, prompt, cfg, max_new_tokens=5)

        naive = prompt
        for _ in range(5):
            logits = mixtral.forward(params, naive, cfg)[0]
            nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(naive.dtype)
            naive = jnp.concatenate([naive, nxt], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))


# -- a family is its model module behind one adapter (PR 49) ------------------------


def _module_and_tiny(name: str):
    from test_engine_programs import tiny_family

    # qwen2 is llama's module under its own rules and shape reader
    return tiny_family({"qwen2": "llama"}.get(name, name))


@pytest.mark.parametrize("name", list(FAMILIES))
def test_every_family_is_its_module_behind_the_adapter(name):
    """The module interface ``dl/families.py`` states, held to each row of the
    table: what a row says of its module (``paged_table``, ``told_lengths``,
    a cache per layer kind, config.json as the source) is what the module's
    own signatures and functions say."""
    import inspect

    family = FAMILIES[name]
    assert family.name == name
    if name == "bert":  # an encoder: its one forward, nothing to decode
        assert family.generate is family.generate_ragged is family.decode_fns is None
        assert family.paged_decode_fns is family.layer_kind_decode_fns is None
        return
    module, cfg = _module_and_tiny(name)
    takes = inspect.signature(module.forward).parameters
    assert list(takes)[:3] == ["params", "tokens", "cfg"]
    assert {"kv_cache", "cache_offset", "mesh"} <= set(takes)
    assert list(inspect.signature(module.init_kv_cache).parameters)[:3] == [
        "cfg", "batch", "max_len"]
    assert None not in (family.generate, family.generate_ragged, family.decode_fns)
    assert (family.paged_decode_fns is not None) == ("paged_table" in takes)

    # config.json is the source where shapes cannot say, and a checkpoint
    # without one is refused in words that name the family
    assert (family.config_from_sidecar is not None) == (name in (
        "laguna", "minicpm_sala", "deepseek_v2", "nemotron_h", "mimo_v2"))
    if family.config_from_sidecar is not None:
        assert callable(module.config_from_hf)
        with pytest.raises(ValueError, match=f"{name} checkpoint.*config.json must lie beside"):
            family.infer_config({})

    # a block of prompt positions lands untold exactly where no layer keeps a state
    fwd, init = family.decode_fns(cfg)
    block = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    params = jax.eval_shape(lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
    lands = lambda **told: jax.eval_shape(  # noqa: E731
        lambda p, t: fwd(p, t, init(1, 32), 0, **told)[0], params, block)
    if {"valid_len", "live"} <= set(takes):
        with pytest.raises(ValueError, match="real lengths"):
            lands()
        assert lands(valid_len=None).shape == (1, 16, cfg.vocab_size)
    else:
        assert not {"valid_len", "live"} & set(takes)
        assert lands().shape == (1, 16, cfg.vocab_size)

    # a cache per layer kind: the state's leaves are the kinds', and what
    # /metrics names is the module's own
    kinds = family.layer_kind_decode_fns
    assert (kinds is not None) == hasattr(module, "init_layer_state") == hasattr(
        module, "cache_kinds") == hasattr(module, "published")
    if kinds is not None:
        fns = kinds(cfg)
        assert set(fns) == {"fwd", "init_state", "kinds", "counters", "gauges"}
        assert fns["kinds"] == module.cache_kinds(cfg)
        assert set(jax.eval_shape(lambda: fns["init_state"](2, 32))) == set(fns["kinds"])
        own = [v for k, v in vars(module).items() if k.endswith("_COUNTERS")]
        for leaf, (block, names) in fns["counters"].items():
            assert fns["kinds"][leaf] == "counter" and any(names is t for t in own)
            assert block in fns["gauges"]
        assert {k: fns[k] for k in ("counters", "gauges")} == module.published(cfg)


def test_importing_the_table_imports_no_model_module():
    """A pod's start pays for the family it serves, at its first call."""
    import subprocess
    import sys

    code = ("import sys, modelx_tpu.dl.families\n"
            "print([m for m in sys.modules if m.startswith('modelx_tpu.models')])\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=240, env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
