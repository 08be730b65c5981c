"""LoRA adapter merge (dl/lora.py): PEFT-style adapters fold into base
weights at load, with the merged model serving exactly W + (alpha/r)BA."""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.families import FAMILIES
from modelx_tpu.dl.lora import merge_adapter, parse_adapter_dir


def _write_adapter(d, pairs: dict, alpha=None, r=None, prefix="base_model.model."):
    tensors = {}
    for target, (a, b) in pairs.items():
        base = target.removesuffix(".weight")
        tensors[f"{prefix}{base}.lora_A.weight"] = a
        tensors[f"{prefix}{base}.lora_B.weight"] = b
    d.mkdir(parents=True, exist_ok=True)
    st.write_safetensors(str(d / "adapter_model.safetensors"), tensors)
    if alpha is not None:
        (d / "adapter_config.json").write_text(json.dumps({"lora_alpha": alpha, "r": r}))


class TestParse:
    def test_pairs_and_scale(self, tmp_path):
        rng = np.random.RandomState(0)
        a = rng.rand(4, 16).astype(np.float32)
        b = rng.rand(8, 4).astype(np.float32)
        _write_adapter(tmp_path / "ad", {"model.q.weight": (a, b)}, alpha=8, r=4)
        scale, pairs = parse_adapter_dir(str(tmp_path / "ad"))
        assert scale == 2.0
        np.testing.assert_array_equal(pairs["model.q.weight"]["A"], a)
        np.testing.assert_array_equal(pairs["model.q.weight"]["B"], b)

    def test_rslora_scale(self, tmp_path):
        """use_rslora scales by alpha/sqrt(r), not alpha/r. (Pair rank must
        match config r — mismatches are refused, TestPerModuleScaleRefusal.)"""
        a = np.ones((64, 16), np.float32)
        b = np.ones((8, 64), np.float32)
        _write_adapter(tmp_path / "ad", {"q.weight": (a, b)})
        (tmp_path / "ad" / "adapter_config.json").write_text(
            json.dumps({"lora_alpha": 16, "r": 64, "use_rslora": True})
        )
        scale, _ = parse_adapter_dir(str(tmp_path / "ad"))
        assert scale == 16 / 8.0  # alpha / sqrt(64)

    def test_unrecognized_tensors_are_an_error(self, tmp_path):
        """modules_to_save weights must refuse to load, not silently drop."""
        d = tmp_path / "ad"
        d.mkdir()
        st.write_safetensors(
            str(d / "adapter_model.safetensors"),
            {
                "base_model.model.q.lora_A.weight": np.ones((2, 4), np.float32),
                "base_model.model.q.lora_B.weight": np.ones((3, 2), np.float32),
                "base_model.model.lm_head.modules_to_save.weight": np.ones((3,), np.float32),
            },
        )
        with pytest.raises(ValueError, match="modules_to_save"):
            parse_adapter_dir(str(d))

    def test_default_scale_is_one(self, tmp_path):
        a = np.ones((2, 4), np.float32)
        b = np.ones((3, 2), np.float32)
        _write_adapter(tmp_path / "ad", {"w.weight": (a, b)})
        scale, _ = parse_adapter_dir(str(tmp_path / "ad"))
        assert scale == 1.0

    def test_missing_pair_is_error(self, tmp_path):
        d = tmp_path / "ad"
        d.mkdir()
        st.write_safetensors(
            str(d / "adapter_model.safetensors"),
            {"base_model.model.w.lora_A.weight": np.ones((2, 4), np.float32)},
        )
        with pytest.raises(ValueError, match="missing A or B"):
            parse_adapter_dir(str(d))

    def test_empty_dir_is_error(self, tmp_path):
        (tmp_path / "ad").mkdir()
        with pytest.raises(ValueError):
            parse_adapter_dir(str(tmp_path / "ad"))


class TestMerge:
    def test_merge_math(self, tmp_path):
        rng = np.random.RandomState(1)
        w = rng.rand(8, 16).astype(np.float32)
        a = rng.rand(4, 16).astype(np.float32)
        b = rng.rand(8, 4).astype(np.float32)
        _write_adapter(tmp_path / "ad", {"model.q.weight": (a, b)}, alpha=8, r=4)
        params = {"model.q.weight": jnp.asarray(w)}
        merged = merge_adapter(params, str(tmp_path / "ad"))
        np.testing.assert_allclose(
            np.asarray(merged["model.q.weight"]), w + 2.0 * (b @ a), rtol=1e-5
        )

    def test_sharded_base_keeps_sharding(self, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec
        from modelx_tpu.parallel.mesh import make_mesh

        rng = np.random.RandomState(2)
        w = rng.rand(8, 16).astype(np.float32)
        a = rng.rand(2, 16).astype(np.float32)
        b = rng.rand(8, 2).astype(np.float32)
        _write_adapter(tmp_path / "ad", {"q.weight": (a, b)})
        mesh = make_mesh("tp=8")
        sharded = jax.device_put(w, NamedSharding(mesh, PartitionSpec("tp", None)))
        merged = merge_adapter({"q.weight": sharded}, str(tmp_path / "ad"))
        out = merged["q.weight"]
        np.testing.assert_allclose(np.asarray(out), w + b @ a, rtol=1e-5)
        assert out.sharding.spec == ("tp", None)

    def test_shape_mismatch_and_missing_target(self, tmp_path):
        a = np.ones((2, 4), np.float32)
        b = np.ones((3, 2), np.float32)
        _write_adapter(tmp_path / "ad", {"q.weight": (a, b)})
        with pytest.raises(ValueError, match="not in base model"):
            merge_adapter({"other.weight": jnp.zeros((3, 4))}, str(tmp_path / "ad"))
        with pytest.raises(ValueError, match="do not match"):
            merge_adapter({"q.weight": jnp.zeros((9, 9))}, str(tmp_path / "ad"))


class TestServeIntegration:
    def test_adapter_changes_served_model_exactly(self, tmp_path):
        """End-to-end: base + adapter served == manual merged-forward."""
        from modelx_tpu.dl.serve import ModelServer
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32, rope_theta=500000.0)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        base_dir = tmp_path / "base"
        base_dir.mkdir()
        st.write_safetensors(
            str(base_dir / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        rng = np.random.RandomState(3)
        target = "model.layers.0.self_attn.q_proj.weight"
        out_f, in_f = params[target].shape
        a = (rng.rand(2, in_f).astype(np.float32) - 0.5) * 0.2
        b = (rng.rand(out_f, 2).astype(np.float32) - 0.5) * 0.2
        _write_adapter(tmp_path / "ad", {target: (a, b)}, alpha=4, r=2)

        server = ModelServer(str(base_dir), mesh_spec="dp=1", dtype="float32",
                             name="l", lora_dir=str(tmp_path / "ad"))
        server.load()
        prompt = np.asarray([[1, 2, 3]], np.int32)
        got = server.generate(prompt, max_new_tokens=4)

        merged = dict(params)
        merged[target] = params[target] + 2.0 * jnp.asarray(b @ a)
        want = FAMILIES["llama"].generate(
            merged, jnp.asarray(prompt), cfg, max_new_tokens=4
        )
        np.testing.assert_array_equal(got, np.asarray(want))

    def test_quantized_merge_rejected(self, tmp_path):
        from modelx_tpu.ops.quant import QTensor

        a = np.ones((2, 4), np.float32)
        b = np.ones((3, 2), np.float32)
        _write_adapter(tmp_path / "ad", {"q.weight": (a, b)})
        qt = QTensor(jnp.zeros((3, 4), jnp.int8), jnp.ones((3,), jnp.float32))
        with pytest.raises(ValueError, match="quantize"):
            merge_adapter({"q.weight": qt}, str(tmp_path / "ad"))


class TestPerModuleScaleRefusal:
    """Adapters with per-module ranks/alphas must refuse to
    merge with a single global scale, not silently mis-scale targets."""

    def test_rank_pattern_rejected(self, tmp_path):
        a = np.ones((4, 16), np.float32)
        b = np.ones((8, 4), np.float32)
        _write_adapter(tmp_path / "ad", {"q.weight": (a, b)})
        (tmp_path / "ad" / "adapter_config.json").write_text(
            json.dumps({"lora_alpha": 8, "r": 4, "rank_pattern": {"q": 8}})
        )
        with pytest.raises(ValueError, match="rank_pattern"):
            parse_adapter_dir(str(tmp_path / "ad"))

    def test_alpha_pattern_rejected(self, tmp_path):
        a = np.ones((4, 16), np.float32)
        b = np.ones((8, 4), np.float32)
        _write_adapter(tmp_path / "ad", {"q.weight": (a, b)})
        (tmp_path / "ad" / "adapter_config.json").write_text(
            json.dumps({"lora_alpha": 8, "r": 4, "alpha_pattern": {"q": 32}})
        )
        with pytest.raises(ValueError, match="alpha_pattern"):
            parse_adapter_dir(str(tmp_path / "ad"))

    def test_pair_rank_mismatch_rejected(self, tmp_path):
        """Pairs whose actual rank differs from config r merge with the
        wrong scale — refuse."""
        a4 = np.ones((4, 16), np.float32)
        b4 = np.ones((8, 4), np.float32)
        a8 = np.ones((8, 16), np.float32)
        b8 = np.ones((8, 8), np.float32)
        _write_adapter(tmp_path / "ad", {"q.weight": (a4, b4), "k.weight": (a8, b8)},
                       alpha=8, r=4)
        with pytest.raises(ValueError, match="ranks differ"):
            parse_adapter_dir(str(tmp_path / "ad"))

    def test_empty_patterns_fine(self, tmp_path):
        a = np.ones((4, 16), np.float32)
        b = np.ones((8, 4), np.float32)
        _write_adapter(tmp_path / "ad", {"q.weight": (a, b)})
        (tmp_path / "ad" / "adapter_config.json").write_text(
            json.dumps({"lora_alpha": 8, "r": 4, "rank_pattern": {}, "alpha_pattern": {}})
        )
        scale, _ = parse_adapter_dir(str(tmp_path / "ad"))
        assert scale == 2.0
