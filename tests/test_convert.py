"""Checkpoint converters (client/convert.py): orbax and torch state_dicts
to pushable safetensors dirs, round-tripped through our own reader."""

import numpy as np
import pytest

from modelx_tpu.client.convert import _apply_renames, _flatten
from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.families import FAMILIES


class TestFlatten:
    def test_nested_dicts_and_lists(self):
        tree = {"a": {"b": np.ones(2)}, "c": [np.zeros(1), {"d": np.full(3, 7)}]}
        flat = _flatten(tree)
        assert set(flat) == {"a.b", "c.0", "c.1.d"}
        np.testing.assert_array_equal(flat["c.1.d"], np.full(3, 7))

    def test_renames_prefix_only(self):
        flat = {"params.w": np.ones(1), "other.w": np.zeros(1)}
        out = _apply_renames(flat, ["params.=model."])
        assert set(out) == {"model.w", "other.w"}
        out = _apply_renames(flat, ["params.="])  # strip
        assert set(out) == {"w", "other.w"}
        with pytest.raises(ValueError):
            _apply_renames(flat, ["nope"])

    def test_rename_collision_is_an_error(self):
        """Two names mapping onto one key would silently drop a weight."""
        flat = {"module.w": np.ones(1), "w": np.zeros(1)}
        with pytest.raises(ValueError, match="maps two tensors"):
            _apply_renames(flat, ["module.="])

    def test_flatten_collision_is_an_error(self):
        tree = {"a.b": np.ones(1), "a": {"b": np.zeros(1)}}
        with pytest.raises(ValueError, match="collide"):
            _flatten(tree)

    def test_orbax_metadata_leaves_skipped(self, tmp_path):
        """String/format metadata leaves must not crash or pollute the
        artifact; numeric scalars remain legitimate 0-d tensors."""
        ocp = pytest.importorskip("orbax.checkpoint")
        tree = {"params": {"w": np.ones(2, np.float32)}, "format": "v2", "step": 7}
        src = tmp_path / "ck"
        with ocp.PyTreeCheckpointer() as ckptr:
            ckptr.save(str(src), tree)

        from modelx_tpu.client.convert import convert_orbax

        dst = tmp_path / "out"
        out = convert_orbax(str(src), str(dst))
        with open(dst / "model.safetensors", "rb") as f:
            infos, _ = st.read_header(f)
        assert "format" not in infos
        assert set(infos) == {"params.w", "step"}


class TestOrbax:
    def test_roundtrip(self, tmp_path):
        ocp = pytest.importorskip("orbax.checkpoint")
        tree = {
            "params": {
                "embed": np.arange(12, dtype=np.float32).reshape(3, 4),
                "layers": [{"w": np.ones((2, 2), np.float32)}],
            }
        }
        src = tmp_path / "orbax-ckpt"
        with ocp.PyTreeCheckpointer() as ckptr:
            ckptr.save(str(src), tree)

        from modelx_tpu.client.convert import convert_orbax

        dst = tmp_path / "out"
        out = convert_orbax(str(src), str(dst), ["params.=model."])
        assert out["tensors"] == 2
        with open(dst / "model.safetensors", "rb") as f:
            infos, off = st.read_header(f)
            assert set(infos) == {"model.embed", "model.layers.0.w"}
            f.seek(off + infos["model.embed"].start)
            got = np.frombuffer(
                f.read(infos["model.embed"].nbytes),
                infos["model.embed"].np_dtype(),
            ).reshape(infos["model.embed"].shape)
        np.testing.assert_array_equal(got, tree["params"]["embed"])


class TestTorch:
    def test_roundtrip_incl_bf16(self, tmp_path):
        torch = pytest.importorskip("torch")
        sd = {
            "model.w1": torch.arange(6, dtype=torch.float32).reshape(2, 3),
            "model.w2": torch.ones(4, dtype=torch.bfloat16) * 1.5,
            "step": 7,  # non-tensor metadata must be skipped
        }
        src = tmp_path / "ckpt.bin"
        torch.save(sd, str(src))

        from modelx_tpu.client.convert import convert_torch

        dst = tmp_path / "out"
        out = convert_torch(str(src), str(dst))
        assert out["tensors"] == 2
        with open(dst / "model.safetensors", "rb") as f:
            infos, off = st.read_header(f)
            assert set(infos) == {"model.w1", "model.w2"}
            assert infos["model.w2"].dtype == "BF16"
            f.seek(off + infos["model.w2"].start)
            import ml_dtypes

            got = np.frombuffer(f.read(infos["model.w2"].nbytes), ml_dtypes.bfloat16)
        np.testing.assert_array_equal(got.astype(np.float32), np.full(4, 1.5, np.float32))

    def test_converted_checkpoint_serves(self, tmp_path):
        """End-to-end: a torch llama-shaped state_dict converts, loads, and
        serves through the family machinery."""
        torch = pytest.importorskip("torch")
        import dataclasses

        import jax
        import jax.numpy as jnp

        from modelx_tpu.client.convert import convert_torch
        from modelx_tpu.dl.serve import ModelServer
        from modelx_tpu.models import llama

        # rope_theta matches what config inference assumes (it is not
        # derivable from the weights), so served output is comparable
        cfg = dataclasses.replace(
            llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32,
            rope_theta=500000.0,
        )
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        sd = {k: torch.tensor(np.asarray(v)) for k, v in params.items()}
        src = tmp_path / "llama.bin"
        torch.save(sd, str(src))
        dst = tmp_path / "model"
        convert_torch(str(src), str(dst))

        server = ModelServer(str(dst), mesh_spec="dp=1", dtype="float32", name="t")
        server.load()
        out = server.generate(np.asarray([[1, 2, 3]], np.int32), max_new_tokens=4)
        want = FAMILIES["llama"].generate(
            params, jnp.asarray([[1, 2, 3]], jnp.int32), cfg, max_new_tokens=4
        )
        np.testing.assert_array_equal(out, np.asarray(want))
