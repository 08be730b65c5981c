"""Serialized-executable cache (dl/aot_cache): warm starts skip tracing.

The persistent XLA cache covers the compile; these cover the export blob's
correctness (same results), keying (rules/quantize changes miss), and the
fallback when a blob is stale/corrupt."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import aot_cache
from modelx_tpu.dl import families as fam
from modelx_tpu.dl import safetensors as st
from modelx_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def llama_ckpt(tmp_path_factory):
    import dataclasses

    from modelx_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    d = tmp_path_factory.mktemp("aot")
    path = str(d / "model.safetensors")
    st.write_safetensors(path, {k: np.asarray(v) for k, v in params.items()})
    return path, params


def _counting_export(monkeypatch):
    calls = {"export": 0, "deserialize": 0}
    real_export = jax.export.export
    real_deser = jax.export.deserialize

    def counting_export(*a, **kw):
        calls["export"] += 1
        return real_export(*a, **kw)

    def counting_deser(*a, **kw):
        calls["deserialize"] += 1
        return real_deser(*a, **kw)

    monkeypatch.setattr(jax.export, "export", counting_export)
    monkeypatch.setattr(jax.export, "deserialize", counting_deser)
    return calls


class TestAOTCache:
    def test_cold_then_warm_same_result(self, llama_ckpt, tmp_path, monkeypatch):
        path, params = llama_ckpt
        calls = _counting_export(monkeypatch)
        infos, _ = st.read_header_from_file(path)
        family = fam.detect(list(infos))
        mesh = make_mesh("dp=1")
        cfg = family.infer_config(fam.abstract_params(infos))
        sds = fam.abstract_params(infos, family.rules, mesh)
        cache = str(tmp_path / "cache")
        tokens = jnp.asarray(np.array([[1, 2, 3, 4]], np.int32))

        cold = fam.precompile_forward(
            family, cfg, sds, (1, 4), mesh=mesh, mode="argmax_last", cache_dir=cache
        )
        # the cold path compiles the serialize->deserialize ROUNDTRIP of
        # its export (one deserialize), so the persistent-XLA-cache entry
        # lands under the key warm starts compute (dl/program_store.py)
        assert calls["export"] == 1 and calls["deserialize"] == 1
        blobs = [f for f in os.listdir(cache) if f.startswith("aot-")]
        assert len(blobs) == 1

        warm = fam.precompile_forward(
            family, cfg, sds, (1, 4), mesh=mesh, mode="argmax_last", cache_dir=cache
        )
        # warm start read the blob instead of retracing
        assert calls["export"] == 1 and calls["deserialize"] == 2

        p = {k: jnp.asarray(np.asarray(v)) for k, v in params.items()}
        np.testing.assert_array_equal(np.asarray(cold(p, tokens)), np.asarray(warm(p, tokens)))
        # and both agree with the uncached path
        plain = fam.precompile_forward(family, cfg, sds, (1, 4), mesh=mesh, mode="argmax_last")
        np.testing.assert_array_equal(np.asarray(plain(p, tokens)), np.asarray(warm(p, tokens)))

    def test_key_varies_with_program_shape(self, llama_ckpt):
        path, _ = llama_ckpt
        infos, _ = st.read_header_from_file(path)
        family = fam.detect(list(infos))
        mesh = make_mesh("dp=1")
        sds = fam.abstract_params(infos, family.rules, mesh)
        base = (family.name, "cfg", "argmax_last", (1, 4),
                tuple(mesh.shape.items()), aot_cache.describe_sds(sds))
        k0 = aot_cache.cache_key(*base)
        assert aot_cache.cache_key(family.name, "cfg", "argmax_last", (1, 8),
                                   tuple(mesh.shape.items()),
                                   aot_cache.describe_sds(sds)) != k0
        sds_q = fam.abstract_params(infos, family.rules, mesh, quantize="int8")
        assert aot_cache.cache_key(family.name, "cfg", "argmax_last", (1, 4),
                                   tuple(mesh.shape.items()),
                                   aot_cache.describe_sds(sds_q)) != k0

    def test_corrupt_blob_falls_back(self, llama_ckpt, tmp_path):
        path, params = llama_ckpt
        infos, _ = st.read_header_from_file(path)
        family = fam.detect(list(infos))
        mesh = make_mesh("dp=1")
        cfg = family.infer_config(fam.abstract_params(infos))
        sds = fam.abstract_params(infos, family.rules, mesh)
        cache = str(tmp_path / "cache")
        fam.precompile_forward(
            family, cfg, sds, (1, 4), mesh=mesh, mode="argmax_last", cache_dir=cache
        )
        (blob,) = [f for f in os.listdir(cache) if f.startswith("aot-")]
        with open(os.path.join(cache, blob), "wb") as f:
            f.write(b"garbage")
        compiled = fam.precompile_forward(
            family, cfg, sds, (1, 4), mesh=mesh, mode="argmax_last", cache_dir=cache
        )
        p = {k: jnp.asarray(np.asarray(v)) for k, v in params.items()}
        out = compiled(p, jnp.asarray(np.array([[1, 2, 3, 4]], np.int32)))
        assert np.asarray(out).shape == (1,)
        # the corrupt blob was replaced by a fresh one
        with open(os.path.join(cache, blob), "rb") as f:
            assert f.read() != b"garbage"

    def test_quantized_program_serializes(self, llama_ckpt, tmp_path):
        """QTensor must be registered for jax.export serialization: an int8
        warmup that silently never persists would make every quantized pod
        start cold (caught live — the fallback hides the failure)."""
        path, _ = llama_ckpt
        infos, _ = st.read_header_from_file(path)
        family = fam.detect(list(infos))
        mesh = make_mesh("dp=1")
        cfg = family.infer_config(fam.abstract_params(infos))
        sds = fam.abstract_params(infos, family.rules, mesh, quantize="int8")
        cache = str(tmp_path / "qcache")
        fam.precompile_forward(
            family, cfg, sds, (1, 4), mesh=mesh, mode="argmax_last", cache_dir=cache
        )
        blobs = [f for f in os.listdir(cache) if f.startswith("aot-") and f.endswith(".bin")]
        assert len(blobs) == 1, os.listdir(cache)

    def test_quantized_abstract_params_mirror_loader(self, llama_ckpt):
        """abstract_params(quantize=int8) must produce exactly the pytree
        structure the loader delivers, or the AOT program can't be called."""
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors

        path, _ = llama_ckpt
        infos, _ = st.read_header_from_file(path)
        family = fam.detect(list(infos))
        mesh = make_mesh("dp=1")
        sds = fam.abstract_params(infos, family.rules, mesh, quantize="int8")
        src = LocalFileSource(path)
        try:
            arrays, _stats = load_safetensors(src, mesh, family.rules, quantize="int8")
        finally:
            src.close()
        s_struct = jax.tree_util.tree_structure(sds)
        a_struct = jax.tree_util.tree_structure(arrays)
        assert s_struct == a_struct
        for (pth, s), (_pth2, a) in zip(
            jax.tree_util.tree_flatten_with_path(sds)[0],
            jax.tree_util.tree_flatten_with_path(arrays)[0],
        ):
            assert tuple(s.shape) == tuple(a.shape), pth
            assert s.dtype == a.dtype, pth


class TestCompileCachePlacement:
    """Where the persistent XLA cache lives (dl/serve.enable_compile_cache):
    JAX_COMPILATION_CACHE_DIR when the environment sets it — and then no
    other directory is set in code — else one fixed path inside the
    checkout. A path that moves never hits."""

    @pytest.fixture(autouse=True)
    def _isolate(self, monkeypatch):
        from modelx_tpu.dl import serve

        monkeypatch.setattr(serve, "_compile_cache_dir", "")
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)

    def test_env_var_wins_and_nothing_else_is_set(self, tmp_path, monkeypatch):
        from modelx_tpu.dl import serve

        env_dir = str(tmp_path / "from-env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        serve.enable_compile_cache()
        assert serve.compile_cache_dir() == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert serve.compile_cache_stats()["dir"] == env_dir

    def test_unset_resolves_to_the_fixed_in_checkout_path(self, monkeypatch):
        from modelx_tpu.dl import serve

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        serve.enable_compile_cache()
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert serve.compile_cache_dir() == os.path.join(here, ".cache", "xla")
        assert serve.compile_cache_dir() == serve.DEFAULT_COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == serve.DEFAULT_COMPILE_CACHE_DIR

    def test_cold_leg_is_a_fixed_name_cleared_not_renamed(self, monkeypatch):
        from modelx_tpu.dl import serve

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent/kept-cache")
        first = serve.cold_cache_dir("unit-test-leg")
        with open(os.path.join(first, "stale-entry"), "w") as f:
            f.write("x")
        second = serve.cold_cache_dir("unit-test-leg")
        assert first == second == os.path.join(
            serve.DEFAULT_COMPILE_CACHE_DIR, "unit-test-leg")
        assert os.listdir(second) == []
        os.rmdir(second)

    def test_hits_and_misses_are_counted_from_jax_events(self, tmp_path):
        """A restart that found its programs says so in numbers: same
        program, fresh in-memory caches, second compile is a persistent
        hit. The directory switch takes effect in a process that has
        already compiled (jax opens its cache once otherwise)."""
        from modelx_tpu.dl import serve

        serve.enable_compile_cache(str(tmp_path / "leg"))
        f = jax.jit(lambda x: jnp.sin(x) * 3 + 1)
        x = jnp.arange(8, dtype=jnp.float32)
        before = serve.compile_cache_stats()
        f(x).block_until_ready()
        mid = serve.compile_cache_stats()
        assert mid["misses"] > before["misses"]
        assert any(n.endswith("-cache") for n in os.listdir(tmp_path / "leg"))
        jax.clear_caches()
        f(x).block_until_ready()
        after = serve.compile_cache_stats()
        assert after["hits"] > mid["hits"]
        assert after["requests"] - before["requests"] >= 2
