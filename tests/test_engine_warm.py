"""A --continuous-batch pod warms the engine it will run (ISSUE 26): the
engine is built by the load, its chunk program is fetched on the load's side
thread from abstract weights, and a pod that does not serve through the engine
keeps the forward warm-up. Over a real ServerSet on the CPU backend: counts,
names and tokens; never a time."""

import dataclasses
import functools
import importlib
import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.continuous import ContinuousBatcher
from modelx_tpu.dl.serve import ModelServer, ServerSet
from modelx_tpu.testing import faults
from modelx_tpu.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPT = np.array([[5, 9, 2, 7, 11]], np.int32)
SAMPLED = dict(temperature=0.8, top_k=8, top_p=0.9, seed=3)


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from modelx_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    params = {k: np.asarray(v) for k, v in
              llama.init_params(cfg, jax.random.PRNGKey(0)).items()}
    d = tmp_path_factory.mktemp("engine_warm")
    st.write_safetensors(str(d / "model.safetensors"), params)
    return str(d)


@pytest.fixture
def started(monkeypatch):
    """A ``startup`` clock that has begun, as in a process started through
    serve_main: ``note`` and ``count`` keep nothing before that."""
    fresh = trace.Startup()
    monkeypatch.setattr(trace, "startup", fresh)
    fresh.begin("backend_init")
    fresh.stage("load")
    return fresh


@pytest.fixture
def chunk_traces(monkeypatch):
    """How often jax traced the chunk implementation (its python body runs
    only while tracing)."""
    seen = []
    impl = ContinuousBatcher._chunk_impl

    @functools.wraps(impl)
    def counted(self, *args, **kwargs):
        seen.append(impl.__name__)
        return impl(self, *args, **kwargs)

    monkeypatch.setattr(ContinuousBatcher, "_chunk_impl", counted)
    return seen


# (ModelServer arguments, ServerSet arguments): the dense cache, the page
# pool, and a cache laid out over a dp x tp mesh of the CPU's virtual devices
LAYOUTS = {"dense": ({}, {}),
           "paged": ({}, {"kv_page_size": 16}),
           "mesh": ({"mesh_spec": "dp=2,tp=2"}, {"max_slots": 4})}


def new_set(model_dir, continuous=True, layout="dense", max_seq_len=96):
    server_args, set_args = LAYOUTS[layout]
    server = ModelServer(model_dir, **{"mesh_spec": "dp=1", **server_args},
                         dtype="float32", max_seq_len=max_seq_len, name="m")
    return server, ServerSet({"m": server}, continuous_batch=continuous,
                             **{"max_slots": 2, **set_args}, stream_chunk_size=4)


def close(sset):
    for cb in list(sset.cbatchers.values()):
        cb.close()


def warmed(startup, timeout=60.0) -> dict:
    """``startup``'s snapshot once the load's side thread has finished: ready
    does not wait for the chunk program, a dispatch does."""
    deadline = time.monotonic() + timeout
    while "engine_warm_programs" not in startup.snapshot() and time.monotonic() < deadline:
        time.sleep(0.01)
    return startup.snapshot()


class TestEngineBuiltByTheLoad:
    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_engine_and_chunk_program_exist_when_load_returns(
            self, model_dir, started, chunk_traces, layout):
        server, sset = new_set(model_dir, layout=layout)
        try:
            sset.load_all()
            cb = sset.cbatchers.get("m")
            assert cb is not None and sset.continuous_for(server) is cb
            assert len(cb._chunk_prog._fetched) >= 1  # reserved before the weights moved
            snap = warmed(started)
            assert snap["engine_warm_programs"] == 1
            assert snap["engine_warm_s"] > 0 and snap["engine_init_s"] > 0
            assert snap["engine_init_at_s"] <= snap["ready_s"]  # no longer the first request's
            assert not server._forward_aot  # the forward (1, 16): nobody would call it
            agg = trace.tracer().summary(prefix="serve.load/engine_warm")
            assert agg["serve.load/engine_warm"]["count"] >= 1
            assert len(chunk_traces) == 1
            out = cb.generate(PROMPT, max_new_tokens=6)
            assert out.shape == (1, PROMPT.shape[1] + 6)
            assert len(chunk_traces) == 1, "the first request traced the chunk program again"
            # the sampled variant with filters is another program: the jit compiles it
            cb.generate(PROMPT, max_new_tokens=6, **SAMPLED)
            assert len(chunk_traces) == 2
        finally:
            close(sset)

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("samp", [{}, SAMPLED, {"temperature": 0.7, "seed": 11}],
                             ids=["greedy", "filtered", "sampled"])
    def test_first_request_tokens_equal_a_lazily_built_engine(self, model_dir, layout, samp):
        warm_server, warm = new_set(model_dir, layout=layout)
        lazy_server, lazy = new_set(model_dir, layout=layout)
        try:
            warm.load_all()
            lazy_server.load()  # as a model added at run time: no engine yet
            assert "m" in warm.cbatchers and "m" not in lazy.cbatchers
            got = warm.continuous_for(warm_server).generate(PROMPT, max_new_tokens=10, **samp)
            want = lazy.continuous_for(lazy_server).generate(PROMPT, max_new_tokens=10, **samp)
            np.testing.assert_array_equal(got, want)
        finally:
            close(warm)
            close(lazy)

    def test_without_the_engine_the_forward_warm_up_runs(self, model_dir, started):
        server, sset = new_set(model_dir, continuous=False)
        sset.load_all()
        assert not sset.cbatchers and sset.continuous_for(server) is None
        assert ModelServer.WARMUP_TOKEN_SHAPES[0] in server._forward_aot
        snap = started.snapshot()
        assert snap["engine_warm_programs"] == 0
        assert "engine_warm_s" not in snap and "engine_init_s" not in snap
        assert sum(v for k, v in snap.items() if k in ("imports_s", "backend_init_s", "load_s")) \
            == pytest.approx(snap["ready_s"], abs=1e-3)

    @pytest.mark.parametrize("where", ["__init__", "allocate_device_state"])
    def test_out_of_memory_at_load_leaves_the_lazy_path(self, model_dir, started,
                                                        monkeypatch, where):
        """Building the engine (after the headers) or allocating its KV
        cache (behind the weights) raises once: the load goes on, ready is
        true, and the first request builds the engine lazily."""
        server, sset = new_set(model_dir)
        real, calls = getattr(ContinuousBatcher, where), []

        def once(self, *args, **kwargs):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating the KV cache")
            return real(self, *args, **kwargs)

        monkeypatch.setattr(ContinuousBatcher, where, once)
        try:
            sset.load_all()
            assert server.ready and sset.ready and server.load_error is None
            assert not sset.cbatchers and len(calls) == 1  # no demote-and-retry at load
            cb = sset.continuous_for(server)
            assert cb is not None and len(calls) == 2
            np.testing.assert_array_equal(cb.generate(PROMPT, max_new_tokens=4),
                                          server.generate(PROMPT, max_new_tokens=4))
        finally:
            close(sset)
        if where == "__init__":  # no engine to warm: the forward (1, 16) instead
            assert ModelServer.WARMUP_TOKEN_SHAPES[0] in server._forward_aot
            assert started.snapshot()["engine_warm_programs"] == 0

    def test_the_kv_cache_is_allocated_behind_the_weights(self, model_dir, monkeypatch):
        """The engine exists while the weights stream (so its chunk program
        can be fetched), its device state only once they are placed: where a
        lazily built engine always had it."""
        from modelx_tpu.dl import loader

        server, sset = new_set(model_dir)
        real, seen = loader.load_safetensors, []

        def spy(*args, **kwargs):
            cb = sset.cbatchers.get("m")
            seen.append((cb is not None, cb._cache, cb._tok, dict(cb._chunk_prog._fetched)))
            return real(*args, **kwargs)

        monkeypatch.setattr(loader, "load_safetensors", spy)
        try:
            sset.load_all()
            (built, cache, tok, reserved), = seen
            assert built and cache is None and tok is None and reserved
            cb = sset.cbatchers["m"]
            assert cb._cache is not None and cb._tok.shape == (cb.max_slots, 1)
        finally:
            close(sset)

    def test_a_failed_load_returns_the_engine_it_built(self, model_dir, monkeypatch):
        from modelx_tpu.dl import loader

        _, sset = new_set(model_dir)

        def broken(*args, **kwargs):
            raise OSError("shard unreadable")

        monkeypatch.setattr(loader, "load_safetensors", broken)
        with pytest.raises(RuntimeError, match="shard unreadable"):
            sset.load_all()
        assert not sset.cbatchers


class TestPersistentCacheKey:
    @pytest.fixture(autouse=True)
    def _restore(self, monkeypatch):
        from modelx_tpu.dl import serve as serve_mod

        monkeypatch.setattr(serve_mod, "_compile_cache_dir", "")
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("kept", ["programs", "jax_cache_only"])
    def test_a_node_that_kept_its_cache_misses_nothing(self, model_dir, tmp_path, layout, kept):
        """The program the load fetches is, to the node's executable store and
        to jax's persistent cache, the one a lazily built engine's first
        dispatch compiles: what an older pod left is found — in the store,
        before any tracing; where only jax's cache was kept, there, as
        before ISSUE 32 — and nothing is compiled for a new key."""
        import shutil

        from modelx_tpu.dl import serve as serve_mod

        serve_mod.enable_compile_cache(str(tmp_path / "node"))
        # programs of their own: an earlier test's side thread, still compiling,
        # would file the same module in this directory, and on the CPU the store
        # takes no executable that jax's cache served
        lazy_server, lazy = new_set(model_dir, layout=layout, max_seq_len=80)
        warm_server, warm = new_set(model_dir, layout=layout, max_seq_len=80)
        try:
            lazy_server.load()
            want = lazy.continuous_for(lazy_server).generate(PROMPT, max_new_tokens=6)
            stored = len(os.listdir(tmp_path / "node" / "programs"))
            assert stored >= 2  # the admit and the chunk (over a mesh, two of it)
            if kept == "jax_cache_only":
                shutil.rmtree(tmp_path / "node" / "programs")
            before = serve_mod.compile_cache_stats()
            warm.load_all()
            got = warm.continuous_for(warm_server).generate(PROMPT, max_new_tokens=6)
            after = serve_mod.compile_cache_stats()
            np.testing.assert_array_equal(got, want)
            assert after["misses"] == before["misses"]
            if kept == "programs":
                assert after["store_hits"] - before["store_hits"] == stored
                assert after["store_misses"] == before["store_misses"]
            else:
                assert after["hits"] - before["hits"] >= stored
                assert after["store_misses"] - before["store_misses"] == stored
                # XLA:CPU cannot serialize an executable it deserialized
                assert not os.path.isdir(tmp_path / "node" / "programs")
        finally:
            close(lazy)
            close(warm)


class TestChunkWarmer:
    @pytest.fixture
    def engine(self, model_dir):
        server = ModelServer(model_dir, mesh_spec="dp=1", dtype="float32", max_seq_len=96)
        server.load()
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        yield cb
        cb.close()

    def test_a_request_waits_for_the_side_thread_and_compiles_nothing_twice(
            self, chunk_traces, engine):
        fetch = engine.chunk_warmer(engine.server._param_sds)
        result = {}
        t = threading.Thread(
            target=lambda: result.update(out=engine.generate(PROMPT, max_new_tokens=6)),
            daemon=True)
        t.start()
        t.join(2.0)
        assert t.is_alive(), "the dispatch did not wait for the reserved program"
        assert chunk_traces == []
        assert fetch() == 1
        t.join(60)
        assert not t.is_alive() and result["out"].shape == (1, PROMPT.shape[1] + 6)
        assert len(chunk_traces) == 1
        np.testing.assert_array_equal(
            result["out"], engine.server.generate(PROMPT, max_new_tokens=6))

    def test_a_failed_fetch_falls_back_to_the_jit(self, chunk_traces, engine):
        fetch = engine.chunk_warmer(engine.server._param_sds)
        jit = engine._chunk_prog.jit

        class Refusing:
            def lower(self, *args, **kwargs):
                raise RuntimeError("no such program")

            def __call__(self, *args, **kwargs):
                return jit(*args, **kwargs)

        engine._chunk_prog.jit = Refusing()
        assert fetch() == 0
        out = engine.generate(PROMPT, max_new_tokens=6)
        np.testing.assert_array_equal(out, engine.server.generate(PROMPT, max_new_tokens=6))
        assert len(chunk_traces) == 1

    def test_params_the_abstract_ones_did_not_describe_fall_back(self, chunk_traces, engine):
        sds = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
               for k, v in engine.server._param_sds.items()}
        assert engine.chunk_warmer(sds)() == 1
        out = engine.generate(PROMPT, max_new_tokens=6)
        np.testing.assert_array_equal(out, engine.server.generate(PROMPT, max_new_tokens=6))
        assert None in engine._chunk_prog._memo.values() and len(chunk_traces) == 2

    def test_fault_injection_still_intercepts_dispatches_after_a_warm_start(
            self, model_dir, started):
        server, sset = new_set(model_dir)
        try:
            sset.load_all()
            cb = sset.cbatchers["m"]
            plan = faults.FaultPlan()
            plan.add("engine.dispatch", errors_at=[0], error=RuntimeError("injected"))
            cb._chunk = faults.wrap_dispatch(cb._chunk, plan)
            with pytest.raises(Exception, match="injected|engine"):
                cb.generate(PROMPT, max_new_tokens=9)
            deadline = time.monotonic() + 30
            while cb.snapshot()["engine_restarts"] < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert cb.snapshot()["engine_restarts"] >= 1
            want = server.generate(PROMPT, max_new_tokens=9)
            np.testing.assert_array_equal(cb.generate(PROMPT, max_new_tokens=9), want)
            assert plan.count("engine.dispatch") >= 3  # every dispatch went through the seam
        finally:
            close(sset)


class TestStartupCount:
    def test_count_adds_up_and_keeps_nothing_before_begin(self):
        st_ = trace.Startup()
        st_.count("engine_warm_programs", 1)
        assert st_.snapshot() == {}
        st_.begin("load")
        st_.count("engine_warm_programs", 0)
        assert st_.snapshot()["engine_warm_programs"] == 0
        st_.count("engine_warm_programs", 2)
        assert st_.snapshot()["engine_warm_programs"] == 2


class TestLayerMetricFiles:
    """The two per-layer metrics this PR adds are data for a reader the
    benchmark already had: on a pod's /metrics dumps they read the engine's
    warm-up and the first request's programs, and on a parent's they find
    nothing and say nothing."""

    @staticmethod
    def read(name, sources):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
        return reader.read(sources, spec)

    @pytest.mark.parametrize("name, startup, want", [
        ("engine.warm_s", {"engine_warm_s": 7.5, "engine_warm_programs": 1}, 7.5),
        ("engine.warm_s", {"engine_init_s": 0.1}, None),  # the parent has no such counter
        ("cache.first_request_programs", {}, 5),
    ])
    def test_reads_the_pods_dumps(self, name, startup, want):
        sources = {"trace_span": {
            "metrics_before": {"compile_cache": {"programs": 3}, "startup": startup},
            "metrics_after": {"compile_cache": {"programs": 8}, "startup": startup}}}
        assert self.read(name, sources) == want

    @pytest.mark.parametrize("name", ["engine.warm_s", "cache.first_request_programs"])
    def test_an_untraced_run_reads_nothing(self, name):
        assert self.read(name, {}) is None

    def test_benchmark_json_lists_both_for_the_deploy_cell(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
        for name, layer in (("engine.warm_s", "Engine"),
                            ("cache.first_request_programs", "Compile caches")):
            m = per_layer[name]
            assert (m["layer"], m["moves"], m["better"]) == (layer, "pod_listen_ttft_s", "lower")
            assert "phi3-mini-4k.deploy" in m["workloads"]
