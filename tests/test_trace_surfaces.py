"""What the one span system puts on a pod's surfaces (ISSUE 25): the
aggregate behind ``/v1/trace``, the profiler bridge, the engine's phase
clock, the ``startup`` and ``compile_cache`` blocks of ``/metrics``, the
loader's split and the device's peak — over a real ServerSet on the CPU
backend. A CPU run checks counts, names and that sums close; never a time."""

import dataclasses
import glob
import os
import threading

import numpy as np
import pytest
import requests

import jax
import jax.numpy as jnp

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.continuous import _PHASES, ContinuousBatcher
from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
from modelx_tpu.registry.server import free_port
from modelx_tpu.utils import devmem, promexp, trace


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    """A tiny llama in TWO safetensors files, so a load sums two LoadStats."""
    from modelx_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    params = {k: np.asarray(v) for k, v in
              llama.init_params(cfg, jax.random.PRNGKey(0)).items()}
    d = tmp_path_factory.mktemp("trace_surfaces")
    names = sorted(params)
    half = len(names) // 2
    st.write_safetensors(str(d / "model-00001-of-00002.safetensors"),
                         {k: params[k] for k in names[:half]})
    st.write_safetensors(str(d / "model-00002-of-00002.safetensors"),
                         {k: params[k] for k in names[half:]})
    return str(d)


@pytest.fixture(scope="module")
def server(model_dir):
    srv = ModelServer(model_dir, mesh_spec="dp=1", dtype="float32", max_seq_len=96)
    srv.load()
    return srv


@pytest.fixture(scope="module")
def front(server, tmp_path_factory):
    d = tmp_path_factory.mktemp("trace_front")
    sset = ServerSet({"m": server}, continuous_batch=True, max_slots=2,
                     stream_chunk_size=4, admin_tokens=("sekrit",),
                     trace_dir=str(d / "traces"))
    port = free_port()
    httpd = serve(sset, listen=f"127.0.0.1:{port}")
    yield sset, f"http://127.0.0.1:{port}"
    for cb in list(sset.cbatchers.values()):
        cb.close()
    httpd.shutdown()


def generate(base: str, rid: str = "", n: int = 6) -> requests.Response:
    r = requests.post(base + "/v1/m/generate",
                      json={"tokens": [[5, 9, 2]], "max_new_tokens": n, "stream": True},
                      headers={"X-ModelX-Request-Id": rid} if rid else {})
    assert r.status_code == 200 and r.content
    return r


class TestTraceEndpoint:
    def test_summary_keeps_the_load_after_ten_thousand_engine_spans(self, front):
        _, base = front
        for i in range(10_000):
            trace.record("continuous.admit", float(i), 0.001)
        agg = requests.get(base + "/v1/trace").json()
        assert agg["serve.load"]["count"] >= 1  # closed once, before any traffic
        assert {"serve.load/headers", "serve.load/shards"} <= set(agg)
        assert agg["continuous.admit"]["count"] >= 10_000
        assert set(agg["serve.load"]) == {"count", "total_s", "max_s", "self_s"}
        only = requests.get(base + "/v1/trace?prefix=serve.load").json()
        assert only and all(p.startswith("serve.load") for p in only)

    def test_request_id_still_slices_one_request(self, front):
        _, base = front
        generate(base, "rid-slice-1")
        generate(base, "rid-slice-2")
        mine = requests.get(base + "/v1/trace?request_id=rid-slice-1").json()
        assert mine["serve.request"]["count"] == 1
        both = requests.get(base + "/v1/trace?prefix=serve.request").json()
        assert both["serve.request"]["count"] >= 2
        assert requests.get(base + "/v1/trace?request_id=rid-nope").json() == {}
        # the engine's phases are in the aggregate, never in a request's slice
        assert not any(p.startswith("continuous.boundary") for p in mine)
        agg = requests.get(base + "/v1/trace?prefix=continuous.boundary").json()
        assert agg["continuous.boundary/chunk_dispatch"]["count"] >= 1


class TestProfilerBridge:
    def test_a_capture_holds_the_boundary_and_its_phases_and_no_envelope(self, front):
        """Inside a capture the engine's step and phases, and spans under a
        request, are events of the same .xplane.pb; ``serve.request`` is not."""
        sset, base = front
        generate(base)  # the engine exists and its programs are compiled
        done = threading.Event()

        def traffic():
            while not done.is_set():
                generate(base, n=12)

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        try:
            r = requests.post(base + "/v1/profile", json={"seconds": 1.0}, timeout=120)
        finally:
            done.set()
            t.join(60)
        assert r.status_code == 200
        assert trace._annotate is None  # the capture is over: no jax call per span
        found = glob.glob(os.path.join(sset.trace_dir, "**", "*.xplane.pb"), recursive=True)
        assert found, "the capture wrote no .xplane.pb"
        data = jax.profiler.ProfileData.from_file(max(found, key=os.path.getmtime))
        names = {e.name for plane in data.planes for line in plane.lines for e in line.events}
        assert "continuous.boundary" in names
        for phase in ("sweep", "chunk_dispatch", "wait_tokens", "fanout"):
            assert f"continuous.boundary/{phase}" in names, phase
        assert "serve.request" not in names  # an envelope overlaps every gap
        steps = [e for plane in data.planes for line in plane.lines for e in line.events
                 if e.name == "continuous.boundary"]
        assert all("step_num" in dict(e.stats) for e in steps)

    @pytest.mark.parametrize("path, body, want", [
        ("/v1/profile", {"seconds": 0}, 0),
        ("/v1/profile", {"seconds": 0, "python_tracer": True}, 1),
        ("/admin/profile", {"duration_s": 0.01}, 0),
        ("/admin/profile", {"duration_s": 0.01, "python_tracer": True}, 1),
        ("/admin/profile", {"duration_s": 0.01, "python_tracer": "yes"}, 0),
    ])
    def test_python_tracer_is_off_unless_asked_for(self, front, monkeypatch, path, body, want):
        _, base = front
        seen = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d, profiler_options=None: seen.append(profiler_options))
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        r = requests.post(base + path, json=body, timeout=60,
                          headers={"Authorization": "Bearer sekrit"})
        assert r.status_code == 200, r.text
        (options,) = seen
        assert options.python_tracer_level == want
        assert options.host_tracer_level == 2
        assert ("capture_dir" if path.startswith("/admin") else "trace_dir") in r.json()


class TestEnginePhases:
    def test_phases_tile_the_loop(self, server):
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        try:
            for i in range(4):
                out = cb.generate(np.array([[1 + i, 2, 3]], np.int32), max_new_tokens=9)
                assert out.shape == (1, 12)
            live = cb.snapshot()
        finally:
            cb.close()
        snap = cb.snapshot()
        assert list(snap["phase_s"]) == list(_PHASES) == list(snap["phase_n"])
        total = sum(snap["phase_s"].values())
        assert snap["loop_wall_s"] > 0
        assert total >= 0.97 * snap["loop_wall_s"]
        assert total <= 1.03 * snap["loop_wall_s"]
        # the live snapshot (a step still open) never counts less than its steps
        assert sum(live["phase_s"].values()) >= 0.97 * live["loop_wall_s"]
        for phase in ("sweep", "idle", "admit_prep", "admit_dispatch", "chunk_dispatch",
                      "firsts_wait", "wait_tokens", "fanout", "overlap_prep"):
            assert snap["phase_n"][phase] >= 1, phase
        assert snap["phase_n"]["chunk_dispatch"] <= snap["dispatches"]
        assert 0 < snap["loop_cpu_s"] <= snap["loop_wall_s"]

    def test_boundary_host_ms_is_still_reported(self, server):
        cb = ContinuousBatcher(server, max_slots=2, chunk_size=4)
        try:
            cb.generate(np.array([[1, 2, 3]], np.int32), max_new_tokens=17)
            snap = cb.snapshot()
        finally:
            cb.close()
        assert snap["boundary_host_ms_count"] >= 1 and snap["boundary_host_ms_p50"] >= 0


class TestMetricsBlocks:
    def test_startup_block_and_prometheus_rendering(self, front, monkeypatch):
        _, base = front
        assert "startup" not in requests.get(base + "/metrics").json()  # never began here
        fresh = trace.Startup()
        monkeypatch.setattr(trace, "startup", fresh)
        fresh.begin("backend_init")
        fresh.stage("load")
        fresh.note("engine_init", 0.25)
        fresh.ready()
        generate(base)
        body = requests.get(base + "/metrics").json()
        started = body["startup"]
        stages = ("imports_s", "backend_init_s", "load_s")
        assert sum(started[k] for k in stages) == pytest.approx(started["ready_s"], abs=1e-3)
        assert started["engine_init_s"] >= 0.25  # and the engine this test may build
        cont = body["m"]["continuous"]
        assert set(cont["phase_s"]) == set(_PHASES)
        text = requests.get(base + "/metrics?format=prometheus").text
        for needle in ('ready_s{model="startup"}', 'imports_s{model="startup"}',
                       'engine_init_s{model="startup"}',
                       'continuous_phase_s_fanout{model="m"}',
                       'continuous_phase_n_chunk_dispatch{model="m"}',
                       'continuous_loop_cpu_s{model="m"}',
                       'load_fetch_seconds{model="m"}',
                       'load_device_put_seconds{model="m"}'):
            assert any(needle in line for line in text.splitlines()
                       if not line.startswith("#")), needle
        for line in text.splitlines():  # still the 0.0.4 exposition: comment or sample
            assert line.startswith("#") or len(line.rsplit(" ", 1)) == 2, line

    def test_compile_cache_durations_render(self):
        from modelx_tpu.dl import serve as serve_mod

        text = promexp.render({"compile_cache": serve_mod.compile_cache_stats()})
        for key in ("trace_s", "lower_s", "backend_compile_s", "retrieval_s", "programs"):
            assert f"compile_cache_{key} " in text


class TestCompileCacheDurations:
    @pytest.fixture(autouse=True)
    def _restore(self, monkeypatch):
        from modelx_tpu.dl import serve as serve_mod

        monkeypatch.setattr(serve_mod, "_compile_cache_dir", "")
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)

    def test_durations_grow_on_a_compile_and_retrieval_on_a_hit(self, tmp_path):
        from modelx_tpu.dl import serve as serve_mod

        serve_mod.enable_compile_cache(str(tmp_path / "leg"))
        f = jax.jit(lambda x: jnp.cos(x) * 5 - 2)
        x = jnp.arange(8, dtype=jnp.float32)
        before = serve_mod.compile_cache_stats()
        f(x).block_until_ready()
        mid = serve_mod.compile_cache_stats()
        for key in ("trace_s", "lower_s", "backend_compile_s"):
            assert mid[key] > before[key], key
        assert mid["programs"] > before["programs"]
        assert mid["misses"] > before["misses"]
        assert mid["retrieval_s"] == before["retrieval_s"]  # nothing was read back
        jax.clear_caches()
        f(x).block_until_ready()
        after = serve_mod.compile_cache_stats()
        assert after["hits"] > mid["hits"]
        assert after["retrieval_s"] > mid["retrieval_s"]
        # the backend's compile-or-read-back contains the retrieval
        assert (after["backend_compile_s"] - mid["backend_compile_s"]
                >= after["retrieval_s"] - mid["retrieval_s"])


class TestLoaderSplit:
    def test_load_seconds_are_the_sum_of_the_shards(self, model_dir, monkeypatch):
        from modelx_tpu.dl import loader

        seen = []
        real = loader.load_safetensors

        def spy(*args, **kwargs):
            arrays, stats = real(*args, **kwargs)
            seen.append(stats)
            return arrays, stats

        monkeypatch.setattr(loader, "load_safetensors", spy)
        srv = ModelServer(model_dir, mesh_spec="dp=1", dtype="float32", max_seq_len=96)
        stats = srv.load()
        assert len(seen) == 2
        for key, field in (("load_fetch_seconds", "fetch_seconds"),
                           ("load_fetch_busy_seconds", "fetch_busy_seconds"),
                           ("load_device_put_seconds", "device_put_seconds"),
                           ("load_overlap_seconds", "overlap_seconds")):
            assert stats[key] == pytest.approx(sum(getattr(s, field) for s in seen), abs=2e-3), key
        assert stats["load_fetch_busy_seconds"] <= stats["load_seconds"] + 2e-3
        agg = trace.tracer().summary()
        # reads run on the loader's threads; the packed transfer of small
        # tensors runs on the caller's, under the span that is open there
        assert agg["dl.fetch"]["count"] >= 2
        assert agg["serve.load/shards/dl.put"]["count"] >= 1
        assert {"serve.load/headers", "serve.load/shards", "serve.load/compile_join"} <= set(agg)


class _Dev:
    platform, device_kind = "tpu", "fake"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


class TestDevicePeak:
    @pytest.mark.parametrize("per_device, want", [
        ([{"bytes_in_use": 5, "bytes_limit": 10, "peak_bytes_in_use": 7},
          {"bytes_in_use": 6, "bytes_limit": 10, "peak_bytes_in_use": 9}], 9),
        ([{"bytes_in_use": 5, "bytes_limit": 10}], None),
    ])
    def test_peak_is_the_fullest_device_and_absent_without_an_accountant(
            self, monkeypatch, per_device, want):
        monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(s) for s in per_device])
        dm = devmem.raw_sample()
        assert dm["source"] == "memory_stats"
        assert dm.get("hbm_peak_bytes") == want
        assert dm["hbm_bytes_in_use"] == sum(s["bytes_in_use"] for s in per_device)

    def test_the_cpu_backend_reports_no_peak(self):
        assert "hbm_peak_bytes" not in devmem.raw_sample()
