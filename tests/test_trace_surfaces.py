"""What the one span system puts on a pod's surfaces (ISSUE 25): the
aggregate behind ``/v1/trace``, the profiler bridge, the engine's phase
clock, the ``startup`` and ``compile_cache`` blocks of ``/metrics``, the
loader's split and the device's peak — over a real ServerSet on the CPU
backend. A CPU run checks counts, names and that sums close; never a time."""

import dataclasses
import glob
import json
import os
import subprocess
import sys
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


class TestProfileDuringALoad:
    def test_a_capture_begun_before_the_load_holds_reads_puts_and_program_loads(
            self, model_dir, tmp_path, monkeypatch):
        """The listener is up before the load and /v1/profile is routed
        before any ready check: a capture begun when the port answers holds
        the loader's reads and puts and the stored chunk program's load."""
        from jax.experimental.compilation_cache import compilation_cache

        from modelx_tpu.dl import aot_cache
        from modelx_tpu.dl import serve as serve_mod

        # a node's compile cache directory with jax's own cache off: on the
        # CPU the store takes no executable that cache served
        monkeypatch.setattr(serve_mod, "_compile_cache_dir", str(tmp_path / "node"))
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        engine = dict(continuous_batch=True, max_slots=2, stream_chunk_size=4)
        try:
            first = ServerSet({"m": ModelServer(model_dir, mesh_spec="dp=1", dtype="float32",
                                                max_seq_len=96)}, **engine)
            first.load_all()  # builds the chunk program beside the load, and stores it
            for cb in list(first.cbatchers.values()):
                cb.generate(np.array([[5, 9, 2]], np.int32), max_new_tokens=2)  # waits for it
                cb.close()
            assert os.listdir(tmp_path / "node" / "programs")
            sset = ServerSet({"m": ModelServer(model_dir, mesh_spec="dp=1", dtype="float32",
                                               max_seq_len=96)}, **engine,
                             trace_dir=str(tmp_path / "traces"))
            port = free_port()
            httpd = serve(sset, listen=f"127.0.0.1:{port}")
            base = f"http://127.0.0.1:{port}"
            try:
                assert requests.get(base + "/healthz").status_code == 503  # loading
                answer = {}
                t = threading.Thread(target=lambda: answer.update(r=requests.post(
                    base + "/v1/profile", json={"seconds": 3.0}, timeout=120)), daemon=True)
                t.start()
                for _ in range(500):
                    if trace._annotate is not None:
                        break
                    threading.Event().wait(0.01)
                assert trace._annotate is not None, "the capture never began"
                hits = aot_cache.store_stats()["store_hits"]
                sset.load_all()
                for _ in range(1000):  # the side thread may outlast the load
                    if aot_cache.store_stats()["store_hits"] > hits:
                        break
                    threading.Event().wait(0.01)
                assert aot_cache.store_stats()["store_hits"] == hits + 1
                t.join(120)
                assert answer["r"].status_code == 200, answer["r"].text
            finally:
                for cb in list(sset.cbatchers.values()):
                    cb.close()
                httpd.shutdown()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()
        found = glob.glob(os.path.join(sset.trace_dir, "**", "*.xplane.pb"), recursive=True)
        assert found, "the capture wrote no .xplane.pb"
        data = jax.profiler.ProfileData.from_file(max(found, key=os.path.getmtime))
        names = {e.name for plane in data.planes for line in plane.lines for e in line.events}
        assert {"dl.fetch", "serve.load", "serve.load/headers", "serve.load/shards"} <= names
        assert any(n.endswith("dl.put") for n in names)
        for child in ("read", "unpickle", "deserialize"):
            assert any(n.endswith(f"programs.load/{child}") for n in names), child


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
                       'load_idle_seconds{model="m"}',
                       'load_shards_seconds{model="m"}',
                       'load_device_put_seconds{model="m"}',
                       'load_assemble_copied_bytes{model="m"}'):
            assert any(needle in line for line in text.splitlines()
                       if not line.startswith("#")), needle
        for line in text.splitlines():  # still the 0.0.4 exposition: comment or sample
            assert line.startswith("#") or len(line.rsplit(" ", 1)) == 2, line

    def test_compile_cache_durations_render(self):
        from modelx_tpu.dl import serve as serve_mod

        text = promexp.render({"compile_cache": serve_mod.compile_cache_stats()})
        for key in ("trace_s", "lower_s", "backend_compile_s", "retrieval_s", "programs",
                    "store_load_s", "store_read_s", "store_deserialize_s", "store_bytes_read"):
            assert f"compile_cache_{key} " in text

    def test_the_loaders_tiling_is_on_metrics_and_thread_seconds_are_gone(self, front):
        _, base = front
        model = requests.get(base + "/metrics").json()["m"]
        for key in ("idle", "backpressure", "assemble", "drain", "shards", "fetch_busy",
                    "device_put", "overlap"):
            assert model[f"load_{key}_seconds"] >= 0, key
        assert model["load_shard_files"] == 2
        assert model["load_assemble_copied_bytes"] == 0  # float32 as stored: nothing copied twice
        assert "load_fetch_seconds" not in model
        busy = (model["load_fetch_busy_seconds"] + model["load_device_put_seconds"]
                - model["load_overlap_seconds"] + model["load_assemble_seconds"])
        # a few milliseconds of load, each term rounded to one
        assert busy + model["load_idle_seconds"] == pytest.approx(
            model["load_shards_seconds"], abs=5e-3)

    def test_the_first_token_closes_the_startup_clock_and_freezes_its_timeline(
            self, front, monkeypatch):
        _, base = front
        fresh = trace.Startup()
        monkeypatch.setattr(trace, "startup", fresh)
        assert requests.get(base + "/v1/trace?startup=1").json() == {
            "spans": [], "dropped": 0, "frozen": False}
        fresh.begin("backend_init")
        fresh.sub("distributed")
        fresh.sub("devices")
        fresh.stage("load")
        with trace.span("serve.load", model="m"):
            pass
        fresh.ready()
        assert "first_token_s" not in requests.get(base + "/metrics").json()["startup"]
        assert requests.get(base + "/v1/trace?startup=1").json()["frozen"] is False
        generate(base, "rid-first")
        started = requests.get(base + "/metrics").json()["startup"]
        assert started["ready_s"] + started["first_wait_s"] + started[
            "first_request_s"] == pytest.approx(started["first_token_s"], abs=1e-3)
        assert started["backend_init_distributed_s"] + started[
            "backend_init_devices_s"] == pytest.approx(started["backend_init_s"], abs=1e-3)
        line = requests.get(base + "/v1/trace?startup=1").json()
        assert line["frozen"] is True and line["dropped"] == 0
        paths = [e["path"] for e in line["spans"]]
        assert {"startup.imports/interpreter", "startup.backend_init/devices", "serve.load",
                "startup.load", "startup.first_wait", "startup.first_request"} <= set(paths)
        assert [e["at_s"] for e in line["spans"]] == sorted(e["at_s"] for e in line["spans"])
        assert all(set(e) == {"path", "at_s", "duration_s", "thread", "attrs"}
                   for e in line["spans"])
        # the first request's own spans, up to its first token, are on the line
        assert any(e["attrs"].get("request_id") == "rid-first" for e in line["spans"])
        generate(base, "rid-second")  # a second request moves nothing
        assert requests.get(base + "/metrics").json()["startup"] == started
        assert requests.get(base + "/v1/trace?startup=1").json() == line
        # the aggregate and a request's slice answer as before
        assert "serve.load" in requests.get(base + "/v1/trace").json()
        assert requests.get(base + "/v1/trace?startup=0&request_id=rid-second").json()[
            "serve.request"]["count"] == 1


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
        for key, field in (("load_drain_seconds", "drain_seconds"),
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


class TestLayerMetricFiles:
    """The sixteen per-layer metrics ISSUE 40 adds are data for readers the
    benchmark already had: on a pod's dumps they read the new keys, and on a
    parent's, which has none of them, they say nothing."""

    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    DEPLOY, DECODE = "phi3-mini-4k.deploy", "mixtral-8x7b-d4.decode"
    # name -> (what the hand-made dumps below give, cell, layer, moves, better)
    FRONT, LOADER, CACHE = "CLI + Serving front", "Loader", "Compile caches"
    WANT = {
        "front.interpreter_s": (0.4, DEPLOY, FRONT, "pod_listen_ttft_s", "lower"),
        "front.backend_devices_s": (8.5, DEPLOY, FRONT, "pod_listen_ttft_s", "lower"),
        "front.first_token_s": (26.5, DEPLOY, FRONT, "pod_listen_ttft_s", "lower"),
        "loader.shards_share": (0.9, DEPLOY, LOADER, "pod_listen_ttft_s", "higher"),
        "loader.idle_share": (0.2, DEPLOY, LOADER, "pod_listen_ttft_s", "lower"),
        "loader.drain_share": (0.1, DEPLOY, LOADER, "pod_listen_ttft_s", "lower"),
        "loader.backpressure_share": (0.05, DEPLOY, LOADER, "pod_listen_ttft_s", "lower"),
        "loader.assemble_share": (0.01, DEPLOY, LOADER, "pod_listen_ttft_s", "lower"),
        "cache.store_deserialize_s_per_program": (3.0, DEPLOY, CACHE, "pod_listen_ttft_s", "lower"),
        "cache.store_mb_per_program": (20.0, DEPLOY, CACHE, "pod_listen_ttft_s", "lower"),
        "device.idle_named_share.deploy": (0.75, DEPLOY, "Device", "pod_listen_ttft_s", "higher"),
        "loader.load_gbps.decode": (0.45, DECODE, LOADER, "setup_s", "higher"),
        "loader.idle_share.decode": (0.25, DECODE, LOADER, "setup_s", "lower"),
        "loader.assemble_share.decode": (0.5, DECODE, LOADER, "setup_s", "lower"),
        "cache.store_deserialize_s_per_program.decode": (0.5, DECODE, CACHE, "setup_s", "lower"),
        "cache.store_mb_per_program.decode": (13.0, DECODE, CACHE, "setup_s", "lower"),
    }

    @classmethod
    def read(cls, name, sources):
        import importlib
        import json

        with open(os.path.join(cls.ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
        return reader.read(sources, spec)

    @staticmethod
    def dumps() -> dict:
        load = {"load_seconds": 10.0, "load_shards_seconds": 9.0, "load_idle_seconds": 1.8,
                "load_drain_seconds": 0.9, "load_backpressure_seconds": 0.45,
                "load_assemble_seconds": 0.09, "load_gbps": 0.76}
        before = {"default": load, "compile_cache": {
            "store_hits": 1, "store_deserialize_s": 7.0, "store_bytes_read": 30_000_000}}
        after = {"default": load, "startup": {
            "imports_interpreter_s": 0.4, "backend_init_devices_s": 8.5, "first_token_s": 26.5},
            "compile_cache": {"store_hits": 3, "store_deserialize_s": 13.0,
                              "store_bytes_read": 70_000_000}}
        warm = {"default": {"load_gbps": 0.45, "load_shards_seconds": 20.0,
                            "load_idle_seconds": 5.0, "load_assemble_seconds": 10.0},
                "compile_cache": {"store_hits": 48, "store_deserialize_s": 24.0,
                                  "store_bytes_read": 624_000_000}}
        return {"model": "default", "metrics_before": warm,
                "trace_span": {"metrics_before": before, "metrics_after": after},
                "trace": {"idle_gaps": [["continuous.admit/programs.load/deserialize", 3.0],
                                        ["$pjit.py:123 cache_miss", 1.0]]}}

    @pytest.mark.parametrize("name", list(WANT))
    def test_reads_the_pods_dumps(self, name):
        assert self.read(name, self.dumps()) == pytest.approx(self.WANT[name][0])

    @pytest.mark.parametrize("name", list(WANT))
    def test_a_parent_without_the_keys_reads_nothing(self, name):
        """The parent's dumps: the keys it has (``load_seconds``, ``load_gbps``,
        ``store_hits``, the five stages) and none of this PR's."""
        parent = {"default": {"load_seconds": 10.0, "load_gbps": 0.45,
                              "load_fetch_busy_seconds": 7.0},
                  "startup": {"imports_s": 3.8, "backend_init_s": 9.0, "ready_s": 22.0},
                  "compile_cache": {"store_hits": 3, "store_load_s": 9.0, "store_bytes": 7}}
        sources = {"model": "default", "metrics_before": parent, "metrics_after": parent,
                   "trace_span": {"metrics_before": parent, "metrics_after": parent},
                   "trace": {"idle_gaps": [["continuous.admit/programs.load", 3.0]]}}
        got = self.read(name, sources)
        if name in ("device.idle_named_share.deploy", "loader.load_gbps.decode"):
            # read from what the parent already reports
            assert got == {"device.idle_named_share.deploy": 1.0,
                           "loader.load_gbps.decode": 0.45}[name]
        else:
            assert got is None
        assert self.read(name, {}) is None

    def test_benchmark_json_lists_them_at_the_end_for_their_cells(self):
        import json

        with open(os.path.join(self.ROOT, "BENCHMARK.json")) as f:
            per_layer = json.load(f)["per_layer"]
        by_name = {m["name"]: m for m in per_layer}  # found by name and cell, never by place
        assert len(by_name) == len(per_layer)
        for name, (_, cell, layer, moves, better) in self.WANT.items():
            m = by_name[name]
            assert cell in m["workloads"], name
            assert (m["layer"], m["moves"], m["better"]) == (layer, moves, better), name
            assert m["source"] == ("device_trace" if name.startswith("device.")
                                   else "program_counter")


class TestProfileEdges:
    """The pod alone knows where a capture begins and ends: ``/metrics`` carries
    the newest one under ``profile``, with the engines' counters read inside the
    profiler's own start and stop."""

    @pytest.fixture(scope="class")
    def captured(self, server, tmp_path_factory):
        d = tmp_path_factory.mktemp("profile_edges")
        sset = ServerSet({"m": server}, continuous_batch=True, max_slots=2,
                         stream_chunk_size=4, trace_dir=str(d / "traces"))
        port = free_port()
        httpd = serve(sset, listen=f"127.0.0.1:{port}")
        base = f"http://127.0.0.1:{port}"
        try:
            generate(base)
            never = requests.get(base + "/metrics").json()
            never_text = requests.get(base + "/metrics?format=prometheus").text
            first = requests.post(base + "/v1/profile", json={"seconds": 0}, timeout=300)
            once = requests.get(base + "/metrics").json()
            generate(base, n=12)
            second = requests.post(base + "/v1/profile", json={"seconds": 0.2}, timeout=300)
            yield {"never": never, "never_text": never_text, "first": first, "once": once,
                   "second": second, "twice": requests.get(base + "/metrics").json(),
                   "text": requests.get(base + "/metrics?format=prometheus").text}
        finally:
            for cb in list(sset.cbatchers.values()):
                cb.close()
            httpd.shutdown()

    def test_a_pod_never_profiled_has_no_profile_key(self, captured):
        assert "profile" not in captured["never"]
        assert "profile" not in captured["never_text"]
        assert captured["never"]["m"]["continuous"]["row_steps"]["total"] > 0

    def test_the_response_keeps_its_keys(self, captured):
        assert captured["first"].status_code == captured["second"].status_code == 200
        assert set(captured["first"].json()) == {"trace_dir"}

    def test_the_block_holds_the_two_calls_times_and_the_engines_at_both_edges(self, captured):
        profile = captured["once"]["profile"]
        assert set(profile) == {"captures", "start_s", "stop_s", "traced_s", "at_start", "at_stop"}
        assert profile["captures"] == 1
        assert profile["start_s"] >= 0 and profile["stop_s"] >= 0 and profile["traced_s"] >= 0
        for edge in ("at_start", "at_stop"):
            # the shape of /metrics itself: the benchmark's metrics_path reads it as it is
            assert set(profile[edge]) == {"m"} and set(profile[edge]["m"]) == {"continuous"}
            ledger = profile[edge]["m"]["continuous"]["row_steps"]
            assert sum(ledger[k] for k in ledger if k != "total") == ledger["total"] > 0

    def test_at_stop_is_not_before_at_start_and_the_newest_capture_stands(self, captured):
        profile = captured["twice"]["profile"]
        assert profile["captures"] == 2 and profile["traced_s"] >= 0.2
        start, stop = (profile[e]["m"]["continuous"] for e in ("at_start", "at_stop"))
        for key in ("dispatches", "chunks", "admitted", "decode_rows"):
            assert stop[key] >= start[key], key
        assert all(stop["row_steps"][k] >= start["row_steps"][k] for k in stop["row_steps"])
        # the second capture's first edge lies after the first capture's last
        earlier = captured["once"]["profile"]["at_stop"]["m"]["continuous"]
        assert start["row_steps"]["total"] > earlier["row_steps"]["total"]

    def test_the_text_view_renders_the_times_and_leaves_the_dumps_out(self, captured):
        text = captured["text"]
        # a top-level block renders as its keys under model="<block>", like ``startup``
        lines = [l for l in text.splitlines() if 'model="profile"' in l]
        assert {l.split("{")[0] for l in lines} == {
            "modelx_captures", "modelx_start_s", "modelx_stop_s", "modelx_traced_s"}
        assert 'modelx_captures{model="profile"} 2' in lines
        assert not any("at_start" in l or "at_stop" in l for l in text.splitlines())
        # the engines' own series stand once
        assert sum(1 for l in text.splitlines()
                   if "row_steps_total" in l and not l.startswith("#")) == 1


class TestRowStepMetricFiles:
    """The seven per-layer metrics ISSUE 58 adds: six are data for
    ``metrics_path`` over the profile block's two dumps, the seventh reads the
    reduced trace's modules; a parent's sources give nothing."""

    ROOT = TestLayerMetricFiles.ROOT
    TOKEN_CELLS = ["mixtral-8x7b-d4.decode", "laguna-s-2.1-ep2-d5.reason",
                   "minicpm-sala-d12.longctx", "deepseek-v2-ep8-d5.longdoc",
                   "nemotron-3-super-ep4-d11.agent", "deepseek-v3.2-exp-ep16-d5.sparsedoc",
                   "mimo-v2-flash-ep16-d7.longcode"]
    # the cells whose traced window never lacks an admission (6 and 3 at the least over 4,000
    # draws of the generator): four are primed in the lead-in, .reason's 4 s hold 3-13 and may
    # hold none, and a traced line may not lack a metric its cell is listed for
    ADMITTING_CELLS = ["mixtral-8x7b-d4.decode", "nemotron-3-super-ep4-d11.agent"]
    # name -> (what the hand-made sources give, unit, better, source)
    WANT = {
        "engine.token_share": (0.9, "ratio", "higher", "program_counter"),
        "engine.edge_share": (0.02, "ratio", "lower", "program_counter"),
        "engine.filling_share": (0.01, "ratio", "lower", "program_counter"),
        "engine.vacant_queued_share": (0.03, "ratio", "lower", "program_counter"),
        "engine.vacant_idle_share": (0.04, "ratio", "lower", "program_counter"),
        "engine.turnover_row_steps": (25.0, "row-steps", "lower", "program_counter"),
        "engine.nonchunk_device_share": (0.125, "ratio", "lower", "device_trace"),
    }

    @staticmethod
    def sources() -> dict:
        def dump(tokens, edge, filling, queued, idle, admitted):
            return {"default": {"continuous": {"admitted": admitted, "row_steps": {
                "tokens": tokens, "edge": edge, "filling": filling, "vacant_queued": queued,
                "vacant_idle": idle, "total": tokens + edge + filling + queued + idle}}}}
        profile = {"captures": 1, "start_s": 0.5, "stop_s": 90.0, "traced_s": 4.0,
                   "at_start": dump(5000, 700, 100, 100, 100, 40),
                   "at_stop": dump(14000, 900, 200, 400, 500, 80)}
        return {"model": "default", "metrics_after": {"profile": profile},
                "trace": {"window_s": 4.0, "modules": {
                    "jit__chunk_impl_d4": {"seconds": 2.5, "count": 5},
                    "jit__chunk_impl_s12": {"seconds": 1.0, "count": 9},
                    "jit__admit_nosmall": {"seconds": 0.3, "count": 7},
                    "jit__piece_impl": {"seconds": 0.2, "count": 2}}}}

    @pytest.mark.parametrize("name", list(WANT))
    def test_reads_the_growth_between_the_profilers_edges(self, name):
        assert TestLayerMetricFiles.read(name, self.sources()) == pytest.approx(self.WANT[name][0])

    def test_the_five_shares_sum_to_one(self):
        shares = [TestLayerMetricFiles.read(name, self.sources()) for name in list(self.WANT)[:5]]
        assert sum(shares) == pytest.approx(1.0, abs=1e-9)

    @pytest.mark.parametrize("name", list(WANT))
    def test_a_parent_an_untraced_pod_and_a_window_without_admissions_read_nothing(self, name):
        counters = {"default": {"continuous": {"admitted": 9, "decode_rows": 64,
                                               "decode_pad_rows": 3}}}
        parent = {"model": "default", "metrics_before": counters, "metrics_after": counters,
                  "trace_span": {"metrics_before": counters, "metrics_after": counters},
                  "trace": {"window_s": 4.0, "modules": {}}}
        assert TestLayerMetricFiles.read(name, parent) is None  # no profile block, no module
        # a pod of this PR whose dumps lack the ledger (another engine's): nothing raises
        bare = dict(parent, metrics_after={"profile": {"at_start": counters, "at_stop": counters}})
        assert TestLayerMetricFiles.read(name, bare) is None
        assert TestLayerMetricFiles.read(name, {}) is None
        if name == "engine.turnover_row_steps":
            primed = self.sources()
            edges = primed["metrics_after"]["profile"]
            edges["at_stop"]["default"]["continuous"]["admitted"] = 40  # nothing admitted
            assert TestLayerMetricFiles.read(name, primed) is None

    @pytest.mark.parametrize("name", list(WANT))
    def test_benchmark_json_lists_each_for_the_token_cells_that_give_it_a_reading(self, name):
        import json

        with open(os.path.join(self.ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        found = [m for m in bench["per_layer"] if m["name"] == name]  # by name, never by place
        assert len(found) == 1
        _, unit, better, source = self.WANT[name]
        assert {k: v for k, v in found[0].items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "Engine", "moves": "tokens_per_s"}
        listed = self.ADMITTING_CELLS if name == "engine.turnover_row_steps" else self.TOKEN_CELLS
        for cell in self.TOKEN_CELLS:
            assert (cell in found[0]["workloads"]) == (cell in listed)
        assert "phi3-mini-4k.deploy" not in found[0]["workloads"]
        with open(os.path.join(self.ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        if source == "device_trace":
            assert spec == {"reader": "module_share", "excluding": "chunk_impl_"}
        else:
            assert (spec["reader"], spec["before"], spec["after"]) == (
                "metrics_path", "metrics_after.profile.at_start", "metrics_after.profile.at_stop")


def test_a_rehearsed_traced_cells_last_line_carries_the_six_counter_metrics():
    """``mixtral-8x7b-d4.decode`` walked at its tiny preset with ``--trace 1``: the pod's
    profile block reaches the benchmark's readers through ``metrics_after`` as it is."""
    root = TestLayerMetricFiles.ROOT
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload",
         "mixtral-8x7b-d4.decode", "--rehearse", "--trace", "1"],
        # one CPU device, as a pod finds it (the suite's eight virtual ones slow the window)
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
                 PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", "")),
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] and last["failed"] == 0 and last["attempted"] > 0
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    shares = [metrics["engine." + k + "_share"]
              for k in ("token", "edge", "filling", "vacant_queued", "vacant_idle")]
    assert sum(shares) == pytest.approx(1.0, abs=1e-6) and min(shares) >= 0
    assert 0.3 < metrics["engine.token_share"] < 1  # four clients, requests of 24-48 tokens
    assert metrics["engine.edge_share"] > 0  # a budget ends inside a program
    assert metrics["engine.turnover_row_steps"] > 0
    assert last["metrics"]["engine.turnover_row_steps"]["unit"] == "row-steps"
    # the vacant slots the ledger counts are the rows pad_fraction calls idle, over the
    # pod's own edges (the two spans differ by the profiler's start: close, not equal)
    assert (metrics["engine.vacant_queued_share"] + metrics["engine.vacant_idle_share"]
            == pytest.approx(metrics["engine.pad_fraction"], abs=0.1))
    # a CPU trace is no device trace: the seventh goes on the rehearsal's own line
    rehearsed = next(l for l in lines if l.get("phase", "").startswith("rehearsed_on_a_cpu"))
    assert 0 <= rehearsed["engine.nonchunk_device_share"] < 1
    assert "engine.nonchunk_device_share" not in metrics
