"""Tracing subsystem (utils/trace.py): span paths, error capture, the
cumulative aggregate, the ring of request spans, the phase and start-up
clocks — the observability layer SURVEY.md §5 prescribes (the reference has
only per-request wall-clock logging). The ring keeps the spans of requests,
so tests that read single spans back run under a request context."""

import subprocess
import sys
import time

import pytest

from modelx_tpu.utils import trace
from modelx_tpu.utils.trace import (Phases, Startup, Tracer, jax_profile, request_context,
                                    span, tracer)


@pytest.fixture(autouse=True)
def clean_tracer():
    tracer().clear()
    yield
    tracer().clear()


@pytest.fixture
def in_request():
    with request_context("req-test"):
        yield


@pytest.mark.usefixtures("in_request")
class TestSpan:
    def test_nested_paths(self):
        with span("outer"):
            with span("inner", k=1):
                pass
        paths = [s["path"] for s in tracer().spans()]
        assert paths == ["outer/inner", "outer"]  # children close first

    def test_attrs_and_duration(self):
        with span("op", model="m") as rec:
            rec["extra"] = 42
        (s,) = tracer().spans("op")
        assert s["model"] == "m" and s["extra"] == 42
        assert s["duration_s"] >= 0

    def test_error_captured_and_reraised(self):
        with pytest.raises(ValueError):
            with span("boom"):
                raise ValueError("nope")
        (s,) = tracer().spans("boom")
        assert "ValueError" in s["error"]

    def test_prefix_filter(self):
        with span("a.x"):
            pass
        with span("b.y"):
            pass
        assert len(tracer().spans("a.")) == 1

    def test_thread_isolation(self):
        import threading

        def worker():
            with span("w"):
                pass

        with span("main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        paths = set(tracer().summary())
        # the worker thread's span must not nest under "main"
        assert "w" in paths and "main" in paths


class TestTracer:
    def test_ring_bound_and_dropped(self):
        t = Tracer(max_spans=3)
        for i in range(5):
            t.record({"path": f"s{i}", "start_s": 0, "duration_s": 0, "request_id": "r"})
        assert len(t.spans()) == 3
        assert t.dropped == 2
        assert t.spans()[0]["path"] == "s2"

    def test_summary_aggregates(self):
        t = Tracer()
        for d in (0.1, 0.3):
            t.record({"path": "op", "start_s": 0, "duration_s": d})
        agg = t.summary()["op"]
        assert agg["count"] == 2
        assert abs(agg["total_s"] - 0.4) < 1e-9
        assert abs(agg["max_s"] - 0.3) < 1e-9

    def test_ring_keeps_request_spans_only(self):
        t = Tracer(max_spans=4)
        t.record({"path": "engine.phase", "start_s": 0, "duration_s": 0.1})
        t.record({"path": "op", "start_s": 0, "duration_s": 0.1, "request_id": "r"})
        assert [s["path"] for s in t.spans()] == ["op"]
        assert set(t.summary()) == {"engine.phase", "op"}

    def test_aggregate_survives_what_the_ring_forgets(self):
        """``serve.load`` closed once at start-up is still in the summary
        after 10 000 engine and request spans turned the ring over."""
        t = Tracer(max_spans=64)
        t.record({"path": "serve.load", "start_s": 0, "duration_s": 9.3})
        for i in range(10_000):
            t.record({"path": "continuous.admit", "start_s": i, "duration_s": 0.001})
            t.record({"path": "serve.request", "start_s": i, "duration_s": 0.002,
                      "request_id": f"r{i}"})
        agg = t.summary()
        assert agg["serve.load"] == {"count": 1, "total_s": 9.3, "max_s": 9.3, "self_s": 9.3}
        assert agg["continuous.admit"]["count"] == 10_000
        assert len(t.spans()) == 64 and t.dropped == 10_000 - 64
        assert t.summary(prefix="serve.l").keys() == {"serve.load"}
        # one request's slice still comes from the ring
        assert t.summary(request_id="r9999")["serve.request"]["count"] == 1

    def test_self_seconds_on_a_hand_made_nest(self, monkeypatch):
        """Self time = total - children, exactly — on a clock the test sets
        (three ``time.sleep`` calls under an upper bound overran on a loaded
        host: six xdist workers, PR 49's run)."""
        now = [100.0]
        monkeypatch.setattr(time, "monotonic", lambda: now[0])

        def passes(seconds: float) -> None:
            now[0] += seconds

        with span("outer"):
            passes(0.02)
            with span("a"):
                passes(0.03)
            with span("b"):
                with span("c"):
                    passes(0.01)
        agg = tracer().summary()
        outer, a, b, c = (agg[p] for p in ("outer", "outer/a", "outer/b", "outer/b/c"))
        assert a["self_s"] == a["total_s"] and c["self_s"] == c["total_s"]
        assert b["self_s"] == pytest.approx(b["total_s"] - c["total_s"], abs=1e-9)
        assert outer["self_s"] == pytest.approx(
            outer["total_s"] - a["total_s"] - b["total_s"], abs=1e-9)
        assert outer["self_s"] == pytest.approx(0.02, abs=1e-9)
        assert (a["total_s"], c["total_s"]) == (pytest.approx(0.03), pytest.approx(0.01))
        assert b["self_s"] == pytest.approx(0.0, abs=1e-9)

    def test_a_step_that_selects_says_so_under_the_steps_span_path(self):
        """The learned selection's spans (models/deepseek_v2.py with an
        indexer): a decode step over a cache longer than ``index_topk`` leaves
        ``dsa.select[rows x cache -> kept]`` under the span it was traced in,
        once a layer, and its program names the four ``dsa.*`` scopes; a cache
        of at most ``index_topk`` positions selects nothing and says nothing."""
        import jax
        import jax.numpy as jnp

        from modelx_tpu.models import deepseek_v2 as ds

        cfg = ds.DeepseekV2Config.tiny_v32(vocab_size=64)
        params = ds.init_params(cfg, jax.random.PRNGKey(0))
        tok, at = jnp.ones((2, 1), jnp.int32), jnp.asarray([9, 20], jnp.int32)

        def step(cache):
            return ds.forward(params, tok, cfg, kv_cache=cache, cache_offset=at)[0]

        with span("continuous.step"):
            text = jax.jit(step).lower(ds.init_layer_state(cfg, 2, 32)).as_text(debug_info=True)
        got = tracer().summary("continuous.step/dsa.")
        assert got["continuous.step/dsa.select[2x32->8]"]["count"] == cfg.num_layers
        for scope in ("dsa.index/", "dsa.score/", "dsa.select/", "dsa.gather/", "dsa.attend/"):
            assert scope in text, scope
        with span("continuous.short"):
            text = jax.jit(step).lower(ds.init_layer_state(cfg, 2, 8)).as_text(debug_info=True)
        assert not tracer().summary("continuous.short/dsa.")
        assert "dsa.select/" not in text and "dsv2.attn.attend/" in text

    def test_a_shape_or_a_decision_in_the_name_is_a_path_of_its_own(self):
        for name in ("attention.flash[144x144]+pad[256x256]", "attention.reference[16x16]"):
            with span(name):
                pass
        assert {"attention.flash[144x144]+pad[256x256]",
                "attention.reference[16x16]"} <= set(tracer().summary("attention."))


@pytest.mark.usefixtures("in_request")
class TestIntegration:
    def test_loader_emits_load_span(self, tmp_path):
        import ml_dtypes
        import numpy as np

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors
        from modelx_tpu.dl.sharding import LLAMA_RULES
        from modelx_tpu.parallel.mesh import make_mesh

        path = str(tmp_path / "m.safetensors")
        st.write_safetensors(path, {"model.norm.weight": np.ones((8,), ml_dtypes.bfloat16)})
        load_safetensors(LocalFileSource(path), make_mesh("dp=1"), LLAMA_RULES)
        (s,) = tracer().spans("dl.load")
        assert s["tensors"] == 1 and s["bytes_to_device"] == 16

    @pytest.mark.parametrize("mesh_spec", ["dp=1", "ep=1,tp=2"])
    def test_the_load_span_says_which_bytes_the_host_copied_twice(self, tmp_path, mesh_spec):
        import numpy as np

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors
        from modelx_tpu.dl.sharding import MIXTRAL_RULES
        from modelx_tpu.parallel.mesh import make_mesh

        path = str(tmp_path / "experts.safetensors")
        st.write_safetensors(path, {
            f"model.layers.0.block_sparse_moe.experts.{e}.w2.weight":
            np.full((8, 16), e, np.float32) for e in range(4)})
        _, stats = load_safetensors(LocalFileSource(path), make_mesh(mesh_spec), MIXTRAL_RULES)
        (s,) = tracer().spans("dl.load")
        # a clean fold: 0; w2's last axis over tp: every member cut from its
        # whole tensor, all 4 x 8 x 16 float32 written once more
        assert s["assemble_copied_bytes"] == stats.assemble_copied_bytes
        assert stats.assemble_copied_bytes == (0 if mesh_spec == "dp=1" else 4 * 8 * 16 * 4)
        assert s["assemble_s"] == round(stats.assemble_seconds, 3)

    # tier-1 wall (ISSUE 16): failure-path profile drill; `make slow` is the home
    @pytest.mark.slow
    def test_jax_profile_noop_on_failure(self, tmp_path):
        # an unwritable dir must not raise out of the context manager
        with jax_profile(str(tmp_path / "trace")):
            pass


class TestPhases:
    """The engine's clock: leaf phases that tile a thread's loop."""

    def test_phases_tile_the_steps(self):
        ph = Phases("loop.step", ("work", "wait"))
        t0 = time.monotonic()
        for i in range(3):
            ph.begin(0, i)
            time.sleep(0.004)
            ph.to(1)
            time.sleep(0.002)
            ph.to(1)  # staying in a phase is not an entry
        ph.end()
        wall = time.monotonic() - t0
        assert ph.entries == [3, 3] and ph.steps == 3
        assert sum(ph.seconds) == pytest.approx(ph.wall_s, abs=1e-9)
        assert ph.wall_s <= wall and ph.seconds[0] >= 0.012 and ph.seconds[1] >= 0.006
        assert 0 <= ph.cpu_s < ph.wall_s  # the sleeps are not CPU time

    def test_phases_reach_the_summary_and_never_the_ring(self):
        ph = Phases("loop.step", ("work", "wait"))
        ph.begin(0)
        ph.to(1)
        ph.end()
        agg = tracer().summary("loop.")
        assert set(agg) == {"loop.step", "loop.step/work", "loop.step/wait"}
        assert agg["loop.step"]["self_s"] == 0.0  # all of a step is its phases
        assert tracer().spans("loop.") == []


class TestStartup:
    def test_stages_tile_process_start_to_ready(self):
        st = Startup()
        st.begin("backend_init")
        time.sleep(0.01)
        st.stage("load")
        time.sleep(0.01)
        st.note("engine_init", 0.5)  # outside the tiling
        st.ready()
        st.stage("too_late")  # after ready nothing moves
        snap = st.snapshot()
        stages = [k for k in snap if k.endswith("_s") and k != "ready_s"
                  and not k.startswith(("engine_init", "imports_interpreter",
                                        "imports_modules"))]
        assert stages == ["imports_s", "backend_init_s", "load_s"]
        assert sum(snap[k] for k in stages) == pytest.approx(snap["ready_s"], abs=1e-3)
        assert snap["imports_s"] > 0 and snap["engine_init_s"] == 0.5
        assert 0 < snap["engine_init_at_s"] <= snap["ready_s"]  # noted before ready here
        assert snap["source"] in ("proc_stat", "first_line")
        # each closed stage is a span too
        assert {"startup.imports", "startup.backend_init", "startup.load"} <= set(
            tracer().summary("startup."))

    def test_a_process_that_never_began_reports_nothing(self):
        st = Startup()
        st.stage("load")
        st.sub("shards")
        st.ready()
        st.first_token(time.monotonic())
        assert st.snapshot() == {}
        assert st.timeline() == {"spans": [], "dropped": 0, "frozen": False}

    @staticmethod
    def started() -> Startup:
        """imports | backend_init = distributed + devices | configure (not
        split) | load = install + headers + shards + headers + finish."""
        st = Startup()
        st.begin("backend_init")
        st.sub("distributed")
        time.sleep(0.002)
        st.sub("devices")
        time.sleep(0.004)
        st.stage("configure")
        time.sleep(0.002)
        st.stage("load")
        time.sleep(0.002)
        for name in ("install", "headers", "shards", "headers", "headers", "finish"):
            st.sub(name)  # a second model's headers add up; staying is no entry
            time.sleep(0.003)
        return st

    def test_sub_stages_tile_their_stage_and_the_stages_still_tile_ready(self):
        st = self.started()
        st.ready()
        st.sub("too_late")
        snap = st.snapshot()
        subs = {"imports": ("interpreter", "modules"),
                "backend_init": ("distributed", "devices"),
                "load": ("install", "headers", "shards", "finish")}
        for stage, names in subs.items():
            assert [k for k in snap if k.startswith(stage + "_") and k != stage + "_s"] == [
                f"{stage}_{n}_s" for n in names]
            assert sum(snap[f"{stage}_{n}_s"] for n in names) == pytest.approx(
                snap[f"{stage}_s"], abs=1e-3), stage
        assert [k for k in snap if k.startswith("configure_")] == ["configure_s"]  # not split
        assert sum(snap[k] for k in ("imports_s", "backend_init_s", "configure_s",
                                     "load_s")) == pytest.approx(snap["ready_s"], abs=1e-3)
        # the first sub-stage began with its stage: `install` holds the 2 ms before it
        assert snap["load_install_s"] >= 0.004 and snap["load_headers_s"] >= 0.005
        assert snap["backend_init_devices_s"] >= 0.004
        agg = tracer().summary("startup.")
        assert {"startup.imports/interpreter", "startup.imports/modules",
                "startup.backend_init/devices", "startup.load/install",
                "startup.load/finish", "startup.load"} <= set(agg)
        assert agg["startup.load/headers"]["count"] == 2

    def test_another_threads_sub_stage_is_not_the_stages(self):
        import threading

        st = Startup()
        st.begin("load")
        t = threading.Thread(target=st.sub, args=("shards",))
        t.start()
        t.join()
        st.ready()
        assert [k for k in st.snapshot() if k.startswith("load_")] == ["load_s"]

    def test_the_first_token_closes_two_stages_once(self):
        st = self.started()
        st.first_token(time.monotonic())  # not ready yet: nothing to close
        assert st.first_token_s is None
        st.ready()
        time.sleep(0.004)
        arrived = time.monotonic()
        time.sleep(0.006)
        st.first_token(arrived)
        snap = st.snapshot()
        assert snap["first_wait_s"] >= 0.004 and snap["first_request_s"] >= 0.006
        assert snap["ready_s"] + snap["first_wait_s"] + snap["first_request_s"] == pytest.approx(
            snap["first_token_s"], abs=1e-3)
        time.sleep(0.003)
        st.first_token(time.monotonic())  # a second request moves nothing
        assert st.snapshot() == snap
        assert tracer().summary("startup.first")["startup.first_request"]["count"] == 1

    def test_a_request_that_arrived_before_ready_waited_no_time(self):
        st = Startup()
        st.begin("load")
        early = time.monotonic()
        st.ready()
        st.first_token(early)
        snap = st.snapshot()
        assert snap["first_wait_s"] == 0.0
        assert snap["first_request_s"] == pytest.approx(snap["first_token_s"] - snap["ready_s"],
                                                        abs=1e-3)


class TestStartupTimeline:
    @pytest.fixture
    def st(self, monkeypatch):
        fresh = Startup()
        monkeypatch.setattr(trace, "startup", fresh)  # the tracer feeds this one
        return fresh

    def test_spans_in_start_order_with_thread_and_attributes(self, st):
        import threading

        with span("before.begin"):
            pass  # nothing is kept before the clock has a zero
        st.begin("load")
        with span("serve.load", model="m"):
            t = threading.Thread(target=lambda: trace.record(
                "dl.fetch", time.monotonic(), 0.25, bytes=7), name="fetcher")
            t.start()
            t.join()
            with span("shards", files=2, where=("a", 1)):
                pass
        st.ready()
        st.first_token(time.monotonic())
        with span("after.first.token"):
            pass
        line = st.timeline()
        assert line["frozen"] is True and line["dropped"] == 0
        paths = [e["path"] for e in line["spans"]]
        assert set(paths[:2]) == {"startup.imports", "startup.imports/interpreter"}
        assert "before.begin" not in paths and "after.first.token" not in paths
        assert [e["at_s"] for e in line["spans"]] == sorted(e["at_s"] for e in line["spans"])
        assert paths.index("serve.load") < paths.index("serve.load/shards")  # by start, not close
        by = {e["path"]: e for e in line["spans"]}
        assert by["dl.fetch"]["thread"] == "fetcher" and by["dl.fetch"]["attrs"] == {"bytes": 7}
        assert by["serve.load"]["attrs"] == {"model": "m"}
        assert by["serve.load/shards"]["attrs"] == {"files": 2, "where": "('a', 1)"}
        assert set(by["dl.fetch"]) == {"path", "at_s", "duration_s", "thread", "attrs"}
        assert {"startup.load", "startup.first_wait", "startup.first_request"} <= set(by)
        assert by["startup.first_request"]["at_s"] + by["startup.first_request"][
            "duration_s"] == pytest.approx(st.first_token_s, abs=1e-3)
        import json

        json.dumps(line)  # what /v1/trace?startup=1 answers with

    def test_bounded_with_its_drops_counted_and_short_reads_merged(self, st, monkeypatch):
        monkeypatch.setattr(Startup, "MAX_TIMELINE", 8)
        st.begin("load")  # imports, imports/interpreter, imports/modules: 3 entries
        for i in range(5):
            trace.record("dl.fetch", time.monotonic(), 0.0001, bytes=10)  # one merged entry
        trace.record("dl.fetch", time.monotonic(), 0.5, bytes=99)  # a long read: its own
        for i in range(6):
            trace.record("x.filler", time.monotonic(), 0.002)
        line = st.timeline()
        assert len(line["spans"]) == 8 and line["dropped"] == 3 and line["frozen"] is False
        merged = [e for e in line["spans"] if e["attrs"].get("merged")]
        assert len(merged) == 1 and merged[0]["attrs"] == {"bytes": 50, "merged": 5}
        assert merged[0]["duration_s"] == pytest.approx(0.0005, abs=1e-6)
        # a short read still merges into its entry when the list is full
        trace.record("dl.fetch", time.monotonic(), 0.0001, bytes=10)
        assert st.timeline()["dropped"] == 3


class TestNoJax:
    def test_imports_and_spans_without_jax(self):
        """The registry and the client import this module: it must load, and
        spans and phases must run, with jax never imported."""
        code = (
            "import sys\n"
            "from modelx_tpu.utils import trace\n"
            "with trace.span('a'):\n"
            "    with trace.span('b'):\n"
            "        pass\n"
            "ph = trace.Phases('s', ('x',)); ph.begin(0); ph.end()\n"
            "assert set(trace.tracer().summary()) == {'a', 'a/b', 's', 's/x'}\n"
            "trace.startup.begin('load'); trace.startup.sub('shards')\n"
            "trace.startup.ready(); trace.startup.first_token(0.0)\n"
            "assert trace.startup.snapshot()['first_token_s'] > 0\n"
            "assert trace.startup.timeline()['frozen']\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
        )
        subprocess.run([sys.executable, "-c", code], check=True, timeout=60)

    def test_no_annotation_outside_a_capture(self, monkeypatch):
        calls = []
        assert trace._annotate is None
        with span("quiet"):
            pass
        monkeypatch.setattr(trace, "_annotate", lambda path, **kw: calls.append(path) or _Null())
        with span("serve.request"):  # an envelope: never bridged
            with span("serve.forward"):
                pass
        assert calls == ["serve.request/serve.forward"]


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class TestRequestContext:
    """The request id rides a contextvar parallel to the span path: every
    span closed inside ``request_context`` carries it, and the /v1/trace
    filters slice one request's timeline out of the ring."""

    def test_spans_stamped_and_filterable(self):
        from modelx_tpu.utils.trace import current_request_id, request_context

        assert current_request_id() == ""
        with request_context("req-42"):
            assert current_request_id() == "req-42"
            with span("inside"):
                pass
        assert current_request_id() == ""
        (s,) = tracer().spans(request_id="req-42")
        assert s["path"] == "inside"
        with request_context(""):
            with span("outside"):
                pass
        # a span closed under no request is aggregated, not kept
        assert tracer().spans("outside") == []
        assert tracer().summary()["outside"]["count"] == 1

    def test_summary_filters_by_request_id(self):
        from modelx_tpu.utils.trace import request_context

        for rid in ("req-a", "req-a", "req-b"):
            with request_context(rid):
                with span("op"):
                    pass
        assert tracer().summary(request_id="req-a")["op"]["count"] == 2
        assert tracer().summary(request_id="req-b")["op"]["count"] == 1
        assert tracer().summary(request_id="req-zzz") == {}

    def test_context_isolated_per_thread(self):
        import threading

        from modelx_tpu.utils.trace import request_context

        seen = []

        def worker():
            with span("w.op"):
                pass
            seen.append(True)

        with request_context("req-main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen
        # the worker thread's span never inherits the main thread's id
        assert tracer().spans("w.op") == []
        assert tracer().summary()["w.op"]["count"] == 1
