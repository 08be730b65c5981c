"""The engine's programs are loaded by a key taken before tracing (ISSUE 32):
a node-local store of loaded executables under ``<compile cache>/programs/``
(dl/aot_cache.ExecutableStore), reached through the one wrapper every jitted
program of the continuous engine is called through (StoredProgram). On the
CPU backend: tokens, counts, keys and files; never a time."""

import copy
import dataclasses
import functools
import json
import logging
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import aot_cache
from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl import serve as serve_mod
from modelx_tpu.dl.continuous import ContinuousBatcher
from modelx_tpu.dl.serve import ModelServer
from modelx_tpu.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPT = np.array([[5, 9, 2, 7, 11]], np.int32)
BURST = np.array([[5, 9, 2, 7, 11], [3, 1, 4, 1, 5], [2, 7, 1, 8, 2]], np.int32)
SAMPLED = dict(temperature=0.8, top_k=8, top_p=0.9, seed=3)
ENGINE = dict(max_slots=4, chunk_size=4)
IMPLS = [name for name in vars(ContinuousBatcher) if name.endswith("_impl")]


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    from modelx_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    params = {k: np.asarray(v) for k, v in
              llama.init_params(cfg, jax.random.PRNGKey(0)).items()}
    d = tmp_path_factory.mktemp("program_store")
    st.write_safetensors(str(d / "model.safetensors"), params)
    return str(d)


@pytest.fixture(scope="module")
def server(model_dir):
    server = ModelServer(model_dir, mesh_spec="dp=1", dtype="float32", max_seq_len=96)
    server.load()
    return server


@pytest.fixture
def node(tmp_path, monkeypatch):
    """A node's compile cache directory, as the engine learns of it. jax's
    own persistent cache is off: what is found again was in the store, and
    on the CPU the store takes no executable that cache served."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(serve_mod, "_compile_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield str(tmp_path)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture
def engine_traces(monkeypatch):
    """Which engine programs jax traced (a program's python body runs only
    while tracing)."""
    seen = []
    for name in IMPLS:
        impl = getattr(ContinuousBatcher, name)

        @functools.wraps(impl)
        def counted(self, *args, _impl=impl, **kwargs):
            seen.append(_impl.__name__)
            return _impl(self, *args, **kwargs)

        monkeypatch.setattr(ContinuousBatcher, name, counted)
    return seen


def entries(node: str) -> list[str]:
    try:
        return sorted(os.listdir(os.path.join(node, "programs")))
    except FileNotFoundError:
        return []


def growth(before: dict) -> dict:
    after = aot_cache.store_stats()
    return {k: after[k] - before[k] for k in after}


def chunk_digest(cb, param_sds=None, n_steps=None, filtered=False) -> str:
    """The store key of a chunk variant, from abstract arguments as
    ``chunk_warmer`` describes them."""
    prog = cb._chunk_prog
    tok = jax.ShapeDtypeStruct((cb.max_slots, 1), jnp.int32)
    key, _ = prog._key((param_sds or cb.server._param_sds, cb.kv.abstract_state(), tok,
                        *cb._chunk_args(filtered)), {"n_steps": n_steps or cb.chunk_size})
    return prog._digest(key)


def unallocated(server, **kw) -> ContinuousBatcher:
    return ContinuousBatcher(server, **{**ENGINE, **kw}, allocate=False)


class TestWarmStart:
    @pytest.mark.parametrize("layout", ["dense", "paged"])
    @pytest.mark.parametrize("samp", [{}, SAMPLED], ids=["greedy", "sampled"])
    @pytest.mark.parametrize("prompts", [PROMPT, BURST], ids=["single", "batched"])
    @pytest.mark.parametrize("new_tokens", [6, 40], ids=["one_depth", "depth_ladder"])
    def test_a_second_engine_loads_what_the_first_built_and_traces_nothing(
            self, server, node, engine_traces, layout, samp, prompts, new_tokens):
        args = dict(ENGINE, page_size=16 if layout == "paged" else 0)
        first = ContinuousBatcher(server, **args)
        try:
            want = first.generate(prompts, max_new_tokens=new_tokens, **samp)
        finally:
            first.close()
        built, stored = list(engine_traces), entries(node)
        assert built and len(stored) == len(built)  # each variant traced once, written once
        before = aot_cache.store_stats()
        second = ContinuousBatcher(server, **args)  # a new pod's engine: its memo is empty
        try:
            got = second.generate(prompts, max_new_tokens=new_tokens, **samp)
        finally:
            second.close()
        np.testing.assert_array_equal(got, want)
        assert engine_traces == built, "a program this node had run before was traced again"
        moved = growth(before)
        assert moved["store_hits"] == len(stored) and moved["store_misses"] == 0
        assert moved["store_bytes"] == sum(
            os.path.getsize(os.path.join(node, "programs", e)) for e in stored)
        assert entries(node) == stored

    def test_prefix_cache_prefill_chunk_and_speculation_go_through_the_store(
            self, server, node, engine_traces):
        """The programs no benchmark cell runs: cached admit, seed, snap,
        piece, flip and the speculative verify step."""
        from modelx_tpu.models.decode import PrefixKVCache

        long = np.arange(1, 41, dtype=np.int32)[None, :] % 60 + 1
        turn2 = np.concatenate([long, np.array([[7, 3, 9]], np.int32)], axis=1)

        def serve():
            outs = []
            for kw in (dict(prefix_cache=PrefixKVCache(8)),
                       dict(prefix_cache=PrefixKVCache(8), prefill_chunk=16),
                       dict(speculative_k=3)):
                cb = ContinuousBatcher(server, **ENGINE, **kw)
                try:
                    outs += [cb.generate(long, max_new_tokens=5),
                             cb.generate(turn2, max_new_tokens=5)]
                finally:
                    cb.close()
            return outs

        want = serve()
        built = list(engine_traces)
        assert {"_admit_cached_impl", "_piece_impl", "_piece_flip_impl", "_snap_impl",
                "_spec_verify_impl"} <= set(built)
        before = aot_cache.store_stats()
        for got, ref in zip(serve(), want):
            np.testing.assert_array_equal(got, ref)
        assert engine_traces == built
        assert growth(before)["store_misses"] == 0

    def test_the_donated_state_is_consumed_by_a_stored_program(self, server, node):
        for _ in range(2):  # the second engine's programs come from the store
            cb = ContinuousBatcher(server, **ENGINE)
            try:
                state = [*jax.tree_util.tree_leaves(cb._cache), cb._tok]
                before = aot_cache.store_stats()
                cb.generate(PROMPT, max_new_tokens=6)
            finally:
                cb.close()
            assert all(x.is_deleted() for x in state)
        assert growth(before)["store_hits"] >= 2

    def test_without_a_compile_cache_there_is_no_store(self, server, tmp_path, monkeypatch):
        monkeypatch.setattr(serve_mod, "_compile_cache_dir", "")
        monkeypatch.chdir(tmp_path)
        before = aot_cache.store_stats()
        cb = ContinuousBatcher(server, **ENGINE)
        try:
            out = cb.generate(PROMPT, max_new_tokens=6)
            assert cb._chunk_prog.store.dir == ""
        finally:
            cb.close()
        np.testing.assert_array_equal(out, server.generate(PROMPT, max_new_tokens=6))
        assert not any(growth(before).values())
        assert not any("programs" in dirs for _, dirs, _ in os.walk(tmp_path))


class TestKey:
    def test_everything_that_shapes_a_program_changes_its_key(self, server, monkeypatch):
        """A miss, never a stale hit: each of these differs from the base in
        one thing, and all keys differ from one another."""
        from jax.sharding import NamedSharding, PartitionSpec

        sds = server._param_sds
        name = "model.layers.0.mlp.up_proj.weight"
        mesh2 = jax.make_mesh((2,), ("tp",), devices=jax.devices()[:2]) \
            if len(jax.devices()) >= 2 else None
        other = copy.copy(server)
        other.cfg = dataclasses.replace(server.cfg, rope_theta=10000.0)
        engines = {"base": unallocated(server),
                   "max_slots": unallocated(server, max_slots=2),
                   "max_len": unallocated(server, max_len=64),
                   "chunk_size": unallocated(server, chunk_size=8),
                   "paged": unallocated(server, page_size=16),
                   "prefix_cache": unallocated(server, prefix_cache=object()),
                   "cfg": unallocated(other)}
        try:
            base = engines["base"]
            keys = {k: chunk_digest(cb, n_steps=8) for k, cb in engines.items()}
            keys["n_steps"] = chunk_digest(base, n_steps=16)
            keys["filters"] = chunk_digest(base, n_steps=8, filtered=True)
            for what, leaf in (
                    ("param_dtype", jax.ShapeDtypeStruct(sds[name].shape, jnp.bfloat16)),
                    ("param_sharding", mesh2 and jax.ShapeDtypeStruct(
                        sds[name].shape, sds[name].dtype,
                        sharding=NamedSharding(mesh2, PartitionSpec("tp"))))):
                if leaf is None:
                    continue
                changed = copy.copy(server)
                changed._param_sds = {**sds, name: leaf}
                engines[what] = unallocated(changed)
                keys[what] = chunk_digest(engines[what], n_steps=8)
            monkeypatch.setattr(aot_cache, "_code_version", "another source tree")
            engines["source"] = unallocated(server)
            keys["source"] = chunk_digest(engines["source"], n_steps=8)
            monkeypatch.undo()
            engines["again"] = unallocated(server)
            assert chunk_digest(engines["again"], n_steps=8) == keys["base"]
            assert len(set(keys.values())) == len(keys), keys
            # two programs of one engine never share an entry
            assert base._admit_prog.name != base._admit_many_prog.name
        finally:
            for cb in engines.values():
                cb.close()

    def test_a_committed_argument_and_one_jax_may_place_are_two_variants(self, server):
        """As for jax.jit: an array committed to a sharding lowers with it."""
        cb = unallocated(server)
        try:
            placed = jnp.zeros((cb.max_slots, 1), jnp.int32)
            committed = jax.device_put(placed, jax.devices()[0])
            keys = [cb._chunk_prog._key((None, None, tok), {"n_steps": 4})[0]
                    for tok in (placed, committed,
                                jax.ShapeDtypeStruct(placed.shape, placed.dtype),
                                jax.ShapeDtypeStruct(placed.shape, placed.dtype,
                                                     sharding=committed.sharding))]
            digests = [cb._chunk_prog._digest(k) for k in keys]
            assert digests[0] == digests[2] != digests[1] == digests[3]
        finally:
            cb.close()

    def test_the_key_is_equal_in_two_interpreters(self, model_dir):
        code = (
            "import json, sys\n"
            "import jax, jax.numpy as jnp\n"
            "from modelx_tpu.dl.continuous import ContinuousBatcher\n"
            "from modelx_tpu.dl.serve import ModelServer\n"
            "server = ModelServer(sys.argv[1], mesh_spec='dp=2,tp=2', dtype='float32',\n"
            "                     max_seq_len=96)\n"
            "server.load()\n"
            "cb = ContinuousBatcher(server, max_slots=4, chunk_size=4)\n"
            "seen = {}\n"
            "for prog in (cb._chunk_prog, cb._admit_prog):\n"
            "    digest = prog._digest\n"
            "    prog._digest = lambda key, d=digest, n=prog.name: seen.setdefault(n, d(key))\n"
            "cb.generate(jnp.array([[5, 9, 2, 7, 11]]), max_new_tokens=3)\n"
            "cb.close()\n"
            "print(json.dumps(seen))\n")
        outs = []
        for hash_seed in ("1", "2"):
            env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=hash_seed,
                       PYTHONPATH=ROOT,
                       XLA_FLAGS="--xla_force_host_platform_device_count=8")
            p = subprocess.run([sys.executable, "-c", code, model_dir], env=env,
                               capture_output=True, text=True, timeout=300)
            assert p.returncode == 0, p.stderr[-2000:]
            outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
        assert outs[0] == outs[1] and set(outs[0]) == {"chunk", "admit"}


class TestNeverLoadBearing:
    @pytest.mark.parametrize("damage", ["truncated", "garbage"])
    def test_a_damaged_entry_is_rebuilt_logged_and_replaced(
            self, server, node, engine_traces, caplog, damage):
        cb = ContinuousBatcher(server, **ENGINE)
        try:
            want = cb.generate(PROMPT, max_new_tokens=6)
        finally:
            cb.close()
        built, stored = len(engine_traces), entries(node)
        for e in stored:
            path = os.path.join(node, "programs", e)
            with open(path, "r+b") as f:
                if damage == "truncated":
                    f.truncate(os.path.getsize(path) // 2)
                else:
                    f.write(b"\x00not a pickle" * 8)
        before = aot_cache.store_stats()
        with caplog.at_level(logging.WARNING, logger="modelx.aot"):
            cb = ContinuousBatcher(server, **ENGINE)
            try:
                got = cb.generate(PROMPT, max_new_tokens=6)
            finally:
                cb.close()
        np.testing.assert_array_equal(got, want)
        assert len(engine_traces) == 2 * built  # every program was built again
        assert growth(before)["store_misses"] == len(stored)
        assert sum("unusable" in r.getMessage() for r in caplog.records) == len(stored)
        assert entries(node) == stored  # ... and whole again: the next pod loads them
        before = aot_cache.store_stats()
        cb = ContinuousBatcher(server, **ENGINE)
        try:
            np.testing.assert_array_equal(cb.generate(PROMPT, max_new_tokens=6), want)
        finally:
            cb.close()
        assert growth(before)["store_hits"] == len(stored)

    def test_a_stored_program_that_refuses_its_arguments_falls_back_undonated(
            self, server, node, engine_traces, caplog):
        """The weights the loader delivered are not the ones the abstract
        description promised: the stored executable refuses them before it
        runs or donates, its entry goes, and the jit serves the request."""
        described = copy.copy(server)
        described._param_sds = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
                                for k, v in server._param_sds.items()}
        cb = ContinuousBatcher(described, **ENGINE)
        try:
            assert cb.chunk_warmer(described._param_sds)() == 1  # written as described
            stored = entries(node)
            assert len(stored) == 1 and stored[0].startswith("chunk-")
        finally:
            cb.close()
        cb = ContinuousBatcher(described, **ENGINE)
        try:
            state, seen = [*jax.tree_util.tree_leaves(cb._cache)], []
            jit = cb._chunk_prog.jit
            cb._chunk_prog.jit = lambda *a, **kw: (
                seen.append([x.is_deleted() for x in jax.tree_util.tree_leaves(a[1])]),
                jit(*a, **kw))[1]
            before = aot_cache.store_stats()
            with caplog.at_level(logging.WARNING, logger="modelx.aot"):
                out = cb.generate(PROMPT, max_new_tokens=3)
        finally:
            cb.close()
        np.testing.assert_array_equal(out, server.generate(PROMPT, max_new_tokens=3))
        assert growth(before)["store_hits"] == 1  # it was loaded, then refused
        assert seen and not any(seen[0]), "the refused call had donated the cache"
        assert any("refused its arguments" in r.getMessage() for r in caplog.records)
        assert not [e for e in entries(node) if e.startswith("chunk-")]
        assert all(x.is_deleted() for x in state)  # ... and the jit's call did donate

    def test_two_threads_writing_one_key_leave_one_valid_file(self, server, node):
        cb = unallocated(server)
        try:
            store = cb._chunk_prog.store
        finally:
            cb.close()
        double = jax.jit(lambda x: x * 2)
        x = jnp.arange(8.0)
        compiled = double.lower(x).compile()
        start = threading.Barrier(16)

        def write():
            start.wait(10)
            for _ in range(20):
                store.save("double", "k" * 32, compiled)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=write, daemon=True) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
        assert entries(node) == ["double-" + "k" * 32 + ".bin"]  # and no temp file
        loaded = store.load("double", "k" * 32)
        np.testing.assert_array_equal(loaded(x), np.arange(8.0) * 2)

    def test_a_call_that_comes_first_waits_for_the_prefetch(self, server, node, engine_traces):
        cb = ContinuousBatcher(server, **ENGINE)
        try:
            fetch = cb.chunk_warmer(server._param_sds)
            assert cb.chunk_warmer(server._param_sds)() == 0  # reserved once
            result = {}
            t = threading.Thread(
                target=lambda: result.update(out=cb.generate(PROMPT, max_new_tokens=3)),
                daemon=True)
            t.start()
            t.join(2.0)
            assert t.is_alive() and "_chunk_impl" not in engine_traces
            assert fetch() == 1
            t.join(60)
            assert not t.is_alive() and engine_traces.count("_chunk_impl") == 1
        finally:
            cb.close()


class TestMetrics:
    def test_metrics_compile_cache_reports_the_store(self, node):
        stats = serve_mod.compile_cache_stats()
        assert {"store_hits", "store_misses", "store_load_s", "store_write_s",
                "store_bytes"} <= set(stats)
        assert {"requests", "hits", "misses", "trace_s", "programs"} <= set(stats)

    def test_loads_and_builds_are_spans_that_name_the_program(self, server, node):
        def count(name):
            return sum(v["count"] for k, v in trace.tracer().summary().items()
                       if k.split("/")[-1] == name)

        loads, builds = count("programs.load"), count("programs.build")
        for _ in range(2):
            cb = ContinuousBatcher(server, **ENGINE)
            try:
                cb.generate(PROMPT, max_new_tokens=3)
            finally:
                cb.close()
        n = len(entries(node))
        assert count("programs.build") - builds == n
        assert count("programs.load") - loads == n


class TestLoadInThreeParts:
    """A stored program's load is its file read, the unpickling and PJRT's
    deserialize-and-load (ISSUE 40): three child spans, two counters beside
    ``store_load_s``, and the bytes read."""

    PARTS = ("store_load_s", "store_read_s", "store_deserialize_s")

    @staticmethod
    def spans() -> dict:
        agg = {}
        for path, row in trace.tracer().summary().items():
            for tail in ("programs.load", "programs.load/read", "programs.load/unpickle",
                         "programs.load/deserialize"):
                if path.endswith(tail):
                    cur = agg.setdefault(tail, {"count": 0, "total_s": 0.0})
                    cur["count"] += row["count"]
                    cur["total_s"] += row["total_s"]
        return agg

    @pytest.fixture
    def stored(self, server, node):
        cb = ContinuousBatcher(server, **ENGINE)
        try:
            cb.generate(PROMPT, max_new_tokens=6)
        finally:
            cb.close()
        return entries(node)

    def test_read_unpickle_and_deserialize_sum_to_the_load_on_a_hit(self, server, node, stored):
        before, spans0 = aot_cache.store_stats(), self.spans()
        cb = ContinuousBatcher(server, **ENGINE)
        try:
            cb.generate(PROMPT, max_new_tokens=6)
        finally:
            cb.close()
        moved, spans = growth(before), self.spans()
        assert moved["store_hits"] == len(stored) and moved["store_misses"] == 0
        grew = {k: spans[k]["total_s"] - spans0.get(k, {"total_s": 0.0})["total_s"] for k in spans}
        for tail in grew:  # one of each a hit
            assert spans[tail]["count"] - spans0.get(tail, {"count": 0})["count"] == len(stored)
        assert all(moved[k] > 0 for k in self.PARTS)
        unpickle = grew["programs.load/unpickle"]
        # the counters are cut at three clock reads, so only the spans' own
        # entry and exit lie between them and the unpickle span
        assert moved["store_read_s"] + unpickle + moved["store_deserialize_s"] == pytest.approx(
            moved["store_load_s"], rel=0.01, abs=1e-3 * len(stored))
        assert moved["store_read_s"] + moved["store_deserialize_s"] <= moved["store_load_s"]
        size = sum(os.path.getsize(os.path.join(node, "programs", e)) for e in stored)
        assert moved["store_bytes_read"] == moved["store_bytes"] == size

    def test_a_torn_entry_is_one_miss_and_leaves_the_three_unchanged(self, server, node, stored):
        for e in stored:
            path = os.path.join(node, "programs", e)
            with open(path, "r+b") as f:
                f.truncate(os.path.getsize(path) // 2)
        before = aot_cache.store_stats()
        cb = ContinuousBatcher(server, **ENGINE)
        try:
            cb.generate(PROMPT, max_new_tokens=6)
        finally:
            cb.close()
        moved = growth(before)
        assert moved["store_misses"] == len(stored) and moved["store_hits"] == 0
        assert all(moved[k] == 0 for k in self.PARTS)
        assert moved["store_bytes_read"] == 0 and moved["store_bytes"] > 0  # written again

    def test_a_lookup_that_finds_no_file_is_a_miss_without_a_span(self, server, node):
        before, spans0 = aot_cache.store_stats(), self.spans()
        store = aot_cache.ExecutableStore(node, server.mesh, "ctx")
        assert store.load("chunk", "0" * 32) is None
        assert growth(before)["store_misses"] == 1
        assert self.spans() == spans0


class TestLayerMetricFiles:
    """The three per-layer metrics this PR adds are data for a reader the
    benchmark already had: on a pod's /metrics dumps they read the store's
    counters, and on a parent's, which has none, they say nothing."""

    NAMES = ("cache.store_hit_share.decode", "cache.store_load_s_per_program.decode",
             "cache.store_load_s_per_program")

    @staticmethod
    def spec(name):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            return json.load(f)

    @classmethod
    def read(cls, name, sources):
        import importlib

        spec = cls.spec(name)
        reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
        return reader.read(sources, spec)

    @pytest.mark.parametrize("name, want", [
        ("cache.store_hit_share.decode", 0.96),
        ("cache.store_load_s_per_program.decode", 0.5),
        ("cache.store_load_s_per_program", 0.25),
    ])
    def test_reads_the_pods_dumps(self, name, want):
        warm = {"compile_cache": {"store_hits": 48, "store_misses": 2, "store_load_s": 24.0}}
        sources = {"metrics_before": warm, "trace_span": {
            "metrics_before": {"compile_cache": {"store_hits": 1, "store_misses": 0,
                                                 "store_load_s": 9.0}},
            "metrics_after": {"compile_cache": {"store_hits": 3, "store_misses": 0,
                                                "store_load_s": 9.5}}}}
        assert self.read(name, sources) == pytest.approx(want)

    @pytest.mark.parametrize("name", NAMES)
    def test_a_parent_without_the_counters_reads_nothing(self, name):
        parent = {"compile_cache": {"requests": 48, "hits": 48, "trace_s": 12.0}}
        sources = {"metrics_before": parent, "metrics_after": parent,
                   "trace_span": {"metrics_before": parent, "metrics_after": parent}}
        assert self.read(name, sources) is None
        assert self.read(name, {}) is None

    def test_benchmark_json_lists_them_at_the_end_for_their_cells(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            per_layer = json.load(f)["per_layer"]
        # found by name and cell, never by place: the table may be merged and reordered
        by_name = {m["name"]: m for m in per_layer}
        assert len(by_name) == len(per_layer)
        mine = [by_name[name] for name in self.NAMES]
        share, load_decode, load_deploy = mine
        assert all(m["layer"] == "Compile caches" and m["source"] == "program_counter"
                   for m in mine)
        assert all(self.spec(name)["reader"] == "metrics_path" for name in self.NAMES)
        assert (share["moves"], share["better"], share["unit"]) == ("setup_s", "higher", "ratio")
        assert (load_decode["moves"], load_decode["better"]) == ("setup_s", "lower")
        assert "mixtral-8x7b-d4.decode" in share["workloads"]
        assert "mixtral-8x7b-d4.decode" in load_decode["workloads"]
        assert load_deploy["moves"] == "pod_listen_ttft_s"
        assert "phi3-mini-4k.deploy" in load_deploy["workloads"]


# -- the other families' programs are the parent's (PR 43) ---------------------------

# sha-256 of the lowered text of each family's engine programs at its tiny preset on the
# CPU, taken on the parent commit of PR 43 (a705d9f) by this same code: what the change
# shares with them (``ops/moe.moe_share_ffn``'s third scope and ``groups``, YaRN lifted
# into ``ops/rope``, the ragged decode kernel's ``value_lanes``, ``kv_layout``'s new leaf
# kind) must leave them byte for byte. A jax upgrade changes the text too: then take the
# table anew on a checkout of the commit before it. PR 44 (the expert layer's decode step
# reads only the hit experts, a fourth counter beside the three) leaves the table's three
# families as they stand and takes Laguna's two entries out: its programs carry the new
# counter, and are held to the parent's expert layer line for line below instead.
PARENT_PROGRAMS = {
    "llama.chunk": "5856132ebf1b5af530ef582acf5631631bbdc7a9a20762dba84ccbf434e36793",
    "llama.admit": "66c126a4c51da1404639bb4d97da6181814cef4298958db4244315827025f764",
    "llama.piece": "c862c3c73c093888ab865c00577bbc7d88f11f489d50ec87312569556eed74fe",
    "mixtral.chunk": "f0b8f15b06fb6dd7111eeac9e5c2e29724792f6053e86dd34d2d9a7c3d505558",
    "mixtral.admit": "4742a17bb4e300902560f2726f0aad29a0ae368dcd9c894a7021771d1ddfc6ee",
    "mixtral.piece": "d0680983f8ad7fbaedfd19f26bfefd133bdd6478470a27dc2b61256d54218ea2",
    "minicpm_sala.chunk": "64ea55ee3d9fbb83b82171dc550b32793182bfe0aa7ffdbf99691c339a1da1f5",
    "minicpm_sala.admit": "35f181545364bfaab19c9e0d278596198a9823fdb55c9e651084f3a8ac5c3900",
    "minicpm_sala.piece": "0fb1c8cab7f7886f3b7fbad41c46cc35a291368ded4bbe8a2450ecd2774497ee"
}
# PR 48 (a window layer's decode step over its ring takes ``ring_decode_attention`` by
# ``cached_attention``'s rule, which also builds a ring's ``key_positions`` now): the same
# texts taken on ITS parent (41bbc73) for the three families that call ``cached_attention``
# or sit beside it and were not in the table — on the CPU the rule says reference for
# every call, so Laguna's programs too are the parent's, the rings' lines included
PARENT_PROGRAMS.update({
    "laguna.chunk": "7ebf731e20387c12c808d2f5f8d9f86159dae73341f2b23e98a3739ea3f1553c",
    "laguna.admit": "5f7d0d4902bdc463925d3faad3d1429049c3dd28e323d81d2fbc7178966cd8db",
    "deepseek_v2.chunk": "4adc8e5c98608b4b96bd0dabd8f18ea0f93a74d12dab76f358cb41cd99e60d36",
    "deepseek_v2.admit": "0236fc1059a5b59cc7820de04679a031dacbb44df4ac744bfe9333c7557e7683",
    "deepseek_v2.piece": "237e44aa41d333c8ef2b0f02378e94c2e2ec63df9baae26fdd7ce19c68afbd24",
    "nemotron_h.chunk": "33731f75909de194601e995f7f91704b07f30d8f1cf99960fc456e9ca9476d59",
    "nemotron_h.admit": "193f324e542b3f064c2cdfda82185dcb079b16a2199d4789333a563be544de5e",
    "nemotron_h.piece": "38a1bd0cf07ef3251bef61edeb029b49bc6242db560184ca0e291c221c7db483",
})
# PR 49 (every field of a ``Family`` is built from its model module by one adapter in
# ``dl/families.py``; the per-family closures and the modules' generate wrappers went):
# the three families the table left out, taken on ITS parent (9742c99) before the first
# edit — phi3 is the deploy cell's family
PARENT_PROGRAMS.update({
    "phi3.chunk": "42e4f7cc5d75ecda0cdc854c9549f383cfff5c66026591d002f0ce45ef1007b1",
    "phi3.admit": "b1d9d9413dfea319d381e50493457eb8755ebc85d60b540020d12154599f357c",
    "phi3.piece": "6db48b13bee5ae573dddfa31ed60541904d5e36632c73a900adc1e199397706f",
    "gemma2.chunk": "60b785d68f8cbfcaef65dddb6ba3214fdeffce9ad20ee6ac803d6b9832aa3b8d",
    "gemma2.admit": "ad2a1412f6dc9d03a21d1d153b5633a241fab8dd98cb7880d3a8153825729290",
    "gemma2.piece": "1b62535665256298725e5ccc52b8c6b568d2cca7460aeb00f2492f4bab391ea2",
    "gpt2.chunk": "9f9d94e30162a6a868eb44e73bf88847f98dca19ace2edd51fe7221c3c55b439",
    "gpt2.admit": "8f3a159318a95acac721e3dd7161e1224c907e0d9db4918a37697c76937704d2",
    "gpt2.piece": "227ad4135b141c5394221db2e78831e8ab8f5202baaed1d1e50e9a1ba1403457",
})
# PR 51 (V3.2's decode step chooses without a sort where ``index_select.takes_kernel``
# says so, a fifth ``dsa`` counter): V3.2's own three, ``tiny_v32`` in V2's module, taken
# on PR 51's FINAL tree — the parent's decode step counted four — so that the next change
# to the shared module sees when V3.2's text moves, as V2's three above show for V2. On
# the CPU the rule says sort: the step holds ``select_reference``'s ``top_k``, no kernel
PARENT_PROGRAMS.update({
    "deepseek_v32.chunk": "987b4dc2b688efa84aeb6302f7274f0722c71d50c2de3488a3e90cee7c90303c",
    "deepseek_v32.admit": "c24085b90c25f277c976db7641b7103fe0b763307e9ca01389874372a2ecbbd5",
    "deepseek_v32.piece": "1212ecf122e69f3b463d0995b5775c5dc0ba1ea40f9d76a7dba08f06483c04a5",
})
# the ragged decode kernel's own jaxpr (the Mosaic body's source; it names no file) at
# the two decode cells' widths: (rows, query heads, cache length)
PARENT_RAGGED_KERNEL = {
    (32, 32, 2048): "4c68e22feba6bbd98962d59eab53513e16446567c9c8604dbab12f17e52f83e4",
    (64, 48, 4096): "54e39aa294df09bdd78a8b3addfc790d20278cddb0e95aa7d055cb638a72858f",
}


def tiny_family(family: str):
    import importlib

    if family == "deepseek_v32":  # V2's module and row, the indexer and ``noaux_tc`` on
        module = importlib.import_module("modelx_tpu.models.deepseek_v2")
        return module, module.DeepseekV2Config.tiny_v32(vocab_size=64)
    module = importlib.import_module("modelx_tpu.models." + family)
    name = {"llama": "LlamaConfig", "mixtral": "MixtralConfig", "laguna": "LagunaConfig",
            "minicpm_sala": "SalaConfig", "deepseek_v2": "DeepseekV2Config",
            "nemotron_h": "NemotronHConfig", "phi3": "LlamaConfig", "gemma2": "Gemma2Config",
            "gpt2": "GPT2Config", "mimo_v2": "MimoV2Config"}[family]
    # phi3 is llama's decoder under fused weights (its config is llama's, which its
    # module names too); gpt2's tiny preset has one vocabulary
    return module, getattr(module, name).tiny(**({} if family == "gpt2" else {"vocab_size": 64}))


def lowered_programs(family: str) -> dict:
    """``{family.chunk, .admit[, .piece]: lowered text}`` of a NEW engine over
    the family's tiny preset on the CPU (new jits: nothing traced before is
    reused)."""
    import types

    from modelx_tpu.dl.families import FAMILIES
    from modelx_tpu.parallel.mesh import make_mesh

    module, cfg = tiny_family(family)
    params = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                                    module.init_params(cfg, jax.random.PRNGKey(0)))
    server = types.SimpleNamespace(
        family=FAMILIES[module.__name__.rpartition(".")[2]], cfg=cfg,
        mesh=make_mesh("dp=1", jax.devices()[:1]),
        params=params, max_seq_len=64, stats={})
    # laguna's two hashes were taken without a piece program (a ring took none before PR 54)
    pieces = {"prefill_chunk": 16} if family != "laguna" else {}
    engine = ContinuousBatcher(server, max_slots=4, chunk_size=4, max_len=64, allocate=False,
                               supervise=False, **pieces)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    try:
        state, tok = engine.kv.abstract_state(), i32(4, 1)
        got = {family + ".chunk": engine._chunk_prog.jit.lower(
            params, state, tok, *engine._chunk_args(False), n_steps=4).as_text()}
        got[family + ".admit"] = jax.jit(engine._admit_impl).lower(
            params, i32(1, 16), state, tok, i32(1), engine.kv.at(0),
            jax.ShapeDtypeStruct((1,), jnp.float32), None, None, i32(1), i32(1)).as_text()
        if pieces:
            got[family + ".piece"] = jax.jit(engine._piece_impl).lower(
                params, i32(1, 16), state, i32(), engine.kv.at(0, 16, 16)).as_text()
    finally:
        engine.close()
    return got


@pytest.mark.parametrize("family", ["llama", "mixtral", "minicpm_sala", "laguna", "deepseek_v2",
                                    "deepseek_v32", "nemotron_h", "phi3", "gemma2", "gpt2"])
def test_the_other_families_programs_lower_to_the_parents_text(family):
    import hashlib

    got = {k: hashlib.sha256(v.encode()).hexdigest() for k, v in lowered_programs(family).items()}
    assert got == {k: v for k, v in PARENT_PROGRAMS.items() if k.startswith(family + ".")}


@pytest.mark.parametrize("family,programs", [("laguna", 2), ("deepseek_v2", 3)])
def test_the_expert_families_programs_are_the_parents_where_the_rule_says_einsum(
        monkeypatch, family, programs):
    """PR 44: on the CPU ``ops.moe.lowering`` answers "einsum" for every call,
    and the two families' chunk, admit and piece programs then lower to the
    text they lower to with the parent's expert layer in ``moe_share_ffn``'s
    place (``test_moe_share.parents_layer``: the parent's lines and the fourth
    counter) — operation for operation, the decode step's included."""
    from test_moe_share import parents_layer

    from modelx_tpu.ops import moe

    got = lowered_programs(family)
    assert len(got) == programs and all("pallas" not in text for text in got.values())
    monkeypatch.setattr(moe, "moe_share_ffn", parents_layer)
    assert lowered_programs(family) == got


@pytest.mark.parametrize("shape", sorted(PARENT_RAGGED_KERNEL))
def test_the_ragged_decode_kernel_traces_to_the_parents_jaxpr(shape):
    import hashlib

    from modelx_tpu.ops import attention as attn

    rows, heads, length = shape
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    kv = sds((rows, length, 8, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v, n: attn.decode_attention(q, k, v, n))(
        sds((rows, 1, heads, 128), jnp.bfloat16), kv, kv, sds((rows,), jnp.int32))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() == PARENT_RAGGED_KERNEL[shape]
