"""bench.py smoke: it runs unattended on real hardware — import errors,
signature drift between bench and the library, or a broken checkpoint
builder must fail HERE, in CI, not there."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest


class TestWaitForDevice:
    """wait_for_device: ONE fail-fast probe in a short-lived subprocess — it
    returns the device as jax reports it, refuses a cpu backend, and raises
    (not hangs, not retries) when the probe fails or hangs."""

    def test_returns_device_when_probe_succeeds(self, monkeypatch):
        import bench

        calls = []

        def fake_run(cmd, **kw):
            calls.append(cmd)
            return subprocess.CompletedProcess(
                cmd, 0, stdout='{"platform": "tpu", "kind": "TPU v5 lite", "count": 1}\n',
                stderr="")

        monkeypatch.setattr(bench, "_device_child_env", dict)
        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        dev = bench.wait_for_device(probe_timeout_s=1)
        assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        assert len(calls) == 1
        # the probe itself is what rejects a cpu backend
        assert "platform != 'cpu'" in calls[0][-1]

    def test_raises_at_once_when_probe_hangs(self, monkeypatch):
        import bench

        calls = []

        def fake_run(cmd, **kw):
            calls.append(cmd)
            raise subprocess.TimeoutExpired(cmd, kw.get("timeout", 0))

        monkeypatch.setattr(bench, "_device_child_env", dict)
        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        with pytest.raises(RuntimeError, match="hung"):
            bench.wait_for_device(probe_timeout_s=0.01)
        assert len(calls) == 1  # one probe, no retry loop

    def test_failed_probe_raises_with_its_stderr(self, monkeypatch):
        import bench

        calls = []

        def fake_run(cmd, **kw):
            calls.append(cmd)
            return subprocess.CompletedProcess(
                cmd, 1, stdout="",
                stderr="AssertionError: cpu backend — accelerator not found")

        monkeypatch.setattr(bench, "_device_child_env", dict)
        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        with pytest.raises(RuntimeError, match="accelerator not found"):
            bench.wait_for_device(probe_timeout_s=1)
        assert len(calls) == 1

    def test_real_probe_refuses_the_cpu(self, monkeypatch):
        """No chip here: the real probe subprocess (JAX held to the CPU)
        must be refused — nothing on the bench path records a capture
        without an accelerator."""
        import bench

        monkeypatch.setattr(
            bench, "_device_child_env",
            lambda: dict(os.environ, JAX_PLATFORMS="cpu"))
        with pytest.raises(RuntimeError, match="no accelerator"):
            bench.wait_for_device(probe_timeout_s=120)


class TestNoChipNoNumber:
    def test_chip_spec_raises_on_unknown_kind(self):
        import bench

        assert bench._chip_spec(bench.PEAK_FLOPS, "TPU v5 lite") == 197e12
        assert bench._chip_spec(bench.HBM_GBPS, "TPU v5p chip") == 2765e9
        for kind in ("cpu", "TPU v9", ""):
            with pytest.raises(KeyError, match="no published peak"):
                bench._chip_spec(bench.PEAK_FLOPS, kind)
        assert "cpu" not in bench.PEAK_FLOPS and "cpu" not in bench.HBM_GBPS

    def test_parent_that_touched_jax_cannot_hand_out_the_device(self, monkeypatch):
        """A chip belongs to one process: the stay-off-jax ordering of
        main() is checked where every device child gets its environment."""
        import types

        import bench

        fake_sys = types.SimpleNamespace(modules={"jax": object()})
        monkeypatch.setattr(bench, "sys", fake_sys)
        with pytest.raises(RuntimeError, match="imported jax"):
            bench._device_child_env()
        fake_sys.modules = {}
        env = bench._device_child_env()
        assert "JAX_PLATFORMS" not in env
        assert os.path.dirname(os.path.abspath(bench.__file__)) in env["PYTHONPATH"]

    @pytest.mark.parametrize("fail_probe", [True, False])
    def test_capture_with_a_failed_leg_exits_nonzero(self, monkeypatch, capsys,
                                                     fail_probe):
        """A capture with leg_errors still prints its one JSON line — and
        then exits non-zero: with no accelerator (the probe refuses), or
        with one and a stage that dies."""
        import bench

        def probe():
            if fail_probe:
                raise RuntimeError("no accelerator")
            return {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

        def dead_stage(*a, **kw):
            raise RuntimeError("stage died")

        monkeypatch.setattr(bench, "wait_for_device", probe)
        monkeypatch.setattr(bench, "build_checkpoint", dead_stage)
        rc = bench.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 1
        assert ("no accelerator" if fail_probe else "stage died") in out["leg_errors"]["fatal"]
        assert out["value"] is None and ("device" in out) is (not fail_probe)


class TestBenchSmoke:
    def test_checkpoint_builder_and_loader_roundtrip(self, tmp_path):
        import jax

        from bench import build_checkpoint
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors
        from modelx_tpu.dl.sharding import LLAMA_RULES
        from modelx_tpu.parallel.mesh import make_mesh

        ckpt = str(tmp_path / "m.safetensors")
        target = 1 << 20
        size = build_checkpoint(ckpt, target, hidden=64, inter=128, vocab=256)
        # independent checks: roughly the asked-for size (base tensors can
        # exceed a tiny target, never 4x it at these shapes), real layers
        assert 0 < size < 4 * target
        src = LocalFileSource(ckpt)
        try:
            arrays, stats = load_safetensors(src, make_mesh("dp=1"), LLAMA_RULES)
        finally:
            src.close()
        assert stats.tensors == len(arrays) > 0
        assert "model.layers.0.self_attn.q_proj.weight" in arrays
        jax.block_until_ready(arrays)

    # ~11 s; the subprocess roundtrip + schema tests catch bench drift in
    # tier-1, the full engine drive rides the slow set
    @pytest.mark.slow
    def test_measure_continuous_signature(self):
        """measure_continuous drives the engine through the same shim the
        bench uses — catches ContinuousBatcher API drift."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        import bench
        from modelx_tpu.models import llama
        from modelx_tpu.parallel.mesh import make_mesh

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        out = bench.measure_continuous(params, make_mesh("dp=1"), 100.0)
        assert out["continuous_clients"] == 8
        assert out["continuous_agg_tokens_per_s"] > 0
        assert out["continuous_vs_sequential"] > 0
        # the in-engine speculation leg: device-steps/token on a
        # self-repeating continuation, < 1.0 when acceptance works
        assert out["continuous_spec_device_steps"] > 0
        assert out["continuous_spec_steps_per_token"] < 1.0, out

    @pytest.mark.slow
    def test_measure_mixed_prefill_schema(self):
        """The mixed prefill/decode leg (chunked-prefill acceptance):
        tiny traffic, but the full two-scenario harness — schema-checks
        the load-bearing JSON keys and that the chunked scenario actually
        chunked. Long: two engines decode a saturated batch each."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        import bench
        from modelx_tpu.models import llama
        from modelx_tpu.parallel.mesh import make_mesh

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        out = bench.measure_mixed_prefill(
            params, make_mesh("dp=1"), slots=4, chunk=4, prefill_chunk=16,
            decode_prompt=16, decode_new=48, long_prompt=48, long_new=8,
            max_len=160,
        )
        for key in ("itl_p99_ms_mixed", "itl_p99_ms_mixed_baseline",
                    "itl_p99_ms_idle", "admission_stall_ms_max",
                    "admission_stall_ms_max_baseline", "mixed_prefill_pieces"):
            assert key in out, key
        assert out["mixed_prefill_pieces"] >= 3  # the long prompt chunked
        assert out["itl_p99_ms_mixed"] is None or out["itl_p99_ms_mixed"] > 0

    def test_pull_snippets_run(self, tmp_path):
        """The stdlib-only multitenant pullers must keep working against a
        live registry (they run as bare -S subprocesses in the bench)."""
        from bench import _PULL_SNIPPET
        from modelx_tpu.client.client import Client
        from modelx_tpu.client.helper import descriptor_for_file
        from modelx_tpu.registry.fs import LocalFSProvider
        from modelx_tpu.registry.server import Options, RegistryServer, free_port
        from modelx_tpu.registry.store_fs import FSRegistryStore
        from modelx_tpu.types import Manifest

        blob = tmp_path / "blob.bin"
        blob.write_bytes(np.arange(65536, dtype=np.uint8).tobytes())
        srv = RegistryServer(
            Options(listen=f"127.0.0.1:{free_port()}"),
            store=FSRegistryStore(LocalFSProvider(str(tmp_path / "store"))),
        )
        base = srv.serve_background()
        try:
            client = Client(base, quiet=True)
            desc = descriptor_for_file(str(blob), "blob.bin", "application/octet-stream")
            with open(blob, "rb") as f:
                client.remote.upload_blob_content("library/smoke", desc, f)
            client.remote.put_manifest("library/smoke", "v1", Manifest(blobs=[desc]))
            url = f"{base}/library/smoke/blobs/{desc.digest}"
            p = subprocess.run(
                [sys.executable, "-S", "-c", _PULL_SNIPPET, url],
                capture_output=True, text=True, timeout=60,
                env={"PATH": os.environ.get("PATH", "")},
            )
            assert p.returncode == 0, p.stderr[-500:]
            assert int(p.stdout.split()[1]) == blob.stat().st_size
        finally:
            srv.shutdown()

    def test_leg_subprocess_roundtrip(self, tmp_path):
        """The timed legs run as `bench.py --leg <kind>` children; each
        must load against a live registry and print one JSON line with the
        fields the parent consumes (CPU backend here). Only cold -> warm is
        ordered: the warm blob-cache leg consumes the cache the cold leg
        admitted, and must report a warm hit (zero network reads — its
        source is the cache's LocalFileSource). The rest run side by side
        (each child pays its own interpreter + jax start)."""
        from concurrent.futures import ThreadPoolExecutor

        from bench import build_checkpoint, push_checkpoint, start_registry

        import shutil

        workdir = str(tmp_path)
        ckpt = os.path.join(workdir, "model.safetensors")
        build_checkpoint(ckpt, 1 << 20, hidden=64, inter=128, vocab=256)
        srv, base = start_registry(workdir)
        try:
            push_checkpoint(base, "library/smoke", ckpt)
            here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            env = dict(os.environ, PYTHONPATH=here, JAX_PLATFORMS="cpu")
            fields = {
                "ours": ("seconds", "source", "fetch_width", "bytes_to_device",
                         "link_gbps", "overlap_seconds", "staging_allocs"),
                "baseline": ("seconds", "link_gbps"),
                "int8": ("seconds", "bytes_to_device"),
                "cold": ("seconds", "cache_state", "blob_cache",
                         "overlap_seconds", "staging_allocs"),
                "warm": ("seconds", "cache_state", "blob_cache"),
            }

            def legs(*kinds: str) -> dict:
                out = {}
                for kind in kinds:
                    p = subprocess.run(
                        [sys.executable, os.path.join(here, "bench.py"),
                         "--leg", kind, base, "library/smoke", workdir],
                        capture_output=True, text=True, timeout=300, env=env,
                    )
                    assert p.returncode == 0, f"{kind}: {p.stderr[-1000:]}"
                    rec = json.loads(p.stdout.strip().splitlines()[-1])
                    for f in fields[kind]:
                        assert f in rec, (kind, f, rec)
                    assert rec["seconds"] > 0
                    out[kind] = rec
                return out

            recs = {}
            with ThreadPoolExecutor(4) as pool:
                for got in pool.map(lambda ks: legs(*ks), [
                        ("ours",), ("baseline",), ("int8",), ("cold", "warm")]):
                    recs.update(got)
            # the cold leg streamed the network source and admitted the blob
            assert recs["cold"]["cache_state"] == "cold"
            assert recs["cold"]["blob_cache"]["admitted"] == 1
            # the warm leg was served entirely by the cache: LocalFileSource,
            # zero network reads
            assert recs["warm"]["cache_state"] == "warm"
            assert recs["warm"]["source"] == "LocalFileSource"
            assert recs["warm"]["blob_cache"]["hits"] == 1
        finally:
            srv.terminate()
            shutil.rmtree(os.path.join(workdir, "registry"), ignore_errors=True)

    def test_cache_split_summary_schema(self):
        """The cold/warm bench fields the driver consumes (ISSUE 1): key
        names are load-bearing — schema-check them in tier-1."""
        from bench import cache_split_summary, ttft_warm_fields

        cold = {"seconds": 2.0, "overlap_seconds": 0.5, "staging_allocs": 12,
                "fetch_growths": 1, "cache_state": "cold"}
        warm = {"seconds": 0.5, "cache_state": "warm"}
        out = cache_split_summary(1 << 30, cold, warm)
        for key in ("registry_to_hbm_warm_gbps", "registry_to_hbm_cold_cached_gbps",
                    "warm_vs_cold", "warm_hit", "warm_seconds",
                    "cold_overlap_seconds", "cold_staging_allocs"):
            assert key in out, key
        assert out["warm_hit"] is True
        assert out["warm_vs_cold"] == pytest.approx(4.0)
        ttft = ttft_warm_fields({"ttft_ms": 120.0, "ttft_weights_ready_ms": 80.0})
        assert ttft == {"ttft_warm_ms": 120.0, "ttft_warm_weights_ready_ms": 80.0}


class TestOverloadLeg:
    @pytest.mark.slow
    @pytest.mark.chaos
    def test_measure_overload_schema(self):
        """The overload/self-healing leg end to end on a tiny model:
        saturating traffic sheds at the bound, a stale queued request
        expires with 504, and the engine recovers from the injected
        dispatch crash — schema-checks the load-bearing JSON keys."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        import bench
        from modelx_tpu.models import llama
        from modelx_tpu.parallel.mesh import make_mesh

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        out = bench.measure_overload(
            params, make_mesh("dp=1"), slots=2, chunk=4, queue_depth=2,
            clients=10, prompt=8, new_tokens=16, max_len=128,
        )
        for key in ("shed_429_count", "deadline_504_count", "recovery_ms",
                    "overload_engine_restarts", "overload_served"):
            assert key in out, key
        assert out["shed_429_count"] >= 1  # saturation actually shed
        assert out["deadline_504_count"] == 1
        assert out["overload_engine_restarts"] >= 1
        assert out["recovery_ms"] is not None and out["recovery_ms"] > 0


class TestSwapLeg:
    @pytest.mark.slow
    def test_measure_model_swap_schema(self, tmp_path):
        """The model-swap leg end to end on tiny models (ISSUE 5): unload
        A / load B through the lifecycle pool under live traffic to C,
        cold then blob-cache-warm — schema-checks the load-bearing JSON
        keys, that traffic never failed, and that the warm swap actually
        hit the cache."""
        import bench
        from modelx_tpu.registry.fs import MemoryFSProvider
        from modelx_tpu.registry.server import (
            Options, RegistryServer, free_port,
        )
        from modelx_tpu.registry.store_fs import FSRegistryStore

        srv = RegistryServer(
            Options(listen=f"127.0.0.1:{free_port()}"),
            store=FSRegistryStore(MemoryFSProvider()),
        )
        base = srv.serve_background()
        try:
            out = bench.measure_model_swap(
                base, str(tmp_path), target_bytes=1,
                hidden=64, inter=176, vocab=256, prompt_len=4, new_tokens=2,
            )
        finally:
            srv.shutdown()
        for key in ("ttft_swap_cold_ms", "ttft_swap_warm_ms",
                    "swap_traffic_served", "swap_traffic_errors",
                    "swap_cache_hits"):
            assert key in out, key
        assert out["ttft_swap_cold_ms"] > 0 and out["ttft_swap_warm_ms"] > 0
        # the uninterrupted-traffic contract: C kept serving throughout
        assert out["swap_traffic_errors"] == 0
        assert out["swap_traffic_served"] >= 1
        # the warm swap was served by the blob cache the cold pull admitted
        assert out["swap_cache_hits"] >= 1


class TestTierSwapLeg:
    @pytest.mark.slow
    def test_measure_tier_swap_schema(self, tmp_path):
        """The tier-swap leg end to end on tiny models (ISSUE 18): cold
        swap-in, host-tier promotion swap-in, forced spill, disk-tier
        promotion swap-in — all under live traffic to C. Schema-checks
        the JSON keys, that the host and disk legs actually hit their
        tiers, and that traffic never failed."""
        import bench
        from modelx_tpu.registry.fs import MemoryFSProvider
        from modelx_tpu.registry.server import (
            Options, RegistryServer, free_port,
        )
        from modelx_tpu.registry.store_fs import FSRegistryStore

        srv = RegistryServer(
            Options(listen=f"127.0.0.1:{free_port()}"),
            store=FSRegistryStore(MemoryFSProvider()),
        )
        base = srv.serve_background()
        try:
            out = bench.measure_tier_swap(
                base, str(tmp_path), target_bytes=1,
                hidden=64, inter=176, vocab=256, prompt_len=4, new_tokens=2,
            )
        finally:
            srv.shutdown()
        for key in ("ttft_swap_cold_ms", "ttft_swap_host_ms",
                    "ttft_swap_disk_ms", "tier_traffic_served",
                    "tier_traffic_errors", "tier_host_hits",
                    "tier_disk_hits", "tier_spills"):
            assert key in out, key
        assert out["ttft_swap_cold_ms"] > 0
        assert out["ttft_swap_host_ms"] > 0
        assert out["ttft_swap_disk_ms"] > 0
        # each promotion leg was served by its tier, not a re-pull
        assert out["tier_host_hits"] == 1
        assert out["tier_disk_hits"] == 1
        assert out["tier_spills"] >= 1
        # the uninterrupted-traffic contract: C kept serving throughout
        assert out["tier_traffic_errors"] == 0
        assert out["tier_traffic_served"] >= 1


class TestRegistryOutageLeg:
    @pytest.mark.slow
    def test_measure_registry_outage_schema(self, tmp_path):
        """The registry-outage leg end to end on tiny models (ISSUE 19):
        own in-process registry, kill switch mid-traffic, offline swap-in
        off the pinned manifest + blob cache, restart, outbox drain.
        Schema-checks the JSON keys and the acceptance contract: zero
        dropped requests, the swap served from the cache ladder, the
        outbox empty after restart."""
        import bench

        out = bench.measure_registry_outage(
            str(tmp_path), target_bytes=1,
            hidden=64, inter=176, vocab=256, prompt_len=4, new_tokens=2,
            clients=2,
        )
        for key in ("outage_dropped_requests", "outage_traffic_served",
                    "swap_offline_ttft_ms", "outage_swap_source",
                    "outage_control_plane_state",
                    "outbox_depth_after_restart", "outbox_drained_total",
                    "outbox_publish_failures"):
            assert key in out, key
        # the acceptance bar: the outage never touched the data path
        assert out["outage_dropped_requests"] == 0
        assert out["outage_traffic_served"] >= 2
        assert out["swap_offline_ttft_ms"] > 0
        # the swap came off the pinned-manifest ladder, not a re-pull
        assert out["outage_swap_source"] == "cache"
        assert out["outage_control_plane_state"] == "offline"
        # the spooled publish survived the outage and landed on restart
        assert out["outbox_depth_after_restart"] == 0
        assert out["outbox_drained_total"] >= 1


class TestKVStoreLeg:
    @pytest.mark.slow
    def test_measure_kv_store_schema(self, tmp_path):
        """The content-addressed prefix-KV leg end to end on a tiny model
        (ISSUE 20): pod 1 publishes its hot-prefix bundle to the registry,
        a FRESH pod 2 installs it at load and serves the warm stream from
        the installed entry — schema-checks the JSON keys and that the
        scored stream really hit the installed KV (the leg raises on a
        vacuous warm number)."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        import bench
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.models import llama
        from modelx_tpu.registry.fs import MemoryFSProvider
        from modelx_tpu.registry.server import (
            Options, RegistryServer, free_port,
        )
        from modelx_tpu.registry.store_fs import FSRegistryStore

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        st.write_safetensors(
            str(tmp_path / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        srv = RegistryServer(
            Options(listen=f"127.0.0.1:{free_port()}"),
            store=FSRegistryStore(MemoryFSProvider()),
        )
        base = srv.serve_background()
        try:
            out = bench.measure_kv_store(
                str(tmp_path), base, dtype="float32", prompt_len=48,
                suffix_len=8, new_tokens=4, max_seq_len=128,
            )
        finally:
            srv.shutdown()
        for key in ("kv_published", "kv_installed", "kv_install_skipped",
                    "kv_hits_installed", "kv_warm_ttft_ms",
                    "kv_cold_ttft_ms", "kv_warm_ttft_ratio"):
            assert key in out, key
        assert out["kv_published"] >= 1
        assert out["kv_installed"] >= 1
        assert out["kv_hits_installed"] >= 1
        assert out["kv_warm_ttft_ms"] > 0 and out["kv_cold_ttft_ms"] > 0
        # the < 0.6 acceptance bar is a hardware number; the CPU smoke
        # only proves the ratio is wired to the two scored streams
        assert out["kv_warm_ttft_ratio"] is not None


class TestFleetLeg:
    @pytest.mark.slow
    def test_measure_fleet_schema(self, tmp_path):
        """The fleet front-door leg end to end on a tiny model (ISSUE 8):
        3 pods behind the router vs one pod direct, repeated-prefix
        conversations, and a pod kill under traffic — schema-checks the
        load-bearing JSON keys and the zero-drop failover contract."""
        import jax
        import numpy as np

        import bench
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        st.write_safetensors(
            str(tmp_path / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        out = bench.measure_fleet(
            str(tmp_path), pods=3, clients=2, requests_per_client=2,
            conversations=3, turns=6, new_tokens=4, max_seq_len=128,
        )
        for key in ("fleet_pods", "fleet_tokens_per_s_direct",
                    "fleet_tokens_per_s_routed", "fleet_throughput_scaling",
                    "fleet_traffic_errors", "sticky_hit_ratio",
                    "failover_recovery_ms", "fleet_dropped_requests",
                    "fleet_failovers", "fair_share_jain_index",
                    "shed_429_count_by_class", "retry_amplification"):
            assert key in out, key
        assert out["fleet_pods"] == 3
        assert out["fleet_traffic_errors"] == 0
        assert out["fleet_throughput_scaling"] is not None
        # repeated-prefix traffic actually stuck (3 convs x 6 turns: 15/18)
        assert out["sticky_hit_ratio"] is not None
        assert out["sticky_hit_ratio"] >= 0.8
        # the kill drill recovered with zero dropped requests
        assert out["failover_recovery_ms"] is not None
        assert out["fleet_dropped_requests"] == 0
        # the fair-share storm (ISSUE 9): one client at 10x the rate of
        # the other converges to ~equal goodput shares through the
        # admission-enabled router (FIFO would read ~0.6), sheds are
        # typed by class, and healthy pods mean no retry amplification
        assert out["fair_share_jain_index"] is not None
        assert out["fair_share_jain_index"] >= 0.9
        assert set(out["shed_429_count_by_class"]) == {"interactive", "batch"}
        assert out["retry_amplification"] is not None
        assert out["retry_amplification"] <= 1.2
        assert out["fleet_failovers"] >= 1


class TestContinuationLeg:
    @pytest.mark.slow
    @pytest.mark.chaos
    def test_measure_continuation_schema(self, tmp_path):
        """The stream-continuation drill end to end on a tiny model
        (ISSUE 12): a seeded mid-stream pod kill behind the router on a
        seeded sampled stream — schema-checks the load-bearing JSON keys
        and the zero-loss contract (``tokens_lost`` == 0, the stream was
        resumed, never severed)."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np

        import bench
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        st.write_safetensors(
            str(tmp_path / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        out = bench.measure_continuation(str(tmp_path), new_tokens=12,
                                         max_seq_len=96)
        for key in ("continuation_clients", "tokens_lost",
                    "streams_continued", "streams_severed",
                    "continuation_gap_ms"):
            assert key in out, key
        # the zero-loss contract: every routed stream reproduced the
        # uninterrupted reference token-exactly through the kill —
        # committed streams via resume, uncommitted ones via plain
        # failover, and NO stream ended with the severed payload
        assert out["continuation_clients"] == 8
        assert out["tokens_lost"] == 0
        assert out["streams_continued"] >= 1
        assert out["streams_severed"] == 0
        # the seeded kill stalls the client for at least its armed 300ms
        assert out["continuation_gap_ms"] is not None
        assert out["continuation_gap_ms"] >= 300


class TestLatencyBreakdownLeg:
    # real continuous pod + compiles: rides the slow set like the other
    # serving-pod bench legs
    @pytest.mark.slow
    def test_measure_latency_breakdown_schema(self, tmp_path):
        """The per-request latency-breakdown micro-leg (ISSUE 13) on a
        tiny model: schema-checks the TTFT split keys and the leg's own
        accounting contract (phase spans cover >= 90% of wall time — the
        leg RAISES below that, so a passing run is the assertion)."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        import bench
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        st.write_safetensors(
            str(tmp_path / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        out = bench.measure_latency_breakdown(str(tmp_path), requests_n=4,
                                              new_tokens=6, max_seq_len=96)
        for key in ("breakdown_requests", "breakdown_coverage_min",
                    "ttft_queue_ms_p50", "ttft_queue_ms_p99",
                    "ttft_compute_ms_p50", "ttft_compute_ms_p99"):
            assert key in out, key
        assert out["breakdown_requests"] == 4
        assert out["breakdown_coverage_min"] >= 0.9
        # compute-side TTFT is real work on every request; queue time may
        # be ~0 on an idle pod but never negative
        assert out["ttft_compute_ms_p50"] > 0
        assert out["ttft_queue_ms_p50"] >= 0
        assert out["ttft_queue_ms_p99"] >= out["ttft_queue_ms_p50"]
        assert out["ttft_compute_ms_p99"] >= out["ttft_compute_ms_p50"]


class TestObsOverheadLeg:
    # two real continuous pods + compiles: slow set, like the other
    # serving-pod bench legs
    @pytest.mark.slow
    def test_measure_obs_overhead_schema(self, tmp_path):
        """The observability-overhead micro-leg (ISSUE 15) on a tiny
        model: schema-checks the on/off wall times, the overhead
        percentage, and the measured-vs-reserved HBM accounting the
        instrumented leg reads off the pod."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        import bench
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        st.write_safetensors(
            str(tmp_path / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        out = bench.measure_obs_overhead(str(tmp_path), clients_n=4,
                                         requests_per_client=2,
                                         new_tokens=4, rounds=2,
                                         max_seq_len=96)
        for key in ("obs_overhead_clients", "obs_on_wall_s",
                    "obs_off_wall_s", "flightrec_overhead_pct",
                    "hbm_measured_vs_reserved_ratio",
                    "hbm_measured_source", "flightrec_events"):
            assert key in out, key
        assert out["obs_overhead_clients"] == 4
        assert out["obs_on_wall_s"] > 0
        assert out["obs_off_wall_s"] > 0
        assert out["flightrec_overhead_pct"] is not None
        # the instrumented leg really recorded engine events
        assert out["flightrec_events"] > 0
        # CPU backend: the census fallback still measures SOMETHING
        assert out["hbm_measured_source"] in ("memory_stats",
                                              "live_buffers")


class TestShardedServingLeg:
    # spawns a fresh 8-forced-host-device child process and compiles two
    # full serving stacks: rides the slow set like the other serving legs
    @pytest.mark.slow
    def test_measure_sharded_serving_schema(self, tmp_path):
        """The tensor-parallel serving leg (ISSUE 16) end to end on a tiny
        model: the forced-host child serves the same checkpoint on dp=1
        and dp=2,tp=2 — schema-checks the JSON keys, the >1-device mesh,
        the per-device throughput ratio, and the dp=1 byte-equality
        verdict the acceptance gate reads."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np

        import bench
        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        st.write_safetensors(
            str(tmp_path / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        out = bench.measure_sharded_serving(str(tmp_path))
        for key in ("sharded_mesh", "sharded_devices",
                    "sharded_tokens_per_s", "sharded_dp1_tokens_per_s",
                    "sharded_per_device_ratio", "sharded_dp1_byte_equal"):
            assert key in out, key
        assert out["sharded_mesh"] == "dp=2,tp=2"
        assert out["sharded_devices"] == 4
        assert out["sharded_tokens_per_s"] > 0
        assert out["sharded_dp1_tokens_per_s"] > 0
        # tp devices all work on every token: the mesh aggregate IS the
        # per-device rate, and the acceptance bar is 0.7x the dp=1 pod
        assert out["sharded_per_device_ratio"] is not None
        # the mesh-aware engine on a single-device mesh must reproduce
        # the legacy serving path byte-for-byte (greedy AND sampled)
        assert out["sharded_dp1_byte_equal"] is True


class TestBenchBudget:
    """The r05-timeout fix (rc 124, nothing recorded): the soft budget
    skips stages that no longer fit — NAMED in timed_out_legs — records
    failed stages in leg_errors, and a partial capture still prints."""

    def test_budget_allows_then_exhausts(self):
        import bench

        b = bench._Budget(3600.0)
        assert b.allows(60.0)
        assert b.remaining() <= 3600.0
        b2 = bench._Budget(0.0)
        assert not b2.allows(1.0)
        assert b2.remaining() <= 0.0

    def test_run_guarded_skips_over_budget_stage(self):
        import bench

        timed_out, errors = [], {}
        out = bench.run_guarded(
            bench._Budget(0.0), "serving", lambda: {"x": 1},
            est_s=10.0, timed_out=timed_out, leg_errors=errors,
        )
        assert out is None
        assert timed_out == ["serving"]
        assert errors == {}

    def test_run_guarded_records_failed_stage_and_continues(self):
        import bench

        timed_out, errors = [], {}

        def boom():
            raise RuntimeError("leg died")

        out = bench.run_guarded(
            bench._Budget(3600.0), "multitenant", boom,
            est_s=1.0, timed_out=timed_out, leg_errors=errors,
        )
        assert out is None
        assert timed_out == []
        assert "multitenant" in errors and "leg died" in errors["multitenant"]

    def test_run_guarded_passes_result_through(self):
        import bench

        timed_out, errors = [], {}
        out = bench.run_guarded(
            bench._Budget(3600.0), "ttft", lambda: {"ttft_ms": 5.0},
            est_s=1.0, timed_out=timed_out, leg_errors=errors,
        )
        assert out == {"ttft_ms": 5.0}
        assert timed_out == [] and errors == {}


class TestPipelinedLeg:
    @pytest.mark.slow
    def test_measure_decode_pipelined_schema(self):
        """The pipelined-dispatch leg end to end on a tiny model: serial
        vs dispatch-ahead engines over identical traffic — schema-checks
        the load-bearing JSON keys and the structural win (fewer device
        dispatches for the same tokens, depth > 1 actually used)."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        import bench
        from modelx_tpu.models import llama
        from modelx_tpu.parallel.mesh import make_mesh

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        out = bench.measure_decode_pipelined(
            params, make_mesh("dp=1"), 1e9, clients=3, chunk=4,
            new_tokens=24, prompt_len=8, max_len=96,
        )
        for key in ("decode_call_overhead_ms_serial",
                    "decode_call_overhead_ms_pipelined",
                    "dispatches_serial", "dispatches_pipelined",
                    "serial_agg_tokens_per_s", "pipelined_agg_tokens_per_s",
                    "continuous_vs_batch_decode_pipelined",
                    "pipelined_dispatch_depth_max",
                    "boundary_host_ms_p50_serial",
                    "boundary_host_ms_p50_pipelined",
                    "boundary_host_ms_p99_pipelined",
                    "pipelined_tokens_in_flight_peak",
                    "pipelined_host_syncs_per_boundary",
                    "pipelined_sync_lag_chunks_max",
                    # ISSUE 17: sampled-client mix + fused-sampler accounting
                    "sampled_agg_tokens_per_s",
                    "sampled_vs_greedy_decode_ratio",
                    "pad_fraction",
                    "sampling_ms_p50", "sampling_ms_p99",
                    "sampling_sort_ms_p50"):
            assert key in out, key
        # half the clients sample: the mixed run must still move tokens,
        # and its throughput should land in the same decade as greedy
        assert out["sampled_agg_tokens_per_s"] > 0
        assert out["sampled_vs_greedy_decode_ratio"] > 0.1
        assert 0.0 <= out["pad_fraction"] < 1.0
        assert out["sampling_ms_p50"] > 0
        # the structural evidence, independent of timing noise: depth-D
        # programs mean FEWER device dispatches for the same token volume
        assert out["dispatches_pipelined"] < out["dispatches_serial"]
        assert out["pipelined_dispatch_depth_max"] > 1
        assert out["pipelined_tokens_in_flight_peak"] > 0
        # steady decode must cost at most the one lagged token readback
        assert out["pipelined_host_syncs_per_boundary"] <= 1
