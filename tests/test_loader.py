"""TPU loader tests on a virtual 8-device CPU mesh (SURVEY.md §4: 'CPU-backend
JAX tests with shard-layout fixtures, so no TPU is needed in CI')."""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.loader import LocalFileSource, load_safetensors
from modelx_tpu.dl.sharding import (
    LLAMA_RULES,
    decode_rules,
    encode_rules,
    infer_family,
    sharding_for,
    spec_for,
)
from modelx_tpu.parallel.mesh import make_mesh, parse_mesh_spec


class TestSafetensors:
    def test_write_read_roundtrip(self, tmp_path):
        path = str(tmp_path / "m.safetensors")
        tensors = {
            "a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.ones((2,), dtype=np.int8),
        }
        st.write_safetensors(path, tensors, metadata={"format": "pt"})
        infos, data_offset = st.read_header_from_file(path)
        assert set(infos) == {"a", "b"}
        assert infos["a"].shape == (3, 4)
        assert infos["a"].dtype == "F32"
        with open(path, "rb") as f:
            f.seek(data_offset + infos["a"].start)
            raw = f.read(infos["a"].nbytes)
        assert np.frombuffer(raw, np.float32).reshape(3, 4).tolist() == tensors["a"].tolist()

    def test_bf16(self, tmp_path):
        import ml_dtypes

        path = str(tmp_path / "bf.safetensors")
        arr = np.arange(8, dtype=np.float32).astype(ml_dtypes.bfloat16).reshape(2, 4)
        st.write_safetensors(path, {"w": arr})
        infos, _ = st.read_header_from_file(path)
        assert infos["w"].dtype == "BF16"
        assert infos["w"].nbytes == 16

    def test_matches_official_safetensors_lib(self, tmp_path):
        """Cross-check our writer against the official parser."""
        from safetensors.numpy import load_file

        path = str(tmp_path / "x.safetensors")
        tensors = {"t": np.random.rand(4, 5).astype(np.float32)}
        st.write_safetensors(path, tensors)
        loaded = load_file(path)
        np.testing.assert_array_equal(loaded["t"], tensors["t"])

    def test_row_range(self):
        info = st.TensorInfo(name="x", dtype="F32", shape=(10, 4), start=100, end=100 + 160)
        b0, b1 = st.row_range(info, 2, 5)
        assert (b0, b1) == (100 + 2 * 16, 100 + 5 * 16)

    def test_index_annotation_roundtrip(self, tmp_path):
        path = str(tmp_path / "m.safetensors")
        st.write_safetensors(path, {"a": np.zeros((2, 2), np.float32)})
        infos, off = st.read_header_from_file(path)
        payload = st.tensor_index_annotation(infos, off)
        infos2, off2 = st.parse_index_annotation(payload)
        assert off2 == off and infos2["a"].shape == (2, 2)


class TestMesh:
    def test_parse(self):
        spec = parse_mesh_spec("dp=2,tp=4")
        assert spec.axes == {"dp": 2, "tp": 4}
        assert spec.size == 8
        assert str(spec) == "dp=2,tp=4"

    def test_parse_errors(self):
        for bad in ("", "dp", "dp=x", "dp=0", "dp=2,dp=2", "dp=-1,tp=-1"):
            with pytest.raises(ValueError):
                parse_mesh_spec(bad)

    def test_make_mesh_8_devices(self):
        mesh = make_mesh("dp=2,tp=4")
        assert mesh.shape == {"dp": 2, "tp": 4}

    def test_make_mesh_wildcard(self):
        mesh = make_mesh("dp=2,tp=-1")
        assert mesh.shape == {"dp": 2, "tp": 4}

    def test_make_mesh_wrong_size(self):
        with pytest.raises(ValueError):
            make_mesh("dp=3,tp=3")


class TestShardingRules:
    def test_llama_rules(self):
        assert spec_for("model.layers.0.self_attn.q_proj.weight", LLAMA_RULES) == PartitionSpec("tp", None)
        assert spec_for("model.layers.3.self_attn.o_proj.weight", LLAMA_RULES) == PartitionSpec(None, "tp")
        assert spec_for("model.layers.0.input_layernorm.weight", LLAMA_RULES) == PartitionSpec(None)
        assert spec_for("model.norm.weight", LLAMA_RULES) == PartitionSpec(None)
        assert spec_for("lm_head.weight", LLAMA_RULES) == PartitionSpec("tp", None)

    def test_encode_decode(self):
        rules = [("q_proj", ["tp", None]), (".*", [])]
        assert decode_rules(encode_rules(rules)) == [("q_proj", ["tp", None]), (".*", [])]

    def test_unknown_axis_dropped(self):
        mesh = make_mesh("dp=8")
        s = sharding_for("model.layers.0.self_attn.q_proj.weight", LLAMA_RULES, mesh)
        assert s.spec == PartitionSpec(None, None)

    def test_infer_family(self):
        assert infer_family(["model.layers.0.self_attn.q_proj.weight"]) == "llama"
        assert infer_family(["h.0.attn.c_attn.weight", "wte.weight"]) == "gpt2"
        assert infer_family(["bert.embeddings.word_embeddings.weight"]) == "bert"
        assert infer_family(["mystery"]) == ""

    def test_gemma3_not_matched_as_gemma2(self):
        """Gemma3 carries gemma2's sandwich norms PLUS q_norm/k_norm
        attention norms gemma2's math doesn't have — it must fail loudly
        (families.detect raises), not silently serve through the gemma2
        branch."""
        gemma2_names = [
            "model.layers.0.self_attn.q_proj.weight",
            "model.layers.0.pre_feedforward_layernorm.weight",
        ]
        assert infer_family(gemma2_names) == "gemma2"
        gemma3_names = gemma2_names + [
            "model.layers.0.self_attn.q_norm.weight",
            "model.layers.0.self_attn.k_norm.weight",
        ]
        assert infer_family(gemma3_names) == ""
        from modelx_tpu.dl import families as fam

        with pytest.raises(ValueError, match="family"):
            fam.detect(gemma3_names)


class TestLoader:
    @pytest.fixture
    def checkpoint(self, tmp_path):
        rng = np.random.RandomState(0)
        tensors = {
            "model.layers.0.self_attn.q_proj.weight": rng.rand(32, 16).astype(np.float32),
            "model.layers.0.self_attn.o_proj.weight": rng.rand(16, 32).astype(np.float32),
            "model.norm.weight": rng.rand(16).astype(np.float32),
            "scalar_step": np.array(7, dtype=np.int64),
        }
        path = str(tmp_path / "ckpt.safetensors")
        st.write_safetensors(path, tensors)
        return path, tensors

    def test_load_onto_tp_mesh(self, checkpoint):
        path, tensors = checkpoint
        mesh = make_mesh("dp=2,tp=4")
        arrays, stats = load_safetensors(LocalFileSource(path), mesh, LLAMA_RULES)
        assert stats.tensors == 4
        for name, expected in tensors.items():
            got = np.asarray(arrays[name])
            np.testing.assert_array_equal(got, expected)
        # q_proj is column-parallel: each tp shard holds 32/4 rows
        q = arrays["model.layers.0.self_attn.q_proj.weight"]
        shard_shapes = {s.data.shape for s in q.addressable_shards}
        assert shard_shapes == {(8, 16)}
        # o_proj is row-parallel: shards split dim 1
        o = arrays["model.layers.0.self_attn.o_proj.weight"]
        assert {s.data.shape for s in o.addressable_shards} == {(16, 8)}

    def test_leading_axis_fetches_only_shard_bytes(self, checkpoint):
        """The multi-host story: row-sharded tensors read only their rows."""
        path, tensors = checkpoint
        mesh = make_mesh("tp=8")

        reads = []

        class SpySource(LocalFileSource):
            def read_range(self, offset, length, out=None):
                reads.append((offset, length))
                return super().read_range(offset, length, out)

        arrays, stats = load_safetensors(SpySource(path), mesh, LLAMA_RULES)
        q = tensors["model.layers.0.self_attn.q_proj.weight"]
        # q_proj (32x16 f32, 2048B) sharded 8-way -> 8 reads of 256B
        q_reads = [l for _o, l in reads if l == 2048 // 8]
        assert len(q_reads) == 8
        np.testing.assert_array_equal(np.asarray(arrays["model.layers.0.self_attn.q_proj.weight"]), q)

    def test_byte_budget_balances_when_clamped(self):
        """acquire() returns the clamped charge; releasing exactly that must
        restore the budget to its limit, never inflate past it."""
        from modelx_tpu.dl.loader import _ByteBudget

        b = _ByteBudget(100)
        got = b.acquire(300)
        assert got == 100 and b._avail == 0
        b.release(got - 40)  # partial give-back (post-fetch trim)
        b.release(40)  # transfer done
        assert b._avail == 100

    def test_tiny_transfer_budget_still_streams(self, checkpoint):
        """A byte budget smaller than every tensor must admit them one at a
        time (clamped), not deadlock — the RAM bound is independent of the
        dispatch-thread count."""
        path, tensors = checkpoint
        mesh = make_mesh("dp=2,tp=4")
        arrays, stats = load_safetensors(
            LocalFileSource(path), mesh, LLAMA_RULES, transfer_budget_bytes=64
        )
        assert stats.tensors == 4
        for name, expected in tensors.items():
            np.testing.assert_array_equal(np.asarray(arrays[name]), expected)

    def test_dtype_cast_on_host(self, checkpoint):
        import ml_dtypes

        path, tensors = checkpoint
        mesh = make_mesh("dp=8")
        arrays, _ = load_safetensors(LocalFileSource(path), mesh, LLAMA_RULES, dtype=ml_dtypes.bfloat16)
        assert arrays["model.norm.weight"].dtype == jax.numpy.bfloat16.dtype

    def test_http_source(self, checkpoint):
        """Loader over the registry's ranged blob GET."""
        from modelx_tpu.client.client import Client
        from modelx_tpu.dl.loader import HTTPSource
        from modelx_tpu.registry.fs import MemoryFSProvider
        from modelx_tpu.registry.server import Options, RegistryServer, free_port
        from modelx_tpu.registry.store_fs import FSRegistryStore
        from modelx_tpu.types import Digest

        path, tensors = checkpoint
        srv = RegistryServer(Options(listen=f"127.0.0.1:{free_port()}"), store=FSRegistryStore(MemoryFSProvider()))
        base = srv.serve_background()
        try:
            with open(path, "rb") as f:
                data = f.read()
            digest = str(Digest.from_bytes(data))
            import requests

            requests.put(f"{base}/library/l/blobs/{digest}", data=data)
            mesh = make_mesh("dp=2,tp=4")
            src = HTTPSource(f"{base}/library/l/blobs/{digest}")
            arrays, stats = load_safetensors(src, mesh, LLAMA_RULES)
            for name, expected in tensors.items():
                np.testing.assert_array_equal(np.asarray(arrays[name]), expected)
            assert stats.gbps > 0
        finally:
            srv.shutdown()


class TestLoaderFailure:
    # ~11 s of retry backoff; chaos-marked so `make chaos` (which runs
    # under lockdep) keeps exercising the deadlock-regression path
    @pytest.mark.slow
    @pytest.mark.chaos
    def test_fetch_error_propagates_without_deadlock(self, tmp_path):
        """A mid-load fetch failure must raise, not deadlock the fetch pool
        on the transfer backpressure semaphore (regression: permits leaked
        when transfer_pool.submit refused work after shutdown)."""
        import ml_dtypes

        path = str(tmp_path / "m.safetensors")
        t = {
            f"model.layers.{i}.mlp.gate_proj.weight": np.ones((64, 32), ml_dtypes.bfloat16)
            for i in range(40)
        }
        st.write_safetensors(path, t)
        tensors, off = st.read_header_from_file(path)

        class FlakySource(LocalFileSource):
            calls = 0

            def read_range(self, offset, length, out=None):
                FlakySource.calls += 1
                if FlakySource.calls >= 3:  # persistent: outlives the retry budget
                    raise OSError("injected fetch failure")
                return super().read_range(offset, length, out)

        mesh = make_mesh("dp=1")
        with pytest.raises(OSError, match="injected"):
            load_safetensors(
                FlakySource(path), mesh, LLAMA_RULES, tensors=tensors, data_offset=off
            )


class TestExpertFusionGate:
    def _experts(self):
        infos = {}
        start = 0
        for e in range(4):
            name = f"model.layers.0.block_sparse_moe.experts.{e}.w1.weight"
            infos[name] = st.TensorInfo(name=name, dtype="BF16", shape=(8, 4), start=start, end=start + 64)
            start += 64
        return infos

    def test_fuses_under_family_rules(self):
        from modelx_tpu.dl.loader import fuse_expert_tensors
        from modelx_tpu.dl.sharding import MIXTRAL_RULES

        fused = fuse_expert_tensors(self._experts(), MIXTRAL_RULES)
        assert list(fused) == ["model.layers.0.block_sparse_moe.experts.w1.weight"]
        assert fused["model.layers.0.block_sparse_moe.experts.w1.weight"].shape == (4, 8, 4)

    def test_fuses_on_catch_all_tie(self):
        """Catch-all-only rules (checkpoint pushed without annotations) must
        still fuse — models/mixtral.py consumes the stacked layout."""
        from modelx_tpu.dl.loader import fuse_expert_tensors

        fused = fuse_expert_tensors(self._experts(), [(r".*", [])])
        assert list(fused) == ["model.layers.0.block_sparse_moe.experts.w1.weight"]

    def test_user_rules_targeting_hf_names_disable_fusion(self):
        """A shard-spec annotation written against the on-disk per-expert
        names wins: tensors keep their HF names and specs apply."""
        from modelx_tpu.dl.loader import fuse_expert_tensors

        rules = [(r"experts\.\d+\.w1\.weight$", ["tp", None]), (r".*", [])]
        out = fuse_expert_tensors(self._experts(), rules)
        assert len(out) == 4
        assert all("experts." in n for n in out)

    def test_transient_fetch_error_retried(self, tmp_path):
        """One flaky read inside the retry budget must not fail the load
        (SURVEY §5: loader retries per shard)."""
        import ml_dtypes

        path = str(tmp_path / "m.safetensors")
        st.write_safetensors(
            path, {"model.norm.weight": np.ones((16,), ml_dtypes.bfloat16)}
        )
        tensors, off = st.read_header_from_file(path)

        class OnceFlaky(LocalFileSource):
            calls = 0

            def read_range(self, offset, length, out=None):
                OnceFlaky.calls += 1
                if OnceFlaky.calls == 1:
                    raise OSError("transient")
                return super().read_range(offset, length, out)

        mesh = make_mesh("dp=1")
        arrays, _ = load_safetensors(
            OnceFlaky(path), mesh, LLAMA_RULES, tensors=tensors, data_offset=off
        )
        assert np.asarray(arrays["model.norm.weight"]).shape == (16,)


class TestFoldHasOneDestination:
    """A folded expert tensor is read where it will be put (ISSUE 55): one
    host buffer a group, each member's rows read straight into their place in
    it. Whatever the mesh, the source or the dtype, the stacked array is
    ``np.stack`` of the members bit for bit, and only a member whose inner
    dims are strided — or a cast — writes a byte twice on the host."""

    W1 = "model.layers.0.block_sparse_moe.experts.{e}.w1.weight"  # [ep, tp, None]
    W2 = "model.layers.0.block_sparse_moe.experts.{e}.w2.weight"  # [ep, None, tp]
    STACKED_W1 = "model.layers.0.block_sparse_moe.experts.w1.weight"
    STACKED_W2 = "model.layers.0.block_sparse_moe.experts.w2.weight"

    class RangeSource:
        """What a registry-backed source looks like to the loader: no file
        behind it, ranged reads that land in ``out`` when one is given."""

        def __init__(self, path):
            with open(path, "rb") as f:
                self.blob = f.read()
            self.reads_with_out = self.reads_without = 0

        def read_range(self, offset, length, out=None):
            data = self.blob[offset:offset + length]
            if out is None:
                self.reads_without += 1
                return data
            self.reads_with_out += 1
            memoryview(out)[:] = data
            return out

        def size(self):
            return len(self.blob)

    @staticmethod
    def members(first: int = 0, count: int = 4, dtype=np.float32) -> dict:
        rng = np.random.RandomState(first + count)
        out = {}
        for e in range(first, first + count):
            out[TestFoldHasOneDestination.W1.format(e=e)] = rng.rand(16, 8).astype(dtype)
            out[TestFoldHasOneDestination.W2.format(e=e)] = rng.rand(8, 16).astype(dtype)
        return out

    CASES = {
        # name: (mesh, first expert, load_safetensors arguments, bytes copied twice)
        "whole_fold": ("dp=1", 0, {"staging_min_bytes": 1024}, 0),
        "ep_share_on_a_mesh": ("ep=4,tp=1", 0, {"staging_min_bytes": 256}, 0),
        # w2's last axis over tp: each member is cut from its whole tensor,
        # once a tp group — every w2 byte is written once more, no w1 byte
        "strided_inner_slice": ("ep=2,tp=4", 0, {"staging_min_bytes": 256}, 4 * 8 * 16 * 4),
        "host_side_cast": ("dp=1", 0, {"staging_min_bytes": 1024, "dtype": np.float16},
                           2 * 4 * 16 * 8 * 2),
        "run_not_from_zero": ("ep=2,tp=1", 3, {"staging_min_bytes": 256}, 0),
        "range_source_honouring_out": ("dp=1", 0, {"staging_min_bytes": 1024,
                                                   "split_read_bytes": 128}, 0),
        "below_staging_min": ("ep=2,tp=2", 0, {}, 2 * 2 * 8 * 8 * 4 * 2),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_stacked_array_is_np_stack_of_the_members(self, tmp_path, case):
        from modelx_tpu.dl.sharding import MIXTRAL_RULES

        mesh_spec, first, kwargs, copied = self.CASES[case]
        tensors = self.members(first)
        path = str(tmp_path / "experts.safetensors")
        st.write_safetensors(path, tensors)
        source = (self.RangeSource(path) if case == "range_source_honouring_out"
                  else LocalFileSource(path))
        mesh = make_mesh(mesh_spec)  # a prefix of the eight virtual devices
        arrays, stats = load_safetensors(source, mesh, MIXTRAL_RULES, pack_threshold=0, **kwargs)
        assert sorted(arrays) == [self.STACKED_W1, self.STACKED_W2]
        for name, member in ((self.STACKED_W1, self.W1), (self.STACKED_W2, self.W2)):
            want = np.stack([tensors[member.format(e=e)] for e in range(first, first + 4)])
            want = want.astype(kwargs.get("dtype", want.dtype))
            got = arrays[name]
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.asarray(got).tobytes() == want.tobytes(), name
            for shard in got.addressable_shards:  # and each device holds its own share
                assert np.asarray(shard.data).tobytes() == want[shard.index].tobytes()
        assert stats.assemble_copied_bytes == copied
        assert stats.bytes_to_device == sum(
            int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays.values())
        pooled = stats.staging_allocs + stats.staging_reuses
        if case == "below_staging_min":
            assert pooled == 0
        elif case == "range_source_honouring_out":
            # one pooled buffer a fold; every member's read — split in
            # subranges of 128 bytes — landed in its place in it
            assert pooled == 2 and source.reads_with_out == 2 * 4 * 4
            assert source.reads_without == 2  # the header's two
        else:
            assert pooled >= 2  # a buffer a group

    @pytest.mark.parametrize("failing", [None, 2])
    def test_a_groups_pooled_buffer_goes_back_exactly_once(self, tmp_path, monkeypatch, failing):
        from modelx_tpu.dl import loader
        from modelx_tpu.dl.sharding import MIXTRAL_RULES

        pools = []

        class Recording(loader._StagingPool):
            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                self.taken, self.back = [], []
                pools.append(self)

            def acquire(self, nbytes):
                view = super().acquire(nbytes)
                self.taken.append(view)
                return view

            def release(self, view):
                self.back.append(view)
                super().release(view)

            def forfeit(self, view):
                self.back.append(view)
                super().forfeit(view)

        monkeypatch.setattr(loader, "_StagingPool", Recording)
        tensors = self.members()
        path = str(tmp_path / "experts.safetensors")
        st.write_safetensors(path, tensors)
        infos, data_offset = st.read_header_from_file(path)
        bad = infos[self.W1.format(e=failing)] if failing is not None else None

        class Source(LocalFileSource):
            def read_range(self, offset, length, out=None):
                if bad is not None and offset == data_offset + bad.start:
                    raise OSError("injected: this member cannot be read")
                return super().read_range(offset, length, out)

        def load():
            return load_safetensors(
                Source(path), make_mesh("dp=1", jax.devices()[:1]), MIXTRAL_RULES,
                tensors=infos, data_offset=data_offset, pack_threshold=0,
                staging_min_bytes=1024)

        if failing is None:
            load()
        else:
            with pytest.raises(OSError, match="injected"):
                load()
        (pool,) = pools
        assert len(pool.taken) == 2  # one buffer a fold: w1's and w2's
        assert sorted(map(id, pool.back)) == sorted(map(id, pool.taken))
        assert pool._out == 0


class TestPackedTransfer:
    """Small tensors ride one packed uint8 buffer + on-device bitcast; the
    result must be bit-identical to per-tensor device_put for every dtype,
    sharded or replicated."""

    @pytest.fixture
    def mixed_checkpoint(self, tmp_path):
        import ml_dtypes

        rng = np.random.RandomState(1)
        tensors = {
            "model.layers.0.self_attn.q_proj.weight": rng.rand(32, 16).astype(
                ml_dtypes.bfloat16
            ),
            "model.layers.0.self_attn.o_proj.weight": rng.rand(16, 32).astype(np.float32),
            "model.norm.weight": rng.rand(16).astype(np.float16),
            "model.embed_tokens.weight": rng.rand(64, 16).astype(ml_dtypes.bfloat16),
            "quant_flag": (rng.rand(8) * 100).astype(np.int8),
            "scalar_step": np.array(7, dtype=np.int64),  # forces unpackable path
        }
        path = str(tmp_path / "mixed.safetensors")
        st.write_safetensors(path, tensors)
        return path, tensors

    @pytest.mark.parametrize("mesh_spec", ["dp=1", "dp=2,tp=4"])
    def test_packed_equals_unpacked(self, mixed_checkpoint, mesh_spec):
        path, tensors = mixed_checkpoint
        mesh = make_mesh(mesh_spec)
        packed, _ = load_safetensors(
            LocalFileSource(path), mesh, LLAMA_RULES, pack_threshold=1 << 20
        )
        plain, _ = load_safetensors(
            LocalFileSource(path), mesh, LLAMA_RULES, pack_threshold=0
        )
        for name in tensors:
            a, b = np.asarray(packed[name]), np.asarray(plain[name])
            assert a.dtype == b.dtype, name
            np.testing.assert_array_equal(a, b, err_msg=name)
            assert packed[name].sharding == plain[name].sharding, name

    def test_sharded_small_tensors_keep_layout(self, mixed_checkpoint):
        path, _ = mixed_checkpoint
        mesh = make_mesh("dp=2,tp=4")
        arrays, _ = load_safetensors(
            LocalFileSource(path), mesh, LLAMA_RULES, pack_threshold=1 << 20
        )
        q = arrays["model.layers.0.self_attn.q_proj.weight"]
        assert {s.data.shape for s in q.addressable_shards} == {(8, 16)}


class TestAdaptiveFetchWidth:
    """Regression: fetch width must derive from the host, and the
    governor must shed width when per-thread throughput collapses."""

    def test_auto_concurrency_scales_with_host(self, tmp_path, monkeypatch):
        import modelx_tpu.dl.loader as ldr

        p = tmp_path / "f.bin"
        p.write_bytes(b"x" * 64)
        src = ldr.LocalFileSource(str(p))
        try:
            monkeypatch.setattr(ldr.os, "cpu_count", lambda: 1)
            assert ldr.auto_fetch_concurrency(src) == 2  # not 16 on 1 core
            monkeypatch.setattr(ldr.os, "cpu_count", lambda: 16)
            assert ldr.auto_fetch_concurrency(src) == 8  # local cap
        finally:
            src.close()
        http = ldr.HTTPSource("http://example.invalid/blob", total=64)
        monkeypatch.setattr(ldr.os, "cpu_count", lambda: 1)
        assert ldr.auto_fetch_concurrency(http) == 8
        monkeypatch.setattr(ldr.os, "cpu_count", lambda: 8)
        assert ldr.auto_fetch_concurrency(http) == 16

    def test_governor_halves_width_on_collapse(self):
        from modelx_tpu.dl.loader import _FetchGovernor

        gov = _FetchGovernor(16, floor_bps=32e6, min_width=2)
        # simulate reads at 1 MB/s per thread (the r4 collapse signature)
        for _ in range(8):
            gov.acquire()
            gov.release(nbytes=1 << 20, seconds=1.0)
        assert gov.width < 16
        assert gov.backoffs >= 1
        # keeps shedding down to the floor width, never below
        for _ in range(64):
            gov.acquire()
            gov.release(nbytes=1 << 20, seconds=1.0)
        assert gov.width == 2

    def test_governor_keeps_width_when_healthy(self):
        from modelx_tpu.dl.loader import _FetchGovernor

        gov = _FetchGovernor(8, floor_bps=32e6)
        for _ in range(32):  # 400 MB/s per thread: healthy page-cache reads
            gov.acquire()
            gov.release(nbytes=100 << 20, seconds=0.25)
        assert gov.width == 8
        assert gov.backoffs == 0

    def test_governor_disabled_floor_never_fires(self):
        from modelx_tpu.dl.loader import _FetchGovernor

        gov = _FetchGovernor(16, floor_bps=0.0)  # HTTP sources: no floor
        for _ in range(32):
            gov.acquire()
            gov.release(nbytes=1024, seconds=1.0)  # 1 KB/s would trip any floor
        assert gov.width == 16

    def test_governor_grows_with_headroom(self):
        """Per-thread throughput above growth_bps means the link has
        headroom: width doubles up to max_width (the r5 capture sat at
        width 2 with the link 56% idle)."""
        from modelx_tpu.dl.loader import _FetchGovernor

        gov = _FetchGovernor(2, floor_bps=0.0, max_width=16, growth_bps=24e6)
        for _ in range(32):  # 400 MB/s per thread: plenty of headroom
            gov.acquire()
            gov.release(nbytes=100 << 20, seconds=0.25)
        assert gov.width == 16  # capped at max_width, never beyond
        assert gov.growths >= 3

    def test_governor_growth_disabled_after_repeated_collapse(self):
        """Three backoffs = the link punishes added width; growth must not
        oscillate against it."""
        from modelx_tpu.dl.loader import _FetchGovernor

        gov = _FetchGovernor(16, floor_bps=32e6, min_width=2,
                             max_width=32, growth_bps=128e6)
        for _ in range(64):  # collapse to the floor
            gov.acquire()
            gov.release(nbytes=1 << 20, seconds=1.0)
        assert gov.width == 2 and gov.backoffs >= 3
        for _ in range(32):  # throughput recovers — but trust is spent
            gov.acquire()
            gov.release(nbytes=200 << 20, seconds=0.25)
        assert gov.width == 2
        assert gov.growths == 0

    def test_load_reports_governor_stats(self, tmp_path):
        """End-to-end: a local load records the width it ran at."""
        import jax

        from modelx_tpu.dl import safetensors as st_mod
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors
        from modelx_tpu.dl.sharding import LLAMA_RULES
        from modelx_tpu.parallel.mesh import make_mesh

        rng = np.random.RandomState(0)
        tensors = {"model.embed_tokens.weight": rng.rand(64, 16).astype(np.float32)}
        path = str(tmp_path / "m.safetensors")
        st_mod.write_safetensors(path, tensors)
        src = LocalFileSource(path)
        try:
            mesh = make_mesh(f"dp={len(jax.devices())}")
            _loaded, stats = load_safetensors(src, mesh, LLAMA_RULES)
        finally:
            src.close()
        assert stats.fetch_width >= 2
        assert stats.fetch_backoffs == 0


class TestStagingPool:
    """The reusable host staging pool (ISSUE 1 tentpole): shard reads must
    recycle buffers, so allocation count tracks CONCURRENCY, not shard
    count, and the load reports fetch-vs-device_put overlap accounting."""

    def _many_shard_checkpoint(self, tmp_path, layers: int):
        rng = np.random.RandomState(9)
        tensors = {
            f"model.layers.{i}.mlp.gate_proj.weight": rng.rand(64, 32).astype(np.float32)
            for i in range(layers)
        }
        path = str(tmp_path / f"many{layers}.safetensors")
        st.write_safetensors(path, tensors)
        return path, tensors

    def test_pool_reused_across_shards(self, tmp_path):
        # host-side bf16 cast: the shard bytes are COPIED out of the pooled
        # buffer before device_put, so the buffer recycles deterministically
        # on every backend (without a cast, a zero-copy backend like PJRT
        # CPU may alias some buffers, which forfeit instead of recycling —
        # covered by test_zero_copy_backend_stays_correct)
        import ml_dtypes

        path, tensors = self._many_shard_checkpoint(tmp_path, 48)
        src = LocalFileSource(path)
        try:
            arrays, stats = load_safetensors(
                src, make_mesh("dp=1"), LLAMA_RULES,
                concurrency=2, transfer_concurrency=2,
                dtype=ml_dtypes.bfloat16,
                pack_threshold=0,  # every shard through the transfer path
                staging_min_bytes=1024,  # the 8 KB test shards qualify
            )
        finally:
            src.close()
        for name, expected in tensors.items():
            np.testing.assert_array_equal(
                np.asarray(arrays[name]), expected.astype(ml_dtypes.bfloat16)
            )
        # every qualifying read staged, and the pool turned over: fresh
        # allocations bounded by in-flight concurrency (2 fetch + 2
        # transfer + slack), NOT by the 48 shards
        assert stats.staging_allocs + stats.staging_reuses >= 48
        assert stats.staging_allocs <= 8, stats
        assert stats.staging_reuses >= 40, stats

    def test_alloc_count_independent_of_shard_count(self, tmp_path):
        """2x the shards must not mean 2x the allocations — the pool's
        whole point (ISSUE 1 acceptance criterion)."""
        import ml_dtypes

        allocs = {}
        for layers in (24, 48):
            path, _ = self._many_shard_checkpoint(tmp_path, layers)
            src = LocalFileSource(path)
            try:
                _arrays, stats = load_safetensors(
                    src, make_mesh("dp=1"), LLAMA_RULES,
                    concurrency=2, transfer_concurrency=2,
                    dtype=ml_dtypes.bfloat16,
                    pack_threshold=0, staging_min_bytes=1024,
                )
            finally:
                src.close()
            allocs[layers] = stats.staging_allocs
        assert allocs[48] <= allocs[24] + 2, allocs

    def test_zero_copy_backend_stays_correct(self, tmp_path):
        """No cast: device_put may zero-copy the pooled buffer (PJRT CPU,
        64-byte-aligned hosts). Those buffers must be FORFEITED, never
        recycled — every loaded tensor must still hold its own bytes."""
        path, tensors = self._many_shard_checkpoint(tmp_path, 48)
        src = LocalFileSource(path)
        try:
            arrays, stats = load_safetensors(
                src, make_mesh("dp=1"), LLAMA_RULES,
                concurrency=2, transfer_concurrency=2,
                pack_threshold=0, staging_min_bytes=1024,
            )
        finally:
            src.close()
        for name, expected in tensors.items():
            np.testing.assert_array_equal(
                np.asarray(arrays[name]), expected, err_msg=name
            )
        assert stats.staging_allocs + stats.staging_reuses >= 48

    def test_overlap_accounting_reported(self, tmp_path):
        path, tensors = self._many_shard_checkpoint(tmp_path, 16)
        src = LocalFileSource(path)
        try:
            _arrays, stats = load_safetensors(
                src, make_mesh("dp=1"), LLAMA_RULES,
                pack_threshold=0, staging_min_bytes=1024,
            )
        finally:
            src.close()
        assert stats.device_put_seconds > 0
        assert stats.overlap_seconds >= 0
        assert stats.overlap_seconds <= stats.total_seconds
        assert stats.fetch_growths >= 0

    def test_cast_and_pack_paths_stay_correct_with_staging(self, tmp_path):
        """Host-side dtype casts copy out of the pooled buffer; packed
        small tensors copy too — bytes on device must match either way."""
        import ml_dtypes

        path, tensors = self._many_shard_checkpoint(tmp_path, 12)
        src = LocalFileSource(path)
        try:
            arrays, stats = load_safetensors(
                src, make_mesh("dp=2,tp=4"), LLAMA_RULES,
                dtype=ml_dtypes.bfloat16,
                pack_threshold=1 << 20, staging_min_bytes=1024,
            )
        finally:
            src.close()
        for name, expected in tensors.items():
            np.testing.assert_array_equal(
                np.asarray(arrays[name]),
                expected.astype(ml_dtypes.bfloat16),
            )


class TestByteAccounting2DMesh:
    """BASELINE config #4's core claim, asserted in CI (VERDICT r4 item 7):
    on a dp x tp mesh, the loader's fetch plan reads each tensor's bytes
    EXACTLY ONCE in total, and a leading-axis tp-sharded tensor's reads are
    the tp disjoint row slices — i.e. each host-equivalent fetch group pulls
    only the rows its devices own (dp replicas share one fetch), never the
    whole tensor per device."""

    class CountingSource:
        def __init__(self, path):
            self.inner = LocalFileSource(path)
            self.reads: list[tuple[int, int]] = []
            import threading as _t

            self._lock = _t.Lock()

        def read_range(self, offset, length, out=None):
            with self._lock:
                self.reads.append((offset, length))
            return self.inner.read_range(offset, length, out)

        def size(self):
            return self.inner.size()

        def close(self):
            self.inner.close()

    def test_dp_tp_pull_fetches_owned_shard_bytes_once(self, tmp_path):
        mesh = make_mesh("dp=2,tp=4")
        rng = np.random.RandomState(5)
        tensors = {
            # leading axis over tp: the per-shard ranged-read case
            "model.layers.0.self_attn.q_proj.weight": rng.rand(64, 32).astype(np.float32),
            "model.embed_tokens.weight": rng.rand(128, 32).astype(np.float32),
            # inner axis over tp: one full fetch, sliced in memory
            "model.layers.0.mlp.down_proj.weight": rng.rand(32, 64).astype(np.float32),
            # replicated: one fetch for all 8 devices
            "model.norm.weight": rng.rand(32).astype(np.float32),
        }
        path = str(tmp_path / "acct.safetensors")
        st.write_safetensors(path, tensors)
        infos, data_offset = st.read_header_from_file(path)
        src = self.CountingSource(path)
        try:
            loaded, stats = load_safetensors(
                src, mesh, LLAMA_RULES, tensors=infos, data_offset=data_offset
            )
        finally:
            src.close()
        # correctness first: the assembled arrays equal the originals
        for name, arr in tensors.items():
            np.testing.assert_array_equal(np.asarray(loaded[name]), arr)

        total_bytes = sum(a.nbytes for a in tensors.values())
        fetched = sum(n for _off, n in src.reads)
        # THE config-#4 assertion: fetched bytes ~ owned shard bytes, not
        # devices x bytes (a per-device refetch would be 4-8x)
        assert fetched / total_bytes <= 1.1, (fetched, total_bytes)

        # exactly-once coverage: reads tile the data section without
        # overlap or holes
        spans = sorted((off, off + n) for off, n in src.reads)
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            assert a1 <= b0, f"overlapping reads {a0}-{a1} / {b0}-{b1}"
        assert fetched == total_bytes

        # the leading-axis tp-sharded tensor fetched as tp=4 disjoint row
        # slices of nbytes/4 each (dp replicas shared their group's fetch)
        q = infos["model.layers.0.self_attn.q_proj.weight"]
        q_reads = [
            (off - data_offset - q.start, n)
            for off, n in src.reads
            if q.start <= off - data_offset < q.end
        ]
        assert len(q_reads) == 4, q_reads
        assert {n for _o, n in q_reads} == {q.nbytes // 4}
        # the inner-sharded and replicated tensors fetched once, whole
        for name in ("model.layers.0.mlp.down_proj.weight", "model.norm.weight"):
            info = infos[name]
            n_reads = [
                n for off, n in src.reads
                if info.start <= off - data_offset < info.end
            ]
            assert n_reads == [info.nbytes], (name, n_reads)


class TestFaultInjectedLoads:
    """The loader's transient-fault stance (retry x3 with backoff, SURVEY
    §5) proven by deterministic FaultPlan schedules instead of hoping a
    flaky network shows up in CI."""

    @pytest.fixture
    def checkpoint(self, tmp_path):
        rng = np.random.RandomState(5)
        tensors = {
            "model.layers.0.self_attn.q_proj.weight": rng.rand(32, 16).astype(np.float32),
            "model.layers.0.self_attn.o_proj.weight": rng.rand(16, 32).astype(np.float32),
            "model.norm.weight": rng.rand(16).astype(np.float32),
        }
        path = str(tmp_path / "ckpt.safetensors")
        st.write_safetensors(path, tensors)
        return path, tensors

    def test_transient_faults_retry_to_an_exact_load(self, checkpoint):
        """Errors and short reads early in the schedule stay invisible to
        the caller: _read_with_retry absorbs them and the loaded arrays
        are byte-identical."""
        from modelx_tpu.testing import faults

        path, tensors = checkpoint
        plan = faults.FaultPlan(seed=9)
        # one hard error and one short read, on separate read calls
        plan.add("loader.read", errors_at=[0], error=OSError("reset"))
        plan.add("loader.read", truncate_at=[3], keep_bytes=2)
        src = faults.FaultyByteSource(LocalFileSource(path), plan)
        mesh = make_mesh("dp=2,tp=4")
        arrays, stats = load_safetensors(src, mesh, LLAMA_RULES)
        for name, expected in tensors.items():
            np.testing.assert_array_equal(np.asarray(arrays[name]), expected)
        assert plan.count("loader.read") > 3  # the faults actually fired

    def test_fault_past_retry_budget_surfaces(self, checkpoint):
        """Three consecutive failures on one range exhaust FETCH_RETRIES:
        the load fails loudly instead of silently dropping a tensor."""
        from modelx_tpu.dl.loader import FETCH_RETRIES
        from modelx_tpu.testing import faults

        path, _tensors = checkpoint
        plan = faults.FaultPlan()
        plan.add("loader.read", errors_at=range(FETCH_RETRIES),
                 error=OSError("hard down"))
        src = faults.FaultyByteSource(LocalFileSource(path), plan)
        mesh = make_mesh("dp=2,tp=4")
        with pytest.raises(OSError, match="hard down"):
            load_safetensors(src, mesh, LLAMA_RULES)

    def test_env_gated_plan_wraps_real_loads(self, checkpoint, monkeypatch):
        """MODELX_FAULT_PLAN (default off) injects into load_safetensors
        itself — the chaos-drill seam for real deployments."""
        import json as _json

        path, tensors = checkpoint
        spec = {"rules": [{"op": "loader.read", "errors_at": [0],
                           "error": "drill"}]}
        monkeypatch.setenv("MODELX_FAULT_PLAN", _json.dumps(spec))
        mesh = make_mesh("dp=2,tp=4")
        # the injected first-read error is retried away; the load succeeds
        arrays, _ = load_safetensors(LocalFileSource(path), mesh, LLAMA_RULES)
        for name, expected in tensors.items():
            np.testing.assert_array_equal(np.asarray(arrays[name]), expected)
