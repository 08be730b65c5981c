"""Mesh-serving tests (ISSUE 16): family shard rules -> NamedSharding,
KV-cache placement, sharded byte-range math, push-side shard annotations,
per-device HBM budgeting + telemetry, and the multi-device continuous-
decode matrix.

Everything runs on the forced-host 8-device CPU backend
(tests/conftest.py sets ``--xla_force_host_platform_device_count=8``), so
no TPU is needed in CI. Tier-1 keeps one representative of the engine
matrix (greedy exactness on a dp=2,tp=2 mesh + placement/telemetry
asserts); the sampled/multirow/paged/dp-only sweeps are slow-marked and
run from ``make mesh``. The dp=1 mesh-vs-legacy byte-equality
representative lives in tests/test_continuous.py::TestExactness — the
engine there IS the mesh-aware engine on a single-device mesh.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.sharding import (
    DEFAULT_RULES,
    LLAMA_RULES,
    cache_sharding,
    decode_rules,
    rules_for_family,
    sharding_for,
    spec_for,
)
from modelx_tpu.parallel.mesh import make_mesh, mesh_str, weight_shard_factor


class TestMeshStr:
    def test_round_trip(self):
        assert mesh_str(make_mesh("dp=2,tp=4")) == "dp=2,tp=4"
        assert mesh_str(make_mesh("dp=1")) == "dp=1"
        assert mesh_str(make_mesh(mesh_str(make_mesh("dp=2,tp=2")))) == "dp=2,tp=2"

    def test_weight_shard_factor(self):
        # dp and sp replicate weights; tp/ep/pp/fsdp divide them
        assert weight_shard_factor(make_mesh("dp=8")) == 1
        assert weight_shard_factor(make_mesh("dp=2,tp=4")) == 4
        assert weight_shard_factor(make_mesh("dp=2,sp=2,tp=2")) == 2
        assert weight_shard_factor(make_mesh("fsdp=2,tp=2")) == 4
        assert weight_shard_factor(make_mesh("dp=1")) == 1


# representative checkpoint tensor names per family and the PartitionSpec
# the family's rule set must yield on a dp/tp mesh (first match wins)
FAMILY_SPEC_CASES = {
    "llama": [
        ("model.embed_tokens.weight", PartitionSpec("tp", None)),
        ("model.layers.0.self_attn.q_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.self_attn.o_proj.weight", PartitionSpec(None, "tp")),
        ("model.layers.0.mlp.gate_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.mlp.down_proj.weight", PartitionSpec(None, "tp")),
        ("model.norm.weight", PartitionSpec(None)),
        ("lm_head.weight", PartitionSpec("tp", None)),
    ],
    "qwen2": [
        ("model.layers.0.self_attn.q_proj.bias", PartitionSpec("tp")),
        ("model.layers.0.self_attn.q_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.mlp.down_proj.weight", PartitionSpec(None, "tp")),
    ],
    "gemma2": [
        ("model.layers.0.self_attn.v_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.pre_feedforward_layernorm.weight", PartitionSpec(None)),
    ],
    "phi3": [
        ("model.layers.0.self_attn.qkv_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.mlp.gate_up_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.mlp.down_proj.weight", PartitionSpec(None, "tp")),
    ],
    "gpt2": [
        ("wte.weight", PartitionSpec("tp", None)),
        ("wpe.weight", PartitionSpec(None, None)),
        ("h.0.attn.c_attn.weight", PartitionSpec(None, "tp")),
        ("h.0.attn.c_proj.weight", PartitionSpec("tp", None)),
        ("h.0.mlp.c_fc.weight", PartitionSpec(None, "tp")),
        ("h.0.mlp.c_proj.weight", PartitionSpec("tp", None)),
    ],
    "bert": [
        ("encoder.layer.0.attention.self.query.weight", PartitionSpec("tp", None)),
        ("encoder.layer.0.attention.output.dense.weight", PartitionSpec(None, "tp")),
        ("encoder.layer.0.intermediate.dense.weight", PartitionSpec("tp", None)),
        ("encoder.layer.0.output.dense.weight", PartitionSpec(None, "tp")),
        ("embeddings.word_embeddings.weight", PartitionSpec("tp", None)),
    ],
    "mixtral": [
        ("model.layers.0.self_attn.q_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.block_sparse_moe.gate.weight", PartitionSpec(None, None)),
        # ep drops on a dp/tp mesh (clean_spec), tp survives
        ("model.layers.0.block_sparse_moe.experts.w1.weight",
         PartitionSpec(None, "tp", None)),
        ("model.layers.0.block_sparse_moe.experts.w2.weight",
         PartitionSpec(None, None, "tp")),
    ],
    "laguna": [
        ("model.layers.1.self_attn.g_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.1.self_attn.o_proj.weight", PartitionSpec(None, "tp")),
        ("model.layers.1.mlp.gate.weight", PartitionSpec(None, None)),  # the router
        ("model.layers.1.mlp.experts.gate_proj.weight", PartitionSpec(None, "tp", None)),
        ("model.layers.1.mlp.experts.down_proj.weight", PartitionSpec(None, None, "tp")),
        ("model.layers.1.mlp.shared_expert.up_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.mlp.down_proj.weight", PartitionSpec(None, "tp")),  # the dense layer
    ],
    "mimo_v2": [
        ("model.layers.1.self_attn.k_proj.weight", PartitionSpec("tp", None)),  # keys of 192 by head
        ("model.layers.1.self_attn.o_proj.weight", PartitionSpec(None, "tp")),
        ("model.layers.1.self_attn.attention_sink_bias", PartitionSpec("tp")),  # with its heads
        ("model.layers.1.mlp.gate.weight", PartitionSpec(None, None)),  # the router, whole
        ("model.layers.1.mlp.gate.e_score_correction_bias", PartitionSpec(None)),
        ("model.layers.1.mlp.experts.up_proj.weight", PartitionSpec(None, "tp", None)),  # no ep axis here
        ("model.layers.1.mlp.experts.down_proj.weight", PartitionSpec(None, None, "tp")),
        ("model.layers.0.mlp.down_proj.weight", PartitionSpec(None, "tp")),  # the dense layer
    ],
    "minicpm_sala": [
        ("model.layers.9.self_attn.o_gate.weight", PartitionSpec("tp", None)),  # with the heads
        ("model.layers.9.self_attn.o_proj.weight", PartitionSpec(None, "tp")),
        ("model.layers.10.self_attn.k_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.10.self_attn.norm.weight", PartitionSpec(None)),  # the output norm
        ("model.layers.10.self_attn.q_norm.weight", PartitionSpec(None)),
        ("model.layers.10.mlp.down_proj.weight", PartitionSpec(None, "tp")),
        ("lm_head.weight", PartitionSpec("tp", None)),
    ],
    "deepseek_v2": [
        # the low-rank pairs' down-projections whole (a latent line has one "KV head"),
        # their up-projections and the output by head
        ("model.layers.0.self_attn.q_a_proj.weight", PartitionSpec(None, None)),
        ("model.layers.0.self_attn.kv_a_proj_with_mqa.weight", PartitionSpec(None, None)),
        ("model.layers.0.self_attn.kv_a_layernorm.weight", PartitionSpec(None)),
        ("model.layers.0.self_attn.q_b_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.self_attn.kv_b_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.0.self_attn.o_proj.weight", PartitionSpec(None, "tp")),
        ("model.layers.1.mlp.gate.weight", PartitionSpec(None, None)),  # the router, whole
        # DeepSeek-V3 / V3.2 in the same family: the router's choice bias and the lightning
        # indexer (one key a position for all heads, data-parallel in the deployment) whole —
        # ``wq_b`` must not fall to the q_b / kv_b rule, ``weights_proj`` not to a projection's
        ("model.layers.1.mlp.gate.e_score_correction_bias", PartitionSpec(None)),
        ("model.layers.0.self_attn.indexer.wq_b.weight", PartitionSpec(None, None)),
        ("model.layers.0.self_attn.indexer.wk.weight", PartitionSpec(None, None)),
        ("model.layers.0.self_attn.indexer.weights_proj.weight", PartitionSpec(None, None)),
        ("model.layers.0.self_attn.indexer.k_norm.weight", PartitionSpec(None)),
        ("model.layers.0.self_attn.indexer.k_norm.bias", PartitionSpec(None)),
        ("model.layers.1.mlp.experts.gate_proj.weight", PartitionSpec(None, "tp", None)),  # no ep axis here
        ("model.layers.1.mlp.experts.down_proj.weight", PartitionSpec(None, None, "tp")),
        # two dimensions: the stacked experts' patterns must not catch them
        ("model.layers.1.mlp.shared_experts.gate_proj.weight", PartitionSpec("tp", None)),
        ("model.layers.1.mlp.shared_experts.down_proj.weight", PartitionSpec(None, "tp")),
        ("model.layers.0.mlp.up_proj.weight", PartitionSpec("tp", None)),
        ("lm_head.weight", PartitionSpec("tp", None)),
    ],
    "nemotron_h": [
        # a Mamba layer's fused projection is three runs of rows: whole, with its per-head vectors
        ("backbone.layers.0.mixer.in_proj.weight", PartitionSpec(None, None)),
        ("backbone.layers.0.mixer.out_proj.weight", PartitionSpec(None, None)),
        ("backbone.layers.0.mixer.conv1d.weight", PartitionSpec()),
        ("backbone.layers.0.mixer.A_log", PartitionSpec()),
        ("backbone.layers.0.mixer.norm.weight", PartitionSpec(None)),
        ("backbone.layers.1.mixer.gate.weight", PartitionSpec(None, None)),  # the router, whole
        ("backbone.layers.1.mixer.fc1_latent_proj.weight", PartitionSpec(None, None)),
        ("backbone.layers.1.mixer.experts.up_proj.weight", PartitionSpec(None, "tp", None)),  # no ep axis here
        ("backbone.layers.1.mixer.experts.down_proj.weight", PartitionSpec(None, None, "tp")),
        # two dimensions: the stacked experts' patterns must not catch them
        ("backbone.layers.1.mixer.shared_experts.up_proj.weight", PartitionSpec("tp", None)),
        ("backbone.layers.1.mixer.shared_experts.down_proj.weight", PartitionSpec(None, "tp")),
        ("backbone.layers.7.mixer.q_proj.weight", PartitionSpec("tp", None)),
        ("backbone.layers.7.mixer.o_proj.weight", PartitionSpec(None, "tp")),
        ("backbone.embeddings.weight", PartitionSpec("tp", None)),
        ("lm_head.weight", PartitionSpec("tp", None)),
    ],
}


class TestFamilyRuleSharding:
    """Every family rule set must produce a mesh-attached NamedSharding for
    its representative tensors — the push-side annotation and the loader's
    placement planning both ride on these specs."""

    def test_every_family_has_cases(self):
        assert set(FAMILY_SPEC_CASES) == set(DEFAULT_RULES)

    @pytest.mark.parametrize("family", sorted(DEFAULT_RULES))
    def test_family_specs_on_mesh(self, family):
        mesh = make_mesh("dp=2,tp=4")
        for name, expected in FAMILY_SPEC_CASES[family]:
            s = sharding_for(name, rules_for_family(family), mesh)
            assert isinstance(s, NamedSharding), name
            assert s.mesh.shape == mesh.shape, name
            assert s.spec == expected, (family, name, s.spec)

    @pytest.mark.parametrize("family", sorted(DEFAULT_RULES))
    def test_catch_all_replicates_unknowns(self, family):
        # the trailing (".*", []) rule: an unmatched tensor replicates
        # rather than erroring — optimizer states, rope caches, etc.
        assert spec_for("totally.unknown.tensor", rules_for_family(family)) \
            == PartitionSpec()

    @pytest.mark.parametrize("family", sorted(DEFAULT_RULES))
    def test_rules_survive_annotation_round_trip(self, family):
        from modelx_tpu.dl.sharding import encode_rules

        rules = rules_for_family(family)
        assert decode_rules(encode_rules(rules)) == [
            (p, s) for p, s in rules
        ]

    def test_expert_axis_applies_on_ep_mesh(self):
        mesh = make_mesh("ep=2,tp=2")
        s = sharding_for("model.layers.0.block_sparse_moe.experts.w1.weight",
                         rules_for_family("mixtral"), mesh)
        assert s.spec == PartitionSpec("ep", "tp", None)


class TestCacheSharding:
    """KV-cache leaf placement: slots over dp, kv heads over tp, each axis
    only when it divides the dim (cache_sharding in dl/sharding.py)."""

    def test_dense_leaf_dp_and_tp(self):
        mesh = make_mesh("dp=2,tp=2")
        s = cache_sharding(mesh, (4, 96, 2, 32), batch_dim=0, head_dim=2)
        assert s.spec == PartitionSpec("dp", None, "tp", None)

    def test_indivisible_heads_replicate(self):
        # GQA with 3 kv heads on tp=2: the head dim replicates, dp still
        # splits the slots — no error, no silent corruption
        mesh = make_mesh("dp=2,tp=2")
        s = cache_sharding(mesh, (4, 96, 3, 32), batch_dim=0, head_dim=2)
        assert s.spec == PartitionSpec("dp", None, None, None)

    def test_indivisible_slots_replicate(self):
        mesh = make_mesh("dp=2,tp=2")
        s = cache_sharding(mesh, (3, 96, 2, 32), batch_dim=0, head_dim=2)
        assert s.spec == PartitionSpec(None, None, "tp", None)

    def test_paged_pool_page_dim_never_splits(self):
        # batch_dim=-1: pooled/paged leaves' leading dim is a GLOBAL page
        # index; only the head dim may shard
        mesh = make_mesh("dp=2,tp=2")
        s = cache_sharding(mesh, (8, 16, 2, 32), batch_dim=-1, head_dim=2)
        assert s.spec == PartitionSpec(None, None, "tp", None)

    def test_single_device_mesh_fully_replicated(self):
        mesh = make_mesh("dp=1")
        s = cache_sharding(mesh, (4, 96, 2, 32), batch_dim=0, head_dim=2)
        assert s.spec == PartitionSpec(None, None, None, None)
        assert s.is_fully_replicated


class TestShardedByteRanges:
    """The loader's placed ranged reads: a tp-sharded tensor's per-device
    row slices must map to disjoint byte ranges that cover the tensor —
    stream-to-placement fetches exactly 1/tp of the bytes per device."""

    def test_row_slices_partition_the_bytes(self):
        mesh = make_mesh("dp=2,tp=4")
        rows, cols = 16, 8
        itemsize = 4  # F32
        nbytes = rows * cols * itemsize
        info = st.TensorInfo(name="model.layers.0.self_attn.q_proj.weight",
                             dtype="F32", shape=(rows, cols),
                             start=1000, end=1000 + nbytes)
        sharding = sharding_for(info.name, LLAMA_RULES, mesh)
        assert sharding.spec == PartitionSpec("tp", None)

        ranges = set()
        for dev, idx in sharding.devices_indices_map((rows, cols)).items():
            r0, r1, step = idx[0].indices(rows)
            assert step == 1
            ranges.add(st.row_range(info, r0, r1))
        # tp=4 distinct shards (dp replicates: 8 devices, 4 unique ranges)
        assert len(ranges) == 4
        ordered = sorted(ranges)
        assert ordered[0][0] == 1000
        assert ordered[-1][1] == 1000 + nbytes
        for (a0, a1), (b0, b1) in zip(ordered, ordered[1:]):
            assert a1 == b0  # contiguous, disjoint
        assert all(b1 - b0 == nbytes // 4 for b0, b1 in ranges)

    def test_replicated_tensor_is_one_full_range(self):
        mesh = make_mesh("dp=2,tp=4")
        info = st.TensorInfo(name="model.norm.weight", dtype="F32",
                             shape=(64,), start=0, end=256)
        sharding = sharding_for(info.name, LLAMA_RULES, mesh)
        for dev, idx in sharding.devices_indices_map((64,)).items():
            r0, r1, _ = idx[0].indices(64)
            assert (r0, r1) == (0, 64)


class TestPushShardAnnotations:
    """Push attaches the family's layout rules and the pinned serving mesh
    to the manifest — a puller plans placed reads and per-device budgets
    before any blob byte moves."""

    def _write_llama_ckpt(self, d):
        tensors = {
            "model.embed_tokens.weight": np.zeros((8, 4), np.float32),
            "model.layers.0.self_attn.q_proj.weight": np.zeros((4, 4), np.float32),
            "model.layers.0.mlp.gate_proj.weight": np.zeros((8, 4), np.float32),
            "model.norm.weight": np.ones((4,), np.float32),
        }
        st.write_safetensors(str(d / "model.safetensors"), tensors)

    def test_shard_spec_annotation(self, tmp_path):
        from modelx_tpu.client.push import parse_manifest_from_dir
        from modelx_tpu.dl.sharding import encode_rules
        from modelx_tpu.types import AnnotationShardSpec

        self._write_llama_ckpt(tmp_path)
        manifest, _ = parse_manifest_from_dir(str(tmp_path))
        (blob,) = [b for b in manifest.blobs
                   if b.annotations.get(AnnotationShardSpec)]
        payload = blob.annotations[AnnotationShardSpec]
        assert decode_rules(payload) == decode_rules(
            encode_rules(rules_for_family("llama")))

    def test_mesh_annotation_from_sidecar(self, tmp_path):
        from modelx_tpu.client.push import parse_manifest_from_dir
        from modelx_tpu.types import AnnotationShardMesh

        self._write_llama_ckpt(tmp_path)
        (tmp_path / "modelx.yaml").write_text(
            "serving:\n  mesh: dp=2,tp=2\n")
        manifest, _ = parse_manifest_from_dir(str(tmp_path))
        assert manifest.annotations[AnnotationShardMesh] == "dp=2,tp=2"

    def test_no_sidecar_no_mesh_annotation(self, tmp_path):
        from modelx_tpu.client.push import parse_manifest_from_dir
        from modelx_tpu.types import AnnotationShardMesh

        self._write_llama_ckpt(tmp_path)
        manifest, _ = parse_manifest_from_dir(str(tmp_path))
        assert AnnotationShardMesh not in manifest.annotations


class _StubSet:
    def __init__(self):
        self.servers = {}


class _StubServer:
    def __init__(self, load_bytes):
        self.stats = {"load_bytes": load_bytes}
        self.model_dir = ""


class TestPerDeviceBudget:
    """--hbm-budget-bytes is PER-DEVICE: on a weight-sharding mesh the
    pool divides footprints by the mesh's weight-shard factor (ceiling —
    never round a footprint down to a free lunch)."""

    def _pool(self, mesh_spec=None, **kw):
        from modelx_tpu.dl.lifecycle import ModelPool

        mesh = make_mesh(mesh_spec) if mesh_spec else None
        return ModelPool(_StubSet(), mesh=mesh, **kw)

    def test_per_device_division(self):
        pool = self._pool("dp=2,tp=4")
        assert pool.weight_shard_factor == 4
        assert pool._per_device(1000) == 250
        assert pool._per_device(1001) == 251  # ceiling, not floor
        assert pool._per_device(0) == 0

    def test_dp_only_mesh_keeps_full_footprint(self):
        pool = self._pool("dp=8")
        assert pool.weight_shard_factor == 1
        assert pool._per_device(1000) == 1000

    def test_no_mesh_behaves_as_before(self):
        pool = self._pool(None)
        assert pool.weight_shard_factor == 1
        assert pool._per_device(12345) == 12345

    def test_mark_ready_tightens_to_per_device_bytes(self):
        from modelx_tpu.dl.lifecycle import ModelPool

        sset = _StubSet()
        sset.servers["m"] = _StubServer(load_bytes=1000)
        pool = ModelPool(sset, mesh=make_mesh("dp=2,tp=4"))
        pool.mark_ready("m")
        assert pool.entries["m"].hbm_reserved_bytes == 250

    def test_pool_snapshot_mesh_keys(self):
        pool = self._pool("dp=2,tp=2")
        snap = pool.pool_snapshot()
        assert snap["mesh"] == "dp=2,tp=2"
        assert snap["mesh_devices"] == 4
        assert snap["weight_shard_factor"] == 2

    def test_pool_snapshot_without_mesh_stays_legacy(self):
        snap = self._pool(None).pool_snapshot()
        assert "mesh" not in snap
        assert "weight_shard_factor" not in snap


class _FakeDevice:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, in_use, limit):
        self._in_use, self._limit = in_use, limit

    def memory_stats(self):
        return {"bytes_in_use": self._in_use, "bytes_limit": self._limit}


class _BareDevice:
    """A device without an accountant (CPU backend)."""

    platform, device_kind = "cpu", "cpu"


class TestDevmemPerDevice:
    def test_per_device_breakdown(self, monkeypatch):
        from modelx_tpu.utils import devmem

        monkeypatch.setattr(
            jax, "local_devices",
            lambda: [_FakeDevice(5, 10), _FakeDevice(7, 10)])
        out = devmem.raw_sample()
        assert out["source"] == "memory_stats"
        assert out["device_count"] == 2
        # what jax found rides the same block: a pod on the cpu backend
        # must not look like a healthy TPU pod
        assert (out["platform"], out["device_kind"]) == ("tpu", "TPU v5 lite")
        assert out["hbm_bytes_in_use"] == 12
        assert out["hbm_bytes_reservable"] == 5 + 3
        assert out["devices"]["0"] == {
            "hbm_bytes_in_use": 5, "hbm_bytes_reservable": 5}
        assert out["devices"]["1"] == {
            "hbm_bytes_in_use": 7, "hbm_bytes_reservable": 3}

    def test_accountant_free_device_skipped(self, monkeypatch):
        from modelx_tpu.utils import devmem

        monkeypatch.setattr(
            jax, "local_devices",
            lambda: [_FakeDevice(4, 8), _BareDevice()])
        out = devmem.raw_sample()
        assert out["device_count"] == 2
        assert set(out["devices"]) == {"0"}
        assert out["hbm_bytes_in_use"] == 4

    def test_sample_copy_isolates_cache(self, monkeypatch):
        from modelx_tpu.utils import devmem

        monkeypatch.setattr(jax, "local_devices",
                            lambda: [_FakeDevice(5, 10)])
        monkeypatch.setattr(devmem, "_cached", None)
        first = devmem.sample(max_age_s=60.0)
        first["devices"]["0"]["hbm_bytes_in_use"] = 999  # caller mutates
        second = devmem.sample(max_age_s=60.0)  # cache hit
        assert second["devices"]["0"]["hbm_bytes_in_use"] == 5


class TestPromexpDeviceLabel:
    def test_devices_dict_renders_with_device_label(self):
        from modelx_tpu.utils import promexp

        tree = {
            "default": {"requests_total": 3},
            "device": {
                "source": "memory_stats",
                "hbm_bytes_in_use": 12,
                "devices": {"0": {"hbm_bytes_in_use": 5},
                            "1": {"hbm_bytes_in_use": 7}},
            },
        }
        text = promexp.render(tree, label_levels={
            ("*",): "model",
            ("*", "devices", "*"): "device",
        })
        got = {}
        for line in text.splitlines():
            m = re.match(
                r'modelx_devices_hbm_bytes_in_use\{(.*)\} ([\d.e+-]+)$',
                line)
            if m:
                labels = dict(kv.split("=", 1) for kv in m.group(1).split(","))
                got[labels['device']] = float(m.group(2))
        assert got == {'"0"': 5.0, '"1"': 7.0}
        # the aggregate keeps its own family, no device label
        assert 'modelx_hbm_bytes_in_use{model="device"} 12' in text


# -- multi-device continuous decode -------------------------------------------


@pytest.fixture(scope="module")
def mesh_server(tmp_path_factory):
    from modelx_tpu.dl.serve import ModelServer
    from modelx_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                              dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    d = tmp_path_factory.mktemp("mesh-serve")
    st.write_safetensors(
        str(d / "model.safetensors"),
        {k: np.asarray(v) for k, v in params.items()})
    srv = ModelServer(str(d), mesh_spec="dp=2,tp=2", dtype="float32",
                      max_seq_len=96)
    srv.load()
    return srv


@pytest.fixture(scope="module")
def mesh_engine(mesh_server):
    from modelx_tpu.dl.continuous import ContinuousBatcher

    cb = ContinuousBatcher(mesh_server, max_slots=4, chunk_size=4)
    yield cb
    cb.close()


class TestMeshEngine:
    """Continuous decode on a real (forced-host) dp=2,tp=2 mesh. The
    exactness oracle is the plain path ON THE SAME MESH: tp row-parallel
    projections split float contractions, so cross-mesh outputs may
    legitimately differ in low bits — but engine-vs-plain on one mesh must
    stay byte-identical, exactly like the dp=1 suite."""

    def test_greedy_matches_server_on_mesh(self, mesh_server, mesh_engine):
        tokens = np.array([[5, 9, 2, 7, 1]], np.int32)
        expected = mesh_server.generate(tokens, max_new_tokens=11)
        got = mesh_engine.generate(tokens, max_new_tokens=11)
        np.testing.assert_array_equal(got, expected)

    def test_engine_mesh_telemetry_and_cache_placement(self, mesh_server,
                                                       mesh_engine):
        assert mesh_engine.mesh_devices == 4
        snap = mesh_engine.snapshot()
        assert snap["mesh"] == "dp=2,tp=2"
        assert snap["mesh_devices"] == 4
        # the KV state actually lives sharded on the mesh: dense leaves
        # [slots, len, Hkv, D] carry dp on slots and tp on kv heads
        placed = [
            leaf for leaf in jax.tree_util.tree_leaves(mesh_engine._cache)
            if hasattr(leaf, "sharding") and getattr(leaf, "ndim", 0) == 4
        ]
        assert placed, "no 4-D cache leaves found"
        for leaf in placed:
            assert isinstance(leaf.sharding, NamedSharding)
            # device_put canonicalizes the spec (trailing None dropped)
            assert leaf.sharding.spec == PartitionSpec("dp", None, "tp")

    def test_server_stats_mesh_keys(self, mesh_server):
        assert mesh_server.stats["mesh"] == "dp=2,tp=2"
        assert mesh_server.stats["mesh_devices"] == 4
        assert mesh_server.stats["weight_shard_factor"] == 2

    @pytest.mark.slow
    def test_sampled_matches_server_on_mesh(self, mesh_server, mesh_engine):
        tokens = np.array([[3, 4, 5]], np.int32)
        expected = mesh_server.generate(
            tokens, max_new_tokens=9, temperature=0.8, top_k=12, top_p=0.9,
            seed=41)
        got = mesh_engine.generate(
            tokens, max_new_tokens=9, temperature=0.8, top_k=12, top_p=0.9,
            seed=41)
        np.testing.assert_array_equal(got, expected)

    @pytest.mark.slow
    def test_multirow_matches_server_on_mesh(self, mesh_server, mesh_engine):
        tokens = np.array([[5, 9, 2], [8, 1, 1]], np.int32)
        expected = mesh_server.generate(tokens, max_new_tokens=6)
        got = mesh_engine.generate(tokens, max_new_tokens=6)
        np.testing.assert_array_equal(got, expected)

    @pytest.mark.slow
    def test_paged_pool_on_mesh(self, mesh_server):
        from modelx_tpu.dl.continuous import ContinuousBatcher

        cb = ContinuousBatcher(mesh_server, max_slots=4, chunk_size=4,
                               page_size=16)
        try:
            tokens = np.array([[5, 9, 2, 7, 1]], np.int32)
            expected = mesh_server.generate(tokens, max_new_tokens=11)
            got = cb.generate(tokens, max_new_tokens=11)
            np.testing.assert_array_equal(got, expected)
            # pooled leaves: page dim global (never split), heads over tp
            placed = [
                leaf for leaf in jax.tree_util.tree_leaves(cb._cache)
                if hasattr(leaf, "sharding") and getattr(leaf, "ndim", 0) == 4
            ]
            assert placed
            for leaf in placed:
                assert leaf.sharding.spec[0] is None
                assert "tp" in tuple(
                    a for a in leaf.sharding.spec if a is not None
                )
        finally:
            cb.close()

    @pytest.mark.slow
    def test_dp_only_mesh(self, tmp_path_factory):
        """dp=4: weights replicate, the cache shards its slot dim — and
        engine-vs-plain exactness holds like on every other mesh."""
        from modelx_tpu.dl.continuous import ContinuousBatcher
        from modelx_tpu.dl.serve import ModelServer
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                                  dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        d = tmp_path_factory.mktemp("dp-only")
        st.write_safetensors(
            str(d / "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()})
        srv = ModelServer(str(d), mesh_spec="dp=4", dtype="float32",
                          max_seq_len=96)
        srv.load()
        assert srv.stats["weight_shard_factor"] == 1
        cb = ContinuousBatcher(srv, max_slots=4, chunk_size=4)
        try:
            tokens = np.array([[5, 9, 2, 7]], np.int32)
            np.testing.assert_array_equal(
                cb.generate(tokens, max_new_tokens=8),
                srv.generate(tokens, max_new_tokens=8))
        finally:
            cb.close()


def test_a_deepseek_v32_checkpoints_names_detect_the_deepseek_v2_family():
    """One module, one row: the indexer's tensors and the router's bias beside
    ``kv_a_proj_with_mqa`` are still the deepseek_v2 family's."""
    from modelx_tpu.dl.sharding import infer_family

    names = ["model.embed_tokens.weight", "model.layers.0.self_attn.kv_a_proj_with_mqa.weight",
             "model.layers.0.self_attn.indexer.wq_b.weight",
             "model.layers.1.mlp.gate.e_score_correction_bias",
             "model.layers.1.mlp.experts.3.gate_proj.weight"]
    assert infer_family(names) == "deepseek_v2"
