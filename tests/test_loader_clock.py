"""The loader's clock says what waits (ISSUE 40): every wall second of a
``load_safetensors`` call falls to a read, a put, host work between them, or
nothing, and ``ModelServer.load`` sums the calls and the gaps between them.
On the CPU backend: the sums close and each cause shows under its own name;
never a time."""

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import loader
from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.serve import ModelServer
from modelx_tpu.parallel.mesh import make_mesh

FILES = 3


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    """A tiny llama in three safetensors files."""
    from modelx_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
    params = {k: np.asarray(v) for k, v in
              llama.init_params(cfg, jax.random.PRNGKey(0)).items()}
    d = tmp_path_factory.mktemp("loader_clock")
    names = sorted(params)
    for i in range(FILES):
        st.write_safetensors(str(d / f"model-{i + 1:05d}-of-{FILES:05d}.safetensors"),
                             {k: params[k] for k in names[i::FILES]})
    return str(d)


@pytest.fixture
def mesh():
    return make_mesh("dp=1", jax.devices()[:1])


def one_file(tmp_path, tensors: dict) -> loader.LocalFileSource:
    path = str(tmp_path / "w.safetensors")
    st.write_safetensors(path, tensors)
    return loader.LocalFileSource(path)


def tiled(stats: loader.LoadStats) -> float:
    """The wall seconds the clock gave to a read or a put, to host work
    alone, or to nothing."""
    return (stats.fetch_busy_seconds + stats.device_put_seconds - stats.overlap_seconds
            + stats.assemble_seconds + stats.idle_seconds)


def slow(monkeypatch, target, name: str, seconds: float):
    real = getattr(target, name)

    def slowed(*args, **kwargs):
        time.sleep(seconds)
        return real(*args, **kwargs)

    monkeypatch.setattr(target, name, slowed)


class TestTheTilingOfALoad:
    def test_read_put_assemble_and_idle_sum_to_the_shards_span(self, model_dir, monkeypatch):
        # reads of 20 ms make the load long against the millisecond the
        # stats are rounded to, so that 2 % means something
        slow(monkeypatch, loader, "_read_with_retry", 0.02)
        seen = []
        real = loader.load_safetensors

        def spy(*args, **kwargs):
            arrays, stats = real(*args, **kwargs)
            seen.append(stats)
            time.sleep(0.01)  # between two files' calls: idle
            return arrays, stats

        monkeypatch.setattr(loader, "load_safetensors", spy)
        srv = ModelServer(model_dir, mesh_spec="dp=1", dtype="float32", max_seq_len=96)
        stats = srv.load()
        assert len(seen) == FILES == stats["load_shard_files"]
        shards = stats["load_shards_seconds"]
        assert 0 < shards <= stats["load_seconds"]
        busy = (stats["load_fetch_busy_seconds"] + stats["load_device_put_seconds"]
                - stats["load_overlap_seconds"] + stats["load_assemble_seconds"])
        assert busy + stats["load_idle_seconds"] == pytest.approx(shards, rel=0.02)
        # the gaps between the calls went to the idle seconds
        assert stats["load_idle_seconds"] >= sum(s.idle_seconds for s in seen) + FILES * 0.009
        for key in ("idle", "backpressure", "assemble", "drain", "fetch_busy", "device_put",
                    "overlap"):
            assert stats[f"load_{key}_seconds"] >= sum(
                getattr(s, f"{key}_seconds") for s in seen) - 2e-3, key
        assert "load_fetch_seconds" not in stats  # thread-seconds: gone (ISSUE 40)
        # each call has a drain of its own: the last read's end -> its return
        assert stats["load_drain_seconds"] == pytest.approx(
            sum(s.drain_seconds for s in seen), abs=2e-3)
        assert all(0 < s.drain_seconds <= s.total_seconds for s in seen)

    def test_each_call_is_tiled_from_its_first_line_to_its_return(self, tmp_path, mesh):
        src = one_file(tmp_path, {f"t{i}": np.full((64, 64), i, np.float32) for i in range(6)})
        _, stats = loader.load_safetensors(src, mesh, [])
        assert tiled(stats) == pytest.approx(stats.total_seconds, rel=0.02)
        assert 0 <= stats.overlap_seconds <= min(stats.fetch_busy_seconds,
                                                 stats.device_put_seconds) + 1e-6
        assert stats.idle_seconds > 0  # the header and the plan, at the least

    def test_a_single_file_load_has_one_drain(self, tmp_path, monkeypatch):
        d = tmp_path / "one"
        d.mkdir()
        from modelx_tpu.models import llama

        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), dtype=jnp.float32)
        st.write_safetensors(str(d / "model.safetensors"), {
            k: np.asarray(v) for k, v in llama.init_params(cfg, jax.random.PRNGKey(1)).items()})
        seen = []
        real = loader.load_safetensors
        monkeypatch.setattr(loader, "load_safetensors",
                            lambda *a, **kw: seen.append(real(*a, **kw)) or seen[-1])
        stats = ModelServer(str(d), mesh_spec="dp=1", dtype="float32", max_seq_len=96).load()
        ((_, only),) = seen
        assert stats["load_shard_files"] == 1
        assert stats["load_drain_seconds"] == pytest.approx(only.drain_seconds, abs=1e-3)
        assert stats["load_idle_seconds"] == pytest.approx(
            only.idle_seconds + stats["load_shards_seconds"] - only.total_seconds, abs=3e-3)


class TestWhatWaits:
    TENSORS = {f"t{i}": np.full((256, 256), i, np.float32) for i in range(8)}  # 256 KiB each

    def test_slow_puts_under_a_small_budget_show_as_backpressure(
            self, tmp_path, mesh, monkeypatch):
        slow(monkeypatch, jax, "device_put", 0.03)
        _, stats = loader.load_safetensors(
            one_file(tmp_path, self.TENSORS), mesh, [], concurrency=4, transfer_concurrency=1,
            pack_threshold=0, transfer_budget_bytes=256 << 10)
        # one array fits the budget: the next read waits for the put before it
        assert stats.backpressure_seconds >= 0.5 * stats.total_seconds
        assert stats.backpressure_seconds >= 6 * 0.03
        assert stats.device_put_seconds >= 8 * 0.03
        assert stats.assemble_seconds < 0.02
        assert tiled(stats) == pytest.approx(stats.total_seconds, rel=0.02)

    def test_a_slow_read_is_neither_backpressure_nor_host_work(self, tmp_path, mesh, monkeypatch):
        slow(monkeypatch, loader, "_read_with_retry", 0.03)
        _, stats = loader.load_safetensors(
            one_file(tmp_path, self.TENSORS), mesh, [], concurrency=2, pack_threshold=0)
        assert stats.fetch_busy_seconds >= 4 * 0.03
        assert stats.fetch_busy_seconds >= 0.5 * stats.total_seconds
        assert stats.backpressure_seconds < 0.02 and stats.assemble_seconds < 0.02
        assert tiled(stats) == pytest.approx(stats.total_seconds, rel=0.02)

    def test_fused_experts_are_read_where_they_are_put(self, tmp_path, mesh, monkeypatch):
        experts = {f"model.layers.0.block_sparse_moe.experts.{e}.w1.weight":
                   np.full((32, 16), e, np.float32) for e in range(4)}

        def no_stack(*args, **kwargs):
            raise AssertionError("a fold is not stacked: its members are read in place")

        monkeypatch.setattr(loader.np, "stack", no_stack)
        arrays, stats = loader.load_safetensors(one_file(tmp_path, experts), mesh, [])
        monkeypatch.undo()
        (name,) = arrays  # the four members came back as one stacked tensor
        assert arrays[name].shape == (4, 32, 16)
        np.testing.assert_array_equal(
            np.asarray(arrays[name]), np.stack(list(experts.values())))
        # no host work between the reads and the put, and no byte written twice
        assert stats.assemble_seconds < 0.02
        assert stats.assemble_copied_bytes == 0
        assert stats.backpressure_seconds < 0.02
        assert tiled(stats) == pytest.approx(stats.total_seconds, rel=0.02)

    def test_a_host_side_cast_shows_as_assemble(self, tmp_path, mesh):
        src = one_file(tmp_path, {"big": np.ones((2048, 2048), np.float16)})  # 8 MB, 16 cast
        arrays, stats = loader.load_safetensors(src, mesh, [], dtype=np.float32)
        assert arrays["big"].dtype == np.float32
        assert stats.assemble_seconds > 0.001  # 4 M elements widened on the host
        assert stats.assemble_copied_bytes == 2048 * 2048 * 4  # the cast's result
        assert tiled(stats) == pytest.approx(stats.total_seconds, rel=0.02)
