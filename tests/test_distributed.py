"""Multi-host helpers (parallel/distributed.py). Real multi-process runs
need a pod; these cover env resolution, idempotence, and host-work splits."""

import pytest

from modelx_tpu.parallel import distributed


class TestInitialize:
    def test_single_process_noop(self, monkeypatch):
        for k in ("MODELX_COORDINATOR", "MODELX_NUM_PROCESSES", "MODELX_PROCESS_ID",
                  "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS", "CLOUD_TPU_TASK_ID"):
            monkeypatch.delenv(k, raising=False)
        monkeypatch.setattr(distributed, "_initialized", False)
        called = []
        monkeypatch.setattr(distributed.jax.distributed, "initialize",
                            lambda **kw: called.append(kw))
        distributed.initialize()
        assert not called  # nothing configured -> no-op

    def test_env_resolution(self, monkeypatch):
        monkeypatch.setattr(distributed, "_initialized", False)
        monkeypatch.setenv("MODELX_COORDINATOR", "10.0.0.1:1234")
        monkeypatch.setenv("MODELX_NUM_PROCESSES", "4")
        monkeypatch.setenv("MODELX_PROCESS_ID", "2")
        called = []
        monkeypatch.setattr(distributed.jax.distributed, "initialize",
                            lambda **kw: called.append(kw))
        distributed.initialize()
        assert called == [{
            "coordinator_address": "10.0.0.1:1234",
            "num_processes": 4,
            "process_id": 2,
        }]

    def test_single_host_tpu_env_is_one_process(self, monkeypatch):
        """What the one-host v5e machine sets (seen on the chip, PR 21):
        pod variables naming ONE worker must not start the coordinator
        handshake — it has nobody to talk to and used to fail into a
        swallowed warning."""
        for k in ("MODELX_COORDINATOR", "MODELX_NUM_PROCESSES",
                  "MEGASCALE_COORDINATOR_ADDRESS"):
            monkeypatch.delenv(k, raising=False)
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        monkeypatch.setenv("TPU_WORKER_ID", "0")
        monkeypatch.setattr(distributed, "_initialized", False)
        called = []
        monkeypatch.setattr(distributed.jax.distributed, "initialize",
                            lambda **kw: called.append(kw))
        distributed.initialize()
        assert not called

    def test_multi_host_failure_raises(self, monkeypatch):
        """Several workers configured and the handshake fails: the error
        surfaces instead of a pod that serves a fraction of its mesh."""
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-0,host-1")
        monkeypatch.setattr(distributed, "_initialized", False)

        def boom(**kw):
            raise RuntimeError("coordinator unreachable")

        monkeypatch.setattr(distributed.jax.distributed, "initialize", boom)
        with pytest.raises(RuntimeError, match="coordinator unreachable"):
            distributed.initialize()
        assert distributed._initialized is False

    def test_idempotent(self, monkeypatch):
        monkeypatch.setattr(distributed, "_initialized", True)
        called = []
        monkeypatch.setattr(distributed.jax.distributed, "initialize",
                            lambda **kw: called.append(kw))
        distributed.initialize("x:1", 2, 0)
        assert not called


class TestHostLocalSlice:
    def test_single_process_gets_all(self):
        assert distributed.host_local_slice(10) == (0, 10)

    @pytest.mark.parametrize("idx,count,total,want", [
        (0, 4, 10, (0, 3)), (1, 4, 10, (3, 6)), (3, 4, 10, (9, 10)),
        (3, 4, 2, (2, 2)),  # more hosts than items: trailing hosts idle
    ])
    def test_even_split(self, monkeypatch, idx, count, total, want):
        monkeypatch.setattr(distributed, "process_span", lambda: (idx, count))
        assert distributed.host_local_slice(total) == want
