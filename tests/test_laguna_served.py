"""laguna through the normal path, all real processes: ``modelx push`` ->
``modelx dl`` -> ``modelx serve-model --continuous-batch``, the engine's
tokens held against the float32 reference; and the benchmark's new cell,
rehearsed end to end."""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from modelx_tpu.dl import safetensors as st
from modelx_tpu.models import laguna, laguna_reference as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = [sys.executable, "-m", "modelx_tpu.cli"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def request(port, method, path, body=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=None if body is None else json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, (json.loads(raw) if raw.startswith(b"{") else raw)
    finally:
        conn.close()


def wait_ready(port, proc, log, timeout=240):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        assert proc.poll() is None, f"exited {proc.returncode}: {open(log).read()[-3000:]}"
        try:
            if request(port, "GET", "/healthz", timeout=5)[0] == 200:
                return
        except OSError:
            pass
        time.sleep(0.2)
    raise AssertionError(f"not ready: {open(log).read()[-3000:]}")


def test_push_dl_serve_model_continuous_batch_follows_the_reference(tmp_path):
    cfg = laguna.LagunaConfig.tiny(vocab_size=96, expert_first=0, expert_count=8)
    params = laguna.init_params(cfg, jax.random.PRNGKey(2))
    hf, raw = laguna.to_hf_state_dict(params), laguna.to_hf_config(cfg)
    src = tmp_path / "src"
    src.mkdir()
    st.write_safetensors(str(src / "model.safetensors"), hf)
    (src / "config.json").write_text(json.dumps(raw))
    procs = []
    try:
        reg_port, pod_port = free_port(), free_port()
        reg_log = open(tmp_path / "registry.log", "wb")
        reg = subprocess.Popen(CLI + ["serve", "--listen", f"127.0.0.1:{reg_port}", "--data",
                                      str(tmp_path / "reg")], env=ENV, stdout=reg_log,
                               stderr=subprocess.STDOUT)
        procs.append(reg)
        wait_ready(reg_port, reg, tmp_path / "registry.log")
        ref = f"http://127.0.0.1:{reg_port}/library/laguna-tiny@v1"
        for argv in (["init", str(src)], ["push", ref, str(src)], ["dl", ref, str(tmp_path / "vol")]):
            done = subprocess.run(CLI + argv, env=ENV, capture_output=True, text=True, timeout=240)
            assert done.returncode == 0, (argv, done.stderr[-2000:])
        assert (tmp_path / "vol" / "config.json").exists()  # the share key travels with the weights
        pod_log = open(tmp_path / "pod.log", "wb")
        pod = subprocess.Popen(
            CLI + ["serve-model", "--model-dir", str(tmp_path / "vol"), "--listen",
                   f"127.0.0.1:{pod_port}", "--dtype", "float32", "--continuous-batch",
                   "--max-seq-len", "128", "--max-slots", "4", "--drain-seconds", "0",
                   "--exit-with-parent"], env=ENV, stdout=pod_log, stderr=subprocess.STDOUT)
        procs.append(pod)
        wait_ready(pod_port, pod, tmp_path / "pod.log")
        prompt = np.random.default_rng(0).integers(1, 96, 21).tolist()
        status, body = request(pod_port, "POST", "/v1/generate",
                               {"tokens": [prompt], "max_new_tokens": 50})
        assert status == 200, body
        out = body["tokens"][0][-50:]
        seq = prompt + out
        want = np.asarray(reference.forward(hf, raw, seq)).argmax(-1)[len(prompt) - 1:-1]
        assert out == want.tolist()  # 50 tokens: the ring of 32 wrapped
        _, metrics = request(pod_port, "GET", "/metrics")
        engine = metrics["default"]["continuous"]
        assert metrics["default"]["family"] == "laguna"
        assert metrics["default"]["load_bytes"] == sum(v.nbytes for v in hf.values())
        assert engine["kv"]["window_positions"] == 32 and engine["moe"]["published_experts"] == 16
        assert engine["moe"]["held_experts"] == 8 and engine["moe"]["assignments"] > 0
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(30)
            except subprocess.TimeoutExpired:
                p.kill()


def test_rehearse_of_the_benchmarks_new_cell_ends():
    """The cell's files, the checkpoint layout, the pod's flags, the new
    readers: walked at the tiny preset, as ``--rehearse`` always ends."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "laguna-s-2.1-ep2-d5.reason", "--rehearse", "--trace", "1"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 0
    probes = next(l for l in lines if l.get("phase") == "probes")
    assert probes["argmax_agreement"] >= 0.9  # float32 here: the two programs agree
    metrics = last["metrics"]
    assert 0.3 < metrics["moe.held_assignment_share.reason"]["value"] < 0.7
    assert 0 < metrics["moe.held_hit_share.reason"]["value"] <= 1
    rehearsed = next(l for l in lines if l.get("phase", "").startswith("rehearsed_on_a_cpu"))
    assert rehearsed["model.decode_step_ms.reason"] > 0  # the depth was read from the module names
