"""minicpm_sala through the normal path, all real processes: ``modelx push`` ->
``modelx dl`` -> ``modelx serve-model --continuous-batch --prefill-chunk``, the
engine's tokens held against the float32 reference on both sides of
``dense_len``; the options a state cannot carry refused at start-up by name;
and the benchmark's new cell, rehearsed end to end."""

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
from modelx_tpu.models import minicpm_sala as sala, minicpm_sala_reference as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = [sys.executable, "-m", "modelx_tpu.cli"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def request(port, method, path, body=None, timeout=240):
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


def stop(procs):
    for p in procs:
        p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(30)
        except subprocess.TimeoutExpired:
            p.kill()


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """Layers 2-4 of a "published" eight, as a push holds them."""
    src = tmp_path_factory.mktemp("sala_src")
    cfg = sala.SalaConfig.tiny(vocab_size=96)
    params = {k: np.asarray(v) for k, v in sala.init_params(cfg, jax.random.PRNGKey(2)).items()}
    raw = sala.to_hf_config(cfg)
    st.write_safetensors(str(src / "model.safetensors"), params)
    (src / "config.json").write_text(json.dumps(raw))
    return src, params, raw


def test_push_dl_serve_model_with_chunked_prefill_follows_the_reference(checkpoint, tmp_path):
    src, params, raw = checkpoint
    procs = []
    try:
        reg_port, pod_port = free_port(), free_port()
        reg_log = open(tmp_path / "registry.log", "wb")
        reg = subprocess.Popen(CLI + ["serve", "--listen", f"127.0.0.1:{reg_port}", "--data",
                                      str(tmp_path / "reg")], env=ENV, stdout=reg_log,
                               stderr=subprocess.STDOUT)
        procs.append(reg)
        wait_ready(reg_port, reg, tmp_path / "registry.log")
        ref = f"http://127.0.0.1:{reg_port}/library/sala-tiny@v1"
        for argv in (["init", str(src)], ["push", ref, str(src)], ["dl", ref, str(tmp_path / "vol")]):
            done = subprocess.run(CLI + argv, env=ENV, capture_output=True, text=True, timeout=240)
            assert done.returncode == 0, (argv, done.stderr[-2000:])
        assert (tmp_path / "vol" / "config.json").exists()  # mixer types, scales, the share key
        pod_log = open(tmp_path / "pod.log", "wb")
        pod = subprocess.Popen(  # no flag names the model
            CLI + ["serve-model", "--model-dir", str(tmp_path / "vol"), "--listen",
                   f"127.0.0.1:{pod_port}", "--dtype", "float32", "--continuous-batch",
                   "--max-seq-len", "128", "--max-slots", "4", "--prefill-chunk", "16",
                   "--drain-seconds", "0", "--exit-with-parent"],
            env=ENV, stdout=pod_log, stderr=subprocess.STDOUT)
        procs.append(pod)
        wait_ready(pod_port, pod, tmp_path / "pod.log")
        rng = np.random.default_rng(0)
        for prompt_len, new in ((9, 40), (45, 50)):  # admitted whole; landed in three pieces
            prompt = rng.integers(1, 96, prompt_len).tolist()
            status, body = request(pod_port, "POST", "/v1/generate",
                                   {"tokens": [prompt], "max_new_tokens": new})
            assert status == 200, body
            out = body["tokens"][0][-new:]
            logits = np.asarray(reference.forward(params, raw, prompt + out))[prompt_len - 1:-1]
            below = logits.max(-1) - logits[np.arange(new), out]
            assert below.max() < 1e-3  # decode crosses dense_len = 32, and starts past it
        seq = rng.integers(1, 96, 70).tolist()
        status, body = request(pod_port, "POST", "/v1/forward", {"tokens": [seq]})
        assert status == 200, body
        assert body["logits_argmax"][0] == np.asarray(
            reference.forward(params, raw, seq)).argmax(-1).tolist()
        _, metrics = request(pod_port, "GET", "/metrics")
        engine = metrics["default"]["continuous"]
        assert metrics["default"]["family"] == "minicpm_sala"
        assert metrics["default"]["load_bytes"] == sum(v.nbytes for v in params.values())
        assert engine["fill"]["pieces"] == 3 and engine["fill"]["tokens"] == 45
        assert engine["kv"]["bytes_state"] == 2 * 4 * 4 * 8 * 8 * 4 and engine["kv"]["bytes_index"] > 0
        assert engine["sparse"]["steps_sparse"] > 0 and engine["sparse"]["dense_len"] == 32
        # who fetched the selected blocks: on the CPU the gather, never the kernel
        assert engine["sparse"]["steps_kernel"] == 0
        assert engine["sparse"]["positions_read"] < engine["sparse"]["positions_cached"]
    finally:
        stop(procs)


@pytest.mark.parametrize("flags,message", [
    (["--kv-page-size", "16"], "--kv-page-size"),
    (["--speculative-k", "2"], "--speculative-k"),
    (["--prefix-cache", "4"], "--prefix-cache"),
])
def test_serve_model_refuses_what_a_state_cannot_carry_at_start_up(checkpoint, tmp_path, flags, message):
    src, _, _ = checkpoint
    pod = subprocess.run(
        CLI + ["serve-model", "--model-dir", str(src), "--listen", f"127.0.0.1:{free_port()}",
               "--dtype", "float32", "--continuous-batch", "--max-seq-len", "128", "--max-slots",
               "4", "--drain-seconds", "0", *flags],
        env=ENV, capture_output=True, text=True, timeout=240)
    assert pod.returncode != 0
    assert message in pod.stderr + pod.stdout and "state" in pod.stderr + pod.stdout


def test_rehearse_of_the_benchmarks_new_cell_ends():
    """The cell's files, the checkpoint layout, the pod's flags, the primed
    generator, the new readers: walked at the tiny preset, as ``--rehearse``
    always ends."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "minicpm-sala-d12.longctx", "--rehearse", "--trace", "1"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 0
    probes = next(l for l in lines if l.get("phase") == "probes")
    assert probes["argmax_agreement"] >= 0.9  # float32 here: the two programs agree
    metrics = last["metrics"]
    assert metrics["sparse.engaged_share.longctx"]["value"] > 0.5  # prompts land past dense_len
    assert 0 < metrics["sparse.kv_read_share.longctx"]["value"] < 0.6
    assert metrics["linear.state_gb.longctx"]["value"] > 0
    assert "engine.fill_pieces.longctx" in metrics and "engine.wait_ms.longctx" in metrics
    rehearsed = next(l for l in lines if l.get("phase", "").startswith("rehearsed_on_a_cpu"))
    assert rehearsed["model.decode_step_ms.longctx"] > 0  # the depth was read from the module names
