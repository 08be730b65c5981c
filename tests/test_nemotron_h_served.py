"""nemotron_h through the normal path, all real processes: ``modelx push`` ->
``modelx dl`` -> ``modelx serve-model --continuous-batch`` on a checkpoint that
holds a share of the experts under per-expert names (the loader folds them),
the engine's tokens held against the float32 reference — logits, not tokens:
each token the engine chose must lie within float32 rounding of the
reference's maximum — beside idle and filling slots; the options a state
cannot carry refused at start-up by name; the benchmark's new cell, rehearsed
end to end; and a pod of another family that never loads this one."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax

# the pod helpers of the other served family tests
from test_minicpm_sala_served import CLI, ENV, ROOT, free_port, request, stop, wait_ready

from modelx_tpu.dl import safetensors as st
from modelx_tpu.models import nemotron_h as nh, nemotron_h_reference as reference


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """Experts 4-11 of 16 under the router's 16 outputs, as a push holds them:
    per-expert names, ``config.json`` with the share key."""
    src = tmp_path_factory.mktemp("nemh_src")
    cfg = nh.NemotronHConfig.tiny(vocab_size=96, expert_first=4, expert_count=8)
    params = nh.init_params(cfg, jax.random.PRNGKey(2))
    hf = nh.to_hf_state_dict(params, first=4)
    raw = nh.to_hf_config(cfg)
    st.write_safetensors(str(src / "model.safetensors"), hf)
    (src / "config.json").write_text(json.dumps(raw))
    return src, hf, raw


def test_push_dl_serve_model_follows_the_reference(checkpoint, tmp_path):
    src, hf, raw = checkpoint
    assert "backbone.layers.1.mixer.experts.4.up_proj.weight" in hf
    assert "backbone.layers.1.mixer.experts.0.up_proj.weight" not in hf
    procs = []
    try:
        reg_port, pod_port = free_port(), free_port()
        reg_log = open(tmp_path / "registry.log", "wb")
        reg = subprocess.Popen(CLI + ["serve", "--listen", f"127.0.0.1:{reg_port}", "--data",
                                      str(tmp_path / "reg")], env=ENV, stdout=reg_log,
                               stderr=subprocess.STDOUT)
        procs.append(reg)
        wait_ready(reg_port, reg, tmp_path / "registry.log")
        ref = f"http://127.0.0.1:{reg_port}/library/nemh-tiny@v1"
        for argv in (["init", str(src)], ["push", ref, str(src)], ["dl", ref, str(tmp_path / "vol")]):
            done = subprocess.run(CLI + argv, env=ENV, capture_output=True, text=True, timeout=240)
            assert done.returncode == 0, (argv, done.stderr[-2000:])
        assert (tmp_path / "vol" / "config.json").exists()  # the pattern, the heads, the share
        pod_log = open(tmp_path / "pod.log", "wb")
        pod = subprocess.Popen(  # no flag names the model
            CLI + ["serve-model", "--model-dir", str(tmp_path / "vol"), "--listen",
                   f"127.0.0.1:{pod_port}", "--dtype", "float32", "--continuous-batch",
                   "--max-seq-len", "128", "--max-slots", "4", "--drain-seconds", "0",
                   "--exit-with-parent"],
            env=ENV, stdout=pod_log, stderr=subprocess.STDOUT)
        procs.append(pod)
        wait_ready(pod_port, pod, tmp_path / "pod.log")
        rng = np.random.default_rng(0)
        # a padded bucket (9 of 16) and a longer one (45 of 48), sent TOGETHER, so that
        # each decodes beside the other's admission and beside two idle slots
        asks = [(rng.integers(1, 96, 9).tolist(), 40), (rng.integers(1, 96, 45).tolist(), 50)]
        got: dict[int, list] = {}

        def ask(i):
            prompt, new = asks[i]
            got[i] = request(pod_port, "POST", "/v1/generate",
                             {"tokens": [prompt], "max_new_tokens": new})

        threads = [threading.Thread(target=ask, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, (prompt, new) in enumerate(asks):
            status, body = got[i]
            assert status == 200, body
            out = body["tokens"][0][-new:]
            logits = np.asarray(reference.forward(hf, raw, prompt + out))[len(prompt) - 1:-1]
            below = logits.max(-1) - logits[np.arange(new), out]
            # float32 on both sides: the engine's token is the reference's argmax but
            # for rounding (1e-3 of logits with a standard deviation of 1); bfloat16
            # moves a logit by 2e-2 and fails this
            assert below.max() < 1e-3
        seq = rng.integers(1, 96, 70).tolist()
        status, body = request(pod_port, "POST", "/v1/forward", {"tokens": [seq]})
        assert status == 200, body
        assert body["logits_argmax"][0] == np.asarray(
            reference.forward(hf, raw, seq)).argmax(-1).tolist()
        _, metrics = request(pod_port, "GET", "/metrics")
        engine = metrics["default"]["continuous"]
        assert metrics["default"]["family"] == "nemotron_h"
        assert metrics["default"]["load_bytes"] == sum(v.nbytes for v in hf.values())
        # two Mamba layers' float32 states [8, 4, 8] and tails [3, 64]; one attention layer
        assert engine["kv"]["bytes_state"] == 2 * 4 * (8 * 4 * 8 + 3 * 64) * 4
        assert engine["kv"]["bytes_full"] == 2 * 4 * 128 * 16 * 4
        ssm, moe = engine["ssm"], engine["moe"]
        assert ssm["steps_all"] % 4 == 0 and 0 < ssm["steps_live"] < ssm["steps_all"]
        assert ssm["positions_live"] > ssm["steps_live"]
        assert (ssm["layers"], ssm["heads"], ssm["state_size"], ssm["conv_kernel"]) == (2, 8, 8, 4)
        assert (moe["held_experts"], moe["published_experts"], moe["latent_size"]) == (8, 16, 16)
        assert 0 < moe["assignments_held"] < moe["assignments"]
    finally:
        stop(procs)


@pytest.mark.parametrize("flags,message", [
    (["--kv-page-size", "16"], "--kv-page-size"),
    (["--speculative-k", "2"], "--speculative-k"),
    (["--prefix-cache", "4"], "--prefix-cache"),
])
def test_serve_model_refuses_what_a_state_cannot_carry(checkpoint, tmp_path, flags, message):
    src, _, _ = checkpoint
    pod = subprocess.run(
        CLI + ["serve-model", "--model-dir", str(src), "--listen", f"127.0.0.1:{free_port()}",
               "--dtype", "float32", "--continuous-batch", "--max-seq-len", "128", "--max-slots",
               "4", "--drain-seconds", "0", *flags],
        env=ENV, capture_output=True, text=True, timeout=240)
    assert pod.returncode != 0
    assert message in pod.stderr + pod.stdout and "'state' leaves" in pod.stderr + pod.stdout


def test_rehearse_of_the_benchmarks_new_cell_ends():
    """The cell's files, the checkpoint layout, the pod's flags, the new
    readers: walked at the tiny preset, as ``--rehearse`` always ends."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "nemotron-3-super-ep4-d11.agent", "--rehearse", "--trace", "1"],
        # one CPU device, as a pod finds it: the suite's eight virtual ones make the
        # traced window's threads fight over the cores (41 s against 3.5 minutes)
        env=dict(ENV, XLA_FLAGS=""), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 0
    probes = next(l for l in lines if l.get("phase") == "probes")
    assert probes["argmax_agreement"] >= 0.9  # float32 here: the two programs agree
    metrics = last["metrics"]
    assert metrics["ssm.state_gb.agent"]["value"] > 0
    assert 0 < metrics["ssm.live_share.agent"]["value"] <= 1
    assert metrics["ssm.live_share.agent"]["value"] == pytest.approx(
        1 - metrics["engine.pad_fraction.agent"]["value"], abs=0.05)
    assert 0 < metrics["moe.held_assignment_share.agent"]["value"] < 1
    assert 0 < metrics["moe.read_hit_share.agent"]["value"] <= 1  # the einsums read every held one
    assert "engine.wait_ms.agent" in metrics and "engine.boundary_host_ms.agent" in metrics
    rehearsed = next(l for l in lines if l.get("phase", "").startswith("rehearsed_on_a_cpu"))
    assert rehearsed["model.decode_step_ms.agent"] > 0  # the steps were the program's own count


def test_a_phi3_pods_start_loads_neither_the_family_nor_its_ops():
    """PR 42 was lost on the deploy cell's spread: this family stays off that
    pod's start path. Importing the pod's entry point and resolving another
    family loads no module this PR adds."""
    code = (
        "import sys\n"
        "import modelx_tpu.dl.serve_main, modelx_tpu.dl.continuous\n"
        "from modelx_tpu.dl import families\n"
        "fam = families.detect(['model.layers.0.self_attn.qkv_proj.weight'])\n"
        "assert fam.name == 'phi3', fam.name\n"
        "fam.decode_fns\n"
        "new = ['modelx_tpu.models.nemotron_h', 'modelx_tpu.models.nemotron_h_reference',\n"
        "       'modelx_tpu.ops.ssm']\n"
        "print([m for m in new if m in sys.modules])\n")
    out = subprocess.run([sys.executable, "-c", code], env=ENV, capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
