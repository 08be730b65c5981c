"""deepseek_v2 through the normal path, all real processes: ``modelx push`` ->
``modelx dl`` -> ``modelx serve-model --continuous-batch --prefill-chunk`` on a
checkpoint that holds a share of the experts under per-expert names (the loader
folds them), the engine's tokens held against the float32 reference — logits,
not tokens: each token the engine chose must lie within float32 rounding of the
reference's maximum — beside idle and filling slots; the options no test holds
over latent lines refused at start-up by name; the benchmark's new cell,
rehearsed end to end; and a pod of another family that never loads this one."""

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
from modelx_tpu.models import deepseek_v2 as ds, deepseek_v2_reference as reference


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """Group 1 of 4 (experts 4-7 of 16) under the router's 16 outputs, as a
    push holds them: per-expert names, ``config.json`` with the share key."""
    src = tmp_path_factory.mktemp("dsv2_src")
    cfg = ds.DeepseekV2Config.tiny(vocab_size=96, expert_first=4, expert_count=4)
    params = ds.init_params(cfg, jax.random.PRNGKey(2))
    hf = ds.to_hf_state_dict(params, first=4)
    raw = ds.to_hf_config(cfg)
    st.write_safetensors(str(src / "model.safetensors"), hf)
    (src / "config.json").write_text(json.dumps(raw))
    return src, hf, raw


def test_push_dl_serve_model_with_chunked_prefill_follows_the_reference(checkpoint, tmp_path):
    src, hf, raw = checkpoint
    assert "model.layers.1.mlp.experts.4.gate_proj.weight" in hf
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in hf
    procs = []
    try:
        reg_port, pod_port = free_port(), free_port()
        reg_log = open(tmp_path / "registry.log", "wb")
        reg = subprocess.Popen(CLI + ["serve", "--listen", f"127.0.0.1:{reg_port}", "--data",
                                      str(tmp_path / "reg")], env=ENV, stdout=reg_log,
                               stderr=subprocess.STDOUT)
        procs.append(reg)
        wait_ready(reg_port, reg, tmp_path / "registry.log")
        ref = f"http://127.0.0.1:{reg_port}/library/dsv2-tiny@v1"
        for argv in (["init", str(src)], ["push", ref, str(src)], ["dl", ref, str(tmp_path / "vol")]):
            done = subprocess.run(CLI + argv, env=ENV, capture_output=True, text=True, timeout=240)
            assert done.returncode == 0, (argv, done.stderr[-2000:])
        assert (tmp_path / "vol" / "config.json").exists()  # heads, groups, rope scaling, the share
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
        # one row admitted whole, one landed in three pieces — sent TOGETHER, so that
        # each decodes beside the other's filling and beside two idle slots
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
        assert metrics["default"]["family"] == "deepseek_v2"
        assert metrics["default"]["load_bytes"] == sum(v.nbytes for v in hf.values())
        assert engine["fill"]["pieces"] == 3 and engine["fill"]["tokens"] == 45
        # one float32 line of 128 lanes (32 + 8 used) a position a layer
        assert engine["kv"]["bytes_latent"] == 4 * 128 * 128 * 4 * 3 and engine["kv"]["bytes_full"] == 0
        mla, moe = engine["mla"], engine["moe"]
        assert mla["steps_absorbed"] == mla["steps_all"] > 0
        assert mla["positions_read"] > mla["positions_cached"] > 0  # the CPU contracts the whole cache
        assert (mla["layers"], mla["heads"], mla["kv_lora_rank"], mla["rope_dim"]) == (3, 4, 32, 8)
        assert (moe["held_experts"], moe["published_experts"], moe["groups"], moe["groups_kept"]) \
            == (4, 16, 4, 2)
        assert 0 < moe["assignments_held"] < moe["assignments"]
    finally:
        stop(procs)


@pytest.mark.parametrize("flags,message", [
    (["--kv-page-size", "16"], "--kv-page-size"),
    (["--speculative-k", "2"], "--speculative-k"),
    (["--prefix-cache", "4"], "--prefix-cache"),
])
def test_serve_model_refuses_what_no_test_holds_over_latent_lines(checkpoint, tmp_path, flags, message):
    src, _, _ = checkpoint
    pod = subprocess.run(
        CLI + ["serve-model", "--model-dir", str(src), "--listen", f"127.0.0.1:{free_port()}",
               "--dtype", "float32", "--continuous-batch", "--max-seq-len", "128", "--max-slots",
               "4", "--drain-seconds", "0", *flags],
        env=ENV, capture_output=True, text=True, timeout=240)
    assert pod.returncode != 0
    assert message in pod.stderr + pod.stdout and "'latent' leaves" in pod.stderr + pod.stdout


def test_rehearse_of_the_benchmarks_new_cell_ends():
    """The cell's files, the checkpoint layout, the pod's flags, the primed
    generator, the new readers: walked at the tiny preset, as ``--rehearse``
    always ends."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "deepseek-v2-ep8-d5.longdoc", "--rehearse", "--trace", "1"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 0
    probes = next(l for l in lines if l.get("phase") == "probes")
    assert probes["argmax_agreement"] >= 0.9  # float32 here: the two programs agree
    metrics = last["metrics"]
    assert metrics["mla.absorbed_share.longdoc"]["value"] == 1.0
    assert metrics["mla.kv_read_share.longdoc"]["value"] > 1.0  # the CPU reads the whole cache
    assert metrics["latent.cache_gb.longdoc"]["value"] > 0
    assert 0 < metrics["moe.held_assignment_share.longdoc"]["value"] < 1
    assert "engine.fill_pieces.longdoc" in metrics and "engine.wait_ms.longdoc" in metrics
    rehearsed = next(l for l in lines if l.get("phase", "").startswith("rehearsed_on_a_cpu"))
    assert rehearsed["model.decode_step_ms.longdoc"] > 0  # the depth was read from the module names


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
        "new = ['modelx_tpu.models.deepseek_v2', 'modelx_tpu.models.deepseek_v2_reference',\n"
        "       'modelx_tpu.ops.latent_attention', 'modelx_tpu.ops.rope']\n"
        "print([m for m in new if m in sys.modules])\n")
    out = subprocess.run([sys.executable, "-c", code], env=ENV, capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
