"""MiMo-V2-Flash through the normal path, all real processes: ``modelx push``
-> ``modelx dl`` -> ``modelx serve-model --continuous-batch --prefill-chunk``
on a checkpoint that holds a share of the experts under per-expert names below
a router of the published width with its choice bias (the loader folds them)
and a sink a query head on the window layers — no flag names the model: the
family is detected from the tensor names and its config read from
``config.json``. The engine's tokens are held against the float32 reference —
logits, not tokens — for a prompt admitted whole and one landed in pieces over
the rings, sent together; what a ring cannot carry is refused at start-up by
name; the benchmark's cell is rehearsed."""

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
from modelx_tpu.models import mimo_v2, mimo_v2_reference as reference


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """Experts 4-7 of 16 under the router's 16 outputs and its bias of 16, as
    a push holds them: per-expert names, ``config.json`` with the share key."""
    src = tmp_path_factory.mktemp("mimo_src")
    cfg = mimo_v2.MimoV2Config.tiny(vocab_size=96, expert_first=4, expert_count=4)
    params = mimo_v2.init_params(cfg, jax.random.PRNGKey(2))
    hf = mimo_v2.to_hf_state_dict(params, first=4)
    raw = mimo_v2.to_hf_config(cfg)
    st.write_safetensors(str(src / "model.safetensors"), hf)
    (src / "config.json").write_text(json.dumps(raw))
    return src, hf, raw


def test_push_dl_serve_model_with_chunked_prefill_over_rings_follows_the_reference(
        checkpoint, tmp_path):
    src, hf, raw = checkpoint
    assert raw["model_type"] == "mimo_v2_flash" and raw["n_routed_experts"] == 4
    assert "model.layers.1.mlp.experts.4.gate_proj.weight" in hf
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in hf
    assert hf["model.layers.1.mlp.gate.e_score_correction_bias"].shape == (16,)
    assert hf["model.layers.1.self_attn.attention_sink_bias"].shape == (8,)
    procs = []
    try:
        reg_port, pod_port = free_port(), free_port()
        reg_log = open(tmp_path / "registry.log", "wb")
        reg = subprocess.Popen(CLI + ["serve", "--listen", f"127.0.0.1:{reg_port}", "--data",
                                      str(tmp_path / "reg")], env=ENV, stdout=reg_log,
                               stderr=subprocess.STDOUT)
        procs.append(reg)
        wait_ready(reg_port, reg, tmp_path / "registry.log")
        ref = f"http://127.0.0.1:{reg_port}/library/mimo-tiny@v1"
        for argv in (["init", str(src)], ["push", ref, str(src)], ["dl", ref, str(tmp_path / "vol")]):
            done = subprocess.run(CLI + argv, env=ENV, capture_output=True, text=True, timeout=240)
            assert done.returncode == 0, (argv, done.stderr[-2000:])
        assert (tmp_path / "vol" / "config.json").exists()
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
        # one row admitted whole inside the window, one landed in three pieces past the
        # ring's 32 positions — sent TOGETHER, so that each decodes beside the other's filling
        asks = [(rng.integers(1, 96, 5).tolist(), 40), (rng.integers(1, 96, 45).tolist(), 50)]
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
            assert below.max() < 1e-3  # float32 on both sides: the reference's argmax
        seq = rng.integers(1, 96, 70).tolist()
        status, body = request(pod_port, "POST", "/v1/forward", {"tokens": [seq]})
        assert status == 200, body
        assert body["logits_argmax"][0] == np.asarray(
            reference.forward(hf, raw, seq)).argmax(-1).tolist()
        _, metrics = request(pod_port, "GET", "/metrics")
        engine = metrics["default"]["continuous"]
        assert metrics["default"]["family"] == "mimo_v2"
        assert metrics["default"]["load_bytes"] == sum(v.nbytes for v in hf.values())
        assert engine["fill"]["pieces"] == 3 and engine["fill"]["tokens"] == 45
        assert engine["kv_ring_pieces"] == 3
        # float32 lines: 2 x (24 + 16) on two full layers, 4 x (24 + 16) on three rings of 32
        assert engine["kv"]["bytes_full"] == 4 * 128 * 2 * (2 * 40) * 4
        assert engine["kv"]["bytes_window"] == 4 * 32 * 3 * (4 * 40) * 4
        moe, attn = engine["moe"], engine["attn"]
        assert (moe["held_experts"], moe["published_experts"], moe["sparse_layers"]) == (4, 16, 4)
        assert 0 < moe["assignments_held"] < moe["assignments"]
        assert (attn["window_layers"], attn["sink_layers"]) == (3, 3) and attn["sink_calls"] > 0
    finally:
        stop(procs)


@pytest.mark.parametrize("flags,message", [
    (["--kv-page-size", "16"], "--kv-page-size"),
    (["--speculative-k", "2"], "--speculative-k"),
    (["--prefix-cache", "4"], "--prefix-cache"),
])
def test_serve_model_refuses_what_a_ring_cannot_carry_by_name(checkpoint, flags, message):
    src, _, _ = checkpoint
    pod = subprocess.run(
        CLI + ["serve-model", "--model-dir", str(src), "--listen", f"127.0.0.1:{free_port()}",
               "--dtype", "float32", "--continuous-batch", "--max-seq-len", "128", "--max-slots",
               "4", "--prefill-chunk", "16", "--drain-seconds", "0", *flags],
        env=ENV, capture_output=True, text=True, timeout=240)
    assert pod.returncode != 0
    said = pod.stderr + pod.stdout
    assert message in said and "'window' leaves" in said and "--prefill-chunk" not in said


def test_a_config_the_module_does_not_implement_stops_the_pod_at_once(checkpoint, tmp_path):
    src, hf, raw = checkpoint
    bad = tmp_path / "bad"
    bad.mkdir()
    st.write_safetensors(str(bad / "model.safetensors"), hf)
    (bad / "config.json").write_text(json.dumps(dict(raw, n_group=8, topk_group=4)))
    pod = subprocess.run(
        CLI + ["serve-model", "--model-dir", str(bad), "--listen", f"127.0.0.1:{free_port()}",
               "--dtype", "float32", "--continuous-batch", "--max-seq-len", "128", "--max-slots",
               "4", "--drain-seconds", "0"],
        env=ENV, capture_output=True, text=True, timeout=240)
    assert pod.returncode != 0
    assert "group-limited routing is not implemented" in pod.stderr + pod.stdout


def test_rehearse_of_the_benchmarks_new_cell_ends():
    """The cell's files, the checkpoint layout, the pod's flags, the primed
    generator, the new readers: walked at the tiny preset, as ``--rehearse``
    always ends."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "mimo-v2-flash-ep16-d7.longcode", "--rehearse", "--trace", "1"],
        env=dict(ENV, XLA_FLAGS=""), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 0
    probes = next(l for l in lines if l.get("phase") == "probes")
    assert probes["argmax_agreement"] >= 0.9  # float32 here: the two programs agree
    metrics = last["metrics"]
    assert metrics["engine.fill_pieces.longdoc"]["value"] > 0  # 96-token prompts in pieces of 32
    assert 0 < metrics["moe.held_assignment_share.longdoc"]["value"] < 1
    # the CPU takes neither kernel: no ring or ragged counter, so no share of either
    assert "attn.ring_kernel_share.reason" not in metrics
    assert "attn.sink_share.longcode" not in metrics


def test_a_phi3_pods_start_loads_neither_the_family_nor_its_reference():
    code = (
        "import sys\n"
        "import modelx_tpu.dl.serve_main, modelx_tpu.dl.continuous\n"
        "from modelx_tpu.dl import families\n"
        "fam = families.detect(['model.layers.0.self_attn.qkv_proj.weight'])\n"
        "assert fam.name == 'phi3', fam.name\n"
        "fam.decode_fns\n"
        "new = ['modelx_tpu.models.mimo_v2', 'modelx_tpu.models.mimo_v2_reference']\n"
        "print([m for m in new if m in sys.modules])\n")
    out = subprocess.run([sys.executable, "-c", code], env=ENV, capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
