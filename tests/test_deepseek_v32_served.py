"""DeepSeek-V3.2-Exp through the normal path, all real processes: ``modelx
push`` -> ``modelx dl`` -> ``modelx serve-model --continuous-batch
--prefill-chunk`` on a checkpoint that holds a share of the experts under
per-expert names below a router of the published width with its choice bias
(the loader folds them), ``model_type`` ``deepseek_v32`` read from
``config.json`` — no flag names the model, and the family is deepseek_v2's row.
The engine's tokens are held against the float32 reference — logits, not
tokens — with an ``index_topk`` of 8 that every request passes in its prompt or
its first steps: pieces select among what landed before them, decode steps
score, choose and gather through BOTH cache leaves beside idle and filling
slots. The options no test holds over latent lines are refused at start-up by
name with an index leaf beside them, and the benchmark's cell is rehearsed."""

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
from modelx_tpu.models import deepseek_v2 as ds, deepseek_v32_reference as reference


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    """Group 1 of 4 (experts 4-7 of 16) under the router's 16 outputs and its
    bias of 16, as a push holds them: per-expert names, ``config.json`` with the
    share key, the indexer's tensors a layer."""
    src = tmp_path_factory.mktemp("dsv32_src")
    cfg = ds.DeepseekV2Config.tiny_v32(vocab_size=96, expert_first=4, expert_count=4)
    params = ds.init_params(cfg, jax.random.PRNGKey(2))
    hf = ds.to_hf_state_dict(params, first=4)
    raw = ds.to_hf_config(cfg)
    st.write_safetensors(str(src / "model.safetensors"), hf)
    (src / "config.json").write_text(json.dumps(raw))
    return src, hf, raw


def test_push_dl_serve_model_with_chunked_prefill_follows_the_reference(checkpoint, tmp_path):
    src, hf, raw = checkpoint
    assert raw["model_type"] == "deepseek_v32" and raw["index_topk"] == 8
    assert "model.layers.1.mlp.experts.4.gate_proj.weight" in hf
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in hf
    assert hf["model.layers.1.mlp.gate.e_score_correction_bias"].shape == (16,)
    assert hf["model.layers.0.self_attn.indexer.k_norm.bias"].shape == (16,)
    procs = []
    try:
        reg_port, pod_port = free_port(), free_port()
        reg_log = open(tmp_path / "registry.log", "wb")
        reg = subprocess.Popen(CLI + ["serve", "--listen", f"127.0.0.1:{reg_port}", "--data",
                                      str(tmp_path / "reg")], env=ENV, stdout=reg_log,
                               stderr=subprocess.STDOUT)
        procs.append(reg)
        wait_ready(reg_port, reg, tmp_path / "registry.log")
        ref = f"http://127.0.0.1:{reg_port}/library/dsv32-tiny@v1"
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
        # one row admitted whole below index_topk (it passes 8 positions while it decodes),
        # one landed in three pieces well past it — sent TOGETHER, so that each decodes
        # beside the other's filling and beside two idle slots
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
            # float32 on both sides: the engine's token is the reference's argmax but
            # for rounding; another selection of 8 positions moves a logit by whole units
            assert below.max() < 1e-3
        seq = rng.integers(1, 96, 70).tolist()
        status, body = request(pod_port, "POST", "/v1/forward", {"tokens": [seq]})
        assert status == 200, body
        assert body["logits_argmax"][0] == np.asarray(
            reference.forward(hf, raw, seq)).argmax(-1).tolist()
        _, metrics = request(pod_port, "GET", "/metrics")
        engine = metrics["default"]["continuous"]
        assert metrics["default"]["family"] == "deepseek_v2"  # one module, one row
        assert metrics["default"]["load_bytes"] == sum(v.nbytes for v in hf.values())
        assert engine["fill"]["pieces"] == 3 and engine["fill"]["tokens"] == 45
        # a float32 line of 128 lanes and an index key of 16 a position a layer
        assert engine["kv"]["bytes_latent"] == 4 * 128 * 128 * 4 * 3
        assert engine["kv"]["bytes_index"] == 4 * 128 * 16 * 4 * 3 and engine["kv"]["bytes_full"] == 0
        mla, moe, dsa = engine["mla"], engine["moe"], engine["dsa"]
        assert mla["steps_absorbed"] == mla["steps_all"] == dsa["steps_all"] > 0
        assert dsa["positions_scored"] == mla["positions_cached"] > dsa["lines_selected"] > 0
        assert 0 < dsa["steps_selecting"] < dsa["steps_all"]  # the short row's first steps do not
        assert mla["positions_read"] < mla["positions_cached"]  # 8 gathered lines a row-step at most
        assert (dsa["layers"], dsa["index_topk"], dsa["index_heads"], dsa["index_dim"]) == (3, 8, 4, 16)
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
    said = pod.stderr + pod.stdout
    assert message in said and "'latent' leaves" in said and "index, latent leaves" in said


def test_a_config_the_module_does_not_implement_stops_the_pod_at_once(checkpoint, tmp_path):
    """What the parent of this family's PR did with the whole configuration:
    a pod whose ``config.json`` asks for what is not implemented exits at
    start-up, naming it, and leaves nothing behind."""
    src, hf, raw = checkpoint
    bad = tmp_path / "bad"
    bad.mkdir()
    st.write_safetensors(str(bad / "model.safetensors"), hf)
    (bad / "config.json").write_text(json.dumps(dict(raw, topk_method="group_limited_greedy")))
    pod = subprocess.run(
        CLI + ["serve-model", "--model-dir", str(bad), "--listen", f"127.0.0.1:{free_port()}",
               "--dtype", "float32", "--continuous-batch", "--max-seq-len", "128", "--max-slots",
               "4", "--drain-seconds", "0"],
        env=ENV, capture_output=True, text=True, timeout=240)
    assert pod.returncode != 0
    assert "scoring_func 'sigmoid' with topk_method 'group_limited_greedy'" in pod.stderr + pod.stdout


def test_rehearse_of_the_benchmarks_new_cell_ends():
    """The cell's files, the checkpoint layout, the pod's flags, the primed
    generator, the new readers: walked at the tiny preset, as ``--rehearse``
    always ends."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "deepseek-v3.2-exp-ep16-d5.sparsedoc", "--rehearse", "--trace", "1"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 0
    probes = next(l for l in lines if l.get("phase") == "probes")
    assert probes["argmax_agreement"] >= 0.9  # float32 here: the two programs agree
    metrics = last["metrics"]
    assert 0.5 < metrics["dsa.selecting_share.sparsedoc"]["value"] <= 1.0
    assert 0 < metrics["dsa.selected_share.sparsedoc"]["value"] < 1.0
    assert metrics["dsa.selected_share.sparsedoc"]["value"] == pytest.approx(
        metrics["mla.kv_read_share.sparsedoc"]["value"], rel=0.5)  # the CPU reads the 24 whole
    assert metrics["dsa.index_cache_gb.sparsedoc"]["value"] > 0
    assert metrics["latent.cache_gb.sparsedoc"]["value"] == pytest.approx(
        8 * metrics["dsa.index_cache_gb.sparsedoc"]["value"])  # 128 float32 lanes against 16
    assert metrics["mla.absorbed_share.sparsedoc"]["value"] == 1.0


def test_a_phi3_pods_start_loads_neither_the_selector_nor_the_family():
    code = (
        "import sys\n"
        "import modelx_tpu.dl.serve_main, modelx_tpu.dl.continuous\n"
        "from modelx_tpu.dl import families\n"
        "fam = families.detect(['model.layers.0.self_attn.qkv_proj.weight'])\n"
        "assert fam.name == 'phi3', fam.name\n"
        "fam.decode_fns\n"
        "new = ['modelx_tpu.models.deepseek_v2', 'modelx_tpu.models.deepseek_v32_reference',\n"
        "       'modelx_tpu.ops.index_select', 'modelx_tpu.ops.latent_attention']\n"
        "print([m for m in new if m in sys.modules])\n")
    out = subprocess.run([sys.executable, "-c", code], env=ENV, capture_output=True, text=True,
                         timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
