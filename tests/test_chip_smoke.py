"""chip_smoke.py off the chip: the CPU rehearsal walks every phase at tiny
size and the device check — the final gate — fails it; with no accelerator
and no ``--rehearse``, or with nothing of the repo beside it, the script
exits non-zero and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

PHASES = ["device", "native", "checkpoint", "push", "dl", "serve", "generate",
          "forward", "logprobs", "sample_stream", "restart"]


def _run(argv, cwd=REPO, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one host device, like the one-chip machine
    # the children's persistent cache: the suite's own, so reruns are warm
    env["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    p = subprocess.run([sys.executable, *argv], cwd=cwd, env=env, timeout=timeout,
                       capture_output=True, text=True)
    return p, [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]


def test_cpu_rehearsal_runs_every_phase_then_fails_the_device_gate(tmp_path):
    p, lines = _run([SMOKE, "--rehearse", "--workdir", str(tmp_path / "work")])
    assert p.returncode == 1, p.stderr[-2000:]
    assert [ln["phase"] for ln in lines[:-1]] == PHASES
    assert lines[-1] == {"ok": False,
                         "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    by = {ln["phase"]: ln for ln in lines[:-1]}
    # what the phase lines must carry (ISSUE 21, "earlier lines")
    assert by["checkpoint"]["reduced"] == {"num_layers": "2 of 32"}
    assert by["serve"]["platform"] == "cpu" and by["serve"]["native_engine"] is True
    assert by["serve"]["weights_bytes_on_device"] == by["checkpoint"]["bytes"]
    assert by["generate"]["engine"]["active_peak"] >= 2
    # off the TPU impl="auto" is the reference — and says so, with the group
    # it contracts (the smoke model has 2 query heads per KV head)
    assert by["forward"]["attention"]["512"] == "reference[512x512]+gqa2"
    assert by["restart"]["second_start"]["hits"] > 0
    assert by["restart"]["cache_dir"] == jax.config.jax_compilation_cache_dir
    assert not os.path.exists(tmp_path / "work")  # cleaned up, nothing left running


def test_no_accelerator_and_no_rehearse_prints_no_result():
    p, lines = _run([SMOKE])
    assert p.returncode not in (0, 1), p.stderr[-2000:]
    assert [ln.get("phase") for ln in lines] == ["device"]  # no "ok" line at all
    assert "no accelerator" in p.stderr


def test_alone_in_a_directory_it_fails_at_once(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p, lines = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path), timeout=60)
    assert p.returncode not in (0, 1) and lines == []
    assert "modelx_tpu/ is not beside this script" in p.stderr
