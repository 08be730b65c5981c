"""``run.py``'s own arithmetic, without a pod: how ``correct`` follows from the
numbers compared, how many prompts the probe sends, what a traced span is, and
that a traced run which lost its span says so (PR 57)."""

import argparse
import json
import os
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402
from benchmark.procs import Fail  # noqa: E402


def a_run(cell="phi3-mini-4k.deploy", trace=0, **config) -> harness.Run:
    """A ``Run`` with its data and no children: ``__init__`` makes directories."""
    run = harness.Run.__new__(harness.Run)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        run.bench = json.load(f)
    run.cell = next(w for w in run.bench["workloads"] if w["name"] == cell)
    run.args = argparse.Namespace(seed=7, trace=trace, rehearse=False)
    run.config = {"vocab_size": 512, **config}
    run.device, run.memory_peak, run.sources = {"platform": "tpu", "kind": "k", "count": 1}, 5, {}
    return run


GOOD = {"argmax_agreement_min": (0.93, 0.9), "deploys_failed_max": (0, 0),
        "hbm_bytes_in_use_min": (10, 10)}


@pytest.mark.parametrize("name, value, correct", [
    ("argmax_agreement_min", 0.93, True), ("argmax_agreement_min", 0.9, True),
    ("argmax_agreement_min", 0.8984375, False),  # 230 of 256: one token under the tolerance
    ("deploys_failed_max", 1, False), ("hbm_bytes_in_use_min", 9, False),
])
def test_correct_is_every_compared_number_inside_its_limit(name, value, correct):
    compared = dict(GOOD, **{name: (value, GOOD[name][1])})
    line = a_run().result(2, 0, {"pod_listen_ttft_s": 14.5, "setup_s": 20.0}, compared)
    assert line["correct"] is correct
    assert list(line)[-1] == "compared"  # last in the line, each number beside its limit
    assert line["compared"][name] == {"value": value, "limit": GOOD[name][1]}
    assert set(line["metrics"]) == {"pod_listen_ttft_s", "setup_s"}


def test_a_traced_run_without_its_span_fails_and_prints_no_line():
    run = a_run(trace=1)
    run.sources["trace"] = {"busy_s": 1.0, "window_s": 2.0, "device_planes": 1,
                            "device_ops": [], "idle_gaps": []}
    with pytest.raises(Fail, match="trace_span"):
        run.result(1, 0, {"pod_listen_ttft_s": 14.5, "setup_s": 20.0}, GOOD)


@pytest.fixture
def a_pod_that_answers(monkeypatch):
    sent = []

    def stream_request(port, prompt, n):
        sent.append(prompt)
        return {"done": True, "error": None, "tokens": list(range(1, n + 1))}

    monkeypatch.setattr(harness.loadgen, "stream_request", stream_request)
    monkeypatch.setattr(harness.loadgen, "tokens_of", lambda rec: rec["tokens"])
    # the forward agrees on every token but each prompt's last
    monkeypatch.setattr(harness, "post_ok", lambda port, path, body: {"logits_argmax": [
        [0] * (len(body["tokens"][0]) - 5) + [1, 2, 3, 99, 0]]})
    return sent


def test_a_configuration_states_how_many_prompts_the_probe_sends(a_pod_that_answers):
    spec = {"prompt_tokens": 6, "new_tokens": 4}
    four = a_run().probes(0, spec)
    assert (four["probes"], four["tokens"], four["tolerance"]) == (harness.PROBES, 16, 0.9)
    first = list(a_pod_that_answers)
    del a_pod_that_answers[:]
    sixteen = a_run(probes=16).probes(0, spec)
    assert (sixteen["probes"], sixteen["tokens"], sixteen["tolerance"]) == (16, 64, 0.9)
    assert a_pod_that_answers[:4] == first and len(a_pod_that_answers) == 16  # one rng stream
    assert sixteen["argmax_agreement"] == four["argmax_agreement"] == 0.75 and not sixteen["ok"]
    assert sixteen["seconds"] >= 0


def test_the_deploy_cells_configuration_sends_sixteen_at_the_tolerance_it_had():
    with open(os.path.join(ROOT, "benchmark", "configs", "phi3-mini-4k.json")) as f:
        config = json.load(f)
    assert config["probes"] == 16 and config["probes_why"]
    assert "min_argmax_agreement" not in config and harness.MIN_ARGMAX_AGREEMENT == 0.9
    assert {"probes", "probes_why"} <= harness.META_KEYS  # not a key of the checkpoint's config.json


def test_the_span_is_the_traced_seconds_by_the_clock_whenever_the_post_returns(monkeypatch):
    """The profiler's stop outlasts the load: the second dump is taken ``seconds``
    after the POST was sent, not when it returns."""
    t0 = time.monotonic()
    reads, calls = [], []

    def http_json(port, method, path, body=None, timeout=900.0):
        calls.append((method, path, threading.current_thread() is threading.main_thread()))
        if method == "POST":
            time.sleep(0.5)  # 0.2 s of trace, 0.3 s of stopping
            return 200, {"trace_dir": "x"}
        reads.append(time.monotonic() - t0)
        return 200, {"default": {"continuous": {"decode_rows": len(reads)}}}

    monkeypatch.setattr(harness, "http_json", http_json)
    run = a_run()
    run.profile(0, 0.2)
    span = run.sources["trace_span"]
    assert [c[:2] for c in calls] == [("GET", "/metrics"), ("POST", "/v1/profile"), ("GET", "/metrics")]
    assert calls[1][2] is False  # on a thread of its own
    assert 0.2 <= span["seconds"] < 0.4 <= 0.5 <= span["post_seconds"] < 0.9
    assert reads[1] - reads[0] == pytest.approx(span["seconds"], abs=0.05)
    assert span["metrics_after"]["default"]["continuous"]["decode_rows"] == 2


def test_a_profile_the_pod_refuses_leaves_no_span(monkeypatch, capsys):
    monkeypatch.setattr(harness, "http_json", lambda port, method, path, body=None, timeout=900.0: (
        (409, {"error": "profile already running"}) if method == "POST" else (200, {})))
    run = a_run()
    run.profile(0, 5.0)  # does not wait the five seconds out
    assert "trace_span" not in run.sources
    assert "409" in capsys.readouterr().out


def test_wait_ready_notes_when_the_port_first_answered(monkeypatch):
    """``pod_listen_ttft_s`` starts where a pod first answers at all — a 503 while
    it loads — not where it is ready: refused, refused, 503, 503, 200."""
    from benchmark import procs

    answers = iter([OSError(), OSError(), 503, 503, 200])
    clock = iter(range(100))

    def http_json(port, method, path, timeout=0):
        a = next(answers)
        if isinstance(a, OSError):
            raise a
        return a, {}

    monkeypatch.setattr(procs, "http_json", http_json)
    monkeypatch.setattr(procs.time, "monotonic", lambda: float(next(clock)))
    monkeypatch.setattr(procs.time, "sleep", lambda s: None)
    pod = argparse.Namespace(poll=lambda: None, log_name="pod", returncode=None)
    seen: dict = {}
    ready_s = procs.wait_ready(0, pod, "/nonexistent", 1000, seen=seen)
    # the fake clock ticks once a call: t0 = 0, the third answer is the first heard
    assert set(seen) == {"listen_at"} and 0 < seen["listen_at"] < ready_s
