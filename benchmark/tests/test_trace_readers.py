"""The readers PR 25 added (metrics_path, idle_named_share) on hand-made
sources, and every metric file that uses them against a program that has
none of its keys (the parent of that PR): nothing read, nothing raised."""

import glob
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER_METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

metrics_path = importlib.import_module("benchmark.layer_metrics.readers.metrics_path")
idle_named_share = importlib.import_module("benchmark.layer_metrics.readers.idle_named_share")


def spec(name: str) -> dict:
    with open(os.path.join(LAYER_METRICS, name + ".json")) as f:
        return json.load(f)


def engine(**cont) -> dict:
    return {"default": {"continuous": cont}}


def test_a_value_with_a_scale():
    sources = {"metrics_after": {"device": {"hbm_peak_bytes": 15_500_000_000}}}
    assert metrics_path.read(sources, spec("device.hbm_peak_gb")) == pytest.approx(15.5)
    sources = {"trace_span": {"metrics_after": {"startup": {"imports_s": 4.25}}}}
    assert metrics_path.read(sources, spec("front.imports_s")) == 4.25


def test_a_ratio_of_two_values():
    sources = {"trace_span": {"metrics_after": {"default": {
        "load_seconds": 10.0, "load_shards_seconds": 9.0, "load_idle_seconds": 3.6}}}}
    assert metrics_path.read(sources, spec("loader.shards_share")) == pytest.approx(0.9)
    assert metrics_path.read(sources, spec("loader.idle_share")) == pytest.approx(0.4)
    sources = {"metrics_before": {"compile_cache": {
        "store_load_s": 24.5, "store_hits": 49, "requests": 49, "trace_s": 30.0, "lower_s": 19.0}}}
    assert metrics_path.read(sources, spec("cache.store_load_s_per_program.decode")) == 0.5
    assert metrics_path.read(sources, spec("cache.trace_lower_s_per_program.decode")) == 1.0


def test_a_ratio_of_two_differences_sums_and_subtracts_its_terms():
    before = engine(dispatches=100, loop_wall_s=50.0, loop_cpu_s=5.0,
                    phase_s={"admit_prep": 1.0, "admit_dispatch": 2.0, "chunk_dispatch": 1.0,
                             "fanout": 3.0, "wait_tokens": 30.0, "firsts_wait": 1.0, "idle": 9.0})
    after = engine(dispatches=200, loop_wall_s=80.0, loop_cpu_s=7.0,
                   phase_s={"admit_prep": 1.5, "admit_dispatch": 2.7, "chunk_dispatch": 1.4,
                            "fanout": 4.1, "wait_tokens": 55.0, "firsts_wait": 1.5, "idle": 9.0},
                   queue_ms_hist={"sum": 900.0, "count": 30, "buckets": {}})
    # the window's two dumps, and the traced span's (PR 57: the idle rows and the waits
    # of every token cell are read over the traced span)
    sources = {"metrics_before": before, "metrics_after": after,
               "trace_span": {"metrics_before": before, "metrics_after": after}}
    assert metrics_path.read({"metrics_before": before, "metrics_after": after},
                             spec("engine.wait_ms")) is None
    assert metrics_path.read(sources, spec("engine.admit_ms.decode")) == pytest.approx(12.0)
    assert metrics_path.read(sources, spec("engine.dispatch_ms.decode")) == pytest.approx(4.0)
    assert metrics_path.read(sources, spec("engine.fanout_ms.decode")) == pytest.approx(11.0)
    assert metrics_path.read(sources, spec("engine.wait_ms")) == pytest.approx(255.0)
    # 2 s of CPU over 30 s of wall less 25.5 s of waiting: 2 / 4.5
    assert metrics_path.read(sources, spec("engine.cpu_share.decode")) == pytest.approx(2 / 4.5)
    # the histogram was not there before its first sample: it counts from 0
    assert metrics_path.read(sources, spec("engine.queue_wait_ms.decode")) == pytest.approx(30.0)


def test_the_first_request_is_the_span_between_the_profile_calls_dumps():
    cc = lambda **kw: {"compile_cache": kw}
    sources = {"trace_span": {
        "metrics_before": cc(requests=1, store_hits=1, store_load_s=0.2, trace_s=0.1, lower_s=0.1),
        "metrics_after": cc(requests=9, store_hits=9, store_load_s=4.2, trace_s=2.1, lower_s=2.1)}}
    assert metrics_path.read(sources, spec("cache.store_load_s_per_program")) == pytest.approx(0.5)
    assert metrics_path.read(sources, spec("cache.trace_lower_s_per_program")) == pytest.approx(0.5)


def test_another_model_name_is_followed():
    sources = {"model": "m", "metrics_before": {"m": {"continuous": {
        "dispatches": 0, "phase_s": {"fanout": 0.0}}}},
        "metrics_after": {"m": {"continuous": {"dispatches": 10, "phase_s": {"fanout": 0.05}}}}}
    assert metrics_path.read(sources, spec("engine.fanout_ms.decode")) == pytest.approx(5.0)


def test_idle_named_share_counts_the_programs_own_spans():
    trace = {"idle_gaps": [["continuous.boundary/fanout", 0.5], ["continuous.boundary", 0.2],
                           ["startup.engine_init", 0.1], ["_threading.py:323_wait", 0.1],
                           ["PjitFunction(f)", 0.05], ["(no host event)", 0.05]]}
    share = idle_named_share.read({"trace": trace}, spec("device.idle_named_share.deploy"))
    assert share == pytest.approx(0.8)
    only_frames = {"idle_gaps": [["_threading.py:323_wait", 0.63], ["_pjit.py:250_cache_miss", 0.06]]}
    assert idle_named_share.read({"trace": only_frames},
                                 spec("device.idle_named_share.deploy")) == 0.0


def new_specs() -> list[str]:
    out = []
    for path in sorted(glob.glob(os.path.join(LAYER_METRICS, "*.json"))):
        with open(path) as f:
            if json.load(f)["reader"] in ("metrics_path", "idle_named_share"):
                out.append(os.path.basename(path)[:-len(".json")])
    return out


@pytest.mark.parametrize("name", new_specs())
def test_a_program_without_the_key_gives_nothing(name):
    """What the parent of PR 25 answers: the dumps are there, the keys are not."""
    reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec(name)['reader']}")
    old = {"default": {"load_seconds": 9.3, "continuous": {"dispatches": 7, "chunks": 9}},
           "compile_cache": {"dir": "x", "requests": 8, "hits": 8, "misses": 0},
           "device": {"hbm_bytes_in_use": 1}}
    for sources in ({}, {"metrics_before": old, "metrics_after": old,
                         "trace_span": {"metrics_before": old, "metrics_after": old},
                         "trace": {"window_s": 1.0, "idle_gaps": []}}):
        assert reader.read(sources, spec(name)) is None


def test_a_zero_denominator_gives_nothing():
    same = {"compile_cache": {"requests": 8, "hits": 8, "retrieval_s": 1.0,
                              "trace_s": 1.0, "lower_s": 1.0}}
    sources = {"trace_span": {"metrics_before": same, "metrics_after": same}}
    assert metrics_path.read(sources, spec("cache.trace_lower_s_per_program")) is None
