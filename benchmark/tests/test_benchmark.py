"""The benchmark's own tests: run by hand and in rehearsal, outside ``tests/``.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import bytes_model, stats, xplane  # noqa: E402


def ev(name, start, dur, **st):
    return (name, float(start), float(dur), st)


# -- the xplane reduction on a hand-made trace ---------------------------------


def synthetic_trace():
    """One chip, 10 us window. Ops busy [0,2) [1,4) [6,8): union 6 us, so
    idle 0.4; two programs: chunk 2 x 3 us, admit 1 x 2 us."""
    ops = [ev("fusion.1", 0, 2000), ev("fusion.2", 1000, 3000), ev("fusion.1", 6000, 2000)]
    modules = [ev("jit__chunk_impl(111)", 0, 3000), ev("jit__chunk_impl(111)", 6000, 3000),
               ev("jit__admit_nosmall(222)", 3000, 2000)]
    host = [ev("outer", 0, 10_000_000), ev("prepare inputs", 4_000, 40_000),
            ev("end marker", 9_999, 1)]
    # host events are long (>= 20 us) to count; stretch the window with them
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", modules), ("Steps", [])]),
            ("/host:CPU", [("python3", host)])]


def test_union_merges_overlaps_and_counts_gaps():
    seconds, merged = xplane.union_seconds([(0, 2000), (1000, 4000), (6000, 8000)])
    assert seconds == pytest.approx(6e-6)
    assert merged == [(0, 4000), (6000, 8000)]
    assert xplane.gaps_of(merged, 0, 10_000) == [(4000, 6000), (8000, 10_000)]


def test_reduce_busy_idle_modules_and_ops():
    planes = synthetic_trace()
    # keep the window to the device's 10 us: drop the long host events
    planes[1] = ("/host:CPU", [("python3", [ev("prepare inputs", 4_000, 2_000)])])
    r = xplane.reduce_planes(planes)
    assert r["device_planes"] == 1
    assert r["window_s"] == pytest.approx(9e-6)          # first event 0 .. last end 9000
    assert r["busy_s"] == pytest.approx(6e-6)
    assert r["idle_share"] == pytest.approx(1 - 6 / 9)
    assert r["modules"]["jit__chunk_impl"] == {"seconds": pytest.approx(6e-6), "count": 2}
    assert r["modules"]["jit__admit_nosmall"]["count"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(4e-6)]


def test_idle_gaps_go_to_the_deepest_host_event_that_spans_them():
    r = xplane.reduce_planes(synthetic_trace())
    gaps = dict(r["idle_gaps"])
    # the gap [4,6) us lies inside "prepare inputs" (40 us) and "outer" (10 ms):
    # the shorter one names it
    assert gaps["prepare inputs"] >= 2e-6
    assert "outer" in gaps  # the long tail after the device's last op


def test_cpu_trace_stands_in_but_says_so():
    planes = [("/host:CPU", [("tf_XLAPjRtCpuClient/1", [
        ev("dot.1", 0, 1000, hlo_op="dot.1", hlo_module="jit_f"),
        ev("dot.2", 2000, 1000, hlo_op="dot.2", hlo_module="jit_f")])])]
    r = xplane.reduce_planes(planes)
    assert r["device_planes"] == 0
    assert r["busy_s"] == pytest.approx(2e-6)
    assert r["modules"]["jit_f"]["count"] == 2


def test_module_name_strips_the_fingerprint():
    assert xplane.module_name("jit__chunk_impl(123456789)") == "jit__chunk_impl"
    assert xplane.module_name("jit__chunk_impl") == "jit__chunk_impl"


# -- traffic: same seed same schedule, another seed another order ------------------


def traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["deploy", "decode", "chat"])
def test_schedule_is_a_function_of_the_seed(name):
    p = traffic(name)
    gen = importlib.import_module(f"benchmark.generators.{p['generator']}")
    a = gen.schedule(7, p, 32000, 45.0, 1024)
    b = gen.schedule(7, p, 32000, 45.0, 1024)
    c = gen.schedule(2_500_000_011, p, 32000, 45.0, 1024)  # more than 32 signed bits hold
    assert a == b
    assert a != c


def flat(s):
    return s["requests"] if s["mode"] == "open" else [r for c in s["clients"] for r in c]


@pytest.mark.parametrize("name", ["decode", "chat"])
def test_every_seed_offers_the_same_sizes_in_another_order(name):
    p = traffic(name)
    gen = importlib.import_module(f"benchmark.generators.{p['generator']}")
    sizes = []
    for seed in (1, 2):
        s = flat(gen.schedule(seed, p, 32000, 45.0, 2048))
        sizes.append(sorted((len(r["prompt"]), r["max_new_tokens"]) for r in s))
    assert sizes[0] == sizes[1]
    one = flat(gen.schedule(1, p, 32000, 45.0, 2048))
    two = flat(gen.schedule(2, p, 32000, 45.0, 2048))
    assert [len(r["prompt"]) for r in one] != [len(r["prompt"]) for r in two]


def test_open_loop_arrivals_are_the_same_gaps_reordered_and_inside_the_window():
    p = traffic("chat")
    gen = importlib.import_module("benchmark.generators.open_loop")
    due = []
    for seed in (1, 2):
        d = [r["due_s"] for r in gen.schedule(seed, p, 32000, 45.0, 1024)["requests"]]
        assert d == sorted(d) and 0 < d[0] and d[-1] < 45.0
        due.append(d)
    assert len(due[0]) == len(due[1]) == round(p["rate_rps"] * 45)
    gaps = [sorted(round(b - a, 9) for a, b in zip([0.0] + d, d)) for d in due]
    assert gaps[0] == pytest.approx(gaps[1])
    assert due[0][-1] == pytest.approx(due[1][-1])


@pytest.mark.parametrize("name,max_seq_len", [("chat", 1024), ("decode", 2048)])
def test_no_request_is_one_the_engine_would_refuse(name, max_seq_len):
    p = traffic(name)
    gen = importlib.import_module(f"benchmark.generators.{p['generator']}")
    for r in flat(gen.schedule(3, p, 32000, 45.0, max_seq_len)):
        # dl/continuous.py _validate: pad16(prompt) + max_new_tokens + overrun <= max_len
        assert stats.pad16(len(r["prompt"])) + r["max_new_tokens"] + 8 <= max_seq_len
        assert r["max_new_tokens"] >= 1 and all(1 <= t < 32000 for t in r["prompt"])


# -- the arithmetic of the end-to-end metrics -------------------------------------------


def test_percentile_on_a_hand_made_sample():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)   # 40 + 0.6 * 10
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_counts_from_the_due_instant():
    assert stats.ttft_ms(10.0, [10.25, 10.30]) == pytest.approx(250.0)
    assert stats.ttft_ms(10.0, []) is None


def test_tpot_is_robust_to_grouped_delivery_and_skips_one_token_requests():
    # 9 tokens, delivered as one, then eight at once 0.4 s later: 0.4 / 8 = 50 ms
    assert stats.tpot_ms([1.0] + [1.4] * 8) == pytest.approx(50.0)
    assert stats.tpot_ms([1.0, 1.1, 1.2]) == pytest.approx(100.0)
    assert stats.tpot_ms([1.0]) is None      # one token has no gap
    assert stats.tpot_ms([]) is None


def test_tokens_in_window_counts_arrivals_not_requests():
    times = [[0.5, 1.5, 2.5], [1.0, 3.5]]
    assert stats.tokens_in_window(times, 1.0, 3.0) == 3


def test_pad16_is_the_engines_bucket():
    assert [stats.pad16(n) for n in (1, 16, 17, 768)] == [16, 16, 32, 768]


# -- the bytes a decode step must read ----------------------------------------------


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_mixtral_decode_step_bytes_match_the_reckoning_by_hand():
    need = bytes_model.decode_step_bytes(config("mixtral-8x7b-d4"), live_rows=32,
                                         mean_context=300)
    # 4 layers x 8 experts x 3 x 4096 x 14336 x 2 B = 11.27 GB when every expert is hit
    assert need["experts_hit"] == pytest.approx(8.0, abs=0.01)
    assert need["ffn"] == pytest.approx(11.27e9, rel=0.01)
    assert need["attention"] == pytest.approx(4 * 2 * (4096 * 4096 * 2 + 1024 * 4096 * 2), rel=1e-9)
    assert need["head"] == 32000 * 4096 * 2
    assert need["kv"] == pytest.approx(16384 * 32 * 300)
    assert need["total"] == pytest.approx(12.0e9, rel=0.02)


def test_one_row_hits_two_experts_and_dense_models_one():
    assert bytes_model.expected_experts_hit(8, 2, 1) == pytest.approx(2.0)
    assert bytes_model.expected_experts_hit(1, 1, 12) == 1.0
    need = bytes_model.decode_step_bytes(config("phi3-mini-4k"), live_rows=12, mean_context=300)
    assert need["kv"] == pytest.approx(393216 * 12 * 300)
    assert need["attention"] + need["ffn"] + need["head"] == pytest.approx(7.64e9 - 0.197e9, rel=0.01)


# -- the data files agree with BENCHMARK.json ------------------------------------------


def test_every_name_in_benchmark_json_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert config(c["name"])["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        cell = os.path.join(ROOT, "benchmark", "workloads", w["name"] + ".json")
        with open(cell) as f:
            data = json.load(f)
        assert (data["config"], data["traffic"]) == (w["config"], w["traffic"])
        assert data["why"] and data["who"]
        assert traffic(w["traffic"])["generator"]
    for m in bench["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")


def test_a_reader_that_finds_nothing_returns_nothing():
    for name in ("deploy_stage", "load_gbps", "engine_gauge", "engine_delta_ratio",
                 "decode_step_ms", "decode_hbm_share", "trace_value", "lag_percentile"):
        reader = importlib.import_module(f"benchmark.layer_metrics.readers.{name}")
        params = {"stage": "x", "key": "x", "numerator": "a", "denominator": "b",
                  "module_regex": "x", "chunk_size": 8, "q": 90}
        assert reader.read({}, params) is None


def test_decode_step_reader_on_hand_made_sources():
    reader = importlib.import_module("benchmark.layer_metrics.readers.decode_step_ms")
    cont = lambda chunks, dispatches: {"default": {"continuous": {"chunks": chunks,
                                                                  "dispatches": dispatches}}}
    sources = {
        "trace": {"window_s": 2.0, "modules": {"jit__chunk_impl": {"seconds": 1.5, "count": 10},
                                               "jit__admit_nosmall": {"seconds": 0.2, "count": 3}}},
        "trace_span": {"metrics_before": cont(100, 80), "metrics_after": cont(150, 105),
                       "seconds": 4.0},
    }
    # 50 chunks in 25 dispatches: mean depth 2; 10 runs x 8 steps x 2 = 160 steps in 1.5 s
    assert reader.read(sources, {"module_regex": "chunk_impl", "chunk_size": 8}) == pytest.approx(
        1.5 / 160 * 1e3)


def test_op_names_are_cut_to_what_a_person_reads():
    long = ("%fusion.590 = bf16[8,32,1,14336]{3,1,0,2:T(8,128)(2,1)S(1)} fusion(bf16[8,14336,4096]"
            "{2,1,0:T(8,128)(2,1)} %get-tuple-element.3049), kind=kOutput")
    assert xplane.op_name(long) == "fusion.590 bf16[8,32,1,14336]"
    assert xplane.op_name("%while.50 = (s32[]{:T(128)}, bf16[32,2048,8,128]{3,2,1,0}) while(...)") == "while.50"
    assert xplane.op_name("dot.1") == "dot.1"
