"""The MiMo-V2-Flash configuration's files, byte count and readers (PR 54):
what ``test_benchmark.py`` checks of the other cells, for the files this cell
adds. Counts of metrics are written "at least": a later PR may add to them.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import bytes_mimo_v2, checkpoint  # noqa: E402
from benchmark.generators import closed_loop_primed  # noqa: E402
from benchmark.layer_metrics.readers import (attn_full_roofline_share,  # noqa: E402
                                             decode_hbm_share_mimo_v2, decode_step_ms_named,
                                             dsa_select_step_share)

CELL, CONFIG = "mimo-v2-flash-ep16-d7.longcode", "mimo-v2-flash-ep16-d7"
E = 4096
FULL = E * 64 * 192 + E * 4 * 192 + E * 4 * 128 + 64 * 128 * E        # 89.13 M
WINDOW = E * 64 * 192 + E * 8 * 192 + E * 8 * 128 + 64 * 128 * E + 64  # 94.37 M and 64 sinks
DENSE, EXPERT, ROUTER = 3 * E * 16384, 3 * E * 2048, 256 * E + 256
PARAMETERS = 3_429_955_392


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reader_of(name):
    spec = load(BENCH, "layer_metrics", name + ".json")
    return importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}"), spec


@pytest.fixture(scope="module")
def config():
    return load(BENCH, "configs", CONFIG + ".json")


# -- the configuration ---------------------------------------------------------


def test_every_published_number_is_kept_but_the_five_cuts(config):
    """Against the catalog's values, written out here: no width is touched."""
    published = {
        "attention_value_scale": 0.707, "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "max_position_embeddings": 262144,
        "model_type": "mimo_v2_flash", "num_attention_heads": 64, "head_dim": 192,
        "num_key_value_heads": 4, "layernorm_epsilon": 1e-05, "rope_theta": 5000000,
        "tie_word_embeddings": False, "partial_rotary_factor": 0.334, "sliding_window": 128,
        "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 128,
        "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
        "sliding_window_size": 128, "attention_chunk_size": 128, "moe_intermediate_size": 2048,
        "n_shared_experts": None, "num_experts_per_tok": 8, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
        "routed_scaling_factor": None, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 8, "swa_head_dim": 192, "swa_v_head_dim": 128}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        7, 16, 19072)
    assert config["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert config["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert config["expert_share"] == {"published": 256, "first": 0}
    assert config["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
                                 "n_routed_experts", "vocab_size"]
    assert set(config["reduced_from"]) == set(config["reduced"])
    assert (config["reduced_from"]["num_hidden_layers"], config["reduced_from"]["n_routed_experts"],
            config["reduced_from"]["vocab_size"]) == (48, 256, 152576)
    entry = next(c for c in load(ROOT, "BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/config.json")
    assert set(config["assumed"]) >= {
        "a_value_scale", "b_rope", "c_sink", "d_window", "e_qk_norm", "f_attention_chunk_size",
        "g_names", "h_mtp", "i_precision"}
    assert "16 v5e chips" in config["deployment"] and config["chips"] == 1
    assert config["serve_args"] == ["--continuous-batch", "--max-seq-len", "32768", "--max-slots",
                                    "32", "--prefill-chunk", "2048", "--exit-with-parent"]


def test_the_checkpoints_bytes_are_the_issues_table(config):
    """ISSUE 54: 3,430 M parameters, 6.86 GB in bf16; the experts held are
    named for their published indices under a full router and its bias."""
    assert (FULL, WINDOW - 64, DENSE, EXPERT) == (89_128_960, 94_371_840, 201_326_592, 25_165_824)
    shards = checkpoint.family_module("mimo_v2").shards(config)
    assert checkpoint.nbytes(shards) == 2 * PARAMETERS == config["bytes_predicted"]["weights_bf16"]
    norms = 2 * E
    assert PARAMETERS == (FULL + DENSE + norms) + (FULL + 16 * EXPERT + ROUTER + norms) + 5 * (
        WINDOW + 16 * EXPERT + ROUTER + norms) + 2 * 19072 * E + E
    names = dict(t for shard in shards for t in shard)
    assert names["model.layers.1.mlp.gate.weight"] == (256, E)
    assert names["model.layers.1.mlp.gate.e_score_correction_bias"] == (256,)
    assert names["model.layers.6.mlp.experts.15.down_proj.weight"] == (E, 2048)
    assert "model.layers.1.mlp.experts.16.up_proj.weight" not in names
    assert names["model.layers.0.mlp.up_proj.weight"] == (16384, E)
    assert names["model.layers.0.self_attn.k_proj.weight"] == (4 * 192, E)
    assert names["model.layers.0.self_attn.v_proj.weight"] == (4 * 128, E)
    assert names["model.layers.1.self_attn.k_proj.weight"] == (8 * 192, E)
    assert names["model.layers.5.self_attn.o_proj.weight"] == (E, 64 * 128)
    assert names["model.layers.1.self_attn.attention_sink_bias"] == (64,)
    assert "model.layers.0.self_attn.attention_sink_bias" not in names
    assert "model.layers.5.self_attn.attention_sink_bias" not in names
    assert [n for n, _ in shards[-1]] == ["model.norm.weight", "lm_head.weight"]
    predicted = config["bytes_predicted"]
    assert predicted["kv_full_layers_32_slots_x_32768_x_2"] == 32 * 32768 * 2 * 2560 == 5_368_709_120
    assert predicted["kv_window_layers_5_rings_of_144_x_32"] == 32 * 144 * 5 * 5120
    assert predicted["sum"] == 2 * PARAMETERS + 5_368_709_120 + 117_964_800
    assert predicted["share_of_16GB"] == pytest.approx(0.772, abs=1e-3)


def test_the_checkpoint_layout_is_what_the_family_folds(config):
    from modelx_tpu.models import mimo_v2

    hf = {k: v for k, v in config.items() if k not in ("rehearse", "assumed", "reduced_from")}
    cfg = mimo_v2.config_from_hf(hf)
    theirs = mimo_v2.param_shapes(cfg)
    mine = dict(t for shard in checkpoint.family_module("mimo_v2").shards(config) for t in shard)
    folded = {}
    for name, shape in mine.items():
        if ".mlp.experts." in name:
            head, rest = name.split(".mlp.experts.")
            _, tail = rest.split(".", 1)
            key = f"{head}.mlp.experts.{tail}"
            folded[key] = (folded.get(key, (0,))[0] + 1, *shape)
        else:
            folded[name] = shape
    assert theirs == folded
    tiny = dict(config, **config["rehearse"])
    assert mimo_v2.config_from_hf(tiny).heads(1) == (8, 4, 24, 16)  # the rehearsal's preset reads


# -- the traffic -----------------------------------------------------------------


def test_the_cells_traffic_is_the_issues_and_no_request_would_be_refused(config):
    traffic = load(BENCH, "traffic", "longcode.json")
    want = {"generator": "closed_loop_primed", "clients": 32, "requests_per_client": 3,
            "prime": {"prompt_tokens": 48, "new_tokens": 16},
            "prompt": {"dist": "fixed", "value": 16384},
            "output": {"dist": "uniform", "min": 12288, "max": 16000}, "overrun": 8,
            "shape_seed": 1, "warm_group_sizes": [2], "trace_seconds": 8,
            "probe": {"prompt_tokens": 8240, "new_tokens": 16}}
    assert {k: traffic[k] for k in want} == want
    # ISSUE 43's rule: lead_in_s a multiple of 10 s; stagger_s = (lead_in_s - 25) / 32
    # rounded down to 0.5 s
    assert traffic["lead_in_s"] % 10 == 0 and traffic["lead_in_s"] <= 200
    assert traffic["stagger_s"] == math.floor((traffic["lead_in_s"] - 25) / 32 * 2) / 2
    assert set(traffic["rehearse"]) == set(load(BENCH, "traffic", "longdoc.json")["rehearse"])
    slots = int(config["serve_args"][config["serve_args"].index("--max-slots") + 1])
    sizes = []
    for seed in (0, 2**31 + 5):
        sched = closed_loop_primed.schedule(seed, traffic, config["vocab_size"], 45.0, 32768)
        assert sched["mode"] == "closed" and len(sched["clients"]) == 32 == slots
        for client in sched["clients"]:
            assert [len(r["prompt"]) for r in client] == [48, 16384, 16384]  # the prime one first
            assert client[0]["max_new_tokens"] == 16
            assert all(12288 <= r["max_new_tokens"] <= 16000 for r in client[1:])
        reqs = [r for c in sched["clients"] for r in c]
        for r in reqs:
            pad = -(-len(r["prompt"]) // 16) * 16
            assert pad + r["max_new_tokens"] + 8 <= 32768
            assert 0 < min(r["prompt"]) and max(r["prompt"]) < config["vocab_size"] == 19072
        sizes.append(sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs))
    assert sizes[0] == sizes[1]  # every seed offers the same sizes
    assert (traffic["lead_in_s"], traffic["stagger_s"]) == (60.0, 1.0)  # as measured, PR 54
    # no long request ends inside the window: at the 10 ms a step the byte count allows at
    # best, the shortest long request of the first client outlasts lead-in + window
    assert 12288 * 0.010 > traffic["lead_in_s"] + 45
    assert min(n for _, n in sizes[0] if n > 16) >= 12288


def test_every_name_the_cell_adds_has_its_files():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longcode", 1)
    assert len(cell["why"]) <= 200
    workload = load(BENCH, "workloads", CELL + ".json")
    assert workload["config"] == CONFIG and workload["traffic"] == "longcode"
    reported = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    mine = [m for m in reported if m["workloads"] == [CELL]]
    assert len(bench["per_layer"]) <= 128  # the contract's most: why only three are new
    assert {m["name"] for m in mine} >= {"model.decode_hbm_share.longcode",
                                         "attn.full_roofline_share.longcode",
                                         "attn.sink_share.longcode"}
    # the rest are accepted metrics whose readers find the same counters in this pod
    assert {m["name"] for m in reported} >= {
        "model.decode_step_ms.longdoc", "moe.held_hit_share.longdoc",
        "moe.held_assignment_share.longdoc", "moe.read_hit_share.longdoc",
        "device.hbm_peak_gb", "device.idle_share", "engine.pad_fraction",
        "engine.wait_ms", "engine.fill_pieces.longdoc", "cache.store_hit_share.longdoc",
        "attn.kv_read_share.reason", "attn.ring_kernel_share.reason",
        "kv.write_kernel_share.reason"}
    assert len(reported) >= 16
    for m in reported:
        reader, spec = reader_of(m["name"])
        assert reader.read({}, spec) is None  # a program without the source: nothing, no raise
        assert m["moves"] == ("setup_s" if m["name"].startswith("cache.") else "tokens_per_s")
    ends = [m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]]
    assert ends == ["tokens_per_s", "setup_s"]


# -- the byte count and the readers, by hand -------------------------------------


def test_decode_step_bytes_match_the_reckoning_by_hand(config):
    """ISSUE 54: at 32 rows x 22 k positions the two full layers' caches are
    3.6 GB of about 8.7, the hit experts 3.1 GB, the rings 0.12 GB."""
    need = bytes_mimo_v2.decode_step_bytes(config, live_rows=32, mean_context=22000.0)
    assert need["attention"] == 2 * (2 * FULL + 5 * WINDOW)
    assert need["dense_mlp"] == 2 * DENSE and need["router"] == 2 * 6 * ROUTER
    hit = 16 * (1 - (1 - 8 / 256) ** 32)
    assert need["experts_hit_per_layer"] == pytest.approx(hit) and 10.1 < hit < 10.3
    assert need["experts"] == pytest.approx(2 * 6 * hit * EXPERT)
    assert need["head"] == 2 * 19072 * E
    assert need["kv_full"] == 2 * 32 * 22000 * 2560 == 3_604_480_000
    assert need["kv_window"] == 5 * 32 * 144 * 5120 == 117_964_800
    assert need["total"] == pytest.approx(sum(v for k, v in need.items()
                                              if k not in ("total", "experts_hit_per_layer")))
    assert 8.5e9 < need["total"] < 8.8e9 and 0.40 < need["kv_full"] / need["total"] < 0.43
    every = bytes_mimo_v2.decode_step_bytes(config, live_rows=1e9, mean_context=0.0)
    assert every["total"] == pytest.approx(2 * (PARAMETERS - 19072 * E - 7 * 2 * E - E))
    assert bytes_mimo_v2.line_values(config, 0) == 4 * 320 and bytes_mimo_v2.line_values(config, 1) == 8 * 320
    assert bytes_mimo_v2.full_layers(config) == [0, 5]
    assert bytes_mimo_v2.full_attention_bytes(config, 2 * 32 * 22016) == 2 * 32 * 22016 * 2560


def hand_made_sources(config):
    """Chunk programs of depth 4 on a hand-made trace: 20 runs are 640 steps
    in 7.68 s, 12 ms a step. Over the traced span 32 live rows at a mean
    context of 22,000 (22,016 read: whole blocks of 512), the two ragged
    kernels kept among the operations at 2.4 ms a step each."""
    steps = 640

    def dump(chunks, scale):
        return {"default": {"continuous": {
            "chunks": chunks, "dispatches": chunks // 4, "decode_rows": 32 * chunks * 8,
            "decode_pad_rows": 0,
            "phase_s": {"wait_tokens": 0.3 * chunks, "firsts_wait": 0.0},
            "fill": {"pieces": 288, "tokens": 32 * 16384 + 32 * 48},
            "kv": {"bytes_full": 5_368_709_120, "bytes_window": 117_964_800},
            "attn_kv_positions_read": 9 + scale * steps * 2 * 32 * 22016,
            "attn_kv_positions_cached": 9 + scale * steps * 2 * 32 * 32768,
            "attn_ring_calls": 4 + scale * steps * 5, "attn_ring_kernel_calls": 4 + scale * steps * 5,
            "attn": {"sink_calls": 4 + scale * steps * 5, "window_layers": 5, "sink_layers": 5},
            "kv_write_rows": 3 + scale * steps * 32 * 14,
            "kv_write_rows_kernel": 3 + scale * steps * 32 * 14,
            "moe": {"assignments": scale * steps * 32 * 6 * 8, "assignments_held": scale * steps * 96,
                    "experts_hit": scale * steps * 6 * 10, "experts_read": scale * steps * 6 * 10,
                    "held_experts": 16, "sparse_layers": 6, "published_experts": 256}}},
            "compile_cache": {"store_hits": 9, "store_misses": 0},
            "device": {"hbm_peak_bytes": 13_400_000_000}}

    ops = [[f"ragged_decode_attention.{50 + i} bf16[32,64,512]", 0.0024 * steps] for i in range(2)]
    ops += [["moe_hit_experts.9 f32[32,4096]", 2.0], ["ring_decode_attention.7 bf16[32,64,1024]", 0.1]]
    return {"trace": {"window_s": 7.7, "idle_share": 0.002, "device_ops": ops, "modules": {
                "jit__chunk_impl_d4": {"seconds": 7.68, "count": 20}}},
            "trace_span": {"metrics_before": dump(800, 0), "metrics_after": dump(880, 1),
                           "seconds": 7.9},
            "metrics_before": dump(0, 0), "metrics_after": dump(80, 1),
            "config": config, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "max_slots": 32, "model": "default"}


KERNEL = "^%?ragged_decode_attention[.\\d]* = "


def test_the_new_readers_on_a_hand_made_trace(config, monkeypatch):
    """The kept trace holds the window's 20 runs less one cut in half: 624
    steps in 7.488 s, the kernel's 1,248 calls at 2.4 ms; the named reader
    takes the cut run for a whole one and reads the step short."""
    sources = hand_made_sources(config)
    sources["trace"]["modules"]["jit__chunk_impl_d4"]["seconds"] = 7.488
    monkeypatch.setattr(dsa_select_step_share, "operations", lambda src, patterns: (
        {KERNEL: [0.0024 * 1248, 1248]} if patterns == [KERNEL] and "trace" in src else None))
    reader, spec = reader_of("model.decode_step_ms.longdoc")
    assert reader is decode_step_ms_named and reader.read(sources, spec) == pytest.approx(11.7)
    found = decode_hbm_share_mimo_v2.live_rows_and_context(sources, {"chunk_size": 8})
    assert found == pytest.approx((32.0, 22016.0, 2 * 32 * 22016.0))
    assert decode_hbm_share_mimo_v2.experts_hit(sources, {"chunk_size": 8}) == pytest.approx(10.0)
    need = bytes_mimo_v2.decode_step_bytes(config, live_rows=32, mean_context=22016.0,
                                           experts_hit=10.0)
    assert need["experts"] == 2 * 6 * 10 * EXPERT
    reader, spec = reader_of("model.decode_hbm_share.longcode")
    assert reader is decode_hbm_share_mimo_v2
    share = reader.read(sources, spec)
    assert share == pytest.approx(need["total"] / 819e9 / 0.012) and 0.8 < share < 1.0  # counted
    reader, spec = reader_of("attn.full_roofline_share.longcode")
    assert reader is attn_full_roofline_share
    roof = 32 * 22016 * 2560 / 819e9  # one layer's lines once, at the HBM peak
    assert reader.read(sources, spec) == pytest.approx(roof / 0.0024) and roof / 0.0024 < 1.0
    want = {"attn.sink_share.longcode": 1.0, "attn.ring_kernel_share.reason": 1.0,
            "attn.kv_read_share.reason": 22016 / 32768, "kv.write_kernel_share.reason": 1.0,
            "moe.held_hit_share.longdoc": 10 / 16,
            "moe.held_assignment_share.longdoc": 0.0625, "moe.read_hit_share.longdoc": 1.0,
            "engine.fill_pieces.longdoc": 0.0, "engine.pad_fraction": 0.0,
            "engine.wait_ms.longdoc": 0.3 * 80 / 20 * 1e3, "device.idle_share": 0.002,
            "device.hbm_peak_gb": 13.4, "cache.store_hit_share.longdoc": 1.0}
    for name, value in want.items():
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) == pytest.approx(value), name
    # a trace without the kernel (another implementation under another name): no share
    monkeypatch.setattr(dsa_select_step_share, "operations", lambda src, patterns: {KERNEL: [0.0, 0]})
    for name in ("attn.full_roofline_share.longcode", "model.decode_hbm_share.longcode"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None


def test_a_program_without_the_counters_gives_nothing(config, monkeypatch):
    """The parent commit (it cannot run the configuration at all) or the CPU:
    no kernel's counters, no ``attn`` block."""
    monkeypatch.setattr(dsa_select_step_share, "operations",
                        lambda src, patterns: {KERNEL: [3.0, 1248]})
    sources = hand_made_sources(config)
    dumps = [sources["metrics_before"], sources["metrics_after"],
             sources["trace_span"]["metrics_before"], sources["trace_span"]["metrics_after"]]
    for dump in dumps:
        engine = dump["default"]["continuous"]
        for key in ("attn", "attn_kv_positions_read", "attn_kv_positions_cached",
                    "attn_ring_calls", "attn_ring_kernel_calls"):
            del engine[key]
    for name in ("model.decode_hbm_share.longcode", "attn.full_roofline_share.longcode",
                 "attn.sink_share.longcode"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None, name
    sources = hand_made_sources(config)
    del sources["trace_span"]  # an untraced run
    for name in ("model.decode_hbm_share.longcode", "attn.full_roofline_share.longcode"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None


def test_the_benchmarks_reference_is_the_programs_copy():
    with open(os.path.join(BENCH, "references", "mimo_v2.py")) as f:
        mine = f.read()
    with open(os.path.join(ROOT, "modelx_tpu", "models", "mimo_v2_reference.py")) as f:
        assert f.read() == mine
    assert "import modelx_tpu" not in mine and "from modelx_tpu" not in mine
    assert 'default_matmul_precision("highest")' in mine and "pallas" not in mine


def test_the_comparison_plants_sinks_large_enough_to_be_missed(tmp_path):
    """``compare_mimo_v2.plant_sinks`` writes over the sink tensors alone, in
    place; a second writing draws the same."""
    import numpy as np

    from benchmark.references import compare_mimo_v2
    from benchmark.references.compare_laguna import Checkpoint

    config = load(BENCH, "configs", CONFIG + ".json")
    tiny = dict(config, **config["rehearse"])
    hf = {k: v for k, v in tiny.items() if k not in ("rehearse", "assumed", "reduced_from")}
    model_dir, _, _ = checkpoint.ensure(str(tmp_path), "tiny", "mimo_v2", tiny, hf, 3, "F32")
    before = Checkpoint(model_dir)
    q_before = before["model.layers.1.self_attn.q_proj.weight"].copy()
    assert np.abs(before["model.layers.1.self_attn.attention_sink_bias"]).max() < 1.0
    assert compare_mimo_v2.plant_sinks(model_dir, 3) == 5  # the five window layers
    after = Checkpoint(model_dir)
    sinks = after["model.layers.1.self_attn.attention_sink_bias"]
    assert sinks.shape == (8,) and 2.0 <= sinks.min() and sinks.max() <= 5.0
    np.testing.assert_array_equal(after["model.layers.1.self_attn.q_proj.weight"], q_before)
    assert compare_mimo_v2.plant_sinks(model_dir, 3) == 5
    np.testing.assert_array_equal(
        Checkpoint(model_dir)["model.layers.1.self_attn.attention_sink_bias"], sinks)
