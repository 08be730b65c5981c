"""The MiniCPM-SALA configuration's files, byte count, readers and generator
(PR 35): what ``test_benchmark.py`` checks of the other cells, for the files
this cell adds. The generator's cases are here and not in ``test_benchmark.py``
because a PR that adds a cell may not edit a file the benchmark has.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import bytes_minicpm_sala, checkpoint  # noqa: E402
from benchmark.generators import closed_loop_primed  # noqa: E402
from benchmark.layer_metrics.readers import (decode_hbm_share_minicpm_sala,  # noqa: E402
                                             decode_step_ms_named)

CELL, CONFIG = "minicpm-sala-d12.longctx", "minicpm-sala-d12"
SPARSE_LAYER, LIGHTNING_LAYER, EMBED_AND_HEAD = 253_763_840, 285_225_216, 601_690_112


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


def test_every_published_number_is_kept_but_the_depth(config):
    """Against the source's values, written out here: no width is touched."""
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32, "lightning_scale": "1/sqrt(d)",
        "lightning_use_rope": True, "max_position_embeddings": 524288,
        "model_type": "minicpm_sala", "num_attention_heads": 32, "num_key_value_heads": 2,
        "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
        "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 256, "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True}
    assert {k: config[k] for k in published} == published
    all32 = (["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] + ["lightning-attn"] * 6
             + ["minicpm4"] * 2 + ["lightning-attn"] * 4 + ["minicpm4"] + ["lightning-attn"] * 6
             + ["minicpm4"] * 3)
    assert len(all32) == 32 and all32.count("minicpm4") == 8
    assert config["layer_share"] == {"published": 32, "first": 9}
    assert config["num_hidden_layers"] == 12 and config["mixer_types"] == all32[9:21]
    assert config["mixer_types"].count("minicpm4") * 24 == config["mixer_types"].count("lightning-attn") * 8
    assert config["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "init_blocks": 1,
                                       "block_size": 64, "window_size": 2048, "topk": 64,
                                       "dense_len": 8192}
    entry = next(c for c in load(ROOT, "BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert set(config["reduced_from"]) == set(entry["reduced"]) and entry["source"] == config["source"]
    assert config["reduced_from"]["num_hidden_layers"] == 32
    assert {k[0] for k in config["assumed"]} >= set("abcdefg")
    args = config["serve_args"]
    assert args == ["--continuous-batch", "--max-seq-len", "32768", "--max-slots", "32",
                    "--prefill-chunk", "2048", "--exit-with-parent"]
    tiny = dict(config, **config["rehearse"])["sparse_config"]
    assert tiny["dense_len"] < 256  # the rehearsal engages the selection inside its 256 positions


def test_the_checkpoints_bytes_are_the_tables(config):
    """ISSUE 35's arithmetic: 3 sparse and 9 lightning layers and the whole
    vocabulary are 3,930,008,576 parameters, 7.86 GB in bf16; the layers keep
    their published indices."""
    shards = checkpoint.family_module("minicpm_sala").shards(config)
    per_shard = [sum(checkpoint.nbytes([[t]]) for t in shard) // 2 for shard in shards]
    assert per_shard[1] == per_shard[8] == per_shard[9] == SPARSE_LAYER     # layers 9, 16, 17
    assert per_shard[2] == per_shard[7] == per_shard[12] == LIGHTNING_LAYER  # 10, 15, 20
    assert per_shard[0] + per_shard[-1] == EMBED_AND_HEAD
    total = 3 * SPARSE_LAYER + 9 * LIGHTNING_LAYER + EMBED_AND_HEAD
    assert total == 3_930_008_576 == config["bytes_predicted"]["parameters"]
    assert checkpoint.nbytes(shards) == 2 * total == config["bytes_predicted"]["weights_bf16"]
    names = dict(t for shard in shards for t in shard)
    assert names["model.layers.9.self_attn.k_proj.weight"] == (256, 4096)
    assert names["model.layers.10.self_attn.k_proj.weight"] == (4096, 4096)
    assert names["model.layers.20.self_attn.norm.weight"] == (4096,)
    assert names["model.layers.17.self_attn.o_gate.weight"] == (4096, 4096)
    assert "model.layers.17.self_attn.norm.weight" not in names
    assert not any(n.startswith(("model.layers.8.", "model.layers.21.", "model.layers.0.")) for n in names)
    assert [n for n, _ in shards[-1]] == ["model.norm.weight", "lm_head.weight"]
    b = config["bytes_predicted"]
    assert b["kv_sparse_layers"] == 3 * 32 * 32768 * 1024 and b["compressed_keys"] == 3 * 32 * 32768 * 32
    assert b["lightning_state"] == 9 * 32 * 32 * 128 * 128 * 4
    assert b["sum"] == b["weights_bf16"] + b["kv_sparse_layers"] + b["compressed_keys"] + b["lightning_state"]
    assert b["kv_if_every_layer_kept_32_full_kv_heads_at_slots_x_max_len"] > 200e9  # would not fit


def test_the_program_reads_the_same_shapes_as_the_checkpoint_layout(config):
    """The family's own table of tensors (models/minicpm_sala.param_shapes)
    against ``checkpoints/minicpm_sala.py``, so that a load finds every name."""
    from modelx_tpu.models import minicpm_sala

    hf = {k: v for k, v in config.items() if k not in ("rehearse", "assumed", "reduced_from")}
    theirs = minicpm_sala.param_shapes(minicpm_sala.config_from_hf(hf))
    mine = dict(t for shard in checkpoint.family_module("minicpm_sala").shards(config) for t in shard)
    assert theirs == mine


# -- the traffic -----------------------------------------------------------------


def test_the_cells_traffic_is_the_issues_and_no_request_would_be_refused(config):
    traffic = load(BENCH, "traffic", "longctx.json")
    want = {"generator": "closed_loop_primed", "clients": 32, "requests_per_client": 3,
            "prime": {"prompt_tokens": 48, "new_tokens": 16},
            "prompt": {"dist": "fixed", "value": 16384},
            "output": {"dist": "uniform", "min": 12288, "max": 16000}, "overrun": 8,
            "stagger_s": 2.5, "warm_group_sizes": [2], "trace_seconds": 8,
            "probe": {"prompt_tokens": 8240, "new_tokens": 16}}
    assert {k: traffic[k] for k in want} == want
    assert 95.0 <= traffic["lead_in_s"] <= 150.0
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
            assert 0 < min(r["prompt"]) and max(r["prompt"]) < config["vocab_size"]
        sizes.append(sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs))
        assert {-(-len(r["prompt"]) // 16) * 16 for r in reqs} == {48, 16384}  # buckets to warm
    assert sizes[0] == sizes[1]  # every seed offers the same sizes
    # the last client starts at 31 x 2.5 s and its prompt has the rest of the lead-in to land
    assert 31 * traffic["stagger_s"] + 10 < traffic["lead_in_s"]
    # at the 15 ms a step the chip takes at best (15.6 measured, PR 35) the shortest long
    # request outlasts lead-in + window even for the client that starts first
    assert 12288 * 0.015 > traffic["lead_in_s"] + 45
    assert traffic["probe"]["prompt_tokens"] > config["sparse_config"]["dense_len"]


def test_the_primed_generator_is_a_function_of_the_seed_and_reorders_the_same_sizes():
    """As ``test_benchmark.py`` holds of the other generators, on a mix whose
    sizes differ so that an order can be seen."""
    p = dict(load(BENCH, "traffic", "longctx.json"),
             prompt={"dist": "uniform", "min": 64, "max": 256}, requests_per_client=4)
    a = closed_loop_primed.schedule(7, p, 32000, 45.0, 32768)
    assert a == closed_loop_primed.schedule(7, p, 32000, 45.0, 32768)
    c = closed_loop_primed.schedule(2_500_000_011, p, 32000, 45.0, 32768)  # past 32 signed bits
    assert a != c
    flat = lambda s: [r for client in s["clients"] for r in client]  # noqa: E731
    shape = lambda s: sorted((len(r["prompt"]), r["max_new_tokens"]) for r in flat(s))  # noqa: E731
    assert shape(a) == shape(c)
    assert [len(r["prompt"]) for r in flat(a)] != [len(r["prompt"]) for r in flat(c)]
    for s in (a, c):
        assert all(len(client) == 4 and len(client[0]["prompt"]) == 48 for client in s["clients"])
    assert (a["lead_in_s"], a["stagger_s"]) == (p["lead_in_s"], p["stagger_s"])


def test_every_name_the_cell_adds_has_its_files():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longctx", 1)
    assert load(BENCH, "workloads", CELL + ".json")["config"] == CONFIG
    # an entry is a reading and the cells that report it are its ``workloads``: this
    # cell's entries are those that list it, under a name of its own or one it shares
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # at least, not exactly: a later PR may add a metric to this cell (PERF.md section 7)
    assert len(mine) >= 11
    assert all(m["name"].endswith(".longctx") == (m["workloads"] == [CELL]) for m in mine)
    assert {m["name"] for m in mine} >= {
        "model.decode_step_ms.longctx", "model.decode_hbm_share.longctx",
        "sparse.kv_read_share.longctx", "sparse.engaged_share.longctx", "linear.state_gb.longctx",
        "engine.fill_pieces.longctx", "engine.pad_fraction", "engine.wait_ms.longctx",
        "device.idle_share", "device.hbm_peak_gb", "cache.store_hit_share.longctx"}
    for m in mine:
        reader, spec = reader_of(m["name"])
        assert reader.read({}, spec) is None  # a program without the source: nothing, no raise
        assert m["moves"] == ("setup_s" if m["name"].startswith("cache.") else "tokens_per_s")
    reported = [m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]]
    assert reported == ["tokens_per_s", "setup_s"]
    assert importlib.import_module("benchmark.generators.closed_loop_primed").schedule


# -- the byte count and the readers, by hand -------------------------------------


def test_decode_step_bytes_match_the_reckoning_by_hand(config):
    """ISSUE 35: weights 7.26 GB, state 1.21 GB, sparse reads 0.46 GB a step
    with 32 rows at a context of 19,000: 10.9 ms at 819 GB/s."""
    need = bytes_minicpm_sala.decode_step_bytes(config, live_rows=32, mean_context=19000.0)
    attention = 3 * (3 * 4096 * 4096 + 2 * 256 * 4096) + 9 * 5 * 4096 * 4096
    assert need["attention_weights"] == 2 * attention
    assert need["mlp_weights"] == 2 * 12 * 3 * 4096 * 16384 and need["head"] == 2 * 73448 * 4096
    weights = need["attention_weights"] + need["mlp_weights"] + need["head"]
    norms = 2 * (12 * (2 * 4096 + 2 * 128) + 9 * 4096 + 4096)
    assert weights == 2 * (3_930_008_576 - 73448 * 4096) - norms  # all but the embedding and the norms
    assert need["state"] == 9 * 32 * 2 * 32 * 128 * 128 * 4       # read and written
    assert need["kv_attended"] == 3 * 32 * 4096 * 1024            # 64 blocks of 64, K and V, 2 heads of 128
    assert need["compressed_keys"] == 3 * 32 * 19000 / 16 * 512
    assert need["total"] == pytest.approx(sum(v for k, v in need.items() if k != "total"))
    assert 7.25e9 < weights < 7.27e9 and 1.20e9 < need["state"] < 1.22e9
    assert 0.45e9 < need["kv_attended"] + need["compressed_keys"] < 0.47e9
    assert need["total"] / 819e9 == pytest.approx(0.0109, abs=1e-4)
    # below dense_len a row reads all it holds; the counters' own count takes precedence
    assert bytes_minicpm_sala.positions_attended(config, 5000) == 5000
    assert bytes_minicpm_sala.positions_attended(config, 8192) == 4096
    short = bytes_minicpm_sala.decode_step_bytes(config, live_rows=32, mean_context=5000.0)
    assert short["kv_attended"] == 3 * 32 * 5000 * 1024
    told = bytes_minicpm_sala.decode_step_bytes(config, 32, 19000.0, positions_read=19000.0)
    assert told["kv_attended"] == 3 * 32 * 19000 * 1024  # the gather not happening: 1.4 GB more
    assert 1.3e9 < told["total"] - need["total"] < 1.5e9


def hand_made_sources(config):
    """Chunk programs of depth 4 on a hand-made trace: 20 runs are 640 steps
    in 8.96 s, 14 ms a step. Over the traced span 28 live rows of 32, three
    sparse layers: steps_all grows by 640 x 28 x 3, every one through the
    selection, 4,096 of 20,000 positions read."""
    steps = 640 * 28 * 3

    def dump(chunks, scale):
        return {"default": {"continuous": {
            "chunks": chunks, "dispatches": chunks // 4, "decode_rows": 32 * chunks * 8,
            "decode_pad_rows": 4 * chunks * 8,
            "phase_s": {"wait_tokens": 0.4 * chunks, "firsts_wait": 0.0},
            "fill": {"pieces": 256, "tokens": 256 * 2048},
            "kv": {"bytes_state": 603_979_776, "bytes_full": 3_221_225_472,
                   "bytes_index": 100_663_296, "states_live": 32},
            "sparse": {"positions_read": 1000 + scale * steps * 4096,
                       "positions_cached": 5000 + scale * steps * 20000,
                       "steps_sparse": 10 + scale * steps, "steps_all": 90 + scale * steps,
                       "sparse_layers": 3}}},
            "compile_cache": {"store_hits": 40, "store_misses": 0},
            "device": {"hbm_peak_bytes": 13_500_000_000}}

    return {"trace": {"window_s": 9.0, "idle_share": 0.001, "modules": {
                "jit__chunk_impl_d4": {"seconds": 8.96, "count": 20},
                "jit__piece_impl": {"seconds": 0.0, "count": 0}}},
            "trace_span": {"metrics_before": dump(800, 0), "metrics_after": dump(880, 1),
                           "seconds": 9.2},
            "metrics_before": dump(0, 0), "metrics_after": dump(1200, 1),
            "schedule_means": {"prompt": 10000.0, "output": 9000.0}, "config": config,
            "peaks": {"hbm_bytes_per_s": 819e9}, "max_slots": 32, "model": "default"}


def test_the_new_readers_on_a_hand_made_trace(config):
    sources = hand_made_sources(config)
    reader, spec = reader_of("model.decode_step_ms.longctx")
    assert reader is decode_step_ms_named and reader.read(sources, spec) == pytest.approx(14.0)
    need = bytes_minicpm_sala.decode_step_bytes(config, live_rows=28, mean_context=20000.0,
                                                positions_read=4096.0)
    reader, spec = reader_of("model.decode_hbm_share.longctx")
    share = reader.read(sources, spec)
    assert reader is decode_hbm_share_minicpm_sala
    assert share == pytest.approx(need["total"] / 819e9 / 0.014) and 0.7 < share < 0.8
    want = {"sparse.kv_read_share.longctx": 4096 / 20000, "sparse.engaged_share.longctx": 1.0,
            "linear.state_gb.longctx": 0.603979776, "engine.fill_pieces.longctx": 0.0,
            "engine.pad_fraction": 4 / 32, "engine.wait_ms.longctx": 0.4 * 80 / 20 * 1e3,
            "device.idle_share": 0.001, "device.hbm_peak_gb": 13.5,
            "cache.store_hit_share.longctx": 1.0}
    for name, value in want.items():
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) == pytest.approx(value), name
    # the counters are read over the traced span, not over lead-in + window:
    # the 256 pieces of the lead-in are in both of its dumps
    assert sources["metrics_after"]["default"]["continuous"]["fill"]["pieces"] == 256


def test_a_program_without_the_counters_gives_nothing(config):
    """The parent commit: no ``sparse``, ``fill`` or ``kv.bytes_state`` block."""
    sources = hand_made_sources(config)
    dumps = [sources["metrics_before"], sources["metrics_after"],
             sources["trace_span"]["metrics_before"], sources["trace_span"]["metrics_after"]]
    for dump in dumps:
        engine = dump["default"]["continuous"]
        del engine["sparse"], engine["fill"]
        engine["kv"] = {"bytes_full": 1}
    for name in ("model.decode_hbm_share.longctx", "sparse.kv_read_share.longctx",
                 "sparse.engaged_share.longctx", "linear.state_gb.longctx",
                 "engine.fill_pieces.longctx"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None, name
    sources = hand_made_sources(config)
    del sources["trace_span"]  # an untraced run
    reader, spec = reader_of("model.decode_hbm_share.longctx")
    assert reader.read(sources, spec) is None


def test_the_benchmarks_reference_is_the_programs_copy():
    with open(os.path.join(BENCH, "references", "minicpm_sala.py")) as f:
        mine = f.read()
    with open(os.path.join(ROOT, "modelx_tpu", "models", "minicpm_sala_reference.py")) as f:
        assert f.read() == mine
    assert "import modelx_tpu" not in mine and "from modelx_tpu" not in mine


@pytest.mark.skipif(os.environ.get("BENCH_REHEARSE") != "1",
                    reason="a minute: BENCH_REHEARSE=1 (tests/test_minicpm_sala_served.py rehearses the cell in tier 1)")
def test_rehearse_of_the_new_cell_ends():
    out = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--rehearse"],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0 and line["attempted"] > 0
