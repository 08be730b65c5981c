"""The Laguna configuration's files, byte count and readers (PR 33): what
``test_benchmark.py`` checks of the other cells, for the files this cell adds.

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

from benchmark import bytes_laguna, checkpoint  # noqa: E402
from benchmark.layer_metrics.readers import (decode_hbm_share_laguna, decode_step_ms_named,  # noqa: E402
                                             moe_held_hit_share)

CELL, CONFIG = "laguna-s-2.1-ep2-d5.reason", "laguna-s-2.1-ep2-d5"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(BENCH, "configs", CONFIG + ".json")


# -- the configuration ---------------------------------------------------------


def test_every_published_number_is_kept_but_the_three_cuts(config):
    """Against the source's values, written out here: no width is touched."""
    published = {
        "hidden_size": 3072, "intermediate_size": 12288, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 1048576,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "decoder_sparse_step": 1, "sliding_window": 512,
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
        "norm_topk_prob": True, "attention_bias": False, "tie_word_embeddings": False,
        "moe_apply_router_weight_on_input": False, "gating": "per-head", "mlp_only_layers": [0]}
    assert {k: config[k] for k in published} == published
    assert config["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5}
    assert config["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 128, 50176)
    assert config["reduced_from"]["num_experts"] == 256 and config["expert_share"] == {
        "published": 256, "first": 0}
    assert config["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert config["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    entry = next(c for c in load(ROOT, "BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] and set(config["reduced_from"]) == set(entry["reduced"])
    assert set(config["assumed"]) >= {"a_router", "b_gating", "c_qk_norm", "d_shared_expert", "e_names"}


def test_the_checkpoints_bytes_are_the_tables(config):
    """ISSUE 33's table: 5,572,076,544 parameters, 11.14 GB in bf16; the
    experts held are named for their published indices under a full router."""
    shards = checkpoint.family_module("laguna").shards(config)
    assert checkpoint.nbytes(shards) == 2 * 5_572_076_544 == config["bytes_predicted"]["weights_bf16"]
    names = dict(t for shard in shards for t in shard)
    assert names["model.layers.1.mlp.gate.weight"] == (256, 3072)
    assert names["model.layers.4.mlp.experts.127.down_proj.weight"] == (3072, 1024)
    assert "model.layers.1.mlp.experts.128.up_proj.weight" not in names
    assert names["model.layers.0.mlp.up_proj.weight"] == (12288, 3072)
    assert names["model.layers.2.self_attn.g_proj.weight"] == (72, 3072)
    assert names["model.layers.4.self_attn.q_proj.weight"] == (48 * 128, 3072)
    assert [n for n, _ in shards[-1]] == ["model.norm.weight", "lm_head.weight"]
    second = dict(config, expert_share={"published": 256, "first": 128})
    names = dict(t for shard in checkpoint.family_module("laguna").shards(second) for t in shard)
    assert "model.layers.1.mlp.experts.128.up_proj.weight" in names
    assert "model.layers.1.mlp.experts.0.up_proj.weight" not in names
    kv = config["bytes_predicted"]
    assert kv["kv_full_layers"] == 2 * 64 * 4096 * 4096 and kv["kv_window_layers_ring_528"] == 3 * 64 * 528 * 4096
    assert kv["kv_if_every_layer_were_slots_x_max_len"] + kv["weights_bf16"] > 16e9  # would not fit


# -- the traffic -----------------------------------------------------------------


def test_the_cells_traffic_is_the_issues_and_no_request_would_be_refused(config):
    traffic = load(BENCH, "traffic", "reason.json")
    want = {"generator": "closed_loop", "clients": 64, "requests_per_client": 6, "overrun": 8,
            "shape_seed": 1, "lead_in_s": 12.0, "stagger_s": 0.15, "warm_group_sizes": [2, 4],
            "trace_seconds": 4, "probe": {"prompt_tokens": 48, "new_tokens": 16},
            "prompt": {"dist": "uniform", "min": 64, "max": 256},
            "output": {"dist": "uniform", "min": 1024, "max": 3072}}
    assert {k: traffic[k] for k in want} == want
    generator = importlib.import_module("benchmark.generators.closed_loop")
    sizes = []
    for seed in (0, 2**31 + 5):
        sched = generator.schedule(seed, traffic, config["vocab_size"], 45.0, 4096)
        assert len(sched["clients"]) == 64 == int(config["serve_args"][config["serve_args"].index("--max-slots") + 1])
        reqs = [r for c in sched["clients"] for r in c]
        for r in reqs:
            pad = -(-len(r["prompt"]) // 16) * 16
            assert pad + r["max_new_tokens"] + 8 <= 4096
            assert 0 < min(r["prompt"]) and max(r["prompt"]) < config["vocab_size"]
        sizes.append(sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs))
        assert len({-(-len(r["prompt"]) // 16) for r in reqs}) == 13  # prompt buckets to warm
    assert sizes[0] == sizes[1]  # every seed offers the same sizes


def test_every_name_the_cell_adds_has_its_files():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason", 1)
    assert load(BENCH, "workloads", CELL + ".json")["config"] == CONFIG
    # an entry is a reading and the cells that report it are its ``workloads``: this
    # cell's entries are those that list it, under a name of its own or one it shares
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # at least, not exactly: later PRs added metrics to this cell (14 at PR 48)
    assert len(mine) >= 10
    for m in mine:
        spec = load(BENCH, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
        assert reader.read({}, spec) is None  # a program without the source: nothing, no raise
    reported = [m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]]
    assert reported == ["tokens_per_s", "setup_s"]


# -- the byte count and the readers, by hand -------------------------------------


def test_decode_step_bytes_match_the_reckoning_by_hand(config):
    need = bytes_laguna.decode_step_bytes(config, live_rows=64, mean_context=1000.0)
    hit = 128 * (1 - (1 - 10 / 256) ** 64)
    assert need["experts_hit_per_layer"] == pytest.approx(hit) and 117 < hit < 119
    assert need["attention"] == 2 * (2 * 44_187_648 + 3 * 63_135_744)
    assert need["dense_mlp"] == 2 * 113_246_208
    assert need["router"] == 2 * 4 * 256 * 3072 and need["shared_expert"] == 2 * 4 * 9_437_184
    assert need["experts"] == pytest.approx(2 * 4 * hit * 9_437_184)
    assert need["kv_full"] == 2 * 64 * 1000 * 4096 and need["kv_window"] == 3 * 64 * 512 * 4096
    assert need["head"] == 2 * 50176 * 3072
    assert need["total"] == pytest.approx(sum(need[k] for k in (
        "attention", "dense_mlp", "router", "shared_expert", "experts", "kv_full", "kv_window", "head")))
    assert 10.5e9 < need["total"] < 11.5e9
    short = bytes_laguna.decode_step_bytes(config, live_rows=64, mean_context=100.0)
    assert short["kv_window"] == 3 * 64 * 100 * 4096  # a window layer reads min(context, window)
    assert bytes_laguna.expected_held_hit(128, 256, 10, 0) == 0


def hand_made_sources(config):
    """Chunk programs of two depths on a hand-made trace: 10 runs of depth 1
    and 5 of depth 4 are (10 + 20) x 8 = 240 steps in 4.8 s: 20 ms a step."""
    engine = lambda chunks, hit, held, routed: {"default": {"continuous": {  # noqa: E731
        "chunks": chunks, "dispatches": chunks // 2, "decode_rows": 64 * chunks,
        "decode_pad_rows": 0, "moe": {"experts_hit": hit, "assignments_held": held,
                                      "assignments": routed, "held_experts": 128,
                                      "published_experts": 256, "sparse_layers": 4}}}}
    return {"trace": {"window_s": 5.0, "modules": {
                "jit__chunk_impl_d1": {"seconds": 1.6, "count": 10},
                "jit__chunk_impl_d4": {"seconds": 3.2, "count": 5},
                "jit__admit_nosmall": {"seconds": 0.1, "count": 3}}},
            "metrics_before": engine(100, 1000, 5000, 10000),
            "metrics_after": engine(130, 1000 + 240 * 4 * 118, 5000 + 301, 10000 + 600),
            "schedule_means": {"prompt": 160.0, "output": 2048.0}, "config": config,
            "peaks": {"hbm_bytes_per_s": 819e9}, "max_slots": 64, "model": "default"}


def test_the_new_readers_on_a_hand_made_trace(config):
    sources = hand_made_sources(config)
    spec = load(BENCH, "layer_metrics", "model.decode_step_ms.reason.json")
    assert decode_step_ms_named.read(sources, spec) == pytest.approx(20.0)
    need = bytes_laguna.decode_step_bytes(config, live_rows=64, mean_context=160 + 1024)
    share = decode_hbm_share_laguna.read(sources, load(BENCH, "layer_metrics", "model.decode_hbm_share.reason.json"))
    assert share == pytest.approx(need["total"] / 819e9 / 0.020) and 0.6 < share < 0.8
    hit = moe_held_hit_share.read(sources, load(BENCH, "layer_metrics", "moe.held_hit_share.reason.json"))
    assert hit == pytest.approx(118 / 128)
    spec = load(BENCH, "layer_metrics", "moe.held_assignment_share.reason.json")
    reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
    assert reader.read(sources, spec) == pytest.approx(301 / 600)


def test_a_program_older_than_the_naming_or_the_counters_gives_nothing(config):
    """The parent commit: ``jit__chunk_impl`` without a depth, no ``moe`` block."""
    sources = hand_made_sources(config)
    sources["trace"]["modules"] = {"jit__chunk_impl": {"seconds": 4.8, "count": 15}}
    for side in ("metrics_before", "metrics_after"):
        del sources[side]["default"]["continuous"]["moe"]
    for name in ("model.decode_step_ms.reason", "model.decode_hbm_share.reason",
                 "moe.held_hit_share.reason", "moe.held_assignment_share.reason"):
        spec = load(BENCH, "layer_metrics", name + ".json")
        reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
        assert reader.read(sources, spec) is None, name


def test_the_benchmarks_reference_is_the_programs_copy():
    with open(os.path.join(BENCH, "references", "laguna.py")) as f:
        mine = f.read()
    with open(os.path.join(ROOT, "modelx_tpu", "models", "laguna_reference.py")) as f:
        assert f.read() == mine
    assert "modelx_tpu" not in mine.replace("models/laguna.py", "")  # it imports nothing of the program


@pytest.mark.skipif(os.environ.get("BENCH_REHEARSE") != "1",
                    reason="a minute: BENCH_REHEARSE=1 (tests/test_bench_smoke.py rehearses the cell in tier 1)")
def test_rehearse_of_the_new_cell_ends():
    out = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--rehearse"],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0 and line["attempted"] > 0
