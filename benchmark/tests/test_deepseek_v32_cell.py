"""The DeepSeek-V3.2-Exp configuration's files, byte count, readers and traffic
(PR 50), in ``test_deepseek_v2_cell.py``'s form: what ``test_benchmark.py``
checks of the other cells, for the files this cell adds.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import json
import math
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import bytes_deepseek_v32, checkpoint  # noqa: E402
from benchmark.generators import closed_loop_primed  # noqa: E402
from benchmark.layer_metrics.readers import (decode_hbm_share_deepseek_v32,  # noqa: E402
                                             decode_step_ms_counted, dsa_select_step_share)

CELL, CONFIG = "deepseek-v3.2-exp-ep16-d5.sparsedoc", "deepseek-v3.2-exp-ep16-d5"
ATTENTION, INDEXER, NORMS = 187_107_328, 13_959_424, 14_336  # attention with its two latent norms
DENSE_MLP, EXPERT_FFN, EMBED_HEAD_NORM = 396_361_728, 750_518_528, 231_676_928
PARAMETERS = 4_635_518_208


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


def test_every_published_number_is_kept_but_the_four_reduced(config):
    """Against the source's values, written out here: no width is touched."""
    published = {
        "attention_bias": False, "ep_size": 1, "hidden_act": "silu", "hidden_size": 7168,
        "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
        "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v32", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
        "n_group": 8, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "noaux_tc", "v_head_dim": 128,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                         "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                         "type": "yarn"}}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"]) == (5, 1, 16, 16160)
    assert config["reduced_from"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                      "n_routed_experts": 256, "vocab_size": 129280}
    assert config["expert_share"] == {"published": 256, "first": 0}
    # the floors: four layers after the dense one, 8 or more routed experts, an eighth
    # of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 == 129280
    entry = next(c for c in load(ROOT, "BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert entry["source"] == config["source"] and entry["file"].endswith(CONFIG + ".json")
    assert "one of 16 v5e chips" in config["deployment"] and "0-15 of 256" in config["deployment"]
    assert "pipeline stages" in config["deployment"] and "0-16159" in config["deployment"]
    assert {k[0] for k in config["assumed"]} >= set("abcdefghijklm")
    assert config["serve_args"] == ["--continuous-batch", "--max-seq-len", "32768", "--max-slots",
                                    "16", "--prefill-chunk", "2048", "--exit-with-parent"]
    assert config["min_argmax_agreement"] == 0.6 and config["min_argmax_agreement_why"]
    tiny = dict(config, **config["rehearse"])
    # the rehearsal selects: its index_topk is below its contexts (prompts of 96, probes of 72)
    assert tiny["index_topk"] < 72 and tiny["model_type"] == "deepseek_v32"


def test_the_checkpoints_bytes_are_the_tables(config):
    """ISSUE 50's table: attention 187,107,328 a layer, the indexer 13,959,424,
    the dense MLP 396,361,728, an expert layer's FFN 750,518,528 (16 experts,
    the shared one, the router at 256 with its bias), 16,160 rows of embedding
    and of head: 4,635,518,208 parameters, 9,271,036,416 bytes in bf16."""
    shards = checkpoint.family_module("deepseek_v32").shards(config)
    per_shard = [sum(checkpoint.nbytes([[t]]) for t in shard) // 2 for shard in shards]
    assert per_shard[1] == ATTENTION + INDEXER + NORMS + DENSE_MLP == 597_442_816
    assert per_shard[2] == per_shard[5] == ATTENTION + INDEXER + NORMS + EXPERT_FFN == 951_599_616
    assert per_shard[0] + per_shard[-1] == EMBED_HEAD_NORM
    b = config["bytes_predicted"]
    assert sum(per_shard) == PARAMETERS == b["parameters"]
    assert checkpoint.nbytes(shards) == 9_271_036_416 == b["weights_bf16"]
    assert (b["attention_parameters_per_layer"], b["indexer_parameters_per_layer"],
            b["dense_mlp_parameters_layer_0"],
            b["expert_layer_ffn_parameters_router_bias_shared_16_experts"]) \
        == (ATTENTION, INDEXER, DENSE_MLP, EXPERT_FFN)
    names = dict(t for shard in shards for t in shard)
    x = "model.layers.0.self_attn.indexer."
    assert names[x + "wq_b.weight"] == (8192, 1536) and names[x + "wk.weight"] == (128, 7168)
    assert names[x + "k_norm.weight"] == names[x + "k_norm.bias"] == (128,)
    assert names[x + "weights_proj.weight"] == (64, 7168)
    assert names["model.layers.4.self_attn.kv_a_proj_with_mqa.weight"] == (576, 7168)
    assert names["model.layers.1.mlp.gate.weight"] == (256, 7168)  # the router's published width
    assert names["model.layers.1.mlp.gate.e_score_correction_bias"] == (256,)
    assert names["model.layers.1.mlp.shared_experts.gate_proj.weight"] == (2048, 7168)
    assert names["model.layers.1.mlp.experts.15.down_proj.weight"] == (7168, 2048)
    assert "model.layers.1.mlp.experts.16.down_proj.weight" not in names
    assert names["model.layers.0.mlp.gate_proj.weight"] == (18432, 7168)
    assert "model.layers.0.mlp.gate.weight" not in names and "model.layers.5.input_layernorm.weight" not in names
    assert not any("nextn" in n or "eh_proj" in n for n in names)  # no prediction layer
    assert [n for n, _ in shards[-1]] == ["model.norm.weight", "lm_head.weight"]
    # the cache: a 640-lane line and a 128-lane index key a position a layer
    assert (b["latent_line_lanes_cached"], b["index_key_lanes_cached"]) == (640, 128)
    assert b["cache_bytes_per_position_per_layer"] == 1536
    assert b["cache_16_slots_x_32768_positions_x_5_layers"] == 16 * 32768 * 5 * 1536 == 4_026_531_840
    assert b["latent_leaves"] + b["index_leaves"] == 4_026_531_840
    assert b["sum"] == 13_297_568_256 and 0.25 < b["share_of_16GB"] < 1.0


def test_the_program_reads_the_same_shapes_as_the_checkpoint_layout(config):
    """The family's own table of tensors (models/deepseek_v2.param_shapes, the
    experts folded) against ``checkpoints/deepseek_v32.py``'s per-expert names."""
    from modelx_tpu.models import deepseek_v2

    hf = {k: v for k, v in config.items() if k not in ("rehearse", "assumed", "reduced_from")}
    cfg = deepseek_v2.config_from_hf(hf)
    theirs = deepseek_v2.param_shapes(cfg)
    mine = dict(t for shard in checkpoint.family_module("deepseek_v32").shards(config) for t in shard)
    folded = {}
    for name, shape in mine.items():
        if ".mlp.experts." in name:
            head, rest = name.split(".mlp.experts.")
            index, tail = rest.split(".", 1)
            key = f"{head}.mlp.experts.{tail}"
            folded[key] = (folded.get(key, (0,))[0] + 1, *shape)
        else:
            folded[name] = shape
    assert theirs == folded
    assert (cfg.held, cfg.groups, cfg.line_width, cfg.index_topk) == ((0, 16), (8, 4), 640, 2048)
    assert cfg.softmax_scale == pytest.approx(0.1352, abs=1e-4)
    tiny = deepseek_v2.config_from_hf(dict(hf, **config["rehearse"]))
    assert (tiny.index_topk, tiny.index_heads, tiny.index_dim, tiny.noaux) == (24, 4, 16, True)


# -- the traffic -----------------------------------------------------------------


def test_the_cells_traffic_is_the_issues_and_no_request_would_be_refused(config):
    traffic = load(BENCH, "traffic", "sparsedoc.json")
    want = {"generator": "closed_loop_primed", "clients": 16, "requests_per_client": 3,
            "prime": {"prompt_tokens": 48, "new_tokens": 16},
            "prompt": {"dist": "fixed", "value": 16384},
            "output": {"dist": "uniform", "min": 12288, "max": 16000}, "overrun": 8,
            "shape_seed": 1, "warm_group_sizes": [2], "trace_seconds": 8,
            "probe": {"prompt_tokens": 8240, "new_tokens": 16}}
    assert {k: traffic[k] for k in want} == want
    # ISSUE 43's rule, at 16 clients: lead_in_s a multiple of 10 s, 160 s at most;
    # stagger_s = (lead_in_s - 25) / 16 rounded down to 0.5 s
    assert traffic["lead_in_s"] % 10 == 0 and traffic["lead_in_s"] <= 160
    assert traffic["stagger_s"] == math.floor((traffic["lead_in_s"] - 25) / 16 * 2) / 2
    slots = int(config["serve_args"][config["serve_args"].index("--max-slots") + 1])
    sizes = []
    for seed in (0, 2**31 + 5):
        sched = closed_loop_primed.schedule(seed, traffic, config["vocab_size"], 45.0, 32768)
        assert sched["mode"] == "closed" and len(sched["clients"]) == 16 == slots
        for client in sched["clients"]:
            assert [len(r["prompt"]) for r in client] == [48, 16384, 16384]  # the prime one first
            assert client[0]["max_new_tokens"] == 16
            assert all(12288 <= r["max_new_tokens"] <= 16000 for r in client[1:])
        reqs = [r for c in sched["clients"] for r in c]
        for r in reqs:
            pad = -(-len(r["prompt"]) // 16) * 16
            assert pad + r["max_new_tokens"] + 8 <= 32768
            assert 0 < min(r["prompt"]) and max(r["prompt"]) < config["vocab_size"] == 16160
        sizes.append(sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs))
    assert sizes[0] == sizes[1]  # every seed offers the same sizes
    # every probe and every long prompt selects: well past index_topk
    assert traffic["probe"]["prompt_tokens"] > 4 * config["index_topk"]
    assert (traffic["lead_in_s"], traffic["stagger_s"]) == (100.0, 4.5)  # the rule's fixed point
    # no long request ends inside the window: at the 12 ms a step the window's steps take
    # at best (12.99 measured, PR 50), and with the fifteen prompts that land after it
    # holding the device 4 s each at least (4.45 s from sent to first token on an idle pod),
    # the shortest request of the client that starts first outlasts lead-in + window
    assert 12288 * 0.012 + 15 * 4.0 > traffic["lead_in_s"] + 45


def test_every_name_the_cell_adds_has_its_files():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "sparsedoc", 1)
    assert len(cell["why"]) <= 200
    sidecar = load(BENCH, "workloads", CELL + ".json")
    assert sidecar["config"] == CONFIG and sidecar["why"] and sidecar["who"]
    # an entry is a reading and the cells that report it are its ``workloads``: this
    # cell's entries are those that list it, under a name of its own or one it shares
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # at least, not exactly: a later PR may add a metric to this cell
    assert len(mine) >= 17
    assert {m["name"] for m in mine} >= {
        "dsa.selected_share.sparsedoc", "dsa.selecting_share.sparsedoc",
        "dsa.index_cache_gb.sparsedoc", "dsa.selection_step_share.sparsedoc",
        "model.decode_step_ms.sparsedoc", "model.decode_hbm_share.sparsedoc",
        "mla.kv_read_share.sparsedoc", "mla.absorbed_share.sparsedoc",
        "latent.cache_gb.sparsedoc", "moe.held_hit_share.sparsedoc",
        "moe.held_assignment_share.sparsedoc", "moe.read_hit_share.sparsedoc",
        "engine.pad_fraction", "engine.wait_ms",
        "engine.fill_pieces.sparsedoc", "device.idle_share",
        "device.hbm_peak_gb", "cache.store_hit_share.sparsedoc"}
    layers = {m["layer"] for m in bench["per_layer"] if m.get("workloads") != [CELL]}
    for m in mine:
        reader, spec = reader_of(m["name"])
        assert reader.read({}, spec) is None  # a program without the source: nothing, no raise
        assert m["moves"] == ("setup_s" if m["name"].startswith("cache.") else "tokens_per_s")
        assert m["layer"] in layers  # the names PERF.md's list of layers already has
    reported = [m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]]
    assert reported == ["tokens_per_s", "setup_s"]
    # "in", not "last": a later configuration's cell is appended after this one
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]
    # what it shares with other cells has no cell's suffix (PR 57); what is its own has its own
    assert all(m["name"].endswith(".sparsedoc") == (m["workloads"] == [CELL]) for m in mine)
    assert "dsa.select_step_share.sparsedoc" not in {m["name"] for m in bench["per_layer"]}


# -- the byte count and the readers, by hand -------------------------------------


def test_decode_step_bytes_match_the_reckoning_by_hand(config):
    """ISSUE 50: a step reads the weights (all but the embedding, the norms and
    the held experts no row hits) and, at 16 rows x 21 k positions, 0.43 GB of
    index keys and 0.19 GB of selected lines (2,048 of 576 values a row a
    layer) — a tenth of the 1.9 GB V2's dense read of the same contexts costs."""
    need = bytes_deepseek_v32.decode_step_bytes(config, live_rows=16, mean_context=21000.0)
    assert need["attention"] == 2 * 5 * (ATTENTION - 2048)  # matrices, not the two latent norms
    assert need["indexer"] == 2 * 5 * INDEXER and need["dense_mlp"] == 2 * DENSE_MLP
    assert need["router"] == 2 * 4 * (256 * 7168 + 256)
    assert need["shared_experts"] == 2 * 4 * 3 * 7168 * 2048
    hit = 16 * (1 - (1 - 8 / 256) ** 16)
    assert need["experts_read_per_layer"] == pytest.approx(hit) and 6.3 < hit < 6.5
    assert need["experts"] == pytest.approx(2 * 4 * hit * 3 * 7168 * 2048)
    assert need["head"] == 2 * 16160 * 7168
    assert need["index_keys"] == 5 * 16 * 21000 * 128 * 2 == 430_080_000
    assert need["latent_lines"] == 5 * 16 * 2048 * 576 * 2 == 188_743_680
    assert need["total"] == pytest.approx(sum(v for k, v in need.items()
                                              if k not in ("total", "experts_read_per_layer")))
    assert 5.4e9 < need["total"] < 6.4e9  # the issue's "about 5.6 GB of weights" and the cache reads
    # a context below index_topk keeps every line; the counters, where given, are taken as they are
    short = bytes_deepseek_v32.decode_step_bytes(config, live_rows=16, mean_context=1000.0)
    assert short["latent_lines"] == 5 * 16 * 1000 * 576 * 2
    told = bytes_deepseek_v32.decode_step_bytes(config, 16, 21000.0, experts_read=16.0,
                                                lines_selected=2048.0)
    assert told["experts"] == 2 * 4 * 16 * 3 * 7168 * 2048 and told["latent_lines"] == need["latent_lines"]
    every = bytes_deepseek_v32.decode_step_bytes(config, live_rows=1e9, mean_context=0.0)
    assert every["total"] == pytest.approx(
        2 * (PARAMETERS - 16160 * 7168 - 5 * (NORMS + 2048) - 7168))


def hand_made_sources(config):
    """Chunk programs of depth 4 on a hand-made trace: 20 whole runs are 640
    steps in 8.32 s, 13 ms a step, and a run of depth 2 cut at the window's
    edge. Over the traced span 16 live rows, five layers at a mean context of
    21,000, 2,048 lines kept a row, every row selecting; the kernel read 6.4
    experts a layer a step."""
    row_steps = 640 * 16 * 5

    def dump(chunks, scale):
        return {"default": {"continuous": {
            "chunks": chunks, "dispatches": chunks // 4, "decode_rows": 16 * chunks * 8,
            "decode_pad_rows": 0,
            "phase_s": {"wait_tokens": 0.4 * chunks, "firsts_wait": 0.0},
            "fill": {"pieces": 144, "tokens": 16 * 16384 + 16 * 48},
            "kv": {"bytes_latent": 3_355_443_200, "bytes_index": 671_088_640, "bytes_full": 0},
            "mla": {"positions_read": 7 + scale * row_steps * 2048,
                    "positions_cached": 5 + scale * row_steps * 21000,
                    "steps_absorbed": 3 + scale * row_steps, "steps_all": 3 + scale * row_steps,
                    "layers": 5, "heads": 128, "kv_lora_rank": 512, "rope_dim": 64},
            "dsa": {"positions_scored": 5 + scale * row_steps * 21000,
                    "lines_selected": 9 + scale * row_steps * 2048,
                    "steps_selecting": 2 + scale * row_steps, "steps_all": 3 + scale * row_steps,
                    "layers": 5, "index_topk": 2048},
            "moe": {"assignments": scale * 640 * 16 * 4 * 8, "assignments_held": scale * 640 * 32,
                    "experts_hit": scale * 640 * 4 * 6.4, "experts_read": scale * 640 * 4 * 6.4,
                    "held_experts": 16, "sparse_layers": 4, "published_experts": 256}}},
            "compile_cache": {"store_hits": 9, "store_misses": 0},
            "device": {"hbm_peak_bytes": 14_100_000_000}}

    return {"trace": {"window_s": 8.4, "idle_share": 0.001, "device_planes": 1,
                      "device_ops": [["fusion.9 bf16[16,16160]", 0.1]],
                      "modules": {"jit__chunk_impl_d4": {"seconds": 8.32, "count": 20},
                                  "jit__chunk_impl_d2": {"seconds": 0.05, "count": 1}}},
            "trace_span": {"metrics_before": dump(800, 0), "metrics_after": dump(880, 1),
                           "seconds": 9.8},
            "metrics_before": dump(0, 0), "metrics_after": dump(80, 1), "cell": CELL,
            "config": config, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "max_slots": 16, "model": "default"}


def test_the_new_readers_on_a_hand_made_trace(config, monkeypatch):
    sources = hand_made_sources(config)
    reader, spec = reader_of("model.decode_step_ms.sparsedoc")
    # the window's own count of steps, not runs x depth x 8: of the 21 runs one is cut, and
    # the kept trace (none here) holds 644 events of the head's product in 8.37 s
    assert reader is decode_step_ms_counted and re.search(spec["once"], "%f.7 = bf16[16,16160]{1,0}")
    assert reader.read(sources, spec) is None  # no kept trace to count in: nothing
    monkeypatch.setattr(dsa_select_step_share, "operations",
                        lambda sources, patterns: {p: [0.19, 644] for p in patterns})
    assert reader.read(sources, spec) == pytest.approx(8.37 / 644 * 1e3)
    monkeypatch.setattr(dsa_select_step_share, "operations",
                        lambda sources, patterns: {p: [0.19, 643.8461538] for p in patterns})
    reader, spec = reader_of("model.decode_hbm_share.sparsedoc")
    assert reader is decode_hbm_share_deepseek_v32
    assert reader.counted(sources) == pytest.approx((16.0, 21000.0, 2048.0, 6.4))
    need = bytes_deepseek_v32.decode_step_bytes(config, 16, 21000.0, experts_read=6.4,
                                                lines_selected=2048.0)
    share = reader.read(sources, spec)
    assert share == pytest.approx(need["total"] / 819e9 / 0.013) and 0.4 < share < 0.7
    want = {"dsa.selected_share.sparsedoc": 2048 / 21000, "dsa.selecting_share.sparsedoc": 1.0,
            "dsa.index_cache_gb.sparsedoc": 0.67108864, "latent.cache_gb.sparsedoc": 3.3554432,
            "mla.kv_read_share.sparsedoc": 2048 / 21000, "mla.absorbed_share.sparsedoc": 1.0,
            "moe.held_hit_share.sparsedoc": 6.4 / 16, "moe.held_assignment_share.sparsedoc": 1 / 16,
            "moe.read_hit_share.sparsedoc": 1.0, "engine.fill_pieces.sparsedoc": 0.0,
            "engine.pad_fraction": 0.0, "engine.wait_ms": 0.4 * 80 / 20 * 1e3,
            "device.idle_share": 0.001, "device.hbm_peak_gb": 14.1,
            "cache.store_hit_share.sparsedoc": 1.0}
    for name, value in want.items():
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) == pytest.approx(value), name
    # the selection's share goes back to the kept trace itself: none here, so nothing
    monkeypatch.undo()
    reader, spec = reader_of("dsa.selection_step_share.sparsedoc")
    assert reader is dsa_select_step_share and spec["match"]
    assert reader.read(sources, spec) is None


def test_a_program_without_the_counters_gives_nothing(config):
    """The parent commit: no ``dsa`` block, no ``kv.bytes_index``."""
    sources = hand_made_sources(config)
    dumps = [sources["metrics_before"], sources["metrics_after"],
             sources["trace_span"]["metrics_before"], sources["trace_span"]["metrics_after"]]
    for dump in dumps:
        engine = dump["default"]["continuous"]
        del engine["dsa"]
        engine["kv"] = {"bytes_full": 1, "bytes_latent": 2}
    for name in ("model.decode_hbm_share.sparsedoc", "dsa.selected_share.sparsedoc",
                 "dsa.selecting_share.sparsedoc", "dsa.index_cache_gb.sparsedoc"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None, name
    sources = hand_made_sources(config)
    del sources["trace_span"]  # an untraced run
    for name in ("model.decode_hbm_share.sparsedoc", "dsa.selected_share.sparsedoc"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None


def test_the_selections_share_is_read_off_a_kept_trace(tmp_path, monkeypatch):
    """``dsa_select_step_share`` adds up the operations its patterns match on
    the first device plane: here through its own reduction on hand-made planes
    (the child that reads an ``.xplane.pb`` needs a real trace)."""
    from benchmark import xplane

    ops = [("%fusion.1 = f32[16,32768]{1,0} fusion(...), metadata={op_name=\"jit(f)/dsa.score/dot\"}",
            0.0, 2e6, {}),
           ("%sort.3 = (f32[16,32768], s32[16,32768]) sort(...), metadata={op_name=\"jit(f)/dsa.select/top_k\"}",
            3e6, 4e6, {}),
           ("%gather.9 = bf16[16,2048,640]{2,1,0} gather(...), metadata={op_name=\"jit(f)/dsa.gather/gather\"}",
            8e6, 1e6, {}),
           ("%fusion.7 = bf16[16,16160]{1,0} fusion(...)", 10e6, 5e6, {})]
    planes = [("/device:TPU:0", [(xplane.OPS_LINE, ops), (xplane.MODULES_LINE, [])])]
    monkeypatch.setattr(xplane, "load", lambda path: planes)
    found = dsa_select_step_share.matching(str(tmp_path), [r"dsa\.score", r"dsa\.select",
                                                           r"dsa\.gather"])
    assert {p: n for p, (_, n) in found.items()} == {r"dsa\.score": 1, r"dsa\.select": 1,
                                                     r"dsa\.gather": 1}
    assert {p: s for p, (s, _) in found.items()} == pytest.approx(
        {r"dsa\.score": 2e-3, r"dsa\.select": 4e-3, r"dsa\.gather": 1e-3})
    assert dsa_select_step_share.matching(str(tmp_path), ["nothing"]) == {"nothing": [0.0, 0]}
    # the step's reader counts the head's product in the same pass: one event, 5 ms of modules
    _, spec = reader_of("model.decode_step_ms.sparsedoc")
    assert dsa_select_step_share.matching(str(tmp_path), [spec["once"]])[spec["once"]][1] == 1


def test_the_benchmarks_reference_is_the_programs_copy():
    with open(os.path.join(BENCH, "references", "deepseek_v32.py")) as f:
        mine = f.read()
    with open(os.path.join(ROOT, "modelx_tpu", "models", "deepseek_v32_reference.py")) as f:
        theirs = f.read()
    assert mine == theirs
    assert "import modelx" not in mine and "from modelx" not in mine


def test_rehearse_of_the_cell_ends_with_its_last_line():
    """The cell's files, the checkpoint layout, the pod's flags, the primed
    generator, the new readers: walked at the tiny preset (``index_topk`` 24
    under prompts of 96), as ``--rehearse`` always ends."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--rehearse",
         "--trace", "1"], env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 0
    probes = next(l for l in lines if l.get("phase") == "probes")
    assert probes["argmax_agreement"] >= 0.9  # float32 here: the two programs agree
    metrics = last["metrics"]
    assert len(metrics) >= 12
    assert 0.5 < metrics["dsa.selecting_share.sparsedoc"]["value"] <= 1.0
    assert 0 < metrics["dsa.selected_share.sparsedoc"]["value"] < 1.0
    assert metrics["dsa.index_cache_gb.sparsedoc"]["value"] > 0
    assert metrics["mla.absorbed_share.sparsedoc"]["value"] == 1.0
    assert metrics["mla.kv_read_share.sparsedoc"]["value"] < 1.0  # the gathered lines alone
    assert 0 < metrics["moe.held_assignment_share.sparsedoc"]["value"] < 1
    # the step's and the selection's readers go back to the kept trace in a child of their
    # own: a CPU trace has no device plane, so the run above gave neither metric; the child
    # itself starts, imports its neighbours and reads the trace the run left
    rehearsed = next((l for l in lines if l.get("phase", "").startswith("rehearsed_on_a_cpu")), {})
    for name in ("dsa.selection_step_share.sparsedoc", "model.decode_step_ms.sparsedoc",
                 "model.decode_hbm_share.sparsedoc"):
        assert name not in metrics and name not in rehearsed
    dsa_select_step_share.kept_operations.cache_clear()
    assert dsa_select_step_share.kept_operations(CELL, ("^%?sort",), 0.0) == {}
