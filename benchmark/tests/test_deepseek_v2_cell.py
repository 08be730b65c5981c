"""The DeepSeek-V2 configuration's files, byte count, readers and traffic
(PR 43), in ``test_minicpm_sala_cell.py``'s form: what ``test_benchmark.py``
checks of the other cells, for the files this cell adds.

    python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib
import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import bytes_deepseek_v2, checkpoint  # noqa: E402
from benchmark.generators import closed_loop_primed  # noqa: E402
from benchmark.layer_metrics.readers import (decode_hbm_share_deepseek_v2,  # noqa: E402
                                             decode_step_ms_named, mla_attn_roofline_share)

CELL, CONFIG = "deepseek-v2-ep8-d5.longdoc", "deepseek-v2-ep8-d5"
ATTENTION, NORMS = 149_225_472, 12_288
DENSE_MLP, EXPERT_FFN, EMBED_AND_HEAD = 188_743_680, 519_864_320, 131_072_000
PARAMETERS = 3_145_466_880


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


def test_every_published_number_is_kept_but_the_three_reduced(config):
    """Against the source's values, written out here: no width is touched."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "model_type": "deepseek_v2",
        "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 2,
        "norm_topk_prob": False, "num_attention_heads": 128, "num_experts_per_tok": 6,
        "num_key_value_heads": 128, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 16, "scoring_func": "softmax", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 3, "topk_method": "group_limited_greedy",
        "v_head_dim": 128,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                         "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                         "type": "yarn"}}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) \
        == (5, 20, 12800)
    assert config["reduced_from"] == {"num_hidden_layers": 60, "n_routed_experts": 160,
                                      "vocab_size": 102400}
    assert config["expert_share"] == {"published": 160, "first": 0}
    # the floors: four layers after the dense one, a whole group of 8 or more experts,
    # an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] == 160 // config["n_group"] >= 8
    assert config["vocab_size"] * 8 == 102400
    entry = next(c for c in load(ROOT, "BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                                     "vocab_size"]
    assert entry["source"] == config["source"]
    assert "one of 8 v5e chips" in config["deployment"] and "group 0" in config["deployment"]
    assert "pipeline stages" in config["deployment"] and "0-12799" in config["deployment"]
    assert {k[0] for k in config["assumed"]} >= set("abcdefghi")
    assert config["serve_args"] == ["--continuous-batch", "--max-seq-len", "32768", "--max-slots",
                                    "32", "--prefill-chunk", "2048", "--exit-with-parent"]
    tiny = dict(config, **config["rehearse"])
    assert tiny["n_routed_experts"] * tiny["n_group"] // 2 == tiny["expert_share"]["published"]


def test_the_checkpoints_bytes_are_the_tables(config):
    """ISSUE 43's table: attention 149,225,472 a layer, the dense MLP
    188,743,680, an expert layer's FFN 519,864,320 (router at 160, the shared
    pair, 20 experts), 12,800 rows of embedding and of head: 3,145,466,880
    parameters, 6,290,933,760 bytes in bf16."""
    shards = checkpoint.family_module("deepseek_v2").shards(config)
    per_shard = [sum(checkpoint.nbytes([[t]]) for t in shard) // 2 for shard in shards]
    assert per_shard[1] == ATTENTION + NORMS + DENSE_MLP == 337_981_440
    assert per_shard[2] == per_shard[5] == ATTENTION + NORMS + EXPERT_FFN == 669_102_080
    assert per_shard[0] + per_shard[-1] == EMBED_AND_HEAD + 5120
    b = config["bytes_predicted"]
    assert sum(per_shard) == PARAMETERS == b["parameters"]
    assert checkpoint.nbytes(shards) == 6_290_933_760 == b["weights_bf16"]
    assert (b["attention_parameters_per_layer"], b["dense_mlp_parameters_layer_0"],
            b["expert_layer_ffn_parameters_router_shared_20_experts"]) \
        == (ATTENTION, DENSE_MLP, EXPERT_FFN)
    names = dict(t for shard in shards for t in shard)
    assert names["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"] == (576, 5120)
    assert names["model.layers.0.self_attn.kv_b_proj.weight"] == (32768, 512)
    assert names["model.layers.4.self_attn.q_b_proj.weight"] == (24576, 1536)
    assert names["model.layers.1.mlp.gate.weight"] == (160, 5120)  # the router's published width
    assert names["model.layers.1.mlp.shared_experts.gate_proj.weight"] == (3072, 5120)
    assert names["model.layers.1.mlp.experts.19.down_proj.weight"] == (5120, 1536)
    assert "model.layers.1.mlp.experts.20.down_proj.weight" not in names
    assert names["model.layers.0.mlp.gate_proj.weight"] == (12288, 5120)
    assert "model.layers.0.mlp.gate.weight" not in names and "model.layers.5.input_layernorm.weight" not in names
    assert [n for n, _ in shards[-1]] == ["model.norm.weight", "lm_head.weight"]
    # the cache: one line a position a layer, 576 values padded to five lane tiles
    assert (b["latent_line_values"], b["latent_line_lanes_cached"]) == (576, 640)
    assert b["latent_cache_32_slots_x_32768_positions_x_5_layers"] == 32 * 32768 * 640 * 2 * 5
    assert b["latent_cache_if_unpadded_576"] == 6_039_797_760
    assert b["sum"] == b["weights_bf16"] + b["latent_cache_32_slots_x_32768_positions_x_5_layers"] \
        == 13_001_820_160
    assert b["per_head_keys_and_values_bytes_per_position_per_layer"] == 81_920  # 71 x a line


def test_the_program_reads_the_same_shapes_as_the_checkpoint_layout(config):
    """The family's own table of tensors (models/deepseek_v2.param_shapes, the
    experts folded) against ``checkpoints/deepseek_v2.py``'s per-expert names."""
    from modelx_tpu.models import deepseek_v2

    hf = {k: v for k, v in config.items() if k not in ("rehearse", "assumed", "reduced_from")}
    cfg = deepseek_v2.config_from_hf(hf)
    theirs = deepseek_v2.param_shapes(cfg)
    mine = dict(t for shard in checkpoint.family_module("deepseek_v2").shards(config) for t in shard)
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
    assert (cfg.held, cfg.groups, cfg.line_width) == ((0, 20), (8, 3), 640)
    assert cfg.softmax_scale == pytest.approx(0.1147, abs=1e-4)


# -- the traffic -----------------------------------------------------------------


def test_the_cells_traffic_is_the_issues_and_no_request_would_be_refused(config):
    traffic = load(BENCH, "traffic", "longdoc.json")
    want = {"generator": "closed_loop_primed", "clients": 32, "requests_per_client": 3,
            "prime": {"prompt_tokens": 48, "new_tokens": 16},
            "prompt": {"dist": "fixed", "value": 16384},
            "output": {"dist": "uniform", "min": 12288, "max": 16000}, "overrun": 8,
            "shape_seed": 1, "warm_group_sizes": [2], "trace_seconds": 8,
            "probe": {"prompt_tokens": 8240, "new_tokens": 16}}
    assert {k: traffic[k] for k in want} == want
    # ISSUE 43's rule: lead_in_s a multiple of 5 s, 200 s at most; stagger_s =
    # (lead_in_s - 25) / 32 rounded down to 0.5 s
    assert traffic["lead_in_s"] % 5 == 0 and traffic["lead_in_s"] <= 200
    assert traffic["stagger_s"] == math.floor((traffic["lead_in_s"] - 25) / 32 * 2) / 2
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
            assert 0 < min(r["prompt"]) and max(r["prompt"]) < config["vocab_size"] == 12800
        sizes.append(sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs))
    assert sizes[0] == sizes[1]  # every seed offers the same sizes
    assert (traffic["lead_in_s"], traffic["stagger_s"]) == (130.0, 3.0)  # as measured, PR 43
    # at the 15.5 ms a step the window's steps take at best (15.85 measured, PR 43; the
    # lead-in's are slower, pieces land between them) the shortest long request of the
    # client that starts first outlasts lead-in + window
    assert 12288 * 0.0155 > traffic["lead_in_s"] + 45


def test_every_name_the_cell_adds_has_its_files():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc", 1)
    assert load(BENCH, "workloads", CELL + ".json")["config"] == CONFIG
    # an entry is a reading and the cells that report it are its ``workloads``: this
    # cell's entries are those that list it, under a name of its own or one it shares
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # at least, not exactly: a later PR may add a metric to this cell
    assert len(mine) >= 14
    assert {m["name"] for m in mine} >= {
        "model.decode_step_ms.longdoc", "model.decode_hbm_share.longdoc",
        "mla.attn_roofline_share.longdoc", "mla.kv_read_share.longdoc",
        "mla.absorbed_share.longdoc", "moe.held_hit_share.longdoc",
        "moe.held_assignment_share.longdoc", "latent.cache_gb.longdoc",
        "device.hbm_peak_gb", "device.idle_share", "engine.pad_fraction",
        "engine.wait_ms.longdoc", "engine.fill_pieces.longdoc", "cache.store_hit_share.longdoc"}
    for m in mine:
        reader, spec = reader_of(m["name"])
        assert reader.read({}, spec) is None  # a program without the source: nothing, no raise
        assert m["moves"] == ("setup_s" if m["name"].startswith("cache.") else "tokens_per_s")
    reported = [m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]]
    assert reported == ["tokens_per_s", "setup_s"]
    # "in", not "last": a later configuration's cell is appended after this one
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]


# -- the byte count and the readers, by hand -------------------------------------


def test_decode_step_bytes_match_the_reckoning_by_hand(config):
    """ISSUE 43: the weights a step reads (all but the embedding, the norms
    and the held experts no row hits) and, at 32 rows x 20 k positions, 737 MB
    of latent lines; a layer's latent attention 178 GFLOP on 147 MB: 242
    FLOP/B against the v5e's ridge of 240."""
    need = bytes_deepseek_v2.decode_step_bytes(config, live_rows=32, mean_context=20000.0)
    assert need["attention"] == 2 * 5 * ATTENTION and need["dense_mlp"] == 2 * DENSE_MLP
    assert need["router"] == 2 * 4 * 160 * 5120 and need["shared_experts"] == 2 * 4 * 3 * 5120 * 3072
    hit = 20 * (1 - (1 - 6 / 160) ** 32)
    assert need["experts_hit_per_layer"] == pytest.approx(hit) and 14.0 < hit < 14.2
    assert need["experts"] == pytest.approx(2 * 4 * hit * 3 * 5120 * 1536)
    assert need["head"] == 2 * 12800 * 5120
    assert need["latent_lines"] == 5 * 32 * 20000 * 576 * 2 == 3_686_400_000
    assert need["total"] == pytest.approx(sum(v for k, v in need.items()
                                              if k not in ("total", "experts_hit_per_layer")))
    every = bytes_deepseek_v2.decode_step_bytes(config, live_rows=1e9, mean_context=0.0)
    assert every["total"] == pytest.approx(2 * (PARAMETERS - 12800 * 5120 - 5 * NORMS - 5120))
    one = bytes_deepseek_v2.latent_attention_step(config, live_rows=32, mean_context=20000.0)
    assert one["bytes"] == 32 * 20000 * 1152 == 737_280_000
    assert one["flops"] == 32 * 20000 * 2 * 128 * (576 + 512) == pytest.approx(178.3e9, rel=1e-3)
    assert one["flops"] / one["bytes"] == pytest.approx(241.8, abs=0.1)
    assert 197e12 / 819e9 == pytest.approx(240.5, abs=0.1)


def hand_made_sources(config):
    """Chunk programs of depth 4 on a hand-made trace: 20 runs are 640 steps
    in 9.6 s, 15 ms a step. Over the traced span 32 live rows, five latent
    layers at a mean context of 20,000, whole blocks of 1,024 read (20,480);
    the five absorbed kernels kept among the operations at 1.2 ms a step."""
    row_steps = 640 * 32 * 5

    def dump(chunks, scale):
        return {"default": {"continuous": {
            "chunks": chunks, "dispatches": chunks // 4, "decode_rows": 32 * chunks * 8,
            "decode_pad_rows": 0,
            "phase_s": {"wait_tokens": 0.4 * chunks, "firsts_wait": 0.0},
            "fill": {"pieces": 288, "tokens": 32 * 16384 + 32 * 48},
            "kv": {"bytes_latent": 6_710_886_400, "bytes_full": 0},
            "mla": {"positions_read": 7 + scale * row_steps * 20480,
                    "positions_cached": 5 + scale * row_steps * 20000,
                    "steps_absorbed": 3 + scale * row_steps, "steps_all": 3 + scale * row_steps,
                    "layers": 5, "heads": 128, "kv_lora_rank": 512, "rope_dim": 64},
            "moe": {"assignments": scale * 640 * 32 * 4 * 6, "assignments_held": scale * 640 * 96,
                    "experts_hit": scale * 640 * 4 * 14, "held_experts": 20, "sparse_layers": 4,
                    "published_experts": 160}}},
            "compile_cache": {"store_hits": 9, "store_misses": 0},
            "device": {"hbm_peak_bytes": 14_200_000_000}}

    ops = [[f"latent_decode_attention.{50 + i} bf16[32,128,512]", 0.0012 * 640] for i in range(5)]
    ops += [["fusion.9 bf16[20,32,1536]", 2.0], ["custom-call.77 f32[8,128]", 0.5]]
    return {"trace": {"window_s": 9.7, "idle_share": 0.001, "device_ops": ops, "modules": {
                "jit__chunk_impl_d4": {"seconds": 9.6, "count": 20}}},
            "trace_span": {"metrics_before": dump(800, 0), "metrics_after": dump(880, 1),
                           "seconds": 9.8},
            "metrics_before": dump(0, 0), "metrics_after": dump(80, 1),
            "config": config, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "max_slots": 32, "model": "default"}


def test_the_new_readers_on_a_hand_made_trace(config):
    sources = hand_made_sources(config)
    reader, spec = reader_of("model.decode_step_ms.longdoc")
    assert reader is decode_step_ms_named and reader.read(sources, spec) == pytest.approx(15.0)
    need = bytes_deepseek_v2.decode_step_bytes(config, live_rows=32, mean_context=20000.0)
    reader, spec = reader_of("model.decode_hbm_share.longdoc")
    assert reader is decode_hbm_share_deepseek_v2
    share = reader.read(sources, spec)
    assert share == pytest.approx(need["total"] / 819e9 / 0.015) and 0.6 < share < 0.8
    reader, spec = reader_of("mla.attn_roofline_share.longdoc")
    assert reader is mla_attn_roofline_share
    one = bytes_deepseek_v2.latent_attention_step(config, 32, 20000.0)
    roof = max(one["flops"] / 197e12, one["bytes"] / 819e9)
    assert roof == pytest.approx(0.905e-3, rel=1e-2)  # the issue's 0.90 ms a layer, by either roof
    assert reader.read(sources, spec) == pytest.approx(roof / 0.0012) and roof / 0.0012 < 1.0
    want = {"mla.kv_read_share.longdoc": 20480 / 20000, "mla.absorbed_share.longdoc": 1.0,
            "moe.held_hit_share.longdoc": 14 / 20, "moe.held_assignment_share.longdoc": 0.125,
            "latent.cache_gb.longdoc": 6.7108864, "engine.fill_pieces.longdoc": 0.0,
            "engine.pad_fraction": 0.0, "engine.wait_ms.longdoc": 0.4 * 80 / 20 * 1e3,
            "device.idle_share": 0.001, "device.hbm_peak_gb": 14.2,
            "cache.store_hit_share.longdoc": 1.0}
    for name, value in want.items():
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) == pytest.approx(value), name
    # four of the five kernels kept: part of the time is out of sight, no share is given
    sources["trace"]["device_ops"] = sources["trace"]["device_ops"][1:]
    reader, spec = reader_of("mla.attn_roofline_share.longdoc")
    assert reader.read(sources, spec) is None


def test_a_program_without_the_counters_gives_nothing(config):
    """The parent commit: no ``mla`` block, no ``kv.bytes_latent``."""
    sources = hand_made_sources(config)
    dumps = [sources["metrics_before"], sources["metrics_after"],
             sources["trace_span"]["metrics_before"], sources["trace_span"]["metrics_after"]]
    for dump in dumps:
        engine = dump["default"]["continuous"]
        del engine["mla"], engine["moe"]
        engine["kv"] = {"bytes_full": 1}
    for name in ("model.decode_hbm_share.longdoc", "mla.attn_roofline_share.longdoc",
                 "mla.kv_read_share.longdoc", "mla.absorbed_share.longdoc",
                 "moe.held_hit_share.longdoc", "moe.held_assignment_share.longdoc",
                 "latent.cache_gb.longdoc"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None, name
    sources = hand_made_sources(config)
    del sources["trace_span"]  # an untraced run
    for name in ("model.decode_hbm_share.longdoc", "mla.attn_roofline_share.longdoc"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None


def test_the_benchmarks_reference_is_the_programs_copy():
    with open(os.path.join(BENCH, "references", "deepseek_v2.py")) as f:
        mine = f.read()
    with open(os.path.join(ROOT, "modelx_tpu", "models", "deepseek_v2_reference.py")) as f:
        assert f.read() == mine
    assert "import modelx_tpu" not in mine and "from modelx_tpu" not in mine
    assert 'default_matmul_precision("highest")' in mine and "pallas" not in mine


@pytest.mark.skipif(os.environ.get("BENCH_REHEARSE") != "1",
                    reason="a minute: BENCH_REHEARSE=1 (tests/test_deepseek_v2_served.py rehearses the cell in tier 1)")
def test_rehearse_of_the_new_cell_ends():
    out = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--rehearse"],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0 and line["attempted"] > 0
