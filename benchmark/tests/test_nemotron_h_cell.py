"""The Nemotron-H configuration's files, byte count, readers and traffic
(PR 46), in ``test_deepseek_v2_cell.py``'s form: what ``test_benchmark.py``
checks of the other cells, for the files this cell adds.

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

from benchmark import bytes_nemotron_h, checkpoint  # noqa: E402
from benchmark.generators import closed_loop  # noqa: E402
from benchmark.layer_metrics.readers import (decode_hbm_share_nemotron_h,  # noqa: E402
                                             decode_step_ms_named, decode_step_ms_whole_runs)

CELL, CONFIG = "nemotron-3-super-ep4-d11.agent", "nemotron-3-super-ep4-d11"
# ISSUE 46's table
MAMBA_LAYER, EXPERT_LAYER, ATTENTION_LAYER = 109_640_064, 759_173_632, 35_655_680
EMBED_AND_HEAD, PARAMETERS = 268_435_456, 4_648_163_712
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEM*EMEMEMEME")


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
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 4096, "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 2688,
        "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E", "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 22, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["hybrid_override_pattern"],
            config["n_routed_experts"], config["vocab_size"]) == (11, "MEMEMEM*EME", 128, 32768)
    assert config["reduced_from"] == {"num_hidden_layers": 88, "n_routed_experts": 512,
                                      "hybrid_override_pattern": PUBLISHED_PATTERN,
                                      "vocab_size": 131072}
    assert config["expert_share"] == {"published": 512, "first": 0}
    # a prefix of the published pattern that is one whole period of it, in its ratio
    assert PUBLISHED_PATTERN.startswith(config["hybrid_override_pattern"])
    assert len(PUBLISHED_PATTERN) == 88
    assert [PUBLISHED_PATTERN.count(k) for k in "ME*"] == [40, 40, 8]
    assert [config["hybrid_override_pattern"].count(k) for k in "ME*"] == [5, 5, 1]
    # the floors: at least four layers after a period's start, 8 or more experts, an
    # eighth of the vocabulary
    assert config["n_routed_experts"] * 4 == 512 and config["vocab_size"] * 4 == 131072
    entry = next(c for c in load(ROOT, "BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    assert "one of 4 v5e chips that share each layer" in config["deployment"]
    assert "pipeline stages" in config["deployment"] and "0-32767" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for word in ("router_input", "no_rotary", "prediction_layer", "dt_limits", "ties"):
        assert word in assumed
    assert config["serve_args"] == ["--continuous-batch", "--max-seq-len", "4096", "--max-slots",
                                    "64", "--exit-with-parent"]
    assert 0 < config["min_argmax_agreement"] < 0.9 and config["min_argmax_agreement_why"]
    tiny = dict(config, **config["rehearse"])
    assert tiny["n_routed_experts"] * 2 == tiny["expert_share"]["published"]
    assert len(tiny["hybrid_override_pattern"]) == tiny["num_hidden_layers"]


def test_the_checkpoints_bytes_are_the_tables(config):
    """ISSUE 46's table: a Mamba layer 109,640,064 parameters, an expert layer
    759,173,632 (128 experts of 5,505,024, the shared one 44,040,192, the
    latent projections 8,388,608, the router 2,097,152, its bias, the norm),
    the attention layer 35,655,680, 32,768 rows of embedding and of head:
    4,648,163,712 parameters, 9.30 GB in bf16."""
    shards = checkpoint.family_module("nemotron_h").shards(config)
    per_shard = [sum(checkpoint.nbytes([[t]]) for t in shard) // 2 for shard in shards]
    assert len(shards) == 13
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        assert per_shard[1 + i] == {"M": MAMBA_LAYER, "E": EXPERT_LAYER, "*": ATTENTION_LAYER}[kind]
    assert EXPERT_LAYER == 128 * 5_505_024 + 44_040_192 + 8_388_608 + 2_097_152 + 512 + 4096
    assert per_shard[0] + per_shard[-1] == EMBED_AND_HEAD + 4096
    b = config["bytes_predicted"]
    assert sum(per_shard) == PARAMETERS == b["parameters"]
    assert checkpoint.nbytes(shards) == 9_296_327_424 == b["weights_bf16"]
    assert (b["mamba_layer_parameters"], b["attention_layer_parameters"],
            b["expert_layer_parameters_128_experts_shared_latent_router"]) \
        == (MAMBA_LAYER, ATTENTION_LAYER, EXPERT_LAYER)
    names = dict(t for shard in shards for t in shard)
    assert names["backbone.layers.0.mixer.in_proj.weight"] == (8192 + 10240 + 128, 4096)
    assert names["backbone.layers.0.mixer.conv1d.weight"] == (10240, 1, 4)
    assert names["backbone.layers.0.mixer.A_log"] == (128,)
    assert names["backbone.layers.0.mixer.norm.weight"] == (8192,)
    assert names["backbone.layers.1.mixer.gate.weight"] == (512, 4096)  # the published width
    assert names["backbone.layers.1.mixer.fc1_latent_proj.weight"] == (1024, 4096)
    assert names["backbone.layers.1.mixer.experts.127.up_proj.weight"] == (2688, 1024)
    assert names["backbone.layers.1.mixer.experts.0.down_proj.weight"] == (1024, 2688)
    assert "backbone.layers.1.mixer.experts.128.up_proj.weight" not in names
    assert names["backbone.layers.1.mixer.shared_experts.up_proj.weight"] == (5376, 4096)
    assert names["backbone.layers.7.mixer.k_proj.weight"] == (256, 4096)
    assert "backbone.layers.11.norm.weight" not in names
    assert [n for n, _ in shards[-1]] == ["backbone.norm_f.weight", "lm_head.weight"]
    # the cache: a float32 state and a three-position tail a slot a Mamba layer, keys and
    # values of one layer
    assert b["state_bytes_per_slot_per_mamba_layer_float32"] == 128 * 64 * 128 * 4
    assert b["states_64_slots_x_5_layers"] == 5 * 64 * 4_194_304 == 1_342_177_280
    assert b["conv_tails_64_slots_x_5_layers_bf16"] == 5 * 64 * 3 * 10240 * 2
    assert b["keys_and_values_64_slots_x_4096_positions_x_1_layer"] == 2 * 64 * 4096 * 256 * 2
    assert b["sum"] == (b["weights_bf16"] + b["states_64_slots_x_5_layers"]
                        + b["conv_tails_64_slots_x_5_layers_bf16"]
                        + b["keys_and_values_64_slots_x_4096_positions_x_1_layer"]) == 10_926_600_960
    assert 0.6 < b["sum"] / 16e9 < 0.7


def test_the_program_reads_the_same_shapes_as_the_checkpoint_layout(config):
    """The family's own table of tensors (models/nemotron_h.param_shapes, the
    experts folded) against ``checkpoints/nemotron_h.py``'s per-expert names."""
    from modelx_tpu.models import nemotron_h

    for cut in (config, dict(config, **config["rehearse"])):
        hf = {k: v for k, v in cut.items() if k not in ("rehearse", "assumed", "reduced_from")}
        cfg = nemotron_h.config_from_hf(hf)
        theirs = nemotron_h.param_shapes(cfg)
        mine = dict(t for shard in checkpoint.family_module("nemotron_h").shards(cut) for t in shard)
        folded = {}
        for name, shape in mine.items():
            if ".mixer.experts." in name:
                head, rest = name.split(".mixer.experts.")
                _, tail = rest.split(".", 1)
                key = f"{head}.mixer.experts.{tail}"
                folded[key] = (folded.get(key, (0,))[0] + 1, *shape)
            else:
                folded[name] = shape
        assert theirs == folded
    assert cfg.held == (0, 8)


# -- the traffic -----------------------------------------------------------------


def test_the_cells_traffic_is_the_issues_and_no_request_would_be_refused(config):
    traffic = load(BENCH, "traffic", "agent.json")
    want = {"generator": "closed_loop", "clients": 64, "requests_per_client": 6,
            "prompt": {"dist": "uniform", "min": 128, "max": 256},
            "output": {"dist": "uniform", "min": 1024, "max": 3072}, "overrun": 8,
            "shape_seed": 1, "lead_in_s": 12.0, "stagger_s": 0.15, "warm_group_sizes": [2, 4],
            "trace_seconds": 8, "probe": {"prompt_tokens": 48, "new_tokens": 16}}
    assert {k: traffic[k] for k in want} == want
    slots = int(config["serve_args"][config["serve_args"].index("--max-slots") + 1])
    sizes = []
    for seed in (0, 2**31 + 5):
        sched = closed_loop.schedule(seed, traffic, config["vocab_size"], 45.0, 4096)
        assert sched["mode"] == "closed" and len(sched["clients"]) == 64 == slots
        reqs = [r for c in sched["clients"] for r in c]
        assert len(reqs) == 64 * 6
        for r in reqs:
            pad = -(-len(r["prompt"]) // 16) * 16
            assert 128 <= len(r["prompt"]) <= 256 and 1024 <= r["max_new_tokens"] <= 3072
            assert pad + r["max_new_tokens"] + 8 <= 4096
            assert 0 < min(r["prompt"]) and max(r["prompt"]) < config["vocab_size"] == 32768
        sizes.append(sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs))
        # nine admit buckets, 128 .. 256: four fewer than .reason's 64 .. 256
        assert {-(-len(r["prompt"]) // 16) * 16 for r in reqs} == set(range(128, 257, 16))
    assert sizes[0] == sizes[1]  # every seed offers the same sizes


def test_every_name_the_cell_adds_has_its_files():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "agent", 1)
    assert load(BENCH, "workloads", CELL + ".json")["config"] == CONFIG
    # an entry is a reading and the cells that report it are its ``workloads``: this
    # cell's entries are those that list it, under a name of its own or one it shares
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    # at least, not exactly: a later PR may add a metric to this cell
    assert len(mine) >= 13
    assert all(m["name"].endswith(".agent") == (m["workloads"] == [CELL]) for m in mine)
    assert {m["name"] for m in mine} >= {
        "model.decode_step_ms.agent", "model.decode_hbm_share.agent", "ssm.state_gb.agent",
        "ssm.live_share.agent", "moe.held_hit_share.agent", "moe.held_assignment_share.agent",
        "moe.read_hit_share.agent", "engine.pad_fraction.agent", "engine.wait_ms.agent",
        "engine.boundary_host_ms.agent", "device.idle_share", "device.hbm_peak_gb",
        "cache.store_hit_share.agent"}
    for m in mine:
        reader, spec = reader_of(m["name"])
        assert reader.read({}, spec) is None  # a program without the source: nothing, no raise
        assert m["moves"] == ("setup_s" if m["name"].startswith("cache.") else "tokens_per_s")
    reported = [m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]]
    assert reported == ["tokens_per_s", "setup_s"]
    # "in", not "the last": a later PR appends its own cell after this one
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]


# -- the byte count and the readers, by hand -------------------------------------


def test_decode_step_bytes_match_the_reckoning_by_hand(config):
    """ISSUE 46: a step at 64 rows reads about 11.4 GB — the hit experts' 6.6
    GB (about 120 of 128 held), the states read and written 2.7 GB, Mamba's
    weights 1.1 GB, the keys and values 0.13 GB at a context of 2,000."""
    need = bytes_nemotron_h.decode_step_bytes(config, live_rows=64, mean_context=2000.0)
    hit = 128 * (1 - (1 - 22 / 512) ** 64)
    assert need["experts_read_per_layer"] == pytest.approx(hit) and 120.0 < hit < 121.0
    assert need["experts"] == pytest.approx(5 * hit * 5_505_024 * 2) and 6.5e9 < need["experts"] < 6.7e9
    assert need["state"] == 5 * 64 * 4_194_304 * 2 == 2_684_354_560
    assert need["conv_tail"] == 5 * 64 * 3 * 10240 * 2 * 2
    assert need["mamba_weights"] == 5 * (MAMBA_LAYER - 4096) * 2 and 1.0e9 < need["mamba_weights"] < 1.2e9
    assert need["router"] == 5 * (512 * 4096 + 512) * 2
    assert need["latent_projections"] == 5 * 8_388_608 * 2 and need["shared_expert"] == 5 * 44_040_192 * 2
    assert need["attention_weights"] == (ATTENTION_LAYER - 4096) * 2
    assert need["kv"] == 2 * 256 * 64 * 2000 * 2 == 131_072_000
    assert need["head"] == 32768 * 4096 * 2 and need["dense_mlp"] == 0
    assert need["total"] == pytest.approx(sum(v for k, v in need.items()
                                              if k not in ("total", "experts_read_per_layer")))
    assert 11.2e9 < need["total"] < 11.6e9
    assert 0.56 < (need["experts"] + need["router"] + need["latent_projections"]
                   + need["shared_expert"]) / need["total"] < 0.68
    assert 0.22 < (need["state"] + need["conv_tail"]) / need["total"] < 0.26
    # the counter's reading where the einsums read every held expert
    every = bytes_nemotron_h.decode_step_bytes(config, 64, 2000.0, experts_read=128)
    assert every["experts"] == 5 * 128 * 5_505_024 * 2
    # every weight but the embedding and the norms, no row live
    idle = bytes_nemotron_h.decode_step_bytes(config, 0.0, 0.0, experts_read=128)
    assert idle["total"] == 2 * (PARAMETERS - 32768 * 4096 - 11 * 4096 - 4096)


def hand_made_sources(config):
    """Over the traced span the program counted 600 decode steps of 64 slots,
    57.6 live rows a step at a mean context of 2,000, every held expert read.
    The trace watched 8.0 s: twelve runs of the depth-4 chunk program, the
    first and the last cut at the window's edges to half and a quarter of
    their 32 steps, and 29 whole runs of the depth-1 program, 14.85 ms a
    step."""
    steps = 600

    def dump(scale, chunks):
        return {"default": {"continuous": {
            "chunks": chunks, "dispatches": chunks // 4, "decode_rows": 64 * chunks * 8,
            "decode_pad_rows": int(6.4 * chunks * 8), "boundary_host_ms_p50": 61.0,
            "phase_s": {"wait_tokens": 0.1 * chunks, "firsts_wait": 0.0},
            "kv": {"bytes_state": 1_361_838_080, "bytes_full": 268_435_456},
            "ssm": {"steps_live": 11 + scale * int(57.6 * steps), "steps_all": 64 + scale * 64 * steps,
                    "positions_live": 7 + scale * int(57.6 * steps) * 2000, "layers": 5},
            "moe": {"assignments": scale * steps * 64 * 5 * 22,
                    "assignments_held": scale * steps * 64 * 5 * 22 // 4,
                    "experts_hit": scale * steps * 5 * 120, "experts_read": scale * steps * 5 * 128,
                    "held_experts": 128, "sparse_layers": 5, "published_experts": 512}}},
            "compile_cache": {"store_hits": 30, "store_misses": 0},
            "device": {"hbm_peak_bytes": 11_200_000_000}}

    return {"trace": {"window_s": 8.0, "busy_s": 7.92, "idle_share": 0.01, "device_ops": [],
                      "modules": {"jit__chunk_impl_d4": {"seconds": 10.75 * 32 * 0.01485, "count": 12},
                                  "jit__chunk_impl_d1": {"seconds": 29 * 8 * 0.01485, "count": 29},
                                  "jit__admit_nosmall": {"seconds": 0.3, "count": 20}}},
            "trace_span": {"metrics_before": dump(0, 800), "metrics_after": dump(1, 875),
                           "seconds": 88.0},
            "metrics_before": dump(0, 0), "metrics_after": dump(1, 75),
            "config": config, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "max_slots": 64, "model": "default"}


def test_the_new_readers_on_a_hand_made_trace(config):
    sources = hand_made_sources(config)
    reader, spec = reader_of("model.decode_step_ms.agent")
    assert reader is decode_step_ms_whole_runs
    assert reader.read(sources, spec) == pytest.approx(14.85)  # the module with no cut run
    # all module events as whole runs: 12 x 32 + 29 x 8 = 616 steps where 576 ran, 13.9 ms
    named = decode_step_ms_named.read(sources, spec)
    assert named == pytest.approx(14.85 * 576 / 616)
    only_cut = {k: v for k, v in sources["trace"]["modules"].items() if "d1" not in k}
    assert reader.read({"trace": {"modules": only_cut}}, spec) == pytest.approx(14.85 * 10.75 / 12)
    need = bytes_nemotron_h.decode_step_bytes(config, live_rows=57.6, mean_context=2000.0,
                                             experts_read=128)
    reader, spec = reader_of("model.decode_hbm_share.agent")
    assert reader is decode_hbm_share_nemotron_h
    share = reader.read(sources, spec)
    assert share == pytest.approx(need["total"] / 819e9 / 0.01485, rel=1e-3) and 0.9 < share < 1.0
    want = {"ssm.state_gb.agent": 1.36183808, "ssm.live_share.agent": 0.9,
            "moe.held_hit_share.agent": 120 / 128, "moe.held_assignment_share.agent": 0.25,
            "moe.read_hit_share.agent": 120 / 128, "engine.pad_fraction.agent": 0.1,
            "engine.wait_ms.agent": 0.1 * 75 / (875 // 4 - 800 // 4) * 1e3,
            "engine.boundary_host_ms.agent": 61.0, "device.idle_share": 0.01,
            "device.hbm_peak_gb": 11.2, "cache.store_hit_share.agent": 1.0}
    for name, value in want.items():
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) == pytest.approx(value, rel=1e-3), name


def test_a_program_without_the_counters_gives_nothing(config):
    """The parent commit: no ``ssm`` block, no ``moe`` block, no state."""
    sources = hand_made_sources(config)
    dumps = [sources["metrics_before"], sources["metrics_after"],
             sources["trace_span"]["metrics_before"], sources["trace_span"]["metrics_after"]]
    for dump in dumps:
        engine = dump["default"]["continuous"]
        del engine["ssm"], engine["moe"]
        engine["kv"] = {"bytes_full": 1}
    for name in ("model.decode_hbm_share.agent",
                 "ssm.state_gb.agent", "ssm.live_share.agent", "moe.held_hit_share.agent",
                 "moe.held_assignment_share.agent", "moe.read_hit_share.agent"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None, name
    sources = hand_made_sources(config)
    del sources["trace_span"], sources["trace"]  # an untraced run
    for name in ("model.decode_step_ms.agent", "model.decode_hbm_share.agent",
                 "ssm.live_share.agent"):
        reader, spec = reader_of(name)
        assert reader.read(sources, spec) is None
    sources = hand_made_sources(config)
    sources["trace"] = {"window_s": 2.0, "busy_s": 0.0, "device_planes": 0}  # nothing ran
    reader, spec = reader_of("model.decode_step_ms.agent")
    assert reader.read(sources, spec) is None


def test_the_benchmarks_reference_is_the_programs_copy():
    with open(os.path.join(BENCH, "references", "nemotron_h.py")) as f:
        mine = f.read()
    with open(os.path.join(ROOT, "modelx_tpu", "models", "nemotron_h_reference.py")) as f:
        assert f.read() == mine
    assert "import modelx_tpu" not in mine and "from modelx_tpu" not in mine
    assert 'default_matmul_precision("highest")' in mine and "pallas" not in mine
    assert "jax.lax.scan(token" in mine  # the recurrence token by token, no chunked form


@pytest.mark.skipif(os.environ.get("BENCH_REHEARSE") != "1",
                    reason="a minute: BENCH_REHEARSE=1 (tests/test_nemotron_h_served.py rehearses the cell in tier 1)")
def test_rehearse_of_the_new_cell_ends():
    out = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--rehearse"],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0 and line["attempted"] > 0
