"""The per-layer table of ``BENCHMARK.json``: an entry is a reading, and the
cells that report it are its ``workloads`` (PR 57, README.md "One entry a
reading"). Parametrised over the table, so that each entry counts.

Tier-1 (``tests/``) pins some entries by their place or by a cell's name in a
rehearsed line; a ``benchmark`` PR may not edit ``tests/``, so those entries
keep a suffix and a copy of their spec until a PR of another kind frees them.
``PINNED_COPIES`` below is that list, and it may only shrink.
"""

import importlib
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    TABLE = json.load(_f)
PER_LAYER = TABLE["per_layer"]
CELLS = [w["name"] for w in TABLE["workloads"]]
SUFFIXES = {c.rsplit(".", 1)[1] for c in CELLS}
# files of the cell PERF.md section 7 keeps for later (`phi3-mini-4k.chat`): no entry yet
KEPT_FOR_LATER = {"device.idle_share.chat", "engine.boundary_host_ms.chat", "loadgen.lag_p90_ms"}
READERS = os.path.join(BENCH, "layer_metrics", "readers")
SPEC_NAMES = sorted(f[:-len(".json")] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
                    if f.endswith(".json"))

# groups of entries whose readings are one (equal spec, unit, better, source,
# layer, moves) and that tests/ holds apart: by index (test_engine_programs,
# test_decode_attention), or by a suffixed name in a cell's rehearsed line
# (tests/test_*_served.py). What each waits for is in PERF.md section 7.
PINNED_COPIES = [
    {"cache.store_hit_share.decode", "cache.store_hit_share.reason",
     "cache.store_hit_share.longctx", "cache.store_hit_share.longdoc",
     "cache.store_hit_share.agent", "cache.store_hit_share.sparsedoc"},
    {"engine.boundary_host_ms.decode", "engine.boundary_host_ms.reason",
     "engine.boundary_host_ms.agent"},
    {"engine.pad_fraction", "engine.pad_fraction.agent"},
    {"engine.wait_ms", "engine.wait_ms.longctx", "engine.wait_ms.longdoc", "engine.wait_ms.agent"},
    {"engine.fill_pieces.longctx", "engine.fill_pieces.longdoc", "engine.fill_pieces.sparsedoc"},
    {"model.decode_step_ms.reason", "model.decode_step_ms.longctx", "model.decode_step_ms.longdoc"},
    {"moe.held_hit_share.reason", "moe.held_hit_share.longdoc", "moe.held_hit_share.agent",
     "moe.held_hit_share.sparsedoc"},
    {"moe.held_assignment_share.reason", "moe.held_assignment_share.longdoc",
     "moe.held_assignment_share.agent", "moe.held_assignment_share.sparsedoc"},
    {"moe.read_hit_share.reason", "moe.read_hit_share.longdoc", "moe.read_hit_share.agent",
     "moe.read_hit_share.sparsedoc"},
    {"attn.kv_read_share.reason", "attn.kv_read_share.decode"},
    {"kv.write_kernel_share.reason", "kv.write_kernel_share.decode"},
    {"mla.kv_read_share.longdoc", "mla.kv_read_share.sparsedoc"},
    {"mla.absorbed_share.longdoc", "mla.absorbed_share.sparsedoc"},
    {"latent.cache_gb.longdoc", "latent.cache_gb.sparsedoc"},
    {"linear.state_gb.longctx", "ssm.state_gb.agent"},
]


def spec_of(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def reading(m: dict) -> str:
    """What makes two entries one reading: the spec and everything but the name
    and the cells."""
    return json.dumps([spec_of(m["name"]), m["unit"], m["better"], m["source"], m["layer"],
                       m["moves"]], sort_keys=True)


@pytest.mark.parametrize("m", PER_LAYER, ids=lambda m: m["name"])
def test_an_entry_has_a_spec_a_reader_that_imports_and_cells_that_exist(m):
    reader = importlib.import_module(
        f"benchmark.layer_metrics.readers.{spec_of(m['name'])['reader']}")
    assert callable(reader.read)
    assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
    # in the order the cells stand in ``workloads``, each once
    assert m["workloads"] == [c for c in CELLS if c in m["workloads"]]
    moved = next(e for e in TABLE["end_to_end"] if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))


@pytest.mark.parametrize("m", PER_LAYER, ids=lambda m: m["name"])
def test_an_entry_several_cells_report_carries_no_cells_suffix(m):
    """A shared reading is named for what it reads. The entries tier-1 pins
    (PR 54 appended its cell to thirteen entries of another cell's name) are
    the exception, and the list of them may only shrink."""
    suffix = m["name"].rsplit(".", 1)[-1]
    if len(m["workloads"]) > 1 and suffix in SUFFIXES:
        assert m["name"] in {"attn.kv_read_share.reason", "attn.ring_kernel_share.reason",
                             "kv.write_kernel_share.reason", "model.decode_step_ms.longdoc",
                             "moe.held_hit_share.longdoc", "moe.held_assignment_share.longdoc",
                             "moe.read_hit_share.longdoc", "engine.fill_pieces.longdoc",
                             "cache.store_hit_share.longdoc"}
    if suffix in SUFFIXES:
        assert any(c.endswith("." + suffix) for c in m["workloads"])  # its own cell is there


@pytest.mark.parametrize("name", SPEC_NAMES)
def test_a_spec_file_has_an_entry(name):
    assert name in {m["name"] for m in PER_LAYER} | KEPT_FOR_LATER


def readers_in_use() -> set:
    """Readers a spec file names, and those another reader imports from beside it."""
    used = {spec_of(name)["reader"] for name in SPEC_NAMES}
    for f in os.listdir(READERS):
        if f.endswith(".py"):
            with open(os.path.join(READERS, f)) as src:
                for line in re.findall(r"^from \. import (.+)$", src.read(), re.M):
                    used |= {x.strip() for x in line.split(",")}
    return used


IN_USE = readers_in_use()


@pytest.mark.parametrize("name", sorted(
    f[:-len(".py")] for f in os.listdir(READERS) if f.endswith(".py") and f != "__init__.py"))
def test_a_reader_is_named_by_a_spec_or_by_another_reader(name):
    assert name in IN_USE


def test_no_two_entries_are_one_reading_but_the_pinned_ones():
    groups: dict[str, set] = {}
    for m in PER_LAYER:
        groups.setdefault(reading(m), set()).add(m["name"])
    copies = sorted((g for g in groups.values() if len(g) > 1), key=sorted)
    assert copies == sorted(PINNED_COPIES, key=sorted)
    names = [m["name"] for m in PER_LAYER]
    assert len(set(names)) == len(names)


def test_the_table_leaves_room():
    """128 is the contract's most. Tier-1 pins an entry at index 104
    (tests/test_decode_attention.py), so 105 is the fewest this table can hold
    until that test finds its entry by name; at most 80 is the aim after it."""
    assert 105 <= len(PER_LAYER) <= 105 + 18
    gone = {"dsa.select_step_share.sparsedoc", "engine.init_s", "loader.fetch_busy_share",
            "loader.put_busy_share", "cache.retrieval_s_per_program",
            "cache.retrieval_s_per_program.decode", "device.idle_named_share.decode"}
    assert not gone & {m["name"] for m in PER_LAYER}


@pytest.mark.parametrize("name", ["engine.pad_fraction", "engine.pad_fraction.agent",
                                  "engine.wait_ms", "engine.wait_ms.longctx",
                                  "engine.wait_ms.longdoc", "engine.wait_ms.agent"])
def test_idle_rows_and_waits_are_read_over_the_traced_span_in_every_token_cell(name):
    spec = spec_of(name)
    assert (spec["reader"], spec["before"], spec["after"]) == (
        "metrics_path", "trace_span.metrics_before", "trace_span.metrics_after")
    reader = importlib.import_module("benchmark.layer_metrics.readers.metrics_path")
    dump = lambda pad, rows, wait, n: {"default": {"continuous": {  # noqa: E731
        "decode_pad_rows": pad, "decode_rows": rows, "dispatches": n,
        "phase_s": {"wait_tokens": wait, "firsts_wait": 0.0}}}}
    ramp = {"metrics_before": dump(0, 0, 0.0, 0), "metrics_after": dump(900, 9000, 9.0, 90)}
    span = {"metrics_before": dump(500, 4000, 4.0, 40), "metrics_after": dump(520, 5000, 5.0, 50)}
    got = reader.read({"model": "default", **ramp, "trace_span": span}, spec)
    assert got == pytest.approx(0.02 if "pad" in name else 100.0)  # the span's, not the ramp's 0.1
    assert reader.read({"model": "default", **ramp}, spec) is None  # an untraced run reads nothing


def test_every_token_cell_reports_the_shared_readings():
    by_name = {m["name"]: m for m in PER_LAYER}
    tokens = next(e for e in TABLE["end_to_end"] if e["name"] == "tokens_per_s")["workloads"]
    for name in ("device.idle_share", "device.hbm_peak_gb"):
        assert by_name[name]["workloads"] == tokens
    for stem in ("engine.pad_fraction", "engine.wait_ms", "cache.store_hit_share"):
        reported = [c for m in PER_LAYER if m["name"] == stem or m["name"].startswith(stem + ".")
                    for c in m["workloads"]]
        assert sorted(reported) == sorted(tokens), stem  # each token cell once
