"""The two decode-attention kernels (``ops.attention.decode_attention``, the
ragged one over a dense cache, and ``ring_decode_attention`` over a window
layer's ring) and the rule that picks them (``cached_attention``). The oracle
is ``attention_reference`` over the whole cache; the kernels run in pallas
interpret mode here, asked for by name. What interpret mode cannot see —
tiling, the cache read as it lies — is in tests/test_tpu_compile.py."""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.ops import attention as attn
from modelx_tpu.utils.trace import tracer

L, BLOCK, HKV, D = 64, 16, 2, 128
LENGTHS = {"one": 1, "block-1": BLOCK - 1, "block": BLOCK, "block+1": BLOCK + 1, "all": L}


def _qkv(rows, group, dtype=jnp.float32, d=D, hkv=HKV, cache_len=L, qlen=1):
    rng = np.random.RandomState(group * 10 + rows)
    q = jnp.asarray(rng.randn(rows, qlen, hkv * group, d), dtype)
    k, v = (jnp.asarray(rng.randn(rows, cache_len, hkv, d), dtype) for _ in range(2))
    return q, k, v


def reference(q, k, v, offsets, **kwargs):
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(attn.attention_reference(t(q), t(k), t(v), causal=True, q_offset=offsets, **kwargs))


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("group", [1, 4, 6, 9])
def test_the_kernel_gives_the_references_values(group, length):
    """Every group size the families have (MHA, Mixtral's 4, Laguna's 6 and
    9: 18 query heads pad to two row tiles), rows that end inside the first
    block, on a block's edge, one past it, and at the cache's end."""
    q, k, v = _qkv(3, group)
    lengths = jnp.full((3,), LENGTHS[length], jnp.int32)
    got = attn.decode_attention(q, k, v, lengths, block=BLOCK, interpret=True)
    np.testing.assert_allclose(got, reference(q, k, v, lengths - 1), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("group", [1, 4, 6, 9])
def test_a_ragged_batch_with_an_idle_row(group):
    """Rows at their own depths in one call, one of them an idle slot at
    offset 0, and a custom scale."""
    q, k, v = _qkv(5, group)
    offsets = jnp.asarray([0, 37, BLOCK - 1, 2 * BLOCK, L - 1], jnp.int32)
    got = attn.decode_attention(q, k, v, offsets + 1, 0.11, block=BLOCK, interpret=True)
    np.testing.assert_allclose(got, reference(q, k, v, offsets, scale=0.11),
                               rtol=2e-5, atol=2e-5)


def test_bf16_operands_keep_f32_statistics():
    q, k, v = _qkv(3, 6, jnp.bfloat16)
    offsets = jnp.asarray([3, 40, L - 1], jnp.int32)
    got = attn.decode_attention(q, k, v, offsets + 1, block=BLOCK, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               reference(q, k, v, offsets).astype(jnp.float32), atol=2e-2)


def test_a_length_past_the_cache_reads_the_whole_cache_and_no_further():
    q, k, v = _qkv(2, 4)
    got = attn.decode_attention(q, k, v, jnp.asarray([L + 9, 0]), block=BLOCK, interpret=True)
    want = reference(q, k, v, jnp.asarray([L - 1, 0]))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- the ring kernel ----------------------------------------------------------

RING, WINDOW = 48, 32  # a ring is its window plus one 16-token bucket
RING_OFFSETS = {
    "below_the_window": [3, 17, WINDOW - 2],
    "the_windows_edge": [WINDOW - 1, WINDOW, WINDOW + 1],
    "between_window_and_ring": [WINDOW + 5, RING - 2, RING - 1],
    "the_first_wrap": [RING, RING + 1, RING + WINDOW],
    "past_several_wraps": [3 * RING - 1, 5 * RING + 7, 1000],
    "rows_at_different_depths_and_an_idle_slot": [0, 9, RING - 1, RING, 4 * RING + 20],
}


def ring_reference(q, k, v, offsets, window=WINDOW, **kwargs):
    return reference(q, k, v, offsets, window=window,
                     key_positions=attn.ring_key_positions(offsets, k.shape[1]), **kwargs)


@pytest.mark.parametrize("offsets", RING_OFFSETS)
@pytest.mark.parametrize("group", [6, 9])
def test_the_ring_kernel_gives_the_references_values(group, offsets):
    """Laguna's two group sizes (9 under its window layers: 18 query heads pad
    to two row tiles), rings not yet full, full, and overwritten several times:
    the kernel's mask by age is the reference's by ``key_positions``."""
    offsets = jnp.asarray(RING_OFFSETS[offsets], jnp.int32)
    q, k, v = _qkv(len(offsets), group, cache_len=RING)
    got = attn.ring_decode_attention(q, k, v, offsets, WINDOW, interpret=True)
    np.testing.assert_allclose(got, ring_reference(q, k, v, offsets), rtol=2e-5, atol=2e-5)


def test_the_ring_kernel_in_bf16_with_a_scale_and_a_window_as_long_as_the_ring():
    q, k, v = _qkv(4, 9, jnp.bfloat16, cache_len=RING)
    offsets = jnp.asarray([0, 20, RING + 3, 7 * RING], jnp.int32)
    got = attn.ring_decode_attention(q, k, v, offsets, RING, 0.11, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = ring_reference(q, k, v, offsets, window=RING, scale=0.11)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=2e-2)


def test_an_index_is_as_old_as_its_distance_behind_the_querys_modulo_the_ring():
    """``ring_key_positions``: index r holds the newest position <= the
    query's congruent to r; negative = never written."""
    got = np.asarray(attn.ring_key_positions(jnp.asarray([0, 5, 11, 12, 30]), 12))
    for row, offset in zip(got, [0, 5, 11, 12, 30]):
        want = [max(p for p in range(offset, offset - 12, -1) if p % 12 == r) for r in range(12)]
        assert row.tolist() == want


@pytest.mark.parametrize("cache_len,kv_heads,block", [
    (4096, 8, 256), (2048, 8, 256), (1024, 8, 256), (384, 8, 128), (256, 8, 128),
    (128, 8, 64), (4096, 32, 64), (4096, 16, 128), (128, 2, 64), (96, 2, 32), (1, 2, 0)])
def test_the_block_divides_the_cache_at_least_twice(cache_len, kv_heads, block):
    assert attn.ragged_block(cache_len, kv_heads) == block


# -- who takes the kernel -----------------------------------------------------

ROWS, CACHE = 4, 512
OFFSETS = jax.ShapeDtypeStruct((ROWS,), jnp.int32)


def _shapes(d=128, hkv=8, group=4, qlen=1, cache_len=CACHE):
    q = jax.ShapeDtypeStruct((ROWS, qlen, hkv * group, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((ROWS, cache_len, hkv, d), jnp.bfloat16)
    return q, kv, kv


# what keeps attention_reference, on a TPU too: (shapes, offsets, keywords)
KEEPS_THE_REFERENCE = {
    "phi3_head_dim_96": (_shapes(d=96, hkv=32, group=1), OFFSETS, {}),
    "a_query_of_16": (_shapes(qlen=16), OFFSETS, {}),
    "a_softcap": (_shapes(), OFFSETS, {"logit_softcap": 30.0}),
    "a_window_over_a_dense_cache": (_shapes(), OFFSETS, {"window": 64}),
    "a_scalar_offset": (_shapes(), jax.ShapeDtypeStruct((), jnp.int32), {}),
    "two_kv_heads": (_shapes(hkv=2), OFFSETS, {}),
    "a_cache_of_one_block": (_shapes(cache_len=128), OFFSETS, {}),
    "a_ring_with_a_softcap": (_shapes(group=9, cache_len=528), OFFSETS,
                              {"window": 512, "ring": True, "logit_softcap": 30.0}),
    "a_ring_of_two_kv_heads": (_shapes(hkv=2, cache_len=528), OFFSETS,
                               {"window": 512, "ring": True}),
    "a_ring_of_heads_of_96": (_shapes(d=96, cache_len=528), OFFSETS,
                              {"window": 512, "ring": True}),
    "a_ring_at_a_scalar_offset": (_shapes(group=9, cache_len=528),
                                  jax.ShapeDtypeStruct((), jnp.int32),
                                  {"window": 512, "ring": True}),
    "a_ring_too_long_for_one_block": (_shapes(group=9, cache_len=2064), OFFSETS,
                                      {"window": 2048, "ring": True}),
}


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The rule asks for the backend; a test steers it, no option does."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("case", KEEPS_THE_REFERENCE)
def test_what_is_not_a_plain_decode_step_lowers_as_the_reference_did(on_a_tpu, case):
    (q, k, v), offsets, kwargs = KEEPS_THE_REFERENCE[case]

    def picked(q, k, v, off):
        return attn.cached_attention(q, k, v, off, **kwargs)

    def direct(q, k, v, off):
        told = {n: a for n, a in kwargs.items() if n != "ring"}
        if kwargs.get("ring"):  # what Laguna's own lines built before the rule took rings
            told["key_positions"] = attn.ring_key_positions(
                jnp.broadcast_to(off, k.shape[:1]), k.shape[1])
        return reference(q, k, v, off, **told)

    got = jax.jit(picked).lower(q, k, v, offsets).as_text()
    want = jax.jit(direct).lower(q, k, v, offsets).as_text()
    assert got.replace("jit_picked", "jit_direct") == want
    assert "custom_call" not in got and "pallas" not in got


@pytest.mark.parametrize("qlen,window", [(16, 512), (1, 0)])
def test_a_ring_takes_one_query_a_row_under_a_window_or_nothing(qlen, window):
    with pytest.raises(ValueError, match="a ring cache decodes one token a step"):
        jax.eval_shape(lambda q, k, v, off: attn.cached_attention(
            q, k, v, off, window=window, ring=True), *_shapes(qlen=qlen, cache_len=528), OFFSETS)


def test_a_windows_ring_takes_the_ring_kernel_on_one_tpu_device_only(on_a_tpu):
    """Laguna's window layers: 72 query heads over a ring of 528 positions of
    8 KV heads. The ring's calls are not the ragged kernel's: nothing enters
    ``ragged_calls`` (``attn.kv_read_share`` stays the full layers')."""
    q, k, v = _shapes(group=9, cache_len=528)
    take = lambda **kw: str(jax.make_jaxpr(lambda q, k, v, off: attn.cached_attention(
        q, k, v, off, window=512, ring=True, **kw))(q, k, v, OFFSETS))
    tracer().clear()
    with attn.ragged_calls() as calls:
        assert "ring_decode_attention" in take() and "ragged_decode_attention" not in take()
    assert calls == []
    assert "attention.ring[1x528]+gqa9" in tracer().summary("attention.")
    from modelx_tpu.parallel.mesh import make_mesh

    assert "pallas_call" in take(mesh=make_mesh("dp=1", devices=jax.devices()[:1]))
    if len(jax.devices()) > 1:
        assert "pallas_call" not in take(mesh=make_mesh("dp=2", devices=jax.devices()[:2]))


@pytest.mark.parametrize("length,told,block", [
    (4096, {}, 256), (528, {"ring": True}, 528), (528, {}, 0), (4096, {"impl": "ragged"}, 256),
    (4096, {"mesh": "dp=2"}, 0), (528, {"ring": True, "mesh": "dp=2"}, 0),
    (528, {"ring": True, "impl": "ragged+interpret", "mesh": "dp=2"}, 528)])
def test_the_rule_is_a_function_of_shapes_backend_and_mesh(on_a_tpu, length, told, block):
    """``decode_block``: what the engine's layout asks of its leaves without
    tracing. A dense leaf's block is ``ragged_block``'s, a ring's the ring."""
    from modelx_tpu.parallel.mesh import make_mesh

    told = dict(told)
    if "mesh" in told:
        if len(jax.devices()) < 2:
            pytest.skip("one device")
        told["mesh"] = make_mesh(told["mesh"], devices=jax.devices()[:2])
    assert attn.decode_block((64, length, 8, 128), 2, **told) == block


def test_a_rings_block_is_bounded_by_bytes_and_off_the_tpu_by_the_name():
    ring = lambda n, **kw: attn.decode_block((64, n, 8, 128), 2, ring=True, **kw)  # noqa: E731
    assert ring(528) == 0 and ring(528, impl="ragged+interpret") == 528  # the CPU
    import unittest.mock

    with unittest.mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert (ring(528), ring(1040), ring(2064)) == (528, 1040, 0)
        assert attn.decode_block((64, 528, 8, 128), 4, ring=True) == 528  # 4.3 MB in float32
        assert attn.decode_block((64, 1040, 8, 128), 4, ring=True) == 0


def test_a_plain_decode_step_takes_the_kernel_on_one_tpu_device_only(on_a_tpu):
    q, k, v = _shapes(group=6)
    take = lambda **kw: str(jax.make_jaxpr(
        lambda q, k, v, off: attn.cached_attention(q, k, v, off, **kw))(q, k, v, OFFSETS))
    tracer().clear()
    assert "pallas_call" in take() and "ragged_decode_attention" in take()
    assert "attention.ragged[1x512]+gqa6" in tracer().summary("attention.")
    from modelx_tpu.parallel.mesh import make_mesh

    assert "pallas_call" in take(mesh=make_mesh("dp=1", devices=jax.devices()[:1]))
    if len(jax.devices()) > 1:
        assert "pallas_call" not in take(mesh=make_mesh("dp=2", devices=jax.devices()[:2]))


def test_on_the_cpu_nothing_takes_the_kernel_unless_asked_by_name():
    q, k, v = _shapes()
    jaxpr = lambda impl: str(jax.make_jaxpr(lambda q, k, v, off: attn.cached_attention(
        q, k, v, off, impl=impl))(q, k, v, OFFSETS))
    assert "pallas_call" not in jaxpr("auto") and "pallas_call" not in jaxpr("flash+interpret")
    assert "pallas_call" in jaxpr("ragged+interpret")
    q, k, v = _shapes(group=9, cache_len=528)
    ring = lambda impl: str(jax.make_jaxpr(lambda q, k, v, off: attn.cached_attention(  # noqa: E731
        q, k, v, off, impl=impl, window=512, ring=True))(q, k, v, OFFSETS))
    assert "pallas_call" not in ring("auto") and "pallas_call" not in ring("flash+interpret")
    assert "ring_decode_attention" in ring("ragged+interpret")


def test_the_engine_counts_what_the_calls_blocks_cover():
    """``kv_positions``: ceil(length / block) * block a row and call, never
    more than the cache, beside what the caches hold."""
    lengths = jnp.asarray([1, 256, 257, 5000], jnp.int32)
    got = attn.kv_positions([(256, 4096), (128, 512)], lengths)
    assert got.tolist() == [256 + 256 + 512 + 4096 + 128 + 256 + 384 + 512, 4 * (4096 + 512)]
    with attn.ragged_calls() as outer:
        with attn.ragged_calls() as inner:
            pass
        assert getattr(attn._ragged_calls, "calls") is outer and inner == []


# -- the two per-layer metrics that read the counters -------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = {"attn.kv_read_share.reason": "laguna-s-2.1-ep2-d5.reason",
         "attn.kv_read_share.decode": "mixtral-8x7b-d4.decode"}


def read_metric(name, sources):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
    return reader.read(sources, spec)


@pytest.mark.parametrize("name", CELLS)
def test_the_read_share_is_the_counters_growth_over_the_window(name):
    dump = lambda read, cached: {"default": {"continuous": {  # noqa: E731
        "attn_kv_positions_read": read, "attn_kv_positions_cached": cached, "chunks": 9}}}
    sources = {"metrics_before": dump(1000, 2000), "metrics_after": dump(4000, 12000)}
    assert read_metric(name, sources) == pytest.approx(0.3)


@pytest.mark.parametrize("name", CELLS)
def test_a_pod_without_the_counters_reports_no_read_share(name):
    """The parent commit, or a model none of whose layers took the kernel:
    nothing is read, nothing raises, and the line leaves the metric out."""
    parent = {"default": {"continuous": {"chunks": 9, "decode_rows": 64}}}
    assert read_metric(name, {"metrics_before": parent, "metrics_after": parent}) is None
    assert read_metric(name, {}) is None


def test_benchmark_json_ends_with_the_two_read_shares():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    # found by name and by cell (a later cell whose pod has the counters is appended to
    # a metric's list, PR 54; a `benchmark` PR may merge the two into one entry's list)
    for name, cell in CELLS.items():
        m = per_layer[name]
        assert cell in m["workloads"]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            "ratio", "lower", "program_counter", "Kernels / model step", "tokens_per_s")


# -- the ring kernel's share (PR 48) ---------------------------------------------------


def test_the_ring_kernel_share_is_the_layouts_two_counters_growth():
    """``attn.ring_kernel_share.reason``: window layers' steps that read their
    ring in the kernel over window layers' steps; a pod without the counters
    (the parent commit, the CPU) reports nothing and nothing raises."""
    dump = lambda kernel, calls: {"default": {"continuous": {  # noqa: E731
        "attn_ring_kernel_calls": kernel, "attn_ring_calls": calls, "chunks": 9}}}
    name = "attn.ring_kernel_share.reason"
    assert read_metric(name, {"metrics_before": dump(300, 300),
                              "metrics_after": dump(3300, 3300)}) == 1.0
    assert read_metric(name, {"metrics_before": dump(0, 300),
                              "metrics_after": dump(0, 3300)}) == 0.0
    parent = {"default": {"continuous": {"chunks": 9, "attn_kv_positions_read": 5}}}
    assert read_metric(name, {"metrics_before": parent, "metrics_after": parent}) is None
    assert read_metric(name, {}) is None


def test_benchmark_json_ends_with_the_ring_kernel_share():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        found = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "attn.ring_kernel_share.reason"]  # by name, not by place
    assert len(found) == 1
    assert "laguna-s-2.1-ep2-d5.reason" in found[0]["workloads"]  # later rings are appended
    assert dict(found[0], workloads=None) == {
        "name": "attn.ring_kernel_share.reason", "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "Kernels / model step",
        "moves": "tokens_per_s", "workloads": None}


# -- keys wider than values, sinks, and lines of a position's heads side by side ----------
# (MiMo-V2-Flash: keys of 192 over values of 128, a sink a query head on the window layers)


def _wide(rows, group, cache_len, d=24, dv=16, hkv=2, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed + rows + group)
    q = jnp.asarray(rng.randn(rows, 1, hkv * group, d), dtype)
    k = jnp.asarray(rng.randn(rows, cache_len, hkv, d), dtype)
    v = jnp.asarray(rng.randn(rows, cache_len, hkv, dv), dtype)
    sinks = jnp.asarray(rng.randn(hkv * group), jnp.float32)
    return q, k, v, sinks


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


FORMS = {"by_head": lambda k, v: (k, v), "flat": lambda k, v: (_flat(k), _flat(v))}


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("sunk", [False, True])
@pytest.mark.parametrize("group", [4, 9])
def test_the_ragged_kernel_takes_wider_keys_sinks_and_flat_lines(group, sunk, form):
    """``dv != d``, with sinks and without, over ``[B, L, Hkv, D]`` leaves and
    over ``[B, L, Hkv * D]`` ones (each query in its own KV head's lanes): rows
    inside a block, on its edge, at the cache's end, one idle at offset 0."""
    q, k, v, sinks = _wide(5, group, L)
    sinks = sinks if sunk else None
    offsets = jnp.asarray([0, 37, BLOCK - 1, 2 * BLOCK, L - 1], jnp.int32)
    got = attn.decode_attention(q, *FORMS[form](k, v), offsets + 1, block=BLOCK,
                                interpret=True, sinks=sinks)
    assert got.shape == (5, 1, 2 * group, 16)
    want = reference(q, k, v, offsets, sinks=sinks)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("sunk", [False, True])
def test_the_ring_kernel_takes_wider_keys_sinks_and_flat_lines(sunk, form):
    """Rings not yet full, full and overwritten several times; a sink is the
    softmax's starting state, so a ring that holds one position gives that
    position's value times ``e^s / (e^s + e^sink)``."""
    offsets = jnp.asarray([0, 5, RING - 1, RING + 3, 7 * RING + 11], jnp.int32)
    q, k, v, sinks = _wide(len(offsets), 4, RING)
    sinks = sinks if sunk else None
    got = attn.ring_decode_attention(q, *FORMS[form](k, v), offsets, WINDOW, interpret=True,
                                     sinks=sinks)
    want = ring_reference(q, k, v, offsets, sinks=sinks)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if sunk:  # row 0 sees its own position only
        s = jnp.einsum("hd,hd->h", q[0, 0].reshape(2, 4, -1).reshape(8, -1),
                       jnp.repeat(k[0, 0], 4, axis=0)) / np.sqrt(24)
        share = jnp.exp(s) / (jnp.exp(s) + jnp.exp(sinks))
        np.testing.assert_allclose(got[0, 0], jnp.repeat(v[0, 0], 4, axis=0) * share[:, None],
                                   rtol=2e-5, atol=2e-5)


def test_sinks_and_wider_keys_in_bf16_keep_f32_statistics():
    q, k, v, sinks = _wide(3, 8, L, d=192, dv=128, hkv=2, dtype=jnp.bfloat16)
    offsets = jnp.asarray([3, 40, L - 1], jnp.int32)
    got = attn.decode_attention(q, _flat(k), _flat(v), offsets + 1, block=BLOCK, interpret=True,
                                sinks=sinks)
    assert got.dtype == jnp.bfloat16 and got.shape == (3, 1, 16, 128)
    want = reference(q, k, v, offsets, sinks=sinks)
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32), atol=3e-2)


@pytest.mark.parametrize("window", [0, WINDOW])
@pytest.mark.parametrize("form", FORMS)
def test_a_block_of_queries_a_key_block_at_a_time_is_the_reference(form, window):
    """``blocked_attention``: a prompt piece over a cache that holds it, rows
    at their own offsets, keys 16 at a time — the blocks past the last query
    and below the first one's window are not visited."""
    rng = np.random.RandomState(3)
    _, k, v, sinks = _wide(3, 4, L)
    q = jnp.asarray(rng.randn(3, 16, 8, 24), jnp.float32)
    offsets = jnp.asarray([0, 20, L - 16], jnp.int32)
    for sk in (None, sinks):
        got = attn.blocked_attention(q, *FORMS[form](k, v), offsets, window=window, sinks=sk,
                                     block_k=16)
        want = reference(q, k, v, offsets, window=window, sinks=sk)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("filled", [0, 16, 48, 200])
def test_a_piece_over_an_unrolled_ring_is_the_window_over_the_whole_sequence(filled):
    """``ring_context_attention``: the slot's last RING positions in position
    order from ``filled - RING`` on (negative: nothing) and the piece's own
    give what the window gives over the whole sequence, and the last RING of
    the two come back."""
    piece, total = 16, 216
    rng = np.random.RandomState(filled)
    _, k, v, sinks = _wide(1, 4, total)
    q = jnp.asarray(rng.randn(1, piece, 8, 24), jnp.float32)
    want = reference(q, k[:, :filled + piece], v[:, :filled + piece], filled, window=WINDOW,
                     sinks=sinks)
    at = np.arange(filled - RING, filled)
    held = lambda x: jnp.where((at >= 0)[None, :, None, None], x[:, np.maximum(at, 0)], 7.0)
    new = lambda x: x[:, filled: filled + piece]
    got, (ck, cv) = attn.ring_context_attention(
        q, _flat(held(k)), _flat(held(v)), _flat(new(k)), _flat(new(v)), filled - RING, filled,
        WINDOW, sinks=sinks)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    last = np.arange(filled + piece - RING, filled + piece)
    np.testing.assert_array_equal(np.asarray(ck)[0, last >= 0], np.asarray(_flat(k))[0, last[last >= 0]])
    np.testing.assert_array_equal(np.asarray(cv)[0, last >= 0], np.asarray(_flat(v))[0, last[last >= 0]])


def test_the_flash_kernel_takes_wider_keys_and_a_sink_a_head():
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 8, 40, 24), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 40, 24), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 40, 16), jnp.float32)
    sinks = jnp.asarray(rng.randn(8), jnp.float32)
    for sk in (None, sinks):
        got = attn.flash_attention(q, k, v, causal=True, window=WINDOW, interpret=True, sinks=sk)
        want = attn.attention_reference(q, k, v, causal=True, window=WINDOW, sinks=sk)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_without_sinks_and_with_one_width_the_kernels_trace_as_they_did():
    """The existing families' programs must not move: ``sinks=None`` over
    ``[B, L, Hkv, D]`` leaves of one width adds no operand and no operation."""
    q, k, v = _shapes(group=6)
    plain = str(jax.make_jaxpr(lambda q, k, v, n: attn.decode_attention(
        q, k, v, n, interpret=True))(q, k, v, OFFSETS))
    told = str(jax.make_jaxpr(lambda q, k, v, n: attn.decode_attention(
        q, k, v, n, interpret=True, sinks=None))(q, k, v, OFFSETS))
    assert plain == told
    sunk = str(jax.make_jaxpr(lambda q, k, v, n, s: attn.decode_attention(
        q, k, v, n, interpret=True, sinks=s))(q, k, v, OFFSETS, jax.ShapeDtypeStruct((48,), jnp.float32)))
    assert sunk != plain


@pytest.mark.parametrize("shape,told,block", [
    ((32, 32768, 768), {}, 512), ((32, 32768, 512), {}, 512), ((32, 144, 1536), {"ring": True}, 144),
    ((32, 144, 1024), {"ring": True}, 144), ((32, 32768, 96), {}, 0), ((32, 144, 96), {"ring": True}, 0),
    ((32, 512, 768), {}, 256), ((32, 128, 768), {}, 0), ((32, 128, 768), {"impl": "ragged"}, 64)])
def test_a_flat_leafs_rule_asks_whole_lane_tiles_of_its_line(on_a_tpu, shape, told, block):
    """``decode_block`` of ``[B, L, W]``: MiMo-V2-Flash's four leaf shapes take
    the kernels (blocks of 512 positions; a ring whole), a line that is not
    whole 128-lane tiles does not, a short cache only by name."""
    assert attn.decode_block(shape, 2, **told) == block
