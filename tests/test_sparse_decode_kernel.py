"""The sparse layers' one-token kernel
(``ops.sparse_attention.decode_attention_kernel``), the rule that picks it
(``decode_takes_kernel``) and the counter that says it engaged. Two oracles:
the gather form (``decode_attention``, the same arithmetic with another way to
fetch its operands) and dense attention over the whole row under the
selected-block mask. The kernel runs in pallas interpret mode here, asked for
by name; what interpret mode cannot see — tiling, the cache read as it lies —
is in tests/test_tpu_compile.py.

Tolerance: float32 operands, so all that differs is the order of sums 64-640
terms long — 2e-5 absolute on outputs of order 0.1-1. With bfloat16 operands
both forms round the probabilities to bfloat16 before the second contraction,
and a probability that lands on the other side of a rounding boundary moves an
output by 2^-9 of one value's share: 5e-3."""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.models import minicpm_sala as sala
from modelx_tpu.ops import sparse_attention as sparse
from modelx_tpu.ops.attention import attention_reference
from modelx_tpu.ops.sparse_attention import SparseSpec
from modelx_tpu.parallel.mesh import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, BLOCKS, ATOL = 128, 10, 2e-5


@pytest.fixture(autouse=True, scope="module")
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def spec_of(size, topk=6):
    return SparseSpec(kernel_size=8, kernel_stride=4, init_blocks=1, block_size=size,
                      window_size=2 * size, topk=topk, dense_len=2 * size)


def operands(size, hkv, rows=3, group=4, seed=0, blocks=BLOCKS, dtype=jnp.float32):
    rng = np.random.RandomState(seed + 10 * size + hkv)
    q = jnp.asarray(rng.randn(rows, hkv * group, D), dtype)
    k, v = (jnp.asarray(rng.randn(rows, blocks * size, hkv * D), dtype) for _ in range(2))
    return q, k, v


def pick(rows, hkv, k_blocks, blocks=BLOCKS, seed=0):
    """[rows, hkv, k_blocks]: each (row, head) its own blocks, in no order."""
    rng = np.random.RandomState(seed)
    return jnp.asarray([[rng.permutation(blocks)[:k_blocks] for _ in range(hkv)]
                        for _ in range(rows)], jnp.int32)


def kernel(q, k, v, chosen, position, spec):
    return np.asarray(sparse.decode_attention_kernel(q, k, v, chosen, position, spec,
                                                     interpret=True))


def gather(q, k, v, chosen, position, spec):
    return np.asarray(sparse.decode_attention(q, k, v, chosen, position, spec))


def dense_under_the_mask(q, k, v, chosen, position, spec):
    """Every position of the row, visible where its block is selected for the
    head's KV head and it is not past the query: float64, no gather."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    chosen, position = np.asarray(chosen), np.asarray(position)
    b, h, d = q.shape
    length, hkv = k.shape[1], k.shape[2] // d
    at = np.arange(length)
    out = np.zeros((b, h, d))
    for i in range(b):
        for head in range(h):
            kv = head // (h // hkv)
            visible = np.isin(at // spec.block_size, chosen[i, kv]) & (at <= position[i])
            logits = k[i, :, kv * d: (kv + 1) * d] @ q[i, head] / np.sqrt(d)
            p = np.where(visible, np.exp(logits - logits[visible].max()), 0.0)
            out[i, head] = (p / p.sum()) @ v[i, :, kv * d: (kv + 1) * d]
    return out


SHAPES = [(16, 2), (16, 1), (64, 2), (64, 1)]
shapes = pytest.mark.parametrize("size,hkv", SHAPES, ids=[f"block{s}-kv{h}" for s, h in SHAPES])


@shapes
@pytest.mark.parametrize("oracle", [gather, dense_under_the_mask], ids=["gather", "dense"])
def test_the_kernel_gives_the_oracles_values(size, hkv, oracle):
    spec = spec_of(size)
    q, k, v = operands(size, hkv)
    chosen = pick(3, hkv, 6)
    position = jnp.asarray([BLOCKS * size - 1, 5 * size + 3, 7 * size], jnp.int32)
    np.testing.assert_allclose(kernel(q, k, v, chosen, position, spec),
                               oracle(q, k, v, chosen, position, spec), atol=ATOL)


@shapes
def test_a_partly_filled_own_block_is_masked_past_the_query(size, hkv):
    """The query sits in the middle of a selected block: what lies behind it in
    that block — and every selected block past it — counts for nothing."""
    spec = spec_of(size, topk=4)
    q, k, v = operands(size, hkv, rows=2)
    own = 6
    chosen = jnp.asarray([[[0, own - 1, own, own + 2]] * hkv] * 2, jnp.int32)
    position = jnp.asarray([own * size + size // 2, own * size], jnp.int32)
    got = kernel(q, k, v, chosen, position, spec)
    np.testing.assert_allclose(got, dense_under_the_mask(q, k, v, chosen, position, spec),
                               atol=ATOL)
    past = (jnp.arange(BLOCKS * size)[None, :, None] > position[:, None, None])
    k2, v2 = (jnp.where(past, 1e3, x) for x in (k, v))
    np.testing.assert_array_equal(kernel(q, k2, v2, chosen, position, spec), got)


def test_both_heads_may_select_the_same_blocks():
    spec = spec_of(16)
    q, k, v = operands(16, 2)
    chosen = jnp.broadcast_to(pick(3, 1, 6), (3, 2, 6))
    position = jnp.full((3,), BLOCKS * 16 - 1, jnp.int32)
    want = dense_under_the_mask(q, k, v, chosen, position, spec)
    np.testing.assert_allclose(kernel(q, k, v, chosen, position, spec), want, atol=ATOL)
    # and a head reads its own lanes of them: the two heads' outputs differ
    assert np.abs(want[:, :4] - want[:, 4:]).max() > 0.01


@shapes
def test_score_order_and_index_order_give_the_same_output(size, hkv):
    """``select_blocks`` returns the forced blocks first, in order — the window
    is then one run of the row, which the kernel copies as one — then the rest
    by score; sorted by index the same blocks are read block by block."""
    spec = spec_of(size)  # window of 2 blocks, 1 initial block
    q, k, v = operands(size, hkv)
    own = np.asarray([9, 5, 7])
    rest = np.asarray([[[3, 1, 2], [2, 3, 1]][:hkv]] * 3)  # before every row's window
    forced = np.stack([np.zeros(3, int), own - 1, own], axis=-1)[:, None].repeat(hkv, 1)
    by_score = jnp.asarray(np.concatenate([forced, rest], axis=-1), jnp.int32)
    position = jnp.asarray(own * size + 2, jnp.int32)
    got = kernel(q, k, v, by_score, position, spec)
    np.testing.assert_allclose(got, dense_under_the_mask(q, k, v, by_score, position, spec),
                               atol=ATOL)
    np.testing.assert_allclose(kernel(q, k, v, jnp.sort(by_score, axis=-1), position, spec), got,
                               atol=ATOL)
    np.testing.assert_allclose(kernel(q, k, v, by_score[..., ::-1], position, spec), got,
                               atol=ATOL)


@pytest.mark.parametrize("blocks", [2, 3, 5])
def test_a_row_of_fewer_blocks_than_topk_is_read_whole(blocks):
    """``select_blocks`` returns ``min(topk, blocks)`` blocks: fewer than the
    forced ones fill, so no run of the window is looked for."""
    spec = spec_of(16, topk=6)
    q, k, v = operands(16, 2, blocks=blocks)
    chosen = pick(3, 2, blocks, blocks=blocks)
    position = jnp.asarray([blocks * 16 - 1, 20, 0], jnp.int32)
    want = gather(q, k, v, chosen, position, spec)
    np.testing.assert_allclose(kernel(q, k, v, chosen, position, spec), want, atol=ATOL)
    t = lambda x: x.reshape(3, blocks * 16, 2, D)  # noqa: E731
    dense = attention_reference(q[:, :, None], t(k).transpose(0, 2, 1, 3),
                                t(v).transpose(0, 2, 1, 3), causal=True, q_offset=position)
    np.testing.assert_allclose(want, np.asarray(dense)[:, :, 0], atol=ATOL)


def test_rows_at_different_contexts_and_groups_that_fill_no_tile():
    """Five rows from the first block to the last in one call; a group of 3
    query heads is padded to the tile's 16 rows and cut back."""
    spec = spec_of(16)
    q, k, v = operands(16, 2, rows=5, group=3)
    chosen = pick(5, 2, 6, seed=3)
    position = jnp.asarray([0, 15, 16, 77, BLOCKS * 16 - 1], jnp.int32)
    got = kernel(q, k, v, chosen, position, spec)
    assert got.shape == (5, 6, D) and got.dtype == np.float32
    np.testing.assert_allclose(got, gather(q, k, v, chosen, position, spec), atol=ATOL)


def test_bf16_operands_keep_f32_logits_and_accumulation():
    spec = spec_of(64)
    q, k, v = operands(64, 2, dtype=jnp.bfloat16)
    chosen = pick(3, 2, 6)
    position = jnp.asarray([BLOCKS * 64 - 1, 300, 450], jnp.int32)
    got = kernel(q, k, v, chosen, position, spec)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, gather(q, k, v, chosen, position, spec), atol=5e-3)
    np.testing.assert_allclose(got, dense_under_the_mask(q, k, v, chosen, position, spec),
                               atol=2e-2)


# -- who takes the kernel -------------------------------------------------------


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The rule asks for the backend; a test steers it, no option does."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def shapes_of(d=D, hkv=2, length=640):
    sds = jax.ShapeDtypeStruct
    return sds((4, 1, 8, d), jnp.bfloat16), sds((4, length, hkv * d), jnp.bfloat16)


def test_on_the_cpu_the_gather_runs_unless_the_kernel_is_asked_for_by_name():
    q, k = shapes_of()
    assert sparse.decode_takes_kernel(q, k, SparseSpec()) == (False, False)
    assert sparse.decode_takes_kernel(q, k, SparseSpec(), "ragged+interpret") == (False, False)
    assert sparse.decode_takes_kernel(q, k, SparseSpec(), "sparse+interpret") == (True, True)
    assert sparse.decode_takes_kernel(q, k, SparseSpec(), "sparse") == (True, False)


def test_on_one_tpu_device_the_inputs_decide(on_a_tpu):
    q, k = shapes_of()
    assert sparse.decode_takes_kernel(q, k, SparseSpec()) == (True, False)
    one = make_mesh("dp=1", devices=jax.devices()[:1])
    assert sparse.decode_takes_kernel(q, k, SparseSpec(), mesh=one) == (True, False)
    two = make_mesh("dp=2", devices=jax.devices()[:2])
    assert sparse.decode_takes_kernel(q, k, SparseSpec(), mesh=two) == (False, False)
    tiny = sala.SalaConfig.tiny().sparse  # blocks of 8 positions, heads of 8
    assert sparse.decode_takes_kernel(q, k, tiny) == (False, False)
    assert sparse.decode_takes_kernel(*shapes_of(d=8), SparseSpec()) == (False, False)
    assert sparse.decode_takes_kernel(*shapes_of(d=64), SparseSpec()) == (False, False)


def step_of(impl, on_tpu=False):
    """The tiny family's decode step past ``dense_len``, as a jaxpr."""
    cfg = sala.SalaConfig.tiny(vocab_size=64)
    params = jax.eval_shape(lambda: sala.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: sala.init_layer_state(cfg, 2, 64))
    return str(jax.make_jaxpr(lambda p, s, t, o: sala.forward(
        p, t, cfg, kv_cache=s, cache_offset=o, attention_impl=impl))(
            params, state, jax.ShapeDtypeStruct((2, 1), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32)))


def test_the_familys_step_takes_the_kernel_by_name_only_here(on_a_tpu):
    """On the CPU, and on a TPU at the tiny configuration's blocks of 8 and
    heads of 8, the step lowers as it did; by name it holds the kernel."""
    assert "pallas_call" not in step_of("auto")
    assert "sparse_decode_attention" in step_of("sparse+interpret")


def test_by_name_the_familys_step_gives_the_same_logits_and_counts_the_kernels_rows():
    cfg = sala.SalaConfig.tiny(vocab_size=64)
    params = sala.init_params(cfg, jax.random.PRNGKey(1))
    toks = np.random.RandomState(0).randint(0, 64, (2, 48)).astype(np.int32)
    logits = {}
    for impl in ("auto", "sparse+interpret"):
        state = sala.init_layer_state(cfg, 2, 64)
        _, state = sala.forward(params, jnp.asarray(toks[:, :24]), cfg, kv_cache=state,
                                cache_offset=jnp.int32(0), valid_len=jnp.asarray([24, 24]))
        outs = []
        for t in range(24, 48):  # the rows cross dense_len = 32 on the way
            out, state = sala.forward(params, jnp.asarray(toks[:, t: t + 1]), cfg, kv_cache=state,
                                      cache_offset=jnp.asarray([t, t], jnp.int32),
                                      attention_impl=impl)
            outs.append(np.asarray(out))
        logits[impl] = np.concatenate(outs, axis=1)
        counts = dict(zip(sala.SPARSE_COUNTERS, np.asarray(state["sparse_counts"])))
        assert counts["steps_all"] == 48 and counts["steps_sparse"] == 2 * (48 - 31)
        assert counts["steps_kernel"] == (counts["steps_sparse"] if impl != "auto" else 0)
    np.testing.assert_allclose(logits["sparse+interpret"], logits["auto"], atol=1e-4)


# -- the per-layer metric that reads the counter --------------------------------

METRIC, CELL = "sparse.kernel_share.longctx", "minicpm-sala-d12.longctx"


def read_metric(sources):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", METRIC + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
    return reader.read(sources, spec)


def dump(sparse_steps, kernel_steps=None):
    counters = {"steps_sparse": sparse_steps, "steps_all": sparse_steps + 7}
    if kernel_steps is not None:
        counters["steps_kernel"] = kernel_steps
    return {"default": {"continuous": {"sparse": counters}}}


def test_the_kernel_share_is_the_counters_growth_over_the_traced_span():
    span = {"metrics_before": dump(1000, 400), "metrics_after": dump(4000, 3400)}
    assert read_metric({"model": "default", "trace_span": span}) == pytest.approx(1.0)
    span = {"metrics_before": dump(1000, 0), "metrics_after": dump(4000, 0)}
    assert read_metric({"model": "default", "trace_span": span}) == 0.0


def test_a_pod_without_the_counter_reports_no_kernel_share():
    """The parent commit counts no ``steps_kernel``: nothing is read, nothing
    raises, and the line leaves the metric out."""
    span = {"metrics_before": dump(1000), "metrics_after": dump(4000)}
    assert read_metric({"model": "default", "trace_span": span}) is None
    assert read_metric({}) is None


def test_benchmark_json_lists_the_kernel_share_for_the_longctx_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [m for m in bench["per_layer"] if m["name"] == METRIC]  # by name, not by place
    assert len(entry) == 1 and CELL in entry[0]["workloads"]
    assert dict(entry[0], workloads=None) == {
        "name": METRIC, "unit": "ratio", "better": "higher", "source": "program_counter",
        "layer": "Kernels / model step", "moves": "tokens_per_s", "workloads": None}
    names = [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names)
    assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", "readers",
                                       "metrics_path.py"))
