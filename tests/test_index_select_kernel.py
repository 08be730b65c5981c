"""The decode step's selection without a sort (``ops/index_select.py``:
``score_kernel``, ``chosen_mask`` interpreted on the CPU, ``compact``),
against the forms they stand in for: ``select_reference`` (``lax.top_k``) as SETS, the
einsum scores on the positions a row holds, the prompt block's
``selection_mask``. What interpret mode cannot see — tiling, VMEM — is
``tests/test_tpu_compile.py``'s."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.models import deepseek_v2 as ds
from modelx_tpu.ops import index_select as select_ops
from modelx_tpu.ops import latent_attention as latent_ops

LANES = select_ops.LANES
K, LENGTH = 128, 1024
# the nine values ``test_the_selection_is_a_plain_sort_ties_included`` draws from, as floats
# that tie across the k-th place in every row, signed zeros among them
NINE = np.array([-4.0, -3.0, -2.0, -1.0, -0.0, 0.0, 1.0, 2.0, 3.0], np.float32)
# a row of length 1, k - 1, k, k + 1, the whole cache, and an idle one
LENGTHS = np.array([1, K - 1, K, K + 1, LENGTH, 0], np.int32)


def _chunks(scores):
    return jnp.asarray(scores).reshape(scores.shape[0], -1, LANES)


def _draw(kind: str, seed: int, rows: int = len(LENGTHS)) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "ties":
        return NINE[rng.integers(0, 9, (rows, LENGTH))]
    if kind == "one_value":  # every position ties: the lowest positions are the selection
        return np.full((rows, LENGTH), rng.standard_normal(), np.float32)
    x = rng.standard_normal((rows, LENGTH)).astype(np.float32)
    if kind == "tiny":  # subnormals and both signs: the bit pattern's order is the floats'
        x = (x * 1e-41).astype(np.float32)
    return x


@pytest.mark.parametrize("kind,seed", [("ties", 0), ("ties", 1), ("ties", 2), ("one_value", 3),
                                       ("normal", 4), ("normal", 5), ("tiny", 6)])
def test_the_kernels_selection_is_the_sorts_as_a_set(kind, seed):
    scores = _draw(kind, seed)
    got = np.asarray(select_ops.select_ascending(_chunks(scores), jnp.asarray(LENGTHS), K))
    want = np.asarray(select_ops.select_reference(jnp.asarray(scores), jnp.asarray(LENGTHS), K))
    assert got.shape == want.shape == (len(LENGTHS), K) and got.dtype == np.int32
    for row, n in enumerate(np.minimum(LENGTHS, K)):
        assert sorted(got[row, :n].tolist()) == sorted(want[row, :n].tolist()), (kind, row)
        assert got[row, :n].tolist() == sorted(got[row, :n].tolist())  # ascending
        # a short row's own positions first, all of them; behind them positions in range
        assert set(got[row, :n].tolist()) <= set(range(int(LENGTHS[row])))
    assert got.min() >= 0 and got.max() < LENGTH


@pytest.mark.parametrize("seed", [0, 1])
def test_select_reads_the_lowering_off_the_scores_form(seed):
    """``select`` is one function: ``[B, L]`` is the sort, best first; the
    chunks a scoring kernel writes are chosen without one, ascending."""
    scores = _draw("ties", 10 + seed)
    lengths = jnp.asarray(LENGTHS)
    flat = np.asarray(select_ops.select(jnp.asarray(scores), lengths, K))
    np.testing.assert_array_equal(
        flat, np.asarray(select_ops.select_reference(jnp.asarray(scores), lengths, K)))
    chunked = np.asarray(select_ops.select(_chunks(scores), lengths, K))
    np.testing.assert_array_equal(
        chunked, np.asarray(select_ops.select_ascending(_chunks(scores), lengths, K)))
    def traced(x):
        return str(jax.make_jaxpr(lambda x, n: select_ops.select(x, n, K))(x, lengths))

    assert " top_k[" not in traced(_chunks(scores)) and " sort" not in traced(_chunks(scores))
    assert traced(jnp.asarray(scores)).count(" top_k[") == 1  # a sort on the TPU


@pytest.mark.parametrize("kind,seed", [("ties", 20), ("normal", 21)])
def test_the_prompt_blocks_mask_of_one_query_a_row_is_the_sorts_set(kind, seed):
    """What ``select_ascending`` compacts: ``selection_mask`` of a query at
    position ``n - 1`` (it sees the first ``n``) holds exactly the positions
    ``lax.top_k`` returns, ``min(n, k)`` of them, the ties to the lower ones."""
    scores = _draw(kind, seed)[:5]
    lengths = LENGTHS[:5]
    got = np.asarray(select_ops.selection_mask(
        jnp.asarray(scores)[:, None], jnp.asarray(lengths - 1)[:, None], K))[:, 0]
    want = np.asarray(select_ops.select_reference(jnp.asarray(scores), jnp.asarray(lengths), K))
    for row, n in enumerate(np.minimum(lengths, K)):
        assert np.nonzero(got[row])[0].tolist() == sorted(want[row, :n].tolist())


@pytest.mark.parametrize("kind,seed,rows_a_step", [("ties", 22, 6), ("one_value", 23, 6),
                                                    ("normal", 24, 6), ("ties", 25, 2),
                                                    ("normal", 26, 1)])
def test_the_mask_is_the_prompt_blocks_mask(monkeypatch, kind, seed, rows_a_step):
    """``chosen_mask`` (47 passes in VMEM, the ties by position; a grid over
    groups of the rows that fit ``MASK_VMEM_BYTES``) against ``selection_mask``
    (32 passes in XLA, the ties by a cumulative sum): a query at position
    ``n - 1`` sees the first ``n``; an idle row chooses nothing."""
    monkeypatch.setattr(select_ops, "MASK_VMEM_BYTES", rows_a_step * 16 * LENGTH)
    assert select_ops.mask_group(len(LENGTHS), LENGTH) == rows_a_step
    scores = _draw(kind, seed)
    got = np.asarray(select_ops.chosen_mask(_chunks(scores), jnp.asarray(LENGTHS), K,
                                            interpret=True))
    want = np.asarray(select_ops.selection_mask(
        jnp.asarray(scores)[:, None], jnp.asarray(LENGTHS - 1)[:, None], K))[:, 0]
    np.testing.assert_array_equal(got.reshape(len(LENGTHS), LENGTH).astype(bool), want)
    assert got.sum(axis=(1, 2)).tolist() == np.minimum(LENGTHS, K).tolist()


def test_rows_a_step_of_the_mask_kernel_divide_the_rows_and_fit():
    assert select_ops.mask_group(16, 32768) == 16  # the cell: one step, 8 MB
    assert select_ops.mask_group(32, 32768) == 16 and select_ops.mask_group(24, 32768) == 24
    assert select_ops.mask_group(48, 32768) == 24 and select_ops.mask_group(17, 65536) == 1
    assert select_ops.mask_group(16, 2**20) == 0  # a row of a million positions: the sort
    assert select_ops.takes_kernel((16, 2**20, 640), 512, 2048, "ragged") == (0, False)


@pytest.mark.parametrize("ones", [0, 1, 127, 128, 129, 500])
def test_compaction_lists_the_ones_ascending_and_stays_in_range_behind_them(ones):
    rng = np.random.default_rng(ones)
    mask = np.zeros((3, LENGTH), np.float32)
    for row in range(3):
        mask[row, rng.choice(LENGTH, ones, replace=False)] = 1
    mask[2] = 0
    mask[2, LENGTH - ones:] = 1  # the last chunks alone
    got = np.asarray(select_ops.compact(_chunks(mask).astype(jnp.bfloat16), K))
    for row in range(3):
        want = np.nonzero(mask[row])[0][:K]
        assert got[row, : len(want)].tolist() == want.tolist()
    assert got.min() >= 0 and got.max() < LENGTH


# contexts that end inside a key block, at its edge, one past it; one key; the whole leaf
@pytest.mark.parametrize("lengths", [(200, 256, 257), (1, 512, 1024), (255, 769, 1023)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_scoring_kernel_is_the_einsum_on_what_a_row_holds(lengths, dtype):
    rng = np.random.default_rng(sum(lengths))
    b, heads, d, block = 3, 8, 32, 256
    q = jnp.asarray(rng.standard_normal((b, heads, d)), dtype)
    w = jnp.asarray(rng.standard_normal((b, heads)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((b, LENGTH, d)), dtype)
    want = np.asarray(select_ops.step_scores(q, w, keys))
    got = select_ops.step_scores(q, w, keys, jnp.asarray(lengths, jnp.int32), block=block,
                                 interpret=True)
    assert got.shape == (b, LENGTH // LANES, LANES) and got.dtype == jnp.float32
    got = np.asarray(got).reshape(b, LENGTH)
    for row, n in enumerate(lengths):
        held = -(-n // block) * block  # whole blocks are scored; past them nothing is defined
        assert np.abs(got[row, :held] - want[row, :held]).max() < 1e-4 * max(
            1.0, np.abs(want[row]).max())
    # and the selection over them is the selection over the einsum's
    chosen = np.asarray(select_ops.select(jnp.asarray(got).reshape(b, -1, LANES),
                                          jnp.asarray(lengths, jnp.int32), K))
    plain = np.asarray(select_ops.select(jnp.asarray(want), jnp.asarray(lengths, jnp.int32), K))
    for row, n in enumerate(np.minimum(lengths, K)):
        if dtype == jnp.float32:
            assert sorted(chosen[row, :n].tolist()) == sorted(plain[row, :n].tolist())


def test_a_cache_no_block_tiles_is_refused():
    q, w = jnp.zeros((1, 8, 32)), jnp.zeros((1, 8))
    with pytest.raises(ValueError, match="tiles"):
        select_ops.score_kernel(q, w, jnp.zeros((1, 384, 32)), jnp.ones((1,), jnp.int32),
                                block=256, interpret=True)
    with pytest.raises(ValueError, match="tiles"):
        select_ops.score_kernel(q, w, jnp.zeros((1, 512, 32)), jnp.ones((1,), jnp.int32),
                                block=64, interpret=True)


@pytest.mark.parametrize("shape,k,impl,backend,devices,want", [
    ((16, 32768, 640), 2048, "auto", "tpu", 1, (2048, False)),  # the cell
    ((16, 32768, 640), 2048, "auto", "tpu", 4, (0, False)),  # a mesh: the sort
    ((16, 32768, 640), 2048, "auto", "cpu", 1, (0, False)),
    ((16, 32768, 640), 2000, "auto", "tpu", 1, (0, False)),  # k in no whole chunks
    ((2, 512, 128), 128, "ragged+interpret", "cpu", 1, (256, True)),  # by name, as the tests do
    ((2, 512, 128), 128, "ragged", "cpu", 1, (256, False)),
    ((2, 64, 128), 8, "ragged+interpret", "cpu", 1, (0, False)),  # no chunk of 128 tiles 64
    ((2, 512, 128), 128, "expanded", "cpu", 1, (0, False)),
])
def test_the_kernels_run_where_the_absorbed_kernel_runs_and_chunks_tile(
        monkeypatch, shape, k, impl, backend, devices, want):
    import types

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = types.SimpleNamespace(size=devices)
    rank = 512 if shape[2] == 640 else 32
    assert select_ops.takes_kernel(shape, rank, k, impl, mesh) == want
    if want[0]:
        assert want == latent_ops.absorbed_takes_kernel(shape, rank, impl, mesh)


@pytest.fixture(scope="module")
def wide():
    """``tiny_v32`` keeping 128 of a 512-position cache: the smallest shapes
    the kernels' rule takes (blocks of 256 keys, chunks of 128)."""
    cfg = ds.DeepseekV2Config.tiny_v32(index_topk=K)
    params = ds.init_params(cfg, jax.random.PRNGKey(1))
    tokens = np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 400)).astype(np.int32)
    cache = ds.init_layer_state(cfg, 2, 512)
    # row 0 holds 300 positions (it selects), row 1 holds 100 (it keeps them all)
    _, cache = ds.forward(params, jnp.asarray(tokens[:, :300]), cfg, kv_cache=cache, cache_offset=0)
    return cfg, params, tokens, cache


def test_a_decode_step_by_the_kernels_is_the_step_by_the_sort(wide):
    cfg, params, tokens, cache = wide
    at = np.array([300, 100])
    tok = jnp.asarray(tokens[np.arange(2), at][:, None])
    outs = {}
    for impl in ("auto", "ragged+interpret"):
        state = cache
        for step in range(2):
            logits, state = ds.forward(params, tok, cfg, kv_cache=state,
                                       cache_offset=jnp.asarray(at + step), attention_impl=impl)
        outs[impl] = (np.asarray(logits), state)
    assert np.abs(outs["auto"][0] - outs["ragged+interpret"][0]).max() < 1e-4
    plain, kernel = (np.asarray(outs[i][1]["dsa_counts"]) for i in ("auto", "ragged+interpret"))
    layers = cfg.num_layers
    assert plain.tolist() == [layers * (301 + 101 + 302 + 102), layers * 2 * (K + 101) + layers,
                              layers * 2, layers * 4, 0]
    assert kernel[:4].tolist() == plain[:4].tolist() and kernel[4] == kernel[2] == layers * 2


def test_a_v32_decode_step_by_the_kernels_traces_no_sort(wide):
    """The twin of ``test_deepseek_v2_is_what_it_was``'s count: the router's
    ``top_k`` and no other (``noaux_tc`` has three a sparse layer: a group's two
    best, the groups, the experts), no ``sort``; the plain path of the same
    step has one a layer more, the selection's."""
    cfg, params, tokens, cache = wide
    step = lambda impl: str(jax.make_jaxpr(lambda c: ds.forward(  # noqa: E731
        params, jnp.asarray(tokens[:, 300:301]), cfg, kv_cache=c,
        cache_offset=jnp.asarray([300, 100]), attention_impl=impl))(cache))
    routers = 3 * (cfg.num_layers - cfg.first_k_dense_replace)
    kernels = step("ragged+interpret")
    assert kernels.count(" top_k[") == routers and " sort" not in kernels
    assert kernels.count("dsa_step_scores") == cfg.num_layers
    assert step("auto").count(" top_k[") == routers + cfg.num_layers
