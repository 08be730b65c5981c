"""The decode step's per-row cache write (ops/kv_write.py, ISSUE 41): one
rule for every family, two lowerings of one copy. On the CPU the kernel runs
in Pallas's interpret mode — values, clamps, aliasing inside a scan, and which
callers the rule leaves on the scatter; never a time."""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.continuous import ContinuousBatcher
from modelx_tpu.dl.serve import ModelServer
from modelx_tpu.ops import kv_write
from modelx_tpu.parallel.mesh import make_mesh


def scatter(cache, new, index):
    """What every family wrote before: the parent commit's three lines."""
    if jnp.ndim(index) == 0:
        return jax.lax.dynamic_update_slice(cache, new, (0, index) + (0,) * (cache.ndim - 2))
    zeros = (0,) * (cache.ndim - 2)
    return jax.vmap(lambda c, u, o: jax.lax.dynamic_update_slice(c, u, (o,) + zeros))(
        cache, new, index)


def operands(rows, length, dtype, seed=0, heads=8, head_dim=128):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    cache = jax.random.normal(keys[0], (rows, length, heads, head_dim), jnp.float32).astype(dtype)
    new = jax.random.normal(keys[1], (rows, 1, heads, head_dim), jnp.float32).astype(dtype)
    return cache, new


def indices(kind: str, rows: int, length: int) -> np.ndarray:
    """One start a row. ``edges``: 0, the last position, past the end and
    negative ones (``dynamic_update_slice`` counts a negative start from the
    end, then clamps), cycled over the rows; ``equal``: every row at the same
    start; ``spread``: the rows at depths of their own, as slots are."""
    if kind == "edges":
        edge = [0, length - 1, length, length + 7, -1, -length, -length - 3, 5]
        return np.resize(np.asarray(edge, np.int32), rows)
    if kind == "equal":
        return np.full(rows, length // 3, np.int32)
    return np.random.default_rng(rows).integers(0, length, rows).astype(np.int32)


# the two cells' leaves and Laguna's ring; float32 where a gigabyte is not needed to show it
LEAVES = [(32, 2048, "bfloat16"), (64, 4096, "bfloat16"), (64, 512, "bfloat16"),
          (64, 528, "bfloat16"), (32, 2048, "float32"), (64, 512, "float32")]


@pytest.mark.parametrize("kind", ["edges", "equal", "spread"])
@pytest.mark.parametrize("rows,length,dtype", LEAVES)
def test_the_kernel_writes_what_the_scatter_writes_bit_for_bit(rows, length, dtype, kind):
    cache, new = operands(rows, length, jnp.dtype(dtype))
    index = jnp.asarray(indices(kind, rows, length))
    got = kv_write.write_rows_kernel(cache, new, index, interpret=True)
    assert got.dtype == cache.dtype and bool(jnp.array_equal(got, scatter(cache, new, index)))


@pytest.mark.parametrize("kind", ["edges", "equal", "spread"])
@pytest.mark.parametrize("rows,length,width,dtype", [
    (32, 2048, 768, "bfloat16"), (32, 144, 1536, "bfloat16"), (8, 144, 1024, "float32"),
    (8, 64, 512, "bfloat16")])
def test_the_line_kernel_writes_what_the_scatter_writes_bit_for_bit(rows, length, width, dtype, kind):
    """A leaf that keeps a position in ONE line (MiMo-V2-Flash's four leaf
    shapes, cut in length): the row's group of 16 positions in, the line
    replaced, the group out — the scatter's result, its clamp included."""
    keys = jax.random.split(jax.random.PRNGKey(width), 2)
    cache = jax.random.normal(keys[0], (rows, length, width), jnp.float32).astype(dtype)
    new = jax.random.normal(keys[1], (rows, 1, width), jnp.float32).astype(dtype)
    index = jnp.asarray(indices(kind, rows, length))
    got = kv_write.write_rows_kernel(cache, new, index, interpret=True)
    assert got.dtype == cache.dtype and bool(jnp.array_equal(got, scatter(cache, new, index)))


def test_the_kernel_inside_a_scan_whose_carry_is_the_donated_cache():
    """As the chunk program holds it: the cache is the scan's carry, donated,
    each step writes every row's next position and reads the line back."""
    cache, new = operands(8, 64, jnp.bfloat16)
    start = jnp.asarray(indices("spread", 8, 40))

    def chunk(write, cache, new, offsets):
        def step(carry, _):
            cache, new, offsets = carry
            cache = write(cache, new, offsets)
            line = jax.vmap(lambda c, o: jax.lax.dynamic_slice_in_dim(c, o, 1))(cache, offsets)
            return (cache, (new + line).astype(new.dtype), offsets + 1), line[:, 0, 0, 0]
        return jax.lax.scan(step, (cache, new, offsets), None, length=12)

    kernel = lambda c, n, i: kv_write.write_rows_kernel(c, n, i, interpret=True)  # noqa: E731
    want = chunk(scatter, cache, new, start)
    got = jax.jit(lambda *a: chunk(kernel, *a), donate_argnums=(0,))(cache + 0, new, start)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert bool(jnp.array_equal(a, b))


# -- who takes the kernel -------------------------------------------------------


def test_the_rule_picks_the_kernel_for_the_two_cells_leaves_on_one_tpu_device(monkeypatch):
    assert kv_write.lowering((64, 4096, 8, 128), (64, 1, 8, 128), 1) == "scatter"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for rows, length in ((64, 4096), (32, 2048), (64, 528)):
        assert kv_write.lowering((rows, length, 8, 128), (rows, 1, 8, 128), 1) == "kernel"
    one = make_mesh("dp=1", jax.devices()[:1])
    assert kv_write.lowering((64, 4096, 8, 128), (64, 1, 8, 128), 1, one) == "kernel"
    # lines a position (PR 54): whole lane tiles, 512 lanes at least, whole groups of 16
    for shape in ((32, 32768, 768), (32, 32768, 512), (32, 144, 1536), (32, 144, 1024)):
        assert kv_write.lowering(shape, (32, 1, shape[2]), 1) == "kernel"
    for shape in ((32, 32768, 256), (32, 32768, 576), (32, 150, 1024)):
        assert kv_write.lowering(shape, (32, 1, shape[2]), 1) == "scatter"


NOT_THE_KERNEL = {
    "scalar_index": ((4, 64, 8, 128), 1, None, False),
    "an_admissions_block": ((4, 64, 8, 128), 16, "rows", False),
    "phi3s_heads_of_96": ((4, 64, 32, 96), 1, "rows", False),
    "two_kv_heads": ((4, 64, 2, 128), 1, "rows", False),
    "a_leaf_of_256_lanes": ((4, 64, 256), 1, "rows", False),
    "a_mesh_of_two_devices": ((4, 64, 8, 128), 1, "rows", True),
}


@pytest.mark.parametrize("case", NOT_THE_KERNEL)
def test_every_other_caller_traces_the_parents_primitives_exactly(monkeypatch, case):
    """With the backend steered to a TPU — the one condition a CPU run cannot
    meet — each shape the rule leaves out traces to the same jaxpr, equation
    for equation, as the three lines the families had."""
    shape, new_len, index, meshed = NOT_THE_KERNEL[case]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh("dp=2", jax.devices()[:2]) if meshed else None
    cache = jnp.zeros(shape, jnp.bfloat16)
    new = jnp.ones((shape[0], new_len, *shape[2:]), jnp.bfloat16)
    index = jnp.int32(3) if index is None else jnp.arange(shape[0], dtype=jnp.int32)
    got = jax.make_jaxpr(lambda c, n, i: kv_write.write_rows(c, n, i, mesh))(cache, new, index)
    assert "pallas_call" not in str(got) and str(got) == str(jax.make_jaxpr(scatter)(cache, new, index))
    assert kv_write.lowering(shape, new.shape, jnp.ndim(index), mesh) == "scatter"


def test_the_pick_is_recorded_at_trace_time():
    """Beside ``attention.*`` in ``/v1/trace``: a zero-length span a call
    site, named for the lowering and the leaf's slots x positions. A scalar
    start is one slice — no choice, no record."""
    from modelx_tpu.utils.trace import tracer

    cache, new = operands(3, 40, jnp.float32)  # a leaf no other test of this process writes
    count = lambda: sum(s["count"] for s in tracer().summary("kv_write.").values())  # noqa: E731
    before = count()
    jax.make_jaxpr(kv_write.write_rows)(cache, new, jnp.arange(3, dtype=jnp.int32))
    jax.make_jaxpr(kv_write.write_rows)(cache, new, jnp.int32(0))
    assert tracer().summary("kv_write.")["kv_write.scatter[3x40]"]["count"] == 1
    assert count() == before + 1


# -- the engines, either lowering -------------------------------------------------


def forced(cache_shape, new_shape, index_ndim, mesh=None):
    """The rule with everything but the tiling and the backend: what a tiny
    model on the CPU needs to reach the kernel (interpreted)."""
    return "kernel" if index_ndim == 1 and len(cache_shape) == 4 and new_shape[1] == 1 \
        else "scatter"


def mixtral_dir(path):
    from modelx_tpu.models import mixtral

    cfg = dataclasses.replace(mixtral.MixtralConfig.tiny(vocab_size=64), dtype=jnp.float32)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    st.write_safetensors(str(path / "model.safetensors"),
                         {k: np.asarray(v, np.float32) for k, v in params.items()})


def laguna_dir(path):
    from modelx_tpu.models import laguna

    cfg = laguna.LagunaConfig.tiny(vocab_size=64)
    params = laguna.init_params(cfg, jax.random.PRNGKey(0))
    st.write_safetensors(str(path / "model.safetensors"),
                         laguna.to_hf_state_dict(params, first=cfg.expert_first))
    (path / "config.json").write_text(json.dumps(laguna.to_hf_config(cfg)))


@pytest.mark.parametrize("family,write,leaves", [("mixtral", mixtral_dir, None),
                                                 ("laguna", laguna_dir, 10)])
def test_a_tiny_engine_gives_the_same_greedy_tokens_with_either_lowering(
        tmp_path, monkeypatch, family, write, leaves):
    """Prompts of several lengths through the engine's admit and chunk
    programs, outputs long enough to wrap Laguna's ring: the scatter's tokens,
    then the kernel's from a new engine (its programs trace anew). The engine
    that took the kernel counts every row of every written leaf to it; the
    one that did not has no such counter."""
    write(tmp_path)
    server = ModelServer(str(tmp_path), mesh_spec="dp=1", dtype="float32", max_seq_len=96)
    server.load()
    assert server.family.name == family
    prompts = np.random.default_rng(1).integers(1, 60, (3, 9)).astype(np.int32)

    def run():
        cb = ContinuousBatcher(server, max_slots=4, chunk_size=4)
        try:
            return np.asarray(cb.generate(prompts, max_new_tokens=44)), dict(cb.stats)
        finally:
            cb.close()

    want, plain = run()
    assert "kv_write_rows" not in plain and "kv_write_rows_kernel" not in plain
    monkeypatch.setattr(kv_write, "lowering", forced)
    got, stats = run()
    np.testing.assert_array_equal(got, want)
    assert stats["kv_write_rows_kernel"] == stats["kv_write_rows"] > 0
    written = leaves or 2 * server.cfg.num_layers
    assert stats["kv_write_rows"] % (4 * 4 * written) == 0  # chunk steps x slots x leaves
