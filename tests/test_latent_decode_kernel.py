"""The latent decode kernel (``ops.latent_attention.decode_kernel``), interpreted
on the CPU, against ``absorbed_reference`` — the ``jnp`` form over the whole
cache — and its schedule: a grid step for every block a row holds, none for
the others."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from modelx_tpu.ops import latent_attention as latent

B, H, RANK, ROPE, L, BLOCK = 4, 6, 128, 64, 256, 32
WIDTH = latent.line_width(RANK, ROPE)
SCALE = 0.11


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(0)
    q = rng.standard_normal((B, H, WIDTH)).astype(np.float32)
    cache = rng.standard_normal((B, L, WIDTH)).astype(np.float32)
    q[..., RANK + ROPE:] = 0.0
    cache[..., RANK + ROPE:] = 0.0
    return jnp.asarray(q), jnp.asarray(cache)


# what each case is about, the positions each of the four rows holds
LENGTHS = {
    "a_row_of_length_one": [1, 77, 130, 200],
    "a_length_that_ends_a_block_exactly": [32, 64, 96, 256],
    "a_last_block_one_position_long": [33, 65, 129, 225],
    "a_length_equal_to_the_cache": [256, 256, 256, 256],
    "lengths_past_the_cache": [300, 256, 1000, 257],
    "an_idle_row_beside_busy_ones": [0, 190, 0, 45],
    "every_row_at_a_different_block_count": [20, 50, 100, 250],
    "every_row_in_its_first_block": [1, 2, 31, 32],
}


@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_the_kernel_is_the_jnp_form(operands, case):
    q, cache = operands
    lengths = jnp.asarray(LENGTHS[case], jnp.int32)
    got = jax.jit(lambda q, c, n: latent.decode_kernel(
        q, c, n, SCALE, RANK, block=BLOCK, interpret=True))(q, cache, lengths)
    # the kernel clips to 1..L as the engine's offsets never leave it: an idle
    # row (offset 0) holds position 0
    held = jnp.clip(lengths, 1, L)
    want = latent.absorbed_reference(q, cache, held - 1, SCALE, RANK)
    assert got.shape == (B, H, RANK) and got.dtype == q.dtype
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_absorbed_takes_the_kernel_by_name_and_gives_q_s_dtype(operands, dtype):
    q, cache = (x.astype(dtype) for x in operands)
    offsets = jnp.asarray([0, 31, 32, 255], jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda q, c, o: latent.absorbed(
        q, c, o, SCALE, RANK, impl="ragged+interpret"))(q, cache, offsets))
    assert "pallas_call" in jaxpr and "latent_decode_attention" in jaxpr
    assert "pallas_call" not in str(jax.make_jaxpr(lambda q, c, o: latent.absorbed(
        q, c, o, SCALE, RANK))(q, cache, offsets))  # the CPU keeps the jnp form
    got = latent.absorbed(q, cache, offsets, SCALE, RANK, impl="ragged+interpret")
    want = latent.absorbed_reference(q, cache, offsets, SCALE, RANK)
    assert got.dtype == q.dtype
    tol = 2e-6 if dtype == "float32" else 2e-2
    assert np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max() < tol


def test_rows_that_hold_3_of_32_blocks_run_3_steps_a_row():
    """The grid's bound and the two tables, not a time: 32 rows whose contexts
    end in their third block of a cache of 32 blocks make 96 steps, a row's
    blocks one after another."""
    rows, block, cache_len = 32, 64, 2048
    lengths = jnp.asarray(np.random.default_rng(1).integers(2 * block + 1, 3 * block + 1, rows))
    row_of, block_of, steps = latent.block_table(lengths, block, rows * (cache_len // block))
    assert int(steps) == 3 * rows and row_of.shape == block_of.shape == (rows * 32,)
    assert np.array_equal(np.asarray(row_of)[:96], np.repeat(np.arange(rows), 3))
    assert np.array_equal(np.asarray(block_of)[:96], np.tile(np.arange(3), rows))
    # and that count is the kernel's grid: one bound, traced, the call's first operand
    q = jnp.zeros((rows, 16, 128), jnp.float32)
    cache = jnp.zeros((rows, cache_len, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, c, n: latent.decode_kernel(
        q, c, n, 1.0, 128, block=block, interpret=True))(q, cache, lengths)
    call, = (e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
    mapping = call.params["grid_mapping"]
    assert len(mapping.grid) == 1 and mapping.num_dynamic_grid_bounds == 1
    assert call.invars[0].aval.shape == () and call.invars[1].aval.shape == (rows * 32,)


@pytest.mark.parametrize("lengths, steps", [
    ([1, 1, 1, 1], 4), ([32, 33, 64, 65], 1 + 2 + 2 + 3), ([256, 256, 256, 256], 32),
    ([1, 256, 1, 256], 18)])
def test_the_schedule_counts_the_blocks_that_exist(lengths, steps):
    row_of, block_of, total = latent.block_table(jnp.asarray(lengths, jnp.int32), BLOCK,
                                                 B * (L // BLOCK))
    assert int(total) == steps
    visited = list(zip(np.asarray(row_of)[:steps].tolist(), np.asarray(block_of)[:steps].tolist()))
    assert visited == [(row, j) for row, n in enumerate(lengths) for j in range(-(-n // BLOCK))]


def test_a_block_that_does_not_tile_the_cache_is_refused(operands):
    q, cache = operands
    with pytest.raises(ValueError, match="tiles a cache"):
        latent.decode_kernel(q, cache, jnp.ones((B,), jnp.int32), SCALE, RANK, block=48)


@pytest.mark.parametrize("cache_len, block", [(32768, 2048), (4096, 2048), (2048, 1024),
                                              (64, 32), (3072, 1024), (1, 0)])
def test_the_block_follows_the_cache(cache_len, block):
    assert latent.absorbed_block(cache_len) == block
    lengths = jnp.asarray([1, cache_len], jnp.int32)
    read = latent.positions_read((2, cache_len, 640), 512, lengths, "ragged")
    assert read.tolist() == ([min(block, cache_len), cache_len] if block else [cache_len] * 2)
