"""Compile the main path's kernels for a DESCRIBED TPU (v5e 2x2), no chip.

The TPU compiler is installed wherever jax[tpu] is; it compiles for a
topology that is described and not attached, and raises what the chip's
compiler would raise — a block the tiling refuses, a kernel GSPMD cannot
partition. Interpret-mode tests cannot see either. Kernel level only
(about two seconds each); nothing runs, so nothing here is a device number.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from modelx_tpu.ops import attention as attn
from modelx_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # an entry written for a described device cannot be read back without a
    # chip: keep the persistent cache out of these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _mosaic_calls(text: str) -> dict:
    """Kernel name -> its Mosaic calls in a compiled program's text (the
    compiler names the instruction for the kernel: ``%kv_write_rows.3 = ``)."""
    calls: dict = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.search(r"%([A-Za-z_]\w*?)(?:\.\d+)* = ", line).group(1)
            calls[name] = calls.get(name, 0) + 1
    return calls


def _qkv(sharding, seq, heads=32, kv_heads=8, head_dim=128, batch=1):
    q = jax.ShapeDtypeStruct((batch, heads, seq, head_dim), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((batch, kv_heads, seq, head_dim), jnp.bfloat16, sharding=sharding)
    return q, kv, kv


@pytest.mark.parametrize("seq", [512, 2048, 144, 5])
def test_flash_kernel_at_8b_widths(topo, seq):
    """Llama-3-8B attention geometry (GQA 32/8, head_dim 128): the kernel
    compiles for the chip at block-multiple lengths, at a 16-bucketed
    ragged one (144 pads to 256 inside the kernel call), and at raw
    /v1/forward lengths that are not a multiple of the row tile (Mosaic
    refused those: 'cannot statically prove that index in dimension 1 is a
    multiple of 8')."""
    args = _qkv(SingleDeviceSharding(topo.devices[0]), seq)
    text = attn.flash_attention.lower(*args, causal=True).compile().as_text()
    assert "tpu_custom_call" in text


def test_gemma2_softcap_window_variant(topo):
    """gemma2-9b: head_dim 256, GQA 16/8, logit softcap 50, window 4096."""
    args = _qkv(SingleDeviceSharding(topo.devices[0]), 512, heads=16,
                kv_heads=8, head_dim=256)
    text = attn.flash_attention.lower(
        *args, causal=True, scale=256 ** -0.5, logit_softcap=50.0, window=4096,
    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("spec,batch,in_spec", [
    ("dp=1,tp=4", 1, P("dp", "tp")),   # the README's tensor-parallel serving
    ("dp=4", 1, P()),                  # the default mesh, one request: replicated
    ("dp=2,tp=2", 2, P("dp", "tp")),
])
def test_flash_kernel_under_a_mesh(topo, spec, batch, in_spec):
    """The documented multi-chip meshes: GSPMD cannot partition a Mosaic
    kernel ('Mosaic kernels cannot be automatically partitioned. Please
    wrap the call in a shard_map'), so the call is shard_mapped — heads
    over tp, batch over dp — and the kernel is still in the program."""
    mesh = make_mesh(spec, devices=topo.devices)
    args = _qkv(NamedSharding(mesh, in_spec), 512, batch=batch)
    text = attn.flash_attention.lower(*args, causal=True, mesh=mesh).compile().as_text()
    assert "tpu_custom_call" in text


def test_bare_kernel_under_a_mesh_is_refused(topo):
    """What this file guards against: the same call WITHOUT the mesh
    argument, on sharded inputs, is what the chip's compiler refuses."""
    mesh = make_mesh("dp=1,tp=4", devices=topo.devices)
    args = _qkv(NamedSharding(mesh, P("dp", "tp")), 512)
    with pytest.raises(Exception, match="(?i)mosaic|shard_map|partition"):
        attn.flash_attention.lower(*args, causal=True).compile()


@pytest.mark.parametrize("heads,window,seq", [(48, 0, 640), (72, 512, 640), (72, 512, 64)])
def test_flash_kernel_at_lagunas_head_counts_and_window(topo, heads, window, seq):
    """laguna-s-2.1: 48 (full) and 72 (sliding, window 512) query heads over
    8 KV heads of 128 — what ``/v1/forward`` compiles, a row longer than the
    window included."""
    args = _qkv(SingleDeviceSharding(topo.devices[0]), seq, heads=heads)
    text = attn.flash_attention.lower(*args, causal=True, window=window).compile().as_text()
    assert "tpu_custom_call" in text


def test_lagunas_decode_step_fits_one_chip_with_a_cache_per_layer_kind(topo):
    """The benchmark's configuration at its published widths — 128 of 256
    experts held, 64 slots of 4096 positions: one decode step over the
    per-kind state (full layers whole, window layers as rings of 528)
    compiles for the chip and, weights and state included, stays under the
    chip's 15.75 GiB; with ``[slots, max_len]`` for all five layers the
    arguments alone would not. Shapes only: nothing is allocated or run."""
    import json

    from modelx_tpu.models import laguna

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "laguna-s-2.1-ep2-d5.json")) as f:
        cfg = laguna.config_from_hf(json.load(f))
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    params = {k: sds(v, jnp.bfloat16) for k, v in laguna.param_shapes(cfg).items()}
    state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: laguna.init_layer_state(cfg, 64, 4096)))

    def step(params, state, tok, offsets):
        logits, state = laguna.forward(params, tok, cfg, kv_cache=state,
                                       cache_offset=offsets, ring=True)
        return state, jnp.argmax(logits[:, -1], -1)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, state, sds((64, 1), jnp.int32), sds((64,), jnp.int32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    weights_and_kv = 2 * 5_572_076_544 + 2_562_719_744  # the configuration's bytes_predicted
    assert weights_and_kv <= m.argument_size_in_bytes < weights_and_kv + 4096  # + tok, offsets, counters
    assert live < 15.75 * 2**30 - 1.5e9  # room for an admission's scratch beside it
    uniform = 2 * 5_572_076_544 + 5 * 2 * 64 * 4096 * 8 * 128 * 2
    assert uniform > 15.75 * 2**30 - 1.5e9


def minicpm_sala_cell(topo):
    """The ``minicpm-sala-d12`` configuration as the benchmark runs it -> (its
    file, the config, the weights as shapes on one described device, ``sds``)."""
    import json

    from modelx_tpu.models import minicpm_sala as sala

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "minicpm-sala-d12.json")) as f:
        raw = json.load(f)
    cfg = sala.config_from_hf(raw)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    params = {k: sds(v, jnp.bfloat16) for k, v in sala.param_shapes(cfg).items()}
    return raw, cfg, params, sds


def test_minicpm_salas_decode_step_fits_one_chip_and_re_lays_no_cache_leaf(topo):
    """The benchmark's configuration at its published widths — layers 9-20 of
    32, 32 slots of 32,768 positions: the decode step over keys, values,
    compressed keys and lightning states compiles for the chip and, weights
    and state included, stays under the chip's 15.75 GiB. And it copies no
    ``[slots, max_len]`` leaf: with 2 KV heads laid as ``[B, L, 2, 128]`` the
    compiler kept the heads outermost, and the block gather's reshape, the
    per-row write (a scatter) and the compressed key's window (a gather) each
    re-laid every leaf whole in every step — 23 of a 45 ms step on the chip
    (my chip run, PR 35). Shapes only: nothing is allocated or run."""
    import re

    from modelx_tpu.models import minicpm_sala as sala

    raw, cfg, params, sds = minicpm_sala_cell(topo)
    state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: sala.init_layer_state(cfg, 32, 32768)))

    def steps(params, state, tok, offsets, live):
        def one_step(carry, _):
            state, tok, offsets = carry
            logits, state = sala.forward(params, tok, cfg, kv_cache=state, cache_offset=offsets,
                                         live=live)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            return (state, nxt, offsets + 1), nxt
        (state, tok, _), toks = jax.lax.scan(one_step, (state, tok, offsets), None, length=2)
        return state, toks

    compiled = jax.jit(steps, donate_argnums=(1,)).lower(
        params, state, sds((32, 1), jnp.int32), sds((32,), jnp.int32), sds((32,), jnp.bool_)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    predicted = raw["bytes_predicted"]
    assert predicted["sum"] == 11_785_885_696
    assert predicted["sum"] <= m.argument_size_in_bytes < predicted["sum"] + 16384  # + tok, offsets, counters
    assert live < 15.75 * 2**30 - 2.5e9  # room for the probe's cache-less forward beside it
    text = compiled.as_text()
    relaid = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[32,32768,256\]\S* (copy|transpose)\(", line)]
    assert not relaid, relaid
    assert "bf16[32,32768,256]" in text  # the leaves are there under that shape


# -- the sparse layers' one-token kernel (ops.sparse_attention) ----------------


def test_sparse_decode_kernel_at_the_cells_widths(topo):
    """Mosaic takes the kernel at the ``.longctx`` cell's shapes — 32 rows, 32
    query / 2 KV heads of 128, rows of 32,768 positions laid ``[B, L, 256]``,
    64 blocks of 64 a KV head: a head's 128 lanes of a block sliced out of the
    cache where it lies (a dynamic lane offset), 4 MB of double-buffered
    scratch. The caches stay where they are: no temporary of a block's size."""
    from modelx_tpu.ops import sparse_attention as sparse

    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    kv = sds((32, 32768, 256), jnp.bfloat16)
    compiled = jax.jit(lambda *a: sparse.decode_attention_kernel(*a, sparse.SparseSpec())).lower(
        sds((32, 32, 128), jnp.bfloat16), kv, kv, sds((32, 2, 64), jnp.int32),
        sds((32,), jnp.int32)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text and "sparse_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**21  # the positions' places: 1 MB


def test_minicpm_salas_chunk_program_reads_the_selected_blocks_in_the_kernel(topo, monkeypatch):
    """The engine's OWN depth-4 chunk program at the cell's size (32 slots of
    32,768 positions, ``--prefill-chunk 2048``), the rule steered to a TPU
    (the compile runs where ``default_backend`` says cpu): one Mosaic call a
    sparse layer under ``sala.sparse.attend``, no gather there — the six
    ``bf16[4096,64,256]`` gathers of 268 MB were 16 % of the device's time
    (ledger, PR 35); the shape survives only in the dense branch's ``cond``,
    the rows' fronts below ``dense_len`` — and still no cache leaf re-laid."""
    import re
    import types

    from modelx_tpu.dl.continuous import ContinuousBatcher
    from modelx_tpu.dl.families import FAMILIES

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, cfg, params, sds = minicpm_sala_cell(topo)
    server = types.SimpleNamespace(
        family=FAMILIES["minicpm_sala"], cfg=cfg, mesh=make_mesh("dp=1", [topo.devices[0]]),
        params=params, max_seq_len=32768, stats={})
    engine = ContinuousBatcher(server, max_slots=32, chunk_size=8, max_len=32768,
                               prefill_chunk=2048, allocate=False, supervise=False)
    try:
        tok = sds((32, 1), jnp.int32)
        compiled = engine._chunk_prog.jit.lower(
            params, engine.kv.abstract_state(), tok, *engine._chunk_args(False),
            n_steps=32).compile()
    finally:
        engine.close()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    assert all("sala.sparse.attend" in c and "sparse_decode_attention" in c for c in calls)
    gathers = [line for line in text.splitlines()
               if re.search(r"bf16\[(32,2,64,64,256|4096,64,256)\]\S* gather\(", line)]
    assert gathers and all("sala.attn.dense" in g for g in gathers), gathers
    relaid = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[32,32768,256\]\S* (copy|transpose)\(", line)]
    assert not relaid, relaid


# -- the ragged decode kernel (ops.attention.decode_attention) ----------------

# (rows, query heads, cache length): the decode cells' full-attention layers
DECODE_SHAPES = {"laguna_full_layer": (64, 48, 4096), "mixtral": (32, 32, 2048)}


def _decode_args(one, rows, heads, cache_len):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    kv = sds((rows, cache_len, 8, 128), jnp.bfloat16)
    return sds((rows, 1, heads, 128), jnp.bfloat16), kv, kv, sds((rows,), jnp.int32)


@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_ragged_decode_kernel_at_the_cells_widths(topo, shape):
    """Mosaic takes the kernel at both cells' shapes (48 and 32 query heads
    over 8 KV heads of 128, 256-position blocks of the cache viewed as
    ``[rows, L * 8, 128]``), and that view costs nothing: no temporary of a
    cache leaf's size exists in the program."""
    rows, heads, cache_len = DECODE_SHAPES[shape]
    args = _decode_args(SingleDeviceSharding(topo.devices[0]), rows, heads, cache_len)
    compiled = jax.jit(attn.decode_attention).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_ring_decode_kernel_at_the_cells_widths(topo):
    """Mosaic takes the ring kernel at the ``.reason`` cell's window layers
    (64 rings of 528 positions of 8 KV heads of 128 under 72 query heads: the
    whole ring one block of 4,224 lines, keys and values double-buffered
    beside ``[80, 4224]`` float32 logits, inside Mosaic's own VMEM limit), the
    scalar modulo included; the rings reach it by bitcast — no temporary."""
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    ring = sds((64, 528, 8, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, n: attn.ring_decode_attention(q, k, v, n, 512)).lower(
        sds((64, 1, 72, 128), jnp.bfloat16), ring, ring, sds((64,), jnp.int32)).compile()
    assert _mosaic_calls(compiled.as_text()) == {"ring_decode_attention": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.slow
@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_ragged_decode_kernel_inside_a_scan_reads_the_cache_as_it_lies(topo, shape):
    """As the engine's chunk program holds it: a ``lax.scan`` whose step
    writes each row's new key and value into the donated caches and then
    attends. The caches reach the kernel by bitcast — no copy, no leaf-sized
    temporary — and leave the program aliased to their inputs."""
    rows, heads, cache_len = DECODE_SHAPES[shape]
    q, k, v, offsets = _decode_args(SingleDeviceSharding(topo.devices[0]), rows, heads, cache_len)

    def chunk(q, k, v, offsets):
        write = jax.vmap(lambda c, u, o: jax.lax.dynamic_update_slice(c, u, (o, 0, 0)))

        def step(carry, _):
            q, k, v, offsets = carry
            k, v = write(k, q[:, :, :8], offsets), write(v, q[:, :, 8:16], offsets)
            out = attn.decode_attention(q, k, v, offsets + 1)
            return ((q + out).astype(q.dtype), k, v, offsets + 1), None

        return jax.lax.scan(step, (q, k, v, offsets), None, length=8)[0]

    compiled = jax.jit(chunk, donate_argnums=(1, 2)).lower(q, k, v, offsets).compile()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in text
    leaf = rows * cache_len * 8 * 128 * 2
    assert m.temp_size_in_bytes < 2**20 and m.alias_size_in_bytes == 2 * leaf
    operands = [line for line in text.splitlines() if f"bf16[{rows},{cache_len * 8},128]" in line
                and "= bf16" in line and "custom-call" not in line]
    assert operands and all(" bitcast(" in line for line in operands)


@pytest.mark.slow
def test_lagunas_decode_step_takes_the_ragged_kernel_on_its_full_layers_only(topo, monkeypatch):
    """The cell's decode step with the rules steered to a TPU (the compile
    runs where ``default_backend`` says cpu): by the kernels' names, two
    ragged attention calls, one a full layer, three ring attention calls, one
    a window layer, ten cache writes, one a leaf, and the hit experts' kernel
    on each of the four expert layers; the f32 logits over all 4096 positions
    and over the rings' 528 are gone from the program."""
    import json

    from modelx_tpu.models import laguna

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "laguna-s-2.1-ep2-d5.json")) as f:
        cfg = laguna.config_from_hf(json.load(f))
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    params = {k: sds(v, jnp.bfloat16) for k, v in laguna.param_shapes(cfg).items()}
    state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: laguna.init_layer_state(cfg, 64, 4096)))

    def step(params, state, tok, offsets):
        with attn.ragged_calls() as calls:
            logits, state = laguna.forward(params, tok, cfg, kv_cache=state,
                                           cache_offset=offsets, ring=True)
        assert calls == [(256, 4096), (256, 4096)]
        return state, jnp.argmax(logits[:, -1], -1)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, state, sds((64, 1), jnp.int32), sds((64,), jnp.int32)).compile()
    text = compiled.as_text()
    assert _mosaic_calls(text) == {"ragged_decode_attention": 2, "ring_decode_attention": 3,
                                   "kv_write_rows": 10,
                                   "moe_hit_experts": cfg.mlp_layer_types.count("sparse")}
    assert cfg.mlp_layer_types.count("sparse") == 4
    assert "f32[64,8,6,4096]" not in text and "f32[64,8,9,528]" not in text


# -- the decode step's per-row cache write (ops.kv_write.write_rows_kernel) ------

# (slots, positions) of a written leaf: Laguna's full layers, its rings, Mixtral's layers
WRITE_SHAPES = {"laguna_full_layer": (64, 4096), "laguna_ring": (64, 528), "mixtral": (32, 2048)}


@pytest.mark.parametrize("shape", WRITE_SHAPES)
def test_kv_write_kernel_at_the_cells_widths(topo, shape):
    """Mosaic takes the kernel at the cells' leaves (8 KV heads of 128: one
    position is one 2 KB tile row of ``[B, L, 8, 128]``), the leaf stays
    where it lies — aliased to the output, no temporary at all."""
    from modelx_tpu.ops import kv_write

    rows, length = WRITE_SHAPES[shape]
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    compiled = jax.jit(kv_write.write_rows_kernel, donate_argnums=(0,)).lower(
        sds((rows, length, 8, 128), jnp.bfloat16), sds((rows, 1, 8, 128), jnp.bfloat16),
        sds((rows,), jnp.int32)).compile()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert 'custom_call_target="tpu_custom_call"' in text and "kv_write_rows" in text
    assert m.temp_size_in_bytes == 0 and m.alias_size_in_bytes == rows * length * 8 * 128 * 2


def _cell_engine(topo, family: str):
    """The ``.decode`` / ``.reason`` cell's engine over shapes on one described
    device -> (engine, params, slots, cache positions, written leaves a step)."""
    import json
    import types

    from modelx_tpu.dl.continuous import ContinuousBatcher
    from modelx_tpu.dl.families import FAMILIES
    from modelx_tpu.models import laguna, mixtral

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = {"mixtral": "mixtral-8x7b-d4", "laguna": "laguna-s-2.1-ep2-d5"}[family]
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        raw = json.load(f)
    if family == "laguna":
        cfg, slots, max_len = laguna.config_from_hf(raw), 64, 4096
        shapes, leaves = laguna.param_shapes(cfg), 2 * cfg.num_layers
    else:
        cfg = mixtral.MixtralConfig(
            vocab_size=raw["vocab_size"], hidden_size=raw["hidden_size"],
            intermediate_size=raw["intermediate_size"], num_layers=raw["num_hidden_layers"],
            num_heads=raw["num_attention_heads"], num_kv_heads=raw["num_key_value_heads"],
            head_dim=raw["head_dim"], num_experts=raw["num_local_experts"],
            top_k=raw["num_experts_per_tok"], rope_theta=raw["rope_theta"])
        slots, max_len = 32, 2048
        shapes, leaves = mixtral.param_shapes(cfg), 2 * cfg.num_layers
    one = SingleDeviceSharding(topo.devices[0])
    params = {k: jax.ShapeDtypeStruct(v, jnp.bfloat16, sharding=one) for k, v in shapes.items()}
    server = types.SimpleNamespace(
        family=FAMILIES[family], cfg=cfg, mesh=make_mesh("dp=1", [topo.devices[0]]),
        params=params, max_seq_len=max_len, stats={})
    engine = ContinuousBatcher(server, max_slots=slots, chunk_size=8, max_len=max_len,
                               allocate=False, supervise=False)
    return engine, params, slots, max_len, leaves


# temporaries of the parent commit's depth-1 chunk program, compiled the same
# way (PR 41, no chip): what the change's must not exceed
PARENT_CHUNK_TEMP = {"mixtral": 4_906_496, "laguna": 11_425_792}


@pytest.mark.parametrize("family", ["mixtral", "laguna"])
def test_the_cells_chunk_program_writes_every_leaf_in_the_kernel_in_place(topo, monkeypatch, family):
    """The engine's OWN chunk program of the ``.decode`` and ``.reason`` cells,
    the rule steered to a TPU (the compile runs where ``default_backend`` says
    cpu): one ``kv_write_rows`` call a written leaf — eight, and ten with
    Laguna's rings — beside the ragged attention's; the one ``while`` left is
    the scan (the parent's had one of ``slots`` trips a leaf around a 2 KB
    update: the scatter), and in Laguna's the hit experts' kernel on its four
    expert layers and ``ring_decode_attention`` on its three window layers
    (PR 48: the layout counts them from the same rule); no cache leaf is
    copied, transposed or, a ring whole, prefetched to another memory space
    and back (the ``copy-start`` / ``copy-done`` pairs the reference's operand
    cost, six a step); the state leaves the program aliased to its input;
    temporaries not above the parent's (but for Laguna's routed sum, a float32
    ``[64, 3072]``)."""
    import math
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine, params, slots, max_len, leaves = _cell_engine(topo, family)
    try:
        assert engine.kv.row_writes == (leaves, leaves)
        if family == "laguna":
            assert engine.kv.ring_reads == (3, 3)
        state = engine.kv.abstract_state()
        tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=SingleDeviceSharding(topo.devices[0]))
        compiled = engine._chunk_prog.jit.lower(
            params, state, tok, *engine._chunk_args(False), n_steps=8).compile()
    finally:
        engine.close()
    text, m = compiled.as_text(), compiled.memory_analysis()
    calls = _mosaic_calls(text)
    # Mixtral's expert layer is ``moe_ffn``: no hit-experts kernel there
    laguna = {"moe_hit_experts": 4, "ring_decode_attention": 3} if family == "laguna" else {}
    assert calls == {"kv_write_rows": leaves, "ragged_decode_attention": leaves // 2 - (
        3 if family == "laguna" else 0), **laguna}
    assert len([line for line in text.splitlines() if " while(" in line]) == 1
    scattered = [line.strip()[:120] for line in text.splitlines()
                 if "scatter" in line and re.search(rf"bf16\[{slots},(\d\d\d+),8,128\]", line)]
    assert not scattered, scattered
    copied = [line.strip()[:120] for line in text.splitlines() if re.search(
        rf"bf16\[{slots},(\d\d\d+),8,128\][^=]* (copy|transpose|copy-start|copy-done)\(", line)]
    assert not copied, copied
    kv_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(state) if len(x.shape) == 4)
    assert kv_bytes <= m.alias_size_in_bytes < kv_bytes + 4096  # + tok, counters
    # the hit experts' kernel hands its float32 sum [64, 3072] to the shared expert's
    # through HBM, where the einsums' fused into it: 0.8 MB beside 14.5 GB
    routed_sum = 64 * 3072 * 4 if family == "laguna" else 0
    assert m.temp_size_in_bytes <= PARENT_CHUNK_TEMP[family] + routed_sum


# -- the expert layer's decode step (ops.moe.hit_experts) ---------------------------

# rows, held experts, F, D -> blocks a matrix (gate and up, down)
EXPERT_SHAPES = {"laguna-s-2.1-ep2-d5.reason": ((64, 128, 1024, 3072), (1, 1)),
                 "deepseek-v2-ep8-d5.longdoc": ((32, 20, 1536, 5120), (2, 2)),
                 "the_most_rows_the_rule_admits": ((256, 20, 1536, 5120), (2, 2)),
                 # experts of two matrices (no gate) in a latent width: up, relu2, down
                 "nemotron-3-super-ep4-d11.agent": ((64, 128, 2688, 1024), (1, 1))}


@pytest.mark.parametrize("cell", EXPERT_SHAPES)
def test_hit_experts_kernel_at_the_cells_widths(topo, cell):
    """Mosaic takes the kernel at both cells' shapes: blocks of whole matrices
    (6.3 MB) for Laguna, of half ones (7.9 MB) for DeepSeek-V2, six of them in
    VMEM under the limit the call asks for; the stacks stay where they lie —
    the program's only temporaries are the scalars' and the combine weights'."""
    from modelx_tpu.ops import moe

    (rows, e, f, d), blocks = EXPERT_SHAPES[cell]
    assert (moe._chunks(f, d * 2), moe._chunks(d, f * 2)) == blocks and rows <= moe.ROWS_MAX
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    gate = None if cell.startswith("nemotron") else sds((e, f, d))
    compiled = jax.jit(moe.hit_experts).lower(
        sds((rows, d)), sds((rows, e), jnp.float32), gate, sds((e, f, d)),
        sds((e, d, f))).compile()
    assert _mosaic_calls(compiled.as_text()) == {"moe_hit_experts": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20  # ... and not a row's more


# -- deepseek_v2: the latent cache, the absorbed kernel, the piece program ----------


def deepseek_v2_cell(topo, monkeypatch, config="deepseek-v2-ep8-d5", slots=32):
    """The ``deepseek-v2-ep8-d5.longdoc`` cell's engine (or, of the same
    module, ``deepseek-v3.2-exp-ep16-d5.sparsedoc``'s) over shapes on one
    described device, the rules steered to a TPU -> (its file, engine, params,
    ``sds``)."""
    import json
    import types

    from modelx_tpu.dl.continuous import ContinuousBatcher
    from modelx_tpu.dl.families import FAMILIES
    from modelx_tpu.models import deepseek_v2

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config + ".json")) as f:
        raw = json.load(f)
    cfg = deepseek_v2.config_from_hf(raw)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    params = {k: sds(v, jnp.bfloat16) for k, v in deepseek_v2.param_shapes(cfg).items()}
    server = types.SimpleNamespace(
        family=FAMILIES["deepseek_v2"], cfg=cfg, mesh=make_mesh("dp=1", [topo.devices[0]]),
        params=params, max_seq_len=32768, stats={})
    engine = ContinuousBatcher(server, max_slots=slots, chunk_size=8, max_len=32768,
                               prefill_chunk=2048, allocate=False, supervise=False)
    return raw, engine, params, sds


def test_the_absorbed_kernel_at_the_cells_widths(topo):
    """Mosaic takes the latent decode kernel at the ``.longdoc`` cell's
    shapes — 32 rows, 128 query heads over ONE line of 640 lanes a position,
    blocks of 2,048 positions on a flat grid whose bound is traced, the values
    the line's first 512 lanes: no second operand, the cache where it lies, a
    temporary for nothing but the two tables of 512 steps."""
    from modelx_tpu.ops import latent_attention as latent

    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    compiled = jax.jit(lambda q, c, o: latent.absorbed(q, c, o, 0.1147, 512, impl="ragged")).lower(
        sds((32, 128, 640), jnp.bfloat16), sds((32, 32768, 640), jnp.bfloat16),
        sds((32,), jnp.int32)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text and "latent_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    assert not [line for line in text.splitlines()
                if re.search(r"= bf16\[32,32768,(1,)?640\]\S* (copy|transpose)\(", line)]


def test_deepseek_v2s_chunk_program_reads_each_latent_line_once_where_it_lies(topo, monkeypatch):
    """The engine's OWN chunk program at the cell's size: one absorbed kernel
    call a layer under ``dsv2.attn.attend``; no latent leaf copied or re-laid
    (``[slots, L, 576]`` the compiler lays with the positions minor: the
    padded 640-lane line is what keeps the leaf as the kernel reads it); no
    per-head keys or values of cached positions (68 GB at this size) and no
    ``[32, 128, 32768]`` score tensor; the state leaves aliased to its input;
    weights and cache are the configuration's ``bytes_predicted``."""
    raw, engine, params, sds = deepseek_v2_cell(topo, monkeypatch)
    try:
        compiled = engine._chunk_prog.jit.lower(
            params, engine.kv.abstract_state(), sds((32, 1), jnp.int32),
            *engine._chunk_args(False), n_steps=8).compile()
    finally:
        engine.close()
    text, m = compiled.as_text(), compiled.memory_analysis()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line
             and "latent_decode_attention" in line]
    assert len(calls) == 5 and all("dsv2.attn.attend" in c for c in calls)
    assert _mosaic_calls(text) == {"latent_decode_attention": 5, "moe_hit_experts": 4}
    relaid = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[32,32768,640\]\S* (copy|transpose)\(", line)]
    assert not relaid, relaid
    assert "bf16[32,32768,640]" in text  # the leaves are there under that shape
    expanded = [line.strip()[:120] for line in text.splitlines()
                if re.search(r"\[32,32768,128,\d+\]|\[32,128,32768(,\d+)?\]", line)]
    assert not expanded, expanded
    predicted = raw["bytes_predicted"]
    assert predicted["sum"] == 2 * 3_145_466_880 + 32 * 32768 * 640 * 2 * 5
    assert predicted["sum"] <= m.argument_size_in_bytes < predicted["sum"] + 16384
    cache = predicted["latent_cache_32_slots_x_32768_positions_x_5_layers"]
    assert cache <= m.alias_size_in_bytes < cache + 4096
    assert m.temp_size_in_bytes < 64 * 2**20  # 16 MB: nothing of a leaf's or the scores' size
    assert len([line for line in text.splitlines() if " while(" in line]) == 1  # the scan


def test_deepseek_v2s_chunk_program_reads_the_hit_experts_where_they_lie(topo, monkeypatch):
    """The same program's expert layers (ISSUE 44): ``moe_hit_experts`` under
    ``dsv2.moe.routed`` on each of the four, its three operands the held
    stacks ``[20, 1536, 5120]`` / ``[20, 5120, 1536]`` as the checkpoint laid
    them — none copied, transposed or re-laid whole — and no ``[20, 32, 1536]``
    activation of every held expert left beside it."""
    _, engine, params, sds = deepseek_v2_cell(topo, monkeypatch)
    try:
        compiled = engine._chunk_prog.jit.lower(
            params, engine.kv.abstract_state(), sds((32, 1), jnp.int32),
            *engine._chunk_args(False), n_steps=8).compile()
    finally:
        engine.close()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line
             and "moe_hit_experts" in line]
    assert len(calls) == 4 and all("dsv2.moe.routed" in c for c in calls)
    assert all(c.count("bf16[20,1536,5120]") >= 2 and "bf16[20,5120,1536]" in c for c in calls)
    moved = [line.strip()[:120] for line in text.splitlines()
             if re.search(r"= bf16\[20,(1536,5120|5120,1536)\]\S* (copy|transpose|fusion)\(", line)]
    assert not moved, moved
    every = [line.strip()[:120] for line in text.splitlines()
             if re.search(r"= bf16\[20,32,1536\]", line)]
    assert not every, every


def test_deepseek_v2s_piece_program_expands_a_key_block_at_a_time(topo, monkeypatch):
    """A 2,048-token piece over the slot's 32,768 positions: keys and values
    are expanded 1,024 positions at a time and scores held a query tile at a
    time, so the temporaries stay near a gigabyte where the whole context's
    per-head keys and values (2.7 GB at 16 k) and ``[128, 2048, 16384]``
    scores (17 GB) would not fit; no latent leaf is copied whole."""
    _, engine, params, sds = deepseek_v2_cell(topo, monkeypatch)
    try:
        compiled = jax.jit(engine._piece_impl, donate_argnums=(2,)).lower(
            params, sds((1, 2048), jnp.int32), engine.kv.abstract_state(), sds((), jnp.int32),
            sds((), jnp.int32)).compile()
    finally:
        engine.close()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert m.temp_size_in_bytes < 1.25 * 2**30
    relaid = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[32,32768,640\]\S* (copy|transpose)\(", line)]
    assert not relaid, relaid
    whole = [line.strip()[:120] for line in text.splitlines()
             if re.search(r"\[1,(32768|16384),128,\d+\]|\[1,128,2048,(32768|16384)\]", line)]
    assert not whole, whole


# -- deepseek_v32: the same module with an indexer — two leaves a layer, the selection --


def test_the_selections_kernels_at_the_cells_widths(topo):
    """Mosaic takes the decode step's two selection kernels at the
    ``.sparsedoc`` cell's shapes: ``dsa_step_scores`` — 16 rows, 64 index heads
    of 128 lanes, blocks of 2,048 keys on ``latent_decode_attention``'s flat
    grid, the leaf where it lies, a chunk of 128 scores a sublane of the output —
    and ``dsa_chosen_mask`` — the ``[16, 256, 128]`` float32 scores, their
    integer keys and the mask resident in VMEM (8 MB with the two buffers of
    a grid's operands) for 47 passes, sixteen rows a step at any number of
    slots; the compaction behind them is XLA's, a one-hot product and no sort."""
    from modelx_tpu.ops import index_select as sel

    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    assert sel.takes_kernel((16, 32768, 640), 512, 2048, "ragged") == (2048, False)
    scores = jax.jit(lambda q, w, k, n: sel.step_scores(q, w, k, n, block=2048)).lower(
        sds((16, 64, 128), jnp.bfloat16), sds((16, 64), jnp.float32),
        sds((16, 32768, 128), jnp.bfloat16), sds((16,), jnp.int32)).compile()
    text = scores.as_text()
    assert _mosaic_calls(text) == {"dsa_step_scores": 1}
    assert scores.memory_analysis().temp_size_in_bytes < 2**20  # the heads' weights a lane each
    assert not [line for line in text.splitlines()
                if re.search(r"= bf16\[16,32768,128\]\S* (copy|transpose)\(", line)]
    select = jax.jit(lambda x, n: sel.compact(sel.chosen_mask(x, n, 2048), 2048)).lower(
        sds((16, 256, 128), jnp.float32), sds((16,), jnp.int32)).compile()
    text = select.as_text()
    assert _mosaic_calls(text) == {"dsa_chosen_mask": 1}
    assert not re.search(r" sort\(", text) and "s32[16,2048]" in text
    assert select.memory_analysis().temp_size_in_bytes < 8 * 2**20
    # thirty-two rows are two steps of sixteen: the same VMEM at any number of slots
    assert sel.mask_group(32, 32768) == 16
    more = jax.jit(lambda x, n: sel.chosen_mask(x, n, 2048)).lower(
        sds((32, 256, 128), jnp.float32), sds((32,), jnp.int32)).compile()
    assert _mosaic_calls(more.as_text()) == {"dsa_chosen_mask": 1}


def test_deepseek_v32s_chunk_program_scores_what_rows_hold_and_selects_without_a_sort(
        topo, monkeypatch):
    """The engine's OWN chunk program at the ``.sparsedoc`` cell's size, 16
    slots of 32,768 positions: every layer scores its ``[16, 32768, 128]``
    index leaf in ``dsa_step_scores`` under ``dsa.score`` (the key blocks a row
    holds), finds its 2,048 positions in ``dsa_chosen_mask`` and a compaction
    under ``dsa.select`` — NO ``sort`` anywhere in the program, no ``[16, 64,
    32768]`` score tensor — gathers ``[16, 2048, 640]`` lines and runs the
    absorbed kernel over THEM under ``dsa.attend`` (five calls each; the four
    expert layers' ``moe_hit_experts`` beside them, at 16 experts of 2,048);
    neither leaf copied or re-laid whole; both leaves aliased to the input;
    weights and cache are the configuration's ``bytes_predicted``. DeepSeek-V2's
    program, two tests up, is what it was: five kernels under
    ``dsv2.attn.attend`` and nothing of these."""
    raw, engine, params, sds = deepseek_v2_cell(topo, monkeypatch, "deepseek-v3.2-exp-ep16-d5", 16)
    try:
        assert sorted(set(engine.kv.kinds.values())) == ["counter", "index", "latent"]
        compiled = engine._chunk_prog.jit.lower(
            params, engine.kv.abstract_state(), sds((16, 1), jnp.int32),
            *engine._chunk_args(False), n_steps=8).compile()
    finally:
        engine.close()
    text, m = compiled.as_text(), compiled.memory_analysis()
    kernels = {name: [line for line in text.splitlines()
                      if 'custom_call_target="tpu_custom_call"' in line
                      and re.search(rf"%{name}[.\d]* = ", line)]
               for name in ("latent_decode_attention", "dsa_step_scores", "dsa_chosen_mask")}
    assert all(len(calls) == 5 for calls in kernels.values())
    assert all("dsa.attend" in c and "bf16[16,2048,640]" in c  # over the gathered lines
               for c in kernels["latent_decode_attention"])
    assert all("dsa.score" in c for c in kernels["dsa_step_scores"])
    assert all("dsa.select" in c for c in kernels["dsa_chosen_mask"])
    assert _mosaic_calls(text) == {"latent_decode_attention": 5, "dsa_step_scores": 5,
                                   "dsa_chosen_mask": 5, "moe_hit_experts": 4}
    # the routers sort their 256 experts in groups; nothing of the selection sorts
    assert not [line for line in text.splitlines()
                if re.search(r" sort\(", line) and ("dsa." in line or "32768" in line)]
    relaid = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[16,32768,(640|128)\]\S* (copy|transpose)\(", line)]
    assert not relaid, relaid
    assert "bf16[16,32768,640]" in text and "bf16[16,32768,128]" in text
    scores = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= f32\[16,64,32768\]", line)]
    assert not scores, scores
    predicted = raw["bytes_predicted"]
    assert predicted["sum"] == 2 * 4_635_518_208 + 16 * 32768 * (640 + 128) * 2 * 5
    assert predicted["sum"] <= m.argument_size_in_bytes < predicted["sum"] + 16384
    cache = predicted["cache_16_slots_x_32768_positions_x_5_layers"]
    assert cache <= m.alias_size_in_bytes < cache + 4096
    assert m.temp_size_in_bytes < 256 * 2**20  # the gathered lines of a layer are 42 MB


def test_deepseek_v32s_piece_program_selects_a_query_tile_at_a_time(topo, monkeypatch):
    """A 2,048-token piece over the slot's 32,768 positions: index scores and
    the bisected threshold 1,024 queries at a time (``[1, 1024, 32768]``
    float32, 134 MB), the mask ``[1, 2048, 32768]`` bool, then V2's expansion
    under it — no ``[64, 2048, 32768]`` scores (17 GB), no sort of ``[2048,
    32768]``, neither leaf copied whole."""
    _, engine, params, sds = deepseek_v2_cell(topo, monkeypatch, "deepseek-v3.2-exp-ep16-d5", 16)
    try:
        compiled = jax.jit(engine._piece_impl, donate_argnums=(2,)).lower(
            params, sds((1, 2048), jnp.int32), engine.kv.abstract_state(), sds((), jnp.int32),
            sds((), jnp.int32)).compile()
    finally:
        engine.close()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert m.temp_size_in_bytes < 1.5 * 2**30
    relaid = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[16,32768,(640|128)\]\S* (copy|transpose)\(", line)]
    assert not relaid, relaid
    whole = [line.strip()[:120] for line in text.splitlines()
             if re.search(r"\[1,2048,64,32768\]|\[1,64,2048,32768\]|f32\[1,2048,32768\]", line)]
    assert not whole, whole
    assert not [line for line in text.splitlines()
                if re.search(r" sort\(", line) and "32768" in line and "dsa." in line]


# -- mimo_v2: lines of a position's heads, sinks, pieces over rings ------------------


def test_the_decode_kernels_at_mimo_v2s_widths(topo):
    """Mosaic takes both decode kernels at the ``.longcode`` cell's leaves —
    lines a position, not whole 128-lane heads: 64 query heads over a full
    layer's 768-lane keys and 512-lane values in blocks of 512 positions, and
    over a window layer's ring of 144 positions of 1,536 and 1,024 lanes with
    the sinks as the softmax's starting state — and the leaves reach them as
    they lie: no temporary."""
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    q, rows = sds((32, 1, 64, 192)), sds((32,), jnp.int32)
    full = jax.jit(attn.decode_attention).lower(
        q, sds((32, 32768, 768)), sds((32, 32768, 512)), rows).compile()
    assert _mosaic_calls(full.as_text()) == {"ragged_decode_attention": 1}
    ring = jax.jit(lambda q, k, v, n, s: attn.ring_decode_attention(q, k, v, n, 128, sinks=s)).lower(
        q, sds((32, 144, 1536)), sds((32, 144, 1024)), rows, sds((64,), jnp.float32)).compile()
    assert _mosaic_calls(ring.as_text()) == {"ring_decode_attention": 1}
    for compiled in (full, ring):
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def mimo_v2_cell(topo, monkeypatch):
    """The ``mimo-v2-flash-ep16-d7.longcode`` cell's engine over shapes on one
    described device, the rules steered to a TPU -> (its file, engine, params, ``sds``)."""
    import json
    import types

    from modelx_tpu.dl.continuous import ContinuousBatcher
    from modelx_tpu.dl.families import FAMILIES
    from modelx_tpu.models import mimo_v2

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "mimo-v2-flash-ep16-d7.json")) as f:
        raw = json.load(f)
    cfg = mimo_v2.config_from_hf(raw)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    params = {k: sds(v, jnp.bfloat16) for k, v in mimo_v2.param_shapes(cfg).items()}
    server = types.SimpleNamespace(
        family=FAMILIES["mimo_v2"], cfg=cfg, mesh=make_mesh("dp=1", [topo.devices[0]]),
        params=params, max_seq_len=32768, stats={})
    engine = ContinuousBatcher(server, max_slots=32, chunk_size=8, max_len=32768,
                               prefill_chunk=2048, allocate=False, supervise=False)
    return raw, engine, params, sds


def test_mimo_v2s_chunk_program_reads_lines_and_rings_where_they_lie(topo, monkeypatch):
    """The engine's OWN chunk program of the ``.longcode`` cell at its
    published widths: the ragged kernel on the two full layers, the ring kernel
    on the five window layers, the hit experts' kernel on the six expert
    layers, the line-write kernel on all fourteen leaves (a line a position is
    one row of a packed tile: a read-modify-write of its group of 16) — the one
    ``while`` left is the scan; no full leaf is copied or transposed (2 x 2.7
    GB a step); weights and cache are what ISSUE 54 predicted to the byte, the
    cache leaves the program aliased to its input, temporaries of a decode
    step stay small."""
    raw, engine, params, sds = mimo_v2_cell(topo, monkeypatch)
    try:
        assert engine.kv.row_writes == (14, 14) and engine.kv.ring_reads == (5, 5)
        state = engine.kv.abstract_state()
        compiled = engine._chunk_prog.jit.lower(
            params, state, sds((32, 1), jnp.int32), *engine._chunk_args(False), n_steps=8).compile()
    finally:
        engine.close()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert _mosaic_calls(text) == {"ragged_decode_attention": 2, "ring_decode_attention": 5,
                                   "moe_hit_experts": 6, "kv_write_rows": 14}
    assert len([line for line in text.splitlines() if " while(" in line]) == 1
    relaid = [line.strip()[:120] for line in text.splitlines() if re.search(
        r"bf16\[32,32768,(768|512)\][^=]* (copy|transpose|copy-start|copy-done)\(", line)]
    assert not relaid, relaid
    predicted = raw["bytes_predicted"]
    assert predicted["sum"] <= m.argument_size_in_bytes < predicted["sum"] + 16384
    assert predicted["kv_bytes"] <= m.alias_size_in_bytes < predicted["kv_bytes"] + 4096
    assert m.temp_size_in_bytes < 64 * 2**20


def test_mimo_v2s_piece_program_attends_a_key_block_at_a_time(topo, monkeypatch):
    """A 2,048-token piece over the slot's 32,768 positions and its unrolled
    rings: 64 heads' scores against 512 keys at a time (268 MB of float32), no
    ``[64, 2048, 32768]`` scores (17 GB), neither full leaf copied whole."""
    _, engine, params, sds = mimo_v2_cell(topo, monkeypatch)
    try:
        where = engine.kv.at(3, 4096, 2048)
        assert isinstance(where, tuple) and engine.kv.stats["kv_ring_pieces"] == 1
        compiled = jax.jit(engine._piece_impl, donate_argnums=(2,)).lower(
            params, sds((1, 2048), jnp.int32), engine.kv.abstract_state(), sds((), jnp.int32),
            tuple(sds((), jnp.int32) for _ in where)).compile()
    finally:
        engine.close()
    text, m = compiled.as_text(), compiled.memory_analysis()
    assert m.temp_size_in_bytes < 2**30
    relaid = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[32,32768,(768|512)\]\S* (copy|transpose)\(", line)]
    assert not relaid, relaid
    whole = [line.strip()[:120] for line in text.splitlines()
             if re.search(r"\[1,4,16,2048,32768\]|\[1,64,2048,32768\]", line)]
    assert not whole, whole
