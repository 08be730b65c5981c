"""Compile the main path's kernels for a DESCRIBED TPU (v5e 2x2), no chip.

The TPU compiler is installed wherever jax[tpu] is; it compiles for a
topology that is described and not attached, and raises what the chip's
compiler would raise — a block the tiling refuses, a kernel GSPMD cannot
partition. Interpret-mode tests cannot see either. Kernel level only
(about two seconds each); nothing runs, so nothing here is a device number.
"""

import os

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
