"""Weight-only int8 quantization for serving.

Symmetric per-output-channel int8: ``w ≈ q * scale[:, None]`` with
``q ∈ [-127, 127]``. The matmul stays on the MXU in the activation dtype —
``y = (x @ q.T) * scale`` — so the only change is HALF the weight bytes in
HBM (and over the host->device link at load time); the per-channel scale
multiply fuses into the matmul's epilogue under XLA.

Scales are per *output* channel, so any sharding of the input (contraction)
dimension keeps the math exact across devices: partial products psum before
the channel scale, which is constant per channel.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np

# weights worth quantizing: the big attention + mlp matmuls ([out, in]
# torch layout), including phi3's FUSED qkv_proj/gate_up_proj (per-row
# scales slice exactly with the rows, so the un-fusing views stay correct
# — see models/phi3._slice_rows). Anchored on the preceding dot so the
# fused names match by intent, not by suffix accident. Embeddings/norms/
# expert stacks stay full precision (gathers and einsums, not nn.linear
# matmuls).
DEFAULT_ELIGIBLE = re.compile(
    r"(\.(q|k|v|o|qkv)_proj|\.(gate|up|gate_up|down)_proj|(^|\.)lm_head)"
    r"\.weight$"
)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """int8 weight + per-output-channel scale; drop-in for a 2-D weight in
    ops.nn.linear. Registered for jax.export serialization below so AOT
    programs over quantized params persist in the dl/aot_cache."""

    q: jax.Array  # int8 [out, in]
    scale: jax.Array  # f32 [out]

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.scale.dtype

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


try:  # auxdata is always None (pure pair pytree); empty-bytes round-trip
    jax.export.register_pytree_node_serialization(
        QTensor,
        serialized_name="modelx_tpu.ops.quant.QTensor",
        serialize_auxdata=lambda aux: b"",
        deserialize_auxdata=lambda b: None,
    )
except ValueError:  # double registration (module re-imported under a new name)
    pass


def _native_quant(w, scales=None, want_q: bool = True):
    """The native fused kernel (modelx_io.cc mx_quantize_rows) when the
    engine + dtype allow, else None. One GIL-free pass replaces several
    numpy passes — decisive for bfloat16 sources, whose ml_dtypes ufuncs
    are generic element loops (int8 host quantize can cost more than the
    link bytes it saves when cores are few)."""
    try:
        from modelx_tpu import native

        return native.quantize_rows(w, scales=scales, want_q=want_q)
    except ImportError:
        return None


def channel_scales(w: np.ndarray) -> np.ndarray:
    """Per-output-channel symmetric scale (f32 [out]) for an [out, in] weight."""
    got = _native_quant(w, want_q=False)
    if got is not None:
        return got[1]
    w32 = np.asarray(w, np.float32)
    amax = np.max(np.abs(w32), axis=1)
    return (amax / 127.0 + (amax == 0)).astype(np.float32)  # avoid /0 for zero rows


def quantize_rows(w: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """int8 rows of an [out_rows, in] slice given those rows' scales.
    Multiplies by the f32 reciprocal (not a divide): bit-identical to the
    native kernel, so sharded/native/fallback loads of the same checkpoint
    produce the same q bytes."""
    got = _native_quant(w, scales=scale)
    if got is not None:
        return got[0]
    w32 = np.asarray(w, np.float32)
    inv = (np.float32(1.0) / np.asarray(scale, np.float32))[:, None]
    return np.clip(np.rint(w32 * inv), -127, 127).astype(np.int8)


def quantize_fused(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(q int8, scales f32) in one pass over ``w`` when the rows' local
    absmax IS the global per-channel scale (inner dims unsharded — the
    loader's common case). Identical results to channel_scales +
    quantize_rows, but the native path reads the source once."""
    got = _native_quant(w)
    if got is not None:
        return got
    scale = channel_scales(w)
    return quantize_rows(w, scale), scale


def quantize(w: np.ndarray) -> QTensor:
    """Host-side quantize of a full [out, in] weight (tests / serve-time)."""
    q, scale = quantize_fused(np.ascontiguousarray(w))
    return QTensor(q=jnp.asarray(q), scale=jnp.asarray(scale))


def dequantize(t: QTensor, dtype=jnp.float32) -> jax.Array:
    return (t.q.astype(jnp.float32) * t.scale[:, None]).astype(dtype)
