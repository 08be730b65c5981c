"""Grouped-query attention in ``attention_reference``: the query heads fold
over their KV head and contract against k/v as they lie — no KV tensor larger
than the cache exists. The oracle is the repeat-based form that left the
function; with equal head counts the function must still trace exactly it."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from modelx_tpu.models import mixtral
from modelx_tpu.ops.attention import NEG_INF, attention_reference

B, K = 2, 48  # rows, keys (a cache longer than any query's position)
OFFSETS = {"zero": 0, "scalar": 7, "per_row": (3, 20)}
VARIANTS = {
    "plain": {},
    "window": {"window": 5},
    "softcap_scale": {"logit_softcap": 30.0, "scale": 0.17},
}


def repeat_oracle(q, k, v, q_offset=0, scale=None, logit_softcap=0.0, window=0):
    """The function as it was: unfold the KV heads, then plain attention."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    off = jnp.asarray(q_offset)
    qpos = jnp.arange(q.shape[2])[:, None] + (off[:, None, None, None] if off.ndim else off)
    kpos = jnp.arange(k.shape[2])[None, :]
    visible = kpos <= qpos
    if window > 0:
        visible = visible & (kpos > qpos - window)
    probs = jax.nn.softmax(jnp.where(visible, logits, NEG_INF), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _qkv(hq, hkv, qlen, dtype):
    rng = np.random.RandomState(hq * 100 + hkv * 10 + qlen)
    mk = lambda h, s: jnp.asarray(rng.randn(B, h, s, 16), dtype)
    return mk(hq, qlen), mk(hkv, K), mk(hkv, K)


def _offset(kind):
    off = OFFSETS[kind]
    return jnp.asarray(off, jnp.int32) if isinstance(off, tuple) else off


@pytest.mark.parametrize("qlen", [1, 16], ids=["decode", "prefill"])
@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("offset", OFFSETS)
@pytest.mark.parametrize("heads", [(8, 8), (8, 2), (8, 1)], ids=lambda h: f"{h[0]}q{h[1]}kv")
def test_values_match_the_repeat_oracle(heads, offset, variant, qlen):
    """float32 to 1e-5; bf16 to ONE ulp of the output — the grouped
    contraction may sum in another order than the repeated one, so the
    contract is a tolerance, not bit-equality (on a CPU: equal at these
    sizes, one ulp = 0.00049 apart at Mixtral's 32/8 heads over 2048 keys)."""
    kw = dict(q_offset=_offset(offset), **VARIANTS[variant])
    for dtype in (jnp.float32, jnp.bfloat16):
        q, k, v = _qkv(*heads, qlen, dtype)
        got = attention_reference(q, k, v, **kw)
        want = repeat_oracle(q, k, v, **kw)
        assert got.shape == want.shape == q.shape and got.dtype == want.dtype == dtype
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
        else:  # bf16 keeps 8 significant bits: ulp(x) = 2**(floor(log2|x|) - 7)
            mag = np.maximum(np.maximum(np.abs(got), np.abs(want)), 2.0 ** -20)
            ulp = 2.0 ** (np.floor(np.log2(mag)) - 7)
            assert np.all(np.abs(got - want) <= ulp), float(np.max(np.abs(got - want) / ulp))


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (pjit, scan, custom_jvp) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _mixtral_decode_holds_no_unfolded_cache(offset):
    """Walk the tiny Mixtral's cached decode step: nothing of the cache's
    dtype may be larger than one cache leaf (the repeat made one G-fold)."""
    cfg = mixtral.MixtralConfig.tiny()
    assert cfg.num_kv_heads < cfg.num_heads
    rows, max_len = 4, 512
    params = jax.eval_shape(lambda: mixtral.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: mixtral.init_kv_cache(cfg, rows, max_len))
    tok = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    off = jax.ShapeDtypeStruct((rows,) if offset == "per_row" else (), jnp.int32)
    step = lambda p, t, c, o: mixtral.forward(p, t, cfg, kv_cache=c, cache_offset=o)
    jaxpr = jax.make_jaxpr(step)(params, tok, cache, off).jaxpr
    leaf = cache["k0"]
    big = [
        (eqn.primitive.name, var.aval.shape)
        for eqn in _eqns(jaxpr) for var in eqn.outvars
        if getattr(var.aval, "dtype", None) == leaf.dtype and var.aval.size > leaf.size
    ]
    assert not big, big


def _equal_heads_trace_the_oracle(offset):
    """Hq == Hkv: primitive for primitive the oracle's program (no group axis,
    no extra reshape) — what keeps an MHA model's compiled programs, and its
    persistent-cache keys, what they were."""
    sig = lambda f, kw: [
        (eqn.primitive.name, [str(v.aval) for v in eqn.outvars])
        for eqn in _eqns(jax.make_jaxpr(lambda q, k, v: f(q, k, v, **kw))(
            *_qkv(8, 8, 1, jnp.bfloat16)).jaxpr)
    ]
    for variant in VARIANTS.values():
        kw = dict(q_offset=_offset(offset), **variant)
        assert sig(attention_reference, kw) == sig(repeat_oracle, kw)


@pytest.mark.parametrize("offset", ["scalar", "per_row"])
@pytest.mark.parametrize(
    "check", [_mixtral_decode_holds_no_unfolded_cache, _equal_heads_trace_the_oracle],
    ids=["mixtral_decode_holds_no_unfolded_cache", "equal_heads_trace_the_oracle"])
def test_shapes(check, offset):
    check(offset)
