"""Pipeline parallelism over the ``pp`` mesh axis.

The GPipe-style collective recipe, written the TPU/JAX way rather than as a
torch scheduler: per-layer params are *stacked* along a leading L axis and
sharded over ``pp`` (each rank holds its contiguous block of layers); the
pipeline itself is a ``shard_map`` over ``pp`` in which every step each rank
applies its stage (a ``lax.scan`` over its local layers) and rotates
activations one hop around the ring with ``ppermute`` — neighbor-only ICI
traffic, static shapes, no host scheduler. Microbatches enter at rank 0 and
results drain from the last rank; the loop runs M + P - 1 steps (the
classic bubble). ``lax.fori_loop`` with static bounds lowers to ``scan`` so
the whole pipeline is reverse-differentiable and a pipelined *training*
step works with plain ``jax.grad``.

The reference registry has no model execution at all (SURVEY §2.2); this
module is part of the TPU serve/train path the build brief makes
first-class ("real tp/pp/dp/sp/ep shardings").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from modelx_tpu.models import llama


_ALL_SUFFIXES = llama.LAYER_PARAM_SUFFIXES + llama.BIAS_SUFFIXES


def stack_layer_params(params: dict[str, jax.Array], num_layers: int) -> dict[str, jax.Array]:
    """Fold "model.layers.N.<suffix>" params into stacked [L, ...] arrays
    keyed by suffix (qwen2's optional qkv biases included when present).
    Non-layer params pass through under their own names."""
    out: dict[str, jax.Array] = {
        name: v for name, v in params.items() if not name.startswith("model.layers.")
    }
    for suffix in _ALL_SUFFIXES:
        if f"model.layers.0.{suffix}" not in params:
            continue
        out[suffix] = jnp.stack(
            [params[f"model.layers.{i}.{suffix}"] for i in range(num_layers)]
        )
    return out


def unstack_layer_params(stacked: dict[str, jax.Array], num_layers: int) -> dict[str, jax.Array]:
    """Inverse of stack_layer_params."""
    out = {k: v for k, v in stacked.items() if k not in _ALL_SUFFIXES}
    for suffix in _ALL_SUFFIXES:
        if suffix not in stacked:
            continue
        for i in range(num_layers):
            out[f"model.layers.{i}.{suffix}"] = stacked[suffix][i]
    return out


def stacked_shardings(mesh: Mesh) -> dict[str, NamedSharding]:
    """Shardings for a stacked param dict: layers over pp, per-layer specs
    derived from the canonical rules (so tp layout can't drift). QWEN2_RULES
    is LLAMA_RULES plus the qkv-bias specs; extra entries for params a dict
    doesn't have are simply unused."""
    from modelx_tpu.dl.sharding import QWEN2_RULES, clean_spec, spec_for

    sh = {}
    for name in ("model.embed_tokens.weight", "model.norm.weight", "lm_head.weight"):
        sh[name] = NamedSharding(mesh, clean_spec(spec_for(name, QWEN2_RULES), mesh))
    for suffix in _ALL_SUFFIXES:
        spec = P("pp", *spec_for(suffix, QWEN2_RULES))
        sh[suffix] = NamedSharding(mesh, clean_spec(spec, mesh))
    return sh


def pipeline_forward(
    stacked: dict[str, jax.Array],
    tokens: jax.Array,
    cfg: llama.LlamaConfig,
    mesh: Mesh,
    num_microbatches: int | None = None,
) -> jax.Array:
    """Pipelined llama forward. ``stacked`` from :func:`stack_layer_params`
    (layer arrays sharded over ``pp``). tokens: [B, S]; B must divide by
    num_microbatches (default: pp size). Returns logits [B, S, V]."""
    pp = mesh.shape["pp"]
    m = num_microbatches or pp
    b, s = tokens.shape
    if b % m:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    mb = b // m

    positions = jnp.arange(s)[None, :]  # [1, S]; broadcasts inside _rope
    ctx = llama.ShardingCtx(None)  # inside shard_map: no GSPMD constraints

    x = jnp.take(stacked["model.embed_tokens.weight"], tokens, axis=0).astype(cfg.dtype)
    x_mb = x.reshape(m, mb, s, cfg.hidden_size)

    layer_stack = {k: stacked[k] for k in _ALL_SUFFIXES if k in stacked}

    def stage_scan(local_layers, h):
        def body(h, lp):
            h, _ = llama.decoder_layer(lp, h, positions, cfg, ctx, attention_impl="reference")
            return h, None

        h, _ = jax.lax.scan(body, h, local_layers)
        return h

    def pipelined(local_layers, x_mb):
        rank = jax.lax.axis_index("pp")
        steps = m + pp - 1
        state = jnp.zeros_like(x_mb[0])
        outputs = jnp.zeros_like(x_mb)

        def step(t, carry):
            state, outputs = carry
            feed = x_mb[jnp.minimum(t, m - 1)]
            inp = jnp.where(rank == 0, feed, state)
            out = stage_scan(local_layers, inp)
            # the last rank drains microbatch t-(pp-1) once the fill ends
            idx = t - (pp - 1)
            upd = jax.lax.dynamic_update_slice(
                outputs, out[None], (jnp.maximum(idx, 0), 0, 0, 0)
            )
            take = (idx >= 0) & (rank == pp - 1)
            outputs = jnp.where(take, upd, outputs)
            state = jax.lax.ppermute(out, "pp", [(i, (i + 1) % pp) for i in range(pp)])
            return state, outputs

        _state, outputs = jax.lax.fori_loop(0, steps, step, (state, outputs))
        # results live on the last rank; broadcast around the ring
        return jax.lax.psum(
            jnp.where(rank == pp - 1, outputs, jnp.zeros_like(outputs)), "pp"
        )

    # layers shard over pp; the microbatch's batch dim shards over dp (tp
    # inside the stage would need manual psum in shard_map — the pipelined
    # path composes pp×dp and leaves tp to the GSPMD forward).
    layer_spec = jax.tree.map(lambda _: P("pp"), layer_stack)
    batch_spec = P(None, "dp" if "dp" in mesh.axis_names else None)
    x_mb = shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(layer_spec, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(layer_stack, x_mb)

    x = x_mb.reshape(b, s, cfg.hidden_size)
    x = llama._rms_norm(x, stacked["model.norm.weight"], cfg.rms_eps)
    head = stacked.get("lm_head.weight", stacked["model.embed_tokens.weight"])
    from modelx_tpu.ops.nn import linear as _linear

    return _linear(x, head)


def make_pipeline_train_step(cfg: llama.LlamaConfig, optimizer, mesh: Mesh, num_microbatches: int | None = None):
    """train_step(stacked_params, opt_state, batch) -> (params, opt_state, loss)
    where the forward is the pp pipeline above and grads flow back through
    the ppermute ring (fori_loop lowers to scan, so reverse-mode works)."""
    from modelx_tpu.models.train import make_train_step

    return make_train_step(
        cfg,
        optimizer,
        mesh=mesh,
        forward_fn=lambda stacked, tokens: pipeline_forward(
            stacked, tokens, cfg, mesh, num_microbatches
        ),
    )
