"""``modelx-train``: the training loop as a usable surface.

The training STEP (models/train.py) and the checkpoint subsystem
(dl/checkpoint.py) are library pieces; this CLI strings them into the
registry-centric loop the framework is built around:

    pull (or init) -> shard onto the mesh -> train -> checkpoint shards ->
    push (content-addressed: only changed layer shards upload)

Data is a token stream: an int32 ``.npy``/``.bin`` memmap of token ids, or
``synthetic`` for smoke/benchmark runs. Sequences are consecutive windows;
targets are the inputs shifted by one. Resume is automatic when the
checkpoint directory holds a prior state (dl/checkpoint.py commit-point
semantics guarantee it is a consistent one).

Reference parity: none (the reference stores models, it doesn't train
them); this surface exists because distributed training is first-class in
the TPU build (SURVEY.md §5, __graft_entry__ dry-run contract).
"""

from __future__ import annotations

import json
import logging
import os
import time

import click
import numpy as np

logger = logging.getLogger("modelx.train")


def _load_tokens(data: str, vocab_size: int, steps: int, batch: int, seq: int) -> np.ndarray:
    """Token id stream as a flat int32 array (memmapped when on disk)."""
    if data == "synthetic":
        rng = np.random.RandomState(0)
        return rng.randint(1, vocab_size, steps * batch * (seq + 1)).astype(np.int32)
    if data.endswith(".npy"):
        arr = np.load(data, mmap_mode="r")
    else:
        arr = np.memmap(data, dtype=np.int32, mode="r")
    if arr.ndim != 1:
        arr = arr.reshape(-1)
    return arr


def _batches(tokens: np.ndarray, steps: int, batch: int, seq: int,
             start_step: int = 0, vocab_size: int | None = None):
    """Consecutive [B, S+1] windows -> {"tokens", "targets"}; wraps around.
    ``start_step`` places the cursor where a resumed run left off, so a
    restart continues through the stream instead of replaying the start.
    Ids are validated against ``vocab_size``: XLA's gather silently CLAMPS
    out-of-range indices inside jit, so a vocab-mismatched tokenizer would
    otherwise train on garbage with a finite loss."""
    need = batch * (seq + 1)
    total = len(tokens)
    if total < need:
        raise click.ClickException(
            f"data holds {total} tokens; one step needs {need} (batch*(seq+1))"
        )
    per_epoch = total // need
    off = (start_step % per_epoch) * need
    for _ in range(steps):
        if off + need > total:
            off = 0
        window = np.asarray(tokens[off : off + need]).reshape(batch, seq + 1)
        off += need
        if vocab_size is not None:
            hi, lo = int(window.max()), int(window.min())
            if hi >= vocab_size or lo < 0:
                raise click.ClickException(
                    f"data contains token id {hi if hi >= vocab_size else lo}, "
                    f"outside the model's vocab [0, {vocab_size}) — wrong tokenizer?"
                )
        yield {"tokens": window[:, :-1].copy(), "targets": window[:, 1:].copy()}


def _scan_model_dir(model_dir: str):
    """(config, shard paths) from the checkpoint headers alone (no weight
    bytes) — the single owner of *.safetensors discovery."""
    import glob as _glob

    from modelx_tpu.dl import families as fam
    from modelx_tpu.dl.safetensors import read_header_from_file

    paths = sorted(_glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not paths:
        raise click.ClickException(f"no safetensors under {model_dir}")
    infos: dict = {}
    for p in paths:
        h, _ = read_header_from_file(p)
        infos.update(h)
    return fam.infer_llama_config(fam.abstract_params(infos)), paths


@click.command("modelx-train")
@click.option("--model-dir", default="", help="checkpoint dir with *.safetensors to start from")
@click.option("--config", default="tiny",
              type=click.Choice(["tiny", "llama3_8b", "llama3_70b"]),
              help="llama config when starting fresh")
@click.option("--data", default="synthetic", help="token id stream: .npy / int32 .bin / 'synthetic'")
@click.option("--mesh", "mesh_spec", default="", help='mesh spec, e.g. "dp=2,fsdp=4" (default: dp over all devices)')
@click.option("--fsdp", is_flag=True, help="use the ZeRO-3 partition rules (params sharded over fsdp)")
@click.option("--steps", default=100, type=int,
              help="steps to run NOW (a resumed run trains this many MORE)")
@click.option("--batch", default=8, type=int)
@click.option("--seq", default=512, type=int)
@click.option("--lr", default=3e-4, type=float)
@click.option("--checkpoint-dir", default="", help="save/resume dir (layer-sharded safetensors)")
@click.option("--checkpoint-every", default=100, type=int)
@click.option("--push", "push_uri", default="", help="push the checkpoint here when done (registry URI)")
@click.option("--log-every", default=10, type=int)
def main(model_dir, config, data, mesh_spec, fsdp, steps, batch, seq, lr,
         checkpoint_dir, checkpoint_every, push_uri, log_every) -> None:
    """Train a llama-family model on a device mesh, checkpointing through
    the registry's content-addressed store."""
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    import jax

    from modelx_tpu.dl.checkpoint import Checkpointer
    from modelx_tpu.dl.sharding import LLAMA_FSDP_RULES, LLAMA_RULES
    from modelx_tpu.models import llama
    from modelx_tpu.models.train import (
        batch_sharding,
        make_optimizer,
        shard_params,
    )
    from modelx_tpu.parallel.mesh import make_mesh

    if push_uri and not checkpoint_dir:
        raise click.ClickException("--push requires --checkpoint-dir (the pushed artifact)")
    mesh = make_mesh(mesh_spec) if mesh_spec else make_mesh(f"dp={len(jax.devices())}")
    rules = LLAMA_FSDP_RULES if (fsdp or "fsdp" in mesh.axis_names) else LLAMA_RULES
    data_ways = 1
    for ax in ("dp", "fsdp"):
        if ax in mesh.axis_names:
            data_ways *= mesh.shape[ax]
    if batch % data_ways:
        raise click.ClickException(
            f"--batch {batch} must be divisible by the data axes (dp*fsdp = {data_ways})"
        )
    if "sp" in mesh.axis_names and seq % mesh.shape["sp"]:
        raise click.ClickException(
            f"--seq {seq} must be divisible by the sp axis ({mesh.shape['sp']})"
        )

    # -- model: resume > checkpoint dir > fresh config ------------------------
    ckpt = Checkpointer(checkpoint_dir) if checkpoint_dir else None
    resuming = ckpt is not None and os.path.exists(
        os.path.join(ckpt.directory, "checkpoint.json")
    )
    start_step = 0
    optimizer = make_optimizer(lr=lr)
    if model_dir:
        cfg, shard_paths = _scan_model_dir(model_dir)
    else:
        cfg, shard_paths = getattr(llama.LlamaConfig, config)(), []
    if resuming:
        # restore() delivers both weights and optimizer state; all it needs
        # from the templates is names/shapes — abstract values avoid
        # materializing (and device_put-ing) a full random init just to
        # throw it away
        abstract = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0))
        )
        opt_abstract = jax.eval_shape(optimizer.init, abstract)
        params, opt_state, start_step = ckpt.restore(abstract, opt_abstract, mesh, rules)
        logger.info("resumed from step %d (%s)", start_step, ckpt.directory)
    elif model_dir:
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors

        params = {}
        for p in shard_paths:
            src = LocalFileSource(p)
            try:
                arrays, _ = load_safetensors(src, mesh, rules)
            finally:
                src.close()
            params.update(arrays)
        opt_state = optimizer.init(params)
    else:
        params = shard_params(llama.init_params(cfg, jax.random.PRNGKey(0)), rules, mesh)
        opt_state = optimizer.init(params)

    from modelx_tpu.models.train import jit_train_step

    step_fn = jit_train_step(cfg, optimizer, mesh, rules)
    bsh = batch_sharding(mesh)
    tokens = _load_tokens(data, cfg.vocab_size, steps, batch, seq)

    t0 = time.monotonic()
    losses = []
    n = last_saved = start_step
    for batch_np in _batches(tokens, steps, batch, seq, start_step=start_step,
                             vocab_size=cfg.vocab_size):
        dev_batch = {k: jax.device_put(v, bsh) for k, v in batch_np.items()}
        params, opt_state, loss = step_fn(params, opt_state, dev_batch)
        n += 1
        if n % log_every == 0 or n == start_step + steps:
            loss_f = float(loss)
            losses.append(loss_f)
            dt = time.monotonic() - t0
            tps = (n - start_step) * batch * seq / dt
            logger.info("step %d  loss %.4f  %.0f tok/s", n, loss_f, tps)
        if ckpt is not None and checkpoint_every and n % checkpoint_every == 0:
            _save(ckpt, params, opt_state, n)
            last_saved = n
    if ckpt is not None and n > last_saved:
        _save(ckpt, params, opt_state, n)
    if ckpt is not None and push_uri:  # push regardless of save boundaries
        ckpt.push(push_uri)
        logger.info("pushed checkpoint to %s", push_uri)
    click.echo(json.dumps({
        "steps": n, "final_loss": losses[-1] if losses else None,
        "tokens_per_s": round((n - start_step) * batch * seq / (time.monotonic() - t0), 1),
        "mesh": str(dict(mesh.shape)),
    }))


def _save(ckpt, params, opt_state, step: int) -> None:
    host_params = {k: np.asarray(v) for k, v in params.items()}
    ckpt.save(host_params, opt_state, step=step)
    logger.info("checkpointed step %d -> %s", step, ckpt.directory)


if __name__ == "__main__":
    main()
