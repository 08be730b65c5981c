#!/usr/bin/env python3
"""Hold the served MiMo-V2-Flash pod against the float32 reference.

    python3 benchmark/references/compare_mimo_v2.py [--seed N]                 # on the chip: both, in turn
    python3 benchmark/references/compare_mimo_v2.py --collect OUT.npz [--seed N]
    python3 benchmark/references/compare_mimo_v2.py --judge OUT.npz [--reference-on cpu]
    JAX_PLATFORMS=cpu python3 benchmark/references/compare_mimo_v2.py --rehearse

A tool for the builder, not a part of a run: ``correct`` in ``run.py`` stays
what it is (in the manner of ``compare_deepseek_v2.py``; the checkpoint reader
and the 8-bit rounding are ``compare_laguna.py``'s). **Collecting** writes a
checkpoint of the committed configuration under a name of its own, PLANTS
SINKS in it (below), starts ``modelx serve-model`` on it with the
configuration's own ``serve_args`` (32 slots of 32,768 positions,
``--prefill-chunk 2048``), keeps EVERY slot busy with long streaming requests,
and records what the timed path produced at the published widths: the engine's
own greedy tokens of ``ROWS`` rows whose prompts of 8,240 tokens land IN PIECES
(four of 2,048 and a last one of 48 — over the full leaves and, unrolled, over
the rings) while the other slots decode, and which then decode ``DECODE``
tokens through the cache: the ragged kernel over the full layers' lines, the
ring kernel with the sinks over the window layers' — every compared position
past 128, past the ring's wrap at 144 and past four pieces.

**The sinks.** ``benchmark/checkpoint.py`` draws every tensor from one table by
its last dimension: a sink of +-0.22 beside 128 keys' scores takes half a per
cent of a softmax's mass, and a pod that dropped its sinks could not be told
from one that kept them. The comparison's checkpoint gets sinks drawn
uniformly from [2, 5] (a fifth to a half of a window's mass at random scores),
written over the ``attention_sink_bias`` tensors in place; the cell's own
checkpoint is left as it is.

**Judging** makes the same checkpoint and runs ``references/mimo_v2.py`` —
float32 at ``highest`` precision, no cache, no kernel, the attention
``--head-block`` heads at a time so that ``[heads, T, T]`` scores fit, every
held expert on every token (no shape depends on the routing, so an accelerator
compiles each operation once) — in a child on whatever jax finds there (the
pod has gone by then: a chip belongs to one process at a time), or on the CPU
with ``--reference-on cpu``. Per compared position, in units of the standard
deviation of the reference's logits over the vocabulary at that position:

- **engine margin** ``m_p``: how far the reference's logit of the engine's
  token (teacher-forced) lies below the reference's maximum; 0 where the
  engine's token is the reference's argmax;
- **engine agreement**: the share of the engine's tokens that are the
  reference's argmax.

This model has a ROUTER: where two experts' scores nearly tie for the eighth
place, bfloat16 may take the other one. The three limits, each between the
readings that set it (PERF.md section 6, with the seeds):
``ENGINE_AGREEMENT_MIN``, ``ENGINE_MARGIN_P90_TOL``, ``ENGINE_MARGIN_WORST``.

Four more verdicts ride every judging, each the same readings of the SAME
engine tokens against another model:

- **the 8-bit control**: the reference with every weight rounded to float8
  (e4m3) — the nearest precision below the configuration's. At least one limit
  must refuse it.
- **two planted faults**: the reference WITHOUT its sinks, and the reference
  with ``v`` UNSCALED (``attention_value_scale`` 1.0). A pod that is right by
  the true reference must be wrong by these: each must be refused by at least
  one limit — if the limits cannot tell the pod from a model without sinks,
  they would not tell a pod without sinks from the model.
- **the bf16 witness**: the reference with every layer's output rounded to
  bfloat16 — the plain equations in the precision the configuration states.
  What that precision alone does; the pod should read like it.
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import checkpoint, loadgen  # noqa: E402
from benchmark.procs import CLI, Children, emit, free_port, wait_ready  # noqa: E402
from benchmark.references.compare_laguna import Checkpoint, quantiles, to_8_bits  # noqa: E402
from benchmark.run import META_KEYS  # noqa: E402  (what of a configuration file is not config.json)

CONFIG = "mimo-v2-flash-ep16-d7"
ROWS, DECODE = 2, 48
PIECES = 4  # of --prefill-chunk, then a last piece of 48: the benchmark's probe
SINKS = (2.0, 5.0)  # the planted sinks' range
# each limit between its readings (my chip runs, PR 54, seeds 5400001001 and 5400003003,
# both rows; PERF.md section 6): the pod's and the bf16 witness's | the 8-bit control's;
# sinks dropped; v unscaled
ENGINE_AGREEMENT_MIN = 0.8  # 0.958, 0.958, bf16 0.969, 0.979 | 0.635, 0.708; 0.469, 0.542; 0.323, 0.375
ENGINE_MARGIN_P90_TOL = 0.1  # 0.0, 0.0, bf16 0.0, 0.0 | 0.236, 0.249; 0.508, 0.460; 0.885, 0.749
# one or two tokens a row are not the reference's argmax (a near-tie the rounding decides):
# 0.033, 0.191, bf16 0.032, 0.006 | 0.511, 0.471; 1.125, 0.762; 1.477, 1.323
ENGINE_MARGIN_WORST = 0.35
HOWS = {"": {}, "8bit": {"cast": to_8_bits}, "no_sinks": {"drop_sinks": True},
        "v_unscaled": {"value_scale": 1.0}}


def to_bf16(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


def plant_sinks(model_dir: str, seed: int) -> int:
    """Write sinks drawn uniformly from ``SINKS`` over every
    ``attention_sink_bias`` tensor of the checkpoint, in place (same bytes,
    same header, the same draw for a seed: writing twice changes nothing).
    Returns how many."""
    import ml_dtypes

    rng, planted = np.random.default_rng([seed, 54]), 0
    for name in sorted(os.listdir(model_dir)):
        if not name.endswith(".safetensors"):
            continue
        path = os.path.join(model_dir, name)
        with open(path, "r+b") as f:
            (hlen,) = struct.unpack("<Q", f.read(8))
            header = json.loads(f.read(hlen))
            for tensor, info in sorted(header.items()):
                if not tensor.endswith("attention_sink_bias"):
                    continue
                values = rng.uniform(*SINKS, size=info["shape"]).astype(np.float32)
                raw = (values if info["dtype"] == "F32" else values.astype(ml_dtypes.bfloat16))
                f.seek(8 + hlen + info["data_offsets"][0])
                f.write(raw.tobytes())
                planted += 1
    return planted


def the_cell(args):
    """(config as run, the checkpoint's config.json, model dir, work dir)."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
    hf = {k: v for k, v in config.items() if k not in META_KEYS}
    work = os.path.join(ROOT, ".cache", "benchmark")
    name = CONFIG + "-compare" + ("-rehearse" if args.rehearse else "")
    model_dir, nbytes, wrote_s = checkpoint.ensure(
        os.path.join(work, "checkpoint"), name, config["family"], config, hf, args.seed,
        config.get("checkpoint_dtype", "BF16"))
    planted = plant_sinks(model_dir, args.seed)
    emit("checkpoint", bytes=nbytes, wrote_seconds=round(wrote_s, 1), seed=args.seed,
         sinks_planted=planted, sinks_range=list(SINKS))
    return config, hf, model_dir, work


def collect(args, out_path: str) -> None:
    config, hf, model_dir, work = the_cell(args)
    vocab = config["vocab_size"]
    serve_args = list(config["serve_args"])
    max_slots = int(serve_args[serve_args.index("--max-slots") + 1])
    max_len = int(serve_args[serve_args.index("--max-seq-len") + 1])
    piece = int(serve_args[serve_args.index("--prefill-chunk") + 1])
    prompt_len = PIECES * piece + 3 * 16  # pieces of --prefill-chunk, then a last one of 48
    assert prompt_len + DECODE + 24 <= max_len and prompt_len > piece
    rng = np.random.default_rng([args.seed, 54])
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".cache", "xla")
    os.makedirs(cache_dir, exist_ok=True)
    kids = Children(os.path.join(work, "logs", "compare_mimo_v2"), cache_dir)
    try:
        port = free_port()
        pod = kids.start("pod", CLI + ["serve-model", "--model-dir", model_dir, "--listen",
                                       f"127.0.0.1:{port}", "--drain-seconds", "0", *serve_args],
                         jax_child=not args.rehearse)
        wait_ready(port, pod, kids.log_dir, 1100)
        # every other slot busy for the whole collection: long streaming requests
        busy = max_slots - ROWS
        fillers = [threading.Thread(target=loadgen.stream_request, daemon=True, args=(
            port, [int(t) for t in rng.integers(1, vocab, 48)], max_len - 8 - 48 - 16),
            kwargs={"timeout": 3000.0}) for _ in range(busy)]
        for t in fillers:
            t.start()
        time.sleep(5.0)
        prompts = [[int(t) for t in rng.integers(1, vocab, prompt_len)] for _ in range(ROWS)]
        recs: list = [None] * ROWS

        def one(i: int) -> None:
            recs[i] = loadgen.stream_request(port, prompts[i], DECODE, timeout=1500.0)

        rows = [threading.Thread(target=one, args=(i,)) for i in range(ROWS)]
        for t in rows:
            t.start()
        for t in rows:
            t.join()
        _, metrics = loadgen.http_json(port, "GET", "/metrics")
        stats = metrics["default"]["continuous"]
        engine = []
        for i, rec in enumerate(recs):
            assert rec["done"] and not rec["error"], rec["error"]
            engine.append(prompts[i] + loadgen.tokens_of(rec))
    finally:
        kids.stop_all()
    counted = {k: int(stats.get(k, -1)) for k in (
        "active_peak", "kv_ring_pieces", "attn_ring_calls", "attn_ring_kernel_calls",
        "attn_kv_positions_read", "attn_kv_positions_cached")}
    counted.update(fill_pieces=stats.get("fill", {}).get("pieces", -1),
                   sink_calls=stats.get("attn", {}).get("sink_calls", -1))
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    np.savez_compressed(out_path, seed=args.seed, rehearse=bool(args.rehearse),
                        engine=np.asarray(engine), prompt_len=prompt_len, **counted)
    emit("collected", out=out_path, rows=ROWS, engine_positions=DECODE,
         first_decoded_position=prompt_len, last_position=prompt_len + DECODE - 1,
         **counted, moe=stats.get("moe"), kv=stats.get("kv"),
         hbm_peak_bytes=metrics.get("device", {}).get("hbm_peak_bytes"),
         hbm_bytes_in_use=metrics.get("device", {}).get("hbm_bytes_in_use"))


def judge(path: str, rows: int, where: str, head_block: int) -> bool:
    """Every pass in THIS process (it is the reference's: on the CPU it says
    so before jax is imported; on an accelerator the shapes repeat from pass
    to pass, so each operation compiles once)."""
    if where == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark.references import mimo_v2 as reference

    data = dict(np.load(path))
    args = argparse.Namespace(seed=int(data["seed"]), rehearse=bool(data["rehearse"]))
    _, hf, model_dir, _ = the_cell(args)
    weights = Checkpoint(model_dir)
    prompt_len = int(data["prompt_len"])
    engine = data["engine"][:rows]
    at = list(range(prompt_len - 1, engine.shape[1] - 1))  # p predicts token p + 1
    hows = dict(HOWS, bf16={"cast_activations": to_bf16})
    passes = {}
    for i, seq in enumerate(engine):
        for how, hooks in hows.items():
            t0 = time.monotonic()
            passes[how, i] = np.asarray(reference.forward(
                weights, hf, seq, positions=at, head_block=head_block, dense_experts=True,
                **hooks))
            emit("reference_pass", row=i, how=how or "reference", positions=len(at),
                 sequence=len(seq), seconds=round(time.monotonic() - t0, 1), on=where)
            weights.kept.clear()  # a pass reads each weight as it uses it and keeps none

    limits = {"engine_agreement": ENGINE_AGREEMENT_MIN, "engine_margin_p90_sd": ENGINE_MARGIN_P90_TOL,
              "engine_margin_worst_sd": ENGINE_MARGIN_WORST}

    def verdict(how: str) -> dict:
        margins, agree = [], []
        for i, seq in enumerate(engine):
            ref, tokens = passes[how, i], seq[prompt_len:]
            sd = np.std(ref, axis=-1)
            margins.append((ref.max(-1) - ref[np.arange(len(tokens)), tokens]) / sd)
            agree.append(ref.argmax(-1) == tokens)
        m, a = quantiles(np.concatenate(margins)), float(np.mean(np.concatenate(agree)))
        held = {"engine_agreement": a >= ENGINE_AGREEMENT_MIN,
                "engine_margin_p90": m["p90"] <= ENGINE_MARGIN_P90_TOL,
                "engine_margin_worst": m["worst"] <= ENGINE_MARGIN_WORST}
        return {"engine_margin_sd": m, "engine_argmax_agreement": a, "held": held,
                "ok": all(held.values())}

    counted = {k: int(data[k]) for k in data if k not in ("engine", "seed", "rehearse")}
    pod = verdict("")
    emit("pod_against_reference", **pod, limits=limits, seed=args.seed, rows=len(engine),
         engine_positions=len(at) * len(engine), reference_on=where, **counted)
    refused = True
    for how, what in (("8bit", "control_8_bit_weights"), ("no_sinks", "planted_fault_sinks_dropped"),
                      ("v_unscaled", "planted_fault_v_unscaled")):
        other = verdict(how)
        emit(what, **other, refused=not other["ok"],
             refused_by=[k for k, v in other["held"].items() if not v])
        refused = refused and not other["ok"]
    emit("witness_reference_in_bf16", **verdict("bf16"))
    return pod["ok"] and refused


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on whatever jax finds")
    ap.add_argument("--collect", metavar="OUT.npz", help="only run the pod and record it")
    ap.add_argument("--judge", metavar="OUT.npz", help="only hold a record against the reference")
    ap.add_argument("--rows", type=int, default=ROWS, help="rows that are judged (five passes each)")
    ap.add_argument("--reference-on", choices=("cpu", "device"), default="device",
                    help="where the reference computes: the CPU, or whatever accelerator jax "
                         "finds (float32 at highest precision either way)")
    ap.add_argument("--head-block", type=int, default=4,
                    help="heads whose [T, T] scores the reference holds at a time")
    ap.add_argument("--verdict-on-stdout", action="store_true",
                    help="exit 0 whatever the verdict: the last line says it (the child of a "
                         "run that collects and judges in turn)")
    args = ap.parse_args()
    if args.judge:
        ok = judge(args.judge, args.rows, args.reference_on, args.head_block)
    else:
        out = args.collect or os.path.join(ROOT, ".cache", "benchmark", "compare_mimo_v2.npz")
        collect(args, out)
        ok = True
        if not args.collect:  # the pod has gone: a child of its own holds the reference
            kids = Children(os.path.join(ROOT, ".cache", "benchmark", "logs", "compare_mimo_v2"),
                            os.environ.get("JAX_COMPILATION_CACHE_DIR")
                            or os.path.join(ROOT, ".cache", "xla"))
            where = "cpu" if args.rehearse else args.reference_on
            said = kids.run("judge", [os.path.abspath(__file__), "--judge", out, "--rows",
                                      str(args.rows), "--reference-on", where, "--head-block",
                                      str(args.head_block), "--verdict-on-stdout"],
                            jax_child=where != "cpu",
                            timeout=3000)
            sys.stdout.write(said)
            ok = json.loads(said.strip().splitlines()[-1])["ok"]
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok or args.verdict_on_stdout else 1


if __name__ == "__main__":
    sys.exit(main())
