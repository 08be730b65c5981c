#!/usr/bin/env python3
"""Hold the served DeepSeek-V2 pod against the float32 reference.

    python3 benchmark/references/compare_deepseek_v2.py --collect OUT.npz [--seed N]  # on the chip
    python3 benchmark/references/compare_deepseek_v2.py --judge OUT.npz               # anywhere
    python3 benchmark/references/compare_deepseek_v2.py [--seed N] [--rehearse]       # both, in turn

A tool for the builder, not a part of a run: ``correct`` in ``run.py`` stays
what it is (in the manner of ``compare_minicpm_sala.py``; the checkpoint
reader and the 8-bit rounding are ``compare_laguna.py``'s). **Collecting**
writes the cell's checkpoint, starts ``modelx serve-model`` on it with the
configuration's own ``serve_args`` (32 slots of 32,768 positions,
``--prefill-chunk 2048``), keeps EVERY slot busy with long streaming requests,
and records what the served path produced at the published widths:

(i) the engine's own greedy tokens of ``ROWS`` rows whose prompts of 8,240
    tokens land IN PIECES (four of 2,048 and a last one of 48: each piece
    expands the keys and values of what landed before it from the latent
    lines) while the other slots decode, and which then decode ``DECODE``
    tokens through the ABSORBED form over those lines, group-limited routing
    over the 20 held experts of 160 at every layer but the first;
(ii) ``/v1/forward`` logits (``logits_at``) of ``ROWS`` sequences of 8,304
    tokens, one request a sequence, at ``POSITIONS`` positions spread over the
    sequence: the cache-less expanded form, the expert layer 1,024 tokens at a
    time.

**Judging** makes the same checkpoint from the same seed and runs
``references/deepseek_v2.py`` — float32 at ``highest`` precision, no cache, no
absorption, the attention ``--head-block`` heads at a time so that ``[H, T, T]``
scores fit — in worker processes on the CPU (14 minutes a pass of 8,3xx
positions at the published widths on 8 cores), or with ``--reference-on
device`` in one worker on whatever accelerator jax finds (the pod has gone by
then: a chip belongs to one process at a time; NOT faster on a TPU — the
reference runs op by op, and an expert's tokens differ in number every time,
so each is compiled anew: 8-12 minutes a pass, my chip run, PR 43). It measures, per compared position, in
units of the standard deviation of the reference's logits over the vocabulary
at that position:

- **forward error** ``e_p``: root mean square over the vocabulary of (served
  - reference);
- **engine margin** ``m_p``: how far the reference's logit of the engine's
  token (teacher-forced) lies below the reference's maximum; 0 where the
  engine's token is the reference's argmax.

This model has a ROUTER: where two experts' (or two groups') scores nearly tie
for the last place, bfloat16 may take the other one, and that position's
output differs by one expert's part of the sum times 16 — of which this chip
holds an eighth. The four limits, each between the two readings that set it
(PERF.md section 6, PR 43, with the seed):

- ``FORWARD_MEDIAN_TOL``: the median of ``e_p`` — bfloat16 activations through
  five layers against float8 weights (the control);
- ``ENGINE_AGREEMENT_MIN`` and ``ENGINE_MARGIN_P90_TOL``: the share of the
  engine's tokens that are the reference's argmax, and the 90th percentile of
  ``m_p``. A wrong cache offset, rope, absorption or routing agrees on a few
  per cent and lies standard deviations down at nearly every position;
- ``ENGINE_MARGIN_WORST``: no single token further below the reference's
  maximum than rounding and a flipped expert explain; a random token of a
  12,800-word vocabulary lies about 3.8 down.

The control (always part of judging): the reference against itself with every
weight rounded to float8 (e4m3). A program that computed in a precision below
the configuration's would err so, and every one of the four limits refuses it.
"""

from __future__ import annotations

import argparse
import base64
import functools
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import checkpoint, loadgen  # noqa: E402
from benchmark.procs import CLI, Children, emit, free_port, post_ok, wait_ready  # noqa: E402
from benchmark.references.compare_laguna import Checkpoint, quantiles, to_8_bits  # noqa: E402
from benchmark.run import META_KEYS  # noqa: E402  (what of a configuration file is not config.json)

CONFIG = "deepseek-v2-ep8-d5"
ROWS, POSITIONS, DECODE = 2, 32, 48
PIECES = 4  # of --prefill-chunk, then a last piece of 48: the benchmark's probe
# each limit between its two readings (my chip run, PR 43, seed 4300004001, the two
# rows judged one after the other): the pod's, then the 8-bit control's
FORWARD_MEDIAN_TOL = 0.1  # 0.0226, 0.0260 | 0.484, 0.493
ENGINE_AGREEMENT_MIN = 0.65  # 0.9375, 0.896 | 0.3125, 0.3125
ENGINE_MARGIN_P90_TOL = 0.15  # 0.0, 0.0011 | 1.143, 1.276
# a flipped expert a row or two: three and five of 48 tokens were not the reference's
# argmax, the furthest 0.652 and 0.665 down; the control's furthest 2.869 and 1.730
ENGINE_MARGIN_WORST = 1.1


def the_cell(args):
    """(config as run, the checkpoint's config.json, model dir, work dir)."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
    hf = {k: v for k, v in config.items() if k not in META_KEYS}
    work = os.path.join(ROOT, ".cache", "benchmark")
    model_dir, nbytes, wrote_s = checkpoint.ensure(
        os.path.join(work, "checkpoint"), CONFIG + ("-rehearse" if args.rehearse else ""),
        config["family"], config, hf, args.seed, config.get("checkpoint_dtype", "BF16"))
    emit("checkpoint", bytes=nbytes, wrote_seconds=round(wrote_s, 1), seed=args.seed)
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
    rng = np.random.default_rng([args.seed, 43])
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".cache", "xla")
    os.makedirs(cache_dir, exist_ok=True)
    kids = Children(os.path.join(work, "logs", "compare_deepseek_v2"), cache_dir)
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
        engine_stats = metrics["default"]["continuous"]
        engine = []
        for i, rec in enumerate(recs):
            assert rec["done"] and not rec["error"], rec["error"]
            engine.append(prompts[i] + loadgen.tokens_of(rec))
        seq_len = prompt_len + 64
        seqs = [[int(t) for t in rng.integers(1, vocab, seq_len)] for _ in range(ROWS)]
        at = sorted({int(p) for p in np.linspace(0, seq_len - 1, POSITIONS)})
        served = []
        for seq in seqs:  # one a request, beside the engine's state
            got = post_ok(port, "/v1/forward", {"tokens": [seq], "logits_at": at})["logits"]
            served.append(np.frombuffer(base64.b64decode(got["b64"]), np.float32)
                          .reshape(got["shape"])[0])
        served = np.stack(served)
        # a bfloat16 pod's logits are bfloat16 values: their top halves lose nothing
        halves = (served.view(np.uint32) >> 16).astype(np.uint16)
        if np.array_equal((halves.astype(np.uint32) << 16).view(np.float32), served):
            served = halves
    finally:
        kids.stop_all()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    np.savez_compressed(out_path, seed=args.seed, rehearse=bool(args.rehearse),
                        engine=np.asarray(engine), prompt_len=prompt_len,
                        forward_tokens=np.asarray(seqs), forward_at=np.asarray(at), served=served,
                        slots_busy=engine_stats["active_peak"],
                        fill_pieces=engine_stats.get("fill", {}).get("pieces", -1),
                        steps_absorbed=engine_stats.get("mla", {}).get("steps_absorbed", -1),
                        steps_all=engine_stats.get("mla", {}).get("steps_all", -1))
    emit("collected", out=out_path, slots_busy_at_peak=int(engine_stats["active_peak"]), rows=ROWS,
         engine_positions=DECODE, first_decoded_position=prompt_len,
         last_position=prompt_len + DECODE - 1,
         fill=engine_stats.get("fill"), mla=engine_stats.get("mla"), moe=engine_stats.get("moe"),
         kv=engine_stats.get("kv"),
         forward_positions=len(at), forward_sequence=seq_len)


@functools.lru_cache(maxsize=1)
def weights_of(model_dir: str) -> Checkpoint:
    """A worker's checkpoint, read once: every pass it is given is of one model."""
    return Checkpoint(model_dir)


def reference_pass(job):
    """One pass of the reference, in a worker process of :func:`judge`:
    (model dir, config.json, sequence, positions, control, where, head block)
    -> (logits, seconds)."""
    model_dir, hf, seq, at, control, where, head_block = job
    if where == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"  # this process is the reference
    from benchmark.references import deepseek_v2 as reference

    t0 = time.monotonic()
    how = {"cast": to_8_bits} if control else {}
    out = np.asarray(reference.forward(weights_of(model_dir), hf, seq, positions=at,
                                       head_block=head_block, **how))
    return out, round(time.monotonic() - t0, 1)


def judge(path: str, control_rows: int, workers: int, where: str = "cpu",
          head_block: int = 8, rows: int = ROWS) -> bool:
    import concurrent.futures
    import multiprocessing

    data = dict(np.load(path))
    for key in ("engine", "forward_tokens", "served"):  # the first ``rows`` of each kind
        data[key] = data[key][:rows]
    args = argparse.Namespace(seed=int(data["seed"]), rehearse=bool(data["rehearse"]))
    _, hf, model_dir, _ = the_cell(args)
    prompt_len = int(data["prompt_len"])
    sd = lambda logits: np.std(logits, axis=-1)  # noqa: E731

    def margin(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
        return (ref.max(-1) - ref[np.arange(len(tokens)), tokens]) / sd(ref)

    # every pass is its own sequence of 8 k positions at the published widths — minutes
    # each on the CPU, where they run side by side; one worker where the reference runs on
    # the accelerator (one process holds it): (kind, row, control) -> logits
    workers = workers if where == "cpu" else 1
    engine_at = list(range(prompt_len - 1, data["engine"].shape[1] - 1))  # p predicts token p + 1
    at = data["forward_at"].tolist()
    jobs = {}
    for kind, seqs, at_these in (("engine", data["engine"], engine_at),
                              ("forward", data["forward_tokens"], at)):
        for i, seq in enumerate(seqs):
            for control in (False, True)[: 1 + (i < control_rows)]:
                jobs[kind, i, control] = (model_dir, hf, seq, at_these, control, where, head_block)
    with concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as pool:
        futures = {key: pool.submit(reference_pass, job) for key, job in jobs.items()}
        passes = {}
        for key, future in futures.items():
            passes[key], seconds = future.result()
            emit("reference_pass", of=key[0], row=key[1], control=key[2],
                 positions=len(jobs[key][3]), seconds=seconds)

    margins, agree, low_margins, low_agree = [], [], [], []
    for i, seq in enumerate(data["engine"]):
        ref = passes["engine", i, False]
        margins.append(margin(ref, seq[prompt_len:]))
        agree.append(ref.argmax(-1) == seq[prompt_len:])
        if i < control_rows:
            low = passes["engine", i, True]
            low_margins.append(margin(ref, low.argmax(-1)))
            low_agree.append(ref.argmax(-1) == low.argmax(-1))
    served = data["served"]
    if served.dtype == np.uint16:  # the top halves of a bfloat16 pod's logits
        served = (served.astype(np.uint32) << 16).view(np.float32)
    errors, low_errors = [], []
    for i in range(len(data["forward_tokens"])):
        ref = passes["forward", i, False]
        errors.append(np.sqrt(np.mean((served[i] - ref) ** 2, -1)) / sd(ref))
        if i < control_rows:
            low = passes["forward", i, True]
            low_errors.append(np.sqrt(np.mean((low - ref) ** 2, -1)) / sd(ref))

    def verdict(errors, margins, agree) -> dict:
        e, m, a = quantiles(np.concatenate(errors)), quantiles(np.concatenate(margins)), float(
            np.mean(np.concatenate(agree)))
        held = {"forward_median": e["median"] <= FORWARD_MEDIAN_TOL,
                "engine_agreement": a >= ENGINE_AGREEMENT_MIN,
                "engine_margin_p90": m["p90"] <= ENGINE_MARGIN_P90_TOL,
                "engine_margin_worst": m["worst"] <= ENGINE_MARGIN_WORST}
        return {"forward_error_sd": e, "engine_margin_sd": m, "engine_argmax_agreement": a,
                "held": held, "ok": all(held.values())}

    tolerances = {"forward_median_sd": FORWARD_MEDIAN_TOL, "engine_agreement": ENGINE_AGREEMENT_MIN,
                  "engine_margin_p90_sd": ENGINE_MARGIN_P90_TOL,
                  "engine_margin_worst_sd": ENGINE_MARGIN_WORST}
    pod = verdict(errors, margins, agree)
    control = verdict(low_errors, low_margins, low_agree)
    emit("pod_against_reference", **pod, tolerances=tolerances, seed=args.seed,
         slots_busy=int(data["slots_busy"]), fill_pieces=int(data["fill_pieces"]),
         steps_absorbed=int(data["steps_absorbed"]), steps_all=int(data["steps_all"]),
         forward_positions=len(at) * len(errors), reference_on=where,
         engine_positions=int(sum(len(m) for m in margins)))
    emit("control_8_bit_weights_against_reference", **control,
         refused_by_every_limit=not any(control["held"].values()), rows=control_rows)
    return pod["ok"] and not any(control["held"].values())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on whatever jax finds")
    ap.add_argument("--collect", metavar="OUT.npz", help="only run the pod and record it")
    ap.add_argument("--judge", metavar="OUT.npz", help="only hold a record against the reference")
    ap.add_argument("--control-rows", type=int, default=ROWS,
                    help="rows the 8-bit control is computed on (each a reference pass)")
    ap.add_argument("--rows", type=int, default=ROWS,
                    help="rows of each kind that are judged (a pass of 8,3xx positions at the "
                         "published widths is 14 minutes on 8 cores)")
    ap.add_argument("--workers", type=int, default=3,
                    help="reference passes run side by side when judging on the CPU (each "
                         "holds the weights in float32: 12.6 GB at the published widths)")
    ap.add_argument("--reference-on", choices=("cpu", "device"), default="cpu",
                    help="where the reference computes: the CPU, or whatever accelerator jax "
                         "finds (float32 at highest precision either way)")
    ap.add_argument("--head-block", type=int, default=8,
                    help="heads whose [T, T] scores the reference holds at a time")
    args = ap.parse_args()
    if args.judge:
        ok = judge(args.judge, args.control_rows, args.workers, args.reference_on,
                   args.head_block, args.rows)
    else:
        out = args.collect or os.path.join(ROOT, ".cache", "benchmark", "compare_deepseek_v2.npz")
        collect(args, out)
        ok = True if args.collect else judge(out, args.control_rows, args.workers,
                                             args.reference_on, args.head_block, args.rows)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
