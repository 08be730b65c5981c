#!/usr/bin/env python3
"""Hold the served Nemotron-H pod against the float32 reference.

    python3 benchmark/references/compare_nemotron_h.py --collect OUT.npz [--seed N]  # on the chip
    python3 benchmark/references/compare_nemotron_h.py --judge OUT.npz               # anywhere
    python3 benchmark/references/compare_nemotron_h.py [--seed N] [--rehearse]       # both, in turn

A tool for the builder, not a part of a run: ``correct`` in ``run.py`` stays
what it is (in the manner of ``compare_deepseek_v2.py``; the checkpoint reader
and the 8-bit rounding are ``compare_laguna.py``'s). **Collecting** writes the
cell's checkpoint, starts ``modelx serve-model`` on it with the configuration's
own ``serve_args`` (64 slots of 4,096 positions), keeps EVERY slot busy with
long streaming requests, and records what the served path produced at the
published widths:

(i) the engine's own greedy tokens of two rows whose prompts are of the
    ``.agent`` cell's lengths — 131 tokens, a bucket of 144 whose padded tail
    must enter neither state nor convolution tail, and 256, a bucket to the
    brim — admitted while the other slots decode, and which then decode
    ``DECODE`` tokens through the cache: the one-token state step from the
    state and tail the admission's chunked scan left, the latent expert layer
    over the 128 held experts of 512, the attention layer over the keys and
    values the admission wrote;
(ii) ``/v1/forward`` logits (``logits_at``) of two sequences of 320
    tokens, one request a sequence, at ``POSITIONS`` positions spread over the
    sequence: the cache-less form, the Mamba layers chunk by chunk from a zero
    state.

**Judging** makes the same checkpoint from the same seed and runs
``references/nemotron_h.py`` — float32 at ``highest`` precision, no cache, the
recurrence token by token — in worker processes on the CPU; a pass reads each
weight from the checkpoint as it uses it and keeps none (18.6 GB of float32
would not fit beside another pass). It measures, per compared position, in
units of the standard deviation of the reference's logits over the vocabulary
at that position:

- **forward error** ``e_p``: root mean square over the vocabulary of (served
  - reference);
- **engine margin** ``m_p``: how far the reference's logit of the engine's
  token (teacher-forced) lies below the reference's maximum; 0 where the
  engine's token is the reference's argmax.

This model has a ROUTER over 512 experts of which 22 are chosen: around the
22nd place the scores lie about 0.02 of a logit apart (512 normal logits), near
what bfloat16 moves a logit by, so at most positions some layer takes another
expert than the reference does, and that position's output differs by one expert's part of the
sum — of which this chip holds a quarter. The reference ITSELF, with nothing
but its eleven layers' outputs rounded to bfloat16, moves by 0.009-0.02
standard deviations where no expert flipped and by 0.07-0.30 where one did
(median 0.046 over 32 positions; the pod's on the same sequence 0.177: PERF.md
section 6, PR 46). The four limits, each between the two readings that set it
(there too, with the seed):

- ``FORWARD_MEDIAN_TOL``: the median of ``e_p`` — bfloat16 activations through
  eleven layers against float8 weights (the control);
- ``ENGINE_AGREEMENT_MIN`` and ``ENGINE_MARGIN_P90_TOL``: the share of the
  engine's tokens that are the reference's argmax, and the 90th percentile of
  ``m_p``. A wrong cache offset, a state or a tail that took in a padded
  position, a dropped skip term or a wrong group of ``B`` and ``C`` agrees on
  a few per cent and lies standard deviations down at nearly every position;
- ``ENGINE_MARGIN_WORST``: no single token further below the reference's
  maximum than rounding and a flipped expert explain; a random token of a
  32,768-word vocabulary lies about 4 down.

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

CONFIG = "nemotron-3-super-ep4-d11"
POSITIONS, DECODE = 32, 48
PROMPTS = (131, 256)  # the cell's lengths: a padded bucket (144) and a bucket to the brim
FORWARD = 320  # tokens of a /v1/forward sequence
# each limit between its two readings (my chip runs, PR 46, seeds 4600004001 | 4600004002,
# both rows together, 64 slots busy): the pod's, then the 8-bit control's
FORWARD_MEDIAN_TOL = 0.3  # 0.152 | 0.110, control 0.555 | 0.563
ENGINE_AGREEMENT_MIN = 0.45  # 0.740 | 0.635, control 0.219 | 0.146
ENGINE_MARGIN_P90_TOL = 0.55  # 0.208 | 0.349, control 1.478 | 1.308
ENGINE_MARGIN_WORST = 1.4  # 0.671 | 0.933, control 2.075 | 2.017


class Streamed(Checkpoint):
    """The checkpoint's tensors by name, read as float32 at each use and NOT
    kept: a reference pass uses each weight once, and the 18.6 GB of float32
    would not fit beside another pass's."""

    def __getitem__(self, name: str) -> np.ndarray:
        out = super().__getitem__(name)
        self.kept.clear()
        return out


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
    prompts_len = [min(n, max_len - DECODE - 24) for n in PROMPTS]
    forward_len = min(FORWARD, max_len)
    rng = np.random.default_rng([args.seed, 46])
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".cache", "xla")
    os.makedirs(cache_dir, exist_ok=True)
    kids = Children(os.path.join(work, "logs", "compare_nemotron_h"), cache_dir)
    try:
        port = free_port()
        pod = kids.start("pod", CLI + ["serve-model", "--model-dir", model_dir, "--listen",
                                       f"127.0.0.1:{port}", "--drain-seconds", "0", *serve_args],
                         jax_child=not args.rehearse)
        wait_ready(port, pod, kids.log_dir, 1100)
        # every other slot busy for the whole collection: long streaming requests
        busy = max_slots - len(prompts_len)
        fillers = [threading.Thread(target=loadgen.stream_request, daemon=True, args=(
            port, [int(t) for t in rng.integers(1, vocab, 48)], max_len - 8 - 48 - 16),
            kwargs={"timeout": 3000.0}) for _ in range(busy)]
        for t in fillers:
            t.start()
        time.sleep(5.0)
        prompts = [[int(t) for t in rng.integers(1, vocab, n)] for n in prompts_len]
        recs: list = [None] * len(prompts)

        def one(i: int) -> None:
            recs[i] = loadgen.stream_request(port, prompts[i], DECODE, timeout=1500.0)

        rows = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
        for t in rows:
            t.start()
        for t in rows:
            t.join()
        _, metrics = loadgen.http_json(port, "GET", "/metrics")
        engine_stats = metrics["default"]["continuous"]
        engine = {}
        for i, rec in enumerate(recs):
            assert rec["done"] and not rec["error"], rec["error"]
            engine[f"engine_{i}"] = np.asarray(prompts[i] + loadgen.tokens_of(rec))
        seqs = [[int(t) for t in rng.integers(1, vocab, forward_len)] for _ in prompts]
        at = sorted({int(p) for p in np.linspace(0, forward_len - 1, POSITIONS)})
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
    ssm = engine_stats.get("ssm", {})
    np.savez_compressed(out_path, seed=args.seed, rehearse=bool(args.rehearse),
                        prompts_len=np.asarray(prompts_len), **engine,
                        forward_tokens=np.asarray(seqs), forward_at=np.asarray(at), served=served,
                        slots_busy=engine_stats["active_peak"],
                        steps_live=ssm.get("steps_live", -1), steps_all=ssm.get("steps_all", -1))
    emit("collected", out=out_path, slots_busy_at_peak=int(engine_stats["active_peak"]),
         rows=len(prompts), prompts=prompts_len, engine_positions=DECODE, ssm=ssm,
         moe=engine_stats.get("moe"), kv=engine_stats.get("kv"), forward_positions=len(at),
         forward_sequence=forward_len)


@functools.lru_cache(maxsize=1)
def weights_of(model_dir: str) -> Streamed:
    return Streamed(model_dir)


def reference_pass(job):
    """One pass of the reference, in a worker process of :func:`judge`:
    (model dir, config.json, sequence, positions, control) -> (logits,
    seconds)."""
    model_dir, hf, seq, at, control = job
    os.environ["JAX_PLATFORMS"] = "cpu"  # this process is the reference
    from benchmark.references import nemotron_h as reference

    t0 = time.monotonic()
    how = {"cast": to_8_bits} if control else {}
    out = np.asarray(reference.forward(weights_of(model_dir), hf, seq, positions=at, **how))
    return out, round(time.monotonic() - t0, 1)


def judge(path: str, workers: int) -> bool:
    import concurrent.futures
    import multiprocessing

    data = dict(np.load(path))
    args = argparse.Namespace(seed=int(data["seed"]), rehearse=bool(data["rehearse"]))
    _, hf, model_dir, _ = the_cell(args)
    prompts_len = [int(n) for n in data["prompts_len"]]
    engine = [data[f"engine_{i}"] for i in range(len(prompts_len))]
    sd = lambda logits: np.std(logits, axis=-1)  # noqa: E731

    def margin(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
        return (ref.max(-1) - ref[np.arange(len(tokens)), tokens]) / sd(ref)

    # every pass is its own sequence at the published widths, in its own worker on the
    # CPU: (kind, row, control) -> logits; position p predicts token p + 1
    at = data["forward_at"].tolist()
    jobs = {}
    for i, (seq, n) in enumerate(zip(engine, prompts_len)):
        for control in (False, True):
            jobs["engine", i, control] = (model_dir, hf, seq, list(range(n - 1, len(seq) - 1)),
                                          control)
    for i, seq in enumerate(data["forward_tokens"]):
        for control in (False, True):
            jobs["forward", i, control] = (model_dir, hf, seq, at, control)
    with concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as pool:
        futures = {key: pool.submit(reference_pass, job) for key, job in jobs.items()}
        passes = {}
        for key, future in futures.items():
            passes[key], seconds = future.result()
            emit("reference_pass", of=key[0], row=key[1], control=key[2],
                 positions=len(jobs[key][3]), seconds=seconds)

    margins, agree, low_margins, low_agree = [], [], [], []
    for i, (seq, n) in enumerate(zip(engine, prompts_len)):
        ref, low = passes["engine", i, False], passes["engine", i, True]
        margins.append(margin(ref, seq[n:]))
        agree.append(ref.argmax(-1) == seq[n:])
        low_margins.append(margin(ref, low.argmax(-1)))
        low_agree.append(ref.argmax(-1) == low.argmax(-1))
    served = data["served"]
    if served.dtype == np.uint16:  # the top halves of a bfloat16 pod's logits
        served = (served.astype(np.uint32) << 16).view(np.float32)
    errors, low_errors = [], []
    for i in range(len(data["forward_tokens"])):
        ref, low = passes["forward", i, False], passes["forward", i, True]
        errors.append(np.sqrt(np.mean((served[i] - ref) ** 2, -1)) / sd(ref))
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
         slots_busy=int(data["slots_busy"]), steps_live=int(data["steps_live"]),
         steps_all=int(data["steps_all"]), forward_positions=len(at) * len(errors),
         engine_positions=int(sum(len(m) for m in margins)))
    emit("control_8_bit_weights_against_reference", **control,
         refused_by_every_limit=not any(control["held"].values()))
    return pod["ok"] and not any(control["held"].values())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on whatever jax finds")
    ap.add_argument("--collect", metavar="OUT.npz", help="only run the pod and record it")
    ap.add_argument("--judge", metavar="OUT.npz", help="only hold a record against the reference")
    ap.add_argument("--workers", type=int, default=4,
                    help="reference passes run side by side when judging (each reads the "
                         "checkpoint's 9.3 GB as it goes and keeps none of it)")
    args = ap.parse_args()
    if args.judge:
        ok = judge(args.judge, args.workers)
    else:
        out = args.collect or os.path.join(ROOT, ".cache", "benchmark", "compare_nemotron_h.npz")
        collect(args, out)
        ok = True if args.collect else judge(out, args.workers)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
