#!/usr/bin/env python3
"""Hold the served Laguna pod against the float32 reference.

    python3 benchmark/references/compare_laguna.py --collect OUT.npz [--seed N]   # on the chip
    python3 benchmark/references/compare_laguna.py --judge OUT.npz                # anywhere, on the CPU
    python3 benchmark/references/compare_laguna.py [--seed N] [--rehearse]        # both, in turn

A tool for the builder, not a part of a run: ``correct`` in ``run.py`` stays
what it is. **Collecting** writes the cell's checkpoint
(``benchmark/checkpoint.py``), starts ``modelx serve-model`` on it with the
configuration's own ``serve_args``, keeps every slot busy with long streaming
requests, and records what the served path produced at the published widths:

(i) ``/v1/forward`` logits (``logits_at``) at ``POSITIONS`` positions of each
    of ``ROWS`` rows, the rows longer than the window;
(ii) the engine's own greedy tokens of ``ROWS`` rows — prefill, then decode
    through the rings and the full caches, past position 512 and past the
    ring's 528 — beside the rows that keep the other slots busy.

**Judging** needs no chip (the collecting call holds one only while the pod
runs): it makes the same checkpoint from the same seed, runs
``references/laguna.py`` in this process on the CPU — float32 at ``highest``
precision, one sequence at a time, layer by layer — and measures, per
compared position, in units of the standard deviation of the reference's
logits over the vocabulary at that position (random weights give logits of
no natural scale):

- **forward error** ``e_p``: root mean square over the vocabulary of (served
  − reference);
- **engine margin** ``m_p``: how far the reference's logit of the engine's
  token (teacher-forced) lies below the reference's maximum. 0 where the
  engine's token is the reference's argmax.

Top-10 routing over 256 random routers has near-ties, and bfloat16 resolves
some of them otherwise than float32 does: such a token trades one of its ten
experts for a near-equal one in some layer, and its logits then differ by a
large part of a standard deviation though nothing is wrong. A worst case over
thousands of positions therefore measures the routing's ties, not the
program. The tolerances are on what a tie cannot move, and each is stated
with the readings that set it (my chip runs, PR 33, seed 2300000004, all 64
slots busy; a second seed, 3300000003, read inside them; PERF.md section 6):

- ``FORWARD_MEDIAN_TOL`` = 0.2: the median of ``e_p`` over the compared
  positions. bfloat16 rounds activations to 8 bits of mantissa in each of
  five layers: the pod read 0.063 (p90 0.197, worst 0.382, at the positions
  whose routing tied). Weights rounded to 8 bits (float8 e4m3: 3 bits of
  mantissa) read 0.501: the limit has a factor of three above the one reading
  and 2.5 below the other.
- ``ENGINE_AGREEMENT_MIN`` = 0.5 and ``ENGINE_MARGIN_P90_TOL`` = 0.3: the
  share of the engine's tokens that are the reference's argmax (pod 0.816,
  8-bit control 0.208), and the 90th percentile of ``m_p`` (pod 0.085 — past
  the ring 0.092 — control 1.23). A wrong cache offset, ring index, rope or
  mask agrees on a few per cent and sits several standard deviations down at
  nearly every position.
- ``ENGINE_MARGIN_WORST`` = 2.3: no single token may lie further below the
  reference's maximum than a routing tie explains. The pod's worst of 1,856
  positions read 1.80 (0.76 past the ring), the control's 2.82, a random
  token of a 50,176-word vocabulary lies about 4 down. An extreme of one
  seed on either side: the weakest of the four limits, kept because "every
  position" is what it says; the other three carry the control.

The control (always part of judging): the reference against itself with
every weight rounded to float8. A program that computed in a precision below
the configuration's would err so, and by at least one of the tolerances it is
refused.
"""

from __future__ import annotations

import argparse
import base64
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
from benchmark.procs import CLI, Children, emit, free_port, post_ok, wait_ready  # noqa: E402

from benchmark.run import META_KEYS  # noqa: E402  (what of a configuration file is not config.json)

CONFIG = "laguna-s-2.1-ep2-d5"
ROWS, POSITIONS = 4, 64
FORWARD_MEDIAN_TOL = 0.2
ENGINE_AGREEMENT_MIN = 0.5
ENGINE_MARGIN_P90_TOL = 0.3
ENGINE_MARGIN_WORST = 2.3


class Checkpoint:
    """The checkpoint's tensors by name, read from its safetensors files on
    first use and kept as float32 (a mapping for ``references/laguna.py``)."""

    DTYPES = {"BF16": (np.uint16, 2), "F32": (np.float32, 4)}

    def __init__(self, model_dir: str) -> None:
        self.where: dict[str, tuple] = {}
        self.kept: dict[str, np.ndarray] = {}
        for name in sorted(os.listdir(model_dir)):
            if not name.endswith(".safetensors"):
                continue
            path = os.path.join(model_dir, name)
            with open(path, "rb") as f:
                (hlen,) = struct.unpack("<Q", f.read(8))
                header = json.loads(f.read(hlen))
            for tensor, info in header.items():
                if tensor != "__metadata__":
                    self.where[tensor] = (path, 8 + hlen, info)

    def __getitem__(self, name: str) -> np.ndarray:
        if name not in self.kept:
            path, base, info = self.where[name]
            dtype, width = self.DTYPES[info["dtype"]]
            start, end = info["data_offsets"]
            raw = np.fromfile(path, dtype=dtype, count=(end - start) // width, offset=base + start)
            if info["dtype"] == "BF16":  # bf16 is the top half of a float32
                raw = (raw.astype(np.uint32) << 16).view(np.float32)
            self.kept[name] = raw.reshape(info["shape"])
        return self.kept[name]


def to_8_bits(x):
    """Round to float8 (e4m3: 3 bits of mantissa, the 8-bit float of today's
    accelerators) and back."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def the_cell(args):
    """(config as run, the checkpoint's config.json, model dir, serve args)."""
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
    vocab, window = config["vocab_size"], config["sliding_window"]
    serve_args = list(config["serve_args"])
    max_slots = int(serve_args[serve_args.index("--max-slots") + 1])
    max_len = int(serve_args[serve_args.index("--max-seq-len") + 1])
    ring = window + 16
    prompt_len = min(3 * window // 8, max_len // 4)
    n_decode = min(ring + window // 4 - prompt_len, max_len - prompt_len - 24)
    rng = np.random.default_rng([args.seed, 33])
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".cache", "xla")
    os.makedirs(cache_dir, exist_ok=True)
    kids = Children(os.path.join(work, "logs", "compare_laguna"), cache_dir)
    try:
        port = free_port()
        pod = kids.start("pod", CLI + ["serve-model", "--model-dir", model_dir, "--listen",
                                       f"127.0.0.1:{port}", "--drain-seconds", "0", *serve_args],
                         jax_child=not args.rehearse)
        wait_ready(port, pod, kids.log_dir, 1100)
        # every other slot busy for the whole collection: long streaming requests
        busy = max_slots - ROWS
        fillers = [threading.Thread(target=loadgen.stream_request, daemon=True, args=(
            port, [int(t) for t in rng.integers(1, vocab, 48)], max_len - 8 - 48 - 16))
            for _ in range(busy)]
        for t in fillers:
            t.start()
        time.sleep(2.0)
        prompts = [[int(t) for t in rng.integers(1, vocab, prompt_len)] for _ in range(ROWS)]
        recs: list = [None] * ROWS

        def one(i: int) -> None:
            recs[i] = loadgen.stream_request(port, prompts[i], n_decode)

        rows = [threading.Thread(target=one, args=(i,)) for i in range(ROWS)]
        for t in rows:
            t.start()
        for t in rows:
            t.join()
        _, metrics = loadgen.http_json(port, "GET", "/metrics")
        active_peak = metrics["default"]["continuous"]["active_peak"]
        engine = []
        for i, rec in enumerate(recs):
            assert rec["done"] and not rec["error"], rec["error"]
            engine.append(prompts[i] + loadgen.tokens_of(rec))
        seq_len = min(window + window // 4, max_len)
        seqs = [[int(t) for t in rng.integers(1, vocab, seq_len)] for _ in range(ROWS)]
        at = sorted({int(p) for p in np.linspace(0, seq_len - 1, POSITIONS)})
        got = post_ok(port, "/v1/forward", {"tokens": seqs, "logits_at": at})["logits"]
        served = np.frombuffer(base64.b64decode(got["b64"]), np.float32).reshape(got["shape"])
        # a bfloat16 pod's logits are bfloat16 values: their top halves lose
        # nothing and halve the record (a float32 pod's are kept whole)
        halves = (served.view(np.uint32) >> 16).astype(np.uint16)
        if np.array_equal((halves.astype(np.uint32) << 16).view(np.float32), served):
            served = halves
    finally:
        kids.stop_all()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    np.savez_compressed(out_path, seed=args.seed, rehearse=bool(args.rehearse),
                        engine=np.asarray(engine), prompt_len=prompt_len, forward_tokens=np.asarray(seqs),
                        forward_at=np.asarray(at), served=served, slots_busy=active_peak,
                        ring=ring, window=window)
    emit("collected", out=out_path, slots_busy_at_peak=int(active_peak), rows=ROWS,
         engine_positions=n_decode, last_position=prompt_len + n_decode - 1, ring=ring,
         forward_positions=len(at), forward_sequence=seq_len)


def quantiles(x: np.ndarray) -> dict:
    return {"median": float(np.median(x)), "p90": float(np.percentile(x, 90)),
            "worst": float(x.max())}


def judge(path: str) -> bool:
    os.environ["JAX_PLATFORMS"] = "cpu"  # this process is the reference
    from benchmark.references import laguna as reference

    data = np.load(path)
    args = argparse.Namespace(seed=int(data["seed"]), rehearse=bool(data["rehearse"]))
    _, hf, model_dir, _ = the_cell(args)
    weights = Checkpoint(model_dir)
    prompt_len = int(data["prompt_len"])
    sd = lambda logits: np.std(logits, axis=-1)  # noqa: E731

    def margin(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
        return (ref.max(-1) - ref[np.arange(len(tokens)), tokens]) / sd(ref)

    margins, agree, low_margins, low_agree = [], [], [], []
    for seq in data["engine"]:
        at = list(range(prompt_len - 1, len(seq) - 1))  # position p predicts token p + 1
        ref = np.asarray(reference.forward(weights, hf, seq, positions=at))
        margins.append(margin(ref, seq[prompt_len:]))
        agree.append(ref.argmax(-1) == seq[prompt_len:])
        low = np.asarray(reference.forward(weights, hf, seq, cast=to_8_bits, positions=at))
        low_margins.append(margin(ref, low.argmax(-1)))
        low_agree.append(ref.argmax(-1) == low.argmax(-1))
    at = data["forward_at"].tolist()
    served = data["served"]
    if served.dtype == np.uint16:  # the top halves of a bfloat16 pod's logits
        served = (served.astype(np.uint32) << 16).view(np.float32)
    errors, low_errors = [], []
    for i, seq in enumerate(data["forward_tokens"]):
        ref = np.asarray(reference.forward(weights, hf, seq, positions=at))
        errors.append(np.sqrt(np.mean((served[i] - ref) ** 2, -1)) / sd(ref))
        low = np.asarray(reference.forward(weights, hf, seq, cast=to_8_bits, positions=at))
        low_errors.append(np.sqrt(np.mean((low - ref) ** 2, -1)) / sd(ref))

    def verdict(errors, margins, agree) -> dict:
        e, m, a = quantiles(np.concatenate(errors)), quantiles(np.concatenate(margins)), float(
            np.mean(np.concatenate(agree)))
        past = np.concatenate([x[max(0, int(data["ring"]) - prompt_len):] for x in margins])
        held = {"forward_median": e["median"] <= FORWARD_MEDIAN_TOL,
                "engine_agreement": a >= ENGINE_AGREEMENT_MIN,
                "engine_margin_p90": m["p90"] <= ENGINE_MARGIN_P90_TOL,
                "engine_margin_worst": m["worst"] <= ENGINE_MARGIN_WORST}
        return {"forward_error_sd": e, "engine_margin_sd": m, "engine_argmax_agreement": a,
                "engine_margin_past_the_ring_sd": quantiles(past) if past.size else None,
                "held": held, "ok": all(held.values())}

    tolerances = {"forward_median_sd": FORWARD_MEDIAN_TOL, "engine_agreement": ENGINE_AGREEMENT_MIN,
                  "engine_margin_p90_sd": ENGINE_MARGIN_P90_TOL,
                  "engine_margin_worst_sd": ENGINE_MARGIN_WORST}
    pod = verdict(errors, margins, agree)
    control = verdict(low_errors, low_margins, low_agree)
    emit("pod_against_reference", **pod, tolerances=tolerances, seed=args.seed,
         slots_busy=int(data["slots_busy"]), forward_positions=len(at) * len(errors),
         engine_positions=int(sum(len(m) for m in margins)))
    emit("control_8_bit_weights_against_reference", **control, refused=not control["ok"])
    return pod["ok"] and not control["ok"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on whatever jax finds")
    ap.add_argument("--collect", metavar="OUT.npz", help="only run the pod and record it")
    ap.add_argument("--judge", metavar="OUT.npz", help="only hold a record against the reference")
    args = ap.parse_args()
    if args.judge:
        ok = judge(args.judge)
    else:
        out = args.collect or os.path.join(ROOT, ".cache", "benchmark", "compare_laguna.npz")
        collect(args, out)
        ok = True if args.collect else judge(out)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
