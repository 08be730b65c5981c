#!/usr/bin/env python3
"""Hold the served DeepSeek-V3.2-Exp pod against the float32 reference.

    python3 benchmark/references/compare_deepseek_v32.py --collect OUT.npz [--seed N]  # on the chip
    python3 benchmark/references/compare_deepseek_v32.py --judge OUT.npz               # anywhere
    python3 benchmark/references/compare_deepseek_v32.py [--seed N] [--rehearse]       # both, in turn
    ... --collect OUT.npz --fault recent     # a pod whose decode step keeps the LAST 2,048 positions

A tool for the builder, not a part of a run: ``correct`` in ``run.py`` stays
what it is. ``compare_deepseek_v2.py`` with a fifth reading (its checkpoint
reader and 8-bit rounding are ``compare_laguna.py``'s). **Collecting** writes
the cell's checkpoint, starts ``modelx serve-model`` on it with the
configuration's own ``serve_args`` (16 slots of 32,768 positions,
``--prefill-chunk 2048``) — the real CLI, loader and engine, behind a
launcher of this file's that wraps ``ops/index_select.select`` so that every
decode step also hands the host what it chose (a ``jax.debug.callback``; the
product has no such channel) — keeps EVERY slot busy with long streaming
requests, and records what the served path produced at the published widths:

(i) the engine's own greedy tokens of ``ROWS`` rows whose prompts of 8,240
    and 8,224 tokens land IN PIECES (four of 2,048 and a last one of 48 or 32:
    from the second on a piece scores the index keys of what landed before it
    and attends under the selection's mask) while the other slots decode, and
    which then decode ``DECODE`` tokens through BOTH cache leaves — index
    scores over 8.2 k keys, the 2,048 best, their lines gathered, the absorbed
    kernel over them — ``noaux_tc`` routing over the 16 held experts of 256 at
    every layer but the first;
(ii) THE ENGINE'S OWN SELECTION at every one of those decode steps and every
    layer: the positions ``select`` returned inside the engine's compiled
    chunk program, over the index leaf the loader's weights and the piece
    programs wrote — int32 ``[layers, DECODE - 1, index_topk]`` a row;
(iii) ``/v1/forward`` logits (``logits_at``) of ``ROWS`` sequences of 8,304
    tokens, one request a sequence, at ``POSITIONS`` positions spread over the
    sequence: the cache-less expanded form under the selection's mask.

**Judging** makes the same checkpoint from the same seed and runs
``references/deepseek_v32.py`` — float32 at ``highest`` precision, no cache, no
absorption, the selection by a plain sort, the attention ``HEAD_BLOCK`` heads
and the index scores ``QUERY_BLOCK`` queries at a time — in worker processes
on the CPU (each pass is kept under ``--passes``: judging again costs nothing).
It measures, per compared position, in units of the standard deviation of the
reference's logits over the vocabulary at that position, the four readings of
``compare_deepseek_v2.py`` (forward error median, engine argmax agreement,
engine margin p90 and worst), and a fifth:

- **selection overlap**: of the 2,048 positions the reference's ``S_t`` holds
  at a layer for the engine's sequence, the share the ENGINE's decode step
  chose too, over the decoded positions and all layers;
  ``SELECTION_OVERLAP_MIN`` holds its MEAN. It is the one reading that says
  WHERE a pod that reads badly went wrong: a wrong rope, norm, weight or
  offset in the indexer, or a key written to the wrong row of the leaf,
  overlaps at about 2,048 / context from layer 0 on (the planted fault reads
  0.249 at every layer), where the drift of bfloat16 leaves layer 0 at 0.997
  and takes a few per cent a layer (``by_layer``).

Three more verdicts ride every judging, each the same five readings:

- **the 8-bit control**: the reference against itself with every weight
  rounded to float8 (e4m3). A program that computed in a precision below the
  configuration's would err so; every one of the five limits refuses it.
- **the bf16 witness** (``--witness-rows``): the reference against itself
  with every ACTIVATION rounded to bfloat16 — the same plain equations in the
  precision the configuration states. What it reads is what that precision
  alone does to a model that chooses 2,048 lines of 8 k at every layer; the
  pod should read like it, and does (PERF.md section 6).
- a record collected with ``--fault`` is a PLANTED FAULT: its pod's decode
  step keeps the most recent 2,048 positions whatever the index scores say.
  Judging it succeeds when a pod-side limit refuses it.
"""

from __future__ import annotations

import argparse
import base64
import functools
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import checkpoint, loadgen  # noqa: E402
from benchmark.procs import Children, check, emit, free_port, post_ok, wait_ready  # noqa: E402
from benchmark.references.compare_laguna import Checkpoint, quantiles, to_8_bits  # noqa: E402
from benchmark.run import META_KEYS  # noqa: E402  (what of a configuration file is not config.json)

CONFIG = "deepseek-v3.2-exp-ep16-d5"
ROWS, POSITIONS, DECODE = 2, 32, 48
HEAD_BLOCK, QUERY_BLOCK = 8, 1024  # heads, queries whose scores the reference holds at a time
PIECES = 4  # of --prefill-chunk, then a last piece of 48 (row 0: the benchmark's probe) or 32
# each limit between the readings that bound it (my chip runs, PR 50, seeds 5000009009 and
# 5000031031, both rows of each kind: PERF.md section 6): the pod's on the two seeds and the
# reference's own in bfloat16 | the 8-bit control's and the planted fault's. The first
# limits tried were compare_deepseek_v2.py's (0.1 / 0.65 / 0.15 / 1.1) and the pod failed
# all four: they were V2's readings, of a model that chooses nothing — here the plain
# equations in the configuration's own precision read as the pod does
FORWARD_MEDIAN_TOL = 0.4  # 0.200, 0.195, bf16 0.208 | 0.801 (the fault is not in this path)
ENGINE_AGREEMENT_MIN = 0.3  # 0.500, 0.500, bf16 0.479 | 0.0625, fault 0.0104
ENGINE_MARGIN_P90_TOL = 1.5  # 0.637, 0.622, bf16 0.722 | 2.941, fault 4.886
ENGINE_MARGIN_WORST = 2.5  # 1.447, 1.383, bf16 1.125 | 4.747, fault 5.486
SELECTION_OVERLAP_MIN = 0.72  # 0.8757, 0.8808, bf16 0.8746 | 0.5477, fault 0.2490


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


def tapped_pod(tap_dir: str, layers: int, above: int, fault: str, cli_args: list[str]) -> None:
    """``python -m modelx_tpu.cli <cli_args>`` in this process, with
    ``ops/index_select.select`` wrapped: what a decode step chose goes to the
    host as ``<tap_dir>/<layer>-<slot>-<length>.npy`` for every row whose
    context is past ``above`` (the probes; the fillers stay below it).
    ``fault`` ``"recent"`` plants the wrong selection: the last ``k`` positions."""
    import itertools

    import jax
    import jax.numpy as jnp

    from modelx_tpu.ops import index_select

    plain, traced = index_select.select, threading.local()

    def record(layer: int, lengths, chosen) -> None:
        lengths, chosen = np.asarray(lengths), np.asarray(chosen)
        for slot in np.nonzero(lengths > above)[0]:
            path = os.path.join(tap_dir, f"{layer}-{slot}-{int(lengths[slot])}")
            np.save(path + ".part.npy", chosen[slot])
            os.replace(path + ".part.npy", path + ".npy")

    def tapped(scores, lengths, k: int):
        if fault == "recent":
            chosen = (jnp.maximum(lengths - k, 0)[:, None] + jnp.arange(k)[None, :]).astype(jnp.int32)
        else:
            chosen = plain(scores, lengths, k)
        # a program is traced in one thread, its layers in order, a call a layer
        if not hasattr(traced, "calls"):
            traced.calls = itertools.count()
        jax.debug.callback(functools.partial(record, next(traced.calls) % layers), lengths, chosen)
        return chosen

    index_select.select = tapped
    from modelx_tpu.cli import main as cli

    cli(args=cli_args, prog_name="modelx")


def tapped_selection(tap_dir: str, prompt_lens: list[int], layers: int) -> list[np.ndarray]:
    """What the tap wrote, a probe row at a time: int32 ``[layers, DECODE - 1,
    k]``."""
    by_slot: dict = {}
    for name in os.listdir(tap_dir):
        if name.endswith(".npy") and ".part." not in name:
            layer, slot, length = (int(x) for x in name[:-4].split("-"))
            by_slot.setdefault(slot, {})[layer, length] = os.path.join(tap_dir, name)
    rows, taken = [], set()
    for p in sorted(prompt_lens):
        need = [(i, n) for i in range(layers) for n in range(p + 1, p + DECODE)]
        # a slot that is still being filled rides the others' steps as a pad row, at lengths
        # below its prompt's: of the slots that hold every step of this row, the one that
        # stopped first is the row's
        slots = sorted((max(n for _, n in got), s) for s, got in by_slot.items()
                       if s not in taken and all(x in got for x in need))
        check(bool(slots), f"the tap holds no slot with every decode step of the row of {p}: "
              + str({s: (min(n for _, n in g), max(n for _, n in g)) for s, g in by_slot.items()}))
        got = by_slot[slots[0][1]]
        taken.add(slots[0][1])
        rows.append((p, np.stack([np.stack([np.load(got[i, n]) for n in range(p + 1, p + DECODE)])
                                  for i in range(layers)])))
    rows = [dict(rows)[p] for p in prompt_lens]
    return rows


def collect(args, out_path: str) -> None:
    config, hf, model_dir, work = the_cell(args)
    vocab, layers = config["vocab_size"], int(hf["num_hidden_layers"])
    serve_args = list(config["serve_args"])
    max_slots = int(serve_args[serve_args.index("--max-slots") + 1])
    max_len = int(serve_args[serve_args.index("--max-seq-len") + 1])
    piece = int(serve_args[serve_args.index("--prefill-chunk") + 1])
    # pieces of --prefill-chunk, then a last one of 48, 32, ..: no two rows of one length
    prompt_lens = [PIECES * piece + 3 * 16 - 16 * i for i in range(ROWS)]
    assert prompt_lens[0] + DECODE + 24 <= max_len and prompt_lens[-1] > piece
    rng = np.random.default_rng([args.seed, 43])
    # a cache of its own: the engine's stored executables are keyed BEFORE tracing, and a
    # tapped program must neither be taken for the cell's nor be loaded in its place
    cache_dir, tap_dir = os.path.join(work, "xla-tapped"), os.path.join(work, "tap")
    for path in (cache_dir, tap_dir):
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
    kids = Children(os.path.join(work, "logs", "compare_deepseek_v32"), cache_dir)
    try:
        port = free_port()
        pod = kids.start("pod", [os.path.abspath(__file__), "--tapped-pod", tap_dir, str(layers),
                                 str(prompt_lens[-1]), args.fault, "serve-model", "--model-dir",
                                 model_dir, "--listen", f"127.0.0.1:{port}", "--drain-seconds",
                                 "0", *serve_args], jax_child=not args.rehearse)
        wait_ready(port, pod, kids.log_dir, 1100)
        # every other slot busy while the rows land and decode: long streaming requests,
        # which end before their contexts reach the probes' (the tap tells rows by length)
        busy = max_slots - ROWS
        budget = min(max_len - 8 - 48 - 16, prompt_lens[-1] - 48 - 64)
        fillers = [threading.Thread(target=loadgen.stream_request, daemon=True, args=(
            port, [int(t) for t in rng.integers(1, vocab, 48)], budget),
            kwargs={"timeout": 3000.0}) for _ in range(busy)]
        for t in fillers:
            t.start()
        time.sleep(5.0)
        prompts = [[int(t) for t in rng.integers(1, vocab, n)] for n in prompt_lens]
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
            engine.append(np.asarray(prompts[i] + loadgen.tokens_of(rec)))
        time.sleep(2.0)  # a callback may trail the tokens it rode with
        chosen = tapped_selection(tap_dir, prompt_lens, layers)
        seq_len = prompt_lens[0] + 64
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
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(tap_dir, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    np.savez_compressed(out_path, seed=args.seed, rehearse=bool(args.rehearse), fault=args.fault,
                        prompt_lens=np.asarray(prompt_lens),
                        **{f"engine_{i}": row for i, row in enumerate(engine)},
                        **{f"chosen_{i}": row for i, row in enumerate(chosen)},
                        forward_tokens=np.asarray(seqs), forward_at=np.asarray(at), served=served,
                        slots_busy=engine_stats["active_peak"],
                        fill_pieces=engine_stats.get("fill", {}).get("pieces", -1),
                        steps_absorbed=engine_stats.get("mla", {}).get("steps_absorbed", -1),
                        steps_all=engine_stats.get("mla", {}).get("steps_all", -1))
    emit("collected", out=out_path, fault=args.fault,
         slots_busy_at_peak=int(engine_stats["active_peak"]), rows=ROWS,
         engine_positions=DECODE, first_decoded_positions=prompt_lens,
         selections=[list(c.shape) for c in chosen],
         fill=engine_stats.get("fill"), mla=engine_stats.get("mla"), moe=engine_stats.get("moe"),
         dsa=engine_stats.get("dsa"),
         kv=engine_stats.get("kv"),
         forward_positions=len(at), forward_sequence=seq_len)


@functools.lru_cache(maxsize=1)
def weights_of(model_dir: str) -> Checkpoint:
    """A worker's checkpoint, read once: every pass it is given is of one model."""
    return Checkpoint(model_dir)


def to_bf16(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


def reference_pass(job):
    """One pass of the reference, in a worker process of :func:`judge`:
    (model dir, config.json, sequence, positions, how, keep the selection, the
    file it is kept in) -> (logits, the
    selection at the positions ``[layers, positions, T]`` bits or None,
    seconds). ``how``: ``""`` the reference, ``"8bit"`` the control (weights
    rounded), ``"bf16"`` the witness (activations rounded)."""
    model_dir, hf, seq, at, how, keep, kept_in = job
    if os.path.exists(kept_in):
        with np.load(kept_in) as got:
            return got["logits"], got["kept"] if keep else None, 0.0
    os.environ["JAX_PLATFORMS"] = "cpu"  # this process is the reference
    from benchmark.references import deepseek_v32 as reference

    t0 = time.monotonic()
    hooks = {"8bit": {"cast": to_8_bits}, "bf16": {"cast_activations": to_bf16}}.get(how, {})
    chosen: list | None = [] if keep else None
    out = np.asarray(reference.forward(weights_of(model_dir), hf, seq, positions=at,
                                       head_block=HEAD_BLOCK, query_block=QUERY_BLOCK,
                                       selected=chosen, **hooks))
    kept = np.packbits(np.stack([layer[np.asarray(at)] for layer in chosen]), axis=-1) \
        if keep else np.zeros(0, np.uint8)
    np.savez(kept_in + ".part.npz", logits=out, kept=kept)
    os.replace(kept_in + ".part.npz", kept_in)
    return out, kept if keep else None, round(time.monotonic() - t0, 1)


def judge(path: str, control_rows: int, workers: int, rows: int = ROWS,
          witness_rows: int = 1, passes_dir: str = "") -> bool:
    import concurrent.futures
    import hashlib
    import multiprocessing

    data = dict(np.load(path))
    fault = str(data["fault"])
    engine = [data[f"engine_{i}"] for i in range(rows)]
    mine = [data[f"chosen_{i}"] for i in range(rows)]  # [layers, DECODE - 1, k] a row
    prompt_lens = [int(p) for p in data["prompt_lens"][:rows]]
    for key in ("forward_tokens", "served"):  # the first ``rows`` of each
        data[key] = data[key][:rows]
    args = argparse.Namespace(seed=int(data["seed"]), rehearse=bool(data["rehearse"]))
    _, hf, model_dir, _ = the_cell(args)
    passes_dir = passes_dir or os.path.join(os.path.dirname(os.path.abspath(path)), "passes")
    os.makedirs(passes_dir, exist_ok=True)
    sd = lambda logits: np.std(logits, axis=-1)  # noqa: E731

    def margin(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
        return (ref.max(-1) - ref[np.arange(len(tokens)), tokens]) / sd(ref)

    # every pass is its own sequence of 8 k positions at the published widths — minutes
    # each on the CPU, where they run side by side: (kind, row, how) -> logits
    # position p predicts token p + 1; the step that reads position p selects for it
    engine_at = [list(range(p - 1, len(seq) - 1)) for p, seq in zip(prompt_lens, engine)]
    at = data["forward_at"].tolist()
    jobs = {}
    for kind, seqs, at_these in (("engine", engine, engine_at),
                                 ("forward", data["forward_tokens"], [at] * rows)):
        for i, seq in enumerate(seqs):
            hows = [""] + ["8bit"] * (i < control_rows) + ["bf16"] * (i < witness_rows)
            for how in hows:
                tag = hashlib.sha1(repr((args.seed, args.rehearse, how, at_these[i])).encode()
                                   + np.asarray(seq, np.int64).tobytes()).hexdigest()[:20]
                jobs[kind, i, how] = (model_dir, hf, seq, at_these[i], how, kind == "engine",
                                      os.path.join(passes_dir, tag + ".npz"))
    with concurrent.futures.ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as pool:
        futures = {key: pool.submit(reference_pass, job) for key, job in jobs.items()}
        passes, chosen = {}, {}
        for key, future in futures.items():
            passes[key], chosen[key], seconds = future.result()
            emit("reference_pass", of=key[0], row=key[1], how=key[2] or "float32",
                 positions=len(jobs[key][3]), seconds=seconds)

    served = data["served"]
    if served.dtype == np.uint16:  # the top halves of a bfloat16 pod's logits
        served = (served.astype(np.uint32) << 16).view(np.float32)
    bits = lambda packed, n: np.unpackbits(packed, axis=-1)[..., :n].astype(bool)  # noqa: E731

    def readings(how: str, over: int) -> dict | None:
        """The five readings of the pod (``how`` ""), or of the reference
        computed ``how`` against itself, over the first ``over`` rows."""
        errors, margins, agree, overlaps = [], [], [], []
        for i in range(over):
            ref, p, seq = passes["engine", i, ""], prompt_lens[i], engine[i]
            tokens = seq[p:] if not how else passes["engine", i, how].argmax(-1)
            margins.append(margin(ref, tokens))
            agree.append(ref.argmax(-1) == tokens)
            # the reference's S_t of the positions a decode step read: at[1:], p .. p + DECODE - 2
            theirs = bits(chosen["engine", i, ""], len(seq))[:, 1:]
            if how:
                held = bits(chosen["engine", i, how], len(seq))[:, 1:]
                overlaps.append((held & theirs).sum(-1) / theirs.sum(-1))
            else:
                held = np.take_along_axis(theirs, mine[i].astype(np.int64), axis=-1)
                overlaps.append(held.sum(-1) / theirs.sum(-1))
            ref = passes["forward", i, ""]
            got = served[i] if not how else passes["forward", i, how]
            errors.append(np.sqrt(np.mean((got - ref) ** 2, -1)) / sd(ref))
        if not over:
            return None
        e, m, a = quantiles(np.concatenate(errors)), quantiles(np.concatenate(margins)), float(
            np.mean(np.concatenate(agree)))
        o = np.concatenate(overlaps, axis=1)  # [layers, rows x (DECODE - 1)]
        held = {"forward_median": e["median"] <= FORWARD_MEDIAN_TOL,
                "engine_agreement": a >= ENGINE_AGREEMENT_MIN,
                "engine_margin_p90": m["p90"] <= ENGINE_MARGIN_P90_TOL,
                "engine_margin_worst": m["worst"] <= ENGINE_MARGIN_WORST,
                "selection_overlap": float(o.mean()) >= SELECTION_OVERLAP_MIN}
        return {"forward_error_sd": e, "engine_margin_sd": m, "engine_argmax_agreement": a,
                # layer 0 reads the same inputs on both sides (the embedding): its overlap is
                # what the index scores' own precision does; deeper layers add the stream's drift
                "selection_overlap": {"mean": float(o.mean()), "least": float(o.min()),
                                      "positions": int(o.size),
                                      "by_layer": [round(float(x), 4) for x in o.mean(axis=1)]},
                "held": held, "ok": all(held.values()), "rows": over}

    tolerances = {"forward_median_sd": FORWARD_MEDIAN_TOL, "engine_agreement": ENGINE_AGREEMENT_MIN,
                  "engine_margin_p90_sd": ENGINE_MARGIN_P90_TOL,
                  "engine_margin_worst_sd": ENGINE_MARGIN_WORST,
                  "selection_overlap_mean": SELECTION_OVERLAP_MIN}
    pod = readings("", rows)
    emit("pod_against_reference", **pod, tolerances=tolerances, seed=args.seed, fault=fault,
         selection_of="the engine's decode steps (the tap)",
         slots_busy=int(data["slots_busy"]), fill_pieces=int(data["fill_pieces"]),
         steps_absorbed=int(data["steps_absorbed"]), steps_all=int(data["steps_all"]),
         forward_positions=len(at) * rows,
         engine_positions=sum(len(a) for a in engine_at))
    witness = readings("bf16", min(witness_rows, rows))
    if witness:
        emit("reference_in_bf16_against_reference", **witness)
    control = readings("8bit", min(control_rows, rows))
    refused = control is None or not any(control["held"].values())
    if control:
        emit("control_8_bit_weights_against_reference", **control, refused_by_every_limit=refused)
    if fault:  # a planted fault: the pod must NOT pass
        refusing = [name for name, ok in pod["held"].items() if not ok]
        emit("planted_fault", fault=fault, refused_by=refusing)
        return bool(refusing) and refused
    return pod["ok"] and refused


def main() -> int:
    if sys.argv[1:2] == ["--tapped-pod"]:  # collect's own child: the pod, tapped
        tap_dir, layers, above, fault = sys.argv[2:6]
        tapped_pod(tap_dir, int(layers), int(above), fault, sys.argv[6:])
        return 0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on whatever jax finds")
    ap.add_argument("--collect", metavar="OUT.npz", help="only run the pod and record it")
    ap.add_argument("--judge", metavar="OUT.npz", help="only hold a record against the reference")
    ap.add_argument("--fault", choices=("", "recent"), default="",
                    help="collect from a pod with a planted fault: its decode step keeps the "
                         "most recent index_topk positions; judging it must refuse it")
    ap.add_argument("--control-rows", type=int, default=1,
                    help="rows the 8-bit control is computed on (each two reference passes)")
    ap.add_argument("--witness-rows", type=int, default=1,
                    help="rows the reference is also computed on with bfloat16 activations")
    ap.add_argument("--rows", type=int, default=ROWS,
                    help="rows of each kind that are judged (a pass of 8,3xx positions at the "
                         "published widths is 14 minutes on 8 cores)")
    ap.add_argument("--workers", type=int, default=3,
                    help="reference passes run side by side when judging on the CPU (each "
                         "holds the weights in float32: 12.6 GB at the published widths)")
    ap.add_argument("--passes", metavar="DIR", default="",
                    help="where reference passes are kept (default: passes/ beside the record)")
    args = ap.parse_args()
    judging = functools.partial(judge, control_rows=args.control_rows, workers=args.workers,
                                rows=args.rows, witness_rows=args.witness_rows,
                                passes_dir=args.passes)
    if args.judge:
        ok = judging(args.judge)
    else:
        out = args.collect or os.path.join(ROOT, ".cache", "benchmark", "compare_deepseek_v32.npz")
        collect(args, out)
        ok = True if args.collect else judging(out)
    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
