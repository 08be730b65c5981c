#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 benchmark/run.py --workload <cell> --rehearse

One run measures one cell of ``BENCHMARK.json`` on the machine it is started
on, through the program's console entry points (``python -m modelx_tpu.cli
serve | push | dl | serve-model``), and prints as its LAST line of stdout the
object the contract names: ``correct``, ``attempted``, ``failed``, ``metrics``
(``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: its per-layer
metrics), ``device``, traced ``breakdown``, and last ``compared``: every number
``correct`` was decided from beside its limit, which are also the last lines of
stderr. Earlier lines are one JSON object per phase: medians, counts, stage
times, the generator's lateness.

The parent never imports jax: a chip belongs to one process at a time, so
every process that may touch it is a child, one at a time, and children that
must not get ``JAX_PLATFORMS=cpu``. Where jax finds no accelerator, or fewer
chips than the cell asks for, the run exits non-zero and prints no result.
``--rehearse`` walks the same code at the tiny presets of the configuration
and traffic files on whatever jax finds, and always ends ``"correct": false``.

Everything that belongs to one cell is data found by name (README.md): the
configuration, the traffic mix, the generator of its kind, one reader per
per-layer metric, the tensor layout of its family.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import importlib
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import checkpoint, loadgen, stats  # noqa: E402
from benchmark.procs import (CLI, Children, Fail, check, emit, free_port, http_json,  # noqa: E402
                             post_ok, wait_ready)

# Engine greedy tokens against the argmax of the pod's own teacher-forced
# /v1/forward. The two run different programs (cached decode with
# attention_reference; one cache-less pass through the flash kernel), so on
# random weights in bf16 a near-tie between two logits can flip a position.
# 0.9 is the share chip_smoke.py holds its tp=4-vs-one-chip argmax
# comparison to (TP_MIN_ARGMAX_AGREEMENT), for the same reason; a wrong
# program (bad cache offset, wrong rope, dropped expert) agrees on far fewer.
# A configuration may state another share with its reason
# (``min_argmax_agreement``): a sparse-expert model does, see its file. It may
# also state how many prompts the probe sends (``probes``, with its reason): the
# share is held over ``probes`` x ``new_tokens`` tokens, and over few of them a
# sound pod's near-ties alone cross the tolerance now and then.
MIN_ARGMAX_AGREEMENT = 0.9
PROBES = 4
META_KEYS = {"source", "family", "reduced", "reduced_from", "assumed", "deployment", "chips",
             "serve_args", "bytes_predicted", "bytes_measured", "rehearse", "checkpoint_dtype",
             "min_argmax_agreement", "min_argmax_agreement_why", "probes", "probes_why"}


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def arg_after(argv: list[str], flag: str) -> int:
    return int(argv[argv.index(flag) + 1])


class Run:
    """One run of one cell: its data, its children, what it has collected."""

    def __init__(self, args) -> None:
        self.args = args
        self.t_start = time.monotonic()
        bench = load_json(ROOT, "BENCHMARK.json")
        self.bench = bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise Fail(f"BENCHMARK.json names no workload {args.workload!r}: {sorted(cells)}")
        self.cell = cells[args.workload]
        entry = next(c for c in bench["configs"] if c["name"] == self.cell["config"])
        self.config = load_json(ROOT, entry["file"])
        self.traffic = load_json(HERE, "traffic", self.cell["traffic"] + ".json")
        if args.rehearse:
            self.config.update(self.config.get("rehearse", {}))
            self.traffic.update(self.traffic.get("rehearse", {}))
        self.seconds = float(args.seconds)
        self.serve_args = list(self.config["serve_args"])
        self.max_seq_len = arg_after(self.serve_args, "--max-seq-len")
        self.max_slots = arg_after(self.serve_args, "--max-slots")
        self.work = os.path.join(ROOT, ".cache", "benchmark")
        self.cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            ROOT, ".cache", "xla")
        os.makedirs(self.cache_dir, exist_ok=True)
        log_dir = os.path.join(self.work, "logs", self.cell["name"])
        shutil.rmtree(log_dir, ignore_errors=True)
        self.kids = Children(log_dir, self.cache_dir)
        self.device: dict = {}
        self.memory_peak = 0
        self.sources: dict = {"cell": self.cell["name"], "config": self.config,
                              "traffic": self.traffic, "model": "default",
                              "max_slots": self.max_slots}

    # -- set-up ---------------------------------------------------------------

    def probe_and_checkpoint(self) -> None:
        """What jax finds, asked from a child while the parent writes the
        checkpoint (numpy only), so that the probe's 10-15 s hide behind it."""
        code = ("import json, jax; d = jax.devices(); "
                "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind, "
                "'count': len(d), 'jax': jax.__version__}))")
        cancelled = threading.Event()
        hf = {k: v for k, v in self.config.items() if k not in META_KEYS}
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            writing = pool.submit(
                checkpoint.ensure, os.path.join(self.work, "checkpoint"), self.cell["config"],
                self.config["family"], self.config, hf, self.args.seed,
                self.config.get("checkpoint_dtype", "BF16"), cancelled)
            try:
                out = self.kids.run("device-probe", ["-c", code], True, 300)
                probe = json.loads(out.strip().splitlines()[-1])
                if probe["platform"] == "cpu" and not self.args.rehearse:
                    raise Fail("jax found no accelerator (platform cpu): nothing to measure")
                check(probe["count"] >= self.cell["chips"],
                      f"the cell needs {self.cell['chips']} chips, jax found {probe['count']}")
            except Fail:
                cancelled.set()
                writing.exception()  # wait for the writer to stop
                shutil.rmtree(os.path.join(self.work, "checkpoint", self.cell["config"]),
                              ignore_errors=True)
                raise
            self.model_dir, self.ckpt_bytes, wrote_s = writing.result()
        emit("device", **probe, compile_cache_dir=self.cache_dir)
        peaks = load_json(HERE, "peaks.json")
        if probe["kind"] in peaks:
            self.sources["peaks"] = peaks[probe["kind"]]
        elif not self.args.rehearse:
            raise Fail(f"benchmark/peaks.json has no row for device_kind {probe['kind']!r}")
        emit("checkpoint", config=self.cell["config"], seed=self.args.seed,
             bytes=self.ckpt_bytes, wrote_seconds=round(wrote_s, 2),
             gb_per_s=round(self.ckpt_bytes / wrote_s / 1e9, 2) if wrote_s else None)

    def start_pod(self, name: str, model_dir: str, trace_dir: str = ""):
        port = free_port()
        argv = CLI + ["serve-model", "--model-dir", model_dir, "--listen", f"127.0.0.1:{port}",
                      "--drain-seconds", "0", *self.serve_args]
        if trace_dir:
            argv += ["--trace-dir", trace_dir]
        t0 = time.monotonic()
        pod = self.kids.start(name, argv, jax_child=True)
        seen: dict = {}
        wait_ready(port, pod, self.kids.log_dir, 1100, seen=seen)
        self.listen_s = seen["listen_at"] - t0  # spawn -> the port answers (503: loading)
        return pod, port, time.monotonic() - t0

    def pod_report(self, port: int) -> dict:
        """What the SERVING process says it runs on and holds."""
        _, metrics = http_json(port, "GET", "/metrics")
        dev, model = metrics.get("device", {}), metrics.get("default", {})
        check(bool(dev.get("platform")), f"/metrics device block names no platform: {dev}")
        check(model.get("load_bytes") == self.ckpt_bytes,
              f"loaded {model.get('load_bytes')} bytes, the checkpoint has {self.ckpt_bytes}")
        if not self.args.rehearse:
            check(dev["platform"] != "cpu", "the serving process runs on the CPU")
            check(dev["device_count"] == self.cell["chips"],
                  f"the serving process sees {dev['device_count']} devices, the cell asks "
                  f"{self.cell['chips']}")
            check(model.get("native_io") is True,
                  "the serving process loaded its weights without the native IO engine")
        self.device = {"platform": dev["platform"], "kind": dev["device_kind"],
                       "count": dev["device_count"]}
        self.note_memory(metrics)
        return metrics

    def note_memory(self, metrics: dict) -> None:
        self.memory_peak = max(self.memory_peak,
                               loadgen.MetricsPoller.fullest(metrics.get("device", {})))

    def sweep_schedules(self) -> dict:
        """--sweep-rates: one open-loop schedule per rate, else none."""
        out = {}
        for rate in self.args.sweep_rates:
            generator = importlib.import_module(f"benchmark.generators.{self.traffic['generator']}")
            out[rate] = generator.schedule(self.args.seed, dict(self.traffic, rate_rps=rate),
                                           self.config["vocab_size"], self.seconds,
                                           self.max_seq_len)
        return out

    # -- correctness ----------------------------------------------------------

    def probes(self, port: int, spec: dict) -> dict:
        """Engine greedy tokens against the pod's own teacher-forced forward."""
        import numpy as np

        rng = np.random.default_rng([self.args.seed, 4])
        vocab, n = self.config["vocab_size"], spec["new_tokens"]
        count = self.config.get("probes", PROBES)  # one stream: the first PROBES prompts are the same
        agree = first = 0
        t0 = time.monotonic()
        for _ in range(count):
            prompt = [int(t) for t in rng.integers(1, vocab, spec["prompt_tokens"])]
            rec = loadgen.stream_request(port, prompt, n)
            check(rec["done"] and not rec["error"], f"probe request failed: {rec['error']}")
            toks = loadgen.tokens_of(rec)
            check(len(toks) == n, f"probe asked {n} tokens, got {len(toks)}")
            fwd = post_ok(port, "/v1/forward", {"tokens": [prompt + toks]})["logits_argmax"][0]
            check(len(set(fwd)) > 1, "forward argmax is constant (non-finite logits?)")
            hits = [fwd[len(prompt) - 1 + i] == toks[i] for i in range(n)]
            agree += sum(hits)
            first += hits[0]
        share = agree / (count * n)
        tolerance = self.config.get("min_argmax_agreement", MIN_ARGMAX_AGREEMENT)
        out = {"probes": count, "tokens": count * n, "argmax_agreement": share,
               "first_token_agrees": first, "tolerance": tolerance, "ok": share >= tolerance,
               "seconds": time.monotonic() - t0}
        emit("probes", **out)
        return out

    def check_tokens(self, recs: list[dict]) -> tuple[int, list[str]]:
        """Completed requests gave the number of tokens asked, all inside the
        vocabulary. Returns (completed, what was wrong)."""
        vocab, wrong, done = self.config["vocab_size"], [], 0
        for r in recs:
            if r["error"] or r["cut"]:
                continue
            done += 1
            toks = loadgen.tokens_of(r)
            if len(toks) != r["asked"]:
                wrong.append(f"asked {r['asked']} tokens, got {len(toks)}")
            elif not all(0 <= t < vocab for t in toks):
                wrong.append("token id outside the vocabulary")
        return done, wrong

    # -- tracing --------------------------------------------------------------

    def trace_dir(self) -> str:
        path = os.path.join(self.work, "trace", self.cell["name"])
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
        return path

    def profile(self, port: int, seconds: float) -> None:
        """The pod's own POST /v1/profile, with the engine's counters read
        just before it is sent and ``seconds`` after, by the clock: the
        profiler's stop can outlast the load by a minute and more, so the POST
        goes on a thread of its own and its return (``post_seconds``) is
        waited for only after the second read."""
        answer: list = []
        _, before = http_json(port, "GET", "/metrics")
        t0 = time.monotonic()
        post = threading.Thread(target=lambda: answer.append(http_json(
            port, "POST", "/v1/profile", {"seconds": seconds}, timeout=seconds + 300)))
        post.start()
        post.join(seconds)
        _, after = http_json(port, "GET", "/metrics")
        span = time.monotonic() - t0
        post.join()
        status, data = answer[0] if answer else (None, "the POST raised")
        if status != 200:
            emit("profile", error=f"{status}: {data}")
            return
        post_s = time.monotonic() - t0
        self.sources["trace_span"] = {"metrics_before": before, "metrics_after": after,
                                      "seconds": span, "post_seconds": post_s}
        emit("profile", seconds=round(span, 3), post_seconds=round(post_s, 3))

    def reduce_trace(self, path: str) -> None:
        """After the pod has stopped: a child under JAX_PLATFORMS=cpu reads
        the .xplane.pb."""
        try:
            out = self.kids.run("xplane", [os.path.join(HERE, "xplane.py"), path],
                                jax_child=False, timeout=600)
        except Fail as e:
            emit("trace", error=str(e)[-500:])
            return
        trace = json.loads(out.strip().splitlines()[-1])
        self.sources["trace"] = trace
        emit("trace", window_s=trace.get("window_s"), busy_s=trace.get("busy_s"),
             device_planes=trace.get("device_planes"),
             modules={k: round(v["seconds"], 4) for k, v in sorted(
                 trace.get("modules", {}).items(), key=lambda kv: -kv[1]["seconds"])[:12]})
        if self.args.keep_trace:
            keep = os.path.join(ROOT, "chiprun_out", "trace-" + self.cell["name"])
            os.makedirs(keep, exist_ok=True)
            desc = self.kids.run("xplane-describe", [os.path.join(HERE, "xplane.py"), path,
                                                     "--describe"], jax_child=False, timeout=600)
            with open(os.path.join(keep, "describe.json"), "w") as f:
                f.write(desc)
            with open(os.path.join(keep, "reduced.json"), "w") as f:
                json.dump(trace, f)

    # -- the last line --------------------------------------------------------

    def metric_defs(self, group: str) -> list[dict]:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.cell["name"] in m["workloads"]]

    def layer_metrics(self) -> dict:
        """The cell's per-layer metrics, each from its own reader. A trace
        without a device plane (a CPU rehearsal) yields no device metric:
        what its readers give goes on an earlier line, under another name."""
        on_device = (self.sources.get("trace") or {}).get("device_planes", 0) > 0
        out, rehearsed = {}, {}
        for m in self.metric_defs("per_layer"):
            spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
            reader = importlib.import_module(f"benchmark.layer_metrics.readers.{spec['reader']}")
            value = reader.read(self.sources, spec)
            if value is None:  # a reader that finds nothing returns nothing
                continue
            if m["source"] == "device_trace" and not on_device:
                rehearsed[m["name"]] = value
            else:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        if rehearsed:
            emit("rehearsed_on_a_cpu_trace_not_device_metrics", **rehearsed)
        return out

    def result(self, attempted: int, failed: int, end_to_end: dict, compared: dict) -> dict:
        """The last line. ``compared`` is every number ``correct`` is decided from,
        ``name_min`` or ``name_max`` -> (value, limit): the run is correct where each
        holds, and the line carries them all, last."""
        correct = all(v >= lim if name.endswith("_min") else v <= lim
                      for name, (v, lim) in compared.items())
        units = {m["name"]: m["unit"] for m in self.metric_defs("end_to_end")}
        missing = sorted(set(units) - set(end_to_end))
        check(not missing, f"the run measured no {missing}")
        e2e = {k: {"value": end_to_end[k], "unit": units[k]} for k in units}
        device = dict(self.device, memory_peak_bytes=self.memory_peak)
        out = {"correct": bool(correct) and not self.args.rehearse, "attempted": attempted,
               "failed": failed}
        if self.args.trace:
            emit("end_to_end", **{k: v["value"] for k, v in e2e.items()})
            check("trace_span" in self.sources,
                  "the traced run has no trace_span: /v1/profile failed or did not return")
            trace = self.sources.get("trace") or {}
            check(trace.get("busy_s", 0) > 0, "the trace shows no operation on the device")
            out["metrics"] = self.layer_metrics()
            if trace["device_planes"]:
                device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
                out["breakdown"] = {"device_ops": trace["device_ops"],
                                    "idle_gaps": trace["idle_gaps"]}
        else:
            out["metrics"] = e2e
        out["device"] = device
        if self.args.rehearse:
            out["rehearsal"] = True
        out["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
        return out


# -- the three modes of a schedule ----------------------------------------------


def warm_up(run: Run, port: int, requests: list[dict]) -> dict:
    """Compile (or read from the persistent cache) every program the window
    will use, and no other: the chunk programs at every depth of the engine's
    power-of-two ladder (one request long enough to walk it), the admit
    program at every 16-token bucket the schedule hits, and the batched admit
    program at the group sizes the traffic file names, at the same buckets."""
    import numpy as np

    rng = np.random.default_rng([run.args.seed, 5])
    vocab = run.config["vocab_size"]
    buckets = sorted({stats.pad16(len(r["prompt"])) for r in requests})
    t0 = time.monotonic()
    ladder = min(72, run.max_seq_len - 8 - buckets[0])
    rec = loadgen.stream_request(port, [int(t) for t in rng.integers(1, vocab, buckets[0])],
                                 ladder)
    check(rec["done"], f"warm-up request failed: {rec['error']}")
    for b in buckets:
        rec = loadgen.stream_request(port, [int(t) for t in rng.integers(1, vocab, b)], 2)
        check(rec["done"], f"warm-up request (bucket {b}) failed: {rec['error']}")
    singles_s = time.monotonic() - t0
    # batched admits: at every bucket, or at the ``warm_group_buckets`` the
    # schedule hits most often (two requests meet in a bucket with the square
    # of its share, so the frequent ones carry nearly all such meetings)
    counts: dict[int, int] = {}
    for r in requests:
        b = stats.pad16(len(r["prompt"]))
        counts[b] = counts.get(b, 0) + 1
    top = sorted(buckets, key=lambda b: (-counts[b], b))
    grouped = sorted(top[:run.traffic.get("warm_group_buckets") or len(top)])
    for g in run.traffic.get("warm_group_sizes", []):
        for b in grouped:
            rows = [[int(t) for t in rng.integers(1, vocab, b)] for _ in range(g)]
            post_ok(port, "/v1/generate", {"tokens": rows, "max_new_tokens": 2})
    out = {"buckets": len(buckets), "bucket_min": buckets[0], "bucket_max": buckets[-1],
           "group_sizes": run.traffic.get("warm_group_sizes", []), "group_buckets": len(grouped),
           "singles_seconds": round(singles_s, 2),
           "seconds": round(time.monotonic() - t0, 2)}
    return out


def latency_summary(recs: list[dict], t0: float, t1: float) -> dict | None:
    """Medians and 90th percentiles of an open-loop window, the generator's
    lateness, and the backlog (requests due but unanswered) at mid-window and
    at its end: a backlog that grows says the rate is past the knee."""
    ok = [r for r in recs if not r["error"]]
    ttft = [stats.ttft_ms(r["due"], r["times"]) for r in ok]
    tpot = [v for v in (stats.tpot_ms(r["times"]) for r in ok) if v is not None]
    lags = [r["lag_ms"] for r in recs]
    if not ttft or not tpot:
        return None
    backlog = lambda t: sum(1 for r in recs if r["due"] <= t and
                            (not r["times"] or r["times"][-1] > t))
    return {"requests": len(recs), "failed": len(recs) - len(ok),
            "ttft_p50_ms": stats.median(ttft), "ttft_p90_ms": stats.percentile(ttft, 90),
            "tpot_p50_ms": stats.median(tpot), "tpot_p90_ms": stats.percentile(tpot, 90),
            "tpot_samples": len(tpot), "lag_p50_ms": stats.median(lags),
            "lag_p90_ms": stats.percentile(lags, 90), "lag_max_ms": max(lags),
            "in_flight_mid_window": backlog(t0 + (t1 - t0) / 2), "in_flight_window_end": backlog(t1),
            "output_tokens_per_s": sum(len(r["times"]) for r in ok) / (t1 - t0)}


def sweep(run: Run, port: int, schedules: dict) -> None:
    """Find the knee once: the same pod under each rate in turn, one line a
    rate. The knee is the highest rate whose backlog at the window's end is no
    larger than at mid-window; a cell's fixed rate is 0.8 of it."""
    for rate, schedule in schedules.items():
        t0, t1, recs = loadgen.run_open(port, schedule["requests"], schedule["drain_s"])
        emit("sweep", rate_rps=rate, **(latency_summary(recs, t0, t1) or {"failed": len(recs)}))


def serve_mode(run: Run, schedule: dict) -> dict | None:
    """A pod that stays up, under an open or a closed loop."""
    trace_dir = run.trace_dir() if run.args.trace else ""
    pod, port, ready_s = run.start_pod("pod", run.model_dir, trace_dir)
    first = run.pod_report(port)
    flat = (schedule["requests"] if schedule["mode"] == "open"
            else [r for c in schedule["clients"] for r in c])
    run.sources["schedule_means"] = {
        "prompt": sum(len(r["prompt"]) for r in flat) / len(flat),
        "output": sum(r["max_new_tokens"] for r in flat) / len(flat)}
    emit("pod", ready_seconds=round(ready_s, 2), load_seconds=first["default"].get("load_seconds"),
         hbm_bytes_in_use=first["device"].get("hbm_bytes_in_use"),
         compile_cache=first.get("compile_cache"), **run.device)
    schedules = run.sweep_schedules()
    warm = warm_up(run, port, flat + [r for sc in schedules.values() for r in sc["requests"]])
    _, before = http_json(port, "GET", "/metrics")
    emit("warm_up", **warm, compile_cache=before.get("compile_cache"),
         hbm_bytes_in_use=before["device"].get("hbm_bytes_in_use"))
    run.note_memory(before)
    if schedules:
        sweep(run, port, schedules)
        run.kids.stop(pod)
        return None
    tracer = None
    if run.args.trace:
        lead = schedule.get("lead_in_s", 0.0)
        delay = lead + max(0.0, (run.seconds - run.traffic["trace_seconds"]) / 2)
        tracer = threading.Timer(delay, run.profile, (port, run.traffic["trace_seconds"]))
        tracer.start()
    poller = loadgen.MetricsPoller(port)
    with poller:
        if schedule["mode"] == "open":
            t0, t1, recs = loadgen.run_open(port, schedule["requests"], schedule["drain_s"])
        else:
            t0, t1, recs = loadgen.run_closed(port, schedule["clients"], schedule["lead_in_s"],
                                              schedule["stagger_s"], run.seconds)
    setup_s = t0 - run.t_start
    if tracer is not None:
        # as long as the POST's own timeout: a pod stopped under its profiler loses the span
        tracer.join(run.traffic["trace_seconds"] + 300)
    _, after = http_json(port, "GET", "/metrics")
    run.memory_peak = max(run.memory_peak, poller.peak)
    run.note_memory(after)
    run.sources.update(metrics_before=before, metrics_after=after)
    probe = run.probes(port, run.traffic["probe"])
    run.kids.stop(pod)
    if run.args.trace:
        run.reduce_trace(trace_dir)

    completed, wrong = run.check_tokens(recs)
    failed = [r for r in recs if r["error"]]
    cc0, cc1 = before.get("compile_cache", {}), after.get("compile_cache", {})
    eng0, eng1 = before["default"]["continuous"], after["default"]["continuous"]
    emit("window", seconds=round(t1 - t0, 3), completed=completed, failed=len(failed),
         cut_at_window_end=sum(1 for r in recs if r["cut"]),
         errors=sorted({r["error"] for r in failed})[:3], wrong=wrong[:3],
         compile_requests_in_window=cc1.get("requests", 0) - cc0.get("requests", 0),
         compile_misses_in_window=cc1.get("misses", 0) - cc0.get("misses", 0),
         engine={k: eng1.get(k, 0) - eng0.get(k, 0) for k in
                 ("admitted", "chunks", "dispatches", "admit_batches", "decode_rows",
                  "decode_pad_rows")},
         boundary_host_ms_p50=eng1.get("boundary_host_ms_p50"),
         boundary_host_ms_p99=eng1.get("boundary_host_ms_p99"),
         active_peak=eng1.get("active_peak"), memory_peak_bytes=run.memory_peak)
    end_to_end = {"setup_s": setup_s}
    if schedule["mode"] == "open":
        run.sources["lags_ms"] = [r["lag_ms"] for r in recs]
        summary = latency_summary(recs, t0, t1)
        if summary:
            emit("latency", **summary)
            end_to_end.update(ttft_p90_ms=summary["ttft_p90_ms"],
                              tpot_p90_ms=summary["tpot_p90_ms"])
        attempted = len(recs)
    else:
        tokens = stats.tokens_in_window([r["times"] for r in recs], t0, t1)
        end_to_end["tokens_per_s"] = tokens / (t1 - t0)
        emit("throughput", tokens_in_window=tokens, tokens_per_s=end_to_end["tokens_per_s"],
             requests_completed=completed)
        attempted = completed + len(failed)
    compared = {"argmax_agreement_min": (probe["argmax_agreement"], probe["tolerance"]),
                "requests_failed_max": (len(failed), 0), "answers_wrong_max": (len(wrong), 0),
                "requests_completed_min": (completed, 1)}
    return run.result(attempted, len(failed), end_to_end, compared)


def deploy_mode(run: Run, schedule: dict) -> dict:
    """Deploys back to back, each on an empty volume and a new pod."""
    kids, req = run.kids, schedule["request"]
    reg_dir = os.path.join(run.work, "registry")
    marker = os.path.join(reg_dir, ".pushed.json")
    want = {"config": run.cell["config"], "seed": run.args.seed, "bytes": run.ckpt_bytes}
    try:
        have = load_json(marker)
    except (OSError, ValueError):
        have = {}
    pushed = have == want
    # a checkout's first run of the cell: the checkpoint and the registry's blobs are new
    fresh = (have.get("config"), have.get("bytes")) != (want["config"], want["bytes"])
    if fresh:
        shutil.rmtree(reg_dir, ignore_errors=True)  # another configuration's blobs
    reg_port = free_port()
    reg = kids.start("registry", CLI + ["serve", "--listen", f"127.0.0.1:{reg_port}",
                                        "--data", os.path.join(reg_dir, "data")], jax_child=False)
    wait_ready(reg_port, reg, kids.log_dir, 120)
    ref = f"http://127.0.0.1:{reg_port}/library/{run.cell['config']}@v1"
    if not pushed:
        t0 = time.monotonic()
        if not os.path.exists(os.path.join(run.model_dir, "modelx.yaml")):
            kids.run("init", CLI + ["init", run.model_dir], jax_child=False, timeout=120)
        kids.run("push", CLI + ["push", ref, run.model_dir], jax_child=False, timeout=900)
        os.makedirs(reg_dir, exist_ok=True)
        with open(marker, "w") as f:
            json.dump(want, f)
        # where only the seed is new, the registry has every blob but the head's:
        # push hashes the files and uploads that one
        emit("push", ref=ref, seconds=round(time.monotonic() - t0, 2))
    volume = os.path.join(run.work, "volume")
    trace_dir = run.trace_dir() if run.args.trace else ""

    def deploy(name: str, traced: bool = False, probe: bool = False) -> dict:
        shutil.rmtree(volume, ignore_errors=True)
        t0 = time.monotonic()
        out = kids.run(f"{name}-dl", CLI + ["dl", ref, volume], jax_child=False, timeout=900)
        t_pull = time.monotonic()
        summary = json.loads(out.strip().splitlines()[-1])
        pod, port, ready_s = run.start_pod(f"{name}-pod", volume, trace_dir if traced else "")
        t_ready = time.monotonic()
        tracer = None
        if traced:
            tracer = threading.Thread(target=run.profile,
                                      args=(port, run.traffic["trace_seconds"]), daemon=True)
            tracer.start()
            time.sleep(1.0)  # the profiler is on before the request goes
        rec = loadgen.stream_request(port, req["prompt"], req["max_new_tokens"])
        stage = {"name": name, "error": rec["error"], "tokens": None}
        if rec["times"]:
            metrics = run.pod_report(port)
            model, cc = metrics["default"], metrics.get("compile_cache", {})
            stage.update(
                tokens=loadgen.tokens_of(rec),
                deploy_ttft_s=rec["times"][0] - t0, pod_ttft_s=rec["times"][0] - t_pull,
                pull_wall_s=t_pull - t0,
                pull_seconds=summary.get("pull_seconds"), pulled_bytes=summary.get("bytes"),
                ready_s=ready_s, first_request_s=rec["times"][0] - t_ready,
                listen_s=run.listen_s,
                pod_listen_ttft_s=rec["times"][0] - t_pull - run.listen_s,
                imports_s=metrics.get("startup", {}).get("imports_s"),
                backend_devices_s=metrics.get("startup", {}).get("backend_init_devices_s"),
                load_seconds=model.get("load_seconds"), load_bytes=model.get("load_bytes"),
                cache_requests=cc.get("requests"), cache_hits=cc.get("hits"),
                cache_misses=cc.get("misses"),
                hbm_bytes_in_use=metrics["device"].get("hbm_bytes_in_use"))
        if tracer is not None:
            tracer.join(run.traffic["trace_seconds"] + 300)
        if probe and rec["times"]:
            stage["probe"] = run.probes(port, {"prompt_tokens": len(req["prompt"]),
                                               "new_tokens": req["max_new_tokens"]})
            _, metrics = http_json(port, "GET", "/metrics")
            run.note_memory(metrics)
        kids.stop(pod)
        emit("deploy", **{k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in stage.items() if k not in ("tokens", "probe")})
        return stage

    warm_marker = os.path.join(run.cache_dir, f".benchmark-warm-{run.cell['name']}")
    reference = None
    if fresh or not os.path.exists(warm_marker):
        # untimed: fills an empty compile cache, and on a checkout's first run takes
        # what the file system still owes for the 15 GB just written (PERF.md finding 9)
        reference = deploy("setup")
        check(reference["tokens"] is not None, f"the set-up deploy failed: {reference['error']}")
        with open(warm_marker, "w") as f:
            f.write("1")
    t_window = time.monotonic()
    setup_s = t_window - run.t_start
    deploys: list[dict] = []
    probing_s = 0.0  # the window's clock stands while the probes run
    while not deploys or time.monotonic() - t_window - probing_s < run.seconds:
        # the first deploy of the window is the traced one and carries the
        # probes — both after its first token, outside what is timed
        deploys.append(deploy(f"deploy{len(deploys)}", traced=bool(run.args.trace) and not deploys,
                              probe=not deploys))
        probing_s += deploys[-1].get("probe", {}).get("seconds", 0.0)
    kids.stop(reg)
    shutil.rmtree(volume, ignore_errors=True)  # 7.6 GB the next run would delete anyway
    check("probe" in deploys[0], f"the first deploy failed: {deploys[0]['error']}")
    if run.args.trace:
        run.reduce_trace(trace_dir)
    good = [d for d in deploys if d["tokens"] is not None]
    run.sources["deploys"] = good
    answers = [d["tokens"] for d in good] + ([reference["tokens"]] if reference else [])
    asked, vocab = req["max_new_tokens"], run.config["vocab_size"]
    probe = deploys[0]["probe"]
    end_to_end = {"setup_s": setup_s}
    if good:
        # dl start -> first token, pod spawn -> first token (the same without the
        # pull) and the pod's port answers -> first token (the same without the
        # interpreter, the imports and the chip's bring-up, whose seconds are the
        # machine's); BENCHMARK.json says which of them the cell reports end to end
        for k in ("deploy_ttft_s", "pod_ttft_s", "pod_listen_ttft_s"):
            end_to_end[k] = stats.median([d[k] for d in good])
    emit("window", seconds=round(time.monotonic() - t_window, 2), deploys=len(deploys),
         reached_first_token=len(good), answers_equal=all(a == answers[0] for a in answers),
         compared_with_setup_deploy=reference is not None,
         memory_peak_bytes=run.memory_peak)
    compared = {"argmax_agreement_min": (probe["argmax_agreement"], probe["tolerance"]),
                "deploys_failed_max": (len(deploys) - len(good), 0),
                "answers_unlike_the_first_max": (sum(a != answers[0] for a in answers), 0),
                "answers_malformed_max": (sum(not (len(a) == asked and all(0 <= t < vocab for t in a))
                                              for a in answers), 0),
                "hbm_bytes_in_use_min": (min((d["hbm_bytes_in_use"] for d in good), default=0),
                                         run.ckpt_bytes)}
    return run.result(len(deploys), len(deploys) - len(good), end_to_end, compared)


MODES = {"open": serve_mode, "closed": serve_mode, "deploy": deploy_mode}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep-rates", type=lambda s: [float(x) for x in s.split(",")], default=[],
                    help="open-loop cells: run these rates in turn on one pod, print a line "
                         "for each and no result (how a cell's fixed rate was found)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="also write the trace's planes and lines under chiprun_out/")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny presets on whatever jax finds; always ends correct: false")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "modelx_tpu")):
        print("benchmark/run.py: modelx_tpu/ is not beside benchmark/ — nothing to measure",
              file=sys.stderr)
        return 2
    run = None
    try:
        if args.seconds is None:
            args.seconds = 6.0 if args.rehearse else load_json(ROOT, "BENCHMARK.json")["run_seconds"]
        run = Run(args)
        run.probe_and_checkpoint()
        generator = importlib.import_module(f"benchmark.generators.{run.traffic['generator']}")
        schedule = generator.schedule(args.seed, run.traffic, run.config["vocab_size"],
                                      run.seconds, run.max_seq_len)
        result = MODES[schedule["mode"]](run, schedule)
    except Fail as e:
        print(f"benchmark/run.py: FAILED: {e}", file=sys.stderr)
        return 2
    finally:
        if run is not None:
            run.kids.stop_all()
    if result is not None:
        for name, c in result["compared"].items():
            print(f"benchmark/run.py: compared {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
