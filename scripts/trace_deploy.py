#!/usr/bin/env python
"""A pod's start, traced from inside: spawn -> first token as one timeline,
with the profiler on from the moment the port answers.

    # in one chiprun call (the machine and its .xplane.pb go when the call ends)
    python3 scripts/trace_deploy.py --config phi3-mini-4k --gaps 0,5,20 \\
        [--profile-seconds 50] [--seed 1] [--out chiprun_out/trace-deploy]
    JAX_PLATFORMS=cpu python3 scripts/trace_deploy.py --config phi3-mini-4k --rehearse

For each of ``--pods`` it copies the configuration's checkpoint
(benchmark/checkpoints, as the benchmark writes it) to an empty volume
(``--no-copy``: serves the checkpoint where it lies, and writes nothing),
waits its ``--gaps`` entry in seconds after the previous pod's exit, starts
``modelx serve-model`` on the volume with the configuration's serve
arguments, posts ``/v1/profile`` as soon as the listener answers — before the
load, so the capture holds ``dl.fetch``, ``dl.put``, ``serve.load/*`` and
``programs.load/*`` beside the device plane's transfers — waits for
``/healthz``, streams one request, and reads ``/metrics`` and
``/v1/trace?startup=1``; with ``--second-prompt-tokens`` it then streams a
request of another length, whose admit program no one has loaded yet, and
reads the store's counters around it: a stored program loaded ALONE, with no
weight stream and no other load beside it. It prints per pod the stages and sub-stages, the
loader's tiling, each stored program's read / unpickle / deserialize seconds
with its bytes and whether the weights were still streaming, and the
capture's idle gaps (benchmark/xplane.py's reduction); everything whole goes
to ``--out``. ``--gaps``, ``--profile-seconds`` (0: no capture) and
``--roots`` (another checkout of the package, say the parent commit's, for a
same-call comparison of ``pod_ttft_s``) are lists the pods cycle through.
The parent never imports jax: the pod is the one process on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import checkpoint, loadgen  # noqa: E402
from benchmark.procs import CLI, Children, Fail, free_port, http_json, wait_ready  # noqa: E402
from benchmark.run import META_KEYS, load_json  # noqa: E402

LOADER_KEYS = ("seconds", "shards_seconds", "shard_files", "fetch_busy_seconds",
               "device_put_seconds", "overlap_seconds", "assemble_seconds", "idle_seconds",
               "backpressure_seconds", "drain_seconds", "assemble_copied_bytes", "gbps")
STORE_KEYS = ("store_hits", "store_misses", "store_load_s", "store_read_s",
              "store_deserialize_s", "store_bytes_read")


def wait_listening(port: int, pod, timeout: float = 600.0) -> None:
    """Until the listener answers anything at all (503 while loading)."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pod.poll() is not None:
            raise Fail(f"the pod exited {pod.returncode} before it listened")
        try:
            http_json(port, "GET", "/livez", timeout=2.0)
            return
        except OSError:
            time.sleep(0.02)
    raise Fail(f"no listener in {timeout:.0f}s")


def program_loads(timeline: dict, load_end_s: float) -> list[dict]:
    """Each ``programs.load`` of the timeline with its three children."""
    spans = timeline["spans"]
    out = []
    for s in spans:
        if not s["path"].endswith("programs.load"):
            continue
        row = {"program": s["attrs"].get("program"), "bytes": s["attrs"].get("bytes"),
               "thread": s["thread"], "at_s": s["at_s"], "load_s": s["duration_s"],
               "beside_the_weight_stream": s["at_s"] < load_end_s}
        for child in ("read", "unpickle", "deserialize"):
            row[f"{child}_s"] = sum(
                c["duration_s"] for c in spans
                if c["path"] == f"{s['path']}/{child}" and c["thread"] == s["thread"]
                and s["at_s"] <= c["at_s"] <= s["at_s"] + s["duration_s"])
        if row["bytes"]:
            row["s_per_mb"] = row["load_s"] / (row["bytes"] / 1e6)
        out.append(row)
    return out


def one_pod(kids: Children, root: str, name: str, ckpt_dir: str, volume: str,
            serve_args: list[str], trace_dir: str, profile_s: float, request: dict,
            second: dict | None, t_prev_exit: float | None, gap_s: float) -> dict:
    if volume != ckpt_dir:
        shutil.rmtree(volume, ignore_errors=True)
        shutil.copytree(ckpt_dir, volume)  # new files, in the page cache, as after `modelx dl`
    if t_prev_exit is not None:
        time.sleep(max(0.0, gap_s - (time.monotonic() - t_prev_exit)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    port = free_port()
    t_spawn = time.monotonic()
    since_prev = None if t_prev_exit is None else t_spawn - t_prev_exit
    here = os.getcwd()
    os.chdir(root)  # `python -m` looks in its working directory first: root's modelx_tpu
    try:
        pod = kids.start(name, CLI + ["serve-model", "--model-dir", volume, "--listen",
                                      f"127.0.0.1:{port}", "--drain-seconds", "0",
                                      "--trace-dir", trace_dir, *serve_args], jax_child=True)
    finally:
        os.chdir(here)
    profile: dict = {}
    try:
        wait_listening(port, pod)
        t_listening = time.monotonic()

        def capture() -> None:
            profile["status"], profile["answer"] = http_json(
                port, "POST", "/v1/profile", {"seconds": profile_s}, timeout=profile_s + 300)

        tracer = threading.Thread(target=capture if profile_s else lambda: None, daemon=True)
        tracer.start()
        wait_ready(port, pod, kids.log_dir, 1100)
        t_ready = time.monotonic()
        rec = loadgen.stream_request(port, request["prompt"], request["max_new_tokens"])
        if not rec["times"]:
            raise Fail(f"the first request gave no token: {rec['error']}")
        _, metrics = http_json(port, "GET", "/metrics")
        _, timeline = http_json(port, "GET", "/v1/trace?startup=1")
        tracer.join(profile_s + 300)
        alone = None
        if second is not None:
            t0 = time.monotonic()
            again = loadgen.stream_request(port, second["prompt"], second["max_new_tokens"])
            _, later = http_json(port, "GET", "/metrics")
            alone = {k: later.get("compile_cache", {}).get(k, 0)
                     - metrics.get("compile_cache", {}).get(k, 0) for k in STORE_KEYS}
            alone.update(prompt_tokens=len(second["prompt"]),
                         first_token_s=again["times"][0] - t0 if again["times"] else None)
    finally:
        kids.stop(pod)
    t_exit = time.monotonic()
    if not isinstance(timeline.get("spans"), list):  # a checkout without the timeline
        timeline = {"spans": [], "dropped": 0, "frozen": False}
    out = {"name": name, "root": root, "profile_seconds": profile_s,
           "since_previous_exit_s": since_prev, "gap_asked_s": gap_s,
           "listening_s": t_listening - t_spawn, "ready_s": t_ready - t_spawn,
           "pod_ttft_s": rec["times"][0] - t_spawn,
           "first_request_s": rec["times"][0] - t_ready,
           "profile": profile, "startup": metrics.get("startup", {}),
           "loader": {k: metrics["default"].get(f"load_{k}") for k in LOADER_KEYS},
           "compile_cache": {k: metrics.get("compile_cache", {}).get(k) for k in STORE_KEYS},
           "second_request_store_growth": alone, "timeline": timeline, "t_exit": t_exit,
           "timeline_bytes": len(json.dumps(timeline))}
    started = out["startup"]
    load_end = sum(started.get(f"{k}_s", 0.0) for k in
                   ("imports", "backend_init", "configure", "listener", "load"))
    out["program_loads"] = program_loads(timeline, load_end)
    if not profile_s:
        out["trace"] = {}
        return out
    try:
        reduced = json.loads(kids.run(f"{name}-xplane", [os.path.join(
            ROOT, "benchmark", "xplane.py"), trace_dir], jax_child=False,
            timeout=900).strip().splitlines()[-1])
        out["trace"] = {k: reduced.get(k) for k in
                        ("window_s", "busy_s", "device_planes", "idle_gaps", "modules")}
    except Fail as e:
        out["trace"] = {"error": str(e)[-400:]}
    return out


def show(pod: dict) -> None:
    started = pod["startup"]
    print(f"\n== {pod['name']} ({os.path.relpath(pod['root'], ROOT)}, capture "
          f"{pod['profile_seconds']:.0f} s): pod_ttft_s {pod['pod_ttft_s']:.3f} = ready {pod['ready_s']:.3f} "
          f"+ first request {pod['first_request_s']:.3f}; since the previous pod's exit: "
          f"{pod['since_previous_exit_s']}; timeline {pod['timeline_bytes']} bytes, "
          f"{len(pod['timeline']['spans'])} spans, dropped {pod['timeline']['dropped']}")
    print("startup:", json.dumps({k: v for k, v in started.items() if k != "source"}))
    print("loader:", json.dumps(pod["loader"]))
    print("store:", json.dumps(pod["compile_cache"]))
    for row in pod["program_loads"]:
        print("program:", json.dumps({k: round(v, 4) if isinstance(v, float) else v
                                      for k, v in row.items()}))
    if pod["second_request_store_growth"]:
        print("second request, its programs loaded alone:",
              json.dumps(pod["second_request_store_growth"]))
    long = [s for s in pod["timeline"]["spans"]
            if s["duration_s"] >= 0.25 and not s["path"].startswith(("dl.fetch", "dl.put"))]
    for s in long:
        print(f"  {s['at_s']:8.3f} +{s['duration_s']:7.3f}  {s['thread'][:18]:18}  {s['path']}")
    trace = pod["trace"]
    print("capture:", json.dumps({k: trace.get(k) for k in
                                  ("window_s", "busy_s", "device_planes", "error") if k in trace}),
          "profile:", pod["profile"].get("status"))
    for gap_name, seconds in (trace.get("idle_gaps") or [])[:12]:
        print(f"  idle {seconds:7.3f}  {gap_name}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="phi3-mini-4k", help="a name of BENCHMARK.json's configs")
    ap.add_argument("--seed", type=int, default=1)
    floats = lambda s: [float(x) for x in s.split(",")]  # noqa: E731
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--gaps", type=floats, default=[5.0],
                    help="seconds from the previous pod's exit to a pod's spawn")
    ap.add_argument("--profile-seconds", type=floats, default=[50.0],
                    help="the capture's length from the listener's first answer (at most 60; "
                         "0: no capture)")
    ap.add_argument("--roots", type=lambda s: s.split(","), default=[ROOT],
                    help="checkouts the pods' modelx_tpu comes from")
    ap.add_argument("--no-copy", action="store_true",
                    help="serve the checkpoint where it lies instead of a copy on a new volume")
    ap.add_argument("--prompt-tokens", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--second-prompt-tokens", type=int, default=0,
                    help="then a request of this length: its admit program loads alone (0: none)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "trace-deploy"))
    ap.add_argument("--rehearse", action="store_true", help="the configuration's tiny preset")
    args = ap.parse_args()
    entry = next(c for c in load_json(ROOT, "BENCHMARK.json")["configs"]
                 if c["name"] == args.config)
    config = load_json(ROOT, entry["file"])
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        args.profile_seconds = [min(p, 20.0) for p in args.profile_seconds]
    work = os.path.join(ROOT, ".cache", "benchmark")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".cache", "xla")
    os.makedirs(cache_dir, exist_ok=True)
    os.makedirs(args.out, exist_ok=True)
    kids = Children(os.path.join(work, "logs", "trace-deploy"), cache_dir)
    hf = {k: v for k, v in config.items() if k not in META_KEYS}
    ckpt_dir, nbytes, wrote_s = checkpoint.ensure(
        os.path.join(work, "checkpoint"), args.config, config["family"], config, hf, args.seed,
        config.get("checkpoint_dtype", "BF16"))
    print(json.dumps({"checkpoint": ckpt_dir, "bytes": nbytes, "wrote_s": round(wrote_s, 2)}))
    rng = random.Random(args.seed)
    request = {"prompt": [rng.randrange(1, config["vocab_size"])
                          for _ in range(args.prompt_tokens)],
               "max_new_tokens": args.new_tokens}
    second = {"prompt": [rng.randrange(1, config["vocab_size"])
                         for _ in range(args.second_prompt_tokens)],
              "max_new_tokens": args.new_tokens} if args.second_prompt_tokens else None
    volume = ckpt_dir if args.no_copy else os.path.join(work, "trace-deploy-volume")
    t_prev = None
    try:
        for i in range(args.pods):
            pod = one_pod(kids, os.path.abspath(args.roots[i % len(args.roots)]), f"pod{i}",
                          ckpt_dir, volume, list(config["serve_args"]),
                          os.path.join(work, "trace", "trace-deploy"),
                          args.profile_seconds[i % len(args.profile_seconds)],
                          request, second, t_prev, args.gaps[i % len(args.gaps)])
            t_prev = pod.pop("t_exit")
            show(pod)
            with open(os.path.join(args.out, f"pod{i}.json"), "w") as f:
                json.dump(pod, f)
    except Fail as e:
        print(f"scripts/trace_deploy.py: FAILED: {e}", file=sys.stderr)
        return 2
    finally:
        kids.stop_all()
        if not args.no_copy:
            shutil.rmtree(volume, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
