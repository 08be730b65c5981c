#!/usr/bin/env python3
"""chip_smoke.py — the README's journey, once, on the chip, through the CLI.

    python chip_smoke.py              # one chip: registry -> HBM -> continuous decode
    python chip_smoke.py --chips 4    # only tp=4 serving vs the one-chip answers
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # every phase, tiny, ends ok:false

What it drives (console entry points only, ``python -m modelx_tpu.cli ...``):
build the native IO engine from the committed source -> write a seeded
llama checkpoint at the published Llama-3-8B widths (depth cut, printed as
``reduced``) -> ``modelx serve`` (registry) -> ``modelx push`` ->
``modelx dl --device-put`` (registry -> pod volume -> HBM) ->
``modelx serve-model --model-dir ... --continuous-batch`` -> a few requests
whose answers are checked against what a run can show -> restart the pod
against the same compile cache and count its persistent-cache hits.

Contract: one JSON object per phase on stdout; any failed check exits
non-zero at once; the LAST line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}`` with
the device as the SERVING process reported it. The script selects no
platform: where jax finds no accelerator it exits non-zero (without
``--rehearse``: before doing anything, printing no result line; with it:
after rehearsing every phase at tiny size, ``"ok": false``). This parent
never imports jax — a chip belongs to one process at a time, so each phase
that needs it is one child process, and children that do not get
``JAX_PLATFORMS=cpu``. Nothing here is a benchmark: times and rates printed
along the way are first observations of one run.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import http.client
import json
import os
import shutil
import math
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Llama-3-8B as published (meta-llama/Meta-Llama-3-8B config.json; the
# defaults of modelx_tpu/models/llama.py LlamaConfig). Widths are never cut;
# depth is.
PUBLISHED = dict(hidden=4096, inter=14336, heads=32, kv_heads=8, head_dim=128,
                 vocab=128256, layers=32)
# --rehearse: every phase on the CPU in about a minute
TINY = dict(hidden=128, inter=256, heads=4, kv_heads=2, head_dim=32,
            vocab=512, layers=2)

NEW_TOKENS = 40
# tolerances of the --chips 4 comparison (tp=4 vs one chip, same weights,
# same requests): bf16 reduce order differs across shards, so answers are
# compared, not byte-matched. A wrong sharding fails all of these at once.
# Free-running greedy decode is chaotic on random weights — at a 128k
# vocabulary one near-tie ends the common prefix (first chip run: 4, 2 and 5
# tokens) — so the prefix bound is loose and the teacher-forced numbers
# (argmax agreement, logprobs) carry the comparison.
TP_MIN_COMMON_PREFIX = 2           # greedy tokens in common on >= 2 of 3 prompts (>= 1 on all)
TP_MIN_ARGMAX_AGREEMENT = 0.9      # /v1/forward per-position argmax agreement
TP_MAX_LOGPROB_DIFF = 0.15         # |delta logprob| over the common scored prefix
TP_WEIGHT_SHARE = (0.15, 0.40)     # each device's share of measured HBM in use


class Fail(Exception):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Fail(what)


# -- child processes ----------------------------------------------------------


class Children:
    """Every process this script starts, so that every one is stopped."""

    def __init__(self, log_dir: str) -> None:
        self.log_dir = log_dir
        self.live: list[subprocess.Popen] = []

    @staticmethod
    def env(jax_child: bool) -> dict:
        existing = os.environ.get("PYTHONPATH", "")
        env = dict(os.environ,
                   PYTHONPATH=HERE + (os.pathsep + existing if existing else ""))
        if not jax_child:
            env["JAX_PLATFORMS"] = "cpu"  # must never reach for the chip
        return env

    def run(self, name: str, argv: list[str], jax_child: bool,
            timeout: float) -> str:
        """Run to completion; returns stdout. stderr goes to a log file whose
        tail rides the failure."""
        err_path = os.path.join(self.log_dir, f"{name}.err")
        with open(err_path, "wb") as err:
            p = subprocess.Popen([sys.executable, *argv], env=self.env(jax_child),
                                 stdout=subprocess.PIPE, stderr=err)
            self.live.append(p)
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.stop(p)
                raise Fail(f"{name}: no answer in {timeout:.0f}s\n{_tail(err_path)}") from None
            finally:
                if p in self.live and p.poll() is not None:
                    self.live.remove(p)
        if p.returncode != 0:
            raise Fail(f"{name}: exit {p.returncode}\n{_tail(err_path)}")
        return out.decode()

    def start(self, name: str, argv: list[str], jax_child: bool) -> subprocess.Popen:
        log = open(os.path.join(self.log_dir, f"{name}.log"), "wb")
        p = subprocess.Popen([sys.executable, *argv], env=self.env(jax_child),
                             stdout=log, stderr=subprocess.STDOUT)
        log.close()
        p.log_name = name
        self.live.append(p)
        return p

    def stop(self, p: subprocess.Popen, grace: float = 30.0) -> None:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
            try:
                p.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=30)
        if p in self.live:
            self.live.remove(p)

    def stop_all(self) -> None:
        for p in list(self.live):
            self.stop(p, grace=10.0)


def _tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")
    except OSError:
        return ""


CLI = ["-m", "modelx_tpu.cli"]


# -- http ---------------------------------------------------------------------


def http_json(port: int, method: str, path: str, body=None, timeout: float = 900.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=payload,
                     headers={"Content-Type": "application/json"} if payload else {})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    try:
        data = json.loads(raw) if raw else {}
    except ValueError:
        data = {"raw": raw[:500].decode(errors="replace")}
    return resp.status, data


def post_ok(port: int, path: str, body: dict) -> dict:
    status, data = http_json(port, "POST", path, body)
    check(status == 200, f"POST {path} -> {status}: {data}")
    return data


def stream_tokens(port: int, body: dict) -> list[int]:
    """POST a streaming /v1/generate; returns the tokens of its NDJSON lines."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900.0)
    try:
        conn.request("POST", "/v1/generate", body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    check(resp.status == 200, f"stream -> {resp.status}: {raw[:300]!r}")
    lines = [json.loads(ln) for ln in raw.splitlines() if ln.strip()]
    check(lines and lines[-1] == {"done": True}, f"stream did not end in done: {lines[-1:]}")
    toks: list[int] = []
    for ln in lines[:-1]:
        check("tokens" in ln, f"stream line without tokens: {ln}")
        toks.extend(ln["tokens"][0])
    return toks


def wait_ready(port: int, proc: subprocess.Popen, log_dir: str, timeout: float) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc.poll() is not None:
            raise Fail(f"{proc.log_name} exited {proc.returncode} while starting\n"
                       + _tail(os.path.join(log_dir, f"{proc.log_name}.log")))
        try:
            status, _ = http_json(port, "GET", "/healthz", timeout=5.0)
            if status == 200:
                return time.monotonic() - t0
        except OSError:
            pass
        time.sleep(0.5)
    raise Fail(f"{proc.log_name} not ready in {timeout:.0f}s\n"
               + _tail(os.path.join(log_dir, f"{proc.log_name}.log")))


# -- phases -------------------------------------------------------------------


def phase_device(kids: Children) -> dict:
    """What jax finds, asked from a child (the parent stays off jax)."""
    out = kids.run("device-probe", ["-c", (
        "import json, jax; d = jax.devices(); "
        "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d), 'jax': jax.__version__}))")], jax_child=True, timeout=300)
    dev = json.loads(out.strip().splitlines()[-1])
    emit("device", **dev,
         compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    return dev


def phase_native(kids: Children) -> None:
    """Build the IO engine from modelx_io.cc AS COMMITTED: force=True
    compiles past whatever sits in _build/ (and replaces it atomically — a
    process that has the old file open keeps it), the name carries the
    source's digest, and a library that cannot be built or loaded is a
    failure here, not a quiet pure-Python run."""
    out = kids.run("native-build", ["-c", (
        "import hashlib, json\n"
        "from modelx_tpu import native\n"
        "path = native.build(force=True)\n"
        "assert path, 'native IO engine did not build'\n"
        "assert native.lib() is not None, 'native IO engine did not load'\n"
        "want = hashlib.sha256(open(native._SRC, 'rb').read()).hexdigest()\n"
        "assert native.sha256_file(native._SRC) == want, 'native sha256 disagrees with hashlib'\n"
        "print(json.dumps({'library': path, 'loaded': True}))\n")],
        jax_child=False, timeout=300)
    emit("native", **json.loads(out.strip().splitlines()[-1]))


def _shapes(w: dict, layers: int) -> list[list[tuple[str, tuple[int, ...]]]]:
    """Tensor (name, shape) lists, one per safetensors shard: embedding, the
    layers in pairs, norm + head — HF layout, [out_features, in_features]."""
    e, q, kv, f = w["hidden"], w["heads"] * w["head_dim"], w["kv_heads"] * w["head_dim"], w["inter"]
    shards = [[("model.embed_tokens.weight", (w["vocab"], e))]]
    for i in range(layers):
        if i % 2 == 0:
            shards.append([])
        p = f"model.layers.{i}."
        shards[-1] += [
            (p + "self_attn.q_proj.weight", (q, e)), (p + "self_attn.k_proj.weight", (kv, e)),
            (p + "self_attn.v_proj.weight", (kv, e)), (p + "self_attn.o_proj.weight", (e, q)),
            (p + "mlp.gate_proj.weight", (f, e)), (p + "mlp.up_proj.weight", (f, e)),
            (p + "mlp.down_proj.weight", (e, f)),
            (p + "input_layernorm.weight", (e,)), (p + "post_attention_layernorm.weight", (e,)),
        ]
    shards.append([("model.norm.weight", (e,)), ("lm_head.weight", (w["vocab"], e))])
    return shards


def checkpoint_bytes(w: dict, layers: int) -> int:
    return 2 * sum(math.prod(shape) for shard in _shapes(w, layers) for _, shape in shard)


def phase_checkpoint(model_dir: str, w: dict, layers: int, seed: int) -> int:
    """Seeded bf16 weights, written with the repo's own safetensors writer.
    Uniform with variance 1/fan_in (models/llama.init_params' scale), made in
    bulk: float32 uniforms truncated to bfloat16 by dropping the low half."""
    import ml_dtypes
    import numpy as np

    from modelx_tpu.dl import safetensors as st

    t0 = time.monotonic()
    shards = _shapes(w, layers)

    def tensor(index: int, name: str, shape) -> "np.ndarray":
        if name.endswith("norm.weight"):
            return np.ones(shape, ml_dtypes.bfloat16)
        rng = np.random.default_rng([seed, index])
        x = rng.random(math.prod(shape), dtype=np.float32)
        x -= np.float32(0.5)
        x *= np.float32((12.0 / shape[-1]) ** 0.5)
        return (x.view(np.uint32) >> 16).astype(np.uint16).view(ml_dtypes.bfloat16).reshape(shape)

    def write_shard(si: int) -> None:
        base = sum(len(s) for s in shards[:si])
        tensors = {name: tensor(base + j, name, shape)
                   for j, (name, shape) in enumerate(shards[si])}
        st.write_safetensors(
            os.path.join(model_dir, f"model-{si + 1:05d}-of-{len(shards):05d}.safetensors"),
            tensors)

    with concurrent.futures.ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(write_shard, range(len(shards))))
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump({
            "architectures": ["LlamaForCausalLM"], "model_type": "llama",
            "hidden_size": w["hidden"], "intermediate_size": w["inter"],
            "num_attention_heads": w["heads"], "num_key_value_heads": w["kv_heads"],
            "head_dim": w["head_dim"], "num_hidden_layers": layers,
            "vocab_size": w["vocab"], "rope_theta": 500000.0, "rms_norm_eps": 1e-5,
            "tie_word_embeddings": False, "torch_dtype": "bfloat16",
        }, f, indent=1)
    # a word-level tokenizer ("t<id>"), so /v1/completions can return the
    # logprobs that are the only numeric values the HTTP surface exposes
    with open(os.path.join(model_dir, "tokenizer.json"), "w") as f:
        json.dump({
            "version": "1.0", "truncation": None, "padding": None,
            "added_tokens": [], "normalizer": None,
            "pre_tokenizer": {"type": "WhitespaceSplit"},
            "post_processor": None, "decoder": None,
            "model": {"type": "WordLevel", "unk_token": "t0",
                      "vocab": {f"t{i}": i for i in range(w["vocab"])}},
        }, f)
    nbytes = checkpoint_bytes(w, layers)
    emit("checkpoint", seed=seed, family="llama", bytes=nbytes, shards=len(shards),
         widths={k: v for k, v in w.items() if k != "layers"},
         reduced={"num_layers": f"{layers} of {PUBLISHED['layers']}"},
         seconds=round(time.monotonic() - t0, 1))
    return nbytes


def phase_registry_push(kids: Children, work: str, model_dir: str) -> tuple[subprocess.Popen, str]:
    from modelx_tpu.registry.server import free_port

    port = free_port()
    reg = kids.start("registry", CLI + ["serve", "--listen", f"127.0.0.1:{port}",
                                         "--data", os.path.join(work, "registry")],
                     jax_child=False)
    wait_ready(port, reg, kids.log_dir, 60)
    ref = f"http://127.0.0.1:{port}/library/llama3-8b-cut@v1"
    t0 = time.monotonic()
    kids.run("push", CLI + ["push", ref, model_dir], jax_child=False, timeout=900)
    emit("push", ref=ref, seconds=round(time.monotonic() - t0, 1))
    return reg, ref


def phase_dl(kids: Children, ref: str, dest: str, mesh: str) -> None:
    """``modelx dl --device-put``: registry -> pod volume, then registry ->
    HBM through the loader. The README's normal deploy path."""
    out = kids.run("dl", CLI + ["dl", ref, dest, "--device-put", "--mesh", mesh],
                   jax_child=True, timeout=900)
    summary = json.loads(out.strip().splitlines()[-1])
    load = summary["load"]
    check(load["bytes"] > 0 and load["tensors"] > 0, f"dl loaded nothing: {summary}")
    emit("dl", deploy_path="modelx dl --device-put, then modelx serve-model --model-dir",
         pulled_bytes=summary["bytes"], pull_seconds=summary["pull_seconds"],
         bytes_to_device=load["bytes"], load_seconds=load["seconds"],
         load_gbps=load["gbps"], mesh=load["mesh"])


def start_pod(kids: Children, name: str, model_dir: str, mesh: str) -> tuple[subprocess.Popen, int, float]:
    from modelx_tpu.registry.server import free_port

    port = free_port()
    argv = CLI + ["serve-model", "--model-dir", model_dir, "--listen", f"127.0.0.1:{port}",
                  "--continuous-batch", "--drain-seconds", "0"]
    if mesh:
        argv += ["--mesh", mesh]
    pod = kids.start(name, argv, jax_child=True)
    return pod, port, wait_ready(port, pod, kids.log_dir, 900)


def pod_report(port: int, phase: str, ready_s: float, expect_bytes: int, full_size: bool) -> dict:
    """What the SERVING process says it runs on and holds."""
    _, metrics = http_json(port, "GET", "/metrics")
    _, admin = http_json(port, "GET", "/admin/models")
    dev = metrics.get("device", {})
    model = metrics.get("default", {})
    check(admin.get("models", {}).get("default", {}).get("state") == "READY",
          f"model not READY: {admin.get('models')}")
    check(dev.get("platform") and dev.get("device_kind"),
          f"/metrics device block names no platform: {dev}")
    check(model.get("load_bytes") == expect_bytes,
          f"loaded {model.get('load_bytes')} bytes, checkpoint has {expect_bytes}")
    if full_size:
        check(model["load_bytes"] >= 4e9, f"weights on device < 4 GB: {model['load_bytes']}")
    emit(phase, platform=dev["platform"], device_kind=dev["device_kind"],
         device_count=dev["device_count"], mesh=model.get("mesh"),
         weights_bytes_on_device=model["load_bytes"],
         hbm_bytes_in_use=dev.get("hbm_bytes_in_use"), hbm_source=dev.get("source"),
         hbm_per_device=dev.get("devices"),
         load_seconds=model.get("load_seconds"), ready_seconds=round(ready_s, 1),
         native_engine=model.get("native_io"), compile_cache=metrics.get("compile_cache"))
    check(model.get("native_io") is True,
          "the serving process loaded its weights without the native IO engine")
    return metrics


def prompts(w: dict, seed: int) -> list[list[int]]:
    """Three prompts whose 16-bucketed lengths differ: 16, 40 (-> 48), 150 (-> 160)."""
    import numpy as np

    rng = np.random.default_rng([seed, 10_000])
    return [rng.integers(1, w["vocab"], n).tolist() for n in (16, 40, 150)]


def forward_inputs(w: dict, seed: int) -> dict[int, list[int]]:
    """/v1/forward inputs by length: a block multiple (512) and a ragged
    16-bucketed length (144, which the kernel pads to 256)."""
    import numpy as np

    rng = np.random.default_rng([seed, 20_000])
    return {n: rng.integers(1, w["vocab"], n).tolist() for n in (512, 144)}


def generate(port: int, ids: list[int], **extra) -> list[int]:
    out = post_ok(port, "/v1/generate",
                  {"tokens": [ids], "max_new_tokens": NEW_TOKENS, **extra})
    row = out["tokens"][0]
    check(row[:len(ids)] == ids, "generate did not echo the prompt")
    return row[len(ids):]


def phase_generate(port: int, ps: list[list[int]], vocab: int) -> list[list[int]]:
    t0 = time.monotonic()
    first = generate(port, ps[0])
    first_s = time.monotonic() - t0
    # two at once: the engine has to batch them into one running decode
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        second, third = pool.map(lambda p: generate(port, p), ps[1:])
    greedy = [first, second, third]
    for g in greedy:
        check(len(g) == NEW_TOKENS, f"asked {NEW_TOKENS} tokens, got {len(g)}")
        check(all(0 <= t < vocab for t in g), "token id outside the vocabulary")
    check(generate(port, ps[0]) == first, "the same greedy request gave different tokens")
    _, metrics = http_json(port, "GET", "/metrics")
    eng = metrics["default"].get("continuous", {})
    check(eng.get("admitted", 0) >= 4 and eng.get("chunks", 0) > 0,
          f"the continuous engine did not answer: {eng}")
    check(eng.get("active_peak", 0) >= 2, f"concurrent requests were not batched: {eng}")
    emit("generate", requests=4, tokens_returned=4 * NEW_TOKENS,
         first_request_seconds=round(first_s, 1),
         prompt_lengths=[len(p) for p in ps], new_tokens=NEW_TOKENS,
         repeat_equal=True, engine={k: eng.get(k) for k in
                                    ("admitted", "chunks", "dispatches", "active_peak")})
    return greedy


def phase_sample_stream(port: int, ps: list[list[int]], greedy: list[list[int]]) -> None:
    samp = dict(temperature=0.8, top_k=40, top_p=0.95, seed=7)
    a, b = generate(port, ps[0], **samp), generate(port, ps[0], **samp)
    check(len(a) == NEW_TOKENS and a == b, "a seeded sampled request is not reproducible")
    check(a != greedy[0], "sampling at temperature 0.8 returned the greedy tokens")
    streamed = stream_tokens(port, {"tokens": [ps[0]], "max_new_tokens": NEW_TOKENS,
                                    "stream": True})
    check(streamed == greedy[0], "streamed tokens differ from the non-streamed answer")
    emit("sample_stream", sampled_reproducible=True, stream_equals_non_stream=True,
         tokens_returned=3 * NEW_TOKENS)


def forward_argmax(port: int, ids: list[int]) -> list[int]:
    out = post_ok(port, "/v1/forward", {"tokens": [ids]})["logits_argmax"][0]
    check(len(out) == len(ids), "forward returned a different length")
    return out


def phase_forward(port: int, ps: list[list[int]], greedy: list[list[int]], w: dict,
                  seed: int, on_accelerator: bool) -> dict:
    """/v1/forward at a block-multiple length (512), a ragged one (144) and
    the two short prompts — and what /v1/trace says each compiled with."""
    lengths = {**forward_inputs(w, seed), len(ps[0]): ps[0], len(ps[1]): ps[1]}
    argmax = {n: forward_argmax(port, ids) for n, ids in lengths.items()}
    for n, out in argmax.items():
        check(all(0 <= t < w["vocab"] for t in out), f"forward S={n}: argmax outside the vocabulary")
        # all-NaN logits argmax to 0 everywhere
        check(len(set(out)) > 1 or n == 1, f"forward S={n}: constant argmax (non-finite logits?)")
    for i in (0, 1):
        check(argmax[len(ps[i])][-1] == greedy[i][0],
              f"prompt {i}: forward's last-position argmax {argmax[len(ps[i])][-1]} "
              f"is not the first generated token {greedy[i][0]}")
    # ops/attention.note_choice records, at trace time, a span named after
    # the decision: ".../serve.forward/attention.flash[144x144]+pad[256x256]"
    _, spans = http_json(port, "GET", "/v1/trace")
    impl: dict[int, str] = {}
    for path in spans:
        leaf = path.rsplit("/", 1)[-1]
        if leaf.startswith("attention.") and "serve.forward" in path:
            impl[int(leaf.split("[", 1)[1].split("x", 1)[0])] = leaf[len("attention."):]
    # the 16-token prompt rides the warm-up program compiled at load (from a
    # serialized export on a warm cache, which traces nothing): not required
    for n in (512, 144, len(ps[1])):
        check(n in impl, f"/v1/trace names no attention implementation for S={n}: {sorted(spans)}")
        if on_accelerator:
            check(impl[n].startswith("flash"),
                  f"forward S={n} did not compile with the pallas kernel: {impl[n]}")
    emit("forward", lengths=sorted(lengths),
         attention={str(n): impl[n] for n in sorted(impl)},
         last_argmax_is_first_generated=True)
    return {n: argmax[n] for n in (512, 144)}


def phase_logprobs(port: int, ps: list[list[int]], greedy: list[list[int]]) -> list[float]:
    """The one surface that returns numbers: /v1/completions with logprobs,
    scored by a cache-less forward over prompt + completion."""
    n = 8
    out = post_ok(port, "/v1/completions", {
        "prompt": " ".join(f"t{t}" for t in ps[0]), "max_tokens": n,
        "temperature": 0, "logprobs": 0})
    choice = out["choices"][0]
    got = [int(t[1:]) for t in choice["text"].split()]
    lps = choice["logprobs"]["token_logprobs"]
    check(got == greedy[0][:n], f"completions tokens {got} are not the greedy tokens {greedy[0][:n]}")
    check(len(lps) == n and all(math.isfinite(x) and x <= 0 for x in lps),
          f"logprobs not finite and <= 0: {lps}")
    emit("logprobs", tokens=n, logprobs_finite=True, min_logprob=round(min(lps), 3))
    return lps


# jax's persistent cache, and the engine's executable store beside it
CACHE_KEYS = ("requests", "hits", "misses", "store_hits", "store_misses")


def phase_restart(kids: Children, pod: subprocess.Popen, port: int, model_dir: str,
                  ps: list[list[int]], greedy: list[list[int]], fwd_ids: list[int],
                  fwd512: list[int]) -> tuple[subprocess.Popen, int]:
    """Stop the pod, start another against the same compile cache, ask the
    same things: did the second start compile anything?"""
    _, before = http_json(port, "GET", "/metrics")
    kids.stop(pod)
    pod2, port2, ready_s = start_pod(kids, "pod-restart", model_dir, "")
    check(generate(port2, ps[0]) == greedy[0], "greedy tokens changed across a pod restart")
    check(forward_argmax(port2, fwd_ids) == fwd512, "forward argmax changed across a pod restart")
    _, after = http_json(port2, "GET", "/metrics")
    first, second = before.get("compile_cache", {}), after.get("compile_cache", {})
    check(second.get("dir") == first.get("dir") and second.get("dir"),
          f"the restart used another cache directory: {first} vs {second}")
    check(second.get("hits", 0) > 0, f"the restarted pod hit nothing in {second.get('dir')}: {second}")
    emit("restart", ready_seconds=round(ready_s, 1), cache_dir=second["dir"],
         first_start={k: first.get(k) for k in CACHE_KEYS},
         second_start={k: second.get(k) for k in CACHE_KEYS},
         second_start_compiled_anything=second.get("misses", 0) > 0,
         answers_equal_across_restart=True)
    return pod2, port2


# -- the two journeys ---------------------------------------------------------


def answers(port: int, ps: list[list[int]], w: dict, seed: int, on_accelerator: bool) -> dict:
    greedy = phase_generate(port, ps, w["vocab"])
    fwd = phase_forward(port, ps, greedy, w, seed, on_accelerator)
    return {"greedy": greedy, "forward": fwd, "logprobs": phase_logprobs(port, ps, greedy)}


def one_chip(kids: Children, args, w: dict, layers: int, work: str) -> dict:
    phase_native(kids)
    model_dir = os.path.join(work, "model")
    kids.run("init", CLI + ["init", model_dir], jax_child=False, timeout=120)
    nbytes = phase_checkpoint(model_dir, w, layers, args.seed)
    reg, ref = phase_registry_push(kids, work, model_dir)
    pod_dir = os.path.join(work, "pod-volume")
    phase_dl(kids, ref, pod_dir, "dp=1")
    pod, port, ready_s = start_pod(kids, "pod", pod_dir, "")
    metrics = pod_report(port, "serve", ready_s, nbytes, full_size=not args.rehearse)
    on_acc = metrics["device"]["platform"] != "cpu"
    ps = prompts(w, args.seed)
    got = answers(port, ps, w, args.seed, on_acc)
    phase_sample_stream(port, ps, got["greedy"])
    pod, port = phase_restart(kids, pod, port, pod_dir, ps, got["greedy"],
                              forward_inputs(w, args.seed)[512], got["forward"][512])
    _, metrics = http_json(port, "GET", "/metrics")
    kids.stop(pod)
    kids.stop(reg)
    return metrics["device"]


def four_chips(kids: Children, args, w: dict, layers: int, work: str) -> dict:
    """Only tensor-parallel serving and what it is compared with."""
    model_dir = os.path.join(work, "model")
    os.makedirs(model_dir)
    nbytes = phase_checkpoint(model_dir, w, layers, args.seed)
    ps = prompts(w, args.seed)
    got, device = {}, {}
    for mesh in ("dp=1", "dp=1,tp=4"):
        pod, port, ready_s = start_pod(kids, f"pod-{mesh.replace('=', '').replace(',', '-')}",
                                       model_dir, mesh)
        metrics = pod_report(port, f"serve[{mesh}]", ready_s, nbytes, full_size=False)
        device = metrics["device"]
        got[mesh] = answers(port, ps, w, args.seed, device["platform"] != "cpu")
        if mesh != "dp=1" and device.get("devices"):
            # the accountant's per-device truth: tp must spread the weights
            _, metrics = http_json(port, "GET", "/metrics")
            per = {k: v["hbm_bytes_in_use"] for k, v in metrics["device"]["devices"].items()}
            total = sum(per.values())
            shares = {k: round(v / total, 3) for k, v in per.items()}
            check(len(per) == 4 and all(TP_WEIGHT_SHARE[0] <= s <= TP_WEIGHT_SHARE[1]
                                        for s in shares.values())
                  and max(per.values()) < 0.6 * nbytes,
                  f"weights ({nbytes} bytes) are not spread over four devices: {per}")
            emit("tp_placement", hbm_bytes_in_use=per, shares=shares,
                 allowed_share=TP_WEIGHT_SHARE)
        kids.stop(pod)
    one, tp = got["dp=1"], got["dp=1,tp=4"]
    common = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b), len(x))
              for x, y in zip(one["greedy"], tp["greedy"])]
    agree = {n: sum(a == b for a, b in zip(one["forward"][n], tp["forward"][n])) / n
             for n in one["forward"]}
    k = min(common[0], len(one["logprobs"]))
    lp_diff = max((abs(a - b) for a, b in zip(one["logprobs"][:k], tp["logprobs"][:k])), default=0.0)
    emit("tp_vs_one_chip", common_greedy_prefix=common, forward_argmax_agreement=agree,
         max_logprob_diff=round(lp_diff, 4), scored_prefix=k,
         tolerance={"first_token_equal": True,
                    "min_common_prefix_on_2_of_3": TP_MIN_COMMON_PREFIX,
                    "min_argmax_agreement": TP_MIN_ARGMAX_AGREEMENT,
                    "max_logprob_diff": TP_MAX_LOGPROB_DIFF})
    check(all(c >= 1 for c in common), f"first generated token differs under tp=4: {common}")
    check(sum(c >= TP_MIN_COMMON_PREFIX for c in common) >= 2,
          f"greedy prefixes diverge too early under tp=4: {common}")
    check(all(a >= TP_MIN_ARGMAX_AGREEMENT for a in agree.values()),
          f"/v1/forward argmax disagrees under tp=4: {agree}")
    check(lp_diff <= TP_MAX_LOGPROB_DIFF, f"logprobs differ by {lp_diff} under tp=4")
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--layers", type=int, default=0,
                    help="depth (default: 8 of 32 on one chip, 4 with --chips 4, 2 with --rehearse)")
    ap.add_argument("--hidden", type=int, default=0,
                    help="--rehearse only: another tiny hidden size (widths are never cut on the chip)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever jax finds; the device gate still decides ok")
    ap.add_argument("--workdir", default=os.path.join(HERE, ".cache", "chip_smoke"))
    ap.add_argument("--keep", action="store_true", help="keep the workdir (logs, checkpoint)")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(HERE, "modelx_tpu")):
        print("chip_smoke.py: modelx_tpu/ is not beside this script — nothing to drive",
              file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    w = dict(TINY if args.rehearse else PUBLISHED)
    if args.hidden:
        if not args.rehearse:
            ap.error("--hidden is for --rehearse: the chip run keeps the published widths")
        w.update(hidden=args.hidden, inter=2 * args.hidden, head_dim=args.hidden // w["heads"])
    layers = args.layers or (w["layers"] if args.rehearse else (8 if args.chips == 1 else 4))

    work = args.workdir
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.join(work, "logs"))
    kids = Children(os.path.join(work, "logs"))
    try:
        probe = phase_device(kids)
        if probe["platform"] == "cpu" and not args.rehearse:
            raise Fail("jax found no accelerator (platform cpu): nothing to smoke")
        check(probe["count"] >= args.chips,
              f"--chips {args.chips} needs {args.chips} devices, jax found {probe['count']}")
        if not args.rehearse:
            # the file exists up to three times: source, registry blob, pod volume
            need = 3.3 * checkpoint_bytes(w, layers)
            while layers > 5 and shutil.disk_usage(work).free < need:
                layers -= 1
                need = 3.3 * checkpoint_bytes(w, layers)
            check(shutil.disk_usage(work).free >= need,
                  f"not enough disk under {work} for a {layers}-layer checkpoint")
        device = (one_chip if args.chips == 1 else four_chips)(kids, args, w, layers, work)
    except Fail as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 2
    finally:
        kids.stop_all()
        if not args.keep:
            shutil.rmtree(work, ignore_errors=True)

    final = {"platform": device["platform"], "kind": device["device_kind"],
             "count": device["device_count"]}
    ok = final["platform"] != "cpu" and final["count"] == args.chips
    print(json.dumps({"ok": ok, "device": final}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
