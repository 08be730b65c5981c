"""Benchmark: registry -> TPU HBM load, TTFT, and serving throughput.

Stands up a local registry, pushes a synthetic llama-shaped bf16 checkpoint,
then measures:

- baseline: the reference's deployment shape — download the blob to a pod
  volume as one sequential stream (modelxdl semantics, pull.go:111-143),
  then read it and device_put tensor-by-tensor;
- modelx-tpu: the loader path — blob-location redirect (file provider for
  the colocated registry, ranged HTTP otherwise) planned from the manifest's
  tensor index, streamed into device memory overlapped with fetches;
- link probe: raw host->device bandwidth of the machine (the ceiling for
  any loader; reported so the ratio value/link is interpretable);
- ttft_ms: p50 time from "fresh process asks the registry for the model" to
  "first decoded token", warm persistent XLA cache (BASELINE.md north star);
- serving: prefill/decode tokens/s and MFU for the pushed model;
- mixed prefill/decode: admit a long prompt into a saturated continuous
  decode batch and report inter-token latency p99 with chunked prefill on
  vs the monolithic-admission baseline (``itl_p99_ms_mixed``,
  ``itl_p99_ms_mixed_baseline``, ``admission_stall_ms_max``).

Leg isolation: every TIMED load leg runs in its own FRESH subprocess
(``python bench.py --leg <kind> ...``) — a deploy is a fresh process, and
a chip belongs to one process at a time, so this parent stays off jax
until the measured children are done (``_device_child_env`` refuses
otherwise). Each child also probes the raw link AFTER its load, so every
leg carries its own ceiling context. A collapsed-leg guard rechecks the
verdict: if the best loader leg lost 4x to the baseline AND sat under 10%
of the measured link, that leg reruns once in another fresh process, and
the JSON records which legs were retried (``legs_retried``).

No accelerator, no capture: the device probe rejects a CPU backend, the
peaks tables have no entry for one, and a capture with a failed or
skipped leg exits non-zero after printing. Nothing here has been measured
on the current chip (ROADMAP S0 rebuilds this file as a benchmark of
cells).

Prints ONE JSON line; "value" stays registry->HBM GB/s (the BASELINE
metric), extras carry the rest.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

# Per-chip peaks used for MFU / bandwidth-utilization, keyed by the prefix
# of jax's ``device_kind``. Public specs (Google Cloud TPU documentation):
# v5e 197 bf16 TFLOP/s + 819 GB/s HBM; v5p 459 TFLOP/s + 2765 GB/s;
# v4 275 TFLOP/s + 1228 GB/s. Accelerators only: a device that is not in
# the table is an error, not a default.
PEAK_FLOPS = {"TPU v5p": 459e12, "TPU v5 lite": 197e12, "TPU v5e": 197e12,
              "TPU v4": 275e12}
HBM_GBPS = {"TPU v5p": 2765e9, "TPU v5 lite": 819e9, "TPU v5e": 819e9,
            "TPU v4": 1228e9}


def _chip_spec(table: dict, device_kind: str) -> float:
    for k, v in table.items():
        if device_kind.startswith(k):
            return v
    raise KeyError(
        f"no published peak for device kind {device_kind!r}: a utilization "
        "is only reported against a known accelerator")


def build_checkpoint(path: str, target_bytes: int, hidden: int = 2048,
                     inter: int = 5632, vocab: int = 32000,
                     seed: int = 0) -> int:
    """Synthetic llama-shaped checkpoint (bf16) of roughly target_bytes.
    ``seed`` varies the weight bytes so legs that must distinguish
    models by CONTENT (the tier store keys on manifest digests) get
    genuinely different checkpoints, not byte-identical ones."""
    import ml_dtypes

    from modelx_tpu.dl import safetensors as st

    rng = np.random.RandomState(seed)
    tensors: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": rng.rand(vocab, hidden).astype(ml_dtypes.bfloat16),
        "model.norm.weight": np.ones((hidden,), ml_dtypes.bfloat16),
    }
    layer_bytes = 2 * (4 * hidden * hidden + 3 * hidden * inter + 2 * hidden)
    base = 2 * vocab * hidden
    layers = max(1, (target_bytes - base) // layer_bytes)
    for i in range(layers):
        p = f"model.layers.{i}."
        tensors[p + "self_attn.q_proj.weight"] = rng.rand(hidden, hidden).astype(ml_dtypes.bfloat16)
        tensors[p + "self_attn.k_proj.weight"] = rng.rand(hidden, hidden).astype(ml_dtypes.bfloat16)
        tensors[p + "self_attn.v_proj.weight"] = rng.rand(hidden, hidden).astype(ml_dtypes.bfloat16)
        tensors[p + "self_attn.o_proj.weight"] = rng.rand(hidden, hidden).astype(ml_dtypes.bfloat16)
        tensors[p + "mlp.gate_proj.weight"] = rng.rand(inter, hidden).astype(ml_dtypes.bfloat16)
        tensors[p + "mlp.up_proj.weight"] = rng.rand(inter, hidden).astype(ml_dtypes.bfloat16)
        tensors[p + "mlp.down_proj.weight"] = rng.rand(hidden, inter).astype(ml_dtypes.bfloat16)
        tensors[p + "input_layernorm.weight"] = np.ones((hidden,), ml_dtypes.bfloat16)
        tensors[p + "post_attention_layernorm.weight"] = np.ones((hidden,), ml_dtypes.bfloat16)
    st.write_safetensors(path, tensors)
    return os.path.getsize(path)


def start_registry(workdir: str) -> tuple[subprocess.Popen, str]:
    from modelx_tpu.registry.server import free_port

    port = free_port()
    base = f"http://127.0.0.1:{port}"
    env = dict(os.environ,
               PYTHONPATH=os.path.dirname(os.path.abspath(__file__)),
               JAX_PLATFORMS="cpu")
    srv = subprocess.Popen(
        [sys.executable, "-m", "modelx_tpu.cli", "serve",
         "--listen", f"127.0.0.1:{port}",
         "--data", os.path.join(workdir, "registry")],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    import requests

    for _ in range(50):
        try:
            requests.get(base + "/healthz", timeout=1)
            break
        except Exception:
            time.sleep(0.2)
    return srv, base


def push_checkpoint(base: str, repo: str, ckpt: str):
    from modelx_tpu.client.client import Client
    from modelx_tpu.client.helper import descriptor_for_file
    from modelx_tpu.client.push import _annotate_safetensors
    from modelx_tpu.types import Manifest

    client = Client(base, quiet=True)
    desc = descriptor_for_file(ckpt, "model.safetensors", "application/vnd.modelx.model.file.v1")
    _annotate_safetensors(ckpt, desc)
    with open(ckpt, "rb") as f:
        client.remote.upload_blob_content(repo, desc, f)
    client.remote.put_manifest(repo, "v1", Manifest(blobs=[desc]))
    return client, desc


def probe_link_gbps(device, nbytes: int = 16 << 20, reps: int = 3) -> float:
    """Median raw host->device bandwidth for random (incompressible) bytes."""
    import jax

    a = np.random.randint(0, 256, nbytes, dtype=np.uint8)
    x = jax.device_put(a, device)
    x.block_until_ready()
    del x
    ts = []
    for _ in range(reps):
        t0 = time.monotonic()
        x = jax.device_put(a, device)
        x.block_until_ready()
        ts.append(time.monotonic() - t0)
        del x
    return nbytes / statistics.median(ts) / 1e9


def run_ours(client, repo: str, desc, mesh, size: int,
             quantize: str | None = None, cache=None,
             prefer_local: bool | None = None) -> tuple[float, str, object]:
    """The loader path through the blob-location seam. Returns (seconds,
    source-class name actually used — proves which engine ran, LoadStats
    for the fetch/device decomposition). ``cache`` routes the load through
    the local blob-cache tier; ``prefer_local=False`` skips the colocated
    file redirect so the leg models a remote pod (the cache legs' shape)."""
    from modelx_tpu.dl.initializer import _blob_source
    from modelx_tpu.dl.loader import load_safetensors
    from modelx_tpu.dl import safetensors as st
    from modelx_tpu.dl.sharding import LLAMA_RULES

    t0 = time.monotonic()
    source = _blob_source(client, repo, desc, cache=cache, prefer_local=prefer_local)
    tensors = data_offset = None
    from modelx_tpu.types import AnnotationTensorIndex

    if AnnotationTensorIndex in desc.annotations:
        tensors, data_offset = st.parse_index_annotation(desc.annotations[AnnotationTensorIndex])
    try:
        loaded, stats = load_safetensors(
            source, mesh, LLAMA_RULES, tensors=tensors, data_offset=data_offset,
            quantize=quantize,
        )
    finally:
        if hasattr(source, "close"):
            source.close()
    seconds = time.monotonic() - t0
    del loaded
    return seconds, type(source).__name__, stats


def run_baseline(base: str, repo: str, desc, workdir: str, devices) -> float:
    """Reference deployment shape: one sequential download to a volume file,
    then read + per-tensor device_put (cmd/modelxdl semantics)."""
    import jax
    import requests

    from modelx_tpu.dl import safetensors as st

    url = f"{base}/{repo}/blobs/{desc.digest}"
    t0 = time.monotonic()
    vol = os.path.join(workdir, "volume.safetensors")
    with requests.get(url, stream=True) as r, open(vol, "wb") as f:
        for chunk in r.iter_content(chunk_size=1024 * 1024):
            f.write(chunk)
    arrays = []
    with open(vol, "rb") as f:
        infos, off = st.read_header(f)
        for name, info in infos.items():
            f.seek(off + info.start)
            raw = f.read(info.nbytes)
            arr = np.frombuffer(raw, info.np_dtype()).reshape(info.shape)
            arrays.append(jax.device_put(arr, devices[0]))
    jax.block_until_ready(arrays)
    seconds = time.monotonic() - t0
    del arrays
    os.unlink(vol)
    return seconds


def measure_ttft(base: str, repo: str, runs: int = 5, int8_runs: int = 2,
                 blob_cache_dir: str = "", child_timeout_s: float = 900.0) -> dict:
    """p50 registry->first-token (BASELINE north star), subprocess-per-run.

    Each run is a FRESH process (``python -m modelx_tpu.dl.ttft``), because
    a deploy is one, with the warm persistent caches a pre-baked sidecar
    image ships (XLA compile cache + serialized-export cache, wherever
    dl/serve.enable_compile_cache resolves). The caller must NOT have
    initialized the TPU backend yet — the child processes own the device
    while this runs.

    Reported decomposition (medians over scored runs): plan (manifest +
    family detect), load (registry->HBM, overlapped with the AOT compile),
    compile_join (leftover compile after load), first_exec; and
    ``ttft_weights_ready_ms`` (the registry+loader leg this framework owns)
    alongside the headline."""
    env = _device_child_env()  # children use the real device
    if blob_cache_dir:
        # blob-cache (warm-restart) variant: the children share one local
        # blob cache and skip the colocated file redirect, so run 0 pays
        # the network (and fills the cache) while every scored run models a
        # warm pod restart — zero network reads for the weights
        env = dict(env, MODELX_BLOB_CACHE_DIR=blob_cache_dir,
                   MODELX_DL_NO_LOCAL_REDIRECT="1")

    def run_once(quantize: str = "") -> dict:
        cmd = [sys.executable, "-m", "modelx_tpu.dl.ttft", base, repo, ""]
        if quantize:
            cmd.append(quantize)
        p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=max(60.0, child_timeout_s))
        if p.returncode != 0:
            raise RuntimeError(f"ttft run failed: {p.stderr[-2000:]}")
        return json.loads(p.stdout.strip().splitlines()[-1])

    records = []
    for i in range(runs + 1):  # run 0 warms the persistent caches, unscored
        rec = run_once()
        if i > 0:
            records.append(rec)
    if not records:
        return {}

    def med(key: str) -> float:
        return round(statistics.median(r[key] for r in records), 1)

    out = {
        "ttft_ms": med("ttft_ms"),
        "ttft_ms_runs": [round(r["ttft_ms"], 1) for r in records],
        "ttft_plan_ms": med("plan_ms"),
        "ttft_load_ms": med("load_ms"),
        "ttft_compile_join_ms": med("compile_join_ms"),
        "ttft_first_exec_ms": med("first_exec_ms"),
        "ttft_weights_ready_ms": med("weights_ready_ms"),
        # best-of alongside the medians; ttft_ms_runs is the full evidence
        "ttft_ms_best": round(min(r["ttft_ms"] for r in records), 1),
        "ttft_weights_ready_best_ms": round(
            min(r["weights_ready_ms"] for r in records), 1
        ),
    }
    if int8_runs > 0:
        q_records = []
        for _ in range(int8_runs + 1):
            q_records.append(run_once("int8"))
        q_records = q_records[1:]
        out["ttft_int8_ms"] = round(
            statistics.median(r["ttft_ms"] for r in q_records), 1
        )
        out["ttft_int8_weights_ready_ms"] = round(
            statistics.median(r["weights_ready_ms"] for r in q_records), 1
        )
    return out


def measure_program_store(base: str, repo: str,
                          child_timeout_s: float = 600.0,
                          env: dict | None = None) -> dict:
    """Compiled-program registry leg (ISSUE 11): pod 1 boots with an EMPTY
    compile cache, pays the full trace+lower+compile, and publishes its
    AOT surface to the model version as a program bundle; pod 2 boots in
    another fresh process with its own empty cache, pulls the bundle
    on-the-clock, and its compile leg becomes deserialize + XLA-cache
    hit. Both are real ``dl/ttft.py`` children — the same measurement the
    headline TTFT legs use — differing ONLY in whether the registry holds
    programs when they boot.

    Reported: cold vs bundle-warm ``compile_thread_ms`` (the acceptance
    ratio: warm <= 0.5x cold), the matching ``ttft_ms``/``first_exec_ms``
    pairs, and the publish/install counts proving bytes actually moved
    through the registry rather than a shared local cache dir."""
    env = dict(env if env is not None else _device_child_env())

    def run_child(cache_dir: str, publish: bool) -> dict:
        cmd = [sys.executable, "-m", "modelx_tpu.dl.ttft", base, repo,
               cache_dir]
        if publish:
            # argv is positional: empty quantize / blob_cache_dir slots
            cmd += ["", "", "publish"]
        p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=max(60.0, child_timeout_s))
        if p.returncode != 0:
            raise RuntimeError(
                f"program-store ttft child failed: {p.stderr[-2000:]}"
            )
        return json.loads(p.stdout.strip().splitlines()[-1])

    # both pods boot with an EMPTY compile cache: "cold:<leg>" has the child
    # clear a fixed name under the checkout's cache dir
    # (dl/serve.cold_cache_dir) — this parent stays off jax
    cold = run_child("cold:program-store-cold", publish=True)
    warm = run_child("cold:program-store-warm", publish=False)
    ratio = (
        round(warm["compile_thread_ms"] / cold["compile_thread_ms"], 3)
        if cold["compile_thread_ms"] else None
    )
    return {
        "programs_published": cold["programs_published"],
        "programs_installed": warm["programs_installed"],
        "program_cold_compile_ms": cold["compile_thread_ms"],
        "program_warm_compile_ms": warm["compile_thread_ms"],
        "program_warm_compile_ratio": ratio,
        "program_cold_first_exec_ms": cold["first_exec_ms"],
        "program_warm_first_exec_ms": warm["first_exec_ms"],
        "program_cold_ttft_ms": cold["ttft_ms"],
        "program_warm_ttft_ms": warm["ttft_ms"],
    }


def measure_kv_store(model_dir: str, base: str, repo: str = "library/kv",
                     dtype: str = "bfloat16", prompt_len: int = 192,
                     suffix_len: int = 16, new_tokens: int = 8,
                     max_seq_len: int = 512) -> dict:
    """Content-addressed prefix-KV registry leg (ISSUE 20): pod 1 serves a
    hot shared system prompt H until its prefix KV crosses the publish
    threshold, builds the bundle and attaches it to the model version; a
    SECOND fresh pod (its own ModelServer, empty prefix cache) pulls the
    bundle from the registry at load and answers H + a new suffix from
    the INSTALLED entry — its TTFT drops from a full prefill to a
    suffix prefill (``kv_warm_ttft_ratio``, pass < 0.6).

    Compile isolation: both scored streams run against programs the
    DECOY prompts B / B+S' / D already compiled on pod 2 (same padded
    shapes, different tokens), so the ratio prices prefill compute, not
    trace+compile. ``kv_hits_installed`` >= 1 is asserted — a warm number
    that never touched the installed entry would be a vacuous pass."""
    from modelx_tpu.client.client import Client
    from modelx_tpu.dl import kv_store
    from modelx_tpu.dl.serve import ModelServer

    ckpt = os.path.join(model_dir, "model.safetensors")
    client, _desc = push_checkpoint(base, repo, ckpt)
    ref = f"{base}/{repo}@v1"

    def pod() -> ModelServer:
        srv = ModelServer(model_dir, dtype=dtype, max_seq_len=max_seq_len,
                          prefix_cache_size=8)
        srv.load()
        return srv

    def stream(srv, ids) -> float | None:
        """Drain one stream fully; returns ms-to-first-piece (TTFT)."""
        toks = np.asarray([ids], np.int32)
        t0 = time.monotonic()
        first_ms = None
        for _piece in srv.generate_stream(toks, max_new_tokens=new_tokens,
                                          chunk_size=8):
            if first_ms is None:
                first_ms = (time.monotonic() - t0) * 1e3
        return first_ms

    pod1 = pod()
    rng = np.random.RandomState(31)
    vocab = int(pod1.cfg.vocab_size)

    def prompt(n: int) -> list[int]:
        return rng.randint(1, vocab, n).astype(np.int32).tolist()

    hot = prompt(prompt_len)  # the shared system prompt
    # turn 1 stores H; two follow-up turns extending H push its hit count
    # to the publish threshold (an identical re-send is NOT a hit — the
    # cache serves strict prefixes, like real multi-turn traffic)
    stream(pod1, hot)
    stream(pod1, hot + prompt(suffix_len))
    stream(pod1, hot + prompt(suffix_len))
    model_key = kv_store.model_key_for_ref(ref)
    published = 0
    for key, entry in pod1._prefix_cache.take_publishable(2):
        data = kv_store.build_bundle(list(key), entry, model_key=model_key,
                                     mesh=pod1.mesh)
        if data is not None:
            kv_store.publish_bundle(ref, data)
            published += 1
    if published < 1:
        raise RuntimeError("kv leg: pod 1 published no bundle "
                           f"(cache stats {pod1._prefix_cache.stats()})")
    del pod1

    # pod 2: fresh server + empty prefix cache; the registry is the only
    # channel the hot prefix can arrive through
    pod2 = pod()
    _fwd, init = pod2.family.decode_fns(pod2.cfg, mesh=pod2.mesh)
    inst = kv_store.pull_and_install(
        client, repo, client.get_manifest(repo, "v1"), init,
        pod2._prefix_cache, mesh=pod2.mesh, model_key=model_key)
    if inst["installed"] < 1:
        raise RuntimeError(f"kv leg: pod 2 installed nothing: {inst}")

    # decoy prewarm: D compiles the full-prefill program at the scored
    # total length, B then B+S' compile the suffix-prefill (hit) pair at
    # the scored shapes — different tokens, so nothing leaks into the
    # scored prompts' cache keys
    stream(pod2, prompt(prompt_len + suffix_len))            # D: cold shape
    decoy = prompt(prompt_len)
    stream(pod2, decoy)                                      # B: stores B
    stream(pod2, decoy + prompt(suffix_len))                 # B+S': hit shape

    warm_ms = stream(pod2, hot + prompt(suffix_len))
    hits_installed = pod2._prefix_cache.stats()["hits_installed"]
    if hits_installed < 1:
        raise RuntimeError(
            "kv leg: the scored warm stream missed the installed entry "
            f"(cache stats {pod2._prefix_cache.stats()})")
    cold_ms = stream(pod2, prompt(prompt_len + suffix_len))
    return {
        "kv_published": published,
        "kv_installed": inst["installed"],
        "kv_install_skipped": inst["skipped"],
        "kv_hits_installed": hits_installed,
        "kv_warm_ttft_ms": round(warm_ms, 1),
        "kv_cold_ttft_ms": round(cold_ms, 1),
        "kv_warm_ttft_ratio": round(warm_ms / cold_ms, 3) if cold_ms else None,
    }


def cache_split_summary(size: int, cold_rec: dict, warm_rec: dict) -> dict:
    """The multi-tier cache's cold/warm split from two blob-cache legs
    (leg_main kinds "cold"/"warm"). ``warm_hit`` is the zero-network-reads
    verdict: the warm leg's source must be the cache's LocalFileSource.
    ``cold_overlap_seconds``/``cold_staging_allocs`` surface the cold
    pipeline's fetch-vs-device_put overlap and staging-pool reuse."""
    cold_gbps = size / max(cold_rec["seconds"], 1e-9) / 1e9
    warm_gbps = size / max(warm_rec["seconds"], 1e-9) / 1e9
    return {
        "registry_to_hbm_cold_cached_gbps": round(cold_gbps, 3),
        "registry_to_hbm_warm_gbps": round(warm_gbps, 3),
        "warm_seconds": round(warm_rec["seconds"], 3),
        "warm_vs_cold": round(warm_gbps / max(cold_gbps, 1e-9), 3),
        "warm_hit": bool(warm_rec.get("cache_state") == "warm"),
        "cold_overlap_seconds": cold_rec.get("overlap_seconds"),
        "cold_staging_allocs": cold_rec.get("staging_allocs"),
        "cold_fetch_growths": cold_rec.get("fetch_growths"),
    }


def ttft_warm_fields(warm_ttft: dict) -> dict:
    """Key mapping for the warm-restart TTFT variant (measure_ttft with a
    shared blob cache): the bench JSON carries them under ttft_warm_*."""
    return {
        "ttft_warm_ms": warm_ttft.get("ttft_ms"),
        "ttft_warm_weights_ready_ms": warm_ttft.get("ttft_weights_ready_ms"),
    }


# stdlib-only puller (no jax import: interpreter startup must not drown the
# transfer) — http.client + readinto into one reused buffer, the same
# zero-copy discipline the loader's HTTPSource uses. The stream is
# consumed, counted, and discarded: in the deployment being modeled each
# tenant lands bytes on its own pod volume (or straight in HBM), so N
# tenants funneling ~2 GB through one shared disk would measure the
# kernel's dirty-page writeback throttle, not the registry's data plane.
# Byte count goes to stdout for verification.
_PULL_SNIPPET = r"""
import sys, time, http.client, urllib.parse
url = sys.argv[1]
u = urllib.parse.urlsplit(url)
t0 = time.monotonic()
conn = http.client.HTTPConnection(u.hostname, u.port, timeout=300)
conn.request("GET", u.path)
resp = conn.getresponse()
assert resp.status == 200, resp.status
buf = bytearray(16 << 20)
view = memoryview(buf)
n = 0
while True:
    got = resp.readinto(view)
    if not got:
        break
    n += got
print(time.monotonic() - t0, n)
"""


def measure_multitenant(base: str, repo: str, desc, size: int,
                        clients: int = 4) -> dict:
    """BASELINE config #5: N tenants pulling concurrently from one registry.
    Each tenant is its own process (the pod shape), streaming through the
    server's direct GET — this stresses the registry data plane itself;
    colocated tenants would take the file redirect and not touch it at all.
    Pass = aggregate GB/s with N clients >= 1 client."""
    url = f"{base}/{repo}/blobs/{desc.digest}"

    # -S + clean env: nothing but the stdlib in the pullers, so interpreter
    # startup is not billed to the transfer
    env = {"PATH": os.environ.get("PATH", "")}

    def run_n(n: int) -> float:
        procs = []
        t0 = time.monotonic()
        for i in range(n):
            procs.append(subprocess.Popen(
                [sys.executable, "-S", "-c", _PULL_SNIPPET, url],
                stdout=subprocess.PIPE, text=True, env=env))
        outs = []
        for i, p in enumerate(procs):
            out, _ = p.communicate(timeout=600)
            if p.returncode != 0:
                raise RuntimeError(f"multitenant puller {i} exited {p.returncode}")
            outs.append(out)
        wall = time.monotonic() - t0
        for i, out in enumerate(outs):
            got = int(out.split()[1])
            if got != size:  # a partial transfer must not inflate the GB/s
                raise RuntimeError(f"multitenant puller {i}: {got} of {size} bytes")
        return wall

    run_n(1)  # warm page cache + interpreter startup path
    single = run_n(1)
    multi = run_n(clients)
    return {
        "mt_clients": clients,
        "mt_single_gbps": round(size / single / 1e9, 3),
        "mt_aggregate_gbps": round(clients * size / multi / 1e9, 3),
        # context for the aggregate number: the server's data plane is kernel
        # sendfile (no Python byte-shuffling), so N clients scale with CPU
        # cores — with fewer cores than tenants their own read loops
        # contend and aggregate can sit below single-client
        "mt_host_cores": os.cpu_count(),
    }


# Colocated tenant: ask the registry for the blob's location (control
# plane), then pread the advertised file directly (data plane) — the
# load-separation deployment shape. Stdlib-only like _PULL_SNIPPET.
_REDIRECT_PULL_SNIPPET = r"""
import json, sys, time, os, http.client, urllib.parse
url = sys.argv[1]  # .../{repo}/blobs/{digest}/locations/download
u = urllib.parse.urlsplit(url)
t0 = time.monotonic()
conn = http.client.HTTPConnection(u.hostname, u.port, timeout=60)
conn.request("GET", u.path)
resp = conn.getresponse()
assert resp.status == 200, resp.status
loc = json.loads(resp.read())
assert loc["provider"] == "file", loc
path = loc["properties"]["path"]
fd = os.open(path, os.O_RDONLY)
buf = bytearray(16 << 20)
view = memoryview(buf)
n = 0
while True:
    got = os.preadv(fd, [view], n)
    if got <= 0:
        break
    n += got
os.close(fd)
print(time.monotonic() - t0, n)
"""


def measure_redirect_multitenant(base: str, repo: str, desc, size: int,
                                 clients: int = 4) -> dict:
    """Load separation, measured (docs/api.md:32-42 is the reference's core
    architectural claim): colocated tenants fetch the blob LOCATION from the
    server (tiny control-plane JSON) and read the bytes straight from the
    store's filesystem — the bulk data plane never crosses the registry
    process, so N tenants scale with storage bandwidth, not server CPU."""
    url = f"{base}/{repo}/blobs/{desc.digest}/locations/download"
    env = {"PATH": os.environ.get("PATH", "")}

    def run_n(n: int) -> float:
        t0 = time.monotonic()
        procs = [subprocess.Popen(
            [sys.executable, "-S", "-c", _REDIRECT_PULL_SNIPPET, url],
            stdout=subprocess.PIPE, text=True, env=env) for _ in range(n)]
        for i, p in enumerate(procs):
            out, _ = p.communicate(timeout=600)
            if p.returncode != 0:
                raise RuntimeError(f"redirect puller {i} exited {p.returncode}")
            got = int(out.split()[1])
            if got != size:
                raise RuntimeError(f"redirect puller {i}: {got} of {size} bytes")
        return time.monotonic() - t0

    run_n(1)
    single = run_n(1)
    multi = run_n(clients)
    return {
        "mt_redirect_single_gbps": round(size / single / 1e9, 3),
        "mt_redirect_aggregate_gbps": round(clients * size / multi / 1e9, 3),
    }


def measure_serving(params: dict, mesh, device_kind: str, decode_only: bool = False,
                    weight_bytes_per_param: int = 2) -> dict:
    """Prefill + cached-decode throughput and MFU for the loaded model."""
    import jax
    import jax.numpy as jnp

    from modelx_tpu.dl import families as fam

    family = fam.detect(list(params))
    cfg = family.infer_config(params)
    # the forward spans the whole mesh: utilization is against ALL its chips
    peak = _chip_spec(PEAK_FLOPS, device_kind) * mesh.devices.size

    h, layers, inter, vocab = (cfg.hidden_size, cfg.num_layers,
                               cfg.intermediate_size, cfg.vocab_size)
    # dense matmul params touched per token: attention + mlp + lm_head
    # (embedding lookup is a gather, not a matmul)
    p_matmul = layers * (4 * h * h + 3 * h * inter) + vocab * h

    out: dict = {}
    rng = np.random.RandomState(7)

    # Timing discipline: every timed call ends in a small result fetch (jax
    # dispatch is asynchronous). Per-call latency includes the host<->device
    # round trip; steady-state throughput pipelines N dispatches and fetches
    # once, the shape a serving batcher actually drives.
    def fetch(x):
        return float(jnp.reshape(x, (-1,))[0])

    # -- prefill ------------------------------------------------------------
    B, S = 8, 512
    toks = jnp.asarray(rng.randint(1, vocab, (B, S)), jnp.int32)
    if not decode_only:
        fwd = jax.jit(lambda p, t: family.forward(p, t, cfg, mesh=mesh))
        fetch(fwd(params, toks))  # compile
        lat = []
        for _ in range(3):
            t0 = time.monotonic()
            fetch(fwd(params, toks))
            lat.append(time.monotonic() - t0)
        t0 = time.monotonic()
        outs = [fwd(params, toks) for _ in range(8)]
        fetch(outs[-1])
        pipe_dt = (time.monotonic() - t0) / 8
        dt = statistics.median(lat)
        # attention score+value matmuls: 2 * 2 * h per (query, key<=query) pair
        flops = 2 * p_matmul * B * S + layers * 4 * h * B * S * S / 2
        out["prefill_latency_ms"] = round(dt * 1e3, 1)
        out["prefill_tokens_per_s"] = round(B * S / pipe_dt, 1)
        out["prefill_mfu"] = round(flops / pipe_dt / peak, 4)

    # -- cached decode ------------------------------------------------------
    # one jit call decodes N tokens via lax.scan. Per-step cost comes from
    # the slope between two generation lengths — a single-length timing
    # would bill the fixed per-call host<->device round trip to the decode
    # loop.
    prompt = toks[:, :128]
    lens = (16, 144)  # wide spread: slope noise shrinks with the step gap
    call_dt = {}
    for new in lens:
        gen = jax.jit(
            lambda p, t, n=new: family.generate(p, t, cfg, mesh=mesh, max_new_tokens=n)
        )
        fetch(gen(params, prompt))  # compile
        lat = []
        for _ in range(4):
            t0 = time.monotonic()
            fetch(gen(params, prompt))
            lat.append(time.monotonic() - t0)
        call_dt[new] = statistics.median(lat)
    slope = (call_dt[lens[1]] - call_dt[lens[0]]) / (lens[1] - lens[0])
    if slope <= 0:
        # noise won: a longer generation measured faster than a shorter one.
        # Flag it instead of publishing a nonsense throughput.
        out["decode_slope_invalid"] = True
        out["decode_call_seconds"] = {str(k): round(v, 4) for k, v in call_dt.items()}
    else:
        out["decode_tokens_per_s"] = round(B / slope, 1)
        out["decode_call_overhead_ms"] = round((call_dt[lens[0]] - lens[0] * slope) * 1e3, 1)
        # decode is HBM-bound: every step re-reads the weights; utilization
        # against the mesh's aggregate memory bandwidth is the roofline
        hbm_bw = _chip_spec(HBM_GBPS, device_kind) * mesh.devices.size
        out["decode_model_bandwidth_util"] = round(
            weight_bytes_per_param * p_matmul / slope / hbm_bw, 4
        )
    out["serving_batch"] = B
    return out


def _engine_shim(params: dict, mesh, max_seq_len: int):
    """ContinuousBatcher's ModelServer surface over already-loaded arrays
    (family/config re-detected from the parameter names). Every serving
    leg builds one; keeping the attribute set in ONE place means a new
    required server attribute cannot silently miss a leg."""
    from modelx_tpu.dl import families as fam

    family = fam.detect(list(params))

    class _Shim:
        pass

    shim = _Shim()
    shim.family, shim.cfg, shim.mesh = family, family.infer_config(params), mesh
    shim.max_seq_len, shim.params = max_seq_len, params
    shim.stats = {"tokens_generated": 0}
    return shim


def measure_continuous(params: dict, mesh, decode_tps: float | None) -> dict:
    """In-flight batching under load: 8 concurrent clients, each submitting
    independent generate requests against one running engine. The engine
    runs a LARGE chunk here (128) to amortize the per-dispatch round trip
    (decode_call_overhead_ms) — a value tuned on a rig that is gone;
    re-measure on the current chip, where the default 8-16 may serve the
    same aggregate at finer flush granularity. Target (VERDICT r3):
    aggregate tokens/s >= 0.8x the batch-8 slope-derived decode
    throughput."""
    import threading as _t
    from concurrent.futures import ThreadPoolExecutor

    from modelx_tpu.dl.continuous import ContinuousBatcher

    import jax
    import jax.numpy as jnp

    shim = _engine_shim(params, mesh, 1024)
    cfg = shim.cfg
    chunk = 128
    clients, new_tokens = 8, 256
    # burst_window_ms 5: the 8 barrier-released clients contend on the GIL
    # while submitting, so give co-arrivals a real window — admitting the
    # whole burst as one batch keeps every row at the same decode depth
    # (stragglers that miss a 128-step chunk boundary cost a whole extra
    # chunk of misaligned decode)
    cb = ContinuousBatcher(shim, max_slots=8, chunk_size=chunk, max_len=1024,
                           burst_window_ms=5.0)
    try:
        rng = np.random.RandomState(11)
        prompts = [
            rng.randint(1, cfg.vocab_size, (1, 128)).astype(np.int32)
            for _ in range(clients + 1)
        ]
        # warm generates: the first compiles single-admit+chunk, the
        # two-row one compiles the size-invariant BATCHED admit program
        # (one compile per prompt bucket — burst size doesn't retrace)
        cb.generate(prompts[-1], max_new_tokens=8)
        cb.generate(np.concatenate([prompts[-1], prompts[-1]]), max_new_tokens=8)
        start = _t.Barrier(clients)

        def client(i: int) -> int:
            start.wait()  # all clients hit the running engine together
            out = cb.generate(prompts[i], max_new_tokens=new_tokens)
            return out.shape[1] - prompts[i].shape[1]

        t0 = time.monotonic()
        with ThreadPoolExecutor(clients) as pool:
            totals = list(pool.map(client, range(clients)))
        dt = time.monotonic() - t0
        agg = sum(totals) / dt

        # in-engine speculation (a lone greedy row swaps chunks for n-gram
        # verify steps): feed a self-repeating continuation and report
        # device-steps/token — the whole value proposition is < 1.0.
        # NB steps/token is the device-efficiency signal; the tokens/s
        # alongside it is bound by the per-verify synchronous dispatch
        spec_cb = ContinuousBatcher(shim, max_slots=2, chunk_size=8,
                                    max_len=1024, speculative_k=6)
        try:
            seed_prompt = prompts[-1][:, :32]
            warm = spec_cb.generate(seed_prompt, max_new_tokens=8)
            rep = np.concatenate([warm, warm[:, -24:]], axis=1)
            spec_cb.generate(rep, max_new_tokens=8)  # compile the verify
            steps0 = spec_cb.stats.get("spec_steps", 0)
            chunks0 = spec_cb.stats["chunks"]
            acc0 = spec_cb.stats.get("spec_accepted", 0)
            n_spec = 96
            t0 = time.monotonic()
            spec_cb.generate(rep, max_new_tokens=n_spec)
            spec_dt = time.monotonic() - t0
            dev_steps = (
                spec_cb.stats.get("spec_steps", 0) - steps0
                + (spec_cb.stats["chunks"] - chunks0) * spec_cb.chunk_size
            )
            spec_out = {
                "continuous_spec_tokens": n_spec,
                "continuous_spec_device_steps": dev_steps,
                "continuous_spec_steps_per_token": round(dev_steps / n_spec, 3),
                "continuous_spec_tokens_per_s": round(n_spec / spec_dt, 1),
                "continuous_spec_accepted": (
                    spec_cb.stats.get("spec_accepted", 0) - acc0
                ),
            }
        finally:
            spec_cb.close()

        # what the same clients got BEFORE in-flight batching: sequential
        # single-row decodes through the one generation worker (streams and
        # mid-decode arrivals bypassed the window batcher entirely in r3)
        gen1 = jax.jit(
            lambda p, t: shim.family.generate(
                p, t, cfg, mesh=mesh, max_new_tokens=new_tokens
            )
        )
        np.asarray(gen1(params, jnp.asarray(prompts[-1])))  # compile
        t0 = time.monotonic()
        for i in range(clients):
            np.asarray(gen1(params, jnp.asarray(prompts[i])))
        seq_dt = time.monotonic() - t0
        seq_agg = clients * new_tokens / seq_dt
        return {
            "continuous_clients": clients,
            "continuous_chunk_size": chunk,
            "continuous_new_tokens": new_tokens,
            "continuous_agg_tokens_per_s": round(agg, 1),
            # vs the slope-derived batch-8 decode rate: that denominator
            # excludes ALL dispatch round-trips, which the admissions+chunks
            # schedule pays; the sequential ratio below is the deploy-shaped
            # comparison
            "continuous_vs_batch_decode": (
                round(agg / decode_tps, 3) if decode_tps else None
            ),
            "continuous_sequential_tokens_per_s": round(seq_agg, 1),
            "continuous_vs_sequential": round(agg / seq_agg, 3),
            "continuous_chunks": cb.stats["chunks"],
            **spec_out,
        }
    finally:
        cb.close()


def sharded_child_main(ckpt_dir: str) -> int:
    """``bench.py --sharded-child``: the forced-host multi-device half of
    ``measure_sharded_serving``, in a FRESH process so
    ``--xla_force_host_platform_device_count=8`` is set before jax
    initializes (the parent's backend is already up with its own device
    count). Boots the same checkpoint twice — a dp=1 single-device server
    and a dp=2,tp=2 four-device server — runs the continuous engine under
    concurrent clients on each, and prints one JSON line of aggregate
    rates plus the dp=1 engine-vs-legacy byte-equality verdict."""
    import threading as _t
    from concurrent.futures import ThreadPoolExecutor

    from modelx_tpu.dl.continuous import ContinuousBatcher
    from modelx_tpu.dl.serve import ModelServer

    clients, new_tokens = 4, 64
    rng = np.random.RandomState(7)
    out: dict = {}
    for tag, spec in (("dp1", "dp=1"), ("mesh", "dp=2,tp=2")):
        srv = ModelServer(ckpt_dir, mesh_spec=spec, dtype="float32",
                          max_seq_len=256)
        srv.load()
        cb = ContinuousBatcher(srv, max_slots=4, chunk_size=16, max_len=256)
        try:
            prompts = [
                rng.randint(1, srv.cfg.vocab_size, (1, 32)).astype(np.int32)
                for _ in range(clients)
            ]
            # warm: single + batched admission programs, then one repeat
            cb.generate(prompts[0], max_new_tokens=8)
            cb.generate(np.concatenate([prompts[0], prompts[0]]),
                        max_new_tokens=8)
            cb.generate(prompts[0], max_new_tokens=8)
            if tag == "dp1":
                # the byte-equality acceptance: the mesh-aware engine on a
                # single-device mesh must reproduce the legacy serving
                # path's tokens exactly (greedy AND sampled)
                toks = prompts[0][:, :16]
                greedy_eq = np.array_equal(
                    cb.generate(toks, max_new_tokens=12),
                    srv.generate(toks, max_new_tokens=12))
                sampled_eq = np.array_equal(
                    cb.generate(toks, max_new_tokens=12, temperature=0.8,
                                top_k=12, seed=7),
                    srv.generate(toks, max_new_tokens=12, temperature=0.8,
                                 top_k=12, seed=7))
                out["sharded_dp1_byte_equal"] = bool(greedy_eq and sampled_eq)
            start = _t.Barrier(clients)

            def client(i: int) -> int:
                start.wait()
                got = cb.generate(prompts[i], max_new_tokens=new_tokens)
                return got.shape[1] - prompts[i].shape[1]

            t0 = time.monotonic()
            with ThreadPoolExecutor(clients) as pool:
                totals = list(pool.map(client, range(clients)))
            dt = time.monotonic() - t0
            snap = cb.snapshot()
            out[f"{tag}_tokens_per_s"] = round(sum(totals) / dt, 1)
            out[f"{tag}_mesh"] = snap["mesh"]
            out[f"{tag}_devices"] = snap["mesh_devices"]
        finally:
            cb.close()
    print(json.dumps(out))
    return 0


def measure_sharded_serving(ckpt_dir: str, env=None,
                            timeout_s: float = 900.0) -> dict:
    """Tensor-parallel continuous decode on a real (forced-host) multi-
    device mesh — the ISSUE 16 acceptance leg. A child process pins
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE jax
    imports, serves one checkpoint on dp=1 and on dp=2,tp=2, and this
    parent reports the aggregate rates, the per-device throughput ratio
    (tp devices all work on every token, so the mesh aggregate IS the
    per-device rate; pass >= 0.7x the single-device baseline), and the
    dp=1 byte-equality verdict."""
    child_env = dict(env or os.environ)
    child_env["JAX_PLATFORMS"] = "cpu"
    flags = child_env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        child_env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--sharded-child",
         ckpt_dir],
        capture_output=True, text=True, env=child_env, timeout=timeout_s)
    if p.returncode != 0:
        raise RuntimeError(f"sharded child failed: {p.stderr[-2000:]}")
    child = json.loads(p.stdout.strip().splitlines()[-1])
    dp1 = child.get("dp1_tokens_per_s") or 0.0
    mesh_tps = child.get("mesh_tokens_per_s") or 0.0
    return {
        "sharded_mesh": child.get("mesh_mesh"),
        "sharded_devices": child.get("mesh_devices"),
        "sharded_tokens_per_s": mesh_tps,
        "sharded_dp1_tokens_per_s": dp1,
        "sharded_per_device_ratio": (
            round(mesh_tps / dp1, 3) if dp1 else None
        ),
        "sharded_dp1_byte_equal": child.get("sharded_dp1_byte_equal"),
    }


def _sampling_microbench(rows: int, vocab: int, reps: int = 40) -> dict:
    """Per-step sampling cost at the engine's [rows, vocab] logits shape:
    the fused top-k prefix path (``sampling_ms_*``) vs the same filters
    forced through the full-vocab sort (``sampling_sort_ms_p50``,
    ``k_cap=None``) — the direct price ISSUE 17's tentpole removes from
    every sampled decode step."""
    import jax
    import jax.numpy as jnp

    from modelx_tpu.ops import sampling as sampling_ops

    key = jax.random.PRNGKey(0)
    temp = jnp.full((rows,), 0.8, jnp.float32)
    tk = jnp.full((rows,), 40, jnp.int32)
    tp = jnp.full((rows,), 0.95, jnp.float32)
    seeds = jnp.arange(rows, dtype=jnp.int32)

    def _fused(lg, step):
        return sampling_ops.sample(lg, key, temp, tk, tp,
                                   seeds=seeds, step=step)

    def _sorted(lg, step):
        filt = sampling_ops.scale_and_filter_reference(
            lg, temp, tk, tp, k_cap=None)
        steps = jnp.broadcast_to(jnp.asarray(step, jnp.int32), (rows,))
        keys = jax.vmap(lambda s, st: jax.random.fold_in(
            jax.random.fold_in(key, s), st))(seeds, steps)
        return jax.vmap(jax.random.categorical)(keys, filt)

    fused = jax.jit(_fused)
    sortp = jax.jit(_sorted)
    logits = [
        jax.random.normal(jax.random.fold_in(key, i), (rows, vocab),
                          jnp.float32) * 3.0
        for i in range(4)
    ]

    def timed(fn) -> list[float]:
        jax.block_until_ready(fn(logits[0], 0))  # compile outside the clock
        ms = []
        for i in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(logits[i % len(logits)], i))
            ms.append((time.perf_counter() - t0) * 1e3)
        return ms

    f_ms = np.asarray(timed(fused))
    s_ms = np.asarray(timed(sortp))
    return {
        "sampling_ms_p50": round(float(np.percentile(f_ms, 50)), 4),
        "sampling_ms_p99": round(float(np.percentile(f_ms, 99)), 4),
        "sampling_sort_ms_p50": round(float(np.percentile(s_ms, 50)), 4),
    }


def measure_decode_pipelined(params, mesh, decode_tps: float | None, *,
                             clients: int = 8, chunk: int = 16,
                             new_tokens: int = 192, prompt_len: int = 64,
                             max_len: int = 512) -> dict:
    """Pipelined-dispatch leg (ISSUE 7): identical 8-client decode traffic
    against two engines — SERIAL boundaries (pipeline_depth=1,
    dispatch_depth=1: dispatch, blocking sync, plan, repeat — the r05
    shape whose ~66 ms/chunk host overhead halved throughput) vs
    DISPATCH-AHEAD (pipeline_depth=2, dispatch_depth auto: depth-D
    programs + async token readback + boundary-prep overlap).

    ``decode_call_overhead_ms_{serial,pipelined}`` is the per-chunk
    boundary overhead: (wall - tokens/decode_tps) / chunk_equivalents —
    the slope-derived batch decode rate prices the pure device time, what
    is left is dispatch + host work per chunk. A depth-D program spreads
    one dispatch across D chunks, so the pipelined number should drop
    ~Dx (acceptance: >= 3x on the bench rig). ``dispatches_serial`` /
    ``dispatches_pipelined`` carry the structural evidence (fewer device
    calls for the same tokens) independent of timing noise.

    ISSUE 17 adds a SAMPLED leg: the same dispatch-ahead engine under a
    mixed client population (every other client samples at temperature
    0.8 / top_k 40 / top_p 0.95 — cuts that resolve inside the fused
    sampler's K_CAP prefix). Before the fused path, sampled rows paid a
    full-vocab sort per token; ``sampled_vs_greedy_decode_ratio`` is the
    acceptance signal (>= 0.9: sampling within 10% of greedy), with
    ``sampling_ms_p50/p99`` (fused) vs ``sampling_sort_ms_p50`` (forced
    sort path) microbenched at the engine's [clients, vocab] shape, and
    ``pad_fraction`` read off the engine's dispatch accounting."""
    import threading as _t
    from concurrent.futures import ThreadPoolExecutor

    from modelx_tpu.dl.continuous import ContinuousBatcher

    shim = _engine_shim(params, mesh, max_len)
    cfg = shim.cfg
    rng = np.random.RandomState(17)
    prompts = [
        rng.randint(1, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
        for _ in range(clients + 1)
    ]
    # the sampled leg's non-greedy client kwargs: cuts inside K_CAP, a
    # per-client seed so streams are independent
    samp_kw = {"temperature": 0.8, "top_k": 40, "top_p": 0.95}

    def run(pipeline_depth: int, dispatch_depth: int,
            sampled: bool = False) -> dict:
        cb = ContinuousBatcher(shim, max_slots=clients, chunk_size=chunk,
                               max_len=max_len, burst_window_ms=5.0,
                               pipeline_depth=pipeline_depth,
                               dispatch_depth=dispatch_depth)
        try:
            # warm every compiled shape the measured phase uses, so no
            # program compiles inside the timed run: the single admit,
            # EVERY pow2 burst-admit width (the barrier start below can
            # land any subset of clients in one admission group, and
            # groups pad to pow2), the per-chunk program, and (auto
            # depth) EVERY power-of-two depth rung. A lone decode's first
            # pipeline_depth dispatches stay depth-1 (first token still
            # owed), then the deep pick sees rem = budget - depth*chunk —
            # budget (pipe_depth + d) * chunk puts rung d exactly there.
            cb.generate(prompts[-1], max_new_tokens=8)
            w = 1
            while w < clients:
                w *= 2
                cb.generate(np.concatenate([prompts[-1]] * min(w, clients)),
                            max_new_tokens=8)
            d = 2
            while d <= (dispatch_depth or cb.AUTO_DISPATCH_DEPTH):
                cb.generate(prompts[-1],
                            max_new_tokens=(pipeline_depth + d) * chunk)
                if sampled:
                    # the filtered chunk-program variant compiles per
                    # depth rung too — warm it so the measured phase's
                    # mixed batches never compile
                    cb.generate(prompts[-1], seed=9,
                                max_new_tokens=(pipeline_depth + d) * chunk,
                                **samp_kw)
                d *= 2
            if sampled:
                cb.generate(prompts[-1], max_new_tokens=8, seed=9, **samp_kw)
            # the warmup's compiles landed in the boundary histogram and
            # the max/peak counters: reset so the reported observability
            # numbers describe the MEASURED phase only
            cb._boundary_host_ms.clear()
            cb.stats["host_syncs_per_boundary"] = 0
            cb.stats["tokens_in_flight_peak"] = 0
            cb.stats["dispatch_depth_max"] = 1
            cb.stats["sync_lag_chunks_max"] = 0
            d0, c0 = cb.stats["dispatches"], cb.stats["chunks"]
            start = _t.Barrier(clients)

            def client(i: int) -> int:
                start.wait()
                kw = dict(seed=100 + i, **samp_kw) if sampled and i % 2 else {}
                out = cb.generate(prompts[i], max_new_tokens=new_tokens, **kw)
                return out.shape[1] - prompts[i].shape[1]

            t0 = time.monotonic()
            with ThreadPoolExecutor(clients) as pool:
                totals = list(pool.map(client, range(clients)))
            wall = time.monotonic() - t0
            return {"wall": wall, "tokens": sum(totals),
                    "dispatches": cb.stats["dispatches"] - d0,
                    "chunks": cb.stats["chunks"] - c0,
                    "snap": cb.snapshot()}
        finally:
            cb.close()

    serial = run(1, 1)
    pipe = run(2, 0)
    samp = run(2, 0, sampled=True)

    def overhead_ms(rec: dict) -> float | None:
        if not decode_tps:
            return None
        device_s = rec["tokens"] / decode_tps
        return round(
            max(0.0, (rec["wall"] - device_s) / max(rec["chunks"], 1) * 1e3), 3
        )

    o_serial, o_pipe = overhead_ms(serial), overhead_ms(pipe)
    agg_pipe = pipe["tokens"] / pipe["wall"]
    agg_samp = samp["tokens"] / samp["wall"]
    out = {
        "pipelined_clients": clients,
        "pipelined_chunk_size": chunk,
        "pipelined_new_tokens": new_tokens,
        "dispatches_serial": serial["dispatches"],
        "dispatches_pipelined": pipe["dispatches"],
        "pipelined_dispatch_depth_max": pipe["snap"].get("dispatch_depth_max"),
        "decode_call_overhead_ms_serial": o_serial,
        "decode_call_overhead_ms_pipelined": o_pipe,
        "serial_agg_tokens_per_s": round(serial["tokens"] / serial["wall"], 1),
        "pipelined_agg_tokens_per_s": round(agg_pipe, 1),
        "continuous_vs_batch_decode_pipelined": (
            round(agg_pipe / decode_tps, 3) if decode_tps else None
        ),
        "boundary_host_ms_p50_serial": serial["snap"].get("boundary_host_ms_p50"),
        "boundary_host_ms_p50_pipelined": pipe["snap"].get("boundary_host_ms_p50"),
        "boundary_host_ms_p99_pipelined": pipe["snap"].get("boundary_host_ms_p99"),
        "pipelined_tokens_in_flight_peak": pipe["snap"].get("tokens_in_flight_peak"),
        "pipelined_host_syncs_per_boundary": pipe["snap"].get("host_syncs_per_boundary"),
        "pipelined_sync_lag_chunks_max": pipe["snap"].get("sync_lag_chunks_max"),
        # sampled leg (ISSUE 17): mixed greedy/sampled clients through the
        # fused on-device sampler — the ratio to the all-greedy run is the
        # acceptance signal (sampled rows used to pay a full-vocab sort)
        "sampled_agg_tokens_per_s": round(agg_samp, 1),
        "continuous_vs_batch_decode_sampled": (
            round(agg_samp / decode_tps, 3) if decode_tps else None
        ),
        "sampled_vs_greedy_decode_ratio": (
            round(agg_samp / agg_pipe, 3) if agg_pipe else None
        ),
        # padding tax, read off the engine's dispatch accounting (the
        # sampled run's snapshot — identical traffic shape to pipe)
        "pad_fraction": samp["snap"].get("pad_fraction"),
        "pages_swept_fraction": samp["snap"].get("pages_swept_fraction"),
    }
    out.update(_sampling_microbench(clients, int(cfg.vocab_size)))
    if o_serial is not None and o_pipe is not None:
        # o_pipe can legitimately clamp to 0.0 (pipelined wall under the
        # device-time estimate — the best possible outcome); floor + cap
        # so the >=3x acceptance evidence is present rather than silently
        # omitted exactly when the win is total
        out["decode_overhead_reduction"] = min(
            round(o_serial / max(o_pipe, 1e-3), 2), 999.0
        )
    return out


def measure_mixed_prefill(params, mesh, *, slots: int = 8, chunk: int = 32,
                          prefill_chunk: int = 128, decode_prompt: int = 128,
                          decode_new: int = 256, long_prompt: int = 704,
                          long_new: int = 64, max_len: int = 1024) -> dict:
    """Admission jitter under load (the chunked-prefill acceptance leg):
    saturate ``slots - 1`` decode rows, then admit a long prompt into the
    running batch and measure each decoding client's inter-token latency.
    Two scenarios on identical traffic: chunked prefill ON (pieces
    interleave with decode chunks) vs OFF (today's monolithic admission
    prefill, the baseline whose stall scales with prompt length).

    Reported: ``itl_p99_ms_mixed`` / ``itl_p99_ms_mixed_baseline`` (p99
    per-token gap over the admission window, chunked vs monolithic),
    ``itl_p99_ms_idle`` (the same engine's p99 with no admission in
    flight — the ≤ 2x acceptance denominator), and
    ``admission_stall_ms_max`` (the engine's own max decode-boundary gap,
    from its stats — no internals poking)."""
    from modelx_tpu.dl.continuous import ContinuousBatcher

    shim = _engine_shim(params, mesh, max_len)
    cfg = shim.cfg
    rng = np.random.RandomState(23)
    n_dec = max(1, slots - 1)
    dec_prompts = [
        rng.randint(1, cfg.vocab_size, decode_prompt).astype(np.int32).tolist()
        for _ in range(n_dec)
    ]
    long_ids = rng.randint(1, cfg.vocab_size, long_prompt).astype(np.int32).tolist()

    def scenario(pc_tokens: int) -> dict:
        cb = ContinuousBatcher(shim, max_slots=slots, chunk_size=chunk,
                               max_len=max_len, burst_window_ms=5.0,
                               prefill_chunk=pc_tokens)
        try:
            # warm every compiled shape the measured phase touches (the
            # n_dec-row burst admit, chunk, the long prompt's piece
            # buckets / monolithic bucket) so the ITL numbers aren't
            # compile stalls
            cb.generate(np.asarray(dec_prompts, np.int32), max_new_tokens=8)
            cb.generate(np.asarray([long_ids], np.int32), max_new_tokens=8)
            cb.stats["stall_ms_max"] = 0.0
            cb.stats["chunks"] = 0
            cb.stats["prefill_pieces"] = 0  # warm-up pieces aren't the leg's

            arrivals: list[list[tuple[float, int]]] = [[] for _ in range(n_dec)]

            def client(i: int, ticket) -> None:
                while True:
                    item = ticket.out.get()
                    if not isinstance(item, np.ndarray):
                        if isinstance(item, BaseException):
                            raise item
                        return
                    arrivals[i].append((time.monotonic(), int(item.size)))

            from concurrent.futures import ThreadPoolExecutor

            tickets = cb.submit_many([
                (ids, decode_new, {}) for ids in dec_prompts
            ])
            # executor, not bare threads: a broken engine must fail the
            # leg loudly (futures re-raise), not silently truncate the
            # arrival records the p99s are computed from
            pool = ThreadPoolExecutor(n_dec)
            futs = [pool.submit(client, i, t) for i, t in enumerate(tickets)]
            # let the batch reach steady-state boundary cadence first (the
            # pre-admission gaps ARE the idle-ITL baseline — a couple of
            # boundaries' worth of clustered warm-in arrivals would make
            # it degenerate), then admit into the running batch
            deadline = time.monotonic() + 120
            while cb.stats["chunks"] < 6 and time.monotonic() < deadline:
                time.sleep(0.002)
            t_admit = time.monotonic()
            long_ticket = cb.submit(long_ids, long_new, {})
            long_first = None
            long_toks = 0
            while True:
                item = long_ticket.out.get()
                if not isinstance(item, np.ndarray):
                    if isinstance(item, BaseException):
                        raise item
                    break
                if long_first is None:
                    long_first = time.monotonic()
                long_toks += int(item.size)
            for fut in futs:
                fut.result(timeout=300)
            pool.shutdown()

            idle, mixed = [], []
            window_end = long_first if long_first is not None else time.monotonic()
            for rec in arrivals:
                for gi, ((t0, _n0), (t1, n1)) in enumerate(zip(rec, rec[1:])):
                    per_tok = (t1 - t0) * 1e3 / max(1, n1)
                    # a gap OVERLAPPING the admission window is admission
                    # jitter; the idle baseline is STRICTLY pre-admission
                    # gaps (post-window gaps come from the now-larger
                    # batch and would flatter the <=2x acceptance ratio),
                    # minus each client's first two warm-in gaps, whose
                    # clustered burst-admission deliveries aren't cadence
                    if t1 >= t_admit and t0 <= window_end:
                        mixed.append(per_tok)
                    elif t1 < t_admit and gi >= 2:
                        idle.append(per_tok)
            out = {
                "stall_ms_max": cb.stats["stall_ms_max"],
                "prefill_pieces": cb.stats["prefill_pieces"],
                "long_tokens": long_toks,
                "ttft_long_ms": round((long_first - t_admit) * 1e3, 1)
                if long_first else None,
            }
            for key, samples in (("itl_p99_ms_idle", idle), ("itl_p99_ms_mixed", mixed)):
                out[key] = round(float(np.percentile(samples, 99)), 3) if samples else None
            return out
        finally:
            cb.close()

    chunked = scenario(prefill_chunk)
    mono = scenario(0)
    out = {
        "mixed_slots": slots,
        "mixed_chunk_size": chunk,
        "mixed_prefill_chunk": prefill_chunk,
        "mixed_long_prompt": long_prompt,
        "itl_p99_ms_mixed": chunked["itl_p99_ms_mixed"],
        "itl_p99_ms_idle": chunked["itl_p99_ms_idle"],
        "itl_p99_ms_mixed_baseline": mono["itl_p99_ms_mixed"],
        "admission_stall_ms_max": chunked["stall_ms_max"],
        "admission_stall_ms_max_baseline": mono["stall_ms_max"],
        "mixed_prefill_pieces": chunked["prefill_pieces"],
        "mixed_ttft_long_ms": chunked["ttft_long_ms"],
        "mixed_ttft_long_ms_baseline": mono["ttft_long_ms"],
    }
    if (chunked["itl_p99_ms_mixed"] and chunked["itl_p99_ms_idle"]
            and chunked["itl_p99_ms_idle"] > 0.05):
        # the acceptance dial: admission must raise ITL p99 by <= 2x idle
        # (guarded against a degenerate near-zero idle capture)
        out["mixed_jitter_ratio"] = round(
            chunked["itl_p99_ms_mixed"] / chunked["itl_p99_ms_idle"], 3
        )
    if chunked["itl_p99_ms_mixed"] and mono["itl_p99_ms_mixed"]:
        out["mixed_vs_monolithic"] = round(
            mono["itl_p99_ms_mixed"] / chunked["itl_p99_ms_mixed"], 3
        )
    return out


def measure_overload(params, mesh, *, slots: int = 2, chunk: int = 8,
                     queue_depth: int = 4, clients: int = 16,
                     prompt: int = 16, new_tokens: int = 32,
                     max_len: int = 256) -> dict:
    """Overload + self-healing leg (ISSUE 3 acceptance): saturate a
    bounded-admission engine and count the sheds, expire a queued request
    past its deadline, then crash the engine's dispatch with a
    deterministic FaultPlan and time the supervisor's recovery.

    Reported: ``shed_429_count`` (submits rejected at --max-queue-depth),
    ``deadline_504_count`` (requests expired at a chunk boundary),
    ``recovery_ms`` (injected crash -> first successful generate on the
    restarted engine), and ``overload_engine_restarts``."""
    from concurrent.futures import ThreadPoolExecutor

    from modelx_tpu.dl.continuous import ContinuousBatcher
    from modelx_tpu.dl.serving_errors import (
        DeadlineExceededError, EngineBrokenError, QueueFullError,
    )
    from modelx_tpu.testing import faults

    shim = _engine_shim(params, mesh, max_len)
    cfg = shim.cfg
    rng = np.random.RandomState(31)
    prompts = [
        rng.randint(1, cfg.vocab_size, (1, prompt)).astype(np.int32)
        for _ in range(clients)
    ]
    cb = ContinuousBatcher(shim, max_slots=slots, chunk_size=chunk,
                           max_len=max_len, max_queue_depth=queue_depth,
                           restart_backoff_s=0.05)
    try:
        cb.generate(prompts[0], max_new_tokens=8)  # warm the compiled set

        # -- shed leg: saturating concurrent traffic against the bound ----
        shed = ok = 0
        lock = threading.Lock()

        def client(i: int) -> None:
            nonlocal shed, ok
            try:
                cb.generate(prompts[i], max_new_tokens=new_tokens)
                with lock:
                    ok += 1
            except QueueFullError:
                with lock:
                    shed += 1

        with ThreadPoolExecutor(clients) as pool:
            list(pool.map(client, range(clients)))

        # -- deadline leg: a queued request expired at the boundary -------
        deadline_504 = 0
        blocker = cb.submit(prompts[0][0].tolist(), 64, {})
        blocker.out.get(timeout=60)  # admitted: the slot array is busy
        fillers = [
            cb.submit(prompts[1 + i % (clients - 1)][0].tolist(), 64, {})
            for i in range(slots - 1)
        ]
        waiter = cb.submit(prompts[2][0].tolist(), 8, {})
        waiter.deadline = 0.0  # already past: expires at the next boundary
        item = waiter.out.get(timeout=60)
        if isinstance(item, DeadlineExceededError):
            deadline_504 += 1
        blocker.cancel()
        for f in fillers:
            f.cancel()

        # -- crash/recovery leg: injected dispatch fault ------------------
        plan = faults.FaultPlan(seed=7)
        plan.add("engine.dispatch", errors_at=[0],
                 error=RuntimeError("bench-injected crash"))
        cb._chunk = faults.wrap_dispatch(cb._chunk, plan)
        t0 = time.monotonic()
        try:
            cb.generate(prompts[3], max_new_tokens=8)
        except EngineBrokenError:
            pass
        recovery_ms = None
        give_up = time.monotonic() + 60
        while time.monotonic() < give_up:
            try:
                cb.generate(prompts[3], max_new_tokens=8)
                recovery_ms = round((time.monotonic() - t0) * 1e3, 1)
                break
            except EngineBrokenError:
                time.sleep(0.01)
        snap = cb.snapshot()
        return {
            "overload_clients": clients,
            "overload_queue_depth": queue_depth,
            "shed_429_count": shed,
            "overload_served": ok,
            "deadline_504_count": deadline_504,
            "recovery_ms": recovery_ms,
            "overload_engine_restarts": snap["engine_restarts"],
        }
    finally:
        cb.close()


def measure_model_swap(base: str, workdir: str, *, target_bytes: int = 16 << 20,
                       hidden: int = 512, inter: int = 1408, vocab: int = 8192,
                       prompt_len: int = 8, new_tokens: int = 4) -> dict:
    """Model lifecycle swap leg (ISSUE 5): with live traffic to a third
    model C, unload A and load B through the pool — cold (empty blob
    cache, bytes come from the registry) vs blob-cache-warm (B's blobs
    already on the node from the cold swap, zero network reads).

    Reported: ``ttft_swap_cold_ms`` / ``ttft_swap_warm_ms`` (DELETE of the
    old model -> first token out of the newly loaded one),
    ``swap_traffic_errors`` (C requests that failed during either swap —
    the uninterrupted-traffic contract, must be 0), and the pull path's
    ``swap_cache_hits``."""
    import threading as _threading

    from modelx_tpu.dl.blob_cache import BlobCache
    from modelx_tpu.dl.serve import ModelServer, ServerSet

    root = os.path.join(workdir, "swap")
    dirs: dict[str, str] = {}
    for name in ("a", "b", "c"):
        d = os.path.join(root, name)
        os.makedirs(d, exist_ok=True)
        build_checkpoint(os.path.join(d, "model.safetensors"), target_bytes,
                         hidden=hidden, inter=inter, vocab=vocab)
        push_checkpoint(base, f"library/swap-{name}",
                        os.path.join(d, "model.safetensors"))
        dirs[name] = d
    cache = BlobCache(os.path.join(root, "blobcache"))
    servers = {n: ModelServer(dirs[n], name=n) for n in ("a", "c")}
    sset = ServerSet(servers, default="c", allow_admin_load=True,
                     staging_root=os.path.join(root, "staging"))
    sset.pool.blob_cache = cache
    sset.load_all()

    stop = _threading.Event()
    counts = {"served": 0, "errors": 0}
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, vocab, (1, prompt_len)).astype(np.int32)

    def traffic() -> None:
        while not stop.is_set():
            try:
                sset.servers["c"].generate(prompt, max_new_tokens=new_tokens)
                counts["served"] += 1
            except Exception:
                counts["errors"] += 1

    t = _threading.Thread(target=traffic, daemon=True)
    t.start()

    def swap(old: str, new: str) -> float:
        t0 = time.monotonic()
        sset.pool.request_unload(old, wait=True)
        sset.pool.request_load(new, ref=f"{base}/library/swap-{new}@v1",
                               wait=True)
        state = sset.pool.states()[new]
        if state["state"] != "READY":
            raise RuntimeError(f"swap load of {new} landed {state}")
        sset.servers[new].generate(prompt, max_new_tokens=1)  # first token
        return (time.monotonic() - t0) * 1e3

    try:
        cold_ms = swap("a", "b")       # empty cache: bytes from the registry
        warm_ms = swap("b", "b")       # B's blobs admitted by the cold pull
    finally:
        stop.set()
        t.join(timeout=30)
    return {
        "ttft_swap_cold_ms": round(cold_ms, 1),
        "ttft_swap_warm_ms": round(warm_ms, 1),
        "swap_traffic_served": counts["served"],
        "swap_traffic_errors": counts["errors"],
        "swap_cache_hits": cache.stats["hits"],
    }


def measure_tier_swap(base: str, workdir: str, *, target_bytes: int = 16 << 20,
                      hidden: int = 512, inter: int = 1408, vocab: int = 8192,
                      prompt_len: int = 8, new_tokens: int = 4) -> dict:
    """Tiered-state swap leg (ISSUE 18): with live traffic to a third
    model C, swap model B in three ways — cold (empty blob cache: registry
    pull + safetensors parse + placement), host-tier promotion (B's
    params demoted to host RAM at unload, re-load is device_put only),
    and disk-tier promotion (host entry spooled to the decoded-tensor
    spool first, re-load is np.load + device_put).

    Reported: ``ttft_swap_cold_ms`` / ``ttft_swap_host_ms`` /
    ``ttft_swap_disk_ms`` (each DELETE old -> first token out of the new
    load), ``tier_traffic_errors`` (C requests failed during any swap —
    the uninterrupted-traffic contract, must be 0), and the tier store's
    hit/spill counters. The ServerlessLLM-style bar: host promotion
    beats the cold path by at least 2x."""
    import threading as _threading

    from modelx_tpu.dl.blob_cache import BlobCache
    from modelx_tpu.dl.serve import ModelServer, ServerSet

    root = os.path.join(workdir, "tierswap")
    dirs: dict[str, str] = {}
    for i, name in enumerate(("a", "b", "c")):
        d = os.path.join(root, name)
        os.makedirs(d, exist_ok=True)
        # distinct seeds: the tier key is CONTENT identity (manifest
        # digests), so byte-identical checkpoints would turn the cold leg
        # into a cross-model tier hit and understate ttft_swap_cold_ms
        build_checkpoint(os.path.join(d, "model.safetensors"), target_bytes,
                         hidden=hidden, inter=inter, vocab=vocab, seed=i + 1)
        push_checkpoint(base, f"library/tier-{name}",
                        os.path.join(d, "model.safetensors"))
        dirs[name] = d
    cache = BlobCache(os.path.join(root, "blobcache"))
    servers = {n: ModelServer(dirs[n], name=n) for n in ("a", "c")}
    sset = ServerSet(servers, default="c", allow_admin_load=True,
                     staging_root=os.path.join(root, "staging"),
                     host_state_budget_bytes=1 << 30,
                     disk_state_budget_bytes=1 << 30,
                     state_spool_dir=os.path.join(root, "spool"))
    sset.pool.blob_cache = cache
    sset.load_all()

    stop = _threading.Event()
    counts = {"served": 0, "errors": 0}
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, vocab, (1, prompt_len)).astype(np.int32)

    def traffic() -> None:
        while not stop.is_set():
            try:
                sset.servers["c"].generate(prompt, max_new_tokens=new_tokens)
                counts["served"] += 1
            except Exception:
                counts["errors"] += 1

    t = _threading.Thread(target=traffic, daemon=True)
    t.start()

    def swap(old: str, new: str) -> float:
        t0 = time.monotonic()
        sset.pool.request_unload(old, wait=True)
        sset.pool.request_load(new, ref=f"{base}/library/tier-{new}@v1",
                               wait=True)
        state = sset.pool.states()[new]
        if state["state"] != "READY":
            raise RuntimeError(f"tier swap load of {new} landed {state}")
        sset.servers[new].generate(prompt, max_new_tokens=1)  # first token
        return (time.monotonic() - t0) * 1e3

    tiers = sset.pool.tiers
    try:
        cold_ms = swap("a", "b")     # B never demoted: full pull + parse
        host_ms = swap("b", "b")     # unload demotes to host; load promotes
        # keep-on-promote left B's entry in the host tier; spool it so the
        # next promotion reads the disk tier
        spilled = tiers.spill_host()
        disk_ms = swap("b", "b")
    finally:
        stop.set()
        t.join(timeout=30)
    snap = tiers.snapshot()
    return {
        "ttft_swap_cold_ms": round(cold_ms, 1),
        "ttft_swap_host_ms": round(host_ms, 1),
        "ttft_swap_disk_ms": round(disk_ms, 1),
        "tier_traffic_served": counts["served"],
        "tier_traffic_errors": counts["errors"],
        "tier_host_hits": snap["host"]["hits"],
        "tier_disk_hits": snap["disk"]["hits"],
        "tier_spills": snap["spills"],
        "tier_host_spilled": spilled,
    }


def measure_registry_outage(workdir: str, *, target_bytes: int = 16 << 20,
                            hidden: int = 512, inter: int = 1408,
                            vocab: int = 8192, prompt_len: int = 8,
                            new_tokens: int = 4, clients: int = 4) -> dict:
    """Registry-outage leg (ISSUE 19): kill the registry under live
    traffic and swap a model in OFFLINE from the pinned manifest + blob
    cache, then restart the registry and watch the publish outbox drain.

    Runs against its OWN in-process registry (the shared bench registry
    is a subprocess the leg could not brown out), killed mid-leg by
    :class:`RegistryKillSwitch` and restarted on the same port over the
    same store. Reported: ``outage_dropped_requests`` (data-path failures
    on model A across the whole outage — the acceptance bar is 0),
    ``swap_offline_ttft_ms`` (admin load of B with the registry dead ->
    first token), ``outage_swap_source`` (must be ``cache``: the ladder,
    not a lucky re-pull), and the outbox drain counters after restart."""
    import threading as _threading

    from modelx_tpu.dl import manifest_cache, program_store
    from modelx_tpu.dl.blob_cache import BlobCache
    from modelx_tpu.dl.serve import ModelServer, ServerSet
    from modelx_tpu.registry.fs import MemoryFSProvider
    from modelx_tpu.registry.server import Options, RegistryServer, free_port
    from modelx_tpu.registry.store_fs import FSRegistryStore
    from modelx_tpu.testing.faults import RegistryKillSwitch

    root = os.path.join(workdir, "outage")
    port = free_port()
    store = FSRegistryStore(MemoryFSProvider())
    srv = RegistryServer(Options(listen=f"127.0.0.1:{port}"), store=store)
    base = srv.serve_background()

    dirs: dict[str, str] = {}
    for i, name in enumerate(("a", "b")):
        d = os.path.join(root, name)
        os.makedirs(d, exist_ok=True)
        build_checkpoint(os.path.join(d, "model.safetensors"), target_bytes,
                         hidden=hidden, inter=inter, vocab=vocab, seed=i + 1)
        push_checkpoint(base, f"library/outage-{name}",
                        os.path.join(d, "model.safetensors"))
        dirs[name] = d

    # a real (tiny) program bundle for the outbox: publish parses bundle
    # meta before it ever talks to the registry, so the payload must be
    # wire-true even though its contents are fabricated
    aot_dir = os.path.join(root, "aot-cache")
    os.makedirs(aot_dir, exist_ok=True)
    with open(os.path.join(aot_dir, "aot-" + "ab" * 8 + ".bin"), "wb") as f:
        f.write(b"export-one")
    bundle = program_store.build_bundle(aot_dir)

    manifest_cache.configure_default(os.path.join(root, "manifest-cache"))
    manifest_cache.health().reset()
    sset = ServerSet({"a": ModelServer(dirs["a"], name="a")}, default="a",
                     allow_admin_load=True,
                     staging_root=os.path.join(root, "staging"))
    sset.pool.blob_cache = BlobCache(os.path.join(root, "blobcache"))
    sset.pool.attach_outbox(os.path.join(root, "outbox"), backoff_s=0.2)
    sset.load_all()
    switch = RegistryKillSwitch(srv)

    stop = _threading.Event()
    counts = {"served": 0, "errors": 0}
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, vocab, (1, prompt_len)).astype(np.int32)
    bref = f"{base}/library/outage-b@v1"

    def traffic() -> None:
        while not stop.is_set():
            try:
                sset.servers["a"].generate(prompt, max_new_tokens=new_tokens)
                counts["served"] += 1
            except Exception:
                counts["errors"] += 1

    srv2 = None
    threads: list = []
    try:
        # warm the ladder: pull B through the caches once, then drop it
        sset.pool.request_load("b", ref=bref, wait=True)
        if sset.pool.states()["b"]["state"] != "READY":
            raise RuntimeError("outage warm pull of b failed")
        sset.pool.request_unload("b", wait=True)

        threads = [_threading.Thread(target=traffic, daemon=True)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30.0
        while counts["served"] < clients and time.monotonic() < deadline:
            time.sleep(0.02)
        if counts["served"] < clients:
            raise RuntimeError("outage traffic never established")

        # kill the control plane mid-traffic; a publish lands in the
        # spool and fails against the dead registry
        switch.kill()
        if not sset.pool.outbox.enqueue("programs", bref, bundle):
            raise RuntimeError("outbox refused the outage-era publish")
        sset.pool.outbox_drainer.kick()

        # offline swap-in: admin load of B with the registry dead
        t0 = time.monotonic()
        sset.pool.request_load("b", ref=bref, wait=True)
        state = sset.pool.states()["b"]
        if state["state"] != "READY":
            raise RuntimeError(f"offline swap of b landed {state}")
        sset.servers["b"].generate(prompt, max_new_tokens=1)  # first token
        swap_ms = (time.monotonic() - t0) * 1e3
        swap_source = state.get("load_source", "")
        cp_during = manifest_cache.health().state

        # restart the registry (same port, same store); the outbox drains
        srv2 = RegistryServer(Options(listen=f"127.0.0.1:{port}"),
                              store=store)
        srv2.serve_background()
        sset.pool.outbox_drainer.kick()
        deadline = time.monotonic() + 60.0
        while sset.pool.outbox.depth() and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        switch.kill()
        sset.pool.stop_outbox()
        if srv2 is not None:
            srv2.shutdown()
        # the leg marched the process-wide control-plane health through
        # offline; don't leak that state into later in-process legs
        manifest_cache.health().reset()
        with manifest_cache._default_lock:
            manifest_cache._default = None
            manifest_cache._default_configured = False
    return {
        "outage_dropped_requests": counts["errors"],
        "outage_traffic_served": counts["served"],
        "swap_offline_ttft_ms": round(swap_ms, 1),
        "outage_swap_source": swap_source,
        "outage_control_plane_state": cp_during,
        "outbox_depth_after_restart": sset.pool.outbox.depth(),
        "outbox_drained_total": sset.pool.outbox.stats["drained_total"],
        "outbox_publish_failures": sset.pool.outbox.stats[
            "publish_failures_total"],
    }


def measure_fleet(model_dir: str, *, pods: int = 3, clients: int = 4,
                  requests_per_client: int = 5, conversations: int = 6,
                  turns: int = 8, new_tokens: int = 8,
                  max_seq_len: int = 256) -> dict:
    """Fleet front-door leg (ISSUE 8): N in-process pods behind the
    router vs ONE pod addressed directly, identical client traffic.

    The pods are HTTP fronts around ONE loaded model (this host has one
    accelerator, so compute does not multiply with pod count);
    ``fleet_throughput_scaling`` therefore reads as the ROUTER TAX on this
    rig — ~1.0 means the front door's placement + proxy layer costs
    nothing observable at this load; a real fleet's scaling multiplies
    device counts on top. Also driven: repeated-prefix conversations for
    ``sticky_hit_ratio`` and a seeded pod kill under traffic for
    ``failover_recovery_ms`` (kill -> first successful routed response)
    with ``fleet_dropped_requests`` asserting the zero-drop contract."""
    import requests as _requests

    from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
    from modelx_tpu.registry.server import free_port
    from modelx_tpu.router.registry import PodRegistry
    from modelx_tpu.router.server import FleetRouter, route_serve
    from modelx_tpu.testing.faults import PodKillSwitch

    server = ModelServer(model_dir, name="default", max_seq_len=max_seq_len)
    server.load()
    vocab = int(getattr(server.cfg, "vocab_size", 0) or 256)

    pod_set = []
    for _ in range(pods):
        sset = ServerSet({"default": server})
        sset.pool.mark_ready("default")
        httpd = serve(sset, listen=f"127.0.0.1:{free_port()}")
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        pod_set.append({"httpd": httpd, "url": url,
                        "kill": PodKillSwitch(httpd)})
    registry = PodRegistry([p["url"] for p in pod_set], poll_interval_s=0.5)
    router = FleetRouter(registry, request_timeout_s=60.0)
    router.start()
    rhttpd = route_serve(router, listen=f"127.0.0.1:{free_port()}")
    rbase = f"http://127.0.0.1:{rhttpd.server_address[1]}"

    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, vocab, (8,)).tolist()
               for _ in range(clients)]

    def drive(base_url: str) -> tuple[int, int, float]:
        """clients x requests_per_client generates; (ok, errors, seconds)."""
        counts = {"ok": 0, "err": 0}
        lock = threading.Lock()

        def client(prompt) -> None:
            sess = _requests.Session()
            for _ in range(requests_per_client):
                try:
                    r = sess.post(base_url + "/v1/generate",
                                  json={"tokens": [prompt],
                                        "max_new_tokens": new_tokens},
                                  timeout=60)
                    ok = r.status_code == 200
                except _requests.RequestException:
                    ok = False
                with lock:
                    counts["ok" if ok else "err"] += 1

        threads = [threading.Thread(target=client, args=(p,), daemon=True)
                   for p in prompts]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return counts["ok"], counts["err"], time.monotonic() - t0

    out: dict = {"fleet_pods": pods}
    try:
        # warm every compiled shape once so both legs measure serving, not
        # compilation (the same prompt shapes repeat throughout)
        drive(pod_set[0]["url"])
        ok_d, err_d, dt_d = drive(pod_set[0]["url"])
        ok_r, err_r, dt_r = drive(rbase)
        tps_direct = ok_d * new_tokens / max(dt_d, 1e-9)
        tps_routed = ok_r * new_tokens / max(dt_r, 1e-9)
        out["fleet_tokens_per_s_direct"] = round(tps_direct, 1)
        out["fleet_tokens_per_s_routed"] = round(tps_routed, 1)
        out["fleet_throughput_scaling"] = (
            round(tps_routed / tps_direct, 3) if tps_direct > 0 else None
        )
        out["fleet_traffic_errors"] = err_d + err_r

        # repeated-prefix conversations -> sticky hit ratio
        convs = [rng.randint(1, vocab, (8,)).tolist()
                 for _ in range(conversations)]
        before = router.sticky.stats()
        sess = _requests.Session()
        for _turn in range(turns):
            for conv in convs:
                sess.post(rbase + "/v1/generate",
                          json={"tokens": [conv],
                                "max_new_tokens": new_tokens}, timeout=60)
        after = router.sticky.stats()
        hits = after["sticky_hits"] - before["sticky_hits"]
        misses = after["sticky_misses"] - before["sticky_misses"]
        out["sticky_hit_ratio"] = (
            round(hits / (hits + misses), 4) if hits + misses else None
        )

        # fair-share storm (ISSUE 9): two clients — one 10x hotter —
        # saturate the SAME pods through a second, admission-enabled
        # router (fair_share + bounded backlog + retry budget on; the
        # main router above keeps observe-only defaults, which is itself
        # the no-behavior-change leg). Reported: Jain index of per-client
        # goodput (1.0 = equal shares; FIFO would give the hot client
        # ~10x), sheds by priority class, and retry amplification
        # (upstream attempts per logical request; ~1.0 = no retry storm).
        from modelx_tpu.router.admission import (
            AdmissionController,
            RetryBudget,
            jain_index,
        )

        fair_registry = PodRegistry([p["url"] for p in pod_set],
                                    poll_interval_s=0.5)
        fair_router = FleetRouter(
            fair_registry, request_timeout_s=30.0,
            admission=AdmissionController(fair_share=2, max_backlog=8),
            retry_budget=RetryBudget(ratio=0.2),
        )
        fair_router.start()
        fhttpd = route_serve(fair_router, listen=f"127.0.0.1:{free_port()}")
        fbase = f"http://127.0.0.1:{fhttpd.server_address[1]}"
        try:
            storm_prompt = rng.randint(1, vocab, (8,)).tolist()
            goodput = {"hot": 0, "cold": 0}
            storm_lock = threading.Lock()
            stop_at = time.monotonic() + 5.0

            def storm_client(name: str) -> None:
                # /v1/forward traffic, like the sticky drill: admission
                # semantics are identical for every proxied verb, and the
                # single-forward service time packs enough completions
                # into the window for the Jain index to mean something
                sess = _requests.Session()
                while time.monotonic() < stop_at:
                    try:
                        r = sess.post(
                            fbase + "/v1/forward",
                            json={"tokens": [storm_prompt]},
                            headers={"X-ModelX-Client": name},
                            timeout=30)
                        ok = r.status_code == 200
                    except _requests.RequestException:
                        ok = False
                    if ok:
                        # goodput counts only completions INSIDE the
                        # window: the backlogged (hot) client's queued
                        # waiters all drain after stop_at, and counting
                        # that tail would credit the monopolist with the
                        # very backlog fairness denied it
                        if time.monotonic() <= stop_at:
                            with storm_lock:
                                goodput[name] += 1
                    else:
                        # back off briefly on a shed: a zero-sleep 429
                        # spin across 20 threads would burn the one-CPU
                        # rig's cycles against the very router being
                        # measured (real clients honor Retry-After)
                        time.sleep(0.05)

            # 10x rate asymmetry by connection count: 20 hot vs 2 cold.
            # The cold client needs >= 2 connections to OCCUPY its fair
            # slot share — a single closed-loop connection waits a full
            # service time between its own grants and can never reach
            # 50% goodput no matter how fair the scheduler is
            storm_threads = [
                threading.Thread(target=storm_client, args=("hot",),
                                 daemon=True)
                for _ in range(20)
            ] + [
                threading.Thread(target=storm_client, args=("cold",),
                                 daemon=True)
                for _ in range(2)
            ]
            for t in storm_threads:
                t.start()
            for t in storm_threads:
                t.join()
            out["fair_share_jain_index"] = jain_index(
                [goodput["hot"], goodput["cold"]])
            out["fair_share_goodput"] = dict(goodput)
            adm = fair_router.admission.snapshot()
            out["shed_429_count_by_class"] = dict(adm["shed_by_class"])
            fm = fair_router.metrics.snapshot()
            dispatched = fm["requests_total"] - fm["admission_shed_total"]
            out["retry_amplification"] = (
                round(fm["upstream_attempts_total"] / dispatched, 3)
                if dispatched > 0 else None
            )
        finally:
            fhttpd.shutdown()
            fair_router.close()

        # pod-kill drill: kill the pod that owns a conversation, then time
        # kill -> first successful response for that same conversation
        target = convs[0]
        routes = router.metrics.snapshot()["routes"]
        victim = max(pod_set, key=lambda p: routes.get(p["url"], 0))
        dropped = 0
        victim["kill"].kill()
        t0 = time.monotonic()
        recovery_ms = None
        for _ in range(20):
            try:
                r = sess.post(rbase + "/v1/generate",
                              json={"tokens": [target],
                                    "max_new_tokens": new_tokens},
                              timeout=60)
                if r.status_code == 200:
                    recovery_ms = (time.monotonic() - t0) * 1e3
                    break
                dropped += 1
            except _requests.RequestException:
                dropped += 1
        out["failover_recovery_ms"] = (
            round(recovery_ms, 1) if recovery_ms is not None else None
        )
        out["fleet_dropped_requests"] = dropped
        snap = router.metrics.snapshot()
        out["fleet_failovers"] = snap["failovers_total"]
    finally:
        rhttpd.shutdown()
        router.close()
        for p in pod_set:
            p["httpd"].shutdown()
    return out


def measure_continuation(model_dir: str, *, pods: int = 2, clients: int = 8,
                         new_tokens: int = 16,
                         max_seq_len: int = 128) -> dict:
    """Stream-continuation drill (ISSUE 12): a seeded mid-stream pod kill
    behind the router under ``clients`` concurrent seeded SAMPLED streams
    (identical prompt+seed, so prefix stickiness pins them ALL to the
    dying pod). The router must resume every committed stream on a
    surviving pod token-exactly — ``tokens_lost`` asserts the zero-loss
    contract against an uninterrupted reference stream — and the only
    client-visible cost is one stall, ``continuation_gap_ms`` (last
    pre-kill line -> first post-resume line, read as the max inter-line
    arrival gap across clients; the kill is armed at a line boundary so
    other gaps are per-token decode intervals)."""
    import requests as _requests

    from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
    from modelx_tpu.registry.server import free_port
    from modelx_tpu.router.registry import PodRegistry
    from modelx_tpu.router.server import FleetRouter, route_serve
    from modelx_tpu.testing.faults import PodKillSwitch

    server = ModelServer(model_dir, name="default", max_seq_len=max_seq_len)
    server.load()
    vocab = int(getattr(server.cfg, "vocab_size", 0) or 256)

    rng = np.random.RandomState(23)
    prompt = rng.randint(1, vocab, (6,)).tolist()
    body = {"tokens": [prompt], "max_new_tokens": new_tokens, "stream": True,
            "temperature": 0.9, "top_k": 8, "top_p": 0.95, "seed": 1234}

    # continuous-engine pods around the ONE loaded model: the resume
    # contract needs per-step sample streams (chunked single-row NDJSON)
    pod_set = []
    for _ in range(pods):
        sset = ServerSet({"default": server}, continuous_batch=True,
                         max_slots=2, stream_chunk_size=4)
        sset.pool.mark_ready("default")
        httpd = serve(sset, listen=f"127.0.0.1:{free_port()}")
        pod_set.append({"sset": sset, "httpd": httpd,
                        "url": f"http://127.0.0.1:{httpd.server_address[1]}",
                        "kill": PodKillSwitch(httpd, sset=sset)})

    def read_lines(resp) -> tuple[list, list]:
        """NDJSON payloads + per-line arrival stamps (chunk_size=1 so a
        line's stamp is its flush time, not a buffer boundary)."""
        payloads, stamps = [], []
        for raw in resp.iter_lines(chunk_size=1):
            if raw:
                stamps.append(time.monotonic())
                payloads.append(json.loads(raw))
        return payloads, stamps

    out: dict = {}
    router = None
    rhttpd = None
    try:
        # reference: an uninterrupted direct stream (also warms the
        # compiled shapes, so the routed leg's gap is not a compile)
        r = _requests.post(pod_set[0]["url"] + "/v1/generate", json=body,
                           stream=True, timeout=120)
        if r.status_code != 200:
            raise RuntimeError(f"reference stream failed: {r.text[:200]}")
        ref, _ = read_lines(r)
        ref_ids = [p["tokens"][0][0] for p in ref if "tokens" in p]
        if len(ref_ids) != new_tokens or not ref[-1].get("done"):
            raise RuntimeError(f"malformed reference stream: {ref}")

        registry = PodRegistry([p["url"] for p in pod_set],
                               poll_interval_s=0.5)
        router = FleetRouter(registry, request_timeout_s=60.0)
        router.start()
        rhttpd = route_serve(router, listen=f"127.0.0.1:{free_port()}")
        rbase = f"http://127.0.0.1:{rhttpd.server_address[1]}"

        # arm EVERY pod (placement is the router's call): at piece 2 of
        # the first stream served, the serving pod hard-dies at a line
        # boundary — listener closed, live connections severed
        fired = threading.Event()
        for p in pod_set:
            orig = p["sset"].stream_source

            def src(server_, tokens, n, samp, stop_token_ids=None,
                    _orig=orig, _pod=p, **kw):
                gen = _orig(server_, tokens, n, samp,
                            stop_token_ids=stop_token_ids, **kw)

                def run():
                    for i, piece in enumerate(gen):
                        if i == 2 and not fired.is_set():
                            fired.set()
                            time.sleep(0.3)  # router drains pieces 0-1
                            _pod["kill"].kill()
                            raise RuntimeError("pod dies")
                        yield piece

                return run()

            p["sset"].stream_source = src

        results: list = [None] * clients
        errors: list = []

        def client(i: int) -> None:
            try:
                r_ = _requests.post(rbase + "/v1/generate", json=body,
                                    stream=True, timeout=120)
                if r_.status_code != 200:
                    raise RuntimeError(f"status {r_.status_code}")
                results[i] = read_lines(r_)
            except Exception as e:  # surfaced below — the drill must fail
                errors.append(f"client {i}: {e!r}")

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError("; ".join(errors[:3]))
        if not fired.is_set():
            raise RuntimeError("seeded kill never fired")

        # zero-loss contract, per client: reference tokens NOT reproduced
        # in order (a wrong token loses the whole tail — the stream
        # diverged), summed across the fleet of streams
        lost = 0
        worst_gap = None
        for got, stamps in results:
            got_ids = [p_["tokens"][0][0] for p_ in got if "tokens" in p_]
            prefix = 0
            for a, b in zip(got_ids, ref_ids):
                if a != b:
                    break
                prefix += 1
            lost += len(ref_ids) - prefix
            for a, b in zip(stamps, stamps[1:]):
                if worst_gap is None or b - a > worst_gap:
                    worst_gap = b - a
        out["continuation_clients"] = clients
        out["tokens_lost"] = lost
        snap = router.metrics.snapshot()
        out["streams_continued"] = snap["streams_continued_total"]
        out["streams_severed"] = snap["severed_streams_total"]
        out["continuation_gap_ms"] = (
            round(worst_gap * 1e3, 1) if worst_gap is not None else None
        )
    finally:
        if rhttpd is not None:
            rhttpd.shutdown()
        if router is not None:
            router.close()
        for p in pod_set:
            p["httpd"].shutdown()
            for cb in p["sset"].cbatchers.values():
                cb.close()
                cb.release_device_state()
    return out


def measure_latency_breakdown(model_dir: str, *, requests_n: int = 8,
                              new_tokens: int = 8,
                              max_seq_len: int = 128) -> dict:
    """Per-request latency breakdown micro-leg (ISSUE 13): fire
    ``requests_n`` non-streaming requests at one continuous-batching pod
    and read the ``X-ModelX-Timing-*`` headers back. Two checks ride it:
    the phase spans must ACCOUNT for the request (the engine-reported
    ``total_ms`` covers >= 90% of the client-observed wall time — a
    breakdown that loses a tenth of the latency is lying), and the
    TTFT split (``ttft_queue_ms_*`` = admission wait vs
    ``ttft_compute_ms_*`` = prefill-to-first-token) is the capacity
    signal: queue-dominated TTFT means add pods, compute-dominated
    means the model/batching is the floor."""
    import requests as _requests

    from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
    from modelx_tpu.dl.serving_errors import TIMING_HEADER_PREFIX
    from modelx_tpu.registry.server import free_port

    server = ModelServer(model_dir, name="default", max_seq_len=max_seq_len)
    server.load()
    vocab = int(getattr(server.cfg, "vocab_size", 0) or 256)
    sset = ServerSet({"default": server}, continuous_batch=True,
                     max_slots=2, stream_chunk_size=4)
    sset.pool.mark_ready("default")
    httpd = serve(sset, listen=f"127.0.0.1:{free_port()}")
    base = f"http://127.0.0.1:{httpd.server_address[1]}"

    def hdr_ms(resp, key: str) -> float:
        name = TIMING_HEADER_PREFIX + "-".join(
            p.capitalize() for p in key.split("_"))
        return float(resp.headers.get(name, 0) or 0)

    rng = np.random.RandomState(31)
    queue_ms, compute_ms, coverage = [], [], []
    try:
        for i in range(requests_n):
            prompt = rng.randint(1, vocab, (6,)).tolist()
            t0 = time.monotonic()
            r = _requests.post(base + "/v1/generate",
                               json={"tokens": [prompt],
                                     "max_new_tokens": new_tokens},
                               timeout=120)
            wall_ms = (time.monotonic() - t0) * 1e3
            if r.status_code != 200:
                raise RuntimeError(f"request {i}: {r.text[:200]}")
            q, ttft = hdr_ms(r, "queue_ms"), hdr_ms(r, "ttft_ms")
            total = hdr_ms(r, "total_ms")
            if not total or not ttft:
                raise RuntimeError(
                    f"request {i}: timing headers missing: "
                    f"{dict(r.headers)}")
            queue_ms.append(q)
            compute_ms.append(max(0.0, ttft - q))
            coverage.append(total / wall_ms if wall_ms else 0.0)
    finally:
        httpd.shutdown()
        for cb in sset.cbatchers.values():
            cb.close()
            cb.release_device_state()

    worst = min(coverage)
    # the >= 0.9 coverage bar is a SOFT gate (known clean-tree flake on
    # loaded boxes: the wall clock spans scheduler preemptions the phase
    # spans legitimately exclude) — report the measured coverage and a
    # boolean instead of failing the whole bench run
    coverage_ok = worst >= 0.9
    if not coverage_ok:
        print(f"  warning: phase spans cover only {worst:.1%} of wall time "
              f"(coverage per request: {[round(c, 3) for c in coverage]}); "
              "queue/compute percentiles may under-report on this box",
              file=sys.stderr)

    def pct(vals, p) -> float:
        return round(float(np.percentile(vals, p)), 3)

    return {
        "breakdown_requests": requests_n,
        "breakdown_coverage_min": round(worst, 3),
        "breakdown_coverage_ok": coverage_ok,
        "ttft_queue_ms_p50": pct(queue_ms, 50),
        "ttft_queue_ms_p99": pct(queue_ms, 99),
        "ttft_compute_ms_p50": pct(compute_ms, 50),
        "ttft_compute_ms_p99": pct(compute_ms, 99),
    }


def measure_obs_overhead(model_dir: str, *, clients_n: int = 8,
                         requests_per_client: int = 3, new_tokens: int = 8,
                         rounds: int = 3, max_seq_len: int = 128) -> dict:
    """Observability-overhead micro-leg (ISSUE 15): the flight recorder
    and device telemetry are always-on by default, so their cost must be
    measured, not asserted. Runs the SAME 8-client generate workload
    against two pods that differ only in the recorder+telemetry knobs
    and compares best-of-``rounds`` wall time (min-of-rounds because CPU
    scheduling noise dwarfs the dict stores being measured — the bar is
    ``flightrec_overhead_pct`` < 2%). Also reads the measured-vs-
    reserved HBM accounting off the instrumented pod
    (``hbm_measured_vs_reserved_ratio``)."""
    import requests as _requests

    from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
    from modelx_tpu.registry.server import free_port

    server = ModelServer(model_dir, name="default", max_seq_len=max_seq_len)
    server.load()
    vocab = int(getattr(server.cfg, "vocab_size", 0) or 256)
    out: dict = {"obs_overhead_clients": clients_n}

    def run_leg(obs_on: bool) -> float:
        sset = ServerSet({"default": server}, continuous_batch=True,
                         max_slots=4, stream_chunk_size=4,
                         flight_recorder=obs_on, device_telemetry=obs_on)
        sset.pool.mark_ready("default")
        httpd = serve(sset, listen=f"127.0.0.1:{free_port()}")
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        rng = np.random.RandomState(47)
        prompts = [rng.randint(1, vocab, (6,)).tolist()
                   for _ in range(clients_n)]
        errors: list = []

        def client(idx: int) -> None:
            try:
                for _ in range(requests_per_client):
                    r = _requests.post(
                        base + "/v1/generate",
                        json={"tokens": [prompts[idx]],
                              "max_new_tokens": new_tokens},
                        timeout=120)
                    if r.status_code != 200:
                        raise RuntimeError(f"client {idx}: {r.text[:200]}")
            except Exception as e:  # surfaced after join
                errors.append(e)

        def one_round() -> float:
            threads = [threading.Thread(target=client, args=(i,), daemon=True)
                       for i in range(clients_n)]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise RuntimeError(f"obs-overhead leg failed: {errors[0]}")
            return time.monotonic() - t0

        try:
            one_round()  # warmup: compiles + first-admission costs
            best = min(one_round() for _ in range(rounds))
            if obs_on:
                # the instrumented leg also proves the telemetry surface:
                # measured occupancy lands next to the estimate
                snap = sset.pool.pool_snapshot()
                measured = int(snap.get("hbm_bytes_measured", 0))
                reserved = int(snap.get("hbm_reserved_bytes", 0))
                out["hbm_measured_vs_reserved_ratio"] = (
                    round(measured / reserved, 3) if reserved else None)
                out["hbm_measured_source"] = snap.get(
                    "hbm_measured_source", "none")
                cb = sset.cbatchers.get("default")
                out["flightrec_events"] = (
                    cb.flightrec.total if cb is not None
                    and cb.flightrec is not None else 0)
        finally:
            httpd.shutdown()
            for cb in sset.cbatchers.values():
                cb.close()
                cb.release_device_state()
        return best

    on_s = run_leg(True)
    off_s = run_leg(False)
    out["obs_on_wall_s"] = round(on_s, 4)
    out["obs_off_wall_s"] = round(off_s, 4)
    out["flightrec_overhead_pct"] = (
        round((on_s - off_s) / off_s * 100.0, 2) if off_s else None)
    return out


class _Budget:
    """Soft wall-clock budget for the whole capture (a run that exceeds
    the driver's hard timeout records NOTHING, rc 124). Stages check ``allows(est)`` before starting and get skipped —
    recorded in ``timed_out_legs`` — when the remainder can't cover them;
    subprocess legs additionally clamp their own timeout to the remainder,
    so one wedged leg can't eat the capture."""

    def __init__(self, total_s: float) -> None:
        self.t0 = time.monotonic()
        self.total = float(total_s)

    def elapsed(self) -> float:
        return time.monotonic() - self.t0

    def remaining(self) -> float:
        return self.total - self.elapsed()

    def allows(self, est_s: float) -> bool:
        return self.remaining() >= est_s


def run_guarded(budget: _Budget, name: str, fn, est_s: float = 0.0,
                timed_out: list | None = None,
                leg_errors: dict | None = None):
    """Run one bench stage under the soft budget. Skipped stages land in
    ``timed_out`` (budget exhausted), failed ones in ``leg_errors`` — the
    capture keeps going and the final JSON always prints (a partial
    capture with named holes beats rc 124 with nothing); ``main`` then
    exits non-zero, because a capture with holes is not a passing one."""
    if not budget.allows(est_s):
        if timed_out is not None:
            timed_out.append(name)
        return None
    try:
        return fn()
    except Exception as e:
        if leg_errors is None:
            raise
        leg_errors[name] = repr(e)[:300]
        return None


def run_leg(kind: str, base: str, repo: str, workdir: str,
            timeout_s: float = 900.0) -> dict:
    """One timed leg in a FRESH subprocess (a deploy is a fresh process —
    see module docstring). Returns the child's JSON."""
    env = _device_child_env()  # children use the real device
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--leg", kind, base, repo, workdir],
        capture_output=True, text=True, env=env,
        timeout=max(60.0, timeout_s),
    )
    if p.returncode != 0:
        raise RuntimeError(f"{kind} leg failed: {p.stderr[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def leg_main(kind: str, base: str, repo: str, workdir: str) -> int:
    """Child entry for one timed leg. Loads, then probes the raw link in
    the SAME process, so every leg carries its own ceiling."""
    from modelx_tpu.client.client import Client

    client = Client(base, quiet=True)
    manifest = client.get_manifest(repo, "v1")
    desc = next(b for b in manifest.blobs if b.name.endswith(".safetensors"))
    size = desc.size

    import jax

    devices = jax.devices()
    if kind == "baseline":
        secs = run_baseline(base, repo, desc, workdir, devices)
        print(json.dumps({
            "seconds": round(secs, 3),
            "link_gbps": round(probe_link_gbps(devices[0]), 3),
        }))
        return 0
    from modelx_tpu import native
    from modelx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(f"dp={len(devices)}")
    cache = None
    prefer_local: bool | None = None
    if kind in ("cold", "warm"):
        # blob-cache legs model a REMOTE pod: skip the colocated file
        # redirect (the registry and the leg share this host) so the cold
        # leg streams HTTP + tees to the cache, and the warm leg must be
        # served by the cache alone (zero network reads)
        from modelx_tpu.dl.blob_cache import BlobCache

        cache_dir = os.path.join(workdir, "blobcache")
        if kind == "cold":
            shutil.rmtree(cache_dir, ignore_errors=True)
        cache = BlobCache(cache_dir)
        prefer_local = False
    secs, src, stats = run_ours(
        client, repo, desc, mesh, size,
        quantize="int8" if kind == "int8" else None,
        cache=cache, prefer_local=prefer_local,
    )
    rec = {
        "seconds": round(secs, 3),
        "source": src,
        "native": native.available(),
        "bytes_fetched": stats.bytes_fetched,
        "fetch_seconds": round(stats.fetch_seconds, 3),
        "bytes_to_device": stats.bytes_to_device,
        "fetch_width": stats.fetch_width,
        "fetch_backoffs": stats.fetch_backoffs,
        "fetch_growths": stats.fetch_growths,
        "overlap_seconds": round(stats.overlap_seconds, 3),
        "device_put_seconds": round(stats.device_put_seconds, 3),
        "staging_allocs": stats.staging_allocs,
        "staging_reuses": stats.staging_reuses,
        "link_gbps": round(probe_link_gbps(devices[0]), 3),
    }
    if cache is not None:
        # warm = the load came off the local cache tier (LocalFileSource
        # over the verified entry), i.e. zero network reads
        rec["cache_state"] = "warm" if src == "LocalFileSource" else "cold"
        rec["blob_cache"] = dict(cache.stats)
    print(json.dumps(rec))
    return 0


def _device_child_env() -> dict:
    """Environment for subprocesses that must see the REAL device: this
    repo on PYTHONPATH, and any JAX_PLATFORMS=cpu override stripped.

    A chip belongs to one process at a time, so the parent must not have
    touched jax when it hands the device to a child — checked here, where
    every device child gets its environment, instead of trusted to the
    ordering of ``main``."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "bench parent imported jax before a device child ran: the child "
            "would fail or hang on a chip this process holds")
    here = os.path.dirname(os.path.abspath(__file__))
    existing = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ,
               PYTHONPATH=here + (os.pathsep + existing if existing else ""))
    env.pop("JAX_PLATFORMS", None)
    return env


def wait_for_device(probe_timeout_s: float = 120.0) -> dict:
    """ONE fail-fast probe that an ACCELERATOR answers, in a short-lived
    subprocess (this parent stays off jax; a hung backend init cannot be
    cancelled in-process). The probe REJECTS a cpu backend — a capture
    without a chip is refused, not recorded — and its stderr rides in the
    error so a broken environment names itself. Returns the device as jax
    reports it: ``{"platform", "kind", "count"}``."""
    try:
        p = subprocess.run(
            [sys.executable, "-c",
             "import json, jax; d = jax.devices(); "
             "assert d[0].platform != 'cpu', 'cpu backend — accelerator not found'; "
             "print(json.dumps({'platform': d[0].platform, "
             "'kind': d[0].device_kind, 'count': len(d)}))"],
            env=_device_child_env(), timeout=probe_timeout_s,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
    except subprocess.TimeoutExpired:
        raise RuntimeError(
            f"accelerator probe hung > {probe_timeout_s:.0f}s (backend init) "
            "— refusing to record a dead capture") from None
    if p.returncode != 0:
        raise RuntimeError(
            "no accelerator — refusing to record a capture; probe said: "
            f"{(p.stderr or '').strip()[-500:] or 'no stderr'}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def main() -> int:
    # registry data + checkpoints only; no compile cache hangs under it
    workdir = tempfile.mkdtemp(prefix="modelx-bench-")
    # soft wall-clock budget for the WHOLE capture (a run that outgrows the
    # driver's hard timeout records NOTHING, rc 124). Stages that no longer
    # fit are skipped — named in ``timed_out_legs`` — subprocess children
    # clamp their timeouts to the remainder, and the one JSON line prints
    # no matter what. The default must clear the harness's hard wall with
    # margin: pre-budget overhead (device probe, interpreter start) counts
    # against the wall, not the budget.
    budget = _Budget(float(os.environ.get("BENCH_BUDGET_S", 1500.0)))
    timed_out: list[str] = []
    leg_errors: dict[str, str] = {}
    # headline keys are always present so a partial capture still parses
    # as the bench schema; stages fill them in as they complete
    out: dict = {"metric": "registry_to_hbm_gbps", "value": None,
                 "unit": "GB/s"}
    srv = None
    try:
        out["device"] = wait_for_device()
        ckpt = os.path.join(workdir, "model.safetensors")
        target = int(os.environ.get("BENCH_BYTES", 512 * 1024 * 1024))
        size = build_checkpoint(ckpt, target)
        srv, base = start_registry(workdir)
        client, desc = push_checkpoint(base, "library/bench", ckpt)

        # small model for TTFT (BASELINE #3 cut to one chip: the 500 ms
        # budget was set for a multi-chip pod)
        ttft_ckpt = os.path.join(workdir, "ttft.safetensors")
        build_checkpoint(ttft_ckpt, 48 * 1024 * 1024, hidden=512, inter=1408, vocab=8192)
        push_checkpoint(base, "library/ttft", ttft_ckpt)

        # TTFT first and subprocess-per-run; like every timed leg below, the
        # children own the device — this parent must not touch jax until
        # all measured subprocesses are done (_device_child_env checks).
        # 3 scored runs + 1 int8 sample
        ttft = run_guarded(
            budget, "ttft",
            lambda: measure_ttft(
                base, "library/ttft", runs=3, int8_runs=1,
                child_timeout_s=min(600.0, budget.remaining()),
            ),
            est_s=180.0, timed_out=timed_out, leg_errors=leg_errors,
        ) or {}
        # warm-restart TTFT: the children share a blob cache, run 0 fills
        # it, the scored runs model a pod restart that skips the network
        warm_ttft = run_guarded(
            budget, "ttft_warm",
            lambda: measure_ttft(
                base, "library/ttft", runs=2, int8_runs=0,
                blob_cache_dir=os.path.join(workdir, "ttft-blobcache"),
                child_timeout_s=min(600.0, budget.remaining()),
            ),
            est_s=120.0, timed_out=timed_out, leg_errors=leg_errors,
        )
        if warm_ttft:
            ttft.update(ttft_warm_fields(warm_ttft))
        out.update(ttft)

        # compiled-program registry leg (ISSUE 11): the first pod pays the
        # full compile and publishes its AOT surface as a program bundle;
        # a second fresh-process pod with an EMPTY compile cache pulls the
        # bundle and warm-starts its compile leg — both children on the
        # same repo/registry as the TTFT legs above, with per-child emptied
        # cache dirs so nothing leaks between them
        out.update(run_guarded(
            budget, "program_store",
            lambda: measure_program_store(
                base, "library/ttft",
                child_timeout_s=min(600.0, budget.remaining()),
            ),
            est_s=120.0, timed_out=timed_out, leg_errors=leg_errors,
        ) or {})

        # alternate subprocess legs, baseline first
        baseline_recs: list[dict] = []
        ours_recs: list[dict] = []
        int8_recs: list[dict] = []

        def leg(kind: str) -> dict:
            return run_leg(kind, base, "library/bench", workdir,
                           timeout_s=min(900.0, budget.remaining()))

        # best-of-2 rounds — the collapsed-leg guard below already reruns
        # collapsed captures
        rounds = int(os.environ.get("BENCH_LOAD_ROUNDS", 2))
        for i in range(rounds):
            # each round is up to 3 subprocess legs: skip remaining rounds
            # (named) rather than let them blow the capture's budget
            if i and not budget.allows(3 * 60.0):
                timed_out.append(f"load_round_{i}")
                break
            baseline_recs.append(leg("baseline"))
            ours_recs.append(leg("ours"))
            if i < 1:
                # int8 deploy leg: the loader quantizes on the host
                # (native fused kernel), so HALF the bytes cross the
                # link and the model decodes faster once resident
                # (int8_decode_speedup below). Effective GB/s counts
                # SOURCE bytes.
                int8_recs.append(leg("int8"))

        legs_retried: list[str] = []

        def best(recs: list[dict]) -> dict:
            return min(recs, key=lambda r: r["seconds"])

        def link_ceiling() -> float:
            return max(
                (r.get("link_gbps") or 0.0)
                for r in baseline_recs + ours_recs + int8_recs
            )

        # collapsed-leg guard (VERDICT r4): a leg that lost 4x to the
        # same-round baseline AND sat under 10% of the measured link is a
        # disturbed capture, not a code result — rerun it once in another
        # fresh process and keep the best.
        def collapsed(rec: dict, baseline_gbps: float) -> bool:
            gbps = size / rec["seconds"] / 1e9
            link = link_ceiling()
            return gbps < 0.25 * baseline_gbps and (
                not link or gbps < 0.10 * link
            )

        retry_est = 60.0
        base_gbps = size / best(baseline_recs)["seconds"] / 1e9
        if base_gbps < 0.10 * link_ceiling() and budget.allows(retry_est):
            # the baseline itself collapsed: an inflated ratio would flatter
            # us dishonestly — rerun the baseline too
            baseline_recs.append(leg("baseline"))
            legs_retried.append("baseline")
            base_gbps = size / best(baseline_recs)["seconds"] / 1e9
        if collapsed(best(ours_recs), base_gbps) and budget.allows(retry_est):
            ours_recs.append(leg("ours"))
            legs_retried.append("ours")
        if collapsed(best(int8_recs), base_gbps) and budget.allows(retry_est):
            int8_recs.append(leg("int8"))
            legs_retried.append("int8")

        # blob-cache cold/warm split: one cold leg (HTTP + tee, fresh
        # cache), then warm legs served purely off the local cache tier —
        # the ServerlessLLM re-deploy story, measured
        def cold_warm() -> dict:
            cold_rec = leg("cold")
            warm_recs = [leg("warm"), leg("warm")]
            return cache_split_summary(size, cold_rec, best(warm_recs))

        cache_split = run_guarded(
            budget, "cache_split", cold_warm, est_s=3 * 60.0,
            timed_out=timed_out, leg_errors=leg_errors,
        ) or {}

        ours_s = best(ours_recs)["seconds"]
        baseline_s = best(baseline_recs)["seconds"]
        int8_s = best(int8_recs)["seconds"]
        best_rec = best(ours_recs)
        int8_rec = best(int8_recs)
        link_gbps = link_ceiling()

        def mt_stage() -> dict:
            m = measure_multitenant(base, "library/bench", desc, size)
            m.update(
                measure_redirect_multitenant(base, "library/bench", desc, size)
            )
            # load separation (the reference's core architectural claim,
            # docs/api.md:32-42): per-leg pass verdicts, stated explicitly
            # so host scheduling noise can't read as an architecture
            # regression. Direct legs stream through the
            # server process; the redirect legs never touch it — pass =
            # redirect path under 4-way load sustains the direct path's
            # single-client rate, with a 10% tolerance for the shared-core
            # scheduling noise.
            m["load_separation_pass"] = bool(
                m["mt_redirect_aggregate_gbps"] >= 0.9 * m["mt_single_gbps"]
            )
            return m

        multitenant = run_guarded(
            budget, "multitenant", mt_stage, est_s=150.0,
            timed_out=timed_out, leg_errors=leg_errors,
        ) or {}

        ours_gbps = size / ours_s / 1e9
        baseline_gbps = size / baseline_s / 1e9

        # headline recorded BEFORE the serving legs: if a later stage dies
        # or the budget runs out, the loader capture still prints
        out.update({
            "value": round(ours_gbps, 3),
            "vs_baseline": round(ours_gbps / baseline_gbps, 3),
            "baseline_gbps": round(baseline_gbps, 3),
            "bytes": size,
            "seconds": round(ours_s, 3),
            "baseline_seconds": round(baseline_s, 3),
            "seconds_runs": [round(r["seconds"], 3) for r in ours_recs],
            "baseline_seconds_runs": [round(r["seconds"], 3) for r in baseline_recs],
            # every timed leg ran in its own fresh subprocess; the guard
            # reruns collapsed captures once (see module docstring)
            "leg_isolation": "subprocess",
            "legs_retried": legs_retried,
            # per-leg link probes (same process as the leg, post-load):
            # the ceiling each leg actually had
            "leg_link_gbps": [r.get("link_gbps") for r in ours_recs],
            # decomposition of the winning leg: aggregate fetch-thread rate
            # vs bytes that crossed the host->device link (fetch and
            # transfer overlap, so the pieces don't sum to wall time)
            "fetch_gbps": round(
                best_rec["bytes_fetched"] / max(best_rec["fetch_seconds"], 1e-9) / 1e9, 3
            ),
            "fetch_thread_seconds": best_rec["fetch_seconds"],
            "bytes_to_device": best_rec["bytes_to_device"],
            "fetch_width": best_rec.get("fetch_width"),
            "fetch_backoffs": best_rec.get("fetch_backoffs"),
            "fetch_growths": best_rec.get("fetch_growths"),
            "overlap_seconds": best_rec.get("overlap_seconds"),
            "device_put_seconds": best_rec.get("device_put_seconds"),
            "staging_allocs": best_rec.get("staging_allocs"),
            "staging_reuses": best_rec.get("staging_reuses"),
            # blob-cache tier: cold tee vs warm (zero-network) restart
            **cache_split,
            # int8 deploy leg: same source checkpoint, half the link bytes
            "int8_load_seconds": round(int8_s, 3),
            "int8_load_gbps_effective": round(size / int8_s / 1e9, 3),
            "int8_vs_baseline": round(baseline_s / int8_s, 3),
            "int8_bytes_to_device": int8_rec["bytes_to_device"],
            "link_gbps": round(link_gbps, 3),
            "link_utilization": round(ours_gbps / link_gbps, 3) if link_gbps else None,
            "engine": {"native": best_rec.get("native"), "source": best_rec.get("source")},
            **multitenant,
        })

        if not budget.allows(240.0):
            # the serving legs need an in-process load + compiles: don't
            # start what can't finish
            timed_out.append("serving")
            return 1
        # the measured subprocesses are done: the parent may now take the
        # device for the serving legs
        import jax

        from modelx_tpu.dl.loader import load_safetensors
        from modelx_tpu.dl.sharding import LLAMA_RULES
        from modelx_tpu.dl.initializer import _blob_source
        from modelx_tpu.parallel.mesh import make_mesh

        devices = jax.devices()
        device_kind = getattr(devices[0], "device_kind", str(devices[0]))
        mesh = make_mesh(f"dp={len(devices)}")
        out.update({
            "device_kind": device_kind,
            "n_devices": len(devices),
        })

        # serving: load once more (cheap assert it still works), reuse arrays
        source = _blob_source(client, "library/bench", desc)
        try:
            loaded, _stats = load_safetensors(source, mesh, LLAMA_RULES)
        finally:
            if hasattr(source, "close"):
                source.close()

        def guard(name: str, fn, est_s: float) -> None:
            out.update(run_guarded(budget, name, fn, est_s=est_s,
                                   timed_out=timed_out,
                                   leg_errors=leg_errors) or {})

        guard("serving",
              lambda: measure_serving(loaded, mesh, device_kind), 120.0)
        dtps = out.get("decode_tokens_per_s")
        guard("continuous",
              lambda: measure_continuous(loaded, mesh, dtps), 90.0)
        # pipelined-dispatch leg (ISSUE 7): identical traffic against
        # serial boundaries vs dispatch-ahead — the per-chunk overhead and
        # continuous-vs-batch ratio the tentpole is accountable for
        guard("decode_pipelined",
              lambda: measure_decode_pipelined(loaded, mesh, dtps), 120.0)
        # mixed prefill/decode leg: admit a long prompt into a saturated
        # decode batch; chunked prefill must bound the ITL jitter the
        # monolithic-admission baseline inflicts (ISSUE 2 acceptance)
        guard("mixed_prefill",
              lambda: measure_mixed_prefill(loaded, mesh), 90.0)
        # overload/self-healing leg: bounded admission sheds, deadline
        # expiry, and supervised recovery after an injected engine crash
        # (ISSUE 3 acceptance)
        guard("overload", lambda: measure_overload(loaded, mesh), 90.0)
        del loaded

        # model-swap leg: unload A / load B through the lifecycle pool
        # under live traffic to C, cold vs blob-cache-warm (ISSUE 5)
        guard("model_swap", lambda: measure_model_swap(base, workdir), 180.0)

        # registry-outage drill: brown out / kill the control plane under
        # live traffic; the data path must not drop a request and a swap-in
        # must still materialize from the pinned-manifest + blob caches
        # (ISSUE 19 acceptance: outage_dropped_requests == 0)
        guard("registry_outage",
              lambda: measure_registry_outage(workdir), 180.0)

        # fleet front-door leg: N pods behind the router vs one pod
        # direct (router tax on one device), sticky-hit ratio on
        # repeated-prefix conversations, pod-kill failover drill (ISSUE 8)
        def fleet_leg() -> dict:
            fleet_dir = os.path.join(workdir, "fleet")
            os.makedirs(fleet_dir, exist_ok=True)
            build_checkpoint(os.path.join(fleet_dir, "model.safetensors"),
                             48 * 1024 * 1024, hidden=512, inter=1408,
                             vocab=8192)
            return measure_fleet(fleet_dir)

        guard("fleet", fleet_leg, 180.0)

        # stream-continuation drill: seeded mid-stream pod kill behind the
        # router on a seeded sampled stream; the resume contract must hold
        # token-exactly (tokens_lost == 0) and the cost is one stall
        # (continuation_gap_ms) — ISSUE 12 acceptance
        def continuation_leg() -> dict:
            cont_dir = os.path.join(workdir, "fleet")
            if not os.path.exists(os.path.join(cont_dir,
                                               "model.safetensors")):
                os.makedirs(cont_dir, exist_ok=True)
                build_checkpoint(
                    os.path.join(cont_dir, "model.safetensors"),
                    48 * 1024 * 1024, hidden=512, inter=1408, vocab=8192)
            return measure_continuation(cont_dir)

        guard("continuation", continuation_leg, 120.0)

        # content-addressed prefix-KV leg (ISSUE 20): pod 1 publishes the
        # hot shared prompt's prefill KV to the registry; a fresh pod 2
        # installs it and serves that prompt with a suffix-only prefill
        def kv_leg() -> dict:
            kv_dir = os.path.join(workdir, "fleet")
            if not os.path.exists(os.path.join(kv_dir, "model.safetensors")):
                os.makedirs(kv_dir, exist_ok=True)
                build_checkpoint(
                    os.path.join(kv_dir, "model.safetensors"),
                    48 * 1024 * 1024, hidden=512, inter=1408, vocab=8192)
            return measure_kv_store(kv_dir, base)

        guard("kv_store", kv_leg, 120.0)

        # int8 weight-only serving: per-step weight reads halve, so decode
        # (HBM-bound) speeds up — the quantize flag the serve sidecar ships
        def int8_serving() -> dict:
            source = _blob_source(client, "library/bench", desc)
            try:
                loaded_q, _ = load_safetensors(
                    source, mesh, LLAMA_RULES, quantize="int8"
                )
            finally:
                if hasattr(source, "close"):
                    source.close()
            q = measure_serving(
                loaded_q, mesh, device_kind, decode_only=True,
                weight_bytes_per_param=1,  # int8 matmuls (embed stays bf16)
            )
            return {
                "int8_decode_tokens_per_s": q.get("decode_tokens_per_s"),
                "int8_decode_speedup": (
                    round(q["decode_tokens_per_s"] / dtps, 2)
                    if q.get("decode_tokens_per_s") and dtps else None
                ),
            }

        guard("int8_serving", int8_serving, 120.0)
    except Exception as e:
        import traceback

        traceback.print_exc(file=sys.stderr)
        leg_errors["fatal"] = repr(e)[:500]
    finally:
        # the one JSON line ALWAYS prints: a partial capture with named
        # holes beats rc 124 with nothing
        out["timed_out_legs"] = timed_out
        if leg_errors:
            out["leg_errors"] = leg_errors
        out["bench_budget_s"] = budget.total
        out["bench_elapsed_s"] = round(budget.elapsed(), 1)
        print(json.dumps(out))
        if srv is not None:
            srv.terminate()  # before rmtree: never delete a live server's data
        shutil.rmtree(workdir, ignore_errors=True)
    # ...but a capture with holes is not a passing one
    return 1 if (leg_errors or timed_out) else 0


def tiny_main() -> int:
    """``bench.py --tiny``: the CPU proxy capture (``JAX_PLATFORMS=cpu``),
    one JSON line. Three stages: the fleet leg on a tiny synthetic llama
    (``fleet_throughput_scaling`` / ``sticky_hit_ratio`` /
    ``failover_recovery_ms``, ISSUE 8), the stream-continuation drill
    (``tokens_lost`` == 0 / ``continuation_gap_ms``, ISSUE 12), then
    the compiled-program registry
    acceptance (ISSUE 11) against a real registry subprocess — a
    bundle-warm second process's compile leg vs the cold publisher's
    (``program_warm_compile_ratio``, pass <= 0.5), and the lifecycle
    pool's swap-in time for a manifest with vs without programs
    (``ttft_swap_cold_ms`` vs ``ttft_swap_cold_ms_programs``)."""
    workdir = tempfile.mkdtemp(prefix="modelx-fleet-tiny-")
    srv = None
    try:
        import jax

        from modelx_tpu.dl import safetensors as st
        from modelx_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        st.write_safetensors(
            os.path.join(workdir, "model.safetensors"),
            {k: np.asarray(v) for k, v in params.items()},
        )
        out: dict = {"metric": "fleet_throughput_scaling", "value": None,
                     "unit": "x"}
        out.update(measure_fleet(workdir, pods=3, clients=2,
                                 requests_per_client=3, conversations=4,
                                 turns=12, new_tokens=4, max_seq_len=128))
        out["value"] = out.get("fleet_throughput_scaling")

        # stream-continuation drill (ISSUE 12): seeded mid-stream pod
        # kill behind the router; tokens_lost must read 0
        out.update(measure_continuation(workdir, new_tokens=12,
                                        max_seq_len=128))

        # per-request latency breakdown (ISSUE 13): the engine's phase
        # timeline must account for >= 90% of client wall time, and the
        # TTFT queue-vs-compute split is the scaling signal
        out.update(measure_latency_breakdown(workdir, new_tokens=8,
                                             max_seq_len=128))

        # observability overhead (ISSUE 15): the always-on flight
        # recorder + device telemetry must cost < 2% of wall time, and
        # the measured-vs-reserved HBM accounting must be present
        out.update(measure_obs_overhead(workdir, new_tokens=8,
                                        max_seq_len=128))

        # tensor-parallel serving (ISSUE 16): continuous decode on a
        # forced-host dp=2,tp=2 mesh vs the dp=1 baseline — per-device
        # ratio passes >= 0.7, and the dp=1 engine must stay byte-exact
        out.update(measure_sharded_serving(workdir))

        # fused-sampling decode leg (ISSUE 17): mixed sampled/greedy
        # clients through the fused on-device sampler vs the all-greedy
        # baseline (sampled_vs_greedy_decode_ratio), the sampling
        # microbench at the engine's logits shape (sampling_ms_p50/p99
        # vs sampling_sort_ms_p50), and the pad-fraction accounting
        from modelx_tpu.parallel.mesh import make_mesh

        out.update(measure_decode_pipelined(
            params, make_mesh("dp=1"), None, clients=3, chunk=4,
            new_tokens=24, prompt_len=8, max_len=96))

        # --- compiled-program registry (ISSUE 11), CPU proxy ---
        # bench-shaped small checkpoint, not LlamaConfig.tiny: the ratio
        # should be measured on a model whose trace+compile is non-trivial
        prog_dir = os.path.join(workdir, "prog")
        os.makedirs(prog_dir, exist_ok=True)
        build_checkpoint(os.path.join(prog_dir, "model.safetensors"),
                         16 * 1024 * 1024, hidden=512, inter=1408, vocab=8192)
        srv, base = start_registry(workdir)
        push_checkpoint(base, "library/prog",
                        os.path.join(prog_dir, "model.safetensors"))
        env = dict(os.environ,
                   PYTHONPATH=os.path.dirname(os.path.abspath(__file__)),
                   JAX_PLATFORMS="cpu")

        # tiered-state swap (ISSUE 18): cold vs host-tier vs disk-tier
        # swap-in through the pool, live traffic on a neighbor model.
        # The bar: host promotion < 0.5x the cold swap. (The program leg
        # below re-sets ttft_swap_cold_ms with its own cold baseline;
        # the ratio here is computed against the tier leg's own.)
        tier = measure_tier_swap(base, workdir)
        out.update(tier)
        out["tier_swap_host_ratio"] = (
            round(tier["ttft_swap_host_ms"] / tier["ttft_swap_cold_ms"], 3)
            if tier["ttft_swap_cold_ms"] else None
        )

        # registry-outage leg (ISSUE 19): kill the registry under live
        # traffic, swap a model in offline off the pinned manifest + blob
        # cache, restart, drain the publish outbox. The acceptance bar:
        # outage_dropped_requests == 0.
        out.update(measure_registry_outage(workdir))

        # content-addressed prefix-KV leg (ISSUE 20): pod 1 streams a hot
        # shared prompt past the publish threshold and attaches its prefix
        # KV to the version; a fresh pod 2 installs it from the registry
        # and answers that prompt with a suffix-only prefill
        # (kv_warm_ttft_ratio, pass < 0.6)
        out.update(measure_kv_store(workdir, base, dtype="float32",
                                    prompt_len=48, suffix_len=8,
                                    new_tokens=4, max_seq_len=128))

        from modelx_tpu.dl.blob_cache import BlobCache
        from modelx_tpu.dl.serve import (ModelServer, ServerSet,
                                         cold_cache_dir, enable_compile_cache)

        swap_root = os.path.join(workdir, "prog-swap")
        sset = ServerSet({"c": ModelServer(workdir, name="c")}, default="c",
                         allow_admin_load=True,
                         staging_root=os.path.join(swap_root, "staging"))
        sset.pool.blob_cache = BlobCache(os.path.join(swap_root, "blobcache"))
        sset.load_all()
        toks = np.ones((1, 16), np.int32)

        def one_swap(tag: str) -> float:
            # emptied compile cache per swap: every swap is a cold pod boot;
            # only the manifest's program bundle may warm the compile leg
            enable_compile_cache(cold_cache_dir(f"swap-{tag}"))
            t0 = time.monotonic()
            sset.pool.request_load("b", ref=f"{base}/library/prog@v1",
                                   wait=True)
            state = sset.pool.states()["b"]
            if state["state"] != "READY":
                raise RuntimeError(f"swap load of b landed {state}")
            sset.servers["b"].forward_argmax(toks)  # first token, AOT shape
            dt = (time.monotonic() - t0) * 1e3
            sset.pool.request_unload("b", wait=True)
            return dt

        # prime swap (unscored) fills the blob cache, so the two scored
        # swaps are equally byte-warm and differ ONLY in program bundles
        one_swap("prime")
        plain_ms = one_swap("plain")  # manifest holds no programs yet

        # pod-1-pays: the cold ttft child publishes its surface, the warm
        # child proves a second process boots compile-warm off the registry
        out.update(measure_program_store(base, "library/prog",
                                         child_timeout_s=300.0, env=env))

        # full-surface publish (the `modelx programs push` flow) so the
        # pool's warmup shapes are covered, then the with-programs swap
        p = subprocess.run(
            [sys.executable, "-m", "modelx_tpu.cli", "programs", "push",
             f"{base}/library/prog@v1"],
            capture_output=True, text=True, env=env, timeout=300)
        if p.returncode != 0:
            raise RuntimeError(f"programs push failed: {p.stderr[-2000:]}")
        progs_ms = one_swap("programs")
        out["ttft_swap_cold_ms"] = round(plain_ms, 1)
        out["ttft_swap_cold_ms_programs"] = round(progs_ms, 1)
        out["program_swap_ratio"] = (
            round(progs_ms / plain_ms, 3) if plain_ms else None
        )
        print(json.dumps(out))
        return 0
    finally:
        if srv is not None:
            srv.terminate()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--leg":
        sys.exit(leg_main(sys.argv[2], sys.argv[3], sys.argv[4], sys.argv[5]))
    if len(sys.argv) > 1 and sys.argv[1] == "--tiny":
        sys.exit(tiny_main())
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded-child":
        sys.exit(sharded_child_main(sys.argv[2]))
    sys.exit(main())
