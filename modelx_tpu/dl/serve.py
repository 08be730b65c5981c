"""JAX serving sidecar: the container the pod spec runs next to the volume.

Replaces the reference deployment's GPU serving container (BASELINE.json
north_star). Loads one or more checkpoints (multi-tenant: BASELINE config #5
is concurrent pull+serve of 4 models) onto a mesh, compiles the
forward/decode functions, and serves:

    GET  /healthz               readiness (200 once every model is compiled;
                                503 while loading/draining/engine-restarting)
    GET  /livez                 liveness (503 only when the serving engine is
                                circuit-broken -> k8s restarts the pod)
    GET  /metrics               load + inference counters (all models)
    GET  /v1/models             model inventory + per-model stats
    GET  /v1/trace              span summary (utils/trace.py)
    POST /v1/profile            {"seconds": N} -> device-level jax profiler
                                trace written to trace_dir
    POST /v1/forward            default model      {"tokens": [[...]]}
    POST /v1/generate           default model      + {"max_new_tokens": N,
                                "temperature": t, "top_k": k, "top_p": p,
                                "seed": s}  (temperature 0 = greedy)
    POST /v1/{model}/forward    named model
    POST /v1/{model}/generate   named model
    GET  /admin/models          lifecycle states + HBM accounting
    POST /admin/models          runtime load: {"name", "ref"|"model_dir"}
    DELETE /admin/models/{name} drain + unload (dl/lifecycle.py; the
                                mutations need --allow-admin-load, the
                                surface honors --admin-token bearer auth)

Model family (llama / mixtral / gpt2 / bert) is detected from checkpoint
tensor names (dl/families.py) — the checkpoint is self-describing, no
config.json needed. Token IDs in, token IDs out by default; when the model
directory carries a ``tokenizer.json`` (pulled alongside the weights),
``/v1/generate`` also takes ``{"text": "..."}`` and returns the decoded
continuation.

Compile latency: a persistent XLA compilation cache is on by default
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.cache/xla`` —
see :func:`enable_compile_cache`) so a sidecar restart skips recompilation
— the TTFT budget (BASELINE: p50 < 500 ms) has no room for a cold pjit.
"""

from __future__ import annotations

import base64
import glob
import itertools
import json
import logging
import os
import re
import shutil
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import jax.numpy as jnp
import numpy as np

from modelx_tpu.dl import families as fam
from modelx_tpu.dl import kv_layout
from modelx_tpu.dl.serving_errors import (
    ATTEMPT_HEADER,
    DEADLINE_HEADER,
    PRIORITY_HEADER,
    REQUEST_ID_HEADER,
    RESUME_EMITTED_HEADER,
    RESUME_SEED_HEADER,
    DeadlineExceededError,
    MalformedResumeError,
    ModelLoadingError,
    ResumeExhaustedError,
    ServingError,
    client_identity as _client_hash,
    deadline_kwargs,
    mint_request_id,
    parse_attempt,
    parse_deadline_ms,
    parse_priority,
    parse_request_id,
    parse_resume,
    timing_headers,
)
from modelx_tpu.parallel.mesh import make_mesh
from modelx_tpu.utils import accesslog, devmem, promexp, trace, tswheel

logger = logging.getLogger("modelx.serve")

# /v1/generate decode budget an unauthenticated client may request; each
# distinct max_new_tokens value also compiles a new decode program, so the
# cap bounds both HBM for the KV cache and compile-cache churn.
DEFAULT_MAX_NEW_TOKENS_LIMIT = 1024
# /v1/profile holds the handler thread and the profiler for this long at most
MAX_PROFILE_SECONDS = 60
# /admin/profile captures kept on disk; older ones are pruned after each
# capture so the on-demand profiler can never fill the pod's disk
MAX_PROFILE_CAPTURES = 4

_UNSET = object()  # tokenizer not probed yet (absent is cached as None)


class ChatTemplateRejected(Exception):
    """A model chat template called raise_exception(msg) on the request's
    messages — a CLIENT error (the OpenAI layer maps it to 400)."""


_EOS_CANDIDATES = (
    # the end-of-sequence spellings of the supported families' tokenizers:
    # llama2/mistral, gpt2/gpt-j, llama3, chatml/qwen2, llama3 base, gemma
    "</s>", "<|endoftext|>", "<|eot_id|>", "<|im_end|>", "<|end_of_text|>",
    "<eos>", "<|end|>",
)


def _eos_from_config(model_dir: str, tok) -> tuple[int, ...] | None:
    """Explicit end-of-sequence ids from the checkpoint's sidecar configs
    (pulled alongside the weights like tokenizer.json). Precedence follows
    the HF convention: generation_config.json > config.json eos_token_id,
    then tokenizer_config.json's eos_token spelling resolved through the
    vocab. None = no explicit declaration (callers fall back to the
    well-known-spelling probe). An explicit id beats the probe because
    vocabs can carry probe spellings as NON-eos specials (e.g. chatml
    models where <|endoftext|> is pad while <|im_end|> ends turns)."""

    def ids_from(val) -> tuple[int, ...] | None:
        if isinstance(val, bool):
            return None
        if isinstance(val, int):
            return (int(val),)
        if (
            isinstance(val, list) and val
            and all(isinstance(v, int) and not isinstance(v, bool) for v in val)
        ):
            return tuple(dict.fromkeys(int(v) for v in val))
        return None

    for fname in ("generation_config.json", "config.json"):
        path = os.path.join(model_dir, fname)
        if not os.path.isfile(path):
            continue
        try:
            with open(path, encoding="utf-8") as f:
                got = ids_from(json.load(f).get("eos_token_id"))
        except (OSError, ValueError):
            continue  # malformed sidecar must not kill the tokenizer load
        if got:
            return got
    path = os.path.join(model_dir, "tokenizer_config.json")
    if os.path.isfile(path):
        try:
            with open(path, encoding="utf-8") as f:
                eos = json.load(f).get("eos_token")
        except (OSError, ValueError):
            eos = None
        if isinstance(eos, dict):  # added-token object form
            eos = eos.get("content")
        if isinstance(eos, str):
            tid = tok.token_to_id(eos)
            if tid is not None:
                return (int(tid),)
    return None


class _Tokenizer:
    """list[int]-in/str-out facade over a raw ``tokenizers.Tokenizer``.

    ``eos_override``: explicit eos ids from the model's config sidecars
    (_eos_from_config); when present the spelling probe is skipped."""

    def __init__(self, tok, eos_override: tuple[int, ...] | None = None) -> None:
        self._tok = tok
        self._eos: tuple[int, ...] | None = eos_override

    def encode(self, text: str, add_special_tokens: bool = True) -> list[int]:
        # chat-template renders carry their own special tokens (bos etc.),
        # so that path encodes raw — the HF apply_chat_template convention
        return self._tok.encode(text, add_special_tokens=add_special_tokens).ids

    def decode(self, ids) -> str:
        # keep special tokens: clients watch for e.g. "</s>" in the text,
        # and tokenizers' own default (skip=True) would silently strip them
        return self._tok.decode(list(ids), skip_special_tokens=False)

    def eos_ids(self) -> tuple[int, ...]:
        """End-of-sequence token ids: the config sidecars' explicit
        declaration when the model ships one, otherwise discovered from
        the vocab's well-known spellings (tokenizer.json alone carries no
        EOS marker). Empty = unknown: callers then keep budget-only
        decode; ``ignore_eos`` is the per-request escape hatch."""
        if self._eos is None:
            ids = []
            for cand in _EOS_CANDIDATES:
                tid = self._tok.token_to_id(cand)
                if tid is not None:
                    ids.append(int(tid))
            self._eos = tuple(dict.fromkeys(ids))
        return self._eos


# The one fallback when JAX_COMPILATION_CACHE_DIR is unset: a fixed path
# inside the checkout. The directory is how one run finds another's entries
# (a path that moves never hits), so nothing derives it from a pid, a clock
# or a temporary name.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache", "xla",
)

_compile_cache_dir = ""  # set by enable_compile_cache; "" = cold every start

# this process's persistent-cache traffic, counted from jax's own monitoring
# events: requests = compiles that consulted the cache, hits = executables
# read back, misses = entries written after a real compile
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
# ... and where a program's time goes, from jax's duration events: tracing
# the python function to a jaxpr, lowering it to MLIR, and the backend's
# compile-or-read-back (which contains the persistent cache's retrieval).
# What a "cache hit" costs beyond these — loading the executable, its first
# run — is the residue a caller's own clock sees.
_CACHE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
}
_cache_counts = {"requests": 0, "hits": 0, "misses": 0, "programs": 0,
                 "trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0,
                 "retrieval_s": 0.0}
_cache_counts_lock = threading.Lock()


def _count_cache_event(event: str, **_kwargs) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        with _cache_counts_lock:
            _cache_counts[key] += 1


def _add_cache_duration(event: str, duration: float, **_kwargs) -> None:
    key = _CACHE_DURATIONS.get(event)
    if key is not None:
        with _cache_counts_lock:
            _cache_counts[key] += duration
            if key == "backend_compile_s":
                _cache_counts["programs"] += 1


jax.monitoring.register_event_listener(_count_cache_event)
jax.monitoring.register_event_duration_secs_listener(_add_cache_duration)


def compile_cache_dir() -> str:
    """The enabled persistent cache dir ("" when not enabled) — warmup paths
    key their serialized-executable (aot_cache) artifacts under it."""
    return _compile_cache_dir


def compile_cache_stats() -> dict:
    """``{"dir", "requests", "hits", "misses", "programs", "trace_s",
    "lower_s", "backend_compile_s", "retrieval_s"}`` — what went through
    jax — and ``{"store_hits", "store_misses", "store_load_s",
    "store_read_s", "store_deserialize_s", "store_write_s", "store_bytes",
    "store_bytes_read"}`` — what the engine's executable store
    (dl/aot_cache.py) served without it — for /metrics: whether a restart
    found its programs, and what each cost, is read here, not inferred from
    timing."""
    from modelx_tpu.dl import aot_cache

    with _cache_counts_lock:
        return {"dir": _compile_cache_dir,
                **{k: round(v, 6) if isinstance(v, float) else v
                   for k, v in _cache_counts.items()},
                **aot_cache.store_stats()}


def cold_cache_dir(leg: str) -> str:
    """An EMPTY cache directory for a measurement whose meaning is a cold
    start (the program-store legs): a fixed name under the default cache,
    cleared on every call rather than renamed."""
    path = os.path.join(DEFAULT_COMPILE_CACHE_DIR, leg)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def enable_compile_cache(path: str = "") -> None:
    """Persistent XLA compilation cache (idempotent).

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
    it — jax reads the variable itself, and this function then points the
    cache nowhere else — otherwise :data:`DEFAULT_COMPILE_CACHE_DIR`. An
    explicit ``path`` is for :func:`cold_cache_dir` legs only."""
    global _compile_cache_dir
    path = (path or os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or DEFAULT_COMPILE_CACHE_DIR)
    try:
        os.makedirs(path, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != path:
            from jax.experimental.compilation_cache import compilation_cache

            jax.config.update("jax_compilation_cache_dir", path)
            # jax opens its cache once, at the first compile: a process that
            # already compiled keeps writing to the old directory otherwise
            compilation_cache.reset_cache()
        # no min-compile-time floor: the program store (dl/program_store.py)
        # ships this cache's executables fleet-wide, and a program under
        # the default 1 s threshold would stay cold on EVERY pod — small
        # entries cost bytes once, a fleet of retraces costs TTFT always
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # keep the cache-dir PATH out of the cache key: with XLA side
        # caches on, jax points xla_gpu_per_fusion_autotune_cache_dir at a
        # subdir of `path`, which lands in the hashed compile options — so
        # two pods with different cache dirs (or the bench's fresh per-leg
        # dirs) could never hit each other's shipped executables
        jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
        _compile_cache_dir = path
    except OSError as e:  # cache is an optimization, never fatal
        logger.warning("compile cache unavailable at %s: %s", path, e)


class ModelServer:
    """One loaded model: params on the mesh + compiled entry points."""

    def __init__(
        self,
        model_dir: str,
        mesh_spec: str = "",
        dtype: str = "bfloat16",
        config=None,
        max_seq_len: int = 2048,
        mesh=None,
        name: str = "default",
        quantize: str | None = None,
        speculative_k: int = 0,
        lora_dir: str = "",
        prefix_cache_size: int = 0,
        prefix_cache_max_bytes: int = 0,
    ) -> None:
        self.name = name
        self.model_dir = model_dir
        self.quantize = quantize
        self.lora_dir = lora_dir
        # > 0 keeps the prefill KV of the last N single-row stream prompts
        # on device (models/decode.PrefixKVCache): multi-turn chats that
        # re-send their history prefill only the new suffix.
        # prefix_cache_max_bytes additionally caps the entries' actual KV
        # bytes — an entry count alone over-commits HBM for long prefixes
        self._prefix_cache = None
        if int(prefix_cache_size) > 0:
            from modelx_tpu.models.decode import PrefixKVCache

            self._prefix_cache = PrefixKVCache(
                int(prefix_cache_size), max_bytes=int(prefix_cache_max_bytes)
            )
        # > 0 turns on prompt-lookup speculative decoding for single-row
        # greedy requests (models/speculative.py): token-exact, fewer
        # device steps on self-repeating continuations
        self.speculative_k = int(speculative_k)
        self._spec_decoder = None
        self.mesh = mesh if mesh is not None else (
            make_mesh(mesh_spec) if mesh_spec else make_mesh(f"dp={len(jax.devices())}")
        )
        self.dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        self.max_seq_len = max_seq_len
        self.ready = False
        # set by ServerSet.load_all when this model's load crashed: the
        # pool marks it FAILED, /healthz reports the degraded set, and the
        # reason is visible in GET /v1/models — the OTHER tenants keep
        # serving instead of the whole process dying
        self.load_error: str | None = None
        self.stats: dict = {"requests": 0, "tokens_generated": 0}
        self.cfg = config
        self.family: fam.Family | None = None
        self.params: dict | None = None
        self._forward_aot: dict[tuple, object] = {}
        self._param_sds: dict | None = None  # abstract params, set by load()
        self._decoders: dict[int, object] = {}  # chunk_size -> ChunkedDecoder
        self._score_progs: dict[tuple, object] = {}  # (len bucket, top_k)
        self._decoders_lock = threading.Lock()
        # separate lock: tokenizer loading must not block streaming-decoder
        # creation (unrelated caches)
        self._tokenizer_lock = threading.Lock()
        self._tokenizer: object = _UNSET
        self._chat_template: object = _UNSET

    # the shape the dynamic batcher pads a lone first request to (seq to a
    # multiple of 16, batch to a power of two): precompiling it during load
    # means the first real request meets a ready executable — on a pod
    # that answers with the forward program (direct and --dynamic-batch
    # paths, encoders). A pod that serves through the continuous engine
    # never calls it and warms the engine's chunk program instead (load)
    WARMUP_TOKEN_SHAPES = ((1, 16),)

    def load(self, engine_at_load=None) -> dict:
        """Load every *.safetensors under model_dir onto the mesh. The
        checkpoint headers fully determine the architecture, so the
        programs this pod's first request will run are fetched on a side
        thread WHILE the weight bytes stream — a deploy pays max(load,
        compile), not their sum (TTFT budget, BASELINE.md).

        Which programs: ``engine_at_load(server, allocate)``
        (``ServerSet.engine_at_load`` at boot) returns the continuous engine
        when the pod will answer through one. It is built here, as soon as
        the headers are read, the side thread fetches its
        request-independent chunk program
        (``ContinuousBatcher.chunk_warmer``), and its KV cache is allocated
        once the weights are placed. Where it returns None or is not given
        (no --continuous-batch, an encoder family, a model loaded at run
        time) the side thread AOT-compiles the forward for
        WARMUP_TOKEN_SHAPES."""
        from modelx_tpu.dl.loader import LocalFileSource, load_safetensors
        from modelx_tpu.dl.safetensors import read_header_from_file

        # a boot-time load on the thread that opened start-up's `load` stage
        # splits it (utils/trace.Startup.sub): install, headers, plan, shards,
        # kv_alloc, finish; any other load's calls return at once
        trace.startup.sub("install")
        with trace.span("serve.load", model=self.name, dir=self.model_dir):
            t0 = time.monotonic()
            paths = sorted(glob.glob(os.path.join(self.model_dir, "*.safetensors")))
            if not paths:
                raise FileNotFoundError(f"no safetensors under {self.model_dir}")
            # program-store bundles pulled alongside the weights install
            # into the AOT cache BEFORE any compile below — the warmup
            # thread then warm-starts from another pod's exports. Purely
            # an optimization: any failure just compiles cold.
            cache_dir = compile_cache_dir()
            if cache_dir:
                from modelx_tpu.dl import program_store

                try:
                    pstats = program_store.install_from_dir(
                        self.model_dir, cache_dir, mesh=self.mesh
                    )
                    if pstats["bundles"] or pstats["skipped"]:
                        self.stats["programs"] = {
                            k: pstats[k]
                            for k in ("bundles", "installed", "present", "skipped")
                        }
                except Exception as e:
                    logger.warning("program bundle install failed: %s", e)
            # detect the family from the headers so the right partition rules
            # apply from the first byte fetched
            infos_all: dict = {}
            trace.startup.sub("headers")
            with trace.span("headers", files=len(paths)):
                for path in paths:
                    infos, _ = read_header_from_file(path)
                    infos_all.update(infos)
            trace.startup.sub("plan")
            self.family = fam.detect(list(infos_all))
            # mirror the loader's expert fusion so header-derived shapes
            # match the params it will deliver (stacked [E, ...] experts)
            from modelx_tpu.dl.loader import fuse_expert_tensors

            infos_all = fuse_expert_tensors(infos_all, self.family.rules)
            if self.cfg is None:
                # reconciled with the pulled config.json sidecar: rope_theta
                # overrides apply; unimplemented rope_scaling (phi-3-*-128k
                # longrope etc.) refuses BEFORE the weights stream to HBM
                self.cfg = fam.config_for(
                    self.family, fam.abstract_params(infos_all), self.model_dir)
            # quantized included: abstract_params mirrors the loader's int8
            # transform (QTensor pytrees of structs), so int8 deploys overlap
            # load and compile like bf16 ones
            sds = fam.abstract_params(
                infos_all, self.family.rules, self.mesh, quantize=self.quantize
            )
            # kept for the program store: surface keys (publish) and score
            # program AOT routing both need the abstract params later
            self._param_sds = sds
            engine = engine_at_load(self, False) if engine_at_load else None
            compile_thread = threading.Thread(
                target=self._warm_programs,
                args=(sds, None if engine is None else engine.chunk_warmer(sds)),
                daemon=True,
            )
            compile_thread.start()
            params: dict = {}
            total = 0
            # the loader's clock (dl/loader._OverlapClock), summed over the
            # files: wall seconds with a read in flight, with a device_put in
            # flight, with both; with neither nor host work between them;
            # with a fetch thread held up and no read; with host work alone;
            # and from the last read's end to the call's
            split = dict.fromkeys(("fetch_busy", "device_put", "overlap", "idle",
                                   "backpressure", "assemble", "drain"), 0.0)
            in_calls = 0.0
            copied = 0  # bytes the host wrote twice on their way (LoadStats)
            trace.startup.sub("shards")
            with trace.span("shards", files=len(paths)) as shards:
                for path in paths:
                    src = LocalFileSource(path)
                    try:
                        arrays, stats = load_safetensors(
                            src, self.mesh, self.family.rules, quantize=self.quantize
                        )
                    finally:
                        src.close()
                    params.update(arrays)
                    total += stats.bytes_to_device
                    in_calls += stats.total_seconds
                    copied += stats.assemble_copied_bytes
                    for key in split:
                        split[key] += getattr(stats, f"{key}_seconds")
            # between two files' calls nothing is read or put either
            split["idle"] += max(0.0, shards["duration_s"] - in_calls)
            self.params = params
            trace.startup.sub("kv_alloc")
            if engine is not None:
                # behind the weights, where a lazily built engine always had
                # it: allocated before them, the KV cache moved the decode
                # cell's tokens/s by -0.6 and -2.1 % (PERF.md, PR 26)
                engine_at_load(self, True)
            trace.startup.sub("finish")
            if self.lora_dir:
                from modelx_tpu.dl import lora

                # merge BEFORE compiling: the jitted programs close over the
                # merged weights, and merge-into-int8 is rejected upstream
                with trace.span("serve.lora", model=self.name, dir=self.lora_dir):
                    self.params = lora.merge_adapter(self.params, self.lora_dir)
                self.stats["lora_dir"] = self.lora_dir
            seconds = time.monotonic() - t0
            from modelx_tpu.parallel.mesh import mesh_str, weight_shard_factor

            self.stats["mesh"] = mesh_str(self.mesh)
            self.stats["mesh_devices"] = int(self.mesh.size)
            # how many ways the weight bytes divide across devices — what
            # load_bytes must be divided by to get the per-device footprint
            self.stats["weight_shard_factor"] = weight_shard_factor(self.mesh)
            self.stats["family"] = self.family.name
            self.stats["load_seconds"] = round(seconds, 3)
            for key, value in split.items():
                self.stats[f"load_{key}_seconds"] = round(value, 3)
            self.stats["load_assemble_copied_bytes"] = copied
            self.stats["load_shards_seconds"] = round(shards["duration_s"], 3)
            self.stats["load_shard_files"] = len(paths)
            self.stats["load_bytes"] = total
            self.stats["load_gbps"] = round(total / max(seconds, 1e-9) / 1e9, 3)
            from modelx_tpu import native

            # whether the loader's fetch/hash hot loop ran on the C++ engine
            # or on its pure-Python stand-in (native.lib() warns when it is
            # the latter; this says it where a dashboard looks)
            self.stats["native_io"] = native.available()
            self._compile()
            if engine is None:
                with trace.span("compile_join"):
                    compile_thread.join()
            # else: the chunk program finishes behind the first admit — a
            # dispatch waits for it (aot_cache.StoredProgram) — because
            # beside the load its read-back outlasts the load by seconds that
            # ready would otherwise wait for, and the admit program's own
            # read-back covers them (PERF.md, PR 26)
            self.stats["ready_seconds"] = round(time.monotonic() - t0, 3)
            self.ready = True
            self._install_kv_bundles()
        return dict(self.stats)

    def _install_kv_bundles(self) -> None:
        """Install prefix-KV bundles pulled next to the weights
        (``.kv-*.tar``, dl/kv_store.py) into the prefix cache — AFTER the
        family/compile so ``decode_fns`` can validate the leaf layout.
        Purely an optimization: any failure just prefills cold."""
        if self._prefix_cache is None:
            return
        from modelx_tpu.dl import kv_store

        try:
            kstats = kv_store.install_for_server(self, self.model_dir)
        except Exception as e:
            logger.warning("kv bundle install failed: %s", e)
            return
        if kstats and (kstats["bundles"] or kstats["skipped"]):
            self.stats["kv"] = {
                k: kstats[k]
                for k in ("bundles", "installed", "present", "skipped")
            }

    def load_from_tier(self, promo) -> dict:
        """Materialize a demoted model from a tier promotion
        (dl/tiers.Promotion) instead of the checkpoint files: device_put
        each host leaf straight to its recorded NamedSharding placement —
        no fetch, no safetensors parse, no sharding-plan walk. The compile
        overlap works exactly as in ``load`` (and usually hits the AOT
        cache outright, since this content compiled here before)."""
        with trace.span("serve.load_from_tier", model=self.name,
                        tier=promo.tier):
            t0 = time.monotonic()
            self.family = promo.family
            self.cfg = promo.cfg
            self._param_sds = promo.param_sds
            compile_thread = None
            if promo.param_sds is not None:
                compile_thread = threading.Thread(
                    target=self._precompile_warmup, args=(promo.param_sds,),
                    daemon=True,
                )
                compile_thread.start()
            leaves = []
            for arr, sharding in zip(promo.leaves, promo.shardings):
                if sharding is not None:
                    leaves.append(jax.device_put(arr, sharding))
                else:
                    leaves.append(jax.device_put(arr))
            self.params = jax.tree_util.tree_unflatten(promo.treedef, leaves)
            seconds = time.monotonic() - t0
            from modelx_tpu.parallel.mesh import mesh_str, weight_shard_factor

            self.stats["mesh"] = mesh_str(self.mesh)
            self.stats["mesh_devices"] = int(self.mesh.size)
            self.stats["weight_shard_factor"] = weight_shard_factor(self.mesh)
            self.stats["family"] = self.family.name
            self.stats["load_seconds"] = round(seconds, 3)
            self.stats["load_bytes"] = promo.nbytes
            self.stats["load_gbps"] = round(
                promo.nbytes / max(seconds, 1e-9) / 1e9, 3)
            self.stats["tier"] = promo.tier
            self._compile()
            if compile_thread is not None:
                compile_thread.join()
            self.stats["ready_seconds"] = round(time.monotonic() - t0, 3)
            self.ready = True
            self._install_kv_bundles()
        return dict(self.stats)

    def _warm_programs(self, sds: dict, engine_warm=None) -> None:
        """A load's side thread: ``engine_warm`` (the engine's chunk
        warmer) where the pod serves through the continuous engine, else
        the forward for the warmup token shapes. ``startup`` keeps how
        many engine programs were delivered — 0 says the forward path."""
        programs = 0
        if engine_warm is None:
            self._precompile_warmup(sds)
        else:
            with trace.span("serve.load/engine_warm", model=self.name) as rec:
                programs = rec["programs"] = engine_warm()
            trace.startup.note("engine_warm", rec["duration_s"])
        trace.startup.count("engine_warm_programs", programs)

    def _precompile_warmup(self, sds: dict) -> None:
        """AOT-compile the forward for the warmup token shapes (overlapped
        with the weight load). Failures only lose the warm start."""
        for shape in self.WARMUP_TOKEN_SHAPES:
            try:
                with trace.span("serve.precompile", model=self.name, shape=str(shape)):
                    compiled = fam.precompile_forward(
                        self.family, self.cfg, sds, shape,
                        mesh=self.mesh, mode="argmax_all",
                        cache_dir=compile_cache_dir(),
                    )
                self._forward_aot[shape] = compiled
            except Exception as e:
                logger.warning("precompile %s failed (cold first request): %s", shape, e)

    def _compile(self) -> None:
        cfg, mesh, family = self.cfg, self.mesh, self.family
        with trace.span("serve.compile", model=self.name, family=family.name):
            self._forward = jax.jit(
                lambda p, t: family.forward(p, t, cfg, mesh=mesh)
            )

    def forward_argmax(self, tokens: np.ndarray) -> np.ndarray:
        with trace.span("serve.forward", model=self.name, batch=int(tokens.shape[0])):
            aot = self._forward_aot.get(tuple(tokens.shape))
            if aot is not None:
                return np.asarray(aot(self.params, jnp.asarray(tokens, jnp.int32)))
            out = self._forward(self.params, jnp.asarray(tokens, jnp.int32))
            return np.asarray(jnp.argmax(out, axis=-1))

    def forward_logits(self, tokens: np.ndarray, positions: np.ndarray):
        """One cache-less forward, read twice: the per-position argmax that
        ``forward_argmax`` gives, and the float32 logits [B, len(positions),
        V] at ``positions``, where a reference is to be held against them."""
        with trace.span("serve.forward_logits", model=self.name, batch=int(tokens.shape[0])):
            out = self._forward(self.params, jnp.asarray(tokens, jnp.int32))
            return (np.asarray(jnp.argmax(out, axis=-1)),
                    np.asarray(out[:, jnp.asarray(positions), :].astype(jnp.float32)))

    def generate(
        self,
        tokens: np.ndarray,
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
    ) -> np.ndarray:
        """Greedy by default; temperature > 0 samples (with optional top-k /
        nucleus cuts and a request seed) via the ragged decode path. With
        --speculative-k, single rows speculate at ANY temperature: greedy
        acceptance is token-exact, sampled acceptance is modified rejection
        (distribution-preserving)."""
        if self.family.generate is None:
            raise ValueError(f"family {self.family.name} is not generative")
        tokens_arr = np.asarray(tokens, np.int32)
        if (
            self.speculative_k > 0
            and tokens_arr.shape[0] == 1
            and self.family.decode_fns is not None
        ):
            with trace.span("serve.generate_spec", model=self.name,
                            new_tokens=max_new_tokens):
                dec = self._speculative_decoder()
                new, stats = dec.generate(
                    self.params, tokens_arr[0].tolist(), max_new_tokens,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    seed=seed,
                )
                self.stats["tokens_generated"] += len(new)
                self._record_spec_stats(stats)
                return np.concatenate(
                    [tokens_arr, np.asarray([new], np.int32)], axis=1
                )
        if temperature > 0:
            if self.family.generate_ragged is None:
                raise ValueError(
                    f"family {self.family.name} does not support sampling"
                )
            b, s = np.asarray(tokens).shape
            with trace.span("serve.generate", model=self.name, new_tokens=max_new_tokens):
                gen = self.generate_ragged(
                    tokens, np.full((b,), s, np.int32), max_new_tokens,
                    temperature=np.full((b,), temperature, np.float32),
                    top_k=np.full((b,), top_k, np.int32) if top_k > 0 else None,
                    top_p=np.full((b,), top_p, np.float32) if top_p < 1.0 else None,
                    # distinct per-row streams: a request asking for B samples
                    # of one prompt gets B different completions
                    seeds=((seed + np.arange(b)) % (2**31)).astype(np.int32),
                )
            self.stats["tokens_generated"] += int(b * max_new_tokens)
            return np.concatenate([np.asarray(tokens, np.int32), gen], axis=1)
        with trace.span("serve.generate", model=self.name, new_tokens=max_new_tokens):
            out = self.family.generate(
                self.params, jnp.asarray(tokens_arr, jnp.int32), self.cfg,
                mesh=self.mesh, max_new_tokens=max_new_tokens,
            )
            self.stats["tokens_generated"] += int(out.shape[0] * max_new_tokens)
            return np.asarray(out)

    def score_logprobs_rows(self, rows, top_k: int = 0) -> list:
        """Per-token log-probabilities for completed generations: a prefill
        over [prompt + generated] and a log-softmax gather — the values the
        decode programs saw when they picked each token (the forward is
        deterministic; no decode-path surgery needed, logprobs requests
        just pay scoring forwards). ``rows`` is [(ids, new_ids), ...]; rows
        sharing a length bucket score as ONE batched device call — a
        request's n samples of one prompt all ride one program. Returns,
        per row, (token_logprobs [m], top_ids [m, top_k], top_logprobs
        [m, top_k]); the top_* pair is None when top_k == 0.

        Programs compile per (16-bucketed length, pow2 batch, top_k) — the
        same shape discipline as every other serving path."""
        from modelx_tpu.models.decode import pad_seq_len

        empty = (
            (np.zeros((0,), np.float32),) + (
                (np.zeros((0, top_k), np.int32), np.zeros((0, top_k), np.float32))
                if top_k else (None, None)
            )
        )
        out: list = [empty] * len(rows)
        groups: dict[int, list[int]] = {}
        for i, (ids, new_ids) in enumerate(rows):
            if new_ids:
                groups.setdefault(pad_seq_len(len(ids) + len(new_ids)), []).append(i)
        for lb, idxs in groups.items():
            bb = 1 << (len(idxs) - 1).bit_length()  # pow2 batch bucket
            key = (lb, bb, int(top_k))
            prog = self._score_progs.get(key)
            if prog is None:
                with self._decoders_lock:
                    prog = self._score_progs.get(key)
                    if prog is None and self._param_sds is not None:
                        # route through the AOT cache (families.precompile_score
                        # shares the inline closure's exact body): warm pods —
                        # and pods that pulled a program bundle — skip the
                        # trace+lower; any failure falls through to the
                        # plain jit below
                        cache_dir = compile_cache_dir()
                        if cache_dir:
                            try:
                                prog = fam.precompile_score(
                                    self.family, self.cfg, self._param_sds,
                                    (bb, lb), top_k=int(top_k), mesh=self.mesh,
                                    cache_dir=cache_dir,
                                )
                                self._score_progs[key] = prog
                            except Exception as e:
                                logger.warning(
                                    "score precompile %s failed (%s); plain jit",
                                    key, e,
                                )
                    if prog is None:
                        family, cfg, mesh = self.family, self.cfg, self.mesh

                        def _score(params, toks, k=int(top_k)):
                            logits = family.forward(params, toks, cfg, mesh=mesh)
                            lp = jax.nn.log_softmax(
                                logits.astype(jnp.float32), axis=-1
                            )  # [B, Lb, V]
                            nxt = jnp.concatenate(
                                [toks[:, 1:], jnp.zeros((toks.shape[0], 1), jnp.int32)],
                                axis=1,
                            )
                            chosen = jnp.take_along_axis(
                                lp, nxt[..., None], axis=-1
                            )[..., 0]  # position j scores token j+1
                            if k:
                                top_lp, top_id = jax.lax.top_k(lp, k)
                                return chosen, top_id, top_lp
                            return chosen, None, None

                        prog = self._score_progs[key] = jax.jit(_score)
            padded = np.zeros((bb, lb), np.int32)
            for r, i in enumerate(idxs):
                ids, new_ids = rows[i]
                full = list(ids) + list(new_ids)
                padded[r, : len(full)] = full
            chosen, top_id, top_lp = prog(self.params, jnp.asarray(padded))
            chosen = np.asarray(chosen)
            if top_k:
                top_id, top_lp = np.asarray(top_id), np.asarray(top_lp)
            for r, i in enumerate(idxs):
                ids, new_ids = rows[i]
                lo, hi = len(ids) - 1, len(ids) + len(new_ids) - 1
                if top_k:
                    out[i] = (chosen[r, lo:hi], top_id[r, lo:hi], top_lp[r, lo:hi])
                else:
                    out[i] = (chosen[r, lo:hi], None, None)
        return out

    def score_logprobs(self, ids: list[int], new_ids: list[int],
                       top_k: int = 0):
        """Single-row convenience over score_logprobs_rows."""
        return self.score_logprobs_rows([(ids, new_ids)], top_k=top_k)[0]

    def _speculative_decoder(self):
        if self._spec_decoder is None:
            with self._decoders_lock:  # double-checked, like the stream decoders
                if self._spec_decoder is None:
                    from modelx_tpu.models.speculative import SpeculativeDecoder

                    fwd, init = self.family.decode_fns(self.cfg, mesh=self.mesh)
                    self._spec_decoder = SpeculativeDecoder(fwd, init, k=self.speculative_k)
        return self._spec_decoder

    def tokenizer(self):
        """The model's tokenizer (``tokenizer.json`` pulled alongside the
        weights — the registry stores tokenizer files as ordinary blobs), or
        None. Loaded lazily: the token-id API never pays the import."""
        if self._tokenizer is _UNSET:
            with self._tokenizer_lock:
                if self._tokenizer is _UNSET:
                    path = os.path.join(self.model_dir, "tokenizer.json")
                    if not os.path.isfile(path):
                        self._tokenizer = None  # genuinely absent: cache it
                    else:
                        try:
                            import tokenizers  # rust core; loads in ms where
                            # transformers' wrapper costs a multi-second import

                            raw = tokenizers.Tokenizer.from_file(path)
                            self._tokenizer = _Tokenizer(
                                raw,
                                eos_override=_eos_from_config(self.model_dir, raw),
                            )
                        except Exception as e:
                            # NOT cached: a missing optional dep or transient
                            # read error must surface as a load failure (and
                            # retry later), not as "no tokenizer.json"
                            raise RuntimeError(
                                f"tokenizer.json exists but failed to load: {e}"
                            ) from e
        return self._tokenizer

    def chat_template(self) -> dict | None:
        """The model's own chat template from ``tokenizer_config.json``
        (pulled alongside the weights like any blob), or None. Returns
        ``{"template": str, "compiled": jinja Template, "bos_token": str,
        "eos_token": str}``. Handles the string form and the named-list
        form (a "default" entry ONLY — silently serving an arbitrary named
        template like "tool_use" would format every chat wrong); special
        tokens may be strings or HF AddedToken dicts. The template is
        compiled ONCE here in a sandboxed environment with the HF
        apply_chat_template conveniences (loop controls, strftime_now).
        Cached under double-checked locking (publishing a half-built state
        would race the first concurrent chat requests into inconsistent
        render-vs-encode decisions); any problem — including a missing
        jinja2 — degrades to None (generic role template) with one
        warning, never a 500 per request."""
        if self._chat_template is _UNSET:
            with self._tokenizer_lock:
                if self._chat_template is _UNSET:
                    self._chat_template = self._load_chat_template()
        return self._chat_template

    def _load_chat_template(self) -> dict | None:
        path = os.path.join(self.model_dir, "tokenizer_config.json")
        if not os.path.isfile(path):
            return None
        try:
            with open(path, encoding="utf-8") as f:
                cfg = json.load(f)
            tpl = cfg.get("chat_template")
            if isinstance(tpl, list):  # [{name, template}, ...]
                by_name = {
                    t.get("name"): t.get("template")
                    for t in tpl if isinstance(t, dict)
                }
                tpl = by_name.get("default")
                if tpl is None and by_name:
                    logger.warning(
                        "tokenizer_config.json has named chat templates %s "
                        "but no 'default'; using the generic role template",
                        sorted(k for k in by_name if k),
                    )
                    return None
            if not (isinstance(tpl, str) and tpl.strip()):
                return None
            try:
                from jinja2.sandbox import ImmutableSandboxedEnvironment
            except ImportError:
                logger.warning(
                    "model ships a chat_template but jinja2 is not "
                    "installed (pip install 'modelx-tpu[text]'); using the "
                    "generic role template"
                )
                return None
            env = ImmutableSandboxedEnvironment(
                trim_blocks=True, lstrip_blocks=True,
                extensions=["jinja2.ext.loopcontrols"],
            )
            # the conveniences HF's apply_chat_template provides and real
            # shipped templates use (llama-3.1 calls strftime_now for its
            # date line); raise_exception surfaces as ChatTemplateRejected
            # so the API layer can map it to a clean 400
            import datetime as _dt

            env.globals["strftime_now"] = (
                lambda fmt: _dt.datetime.now().strftime(fmt)
            )

            def _raise(msg):
                raise ChatTemplateRejected(str(msg))

            env.globals["raise_exception"] = _raise

            def token_str(v) -> str:
                if isinstance(v, dict):  # AddedToken form
                    return str(v.get("content", ""))
                return v if isinstance(v, str) else ""

            return {
                "template": tpl,
                "compiled": env.from_string(tpl),
                "bos_token": token_str(cfg.get("bos_token")),
                "eos_token": token_str(cfg.get("eos_token")),
            }
        except Exception as e:
            logger.warning(
                "tokenizer_config.json unusable for chat templating (%s); "
                "falling back to the generic role template", e,
            )
            return None

    def generate_stream(
        self,
        tokens: np.ndarray,
        max_new_tokens: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
        chunk_size: int = 8,
        stop_token_ids=None,
    ):
        """Yields [B, k] arrays of new tokens as they decode — the transport
        behind streaming /v1/generate. On the plain path k <= chunk_size;
        the speculative path instead emits one chunk per device step (up to
        speculative_k + 1 tokens). Either way the concatenated chunks equal
        the non-streaming result exactly."""
        if self.family.decode_fns is None:
            raise ValueError(f"family {self.family.name} does not support streaming")
        tokens_arr = np.asarray(tokens, np.int32)
        if self.speculative_k > 0 and tokens_arr.shape[0] == 1:
            # single-row stream: speculation's target — chunks flush per
            # device step (accepted run + bonus token). Greedy concatenates
            # to the plain stream token-for-token; sampled streams keep the
            # plain sampler's distribution (modified rejection).
            # (yield from, not return: this function is itself a generator)
            yield from self._generate_stream_speculative(
                tokens_arr, max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, seed=seed,
                stop_token_ids=stop_token_ids,
            )
            return
        dec = self._decoders.get(chunk_size)
        if dec is None:
            with self._decoders_lock:
                dec = self._decoders.get(chunk_size)
                if dec is None:  # double-checked: concurrent first streams
                    from modelx_tpu.models.decode import ChunkedDecoder

                    fwd, init = self.family.decode_fns(self.cfg, mesh=self.mesh)
                    dec = self._decoders[chunk_size] = ChunkedDecoder(
                        fwd, init, chunk_size, prefix_cache=self._prefix_cache
                    )
        from modelx_tpu.models.decode import pad_seq_len

        b, s = tokens_arr.shape
        pad_s = pad_seq_len(s)  # bound compiled shapes like the batcher
        padded = np.zeros((b, pad_s), np.int32)
        padded[:, :s] = tokens_arr
        with trace.span("serve.generate_stream", model=self.name,
                        new_tokens=max_new_tokens):
            # unfiltered requests (top_k 0, top_p off) pass None so the
            # decoder's sampler variant compiles without any filter work —
            # with filters off the mask is all-True, so tokens are
            # byte-identical between the two variants
            filtered = top_k > 0 or top_p < 1.0
            for piece in dec.stream(
                self.params, jnp.asarray(padded), np.full((b,), s, np.int32),
                max_new_tokens,
                temperature=np.full((b,), temperature, np.float32),
                top_k=np.full((b,), top_k, np.int32) if filtered else None,
                top_p=np.full((b,), top_p, np.float32) if filtered else None,
                seeds=((seed + np.arange(b)) % (2**31)).astype(np.int32),
                stop_token_ids=stop_token_ids,
            ):
                # account as chunks leave: a client disconnect must not
                # erase the decode work the device already did
                self.stats["tokens_generated"] += int(piece.size)
                yield piece

    def _record_spec_stats(self, stats: dict) -> None:
        self.stats["spec_device_steps"] = (
            self.stats.get("spec_device_steps", 0) + stats["device_steps"]
        )
        self.stats["spec_accepted"] = (
            self.stats.get("spec_accepted", 0) + stats["accepted"]
        )

    def _generate_stream_speculative(self, tokens: np.ndarray, max_new_tokens: int,
                                     temperature: float = 0.0, top_k: int = 0,
                                     top_p: float = 1.0, seed: int = 0,
                                     stop_token_ids=None):
        dec = self._speculative_decoder()
        stats = {"device_steps": 0, "proposed": 0, "accepted": 0}
        stops = set(stop_token_ids or ())
        try:
            with trace.span("serve.generate_stream_spec", model=self.name,
                            new_tokens=max_new_tokens):
                for piece in dec.stream(self.params, tokens[0].tolist(),
                                        max_new_tokens, stats=stats,
                                        temperature=temperature, top_k=top_k,
                                        top_p=top_p, seed=seed):
                    if stops:
                        from modelx_tpu.models.decode import stop_cut

                        cut = stop_cut(piece[0].tolist(), stops)
                        if cut is not None:  # emit through the stop, then end
                            piece = piece[:, :cut]
                            self.stats["tokens_generated"] += int(piece.size)
                            yield piece
                            return
                    self.stats["tokens_generated"] += int(piece.size)
                    yield piece
        finally:
            # an early-stopped consumer (SSE stop match, client disconnect)
            # closes the generator mid-loop; the device work already
            # happened and must still show up in /metrics
            self._record_spec_stats(stats)

    def generate_ragged(
        self, tokens: np.ndarray, row_lens: np.ndarray, max_new_tokens: int,
        temperature=None, top_k=None, top_p=None, seeds=None,
    ) -> np.ndarray:
        """Ragged-batch decode: right-padded rows [B,S] with per-row real
        lengths. Returns generated tokens only, [B, max_new_tokens]. The
        caller accounts tokens_generated — padded rows and bucket rounding
        here would inflate the counter."""
        if self.family.generate_ragged is None:
            raise ValueError(f"family {self.family.name} has no ragged decode")
        with trace.span(
            "serve.generate_ragged", model=self.name,
            rows=int(tokens.shape[0]), new_tokens=max_new_tokens,
        ):
            out = self.family.generate_ragged(
                self.params, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(row_lens, jnp.int32), self.cfg,
                mesh=self.mesh, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p, seeds=seeds,
            )
            return np.asarray(out)


def infer_llama_config(params: dict):
    """Back-compat alias (dl/families.py owns config inference now)."""
    return fam.infer_llama_config(params)


class Batcher:
    """Dynamic batching: concurrent requests arriving within a small window
    coalesce into one device call — forward requests into one padded
    forward, generate requests into one RAGGED decode (per-row prompt
    lengths and offsets, models/decode.ragged_greedy_generate).

    Right-padding is output-preserving ONLY for causal models (later
    positions never influence earlier ones) — bidirectional encoders like
    BERT attend to the pad tokens, so ServerSet only routes causal families
    through a batcher. Rows pad to the group's max sequence and the batch
    to the next power of two, and decode lengths round up to a power of two
    — bounding the set of compiled shapes — then results are sliced back
    per request."""

    def __init__(self, server: ModelServer, max_batch: int = 32, window_ms: float = 3.0) -> None:
        import queue

        self.server = server
        self.max_batch = max_batch
        self.window_s = window_ms / 1e3
        self._q: "queue.Queue" = queue.Queue()
        self._closed = False
        self._close_lock = threading.Lock()
        # decodes run for seconds; dispatching them off the worker thread
        # keeps fast forward groups from queueing behind them. One worker
        # preserves decode-group ordering.
        from concurrent.futures import ThreadPoolExecutor

        self._gen_pool = ThreadPoolExecutor(max_workers=1)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()
        self.batches = 0  # observability: device calls issued

    def _submit(self, kind: str, tokens: np.ndarray, n: int, samp=None):
        import concurrent.futures

        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 2 or tokens.shape[0] < 1 or tokens.shape[1] < 1:
            # validate BEFORE enqueueing: a malformed request inside _run
            # would fail every other request coalesced into its group (and
            # a zero-length prompt has no last position to decode from)
            raise ValueError(
                f"tokens must be non-empty 2-D [batch, seq], got shape {tokens.shape}"
            )
        fut: "concurrent.futures.Future" = concurrent.futures.Future()
        # enqueue under the close lock so a racing close() can't consume the
        # sentinel and exit between our check and our put (hung future)
        with self._close_lock:
            if self._closed:
                raise RuntimeError("batcher is closed")
            self._q.put((kind, tokens, n, samp, fut))
        return fut.result()

    def forward_argmax(self, tokens: np.ndarray) -> np.ndarray:
        return self._submit("fwd", tokens, 0)

    def generate(self, tokens: np.ndarray, max_new_tokens: int = 16,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 seed: int = 0) -> np.ndarray:
        """Returns [B, S + max_new_tokens] (prompt + generated), matching
        ModelServer.generate. Sampling controls are per-request: a coalesced
        batch can mix greedy and sampled rows (ops/sampling.py)."""
        return self._submit(
            "gen", tokens, max_new_tokens,
            (float(temperature), int(top_k), float(top_p), int(seed)),
        )

    def _worker(self) -> None:
        import queue

        while True:
            item = self._q.get()
            if item is None:
                self._drain_closed()
                return
            group = [item]
            deadline = time.monotonic() + self.window_s
            while len(group) < self.max_batch:
                timeout = deadline - time.monotonic()
                if timeout <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=timeout)
                except queue.Empty:
                    break
                if nxt is None:
                    self._run(group)
                    self._drain_closed()
                    return
                group.append(nxt)
            self._run(group)

    def _drain_closed(self) -> None:
        """Fail anything that raced past close() rather than hang its waiter."""
        import queue

        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return
            if item is not None:
                item[-1].set_exception(RuntimeError("batcher is closed"))

    def _run(self, group: list) -> None:
        fwd = [(t, f) for kind, t, _n, _s, f in group if kind == "fwd"]
        gen = [(t, n, s, f) for kind, t, n, s, f in group if kind == "gen"]
        if gen:
            # off-thread: a long decode must not head-of-line-block the next
            # window's forward requests
            try:
                self._gen_pool.submit(self._run_generate, gen)
            except RuntimeError:  # pool shut down by a racing close(): inline
                self._run_generate(gen)
        if fwd:
            self._run_forward(fwd)

    @staticmethod
    def _pack(token_rows: list) -> tuple:
        """Right-pad a list of [b,s] token arrays into one padded batch:
        seq to a multiple of 16, batch rows to a power of two — bounding
        the set of compiled shapes. Returns (batch, spans=[(start, b, s)])."""
        from modelx_tpu.models.decode import pad_seq_len

        rows = sum(t.shape[0] for t in token_rows)
        max_s = max(t.shape[1] for t in token_rows)
        pad_s = pad_seq_len(max_s)
        pad_b = 1 << (rows - 1).bit_length()
        batch = np.zeros((pad_b, pad_s), np.int32)
        r = 0
        spans = []
        for tokens in token_rows:
            b, s = tokens.shape
            batch[r : r + b, :s] = tokens
            spans.append((r, b, s))
            r += b
        return batch, spans

    def _run_forward(self, group: list) -> None:
        try:
            batch, spans = self._pack([t for t, _f in group])
            out = self.server.forward_argmax(batch)
            self.batches += 1
            for (tokens, fut), (start, b, s) in zip(group, spans):
                fut.set_result(out[start : start + b, :s])
        except BaseException as e:
            for _tokens, fut in group:
                if not fut.done():
                    fut.set_exception(e)

    def _run_generate(self, group: list) -> None:
        """Coalesce generate requests into one ragged decode: rows pad right
        to a common (16-aligned) length, decode steps round up to a power of
        two, each request slices back its own rows and first n tokens.
        Per-request sampling controls become per-row vectors; an all-greedy
        group takes the plain greedy program (no sampling compile)."""
        try:
            batch, spans = self._pack([t for t, _n, _s, _f in group])
            new_bucket = 1 << max(3, (max(n for _t, n, _s, _f in group) - 1).bit_length())
            pad_b = batch.shape[0]
            row_lens = np.ones(pad_b, np.int32)  # pad rows decode harmlessly
            for (start, b, s) in spans:
                row_lens[start : start + b] = s
            sampling: dict = {}
            if any(samp and samp[0] > 0 for _t, _n, samp, _f in group):
                temp = np.zeros(pad_b, np.float32)
                seeds = np.zeros(pad_b, np.int32)
                # filters only when some request asked: the filter-free
                # program skips a full-vocab sort per decode step
                # ...asked by a request that actually SAMPLES — a greedy
                # request's stray filter values must not force the sort
                use_k = any(samp and samp[0] > 0 and samp[1] > 0 for _t, _n, samp, _f in group)
                use_p = any(samp and samp[0] > 0 and samp[2] < 1.0 for _t, _n, samp, _f in group)
                top_k = np.zeros(pad_b, np.int32) if use_k else None
                top_p = np.ones(pad_b, np.float32) if use_p else None
                for (_t, _n, samp, _f), (start, b, _s) in zip(group, spans):
                    if samp:
                        temp[start : start + b] = samp[0]
                        if use_k:
                            top_k[start : start + b] = samp[1]
                        if use_p:
                            top_p[start : start + b] = samp[2]
                        # distinct per-row streams within a multi-row request
                        seeds[start : start + b] = (samp[3] + np.arange(b)) % (2**31)
                sampling = {"temperature": temp, "top_k": top_k,
                            "top_p": top_p, "seeds": seeds}
            out = self.server.generate_ragged(batch, row_lens, new_bucket, **sampling)
            self.batches += 1
            # the padded rows and the bucket rounding are implementation
            # details: account only the tokens requests asked for
            requested = sum(b * n for (_t, n, _ss, _f), (_r, b, _s) in zip(group, spans))
            self.server.stats["tokens_generated"] += requested
            for (tokens, n, _samp, fut), (start, b, _s) in zip(group, spans):
                generated = out[start : start + b, :n]
                fut.set_result(np.concatenate([tokens, generated], axis=1))
        except BaseException as e:
            for _tokens, _n, _samp, fut in group:
                if not fut.done():
                    fut.set_exception(e)

    def close(self) -> None:
        with self._close_lock:
            self._closed = True
            self._q.put(None)
        # let any in-flight decode finish delivering its futures
        self._gen_pool.shutdown(wait=False)


_MODEL_ROUTE = re.compile(r"^/v1/(?P<model>[A-Za-z0-9._-]+)/(?P<verb>forward|generate)$")
_ADMIN_MODEL_ROUTE = re.compile(r"^/admin/models/(?P<model>[A-Za-z0-9._-]+)(?:\?.*)?$")


class ServerSet:
    """Named ModelServers behind one HTTP front (multi-tenant serving)."""

    def __init__(self, servers: dict[str, ModelServer], default: str | None = None,
                 trace_dir: str = "", dynamic_batch: bool = False,
                 max_new_tokens_limit: int | None = None,
                 continuous_batch: bool = False, max_slots: int = 8,
                 max_batch: int = 32, batch_window_ms: float = 3.0,
                 stream_chunk_size: int = 8, kv_page_size: int = 0,
                 kv_live_tokens: int = 0,
                 kv_attention: str = "gather",
                 pipeline_depth: int = 2,
                 dispatch_depth: int = 0,
                 burst_window_ms: float = 1.0,
                 prefill_chunk: int = 0,
                 prefill_budget: int = 0,
                 max_queue_depth: int = 0,
                 request_timeout_s: float = 0.0,
                 boundary_watchdog_s: float = 0.0,
                 hbm_budget_bytes: int = 0,
                 evict_idle: bool = False,
                 allow_admin_load: bool = False,
                 admin_tokens: tuple[str, ...] = (),
                 staging_root: str = "",
                 host_state_budget_bytes: int = 0,
                 disk_state_budget_bytes: int = 0,
                 state_spool_dir: str = "",
                 flight_recorder: bool = True,
                 flightrec_capacity: int = 0,
                 flight_dump_dir: str = "",
                 device_telemetry: bool = True) -> None:
        if not servers:
            raise ValueError("no models")
        if max_new_tokens_limit is None:
            # the cap bounds KV memory and, on the plain paths, one compiled
            # decode program per distinct value. The continuous engine
            # compiles nothing per value and holds every request to its
            # slot's span (prompt bucket + max_new_tokens + one chunk <=
            # max_seq_len, ``_validate``), so there the span is the bound:
            # a reasoning job may ask for thousands of tokens
            max_new_tokens_limit = DEFAULT_MAX_NEW_TOKENS_LIMIT
            if continuous_batch:
                max_new_tokens_limit = max(
                    max_new_tokens_limit, *(s.max_seq_len for s in servers.values()))
        self.max_new_tokens_limit = max_new_tokens_limit
        self.servers = servers
        # the model set is MUTABLE at runtime (dl/lifecycle.py admin
        # loads/unloads): every structural change goes through
        # add_server/remove_server under this lock
        self._servers_lock = threading.RLock()
        for name, s in servers.items():
            s.name = name  # route key and server identity must agree
        self.default = default or next(iter(servers))
        # template for runtime-loaded ModelServers (the pool's admin load
        # path): same mesh, dtype, context budget, quantization, and cache
        # knobs the boot-time set got — serve_main overrides as needed
        first = next(iter(servers.values()))
        self.server_defaults: dict = {
            "mesh": first.mesh,
            "dtype": "bfloat16" if first.dtype == jnp.bfloat16 else "float32",
            "max_seq_len": first.max_seq_len,
            "quantize": first.quantize,
            "speculative_k": first.speculative_k,
        }
        if first._prefix_cache is not None:
            # a runtime-loaded tenant must not silently lose the boot
            # set's prefix cache: its serving block would then have no
            # hit-rate signal for the router and no KV to publish
            self.server_defaults.update(
                prefix_cache_size=first._prefix_cache.capacity,
                prefix_cache_max_bytes=first._prefix_cache.max_bytes,
            )
        # bearer tokens gating the /admin surface (the registry auth
        # model's static-token tier; empty = anonymous admin, for
        # single-tenant dev pods and tests)
        self.admin_tokens = tuple(admin_tokens)
        self.trace_dir = trace_dir or os.path.join(os.getcwd(), "jax-trace")
        self._profiling = threading.Lock()
        # the newest capture as the pod itself saw it (Handler._profile):
        # /metrics serves it under "profile" once there is one
        self.profile_capture: dict | None = None
        # on-demand profiler captures (POST /admin/profile) land in
        # numbered subdirs under trace_dir; only the newest
        # MAX_PROFILE_CAPTURES survive (the capture dir is CAPPED — an
        # operator probing a live incident must not fill the disk)
        self._capture_seq = 0
        self._capture_lock = threading.Lock()
        # engine flight recorder + black-box dump dir (ISSUE 15), threaded
        # into every ContinuousBatcher this set creates
        self.flight_recorder = bool(flight_recorder)
        self.flightrec_capacity = int(flightrec_capacity)
        self.flight_dump_dir = str(flight_dump_dir or "")
        # measured device telemetry (utils/devmem) in engine snapshots and
        # the /metrics device family
        self.device_telemetry = bool(device_telemetry)
        # windowed pod rates (utils/tswheel): requests/s, 5xx/s, sheds/s
        # over 1m/5m, marked once per completed POST in the handler
        self.rates = tswheel.RateSet(("requests", "http_5xx", "sheds"))
        self._dynamic_batch = dynamic_batch
        self._continuous_batch = continuous_batch
        self.max_slots = max_slots
        # paged KV for the continuous engine: page_size > 0 switches the
        # engine's per-layer state to a page pool sized by kv_live_tokens
        # (see dl/continuous.py) — required for max_slots much beyond 8
        self.kv_page_size = kv_page_size
        self.kv_live_tokens = kv_live_tokens
        # "gather" = bit-exact dense view per step; "in-place" = blockwise
        # paged attention reading pools directly (see ContinuousBatcher)
        self.kv_attention = kv_attention
        # chunks the continuous engine keeps in flight before syncing the
        # oldest (hides the per-chunk fetch round-trip; value-dependent row
        # exits lag by up to this many chunks of wasted compute)
        self.pipeline_depth = pipeline_depth
        # decode chunks scanned per device program in steady decode
        # (amortizes the fixed dispatch cost; 0 = auto, 1 = per-chunk —
        # see ContinuousBatcher.dispatch_depth)
        self.dispatch_depth = dispatch_depth
        # idle-burst gather window (ms): co-arrivals at an idle engine admit
        # as one program + decode in step; 0 disables
        self.burst_window_ms = burst_window_ms
        # chunked prefill (Sarathi-style): prompts longer than one piece
        # land piece by piece between decode chunks instead of as one
        # monolithic admission prefill (0 = off); prefill_budget bounds
        # the per-boundary prefill tokens once decode rows have spent
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = prefill_budget
        # bounded admission + deadlines for the continuous engine: submits
        # past max_queue_depth shed with 429 + Retry-After; requests older
        # than request_timeout_s expire with 504 at chunk boundaries
        self.max_queue_depth = max_queue_depth
        self.request_timeout_s = request_timeout_s
        # no-progress boundary watchdog for the continuous engine: a wedged
        # device dispatch (real on TPU) is treated as a crash after this
        # many seconds so the restart/breaker machinery applies (0 = off)
        self.boundary_watchdog_s = boundary_watchdog_s
        self.max_batch = max_batch
        self.batch_window_ms = batch_window_ms
        self.stream_chunk_size = stream_chunk_size
        self._batcher_lock = threading.Lock()
        self.batchers: dict[str, Batcher] = {}
        self.cbatchers: dict = {}  # name -> ContinuousBatcher
        self._engine_locks: dict[str, threading.Lock] = {}  # per-model creation
        # set on SIGTERM: /healthz flips to 503 so load balancers stop
        # routing here while in-flight requests finish (graceful drain)
        self.draining = False
        # live POST count (streams included, until their last byte): the
        # drain loop in serve_main waits for this to reach zero before
        # closing engines, instead of sleeping a fixed interval
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        # the lifecycle pool (dl/lifecycle.py): state machine + HBM budget
        # + in-flight accounting for every tenant, boot-time set included
        from modelx_tpu.dl.lifecycle import ModelPool

        self.pool = ModelPool(
            self, hbm_budget_bytes=hbm_budget_bytes, evict_idle=evict_idle,
            allow_admin_load=allow_admin_load, staging_root=staging_root,
            # the shared serving mesh: --hbm-budget-bytes is per-device
            # HBM, and on a weight-sharding mesh the pool divides each
            # model's footprint by the mesh's weight-shard factor
            mesh=first.mesh,
            # tiered live state (dl/tiers.py): demoted models stage in
            # host RAM/disk instead of being discarded, and a re-load of
            # the same content is a tier promotion
            host_state_budget_bytes=host_state_budget_bytes,
            disk_state_budget_bytes=disk_state_budget_bytes,
            state_spool_dir=state_spool_dir,
        )

    def request_began(self) -> None:
        """Count a POST as in-flight until its last byte is written — a
        streaming response stays in-flight for its whole body, which is
        what the SIGTERM drain loop must wait out."""
        with self._inflight_lock:
            self._inflight += 1

    def request_ended(self) -> None:
        with self._inflight_lock:
            self._inflight -= 1

    @property
    def inflight(self) -> int:
        with self._inflight_lock:
            return self._inflight

    def next_capture_dir(self) -> str:
        """A fresh numbered capture dir under ``trace_dir/captures`` for
        one on-demand profiler run; prunes all but the newest
        ``MAX_PROFILE_CAPTURES - 1`` existing captures first (the new one
        brings the total back to the cap)."""
        import shutil

        root = os.path.join(self.trace_dir, "captures")
        # only the sequence bump needs the lock; the filesystem work runs
        # outside it (callers are already serialized by _profiling — this
        # lock just keeps the counter coherent for any future caller)
        with self._capture_lock:
            self._capture_seq += 1
            seq = self._capture_seq
        os.makedirs(root, exist_ok=True)
        keep = MAX_PROFILE_CAPTURES - 1
        old = sorted(
            (d for d in os.listdir(root)
             if d.startswith("cap-")
             and os.path.isdir(os.path.join(root, d))),
            key=lambda d: os.path.getmtime(os.path.join(root, d)),
        )
        for name in old[:max(0, len(old) - keep)]:
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        path = os.path.join(root, "cap-%d-%d" % (int(time.time()), seq))
        os.makedirs(path, exist_ok=True)
        return path

    def add_server(self, name: str, server: ModelServer) -> None:
        """Insert a runtime-loaded model into the routing set (the pool's
        READY transition)."""
        with self._servers_lock:
            server.name = name
            self.servers[name] = server

    def remove_server(self, name: str, close: bool = True):
        """Remove a model from routing; returns ``(server, batcher,
        engine)``. With ``close`` the window batcher and continuous engine
        close and the engine's device state (KV cache / page pool) is
        released here; the pool's unload path passes ``close=False`` and
        closes them OUTSIDE its lock, so freeing one tenant never stalls
        admission for the others."""
        with self._servers_lock:
            server = self.servers.pop(name, None)
            batcher = self.batchers.pop(name, None)
            cb = self.cbatchers.pop(name, None)
            self._engine_locks.pop(name, None)
            if self.default == name and self.servers:
                ready = [n for n, s in self.servers.items() if s.ready]
                self.default = (ready or list(self.servers))[0]
        if close:
            if batcher is not None:
                batcher.close()
            if cb is not None:
                cb.close()
                cb.release_device_state()
        return server, batcher, cb

    def batcher_for(self, server: ModelServer) -> "Batcher | None":
        """Lazily create a batcher once the model is loaded — only causal
        families batch (right-padding changes bidirectional-encoder
        outputs, see Batcher docstring)."""
        if not self._dynamic_batch or server.family is None or server.family.generate is None:
            return None
        b = self.batchers.get(server.name)
        if b is None:
            with self._batcher_lock:
                b = self.batchers.get(server.name)
                if b is None:
                    b = self.batchers[server.name] = Batcher(
                        server, max_batch=self.max_batch, window_ms=self.batch_window_ms
                    )
        return b

    def continuous_for(self, server: ModelServer):
        """The continuous (in-flight) batching engine — cached-decode
        causal families only. When enabled it supersedes both the window
        batcher and speculation for generate/stream traffic:
        iteration-level scheduling owns the device. A boot-time model's
        engine was built by its load (``engine_at_load``); a model added at
        run time, one promoted from a tier, or one whose engine could not
        be allocated at load gets it here, lazily, with the
        demote-and-retry on RESOURCE_EXHAUSTED."""
        if not self._serves_continuous(server):
            return None
        cb = self.cbatchers.get(server.name)
        if cb is not None:
            return cb
        return self._build_engine(server, shed_on_oom=True)

    def engine_at_load(self, server: ModelServer, allocate: bool):
        """What ``ModelServer.load`` is given, and calls twice. After the
        headers have given family and config (``allocate=False``): build
        the engine a --continuous-batch pod will answer with, without its
        device state, so the load can fetch its chunk program beside the
        weight stream. Once the weights are placed (``allocate=True``):
        allocate its KV cache, so /healthz follows an engine that exists.
        None where the pod does not serve through one. A load never fails
        for it: an engine that cannot be built or allocated now
        (RESOURCE_EXHAUSTED in a multi-model set) is dropped and left to
        ``continuous_for``."""
        if not self._serves_continuous(server):
            return None
        try:
            cb = self.cbatchers.get(server.name)
            if cb is None:
                cb = self._build_engine(server, shed_on_oom=False, allocate=False)
            if allocate:
                cb.allocate_device_state()
            return cb
        except kv_layout.Refused:
            raise  # an option this family's cache cannot serve ends the load
        except Exception as e:
            logger.warning("engine for %s not ready at load (left to the "
                           "first request): %s", server.name, e)
            self._drop_engine(server.name)
            return None

    def _drop_engine(self, name: str) -> None:
        cb = self.cbatchers.pop(name, None)
        if cb is not None:
            cb.close()
            cb.release_device_state()

    def _serves_continuous(self, server: ModelServer) -> bool:
        return bool(
            self._continuous_batch
            and server.family is not None
            and server.family.decode_fns is not None
        )

    def _build_engine(self, server: ModelServer, shed_on_oom: bool,
                      allocate: bool = True):
        """Construct and register ``server``'s ContinuousBatcher.
        Construction (a full [max_slots, max_len] KV-cache allocation) runs
        under a PER-MODEL lock so other tenants' traffic never stalls
        behind it."""
        with self._batcher_lock:
            lk = self._engine_locks.setdefault(server.name, threading.Lock())
        with lk:
            cb = self.cbatchers.get(server.name)
            if cb is None:
                from modelx_tpu.dl.continuous import ContinuousBatcher

                max_len = server.max_seq_len
                n_pos = getattr(server.cfg, "n_positions", 0) or 0
                if n_pos:  # gpt2: positions past wpe silently clamp
                    max_len = min(max_len, n_pos)
                page_size = self.kv_page_size
                if page_size > 0 and max_len % page_size:
                    # gpt2-style clamped max_len may not be a page multiple:
                    # clamp max_len DOWN (losing < one page of context)
                    # rather than degrading to an arbitrary tiny page size
                    clamped = (max_len // page_size) * page_size
                    if clamped <= 0:
                        logger.warning(
                            "kv_page_size %d exceeds max_len %d for %s; "
                            "paged KV disabled", page_size, max_len, server.name,
                        )
                        page_size = 0
                    else:
                        logger.warning(
                            "max_len %d -> %d for %s (kv_page_size %d multiple)",
                            max_len, clamped, server.name, page_size,
                        )
                        max_len = clamped
                def build():
                    return ContinuousBatcher(
                        server, max_slots=self.max_slots,
                        chunk_size=self.stream_chunk_size, max_len=max_len,
                        prefix_cache=server._prefix_cache,
                        page_size=page_size,
                        max_live_tokens=self.kv_live_tokens,
                        paged_attention=self.kv_attention,
                        # --speculative-k composes with continuous batching:
                        # the engine speculates whenever exactly one greedy
                        # row is active (VERDICT r4: the flags must not be
                        # mutually exclusive)
                        speculative_k=server.speculative_k,
                        pipeline_depth=self.pipeline_depth,
                        dispatch_depth=self.dispatch_depth,
                        burst_window_ms=self.burst_window_ms,
                        prefill_chunk=self.prefill_chunk,
                        prefill_budget=self.prefill_budget,
                        max_queue_depth=self.max_queue_depth,
                        request_timeout_s=self.request_timeout_s,
                        boundary_watchdog_s=self.boundary_watchdog_s,
                        flight_recorder=self.flight_recorder,
                        flightrec_capacity=self.flightrec_capacity,
                        flight_dump_dir=self.flight_dump_dir,
                        device_telemetry=self.device_telemetry,
                        allocate=allocate,
                    )

                t_build = time.monotonic()
                try:
                    with trace.span("startup.engine_init", model=server.name):
                        cb = build()
                except Exception as exc:
                    # RESOURCE_EXHAUSTED allocating the KV/page pool: demote
                    # idle tenants' state to the host tier and retry ONCE;
                    # anything else (or a dry pool) is a typed 503 — the
                    # request sheds instead of wedging the engine slot
                    from modelx_tpu.dl import tiers as tiers_mod
                    from modelx_tpu.dl.serving_errors import EngineBrokenError

                    if not (shed_on_oom
                            and tiers_mod.is_resource_exhausted(exc)):
                        raise
                    freed = self.pool.shed_idle_for_bytes(
                        0, exclude=server.name)
                    self.pool.flightrec.record(
                        "engine.alloc_oom_retry", model=server.name,
                        freed_bytes=freed)
                    if freed <= 0:
                        raise EngineBrokenError(
                            f"KV allocation for {server.name} hit "
                            "RESOURCE_EXHAUSTED and no idle model could be "
                            "demoted") from exc
                    logger.warning(
                        "KV allocation for %s hit RESOURCE_EXHAUSTED; "
                        "demoted %d reserved bytes of idle state, retrying "
                        "once", server.name, freed,
                    )
                    try:
                        cb = build()
                    except Exception as exc2:
                        raise EngineBrokenError(
                            f"KV allocation for {server.name} failed after "
                            "demoting idle state") from exc2
                # noted beside the stages that tile process start ->
                # ready: inside `load` at boot, after ready when lazy
                trace.startup.note("engine_init", time.monotonic() - t_build)
                self.cbatchers[server.name] = cb
        return cb

    def serving_stats(self) -> dict:
        """Per-model load + locality stats for the fleet router's placement
        table (rides GET /admin/models next to the lifecycle states):
        ``queue_depth``/``active``/``waiting`` from the continuous engine
        (0s when the engine is off — the plain path has no backlog),
        ``engine_state``, and the prefix cache's entry/byte/hit counters —
        what prefix-sticky routing ranks pods by."""
        out: dict = {}
        # snapshot the mutable set under its lock (remove_server pops
        # entries at runtime); the per-engine reads below then run
        # lock-free like /metrics does
        with self._servers_lock:
            pairs = [(n, s, self.cbatchers.get(n))
                     for n, s in self.servers.items()]
        for name, s, cb in pairs:
            d: dict = {"queue_depth": 0, "active": 0, "waiting": 0}
            if cb is not None:
                snap = cb.snapshot()
                d["queue_depth"] = int(snap.get("queue_depth", 0))
                d["active"] = int(snap.get("active", 0))
                d["waiting"] = int(snap.get("waiting", 0))
                d["engine_state"] = snap.get("engine_state", "running")
            if s._prefix_cache is not None:
                d["prefix_cache"] = s._prefix_cache.stats()
            out[name] = d
        return out

    def engine_health(self) -> str | None:
        """Worst continuous-engine state across tenants, or None when every
        engine is healthy: "engine-broken" (circuit open — the pod needs a
        restart) beats "engine-restarting" (the supervisor is mid-backoff;
        load balancers should drain until it comes back)."""
        worst = None
        for cb in list(self.cbatchers.values()):
            state = getattr(cb, "engine_state", "running")
            if state == "broken":
                return "engine-broken"
            if state == "restarting":
                worst = "engine-restarting"
        return worst

    def engine_for(self, server: ModelServer, n_rows: int, temperature: float):
        """THE generate-routing policy, in one place: continuous batching
        (when enabled; with --speculative-k the ENGINE speculates whenever
        a single greedy row has the device to itself) > standalone
        speculation (single-row, --speculative-k) > window batcher > plain
        server."""
        cb = self.continuous_for(server)
        if cb is not None:
            return cb
        if (
            server.speculative_k > 0
            and n_rows == 1
            and server.family.decode_fns is not None
        ):
            # speculation's target shape (greedy = token-exact, sampled =
            # modified rejection); it must not be silently inert under
            # --dynamic-batch
            return server
        batcher = self.batcher_for(server)
        if batcher is not None and server.family.generate_ragged is not None:
            return batcher
        return server

    def stream_source(self, server: ModelServer, tokens, n: int, samp: dict,
                      stop_token_ids=None, timeout_s: float | None = None,
                      priority: str = "interactive", resume_step: int = 0,
                      request_id: str = "", timing: dict | None = None):
        """Streaming analogue of engine_for: a token-chunk iterator.
        Single-row streams join the continuous engine when enabled; all
        paths honor the operator's --stream-chunk-size and end early on a
        stop-token hit. ``timeout_s``/``priority`` (a propagated
        X-ModelX-Deadline-Ms remainder + priority class) reach only the
        continuous engine — the plain path has no deadline machinery, so
        the handler's up-front expiry check is its whole contract.
        ``resume_step`` > 0 continues a severed stream token-exactly (the
        row is ``prompt + emitted`` and sampling restarts at step k) —
        continuous-engine only; the plain path has no per-step sample
        streams to rejoin, so the handler refuses resume before we get
        here (MalformedResumeError, 400).
        ``request_id``/``timing`` (ISSUE 13) thread the end-to-end id
        into the engine ticket and return its phase breakdown via the
        caller's out-param — continuous-engine only; the plain path has
        no per-request phases to report."""
        cb = self.continuous_for(server)
        if cb is not None and tokens.shape[0] == 1:
            return cb.stream(tokens, max_new_tokens=n,
                             stop_token_ids=stop_token_ids,
                             timeout_s=timeout_s, priority=priority,
                             resume_step=resume_step,
                             request_id=request_id, timing=timing, **samp)
        if resume_step:
            raise MalformedResumeError(
                "resume requires the continuous engine (single-row stream)"
            )
        return server.generate_stream(
            tokens, max_new_tokens=n, chunk_size=self.stream_chunk_size,
            stop_token_ids=stop_token_ids, **samp
        )

    @property
    def ready(self) -> bool:
        """Readiness over the HEALTHY set: models whose load crashed are
        FAILED (degraded, reported on /healthz and /v1/models) but must
        not hold the whole pod un-ready forever — the other tenants are
        serving. Empty-or-all-failed is not ready."""
        if self.draining:
            return False
        with self._servers_lock:
            healthy = [s for s in self.servers.values() if s.load_error is None]
        return bool(healthy) and all(s.ready for s in healthy)

    def load_all(self, concurrent: bool = False) -> dict:
        """Load every model; ``concurrent`` overlaps the fetch phases (device
        transfers already funnel through the loader's transfer pool).

        One model failing marks ONLY that model FAILED (load_error set,
        pool state FAILED, reason on /v1/models) — the others keep
        serving. Only when EVERY model fails does the process-level error
        propagate (a single-tenant pod with a broken checkpoint should
        still crash-loop visibly)."""
        def _load(s: ModelServer, catch=Exception) -> None:
            if self.pool is not None:
                self.pool.mark_loading(s.name)
            try:
                s.load(engine_at_load=self.engine_at_load)
            except catch as e:
                s.load_error = str(e)
                errs[s.name] = e
                self._drop_engine(s.name)  # built at load: return its KV cache
                if self.pool is not None:
                    self.pool.mark_failed(s.name, str(e))
                logger.error("loading %s failed (tenant marked FAILED, "
                             "others keep serving): %s", s.name, e)
            else:
                if self.pool is not None:
                    self.pool.mark_ready(s.name)

        errs: dict[str, BaseException] = {}
        servers = list(self.servers.values())
        if concurrent and len(servers) > 1:
            # worker threads catch BaseException so a crash surfaces as a
            # FAILED tenant rather than a silently dead thread
            threads = [
                threading.Thread(target=_load, args=(s, BaseException),
                                 daemon=True)
                for s in servers
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        else:
            # sequential path runs on the MAIN thread: Exception only, so
            # an operator Ctrl-C (KeyboardInterrupt) still aborts the boot
            # instead of marking the in-flight model FAILED
            for s in servers:
                _load(s)
        if errs and len(errs) == len(servers):
            name, err = next(iter(errs.items()))
            raise RuntimeError(f"loading {name} failed: {err}") from err
        trace.startup.ready()
        return {
            name: dict(s.stats, **({"error": s.load_error} if s.load_error else {}))
            for name, s in self.servers.items()
        }

    def resolve(self, path: str) -> tuple[ModelServer | None, str | None]:
        """(server, verb) for a POST path; (None, None) if unroutable."""
        with self._servers_lock:
            if path in ("/v1/forward", "/v1/generate"):
                server = self.servers.get(self.default)
                return server, (path.rsplit("/", 1)[1] if server else None)
            m = _MODEL_ROUTE.match(path)
            if m and m.group("model") in self.servers:
                return self.servers[m.group("model")], m.group("verb")
        return None, None

    def route_name(self, path: str) -> str | None:
        """The model name a POST path addresses (resolved or not) — the
        404 path asks the pool about THIS name before giving up, so a
        PULLING/LOADING model answers 503 + Retry-After instead of 404."""
        if path in ("/v1/forward", "/v1/generate"):
            return self.default
        m = _MODEL_ROUTE.match(path)
        return m.group("model") if m else None


def _query_param(path: str, name: str) -> str:
    """One query-string value from a raw request path ("" when absent)."""
    from urllib.parse import parse_qs, urlparse

    vals = parse_qs(urlparse(path).query).get(name)
    return vals[0] if vals else ""


def propagated_timeout(headers) -> float | None:
    """The caller's remaining budget from ``X-ModelX-Deadline-Ms``
    (stamped by the fleet router per upstream attempt; the header name
    AND its parser are shared with the router via serving_errors so the
    two halves of the wire contract cannot drift): None = no propagated
    deadline, else remaining seconds (0.0 = the caller's budget is
    ALREADY gone — answer 504 without doing any work). The engine clamps
    its own --request-timeout to this remainder, so a router failover
    never re-grants a fresh full timeout."""
    return parse_deadline_ms(headers.get(DEADLINE_HEADER))


def request_priority(headers) -> str:
    """Priority class from ``X-ModelX-Priority`` (shared parser: batch
    only on an explicit opt-in). Batch rows queue behind interactive
    ones at the engine's admission boundary."""
    return parse_priority(headers.get(PRIORITY_HEADER))


def serve(servers: ModelServer | ServerSet, listen: str = ":8000",
          access_log: str = "", access_log_max_bytes: int = 0) -> ThreadingHTTPServer:
    sset = servers if isinstance(servers, ServerSet) else ServerSet({servers.name: servers})
    access = accesslog.open_log(access_log, max_bytes=access_log_max_bytes)

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def send_response(self, code, message=None):
            # remember the committed status for the access-log line (one
            # capture point covers _json AND the streaming 200)
            self._resp_status = code
            super().send_response(code, message)

        def _obs_headers(self) -> None:
            """Echo the request id + attempt on EVERY response (JSON and
            streamed): the client joins its response to the fleet's logs
            and traces by this one header. No-op on paths that never
            bound an id (GETs)."""
            rid = getattr(self, "_rid", "")
            if rid:
                self.send_header(REQUEST_ID_HEADER, rid)
                self.send_header(ATTEMPT_HEADER, str(self._attempt))

        def _json(self, status: int, obj, headers: dict | None = None) -> None:
            body = json.dumps(obj).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self._obs_headers()
            if getattr(self, "_rid", ""):
                # the non-streaming timing contract: whatever phases this
                # request reached ride as X-ModelX-Timing-* headers — a
                # 504 still reports the queue time it burned
                timing = dict(self._timing)
                timing["total_ms"] = round(
                    (time.monotonic() - self._t0) * 1e3, 3)
                for k, v in timing_headers(timing).items():
                    self.send_header(k, v)
            for k, v in (headers or {}).items():  # e.g. Retry-After on 429
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _text(self, status: int, text: str, content_type: str) -> None:
            body = text.encode("utf-8")
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _first_token(self) -> None:
            """This request has its first token: where it is the pod's
            first, start-up's clock closes (utils/trace.Startup). One
            branch a request."""
            if trace.startup.first_token_s is None:
                trace.startup.first_token(self._t0)

        def _stream_chunks(self, content_type: str, payloads, error_payload) -> None:
            """Commit a 200 + chunked transfer encoding and write each bytes
            payload. A mid-stream error (status already on the wire) writes
            ``error_payload(e)``; the terminator always goes out. Shared by
            the NDJSON token stream and the OpenAI SSE stream."""
            self.send_response(200)
            self.send_header("Content-Type", content_type)
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Transfer-Encoding", "chunked")
            self._obs_headers()
            self.end_headers()

            def write_chunk(payload: bytes) -> None:
                self.wfile.write(f"{len(payload):x}\r\n".encode())
                self.wfile.write(payload + b"\r\n")

            try:
                for payload in payloads:
                    write_chunk(payload)
            except Exception as e:
                logger.exception("stream error")
                try:
                    write_chunk(error_payload(e))
                except OSError:
                    pass  # client went away
            finally:
                try:
                    self.wfile.write(b"0\r\n\r\n")  # chunked terminator
                except OSError:
                    pass

        def _stream_generate(self, server, tokens, n, samp, stop_ids=None,
                             timeout_s=None, priority="interactive",
                             resume_step=0, include_timing=False) -> None:
            """NDJSON token stream, then {"done": true}; concatenates to
            the non-streaming result. Single-row streams emit ONE token
            per line ({"tokens": [[t]]}): position-independent framing, so
            a router splicing a continuation (resume after a pod death)
            produces a body byte-identical to the uninterrupted stream
            regardless of where the original died relative to chunk
            boundaries. Multi-row streams (plain path only) keep one line
            per decoded chunk. Single-row streams ride the continuous
            engine when enabled, so N concurrent clients share one running
            decode instead of contending with N independent loops."""
            kw = deadline_kwargs(timeout_s, priority)
            if resume_step:
                kw["resume_step"] = resume_step
            timing: dict = self._timing
            gen = sset.stream_source(server, tokens, n, samp,
                                     stop_token_ids=stop_ids,
                                     request_id=getattr(self, "_rid", ""),
                                     timing=timing, **kw)
            try:
                # pull the first chunk BEFORE committing a 200: an
                # unsupported family / bad request must still be a 4xx
                first = next(gen, None)
            except ValueError as e:
                return self._json(400, {"error": str(e)})
            except ServingError as e:
                # typed serving failures (queue full / deadline / engine
                # broken) carry their canonical status + headers — a shed
                # stream request still gets its 429 + Retry-After
                return self._json(e.http_status, {"error": str(e)},
                                  headers=e.headers())
            if first is not None:
                self._first_token()

            def payloads():
                emitted = 0
                if first is not None:
                    for piece in itertools.chain([first], gen):
                        rows = piece.tolist()
                        if len(rows) == 1:
                            for t in rows[0]:
                                emitted += 1
                                yield (json.dumps({"tokens": [[t]]}).encode()
                                       + b"\n")
                        else:
                            emitted += sum(len(r) for r in rows)
                            yield (json.dumps({"tokens": rows}).encode()
                                   + b"\n")
                if include_timing:
                    # OPT-IN final timing line, BEFORE the done line. The
                    # default stream is byte-unchanged — the router's
                    # continuation splice and the byte-equality contract
                    # it tests depend on that. gen.close() runs the
                    # engine-side finally, so the breakdown is complete.
                    gen.close()
                    yield (json.dumps(
                        {"timing": self._finish_timing(timing, emitted)}
                    ).encode() + b"\n")
                yield b'{"done": true}\n'

            self._stream_chunks(
                "application/x-ndjson", payloads(),
                lambda e: json.dumps({"error": str(e)}).encode() + b"\n",
            )

        def _finish_timing(self, timing: dict, emitted: int) -> dict:
            """Complete a phase breakdown with the handler-side view:
            wall total, emitted count, and the decode rate (tokens after
            the first over the post-TTFT wall time)."""
            t = dict(timing)
            total_ms = round((time.monotonic() - self._t0) * 1e3, 3)
            t["total_ms"] = total_ms
            t["tokens"] = emitted
            ttft = t.get("ttft_ms")
            if ttft is not None and emitted > 1 and total_ms > ttft:
                t["decode_tps"] = round(
                    (emitted - 1) / ((total_ms - ttft) / 1e3), 2)
            self._timing.update(t)  # the access-log line sees it too
            return t

        def _openai(self, req: dict, chat: bool) -> None:
            """/v1/completions + /v1/chat/completions (openai_api.py). SSE
            for stream=true; errors use the OpenAI {"error": {...}} shape."""
            from modelx_tpu.dl import openai_api as oai

            # lifecycle gate, in the OpenAI error shape: PULLING/LOADING
            # 503 + Retry-After, DRAINING 409, FAILED 503 + reason — the
            # SAME typed errors the native surface maps
            name = str(req.get("model") or sset.default)
            self._log_model = name
            if sset.pool is not None:
                try:
                    sset.pool.check_admission(name)
                    sset.pool.enter(name)  # raises 409 if a drain raced in
                except ServingError as e:
                    api = oai.api_error_for(e)
                    return self._json(api.status, api.payload, headers=e.headers())
            # deadline propagation + priority class, same contract as the
            # native path: expired budgets 504 in the OpenAI error shape
            # before any engine work, live ones clamp the engine deadline
            timeout_s = propagated_timeout(self.headers)
            priority = request_priority(self.headers)
            if timeout_s is not None and timeout_s <= 0:
                e = DeadlineExceededError("admitting", timeout_s)
                api = oai.api_error_for(e)
                if sset.pool is not None:
                    sset.pool.exit(name)
                return self._json(api.status, api.payload, headers=e.headers())
            try:
                # mid-stream failover resume (ISSUE 12): the SAME wire
                # block as the native surface — router headers win over a
                # native ``resume`` field. Validation and token-exact
                # continuation run here too; the fleet router only
                # SPLICES native NDJSON streams (docs/router.md), but the
                # pod-side contract must not differ between surfaces.
                resume = None
                hdr_e = self.headers.get(RESUME_EMITTED_HEADER)
                hdr_s = self.headers.get(RESUME_SEED_HEADER)
                if hdr_e is not None or hdr_s is not None:
                    resume = parse_resume(hdr_e, hdr_s)
                else:
                    rz = req.get("resume")
                    if rz is not None:
                        if not isinstance(rz, dict):
                            raise MalformedResumeError(
                                "resume must be an object with emitted + seed")
                        resume = parse_resume(rz.get("emitted"), rz.get("seed"))
                if resume is not None and not bool(req.get("stream", False)):
                    raise MalformedResumeError(
                        "resume requires a streaming request")
                if bool(req.get("stream", False)):
                    events = oai.stream_completion(sset, req, chat,
                                                   timeout_s=timeout_s,
                                                   priority=priority,
                                                   resume=resume,
                                                   request_id=self._rid,
                                                   timing=self._timing)
                    try:
                        # validation + compile errors must surface as a real
                        # status, so pull the first event before the 200
                        # (stream_completion primes generation before its
                        # first yield, chat role chunk included)
                        first = next(events, None)
                    except ValueError as e:
                        raise oai.APIError(400, str(e)) from e
                    if first is not None:
                        self._first_token()

                    def payloads():
                        if first is not None:
                            yield oai.sse_encode(first)
                            for ev in events:
                                yield oai.sse_encode(ev)
                        yield oai.SSE_DONE

                    return self._stream_chunks(
                        "text/event-stream", payloads(),
                        # mid-stream failures: typed serving errors keep
                        # their one canonical payload even after the 200
                        # is on the wire (a deadline expiry mid-SSE reads
                        # the same as a pre-stream 504 body)
                        lambda e: oai.sse_encode(
                            oai.api_error_for(e).payload
                            if isinstance(e, ServingError)
                            else {"error": {"message": str(e), "type": "server_error"}}
                        ),
                    )
                done = oai.run_completion(
                    sset, req, chat, timeout_s=timeout_s, priority=priority,
                    request_id=self._rid, timing=self._timing)
                self._first_token()  # unstreamed: the first token is the answer
                return self._json(200, done)
            except oai.APIError as e:
                # typed lifecycle 503s raised inside the API layer carry
                # Retry-After like the native surface's (satellite:
                # resolve_model's still-loading must back clients off)
                return self._json(e.status, e.payload,
                                  headers=getattr(e, "headers", None))
            except ValueError as e:
                return self._json(400, oai.APIError(400, str(e)).payload)
            except ServingError as e:
                # one OpenAI-shaped payload per typed failure class: 429
                # sheds carry Retry-After, deadlines 504, engine death 503
                api = oai.api_error_for(e)
                return self._json(api.status, api.payload, headers=e.headers())
            except Exception as e:
                logger.exception("openai api error")
                return self._json(500, oai.APIError(500, str(e), "server_error").payload)
            finally:
                if sset.pool is not None:
                    sset.pool.exit(name)

        def _admin_auth(self) -> bool:
            """Bearer-token filter for the /admin surface (the registry
            auth model's static-token tier — --admin-token). Empty token
            set = anonymous admin. Returns False after writing the 401."""
            if not sset.admin_tokens:
                return True
            import hmac

            authz = self.headers.get("Authorization", "")
            presented = authz[len("Bearer "):] if authz.startswith("Bearer ") else ""
            # constant-time per candidate: the admin surface controls model
            # load/unload, so token comparison must not leak prefix timing
            if any(hmac.compare_digest(presented, t) for t in sset.admin_tokens):
                return True
            self._json(401, {"error": "invalid or missing bearer token"})
            return False

        def do_GET(self):
            # GETs share keep-alive connections with POSTs: clear the
            # per-request observability state a previous POST bound
            self._rid = ""
            self._resp_status = 0
            if self.path == "/healthz":
                from modelx_tpu.dl import manifest_cache

                engine = sset.engine_health()
                failed = sset.pool.failed() if sset.pool is not None else {}
                # registry reachability rides ALONGSIDE readiness, never
                # into it: a pod serving READY models through a registry
                # outage stays 200/routable — control_plane is the
                # operator/rebalancer signal that freshness is degraded
                cp = manifest_cache.health().status()
                if engine is not None:
                    # a crash-looping or circuit-broken engine must flip
                    # readiness so load balancers drain instead of routing
                    # every request into a dead engine
                    self._json(503, {"status": engine, "control_plane": cp})
                elif sset.ready:
                    # degraded: some tenants FAILED to load, the rest are
                    # serving — stay routable but say who is down and why
                    if failed:
                        self._json(200, {"status": "degraded", "failed": failed,
                                         "control_plane": cp})
                    else:
                        self._json(200, {"status": "ok", "control_plane": cp})
                else:
                    status = "draining" if sset.draining else (
                        "failed" if failed else "loading"
                    )
                    body = {"status": status, "control_plane": cp}
                    if failed:
                        body["failed"] = failed
                    # loading resolves on its own: tell the LB when to look
                    # again (the same contract the 429 shed path set)
                    headers = {} if sset.draining else {"Retry-After": "2"}
                    self._json(503, body, headers=headers)
            elif self.path == "/livez":
                # liveness, distinct from readiness: fails ONLY on the
                # unrecoverable engine-broken state (circuit open), so the
                # podspec livenessProbe restarts the pod — the blob cache +
                # compile cache make that restart cheap. Loading, draining,
                # and supervised restarting are all ALIVE (killing a pod
                # mid-load/drain/backoff would turn recoverable states into
                # restart loops).
                if sset.engine_health() == "engine-broken":
                    self._json(503, {"status": "engine-broken"})
                else:
                    self._json(200, {"status": "ok"})
            elif self.path.split("?", 1)[0] == "/metrics":
                payload = {}
                lifecycle = sset.pool.states() if sset.pool is not None else {}
                for n, s in list(sset.servers.items()):
                    d = dict(s.stats)
                    cb = sset.cbatchers.get(n)
                    if cb is not None:
                        # counters + live gauges (chunks/admitted/
                        # active_peak, prefill_pieces, stall_ms_max,
                        # spec accept stats, pages) — the operator/bench
                        # surface for the engine, no internals poking
                        d["continuous"] = cb.snapshot()
                    if s._prefix_cache is not None:
                        d["prefix_cache"] = s._prefix_cache.stats()
                    if n in lifecycle:
                        # per-model lifecycle gauges: state, loads_total,
                        # evictions_total, hbm_reserved_bytes, drain_seconds
                        d["lifecycle"] = lifecycle[n]
                    payload[n] = d
                for n, st in lifecycle.items():
                    if n not in payload:  # PULLING/UNLOADED: no server yet
                        payload[n] = {"lifecycle": st}
                if sset.pool is not None and "pool" not in payload:
                    payload["pool"] = sset.pool.pool_snapshot()
                # pod-level windowed rates (ISSUE 15): requests/s,
                # 5xx/s, sheds/s over 1m and 5m — floats, so they
                # render as gauges in the Prometheus view for free
                payload["rates"] = sset.rates.snapshot()
                # registry reachability counters (PR 19); the string
                # state key is JSON-only, the totals render as gauges
                from modelx_tpu.dl import manifest_cache as _mc

                payload["control_plane"] = _mc.health().status()
                if sset.device_telemetry:
                    # measured device memory next to the lifecycle
                    # ESTIMATES (hbm_reserved_bytes), plus what jax found
                    # (platform / device_kind / device_count): the string
                    # keys are skipped by the text renderer, kept in JSON
                    # so a reader knows what was measured and how
                    payload["device"] = devmem.sample()
                if compile_cache_dir():
                    payload["compile_cache"] = compile_cache_stats()
                started = trace.startup.snapshot()
                if started:
                    # process creation -> ready by stage (utils/trace.py):
                    # the stages sum to ready_s
                    payload["startup"] = started
                fmt = _query_param(self.path, "format")
                text = promexp.wants_prometheus(self.headers.get("Accept"), fmt)
                if sset.profile_capture is not None:
                    # the newest profiler capture by the pod's own clock, with
                    # the engines' counters at its two edges; the text view
                    # leaves the two dumps out (a second series a counter)
                    payload["profile"] = {
                        k: v for k, v in sset.profile_capture.items()
                        if not (text and k in ("at_start", "at_stop"))}
                # content negotiation (ISSUE 13): the SAME tree renders
                # as Prometheus text on Accept: text/plain or
                # ?format=prometheus; the default JSON is byte-unchanged
                if text:
                    # the second rule labels the per-device HBM breakdown
                    # (payload["device"]["devices"][i]) with device="<i>"
                    # instead of minting one metric name per device index
                    self._text(200, promexp.render(
                        payload, label_levels={
                            ("*",): "model",
                            ("*", "devices", "*"): "device",
                        }),
                        promexp.CONTENT_TYPE)
                else:
                    self._json(200, payload)
            elif self.path == "/admin/models":
                if not self._admin_auth():
                    return
                from modelx_tpu.dl import manifest_cache

                self._json(200, {
                    "models": sset.pool.states(),
                    "pool": sset.pool.pool_snapshot(),
                    # per-model serving load + locality stats: the fleet
                    # router ranks stickiness (prefix-cache state) and
                    # load (queue depth) from THIS one endpoint instead of
                    # scraping /metrics too (PR 8)
                    "serving": sset.serving_stats(),
                    # registry reachability (PR 19): ok|degraded|offline —
                    # the rebalancer reads this to go observe-only when
                    # the whole fleet has lost the control plane
                    "control_plane": manifest_cache.health().status(),
                })
            elif self.path == "/v1/models":
                from modelx_tpu.dl import openai_api as oai

                # one body, two contracts: the native {default, models} keys
                # plus OpenAI's {object: "list", data: [...]}
                self._json(200, oai.models_payload(sset))
            elif self.path.split("?", 1)[0] == "/v1/trace":
                if _query_param(self.path, "startup") == "1":
                    # process creation -> first token as one list of spans
                    # in start order (utils/trace.Startup.timeline)
                    return self._json(200, trace.startup.timeline())
                # ?request_id= filters the summary to one request's
                # timeline; ?prefix= narrows by span path (both optional)
                self._json(200, trace.tracer().summary(
                    prefix=_query_param(self.path, "prefix"),
                    request_id=_query_param(self.path, "request_id"),
                ))
            elif self.path.split("?", 1)[0] == "/debug/flightrec":
                # the live flight-recorder ring (ISSUE 15): the same
                # timeline the black-box dump freezes, served while the
                # engine is still flying. Admin-gated — events carry
                # request ids — with /v1/trace's ?request_id= slicing.
                if not self._admin_auth():
                    return
                rid = _query_param(self.path, "request_id") or None
                body = {}
                for n, cb in list(sset.cbatchers.items()):
                    if cb.flightrec is not None:
                        body[n] = cb.flightrec.summary(rid)
                # pool-level ring: tier promotions/demotions, OOM retries
                body["pool"] = sset.pool.flightrec.summary(rid)
                self._json(200, body)
            else:
                self._json(404, {"error": "not found"})

        def do_POST(self):
            # pod-level in-flight accounting for coordinated drain: a
            # SIGTERM'd pod stops admitting (ready flips false) and waits
            # for this count — streams included, until their LAST byte —
            # to reach zero before closing engines (serve_main's
            # --drain-grace loop), instead of sleeping a fixed interval
            sset.request_began()
            # end-to-end request identity (ISSUE 13): honor the router's
            # (or client's) id, mint one for direct traffic; the id binds
            # every span this handler thread closes, echoes on the
            # response, and threads into the engine ticket
            self._rid = (parse_request_id(self.headers.get(REQUEST_ID_HEADER))
                         or mint_request_id())
            self._attempt = parse_attempt(self.headers.get(ATTEMPT_HEADER))
            self._timing = {}
            self._resp_status = 0
            self._log_model = ""
            self._t0 = time.monotonic()
            path = self.path.split("?", 1)[0]
            try:
                with trace.request_context(self._rid), \
                        trace.span("serve.request", http_path=path,
                                   attempt=self._attempt):
                    self._do_POST()
            finally:
                sset.request_ended()
                # windowed fleet rates (ISSUE 15): one mark per request
                # plus outcome classes, bucketed into 1-s wheels the
                # /metrics snapshot reads as *_per_s_{1m,5m}
                sset.rates.mark("requests")
                if self._resp_status >= 500:
                    sset.rates.mark("http_5xx")
                elif self._resp_status == 429:
                    sset.rates.mark("sheds")
                if access is not None:
                    access.write(
                        request_id=self._rid,
                        attempt=self._attempt,
                        client=_client_hash(self.headers,
                                            self.client_address),
                        path=path,
                        model=self._log_model,
                        status=self._resp_status,
                        ms=round((time.monotonic() - self._t0) * 1e3, 3),
                        timing=self._timing,
                    )

        def _profile(self, req: dict, key: str, out_dir, dir_key: str,
                     admin: bool = False):
            """One profiler capture of ``req[key]`` seconds into
            ``out_dir()``: one at a time, python tracer off unless the body
            says ``"python_tracer": true`` (utils/trace.jax_profile). The
            admin surface refuses 0 seconds and echoes the duration."""
            try:
                seconds = float(req.get(key, 3))
            except (TypeError, ValueError):
                seconds = -1.0
            if not (0 <= seconds <= MAX_PROFILE_SECONDS) or (admin and not seconds):
                return self._json(
                    400,
                    {"error": f"{key} must be a number in {'(' if admin else '['}0, "
                              f"{MAX_PROFILE_SECONDS}]"},
                )
            def engines() -> dict:
                # the shape of /metrics itself, the engines' counters alone
                return {n: {"continuous": cb.snapshot()}
                        for n, cb in list(sset.cbatchers.items())}

            if not sset._profiling.acquire(blocking=False):
                return self._json(409, {"error": "profile already running"})
            try:
                path = out_dir()
                # the pod alone knows where the device trace begins and ends:
                # the engines' counters are read at both edges, inside the
                # profiler's own start and stop, and the two calls are timed
                t_call = time.monotonic()
                with trace.jax_profile(
                        path, python_tracer=req.get("python_tracer") is True):
                    t_on = time.monotonic()
                    at_start = engines()
                    time.sleep(seconds)
                    at_stop = engines()
                    t_off = time.monotonic()
                captures = (sset.profile_capture or {"captures": 0})["captures"] + 1
                sset.profile_capture = {
                    "captures": captures, "start_s": round(t_on - t_call, 6),
                    "stop_s": round(time.monotonic() - t_off, 6),
                    "traced_s": round(t_off - t_on, 6),
                    "at_start": at_start, "at_stop": at_stop}
            finally:
                sset._profiling.release()
            return self._json(200, {dir_key: path, **({key: seconds} if admin else {})})

        def _do_POST(self):
            length = int(self.headers.get("Content-Length", 0) or 0)
            try:
                req = json.loads(self.rfile.read(length)) if length else {}
            except ValueError as e:
                return self._json(400, {"error": f"bad request: {e}"})

            if not isinstance(req, dict):
                # a non-object body ({"tokens": ...} is the contract) must be
                # a 400, not an uncaught TypeError that drops the connection
                return self._json(400, {"error": "request body must be a JSON object"})

            if self.path == "/v1/profile":
                return self._profile(req, "seconds", lambda: sset.trace_dir,
                                     "trace_dir")
            if self.path == "/admin/profile":
                # on-demand XLA profiler capture (ISSUE 15): same
                # one-at-a-time lock as /v1/profile, but admin-gated and
                # writing into a fresh CAPPED capture dir (the oldest
                # captures age out) so repeated captures on a live pod
                # never grow the disk without bound
                if not self._admin_auth():
                    return
                return self._profile(req, "duration_s", sset.next_capture_dir,
                                     "capture_dir", admin=True)

            if self.path == "/admin/models":
                # runtime load: pull a registry ref (or point at a local
                # dir) and materialize it while traffic is live
                if not self._admin_auth():
                    return
                from modelx_tpu.dl.lifecycle import PoolError

                wait = bool(req.get("wait", False))
                try:
                    snap = sset.pool.request_load(
                        str(req.get("name") or ""),
                        ref=str(req.get("ref") or ""),
                        model_dir=str(req.get("model_dir") or ""),
                        wait=wait,
                    )
                except PoolError as e:
                    # a 507 that demotion could clear carries Retry-After
                    # (ISSUE 18's 507 contract); hard refusals carry none
                    return self._json(e.status, {"error": str(e)},
                                      headers=e.headers or None)
                return self._json(200 if wait else 202, snap)

            if self.path in ("/v1/completions", "/v1/chat/completions"):
                return self._openai(req, chat=self.path.endswith("chat/completions"))

            server, verb = sset.resolve(self.path)
            if server is not None:
                self._log_model = server.name
            if server is None:
                # a name the routing set doesn't know may still be a
                # lifecycle entry: PULLING/LOADING answers 503 +
                # Retry-After (it will be READY shortly), DRAINING 409,
                # FAILED 503 + reason; only truly unknown names 404
                name = sset.route_name(self.path)
                err = (
                    sset.pool.routing_error(name)
                    if (sset.pool is not None and name) else None
                )
                if err is not None:
                    return self._json(err.http_status, {"error": str(err)},
                                      headers=err.headers())
                return self._json(404, {"error": "not found"})
            try:
                # lifecycle gate for resolved models too: DRAINING models
                # still sit in the routing set while in-flight requests
                # finish, but must not admit new ones (409)
                if sset.pool is not None:
                    sset.pool.check_admission(server.name)
            except ServingError as e:
                return self._json(e.http_status, {"error": str(e)},
                                  headers=e.headers())
            # deadline propagation (ISSUE 9): the router stamps each
            # upstream attempt's REMAINING budget — a failover hop must
            # not restart the clock. Already-expired budgets 504 before
            # any tokenization or engine work; live ones clamp the
            # engine's own --request-timeout below.
            timeout_s = propagated_timeout(self.headers)
            priority = request_priority(self.headers)
            if timeout_s is not None and timeout_s <= 0:
                e = DeadlineExceededError("admitting", timeout_s)
                return self._json(e.http_status, {"error": str(e)},
                                  headers=e.headers())
            if "text" in req and "tokens" in req:
                # generating from the tokens while silently dropping the text
                # would answer the wrong prompt; make the caller pick one
                return self._json(400, {"error": "send either text or tokens, not both"})
            if "text" in req and verb != "generate":
                # text is a generate-only contract (docs/api.md); a typo'd
                # endpoint must not return an undocumented hybrid response
                return self._json(400, {"error": "text is only supported on generate"})
            try:
                tok = None
                if "text" in req:
                    # text in, text out — needs the model's tokenizer.json
                    if not isinstance(req["text"], str) or not req["text"]:
                        raise ValueError("text must be a non-empty string")
                    if bool(req.get("stream", False)):
                        return self._json(400, {
                            "error": "text with stream is not supported; send token ids"
                        })
                    try:
                        tok = server.tokenizer()
                    except RuntimeError as e:  # file exists, load failed
                        return self._json(503, {"error": str(e)})
                    if tok is None:
                        return self._json(400, {
                            "error": "model has no tokenizer.json; send token ids"
                        })
                    ids = tok.encode(req["text"])
                    if not ids:
                        raise ValueError("text tokenized to zero tokens")
                    tokens = np.asarray([ids], np.int32)
                else:
                    tokens = np.asarray(req["tokens"], np.int32)
                if tokens.ndim != 2 or tokens.shape[0] < 1 or tokens.shape[1] < 1:
                    raise ValueError(
                        f"tokens must be non-empty 2-D [batch, seq], got shape {tokens.shape}"
                    )
            except (ValueError, KeyError, TypeError, OverflowError) as e:
                # numpy raises OverflowError for ids outside int32 and
                # TypeError for null/ragged rows — those are 400s, not a
                # dropped connection
                return self._json(400, {"error": f"bad request: {e}"})
            if not server.ready:
                # 503 + Retry-After, like the 429 shed path: load
                # balancers and the retrying RegistryClient back off and
                # come back once the load lands READY
                e = ModelLoadingError(server.name)
                return self._json(e.http_status, {"error": str(e)},
                                  headers=e.headers())
            vocab = getattr(server.cfg, "vocab_size", 0) or 0
            if vocab and (int(tokens.min()) < 0 or int(tokens.max()) >= vocab):
                # inside jit the gather CLAMPS out-of-range ids (silent
                # garbage); this also catches a tokenizer.json whose vocab
                # outgrew the checkpoint's embedding table
                return self._json(400, {"error": f"token ids must be in [0, {vocab})"})
            n_pos = getattr(server.cfg, "n_positions", 0) or 0
            if n_pos and tokens.shape[1] > n_pos:
                # absolute-position families (gpt2 wpe): the position gather
                # would clamp inside jit past n_positions and return
                # plausible garbage — same failure mode as the vocab check
                return self._json(400, {
                    "error": f"prompt length {tokens.shape[1]} exceeds the "
                    f"model's {n_pos}-position context"
                })
            server.stats["requests"] += 1
            if sset.pool is not None:
                # in-flight accounting: the pool's drain waits for this
                # request to finish before freeing the model (streams
                # complete inside this handler, so exit() fires after the
                # last chunk is on the wire); a drain that started since
                # the admission check above refuses here instead (409)
                try:
                    sset.pool.enter(server.name)
                except ServingError as e:
                    return self._json(e.http_status, {"error": str(e)},
                                      headers=e.headers())
            try:
                if verb == "forward":
                    if req.get("logits_at") is None:
                        batcher = sset.batcher_for(server)
                        out = (batcher or server).forward_argmax(tokens)
                        self._json(200, {"logits_argmax": out.tolist()})
                    else:
                        # optional: also the float32 logits of the named
                        # positions, so that the served forward can be held
                        # against a reference (raw little-endian, base64)
                        try:
                            at = np.asarray(req["logits_at"], np.int32).reshape(-1)
                            if at.size < 1 or at.min() < 0 or at.max() >= tokens.shape[1]:
                                raise ValueError(f"positions must lie in [0, {tokens.shape[1]})")
                        except (ValueError, TypeError, OverflowError) as e:
                            return self._json(400, {"error": f"bad logits_at: {e}"})
                        out, logits = server.forward_logits(tokens, at)
                        self._json(200, {"logits_argmax": out.tolist(), "logits": {
                            "dtype": "float32", "shape": list(logits.shape),
                            "positions": at.tolist(),
                            "b64": base64.b64encode(logits.tobytes()).decode("ascii")}})
                else:
                    try:
                        n = int(req.get("max_new_tokens", 16))
                    except (TypeError, ValueError):
                        return self._json(400, {"error": "max_new_tokens must be an integer"})
                    if not (1 <= n <= sset.max_new_tokens_limit):
                        # an unauthenticated client must not be able to force
                        # a huge compile / HBM alloc with one request
                        return self._json(
                            400,
                            {
                                "error": "max_new_tokens must be in "
                                f"[1, {sset.max_new_tokens_limit}]"
                            },
                        )
                    if n_pos and tokens.shape[1] + n > n_pos:
                        # decode past n_positions would silently clamp the
                        # wpe gather (gpt2.py:101)
                        return self._json(400, {
                            "error": f"prompt ({tokens.shape[1]}) + "
                            f"max_new_tokens ({n}) exceeds the model's "
                            f"{n_pos}-position context"
                        })
                    try:
                        samp = {
                            "temperature": float(req.get("temperature", 0.0)),
                            "top_k": int(req.get("top_k", 0)),
                            "top_p": float(req.get("top_p", 1.0)),
                            "seed": int(req.get("seed", 0)),
                        }
                    except (TypeError, ValueError):
                        return self._json(
                            400, {"error": "temperature/top_k/top_p/seed must be numbers"}
                        )
                    if (
                        not (0.0 <= samp["temperature"] <= 100.0)
                        or not (0 <= samp["top_k"] < 2**31)
                        or not (0.0 < samp["top_p"] <= 1.0)
                        or not (0 <= samp["seed"] < 2**31)
                        # int32 vectors carry these on device; out-of-range
                        # values must 400 here, not overflow a whole batch
                    ):
                        return self._json(400, {
                            "error": "temperature in [0,100], top_k/seed in "
                            "[0, 2^31), top_p in (0,1] required"
                        })
                    stop_ids = req.get("stop_token_ids")
                    if stop_ids is not None:
                        if (
                            not isinstance(stop_ids, list)
                            or len(stop_ids) > 16
                            or not all(isinstance(t, int) and not isinstance(t, bool)
                                       and 0 <= t < (vocab or 2**31) for t in stop_ids)
                        ):
                            return self._json(400, {
                                "error": "stop_token_ids must be a list of up "
                                "to 16 in-vocab token ids"
                            })
                    # mid-stream failover resume (ISSUE 12): both surfaces
                    # carry the same block — X-ModelX-Resume-* headers (the
                    # router's continuation path) win over the native
                    # ``resume`` field (a resumed client request that is
                    # itself being continued keeps the router's LONGER
                    # emitted list); each surface is both-or-neither
                    resume = None
                    resume_step = 0
                    try:
                        hdr_e = self.headers.get(RESUME_EMITTED_HEADER)
                        hdr_s = self.headers.get(RESUME_SEED_HEADER)
                        if hdr_e is not None or hdr_s is not None:
                            resume = parse_resume(hdr_e, hdr_s)
                        else:
                            rz = req.get("resume")
                            if rz is not None:
                                if not isinstance(rz, dict):
                                    raise MalformedResumeError(
                                        "resume must be an object with "
                                        "emitted + seed")
                                resume = parse_resume(rz.get("emitted"),
                                                      rz.get("seed"))
                        if resume is not None:
                            emitted, rseed = resume
                            if (not bool(req.get("stream", False))
                                    or tokens.shape[0] != 1):
                                raise MalformedResumeError(
                                    "resume requires a single-row "
                                    "streaming request")
                            if vocab and max(emitted) >= vocab:
                                raise MalformedResumeError(
                                    f"emitted token ids must be in "
                                    f"[0, {vocab})")
                            if len(emitted) >= n:
                                # the original stream was COMPLETE — the
                                # router finishes the client stream (done
                                # line) instead of re-decoding anything
                                raise ResumeExhaustedError(
                                    f"{len(emitted)} tokens already "
                                    f"emitted of a {n}-token budget")
                            if stop_ids and any(t in stop_ids
                                                for t in emitted):
                                raise ResumeExhaustedError(
                                    "a stop token was already emitted")
                    except ServingError as e:
                        return self._json(e.http_status, {"error": str(e)},
                                          headers=e.headers())
                    if resume is not None:
                        # re-prefill prompt + emitted (chunked prefill and
                        # the prefix cache apply unchanged) and continue
                        # the ORIGINAL (seed, step) sample stream at step
                        # k; resume.seed pins the effective seed — the
                        # OpenAI surface derives a random one when the
                        # request omits it, and a continuation must not
                        samp["seed"] = rseed
                        resume_step = len(emitted)
                        tokens = np.concatenate(
                            [tokens, np.asarray([emitted], np.int32)],
                            axis=1)
                        n -= resume_step
                    if bool(req.get("stream", False)):
                        if stop_ids and tokens.shape[0] > 1:
                            # per-row early stop breaks the [B, k]-aligned
                            # stream contract; refuse rather than silently
                            # return untrimmed rows
                            return self._json(400, {
                                "error": "stop_token_ids with stream is "
                                "single-row only"
                            })
                        return self._stream_generate(
                            server, tokens, n, samp, stop_ids,
                            timeout_s=timeout_s, priority=priority,
                            resume_step=resume_step,
                            include_timing=bool(
                                req.get("include_timing", False)))
                    engine = sset.engine_for(
                        server, tokens.shape[0], samp["temperature"]
                    )
                    if engine is sset.cbatchers.get(server.name):
                        # the continuous engine honors stops server-side:
                        # every row's slot frees at its stop token (short
                        # rows come back padded with the stop; the trim
                        # below cuts at the FIRST stop either way) — and
                        # the propagated deadline remainder clamps the
                        # per-request expiry
                        out = engine.generate(tokens, max_new_tokens=n,
                                              stop_token_ids=stop_ids,
                                              timeout_s=timeout_s,
                                              priority=priority,
                                              timing=self._timing, **samp)
                    else:
                        out = engine.generate(tokens, max_new_tokens=n, **samp)
                    self._first_token()  # unstreamed: the first token is the answer
                    rows = out.tolist()
                    if stop_ids:
                        # trim each row's GENERATED portion at the first stop
                        # token (inclusive) — response rows may be ragged
                        from modelx_tpu.models.decode import stop_cut

                        stops = set(stop_ids)
                        plen = tokens.shape[1]
                        trimmed = []
                        for row in rows:
                            gen_part = row[plen:]
                            cut = stop_cut(gen_part, stops)
                            if cut is not None:
                                gen_part = gen_part[:cut]
                            trimmed.append(row[:plen] + gen_part)
                        rows = trimmed
                    resp = {"tokens": rows}
                    if tok is not None:  # text request: decode the new tokens
                        resp["text"] = tok.decode(rows[0][tokens.shape[1]:])
                    self._json(200, resp)
            except ValueError as e:  # e.g. generate on a non-generative family
                self._json(400, {"error": str(e)})
            except ServingError as e:
                # typed serving failures carry their canonical status:
                # 429 (queue full, + Retry-After), 504 (deadline),
                # 503 (engine broken/restarting), 400 (quarantined)
                self._json(e.http_status, {"error": str(e)}, headers=e.headers())
            except Exception as e:  # surface inference errors as 500 JSON
                logger.exception("inference error")
                self._json(500, {"error": str(e)})
            finally:
                if sset.pool is not None:
                    sset.pool.exit(server.name)

        def do_DELETE(self):
            """DELETE /admin/models/{name}: drain in-flight requests, stop
            admission (new requests 409 while draining, 404 once gone),
            then free params, KV/page pools, compiled programs, and
            pool-owned staging. ``?wait=0`` returns 202 immediately and
            drains in the background."""
            m = _ADMIN_MODEL_ROUTE.match(self.path)
            if m is None:
                return self._json(404, {"error": "not found"})
            if not self._admin_auth():
                return
            from urllib.parse import parse_qs, urlparse

            from modelx_tpu.dl.lifecycle import PoolError

            if not sset.pool.allow_admin_load:
                return self._json(403, {
                    "error": "admin model unloading is disabled "
                             "(start with --allow-admin-load)"
                })
            q = parse_qs(urlparse(self.path).query)
            wait = q.get("wait", ["1"])[0] not in ("0", "false")
            try:
                snap = sset.pool.request_unload(m.group("model"), wait=wait)
            except PoolError as e:
                return self._json(e.status, {"error": str(e)},
                                  headers=e.headers or None)
            return self._json(200 if wait else 202, snap)

    host, _, port = listen.rpartition(":")
    httpd = ThreadingHTTPServer((host or "0.0.0.0", int(port)), Handler)
    httpd.daemon_threads = True
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    return httpd
