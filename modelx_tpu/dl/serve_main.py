"""`modelx-serve` console entrypoint: the serving container's command
(referenced by dl/podspec.py's generated pod spec).

Single model:      modelx-serve --model-dir /mnt/model
Multi-tenant:      modelx-serve --model a=/mnt/a --model b=/mnt/b
                   (BASELINE config #5: concurrent pull+serve of N models)
"""

from __future__ import annotations

import logging
import os
import signal
import threading
import time

import click
import jax

from modelx_tpu.dl.serve import ModelServer, ServerSet, enable_compile_cache, serve
from modelx_tpu.utils import trace


def exit_when_parent_is_gone(poll_s: float = 0.25) -> None:
    """Watch the process that started this one and leave when it is gone
    (``--exit-with-parent``): a daemon thread polls the parent pid — the
    kernel re-parents an orphan, so a change is the parent's death, however
    it died — and ends the process with ``os._exit``: no drain, no handlers,
    the chip and the port are free when the kernel has reaped it. A watch,
    not ``PR_SET_PDEATHSIG``: that signal follows the THREAD that forked,
    and a harness may start pods from a worker thread that ends early."""
    parent = os.getppid()
    if parent <= 1:
        logging.getLogger("modelx.serve").warning(
            "--exit-with-parent: started by pid %d (an init): nothing to watch", parent)
        return

    def watch() -> None:
        while os.getppid() == parent:
            time.sleep(poll_s)
        os._exit(0)

    threading.Thread(target=watch, name="parent-watch", daemon=True).start()


@click.command("modelx-serve")
@click.option("--model-dir", default="", help="volume with *.safetensors (from modelx dl)")
@click.option("--model", "models", multiple=True,
              help="name=dir; repeatable for multi-tenant serving")
@click.option("--mesh", default="", help='mesh spec, e.g. "dp=1,tp=8" (default: dp over all devices)')
@click.option("--dtype", default="bfloat16", type=click.Choice(["bfloat16", "float32"]))
@click.option("--listen", default=":8000")
@click.option("--max-seq-len", default=2048, type=int)
@click.option("--compile-cache/--no-compile-cache", default=True,
              help="persistent XLA compilation cache (restart TTFT)")
@click.option("--blob-cache-dir", default="",
              help="content-addressed local blob cache for registry-backed "
                   "loads (dl/blob_cache.py): warm restarts of an "
                   "already-served checkpoint skip the network")
@click.option("--blob-cache-max-bytes", default=0, type=int,
              help="blob cache size cap; LRU eviction (0 = unbounded)")
@click.option("--concurrent-load", is_flag=True, help="overlap multi-model loads")
@click.option("--trace-dir", default="", help="jax profiler output dir (/v1/profile)")
@click.option("--dynamic-batch", is_flag=True,
              help="coalesce concurrent forward requests into one device call")
@click.option("--continuous-batch", is_flag=True,
              help="iteration-level (in-flight) batching: generate/stream "
                   "requests join a running decode at chunk boundaries "
                   "(supersedes --dynamic-batch for generate traffic; "
                   "composes with --speculative-k: a lone greedy row "
                   "speculates inside the engine)")
@click.option("--max-slots", default=8, type=int,
              help="continuous batching: concurrent decode slots (KV cache "
                   "rows held on device)")
@click.option("--kv-page-size", default=0, type=int,
              help="continuous batching: paged KV — the engine state becomes "
                   "a pool of PAGE_SIZE-token pages + a block table, so HBM "
                   "scales with live tokens instead of max_slots x "
                   "max_seq_len (use with --max-slots 16+; 0 = dense)")
@click.option("--kv-live-tokens", default=0, type=int,
              help="paged KV: pool capacity in tokens (default "
                   "max_slots x max_seq_len / 4)")
@click.option("--kv-attention", default="gather",
              type=click.Choice(["gather", "in-place"]),
              help="paged KV chunk attention: 'gather' (default) is "
                   "bit-exact vs every other decode path; 'in-place' reads "
                   "the page pools directly (blockwise softmax, per-step "
                   "transient = one page block — long-context deployments; "
                   "sampled rows may flip at bf16 near-ties)")
@click.option("--max-batch", default=32, type=int,
              help="dynamic batching: max requests coalesced per device call")
@click.option("--batch-window-ms", default=3.0, type=float,
              help="dynamic batching: how long a request waits for "
                   "companions (latency/throughput dial)")
@click.option("--stream-chunk-size", default=8, type=int,
              help="tokens decoded per flush on streaming responses (also "
                   "the continuous engine's chunk length)")
@click.option("--pipeline-depth", default=2, type=int,
              help="continuous batching: decode chunks kept in flight "
                   "before syncing the oldest — hides the dispatch/fetch "
                   "round-trip behind device compute (stop-token and "
                   "disconnect exits lag by up to DEPTH chunks of wasted "
                   "compute; 1 = classic lockstep)")
@click.option("--dispatch-depth", default=0, type=int,
              help="continuous batching: decode chunks scanned per device "
                   "program — in steady decode (no admission, prefill "
                   "piece, or stream flush due) the engine dispatches "
                   "DEPTH x stream-chunk-size steps per call, amortizing "
                   "the fixed dispatch round-trip DEPTH-fold; any pending "
                   "boundary event snaps back to per-chunk dispatch. "
                   "EOS/cancel/deadline detection lags by up to the "
                   "program's span (wasted compute, never wrong tokens — "
                   "outputs stay byte-exact and streams keep per-chunk "
                   "flush granularity). 0 = auto (4 in steady decode); "
                   "1 = classic per-chunk dispatch")
@click.option("--burst-window-ms", default=1.0, type=float,
              help="continuous batching: when a request hits an IDLE "
                   "engine, wait this long for co-arrivals so the burst "
                   "admits as one program and decodes in step (0 = off)")
@click.option("--prefill-chunk", default=0, type=int,
              help="continuous batching: chunked prefill — prompts longer "
                   "than this many tokens (16-bucketed) land piece by "
                   "piece between decode chunks instead of as one "
                   "monolithic admission prefill, bounding the inter-token "
                   "latency jitter a long admission inflicts on the "
                   "running batch (0 = off)")
@click.option("--prefill-budget", default=0, type=int,
              help="chunked prefill: per-boundary token budget — decode "
                   "rows spend chunk_size each first, prefill pieces pack "
                   "into the remainder (the head piece always lands; "
                   "0 = one piece per filling row per boundary)")
@click.option("--max-queue-depth", default=0, type=int,
              help="continuous batching: bound the admission backlog — a "
                   "submit past this many not-yet-admitted rows is shed "
                   "with 429 + Retry-After instead of queueing into "
                   "unbounded latency (0 = unbounded)")
@click.option("--request-timeout", default=0.0, type=float,
              help="continuous batching: per-request deadline in seconds — "
                   "a request older than this expires with 504 at the next "
                   "chunk boundary, whether it is still queued, prefilling, "
                   "or decoding (0 = no deadline)")
@click.option("--prefix-cache", default=0, type=int,
              help="keep the prefill KV of the last N single-row stream "
                   "prompts on device: multi-turn chats that re-send their "
                   "history prefill only the new suffix (0 = off)")
@click.option("--prefix-cache-max-bytes", default=0, type=int,
              help="additional BYTE cap on the prefix cache's stored KV "
                   "(entry count alone over-commits HBM for long "
                   "prefixes; 0 = entry cap only)")
@click.option("--quantize", type=click.Choice(["int8"]), default=None,
              help="weight-only int8: half the HBM/transfer bytes for the big matmuls")
@click.option("--speculative-k", default=0, type=int,
              help="prompt-lookup speculative decoding for single-row greedy "
                   "requests: verify up to K proposed tokens per device step "
                   "(token-exact; 0 = off)")
@click.option("--lora", "loras", multiple=True, metavar="NAME=ADAPTER_DIR",
              help="merge a PEFT-style LoRA adapter into model NAME at load "
                   "('default' for --model-dir); repeatable")
@click.option("--hbm-budget-bytes", default=0, type=int,
              help="model lifecycle pool: PER-DEVICE memory budget — a "
                   "runtime load whose estimated per-device footprint "
                   "(manifest/safetensors sizes divided by the mesh's "
                   "weight-shard factor: tp*ep*pp*fsdp) does not fit is "
                   "refused with 507, or makes room by LRU-evicting idle "
                   "models under --evict-idle (0 = unbudgeted)")
@click.option("--evict-idle", is_flag=True,
              help="with --hbm-budget-bytes: LRU-evict READY models that "
                   "have no in-flight requests to make room for a new load "
                   "instead of refusing it")
@click.option("--host-state-budget-bytes", default=0, type=int,
              help="tiered live state (dl/tiers.py): bound for the host-RAM "
                   "tier that evicted/unloaded models' params demote into "
                   "instead of being discarded — a later load of the same "
                   "content is a tier promotion (device_put, no pull/parse). "
                   "LRU within the tier; overflow spills to the disk tier "
                   "(0 = host tier off)")
@click.option("--disk-state-budget-bytes", default=0, type=int,
              help="bound for the local-disk tier (decoded-tensor spool "
                   "under --state-spool-dir) that host-tier overflow spills "
                   "into; disk overflow drops oldest (0 = disk tier off; "
                   "both 0 = tiering off, eviction discards as before)")
@click.option("--state-spool-dir", default="",
              help="where the disk tier spools decoded tensors — put it "
                   "next to --blob-cache-dir (default: "
                   "$TMPDIR/modelx-state-spool)")
@click.option("--allow-admin-load", is_flag=True,
              help="enable the runtime lifecycle surface: POST "
                   "/admin/models pulls+loads a registry ref while traffic "
                   "is live, DELETE /admin/models/{name} drains and frees "
                   "one (GET /admin/models always reports states)")
@click.option("--publish-programs", is_flag=True,
              help="after a runtime (registry-ref) load reaches READY, "
                   "export the pod's compiled programs and attach them to "
                   "the model version as a program bundle "
                   "(application/vnd.modelx.program.v1) so the next "
                   "puller boots compile-warm")
@click.option("--registry-mirror", "registry_mirrors", multiple=True,
              help="read mirror(s) of the registry (comma list; "
                   "repeatable): manifest/blob GETs fail over to them and "
                   "ranged blob reads hedge across them — writes (publish) "
                   "always go to the primary (docs/serving.md outage "
                   "playbook)")
@click.option("--manifest-cache-dir", default="",
              help="pin every fetched manifest to this dir: when the "
                   "registry AND all mirrors are down, digest-pinned "
                   "cached manifests + the blob cache serve pulls offline "
                   "(control_plane: offline on /healthz; readiness is "
                   "never gated on it)")
@click.option("--publish-kv", is_flag=True,
              help="sweep the prefix caches of runtime (registry-ref) "
                   "loaded models for entries hit at least "
                   "--kv-publish-threshold times and attach them to the "
                   "model version as kv bundles "
                   "(application/vnd.modelx.kvcache.v1) so replicas skip "
                   "re-prefilling shared prompt prefixes (docs/kv.md)")
@click.option("--kv-publish-threshold", default=2, type=int,
              help="prefix-cache hit count at which an entry becomes hot "
                   "enough to publish (with --publish-kv)")
@click.option("--kv-fetch-through", is_flag=True,
              help="on a prefix-cache miss, consult the model version's "
                   "published kv bundles and install a matching prefix "
                   "(bounded by --prefix-cache-max-bytes; runtime loads "
                   "only)")
@click.option("--publish-outbox-dir", default="",
              help="durable publish outbox: --publish-programs and "
                   "--publish-kv bundles spool here and a background "
                   "drainer pushes them with backoff, so a registry outage "
                   "never blocks or fails a load (pending entries survive "
                   "pod restarts)")
@click.option("--outbox-max-entries", default=0, type=int,
              help="outbox spool bound; a full spool drops new publishes "
                   "with a counted warning (0 = default 64)")
@click.option("--admin-token", "admin_tokens", multiple=True,
              help="bearer token accepted on the /admin surface "
                   "(repeatable; none = anonymous admin — dev pods only)")
@click.option("--staging-dir", default="",
              help="where runtime-pulled model blobs land before loading "
                   "(default: $TMPDIR/modelx-pool-staging)")
@click.option("--drain-seconds", default=5.0, type=float,
              help="on SIGTERM, serve 503 on /healthz for this long (so load "
                   "balancers drain) before stopping")
@click.option("--drain-grace", default=0.0, type=float,
              help="coordinated drain: on SIGTERM, stop admission and wait "
                   "for in-flight requests (streams included, to their last "
                   "byte) to reach zero, up to this many seconds, instead "
                   "of the fixed --drain-seconds sleep. The fleet router "
                   "proactively CONTINUES this pod's live streams elsewhere "
                   "once /healthz reports draining (docs/router.md), so the "
                   "count drains fast (0 = fixed-sleep drain)")
@click.option("--boundary-watchdog-s", default=0.0, type=float,
              help="continuous batching: treat a device dispatch that makes "
                   "no chunk-boundary progress for this many seconds as a "
                   "crash — the engine's restart/breaker machinery applies "
                   "and waiters get EngineBrokenError instead of hanging "
                   "forever (a wedged TPU dispatch is otherwise silent; "
                   "0 = off). Size it well above the worst legitimate "
                   "boundary: first-request compiles run minutes on TPU")
@click.option("--access-log", default="",
              help="append one JSON line per request (request id, hashed "
                   "client identity, model, status, per-phase timing) to "
                   "this path; empty = off")
@click.option("--access-log-max-bytes", default=0, type=int,
              help="rotate the access log once it exceeds this many bytes "
                   "(renamed to <path>.1, one generation kept; 0 = never)")
@click.option("--flight-dump-dir", default="",
              help="continuous batching: on an engine crash, watchdog "
                   "fire, or circuit-break, write the flight recorder's "
                   "last events + per-slot state as a JSON-lines black-box "
                   "file here (the live ring is GET /debug/flightrec; "
                   "empty = no dump files)")
@click.option("--flightrec-capacity", default=0, type=int,
              help="flight recorder ring size in events (0 = default 512)")
@click.option("--flight-recorder/--no-flight-recorder", default=True,
              help="record structured engine events (admission, dispatch, "
                   "readback, preemption, EOS, deadline, crash) into a "
                   "bounded in-memory ring")
@click.option("--device-telemetry/--no-device-telemetry", default=True,
              help="sample measured device memory (jax memory_stats, "
                   "live-buffer census fallback) into /metrics and "
                   "/admin/models next to the lifecycle estimates")
@click.option("--exit-with-parent/--no-exit-with-parent", default=False,
              help="end this pod, at once and without a drain, when the "
                   "process that started it is gone — for a pod a harness or "
                   "a test starts, so that a killed driver leaves nothing on "
                   "the chip. Off by default: a pod in a container has no "
                   "such parent")
def main(model_dir: str, models: tuple[str, ...], mesh: str, dtype: str, listen: str,
         max_seq_len: int, compile_cache: bool,
         blob_cache_dir: str, blob_cache_max_bytes: int,
         concurrent_load: bool, trace_dir: str,
         dynamic_batch: bool, continuous_batch: bool, max_slots: int,
         kv_page_size: int, kv_live_tokens: int, kv_attention: str,
         max_batch: int, batch_window_ms: float, stream_chunk_size: int,
         pipeline_depth: int, dispatch_depth: int, burst_window_ms: float,
         prefill_chunk: int, prefill_budget: int,
         max_queue_depth: int, request_timeout: float,
         prefix_cache: int, prefix_cache_max_bytes: int,
         quantize: str | None, speculative_k: int,
         hbm_budget_bytes: int, evict_idle: bool,
         host_state_budget_bytes: int, disk_state_budget_bytes: int,
         state_spool_dir: str, allow_admin_load: bool,
         publish_programs: bool, publish_kv: bool,
         kv_publish_threshold: int, kv_fetch_through: bool,
         registry_mirrors: tuple[str, ...], manifest_cache_dir: str,
         publish_outbox_dir: str, outbox_max_entries: int,
         admin_tokens: tuple[str, ...], staging_dir: str,
         loras: tuple[str, ...], drain_seconds: float,
         drain_grace: float, boundary_watchdog_s: float,
         access_log: str, access_log_max_bytes: int,
         flight_dump_dir: str, flightrec_capacity: int,
         flight_recorder: bool, device_telemetry: bool,
         exit_with_parent: bool) -> None:
    if exit_with_parent:
        exit_when_parent_is_gone()
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    # `modelx serve-model` arrives with the CLI group's WARNING root already
    # configured (basicConfig above is then a no-op): a pod's start-up lines
    # — what it runs on, what it loaded — must print either way
    logging.getLogger("modelx").setLevel(logging.INFO)
    from modelx_tpu.parallel.distributed import initialize

    # process creation -> ready by stage (/metrics "startup"): everything up
    # to here — interpreter, imports, flag parsing — was `imports`
    trace.startup.begin("backend_init")
    trace.startup.sub("distributed")
    initialize()  # no-op single-process; wires multi-host TPU pods
    trace.startup.sub("devices")
    devices = jax.devices()  # the backend answers: on a TPU, seconds
    trace.startup.stage("configure")
    if compile_cache:
        enable_compile_cache()
    if blob_cache_dir:
        # process-default blob cache: every registry-backed load this
        # process performs (deploy-time pulls, re-loads) tees through it
        from modelx_tpu.dl.blob_cache import configure_default

        configure_default(blob_cache_dir, max_bytes=blob_cache_max_bytes)
    if registry_mirrors:
        # comma lists and repeats both accepted; process-wide so every
        # registry client this pod builds (pulls, tier keying, outbox
        # drains) fails over identically
        from modelx_tpu.client.remote import set_mirrors

        flat: list[str] = []
        for m in registry_mirrors:
            flat.extend(p.strip() for p in m.split(","))
        set_mirrors([m for m in flat if m])
    if manifest_cache_dir:
        from modelx_tpu.dl import manifest_cache

        manifest_cache.configure_default(manifest_cache_dir)
    entries: dict[str, str] = {}
    if model_dir:
        entries["default"] = model_dir
    for spec in models:
        name, _, path = spec.partition("=")
        if not path:
            raise click.UsageError(f"--model wants name=dir, got {spec!r}")
        entries[name] = path
    if not entries:
        raise click.UsageError("need --model-dir or at least one --model name=dir")
    lora_dirs: dict[str, str] = {}
    for spec in loras:
        name, _, path = spec.partition("=")
        if not path:
            raise click.UsageError(f"--lora wants NAME=ADAPTER_DIR, got {spec!r}")
        if name not in entries:
            raise click.UsageError(f"--lora {name!r}: no such --model")
        lora_dirs[name] = path
    if lora_dirs and quantize:
        # int8 quantizes exactly the 2-D proj weights LoRA targets; merging
        # into QTensors is rejected downstream — fail before the multi-GB
        # base streams to HBM, not after
        raise click.UsageError("--lora cannot combine with --quantize "
                               "(adapters merge into full-precision weights)")

    # one mesh shared by every tenant (same devices either way; sharing keeps
    # shardings comparable and avoids rebuilding device lists per model)
    from modelx_tpu.parallel.mesh import make_mesh

    shared_mesh = make_mesh(mesh) if mesh else make_mesh(f"dp={len(devices)}")
    from modelx_tpu.parallel.mesh import mesh_str, weight_shard_factor

    # what jax actually found: a pod whose accelerator did not come up runs
    # on the CPU backend and must say so (the same three fields ride the
    # /metrics "device" block, utils/devmem.py)
    logging.getLogger("modelx.serve").info(
        "devices: platform=%s kind=%s count=%d; serving mesh %s "
        "(%d device(s), weight shard factor %d)",
        devices[0].platform, devices[0].device_kind, len(devices),
        mesh_str(shared_mesh), shared_mesh.size,
        weight_shard_factor(shared_mesh),
    )
    servers = {
        name: ModelServer(path, dtype=dtype, max_seq_len=max_seq_len,
                          name=name, mesh=shared_mesh, quantize=quantize,
                          speculative_k=speculative_k,
                          lora_dir=lora_dirs.get(name, ""),
                          prefix_cache_size=prefix_cache,
                          prefix_cache_max_bytes=prefix_cache_max_bytes)
        for name, path in entries.items()
    }
    if continuous_batch and speculative_k:
        logging.getLogger("modelx.serve").info(
            "--continuous-batch + --speculative-k: the engine speculates "
            "whenever a single greedy row has the device to itself"
        )
    if prefill_chunk and not continuous_batch:
        logging.getLogger("modelx.serve").warning(
            "--prefill-chunk is inert without --continuous-batch "
            "(chunked prefill is the continuous engine's admission policy)"
        )
    if (max_queue_depth or request_timeout) and not continuous_batch:
        logging.getLogger("modelx.serve").warning(
            "--max-queue-depth/--request-timeout are inert without "
            "--continuous-batch (bounded admission is the continuous "
            "engine's submit policy)"
        )
    if prefix_cache and speculative_k and not continuous_batch:
        # the speculative decoder owns single-row streams before the
        # ChunkedDecoder (the prefix cache's stream seam) is consulted;
        # under --continuous-batch the engine's ADMISSION path uses the
        # prefix cache, so that combination is first-class
        logging.getLogger("modelx.serve").warning(
            "--prefix-cache is inert under --speculative-k "
            "(the speculative decoder handles the streams it would accelerate)"
        )
    sset = ServerSet(servers, trace_dir=trace_dir, dynamic_batch=dynamic_batch,
                     continuous_batch=continuous_batch, max_slots=max_slots,
                     max_batch=max_batch, batch_window_ms=batch_window_ms,
                     stream_chunk_size=stream_chunk_size,
                     kv_page_size=kv_page_size, kv_live_tokens=kv_live_tokens,
                     kv_attention=kv_attention, pipeline_depth=pipeline_depth,
                     dispatch_depth=dispatch_depth,
                     burst_window_ms=burst_window_ms,
                     prefill_chunk=prefill_chunk,
                     prefill_budget=prefill_budget,
                     max_queue_depth=max_queue_depth,
                     request_timeout_s=request_timeout,
                     boundary_watchdog_s=boundary_watchdog_s,
                     hbm_budget_bytes=hbm_budget_bytes,
                     evict_idle=evict_idle,
                     allow_admin_load=allow_admin_load,
                     admin_tokens=admin_tokens,
                     staging_root=staging_dir,
                     host_state_budget_bytes=host_state_budget_bytes,
                     disk_state_budget_bytes=disk_state_budget_bytes,
                     state_spool_dir=state_spool_dir,
                     flight_recorder=flight_recorder,
                     flightrec_capacity=flightrec_capacity,
                     flight_dump_dir=flight_dump_dir,
                     device_telemetry=device_telemetry)
    # runtime-loaded models get the same cache knobs the boot set got
    sset.server_defaults.update(
        prefix_cache_size=prefix_cache,
        prefix_cache_max_bytes=prefix_cache_max_bytes,
    )
    if (publish_programs or publish_kv) and publish_outbox_dir \
            and sset.pool is not None:
        sset.pool.attach_outbox(
            publish_outbox_dir,
            max_entries=outbox_max_entries or None,
        )
    if publish_programs:
        if sset.pool is not None:
            sset.pool.publish_programs = True
        if not allow_admin_load:
            logging.getLogger("modelx.serve").warning(
                "--publish-programs only fires on runtime (registry-ref) "
                "loads; without --allow-admin-load none happen — use "
                "`modelx programs push` to publish for boot-loaded models"
            )
    if publish_kv and sset.pool is not None:
        sset.pool.attach_kv_publisher(threshold=kv_publish_threshold)
        if not prefix_cache:
            logging.getLogger("modelx.serve").warning(
                "--publish-kv is inert without --prefix-cache "
                "(there is no prefix KV to publish)"
            )
    if kv_fetch_through and sset.pool is not None:
        sset.pool.kv_fetch_through = True
        if not prefix_cache:
            logging.getLogger("modelx.serve").warning(
                "--kv-fetch-through is inert without --prefix-cache "
                "(there is no prefix cache to install into)"
            )
    if evict_idle and not hbm_budget_bytes:
        logging.getLogger("modelx.serve").warning(
            "--evict-idle is inert without --hbm-budget-bytes "
            "(eviction only runs to fit a load under the budget)"
        )
    if publish_outbox_dir and not (publish_programs or publish_kv):
        logging.getLogger("modelx.serve").warning(
            "--publish-outbox-dir is inert without --publish-programs or "
            "--publish-kv (only derived-artifact publishes spool through "
            "the outbox)"
        )
    if state_spool_dir and not disk_state_budget_bytes:
        logging.getLogger("modelx.serve").warning(
            "--state-spool-dir is inert without --disk-state-budget-bytes "
            "(nothing spools to a 0-byte disk tier)"
        )
    trace.startup.stage("listener")
    httpd = serve(sset, listen=listen,  # starts serving 503s while loading
                  access_log=access_log,
                  access_log_max_bytes=access_log_max_bytes)
    trace.startup.stage("load")
    stats = sset.load_all(concurrent=concurrent_load)  # ends the last stage
    logging.getLogger("modelx.serve").info("models loaded: %s", stats)
    stop = threading.Event()
    abort = threading.Event()  # SIGINT: skip/cut short any drain window

    def _on_signal(num, _frame):
        if num == signal.SIGINT:
            abort.set()
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    stop.wait()
    # graceful drain: flip /healthz to 503 so the load balancer stops
    # routing here, give in-flight requests the drain window, then stop.
    # Only for SIGTERM (the LB-managed path); an interactive Ctrl-C —
    # whether it started the shutdown or lands MID-drain — exits now
    # (an Event wait, unlike time.sleep, isn't resumed after the handler)
    sset.draining = True
    if not abort.is_set() and drain_grace > 0:
        # coordinated drain: admission is off (ready is False -> /healthz
        # 503 "draining"), so the in-flight count only falls. The fleet
        # router sees DRAINING and proactively continues this pod's live
        # streams on other pods (token-exact resume), so streams hand off
        # instead of running to completion here. Exit as soon as the pod
        # is idle; the grace bound caps a stuck stream.
        log = logging.getLogger("modelx.serve")
        log.info("draining: waiting up to %.0fs for %d in-flight "
                 "request(s)", drain_grace, sset.inflight)
        deadline = time.monotonic() + drain_grace
        while sset.inflight > 0 and time.monotonic() < deadline:
            if abort.wait(timeout=0.05):
                break  # Ctrl-C mid-drain: exit now
        if sset.inflight > 0:
            log.warning("drain grace expired with %d request(s) still "
                        "in flight", sset.inflight)
    elif not abort.is_set() and drain_seconds > 0:
        logging.getLogger("modelx.serve").info(
            "draining for %.0fs before shutdown", drain_seconds)
        abort.wait(timeout=drain_seconds)
    # snapshot: requests during the drain window may still lazily create
    # batchers while this iterates
    for batcher in list(sset.batchers.values()):
        batcher.close()
    for cb in list(sset.cbatchers.values()):
        cb.close()
    if sset.pool is not None:
        # pending outbox entries stay on disk; the next generation's
        # drainer picks them up (that persistence is the point)
        sset.pool.stop_kv()
        sset.pool.stop_outbox()
    httpd.shutdown()


if __name__ == "__main__":
    main()
