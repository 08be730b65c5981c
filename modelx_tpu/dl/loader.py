"""The registry -> TPU HBM loader (the BASELINE metric lives here).

Pipeline: tensor index (from the ``modelx.tensor.index`` manifest annotation
or the safetensors header) -> per-tensor shard plan against the target
`Mesh` + partition rules -> parallel ranged reads (HTTP Range against the
registry/presigned URL, or local pread) -> `jax.Array` assembly via
`jax.make_array_from_single_device_arrays`, so each device shard is built
from exactly the bytes it needs and host->device copies overlap the fetches.

Fetch planning:

- tensors sharded on their leading axis (the common case for the big
  matmul weights) fetch **only each shard's rows** — a host never pulls
  bytes for devices it doesn't own (SURVEY.md §7 'aligning blob byte-ranges
  with shard slices so each host fetches exactly its bytes once');
- tensors sharded on inner axes or replicated fetch once per host and are
  sliced in memory (an inner-axis shard is byte-strided; one contiguous read
  beats thousands of tiny ranged reads);
- per-expert tensors of a stock HF checkpoint fold into one virtual stacked
  tensor (`fuse_expert_tensors`), and **a fold has one destination**: a
  shard-group takes ONE host buffer for its ``[members, ...]`` slice (pooled
  under a plain tensor's rule) and reads each member's rows straight into
  their place in it — no list of parts, no ``np.stack``: an expert byte is
  written once on the host, where its put reads it. Only a member whose
  inner dims are strided is cut from its whole tensor by a copy, as a plain
  tensor's slice is (``LoadStats.assemble_copied_bytes`` counts the bytes
  the host wrote twice; 0 for a clean fold).

Reference parity: this replaces cmd/modelxdl's "download files into a pod
volume, let a GPU container mmap them" with "bytes land in HBM, laid out for
GSPMD" (BASELINE.json north_star).

Tiering (docs/loading.md): fetched bytes stage through a reusable host
buffer pool (_StagingPool) whose bounded occupancy double-buffers the
fetch of shard k+1 against the device_put of shard k (_OverlapClock
reports the achieved overlap), and a content-addressed local blob cache
(dl/blob_cache.py, wired at the dl/initializer._blob_source seam) makes a
warm re-deploy of an already-served blob entirely network-free.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import re
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Protocol

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from modelx_tpu.dl import safetensors as st
from modelx_tpu.dl.sharding import Rules, sharding_for
from modelx_tpu.utils import trace

DEFAULT_FETCH_CONCURRENCY = 0  # 0 = auto (auto_fetch_concurrency)
FETCH_RETRIES = 3  # per-shard retry budget (SURVEY §5: loader retries per shard)
# Shards below this ride a BATCHED jax.device_put (one dispatch for a whole
# list of arrays) instead of one dispatch each — deploy TTFT for small
# models is dispatch-latency-bound, and a list device_put involves no
# on-device program (nothing to compile per fresh process), so it is on by
# default. The threshold was tuned on a rig that is gone: not measured on
# the current chip.
DEFAULT_PACK_THRESHOLD = 1 << 20
PACK_CHUNK = 64 << 20  # bytes of small tensors batched per device_put call
# host bytes allowed to sit in the fetch->transfer queue (see _ByteBudget)
DEFAULT_TRANSFER_BUDGET = 1 << 30
# reads at least this big stage into the reusable host buffer pool
# (_StagingPool) instead of allocating fresh — below it the allocator is
# cheaper than the bookkeeping, and the packed-transfer path (which parks
# its arrays until load end) stays out of the pool by construction
DEFAULT_STAGING_MIN = 1 << 20
# remote ranged reads above this split into governor-gated subranges on
# parallel connections (HTTPSource keeps one connection per thread), so a
# lone multi-GB tensor can use the whole fetch width instead of one stream
DEFAULT_SPLIT_READ = 64 << 20


class _StagingPool:
    """Reusable host staging buffers for fetched shard bytes (the
    ServerlessLLM pinned-pool idea, arxiv 2401.14351): every shard read
    used to allocate a fresh numpy buffer, so a multi-hundred-shard load
    churned the allocator at GB/s. Buffers live in power-of-two size
    classes, and at most ``max_outstanding`` are out at once — an acquire
    past the cap BLOCKS until a transfer returns one, which is the
    double-buffering gate: fetch k+1 proceeds exactly while the puts of
    earlier shards drain, and allocation count tracks CONCURRENCY (fetch
    width + transfer width), not shard count. Freelists are bounded —
    overflow buffers fall to the GC rather than pinning peak-burst
    memory. Every acquired buffer MUST be released on every path, or the
    cap starves the remaining fetch workers."""

    MAX_FREE_PER_CLASS = 8

    def __init__(self, max_outstanding: int = 0) -> None:
        self._cv = threading.Condition()
        self._free: dict[int, list[np.ndarray]] = {}
        self._out = 0
        self.max_outstanding = int(max_outstanding)
        self.allocs = 0
        self.reuses = 0

    def acquire(self, nbytes: int) -> np.ndarray:
        cls = 1 << max(nbytes - 1, 0).bit_length()
        with self._cv:
            while True:
                free = self._free.get(cls)
                if free:
                    base = free.pop()
                    self.reuses += 1
                    break
                if not self.max_outstanding or self._out < self.max_outstanding:
                    base = None
                    self.allocs += 1
                    break
                self._cv.wait()
            self._out += 1
        if base is None:
            base = np.empty(cls, np.uint8)
        return base[:nbytes]

    def release(self, view: np.ndarray) -> None:
        base = view.base if view.base is not None else view
        if not isinstance(base, np.ndarray) or base.dtype != np.uint8:
            return
        cls = base.nbytes
        if cls & (cls - 1):  # not a pool buffer
            return
        with self._cv:
            self._out -= 1
            free = self._free.setdefault(cls, [])
            if len(free) < self.MAX_FREE_PER_CLASS:
                free.append(base)
            self._cv.notify_all()

    def forfeit(self, view: np.ndarray) -> None:
        """Give up a buffer WITHOUT recycling it: the device array aliases
        it (PJRT CPU zero-copies 64-byte-aligned host buffers), so its
        memory now belongs to the loaded weights. Frees the outstanding
        slot so the pipeline keeps moving; the buffer itself lives as long
        as the arrays that share it."""
        with self._cv:
            self._out -= 1
            self._cv.notify_all()


def _aliases_buffer(dev_arrays, host: np.ndarray) -> bool:
    """True when any device shard's buffer lives inside ``host``'s
    allocation — the zero-copy case where recycling the host buffer would
    rewrite the 'device' bytes. Unprovable (no buffer pointer API on this
    backend) counts as aliased: correctness over reuse."""
    base = host.base if host.base is not None else host
    h0 = base.__array_interface__["data"][0]
    h1 = h0 + base.nbytes
    for arr in dev_arrays:
        try:
            for shard in arr.addressable_shards:
                if h0 <= shard.data.unsafe_buffer_pointer() < h1:
                    return True
        except Exception:
            return True
    return False


class _OverlapClock:
    """Wall-clock accounting of one load's fetch / device_put pipeline.
    Four activities are counted in and out by the threads that do them:
    ``fetch`` (a ranged read), ``put`` (a device_put dispatch and its wait),
    ``assemble`` (a fetch thread between its read's end and its hand-off:
    a cast, a quantise, the full-tensor fallback's slice — a fold's members
    are read where they are put, so stacking experts is no work of its own)
    and ``blocked`` (a fetch thread waiting for the byte budget or for a
    staging buffer). Every instant from ``t0`` to :meth:`stop` falls to
    exactly one of: a read or a put in flight (``busy``, each phase's own
    wall seconds), host work alone (``assemble_s``), nothing (``idle_s``) —
    so ``busy[fetch] + busy[put] - overlap + assemble_s + idle_s`` is the
    load's wall time, and the overlap of reads and puts (what the two-pool
    design exists to create: ~ 0 on a big checkpoint means the stages run
    one after the other) follows from it. Beside the tiling:
    ``backpressure_s``, a fetch thread blocked and no read in flight (the
    puts hold the reads up), and ``drain_s``, from the last read's end to
    the stop. Entirely host-side counters."""

    def __init__(self, t0: float) -> None:
        self._lock = threading.Lock()
        self._n = {"fetch": 0, "put": 0, "assemble": 0, "blocked": 0}
        self._t0 = self._last = self._last_read_end = t0
        self.busy = {"fetch": 0.0, "put": 0.0}
        self.idle_s = self.assemble_s = self.backpressure_s = 0.0
        self.wall_s = self.drain_s = 0.0

    def _tick(self) -> float:
        now = time.monotonic()
        dt = now - self._last
        self._last = now
        if dt <= 0:
            return now
        n = self._n
        if n["fetch"] > 0:
            self.busy["fetch"] += dt
        elif n["blocked"] > 0:
            self.backpressure_s += dt
        if n["put"] > 0:
            self.busy["put"] += dt
        elif n["fetch"] <= 0:
            if n["assemble"] > 0:
                self.assemble_s += dt
            else:
                self.idle_s += dt
        return now

    @contextlib.contextmanager
    def during(self, kind: str):
        with self._lock:
            self._tick()
            self._n[kind] += 1
        try:
            yield
        finally:
            with self._lock:
                now = self._tick()
                self._n[kind] -= 1
                if kind == "fetch":
                    self._last_read_end = now

    def stop(self) -> float:
        """Close the books at the call's end; returns the instant."""
        with self._lock:
            now = self._tick()
            self.wall_s = now - self._t0
            self.drain_s = now - self._last_read_end
        return now

    @property
    def overlap_s(self) -> float:
        union = self.wall_s - self.idle_s - self.assemble_s
        return max(0.0, self.busy["fetch"] + self.busy["put"] - union)


class _ByteBudget:
    """Bounds the BYTES of fetched host arrays parked awaiting transfer, so
    the memory ceiling is independent of how many dispatch threads run. A
    request larger than the whole budget is admitted alone (clamped) rather
    than deadlocking."""

    def __init__(self, limit: int) -> None:
        self.limit = max(1, limit)
        self._avail = self.limit
        self._cv = threading.Condition()

    def acquire(self, n: int) -> int:
        """Returns the amount actually charged (clamped to the limit);
        callers must release exactly that — releasing the unclamped request
        would inflate the budget past its limit over time."""
        n = min(n, self.limit)
        with self._cv:
            while self._avail < n:
                self._cv.wait()
            self._avail -= n
        return n

    def release(self, n: int) -> None:
        with self._cv:
            self._avail += n
            self._cv.notify_all()


def _read_with_retry(source: "ByteSource", offset: int, length: int, out=None,
                     retries: int = FETCH_RETRIES, timer=None):
    """Ranged read with exponential backoff — a transient fetch error must
    not kill a multi-hundred-shard load (mirrors the reference's per-part
    retry x3, extension_s3.go:133-148). ``timer(nbytes, seconds)`` fires
    for the SUCCESSFUL attempt only: throughput consumers (the fetch
    governor) must see transfer time, not backoff sleeps or failed I/O."""
    for attempt in range(retries):
        t0 = time.monotonic()
        try:
            result = source.read_range(offset, length, out)
        except OSError:
            if attempt == retries - 1:
                raise
            time.sleep(0.2 * (2 ** attempt))
        else:
            if timer is not None:
                timer(length, time.monotonic() - t0)
            return result


def auto_fetch_concurrency(source) -> int:
    """Fetch width derived from the HOST, not a constant (a hard-coded 16
    local-file fetchers + the transfer pool thrash a host with few cores —
    worse than one sequential stream).

    Local files: pread from page cache is memcpy-bound, so width beyond a
    couple of threads per core only adds scheduler churn; 2/core, max 8.
    HTTP: threads block on sockets (native path holds no GIL), so width
    buys round-trip overlap — 4/core in [8, 16]."""
    cpu = os.cpu_count() or 1
    if isinstance(getattr(source, "_source", source), LocalFileSource):
        return max(2, min(8, 2 * cpu))
    return max(8, min(16, 4 * cpu))


class _FetchGovernor:
    """Admission gate for fetch reads that HALVES its width when measured
    per-thread throughput collapses (the r4 failure signature: local reads
    at ~1.5 MB/s per thread while the same file streams at 1+ GB/s) and —
    new for the cache-tier loader — GROWS it while per-thread throughput
    shows headroom (``growth_bps``), up to ``max_width``. The r5 capture
    sat at width 2 with the link 56% idle; growth is what lets the width
    recover above the collapse floor. Oscillation guard: after 3 backoffs
    growth disables permanently — a link that keeps punishing added width
    gets no more probes. Gating happens per READ, so width changes take
    effect mid-load without tearing down pool threads."""

    MAX_GROWTH_BACKOFFS = 3

    def __init__(self, width: int, floor_bps: float, min_width: int = 2,
                 max_width: int = 0, growth_bps: float = 0.0) -> None:
        self.width = max(1, int(width))
        self.floor_bps = float(floor_bps)
        self.min_width = min(min_width, self.width)
        self.max_width = max(self.width, int(max_width))
        self.growth_bps = float(growth_bps)
        self._cv = threading.Condition()
        self._active = 0
        self._bytes = 0
        self._busy_s = 0.0
        self.backoffs = 0  # observability: how often the governor shrank
        self.growths = 0  # ... and how often it grew

    def acquire(self) -> None:
        with self._cv:
            while self._active >= self.width:
                self._cv.wait()
            self._active += 1

    def release(self, nbytes: int, seconds: float) -> None:
        with self._cv:
            self._active -= 1
            self._bytes += nbytes
            self._busy_s += seconds
            if (self.floor_bps or self.growth_bps) and self._busy_s >= 0.25:
                # per-busy-thread-second rate: busy seconds sum across
                # threads, so this is throughput per active thread
                rate = self._bytes / self._busy_s
                if (
                    self.floor_bps
                    and rate < self.floor_bps
                    and self.width > self.min_width
                ):
                    self.width = max(self.min_width, self.width // 2)
                    self.backoffs += 1
                elif (
                    self.growth_bps
                    and rate >= self.growth_bps
                    and self.width < self.max_width
                    and self.backoffs < self.MAX_GROWTH_BACKOFFS
                ):
                    self.width = min(self.max_width, self.width * 2)
                    self.growths += 1
                # decay: recent reads dominate the next verdict
                self._bytes //= 2
                self._busy_s /= 2
            self._cv.notify_all()


class ByteSource(Protocol):
    """Anything that serves ranged reads of a safetensors blob.

    ``read_range(offset, length, out=None)``: when ``out`` (a writable
    length-sized memoryview) is given, bytes land directly in it — the
    loader passes views over numpy-owned allocations, because jax's
    host->device fast path wants aligned, array-owned buffers.
    """

    def read_range(self, offset: int, length: int, out: memoryview | None = None): ...

    def size(self) -> int: ...


class LocalFileSource:
    def __init__(self, path: str) -> None:
        self.path = path
        self._size = os.path.getsize(path)
        self._fd = os.open(path, os.O_RDONLY)
        try:
            from modelx_tpu import native

            self._native = native if native.available() else None
        except ImportError:
            self._native = None

    def read_range(self, offset: int, length: int, out: memoryview | None = None):
        if out is None:
            buf = np.empty(length, np.uint8)
            out = memoryview(buf)
        else:
            buf = out
        if self._native is not None and length > 0:
            # GIL-free positional read on the open fd (modelx_io.cc mx_pread_fd)
            self._native.pread_fd(self._fd, offset, length, out)
            return buf
        n = 0
        while n < length:
            got = os.preadv(self._fd, [out[n:]], offset + n)
            if got <= 0:
                break
            n += got
        if n != length:
            raise OSError(f"short read: want {length}, got {n}")
        return buf

    def size(self) -> int:
        return self._size

    def close(self) -> None:
        os.close(self._fd)


class HTTPSource:
    """Ranged GETs against a URL (registry blob endpoint or presigned S3).

    Built on raw ``http.client`` with ``readinto`` and one persistent
    connection per thread: the requests/urllib3 stack shuttles small chunks
    through Python, which would throttle the registry->HBM path. Colocated
    clients should prefer the registry's ``file`` location redirect
    (LocalFileSource) — direct preads beat any loopback HTTP.
    """

    def __init__(self, url: str, headers: dict[str, str] | None = None, total: int = -1) -> None:
        import urllib.parse

        self.url = url
        self.headers = headers or {}
        u = urllib.parse.urlsplit(url)
        self._scheme = u.scheme
        self._host = u.hostname or ""
        self._port = u.port or (443 if u.scheme == "https" else 80)
        self._path = u.path + (f"?{u.query}" if u.query else "")
        self._netloc = u.netloc
        self._local = threading.local()
        self._size = total
        # native engine: raw-socket ranged GETs with the GIL released for the
        # whole transfer (http only; TLS stays on the python path)
        self._use_native = u.scheme == "http"
        self._native_headers = "".join(f"{k}: {v}\r\n" for k, v in self.headers.items())

    def _conn(self):
        import http.client

        conn = getattr(self._local, "conn", None)
        if conn is None:
            if self._scheme == "https":
                kwargs = {}
                from modelx_tpu.client.remote import insecure_default

                if insecure_default():  # CLI --insecure covers ranged loads too
                    # NB set_insecure/Client(insecure=True) is PROCESS-WIDE
                    # (documented in docs/api.md): every source built after
                    # the flag flips skips verification. Public-API context
                    # construction, not ssl's private helper.
                    import ssl

                    ctx = ssl.create_default_context()
                    ctx.check_hostname = False
                    ctx.verify_mode = ssl.CERT_NONE
                    kwargs["context"] = ctx
                conn = http.client.HTTPSConnection(
                    self._host, self._port, timeout=300, **kwargs
                )
            else:
                conn = http.client.HTTPConnection(self._host, self._port, timeout=300)
            self._local.conn = conn
        return conn

    def _request(self, method: str, headers: dict[str, str]):
        conn = self._conn()
        try:
            conn.request(method, self._path, headers=headers)
            return conn.getresponse()
        except (OSError, __import__("http.client", fromlist=["HTTPException"]).HTTPException):
            # stale keep-alive connection: rebuild once
            conn.close()
            self._local.conn = None
            conn = self._conn()
            conn.request(method, self._path, headers=headers)
            return conn.getresponse()

    def _native_conn(self):
        """Thread-local native keep-alive connection (None once disabled)."""
        conn = getattr(self._local, "native", None)
        if conn is None:
            try:
                from modelx_tpu import native
            except ImportError:
                self._use_native = False
                return None
            if not native.available():
                self._use_native = False
                return None
            conn = native.NativeHTTPConnection(self._host, self._port)
            self._local.native = conn
        return conn

    def read_range(self, offset: int, length: int, out: memoryview | None = None):
        if self._use_native:
            try:
                conn = self._native_conn()
            except OSError:
                conn = None
                self._use_native = False
            if conn is not None:
                if out is None:
                    buf = np.empty(length, np.uint8)
                    view = memoryview(buf)
                else:
                    buf, view = out, memoryview(out)
                try:
                    status = conn.get_range(self._path, offset, length, view, self._native_headers)
                except OSError:
                    # transport/protocol trouble (e.g. server ignored Range):
                    # drop to the python path for this source
                    self._local.native = None
                    conn.close()
                    self._use_native = False
                else:
                    if status in (200, 206):
                        return buf
                    raise OSError(f"ranged read failed: HTTP {status}")
        h = dict(self.headers)
        h["Range"] = f"bytes={offset}-{offset + length - 1}"
        resp = self._request("GET", h)
        try:
            if resp.status not in (200, 206):
                body = resp.read(4096)
                raise OSError(f"ranged read failed: HTTP {resp.status}: {body[:200]!r}")
            if resp.status == 200:  # server ignored Range
                data = resp.read()
                data = data[offset : offset + length]
                if out is not None:
                    out[:] = data
                    return out
                return data
            if out is None:
                buf = np.empty(length, np.uint8)
                view = memoryview(buf)
            else:
                buf, view = out, out
            n = 0
            while n < length:
                got = resp.readinto(view[n:])
                if not got:
                    break
                n += got
            if n != length:
                raise OSError(f"ranged read short: want {length}, got {n}")
            return buf
        finally:
            # drain so the keep-alive connection stays usable
            resp.read()

    def size(self) -> int:
        if self._size < 0:
            resp = self._request("HEAD", dict(self.headers))
            resp.read()
            self._size = int(resp.headers.get("Content-Length", -1))
        return self._size


@dataclasses.dataclass
class LoadStats:
    bytes_fetched: int = 0
    bytes_to_device: int = 0
    tensors: int = 0
    fetch_seconds: float = 0.0
    total_seconds: float = 0.0
    fetch_width: int = 0  # governor's final width (== initial when healthy)
    fetch_backoffs: int = 0  # times the governor halved the width
    fetch_growths: int = 0  # times the governor doubled it (headroom)
    # pipeline accounting (_OverlapClock): wall time ranged fetches were in
    # flight vs device_put dispatches, and the window where both were —
    # overlap ~ 0 on a big load means the fetch->HBM pipeline collapsed
    fetch_busy_seconds: float = 0.0  # fetch_seconds is thread-seconds
    device_put_seconds: float = 0.0
    overlap_seconds: float = 0.0
    # the rest of the clock's tiling, in wall seconds: fetch_busy +
    # device_put - overlap + assemble + idle = total_seconds. Neither a
    # read nor a put nor host work between them; host work alone. And two
    # readings beside it: a fetch thread held up while nothing reads (the
    # puts hold the reads up), and the last read's end -> the call's
    # (packs, array assembly, the tail of the puts)
    idle_seconds: float = 0.0
    assemble_seconds: float = 0.0
    backpressure_seconds: float = 0.0
    drain_seconds: float = 0.0
    # bytes that took a second host copy between their read and their put:
    # an inner-strided slice cut from its whole tensor (a plain tensor's or
    # a fold's member's), a host-side cast's or quantise's result, a small
    # shard copied out of its pooled buffer for a pack. 0 for a load whose
    # every byte was read where it was put from — a clean fold included
    assemble_copied_bytes: int = 0
    # staging pool: fresh buffer allocations vs pooled reuses; allocs track
    # concurrency, not shard count (tests assert this stays bounded)
    staging_allocs: int = 0
    staging_reuses: int = 0

    @property
    def gbps(self) -> float:
        return self.bytes_to_device / max(self.total_seconds, 1e-9) / 1e9


_EXPERT_NAME = re.compile(r"^(.+\.experts)\.(\d+)\.(.+)$")


def _match_index(name: str, rules: Rules) -> int:
    """Position of the first rule matching ``name`` (len(rules) if none)."""
    for i, (pattern, _spec) in enumerate(rules):
        if re.search(pattern, name):
            return i
    return len(rules)


def fuse_expert_tensors(
    tensors: dict[str, st.TensorInfo], rules: Rules | None = None
) -> dict[str, st.TensorInfo]:
    """Fold HF per-expert tensor entries (``...experts.<i>.w1.weight``) into
    virtual stacked tensors (``...experts.w1.weight`` with shape [E, ...])
    so MoE checkpoints pushed in stock HF layout load directly onto an
    ``ep``-sharded mesh (MIXTRAL_RULES target the stacked names, and
    models/mixtral.py consumes the stacked layout). Each device still
    fetches only the expert rows it owns — the stacked tensor's shards are
    assembled from the member tensors' byte ranges.

    When ``rules`` are given, a group is fused only if the rules address the
    fused name *more specifically* than the per-expert names — so shard-spec
    annotations written against the on-disk HF names keep working untouched.

    A checkpoint may hold only a share of a layer's experts (experts
    ``first .. first + count`` of those its router publishes): any unbroken
    run of indices folds, in index order, and the stacked tensor's leading
    axis counts the experts held.
    """
    groups: dict[str, dict[int, st.TensorInfo]] = {}
    out: dict[str, st.TensorInfo] = {}
    for name, info in tensors.items():
        m = _EXPERT_NAME.match(name)
        if m:
            groups.setdefault(f"{m.group(1)}.{m.group(3)}", {})[int(m.group(2))] = info
        else:
            out[name] = info
    for key, members in groups.items():
        idxs = sorted(members)
        first = members[idxs[0]]
        # one unbroken run of expert indices — from 0 for a whole checkpoint,
        # from anywhere for one that holds a share of the published experts
        uniform = idxs == list(range(idxs[0], idxs[0] + len(idxs))) and all(
            m.shape == first.shape and m.dtype == first.dtype for m in members.values()
        )
        if rules is not None and uniform:
            # first-match-wins: skip fusion only when a rule addresses the
            # per-expert HF name *strictly* earlier than the fused name —
            # on a tie (e.g. catch-all rules only) fuse, the stacked layout
            # is what models/mixtral.py consumes
            uniform = _match_index(key, rules) <= _match_index(first.name, rules)
        if not uniform:  # unexpected layout (or rules target HF names): pass through
            for info in members.values():
                out[info.name] = info
            continue
        ms = [members[i] for i in idxs]
        out[key] = st.TensorInfo(
            name=key, dtype=first.dtype, shape=(len(ms), *first.shape),
            start=first.start, end=first.start + sum(m.nbytes for m in ms),
            members=ms,
        )
    return out


def _transfer_packs(pack_jobs: dict) -> dict:
    """Ship small tensors batched: per device-set, ONE ``jax.device_put``
    of a whole list per <=PACK_CHUNK of host bytes — a single dispatch
    round-trip covers the lot, with no on-device unpack program (each list
    element arrives as its own typed array). Returns
    {(tensor name, group index): [(device, shard), ...]}."""
    out: dict[tuple, list] = {}
    for items in pack_jobs.values():
        chunks, cur, cur_bytes = [], [], 0
        for item in items:
            nb = item[2].nbytes
            if cur and cur_bytes + nb > PACK_CHUNK:
                chunks.append(cur)
                cur, cur_bytes = [], 0
            cur.append(item)
            cur_bytes += nb
        if cur:
            chunks.append(cur)
        for chunk in chunks:
            arrs = [np.ascontiguousarray(arr) for _n, _gi, arr, _g in chunk]
            devices = [dev for dev, _idx in chunk[0][3]]
            for dev in devices:
                shards = jax.device_put(arrs, dev)
                for (name, gi, _arr, _group), shard in zip(chunk, shards):
                    out.setdefault((name, gi), []).append((dev, shard))
    return out


def _inner_whole(spec: tuple, shape: tuple) -> bool:
    """True when a slice takes every axis after the first whole: its bytes
    are one contiguous run of the tensor's rows."""
    return all(s.start == 0 and s.stop == dim for s, dim in zip(spec[1:], shape[1:]))


def _leading_axis_only(spec: PartitionSpec) -> bool:
    if len(spec) == 0 or spec[0] is None:
        return False
    return all(s is None for s in spec[1:])


def load_safetensors(
    source: ByteSource,
    mesh: Mesh,
    rules: Rules,
    tensors: dict[str, st.TensorInfo] | None = None,
    data_offset: int | None = None,
    concurrency: int = DEFAULT_FETCH_CONCURRENCY,
    dtype=None,
    progress: Callable[[int], None] | None = None,
    transfer_concurrency: int = 0,
    quantize: str | None = None,
    pack_threshold: int = DEFAULT_PACK_THRESHOLD,
    transfer_budget_bytes: int = DEFAULT_TRANSFER_BUDGET,
    staging_min_bytes: int = DEFAULT_STAGING_MIN,
    split_read_bytes: int = DEFAULT_SPLIT_READ,
) -> tuple[dict[str, jax.Array], LoadStats]:
    """Load every tensor of a safetensors blob onto ``mesh`` per ``rules``.

    ``tensors``/``data_offset`` come from the manifest annotation when
    available; otherwise the header is fetched with two small ranged reads.
    ``concurrency`` <= 0 (the default) derives the fetch width from the
    host and source type (auto_fetch_concurrency), and a governor halves
    the ACTIVE width mid-load if per-thread throughput collapses
    (_FetchGovernor — thrash protection for small-core hosts).
    ``dtype`` optionally casts on the host before transfer (halves PCIe bytes
    when serving bf16 from an f32 checkpoint). ``transfer_concurrency``
    bounds concurrent host->device dispatches (0 = auto: 8, or 2 per local
    device up to 16 — concurrent device_puts pipeline per-dispatch latency
    and fill the link; the widths were tuned on a rig that is gone and are
    not measured on the current chip).
    ``transfer_budget_bytes`` caps the host bytes parked between fetch and
    transfer — the RAM ceiling no longer scales with dispatch width. (The
    whole-tensor cache for byte-strided/int8-global-scale tensors is held
    OUTSIDE the budget until load end; checkpoints dominated by such
    tensors need headroom above the budget for the cached originals.)
    ``quantize="int8"`` converts the big matmul weights to weight-only int8
    (ops/quant.py) ON THE HOST, halving host->device bytes and HBM; the
    per-output-channel scales are computed globally so sharded math stays
    exact. Quantized entries come back as ``QTensor``s.
    ``pack_threshold``: per-device shards smaller than this collect into
    batched list ``jax.device_put`` calls (one dispatch per ~PACK_CHUNK of
    small tensors, no on-device program) — per-tensor dispatch latency
    would otherwise dominate checkpoints with many small tensors. 0
    disables (every shard dispatches alone).
    ``staging_min_bytes``: reads at least this big land in pooled, reusable
    host staging buffers (_StagingPool) instead of fresh allocations; the
    pool plus the fetch/transfer thread pair is the double-buffering that
    overlaps the fetch of shard k+1 with the device_put of shard k
    (LoadStats carries the overlap accounting). 0 disables the pool.
    ``split_read_bytes``: remote ranged reads above this split into
    parallel governor-gated subrange reads (one connection per thread), so
    a single huge tensor doesn't serialize the link. 0 disables splitting;
    local files never split (pread has no per-stream ceiling to beat).
    """
    t0 = time.monotonic()
    clock = _OverlapClock(t0)  # the headers and the plan below are its idle time
    # env-gated chaos drills (default off): MODELX_FAULT_PLAN with a
    # "loader.read" schedule wraps the source so operators can rehearse the
    # retry/governor behavior against a real deployment on demand
    from modelx_tpu.testing import faults as _faults

    _env_plan = _faults.from_env()
    if _env_plan is not None and _env_plan.has("loader.read"):
        source = _faults.FaultyByteSource(source, _env_plan)
    if tensors is None or data_offset is None:
        head = bytes(_read_with_retry(source, 0, 8))
        import struct

        (hlen,) = struct.unpack("<Q", head)
        tensors = st.parse_header(bytes(_read_with_retry(source, 8, hlen)))
        data_offset = 8 + hlen
    tensors = fuse_expert_tensors(tensors, rules)

    if concurrency <= 0:
        concurrency = auto_fetch_concurrency(source)
    # collapse floor: local page-cache reads under ~32 MB/s PER THREAD mean
    # the threads are fighting the scheduler, not the disk (healthy is
    # 300+ MB/s; the r4 collapse was 1.5 MB/s). HTTP sources skip the
    # governor's floor — a genuinely slow remote link must not trigger a
    # width collapse that makes it slower still. Growth: remote sources may
    # double width up to 2x the auto width while per-thread throughput holds
    # above 24 MB/s (the r5 capture left 56% of the link idle at width 2);
    # local sources may regrow only back to the auto width, and only while
    # per-thread reads run at healthy page-cache rates (4x the floor).
    # unwrap a fault-injection wrapper for the policy check: injected
    # faults must not silently flip the governor to the remote profile
    is_local = isinstance(
        getattr(source, "_source", source), LocalFileSource
    )
    governor = _FetchGovernor(
        concurrency,
        floor_bps=32e6 if is_local else 0.0,
        max_width=concurrency if is_local else 2 * concurrency,
        growth_bps=128e6 if is_local else 24e6,
    )
    n_transfer = transfer_concurrency
    if n_transfer <= 0:
        n_transfer = max(8, min(16, 2 * len(mesh.local_devices)))
    # the outstanding-buffer cap is what makes the pool a PIPELINE gate:
    # one buffer per fetch thread, one per transfer thread, plus slack so a
    # fetch never waits on an about-to-finish put
    staging_pool = _StagingPool(max_outstanding=concurrency + n_transfer + 2)

    def _gated_read(offset: int, length: int, out=None):
        """Ranged read under the governor's gate; the retry policy stays
        single-sourced in _read_with_retry, whose timer reports only the
        successful attempt — backoff sleeps and failed attempts' I/O are a
        retry story, not a width story, and must not read as a collapse
        that permanently sheds fetch parallelism."""
        sample = [0, 0.0]

        def timer(n: int, secs: float) -> None:
            sample[0], sample[1] = n, secs

        # acquire is pinned by the try/finally IMMEDIATELY (lint:
        # lock-leak): clock.enter used to sit between acquire and try, so
        # an exception there would have leaked a governor slot forever
        governor.acquire()
        try:
            with clock.during("fetch"), trace.span("dl.fetch", bytes=length):
                return _read_with_retry(source, offset, length, out, timer=timer)
        finally:
            governor.release(sample[0], sample[1])

    # per-blob multi-connection fetch: huge reads split into subranges run
    # on a DEDICATED executor (split tasks never submit further work, so the
    # fetch pool can block on them without starving itself); the governor
    # still gates every subrange, so total width stays under its control
    split_pool = None
    if split_read_bytes and not is_local:
        split_pool = ThreadPoolExecutor(max_workers=min(8, max(2, concurrency)))

    def _fetch_bytes(offset: int, length: int, out=None):
        if split_pool is None or length <= split_read_bytes:
            return _gated_read(offset, length, out)
        if out is None:
            buf = np.empty(length, np.uint8)
            view = memoryview(buf)
        else:
            buf = out
            view = out if isinstance(out, memoryview) else memoryview(out)
        futs = [
            split_pool.submit(
                _gated_read, offset + o, min(split_read_bytes, length - o),
                view[o : o + min(split_read_bytes, length - o)],
            )
            for o in range(0, length, split_read_bytes)
        ]
        for f in futs:
            f.result()
        return buf

    stats = LoadStats()
    lock = threading.Lock()
    results: dict[str, jax.Array] = {}

    # plan: one job per (tensor, shard-group). A shard-group is the set of
    # devices that receive identical bytes (replicas); bytes are fetched once
    # per group and device_put to each member.
    plans: dict[str, tuple[NamedSharding, list]] = {}
    for name, info in tensors.items():
        sharding = sharding_for(name, rules, mesh)
        # index per device: mapping device -> tuple of slices
        dev_indices = sharding.addressable_devices_indices_map(info.shape)
        groups: dict[tuple, list] = {}
        for dev, idx in dev_indices.items():
            key = _index_key(idx, info.shape)
            groups.setdefault(key, []).append((dev, idx))
        plans[name] = (sharding, list(groups.values()))

    if quantize not in (None, "int8"):
        raise ValueError(f"unsupported quantize mode {quantize!r}")
    if quantize:
        from modelx_tpu.ops import quant as qt

    def _quantized(name: str, info: st.TensorInfo) -> bool:
        return (
            quantize == "int8"
            and info.members is None
            and len(info.shape) == 2
            and qt.DEFAULT_ELIGIBLE.search(name) is not None
        )

    # whole-tensor fetches are deduped across shard-groups of the same tensor
    _full_cache: dict[str, bytes] = {}
    _full_lock = threading.Lock()
    # single-flight events: the get-then-fetch window would otherwise let
    # two groups of the same inner-sharded tensor BOTH miss and BOTH pull
    # the whole tensor (the exactly-once byte accounting the fetch plan
    # promises — TestByteAccounting2DMesh — raced away under load)
    _full_events: dict[str, threading.Event] = {}
    # global per-channel scales for quantized tensors on the full-fetch path
    _scale_cache: dict[str, np.ndarray] = {}

    def _cached_full_tensor(info: st.TensorInfo) -> bytes:
        while True:
            with _full_lock:
                cached = _full_cache.get(info.name)
                if cached is not None:
                    return cached
                ev = _full_events.get(info.name)
                if ev is None:
                    ev = _full_events[info.name] = threading.Event()
                    fetching = True
                else:
                    fetching = False
            if not fetching:
                ev.wait()  # the owner fills the cache (or fails; then retry)
                continue
            try:
                raw = _fetch_bytes(data_offset + info.start, info.nbytes)
                with _full_lock:
                    _full_cache[info.name] = raw
                return raw
            finally:
                # event removed BEFORE set: a waiter that finds no cache
                # entry and no event becomes the next owner (owner failed)
                with _full_lock:
                    _full_events.pop(info.name, None)
                ev.set()

    def _stage(nbytes: int) -> np.ndarray | None:
        """A pooled host buffer for a read of ``nbytes``, or None where the
        allocator is cheaper (``staging_min_bytes``). Whoever takes one
        releases or forfeits it, once, on every path."""
        if not staging_min_bytes or nbytes < staging_min_bytes:
            return None
        with clock.during("blocked"):
            return staging_pool.acquire(nbytes)

    def _copied(nbytes: int) -> None:
        with lock:
            stats.assemble_copied_bytes += nbytes

    def _fetch_slice(
        info: st.TensorInfo, full_spec: tuple
    ) -> tuple[np.ndarray, int, np.ndarray | None]:
        """Fetch one tensor's slice. Contiguous row blocks (inner dims full)
        are fetched with one exact ranged read; byte-strided inner-axis
        slices fetch the whole tensor once (cached) and slice in memory.
        Returns (array, bytes_read, staging): ``staging`` is the pooled host
        buffer backing the array when one was used — the caller must release
        it to the pool once the bytes are on device (or copied)."""
        np_dtype = info.np_dtype()
        if info.shape and _inner_whole(full_spec, info.shape):
            lead = full_spec[0]
            b0, b1 = st.row_range(info, lead.start, lead.stop)
            length = b1 - b0
            staging = _stage(length)
            try:
                raw = _fetch_bytes(
                    data_offset + b0, length,
                    None if staging is None else memoryview(staging),
                )
            except BaseException:
                # a leaked buffer starves the pool's outstanding cap — the
                # sibling fetch workers would deadlock behind a dead load
                if staging is not None:
                    staging_pool.release(staging)
                raise
            arr = _as_np(
                staging if staging is not None else raw,
                np_dtype, (lead.stop - lead.start, *info.shape[1:]),
            )
            return arr, length, staging
        raw = _cached_full_tensor(info)
        arr = _as_np(raw, np_dtype, info.shape)
        with clock.during("assemble"):
            sliced = np.ascontiguousarray(arr[full_spec]) if info.shape else arr.reshape(())
        _copied(sliced.nbytes)
        return sliced, len(raw), None

    def _fetch_member(info: st.TensorInfo, spec: tuple, dest: np.ndarray) -> int:
        """Fetch one member of a fold to where the put will read it: ``dest``
        is the bytes of its place in the group's one buffer. Its row range
        (inner dims whole) lands there by one exact ranged read; a
        byte-strided inner-axis slice is cut from the whole tensor (fetched
        once, cached) by the one copy a plain tensor's slice pays too.
        Returns the bytes read."""
        if info.shape and _inner_whole(spec, info.shape):
            b0, b1 = st.row_range(info, spec[0].start, spec[0].stop)
            _fetch_bytes(data_offset + b0, b1 - b0, memoryview(dest))
            return b1 - b0
        raw = _cached_full_tensor(info)
        np_dtype = info.np_dtype()
        whole = _as_np(raw, np_dtype, info.shape)
        with clock.during("assemble"):
            np.copyto(_as_np(dest, np_dtype, tuple(s.stop - s.start for s in spec)),
                      whole[spec])
        _copied(dest.nbytes)
        return len(raw)

    def fetch_group(info: st.TensorInfo, group: list):
        """Fetch one shard-group's bytes; hand the host array to the transfer
        pool. Fetches run wide (network-bound); device dispatches run
        several-wide too — each device_put pays a round-trip dispatch
        latency, so a single dispatch thread leaves the link idle between
        puts.
        Returns a future of [(device, on-device shard), ...]."""
        _dev0, idx0 = group[0]
        full_spec = _normalize_index(idx0, info.shape)
        # backpressure: admit the group against the byte budget BEFORE the
        # read — acquiring after the fetch would let fetch_concurrency whole
        # arrays pile up uncounted. The cost is the bytes this group will
        # materialize: its slice, or the whole tensor when a byte-strided
        # inner-axis slice forces a (cached) full fetch.
        np_dtype = info.np_dtype()
        itemsize = np_dtype.itemsize
        if dtype is not None:
            # a host-side upcast parks the POST-cast bytes; charge for those
            itemsize = max(itemsize, np.dtype(dtype).itemsize)
        shape = tuple(s.stop - s.start for s in full_spec)
        slice_bytes = itemsize * int(np.prod(shape, initial=1))
        if info.members is not None:
            # stacked expert tensor: fetched per member against
            # full_spec[1:], so the full-fetch fallback triggers only when
            # the MEMBER's inner dims (full_spec[2:]) are strided — charging
            # the whole E-stacked tensor here would serialize MoE loads
            if _inner_whole(full_spec[1:], info.shape[1:]):
                cost = slice_bytes
            else:
                lead = full_spec[0]
                cost = max(slice_bytes, sum(
                    info.members[e].nbytes for e in range(lead.start, lead.stop)
                ))
        elif _inner_whole(full_spec, info.shape):
            cost = slice_bytes
        else:
            # strided inner-axis slice -> whole-tensor fetch, but only the
            # group that MISSES the cache pays it; siblings arriving later
            # slice the cached bytes and must not serialize on a full charge
            with _full_lock:
                cached = info.name in _full_cache
            cost = slice_bytes if cached else max(slice_bytes, info.nbytes)
        with clock.during("blocked"):
            cost = inflight.acquire(cost)  # clamped: release exactly this much
        staging = None
        try:
            tf0 = time.monotonic()
            if info.members is not None:
                # virtual stacked tensor: the fold has ONE destination. The
                # group's [members, ...] slice is one host buffer, pooled
                # under a plain tensor's rule (one buffer a group, so no
                # hold-and-wait against the pool's cap), and each member
                # this group owns is read straight into its place in it —
                # no list of parts, no second copy of every expert byte
                nbytes = np_dtype.itemsize * int(np.prod(shape))
                staging = _stage(nbytes)
                buf = staging if staging is not None else np.empty(nbytes, np.uint8)
                each = nbytes // shape[0]
                lead = full_spec[0]
                nread = 0
                for i, e in enumerate(range(lead.start, lead.stop)):
                    nread += _fetch_member(
                        info.members[e], full_spec[1:], buf[i * each:(i + 1) * each]
                    )
                arr = _as_np(buf, np_dtype, shape)
            else:
                arr, nread, staging = _fetch_slice(info, full_spec)
            with lock:
                stats.bytes_fetched += nread
                stats.fetch_seconds += time.monotonic() - tf0
            # from the read's end to the hand-off: host work the clock names
            with clock.during("assemble"):
                scale = None
                if _quantized(info.name, info):
                    if _inner_whole(full_spec, info.shape):
                        # this group's rows are complete channels: local scales
                        # ARE the global per-channel scales — fused single-pass
                        # quantize (native when available)
                        arr, scale = qt.quantize_fused(arr)
                    else:
                        # input dim sharded: scales must span the full contraction
                        # axis — compute once from the cached full tensor
                        with _full_lock:
                            scale_full = _scale_cache.get(info.name)
                        if scale_full is None:
                            full = _as_np(_cached_full_tensor(info), info.np_dtype(), info.shape)
                            scale_full = qt.channel_scales(full)
                            with _full_lock:
                                _scale_cache[info.name] = scale_full
                        scale = np.ascontiguousarray(
                            scale_full[full_spec[0].start : full_spec[0].stop]
                        )
                        arr = qt.quantize_rows(arr, scale)
                    _copied(arr.nbytes)
                elif dtype is not None and arr.dtype != np.dtype(dtype):
                    arr = arr.astype(dtype)
                    _copied(arr.nbytes)
                if staging is not None and not np.may_share_memory(arr, staging):
                    # a host-side cast/quantize copied the bytes out: the pooled
                    # buffer is free for the next fetch right now, not after the
                    # transfer
                    staging_pool.release(staging)
                    staging = None
                if progress:
                    progress(arr.nbytes * len(group))
                if arr.nbytes < cost:
                    # the parked array is smaller than what the fetch charged
                    # (full-fetch fallback, host-side cast/quantize): give the
                    # difference back so sibling groups stop waiting on bytes
                    # nobody is holding
                    inflight.release(cost - arr.nbytes)
                    cost = arr.nbytes
                # batched transfer involves plain device_put (same dtype
                # canonicalization as the unbatched path), so ANY small
                # unquantized shard qualifies
                packable = (
                    scale is None and pack_threshold and arr.nbytes < pack_threshold
                )
                if packable:
                    # small shard: ride the packed transfer instead of paying a
                    # per-tensor device round-trip. Budget released now: packs
                    # park until every fetch settles, and the packable tail is
                    # bounded by pack_threshold x tensor count, not the budget
                    inflight.release(cost)
                    if staging is not None:
                        # packs park until load end — copy out so the pooled
                        # buffer doesn't sit hostage under a small tensor
                        arr = arr.copy()
                        _copied(arr.nbytes)
                        staging_pool.release(staging)
                    return ("pack", arr, group)
        except BaseException:
            inflight.release(cost)
            if staging is not None:
                staging_pool.release(staging)
            raise

        def xfer():
            pooled = staging
            try:
                with clock.during("put"), trace.span("dl.put", bytes=arr.nbytes):
                    out = [
                        (
                            dev,
                            jax.device_put(arr, dev),
                            jax.device_put(scale, dev) if scale is not None else None,
                        )
                        for dev, _ in group
                    ]
                    if pooled is not None:
                        # the transfer may still be reading the pooled host
                        # buffer asynchronously: wait before recycling it —
                        # and if the backend zero-copied (the device array
                        # ALIASES the buffer, PJRT CPU with 64-byte-aligned
                        # hosts), hand the memory over instead of recycling
                        devs = [t[1] for t in out]
                        jax.block_until_ready(devs)
                        if _aliases_buffer(devs, pooled):
                            staging_pool.forfeit(pooled)
                        else:
                            staging_pool.release(pooled)
                        pooled = None
                return out
            finally:
                inflight.release(cost)
                if pooled is not None:  # device_put raised before handoff
                    staging_pool.release(pooled)

        try:
            return transfer_pool.submit(xfer)
        except BaseException:
            # submit can refuse (pool shut down after a sibling error); give
            # the budget back or the remaining fetch workers deadlock
            inflight.release(cost)
            if staging is not None:
                staging_pool.release(staging)
            raise

    inflight = _ByteBudget(transfer_budget_bytes)
    # contexts unwind LIFO, so the cleanup stack runs first on ANY exit: a
    # failed load must not strand the split executor's idle threads in a
    # long-lived serve process (one leak per retry against a flaky registry)
    with ThreadPoolExecutor(max_workers=concurrency) as pool, ThreadPoolExecutor(
        max_workers=n_transfer
    ) as transfer_pool, contextlib.ExitStack() as _cleanup:
        if split_pool is not None:
            _cleanup.callback(split_pool.shutdown, False)
        futures = {}
        # big tensors first: their fetch+transfer dominates the critical path
        for name, info in sorted(tensors.items(), key=lambda kv: -kv[1].nbytes):
            _sharding, groups = plans[name]
            futures[name] = [pool.submit(fetch_group, info, g) for g in groups]
        # drain fetches: big tensors already stream through the transfer
        # pool; small ones collect into pack jobs keyed by device-set
        settled: dict[str, list] = {}
        pack_jobs: dict[tuple, list] = {}
        for name in futures:
            entries = []
            for gi, fut in enumerate(futures[name]):
                r = fut.result()
                if isinstance(r, tuple) and r and r[0] == "pack":
                    _tag, arr, group = r
                    key = tuple(sorted(d.id for d, _idx in group))
                    pack_jobs.setdefault(key, []).append((name, gi, arr, group))
                    entries.append(None)  # shard arrives via the pack
                else:
                    entries.append(r)
            settled[name] = entries
        with clock.during("put"), trace.span("dl.put", packs=len(pack_jobs)):
            packed = _transfer_packs(pack_jobs)
        for name, info in tensors.items():
            sharding, _groups = plans[name]
            shards, scale_shards = [], []
            for gi, entry in enumerate(settled[name]):
                if entry is None:
                    shards.extend(arr for _dev, arr in packed[(name, gi)])
                    continue
                for _dev, arr, sc in entry.result():
                    shards.append(arr)
                    if sc is not None:
                        scale_shards.append(sc)
            global_shape = info.shape if info.shape else ()
            if scale_shards:
                spec = sharding.spec
                scale_sharding = NamedSharding(
                    mesh, PartitionSpec(spec[0] if len(spec) else None)
                )
                results[name] = qt.QTensor(
                    jax.make_array_from_single_device_arrays(global_shape, sharding, shards),
                    jax.make_array_from_single_device_arrays(
                        (info.shape[0],), scale_sharding, scale_shards
                    ),
                )
                stats.bytes_to_device += int(np.prod(info.shape)) + info.shape[0] * 4
            else:
                target_dtype = np.dtype(dtype) if dtype is not None else info.np_dtype()
                results[name] = jax.make_array_from_single_device_arrays(
                    global_shape, sharding, shards
                )
                stats.bytes_to_device += int(np.prod(info.shape or (1,))) * target_dtype.itemsize
            stats.tensors += 1
        _full_cache.clear()
        _scale_cache.clear()

    jax.block_until_ready(results)  # QTensor entries are pytrees
    stats.total_seconds = clock.stop() - t0
    stats.fetch_width = governor.width
    stats.fetch_backoffs = governor.backoffs
    stats.fetch_growths = governor.growths
    stats.fetch_busy_seconds = clock.busy["fetch"]
    stats.device_put_seconds = clock.busy["put"]
    stats.overlap_seconds = clock.overlap_s
    stats.idle_seconds = clock.idle_s
    stats.assemble_seconds = clock.assemble_s
    stats.backpressure_seconds = clock.backpressure_s
    stats.drain_seconds = clock.drain_s
    stats.staging_allocs = staging_pool.allocs
    stats.staging_reuses = staging_pool.reuses
    trace.record(
        "dl.load", t0, stats.total_seconds,
        tensors=stats.tensors,
        bytes_fetched=stats.bytes_fetched,
        bytes_to_device=stats.bytes_to_device,
        fetch_thread_s=round(stats.fetch_seconds, 3),
        overlap_s=round(stats.overlap_seconds, 3),
        assemble_s=round(stats.assemble_seconds, 3),
        assemble_copied_bytes=stats.assemble_copied_bytes,
        staging_allocs=stats.staging_allocs,
        gbps=round(stats.gbps, 3),
    )
    return results, stats


def _as_np(raw, np_dtype, shape) -> np.ndarray:
    """View raw bytes (np.uint8 array or bytes) as a typed array, zero-copy."""
    if isinstance(raw, np.ndarray):
        return raw.view(np_dtype).reshape(shape)
    return np.frombuffer(raw, dtype=np_dtype).reshape(shape)


def _normalize_index(idx: tuple, shape: tuple) -> tuple:
    out = []
    for s, dim in zip(idx, shape):
        start = s.start or 0
        stop = s.stop if s.stop is not None else dim
        out.append(slice(start, stop))
    return tuple(out)


def _index_key(idx: tuple, shape: tuple) -> tuple:
    return tuple((s.start or 0, s.stop if s.stop is not None else dim) for s, dim in zip(idx, shape))
