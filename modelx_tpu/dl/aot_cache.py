"""What a node keeps of its compiled programs between pods.

The persistent XLA compilation cache (dl/serve.enable_compile_cache) removes
the *XLA compile* from a fresh pod's critical path, but its key is the
LOWERED module: a hit still pays tracing and lowering in Python, in every
process, for every program. On the TPU v5e that is 1.54-1.63 s a program
before the 0.49 s read-back (PERF_LEDGER.jsonl, PR 31,
``cache.trace_lower_s_per_program.decode`` / ``cache.retrieval_s_per_program
.decode``): 74 s of the decode cell's 165 s set-up for its 48 programs, and
3.9 s of a deploy's first request. Two stores here take that away, each keyed
by a description taken BEFORE tracing:

- :func:`load_or_compile` keeps the ``jax.export`` artifact (StableHLO) of the
  forward path and of the program bundles (dl/program_store.py): a warm start
  deserializes it and compiles it (a persistent-cache hit), no tracing.
- :class:`ExecutableStore` / :class:`StoredProgram` keep the loaded executable
  itself (``jax.experimental.serialize_executable``) for the continuous
  engine's programs, under ``<compile cache dir>/programs/``: a warm start is
  hash a description -> read a file -> deserialize -> call. A pod started with
  the compile cache off has no store. Entries are invalidated by their key
  (anything that shapes the executable, the package source included) and by
  any failure to read, load or call them; ``rm -r <dir>/programs`` clears it.

Neither is ever load-bearing: every failure takes the plain
trace + lower + compile path.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
import time
from concurrent.futures import Future

import jax

from modelx_tpu.utils import trace

logger = logging.getLogger("modelx.aot")

_code_version: str | None = None  # digest of the package source, once


def _version_tag() -> str:
    """Digest of every modelx_tpu source file. NOT git metadata: a deployed
    image has no .git (and `git` in an arbitrary CWD reads some other
    repo's HEAD), yet a forward fix shipped by image upgrade must still
    miss the cache. ~0.5 MB of source hashes in milliseconds, once."""
    global _code_version
    if _code_version is None:
        import modelx_tpu

        root = os.path.dirname(os.path.abspath(modelx_tpu.__file__))
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in sorted(os.walk(root)):
            dirnames.sort()
            for name in sorted(filenames):
                if name.endswith(".py"):
                    p = os.path.join(dirpath, name)
                    h.update(os.path.relpath(p, root).encode())
                    with open(p, "rb") as f:
                        h.update(f.read())
        _code_version = h.hexdigest()[:16]
    return _code_version


def code_version() -> str:
    """Public handle on the package-source digest, for callers that stamp
    artifacts with the environment they were built in (dl/program_store.py):
    a bundle exported by different code must be rejected at install, not
    deserialize a pre-fix program."""
    return _version_tag()


def artifact_name(key: str) -> str:
    """Filename of the serialized export for ``key`` — the single naming
    convention shared by load_or_compile and the program-store bundler."""
    return f"aot-{key}.bin"


def artifact_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, artifact_name(key))


def cache_key(*parts) -> str:
    """Stable digest over everything that shapes the compiled program —
    including the framework version + git commit, because the program BODY
    (family.forward) lives in this package: a forward fix must miss the
    cache, not warm-start the pre-fix StableHLO."""
    h = hashlib.sha256()
    h.update(jax.__version__.encode())
    h.update(jax.default_backend().encode())
    h.update(_version_tag().encode())
    for p in parts:
        h.update(b"\x00")
        h.update(repr(p).encode())
    return h.hexdigest()[:32]


def describe_sds(param_sds: dict) -> list:
    """Key material for a pytree of ShapeDtypeStructs (QTensor entries
    flatten to their leaves), shardings included — a changed partition rule
    or quantize mode must miss the cache, not execute stale."""
    out = []
    for path, s in jax.tree_util.tree_flatten_with_path(param_sds)[0]:
        sharding = getattr(s, "sharding", None)
        spec = getattr(sharding, "spec", None)
        out.append((jax.tree_util.keystr(path), tuple(s.shape), str(s.dtype), str(spec)))
    return out


def load_or_compile(fn, args: tuple, cache_dir: str, key: str):
    """Compile ``fn`` for abstract ``args``, reusing a serialized export.

    Warm path: deserialize the stored StableHLO and compile it (persistent
    XLA cache makes that compile cheap) — no tracing of ``fn``. Cold path:
    export ``fn`` once (one trace), compile from the exported artifact, and
    persist it. Every failure falls back to the plain trace+lower+compile —
    the cache is an optimization, never load-bearing.
    """
    path = artifact_path(cache_dir, key)
    if os.path.isfile(path):
        try:
            with open(path, "rb") as f:
                exp = jax.export.deserialize(bytearray(f.read()))
            return jax.jit(exp.call).lower(*args).compile()
        except Exception as e:
            logger.warning("aot cache read failed (%s); recompiling", e)
            try:
                os.unlink(path)
            except OSError:
                pass
    try:
        exp = jax.export.export(jax.jit(fn))(*args)
        # compile the serialize->deserialize ROUNDTRIP, not the in-memory
        # export: the roundtrip perturbs the module bytes enough to change
        # the persistent-XLA-cache key, so compiling `exp` directly would
        # file that cache's executable under a key no warm start (which
        # only ever sees deserialized artifacts) can hit — measured, the
        # warm compile then pays the full XLA compile despite a "warm"
        # cache dir. Compiling the roundtrip writes the entry the warm
        # path (and every pod installing this node's program bundle,
        # dl/program_store.py) will actually look up, and proves the
        # artifact deserializes before it is persisted or shipped.
        blob = exp.serialize()
        warm = jax.export.deserialize(bytearray(blob))
        compiled = jax.jit(warm.call).lower(*args).compile()
    except Exception as e:
        logger.warning("aot export failed (%s); plain compile", e)
        return jax.jit(fn).lower(*args).compile()
    try:
        _write_atomic(path, blob)
    except OSError as e:
        logger.warning("aot cache write failed: %s", e)
    return compiled


def _write_atomic(path: str, blob: bytes) -> None:
    """Write under a name of this thread's own, then rename: concurrent
    warm-ups and other pods must never read a torn entry."""
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# -- loaded executables, under a key taken before tracing ---------------------

# this process's traffic with the store, beside jax's own counters in
# /metrics ``compile_cache``: hits = executables loaded from the store,
# misses = lookups that found nothing usable (the program was then built and
# written), bytes = entry bytes read on hits plus written on misses
# (bytes_read: the hits' alone). A hit's load_s is its file read (read_s),
# the unpickling, and PJRT's deserialize-and-load (deserialize_s)
_store_counts = {"store_hits": 0, "store_misses": 0, "store_load_s": 0.0,
                 "store_read_s": 0.0, "store_deserialize_s": 0.0,
                 "store_write_s": 0.0, "store_bytes": 0, "store_bytes_read": 0}
_store_counts_lock = threading.Lock()


def _count_store(**deltas) -> None:
    with _store_counts_lock:
        for key, delta in deltas.items():
            _store_counts[key] += delta


def store_stats() -> dict:
    with _store_counts_lock:
        return {k: round(v, 6) if isinstance(v, float) else v
                for k, v in _store_counts.items()}


# whether jax's persistent cache served this thread's last compile: XLA:CPU
# cannot serialize an executable it deserialized (jax 0.9.0 keeps no object
# code for it: the bytes load, then fail at run time with NOT_FOUND), so the
# store takes none of those there. The TPU runtime serializes them whole
# (PERF.md, PR 32). A build's own compile is its last: what tracing runs
# eagerly compiles before it.
_jax_cache = threading.local()
_JAX_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": True,
                     "/jax/compilation_cache/cache_misses": False}


def _note_jax_cache_event(event: str, **_kwargs) -> None:
    served = _JAX_CACHE_EVENTS.get(event)
    if served is not None:
        _jax_cache.served_last = served


jax.monitoring.register_event_listener(_note_jax_cache_event)


def executable_context(mesh) -> tuple:
    """What a loaded executable depends on beyond :func:`cache_key`'s jax
    version, backend and source digest: the runtime that compiled it, the
    devices it was compiled for, and the process-wide settings that shape
    lowering and compilation."""
    import jaxlib

    devices = list(mesh.devices.flat)
    return (
        jaxlib.__version__, devices[0].client.platform_version,
        devices[0].device_kind, tuple(mesh.axis_names),
        tuple(mesh.devices.shape), tuple(d.id for d in devices),
        jax.process_count(), jax.process_index(),
        os.environ.get("XLA_FLAGS", ""), os.environ.get("LIBTPU_INIT_ARGS", ""),
        bool(jax.config.jax_enable_x64),
        str(jax.config.jax_default_matmul_precision),
        str(jax.config.jax_default_prng_impl),
        bool(jax.config.jax_threefry_partitionable),
    )


def _leaf_key(x) -> tuple:
    """What a call's compiled variant depends on in one argument leaf, as
    hashable objects (the in-process memo's key): shape, dtype, weak type,
    and the sharding where the leaf is committed to one — an array jax may
    still place lowers without one. An abstract leaf that names a sharding
    is committed to it."""
    if not hasattr(x, "shape"):  # a python scalar: jax places it
        aval = jax.typeof(x)
        return (aval.shape, aval.dtype, aval.weak_type, None)
    sharding = getattr(x, "sharding", None)
    if sharding is not None and not getattr(x, "committed", True):
        sharding = None
    return (x.shape, x.dtype, getattr(x, "weak_type", False), sharding)


def _describe_leaf(shape, dtype, weak_type, sharding) -> tuple:
    """:func:`_leaf_key` as key material that is equal in two interpreters.
    A sharding is described by the slice of the leaf each device holds, and
    in which memory, so that shardings that lower alike (one device's, a
    one-device mesh's replicated one) describe alike."""
    where = None
    if sharding is not None:
        where = (getattr(sharding, "memory_kind", None), sorted(
            (d.id, str(index))
            for d, index in sharding.devices_indices_map(tuple(shape)).items()))
    return (tuple(shape), str(dtype), bool(weak_type), where)


class ExecutableStore:
    """Loaded-executable bytes under ``<cache_dir>/programs/``, for the
    programs of one engine: ``context`` is everything its programs share
    (the model, its abstract weights, the engine's geometry), hashed once
    with :func:`executable_context`. ``cache_dir`` "" (the compile cache is
    off) keeps nothing. Never load-bearing: an entry that cannot be read,
    loaded or called is unlinked and its program built as without a store."""

    def __init__(self, cache_dir: str, mesh, *context) -> None:
        self.dir = os.path.join(cache_dir, "programs") if cache_dir else ""
        self.devices = list(mesh.devices.flat)
        self.context = cache_key(executable_context(mesh), *context)

    def path(self, name: str, digest: str) -> str:
        return os.path.join(self.dir, f"{name}-{digest}.bin")

    def discard(self, name: str, digest: str) -> None:
        if self.dir:
            try:
                os.unlink(self.path(name, digest))
            except OSError:
                pass

    def load(self, name: str, digest: str):
        """The stored executable, loaded onto this store's devices, or None
        where there is none or it proved unusable (and was unlinked)."""
        if not self.dir:
            return None
        from jax.experimental.serialize_executable import deserialize_and_load

        path = self.path(name, digest)
        try:
            f = open(path, "rb")
        except OSError as e:  # no entry: a miss, and no span
            if not isinstance(e, FileNotFoundError):
                logger.warning("stored program %s unreadable (%s); rebuilding", path, e)
            _count_store(store_misses=1)
            return None
        t0 = time.monotonic()
        try:
            with f, trace.span("programs.load", program=name) as rec:
                with trace.span("read"):
                    blob = f.read()
                t_read = time.monotonic()
                rec["bytes"] = len(blob)
                with trace.span("unpickle"):
                    # unpickles only what save() below wrote on this node
                    payload, in_tree, out_tree = pickle.loads(blob)
                t_unpickled = time.monotonic()
                with trace.span("deserialize"):
                    compiled = deserialize_and_load(
                        payload, in_tree, out_tree, backend=self.devices[0].client,
                        execution_devices=self.devices)
        except Exception as e:  # whatever a torn or foreign entry raises
            logger.warning("stored program %s unusable (%s: %s); rebuilding",
                           path, type(e).__name__, e)
            self.discard(name, digest)
            _count_store(store_misses=1)
            return None
        t_loaded = time.monotonic()
        _count_store(store_hits=1, store_load_s=t_loaded - t0, store_read_s=t_read - t0,
                     store_deserialize_s=t_loaded - t_unpickled,
                     store_bytes=len(blob), store_bytes_read=len(blob))
        return compiled

    def save(self, name: str, digest: str, compiled, reread: bool = False) -> None:
        """``reread``: jax's persistent cache served ``compiled``."""
        if not self.dir or (reread and self.devices[0].platform == "cpu"):
            return
        from jax.experimental.serialize_executable import serialize

        path = self.path(name, digest)
        t0 = time.monotonic()
        try:
            blob = pickle.dumps(serialize(compiled))
            _write_atomic(path, blob)
        except Exception as e:  # an executable that does not serialize, a full disk
            logger.warning("executable store write failed (%s): %s", path, e)
            return
        _count_store(store_write_s=time.monotonic() - t0, store_bytes=len(blob))


class StoredProgram:
    """One ``jax.jit`` of an engine, called through its store. A call looks
    its compiled variant up in an in-process memo, by the static arguments
    and by what :func:`_leaf_key` reads of the others — not of the first
    ``described`` ones, the weights, which the store's context describes
    once. A variant the memo lacks is loaded from the store, else built
    (``jit.lower(...).compile()``, where jax's persistent cache still serves
    or fills) and written there; whoever asks first fetches it and everyone
    else waits, so no program is built twice. ``prefetch`` is the same fetch
    from abstract arguments, ahead of the first call."""

    def __init__(self, store: ExecutableStore, name: str, jit,
                 static_argnums: tuple = (), described: int = 0) -> None:
        self.store, self.name, self.jit = store, name, jit
        self._static = tuple(sorted(static_argnums))
        self._described = described
        # call key -> Compiled, or None where the jit itself runs the call
        self._memo: dict = {}
        self._fetched: dict[str, Future] = {}  # digest -> the same, once fetched
        self._lock = threading.Lock()

    def _key(self, args: tuple, kwargs: dict) -> tuple[tuple, tuple]:
        """(the call's memo key, its non-static arguments). Every keyword
        argument is static."""
        statics = ()
        if self._static:
            statics = tuple(args[i] for i in self._static)
            args = tuple(a for i, a in enumerate(args) if i not in self._static)
        leaves, tree = jax.tree_util.tree_flatten(args[self._described:])
        return (statics, tuple(sorted(kwargs.items())), tree,
                tuple(map(_leaf_key, leaves))), args

    def _digest(self, key: tuple) -> str:
        statics, kwargs, tree, leaves = key
        return cache_key(self.store.context, self.name, statics, kwargs, str(tree),
                         [_describe_leaf(*leaf) for leaf in leaves])

    def _reserve(self, digest: str) -> tuple[Future, bool]:
        """(the variant's future, whether the caller is the one to fetch it)."""
        with self._lock:
            fut = self._fetched.get(digest)
            if fut is not None:
                return fut, False
            fut = self._fetched[digest] = Future()
            return fut, True

    def _fetch(self, fut: Future, digest: str, args: tuple, kwargs: dict) -> None:
        try:
            compiled = self.store.load(self.name, digest)
            if compiled is None:
                _jax_cache.served_last = False
                with trace.span("programs.build", program=self.name):
                    compiled = self.jit.lower(*args, **kwargs).compile()
                self.store.save(self.name, digest, compiled,
                                reread=_jax_cache.served_last)
            fut.set_result(compiled)
        finally:
            if not fut.done():  # a waiting call must never hang: the jit runs it
                fut.set_result(None)

    def prefetch(self, *args, **kwargs):
        """Reserve the variant these arguments describe — abstract ones
        (``jax.ShapeDtypeStruct``) where nothing is allocated yet, described
        as the first call will meet them — and return the work that fetches
        it, for a side thread: a call that comes first waits for it. The
        work returns how many programs it delivered."""
        digest = self._digest(self._key(args, kwargs)[0])
        fut, mine = self._reserve(digest)
        if not mine:
            return lambda: 0

        def fetch() -> int:
            try:
                self._fetch(fut, digest, args, kwargs)
            except Exception as e:  # only the warm start is lost
                logger.warning("%s program warm-up failed (built at first use): %s",
                               self.name, e)
            return int(fut.result() is not None)

        return fetch

    def __call__(self, *args, **kwargs):
        key, dynamic = self._key(args, kwargs)
        try:
            compiled = self._memo[key]
        except KeyError:
            digest = self._digest(key)
            fut, mine = self._reserve(digest)
            if mine:
                self._fetch(fut, digest, args, kwargs)
            compiled = self._memo[key] = fut.result()
        if compiled is not None:
            try:
                return compiled(*dynamic)
            except (TypeError, ValueError) as e:
                # a Compiled checks its arguments before it runs or donates
                # anything: a stored or prefetched variant that these do not
                # fit (a loader that delivered other arrays than the abstract
                # weights described) goes, and the jit traces for what came
                logger.warning("%s program refused its arguments, compiling at "
                               "first use: %s", self.name, e)
                self._memo[key] = None
                self.store.discard(self.name, self._digest(key))
        return self.jit(*args, **kwargs)
