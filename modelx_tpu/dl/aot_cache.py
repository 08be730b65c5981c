"""Serialized-executable cache: skip trace+lower on warm starts.

The persistent XLA compilation cache (dl/serve.enable_compile_cache) removes
the *XLA compile* from a fresh sidecar's critical path, but jax still pays
tracing + lowering in Python every process (~370 ms measured for the 48 MB
bench model on this host — 80% of the warm precompile cost, and on a
small-core host that CPU time is stolen from the concurrent weight load).
This cache stores the ``jax.export`` artifact (StableHLO, ~36 KB for the
same model) keyed by everything that shapes the program; a warm start
deserializes (~10 ms) and compiles the artifact (persistent-cache hit), so
the deploy's compile leg is ~4x cheaper on CPU.

No reference equivalent (the reference never compiles anything); this is
TTFT machinery for the BASELINE north star (p50 < 500 ms leaves no room for
retracing a model every pod start).
"""

from __future__ import annotations

import hashlib
import logging
import os

import jax

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
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(cache_dir, exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)  # atomic: concurrent warmups must not torn-read
    except Exception as e:
        logger.warning("aot cache write failed: %s", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
    return compiled
