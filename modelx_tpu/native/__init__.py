"""ctypes bindings for the native IO engine (modelx_io.cc).

The reference's data plane is a compiled Go binary; here the byte-moving hot
loops (ranged HTTP fetch, positional file scatter reads, sha256 content
addressing) are C++ compiled on demand with the baked-in g++ and loaded via
ctypes — every call releases the GIL for its full duration, so loader fetch
threads don't contend with the jax.device_put dispatch thread.

The library is named after the digest of the source it was built from
(``_build/libmodelx_io-<sha256[:16]>.so``), so a binary is only ever loaded
for the exact ``modelx_io.cc`` beside it: a file left in ``_build/`` by
another checkout, or one whose mtime merely looks newer, cannot stand in
for the committed source. If the library cannot be built or loaded,
``lib()`` says why at WARNING (compiler stderr included), returns None, and
callers keep their pure-Python paths.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading

logger = logging.getLogger("modelx.native")

_SRC = os.path.join(os.path.dirname(__file__), "modelx_io.cc")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


class MxRange(ctypes.Structure):
    _fields_ = [
        ("offset", ctypes.c_int64),
        ("length", ctypes.c_int64),
        ("buf", ctypes.c_void_p),
    ]


def so_path() -> str:
    """Where the library for the CURRENT source lives (whether or not it
    has been built yet)."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libmodelx_io-{digest}.so")


def build(force: bool = False) -> str | None:
    """Compile modelx_io.cc -> ``so_path()``. Returns the path, or None
    (with a WARNING carrying the compiler's stderr) when it cannot be
    built. Cached by source digest: an existing library for this exact
    source is reused — that is how a container image that bakes the .so
    but ships no toolchain keeps working — and one built from any other
    source is never picked up."""
    so = so_path()
    if not force and os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # per-process temp output so concurrent builds can't corrupt each other;
    # os.replace publishes atomically and last-writer-wins is fine (same src)
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", tmp, _SRC, "-ldl"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        stderr = getattr(e, "stderr", b"") or b""
        logger.warning("native IO engine build failed (%s)%s", e,
                       ": " + stderr.decode(errors="replace")[-2000:] if stderr else "")
        return None
    for stale in glob.glob(os.path.join(_BUILD_DIR, "libmodelx_io*.so")):
        if stale != so:  # libraries of earlier sources: never loaded again
            try:
                os.unlink(stale)
            except OSError:
                pass
    return so


def lib() -> ctypes.CDLL | None:
    """The loaded native library, building it on first use; None if the
    native engine is unavailable (callers fall back to pure Python)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = build()  # no-op when this source's library already exists
        if path is None:
            return None
        try:
            l = ctypes.CDLL(path)
        except OSError as e:
            logger.warning("native IO engine load failed (%s): pure-Python "
                           "fetch/hash paths in use", e)
            return None
        l.mx_pread_scatter.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(MxRange), ctypes.c_int, ctypes.c_int,
        ]
        l.mx_pread_scatter.restype = ctypes.c_int
        l.mx_pread_fd.argtypes = [
            ctypes.c_int, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
        ]
        l.mx_pread_fd.restype = ctypes.c_int
        l.mx_sha256_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        l.mx_sha256_file.restype = ctypes.c_int
        l.mx_sha256_buf.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p]
        l.mx_sha256_buf.restype = ctypes.c_int
        l.mx_http_connect.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
        l.mx_http_connect.restype = ctypes.c_void_p
        l.mx_http_close.argtypes = [ctypes.c_void_p]
        l.mx_http_close.restype = None
        l.mx_http_get_range.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
        ]
        l.mx_http_get_range.restype = ctypes.c_int
        l.mx_quantize_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ]
        l.mx_quantize_rows.restype = ctypes.c_int
        _lib = l
        return _lib


def available() -> bool:
    return lib() is not None


# -- high-level wrappers ------------------------------------------------------


def sha256_file(path: str) -> str | None:
    """Hex sha256 of a file, GIL-free; None if the engine is unavailable."""
    l = lib()
    if l is None:
        return None
    out = ctypes.create_string_buffer(65)
    rc = l.mx_sha256_file(path.encode(), out)
    if rc != 0:
        raise OSError(-rc, f"mx_sha256_file({path}): {os.strerror(-rc)}")
    return out.value.decode()


def sha256_buffer(view) -> str | None:
    """Hex sha256 of a bytes-like object; None if unavailable."""
    l = lib()
    if l is None:
        return None
    mv = memoryview(view)
    if not mv.c_contiguous:
        mv = memoryview(bytes(mv))
    out = ctypes.create_string_buffer(65)
    addr = ctypes.addressof(ctypes.c_char.from_buffer(mv)) if not mv.readonly else None
    if addr is None:
        buf = (ctypes.c_char * len(mv)).from_buffer_copy(mv)
        addr = ctypes.addressof(buf)
    l.mx_sha256_buf(addr, len(mv), out)
    return out.value.decode()


def pread_fd(fd: int, offset: int, length: int, out) -> None:
    """Single GIL-free positional read on an already-open fd. ``out`` must
    hold at least ``length`` bytes — the native side writes ``length`` bytes
    unconditionally, so an undersized buffer would be heap corruption."""
    l = lib()
    if l is None:
        raise RuntimeError("native engine unavailable")
    mv = memoryview(out)
    if length < 0 or mv.nbytes < length:
        raise ValueError(f"buffer holds {mv.nbytes} bytes, need {length}")
    c = ctypes.c_char.from_buffer(out)
    rc = l.mx_pread_fd(fd, offset, length, ctypes.addressof(c))
    if rc != 0:
        raise OSError(-rc, f"mx_pread_fd: {os.strerror(-rc)}")


def pread_scatter(path: str, ranges: list[tuple[int, int, memoryview]], threads: int = 8) -> None:
    """Parallel positional reads: each (offset, length, writable buffer)."""
    l = lib()
    if l is None:
        raise RuntimeError("native engine unavailable")
    arr = (MxRange * len(ranges))()
    _keep = []
    for i, (off, ln, mv) in enumerate(ranges):
        if ln < 0 or memoryview(mv).nbytes < ln:
            raise ValueError(
                f"range {i}: buffer holds {memoryview(mv).nbytes} bytes, need {ln}"
            )
        c = ctypes.c_char.from_buffer(mv)
        _keep.append(c)
        arr[i] = MxRange(off, ln, ctypes.addressof(c))
    rc = l.mx_pread_scatter(path.encode(), arr, len(ranges), threads)
    if rc != 0:
        raise OSError(-rc, f"mx_pread_scatter({path}): {os.strerror(-rc)}")


def _quant_dtype_code(dtype) -> int | None:
    """mx_quantize_rows dtype code for a numpy dtype, or None (unsupported)."""
    import numpy as np

    if dtype == np.float32:
        return 0
    if dtype == np.float16:
        return 2
    try:
        import ml_dtypes

        if dtype == ml_dtypes.bfloat16:
            return 1
    except ImportError:
        pass
    return None


def quantize_rows(arr, scales=None, want_q: bool = True, threads: int = 0):
    """Fused rowwise int8 quantization of a 2-D float array, GIL-free.

    Returns (q int8 [rows, cols] or None, scales f32 [rows]) — numerically
    identical to ops/quant.py's numpy path — or None when the native engine
    is unavailable or the dtype/layout is unsupported (callers fall back).
    ``scales`` given = quantize with the caller's scales (sharded loads);
    absent = compute them (absmax/127). ``want_q=False`` = scales only.
    """
    import numpy as np

    l = lib()
    if l is None:
        return None
    arr = np.asarray(arr)
    if arr.ndim != 2:
        return None
    code = _quant_dtype_code(arr.dtype)
    if code is None:
        return None
    if not arr.flags.c_contiguous:
        return None
    rows, cols = arr.shape
    if rows == 0 or cols == 0:  # degenerate shapes keep the numpy semantics
        return None
    if threads <= 0:
        threads = min(4, os.cpu_count() or 1)
    q = np.empty((rows, cols), np.int8) if want_q else None
    if scales is not None:
        scales_arr = np.ascontiguousarray(scales, np.float32)
        if scales_arr.shape != (rows,):
            raise ValueError(f"scales shape {scales_arr.shape} != ({rows},)")
        scales_in, scales_out = scales_arr.ctypes.data, None
    else:
        scales_arr = np.empty((rows,), np.float32)
        scales_in, scales_out = None, scales_arr.ctypes.data
    rc = l.mx_quantize_rows(
        arr.ctypes.data, code, rows, cols, scales_in, scales_out,
        q.ctypes.data if q is not None else None, threads,
    )
    if rc != 0:
        raise OSError(-rc, f"mx_quantize_rows: {os.strerror(-rc)}")
    return q, scales_arr


class NativeHTTPConnection:
    """One keep-alive connection to an http:// origin; ranged GETs land
    straight in caller buffers with the GIL released."""

    def __init__(self, host: str, port: int, timeout_ms: int = 300_000) -> None:
        l = lib()
        if l is None:
            raise RuntimeError("native engine unavailable")
        self._lib = l
        self._conn = l.mx_http_connect(host.encode(), port, timeout_ms)
        if not self._conn:
            raise OSError(f"connect {host}:{port} failed")
        self._host = host
        self._port = port

    def get_range(self, path: str, offset: int, length: int, out: memoryview,
                  headers: str = "") -> int:
        """Returns the HTTP status; raises on transport errors. ``out`` must
        be exactly ``length`` bytes."""
        if len(out) != length:
            raise ValueError(f"buffer {len(out)} != length {length}")
        c = ctypes.c_char.from_buffer(out)
        # bracket IPv6 literals (urlsplit strips the brackets)
        host = f"[{self._host}]" if ":" in self._host else self._host
        host_hdr = f"{host}:{self._port}"
        rc = self._lib.mx_http_get_range(
            self._conn, host_hdr.encode(), path.encode(), headers.encode(),
            offset, length, ctypes.addressof(c),
        )
        if rc < 0:
            raise OSError(f"native ranged GET failed (code {rc}) for {path}")
        return rc

    def close(self) -> None:
        if getattr(self, "_conn", None):
            self._lib.mx_http_close(self._conn)
            self._conn = None

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass
