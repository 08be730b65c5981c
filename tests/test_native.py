"""Native IO engine (modelx_tpu/native): build, hashing, scatter reads,
raw-socket ranged HTTP — plus graceful pure-Python fallback when disabled.

The engine replaces the byte-moving hot loops the reference ships as a
compiled Go binary (pkg/client/push.go digesting, extension_s3.go ranged
transfers); correctness is asserted against hashlib and the Python paths.
"""

import hashlib
import os

import numpy as np
import pytest

from modelx_tpu import native

pytestmark = pytest.mark.skipif(not native.available(), reason="no native toolchain")


class TestSha256:
    def test_file_matches_hashlib(self, tmp_path):
        data = os.urandom(3 * 1024 * 1024 + 17)
        p = tmp_path / "blob"
        p.write_bytes(data)
        assert native.sha256_file(str(p)) == hashlib.sha256(data).hexdigest()

    def test_empty_file(self, tmp_path):
        p = tmp_path / "empty"
        p.write_bytes(b"")
        assert native.sha256_file(str(p)) == hashlib.sha256(b"").hexdigest()

    def test_missing_file_raises(self, tmp_path):
        with pytest.raises(OSError):
            native.sha256_file(str(tmp_path / "nope"))

    def test_buffer(self):
        for payload in (b"", b"abc", os.urandom(100_000)):
            assert native.sha256_buffer(payload) == hashlib.sha256(payload).hexdigest()

    def test_digest_from_file_uses_native(self, tmp_path):
        from modelx_tpu.types import Digest

        data = b"x" * 123457
        p = tmp_path / "f"
        p.write_bytes(data)
        assert str(Digest.from_file(str(p))) == "sha256:" + hashlib.sha256(data).hexdigest()


class TestPreadScatter:
    def test_scatter(self, tmp_path):
        data = os.urandom(1 << 20)
        p = tmp_path / "blob"
        p.write_bytes(data)
        bufs = [np.empty(4096, np.uint8) for _ in range(16)]
        ranges = [(i * 4096, 4096, memoryview(b)) for i, b in enumerate(bufs)]
        native.pread_scatter(str(p), ranges, threads=4)
        for i, b in enumerate(bufs):
            assert bytes(b) == data[i * 4096 : (i + 1) * 4096]

    def test_short_file_raises(self, tmp_path):
        p = tmp_path / "small"
        p.write_bytes(b"abc")
        buf = np.empty(10, np.uint8)
        with pytest.raises(OSError):
            native.pread_scatter(str(p), [(0, 10, memoryview(buf))])

    def test_undersized_buffer_rejected(self, tmp_path):
        """The native side writes `length` bytes unconditionally — an
        undersized buffer must be rejected before it becomes heap
        corruption."""
        p = tmp_path / "blob"
        p.write_bytes(b"x" * 4096)
        small = np.empty(16, np.uint8)
        with pytest.raises(ValueError, match="buffer"):
            native.pread_scatter(str(p), [(0, 4096, memoryview(small))])

    def test_pread_fd_undersized_buffer_rejected(self, tmp_path):
        p = tmp_path / "blob"
        p.write_bytes(b"y" * 4096)
        fd = os.open(str(p), os.O_RDONLY)
        try:
            small = np.empty(16, np.uint8)
            with pytest.raises(ValueError, match="buffer"):
                native.pread_fd(fd, 0, 4096, memoryview(small))
            # exact-size buffer still works
            buf = np.empty(4096, np.uint8)
            native.pread_fd(fd, 0, 4096, memoryview(buf))
            assert bytes(buf) == b"y" * 4096
        finally:
            os.close(fd)


class TestNativeHTTP:
    @pytest.fixture()
    def served_blob(self):
        from modelx_tpu.registry.fs import MemoryFSProvider
        from modelx_tpu.registry.server import Options, RegistryServer, free_port
        from modelx_tpu.registry.store_fs import FSRegistryStore
        from modelx_tpu.types import Digest

        srv = RegistryServer(
            Options(listen=f"127.0.0.1:{free_port()}"),
            store=FSRegistryStore(MemoryFSProvider()),
        )
        base = srv.serve_background()
        data = os.urandom(2 << 20)
        digest = str(Digest.from_bytes(data))
        import requests

        requests.put(f"{base}/library/n/blobs/{digest}", data=data)
        yield base, f"/library/n/blobs/{digest}", data
        srv.shutdown()

    def test_ranged_get_and_keepalive(self, served_blob):
        base, path, data = served_blob
        from urllib.parse import urlsplit

        u = urlsplit(base)
        conn = native.NativeHTTPConnection(u.hostname, u.port)
        try:
            buf = np.empty(4096, np.uint8)
            assert conn.get_range(path, 100, 4096, memoryview(buf)) == 206
            assert bytes(buf) == data[100:4196]
            # second request on the same connection
            assert conn.get_range(path, 0, 10, memoryview(buf)[:10]) == 206
            assert bytes(buf[:10]) == data[:10]
        finally:
            conn.close()

    def test_error_status_reported_and_connection_survives(self, served_blob):
        base, path, data = served_blob
        from urllib.parse import urlsplit

        u = urlsplit(base)
        conn = native.NativeHTTPConnection(u.hostname, u.port)
        try:
            buf = np.empty(16, np.uint8)
            missing = "/library/n/blobs/sha256:" + "0" * 64
            assert conn.get_range(missing, 0, 16, memoryview(buf)) == 404
            assert conn.get_range(path, 0, 16, memoryview(buf)) == 206
        finally:
            conn.close()

    def test_httpsource_python_fallback(self, served_blob, monkeypatch):
        """With the native engine unavailable the loader's HTTPSource keeps
        serving ranged reads through http.client."""
        from modelx_tpu.dl.loader import HTTPSource

        monkeypatch.setattr(native, "available", lambda: False)
        src = HTTPSource(served_blob[0] + served_blob[1])
        base, path, data = served_blob
        got = bytes(memoryview(src.read_range(7, 1000)))
        assert got == data[7:1007]

    def test_httpsource_native_path(self, served_blob):
        from modelx_tpu.dl.loader import HTTPSource

        base, path, data = served_blob
        src = HTTPSource(base + path)
        assert src._use_native
        got = bytes(memoryview(src.read_range(0, 2 << 20)))
        assert got == data
        assert src.size() == len(data)

    def test_large_error_body_drained_then_reusable(self, served_blob):
        """A 404 whose error body exceeds the header scratch buffer must not
        poison the keep-alive stream for the next request."""
        import socket, threading

        data_big = b"E" * 64 * 1024
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]
        payload = b"0123456789abcdef"

        def serve():
            conn, _ = srv.accept()
            for _ in range(2):
                req = b""
                while b"\r\n\r\n" not in req:
                    req += conn.recv(4096)
                if b"/missing" in req:
                    conn.sendall(
                        b"HTTP/1.1 404 Not Found\r\nContent-Length: "
                        + str(len(data_big)).encode()
                        + b"\r\n\r\n"
                        + data_big
                    )
                else:
                    conn.sendall(
                        b"HTTP/1.1 206 Partial Content\r\nContent-Length: 16\r\n\r\n"
                        + payload
                    )
            conn.close()

        t = threading.Thread(target=serve, daemon=True)
        t.start()
        conn = native.NativeHTTPConnection("127.0.0.1", port)
        try:
            buf = np.empty(16, np.uint8)
            assert conn.get_range("/missing", 0, 16, memoryview(buf)) == 404
            assert conn.get_range("/blob", 0, 16, memoryview(buf)) == 206
            assert bytes(buf) == payload
        finally:
            conn.close()
            srv.close()

    def test_unknown_length_error_redials(self, served_blob):
        """No Content-Length on an error: the connection is dropped and the
        next request transparently redials."""
        import socket, threading

        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(2)
        port = srv.getsockname()[1]
        payload = b"fresh-connection"

        def serve():
            conn, _ = srv.accept()
            req = b""
            while b"\r\n\r\n" not in req:
                req += conn.recv(4096)
            conn.sendall(b"HTTP/1.1 503 Unavailable\r\n\r\nsome trailing junk")
            conn.close()
            conn2, _ = srv.accept()
            req = b""
            while b"\r\n\r\n" not in req:
                req += conn2.recv(4096)
            conn2.sendall(
                b"HTTP/1.1 206 Partial Content\r\nContent-Length: 16\r\n\r\n" + payload
            )
            conn2.close()

        t = threading.Thread(target=serve, daemon=True)
        t.start()
        conn = native.NativeHTTPConnection("127.0.0.1", port)
        try:
            buf = np.empty(16, np.uint8)
            assert conn.get_range("/x", 0, 16, memoryview(buf)) == 503
            assert conn.get_range("/y", 0, 16, memoryview(buf)) == 206
            assert bytes(buf) == payload
        finally:
            conn.close()
            srv.close()


class TestBuildKeyedOnSource:
    """The library is named after the digest of modelx_io.cc: a binary is
    only ever used for the exact source beside it."""

    @staticmethod
    def _no_gxx(monkeypatch):
        import subprocess

        def no_gxx(*a, **kw):
            raise OSError("g++ not found")

        monkeypatch.setattr(subprocess, "run", no_gxx)

    def test_existing_so_used_when_toolchain_missing(self, monkeypatch):
        """Container images bake an arch-correct .so but ship no g++, and
        install mtimes can make the source look newer — build() must return
        the library built from this source, not None."""
        from modelx_tpu import native

        built = native.build(force=True)
        if built is None:
            pytest.skip("no local toolchain to produce a .so")
        os.utime(native._SRC)  # source mtime newer than the library's
        self._no_gxx(monkeypatch)
        assert native.build() == built == native.so_path()

    def test_binary_of_other_source_is_never_picked_up(self, tmp_path,
                                                       monkeypatch, caplog):
        """An untracked library left in _build/ (another checkout's, or one
        whose mtime merely looks newer) must not stand in for the committed
        source: with no toolchain the answer is None plus a WARNING, not a
        stale engine."""
        import logging
        import shutil

        from modelx_tpu import native

        src = tmp_path / "modelx_io.cc"
        shutil.copy(native._SRC, src)
        src.write_text(src.read_text() + "\n// edited\n")
        build_dir = tmp_path / "_build"
        build_dir.mkdir()
        (build_dir / "libmodelx_io.so").write_bytes(b"stale")
        monkeypatch.setattr(native, "_SRC", str(src))
        monkeypatch.setattr(native, "_BUILD_DIR", str(build_dir))
        self._no_gxx(monkeypatch)
        with caplog.at_level(logging.WARNING, logger="modelx.native"):
            assert native.build() is None
        assert "build failed" in caplog.text and "g++ not found" in caplog.text

    def test_compiler_stderr_rides_the_warning(self, tmp_path, monkeypatch, caplog):
        import logging
        import shutil

        from modelx_tpu import native

        if shutil.which("g++") is None:
            pytest.skip("no local toolchain")
        src = tmp_path / "modelx_io.cc"
        src.write_text("this is not c++\n")
        monkeypatch.setattr(native, "_SRC", str(src))
        monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "_build"))
        with caplog.at_level(logging.WARNING, logger="modelx.native"):
            assert native.build() is None
        assert "error" in caplog.text  # g++'s own diagnostics, not just rc 1


class TestQuantizeRows:
    """mx_quantize_rows: the fused int8 kernel must be bit-identical to
    ops/quant.py's numpy fallback (same f32 reciprocal, same round-half-even)
    so native and fallback loads of one checkpoint agree byte-for-byte."""

    @pytest.fixture(autouse=True)
    def _need_native(self):
        from modelx_tpu import native

        if not native.available():
            pytest.skip("native engine unavailable")

    def _numpy_ref(self, w):
        w32 = np.asarray(w, np.float32)
        amax = np.max(np.abs(w32), axis=1)
        scale = (amax / 127.0 + (amax == 0)).astype(np.float32)
        inv = (np.float32(1.0) / scale)[:, None]
        q = np.clip(np.rint(w32 * inv), -127, 127).astype(np.int8)
        return q, scale

    def test_parity_all_dtypes(self):
        import ml_dtypes

        from modelx_tpu import native

        rng = np.random.RandomState(7)
        for dt in (np.float32, ml_dtypes.bfloat16, np.float16):
            w = rng.randn(37, 129).astype(dt)
            w[5] = 0  # all-zero row: scale pins to 1.0
            ref_q, ref_s = self._numpy_ref(w)
            q, s = native.quantize_rows(w)
            np.testing.assert_array_equal(s, ref_s)
            np.testing.assert_array_equal(q, ref_q)
            # caller-provided scales (sharded loads)
            q2, _ = native.quantize_rows(w, scales=ref_s)
            np.testing.assert_array_equal(q2, ref_q)
            # scales-only pass
            q3, s3 = native.quantize_rows(w, want_q=False)
            assert q3 is None
            np.testing.assert_array_equal(s3, ref_s)
            # threaded split must not change results
            q4, s4 = native.quantize_rows(w, threads=4)
            np.testing.assert_array_equal(q4, ref_q)
            np.testing.assert_array_equal(s4, ref_s)

    def test_half_integer_rounding(self):
        """Values landing exactly on .5 boundaries take round-half-even,
        matching np.rint (the magic-number rounding in quant1)."""
        from modelx_tpu import native

        w = (np.arange(-508, 508, dtype=np.float32).reshape(4, 254)) / 2.0
        s_in = np.ones((4,), np.float32)
        ref = np.clip(np.rint(w), -127, 127).astype(np.int8)
        q, _ = native.quantize_rows(w, scales=s_in)
        np.testing.assert_array_equal(q, ref)

    def test_unsupported_shapes_fall_back(self):
        from modelx_tpu import native

        assert native.quantize_rows(np.zeros((3,), np.float32)) is None  # 1-D
        assert native.quantize_rows(np.zeros((2, 2), np.int8)) is None  # int
        assert native.quantize_rows(np.zeros((0, 4), np.float32)) is None
        # non-contiguous views fall back rather than misread strides
        base = np.zeros((4, 8), np.float32)
        assert native.quantize_rows(base[:, ::2]) is None
