"""Child processes and HTTP helpers of the benchmark.

Copied from ``chip_smoke.py`` (PR 21), which proved them on the chip: the
parent never imports jax, every process that may touch the chip is a child,
one at a time, and every child is stopped and waited for.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = ["-m", "modelx_tpu.cli"]


class Fail(Exception):
    """A check of the run did not hold; the run prints no result line."""


def emit(phase: str, **fields) -> None:
    """An earlier line of stdout: one JSON object per phase."""
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Fail(what)


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


class Children:
    """Every process this run starts, so that every one is stopped."""

    def __init__(self, log_dir: str, cache_dir: str) -> None:
        self.log_dir = log_dir
        self.cache_dir = cache_dir
        self.live: list[subprocess.Popen] = []
        os.makedirs(log_dir, exist_ok=True)

    def env(self, jax_child: bool) -> dict:
        existing = os.environ.get("PYTHONPATH", "")
        # the cache may grow: the three cells' programs together are past the
        # 192 MiB some machines cap it at (JAX_COMPILATION_CACHE_MAX_SIZE), and
        # an evicted program would compile again inside a later run's set-up
        env = dict(os.environ,
                   PYTHONPATH=ROOT + (os.pathsep + existing if existing else ""),
                   JAX_COMPILATION_CACHE_DIR=self.cache_dir,
                   JAX_COMPILATION_CACHE_MAX_SIZE="-1")
        if not jax_child:
            env["JAX_PLATFORMS"] = "cpu"  # must never reach for the chip
        return env

    def run(self, name: str, argv: list[str], jax_child: bool, timeout: float) -> str:
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
                raise Fail(f"{name}: no answer in {timeout:.0f}s\n{tail(err_path)}") from None
            finally:
                if p in self.live and p.poll() is not None:
                    self.live.remove(p)
        if p.returncode != 0:
            raise Fail(f"{name}: exit {p.returncode}\n{tail(err_path)}")
        return out.decode()

    def start(self, name: str, argv: list[str], jax_child: bool) -> subprocess.Popen:
        with open(os.path.join(self.log_dir, f"{name}.log"), "wb") as log:
            p = subprocess.Popen([sys.executable, *argv], env=self.env(jax_child),
                                 stdout=log, stderr=subprocess.STDOUT)
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


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


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


def wait_ready(port: int, proc: subprocess.Popen, log_dir: str, timeout: float,
               poll_s: float = 0.05, seen: dict | None = None) -> float:
    """Seconds until ``/healthz`` answers 200. Polled every 50 ms: the wait
    is part of a timed deploy, and a coarser poll would show as noise.
    ``seen["listen_at"]`` is the clock when the port first answered at all
    (a pod answers 503 from the moment it listens, before it loads)."""
    t0 = time.monotonic()
    log = os.path.join(log_dir, f"{proc.log_name}.log")
    while time.monotonic() - t0 < timeout:
        if proc.poll() is not None:
            raise Fail(f"{proc.log_name} exited {proc.returncode} while starting\n{tail(log)}")
        try:
            status, _ = http_json(port, "GET", "/healthz", timeout=5.0)
            if seen is not None:
                seen.setdefault("listen_at", time.monotonic())
            if status == 200:
                return time.monotonic() - t0
        except OSError:
            pass
        time.sleep(poll_s)
    raise Fail(f"{proc.log_name} not ready in {timeout:.0f}s\n{tail(log)}")
