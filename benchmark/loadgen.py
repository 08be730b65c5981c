"""The load generator: one process, one thread per request in flight.

Each streamed request keeps the host-clock instant of every NDJSON line
(the pod streams one token per line). Lines are parsed after the window, so
that the generator does as little as it can while it measures.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

from benchmark.procs import http_json


def stream_request(port: int, prompt: list[int], max_new_tokens: int,
                   stop_at: float | None = None, timeout: float = 600.0) -> dict:
    """POST a streaming greedy /v1/generate. Returns ``{"sent", "times",
    "lines", "done", "cut", "error"}``: ``times`` are the arrivals of token
    lines, ``cut`` says the client hung up at ``stop_at`` (the window's end),
    ``error`` is set when the request failed."""
    rec = {"sent": time.monotonic(), "times": [], "lines": [], "done": False,
           "cut": False, "error": None, "asked": max_new_tokens}
    body = json.dumps({"tokens": [prompt], "max_new_tokens": max_new_tokens,
                       "stream": True}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"status {resp.status}: {resp.read(300)!r}"
            return rec
        while True:
            line = resp.readline()
            now = time.monotonic()
            if not line:
                break
            if line.startswith(b'{"tokens"'):
                rec["times"].append(now)
                rec["lines"].append(line)
            elif line.startswith(b'{"done"'):
                rec["done"] = True
                break
            elif line.strip():
                rec["error"] = f"stream line: {line[:300]!r}"
                break
            if stop_at is not None and now >= stop_at:
                rec["cut"] = True
                break
        if not (rec["done"] or rec["cut"] or rec["error"]):
            rec["error"] = "stream ended without a done line"
    except (OSError, http.client.HTTPException) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def tokens_of(rec: dict) -> list[int]:
    out: list[int] = []
    for line in rec["lines"]:
        out.extend(json.loads(line)["tokens"][0])
    return out


def run_open(port: int, requests: list[dict], drain_s: float) -> tuple[float, float, list[dict]]:
    """Send each request at its due instant, whatever the server does.
    Returns (window start, window end, records); a record carries ``due``
    (absolute) and ``lag_ms`` (how late it left). Requests still unanswered
    ``drain_s`` after the last due instant count as failed."""
    recs: list[dict | None] = [None] * len(requests)
    threads = []
    t0 = time.monotonic() + 0.05

    def one(i: int, due: float) -> None:
        left = time.monotonic()
        r = stream_request(port, requests[i]["prompt"], requests[i]["max_new_tokens"],
                           timeout=drain_s + 60.0)
        r["due"], r["lag_ms"] = due, (left - due) * 1e3
        recs[i] = r

    for i, req in enumerate(requests):
        due = t0 + req["due_s"]
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=one, args=(i, due), daemon=True)
        th.start()
        threads.append(th)
    t1 = time.monotonic()
    deadline = t1 + drain_s
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    out = []
    for i, r in enumerate(recs):
        if r is None:
            r = {"sent": 0.0, "times": [], "lines": [], "done": False, "cut": False,
                 "error": f"unanswered {drain_s:.0f}s after the window",
                 "asked": requests[i]["max_new_tokens"], "due": t0 + requests[i]["due_s"],
                 "lag_ms": 0.0}
        out.append(r)
    return t0, t1, out


def run_closed(port: int, clients: list[list[dict]], lead_in_s: float, stagger_s: float,
               seconds: float) -> tuple[float, float, list[dict]]:
    """Every client sends its next request when the last one ends. The window
    opens ``lead_in_s`` after the first client starts and lasts ``seconds``;
    at its end the clients hang up."""
    start = time.monotonic() + 0.05
    t0 = start + lead_in_s
    t1 = t0 + seconds
    recs: list[dict] = []
    lock = threading.Lock()

    def client(ci: int) -> None:
        delay = start + ci * stagger_s - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        for req in clients[ci]:
            if time.monotonic() >= t1:
                return
            r = stream_request(port, req["prompt"], req["max_new_tokens"], stop_at=t1)
            with lock:
                recs.append(r)
            if r["error"]:
                time.sleep(0.2)  # a refused client does not spin
        with lock:
            recs.append({"sent": time.monotonic(), "times": [], "lines": [], "done": False,
                         "cut": False, "asked": 0,
                         "error": f"client {ci} ran out of requests before the window ended"})

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(clients))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(lead_in_s + seconds + 120.0)
    return t0, t1, recs


class MetricsPoller:
    """Samples the pod's ``/metrics`` ``device`` block once a second for the
    fullest chip's bytes in use: the pod reports no peak of its own."""

    def __init__(self, port: int) -> None:
        self.port = port
        self.peak = 0
        self._stop = threading.Event()
        self._th = threading.Thread(target=self._loop, daemon=True)

    @staticmethod
    def fullest(device: dict) -> int:
        per = [v.get("hbm_bytes_in_use", 0) for v in (device.get("devices") or {}).values()]
        return int(max(per)) if per else int(device.get("hbm_bytes_in_use", 0))

    def sample(self, metrics: dict) -> None:
        self.peak = max(self.peak, self.fullest(metrics.get("device", {})))

    def _loop(self) -> None:
        while not self._stop.wait(1.0):
            try:
                _, m = http_json(self.port, "GET", "/metrics", timeout=5.0)
                self.sample(m)
            except OSError:
                pass

    def __enter__(self):
        self._th.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._th.join(10.0)
