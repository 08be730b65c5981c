"""Measured device memory telemetry (ISSUE 15).

The lifecycle manager's ``--hbm-budget-bytes`` admits models against
FILE-SIZE estimates (safetensors bytes at reservation, tightened to
loaded bytes at READY) — never against what the device actually holds.
ServerlessLLM's argument (PAPERS.md) applies: memory state must be
*accounted*, not estimated, before a scheduler can trust it. This module
samples the accelerator's own accounting — ``Device.memory_stats()``
where the backend provides it (TPU/GPU), the live-buffer census as the
fallback (CPU backend) — into one small dict the engine snapshot,
``pool_snapshot()``, and ``/admin/models`` all share.

jax is imported lazily (the module stays importable in jax-free
contexts), every backend probe degrades gracefully, and the sample says
WHAT it measured (``platform`` / ``device_kind`` as jax reports them — a
pod that fell back to the CPU must not look like a healthy TPU pod) and
HOW (``source`` = ``memory_stats`` | ``live_buffers`` | ``none``) so a
reader never mistakes a fallback census for device truth.

Sampling is cached for ``max_age_s`` (default 1 s): ``/metrics`` is
polled per scrape and ``live_buffers`` walks every allocation — the
cache keeps telemetry off the request path's critical section.
"""

from __future__ import annotations

import logging
import threading
import time

__all__ = ["sample", "raw_sample"]

logger = logging.getLogger("modelx.devmem")

_cache_lock = threading.Lock()
_cached: dict | None = None
_cached_t = 0.0


def _device_stats(dev) -> dict | None:
    """One device's accountant-reported stats, or None when the backend
    has no accountant (CPU) or the probe fails."""
    ms = getattr(dev, "memory_stats", None)
    if ms is None:
        return None
    try:
        stats = ms()
    except Exception:  # backend-dependent: NotImplementedError, RuntimeError
        logger.debug("memory_stats() failed on %s", dev, exc_info=True)
        return None
    if not stats:
        return None
    in_use = int(stats.get("bytes_in_use", 0))
    limit = int(stats.get("bytes_limit",
                          stats.get("bytes_reservable_limit", 0)))
    out = {
        "hbm_bytes_in_use": in_use,
        "hbm_bytes_limit": limit,
        "hbm_bytes_reservable": max(0, limit - in_use),
    }
    if "peak_bytes_in_use" in stats:
        # the accountant's own high-water mark since the process started:
        # sampling bytes_in_use misses a program's temporaries
        out["hbm_peak_bytes"] = int(stats["peak_bytes_in_use"])
    return out


def _live_buffer_bytes(jax_mod) -> int | None:
    """Fallback census: sum the bytes of every live jax array."""
    try:
        return sum(int(a.nbytes) for a in jax_mod.live_arrays())
    except Exception:
        logger.debug("live-buffer census failed", exc_info=True)
        return None


def raw_sample() -> dict:
    """One uncached sample across local devices. Keys are numeric (they
    render as promexp gauges) except ``source``, ``platform`` and
    ``device_kind``, which the renderer skips and the JSON keeps."""
    out = {
        "hbm_bytes_in_use": 0,
        "hbm_bytes_reservable": 0,
        "device_count": 0,
        "source": "none",
        "platform": "",
        "device_kind": "",
    }
    try:
        import jax
    except Exception:  # jax-free context (registry tooling, docs builds)
        logger.debug("jax unavailable for device telemetry", exc_info=True)
        return out
    try:
        devices = jax.local_devices()
    except Exception:
        logger.debug("jax.local_devices() failed", exc_info=True)
        return out
    out["device_count"] = len(devices)
    if devices:
        out["platform"] = str(devices[0].platform)
        out["device_kind"] = str(devices[0].device_kind)
    per = [_device_stats(d) for d in devices]
    if any(p is not None for p in per):
        out["source"] = "memory_stats"
        # per-device breakdown (keyed by local device index as a string):
        # on a sharded mesh the AGGREGATE hides exactly the failure that
        # matters — one device's HBM filling while its peers idle — so the
        # accountant's per-device truth rides along. promexp renders the
        # dict as one gauge per device via a ``device`` label; the JSON
        # surfaces keep it nested.
        out["devices"] = {}
        for i, p in enumerate(per):
            if p is None:
                continue
            out["hbm_bytes_in_use"] += p["hbm_bytes_in_use"]
            out["hbm_bytes_reservable"] += p["hbm_bytes_reservable"]
            out["devices"][str(i)] = {
                "hbm_bytes_in_use": p["hbm_bytes_in_use"],
                "hbm_bytes_reservable": p["hbm_bytes_reservable"],
            }
            if "hbm_peak_bytes" in p:  # the fullest device, not a sum
                out["hbm_peak_bytes"] = max(out.get("hbm_peak_bytes", 0),
                                            p["hbm_peak_bytes"])
        return out
    census = _live_buffer_bytes(jax)
    if census is not None:
        out["source"] = "live_buffers"
        out["hbm_bytes_in_use"] = census
    return out


def sample(max_age_s: float = 1.0) -> dict:
    """The cached sample every surface shares. A copy is returned —
    callers merge it into snapshot trees they then mutate."""
    global _cached, _cached_t
    now = time.monotonic()
    with _cache_lock:
        if _cached is not None and now - _cached_t < max_age_s:
            return _copy(_cached)
    fresh = raw_sample()  # outside the lock: live_buffers can be slow
    with _cache_lock:
        _cached, _cached_t = fresh, time.monotonic()
        return _copy(fresh)


def _copy(sample_dict: dict) -> dict:
    """Copy deep enough that a caller mutating the nested per-device dicts
    cannot corrupt the shared cache entry."""
    out = dict(sample_dict)
    if isinstance(out.get("devices"), dict):
        out["devices"] = {k: dict(v) for k, v in out["devices"].items()}
    return out
