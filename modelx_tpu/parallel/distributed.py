"""Multi-host (multi-process) initialization and host-local helpers.

The reference's only "distributed" machinery is HTTP + S3 multipart
(SURVEY.md §2.2); the TPU build's multi-host story is jax.distributed +
GSPMD: every host runs the same program, `jax.distributed.initialize`
wires the hosts into one runtime, meshes span *all* devices, and the
collectives ride ICI within a slice / DCN across slices. The registry side
needs no changes — each host's loader fetches only the byte ranges of the
shards it can address (loader.py plans from
``sharding.addressable_devices_indices_map``), which is exactly the
"each host fetches its bytes once" contract of SURVEY §7.

On GKE/TPU-pod deployments the coordinator/process-count/process-id come
from the environment (jax.distributed autodetects on Cloud TPU); explicit
arguments or MODELX_* env vars cover everything else (e.g. CPU fleets).

A single TPU host also announces itself with pod variables
(``TPU_WORKER_HOSTNAMES=localhost`` on a one-host v5e — observed on the
chip, PR 21): that is one process, and ``initialize`` leaves it alone. A
run that IS configured for several processes and cannot reach its
coordinator raises — a pod that quietly came up single-process would serve
a fraction of its mesh.
"""

from __future__ import annotations

import logging
import os

import jax

logger = logging.getLogger("modelx.distributed")

_initialized = False


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Idempotent `jax.distributed.initialize` with env fallbacks.

    Resolution order per argument: explicit > MODELX_COORDINATOR /
    MODELX_NUM_PROCESSES / MODELX_PROCESS_ID env > jax autodetection
    (Cloud TPU pods need no configuration at all). Single-process runs
    (nothing configured, no multi-host TPU pod env) are a no-op; a
    configured run whose ``jax.distributed.initialize`` fails raises.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("MODELX_COORDINATOR")
    if num_processes is None and os.environ.get("MODELX_NUM_PROCESSES"):
        num_processes = int(os.environ["MODELX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("MODELX_PROCESS_ID"):
        process_id = int(os.environ["MODELX_PROCESS_ID"])

    if coordinator_address is None and num_processes is None and not _on_tpu_pod():
        logger.debug("single-process run; skipping jax.distributed")
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    logger.info(
        "distributed: process %d/%d, %d local of %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )


def _on_tpu_pod() -> bool:
    """A MULTI-host Cloud TPU environment (jax autodetects the rest there):
    more than one worker hostname, or a multislice coordinator."""
    workers = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h.strip()]
    return len(workers) > 1 or bool(os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))


def process_span() -> tuple[int, int]:
    """(process_index, process_count).

    Calls :func:`initialize` first (idempotent, no-op when single-process):
    querying jax.process_count() before distributed init would silently boot
    a single-process backend and break the later initialize on a pod.
    """
    initialize()
    return jax.process_index(), jax.process_count()


def host_local_slice(total: int) -> tuple[int, int]:
    """Even [start, stop) split of ``total`` items for this process — the
    pattern for sharding host-side work (e.g. which files of a multi-file
    checkpoint this host reads) before device shardings take over."""
    idx, count = process_span()
    per = (total + count - 1) // count
    start = min(idx * per, total)
    return start, min(start + per, total)
