"""Deploy-time storage initializer: the modelxdl-equivalent.

Reference parity: cmd/modelxdl/modelxdl.go:30-98 (Seldon storage-initializer
contract: ``modelxdl <uri> <dest>``): pull (a subset of) a model version into
a pod volume. The ``modelFiles`` filter bug (modelxdl.go:83 used
``filepath.SplitList`` which splits on ``:`` — nested paths never matched) is
fixed with real path-prefix matching.

TPU-native extension (the north star): ``device_put=True`` continues past the
volume — safetensors blobs stream straight onto the local device mesh via
ranged reads, and the function reports GB/s into HBM.
"""

from __future__ import annotations

import logging
import os
import time

from modelx_tpu.client.model_config import ModelConfig
from modelx_tpu.client.pull import Puller
from modelx_tpu.client.reference import parse_reference
from modelx_tpu.types import (
    AnnotationShardSpec,
    AnnotationTensorIndex,
    BlobLocationPurposeDownload,
    Manifest,
    MediaTypeModelKVCache,
    MediaTypeModelProgram,
)

logger = logging.getLogger("modelx.dl")


def filter_blobs(manifest: Manifest, model_files: list[str]) -> Manifest:
    """Keep only blobs selected by modelFiles (modelxdl.go:74-90, fixed).

    A modelFiles entry matches a blob when the blob is the entry itself or
    the entry's first path element (nested files live inside dir blobs).
    Program bundles always ride along: modelFiles names weight/tokenizer
    files, and silently filtering the compiled programs out would make a
    selective pull boot cold for no reason.
    """
    if not model_files:
        return manifest
    wanted: set[str] = set()
    for entry in model_files:
        entry = entry.strip("/")
        if entry:
            wanted.add(entry)
            wanted.add(entry.split("/", 1)[0])  # top-level dir blob
    blobs = [
        b for b in manifest.blobs
        if b.name in wanted
        or b.media_type in (MediaTypeModelProgram, MediaTypeModelKVCache)
    ]
    return Manifest(
        schema_version=manifest.schema_version,
        media_type=manifest.media_type,
        config=manifest.config,
        blobs=blobs,
        annotations=manifest.annotations,
    )


def _resolve_selected(uri: str, quiet: bool):
    """Shared ref-resolution step for the boot-time initializer AND the
    runtime pull path (both must agree on config handling and blob
    filtering): parse the reference, fetch the manifest + modelx.yaml
    sidecar, apply the ``modelFiles`` filter. Returns (ref, client,
    config, selected manifest)."""
    from modelx_tpu.utils import trace

    from modelx_tpu import errors
    from modelx_tpu.utils.retry import retriable_status

    ref = parse_reference(uri)
    client = ref.client(quiet=quiet)
    with trace.span("dl.manifest", uri=uri):
        manifest = client.get_manifest(ref.repository, ref.version)
        config = ModelConfig()
        if manifest.config.digest:
            try:
                raw = client.get_config_content(ref.repository, ref.version)
            except errors.ErrorInfo as e:
                # registry down AND no cached copy of the yaml: the config
                # only drives the modelFiles filter / mesh default, so a
                # degraded resolve pulls everything rather than failing a
                # boot the blob ladder could still serve (PR 19)
                if not retriable_status(e.http_status):
                    raise
                logger.warning(
                    "modelx.yaml for %s unavailable offline; pulling everything", uri)
                raw = b""
            if raw:
                try:
                    config = ModelConfig.from_yaml(raw)
                except ValueError:
                    logger.warning("invalid modelx.yaml in %s; pulling everything", uri)
    return ref, client, config, filter_blobs(manifest, config.model_files)


def run_initializer(
    uri: str,
    dest: str,
    device_put: bool = False,
    mesh_spec: str = "",
    quiet: bool = False,
    blob_cache_dir: str = "",
    blob_cache_max_bytes: int = 0,
) -> dict:
    """modelxdl.go:50-98 Run. Returns a summary dict (timings, GB/s).

    ``blob_cache_dir`` enables the content-addressed local blob cache
    (dl/blob_cache.py) for the ``device_put`` load path: cold loads tee
    their fetched ranges to disk, warm re-deploys of a blob the node has
    already served skip the network entirely."""
    from modelx_tpu.utils import trace

    cache = None
    if device_put:
        from modelx_tpu.dl import blob_cache as bc

        cache = (
            bc.BlobCache(blob_cache_dir, max_bytes=blob_cache_max_bytes)
            if blob_cache_dir else bc.default_cache()
        )

    t0 = time.monotonic()
    ref, client, config, selected = _resolve_selected(uri, quiet)
    with trace.span("dl.pull", blobs=len(selected.blobs)):
        Puller(client.remote, quiet=quiet).pull_blobs(ref.repository, selected, dest)
    pull_seconds = time.monotonic() - t0
    summary: dict = {
        "uri": uri,
        "dest": dest,
        "blobs": len(selected.blobs),
        "bytes": sum(b.size for b in selected.blobs),
        "pull_seconds": round(pull_seconds, 3),
    }
    if device_put:
        summary["load"] = load_to_mesh(
            client, ref.repository, selected, mesh_spec or config.serving.mesh,
            quiet=quiet, cache=cache,
        )
        if cache is not None:
            summary["blob_cache"] = dict(cache.stats)
    summary["total_seconds"] = round(time.monotonic() - t0, 3)
    return summary


def pull_model(uri: str, dest: str, cache=None, quiet: bool = True) -> dict:
    """Pull a registry ref into ``dest`` THROUGH the local blob cache —
    the runtime model-load path (dl/lifecycle.py admin loads).

    Same manifest/filter flow as ``run_initializer``, but file blobs the
    node's blob cache already holds are COPIED from it (zero network
    reads; the Puller's hash-skip then confirms them up-to-date), and
    freshly pulled blobs are admitted for the next swap — a model the
    node served before reloads blob-cache-warm (``ttft_swap_warm_ms``
    in bench.py's swap leg).

    Degradation ladder (PR 19): when the manifest came off the pinned
    cache because every registry endpoint is down (``last_source ==
    "cache"``), the pull runs fully OFFLINE — every weight/tokenizer blob
    must come digest-verified out of the blob cache, program bundles are
    skipped (a cold compile beats a failed load), and a blob the node
    doesn't hold raises :class:`~modelx_tpu.dl.manifest_cache.
    OfflineUnavailableError` for the lifecycle's retryable-507 contract."""
    from modelx_tpu.dl import blob_cache as bc
    from modelx_tpu.dl.manifest_cache import OfflineUnavailableError
    from modelx_tpu.types import MediaTypeModelDirectoryTarGz
    from modelx_tpu.utils import trace

    if cache is None:
        cache = bc.default_cache()
    t0 = time.monotonic()
    ref, client, _config, selected = _resolve_selected(uri, quiet)
    # where the manifest came from: "registry" | "mirror" | "cache" —
    # "cache" means every endpoint was down and this pull must be offline
    source = getattr(client.remote, "last_source", "registry")
    offline = source == "cache"
    os.makedirs(dest, exist_ok=True)
    file_blobs = [
        b for b in selected.blobs
        if b.digest and b.media_type != MediaTypeModelDirectoryTarGz
    ]
    cache_hits = 0
    offline_skipped_programs = 0
    if cache is not None:
        import shutil as _shutil

        for blob in file_blobs:
            hit = cache.lookup(blob.digest, expected_size=blob.size or -1)
            if hit is None:
                if offline:
                    if blob.media_type in (MediaTypeModelProgram,
                                           MediaTypeModelKVCache):
                        # no derived bundle on hand: boot cold, don't fail
                        offline_skipped_programs += 1
                        continue
                    raise OfflineUnavailableError(
                        f"registry unreachable and blob {blob.name!r} "
                        f"({blob.digest}) is not in the local blob cache")
                continue
            target = os.path.join(dest, blob.name)
            os.makedirs(os.path.dirname(target) or ".", exist_ok=True)
            try:
                _shutil.copyfile(hit, target)
                os.chmod(target, blob.mode or 0o644)
                cache_hits += 1
            except OSError:
                if offline:
                    raise OfflineUnavailableError(
                        f"registry unreachable and cached blob {blob.name!r} "
                        "vanished mid-copy (concurrent eviction)")
                # a racing LRU eviction unlinked the entry: the Puller
                # fetches it over the network like any miss
                pass
    if offline:
        missing_dirs = [
            b.name for b in selected.blobs
            if b.digest and b.media_type == MediaTypeModelDirectoryTarGz
        ]
        if missing_dirs:
            raise OfflineUnavailableError(
                "registry unreachable and directory blobs cannot be "
                f"materialized from the blob cache: {missing_dirs}")
        if cache is None:
            raise OfflineUnavailableError(
                "registry unreachable and no local blob cache is configured")
        logger.warning("registry unreachable; %s materialized offline from "
                       "the pinned manifest + blob cache (%d blobs)",
                       uri, cache_hits)
    else:
        with trace.span("dl.pull", blobs=len(selected.blobs)):
            Puller(client.remote, quiet=quiet).pull_blobs(ref.repository, selected, dest)
    admitted = 0
    if cache is not None:
        import shutil as _shutil

        import tempfile

        for blob in file_blobs:
            target = os.path.join(dest, blob.name)
            if not os.path.isfile(target):
                continue
            if os.path.isfile(cache.entry_path(blob.digest)):
                continue  # already cached; don't churn the LRU clock
            try:
                # unique spool per admit: concurrent runtime loads in one
                # process must not overwrite each other's in-flight copies
                fd, tmp = tempfile.mkstemp(dir=cache.root, prefix=".pull-admit-")
                os.close(fd)
                _shutil.copyfile(target, tmp)
            except OSError:
                continue
            if cache.admit_file(blob.digest, tmp) is not None:
                admitted += 1
    return {
        "uri": uri,
        "dest": dest,
        "blobs": len(selected.blobs),
        "bytes": sum(b.size for b in selected.blobs),
        "program_blobs": sum(
            1 for b in selected.blobs if b.media_type == MediaTypeModelProgram
        ),
        "kv_blobs": sum(
            1 for b in selected.blobs if b.media_type == MediaTypeModelKVCache
        ),
        "cache_hits": cache_hits,
        "cache_admitted": admitted,
        "source": source,
        "offline_skipped_programs": offline_skipped_programs,
        "pull_seconds": round(time.monotonic() - t0, 3),
    }


def load_to_mesh(client, repository: str, manifest: Manifest, mesh_spec: str,
                 quiet: bool = False, cache=None) -> dict:
    """Stream every safetensors blob of the manifest onto the local mesh.

    Uses the presigned download location when the registry offers one (bytes
    come straight from object storage) and the registry's ranged blob GET
    otherwise.
    """
    import jax

    from modelx_tpu.dl import safetensors as st
    from modelx_tpu.dl.loader import HTTPSource, load_safetensors
    from modelx_tpu.dl.sharding import decode_rules, infer_family, rules_for_family
    from modelx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(mesh_spec) if mesh_spec else make_mesh(f"dp={len(jax.devices())}")
    out: dict = {"mesh": str(dict(mesh.shape)), "tensors": 0, "bytes": 0, "gbps": 0.0}
    total_bytes = 0
    t0 = time.monotonic()
    arrays = {}
    for blob in manifest.blobs:
        if not blob.name.endswith(".safetensors"):
            continue
        tensors = data_offset = None
        if AnnotationTensorIndex in blob.annotations:
            tensors, data_offset = st.parse_index_annotation(blob.annotations[AnnotationTensorIndex])
        if AnnotationShardSpec in blob.annotations:
            rules = decode_rules(blob.annotations[AnnotationShardSpec])
        else:
            names = list(tensors) if tensors else []
            rules = rules_for_family(infer_family(names))
        source = _blob_source(client, repository, blob, cache=cache)
        try:
            loaded, stats = load_safetensors(
                source, mesh, rules, tensors=tensors, data_offset=data_offset
            )
        finally:
            if hasattr(source, "close"):
                source.close()
        arrays.update(loaded)
        out["tensors"] += stats.tensors
        total_bytes += stats.bytes_to_device
    out["bytes"] = total_bytes
    seconds = time.monotonic() - t0
    out["seconds"] = round(seconds, 3)
    out["gbps"] = round(total_bytes / max(seconds, 1e-9) / 1e9, 6)
    out["arrays"] = arrays
    return out


def _blob_source(client, repository: str, blob, cache=None,
                 prefer_local: bool | None = None):
    """Best transport for a blob, tier by tier: a readable ``file``
    location (colocated registry / shared volume) beats everything — local
    preads cost no server round-trips and no network bytes; next the local
    blob cache (dl/blob_cache.py) serves a digest-verified copy with zero
    network reads; finally the remote paths (presigned URL or the direct
    blob endpoint), teed into the cache for the next deploy.

    ``prefer_local=False`` (or env MODELX_DL_NO_LOCAL_REDIRECT=1) skips the
    colocated-file redirect — the bench/test knob that models a remote pod
    against a colocated registry."""
    from modelx_tpu.client.extension import LocationUnreachable, usable_file_path
    from modelx_tpu.dl.loader import HTTPSource, LocalFileSource

    if prefer_local is None:
        prefer_local = os.environ.get("MODELX_DL_NO_LOCAL_REDIRECT", "") not in ("1", "true")
    location = client.remote.get_blob_location(repository, blob, BlobLocationPurposeDownload)
    if prefer_local and location is not None and location.provider == "file":
        try:
            return LocalFileSource(usable_file_path(location, blob.size or -1))
        except LocationUnreachable:
            pass  # advertised for a colocated client; we're not one
    if cache is not None and blob.digest:
        hit = cache.lookup(blob.digest, expected_size=blob.size or -1)
        if hit is not None:
            try:
                src = LocalFileSource(hit)
            except OSError:
                # a concurrent admit's LRU eviction can unlink the entry
                # between lookup and open — fall through to the network
                pass
            else:
                src.cache_state = "warm"
                return src
    if location is not None and location.properties.get("url"):
        src = HTTPSource(location.properties["url"], total=blob.size)
    else:
        headers = {}
        if client.remote.authorization:
            headers["Authorization"] = client.remote.authorization
        url = f"{client.remote.registry}/{repository}/blobs/{blob.digest}"
        src = HTTPSource(url, headers=headers, total=blob.size)
    if cache is not None and blob.digest:
        src = cache.wrap(src, blob.digest, blob.size or 0)
    return src
