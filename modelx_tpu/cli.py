"""The modelx CLI: user commands, the registry daemon, and the deploy puller.

Reference parity — all three binaries in one entrypoint:

- ``modelx`` user CLI (cmd/modelx/model/model.go:15-28): init / login /
  list / info / push / pull, repo management, shell completion (click's
  built-in completion covers bash/zsh/fish; powershell is hand-rolled over
  the hidden ``__complete`` backend, completion.go parity).
- ``modelx serve`` = modelxd (cmd/modelxd/modelxd.go:26-58) with the full
  flag surface (listen / tls / s3 / auth / redirect).
- ``modelx dl`` = modelxdl (cmd/modelxdl/modelxdl.go:30-98), the Seldon-style
  storage initializer: ``modelx dl <uri> <dest>`` — extended with
  ``--device-put`` to load straight into TPU HBM (the north-star path).
- ``modelx serve-model`` = the TPU serving sidecar (``modelx-serve``,
  dl/serve_main.py), passed through lazily so registry commands never pay
  the jax import.

Run as ``python -m modelx_tpu.cli`` or via the ``modelx`` console script.
"""

from __future__ import annotations

import json
import logging
import os
import sys

import click

from modelx_tpu import errors
from modelx_tpu.client.client import Client
from modelx_tpu.client.model_config import MODEL_CONFIG_FILENAME, README_FILENAME, ModelConfig
from modelx_tpu.client.reference import parse_reference
from modelx_tpu.client.repo import RepoDetails, default_repo_manager
from modelx_tpu.utils.units import human_size
from modelx_tpu.version import get as get_version

logger = logging.getLogger("modelx")


@click.group(name="modelx")
@click.option("--debug", is_flag=True, envvar="DEBUG", help="verbose logging (model.go:32-35)")
@click.option("--insecure", is_flag=True,
              help="skip TLS certificate verification (self-signed "
                   "registries; modelx.go:29-36)")
def main(debug: bool, insecure: bool) -> None:
    """modelx — TPU-native model registry CLI."""
    logging.basicConfig(
        level=logging.DEBUG if debug else logging.WARNING,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    if insecure:
        from modelx_tpu.client.remote import set_insecure

        set_insecure(True)


def _fail(e: BaseException) -> None:
    click.secho(f"error: {e}", fg="red", err=True)
    sys.exit(1)


def _complete_ref(ctx, param, incomplete):
    """Dynamic remote completion (cmd/modelx/repo/list.go:42-106): complete
    ``alias/repository[@version]`` by live-querying the registry indexes."""
    try:
        mgr = default_repo_manager()
        if "/" not in incomplete:
            return [r.name + "/" for r in mgr.list() if r.name.startswith(incomplete)]
        alias, _, rest = incomplete.partition("/")
        details = mgr.get(alias)
        if details is None:
            return []
        client = Client(details.url, "Bearer " + details.token if details.token else "", quiet=True)
        client.remote.timeout = 2  # Tab completion must never hang the shell
        if "@" in rest:
            repo, _, ver = rest.partition("@")
            idx = client.get_index(repo)
            return [f"{alias}/{repo}@{m.name}" for m in idx.manifests if m.name.startswith(ver)]
        gidx = client.get_global_index()
        out = []
        for m in gidx.manifests:
            cand = f"{alias}/{m.name}"
            if cand.startswith(incomplete):
                out.append(cand)
        return out
    except Exception:
        return []  # completion must never crash the shell


# -- init ---------------------------------------------------------------------


INIT_README = """# {name}

A model packaged with modelx. Edit `modelx.yaml` to describe the model, then:

    modelx push <repo>/<project>/{name}@<version> .
"""


@main.command("init")
@click.argument("directory", default=".")
def cmd_init(directory: str) -> None:
    """Scaffold modelx.yaml + README.md (init.go:39-104)."""
    os.makedirs(directory, exist_ok=True)
    cfg_path = os.path.join(directory, MODEL_CONFIG_FILENAME)
    if os.path.exists(cfg_path):
        _fail(FileExistsError(f"{cfg_path} already exists"))
    cfg = ModelConfig(
        description="my model description",
        framework="jax",
        task="text-generation",
        tags=["llm"],
        maintainers=["maintainer@example.com"],
        model_files=[],
        # TPU serving hints replace the reference's GPU resource template
        # (init.go:64-76): declare a mesh, not an nvidia.com/gpu count.
        resources={"tpu": {"topology": "v5e-8"}},
    )
    cfg.serving.mesh = "dp=1,tp=8"
    cfg.serving.dtype = "bfloat16"
    with open(cfg_path, "w") as f:
        f.write(cfg.to_yaml())
    readme = os.path.join(directory, README_FILENAME)
    if not os.path.exists(readme):
        with open(readme, "w") as f:
            f.write(INIT_README.format(name=os.path.basename(os.path.abspath(directory))))
    click.echo(f"initialized {cfg_path}")


# -- login --------------------------------------------------------------------


@main.command("login")
@click.argument("registry")
@click.option("--token", prompt=True, hide_input=True, help="bearer token")
@click.option("--name", default="", help="alias name (defaults to host)")
def cmd_login(registry: str, token: str, name: str) -> None:
    """Verify token against the registry, then store it (login.go:51-62)."""
    try:
        Client(registry, "Bearer " + token, quiet=True).ping()
    except errors.ErrorInfo as e:
        _fail(e)
    from urllib.parse import urlparse

    alias = name or urlparse(registry).netloc
    default_repo_manager().set(RepoDetails(name=alias, url=registry.rstrip("/"), token=token))
    click.echo(f"login succeeded; saved as repo alias {alias!r}")


# -- list / info --------------------------------------------------------------


@main.command("list")
@click.argument("ref", shell_complete=_complete_ref)
@click.option("--search", default="", help="regex filter")
def cmd_list(ref: str, search: str) -> None:
    """Three-mode list: repositories / versions / files (list.go:78-163)."""
    try:
        r = parse_reference(ref)
        client = r.client(quiet=True)
        if not r.repository:
            idx = client.get_global_index(search)
            _table(["NAME", "SIZE", "MODIFIED"], [[m.name, human_size(m.size), m.modified] for m in idx.manifests])
        elif not r.version:
            idx = client.get_index(r.repository, search)
            _table(["VERSION", "SIZE", "MODIFIED"], [[m.name, human_size(m.size), m.modified] for m in idx.manifests])
        else:
            m = client.get_manifest(r.repository, r.version)
            rows = [[d.name, d.media_type.rsplit(".", 1)[-1], human_size(d.size), d.digest[:19]] for d in m.all_descriptors()]
            _table(["FILE", "TYPE", "SIZE", "DIGEST"], rows)
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


@main.command("info")
@click.argument("ref", shell_complete=_complete_ref)
def cmd_info(ref: str) -> None:
    """Print a version's config blob, i.e. modelx.yaml (info.go:47-65)."""
    try:
        r = parse_reference(ref)
        content = r.client(quiet=True).get_config_content(r.repository, r.version)
        click.echo(content.decode(errors="replace"))
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


def _table(headers: list[str], rows: list[list[str]]) -> None:
    from rich.console import Console
    from rich.table import Table

    t = Table(show_edge=False, pad_edge=False, box=None)
    for h in headers:
        t.add_column(h)
    for row in rows:
        t.add_row(*[str(c) for c in row])
    Console().print(t)


# -- push / pull --------------------------------------------------------------


@main.command("push")
@click.argument("ref", shell_complete=_complete_ref)
@click.argument("directory", default=".")
def cmd_push(ref: str, directory: str) -> None:
    """Push a model directory (push.go:43-80). Requires modelx.yaml."""
    cfg_path = os.path.join(directory, MODEL_CONFIG_FILENAME)
    if not os.path.isfile(cfg_path):
        _fail(FileNotFoundError(f"{cfg_path} not found — run `modelx init` first"))
    try:
        ModelConfig.load(cfg_path)  # validate before pushing (push.go:61-80)
        r = parse_reference(ref)
        if not r.repository:
            _fail(ValueError("reference must include a repository"))
        r.client().push(r.repository, r.version or "latest", directory)
        click.echo(f"pushed {r}")
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


@main.command("pull")
@click.argument("ref", shell_complete=_complete_ref)
@click.argument("directory", default="")
def cmd_pull(ref: str, directory: str) -> None:
    """Pull a model version into a directory (pull.go:41-69)."""
    try:
        r = parse_reference(ref)
        target = directory or r.repository.rsplit("/", 1)[-1]
        r.client().pull(r.repository, r.version or "latest", target)
        click.echo(f"pulled {r} -> {target}")
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


@main.command("copy")
@click.argument("src", shell_complete=_complete_ref)
@click.argument("dst", shell_complete=_complete_ref)
@click.option("--quiet", is_flag=True, help="suppress per-blob progress lines")
def cmd_copy(src: str, dst: str, quiet: bool) -> None:
    """Copy a model version between registries/repos with content-address
    skip (blobs the destination already holds move zero bytes)."""
    from modelx_tpu.client.ops import copy_model

    try:
        s, d = parse_reference(src), parse_reference(dst)
        if not s.repository or not d.repository:
            raise ValueError("both references must include a repository")
        if not s.version:
            raise ValueError("source reference needs a version (repo@version)")
        out = copy_model(
            s.client().remote, s.repository, s.version,
            d.client().remote, d.repository, d.version or s.version,
            log=(lambda line: None) if quiet else click.echo,
        )
        click.echo(json.dumps(out))
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


@main.command("diff")
@click.argument("a", shell_complete=_complete_ref)
@click.argument("b", shell_complete=_complete_ref)
def cmd_diff(a: str, b: str) -> None:
    """Manifest-level diff of two model versions (no blob bytes move):
    which blobs were added/removed/changed, how many bytes a pull or copy
    would actually transfer, and — when tensor-index annotations are
    present — which tensors changed layout."""
    from modelx_tpu.client.ops import diff_versions

    try:
        ra, rb = parse_reference(a), parse_reference(b)
        if not ra.repository or not rb.repository:
            raise ValueError("both references must include a repository")
        if not ra.version or not rb.version:
            raise ValueError("both references need a version (repo@version)")
        out = diff_versions(
            ra.client().remote, ra.repository, ra.version,
            rb.client().remote, rb.repository, rb.version,
        )
        click.echo(json.dumps(out))
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


@main.command("verify")
@click.argument("ref", shell_complete=_complete_ref)
@click.option("--quiet", is_flag=True, help="suppress per-blob lines")
@click.option("--remote", "remote_", is_flag=True,
              help="verify server-side via the scrub route (no pull): the "
                   "registry re-hashes its own blobs and quarantines "
                   "corruption in place; repository-wide, so no @version")
def cmd_verify(ref: str, quiet: bool, remote_: bool) -> None:
    """Registry fsck: re-hash every blob the repo's manifests reference
    (all versions, or just one with repo@version); exit 1 on any mismatch.
    With --remote the audit runs where the bytes live instead of streaming
    them down first — note it covers the whole repository and MOVES corrupt
    blobs to quarantine (they 404 until re-pushed)."""
    from modelx_tpu.client.ops import verify_repo

    try:
        r = parse_reference(ref)
        if not r.repository:
            raise ValueError("reference must include a repository")
        if remote_:
            if r.version:
                raise ValueError(
                    "--remote scrubs the whole repository; drop the @version "
                    "(or verify that version locally without --remote)"
                )
            remote = r.client(quiet=True).remote
            out = remote.scrub(r.repository)
            # the scrub result is blob-level; count the compiled-program
            # descriptors client-side so the audit reports how many of the
            # verified blobs are program bundles
            from modelx_tpu.types import MediaTypeModelProgram

            count = 0
            for m in remote.get_index(r.repository).manifests:
                manifest = remote.get_manifest(r.repository, m.name)
                count += sum(
                    1 for b in manifest.blobs
                    if b.media_type == MediaTypeModelProgram
                )
            out["program_blobs"] = count
            click.echo(json.dumps(out))
            if not out.get("clean", False):
                sys.exit(1)
            return
        out = verify_repo(
            r.client().remote, r.repository, r.version,
            log=(lambda line: None) if quiet else click.echo,
        )
        click.echo(json.dumps(out))
        if out["errors"]:
            sys.exit(1)
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


@main.command("scrub")
@click.argument("ref", shell_complete=_complete_ref)
@click.option("--sample", type=int, default=0,
              help="re-hash only N blobs, drawn deterministically from "
                   "--seed (0 = scrub everything)")
@click.option("--seed", type=int, default=0, help="sample seed")
def cmd_scrub(ref: str, sample: int, seed: int) -> None:
    """Server-side integrity scrub of a repository: the registry re-hashes
    stored blobs, moves corrupt ones to quarantine/ (the digest 404s and
    becomes re-pushable), reports dangling manifest references, and
    rebuilds its indexes. Exit 1 when anything was found."""
    try:
        r = parse_reference(ref)
        if not r.repository:
            raise ValueError("reference must include a repository")
        out = r.client(quiet=True).remote.scrub(r.repository, sample=sample, seed=seed)
        click.echo(json.dumps(out))
        if not out.get("clean", False):
            sys.exit(1)
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


# -- repo management (cmd/modelx/repo) ---------------------------------------


@main.group("repo")
def cmd_repo() -> None:
    """Repository alias management (~/.modelx/repos.json)."""


@cmd_repo.command("add")
@click.argument("name")
@click.argument("url")
@click.option("--token", default="")
def cmd_repo_add(name: str, url: str, token: str) -> None:
    try:
        default_repo_manager().set(RepoDetails(name=name, url=url, token=token))
        click.echo(f"added repo {name} -> {url}")
    except ValueError as e:
        _fail(e)


@cmd_repo.command("list")
def cmd_repo_list() -> None:
    rows = [[r.name, r.url, "yes" if r.token else ""] for r in default_repo_manager().list()]
    _table(["NAME", "URL", "TOKEN"], rows)


@cmd_repo.command("remove")
@click.argument("name")
def cmd_repo_remove(name: str) -> None:
    if default_repo_manager().remove(name):
        click.echo(f"removed repo {name}")
    else:
        _fail(KeyError(f"no such repo alias: {name}"))


# -- gc -----------------------------------------------------------------------


@main.command("gc")
@click.argument("ref", shell_complete=_complete_ref)
@click.option(
    "--grace",
    type=float,
    default=None,
    help="Skip blobs younger than this many seconds (default: server's "
    "configured window; 0 sweeps immediately and may race in-flight pushes).",
)
def cmd_gc(ref: str, grace: float | None) -> None:
    """Trigger server-side garbage collection for a repository."""
    try:
        r = parse_reference(ref)
        result = r.client(quiet=True).remote.garbage_collect(r.repository, grace_s=grace)
        click.echo(json.dumps(result))
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


# -- programs (compiled-program bundles, dl/program_store.py) -----------------


@main.group("programs")
def cmd_programs() -> None:
    """Compiled-program bundles: AOT executables shipped with the model."""


@cmd_programs.command("list")
@click.argument("ref", shell_complete=_complete_ref)
def cmd_programs_list(ref: str) -> None:
    """List the program bundles attached to a version (or, without
    @version, to every version of the repository)."""
    from modelx_tpu.types import (
        AnnotationProgramBackend,
        AnnotationProgramCode,
        AnnotationProgramCount,
        AnnotationProgramJax,
        MediaTypeModelProgram,
    )

    try:
        r = parse_reference(ref)
        if not r.repository:
            raise ValueError("reference must include a repository")
        remote = r.client(quiet=True).remote
        versions = [r.version] if r.version else [
            m.name for m in remote.get_index(r.repository).manifests
        ]
        rows = []
        for ver in versions:
            manifest = remote.get_manifest(r.repository, ver)
            for b in manifest.blobs:
                if b.media_type != MediaTypeModelProgram:
                    continue
                rows.append([
                    ver, b.name,
                    b.annotations.get(AnnotationProgramCount, "?"),
                    b.annotations.get(AnnotationProgramJax, "?"),
                    b.annotations.get(AnnotationProgramBackend, "?"),
                    b.annotations.get(AnnotationProgramCode, "?"),
                    human_size(b.size),
                ])
        _table(["VERSION", "BUNDLE", "PROGRAMS", "JAX", "BACKEND", "CODE", "SIZE"], rows)
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


@cmd_programs.command("push")
@click.argument("ref", shell_complete=_complete_ref)
@click.option("--quantize", type=click.Choice(["int8"]), default=None,
              help="export the surface for int8 weight-only deploys "
                   "(the program shapes differ from bf16)")
@click.option("--cache-dir", default="",
              help="AOT cache dir to export into and bundle from (default: "
                   "an emptied .cache/xla/programs-push under the checkout, "
                   "so the bundle holds this surface and nothing else)")
def cmd_programs_push(ref: str, quantize: str | None, cache_dir: str) -> None:
    """Export a model version's compiled surface and attach it as a
    program bundle. Works from the manifest's tensor index alone — no
    weight bytes are pulled; the next pod's pull then boots
    compile-warm."""
    try:
        r = parse_reference(ref)
        if not r.repository or not r.version:
            raise ValueError("programs push needs repo@version "
                             "(bundles pin the exact version they compile for)")
        from modelx_tpu.dl import program_store
        from modelx_tpu.dl.serve import cold_cache_dir, enable_compile_cache

        client = r.client(quiet=True)
        manifest = client.get_manifest(r.repository, r.version)
        out_dir = cache_dir or cold_cache_dir("programs-push")
        enable_compile_cache(out_dir)
        family, cfg, sds, mesh = program_store.plan_from_manifest(
            client, r.repository, manifest, quantize=quantize
        )
        keys = program_store.export_surface(family, cfg, sds, mesh, out_dir)
        data = program_store.build_bundle(out_dir, keys=keys, mesh=mesh)
        if data is None:
            raise ValueError("no programs exported; nothing to push")
        desc = program_store.publish(client.remote, r.repository, r.version, data)
        click.echo(json.dumps({
            "name": desc.name, "digest": str(desc.digest), "size": desc.size,
            "programs": len(keys), "family": family.name,
        }))
    except (errors.ErrorInfo, ValueError, OSError) as e:
        _fail(e)


@cmd_programs.command("prune")
@click.argument("ref", shell_complete=_complete_ref)
def cmd_programs_prune(ref: str) -> None:
    """Detach program bundles from a version (or every version without
    @version). The blobs become unreferenced — the next gc sweep collects
    them; weights and tokenizer files are untouched."""
    from modelx_tpu.types import MediaTypeModelProgram

    try:
        r = parse_reference(ref)
        if not r.repository:
            raise ValueError("reference must include a repository")
        remote = r.client(quiet=True).remote
        versions = [r.version] if r.version else [
            m.name for m in remote.get_index(r.repository).manifests
        ]
        removed = 0
        for ver in versions:
            manifest = remote.get_manifest(r.repository, ver)
            keep = [b for b in manifest.blobs
                    if b.media_type != MediaTypeModelProgram]
            if len(keep) == len(manifest.blobs):
                continue
            removed += len(manifest.blobs) - len(keep)
            manifest.blobs = keep
            remote.put_manifest(r.repository, ver, manifest)
        click.echo(json.dumps({"removed": removed, "versions": len(versions)}))
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


# -- kv (prefix-KV bundles, dl/kv_store.py) -----------------------------------


@main.group("kv")
def cmd_kv() -> None:
    """Prefix-KV bundles: serialized prefill caches shipped with the model."""


@cmd_kv.command("list")
@click.argument("ref", shell_complete=_complete_ref)
def cmd_kv_list(ref: str) -> None:
    """List the prefix-KV bundles attached to a version (or, without
    @version, to every version of the repository)."""
    from modelx_tpu.types import (
        AnnotationKVCode,
        AnnotationKVModel,
        AnnotationKVPrefix,
        AnnotationKVTokens,
        MediaTypeModelKVCache,
    )

    try:
        r = parse_reference(ref)
        if not r.repository:
            raise ValueError("reference must include a repository")
        remote = r.client(quiet=True).remote
        versions = [r.version] if r.version else [
            m.name for m in remote.get_index(r.repository).manifests
        ]
        rows = []
        for ver in versions:
            manifest = remote.get_manifest(r.repository, ver)
            for b in manifest.blobs:
                if b.media_type != MediaTypeModelKVCache:
                    continue
                rows.append([
                    ver, b.name,
                    b.annotations.get(AnnotationKVTokens, "?"),
                    b.annotations.get(AnnotationKVPrefix, "?"),
                    b.annotations.get(AnnotationKVModel, "?"),
                    b.annotations.get(AnnotationKVCode, "?"),
                    human_size(b.size),
                ])
        _table(["VERSION", "BUNDLE", "TOKENS", "PREFIX", "MODEL", "CODE", "SIZE"], rows)
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


@cmd_kv.command("push")
@click.argument("ref", shell_complete=_complete_ref)
@click.argument("bundle", type=click.Path(exists=True, dir_okay=False))
def cmd_kv_push(ref: str, bundle: str) -> None:
    """Attach a pre-built prefix-KV bundle (a ``.kv-*.tar`` a pod wrote,
    or one salvaged from a model dir) to a version. Pods publish their
    own hot entries through the outbox; this is the manual escape hatch —
    the bundle's stamped environment decides its name, so re-pushing the
    same bytes is an idempotent no-op."""
    from modelx_tpu.dl import kv_store

    try:
        r = parse_reference(ref)
        if not r.repository or not r.version:
            raise ValueError("kv push needs repo@version "
                             "(bundles pin the exact version they cache for)")
        with open(bundle, "rb") as f:
            data = f.read()
        meta = kv_store._bundle_meta(data)
        if meta is None:
            raise ValueError(f"{bundle} is not a kv bundle (bad tar/meta)")
        client = r.client(quiet=True)
        desc = kv_store.publish(client.remote, r.repository, r.version, data)
        click.echo(json.dumps({
            "name": desc.name, "digest": str(desc.digest), "size": desc.size,
            "tokens": meta.get("tokens") and len(meta["tokens"]),
        }))
    except (errors.ErrorInfo, ValueError, OSError) as e:
        _fail(e)


@cmd_kv.command("prune")
@click.argument("ref", shell_complete=_complete_ref)
def cmd_kv_prune(ref: str) -> None:
    """Detach prefix-KV bundles from a version (or every version without
    @version). The blobs become unreferenced — the next gc sweep collects
    them; weights, tokenizer files and program bundles are untouched."""
    from modelx_tpu.types import MediaTypeModelKVCache

    try:
        r = parse_reference(ref)
        if not r.repository:
            raise ValueError("reference must include a repository")
        remote = r.client(quiet=True).remote
        versions = [r.version] if r.version else [
            m.name for m in remote.get_index(r.repository).manifests
        ]
        removed = 0
        for ver in versions:
            manifest = remote.get_manifest(r.repository, ver)
            keep = [b for b in manifest.blobs
                    if b.media_type != MediaTypeModelKVCache]
            if len(keep) == len(manifest.blobs):
                continue
            removed += len(manifest.blobs) - len(keep)
            manifest.blobs = keep
            remote.put_manifest(r.repository, ver, manifest)
        click.echo(json.dumps({"removed": removed, "versions": len(versions)}))
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


# -- serve (modelxd) ----------------------------------------------------------


@main.command("serve")
@click.option("--listen", default=":8080", help="listen address")
@click.option("--data", "data_dir", default="data/registry", help="local FS store path")
@click.option("--tls-cert", default="")
@click.option("--tls-key", default="")
@click.option("--s3-url", default="", help="S3 endpoint; presence selects the S3 store")
@click.option("--s3-access-key", default="", envvar="S3_ACCESS_KEY")
@click.option("--s3-secret-key", default="", envvar="S3_SECRET_KEY")
@click.option("--s3-bucket", default="registry")
@click.option("--s3-region", default="us-east-1")
@click.option("--gcs-url", default="",
              help="GCS endpoint (e.g. https://storage.googleapis.com); "
                   "presence selects the GCS store (HMAC keys)")
@click.option("--gcs-access-key", default="", envvar="GCS_ACCESS_KEY")
@click.option("--gcs-secret-key", default="", envvar="GCS_SECRET_KEY")
@click.option("--gcs-bucket", default="registry")
@click.option("--enable-redirect", is_flag=True, help="presigned load separation")
@click.option("--local-redirect/--no-local-redirect", default=True,
              help="FS store: redirect colocated clients to blob paths")
@click.option("--auth-token", multiple=True, help="accepted bearer token (repeatable)")
@click.option("--oidc-issuer", default="", help="OIDC issuer URL for JWT bearer auth")
@click.option("--gc-interval", default=0.0, type=float, help="seconds between GC sweeps (0=off)")
@click.option("--reconcile-on-start/--no-reconcile-on-start", default=True,
              help="rebuild repo + global indexes from storage at boot "
                   "(crash recovery; index-only — deep audits via scrub)")
def cmd_serve(
    listen, data_dir, tls_cert, tls_key, s3_url, s3_access_key, s3_secret_key,
    s3_bucket, s3_region, gcs_url, gcs_access_key, gcs_secret_key, gcs_bucket,
    enable_redirect, local_redirect, auth_token, oidc_issuer,
    gc_interval, reconcile_on_start,
) -> None:
    """Run the registry daemon (cmd/modelxd/modelxd.go:26-58)."""
    from modelx_tpu.registry.server import Options, RegistryServer

    logging.getLogger("modelx.registry").setLevel(logging.INFO)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    opts = Options(
        listen=listen,
        data_dir=data_dir,
        tls_cert=tls_cert,
        tls_key=tls_key,
        s3_url=s3_url,
        s3_access_key=s3_access_key,
        s3_secret_key=s3_secret_key,
        s3_bucket=s3_bucket,
        s3_region=s3_region,
        gcs_url=gcs_url,
        gcs_access_key=gcs_access_key,
        gcs_secret_key=gcs_secret_key,
        gcs_bucket=gcs_bucket,
        enable_redirect=enable_redirect,
        local_redirect=local_redirect,
        auth_tokens=tuple(auth_token),
        oidc_issuer=oidc_issuer,
        gc_interval_s=gc_interval,
        reconcile_on_start=reconcile_on_start,
    )
    RegistryServer(opts).serve_forever()


# -- serve-model (the TPU serving sidecar, modelx-serve) ----------------------


@main.command(
    "serve-model",
    context_settings={"ignore_unknown_options": True, "help_option_names": []},
)
@click.argument("args", nargs=-1, type=click.UNPROCESSED)
def cmd_serve_model(args: tuple[str, ...]) -> None:
    """Run the model-serving sidecar (same as the ``modelx-serve``
    console script): loads checkpoints onto the mesh and serves
    /v1/generate + OpenAI-compatible endpoints, with the full serving
    flag surface (--continuous-batch, --prefill-chunk/--prefill-budget
    chunked prefill, --kv-page-size paged KV, --max-queue-depth /
    --request-timeout bounded admission with deadlines, ...). Args pass
    through verbatim; the import is deferred so plain registry commands
    never pay the jax startup."""
    from modelx_tpu.dl.serve_main import main as serve_model_main

    serve_model_main.main(args=list(args), prog_name="modelx serve-model")


# -- route (the fleet front door, modelx-route) -------------------------------


@main.command(
    "route",
    context_settings={"ignore_unknown_options": True, "help_option_names": []},
)
@click.argument("args", nargs=-1, type=click.UNPROCESSED)
def cmd_route(args: tuple[str, ...]) -> None:
    """Run the fleet router (same as the ``modelx-route`` console
    script): a prefix-sticky, lifecycle-aware HTTP front door over many
    ``modelx serve-model`` pods — same native + OpenAI surface, failover
    on 429/503/connection errors, optional --allow-rebalance lifecycle
    spreading (docs/router.md). Args pass through verbatim; the router
    imports no jax, so this stays registry-command cheap."""
    from modelx_tpu.router.router_main import main as route_main

    route_main.main(args=list(args), prog_name="modelx route")


# -- dl (modelxdl, deploy-time puller) ----------------------------------------


@main.command("dl")
@click.argument("uri")
@click.argument("dest")
@click.option("--device-put", is_flag=True, help="after pulling, load safetensors onto the local TPU mesh and report timings")
@click.option("--mesh", default="", help='mesh override, e.g. "dp=1,tp=8"')
@click.option("--blob-cache-dir", default="",
              help="content-addressed local blob cache for the --device-put "
                   "load: cold loads tee to disk, warm re-deploys of an "
                   "already-served checkpoint skip the network")
@click.option("--blob-cache-max-bytes", default=0, type=int,
              help="blob cache size cap; LRU eviction (0 = unbounded)")
def cmd_dl(uri: str, dest: str, device_put: bool, mesh: str,
           blob_cache_dir: str, blob_cache_max_bytes: int) -> None:
    """Deploy-time puller (cmd/modelxdl/modelxdl.go:30-98): pull (a subset of)
    a model into DEST. With --device-put, continue into TPU HBM."""
    try:
        from modelx_tpu.dl.initializer import run_initializer

        if device_put:
            from modelx_tpu.parallel.distributed import initialize

            initialize()  # no-op single-process; wires multi-host TPU pods
        summary = run_initializer(
            uri, dest, device_put=device_put, mesh_spec=mesh,
            blob_cache_dir=blob_cache_dir,
            blob_cache_max_bytes=blob_cache_max_bytes,
        )
        if "load" in summary:
            summary["load"] = {k: v for k, v in summary["load"].items() if k != "arrays"}
        click.echo(json.dumps(summary))
    except (errors.ErrorInfo, ValueError) as e:
        _fail(e)


# -- convert ------------------------------------------------------------------


@main.group("convert")
def cmd_convert() -> None:
    """Convert foreign checkpoints to a pushable safetensors dir."""


@cmd_convert.command("orbax")
@click.argument("src")
@click.argument("dst_dir")
@click.option("--rename", multiple=True, metavar="OLD=NEW",
              help="prefix rewrite applied to tensor names (repeatable)")
def cmd_convert_orbax(src: str, dst_dir: str, rename: tuple[str, ...]) -> None:
    """Orbax PyTree checkpoint -> DST_DIR/model.safetensors."""
    from modelx_tpu.client.convert import convert_orbax

    try:
        out = convert_orbax(src, dst_dir, list(rename), log=click.echo)
    except Exception as e:  # orbax raises library-internal types for bad
        # checkpoints; a CLI must say "error: ...", not print a traceback
        _fail(e)
    click.echo(json.dumps(out))


@cmd_convert.command("torch")
@click.argument("src")
@click.argument("dst_dir")
@click.option("--rename", multiple=True, metavar="OLD=NEW",
              help="prefix rewrite applied to tensor names (repeatable)")
def cmd_convert_torch(src: str, dst_dir: str, rename: tuple[str, ...]) -> None:
    """torch state_dict (.bin/.pt) -> DST_DIR/model.safetensors."""
    from modelx_tpu.client.convert import convert_torch

    try:
        out = convert_torch(src, dst_dir, list(rename), log=click.echo)
    except Exception as e:  # torch.load raises pickle/runtime errors for
        # incompatible checkpoints; surface them as "error: ..."
        _fail(e)
    click.echo(json.dumps(out))


# -- version ------------------------------------------------------------------


@main.command("version")
def cmd_version() -> None:
    click.echo(str(get_version()))


# -- completion ---------------------------------------------------------------


# click has no powershell backend, so the reference's fourth shell
# (completion.go:1-20) gets a hand-rolled Register-ArgumentCompleter script
# that shells out to the hidden `modelx __complete` command below — same
# dynamic remote completion as the POSIX shells.
_POWERSHELL_COMPLETION = r"""
Register-ArgumentCompleter -Native -CommandName modelx -ScriptBlock {
    param($wordToComplete, $commandAst, $cursorPosition)
    # AST tokens exclude trailing whitespace; $wordToComplete is '' exactly
    # when the cursor sits after a space, i.e. a fresh argument position
    $words = @($commandAst.ToString().Split(" ") | Where-Object { $_ -ne "" } | Select-Object -Skip 1)
    if ([string]::IsNullOrEmpty($wordToComplete)) { $words = $words + "" }
    modelx __complete -- @($words) 2>$null | ForEach-Object {
        [System.Management.Automation.CompletionResult]::new($_, $_, 'ParameterValue', $_)
    }
}
""".strip()


@main.command("completion")
@click.argument("shell", type=click.Choice(["bash", "zsh", "fish", "powershell"]))
def cmd_completion(shell: str) -> None:
    """Emit shell completion script (cmd/modelx/completion)."""
    if shell == "powershell":
        click.echo(_POWERSHELL_COMPLETION)
        return
    var = "_MODELX_COMPLETE"
    prog = "modelx"
    click.echo(f'eval "$({var}={shell}_source {prog})"')


# commands whose FIRST positional argument is a model reference; later
# positions are directories (filename completion is the shell's own job) —
# except `copy`, whose second position is also a ref
_REF_COMMANDS = ("push", "pull", "info", "list", "gc", "dl", "copy", "verify", "diff", "scrub")


@main.command(
    "__complete",
    hidden=True,
    context_settings={"ignore_unknown_options": True},
)
@click.argument("words", nargs=-1, type=click.UNPROCESSED)
def cmd_hidden_complete(words: tuple[str, ...]) -> None:
    """Completion backend for shells click can't drive (powershell):
    ``modelx __complete -- <words...>`` prints one candidate per line. The
    last word is the one being completed (may be empty)."""
    words = list(words) or [""]
    incomplete, prior = words[-1], words[:-1]
    try:
        args = [w for w in prior if not w.startswith("-")]
        if not args:  # completing the subcommand itself
            if not incomplete.startswith("-"):
                for name, cmd in main.commands.items():
                    if not cmd.hidden and name.startswith(incomplete):
                        click.echo(name)
            return
        # only the ref argument completes remotely: `push <ref> <dir>` must
        # not offer repo refs for the directory slot
        # copy/diff: both positional args are refs
        ref_positions = 2 if args[0] in ("copy", "diff") else 1
        if (
            args[0] in _REF_COMMANDS
            and len(args) <= ref_positions
            and not incomplete.startswith("-")
        ):
            for cand in _complete_ref(None, None, incomplete):
                click.echo(cand)
    except Exception:
        pass  # completion must never fail the shell


if __name__ == "__main__":
    main()
