"""modelx-tpu: a TPU-native, content-addressed model registry and deployment framework.

Capabilities mirror kubegems/modelx (see /root/reference and SURVEY.md): an
OCI-inspired index/manifest/blob registry with presigned "load separation",
a push/pull CLI with incremental content-addressed transfers, and a
deploy-time puller. The deployment path is rebuilt TPU-first: manifests carry
GSPMD shard-layout annotations and the loader streams safetensors blob ranges
straight into TPU HBM via `jax.make_array_from_callback` on a
`jax.sharding.Mesh`.

Subpackages
-----------
- ``modelx_tpu.types``    — data model (Index/Manifest/Descriptor/BlobLocation)
- ``modelx_tpu.errors``   — OCI-style error codes
- ``modelx_tpu.registry`` — storage providers, stores, HTTP server
- ``modelx_tpu.client``   — push/pull engine, remote client, extensions
- ``modelx_tpu.dl``       — deploy-time loader: registry -> TPU HBM
- ``modelx_tpu.models``   — flagship JAX model families for the serve path
- ``modelx_tpu.ops``      — TPU kernels (pallas flash attention, ring attention)
- ``modelx_tpu.parallel`` — mesh construction and sharding rules
"""

import time

# the first line any entry point runs: where /proc has no start time for the
# process, start-up (utils/trace.startup) counts from here
T_FIRST_LINE = time.monotonic()

from modelx_tpu.version import __version__  # noqa: E402

__all__ = ["__version__"]
