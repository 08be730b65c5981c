"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (no TPU needed in CI) — the env
vars must be set before jax is first imported anywhere in the test process.
"""

import os

# force CPU even when the ambient env selects the TPU platform (the chip is
# reached through chip_smoke.py / bench.py only; tests always run on the
# virtual 8-device CPU mesh) — set before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # holds even if a plugin imported jax first

# Persistent XLA compilation cache: the suite compiles the same tiny-model
# programs over and over across modules (every ModelServer fixture re-jits
# the identical HLO), which dominates the tier-1 wall. The cache is keyed
# by HLO + jax version + backend, so hits are exact. No compile-time floor:
# the tiny-model programs mostly compile in under 0.2 s each, and there are
# thousands of them — with the old 0.2 s floor a warm rerun of
# test_paged_kv + test_stop_tokens took 36 s, without it 23 s (cold: 39 vs
# 40 s; CPU wall on the PR 21 sandbox, not a device number).
# MODELX_TEST_NO_COMPILE_CACHE=1 opts out.
if not os.environ.get("MODELX_TEST_NO_COMPILE_CACHE"):
    import tempfile

    jax.config.update("jax_compilation_cache_dir", os.environ.get(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(tempfile.gettempdir(), "modelx-jax-test-cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@pytest.fixture(autouse=True)
def _restore_compile_cache_dir():
    """A test that points the cache elsewhere (enable_compile_cache with a
    tmp_path) must not leave every later test compiling cold into it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != before:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()

# lockdep rides every run as a plugin but only instruments when
# MODELX_LOCKDEP=1 (make chaos) — see modelx_tpu/analysis/lockdep.py
pytest_plugins = ["modelx_tpu.analysis.pytest_lockdep"]
