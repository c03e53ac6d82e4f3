"""Test harness: 8 fake CPU devices so the real SPMD path runs hardware-free.

SURVEY §4 "Multi-device without a cluster": JAX's standard trick —
``--xla_force_host_platform_device_count=8`` — lets every sharding/psum
test exercise the genuine multi-chip code path on CPU.
Must run before the first ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)
# Hermetic compiles: the suite (and every engine child it spawns) runs
# with JAX's persistent compilation cache switched off, so no test
# reads what another wrote into the in-checkout default
# (compilecache.resolve_cache_dir). The cache tests turn it back on
# around themselves, pointed at a tmp dir (``compile_cache_dir``).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from imagent_tpu.cluster import make_mesh
    return make_mesh(model_parallel=1)


@pytest.fixture
def compile_cache_dir(tmp_path, monkeypatch):
    """A cold, enabled compile cache at ``tmp_path/cc`` — placed the
    way a deployment places it (``JAX_COMPILATION_CACHE_DIR``, read by
    ``compilecache.arm``) for this process AND the children a test
    spawns; switched off again on the way out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache = tmp_path / "cc"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "true")
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
