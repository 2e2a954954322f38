"""Test configuration.

Tests run on a virtual 8-device CPU mesh (no TPU needed in CI) by forcing
the host platform before JAX is first imported. This mirrors the
multi-chip sharding environment the driver validates via
``__graft_entry__.dryrun_multichip``.
"""

import os

import pytest

# Child processes (producers, the blendjax-launch CLI) must import
# blendjax from this source checkout even when spawned with a foreign
# cwd; export the repo root so the whole process tree inherits it.
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_pp = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p]
if _repo_root not in _pp:
    os.environ["PYTHONPATH"] = os.pathsep.join([_repo_root] + _pp)

# The CURRENT interpreter also needs the repo root importable (tests
# import repo-root modules like `chip_smoke`): the bare `pytest` entry point
# does not put the cwd on sys.path the way `python -m pytest` does.
import sys

if _repo_root not in sys.path:
    sys.path.insert(0, _repo_root)

# Opt-in real-device runs: `BLENDJAX_TEST_TPU=1 pytest -m tpu` skips the
# CPU-mesh override so tpu-marked tests really touch the device.
if os.environ.get("BLENDJAX_TEST_TPU") != "1":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    # Something may have imported jax already (a pytest plugin), and then
    # the env var was read too late; the config update selects the CPU
    # backend as long as it runs before the first backend/device query.
    import jax

    jax.config.update("jax_platforms", "cpu")


@pytest.fixture
def compile_cache_config_guard():
    """configure_compilation_cache mutates process-global jax.config (by
    design — it is a process-level lever); restore it so the rest of the
    suite compiles exactly as it would without the test."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_enable_xla_caches",
    )
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
