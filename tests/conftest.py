"""Test harness: force an 8-device virtual CPU platform before jax loads.

Multi-chip TPU hardware is not available in CI; all sharding/parallelism
tests run over a virtual 8-device CPU mesh, exactly as the driver's
dryrun_multichip does. This must run before any jax import anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch the real chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# the env vars above are for child processes and for a jax not yet
# imported; jax.config covers a plugin that imported jax before conftest
# ran, and an XLA_FLAGS that arrived with a different device count
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture
def tmp_backend_dir(tmp_path):
    d = tmp_path / "backend"
    d.mkdir()
    return str(d)


@pytest.fixture
def tmp_wal_dir(tmp_path):
    d = tmp_path / "wal"
    d.mkdir()
    return str(d)
