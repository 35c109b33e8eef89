"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding paths
(`jax.sharding.Mesh` over partitions) are exercised without TPU hardware.
Must be set before jax initializes its backends.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests are CPU-only wherever they run: the config pin holds even if jax was
# imported (and read its environment) before this file.
import jax

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection tests (the fast smoke subset is "
        "unmarked-slow and rides in tier-1; run `-m chaos` for all)")
