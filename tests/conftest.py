"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding logic is validated on a virtual CPU mesh
(xla_force_host_platform_device_count); the chip itself is checked by
chip_smoke.py through the chip tool.  This must run before jax
initializes its backends.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-node / chaos scenarios excluded from the "
        "tier-1 fast gate (run with -m slow)")
