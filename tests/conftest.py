"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding
(dp/tp/sp meshes, collectives) is exercised without TPU hardware.  Must run
before the first ``import jax`` anywhere in the test process.
"""

import os

# Force CPU even when the environment pins JAX_PLATFORMS to a TPU platform:
# the suite needs 8 virtual devices for sharding tests.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
