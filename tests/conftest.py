"""Test harness config.

Tests run on a virtual 8-device CPU mesh (the sharding/parallelism suites need
multiple devices; real multi-chip TPU hardware is not available in CI). The
platform is pinned to cpu here so a bare ``pytest`` on a TPU host cannot take
the chip (``JAX_PLATFORMS=cpu`` in the environment does the same).

Mirrors the reference's approach of running distributed tests without a
cluster (Spark local[N] — dl4j-spark/.../BaseSparkTest.java:89).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process / long-running tests excluded from the "
        "tier-1 run (tier-1 uses -m 'not slow'); every slow test must "
        "carry its own hard timeout so it can never hang a full run")


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) == 8, f"expected 8 virtual CPU devices, got {d}"
    return d
