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


@pytest.fixture
def step_op_names():
    """``step_op_names(net, *batch, extra=())``: the ``op_name`` of every
    instruction jax emitted into ``net``'s COMPILED train step (a name stack
    starts with ``jit(`` or, inside a loop's body, with a layer's marker; a
    reducer's body and a parameter carry other names). ``batch`` is what
    follows the rng in the step's arguments (arrays or shape structs),
    ``extra`` what stands between ``opt_state`` and the rng (a compressed
    step's state). Compiled with the persistent cache OFF: its key leaves
    metadata out (``jax_compilation_cache_include_metadata_in_key`` is
    False), so another tree's executable would bring that tree's names, and
    a benchmark test earlier in the process may have switched the cache on."""
    import re

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def names(net, *batch, extra=()):
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            text = net._get_jitted("train").lower(
                struct(net.params), struct(net.state), struct(net.opt_state),
                *(struct(e) for e in extra), struct(net._rng), *batch, None,
                None).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
        return [o for o in re.findall(r'op_name="([^"]*)"', text)
                if o.startswith("jit(") or re.match(r"[A-Za-z_]\w*:", o)]

    return names
