"""Test fixtures.

Mirrors the reference's test strategy (SURVEY.md §4): the reference runs its
"distributed" integration tests on `local[*]` Spark with multiple partitions;
the TPU-native analogue is a virtual 8-device CPU mesh via
``--xla_force_host_platform_device_count=8``, which exercises real psum /
sharding semantics without TPU hardware.
"""

import os

# Must be set before jax initializes any backend.
os.environ["JAX_PLATFORMS"] = "cpu"

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# The config flag as well: a pytest plugin may have imported jax before
# this file ran, after which the environment variable is no longer read.
jax.config.update("jax_platforms", "cpu")

# Float64 for finite-difference oracles and scipy parity checks.  Library
# data paths pin float32 explicitly, so this only affects test-constructed
# float64 arrays.
jax.config.update("jax_enable_x64", True)

# Tier-1 is compile-bound: most of the suite's wall clock is XLA compiling
# thousands of tiny per-test programs, and a warm persistent compilation
# cache cuts a rerun several-fold.  The directory is the one every entry
# point uses (utils/compile_cache.cache_dir: $JAX_COMPILATION_CACHE_DIR,
# else <checkout>/.jax_cache — wipe it to measure cold);
# min_compile_secs=0.0 because the win here IS the sub-second compiles.
# tests/test_aux.py's TestCompileCache mutates this process-global config
# and restores it via its autouse fixture.
from photon_ml_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache("auto", min_compile_secs=0.0)

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: wall-clock-bound tests (load generators); excluded from "
        "tier-1 via -m 'not slow'",
    )


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """Free every compiled program when a test module ends.

    Each XLA:CPU executable keeps several memory mappings; a single
    process running the whole suite against a COLD compile cache
    accumulates them past ``vm.max_map_count`` (65,530) around the 860th
    test and dies with a segfault inside the compiler (measured: 42,718
    mappings at 48% of the suite).  Cleared per module the count stays
    bounded, and later modules re-read shared programs from the
    persistent cache instead of recompiling them."""
    yield
    jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual CPU devices, got {len(devices)}"
    return devices[:8]
