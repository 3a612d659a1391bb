"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

This is the TPU build's "fake backend" (SURVEY.md §4): where the reference mocks aiohttp
sessions, we simulate the device mesh with ``--xla_force_host_platform_device_count=8`` so
every ``shard_map``/collective path runs for real, just on CPU.
"""

import os

# Tests run on the virtual CPU mesh whatever the machine holds: the multi-device code
# paths need 8 devices, and a test run must never take a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Entry points under test turn the persistent compilation cache on
# (utils.platform.enable_compilation_cache); a test run neither reads nor writes it —
# tests/unit/tuning/test_compile_cache.py enables it for its own directory.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return jax.random.key(0)


@pytest.fixture
def np_rng():
    return np.random.default_rng(0)
