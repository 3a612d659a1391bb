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


@pytest.fixture(scope="session")
def equations():
    """``equations(fn, *args)``: every equation of ``fn``'s jaxpr, those of every nested
    jaxpr (a rematerialized body, a loop's, a ``custom_vjp``'s rules) included, each where
    it stands: two layers that share one traced function are printed once and run twice."""

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return lambda fn, *args: list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.fixture(scope="session")
def kernel_calls(equations):
    """``kernel_calls(fn, *args)``: every launch of an attention kernel in ``fn``'s jaxpr by
    the kernel's name (a ``pallas_call``, or a ``jit`` equation that stands in for one)."""

    def count(fn, *args):
        calls = {}
        for eqn in equations(fn, *args):
            name = eqn.params.get("name")
            if (eqn.primitive.name in ("pallas_call", "jit", "pjit") and isinstance(name, str)
                    and name.startswith("causal_attention_")):
                calls[name] = calls.get(name, 0) + 1
        return calls

    return count
