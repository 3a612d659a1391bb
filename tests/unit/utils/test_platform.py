"""utils.platform: the TPU requirement of the measuring entry points, and the virtual
CPU mesh set-up.  (The compile-cache location rule is pinned in
tests/unit/tuning/test_compile_cache.py.)"""

import os
from types import SimpleNamespace

import jax
import pytest

from nanofed_tpu.utils import platform


def _fake_devices(monkeypatch, kind: str, n: int = 1):
    devices = [SimpleNamespace(platform="tpu", device_kind=kind) for _ in range(n)]
    monkeypatch.setattr(jax, "devices", lambda: devices)
    return devices


def test_require_tpu_refuses_the_cpu_backend():
    with pytest.raises(SystemExit, match="need a TPU.*'cpu'"):
        platform.require_tpu()


def test_require_tpu_refuses_a_device_without_published_peaks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v99")
    with pytest.raises(SystemExit, match="not in the peaks table"):
        platform.require_tpu()


def test_require_tpu_returns_the_devices_and_their_peaks(monkeypatch):
    devices = _fake_devices(monkeypatch, "TPU v5 lite", n=4)
    got, peaks = platform.require_tpu()
    assert got is devices
    assert peaks.flops_per_s == 197e12 and "v5e" in peaks.basis


def test_force_cpu_mesh_replaces_a_preset_device_count(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--foo=1 --xla_force_host_platform_device_count=8")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    platform.force_cpu_mesh(4)
    assert os.environ["XLA_FLAGS"] == "--foo=1 --xla_force_host_platform_device_count=4"
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert updates == [("jax_platforms", "cpu")]
