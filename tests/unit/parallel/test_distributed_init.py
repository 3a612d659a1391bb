"""initialize_distributed: the single-process no-op path (the multi-host path needs a
real multi-process cluster; its contract is documented in docs/concepts.md and exercised
by jax.distributed itself)."""

import nanofed_tpu.parallel.mesh as mesh_mod
from nanofed_tpu.parallel import initialize_distributed


def test_single_process_noop(monkeypatch):
    """No coordinator configured anywhere -> no jax.distributed call, identity result."""
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    called = []
    monkeypatch.setattr(
        mesh_mod.jax.distributed, "initialize",
        lambda **kw: called.append(kw),
    )
    info = initialize_distributed()
    assert info == {"process_index": 0, "process_count": 1}
    assert called == []


def test_single_host_tpu_hostnames_is_noop(monkeypatch):
    """A single-entry TPU_WORKER_HOSTNAMES (one host) must not trigger multi-host
    init."""
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    called = []
    monkeypatch.setattr(
        mesh_mod.jax.distributed, "initialize",
        lambda **kw: called.append(kw),
    )
    info = initialize_distributed()
    assert info["process_count"] == 1
    assert called == []


def test_explicit_coordinator_calls_jax_distributed(monkeypatch):
    """An explicit coordinator address routes through jax.distributed.initialize with
    the exact arguments given."""
    called = []
    monkeypatch.setattr(
        mesh_mod.jax.distributed, "initialize", lambda **kw: called.append(kw)
    )
    monkeypatch.setattr(mesh_mod.jax, "process_index", lambda: 1, raising=False)
    monkeypatch.setattr(mesh_mod.jax, "process_count", lambda: 4, raising=False)
    info = initialize_distributed(
        coordinator_address="10.0.0.1:8476", num_processes=4, process_id=1
    )
    assert called == [
        {"coordinator_address": "10.0.0.1:8476", "num_processes": 4, "process_id": 1}
    ]
    assert info == {"process_index": 1, "process_count": 4}


def test_env_vars_configure_init(monkeypatch):
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.2:9000")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "2")
    monkeypatch.setenv("JAX_PROCESS_ID", "0")
    called = []
    monkeypatch.setattr(
        mesh_mod.jax.distributed, "initialize", lambda **kw: called.append(kw)
    )
    monkeypatch.setattr(mesh_mod.jax, "process_index", lambda: 0, raising=False)
    monkeypatch.setattr(mesh_mod.jax, "process_count", lambda: 2, raising=False)
    initialize_distributed()
    assert called == [
        {"coordinator_address": "10.0.0.2:9000", "num_processes": 2, "process_id": 0}
    ]


def test_partial_config_without_coordinator_raises(monkeypatch):
    """Process ids without a coordinator address must fail loudly — a silent
    single-process fallback would train N divergent models."""
    import pytest

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    with pytest.raises(ValueError, match="coordinator address"):
        initialize_distributed(num_processes=4, process_id=2)


def test_force_calls_bare_initialize(monkeypatch):
    """force=True hands off to jax.distributed.initialize with no arguments so JAX's
    TPU-metadata auto-detection runs (plain multi-host TPU VMs)."""
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    called = []
    monkeypatch.setattr(
        mesh_mod.jax.distributed, "initialize", lambda **kw: called.append(kw)
    )
    monkeypatch.setattr(mesh_mod.jax, "process_index", lambda: 0, raising=False)
    monkeypatch.setattr(mesh_mod.jax, "process_count", lambda: 8, raising=False)
    info = initialize_distributed(force=True)
    assert called == [
        {"coordinator_address": None, "num_processes": None, "process_id": None}
    ]
    assert info["process_count"] == 8


def test_single_process_path_touches_no_config(monkeypatch):
    """The documented no-op must not flip global config either."""
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    updates = []
    monkeypatch.setattr(
        mesh_mod.jax.config, "update",
        lambda name, value: updates.append((name, value)),
    )
    assert initialize_distributed()["process_count"] == 1
    assert updates == []
