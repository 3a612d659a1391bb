"""Wire → mesh bridge: drain HTTP ingest buffers into the hierarchical reduce.

ROADMAP item 1's fusion.  Before this module the repo had two disjoint
serving stacks: the batched wire tier (``HTTPTransport`` + ``HTTPServer`` +
``DeviceIngestBuffer``, proven at 10k clients single-host) and the 3-axis
``(hosts, clients, model)`` mesh (proven at 100k *simulated* clients with no
wire).  Here they become one aggregation hierarchy:

* Each mesh host runs a listener + ingest buffer front end.  The buffer's
  batched ``coefs @ buffer`` reduce IS the host-local aggregation stage —
  but drained UNNORMALIZED (``DeviceIngestBuffer.drain_fedavg_partial``:
  ``Σ w_i δ_i`` and the weight mass, not ``Σ (w_i/Σw) δ_i``), because the
  FedAvg normalizer is a global quantity.
* ONE cross-host psum over the ``hosts`` axis then moves exactly one
  model-sized tensor per round — each host's ``[P+1]`` partial row
  (numerator ‖ weight mass) — and the apply ``base + num/den`` lands
  replicated on every host.  This is the same client → host → global
  hierarchy :func:`~nanofed_tpu.parallel.mesh.hierarchical_psum` gives the
  simulated path, with wire clients as the leaves.

Two program builders cover the two dispatch shapes:

* :func:`build_cross_host_reduce` — the RUNTIME program of the federate
  harness's two-stage path: host-local drains happen in the ingest buffers
  (outside jit, per arrival), and this program is the round's single
  cross-host collective.
* :func:`build_drained_ingest_reduce` — the FUSED single-program form
  (per-device ingest slabs → host-local reduce → one hosts psum → apply),
  dispatch-shaped for the program auditor's reference catalog: the
  mesh-discipline check (clients reduce before hosts; one model-sized
  cross-host tensor per round) machine-checks the fusion invariant.

Parity contract (tested in ``tests/integration/test_ingest_parity.py``):
host-local partial drains + cross-host sum ≡ a single host draining the
union of the buffers — exactly, for FedAvg trajectories and FedBuff
staleness accounting, because ``Σ_h Σ_{i∈h} w_i δ_i / Σ_h Σ_{i∈h} w_i`` is
the union's weighted mean under any partition of clients into hosts.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from nanofed_tpu.parallel.mesh import (
    CLIENT_AXIS,
    HOST_AXIS,
    hierarchical_psum,
    multi_axis_shard_map_kwargs,
    replicated_sharding,
    shard_map,
)

__all__ = [
    "MASS_LANE",
    "apply_summed_row",
    "assemble_host_rows",
    "build_cross_host_reduce",
    "build_cross_host_row_psum",
    "build_drained_ingest_reduce",
    "host_partial_row",
]

#: Trailing lanes of a host partial row beyond the P model lanes: the weight
#: mass (FedAvg) or live count (FedBuff) that makes the partial composable.
MASS_LANE = 1

#: Division floor for the global weight mass: a round where EVERY host drained
#: an empty buffer divides zero by this instead of NaN-ing the model — the
#: caller detects the failure from the returned mass, not from the params.
_MASS_FLOOR = 1e-12


def _require_hosts(mesh: Mesh) -> None:
    if HOST_AXIS not in mesh.axis_names:
        raise ValueError(
            f"the wire→mesh bridge needs a mesh with a {HOST_AXIS!r} axis "
            f"(got axes {mesh.axis_names}); build one with "
            "make_mesh(shape=(hosts, clients, model))"
        )


def host_partial_row(
    partial: Any | None,
    mass: float,
    flat_size: int,
    extra: tuple[float, ...] = (),
) -> np.ndarray:
    """One host's ``[P+1+E]`` contribution to the cross-host reduce: the
    unnormalized drain numerator ‖ its weight mass ‖ optional control lanes.
    An empty drain (``partial is None``) contributes exact zeros in the model
    and mass lanes — the host still participates in the psum (collectives
    admit no absentees), it just adds nothing.  ``extra`` lanes are summed
    across hosts like everything else; the federate harness uses one as a
    stop vote so workers reach round-count consensus THROUGH the collective
    they already run, instead of diverging and deadlocking the next psum."""
    row = np.zeros(flat_size + MASS_LANE + len(extra), np.float32)
    if partial is not None:
        row[:flat_size] = np.asarray(partial, np.float32)
        row[flat_size] = float(mass)
    for i, v in enumerate(extra):
        row[flat_size + MASS_LANE + i] = float(v)
    return row


def assemble_host_rows(mesh: Mesh, local_rows: Any) -> jax.Array:
    """The global ``[H, P+1]`` rows array, hosts-axis sharded, from each
    process's local row block — ``make_array_from_process_local_data`` on a
    real multi-process mesh (no host ever materializes another host's row),
    a plain sharded ``device_put`` on a single-process virtual-hosts mesh
    (where the caller holds all rows)."""
    _require_hosts(mesh)
    sharding = NamedSharding(mesh, P(HOST_AXIS))
    rows = np.atleast_2d(np.asarray(local_rows, np.float32))
    n_hosts = int(mesh.shape[HOST_AXIS])
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(
            sharding, rows, (n_hosts, rows.shape[1])
        )
    if rows.shape[0] != n_hosts:
        raise ValueError(
            f"single-process assembly needs all {n_hosts} host rows, "
            f"got {rows.shape[0]}"
        )
    return jax.device_put(rows, sharding)


def build_cross_host_reduce(
    mesh: Mesh, flat_size: int
) -> Callable[[jax.Array, jax.Array], tuple[jax.Array, jax.Array]]:
    """The ONE cross-host collective of a federated round (two-stage runtime
    path): psum the ``[H, P+1+E]`` host partial rows over ``hosts`` and apply
    ``base + num / den`` once.

    Returns a jitted ``fn(rows, base) -> (new_flat, tail)`` with both outputs
    replicated.  ``tail`` is the psum'd trailing lanes of the row —
    ``tail[0]`` is the global weight mass, ``tail[1:]`` any extra control
    lanes the caller packed via :func:`host_partial_row`.  ``tail[0] == 0``
    means every host drained empty — the round FAILED and ``new_flat == base``
    (the division floor keeps the params finite; the caller decides the
    outcome from the mass).  No buffers are donated: the output aliases
    nothing (``rows`` is consumed, ``base`` may be republished on failure)."""
    _require_hosts(mesh)

    def body(rows: jax.Array, base: jax.Array) -> tuple[jax.Array, jax.Array]:
        # rows block: this host's [H/H, P+1+E] slice — sum collapses the
        # block dim so the psum moves exactly one model-sized row per host.
        total = jax.lax.psum(jnp.sum(rows, axis=0), HOST_AXIS)
        num, den = total[:flat_size], total[flat_size]
        return base + num / jnp.maximum(den, _MASS_FLOOR), total[flat_size:]

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(HOST_AXIS), P()),
        out_specs=(P(), P()),
        **multi_axis_shard_map_kwargs(mesh),
    )
    repl = replicated_sharding(mesh)
    return jax.jit(mapped, out_shardings=(repl, repl))


def build_cross_host_row_psum(
    mesh: Mesh,
) -> Callable[[jax.Array], jax.Array]:
    """The single-collective runtime path: psum the ``[H, P+1+E]`` host rows
    over ``hosts`` and return ONLY the summed row — the apply stays on the
    host (:func:`apply_summed_row`).

    This exists because of a CPU/gloo failure mode the federate harness hit
    at 4 processes: any round whose dispatch carries MORE than one in-flight
    gloo stream (a psum with several replica groups because the mesh has a
    populated clients axis, a ``device_put`` broadcast of the base, a
    replicated-output materialization) can cross transfers between streams in
    gloo's async slot sequencing — ``op.preamble.length <= op.nbytes``
    aborts.  Callers should hand this builder a HOSTS-ONLY mesh (one device
    per process, ``make_mesh(devices=[one per process], shape=(H, 1, 1))``)
    so the compiled program contains exactly one all-reduce with exactly one
    replica group: one gloo stream per round, nothing to cross.  The output
    is each device's local psum result (replicated by the all-reduce itself —
    ring results are bitwise identical on every rank), so no gather/broadcast
    follows it."""
    _require_hosts(mesh)

    def body(rows: jax.Array) -> jax.Array:
        return jax.lax.psum(jnp.sum(rows, axis=0), HOST_AXIS)

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(HOST_AXIS),),
        out_specs=P(),
        **multi_axis_shard_map_kwargs(mesh),
    )
    return jax.jit(mapped, out_shardings=replicated_sharding(mesh))


def apply_summed_row(
    base: np.ndarray, total: np.ndarray, flat_size: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side FedAvg apply for :func:`build_cross_host_row_psum`:
    ``(base + num / max(mass, floor), tail)`` in float32 numpy.  Every host
    computes this from the SAME psum'd row and the SAME base (identical by
    induction), so the new params are bitwise identical across hosts without
    a second collective.  ``tail[0] == 0`` means every host drained empty —
    the division floor keeps ``new == base`` exactly."""
    total = np.asarray(total, np.float32)
    base = np.asarray(base, np.float32)
    num, den = total[:flat_size], total[flat_size]
    new = base + num / np.maximum(den, np.float32(_MASS_FLOOR))
    return new.astype(np.float32), total[flat_size:]


def build_drained_ingest_reduce(
    mesh: Mesh, capacity: int, flat_size: int
) -> Callable[[jax.Array, jax.Array, jax.Array], jax.Array]:
    """The fused wire→mesh round reduce as ONE program, for the audit
    catalog's mesh-discipline check and the single-dispatch parity path.

    Per-device inputs (global shapes; sharded jointly over
    ``(hosts, clients)``): the ingest slab ``buf[H·C, capacity, P]`` and raw
    FedAvg weights ``coefs[H·C, capacity]`` (unused slots exactly 0.0, the
    buffer's own convention), plus the replicated flat base.  The body is the
    hierarchy in three lines: the drain's batched ``coefs @ buf`` produces
    each shard's partial, ``psum`` over ``clients`` closes the host-local
    stage on ICI, and ONE ``psum`` over ``hosts`` moves the single
    model-sized ``[P+1]`` row per round that the auditor's cross-host byte
    budget enforces.  The FedAvg apply lands replicated."""
    _require_hosts(mesh)
    data_spec = P((HOST_AXIS, CLIENT_AXIS))

    def body(buf: jax.Array, coefs: jax.Array, base: jax.Array) -> jax.Array:
        # buf block [1, capacity, P]; coefs block [1, capacity].
        num = coefs[0] @ buf[0]  # the DeviceIngestBuffer drain reduce
        row = jnp.concatenate([num, jnp.sum(coefs[0])[None]])
        # Innermost first: clients (host-local) then ONE hosts psum.
        total = hierarchical_psum(row, (HOST_AXIS, CLIENT_AXIS))
        return base + total[:flat_size] / jnp.maximum(total[flat_size], _MASS_FLOOR)

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(data_spec, data_spec, P()),
        out_specs=P(),
        **multi_axis_shard_map_kwargs(mesh),
    )
    return jax.jit(mapped, out_shardings=replicated_sharding(mesh))
