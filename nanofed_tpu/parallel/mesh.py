"""Device mesh construction and client/model-axis sharding helpers.

The reference's "cluster" is an aiohttp server plus coroutine clients in one event loop
(``examples/mnist/run_experiment.py:126-131``).  Here the cluster is a
``jax.sharding.Mesh`` with a named ``clients`` axis: each device holds ``C / n_devices``
clients, local training is vmapped within a device, and aggregation is a ``psum`` across
it.  On a single host the mesh spans the local chips over ICI; on a multi-host slice the
SAME program spans every host's chips (ICI within a slice, DCN across slices) after one
extra step — ``initialize_distributed()`` before any JAX computation, so
``jax.devices()`` enumerates the global device set instead of just the local ones.

A second, optional ``model`` axis (``make_mesh(shape=(n_client_shards,
n_model_shards))``) adds FSDP-style parameter sharding: global params and server
optimizer state live split over the model axis (each leaf's largest divisible
dimension — :func:`param_sharding`), client data stays sharded over ``clients`` and
replicated over ``model``, and the round programs run the model axis in shard_map's
``auto`` (GSPMD) mode so XLA inserts the all-gathers/reduce-scatters around the
per-client compute while the FedAvg reduction stays a ``psum`` over ``clients`` only.
On a 1-D mesh every model-axis helper degenerates to the replicated layout, so all
existing call sites keep their exact semantics.

A third, optional ``hosts`` axis (``make_mesh(shape=(n_hosts, n_client_shards,
n_model_shards))``) scales the client axis PAST one host: devices are grouped by
process (``jax.process_index``) so each row of the hosts axis is one host's chips,
client data shards over ``(hosts, clients)`` jointly, and the FedAvg reduction
becomes HIERARCHICAL — a host-local ``psum`` over the ``clients`` axis (ICI) followed
by ONE cross-host ``psum`` over ``hosts`` (DCN): inter-host traffic per round is one
model-sized tensor, not one per client shard (the client → edge → global pattern the
communication survey, arXiv:2405.20431, names as the production topology for
million-user populations).  The hosts axis also works single-process over virtual CPU
devices (``--xla_force_host_platform_device_count``), which is how tier-1 tests the
whole path without a pod; :func:`initialize_distributed` + a multi-process CPU/TPU
cluster make the same program span real hosts.
"""

from __future__ import annotations

import math
import os

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nanofed_tpu.core.types import ClientData

CLIENT_AXIS = "clients"
MODEL_AXIS = "model"
HOST_AXIS = "hosts"

#: The one shard_map every round-program builder uses.
shard_map = jax.shard_map


def pcast_varying(tree, axis_name: str | tuple[str, ...]):
    """Mark a replicated pytree as device-varying inside a ``shard_map`` body.

    The replication checker requires the explicit ``lax.pcast(..., to="varying")``
    before replicated inputs feed per-device compute.  ``axis_name`` may be a tuple
    (the hierarchical ``(hosts, clients)`` client axes) — the cast covers every
    named axis.
    """
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return jax.tree.map(lambda x: lax.pcast(x, axes, to="varying"), tree)


def hierarchical_psum(x, axes: str | tuple[str, ...]):
    """``psum`` over the client axes, HIERARCHICALLY when there is more than one:
    innermost (``clients``) first — the host-local reduce over ICI — then each
    outer axis (``hosts``) over the already-reduced value, so the cross-host
    (DCN) stage moves ONE model-sized tensor per round instead of one per client
    shard.  Mathematically identical to the flat ``psum`` over all axes (same
    sum, different association order — float parity to rounding); structurally it
    is the client → host/edge → global aggregation hierarchy."""
    if isinstance(axes, str):
        return lax.psum(x, axes)
    for ax in reversed(tuple(axes)):
        x = lax.psum(x, ax)
    return x


def hierarchical_pmean(x, axes: str | tuple[str, ...]):
    """Mean companion of :func:`hierarchical_psum` (per-stage ``pmean`` composes
    to the global mean because every stage averages over a fixed axis size)."""
    if isinstance(axes, str):
        return lax.pmean(x, axes)
    for ax in reversed(tuple(axes)):
        x = lax.pmean(x, ax)
    return x


def hierarchical_all_gather(x, axes: str | tuple[str, ...], axis: int = 0):
    """``all_gather`` over the client axes, innermost first — the order-statistics
    companion of :func:`hierarchical_psum` (robust aggregation needs every
    client's value on every device; a sort cannot stream through a psum).  The
    concatenation order interleaves host blocks, which is irrelevant to every
    consumer here (trimmed mean / median / Krum are permutation-invariant)."""
    if isinstance(axes, str):
        return lax.all_gather(x, axes, axis=axis, tiled=True)
    for ax in reversed(tuple(axes)):
        x = lax.all_gather(x, ax, axis=axis, tiled=True)
    return x


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    force: bool = False,
) -> dict[str, int]:
    """Opt-in multi-host initialization: call ONCE, before any JAX computation, on every
    process of a multi-host TPU slice (or GPU/CPU cluster).

    Wraps ``jax.distributed.initialize``.  Three ways in:

    * **Explicit**: pass ``coordinator_address`` (+ ``num_processes``/``process_id``
      where the platform can't infer them), or set ``JAX_COORDINATOR_ADDRESS`` /
      ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``.
    * **TPU pods**: ``force=True`` calls ``jax.distributed.initialize()`` bare and lets
      JAX auto-detect everything from the TPU metadata server (the right mode on plain
      multi-host TPU VMs); GKE-style environments that set a multi-entry
      ``TPU_WORKER_HOSTNAMES`` are detected without ``force``.
    * **Single process** (laptops, CI, one-chip benchmarks): with none of the above,
      the call is a documented no-op returning ``{"process_index": 0,
      "process_count": 1}`` — shared code paths can call it unconditionally.

    Passing ``num_processes``/``process_id`` WITHOUT any coordinator address raises:
    silently proceeding single-process would train N divergent models that each look
    healthy.

    After it returns, ``jax.devices()`` is the GLOBAL device list and ``make_mesh()``
    builds the pod-wide client mesh — the round step is unchanged; XLA routes the psum
    over ICI within a slice and DCN across slices.

    This is the explicit form of the distributed-backend row of SURVEY.md §2: the
    reference's NCCL/MPI-shaped capability is jax.distributed (a gRPC coordination
    service for process bring-up) + XLA collectives (the data plane).
    """
    coordinator_address = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])

    multi_host_tpu = bool(os.environ.get("TPU_WORKER_HOSTNAMES", "").strip().count(","))
    if coordinator_address is None and not (multi_host_tpu or force):
        if num_processes is not None or process_id is not None:
            raise ValueError(
                "num_processes/process_id configured but no coordinator address: "
                "pass coordinator_address= (or JAX_COORDINATOR_ADDRESS), or use "
                "force=True on TPU pods to let JAX auto-detect — refusing to "
                "silently run single-process"
            )
        # Single-process: nothing to coordinate.
        return {"process_index": 0, "process_count": 1}

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
    }


def make_mesh(
    devices: list[jax.Device] | None = None,
    axis_name: str = CLIENT_AXIS,
    shape: tuple[int, int] | tuple[int, int, int] | None = None,
    model_axis: str = MODEL_AXIS,
    host_axis: str = HOST_AXIS,
) -> Mesh:
    """Mesh over all (or the given) devices.

    Without ``shape``: the classic 1-D mesh with only the named client axis.
    With ``shape=(n_client_shards, n_model_shards)``: a 2-D ``clients x model``
    mesh — data parallelism over clients, FSDP-style parameter sharding over
    model.  With ``shape=(n_hosts, n_client_shards, n_model_shards)``: the 3-D
    ``hosts x clients x model`` mesh — devices are sorted by (process, id) so
    each hosts-axis row is one process's chips (on a single process the hosts
    axis slices the local devices into virtual hosts, which is how tier-1
    exercises the hierarchical path), and the FedAvg reduce becomes the
    host-local-then-cross-host hierarchy (:func:`hierarchical_psum`).  The
    product must equal the device count; a model (or hosts) dimension of 1 is
    allowed (that axis degenerates to the smaller layout's semantics).
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    if shape is None:
        return Mesh(devs, axis_names=(axis_name,))
    dims = tuple(int(d) for d in shape)
    if any(d < 1 for d in dims):
        raise ValueError(f"mesh shape must be positive, got {shape}")
    if math.prod(dims) != devs.size:
        raise ValueError(
            f"mesh shape {shape} needs {math.prod(dims)} devices "
            f"but {devs.size} are available"
        )
    if len(dims) == 2:
        return Mesh(devs.reshape(dims), axis_names=(axis_name, model_axis))
    if len(dims) != 3:
        raise ValueError(
            f"mesh shape must be (clients, model) or (hosts, clients, model), "
            f"got {shape}"
        )
    n_hosts = dims[0]
    # Hosts-axis rows must be whole processes: sort the global device list by
    # (process, id) — on a real multi-process cluster each contiguous block of
    # devices_per_process devices then belongs to one process, and the reshape
    # puts process p's chips in rows [p*h/P, (p+1)*h/P).  Single-process
    # (virtual hosts over local/virtual devices) keeps plain id order.
    devs = np.asarray(sorted(
        devs.flat, key=lambda d: (getattr(d, "process_index", 0), d.id)
    ))
    process_count = len({getattr(d, "process_index", 0) for d in devs.flat})
    if n_hosts % process_count != 0:
        raise ValueError(
            f"hosts axis of {n_hosts} cannot group {process_count} processes "
            "into whole rows — n_hosts must be a multiple of the process count "
            "(each process's chips fill complete host rows)"
        )
    return Mesh(
        devs.reshape(dims), axis_names=(host_axis, axis_name, model_axis)
    )


def mesh_shape_for_model_shards(
    model_shards: int, n_devices: int
) -> tuple[int, int] | None:
    """Validate a ``--model-shards`` request against the device count and
    return the 2-D mesh shape it implies (None for the classic 1-D layout).
    The single source of truth for the CLI and ``run_experiment``."""
    if model_shards < 1:
        raise ValueError(f"model_shards must be >= 1, got {model_shards}")
    if model_shards == 1:
        return None
    if n_devices % model_shards != 0:
        raise ValueError(
            f"model_shards={model_shards} does not divide the {n_devices} "
            "available devices — the 2-D mesh needs a full "
            "(devices/N, N) clients x model grid"
        )
    return (n_devices // model_shards, model_shards)


def mesh_shape_for_topology(
    hosts: int, model_shards: int, n_devices: int
) -> tuple[int, ...] | None:
    """Validate a ``--hosts`` x ``--model-shards`` request against the device
    count and return the mesh shape it implies: None for the classic 1-D
    layout, ``(clients, model)`` for a single-host FSDP mesh, and ``(hosts,
    clients, model)`` once the hosts axis engages.  The single source of truth
    for the CLI, ``run_experiment``, and the multi-host harness (the 2-axis
    case delegates to :func:`mesh_shape_for_model_shards` so both validators
    stay one rule)."""
    if hosts < 1:
        raise ValueError(f"hosts must be >= 1, got {hosts}")
    if hosts == 1:
        return mesh_shape_for_model_shards(model_shards, n_devices)
    if model_shards < 1:
        raise ValueError(f"model_shards must be >= 1, got {model_shards}")
    if n_devices % (hosts * model_shards) != 0:
        raise ValueError(
            f"hosts={hosts} x model_shards={model_shards} does not divide the "
            f"{n_devices} available devices — the 3-D mesh needs a full "
            "(hosts, devices/(hosts*model_shards), model_shards) grid"
        )
    return (hosts, n_devices // (hosts * model_shards), model_shards)


def mesh_shape(mesh: Mesh) -> tuple[int, ...]:
    """The mesh's per-axis sizes in axis order — ``(clients,)`` for the 1-D mesh,
    ``(clients, model)`` for the 2-D one.  Recorded in bench/dryrun artifacts."""
    return tuple(mesh.shape[name] for name in mesh.axis_names)


def model_axis_size(mesh: Mesh, model_axis: str = MODEL_AXIS) -> int:
    """Number of model (parameter) shards: 1 on any mesh without a model axis."""
    return mesh.shape[model_axis] if model_axis in mesh.axis_names else 1


def host_axis_size(mesh: Mesh, host_axis: str = HOST_AXIS) -> int:
    """Number of hosts-axis rows: 1 on any mesh without a hosts axis."""
    return mesh.shape[host_axis] if host_axis in mesh.axis_names else 1


def client_axis_size(mesh: Mesh, axis_name: str = CLIENT_AXIS) -> int:
    """Size of the ``clients`` mesh axis alone (per-HOST client shards on a
    3-axis mesh — use :func:`client_shard_count` for the padding divisor).  On
    a mesh whose only axis is a custom name, that axis is the client axis."""
    if axis_name in mesh.axis_names:
        return mesh.shape[axis_name]
    if len(mesh.axis_names) == 1:
        return mesh.shape[mesh.axis_names[0]]
    raise ValueError(
        f"mesh axes {mesh.axis_names} carry no {axis_name!r} axis"
    )


def client_shard_count(
    mesh: Mesh, axis_name: str = CLIENT_AXIS, host_axis: str = HOST_AXIS
) -> int:
    """Total shards of the client DATA axis — the divisor for client padding.
    ``clients`` alone on 1-D/2-D meshes; ``hosts x clients`` jointly on the
    3-axis mesh (data rows shard over both, hosts-major)."""
    return client_axis_size(mesh, axis_name) * host_axis_size(mesh, host_axis)


def client_axes(
    mesh: Mesh, axis_name: str = CLIENT_AXIS, host_axis: str = HOST_AXIS
) -> str | tuple[str, ...]:
    """The mesh axis name(s) the client dimension spans: the plain client axis
    on 1-D/2-D meshes, ``(hosts, clients)`` — outer to inner — on the 3-axis
    mesh.  This tuple is what :func:`hierarchical_psum` reduces over and what
    the shard_map data specs name."""
    if host_axis in mesh.axis_names:
        return (host_axis, axis_name)
    return axis_name


def multi_axis_shard_map_kwargs(mesh: Mesh) -> dict:
    """shard_map kwargs for the fully-manual 2-D round programs: empty on a 1-D
    mesh (the classic path is byte-for-byte unchanged), and on a ``clients x
    model`` mesh they disable the replication checker — metric outputs ARE
    replicated over the model axis (every model column computes them from
    identical gathered params and identical client data), but that equality is
    structural, not something the checker can prove from the collectives (the
    psum runs over ``clients`` only)."""
    if len(mesh.axis_names) == 1:
        return {}
    return {"check_vma": False}


def model_spec_dim(spec: P, model_axis: str = MODEL_AXIS) -> int | None:
    """The dimension a :func:`param_partition_spec` shards over the model axis,
    or None for a replicated leaf."""
    for i, entry in enumerate(spec):
        if entry == model_axis:
            return i
    return None


class MeshLayout:
    """The sharding boundary of a round program, shared by every builder
    (``build_sharded_round``, ``build_round_block`` via it, and
    ``build_scaffold_round_step`` must produce the IDENTICAL sharding program
    or the paths drift).  One object owns BOTH axes of the layout rule:

    **Model axis** (FSDP; 2-D and 3-D meshes):

    * :meth:`boundary_specs` — per-leaf shard_map in/out specs for params-shaped
      state (the :func:`param_partition_spec` layout);
    * :meth:`gather_full` — boundary shards -> full leaves (one all-gather over
      the model axis per sharded leaf), feeding the per-client compute;
    * :meth:`slice_shard` — full aggregate -> this device's model shard (the
      reduce-scatter half of FSDP; a slice suffices because the clients-psum
      already left every model column holding the identical full value).

    **Client axes** (the hierarchy; 3-D meshes):

    * :attr:`client_axes` — the axis name(s) the client dimension spans:
      the plain client axis, or ``(hosts, clients)`` on a 3-axis mesh;
    * :attr:`data_spec` — the shard_map spec for client-stacked arrays;
    * :meth:`client_psum` / :meth:`client_pmean` / :meth:`client_all_gather`
      — the client-axis collectives, HIERARCHICAL when a hosts axis exists:
      host-local over ``clients`` (ICI) first, then one cross-host stage over
      ``hosts`` (DCN) on the already-reduced value, so inter-host traffic per
      round is one model-sized tensor instead of one per client shard;
    * :meth:`cast_varying` — :func:`pcast_varying` over every client axis.

    On a 1-D mesh every method is the identity / plain single-axis collective,
    so the classic program is untouched.

    ``raw_keys_at_boundary``: typed PRNG-key arrays (extended dtypes) get a
    rank-mismatched sharding annotation crossing a multi-axis shard_map
    boundary on this JAX (the hidden ``[2]`` key-data dim confuses the
    per-axis annotation) — keys must cross as raw uint32 key data and be
    re-wrapped inside the body.  Bit-identical key material either way.
    """

    def __init__(
        self,
        mesh: Mesh,
        model_axis: str = MODEL_AXIS,
        axis_name: str = CLIENT_AXIS,
        host_axis: str = HOST_AXIS,
    ) -> None:
        self.mesh = mesh
        self.model_axis = model_axis
        self.host_axis = host_axis
        self.n_model_shards = model_axis_size(mesh, model_axis)
        self.n_hosts = host_axis_size(mesh, host_axis)
        self.client_axes: str | tuple[str, ...] = client_axes(
            mesh, axis_name, host_axis
        )
        self.data_spec = P(self.client_axes)
        self.multi_axis = len(mesh.axis_names) > 1
        self.raw_keys_at_boundary = self.multi_axis

    def client_psum(self, x):
        """Sum over the client axes — hierarchical (host-local psum then ONE
        cross-host psum) once a hosts axis exists."""
        return hierarchical_psum(x, self.client_axes)

    def client_pmean(self, x):
        return hierarchical_pmean(x, self.client_axes)

    def client_all_gather(self, x, axis: int = 0):
        return hierarchical_all_gather(x, self.client_axes, axis=axis)

    def cast_varying(self, tree):
        return pcast_varying(tree, self.client_axes)

    def require_params_like(self, params_like) -> None:
        """2-D builders need leaf shapes at build time — the per-leaf layout
        becomes the shard_map in/out specs."""
        if self.multi_axis and params_like is None:
            raise ValueError(
                "a 2-D clients x model mesh needs params_like= at build time: "
                "the per-leaf model-axis layout becomes the shard_map in/out "
                "specs"
            )

    def _leaf_spec(self, shape) -> P:
        return param_partition_spec(shape, self.n_model_shards, self.model_axis)

    def boundary_specs(self, tree_like) -> P | object:
        if not self.multi_axis:
            return P()
        return jax.tree.map(
            lambda leaf: self._leaf_spec(np.shape(leaf)), tree_like
        )

    def gather_full(self, tree, specs):
        if not self.multi_axis:
            return tree

        return jax.tree.map(
            lambda x, spec: (
                x if model_spec_dim(spec, self.model_axis) is None
                else lax.all_gather(
                    x, self.model_axis,
                    axis=model_spec_dim(spec, self.model_axis), tiled=True,
                )
            ),
            tree, specs,
        )

    def slice_shard(self, tree):
        if not self.multi_axis:
            return tree

        def s(x):
            dim = model_spec_dim(self._leaf_spec(x.shape), self.model_axis)
            if dim is None:
                return x
            size = x.shape[dim] // self.n_model_shards
            return lax.dynamic_slice_in_dim(
                x, lax.axis_index(self.model_axis) * size, size, dim
            )

        return jax.tree.map(s, tree)


#: Back-compat alias: the 2-D FSDP-only layout object grew the client-axis
#: hierarchy and became :class:`MeshLayout`; existing imports keep working.
ModelAxisLayout = MeshLayout


def client_sharding(mesh: Mesh, axis_name: str = CLIENT_AXIS) -> NamedSharding:
    """Shard the leading (client) axis across the mesh — over ``clients`` alone
    on 1-D/2-D meshes, over ``(hosts, clients)`` jointly (hosts-major: each
    host's rows are contiguous) on the 3-axis mesh.  The remaining dims are
    unspecified, i.e. replicated over ``model`` — client data rides every model
    shard whole."""
    return NamedSharding(mesh, P(client_axes(mesh, axis_name)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def param_partition_spec(
    shape: tuple[int, ...], n_model_shards: int, model_axis: str = MODEL_AXIS
) -> P:
    """FSDP layout rule for ONE leaf: shard the largest dimension divisible by
    ``n_model_shards`` over the model axis; replicate leaves with no divisible
    dimension (scalars, odd-sized biases).  Ties pick the first largest dim.
    Pure shape arithmetic, so it works on traced values inside a jit as well as
    on concrete arrays.

    Only the LAST TWO dims of a rank>=3 leaf are candidates: the dims before them
    are stacking/window dims — scan-over-layers stacks the ``L`` transformer blocks
    into ``[L, ...]`` leaves, an expert layer's kernels are ``[layers, experts, d,
    f]``, conv kernels lead with window dims — and sharding one over the model axis
    would split ACROSS layers, experts or windows instead of within a matrix,
    forcing a gather inside every scan step (and an expert axis split this way is
    not expert parallelism: no token is exchanged).  The rule must stay
    pure-shape (``MeshLayout`` recomputes specs from ``x.shape`` inside traced
    code where no path information exists), so the exclusion keys on rank
    alone; a stacked rank-2 leaf (e.g. ``[L, D]`` layer-norm scales) can still
    shard over ``L`` if ``L`` is its largest divisible dim — harmless (the
    slice is still within one leaf) and unreachable for realistic configs
    where width >= depth."""
    if n_model_shards <= 1:
        return P()
    best_dim, best_size = -1, 0
    for i, d in enumerate(shape):
        if len(shape) >= 3 and i < len(shape) - 2:
            continue
        if d % n_model_shards == 0 and d > best_size:
            best_dim, best_size = i, int(d)
    if best_dim < 0:
        return P()
    return P(*([None] * best_dim + [model_axis]))


def param_sharding(
    mesh: Mesh, params, model_axis: str = MODEL_AXIS
):
    """Per-leaf ``NamedSharding`` pytree for params (or any params-shaped state,
    e.g. server optimizer state): each leaf's largest divisible dimension sharded
    over ``model``, replication as the per-leaf fallback.  On a 1-D mesh every
    leaf is replicated — identical to :func:`replicated_sharding`."""
    n = model_axis_size(mesh, model_axis)
    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, param_partition_spec(np.shape(leaf), n, model_axis)
        ),
        params,
    )


def shard_params(params, mesh: Mesh, model_axis: str = MODEL_AXIS):
    """Place params (or params-shaped state) on the mesh in the FSDP layout —
    the one host->device transfer for model state, mirroring
    :func:`shard_client_data` for data."""
    return jax.device_put(params, param_sharding(mesh, params, model_axis))


def pad_client_count(num_clients: int, n_devices: int) -> int:
    """Smallest multiple of ``n_devices`` >= ``num_clients``.  SPMD needs equal shards;
    padding clients carry zero weight so they are aggregation no-ops."""
    return ((num_clients + n_devices - 1) // n_devices) * n_devices


def pad_clients(data: ClientData, target: int) -> ClientData:
    """Pad the leading client axis to ``target`` with zero-mask (dummy) clients."""
    c = data.x.shape[0]
    if c == target:
        return data
    if c > target:
        raise ValueError(f"cannot pad {c} clients down to {target}")
    extra = target - c

    def pad(arr):
        widths = [(0, extra)] + [(0, 0)] * (arr.ndim - 1)
        return np.pad(np.asarray(arr), widths)

    return ClientData(x=pad(data.x), y=pad(data.y), mask=pad(data.mask))


def shard_client_data(data: ClientData, mesh: Mesh, axis_name: str = CLIENT_AXIS) -> ClientData:
    """Place ``ClientData`` on the mesh, client axis sharded.  This is the one
    host->device transfer per experiment (the reference re-serializes weights over HTTP
    every round; here training data goes to HBM once and stays).

    On a MULTI-PROCESS mesh every process must hold the full array for this to
    assemble the global placement (``make_array_from_callback``); prefer
    :func:`shard_host_local_data` there — each process materializes only its
    own rows (true per-host data sharding)."""
    sharding = client_sharding(mesh, axis_name)
    if jax.process_count() > 1:
        return jax.tree.map(
            lambda a: jax.make_array_from_callback(
                np.shape(a), sharding, lambda idx, _a=a: np.asarray(_a)[idx]
            ),
            data,
        )
    return jax.tree.map(lambda a: jax.device_put(a, sharding), data)


def host_client_slice(
    num_padded_clients: int, mesh: Mesh, axis_name: str = CLIENT_AXIS
) -> tuple[int, int]:
    """This PROCESS's contiguous row range ``[start, stop)`` of the padded
    client axis under :func:`client_sharding` — what per-host data loading
    materializes instead of the whole population.  Hosts-major sharding makes
    the range contiguous by construction; asserted anyway so a future layout
    change fails here, not as silent data corruption."""
    sharding = client_sharding(mesh, axis_name)
    index_map = sharding.addressable_devices_indices_map((num_padded_clients,))
    blocks = set()
    for idx in index_map.values():
        sl = idx[0]
        blocks.add((
            0 if sl.start is None else int(sl.start),
            num_padded_clients if sl.stop is None else int(sl.stop),
        ))
    start = min(s for s, _ in blocks)
    stop = max(e for _, e in blocks)
    # Contiguity: the distinct per-device blocks (model columns replicate rows,
    # hence the set) must tile [start, stop) exactly.
    if sum(e - s for s, e in blocks) != stop - start:
        raise ValueError(
            f"this process's client rows are not contiguous under the mesh "
            f"layout ({sorted(blocks)}) — hosts-axis rows must be whole "
            "processes (see make_mesh)"
        )
    return start, stop


def shard_host_local_data(
    local_data: ClientData,
    mesh: Mesh,
    num_padded_clients: int,
    axis_name: str = CLIENT_AXIS,
) -> ClientData:
    """Assemble globally-sharded ``ClientData`` from PER-PROCESS row blocks:
    each process passes only the rows :func:`host_client_slice` assigns it, and
    the result is the same global array :func:`shard_client_data` would build —
    without any host ever materializing the full population.  This is the
    per-host data-sharding path of a multi-process federation (100k+ clients
    never exist on one host).  Single-process it degenerates to
    :func:`shard_client_data` (the local slice IS the whole axis)."""
    sharding = client_sharding(mesh, axis_name)

    def put(a):
        a = np.asarray(a)
        global_shape = (num_padded_clients, *a.shape[1:])
        return jax.make_array_from_process_local_data(sharding, a, global_shape)

    return jax.tree.map(put, local_data)
