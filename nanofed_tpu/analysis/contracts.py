"""Runtime contracts for round programs — the dynamic half of fedlint.

Static analysis (``analysis.fedlint``) proves properties of the *source*;
these helpers prove properties of the *built program* without ever executing
it on data:

* :func:`check_round_step` / :func:`check_round_block` trace the compiled
  round program abstractly via ``jax.eval_shape`` and validate the execution
  contract the Coordinator relies on — output params/opt-state match the
  inputs leaf-for-leaf (structure, shape, dtype), metrics are scalars (or
  ``[R]`` stacks for a fused block), and per-client stacks carry the cohort
  width.  A drifted round program fails HERE, at build time, with a named
  leaf — not three layers deep inside a jit with an opaque pytree error.
* :func:`strict_mode` wraps dispatch in ``jax.transfer_guard("disallow")``:
  inside the context any *implicit* host<->device transfer raises, proving the
  fused hot path syncs only where the Coordinator says it does
  (``Coordinator(strict=True)`` / CLI ``--strict``).
* :func:`check_input_shardings` spot-checks the parallel layout: client data
  sharded over the client axis (and nothing else; jointly over
  ``(hosts, clients)`` on a 3-axis multi-host mesh), params replicated — or
  model-sharded per the FSDP layout on a mesh with a model axis; never client-
  or host-sharded.

Zero execution, zero compilation: ``eval_shape`` only traces, so strict
construction costs milliseconds even at the 1000-client flagship shape.
"""

from __future__ import annotations

import contextlib
from typing import Any, Iterator

import jax

from nanofed_tpu.core.exceptions import NanoFedError


class ContractViolation(NanoFedError):
    """A built round program does not satisfy the round-engine contract."""


def _leaves_with_paths(tree: Any) -> list[tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _spec(x: Any) -> tuple[tuple[int, ...], Any]:
    return tuple(x.shape), x.dtype


def _assert_tree_matches(got: Any, want: Any, what: str) -> None:
    """Leaf-for-leaf structure + shape + dtype equality, named on failure."""
    got_def = jax.tree_util.tree_structure(got)
    want_def = jax.tree_util.tree_structure(want)
    if got_def != want_def:
        raise ContractViolation(
            f"{what}: output tree structure {got_def} does not match the input "
            f"structure {want_def} — the round program must return {what} with "
            "the exact pytree it was given"
        )
    for (path, g), (_, w) in zip(_leaves_with_paths(got), _leaves_with_paths(want)):
        if _spec(g) != _spec(w):
            raise ContractViolation(
                f"{what}{path}: output is {g.dtype}{tuple(g.shape)} but the input "
                f"leaf is {w.dtype}{tuple(w.shape)} — a round program must be "
                "shape/dtype-stable or every block re-traces"
            )


def _assert_leading_dim(tree: Any, dim: int, what: str) -> None:
    for path, leaf in _leaves_with_paths(tree):
        if leaf.ndim < 1 or leaf.shape[0] != dim:
            raise ContractViolation(
                f"{what}{path}: expected leading dimension {dim}, got shape "
                f"{tuple(leaf.shape)}"
            )


def _abstract(tree: Any) -> Any:
    """ShapeDtypeStructs for concrete arrays; passes abstract values through."""
    return jax.tree.map(
        lambda x: x
        if isinstance(x, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(jax.numpy.shape(x), x.dtype),
        tree,
    )


def check_round_step(
    step: Any,
    params: Any,
    server_opt_state: Any,
    data: Any,
    weights: Any,
    rngs: Any,
    lr_scale: Any = 1.0,
    frozen_base: Any = None,
) -> dict[str, Any]:
    """Validate a ``build_round_step`` program against the round-engine contract.

    Traces ``step`` abstractly (``jax.eval_shape`` — nothing executes, nothing
    compiles) and checks:

    * ``result.params`` / ``result.server_opt_state`` match the input trees
      leaf-for-leaf (structure, shape, dtype) — the fixed point the Coordinator
      threads from round to round;
    * every entry of ``result.metrics`` is a scalar;
    * ``result.client_metrics`` / ``result.update_sq_norms`` carry the step's
      client width (``weights.shape[0]``) as their leading dimension.

    ``frozen_base`` (the frozen-base/adapter split, ``parallel.round_step.
    FrozenBase`` programs): the base tree enters the traced signature as the
    third argument but is DELIBERATELY absent from the fixed-point check —
    the base is read-only boundary data, not round state, and the program
    returns no base output for an equality to even anchor on.  ``params``
    is then the TRAINABLE (adapter) tree, and the fixed point covers exactly
    what the Coordinator threads from round to round.

    Returns a small report dict (checked leaf counts) for logging/tests;
    raises :class:`ContractViolation` with the offending leaf path otherwise.
    """
    n_clients = int(weights.shape[0])
    lr_abs = (
        jax.ShapeDtypeStruct((), jax.numpy.float32)
        if isinstance(lr_scale, (int, float)) else _abstract(lr_scale)
    )
    if frozen_base is not None:
        out = jax.eval_shape(
            step, _abstract(params), _abstract(server_opt_state),
            _abstract(frozen_base), _abstract(data), _abstract(weights),
            _abstract(rngs), lr_abs,
        )
    else:
        out = jax.eval_shape(
            step, _abstract(params), _abstract(server_opt_state),
            _abstract(data), _abstract(weights), _abstract(rngs), lr_abs,
        )
    _assert_tree_matches(out.params, _abstract(params), "params")
    _assert_tree_matches(
        out.server_opt_state, _abstract(server_opt_state), "server_opt_state"
    )
    for path, leaf in _leaves_with_paths(out.metrics):
        if tuple(leaf.shape) != ():
            raise ContractViolation(
                f"metrics{path}: round metrics must be weighted scalars, got "
                f"shape {tuple(leaf.shape)}"
            )
    _assert_leading_dim(out.client_metrics, n_clients, "client_metrics")
    _assert_leading_dim(out.update_sq_norms, n_clients, "update_sq_norms")
    return {
        "program": "round_step",
        "params_leaves": len(jax.tree.leaves(params)),
        "metrics": sorted(out.metrics),
        "clients": n_clients,
        **({"frozen_base_leaves": len(jax.tree.leaves(frozen_base))}
           if frozen_base is not None else {}),
    }


def check_round_block(
    block: Any,
    params: Any,
    server_opt_state: Any,
    data: Any,
    num_samples: Any,
    base_keys: Any,
    lr_scales: Any,
    cohort_idx: Any = None,
    cohort_mask: Any = None,
    frozen_base: Any = None,
) -> dict[str, Any]:
    """Validate a fused ``build_round_block`` program (R scanned rounds).

    Same contract as :func:`check_round_step`, lifted over the block: params /
    server state are a fixed point of the whole block, per-round metrics stack
    ``[R]``, survivors is an ``[R]`` integer vector, and the optional
    per-client detail stacks lead with R.  ``frozen_base`` is the adapter
    mode's read-only base (absent from the fixed point — see
    :func:`check_round_step`).  Raises :class:`ContractViolation` with the
    offending leaf path; returns a report dict.
    """
    rounds = int(base_keys.shape[0])
    args = [
        _abstract(params), _abstract(server_opt_state), _abstract(data),
        _abstract(num_samples), _abstract(base_keys), _abstract(lr_scales),
        None if cohort_idx is None else _abstract(cohort_idx),
        None if cohort_mask is None else _abstract(cohort_mask),
        None if frozen_base is None else _abstract(frozen_base),
    ]
    out = jax.eval_shape(block, *args)
    _assert_tree_matches(out.params, _abstract(params), "params")
    _assert_tree_matches(
        out.server_opt_state, _abstract(server_opt_state), "server_opt_state"
    )
    _assert_leading_dim(out.metrics, rounds, "metrics")
    if tuple(out.survivors.shape) != (rounds,):
        raise ContractViolation(
            f"survivors: expected shape ({rounds},), got {tuple(out.survivors.shape)}"
        )
    if not jax.numpy.issubdtype(out.survivors.dtype, jax.numpy.integer):
        raise ContractViolation(
            f"survivors: expected an integer dtype, got {out.survivors.dtype}"
        )
    for name in ("client_metrics", "update_sq_norms", "weights", "cohort_ids"):
        detail = getattr(out, name)
        if detail is not None:
            _assert_leading_dim(detail, rounds, name)
    return {
        "program": "round_block",
        "rounds": rounds,
        "params_leaves": len(jax.tree.leaves(params)),
        "metrics": sorted(out.metrics),
        "client_detail": out.client_metrics is not None,
    }


def _spec_axes(entry: Any) -> tuple:
    """Mesh axes a single PartitionSpec entry shards over (an entry is None, an
    axis name, or a tuple of axis names)."""
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def check_input_shardings(
    data: Any,
    params: Any,
    axis_name: str = "clients",
    model_axis: str = "model",
    host_axis: str = "hosts",
    base_params: Any = None,
) -> None:
    """Spot-check the parallel layout on CONCRETE inputs.

    Client data: every leaf sharded over ``axis_name`` in its leading dimension
    — or over ``(host_axis, axis_name)`` jointly, hosts-major, on a 3-axis
    ``hosts x clients x model`` mesh (per-host data sharding) — and over
    nothing else in the trailing ones (replicated over ``model``: every model
    column holds its clients whole).  A leading dim sharded over ``hosts``
    alone, ``(clients, hosts)`` inverted, or any mix with ``model`` is
    rejected.

    Params (and any params-shaped state): every leaf either fully replicated
    (the 1-D layout) or sharded ONLY over ``model_axis`` (the FSDP layout —
    at most one sharded dimension, never the client OR hosts axis: a client-
    sharded param leaf would make every client train a different slice of the
    model, and a host-sharded one would desynchronize the global model across
    hosts — the exact failure hierarchical aggregation exists to prevent).

    ``base_params`` (adapter mode's frozen base) is audited with the SAME rule
    as params: the frozen-base + trainable-adapter split changes what enters
    the fixed point, not what layouts are legal — a client-sharded adapter (or
    base) leaf would make every client train a different slice of the model
    and is rejected identically.

    Leaves that carry no ``NamedSharding`` (host arrays, abstract values,
    single-device placements) are skipped — this is a layout audit, not a
    placement requirement."""
    from jax.sharding import NamedSharding

    lead_ok = (
        (axis_name,),  # 1-D / 2-D: clients alone
        (host_axis, axis_name),  # 3-axis: hosts-major joint sharding
    )
    for path, leaf in _leaves_with_paths(data):
        sharding = getattr(leaf, "sharding", None)
        if not isinstance(sharding, NamedSharding):
            continue
        spec = sharding.spec
        if len(spec) == 0 or _spec_axes(spec[0]) not in lead_ok:
            raise ContractViolation(
                f"data{path}: expected leading-axis sharding over {axis_name!r} "
                f"(or ({host_axis!r}, {axis_name!r}) jointly on a 3-axis mesh), "
                f"got spec {spec} — the round program shards clients over the "
                "mesh, hosts-major"
            )
        for entry in tuple(spec)[1:]:
            if _spec_axes(entry):
                raise ContractViolation(
                    f"data{path}: trailing dimensions must be replicated (got "
                    f"spec {spec}) — a client's batch rides each model column "
                    "whole"
                )

    def _audit_model_state(tree: Any, what: str) -> None:
        for path, leaf in _leaves_with_paths(tree):
            sharding = getattr(leaf, "sharding", None)
            if not isinstance(sharding, NamedSharding):
                continue
            if sharding.is_fully_replicated:
                continue
            sharded_axes = [
                a for entry in sharding.spec for a in _spec_axes(entry)
            ]
            if any(a != model_axis for a in sharded_axes) or len(sharded_axes) > 1:
                raise ContractViolation(
                    f"{what}{path}: expected replicated placement or a single "
                    f"dimension sharded over {model_axis!r}, got spec "
                    f"{sharding.spec} — model state rides every device whole "
                    "(1-D) or split over the model axis only (FSDP layout); "
                    "client- or host-sharded model state is never valid"
                )

    _audit_model_state(params, "params")
    if base_params is not None:
        _audit_model_state(base_params, "base_params")


@contextlib.contextmanager
def strict_mode() -> Iterator[None]:
    """Disallow IMPLICIT host<->device transfers for the enclosed dispatch.

    Inside the context, any HOST transfer JAX would perform silently — a numpy
    array or Python scalar implicitly uploaded into a jit call, a traced value
    concretized by ``float()``/``np.asarray``, a device array pulled back by
    ``__array__`` — raises instead of degrading throughput.  Explicit
    ``jax.device_put`` / ``jax.device_get`` remain allowed: strict mode proves
    the hot path syncs only where it SAYS it does, not that it never syncs.
    Device-to-device transfers stay permitted — resharding a device array onto
    the mesh is layout work on the fast path (ICI), not a host sync.

    This is the runtime enforcement of fedlint FED001: the linter catches the
    sites it can see statically; the guard catches everything else at dispatch.
    """
    with jax.transfer_guard_host_to_device("disallow"), \
            jax.transfer_guard_device_to_host("disallow"):
        yield
