"""Zero-execution audit of compiled round programs (jaxpr + AOT artifacts).

``fedlint`` (:mod:`nanofed_tpu.analysis.fedlint`) reads SOURCE; this module
reads the PROGRAM.  :func:`audit_program` traces a round program to its closed
jaxpr (and, when the callable exposes ``.lower``, compiles it AOT — persistent-
cache-cheap) and verifies five properties that source-level linting cannot see:

``collective-schedule``
    The ordered psum/pmean/all_gather sequence is extracted per program, and
    inside every ``lax.cond``/``switch`` the branch schedules must be
    IDENTICAL.  A branch-divergent collective is the classic SPMD deadlock —
    the watchdog (PR 13) catches it at runtime after a 30s gloo hang; here it
    is a finding before anything runs.

``mesh-discipline``
    Every collective axis name must be a declared mesh axis, host-axis reduces
    may appear only after a clients-axis reduce (hierarchical order:
    innermost first), and the cross-host collective traffic of a round must
    fit one model-sized tensor (the ROADMAP item-1 invariant, measured against
    the program's own output bytes).

``donation``
    Args the builder declares donated must actually alias in the compiled
    program's ``memory_analysis`` — the compiled truth behind FED004.  A
    donation XLA cannot honor (dtype/shape mismatch between the donated input
    and every output) silently costs a params-sized HBM copy per round.

``dtype-drift``
    No silent f32/f64 upcast of a bf16 input leaf, and no float cast of an
    integer input (token ids) inside the program.  Only casts applied DIRECTLY
    to program inputs are flagged — internal mixed-precision accumulation is
    the trainer's business.

``host-transfer``
    No callbacks / infeed / outfeed embedded in the traced program: a host
    round-trip inside the round body serializes every device step behind
    Python.

What the auditor cannot see: runtime values (a schedule that diverges on DATA
rather than trace structure), cross-PROGRAM ordering (it audits one program at
a time), and anything jit never traces (host-side orchestration — fedlint's
half of the contract).  Findings are returned, never raised; callers decide
severity (``Coordinator(strict=True)`` raises, the CLI exits 1).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from nanofed_tpu.parallel.mesh import CLIENT_AXIS, HOST_AXIS

__all__ = [
    "AUDIT_CHECKS",
    "AuditFinding",
    "AuditReport",
    "audit_program",
    "format_audit_reports",
    "reference_catalog",
    "run_mutation_suite",
    "seeded_mutants",
]

# Every check the auditor runs; ``donation`` needs the AOT compile and is
# skipped (reported via AuditReport.checks) for callables without ``.lower``.
AUDIT_CHECKS = (
    "collective-schedule",
    "mesh-discipline",
    "donation",
    "dtype-drift",
    "host-transfer",
)

# Cross-device collective primitives as they appear in jaxprs.  pmean lowers
# to psum + divide, so schedules are psum-normal; axis names live in the
# ``axes`` param for the reduce family and ``axis_name`` for the gather family.
_COLLECTIVE_PRIMS = frozenset({
    # psum_invariant is psum under shard_map's replication checker (the form
    # 1-D check_vma=True bodies carry); pvary is deliberately absent — it
    # adjusts replication bookkeeping, it moves no bytes.
    "psum", "psum_invariant", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute",
    "reduce_scatter", "psum_scatter", "pgather",
})

# Primitives that embed a host round-trip in the device program.  Callback
# primitives are matched by substring ("debug_callback", "pure_callback",
# "io_callback") so new flavors stay covered.
_HOST_TRANSFER_PRIMS = frozenset({"infeed", "outfeed"})

# Cross-host traffic slack: the budget is the program's own output bytes
# (the aggregate IS model-sized state) times this, plus a constant floor so
# scalar-output probes are not flagged for reducing a handful of metrics.
_CROSS_HOST_SLACK = 1.05
_CROSS_HOST_FLOOR_BYTES = 4096


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    """One violated property of one program."""

    program: str
    check: str
    message: str

    def render(self) -> str:
        return f"{self.program}: [{self.check}] {self.message}"

    def to_dict(self) -> dict[str, str]:
        return {"program": self.program, "check": self.check,
                "message": self.message}


@dataclasses.dataclass(frozen=True)
class AuditReport:
    """Everything one program's audit established.

    ``schedule`` is the flattened collective schedule (``"psum@clients"``
    entries, branch-representative under ``cond``); ``checks`` lists the
    checks that actually ran (``donation`` drops out for non-lowerable
    callables); ``compiled`` says whether the AOT artifact was inspected.
    """

    program: str
    findings: tuple[AuditFinding, ...]
    schedule: tuple[str, ...]
    mesh_axes: tuple[str, ...]
    checks: tuple[str, ...]
    compiled: bool
    attrs: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict[str, Any]:
        return {
            "program": self.program,
            "ok": self.ok,
            "findings": [f.to_dict() for f in self.findings],
            "schedule": list(self.schedule),
            "mesh_axes": list(self.mesh_axes),
            "checks": list(self.checks),
            "compiled": self.compiled,
            "attrs": dict(self.attrs),
        }


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _inner_jaxprs(params: dict[str, Any]) -> Iterator[Any]:
    """Every sub-jaxpr in an eqn's params (pjit/scan/shard_map/custom_*),
    EXCLUDING cond branches — those get schedule-compared, not flattened."""
    for key, val in params.items():
        if key == "branches":
            continue
        for sub in _jaxprs_in(val):
            yield sub


def _jaxprs_in(val: Any) -> Iterator[Any]:
    if isinstance(val, jex_core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex_core.Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _jaxprs_in(item)


def _axes_of(eqn: Any) -> tuple[Any, ...]:
    """Collective axis names, normalized to a tuple (strings for named mesh
    axes; positional ints pass through and are ignored by the mesh checks)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(axes)


def _aval_bytes(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(math.prod(shape)) * int(np.dtype(dtype).itemsize)


@dataclasses.dataclass
class _Schedule:
    """One program's collective schedule: ``(prim, axes, operand_bytes)`` in
    trace order, flattened through every sub-jaxpr."""

    entries: list[tuple[str, tuple[Any, ...], int]] = dataclasses.field(
        default_factory=list
    )
    mesh_axes: set[str] = dataclasses.field(default_factory=set)
    branch_mismatches: list[str] = dataclasses.field(default_factory=list)
    host_transfers: list[str] = dataclasses.field(default_factory=list)

    def render(self) -> tuple[str, ...]:
        return tuple(
            f"{prim}@{','.join(str(a) for a in axes) or '-'}"
            for prim, axes, _ in self.entries
        )


def _walk_schedule(jaxpr: Any, sched: _Schedule) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in _COLLECTIVE_PRIMS:
            op_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars)
            sched.entries.append((prim, _axes_of(eqn), op_bytes))
            continue
        if prim in _HOST_TRANSFER_PRIMS or "callback" in prim:
            sched.host_transfers.append(prim)
            continue
        mesh = eqn.params.get("mesh")
        if mesh is not None and hasattr(mesh, "axis_names"):
            sched.mesh_axes.update(
                a for a in mesh.axis_names if isinstance(a, str)
            )
        if prim == "cond":
            branches = eqn.params.get("branches", ())
            branch_scheds: list[_Schedule] = []
            for br in branches:
                bs = _Schedule()
                for sub in _jaxprs_in(br):
                    _walk_schedule(sub, bs)
                branch_scheds.append(bs)
            if branch_scheds:
                ref = [(p, a) for p, a, _ in branch_scheds[0].entries]
                for i, bs in enumerate(branch_scheds[1:], start=1):
                    got = [(p, a) for p, a, _ in bs.entries]
                    if got != ref:
                        sched.branch_mismatches.append(
                            f"cond branch 0 runs {_fmt_entries(ref)} but "
                            f"branch {i} runs {_fmt_entries(got)} — SPMD "
                            "divergence deadlocks the mesh at runtime"
                        )
                # Branch-representative entries keep outer ordering intact
                # (identical across branches when the check passes).
                for bs in branch_scheds[:1]:
                    sched.entries.extend(bs.entries)
                    sched.mesh_axes.update(bs.mesh_axes)
                    sched.host_transfers.extend(bs.host_transfers)
                    sched.branch_mismatches.extend(bs.branch_mismatches)
            continue
        for sub in _inner_jaxprs(eqn.params):
            _walk_schedule(sub, sched)


def _fmt_entries(entries: list[tuple[str, tuple[Any, ...]]]) -> str:
    if not entries:
        return "[no collectives]"
    return "[" + ", ".join(
        f"{p}@{','.join(str(a) for a in axes) or '-'}" for p, axes in entries
    ) + "]"


# ---------------------------------------------------------------------------
# dtype-drift: casts applied directly to program inputs
# ---------------------------------------------------------------------------

def _walk_dtype_drift(
    jaxpr: Any, tracked: set[Any], program: str,
    findings: list[AuditFinding],
) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "convert_element_type":
            var = eqn.invars[0]
            if not isinstance(var, jex_core.Literal) and var in tracked:
                old = np.dtype(var.aval.dtype)
                new = np.dtype(eqn.params["new_dtype"])
                if old == np.dtype(jnp.bfloat16) and new in (
                    np.dtype(np.float32), np.dtype(np.float64)
                ):
                    findings.append(AuditFinding(
                        program, "dtype-drift",
                        f"bf16 input upcast to {new.name} inside the program "
                        "— the boundary dtype is a contract; upcasting "
                        "silently doubles collective bytes",
                    ))
                elif (
                    np.issubdtype(old, np.integer)
                    and np.issubdtype(new, np.inexact)
                ):
                    findings.append(AuditFinding(
                        program, "dtype-drift",
                        f"integer input ({old.name}, token-id shaped) cast to "
                        f"{new.name} inside the program — ids must stay "
                        "integral across the boundary",
                    ))
            continue
        sub_jaxprs = list(_inner_jaxprs(eqn.params))
        if prim == "cond":
            operands = eqn.invars[1:]
            for br in eqn.params.get("branches", ()):
                for sub in _jaxprs_in(br):
                    inner = set()
                    for outer_v, inner_v in zip(operands, sub.invars):
                        if not isinstance(outer_v, jex_core.Literal) \
                                and outer_v in tracked:
                            inner.add(inner_v)
                    _walk_dtype_drift(sub, inner, program, findings)
        elif sub_jaxprs:
            for sub in sub_jaxprs:
                n = len(sub.invars)
                operands = eqn.invars[-n:] if n else []
                inner = set()
                for outer_v, inner_v in zip(operands, sub.invars):
                    if not isinstance(outer_v, jex_core.Literal) \
                            and outer_v in tracked:
                        inner.add(inner_v)
                _walk_dtype_drift(sub, inner, program, findings)


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

def audit_program(
    name: str,
    fn: Callable,
    *args: Any,
    rounds: int = 1,
    mesh: Any = None,
    compile: bool = True,
    attrs: dict[str, Any] | None = None,
    **kwargs: Any,
) -> AuditReport:
    """Audit one program against the five checks; see the module docstring.

    ``fn`` follows the profiler's contract: the jit callable is ``fn`` itself
    or its ``fn.jit_program``.  ``args``/``kwargs`` are dispatch-shaped
    arguments (values never execute).  ``mesh`` pins the declared axes; when
    omitted they are harvested from the program's own ``shard_map`` eqns (a
    program with neither skips the axis-declaration subcheck).  ``compile=True``
    additionally runs the AOT ``lower().compile()`` to verify donation against
    ``memory_analysis`` — cheap under the persistent compile cache; set False
    for a trace-only audit (construction-time strict mode).
    """
    jit_fn = getattr(fn, "jit_program", fn)
    closed = jax.make_jaxpr(jit_fn)(*args, **kwargs)
    findings: list[AuditFinding] = []

    sched = _Schedule()
    _walk_schedule(closed.jaxpr, sched)

    # -- collective-schedule: branch divergence ---------------------------
    for msg in sched.branch_mismatches:
        findings.append(AuditFinding(name, "collective-schedule", msg))

    # -- mesh-discipline ---------------------------------------------------
    declared_axes: tuple[str, ...]
    if mesh is not None:
        declared_axes = tuple(str(a) for a in mesh.axis_names)
    else:
        declared_axes = tuple(sorted(sched.mesh_axes))
    if declared_axes:
        for prim, axes, _ in sched.entries:
            unknown = [
                a for a in axes if isinstance(a, str) and a not in declared_axes
            ]
            if unknown:
                findings.append(AuditFinding(
                    name, "mesh-discipline",
                    f"{prim} reduces over undeclared axis "
                    f"{', '.join(map(repr, unknown))} (mesh declares "
                    f"{list(declared_axes)})",
                ))
    if HOST_AXIS in declared_axes:
        saw_client_reduce = False
        hierarchy_flagged = False
        for prim, axes, _ in sched.entries:
            if CLIENT_AXIS in axes:
                saw_client_reduce = True
            if HOST_AXIS in axes and not saw_client_reduce \
                    and not hierarchy_flagged:
                findings.append(AuditFinding(
                    name, "mesh-discipline",
                    f"{prim} over the {HOST_AXIS!r} axis before any "
                    f"{CLIENT_AXIS!r}-axis reduce — hierarchical order is "
                    "innermost first: cross-host wires carry pre-reduced "
                    "aggregates, never raw client traffic",
                ))
                hierarchy_flagged = True
        cross_host_bytes = sum(
            op_bytes for _, axes, op_bytes in sched.entries
            if HOST_AXIS in axes
        )
        out_bytes = sum(_aval_bytes(v) for v in closed.out_avals)
        budget = int(
            out_bytes / max(1, rounds) * _CROSS_HOST_SLACK
            + _CROSS_HOST_FLOOR_BYTES
        ) * max(1, rounds)
        if cross_host_bytes > budget:
            findings.append(AuditFinding(
                name, "mesh-discipline",
                f"cross-host collectives move {cross_host_bytes} bytes but "
                f"the round's model-sized budget is {budget} (one aggregate "
                "per round; see ROADMAP item 1) — an extra model-sized "
                "tensor is crossing the slow wire",
            ))

    # -- host-transfer -----------------------------------------------------
    for prim in sorted(set(sched.host_transfers)):
        n_occurrences = sched.host_transfers.count(prim)
        findings.append(AuditFinding(
            name, "host-transfer",
            f"{prim} embedded in the traced program "
            f"({n_occurrences}x) — a host round-trip inside the round body "
            "serializes every device step behind Python",
        ))

    # -- dtype-drift -------------------------------------------------------
    _walk_dtype_drift(
        closed.jaxpr, set(closed.jaxpr.invars), name, findings
    )

    # -- donation (AOT) ----------------------------------------------------
    checks = list(AUDIT_CHECKS)
    compiled_ok = False
    if compile and hasattr(jit_fn, "lower"):
        # The audit reports unusable donations as findings; jax's own warning
        # for the same condition would print once per mutant run on top.
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            lowered = jit_fn.lower(*args, **kwargs)
        donated_bytes = sum(
            _aval_bytes(getattr(info, "aval", getattr(info, "_aval", None)))
            for info in jax.tree_util.tree_leaves(lowered.args_info)
            if getattr(info, "donated", False)
        )
        compiled = lowered.compile()
        alias_bytes = None
        try:
            mem = compiled.memory_analysis()
            if mem is not None:
                alias_bytes = int(getattr(mem, "alias_size_in_bytes"))
        except Exception:
            alias_bytes = None
        if donated_bytes > 0 and alias_bytes == 0:
            findings.append(AuditFinding(
                name, "donation",
                f"builder declares {donated_bytes} donated bytes but the "
                "compiled program aliases 0 — XLA could not honor the "
                "donation (output dtype/shape mismatch?), so every round "
                "pays a full params-sized HBM copy",
            ))
        compiled_ok = True
    else:
        checks.remove("donation")

    return AuditReport(
        program=name,
        findings=tuple(findings),
        schedule=sched.render(),
        mesh_axes=declared_axes,
        checks=tuple(checks),
        compiled=compiled_ok,
        attrs=dict(attrs or {}),
    )


def format_audit_reports(reports: Iterable[AuditReport]) -> str:
    """Human-readable audit table + findings (what ``nanofed-tpu audit``
    prints)."""
    reports = list(reports)
    lines = []
    rows = [("program", "checks", "collectives", "mesh axes", "status")]
    for r in reports:
        rows.append((
            r.program,
            str(len(r.checks)) + ("" if r.compiled else " (trace-only)"),
            str(len(r.schedule)),
            ",".join(r.mesh_axes) or "-",
            "ok" if r.ok else f"{len(r.findings)} finding(s)",
        ))
    widths = [max(len(row[i]) for row in rows) for i in range(len(rows[0]))]
    for j, row in enumerate(rows):
        lines.append(
            "  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip()
        )
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    for r in reports:
        for f in r.findings:
            lines.append(f.render())
    total = sum(len(r.findings) for r in reports)
    lines.append(
        "audit: clean" if total == 0 else f"audit: {total} finding(s)"
    )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# reference catalog: the six program variants on tiny models
# ---------------------------------------------------------------------------

def reference_catalog():
    """A :class:`~nanofed_tpu.observability.profiling.ProgramCatalog` holding
    the six round-program variants on tiny models — single-step, fused-block,
    SCAFFOLD, 2-D FSDP, 3-axis hierarchical, and adapter/FrozenBase — built
    through real ``Coordinator`` constructions so every registered program is
    the dispatch-true one.  Needs 8 devices (the standard CPU test topology).
    Registration is lazy; nothing compiles until ``audit``/``profile``.
    """
    from nanofed_tpu.adapters import AdapterSpec
    from nanofed_tpu.data import (
        federate, synthetic_classification, synthetic_token_streams,
    )
    from nanofed_tpu.models import get_model
    from nanofed_tpu.observability.profiling import ProgramCatalog
    from nanofed_tpu.orchestration import Coordinator, CoordinatorConfig
    from nanofed_tpu.trainer import TrainingConfig

    def _mlp_data(num_clients=8):
        ds = synthetic_classification(256, 3, (8,), seed=0)
        return federate(ds, num_clients=num_clients, scheme="iid",
                        batch_size=16)

    training = TrainingConfig(batch_size=16, local_epochs=1, learning_rate=0.1)

    def _coord(**kw):
        rpb = kw.pop("rounds_per_block", 1)
        return Coordinator(
            model=kw.pop("model", None)
            or get_model("mlp", in_features=8, hidden=16, num_classes=3),
            train_data=kw.pop("train_data", None) or _mlp_data(),
            config=CoordinatorConfig(
                num_rounds=max(1, rpb), rounds_per_block=rpb,
                seed=0, save_metrics=False,
            ),
            training=kw.pop("training", training),
            **kw,
        )

    lm = get_model("transformer_lm", vocab=32, seq_len=8, width=16, depth=1,
                   heads=2)
    lm_data = federate(
        synthetic_token_streams(256, vocab=32, seq_len=8, seed=0),
        num_clients=8, batch_size=16, seed=0,
    )

    variants = [
        # (variant label, coordinator, program-name -> variant-name map)
        ("fused", _coord(rounds_per_block=2),
         {"round_step": "single_step", "round_block": "fused_block"}),
        ("scaffold", _coord(scaffold=True), {"scaffold_round_step": "scaffold"}),
        ("fsdp_2d", _coord(mesh_shape=(4, 2)), {"round_step": "fsdp_2d"}),
        ("hier_3axis", _coord(mesh_shape=(2, 2, 2)),
         {"round_step": "hier_3axis"}),
        ("adapter", _coord(model=lm, train_data=lm_data,
                           adapter=AdapterSpec(rank=2)),
         {"adapter_round_step": "adapter"}),
    ]

    catalog = ProgramCatalog()
    for label, coord, names in variants:
        for prog in coord.program_catalog.names():
            fn, factory, rounds, attrs = coord.program_catalog.registration(prog)
            variant = names.get(prog, f"{label}/{prog}")
            catalog.register(
                variant, fn,
                args_factory=factory, rounds=rounds,
                attrs={**attrs, "variant": variant, "source_program": prog,
                       "mesh": coord.mesh},
            )

    # The wire→mesh bridge's fused drained-ingest reduce (ingest slabs →
    # host-local `coefs @ buf` → ONE hosts psum of the [P+1] row → FedAvg
    # apply).  Registered dispatch-shaped so the mesh-discipline check — the
    # clients reduce must close before the hosts reduce, and exactly one
    # model-sized cross-host tensor may move per round — machine-checks the
    # fusion invariant on every `nanofed-tpu audit`.
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PSpec

    from nanofed_tpu.communication.federation import (
        build_drained_ingest_reduce,
    )
    from nanofed_tpu.parallel.mesh import make_mesh, replicated_sharding

    ingest_mesh = make_mesh(shape=(2, 2, 2))
    ingest_cap, ingest_flat = 4, 96
    drained = build_drained_ingest_reduce(ingest_mesh, ingest_cap, ingest_flat)

    def _drained_args():
        shards = int(
            ingest_mesh.shape[HOST_AXIS] * ingest_mesh.shape[CLIENT_AXIS]
        )
        spec = NamedSharding(ingest_mesh, PSpec((HOST_AXIS, CLIENT_AXIS)))
        rng = np.random.default_rng(0)
        buf = jax.device_put(
            rng.normal(size=(shards, ingest_cap, ingest_flat)).astype(
                np.float32
            ),
            spec,
        )
        coefs = jax.device_put(
            np.abs(rng.normal(size=(shards, ingest_cap))).astype(np.float32),
            spec,
        )
        base = jax.device_put(
            np.zeros(ingest_flat, np.float32),
            replicated_sharding(ingest_mesh),
        )
        return (buf, coefs, base), {}

    catalog.register(
        "drained_ingest", drained,
        args_factory=_drained_args, rounds=1,
        attrs={"variant": "drained_ingest",
               "source_program": "drained_ingest_reduce",
               "mesh": ingest_mesh},
    )
    return catalog


# ---------------------------------------------------------------------------
# seeded mutants: one deliberately-broken program per check
# ---------------------------------------------------------------------------

def seeded_mutants() -> list[tuple[str, str, Callable, tuple]]:
    """One deliberately-broken tiny program per audit check, as
    ``(name, expected_check, fn, args)`` rows.  The mutation suite
    (:func:`run_mutation_suite`, ``make audit-smoke``, and the unit tests)
    audits each and asserts EXACTLY its check fires — proof that no check is
    vacuous.  Needs 8 devices (the mesh mutants build a (2, 2, 2) mesh).
    """
    from functools import partial

    from jax import lax
    from jax.sharding import PartitionSpec as P

    from nanofed_tpu.parallel.mesh import (
        make_mesh, multi_axis_shard_map_kwargs, shard_map,
    )

    mesh = make_mesh(shape=(2, 2, 2))
    smap_kw = multi_axis_shard_map_kwargs(mesh)
    spec = P(None)

    # (1) collective-schedule: cond branches with different collectives —
    # one host psums over clients, the other computes locally.
    @jax.jit
    def cond_divergent(x, pred):
        def body(x, pred):
            return lax.cond(
                pred,
                lambda v: lax.psum(v, CLIENT_AXIS),
                lambda v: v * 2.0,
                x,
            )
        return shard_map(
            body, mesh=mesh, in_specs=(spec, P()), out_specs=spec, **smap_kw
        )(x, pred)

    # (2) mesh-discipline: a hosts-axis reduce with NO clients-axis reduce
    # before it — raw client traffic on the cross-host wire.
    @jax.jit
    def hosts_first(x):
        def body(x):
            return lax.psum(x, HOST_AXIS)
        return shard_map(
            body, mesh=mesh, in_specs=(spec,), out_specs=spec, **smap_kw
        )(x)

    # (3) donation: declared donated input whose dtype matches no output —
    # XLA cannot alias it, so memory_analysis reports 0 aliased bytes.
    @partial(jax.jit, donate_argnums=(0,))
    def dropped_donation(x):
        return x.astype(jnp.bfloat16)

    # (4) dtype-drift: bf16 input silently upcast to f32 inside the program.
    @jax.jit
    def upcast_leaf(p):
        return (p.astype(jnp.float32) * 2.0).sum()

    # (5) host-transfer: a debug callback embedded in the traced program.
    @jax.jit
    def embedded_callback(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2.0

    x32 = jnp.zeros((8, 4), jnp.float32)
    return [
        ("mutant_cond_divergent", "collective-schedule", cond_divergent,
         (x32, jnp.array(True))),
        ("mutant_hosts_first", "mesh-discipline", hosts_first, (x32,)),
        ("mutant_dropped_donation", "donation", dropped_donation,
         (jnp.zeros((64,), jnp.float32),)),
        ("mutant_upcast_leaf", "dtype-drift", upcast_leaf,
         (jnp.zeros((8,), jnp.bfloat16),)),
        ("mutant_embedded_callback", "host-transfer", embedded_callback,
         (x32,)),
    ]


def run_mutation_suite() -> dict[str, dict[str, Any]]:
    """Audit every seeded mutant; returns ``name -> {expected, fired, ok}``
    where ``ok`` means the mutant fired EXACTLY its expected check."""
    results: dict[str, dict[str, Any]] = {}
    for name, expected, fn, args in seeded_mutants():
        report = audit_program(name, fn, *args)
        fired = sorted({f.check for f in report.findings})
        results[name] = {
            "expected": expected,
            "fired": fired,
            "ok": fired == [expected],
        }
    return results
