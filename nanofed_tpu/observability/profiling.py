"""Compiled-program cost profiling: what the COMPILER says a round program costs.

What the program itself measures about its hot path is wall-clock — span durations,
round times.  A wall-clock number cannot say whether a round is compute-bound or
HBM-bound.  FedJAX (arXiv:2108.02117) reports only rounds/sec; Flower/NVFLARE-class
systems (arXiv:2407.00031) stop at run-level metrics — none of them ask the compiler.

This module asks the compiler.  Every round program the framework builds — single
step, fused R-round block, SCAFFOLD, on 1-D and 2-D meshes — is a ``jax.jit``
callable whose AOT path (``.lower(...).compile()``) yields XLA's own
``cost_analysis()`` (FLOPs, bytes accessed, transcendentals) and
``memory_analysis()`` (argument / output / temp / peak device bytes).  A
:class:`ProgramCostReport` pairs those with a per-platform peaks table (bf16 peak
FLOP/s + HBM bandwidth) into a roofline verdict: arithmetic intensity vs the ridge
point, compute-bound vs HBM-bound, and the achievable lower-bound walltime.
Pairing a report with a MEASURED walltime yields compiler-FLOPs MFU.

:class:`ProgramCatalog` is the integration point: the ``Coordinator`` registers
every program it builds (registration is free — no compile), and ``profile()``
compiles + extracts on demand, publishing ``nanofed_program_*`` gauges and a
compile-time (time-to-ready) histogram into the metrics registry.  The ``profile``
CLI subcommand drives the same path without running a federation.

Numbers are PER-DEVICE: XLA reports the cost of the SPMD module each device runs
(the per-device program), which is exactly the basis a per-chip peak wants.  A
fused R-round block's numbers cover all R rounds — divide by R for per-round
comparisons (the CLI table does, and says so).

Profiling compiles.  ``jit``'s call-site executable cache is NOT shared with the
AOT path on this JAX version, so profiling an already-run program pays a second
XLA compile — unless the persistent compilation cache is enabled
(``utils.platform.enable_compilation_cache``), which makes the second compile a
disk hit.  That is why ``Coordinator`` profiling is opt-in
(``profile_programs=True`` / ``--profile-programs``) rather than always-on.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Mapping, NamedTuple

from nanofed_tpu.observability.registry import MetricsRegistry, get_registry

#: Gauge/histogram names (the metric inventory in docs/observability.md).
PROGRAM_FLOPS_GAUGE = "nanofed_program_flops_total"
PROGRAM_PEAK_BYTES_GAUGE = "nanofed_program_peak_bytes"
PROGRAM_BYTES_ACCESSED_GAUGE = "nanofed_program_bytes_accessed"
PROGRAM_INTENSITY_GAUGE = "nanofed_program_arithmetic_intensity"
PROGRAM_COMPILE_HISTOGRAM = "nanofed_program_compile_seconds"
DEVICE_OCCUPANCY_GAUGE = "nanofed_device_occupancy_ratio"

#: Buckets for time-to-ready: XLA compiles span ~100 ms (tiny test programs) to
#: several minutes (the flagship block on a 1-core host).
COMPILE_BUCKETS: tuple[float, ...] = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
)


class PlatformPeaks(NamedTuple):
    """Per-chip peak throughputs the roofline is drawn against."""

    flops_per_s: float  # peak matmul FLOP/s at the training compute dtype (bf16)
    hbm_bytes_per_s: float  # peak HBM bandwidth
    basis: str  # where the numbers come from (device kind + dtype)


#: Published per-chip peaks, matched against ``device.device_kind`` SUBSTRINGS
#: (most specific first — "v5 lite" must win before a bare "v5").  bf16 basis
#: throughout: it is the benchmark compute dtype.  CPU (and any unlisted device)
#: deliberately has NO entry — a made-up peak would make the roofline verdict a
#: fabrication, so those reports say "no peak basis" instead.
TPU_PEAKS: tuple[tuple[str, PlatformPeaks], ...] = (
    ("v5 lite", PlatformPeaks(197e12, 819e9, "TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM")),
    ("v5e", PlatformPeaks(197e12, 819e9, "TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM")),
    ("v6 lite", PlatformPeaks(918e12, 1640e9, "TPU v6e: 918 TFLOP/s bf16, 1640 GB/s HBM")),
    ("v6e", PlatformPeaks(918e12, 1640e9, "TPU v6e: 918 TFLOP/s bf16, 1640 GB/s HBM")),
    ("v5p", PlatformPeaks(459e12, 2765e9, "TPU v5p: 459 TFLOP/s bf16, 2765 GB/s HBM")),
    ("v4", PlatformPeaks(275e12, 1228e9, "TPU v4: 275 TFLOP/s bf16, 1228 GB/s HBM")),
)


def peaks_for_device_kind(device_kind: str, platform: str) -> PlatformPeaks | None:
    """The peaks row for a device, or None when there is no published basis
    (CPU, unknown TPU generations, GPUs)."""
    if platform != "tpu":
        return None
    kind = device_kind.lower()
    for needle, peaks in TPU_PEAKS:
        if needle in kind:
            return peaks
    return None


def extract_cost_analysis(compiled: Any) -> dict[str, float]:
    """The aggregate rows of ``compiled.cost_analysis()``: ``flops``,
    ``transcendentals`` and ``bytes accessed`` (per-operand ``bytes accessedN{}``
    breakdowns are dropped).  Missing analysis (some backends return nothing) yields zeros, never a raise:
    a missing cost must degrade a report, not kill the run that asked for it.
    """
    try:
        raw = compiled.cost_analysis()
    except Exception:
        raw = None
    if not isinstance(raw, dict):
        return {"flops": 0.0, "transcendentals": 0.0, "bytes_accessed": 0.0}
    return {
        "flops": float(raw.get("flops", 0.0)),
        "transcendentals": float(raw.get("transcendentals", 0.0)),
        "bytes_accessed": float(raw.get("bytes accessed", 0.0)),
    }


def extract_memory_analysis(compiled: Any) -> dict[str, int]:
    """Normalize ``compiled.memory_analysis()`` into plain ints.

    ``peak_bytes`` is the device-resident footprint while the program runs:
    arguments + outputs + temporaries, minus the aliased (donated) bytes that
    are counted in both arguments and outputs but occupy HBM once.  Where the
    runtime exposes an explicit peak estimate it would be preferable, but this
    jaxlib does not — the sum is the defensible upper bound and is labeled as
    computed, not measured.
    """
    out = {
        "argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
        "alias_bytes": 0, "generated_code_bytes": 0, "peak_bytes": 0,
    }
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return out
    if ma is None:
        return out

    def _get(name: str) -> int:
        try:
            return int(getattr(ma, name))
        except (AttributeError, TypeError):
            return 0

    out["argument_bytes"] = _get("argument_size_in_bytes")
    out["output_bytes"] = _get("output_size_in_bytes")
    out["temp_bytes"] = _get("temp_size_in_bytes")
    out["alias_bytes"] = _get("alias_size_in_bytes")
    out["generated_code_bytes"] = _get("generated_code_size_in_bytes")
    out["peak_bytes"] = max(
        0,
        out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        - out["alias_bytes"],
    )
    return out


@dataclass(frozen=True)
class ProgramCostReport:
    """One compiled program's compiler-reported cost + roofline placement.

    All byte/FLOP numbers are PER-DEVICE (the SPMD module one device runs); a
    fused R-round block's numbers cover all ``rounds`` rounds.  ``verdict`` is
    ``"compute-bound"`` / ``"memory-bound"`` when a peaks basis exists for the
    platform, else ``"no peak basis"`` (CPU, unknown chips) — the cost numbers
    are still real and comparable, only the roofline placement is undefined.
    """

    program: str
    platform: str
    device_kind: str
    num_devices: int
    rounds: int  # rounds the program covers (R for a fused block, else 1)
    flops: float
    transcendentals: float
    bytes_accessed: float
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    alias_bytes: int
    generated_code_bytes: int
    peak_bytes: int
    compile_seconds: float
    arithmetic_intensity: float  # flops / bytes_accessed (0 when bytes unknown)
    peaks: PlatformPeaks | None = None
    attrs: dict[str, Any] = field(default_factory=dict)

    @property
    def ridge_intensity(self) -> float | None:
        """The roofline ridge point (FLOP/byte) — above it the program is
        compute-bound, below it HBM-bound.  None without a peaks basis."""
        if self.peaks is None:
            return None
        return self.peaks.flops_per_s / self.peaks.hbm_bytes_per_s

    @property
    def verdict(self) -> str:
        ridge = self.ridge_intensity
        if ridge is None:
            return "no peak basis"
        if self.arithmetic_intensity >= ridge:
            return "compute-bound"
        return "memory-bound"

    @property
    def lower_bound_s(self) -> float | None:
        """Roofline lower bound on the program's walltime: the slower of
        feeding the MXU (flops / peak FLOP/s) and feeding HBM (bytes / peak
        bandwidth), per device.  None without a peaks basis."""
        if self.peaks is None:
            return None
        return max(
            self.flops / self.peaks.flops_per_s,
            self.bytes_accessed / self.peaks.hbm_bytes_per_s,
        )

    def mfu(self, walltime_s: float) -> float | None:
        """Compiler-FLOPs MFU for a measured walltime of THIS program (the
        whole program — pass block walltime for a fused block, not per-round).
        None without a peaks basis or a non-positive walltime."""
        if self.peaks is None or walltime_s <= 0:
            return None
        return self.flops / walltime_s / self.peaks.flops_per_s

    def to_dict(self) -> dict[str, Any]:
        """JSON-friendly dump — the shape of a ``telemetry.jsonl``
        ``program_profile`` record."""
        out: dict[str, Any] = {
            "program": self.program,
            "platform": self.platform,
            "device_kind": self.device_kind,
            "num_devices": self.num_devices,
            "rounds": self.rounds,
            "flops": self.flops,
            "flops_per_round": self.flops / self.rounds,
            "transcendentals": self.transcendentals,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "peak_bytes": self.peak_bytes,
            "compile_seconds": round(self.compile_seconds, 4),
            "arithmetic_intensity": round(self.arithmetic_intensity, 4),
            "verdict": self.verdict,
            "basis": (
                "compiled.cost_analysis()/memory_analysis() of the per-device "
                "SPMD module; peak_bytes = args + outputs + temps - aliased"
            ),
        }
        if self.peaks is not None:
            out["peaks_basis"] = self.peaks.basis
            out["ridge_intensity"] = round(self.ridge_intensity, 4)
            out["lower_bound_s"] = self.lower_bound_s
        if self.attrs:
            out["attrs"] = self.attrs
        return out


def profile_program(
    name: str,
    fn: Callable,
    *args: Any,
    rounds: int = 1,
    peaks: PlatformPeaks | None | str = "auto",
    attrs: dict[str, Any] | None = None,
    **kwargs: Any,
) -> ProgramCostReport:
    """Lower + compile ``fn(*args, **kwargs)`` and extract its cost report.

    ``fn`` is a ``jax.jit`` callable, or any callable carrying a ``jit_program``
    attribute pointing at one (the fused-block builder returns a plain wrapper
    and exposes its inner jit that way).  Nothing executes — lowering and
    compiling touch no data, so donated real buffers are safe to pass.
    ``compile_seconds`` is the measured time-to-ready (trace + lower + XLA
    compile); with the persistent compilation cache warm it collapses to the
    deserialize cost, which is the point of timing it.

    ``peaks="auto"`` (default) resolves the peaks table from the program's
    devices; pass an explicit :class:`PlatformPeaks` (tests) or None.
    """
    jit_fn = getattr(fn, "jit_program", fn)
    if not hasattr(jit_fn, "lower"):
        raise TypeError(
            f"program {name!r} is not lowerable: {fn!r} has neither .lower nor "
            "a .jit_program attribute pointing at a jit-compiled callable"
        )
    t0 = time.perf_counter()
    compiled = jit_fn.lower(*args, **kwargs).compile()
    compile_seconds = time.perf_counter() - t0

    import jax

    devices = jax.devices()
    platform = str(devices[0].platform)
    device_kind = str(getattr(devices[0], "device_kind", platform))
    if peaks == "auto":
        peaks = peaks_for_device_kind(device_kind, platform)
    cost = extract_cost_analysis(compiled)
    mem = extract_memory_analysis(compiled)
    intensity = (
        cost["flops"] / cost["bytes_accessed"] if cost["bytes_accessed"] > 0 else 0.0
    )
    return ProgramCostReport(
        program=name,
        platform=platform,
        device_kind=device_kind,
        num_devices=len(devices),
        rounds=max(1, int(rounds)),
        flops=cost["flops"],
        transcendentals=cost["transcendentals"],
        bytes_accessed=cost["bytes_accessed"],
        compile_seconds=compile_seconds,
        arithmetic_intensity=intensity,
        peaks=peaks,
        attrs=dict(attrs or {}),
        **mem,
    )


@dataclass
class _CatalogEntry:
    fn: Callable
    args_factory: Callable[[], tuple[tuple, dict]]
    rounds: int
    attrs: dict[str, Any]


class ProgramCatalog:
    """The round programs a process has built, profiled on demand.

    ``register`` is free (no trace, no compile) — the ``Coordinator`` calls it
    at program-build time for every program it constructs, passing a LAZY
    ``args_factory`` so registration materializes nothing.  ``profile`` runs
    the AOT compile, caches the report, and publishes the ``nanofed_program_*``
    gauges plus the compile-time histogram into the registry.

    Thread-safe; ``registry=None`` resolves the process-wide default at publish
    time (the coordinator rebinds ``catalog.registry`` once its telemetry
    registry exists).
    """

    def __init__(self, registry: MetricsRegistry | None = None) -> None:
        self.registry = registry
        self._lock = threading.Lock()
        self._entries: dict[str, _CatalogEntry] = {}
        self._reports: dict[str, ProgramCostReport] = {}

    def register(
        self,
        name: str,
        fn: Callable,
        args_factory: Callable[[], tuple[tuple, dict]] | None = None,
        args: tuple = (),
        rounds: int = 1,
        attrs: dict[str, Any] | None = None,
    ) -> None:
        """Add (or replace) a program.  Pass either a lazy ``args_factory``
        returning ``(args, kwargs)`` (preferred — nothing materializes until
        profile time) or concrete ``args``."""
        factory = args_factory if args_factory is not None else (lambda: (args, {}))
        with self._lock:
            self._entries[name] = _CatalogEntry(
                fn=fn, args_factory=factory, rounds=max(1, int(rounds)),
                attrs=dict(attrs or {}),
            )
            self._reports.pop(name, None)

    def remove(self, name: str) -> None:
        """Drop a program and its cached report; no-op when absent (a retune
        swap down to rounds_per_block=1 retires the block program)."""
        with self._lock:
            self._entries.pop(name, None)
            self._reports.pop(name, None)

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._entries)

    def registration(
        self, name: str
    ) -> tuple[Callable, Callable[[], tuple[tuple, dict]], int, dict[str, Any]]:
        """The raw registration ``(fn, args_factory, rounds, attrs)`` — what a
        catalog aggregator (``analysis.program_audit.reference_catalog``)
        needs to re-register an entry under another name."""
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise KeyError(f"no program {name!r} registered (have {self.names()})")
        return entry.fn, entry.args_factory, entry.rounds, dict(entry.attrs)

    def report(self, name: str) -> ProgramCostReport | None:
        """The cached report, or None if ``profile`` has not run for it."""
        with self._lock:
            return self._reports.get(name)

    def reports(self) -> list[ProgramCostReport]:
        with self._lock:
            return [self._reports[n] for n in sorted(self._reports)]

    def profile(self, name: str, force: bool = False) -> ProgramCostReport:
        """Compile + extract one registered program (cached unless ``force``)
        and publish its gauges."""
        with self._lock:
            entry = self._entries.get(name)
            cached = self._reports.get(name)
        if entry is None:
            raise KeyError(f"no program {name!r} registered (have {self.names()})")
        if cached is not None and not force:
            return cached
        args, kwargs = entry.args_factory()
        report = profile_program(
            name, entry.fn, *args, rounds=entry.rounds, attrs=entry.attrs, **kwargs
        )
        with self._lock:
            self._reports[name] = report
        self.publish(report)
        return report

    def profile_all(self, force: bool = False) -> list[ProgramCostReport]:
        return [self.profile(name, force=force) for name in self.names()]

    def audit(self, name: str, compile: bool = True):
        """Run the jaxpr/AOT program audit (``analysis.program_audit``) on one
        registered program; returns its ``AuditReport`` (findings included —
        never raises on findings).  ``compile=False`` is trace-only (skips the
        donation check along with the AOT compile)."""
        from nanofed_tpu.analysis.program_audit import audit_program

        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise KeyError(f"no program {name!r} registered (have {self.names()})")
        args, kwargs = entry.args_factory()
        return audit_program(
            name, entry.fn, *args, rounds=entry.rounds,
            mesh=entry.attrs.get("mesh"), compile=compile,
            attrs={k: v for k, v in entry.attrs.items() if k != "mesh"},
            **kwargs,
        )

    def audit_all(self, compile: bool = True) -> list:
        return [self.audit(name, compile=compile) for name in self.names()]

    def publish(self, report: ProgramCostReport) -> None:
        """Expose one report on the metrics registry: per-program gauges
        (labels ``program=``) + the time-to-ready histogram."""
        reg = self.registry or get_registry()
        reg.gauge(
            PROGRAM_FLOPS_GAUGE,
            "Compiler-reported FLOPs of the per-device compiled program "
            "(cost_analysis; a fused block covers all its rounds)",
            labels=("program",),
        ).set(report.flops, program=report.program)
        reg.gauge(
            PROGRAM_PEAK_BYTES_GAUGE,
            "Device-resident bytes while the program runs "
            "(memory_analysis: args + outputs + temps - aliased)",
            labels=("program",),
        ).set(report.peak_bytes, program=report.program)
        reg.gauge(
            PROGRAM_BYTES_ACCESSED_GAUGE,
            "Compiler-reported bytes accessed by the per-device program",
            labels=("program",),
        ).set(report.bytes_accessed, program=report.program)
        reg.gauge(
            PROGRAM_INTENSITY_GAUGE,
            "Arithmetic intensity (FLOPs / bytes accessed) of the program",
            labels=("program",),
        ).set(report.arithmetic_intensity, program=report.program)
        reg.histogram(
            PROGRAM_COMPILE_HISTOGRAM,
            "Time-to-ready (trace + lower + XLA compile) per program",
            labels=("program",),
            buckets=COMPILE_BUCKETS,
        ).observe(report.compile_seconds, program=report.program)


def update_device_occupancy(
    segments: Mapping[str, float], registry: MetricsRegistry | None = None
) -> float | None:
    """Set ``nanofed_device_occupancy_ratio`` from one round's (or one fused block's)
    critical-path segments and return it; None, the gauge untouched, when the
    segments hold no ``device_wait`` (a round that FAILED before any dispatch).

    Occupancy here is the fraction of the loop's walltime the host spent blocked ON
    the device rather than doing host work around it: ``device_wait`` over the sum of
    the five segments the Coordinator tiles a generator step into (``prepare``,
    ``dispatch``, ``device_wait``, ``readback``, ``publish``).  It is a LOWER bound
    on the device's true busy fraction — the device also computes while ``dispatch``
    is still enqueueing — and, being of the last round alone, it forgets the
    compile-and-warm first round as soon as that is over.  ``publish`` (checkpoint,
    metrics JSON, versioned model) is in the denominator: it is host time the device
    spends idle, and leaving it out would let a publish-heavy run read ABOVE the
    truth, the opposite of a lower bound.
    """
    wait = segments.get("device_wait")
    total = math.fsum(segments.values())
    if wait is None or total <= 0:
        return None
    ratio = min(1.0, wait / total)
    (registry or get_registry()).gauge(
        DEVICE_OCCUPANCY_GAUGE,
        "Host-blocked-on-device fraction of the round loop's walltime (lower "
        "bound on device occupancy), from the last round's critical-path segments",
    ).set(ratio)
    return ratio


def format_cost_table(reports: Iterable[ProgramCostReport]) -> str:
    """Human-readable roofline table (what ``nanofed-tpu profile`` prints).

    One row per program: per-round compiler FLOPs, peak device bytes,
    arithmetic intensity, the roofline verdict, the achievable lower-bound
    round time (when a peaks basis exists), and time-to-ready.
    """
    rows = [(
        "program", "rounds", "flops/round", "peak bytes", "intensity",
        "verdict", "bound s/round", "compile s",
    )]
    reports = list(reports)
    for r in reports:
        bound = r.lower_bound_s
        rows.append((
            r.program,
            str(r.rounds),
            _si(r.flops / r.rounds),
            _si(r.peak_bytes),
            f"{r.arithmetic_intensity:.2f}",
            r.verdict,
            f"{bound / r.rounds:.3g}" if bound is not None else "-",
            f"{r.compile_seconds:.2f}",
        ))
    widths = [max(len(row[i]) for row in rows) for i in range(len(rows[0]))]
    lines = []
    for j, row in enumerate(rows):
        lines.append("  ".join(cell.ljust(w) for cell, w in zip(row, widths)).rstrip())
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    if reports:
        first = reports[0]
        if first.peaks is not None:
            lines.append("")
            lines.append(
                f"roofline basis: {first.peaks.basis} "
                f"(ridge {first.ridge_intensity:.1f} FLOP/byte)"
            )
        else:
            lines.append("")
            lines.append(
                f"roofline basis: none for platform={first.platform!r} "
                f"({first.device_kind}) — cost numbers are real and "
                "comparable, the compute/memory-bound verdict is undefined"
            )
    return "\n".join(lines)


def _si(v: float) -> str:
    """Compact engineering notation (1.23G, 456M, ...)."""
    for factor, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(v) >= factor:
            return f"{v / factor:.2f}{suffix}"
    return f"{v:.0f}"
