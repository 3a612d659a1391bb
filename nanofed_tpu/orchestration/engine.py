"""The ONE round engine: cohort gating + round-outcome accounting.

Before this module, round dispatch bookkeeping lived three times: the SPMD
:class:`~nanofed_tpu.orchestration.coordinator.Coordinator` (single-round and
fused-block paths), the wire
:class:`~nanofed_tpu.communication.network_coordinator.NetworkCoordinator`
(sync FedAvg, FedBuff, and secure rounds), and the tenant sessions (which
drive a NetworkCoordinator each).  Three copies of the same two facts —

* the completion gate: how many cohort members must report before a round
  counts (``ceil(expected * min_completion_rate)``, floored at one), and
* the outcome ledger: the instrument quadruple
  (``nanofed_rounds_total{status}``, ``nanofed_round_duration_seconds``,
  ``nanofed_cohort_size``, ``nanofed_dropouts_total``) plus the ``round``
  telemetry record

— drifted independently (the SPMD path grew a dropouts counter the wire path
never had; the wire path's gate subtracts evicted stragglers).  Every front
now delegates here: :func:`completion_required` is the single gating
expression in the tree, and :class:`RoundLedger` is the single place a round
outcome is charged.  The federate harness (``scripts/multihost_harness.py
federate``) drives the same ledger from inside each mesh worker, which is
what makes the wire tier and the mesh tier "one stack" observable as one:
identical metric names, identical record shape, one grep.

Front-specific state stays in the fronts: the SPMD coordinator keeps its
retune/occupancy hooks and RoundMetrics history, the wire coordinator its
straggler eviction and dict records, checkpoint cadence stays at each front's
commit boundary.  The ledger is accounting, not control flow — it never
decides whether a round runs, only records how it went.
"""

from __future__ import annotations

import math
import time
from typing import Any

__all__ = ["RoundLedger", "completion_required"]


def completion_required(expected: int, min_completion_rate: float) -> int:
    """The cohort completion gate, the only ceil in the repo that computes it:
    how many of ``expected`` participants must report for a round to COMPLETE.
    Floored at one twice over (an empty expectation still needs one report;
    ``min_completion_rate=0`` still needs one report), matching what the SPMD
    and wire engines each enforced separately before the merge."""
    return max(1, math.ceil(max(1, expected) * min_completion_rate))


class RoundLedger:
    """Round-outcome accounting shared by every round engine front.

    Owns the instrument quadruple — created once per front against that
    front's registry, same names and help strings everywhere so a shared
    registry deduplicates them — and the ``round`` telemetry record.  One
    :meth:`charge` per round outcome, from any front::

        ledger = RoundLedger(registry, telemetry=telemetry, track_dropouts=True)
        ...
        ledger.charge(status=metrics.status.name, num_clients=k,
                      duration_s=dt, expected=cohort_size,
                      telemetry_fields={"round": r, "status": ..., ...})

    ``track_dropouts`` gates the ``nanofed_dropouts_total`` counter: the SPMD
    front samples a cohort and can say who dropped; the wire front's expected
    population is a barrier, not a roster, so it never had (or wanted) the
    counter and charging zero would still register the series.
    """

    def __init__(
        self,
        registry: Any,
        *,
        telemetry: Any | None = None,
        track_dropouts: bool = False,
    ) -> None:
        self.registry = registry
        self.telemetry = telemetry
        self._m_rounds = registry.counter(
            "nanofed_rounds_total", "Federation rounds by outcome", labels=("status",)
        )
        self._m_round_duration = registry.histogram(
            "nanofed_round_duration_seconds", "Wall time per federation round"
        )
        self._m_cohort = registry.gauge(
            "nanofed_cohort_size", "Clients whose updates entered the last aggregate"
        )
        self._m_dropouts = (
            registry.counter(
                "nanofed_dropouts_total",
                "Sampled clients that dropped out of a round",
            )
            if track_dropouts
            else None
        )
        self._m_critical_path = registry.histogram(
            "nanofed_round_critical_path_seconds",
            "Per-round walltime by critical-path segment (federate worker: "
            "wire_wait/decode/drain/collective/apply/publish; synchronous loop: "
            "prepare/dispatch/device_wait/readback/publish)",
            labels=("segment",),
        )

    def charge(
        self,
        *,
        status: str,
        num_clients: int,
        duration_s: float,
        expected: int | None = None,
        telemetry_fields: dict[str, Any] | None = None,
        segments: dict[str, float] | None = None,
    ) -> None:
        """Charge one round outcome: counter by lowercased status, duration
        observation, cohort gauge, dropouts (when tracked and ``expected`` is
        given), and — when this front has telemetry — the ``round`` record.

        ``segments`` is the round's critical-path decomposition (segment name
        -> seconds; the federate worker passes wire_wait/decode/drain/
        collective/apply/publish, the SPMD Coordinator prepare/dispatch/
        device_wait/readback/publish; either tiles ``duration_s``): each observes
        ``nanofed_round_critical_path_seconds{segment}`` and the rounded dict
        rides the ``round`` telemetry record as ``segments``."""
        self._m_rounds.inc(status=str(status).lower())
        self._m_round_duration.observe(duration_s)
        self._m_cohort.set(num_clients)
        if self._m_dropouts is not None and expected is not None:
            self._m_dropouts.inc(max(0, expected - num_clients))
        if segments:
            for seg, seconds in segments.items():
                self._m_critical_path.observe(float(seconds), segment=str(seg))
        if self.telemetry is not None and telemetry_fields is not None:
            if segments:
                telemetry_fields = dict(telemetry_fields)
                telemetry_fields.setdefault("segments", {
                    str(seg): round(float(v), 6) for seg, v in segments.items()
                })
            self.telemetry.record("round", **telemetry_fields)

    @staticmethod
    def now() -> float:
        """Round-duration timestamps: always the real ``perf_counter`` (a
        virtual clock compresses exactly the waiting a duration must show)."""
        return time.perf_counter()
