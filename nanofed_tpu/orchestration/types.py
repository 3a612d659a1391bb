"""Round / progress value types (parity: ``nanofed/orchestration/types.py:7-47``)."""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from enum import Enum
from typing import Any


def cohort_size(num_clients: int, participation_rate: float) -> int:
    """Clients sampled per round: ceil(N · rate), floored at 1, capped at N.

    ceil per the CoordinatorConfig contract (round() would banker's-round .5 down).
    THE single definition — privacy-critical: σ calibration (``cli.py``,
    ``noise_multiplier_for_budget`` callers) and spend accounting
    (``Coordinator._train_round``) must agree on the realized inclusion probability
    ``cohort_size/N``, which the floor and ceil make ≥ the nominal rate.
    """
    return min(num_clients, max(1, math.ceil(num_clients * participation_rate)))


class RoundStatus(Enum):
    """Parity with ``RoundStatus`` (``orchestration/types.py``)."""

    PENDING = "pending"
    IN_PROGRESS = "in_progress"
    COMPLETED = "completed"
    FAILED = "failed"


@dataclass(frozen=True)
class ClientInfo:
    """Host-side record of one simulated client (parity: ``ClientInfo``)."""

    client_id: str
    num_samples: int


@dataclass(frozen=True)
class RoundMetrics:
    """One round's outcome (parity: ``RoundMetrics`` — round id, status, client count,
    aggregated metrics — plus eval metrics and wall-clock, which the reference logs but
    does not type)."""

    round_id: int
    status: RoundStatus
    num_clients: int  # participating (completed) clients
    agg_metrics: dict[str, float] = field(default_factory=dict)
    eval_metrics: dict[str, float] = field(default_factory=dict)
    duration_s: float = 0.0
    timestamp: str = ""
    # Where the round's walltime went, in seconds: the Coordinator's tiling of one
    # generator step (prepare / dispatch / device_wait / readback / publish; see
    # docs/observability.md, "Per-round critical-path segments").  ``duration_s`` is
    # the round alone; the segments also cover what runs around it.
    segments: dict[str, float] = field(default_factory=dict)

    def to_dict(self) -> dict[str, Any]:
        return {
            "round_id": self.round_id,
            "status": self.status.value,
            "num_clients": self.num_clients,
            "agg_metrics": self.agg_metrics,
            "eval_metrics": self.eval_metrics,
            "duration_s": self.duration_s,
            "timestamp": self.timestamp,
            "segments": self.segments,
        }


@dataclass(frozen=True)
class TrainingProgress:
    """Live progress snapshot (parity: ``TrainingProgress`` +
    ``Coordinator.training_progress``, ``coordinator.py:181-190``)."""

    current_round: int
    total_rounds: int
    completed_rounds: int
    failed_rounds: int
    global_metrics: dict[str, float] = field(default_factory=dict)
