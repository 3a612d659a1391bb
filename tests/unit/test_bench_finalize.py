"""Unit coverage for bench.py's record arithmetic.

The driver records whatever JSON line bench.py prints last; these pin the median
arithmetic and the compact tail line without a measurement run — bench.py's module
level imports no jax, so this is pure-host testing.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

from bench import (  # noqa: E402
    METRIC_FLAGSHIP,
    METRIC_PARITY,
    compact_summary,
    finalize_measurement,
)

DEVICE = {"platform": "tpu", "device_kind": "TPU v5 lite", "devices": 1}


def test_finalize_takes_the_median_and_keeps_every_round():
    out = finalize_measurement(
        np.array([0.75, 0.73, 0.76]), 200.55, {"metric": "m", "unit": "s"}
    )
    assert out["value"] == 0.75  # median
    assert out["vs_baseline"] == pytest.approx(267.4, abs=0.1)
    assert out["round_times_s"] == [0.75, 0.73, 0.76]
    assert out["aggregation"] == "median of 3 steady-state rounds"


def test_compact_summary_distills_both_metrics_and_stays_short():
    results = [
        {"metric": METRIC_PARITY, "value": 0.31, "unit": "s", "vs_baseline": 172.5,
         **DEVICE, "round_times_s": [0.31] * 50},
        {"metric": METRIC_FLAGSHIP, "value": 0.9, "unit": "s", "vs_baseline": 222.8,
         **DEVICE, "est_mfu_pct": 5.84, "strict": True,
         "cost_analysis": {"flops": 1e12, "note": "x" * 2000},
         "tuned_config": {"client_chunk": 25, "rounds_per_block": 3,
                          "model_shards": 1, "used": "tuned", "measured": True},
         "tuned_value": 0.8},
    ]
    out = compact_summary(results)
    assert out["metric"] == METRIC_FLAGSHIP
    assert out["value"] == 0.9 and out["vs_baseline"] == 222.8
    assert (out["platform"], out["device_kind"], out["devices"]) == (
        "tpu", "TPU v5 lite", 1
    )
    assert out["summary"] is True and out["strict"] is True
    assert out["est_mfu_pct"] == 5.84
    assert out["tuned"] == {"client_chunk": 25, "rounds_per_block": 3,
                            "used": "tuned", "measured": True, "value": 0.8}
    assert out["parity"] == {"value": 0.31, "vs_baseline": 172.5}
    # The whole point: short enough that a tail buffer can never cut it.
    assert len(json.dumps(out)) < 600


def test_compact_summary_carries_round_phase_digest():
    """The observability spans' phase summary rides the tail line as a compact
    phase -> total-seconds map (and the line stays tail-buffer safe)."""
    results = [
        {"metric": METRIC_FLAGSHIP, "value": 2.0, "unit": "s",
         "vs_baseline": 100.0, **DEVICE,
         "phases": {
             "prepare": {"count": 1, "total_s": 1.23456, "max_s": 1.2, "mean_s": 1.2},
             "compile": {"count": 1, "total_s": 10.5, "max_s": 10.5, "mean_s": 10.5},
             "round": {"count": 3, "total_s": 6.0, "max_s": 2.1, "mean_s": 2.0},
         }},
    ]
    out = compact_summary(results)
    assert out["phases"] == {"prepare": 1.235, "compile": 10.5, "round": 6.0}
    assert "parity" not in out  # absent metric is simply omitted
    assert len(json.dumps(out)) < 600
