#!/usr/bin/env python3
"""What one ``SpanTracer.span`` costs the host, as the round loop opens it.

    python scripts/span_cost.py [--spans 100000]

Opens and closes ``--spans`` empty spans on a ``RunTelemetry`` tracer with its sink
attached (the Coordinator's default: ``save_metrics=True`` streams every span into
``telemetry.jsonl``), then on a tracer with no sink and no registry, and prints one
JSON line: microseconds per span in each mode.  The round loop's budget for its spans
is a fixed count of these a round (docs/observability.md, "Span taxonomy"); run it on
the host whose rounds are being timed — it touches no device.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from nanofed_tpu.observability import MetricsRegistry, RunTelemetry, SpanTracer  # noqa: E402


def per_span_us(tracer: SpanTracer, spans: int, batches: int = 10) -> dict[str, float]:
    """Median and worst microseconds per span over ``batches`` equal batches."""
    each = max(1, spans // batches)
    costs = []
    for _ in range(batches):
        t0 = time.perf_counter()
        for i in range(each):
            with tracer.span("round-keys", round=i):
                pass
        costs.append(1e6 * (time.perf_counter() - t0) / each)
    return {"median_us": statistics.median(costs), "max_us": max(costs)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spans", type=int, default=100_000)
    args = ap.parse_args()
    with tempfile.TemporaryDirectory() as work:
        telemetry = RunTelemetry(work, registry=MetricsRegistry())
        with_sink = per_span_us(telemetry.tracer, args.spans)
        telemetry.close()
    bare = per_span_us(SpanTracer(registry=False, keep_records=False), args.spans)
    print(json.dumps({"spans": args.spans, "telemetry_sink": with_sink, "no_sink": bare}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
