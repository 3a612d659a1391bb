"""Closed loop of synchronous rounds: one federation, rounds back to back.

Drives ``Coordinator.start_training()`` — the generator a user iterates — and clocks
each step of it from the benchmark's side: from asking for the next round to holding
its metrics on the host.  Parameters, from the traffic file:

* ``rounds_per_sample``: consecutive rounds clocked together as one sample, so that a
  host-clock reading spans a quarter of a second even where a round is shorter;
* ``trace_skip`` / ``trace_rounds``: in a traced run, which rounds of the window the
  profiler records.
"""

from __future__ import annotations

import time

import jax


def step(generator):
    """One round: ``(seconds on the benchmark's clock, RoundMetrics)``."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.round"):
        metrics = next(generator)
    return time.perf_counter() - t0, metrics


def measure(generator, traffic: dict, seconds: float, trace_dir: str | None) -> dict:
    """Whole rounds until ``seconds`` have passed.  Returns every round's step time and
    metrics, the samples (groups of ``rounds_per_sample`` rounds, seconds per round),
    the window's length (the profiler's own start and stop taken out), and how many
    rounds were traced."""
    group = int(traffic["rounds_per_sample"])
    first = int(traffic["trace_skip"]) if trace_dir else -1
    last = first + int(traffic["trace_rounds"]) if trace_dir else 0
    rounds, samples, profiler_s = [], [], 0.0
    start = time.perf_counter()
    while True:
        if len(rounds) == first:
            t = time.perf_counter()
            jax.profiler.start_trace(trace_dir)
            profiler_s += time.perf_counter() - t
        step_s, metrics = step(generator)
        rounds.append((step_s, metrics))
        if trace_dir and len(rounds) == last:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            profiler_s += time.perf_counter() - t
        if len(rounds) % group == 0:
            samples.append(sum(s for s, _ in rounds[-group:]) / group)
            if time.perf_counter() - start - profiler_s >= seconds and len(rounds) >= last:
                break
    window_s = time.perf_counter() - start - profiler_s
    return {
        "rounds": rounds, "samples": samples, "window_s": window_s,
        "traced_rounds": last - first if trace_dir else 0,
    }
