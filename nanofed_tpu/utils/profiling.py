"""Device-level profiling helpers (the deep end of SURVEY.md §5 'tracing/profiling').

The reference's only profiler is the ``log_exec`` wall-time decorator
(``nanofed/utils/logger.py:189-226``), which this framework keeps (``utils.logger``) —
but wall time alone cannot attribute a TPU round to compute vs HBM vs host gaps.  These
helpers wrap ``jax.profiler`` so a round (or any block) can be captured as an XLA/TPU
trace viewable in TensorBoard or Perfetto (``tensorboard --logdir <dir>`` →  Profile).
"""

from __future__ import annotations

import contextlib
import time
from pathlib import Path
from typing import Any, Callable, Iterator

import jax

from nanofed_tpu.utils.logger import Logger


@contextlib.contextmanager
def trace(log_dir: str | Path, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a device trace of the enclosed block::

        with trace("runs/profile"):
            coordinator.run_round()

    Writes a TensorBoard-profile/Perfetto trace under ``log_dir``.  Host-side
    ``annotate(...)`` / ``jax.profiler.TraceAnnotation`` blocks show up as named spans;
    every XLA executable, transfer, and host gap is attributed.
    """
    log_dir = str(log_dir)
    Logger().info("profiler trace -> %s", log_dir)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named span inside a :func:`trace` capture (host-side annotation)."""
    with jax.profiler.TraceAnnotation(name):
        yield


def device_time(fn: Callable[[], Any], reps: int = 3) -> dict[str, float]:
    """Honest on-device timing of a nullary callable: one untimed warm-up (compile),
    then ``reps`` blocked executions.  Returns min/median/max wall seconds.

    This is the measurement discipline every recorded artifact in ``runs/`` uses
    (compile excluded, ``block_until_ready`` so host-async dispatch can't lie).
    """
    import numpy as np

    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t)
    return {
        "min_s": float(np.min(times)),
        "median_s": float(np.median(times)),
        "max_s": float(np.max(times)),
    }
