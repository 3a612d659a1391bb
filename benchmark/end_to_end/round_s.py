"""Median, over the window's samples, of one round's wall time on the benchmark's
clock: from asking ``start_training()`` for the next round to holding its metrics."""

import statistics


def read(ctx):
    return statistics.median(ctx["samples"])
