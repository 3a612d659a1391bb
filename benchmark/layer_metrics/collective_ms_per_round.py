"""Time in collectives: the summed durations of the collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) on the busiest device,
per traced round.  On one chip the program has none and the metric is left out."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_rounds"] or not trace["collective_events"]:
        return None
    return 1000.0 * trace["collective_s"] / ctx["traced_rounds"]
