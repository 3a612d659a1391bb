"""Host time from the device being done to the round's metrics being built, the program's
own ``readback`` segment (``RoundMetrics.segments``): the scalar metrics converted, the
per-client detail read back, the log line — averaged over the window's rounds.  Left out
where a round carries no such segment."""


def read(ctx):
    rounds = ctx["rounds"]
    values = [getattr(m, "segments", {}).get("readback") for _, m in rounds]
    if not values or None in values:
        return None
    return 1000.0 * sum(values) / len(values)
