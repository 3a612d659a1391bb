"""Host time after the round itself, the program's own ``publish`` segment
(``RoundMetrics.segments``): the metrics JSON and whatever else is published, the
round-end callback — averaged over the window's rounds.  Left out where a round carries
no such segment."""


def read(ctx):
    rounds = ctx["rounds"]
    values = [getattr(m, "segments", {}).get("publish") for _, m in rounds]
    if not values or None in values:
        return None
    return 1000.0 * sum(values) / len(values)
