"""Host time before the round program is called: the program's own ``prepare`` segment
(``RoundMetrics.segments``) — from the generator being resumed to just before the jitted
call: the retune check, cohort sampling and gather, the round's keys and learning-rate
scalar made device-ready — averaged over the window's rounds.  Left out where a round
carries no such segment (a program from before the segments)."""


def read(ctx):
    rounds = ctx["rounds"]
    values = [getattr(m, "segments", {}).get("prepare") for _, m in rounds]
    if not values or None in values:
        return None
    return 1000.0 * sum(values) / len(values)
