"""Host time inside the call of the jitted round program until it returns, the program's
own ``dispatch`` segment (``RoundMetrics.segments``), averaged over the window's rounds.
The device starts somewhere inside it.  Left out where a round carries no such segment."""


def read(ctx):
    rounds = ctx["rounds"]
    values = [getattr(m, "segments", {}).get("dispatch") for _, m in rounds]
    if not values or None in values:
        return None
    return 1000.0 * sum(values) / len(values)
