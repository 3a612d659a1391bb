"""Host time blocked on the device, the program's own ``device_wait`` segment
(``RoundMetrics.segments``: the ``block_until_ready`` on the new parameters), averaged
over the window's rounds.  It is the host's clock, not the device's: the device starts
inside ``dispatch``, and the host learns a little late that it is done (on the chip it
reads under a millisecond over ``device_busy_ms_per_round``; PERF.md).  Left out where a
round carries no such segment."""


def read(ctx):
    rounds = ctx["rounds"]
    values = [getattr(m, "segments", {}).get("device_wait") for _, m in rounds]
    if not values or None in values:
        return None
    return 1000.0 * sum(values) / len(values)
