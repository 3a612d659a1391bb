"""Device time of a round: the union of the intervals in which an operation ran on the
device, over the traced rounds (mean over the chips used), per round."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_rounds"]:
        return None
    return 1000.0 * trace["busy_s"] / ctx["traced_rounds"]
