"""90th percentile of the same samples as ``round_s`` (linear interpolation between
order statistics): the hiccup rounds a median hides."""

import math


def read(ctx):
    ordered = sorted(ctx["samples"])
    pos = (len(ordered) - 1) * 0.9
    lo = math.floor(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)
