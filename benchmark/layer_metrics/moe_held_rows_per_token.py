"""Rows of expert product a token costs an expert layer here: ``top_k`` times the share
of all picks that landed on held experts, as the program counts it in every local step
(``moe_held_pick_share`` of ``RoundMetrics.agg_metrics``), averaged over the window's
rounds.  ``top_k * experts_held / experts`` under uniform routing (0.375 at 6, 8 of
128); a router collapsing onto the held experts moves it towards ``top_k``, and the
expert loop's work with it.  Left out where the rounds carry no such counter."""


def read(ctx):
    seen = [m.agg_metrics["moe_held_pick_share"] for _, m in ctx["rounds"]
            if "moe_held_pick_share" in getattr(m, "agg_metrics", {})]
    if not seen:
        return None
    return ctx["config"]["model"]["kwargs"]["top_k"] * sum(seen) / len(seen)
