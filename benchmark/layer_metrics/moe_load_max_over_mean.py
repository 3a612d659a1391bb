"""How unevenly the router loads the experts held here: the largest token count among
the held experts over their mean, as the program counts it in every local step
(``moe_load_max_over_mean`` of ``RoundMetrics.agg_metrics``: mean over the expert layers,
sample-weighted over steps and clients), averaged over the window's rounds.  1.0 is even.
The busiest expert's rows set how many blocks the expert loop runs.  Left out where the
rounds carry no such counter (a model with no expert layer, a program from before it)."""


def read(ctx):
    seen = [m.agg_metrics["moe_load_max_over_mean"] for _, m in ctx["rounds"]
            if "moe_load_max_over_mean" in getattr(m, "agg_metrics", {})]
    return sum(seen) / len(seen) if seen else None
