"""How full the expert loop's blocks run: rows taken over rows of the blocks the loop
ran, as the program counts it in every local step (``moe_block_fill`` of
``RoundMetrics.agg_metrics``: mean over the layers, sample-weighted over steps and
clients), averaged over the window's rounds, in percent.  A held expert's picks are padded
to whole blocks, and a block costs its expert's matrices read and its gradient
accumulators read and written whatever it holds: the empty share is MXU work and traffic
that buys nothing but a trip count that does not follow the routing.  Left out where the
rounds carry no such counter (a model with no expert layer, a program from before it)."""


def read(ctx):
    seen = [m.agg_metrics["moe_block_fill"] for _, m in ctx["rounds"]
            if "moe_block_fill" in getattr(m, "agg_metrics", {})]
    return 100.0 * sum(seen) / len(seen) if seen else None
