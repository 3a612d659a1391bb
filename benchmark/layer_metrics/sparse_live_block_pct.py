"""What a kernel that skipped could save: of the attention kernels' (query block, key
block) pairs on or under the diagonal, the share that holds at least one kept pair, as
the program counts it in every local step (``sparse_live_block_share`` of
``RoundMetrics.agg_metrics``: mean over the layers, sample-weighted over steps and
clients), averaged over the window's rounds, in percent.  The kernels visit every such
block and mask; at 100 a skipping kernel saves nothing, and the distance to
``sparse_kept_pair_pct`` is what only a gathering kernel could save.  Left out where the
rounds carry no such counter."""


def read(ctx):
    seen = [m.agg_metrics["sparse_live_block_share"] for _, m in ctx["rounds"]
            if "sparse_live_block_share" in getattr(m, "agg_metrics", {})]
    return 100.0 * sum(seen) / len(seen) if seen else None
