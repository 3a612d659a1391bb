"""How hard the indexer's pick binds: kept (query, key) pairs over causal pairs, as the
program counts them in every local step from the mask it hands the attention kernels
(``sparse_kept_pair_share`` of ``RoundMetrics.agg_metrics``: mean over the layers,
sample-weighted over steps and clients), averaged over the window's rounds, in percent.
``topk (2T - topk + 1) / (T (T + 1))`` exactly when every query keeps ``min(topk, t + 1)``
keys: 43.75 at 8192 positions of 2048; 100 where the pick never binds.  Another reading
is a selection that lost or added keys.  Left out where the rounds carry no such counter
(a model with no indexer, a program from before it)."""


def read(ctx):
    seen = [m.agg_metrics["sparse_kept_pair_share"] for _, m in ctx["rounds"]
            if "sparse_kept_pair_share" in getattr(m, "agg_metrics", {})]
    return 100.0 * sum(seen) / len(seen) if seen else None
