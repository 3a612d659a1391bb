"""How much of a block-diffusion step's noised copy is masked: masked positions over
noised positions, as the program counts them in every local step from the mask it drew
(``diffusion_masked_share`` of ``RoundMetrics.agg_metrics``: sample-weighted over steps
and clients), averaged over the window's rounds, in percent.  A noise level a block
uniform over ``(eps, 1)`` gives ``(1 + eps) / 2``: 50.05 at ``eps`` 0.001; a reading far
from it is a schedule that lost its key or its floor.  It is also the share of the noised
half's positions that carry a loss.  Left out where the rounds carry no such counter (a
model with no noise of its own, a program from before it)."""


def read(ctx):
    seen = [m.agg_metrics["diffusion_masked_share"] for _, m in ctx["rounds"]
            if "diffusion_masked_share" in getattr(m, "agg_metrics", {})]
    return 100.0 * sum(seen) / len(seen) if seen else None
