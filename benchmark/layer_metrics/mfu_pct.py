"""End-to-end model FLOP/s utilization: the operations the rounds' forward and backward
passes need (``flops/<family>.py``, recomputation not counted) over the window's whole
time, against the chips' published bf16 peak.  Not a kernel's roofline share."""


def read(ctx):
    if not ctx["window_s"]:
        return None
    achieved = ctx["train_flops_per_sample"] * ctx["client_samples"] / ctx["window_s"]
    return 100.0 * achieved / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
