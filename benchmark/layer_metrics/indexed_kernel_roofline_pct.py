"""The attention kernels' share of the chip's bf16 peak where they run under a mask the
model computed (``causal_attention_fwd_keep`` / ``causal_attention_bwd_keep``):
``attn_kernel_roofline_pct``'s reading, not a second one — the executions FOUND among the
trace's ten longest operations, each by its kind, over their self time times the peak, so
the share errs low and never over 100.  What makes the number this metric's own is the
family's count (``flops/<family>.py``: ``attention_kernel_flops``): the KEPT (query, key)
pairs alone, the work the mathematics needs, so kernels that visit every causal block and
mask read at most the kept share of their matmul share (43.75% at 8192 positions of 2048
keys), and kernels that skipped or gathered would be measured by the same count.

Left out where that reader leaves its own out: no kernel among the ten, a family that
counts no such operations, a run not traced."""

from pathlib import Path


def read(ctx):
    from benchmark import federation  # the reader's file, found by name as run.py finds it

    root = Path(__file__).resolve().parents[2]
    return federation.load_named(root, "layer_metrics", "attn_kernel_roofline_pct").read(ctx)
