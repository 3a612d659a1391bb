"""The attention kernels' share of the chip's bf16 peak where score heads and value heads
differ in size (latent attention): ``attn_kernel_roofline_pct``'s reading, not a second
one — the executions FOUND among the trace's ten longest operations, each by its kind,
over their self time times the peak, so the share errs low and never over 100.  What
makes the number this metric's own is the family's count (``flops/<family>.py``:
``attention_kernel_flops``): score products over the score heads' width, value products
over the value heads', on the unmasked (query, key) pairs alone.

Left out where that reader leaves its own out: no kernel among the ten, a family that
counts no such operations, a run not traced."""

from pathlib import Path


def read(ctx):
    from benchmark import federation  # the reader's file, found by name as run.py finds it

    root = Path(__file__).resolve().parents[2]
    return federation.load_named(root, "layer_metrics", "attn_kernel_roofline_pct").read(ctx)
