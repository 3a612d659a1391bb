"""The attention kernels' share of the chip's bf16 peak: the operations their executions
need (``flops/<family>.py``: ``attention_kernel_flops``, the unmasked (query, key) pairs
alone) over the kernels' self time times the peak.  The kernels are compute-bound (a
head's ``K`` and ``V`` stay in VMEM), so the roofline is the matmul peak.

Time: the entries of the trace's ten longest operations whose names begin with the
kernels' names (``pallas_call``'s ``name``: ``causal_attention_fwd``,
``causal_attention_bwd``, ``..._window`` where a window cuts the keys).  Every layer's
forward and its backward are instructions of their own (and a forward the backward pass
reruns would be a third), so more of them can exist than the ten hold: operations are
counted for the executions FOUND, each by its kind, so an entry that fell off the list
takes its time and its operations with it and the share never reads high.  With every execution among the ten
this is ``attention_kernel_flops_per_round`` over the kernels' time a round.

Left out where none is among the ten, where the family's file counts no such
operations, or where the run was not traced."""

from pathlib import Path

FORWARD, BACKWARD = "causal_attention_fwd", "causal_attention_bwd"


def read(ctx):
    trace, rounds = ctx["trace"], ctx["traced_rounds"]
    found = [(name, s) for name, s in (trace or {}).get("device_ops", [])
             if name.startswith((FORWARD, BACKWARD))]
    if not found or not rounds:
        return None
    from benchmark import federation  # the family's file, found by name as run.py finds it

    config = ctx["config"]
    flops = federation.load_named(Path(__file__).resolve().parents[2], "flops", config["family"])
    if not hasattr(flops, "attention_kernel_flops"):
        return None
    kw, fed = config["model"]["kwargs"], config["federation"]
    needed = sum(
        flops.attention_kernel_flops(kw, backward=name.startswith(BACKWARD),
                                     windowed=name.split(".")[0].endswith("_window"))
        for name, _ in found) * flops.samples_per_round(fed)
    seconds_a_round = sum(s for _, s in found) / rounds
    return 100.0 * needed / (seconds_a_round * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
