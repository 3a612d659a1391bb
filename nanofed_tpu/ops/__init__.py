"""Pallas TPU kernels for the framework's hot data-movement ops.

The compute-heavy path (conv/matmul forward+backward in local training) is left to XLA —
its conv kernels already schedule the MXU well.  Pallas is applied where fusion or
hardware PRNG buys something XLA's pattern library doesn't express:

* ``ops.reduce``    — the FedAvg weighted reduce over the stacked client axis as one
                      MXU contraction per tile ([C, P] x [C] -> [P]).
* ``ops.quantize``  — fixed-point uint32 quantize / dequantize and seeded additive
                      masking (the SecAgg inner loop) with the on-core PRNG, so masking
                      never round-trips to the host; plus the fused q8/topk aggregation
                      epilogue (``dequant_accumulate_flat``: the per-client dequant
                      scale folds into the reduce coefficients, so the int8 stack is
                      read once and the dequantized [C, P] float never exists).
* ``ops.reduce`` also carries the fused validated-aggregation epilogue
  (``masked_weighted_mean_flat``): non-finite sanitization + validity mask +
  weighted reduce in one read pass instead of sanitize-write-reduce.

* ``ops.attention`` — causal self-attention block by block with an online softmax and a
                      recomputing backward (``causal_attention``): no ``[N, H, T, T]``
                      array on either pass; ``models.transformer`` takes it for long
                      sequences.

* ``ops.experts``   — the held experts' MLPs of a mixture-of-experts layer as a grouped
                      matmul over row tiles, forward and backward, an expert's matrices
                      and its float32 weight gradients resident in VMEM while its tiles
                      run; ``models.experts.held_experts`` takes it on the TPU.

Every exported op takes ``interpret=None`` (auto: real kernels on TPU, interpreter elsewhere) so
the same code paths are exercised by the CPU-mesh test suite; ``ops.experts``' entry points
are jitted with ``interpret`` static, and their caller decides (``models.experts.kernels_run``).
"""

from nanofed_tpu.ops.attention import causal_attention
from nanofed_tpu.ops.quantize import (
    add_mask,
    dequant_accumulate_flat,
    dequantize_u32,
    quantize_u32,
)
from nanofed_tpu.ops.reduce import (
    masked_weighted_mean_flat,
    weighted_mean_flat,
    weighted_mean_tree,
)

__all__ = [
    "add_mask",
    "causal_attention",
    "dequant_accumulate_flat",
    "dequantize_u32",
    "masked_weighted_mean_flat",
    "quantize_u32",
    "weighted_mean_flat",
    "weighted_mean_tree",
]
