"""FedAvg weighted reduce as a Pallas TPU kernel.

The reduce is ``out[p] = sum_c w[c] * x[c, p] / sum_c w[c]`` over the stacked client axis
— a [C, P] x [C] contraction expressed as one MXU ``dot`` per parameter tile.

MEASURED (v5e-1, C=1000, P=1.2M, f32): this kernel runs at ~0.85x XLA's fused
broadcast-multiply-reduce, so ``utils.trees.tree_weighted_mean`` (XLA) remains the
production reduce in ``aggregation``/``parallel``; the kernel is kept as the measured
baseline for future fusion work (e.g. folding clip/noise into the same pass, where
single-pass HBM traffic would beat XLA's two passes).  The reduce itself is ~1% of a
1000-client round, so this choice is not on the critical path.

Reference parity: this computes the same quantity as the reference's per-key Python loop
(``nanofed/server/aggregator/fedavg.py:56-63``); a parity test pins kernel vs XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanofed_tpu.core.types import Params
from nanofed_tpu.ops._common import auto_interpret
from nanofed_tpu.utils.trees import tree_ravel

_TILE = 512  # lanes per program; P is padded to a multiple of this


def _wmean_kernel(w_ref, x_ref, denom_ref, out_ref):
    # x block: [C, TILE]; w: [1, C]; out block: [1, TILE].  dot -> MXU.
    # HIGHEST: full-f32 MXU passes — the default would split f32 into bf16 passes and
    # lose ~3 decimal digits on the aggregate, visible at FedAvg's accuracy tolerances.
    acc = jax.lax.dot_general(
        w_ref[:], x_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    out_ref[:] = acc / denom_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_mean_flat(
    x: jax.Array,
    weights: jax.Array,
    interpret: bool | None = None,
    denom: jax.Array | None = None,
) -> jax.Array:
    """``[C, P] x [C] -> [P]`` weighted mean (weights normalized by their sum, or by an
    explicit ``denom`` — for callers that fold per-row coefficients into the weights
    but still divide by the participant sum)."""
    c, p = x.shape
    pad = (-p) % _TILE
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    w = weights.astype(jnp.float32)
    denom = jnp.maximum(w.sum() if denom is None else denom, 1e-12)[None]
    out = pl.pallas_call(
        _wmean_kernel,
        grid=((p + pad) // _TILE,),
        in_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((c, _TILE), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, _TILE), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, p + pad), jnp.float32),
        interpret=auto_interpret(interpret),
    )(w[None, :], xp.astype(jnp.float32), denom)
    return out[0, :p]


# ---------------------------------------------------------------------------
# Fused validation-mask + weighted-reduce epilogue
# ---------------------------------------------------------------------------
#
# The validated aggregation path (``security.validation.stacked_leaf_stats`` +
# weighted mean) touches the stacked [C, P] deltas twice: once to SANITIZE them
# (non-finite -> 0, a [C, P] read + [C, P] write) and once to reduce the sanitized
# stack.  The validity decision itself is O(C) — finiteness, norm bound, z-score
# all collapse to a per-client mask — so the only [C, P]-sized work is sanitize +
# reduce, and those fuse: sanitize in VMEM on the tile just read, contract on the
# MXU, never write the sanitized stack back.  One read pass instead of
# read + write + read.


def _masked_wmean_kernel(coefs_ref, x_ref, out_ref):
    # x block: [C, TILE] f32; coefs: [1, C] (validity mask folded into the
    # normalized weights).  Sanitize IN VMEM (a rejected client's NaN/inf delta
    # must not poison the contraction: 0 * inf = nan, so zero the VALUE, not just
    # the weight), then one MXU pass.
    x = x_ref[:]
    y = jnp.where(jnp.isfinite(x), x, jnp.zeros_like(x))
    out_ref[:] = jax.lax.dot_general(
        coefs_ref[:], y, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_weighted_mean_flat(
    x: jax.Array,
    weights: jax.Array,
    valid: jax.Array,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused validated-aggregation epilogue: ``[C, P] x [C] weights x [C] validity
    -> [P]`` weighted mean over the VALID clients, with non-finite values
    sanitized to zero inside the same pass.

    Equivalent to ``weighted_mean_flat(sanitize(x), weights * valid)`` where
    ``sanitize`` zeroes NaN/inf coordinates — but the sanitized ``[C, P]`` stack
    is never materialized.  ``valid`` is any 0/1 (or boolean) per-client mask;
    an all-invalid cohort degenerates to zeros (denominator floored), matching
    the unfused path's empty-round behavior.
    """
    c, p = x.shape
    w = weights.astype(jnp.float32) * valid.astype(jnp.float32)
    denom = jnp.maximum(w.sum(), 1e-12)
    coefs = w / denom
    pad = (-p) % _TILE
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad)))
    out = pl.pallas_call(
        _masked_wmean_kernel,
        grid=((p + pad) // _TILE,),
        in_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((c, _TILE), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, _TILE), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, p + pad), jnp.float32),
        interpret=auto_interpret(interpret),
    )(coefs[None, :], xp)
    return out[0, :p]


def weighted_mean_tree(
    stacked: Params, weights: jax.Array, interpret: bool | None = None
) -> Params:
    """Drop-in for ``tree_weighted_mean`` on a stacked ``[C, ...]`` pytree: ravel the
    per-client trees into one [C, P] matrix (one reshape per leaf, independent of C),
    run the kernel, unravel."""
    c = weights.shape[0]
    flat = jnp.concatenate(
        [leaf.reshape(c, -1) for leaf in jax.tree.leaves(stacked)], axis=1
    )
    _, unravel = tree_ravel(jax.tree.map(lambda l: l[0], stacked))
    return unravel(weighted_mean_flat(flat, weights, interpret=interpret))
