"""Fixed-point quantization + seeded masking kernels (the SecAgg inner loop on-device).

The host-path secure aggregation (``security.secure_agg``) quantizes updates to uint32
fixed point and adds PRG masks with numpy — fine for small models, but a 100 M-param
update means several 400 MB host passes per client per round.  These kernels run the same
arithmetic on-chip: int32 round-to-nearest (values are bounded well inside +/-2^31 by the
SecAgg config contract), bitcast to uint32 for exact modular arithmetic, and mask
generation from the on-core PRNG (``pltpu.prng_seed``/``prng_random_bits``) so masks are
never materialized in host memory.  Arrays are processed as a grid of
``[_BLOCK_ROWS, _LANES]`` VMEM tiles, so operand size is bounded by the tile, not VMEM.

NOTE: the on-core PRNG stream differs from the host path's Philox stream, so TPU-masked
updates unmask only against TPU-generated masks (all parties use the same kernel) — the
two paths are deliberately not wire-compatible.  Parity tests pin quantize/dequantize
round-trips and exact mask cancellation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanofed_tpu.ops._common import auto_interpret

_LANES = 512
_BLOCK_ROWS = 256  # 256 x 512 x 4B = 512 KB per operand block in VMEM


def _pad_grid(x: jax.Array) -> tuple[jax.Array, int, int]:
    """Flat vector -> [rows, _LANES] padded so rows divide _BLOCK_ROWS; returns
    (2-D array, real length, grid size)."""
    n = x.shape[0]
    lane_pad = (-n) % _LANES
    x2 = jnp.pad(x, (0, lane_pad)).reshape(-1, _LANES)
    rows = x2.shape[0]
    row_pad = (-rows) % _BLOCK_ROWS
    x2 = jnp.pad(x2, ((0, row_pad), (0, 0)))
    return x2, n, x2.shape[0] // _BLOCK_ROWS


def _block_spec():
    return pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _quantize_kernel(scale_ref, x_ref, out_ref):
    scaled = jnp.round(x_ref[:] * scale_ref[0]).astype(jnp.int32)
    out_ref[:] = pltpu.bitcast(scaled, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("frac_bits", "interpret"))
def quantize_u32(
    x: jax.Array, frac_bits: int = 16, interpret: bool | None = None
) -> jax.Array:
    """Flat f32 vector -> uint32 fixed point (two's complement encodes sign)."""
    x2, n, grid = _pad_grid(x.astype(jnp.float32))
    scale = jnp.float32(1 << frac_bits)[None]
    out = pl.pallas_call(
        _quantize_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), _block_spec()],
        out_specs=_block_spec(),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.uint32),
        interpret=auto_interpret(interpret),
    )(scale, x2)
    return out.reshape(-1)[:n]


def _dequantize_kernel(inv_scale_ref, q_ref, out_ref):
    centered = pltpu.bitcast(q_ref[:], jnp.int32)  # uint32 -> signed two's complement
    out_ref[:] = centered.astype(jnp.float32) * inv_scale_ref[0]


@functools.partial(jax.jit, static_argnames=("frac_bits", "interpret"))
def dequantize_u32(
    q: jax.Array, frac_bits: int = 16, interpret: bool | None = None
) -> jax.Array:
    """uint32 fixed point -> f32 (centered / signed interpretation)."""
    q2, n, grid = _pad_grid(q)
    inv = jnp.float32(1.0 / (1 << frac_bits))[None]
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), _block_spec()],
        out_specs=_block_spec(),
        out_shape=jax.ShapeDtypeStruct(q2.shape, jnp.float32),
        interpret=auto_interpret(interpret),
    )(inv, q2)
    return out.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# Fused dequant + weighted accumulate (the q8/topk aggregation epilogue)
# ---------------------------------------------------------------------------
#
# The compressed-aggregation path dequantizes int8 client deltas to float32 in one
# program and reduces them in another (codec ``decode_delta_q8`` then the weighted
# mean): the [C, P] float32 intermediate is written to and re-read from memory just
# to be summed — at int8 payload q, that is 1 byte read + 4 written + 4 re-read per
# element where 1 read suffices.  The fusion is algebraic: the per-client dequant
# scale is a per-ROW multiplier, so it folds into the reduce weights exactly —
#
#     out[p] = base[p] + sum_c (w_c / denom) * s_c * q[c, p]
#            = base[p] + coefs @ q,      coefs_c = w_c * s_c / denom  (an O(C) vector)
#
# — and the kernel reads the int8 stack ONCE, converts in VMEM, and contracts on the
# MXU.  The dequantized [C, P] float32 array never exists in HBM.  The same kernel
# serves the topk8 path (decoded dense int8 rows, zeros off the shipped
# coordinates).  Registered next to its unfused counterpart in the autotuner's
# program catalog (``tuning.epilogues``) so the bytes-accessed drop is a measured
# row in the cost table, not a claim.

_Q8_SUBLANES = 32  # int8 min tile is (32, 128): pad the client axis to full sublanes


def _dequant_acc_kernel(coefs_ref, q_ref, base_ref, out_ref):
    # q block: [C_pad, TILE] int8; coefs: [1, C_pad] (dequant scale folded in);
    # base/out: [1, TILE].  One int8 read -> f32 convert in VMEM -> MXU contraction.
    # HIGHEST precision for the same reason as ops.reduce._wmean_kernel: bf16 MXU
    # passes would cost ~3 decimal digits on the aggregate.
    x = q_ref[:].astype(jnp.float32)
    acc = jax.lax.dot_general(
        coefs_ref[:], x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    out_ref[:] = base_ref[:] + acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_accumulate_flat(
    q: jax.Array,
    scales: jax.Array,
    weights: jax.Array,
    base: jax.Array,
    denom: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused q8/topk aggregation epilogue: ``[C, P] int8 x [C] scales x [C] weights
    + [P] base -> [P]`` in ONE pass over the quantized stack.

    Computes ``base + Σ_c (w_c / denom) · s_c · q[c, :]`` — the weighted FedAvg
    mean of dequantized client deltas applied to the published base — without ever
    materializing the dequantized ``[C, P]`` float32 stack (the per-client scale
    is a row multiplier, so it folds into the reduce coefficients).  ``denom``
    defaults to ``Σ w`` (the weighted mean); pass an explicit denominator to reuse
    pre-normalized coefficient vectors (e.g. FedBuff staleness discounts).

    All-zero weights degenerate safely (denominator floored at 1e-12): the result
    is ``base`` unchanged, matching the round engine's empty-round identity.
    """
    c, p = q.shape
    if q.dtype != jnp.int8:
        raise TypeError(f"q must be int8 (the wire dtype), got {q.dtype}")
    w = weights.astype(jnp.float32)
    denom = jnp.maximum(w.sum() if denom is None else denom, 1e-12)
    coefs = w * scales.astype(jnp.float32) / denom
    # Pad clients to full int8 sublanes (zero coef rows are exact no-ops) and
    # columns to the lane tile.
    c_pad = (-c) % _Q8_SUBLANES
    lane_pad = (-p) % _LANES
    qp = jnp.pad(q, ((0, c_pad), (0, lane_pad)))
    basep = jnp.pad(base.astype(jnp.float32), (0, lane_pad))
    coefsp = jnp.pad(coefs, (0, c_pad))
    cp = c + c_pad
    out = pl.pallas_call(
        _dequant_acc_kernel,
        grid=((p + lane_pad) // _LANES,),
        in_specs=[
            pl.BlockSpec((1, cp), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((cp, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, p + lane_pad), jnp.float32),
        interpret=auto_interpret(interpret),
    )(coefsp[None, :], qp, basep[None, :])
    return out[0, :p]


def _mask_kernel(seed_ref, sign_ref, q_ref, out_ref):
    # Per-block stream from (128-bit caller seed, block index) — identical for both
    # parties of a pair.  Mosaic seeds the core PRNG with at most TWO words, so the
    # four seed words feed two streams that are XORed: every seed bit and the block
    # index (spread by an odd multiplier so neighbouring blocks do not get
    # neighbouring seeds) reaches the mask.
    mix = pl.program_id(0) * jnp.int32(-1640531527)  # 0x9E3779B9

    def stream(a, b):
        pltpu.prng_seed(a, b)
        return pltpu.bitcast(pltpu.prng_random_bits(q_ref.shape), jnp.uint32)

    bits = stream(seed_ref[0] ^ mix, seed_ref[1]) ^ stream(seed_ref[2], seed_ref[3] ^ mix)
    # sign +1: add mask; sign -1: subtract (uint32 wraps mod 2^32 either way).
    out_ref[:] = jnp.where(sign_ref[0] > 0, q_ref[:] + bits, q_ref[:] - bits)


def _seed_words(seed: jax.Array) -> jax.Array:
    """Normalize a scalar or [4]-vector seed to 4 int32 words (128-bit seed space —
    a 32-bit seed would make the pairwise masks brute-forceable)."""
    seed = jnp.asarray(seed, jnp.int32)
    if seed.ndim == 0:
        seed = jnp.stack([seed, jnp.int32(0), jnp.int32(0), jnp.int32(0)])
    if seed.shape != (4,):
        raise ValueError(f"seed must be a scalar or [4] int32 vector, got {seed.shape}")
    return seed


@functools.partial(jax.jit, static_argnames=("interpret",))
def add_mask(
    q: jax.Array, seed: jax.Array, sign: jax.Array, interpret: bool | None = None
) -> jax.Array:
    """Add (+1) or subtract (-1) the PRG mask expanded from ``seed`` (int32 scalar or
    [4] int32 vector = 128 seed bits).

    Two parties calling with the same seed and opposite signs produce masks that cancel
    exactly in the uint32 sum — the pairwise SecAgg invariant, on-chip.  On non-TPU
    backends the mask comes from ``jax.random`` instead of the core PRNG (the interpreter
    has no ``prng_seed``); either way the stream is deterministic per seed *per backend*.
    """
    words = _seed_words(seed)
    if auto_interpret(interpret):
        # All four seed words are folded through the threefry hash (not XOR-collapsed,
        # which would alias distinct seeds).  NOTE: threefry2x32's keyspace is 64 bits,
        # so this fallback is for functional testing on CPU/GPU — the security-bearing
        # 128-bit-seeded path is the TPU kernel below.
        folded = words.astype(jnp.uint32)
        key = jax.random.wrap_key_data(folded[:2])
        key = jax.random.fold_in(jax.random.fold_in(key, folded[2]), folded[3])
        mask = jax.random.bits(key, q.shape, jnp.uint32)
        return jnp.where(sign > 0, q + mask, q - mask)
    q2, n, grid = _pad_grid(q)
    out = pl.pallas_call(
        _mask_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _block_spec(),
        ],
        out_specs=_block_spec(),
        out_shape=jax.ShapeDtypeStruct(q2.shape, jnp.uint32),
        interpret=False,
    )(words, jnp.asarray(sign, jnp.int32)[None], q2)
    return out.reshape(-1)[:n]
