"""The held experts' MLPs as a grouped matmul over row tiles, as Pallas TPU kernels.

Rows arrive laid out by expert in whole tiles of ``tile`` rows (``models.experts.dispatch``):
tile ``i`` belongs to expert ``tile_expert[i]`` and only the first ``n_tiles`` tiles are in
use.  Both kernels walk the tiles in order with ``tile_expert`` and ``n_tiles`` prefetched
as scalars: a tile's operands are blocks chosen by them, so an expert's ``w_in`` and
``w_out`` are fetched into VMEM when its first tile comes up and stay there while its tiles
run, and a step past ``n_tiles`` names the blocks of the step before it, fetches nothing
and computes nothing.

Forward (:func:`expert_tiles`): ``gates * (act(rows @ w_in[e]) @ w_out[e])`` a tile; the
``[tile, 2f]`` product and the ``[tile, f]`` hidden state live in VMEM alone.

Backward (:func:`expert_tiles_grads`), from the rows, their gates and the cotangent's
rows: the product and the activation are recomputed a tile, ``d_gates = <hidden, dy
w_out^T>`` (which is ``<hidden w_out, dy>`` with one product less), ``d_pre``, ``d_rows =
d_pre w_in^T``, and the two weight gradients ``rows^T d_pre`` and ``hidden^T (gates dy)``
**accumulate in float32 VMEM scratch over the expert's tiles and are written once an
expert**, in the weights' dtype.  Every held expert has a tile (the dispatch gives an
expert nobody picked one empty tile), so every block of the two gradients is written.

Products take their operands in the inputs' dtype and accumulate in float32; the
activation, the gates' scaling and ``d_gates`` are float32.

One expert's matrices (twice, for the pipeline, where that fits), its two float32
accumulators and the gradients' output blocks have to fit the scoped VMEM the kernels ask
for (:func:`engages` reckons it from the shapes, :data:`VMEM_LIMIT`): the widest pair of
the benchmark's cells, ``[2048, 2816]`` + ``[1408, 2048]`` bfloat16, is reckoned at 85 MiB in the
backward kernel with single buffers.  A width need not be whole lanes (the compiler pads
a row: the hybrid's experts are 1856 wide); rows have to be whole sublane tiles.

The four entry points are module-level ``jax.jit`` functions: a model that loops over its
layers in Python traces each kernel once a signature and its lowered program holds each
kernel's module once, whatever the number of layers (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanofed_tpu.ops.attention import _struct

_F32 = jnp.float32
#: ``a @ b^T`` and ``a^T @ b``.
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
#: Scoped VMEM a kernel here may ask for, of a v5e's 128 MiB.
VMEM_LIMIT = 100 * 1024 * 1024
#: Rows of a tile: the largest of these whose float32 intermediates (the product, its
#: cotangent, the hidden state: ``[tile, f_in]`` some five times over) stay under
#: :data:`_TILE_SCRATCH`.  A larger tile amortizes what a tile costs whatever its rows (a
#: grid step, and in the backward kernel one pass over the expert's two float32
#: accumulators); a smaller one pads an expert's last tile with fewer empty rows.
TILES = (512, 256, 128)
_TILE_SCRATCH = 16 * 1024 * 1024


def tile_rows(d: int, f_in: int) -> int:
    """Rows of a tile, and of a block of the layout, for experts of ``[d, f_in]``: 256 at
    the widths of the benchmark's five cells (2048 to 2816), 512 under 1639."""
    wide = max(d, f_in)
    return next((t for t in TILES if 5 * 4 * t * wide <= _TILE_SCRATCH), TILES[-1])


def _vmem_bytes(tile: int, d: int, f_in: int, f: int, itemsize: int, *, backward: bool,
                w_buffers: int) -> int:
    """What a kernel holds in VMEM at once: the expert's matrices ``w_buffers`` times, a
    tile's operands and results twice (the pipeline), its float32 intermediates and, in the
    backward kernel, the float32 accumulators and one output block of each gradient.  A
    row of any array takes whole lanes."""
    d, f_in, f = (-(-width // 128) * 128 for width in (d, f_in, f))
    weights = (d * f_in + f * d) * itemsize
    rows = tile * d * itemsize
    scratch = 5 * 4 * tile * max(d, f_in)
    if not backward:
        return w_buffers * weights + 2 * 2 * rows + scratch
    return w_buffers * weights + 2 * 3 * rows + scratch + 4 * (d * f_in + f * d) + weights


def _w_buffers(tile, d, f_in, f, itemsize, *, backward: bool) -> int | None:
    """2 where the pipeline's second copy of an expert's matrices fits (the next expert's
    are fetched while this one's tiles run), 1 where one copy does, ``None`` where none."""
    return next((b for b in (2, 1) if _vmem_bytes(
        tile, d, f_in, f, itemsize, backward=backward, w_buffers=b) <= VMEM_LIMIT), None)


def engages(tile: int, d: int, f_in: int, f: int, dtype) -> bool:
    """Whether the kernels take experts of ``[d, f_in]`` and ``[f, d]`` in tiles of ``tile``
    rows: the rows of a tile and of each matrix whole sublane tiles of the dtype (8 rows of
    32 bits: 16 of bfloat16; a row's width is the compiler's to pad: the hybrid's 1856), and
    an expert's matrices with their accumulators inside :data:`VMEM_LIMIT`."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(4 // itemsize, 1)
    return (all(rows % sublanes == 0 for rows in (tile, d, f)) and f_in % f == 0
            and _w_buffers(tile, d, f_in, f, itemsize, backward=True) is not None)


def _at(i, nt):
    """The tile a grid step works on: itself while in use, else the last in use (whose
    blocks are in VMEM already: nothing is fetched)."""
    return jnp.maximum(jnp.minimum(i, nt[0] - 1), 0)


def _specs(tile, w_buffers):
    """Block specs: a tile's rows ``[tile, width]``, and the tile's expert's matrices."""
    rows = lambda width: pl.BlockSpec((tile, width), lambda i, te, nt: (_at(i, nt), 0))
    once = {} if w_buffers == 2 else {"pipeline_mode": pl.Buffered(1)}
    expert = lambda a, b, **kw: pl.BlockSpec(
        (None, a, b), lambda i, te, nt: (te[_at(i, nt)], 0, 0), **kw)
    return rows, expert, once


def _fwd_kernel(te_ref, nt_ref, x_ref, g_ref, wi_ref, wo_ref, y_ref, *, activation):
    @pl.when(pl.program_id(0) < nt_ref[0])
    def _():
        pre = jnp.dot(x_ref[...], wi_ref[...], preferred_element_type=_F32)
        hidden = activation.apply(pre).astype(wo_ref.dtype)
        y = jnp.dot(hidden, wo_ref[...], preferred_element_type=_F32)
        y_ref[...] = (y * g_ref[...]).astype(y_ref.dtype)


def _bwd_kernel(te_ref, nt_ref, x_ref, g_ref, dy_ref, wi_ref, wo_ref,
                dx_ref, dg_ref, dwi_ref, dwo_ref, acc_i, acc_o, *, activation):
    i, nt, last_step = pl.program_id(0), nt_ref[0], pl.num_programs(0) - 1
    live = i < nt
    expert = te_ref[i]
    first = (i == 0) | (te_ref[jnp.maximum(i - 1, 0)] != expert)
    last = (i == nt - 1) | (te_ref[jnp.minimum(i + 1, last_step)] != expert)

    @pl.when(live & first)
    def _():
        acc_i[...] = jnp.zeros_like(acc_i)
        acc_o[...] = jnp.zeros_like(acc_o)

    @pl.when(live)
    def _():
        x, dy, g = x_ref[...], dy_ref[...], g_ref[...]
        hidden, pull = activation.with_grad(jnp.dot(x, wi_ref[...], preferred_element_type=_F32))
        d_hidden = lax.dot_general(dy, wo_ref[...], _NT, preferred_element_type=_F32)
        dg_ref[...] = jnp.sum(hidden * d_hidden, axis=-1, keepdims=True)
        d_pre = pull(d_hidden * g).astype(x.dtype)
        dx_ref[...] = lax.dot_general(
            d_pre, wi_ref[...], _NT, preferred_element_type=_F32).astype(dx_ref.dtype)
        acc_i[...] += lax.dot_general(x, d_pre, _TN, preferred_element_type=_F32)
        acc_o[...] += lax.dot_general(hidden.astype(dy.dtype), (dy * g).astype(dy.dtype), _TN,
                                      preferred_element_type=_F32)

    @pl.when(live & last)
    def _():
        dwi_ref[...] = acc_i[...].astype(dwi_ref.dtype)
        dwo_ref[...] = acc_o[...].astype(dwo_ref.dtype)


def _scoped(kernel, *operands):
    """The call under the expert loop's own ``jax.named_scope``: a jitted entry point is
    lowered once for all its sites with a name path that starts at the entry point, so the
    scope the device trace is read by (``moe_experts``) is said inside it too."""
    with jax.named_scope("moe_experts"):
        return kernel(*operands)


def _shapes(rows, w_in, w_out, tile):
    (n_rows, d), (_, _, f_in), f = rows.shape, w_in.shape, w_out.shape[1]
    if n_rows % tile or w_in.shape[1] != d or w_out.shape[2] != d:
        raise ValueError(f"rows {rows.shape} are not whole tiles of {tile} of w_in's width, or "
                         f"w_in {w_in.shape} and w_out {w_out.shape} do not chain")
    return n_rows // tile, d, f_in, f


@functools.partial(jax.jit, static_argnames=("activation", "tile", "interpret"))
def expert_tiles(rows, gates, tile_expert, n_tiles, w_in, w_out, *, activation, tile: int,
                 interpret: bool = False):
    """``gates * (act(rows @ w_in[e]) @ w_out[e])`` [rows, d], ``e`` the expert of a row's
    tile.  ``rows`` [R, d], ``gates`` [R, 1] float32, ``tile_expert`` [R / tile] int32,
    ``n_tiles`` [1] int32, ``w_in`` [held, d, f_in], ``w_out`` [held, f, d]; ``activation``
    a ``models.experts.Activation``.  Rows of tiles past ``n_tiles`` are not written."""
    steps, d, f_in, f = _shapes(rows, w_in, w_out, tile)
    w_buffers = _w_buffers(tile, d, f_in, f, w_in.dtype.itemsize, backward=False)
    tile_of, expert, once = _specs(tile, w_buffers)
    operands = (rows, gates, w_in, w_out)
    return _scoped(pl.pallas_call(
        functools.partial(_fwd_kernel, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,),
            in_specs=[tile_of(d), tile_of(1), expert(d, f_in, **once), expert(f, d, **once)],
            out_specs=tile_of(d)),
        out_shape=_struct(rows.shape, rows.dtype, *operands),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_tiles_fwd",
    ), tile_expert, n_tiles, *operands)


@functools.partial(jax.jit, static_argnames=("activation", "tile", "interpret"))
def expert_tiles_grads(rows, gates, d_out, tile_expert, n_tiles, w_in, w_out, *, activation,
                       tile: int, interpret: bool = False):
    """The cotangents of :func:`expert_tiles`' ``rows``, ``gates``, ``w_in`` and ``w_out``
    for the output's cotangent ``d_out`` [R, d]: ``(d_rows [R, d], d_gates [R, 1] float32,
    d_w_in, d_w_out)``.  Rows of tiles past ``n_tiles`` are not written; every expert of
    ``w_in`` needs a tile among the first ``n_tiles`` for its gradients to be."""
    steps, d, f_in, f = _shapes(rows, w_in, w_out, tile)
    w_buffers = _w_buffers(tile, d, f_in, f, w_in.dtype.itemsize, backward=True)
    tile_of, expert, once = _specs(tile, w_buffers)
    operands = (rows, gates, d_out, w_in, w_out)
    written_once = {"pipeline_mode": pl.Buffered(1)}
    return _scoped(pl.pallas_call(
        functools.partial(_bwd_kernel, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,),
            in_specs=[tile_of(d), tile_of(1), tile_of(d), expert(d, f_in, **once),
                      expert(f, d, **once)],
            out_specs=[tile_of(d), tile_of(1), expert(d, f_in, **written_once),
                       expert(f, d, **written_once)],
            scratch_shapes=[pltpu.VMEM((d, f_in), _F32), pltpu.VMEM((f, d), _F32)]),
        out_shape=[_struct(rows.shape, rows.dtype, *operands),
                   _struct(gates.shape, _F32, *operands),
                   _struct(w_in.shape, w_in.dtype, *operands),
                   _struct(w_out.shape, w_out.dtype, *operands)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_tiles_bwd",
    ), tile_expert, n_tiles, *operands)
