"""Minimal functional neural-net layer library.

The reference builds models on ``torch.nn`` (``nanofed/models/mnist.py:6-28``).  Here models
are pure ``(init, apply)`` functions over explicit parameter pytrees — no module objects, no
mutable state — which is what lets a whole client population train under one
``vmap``/``shard_map`` program.  Layout is NHWC (channels-last), the native layout for TPU
convolutions; matmuls/convs stay large and batched so XLA tiles them onto the MXU.

Normalization is GroupNorm rather than BatchNorm: batch statistics are both mutable state
(breaking pure-function training) and statistically wrong under non-IID federated clients,
so GroupNorm is the standard choice in FL (cf. FedProx/LEAF practice).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from nanofed_tpu.core.types import Params, PRNGKey

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def _fan_in_out(shape: Sequence[int]) -> tuple[int, int]:
    if len(shape) == 2:  # dense [in, out]
        return shape[0], shape[1]
    # conv [kh, kw, cin, cout]
    receptive = math.prod(shape[:-2])
    return shape[-2] * receptive, shape[-1] * receptive


def kaiming_uniform(rng: PRNGKey, shape: Sequence[int], dtype=jnp.float32) -> jax.Array:
    """Kaiming uniform with torch's default bound: torch initializes Conv2d/Linear with
    ``kaiming_uniform_(a=sqrt(5))`` which reduces to U(-1/sqrt(fan_in), 1/sqrt(fan_in)),
    so training dynamics are comparable to the reference CNN."""
    fan_in, _ = _fan_in_out(shape)
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(rng, shape, dtype, -bound, bound)


def uniform_bias(rng: PRNGKey, fan_in: int, shape: Sequence[int], dtype=jnp.float32) -> jax.Array:
    bound = 1.0 / math.sqrt(fan_in) if fan_in > 0 else 0.0
    return jax.random.uniform(rng, shape, dtype, -bound, bound)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------


def dense_init(rng: PRNGKey, in_features: int, out_features: int, dtype=jnp.float32) -> Params:
    k_w, k_b = jax.random.split(rng)
    return {
        "kernel": kaiming_uniform(k_w, (in_features, out_features), dtype),
        "bias": uniform_bias(k_b, in_features, (out_features,), dtype),
    }


def dense(params: Params, x: jax.Array) -> jax.Array:
    return x @ params["kernel"] + params["bias"]


# ---------------------------------------------------------------------------
# Embedding lookup
# ---------------------------------------------------------------------------

#: Most bytes one accumulator of the lookup's gradient may hold, in the table's dtype.
#: The gradient of ``table[tokens]`` is a scatter-add of the cotangent's rows into zeros
#: of the table's shape, and XLA's TPU scatter is fast where memory-space assignment
#: places that accumulator in the chip's on-chip memory (128 MiB on a v5e).  84 MiB is the
#: largest accumulator measured whole at the fast rate: the hybrid's ``[16384, 2688]``
#: bfloat16 slice takes 0.43 us a row, GPT-2's 74 MiB 0.30, while SmallThinker's 185 MiB
#: stays in HBM and takes 2.0 (PERF.md section 6, PR 32; the float32 convert included).
#: Its two bands of 93 MiB are still placed on chip, but four of 46 MiB ran 0.7 ms a step
#: faster (``tests/unit/ops/test_attention_aot.py`` compiles both).
EMBED_BAND_BYTES = 84 * 2**20

_LANES = 128  # a band is whole lane tiles of the table's minor axis


def embed_bands(rows: int, width: int, itemsize: int) -> int:
    """Column bands the gradient of a ``[rows, width]`` lookup accumulates in: the
    fewest whole-tile bands whose accumulator fits :data:`EMBED_BAND_BYTES`, the finest
    whole-tile split where none does, 1 where the width is not whole tiles."""
    tiles = width // _LANES if width % _LANES == 0 else 1
    splits = [k for k in range(1, tiles + 1) if tiles % k == 0]
    return next((k for k in splits if rows * (width // k) * itemsize <= EMBED_BAND_BYTES),
                splits[-1])


def embed_rows(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """``table[tokens]``: ``[V, D]`` and int ``[...]`` -> ``[..., D]``.

    Where the table's gradient fits one accumulator (:func:`embed_bands` says 1) this IS
    the indexing expression, autodiff's scatter-add and all.  A larger table gets the
    same gradient one column band at a time (:func:`_rows_in_bands`); the shape decides,
    as ``ops.attention.engages`` does by sequence length."""
    bands = embed_bands(*table.shape, table.dtype.itemsize)
    with jax.named_scope("token_embed"):
        return table[tokens] if bands == 1 else _rows_in_bands(table, tokens, bands)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_in_bands(table, tokens, bands):
    return table[tokens]


def _rows_in_bands_fwd(table, tokens, bands):
    # The table's rows, dtype and mesh type ride on an empty slice: the table is not kept.
    return table[tokens], (tokens, table[:, :0])


def _rows_in_bands_bwd(bands, saved, g):
    """The transpose of the lookup, band by band: each band's scatter-add (autodiff's own,
    on ``width / bands`` columns: same dtype, same sums over a row's duplicates) is
    finished and stored before the next begins, so one accumulator is live at a time."""
    tokens, like = saved
    band = g.shape[-1] // bands
    # A band of the table, typed as the table is: inside ``shard_map`` its cotangent then
    # varies over the mesh axes the table varies over, as the custom rule's output must.
    accumulate = jax.linear_transpose(
        lambda t: t[tokens], jnp.zeros_like(like, shape=(like.shape[0], band)))

    def one_band(_, b):
        return None, accumulate(lax.dynamic_slice_in_dim(g, b * band, band, axis=-1))[0]

    with jax.named_scope("embed_grad"):
        _, stacked = lax.scan(one_band, None, jnp.arange(bands))
        return jnp.moveaxis(stacked, 0, -2).reshape(like.shape[0], -1), None


_rows_in_bands.defvjp(_rows_in_bands_fwd, _rows_in_bands_bwd)


# ---------------------------------------------------------------------------
# Conv2d (NHWC, HWIO kernels)
# ---------------------------------------------------------------------------


def conv2d_init(
    rng: PRNGKey,
    in_channels: int,
    out_channels: int,
    kernel_size: int | tuple[int, int],
    dtype=jnp.float32,
    use_bias: bool = True,
) -> Params:
    kh, kw = (kernel_size, kernel_size) if isinstance(kernel_size, int) else kernel_size
    k_w, k_b = jax.random.split(rng)
    params = {"kernel": kaiming_uniform(k_w, (kh, kw, in_channels, out_channels), dtype)}
    if use_bias:
        params["bias"] = uniform_bias(k_b, in_channels * kh * kw, (out_channels,), dtype)
    return params


def conv2d(
    params: Params,
    x: jax.Array,
    *,
    stride: int | tuple[int, int] = 1,
    padding: str = "VALID",
) -> jax.Array:
    """NHWC convolution via ``lax.conv_general_dilated`` — lowers straight to the MXU."""
    strides = (stride, stride) if isinstance(stride, int) else stride
    out = lax.conv_general_dilated(
        x,
        params["kernel"],
        window_strides=strides,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "bias" in params:
        out = out + params["bias"]
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------


def max_pool(x: jax.Array, window: int = 2, stride: int | None = None) -> jax.Array:
    stride = window if stride is None else stride
    return lax.reduce_window(
        x,
        -jnp.inf,
        lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )


def avg_pool(x: jax.Array, window: int = 2, stride: int | None = None) -> jax.Array:
    stride = window if stride is None else stride
    summed = lax.reduce_window(
        x,
        0.0,
        lax.add,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )
    return summed / (window * window)


def global_avg_pool(x: jax.Array) -> jax.Array:
    """[N, H, W, C] -> [N, C]."""
    return jnp.mean(x, axis=(1, 2))


# ---------------------------------------------------------------------------
# Dropout (functional — rng passed in, no state)
# ---------------------------------------------------------------------------


def dropout(rng: PRNGKey | None, x: jax.Array, rate: float, train: bool) -> jax.Array:
    """Inverted dropout; identity when ``train`` is False or rate == 0.

    The reference model uses rates .25/.5 (``nanofed/models/mnist.py:12-13``).
    """
    if not train or rate == 0.0:
        return x
    if rng is None:
        raise ValueError("dropout in train mode requires an rng key")
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


# ---------------------------------------------------------------------------
# GroupNorm
# ---------------------------------------------------------------------------


def group_norm_init(num_channels: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((num_channels,), dtype), "bias": jnp.zeros((num_channels,), dtype)}


def group_norm(params: Params, x: jax.Array, num_groups: int = 8, eps: float = 1e-5) -> jax.Array:
    """GroupNorm over NHWC input."""
    n, h, w, c = x.shape
    g = min(num_groups, c)
    while c % g != 0:  # pragma: no cover - configs keep c % g == 0
        g -= 1
    xg = x.reshape(n, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + eps)
    return xg.reshape(n, h, w, c) * params["scale"] + params["bias"]


# ---------------------------------------------------------------------------
# Activations / outputs
# ---------------------------------------------------------------------------

relu = jax.nn.relu
log_softmax = jax.nn.log_softmax


def flatten(x: jax.Array) -> jax.Array:
    """[N, ...] -> [N, prod(...)]."""
    return x.reshape(x.shape[0], -1)
