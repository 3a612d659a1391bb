"""Plain reference of the MNIST CNN (nanofed ``models/mnist.py``; McMahan et al. 2017).

conv 3x3 1->32, relu, conv 3x3 32->64, relu, max-pool 2, dropout .25, flatten 9216,
fc 9216->128, relu, dropout .5, fc 128->10, log-softmax.  NHWC, HWIO kernels.  Straight
``jax.numpy``; imports nothing of the program.

``q`` rounds a matmul/conv operand to the precision under test and returns float32: the
identity for the reference, a float8 round trip for the lower-precision control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

INPUT_SHAPE = (28, 28, 1)
NUM_CLASSES = 10
TOKEN_STREAM = False


def _uniform(key, shape, fan_in):
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def init_params(key, model_kwargs):
    """Weights from the seed: U(-1/sqrt(fan_in), 1/sqrt(fan_in)), torch's default."""
    del model_kwargs
    k = jax.random.split(key, 8)
    return {
        "conv1": {"kernel": _uniform(k[0], (3, 3, 1, 32), 9), "bias": _uniform(k[1], (32,), 9)},
        "conv2": {"kernel": _uniform(k[2], (3, 3, 32, 64), 288), "bias": _uniform(k[3], (64,), 288)},
        "fc1": {"kernel": _uniform(k[4], (9216, 128), 9216), "bias": _uniform(k[5], (128,), 9216)},
        "fc2": {"kernel": _uniform(k[6], (128, 10), 128), "bias": _uniform(k[7], (10,), 128)},
    }


def _conv(p, x, q):
    out = lax.conv_general_dilated(
        q(x), q(p["kernel"]), window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return out + p["bias"]


def _dropout(key, x, rate):
    keep = 1.0 - rate
    return jnp.where(jax.random.bernoulli(key, keep, x.shape), x / keep, 0.0)


def log_probs(params, x, key, model_kwargs, q=lambda t: t):
    """``[N, 10]`` log-probabilities in training mode: ``key`` splits in two, the first
    half masks the pooled map, the second the hidden layer."""
    del model_kwargs
    d1, d2 = jax.random.split(key)
    x = jax.nn.relu(_conv(params["conv1"], x, q))
    x = jax.nn.relu(_conv(params["conv2"], x, q))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = _dropout(d1, x, 0.25).reshape(x.shape[0], -1)
    x = jax.nn.relu(q(x) @ q(params["fc1"]["kernel"]) + params["fc1"]["bias"])
    x = _dropout(d2, x, 0.5)
    x = q(x) @ q(params["fc2"]["kernel"]) + params["fc2"]["bias"]
    return jax.nn.log_softmax(x)
