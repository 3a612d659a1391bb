"""MNIST CNN at architectural parity with the reference.

Reference: ``nanofed/models/mnist.py:6-28`` — conv(1→32, 3x3) → relu → conv(32→64, 3x3) →
relu → maxpool(2) → dropout(.25) → flatten(9216) → fc(9216→128) → relu → dropout(.5) →
fc(128→10) → log_softmax.  Same graph here, NHWC and functional; ~1.2M params.
"""

from __future__ import annotations

import jax

from nanofed_tpu import nn
from nanofed_tpu.core.types import Params, PRNGKey
from nanofed_tpu.models.base import Model, register_model

INPUT_SHAPE = (28, 28, 1)
NUM_CLASSES = 10


def init(rng: PRNGKey) -> Params:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "conv1": nn.conv2d_init(k1, 1, 32, 3),
        "conv2": nn.conv2d_init(k2, 32, 64, 3),
        "fc1": nn.dense_init(k3, 9216, 128),
        "fc2": nn.dense_init(k4, 128, NUM_CLASSES),
    }


def apply(
    params: Params, x: jax.Array, *, train: bool = False, rng: PRNGKey | None = None
) -> jax.Array:
    """Forward pass; returns log-probabilities like the reference's ``log_softmax`` head.

    ``x``: [N, 28, 28, 1] float.
    """
    if train and rng is not None:
        d1, d2 = jax.random.split(rng)
    else:
        d1 = d2 = None
    with jax.named_scope("cnn_conv1"):
        x = nn.relu(nn.conv2d(params["conv1"], x))  # [N, 26, 26, 32]
    with jax.named_scope("cnn_conv2"):
        x = nn.relu(nn.conv2d(params["conv2"], x))  # [N, 24, 24, 64]
    with jax.named_scope("cnn_pool"):
        x = nn.max_pool(x, 2)  # [N, 12, 12, 64]
        x = nn.dropout(d1, x, 0.25, train)
        x = nn.flatten(x)  # [N, 9216]
    with jax.named_scope("cnn_fc1"):
        x = nn.relu(nn.dense(params["fc1"], x))
        x = nn.dropout(d2, x, 0.5, train)
    with jax.named_scope("cnn_fc2"):
        x = nn.dense(params["fc2"], x)
        return nn.log_softmax(x)


@register_model("mnist_cnn")
def mnist_cnn() -> Model:
    return Model(
        name="mnist_cnn",
        init=init,
        apply=apply,
        input_shape=INPUT_SHAPE,
        num_classes=NUM_CLASSES,
    )
