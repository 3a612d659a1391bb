"""Model zoo tests: shapes, parameter counts, determinism, dropout behavior.

Analog of the reference's model usage in trainer tests; the 1,199,882-param count pins
architectural parity with ``nanofed/models/mnist.py:6-28``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.models import get_model, list_models
from nanofed_tpu.utils import tree_size


def test_registry_contents():
    models = list_models()
    for required in ("mnist_cnn", "resnet8", "resnet18", "linear", "mlp"):
        assert required in models


def test_mnist_cnn_shapes_and_param_count(rng):
    m = get_model("mnist_cnn")
    params = m.init(rng)
    # Parity with the torch CNN: conv1 320, conv2 18496, fc1 1179776, fc2 1290.
    assert tree_size(params) == 1_199_882
    x = jnp.zeros((4, 28, 28, 1))
    out = m.apply(params, x)
    assert out.shape == (4, 10)
    # log_softmax head: rows are log-probabilities.
    np.testing.assert_allclose(np.exp(np.asarray(out)).sum(-1), np.ones(4), rtol=1e-4)


def test_mnist_cnn_deterministic_eval(rng):
    m = get_model("mnist_cnn")
    params = m.init(rng)
    x = jax.random.normal(jax.random.key(1), (2, 28, 28, 1))
    np.testing.assert_array_equal(m.apply(params, x), m.apply(params, x))


def test_mnist_cnn_dropout_train_vs_eval(rng):
    m = get_model("mnist_cnn")
    params = m.init(rng)
    x = jax.random.normal(jax.random.key(1), (2, 28, 28, 1))
    out_eval = m.apply(params, x)
    out_train = m.apply(params, x, train=True, rng=jax.random.key(2))
    assert not np.allclose(np.asarray(out_eval), np.asarray(out_train))
    # Same dropout rng => identical output (pure function).
    out_train2 = m.apply(params, x, train=True, rng=jax.random.key(2))
    np.testing.assert_array_equal(out_train, out_train2)


@pytest.mark.parametrize(
    "name,kwargs,in_shape,n_out",
    [
        ("resnet8", {}, (2, 32, 32, 3), 10),
        ("resnet18", {"num_classes": 100}, (2, 32, 32, 3), 100),
    ],
)
def test_resnets_forward(rng, name, kwargs, in_shape, n_out):
    m = get_model(name, **kwargs)
    params = m.init(rng)
    out = m.apply(params, jnp.zeros(in_shape))
    assert out.shape == (in_shape[0], n_out)
    assert np.isfinite(np.asarray(out)).all()


def test_resnet8_param_scale(rng):
    params = get_model("resnet8").init(rng)
    n = tree_size(params)
    assert 70_000 < n < 90_000  # CIFAR ResNet-8 is ~78k params


def test_init_is_seed_deterministic():
    m = get_model("mlp", in_features=8, hidden=4, num_classes=2)
    p1 = m.init(jax.random.key(42))
    p2 = m.init(jax.random.key(42))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("scope", ["cnn_conv1", "cnn_conv2", "cnn_pool", "cnn_fc1", "cnn_fc2"])
def test_mnist_cnn_scopes_are_in_the_lowered_program(rng, scope):
    """Forward and backward: the benchmark's per-scope metrics read both passes."""
    m = get_model("mnist_cnn")
    params = jax.eval_shape(m.init, rng)
    x = jax.ShapeDtypeStruct((2, 28, 28, 1), jnp.float32)
    loss = lambda p, x: m.apply(p, x, train=True, rng=jax.random.key(1)).sum()
    text = jax.jit(jax.grad(loss)).lower(params, x).as_text(debug_info=True)
    assert f"jvp({scope})/" in text and f"transpose(jvp({scope}))/" in text
