"""The local step's ``jax.named_scope``s: what the benchmark's per-scope metrics
(``benchmark/scope_metrics``) read of ``trainer.local`` has to stand in the lowered fit,
forward and, where a gradient flows through it, backward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.core.types import ClientData
from nanofed_tpu.models import get_model
from nanofed_tpu.trainer import TrainingConfig
from nanofed_tpu.trainer.local import make_local_fit


def _lowered_fit(compute_dtype, **training):
    model = get_model("mlp", in_features=8, hidden=4, num_classes=3)
    r = np.random.default_rng(0)
    data = ClientData(x=jnp.asarray(r.normal(size=(8, 8)), jnp.float32),
                      y=jnp.asarray(r.integers(0, 3, size=(8,))), mask=jnp.ones((8,)))
    fit = make_local_fit(model.apply, TrainingConfig(
        batch_size=4, local_epochs=1, learning_rate=0.1, compute_dtype=compute_dtype, **training))
    params = model.init(jax.random.key(0))
    return jax.jit(fit).lower(params, data, jax.random.key(1)).as_text(debug_info=True)


@pytest.fixture(scope="module", params=[None, "bfloat16"])
def lowered(request):
    return request.param, _lowered_fit(request.param)


@pytest.mark.parametrize("path", [
    "batch_gather/gather", "batch_gather/dynamic_slice",
    "jvp(nll_loss)/reduce_sum", "transpose(jvp(nll_loss))/",
    "optimizer_step/jit(_where)", "optimizer_step/add",
])
def test_the_local_step_carries_its_scopes(lowered, path):
    assert path in lowered[1], path


@pytest.mark.parametrize("path", ["jvp(cast_params)/convert_element_type",
                                  "transpose(jvp(cast_params))/convert_element_type"])
def test_the_cast_has_a_scope_only_where_there_is_a_compute_dtype(lowered, path):
    compute_dtype, text = lowered
    assert (path in text) == (compute_dtype is not None)


def test_the_proximal_term_is_part_of_the_optimizer_step():
    assert "optimizer_step/sub" not in _lowered_fit(None)
    assert "optimizer_step/sub" in _lowered_fit(None, prox_mu=0.1)
