"""``trainer.local.make_grad_fn`` and a model that carries its objective
(``apply.sample_nll``): where a model brings none the function traces to the program it
was, line for line; where it brings one, each sample's loss is the objective's, the
step's ``rng`` reaches it, and the masked mean, the cast, the counters' weighting and
``StepStats`` stay ``make_grad_fn``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.models import get_model
from nanofed_tpu.trainer.local import StepStats, make_grad_fn


def _grad_fn_as_it_stood(apply_fn, compute_dtype=None):
    """``make_grad_fn`` before a model could bring an objective, kept here as the yardstick."""
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None else None
    counted_apply = getattr(apply_fn, "with_counters", None)

    def loss_fn(params, xb, yb, mb, rng):
        if cdt is not None:
            with jax.named_scope("cast_params"):
                params = jax.tree.map(lambda p: p.astype(cdt), params)
                if jnp.issubdtype(xb.dtype, jnp.floating):
                    xb = xb.astype(cdt)
        if counted_apply is None:
            logp, counters = apply_fn(params, xb, train=True, rng=rng), {}
        else:
            logp, counters = counted_apply(params, xb, train=True, rng=rng)
        with jax.named_scope("nll_loss"):
            logp = logp.astype(jnp.float32)
            nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
            count = mb.sum()
            loss = (nll * mb).sum() / jnp.maximum(count, 1.0)
            correct = ((jnp.argmax(logp, -1) == yb) * mb).sum()
        return loss, (correct, count, counters)

    def grad_fn(params, xb, yb, mb, rng):
        (loss, (correct, count, counters)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, xb, yb, mb, rng)
        counters = {name: value.astype(jnp.float32) * count for name, value in counters.items()}
        return grads, StepStats(loss_sum=loss * count, correct=correct, count=count,
                                counters=counters)

    return grad_fn


def _batch(model, n=4):
    x = (jnp.zeros((n, *model.input_shape), jnp.int32) if model.token_stream
         else jnp.ones((n, *model.input_shape), jnp.float32))
    return x, jnp.arange(n, dtype=jnp.int32) % model.num_classes, jnp.array([1.0, 1.0, 1.0, 0.0])[:n]


@pytest.mark.parametrize("name,dtype", [("mnist_cnn", None), ("mnist_cnn", "bfloat16"),
                                        ("indexed_moe_lm", "bfloat16"), ("transformer_lm", None)])
def test_a_model_without_an_objective_traces_to_the_program_it_was(name, dtype):
    model = get_model(name)
    assert not hasattr(model.apply, "sample_nll")
    params = jax.eval_shape(model.init, jax.random.key(0))
    args = (params, *_batch(model), jax.random.key(1))
    text = lambda build: str(jax.make_jaxpr(build(model.apply, compute_dtype=dtype))(*args))
    # A ``lax.reduce``'s Python callable prints with its address: one function object, two traces.
    import re
    plain = lambda s: re.sub(r" at 0x[0-9a-f]+", "", s)
    assert plain(text(make_grad_fn)) == plain(text(_grad_fn_as_it_stood))


def _linear_with_objective(seen):
    """A model of one matrix whose objective is the squared distance of ``x w`` from noise
    drawn from the step's key, and whose ``apply`` would give another loss altogether."""
    def apply(params, x, *, train=False, rng=None):
        return jax.nn.log_softmax(x @ params["w"])

    def sample_nll(params, x, y, *, rng):
        seen.append((rng, y))
        noise = jax.random.normal(rng, (x.shape[0], params["w"].shape[1]), jnp.float32)
        out = (x @ params["w"]).astype(jnp.float32)
        return ((out - noise) ** 2).sum(axis=1), jnp.full((x.shape[0],), 0.25), {"my_counter": out.mean()}

    apply.sample_nll = sample_nll
    return apply


def test_a_models_objective_is_taken_and_receives_the_steps_rng():
    seen = []
    apply = _linear_with_objective(seen)
    params = {"w": jnp.ones((3, 5)) * 0.1}
    x = jax.random.normal(jax.random.key(2), (4, 3))
    y, mask, rng = jnp.zeros((4,), jnp.int32), jnp.array([1.0, 1.0, 0.0, 1.0]), jax.random.key(7)
    grads, stats = make_grad_fn(apply)(params, x, y, mask, rng)
    (got_rng, got_y), = seen
    assert got_rng is rng and got_y is y  # the step's key, the batch's labels, untouched
    noise = jax.random.normal(rng, (4, 5), jnp.float32)
    nll = ((x @ params["w"] - noise) ** 2).sum(axis=1)
    # The masked mean over the batch's real rows, the sums of StepStats, the counter times the count.
    np.testing.assert_allclose(stats.loss_sum, (nll * mask).sum(), rtol=1e-6)
    assert float(stats.count) == 3.0 and float(stats.correct) == pytest.approx(0.75)
    np.testing.assert_allclose(stats.counters["my_counter"], 3.0 * (x @ params["w"]).mean(), rtol=1e-5)
    want = jax.grad(lambda p: ((((x @ p["w"]) - noise) ** 2).sum(axis=1) * mask).sum() / 3.0)(params)
    np.testing.assert_allclose(grads["w"], want["w"], rtol=1e-5)
    # Another key is another loss: the noise is the key's.
    _, other = make_grad_fn(apply)(params, x, y, mask, jax.random.key(8))
    assert abs(float(other.loss_sum - stats.loss_sum)) > 1e-3


def test_the_cast_stays_make_grad_fns_under_an_objective():
    seen = []
    apply = _linear_with_objective(seen)
    dtypes = []
    inner = apply.sample_nll
    apply.sample_nll = lambda params, x, y, *, rng: (dtypes.append((params["w"].dtype, x.dtype)),
                                                     inner(params, x, y, rng=rng))[1]
    params = {"w": jnp.ones((3, 5)) * 0.1}
    x = jax.random.normal(jax.random.key(2), (4, 3))
    grads, stats = make_grad_fn(apply, compute_dtype="bfloat16")(
        params, x, jnp.zeros((4,), jnp.int32), jnp.ones((4,)), jax.random.key(7))
    assert dtypes == [(jnp.bfloat16, jnp.bfloat16)]
    assert grads["w"].dtype == jnp.float32 and stats.loss_sum.dtype == jnp.float32
    text = jax.jit(lambda p: make_grad_fn(apply, compute_dtype="bfloat16")(
        p, x, jnp.zeros((4,), jnp.int32), jnp.ones((4,)), jax.random.key(7))[0]).lower(params).as_text(debug_info=True)
    assert "cast_params" in text and "nll_loss" in text


def test_the_zoos_objective_carrier_trains_where_its_apply_would_not():
    """``diffusion_moe_lm`` through ``make_grad_fn``: the loss is the objective's (about
    ``ln vocab`` at the start, weighted), not ``-log_probs[y]`` of ``apply``'s last
    position, and the labels are not read."""
    model = get_model("diffusion_moe_lm")
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (4, 32), 0, 256)
    mask, rng = jnp.ones((4,)), jax.random.key(3)
    grad_fn = jax.jit(make_grad_fn(model.apply, compute_dtype="bfloat16"))
    grads, stats = grad_fn(params, x, jnp.zeros((4,), jnp.int32), mask, rng)
    _, other = grad_fn(params, x, jnp.full((4,), 9, jnp.int32), mask, rng)
    assert float(other.loss_sum) == float(stats.loss_sum)
    nll = model.apply.sample_nll(jax.tree.map(lambda p: p.astype(jnp.bfloat16), params), x, None, rng=rng)[0]
    np.testing.assert_allclose(stats.loss_sum, nll.sum(), rtol=1e-3)
    assert "diffusion_masked_share" in stats.counters and 0.0 <= float(stats.correct) <= 4.0
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))
