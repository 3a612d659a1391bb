"""Plain reference of one synchronous FedAvg round, model family given as a module.

The semantics the system is held to, written out (McMahan et al. 2017, with the
repo's stated seed schedule so that the same seed gives the same shuffles and masks):

* round ``r`` draws ``base = fold_in(key(seed), r)`` and one key per client,
  ``split(base, clients)``;
* a client runs ``E`` local epochs from the global weights; epoch ``e`` takes
  ``split(client_key, E)[e]``, splits it into a permutation key and a step key, visits
  its ``capacity`` rows (padding included) in ``permutation(perm_key, capacity)`` order
  in batches of ``B``, and step ``s`` hands ``split(step_key, steps)[s]`` to the model;
* a step is plain SGD on the masked mean over the batch's real rows of each sample's
  loss (its negative log-likelihood, or the family's own objective: below); a batch of
  padding alone changes nothing;
* a client reports the mean loss of its LAST epoch; the round's loss is the mean of
  those weighted by real samples, and the new global weights are the old ones plus the
  mean of the clients' changes under the same weights.

Everything runs in float32 with matmuls at ``highest`` precision, clients in blocks of
``block`` under ``lax.scan`` so that it fits beside nothing else on one device; the
weights a round started from are deleted when it has ended, the caller's among them, so
two copies are held while a round runs (its input and the sum it aggregates into, which
is its output) and not three.

What a family module (``reference/<family>.py``, found by the configuration's
``family``) gives:

* ``TOKEN_STREAM``: whether ``x`` is token ids (the data then carries one label a
  sequence, an affine teacher of its last token) or float features;
* ``init_params(key, model_kwargs)``: the weights from the seed, leaf for leaf the
  tree of the zoo model the configuration names;
* the objective, in one of two forms.  ``log_probs(params, xb, key, model_kwargs, q)
  -> [batch, classes]``: the sample's loss is ``-log_probs[yb]``, one label a sample.
  Or ``sample_nll(params, xb, yb, key, model_kwargs, q) -> [batch]`` float32: the
  family's own loss of each sample, for a model whose training is not "one label a
  sample" (a loss at every position, at masked positions, with noise of its own).
  **Where a family has both, ``sample_nll`` decides training**; one with ``sample_nll``
  need not define ``log_probs``.

In both forms ``key`` is the step key of the schedule above, the one the program hands
to ``apply(..., rng=)``: a family that draws noise, a mask or a timestep from it draws
what the program draws.  ``q`` rounds matmul operands to the precision under test (the
identity for the reference, ``float8`` for the control) and has to reach every product
of the objective.  ``yb`` is passed to ``sample_nll`` and may be ignored.

What the hook may not do: it returns one loss a sample and nothing else.  The masked
mean over the batch's real rows, the batch of padding that changes nothing, the SGD
step, the last epoch's mean and the weighted aggregate stay this module's: a
``sample_nll`` does not reweight the batch, touch the mask or update a leaf.  A rule
outside the loss (a selection bias's balancing update) has no place here yet.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _local_fit(family, model_kwargs, fed, q, params0, x, y, mask, client_key):
    batch, epochs, lr = fed["batch_size"], fed["local_epochs"], fed["learning_rate"]
    capacity = x.shape[0]
    steps = capacity // batch

    sample_nll = getattr(family, "sample_nll", None)

    def loss_fn(params, xb, yb, mb, key):
        if sample_nll is None:
            logp = family.log_probs(params, xb, key, model_kwargs, q)
            nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        else:
            nll = sample_nll(params, xb, yb, key, model_kwargs, q)
            shape, dtype = getattr(nll, "shape", None), getattr(nll, "dtype", None)
            if shape != mb.shape or dtype != jnp.float32:
                raise TypeError(
                    f"{family.__name__}.sample_nll(params, xb, yb, key, model_kwargs, q) has "
                    f"to return one float32 loss a sample, float32{list(mb.shape)}: it "
                    f"returned {dtype}{list(shape or ())} (reference/fedavg.py's docstring)")
        count = mb.sum()
        return (nll * mb).sum() / jnp.maximum(count, 1.0), count

    def epoch(params, epoch_key):
        perm_key, step_key = jax.random.split(epoch_key)
        perm = jax.random.permutation(perm_key, capacity)

        def step(params, inp):
            s, key = inp
            idx = lax.dynamic_slice(perm, (s * batch,), (batch,))
            (loss, count), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, x[idx], y[idx], mask[idx], key
            )
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            params = jax.tree.map(lambda n, p: jnp.where(count > 0, n, p), new, params)
            return params, (loss * count, count)

        params, (loss_sums, counts) = lax.scan(
            step, params, (jnp.arange(steps), jax.random.split(step_key, steps))
        )
        return params, loss_sums.sum() / jnp.maximum(counts.sum(), 1.0)

    params, epoch_loss = lax.scan(epoch, params0, jax.random.split(client_key, epochs))
    delta = jax.tree.map(jnp.subtract, params, params0)
    return delta, epoch_loss[-1]


@functools.partial(jax.jit, static_argnames=("family", "model_json", "fed_json", "q", "block"))
def _round(params, x, y, mask, base_key, *, family, model_json, fed_json, q, block):
    model_kwargs, fed = json.loads(model_json), json.loads(fed_json)
    clients = x.shape[0]
    weights = mask.sum(axis=1)
    fit = functools.partial(_local_fit, family, model_kwargs, fed, q, params)

    def one_block(acc, args):
        xb, yb, mb, kb, wb = args
        deltas, losses = jax.vmap(fit)(xb, yb, mb, kb)
        wsum, lsum = acc
        wsum = jax.tree.map(lambda a, d: a + jnp.tensordot(wb, d, axes=1), wsum, deltas)
        return (wsum, lsum + (losses * wb).sum()), None

    blocked = jax.tree.map(
        lambda a: a.reshape(clients // block, block, *a.shape[1:]),
        (x, y, mask, jax.random.split(base_key, clients), weights),
    )
    zero = (jax.tree.map(jnp.zeros_like, params), jnp.zeros((), jnp.float32))
    (wsum, lsum), _ = lax.scan(one_block, zero, blocked)
    total = weights.sum()
    return jax.tree.map(lambda p, w: p + w / total, params, wsum), lsum / total


def identity(t):
    return t


def float8(t):
    """The control's precision, one step under the bfloat16 the configurations state."""
    return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def run_rounds(family, model_kwargs, fed, params, data, seed, rounds, *, q=identity, block=1):
    """``rounds`` rounds from ``params`` on ``data = (x, y, mask)`` of shape
    ``[clients, capacity, ...]``.  Returns ``(losses, params after each round)``, the
    parameter trees as host arrays.  ``params`` is consumed: the weights a round started
    from are deleted when it has ended, or the caller's name for them would hold a third
    tree on the device through the second round (a host copy made before stays good)."""
    x, y, mask = data
    kw = dict(
        family=family, model_json=json.dumps(model_kwargs, sort_keys=True),
        fed_json=json.dumps(fed, sort_keys=True), q=q, block=block,
    )
    losses, trees = [], []
    with jax.default_matmul_precision("highest"):
        for r in range(rounds):
            new, loss = _round(
                params, x, y, mask, jax.random.fold_in(jax.random.key(seed), r), **kw
            )
            losses.append(float(loss))  # the round has ended
            jax.tree.map(lambda leaf: leaf.delete(), params)
            params = new
            trees.append(jax.tree.map(np.asarray, params))
    return losses, trees
