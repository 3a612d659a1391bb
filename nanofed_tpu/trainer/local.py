"""Client-side local training as one jitted pure function.

This replaces the reference's hot loop — ``BaseTrainer.train_epoch`` iterating a torch
DataLoader with per-batch ``zero_grad/forward/backward/step`` (``nanofed/trainer/
base.py:116-198``) — with a ``lax.scan`` over shuffled fixed-shape batches, nested in a
scan over local epochs.  The whole multi-epoch fit compiles to a single XLA program, and
``vmap`` of it over the leading client axis is what turns one client's SGD into a whole
federated round on a TPU mesh.

Padding discipline: every client's data is padded to a common capacity with a {0,1} sample
mask (see ``nanofed_tpu.data.batching``).  Masked samples contribute exactly zero to the
loss, the gradient, and the metrics; a batch that is entirely padding applies a zero
parameter update.  This is how clients with 12k/8k/4k samples (the reference example)
share one SPMD program without biasing FedAvg.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from nanofed_tpu.core.types import ClientData, ClientMetrics, Params, PRNGKey
from nanofed_tpu.trainer.config import TrainingConfig
from nanofed_tpu.utils.trees import tree_scale, tree_sub, tree_where

# grad_fn(params, xb, yb, mb, rng) -> (grads, StepStats)
GradFn = Callable[..., tuple[Params, "StepStats"]]


class StepStats(NamedTuple):
    """Per-batch masked sums (not means): summing across steps stays exact."""

    loss_sum: jax.Array  # sum of per-sample loss over real samples
    correct: jax.Array  # count of correct predictions over real samples
    count: jax.Array  # number of real samples in the batch
    # What the model counts about its own layers for this batch, times ``count`` (so it
    # sums like the loss).  Empty — no leaves, nothing compiled — unless the model's
    # ``apply`` carries ``with_counters``.
    counters: dict[str, jax.Array] = {}


class LocalFitResult(NamedTuple):
    params: Params
    metrics: ClientMetrics  # metrics of the FINAL local epoch (what a client reports)
    epoch_loss: jax.Array  # [E] per-epoch mean loss
    epoch_accuracy: jax.Array  # [E] per-epoch accuracy
    batch_loss: jax.Array  # [E, S] per-step mean loss (zeros unless collect_batch_metrics)


def make_grad_fn(
    apply_fn: Callable[..., jax.Array], compute_dtype: str | None = None
) -> GradFn:
    """Standard masked NLL gradient.

    ``apply_fn`` returns log-probabilities (all zoo models end in log_softmax, parity with
    ``nanofed/models/mnist.py:28``); the loss is the masked mean negative log-likelihood —
    what the reference computes with ``F.cross_entropy`` on logits
    (``nanofed/trainer/torch.py:10-14``).

    ``compute_dtype`` enables mixed precision: params and activations are cast (inside
    the differentiated function, so gradients flow back to the float32 masters) and the
    loss/metric reductions stay float32.

    A model may count things about its own layers (an expert layer's load): its
    ``apply`` then carries ``with_counters``, the same call returning ``(log-probs,
    {name: scalar})``.  The scalars ride ``StepStats.counters`` weighted by the batch's
    real samples, and reach the round's metrics under their names.

    A model whose training is not "one label a sample" (a loss at every position, at
    masked positions, with noise of its own) brings its objective: its ``apply`` then
    carries ``sample_nll(params, x, y, *, rng) -> (nll [N] float32, hits [N], {name:
    scalar})``, each sample's own loss, the share of its predictions that were right (what
    ``correct`` then sums) and the model's counters; ``rng`` is the step's key, so noise
    drawn from it is the schedule's.  Where a model brings one it decides training
    (``benchmark/reference/fedavg.py`` has the same rule); the cast, the masked mean over
    the batch's real rows, the counters' weighting and ``StepStats`` stay this
    function's, and ``y`` may be ignored.
    """
    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None else None
    counted_apply = getattr(apply_fn, "with_counters", None)
    objective = getattr(apply_fn, "sample_nll", None)

    def loss_fn(params, xb, yb, mb, rng):
        if cdt is not None:
            with jax.named_scope("cast_params"):
                params = jax.tree.map(lambda p: p.astype(cdt), params)
                # Integer inputs (token-id streams) must stay integer: they index an
                # embedding table, and casting ids to bf16 would corrupt the lookup.
                # fedlint: disable=FED002 (branches on xb.dtype — static trace-time metadata, not a traced value; both arms compile into one program)
                if jnp.issubdtype(xb.dtype, jnp.floating):
                    xb = xb.astype(cdt)
        if objective is not None:
            nll, hits, counters = objective(params, xb, yb, rng=rng)
        elif counted_apply is None:
            logp, counters = apply_fn(params, xb, train=True, rng=rng), {}
        else:
            logp, counters = counted_apply(params, xb, train=True, rng=rng)
        with jax.named_scope("nll_loss"):
            if objective is None:
                logp = logp.astype(jnp.float32)
                nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
            count = mb.sum()
            loss = (nll * mb).sum() / jnp.maximum(count, 1.0)
            if objective is None:  # after the loss, where it stood: the same program
                hits = jnp.argmax(logp, -1) == yb
            correct = (hits * mb).sum()
        return loss, (correct, count, counters)

    def grad_fn(params, xb, yb, mb, rng):
        (loss, (correct, count, counters)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, xb, yb, mb, rng
        )
        counters = {name: value.astype(jnp.float32) * count for name, value in counters.items()}
        return grads, StepStats(loss_sum=loss * count, correct=correct, count=count,
                                counters=counters)

    return grad_fn


def make_optimizer(config: TrainingConfig) -> optax.GradientTransformation:
    """SGD(+momentum, +decoupled weight decay) — the reference's optimizer family
    (``examples/mnist/run_experiment.py:73``: ``torch.optim.SGD(lr=0.1)``)."""
    parts = []
    if config.weight_decay > 0:
        parts.append(optax.add_decayed_weights(config.weight_decay))
    parts.append(optax.sgd(config.learning_rate, momentum=config.momentum or None))
    return optax.chain(*parts) if len(parts) > 1 else parts[0]


def make_local_fit(
    apply_fn: Callable[..., jax.Array],
    config: TrainingConfig,
    grad_fn: GradFn | None = None,
    optimizer: optax.GradientTransformation | None = None,
) -> Callable[[Params, ClientData, PRNGKey], LocalFitResult]:
    """Build the pure local-training function for one client.

    The returned ``local_fit(global_params, data, rng, lr_scale=None)`` is
    jit-compatible and vmap-compatible over stacked clients.  FedProx: with
    ``config.prox_mu > 0`` the proximal gradient ``mu * (w - w_global)`` is added
    analytically each step.

    ``lr_scale`` (an optional TRACED scalar) multiplies every optimizer step — the
    per-round lr-schedule hook (``trainer.schedules``): scheduling via a traced
    multiplier keeps one compiled round program, where re-baking
    ``config.learning_rate`` per round would re-trace and re-compile.  Scaling the
    post-momentum update is equivalent to running this fit at
    ``learning_rate * lr_scale`` (optax applies lr after the momentum trace);
    FedProx and decoupled weight decay scale with it, exactly as if lr changed.
    """
    if grad_fn is not None and config.compute_dtype is not None:
        # A custom grad_fn owns its own casts; silently ignoring the config would let a
        # user believe bf16 is active when it is not.  make_dp_grad_fn/
        # make_private_local_fit accept compute_dtype directly.
        raise ValueError(
            "compute_dtype is set but a custom grad_fn was supplied; bake the dtype "
            "into the grad_fn (e.g. make_dp_grad_fn(..., compute_dtype=...)) and leave "
            "TrainingConfig.compute_dtype unset"
        )
    grad_fn = grad_fn or make_grad_fn(apply_fn, compute_dtype=config.compute_dtype)
    tx = optimizer or make_optimizer(config)
    bsz = config.batch_size

    def local_fit(
        global_params: Params,
        data: ClientData,
        rng: PRNGKey,
        lr_scale: jax.Array | None = None,
    ) -> LocalFitResult:
        n = data.x.shape[0]
        if n % bsz != 0:
            raise ValueError(
                f"data capacity {n} must be a multiple of batch_size {bsz} "
                "(use data.batching.pack_clients with the same batch_size)"
            )
        steps = n // bsz
        if config.max_batches is not None:
            steps = min(steps, config.max_batches)

        opt_state = tx.init(global_params)

        def epoch_body(carry, ekey):
            params, opt_state = carry
            perm_key, step_key = jax.random.split(ekey)
            perm = jax.random.permutation(perm_key, n)

            def step_body(carry, inp):
                params, opt_state = carry
                sidx, skey = inp
                with jax.named_scope("batch_gather"):
                    idx = lax.dynamic_slice(perm, (sidx * bsz,), (bsz,))
                    xb, yb, mb = data.x[idx], data.y[idx], data.mask[idx]
                grads, stats = grad_fn(params, xb, yb, mb, skey)
                with jax.named_scope("optimizer_step"):
                    if config.prox_mu > 0:
                        prox = tree_scale(tree_sub(params, global_params), config.prox_mu)
                        grads = jax.tree.map(jnp.add, grads, prox)
                    updates, new_opt_state = tx.update(grads, opt_state, params)
                    if lr_scale is not None:
                        updates = tree_scale(updates, lr_scale)
                    new_params = optax.apply_updates(params, updates)
                    # A batch of pure padding must be a no-op (both params and opt state).
                    nonempty = stats.count > 0
                    params = tree_where(nonempty, new_params, params)
                    opt_state = tree_where(nonempty, new_opt_state, opt_state)
                return (params, opt_state), stats

            step_keys = jax.random.split(step_key, steps)
            (params, opt_state), stats = lax.scan(
                step_body, (params, opt_state), (jnp.arange(steps), step_keys)
            )
            count = jnp.maximum(stats.count.sum(), 1.0)
            e_loss = stats.loss_sum.sum() / count
            e_acc = stats.correct.sum() / count
            e_counters = {name: sums.sum() / count for name, sums in stats.counters.items()}
            if config.collect_batch_metrics:
                b_loss = stats.loss_sum / jnp.maximum(stats.count, 1.0)
            else:
                b_loss = jnp.zeros((steps,))
            return (params, opt_state), (e_loss, e_acc, b_loss, e_counters)

        epoch_keys = jax.random.split(rng, config.local_epochs)
        (params, _), (e_loss, e_acc, b_loss, e_counters) = lax.scan(
            epoch_body, (global_params, opt_state), epoch_keys
        )
        metrics = ClientMetrics(
            loss=e_loss[-1], accuracy=e_acc[-1], samples=data.mask.sum(),
            counters={name: per_epoch[-1] for name, per_epoch in e_counters.items()},
        )
        return LocalFitResult(
            params=params,
            metrics=metrics,
            epoch_loss=e_loss,
            epoch_accuracy=e_acc,
            batch_loss=b_loss,
        )

    # Marker for build_round_step: a CUSTOM local_fit override may not accept
    # lr_scale, and a traced value cannot be introspected at call time — the round
    # builder checks this attribute instead of the signature.
    local_fit.supports_lr_scale = True
    return local_fit


def make_evaluator(
    apply_fn: Callable[..., jax.Array], batch_size: int = 256
) -> Callable[[Params, ClientData], dict[str, jax.Array]]:
    """Jitted full-dataset evaluation (masked loss/accuracy), scanning fixed-size batches.

    Replaces host-side test loops; used by the coordinator for the global-accuracy metric
    the baselines target (97% MNIST test accuracy).
    """

    # fedlint: disable=FED004 (eval must NOT donate: params are the live global params, reused for the next round's dispatch)
    @jax.jit
    def evaluate(params: Params, data: ClientData) -> dict[str, jax.Array]:
        n = data.x.shape[0]
        steps = -(-n // batch_size)  # ceil: never truncate real samples
        cap = steps * batch_size
        pad = cap - n
        x = jnp.pad(data.x, [(0, pad)] + [(0, 0)] * (data.x.ndim - 1))
        y = jnp.pad(data.y, (0, pad))
        m = jnp.pad(data.mask, (0, pad))
        xb = x.reshape(steps, batch_size, *data.x.shape[1:])
        yb = y.reshape(steps, batch_size)
        mb = m.reshape(steps, batch_size)

        def body(carry, batch):
            loss_sum, correct, count = carry
            x, y, m = batch
            logp = apply_fn(params, x)
            nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
            loss_sum = loss_sum + (nll * m).sum()
            correct = correct + ((jnp.argmax(logp, -1) == y) * m).sum()
            return (loss_sum, correct, count + m.sum()), None

        (loss_sum, correct, count), _ = lax.scan(body, (0.0, 0.0, 0.0), (xb, yb, mb))
        count = jnp.maximum(count, 1.0)
        return {"loss": loss_sum / count, "accuracy": correct / count}

    return evaluate


def stack_rngs(rng: PRNGKey, num_clients: int) -> jax.Array:
    """Split an rng into a ``[C]`` batch of per-client keys (one per vmapped client)."""
    return jax.random.split(rng, num_clients)
