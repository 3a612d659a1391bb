"""FedAvg reductions: host-side (stacked arrays) and in-mesh (``psum`` over the client
axis).

The reference's FedAvg is a Python double loop over clients and state-dict keys
(``nanofed/server/aggregator/fedavg.py:56-63``) with weights proportional to sample counts
(``:101-125``).  Here the same math is one contraction per pytree leaf; inside
``shard_map`` the cross-device half of the reduction is an ICI ``psum`` — this is the wire
protocol of the framework, replacing ``POST /update`` + JSON decode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from nanofed_tpu.core.types import ClientMetrics, ClientUpdates, Params
from nanofed_tpu.utils.trees import tree_weighted_mean


def fedavg_combine(updates: ClientUpdates) -> Params:
    """Sample-count-weighted mean of stacked client params (host/test path).

    Exact parity with ``FedAvgAggregator.aggregate`` (``fedavg.py:46-78``).
    """
    return tree_weighted_mean(updates.params, updates.weights)


def aggregate_metrics(metrics: ClientMetrics, weights: jax.Array) -> dict[str, jax.Array]:
    """Weighted metric averaging, parity with ``_aggregate_metrics``
    (``fedavg.py:80-99``).  ``samples`` counts participants only (weights > 0), matching
    the in-mesh ``psum_weighted_metrics`` exactly."""
    den = jnp.maximum(weights.sum(), 1e-12)
    participating = (weights > 0).astype(metrics.samples.dtype)
    return {
        "loss": (metrics.loss * weights).sum() / den,
        "accuracy": (metrics.accuracy * weights).sum() / den,
        "samples": (metrics.samples * participating).sum(),
        **{name: (value * weights).sum() / den for name, value in metrics.counters.items()},
    }


def compute_weights(
    num_samples: jax.Array, participation: jax.Array | None = None
) -> jax.Array:
    """FedAvg weights: proportional to client sample counts, zeroed for non-participants.

    Parity: ``_compute_weights`` (``fedavg.py:101-125``) defaults a *missing* sample
    count to 1.0; here counts are always known, and a count of ZERO means a padding
    client — it gets weight 0 so ``pad_clients`` dummies never dilute the mean, with or
    without an explicit participation mask.  Partial participation (the reference's
    ``min_completion_rate`` wait-barrier, ``coordinator.py:205-245``) is re-specified as a
    mask — zero-weight clients drop out of the ``psum`` exactly like clients that never
    reported drop out of the buffer.
    """
    w = jnp.maximum(num_samples, 0.0)
    if participation is not None:
        w = w * participation
    return w


def _client_psum(x: jax.Array, axis_name: str | tuple[str, ...]) -> jax.Array:
    """``psum`` over the client axis — hierarchically (innermost first: the
    host-local ICI stage, then ONE cross-host DCN stage on the already-reduced
    value) when ``axis_name`` is the 3-axis mesh's ``(hosts, clients)`` tuple.
    Lazy import: ``aggregation`` must stay importable without triggering the
    ``parallel`` package's own import of this module (cycle)."""
    from nanofed_tpu.parallel.mesh import hierarchical_psum

    return hierarchical_psum(x, axis_name)


def psum_weighted_mean(
    tree: Params, weights: jax.Array, axis_name: str | tuple[str, ...]
) -> Params:
    """In-mesh weighted mean over the client axis: local contraction then ICI ``psum``
    (host-local then cross-host when ``axis_name`` is the hierarchical axis tuple).

    ``tree`` leaves are ``[C_local, ...]`` (this device's clients); ``weights`` is
    ``[C_local]``.  Safe under all-zero weights (returns zeros).
    """
    den = _client_psum(weights.sum(), axis_name)
    den = jnp.maximum(den, 1e-12)

    def leaf_mean(leaf: jax.Array) -> jax.Array:
        w = weights.astype(leaf.dtype)
        local = jnp.tensordot(w, leaf, axes=1)
        return _client_psum(local, axis_name) / den.astype(leaf.dtype)

    return jax.tree.map(leaf_mean, tree)


def psum_weighted_metrics(
    metrics: ClientMetrics, weights: jax.Array, axis_name: str | tuple[str, ...]
) -> dict[str, jax.Array]:
    """In-mesh weighted metric means + total sample count (masked by participation).
    A model's own counters (``ClientMetrics.counters``; none for most models) are
    weighted like the loss and appear under their own names."""
    den = jnp.maximum(_client_psum(weights.sum(), axis_name), 1e-12)
    participating = (weights > 0).astype(metrics.samples.dtype)
    mean = lambda per_client: _client_psum((per_client * weights).sum(), axis_name) / den
    return {
        "loss": mean(metrics.loss),
        "accuracy": mean(metrics.accuracy),
        "samples": _client_psum((metrics.samples * participating).sum(), axis_name),
        **{name: mean(value) for name, value in metrics.counters.items()},
    }
