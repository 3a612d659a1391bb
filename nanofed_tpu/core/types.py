"""Core value types of the framework.

The reference expresses a client's contribution as a ``ModelUpdate`` TypedDict holding a
torch ``state_dict`` plus bookkeeping (``nanofed/core/types.py:11-29``).  On TPU the unit of
work is not one client but a *batch* of clients living on a device mesh, so the central types
here are pytrees-of-arrays with a leading client axis:

* ``ClientData``      — one (or, with a leading axis, many) client's padded training samples.
* ``ClientUpdates``   — the stacked result of local training for every client in a round
                        (the SPMD replacement for a buffer of ``ModelUpdate`` dicts).
* ``ClientMetrics``   — per-client scalar training metrics as arrays.
* ``ModelUpdate``     — the single-client record used by the host-side/HTTP transport path,
                        at parity with the reference's TypedDict.
* ``ModelVersion``    — frozen record of a persisted global model version
                        (parity: ``nanofed/core/types.py:22-29``).

All NamedTuple types are automatically JAX pytrees and can cross ``jit``/``shard_map``
boundaries.
"""

from __future__ import annotations

from dataclasses import dataclass
from datetime import datetime
from typing import Any, Mapping, NamedTuple, TypeAlias

import jax

# A model's parameters (and any pytree of arrays).
Params: TypeAlias = Any
PyTree: TypeAlias = Any
PRNGKey: TypeAlias = jax.Array


class ClientData(NamedTuple):
    """Padded training data for one client (or ``[C, ...]`` for a batch of clients).

    ``x``/``y`` are padded to a common capacity ``N`` so heterogeneous clients (e.g. the
    reference example's 12k/8k/4k sample split, ``examples/mnist/run_experiment.py:126-131``)
    can share one SPMD program; ``mask`` marks real samples (1.0) vs padding (0.0).
    """

    x: jax.Array  # [N, ...features] or [C, N, ...]
    y: jax.Array  # [N] or [C, N] integer labels
    mask: jax.Array  # [N] or [C, N] float {0., 1.}

    @property
    def num_samples(self) -> jax.Array:
        """Number of real (unpadded) samples."""
        return self.mask.sum(axis=-1)


class ClientMetrics(NamedTuple):
    """Scalar training metrics produced by local training.

    Parity with the reference's ``TrainingMetrics`` (``nanofed/trainer/base.py:28-43``):
    loss, accuracy, samples processed.  As arrays these stack/vmap over clients.

    ``counters`` is the optional channel for what a model counts about its own layers
    (an expert layer's load; ``apply.with_counters``, see ``trainer.local``): name ->
    the client's sample-weighted mean over its last local epoch.  Empty for a model that
    reports none — an empty dict has no leaves, so nothing is traced, stacked or reduced
    for it and every compiled program stays what it was.
    """

    loss: jax.Array
    accuracy: jax.Array
    samples: jax.Array
    counters: dict[str, jax.Array] = {}

    def to_dict(self) -> dict[str, Any]:
        return {
            "loss": float(self.loss),
            "accuracy": float(self.accuracy),
            "samples_processed": int(self.samples),
            **{name: float(value) for name, value in self.counters.items()},
        }


class ClientUpdates(NamedTuple):
    """Stacked results of one round of local training across all clients.

    This replaces the reference server's ``_updates`` buffer of JSON dicts
    (``nanofed/communication/http/server.py:87``): ``params`` is the model pytree with a
    leading ``[C]`` client axis, ``weights`` the aggregation weights (sample counts x
    participation mask), ``metrics`` per-client metric arrays.
    """

    params: Params  # pytree, leaves [C, ...]
    weights: jax.Array  # [C]
    metrics: ClientMetrics  # leaves [C]


class ModelUpdate(NamedTuple):
    """A single client's update record, used on the host/transport path.

    Parity with ``ModelUpdate`` in ``nanofed/core/types.py:11-20`` (model_state, client_id,
    round_number, metrics, timestamp, optional privacy_spent).
    """

    client_id: str
    round_number: int
    params: Params
    metrics: Mapping[str, Any]
    timestamp: str
    privacy_spent: Any | None = None  # privacy.PrivacySpent; Any to avoid a core->privacy dep


@dataclass(frozen=True, slots=True)
class ModelVersion:
    """Frozen record of a saved global model version.

    Parity: ``nanofed/core/types.py:22-29`` (version_id, timestamp, config_path, model_path).
    """

    version_id: str
    created_at: datetime
    model_path: str
    config_path: str
    round_number: int = -1
