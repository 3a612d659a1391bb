"""Fused multi-round execution: R federated rounds as ONE jitted device program.

The single-round engine (``parallel.round_step``) already fuses a whole round into
one XLA program, but every round still pays the host tax: a Python dispatch, a
``jax.block_until_ready`` barrier, and a per-round device->host metrics transfer
before the next round can start.  FedJAX (arXiv:2108.02117) showed that federated
*simulation* throughput in JAX is won by keeping the round loop on-device; this
module applies that to the flagship benchmark's hot path.

``build_round_block`` wraps the SAME ``shard_map`` round program that
``build_round_step`` jits (``build_sharded_round`` — shared by construction, so the
fused and single-round paths cannot drift) in a ``lax.scan`` over R rounds inside a
single ``jit``:

* per-round cohorts either stream in as stacked ``[R, K_pad]`` index/mask arrays
  (the ``Coordinator`` path — cohorts stay a pure host function of the seed, so a
  fused run reproduces the single-round run EXACTLY) or are resampled on-device
  (fold the round index into the PRNG, ``jax.random.permutation`` without
  replacement, simulated dropout) when no cohort arrays are passed;
* the cohort gather (``x[idx]``, the coordinator's jitted gather) runs INSIDE the
  scan, so partial participation costs K-client compute per scanned round;
* the lr schedule rides a traced ``[R]`` array of scales (``trainer.schedules``);
* per-round metrics stack ``[R, ...]`` and cross to the host ONCE per block.

The round barrier between scanned rounds is the scan's data dependence itself —
no host involvement until the block completes.  A round whose surviving cohort
falls below ``min_completion_rate`` is gated to zero total weight in-device, which
the round program already defines as an identity (FAILED) round: params AND server
state pass through untouched, exactly like the single-round path.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from nanofed_tpu.aggregation.base import Strategy
from nanofed_tpu.aggregation.fedavg import compute_weights
from nanofed_tpu.core.types import ClientData, ClientMetrics, Params
from nanofed_tpu.parallel.mesh import CLIENT_AXIS, client_sharding
from nanofed_tpu.parallel.round_step import build_sharded_round
from nanofed_tpu.security.validation import ValidationConfig
from nanofed_tpu.trainer.config import TrainingConfig
from nanofed_tpu.trainer.local import GradFn

# Salts folded into the per-round base key for device-side sampling, so the cohort
# draw, the dropout draw, and the per-client training keys are independent streams
# of one key.  The client keys deliberately use the UNSALTED base: they must match
# the coordinator's ``stack_rngs(base, C_pad)`` exactly (client-stable keys are what
# make cohort gathering invisible to the math).
_COHORT_SALT = 0xC0F0
_DROPOUT_SALT = 0xD409


class RoundBlockResult(NamedTuple):
    """Stacked outcome of one fused R-round block.  Leading axis of every stacked
    field is the round-within-block index."""

    params: Params  # end-of-block global params (model-sharded on a 2-D mesh)
    server_opt_state: Any  # end-of-block server optimizer state (same layout)
    metrics: dict[str, jax.Array]  # weighted scalar metrics per round, each [R]
    survivors: jax.Array  # [R] int32 — surviving sampled clients per round
    client_metrics: ClientMetrics | None  # [R, K] (None unless collect_client_detail)
    update_sq_norms: jax.Array | None  # [R, K]
    weights: jax.Array | None  # [R, K] realized aggregation weights
    cohort_ids: jax.Array | None  # [R, K] sampled client ids (device-sampling only)


RoundBlockFn = Callable[..., RoundBlockResult]


def stack_round_keys(seed: int, round_ids) -> jax.Array:
    """The ``[R]`` per-round base keys a block consumes: ``fold_in(key(seed), r)``
    for each round id — element-for-element identical to the single-round
    coordinator's per-round base key, so fused and single-round runs draw the same
    per-client training keys."""
    base = jax.random.key(seed)
    return jax.vmap(lambda r: jax.random.fold_in(base, r))(jnp.asarray(round_ids))


def build_round_block(
    apply_fn: Callable[..., jax.Array],
    training: TrainingConfig,
    mesh: Mesh,
    strategy: Strategy | None = None,
    *,
    num_clients: int,
    padded_clients: int,
    step_clients: int | None = None,
    cohort_size: int | None = None,
    dropout_rate: float = 0.0,
    min_completion_rate: float = 0.5,
    grad_fn: GradFn | None = None,
    local_fit: Callable | None = None,
    validation: ValidationConfig | None = None,
    client_chunk: int | None = None,
    params_like: Params | None = None,
    collect_client_detail: bool = True,
    cohort_mode: bool | None = None,
    axis_name: str = CLIENT_AXIS,
    donate: bool = False,
    frozen_base=None,
) -> RoundBlockFn:
    """Build the fused R-round block function.

    Returns ``round_block(global_params, server_opt_state, data, num_samples,
    base_keys, lr_scales, cohort_idx=None, cohort_mask=None) ->
    RoundBlockResult`` where

    * ``data`` is the FULL population's ``ClientData`` (``[C_pad, ...]`` sharded
      over the client axis) and ``num_samples`` its ``[C_pad]`` per-client sample
      counts — both constant across blocks, resident in HBM;
    * ``base_keys`` is ``[R]`` per-round PRNG keys (``stack_round_keys``) and
      ``lr_scales`` the ``[R]`` traced schedule scales — R, the scan length, is
      static per compile, so run full blocks of one length and finish ragged
      tails on the single-round path;
    * ``cohort_idx``/``cohort_mask`` (``[R, step_clients]``) carry host-sampled
      cohorts (client ids per slot + survivor mask).  Pass BOTH or NEITHER: with
      neither, cohorts are resampled ON-DEVICE each scanned round from the
      round's base key (permutation without replacement over ``num_clients``,
      then simulated dropout at ``dropout_rate``).

    ``num_clients`` is the real population, ``padded_clients`` its device padding,
    ``step_clients`` the (padded) per-round step width, ``cohort_size`` the real
    sampled cohort K (defaults to ``num_clients``).  ``cohort_mode`` decides the
    round's layout: True runs the in-scan cohort GATHER (``cohort_idx`` rows are
    client ids in SLOT order, the mask is slot-ordered); False runs the full
    population directly (the mask is client-id-ordered over ``step_clients ==
    padded_clients`` slots).  It defaults to "a strict subset is sampled or
    stepped" (``cohort_size < num_clients or step_clients < padded_clients``) —
    callers whose layout choice follows other rules (the coordinator disables
    gathering when ``client_chunk`` doesn't divide the cohort padding) must pass
    their own, since cohort padding can equal population padding while the mask is
    still slot-ordered.  Robust aggregation, SCAFFOLD, and central DP are NOT
    supported here (the coordinator falls back to the single-round path for
    those); ``validation`` and ``client_chunk`` are.

    On a 2-D ``clients x model`` mesh the scanned round program keeps params and
    opt state in the FSDP layout (see :func:`build_sharded_round`; pass
    ``params_like=`` exactly like the single-round builder): the scan carry
    stays model-sharded round to round, so a fused block never materializes a
    replicated copy of the model between its rounds either.

    ``donate=True`` donates the params/opt-state buffers to the block call — the
    caller must keep only the returned arrays, as the coordinator does.
    """
    if step_clients is None:
        step_clients = padded_clients
    if cohort_size is None:
        cohort_size = num_clients
    if not 0 < num_clients <= padded_clients:
        raise ValueError("need 0 < num_clients <= padded_clients")
    if not 0 < step_clients <= padded_clients:
        raise ValueError("need 0 < step_clients <= padded_clients")
    if not 0 < cohort_size <= min(num_clients, step_clients):
        raise ValueError("need 0 < cohort_size <= min(num_clients, step_clients)")
    if cohort_mode is None:
        # Width comparison alone is NOT enough: a 97-of-100 cohort pads to the
        # same width as the 100-client population, yet its mask is slot-ordered.
        cohort_mode = cohort_size < num_clients or step_clients < padded_clients
    if not cohort_mode and step_clients != padded_clients:
        raise ValueError(
            "cohort_mode=False runs the full population: step_clients must equal "
            f"padded_clients (got {step_clients} != {padded_clients})"
        )
    # The shared engine's gate, baked into the fused program as a static value.
    # Local import: parallel is imported by orchestration's module body, so a
    # top-level import back into orchestration would be a cycle.
    from nanofed_tpu.orchestration.engine import completion_required

    required = completion_required(cohort_size, min_completion_rate)

    # Frozen-base rounds (adapters): the base is a LOOP-INVARIANT input of the
    # scanned program — it enters the jit once, feeds every scanned round
    # through the shard_map boundary, and is never part of the carry (so a
    # fused block's carry stays adapter-sized, not model-sized).
    sharded = build_sharded_round(
        apply_fn, training, mesh, strategy,
        grad_fn=grad_fn, local_fit=local_fit, validation=validation,
        client_chunk=client_chunk, params_like=params_like, axis_name=axis_name,
        frozen_base=frozen_base,
    )
    # Joint (hosts, clients) spec on a 3-axis mesh: the in-scan cohort gather's
    # result must land in the same layout the data rides, host rows intact.
    csh = client_sharding(mesh, axis_name)

    def one_round(data, num_samples, base_params, carry, xs):
        gp, sos = carry
        base, lr_scale, idx, mask = xs
        device_sampled = mask is None
        if device_sampled:
            if cohort_mode:
                perm = jax.random.permutation(
                    jax.random.fold_in(base, _COHORT_SALT), num_clients
                )
                idx = jnp.zeros(step_clients, jnp.int32)
                idx = idx.at[:cohort_size].set(perm[:cohort_size].astype(jnp.int32))
                keep = jnp.ones(cohort_size, jnp.float32)
                if dropout_rate > 0:
                    keep = (
                        jax.random.uniform(
                            jax.random.fold_in(base, _DROPOUT_SALT), (cohort_size,)
                        )
                        >= dropout_rate
                    ).astype(jnp.float32)
                mask = jnp.zeros(step_clients, jnp.float32).at[:cohort_size].set(keep)
            else:
                mask = (jnp.arange(step_clients) < num_clients).astype(jnp.float32)
                if dropout_rate > 0:
                    mask = mask * (
                        jax.random.uniform(
                            jax.random.fold_in(base, _DROPOUT_SALT), (step_clients,)
                        )
                        >= dropout_rate
                    ).astype(jnp.float32)
        survivors = mask.sum().astype(jnp.int32)
        # Below the completion floor the whole round is gated to zero weight — the
        # round program's documented identity (FAILED) semantics.
        ok = (survivors >= required).astype(jnp.float32)
        mask_eff = mask * ok
        # Client-STABLE keys: slot i carries the key of the client it hosts, so a
        # fused round is bit-identical to the coordinator's single-round draw.
        keys_all = jax.random.split(base, padded_clients)
        if cohort_mode:
            rngs = keys_all[idx]
            data_r = jax.tree.map(lambda x: x[idx], data)
            weights = compute_weights(num_samples[idx], mask_eff)
        else:
            rngs = keys_all
            data_r = data
            weights = compute_weights(num_samples, mask_eff)
        data_r = jax.tree.map(lambda x: lax.with_sharding_constraint(x, csh), data_r)
        noise_rng = jax.random.fold_in(rngs[0], 0x5EED)
        if frozen_base is not None:
            gp, sos, metrics, client_metrics, sq_norms = sharded(
                gp, sos, base_params, data_r, weights, rngs, noise_rng,
                jnp.asarray(lr_scale, jnp.float32),
            )
        else:
            gp, sos, metrics, client_metrics, sq_norms = sharded(
                gp, sos, data_r, weights, rngs, noise_rng,
                jnp.asarray(lr_scale, jnp.float32),
            )
        ys: dict[str, Any] = {"metrics": metrics, "survivors": survivors}
        if collect_client_detail:
            ys["client_metrics"] = client_metrics
            ys["update_sq_norms"] = sq_norms
            ys["weights"] = weights
            if device_sampled and cohort_mode:
                ys["cohort_ids"] = idx
        return (gp, sos), ys

    @partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def _block(
        global_params, server_opt_state, data, num_samples, base_keys, lr_scales,
        cohort_idx, cohort_mask, base_params,
    ):
        xs = (base_keys, jnp.asarray(lr_scales, jnp.float32), cohort_idx, cohort_mask)
        (gp, sos), ys = lax.scan(
            partial(one_round, data, num_samples, base_params),
            (global_params, server_opt_state),
            xs,
        )
        return gp, sos, ys

    def round_block(
        global_params: Params,
        server_opt_state: Any,
        data: ClientData,
        num_samples: jax.Array,
        base_keys: jax.Array,
        lr_scales: jax.Array,
        cohort_idx: jax.Array | None = None,
        cohort_mask: jax.Array | None = None,
        base_params: Params | None = None,
    ) -> RoundBlockResult:
        if (cohort_mask is None) != (cohort_idx is None) and cohort_mode:
            raise ValueError(
                "pass BOTH cohort_idx and cohort_mask (host-sampled cohorts) or "
                "NEITHER (on-device resampling)"
            )
        if (base_params is None) != (frozen_base is None):
            raise ValueError(
                "base_params must be passed exactly when the block was built "
                "with frozen_base= (the frozen-base/adapter program)"
            )
        gp, sos, ys = _block(
            global_params, server_opt_state, data, num_samples, base_keys,
            lr_scales, cohort_idx, cohort_mask, base_params,
        )
        return RoundBlockResult(
            params=gp,
            server_opt_state=sos,
            metrics=ys["metrics"],
            survivors=ys["survivors"],
            client_metrics=ys.get("client_metrics"),
            update_sq_norms=ys.get("update_sq_norms"),
            weights=ys.get("weights"),
            cohort_ids=ys.get("cohort_ids"),
        )

    # Lowered-program access for the cost profiler (observability.profiling):
    # round_block is a plain wrapper, so expose the inner jit — its signature is
    # (params, sos, data, num_samples, base_keys, lr_scales, cohort_idx,
    # cohort_mask, base_params), with None for idx/mask selecting on-device
    # resampling and None for base_params unless built with frozen_base=.
    round_block.jit_program = _block
    return round_block
