"""The SCAFFOLD federated round as one jitted SPMD program.

Same shape as ``parallel.round_step`` — ``jit(shard_map(vmap(local_fit) -> psum))`` —
with two extra pieces of ROUND STATE flowing through the program:

    c        the server control (replicated, params-shaped)
    c_stack  every client's control (``[C, ...]`` sharded over the client axis)

Per round (Karimireddy et al. 2020, Alg. 1):

    per device:  vmap(scaffold_fit) over its client shard — each local step corrected
                 by (c - c_i); each client emits (delta y_i, delta c_i)
    across mesh: x <- x + server_tx( mean_{participants} delta y_i )   (uniform mean:
                 the paper's estimator — sample-count weighting would re-bias exactly
                 the drift the controls remove)
                 c <- c + sum_{participants} delta c_i / N_total
    write-back:  delta c_i rows are returned PER CLIENT (zeroed for non-participants)
                 so the host can ``scatter-add`` them into the population stack —
                 collision-safe under cohort gathering, where padding slots all alias
                 row 0 with weight 0 (an ``.at[idx].add`` of exact zeros).

The reference has no comparable algorithm (its trainer surface is plain SGD + DP-SGD,
``nanofed/trainer/``); SCAFFOLD is part of this framework's non-IID story alongside
FedProx (``trainer.local``) and server momentum/Adam (``aggregation.base``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from nanofed_tpu.aggregation.base import Strategy, fedavg_strategy
from nanofed_tpu.aggregation.fedavg import psum_weighted_mean, psum_weighted_metrics
from nanofed_tpu.core.types import ClientData, ClientMetrics, Params, PRNGKey
from nanofed_tpu.parallel.mesh import (
    CLIENT_AXIS,
    MeshLayout,
    multi_axis_shard_map_kwargs,
    shard_map,
)
from nanofed_tpu.trainer.config import TrainingConfig
from nanofed_tpu.trainer.local import GradFn
from nanofed_tpu.trainer.scaffold import make_scaffold_local_fit
from nanofed_tpu.utils.trees import tree_sq_norm, tree_where


class ScaffoldStepResult(NamedTuple):
    params: Params  # new global params (replicated)
    server_opt_state: Any  # server optimizer state (replicated)
    c_global: Params  # updated server control (replicated)
    delta_c: Params  # [C, ...] per-client control deltas (zero for non-participants)
    metrics: dict[str, jax.Array]
    client_metrics: ClientMetrics  # per-client arrays [C]
    update_sq_norms: jax.Array  # [C]


def build_scaffold_round_step(
    apply_fn: Callable[..., jax.Array],
    training: TrainingConfig,
    mesh: Mesh,
    num_clients_total: int,
    strategy: Strategy | None = None,
    grad_fn: GradFn | None = None,
    client_chunk: int | None = None,
    params_like: Params | None = None,
    axis_name: str = CLIENT_AXIS,
    donate: bool = False,
) -> Callable[..., ScaffoldStepResult]:
    """Compile the SCAFFOLD round for a mesh.

    Returns ``scaffold_step(global_params, server_opt_state, c_global, c_stack, data,
    weights, rngs, lr_scale=1.0)``.  ``c_stack`` leaves are ``[C, ...]`` sharded over
    ``axis_name`` EXACTLY like ``data`` — under cohort gathering the caller gathers the
    cohort's control rows alongside its data rows and scatter-adds the returned
    ``delta_c`` back (``Coordinator`` owns both sides).

    ``num_clients_total`` is the REAL population size N (not the padded stack size):
    the server-control step c <- c + (|S|/N) * mean delta c_i deliberately under-weights
    a small cohort's information, and padding rows are not clients.

    ``weights`` keeps the standard sample-count-times-mask convention so reporting
    (weighted metrics) matches every other path, but the MODEL aggregate is the uniform
    participant mean — the paper's estimator, and the sensitivity-free choice
    (sample-count weighting would let one hoarding client steer the corrected round).

    ``client_chunk`` bounds activation memory via a ``lax.map`` over chunks of a
    chunk-wide ``vmap``.  There is no streaming variant: SCAFFOLD's per-client OUTPUT
    (``delta_c``) is itself params-sized per client, so the ``[C, |params|]`` output
    stack exists regardless — streaming the reduce would save nothing.

    On a 2-D ``clients x model`` mesh pass ``params_like=`` and commit params,
    opt state, and ``c_global`` in the ``param_sharding`` layout (``c_stack``
    stays client-sharded) — all three stay model-sharded end to end, exactly as
    documented on :func:`nanofed_tpu.parallel.round_step.build_sharded_round`.
    """
    strategy = strategy or fedavg_strategy()
    server_tx = strategy.server_tx
    local_fit = make_scaffold_local_fit(apply_fn, training, grad_fn=grad_fn)
    # 2-D clients x model mesh (FSDP, the exact boundary rule build_sharded_round
    # uses — ModelAxisLayout is the single shared implementation): params, opt
    # state, AND the server control are params-shaped round state — they cross
    # the shard_map boundary split over the model axis, are gathered once to
    # feed the per-client compute, and each model shard slices its piece of the
    # full aggregates before updating.  The per-client control stack stays
    # client-sharded like data.  No-op on any 1-D mesh.
    layout = MeshLayout(mesh, axis_name=axis_name)
    layout.require_params_like(params_like)
    c_axes = layout.client_axes
    raw_keys_at_boundary = layout.raw_keys_at_boundary
    params_specs = layout.boundary_specs(params_like)
    sos_specs = layout.boundary_specs(
        jax.eval_shape(server_tx.init, params_like) if layout.multi_axis else None
    )

    def shard_body(gp, sos, c_global, c_stack, data: ClientData, weights, rngs, lr_scale):
        if raw_keys_at_boundary:
            rngs = jax.random.wrap_key_data(rngs)
        # gp / c_global are this device's model shards on a 2-D mesh (full leaves
        # on 1-D): gather once for the per-client compute; the boundary values stay
        # shards for the update at the end.
        gp_full = layout.gather_full(gp, params_specs)
        cg_full = layout.gather_full(c_global, params_specs)
        gp_v = layout.cast_varying(gp_full)
        cg_v = layout.cast_varying(cg_full)
        fit = lambda g, d, r, ci: local_fit(g, d, r, cg_v, ci, lr_scale=lr_scale)
        c_local = rngs.shape[0]
        chunking = client_chunk is not None and client_chunk < c_local
        if chunking and c_local % client_chunk != 0:
            raise ValueError(
                f"client_chunk {client_chunk} must divide per-device client count "
                f"{c_local}"
            )
        vfit = jax.vmap(fit, in_axes=(None, 0, 0, 0))
        if chunking:
            n_chunks = c_local // client_chunk
            chunked = jax.tree.map(
                lambda x: x.reshape(n_chunks, client_chunk, *x.shape[1:]),
                (data, rngs, c_stack),
            )
            with jax.named_scope("local_fit"):
                result = lax.map(
                    lambda args: vfit(gp_v, args[0], args[1], args[2]), chunked
                )
            result = jax.tree.map(lambda x: x.reshape(c_local, *x.shape[2:]), result)
        else:
            with jax.named_scope("local_fit"):
                result = vfit(gp_v, data, rngs, c_stack)

        delta_y = jax.tree.map(lambda p, g: p - g[None], result.params, gp_v)
        participating = (weights > 0).astype(jnp.float32)
        total_w = layout.client_psum(weights.sum())

        # Model update: server_tx over the UNIFORM participant mean of delta y —
        # full aggregate sliced down to this device's model shard first, so the
        # server optimizer only ever touches shard-sized state.
        with jax.named_scope("client_reduce"):
            agg_delta = psum_weighted_mean(delta_y, participating, c_axes)
        with jax.named_scope("server_apply"):
            agg_delta = layout.slice_shard(agg_delta)
            neg_delta = jax.tree.map(jnp.negative, agg_delta)
            updates, new_sos = server_tx.update(neg_delta, sos, gp)
            ok = total_w > 0
            new_gp = tree_where(ok, optax.apply_updates(gp, updates), gp)
            new_sos = tree_where(ok, new_sos, sos)

        # Control updates: dc rows zeroed outside the cohort (the scatter-add then
        # writes exact zeros for padding/dropped slots); the server control moves by
        # sum_participants dc_i / N_total — an empty round moves nothing.
        delta_c = jax.tree.map(
            lambda d: jnp.where(
                participating.reshape((-1,) + (1,) * (d.ndim - 1)) > 0, d, 0.0
            ).astype(d.dtype),
            result.delta_c,
        )
        with jax.named_scope("client_reduce"):
            c_sum = jax.tree.map(
                lambda d: layout.client_psum(d.sum(axis=0)), delta_c
            )
        with jax.named_scope("server_apply"):
            c_sum = layout.slice_shard(c_sum)
            new_c_global = jax.tree.map(
                lambda c, s: jnp.where(
                    ok, c + s / float(num_clients_total), c
                ).astype(c.dtype),
                c_global, c_sum,
            )

        with jax.named_scope("round_metrics"):
            metrics = psum_weighted_metrics(result.metrics, weights, c_axes)
            metrics["participating_clients"] = layout.client_psum(
                (weights > 0).sum())
            sq_norms = jax.vmap(tree_sq_norm)(delta_y)
        return new_gp, new_sos, new_c_global, delta_c, metrics, result.metrics, sq_norms

    dspec = layout.data_spec
    inner = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(params_specs, sos_specs, params_specs, dspec,
                  dspec, dspec, dspec, P()),
        out_specs=(params_specs, sos_specs, params_specs, dspec, P(),
                   dspec, dspec),
        **multi_axis_shard_map_kwargs(mesh),
    )
    if raw_keys_at_boundary:
        def sharded(gp, sos, c_global, c_stack, data, weights, rngs, lr_scale):
            # fedlint: disable=FED002 (dtype is STATIC metadata, not a traced value — the branch selects the key-data conversion at trace time, no concretization)
            if jnp.issubdtype(jnp.asarray(rngs).dtype, jax.dtypes.prng_key):
                rngs = jax.random.key_data(rngs)
            return inner(gp, sos, c_global, c_stack, data, weights, rngs, lr_scale)
    else:
        sharded = inner

    # c_stack (argnum 3) is deliberately NOT donated: in full-participation mode the
    # caller passes its population stack directly and must still scatter-add the
    # returned deltas into that same buffer after the step.
    @partial(jax.jit, donate_argnums=(0, 1, 2) if donate else ())
    def scaffold_step(
        global_params: Params,
        server_opt_state: Any,
        c_global: Params,
        c_stack: Params,
        data: ClientData,
        weights: jax.Array,
        rngs: PRNGKey,
        lr_scale: jax.Array | float = 1.0,
    ) -> ScaffoldStepResult:
        lr_scale = jnp.asarray(lr_scale, jnp.float32)
        gp, sos, cg, dc, metrics, client_metrics, sq_norms = sharded(
            global_params, server_opt_state, c_global, c_stack, data, weights, rngs,
            lr_scale,
        )
        return ScaffoldStepResult(gp, sos, cg, dc, metrics, client_metrics, sq_norms)

    # Lowered-program access for the cost profiler (observability.profiling):
    # same uniform `.jit_program` contract as build_round_step/build_round_block.
    scaffold_step.jit_program = scaffold_step
    return scaffold_step
