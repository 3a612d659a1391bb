"""The federated round as one jitted SPMD program.

This module replaces the reference's entire round machinery — the 1 Hz polling barrier
(``nanofed/orchestration/coordinator.py:205-245``), JSON weight deserialization
(``:307-322``), the Python FedAvg loops (``server/aggregator/fedavg.py:56-63``), and the
HTTP transport between them — with a single ``jit(shard_map(...))``:

    per device:  vmap(local_fit) over its shard of clients      (MXU: batched SGD)
    across mesh: psum-weighted mean of client deltas over ICI   (the "wire")
    replicated:  server optimizer applies the aggregated delta  (FedAvg/FedAvgM/FedAdam)

The round barrier is implicit in SPMD lockstep; partial participation is a zero-weight
mask, not a timeout.
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
from nanofed_tpu.aggregation.privacy import PrivacyAwareAggregationConfig
from nanofed_tpu.aggregation.robust import RobustAggregationConfig, robust_aggregate
from nanofed_tpu.core.types import ClientData, ClientMetrics, Params, PRNGKey
from nanofed_tpu.parallel.mesh import (
    CLIENT_AXIS,
    MeshLayout,
    multi_axis_shard_map_kwargs,
    shard_map,
)
from nanofed_tpu.privacy.noise import get_noise_generator, tree_noise
from nanofed_tpu.security.validation import (
    ValidationConfig,
    loo_zscore,
    stacked_leaf_stats,
)
from nanofed_tpu.trainer.config import TrainingConfig
from nanofed_tpu.trainer.local import GradFn, make_local_fit
from nanofed_tpu.utils.trees import tree_clip_by_global_norm, tree_sq_norm, tree_where


class FrozenBase(NamedTuple):
    """Frozen-base round programs (parameter-efficient federation,
    ``nanofed_tpu.adapters``): the federated ``global_params`` are the small
    TRAINABLE tree (LoRA adapters) while the base model crosses the shard_map
    boundary as an extra, NEVER-UPDATED input — model-sharded on a 2-D/3-D mesh
    exactly like params (one all-gather over the model axis per round feeds the
    per-client compute), absent from the params/opt-state fixed point, and
    never donated (the caller re-passes the same buffers every round).

    ``base_like`` supplies the per-leaf shapes for the boundary specs (concrete
    or abstract); ``bind(base_full)`` receives the gathered full base INSIDE
    the round body and must return an apply with the zoo signature
    ``apply(trainable_params, x, *, train=..., rng=...)`` — for adapters,
    :func:`nanofed_tpu.adapters.make_adapter_apply` partially applied to the
    spec."""

    base_like: Params
    bind: Callable[[Params], Callable[..., jax.Array]]


class RoundStepResult(NamedTuple):
    params: Params  # new global params (replicated over clients; model-sharded on a 2-D mesh)
    server_opt_state: Any  # server optimizer state (same layout as params)
    metrics: dict[str, jax.Array]  # weighted scalar metrics for the round
    client_metrics: ClientMetrics  # per-client arrays [C] (for round metrics JSON parity)
    update_sq_norms: jax.Array  # [C] squared L2 norm of each client's delta


RoundStepFn = Callable[..., RoundStepResult]


def build_sharded_round(
    apply_fn: Callable[..., jax.Array],
    training: TrainingConfig,
    mesh: Mesh,
    strategy: Strategy | None = None,
    grad_fn: GradFn | None = None,
    local_fit: Callable | None = None,
    central_privacy: PrivacyAwareAggregationConfig | None = None,
    validation: ValidationConfig | None = None,
    robust: RobustAggregationConfig | None = None,
    client_chunk: int | None = None,
    params_like: Params | None = None,
    axis_name: str = CLIENT_AXIS,
    frozen_base: FrozenBase | None = None,
) -> Callable:
    """Build the UN-jitted ``shard_map`` round program.

    Returns ``sharded(global_params, server_opt_state, data, weights, rngs,
    noise_rng, lr_scale) -> (params, server_opt_state, metrics, client_metrics,
    update_sq_norms)`` — the SPMD body that ``build_round_step`` wraps in one
    ``jit`` per round, and that ``parallel.multi_round.build_round_block`` scans
    over R rounds inside a SINGLE ``jit`` (the fused multi-round engine).  Both
    callers share this one program, so a fused block is the same math as R
    single-round calls by construction.

    ``data`` leaves are ``[C, N, ...]`` sharded over ``axis_name``, ``weights`` is
    ``[C]`` (sample counts x participation mask — zero drops a client out of the
    reduction), and ``rngs`` is ``[C]`` per-client keys.  ``lr_scale`` is a TRACED
    scalar multiplying every local optimizer step — the per-round lr-schedule hook
    (``trainer.schedules``): varying it across rounds does not retrace.

    ``local_fit`` overrides the default fit (e.g. ``make_private_local_fit`` for DP-SGD
    clients); it must have the ``local_fit(global_params, data, rng)`` signature.

    ``central_privacy`` turns the reduce into DP-FedAvg (McMahan et al. 2018), the in-mesh
    form of ``PrivacyAwareAggregator``'s central path (``nanofed/server/aggregator/
    privacy.py:179-194``): each client's delta is clipped to C, aggregation uses *uniform*
    weights over participants (so per-client sensitivity is exactly C/K), and one Gaussian
    draw of std σ·C/K is added to the replicated aggregate.  The server noise key is
    derived from ``rngs`` so the signature is unchanged; accounting stays host-side via
    ``record_central_privacy``.

    ``validation`` enables in-mesh update validation (the SPMD form of
    ``DefaultModelValidator``, ``nanofed/server/validation.py:53-135``): per-client
    finiteness + global-norm bound checks plus cohort z-score anomaly detection, with the
    cohort statistics computed by ``psum`` across the mesh.  Invalid clients get weight 0 —
    rejection without data-dependent shapes.  The validity count is reported as
    ``metrics["valid_clients"]``.

    ``client_chunk`` bounds HBM when clients-per-device is large (SURVEY.md §7 "clients ≫
    chips"): a full ``vmap`` over N clients materializes N copies of every local-training
    activation at once; with ``client_chunk=k`` the per-device client batch is processed
    as a sequential scan over N/k chunks of a k-wide vmap, so activation memory scales
    with k while the MXU still sees k-client-wide batched matmuls.  Must divide the
    per-device client count.  Without ``validation`` the chunked reduce STREAMS: each
    chunk's weighted delta sum folds into one params-sized accumulator, so the
    ``[N, |params|]`` per-client stacks never exist (see ``streaming_chunk_reduce``);
    with ``validation`` the deltas must materialize, because cohort z-score rejection
    re-weights clients only after every client's statistics are known.

    On a 2-D ``clients x model`` mesh (``make_mesh(shape=(c, m))``), the round
    program is FSDP-shaped: params and server opt state cross the shard_map
    boundary in the :func:`nanofed_tpu.parallel.mesh.param_sharding` layout
    (each leaf's largest divisible dim split over ``model`` — ``params_like``
    is REQUIRED then, so the per-leaf layout can become the shard_map specs),
    the body all-gathers the param shards over the model axis once to feed the
    per-client compute, the FedAvg reduce remains a ``psum`` over ``clients``
    only, and each model shard slices its piece of the full aggregate before
    the server-optimizer update — so params and opt state never materialize
    replicated between rounds, on-device or in the scan carry of a fused block.
    Client data is sharded over ``clients`` and replicated over ``model``
    exactly as on the 1-D mesh (model columns recompute the same clients; the
    model axis buys parameter/optimizer-state capacity, not client throughput).

    ``robust`` replaces the weighted-mean reduce with the coordinate-wise TRIMMED mean
    (Yin et al. 2018; see ``aggregation.robust``): per-client deltas are
    ``all_gather``ed over the client axis (order statistics need every value — a
    ``psum`` cannot express a sort) and each coordinate discards the ``trim_k``
    extremes per side before averaging, bounding any ``<= trim_k`` Byzantine clients'
    influence structurally.  Unweighted over the kept ranks by design (sample-count
    weighting would let an attacker amplify itself).  Composes with ``validation``
    (rejected clients are excluded before the trim); refused alongside
    ``central_privacy`` (the trimmed mean's DP sensitivity differs from the clipped
    mean's — combining them silently would void the stated (ε, δ)).
    """
    strategy = strategy or fedavg_strategy()
    # 2-D clients x model mesh (FSDP): params/opt state cross the shard_map
    # boundary split over the model axis (ModelAxisLayout — the boundary rule
    # shared verbatim with the SCAFFOLD builder); the body gathers the param
    # shards once for the per-client compute and slices the aggregated delta
    # back to its shard before the server update.  On any 1-D mesh every layout
    # method is the identity and the specs stay P()/P(clients) — the classic
    # program, byte for byte.
    layout = MeshLayout(mesh, axis_name=axis_name)
    layout.require_params_like(params_like)
    raw_keys_at_boundary = layout.raw_keys_at_boundary
    # The client DATA axis of the program: the plain client axis on 1-D/2-D
    # meshes, the (hosts, clients) tuple on a 3-axis mesh — every client-axis
    # collective below reduces over c_axes (hierarchically once hosts exist:
    # host-local psum over ICI, then ONE cross-host psum over DCN, so the
    # inter-host stage moves one model-sized tensor per round).
    c_axes = layout.client_axes

    if robust is not None and central_privacy is not None:
        raise ValueError(
            "robust= cannot be combined with central_privacy=: the DP guarantee is "
            "calibrated for the clipped uniform MEAN (sensitivity C/K); a trimmed "
            "mean has a different sensitivity and the stated budget would be wrong"
        )
    if local_fit is not None and grad_fn is not None:
        raise ValueError(
            "pass either grad_fn (used to build the default local fit) or a complete "
            "local_fit, not both — a supplied local_fit ignores grad_fn"
        )
    if frozen_base is not None:
        if local_fit is not None or grad_fn is not None:
            # The bound apply only exists INSIDE the round body (it closes over
            # the gathered base), so a build-time fit/grad override could never
            # see the base it needs — refuse rather than train a base-blind fit.
            raise ValueError(
                "frozen_base= builds the local fit from bind(gathered_base) "
                "inside the round body; a custom local_fit/grad_fn cannot "
                "close over the base and is refused"
            )
        base_specs = layout.boundary_specs(frozen_base.base_like)
        fit_takes_lr_scale = True  # make_local_fit always supports lr_scale
    else:
        local_fit = local_fit or make_local_fit(apply_fn, training, grad_fn=grad_fn)
        # Per-round lr scheduling rides a TRACED scalar (one compiled program; see
        # trainer.schedules).  A custom local_fit that doesn't declare support simply
        # trains unscaled — the Coordinator refuses a non-constant schedule in that
        # case rather than silently ignoring it.
        fit_takes_lr_scale = getattr(local_fit, "supports_lr_scale", False)
    server_tx = strategy.server_tx
    # The optimizer-state layout follows the same per-leaf rule as params —
    # abstract init only (eval_shape), nothing materializes here.
    params_specs = layout.boundary_specs(params_like)
    sos_specs = layout.boundary_specs(
        jax.eval_shape(server_tx.init, params_like) if layout.multi_axis else None
    )

    def clip_deltas(delta):
        """Per-client clip to the central-DP sensitivity bound C (local, cohort-free)."""
        clip = central_privacy.privacy.max_gradient_norm
        return jax.vmap(lambda d: tree_clip_by_global_norm(d, clip)[0])(delta)

    def streaming_chunk_reduce(fit, gp_v, data, rngs, weights, n_chunks):
        """Clients >> chips FAST PATH: fold the weighted reduce into the chunk loop.

        The materializing path below runs every chunk's ``vmap(local_fit)``, stacks all
        ``C_local`` per-client params, and only then forms deltas and reduces — two
        ``[C_local, |params|]`` temporaries (at the 1000-client flagship shape: ~9.6 GB
        of HBM written and re-read per round just to be summed).  Here each chunk's
        weighted delta sum is accumulated into one params-sized carry as soon as it is
        computed, so peak memory scales with ``client_chunk``, not ``C_local``, and the
        big temporaries never exist.  Per-client OUTPUTS that the round reports
        (metrics, squared update norms) are O(C) scalars — those still stack.

        Only taken when ``validation is None``: cohort z-score rejection must adjust
        weights AFTER seeing every client's stats, which a streamed weighted sum cannot
        retroactively honor.  Central-DP clipping IS local (clip to constant C), so the
        DP path streams fine — clip before accumulating, uniform weights.
        """
        uniform_dp = central_privacy is not None
        chunked = jax.tree.map(
            lambda x: x.reshape(n_chunks, client_chunk, *x.shape[1:]),
            (data, rngs, weights),
        )
        acc0 = jax.tree.map(lambda g: jnp.zeros_like(g), gp_v)

        def step_chunk(acc, chunk):
            c_data, c_rngs, c_weights = chunk
            with jax.named_scope("local_fit"):
                result = jax.vmap(fit, in_axes=(None, 0, 0))(gp_v, c_data, c_rngs)
            delta = jax.tree.map(lambda p, g: p - g[None], result.params, gp_v)
            if uniform_dp:
                delta = clip_deltas(delta)
                w = (c_weights > 0).astype(jnp.float32)
            else:
                w = c_weights
            with jax.named_scope("client_reduce"):
                acc = jax.tree.map(
                    lambda a, d: a + jnp.tensordot(w.astype(d.dtype), d, axes=1),
                    acc, delta,
                )
            with jax.named_scope("round_metrics"):
                sq_norms = jax.vmap(tree_sq_norm)(delta)
            return acc, (result.metrics, sq_norms)

        with jax.named_scope("chunk_loop"):
            acc, (metrics, sq_norms) = lax.scan(step_chunk, acc0, chunked)
        flat = lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
        return acc, jax.tree.map(flat, metrics), flat(sq_norms)

    def apply_server_update(gp, sos, agg_delta, total_w):
        # optax convention: pass the NEGATIVE delta as "gradient" so SGD(1.0) applies
        # +delta (exact FedAvg).  A round with zero total weight (no participants /
        # all failed — the reference marks these FAILED, coordinator.py:295-304) must
        # leave params AND server state untouched, even for stateful server optimizers.
        # ``gp``/``sos`` are this device's MODEL SHARDS on a 2-D mesh (full leaves on
        # 1-D); ``agg_delta`` arrives full and is sliced down, so the server optimizer
        # only ever touches shard-sized state.
        with jax.named_scope("server_apply"):
            agg_delta = layout.slice_shard(agg_delta)
            neg_delta = jax.tree.map(jnp.negative, agg_delta)
            updates, new_sos = server_tx.update(neg_delta, sos, gp)
            ok = total_w > 0
            new_gp = tree_where(ok, optax.apply_updates(gp, updates), gp)
            new_sos = tree_where(ok, new_sos, sos)
        return new_gp, new_sos

    def add_central_noise(agg_delta, noise_rng, participants):
        sigma = central_privacy.privacy.noise_multiplier
        clip = central_privacy.privacy.max_gradient_norm
        gen = get_noise_generator(central_privacy.privacy.noise_type)
        server_noise = tree_noise(noise_rng, agg_delta, sigma * clip / participants, gen)
        return jax.tree.map(jnp.add, agg_delta, server_noise)

    def finish_streamed_round(gp, sos, weights, noise_rng, client_metrics, sq_norms,
                              local_wsum):
        """Aggregate a streamed local weighted-delta sum: one tree-psum, then the same
        server transform / metrics as the materializing path."""
        total_w = layout.client_psum(weights.sum())
        with jax.named_scope("client_reduce"):
            global_wsum = jax.tree.map(
                layout.client_psum, local_wsum
            )
        if central_privacy is not None:
            # local_wsum was accumulated with UNIFORM weights over clipped deltas, so
            # sensitivity of the mean is exactly C/K — identical math to the
            # materializing DP path.
            participants = jnp.maximum(
                layout.client_psum(
                    (weights > 0).sum().astype(jnp.float32)),
                1.0,
            )
            agg_delta = jax.tree.map(
                lambda x: x / participants.astype(x.dtype), global_wsum
            )
            agg_delta = add_central_noise(agg_delta, noise_rng, participants)
        else:
            den = jnp.maximum(total_w, 1e-12)
            agg_delta = jax.tree.map(lambda x: x / den.astype(x.dtype), global_wsum)
        new_gp, new_sos = apply_server_update(gp, sos, agg_delta, total_w)
        with jax.named_scope("round_metrics"):
            metrics = psum_weighted_metrics(client_metrics, weights, c_axes)
            metrics["participating_clients"] = layout.client_psum(
                (weights > 0).sum())
        return new_gp, new_sos, metrics, client_metrics, sq_norms

    def shard_body(gp, sos, data: ClientData, weights, rngs, noise_rng, lr_scale,
                   base=None):
        if raw_keys_at_boundary:
            rngs = jax.random.wrap_key_data(rngs)
            noise_rng = jax.random.wrap_key_data(noise_rng)
        # ``gp`` is this device's model shard (full on 1-D); the per-client compute
        # needs full params, so gather over the model axis ONCE per round.  gp stays
        # the shard for the server update at the end.
        gp_full = layout.gather_full(gp, params_specs)
        # gp arrives replicated (unvarying); the per-client scan carry inside local_fit is
        # device-varying, so cast explicitly for the vmapped compute path.
        gp_v = layout.cast_varying(gp_full)
        if frozen_base is not None:
            # Frozen base (adapters): gather the base's model shards ONCE per
            # round — same FSDP boundary rule as params — and bind it into the
            # per-client fit.  The base is read-only: it appears in no output,
            # carries no optimizer state, and the server update never touches it.
            base_full = layout.gather_full(base, base_specs)
            base_v = layout.cast_varying(base_full)
            round_fit = make_local_fit(frozen_base.bind(base_v), training)
        else:
            round_fit = local_fit
        # The schedule scale is replicated data closed over by the per-client fit (the
        # same scalar for every client in the round).
        fit = (
            (lambda g, d, r: round_fit(g, d, r, lr_scale=lr_scale))
            if fit_takes_lr_scale
            else round_fit
        )
        c_local = rngs.shape[0]
        chunking = client_chunk is not None and client_chunk < c_local
        if chunking and c_local % client_chunk != 0:
            raise ValueError(
                f"client_chunk {client_chunk} must divide per-device client count "
                f"{c_local}"
            )
        if chunking and validation is None and robust is None:
            # (robust aggregation, like validation, needs every client's delta
            # materialized — order statistics cannot fold into a streamed sum.)
            local_wsum, client_metrics, sq_norms = streaming_chunk_reduce(
                fit, gp_v, data, rngs, weights, c_local // client_chunk
            )
            return finish_streamed_round(
                gp, sos, weights, noise_rng, client_metrics, sq_norms, local_wsum
            )
        if chunking:
            n_chunks = c_local // client_chunk
            chunked = jax.tree.map(
                lambda x: x.reshape(n_chunks, client_chunk, *x.shape[1:]), (data, rngs)
            )
            with jax.named_scope("local_fit"):
                with jax.named_scope("chunk_loop"):
                    result = lax.map(
                        lambda args: jax.vmap(fit, in_axes=(None, 0, 0))(gp_v, *args),
                        chunked,
                    )
            result = jax.tree.map(
                lambda x: x.reshape(c_local, *x.shape[2:]), result
            )
        else:
            with jax.named_scope("local_fit"):
                result = jax.vmap(fit, in_axes=(None, 0, 0))(gp_v, data, rngs)
        delta = jax.tree.map(lambda p, g: p - g[None], result.params, gp_v)

        if validation is not None:
            # In-mesh DefaultModelValidator: all checks on the client DELTA, cohort stats
            # across the mesh via psum.  Range check is PER-LEAF (ValidationConfig's
            # documented semantics, matching validate_range); anomaly detection uses the
            # GLOBAL norm (matching validate_statistics).
            stats = stacked_leaf_stats(delta)
            delta = stats.sanitized
            range_ok = jnp.all(jnp.sqrt(stats.leaf_sq) <= validation.max_norm, axis=0)
            participating = (weights > 0).astype(jnp.float32)
            # Cohort anomaly detection: leave-one-out z-score over eligible participants
            # (see loo_zscore for why exclusion and LOO both matter).
            eligible = participating * stats.finite * range_ok
            _, anomalous = loo_zscore(
                stats.global_norm,
                eligible,
                validation.z_score_threshold,
                float(validation.min_clients_for_stats),
                sum_fn=lambda x: layout.client_psum(x.sum()),
            )
            valid = stats.finite & range_ok & ~anomalous
            weights = weights * valid.astype(weights.dtype)
            # Rejected clients' metrics may be NaN; zero their whole metric ROW so the
            # weighted reduce stays finite.  Valid clients' metrics pass through untouched
            # — a finite-delta client with an inf loss keeps its divergence visible.
            result = result._replace(
                metrics=jax.tree.map(
                    lambda m: jnp.where(valid, m, jnp.zeros_like(m)), result.metrics
                )
            )

        total_w = layout.client_psum(weights.sum())
        robust_kept = None
        if robust is not None:
            # Order statistics need the FULL client axis on every device: gather,
            # trim each coordinate's extremes, average the kept ranks.  The result
            # is identical on all devices (same gathered inputs), i.e. replicated.
            gathered = jax.tree.map(layout.client_all_gather, delta)
            part_full = layout.client_all_gather(
                (weights > 0).astype(jnp.float32)
            )
            agg_delta, trim_ok, kept = robust_aggregate(robust, gathered, part_full)
            # Every device computed the identical aggregate from the identical
            # gathered inputs, but shard_map's replication checker cannot infer
            # that — a pmean over equal values IS the value and makes the
            # replication explicit (same cost class as the plain path's psum).
            agg_delta = jax.tree.map(layout.client_pmean, agg_delta)
            trim_ok_f = layout.client_pmean(trim_ok.astype(jnp.float32))
            robust_kept = layout.client_pmean(kept)
            # Fail closed below the 2k+1 floor: zero effective weight leaves params
            # AND server state untouched (same semantics as an empty round).
            total_w = total_w * trim_ok_f.astype(total_w.dtype)
        elif central_privacy is not None:
            delta = clip_deltas(delta)
            uniform = (weights > 0).astype(jnp.float32)
            participants = jnp.maximum(
                layout.client_psum(uniform.sum()), 1.0
            )
            with jax.named_scope("client_reduce"):
                agg_delta = psum_weighted_mean(delta, uniform, c_axes)
            agg_delta = add_central_noise(agg_delta, noise_rng, participants)
        else:
            with jax.named_scope("client_reduce"):
                agg_delta = psum_weighted_mean(delta, weights, c_axes)
        new_gp, new_sos = apply_server_update(gp, sos, agg_delta, total_w)

        with jax.named_scope("round_metrics"):
            metrics = psum_weighted_metrics(result.metrics, weights, c_axes)
        if robust_kept is not None:
            # The attacker's DELTA is trimmed but its metric row would still ride
            # the weighted mean (a NaN loss from one client would corrupt every
            # round's reported numbers) — so the reported loss/accuracy are the
            # TRIMMED means of the per-client scalars, same estimator, same k.
            scalar_gather = layout.client_all_gather
            robust_scalars, _, _ = robust_aggregate(
                robust,
                {"loss": scalar_gather(result.metrics.loss),
                 "accuracy": scalar_gather(result.metrics.accuracy)},
                part_full,
            )
            metrics["loss"] = layout.client_pmean(robust_scalars["loss"])
            metrics["accuracy"] = layout.client_pmean(robust_scalars["accuracy"])
            metrics["robust_kept_clients"] = robust_kept
        if validation is not None:
            # participating = PRE-validation cohort; valid = the subset that survived.
            # The difference is the number of rejected updates this round.
            metrics["participating_clients"] = layout.client_psum(
                participating.sum())
            metrics["valid_clients"] = layout.client_psum(
                (valid & (participating > 0)).sum())
        else:
            metrics["participating_clients"] = layout.client_psum(
                (weights > 0).sum())
        with jax.named_scope("round_metrics"):
            sq_norms = jax.vmap(tree_sq_norm)(delta)
        return new_gp, new_sos, metrics, result.metrics, sq_norms

    # On a 2-D mesh the params/opt-state specs are per-leaf trees carrying the
    # model-axis layout (so those leaves enter and leave as shards), client
    # stacks stay P(clients) (replicated over model), and metrics stay P()
    # (identical on every model column by construction — see
    # multi_axis_shard_map_kwargs for why the checker is off there).
    dspec = layout.data_spec
    if frozen_base is not None:
        # The frozen base enters as an EXTRA shard_map operand in the params
        # layout (model-sharded on multi-axis meshes) and leaves in no output —
        # it is boundary data, not round state.
        def body_with_base(gp, sos, base, data, weights, rngs, noise_rng, lr_scale):
            return shard_body(
                gp, sos, data, weights, rngs, noise_rng, lr_scale, base=base
            )

        inner = shard_map(
            body_with_base,
            mesh=mesh,
            in_specs=(
                params_specs, sos_specs, base_specs, dspec, dspec, dspec, P(), P()
            ),
            out_specs=(params_specs, sos_specs, P(), dspec, dspec),
            **multi_axis_shard_map_kwargs(mesh),
        )
        if not raw_keys_at_boundary:
            return inner

        def sharded_base(gp, sos, base, data, weights, rngs, noise_rng, lr_scale):
            if jnp.issubdtype(jnp.asarray(rngs).dtype, jax.dtypes.prng_key):
                rngs = jax.random.key_data(rngs)
            if jnp.issubdtype(jnp.asarray(noise_rng).dtype, jax.dtypes.prng_key):
                noise_rng = jax.random.key_data(noise_rng)
            return inner(gp, sos, base, data, weights, rngs, noise_rng, lr_scale)

        return sharded_base

    inner = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(params_specs, sos_specs, dspec, dspec, dspec, P(), P()),
        out_specs=(params_specs, sos_specs, P(), dspec, dspec),
        **multi_axis_shard_map_kwargs(mesh),
    )
    if not raw_keys_at_boundary:
        return inner

    def sharded(gp, sos, data, weights, rngs, noise_rng, lr_scale):
        if jnp.issubdtype(jnp.asarray(rngs).dtype, jax.dtypes.prng_key):
            rngs = jax.random.key_data(rngs)
        if jnp.issubdtype(jnp.asarray(noise_rng).dtype, jax.dtypes.prng_key):
            noise_rng = jax.random.key_data(noise_rng)
        return inner(gp, sos, data, weights, rngs, noise_rng, lr_scale)

    return sharded


def build_round_step(
    apply_fn: Callable[..., jax.Array],
    training: TrainingConfig,
    mesh: Mesh,
    strategy: Strategy | None = None,
    grad_fn: GradFn | None = None,
    local_fit: Callable | None = None,
    central_privacy: PrivacyAwareAggregationConfig | None = None,
    validation: ValidationConfig | None = None,
    robust: RobustAggregationConfig | None = None,
    client_chunk: int | None = None,
    params_like: Params | None = None,
    axis_name: str = CLIENT_AXIS,
    donate: bool = False,
    frozen_base: FrozenBase | None = None,
) -> RoundStepFn:
    """Compile the single-round function for a mesh.

    Returns ``round_step(global_params, server_opt_state, data, weights, rngs,
    lr_scale=1.0)``; initialize ``server_opt_state`` with ``init_server_state``.
    All configuration semantics (``central_privacy``, ``validation``, ``robust``,
    ``client_chunk``, ``local_fit``/``grad_fn``, the traced ``lr_scale``) are
    documented on :func:`build_sharded_round`, which builds the SPMD program this
    wraps — the fused R-round engine (``parallel.multi_round``) scans the SAME
    program, so the two paths cannot drift.  On a 2-D ``clients x model`` mesh
    pass ``params_like=`` (abstract is fine) and call the step with params/opt
    state committed in the ``param_sharding`` layout — outputs stay in that
    layout.

    ``donate=True`` donates the params/opt-state buffers to the compiled call (saves one
    params-sized HBM copy per round) — the caller must then treat the inputs as consumed
    and keep only the returned arrays, as ``Coordinator`` does.

    ``frozen_base`` (:class:`FrozenBase` — the adapters subsystem's hook) changes
    the signature to ``round_step(trainable_params, server_opt_state,
    base_params, data, weights, rngs, lr_scale)``: the base crosses as an extra
    NEVER-donated input (the caller re-passes the same device buffers every
    round), appears in no output, and the per-client fit is built from
    ``frozen_base.bind(gathered_base)`` inside the program.
    """
    sharded = build_sharded_round(
        apply_fn, training, mesh, strategy,
        grad_fn=grad_fn, local_fit=local_fit, central_privacy=central_privacy,
        validation=validation, robust=robust, client_chunk=client_chunk,
        params_like=params_like, axis_name=axis_name, frozen_base=frozen_base,
    )

    if frozen_base is not None:
        # Donation still covers only the TRAINABLE state (argnums 0/1): the base
        # is reused verbatim every round, so donating it would free the one
        # buffer the whole federation depends on.
        @partial(jax.jit, donate_argnums=(0, 1) if donate else ())
        def adapter_round_step(
            global_params: Params,
            server_opt_state: Any,
            base_params: Params,
            data: ClientData,
            weights: jax.Array,
            rngs: PRNGKey,
            lr_scale: jax.Array | float = 1.0,
        ) -> RoundStepResult:
            noise_rng = jax.random.fold_in(rngs[0], 0x5EED)
            lr_scale = jnp.asarray(lr_scale, jnp.float32)
            gp, sos, metrics, client_metrics, sq_norms = sharded(
                global_params, server_opt_state, base_params, data, weights,
                rngs, noise_rng, lr_scale,
            )
            return RoundStepResult(gp, sos, metrics, client_metrics, sq_norms)

        adapter_round_step.jit_program = adapter_round_step
        return adapter_round_step

    @partial(jax.jit, donate_argnums=(0, 1) if donate else ())
    def round_step(
        global_params: Params,
        server_opt_state: Any,
        data: ClientData,
        weights: jax.Array,
        rngs: PRNGKey,
        lr_scale: jax.Array | float = 1.0,
    ) -> RoundStepResult:
        # Replicated server-side noise key (central DP), derived so every device draws the
        # identical noise on the replicated aggregate.
        noise_rng = jax.random.fold_in(rngs[0], 0x5EED)
        # Traced (not static): callers pass a DIFFERENT scale every round under an lr
        # schedule, and that must not retrace — normalize to f32 so python floats and
        # jnp scalars share one compiled signature.
        lr_scale = jnp.asarray(lr_scale, jnp.float32)
        gp, sos, metrics, client_metrics, sq_norms = sharded(
            global_params, server_opt_state, data, weights, rngs, noise_rng, lr_scale
        )
        return RoundStepResult(gp, sos, metrics, client_metrics, sq_norms)

    # Lowered-program access for the cost profiler (observability.profiling):
    # the jit callable IS the program — `.jit_program.lower(...)` is the uniform
    # contract all three round-program builders expose (the fused-block builder
    # returns a plain wrapper, so the attribute is load-bearing there).
    round_step.jit_program = round_step
    return round_step


def init_server_state(strategy: Strategy, global_params: Params) -> Any:
    return strategy.server_tx.init(global_params)
