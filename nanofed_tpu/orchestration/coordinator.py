"""The round engine.

Replaces ``nanofed/orchestration/coordinator.py`` wholesale.  Where the reference's
``train_round`` clears an HTTP buffer, polls it at 1 Hz until enough clients POST their
weights, deserializes JSON into tensors and loops over them (``coordinator.py:282-382``),
here a round is one call into the jitted SPMD round step: participation is a sampled mask,
the barrier is SPMD lockstep, and aggregation is a ``psum``.  The host loop that remains
does exactly what the reference's host loop does around the hot path: sample participants,
record per-round metrics JSON, version the global model, checkpoint for fault tolerance,
and yield ``RoundMetrics`` to the caller.

Observable parity notes:
- Partial participation: ``participation_rate`` samples a cohort each round (the C
  fraction of the benchmark configs).  ``dropout_rate`` injects simulated client failures
  (the analog of the reference's straggler timeouts); a round whose surviving cohort
  falls below ``min_completion_rate`` of the sample is marked FAILED and leaves the
  global model untouched — the reference's TimeoutError path (``coordinator.py:295-304``).
- Per-round metrics JSON files ``metrics/metrics_round_N.json`` with per-client metrics
  and aggregation weights (``coordinator.py:247-280``).
- Resume: unlike the reference (whose recovery module is never wired into the loop —
  SURVEY.md §5), ``Coordinator`` restores round counter + params from its state store.
"""

from __future__ import annotations

import contextlib
import json
import math
import time
from dataclasses import dataclass, replace
from pathlib import Path
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from nanofed_tpu.aggregation.base import Strategy, fedavg_strategy
from nanofed_tpu.aggregation.fedavg import compute_weights
from nanofed_tpu.core.exceptions import NanoFedError
from nanofed_tpu.core.types import ClientData, Params
from nanofed_tpu.models.base import Model
from nanofed_tpu.observability.profiling import (
    ProgramCatalog,
    ProgramCostReport,
    update_device_occupancy,
)
from nanofed_tpu.observability.registry import get_registry
from nanofed_tpu.observability.spans import SpanTiming, SpanTracer
from nanofed_tpu.observability.telemetry import RunTelemetry, install_jax_event_bridge
from nanofed_tpu.orchestration.engine import RoundLedger, completion_required
from nanofed_tpu.orchestration.types import RoundMetrics, RoundStatus, TrainingProgress
from nanofed_tpu.parallel.mesh import (
    MODEL_AXIS,
    client_shard_count,
    host_axis_size,
    make_mesh,
    mesh_shape as mesh_axis_sizes,
    model_axis_size,
    pad_client_count,
    pad_clients,
    param_sharding,
    replicated_sharding,
    shard_client_data,
)
from nanofed_tpu.parallel.multi_round import build_round_block, stack_round_keys
from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
from nanofed_tpu.trainer.config import TrainingConfig
from nanofed_tpu.trainer.local import GradFn, make_evaluator, stack_rngs
from nanofed_tpu.trainer.schedules import (
    SCHEDULES,
    lr_schedule_scale,
    lr_schedule_scales,
)
from nanofed_tpu.utils.logger import Logger, log_exec


@dataclass(frozen=True)
class CoordinatorConfig:
    """Parity surface of ``CoordinatorConfig`` (``coordinator.py:26-49``: num_rounds,
    min_clients, min_completion_rate, round timeout, base dir) re-specified for SPMD.

    ``participation_rate`` replaces min_clients (cohort size = ceil(C * rate));
    ``dropout_rate`` replaces wall-clock timeouts as the fault model;
    ``min_completion_rate`` keeps its meaning: below it the round FAILs.
    """

    num_rounds: int = 1
    participation_rate: float = 1.0
    min_completion_rate: float = 0.5
    dropout_rate: float = 0.0
    seed: int = 0
    base_dir: str | Path = "runs"
    save_metrics: bool = True
    eval_every: int = 0  # 0 = never evaluate during training
    # Fused multi-round execution (parallel.multi_round): dispatch this many rounds
    # as ONE device program and sync the host only at block boundaries — the
    # per-round Python dispatch / block_until_ready / metrics-transfer tax is paid
    # once per block.  1 = the classic single-round loop.  Configurations the fused
    # engine doesn't cover (SCAFFOLD, robust aggregation, central DP) fall back to
    # the single-round path automatically.
    rounds_per_block: int = 1
    # Per-client metrics detail (weights / losses / update norms, a [C]-sized
    # device->host transfer + JSON dump) lands in the round metrics file every N
    # rounds; 0 = never.  At 1000 clients the default per-round dump is a
    # 1000-element host conversion nobody may read — sample it down.
    client_metrics_every: int = 1
    # Per-round client-lr schedule (trainer.schedules): the scale streams into the
    # compiled round step as a traced scalar, so a decaying lr costs zero recompiles.
    # Pure function of the round index — resumed runs continue the schedule exactly.
    lr_schedule: str = "constant"  # constant | cosine | linear | step
    lr_min_factor: float = 0.0
    lr_decay_every: int = 10  # step schedule: rounds between decays
    lr_decay_gamma: float = 0.5  # step schedule: multiplier per decay
    # Compiled-program cost profiling (observability.profiling): profile every
    # built round program at construction — XLA cost/memory analysis, roofline
    # verdict, nanofed_program_* gauges, and telemetry `program_profile` records.
    # Opt-in because profiling pays a second XLA compile unless the persistent
    # compilation cache is warm; `Coordinator.profile_programs()` runs the same
    # pass on demand either way.
    profile_programs: bool = False
    # Closed-loop online retuning (tuning.retuner): every N completed rounds,
    # re-rank the autotune candidate table by the walltimes the run actually
    # realized and — at the next block boundary, never mid-block — hot-swap the
    # live round program when the measurements disagree with the AOT cost model
    # by more than the retuner's hysteresis.  0 = off.  Only engages on
    # coordinators built via ``from_autotune`` (the sweep result IS the
    # candidate table); measured numbers are written back into the autotune
    # cache entry at run end so the NEXT run starts from reality.
    retune_every: int = 0

    def __post_init__(self) -> None:
        if self.num_rounds < 1:
            raise ValueError("num_rounds must be >= 1")
        if not 0.0 < self.participation_rate <= 1.0:
            raise ValueError("participation_rate must be in (0, 1]")
        if not 0.0 <= self.min_completion_rate <= 1.0:
            raise ValueError("min_completion_rate must be in [0, 1]")
        if not 0.0 <= self.dropout_rate < 1.0:
            raise ValueError("dropout_rate must be in [0, 1)")
        if self.lr_schedule not in SCHEDULES:
            raise ValueError(
                f"unknown lr_schedule {self.lr_schedule!r}; choose from {SCHEDULES}"
            )
        if not 0.0 <= self.lr_min_factor <= 1.0:
            raise ValueError("lr_min_factor must be in [0, 1]")
        if self.lr_decay_every < 1:
            raise ValueError("lr_decay_every must be >= 1")
        if self.rounds_per_block < 1:
            raise ValueError("rounds_per_block must be >= 1")
        if self.client_metrics_every < 0:
            raise ValueError("client_metrics_every must be >= 0 (0 = never)")
        if self.retune_every < 0:
            raise ValueError("retune_every must be >= 0 (0 = off)")
        if not 0.0 < self.lr_decay_gamma <= 1.0:
            # gamma=0 would zero every update from the first decay on (full-cost
            # silent no-op rounds); gamma>1 silently GROWS the lr each decay.
            raise ValueError("lr_decay_gamma must be in (0, 1]")


class Coordinator:
    """Drives federated training over a device mesh."""

    @classmethod
    def from_autotune(
        cls,
        model: Model,
        train_data: ClientData,
        config: CoordinatorConfig,
        training: TrainingConfig | None = None,
        *,
        tuning_space=None,
        hbm_budget_bytes: int | None = None,
        autotune_cache_dir: str | Path | None = ".jax_cache",
        autotune_force: bool = False,
        **kwargs: Any,
    ) -> "Coordinator":
        """Build a coordinator with the configuration the COMPILER's cost model
        picks (``nanofed_tpu.tuning``): the sweep lowers every candidate's round
        program AOT — zero round executions — scores it by achievable roofline
        walltime (TPU) or bytes-accessed ordering (CPU, basis stated), rejects
        candidates over the device HBM budget, and the winner's ``client_chunk``
        / ``rounds_per_block`` / ``mesh_shape`` / batch size replace the
        defaults.  The ranked candidate table lands under ``config.base_dir`` as
        ``autotune_*.json``; sweep results are cached (keyed by model
        fingerprint, population, device kind/count), so repeat constructions
        compile nothing.

        The built coordinator carries ``tuned_config`` (the winner + provenance)
        and ``autotune_result`` (the full :class:`~nanofed_tpu.tuning.
        AutotuneResult`); an ``autotune`` record is appended to the run's
        telemetry when telemetry is on.  Explicit ``client_chunk`` /
        ``mesh_shape`` / ``mesh`` kwargs are refused — the tuner owns those
        knobs here; pin an axis by passing a single-valued ``tuning_space``.
        """
        import dataclasses

        from nanofed_tpu.parallel.mesh import mesh_shape_for_topology
        from nanofed_tpu.trainer.config import TrainingConfig as _TC
        from nanofed_tpu.tuning import PopulationSpec, autotune

        clashing = [
            k for k in ("client_chunk", "mesh_shape", "mesh") if k in kwargs
        ]
        if clashing:
            raise NanoFedError(
                f"from_autotune owns {', '.join(clashing)} — the tuner picks "
                "them; pin an axis with a single-valued tuning_space instead"
            )
        training = training or _TC()
        adapter_spec = kwargs.pop("adapter", None)
        result = autotune(
            model, PopulationSpec.from_client_data(train_data), training,
            participation=config.participation_rate,
            num_rounds=config.num_rounds,
            eval_every=config.eval_every,
            space=tuning_space,
            hbm_budget_bytes=hbm_budget_bytes,
            cache_dir=autotune_cache_dir,
            out_dir=config.base_dir,
            force=autotune_force,
            adapter=adapter_spec,
        )
        winner = result.winner
        import jax as _jax

        if adapter_spec is not None and winner.adapter_rank is not None:
            # The tuner owns the rank axis exactly like chunk/block/mesh: the
            # built coordinator federates at the WINNING rank.
            adapter_spec = dataclasses.replace(
                adapter_spec, rank=winner.adapter_rank
            )
        coord = cls(
            model,
            train_data,
            dataclasses.replace(
                config, rounds_per_block=winner.rounds_per_block
            ),
            training=dataclasses.replace(
                training, batch_size=winner.batch_size
            ),
            client_chunk=winner.client_chunk,
            mesh_shape=mesh_shape_for_topology(
                getattr(winner, "hosts", 1), winner.model_shards,
                len(_jax.devices()),
            ),
            adapter=adapter_spec,
            **kwargs,
        )
        coord.autotune_result = result
        coord.tuned_config = {
            **winner.to_dict(),
            "used": "tuned",
            "scoring_basis": result.scoring_basis,
            "cache_hit": result.cache_hit,
            **({"artifact": result.artifact_path}
               if result.artifact_path else {}),
        }
        if config.retune_every > 0:
            # The sweep result IS the candidate table the online retuner
            # re-ranks; measured numbers land back in the same cache entry.
            coord.enable_retuning(result, cache_dir=autotune_cache_dir)
        if coord.telemetry is not None:
            coord.telemetry.record("autotune", **result.telemetry_payload())
        return coord

    def __init__(
        self,
        model: Model,
        train_data: ClientData,
        config: CoordinatorConfig,
        training: TrainingConfig | None = None,
        strategy: Strategy | None = None,
        mesh=None,
        mesh_shape: tuple[int, int] | None = None,
        eval_data: ClientData | None = None,
        model_manager=None,
        state_store=None,
        grad_fn: GradFn | None = None,
        validation=None,
        central_privacy=None,
        accountant=None,
        local_fit: Callable | None = None,
        client_chunk: int | None = None,
        robust=None,
        scaffold: bool = False,
        on_round_end: Callable[[RoundMetrics], None] | None = None,
        telemetry_dir: str | Path | None = None,
        strict: bool = False,
        chaos=None,
        adapter=None,
    ) -> None:
        self.model = model
        self.config = config
        self.training = training or TrainingConfig()
        self.strategy = strategy or fedavg_strategy()
        # Fault injection (nanofed_tpu.faults.ChaosSchedule): planned per-client
        # crashes are applied to every sampled cohort — the in-process analogue
        # of a network client going silent — exercising the same completion-rate
        # gating real dropouts hit.  Deterministic under the plan's seed, unlike
        # config.dropout_rate's per-round coin flips.
        self._chaos = chaos
        # mesh_shape=(n_client_shards, n_model_shards) builds the 2-D clients x
        # model mesh (FSDP-style parameter sharding — see parallel.mesh);
        # mesh_shape=(n_hosts, n_client_shards, n_model_shards) the 3-D
        # hosts x clients x model mesh with hierarchical (host-local then
        # cross-host) aggregation.  An explicit mesh= wins and must not be
        # combined with it.
        if mesh is not None and mesh_shape is not None:
            raise ValueError(
                "pass either mesh= (a prebuilt Mesh) or mesh_shape= "
                "((n_client_shards, n_model_shards) or (n_hosts, "
                "n_client_shards, n_model_shards)), not both"
            )
        if mesh is not None:
            self.mesh = mesh
        else:
            self.mesh = make_mesh(shape=mesh_shape)
        self.model_manager = model_manager
        self.state_store = state_store
        self.on_round_end = on_round_end
        self._log = Logger()
        # Strict mode (analysis.contracts): round programs are contract-checked at
        # construction via jax.eval_shape, and every device dispatch runs under
        # jax.transfer_guard("disallow") — an implicit host<->device transfer in
        # the hot path raises instead of silently serializing it.
        self.strict = bool(strict)

        # Central DP is applied inside the round step; the coordinator owns the matching
        # accountant so the configured (ε, δ) budget is actually tracked and reported
        # (the noise itself would otherwise be spent but never accounted anywhere).
        # RDP by default — the tight composition; pass ``accountant=`` to override
        # (e.g. GaussianAccountant for the loose-but-simple linear bound).
        self.central_privacy = central_privacy
        if accountant is not None and central_privacy is None:
            raise ValueError(
                "accountant= given without central_privacy=: the coordinator only "
                "records spend for its own central-DP reduce (for DP-SGD clients, "
                "account via the trainer — see trainer.private)"
            )
        self.privacy_accountant = accountant
        if central_privacy is not None and accountant is None:
            from nanofed_tpu.privacy.accounting import RDPAccountant

            self.privacy_accountant = RDPAccountant()
        # OS-entropy generator for DP cohort sampling (_sample_cohort) and the DP
        # round's device-RNG entropy fold (_train_round): seeded from the system RNG at
        # construction, never from config.seed.
        self._secret_sampling_rng = np.random.default_rng()

        self.num_clients = int(train_data.x.shape[0])
        # Clients pad to the number of CLIENT shards (== device count on a 1-D
        # mesh; the first mesh dim on a 2-D clients x model mesh — the model
        # axis holds parameter shards, not clients; hosts x clients jointly on
        # a 3-axis mesh, where data rows shard hosts-major so each host row
        # holds a contiguous client range).
        n_dev = client_shard_count(self.mesh)
        self._n_hosts = host_axis_size(self.mesh)
        padded = pad_client_count(self.num_clients, n_dev)
        padded_data = pad_clients(train_data, padded)
        # Sample counts come from the HOST copy before sharding: pulling the
        # sharded mask back would be a pointless device->host round trip — and
        # is impossible on a multi-process mesh (no process holds every row).
        self._num_samples = jnp.asarray(
            np.asarray(padded_data.mask).sum(axis=1), dtype=jnp.float32
        )
        self._data = shard_client_data(padded_data, self.mesh)
        self._padded_clients = padded
        self._rows_per_host = padded // self._n_hosts

        # Model-state placement: params and server opt state ride the mesh in
        # the param_sharding layout — replicated on a 1-D mesh, FSDP
        # model-sharded on a 2-D one.  The round programs preserve the layout
        # end to end (and round outputs are mesh-placed either way), so this is
        # the only placement these trees ever get and no round triggers a
        # sharding-signature recompile.  Built BEFORE the round programs: on a
        # 2-D mesh the per-leaf layout becomes the programs' shard_map specs.
        self._model_shards = model_axis_size(self.mesh)
        params_host = model.init(jax.random.key(config.seed))
        # Parameter-efficient federation (nanofed_tpu.adapters): with an
        # AdapterSpec, the FEDERATED state is the small LoRA adapter tree —
        # ``self.params``/``self.server_state`` are adapter-shaped, so every
        # downstream mechanism (aggregation, codec, checkpointing, autotuning)
        # operates on the adapter tree without modification — while the frozen
        # base stays device-resident in the same ``param_sharding`` layout
        # (model-sharded on a 2-D/3-D mesh) and rides the round program as a
        # read-only input (``parallel.round_step.FrozenBase``).
        self.adapter = adapter
        self._merge_count = 0
        if adapter is not None:
            if scaffold:
                raise ValueError(
                    "adapter= cannot be combined with scaffold=True: the "
                    "control-variate machinery assumes the federated tree IS "
                    "the model; adapter SCAFFOLD would need control state on "
                    "the adapter tree, which is not built yet"
                )
            if local_fit is not None or grad_fn is not None:
                raise ValueError(
                    "adapter= builds the local fit from the frozen base inside "
                    "the round program; a custom local_fit/grad_fn cannot see "
                    "the base and is refused (see parallel.round_step.FrozenBase)"
                )
            from nanofed_tpu.adapters import init_adapters

            self.base_params: Params | None = jax.device_put(
                params_host, param_sharding(self.mesh, params_host)
            )
            # Adapter init is seeded off config.seed (host draw, like model
            # init); B=0 makes the round-0 merged model exactly the base.
            trainable_host = init_adapters(adapter, params_host, rng=config.seed)
            self._adapter_base_host = params_host
        else:
            self.base_params = None
            trainable_host = params_host
        self.params: Params = jax.device_put(
            trainable_host, param_sharding(self.mesh, trainable_host)
        )
        sos_host = init_server_state(self.strategy, trainable_host)
        self.server_state = jax.device_put(
            sos_host, param_sharding(self.mesh, sos_host)
        )

        # Cohort gathering (participation < 1): running the round step over ALL N
        # clients and zero-weighting non-participants burns (1-q) of every round's
        # FLOPs: at q=0.1 the step trains ten times the clients the round uses.
        # Instead, gather the sampled cohort's rows into a [K_pad, ...] batch (one
        # jitted device-side take, sharded like the source) and run the step over K
        # clients.  The math is identical: FedAvg weights, DP uniform weights,
        # validation stats, and accounting all operate on the same participating
        # set; dropped and padding slots carry weight 0 exactly as before.  Full
        # participation keeps the direct path untouched.
        if robust is not None:
            from nanofed_tpu.aggregation.robust import robust_floor

            if self.cohort_size < robust_floor(robust):
                # Every round would fail closed (zero aggregate) yet still be
                # reported COMPLETED — a run that silently trains nothing. The
                # cohort size is static, so refuse the configuration up front.
                raise ValueError(
                    f"robust method {robust.method!r} needs a cohort of at least "
                    f"{robust_floor(robust)} clients, but participation_rate="
                    f"{config.participation_rate} over {self.num_clients} clients "
                    f"samples only {self.cohort_size} per round"
                )
        self._cohort_mode = self.cohort_size < self.num_clients
        if self._cohort_mode and client_chunk is not None:
            # A chunk size that divided the full padded count may not divide the
            # smaller cohort count — keep the legacy full-N path rather than turn a
            # previously valid config into a trace-time crash.
            per_dev = pad_client_count(self.cohort_size, n_dev) // n_dev
            if client_chunk < per_dev and per_dev % client_chunk != 0:
                self._cohort_mode = False
        self._step_clients = (
            pad_client_count(self.cohort_size, n_dev) if self._cohort_mode else padded
        )
        # Host-local cohorts (3-axis mesh): each host's slot segment of the
        # gathered cohort only ever references that host's resident client
        # rows, so the in-round cohort gather moves zero inter-host data —
        # sampling is stratified per host (proportional quotas), placement
        # fills per-host slot segments (see _sample_cohort/_place_cohort).
        self._slots_per_host = self._step_clients // self._n_hosts
        if self._cohort_mode and self._n_hosts > 1:
            # Every quantity below is static, so an infeasible cohort is
            # refused HERE — before any program compiles — not at round 1's
            # first draw (same up-front rule as the robust-floor check).
            caps = [
                min(max(0, stop - start), self._slots_per_host)
                for start, stop in self._host_populations()
            ]
            if sum(caps) < self.cohort_size:
                raise NanoFedError(
                    f"cohort_size {self.cohort_size} exceeds the hosts-axis "
                    f"capacity (per-host caps {caps} = min(resident clients, "
                    f"slot segment {self._slots_per_host})) — shrink the "
                    "cohort or raise participation"
                )
        if self._cohort_mode:
            from nanofed_tpu.parallel.mesh import client_sharding

            sharded = client_sharding(self.mesh)
            self._gather_cohort = jax.jit(
                lambda data, idx: jax.tree.map(lambda x: x[idx], data),
                out_shardings=jax.tree.map(lambda _: sharded, self._data),
            )

        if (
            config.lr_schedule != "constant"
            and local_fit is not None
            and not getattr(local_fit, "supports_lr_scale", False)
        ):
            # The scale would be silently ignored — the operator would believe lr is
            # decaying while every round trains at full rate.
            raise ValueError(
                f"lr_schedule={config.lr_schedule!r} requires a local_fit that "
                "accepts lr_scale (make_local_fit/make_private_local_fit do; mark a "
                "custom one with `fit.supports_lr_scale = True` once it honors the "
                "argument)"
            )
        # SCAFFOLD (Karimireddy et al. 2020): control-variate round state — the server
        # control rides replicated; every client's control is a row of a stacked pytree
        # sharded exactly like the training data.  Cohort gathering gathers control
        # rows alongside data rows and scatter-ADDS the returned deltas back
        # (collision-safe: padding slots alias row 0 with an exact-zero delta).
        self.scaffold = scaffold
        if scaffold:
            incompatible = {
                "central_privacy": central_privacy, "validation": validation,
                "robust": robust, "local_fit": local_fit,
            }
            bad = [k for k, v in incompatible.items() if v is not None]
            if bad:
                # The control estimate is computed from the UN-noised, UN-trimmed local
                # trajectory; composing it with DP noise / robust trimming / arbitrary
                # fits would silently bias every later round's correction.
                raise ValueError(
                    f"scaffold=True cannot be combined with {', '.join(bad)}: the "
                    "control-variate update assumes the plain corrected-SGD local fit "
                    "and the uniform participant mean"
                )
            from nanofed_tpu.parallel.scaffold_step import build_scaffold_round_step

            self._frozen_base = None
            self._round_step = build_scaffold_round_step(
                model.apply, self.training, self.mesh, self.num_clients,
                strategy=self.strategy, grad_fn=grad_fn, client_chunk=client_chunk,
                params_like=self.params, donate=True,
            )
        else:
            self._frozen_base = None
            if adapter is not None:
                from nanofed_tpu.adapters import make_adapter_apply, merge_adapters
                from nanofed_tpu.parallel.round_step import FrozenBase

                self._frozen_base = FrozenBase(
                    base_like=params_host,
                    bind=lambda base_full: make_adapter_apply(
                        model.apply, adapter, base_full
                    ),
                )
                # Merge for eval / versioned models: one jit, reused; the
                # output placement follows the base leaves, so on a 2-D mesh a
                # merged copy only materializes where a consumer asks for it.
                # fedlint: disable=FED004 (merge must NOT donate: base_params and the live adapter tree are reused for the next round's dispatch)
                self._merge_jit = jax.jit(
                    lambda base, ad: merge_adapters(base, ad, adapter)
                )
            self._round_step = build_round_step(
                model.apply, self.training, self.mesh, self.strategy, grad_fn=grad_fn,
                local_fit=local_fit, central_privacy=central_privacy,
                validation=validation, robust=robust, client_chunk=client_chunk,
                params_like=self.params, donate=True,
                frozen_base=self._frozen_base,
            )
        # Fused multi-round execution: R rounds as one scanned device program,
        # host sync only at block boundaries.  Falls back to the single-round path
        # (built above — it also finishes ragged tail blocks) for configurations
        # the fused engine doesn't cover yet.
        self._round_block = None
        self._fused_fallback_reason: str | None = None
        if config.rounds_per_block > 1:
            unsupported = [
                name for name, active in (
                    ("SCAFFOLD", scaffold),
                    ("robust aggregation", robust is not None),
                    ("central DP", central_privacy is not None),
                    # Blocks are cut at eval boundaries, so an eval cadence
                    # shorter than the block length would leave _block_len
                    # unable to ever emit a full block — the knob would be a
                    # silent no-op; say so instead of building a dead program.
                    ("eval_every < rounds_per_block",
                     0 < config.eval_every < config.rounds_per_block),
                ) if active
            ]
            if unsupported:
                self._fused_fallback_reason = " + ".join(unsupported)
                self._log.info(
                    "rounds_per_block=%d requested but %s is not fused yet; "
                    "using the single-round path",
                    config.rounds_per_block, self._fused_fallback_reason,
                )
            else:
                self._round_block = build_round_block(
                    model.apply, self.training, self.mesh, self.strategy,
                    num_clients=self.num_clients,
                    padded_clients=self._padded_clients,
                    step_clients=self._step_clients,
                    cohort_size=self.cohort_size,
                    dropout_rate=config.dropout_rate,
                    min_completion_rate=config.min_completion_rate,
                    grad_fn=grad_fn, local_fit=local_fit, validation=validation,
                    client_chunk=client_chunk, params_like=self.params,
                    collect_client_detail=(
                        config.save_metrics and config.client_metrics_every > 0
                    ),
                    # Explicit, never derived: _cohort_mode can be False with a
                    # sub-population cohort (client_chunk that doesn't divide the
                    # cohort padding), and True with step == padded (a 97%-cohort
                    # pads to the population width) — the block must lay out the
                    # mask exactly as _train_block builds it.
                    cohort_mode=self._cohort_mode,
                    donate=True,
                    frozen_base=self._frozen_base,
                )
        # Everything a retune swap needs to REBUILD the round programs with a
        # different (client_chunk, rounds_per_block): the swap path re-invokes
        # the builders above with these frozen inputs (see _rebuild_round_programs)
        # — only the two hot-swappable knobs vary.
        self._client_chunk = client_chunk
        self._builder_ctx: dict[str, Any] = dict(
            grad_fn=grad_fn, local_fit=local_fit,
            central_privacy=central_privacy, validation=validation,
            robust=robust,
        )
        # Compiled-program cost catalog (observability.profiling): every program
        # this coordinator built, registered with LAZY dispatch-shaped argument
        # factories — registration is free (no trace, no compile, nothing
        # materializes); `profile_programs()` compiles + extracts on demand.
        self.program_catalog = ProgramCatalog()
        self._register_programs()
        self._evaluator = (
            make_evaluator(model.apply, batch_size=256) if eval_data is not None else None
        )
        # On a 2-D mesh the eval batch rides the mesh replicated so the eval jit
        # sees (model-sharded params, mesh-placed data) — XLA gathers the param
        # shards inside the compiled eval; the 1-D placement is untouched.
        if eval_data is None:
            self._eval_data = None
        elif self._model_shards > 1:
            self._eval_data = jax.device_put(eval_data, replicated_sharding(self.mesh))
        else:
            self._eval_data = jax.tree.map(jnp.asarray, eval_data)

        if scaffold:
            from nanofed_tpu.parallel.mesh import client_sharding
            from nanofed_tpu.trainer.scaffold import stack_zero_controls, zero_controls

            csh = client_sharding(self.mesh)
            # The server control is params-shaped round state: same layout rule
            # as params (model-sharded on a 2-D mesh); the per-client stack
            # stays client-sharded like data.
            self.c_global: Params = jax.device_put(
                zero_controls(params_host), param_sharding(self.mesh, params_host)
            )
            self.c_stack: Params = jax.device_put(
                stack_zero_controls(params_host, self._padded_clients), csh
            )
            stack_shardings = jax.tree.map(lambda _: csh, self.c_stack)
            # Full-participation write-back: rows align with the stack, so the update
            # is a fused elementwise add (a scatter here would invite GSPMD to lower
            # cross-device index traffic for what is really identity addressing).
            # Built in BOTH modes: tests force `_cohort_mode = False` to pin the
            # gathered path against the full-N path.
            self._add_controls = jax.jit(
                lambda stack, delta: jax.tree.map(
                    lambda s, d: s + d.astype(s.dtype), stack, delta
                ),
                donate_argnums=(0,),
                out_shardings=stack_shardings,
            )
            if self._cohort_mode:
                # delta rows arrive with the STEP's client count (cohort-padded), the
                # stack with the population's — scatter-add bridges the two.  Donating
                # the stack keeps the population controls single-buffered in HBM.
                self._scatter_add_controls = jax.jit(
                    lambda stack, idx, delta: jax.tree.map(
                        lambda s, d: s.at[idx].add(d.astype(s.dtype)), stack, delta
                    ),
                    donate_argnums=(0,),
                    out_shardings=stack_shardings,
                )
                # fedlint: disable=FED004 (gather must NOT donate: c_stack is re-consumed by the scatter-add write-back after the round step)
                self._gather_controls = jax.jit(
                    lambda stack, idx: jax.tree.map(lambda x: x[idx], stack),
                    out_shardings=stack_shardings,
                )
        self.current_round = 0
        self.history: list[RoundMetrics] = []
        # Populated by from_autotune: the winner config + provenance, and the
        # full sweep result.  None on hand-configured coordinators.
        self.tuned_config: dict[str, Any] | None = None
        self.autotune_result = None
        # Online retuning (tuning.retuner): attached by enable_retuning /
        # from_autotune(retune_every > 0).  _retune_candidate is the live
        # program's position in the candidate table; _last_retune_round the
        # boundary the cadence counts from.
        self.retuner = None
        self._retune_candidate = None
        self._last_retune_round = 0

        if self.strict:
            if self.scaffold:
                self._log.info(
                    "strict=True: contract check skipped for the SCAFFOLD round "
                    "program (different signature); transfer guard still applies"
                )
            else:
                self._check_contracts()
            # Program audit (analysis.program_audit): trace-only here —
            # collective schedules, mesh discipline, dtype drift, host
            # transfers — signature-agnostic, so SCAFFOLD is covered too.
            # The AOT donation check runs in audit_programs() (compile-time
            # cost belongs to an explicit call, not construction).
            self._audit_strict()

        self.base_dir = Path(config.base_dir)
        if config.save_metrics:
            (self.base_dir / "metrics").mkdir(parents=True, exist_ok=True)

        # Observability: round/phase metrics always flow into the process registry;
        # with save_metrics (or an explicit telemetry_dir) the run additionally gets
        # a telemetry.jsonl artifact of every phase span and round record.  The JAX
        # event bridge surfaces compile-cache hits/misses alongside them.
        install_jax_event_bridge()
        tel_dir = (
            Path(telemetry_dir)
            if telemetry_dir is not None
            else (self.base_dir if config.save_metrics else None)
        )
        self.telemetry = RunTelemetry(tel_dir) if tel_dir is not None else None
        if self.telemetry is not None:
            # The run's topology block (ROADMAP item-1 evidence bar): every
            # telemetry stream states its host/process geometry — single-host
            # runs say 1, they don't omit it — and metrics-summary surfaces it.
            self.telemetry.record(
                "topology",
                process_count=jax.process_count(),
                hosts=self._n_hosts,
                mesh_shape=list(mesh_axis_sizes(self.mesh)),
                devices=len(jax.devices()),
                num_clients=self.num_clients,
            )
            if self.adapter is not None:
                # The adapter record (digested by metrics-summary): rank,
                # trainable-vs-frozen sizes, and the ANALYTIC payload ratio —
                # the measured wire-bytes comparison is appended by whatever
                # harness actually moves bytes (adapters.evidence, loadgen).
                from nanofed_tpu.adapters import adapter_param_count

                self.telemetry.record(
                    "adapter",
                    **self.adapter.to_dict(),
                    **adapter_param_count(self.adapter, self._adapter_base_host),
                )
        self._tracer = (
            self.telemetry.tracer
            if self.telemetry is not None
            # keep_records=False: only the histogram consumes these spans — a
            # long-lived engine must not accumulate every round's records.
            else SpanTracer(keep_records=False)
        )
        _registry = (
            self.telemetry.registry if self.telemetry is not None else get_registry()
        )
        self._registry = _registry
        # Program-cost gauges publish into the same registry every other
        # instrument uses, so one /metrics scrape carries them too.
        self.program_catalog.registry = _registry
        # Round-outcome accounting is the shared engine's, not this front's:
        # the wire coordinator and the federate mesh workers charge the same
        # ledger, so "one stack" is one set of round instruments.
        self._ledger = RoundLedger(
            _registry, telemetry=self.telemetry, track_dropouts=True
        )

        # Resume (improvement over the reference, where recovery isn't integrated).
        if self.state_store is not None:
            restored = self.state_store.restore_latest()
            if restored is not None:
                self.current_round = restored.round_number + 1
                # Same placement as the fresh-init path (param_sharding:
                # replicated on 1-D, model-sharded on 2-D): restored arrays come
                # from the host and would otherwise change the round-step input
                # sharding.  Checkpoints hold gathered host arrays, so a run may
                # resume on a DIFFERENT mesh shape than it trained on.
                self.params = jax.device_put(
                    restored.params, param_sharding(self.mesh, restored.params)
                )
                restored_ss = restored.server_state
                has_controls = (
                    isinstance(restored_ss, dict) and "scaffold_c_stack" in restored_ss
                )
                if not self.scaffold and has_controls:
                    # The symmetric mistake must fail just as loudly: feeding the
                    # wrapper dict to optax as "optimizer state" would surface as an
                    # opaque pytree-structure error deep inside the jitted round step.
                    raise NanoFedError(
                        "the checkpoint carries SCAFFOLD control state but this "
                        "coordinator was built with scaffold=False — resume with "
                        "scaffold=True (or point at a non-SCAFFOLD run's store)"
                    )
                if self.scaffold:
                    if not has_controls:
                        raise NanoFedError(
                            "scaffold=True but the checkpoint carries no control "
                            "state — it was written by a non-SCAFFOLD run; resuming "
                            "would silently zero every client's correction"
                        )
                    from nanofed_tpu.parallel.mesh import client_sharding

                    restored_rows = jax.tree.leaves(
                        restored_ss["scaffold_c_stack"]
                    )[0].shape[0]
                    if restored_rows != self._padded_clients:
                        # Unlike params/server state (replicated, device-count-free),
                        # the control stack's padding is mesh-derived — resuming on a
                        # different device count must refuse clearly, not crash with
                        # a broadcast error inside the first round's jit.
                        raise NanoFedError(
                            f"checkpointed control stack has {restored_rows} rows "
                            f"but this mesh pads {self.num_clients} clients to "
                            f"{self._padded_clients} — resume a SCAFFOLD run on the "
                            "same device count it was checkpointed with"
                        )
                    csh = client_sharding(self.mesh)
                    self.c_global = jax.device_put(
                        restored_ss["scaffold_c_global"],
                        param_sharding(self.mesh, restored_ss["scaffold_c_global"]),
                    )
                    self.c_stack = jax.device_put(
                        restored_ss["scaffold_c_stack"], csh
                    )
                    restored_ss = restored_ss["opt"]
                self.server_state = jax.device_put(
                    restored_ss, param_sharding(self.mesh, restored_ss)
                )
                acct_state = restored.metadata.metrics.get("privacy_accountant")
                if self.privacy_accountant is not None and acct_state is not None:
                    self.privacy_accountant.load_state_dict(acct_state)
                self._log.info(
                    "resumed from round %d checkpoint", restored.round_number
                )

        if config.profile_programs:
            self.profile_programs()

    # ------------------------------------------------------------------
    # Compiled-program cost profiling (observability.profiling)
    # ------------------------------------------------------------------

    def _register_programs(self) -> None:
        """Populate the catalog with every round program this coordinator built.

        The argument factories reproduce the DISPATCH-time shapes and shardings
        exactly — cohort-gathered data rides the client sharding, params/opt
        state their ``param_sharding`` layout — so the lowered program the
        profiler costs is the program the rounds actually run, not a
        replicated-input cousin with different collectives.  Values are
        irrelevant (lowering never executes), so data placeholders are zeros.
        """
        attrs = {
            # Per-axis mesh sizes in axis order: [clients, model] on 1-D/2-D
            # meshes (a 1-D mesh records its implicit model dim of 1), and
            # [hosts, clients, model] once the hosts axis engages.
            "mesh_shape": (
                list(mesh_axis_sizes(self.mesh))
                if len(self.mesh.axis_names) > 1
                else [client_shard_count(self.mesh), self._model_shards]
            ),
            "step_clients": self._step_clients,
        }

        def _data_like():
            if not self._cohort_mode:
                return self._data
            from nanofed_tpu.parallel.mesh import client_sharding

            n = self._step_clients
            return jax.device_put(
                jax.tree.map(
                    lambda x: jnp.zeros((n, *x.shape[1:]), x.dtype), self._data
                ),
                client_sharding(self.mesh),
            )

        def _step_common():
            n = self._step_clients
            weights = jnp.zeros(n, jnp.float32)
            rngs = stack_rngs(jax.random.key(self.config.seed), n)
            return _data_like(), weights, rngs, jnp.float32(1.0)

        if self.scaffold:
            def _scaffold_args():
                data, weights, rngs, lr = _step_common()
                if self._cohort_mode:
                    from nanofed_tpu.parallel.mesh import client_sharding

                    n = self._step_clients
                    c_rows = jax.device_put(
                        jax.tree.map(
                            lambda x: jnp.zeros((n, *x.shape[1:]), x.dtype),
                            self.c_stack,
                        ),
                        client_sharding(self.mesh),
                    )
                else:
                    c_rows = self.c_stack
                return (
                    self.params, self.server_state, self.c_global, c_rows,
                    data, weights, rngs, lr,
                ), {}

            self.program_catalog.register(
                "scaffold_round_step", self._round_step,
                args_factory=_scaffold_args, attrs=attrs,
            )
        elif self.adapter is not None:
            # The adapter program is costed under its own name so autotune /
            # profile tables carry the adapter row next to the dense one; the
            # frozen base enters the lowered signature exactly as dispatched.
            attrs = {**attrs, "adapter_rank": self.adapter.rank}

            def _adapter_step_args():
                data, weights, rngs, lr = _step_common()
                return (
                    self.params, self.server_state, self.base_params,
                    data, weights, rngs, lr,
                ), {}

            self.program_catalog.register(
                "adapter_round_step", self._round_step,
                args_factory=_adapter_step_args, attrs=attrs,
            )
        else:
            def _step_args():
                data, weights, rngs, lr = _step_common()
                return (
                    self.params, self.server_state, data, weights, rngs, lr,
                ), {}

            self.program_catalog.register(
                "round_step", self._round_step, args_factory=_step_args,
                attrs=attrs,
            )

        if self._round_block is not None:
            def _block_args():
                rpb = self.config.rounds_per_block
                n = self._step_clients
                keys = stack_round_keys(self.config.seed, list(range(rpb)))
                lr = jnp.ones(rpb, jnp.float32)
                idx = (
                    jnp.zeros((rpb, n), jnp.int32) if self._cohort_mode else None
                )
                mask = jnp.zeros((rpb, n), jnp.float32)
                # The inner jit takes the frozen base as its LAST positional
                # (None on dense programs — an empty pytree to the lowering).
                return (
                    self.params, self.server_state, self._data,
                    self._num_samples, keys, lr, idx, mask, self.base_params,
                ), {}

            self.program_catalog.register(
                "adapter_round_block" if self.adapter is not None
                else "round_block",
                self._round_block, args_factory=_block_args,
                rounds=self.config.rounds_per_block,
                attrs={**attrs, "rounds_per_block": self.config.rounds_per_block},
            )

    def profile_programs(self, force: bool = False) -> list[ProgramCostReport]:
        """Compile + cost-analyze every catalogued round program.

        Publishes ``nanofed_program_*`` gauges and the time-to-ready histogram
        (via the catalog), appends a ``program_profile`` record per program to
        ``telemetry.jsonl`` when telemetry is on, and returns the reports.
        Reports are cached — a second call is free unless ``force``.
        """
        reports: list[ProgramCostReport] = []
        for name in self.program_catalog.names():
            cached = self.program_catalog.report(name) is not None and not force
            with self._tracer.span("program-profile", program=name):
                report = self.program_catalog.profile(name, force=force)
            if not cached:
                if self.telemetry is not None:
                    self.telemetry.record("program_profile", **report.to_dict())
                bound = report.lower_bound_s
                self._log.info(
                    "program %s: %.3g FLOPs/round, %.3g bytes accessed, peak "
                    "%.3g device bytes, intensity %.2f -> %s%s (compiled in "
                    "%.2fs)",
                    name, report.flops / report.rounds, report.bytes_accessed,
                    report.peak_bytes, report.arithmetic_intensity,
                    report.verdict,
                    (f", >= {bound / report.rounds:.3g}s/round achievable"
                     if bound is not None else ""),
                    report.compile_seconds,
                )
            reports.append(report)
        return reports

    def _audit_strict(self) -> None:
        """Construction-time program audit: trace-only (no AOT compile), and
        findings RAISE — strict mode means a divergent collective schedule or
        an upcast leaf never reaches a dispatch."""
        from nanofed_tpu.analysis.contracts import ContractViolation

        findings = [
            f for report in self.program_catalog.audit_all(compile=False)
            for f in report.findings
        ]
        if findings:
            raise ContractViolation(
                "program audit failed:\n"
                + "\n".join(f.render() for f in findings)
            )
        self._log.info(
            "strict: program audit ok (%s)",
            ", ".join(self.program_catalog.names()),
        )

    def audit_programs(self, compile: bool = True) -> list:
        """Audit every catalogued round program at the jaxpr/AOT level
        (``analysis.program_audit``): collective schedules, mesh discipline,
        donation-vs-memory_analysis, dtype drift, embedded host transfers.

        Appends an ``audit`` record per program to ``telemetry.jsonl`` when
        telemetry is on and returns the reports; findings are REPORTED, not
        raised — the CLI decides the exit code, strict mode has its own
        construction-time raise."""
        reports = []
        for name in self.program_catalog.names():
            with self._tracer.span("program-audit", program=name):
                report = self.program_catalog.audit(name, compile=compile)
            if self.telemetry is not None:
                self.telemetry.record("audit", **report.to_dict())
            self._log.info(
                "audit %s: %s (%d collectives, axes %s%s)",
                name,
                "ok" if report.ok else f"{len(report.findings)} finding(s)",
                len(report.schedule),
                ",".join(report.mesh_axes) or "-",
                "" if report.compiled else ", trace-only",
            )
            reports.append(report)
        return reports

    # ------------------------------------------------------------------
    # Online retuning (tuning.retuner)
    # ------------------------------------------------------------------

    def enable_retuning(
        self,
        result,
        *,
        cache_dir: str | Path | None = ".jax_cache",
        hysteresis: float = 0.05,
        min_rounds: int = 2,
        current=None,
    ):
        """Attach an :class:`~nanofed_tpu.tuning.OnlineRetuner` over ``result``'s
        candidate table (``from_autotune`` calls this when
        ``config.retune_every > 0``; callable directly on a hand-built
        coordinator whose configuration matches a table row).

        ``current`` names the live program's position in the table (default:
        ``result.winner``).  Measured walltimes flow in at every round/block
        boundary; :meth:`start_training` asks for a swap every
        ``config.retune_every`` rounds and writes the measurements back into
        the autotune cache entry when the run completes."""
        from nanofed_tpu.tuning.retuner import OnlineRetuner

        if self.scaffold:
            raise NanoFedError(
                "online retuning does not cover the SCAFFOLD round program "
                "(different signature; the autotuner never sweeps it)"
            )
        self.retuner = OnlineRetuner(
            result, hysteresis=hysteresis, min_rounds=min_rounds,
            cache_dir=cache_dir,
        )
        self._retune_candidate = current if current is not None else result.winner
        self._last_retune_round = self.current_round
        return self.retuner

    def _observe_retune(
        self, rounds: int, walltime_s: float, occupancy: float | None = None,
    ) -> None:
        """Feed one realized round/block walltime to the retuner (no-op when
        retuning is off)."""
        if self.retuner is None or self._retune_candidate is None:
            return
        self.retuner.observe(
            self._retune_candidate, rounds, walltime_s, occupancy=occupancy,
        )

    def _maybe_retune(self) -> None:
        """At a swap-safe boundary (between blocks, before the next dispatch),
        ask the retuner for a verdict every ``config.retune_every`` rounds and
        apply a proposed swap.  Every decision — swap, hold, or a swap the
        coordinator refused — lands as a ``retune`` telemetry record."""
        cfg = self.config
        if self.retuner is None or cfg.retune_every <= 0:
            return
        if self.current_round <= 0 or self.current_round >= cfg.num_rounds:
            return
        if self.current_round - self._last_retune_round < cfg.retune_every:
            return
        self._last_retune_round = self.current_round
        decision = self.retuner.propose(self._retune_candidate)
        applied = False
        if decision.swap:
            applied = self._apply_retune(decision)
        if self.telemetry is not None:
            self.telemetry.record(
                "retune", round=self.current_round, applied=applied,
                **decision.to_dict(),
            )

    def _apply_retune(self, decision) -> bool:
        """Perform a proposed swap: rebuild the round programs under the new
        (client_chunk, rounds_per_block) and re-register the catalog.  Returns
        False (old programs untouched) when the coordinator refuses — the
        rebuild is transactional, a failed swap never leaves a half-built
        program live."""
        from nanofed_tpu.tuning.autotuner import candidate_program_name

        new = decision.new
        try:
            self._rebuild_round_programs(new.client_chunk, new.rounds_per_block)
        except Exception as e:  # noqa: BLE001 — a refused swap must not kill the run
            self._log.warning(
                "retune swap to %s refused at the coordinator (%s); keeping %s",
                candidate_program_name(new), e,
                candidate_program_name(decision.old),
            )
            return False
        self._retune_candidate = new
        self._log.info(
            "retune: swapped round program %s -> %s at round %d "
            "(%s basis, %+.1f%% predicted win)",
            candidate_program_name(decision.old), candidate_program_name(new),
            self.current_round, decision.basis,
            100.0 * (decision.delta or 0.0),
        )
        return True

    def _rebuild_round_programs(
        self, client_chunk: int | None, rounds_per_block: int,
    ) -> None:
        """Rebuild ``_round_step``/``_round_block`` for a hot-swapped
        (client_chunk, rounds_per_block) — the only two knobs swappable without
        resharding resident device state (the retuner's scope rule enforces the
        rest).  Transactional: both programs build before either is installed.
        The catalog re-registers (register REPLACES, so the ``nanofed_program_*``
        gauges re-point at the next profile) and strict mode re-checks the new
        programs' contracts."""
        import dataclasses

        if self.scaffold:
            raise NanoFedError(
                "online retuning does not cover the SCAFFOLD round program"
            )
        ctx = self._builder_ctx
        if self._cohort_mode and client_chunk is not None:
            n_dev = client_shard_count(self.mesh)
            per_dev = pad_client_count(self.cohort_size, n_dev) // n_dev
            if client_chunk < per_dev and per_dev % client_chunk != 0:
                raise NanoFedError(
                    f"client_chunk={client_chunk} does not divide the gathered "
                    f"cohort layout ({per_dev} rows/device)"
                )
        round_step = build_round_step(
            self.model.apply, self.training, self.mesh, self.strategy,
            grad_fn=ctx["grad_fn"], local_fit=ctx["local_fit"],
            central_privacy=ctx["central_privacy"],
            validation=ctx["validation"], robust=ctx["robust"],
            client_chunk=client_chunk, params_like=self.params, donate=True,
            frozen_base=self._frozen_base,
        )
        round_block = None
        if rounds_per_block > 1:
            unsupported = [
                name for name, active in (
                    ("robust aggregation", ctx["robust"] is not None),
                    ("central DP", ctx["central_privacy"] is not None),
                    ("eval_every < rounds_per_block",
                     0 < self.config.eval_every < rounds_per_block),
                ) if active
            ]
            if unsupported:
                raise NanoFedError(
                    f"rounds_per_block={rounds_per_block} is not fused-capable "
                    f"here ({' + '.join(unsupported)})"
                )
            round_block = build_round_block(
                self.model.apply, self.training, self.mesh, self.strategy,
                num_clients=self.num_clients,
                padded_clients=self._padded_clients,
                step_clients=self._step_clients,
                cohort_size=self.cohort_size,
                dropout_rate=self.config.dropout_rate,
                min_completion_rate=self.config.min_completion_rate,
                grad_fn=ctx["grad_fn"], local_fit=ctx["local_fit"],
                validation=ctx["validation"],
                client_chunk=client_chunk, params_like=self.params,
                collect_client_detail=(
                    self.config.save_metrics
                    and self.config.client_metrics_every > 0
                ),
                cohort_mode=self._cohort_mode,
                donate=True,
                frozen_base=self._frozen_base,
            )
        # Commit — nothing above mutated coordinator state.
        self._round_step = round_step
        self._round_block = round_block
        self._fused_fallback_reason = None
        self._client_chunk = client_chunk
        self.config = dataclasses.replace(
            self.config, rounds_per_block=rounds_per_block
        )
        if round_block is None:
            # A swap down to rpb=1 must not leave the OLD block program
            # registered (the catalog would keep profiling a dead program).
            self.program_catalog.remove("round_block")
            self.program_catalog.remove("adapter_round_block")
        self._register_programs()
        if self.strict:
            self._check_contracts()
            # A retuned program is a NEW program: re-audit its schedules
            # before the swap's first dispatch, same bar as construction.
            self._audit_strict()

    # ------------------------------------------------------------------
    # Strict mode (analysis.contracts)
    # ------------------------------------------------------------------

    def _check_contracts(self) -> None:
        """Validate the built round programs against the round-engine contract
        via ``jax.eval_shape`` — nothing executes, nothing compiles; a drifted
        program fails HERE with a named leaf instead of deep inside the jit."""
        from nanofed_tpu.analysis.contracts import (
            check_input_shardings,
            check_round_block,
            check_round_step,
        )
        from nanofed_tpu.parallel.mesh import CLIENT_AXIS

        def lead(tree: Any, n: int) -> Any:
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((n, *x.shape[1:]), x.dtype), tree
            )

        n = self._step_clients
        rngs_sds = jax.eval_shape(lambda: stack_rngs(jax.random.key(0), n))
        report = check_round_step(
            self._round_step,
            self.params,
            self.server_state,
            lead(self._data, n),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            rngs_sds,
            # Adapter mode: the frozen base enters the traced signature but is
            # absent from the fixed-point check (read-only boundary data).
            frozen_base=self.base_params,
        )
        self._log.info("strict: round_step contract ok (%s)", report)
        if self._round_block is not None:
            rpb = self.config.rounds_per_block
            keys_sds = jax.eval_shape(
                lambda: stack_round_keys(0, list(range(rpb)))
            )
            report = check_round_block(
                self._round_block,
                self.params,
                self.server_state,
                self._data,
                self._num_samples,
                keys_sds,
                jax.ShapeDtypeStruct((rpb,), jnp.float32),
                cohort_idx=(
                    jax.ShapeDtypeStruct((rpb, n), jnp.int32)
                    if self._cohort_mode else None
                ),
                cohort_mask=jax.ShapeDtypeStruct((rpb, n), jnp.float32),
                frozen_base=self.base_params,
            )
            self._log.info("strict: round_block contract ok (%s)", report)
        from nanofed_tpu.parallel.mesh import HOST_AXIS

        check_input_shardings(
            self._data, self.params, axis_name=CLIENT_AXIS,
            model_axis=MODEL_AXIS, host_axis=HOST_AXIS,
            base_params=self.base_params,
        )

    def _dispatch_guard(self):
        """The strict-mode transfer guard around device dispatch: every input is
        device-resident by then, so an implicit transfer inside the dispatch is a
        hot-path bug and raises.  A no-op context when ``strict=False``."""
        if not self.strict:
            return contextlib.nullcontext()
        from nanofed_tpu.analysis.contracts import strict_mode

        return strict_mode()

    # ------------------------------------------------------------------
    # Round loop
    # ------------------------------------------------------------------

    def start_training(self) -> Iterator[RoundMetrics]:
        """Generator over rounds (parity with the async generator
        ``Coordinator.start_training``, ``coordinator.py:384-405``).

        With ``rounds_per_block > 1`` (and a fused-capable configuration), full
        blocks of R rounds run as ONE device program: the host syncs, publishes,
        checkpoints, and yields only at block boundaries.  A consumer that
        abandons the generator mid-block therefore resumes at the block edge —
        early-exit granularity is the block, which is the knob's contract."""
        with self._log.context("coordinator"):
            try:
                while self.current_round < self.config.num_rounds:
                    # The top of a generator step: the round's ``prepare`` segment
                    # runs from this reading (see _train_round).
                    resumed_at = time.perf_counter()
                    # Retune checks run BETWEEN blocks (the swap-safe boundary):
                    # the next dispatch picks up a swapped program, the one in
                    # flight never changes under its own feet.
                    self._maybe_retune()
                    n = self._block_len()
                    if n > 1:
                        # _train_block publishes + advances state for the whole
                        # block before anything is yielded, so abandonment cannot
                        # leave params ahead of the recorded round counter.
                        for metrics in self._train_block(n, resumed_at):
                            yield metrics
                        continue
                    metrics, round_span = self._train_round(
                        self.current_round, resumed_at
                    )
                    self.history.append(metrics)
                    with self._tracer.span("publish", round=metrics.round_id):
                        self._publish_round(metrics)
                    if self.on_round_end is not None:
                        self.on_round_end(metrics)
                    self.current_round += 1
                    metrics = self._close_round(metrics, round_span.t_end)
                    self._observe_retune(
                        1, round_span.duration_s,
                        update_device_occupancy(metrics.segments, self._registry),
                    )
                    yield metrics
            finally:
                # Final registry snapshot only when ALL rounds ran: a caller that
                # abandons the generator early (early stopping, interrupt) may
                # resume via a fresh start_training() on the same coordinator, and
                # a closed sink would silently drop every later record.  The cost
                # of not closing on abandonment is an open line-buffered handle
                # (every record is already flushed) and no metrics_snapshot line.
                if (
                    self.retuner is not None
                    and self.current_round >= self.config.num_rounds
                ):
                    # Write the measured numbers back into the autotune cache
                    # entry so the NEXT run's cache hit starts from reality,
                    # and leave the run's retune digest in the telemetry.
                    written = self.retuner.write_back()
                    if self.telemetry is not None:
                        self.telemetry.record(
                            "retune_summary",
                            **self.retuner.summary(),
                            **({"cache_entry": str(written)}
                               if written is not None else {}),
                        )
                if (
                    self.telemetry is not None
                    and self.current_round >= self.config.num_rounds
                ):
                    if self.adapter is not None:
                        # Final merge count: how many times the run paid the
                        # full-model merge (evals + versioned models).
                        self.telemetry.record(
                            "adapter", rank=self.adapter.rank,
                            merges=self._merge_count,
                        )
                    self.telemetry.close()

    def _close_round(
        self, metrics: RoundMetrics, publish_from: float, **record_fields: Any
    ) -> RoundMetrics:
        """A round's last act before it is yielded.  One clock reading closes the
        ``publish`` segment — everything since ``publish_from``: the ``publish``
        span, ``on_round_end`` — the five segments replace the four on the metrics
        (and on ``history``), and the ledger is charged with them: the ``round``
        record and ``nanofed_round_critical_path_seconds{segment}`` get the whole
        tiling, and the charged ``duration_s`` is its sum, the round's share of the
        generator's time, publish included (as the federate worker charges its
        beats).  The charge itself falls after the reading and into no segment."""
        segments = {
            **metrics.segments, "publish": time.perf_counter() - publish_from,
        }
        metrics = replace(metrics, segments=segments)
        self.history[-1] = metrics
        step_s = math.fsum(segments.values())
        self._ledger.charge(
            status=metrics.status.name, num_clients=metrics.num_clients,
            duration_s=step_s, expected=self.cohort_size, segments=segments,
            telemetry_fields=dict(
                round=metrics.round_id, status=metrics.status.name,
                num_clients=metrics.num_clients, duration_s=round(step_s, 6),
                **record_fields,
            ),
        )
        return metrics

    def _publish_round(self, metrics: RoundMetrics, persist_state: bool = True) -> None:
        """Release the round's artifacts — checkpoint, metrics JSON, versioned model.

        The checkpoint is written FIRST, before any released artifact of the
        round (metrics JSON, versioned model): a crash between them then
        loses at most an artifact, never an accounting event.  The reverse
        order would let a persisted noised release outlive its accountant
        entry — a resumed run would re-release round r with fresh noise
        while reporting an ε that counts only one of the two releases.

        ``persist_state=False`` (mid-block rounds of a fused block) skips the
        checkpoint and versioned model: ``self.params`` already holds the
        block-END state, which must only ever be persisted under the block's
        final round id.

        On a 2-D mesh the device copy of params/opt state stays model-sharded;
        persistence needs whole host arrays, so the shards are gathered ONCE
        here (block boundaries only) and both the checkpoint and the versioned
        model consume that single gather."""
        persist_params = self.params
        if (
            persist_state
            and self._model_shards > 1
            and (self.state_store is not None or self.model_manager is not None)
        ):
            # fedlint: disable=FED001 (the ONE deliberate model-shard gather per block boundary — checkpoint + versioned model both consume this single device_get)
            persist_params = jax.device_get(self.params)
        if self.state_store is not None and persist_state:
            ckpt_metrics = metrics.to_dict()
            if self.privacy_accountant is not None:
                ckpt_metrics["privacy_accountant"] = (
                    self.privacy_accountant.state_dict()
                )
            ckpt_server_state = self.server_state
            if self.scaffold:
                # The controls ARE round state: resuming without them would
                # silently restart every client's correction from zero.
                ckpt_server_state = {
                    "opt": self.server_state,
                    "scaffold_c_global": self.c_global,
                    "scaffold_c_stack": self.c_stack,
                }
            if self._model_shards > 1:
                # Checkpoints hold whole host arrays regardless of the training
                # mesh, so resume works across mesh shapes.
                # fedlint: disable=FED001 (deliberate block-boundary gather of the opt-state shards for the checkpoint artifact)
                ckpt_server_state = jax.device_get(ckpt_server_state)
            self.state_store.checkpoint(
                round_number=metrics.round_id,
                params=persist_params,
                server_state=ckpt_server_state,
                metrics=ckpt_metrics,
                status=(
                    "COMPLETED"
                    if metrics.status == RoundStatus.COMPLETED
                    else "FAILED"
                ),
            )
        if self.config.save_metrics:
            self._save_round_metrics(metrics)
        if (
            self.model_manager is not None
            and persist_state
            and metrics.status == RoundStatus.COMPLETED
        ):
            save_params = persist_params
            metadata = {
                "round": metrics.round_id,
                "metrics": metrics.agg_metrics,
            }
            if self.adapter is not None:
                # A versioned model must be runnable by a consumer who knows
                # nothing of adapters: publish the MERGED params (checkpoints,
                # by contrast, stay adapter-shaped — resume needs the adapter
                # tree, and the base is re-derivable from the model seed).
                # fedlint: disable=FED001 (block-boundary gather of the merged model for the versioned-model artifact)
                save_params = jax.device_get(self.merged_params())
                metadata["adapter"] = self.adapter.to_dict()
            self.model_manager.save_model(save_params, metadata=metadata)

    def _sample_cohort(self, round_id: int) -> np.ndarray:
        """Draw this round's participant cohort (replaces the HTTP wait barrier),
        applying the simulated ``dropout_rate`` fault model.

        Without DP this is a deterministic function of the config seed (reproducible
        runs).  Under central DP the amplified ε credited by the accountant is only
        valid if the sampling randomness is SECRET — a cohort predictable from a seed
        persisted in checkpoints/artifacts voids amplification-by-subsampling against
        an adversary who reads the seed — so DP cohorts are drawn from OS entropy
        (trajectories then vary run to run; the privacy guarantee is what must be
        reproducible, not the cohort).
        """
        if self.central_privacy is not None:
            host_rng = self._secret_sampling_rng
        else:
            host_rng = np.random.default_rng(self.config.seed * 100_003 + round_id)
        if self._n_hosts > 1 and self._cohort_mode:
            # Host-LOCAL stratified draw (3-axis mesh): quota_h clients from
            # each host's own resident range, proportional to its population
            # (largest remainder), so every host's slot segment can be filled
            # from rows it already holds.  Per-client inclusion probability
            # stays quota_h / pop_h == cohort/N under proportional quotas.
            # NOTE: the draw ORDER differs from the single-host path, so a
            # hosts-mesh run is seed-deterministic but not cohort-identical
            # to the same seed on a 1-D mesh under partial participation.
            sampled = self._sample_host_local(host_rng)
        else:
            sampled = host_rng.choice(
                self.num_clients, size=self.cohort_size, replace=False
            )
        if self.config.dropout_rate > 0:
            keep = host_rng.random(len(sampled)) >= self.config.dropout_rate
            sampled = sampled[keep]
        if self._chaos is not None:
            # Planned crashes (faults.ChaosSchedule): a crashed client is gone
            # from this and every later cohort, deterministically — the round
            # then stands or falls on min_completion_rate exactly like a real
            # dropout wave.
            alive = [c for c in sampled
                     if not self._chaos.crashed(int(c), round_id)]
            sampled = np.asarray(alive, dtype=sampled.dtype)
        return sampled

    def _host_populations(self) -> list[tuple[int, int]]:
        """Per-host resident client id ranges ``[(start, stop), ...]`` — data
        rows shard hosts-major, so host h owns the contiguous padded rows
        ``[h*rows_per_host, (h+1)*rows_per_host)``; clipping to ``num_clients``
        drops the padding rows (the last host may own fewer real clients)."""
        return [
            (h * self._rows_per_host,
             min((h + 1) * self._rows_per_host, self.num_clients))
            for h in range(self._n_hosts)
        ]

    def _sample_host_local(self, host_rng: np.random.Generator) -> np.ndarray:
        """Stratified cohort draw over the hosts axis: proportional quotas
        with RANDOMIZED largest-remainder rounding, each host's quota drawn
        without replacement from its own resident range, clamped to its slot
        segment.

        The leftover slots after flooring are assigned by per-round weighted
        draws (weight = a host's outstanding remainder, uniform fallback once
        remainders are exhausted) — never by a deterministic remainder sort,
        which would hand the extras to the SAME hosts every round: with
        uneven per-host populations (padding always clips the last host) that
        permanently skews — or zeroes — some clients' inclusion probability,
        while the randomized rounding keeps it at cohort/N in expectation
        (exactly, up to cap clipping), which is the rate the central-DP
        accountant assumes."""
        ranges = self._host_populations()
        pops = [max(0, stop - start) for start, stop in ranges]
        total = sum(pops)
        exact = [self.cohort_size * p / total for p in pops]
        quotas = [int(q) for q in exact]
        # Floor quotas, capped by both the host's population and its slot
        # segment (a quota the slots can't hold would overflow placement).
        caps = [min(p, self._slots_per_host) for p in pops]
        quotas = [min(q, c) for q, c in zip(quotas, caps)]
        short = self.cohort_size - sum(quotas)
        # A shortfall the caps cannot absorb at all is a sizing error,
        # surfaced like _place_cohort's overflow (and refused up front at
        # construction) — never a silently smaller cohort.
        while short > 0:
            open_hosts = [h for h in range(self._n_hosts)
                          if quotas[h] < caps[h]]
            if not open_hosts:
                raise NanoFedError(
                    f"cohort_size {self.cohort_size} exceeds the hosts-axis "
                    f"capacity (per-host caps {caps} = min(resident clients, "
                    f"slot segment {self._slots_per_host})) — shrink the "
                    "cohort or raise participation"
                )
            w = np.array([max(exact[h] - quotas[h], 0.0) for h in open_hosts])
            if w.sum() <= 0:
                w = np.ones(len(open_hosts))
            pick = open_hosts[
                int(host_rng.choice(len(open_hosts), p=w / w.sum()))
            ]
            quotas[pick] += 1
            short -= 1
        parts = []
        for (start, _), pop, quota in zip(ranges, pops, quotas):
            if quota > 0:
                parts.append(
                    start + host_rng.choice(pop, size=quota, replace=False)
                )
        return np.concatenate(parts) if parts else np.zeros(0, dtype=np.int64)

    def _place_cohort(
        self, survived: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Lay a sampled cohort into the step's ``[step_clients]`` slot arrays
        (client ids + survivor mask).  Single-host: front-packed, padding slots
        alias row 0 with weight 0 (the classic layout).  Hosts mesh: each
        host's survivors fill that host's slot segment, and its padding slots
        alias that host's FIRST resident row — a padding slot must never force
        a cross-host gather for a zero-weight client."""
        idx = np.zeros(self._step_clients, dtype=np.int32)
        mask = np.zeros(self._step_clients, dtype=np.float32)
        if self._n_hosts <= 1:
            idx[: len(survived)] = survived
            mask[: len(survived)] = 1.0
            return idx, mask
        slots = self._slots_per_host
        for h, (start, stop) in enumerate(self._host_populations()):
            rows = survived[(survived >= start) & (survived < stop)]
            if len(rows) > slots:
                raise NanoFedError(
                    f"host {h} drew {len(rows)} cohort clients but its slot "
                    f"segment holds {slots} — host-local sampling must cap "
                    "per-host quotas at the segment width"
                )
            base = h * slots
            idx[base : base + slots] = start  # padding aliases a HOST-LOCAL row
            idx[base : base + len(rows)] = rows
            mask[base : base + len(rows)] = 1.0
        return idx, mask

    # ------------------------------------------------------------------
    # Fused multi-round blocks
    # ------------------------------------------------------------------

    def _block_len(self) -> int:
        """Rounds to run next as one fused block; 1 = the single-round path.

        Only FULL blocks of ``rounds_per_block`` rounds run fused (one compiled
        scan length, shared with every other full block); ragged tails and the
        rounds leading into an eval boundary finish on the already-compiled
        single-round program instead of paying a fresh compile per length.
        """
        rpb = self.config.rounds_per_block
        if self._round_block is None or rpb <= 1:
            return 1
        n = min(rpb, self.config.num_rounds - self.current_round)
        if self.config.eval_every > 0:
            # Blocks must END on eval boundaries: eval (and any decision made on
            # it) is host work, and the fused block admits no mid-block sync.
            n = min(n, self.config.eval_every
                    - (self.current_round % self.config.eval_every))
        return n if n == rpb else 1

    def _train_block(self, n: int, resumed_at: float) -> list[RoundMetrics]:
        """Run ``n`` rounds as one fused device block.

        Host work splits into exactly two phases, each its own span so phase
        summaries separate device compute from host-blocked time: ``dispatch``
        (sample cohorts, stack per-round inputs, enqueue the block — returns as
        soon as XLA accepts the program, no blocking) and ``host_sync`` (the one
        ``block_until_ready`` + stacked-metrics fetch at the block boundary).
        Cohorts, keys, and lr scales are the SAME pure host functions of the
        round index the single-round path uses, so a fused run reproduces the
        unfused trajectory round for round.

        Every round of the block reports the single-step loop's five segments
        (``_train_round``): the block's ``prepare`` (from ``resumed_at`` to the end of
        ``round-keys``), ``dispatch`` (the rest of the ``dispatch`` span),
        ``device_wait`` (the ``device-wait`` span inside ``host_sync``) and
        ``readback`` (the rest of ``host_sync``) in even shares, plus what the round
        itself took after the block came back: building its metrics goes to its
        ``readback``, and ``publish`` is its own."""
        cfg = self.config
        first = self.current_round
        rounds = list(range(first, first + n))
        required = completion_required(self.cohort_size, cfg.min_completion_rate)

        with self._tracer.span("dispatch", round=first, rounds=n):
            with self._tracer.span("cohort-sample", round=first, rounds=n):
                idx_rows = np.zeros((n, self._step_clients), dtype=np.int32)
                mask_rows = np.zeros((n, self._step_clients), dtype=np.float32)
                survived_counts = []
                for i, r in enumerate(rounds):
                    survived = self._sample_cohort(r)
                    survived_counts.append(len(survived))
                    if self._cohort_mode:
                        # Slot layout shared with the single-round path
                        # (host-segmented on a 3-axis mesh).
                        idx_rows[i], mask_rows[i] = self._place_cohort(survived)
                    else:
                        mask_rows[i, survived] = 1.0
            with self._tracer.span("round-keys", round=first, rounds=n) as keys:
                lr_scales = lr_schedule_scales(
                    cfg.lr_schedule, first, n, cfg.num_rounds,
                    min_factor=cfg.lr_min_factor, decay_every=cfg.lr_decay_every,
                    gamma=cfg.lr_decay_gamma,
                )
                # Device-ready inputs BEFORE the guarded dispatch: under strict mode
                # the jit call itself must perform zero implicit h2d transfers.
                base_keys = stack_round_keys(cfg.seed, rounds)
                lr_dev = jnp.asarray(lr_scales, jnp.float32)
                idx_dev = jnp.asarray(idx_rows) if self._cohort_mode else None
                mask_dev = jnp.asarray(mask_rows)
            with self._dispatch_guard():
                result = self._round_block(
                    self.params, self.server_state, self._data,
                    self._num_samples, base_keys, lr_dev, idx_dev, mask_dev,
                    base_params=self.base_params,
                )
            self.params = result.params
            self.server_state = result.server_opt_state

        with self._tracer.span("host_sync", round=first, rounds=n) as sync:
            with self._tracer.span("device-wait", round=first, rounds=n) as wait:
                # fedlint: disable=FED001 (the ONE deliberate host sync per fused block — the host_sync span exists to measure exactly this barrier)
                jax.block_until_ready(self.params)
            stacked = {k: np.asarray(v) for k, v in result.metrics.items()}
            detail = None
            # Fetch the [R, K] per-client stacks only when some round in this
            # block will actually dump them — client_metrics_every exists to skip
            # exactly this device->host conversion.
            if result.client_metrics is not None and any(
                self._client_detail_due(r) for r in rounds
            ):
                detail = {
                    "weights": np.asarray(result.weights),
                    "client_loss": np.asarray(result.client_metrics.loss),
                    "client_accuracy": np.asarray(result.client_metrics.accuracy),
                    "update_sq_norms": np.asarray(result.update_sq_norms),
                }
        block_duration = sync.t_end - resumed_at
        per_round_s = block_duration / n
        shared = {
            "prepare": (keys.t_end - resumed_at) / n,
            "dispatch": (wait.t_start - keys.t_end) / n,
            "device_wait": wait.duration_s / n,
            "readback": (sync.t_end - wait.t_end) / n,
        }
        boundary = sync.t_end  # where the block's time stops and a round's own starts

        out: list[RoundMetrics] = []
        for i, r in enumerate(rounds):
            if survived_counts[i] < required:
                self._log.warning(
                    "round %d FAILED: %d/%d clients completed (< %d required)",
                    r, survived_counts[i], self.cohort_size, required,
                )
                metrics = RoundMetrics(
                    round_id=r,
                    status=RoundStatus.FAILED,
                    num_clients=survived_counts[i],
                    duration_s=per_round_s,
                    timestamp=_now_iso(),
                    segments=shared,
                )
            else:
                agg = {k: float(v[i]) for k, v in stacked.items()}
                if cfg.lr_schedule != "constant":
                    agg["lr_scale"] = round(lr_scales[i], 6)
                for count_key in ("participating_clients", "valid_clients"):
                    if count_key in agg:
                        agg[count_key] = int(agg[count_key])
                eval_metrics: dict[str, float] = {}
                if (
                    self._evaluator is not None
                    and cfg.eval_every > 0
                    and (r + 1) % cfg.eval_every == 0
                ):
                    # Only ever the block's LAST round (_block_len cuts blocks at
                    # eval boundaries), so self.params IS this round's model
                    # (merged with the frozen base in adapter mode).
                    eval_metrics = {
                        k: float(v)
                        for k, v in self._evaluator(
                            self.merged_params(), self._eval_data
                        ).items()
                    }
                self._log.info(
                    "round %d: loss=%.4f acc=%.4f clients=%d (fused %d-round "
                    "block, %.2fs/round)",
                    r, agg.get("loss", float("nan")),
                    agg.get("accuracy", float("nan")), survived_counts[i],
                    n, per_round_s,
                )
                metrics = RoundMetrics(
                    round_id=r,
                    status=RoundStatus.COMPLETED,
                    num_clients=survived_counts[i],
                    agg_metrics=agg,
                    eval_metrics=eval_metrics,
                    duration_s=per_round_s,
                    timestamp=_now_iso(),
                    segments=shared,
                )

            self._last_client_detail = None
            if (
                detail is not None
                and metrics.status == RoundStatus.COMPLETED
                and self._client_detail_due(r)
            ):
                self._last_client_detail = {
                    k: v[i].tolist() for k, v in detail.items()
                }
                if self._cohort_mode:
                    self._last_client_detail["client_ids"] = idx_rows[i].tolist()

            self.history.append(metrics)
            with self._tracer.span("publish", round=r) as publish:
                # Checkpoint / versioned model only at the block boundary: a
                # mid-block checkpoint would pair round r's id with the block's
                # END params and make a resume re-apply rounds r+1..end.
                self._publish_round(metrics, persist_state=(i == n - 1))
            if self.on_round_end is not None:
                self.on_round_end(metrics)
            self.current_round += 1
            own_readback = publish.t_start - boundary
            metrics = self._close_round(
                replace(metrics, segments={
                    **shared, "readback": shared["readback"] + own_readback,
                }),
                publish.t_start, fused=True, rounds_per_block=n,
            )
            boundary = publish.t_start + metrics.segments["publish"]
            out.append(metrics)
        # Occupancy of the whole block, updated at every block boundary so /metrics
        # always carries the current ratio (see observability.profiling).
        occupancy = update_device_occupancy(
            {seg: math.fsum(m.segments[seg] for m in out) for seg in out[0].segments},
            self._registry,
        )
        self._observe_retune(n, block_duration, occupancy)
        return out

    def _client_detail_due(self, round_id: int) -> bool:
        every = self.config.client_metrics_every
        return every > 0 and round_id % every == 0

    @log_exec
    def _train_round(
        self, round_id: int, resumed_at: float
    ) -> tuple[RoundMetrics, SpanTiming]:
        """One round, instrumented: the round and its phases land as spans (and in
        the ``nanofed_span_duration_seconds`` histogram), and the metrics come back
        carrying the round's segments up to the end of the ``round`` span, which is
        returned with them: ``publish`` runs from its end (``_close_round``).

        The segments tile ``[resumed_at, end of round]`` at the spans' own clock
        readings: ``prepare`` up to the ``dispatch`` span's start, ``dispatch`` up to
        ``device-wait``'s start, ``device_wait`` that span, ``readback`` the rest.  A
        round that FAILED before anything was dispatched was preparation throughout."""
        with self._tracer.span("round", round=round_id) as round_span:
            metrics, device = self._train_round_impl(round_id)
        if device is None:
            segments = {"prepare": round_span.t_end - resumed_at}
        else:
            dispatch, wait = device
            segments = {
                "prepare": dispatch.t_start - resumed_at,
                "dispatch": wait.t_start - dispatch.t_start,
                "device_wait": wait.duration_s,
                "readback": round_span.t_end - wait.t_end,
            }
        return replace(metrics, segments=segments), round_span

    def _train_round_impl(
        self, round_id: int
    ) -> tuple[RoundMetrics, tuple[SpanTiming, SpanTiming] | None]:
        """The round, and the ``dispatch`` and ``device-wait`` spans' timings (None
        for a round that FAILED before dispatch): where ``_train_round`` cuts the
        round into segments."""
        t0 = time.perf_counter()
        cohort = self.cohort_size
        with self._tracer.span("cohort-sample", round=round_id):
            survived = self._sample_cohort(round_id)
        required = completion_required(cohort, self.config.min_completion_rate)
        if len(survived) < required:
            self._log.warning(
                "round %d FAILED: %d/%d clients completed (< %d required)",
                round_id, len(survived), cohort, required,
            )
            return RoundMetrics(
                round_id=round_id,
                status=RoundStatus.FAILED,
                num_clients=len(survived),
                duration_s=time.perf_counter() - t0,
                timestamp=_now_iso(),
            ), None

        with self._tracer.span("cohort-gather", round=round_id,
                               cohort=len(survived)):
            if self._cohort_mode:
                # Gather the cohort's rows.  Dropped + padding slots point at a
                # resident row (row 0; each host's first row on a 3-axis mesh)
                # with weight 0: their CONTRIBUTION is zero in every reduce,
                # though their (static-shape) local fit still executes — the
                # waste is bounded by the dropout fraction + device padding of
                # K_pad, vs the full-N path burning N - K slots every round.
                idx, mask = self._place_cohort(survived)
                idx_dev = jnp.asarray(idx)
                data = self._gather_cohort(self._data, idx_dev)
                weights = compute_weights(self._num_samples[idx_dev], jnp.asarray(mask))
            else:
                data = self._data
                mask = np.zeros(self._padded_clients, dtype=np.float32)
                mask[survived] = 1.0
                weights = compute_weights(self._num_samples, jnp.asarray(mask))

        # The rest of what the step takes, made device-ready before the dispatch.
        with self._tracer.span("round-keys", round=round_id):
            # Device RNG stack: seed-deterministic without DP.  Under central DP the
            # round step derives the server NOISE key from this stack (round_step.py
            # ``noise_rng``) — noise regenerable from a persisted seed could be
            # subtracted from the released aggregate, voiding DP entirely, so fold in
            # OS entropy (same secrecy argument as _sample_cohort, but for the noise
            # itself).
            base = jax.random.fold_in(jax.random.key(self.config.seed), round_id)
            if self.central_privacy is not None:
                # Fold in 4 secret words — saturating threefry2x32's 64-bit key state,
                # the effective bound here (see ops/quantize.py on the keyspace); a
                # single 31-bit fold would leave the noise key brute-forceable by an
                # adversary testing candidate draws against the released aggregate.
                for word in self._secret_sampling_rng.integers(
                    0, 1 << 32, size=4, dtype=np.uint32
                ):
                    base = jax.random.fold_in(base, word)
            if self._cohort_mode:
                # Client-STABLE keys: slot i carries the key of the client it hosts,
                # so a client's batch shuffling (and any model stochasticity) is
                # identical whether the round ran gathered or full-N masked — the
                # optimization is exactly invisible, not just statistically equivalent.
                rngs = stack_rngs(base, self._padded_clients)[idx_dev]
            else:
                rngs = stack_rngs(base, self._step_clients)
            lr_scale = lr_schedule_scale(
                self.config.lr_schedule, round_id, self.config.num_rounds,
                min_factor=self.config.lr_min_factor,
                decay_every=self.config.lr_decay_every,
                gamma=self.config.lr_decay_gamma,
            )
            lr_dev = jnp.float32(lr_scale)  # h2d BEFORE the guarded dispatch
        # The device step fuses local training AND the psum aggregation into one XLA
        # program, so "local-train" covers both (attr says so); "aggregate" below is
        # the host-side post-aggregation work.  block_until_ready inside the span
        # makes its duration the real device time, not dispatch time.
        with self._tracer.span("local-train", round=round_id,
                               fused="train+aggregate"):
            # ``dispatch``: the jitted call until it returns (the device is then at
            # work, or about to be); ``device-wait``: the host blocked on it.
            with self._tracer.span("dispatch", round=round_id) as dispatch:
                if self.scaffold:
                    c_rows = (
                        self._gather_controls(self.c_stack, idx_dev)
                        if self._cohort_mode
                        else self.c_stack
                    )
                    with self._dispatch_guard():
                        result = self._round_step(
                            self.params, self.server_state, self.c_global, c_rows,
                            data, weights, rngs, lr_dev,
                        )
                    self.c_global = result.c_global
                    if self._cohort_mode:
                        # Participants' control rows move by their delta; padding/dropped
                        # slots add exact zeros (collision-safe though they alias row 0).
                        self.c_stack = self._scatter_add_controls(
                            self.c_stack, idx_dev, result.delta_c
                        )
                    else:
                        # Rows already align with the stack — a fused elementwise add,
                        # not a scatter (which GSPMD may lower with cross-device index
                        # traffic).
                        self.c_stack = self._add_controls(self.c_stack, result.delta_c)
                elif self.adapter is not None:
                    with self._dispatch_guard():
                        result = self._round_step(
                            self.params, self.server_state, self.base_params,
                            data, weights, rngs, lr_dev,
                        )
                else:
                    with self._dispatch_guard():
                        result = self._round_step(
                            self.params, self.server_state, data, weights, rngs,
                            lr_dev,
                        )
                self.params = result.params
                self.server_state = result.server_opt_state
            with self._tracer.span("device-wait", round=round_id) as wait:
                # fedlint: disable=FED001 (deliberate: blocks INSIDE the local-train span so its duration is device time, not dispatch time)
                jax.block_until_ready(self.params)

        with self._tracer.span("aggregate", round=round_id):
            agg = {k: float(v) for k, v in result.metrics.items()}
            if self.config.lr_schedule != "constant":
                agg["lr_scale"] = round(lr_scale, 6)
            for count_key in ("participating_clients", "valid_clients"):
                if count_key in agg:
                    agg[count_key] = int(agg[count_key])

            if self.privacy_accountant is not None:
                from nanofed_tpu.aggregation.privacy import record_central_privacy

                record_central_privacy(
                    self.privacy_accountant,
                    self.central_privacy,
                    sampling_rate=self.cohort_size / self.num_clients,
                )
                spent = self.privacy_accountant.get_privacy_spent(
                    self.central_privacy.privacy.delta
                )
                agg["privacy_epsilon"] = spent.epsilon_spent
                agg["privacy_delta"] = spent.delta_spent

            eval_metrics: dict[str, float] = {}
            if (
                self._evaluator is not None
                and self.config.eval_every > 0
                and (round_id + 1) % self.config.eval_every == 0
            ):
                eval_metrics = {
                    k: float(v)
                    for k, v in self._evaluator(
                        self.merged_params(), self._eval_data
                    ).items()
                }

        # Per-client detail for the metrics file (parity: coordinator.py:247-280).  Only
        # consumed by _save_round_metrics — skip the device->host transfers otherwise;
        # ``client_metrics_every`` samples the dump down further (at 1000 clients each
        # dump is a 1000-element host conversion nobody may read).
        # Under central DP the per-client detail is NOT persisted: the weight vector
        # reveals exactly who participated (voiding amplification-by-subsampling for an
        # artifact-reading adversary), and per-client losses/update norms are
        # statistics of the un-noised deltas — information the DP release never covers.
        self._last_client_detail = None
        if (
            self.config.save_metrics
            and self.central_privacy is None
            and self._client_detail_due(round_id)
        ):
            with self._tracer.span("client-detail", round=round_id):
                self._last_client_detail = {
                    "weights": np.asarray(weights).tolist(),
                    "client_loss": np.asarray(result.client_metrics.loss).tolist(),
                    "client_accuracy": np.asarray(
                        result.client_metrics.accuracy
                    ).tolist(),
                    "update_sq_norms": np.asarray(result.update_sq_norms).tolist(),
                }
                if self._cohort_mode:
                    # Cohort-slot order, not client-id order: record which client
                    # each slot hosted (weight-0 slots host a placeholder row).
                    self._last_client_detail["client_ids"] = idx.tolist()

        # fedlint: disable=FED001 (deliberate end-of-round barrier: duration_s must measure the round, not the async dispatch queue)
        jax.block_until_ready(self.params)
        duration = time.perf_counter() - t0
        self._log.info(
            "round %d: loss=%.4f acc=%.4f clients=%d (%.2fs)",
            round_id, agg.get("loss", float("nan")), agg.get("accuracy", float("nan")),
            len(survived), duration,
        )
        return RoundMetrics(
            round_id=round_id,
            status=RoundStatus.COMPLETED,
            num_clients=len(survived),
            agg_metrics=agg,
            eval_metrics=eval_metrics,
            duration_s=duration,
            timestamp=_now_iso(),
        ), (dispatch, wait)

    def run(self) -> list[RoundMetrics]:
        """Drain the round generator (parity with ``coordinate()``,
        ``orchestration/utils.py:5-25``)."""
        return list(self.start_training())

    # ------------------------------------------------------------------
    # Observability
    # ------------------------------------------------------------------

    @property
    def training_progress(self) -> TrainingProgress:
        completed = [m for m in self.history if m.status == RoundStatus.COMPLETED]
        failed = [m for m in self.history if m.status == RoundStatus.FAILED]
        global_metrics: dict[str, float] = {}
        if completed:
            for key in ("loss", "accuracy"):
                vals = [m.agg_metrics[key] for m in completed if key in m.agg_metrics]
                if vals:
                    global_metrics[key] = float(np.mean(vals))
        return TrainingProgress(
            current_round=self.current_round,
            total_rounds=self.config.num_rounds,
            completed_rounds=len(completed),
            failed_rounds=len(failed),
            global_metrics=global_metrics,
        )

    @property
    def client_data(self) -> ClientData:
        """The training data as placed on the mesh: padded to the client shard
        count, client axis sharded."""
        return self._data

    @property
    def cohort_size(self) -> int:
        """Clients sampled per round (see ``orchestration.types.cohort_size``).

        The realized per-client inclusion probability is ``cohort_size / num_clients``
        — this, not the nominal rate, is what privacy accounting must use (the floor
        and ceil make it ≥ the nominal rate).
        """
        from nanofed_tpu.orchestration.types import cohort_size

        return cohort_size(self.num_clients, self.config.participation_rate)

    @property
    def privacy_spent(self):
        """Cumulative central-DP spend (``PrivacySpent``), or None without central DP."""
        if self.privacy_accountant is None:
            return None
        return self.privacy_accountant.get_privacy_spent(self.central_privacy.privacy.delta)

    def merged_params(self) -> Params:
        """The model the outside world consumes: ``self.params`` directly, or —
        in adapter mode — base + low-rank deltas merged into ordinary params
        (``nanofed_tpu.adapters.merge_adapters``, one jitted call).  Every merge
        is counted (the ``adapter`` telemetry record reports the total): merging
        is the only place adapter federation pays a full-model-sized compute,
        so the count is the knob's honest cost surface."""
        if self.adapter is None:
            return self.params
        self._merge_count += 1
        return self._merge_jit(self.base_params, self.params)

    def evaluate(self) -> dict[str, float]:
        if self._evaluator is None:
            raise NanoFedError("no eval_data was provided to the Coordinator")
        return {
            k: float(v)
            for k, v in self._evaluator(self.merged_params(), self._eval_data).items()
        }

    def _save_round_metrics(self, metrics: RoundMetrics) -> None:
        payload: dict[str, Any] = metrics.to_dict()
        if (
            metrics.status == RoundStatus.COMPLETED
            and getattr(self, "_last_client_detail", None) is not None
        ):
            payload["clients"] = self._last_client_detail
        if self.central_privacy is not None:
            # Honest scoping of what the accounted (ε, δ) covers: eval metrics are
            # post-processing of the noised release (covered); the aggregated TRAIN
            # loss/accuracy are cohort statistics of un-noised local training and sit
            # outside the guarantee.  Per-client detail is suppressed entirely.
            payload["dp_note"] = (
                "train loss/accuracy in agg_metrics are un-noised cohort statistics "
                "outside the accounted (epsilon, delta); eval metrics are "
                "post-processing of the DP release and are covered"
            )
        path = self.base_dir / "metrics" / f"metrics_round_{metrics.round_id}.json"
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(payload, indent=2))
        tmp.replace(path)


def _now_iso() -> str:
    from nanofed_tpu.utils.dates import get_current_time

    return get_current_time().isoformat()
