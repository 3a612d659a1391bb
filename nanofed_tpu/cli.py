"""Command-line interface.

The reference declares a CLI entry point that doesn't exist (``pyproject.toml:22-23`` names
``nanofed.cli:main`` but no module is shipped — SURVEY.md layer-map quirks).  This one is
real: ``run`` drives a simulated federated experiment (``--dp-epsilon`` engages
budget-calibrated central DP), ``serve`` hosts the real-network federation server
(``--secure`` for masked rounds, ``--validate`` for update validation), ``profile``
compiles the round programs WITHOUT running a federation and prints the compiler's
cost/roofline table, ``info`` prints environment and model-zoo facts.
"""

from __future__ import annotations

import argparse
import json
import sys


def _cmd_info(_args: argparse.Namespace) -> int:
    import jax

    from nanofed_tpu import __version__
    from nanofed_tpu.models import list_models

    print(
        json.dumps(
            {
                "version": __version__,
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "devices": [str(d) for d in jax.devices()],
                "models": list_models(),
            },
            indent=2,
        )
    )
    return 0


def _cmd_run(args: argparse.Namespace) -> int:
    from nanofed_tpu.experiments import run_experiment

    if ((args.robust_trim is not None or args.robust_method is not None)
            and args.dp_epsilon is not None):
        # build_round_step refuses the combination too, but with a traceback; the
        # CLI should say why up front (the DP budget is calibrated for the clipped
        # uniform mean — a trimmed mean has a different sensitivity).
        print("error: --robust-trim cannot be combined with --dp-epsilon — the DP "
              "guarantee is calibrated for the clipped mean; a trimmed mean has a "
              "different sensitivity and the stated budget would be wrong",
              file=sys.stderr)
        return 2
    if args.scaffold and (
        args.dp_epsilon is not None
        or args.robust_trim is not None
        or args.robust_method is not None
    ):
        # Same up-front courtesy as above: the Coordinator refuses these too, with
        # a traceback (the control estimate is computed from the un-noised,
        # un-trimmed local trajectory).
        print("error: --scaffold cannot be combined with --dp-epsilon, "
              "--robust-trim, or --robust-method — DP noise / robust "
              "trimming/selection would bias the control estimate every later "
              "round relies on", file=sys.stderr)
        return 2

    central_privacy = None
    if args.dp_epsilon is not None:
        from nanofed_tpu.aggregation.privacy import PrivacyAwareAggregationConfig
        from nanofed_tpu.privacy import PrivacyConfig
        from nanofed_tpu.privacy.accounting import noise_multiplier_for_budget

        from nanofed_tpu.orchestration.types import cohort_size

        # Calibrate at the realized per-client inclusion probability (the coordinator
        # accounts spend at cohort/N, which ceil+floor make >= the nominal rate) so the
        # run actually spends the requested budget instead of over-noising.
        cohort = cohort_size(args.clients, args.participation)
        try:
            sigma = noise_multiplier_for_budget(
                args.dp_epsilon, args.dp_delta, sampling_rate=cohort / args.clients,
                num_events=args.rounds,
            )
            central_privacy = PrivacyAwareAggregationConfig(
                privacy=PrivacyConfig(
                    epsilon=args.dp_epsilon, delta=args.dp_delta,
                    max_gradient_norm=args.dp_clip, noise_multiplier=sigma,
                )
            )
        except ValueError as e:
            # Config bounds (eps in [0.01, 10], delta in [1e-10, 0.1]) or an
            # infeasible budget — a CLI error, not a traceback.
            print(f"error: invalid DP budget: {e}", file=sys.stderr)
            return 2
        print(f"# central DP: sigma={sigma:.4f} calibrated for "
              f"(eps={args.dp_epsilon}, delta={args.dp_delta}) over {args.rounds} "
              "rounds (tight RDP accounting)", file=sys.stderr)

    if args.retune_every > 0 and not args.autotune:
        print("error: --retune-every requires --autotune — the online retuner "
              "re-ranks the sweep's candidate table; without a sweep there is "
              "no table", file=sys.stderr)
        return 2

    if args.autotune:
        pinned = [
            flag for flag, engaged in (
                ("--client-chunk", args.client_chunk is not None),
                ("--rounds-per-block", args.rounds_per_block != 1),
                ("--model-shards", args.model_shards != 1),
                ("--hosts", args.hosts != 1),
            ) if engaged
        ]
        if pinned:
            # The tuner owns the swept knobs; a half-pinned sweep would silently
            # override the operator's explicit choice (or vice versa).
            print(f"error: --autotune cannot be combined with "
                  f"{', '.join(pinned)} — the cost-model sweep picks those "
                  "knobs; drop --autotune to set them by hand",
                  file=sys.stderr)
            return 2

    if args.distributed:
        # Activate jax.distributed BEFORE any backend init: afterwards
        # jax.devices() is the GLOBAL device list and --hosts can span real
        # processes.  Configuration rides the JAX_COORDINATOR_ADDRESS /
        # JAX_NUM_PROCESSES / JAX_PROCESS_ID env (or TPU-pod auto-detection)
        # — see parallel.initialize_distributed.
        from nanofed_tpu.parallel import initialize_distributed

        try:
            info = initialize_distributed()
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        print(f"# distributed: process {info['process_index']} of "
              f"{info['process_count']}", file=sys.stderr)
        if info["process_count"] > 1:
            # The Coordinator is single-controller: its host-built round
            # inputs (cohort slot arrays, weights, rng stacks) are committed
            # process-local arrays a multi-process sharding rejects at the
            # first dispatch.  Refuse up front with the working alternative
            # instead of failing round 1 with an XLA placement error.
            print(
                "error: `run` drives the single-controller Coordinator, "
                "which cannot feed a multi-process mesh (its host-built "
                "round inputs are process-local). Drive real multi-process "
                "rounds with scripts/multihost_harness.py: `federate` runs "
                "the full stack (a wire listener + ingest buffer per host "
                "draining into one cross-host psum per round), "
                "`smoke`/`bench` drive the simulated-client hierarchical "
                "program; single-process `--hosts N` exercises the same "
                "hierarchy on virtual hosts.",
                file=sys.stderr,
            )
            return 2

    if args.model_shards != 1 or args.hosts != 1:
        # Same up-front courtesy as the other invalid combinations: validate
        # against the device count HERE (the one place that forces backend
        # init) so the error is a CLI message, not a traceback —
        # run_experiment re-runs the identical shared validator.
        import jax

        from nanofed_tpu.parallel import mesh_shape_for_topology

        try:
            mesh_shape_for_topology(
                args.hosts, args.model_shards, len(jax.devices())
            )
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    metrics = run_experiment(
        model=args.model,
        num_clients=args.clients,
        num_rounds=args.rounds,
        local_epochs=args.epochs,
        batch_size=args.batch_size,
        learning_rate=args.lr,
        scheme=args.scheme,
        participation=args.participation,
        data_dir=args.data_dir,
        out_dir=args.out_dir,
        seed=args.seed,
        train_size=args.train_size,
        client_chunk=args.client_chunk,
        compute_dtype=args.dtype,
        central_privacy=central_privacy,
        lr_schedule=args.lr_schedule,
        lr_min_factor=args.lr_min_factor,
        lr_decay_every=args.lr_decay_every,
        lr_decay_gamma=args.lr_decay_gamma,
        robust_trim_k=args.robust_trim,
        robust_method=args.robust_method,
        scaffold=args.scaffold,
        telemetry_dir=args.telemetry_dir,
        rounds_per_block=args.rounds_per_block,
        client_metrics_every=args.client_metrics_every,
        model_shards=args.model_shards,
        hosts=args.hosts,
        strict=args.strict,
        profile_programs=args.profile_programs,
        autotune=args.autotune,
        retune_every=args.retune_every,
        adapter_rank=args.adapter_rank,
        adapter_alpha=args.adapter_alpha,
    )
    print(json.dumps(metrics, indent=2, default=str))
    return 0


def _cmd_sweep(args: argparse.Namespace) -> int:
    """``profile --sweep``: run the compile-only autotune sweep (nanofed_tpu.
    tuning) — lower every candidate round-program configuration, score it with
    the compiler's cost model, and print the ranked table plus the fused-
    epilogue bytes-accessed comparison.  Zero round executions; the full table
    lands as ``<out-dir>/autotune_*.json`` and the sweep result is cached under
    ``.jax_cache/`` so a repeat sweep compiles nothing."""
    from nanofed_tpu.data import federate
    from nanofed_tpu.experiments import load_datasets_for
    from nanofed_tpu.models import get_model
    from nanofed_tpu.trainer import TrainingConfig
    from nanofed_tpu.tuning import (
        AutotuneError,
        PopulationSpec,
        TuningSpace,
        autotune,
        format_candidate_table,
    )

    mdl = get_model(args.model)
    train, _ = load_datasets_for(mdl, args.data_dir, args.train_size, args.seed)
    client_data = federate(
        train, num_clients=args.clients, scheme="iid",
        batch_size=args.batch_size, seed=args.seed,
    )
    training = TrainingConfig(
        batch_size=args.batch_size, local_epochs=args.epochs,
        learning_rate=args.lr, compute_dtype=args.dtype,
    )
    pop = PopulationSpec.from_client_data(client_data)
    num_rounds = max(args.rounds_per_block, 8)
    adapter = None
    if args.adapter_rank is not None:
        from nanofed_tpu.adapters import AdapterSpec

        adapter = AdapterSpec(rank=args.adapter_rank)
    # Explicit --client-chunk / --model-shards pin that axis of the sweep to a
    # single value (the same "pin via a single-valued space" mechanism
    # Coordinator.from_autotune documents) — never silently ignored.
    pins = {}
    if args.client_chunk is not None:
        pins["client_chunks"] = (args.client_chunk,)
    if args.model_shards != 1:
        pins["model_shards"] = (args.model_shards,)
    if args.hosts != 1:
        pins["hosts"] = (args.hosts,)
    space = None
    if pins:
        import dataclasses

        import jax

        # TuningSpace.default owns the multi-process hosts-axis rule AND the
        # adapter-rank ladder, so a pin on one knob cannot silently flatten
        # the other axes.
        space = dataclasses.replace(
            TuningSpace.default(
                pop, len(jax.devices()), training.batch_size, num_rounds,
                adapter_rank=args.adapter_rank,
            ),
            **pins,
        )
    telemetry = None
    if args.telemetry_dir is not None:
        from nanofed_tpu.observability import RunTelemetry

        telemetry = RunTelemetry(args.telemetry_dir)
    try:
        result = autotune(
            mdl, pop, training,
            participation=args.participation,
            num_rounds=num_rounds,
            space=space,
            telemetry=telemetry,
            force=args.force_sweep,
            adapter=adapter,
        )
    except AutotuneError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    finally:
        if telemetry is not None:
            telemetry.close()
    if args.json:
        print(json.dumps(result.to_dict(), indent=2))
        return 0
    print(format_candidate_table(result))
    epi = result.epilogues
    if epi and "error" not in epi:
        print()
        for path in ("q8", "validated"):
            cmp = epi[path]
            pct = cmp.get("bytes_accessed_reduction_pct")
            print(
                f"{path} epilogue: fused {cmp['fused_bytes_accessed']:,.0f} "
                f"bytes vs unfused {cmp['unfused_bytes_accessed']:,.0f} bytes"
                + (f" ({pct:+.1f}% reduction)" if pct is not None else "")
            )
        print(f"epilogue basis: {epi['basis']}")
    if result.cache_hit:
        print("\n(cache hit: zero compiles this invocation)")
    if result.artifact_path:
        print(f"ranked table written to {result.artifact_path}")
    return 0


def _cmd_profile(args: argparse.Namespace) -> int:
    """Compile the round programs — single step, fused block, SCAFFOLD — WITHOUT
    running a federation, and print what the COMPILER says each costs: XLA
    ``cost_analysis`` FLOPs, peak device bytes, arithmetic intensity, and the
    roofline verdict against the platform's peaks table (see
    ``observability.profiling`` and docs/performance.md)."""
    if args.sweep:
        return _cmd_sweep(args)

    import jax

    from nanofed_tpu.data import federate
    from nanofed_tpu.experiments import load_datasets_for
    from nanofed_tpu.models import get_model
    from nanofed_tpu.observability import format_cost_table
    from nanofed_tpu.orchestration import Coordinator, CoordinatorConfig
    from nanofed_tpu.parallel import mesh_shape_for_topology
    from nanofed_tpu.trainer import TrainingConfig

    try:
        mesh_shape = mesh_shape_for_topology(
            args.hosts, args.model_shards, len(jax.devices())
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    mdl = get_model(args.model)
    train, _ = load_datasets_for(mdl, args.data_dir, args.train_size, args.seed)
    client_data = federate(
        train, num_clients=args.clients, scheme="iid",
        batch_size=args.batch_size, seed=args.seed,
    )
    training = TrainingConfig(
        batch_size=args.batch_size, local_epochs=args.epochs,
        learning_rate=args.lr, compute_dtype=args.dtype,
    )

    adapter = None
    if args.adapter_rank is not None:
        from nanofed_tpu.adapters import AdapterSpec

        adapter = AdapterSpec(rank=args.adapter_rank)

    def build(scaffold: bool, rounds_per_block: int) -> Coordinator:
        # save_metrics=False: profiling must leave no run artifacts behind
        # (telemetry lands only where --telemetry-dir points).  num_rounds
        # merely has to admit the block length — nothing ever runs.
        return Coordinator(
            model=mdl, train_data=client_data,
            config=CoordinatorConfig(
                num_rounds=max(1, rounds_per_block),
                participation_rate=args.participation,
                seed=args.seed, save_metrics=False,
                rounds_per_block=rounds_per_block,
            ),
            training=training, scaffold=scaffold,
            client_chunk=args.client_chunk, mesh_shape=mesh_shape,
            telemetry_dir=args.telemetry_dir,
            adapter=None if scaffold else adapter,
        )

    reports = []
    coordinators = [build(scaffold=False, rounds_per_block=args.rounds_per_block)]
    if not args.no_scaffold and adapter is None:
        # The SCAFFOLD program is a different ROUND program (control-variate
        # state flows through it), so it gets its own coordinator + report.
        # Skipped in adapter mode: adapter SCAFFOLD is refused by construction.
        coordinators.append(build(scaffold=True, rounds_per_block=1))
    for coord in coordinators:
        reports.extend(coord.profile_programs())
        if coord.telemetry is not None:
            coord.telemetry.close()

    if args.json:
        print(json.dumps([r.to_dict() for r in reports], indent=2))
    else:
        print(format_cost_table(reports))
    return 0 if reports else 1


def _cmd_serve(args: argparse.Namespace) -> int:
    """Host a real-network federation server (the reference's HTTPServer+Coordinator
    pair, ``examples/mnist/run_experiment.py:89-131``, as one command)."""
    import asyncio

    import jax

    from nanofed_tpu.communication import HTTPServer, NetworkCoordinator, NetworkRoundConfig
    from nanofed_tpu.models import get_model

    if args.secure and args.validate:
        # Masked vectors are unvalidatable by construction (uniform uint32); a server
        # operator must not believe norm/z-score checks run when they cannot.
        print("error: --validate cannot be combined with --secure — masked updates "
              "are indistinguishable from noise; range enforcement in secure mode "
              "comes from quantization bounds and client-side DP clipping",
              file=sys.stderr)
        return 2

    if args.dropout_tolerant and not args.secure:
        print("error: --dropout-tolerant requires --secure (it is a secure-"
              "aggregation mode)", file=sys.stderr)
        return 2

    if args.ingest_batch is not None and args.validate:
        # The coordinator refuses this too, with a traceback; say why up front
        # (per-update validation needs individual update trees, which batched
        # ingest folds into the device buffer at submit time).
        print("error: --ingest-batch cannot be combined with --validate — "
              "batched ingest folds updates into a device buffer at submit "
              "time, so per-update shape/norm/z-score checks have nothing to "
              "inspect", file=sys.stderr)
        return 2
    if args.ingest_batch is None and (
        args.ingest_capacity is not None or args.decode_workers is not None
    ):
        print("error: --ingest-capacity/--decode-workers only apply with "
              "--ingest-batch (they size the batched ingest pipeline)",
              file=sys.stderr)
        return 2

    ingest = None
    if args.ingest_batch is not None:
        from nanofed_tpu.ingest import IngestConfig

        capacity = (
            args.ingest_capacity if args.ingest_capacity is not None else 1024
        )
        try:
            ingest = IngestConfig(
                capacity=capacity,
                batch_size=min(args.ingest_batch, capacity),
                decode_workers=(
                    args.decode_workers
                    if args.decode_workers is not None else 4
                ),
            )
        except ValueError as e:
            print(f"error: invalid ingest config: {e}", file=sys.stderr)
            return 2

    if args.async_buffer is not None:
        # Sync-only cohort flags are meaningless under FedBuff (no cohort barrier:
        # aggregations fire on buffer fill, and the buffer size IS --async-buffer);
        # silently accepting them would let an operator believe a completion gate
        # or enrollment cap is active when nothing reads it — same courtesy as the
        # --staleness-window refusal below.
        explicit = [
            flag for flag, value in (
                ("--min-clients", args.min_clients),
                ("--completion-rate", args.completion_rate),
                ("--max-clients", args.max_clients),
            ) if value is not None
        ]
        if explicit:
            print(f"error: {', '.join(explicit)} only appl"
                  f"{'ies' if len(explicit) == 1 else 'y'} to synchronous cohort "
                  "rounds — asynchronous --async-buffer mode has no cohort "
                  "barrier (aggregations fire when K updates are buffered)",
                  file=sys.stderr)
            return 2
    min_clients = args.min_clients if args.min_clients is not None else 1
    completion_rate = (
        args.completion_rate if args.completion_rate is not None else 1.0
    )

    if args.max_clients is not None and not args.dropout_tolerant:
        # Only the tolerant enrollment window reads the cap; silently ignoring it
        # would let an operator believe a larger cohort can enroll when the
        # exact-cohort path caps at min_clients.
        print("error: --max-clients only applies to the --dropout-tolerant "
              "enrollment window (plain --secure cohorts are exactly "
              "--min-clients)", file=sys.stderr)
        return 2

    if args.max_clients is not None and args.max_clients < min_clients:
        print(f"error: --max-clients ({args.max_clients}) must be >= --min-clients "
              f"({min_clients}) — reaching the cap freezes the enrollment "
              "window, which would close below the minimum", file=sys.stderr)
        return 2

    if args.async_buffer is not None and (args.secure or args.validate):
        # The coordinator refuses these too, with a traceback; say why up front.
        print("error: --async-buffer cannot be combined with --secure or "
              "--validate — asynchronous aggregation mixes staleness levels "
              "these round-locked mechanisms assume away", file=sys.stderr)
        return 2
    if args.async_buffer is not None and args.async_buffer < 1:
        print("error: --async-buffer must be >= 1", file=sys.stderr)
        return 2
    if args.async_buffer is not None and args.staleness_window is not None \
            and args.staleness_window < 1:
        print("error: --staleness-window must be >= 1 in async mode",
              file=sys.stderr)
        return 2
    if args.staleness_window is not None and args.async_buffer is None:
        # Same courtesy as --max-clients: a flag only async mode reads must not
        # be silently ignored — the operator would believe a window is active.
        print("error: --staleness-window only applies with --async-buffer",
              file=sys.stderr)
        return 2

    chaos = None
    if args.chaos_plan is not None:
        from nanofed_tpu.faults import ChaosSchedule, FaultPlan

        try:
            chaos = ChaosSchedule(FaultPlan.load(args.chaos_plan))
        except (OSError, ValueError, KeyError) as e:
            print(f"error: could not load chaos plan {args.chaos_plan!r}: {e}",
                  file=sys.stderr)
            return 2

    model = get_model(args.model)
    params = model.init(jax.random.key(args.seed))
    secure = None
    if args.secure:
        from nanofed_tpu.security.secure_agg import SecureAggregationConfig

        # Dropout-tolerant mode: the privacy floor must sit BELOW the enrolled cohort
        # size or the survivor gate fails every round that has a dropout — the whole
        # point of the mode.  One eviction's worth of slack mirrors the
        # secure-federation example; operators wanting more tolerance lower
        # --completion-rate.  The Shamir threshold is NOT wired here: it must exceed
        # half the cohort that ACTUALLY enrolls (split-view defense), so the
        # coordinator derives it when the enrollment window freezes the roster and
        # announces it to clients in the roster payload — a static value computed
        # from min_clients would be wrong for any larger roster.
        floor = (
            max(2, min_clients - 1) if args.dropout_tolerant
            else min_clients
        )
        secure = SecureAggregationConfig(
            min_clients=floor,
            dropout_tolerant=args.dropout_tolerant,
        )
    validation = None
    if args.validate:
        from nanofed_tpu.security.validation import ValidationConfig

        validation = ValidationConfig(max_norm=args.max_norm)

    state_store = None
    if args.state_dir is not None:
        from nanofed_tpu.persistence.state_store import FileStateStore

        state_store = FileStateStore(args.state_dir)

    async def serve() -> list[dict]:
        server = HTTPServer(
            host=args.host, port=args.port, max_inflight=args.max_inflight,
            chaos=chaos, ingest=ingest,
        )
        await server.start()
        try:
            coordinator = NetworkCoordinator(
                server, params,
                NetworkRoundConfig(
                    num_rounds=args.rounds,
                    min_clients=min_clients,
                    min_completion_rate=completion_rate,
                    round_timeout_s=args.timeout,
                    max_clients=args.max_clients,
                    straggler_evict_after=args.evict_stragglers,
                    async_buffer_k=args.async_buffer,
                    staleness_window=(
                        args.staleness_window
                        if args.staleness_window is not None else 4
                    ),
                ),
                validation=validation,
                secure=secure,
                telemetry_dir=args.telemetry_dir,
                state_store=state_store,
                chaos=chaos,
            )
            return await coordinator.run()
        finally:
            await server.stop()

    try:
        history = asyncio.run(serve())
    except TimeoutError as e:
        # Cohort never completed enrollment: keep the JSON-output contract.
        print(json.dumps([{"status": "FAILED", "error": str(e)}]))
        return 1
    except RuntimeError as e:
        from nanofed_tpu.faults import InjectedServerCrash

        if not isinstance(e, InjectedServerCrash):
            raise
        # A planned server kill: exactly what an operator's supervisor sees.
        # Re-running the same command with the same --state-dir resumes from
        # the last completed round's checkpoint.
        print(json.dumps([{
            "status": "CRASHED", "error": str(e),
            "resume": ("re-run with the same --state-dir to resume from the "
                       "last completed round" if args.state_dir is not None
                       else "no --state-dir: a restart would begin from round 0"),
        }]))
        return 1
    print(json.dumps(history, indent=2, default=str))
    return 0 if all(h["status"] == "COMPLETED" for h in history) else 1


def _cmd_chaos_plan(args: argparse.Namespace) -> int:
    """Generate a seeded FaultPlan (chaos harness) and print or save it —
    the operator surface for drills: `serve --chaos-plan` consumes the wire/
    client kinds, the hostchaos supervisor the host kinds."""
    from nanofed_tpu.faults import FaultPlan

    try:
        plan = FaultPlan.generate(
            args.seed,
            [f"c{i}" for i in range(args.clients)],
            args.rounds,
            crash_fraction=args.crash_fraction,
            straggler_fraction=args.straggler_fraction,
            straggler_delay_s=args.straggler_delay,
            drop_fraction=args.drop_fraction,
            duplicate_fraction=args.duplicate_fraction,
            corrupt_fraction=args.corrupt_fraction,
            server_kill_round=args.server_kill_round,
            hosts=args.hosts,
            host_crash_count=args.host_crashes,
            host_stall_count=args.host_stalls,
            dcn_degrade_fraction=args.dcn_degrade_fraction,
            dcn_delay_s=args.dcn_delay,
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if not plan.events:
        print("error: the requested plan is empty — give at least one "
              "fraction/count/round", file=sys.stderr)
        return 2
    if args.out is not None:
        plan.save(args.out)
        print(f"wrote {len(plan.events)} events to {args.out}")
    else:
        print(plan.to_json())
    return 0


def _cmd_metrics_summary(args: argparse.Namespace) -> int:
    """Digest a run's ``telemetry.jsonl`` (observability subsystem): per-phase span
    durations, round outcomes, and headline counters, as one JSON document."""
    from nanofed_tpu.observability import find_latest_telemetry, summarize_telemetry

    path = find_latest_telemetry(args.path)
    if path is None:
        print(f"error: no telemetry.jsonl found under {args.path!r} — run with "
              "--telemetry-dir (or the default runs dir with metrics saving on) "
              "first", file=sys.stderr)
        return 1
    print(json.dumps(summarize_telemetry(path), indent=2))
    return 0


def _cmd_trace(args: argparse.Namespace) -> int:
    """Merge a federation's per-host ``telemetry.jsonl`` streams (observability
    subsystem) into one clock-aligned story: the per-round critical-path
    digest on stdout, and — with ``--chrome-out`` — a host-laned Chrome/
    Perfetto timeline (load it at ui.perfetto.dev or chrome://tracing)."""
    from pathlib import Path

    from nanofed_tpu.observability import (
        clock_offsets,
        federation_timeline,
        load_host_streams,
        merge_timeline,
    )

    root = Path(args.path)
    streams = load_host_streams(root)
    if not streams:
        print(f"error: no telemetry.jsonl streams found under {root} — run "
              "the federate/hostchaos harness with --telemetry-dir first",
              file=sys.stderr)
        return 1
    if args.chrome_out is not None:
        timeline = merge_timeline(streams, clock_offsets(streams))
        out = Path(args.chrome_out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(timeline))
        print(f"# wrote {len(timeline['traceEvents'])} trace events to {out}",
              file=sys.stderr)
    digest = federation_timeline(root, include_trace_map=args.trace_map)
    print(json.dumps(digest, indent=2))
    resolution = digest.get("trace_resolution") or {}
    return 0 if resolution.get("resolved", True) else 1


def _cmd_audit(args: argparse.Namespace) -> int:
    """Audit the round programs at the jaxpr/AOT level WITHOUT running a
    federation (``analysis.program_audit``): collective-schedule consistency
    across cond branches, mesh discipline (declared axes, hosts-after-clients,
    the one-cross-host-tensor budget), donation vs memory_analysis, dtype
    drift, embedded host transfers.  Exit 1 on findings."""
    from nanofed_tpu.analysis.__main__ import _ensure_virtual_devices
    from nanofed_tpu.analysis.program_audit import (
        format_audit_reports, reference_catalog,
    )

    # The reference catalog needs the standard 8-device topology; on a bare
    # CPU host this must land in XLA_FLAGS before the backend initializes.
    _ensure_virtual_devices()
    catalog = reference_catalog()
    reports = catalog.audit_all(compile=not args.no_compile)

    if args.telemetry_dir is not None:
        from nanofed_tpu.observability import RunTelemetry

        telemetry = RunTelemetry(args.telemetry_dir)
        for report in reports:
            telemetry.record("audit", **report.to_dict())
        telemetry.close()

    if args.json:
        print(json.dumps([r.to_dict() for r in reports], indent=2))
    else:
        print(format_audit_reports(reports))
    return 0 if all(r.ok for r in reports) else 1


def _cmd_loadtest(args: argparse.Namespace) -> int:
    """Run the synthetic client swarm against one (or both) serving paths and
    print the artifact (also written under --out-dir)."""
    from nanofed_tpu.loadgen import run_loadtest_comparison

    modes = (
        ("per-submit", "ingest") if args.mode == "both" else (args.mode,)
    )
    artifact = run_loadtest_comparison(
        modes=modes,
        out_dir=args.out_dir,
        telemetry_dir=args.telemetry_dir,
        clients=args.clients,
        submits_per_client=args.submits_per_client,
        model=args.model,
        async_buffer_k=args.async_buffer,
        aggregations=args.aggregations,
        ingest_capacity=args.ingest_capacity,
        decode_workers=args.decode_workers,
        max_inflight=args.max_inflight,
        arrival=args.arrival,
        arrival_rate=args.rate,
        weight_skew=args.weight_skew,
        staleness_window=args.staleness_window,
        round_timeout_s=args.timeout,
        virtual_clock=args.virtual_clock,
        seed=args.seed,
        adapter_rank=args.adapter_rank,
    )
    print(json.dumps(artifact, indent=2))
    # A loadtest that lost submits outright (not 429-shed — those retry) is a
    # failed measurement; surface it in the exit code for CI.
    ok = all(
        rec.get("failed_submits", 0) == 0
        and rec["submit_latency_s"]["count"] > 0
        for rec in artifact["modes"].values()
    )
    return 0 if ok else 1


def _cmd_tenants(args: argparse.Namespace) -> int:
    """Run the multi-tenant service harness and print the artifact (also
    written under --out-dir).  Exit 1 when an untargeted tenant lost rounds
    or submits — the isolation claim IS the exit code."""
    from nanofed_tpu.service import run_tenant_service

    chaos: bool | str | None
    if args.chaos_tenant == "none":
        chaos = None
    elif args.chaos_tenant == "first":
        chaos = True
    else:
        chaos = args.chaos_tenant
    artifact = run_tenant_service(
        tenants=args.tenants,
        rounds=args.rounds,
        clients_per_tenant=args.clients,
        submits_per_client=args.submits_per_client,
        async_buffer_k=args.async_buffer,
        arrival=args.arrival,
        arrival_rate=args.rate,
        chaos_tenant=chaos,
        chaos_seed=args.chaos_seed,
        virtual_clock=args.virtual_clock,
        sequential_baseline=not args.no_sequential,
        hbm_budget_bytes=(
            int(args.hbm_budget) if args.hbm_budget is not None else None
        ),
        seed=args.seed,
        out_dir=args.out_dir,
        telemetry_dir=args.telemetry_dir,
        tag=args.tag,
    )
    print(json.dumps(artifact, indent=2))
    ok = (
        artifact["isolation"]["zero_rounds_lost"]
        and artifact["isolation"]["zero_failed_submits"]
    )
    return 0 if ok else 1


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="nanofed-tpu", description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)

    sub.add_parser("info", help="print environment / model zoo info")

    run = sub.add_parser("run", help="run a federated training experiment")
    run.add_argument("--model", default="mnist_cnn")
    run.add_argument("--clients", type=int, default=10)
    run.add_argument("--rounds", type=int, default=2)
    run.add_argument("--epochs", type=int, default=2)
    run.add_argument("--batch-size", type=int, default=64)
    run.add_argument("--lr", type=float, default=0.1)
    run.add_argument("--scheme", default="iid", choices=["iid", "label_skew", "dirichlet"])
    run.add_argument("--participation", type=float, default=1.0)
    run.add_argument("--data-dir", default=None)
    run.add_argument("--out-dir", default="runs")
    run.add_argument("--seed", type=int, default=0)
    run.add_argument(
        "--train-size", type=int, default=None,
        help="cap the (synthetic) training set size; default = full dataset",
    )
    run.add_argument(
        "--client-chunk", type=int, default=None,
        help="train each device's resident clients in sequential chunks of this many "
        "(memory bound for clients >> chips)",
    )
    run.add_argument(
        "--dtype", default=None, choices=["bfloat16", "float32"],
        help="local-training compute dtype (mixed precision when bfloat16)",
    )
    run.add_argument(
        "--adapter-rank", type=int, default=None, metavar="R",
        help="parameter-efficient federation (nanofed_tpu.adapters): freeze "
        "the base model device-resident and federate only rank-R LoRA A/B "
        "deltas on the 2-D kernel leaves — training, aggregation, "
        "checkpoints, and wire payloads are adapter-sized (the full model "
        "only materializes at eval/versioned-model merges). Composes with "
        "--model-shards (the frozen base shards over the model axis) and "
        "with --autotune (R seeds the tuner's rank-ladder sweep)",
    )
    run.add_argument(
        "--adapter-alpha", type=float, default=None,
        help="LoRA alpha: the merged delta is (alpha/rank) * A @ B "
        "(default: alpha = rank, i.e. scale 1.0)",
    )
    run.add_argument(
        "--model-shards", type=int, default=1, metavar="N",
        help="split params + server optimizer state N ways over a second "
        "'model' mesh axis (FSDP-style; devices arrange as a (devices/N, N) "
        "clients x model mesh). Each leaf's largest divisible dimension is "
        "sharded; the model never materializes replicated between rounds. "
        "N must divide the device count; 1 = classic replicated layout",
    )
    run.add_argument(
        "--hosts", type=int, default=1, metavar="H",
        help="add a third 'hosts' mesh axis: devices arrange as an (H, "
        "devices/(H*model-shards), model-shards) hosts x clients x model "
        "mesh and the FedAvg reduce becomes HIERARCHICAL — host-local psum "
        "over clients (ICI), then ONE cross-host psum over hosts (DCN), so "
        "inter-host traffic per round is one model-sized tensor. Cohorts "
        "sample host-locally. Single-process this slices virtual hosts over "
        "the local devices; combine with --distributed on a real multi-host "
        "cluster. H * model-shards must divide the device count",
    )
    run.add_argument(
        "--distributed", action="store_true",
        help="call jax.distributed.initialize before anything (multi-host "
        "bring-up: JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/JAX_PROCESS_ID "
        "env, or TPU-pod auto-detection; CPU clusters get gloo collectives) "
        "so jax.devices() is the GLOBAL device list. Single-process "
        "environments make this a documented no-op; an ACTUAL multi-process "
        "environment is refused here — the Coordinator is single-controller, "
        "and scripts/multihost_harness.py (federate|smoke|bench) is the "
        "end-to-end multi-process driver",
    )
    run.add_argument(
        "--rounds-per-block", type=int, default=1,
        help="fuse this many rounds into ONE device program (lax.scan inside a "
        "single jit): no Python dispatch, no block_until_ready, no metrics "
        "transfer between fused rounds — host sync only at block boundaries. "
        "Falls back to single rounds for --scaffold/--robust-*/--dp-epsilon",
    )
    run.add_argument(
        "--client-metrics-every", type=int, default=1,
        help="dump per-client metric detail (weights/losses/update norms) into the "
        "round metrics JSON every N rounds; 0 = never. At 1000 clients each dump "
        "is a 1000-element device->host conversion",
    )
    run.add_argument(
        "--lr-schedule", default="constant",
        choices=["constant", "cosine", "linear", "step"],
        help="per-round client-lr schedule; rides a traced scalar through the "
        "compiled round step, so decaying costs zero recompiles",
    )
    run.add_argument("--lr-min-factor", type=float, default=0.0,
                     help="terminal lr fraction for cosine/linear; floor for step")
    run.add_argument("--lr-decay-every", type=int, default=10,
                     help="step schedule: rounds between decays")
    run.add_argument("--lr-decay-gamma", type=float, default=0.5,
                     help="step schedule: multiplier per decay")
    run.add_argument(
        "--scaffold", action="store_true",
        help="SCAFFOLD control-variate correction (Karimireddy et al. 2020): "
        "removes non-IID client drift at its source; shines under partial "
        "participation. Requires plain SGD (no momentum) and refuses --dp-epsilon "
        "and --robust-trim (each would bias the control estimate)")
    run.add_argument(
        "--robust-trim", type=int, default=None, metavar="K",
        help="Byzantine-robust aggregation: coordinate-wise trimmed mean dropping "
        "the K extremes per side (tolerates K colluding clients; unweighted over "
        "the kept ranks; incompatible with --dp-epsilon)",
    )
    run.add_argument(
        "--robust-method", default=None,
        choices=["trimmed_mean", "median", "multi_krum"],
        help="robust estimator: trimmed_mean (default when --robust-trim is set), "
        "median (knob-free, tolerates any Byzantine minority), or multi_krum "
        "(whole-update selection, --robust-trim acts as f); incompatible "
        "with --dp-epsilon",
    )
    run.add_argument(
        "--dp-epsilon", type=float, default=None,
        help="enable central DP-FedAvg with noise CALIBRATED to this epsilon budget "
        "over the run's rounds (tight RDP accounting); spend is reported per round "
        "and in the summary",
    )
    run.add_argument("--dp-delta", type=float, default=1e-5)
    run.add_argument("--dp-clip", type=float, default=1.0,
                     help="central-DP per-update clip norm C")
    run.add_argument(
        "--telemetry-dir", default=None,
        help="write the run's telemetry.jsonl (phase spans + round records + final "
        "metrics snapshot) here instead of the default <out-dir>; read it back "
        "with `nanofed-tpu metrics-summary`",
    )
    run.add_argument(
        "--strict", action="store_true",
        help="strict execution mode (analysis subsystem): contract-check the "
        "round program via jax.eval_shape at build time and run every device "
        "dispatch under jax.transfer_guard('disallow') — an implicit host "
        "transfer in the hot path raises instead of silently serializing it",
    )
    run.add_argument(
        "--autotune", action="store_true",
        help="let the COMPILER's cost model pick client_chunk / "
        "rounds-per-block / mesh shape / batch size (nanofed_tpu.tuning): a "
        "compile-only sweep lowers every candidate round program via AOT "
        "cost_analysis/memory_analysis — ZERO round executions before the "
        "first real round — scores by achievable roofline walltime on TPU "
        "(bytes-accessed ordering on CPU, basis stated), rejects candidates "
        "over the device HBM budget, writes the ranked table as "
        "<out-dir>/autotune_*.json, and caches the result under .jax_cache/ "
        "so repeat runs compile nothing. Incompatible with explicit "
        "--client-chunk/--rounds-per-block/--model-shards",
    )
    run.add_argument(
        "--retune-every", type=int, default=0, metavar="N",
        help="close the tuning loop online (requires --autotune): every N "
        "completed rounds, re-rank the sweep's candidate table by the "
        "walltimes the run actually realized (plus the device-occupancy "
        "gauge) and hot-swap the live round program at the next block "
        "boundary when measurements beat the AOT pick by more than the "
        "retuner's hysteresis. Every decision lands as a `retune` telemetry "
        "record, the summary carries a `retunes` block, and the measured "
        "numbers are written back into the autotune cache entry at run end. "
        "0 = off",
    )
    run.add_argument(
        "--profile-programs", action="store_true",
        help="profile every built round program at construction (XLA "
        "cost_analysis/memory_analysis + roofline verdict): reports land in "
        "the summary, as nanofed_program_* gauges, and as program_profile "
        "telemetry records. Pays a second XLA compile unless the persistent "
        "compilation cache is warm; `nanofed-tpu profile` does this without "
        "running a federation at all",
    )

    serve = sub.add_parser(
        "serve", help="host a real-network federation server (binary HTTP transport)"
    )
    serve.add_argument("--model", default="mnist_cnn")
    serve.add_argument("--host", default="127.0.0.1")
    serve.add_argument("--port", type=int, default=8080)
    serve.add_argument("--rounds", type=int, default=2)
    serve.add_argument(
        "--min-clients", type=int, default=None,
        help="synchronous rounds: cohort size to wait for (default 1); "
        "incompatible with --async-buffer")
    serve.add_argument(
        "--completion-rate", type=float, default=None,
        help="synchronous rounds: fraction of --min-clients required before "
        "aggregating (default 1.0); incompatible with --async-buffer")
    serve.add_argument("--timeout", type=float, default=300.0)
    serve.add_argument("--seed", type=int, default=0)
    serve.add_argument(
        "--secure", action="store_true",
        help="secure-aggregation rounds: clients enroll via /secagg and submit "
        "pairwise-masked updates; the server only ever sees the cohort sum",
    )
    serve.add_argument(
        "--dropout-tolerant", action="store_true",
        help="with --secure: Bonawitz double-masking — per-round ephemeral secrets, "
        "Shamir share recovery of dropped clients' masks, survivor-only FedAvg. "
        "min_clients becomes a true minimum: enrollment stays open for stragglers "
        "and the Shamir threshold is derived from the frozen roster (> n/2)",
    )
    serve.add_argument(
        "--max-clients", type=int, default=None,
        help="with --dropout-tolerant: cap the enrollment window (reaching it "
        "freezes the cohort immediately); default: unbounded until the roster "
        "has been quiet for the grace period",
    )
    serve.add_argument(
        "--validate", action="store_true",
        help="validate every drained update (shape / finite / norm / cohort z-score); "
        "invalid clients are dropped from the round",
    )
    serve.add_argument(
        "--async-buffer", type=int, default=None, metavar="K",
        help="asynchronous FedBuff mode: aggregate whenever K updates are "
        "buffered instead of waiting for a synchronized cohort; --rounds then "
        "counts aggregations. Incompatible with --secure/--validate")
    serve.add_argument(
        "--staleness-window", type=int, default=None,
        help="async mode only: accept updates based on any of the last W "
        "published versions (default 4; staleness discounted as (1+s)^-0.5)")
    serve.add_argument("--max-norm", type=float, default=100.0,
                       help="per-leaf norm cap for --validate")
    serve.add_argument(
        "--max-inflight", type=int, default=None, metavar="N",
        help="admission control: at most N update bodies in the read/decode "
        "pipeline at once; excess submits get an immediate 429 + Retry-After "
        "(clients with a RetryPolicy back off and re-send). Default: unbounded",
    )
    serve.add_argument(
        "--ingest-batch", type=int, default=None, metavar="K",
        help="batched device-resident ingest (nanofed_tpu.ingest): decoded "
        "deltas accumulate into a preallocated on-device buffer and ONE "
        "jit-compiled batched reduce fires per drain instead of one "
        "aggregation per client; npz decode moves into a bounded worker "
        "pool and a full buffer answers 429 + Retry-After. K is the "
        "EXPECTED drain size: the flush programs for batches up to K "
        "pre-compile at startup so no realistic drain compiles on the "
        "event loop (drain granularity itself is --async-buffer in FedBuff "
        "mode, the round barrier in sync mode). Incompatible with "
        "--validate",
    )
    serve.add_argument(
        "--ingest-capacity", type=int, default=None, metavar="N",
        help="with --ingest-batch: buffer slots (bounds device memory at "
        "N * params * 4 bytes and is the 429 backpressure point; "
        "default 1024)",
    )
    serve.add_argument(
        "--decode-workers", type=int, default=None, metavar="N",
        help="with --ingest-batch: bounded decode pool size (default 4) — "
        "the event loop never decompresses an update body itself",
    )
    serve.add_argument(
        "--evict-stragglers", type=int, default=0, metavar="K",
        help="sync rounds: evict a previously-seen client after K consecutive "
        "missed rounds, shrinking the round barrier (completion-rate graceful "
        "degradation) so one dead client stops costing every round a timeout; "
        "0 = never (default)",
    )
    serve.add_argument(
        "--state-dir", default=None, metavar="DIR",
        help="crash recovery: checkpoint every completed round's params + "
        "engine state here, and RESUME from the latest checkpoint at startup "
        "— a killed server re-run with the same --state-dir continues where "
        "it left off (clients re-sync via retried fetches / stale-round 400s)",
    )
    serve.add_argument(
        "--chaos-plan", default=None, metavar="PLAN.json",
        help="fault injection: load a seeded FaultPlan (nanofed_tpu.faults) "
        "and apply its wire faults (drop/ack_drop/delay) at the server "
        "boundary and its server_kill events in the round loop — for drills "
        "proving a deployment's retry/admission/recovery configuration "
        "actually survives the plan",
    )
    serve.add_argument(
        "--telemetry-dir", default=None,
        help="write this server run's telemetry.jsonl (round/phase spans + round "
        "records) here; live metrics are always scrapable at GET /metrics",
    )

    chaos_plan = sub.add_parser(
        "chaos-plan",
        help="generate a seeded FaultPlan JSON (nanofed_tpu.faults) — client "
        "wire faults and/or host-targeted mesh faults (host_crash/host_stall/"
        "dcn_degrade) — consumable by `serve --chaos-plan` and the multihost "
        "harness's hostchaos supervisor",
    )
    chaos_plan.add_argument("--seed", type=int, default=0)
    chaos_plan.add_argument("--clients", type=int, default=0,
                            help="client population the *_fraction draws "
                            "sample from (client ids are c0..cN-1)")
    chaos_plan.add_argument("--rounds", type=int, default=10)
    chaos_plan.add_argument("--crash-fraction", type=float, default=0.0)
    chaos_plan.add_argument("--straggler-fraction", type=float, default=0.0)
    chaos_plan.add_argument("--straggler-delay", type=float, default=1.0)
    chaos_plan.add_argument("--drop-fraction", type=float, default=0.0)
    chaos_plan.add_argument("--duplicate-fraction", type=float, default=0.0)
    chaos_plan.add_argument("--corrupt-fraction", type=float, default=0.0)
    chaos_plan.add_argument("--server-kill-round", type=int, default=None)
    chaos_plan.add_argument("--hosts", type=int, default=0,
                            help="hosts-axis size the host faults draw over")
    chaos_plan.add_argument("--host-crashes", type=int, default=0)
    chaos_plan.add_argument("--host-stalls", type=int, default=0)
    chaos_plan.add_argument("--dcn-degrade-fraction", type=float, default=0.0)
    chaos_plan.add_argument("--dcn-delay", type=float, default=0.5,
                            metavar="SECONDS")
    chaos_plan.add_argument("--out", default=None, metavar="PLAN.json",
                            help="write the plan here (default: stdout)")

    summary = sub.add_parser(
        "metrics-summary",
        help="digest a run's telemetry.jsonl: per-phase durations, round outcomes, "
        "headline counters",
    )
    summary.add_argument(
        "path", nargs="?", default="runs",
        help="a telemetry.jsonl, a run dir containing one, or a tree to search "
        "for the most recent one (default: runs)",
    )

    trace = sub.add_parser(
        "trace",
        help="merge a federation's per-host telemetry.jsonl streams into one "
        "clock-aligned timeline: per-round critical-path digest + trace "
        "resolution on stdout, optional Chrome/Perfetto trace file",
    )
    trace.add_argument(
        "path", nargs="?", default="runs",
        help="the --telemetry-dir of a federate/hostchaos run (per-host "
        "streams live in host_*/ subdirs; default: runs)",
    )
    trace.add_argument(
        "--chrome-out", default=None, metavar="TRACE.json",
        help="also write the merged host-laned Chrome trace_event file here "
        "(open at ui.perfetto.dev or chrome://tracing)",
    )
    trace.add_argument(
        "--trace-map", action="store_true",
        help="include the full per-trace consumption map in the JSON digest "
        "(one entry per accepted submit; large)",
    )

    profile = sub.add_parser(
        "profile",
        help="compile the round programs (single step, fused block, SCAFFOLD) "
        "WITHOUT running a federation and print the compiler's cost/roofline "
        "table: XLA cost_analysis FLOPs, peak device bytes, arithmetic "
        "intensity, compute- vs memory-bound verdict",
    )
    profile.add_argument("--model", default="mnist_cnn")
    profile.add_argument("--clients", type=int, default=16)
    profile.add_argument("--epochs", type=int, default=1)
    profile.add_argument("--batch-size", type=int, default=64)
    profile.add_argument("--lr", type=float, default=0.1)
    profile.add_argument("--seed", type=int, default=0)
    profile.add_argument("--data-dir", default=None)
    profile.add_argument(
        "--train-size", type=int, default=1024,
        help="training-set size (synthetic unless --data-dir has real data); "
        "only shapes matter — nothing executes",
    )
    profile.add_argument(
        "--participation", type=float, default=1.0,
        help="cohort participation rate: < 1 profiles the cohort-gathered "
        "program the real rounds would dispatch",
    )
    profile.add_argument(
        "--rounds-per-block", type=int, default=4,
        help="also profile the fused R-round block program at this R "
        "(1 = single-step only)",
    )
    profile.add_argument("--client-chunk", type=int, default=None)
    profile.add_argument(
        "--adapter-rank", type=int, default=None, metavar="R",
        help="with --sweep: sweep the parameter-efficient axis — every "
        "candidate lowers the frozen-base LoRA round program, the rank "
        "ladder {R/2, R, 2R} joins the space, and the epilogue cost table "
        "is sized to the adapter payload; the ranked table grows a 'lora' "
        "column",
    )
    profile.add_argument("--model-shards", type=int, default=1, metavar="N",
                         help="profile the 2-D clients x model (FSDP) programs")
    profile.add_argument(
        "--hosts", type=int, default=1, metavar="H",
        help="profile the 3-axis hosts x clients x model programs "
        "(hierarchical aggregation; virtual hosts over the local devices)",
    )
    profile.add_argument("--dtype", default=None, choices=["bfloat16", "float32"])
    profile.add_argument("--no-scaffold", action="store_true",
                         help="skip the SCAFFOLD round program")
    profile.add_argument(
        "--sweep", action="store_true",
        help="run the compile-only autotune sweep instead (nanofed_tpu."
        "tuning): rank every candidate (client_chunk x rounds_per_block x "
        "mesh shape x batch size) by the compiler's cost model, print the "
        "ranked table + the fused-epilogue bytes-accessed comparison, and "
        "write <out-dir>/autotune_*.json; zero round executions. Explicit "
        "--client-chunk/--model-shards pin that axis to the given value",
    )
    profile.add_argument(
        "--force-sweep", action="store_true",
        help="with --sweep: ignore the cached sweep result and re-compile "
        "every candidate",
    )
    profile.add_argument("--json", action="store_true",
                         help="full report dicts as JSON instead of the table")
    profile.add_argument(
        "--telemetry-dir", default=None,
        help="also append program_profile records to a telemetry.jsonl here "
        "(read back with `nanofed-tpu metrics-summary`)",
    )

    audit = sub.add_parser(
        "audit",
        help="audit the round programs at the jaxpr/AOT level WITHOUT running "
        "a federation: collective schedules (cond-branch consistency), mesh "
        "discipline (declared axes, hosts-after-clients hierarchy, cross-host "
        "byte budget), donation vs memory_analysis, dtype drift, embedded "
        "host transfers — across single-step, fused-block, SCAFFOLD, 2-D "
        "FSDP, 3-axis hierarchical, and adapter variants; exit 1 on findings",
    )
    audit.add_argument(
        "--no-compile", action="store_true",
        help="trace-only audit: skip the AOT compile (and with it the "
        "donation check) — faster on a cold compile cache",
    )
    audit.add_argument("--json", action="store_true",
                       help="full report dicts as JSON instead of the table")
    audit.add_argument(
        "--telemetry-dir", default=None,
        help="also append an `audit` record per program to a telemetry.jsonl "
        "here (read back with `nanofed-tpu metrics-summary`)",
    )

    loadtest = sub.add_parser(
        "loadtest",
        help="synthetic client swarm load harness (nanofed_tpu.loadgen): "
        "drive N concurrent submits against an in-process federation "
        "server and record p50/p99 submit latency, rounds/sec, and "
        "429/retry counts as a runs/loadtest_*.json artifact",
    )
    loadtest.add_argument("--clients", type=int, default=10_000)
    loadtest.add_argument("--submits-per-client", type=int, default=1)
    loadtest.add_argument(
        "--mode", default="both", choices=["per-submit", "ingest", "both"],
        help="serving path under test; 'both' runs the per-submit and "
        "batched-ingest paths on identical traffic and records the "
        "rounds/sec ratio",
    )
    loadtest.add_argument("--model", default="digits_mlp")
    loadtest.add_argument(
        "--adapter-rank", type=int, default=None, metavar="R",
        help="parameter-efficient wire mode (nanofed_tpu.adapters): the "
        "federated tree — model fetches, canned submit payloads, the "
        "engine's aggregation — is the rank-R LoRA adapter tree; the "
        "artifact records measured full-vs-adapter payload bytes",
    )
    loadtest.add_argument(
        "--async-buffer", type=int, default=64, metavar="K",
        help="FedBuff aggregation size K (the round engine runs in async "
        "mode: aggregations fire on buffer fill)",
    )
    loadtest.add_argument(
        "--aggregations", type=int, default=None,
        help="aggregations to run (default: total submits // K)",
    )
    loadtest.add_argument("--ingest-capacity", type=int, default=1024)
    loadtest.add_argument("--decode-workers", type=int, default=4)
    loadtest.add_argument("--max-inflight", type=int, default=512)
    loadtest.add_argument(
        "--arrival", default="poisson", choices=["poisson", "uniform", "burst"],
    )
    loadtest.add_argument(
        "--rate", type=float, default=2000.0,
        help="mean arrival rate, submits/sec (poisson & uniform)",
    )
    loadtest.add_argument(
        "--weight-skew", type=float, default=0.0,
        help="lognormal sigma over reported num_samples (0 = homogeneous)",
    )
    loadtest.add_argument("--staleness-window", type=int, default=4)
    loadtest.add_argument("--timeout", type=float, default=120.0,
                          help="per-aggregation round timeout (seconds)")
    loadtest.add_argument(
        "--virtual-clock", action="store_true",
        help="run arrivals/backoffs on a VirtualClock (deterministic, "
        "seconds of real time — what the CI smoke uses)",
    )
    loadtest.add_argument("--seed", type=int, default=0)
    loadtest.add_argument("--out-dir", default="runs")
    loadtest.add_argument(
        "--telemetry-dir", default=None,
        help="also append per-mode 'loadtest' telemetry records here "
        "(read back with `nanofed-tpu metrics-summary`)",
    )

    tenants = sub.add_parser(
        "tenants",
        help="multi-tenant federation service harness (nanofed_tpu.service): "
        "run N concurrent tenant jobs (distinct models/algorithms) over one "
        "device pool behind one listener, drive a swarm per tenant, target a "
        "chaos storm at one tenant, and record aggregate rounds/sec vs the "
        "sequential baseline + per-tenant p99 + the isolation proof as a "
        "runs/tenants_*.json artifact",
    )
    tenants.add_argument("--tenants", type=int, default=3,
                         help="concurrent tenant jobs (models/algorithms "
                         "cycle through the default roster)")
    tenants.add_argument("--rounds", type=int, default=4,
                         help="aggregations (fedbuff) / rounds (fedavg) per "
                         "tenant")
    tenants.add_argument("--clients", type=int, default=40,
                         help="swarm clients per tenant")
    tenants.add_argument("--submits-per-client", type=int, default=2)
    tenants.add_argument("--async-buffer", type=int, default=16, metavar="K")
    tenants.add_argument(
        "--arrival", default="poisson", choices=["poisson", "uniform", "burst"],
    )
    tenants.add_argument("--rate", type=float, default=500.0,
                         help="mean arrival rate, submits/sec per tenant")
    tenants.add_argument(
        "--chaos-tenant", default="first",
        help="tenant the wire-fault storm targets: a name, 'first' "
        "(default), or 'none' for a clean run",
    )
    tenants.add_argument("--chaos-seed", type=int, default=7)
    tenants.add_argument(
        "--no-sequential", action="store_true",
        help="skip the one-tenant-at-a-time baseline runs",
    )
    tenants.add_argument(
        "--virtual-clock", action="store_true",
        help="run arrivals/backoffs/timeouts on a VirtualClock "
        "(deterministic, seconds of real time — what the CI smoke uses)",
    )
    tenants.add_argument(
        "--hbm-budget", type=float, default=None, metavar="BYTES",
        help="per-device memory budget for the scheduler's admission "
        "bin-pack (default: the autotuner's provenance chain — env, "
        "runtime bytes_limit, published HBM table, else unbounded)",
    )
    tenants.add_argument("--seed", type=int, default=0)
    tenants.add_argument("--tag", default=None,
                         help="artifact name suffix (default: UTC stamp)")
    tenants.add_argument("--out-dir", default="runs")
    tenants.add_argument(
        "--telemetry-dir", default=None,
        help="also append per-tenant 'tenant' telemetry records here "
        "(read back with `nanofed-tpu metrics-summary`)",
    )

    args = parser.parse_args(argv)
    if args.cmd in ("run", "loadtest"):
        # The commands that compile round programs keep them in the one
        # persistent cache (utils.platform.compilation_cache_dir).
        from nanofed_tpu.utils.platform import enable_compilation_cache

        enable_compilation_cache()
    if args.cmd == "info":
        return _cmd_info(args)
    if args.cmd == "serve":
        return _cmd_serve(args)
    if args.cmd == "chaos-plan":
        return _cmd_chaos_plan(args)
    if args.cmd == "metrics-summary":
        return _cmd_metrics_summary(args)
    if args.cmd == "trace":
        return _cmd_trace(args)
    if args.cmd == "profile":
        return _cmd_profile(args)
    if args.cmd == "audit":
        return _cmd_audit(args)
    if args.cmd == "loadtest":
        return _cmd_loadtest(args)
    if args.cmd == "tenants":
        return _cmd_tenants(args)
    return _cmd_run(args)


if __name__ == "__main__":
    sys.exit(main())
