"""Multi-process pod-scale federation harness (ROADMAP item 1).

One file, two jobs, both driven by REAL ``jax.distributed`` processes on the
CPU backend (gloo collectives — see ``parallel.mesh.initialize_distributed``),
so the whole hosts-axis path is testable without a pod:

* ``smoke`` (``make multihost-smoke``, the non-blocking CI job): a 2-process
  run of the HIERARCHICAL 3-axis round program — per-host data sharding via
  :func:`~nanofed_tpu.parallel.shard_host_local_data` (no process ever holds
  the full population), host-local ``psum`` over ``clients`` then ONE
  cross-host ``psum`` over ``hosts`` — asserted for trajectory parity
  (per-round losses AND final params, float tolerance) against a
  single-process 1-D mesh over the same virtual device count running the
  byte-identical workload.

* ``bench``: the scale jump — ``--clients 100000`` (default) streamed through
  ``client_chunk`` chunking x multi-process, producing a
  ``runs/multihost_*.json`` artifact with rounds/sec and clients/sec plus the
  topology block (``process_count``/``hosts``/``mesh_shape``) the BENCH
  conventions require.  The basis is stated honestly: virtual CPU devices and
  gloo-over-loopback measure the PROGRAM (hierarchical collectives, chunked
  streaming, multi-controller dispatch) at population scale, not TPU silicon.

* ``hostchaos`` (``make hostchaos-smoke``): the host fault-tolerance drill.
  A SUPERVISOR spawns the worker mesh under a seeded fault plan
  (``host_crash``/``host_stall``/``dcn_degrade`` — ``nanofed_tpu.faults``),
  the workers heartbeat (``parallel.resilience.Heartbeat``), bracket every
  cross-host dispatch with a ``CollectiveWatchdog`` deadline, and checkpoint
  at block boundaries under generation numbers with commit markers
  (``persistence.GenerationStore``).  When the plan kills or stalls a host,
  the supervisor detects it (process exit / frozen heartbeat), kills and
  REAPS every survivor, re-forms the mesh over the surviving host set (the
  shrunk hosts axis, cohort quotas, and data sharding all re-derive through
  ``MeshLayout``), resumes from the newest generation committed by ALL
  participants (at most one block of rounds re-run), and optionally lets the
  failed host REJOIN at the next generation boundary.  The run ends with a
  ``runs/hostchaos_*.json`` artifact: MTTR, rounds lost, post-recovery loss
  parity vs an unfailed run on the same shrunk mesh from the same recovery
  point, and a zero-orphans check over every pid ever spawned.

* ``federate`` (``make federation-smoke``): ONE STACK — the wire tier drains
  straight into the hierarchical mesh reduce.  Every mesh host runs an
  ``HTTPServer`` + ``DeviceIngestBuffer`` front end; the ``loadgen`` swarm
  drives the wire population against the listeners (VirtualClock arrival
  schedule, real sockets, real submit latencies); each round is host-local
  partial drains (the buffer's batched ``coefs @ buffer`` reduce, drained
  UNNORMALIZED) joined by ONE cross-host psum
  (``communication.federation.build_cross_host_row_psum`` on a hosts-only
  mesh — one device per process, one gloo stream per beat — with the FedAvg
  apply landing host-side via ``apply_summed_row``), with a stop-vote
  control lane riding the same collective so hosts reach round-count
  consensus without a side channel.  With ``--kill-round`` a seeded plan
  crashes one host mid-campaign: its wire clients reroute to survivors LIVE
  (retry/rotation/dedup), the supervisor re-forms the mesh over the
  survivors from the newest committed generation, re-drives the dead host's
  population, and asserts ZERO lost submits across the whole campaign.
  Artifact: ``runs/federation_*.json``.

Launcher (default entry) spawns the worker processes of itself; workers rendez-
vous through ``jax.distributed`` on a loopback coordinator.  Every knob rides
argv so the launcher and workers cannot drift.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # the hostchaos supervisor imports nanofed_tpu
    sys.path.insert(0, str(REPO))

SMOKE_TOL = 5e-5  # hierarchical vs flat psum: re-association only (~1e-7 seen)

#: Worker exit code when the collective watchdog (or a gloo/distributed error)
#: surfaced a PEER's failure — distinct from the planned victim's own death
#: (HOST_CRASH_RC, imported so the supervisor's rc match can never drift from
#: what the injector actually exits with; host_injector is pure stdlib).
PEER_FAILURE_RC = 32
from nanofed_tpu.faults.host_injector import (  # noqa: E402
    HOST_CRASH_EXIT_CODE as HOST_CRASH_RC,
)


def _worker_env(args: argparse.Namespace, process_id: int) -> dict[str, str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices_per_process}"
    )
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
    env["NANOFED_MH_PROCESS_ID"] = str(process_id)
    return env


def client_rows(client_ids, capacity: int, feat: tuple[int, ...], seed: int):
    """Deterministic synthetic data for a RANGE of global client ids — the same
    rows regardless of which process (or how many) materializes them, which is
    what makes the multi-process run byte-comparable to the single-process
    reference.  Linearly-separable-ish classes so a few rounds visibly learn."""
    import numpy as np

    xs, ys = [], []
    for cid in client_ids:
        rng = np.random.default_rng(seed * 1_000_003 + int(cid))
        y = rng.integers(0, 10, size=capacity)
        x = rng.normal(0, 1, size=(capacity, *feat)).astype(np.float32)
        x[..., 0, 0, 0] += y  # class signal in one coordinate
        xs.append(x.astype(np.float32))
        ys.append(y.astype(np.int32))
    mask = np.ones((len(xs), capacity), np.float32)
    return np.stack(xs), np.stack(ys), mask


def run_worker(args: argparse.Namespace) -> int:
    """One jax.distributed process: build the hosts-axis mesh, shard THIS
    host's client rows, run the round program, report through files."""
    t0 = time.time()
    import jax

    from nanofed_tpu.parallel import initialize_distributed

    if args.num_processes > 1:
        info = initialize_distributed(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    else:
        info = {"process_index": 0, "process_count": 1}

    import jax.numpy as jnp
    import numpy as np

    from nanofed_tpu.aggregation import compute_weights, fedavg_strategy
    from nanofed_tpu.core.types import ClientData
    from nanofed_tpu.models import get_model
    from nanofed_tpu.parallel import (
        build_round_step,
        client_shard_count,
        host_client_slice,
        init_server_state,
        make_mesh,
        mesh_shape,
        pad_client_count,
        param_sharding,
        shard_host_local_data,
    )
    from nanofed_tpu.trainer import TrainingConfig, stack_rngs

    devices = jax.devices()
    pid = info["process_index"]

    def log(msg: str) -> None:
        print(f"[{time.time() - t0:6.1f}s p{pid}] {msg}", file=sys.stderr,
              flush=True)

    log(f"up: {len(devices)} global devices across "
        f"{info['process_count']} process(es)")

    if args.job == "federate":
        return _federate_worker(args, info, log)

    if args.hosts > 1:
        shape = (args.hosts, len(devices) // args.hosts, 1)
    else:
        shape = None  # the 1-D reference mesh
    mesh = make_mesh(shape=shape)
    n_shards = client_shard_count(mesh)

    model = get_model(args.model)
    feat = tuple(model.input_shape)
    padded = pad_client_count(args.clients, n_shards)
    start, stop = host_client_slice(padded, mesh)
    log(f"mesh {mesh_shape(mesh)}: padded {padded} clients, "
        f"this process holds rows [{start}, {stop})")

    # Per-host data sharding: ONLY this process's rows ever materialize here.
    ids = np.arange(start, stop)
    x, y, mask = client_rows(ids, args.capacity, feat, args.seed)
    mask[ids >= args.clients] = 0.0  # padding rows carry zero weight
    local = ClientData(x=x, y=y, mask=mask)
    num_samples_local = mask.sum(axis=1)
    data = shard_host_local_data(local, mesh, padded)
    log(f"data resident: {x.nbytes / 1e6:.1f} MB/process on device")

    training = TrainingConfig(
        batch_size=args.batch_size, local_epochs=1, learning_rate=0.1
    )
    strategy = fedavg_strategy()
    params_host = model.init(jax.random.key(args.seed))
    sos_host = init_server_state(strategy, params_host)
    start_round = 0
    if args.job == "hostchaos" and args.resume:
        from nanofed_tpu.persistence import GenerationStore

        rec = GenerationStore(args.ckpt_dir).latest_complete()
        if rec is not None:
            # Newest generation committed by ALL its participants: the only
            # legal multi-host recovery point (at-most-one-block loss).
            params_host, sos_host = rec.params, rec.server_state
            start_round = rec.round_number
            log(f"resumed generation {rec.generation} at round {start_round} "
                f"(committed by hosts {list(rec.hosts)})")
        else:
            log("resume requested but no complete generation yet — fresh start")
    params = jax.device_put(params_host, param_sharding(mesh, params_host))
    sos = jax.device_put(sos_host, param_sharding(mesh, sos_host))
    step = build_round_step(
        model.apply, training, mesh, strategy,
        client_chunk=args.client_chunk, params_like=params,
        donate=True,
    )

    # Replicated round inputs (weights, per-round key stacks) are pure
    # functions of (client id, seed, round), so every process COMPUTES them as
    # a tiny jitted program with replicated out_shardings instead of shipping
    # host arrays — a committed process-local array cannot be device_put onto
    # a multi-process sharding, and nothing needs to move anyway.
    del num_samples_local  # identical info rides the computed weights below
    from functools import partial

    from nanofed_tpu.parallel import replicated_sharding

    repl = replicated_sharding(mesh)
    weights = jax.jit(
        lambda: compute_weights(jnp.where(
            jnp.arange(padded) < args.clients, float(args.capacity), 0.0
        )),
        out_shardings=repl,
    )()

    # r rides as a TRACED scalar (fold_in accepts one): one compile serves
    # every round — static_argnums here would recompile the key stack per r,
    # polluting the timed round walltimes.
    @partial(jax.jit, out_shardings=repl)
    def round_rngs(r):
        return stack_rngs(
            jax.random.fold_in(jax.random.key(args.seed), r), padded
        )

    if args.job == "hostchaos":
        return _hostchaos_rounds(
            args, info, log, mesh, step, params, sos, data, weights,
            round_rngs, start_round,
        )

    losses: list[float] = []
    round_times: list[float] = []
    for r in range(args.rounds + 1):  # +1: round 0 pays the compile (warm-up)
        rngs = round_rngs(r)
        t = time.perf_counter()
        res = step(params, sos, data, weights, rngs)
        params, sos = res.params, res.server_opt_state
        jax.block_until_ready(params)
        dt = time.perf_counter() - t
        loss = float(res.metrics["loss"])
        losses.append(loss)
        if r > 0:
            round_times.append(dt)
        log(f"round {r}: loss={loss:.5f} ({dt:.2f}s"
            + (", incl. compile)" if r == 0 else ")"))

    result = {
        "mode": args.job,
        "losses": losses,
        "round_times_s": [round(x, 4) for x in round_times],
        "topology": {
            "process_count": info["process_count"],
            "hosts": args.hosts,
            "devices": len(devices),
            "mesh_shape": list(mesh_shape(mesh)),
        },
    }
    if pid == 0 and args.out is not None:
        flat = np.concatenate([
            np.asarray(jax.device_get(leaf)).ravel()
            for leaf in jax.tree.leaves(params)
        ])
        np.save(args.out + ".params.npy", flat)
        Path(args.out).write_text(json.dumps(result, indent=2))
        log(f"wrote {args.out}")
    return 0


def _hostchaos_rounds(
    args: argparse.Namespace,
    info: dict,
    log,
    mesh,
    step,
    params,
    sos,
    data,
    weights,
    round_rngs,
    start_round: int,
) -> int:
    """The fault-tolerant worker round loop: chaos injection at the host
    boundary, heartbeats, a watchdog deadline around every dispatch, and
    generation checkpoints at block boundaries.  The jitted round program is
    byte-identical to the smoke/bench jobs — chaos and resilience live
    entirely on the host side of the dispatch."""
    import jax
    import numpy as np

    from nanofed_tpu.faults import ChaosSchedule, FaultPlan, HostChaosInjector
    from nanofed_tpu.parallel import (
        CollectiveWatchdog,
        Heartbeat,
        HostFailure,
        mesh_shape,
    )
    from nanofed_tpu.persistence import GenerationStore

    host = args.host_id
    hosts_list = [int(h) for h in args.hosts_list.split(",")]
    injector = None
    if args.fault_plan:
        injector = HostChaosInjector(
            ChaosSchedule(FaultPlan.load(args.fault_plan)), host=host
        )
    hb = Heartbeat(args.hb_dir, host)
    store = GenerationStore(args.ckpt_dir, host=host)
    watchdog = CollectiveWatchdog(args.watchdog_deadline)
    progress = Path(args.progress) if args.progress else None
    pid = info["process_index"]

    def dispatch(params, sos, rngs):
        res = step(params, sos, data, weights, rngs)
        # Block INSIDE the watchdog bracket: the hang a dead peer causes
        # lives in the collective the result depends on.
        jax.block_until_ready((res.params, res.server_opt_state, res.metrics))
        return res

    def commit(rounds_done: int, params, sos) -> None:
        gen = rounds_done // args.block_size
        p_host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), params)
        s_host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), sos)
        store.commit(gen, rounds_done, p_host, s_host, hosts=hosts_list)
        hb.beat(round_number=rounds_done, generation=gen, status="committed")
        log(f"committed generation {gen} at round {rounds_done}")

    losses: list[float] = []
    executed: list[int] = []
    first_dispatch = True
    for r in range(start_round, args.rounds):
        if injector is not None:
            injector.maybe_fail(r)  # may os._exit (crash) or park (stall)
            delay = injector.dcn_delay_s(r)
            if delay:
                log(f"chaos: dcn_degrade {delay:.3f}s before round {r}")
                time.sleep(delay)
        else:
            delay = 0.0
        hb.beat(round_number=r, generation=r // args.block_size,
                status="dispatch")
        rngs = round_rngs(r)
        # The first dispatched round pays trace+compile; the deadline must
        # not misread a slow compile (or a planned-degraded DCN link) as a
        # dead peer.
        grace = delay + (args.compile_grace if first_dispatch else 0.0)
        try:
            res = watchdog.run(
                dispatch, params, sos, rngs,
                round_number=r, dcn_grace_s=grace,
                # Keep beating while blocked on the collective: a waiting
                # peer is alive — only the genuinely stalled host freezes.
                tick=lambda: hb.beat(
                    round_number=r, generation=r // args.block_size,
                    status="dispatch",
                ),
            )
        except HostFailure as exc:
            log(f"watchdog: {exc}")
            hb.beat(round_number=r, status="peer_failure")
            # os._exit, not sys.exit: the interpreter's atexit runs JAX's
            # distributed teardown, which BARRIERS on the very peer that just
            # failed — the clean exit would hang as hard as the collective.
            os._exit(PEER_FAILURE_RC)
        except Exception as exc:  # gloo/coordination error: a peer is gone
            log(f"dispatch failed (peer loss?): {type(exc).__name__}: {exc}")
            hb.beat(round_number=r, status="peer_failure")
            os._exit(PEER_FAILURE_RC)
        first_dispatch = False
        params, sos = res.params, res.server_opt_state
        loss = float(res.metrics["loss"])
        losses.append(loss)
        executed.append(r)
        hb.beat(round_number=r + 1, generation=(r + 1) // args.block_size,
                status="running")
        if progress is not None and pid == 0:
            with progress.open("a") as f:
                f.write(json.dumps(
                    {"round": r, "loss": loss, "wall_t": time.time()}
                ) + "\n")
        log(f"round {r}: loss={loss:.5f}")
        if (r + 1) % args.block_size == 0:
            commit(r + 1, params, sos)

    hb.beat(round_number=args.rounds, status="done")
    if pid == 0 and args.out is not None:
        Path(args.out).write_text(json.dumps({
            "mode": "hostchaos",
            "start_round": start_round,
            "rounds": executed,
            "losses": losses,
            "topology": {
                "process_count": info["process_count"],
                "hosts": args.hosts,
                "host_ids": hosts_list,
                "devices": len(jax.devices()),
                "mesh_shape": list(mesh_shape(mesh)),
            },
        }, indent=2))
        log(f"wrote {args.out}")
    return 0


def _federate_worker(args: argparse.Namespace, info: dict, log) -> int:
    """One federate mesh host: a live HTTP listener + device ingest buffer
    front end, drained HOST-LOCALLY each round (the buffer's batched
    ``coefs @ buffer`` reduce is the host-local aggregation stage), then ONE
    cross-host psum over ``hosts`` (``communication.federation``) applies the
    global FedAvg step.  The psum row carries a stop-vote lane: workers agree
    on the final round THROUGH the collective they already run — a worker
    that exited on a local condition alone would deadlock its peers' next
    psum.  The collective runs in an executor thread so the listener keeps
    accepting (and a swarm keeps rerouting INTO this host) while gloo blocks."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.flatten_util import ravel_pytree

    from nanofed_tpu.communication.federation import (
        apply_summed_row,
        assemble_host_rows,
        build_cross_host_row_psum,
        host_partial_row,
    )
    from nanofed_tpu.communication.http_server import HTTPServer
    from nanofed_tpu.faults import ChaosSchedule, FaultPlan, HostChaosInjector
    from nanofed_tpu.ingest import IngestConfig
    from nanofed_tpu.models import get_model
    from nanofed_tpu.observability.registry import MetricsRegistry
    from nanofed_tpu.orchestration.engine import (
        RoundLedger,
        completion_required,
    )
    from nanofed_tpu.parallel import (
        CollectiveWatchdog,
        Heartbeat,
        HostFailure,
        make_mesh,
        mesh_shape,
        replicated_sharding,
    )
    from nanofed_tpu.persistence import GenerationStore

    host = args.host_id
    hosts_list = [int(h) for h in args.hosts_list.split(",")]
    # Hosts-only mesh: ONE device per process.  A populated clients axis
    # would split the psum into one replica group per client column — several
    # concurrent gloo streams per round — and concurrent streams cross in
    # gloo's async slot sequencing (op.preamble.length <= op.nbytes aborts,
    # observed at 4 processes).  One device per host ⇒ one replica group ⇒
    # one gloo stream per beat.  The host-local stage needs no mesh at all:
    # it IS the ingest buffer's batched drain on this process's devices.
    mesh = make_mesh(
        devices=[
            jax.local_devices(process_index=p)[0]
            for p in range(jax.process_count())
        ],
        shape=(args.hosts, 1, 1),
    )
    model = get_model(args.model)
    flat0, unravel = ravel_pytree(model.init(jax.random.key(args.seed)))
    flat_size = int(flat0.size)

    def to_tree(flat: "np.ndarray"):
        return jax.tree.map(np.asarray, unravel(jnp.asarray(flat)))

    psum_fn = build_cross_host_row_psum(mesh)

    injector = None
    if args.fault_plan:
        injector = HostChaosInjector(
            ChaosSchedule(FaultPlan.load(args.fault_plan)), host=host
        )
    hb = Heartbeat(args.hb_dir, host)
    store = GenerationStore(args.ckpt_dir, host=host)
    watchdog = CollectiveWatchdog(args.watchdog_deadline)
    stop_file = Path(args.stop_file) if args.stop_file else None

    flat = np.asarray(flat0, np.float32)
    start_round = 0
    if args.resume:
        rec = store.latest_complete()
        if rec is not None:
            flat = np.asarray(ravel_pytree(rec.params)[0], np.float32)
            start_round = rec.round_number
            log(f"resumed generation {rec.generation} at round {start_round} "
                f"(committed by hosts {list(rec.hosts)})")
        else:
            log("resume requested but no complete generation — fresh start")

    # Warm dispatch: compiles the cross-host program AND doubles as the
    # bring-up barrier — a listener only opens once every peer reached this
    # collective (zero-mass rows change nothing; the mass floor keeps it
    # finite).
    warm = host_partial_row(None, 0.0, flat_size, extra=(0.0,))
    jax.block_until_ready(psum_fn(assemble_host_rows(mesh, warm)))
    # The warm psum is a barrier, so every host's anchor is within collective-
    # completion skew (ms on loopback) of its peers'.  Round deadlines derive
    # from this shared epoch — NOT from each host's own round start — so
    # dispatch skew across hosts stays bounded by one beat period plus drain
    # variance.  Load-bearing: XLA's CPU collectives carry a fixed internal
    # 30 s gloo timeout (CollectiveThunk::DefaultCollectiveTimeout), and a
    # host that reaches the psum a full unanchored round-timeout before a
    # quiet peer trips it, aborting the fleet mid-campaign with a torn-pair
    # gloo error instead of a clean round.
    anchor = time.monotonic()
    anchor_wall = time.time()  # forensic: the same instant on the wall clock
    log(f"cross-host reduce compiled on mesh {mesh_shape(mesh)} "
        "(bring-up barrier passed)")

    registry = MetricsRegistry()
    telemetry = None
    if args.telemetry_dir:
        from nanofed_tpu.observability import RunTelemetry

        # One stream per worker, merged by `nanofed-tpu trace`: the
        # clock_sync record pins this host's wall clock to the barrier
        # epoch every host just exited simultaneously — the offsets the
        # timeline merger subtracts ARE the differences of these stamps.
        telemetry = RunTelemetry(
            Path(args.telemetry_dir) / f"host_{host}", registry=registry
        )
        telemetry.record(
            "clock_sync", host=host, anchor_wall=round(anchor_wall, 6),
            process_id=info["process_index"],
        )
    ledger = RoundLedger(registry, telemetry=telemetry, track_dropouts=True)
    required = completion_required(args.round_quota, args.min_completion_rate)
    n_hosts = len(hosts_list)
    progress = Path(args.progress) if args.progress else None

    async def _serve() -> dict:
        server = HTTPServer(
            port=args.wire_port + host,
            registry=registry,
            max_inflight=512,
            # >= 1 is load-bearing: at window 0 publish_model CLEARS the
            # ingest buffer every round, silently dropping submits that were
            # accepted but not yet drained.
            staleness_window=max(1, args.staleness_window),
            ingest=IngestConfig(capacity=args.ingest_capacity),
            tracer=None if telemetry is None else telemetry.tracer,
        )
        await server.start()
        await server.publish_model(to_tree(flat), start_round)
        if args.ready_file:
            ready = Path(args.ready_file)
            tmp_path = ready.with_suffix(".tmp")
            tmp_path.write_text(json.dumps({
                "host": host,
                "url": f"http://127.0.0.1:{args.wire_port + host}",
                "round": start_round,
            }))
            tmp_path.replace(ready)  # atomic: the supervisor never sees torn
        log(f"listener up on :{args.wire_port + host} at round {start_round}")

        loop = asyncio.get_running_loop()
        base = flat
        rounds_meta: list[dict] = []
        clients_seen: set[str] = set()
        rerouted_total = 0
        r = start_round
        while True:
            if injector is not None:
                injector.maybe_fail(r)  # the planned host_crash: os._exit
                delay = injector.dcn_delay_s(r)
                if delay:
                    await asyncio.sleep(delay)
            hb.beat(round_number=r, status="collecting")
            t_round = time.perf_counter()
            start_wall = time.time()  # forensic: timeline lane placement
            pipeline = server._ingest_pipeline
            decode_before = (
                pipeline.decode_busy_seconds() if pipeline is not None else 0.0
            )
            # Shared beat: every host's round-r deadline is the same offset
            # from the warm-psum epoch, and the beat is STRICT — a full
            # quota never dispatches early.  Both halves are load-bearing:
            # hosts must enter the psum near-simultaneously (XLA CPU
            # collectives carry a fixed internal 30 s gloo timeout), and
            # back-to-back collective bundles fired sub-second by a hot host
            # race gloo's async slot sequencing (observed as op.preamble
            # size-mismatch aborts when a 100k swarm concentrated on one
            # listener).  The quota gates the LEDGER outcome, not dispatch.
            deadline = anchor + (r - start_round + 1) * args.round_timeout_s
            stop_seen = None
            while True:
                if stop_file is not None and stop_file.exists():
                    # The supervisor writes the stop file only after every
                    # swarm submit landed: the buffer is quiescent after a
                    # short grace — drain whatever is left and vote stop.
                    if stop_seen is None:
                        stop_seen = time.monotonic()
                    elif time.monotonic() - stop_seen > 0.5:
                        break
                if time.monotonic() > deadline:
                    break
                await asyncio.sleep(0.02)
            # Critical-path attribution: decode runs on pool threads DURING
            # this wait, so the beat wait splits into decode (the pool's busy
            # seconds this round, clamped to the window) and wire_wait (the
            # remainder — genuinely waiting on the wire).  With the
            # sequential drain/collective/apply/publish stages below, the six
            # segments tile the round walltime.
            wait_measured = time.perf_counter() - t_round
            decode_busy = (
                (pipeline.decode_busy_seconds() if pipeline is not None
                 else 0.0) - decode_before
            )
            seg_decode = min(max(0.0, decode_busy), wait_measured)
            t_drain = time.perf_counter()
            out, mass, metas = await server.drain_ingest_fedavg_partial()
            seg_drain = time.perf_counter() - t_drain
            want_stop = (
                (stop_file is not None and stop_file.exists())
                or (r + 1) >= args.rounds
            )
            row = host_partial_row(
                None if out is None else np.asarray(out), mass, flat_size,
                extra=(1.0 if want_stop else 0.0,),
            )
            hb.beat(round_number=r, status="dispatch")

            dispatch_t: dict = {}

            def dispatch(row=row, base=base):
                # One collective, nothing else on the wire: the psum'd row
                # comes back and the FedAvg apply happens in numpy — bitwise
                # identical on every host (ring all-reduce results are
                # rank-identical), so no broadcast/materialization stream
                # ever coexists with the psum.  Timed in two marks: the
                # blocked collective vs the host-side FedAvg apply.
                t0 = time.perf_counter()
                total_dev = psum_fn(assemble_host_rows(mesh, row))
                jax.block_until_ready(total_dev)
                t1 = time.perf_counter()
                applied = apply_summed_row(base, np.asarray(total_dev),
                                           flat_size)
                dispatch_t["collective"] = t1 - t0
                dispatch_t["apply"] = time.perf_counter() - t1
                return applied

            try:
                # Executor thread: the event loop — and with it the wire
                # listener — stays live while gloo blocks on the psum.
                new_flat, tail = await loop.run_in_executor(
                    None,
                    lambda: watchdog.run(
                        dispatch, round_number=r,
                        tick=lambda: hb.beat(round_number=r,
                                             status="dispatch"),
                    ),
                )
            except HostFailure as exc:
                log(f"watchdog: {exc}")
                hb.beat(round_number=r, status="peer_failure")
                # os._exit, not sys.exit: atexit would barrier on the dead
                # peer (see _hostchaos_rounds).
                os._exit(PEER_FAILURE_RC)
            except Exception as exc:  # gloo/coordination error: a peer died
                log(f"dispatch failed (peer loss?): "
                    f"{type(exc).__name__}: {exc}")
                hb.beat(round_number=r, status="peer_failure")
                os._exit(PEER_FAILURE_RC)
            global_mass = float(tail[0])
            stop_votes = float(tail[1])
            if global_mass > 0.0:
                base = new_flat
                # Strict-beat pacing means the quota no longer gates WHEN a
                # round fires — it gates how the ledger scores the beat: a
                # drain below completion_required() still advances the model
                # (the mass-weighted reduce is exact at any cohort size) but
                # is charged DEGRADED so under-filled beats are visible in
                # nanofed_rounds_total without stalling the collective.
                status = ("COMPLETED" if len(metas) >= required
                          else "DEGRADED")
            else:
                status = "FAILED"  # every host drained empty; params keep
            rerouted = sum(
                1 for m in metas
                if not str(m.client_id).startswith(f"h{host}_")
            )
            rerouted_total += rerouted
            clients_seen.update(str(m.client_id) for m in metas)
            sentinel = want_stop and not metas and global_mass <= 0.0
            round_r = r
            r += 1
            # Publish BEFORE charging the beat: the publish is the round's
            # last critical-path segment, so the charged walltime (and the
            # segments that tile it) must include it.
            t_publish = time.perf_counter()
            await server.publish_model(to_tree(base), r)
            seg_publish = time.perf_counter() - t_publish
            dt = time.perf_counter() - t_round
            hb.beat(round_number=r, status="running")
            if not sentinel:
                segments = {
                    "wire_wait": max(0.0, wait_measured - seg_decode),
                    "decode": seg_decode,
                    "drain": seg_drain,
                    "collective": dispatch_t.get("collective", 0.0),
                    "apply": dispatch_t.get("apply", 0.0),
                    "publish": seg_publish,
                }
                ledger.charge(
                    status=status, num_clients=len(metas), duration_s=dt,
                    expected=args.round_quota, segments=segments,
                    telemetry_fields={
                        "round": round_r, "host": host, "status": status,
                        "duration_s": round(dt, 6),
                        "start_wall": round(start_wall, 6),
                        "drained": len(metas),
                        "mass": round(float(mass), 3),
                        "rerouted_in": rerouted,
                        # Every consumed submit's trace id — the join key the
                        # trace resolver uses to link wire submits to the
                        # round that consumed them ("" = untraced submit).
                        "traces": [m.trace for m in metas],
                    },
                )
                rounds_meta.append({
                    "round": round_r, "drained": len(metas),
                    "mass": round(float(mass), 3),
                    "global_mass": round(global_mass, 3),
                    "rerouted_in": rerouted,
                    "duration_s": round(dt, 4), "status": status,
                })
                if progress is not None:
                    with progress.open("a") as f:
                        f.write(json.dumps({
                            "round": round_r, "drained": len(metas),
                            "mass": round(float(mass), 3),
                            "rerouted_in": rerouted,
                            "duration_s": round(dt, 4),
                            "wall_t": time.time(),
                        }) + "\n")
                log(f"round {round_r}: drained {len(metas)} "
                    f"(mass {mass:.1f}, {rerouted} rerouted in) global mass "
                    f"{global_mass:.1f} [{status}] {dt:.2f}s")
            if r % args.block_size == 0 and not sentinel:
                store.commit(r // args.block_size, r, to_tree(base), {},
                             hosts=hosts_list)
                log(f"committed generation {r // args.block_size} "
                    f"at round {r}")
            if stop_votes >= n_hosts - 0.5:
                log(f"stop consensus at round {r} "
                    f"({stop_votes:.0f}/{n_hosts} votes)")
                break

        if r % args.block_size != 0:
            store.commit(r // args.block_size + 1, r, to_tree(base), {},
                         hosts=hosts_list)
        server.stop_training()
        await asyncio.sleep(0.2)  # let /status pollers observe the stop
        hb.beat(round_number=r, status="done")
        result = {
            "mode": "federate",
            "host": host,
            "start_round": start_round,
            "end_round": r,
            "rounds": rounds_meta,
            "clients_distinct": len(clients_seen),
            "rerouted_in_total": rerouted_total,
            "topology": {
                "process_count": info["process_count"],
                "hosts": args.hosts,
                "host_ids": hosts_list,
                "devices": jax.device_count(),
                "mesh_shape": list(mesh_shape(mesh)),
            },
        }
        await server.stop()
        if telemetry is not None:
            telemetry.close()  # appends the final metrics_snapshot record
        return result

    result = asyncio.run(_serve())
    if args.out is not None:
        Path(args.out).write_text(json.dumps(result, indent=2))
        log(f"wrote {args.out}")
    return 0


def _spawn(args: argparse.Namespace, mode_args: list[str], out: str | None,
           hosts: int, num_processes: int, port: int) -> list[subprocess.Popen]:
    procs = []
    for pid in range(num_processes):
        cmd = [
            sys.executable, str(Path(__file__).resolve()), "worker",
            "--process-id", str(pid),
            "--num-processes", str(num_processes),
            "--coordinator", f"localhost:{port}",
            "--hosts", str(hosts),
            *mode_args,
        ]
        if out is not None and pid == 0:
            cmd += ["--out", out]
        procs.append(subprocess.Popen(cmd, env=_worker_env(args, pid)))
    return procs


def _reap(procs: list[subprocess.Popen], grace_s: float = 5.0) -> None:
    """Terminate AND reap every still-running worker.  Kill-without-wait (the
    old failure path) leaves zombies holding the rendezvous port: the next
    parity run on the machine then dies in jax.distributed bring-up.  SIGTERM
    first (workers flush logs), SIGKILL after the grace, ``wait()`` always —
    no child of the launcher may outlive this call."""
    for q in procs:
        if q.poll() is None:
            q.terminate()
    deadline = time.time() + grace_s
    for q in procs:
        if q.poll() is not None:
            continue
        try:
            q.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            q.kill()
            q.wait()


def _wait(procs: list[subprocess.Popen], timeout_s: float) -> None:
    # Poll ALL workers, not procs[0] first: a fast crash in worker 1 while
    # worker 0 blocks in the jax.distributed rendezvous must surface as the
    # real non-zero exit code immediately, not as a full-timeout "timed out"
    # after the peer-less rendezvous finally expires.  Any failure path reaps
    # the survivors BEFORE raising: a failed parity run must not leave orphan
    # processes holding the rendezvous port.
    deadline = time.time() + timeout_s
    pending = list(procs)
    while pending:
        for p in list(pending):
            rc = p.poll()
            if rc is None:
                continue
            if rc != 0:
                _reap(procs)
                raise SystemExit(f"worker exited rc={rc}")
            pending.remove(p)
        if pending:
            if time.time() > deadline:
                _reap(procs)
                raise SystemExit(f"worker timed out after {timeout_s:.0f}s")
            time.sleep(0.2)


def run_smoke(args: argparse.Namespace) -> int:
    """2-process hierarchical run vs single-process 1-D reference: the losses
    and final params must match to float tolerance — the trajectory-parity
    acceptance bar of the multi-host path."""
    import numpy as np

    tmp = Path(args.tmp_dir)
    tmp.mkdir(parents=True, exist_ok=True)
    mode_args = [
        "--job", "smoke", "--clients", str(args.clients),
        "--capacity", str(args.capacity), "--batch-size", str(args.batch_size),
        "--rounds", str(args.rounds), "--model", args.model,
        "--seed", str(args.seed),
        "--devices-per-process", str(args.devices_per_process),
    ]
    if args.client_chunk is not None:
        mode_args += ["--client-chunk", str(args.client_chunk)]

    multi_out = str(tmp / "multihost_smoke_multi.json")
    t0 = time.time()
    print(f"# spawning {args.num_processes}-process hierarchical run "
          f"(hosts={args.num_processes}, gloo CPU collectives)", flush=True)
    procs = _spawn(args, mode_args, multi_out, hosts=args.num_processes,
                   num_processes=args.num_processes, port=args.port)
    _wait(procs, args.timeout)

    # Single-process 1-D reference over the SAME global device count: one
    # worker, hosts=1, no jax.distributed — the classic flat-psum program.
    ref_out = str(tmp / "multihost_smoke_ref.json")
    print("# running single-process 1-D reference", flush=True)
    ref_args = argparse.Namespace(**vars(args))
    ref_args.devices_per_process = (
        args.devices_per_process * args.num_processes
    )
    procs = _spawn(ref_args, mode_args, ref_out, hosts=1,
                   num_processes=1, port=args.port + 1)
    _wait(procs, args.timeout)

    multi = json.loads(Path(multi_out).read_text())
    ref = json.loads(Path(ref_out).read_text())
    p_multi = np.load(multi_out + ".params.npy")
    p_ref = np.load(ref_out + ".params.npy")
    loss_delta = max(
        abs(a - b) for a, b in zip(multi["losses"], ref["losses"])
    )
    param_delta = float(np.abs(p_multi - p_ref).max())
    verdict = {
        "losses_multi": multi["losses"],
        "losses_ref": ref["losses"],
        "max_loss_delta": loss_delta,
        "max_param_delta": param_delta,
        "tolerance": SMOKE_TOL,
        "topology": multi["topology"],
        "walltime_s": round(time.time() - t0, 1),
    }
    print(json.dumps(verdict, indent=2))
    assert multi["topology"]["process_count"] == args.num_processes, multi
    assert loss_delta <= SMOKE_TOL, (
        f"trajectory diverged: max loss delta {loss_delta} > {SMOKE_TOL}"
    )
    assert param_delta <= SMOKE_TOL, (
        f"params diverged: max delta {param_delta} > {SMOKE_TOL}"
    )
    print("multihost-smoke OK: 2-process hierarchical aggregation == "
          "single-process 1-D mesh to float tolerance")
    return 0


def run_bench(args: argparse.Namespace) -> int:
    """The 100k+ streamed-clients artifact: chunked streaming x multi-process,
    rounds/sec + clients/sec, topology block, honest CPU basis."""
    tmp = Path(args.tmp_dir)
    tmp.mkdir(parents=True, exist_ok=True)
    mode_args = [
        "--job", "bench", "--clients", str(args.clients),
        "--capacity", str(args.capacity), "--batch-size", str(args.batch_size),
        "--rounds", str(args.rounds), "--model", args.model,
        "--seed", str(args.seed),
        "--devices-per-process", str(args.devices_per_process),
        "--client-chunk", str(args.client_chunk if args.client_chunk else 250),
    ]
    worker_out = str(tmp / "multihost_bench_worker.json")
    t0 = time.time()
    print(f"# spawning {args.num_processes}-process bench at "
          f"{args.clients} clients", flush=True)
    procs = _spawn(args, mode_args, worker_out, hosts=args.num_processes,
                   num_processes=args.num_processes, port=args.port)
    _wait(procs, args.timeout)

    worker = json.loads(Path(worker_out).read_text())
    times = worker["round_times_s"]
    median = sorted(times)[len(times) // 2]
    record = {
        "metric": "multihost_fedavg_round_walltime",
        "unit": "s",
        "value": median,
        "per_round_s": times,
        "rounds_per_sec": round(1.0 / median, 4),
        "clients_per_sec": round(args.clients / median, 1),
        "num_clients": args.clients,
        "samples_per_client": args.capacity,
        "client_chunk": args.client_chunk if args.client_chunk else 250,
        "model": args.model,
        "losses": worker["losses"],
        "topology": worker["topology"],
        "platform": "cpu",
        "basis": (
            "multi-process jax.distributed over loopback (gloo CPU "
            "collectives), virtual XLA host devices per process; measures the "
            "hierarchical round PROGRAM — chunked streaming, host-local psum "
            "+ one cross-host psum, multi-controller dispatch — at population "
            "scale on CPU, not TPU silicon. The reference flagship tops out "
            "at 1000 clients (BASELINE.md); this is the 100x population jump."
        ),
        "harness": "scripts/multihost_harness.py bench",
        "walltime_s": round(time.time() - t0, 1),
    }
    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%S")
    path = out_dir / f"multihost_{stamp}_{args.clients // 1000}k.json"
    path.write_text(json.dumps(record, indent=2))
    print(json.dumps(record, indent=2))
    print(f"# artifact written to {path}")
    return 0


def _spawn_hostchaos(
    args: argparse.Namespace,
    host_ids: list[int],
    port: int,
    *,
    rounds: int,
    hb_dir: Path,
    ckpt_dir: Path,
    resume: bool,
    plan_path: Path | None,
    out: Path | None,
    progress: Path | None,
) -> list[subprocess.Popen]:
    """Spawn one hostchaos worker per LOGICAL host id.  Process ids renumber
    0..n-1 every phase (jax.distributed needs a dense range); logical host ids
    survive reshapes — they are what the fault plan targets, what heartbeats
    and commit markers are keyed by, and what lets a restarted host rejoin as
    itself."""
    procs = []
    n = len(host_ids)
    for pid, host in enumerate(host_ids):
        cmd = [
            sys.executable, str(Path(__file__).resolve()), "worker",
            "--job", "hostchaos",
            "--process-id", str(pid),
            "--num-processes", str(n),
            "--coordinator", f"localhost:{port}",
            "--hosts", str(n),
            "--clients", str(args.clients),
            "--capacity", str(args.capacity),
            "--batch-size", str(args.batch_size),
            "--rounds", str(rounds),
            "--model", args.model,
            "--seed", str(args.seed),
            "--devices-per-process", str(args.devices_per_process),
            "--block-size", str(args.block_size),
            "--watchdog-deadline", str(args.watchdog_deadline),
            "--compile-grace", str(args.compile_grace),
            "--host-id", str(host),
            "--hosts-list", ",".join(str(h) for h in host_ids),
            "--hb-dir", str(hb_dir),
            "--ckpt-dir", str(ckpt_dir),
        ]
        if args.client_chunk is not None:
            cmd += ["--client-chunk", str(args.client_chunk)]
        if resume:
            cmd += ["--resume"]
        if plan_path is not None:
            cmd += ["--fault-plan", str(plan_path)]
        if out is not None and pid == 0:
            cmd += ["--out", str(out)]
        if progress is not None and pid == 0:
            cmd += ["--progress", str(progress)]
        procs.append(subprocess.Popen(cmd, env=_worker_env(args, pid)))
    return procs


def _read_progress(path: Path) -> list[dict]:
    if not path.exists():
        return []
    out = []
    for line in path.read_text().splitlines():
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError:
            continue  # torn tail line from a killed writer
    return out


def _fresh_dir(path: Path) -> Path:
    if path.exists():
        shutil.rmtree(path)
    path.mkdir(parents=True)
    return path


def run_hostchaos(args: argparse.Namespace) -> int:
    """The kill-and-recover drill: seeded plan fails one of >=2 hosts
    mid-round; the supervisor detects it, reaps the survivors, re-forms the
    mesh over the surviving host set, resumes from the newest generation
    committed by all participants, optionally rejoins the failed host, and
    writes the ``runs/hostchaos_*.json`` evidence artifact (MTTR, rounds
    lost <= one block, post-recovery parity vs an unfailed shrunk-mesh run,
    zero orphans)."""
    from nanofed_tpu.faults.plan import FaultPlan
    from nanofed_tpu.observability.telemetry import RunTelemetry
    from nanofed_tpu.observability.tracing import (
        FLIGHT_RECORDER_FILENAME,
        FlightRecorder,
        mttr_decomposition,
    )
    from nanofed_tpu.parallel.resilience import (
        HostMonitor,
        no_orphans,
        resilience_metrics,
    )
    from nanofed_tpu.persistence import GenerationStore

    if args.num_processes < 2:
        raise SystemExit("hostchaos needs --num-processes >= 2 (someone must "
                         "survive to recover)")
    P, R, B = args.num_processes, args.rounds, args.block_size
    tmp = Path(args.tmp_dir)
    tmp.mkdir(parents=True, exist_ok=True)
    hb_a = _fresh_dir(tmp / "hb_a")
    hb_c = _fresh_dir(tmp / "hb_c")
    hb_d = _fresh_dir(tmp / "hb_d")
    hb_e = _fresh_dir(tmp / "hb_e")
    ckpt = _fresh_dir(tmp / "ckpt")
    ref_ckpt = tmp / "ckpt_ref"
    if ref_ckpt.exists():
        shutil.rmtree(ref_ckpt)

    if args.plan:
        plan = FaultPlan.load(args.plan)
    else:
        plan = FaultPlan.generate(
            args.seed, [], R, hosts=P,
            host_crash_count=1 if args.host_fault == "crash" else 0,
            host_stall_count=1 if args.host_fault == "stall" else 0,
        )
    host_events = [e for e in plan.events
                   if e.kind in ("host_crash", "host_stall")]
    if not host_events:
        raise SystemExit("the hostchaos plan contains no host_crash/"
                         "host_stall event — nothing to drill")
    if len(host_events) > 1:
        # Phase C re-feeds the plan to the recovered mesh (surviving hosts'
        # remaining dcn events stay live), so a second terminal event would
        # kill a survivor mid-recovery with nobody supervising.  One terminal
        # fault per drill; run the harness again for the next one.
        raise SystemExit(
            f"the hostchaos drill handles ONE terminal host fault per run; "
            f"this plan has {len(host_events)} "
            f"({[e.to_dict() for e in host_events]}) — split it across runs"
        )
    max_dcn = max(
        (e.seconds for e in plan.events if e.kind == "dcn_degrade"),
        default=0.0,
    )
    if max_dcn >= args.watchdog_deadline:
        # The degraded host widens its OWN deadline by the injected delay,
        # but its peers cannot know the plan: their collectives absorb the
        # delay under the base deadline.  The documented contract is that a
        # degraded-but-alive link must NOT be misread as a dead peer — which
        # requires sizing the deadline above the worst planned delay.
        raise SystemExit(
            f"plan injects dcn_degrade of {max_dcn}s but "
            f"--watchdog-deadline is {args.watchdog_deadline}s: peers would "
            "misread the degraded link as a dead host — raise the deadline "
            "above the worst planned delay"
        )
    plan_path = tmp / "hostchaos_plan.json"
    plan.save(plan_path)

    metrics = resilience_metrics()
    if args.telemetry_dir is None:
        # Ours to wipe.  An OPERATOR-supplied dir is never rmtree'd — they may
        # point it at runs/ next to prior artifacts; records just append.
        telemetry_dir = _fresh_dir(tmp / "telemetry")
    else:
        telemetry_dir = Path(args.telemetry_dir)
        telemetry_dir.mkdir(parents=True, exist_ok=True)
    tel = RunTelemetry(telemetry_dir)
    # Bounded crash forensics: marks accumulate in-process and are dumped at
    # the reap — dump() create-if-missing and never raises, so a forensics
    # failure can never abort the recovery it is documenting.
    recorder = FlightRecorder(name="hostchaos-supervisor")
    all_pids: list[int] = []
    t0 = time.time()
    hosts = list(range(P))

    # ---- phase A: full mesh under the plan, run until the failure ----------
    print(f"# hostchaos: {P}-host mesh, plan: "
          + ", ".join(f"{e.kind}@r{e.round} host {e.host}"
                      for e in host_events), flush=True)
    progress_a = tmp / "progress_a.jsonl"
    progress_a.unlink(missing_ok=True)
    procs = _spawn_hostchaos(
        args, hosts, args.port, rounds=R, hb_dir=hb_a, ckpt_dir=ckpt,
        resume=False, plan_path=plan_path, out=tmp / "hc_a.json",
        progress=progress_a,
    )
    all_pids += [p.pid for p in procs]
    monitor = HostMonitor(hb_a, stall_timeout_s=args.stall_timeout)

    def _hb_status(host: int) -> str:
        try:
            return str(json.loads(
                (hb_a / f"host_{host}.hb.json").read_text()
            ).get("status", "?"))
        except (OSError, json.JSONDecodeError, ValueError):
            return "?"

    victim: int | None = None
    kind: str | None = None
    deadline = time.time() + args.timeout
    exits: dict[int, int] = {}
    exit_order: list[int] = []  # indices in the order their exits were seen
    while victim is None:
        for i, p in enumerate(procs):
            rc = p.poll()
            if rc is not None and i not in exits:
                exits[i] = rc
                exit_order.append(i)
                if rc == HOST_CRASH_RC:
                    victim, kind = hosts[i], "host_crash"
                    metrics["host_failures"].inc(kind=kind)
        if victim is None:
            stalled = monitor.stalled()
            if stalled:
                victim, kind = stalled[0].host, "host_stall"
        if victim is None and any(
            rc == PEER_FAILURE_RC for rc in exits.values()
        ):
            # At least one worker exited BLAMING a peer (watchdog / gloo
            # error).  A blaming worker is never the victim; neither is one
            # whose last heartbeat declared peer_failure (it may have been
            # killed mid-exit).  Once exactly one blameless worker remains —
            # still alive (a true stall) or collaterally killed when the
            # coordination service's leader went down — it is the victim.
            blaming = {
                i for i in range(len(procs))
                if exits.get(i) == PEER_FAILURE_RC
                or _hb_status(hosts[i]) == "peer_failure"
            }
            candidates = [i for i in range(len(procs)) if i not in blaming]
            all_blamers_exited = all(
                i in exits for i in range(len(procs)) if i in blaming
            )
            if len(candidates) == 1 and all_blamers_exited:
                i = candidates[0]
                victim = hosts[i]
                # Died BEFORE the first blame → it crashed on its own; died
                # after (or still silently alive) → the stall the blamers
                # timed out on.
                first_blame_pos = min(
                    exit_order.index(j) for j in blaming if j in exits
                ) if any(j in exits for j in blaming) else len(exit_order)
                died_first = (
                    i in exits and exit_order.index(i) < first_blame_pos
                )
                kind = "host_crash" if died_first else "host_stall"
                metrics["host_failures"].inc(kind=kind)
        if victim is None and len(exits) == len(procs):
            if all(rc == 0 for rc in exits.values()):
                _reap(procs)
                raise SystemExit(
                    "hostchaos: every worker completed without the planned "
                    "failure firing — raise --rounds or fix the plan"
                )
            # Every process exited.  Attribute only to a worker that failed
            # on its OWN account (non-zero, non-blaming): if every exit
            # blames a peer, the failure is systemic (e.g. a round-0 gloo
            # bring-up error hit everyone) and naming a victim would fabricate
            # a host_crash, exclude a healthy host, and mask the real cause.
            organic = [
                i for i in exit_order
                if exits[i] not in (0, PEER_FAILURE_RC)
            ]
            if not organic:
                _reap(procs)
                raise SystemExit(
                    f"hostchaos: every worker exited blaming a peer "
                    f"(exit codes {dict(sorted(exits.items()))}) — systemic "
                    "failure, no victim attributable; check the worker logs"
                )
            victim = hosts[organic[0]]
            kind = "host_crash"
            metrics["host_failures"].inc(kind=kind)
        if victim is None and time.time() > deadline:
            _reap(procs)
            raise SystemExit(f"hostchaos: no failure detected within "
                             f"{args.timeout:.0f}s")
        if victim is None:
            time.sleep(0.2)
    t_detect = time.time()
    recorder.note("kill_detected", host=victim, fault=kind)
    victim_hb = hb_a / f"host_{victim}.hb.json"
    last_beat_wall = None
    victim_round = None
    try:
        payload = json.loads(victim_hb.read_text())
        last_beat_wall = float(payload.get("wall_t", 0)) or None
        victim_round = payload.get("round")
    except (OSError, json.JSONDecodeError, ValueError):
        pass
    detection_s = (
        round(t_detect - last_beat_wall, 3) if last_beat_wall else None
    )
    # Kill and REAP everyone — survivors included: the old mesh is dead, and
    # an orphan blocked in gloo would hold the rendezvous port forever.
    # (Every detection path above already counted the failure by kind.)
    _reap(procs)
    recorder.note("reaped", victim=victim, fault=kind)
    dump_path = recorder.dump(
        telemetry_dir / FLIGHT_RECORDER_FILENAME,
        extra={"victim": victim, "kind": kind},
    )
    plan_round = next(
        (e.round for e in host_events if e.host == victim), victim_round
    )
    fail_round = plan_round if plan_round is not None else 0
    print(f"# failure detected: {kind} on host {victim} (round {fail_round}, "
          f"detection {detection_s}s) — reaped {len(procs)} workers",
          flush=True)
    tel.record(
        "host_failure", kind=kind, host=victim, round=fail_round,
        detection_s=detection_s,
        detail=f"exit codes {exits}" if exits else "heartbeat frozen",
    )

    # Reference snapshot BEFORE the recovered run extends the store: the
    # unfailed shrunk-mesh run must start from the identical recovery point.
    shutil.copytree(ckpt, ref_ckpt)
    rec = GenerationStore(ckpt).latest_complete()
    resumed_round = rec.round_number if rec is not None else 0
    resumed_gen = rec.generation if rec is not None else None
    rounds_lost = fail_round - resumed_round
    print(f"# recovery point: generation {resumed_gen} (round "
          f"{resumed_round}); rounds lost = {rounds_lost} (block size {B})",
          flush=True)

    # ---- phase C: re-form over the survivors, resume, finish the run -------
    survivors = [h for h in hosts if h != victim]
    metrics["mesh_reshapes"].inc()
    progress_c = tmp / "progress_c.jsonl"
    progress_c.unlink(missing_ok=True)
    procs = _spawn_hostchaos(
        args, survivors, args.port + 7, rounds=R, hb_dir=hb_c, ckpt_dir=ckpt,
        resume=True, plan_path=plan_path, out=tmp / "hc_c.json",
        progress=progress_c,
    )
    all_pids += [p.pid for p in procs]
    respawn_mark = recorder.note("respawned", hosts=survivors)
    _wait(procs, args.timeout)
    # S2: the telemetry dir (and the supervisor's stream in it) must survive
    # the crash + reap — a recovery drill whose evidence vanished proves
    # nothing.
    assert telemetry_dir.exists() and tel.path.exists(), (
        f"telemetry did not survive the worker crash: dir={telemetry_dir} "
        f"stream={tel.path}"
    )
    recovered = json.loads((tmp / "hc_c.json").read_text())
    prog_c = _read_progress(progress_c)
    if not prog_c:
        raise SystemExit("hostchaos: recovered run reported no rounds")
    mttr_s = round(prog_c[0]["wall_t"] - t_detect, 3)
    metrics["recovery_seconds"].observe(mttr_s)
    # Retroactive mark: map the first post-recovery round's wall clock onto
    # the recorder's monotonic axis via the respawn mark (both clocks were
    # read in this process).
    recorder.note(
        "first_progress", wall=round(prog_c[0]["wall_t"], 6),
        t_mono=round(
            respawn_mark["t_mono"]
            + max(0.0, prog_c[0]["wall_t"] - respawn_mark["t_wall"]),
            6,
        ),
    )
    mttr_phases = mttr_decomposition(recorder.snapshot(), [
        ("kill_detected", None),
        ("reaped", "reap"),
        ("respawned", "respawn"),
        ("first_progress", "recompile"),
    ])
    if detection_s is not None:
        # Detection is measured from the victim's LAST heartbeat, which
        # predates every recorder mark — prepend it rather than difference it.
        mttr_phases = {"detect": detection_s, **mttr_phases}
    recorder.dump(
        telemetry_dir / FLIGHT_RECORDER_FILENAME,
        extra={"victim": victim, "kind": kind, "mttr_phases": mttr_phases},
    )
    print(f"# mesh re-formed over hosts {survivors}: first post-recovery "
          f"round done {mttr_s}s after detection (MTTR: {mttr_phases})",
          flush=True)
    tel.record(
        "recovery", recovery_s=mttr_s, resumed_generation=resumed_gen,
        resumed_round=resumed_round, rounds_lost=rounds_lost,
        hosts_before=P, hosts_after=len(survivors), reshape=True,
        rejoin=False, mttr_phases=mttr_phases,
        flight_recorder=None if dump_path is None else str(dump_path),
    )

    # ---- phase D (optional): the failed host rejoins at a generation
    # boundary, mesh re-grows to the full host set --------------------------
    rejoin_block = None
    if args.rejoin_rounds > 0:
        metrics["mesh_reshapes"].inc()
        total = R + args.rejoin_rounds
        procs = _spawn_hostchaos(
            args, hosts, args.port + 13, rounds=total, hb_dir=hb_d,
            ckpt_dir=ckpt, resume=True, plan_path=None,
            out=tmp / "hc_d.json", progress=tmp / "progress_d.jsonl",
        )
        all_pids += [p.pid for p in procs]
        _wait(procs, args.timeout)
        rejoined = json.loads((tmp / "hc_d.json").read_text())
        rejoin_block = {
            "hosts": hosts,
            "resumed_round": rejoined["start_round"],
            "rounds": rejoined["rounds"],
            "losses": rejoined["losses"],
        }
        assert rejoined["rounds"] and rejoined["rounds"][-1] == total - 1, (
            f"rejoined mesh did not reach round {total - 1}: {rejoined}"
        )
        print(f"# host {victim} rejoined at round {rejoined['start_round']}: "
              f"full {P}-host mesh ran to round {total - 1}", flush=True)
        tel.record(
            "recovery", resumed_generation=rejoined["start_round"] // B,
            resumed_round=rejoined["start_round"], rounds_lost=0,
            hosts_before=len(survivors), hosts_after=P, reshape=True,
            rejoin=True,
        )

    # ---- phase E: the parity reference — an UNFAILED run on the same
    # shrunk mesh from the same recovery point ------------------------------
    procs = _spawn_hostchaos(
        args, survivors, args.port + 19, rounds=R, hb_dir=hb_e,
        ckpt_dir=ref_ckpt, resume=True, plan_path=None,
        out=tmp / "hc_e.json", progress=None,
    )
    all_pids += [p.pid for p in procs]
    _wait(procs, args.timeout)
    reference = json.loads((tmp / "hc_e.json").read_text())

    loss_delta = max(
        (abs(a - b) for a, b in
         zip(recovered["losses"], reference["losses"])),
        default=float("inf"),
    )
    orphans = no_orphans(all_pids)
    artifact = {
        "record_type": "hostchaos",
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "seed": args.seed,
        "plan": json.loads(plan.to_json()),
        "rounds": R,
        "block_size": B,
        "clients": args.clients,
        "model": args.model,
        "topology": {
            "hosts_before": P,
            "hosts_after": len(survivors),
            "devices_per_process": args.devices_per_process,
            "mesh_before": [P, args.devices_per_process, 1],
            "mesh_after": [len(survivors), args.devices_per_process, 1],
        },
        "failure": {
            "kind": kind,
            "host": victim,
            "round": fail_round,
            "detection_s": detection_s,
            "stall_timeout_s": args.stall_timeout,
            "watchdog_deadline_s": args.watchdog_deadline,
            "worker_exit_codes": {str(hosts[i]): rc
                                  for i, rc in sorted(exits.items())},
        },
        "recovery": {
            "mttr_s": mttr_s,
            "resumed_generation": resumed_gen,
            "resumed_round": resumed_round,
            "rounds_lost": rounds_lost,
            "at_most_one_block": rounds_lost <= B,
        },
        "pre_failure_losses": [p["loss"] for p in _read_progress(progress_a)],
        "recovered": {
            "rounds": recovered["rounds"], "losses": recovered["losses"],
        },
        "reference_unfailed_shrunk": {
            "rounds": reference["rounds"], "losses": reference["losses"],
        },
        "parity": {
            "max_loss_delta": loss_delta,
            "tolerance": args.parity_tol,
            "ok": loss_delta <= args.parity_tol,
        },
        "rejoin": rejoin_block,
        "orphans": orphans,
        "platform": "cpu",
        "basis": (
            "multi-process jax.distributed over loopback (gloo CPU "
            "collectives), virtual XLA host devices per process; the drill "
            "measures the RECOVERY MACHINERY — detection, reap, mesh "
            "re-formation, generation resume — not TPU silicon.  MTTR "
            "includes process respawn + jax bring-up + recompile on the "
            "shrunk mesh."
        ),
        "harness": "scripts/multihost_harness.py hostchaos",
        "walltime_s": round(time.time() - t0, 1),
    }
    tel.close()

    assert rounds_lost <= B, (
        f"at-most-one-block violated: lost {rounds_lost} rounds > block {B}"
    )
    assert loss_delta <= args.parity_tol, (
        f"post-recovery trajectory diverged from the unfailed shrunk-mesh "
        f"run: max loss delta {loss_delta} > {args.parity_tol}"
    )
    assert not orphans, f"orphan worker processes survived the run: {orphans}"
    assert recovered["rounds"][-1] == R - 1, recovered

    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%S")
    path = out_dir / f"hostchaos_{stamp}_{P}h.json"
    path.write_text(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps(artifact, indent=2))
    print(f"# artifact written to {path}")
    print(f"# telemetry: {telemetry_dir} (digest: python -m nanofed_tpu.cli "
          f"metrics-summary {telemetry_dir})")
    print(f"hostchaos OK: {kind} on host {victim} at round {fail_round} -> "
          f"recovered on {len(survivors)} host(s) in {mttr_s}s, "
          f"{rounds_lost} round(s) re-run (<= {B}), parity delta "
          f"{loss_delta:.2e}, zero orphans")
    return 0


def _spawn_federate(
    args: argparse.Namespace,
    host_ids: list[int],
    port: int,
    *,
    phase: str,
    hb_dir: Path,
    ckpt_dir: Path,
    resume: bool,
    plan_path: Path | None,
    stop_file: Path,
    tmp: Path,
    telemetry_dir: Path | None = None,
) -> list[subprocess.Popen]:
    """One federate worker per LOGICAL host id (dense process ids per phase,
    stable host ids across the kill — same convention as hostchaos).  Every
    worker gets its own ready/progress/result files: the supervisor reads
    per-host round stats even from a phase that ends in a reap."""
    procs = []
    n = len(host_ids)
    for pid, host in enumerate(host_ids):
        cmd = [
            sys.executable, str(Path(__file__).resolve()), "worker",
            "--job", "federate",
            "--process-id", str(pid),
            "--num-processes", str(n),
            "--coordinator", f"localhost:{port}",
            "--hosts", str(n),
            "--rounds", str(args.max_rounds),
            "--model", args.model,
            "--seed", str(args.seed),
            "--devices-per-process", str(args.devices_per_process),
            "--block-size", str(args.block_size),
            "--watchdog-deadline", str(args.federate_watchdog),
            "--host-id", str(host),
            "--hosts-list", ",".join(str(h) for h in host_ids),
            "--hb-dir", str(hb_dir),
            "--ckpt-dir", str(ckpt_dir),
            "--wire-port", str(args.wire_port),
            "--ingest-capacity", str(args.ingest_capacity),
            "--staleness-window", str(args.staleness_window),
            "--round-quota", str(args.round_quota),
            "--min-completion-rate", str(args.min_completion_rate),
            "--round-timeout-s", str(args.round_timeout_s),
            "--stop-file", str(stop_file),
            "--ready-file", str(tmp / f"fed_ready_h{host}.json"),
            "--progress", str(tmp / f"fed_progress_{phase}_h{host}.jsonl"),
            "--out", str(tmp / f"fed_result_{phase}_h{host}.json"),
        ]
        if resume:
            cmd += ["--resume"]
        if plan_path is not None:
            cmd += ["--fault-plan", str(plan_path)]
        if telemetry_dir is not None:
            # Each worker appends its own stream under host_<h>/ — one
            # telemetry.jsonl per process, merged by `nanofed-tpu trace`.
            cmd += ["--telemetry-dir", str(telemetry_dir)]
        procs.append(subprocess.Popen(cmd, env=_worker_env(args, pid)))
    return procs


def run_federate(args: argparse.Namespace) -> int:
    """ONE STACK: wire clients drain straight into the hierarchical mesh
    reduce.  W jax.distributed mesh hosts each run an HTTP listener + device
    ingest buffer; the loadgen swarm drives the wire population against them
    (VirtualClock schedule, real sockets); each round is host-local drains +
    ONE cross-host psum.  With ``--kill-round`` a seeded plan crashes one
    host mid-campaign: its wire clients reroute to survivors live
    (retry/rotation/dedup), the mesh re-forms over the survivors from the
    newest committed generation, and the dead host's population re-drives —
    zero lost submits, asserted."""
    import asyncio

    import numpy as np

    from nanofed_tpu.communication.retry import RetryPolicy
    from nanofed_tpu.faults.plan import FaultEvent, FaultPlan
    from nanofed_tpu.loadgen.swarm import SwarmConfig, latency_digest, run_swarm
    from nanofed_tpu.observability.critical_path import federation_timeline
    from nanofed_tpu.observability.telemetry import RunTelemetry
    from nanofed_tpu.observability.tracing import (
        FLIGHT_RECORDER_FILENAME,
        FlightRecorder,
        mttr_decomposition,
    )
    from nanofed_tpu.parallel.resilience import no_orphans
    from nanofed_tpu.persistence import GenerationStore
    from nanofed_tpu.utils.clock import VirtualClock

    if args.num_processes < 2:
        raise SystemExit("federate needs --num-processes >= 2 (one wire "
                         "listener per mesh host)")
    P = args.num_processes
    tmp = Path(args.tmp_dir)
    tmp.mkdir(parents=True, exist_ok=True)
    hb_dir = _fresh_dir(tmp / "fed_hb")
    ckpt = _fresh_dir(tmp / "fed_ckpt")
    stop_file = tmp / "federate_stop"
    stop_file.unlink(missing_ok=True)
    for stale in list(tmp.glob("fed_result_*.json")) + list(
        tmp.glob("fed_progress_*.jsonl")
    ):
        stale.unlink()

    hosts = list(range(P))
    counts = [args.clients // P + (1 if i < args.clients % P else 0)
              for i in range(P)]
    urls = [f"http://127.0.0.1:{args.wire_port + h}" for h in hosts]

    kill = args.kill_round is not None
    victim = args.kill_host if args.kill_host is not None else P - 1
    plan = None
    plan_path = None
    if kill:
        plan = FaultPlan(seed=args.seed, events=(
            FaultEvent(kind="host_crash", round=args.kill_round, host=victim),
        ))
        plan_path = tmp / "federate_plan.json"
        plan.save(plan_path)

    # Canned payload base = the same deterministic init the workers publish,
    # so the servers' delta reconstruction lands on base + noise exactly.
    # The supervisor is pinned to the CPU backend like the workers it spawns
    # (_worker_env): on a machine with a chip, a parent that initialized JAX on
    # it would hold it for one model.init — a chip belongs to one process.
    import jax

    jax.config.update("jax_platforms", "cpu")

    from nanofed_tpu.models import get_model

    base_params = jax.tree.map(
        np.asarray, get_model(args.model).init(jax.random.key(args.seed))
    )

    if args.telemetry_dir is None:
        telemetry_dir = _fresh_dir(tmp / "fed_telemetry")
    else:
        telemetry_dir = Path(args.telemetry_dir)
        telemetry_dir.mkdir(parents=True, exist_ok=True)

    # Crash flight recorder: every supervisor lifecycle mark lands in this
    # bounded ring; on reaping a crashed host the ring dumps next to the
    # telemetry (dump() creates missing dirs and never raises — a forensics
    # failure must not break the recovery it documents), and the marks
    # decompose the recovery's MTTR into named phases.
    recorder = FlightRecorder(name="federate-supervisor")

    all_pids: list[int] = []
    t0 = time.time()

    def _retry(seed: int) -> RetryPolicy:
        # Generous on purpose: backoffs ride the VirtualClock (~no real
        # time), and zero lost submits means no client may exhaust while a
        # reroute target is still alive.
        return RetryPolicy(max_attempts=64, base_backoff_s=0.05,
                           max_backoff_s=1.0, multiplier=1.5,
                           budget_s=None, seed=seed)

    def _wait_ready(procs: list, live_hosts: list[int]) -> None:
        deadline = time.time() + args.timeout
        paths = {h: tmp / f"fed_ready_h{h}.json" for h in live_hosts}
        ready: set[int] = set()
        while len(ready) < len(paths):
            for h, p in paths.items():
                if h not in ready and p.exists():
                    ready.add(h)
            for q in procs:
                rc = q.poll()
                if rc is not None:
                    _reap(procs)
                    raise SystemExit(
                        f"federate worker exited rc={rc} during bring-up"
                    )
            if time.time() > deadline:
                _reap(procs)
                raise SystemExit("federate workers not ready within "
                                 f"{args.timeout:.0f}s")
            time.sleep(0.1)

    async def _drive(procs: list, live_hosts: list[int], jobs: list,
                     expect_kill: bool) -> tuple[list, dict]:
        """Run the sub-swarms concurrently with a worker monitor.  The
        monitor's stop decisions are what keep 'zero lost submits' true: a
        pending submit aimed at a doomed fleet is terminated early (and
        re-driven next phase), never left to exhaust its retries as a
        failure."""
        stop_event = asyncio.Event()
        clock = VirtualClock()
        state: dict = {"t_kill": None, "unexpected": None}

        async def monitor() -> None:
            while not stop_event.is_set():
                rcs = [q.poll() for q in procs]
                for h, rc in zip(live_hosts, rcs):
                    if rc is None:
                        continue
                    if rc == HOST_CRASH_RC and expect_kill and h == victim:
                        if state["t_kill"] is None:
                            state["t_kill"] = time.time()
                            recorder.note("kill_detected", host=h, rc=rc)
                            print(f"# host {h} killed by plan (rc={rc}); "
                                  "wire clients rerouting to survivors for "
                                  f"{args.reroute_grace:.1f}s", flush=True)
                    elif rc == PEER_FAILURE_RC and expect_kill:
                        # A survivor's watchdog fired before the grace ended:
                        # stop the swarm now — pending submits terminate
                        # early instead of failing against a dead fleet.
                        stop_event.set()
                        return
                    else:
                        state["unexpected"] = (h, rc)
                        stop_event.set()
                        return
                if state["t_kill"] is not None and (
                    time.time() - state["t_kill"] >= args.reroute_grace
                ):
                    # Reroutes demonstrated live; the remaining population
                    # re-drives against the recovered mesh in phase C.
                    recorder.note("grace_elapsed",
                                  grace_s=args.reroute_grace)
                    stop_event.set()
                    return
                if all(rc is not None for rc in rcs):
                    stop_event.set()
                    return
                await asyncio.sleep(0.2)  # REAL time: process liveness poll

        mon = asyncio.ensure_future(monitor())
        try:
            results = await asyncio.gather(*(
                run_swarm(url, base_params, cfg, clock=clock,
                          stop=stop_event, client_indices=idx)
                for url, cfg, idx in jobs
            ))
        finally:
            stop_event.set()
            mon.cancel()
            try:
                await mon
            except (asyncio.CancelledError, Exception):
                pass
        return results, state

    def _cfg(owner: int, phase_salt: int, failover: tuple[str, ...],
             n_clients: int) -> "SwarmConfig":
        return SwarmConfig(
            num_clients=n_clients,
            submits_per_client=args.submits_per_client,
            arrival="uniform",
            arrival_rate=args.arrival_rate,
            seed=args.seed + 17 * owner + phase_salt,
            retry=_retry(args.seed + 31 * owner + phase_salt),
            client_prefix=f"h{owner}",
            failover_urls=failover,
            connector_limit=256,
            canned_payloads=4,
        )

    # ---- phase A: full mesh, full population -------------------------------
    for h in hosts:
        (tmp / f"fed_ready_h{h}.json").unlink(missing_ok=True)
    print(f"# federate: {P} mesh hosts x wire listeners, {args.clients} wire "
          "clients"
          + (f"; planned host_crash on host {victim} at round "
             f"{args.kill_round}" if kill else ""), flush=True)
    procs = _spawn_federate(
        args, hosts, args.port, phase="a", hb_dir=hb_dir, ckpt_dir=ckpt,
        resume=False, plan_path=plan_path, stop_file=stop_file, tmp=tmp,
        telemetry_dir=telemetry_dir,
    )
    all_pids += [p.pid for p in procs]
    recorder.note("spawned", phase="a", hosts=hosts)
    _wait_ready(procs, hosts)
    recorder.note("fleet_ready", phase="a", hosts=hosts)
    print("# all listeners ready; releasing the swarm", flush=True)

    jobs_a = [
        (urls[h],
         _cfg(h, 0, tuple(urls[j] for j in hosts if j != h), counts[h]),
         None)
        for h in hosts
    ]
    results_a, state_a = asyncio.run(_drive(procs, hosts, jobs_a, kill))
    swarm_a = dict(zip(hosts, results_a))
    if state_a["unexpected"] is not None:
        _reap(procs)
        raise SystemExit(
            f"federate worker host {state_a['unexpected'][0]} exited "
            f"rc={state_a['unexpected'][1]} mid-campaign"
        )

    results_c: dict[int, object] = {}
    survivors = hosts
    recovery = None
    if not kill:
        stop_file.write_text("stop\n")
        _wait(procs, args.timeout)
    else:
        if state_a["t_kill"] is None:
            _reap(procs)
            raise SystemExit("kill was planned but the victim never died — "
                             "lower --kill-round or raise the population")
        # The survivors are blocked in a psum the dead victim will never
        # join: phase A is over for them.  Reap and re-form.
        _reap(procs)
        recorder.note("reaped", victim=victim, phase="a")
        # Dump the ring NEXT TO the telemetry the moment the crashed host is
        # reaped: dump() creates missing parents and never raises, so this
        # cannot break the recovery it documents.
        dump_path = recorder.dump(
            telemetry_dir / FLIGHT_RECORDER_FILENAME,
            extra={"victim": victim, "kill_round": args.kill_round},
        )
        survivors = [h for h in hosts if h != victim]
        rec = GenerationStore(ckpt).latest_complete()
        resumed_round = rec.round_number if rec is not None else 0
        recovery = {
            "victim": victim,
            "kill_round": args.kill_round,
            "reroute_grace_s": args.reroute_grace,
            "resumed_generation": rec.generation if rec is not None else None,
            "resumed_round": resumed_round,
            "hosts_after": len(survivors),
            "flight_recorder": None if dump_path is None else str(dump_path),
        }
        print(f"# phase C: re-forming over hosts {survivors}, resuming at "
              f"round {resumed_round}; re-driving the dead host's "
              f"{counts[victim]} wire clients", flush=True)

        for h in survivors:
            (tmp / f"fed_ready_h{h}.json").unlink(missing_ok=True)
        procs = _spawn_federate(
            args, survivors, args.port + 7, phase="c", hb_dir=hb_dir,
            ckpt_dir=ckpt, resume=True, plan_path=None, stop_file=stop_file,
            tmp=tmp, telemetry_dir=telemetry_dir,
        )
        all_pids += [p.pid for p in procs]
        recorder.note("respawned", phase="c", hosts=survivors)
        _wait_ready(procs, survivors)
        ready_mark = recorder.note("ready", phase="c", hosts=survivors)

        surv_urls = [urls[h] for h in survivors]
        # The victim's whole population re-drives against the survivors: its
        # listener is gone, and anything a survivor accepted after the last
        # committed generation died undrained with phase A (the same
        # at-most-one-block unit hostchaos drills).  Survivors' clients that
        # terminated early when the swarm stopped re-drive too.
        # Stripe the victim's population across the survivors (one job per
        # survivor, disjoint index stripes) instead of pointing 25k clients
        # at one primary URL: rotation-on-failure balances a CRASH, but a
        # re-drive is a planned dispatch — spread it up front.
        owners = []
        jobs_c = []
        for j, s in enumerate(survivors):
            stripe = list(range(counts[victim]))[j::len(survivors)]
            if not stripe:
                continue
            owners.append(victim)
            jobs_c.append((
                urls[s],
                _cfg(victim, 1 + j,
                     tuple(u for u in surv_urls if u != urls[s]),
                     counts[victim]),
                stripe,
            ))
        for h in survivors:
            missing = sorted(
                set(range(counts[h])) - set(swarm_a[h].completed_indices)
            )
            if missing:
                owners.append(h)
                jobs_c.append((
                    urls[h],
                    _cfg(h, 1,
                         tuple(u for u in surv_urls if u != urls[h]),
                         counts[h]),
                    missing,
                ))
        results, state_c = asyncio.run(_drive(procs, survivors, jobs_c, False))
        if state_c["unexpected"] is not None:
            _reap(procs)
            raise SystemExit(
                f"federate worker host {state_c['unexpected'][0]} exited "
                f"rc={state_c['unexpected'][1]} during recovery"
            )
        results_c = {}
        for owner, res in zip(owners, results):
            prev = results_c.get(owner)
            if prev is None:
                results_c[owner] = res
            else:
                # The victim's population runs as one stripe per survivor:
                # fold the stripes back into one per-owner ledger.
                prev.latencies_s += res.latencies_s
                prev.accepted += res.accepted
                prev.duplicates += res.duplicates
                prev.rejected_429 += res.rejected_429
                prev.retries += res.retries
                prev.stale_refreshes += res.stale_refreshes
                prev.failed += res.failed
                prev.terminated_early += res.terminated_early
                prev.reroutes += res.reroutes
                prev.completed_indices += res.completed_indices
        stop_file.write_text("stop\n")
        _wait(procs, args.timeout)
        # MTTR decomposition: "recompile" ends at the recovered fleet's first
        # drained round.  That mark is only observable from the phase-C
        # progress streams after the fact, so it is noted retroactively —
        # its wall stamp mapped onto the monotonic axis via the ready mark.
        first_wall = None
        for h in survivors:
            lines = _read_progress(tmp / f"fed_progress_c_h{h}.jsonl")
            if lines:
                w = lines[0].get("wall_t")
                if w is not None and (first_wall is None or w < first_wall):
                    first_wall = float(w)
        if first_wall is not None:
            recorder.note(
                "first_progress", wall=round(first_wall, 6),
                t_mono=round(
                    ready_mark["t_mono"]
                    + max(0.0, first_wall - ready_mark["t_wall"]), 6,
                ),
            )
        mttr_phases = mttr_decomposition(recorder.snapshot(), [
            ("kill_detected", None),
            ("grace_elapsed", "reroute_grace"),
            ("reaped", "reap"),
            ("respawned", "respawn"),
            ("ready", "bring_up"),
            ("first_progress", "recompile"),
        ])
        recovery["mttr_phases"] = mttr_phases
        recovery["recovery_s"] = round(sum(mttr_phases.values()), 3)
        # Re-dump with the recovery marks included: the reap-time dump froze
        # the crash context; this one appends the phases that followed.
        recorder.dump(
            telemetry_dir / FLIGHT_RECORDER_FILENAME,
            extra={"victim": victim, "kill_round": args.kill_round,
                   "mttr_phases": mttr_phases},
        )

    # ---- accounting + assertions ------------------------------------------
    all_results = list(swarm_a.values()) + list(results_c.values())
    latencies = [x for r in all_results for x in r.latencies_s]
    digest = latency_digest(latencies)
    failed = sum(r.failed for r in all_results)
    reroutes = sum(r.reroutes for r in all_results)
    accepted = sum(r.accepted for r in all_results)
    duplicates = sum(r.duplicates for r in all_results)
    terminated = sum(r.terminated_early for r in all_results)

    lost: dict[int, int] = {}
    for h in hosts:
        done = set(swarm_a[h].completed_indices)
        if h in results_c:
            done |= set(results_c[h].completed_indices)
        missing_n = counts[h] - len(done & set(range(counts[h])))
        if missing_n:
            lost[h] = missing_n

    progress_lines: list[dict] = []
    per_host_phase_a: dict[int, int] = {}
    for phase in ("a", "c"):
        for h in hosts:
            lines = _read_progress(tmp / f"fed_progress_{phase}_h{h}.jsonl")
            if phase == "a":
                per_host_phase_a[h] = len(lines)
            progress_lines += lines
    durations = sorted(ln["duration_s"] for ln in progress_lines)
    median_round = durations[len(durations) // 2] if durations else None
    drained_total = sum(ln["drained"] for ln in progress_lines)
    rerouted_drained = sum(ln.get("rerouted_in", 0) for ln in progress_lines)
    orphans = no_orphans(all_pids)

    assert failed == 0, (
        f"lost submits: {failed} logical submits never got a 200 "
        f"(per-host: {[(h, swarm_a[h].failed) for h in hosts]})"
    )
    assert not lost, (
        f"clients never completed across phases (host -> count): {lost}"
    )
    assert all(per_host_phase_a[h] > 0 for h in hosts), (
        f"a host drained no rounds in phase A: {per_host_phase_a}"
    )
    if kill:
        assert reroutes > 0, (
            "the kill fired but no wire client rerouted — the grace window "
            "closed before any submit hit the dead listener"
        )
        assert rerouted_drained > 0, (
            "no rerouted client's update was ever drained by another host"
        )
    assert not orphans, f"orphan worker processes survived the run: {orphans}"
    if kill:
        # The telemetry dir — including the dead host's stream — must
        # survive a worker crash: the merged timeline is exactly the
        # artifact a post-mortem needs, so losing it to the reap path
        # would defeat the flight recorder's purpose.
        worker_streams = list(telemetry_dir.glob("host_*/telemetry.jsonl"))
        assert telemetry_dir.exists() and len(worker_streams) >= P, (
            f"telemetry did not survive the crash: {telemetry_dir} has "
            f"{len(worker_streams)} worker streams, expected >= {P}"
        )

    # Merged-timeline digest (clock-aligned at the bring-up-barrier epoch):
    # the per-round critical-path table and the submit->round trace
    # resolution ride the artifact — the evidence a reader checks first.
    timeline = federation_timeline(telemetry_dir)

    artifact = {
        "record_type": "federation",
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "seed": args.seed,
        "model": args.model,
        "wire_clients": args.clients,
        "submits_per_client": args.submits_per_client,
        "per_host_clients": counts,
        "topology": {
            "hosts": P,
            "devices_per_process": args.devices_per_process,
            # Hosts-only reduce mesh: one device per process, so the round's
            # cross-host psum compiles to one all-reduce with one replica
            # group (one gloo stream per beat).
            "mesh_shape": [P, 1, 1],
            "wire_ports": [args.wire_port + h for h in hosts],
            "survivors": survivors,
        },
        "rounds": {
            "drained_rounds": len(progress_lines),
            "median_round_s": median_round,
            "rounds_per_sec": (
                round(1.0 / median_round, 4) if median_round else None
            ),
            "round_quota": args.round_quota,
            "min_completion_rate": args.min_completion_rate,
            "updates_aggregated": drained_total,
        },
        "wire": {
            "accepted": accepted,
            "duplicates": duplicates,
            "failed": failed,
            "terminated_early_redriven": terminated,
            "reroutes": reroutes,
            "rerouted_updates_drained": rerouted_drained,
            "submit_latency": digest,
        },
        "chaos": (
            {"plan": json.loads(plan.to_json()), **recovery}
            if kill else None
        ),
        "critical_path": {
            "rounds": timeline["rounds"],
            "segments": timeline.get("segments"),
            "coverage": timeline.get("coverage"),
        },
        "trace_resolution": timeline["trace_resolution"],
        "zero_lost_submits": True,
        "orphans": orphans,
        "platform": "cpu",
        "basis": (
            "multi-process jax.distributed over loopback (gloo CPU "
            "collectives) with a REAL aiohttp wire tier: each mesh host runs "
            "an HTTP listener + device ingest buffer, drains host-locally "
            "(the buffer's batched coefs @ buffer reduce), and joins ONE "
            "cross-host psum per round.  The swarm's arrival schedule and "
            "backoffs ride a VirtualClock; submit latencies are real "
            "wall-clock against live sockets.  Measures the fused "
            "wire-to-mesh PROGRAM and protocol at population scale, not TPU "
            "silicon."
        ),
        "harness": "scripts/multihost_harness.py federate",
        "walltime_s": round(time.time() - t0, 1),
    }
    tel = RunTelemetry(telemetry_dir)
    tel.record(
        "federation",
        wire_clients=args.clients,
        hosts=P,
        survivors=len(survivors),
        rounds=len(progress_lines),
        rounds_per_sec=artifact["rounds"]["rounds_per_sec"],
        p99_submit_s=digest["p99_s"],
        accepted=accepted,
        duplicates=duplicates,
        failed=failed,
        reroutes=reroutes,
        rerouted_updates_drained=rerouted_drained,
        terminated_early_redriven=terminated,
        zero_lost_submits=True,
        host_killed=victim if kill else None,
        kill_round=args.kill_round,
    )
    if kill:
        tel.record(
            "host_failure", kind="host_crash", host=victim,
            round=args.kill_round,
        )
        tel.record("recovery", **recovery)
    tel.close()

    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%S")
    path = out_dir / f"{args.artifact_prefix}_{stamp}_{P}h.json"
    path.write_text(json.dumps(artifact, indent=2) + "\n")
    print(json.dumps(artifact, indent=2))
    print(f"# artifact written to {path}")
    print(f"# telemetry: {telemetry_dir} (digest: python -m nanofed_tpu.cli "
          f"metrics-summary {telemetry_dir})")
    print(f"# merged timeline: python -m nanofed_tpu.cli trace "
          f"{telemetry_dir} --chrome-out /tmp/nanofed_timeline.json")
    print(f"federate OK: {args.clients} wire clients over {P} hosts, "
          f"{len(progress_lines)} drained rounds, p99 submit "
          f"{digest['p99_s']}s, {reroutes} reroutes, zero lost submits")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "mode", choices=["smoke", "bench", "hostchaos", "federate", "worker"],
        help="smoke: 2-process parity vs 1-D reference; bench: 100k-client "
        "throughput artifact; hostchaos: seeded kill-and-recover drill with "
        "elastic mesh re-formation; federate: wire swarm drains straight "
        "into the hierarchical mesh reduce (listener per host, one "
        "cross-host psum per round, optional mid-campaign host kill); "
        "worker: internal (one jax.distributed process)",
    )
    parser.add_argument("--clients", type=int, default=None)
    parser.add_argument("--capacity", type=int, default=8,
                        help="packed samples per client")
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--rounds", type=int, default=3,
                        help="timed rounds (one extra warm-up round compiles)")
    parser.add_argument("--model", default="digits_mlp")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--client-chunk", type=int, default=None)
    parser.add_argument("--num-processes", type=int, default=2)
    parser.add_argument("--devices-per-process", type=int, default=4)
    parser.add_argument("--hosts", type=int, default=1,
                        help="(worker) hosts-axis size of the mesh")
    parser.add_argument("--process-id", type=int, default=0)
    parser.add_argument("--coordinator", default="localhost:12421")
    parser.add_argument("--port", type=int, default=12421)
    parser.add_argument("--timeout", type=float, default=600.0,
                        help="per-phase worker timeout (tier-1-safe)")
    parser.add_argument("--job",
                        choices=["smoke", "bench", "hostchaos", "federate"],
                        default="smoke",
                        help="(worker) which launcher job this worker serves "
                        "— a FULL flag name: an abbreviated --mod* would "
                        "prefix-match argparse's --model and corrupt it")
    parser.add_argument("--out", default=None, help="(worker) result JSON path")
    parser.add_argument("--out-dir", default="runs")
    parser.add_argument("--tmp-dir", default="/tmp/nanofed_multihost")
    # hostchaos: supervisor knobs (fault selection, detection windows, parity)
    parser.add_argument("--plan", default=None,
                        help="(hostchaos) fault-plan JSON; default: generate "
                        "one host fault from --seed")
    parser.add_argument("--host-fault", choices=["crash", "stall"],
                        default="crash",
                        help="(hostchaos) which host fault the generated plan "
                        "draws")
    parser.add_argument("--block-size", type=int, default=2,
                        help="rounds per checkpoint generation (the at-most-"
                        "one-block loss unit)")
    parser.add_argument("--stall-timeout", type=float, default=15.0,
                        help="(hostchaos) heartbeat age that flags a host as "
                        "stalled")
    parser.add_argument("--watchdog-deadline", type=float, default=20.0,
                        help="cross-host dispatch deadline (the bounded "
                        "detection window for a dead/stalled peer)")
    parser.add_argument("--compile-grace", type=float, default=90.0,
                        help="extra watchdog allowance for the first dispatch "
                        "(trace+compile must not read as a dead peer)")
    parser.add_argument("--parity-tol", type=float, default=SMOKE_TOL,
                        help="(hostchaos) max post-recovery loss delta vs the "
                        "unfailed shrunk-mesh reference")
    parser.add_argument("--rejoin-rounds", type=int, default=2,
                        help="(hostchaos) extra rounds after the failed host "
                        "rejoins the mesh (0 disables the rejoin phase)")
    parser.add_argument("--telemetry-dir", default=None,
                        help="(hostchaos/federate) where the supervisor "
                        "writes telemetry.jsonl (default under --tmp-dir)")
    # hostchaos: worker-side identity + wiring (set by the supervisor)
    parser.add_argument("--fault-plan", default=None,
                        help="(worker) fault-plan JSON path")
    parser.add_argument("--host-id", type=int, default=0,
                        help="(worker) LOGICAL host id — stable across "
                        "reshapes, unlike the dense process id")
    parser.add_argument("--hosts-list", default="0",
                        help="(worker) comma-separated logical host ids of "
                        "the current mesh (the commit-marker participant set)")
    parser.add_argument("--hb-dir", default="/tmp/nanofed_multihost/hb")
    parser.add_argument("--ckpt-dir", default="/tmp/nanofed_multihost/ckpt")
    parser.add_argument("--progress", default=None,
                        help="(worker) per-round progress JSONL path")
    parser.add_argument("--resume", action="store_true",
                        help="(worker) resume from the newest complete "
                        "generation in --ckpt-dir")
    # federate: wire tier + round pacing (supervisor) and listener wiring
    # (worker, set by the supervisor)
    parser.add_argument("--wire-port", type=int, default=18480,
                        help="(federate) base HTTP port; host h listens on "
                        "wire-port + h")
    parser.add_argument("--round-quota", type=int, default=1024,
                        help="(federate) accepted updates a host waits for "
                        "before draining its round")
    parser.add_argument("--min-completion-rate", type=float, default=1.0,
                        help="(federate) fraction of --round-quota that "
                        "counts the round COMPLETED in the ledger")
    parser.add_argument("--round-timeout-s", type=float, default=10.0,
                        help="(federate) round beat period: deadlines are "
                        "shared offsets from the bring-up-barrier epoch, so "
                        "hosts dispatch the cross-host psum near-"
                        "simultaneously regardless of quota skew; must stay "
                        "well under XLA's fixed 30s gloo collective timeout")
    parser.add_argument("--ingest-capacity", type=int, default=8192,
                        help="(federate) DeviceIngestBuffer slots per host — "
                        "size for the failover worst case: one survivor "
                        "absorbs a dead host's whole undrained population")
    parser.add_argument("--staleness-window", type=int, default=8,
                        help="(federate) server staleness window; the worker "
                        "floors it at 1 (window 0 clears accepted-but-"
                        "undrained submits on every publish)")
    parser.add_argument("--submits-per-client", type=int, default=1)
    parser.add_argument("--arrival-rate", type=float, default=4000.0,
                        help="(federate) swarm arrivals/s per host on the "
                        "virtual clock")
    parser.add_argument("--max-rounds", type=int, default=10_000,
                        help="(federate) worker round ceiling; the campaign "
                        "normally ends by stop-file consensus when the "
                        "swarm is drained")
    parser.add_argument("--kill-round", type=int, default=None,
                        help="(federate) plan a host_crash at this round; "
                        "omit for a no-chaos campaign")
    parser.add_argument("--kill-host", type=int, default=None,
                        help="(federate) logical host the plan kills "
                        "(default: the last host)")
    parser.add_argument("--reroute-grace", type=float, default=6.0,
                        help="(federate) real seconds of live rerouting to "
                        "survivors after the kill before the swarm pauses "
                        "for mesh re-formation")
    parser.add_argument("--federate-watchdog", type=float, default=240.0,
                        help="(federate) cross-host dispatch deadline — "
                        "generous: round cadence is swarm-driven")
    parser.add_argument("--artifact-prefix", default="federation",
                        help="(federate) artifact filename prefix under "
                        "--out-dir")
    parser.add_argument("--stop-file", default=None,
                        help="(worker) path whose existence votes to stop "
                        "the campaign")
    parser.add_argument("--ready-file", default=None,
                        help="(worker) JSON written once the wire listener "
                        "is up and the mesh barrier has passed")
    args = parser.parse_args(argv)

    if args.clients is None:
        if args.mode == "bench":
            args.clients = 100_000
        elif args.mode == "federate":
            args.clients = 2000
        else:
            args.clients = 16
    if args.mode == "worker":
        return run_worker(args)
    if args.mode == "smoke":
        return run_smoke(args)
    if args.mode == "hostchaos":
        return run_hostchaos(args)
    if args.mode == "federate":
        return run_federate(args)
    return run_bench(args)


if __name__ == "__main__":
    sys.exit(main())
