"""Benchmark: federated MNIST round wall-clock vs the reference, at two scales.

One process that measures on the TPU it finds, or exits non-zero: there is no probe,
no retry, no CPU fallback and no extrapolation.  A record that says ``"platform":
"tpu"`` was measured on that chip by this run.

Two workloads, one JSON line each on stdout, then one compact SUMMARY line (the
driver records the LAST line — kept a few hundred bytes so a tail buffer can never
truncate it mid-JSON; see ``compact_summary``):

1. **Parity** (`mnist_fedavg_round_walltime_2clients_parity`): the reference's only
   recorded perf number is the MNIST tutorial's round-0 wall-clock: 53.48 s for
   2 clients x 2 local epochs (12k + 4k samples, batch 64, SGD lr=0.1, ~1.2M-param CNN)
   on CPU (``examples/mnist/tutorial.ipynb`` cell-17; see BASELINE.md).  This workload
   is the SAME logical round — identical model architecture, client sample counts,
   local epochs, batch size, optimizer, fp32 compute — as one jitted SPMD round.

2. **Flagship** (`mnist_fedavg_round_walltime_1000clients`, printed LAST): the
   BASELINE.json north star — 1000 clients (60k MNIST-shaped samples, 60 each),
   2 local epochs, batch 64, MNIST CNN, bf16 compute, ``client_chunk=125`` sequential
   chunking (clients >> chips).  The reference never ran this scale; ``vs_baseline``
   scales its tutorial number by sample-passes (53.48 s / 32k passes -> 120k passes
   = 200.55 s extrapolated CPU time) and says so in the ``baseline_basis`` field.
   Extra fields: rounds/sec, analytic-FLOP MFU estimate against the peak the
   ``device_kind`` publishes (``observability.profiling.peaks_for_device_kind``), a
   ``cost_analysis`` record with the COMPILER's own FLOP/byte numbers for the headline
   block program, and the autotuner's verdict on ``client_chunk``.

Parity is the MEDIAN of 3 timed steady-state rounds; the flagship is one fused
3-round block (block walltime / 3).  Compile is excluded and reported in the
``phases`` digest.  Data is synthetic MNIST-shaped, generated from a seed.  The
persistent compilation cache lives where ``utils.platform.compilation_cache_dir``
says.  Cells, bounds and ``BENCHMARK.json`` are the benchmark PR's (ROADMAP S1).
"""

from __future__ import annotations

import contextlib
import json
import os
import time

REFERENCE_ROUND_S = 53.48  # tutorial.ipynb cell-17: "Completed train_round in 53.48s"
METRIC_PARITY = "mnist_fedavg_round_walltime_2clients_parity"
METRIC_FLAGSHIP = "mnist_fedavg_round_walltime_1000clients"

# Reference throughput basis for the flagship scale-up: 53.48 s bought 2 clients x
# 2 epochs x (12k + 4k) samples = 32k sample-passes.  The flagship round is 1000
# clients x 2 epochs x 60 samples = 120k sample-passes.
PARITY_SAMPLE_PASSES = 2 * (12_000 + 4_000)
FLAGSHIP_SAMPLE_PASSES = 2 * 60_000
REFERENCE_FLAGSHIP_S = REFERENCE_ROUND_S * FLAGSHIP_SAMPLE_PASSES / PARITY_SAMPLE_PASSES

# Analytic per-sample training FLOPs for the MNIST CNN (NHWC, fwd 2*MACs, bwd ~2x fwd):
#   conv1 26x26x32 @3x3x1 = 389,376 + conv2 24x24x64 @3x3x32 = 21,233,664
#   + fc1 9216x128 = 2,359,296 + fc2 128x10 = 2,560  ->  23.98 MFLOP fwd
CNN_FWD_FLOPS_PER_SAMPLE = 2 * (26 * 26 * 32 * 9 * 1 + 24 * 24 * 64 * 9 * 32 + 9216 * 128 + 128 * 10)
CNN_TRAIN_FLOPS_PER_SAMPLE = 3 * CNN_FWD_FLOPS_PER_SAMPLE

# Strict execution mode (analysis subsystem): run every timed dispatch under
# jax.transfer_guard("disallow") so an implicit host transfer in the measured
# hot path fails the bench instead of silently inflating the headline.  Run
# records carry "strict": true when enabled.
BENCH_STRICT = os.environ.get("NANOFED_BENCH_STRICT", "") not in ("", "0")


def _strict_ctx():
    """The strict-mode transfer guard for a measured dispatch, or a no-op context.
    Inputs are device-resident before entry, so any implicit transfer the guard
    trips on is a real hot-path regression."""
    if not BENCH_STRICT:
        return contextlib.nullcontext()
    from nanofed_tpu.analysis.contracts import strict_mode

    return strict_mode()


def _timed_rounds(step, params, sos, data, weights, stack_rngs, padded, log_stage, t0,
                  reps: int = 3, tracer=None):
    """Time ``reps`` steady-state rounds (caller has already run the compile/warm-up
    round); returns the np.ndarray of per-round wall-clock seconds.  With a
    ``tracer`` (observability ``SpanTracer``), each round is additionally recorded
    as a ``round`` span so the workload's phase summary carries per-round timings."""
    import jax
    import numpy as np

    times = []
    for r in range(1, reps + 1):
        span = (
            tracer.span("round", rep=r) if tracer is not None
            else contextlib.nullcontext()
        )
        # Key derivation is an explicit h2d and stays OUTSIDE the guarded
        # dispatch (strict mode would rightly flag it inside).
        rngs = stack_rngs(jax.random.key(r), padded)
        t = time.perf_counter()
        with span:
            with _strict_ctx():
                res = step(params, sos, data, weights, rngs)
            params, sos = res.params, res.server_opt_state
            jax.block_until_ready(params)
        times.append(time.perf_counter() - t)
        log_stage(f"round {r}: {times[-1]:.4f}s", t0=t0)
    return np.asarray(times)


def finalize_measurement(times, ref_s: float, payload: dict) -> dict:
    """Fill value/vs_baseline/round_times_s from the timed rounds (median).

    Module-level (pure, numpy-only) so the arithmetic is unit-testable without a
    measurement run."""
    import numpy as np

    value = float(np.median(times))
    payload.update(
        value=round(value, 4),
        vs_baseline=round(ref_s / value, 2),
        round_times_s=[round(float(x), 4) for x in times],
        aggregation=f"median of {len(times)} steady-state rounds",
    )
    return payload


def compact_summary(results: list) -> dict:
    """One SHORT driver-parseable record distilling every workload, printed as the
    very LAST stdout line: the flagship headline in the driver schema plus a compact
    per-metric digest, a few hundred bytes no matter how rich the full records above
    it are (a ~2.3 kB flagship line was once truncated mid-JSON by a tail buffer).

    Module-level and pure so the driver-facing shape is unit-testable."""
    by_metric = {r["metric"]: r for r in results}
    flagship = by_metric[METRIC_FLAGSHIP]
    out = {
        "metric": METRIC_FLAGSHIP,
        "value": flagship["value"],
        "unit": flagship["unit"],
        "vs_baseline": flagship["vs_baseline"],
        "platform": flagship["platform"],
        "device_kind": flagship["device_kind"],
        "devices": flagship["devices"],
        "summary": True,
    }
    for key in ("strict", "est_mfu_pct", "est_mfu_pct_cost_basis",
                "est_mfu_pct_cost_basis_tuned"):
        if key in flagship:
            out[key] = flagship[key]
    if "tuned_config" in flagship:
        # Compact tuner digest: which config the cost model endorsed and
        # whether it was measured — a handful of short keys, tail-buffer safe.
        tc = flagship["tuned_config"]
        out["tuned"] = {
            k: tc[k]
            for k in ("client_chunk", "rounds_per_block", "used", "measured")
            if k in tc
        }
        if "tuned_value" in flagship:
            out["tuned"]["value"] = flagship["tuned_value"]
    if "phases" in flagship:
        # Compact round-phase digest (observability spans): phase -> total seconds.
        out["phases"] = {
            name: round(digest["total_s"], 3)
            for name, digest in flagship["phases"].items()
        }
    parity = by_metric.get(METRIC_PARITY)
    if parity is not None:
        out["parity"] = {
            "value": parity["value"], "vs_baseline": parity["vs_baseline"],
        }
    return out


def flagship_autotune(
    model, training, n_clients: int, capacity: int, sample_shape: tuple,
    n_dev: int, padded: int, default_chunk: int, r_block: int, cache_dir: str,
) -> dict:
    """Run the compile-only cost-model sweep over the flagship's tunable axes
    and shape the record fields: ``autotune`` (winner, basis, top candidates,
    sweep economics) and ``tuned_config`` (the winner + whether the tuner or
    the hand-picked default won).  The swept axis is ``client_chunk`` (the
    divisor ladder of the per-device client count, plus the full vmap) at the
    flagship's block length; batch size and mesh shape stay pinned to the
    flagship configuration so the comparison isolates the chunking knob.  The
    sweep table is kept beside the compiled programs in ``cache_dir``."""
    from nanofed_tpu.tuning import PopulationSpec, TuningSpace, autotune

    per_dev = max(1, padded // n_dev)
    divs = sorted({
        d for d in range(1, per_dev) if per_dev % d == 0
    } | {default_chunk})
    if len(divs) > 4:
        divs = sorted({default_chunk, divs[0], divs[len(divs) // 2], divs[-1]})
    space = TuningSpace(
        client_chunks=tuple(divs) + (None,),
        rounds_per_blocks=(r_block,),
        model_shards=(1,),
        batch_sizes=(training.batch_size,),
    )
    pop = PopulationSpec(
        num_clients=n_clients, capacity=capacity, sample_shape=sample_shape
    )
    result = autotune(
        model, pop, training, num_rounds=r_block, space=space,
        include_epilogues=False, cache_dir=cache_dir,
    )
    winner = result.winner.to_dict()
    default_cfg = {
        "client_chunk": default_chunk, "rounds_per_block": r_block,
        "model_shards": 1, "batch_size": training.batch_size,
    }
    feasible = [o for o in result.outcomes if o.feasible]
    return {
        "autotune": {
            "winner": winner,
            "default": default_cfg,
            "scoring_basis": result.scoring_basis,
            "cache_hit": result.cache_hit,
            "compiles": result.compiles,
            "compile_seconds_total": round(result.compile_seconds_total, 2),
            # Sweep economics under a compile budget (NANOFED_AUTOTUNE_COMPILE_
            # BUDGET / _CANDIDATE_DEADLINE): how many candidates were skipped,
            # and — when a compile blew the per-candidate deadline — WHICH
            # program wedged, so a truncated table names its own blind spot.
            **({"skipped": result.skipped} if result.skipped else {}),
            **({"wedged_at": result.wedged_at}
               if result.wedged_at is not None else {}),
            **({"artifact": result.artifact_path}
               if result.artifact_path else {}),
            "top_candidates": [
                {
                    **o.config.to_dict(), "score": o.score,
                    # Per-candidate compile walltime: the price of ADMITTING
                    # this candidate to the sweep (None on cache hits).
                    "compile_seconds": o.cost.get("compile_seconds"),
                }
                for o in feasible[:3]
            ],
        },
        "tuned_config": {
            **winner,
            # "used" says whose config the tuner endorses: "default" when the
            # winner IS the hand-picked flagship config, "tuned" when the cost
            # model picked something else; "measured" flips to True only when
            # the tuned config got its own fused-block measurement.
            "used": "default" if winner == default_cfg else "tuned",
            "measured": False,
        },
    }


def main() -> None:
    t0 = time.time()
    from nanofed_tpu.utils.platform import (
        enable_compilation_cache,
        log_stage,
        require_tpu,
    )

    cache_dir = enable_compilation_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    devices, peaks = require_tpu()
    log_stage(
        f"backend up: {len(devices)}x {devices[0].device_kind}, jax {jax.__version__}, "
        f"compilation cache at {cache_dir}", t0=t0,
    )

    from nanofed_tpu.aggregation import compute_weights, fedavg_strategy
    from nanofed_tpu.data import pack_clients, synthetic_classification
    from nanofed_tpu.models import get_model
    from nanofed_tpu.observability import SpanTracer
    from nanofed_tpu.observability.profiling import profile_program
    from nanofed_tpu.parallel import (
        build_round_block,
        build_round_step,
        host_axis_size,
        init_server_state,
        make_mesh,
        mesh_shape,
        pad_client_count,
        pad_clients,
        replicated_sharding,
        shard_client_data,
        stack_round_keys,
    )
    from nanofed_tpu.trainer import TrainingConfig, stack_rngs

    model = get_model("mnist_cnn")
    mesh = make_mesh()
    n_dev = len(mesh.devices.flat)
    repl = replicated_sharding(mesh)
    strategy = fedavg_strategy()

    # Every record names the device it ran on and its host/process geometry: a
    # reader of the artifact alone can tell a pod measurement from a one-chip one.
    device_block = {
        "platform": str(devices[0].platform),
        "device_kind": str(devices[0].device_kind),
        "devices": n_dev,
        "mesh_shape": list(mesh_shape(mesh)),
        "topology": {
            "process_count": jax.process_count(),
            "hosts": host_axis_size(mesh),
            "devices": n_dev,
            "mesh_shape": list(mesh_shape(mesh)),
        },
    }
    reps = 3

    def prepare(total, parts, batch):
        ds = synthetic_classification(total, 10, (28, 28, 1), seed=0)
        data = pack_clients(ds, parts, batch_size=batch)
        padded = pad_client_count(len(parts), n_dev)
        data = pad_clients(data, padded)
        data = shard_client_data(data, mesh)
        num_samples = jnp.asarray(np.asarray(data.mask).sum(axis=1))
        weights = compute_weights(num_samples) * (num_samples > 0)
        return data, weights, padded

    def fresh_state():
        params = jax.device_put(model.init(jax.random.key(0)), repl)
        return params, jax.device_put(init_server_state(strategy, params), repl)

    def measure(name, step, data, weights, padded, tracer):
        params, sos = fresh_state()
        log_stage(f"{name}: warm-up round (XLA compile)", t0=t0)
        with tracer.span("compile"):
            res = step(params, sos, data, weights, stack_rngs(jax.random.key(0), padded))
            params, sos = res.params, res.server_opt_state
            jax.block_until_ready(params)
        log_stage(f"{name}: warm-up done; timing {reps} steady-state rounds", t0=t0)
        return _timed_rounds(step, params, sos, data, weights, stack_rngs, padded,
                             log_stage, t0, reps=reps, tracer=tracer)

    def measure_fused(name, block, data, num_samples, mask, r_block, tracer):
        """Fused-engine measurement: one R-round device block, timed as a whole.

        The warm-up block pays the scan compile; the timed block then splits into
        the two host phases the fused engine is designed around — ``dispatch``
        (enqueue the block; returns without blocking) and ``host_sync`` (the one
        ``block_until_ready`` at the block boundary).  Returns the
        per-round-equivalent time (block walltime / R): rounds inside a block have
        no host-observable boundaries to time individually."""
        params, sos = fresh_state()
        mask_r = jnp.asarray(np.tile(mask, (r_block, 1)))
        lr = jnp.ones(r_block, jnp.float32)
        log_stage(f"{name}: warm-up {r_block}-round block (XLA compile)", t0=t0)
        with tracer.span("compile", rounds=r_block):
            res = block(params, sos, data, num_samples,
                        stack_round_keys(0, list(range(r_block))), lr,
                        cohort_mask=mask_r)
            params, sos = res.params, res.server_opt_state
            jax.block_until_ready(params)
        log_stage(f"{name}: warm-up done; timing one fused {r_block}-round block",
                  t0=t0)
        keys = stack_round_keys(0, list(range(r_block, 2 * r_block)))
        t = time.perf_counter()
        with tracer.span("dispatch", rounds=r_block):
            # Strict mode proves the fused dispatch itself performs zero
            # implicit transfers — every input above is already device-resident.
            with _strict_ctx():
                res = block(params, sos, data, num_samples, keys, lr,
                            cohort_mask=mask_r)
            params, sos = res.params, res.server_opt_state
        with tracer.span("host_sync", rounds=r_block):
            jax.block_until_ready(params)
        total = time.perf_counter() - t
        log_stage(f"{name}: fused block {total:.4f}s ({total / r_block:.4f}s/round)",
                  t0=t0)
        return total / r_block

    # --- parity: 2 clients with 12k / 4k MNIST-shaped samples, fp32 (the reference
    # number was measured in fp32 torch, and vs_baseline claims the SAME logical
    # workload — bf16 is benchmarked in the flagship line instead).
    training = TrainingConfig(batch_size=64, local_epochs=2, learning_rate=0.1)
    tracer = SpanTracer(registry=False)
    with tracer.span("prepare"):
        data, weights, padded = prepare(
            16_000, [np.arange(0, 12_000), np.arange(12_000, 16_000)], 64
        )
        step = build_round_step(model.apply, training, mesh, strategy, donate=True)
    times = measure("parity", step, data, weights, padded, tracer)
    parity = finalize_measurement(times, REFERENCE_ROUND_S, {
        "metric": METRIC_PARITY, "unit": "s", **device_block,
    })
    if BENCH_STRICT:
        parity["strict"] = True
    parity["phases"] = tracer.phase_summary()
    print(json.dumps(parity), flush=True)

    # --- flagship: 1000 clients x 60 samples, 2 local epochs, bf16, client_chunk=125
    # (8 sequential chunks of a 125-wide vmap per device), FUSED round blocks
    # (parallel.multi_round): R rounds scan on-device inside one jit, so the
    # per-round Python dispatch / block_until_ready / metrics transfer is paid once
    # per block.  Override R with NANOFED_BENCH_ROUNDS_PER_BLOCK.
    training = TrainingConfig(
        batch_size=64, local_epochs=2, learning_rate=0.1, compute_dtype="bfloat16"
    )
    tracer = SpanTracer(registry=False)
    n_clients, chunk = 1000, 125
    r_block = int(os.environ.get("NANOFED_BENCH_ROUNDS_PER_BLOCK") or reps)
    with tracer.span("prepare"):
        data, weights, padded = prepare(
            60 * n_clients,
            [np.arange(i * 60, (i + 1) * 60) for i in range(n_clients)], 64,
        )
        num_samples = jnp.asarray(np.asarray(data.mask).sum(axis=1), dtype=jnp.float32)
        mask = np.asarray(num_samples > 0, dtype=np.float32)

        def build_block(client_chunk):
            return build_round_block(
                model.apply, training, mesh, strategy,
                num_clients=n_clients, padded_clients=padded,
                client_chunk=client_chunk, collect_client_detail=False, donate=True,
            )

        block = build_block(chunk)
    value = measure_fused("flagship", block, data, num_samples, mask, r_block, tracer)
    out = {
        "metric": METRIC_FLAGSHIP,
        "unit": "s",
        **device_block,
        "num_clients": n_clients,
        "client_chunk": chunk,
        "compute_dtype": "bfloat16",
        "rounds_per_block": r_block,
        "baseline_basis": (
            f"reference tutorial 53.48s / {PARITY_SAMPLE_PASSES} sample-passes "
            f"scaled to {FLAGSHIP_SAMPLE_PASSES} passes = {REFERENCE_FLAGSHIP_S:.2f}s CPU"
        ),
        "value": round(value, 4),
        "vs_baseline": round(REFERENCE_FLAGSHIP_S / value, 2),
        # Fused blocks have no host-observable per-round boundaries: the headline
        # is block walltime / R, and the aggregation label says so.
        "aggregation": f"one fused {r_block}-round block (block walltime / rounds)",
        "rounds_per_sec": round(1.0 / value, 3),
    }
    if BENCH_STRICT:
        out["strict"] = True
    flops = CNN_TRAIN_FLOPS_PER_SAMPLE * FLAGSHIP_SAMPLE_PASSES
    out["est_mfu_pct"] = round(100 * flops / value / (peaks.flops_per_s * n_dev), 2)
    out["mfu_basis"] = (
        f"analytic {flops / 1e12:.2f} TFLOP/round (3x fwd MACs) over {n_dev} "
        f"chip(s); peak: {peaks.basis}"
    )

    def block_cost(name, blk):
        """The COMPILER's cost record for a block program (XLA cost_analysis /
        memory_analysis).  The AOT lower+compile hits the persistent compilation
        cache the warm-up populated, so this costs a deserialize, not a second
        full compile."""
        p0, s0 = fresh_state()
        return profile_program(
            name, blk, p0, s0, data, num_samples,
            stack_round_keys(0, list(range(r_block))),
            jnp.ones(r_block, jnp.float32), None,
            jnp.asarray(np.tile(mask, (r_block, 1))), None,
            rounds=r_block, attrs={"clients": n_clients},
        )

    report = block_cost("flagship_round_block", block)
    out["cost_analysis"] = report.to_dict()
    log_stage(
        f"cost profile: {report.flops / r_block:.3g} compiler FLOPs/round/device, "
        f"peak {report.peak_bytes / 1e6:.1f} MB, AI {report.arithmetic_intensity:.2f} "
        f"-> {report.verdict} (ready in {report.compile_seconds:.2f}s)", t0=t0,
    )
    cost_mfu = report.mfu(value * r_block)
    if cost_mfu is not None:
        out["est_mfu_pct_cost_basis"] = round(100 * cost_mfu, 2)

    # Cost-model autotune (nanofed_tpu.tuning): sweep client_chunk at the headline
    # block length with the compiler's cost model and record the winner as
    # `tuned_config`; a winner that DIFFERS from the default is measured next to it
    # (`tuned_value`, `est_mfu_pct_cost_basis_tuned`).  NANOFED_BENCH_AUTOTUNE=0
    # disables.
    if os.environ.get("NANOFED_BENCH_AUTOTUNE", "1") not in ("", "0"):
        out.update(flagship_autotune(
            model=model, training=training, n_clients=n_clients,
            capacity=int(data.x.shape[1]),
            sample_shape=tuple(int(d) for d in data.x.shape[2:]),
            n_dev=n_dev, padded=padded, default_chunk=chunk, r_block=r_block,
            cache_dir=cache_dir,
        ))
        if out["tuned_config"]["used"] == "tuned":
            t_cand = out["tuned_config"]
            log_stage(f"measuring tuned config {t_cand} next to the default", t0=t0)
            block_tuned = build_block(t_cand["client_chunk"])
            tuned_value = measure_fused(
                "flagship-tuned", block_tuned, data, num_samples, mask, r_block,
                tracer,
            )
            out["tuned_value"] = round(tuned_value, 4)
            out["tuned_config"]["measured"] = True
            mfu_t = block_cost("flagship_round_block_tuned", block_tuned).mfu(
                tuned_value * r_block
            )
            if mfu_t is not None:
                out["est_mfu_pct_cost_basis_tuned"] = round(100 * mfu_t, 2)
    out["phases"] = tracer.phase_summary()
    print(json.dumps(out), flush=True)

    log_stage(f"done in {time.time() - t0:.1f}s total", t0=t0)
    # Very last line: the compact driver-facing digest.
    print(json.dumps(compact_summary([parity, out])), flush=True)


if __name__ == "__main__":
    main()
