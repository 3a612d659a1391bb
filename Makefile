# Parity with the reference's Makefile targets (install/test/lint/format/docs/release).

.PHONY: test test-fast lint lint-fed audit-smoke chip-smoke bench-smoke chaos-smoke hostchaos-smoke federation-smoke trace-smoke profile-smoke loadtest-smoke autotune-smoke retune-smoke warm-cache adapter-smoke adapter-evidence fleet-smoke fleet-evidence multihost-smoke multihost-bench tenants-smoke tenants-bench example dryrun dryrun-multichip-2d api-docs notebook accuracy metrics-summary clean

test:
	python -m pytest tests/ -q

test-fast:
	python -m pytest tests/unit -q

lint:
	python -m ruff check nanofed_tpu/ tests/ || true

# fedlint (nanofed_tpu.analysis): JAX-aware static analysis — host syncs in
# traced scope, traced-value branching, PRNG key reuse, missing donation,
# unlocked shared-state mutation, blocking calls in async code.  MUST exit 0;
# intentional sites carry `# fedlint: disable=FEDxxx (reason)` suppressions.
lint-fed:
	python -m nanofed_tpu.analysis nanofed_tpu/

# Program audit (analysis.program_audit): lint the tree AND audit the
# six-variant reference program catalog at the jaxpr/AOT level (collective
# schedules, mesh discipline, donation, dtype drift, host transfers), then
# prove every check fires via the seeded mutation suite.  Tier-1-safe:
# tiny models on the 8-device CPU topology, ~30s, zero execution.
audit-smoke:
	python -m nanofed_tpu.analysis --programs --mutants nanofed_tpu/

# On a machine with a TPU only: exits non-zero when JAX finds none.
chip-smoke:
	python chip_smoke.py

# Fused block against single rounds on a tiny CPU workload (seconds): catches a broken
# fused engine or missing dispatch/host_sync spans in tier-1.  Not a measurement.
bench-smoke:
	python -m pytest tests/integration/test_bench_smoke.py -q -s

# Chaos smoke (nanofed_tpu.faults): a seeded 8-client federation with one
# planned crash + one straggler must COMPLETE every round on a virtual clock
# (tier-1-safe: seconds of real time, determinism from the plan's seed).
chaos-smoke:
	python -m pytest tests/integration/test_chaos.py::test_chaos_smoke -q

# Host-chaos smoke (parallel.resilience + faults host kinds): a REAL
# 2-process kill-and-recover cycle — a seeded plan kills one worker
# mid-round, the supervisor detects it (process exit / frozen heartbeat),
# reaps every survivor, re-forms the mesh over the surviving host set,
# resumes from the newest generation committed by all participants (at most
# one block of rounds re-run), rejoins the failed host, and asserts
# post-recovery loss parity vs an unfailed shrunk-mesh run + zero orphans.
# The telemetry digest at the end proves metrics-summary reads the new
# host_failure / recovery records.
hostchaos-smoke:
	python scripts/multihost_harness.py hostchaos --num-processes 2 \
	  --rounds 6 --block-size 2 --timeout 240 --out-dir /tmp/nanofed_hostchaos_runs
	python -m nanofed_tpu.cli metrics-summary /tmp/nanofed_multihost/telemetry | \
	  python -c "import json,sys; d=json.load(sys.stdin); assert d['host_failures'] and d['recoveries'], d; print('metrics-summary digests host_failure/recovery OK')"

# Federation smoke (the one-stack path): a REAL 2-process jax.distributed
# mesh where each host runs an HTTP listener + device ingest buffer, a
# ~400-client wire swarm (VirtualClock schedule, real sockets) submits
# against the listeners, each round is host-local partial drains joined by
# ONE cross-host psum (communication.federation), and the run asserts every
# host drained rounds + zero lost submits before writing the artifact.  The
# digest check proves metrics-summary reads the new federation record.
federation-smoke:
	python scripts/multihost_harness.py federate --num-processes 2 \
	  --clients 400 --round-quota 100 --ingest-capacity 1024 \
	  --round-timeout-s 20 --timeout 300 --out-dir /tmp/nanofed_federation_runs
	python -m nanofed_tpu.cli metrics-summary /tmp/nanofed_multihost/fed_telemetry | \
	  python -c "import json,sys; d=json.load(sys.stdin); f=d['federations']; assert f['count'] >= 1 and f['zero_lost_submits'], f; print('metrics-summary digests federation OK')"

# Trace smoke (observability.tracing + critical_path): a REAL 2-process
# federate run with per-host telemetry streams, then `nanofed-tpu trace`
# merges them — the Chrome timeline must parse non-empty, every accepted
# submit must resolve to exactly one consuming round (the subcommand's exit
# code enforces it), and each round's critical-path segments must sum to
# >= 95% of its measured walltime.
trace-smoke:
	python scripts/multihost_harness.py federate --num-processes 2 \
	  --clients 200 --round-quota 50 --ingest-capacity 512 \
	  --round-timeout-s 20 --timeout 300 --out-dir /tmp/nanofed_trace_runs \
	  --telemetry-dir /tmp/nanofed_trace_tel
	python -m nanofed_tpu.cli trace /tmp/nanofed_trace_tel \
	  --chrome-out /tmp/nanofed_trace_timeline.json \
	  > /tmp/nanofed_trace_digest.json
	python -c "import json; d = json.load(open('/tmp/nanofed_trace_digest.json')); t = json.load(open('/tmp/nanofed_trace_timeline.json')); assert t['traceEvents'], 'empty merged timeline'; r = d['trace_resolution']; assert r['resolved'] and r['consumed_submits'] > 0, r; c = d['coverage']; assert c['min'] >= 0.95, c; print('trace-smoke OK:', r['consumed_submits'], 'submits resolved across', c['rounds'], 'rounds; coverage min', c['min'])"

# Loadtest smoke (nanofed_tpu.loadgen): a ~200-client synthetic swarm on a
# VirtualClock drives BOTH serving paths — per-submit and batched device
# ingest — against a live HTTPServer; the loadtest artifact must parse, p99
# submit latency must be finite, and no submit may be lost outright.
# Tier-1-safe: virtual time, seconds of real time, seeded determinism.
loadtest-smoke:
	python -m pytest tests/integration/test_loadtest_smoke.py -q

# Tenants smoke (nanofed_tpu.service): two tenants — different models,
# different serving paths — run CONCURRENTLY on one shared transport and one
# VirtualClock while a seeded wire-fault storm (drops, lost-ACK duplicate
# retry storms, delays) targets exactly one of them; the untargeted tenant must
# complete every round with zero lost submits, the chaos counters must show
# the storm hit the targeted tenant only, and metrics-summary must digest
# the per-tenant telemetry records.  The slow-marked 3-tenant
# concurrent-vs-sequential leg runs here too (tier-1 excludes it).
tenants-smoke:
	python -m pytest tests/integration/test_tenant_service.py -q -p no:cacheprovider

# The multi-tenant evidence artifact: >= 3 concurrent tenants (distinct
# models/algorithms), aggregate rounds/sec vs the sequential baseline, and
# per-tenant p99 submit latency while a chaos storm targets one tenant ->
# runs/tenants_*.json.  Exit 1 if any untargeted tenant lost rounds/submits.
# SYSTEM clock on purpose: the concurrency win is real overlapped waiting —
# a VirtualClock compresses the very idle time the service exists to overlap.
tenants-bench:
	python -m nanofed_tpu.cli tenants --tenants 3 --rounds 4 --clients 80 \
	  --arrival uniform --rate 30 --seed 14

# Autotune smoke (nanofed_tpu.tuning): sweep a tiny MLP config space on CPU
# with the compiler's cost model — a winner must be chosen via AOT analysis
# alone (zero round executions), the ranked autotune_*.json artifact must
# parse with its scoring basis stated, the fused q8 aggregation epilogue must
# show a measured bytes-accessed reduction in the catalog's cost table, and a
# repeat sweep must hit the result cache with ZERO compiles.  Tier-1-safe.
autotune-smoke:
	python -m pytest tests/integration/test_autotune_smoke.py -q

# Retune smoke (nanofed_tpu.tuning.retuner): the closed online-retuning loop —
# measured-walltime re-ranking of the sweep table, hysteresis holds, a swap
# landing at a block boundary with a bit-identical loss trajectory, refused
# swaps keeping the incumbent live, and the measured numbers written back into
# the cached autotune entry — plus the compile-cache lifecycle units
# (manifest/warm/verify, hit-miss counters, budget-pruned sweeps).  Runs the
# slow-marked closed-loop legs too, so it compiles a handful of round programs.
retune-smoke:
	python -m pytest tests/integration/test_retune.py \
	  tests/unit/tuning/test_retuner.py tests/unit/tuning/test_compile_cache.py \
	  -q -p no:cacheprovider

# Warm the shippable persistent compilation cache (tuning.compile_cache.warm):
# pre-compile the candidate program set into the compile cache
# ($$JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) with a toolchain
# manifest, ready to tar to the accelerator host.  Verify a shipped cache with
# `python scripts/warm_cache.py --verify-only`.
warm-cache:
	python scripts/warm_cache.py

# Adapter smoke (nanofed_tpu.adapters): the compile-heavy transformer/adapter
# integration legs — strict 2-D frozen-base federation with a descending loss,
# fused-vs-single adapter-block parity, checkpoint resume, the adapter program
# in the cost catalog, run_experiment/CLI --adapter-rank — run here UN-filtered
# (they are slow-marked out of tier-1: a transformer round-program compile
# costs tens of seconds the 870s budget does not have), plus the fast LoRA
# algebra / codec / wire-contract units as a sanity floor.
adapter-smoke:
	python -m pytest tests/integration/test_adapter_federation.py \
	  tests/unit/adapters tests/unit/models/test_transformer.py \
	  tests/unit/communication/test_adapter_codec.py -q -p no:cacheprovider

# Fleet smoke (nanofed_tpu.fleet): a 3-tier heterogeneous fleet — rank-4
# topk8 phones, rank-8 q8 edge boxes, rank-32 f32 silos — drives one live
# fleet server on a VirtualClock: tier-routed model payloads, mixed-codec
# submits on one endpoint, per-tier byte/latency accounting, zero lost
# submits, and BOTH aggregation routes (dense reference vs rank-bucketed
# padded einsum) parity-asserted every round.  The compile-heavy convergence
# comparison legs are slow-marked (tier-1 excludes them) and run here
# un-filtered, plus the fleet unit suites as a sanity floor.
fleet-smoke:
	python -m pytest tests/integration/test_fleet_federation.py \
	  tests/unit/fleet -q -p no:cacheprovider

# The committed fleet evidence artifacts (runs/fleet_r16_*.json +
# runs/fedbuff_staleness_r16.json): the mixed-tier convergence-vs-bytes
# comparison against a homogeneous max-rank baseline, the live-server
# per-tier p99 swarm leg, and the FedBuff staleness-exponent ablation over
# the r15 delay scenario.  A few minutes on CPU — not a CI job.
fleet-evidence:
	python -m nanofed_tpu.fleet.evidence

# The committed evidence artifacts (runs/adapter_r15_*.json +
# runs/fedbuff_adapter_r15_*.json): rank-8 transformer adapter federation
# (rank 8 is the stated headline rank — rank 16 lands at 9.97x, under the
# >= 10x wire-bytes bar) with measured q8/topk wire bytes full-vs-adapter,
# the flagship v5e memory-binding sweep (AOT compiles, ~2 min/candidate),
# and the FedBuff heterogeneous-delay scenario run.  Minutes — not a CI job.
adapter-evidence:
	python -m nanofed_tpu.adapters.evidence

# Multi-host smoke (parallel.mesh hosts axis): a REAL 2-process
# jax.distributed CPU run (gloo collectives, subprocess-spawned, tier-1-safe
# timeout) of the hierarchical 3-axis round program — per-host data sharding,
# host-local psum then one cross-host psum — asserted for trajectory parity
# (losses + final params to float tolerance) against a single-process 1-D
# mesh running the byte-identical workload.
multihost-smoke:
	python scripts/multihost_harness.py smoke --timeout 300
	JAX_PLATFORMS=cpu python -m pytest tests/unit/parallel/test_host_mesh.py -m slow -p no:cacheprovider

# The pod-scale artifact: 100k streamed clients (chunked streaming x
# multi-process) -> runs/multihost_*.json with rounds/sec + clients/sec and
# the process_count/hosts topology block.  Minutes, not seconds — not tier-1.
multihost-bench:
	python scripts/multihost_harness.py bench

# Compile-only cost profile on CPU (observability.profiling): the `profile`
# subcommand must produce a non-empty roofline table — single step, fused
# block, and SCAFFOLD programs — without running a federation.
profile-smoke:
	python -m nanofed_tpu.cli profile --model digits_mlp --clients 8 \
	  --batch-size 16 --rounds-per-block 2 | tee /tmp/profile_smoke.txt
	@grep -q "round_block" /tmp/profile_smoke.txt
	@grep -q "scaffold_round_step" /tmp/profile_smoke.txt
	@grep -q "roofline basis" /tmp/profile_smoke.txt

example:
	python examples/mnist/run_experiment.py --synthetic

dryrun:
	python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"

# 1-D vs 2-D (clients x model) mesh round-step parity on the virtual 8-device
# CPU mesh: asserts loss parity + model-sharded output layout and prints the
# walltime / model-state-memory comparison (FSDP parameter sharding).
dryrun-multichip-2d:
	python -c "from __graft_entry__ import dryrun_multichip_2d; dryrun_multichip_2d(8)"

api-docs:
	python scripts/gen_api_docs.py

notebook:
	python scripts/build_notebook.py

accuracy:
	python scripts/record_accuracy.py

# Digest the most recent run's telemetry.jsonl (phase durations, round outcomes,
# headline counters) — see docs/observability.md.
metrics-summary:
	python -m nanofed_tpu.cli metrics-summary runs

clean:
	rm -rf runs/ .pytest_cache/ $$(find . -name __pycache__ -type d)
