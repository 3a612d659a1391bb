"""Fused-vs-single-round smoke (the `make bench-smoke` target).

R single-round steps (one dispatch + one block_until_ready each) against one fused
R-round block (one dispatch + one sync total), on a tiny CPU workload.  This is a
PLUMBING test, not a benchmark: it pins that the fused engine runs end to end, that
its phase spans (dispatch / host_sync) record, and that the fused block has not
regressed to absurdity relative to the single-round path — so a broken block surfaces
in tier-1 and not in a chip run of `benchmark/run.py`.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from nanofed_tpu.aggregation import compute_weights, fedavg_strategy
from nanofed_tpu.data import federate, synthetic_classification
from nanofed_tpu.models import get_model
from nanofed_tpu.observability import SpanTracer
from nanofed_tpu.parallel import (
    build_round_block,
    build_round_step,
    init_server_state,
    make_mesh,
    shard_client_data,
    stack_round_keys,
)
from nanofed_tpu.trainer import TrainingConfig, stack_rngs

R = 4


def test_bench_smoke_fused_vs_single_round(devices):
    m = get_model("mlp", in_features=8, hidden=16, num_classes=4)
    ds = synthetic_classification(256, 4, (8,), seed=0)
    cd = federate(ds, num_clients=8, scheme="iid", batch_size=32, seed=0)
    cfg = TrainingConfig(batch_size=32, local_epochs=1)
    strat = fedavg_strategy()
    mesh = make_mesh()
    data = shard_client_data(cd, mesh)
    ns = jnp.asarray(cd.num_samples, dtype=jnp.float32)
    weights = compute_weights(ns)
    tracer = SpanTracer(registry=False)

    # --- single-round path: R dispatches, R host syncs --------------------
    step = build_round_step(m.apply, cfg, mesh, strat)
    params = m.init(jax.random.key(0))
    sos = init_server_state(strat, params)
    res = step(params, sos, data, weights, stack_rngs(jax.random.key(99), 8))
    jax.block_until_ready(res.params)  # compile warm-up
    params, sos = res.params, res.server_opt_state
    t0 = time.perf_counter()
    for r in range(R):
        res = step(params, sos, data, weights,
                   stack_rngs(jax.random.fold_in(jax.random.key(0), r), 8))
        params, sos = res.params, res.server_opt_state
        jax.block_until_ready(params)
    single_s = time.perf_counter() - t0
    single_loss = float(res.metrics["loss"])

    # --- fused path: one dispatch, one host sync for the same R rounds ----
    block = build_round_block(
        m.apply, cfg, mesh, strat, num_clients=8, padded_clients=8,
        collect_client_detail=False,
    )
    params = m.init(jax.random.key(0))
    sos = init_server_state(strat, params)
    mask = jnp.ones((R, 8))
    bres = block(params, sos, data, ns, stack_round_keys(1, range(R)),
                 jnp.ones(R), cohort_mask=mask)
    jax.block_until_ready(bres.params)  # compile warm-up
    t0 = time.perf_counter()
    with tracer.span("dispatch", rounds=R):
        bres = block(bres.params, bres.server_opt_state, data, ns,
                     stack_round_keys(0, range(R)), jnp.ones(R), cohort_mask=mask)
    with tracer.span("host_sync", rounds=R):
        jax.block_until_ready(bres.params)
    fused_s = time.perf_counter() - t0

    # Plumbing invariants, not perf numbers: both paths trained R real rounds...
    assert np.isfinite(single_loss)
    assert bres.metrics["loss"].shape == (R,)
    assert np.isfinite(np.asarray(bres.metrics["loss"])).all()
    assert np.asarray(bres.survivors).tolist() == [8] * R
    # ...the phase split recorded...
    phases = tracer.phase_summary()
    assert phases["dispatch"]["count"] == 1
    assert phases["host_sync"]["count"] == 1
    assert phases["dispatch"]["total_s"] + phases["host_sync"]["total_s"] >= fused_s * 0.5
    # ...and fusing R rounds did not make the hot path slower than R dispatched
    # rounds by more than noise allows (generous 2x bound: a real regression —
    # e.g. the scan re-gathering the dataset every round — blows far past it).
    assert fused_s < single_s * 2.0, (
        f"fused {R}-round block took {fused_s:.3f}s vs {single_s:.3f}s for "
        f"{R} single rounds"
    )
    print(f"\nbench-smoke: {R} single rounds {single_s:.4f}s | "
          f"fused block {fused_s:.4f}s "
          f"(dispatch {phases['dispatch']['total_s']:.4f}s, "
          f"host_sync {phases['host_sync']['total_s']:.4f}s)")
