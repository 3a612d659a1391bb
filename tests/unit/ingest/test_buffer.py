"""Device-resident ingest buffer + pipeline units (nanofed_tpu.ingest).

The invariants that make batched ingest SAFE to swap for the per-submit path:
slot bookkeeping (free-list, latest-wins replacement, full -> None), drain
math (FedAvg weighted mean, FedBuff staleness discounts, K-oldest selection,
out-of-window skips), freed-slot hygiene (stale contents can never reach a
reduce), and the flatten layout matching ``tree_ravel`` exactly."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.communication import fedbuff_combine
from nanofed_tpu.core.types import ModelUpdate
from nanofed_tpu.ingest import (
    DeviceIngestBuffer,
    IngestConfig,
    IngestPipeline,
    weight_from_metrics,
)
from nanofed_tpu.ingest.pipeline import flatten_params
from nanofed_tpu.observability.registry import MetricsRegistry
from nanofed_tpu.utils.trees import tree_ravel


def _params():
    return {"a": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
            "b": jnp.ones((4,), jnp.float32)}


def _deltas(n, size=10, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=size).astype(np.float32) for i in range(n)]


def test_ingest_config_validation():
    with pytest.raises(ValueError, match="capacity"):
        IngestConfig(capacity=0)
    with pytest.raises(ValueError, match="batch_size"):
        IngestConfig(capacity=8, batch_size=9)
    with pytest.raises(ValueError, match="decode_workers"):
        IngestConfig(decode_workers=0)


def test_flatten_matches_tree_ravel_layout():
    params = _params()
    flat, unravel = tree_ravel(params)
    host = flatten_params(params)
    np.testing.assert_array_equal(host, np.asarray(flat))
    # The unravel of a host-flattened vector restores the exact tree.
    for got, want in zip(jax.tree.leaves(unravel(jnp.asarray(host))),
                         jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_buffer_reports_where_its_slots_live():
    buf = DeviceIngestBuffer(_params(), capacity=4)
    assert buf.devices == {jax.devices()[0]}  # a bare jnp.zeros: the default device


def test_offer_drain_fedavg_weighted_mean():
    params = _params()
    base = flatten_params(params)
    buf = DeviceIngestBuffer(params, capacity=4)
    deltas, weights = _deltas(3), [1.0, 2.0, 3.0]
    for i, (d, w) in enumerate(zip(deltas, weights)):
        assert buf.offer(d, client_id=f"c{i}", round_number=0,
                         weight=w, metrics={"num_samples": w}) is not None
    assert buf.fill == 3
    out, metas = buf.drain_fedavg(base)
    want = base + sum(w * d for w, d in zip(weights, deltas)) / sum(weights)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-6)
    assert [m.client_id for m in metas] == ["c0", "c1", "c2"]
    assert buf.fill == 0
    # Empty drain is a (None, []) no-op, not an error.
    out2, metas2 = buf.drain_fedavg(base)
    assert out2 is None and metas2 == []


def test_offer_replaces_same_client_latest_wins():
    params = _params()
    base = flatten_params(params)
    buf = DeviceIngestBuffer(params, capacity=2)
    d_old, d_new = _deltas(2)
    buf.offer(d_old, client_id="c0", round_number=0, weight=1.0)
    buf.offer(d_new, client_id="c0", round_number=0, weight=1.0)
    assert buf.fill == 1  # one live slot per client, like _updates[client_id]
    out, _ = buf.drain_fedavg(base)
    np.testing.assert_allclose(np.asarray(out), base + d_new,
                               rtol=1e-4, atol=1e-6)


def test_offer_full_returns_none_and_slots_recycle():
    params = _params()
    base = flatten_params(params)
    buf = DeviceIngestBuffer(params, capacity=2)
    (d,) = _deltas(1)
    assert buf.offer(d, client_id="a", round_number=0, weight=1.0) is not None
    assert buf.offer(d, client_id="b", round_number=0, weight=1.0) is not None
    assert buf.offer(d, client_id="c", round_number=0, weight=1.0) is None
    buf.drain_fedavg(base)
    # Freed slots admit new clients, and the freed contents cannot leak: a
    # drain of ONE new client must not include the two drained deltas.
    assert buf.offer(2 * d, client_id="c", round_number=0, weight=1.0) is not None
    out, metas = buf.drain_fedavg(base)
    assert [m.client_id for m in metas] == ["c"]
    np.testing.assert_allclose(np.asarray(out), base + 2 * d,
                               rtol=1e-4, atol=1e-6)


def test_offer_trace_rides_slot_meta_into_drain():
    """The X-NanoFed-Trace trace id offered with a submit must come back on
    the drained SlotMeta (how a round names the submits it consumed) — and a
    latest-wins replacement must replace the trace with it."""
    params = _params()
    base = flatten_params(params)
    buf = DeviceIngestBuffer(params, capacity=4)
    d0, d1, d2 = _deltas(3)
    buf.offer(d0, client_id="c0", round_number=0, weight=1.0, trace="aa" * 16)
    buf.offer(d1, client_id="c1", round_number=0, weight=1.0)  # untraced
    buf.offer(d2, client_id="c0", round_number=0, weight=1.0, trace="bb" * 16)
    _, metas = buf.drain_fedavg(base)
    assert {m.client_id: m.trace for m in metas} == {
        "c0": "bb" * 16, "c1": "",
    }


def test_pipeline_offer_forwards_trace():
    params = _params()
    pipe = IngestPipeline(params, IngestConfig(capacity=4, batch_size=4),
                          registry=MetricsRegistry())
    (d,) = _deltas(1, size=flatten_params(params).size)
    assert pipe.offer(d, client_id="c0", round_number=0,
                      metrics={"num_samples": 2}, trace="cd" * 16) is not None
    _, _, metas = pipe.drain_fedavg_partial()
    assert [m.trace for m in metas] == ["cd" * 16]


def test_clear_frees_everything():
    params = _params()
    buf = DeviceIngestBuffer(params, capacity=4)
    for i, d in enumerate(_deltas(3)):
        buf.offer(d, client_id=f"c{i}", round_number=0, weight=1.0)
    assert buf.clear() == 3
    assert buf.fill == 0 and buf.client_ids() == set()
    out, metas = buf.drain_fedavg(flatten_params(params))
    assert out is None and metas == []


def test_drain_fedbuff_matches_fedbuff_combine():
    """The batched FedBuff drain must be ``fedbuff_combine`` to float
    tolerance — staleness discounts, the unnormalized 1/K form, server_lr,
    and out-of-window skips included."""
    params = _params()
    base_flat, unravel = tree_ravel(params)
    versions = {0: params,
                1: jax.tree.map(lambda x: x + 0.5, params),
                2: jax.tree.map(lambda x: x + 1.0, params)}
    current = 2
    rounds = [0, 1, 2, 2]
    rng = np.random.default_rng(3)
    client_params = []
    buf = DeviceIngestBuffer(params, capacity=8)
    for i, r in enumerate(rounds):
        noise = rng.normal(size=int(base_flat.size)).astype(np.float32)
        base_r = flatten_params(versions[r])
        client_params.append(unravel(jnp.asarray(base_r + noise)))
        buf.offer(noise, client_id=f"c{i}", round_number=r, weight=1.0)
    # Reference: the host-path combine over equivalent ModelUpdate records.
    updates = [
        ModelUpdate(client_id=f"c{i}", round_number=r, params=client_params[i],
                    metrics={}, timestamp="")
        for i, r in enumerate(rounds)
    ]
    want, want_stats = fedbuff_combine(
        versions[current], updates, versions, current,
        staleness_exponent=0.5, server_lr=0.8,
    )
    out, live, stats = buf.drain_fedbuff(
        4, current, versions, flatten_params(versions[current]),
        staleness_exponent=0.5, server_lr=0.8,
    )
    np.testing.assert_allclose(
        np.asarray(out), flatten_params(want), rtol=1e-4, atol=1e-5
    )
    assert stats["num_aggregated"] == want_stats["num_aggregated"]
    assert stats["staleness"] == want_stats["staleness"]
    assert stats["discounts"] == want_stats["discounts"]


def test_drain_fedbuff_takes_k_oldest_and_leaves_surplus():
    params = _params()
    base = flatten_params(params)
    buf = DeviceIngestBuffer(params, capacity=8)
    deltas = _deltas(5)
    for i, d in enumerate(deltas):
        buf.offer(d, client_id=f"c{i}", round_number=0, weight=1.0)
    out, live, stats = buf.drain_fedbuff(3, 0, [0], base)
    assert [m.client_id for m in live] == ["c0", "c1", "c2"]
    assert buf.fill == 2  # surplus stays for the next aggregation
    want = base + sum(deltas[:3]) / 3
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-6)


def test_drain_fedbuff_skips_out_of_window_and_raises_when_all_stale():
    params = _params()
    base = flatten_params(params)
    buf = DeviceIngestBuffer(params, capacity=4)
    d0, d1 = _deltas(2)
    buf.offer(d0, client_id="stale", round_number=0, weight=1.0)
    buf.offer(d1, client_id="fresh", round_number=3, weight=1.0)
    out, live, stats = buf.drain_fedbuff(2, 3, [2, 3], base)
    assert stats["num_skipped_out_of_window"] == 1
    assert [m.client_id for m in live] == ["fresh"]
    np.testing.assert_allclose(np.asarray(out), base + d1, rtol=1e-4, atol=1e-6)
    # All-stale drain raises (fedbuff_combine parity) but still CONSUMES the
    # slots, so the engine makes progress on the next drain.
    buf.offer(d0, client_id="stale", round_number=0, weight=1.0)
    with pytest.raises(ValueError, match="version window"):
        buf.drain_fedbuff(1, 5, [4, 5], base)
    assert buf.fill == 0


def test_weight_from_metrics_defensive_coercion():
    assert weight_from_metrics({"num_samples": 32}) == 32.0
    assert weight_from_metrics({"samples_processed": 8}) == 8.0
    assert weight_from_metrics({"num_samples": "oops"}) == 1.0
    assert weight_from_metrics({"num_samples": -5}) == 1.0
    assert weight_from_metrics({"num_samples": float("inf")}) == 1.0
    assert weight_from_metrics({}) == 1.0
    assert weight_from_metrics(None) == 1.0


def test_pipeline_version_cache_and_metrics():
    params = _params()
    registry = MetricsRegistry()
    pipe = IngestPipeline(params, IngestConfig(capacity=4, batch_size=2),
                          registry=registry)
    try:
        pipe.note_version(0, params, window=2)
        pipe.note_version(1, jax.tree.map(lambda x: x + 1, params), window=2)
        pipe.note_version(4, jax.tree.map(lambda x: x + 4, params), window=2)
        # Pruned to the window: rounds below 4 - 2 are gone.
        assert pipe.base_flat(0) is None and pipe.base_flat(1) is None
        assert pipe.base_flat(4) is not None
        (d,) = _deltas(1)
        pipe.offer(d, client_id="c0", round_number=4,
                   metrics={"num_samples": 3})
        pipe.offer(d, client_id="c0", round_number=4, metrics={})
        pipe.offer(d, client_id="c1", round_number=4, metrics={})
        out, metas = pipe.drain_fedavg(4)
        assert len(metas) == 2
        snap = registry.snapshot()
        offers = snap["nanofed_ingest_offers_total"]["values"]
        assert offers == {"accepted": 2.0, "replaced": 1.0}
        assert snap["nanofed_ingest_buffer_fill"]["values"][""] == 0.0
        assert snap["nanofed_ingest_drains_total"]["values"]["fedavg"] == 1.0
    finally:
        pipe.close()


def test_pipeline_bounded_decode_pool_runs_off_loop():
    params = _params()
    registry = MetricsRegistry()
    pipe = IngestPipeline(params, IngestConfig(capacity=2, decode_workers=2),
                          registry=registry)

    async def main():
        import threading

        loop_thread = threading.get_ident()
        seen = []

        def job(x):
            seen.append(threading.get_ident())
            return x * 2

        results = await asyncio.gather(*(pipe.run_decode(job, i)
                                         for i in range(8)))
        assert results == [i * 2 for i in range(8)]
        assert all(t != loop_thread for t in seen)
        # Bounded: never more threads than decode_workers.
        assert len(set(seen)) <= 2

    try:
        asyncio.run(main())
        assert pipe.decode_busy_seconds() > 0
        snap = registry.snapshot()
        assert snap["nanofed_ingest_decode_seconds"]["values"][""]["count"] == 8
        assert snap["nanofed_ingest_decode_queue_depth"]["values"][""] == 0.0
    finally:
        pipe.close()
