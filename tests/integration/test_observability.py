"""Observability subsystem, end to end over the real network path.

The acceptance surface of the subsystem: a 2-round ``NetworkCoordinator`` federation
must expose non-zero ``nanofed_rounds_total`` / ``nanofed_bytes_received_total`` and
per-phase span durations via BOTH ``GET /metrics`` (Prometheus text) and the per-run
``telemetry.jsonl`` — plus the satellite regressions this PR folds in: true
error-feedback across a rejected topk8 submit, and the accurate 400 (not 403) for a
straggler racing ``publish_model`` mid-decode.
"""

import asyncio
import json

import aiohttp
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanofed_tpu.communication import (
    HTTPClient,
    HTTPServer,
    NetworkCoordinator,
    NetworkRoundConfig,
)
from nanofed_tpu.core.types import ClientData
from nanofed_tpu.models import get_model
from nanofed_tpu.observability import MetricsRegistry, summarize_telemetry
from nanofed_tpu.trainer import TrainingConfig
from nanofed_tpu.trainer.local import make_local_fit

# A base of its own: under ``--dist loadfile`` each file is another worker's, so two files
# on one base bind the same ports at the same time.  The bases in use under ``tests/``:
# 8931, 18432, 18473, 18560, 18632, 18732, 18832 (here), 18950, 19050, 19100, offsets to 60.
PORT = 18832


def _client_data(seed):
    r = np.random.default_rng(seed)
    x = r.normal(size=(16, 8)).astype(np.float32)
    w = r.normal(size=(8,))
    y = (x @ w > 0).astype(np.int32)
    return ClientData(x=jnp.asarray(x), y=jnp.asarray(y), mask=jnp.ones((16,)))


async def _run_client(client_id, model, local_fit, data, port, registry):
    async with HTTPClient(f"http://127.0.0.1:{port}", client_id, timeout_s=30,
                          registry=registry) as client:
        while True:
            params, rnd, active = await client.fetch_global_model(
                like=model.init(jax.random.key(0))
            )
            if not active:
                return
            result = local_fit(jax.tree.map(jnp.asarray, params), data,
                               jax.random.key(hash(client_id) % 2**31))
            await client.submit_update(
                result.params,
                {"loss": float(result.metrics.loss),
                 "accuracy": float(result.metrics.accuracy),
                 "num_samples": float(result.metrics.samples)},
            )
            status = await client.check_server_status()
            while status["training_active"] and status["round"] == rnd:
                await asyncio.sleep(0.05)
                status = await client.check_server_status()
            if not status["training_active"]:
                return


def test_two_round_federation_populates_metrics_and_telemetry(tmp_path):
    """The PR's acceptance criterion, verbatim: after a 2-round network federation,
    /metrics and telemetry.jsonl both carry rounds, bytes, and phase durations."""
    model = get_model("linear", in_features=8, num_classes=2)
    training = TrainingConfig(batch_size=8, local_epochs=1, learning_rate=0.1)
    local_fit = jax.jit(make_local_fit(model.apply, training))
    registry = MetricsRegistry()  # isolated: assertions must not see other tests

    async def main():
        server = HTTPServer(port=PORT, registry=registry)
        await server.start()
        try:
            init = model.init(jax.random.key(0))
            coordinator = NetworkCoordinator(
                server, init,
                NetworkRoundConfig(num_rounds=2, min_clients=2, round_timeout_s=30),
                telemetry_dir=tmp_path,
            )

            async def scrape():
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"http://127.0.0.1:{PORT}/metrics") as resp:
                        assert resp.status == 200
                        assert resp.headers["Content-Type"].startswith("text/plain")
                        return await resp.text()

            results = await asyncio.gather(
                coordinator.run(),
                _run_client("c1", model, local_fit, _client_data(1), PORT, registry),
                _run_client("c2", model, local_fit, _client_data(2), PORT, registry),
            )
            return results[0], await scrape()
        finally:
            await server.stop()

    history, metrics_text = asyncio.run(main())
    assert [h["status"] for h in history] == ["COMPLETED", "COMPLETED"]

    # --- GET /metrics: Prometheus text with non-zero headline series ---
    lines = metrics_text.splitlines()

    def sample(prefix):
        return [line for line in lines if line.startswith(prefix)
                and not line.startswith("#")]

    rounds = sample('nanofed_rounds_total{status="completed"}')
    assert rounds and float(rounds[0].split()[-1]) == 2.0
    rx = sample('nanofed_bytes_received_total{endpoint="update"}')
    assert rx and float(rx[0].split()[-1]) > 0
    tx = sample('nanofed_bytes_sent_total{endpoint="model"}')
    assert tx and float(tx[0].split()[-1]) > 0
    accepted = sample('nanofed_updates_total{kind="plain",result="accepted"}')
    assert accepted and float(accepted[0].split()[-1]) == 4.0  # 2 clients x 2 rounds
    # Per-phase span durations: every federation phase has a populated histogram.
    for phase in ("round", "publish", "cohort-sample", "aggregate"):
        count = sample(f'nanofed_span_duration_seconds_count{{span="{phase}"}}')
        assert count and float(count[0].split()[-1]) >= 2.0, phase

    # --- telemetry.jsonl: spans + round records + final snapshot ---
    summary = summarize_telemetry(tmp_path / "telemetry.jsonl")
    assert summary["rounds"] == {"COMPLETED": 2}
    for phase in ("round", "publish", "cohort-sample", "aggregate"):
        assert summary["phases"][phase]["count"] == 2, phase
        assert summary["phases"][phase]["total_s"] > 0
    assert summary["round_duration"]["count"] == 2
    assert summary["counters"]["nanofed_rounds_total"] == {"completed": 2.0}
    assert summary["counters"]["nanofed_bytes_received_total"]["update"] > 0
    # Phase spans nest under the round: their wall time is bounded by it.
    assert (summary["phases"]["aggregate"]["total_s"]
            <= summary["phases"]["round"]["total_s"])


def test_topk8_rejected_submit_keeps_error_feedback(tmp_path):
    """Satellite regression (http_client): a rejected topk8 submit folds the WHOLE
    un-sent delta into the residual (error feedback across a dropped round), and an
    immediate retry does NOT double-count the round's delta."""
    model = get_model("linear", in_features=4, num_classes=2)
    params0 = model.init(jax.random.key(0))
    trained = jax.tree.map(lambda p: p + 0.1, params0)

    async def main():
        server = HTTPServer(port=PORT + 1)
        await server.start()
        try:
            await server.publish_model(params0, round_number=5)
            async with HTTPClient(
                f"http://127.0.0.1:{PORT + 1}", "c1", timeout_s=10,
                update_encoding="topk8-delta", topk_fraction=0.4,
                registry=MetricsRegistry(),
            ) as c:
                fetched, rnd, _ = await c.fetch_global_model(like=params0)
                assert rnd == 5
                # Submit against a stale round: rejected, nothing applied.
                c.current_round = 3
                assert not await c.submit_update(trained, {"loss": 0.5})
                assert server.num_updates() == 0
                # True error feedback: the accumulator now holds the FULL delta
                # (params - global), not just the quantization tail.
                full_delta = jax.tree.map(
                    lambda p, g: np.asarray(p, np.float32) - np.asarray(g, np.float32),
                    trained, fetched,
                )
                for acc, want in zip(jax.tree.leaves(c._residual),
                                     jax.tree.leaves(full_delta)):
                    np.testing.assert_allclose(acc, want, atol=1e-6)
                # Immediate retry at the right round with the SAME params: accepted,
                # and the buffered reconstruction is ~ global + 1x delta (a
                # double-count would land near 2x).
                c.current_round = 5
                assert await c.submit_update(trained, {"loss": 0.5})
                (update,) = await server.drain_updates()
                for got, base, want in zip(jax.tree.leaves(update.params),
                                           jax.tree.leaves(fetched),
                                           jax.tree.leaves(full_delta)):
                    applied = np.asarray(got, np.float32) - np.asarray(
                        base, np.float32
                    )
                    # topk_fraction=0.4 sends only part of the mass; what was sent
                    # must be a subset of ONE delta, never more.
                    assert np.abs(applied).max() <= np.abs(want).max() * 1.01
                    overshoot = np.abs(applied) > np.abs(want) * 1.5
                    assert not overshoot.any()
                # Residual + sent still conserves the total mass (nothing lost,
                # nothing duplicated).
                for res, base, got, want in zip(
                    jax.tree.leaves(c._residual), jax.tree.leaves(fetched),
                    jax.tree.leaves(update.params), jax.tree.leaves(full_delta),
                ):
                    sent = np.asarray(got, np.float32) - np.asarray(base, np.float32)
                    np.testing.assert_allclose(res + sent, want, atol=1e-2)
        finally:
            await server.stop()

    asyncio.run(main())


def test_decode_base_is_snapshotted_before_the_decode_thread():
    """Signature-free core of the race fix: the compressed-update decode must
    receive the base params snapshotted under the lock (the round-0 params the
    client fetched), even when publish_model advances the round before the decode
    thread runs — and the straggler still gets the 400 stale-round rejection."""
    model = get_model("linear", in_features=4, num_classes=2)
    params0 = model.init(jax.random.key(0))
    port = PORT + 3

    async def main():
        server = HTTPServer(port=port)
        await server.start()
        try:
            await server.publish_model(params0, round_number=0)
            seen_bases = []
            orig = server._reconstruct_compressed_update
            loop = asyncio.get_event_loop()

            def racy(body, encoding, base):
                seen_bases.append(base)
                fut = asyncio.run_coroutine_threadsafe(
                    server.publish_model(
                        jax.tree.map(lambda p: p + 1.0, params0), 1
                    ),
                    loop,
                )
                fut.result(timeout=10)
                return orig(body, encoding, base)

            server._reconstruct_compressed_update = racy
            async with HTTPClient(
                f"http://127.0.0.1:{port}", "c1", timeout_s=10,
                update_encoding="q8-delta", registry=MetricsRegistry(),
            ) as c:
                fetched, rnd, _ = await c.fetch_global_model(like=params0)
                trained = jax.tree.map(lambda p: p + 0.05, fetched)
                ok = await c.submit_update(trained, {"loss": 0.5})
            assert not ok  # locked re-check: the round moved on -> stale
            assert server.num_updates() == 0
            # The decode saw the ROUND-0 base, not the round-1 params that were
            # published mid-flight.
            (base,) = seen_bases
            for got, want in zip(jax.tree.leaves(base), jax.tree.leaves(params0)):
                np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        finally:
            await server.stop()

    asyncio.run(main())


def test_raced_straggler_gets_stale_round_not_signature_failure():
    """Satellite regression (http_server): when publish_model advances the round
    while a compressed update is being decoded, the straggler must get the accurate
    400 stale-round rejection — the decode base was snapshotted under the lock, so
    the signature check can never see a reconstruction against the wrong params
    (which previously surfaced as a misleading 403)."""
    pytest.importorskip("cryptography")
    from nanofed_tpu.security import SecurityManager

    model = get_model("linear", in_features=4, num_classes=2)
    params0 = model.init(jax.random.key(0))
    signer = SecurityManager(key_size=2048)
    port = PORT + 2

    async def main():
        server = HTTPServer(
            port=port,
            client_keys={"c1": signer.get_public_key()},
            require_signatures=True,
        )
        await server.start()
        try:
            await server.publish_model(params0, round_number=0)
            # Make the decode-thread dispatch the race window: the round advances
            # after the under-lock snapshot but before the decode runs.
            orig = server._reconstruct_compressed_update
            loop = asyncio.get_event_loop()

            def racy(body, encoding, base):
                fut = asyncio.run_coroutine_threadsafe(
                    server.publish_model(
                        jax.tree.map(lambda p: p + 1.0, params0), 1
                    ),
                    loop,
                )
                fut.result(timeout=10)
                return orig(body, encoding, base)

            server._reconstruct_compressed_update = racy

            async with HTTPClient(
                f"http://127.0.0.1:{port}", "c1", timeout_s=10,
                security_manager=signer, update_encoding="q8-delta",
                registry=MetricsRegistry(),
            ) as c:
                fetched, rnd, _ = await c.fetch_global_model(like=params0)
                assert rnd == 0
                trained = jax.tree.map(lambda p: p + 0.05, fetched)
                # Bypass HTTPClient's convenience wrapper to read the raw status.
                import base64

                from nanofed_tpu.communication.codec import (
                    encode_delta_q8,
                    reconstruct_q8,
                )
                from nanofed_tpu.communication.http_server import (
                    HEADER_CLIENT,
                    HEADER_ENCODING,
                    HEADER_METRICS,
                    HEADER_ROUND,
                    HEADER_SIGNATURE,
                )

                delta = jax.tree.map(
                    lambda p, g: np.asarray(p, np.float32)
                    - np.asarray(g, np.float32),
                    trained, fetched,
                )
                body = encode_delta_q8(delta)
                signed_params = reconstruct_q8(fetched, body)
                signature = signer.sign_update(signed_params, "c1", 0, "{}")
                async with aiohttp.ClientSession() as s:
                    async with s.post(
                        f"http://127.0.0.1:{port}/update", data=body,
                        headers={
                            HEADER_CLIENT: "c1", HEADER_ROUND: "0",
                            HEADER_METRICS: "{}",
                            HEADER_ENCODING: "q8-delta",
                            HEADER_SIGNATURE: base64.b64encode(signature).decode(),
                        },
                    ) as resp:
                        payload = await resp.json()
                        # The accurate rejection: 400 stale-round, NOT 403
                        # invalid-signature.
                        assert resp.status == 400, payload
                        assert "round" in payload["message"]
            assert server.num_updates() == 0
        finally:
            await server.stop()

    asyncio.run(main())
