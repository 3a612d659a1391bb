"""Secure aggregation carried end-to-end over the real HTTP transport, and network-path
update validation.

The reference wires ``ThresholdSecureAggregation`` into its aggregator
(``nanofed/server/aggregator/privacy.py:311-319``) but its transport cannot carry a
masked round and its crypto is placeholder-grade; here a full Bonawitz masked round runs
over real aiohttp sockets: enroll -> roster -> mask -> POST -> modular sum -> unmask,
with the aggregate matching plain FedAvg to quantization tolerance while the server only
ever buffers uniform uint32 vectors.

The validation tests cover the gap the reference also has (``DefaultModelValidator``
exists but its coordinator never calls it): a NaN-injecting or oversized networked
client is dropped before aggregation.
"""

import pytest

pytest.importorskip(
    "cryptography", reason="secure-aggregation protocol tests need the optional crypto dependency"
)

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from nanofed_tpu.communication import (
    HTTPClient,
    HTTPServer,
    NetworkCoordinator,
    NetworkRoundConfig,
)
from nanofed_tpu.communication.network_coordinator import stack_model_updates
from nanofed_tpu.aggregation.fedavg import fedavg_combine
from nanofed_tpu.core.types import ModelUpdate
from nanofed_tpu.models import get_model
from nanofed_tpu.security.secure_agg import (
    ClientKeyPair,
    SecureAggregationConfig,
    mask_update,
)
from nanofed_tpu.security.validation import ValidationConfig
from nanofed_tpu.utils.clock import Clock

PORT = 18473


def _client_params(model, seed):
    return model.init(jax.random.key(seed))


async def _fetch_model_retry(client, like, attempts=100, delay=0.05):
    """The coordinator publishes the round-0 model concurrently with client startup;
    retry briefly instead of failing on a 503 'no model published'."""
    from nanofed_tpu.core.exceptions import NanoFedError

    for _ in range(attempts):
        try:
            return await client.fetch_global_model(like=like)
        except NanoFedError:
            await asyncio.sleep(delay)
    raise TimeoutError("model never published")


def test_masked_round_end_to_end_matches_fedavg():
    """3 real aiohttp clients run one full masked round; the coordinator's aggregate
    equals the unmasked weighted FedAvg within quantization tolerance, and the server
    never observes any individual update (its masked buffer holds uniform uint32)."""
    model = get_model("linear", in_features=6, num_classes=2)
    init = _client_params(model, 0)
    cfg = SecureAggregationConfig(min_clients=3, frac_bits=16)
    num_samples = {"c1": 30.0, "c2": 10.0, "c3": 20.0}
    local_params = {cid: _client_params(model, s)
                    for s, cid in enumerate(num_samples, start=1)}
    observed_masked = {}

    async def run_client(cid: str):
        keypair = ClientKeyPair.generate()
        async with HTTPClient(f"http://127.0.0.1:{PORT}", cid, timeout_s=30) as client:
            assert await client.register_secagg(keypair.public_bytes(), num_samples[cid])
            roster = await client.fetch_secagg_roster()
            params, rnd, active = await _fetch_model_retry(client, init)
            assert active
            masked = mask_update(
                local_params[cid],
                roster.index_of(cid),
                keypair,
                roster.ordered_keys(),
                rnd,
                cfg,
                weight=roster.weights[cid],
            )
            observed_masked[cid] = masked
            assert await client.submit_masked_update(masked, {"num_samples": num_samples[cid]})

    async def main():
        server = HTTPServer(port=PORT)
        await server.start()
        try:
            coordinator = NetworkCoordinator(
                server, init,
                NetworkRoundConfig(num_rounds=1, min_clients=3, round_timeout_s=30),
                secure=cfg,
            )
            await asyncio.gather(
                coordinator.run(), *(run_client(c) for c in num_samples)
            )
            return coordinator
        finally:
            await server.stop()

    coordinator = asyncio.run(main())
    assert [h["status"] for h in coordinator.history] == ["COMPLETED"]
    assert coordinator.history[0]["secure"] is True

    # Expected: plain weighted FedAvg over the same updates.
    expected = fedavg_combine(stack_model_updates([
        ModelUpdate(client_id=c, round_number=0, params=local_params[c],
                    metrics={"num_samples": num_samples[c]}, timestamp="")
        for c in num_samples
    ]))
    for got, want in zip(jax.tree.leaves(coordinator.params), jax.tree.leaves(expected)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)

    # The server-side payloads are masked: each wire vector must NOT equal the client's
    # bare quantized update (masks applied), and mask cancellation requires all three.
    from nanofed_tpu.security.secure_agg import quantize
    from nanofed_tpu.utils.trees import tree_ravel

    for cid, masked in observed_masked.items():
        flat, _ = tree_ravel(local_params[cid])
        bare = quantize(np.asarray(flat, np.float64) * 1.0, cfg.frac_bits)
        assert not np.array_equal(masked, bare)


def test_masked_round_fails_on_dropout():
    """No-dropout SecAgg semantics: if an enrolled client never submits, the round is
    FAILED (uncancelled masks must never be dequantized into params)."""
    model = get_model("linear", in_features=4, num_classes=2)
    init = _client_params(model, 0)
    cfg = SecureAggregationConfig(min_clients=3, frac_bits=16)

    async def run_client(cid: str, submit: bool):
        keypair = ClientKeyPair.generate()
        async with HTTPClient(f"http://127.0.0.1:{PORT + 1}", cid, timeout_s=10) as client:
            assert await client.register_secagg(keypair.public_bytes(), 10.0)
            roster = await client.fetch_secagg_roster()
            params, rnd, active = await _fetch_model_retry(client, init)
            if submit:
                masked = mask_update(
                    _client_params(model, 3), roster.index_of(cid), keypair,
                    roster.ordered_keys(), rnd, cfg, weight=roster.weights[cid],
                )
                await client.submit_masked_update(masked, {})

    async def main():
        server = HTTPServer(port=PORT + 1)
        await server.start()
        try:
            coordinator = NetworkCoordinator(
                server, init,
                NetworkRoundConfig(num_rounds=1, min_clients=3, round_timeout_s=1.5),
                secure=cfg,
            )
            await asyncio.gather(
                coordinator.run(),
                run_client("c1", True),
                run_client("c2", True),
                run_client("c3", False),  # enrolled but silent
            )
            return coordinator
        finally:
            await server.stop()

    coordinator = asyncio.run(main())
    assert coordinator.history[0]["status"] == "FAILED"
    # Params untouched by the failed round.
    for got, want in zip(jax.tree.leaves(coordinator.params), jax.tree.leaves(init)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_nan_injecting_client_is_rejected():
    """Network-path validation: a malicious client POSTing NaN params is dropped with a
    logged reason; the aggregate is computed from the honest clients only."""
    model = get_model("linear", in_features=5, num_classes=2)
    init = _client_params(model, 0)
    honest = {f"h{i}": _client_params(model, i) for i in (1, 2, 3)}

    async def run_honest(cid):
        async with HTTPClient(f"http://127.0.0.1:{PORT + 2}", cid, timeout_s=10) as c:
            params, rnd, active = await _fetch_model_retry(c, init)
            assert await c.submit_update(honest[cid], {"num_samples": 10.0})

    async def run_malicious():
        async with HTTPClient(f"http://127.0.0.1:{PORT + 2}", "evil", timeout_s=10) as c:
            params, rnd, active = await _fetch_model_retry(c, init)
            poisoned = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), init)
            assert await c.submit_update(poisoned, {"num_samples": 1e9})

    async def main():
        server = HTTPServer(port=PORT + 2)
        await server.start()
        try:
            coordinator = NetworkCoordinator(
                server, init,
                NetworkRoundConfig(num_rounds=1, min_clients=4, round_timeout_s=10),
                validation=ValidationConfig(max_norm=100.0),
            )
            await asyncio.gather(
                coordinator.run(),
                *(run_honest(c) for c in honest),
                run_malicious(),
            )
            return coordinator
        finally:
            await server.stop()

    coordinator = asyncio.run(main())
    # 4 received, 1 rejected -> below min_clients, round FAILED, but crucially the
    # NaN never reached the params.
    record = coordinator.history[0]
    assert record["num_rejected"] == 1
    assert all(np.all(np.isfinite(np.asarray(leaf)))
               for leaf in jax.tree.leaves(coordinator.params))


class _PollsOnceAllAreIn(Clock):
    """The coordinator's clock for a round whose barrier opens BELOW the cohort's size: a
    poll's sleep ends when every client has submitted (an event), not after 50 ms of wall
    clock, so what the round drains does not depend on which client the machine ran last.
    Once all are in it is the system clock."""

    def __init__(self, cohort: int):
        self._waiting_for, self._all_in = cohort, asyncio.Event()

    def one_is_in(self) -> None:
        self._waiting_for -= 1
        if not self._waiting_for:
            self._all_in.set()

    async def sleep(self, seconds: float) -> None:
        if self._all_in.is_set():
            await asyncio.sleep(seconds)
        else:
            await self._all_in.wait()


def test_nan_client_dropped_but_round_completes_with_completion_rate():
    """With min_completion_rate < 1 the round still completes from the honest cohort.

    The barrier opens at ``ceil(4 * 0.75) = 3`` buffered updates and the assertions need
    the poisoned one among those drained.  On a 50 ms wall-clock poll that is a race
    either side can win (the three honest drained alone: nothing rejected; the poisoned
    and two honest: two valid of three required, FAILED), so the round's poll here waits
    for all four submits instead."""
    model = get_model("linear", in_features=5, num_classes=2)
    init = _client_params(model, 0)
    honest = {f"h{i}": _client_params(model, i) for i in (1, 2, 3)}
    poisoned = jax.tree.map(lambda x: jnp.full_like(x, jnp.inf), init)

    async def run_client(cid, params, clock):
        async with HTTPClient(f"http://127.0.0.1:{PORT + 3}", cid, timeout_s=10) as c:
            await _fetch_model_retry(c, init)
            assert await c.submit_update(params, {"num_samples": 10.0})
            clock.one_is_in()

    async def main():
        server = HTTPServer(port=PORT + 3)
        await server.start()
        clock = _PollsOnceAllAreIn(cohort=4)
        try:
            coordinator = NetworkCoordinator(
                server, init,
                NetworkRoundConfig(num_rounds=1, min_clients=4,
                                   min_completion_rate=0.75, round_timeout_s=10),
                validation=ValidationConfig(max_norm=100.0), clock=clock,
            )
            await asyncio.gather(
                coordinator.run(),
                *(run_client(cid, params, clock)
                  for cid, params in {**honest, "evil": poisoned}.items()),
            )
            return coordinator
        finally:
            await server.stop()

    coordinator = asyncio.run(main())
    record = coordinator.history[0]
    assert record["status"] == "COMPLETED"
    assert record["num_rejected"] == 1
    assert record["num_clients"] == 3
    expected = fedavg_combine(stack_model_updates([
        ModelUpdate(client_id=c, round_number=0, params=honest[c],
                    metrics={"num_samples": 10.0}, timestamp="")
        for c in sorted(honest)
    ]))
    for got, want in zip(jax.tree.leaves(coordinator.params), jax.tree.leaves(expected)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_forged_masked_update_rejected_under_signatures():
    """require_signatures=True applies to MASKED payloads too: an attacker who knows an
    enrolled client id cannot inject an unsigned uint32 vector; the honest cohort's
    signed masked round completes."""
    from nanofed_tpu.security.signing import SecurityManager

    model = get_model("linear", in_features=4, num_classes=2)
    init = _client_params(model, 0)
    cfg = SecureAggregationConfig(min_clients=3, frac_bits=16)
    managers = {c: SecurityManager(key_size=1024) for c in ("c1", "c2", "c3")}
    rejected = {}

    async def run_client(cid: str, forge: bool):
        keypair = ClientKeyPair.generate()
        async with HTTPClient(
            f"http://127.0.0.1:{PORT + 4}", cid, timeout_s=10,
            security_manager=managers[cid],
        ) as client:
            assert await client.register_secagg(keypair.public_bytes(), 10.0)
            roster = await client.fetch_secagg_roster()
            params, rnd, active = await _fetch_model_retry(client, init)
            masked = mask_update(
                _client_params(model, 7), roster.index_of(cid), keypair,
                roster.ordered_keys(), rnd, cfg, weight=roster.weights[cid],
            )
            if forge:
                # Enrolled legitimately, then submits WITHOUT signing (e.g. a stolen
                # session replaying through a different stack).
                client.security_manager = None
            ok = await client.submit_masked_update(masked, {})
            rejected[cid] = not ok

    async def main():
        server = HTTPServer(
            port=PORT + 4,
            client_keys={c: m.get_public_key() for c, m in managers.items()},
            require_signatures=True,
        )
        await server.start()
        try:
            coordinator = NetworkCoordinator(
                server, init,
                NetworkRoundConfig(num_rounds=1, min_clients=3, round_timeout_s=2.0),
                secure=cfg,
            )
            await asyncio.gather(
                coordinator.run(),
                run_client("c1", False),
                run_client("c2", False),
                run_client("c3", True),  # enrolled, but submits UNSIGNED
            )
            return coordinator
        finally:
            await server.stop()

    coordinator = asyncio.run(main())
    # The forged submission bounced (403) -> cohort incomplete -> round FAILED and the
    # forged vector never reached the aggregate.
    assert rejected == {"c1": False, "c2": False, "c3": True}
    assert coordinator.history[0]["status"] == "FAILED"
    for got, want in zip(jax.tree.leaves(coordinator.params), jax.tree.leaves(init)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_unsigned_enrollment_rejected_under_signatures():
    """require_signatures gates ENROLLMENT too: an attacker who knows a client id
    cannot claim its cohort slot (and mask identity) with an unsigned register."""
    import asyncio as aio

    from aiohttp.test_utils import TestClient, TestServer

    from nanofed_tpu.security.signing import SecurityManager

    manager = SecurityManager(key_size=1024)

    async def scenario():
        import base64

        server = HTTPServer(
            port=0, client_keys={"c1": manager.get_public_key()},
            require_signatures=True,
        )
        client = TestClient(TestServer(server._app))
        await client.start_server()
        try:
            await server.open_secagg(1)
            session = (await (await client.get("/secagg/roster")).json())["session"]
            pk = bytes(32)
            body = {"public_key": base64.b64encode(pk).decode(), "num_samples": 10.0}
            # Unsigned -> 403; unknown id -> 403; correctly signed -> 200.
            r = await client.post("/secagg/register", json=body,
                                  headers={"X-NanoFed-Client": "c1"})
            assert r.status == 403
            r = await client.post("/secagg/register", json=body,
                                  headers={"X-NanoFed-Client": "intruder"})
            assert r.status == 403
            sig = base64.b64encode(
                manager.sign_enrollment("c1", pk, 10.0, session)).decode()
            r = await client.post("/secagg/register", json=body,
                                  headers={"X-NanoFed-Client": "c1",
                                           "X-NanoFed-Signature": sig})
            assert r.status == 200
            # Idempotent retry: identical signed payload (int/float sample counts
            # sign identically — JSON round-trips both to float) -> 200.
            sig2 = base64.b64encode(
                manager.sign_enrollment("c1", pk, 10, session)).decode()
            r = await client.post("/secagg/register", json=body,
                                  headers={"X-NanoFed-Client": "c1",
                                           "X-NanoFed-Signature": sig2})
            assert r.status == 200
            # REPLAY into a fresh cohort: the old signature no longer verifies
            # (bound to the previous session nonce).
            await server.open_secagg(1)
            r = await client.post("/secagg/register", json=body,
                                  headers={"X-NanoFed-Client": "c1",
                                           "X-NanoFed-Signature": sig})
            assert r.status == 403
            # A DIFFERENT key for an enrolled id is refused even when validly signed
            # (mid-session key swap would break mask cancellation).
            await server.open_secagg(1)
            session3 = (await (await client.get("/secagg/roster")).json())["session"]
            sig3 = base64.b64encode(
                manager.sign_enrollment("c1", pk, 10.0, session3)).decode()
            assert (await client.post("/secagg/register", json=body,
                                      headers={"X-NanoFed-Client": "c1",
                                               "X-NanoFed-Signature": sig3})).status == 200
            pk2 = bytes(31) + b"x"
            body2 = {"public_key": base64.b64encode(pk2).decode(), "num_samples": 10.0}
            sig4 = base64.b64encode(
                manager.sign_enrollment("c1", pk2, 10.0, session3)).decode()
            r = await client.post("/secagg/register", json=body2,
                                  headers={"X-NanoFed-Client": "c1",
                                           "X-NanoFed-Signature": sig4})
            assert r.status == 409
        finally:
            await client.close()

    aio.new_event_loop().run_until_complete(scenario())
