"""The device-resident ingest buffer: fixed slots, one batched reduce per drain.

Layout: one preallocated ``[capacity, P]`` float32 device array of flattened
client deltas (P = total parameter count of the model), plus HOST-side slot
bookkeeping — a free-list bitmap, and per-slot metadata (client id, base round,
aggregation weight, reported metrics, arrival sequence).  Only the numeric
payload lives on device; the metadata is O(capacity) Python scalars.

Writes are a single donated ``dynamic_update_slice`` jit per accepted submit
(the donation updates the buffer in place — no ``[capacity, P]`` realloc per
client), with the slot index a traced scalar so every insert reuses ONE
compiled program.  Drains are ONE jitted batched reduce::

    new_flat = base_flat + coefs @ buffer        # [P] = [P] + [capacity]·[capacity,P]

where ``coefs`` encodes the aggregation policy entirely as a host-computed
``[capacity]`` vector: FedAvg sets ``w_i / Σw`` on the drained slots (the
weighted mean of deltas against a shared base IS the weighted mean of params),
FedBuff sets ``lr · (1+staleness_i)^-α / K`` (Nguyen et al. 2022, the
unnormalized form ``fedbuff_combine`` implements), and unused or out-of-window
slots carry an exact 0.0 so stale slot contents can never leak into an
aggregate.  One program serves every policy — the per-client aggregation step
the per-submit path paid is gone.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from nanofed_tpu.core.types import Params
from nanofed_tpu.utils.trees import tree_ravel

__all__ = ["DeviceIngestBuffer", "IngestConfig", "SlotMeta"]


@dataclass(frozen=True)
class IngestConfig:
    """Operator knobs for the batched ingest pipeline.

    ``capacity`` bounds DEVICE memory (``capacity * P * 4`` bytes) and is the
    backpressure point: a submit arriving at a full buffer is answered 429 +
    Retry-After instead of queueing unboundedly — admission control the client
    ``RetryPolicy`` already speaks.  ``batch_size`` is the expected drain size:
    construction pre-compiles the flush program for every power-of-two batch
    up to it, so no realistic drain ever compiles on the serving event loop
    (drain *granularity* itself belongs to the engine — ``async_buffer_k`` in
    FedBuff mode, the round barrier in sync mode).  ``decode_workers`` sizes
    the bounded npz-decode pool (the event loop never decompresses a body
    itself)."""

    capacity: int = 256
    batch_size: int | None = None  # None = min(64, capacity)
    decode_workers: int = 4

    def __post_init__(self) -> None:
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")
        if self.batch_size is not None and not (
            1 <= self.batch_size <= self.capacity
        ):
            raise ValueError("need 1 <= batch_size <= capacity")
        if self.decode_workers < 1:
            raise ValueError("decode_workers must be >= 1")

    @property
    def drain_batch(self) -> int:
        """The expected drain size — the flush-program warm bound."""
        return self.batch_size if self.batch_size is not None else min(
            64, self.capacity
        )


class SlotMeta(NamedTuple):
    """Host-side record for one occupied slot (the ``ModelUpdate`` fields the
    round engine still needs — everything numeric stayed on device)."""

    slot: int
    client_id: str
    round_number: int  # the base version this delta was computed against
    weight: float  # FedAvg aggregation weight (client sample count)
    metrics: Mapping[str, Any]
    seq: int  # arrival order — FedBuff drains the K oldest
    trace: str = ""  # X-NanoFed-Trace trace id; "" when the submit was untraced


class DeviceIngestBuffer:
    """Preallocated slot buffer of flattened client deltas on device.

    NOT thread-safe by itself: the owning :class:`~nanofed_tpu.ingest.pipeline.
    IngestPipeline` serializes every mutation under the HTTP server's buffer
    lock (the same lock the per-submit ``_updates`` dict lived under), so the
    invariants here are single-writer."""

    def __init__(
        self, template: Params, capacity: int, warm_batch: int = 64
    ) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        flat, unravel = tree_ravel(template)
        self.flat_size = int(flat.size)
        self.capacity = int(capacity)
        self.unravel = unravel
        self._buf = jnp.zeros((self.capacity, self.flat_size), jnp.float32)
        self._free: list[int] = list(range(self.capacity - 1, -1, -1))
        self._meta: dict[int, SlotMeta] = {}
        self._client_slot: dict[str, int] = {}
        self._seq = 0
        # Write-behind staging: an accepted offer costs the submit path ONE
        # host dict store — no device dispatch on the serving event loop (a
        # jit dispatch per submit measurably starves the loop under storm
        # load).  Staged rows flush to the device buffer in ONE batched
        # scatter at drain time; memory stays bounded by the same slot map.
        self._staged: dict[int, np.ndarray] = {}
        # The flush: indices padded to a power-of-two batch with the
        # out-of-range index `capacity`, which mode="drop" discards — fixed
        # shapes, so at most log2(capacity) programs ever compile.  Donated:
        # the buffer updates in place, never reallocating [capacity, P].
        self._write_batch = jax.jit(
            lambda buf, vals, idx: buf.at[idx].set(vals, mode="drop"),
            donate_argnums=0,
        )
        # THE batched reduce: every drain policy is a coefficient vector.
        self._reduce = jax.jit(lambda buf, coefs, base: base + coefs @ buf)
        # Warm the reduce and the flush ladder NOW (zero writes into the zero
        # buffer are no-ops; the reduce result is discarded): construction
        # happens once at the first publish, BEFORE traffic — lazy first-use
        # compilation would otherwise stall the event loop mid-storm, under
        # the server's lock.  Every power-of-two flush shape up to
        # ``warm_batch`` compiles here (a staged count of n pads to the next
        # power of two, so realistic drains hit MANY rungs of the ladder);
        # drains beyond warm_batch — oversize sync barriers — compile lazily
        # at most log2(capacity) - log2(warm_batch) times ever.
        n = 1
        while True:
            self._buf = self._write_batch(
                self._buf, jnp.zeros((n, self.flat_size), jnp.float32),
                jnp.full((n,), self.capacity, jnp.int32),
            )
            if n >= min(max(1, int(warm_batch)), self.capacity):
                break
            n *= 2
        self._reduce(
            self._buf, jnp.zeros((self.capacity,), jnp.float32),
            jnp.zeros((self.flat_size,), jnp.float32),
        ).block_until_ready()

    @property
    def fill(self) -> int:
        return len(self._meta)

    @property
    def device_bytes(self) -> int:
        return self.capacity * self.flat_size * 4

    @property
    def devices(self) -> set[jax.Device]:
        """The devices the slot array is committed to."""
        return self._buf.devices()

    def occupied(self) -> list[SlotMeta]:
        """Occupied slots in arrival order."""
        return sorted(self._meta.values(), key=lambda m: m.seq)

    def client_ids(self) -> set[str]:
        return set(self._client_slot)

    def has_client(self, client_id: str) -> bool:
        """O(1): does this client hold a live slot?  (``client_ids()`` copies
        the whole map — too expensive for the per-request shed path.)"""
        return client_id in self._client_slot

    def offer(
        self,
        flat_delta: Any,
        *,
        client_id: str,
        round_number: int,
        weight: float,
        metrics: Mapping[str, Any] | None = None,
        trace: str = "",
    ) -> int | None:
        """Write one client's flattened delta into a slot; returns the slot, or
        None when the buffer is FULL (the caller converts that to 429 +
        Retry-After backpressure).

        One live slot per client (parity with the per-submit path's
        ``_updates[client_id] = ...``): a client's newer logical submit
        OVERWRITES its unaggregated older one in place — latest wins, and a
        resubmitting client can never occupy two slots."""
        slot = self._client_slot.get(client_id)
        if slot is None:
            if not self._free:
                return None
            slot = self._free.pop()
        vec = np.asarray(flat_delta, np.float32)
        if vec.shape != (self.flat_size,):
            raise ValueError(
                f"flat delta shape {vec.shape} != ({self.flat_size},)"
            )
        self._staged[slot] = vec  # flushed in one batched scatter at drain
        self._seq += 1
        self._meta[slot] = SlotMeta(
            slot=slot, client_id=client_id, round_number=int(round_number),
            weight=float(weight), metrics=dict(metrics or {}), seq=self._seq,
            trace=trace,
        )
        self._client_slot[client_id] = slot
        return slot

    def _release(self, slots: Iterable[int]) -> None:
        for slot in slots:
            meta = self._meta.pop(slot, None)
            if meta is None:
                continue
            self._staged.pop(slot, None)
            if self._client_slot.get(meta.client_id) == slot:
                del self._client_slot[meta.client_id]
            self._free.append(slot)

    def _flush(self) -> None:
        """Move every staged row onto the device in ONE batched scatter,
        padded to the next power of two with dropped out-of-range indices so
        the program shape set stays O(log capacity)."""
        if not self._staged:
            return
        n = len(self._staged)
        padded = 1 << (n - 1).bit_length()
        vals = np.zeros((padded, self.flat_size), np.float32)
        idx = np.full((padded,), self.capacity, np.int32)  # dropped rows
        for j, (slot, vec) in enumerate(self._staged.items()):
            vals[j] = vec
            idx[j] = slot
        self._buf = self._write_batch(self._buf, vals, idx)
        self._staged.clear()

    def clear(self) -> int:
        """Free every slot (the sync engine's ``publish_model`` buffer clear);
        returns how many were dropped.  The device array is untouched — zeroed
        coefficients already guarantee freed contents never reach a reduce."""
        n = self.fill
        self._release(list(self._meta))
        return n

    def _run_reduce(self, coefs: np.ndarray, base_flat: Any) -> jax.Array:
        base = jnp.asarray(base_flat, jnp.float32)
        if base.shape != (self.flat_size,):
            raise ValueError(f"base shape {base.shape} != ({self.flat_size},)")
        self._flush()
        return self._reduce(self._buf, jnp.asarray(coefs, jnp.float32), base)

    def drain_fedavg(
        self, base_flat: Any
    ) -> tuple[jax.Array | None, list[SlotMeta]]:
        """Drain EVERY occupied slot as one weighted FedAvg step: returns
        ``(new_flat_params, metas)`` where ``new = base + Σ (w_i/Σw) δ_i`` —
        exactly the weighted mean of client params when every delta shares
        ``base`` (the sync round's published model).  Empty buffer returns
        ``(None, [])``."""
        metas = self.occupied()
        if not metas:
            return None, []
        total = sum(m.weight for m in metas)
        coefs = np.zeros(self.capacity, np.float32)
        for m in metas:
            coefs[m.slot] = m.weight / total
        out = self._run_reduce(coefs, base_flat)
        self._release([m.slot for m in metas])
        return out, metas

    def drain_fedavg_partial(
        self,
    ) -> tuple[jax.Array | None, float, list[SlotMeta]]:
        """Drain EVERY occupied slot as the HOST-LOCAL stage of a hierarchical
        FedAvg: returns ``(Σ w_i δ_i, Σ w_i, metas)`` — UNNORMALIZED, because
        the normalizer is global.  Summing the partials across hosts (ONE
        cross-host psum of ``[P]`` numerators ‖ scalar weight masses) and
        dividing once reproduces ``drain_fedavg`` of the union exactly:
        ``Σ_h Σ_{i∈h} w_i δ_i / Σ_h Σ_{i∈h} w_i`` IS the union's weighted
        mean.  ``drain_fedavg``'s local ``w_i/Σw`` normalization cannot
        compose this way — each host would divide by its own mass.  Empty
        buffer returns ``(None, 0.0, [])`` (a zero-mass host contributes
        zeros to the psum)."""
        metas = self.occupied()
        if not metas:
            return None, 0.0, []
        coefs = np.zeros(self.capacity, np.float32)
        for m in metas:
            coefs[m.slot] = m.weight
        out = self._run_reduce(coefs, np.zeros(self.flat_size, np.float32))
        self._release([m.slot for m in metas])
        return out, float(sum(m.weight for m in metas)), metas

    def drain_fedbuff_partial(
        self,
        k: int,
        current_version: int,
        valid_versions: Iterable[int],
        staleness_exponent: float = 0.5,
    ) -> tuple[jax.Array, list[SlotMeta], dict[str, Any]]:
        """Host-local stage of a hierarchical FedBuff step: drain this host's
        K oldest in-window slots as the UNNORMALIZED discounted sum
        ``Σ (1+s_i)^-α δ_i`` (no ``server_lr``, no ``1/K`` — both are global:
        the cross-host psum carries numerator ‖ live-count, and the apply
        divides by the GLOBAL K once).  Same window/skip/consume contract as
        :meth:`drain_fedbuff`, including the all-out-of-window ``ValueError``."""
        window = set(int(v) for v in valid_versions)
        metas = self.occupied()[: max(1, int(k))]
        live = [m for m in metas if m.round_number in window]
        skipped = len(metas) - len(live)
        if not live:
            self._release([m.slot for m in metas])
            raise ValueError(
                f"no aggregatable updates: all {skipped} buffered bases have "
                "left the version window"
            )
        coefs = np.zeros(self.capacity, np.float32)
        staleness, discounts = [], []
        for m in live:
            s = current_version - m.round_number
            d = (1.0 + s) ** (-staleness_exponent)
            staleness.append(s)
            discounts.append(d)
            coefs[m.slot] = d
        out = self._run_reduce(coefs, np.zeros(self.flat_size, np.float32))
        self._release([m.slot for m in metas])
        stats = {
            "num_aggregated": len(live),
            "num_skipped_out_of_window": skipped,
            "staleness": staleness,
            "mean_staleness": float(np.mean(staleness)),
            "discounts": [round(float(d), 4) for d in discounts],
        }
        return out, live, stats

    def drain_fedbuff(
        self,
        k: int,
        current_version: int,
        valid_versions: Iterable[int],
        base_flat: Any,
        staleness_exponent: float = 0.5,
        server_lr: float = 1.0,
    ) -> tuple[jax.Array, list[SlotMeta], dict[str, Any]]:
        """Drain the K OLDEST slots as one FedBuff step (Nguyen et al. 2022):
        ``new = base + lr · (1/K) Σ (1+s_i)^-α δ_i`` over the in-window slots,
        K = the aggregated count — numerically the unnormalized form
        ``communication.fedbuff_combine`` implements, so the two paths are
        interchangeable to float tolerance.

        Slots whose base version has left ``valid_versions`` are SKIPPED with
        an exact 0.0 coefficient (their delta is uncomputable — same contract
        as ``fedbuff_combine``) but still consumed; surplus newer slots stay
        buffered for the next aggregation.  Raises ``ValueError`` when every
        drained slot is out of window (parity with ``fedbuff_combine``)."""
        window = set(int(v) for v in valid_versions)
        metas = self.occupied()[: max(1, int(k))]
        live = [m for m in metas if m.round_number in window]
        skipped = len(metas) - len(live)
        if not live:
            self._release([m.slot for m in metas])
            raise ValueError(
                f"no aggregatable updates: all {skipped} buffered bases have "
                "left the version window"
            )
        coefs = np.zeros(self.capacity, np.float32)
        staleness, discounts = [], []
        for m in live:
            s = current_version - m.round_number
            d = (1.0 + s) ** (-staleness_exponent)
            staleness.append(s)
            discounts.append(d)
            coefs[m.slot] = server_lr * d / len(live)
        out = self._run_reduce(coefs, base_flat)
        self._release([m.slot for m in metas])
        stats = {
            "num_aggregated": len(live),
            "num_skipped_out_of_window": skipped,
            "staleness": staleness,
            "mean_staleness": float(np.mean(staleness)),
            "discounts": [round(float(d), 4) for d in discounts],
        }
        return out, live, stats
