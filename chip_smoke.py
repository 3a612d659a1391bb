"""chip_smoke.py — the quickest proof that the round engine still starts on the chip.

One process, one command, no arguments::

    python chip_smoke.py

It drives the system's main path once, at the full width of the flagship configuration
(``FLAGSHIP``: the 1.2M-parameter MNIST CNN, 1000 clients x 60 samples,
2 local epochs, batch 64, bf16, ``client_chunk=125``), through the entry points a user
calls, and checks what comes out by the repo's own means:

* **simulated cohorts** — ``run_experiment`` for 3 single-step rounds, then a
  ``Coordinator`` built the same way for 3 rounds as ONE fused block under
  ``strict=True`` (transfer guard + construction-time program audit), then
  ``evaluate()``;
* **wire-fed** — ``run_loadtest`` in ``ingest`` mode: a real ``HTTPServer`` with a
  ``DeviceIngestBuffer`` and a FedBuff ``NetworkCoordinator``, a few dozen submits at
  the CNN's payload over loopback sockets on the system clock; plus the buffer's
  batched ``base + coefs @ buf`` drain against its NumPy form;
* **kernels** — every exported Pallas kernel with ``interpret=False`` at the CNN's
  parameter count and cohorts of 64 and 1000 rows, against its ``jax.numpy`` form;
* **multi-chip** (only when JAX finds >= 4 devices) — the simulated feed on a ``(4,)``
  clients mesh and a ``(2, 2)`` clients x model mesh, shard placement read from
  ``addressable_shards``, loss trajectory against a one-device mesh.

The weights are random from a seed and the data is synthetic MNIST-shaped, generated
from a seed (``load_mnist``'s synthetic path): a fresh clone with no network runs it.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` mean every phase
passed on a TPU.  No TPU, an unknown ``device_kind`` or a failed phase is a non-zero
exit and no result line; nothing falls back to the CPU.  Nothing printed here is a
benchmark metric — compile seconds and first-call times are set-up facts.

The phases are functions of a :class:`SmokeSize` so that
``tests/integration/test_chip_smoke.py`` rehearses them tiny on the CPU mesh (kernels
interpreted there); only :func:`main` insists on the TPU and the full size.
"""

from __future__ import annotations

import json
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class SmokeSize:
    """What one smoke run is sized by.  ``experiment`` is ``run_experiment`` kwargs
    (one simulated-cohort configuration, rounds excluded), ``wire`` is
    ``run_loadtest`` kwargs, the kernel phase runs at ``[cohort, kernel_params]``."""

    experiment: dict[str, Any]
    wire: dict[str, Any]
    kernel_params: int
    kernel_cohorts: tuple[int, ...]
    attention_shape: tuple[int, int, int, int] = (4, 12, 1024, 64)  # GPT-2's [N, H, T, hd]
    # SmallThinker's expert layer: tokens, picks a token, experts, held, width, expert width;
    # then the layout's block (None: from the shapes, as the models have it).
    experts_shape: tuple = (8192, 6, 64, 16, 2560, 768, None)
    rounds: int = 3
    interpret: bool = False  # Pallas interpreter: CPU rehearsal only
    loss_tolerance: float = 1e-3  # single-step vs fused vs other meshes, absolute


#: The flagship cross-device configuration, as ``run_experiment`` kwargs: 60k MNIST over
#: 1000 clients is 60 samples each; ``client_chunk`` bounds per-device live memory while
#: ``vmap`` batches the resident clients.  The benchmark's cell of the same federation
#: (``benchmark/configs/mnist-cnn-xdevice-1000.json``) differs in ``client_chunk`` alone.
FLAGSHIP: dict[str, Any] = dict(
    model="mnist_cnn", num_clients=1000, local_epochs=2,
    batch_size=64, learning_rate=0.1, scheme="iid", participation=1.0,
    client_chunk=125, compute_dtype="bfloat16",
)

#: The flagship at full width.  1,199,882 is the MNIST CNN's parameter count (the wire
#: phase reports the model's own ``flat_size`` beside it).
FULL = SmokeSize(
    experiment=FLAGSHIP,
    wire=dict(
        model="mnist_cnn", clients=48, async_buffer_k=16, ingest_capacity=64,
        arrival_rate=100.0,
    ),
    kernel_params=1_199_882,
    kernel_cohorts=(64, 1000),
)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _check(cond: bool, what: str) -> None:
    """A phase check.  Not ``assert``: the smoke must fail under ``python -O`` too."""
    if not cond:
        raise RuntimeError(f"chip smoke check failed: {what}")


def _on_default_backend(tree: Any, what: str) -> list[str]:
    """Every array leaf of ``tree`` sits on devices of the default backend (which
    :func:`main` has already required to be a TPU); returns the device names."""
    known = set(jax.devices())
    names: set[str] = set()
    for leaf in jax.tree.leaves(tree):
        _check(isinstance(leaf, jax.Array), f"{what}: leaf is {type(leaf).__name__}")
        _check(leaf.devices() <= known, f"{what}: on {leaf.devices()}, not {known}")
        names |= {str(d) for d in leaf.devices()}
    return sorted(names)


def _losses_ok(losses: list[float], what: str) -> None:
    _check(len(losses) > 1, f"{what}: need >= 2 rounds, got {losses}")
    _check(all(np.isfinite(losses)), f"{what}: non-finite loss in {losses}")
    _check(losses[-1] < losses[0], f"{what}: loss did not fall: {losses}")


def _rel_err(got: Any, want: Any) -> float:
    """max|got - want| over max|want| — scale-free, so one bound serves every kernel."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def experiment_data(size: SmokeSize):
    """``(client data, eval data)`` as ``run_experiment(**size.experiment)`` prepares
    them: synthetic from a seed, federated, packed."""
    from nanofed_tpu.data import federate, pack_eval
    from nanofed_tpu.experiments import load_datasets_for
    from nanofed_tpu.models import get_model

    cfg = size.experiment
    train, test = load_datasets_for(get_model(cfg["model"]), None, cfg.get("train_size"))
    clients = federate(
        train, num_clients=cfg["num_clients"], scheme=cfg["scheme"],
        batch_size=cfg["batch_size"], seed=0,
    )
    return clients, pack_eval(test, batch_size=256)


def build_coordinator(
    size: SmokeSize, out_dir: Path, data, *, mesh=None, strategy=None
):
    """The ``Coordinator`` that ``run_experiment(**size.experiment)`` builds, for
    ``size.rounds`` rounds as ONE fused block under ``strict=True`` — kept in hand so
    the phase can read where its arrays live."""
    from nanofed_tpu.models import get_model
    from nanofed_tpu.orchestration import Coordinator, CoordinatorConfig
    from nanofed_tpu.trainer import TrainingConfig

    cfg = size.experiment
    train_data, eval_data = data
    return Coordinator(
        model=get_model(cfg["model"]),
        train_data=train_data,
        config=CoordinatorConfig(
            num_rounds=size.rounds, participation_rate=cfg["participation"],
            seed=0, base_dir=out_dir, rounds_per_block=size.rounds,
        ),
        training=TrainingConfig(
            batch_size=cfg["batch_size"], local_epochs=cfg["local_epochs"],
            learning_rate=cfg["learning_rate"],
            compute_dtype=cfg.get("compute_dtype"),
        ),
        strategy=strategy,
        mesh=mesh,
        client_chunk=cfg.get("client_chunk"),
        eval_data=eval_data,
        strict=True,
    )


def _run_coordinator(coordinator, what: str) -> dict[str, Any]:
    from nanofed_tpu.orchestration import RoundStatus

    rounds = coordinator.run()
    failed = [r.round_id for r in rounds if r.status != RoundStatus.COMPLETED]
    _check(not failed, f"{what}: rounds_failed = {failed}")
    losses = [float(r.agg_metrics["loss"]) for r in rounds]
    _losses_ok(losses, what)
    evaluation = coordinator.evaluate()
    _check(all(np.isfinite(list(evaluation.values()))), f"{what}: eval {evaluation}")
    return {
        "losses": losses,
        "eval": evaluation,
        "round_s": [round(r.duration_s, 4) for r in rounds],
        "mesh": {n: int(coordinator.mesh.shape[n]) for n in coordinator.mesh.axis_names},
        "params_on": _on_default_backend(coordinator.params, f"{what}: params"),
    }


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_simulated_single(size: SmokeSize, out_dir: Path) -> dict[str, Any]:
    """``run_experiment`` — the CLI's engine — for ``size.rounds`` single-step rounds
    (``build_round_step``), with its own ``evaluate()`` at the end."""
    from nanofed_tpu.experiments import run_experiment

    summary = run_experiment(
        **size.experiment, num_rounds=size.rounds, rounds_per_block=1,
        out_dir=out_dir,
    )
    _check(summary["rounds_failed"] == 0, f"single-step: {summary['rounds_failed']} failed")
    _check(summary["rounds_completed"] == size.rounds, "single-step: rounds missing")
    losses = [
        float(json.loads(
            (out_dir / "metrics" / f"metrics_round_{r}.json").read_text()
        )["agg_metrics"]["loss"])
        for r in range(size.rounds)
    ]
    _losses_ok(losses, "single-step")
    evaluation = summary["final_eval_metrics"]
    _check(all(np.isfinite(list(evaluation.values()))), f"single-step: eval {evaluation}")
    return {
        "losses": losses,
        "eval": evaluation,
        # round 0 pays the compile: a set-up fact, not a round time.
        "round_s": [round(s, 4) for s in summary["round_durations_s"]],
        "devices": summary["devices"],
    }


def phase_simulated_fused_strict(
    size: SmokeSize, out_dir: Path, single_losses: list[float]
) -> dict[str, Any]:
    """The same rounds as ONE fused block (``build_round_block``) under
    ``strict=True``: the construction-time audit and the transfer guard run on this
    backend too.  The trajectory must agree with the single-step one."""
    coordinator = build_coordinator(size, out_dir, experiment_data(size))
    record = _run_coordinator(coordinator, "fused+strict")
    delta = float(np.max(np.abs(np.subtract(record["losses"], single_losses))))
    _check(
        delta <= size.loss_tolerance,
        f"fused block drifted from single steps by {delta}: "
        f"{record['losses']} vs {single_losses}",
    )
    _on_default_backend(coordinator.client_data, "fused+strict: client data")
    return {**record, "max_loss_delta_vs_single": delta}


def phase_wire_ingest(size: SmokeSize) -> dict[str, Any]:
    """Wire-fed FedBuff through ``run_loadtest`` (ingest mode, system clock, real
    sockets), then the buffer's batched drain against its NumPy form."""
    from nanofed_tpu.ingest import DeviceIngestBuffer
    from nanofed_tpu.loadgen import run_loadtest
    from nanofed_tpu.models import get_model
    from nanofed_tpu.utils.trees import tree_ravel

    rec = run_loadtest(mode="ingest", virtual_clock=False, seed=0, **size.wire)
    total = rec["total_submits"]
    _check(rec["failed_submits"] == 0, f"wire: failed_submits = {rec['failed_submits']}")
    _check(rec["accepted"] == total, f"wire: accepted {rec['accepted']} of {total}")
    _check(rec["aggregations_failed"] == 0, "wire: an aggregation failed")
    _check(
        rec["aggregations_completed"] >= 2
        and rec["aggregations_completed"] == rec["aggregations_target"],
        f"wire: {rec['aggregations_completed']} of {rec['aggregations_target']} "
        "aggregations",
    )
    known = {str(d) for d in jax.devices()}
    _check(
        set(rec["ingest"]["devices"]) <= known and rec["ingest"]["devices"],
        f"wire: ingest buffer on {rec['ingest']['devices']}",
    )

    # The drain itself, where a reference exists: K random deltas of mixed staleness
    # through the [capacity, P] buffer against float64 NumPy.
    template = get_model(size.wire["model"]).init(jax.random.key(0))
    k, capacity = size.wire["async_buffer_k"], size.wire["ingest_capacity"]
    buf = DeviceIngestBuffer(template, capacity, warm_batch=k)
    rng = np.random.default_rng(0)
    base = np.asarray(tree_ravel(template)[0], np.float32)
    deltas = rng.normal(scale=0.05, size=(k, buf.flat_size)).astype(np.float32)
    versions = rng.integers(0, 3, size=k)
    for i in range(k):
        buf.offer(deltas[i], client_id=f"c{i}", round_number=int(versions[i]), weight=1.0)
    got, live, stats = buf.drain_fedbuff(
        k, current_version=2, valid_versions=(0, 1, 2), base_flat=base
    )
    _check(len(live) == k and buf.fill == 0, "wire: drain left slots behind")
    coefs = (1.0 + (2 - versions)) ** -0.5 / k
    want = base.astype(np.float64) + coefs @ deltas.astype(np.float64)
    # The bound is on the aggregate STEP (got - base): the step is what a lossy
    # contraction would hurt, and base would hide it.
    err = _rel_err(np.asarray(got, np.float64) - base, want - base)
    _check(err < 2e-5, f"wire: drain step off its NumPy form by {err:.3g} (relative)")
    return {
        "flat_size": buf.flat_size,
        "buffer_device_bytes": buf.device_bytes,
        "buffer_on": sorted(str(d) for d in buf.devices),
        "drain_step_rel_err": err,
        "mean_staleness": stats["mean_staleness"],
        **{key: rec[key] for key in (
            "total_submits", "accepted", "failed_submits", "aggregations_completed",
            "aggregations_failed", "http_429_total", "client_retries_total",
        )},
        "ingest": rec["ingest"],
    }


def phase_kernels(size: SmokeSize) -> dict[str, Any]:
    """Every exported Pallas kernel against its ``jax.numpy`` form.  ``interpret`` is
    passed explicitly, so on the chip a mis-detected backend cannot turn a Mosaic
    compile into an interpreted pass."""
    from nanofed_tpu import ops

    interp, p = size.interpret, size.kernel_params
    out: dict[str, Any] = {"params": p, "interpret": interp}

    def key(seed: int):
        # RBG keys: XLA compiles a threefry draw of [1000, P] for ~15 s, an RBG one for ~3.
        return jax.random.key(seed, impl="rbg")

    # --- quantize / dequantize / mask: exact integer arithmetic.
    x = jax.random.normal(key(1), (p,), jnp.float32) * 3.0
    q = ops.quantize_u32(x, interpret=interp)
    want_q = jax.lax.bitcast_convert_type(
        jnp.round(x * 65536.0).astype(jnp.int32), jnp.uint32
    )
    _check(bool(jnp.array_equal(q, want_q)), "quantize_u32 != round(x * 2^16)")
    back = ops.dequantize_u32(q, interpret=interp)
    want_back = jax.lax.bitcast_convert_type(q, jnp.int32).astype(jnp.float32) / 65536.0
    _check(bool(jnp.array_equal(back, want_back)), "dequantize_u32 != q * 2^-16")
    _check(float(jnp.max(jnp.abs(back - x))) <= 2.0 ** -17 + 2e-6, "u32 round trip")
    seed = jnp.asarray([7, 11, 13, 17], jnp.int32)

    def mask(seed):
        return ops.add_mask(q, seed, jnp.int32(1), interpret=interp)

    masked = mask(seed)
    _check(float(jnp.mean(masked == q)) < 0.01, "add_mask left the payload in clear")
    _check(bool(jnp.array_equal(masked, mask(seed))), "add_mask is not deterministic")
    for word in range(4):  # each of the 128 seed bits' words reaches the stream
        other = mask(seed.at[word].add(1))
        _check(float(jnp.mean(masked == other)) < 0.01, f"add_mask ignores seed word {word}")
    block = 256 * 512  # ops.quantize's VMEM tile: every tile draws its own stream
    if p >= 2 * block:
        stream = masked - q
        _check(
            float(jnp.mean(stream[:block] == stream[block:2 * block])) < 0.01,
            "add_mask repeats its stream across blocks",
        )
    _check(
        bool(jnp.array_equal(
            ops.add_mask(masked, seed, jnp.int32(-1), interpret=interp), q
        )),
        "add_mask(+1) then add_mask(-1) does not cancel",
    )
    out["u32"] = "exact"
    del x, q, want_q, back, want_back, masked

    # --- the [C, P] contractions, at each cohort size.
    @jax.jit
    def wmean_ref(x, w):
        return jnp.tensordot(w, x, axes=1, precision=_HIGHEST) / jnp.sum(w)

    poison = jax.jit(  # a NaN row, an inf row: donated, so [C, P] exists once
        lambda x: x.at[1].set(jnp.nan).at[2, ::7].set(jnp.inf), donate_argnums=0
    )
    sanitize = jax.jit(
        lambda x: jnp.where(jnp.isfinite(x), x, 0.0), donate_argnums=0
    )
    for c in size.kernel_cohorts:
        kw, kx, kq, ks = jax.random.split(key(c), 4)
        w = jax.random.uniform(kw, (c,), jnp.float32, 0.5, 2.0)
        x = jax.random.normal(kx, (c, p), jnp.float32)
        errs = {"weighted_mean_flat": _rel_err(
            ops.weighted_mean_flat(x, w, interpret=interp), wmean_ref(x, w)
        )}
        valid = jnp.ones((c,), jnp.float32).at[1].set(0.0).at[3].set(0.0)
        x = poison(x)
        got = ops.masked_weighted_mean_flat(x, w, valid, interpret=interp)
        _check(bool(jnp.all(jnp.isfinite(got))), f"masked_weighted_mean_flat C={c}: NaN out")
        x = sanitize(x)
        errs["masked_weighted_mean_flat"] = _rel_err(got, wmean_ref(x, w * valid))
        del x, got

        # Rounded Gaussians, like a q8 codec's rows (XLA compiles randint at this
        # shape for over a minute).
        q8 = jax.jit(lambda k: jnp.clip(
            jnp.round(jax.random.normal(k, (c, p), jnp.float32) * 50.0), -127, 127
        ).astype(jnp.int8))(kq)
        scales = jax.random.uniform(ks, (c,), jnp.float32, 1e-3, 1e-2)
        base = jnp.linspace(-1.0, 1.0, p, dtype=jnp.float32)
        want = base + jnp.tensordot(
            w * scales / jnp.sum(w), q8.astype(jnp.float32), axes=1, precision=_HIGHEST
        )
        got = ops.dequant_accumulate_flat(q8, scales, w, base, interpret=interp)
        errs["dequant_accumulate_flat"] = _rel_err(got - base, want - base)
        del q8, got, want
        for name, err in errs.items():
            _check(err < 2e-5, f"{name} C={c}: off its jax.numpy form by {err:.3g}")
        out[f"C={c}"] = errs

    # --- the tree wrapper, on a stacked tree of uneven leaves.
    c = size.kernel_cohorts[0]
    stacked = {
        "kernel": jax.random.normal(key(2), (c, 37, 19), jnp.float32),
        "bias": jax.random.normal(key(3), (c, 19), jnp.float32),
    }
    w = jax.random.uniform(key(4), (c,), jnp.float32, 0.5, 2.0)
    got = ops.weighted_mean_tree(stacked, w, interpret=interp)
    want = jax.tree.map(
        lambda leaf: wmean_ref(leaf.reshape(c, -1), w).reshape(leaf.shape[1:]), stacked
    )
    err = max(
        _rel_err(a, b) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))
    )
    _check(err < 2e-5, f"weighted_mean_tree: off by {err:.3g}")
    out["weighted_mean_tree"] = err

    # --- blockwise causal attention, output and gradients, bfloat16 as the models run it
    # (float32 reference on the same rounded inputs; 2**-8 on the probabilities and dS).
    from nanofed_tpu.ops.attention import dense_causal_attention

    q, k, v, w = (
        jax.random.normal(key(5 + i), size.attention_shape, jnp.float32).astype(jnp.bfloat16)
        for i in range(4)
    )

    def attend_and_grads(attend, *qkv):
        loss = lambda *a: (attend(*a).astype(jnp.float32) * w.astype(jnp.float32)).sum()
        return attend(*qkv), *jax.grad(loss, (0, 1, 2))(*qkv)

    # The causal rule, then the block-diffusion branch: the same operands as a stream of two
    # halves in blocks of 4, the kernels' mask from positions and their walk against the
    # dense mask.
    halves = (size.attention_shape[2] // 2, 4)
    for what, mask in (("causal_attention", {}), ("causal_attention_blocks", {"blocks": halves})):
        got = jax.jit(lambda *a: attend_and_grads(
            lambda *b: ops.causal_attention(*b, interpret=interp, **mask), *a))(q, k, v)
        want = jax.jit(lambda *a: attend_and_grads(
            lambda *b: dense_causal_attention(*b, **mask), *a))(
            *(a.astype(jnp.float32) for a in (q, k, v)))
        errs = {name: _rel_err(g.astype(jnp.float32), r)
                for name, g, r in zip(("out", "dq", "dk", "dv"), got, want)}
        for name, err in errs.items():
            _check(err < 1.5e-2, f"{what} {name}: off its dense form by {err:.3g}")
        out[what] = errs

    # --- the held experts' grouped matmul, value and four gradients, against the loop it
    # stands in for, both on the same bfloat16 operands.
    from nanofed_tpu.models import experts
    from nanofed_tpu.ops.experts import tile_rows

    n, top_k, routed, held, d, f, block = size.experts_shape
    block = block or tile_rows(d, 2 * f)
    bf16 = lambda k, shape, scale=1.0: (scale * jax.random.normal(key(k), shape)).astype(jnp.bfloat16)
    x, w_in, w_out, d_out = (bf16(9, (n, d)), bf16(10, (held, d, 2 * f), 0.02),
                             bf16(11, (held, f, d), 0.02), bf16(12, (n, d)))
    _, picks = jax.lax.top_k(jax.random.normal(key(13), (n, routed)), top_k)
    gate = jax.random.uniform(key(14), (n * top_k,), jnp.float32)

    def experts_and_grads(spelling, *extra):
        def run(x, gate, w_in, w_out):
            layout = experts.dispatch(picks, first_expert=0, held=held, block=block)[:3]
            value, pull = jax.vjp(lambda *a: spelling(
                a[0], a[1], *layout, a[2], a[3], experts.SWIGLU, block, *extra), x, gate, w_in, w_out)
            return (value, *pull(d_out))
        return jax.jit(run)(x, gate, w_in, w_out)

    got, want = experts_and_grads(experts.expert_tiles, interp), experts_and_grads(experts.expert_blocks)
    errs = {name: _rel_err(g.astype(jnp.float32), r.astype(jnp.float32))
            for name, g, r in zip(("out", "dx", "d_gate", "d_w_in", "d_w_out"), got, want)}
    for name, err in errs.items():
        _check(err < 3e-2, f"expert_tiles {name}: off the loop by {err:.3g}")
    out["expert_tiles"] = errs
    return out


def phase_multichip(size: SmokeSize, out_dir: Path, devices: list) -> dict[str, Any]:
    """Clients on a mesh axis is the point of the system: the simulated feed on a
    ``(4,)`` clients mesh and a ``(2, 2)`` clients x model mesh, against a one-device
    mesh of the same host.  Server momentum (FedAvgM) on all three, because plain
    FedAvg's server optimizer state holds no arrays and there would be nothing to
    find on the model axis."""
    from nanofed_tpu.aggregation import fedavgm_strategy
    from nanofed_tpu.parallel import make_mesh

    four = list(devices[:4])
    _check(len(four) == 4, f"multi-chip phase needs 4 devices, got {len(devices)}")
    meshes = {
        "1": make_mesh(four[:1]),
        "4": make_mesh(four),
        "2x2": make_mesh(four, shape=(2, 2)),
    }
    out: dict[str, Any] = {}
    data = experiment_data(size)
    for name, mesh in meshes.items():
        coordinator = build_coordinator(
            size, out_dir / f"mesh_{name}", data, mesh=mesh,
            strategy=fedavgm_strategy(momentum=0.5),
        )
        record = _run_coordinator(coordinator, f"mesh {name}")
        n_dev = len(mesh.devices.flat)

        def holders(leaf):
            return {s.device for s in leaf.addressable_shards}

        placed = coordinator.client_data.x
        rows = {s.data.shape[0] for s in placed.addressable_shards}
        _check(len(holders(placed)) == n_dev, f"mesh {name}: client data on {holders(placed)}")
        _check(
            rows == {placed.shape[0] // mesh.shape["clients"]},
            f"mesh {name}: client rows per shard {rows} of {placed.shape[0]}",
        )
        record["client_rows_per_device"] = rows.pop()
        for label, tree in (
            ("params", coordinator.params), ("server_state", coordinator.server_state),
        ):
            leaves = [x for x in jax.tree.leaves(tree) if isinstance(x, jax.Array) and x.ndim]
            _check(bool(leaves), f"mesh {name}: {label} holds no arrays")
            _check(
                all(len(holders(x)) == n_dev for x in leaves),
                f"mesh {name}: a {label} leaf is not on all {n_dev} devices",
            )
            split = [
                x for x in leaves
                if any(s.data.shape != x.shape for s in x.addressable_shards)
            ]
            if name == "2x2":
                # FSDP: the big leaves are halved over the model axis, and stay so
                # after the rounds (the program's out specs keep the layout).
                per_device = sum(x.addressable_shards[0].data.nbytes for x in leaves)
                total = sum(x.nbytes for x in leaves)
                _check(bool(split), f"mesh 2x2: no {label} leaf is model-sharded")
                _check(per_device < 0.6 * total, f"mesh 2x2: {label} {per_device}/{total} B")
                record[f"{label}_bytes_per_device"] = [per_device, total]
            else:
                _check(not split, f"mesh {name}: {label} unexpectedly sharded")
        out[name] = record
    for name in ("4", "2x2"):
        delta = float(np.max(np.abs(np.subtract(out[name]["losses"], out["1"]["losses"]))))
        _check(
            delta <= size.loss_tolerance,
            f"mesh {name} drifted from one device by {delta}: "
            f"{out[name]['losses']} vs {out['1']['losses']}",
        )
        out[name]["max_loss_delta_vs_one_device"] = delta
    return out


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def run_phases(
    size: SmokeSize, out_dir: Path, devices: list,
    report: Callable[[str, dict[str, Any]], None],
) -> None:
    """Every phase in order; any failed check raises.  ``report`` gets each phase's
    record as it completes."""
    single = phase_simulated_single(size, out_dir / "single")
    report("simulated_single", single)
    report("simulated_fused_strict",
           phase_simulated_fused_strict(size, out_dir / "fused", single["losses"]))
    report("wire_ingest", phase_wire_ingest(size))
    report("kernels", phase_kernels(size))
    if len(devices) >= 4:
        report("multichip", phase_multichip(size, out_dir / "multichip", devices))


def main() -> int:
    t0 = time.perf_counter()
    from nanofed_tpu.observability.registry import get_registry
    from nanofed_tpu.tuning.compile_cache import (
        COMPILE_CACHE_HITS,
        COMPILE_CACHE_MISSES,
        install_compile_cache_metrics,
    )
    from nanofed_tpu.utils.platform import enable_compilation_cache, require_tpu

    cache_dir = enable_compilation_cache()
    d = jax.devices()[0]
    print(
        f"chip_smoke: platform={d.platform} device_kind={d.device_kind} "
        f"devices={len(jax.devices())} jax={jax.__version__} cache={cache_dir}",
        flush=True,
    )
    devices, _ = require_tpu()  # SystemExit, before any phase, unless a known TPU

    install_compile_cache_metrics()
    compiles: list[tuple[str, float]] = []

    def on_duration(event: str, duration: float, **kwargs: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((str(kwargs.get("fun_name", "?")), float(duration)))

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    def counter(name: str) -> int:
        return int(sum(get_registry().snapshot().get(name, {}).get("values", {}).values()))

    mark = {"t": time.perf_counter(), "hits": 0, "misses": 0, "compiles": 0}

    def report(phase: str, record: dict[str, Any]) -> None:
        hits, misses = counter(COMPILE_CACHE_HITS), counter(COMPILE_CACHE_MISSES)
        new = compiles[mark["compiles"]:]
        print(json.dumps({
            "phase": phase,
            "ok": True,
            "wall_s": round(time.perf_counter() - mark["t"], 2),
            # Set-up, not speed: what this phase spent in XLA/Mosaic compiles (or in
            # reading them back from the persistent cache), and the slowest programs.
            "setup": {
                "compile_s": round(sum(s for _, s in new), 2),
                "programs": len(new),
                "slowest": [
                    [name, round(s, 2)]
                    for name, s in sorted(new, key=lambda e: -e[1])[:4]
                ],
                "nanofed_compile_cache_hits_total": hits - mark["hits"],
                "nanofed_compile_cache_misses_total": misses - mark["misses"],
            },
            **record,
        }), flush=True)
        mark.update(t=time.perf_counter(), hits=hits, misses=misses, compiles=len(compiles))

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        run_phases(FULL, Path(tmp), devices, report)

    print(json.dumps({
        "smoke": "passed",
        "wall_s": round(time.perf_counter() - t0, 1),
        "nanofed_compile_cache_hits_total": counter(COMPILE_CACHE_HITS),
        "nanofed_compile_cache_misses_total": counter(COMPILE_CACHE_MISSES),
        "compile_s": round(sum(s for _, s in compiles), 1),
    }), flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
