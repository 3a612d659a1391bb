#!/usr/bin/env python
"""Author + execute the tutorial notebook (parity: ``examples/mnist/tutorial.ipynb`` in
the reference, a 20-cell executed walkthrough whose cell outputs are the source of the
published baseline numbers).

Builds ``examples/mnist/tutorial.ipynb`` from the cell specs below with nbformat, then
executes it with nbconvert so the committed notebook carries REAL outputs.
"""

from __future__ import annotations

import sys
from pathlib import Path

import nbformat as nbf

REPO = Path(__file__).resolve().parent.parent

MD = [
    # 0
    """# NanoFed-TPU tutorial: federated learning as one SPMD program

This is the TPU-native re-telling of the reference tutorial
(`examples/mnist/tutorial.ipynb` in camille-004/nanofed). The reference runs an aiohttp
server plus client coroutines that exchange weights as JSON over localhost; every round
is a distributed-systems dance of polling, serialization and Python loops. Here the same
federated round is **one jitted XLA program over a device mesh**:

```
round = jit( shard_map( vmap(local_fit) ; psum-weighted-mean ) )
```

- every **client** is a slot on a named `clients` mesh axis (vmapped within a device,
  sharded across devices),
- **local training** is a `lax.scan` over batches inside `vmap` — no Python per-batch loop,
- **aggregation** (FedAvg) is a `psum` weighted mean across the mesh — the "network"
  is the TPU interconnect (ICI),
- the coordinator's wait-barrier disappears: SPMD lockstep *is* the barrier.
""",
    # 1
    """## 1. Platform setup

On a TPU host this cell is unnecessary — JAX finds the chips. For a portable tutorial we
force the **virtual 8-device CPU mesh** (the same trick `tests/conftest.py` uses), so
every `shard_map`/collective path below runs exactly as it would across 8 real chips.

> Skip this cell on a real TPU slice.""",
    # 2
    """## 2. Data: real images, federated

We use a real dataset that ships offline (scikit-learn's 1,797 handwritten 8×8 digit
images; swap in MNIST IDX files via `load_mnist(data_dir=...)` after running
`scripts/fetch_mnist.py`). `federate` partitions it into per-client shards and packs
them into ONE `ClientData` batch — a pytree of `[clients, samples, ...]` arrays with a
padding mask, because SPMD wants equal shapes, not ragged Python lists.""",
    # 3
    """## 3. Model: a pure `(init, apply)` pair

No `nn.Module`s: a model is a named pair of pure functions over an explicit parameter
pytree — the property that lets a whole federated round jit into one program.""",
    # 4
    """## 4. Train: the coordinator drives jitted SPMD rounds

`Coordinator` is the round engine (the reference's `Coordinator.train_round` polls an
HTTP buffer at 1 Hz; ours calls the compiled round step). Round 0 pays the XLA compile;
every later round is sub-millisecond-to-milliseconds at this scale.""",
    # 5
    """### Inspect the metrics artifacts

Per-round metrics land in `metrics/metrics_round_N.json` with per-client detail —
format parity with the reference's artifacts (its `coordinator.py:247-280`).""",
    # 6
    """## 5. Evaluation trajectory

`eval_every` evaluates the global model on held-out data inside the round loop; the
history lets us plot accuracy over rounds.""",
    # 7
    """## 6. Differential privacy in one argument

`central_privacy` turns the reduce into DP-FedAvg: per-client update clipping + Gaussian
noise INSIDE the jitted aggregation, and the coordinator accounts the (ε, δ) spend per
round (`privacy_epsilon` in the metrics).""",
    # 8
    """## 7. Checkpoint & resume

`FileStateStore` checkpoints round state; a new `Coordinator` with the same store picks
up at the next round — resume is integrated into the engine (the reference ships a
recovery module but never wires it in).""",
    # 9
    """## 8. Privacy calibration: pick σ for your budget, not by hand

The reference makes users choose a noise multiplier and hope; here
`noise_multiplier_for_budget` inverts the tight RDP accountant — give it (ε, δ) and the
round count, get the smallest σ that stays within budget.""",
    # 10
    """## 9. Secure aggregation over a REAL network

The masked round end-to-end on localhost aiohttp: clients enroll X25519 keys, fetch the
roster (canonical order + server-computed normalized weights), pre-scale + quantize +
pairwise-mask their update, and POST the masked uint32 vector. The server modular-sums —
the pairwise masks cancel *exactly* — and dequantizes the cohort's weighted mean. It
never sees an individual update. (This is the single-round no-dropout Bonawitz variant;
a missing client fails the round closed.)""",
    # 11
    """## 10. Dropout-tolerant secure aggregation (double masking)

In a real federation, dropout is the common case — one flaky phone must not kill the
cohort's round. `dropout_tolerant=True` runs the Bonawitz §4 double-masking variant:

1. each round, every client draws a **fresh ephemeral mask key + self-mask seed** and
   Shamir-shares both across the cohort (sealed blobs routed through — but unreadable
   by — the server; per-round freshness means a reveal burns only that round);
2. clients mask with pairwise streams **plus a self mask** and submit;
3. whoever misses the timeout is *dropped*: survivors answer the server's **unmask
   request** with shares of the dropped clients' mask keys and the survivors' self
   seeds — never both secrets of one client;
4. the coordinator reconstructs the orphaned masks, completes the round as the
   **weighted FedAvg of the survivors**, and evicts the dropped client.

Below, `c3` vanishes mid-round (after the share barrier — its masks are already baked
into everyone's vectors) and the round still completes from 3 survivors.

> **Serving this over the wire** (`nanofed-tpu serve --secure --dropout-tolerant`):
> `--min-clients` is a true *minimum* — enrollment stays open for stragglers (cap it
> with `--max-clients`) until the roster quiesces, and the Shamir threshold is derived
> from the cohort that **actually enrolled** (`max(configured, n//2+1)`, the
> split-view floor), announced to clients in the roster and re-derived per round as
> evictions shrink the active cohort. The static `threshold=3` below is the
> library-level equivalent for this fixed 4-client demo cohort.""",
    # 12
    """## 11. Per-round learning-rate schedules

Round-wise client-lr decay is standard FL practice the reference lacks. The TPU
constraint shapes the design: re-baking `TrainingConfig.learning_rate` per round is a
*static* jit-argument change — every round would re-trace and re-compile (~20-40 s on
a chip). Instead the schedule's scale streams through the compiled round step as a
**traced scalar** (`round_step(..., lr_scale)`): one program, zero recompiles, and a
resumed run continues the schedule exactly (it is a pure function of the round index).

The *server* optimizer needs no machinery at all — its optax state persists across
rounds, so `fedadam_strategy(learning_rate=optax.cosine_decay_schedule(...))` steps
per round natively.""",
    # 13
    """## 12. SCAFFOLD: correct the drift instead of damping it

Under non-IID data, FedAvg's local steps follow each client's own gradient field and
drift toward local optima; FedProx pulls iterates back with a proximal term.
**SCAFFOLD** (Karimireddy et al. 2020) removes the drift at its source: every local
step is corrected by (server control − client control), so in expectation each client
walks the *global* descent direction even on a one-class shard. The population's
client controls live as ONE stacked pytree sharded over the `clients` mesh axis —
under partial participation the cohort's control rows are gathered alongside its data
rows and the round's deltas scatter-added back.

Partial participation is exactly where it shines (each round's cohort is a biased
sample; the stored controls carry the absent clients' directions into the round), and
the correction is one round stale — it wants a *smaller* local lr than FedAvg's tuned
value (the paper's η_l = O(1/K) bound; `runs/scaffold_r05.json` records a diverged
lr=0.5 arm alongside the win).""",
    # 14
    """## 13. q8-delta wire compression

In a real cross-device federation the client→server update is the bandwidth bill.
`HTTPClient(update_encoding="q8-delta")` ships each round's **delta** stochastically
rounded to int8 with per-leaf absmax scales: unbiased (FedAvg's mean averages the
rounding noise away), **5.25×** fewer bytes than the already-binary npz format — 32×
fewer than the reference's JSON float lists — and signatures still verify, because
the client signs the server's exact float32 reconstruction. Measured end-to-end:
identical final accuracy after 15 fully-quantized rounds
(`runs/wire_compression_r05.json`). Below, the codec itself on a real trained
delta.""",
    # 15
    """## 14. Personalized evaluation

Global accuracy understates what federation gives each participant under non-IID
data: a client holding two classes doesn't need the 10-class decision boundary — it
needs a model that is excellent on ITS distribution after a few local steps.
`split_client_data` carves an honest per-client held-out split, and
`make_personalized_evaluator` fine-tunes the global model on each client's train
split and tests on its held-out split — one `jit(vmap(...))` over the whole
population, reusing the rounds' exact local-fit program. Measured at scale:
global 91.6% → personalized **99.4%** (`runs/personalization_r05.json`).""",
    # 16
    """## 15. Asynchronous federation (FedBuff)

The synchronous protocol is a barrier: every round waits for its slowest client.
`NetworkRoundConfig(async_buffer_k=K)` (CLI: `serve --async-buffer K`) removes it —
the server accepts updates based on any of the last `staleness_window` published
versions and aggregates exactly K whenever they arrive, each delta computed against
the version its client actually fetched and discounted by `(1+s)^-α` (Nguyen et al.
2022). Below, three clients at different speeds feed a live aiohttp server: no
aggregation waits for a cohort, and stale updates contribute at a discount instead
of gating anyone. Measured at scale (`runs/asyncfed_r05.json`): 5.4× faster to the
same update budget than the barrier, at higher accuracy.""",
    # 17
    """## Where to go next

- **Scale**: `client_chunk` trains 1000 clients on 8 chips in sequential chunks
  (`nanofed-tpu bench mnist_1000`); `compute_dtype="bfloat16"` engages the MXU.
  `python chip_smoke.py` runs that configuration end to end on a TPU; its speed on
  the current code is in `PERF_LEDGER.jsonl` once measured (`PERF.md`).
- **Real networks**: `nanofed_tpu.communication` has a binary-payload HTTP server/client
  with RSA-PSS-signed updates and optional q8-delta compression;
  `examples/secure_federation/run_secure.py` is the full secure-aggregation protocol as
  a runnable script (`--dropout-tolerant --drop-client 2` demos multi-round recovery +
  eviction), and `nanofed-tpu serve --secure --dropout-tolerant` hosts it from the CLI.
- **Robustness**: `--robust-trim K` (or `method="median"`) bounds Byzantine clients
  structurally — measured holding 97.5% while plain FedAvg collapses to 7.8% under
  2 poisoned clients (`runs/byzantine_r05.json`).
- **Profiling**: `nanofed_tpu.utils.profiling.trace` captures TensorBoard/Perfetto
  device traces of a round.
- **Benchmarks**: `nanofed-tpu bench --list`; accuracy evidence in
  `runs/accuracy_digits_100c_r05.json` (the 97% bar met at 100 clients) and
  `runs/accuracy_digits_cnn28_r03.json` (the flagship CNN at 97.2% on real images).""",
]

CODE = [
    # A (after MD 1)
    """import os
from nanofed_tpu.utils.platform import force_cpu_mesh
force_cpu_mesh(8)   # portable tutorial: 8 virtual devices; skip on a real TPU slice

import jax
print(f"{len(jax.devices())} devices:", jax.devices()[:2], "...")""",
    # B (after MD 2)
    """from nanofed_tpu.data import federate, load_digits_dataset, pack_eval

train, test = load_digits_dataset("train"), load_digits_dataset("test")
print(f"train {train.x.shape}, test {test.x.shape}  (real 8x8 digit images)")

client_data = federate(train, num_clients=8, scheme="iid", batch_size=16, seed=0)
print("federated:", jax.tree.map(lambda a: a.shape, client_data))""",
    # C (after MD 3)
    """from nanofed_tpu.models import get_model, list_models
from nanofed_tpu.trainer import TrainingConfig

print("model zoo:", list_models())
model = get_model("digits_mlp", hidden=96)
training = TrainingConfig(batch_size=16, local_epochs=2, learning_rate=0.5)
params = model.init(jax.random.key(0))
print("params:", jax.tree.map(lambda a: a.shape, params))""",
    # D (after MD 4)
    """import time
from nanofed_tpu.orchestration import Coordinator, CoordinatorConfig

coord = Coordinator(
    model=model,
    train_data=client_data,
    config=CoordinatorConfig(num_rounds=10, seed=0, base_dir="runs/tutorial",
                             eval_every=2),
    training=training,
    eval_data=pack_eval(test, batch_size=128),
)
t0 = time.time()
history = coord.run()
print(f"{len(history)} rounds in {time.time()-t0:.2f}s "
      f"(round 0 includes the XLA compile)")
for m in history[-3:]:
    print(f"  round {m.round_id}: loss={m.agg_metrics['loss']:.4f} "
          f"acc={m.agg_metrics['accuracy']:.4f} ({m.duration_s*1e3:.1f} ms)")""",
    # E (after MD 5)
    """import json, pathlib
artifact = json.loads(pathlib.Path("runs/tutorial/metrics/metrics_round_9.json").read_text())
print(json.dumps({k: v for k, v in artifact.items() if k != "clients"}, indent=2))
print("per-client weights:", [round(w, 3) for w in artifact["clients"]["weights"]])""",
    # F (after MD 6)
    """final = coord.evaluate()
print("final held-out:", final)
accs = [(m.round_id, m.eval_metrics["accuracy"]) for m in history if m.eval_metrics]
for r, a in accs:
    print(f"  round {r}: test acc {a:.4f} " + "#" * int(a * 40))""",
    # G (after MD 7)
    """from nanofed_tpu.aggregation import PrivacyAwareAggregationConfig
from nanofed_tpu.privacy import PrivacyConfig

dp_coord = Coordinator(
    model=model,
    train_data=client_data,
    config=CoordinatorConfig(num_rounds=3, seed=0, base_dir="runs/tutorial_dp"),
    training=training,
    central_privacy=PrivacyAwareAggregationConfig(
        privacy=PrivacyConfig(epsilon=8.0, delta=1e-5,
                              max_gradient_norm=1.0, noise_multiplier=0.7),
    ),
)
dp_history = dp_coord.run()
for m in dp_history:
    print(f"round {m.round_id}: acc={m.agg_metrics['accuracy']:.4f} "
          f"ε spent={m.agg_metrics['privacy_epsilon']:.3f} "
          f"(δ={m.agg_metrics['privacy_delta']:.0e})")""",
    # H (after MD 8)
    """import shutil

from nanofed_tpu.persistence import FileStateStore

# Fresh store: a leftover checkpoint from an earlier run would make BOTH
# coordinators resume instead of demonstrating train -> crash -> resume.
shutil.rmtree("runs/tutorial_ckpt", ignore_errors=True)
store = FileStateStore("runs/tutorial_ckpt")
c1 = Coordinator(model=model, train_data=client_data,
                 config=CoordinatorConfig(num_rounds=2, seed=0,
                                          base_dir="runs/tutorial_ckpt"),
                 training=training, state_store=store)
c1.run()
print("trained rounds 0-1; store has round", store.restore_latest().round_number)

c2 = Coordinator(model=model, train_data=client_data,
                 config=CoordinatorConfig(num_rounds=4, seed=0,
                                          base_dir="runs/tutorial_ckpt"),
                 training=training, state_store=FileStateStore("runs/tutorial_ckpt"))
resumed = c2.run()
print("resumed coordinator ran rounds:", [m.round_id for m in resumed])""",
    # I (after MD 9)
    """from nanofed_tpu.privacy.accounting import RDPAccountant, noise_multiplier_for_budget

rounds = 10
sigma = noise_multiplier_for_budget(epsilon=8.0, delta=1e-5,
                                    sampling_rate=1.0, num_events=rounds)
print(f"calibrated sigma for (eps=8, delta=1e-5) over {rounds} rounds: {sigma:.4f}")

acc = RDPAccountant()
acc.add_noise_event(sigma, 1.0, count=rounds)
print(f"spend check: eps={acc.get_privacy_spent(1e-5).epsilon_spent:.4f} <= 8.0")""",
    # J (after MD 10)
    """import asyncio, socket, numpy as np
from nanofed_tpu.communication import (HTTPClient, HTTPServer,
                                       NetworkCoordinator, NetworkRoundConfig)
from nanofed_tpu.security.secure_agg import (ClientKeyPair, SecureAggregationConfig,
                                             mask_update)

with socket.socket() as s:      # pick a free port (portable notebook)
    s.bind(("127.0.0.1", 0))
    PORT = s.getsockname()[1]

cfg = SecureAggregationConfig(min_clients=3)
init = model.init(jax.random.key(0))
local = {f"c{i}": model.init(jax.random.key(10 + i)) for i in range(3)}

async def secure_client(cid, n_samples):
    kp = ClientKeyPair.generate()
    async with HTTPClient(f"http://127.0.0.1:{PORT}", cid, timeout_s=30) as c:
        assert await c.register_secagg(kp.public_bytes(), n_samples)
        roster = await c.fetch_secagg_roster()
        for _ in range(200):                      # bounded: a failed round must error,
            try:                                  # not hang the notebook
                params, rnd, active = await c.fetch_global_model(like=init)
                break
            except Exception:
                await asyncio.sleep(0.05)
        else:
            raise TimeoutError("model never published")
        masked = mask_update(local[cid], roster.index_of(cid), kp,
                             roster.ordered_keys(), rnd, cfg,
                             weight=roster.weights[cid])
        await c.submit_masked_update(masked, {"num_samples": n_samples})

async def secure_round():
    server = HTTPServer(port=PORT)
    await server.start()
    try:
        nc = NetworkCoordinator(server, init,
                                NetworkRoundConfig(num_rounds=1, min_clients=3,
                                                   round_timeout_s=30),
                                secure=cfg)
        await asyncio.gather(nc.run(), secure_client("c0", 30.0),
                             secure_client("c1", 10.0), secure_client("c2", 20.0))
        return nc
    finally:
        await server.stop()

nc = await secure_round()
print("history:", nc.history)
delta = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a - b)).max()),
                     nc.params, init)
print("aggregate moved (max |leaf delta|):", delta)""",
    # K (after MD 11) — dropout-tolerant double masking with a mid-round crash
    """import hashlib
from nanofed_tpu.security.secure_agg import (build_unmask_reveals,
                                             make_dropout_shares, open_share_inbox)

with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    PORT2 = s.getsockname()[1]

# threshold > n/2 (split-view defense); min_clients=3 is the privacy floor the
# 3 survivors still satisfy.
cfg_t = SecureAggregationConfig(min_clients=3, threshold=3, dropout_tolerant=True)
order4 = [f"c{i}" for i in range(4)]
local4 = {c: model.init(jax.random.key(20 + i)) for i, c in enumerate(order4)}

async def tolerant_client(cid, n_samples, drops=False):
    identity = ClientKeyPair.generate()
    async with HTTPClient(f"http://127.0.0.1:{PORT2}", cid, timeout_s=30) as c:
        assert await c.register_secagg(identity.public_bytes(), n_samples)
        roster = await c.fetch_secagg_roster()
        for _ in range(200):
            try:
                params, rnd, active = await c.fetch_global_model(like=init)
                break
            except Exception:
                await asyncio.sleep(0.05)
        else:
            raise TimeoutError("model never published")
        # Round start: fresh ephemeral secrets, Shamir-shared across the cohort.
        participants = await c.fetch_secagg_participants()
        mask_key = ClientKeyPair.generate()
        ctx = f"{c.secagg_session}:{rnd}"
        self_seed, sealed = make_dropout_shares(
            identity, mask_key, participants,
            {p: roster.public_keys[p] for p in participants}, cfg_t.threshold,
            my_id=cid, context=ctx)
        assert await c.deposit_secagg_shares(
            rnd, mask_key.public_bytes(), sealed,
            self_seed_commitment=hashlib.sha256(self_seed).digest())
        epks, inbox = await c.fetch_secagg_inbox(rnd)
        held = open_share_inbox(identity, cid, roster.public_keys, inbox, epks, ctx)
        if drops:
            print(f"  {cid}: crashing mid-round (after the share barrier)")
            return
        masked = mask_update(local4[cid], participants.index(cid), mask_key,
                             [epks[p] for p in participants], rnd, cfg_t,
                             weight=roster.weights[cid], self_seed=self_seed)
        await c.submit_masked_update(masked, {"num_samples": n_samples})
        for _ in range(600):                       # answer the unmask round
            request = await c.poll_unmask_request()
            if request is not None and cid in request["survivors"]:
                await c.submit_unmask_reveals(
                    request["round"], build_unmask_reveals(request, cid, held))
                return
            status = await c.check_server_status()
            if not status.get("training_active", True):
                return
            await asyncio.sleep(0.05)

async def tolerant_round():
    server = HTTPServer(port=PORT2)
    await server.start()
    try:
        nc = NetworkCoordinator(server, init,
                                NetworkRoundConfig(num_rounds=1, min_clients=4,
                                                   min_completion_rate=0.5,
                                                   round_timeout_s=2.5),
                                secure=cfg_t)
        await asyncio.gather(nc.run(),
                             tolerant_client("c0", 30.0), tolerant_client("c1", 10.0),
                             tolerant_client("c2", 20.0),
                             tolerant_client("c3", 40.0, drops=True))
        return nc
    finally:
        await server.stop()

nc2 = await tolerant_round()
print("history:", nc2.history)
assert nc2.history[0]["status"] == "COMPLETED" and nc2.history[0]["num_dropped"] == 1""",
    # L (after MD 12) — per-round lr schedule: decaying scale, zero recompiles
    """sched_coord = Coordinator(
    model=model,
    train_data=client_data,
    config=CoordinatorConfig(num_rounds=6, seed=0, base_dir="runs/tutorial_sched",
                             save_metrics=False, eval_every=2,
                             lr_schedule="cosine", lr_min_factor=0.2),
    training=TrainingConfig(batch_size=16, local_epochs=2, learning_rate=0.5),
    eval_data=pack_eval(test, batch_size=128),
)
scales = []
for m in sched_coord.start_training():
    scales.append(m.agg_metrics["lr_scale"])
    acc = m.eval_metrics.get("accuracy")
    print(f"round {m.round_id}: lr_scale={scales[-1]:.3f}"
          + (f"  test acc {acc:.4f}" if acc is not None else ""))
assert scales[0] == 1.0 and all(a >= b for a, b in zip(scales, scales[1:]))
assert scales[-1] > 0.2  # decayed toward — but never ONTO — the floor""",
    # M (after MD 13): SCAFFOLD vs FedAvg under drift + partial participation
    """drift_data = federate(train, num_clients=16, scheme="dirichlet",
                      batch_size=16, seed=1, alpha=0.05)  # ~1-2 classes per client

finals = {}
for name, scaffold in (("fedavg", False), ("scaffold", True)):
    c = Coordinator(
        model=model, train_data=drift_data,
        config=CoordinatorConfig(num_rounds=12, seed=0, participation_rate=0.5,
                                 base_dir="runs/nb_scaffold", save_metrics=False),
        training=TrainingConfig(batch_size=16, local_epochs=16, learning_rate=0.2),
        eval_data=pack_eval(test, batch_size=128),
        scaffold=scaffold,
    )
    c.run()
    finals[name] = c.evaluate()["accuracy"]
    print(f"{name:9s} final held-out accuracy: {finals[name]:.4f}")
print(f"drift correction buys {finals['scaffold'] - finals['fedavg']:+.4f}")""",
    # N (after MD 14): q8-delta codec on a real trained delta
    """import numpy as np
from nanofed_tpu.communication import (decode_delta_q8, encode_delta_q8,
                                       encode_params)
from nanofed_tpu.trainer import make_local_fit

fit = make_local_fit(model.apply, TrainingConfig(batch_size=16, local_epochs=2,
                                                 learning_rate=0.2))
one = jax.tree.map(lambda a: jax.numpy.asarray(a[0]), client_data)
res = fit(params, one, jax.random.key(3))
delta = jax.tree.map(lambda p, g: np.asarray(p, np.float32) - np.asarray(g, np.float32),
                     res.params, params)

wire_q8 = encode_delta_q8(delta, seed=0)
wire_npz = encode_params(res.params)
dq = decode_delta_q8(wire_q8, like=delta)
err = max(float(np.abs(a - b).max())
          for a, b in zip(jax.tree.leaves(dq), jax.tree.leaves(delta)))
print(f"npz full params: {len(wire_npz):7d} bytes")
print(f"q8 delta:        {len(wire_q8):7d} bytes  ({len(wire_npz)/len(wire_q8):.2f}x smaller)")
print(f"max dequantization error: {err:.2e} (bounded by absmax/127 per leaf)")""",
    # O (after MD 15): personalized evaluation on the drift federation
    """from nanofed_tpu.trainer import make_personalized_evaluator, split_client_data

fit_cd, heldout_cd = split_client_data(drift_data, test_fraction=0.25, seed=0)
pers_coord = Coordinator(
    model=model, train_data=fit_cd,
    config=CoordinatorConfig(num_rounds=8, seed=0, base_dir="runs/nb_pers",
                             save_metrics=False),
    training=TrainingConfig(batch_size=16, local_epochs=4, learning_rate=0.5),
)
pers_coord.run()
evaluate = make_personalized_evaluator(
    model.apply, TrainingConfig(batch_size=16, local_epochs=3, learning_rate=0.1))
out = evaluate(pers_coord.params, fit_cd, heldout_cd, jax.random.key(7))
print(f"on clients' OWN held-out data:")
print(f"  global model:       {float(out['global_accuracy']):.4f}")
print(f"  after 3 fine-tune epochs: {float(out['personal_accuracy']):.4f}"
      f"  (gain {float(out['personalization_gain']):+.4f})")""",
    # P (after MD 16): FedBuff async federation over live aiohttp (top-level await)
    """import asyncio
from nanofed_tpu.communication import (HTTPClient, HTTPServer,
                                       NetworkCoordinator, NetworkRoundConfig)
from nanofed_tpu.trainer.local import make_local_fit as _mlf

async_fit = jax.jit(_mlf(model.apply, TrainingConfig(batch_size=16, local_epochs=1,
                                                     learning_rate=0.3)))
async_init = model.init(jax.random.key(0))
_ = async_fit(async_init, jax.tree.map(lambda a: jax.numpy.asarray(a[0]), client_data),
              jax.random.key(0))  # warm the compile outside the timed federation

async def nb_client(cid, idx, delay, port):
    data = jax.tree.map(lambda a: jax.numpy.asarray(a[idx]), client_data)
    async with HTTPClient(f"http://127.0.0.1:{port}", cid, timeout_s=30) as c:
        while True:
            try:
                fetched, rnd, active = await c.fetch_global_model(like=async_init)
                if not active:
                    return
                r = async_fit(jax.tree.map(jax.numpy.asarray, fetched), data,
                              jax.random.key(idx * 100 + rnd))
                await asyncio.sleep(delay)   # heterogeneous device speed
                await c.submit_update(r.params, {"loss": float(r.metrics.loss),
                                                 "num_samples": 100.0})
            except Exception:
                return

import socket
with socket.socket() as _s:      # pick a free port (portable notebook)
    _s.bind(("127.0.0.1", 0))
    PORT = _s.getsockname()[1]
server = HTTPServer(port=PORT)
coord = NetworkCoordinator(server, async_init, NetworkRoundConfig(
    num_rounds=6, async_buffer_k=2, staleness_window=6,
    round_timeout_s=20.0, poll_interval_s=0.01))
await server.start()
tasks = [asyncio.ensure_future(nb_client(f"c{i}", i, 0.08 if i == 0 else 0.02, PORT))
         for i in range(3)]
history = await coord.run()
await asyncio.gather(*tasks)
await server.stop()
all_staleness = []
for h in history:
    s = h.get("staleness", [])   # FAILED records carry no staleness
    all_staleness += s
    print(f"aggregation {h['aggregation']} [{h['status']}]: "
          f"{h['num_clients']} updates, staleness {s}")
stale = sum(v > 0 for v in all_staleness)
print(f"{stale}/{len(all_staleness)} aggregated updates were stale — "
      "discounted by (1+s)^-0.5, and no aggregation waited for a cohort")
assert stale > 0  # the demo only teaches what its own run shows""",
]


def build() -> nbf.NotebookNode:
    nb = nbf.v4.new_notebook()
    nb.metadata["kernelspec"] = {"name": "python3", "display_name": "Python 3",
                                 "language": "python"}
    cells = [nbf.v4.new_markdown_cell(MD[0])]
    # MD[i] pairs with CODE[i-1]; the last MD entry is the unpaired closing section —
    # derived, so adding a section is one MD + one CODE append, not three edits.
    for md_i in range(1, len(CODE) + 1):
        cells.append(nbf.v4.new_markdown_cell(MD[md_i]))
        cells.append(nbf.v4.new_code_cell(CODE[md_i - 1]))
    cells.append(nbf.v4.new_markdown_cell(MD[-1]))
    nb.cells = cells
    return nb


def main() -> int:
    out = REPO / "examples" / "mnist" / "tutorial.ipynb"
    nb = build()
    nbf.write(nb, out)
    print(f"wrote {out} ({len(nb.cells)} cells); executing...")

    from nbclient import NotebookClient

    client = NotebookClient(nb, timeout=600, kernel_name="python3",
                            resources={"metadata": {"path": str(REPO)}})
    client.execute()
    nbf.write(nb, out)
    print("executed + saved with outputs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
