"""End-to-end federated training on the CPU mesh — the replacement for the reference's
``tests/integration/test_client_server_communication.py`` (which needed a live aiohttp
server; here the transport is the mesh itself)."""

import json

import jax
import numpy as np
import pytest

from nanofed_tpu.data import federate, pack_eval, synthetic_classification
from nanofed_tpu.models import get_model
from nanofed_tpu.orchestration import Coordinator, CoordinatorConfig, RoundStatus
from nanofed_tpu.trainer import TrainingConfig


@pytest.fixture(scope="module")
def mlp():
    return get_model("mlp", in_features=16, hidden=32, num_classes=4)


def _data(n=1024, classes=4, feat=16, seed=0):
    return synthetic_classification(n, classes, (feat,), seed=seed)


def test_full_training_run_learns_and_writes_metrics(mlp, tmp_path, devices):
    train = _data()
    test = _data(n=256, seed=9)
    cd = federate(train, num_clients=8, scheme="iid", batch_size=32)
    coord = Coordinator(
        model=mlp,
        train_data=cd,
        config=CoordinatorConfig(num_rounds=4, seed=0, base_dir=tmp_path, eval_every=2),
        training=TrainingConfig(batch_size=32, local_epochs=2),
        eval_data=pack_eval(test, batch_size=64),
    )
    rounds = coord.run()
    assert len(rounds) == 4
    assert all(r.status == RoundStatus.COMPLETED for r in rounds)
    # Learning happened and generalized.
    assert rounds[-1].agg_metrics["loss"] < rounds[0].agg_metrics["loss"]
    final = coord.evaluate()
    assert final["accuracy"] > 0.9

    # Per-round metrics JSON parity (coordinator.py:247-280).
    f = tmp_path / "metrics" / "metrics_round_2.json"
    payload = json.loads(f.read_text())
    assert payload["round_id"] == 2
    assert payload["status"] == "completed"
    assert len(payload["clients"]["weights"]) == 8
    # round ids are 0-based; eval_every=2 evaluates after rounds 1 and 3, not 2.
    assert payload["eval_metrics"] == {}
    f3 = json.loads((tmp_path / "metrics" / "metrics_round_3.json").read_text())
    assert "accuracy" in f3["eval_metrics"]


def test_eval_every_schedule(mlp, tmp_path, devices):
    cd = federate(_data(n=256), num_clients=8, scheme="iid", batch_size=16)
    coord = Coordinator(
        model=mlp,
        train_data=cd,
        config=CoordinatorConfig(num_rounds=2, base_dir=tmp_path, eval_every=2),
        training=TrainingConfig(batch_size=16),
        eval_data=pack_eval(_data(n=128, seed=5), batch_size=64),
    )
    rounds = coord.run()
    assert rounds[0].eval_metrics == {}
    assert "accuracy" in rounds[1].eval_metrics


def test_partial_participation_and_dropout_failed_rounds(mlp, tmp_path, devices):
    cd = federate(_data(n=512), num_clients=8, scheme="iid", batch_size=16)
    coord = Coordinator(
        model=mlp,
        train_data=cd,
        config=CoordinatorConfig(
            num_rounds=6,
            participation_rate=0.5,  # cohort of 4
            dropout_rate=0.9,  # nearly everyone "times out"
            min_completion_rate=0.75,  # needs 3/4 to survive
            base_dir=tmp_path,
        ),
        training=TrainingConfig(batch_size=16),
    )
    rounds = coord.run()
    failed = [r for r in rounds if r.status == RoundStatus.FAILED]
    assert failed, "with 90% dropout some rounds must fail"
    # Failed rounds leave the model untouched and carry no agg metrics.
    assert all(r.agg_metrics == {} for r in failed)
    progress = coord.training_progress
    assert progress.failed_rounds == len(failed)
    assert progress.completed_rounds == 6 - len(failed)


def test_unequal_client_sizes(mlp, tmp_path, devices):
    """The reference example's 12k/8k/4k pattern, scaled down: weights ∝ samples."""
    from nanofed_tpu.data import iid_partition, pack_clients

    ds = _data(n=700)
    parts = iid_partition(700, 3, seed=0, proportions=[0.5, 0.3, 0.2])
    cd = pack_clients(ds, parts, batch_size=16)
    coord = Coordinator(
        model=get_model("mlp", in_features=16, hidden=32, num_classes=4),
        train_data=cd,  # 3 clients on 8 devices -> padded to 8
        config=CoordinatorConfig(num_rounds=2, base_dir=tmp_path),
        training=TrainingConfig(batch_size=16),
    )
    rounds = coord.run()
    assert all(r.status == RoundStatus.COMPLETED for r in rounds)
    assert rounds[0].agg_metrics["participating_clients"] == 3
    payload = json.loads((tmp_path / "metrics" / "metrics_round_0.json").read_text())
    w = np.asarray(payload["clients"]["weights"])
    assert w[0] > w[1] > w[2] > 0
    assert np.all(w[3:] == 0)  # padded dummy clients


def test_label_skew_noniid_run(mlp, tmp_path, devices):
    """Benchmark config #2 shape: non-IID label-skew with partial participation."""
    cd = federate(
        _data(n=512), num_clients=16, scheme="label_skew", batch_size=16, shards_per_client=2
    )
    coord = Coordinator(
        model=mlp,
        train_data=cd,
        config=CoordinatorConfig(
            num_rounds=3, participation_rate=0.25, base_dir=tmp_path, seed=1
        ),
        training=TrainingConfig(batch_size=16),
    )
    rounds = coord.run()
    assert all(r.status == RoundStatus.COMPLETED for r in rounds)
    assert all(r.agg_metrics["participating_clients"] == 4 for r in rounds)


def test_run_experiment_cli_engine(tmp_path, devices):
    from nanofed_tpu.experiments import run_experiment

    out = run_experiment(
        model="mlp",
        num_clients=8,
        num_rounds=2,
        local_epochs=1,
        batch_size=32,
        out_dir=tmp_path,
        train_size=512,
    )
    assert out["rounds_completed"] == 2
    assert "accuracy" in out["final_eval_metrics"]


def test_central_privacy_accounting_surfaces_epsilon(mlp, tmp_path, devices):
    """The coordinator owns an accountant when central DP is configured: ε/δ spend shows
    up in every completed round's metrics and accumulates monotonically."""
    from nanofed_tpu.aggregation import PrivacyAwareAggregationConfig
    from nanofed_tpu.privacy import PrivacyConfig

    cd = federate(_data(n=256), num_clients=8, scheme="iid", batch_size=16)
    coord = Coordinator(
        model=mlp,
        train_data=cd,
        config=CoordinatorConfig(num_rounds=3, base_dir=tmp_path),
        training=TrainingConfig(batch_size=16),
        central_privacy=PrivacyAwareAggregationConfig(
            privacy=PrivacyConfig(max_gradient_norm=1.0, noise_multiplier=1.0)
        ),
    )
    rounds = coord.run()
    eps = [r.agg_metrics["privacy_epsilon"] for r in rounds]
    assert all(e > 0 for e in eps)
    assert eps == sorted(eps) and eps[0] < eps[-1]  # cumulative across rounds
    assert rounds[-1].agg_metrics["privacy_delta"] == 1e-5
    assert coord.privacy_spent.epsilon_spent == pytest.approx(eps[-1])
    # And it lands in the persisted per-round metrics JSON.
    payload = json.loads((tmp_path / "metrics" / "metrics_round_2.json").read_text())
    assert payload["agg_metrics"]["privacy_epsilon"] == pytest.approx(eps[-1])


def test_central_privacy_accounts_at_realized_cohort_rate(mlp, tmp_path, devices):
    """Accounting must use the REALIZED inclusion probability cohort/N, not the nominal
    participation_rate: ceil + the floor-at-1 make cohort/N >= rate, and accounting at
    the smaller nominal q would under-report ε (q² amplification ⇒ ~25× at the extreme)."""
    from nanofed_tpu.aggregation import PrivacyAwareAggregationConfig
    from nanofed_tpu.privacy import PrivacyConfig

    cd = federate(_data(n=256), num_clients=8, scheme="iid", batch_size=16)
    coord = Coordinator(
        model=mlp,
        train_data=cd,
        # nominal q=0.02 -> cohort = max(1, ceil(0.16)) = 1 -> realized q = 1/8
        config=CoordinatorConfig(
            num_rounds=2, participation_rate=0.02, base_dir=tmp_path, seed=3
        ),
        training=TrainingConfig(batch_size=16),
        central_privacy=PrivacyAwareAggregationConfig(
            privacy=PrivacyConfig(max_gradient_norm=1.0, noise_multiplier=1.0)
        ),
    )
    assert coord.cohort_size == 1
    coord.run()
    events = coord.privacy_accountant.state_dict()["events"]
    assert events == [[1.0, 1 / 8, 2.0]]


def test_cohort_gather_equals_full_mask_round(mlp, tmp_path, devices):
    """Partial participation runs the round step over the GATHERED cohort (K_pad
    clients) instead of all N zero-weighted — at q=0.1 that is 10x less compute.
    The optimization must be invisible: same seed, same cohorts, identical released
    params as the full-N masked path.

    Single-batch clients (batch_size == the 16-sample per-client capacity): the
    gathered and full-N rounds are different compiled programs, and some jaxlib CPU
    backends (observed on 0.4.36) draw a context-DEPENDENT (valid, deterministic,
    but program-specific) epoch-shuffle permutation inside fused shard_map programs.
    One batch per client makes the shuffle a within-batch permutation, which every
    sum-reduction is invariant to — the equivalence this test pins (gather indices,
    client-stable keys, weighting) stays exact on every backend."""
    cd = federate(_data(n=256), num_clients=16, scheme="iid", batch_size=16)

    def make():
        return Coordinator(
            model=mlp,
            train_data=cd,
            config=CoordinatorConfig(
                num_rounds=3, participation_rate=0.25, seed=5, base_dir=tmp_path,
                save_metrics=False,
            ),
            training=TrainingConfig(batch_size=16),
        )

    gathered = make()
    assert gathered._cohort_mode and gathered._step_clients < gathered._padded_clients
    full = make()
    # Force the legacy full-N masked path on the second coordinator.
    full._cohort_mode = False
    full._step_clients = full._padded_clients
    gathered.run()
    full.run()
    for a, b in zip(jax.tree.leaves(gathered.params), jax.tree.leaves(full.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # Same cohorts were drawn (deterministic non-DP sampling), so the weighted train
    # metrics agree too.
    for ga, fu in zip(gathered.history, full.history):
        assert ga.agg_metrics["loss"] == pytest.approx(fu.agg_metrics["loss"], abs=1e-5)


def test_dp_cohort_sampling_uses_secret_randomness(mlp, tmp_path, devices):
    """Amplification-by-subsampling requires SECRET sampling randomness: under central
    DP the cohort must NOT be a deterministic function of the persisted config seed
    (two identically-seeded coordinators draw different cohorts), while the no-DP path
    stays reproducible from the seed."""
    from nanofed_tpu.aggregation import PrivacyAwareAggregationConfig
    from nanofed_tpu.privacy import PrivacyConfig

    cd = federate(_data(n=256), num_clients=64, scheme="iid", batch_size=4)

    def make(dp: bool, participation: float = 0.25):
        return Coordinator(
            model=mlp,
            train_data=cd,
            config=CoordinatorConfig(
                num_rounds=1, participation_rate=participation, base_dir=tmp_path,
                seed=7,
            ),
            training=TrainingConfig(batch_size=4),
            central_privacy=PrivacyAwareAggregationConfig(
                privacy=PrivacyConfig(max_gradient_norm=1.0, noise_multiplier=1.0)
            ) if dp else None,
        )

    # No-DP: deterministic in the config seed.
    plain = [sorted(make(False)._sample_cohort(0)) for _ in range(2)]
    assert plain[0] == plain[1]
    # DP: 16-of-64 cohorts from two identically-configured coordinators collide with
    # probability 1/C(64,16) ~ 2e-15 — a match means the seed leaked into sampling.
    dp = [sorted(make(True)._sample_cohort(0)) for _ in range(2)]
    assert dp[0] != dp[1]
    # And the DP draw is not the seed-derived draw either.
    assert dp[0] != plain[0] and dp[1] != plain[0]

    # The NOISE must be secret too: noise regenerable from the persisted seed could be
    # subtracted from the released aggregate, voiding DP outright.  Full participation
    # pins the cohort (all clients), so the noise key is the ONLY nondeterminism — two
    # identically-seeded DP coordinators must still release different params.
    a, b = make(True, participation=1.0), make(True, participation=1.0)
    list(a.start_training())
    list(b.start_training())
    leaves_a, leaves_b = (np.asarray(jax.tree.leaves(c.params)[0]) for c in (a, b))
    assert not np.array_equal(leaves_a, leaves_b)
    # And the per-client detail block (weights = cohort membership; un-noised update
    # norms) must not be persisted under DP.
    payload = json.loads((tmp_path / "metrics" / "metrics_round_0.json").read_text())
    assert "clients" not in payload


def test_no_privacy_no_accounting(mlp, tmp_path, devices):
    cd = federate(_data(n=128), num_clients=8, scheme="iid", batch_size=16)
    coord = Coordinator(
        model=mlp,
        train_data=cd,
        config=CoordinatorConfig(num_rounds=1, base_dir=tmp_path),
        training=TrainingConfig(batch_size=16),
    )
    rounds = coord.run()
    assert coord.privacy_spent is None
    assert "privacy_epsilon" not in rounds[0].agg_metrics


# Six small federations through the public ``run_experiment``: the only tier-1 runs of
# label-skew sampling, FedProx, central DP with a calibrated sigma, and bf16 compute with
# ``client_chunk``.  An MLP stands in for the CNN/ResNet each would train on a chip (their
# XLA compiles take minutes on the CPU mesh; unit forward tests cover the models).
_EXPERIMENT_SMOKE = {
    "mnist_iid": dict(
        num_clients=10, num_rounds=2, local_epochs=2, batch_size=64, learning_rate=0.1,
        scheme="iid", participation=1.0, train_size=640,
    ),
    "mnist_labelskew": dict(
        num_clients=16, num_rounds=2, local_epochs=1, batch_size=32, learning_rate=0.1,
        scheme="label_skew", participation=0.1, shards_per_client=2, train_size=1600,
    ),
    "fedprox_cifar10": dict(
        num_clients=8, num_rounds=1, local_epochs=1, batch_size=32, learning_rate=0.05,
        scheme="dirichlet", participation=0.1, alpha=0.5, prox_mu=0.01, train_size=512,
    ),
    "dp_fedavg_mnist": dict(
        num_clients=10, num_rounds=2, local_epochs=1, batch_size=64, learning_rate=0.1,
        scheme="iid", participation=1.0, train_size=640,
    ),
    "cross_silo": dict(
        num_clients=8, num_rounds=1, local_epochs=1, batch_size=32, learning_rate=0.05,
        scheme="iid", participation=1.0, train_size=256,
    ),
    # 32 clients >> 8 devices with client_chunk=2: the sequential-chunk path and bf16
    # mixed precision (the flagship configuration, scaled down for the CPU mesh).
    "mnist_1000": dict(
        num_clients=32, num_rounds=2, local_epochs=2, batch_size=64, learning_rate=0.1,
        scheme="iid", participation=1.0, client_chunk=2, compute_dtype="bfloat16",
        train_size=640,
    ),
}


@pytest.mark.parametrize("name", sorted(_EXPERIMENT_SMOKE))
def test_run_experiment_smoke(name, tmp_path):
    from nanofed_tpu.experiments import run_experiment

    config = dict(_EXPERIMENT_SMOKE[name], model="mlp")
    if name == "dp_fedavg_mnist":
        from nanofed_tpu.aggregation.privacy import PrivacyAwareAggregationConfig
        from nanofed_tpu.orchestration import cohort_size
        from nanofed_tpu.privacy import PrivacyConfig
        from nanofed_tpu.privacy.accounting import noise_multiplier_for_budget

        # Sigma calibrated so the whole run spends exactly the (8, 1e-5) budget at the
        # realized cohort rate.
        q = cohort_size(config["num_clients"], config["participation"]) / config["num_clients"]
        sigma = noise_multiplier_for_budget(
            8.0, 1e-5, sampling_rate=q, num_events=config["num_rounds"]
        )
        config["central_privacy"] = PrivacyAwareAggregationConfig(
            privacy=PrivacyConfig(
                epsilon=8.0, delta=1e-5, max_gradient_norm=1.0, noise_multiplier=sigma
            )
        )
    summary = run_experiment(out_dir=str(tmp_path), **config)
    assert summary["rounds_failed"] == 0
    assert summary["rounds_completed"] >= 1
    assert "accuracy" in summary["final_eval_metrics"]
    if name == "dp_fedavg_mnist":
        # The experiment summary surfaces cumulative DP spend.
        spent = summary["privacy_spent"]
        assert spent["epsilon_spent"] > 0
        assert 0 < spent["delta_spent"] <= 1e-5
    else:
        assert "privacy_spent" not in summary
