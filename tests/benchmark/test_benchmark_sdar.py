"""The block-diffusion mixture-of-experts configuration through the benchmark: a tiny
copy of it through ``run_cell`` the way ``tiny-keye.sync`` runs (the model carries its
objective, the reference its ``sample_nll``), its float8 control, the new reader on
recorded contexts, the operation and parameter counts against hand counts, the
kernel-execution constant against the ``pallas_call``s of a training step, and the
published keys against the catalog."""

import json
import shutil

import jax
import jax.numpy as jnp
import pytest

from benchlib import CPU_PEAKS, REPO, _merge

from benchmark import check, federation, run

NAME = "sdar-30b-6l-xsilo-4"
REAL = json.loads((REPO / "benchmark" / "configs" / f"{NAME}.json").read_text())
TINY_KWARGS = {
    "vocab": 64, "seq_len": 32, "block": 4, "width": 64,
    "layers": 2, "attn_heads": 4, "kv_heads": 2, "head_dim": 16, "rope_theta": 1000000,
    "experts": 16, "first_expert": 0, "experts_held": 4, "top_k": 3, "expert_width": 48,
    "eps": 1e-6,
}
TINY = {
    "name": "tiny-sdar",
    "model": {"factory": "diffusion_moe_lm", "kwargs": TINY_KWARGS},
    "federation": {"num_clients": 4, "samples_per_client": 8, "batch_size": 4, "learning_rate": 0.02},
    "reference": {"rounds": 3, "block": 2},
    # Read at this size on the CPU (seeds 1-8): the bf16 program gives up to 3.5e-5 /
    # 0.0026 / 0.0029, the float8 control 1.5e-6 to 2.6e-4 / 1.0 / 1.0 on every seed (the
    # loss hardly moves with precision; in float8 a leaf's gradient underflows to zero, so
    # its step's norm is all gap).
    "correct": {"loss_gap": 0.005, "first_step_gap": 0.06, "update_gap": 0.06},
}
CELL = "tiny-sdar.sync"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """BENCHMARK.json and a copy of ``benchmark/`` with one file ADDED: the tiny
    configuration, run under the mix the real cell runs under."""
    root = tmp_path_factory.mktemp("sdar_root")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmark" / "configs" / "tiny-sdar.json").write_text(json.dumps(_merge(REAL, TINY)))
    manifest["configs"].append({"name": "tiny-sdar", "source": REAL["source"],
                                "file": "benchmark/configs/tiny-sdar.json",
                                "reduced": ["federation"], "why": "tiny, for the tests"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-sdar",
                                  "traffic": "sync", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if f"{NAME}.sync" in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def _run(root, traced, seed=3):
    return run.run_cell(root, CELL, seed, 1.5, traced, jax.devices(), CPU_PEAKS)


def test_tiny_cell_is_correct_and_reports_the_end_to_end_metrics(tiny_root):
    result = _run(tiny_root, traced=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"round_s", "client_samples_per_s", "setup_s"}


def test_tiny_traced_run_reads_the_masked_share(tiny_root):
    """Through the default ``Coordinator``, no ``grad_fn`` passed: the objective's counter
    reaches ``RoundMetrics.agg_metrics`` beside the experts' three, and the reader finds
    it."""
    result = _run(tiny_root, traced=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    # 4 silos x 8 sequences x 32 positions a round, a level a block uniform over (0.001, 1).
    assert 40.0 < metrics["diffusion_masked_token_pct"]["value"] < 60.0
    assert 0.5 < metrics["moe_block_fill_pct"]["value"] < 10.0
    assert 0.4 < metrics["moe_held_rows_per_token"]["value"] < 1.2  # 3 picks, 4 of 16 held
    assert {"mfu_pct", "host_gap_ms", "moe_load_max_over_mean"} <= set(metrics)
    # No device trace on the CPU, no kernel at 64 positions: the share and the scopes are left out.
    assert not {"attn_kernel_roofline_pct", "diffusion_attention_ms_per_round",
                "diffusion_noise_ms_per_round", "head_loss_ms_per_round"} & set(metrics)


def _ctx(device_ops, rounds=3, counters=None):
    return {"trace": {"device_ops": device_ops}, "traced_rounds": rounds, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12}, "config": REAL,
            "rounds": [(0.1, type("M", (), {"agg_metrics": {"loss": 1.0, **(counters or {})}})())]}


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program from before the counter (the parent), a run not traced: nothing, and no
    raise."""
    masked = federation.load_named(REPO, "layer_metrics", "diffusion_masked_token_pct")
    assert masked.read(_ctx([])) is None
    assert masked.read({**_ctx([]), "rounds": []}) is None
    assert masked.read(_ctx([], counters={"diffusion_masked_share": 0.5005})) == pytest.approx(50.05)
    for metric in ("diffusion_attention_ms_per_round", "diffusion_noise_ms_per_round"):
        spec = federation.scope_metrics(REPO)[0][metric]
        assert run.scope_ms_per_round({"scopes": None, "traced_rounds": 0}, spec) is None
        assert run.scope_ms_per_round({"scopes": [[["local_fit"], "forward", 1.0]],
                                       "traced_rounds": 3}, spec) is None
        inside = [[["local_fit", spec["scopes"][0]], "forward", 0.003]]
        assert run.scope_ms_per_round({"scopes": inside, "traced_rounds": 3}, spec) == pytest.approx(1.0)


def test_the_kernels_share_counts_the_seen_pairs_and_never_reads_high():
    """The accepted, family-agnostic reader finds ``causal_attention_fwd_blocks`` /
    ``_bwd_blocks`` by their prefix and counts them by ``flops/sdar_moe.py``."""
    share = federation.load_named(REPO, "layer_metrics", "attn_kernel_roofline_pct")
    flops = _flops()
    kw, fed = REAL["model"]["kwargs"], REAL["federation"]
    one = lambda backward: 8 * flops.attention_kernel_flops(kw, backward=backward)
    at_peak = lambda name, backward: [name, 3 * one(backward) / 197e12]
    # The six layers' kernels, each exactly at the peak on the seen pairs: 100%.
    every = ([at_peak(f"causal_attention_fwd_blocks.{i}_bf16_", False) for i in range(6)]
             + [at_peak(f"causal_attention_bwd_blocks.{i}_bf16_", True) for i in range(6, 12)])
    assert share.read(_ctx(every)) == pytest.approx(100.0)
    total = sum(s for _, s in every) / 3 * 197e12
    assert total == pytest.approx(flops.attention_kernel_flops_per_round(kw, fed))
    # Executions that fell off the ten take their time AND their operations with them.
    assert share.read(_ctx(every[2:])) == pytest.approx(100.0)
    # Kernels at the peak on the 80 tiles of 512 they walk read the seen share of it: 80.1%.
    walked = 80 * 512 * 512 / flops.seen_pairs(4096, 4)
    assert share.read(_ctx([[n, walked * s] for n, s in every])) == pytest.approx(80.08, rel=1e-3)
    assert share.read(_ctx([["fusion.1_bf16_8_", 0.5]])) is None


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_float8_is_not_correct(tiny_root, seed):
    _, _, config, _ = run.load_cell(tiny_root, CELL)
    family = federation.load_named(tiny_root, "reference", config["family"])
    fedavg = federation.load_named(tiny_root, "reference", "fedavg")
    assert hasattr(family, "sample_nll") and hasattr(family, "log_probs")  # sample_nll decides
    model = federation.build_model(config, family, seed)
    data = federation.make_data(config, family, seed, model.input_shape, model.num_classes)
    rounds = config["reference"]["rounds"]
    ref = lambda q: check.reference_rounds(fedavg, family, config, data, seed, jax.devices()[0], rounds, q)
    exact, lower = ref(fedavg.identity), ref(fedavg.float8)
    want = check.norms(exact, exact["start"])
    rows = check.compare(check.norms(lower, exact["start"]), want, config["correct"])
    assert not all(r["ok"] for r in rows), rows
    # A loss at every masked position: every matrix learns, the routers and the experts of
    # the LAST layer too (a norm's weight of 1.0 can round a step of under 6e-8 away).
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(federation.make_weights(config, family, seed))]
    assert all(norm > 0 for name, norm in zip(names, want["update"]) if "norm" not in name)


def test_masks_row_gives_the_cut_chip_the_deployments_mean_share():
    """The benchmark's seeded weights: MASK's embedding row is an N(0, 1) draw whose eight
    picks land, in every layer, one on the 16 experts held here, by a margin (at seeded
    rows every masked position routes as its token does; ``reference/sdar_moe.py``
    ``mask_row`` says why the count must not be the seed's luck).  At the cell's widths."""
    family = federation.load_named(REPO, "reference", REAL["family"])
    kw = REAL["model"]["kwargs"]
    for seed in (1, 2):
        routers = 0.02 * jax.random.normal(jax.random.key(seed), (6, 2048, 128))
        row = jax.jit(lambda key: family.mask_row(key, routers, kw))(jax.random.key(100 + seed))
        assert 0.9 < float(row.std()) < 1.1 and abs(float(row.mean())) < 0.1
        unit = row / jnp.sqrt(jnp.mean(row * row))
        top, picks = jax.lax.top_k(jnp.einsum("d,lde->le", unit, routers, precision="highest"), 9)
        assert ((picks[:, :8] < 16).sum(axis=-1) == 1).all()  # one of eight a layer
        # ... by a margin: nudge every logit by a third of it and the count stands.
        logits = jnp.einsum("d,lde->le", unit, routers, precision="highest")
        nudged = logits + family.MASK_MARGIN / 3 * jnp.sign(jax.random.normal(jax.random.key(7), logits.shape))
        assert ((jax.lax.top_k(nudged, 8)[1] < 16).sum(axis=-1) == 1).all()
    # ... and it is the row the seeded tree carries, the other rows plain draws.
    tiny = federation.make_weights(_merge(REAL, TINY), family, 3)
    again = federation.make_weights(_merge(REAL, TINY), family, 3)
    assert bool((tiny["embed"] == again["embed"]).all()) and 0.8 < float(tiny["embed"].std()) < 1.2


def _flops():
    return federation.load_named(REPO, "flops", REAL["family"])


def test_flops_match_a_hand_count():
    kw = REAL["model"]["kwargs"]
    flops = _flops()
    # Pairs a doubled stream: 4096 x 4100 / 2 clean-clean, 4096 x 4092 / 2 noised-clean,
    # 4096 x 4 noised-noised: L^2 + L B.
    assert flops.seen_pairs(4096, 4) == 8_396_800 + 8_380_416 + 16_384 == 16_793_600
    assert flops.seen_pairs(8, 4) == sum([4, 4, 4, 4, 8, 8, 8, 8]) + sum([4] * 4 + [8] * 4)
    assert flops.stream_len(kw) == 8192 and flops.held_rows_per_token(kw) == 1.0
    # A stream position a layer, forward, by hand: projections 2 x 2048 x 9216 = 37.7 M;
    # router 2 x 2048 x 128 = 0.5 M; experts 1.0 x 2 x 3 x 2048 x 768 = 9.4 M; scores and
    # values 4 x 32 x 128 a SEEN pair.
    a_position = 37_748_736 + 524_288 + 9_437_184
    attended = 4 * 32 * 128 * 16_793_600
    head = 4096 * 2 * 2048 * 18992
    forward = 6 * (8192 * a_position + attended) + head
    assert flops.forward_flops_per_sample(kw) == forward
    assert flops.train_flops_per_sample(kw) == 3 * forward
    assert abs(forward - 4.315e12) / 4.315e12 < 1e-3
    a_round = 8 * flops.train_flops_per_sample(kw)
    assert abs(a_round - 103.55e12) / 103.55e12 < 1e-3
    assert attended * 6 / forward == pytest.approx(0.383, abs=2e-3) and head / forward == pytest.approx(0.074, abs=2e-3)
    # The kernels' own: (1 forward run x 2 products + 5) x 2 x 128 a pair = 1792, 32 heads.
    assert flops.attention_kernel_flops_per_round(kw, REAL["federation"]) == (
        8 * 6 * 32 * 1792 * 16_793_600)


def test_param_count_matches_the_zoo_tree():
    from nanofed_tpu.models import get_model

    kw = REAL["model"]["kwargs"]
    a_layer = 18_874_368 + 262_144 + 4_352 + 16 * 4_718_592
    by_hand = 6 * a_layer + 2 * 18992 * 2048 + 2_048
    assert a_layer == 94_638_336
    assert _flops().param_count(kw) == by_hand == 645_623_296 == REAL["held"]["parameters"]
    assert REAL["published"]["parameters_a_whole_layer"] == a_layer + 112 * 4_718_592
    tree = jax.eval_shape(get_model("diffusion_moe_lm", **kw).init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == 645_623_296
    assert all(leaf.dtype == "float32" for leaf in jax.tree.leaves(tree))
    family = federation.load_named(REPO, "reference", REAL["family"])
    federation.build_model(REAL, family, 1)  # the reference's tree is the zoo's, leaf for leaf


def test_forward_kernel_executions_are_the_pallas_calls_of_a_training_step(kernel_calls):
    """Every layer under ``jax.checkpoint``, which keeps the forward kernel's output and
    log-sum-exp: counted in the jaxpr of one gradient step of the OBJECTIVE at 256 tokens
    (a doubled stream of 512 positions: the kernels engage), two layers: 2 forward calls,
    2 backward, all under the block-diffusion mask."""
    from nanofed_tpu.models import get_model

    model = get_model("diffusion_moe_lm", **{**TINY_KWARGS, "seq_len": 256})
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    objective = lambda p, x: model.apply.sample_nll(p, x, None, rng=jax.random.key(0))[0].sum()
    calls = kernel_calls(jax.grad(objective), params, tokens)
    layers = TINY_KWARGS["layers"]
    assert calls == {"causal_attention_fwd_blocks": _flops().FORWARD_KERNEL_EXECUTIONS * layers,
                     "causal_attention_bwd_blocks": layers}


def test_configuration_carries_the_published_keys_unchanged():
    row = next(json.loads(line) for line in _catalog()
               if json.loads(line)["name"] == "SDAR-30B-A3B-Chat")
    held = {"num_hidden_layers": 6, "num_experts": 16, "vocab_size": 18992}
    for key, value in row["config"].items():
        assert REAL[key] == held.get(key, value), key
    assert REAL["source"].startswith(row["source_url"])
    assert REAL["reduced"] == [*held, "local_steps_per_round"]
    assert {k: REAL["published"][k] for k in held} == {k: row["config"][k] for k in held}
    kw = REAL["model"]["kwargs"]
    assert (kw["width"], kw["attn_heads"], kw["kv_heads"], kw["head_dim"], kw["expert_width"],
            kw["top_k"], kw["experts"], kw["rope_theta"], kw["eps"], kw["layers"]) == (
        REAL["hidden_size"], REAL["num_attention_heads"], REAL["num_key_value_heads"],
        REAL["head_dim"], REAL["moe_intermediate_size"], REAL["num_experts_per_tok"],
        row["config"]["num_experts"], REAL["rope_theta"], REAL["rms_norm_eps"], 6) == (
        2048, 32, 4, 128, 768, 8, 128, 1000000, 1e-6, 6)
    assert (kw["experts_held"], kw["vocab"], kw["vocab"] * 8) == (16, 18992, 151936)
    assert (kw["seq_len"], kw["block"]) == (4096, 4)
    family = federation.load_named(REPO, "reference", REAL["family"])
    assert (family.mask_id(kw), family.NOISE_FLOOR) == (18991, 0.001)  # as ``assumed`` states them
    assert set(row["not_given"]) == {"block length", "noise schedule"}
    assert {"block_length", "noise_schedule", "noise_floor", "logit_shift", "mask_token", "stream",
            "apply", "qk_norm", "initialisation", "data", "learning_rate", "mixed_precision",
            "local_steps_per_round", "loss", "dropout", "correct"} <= set(REAL["assumed"])
    assert "8 chips share each layer" in REAL["deployment"]


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        return open(path).read().splitlines()
    except OSError:
        pytest.skip(f"the catalog is not at {path}")
