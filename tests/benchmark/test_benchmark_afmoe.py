"""The gated, sandwich-normed window/full mixture-of-experts configuration through the
benchmark: a tiny copy of it through ``run_cell`` the way ``tiny-moonlight.sync`` runs,
its float8 control, the accepted kernels' share on this family's count from a recorded
``device_ops`` list, the new per-scope metric's file, the operation and parameter counts
against hand counts, the kernel-execution constant against the ``pallas_call``s of a
training step, and the published keys against the catalog."""

import json
import shutil

import jax
import jax.numpy as jnp
import pytest

from benchlib import CPU_PEAKS, REPO, _merge

from benchmark import check, federation, run

NAME = "trinity-mini-26b-5l-xsilo-4"
REAL = json.loads((REPO / "benchmark" / "configs" / f"{NAME}.json").read_text())
TINY_KWARGS = {
    "vocab": 64, "seq_len": 32, "width": 64, "sliding_layout": [1, 1, 0, 1], "window": 8,
    "rope_theta": 10000, "attn_heads": 4, "kv_heads": 2, "head_dim": 16, "dense_layers": 1,
    "dense_width": 160, "experts": 16, "first_expert": 0, "experts_held": 4, "top_k": 3,
    "expert_width": 24, "shared_width": 24, "routed_scale": 2.826, "eps": 1e-5,
}
TINY = {
    "name": "tiny-trinity",
    "model": {"factory": "gated_moe_lm", "kwargs": TINY_KWARGS},
    "federation": {"num_clients": 4, "samples_per_client": 8, "batch_size": 4, "learning_rate": 0.02},
    "reference": {"rounds": 3, "block": 2},
    # Read at this size on the CPU (seeds 1-8): the bf16 program gives up to 1.3e-4 /
    # 0.0161 / 0.0064, the float8 control 8e-5 to 2.9e-3 / 0.455 to 0.663 / 0.530 to 0.673
    # (the loss hardly moves with precision: the step gaps are what fails the control).
    "correct": {"loss_gap": 0.005, "first_step_gap": 0.06, "update_gap": 0.06},
}
CELL = "tiny-trinity.sync"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """BENCHMARK.json and a copy of ``benchmark/`` with one file ADDED: the tiny
    configuration, run under the mix the real cell runs under."""
    root = tmp_path_factory.mktemp("trinity_root")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmark" / "configs" / "tiny-trinity.json").write_text(json.dumps(_merge(REAL, TINY)))
    manifest["configs"].append({"name": "tiny-trinity", "source": REAL["source"],
                                "file": "benchmark/configs/tiny-trinity.json",
                                "reduced": ["federation"], "why": "tiny, for the tests"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-trinity",
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


def test_tiny_traced_run_reads_the_experts_counters(tiny_root):
    """Through the default ``Coordinator``: the experts' three counters reach
    ``RoundMetrics.agg_metrics`` and the accepted readers find them."""
    result = _run(tiny_root, traced=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    assert 0.4 < metrics["moe_held_rows_per_token"]["value"] < 1.2  # 3 picks, 4 of 16 held
    assert 0.2 < metrics["moe_block_fill_pct"]["value"] < 5.0
    assert {"mfu_pct", "host_gap_ms", "moe_load_max_over_mean"} <= set(metrics)
    # No device trace on the CPU, no kernel at 32 positions: the share and the scopes are left out.
    assert not {"attn_kernel_roofline_pct", "attention_gate_ms_per_round",
                "attention_ms_per_round"} & set(metrics)


def _ctx(device_ops, rounds=3):
    return {"trace": {"device_ops": device_ops}, "traced_rounds": rounds, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12}, "config": REAL,
            "rounds": [(0.1, type("M", (), {"agg_metrics": {"loss": 1.0}})())]}


def test_the_gates_metric_is_a_data_file_that_reads_nothing_where_there_is_nothing():
    """A program from before the scope (the parent), a run not traced: nothing, no raise."""
    specs, names = federation.scope_metrics(REPO)
    spec = specs["attention_gate_ms_per_round"]
    assert spec["scopes"] == ["attention_gate"] and "attention_gate" in names
    assert run.scope_ms_per_round({"scopes": None, "traced_rounds": 0}, spec) is None
    assert run.scope_ms_per_round({"scopes": [[["local_fit", "attention_proj"], "forward", 1.0]],
                                   "traced_rounds": 3}, spec) is None
    rows = [[["local_fit", "layer_scan", "attention_gate"], kind, 0.03]
            for kind in ("forward", "recomputed", "backward")]
    assert run.scope_ms_per_round({"scopes": rows, "traced_rounds": 3}, spec) == pytest.approx(30.0)


def test_the_accepted_kernels_share_counts_each_kernel_by_its_kind():
    """The accepted reader on this family's count: a sliding layer's kernels
    (``..._window``) need the window's pairs, the full layer's every causal pair; five
    forward and five backward executions a round, four of each under the window."""
    share = federation.load_named(REPO, "layer_metrics", "attn_kernel_roofline_pct")
    flops = _flops()
    kw, fed = REAL["model"]["kwargs"], REAL["federation"]
    one = lambda backward, windowed: 8 * flops.attention_kernel_flops(kw, backward=backward, windowed=windowed)
    at_peak = lambda name, backward, windowed: [name, 3 * one(backward, windowed) / 197e12]
    every = ([at_peak(f"causal_attention_fwd_window.{i}_bf16_", False, True) for i in range(4)]
             + [at_peak("causal_attention_fwd.4_bf16_", False, False)]
             + [at_peak(f"causal_attention_bwd_window.{i}_bf16_", True, True) for i in range(5, 9)]
             + [at_peak("causal_attention_bwd.9_bf16_", True, False)])
    assert share.read(_ctx(every)) == pytest.approx(100.0)
    total = sum(s for _, s in every) / 3 * 197e12
    assert total == pytest.approx(flops.attention_kernel_flops_per_round(kw, fed))
    # Executions that fell off the ten take their time AND their operations with them.
    assert share.read(_ctx(every[:2] + every[4:])) == pytest.approx(100.0)
    # A windowed kernel at the peak on every pair of the BLOCKS it visits (5 key blocks of
    # 512 a query block at most: 70 of the 136 causal block pairs) reads the needed share.
    needed = flops.attended_pairs(8192, 2048) / (70 * 512 * 512)
    assert needed == pytest.approx(0.80, abs=0.001)
    assert share.read(_ctx([[n, s / needed] for n, s in every[:4]])) == pytest.approx(100 * needed)
    assert share.read(_ctx([["fusion.1_bf16_8_", 0.5]])) is None


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_float8_is_not_correct(tiny_root, seed):
    _, _, config, _ = run.load_cell(tiny_root, CELL)
    family = federation.load_named(tiny_root, "reference", config["family"])
    fedavg = federation.load_named(tiny_root, "reference", "fedavg")
    model = federation.build_model(config, family, seed)
    data = federation.make_data(config, family, seed, model.input_shape, model.num_classes)
    rounds = config["reference"]["rounds"]
    ref = lambda q: check.reference_rounds(fedavg, family, config, data, seed, jax.devices()[0], rounds, q)
    exact, lower = ref(fedavg.identity), ref(fedavg.float8)
    want = check.norms(exact, exact["start"])
    rows = check.compare(check.norms(lower, exact["start"]), want, config["correct"])
    assert not all(r["ok"] for r in rows), rows
    # The selection bias never moves, on either side: a step of exactly zero.
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(federation.make_weights(config, family, seed))]
    still = [i for i, name in enumerate(names) if "router_bias" in name]
    assert len(still) == 1 and want["update"][still[0]] == 0


def _flops():
    return federation.load_named(REPO, "flops", REAL["family"])


def test_flops_match_a_hand_count():
    kw = REAL["model"]["kwargs"]
    flops = _flops()
    # Pairs a sequence and head: causal 8192 x 8193 / 2; under the window 2048 x 2049 / 2
    # + 6144 x 2048: 43.75% of them.
    assert flops.attended_pairs(8192) == 33_558_528
    assert flops.attended_pairs(8192, 2048) == 14_681_088
    assert flops.attended_pairs(8192, 8192) == flops.attended_pairs(8192, 9000) == 33_558_528
    assert flops.attended_pairs(4, 2) == 1 + 2 + 2 + 2
    assert flops.attended_pairs(8192, 2048) / flops.attended_pairs(8192) == pytest.approx(0.4375, abs=1e-4)
    assert flops.held_rows_per_token(kw) == 0.5
    # A token, forward, by hand.  A layer's projections: q, gate, o 2 x 2048 x 4096 each,
    # k, v 2 x 2048 x 512 each = 54.5 M, of which the gate's 16.8 M.  Scores and values
    # 4 x 32 x 128 a pair.  The dense MLP 2 x 3 x 2048 x 6144 = 75.5 M; the shared expert
    # 2 x 3 x 2048 x 1024 = 12.6 M; routed 0.5 rows of that; the router 2 x 2048 x 128.
    assert flops.projection_flops_per_token(kw) == 3 * 16_777_216 + 2 * 2_097_152 == 54_525_952
    parts = flops.by_part(kw)
    assert parts["gate"] == 8192 * 5 * 16_777_216
    assert parts["projections"] + parts["gate"] == 8192 * 5 * 54_525_952
    assert parts["attention"] == 4 * 32 * 128 * (4 * 14_681_088 + 33_558_528)
    assert parts["dense_mlp"] == 8192 * 75_497_472
    assert parts["shared"] == 8192 * 4 * 12_582_912 and parts["routed"] == parts["shared"] / 2
    assert parts["router"] == 8192 * 4 * 524_288
    forward = sum(parts.values()) + 2 * 2048 * 25024
    assert flops.forward_flops_per_sample(kw) == forward
    assert forward / 8192 == pytest.approx(610.3e6, rel=1e-3)
    assert flops.train_flops_per_sample(kw) == 3 * forward
    assert 8 * flops.train_flops_per_sample(kw) == pytest.approx(120.0e12, rel=1e-3)
    # The attention block (projections, gate, kernels) is 75% of the operations, the gate 14%.
    block = parts["projections"] + parts["gate"] + parts["attention"]
    assert block / forward == pytest.approx(0.75, abs=0.005)
    assert parts["gate"] / forward == pytest.approx(0.137, abs=0.002)
    # The kernels' own: (1 forward run x 2 products + 5) x 2 x 128 a pair = 1792, 32 heads.
    assert flops.attention_kernel_flops_per_round(kw, REAL["federation"]) == (
        8 * 32 * 1792 * (4 * 14_681_088 + 33_558_528))


def test_param_count_matches_the_zoo_tree():
    from nanofed_tpu.models import get_model

    kw = REAL["model"]["kwargs"]
    attention = 3 * 8_388_608 + 2 * 1_048_576 + 8_448  # q, gate, o; k, v; four norms and two per-head
    dense = attention + 37_748_736
    expert = attention + 262_144 + 128 + 6_291_456 + 8 * 6_291_456
    by_hand = dense + 4 * expert + 2 * 25024 * 2048 + 2_048
    assert (attention, dense, expert) == (27_271_424, 65_020_160, 84_156_800)
    assert _flops().param_count(kw) == by_hand == 504_147_712 == REAL["held"]["parameters"]
    assert REAL["published"]["parameters_a_whole_expert_layer"] == expert + 120 * 6_291_456
    tree = jax.eval_shape(get_model("gated_moe_lm", **kw).init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == 504_147_712
    assert all(leaf.dtype == "float32" for leaf in jax.tree.leaves(tree))
    family = federation.load_named(REPO, "reference", REAL["family"])
    federation.build_model(REAL, family, 1)  # the reference's tree is the zoo's, leaf for leaf


def test_forward_kernel_executions_are_the_pallas_calls_of_a_training_step(kernel_calls):
    """Every layer under ``jax.checkpoint``, which keeps the forward kernel's output and
    log-sum-exp: counted in the jaxpr of one gradient step at 512 positions (the kernels
    engage, a window of 200 binds), three sliding layers and a full one."""
    from nanofed_tpu.models import get_model

    model = get_model("gated_moe_lm", **{**TINY_KWARGS, "seq_len": 512, "window": 200})
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    calls = kernel_calls(jax.grad(lambda p, x: model.apply(p, x).sum()), params, tokens)
    runs = _flops().FORWARD_KERNEL_EXECUTIONS
    assert calls == {"causal_attention_fwd_window": 3 * runs, "causal_attention_fwd": runs,
                     "causal_attention_bwd_window": 3, "causal_attention_bwd": 1}


def test_configuration_carries_the_published_keys_unchanged():
    row = next(json.loads(line) for line in _catalog()
               if json.loads(line)["name"] == "Trinity-Mini")
    held = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8, "vocab_size": 25024}
    for key, value in row["config"].items():
        assert REAL[key] == held.get(key, value), key
    assert REAL["source"].startswith(row["source_url"])
    assert REAL["reduced"] == [*held, "local_steps_per_round"]
    assert {k: REAL["published"][k] for k in held} == {k: row["config"][k] for k in held}
    kw = REAL["model"]["kwargs"]
    assert (kw["width"], kw["attn_heads"], kw["kv_heads"], kw["head_dim"], kw["dense_width"],
            kw["expert_width"], kw["top_k"], kw["experts"], kw["rope_theta"], kw["eps"],
            kw["window"], kw["routed_scale"]) == (
        REAL["hidden_size"], REAL["num_attention_heads"], REAL["num_key_value_heads"],
        REAL["head_dim"], REAL["intermediate_size"], REAL["moe_intermediate_size"],
        REAL["num_experts_per_tok"], row["config"]["num_experts"], REAL["rope_theta"],
        REAL["rms_norm_eps"], REAL["sliding_window"], REAL["route_scale"])
    assert kw["shared_width"] == REAL["num_shared_experts"] * REAL["moe_intermediate_size"]
    # Published layers 1-5: the second dense layer and one period of the expert layers.
    kinds = row["config"]["layer_types"][1:6]
    assert kw["sliding_layout"] == [int(kind == "sliding_attention") for kind in kinds] == [1, 1, 0, 1, 1]
    assert kw["dense_layers"] == 1 and row["config"]["num_dense_layers"] == 2
    assert (kw["experts_held"], kw["vocab"], kw["vocab"] * 8) == (8, 25024, 200192)
    assert REAL["score_func"] == "sigmoid" and REAL["route_norm"] and REAL["mup_enabled"]
    assert {"output_gate", "qk_norm", "positions", "sandwich_norms", "embedding_scale",
            "balancing_update", "selection_bias", "initialisation", "data", "learning_rate",
            "mixed_precision", "local_steps_per_round", "loss", "correct"} <= set(REAL["assumed"])


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        return open(path).read().splitlines()
    except OSError:
        pytest.skip(f"the catalog is not at {path}")
