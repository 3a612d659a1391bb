"""The latent-attention / mixture-of-experts configuration through the benchmark: a tiny
copy of it through ``run_cell`` the way ``tiny-smallthinker.sync`` runs, its float8
control, the kernels' roofline share from a recorded ``device_ops`` list, the operation
and parameter counts against hand counts, the kernel-execution constant against the
``pallas_call``s of a training step, and the published keys against the catalog."""

import json
import shutil

import jax
import jax.numpy as jnp
import pytest

from benchlib import CPU_PEAKS, REPO, _merge

from benchmark import check, federation, run

NAME = "moonlight-16b-6l-xsilo-4"
REAL = json.loads((REPO / "benchmark" / "configs" / f"{NAME}.json").read_text())
TINY_KWARGS = {
    "vocab": 64, "seq_len": 32, "width": 64, "heads": 4, "latent_rank": 32, "nope_dim": 16,
    "rope_dim": 8, "value_dim": 16, "rope_theta": 50000, "dense_layers": 1, "dense_width": 160,
    "expert_layers": 2, "experts": 16, "first_expert": 0, "experts_held": 4, "top_k": 3,
    "expert_width": 24, "shared_width": 48, "routed_scale": 2.446, "eps": 1e-5,
}
TINY = {
    "name": "tiny-moonlight",
    "model": {"factory": "latent_moe_lm", "kwargs": TINY_KWARGS},
    "federation": {"num_clients": 4, "samples_per_client": 8, "batch_size": 4, "learning_rate": 0.02},
    "reference": {"rounds": 3, "block": 2},
    # Read at this size on the CPU (seeds 1-6): the bf16 program gives up to 1.1e-4 /
    # 0.0065 / 0.0062, the float8 control up to 0.0010 / 1.0 / 1.0 on every seed (the
    # loss hardly moves with precision; in float8 a leaf's gradient underflows to zero,
    # so its step's norm is all gap).
    "correct": {"loss_gap": 0.005, "first_step_gap": 0.02, "update_gap": 0.02},
}
CELL = "tiny-moonlight.sync"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """BENCHMARK.json and a copy of ``benchmark/`` with one file ADDED: the tiny
    configuration, run under the mix the real cell runs under."""
    root = tmp_path_factory.mktemp("moonlight_root")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmark" / "configs" / "tiny-moonlight.json").write_text(
        json.dumps(_merge(REAL, TINY)))
    manifest["configs"].append({"name": "tiny-moonlight", "source": REAL["source"],
                                "file": "benchmark/configs/tiny-moonlight.json",
                                "reduced": ["federation"], "why": "tiny, for the tests"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-moonlight",
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


def test_tiny_traced_run_reports_the_per_layer_metrics_it_can(tiny_root):
    result = _run(tiny_root, traced=True)
    assert result["correct"] is True
    assert {"mfu_pct", "host_gap_ms", "host_dispatch_ms", "device_wait_ms"} <= set(result["metrics"])
    # No device trace on the CPU, no kernel at 32 positions: the share is left out.
    assert "mla_kernel_roofline_pct" not in result["metrics"]


def _ctx(device_ops, rounds=3, config=REAL):
    return {"trace": {"device_ops": device_ops}, "traced_rounds": rounds, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12}, "config": config,
            "rounds": [(0.1, type("M", (), {"agg_metrics": {"loss": 1.0}})())]}


def test_the_new_reader_returns_nothing_where_there_is_nothing_to_read():
    """A trace with no kernel among the ten, a run not traced, a family that counts no
    kernel operations: nothing, and no raise.  It is the accepted reader's reading, on
    this family's count."""
    share = federation.load_named(REPO, "layer_metrics", "mla_kernel_roofline_pct")
    accepted = federation.load_named(REPO, "layer_metrics", "attn_kernel_roofline_pct")
    found = [["causal_attention_fwd.3_bf16_", 0.5]]
    assert share.read(_ctx([["fusion.1_bf16_8_", 0.5]])) is None
    assert share.read({**_ctx([]), "trace": None}) is None
    assert share.read(_ctx(found, rounds=0)) is None
    other = json.loads((REPO / "benchmark" / "configs" / "nemotron-twotower-ctx-9l-xsilo-4.json").read_text())
    assert share.read(_ctx(found, config=other)) is None
    assert share.read(_ctx(found)) == accepted.read(_ctx(found)) > 0


def test_the_kernels_share_from_a_recorded_list_counts_what_it_finds_and_never_reads_high():
    share = federation.load_named(REPO, "layer_metrics", "mla_kernel_roofline_pct")
    flops = _flops()
    kw, fed = REAL["model"]["kwargs"], REAL["federation"]
    one = lambda backward: 8 * flops.attention_kernel_flops(kw, backward=backward)
    # Every execution of the six layers, each exactly at the peak, over three traced
    # rounds: 100%, and the operations are the round's.  (The trace names an instruction
    # ``<kernel name>.<n>_<shape>``.)
    at_peak = lambda name, backward: [name, 3 * one(backward) / 197e12]
    # One forward and one backward instruction a layer: the checkpoints keep the forward's
    # output (PR 34).
    every = ([at_peak(f"causal_attention_fwd.{i}_bf16_1_16_8192_128_", False) for i in range(6)]
             + [at_peak(f"causal_attention_bwd.{i}_bf16_1_16_8192_192_", True) for i in range(6, 12)])
    assert share.read(_ctx(every)) == pytest.approx(100.0)
    total = sum(s for _, s in every) / 3 * 197e12
    assert total == pytest.approx(flops.attention_kernel_flops_per_round(kw, fed))
    # The ten longest hold the six backward executions and four forward: the others take
    # their time AND their operations with them, and the share stays.
    ten = every[6:] + every[:4]
    assert share.read(_ctx(ten)) == pytest.approx(100.0)
    assert share.read(_ctx([[n, 2 * s] for n, s in ten])) == pytest.approx(50.0)
    assert share.read(_ctx(ten + [["fusion.7_f32_", 1.0]])) == pytest.approx(100.0)


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


def _flops():
    return federation.load_named(REPO, "flops", REAL["family"])


def test_flops_match_a_hand_count():
    kw = REAL["model"]["kwargs"]
    flops = _flops()
    assert flops.attended_pairs(8192) == 33_558_528 and flops.attended_pairs(4) == 10
    assert flops.held_rows_per_token(kw) == 0.75
    # A token, forward, by hand: attention's projections 2 x (2048 x 3072 + 2048 x 576 + 512 x
    # 4096 + 2048 x 2048) = 27.5 M a layer; the dense MLP 2 x 3 x 2048 x 11264 = 138.4 M; an
    # expert layer's router 2 x 2048 x 64 = 0.26 M, shared experts 2 x 3 x 2048 x 2816 = 34.6
    # M, routed 0.75 x 2 x 3 x 2048 x 1408 = 13.0 M; scores and values 16 heads x (2 x 192 + 2
    # x 128) a pair.
    assert flops.projection_flops_per_token(kw) == 27_525_120
    a_token = 6 * 27_525_120 + 138_412_032 + 5 * (262_144 + 34_603_008 + 12_976_128)
    attended = 6 * 16 * 640 * 33_558_528
    assert flops.forward_flops_per_sample(kw) == 8192 * a_token + attended + 2 * 2048 * 20480
    assert abs(flops.forward_flops_per_sample(kw) / 8192 - 794.5e6) / 794.5e6 < 1e-3
    # 4 silos x 2 sequences, three times the forward pass: 156.2 TFLOP a round.
    a_round = 8 * flops.train_flops_per_sample(kw)
    assert abs(a_round - 156.2e12) / 156.2e12 < 1e-3
    # The kernels' own: 640 a pair forward, 2 x (3 x 192 + 2 x 128) = 1664 backward; one
    # forward run and one backward a layer.
    assert flops.attention_kernel_flops(kw, backward=False) == 16 * 640 * 33_558_528
    assert flops.attention_kernel_flops(kw, backward=True) == 16 * 1664 * 33_558_528
    assert flops.attention_kernel_flops_per_round(kw, REAL["federation"]) == (
        8 * 6 * 16 * (640 + 1664) * 33_558_528)


def test_param_count_matches_the_zoo_tree():
    from nanofed_tpu.models import get_model

    kw = REAL["model"]["kwargs"]
    attention = 6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304
    dense_layer = attention + 4_096 + 69_206_016
    expert_layer = attention + 4_096 + 131_072 + 64 + 17_301_504 + 8 * 8_650_752
    by_hand = dense_layer + 5 * expert_layer + 2 * 20480 * 2048 + 2_048
    assert (attention, dense_layer, expert_layer) == (13_763_072, 82_973_184, 100_405_824)
    assert _flops().param_count(kw) == by_hand == 668_890_432 == REAL["held"]["parameters"]
    whole = expert_layer + 56 * 8_650_752
    assert whole == REAL["published"]["parameters_a_whole_expert_layer"] == 584_847_936
    tree = jax.eval_shape(get_model("latent_moe_lm", **kw).init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == 668_890_432
    assert all(leaf.dtype == "float32" for leaf in jax.tree.leaves(tree))
    family = federation.load_named(REPO, "reference", REAL["family"])
    federation.build_model(REAL, family, 1)  # the reference's tree is the zoo's, leaf for leaf


def test_forward_kernel_executions_are_the_pallas_calls_of_a_training_step():
    """Every layer under ``jax.checkpoint``, which keeps the forward kernel's output and
    log-sum-exp (PR 34): the backward pass does not run it again.  Counted in the jaxpr of
    one gradient step at 512 positions (the kernels engage), three layers: 3 forward
    calls, 3 backward."""
    from nanofed_tpu.models import get_model

    model = get_model("latent_moe_lm", **{**TINY_KWARGS, "seq_len": 512})
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    calls = {}

    def count(jaxpr):
        """Every ``pallas_call`` by name, each equation as often as it stands: the two expert
        layers share one traced layer function, which the printed jaxpr shows once."""
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] = calls.get(eqn.params["name"], 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                count(sub)

    count(jax.make_jaxpr(jax.grad(lambda p, x: model.apply(p, x).sum()))(params, tokens).jaxpr)
    flops = _flops()
    assert flops.layers(TINY_KWARGS) == 3
    assert calls == {"causal_attention_bwd": 3,
                     "causal_attention_fwd": flops.FORWARD_KERNEL_EXECUTIONS * 3}


def test_configuration_carries_the_published_keys_unchanged():
    row = next(json.loads(line) for line in _catalog()
               if json.loads(line)["name"] == "Moonlight-16B-A3B")
    held = {"num_hidden_layers": 6, "n_routed_experts": 8, "vocab_size": 20480}
    for key, value in row["config"].items():
        assert REAL[key] == held.get(key, value), key
    assert REAL["source"].startswith(row["source_url"])
    assert REAL["reduced"] == [*held, "local_steps_per_round"]
    assert {k: REAL["published"][k] for k in held} == {k: row["config"][k] for k in held}
    kw = REAL["model"]["kwargs"]
    assert (kw["width"], kw["heads"], kw["latent_rank"], kw["nope_dim"], kw["rope_dim"],
            kw["value_dim"], kw["rope_theta"], kw["dense_width"], kw["experts"], kw["top_k"],
            kw["expert_width"], kw["shared_width"], kw["routed_scale"], kw["eps"], kw["seq_len"]) == (
        REAL["hidden_size"], REAL["num_attention_heads"], REAL["kv_lora_rank"],
        REAL["qk_nope_head_dim"], REAL["qk_rope_head_dim"], REAL["v_head_dim"], REAL["rope_theta"],
        REAL["intermediate_size"], row["config"]["n_routed_experts"], REAL["num_experts_per_tok"],
        REAL["moe_intermediate_size"], REAL["n_shared_experts"] * REAL["moe_intermediate_size"],
        REAL["routed_scaling_factor"], REAL["rms_norm_eps"], REAL["max_position_embeddings"])
    assert kw["dense_layers"] == REAL["first_k_dense_replace"] == 1
    assert kw["dense_layers"] + kw["expert_layers"] == REAL["num_hidden_layers"]
    assert kw["experts_held"] == REAL["n_routed_experts"] and kw["vocab"] == REAL["vocab_size"]
    assert "8 chips share each layer" in REAL["deployment"]
    assert "NOT BUILT" in REAL["assumed"]["bias_update_and_seq_aux"]
    assert {"rotary_pairing", "rope_scaling", "selection_bias", "bias_update_and_seq_aux",
            "fused_gate_up", "initialisation", "data", "learning_rate", "local_steps_per_round",
            "mixed_precision", "loss", "correct"} <= set(REAL["assumed"])


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        return open(path).read().splitlines()
    except OSError:
        pytest.skip(f"the catalog is not at {path}")
