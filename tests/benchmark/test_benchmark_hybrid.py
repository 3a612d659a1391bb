"""The hybrid state-space / mixture-of-experts configuration through the benchmark: a
tiny copy of it through ``run_cell`` the way ``tiny-lm.sync`` runs, its float8 control,
the operation count against a hand count, and the parameter count against the zoo."""

import importlib.util
import json
import shutil

import jax
import pytest

from benchlib import CPU_PEAKS, REPO, _merge

from benchmark import check, federation, run

NAME = "nemotron-twotower-ctx-9l-xsilo-4"
REAL = json.loads((REPO / "benchmark" / "configs" / f"{NAME}.json").read_text())
TINY_KWARGS = {
    "vocab": 64, "seq_len": 32, "width": 64, "pattern": "MEMEM*EME",
    "mamba_heads": 2, "mamba_head_dim": 16, "ssm_groups": 2, "ssm_state": 16,
    "conv_kernel": 4, "chunk": 8, "attn_heads": 4, "kv_heads": 2, "head_dim": 16,
    "experts": 16, "first_expert": 0, "experts_held": 4, "top_k": 3,
    "expert_width": 48, "shared_width": 96, "routed_scale": 2.5, "eps": 1e-5,
}
TINY = {
    "name": "tiny-hybrid",
    "model": {"factory": "hybrid_lm", "kwargs": TINY_KWARGS},
    "federation": {"num_clients": 4, "samples_per_client": 8, "batch_size": 4, "learning_rate": 0.02},
    "reference": {"rounds": 3, "block": 2},
    # Read at this size on the CPU (seeds 1-6): the bf16 program gives up to 0.0017 /
    # 0.042 / 0.087, the float8 control 0.0006-0.017 / 0.69-1.0 / 0.60-1.0.  The loss
    # hardly moves with precision; a router pick that flips between bfloat16 and float32
    # hidden states moves a whole token between experts (at 64 tokens a step one flip is
    # seen), which is what the two step gaps read.
    "correct": {"loss_gap": 0.005, "first_step_gap": 0.15, "update_gap": 0.25},
}


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    """BENCHMARK.json and a copy of ``benchmark/`` with one file ADDED: the tiny
    configuration, run under the mix the real cell runs under."""
    root = tmp_path_factory.mktemp("hybrid_root")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmark" / "configs" / "tiny-hybrid.json").write_text(json.dumps(_merge(REAL, TINY)))
    manifest["configs"].append({"name": "tiny-hybrid", "source": REAL["source"],
                                "file": "benchmark/configs/tiny-hybrid.json",
                                "reduced": ["federation"], "why": "tiny, for the tests"})
    manifest["workloads"].append({"name": "tiny-hybrid.sync", "config": "tiny-hybrid",
                                  "traffic": "sync", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"].startswith("moe_"):
            metric["workloads"].append("tiny-hybrid.sync")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def _run(root, traced, seed=3):
    return run.run_cell(root, "tiny-hybrid.sync", seed, 1.5, traced, jax.devices(), CPU_PEAKS)


def test_tiny_hybrid_cell_is_correct_and_reports_the_end_to_end_metrics(hybrid_root):
    result = _run(hybrid_root, traced=False)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"round_s", "client_samples_per_s", "setup_s"}


def test_tiny_hybrid_traced_run_reads_the_two_expert_counters(hybrid_root):
    result = _run(hybrid_root, traced=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    # 3 picks a token, 4 of 16 experts held: 0.75 rows a token under uniform routing.
    assert 0.4 < metrics["moe_held_rows_per_token"]["value"] < 1.2
    assert 1.0 <= metrics["moe_load_max_over_mean"]["value"] < 4.0
    assert {"mfu_pct", "host_gap_ms"} <= set(metrics)


def test_the_expert_counters_are_left_out_where_the_program_has_none():
    """A cell of a model with no expert layer (or of a program from before it) carries no
    such counter: the readers return nothing and do not raise."""
    ctx = {"rounds": [(0.1, type("M", (), {"agg_metrics": {"loss": 1.0}})())],
           "config": {"model": {"kwargs": TINY_KWARGS}}}
    for name in ("moe_held_rows_per_token", "moe_load_max_over_mean"):
        assert federation.load_named(REPO, "layer_metrics", name).read(ctx) is None


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_hybrid_control_in_float8_is_not_correct(hybrid_root, seed):
    _, _, config, _ = run.load_cell(hybrid_root, "tiny-hybrid.sync")
    family = federation.load_named(hybrid_root, "reference", config["family"])
    fedavg = federation.load_named(hybrid_root, "reference", "fedavg")
    model = federation.build_model(config, family, seed)
    data = federation.make_data(config, family, seed, model.input_shape, model.num_classes)
    rounds = config["reference"]["rounds"]
    ref = lambda q: check.reference_rounds(fedavg, family, config, data, seed, jax.devices()[0], rounds, q)
    exact, lower = ref(fedavg.identity), ref(fedavg.float8)
    want = check.norms(exact, exact["start"])
    rows = check.compare(check.norms(lower, exact["start"]), want, config["correct"])
    assert not all(r["ok"] for r in rows), rows


def _flops():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_flops", REPO / "benchmark" / "flops" / "nemotron_h.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_hybrid_flops_match_a_hand_count():
    kw = REAL["model"]["kwargs"]
    flops = _flops()
    # A token, forward, by hand: M 2 x (2688 x 10304 + 4096 x 2688 + 128 x 128 x 8
    # + 128 x 4096 + 2 x 4096 x 128) = 80.8 M; * 2 x (2688 x 8704 + 2 x 2048 x 4096) =
    # 80.3 M; E 2 x (2688 x 128 + 2 x 2688 x 3712 + 0.375 x 2 x 2688 x 1856) = 48.1 M.
    per_token = 4 * 80_822_272 + 80_347_136 + 4 * 48_082_944
    assert flops.forward_flops_per_token(kw) == per_token == 595_968_000
    assert flops.held_rows_per_token(kw) == 0.375
    # 4 silos x 4 sequences of 2048 tokens, three times the forward pass: 58.6 TFLOP a round.
    a_round = 16 * flops.train_flops_per_sample(kw)
    assert abs(a_round - 58.6e12) / 58.6e12 < 1e-3
    assert flops.forward_flops_per_sample(kw) == 2048 * per_token + 2 * 2688 * 16384


def test_hybrid_param_count_matches_the_zoo_tree():
    from nanofed_tpu.models import get_model

    kw = REAL["model"]["kwargs"]
    by_hand = 4 * 38_744_896 + 23_399_040 + 4 * 100_125_312 + 2 * 44_040_192 + 2_688
    assert _flops().param_count(kw) == by_hand == 666_962_944 == REAL["held"]["parameters"]
    tree = jax.eval_shape(get_model("hybrid_lm", **kw).init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == 666_962_944
    assert all(leaf.dtype == "float32" for leaf in jax.tree.leaves(tree))


def test_hybrid_configuration_carries_the_published_keys_unchanged():
    row = next(json.loads(line) for line in _catalog() if json.loads(line)["name"].startswith("Nemotron-Labs-TwoTower"))
    held = {"num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 16384}
    for key, value in row["config"].items():
        assert REAL[key] == held.get(key, value), key
    assert REAL["reduced"] == [*held, "local_steps_per_round"]
    assert {k: REAL["published"][k] for k in held} == {k: row["config"][k] for k in held}
    assert {"denoising_tower", "rope_theta", "partial_rotary_factor", "b_corr", "loss",
            "initialisation"} <= set(REAL["assumed"])


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        return open(path).read().splitlines()
    except OSError:
        pytest.skip(f"the catalog is not at {path}")
