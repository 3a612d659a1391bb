"""The indexed-attention mixture-of-experts configuration through the benchmark: a tiny
copy of it through ``run_cell`` the way ``tiny-smallthinker.sync`` runs, its float8
control, the new readers on recorded contexts, the operation and parameter counts
against hand counts, the kernel-execution constant against the ``pallas_call``s of a
training step, and the published keys against the catalog."""

import json
import shutil

import jax
import jax.numpy as jnp
import pytest

from benchlib import CPU_PEAKS, REPO, _merge

from benchmark import check, federation, run

NAME = "keye-vl2-30b-6l-xsilo-4"
REAL = json.loads((REPO / "benchmark" / "configs" / f"{NAME}.json").read_text())
TINY_KWARGS = {
    "vocab": 64, "seq_len": 32, "width": 64, "layers": 2, "attn_heads": 4, "kv_heads": 2,
    "head_dim": 16, "rope_theta": 10000000, "rope_sections": [2, 3, 3], "index_heads": 4,
    "index_dim": 8, "index_topk": 8, "experts": 16, "first_expert": 0, "experts_held": 4,
    "top_k": 3, "expert_width": 48, "eps": 1e-6,
}
TINY = {
    "name": "tiny-keye",
    "model": {"factory": "indexed_moe_lm", "kwargs": TINY_KWARGS},
    "federation": {"num_clients": 4, "samples_per_client": 8, "batch_size": 4, "learning_rate": 0.02},
    "reference": {"rounds": 3, "block": 2},
    # Read at this size on the CPU (seeds 1-8): the bf16 program gives up to 1.1e-4 /
    # 0.0184 / 0.0181 (a pick that flips between bfloat16 and float32 indexer inputs is an
    # eighth of a query's attention here), the float8 control 2e-5 to 1.0e-3 / 1.0 / 1.0
    # on every seed (the loss hardly moves with precision; in float8 a leaf's gradient
    # underflows to zero, so its step's norm is all gap).
    "correct": {"loss_gap": 0.005, "first_step_gap": 0.06, "update_gap": 0.06},
}
CELL = "tiny-keye.sync"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """BENCHMARK.json and a copy of ``benchmark/`` with one file ADDED: the tiny
    configuration, run under the mix the real cell runs under."""
    root = tmp_path_factory.mktemp("keye_root")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmark" / "configs" / "tiny-keye.json").write_text(json.dumps(_merge(REAL, TINY)))
    manifest["configs"].append({"name": "tiny-keye", "source": REAL["source"],
                                "file": "benchmark/configs/tiny-keye.json",
                                "reduced": ["federation"], "why": "tiny, for the tests"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-keye",
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


def test_tiny_traced_run_reads_the_picks_counters(tiny_root):
    """Through the default ``Coordinator``: the pick's two counters reach
    ``RoundMetrics.agg_metrics`` beside the experts' three, and the readers find them."""
    result = _run(tiny_root, traced=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    # 32 positions of 8 keys: 8 x 57 / (32 x 33) of the causal pairs, in every layer and step.
    assert metrics["sparse_kept_pair_pct"]["value"] == pytest.approx(100 * 8 * 57 / (32 * 33), rel=1e-5)
    assert metrics["sparse_live_block_pct"]["value"] == pytest.approx(100.0)  # one band: one tile
    assert 0.5 < metrics["moe_block_fill_pct"]["value"] < 5.0
    assert 0.4 < metrics["moe_held_rows_per_token"]["value"] < 1.2  # 3 picks, 4 of 16 held
    assert {"mfu_pct", "host_gap_ms", "moe_load_max_over_mean"} <= set(metrics)
    # No device trace on the CPU, no kernel at 32 positions: the shares and scopes are left out.
    assert not {"indexed_kernel_roofline_pct", "indexer_ms_per_round",
                "indexed_attention_ms_per_round"} & set(metrics)


def _ctx(device_ops, rounds=3, counters=None):
    return {"trace": {"device_ops": device_ops}, "traced_rounds": rounds, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12}, "config": REAL,
            "rounds": [(0.1, type("M", (), {"agg_metrics": {"loss": 1.0, **(counters or {})}})())]}


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program from before the counters (the parent), a trace with no kernel among the
    ten, a run not traced: nothing, and no raise."""
    kept = federation.load_named(REPO, "layer_metrics", "sparse_kept_pair_pct")
    live = federation.load_named(REPO, "layer_metrics", "sparse_live_block_pct")
    share = federation.load_named(REPO, "layer_metrics", "indexed_kernel_roofline_pct")
    assert kept.read(_ctx([])) is None and live.read(_ctx([])) is None
    assert share.read(_ctx([["fusion.1_bf16_8_", 0.5]])) is None
    assert share.read({**_ctx([]), "trace": None}) is None
    seen = _ctx([], counters={"sparse_kept_pair_share": 0.4375, "sparse_live_block_share": 0.97})
    assert kept.read(seen) == pytest.approx(43.75) and live.read(seen) == pytest.approx(97.0)
    for metric in ("indexer_ms_per_round", "indexed_attention_ms_per_round"):
        spec = federation.scope_metrics(REPO)[0][metric]
        assert run.scope_ms_per_round({"scopes": None, "traced_rounds": 0}, spec) is None
        assert run.scope_ms_per_round({"scopes": [[["local_fit"], "forward", 1.0]],
                                       "traced_rounds": 3}, spec) is None


def test_the_kernels_share_counts_the_kept_pairs_and_never_reads_high():
    share = federation.load_named(REPO, "layer_metrics", "indexed_kernel_roofline_pct")
    flops = _flops()
    kw, fed = REAL["model"]["kwargs"], REAL["federation"]
    one = lambda backward: 8 * flops.attention_kernel_flops(kw, backward=backward)
    at_peak = lambda name, backward: [name, 3 * one(backward) / 197e12]
    # The six layers' kernels, each exactly at the peak on the kept pairs: 100%.
    every = ([at_peak(f"causal_attention_fwd_keep.{i}_bf16_", False) for i in range(6)]
             + [at_peak(f"causal_attention_bwd_keep.{i}_bf16_", True) for i in range(6, 12)])
    assert share.read(_ctx(every)) == pytest.approx(100.0)
    total = sum(s for _, s in every) / 3 * 197e12
    assert total == pytest.approx(flops.attention_kernel_flops_per_round(kw, fed))
    # Executions that fell off the ten take their time AND their operations with them.
    assert share.read(_ctx(every[2:])) == pytest.approx(100.0)
    # Kernels at the peak on EVERY causal pair, as masking kernels are at their best,
    # read the kept share of it: 43.75%.
    visited = flops.causal_pairs(8192) / flops.kept_pairs(8192, 2048)
    assert share.read(_ctx([[n, visited * s] for n, s in every])) == pytest.approx(43.75, rel=1e-3)


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
    # The indexer's three leaves never move, on either side: a step of exactly zero.
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(federation.make_weights(config, family, seed))]
    still = [i for i, name in enumerate(names) if "index_" in name]
    assert len(still) == 3 and all(want["update"][i] == 0 for i in still)


def _flops():
    return federation.load_named(REPO, "flops", REAL["family"])


def test_flops_match_a_hand_count():
    kw = REAL["model"]["kwargs"]
    flops = _flops()
    # Pairs a sequence: causal 8192 x 8193 / 2; kept 2048 x 2049 / 2 + 6144 x 2048: 43.75%.
    assert flops.causal_pairs(8192) == 33_558_528
    assert flops.kept_pairs(8192, 2048) == 14_681_088
    assert flops.kept_pairs(8192, 8192) == flops.kept_pairs(8192, 9000) == 33_558_528
    assert flops.kept_pairs(4, 2) == 1 + 2 + 2 + 2
    assert flops.kept_pairs(8192, 2048) / flops.causal_pairs(8192) == pytest.approx(0.4375, abs=1e-4)
    assert flops.held_rows_per_token(kw) == 1.0
    # A token a layer, forward, by hand: projections 2 x 2048 x 9216 = 37.7 M; router
    # 2 x 2048 x 128 = 0.5 M; experts 1.0 x 2 x 3 x 2048 x 768 = 9.4 M; the indexer's
    # projections 2 x 2048 x 1104 = 4.5 M and its scores 2 x 16 x 64 a causal pair;
    # scores and values 4 x 32 x 128 a KEPT pair.
    a_token = 37_748_736 + 524_288 + 9_437_184
    indexer = 8192 * 4_521_984 + 2048 * 33_558_528
    attended = 4 * 32 * 128 * 14_681_088
    assert flops.indexer_flops_per_sample(kw) == indexer
    forward = 6 * (8192 * a_token + indexer + attended) + 2 * 2048 * 18992
    assert flops.forward_flops_per_sample(kw) == forward
    # Three times the forward pass, the indexer (which has no backward) once.
    assert flops.train_flops_per_sample(kw) == 3 * forward - 2 * 6 * indexer
    a_round = 8 * flops.train_flops_per_sample(kw)
    assert abs(a_round - 96.0e12) / 96.0e12 < 1e-3
    # The kernels' own: (1 forward run x 2 products + 5) x 2 x 128 a pair = 1792, 32 heads.
    assert flops.attention_kernel_flops_per_round(kw, REAL["federation"]) == (
        8 * 6 * 32 * 1792 * 14_681_088)


def test_param_count_matches_the_zoo_tree():
    from nanofed_tpu.models import get_model

    kw = REAL["model"]["kwargs"]
    a_layer = 18_874_368 + 2_260_992 + 262_144 + 4_352 + 16 * 4_718_592
    by_hand = 6 * a_layer + 2 * 18992 * 2048 + 2_048
    assert a_layer == 96_899_328
    assert _flops().param_count(kw) == by_hand == 659_189_248 == REAL["held"]["parameters"]
    assert REAL["published"]["parameters_a_whole_layer"] == a_layer + 112 * 4_718_592
    tree = jax.eval_shape(get_model("indexed_moe_lm", **kw).init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == 659_189_248
    assert all(leaf.dtype == "float32" for leaf in jax.tree.leaves(tree))
    family = federation.load_named(REPO, "reference", REAL["family"])
    federation.build_model(REAL, family, 1)  # the reference's tree is the zoo's, leaf for leaf


def test_forward_kernel_executions_are_the_pallas_calls_of_a_training_step(kernel_calls):
    """Every layer under ``jax.checkpoint``, which keeps the forward kernel's output and
    log-sum-exp and the pick: counted in the jaxpr of one gradient step at 512 positions
    (the kernels engage, a pick of 96 keys binds), two layers: 2 forward calls, 2 backward,
    all under the mask."""
    from nanofed_tpu.models import get_model

    model = get_model("indexed_moe_lm", **{**TINY_KWARGS, "seq_len": 512, "index_topk": 96})
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    calls = kernel_calls(jax.grad(lambda p, x: model.apply(p, x).sum()), params, tokens)
    layers = TINY_KWARGS["layers"]
    assert calls == {"causal_attention_fwd_keep": _flops().FORWARD_KERNEL_EXECUTIONS * layers,
                     "causal_attention_bwd_keep": layers}


def test_configuration_carries_the_published_keys_unchanged():
    row = next(json.loads(line) for line in _catalog()
               if json.loads(line)["name"] == "Keye-VL-2.0-30B-A3B")
    held = {"num_hidden_layers": 6, "num_experts": 16, "vocab_size": 18992}
    for key, value in row["config"].items():
        assert REAL[key] == held.get(key, value), key
    assert REAL["source"].startswith(row["source_url"])
    assert REAL["reduced"] == [*held, "local_steps_per_round"]
    assert {k: REAL["published"][k] for k in held} == {k: row["config"][k] for k in held}
    kw, sparse = REAL["model"]["kwargs"], REAL["sa_config"]
    assert (kw["width"], kw["attn_heads"], kw["kv_heads"], kw["head_dim"], kw["expert_width"],
            kw["top_k"], kw["experts"], kw["rope_theta"], kw["eps"], kw["layers"]) == (
        REAL["hidden_size"], REAL["num_attention_heads"], REAL["num_key_value_heads"],
        REAL["head_dim"], REAL["moe_intermediate_size"], REAL["num_experts_per_tok"],
        row["config"]["num_experts"], REAL["rope_theta"], REAL["rms_norm_eps"], 6)
    assert (kw["index_heads"], kw["index_dim"], kw["index_topk"]) == (
        sparse["indexer_num_heads"], sparse["indexer_head_dim"], sparse["topk"]) == (16, 64, 2048)
    assert sparse["indexer_num_kv_heads"] == 1  # ONE indexer key head: index_wk is [d, 64]
    assert kw["rope_sections"] == REAL["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert (kw["experts_held"], kw["vocab"], kw["vocab"] * 8) == (16, 18992, 151936)
    assert {"qk_norm", "chunk_sizes", "indexer", "tie_rule", "indexer_training", "positions",
            "vision_tower", "initialisation", "data", "learning_rate", "mixed_precision",
            "local_steps_per_round", "loss", "correct"} <= set(REAL["assumed"])


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        return open(path).read().splitlines()
    except OSError:
        pytest.skip(f"the catalog is not at {path}")
