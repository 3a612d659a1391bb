"""The windowed / full mixture-of-experts configuration through the benchmark: a tiny copy
of it through ``run_cell`` the way ``tiny-hybrid.sync`` runs, its float8 control, the
operation and parameter counts against hand counts, the kernel-execution constant against
the ``pallas_call``s of a training step, and the published keys against the catalog."""

import json
import shutil

import jax
import jax.numpy as jnp
import pytest

from benchlib import CPU_PEAKS, REPO, _merge

from benchmark import check, federation, run

NAME = "smallthinker-21b-4l-xsilo-4"
REAL = json.loads((REPO / "benchmark" / "configs" / f"{NAME}.json").read_text())
TINY_KWARGS = {
    "vocab": 64, "seq_len": 32, "width": 64, "rope_layout": [0, 1, 1, 1],
    "window_layout": [0, 1, 1, 1], "window": 8, "rope_theta": 1500000,
    "attn_heads": 4, "kv_heads": 2, "head_dim": 16, "experts": 16, "first_expert": 0,
    "experts_held": 4, "top_k": 3, "expert_width": 48, "eps": 1e-6,
}
TINY = {
    "name": "tiny-smallthinker",
    "model": {"factory": "moe_decoder_lm", "kwargs": TINY_KWARGS},
    "federation": {"num_clients": 4, "samples_per_client": 8, "batch_size": 4, "learning_rate": 0.02},
    "reference": {"rounds": 3, "block": 2},
    # Read at this size on the CPU (seeds 1-6): the bf16 program gives up to 6e-5 /
    # 0.0050 / 0.0050, the float8 control up to 0.0012 / 1.0 / 1.0 on every seed (the
    # loss hardly moves with precision; in float8 a leaf's gradient underflows to zero,
    # so its step's norm is all gap).
    "correct": {"loss_gap": 0.005, "first_step_gap": 0.02, "update_gap": 0.02},
}
CELL = "tiny-smallthinker.sync"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """BENCHMARK.json and a copy of ``benchmark/`` with one file ADDED: the tiny
    configuration, run under the mix the real cell runs under."""
    root = tmp_path_factory.mktemp("smallthinker_root")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "benchmark" / "configs" / "tiny-smallthinker.json").write_text(
        json.dumps(_merge(REAL, TINY)))
    manifest["configs"].append({"name": "tiny-smallthinker", "source": REAL["source"],
                                "file": "benchmark/configs/tiny-smallthinker.json",
                                "reduced": ["federation"], "why": "tiny, for the tests"})
    manifest["workloads"].append({"name": CELL, "config": "tiny-smallthinker",
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


def test_tiny_traced_run_reads_the_block_fill(tiny_root):
    result = _run(tiny_root, traced=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    # 128 tokens x 3 picks, 4 of 16 held: ~24 rows an expert in a block of 1024.
    assert 0.5 < metrics["moe_block_fill_pct"]["value"] < 5.0
    assert {"mfu_pct", "host_gap_ms"} <= set(metrics)
    # No device trace on the CPU, no kernel at 32 positions: the share is left out.
    assert "attn_kernel_roofline_pct" not in metrics


def _ctx(device_ops, rounds=3):
    return {"trace": {"device_ops": device_ops}, "traced_rounds": rounds, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12}, "config": REAL,
            "rounds": [(0.1, type("M", (), {"agg_metrics": {"loss": 1.0}})())]}


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program from before the counter, a trace with no kernel among the ten, a run not
    traced, a family that counts no kernel operations: nothing, and no raise."""
    fill = federation.load_named(REPO, "layer_metrics", "moe_block_fill_pct")
    share = federation.load_named(REPO, "layer_metrics", "attn_kernel_roofline_pct")
    assert fill.read(_ctx([])) is None
    assert share.read(_ctx([["fusion.1_bf16_8_", 0.5]])) is None
    assert share.read({**_ctx([]), "trace": None}) is None
    hybrid = json.loads((REPO / "benchmark" / "configs" / "nemotron-twotower-ctx-9l-xsilo-4.json").read_text())
    assert share.read({**_ctx([["causal_attention_fwd.3_bf16_", 0.5]]), "config": hybrid}) is None


def test_the_kernels_share_counts_what_it_finds_and_never_reads_high():
    share = federation.load_named(REPO, "layer_metrics", "attn_kernel_roofline_pct")
    flops = _flops()
    kw, fed = REAL["model"]["kwargs"], REAL["federation"]
    one = lambda backward, windowed: 8 * flops.attention_kernel_flops(kw, backward=backward, windowed=windowed)
    # Every execution among the ten, each exactly at the peak: 100%, and the operations
    # are the round's.
    at_peak = lambda name, backward, windowed: [name, 3 * one(backward, windowed) / 197e12]
    # (one forward and one backward instruction a layer: the checkpoints keep the forward's
    # output, PR 34.)
    every = ([at_peak("causal_attention_fwd.1_bf16_", False, False)]
             + [at_peak(f"causal_attention_fwd_window.{i}_bf16_", False, True) for i in (3, 4, 5)]
             + [at_peak("causal_attention_bwd.9_bf16_", True, False)]
             + [at_peak(f"causal_attention_bwd_window.{i}_bf16_", True, True) for i in (10, 11, 12)])
    assert share.read(_ctx(every)) == pytest.approx(100.0)
    total = sum(s for _, s in every) / 3 * 197e12
    assert total == pytest.approx(flops.attention_kernel_flops_per_round(kw, fed))
    # Two executions fell off the list: their time AND their operations go, the share stays.
    assert share.read(_ctx(every[2:])) == pytest.approx(100.0)
    assert share.read(_ctx([[n, 2 * s] for n, s in every])) == pytest.approx(50.0)


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
    # Pairs a sequence and head: full 8192 x 8193 / 2; window 4096 x 4097 / 2 + 4096 x 4096.
    assert flops.attended_pairs(8192) == 33_558_528
    assert flops.attended_pairs(8192, 4096) == 25_167_872
    assert flops.attended_pairs(8192, 8192) == flops.attended_pairs(8192, 9000) == 33_558_528
    assert flops.attended_pairs(4, 2) == 1 + 2 + 2 + 2
    assert flops.held_rows_per_token(kw) == 1.5
    # A token, forward, by hand: projections 2 x 2560 x 8192 = 41.9 M; router 2 x 2560 x 64
    # = 0.33 M; experts 1.5 x 2 x 3 x 2560 x 768 = 17.7 M; scores and values 4 x 28 x 128
    # a pair: 58.7 M a token in the full layer, 44.0 M in a window layer.
    a_token = 41_943_040 + 327_680 + 17_694_720
    attended = 4 * 28 * 128 * (33_558_528 + 3 * 25_167_872)
    assert flops.forward_flops_per_sample(kw) == 4 * 8192 * a_token + attended + 2 * 2560 * 37984
    # 4 silos x 2 sequences, three times the forward pass: 84.7 TFLOP a round.
    a_round = 8 * flops.train_flops_per_sample(kw)
    assert abs(a_round - 84.7e12) / 84.7e12 < 1e-3
    # The kernels' own: (1 forward run x 2 products + 5) x 2 x 128 a pair = 1792, 28 heads.
    assert flops.attention_kernel_flops_per_round(kw, REAL["federation"]) == (
        8 * 28 * 1792 * (33_558_528 + 3 * 25_167_872))


def test_param_count_matches_the_zoo_tree():
    from nanofed_tpu.models import get_model

    kw = REAL["model"]["kwargs"]
    a_layer = 20_971_520 + 163_840 + 16 * 5_898_240 + 5_120
    by_hand = 4 * a_layer + 2 * 37984 * 2560 + 2_560
    assert a_layer == 115_512_320
    assert _flops().param_count(kw) == by_hand == 656_529_920 == REAL["held"]["parameters"]
    tree = jax.eval_shape(get_model("moe_decoder_lm", **kw).init, jax.random.key(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == 656_529_920
    assert all(leaf.dtype == "float32" for leaf in jax.tree.leaves(tree))
    family = federation.load_named(REPO, "reference", REAL["family"])
    federation.build_model(REAL, family, 1)  # the reference's tree is the zoo's, leaf for leaf


def test_forward_kernel_executions_are_the_pallas_calls_of_a_training_step():
    """Every layer under ``jax.checkpoint``, which keeps the forward kernel's output and
    log-sum-exp (PR 34): the backward pass does not run it again.  Counted in the jaxpr of
    one gradient step at 512 positions (the kernels engage), four layers: 4 forward
    calls, 4 backward."""
    from nanofed_tpu.models import get_model

    model = get_model("moe_decoder_lm", **{**TINY_KWARGS, "seq_len": 512, "window": 256})
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    text = str(jax.make_jaxpr(jax.grad(lambda p, x: model.apply(p, x).sum()))(params, tokens))
    calls = {name: text.count(f"name={name}\n") + text.count(f"name={name} ")
             for name in ("causal_attention_fwd", "causal_attention_fwd_window",
                          "causal_attention_bwd", "causal_attention_bwd_window")}
    layers = len(TINY_KWARGS["rope_layout"])
    flops = _flops()
    assert calls["causal_attention_bwd"] + calls["causal_attention_bwd_window"] == layers
    assert (calls["causal_attention_fwd"] + calls["causal_attention_fwd_window"]
            == flops.FORWARD_KERNEL_EXECUTIONS * layers)
    assert calls["causal_attention_fwd_window"] == flops.FORWARD_KERNEL_EXECUTIONS * 3


def test_configuration_carries_the_published_keys_unchanged():
    row = next(json.loads(line) for line in _catalog()
               if json.loads(line)["name"] == "SmallThinker-21BA3B-Instruct")
    held = {"num_hidden_layers": 4, "moe_num_primary_experts": 16, "vocab_size": 37984}
    for key, value in row["config"].items():
        assert REAL[key] == held.get(key, value), key
    assert REAL["source"].startswith(row["source_url"])
    assert REAL["reduced"] == [*held, "local_steps_per_round"]
    assert {k: REAL["published"][k] for k in held} == {k: row["config"][k] for k in held}
    kw = REAL["model"]["kwargs"]
    assert kw["rope_layout"] == REAL["rope_layout"][:4] == [0, 1, 1, 1]
    assert kw["window_layout"] == REAL["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert (kw["width"], kw["attn_heads"], kw["kv_heads"], kw["head_dim"], kw["expert_width"],
            kw["top_k"], kw["experts"], kw["window"], kw["rope_theta"], kw["eps"]) == (
        2560, 28, 4, 128, 768, 6, 64, 4096, 1500000, 1e-6)
    assert {"router_input", "rotary_pairing", "gate_activation", "attention_bias",
            "secondary_experts", "fused_gate_up", "initialisation", "data", "learning_rate",
            "local_steps_per_round", "loss", "correct"} <= set(REAL["assumed"])


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        return open(path).read().splitlines()
    except OSError:
        pytest.skip(f"the catalog is not at {path}")
