"""A run of a cell, all of it but the look for a chip, at tiny sizes on the CPU:
the result line, the reference against the system, the control, a broken timed path,
and the claim that a new cell, mix or metric is files and entries alone."""

import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import pytest

from benchlib import CPU_PEAKS, REPO

from benchmark import check, federation, run

ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _run(root, workload, traced=False, seed=3, seconds=0.3):
    return run.run_cell(root, workload, seed, seconds, traced, jax.devices(), CPU_PEAKS)


CHECKS = ["loss_gap.0", "loss_gap.1", "loss_gap.2", "first_step_gap", "update_gap",
          "compiles_in_window", "failed_rounds"]


def _failing(result):
    """The names in the line's ``checks`` whose number passes its limit: why a run was
    not ``correct``, as a record of the line's end keeps it."""
    assert list(result)[-1] == "checks" and list(result["checks"]) == CHECKS
    assert all(len(pair) == 2 for pair in result["checks"].values())
    return [name for name, (value, limit) in result["checks"].items()
            if value is None or not value <= limit]


def _cells(root, manifest_key, workload):
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"] for m in manifest[manifest_key] if run.applies(m, workload)}


@pytest.mark.parametrize("workload", ["tiny-cnn.pairs", "tiny-lm.sync", "tiny-cnn.sync-4chip"])
def test_untraced_run_reports_the_end_to_end_metrics_and_is_correct(tiny_root, workload, monkeypatch):
    said = []
    monkeypatch.setattr(run, "say", lambda *parts: said.append(" ".join(map(str, parts))))
    result = _run(tiny_root, workload)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == _cells(tiny_root, "end_to_end", workload)
    assert all(set(m) == {"value", "unit"} and m["value"] > 0 for m in result["metrics"].values())
    out = "\n".join(said)
    # Every number compared is printed beside its limit, and the sample count too.
    assert out.count("# compared ") == 5 and "(limit " in out and " samples of " in out
    # ... and is in the line itself, under its last key, each number with its limit.
    assert _failing(result) == []
    limits = run.load_cell(tiny_root, workload)[2]["correct"]
    for name, (value, limit) in result["checks"].items():
        assert f"# compared {name}: {value:.6g} (limit {limit:g})" in out or limit == 0
        assert limit == limits.get(name.split(".")[0], 0)
    assert json.loads(json.dumps(result))["checks"] == {k: list(v) for k, v in result["checks"].items()}


def test_traced_run_reports_per_layer_metrics_and_finds_added_files(tiny_root):
    """``tiny-cnn.pairs`` exists only as files ADDED to the copy: a configuration file,
    a traffic file and a ``layer_metrics`` file, with entries in BENCHMARK.json."""
    result = _run(tiny_root, "tiny-cnn.pairs", traced=True)
    assert result["correct"] is True and _failing(result) == []
    metrics = result["metrics"]
    assert metrics["rounds_seen"]["value"] == result["attempted"] >= 3
    assert {"host_gap_ms", "mfu_pct"} <= set(metrics)
    # No device plane on the CPU: the trace's readers find nothing and are left out.
    assert "device_busy_ms_per_round" not in metrics and "busy_s" not in result["device"]
    assert set(metrics) <= _cells(tiny_root, "per_layer", "tiny-cnn.pairs")
    unchanged = ["run.py", "federation.py", "check.py", "trace.py", "loops/closed_rounds.py"]
    for f in unchanged:
        assert (tiny_root / "benchmark" / f).read_bytes() == (REPO / "benchmark" / f).read_bytes()


def test_traced_run_hands_the_scope_table_to_its_readers_and_prints_it(tmp_path, monkeypatch):
    """The CPU has no device plane, so the trace is put in by hand: the SmallThinker round
    recorded on the chip (``tests/benchmark/data``) stands in for what ``trace.load``
    reads.  The per-scope metrics of the cells listed appear, the table is printed whole
    with its sum beside the busy time, and the ten longest rows go into ``breakdown``."""
    import gzip

    from benchlib import make_tiny_root
    from benchmark import trace

    root = make_tiny_root(tmp_path)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    listed = {"local_fit_ms_per_round", "attention_ms_per_round", "expert_loop_ms_per_round",
              "recompute_ms_per_round", "ssm_mixer_ms_per_round"}
    for metric in manifest["per_layer"]:
        if metric["name"] in listed:
            metric["workloads"].append("tiny-lm.sync")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    data = REPO / "tests" / "benchmark" / "data" / "smallthinker-21b-4l-xsilo-4.sync.trace.json.gz"
    recorded = json.loads(gzip.decompress(data.read_bytes()))
    events = {"devices": {int(k): v for k, v in recorded["devices"].items()}, "host": recorded["host"]}
    monkeypatch.setattr(trace, "find_xplane", lambda trace_dir: "recorded")
    monkeypatch.setattr(trace, "load", lambda path, names: events)
    said = []
    monkeypatch.setattr(run, "say", lambda *parts: said.append(" ".join(map(str, parts))))
    result = _run(root, "tiny-lm.sync", traced=True)
    metrics, rounds = result["metrics"], 3  # sync.json traces three rounds
    want = recorded["expected"]["scope_ms_per_round"]
    for name in listed - {"ssm_mixer_ms_per_round"}:
        assert metrics[name]["value"] == pytest.approx(want[name] * recorded["rounds"] / rounds)
    # Listed, but nothing of this trace ran under its scope: left out, not 0.
    assert "ssm_mixer_ms_per_round" not in metrics and "fit_unscoped_ms_per_round" not in metrics
    scopes = result["breakdown"]["device_scopes"]
    assert len(scopes) == 10 and scopes == sorted(scopes, key=lambda e: -e[1])
    assert scopes[0][0] == "moe_experts.backward" and set(result["breakdown"]) == {
        "device_ops", "idle_gaps", "device_scopes"}
    assert list(result)[-2:] == ["breakdown", "checks"] and _failing(result) == []
    out = "\n".join(said)
    assert "# device time by scope, ms a traced round:" in out and "#   unscoped " in out
    assert "the rows' sum" in out and "(+0.000%)" in out and "# trace read in " in out
    json.dumps(result)


def _first_rounds(root, workload, seed):
    _, cell, config, traffic = run.load_cell(root, workload)
    family = federation.load_named(root, "reference", config["family"])
    fedavg = federation.load_named(root, "reference", "fedavg")
    model = federation.build_model(config, family, seed)
    data = federation.make_data(config, family, seed, model.input_shape, model.num_classes)
    rounds = config["reference"]["rounds"]

    def reference(q):
        return check.reference_rounds(fedavg, family, config, data, seed, jax.devices()[0], rounds, q)

    return config, fedavg, reference


@pytest.mark.parametrize("workload", ["tiny-cnn.pairs", "tiny-lm.sync"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_a_lower_precision_is_not_correct(tiny_root, workload, seed):
    """The control: the reference computed in float8, one step under the configurations'
    bfloat16, put in the program's place.  It has to fail one of the limits."""
    config, fedavg, reference = _first_rounds(tiny_root, workload, seed)
    exact, lower = reference(fedavg.identity), reference(fedavg.float8)
    want = check.norms(exact, exact["start"])
    rows = check.compare(check.norms(lower, exact["start"]), want, config["correct"])
    assert not all(r["ok"] for r in rows), rows
    same = check.compare(want, want, config["correct"])
    assert all(r["ok"] and r["value"] == 0 for r in same)


def test_a_round_that_returns_its_state_unchanged_is_not_correct(tiny_root, monkeypatch):
    """The timed path broken underneath: the round program still runs, but the global
    parameters it hands back are the ones it was given."""
    import jax.numpy as jnp

    from nanofed_tpu.orchestration import coordinator as program

    real_builder = program.build_round_step

    def broken_builder(*args, **kwargs):
        step = real_builder(*args, **kwargs)

        def broken(params, *rest):
            kept = jax.tree.map(jnp.copy, params)
            return step(params, *rest)._replace(params=kept)

        broken.jit_program = step.jit_program
        return broken

    monkeypatch.setattr(program, "build_round_step", broken_builder)
    result = _run(tiny_root, "tiny-lm.sync")
    assert result["correct"] is False and result["failed"] == 0
    # The line says which numbers: the steps' norms, and nothing of the window.
    assert {"first_step_gap", "update_gap"} <= set(_failing(result)) <= set(CHECKS[:5])
    assert result["checks"]["update_gap"][0] == pytest.approx(1.0, abs=1e-3)


def test_a_part_of_the_cohort_left_out_is_not_correct(tiny_root, monkeypatch):
    """Half the clients weigh nothing in the reduce: the loss and the step both move."""
    from nanofed_tpu.orchestration import coordinator as program

    real = program.compute_weights
    monkeypatch.setattr(
        program, "compute_weights",
        lambda n, mask: real(n, mask) * (jax.numpy.arange(n.shape[0]) % 2),
    )
    result = _run(tiny_root, "tiny-cnn.pairs")
    assert result["correct"] is False
    failing = _failing(result)
    assert any(name.startswith("loss_gap.") for name in failing) and "first_step_gap" in failing
    assert set(failing) <= set(CHECKS[:5])


def test_a_compile_inside_the_window_is_not_correct_and_the_line_says_so(tiny_root, monkeypatch):
    """Every compared number within its limit, and one program compiled while the window
    ran: ``checks`` counts it against its limit of 0 and names nothing else."""
    loop = federation.load_named(tiny_root, "loops", "closed_rounds")
    real = loop.measure

    def compiling(generator, traffic, seconds, trace_dir):
        jax.jit(lambda x: x * 3 + len(traffic))(jax.numpy.arange(7.0)).block_until_ready()
        return real(generator, traffic, seconds, trace_dir)

    monkeypatch.setattr(loop, "measure", compiling)
    result = _run(tiny_root, "tiny-lm.sync")
    assert result["correct"] is False and result["failed"] == 0
    assert _failing(result) == ["compiles_in_window"]
    assert result["checks"]["compiles_in_window"][0] >= 1


def test_main_ends_standard_output_with_the_line_and_standard_error_with_the_checks(
        tiny_root, monkeypatch, capsys):
    """``main`` with the look for a chip stepped over: the result is the last line of
    standard output, ``checks`` its last key, and the same pairs end standard error."""
    monkeypatch.setattr(run, "ROOT", tiny_root)
    monkeypatch.setattr(run, "configure_cache", lambda root: None)
    monkeypatch.setattr(run, "look_for_chips", lambda root, chips: (jax.devices(), CPU_PEAKS))
    assert run.main(["--workload", "tiny-lm.sync", "--seed", str(2**31 + 7),
                     "--seconds", "0.3", "--trace", "0"]) == 0
    captured = capsys.readouterr()
    result = json.loads(captured.out.strip().splitlines()[-1])
    assert result["correct"] is True and _failing(result) == []
    said = captured.err.strip().splitlines()[-len(CHECKS):]
    assert said == [f"{name} {value} limit {limit}"
                    for name, (value, limit) in result["checks"].items()]


def _stdout_of(cmd, cwd):
    done = subprocess.run(cmd, cwd=cwd, env=ENV, capture_output=True, text=True, timeout=120)
    return done.returncode, done.stdout


def test_run_py_exits_non_zero_without_a_chip():
    code, out = _stdout_of(
        [sys.executable, "benchmark/run.py", "--workload", "mnist-cnn-xdevice-1000.sync",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"], REPO)
    assert code != 0 and not out.strip()


def test_run_py_exits_non_zero_where_the_program_is_missing():
    with tempfile.TemporaryDirectory() as tmp:
        shutil.copy(REPO / "BENCHMARK.json", tmp)
        shutil.copytree(REPO / "benchmark", Path(tmp) / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copytree(REPO / "tests" / "benchmark", Path(tmp) / "tests" / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__"))
        code, out = _stdout_of(
            [sys.executable, "benchmark/run.py", "--workload", "mnist-cnn-xdevice-1000.sync",
             "--seed", "1", "--seconds", "1", "--trace", "0"], tmp)
    assert code != 0 and not out.strip()


def test_seeds_beyond_32_bits_give_distinct_reproducible_inputs(tiny_root):
    _, _, config, _ = run.load_cell(tiny_root, "tiny-cnn.pairs")
    family = federation.load_named(tiny_root, "reference", config["family"])
    draw = lambda seed: federation.make_data(config, family, seed, (28, 28, 1), 10)[0]
    big = 2**31 + 12345
    assert (draw(big) == draw(big)).all()
    assert not (draw(big) == draw(12345)).all() and not (draw(big) == draw(big + 1)).all()
    assert federation.program_seed(big) < 2**31 - 1
