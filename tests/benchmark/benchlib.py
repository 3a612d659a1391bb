"""Shared pieces of the benchmark's tests: where the repo is, stand-in peaks for the CPU,
and a temporary root that holds a copy of ``benchmark/`` with tiny configurations beside
the real ones.  Tiny sizes live here, never in ``benchmark/configs/``."""

import copy
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

CPU_PEAKS = {"platform": "cpu", "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}

TINY = {
    "tiny-cnn": ("mnist-cnn-xdevice-1000", {
        "federation": {"num_clients": 8, "samples_per_client": 6, "batch_size": 4},
        "client_chunk": 2, "reference": {"rounds": 3, "block": 4},
        # Read at this size on the CPU (seeds 1-4): the bf16 program gives up to 0.0285
        # and 0.030, the float8 control 0.066-0.111 and 0.077-0.130.
        "correct": {"loss_gap": 0.05, "first_step_gap": 0.045, "update_gap": 0.045},
    }),
    "tiny-lm": ("gpt2-124m-xsilo-8", {
        "model": {"factory": "transformer_lm_scan",
                  "kwargs": {"vocab": 64, "seq_len": 16, "width": 32, "depth": 2, "heads": 4}},
        "federation": {"num_clients": 4, "samples_per_client": 8, "batch_size": 4, "learning_rate": 0.1},
        "reference": {"rounds": 3, "block": 2},
        # bf16 program up to 0.0058 / 0.0045, float8 control 0.067-0.174 / 0.176-0.267.
        "correct": {"loss_gap": 0.02, "first_step_gap": 0.02, "update_gap": 0.02},
    }),
}


def tiny_configurations() -> dict[str, dict]:
    """``{a configuration of BENCHMARK.json: what its tiny copy changes}``, found and not
    listed: ``TINY`` above, and every ``test_benchmark_*.py`` beside this file that gives a
    ``NAME`` (the configuration) and a ``TINY`` (its changes), as each family's test
    module does.  A later PR's configuration brings its own by adding such a file."""
    import importlib
    import re

    found = {parent: over for parent, over in TINY.values()}
    for path in sorted(Path(__file__).parent.glob("test_benchmark_*.py")):
        if len(re.findall(r"^(?:NAME|TINY) = ", path.read_text(), flags=re.M)) == 2:
            module = importlib.import_module(path.stem)
            found[module.NAME] = module.TINY
    return found


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def make_tiny_root(root: Path) -> Path:
    """Fill ``root`` with BENCHMARK.json and a copy of ``benchmark/``, to which only files
    are ADDED: two tiny configurations, a second traffic mix, a new per-layer metric."""
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    sync = json.loads((REPO / "benchmark" / "traffic" / "sync.json").read_text())
    (root / "benchmark" / "traffic" / "pairs.json").write_text(
        json.dumps({**sync, "rounds_per_sample": 2, "trace_skip": 1, "trace_rounds": 2}))
    (root / "benchmark" / "layer_metrics" / "rounds_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx['rounds']))\n")
    manifest["per_layer"].append({
        "name": "rounds_seen", "unit": "rounds", "better": "higher", "source": "program_counter",
        "layer": "round loop (orchestration.Coordinator)", "moves": "client_samples_per_s",
        "workloads": ["tiny-cnn.pairs"]})
    for name, (parent, over) in TINY.items():
        real = json.loads((REPO / "benchmark" / "configs" / f"{parent}.json").read_text())
        (root / "benchmark" / "configs" / f"{name}.json").write_text(
            json.dumps(_merge(real, {"name": name, **over})))
        manifest["configs"].append({
            "name": name, "source": real["source"], "file": f"benchmark/configs/{name}.json",
            "reduced": ["federation"], "why": "tiny, for the tests"})
    manifest["workloads"] += [
        {"name": "tiny-cnn.pairs", "config": "tiny-cnn", "traffic": "pairs", "chips": 1, "why": "test"},
        {"name": "tiny-cnn.sync-4chip", "config": "tiny-cnn", "traffic": "sync-4chip", "chips": 4, "why": "test"},
        {"name": "tiny-lm.sync", "config": "tiny-lm", "traffic": "sync", "chips": 1, "why": "test"},
    ]
    for metric in manifest["end_to_end"]:
        if "workloads" in metric:
            metric["workloads"] += ["tiny-cnn.pairs", "tiny-cnn.sync-4chip"]
    for metric in manifest["per_layer"]:
        if metric["name"] == "collective_ms_per_round":
            metric["workloads"].append("tiny-cnn.sync-4chip")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def compiled_round_step_paths(root: Path, workload: str, seed: int = 3) -> set[str]:
    """The name paths (``op_name``) of the round program a cell's first round compiles,
    here for the CPU: the cell's system built as a run builds it
    (``federation.start_system``), the program's builder wrapped so that the step's first
    call also compiles the step for its text."""
    import re
    import tempfile

    import jax

    from benchmark import federation, run
    from nanofed_tpu.orchestration import coordinator as program

    texts: list[str] = []
    real = program.build_round_step

    def recording(*args, **kwargs):
        step = real(*args, **kwargs)

        def call(*a, **kw):
            if not texts:
                texts.append(step.jit_program.lower(*a, **kw).compile().as_text())
            return step(*a, **kw)

        call.jit_program = step.jit_program
        return call

    program.build_round_step = recording
    try:
        _, cell, config, traffic = run.load_cell(root, workload)
        family = federation.load_named(root, "reference", config["family"])
        with tempfile.TemporaryDirectory() as work:
            _, _, generator = federation.start_system(
                config, traffic, family, seed, jax.devices()[: cell["chips"]], work)
            next(generator)
    finally:
        program.build_round_step = real
    return set(re.findall(r'op_name="([^"]+)"', texts[0]))
