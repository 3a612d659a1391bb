"""The per-scope metrics against the program: the scopes their files name are the
``jax.named_scope``s the cells' round programs carry.  Each cell's configuration, tiny,
is built as a run builds it; the name paths of its compiled round step are put through
the function the trace's reduction uses (``trace.scope_chain``), and every per-scope
metric that lists the cell has to find an operation there, in the pass it asks for.  A
renamed scope fails here, not in a ledger line.  Nothing here lists a configuration or
a metric: the tiny copies are found (``benchlib.tiny_configurations``), the metrics are
the files of ``benchmark/scope_metrics``."""

import json
import re
import shutil

import pytest

from benchlib import REPO, _merge, compiled_round_step_paths, tiny_configurations

from benchmark import federation, trace

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
SPECS, NAMES = federation.scope_metrics(REPO)
TINY_OF = tiny_configurations()
PASSES = {trace.FORWARD, trace.RECOMPUTED, trace.BACKWARD}
#: Every cell; one whose configuration has no tiny copy among the tests is skipped, in sight.
CELLS = [w["name"] if w["config"] in TINY_OF else
         pytest.param(w["name"], marks=pytest.mark.skip(reason=f"no tiny copy of {w['config']} in tests/benchmark"))
         for w in MANIFEST["workloads"]]


@pytest.fixture(scope="module")
def scopes_root(tmp_path_factory):
    """BENCHMARK.json and a copy of ``benchmark/`` in which every configuration that has
    a tiny copy is replaced by it, the cells and the mixes as they stand."""
    root = tmp_path_factory.mktemp("scopes_root")
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root)
    for spec in MANIFEST["configs"]:
        if spec["name"] in TINY_OF:
            real = json.loads((REPO / spec["file"]).read_text())
            (root / spec["file"]).write_text(json.dumps(_merge(real, TINY_OF[spec["name"]])))
    return root


@pytest.mark.parametrize("cell", CELLS)
def test_the_round_step_carries_the_scopes_its_cells_metrics_read(scopes_root, cell):
    found = set()
    for path in compiled_round_step_paths(scopes_root, cell):
        chain, mark = trace.scope_chain(path, NAMES)
        found.add((chain, mark or trace.FORWARD))
    rows = [[list(chain), kind, 1.0] for chain, kind in found]  # as ``by_scope`` gives them
    for metric in MANIFEST["per_layer"]:
        spec = SPECS.get(metric["name"])
        if spec is not None and cell in metric.get("workloads", [cell]):
            reads = trace.scope_seconds(rows, spec.get("scopes"), spec.get("pass"), bool(spec.get("innermost")))
            assert reads is not None, f"{metric['name']}: no operation of {cell}'s round step is under {spec.get('scopes')}"


def test_a_per_scope_metric_is_its_file_and_its_entry_and_has_no_reader_module():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert SPECS and set(SPECS) <= set(entries)
    for metric, spec in SPECS.items():
        assert set(spec) <= {"why", "scopes", "pass", "innermost"} and spec["why"]
        assert spec.get("pass") in PASSES | {None}
        assert entries[metric]["source"] == "device_trace" and entries[metric]["unit"] == "ms"
        assert not (REPO / "benchmark" / "layer_metrics" / f"{metric}.py").exists()
    for path in (REPO / "benchmark" / "scope_table").glob("*.json"):
        named = json.loads(path.read_text())
        assert set(named) == {"why", "scopes"} and named["why"] and set(named["scopes"]) <= NAMES


def test_scopes_named_anywhere_are_scopes_of_the_program():
    """Every name a file asks for stands in a ``jax.named_scope("...")`` of the program."""
    source = "\n".join(p.read_text() for p in (REPO / "nanofed_tpu").rglob("*.py"))
    for name in NAMES:
        assert f'"{name}"' in source, name


def test_the_harness_names_no_scope():
    """Which scopes exist and which make a metric is data (``benchmark/scope_metrics``,
    ``benchmark/scope_table``): outside comments and docstrings the harness's own files
    spell none of them."""
    for f in ("run.py", "trace.py", "federation.py", "check.py", "loops/closed_rounds.py"):
        text = (REPO / "benchmark" / f).read_text()
        code = "\n".join(line for line in text.splitlines() if not line.lstrip().startswith("#"))
        code = re.sub(r'"""[\s\S]*?"""', "", code)
        for name in NAMES:
            assert not re.search(rf"\b{re.escape(name)}\b", code), (f, name)
