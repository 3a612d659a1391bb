"""BENCHMARK.json against the contract it is written to, and against the files it names."""

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    cells = MANIFEST["workloads"]
    assert 1 <= len(cells) <= 24
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    # The full check at 24 cells has to fit the driver's 43200 s.
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
    config = json.loads((REPO / entry["file"]).read_text())
    assert config["reduced"] == entry["reduced"] and config["source"] == entry["source"]
    for key in entry["reduced"]:
        assert NAME.match(key) and key in config
        assert not re.search(r"(_dim|_rank|hidden|intermediate|n_embd|n_head|width)$", key)
    for kind in ("reference", "flops"):
        assert (REPO / "benchmark" / kind / f"{config['family']}.py").is_file()
    assert any(w["config"] == entry["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    traffic = json.loads((REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (REPO / "benchmark" / "loops" / f"{traffic['loop']}.py").is_file()
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1


def _cells_of(metric):
    return set(metric.get("workloads", [w["name"] for w in MANIFEST["workloads"]]))


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert (REPO / "benchmark" / "end_to_end" / f"{metric['name']}.py").is_file()
    assert _cells_of(metric) <= {w["name"] for w in MANIFEST["workloads"]}


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    # Its reader: a module of its own, or — a per-scope metric — a data file alone.
    assert ((REPO / "benchmark" / "layer_metrics" / f"{metric['name']}.py").is_file()
            != (REPO / "benchmark" / "scope_metrics" / f"{metric['name']}.json").is_file())
    moved = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric["moves"])
    assert _cells_of(metric) <= _cells_of(moved)


def test_names_are_unique_and_every_cell_is_covered():
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics
    for cell in MANIFEST["workloads"]:
        e2e = [m for m in MANIFEST["end_to_end"] if cell["name"] in _cells_of(m)]
        assert {"setup_s"} < {m["name"] for m in e2e}
        assert any(cell["name"] in _cells_of(m) for m in MANIFEST["per_layer"])
    layers = {}
    for m in MANIFEST["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    perf = (REPO / "PERF.md").read_text()
    assert all(m["layer"].split(" (")[0] in perf for m in MANIFEST["per_layer"])


def test_the_harness_names_no_configuration_mix_or_metric():
    names = ({c["name"] for c in MANIFEST["configs"]} | {w["traffic"] for w in MANIFEST["workloads"]}
             | {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]})
    for f in ("run.py", "federation.py", "check.py", "trace.py", "loops/closed_rounds.py"):
        text = (REPO / "benchmark" / f).read_text()
        code = "\n".join(line for line in text.splitlines() if not line.lstrip().startswith("#"))
        code = re.sub(r'"""[\s\S]*?"""', "", code)
        for name in names:
            assert not re.search(rf"['\"]{re.escape(name)}['\"]", code), (f, name)
