"""chip_smoke.py rehearsed on the CPU mesh: every phase at a tiny size with the Pallas
kernels interpreted, and the entry's refusal to run without a TPU.

The chip is budgeted; a typo in a phase must be found here, not there."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY = chip_smoke.SmokeSize(
    experiment=dict(
        model="digits_mlp", num_clients=16, local_epochs=1, batch_size=16,
        learning_rate=0.1, scheme="iid", participation=1.0, client_chunk=2,
        compute_dtype="bfloat16",
    ),
    wire=dict(
        model="digits_mlp", clients=12, async_buffer_k=4, ingest_capacity=8,
        arrival_rate=500.0,
    ),
    kernel_params=1300,  # not a lane multiple: the padding paths run
    kernel_cohorts=(8, 40),
    attention_shape=(1, 2, 512, 32),
    experts_shape=(64, 3, 16, 4, 32, 16, 8),
    interpret=True,
)


def test_every_phase_passes_tiny_on_the_cpu_mesh(tmp_path, devices):
    records = {}
    chip_smoke.run_phases(TINY, tmp_path, devices, records.__setitem__)
    assert list(records) == [
        "simulated_single", "simulated_fused_strict", "wire_ingest", "kernels",
        "multichip",
    ]
    single, fused = records["simulated_single"], records["simulated_fused_strict"]
    assert len(single["losses"]) == len(fused["losses"]) == TINY.rounds
    assert fused["max_loss_delta_vs_single"] <= TINY.loss_tolerance
    wire = records["wire_ingest"]
    assert wire["accepted"] == 12 and wire["aggregations_completed"] == 3
    assert wire["flat_size"] == 4810  # digits_mlp
    assert set(records["kernels"]) >= {
        "u32", "C=8", "C=40", "weighted_mean_tree", "causal_attention", "causal_attention_blocks",
        "expert_tiles"}
    multi = records["multichip"]
    assert multi["4"]["client_rows_per_device"] == 4
    assert multi["2x2"]["client_rows_per_device"] == 8
    per_device, total = multi["2x2"]["params_bytes_per_device"]
    assert per_device < total


def test_a_failed_check_raises():
    """No phase may swallow a failure: the entry exits through the exception."""
    with pytest.raises(RuntimeError, match="did not fall"):
        chip_smoke._losses_ok([1.0, 2.0], "rehearsal")
    assert chip_smoke._rel_err(np.ones(4), np.ones(4) * (1 + 1e-3)) > 2e-5


def _flagship_samples_per_client():
    clients, _ = chip_smoke.experiment_data(chip_smoke.FULL)
    (real,) = set(np.asarray(clients.mask).sum(axis=1).tolist())
    return int(real)


@pytest.mark.parametrize("field", [
    "num_clients", "samples_per_client", "local_epochs", "batch_size", "learning_rate",
    "compute_dtype",
])
def test_flagship_is_the_federation_of_the_benchmarks_cnn_cell(field):
    """What the bring-up proof runs is the federation the benchmark's one-chip CNN cell
    measures; ``client_chunk`` is the one stated difference (PERF.md section 4)."""
    cell = json.loads(
        (REPO / "benchmark" / "configs" / "mnist-cnn-xdevice-1000.json").read_text()
    )
    flagship = chip_smoke.FULL.experiment
    ours = (
        _flagship_samples_per_client() if field == "samples_per_client" else flagship[field]
    )
    assert ours == {**cell["federation"], **cell["precision"]}[field]
    assert flagship["model"] == cell["model"]["factory"]
    assert flagship["client_chunk"] == 125 != cell["client_chunk"]


def test_entry_refuses_to_run_without_a_tpu():
    """`python chip_smoke.py` on a CPU backend: non-zero exit, the device line and
    nothing else on stdout — no phase ran, no result line."""
    assert jax.default_backend() == "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1 and lines[0].startswith("chip_smoke: platform=cpu ")
    assert "need a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
