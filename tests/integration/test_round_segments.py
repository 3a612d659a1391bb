"""The Coordinator's tiling of its own round: every ``RoundMetrics`` carries the five
critical-path segments (prepare / dispatch / device_wait / readback / publish), cut at
the loop's own spans, and the same numbers reach the ledger's ``round`` record, the
critical-path digest and the occupancy gauge.  Also: the named scopes of the round
program, which a device profile groups its operations by."""

import json
import re
import time

import jax
import pytest

from nanofed_tpu.data import federate, synthetic_classification
from nanofed_tpu.models import get_model
from nanofed_tpu.observability import (
    SYNC_LOOP_SEGMENTS,
    critical_path_rounds,
    load_host_streams,
    summarize_telemetry,
)
from nanofed_tpu.orchestration import Coordinator, CoordinatorConfig
from nanofed_tpu.orchestration.types import RoundStatus
from nanofed_tpu.trainer import TrainingConfig

OLD_SPANS = ("round", "cohort-sample", "cohort-gather", "local-train", "aggregate",
             "publish")


def _coordinator(tmp_path, **config):
    ds = synthetic_classification(256, 3, (8,), seed=0)
    return Coordinator(
        model=get_model("mlp", in_features=8, hidden=16, num_classes=3),
        train_data=federate(ds, num_clients=8, scheme="iid", batch_size=16),
        config=CoordinatorConfig(base_dir=tmp_path, **config),
        training=TrainingConfig(batch_size=16, local_epochs=1, learning_rate=0.1),
    )


def _timed_steps(coordinator):
    """``[(seconds around next(generator), RoundMetrics), ...]``, as the benchmark's
    loop clocks a step."""
    generator, out = coordinator.start_training(), []
    while True:
        t0 = time.perf_counter()
        try:
            metrics = next(generator)
        except StopIteration:
            return out
        out.append((time.perf_counter() - t0, metrics))


def _records(tmp_path, kind):
    with (tmp_path / "telemetry.jsonl").open() as f:
        return [r for r in map(json.loads, f) if r.get("type") == kind]


def test_single_step_segments_tile_the_generator_step(tmp_path, devices):
    coordinator = _coordinator(tmp_path, num_rounds=3)
    steps = _timed_steps(coordinator)
    assert len(steps) == 3
    for step_s, metrics in steps:
        assert tuple(metrics.segments) == SYNC_LOOP_SEGMENTS
        assert all(v >= 0.0 for v in metrics.segments.values())
        assert 0.8 * step_s <= sum(metrics.segments.values()) <= step_s
        # duration_s is still the round alone: it ends before publish begins.
        assert metrics.duration_s <= sum(metrics.segments.values())
        assert metrics.to_dict()["segments"] == metrics.segments
    assert coordinator.history == [m for _, m in steps]
    # The metrics JSON is written inside `publish`, so it carries the other four.
    on_disk = json.loads((tmp_path / "metrics" / "metrics_round_2.json").read_text())
    assert list(on_disk["segments"]) == list(SYNC_LOOP_SEGMENTS[:-1])


def test_fused_block_reports_the_same_five_segments(tmp_path, devices):
    steps = _timed_steps(_coordinator(tmp_path, num_rounds=4, rounds_per_block=2))
    assert len(steps) == 4
    for first in (0, 2):
        block = steps[first:first + 2]
        for _, metrics in block:
            assert tuple(metrics.segments) == SYNC_LOOP_SEGMENTS
            assert all(v >= 0.0 for v in metrics.segments.values())
        # The block's rounds are all run before its first is yielded: the tiling is
        # of the block, whose four device-side stretches its rounds share evenly.
        tiled = sum(sum(m.segments.values()) for _, m in block)
        stepped = sum(step_s for step_s, _ in block)
        assert 0.8 * stepped <= tiled <= stepped
        a, b = (m.segments for _, m in block)
        assert a["device_wait"] == b["device_wait"] and a["prepare"] == b["prepare"]
    fused = [r for r in _records(tmp_path, "round") if r.get("fused")]
    assert len(fused) == 4 and all(tuple(r["segments"]) == SYNC_LOOP_SEGMENTS for r in fused)


def test_failed_round_carries_prepare_and_publish_only(tmp_path, devices):
    steps = _timed_steps(_coordinator(
        tmp_path, num_rounds=2, dropout_rate=0.9, min_completion_rate=1.0))
    for step_s, metrics in steps:
        assert metrics.status == RoundStatus.FAILED
        assert tuple(metrics.segments) == ("prepare", "publish")
        assert 0.0 <= sum(metrics.segments.values()) <= step_s


def test_new_spans_nest_under_the_old_ones_which_stay_one_a_round(tmp_path, devices):
    _coordinator(tmp_path, num_rounds=2).run()
    spans = _records(tmp_path, "span")
    by_id = {s["span_id"]: s for s in spans}
    parent = lambda s: by_id[s["parent_id"]]["name"] if s["parent_id"] is not None else None
    parents = {}
    for s in spans:
        parents.setdefault(s["name"], set()).add(parent(s))
    assert parents["dispatch"] == parents["device-wait"] == {"local-train"}
    assert parents["round-keys"] == parents["client-detail"] == {"round"}
    # The six names the benchmark's gap attribution reads: once a round each, the
    # nesting they had.
    for name in OLD_SPANS:
        assert sum(s["name"] == name for s in spans) == 2, name
    assert parents["round"] == parents["publish"] == {None}
    assert all(parents[n] == {"round"} for n in OLD_SPANS[1:5])


def test_round_record_carries_segments_and_the_digests_read_them(tmp_path, devices):
    coordinator = _coordinator(tmp_path, num_rounds=2)
    # The default registry is the process's: count what this run adds to it.
    histogram = coordinator.program_catalog.registry.histogram(
        "nanofed_round_critical_path_seconds", labels=("segment",))
    before = {s: histogram.sample_count(segment=s) for s in SYNC_LOOP_SEGMENTS}
    history = coordinator.run()
    assert all(histogram.sample_count(segment=s) == before[s] + 2 for s in before)
    records = _records(tmp_path, "round")
    assert len(records) == 2
    for record, metrics in zip(records, history):
        assert tuple(record["segments"]) == SYNC_LOOP_SEGMENTS
        assert record["segments"] == pytest.approx(metrics.segments, abs=1e-6)
        # Charged after publish: the record's walltime is the whole tiled step.
        assert record["duration_s"] == pytest.approx(
            sum(metrics.segments.values()), abs=1e-6)
    rows = critical_path_rounds(load_host_streams(tmp_path))
    assert [row["round"] for row in rows] == [0, 1]
    assert all(tuple(row["segments"]) == SYNC_LOOP_SEGMENTS for row in rows)
    assert all(row["coverage"] == pytest.approx(1.0, abs=0.01) for row in rows)
    summary = summarize_telemetry(tmp_path / "telemetry.jsonl")
    assert set(summary["critical_path"]) == set(SYNC_LOOP_SEGMENTS)
    assert summary["critical_path"]["device_wait"]["count"] == 2
    assert summary["critical_path_coverage"]["rounds"] == 2
    assert summary["critical_path_coverage"]["min"] == pytest.approx(1.0, abs=0.01)


@pytest.mark.parametrize("scaffold", [False, True], ids=["fedavg", "scaffold"])
def test_round_program_names_its_phases(devices, scaffold):
    """``jax.named_scope`` around the round program's phases: the names reach the
    lowered program's debug text as components of the operations' names (and from
    there the compiled program's metadata, which a device profile reads)."""
    from nanofed_tpu.core.types import ClientData
    from nanofed_tpu.parallel.mesh import make_mesh
    from nanofed_tpu.parallel.round_step import build_round_step, init_server_state
    from nanofed_tpu.parallel.scaffold_step import build_scaffold_round_step
    from nanofed_tpu.aggregation.base import fedavg_strategy

    model = get_model("mlp", in_features=8, hidden=16, num_classes=3)
    training = TrainingConfig(batch_size=4, local_epochs=1, learning_rate=0.1)
    mesh = make_mesh(devices=devices[:2])
    params = model.init(jax.random.key(0))
    state = init_server_state(fedavg_strategy(), params)
    clients = 4
    data = ClientData(
        x=jax.numpy.zeros((clients, 8, 8)), y=jax.numpy.zeros((clients, 8), "int32"),
        mask=jax.numpy.ones((clients, 8)))
    weights = jax.numpy.ones((clients,))
    rngs = jax.random.split(jax.random.key(1), clients)
    if scaffold:
        step = build_scaffold_round_step(model.apply, training, mesh, clients)
        zeros = jax.tree.map(jax.numpy.zeros_like, params)
        stack = jax.tree.map(lambda p: jax.numpy.zeros((clients, *p.shape), p.dtype), params)
        args = (params, state, zeros, stack, data, weights, rngs)
    else:
        step = build_round_step(model.apply, training, mesh, client_chunk=1)
        args = (params, state, data, weights, rngs)
    text = step.jit_program.lower(*args).as_text(debug_info=True)
    for scope in ("local_fit", "client_reduce", "server_apply", "round_metrics"):
        # A component of an operation's name path, not a function that happens to
        # be called so (``make_local_fit.<locals>.local_fit``).
        assert re.search(rf'[/"]{scope}/', text), scope
