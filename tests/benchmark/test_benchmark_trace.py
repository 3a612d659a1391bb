"""The trace reduction: on hand-made intervals, on a trace of the CPU backend, and on
traces recorded on the chip in PR 23 (``tests/benchmark/data``: a traced run's events
as ``run.py --keep-trace`` writes them, cut to the first traced rounds and gzipped)."""

import gzip
import json
from pathlib import Path

import pytest

from benchlib import REPO  # noqa: F401  (puts the repo on sys.path)

from benchmark import trace

DATA = Path(__file__).resolve().parent / "data"
RECORDED = sorted(p.name for p in DATA.glob("*.trace.json.gz"))


def test_merge_is_the_union_of_intervals():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9), (20, 21)]) == [[0, 3], [5, 9], [20, 21]]
    assert trace.merge([]) == []


def test_self_time_takes_nested_operations_out_of_their_parent():
    events = [["while", 0, 100], ["fusion.1", 10, 30], ["all-reduce.2", 50, 20], ["copy", 120, 5]]
    assert trace.self_times(events) == {"while": 50, "fusion.1": 30, "all-reduce.2": 20, "copy": 5}


def test_short_name_keeps_the_instruction_and_two_shapes():
    hlo = ("%fusion.347 = (bf16[125,32]{1,0:T(8,128)(2,1)}, bf16[64,26,26,125,32]{0,4,3,2,1}) "
           "fusion(bf16[125,64,24,24,64]{4,1,0,3,2} %copy.123), kind=kOutput")
    assert trace.short_name(hlo) == "fusion.347 bf16[125,32] bf16[64,26,26,125,32]"
    assert trace.short_name("%all-reduce.5") == "all-reduce.5"


def test_reduce_on_hand_made_events():
    events = {
        "devices": {
            0: [["while", 100, 400], ["fusion.1", 100, 100], ["all-reduce.3", 300, 50], ["copy.2", 700, 100]],
            1: [["while", 100, 300], ["all-reduce.3", 300, 100]],
        },
        "host": [["bench.round", 0, 1000], ["round", 50, 600], ["publish", 660, 30], ["local-train", 90, 420]],
    }
    got = trace.reduce(events, "bench.round")
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx((500 + 300) / 2 * 1e-9)
    assert got["collective_s"] == pytest.approx(100e-9) and got["collective_events"] == 2
    assert dict(map(tuple, got["device_ops"]))["while"] == pytest.approx((250 + 200) / 2 * 1e-9)
    # Device 0 is the busiest: idle 0-100 (round is innermost over 50), 500-700 (600: round),
    # 800-1000 (900: bench.round only).
    assert dict(map(tuple, got["idle_gaps"])) == pytest.approx({"round": 300e-9, "bench.round": 200e-9})
    assert trace.reduce({"devices": {}, "host": events["host"]}, "bench.round") is None


def test_a_cpu_trace_loads_and_has_no_device_plane(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.round"):
        f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    assert path is not None
    events = trace.load(path, {"bench.round"})
    assert events["devices"] == {} and [e[0] for e in events["host"]] == ["bench.round"]
    assert trace.reduce(events, "bench.round") is None
    assert trace.find_xplane(str(tmp_path / "nothing")) is None


def _sweep_busy(device_events, start, end):
    """Busy time by an independent method: sweep the edges, count the open intervals."""
    edges = sorted([(s, 1) for _, s, d in device_events] + [(s + d, -1) for _, s, d in device_events])
    busy, open_, last = 0, 0, start
    for t, step in edges:
        if open_ > 0:
            busy += t - last
        open_, last = open_ + step, t
    return busy


@pytest.mark.parametrize("name", RECORDED)
def test_reduce_on_a_trace_recorded_on_the_chip(name):
    recorded = json.loads(gzip.decompress((DATA / name).read_bytes()))
    events = {"devices": {int(k): v for k, v in recorded["devices"].items()}, "host": recorded["host"]}
    got = trace.reduce(events, "bench.round")
    chips = len(events["devices"])
    assert chips == recorded["chips"]
    want_busy = sum(_sweep_busy(ev, 0, 0) for ev in events["devices"].values()) / chips / 1e9
    assert got["busy_s"] == pytest.approx(want_busy, rel=1e-9)
    assert 0.5 * got["window_s"] < got["busy_s"] <= got["window_s"]
    # What the run itself printed for these rounds, kept beside the events.
    assert got["busy_s"] == pytest.approx(recorded["expected"]["busy_s"], rel=1e-9)
    assert got["collective_s"] == pytest.approx(recorded["expected"]["collective_s"], rel=1e-9)
    assert (got["collective_events"] > 0) == (chips > 1)
    assert len(got["device_ops"]) == 10 and all(s > 0 for _, s in got["device_ops"])
    ops_total = sum(s for _, s in got["device_ops"])
    assert ops_total <= got["busy_s"] * 1.0001
    gaps = dict(map(tuple, got["idle_gaps"]))
    assert set(gaps) <= {"bench.round", "round", "cohort-sample", "cohort-gather",
                         "local-train", "aggregate", "publish", "unattributed"}
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - max(
        _sweep_busy(ev, 0, 0) for ev in events["devices"].values()) / 1e9, rel=1e-6)


def test_there_is_a_recorded_trace():
    assert RECORDED, "tests/benchmark/data holds no trace recorded on the chip"
