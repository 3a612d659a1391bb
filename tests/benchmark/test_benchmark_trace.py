"""The trace reduction: on hand-made intervals, on a trace of the CPU backend, and on
traces recorded on the chip (``tests/benchmark/data``: a traced run's events as
``run.py --keep-trace`` writes them, cut to the first traced rounds and gzipped; the two
CNN cells' in PR 23, before an event carried its name path, an expert cell's in PR 37)."""

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
    # Device 0 is the busiest: idle 0-100 (round covers half of it, not most: bench.round),
    # 500-700 (round covers 150 of 200), 800-1000 (bench.round only).
    assert dict(map(tuple, got["idle_gaps"])) == pytest.approx({"round": 200e-9, "bench.round": 300e-9})
    assert trace.reduce({"devices": {}, "host": events["host"]}, "bench.round") is None


def test_an_idle_gap_is_named_by_the_innermost_span_over_most_of_it():
    """Not by the span over its middle: a gap of 100 whose middle a 10-long span covers is
    the enclosing span's; one a short span covers 60 of is the short span's; one no span
    covers more than half of is unattributed."""
    events = {
        "devices": {0: [["a", 0, 100], ["b", 200, 100], ["c", 400, 100], ["d", 700, 100]]},
        "host": [["bench.round", 0, 600], ["dispatch", 245, 10], ["device-wait", 330, 70],
                 ["publish", 590, 40]],
    }
    gaps = dict(map(tuple, trace.reduce(events, "bench.round")["idle_gaps"]))
    # 100-200: bench.round alone; 300-400: device-wait covers 70; 500-700: bench.round
    # covers 100 of 200 and publish 40, neither most of it.
    assert gaps == pytest.approx({"bench.round": 100e-9, "device-wait": 100e-9, "unattributed": 200e-9})


NAMES = {"local_fit", "client_reduce", "attention_full", "moe_experts", "rope"}
FIT = "jit(round_step)/while/body/closed_call/local_fit/vmap()/while/body/closed_call"


@pytest.mark.parametrize("path, chain, mark", [
    (f"{FIT}/jvp()/attention_full/dot_general:", ("local_fit", "attention_full"), None),
    # Transform wrappers come off a component, however deep.
    ("jit(loss)/transpose(jvp(local_fit))/while/body/vmap(attention_full)/mul", ("local_fit", "attention_full"), "backward"),
    # What a checkpoint reruns sits inside the backward pass and is told from it.
    (f"{FIT}/transpose(jvp())/checkpoint/rematted_computation/moe_experts/dot_general:", ("local_fit", "moe_experts"), "recomputed"),
    (f"{FIT}/transpose(jvp())/checkpoint/moe_experts/transpose:", ("local_fit", "moe_experts"), "backward"),
    # A path XLA cut short, a bare primitive, nothing at all.
    ("attention_full/reduce_max", ("attention_full",), None),
    ("dynamic_slice:", (), None),
    ("", (), None),
    # Components are compared whole (``make_local_fit.<locals>.local_fit`` is a function's
    # name, ``local_fit_extra`` another scope), and a jitted function is not a scope
    # unless it is asked for by name.
    ("jit(round_step)/make_local_fit.<locals>.local_fit.<locals>.epoch_body/local_fit_extra/add", (), None),
    # Of two operations XLA merged into one instruction the first counts.
    (f"{FIT}/attention_full/squeeze;rope/reshape:", ("local_fit", "attention_full"), None),
])
def test_a_name_path_gives_its_scopes_outermost_first_and_its_pass(path, chain, mark):
    assert trace.scope_chain(path, NAMES) == (chain, mark)


def _rows(events, names=NAMES):
    return {(tuple(chain), kind): seconds for chain, kind, seconds in trace.by_scope(events, names)}


def test_by_scope_credits_self_time_to_the_innermost_scope_and_the_pass():
    """A ``while`` gives its children's time away and keeps its own; an operation with no
    path, or none of the names, lands where the operation that contains it did; one
    outside everything is unscoped."""
    back = f"{FIT}/transpose(jvp())/while"
    events = {"devices": {0: [
        ["while.1", 0, 1000, f"{FIT}/jvp()/while:"],
        ["fusion.1", 0, 300, f"{FIT}/jvp()/while/body/attention_full/dot_general:"],
        ["copy.1", 300, 100, ""],                                # XLA's own: the while's scope
        ["fusion.2", 400, 200, "attention_full/reduce_max"],     # cut short: adds to the while's
        ["fusion.3", 600, 300, f"{FIT}/jvp()/while/body/moe_experts/dot_general:"],
        ["while.2", 1000, 1000, f"{back}:"],
        ["fusion.4", 1000, 400, f"{back}/body/checkpoint/rematted_computation/moe_experts/dot_general:"],
        ["fusion.5", 1400, 300, f"{back}/body/checkpoint/moe_experts/dot_general:"],
        ["copy.2", 1700, 100, "jit(round_step)/while:"],         # an outer loop's path: inherits
        ["fusion.6", 2000, 50, "jit(round_step)/while/body/closed_call/client_reduce/add:"],
        ["copy.3", 2100, 25, "jit(round_step)/while:"],
        ["copy.4", 2200, 25],                                    # a three-field event: no path
    ]}, "host": []}
    fit, attn, experts = ("local_fit",), ("local_fit", "attention_full"), ("local_fit", "moe_experts")
    assert _rows(events) == pytest.approx({
        (fit, "forward"): (100 + 100) * 1e-9,       # while.1's own 100, copy.1
        (attn, "forward"): (300 + 200) * 1e-9,
        (experts, "forward"): 300e-9,
        (fit, "backward"): (200 + 100) * 1e-9,      # while.2's own 200, copy.2
        (experts, "recomputed"): 400e-9,
        (experts, "backward"): 300e-9,
        (("client_reduce",), "forward"): 50e-9,
        ((), "forward"): 50e-9,
    })
    rows = trace.by_scope(events, NAMES)
    assert rows == sorted(rows, key=lambda r: -r[2])
    # The readers' arithmetic: under a scope, directly under it, one pass, everything.
    assert trace.scope_seconds(rows, {"local_fit"}) == pytest.approx(2000e-9)
    assert trace.scope_seconds(rows, {"local_fit"}, innermost=True) == pytest.approx(500e-9)
    assert trace.scope_seconds(rows, {"moe_experts", "attention_full"}, kind="backward") == pytest.approx(300e-9)
    assert trace.scope_seconds(rows, None, kind="recomputed") == pytest.approx(400e-9)
    assert trace.scope_seconds(rows) == pytest.approx(2100e-9)
    assert trace.scope_seconds(rows, {"rope"}) is None and trace.scope_seconds([], None) is None
    table = trace.scope_table(rows)
    assert table["moe_experts"] == pytest.approx({"forward": 300e-9, "recomputed": 400e-9, "backward": 300e-9})
    assert table["unscoped"] == pytest.approx({"forward": 50e-9}) and "rope" not in table


def test_a_container_with_no_path_is_where_its_operations_paths_agree():
    """XLA rebuilds a loop and the new ``while`` carries no metadata: it, and what it holds
    that has no path either, go where the paths of the operations inside it all begin;
    where those disagree above every scope, nowhere."""
    loop = f"{FIT}/jvp()/moe_experts/while/body"
    events = {"devices": {0: [
        ["while.9", 0, 2000, ""],                    # the round's loop: fit and reduce inside
        ["while.1", 0, 1000, ""],
        ["fusion.1", 0, 300, f"{loop}/dot_general:"],
        ["fusion.2", 300, 200, ""],
        ["fusion.3", 500, 400, f"{loop}/closed_call/mul:"],
        ["while.2", 1000, 400, ""],                  # nothing inside says where: the container's
        ["copy.1", 1000, 100, ""],
        ["fusion.4", 1500, 300, "jit(round_step)/while/body/closed_call/client_reduce/add:"],
    ]}, "host": []}
    assert _rows(events) == pytest.approx({
        (("local_fit", "moe_experts"), "forward"): 1000e-9,
        (("client_reduce",), "forward"): 300e-9,
        ((), "forward"): 700e-9,                     # while.9's own 300, while.2 and its copy
    })


def test_by_scope_is_the_mean_over_the_chips_and_tiles_the_busy_time():
    events = {"devices": {
        0: [["while", 0, 400, "jit(f)/local_fit/while:"], ["fusion.1", 100, 100, ""], ["all-reduce.3", 500, 50, "jit(f)/client_reduce/psum:"]],
        1: [["while", 0, 300, "jit(f)/local_fit/while:"], ["all-reduce.3", 500, 150, "jit(f)/client_reduce/psum:"]],
    }, "host": [["bench.round", 0, 1000]]}
    assert _rows(events) == pytest.approx({(("local_fit",), "forward"): 350e-9, (("client_reduce",), "forward"): 100e-9})
    assert sum(_rows(events).values()) == pytest.approx(trace.reduce(events, "bench.round")["busy_s"])


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One protobuf field: a varint for an int, length-delimited for bytes."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_name_paths_walks_the_wire_format_for_the_metadata_tables_alone():
    """A hand-encoded ``XSpace``: two planes, a line of events to step over, the path as a
    string on one event's metadata and as a reference to a stat's name on another's."""
    stat_meta = lambda i, name: _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, name)))
    event_meta = lambda i, name, stats: _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, name) + stats))
    path = b"jit(f)/local_fit/dot_general:"
    ref = b"jit(f)/transpose(jvp(local_fit))/mul:"
    plane = (_field(1, 7) + _field(2, b"/device:TPU:0")
             + _field(3, _field(2, b"XLA Ops") + _field(4, _field(1, 1) + _field(2, 5) + _field(3, 900)) * 50)
             + stat_meta(1, b"flops") + stat_meta(2, b"tf_op") + stat_meta(9, ref)
             + event_meta(1, b"%fusion.1 = f32[8]{0} fusion()", _field(5, _field(1, 1) + _field(4, 12)) + _field(5, _field(1, 2) + _field(5, path)))
             + event_meta(2, b"%fusion.2 = f32[8]{0} fusion()", _field(5, _field(1, 2) + _field(7, 9)))
             + event_meta(3, b"%copy.3 = f32[8]{0} copy()", _field(5, _field(1, 1) + _field(2, b"\0" * 8))))
    other = _field(2, b"/host:CPU") + event_meta(1, b"bench.round", b"")
    raw = _field(1, plane) + _field(1, other) + _field(4, b"a host name")
    assert trace.name_paths(raw) == {
        "/device:TPU:0": {"%fusion.1 = f32[8]{0} fusion()": path.decode(),
                          "%fusion.2 = f32[8]{0} fusion()": ref.decode(),
                          "%copy.3 = f32[8]{0} copy()": ""},
        "/host:CPU": {"bench.round": ""},
    }
    assert trace.name_paths(b"") == {}


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
    assert trace.reduce(events, "bench.round") is None and trace.by_scope(events, NAMES) == []
    # The walk of the file's own bytes finds the planes ProfileData finds.
    with open(path, "rb") as f:
        assert "/host:CPU" in trace.name_paths(f.read())
    assert trace.find_xplane(str(tmp_path / "nothing")) is None


def _sweep_busy(device_events, start, end):
    """Busy time by an independent method: sweep the edges, count the open intervals."""
    edges = sorted([(e[1], 1) for e in device_events] + [(e[1] + e[2], -1) for e in device_events])
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
    assert set(gaps) <= {"bench.round", "round", "cohort-sample", "cohort-gather", "round-keys",
                         "local-train", "dispatch", "device-wait", "aggregate", "client-detail",
                         "publish", "unattributed"}
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - max(
        _sweep_busy(ev, 0, 0) for ev in events["devices"].values()) / 1e9, rel=1e-6)
    # By scope the same events tile the same busy time, whatever names are asked for; an
    # event of three fields (the traces of PR 23) has no path and is in no scope.
    from benchmark import federation

    rows = trace.by_scope(events, federation.scope_metrics(REPO)[1])
    assert sum(seconds for _, _, seconds in rows) == pytest.approx(want_busy, rel=1e-9)
    if all(len(e) == 3 for ev in events["devices"].values() for e in ev):
        assert [(chain, kind) for chain, kind, _ in rows] == [([], "forward")]


WITH_PATHS = [n for n in RECORDED if "scope_ms_per_round" in json.loads(
    gzip.decompress((DATA / n).read_bytes()))["expected"]]


@pytest.mark.parametrize("name", WITH_PATHS)
def test_the_scope_readers_on_a_trace_recorded_with_its_name_paths(name):
    """An expert cell's traced round as ``--keep-trace`` wrote it in PR 37, each device
    event with its name path: every per-scope metric reads what the reduction read on
    the chip for this round (kept beside the events), and leaves out what the cell has not."""
    from benchmark import federation, run

    recorded = json.loads(gzip.decompress((DATA / name).read_bytes()))
    events = {"devices": {int(k): v for k, v in recorded["devices"].items()}, "host": recorded["host"]}
    assert all(len(e) == 4 for ev in events["devices"].values() for e in ev)
    specs, names = federation.scope_metrics(REPO)
    rows = trace.by_scope(events, names)
    ctx = {"scopes": rows, "traced_rounds": recorded["rounds"]}
    want = recorded["expected"]["scope_ms_per_round"]
    assert set(want) <= set(specs)
    for metric, value in want.items():
        got = run.scope_ms_per_round(ctx, specs[metric])
        assert got == (None if value is None else pytest.approx(value, rel=1e-9)), metric
    cell = name[: -len(".trace.json.gz")]
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in manifest["per_layer"]:
        if entry["name"] in want:
            assert (want[entry["name"]] is not None) == (cell in entry["workloads"]), entry["name"]
    # The table tiles the busy time, and the kernels' scopes hold at least the kernels.
    busy_ms = 1000.0 * recorded["expected"]["busy_s"] / recorded["rounds"]
    assert 1000.0 * sum(s for _, _, s in rows) / recorded["rounds"] == pytest.approx(busy_ms, rel=1e-9)
    kernels = sum(s for n, s in trace.reduce(events, "bench.round", top=1000)["device_ops"]
                  if n.startswith("causal_attention_"))
    assert kernels > 0 and want["attention_ms_per_round"] >= 1000.0 * kernels / recorded["rounds"]
    # A run not traced, a trace with no rounds, scopes the trace has not: nothing, no raise.
    spec = specs["attention_ms_per_round"]
    assert run.scope_ms_per_round({**ctx, "scopes": None}, spec) is None
    assert run.scope_ms_per_round({**ctx, "traced_rounds": 0}, spec) is None
    assert run.scope_ms_per_round(ctx, {"scopes": ["no_such_scope"]}) is None


def test_there_is_a_recorded_trace():
    assert RECORDED, "tests/benchmark/data holds no trace recorded on the chip"
    assert WITH_PATHS, "tests/benchmark/data holds no trace recorded with its name paths"
