"""The six per-layer readers of ``RoundMetrics.segments`` on a hand-made ``ctx``: the
mean over the window's rounds in milliseconds, nothing where a round carries no
segments (the program before it had them), and ``host_unattributed_ms`` as the
benchmark's own step less the program's five."""

from types import SimpleNamespace

import pytest

from benchlib import REPO

from benchmark import federation

SEGMENT_OF = {
    "host_prepare_ms": "prepare", "host_dispatch_ms": "dispatch",
    "device_wait_ms": "device_wait", "host_readback_ms": "readback",
    "host_publish_ms": "publish",
}
ROUNDS = [  # (the loop's step_s, what the program said of the round inside it)
    (0.1000, {"prepare": 0.004, "dispatch": 0.002, "device_wait": 0.080,
              "readback": 0.009, "publish": 0.003}),
    (0.1200, {"prepare": 0.006, "dispatch": 0.004, "device_wait": 0.090,
              "readback": 0.011, "publish": 0.005}),
]


def _ctx(rounds):
    return {"rounds": [(step_s, SimpleNamespace(segments=s)) for step_s, s in rounds]}


@pytest.mark.parametrize("name", [*SEGMENT_OF, "host_unattributed_ms"])
def test_segment_reader(name):
    read = federation.load_named(REPO, "layer_metrics", name).read
    if name in SEGMENT_OF:
        expected = 1000.0 * sum(s[SEGMENT_OF[name]] for _, s in ROUNDS) / len(ROUNDS)
    else:
        expected = 1000.0 * sum(step - sum(s.values()) for step, s in ROUNDS) / len(ROUNDS)
    assert read(_ctx(ROUNDS)) == pytest.approx(expected)
    # Nothing to read: an empty window, a program whose RoundMetrics has no such
    # field, a round that carries none (or, FAILED before dispatch, not this one).
    assert read({"rounds": []}) is None
    assert read({"rounds": [(0.1, SimpleNamespace(duration_s=0.09))]}) is None
    assert read(_ctx([ROUNDS[0], (0.1, {})])) is None
    if name not in ("host_prepare_ms", "host_publish_ms", "host_unattributed_ms"):
        assert read(_ctx([ROUNDS[0], (0.1, {"prepare": 0.09, "publish": 0.01})])) is None
