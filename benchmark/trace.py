"""From the profiler's trace to numbers: device busy time, collectives, the operations
that took most time, and the longest idle gaps named by what the host was doing.

``load`` reads an ``.xplane.pb`` with nothing but JAX into plain lists;
``reduce`` works on those lists alone, so it can be checked against a recorded trace
(``tests/benchmark/data``).

What is read: on each device plane (``/device:TPU:<n>``) the line ``XLA Ops``, whose
events are the operations as the device ran them, nested where an operation (a
``while``, a fusion) contains others; on the host plane every event whose name is one
of the annotations the program's spans and the benchmark's loop write
(``jax.profiler.TraceAnnotation``).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, host_names: set[str]) -> dict:
    """``{"devices": {n: [[name, start_ns, dur_ns], ...]}, "host": [[name, start_ns,
    dur_ns], ...]}`` — device operations per chip, and the host annotations in
    ``host_names``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        [short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name in host_names
                )
    return {"devices": devices, "host": host}


def short_name(hlo: str) -> str:
    """``%fusion.347 = (bf16[125,32]{1,0:T(8,128)}, ...) fusion(...)`` ->
    ``fusion.347 bf16[125,32] bf16[64,26,26,125,32]``: the instruction's name and the
    first two shapes of its text, which is as much as a ledger line can carry."""
    name, _, rest = hlo.partition(" = ")
    shapes = re.findall(r"\b[a-z]\w*\[[\d,]*\]", rest)[:2]
    return " ".join([name.lstrip("%")] + shapes)


def merge(intervals: list[tuple[int, int]]) -> list[list[int]]:
    """Union of ``(start, end)`` intervals as a sorted list of disjoint ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events: list) -> dict[str, int]:
    """Nanoseconds by operation name, a nested operation's time taken out of the one
    that contains it."""
    totals: dict[str, int] = {}
    stack: list[list] = []  # [name, end, self]

    def close(until: int) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return totals


def reduce(events: dict, window_span: str, top: int = 10) -> dict | None:
    """Busy seconds (mean over the chips), the traced window (from the first
    ``window_span`` annotation or device operation to the last),
    collective seconds on the busiest chip, the ``top`` operations by self time (mean
    over the chips) and the ``top`` kinds of idle gap by total time, each gap named by
    the innermost host annotation that covers its middle.  ``None`` where no device
    operation was recorded."""
    devices = {n: ev for n, ev in events["devices"].items() if ev}
    if not devices:
        return None
    spans = [e for e in events["host"] if e[0] == window_span]
    first = min(e[1] for ev in devices.values() for e in ev)
    last = max(e[1] + e[2] for ev in devices.values() for e in ev)
    start = min([first] + [s for _, s, _ in spans])
    end = max([last] + [s + d for _, s, d in spans])
    busy, collective, collective_events, ops = [], [], 0, {}
    gaps_of_busiest: list[list[int]] = []
    for ev in devices.values():
        merged = merge([(s, s + d) for _, s, d in ev])
        busy.append(sum(e - s for s, e in merged))
        own = self_times(ev)
        coll = {n: t for n, t in own.items() if COLLECTIVE.search(n)}
        collective.append(sum(coll.values()))
        collective_events += sum(1 for n, _, _ in ev if COLLECTIVE.search(n))
        for n, t in own.items():
            ops[n] = ops.get(n, 0) + t
        if busy[-1] == max(busy):
            edges = [[start, start]] + merged + [[end, end]]
            gaps_of_busiest = [[a[1], b[0]] for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    host = sorted(events["host"], key=lambda e: e[2])  # innermost (shortest) first
    gap_totals: dict[str, int] = {}
    for s, e in gaps_of_busiest:
        mid = (s + e) // 2
        name = next((n for n, hs, hd in host if hs <= mid < hs + hd), "unattributed")
        gap_totals[name] = gap_totals.get(name, 0) + (e - s)
    n = len(devices)
    rank = lambda d, scale: [
        [k, v / scale] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
    ]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (end - start) / 1e9,
        "collective_s": max(collective) / 1e9,
        "collective_events": collective_events,
        "device_ops": rank(ops, 1e9 * n),
        "idle_gaps": rank(gap_totals, 1e9),
    }
