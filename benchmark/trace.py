"""From the profiler's trace to numbers: device busy time, collectives, the operations
that took most time, and the longest idle gaps named by what the host was doing.

``load`` reads an ``.xplane.pb`` with nothing but JAX and the standard library into
plain lists; ``reduce`` and ``by_scope`` work on those lists alone, so they can be checked
against a recorded trace (``tests/benchmark/data``).  Nothing here knows the benchmark's
files or a run's ``ctx``: which scopes are asked for, and which make a metric, is handed in.

What is read: on each device plane (``/device:TPU:<n>``) the line ``XLA Ops``, whose
events are the operations as the device ran them, nested where an operation (a
``while``, a fusion) contains others; on the host plane every event whose name is one
of the annotations the program's spans and the benchmark's loop write
(``jax.profiler.TraceAnnotation``).  A device operation's NAME PATH, the nest of
``jax.named_scope``s and transforms it was traced under
(``jit(round_step)/while/body/closed_call/local_fit/vmap()/.../dot_general``), is not on
the event but in its plane's table of event metadata, as the stat ``tf_op``;
``ProfileData`` shows an event's own stats and not its metadata's, so ``name_paths``
walks the file's wire format for those tables alone.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
PATH_STAT = "tf_op"
#: A transform's wrapper around a path component: ``jvp(x)``, ``transpose(jvp(x))``, ``vmap()``.
WRAPPER = re.compile(r"^\w+\((.*)\)$")
#: The passes of a training step, told by a path's markers: what ``jax.checkpoint`` reruns
#: in the backward pass, the backward pass proper, and everything else.
FORWARD, RECOMPUTED, BACKWARD = PASSES = ("forward", "recomputed", "backward")
UNSCOPED = "unscoped"


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, host_names: set[str]) -> dict:
    """``{"devices": {n: [[name, start_ns, dur_ns, name path], ...]}, "host": [[name,
    start_ns, dur_ns], ...]}`` — device operations per chip, each with the name path its
    metadata carries (empty where it has none), and the host annotations in
    ``host_names``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    with open(path, "rb") as f:
        paths = name_paths(f.read())
    devices: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    of = paths.get(plane.name, {})
                    devices[int(m.group(1))] = [
                        [short_name(e.name), int(e.start_ns), int(e.duration_ns), of.get(e.name, "")]
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name in host_names
                )
    return {"devices": devices, "host": host}


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint's number, or the bytes
    of a length-delimited or fixed-width field (a ``memoryview`` slice, nothing copied)."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} in an .xplane.pb")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def name_paths(raw: bytes) -> dict[str, dict[str, str]]:
    """``{plane name: {event name: name path}}`` from the bytes of an ``.xplane.pb``: the
    ``tf_op`` stat of every entry of each plane's ``event_metadata`` table
    (tensorflow/tsl ``xplane.proto``: ``XSpace.planes`` = 1; ``XPlane.name`` = 2,
    ``.event_metadata`` = 4, ``.stat_metadata`` = 5, both maps with the entry's value at
    2; ``XEventMetadata.name`` = 2, ``.stats`` = 5; ``XStatMetadata.id`` = 1, ``.name`` =
    2; ``XStat.metadata_id`` = 1, ``.str_value`` = 5, ``.ref_value`` = 7, a reference to a
    stat metadata's name).  The lines, which hold the events and nearly all of the file,
    are stepped over by their length.  An event's name is its instruction's full text,
    so it finds its path by name."""
    text = lambda b: bytes(b).decode("utf-8", "replace")
    out: dict[str, dict[str, str]] = {}
    for number, plane in _fields(memoryview(raw)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = text(value)
            elif number == 4:
                events += [v for n, v in _fields(value) if n == 2]
            elif number == 5:
                for n, entry in _fields(value):
                    if n == 2:
                        stat = dict(_fields(entry))
                        stat_names[stat.get(1)] = text(stat.get(2, b""))
        table = out.setdefault(name, {})
        for event in events:
            event_name, found = "", ""
            for number, value in _fields(event):
                if number == 2:
                    event_name = text(value)
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == PATH_STAT:
                        found = text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
            table[event_name] = found
    return out


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


def nesting(events: list) -> tuple[list, list[int]]:
    """``(the events by start, a container before what it holds, each one's container)``:
    the index in that order of the innermost operation still running when the event
    starts, ``-1`` where none is."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    parent, open_ = [-1] * len(ordered), []
    for i, e in enumerate(ordered):
        while open_ and ordered[open_[-1]][1] + ordered[open_[-1]][2] <= e[1]:
            open_.pop()
        if open_:
            parent[i] = open_[-1]
        open_.append(i)
    return ordered, parent


def self_times(events: list) -> dict[str, int]:
    """Nanoseconds by operation name, a nested operation's time taken out of the one
    that contains it."""
    ordered, parent = nesting(events)
    own = [e[2] for e in ordered]
    for i, p in enumerate(parent):
        if p >= 0:
            own[p] -= ordered[i][2]
    totals: dict[str, int] = {}
    for e, ns in zip(ordered, own):
        totals[e[0]] = totals.get(e[0], 0) + ns
    return totals


def _plain(path: str) -> str:
    """A name path as the program wrote it: of two joined by ``;`` (one instruction made
    of two operations) the first, without the ``:<type>`` the profiler appends."""
    return path.split(";")[0].rsplit(":", 1)[0]


def scope_chain(path: str, names: frozenset[str] | set[str]) -> tuple[tuple[str, ...], str | None]:
    """``(the components of a name path that are among ``names``, outermost first, the
    pass the path's markers tell or None)``.  A component is compared with its transform
    wrappers taken off (``transpose(jvp(local_fit))`` is ``local_fit``)."""
    path = _plain(path)
    chain: list[str] = []
    for part in path.split("/"):
        while (m := WRAPPER.match(part)):
            part = m.group(1)
        if part in names and part not in chain:
            chain.append(part)
    mark = (RECOMPUTED if "rematted_computation" in path
            else BACKWARD if "transpose(" in path else None)
    return tuple(chain), mark


def _common(a: str | None, b: str) -> str:
    """The components two name paths start with alike (``None``: no path seen yet)."""
    if a is None:
        return b
    parts = []
    for x, y in zip(a.split("/"), b.split("/")):
        if x != y:
            break
        parts.append(x)
    return "/".join(parts)


def by_scope(events: dict, names) -> list[list]:
    """``[[scopes, pass, seconds], ...]``, longest first: the self time of the device
    operations (``self_times``' rule: an operation that contains others gives their time
    away), mean over the chips, by the nest of ``names`` (``jax.named_scope``s of the
    program, outermost first; empty: in none) each ran under and by its pass.  The rows
    tile the device's busy time: every nanosecond an operation ran is in exactly one.

    An operation starts from what the operation that contains it in time (the ``while``
    it runs in) was found to be, and its own name path adds to that: scopes the path
    names and the container's lack, and the pass its markers tell.  So an operation XLA
    made itself, whose path is the loop's, a bare primitive, or cut short
    (``attention_full/reduce_max``), still lands in the scope it ran in.  A container
    with no path of its own (a ``while`` XLA rebuilt) is where its operations' paths
    agree: it takes the components they all start with.  Pass: ``rematted_computation``
    in the path is what a checkpoint reruns, else ``transpose(`` is the backward pass,
    else the container's, else forward."""
    names = frozenset(names)
    known: dict[str, tuple] = {}
    totals: dict[tuple, int] = {}
    chips = 0
    for device in events["devices"].values():
        if not device:
            continue
        chips += 1
        ordered, parent = nesting(device)
        paths = [_plain(e[3]) if len(e) > 3 else "" for e in ordered]
        # From the last to the first: what a path-less container's operations agree on.
        agreed: list[str | None] = [None] * len(ordered)
        for i in range(len(ordered) - 1, -1, -1):
            if not paths[i] and agreed[i]:
                paths[i] = agreed[i]
            if paths[i] and parent[i] >= 0 and not paths[parent[i]]:
                agreed[parent[i]] = _common(agreed[parent[i]], paths[i])
        found: list[tuple] = []  # (scopes, pass) of each operation, containers resolved first
        for i, e in enumerate(ordered):
            if paths[i] not in known:
                known[paths[i]] = scope_chain(paths[i], names)
            scopes, kind = known[paths[i]]
            if parent[i] >= 0:
                outer, outer_kind = found[parent[i]]
                scopes = outer + tuple(n for n in scopes if n not in outer)
                kind = kind or outer_kind
                totals[outer, outer_kind] -= e[2]
            found.append((scopes, kind or FORWARD))
            totals[found[i]] = totals.get(found[i], 0) + e[2]
    return [[list(scopes), kind, ns / max(chips, 1) / 1e9]
            for (scopes, kind), ns in sorted(totals.items(), key=lambda kv: -kv[1]) if ns > 0]


def scope_seconds(rows: list[list], scopes=None, kind: str | None = None,
                  innermost: bool = False) -> float | None:
    """Seconds of the ``by_scope`` rows that ran under one of ``scopes`` (``innermost``:
    directly under it, inside no further scope; ``scopes`` None: every row), in pass
    ``kind`` (None: every pass).  ``None`` where no row is asked for: nothing to read."""
    def asked(chain: list[str], row_kind: str) -> bool:
        if kind is not None and row_kind != kind:
            return False
        return scopes is None or any(n in scopes for n in (chain[-1:] if innermost else chain))

    found = [seconds for chain, row_kind, seconds in rows if asked(chain, row_kind)]
    return sum(found) if found else None


def scope_table(rows: list[list]) -> dict[str, dict[str, float]]:
    """``{innermost scope or "unscoped": {pass: seconds}}`` of the ``by_scope`` rows."""
    table: dict[str, dict[str, float]] = {}
    for chain, kind, seconds in rows:
        row = table.setdefault(chain[-1] if chain else UNSCOPED, {})
        row[kind] = row.get(kind, 0.0) + seconds
    return table


def longest_scopes(rows: list[list], top: int = 10) -> list[list]:
    """``[["<scope>.<pass>", seconds], ...]``: the ``top`` longest entries of
    ``scope_table``, seconds over the traced rounds as ``reduce``'s ``device_ops`` has them."""
    flat = [[f"{scope}.{kind}", seconds] for scope, row in scope_table(rows).items()
            for kind, seconds in row.items()]
    return sorted(flat, key=lambda entry: -entry[1])[:top]


def reduce(events: dict, window_span: str, top: int = 10) -> dict | None:
    """Busy seconds (mean over the chips), the traced window (from the first
    ``window_span`` annotation or device operation to the last),
    collective seconds on the busiest chip, the ``top`` operations by self time (mean
    over the chips) and the ``top`` kinds of idle gap by total time, each gap named by
    the innermost host annotation that covers more than half of it.  ``None`` where no
    device operation was recorded."""
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
        merged = merge([(e[1], e[1] + e[2]) for e in ev])
        busy.append(sum(e - s for s, e in merged))
        own = self_times(ev)
        coll = {n: t for n, t in own.items() if COLLECTIVE.search(n)}
        collective.append(sum(coll.values()))
        collective_events += sum(1 for e in ev if COLLECTIVE.search(e[0]))
        for n, t in own.items():
            ops[n] = ops.get(n, 0) + t
        if busy[-1] == max(busy):
            edges = [[start, start]] + merged + [[end, end]]
            gaps_of_busiest = [[a[1], b[0]] for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    host = sorted(events["host"], key=lambda e: e[2])  # innermost (shortest) first
    gap_totals: dict[str, int] = {}
    for s, e in gaps_of_busiest:
        name = next((n for n, hs, hd in host
                     if 2 * (min(e, hs + hd) - max(s, hs)) > e - s), "unattributed")
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
