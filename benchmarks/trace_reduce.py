#!/usr/bin/env python3
"""From a `jax.profiler` trace of the broker process to the device numbers:

    python benchmarks/trace_reduce.py <dir given to start_trace>

prints one JSON object: the traced window, the seconds in which an operation
ran on the device (union of the op intervals, averaged over the device
planes), the sum of the op durations, how many executables were launched,
and the breakdown (longest device ops by name, longest idle gaps by what the
host was doing meanwhile).

What counts as a device op: on a TPU every event of the `XLA Ops` line of a
`/device:TPU:n` plane, and every event of its `XLA Modules` line is one
launch. The CPU backend has no device plane; a rehearsal there reads the
events that carry an `hlo_op` stat on the host plane's XLA client lines, and
counts `hlo_module` runs by their `run_id`. Idle stretches of the device are
split among the outermost events of the host's threads that cover them, so
the breakdown says what the host was doing while the chip waited.
`reduce_events` works on plain tuples so the tests can feed it a recorded
trace.
"""

from __future__ import annotations

import glob
import json
import os
import sys

TOP = 10


def union_ns(intervals: list) -> int:
    """Total length covered by (start, end) intervals."""
    covered, edge = 0, None
    for start, end in sorted(intervals):
        if edge is None or start > edge:
            covered += end - start
            edge = end
        elif end > edge:
            covered += end - edge
            edge = end
    return covered


def gaps(intervals: list, lo: int, hi: int) -> list:
    """(start, end) of the stretches of [lo, hi] no interval covers."""
    out, edge = [], lo
    for start, end in sorted(intervals):
        if start > edge:
            out.append((edge, min(start, hi)))
        edge = max(edge, end)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi))
    return out


NOTHING = "host: nothing traced (the event loop outside JAX, or idle)"


def short(name: str) -> str:
    """`%fusion.1 = u32[...] fusion(...)` -> `fusion.1`: the trace names a
    device op by its whole HLO line."""
    return name.split(" = ")[0].lstrip("%")


def top_level(events: list) -> list:
    """Of one thread's (name, start, dur) events, those no other contains."""
    out, edge = [], -1
    for event in sorted(events, key=lambda e: (e[1], -e[2])):
        if event[1] >= edge:
            out.append(event)
            edge = event[1] + event[2]
    return out


def attribute(idle: list, spans: list) -> dict:
    """Split every idle stretch among the host spans that cover it (each
    instant to the span that started first), the rest to NOTHING."""
    out: dict = {}

    def add(name: str, ns: int) -> None:
        if ns > 0:
            out[name] = out.get(name, 0) + ns

    spans = sorted(spans, key=lambda e: e[1])
    i = 0
    for start, end in idle:
        while i < len(spans) and spans[i][1] + spans[i][2] <= start:
            i += 1
        cursor, j = start, i
        while j < len(spans) and spans[j][1] < end:
            name, s, d = spans[j]
            lo, hi = max(cursor, s), min(end, s + d)
            if hi > lo:
                add(NOTHING, lo - cursor)
                add("host: " + name, hi - lo)
                cursor = hi
            j += 1
        add(NOTHING, end - cursor)
    return out


def reduce_events(planes: dict, host: dict) -> dict:
    """`planes`: device plane name -> {"ops": [(name, start_ns, dur_ns)],
    "launches": n}; `host`: thread name -> [(name, start_ns, dur_ns)].
    All times on the trace's one clock. The traced window runs from the
    first to the last event of either."""
    every = [e for p in planes.values() for e in p["ops"]]
    if not every:
        return {"device_planes": len(planes), "n_ops": 0, "launches": 0,
                "busy_s": 0.0, "window_s": 0.0, "device_op_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    spans = [e for events in host.values() for e in top_level(events)]
    stamps = [e[1] for e in every + spans] + \
        [e[1] + e[2] for e in every + spans]
    lo, hi = min(stamps), max(stamps)
    busy = [union_ns([(s, s + d) for _, s, d in p["ops"]])
            for p in planes.values()]
    by_name: dict = {}
    for name, _, dur in every:
        name = short(name)
        by_name[name] = by_name.get(name, 0) + dur
    first = next(iter(planes.values()))["ops"]
    idle = attribute(gaps([(s, s + d) for _, s, d in first], lo, hi), spans)

    def top(d: dict) -> list:
        return [[k, v / 1e9] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "device_planes": len(planes),
        "n_ops": len(every),
        "launches": sum(p["launches"] for p in planes.values()),
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_op_s": sum(e[2] for e in every) / 1e9,
        "breakdown": {"device_ops": top(by_name), "idle_gaps": top(idle)},
    }


def read_xplane(path: str) -> "tuple[dict, dict]":
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: dict = {}
    host: dict = {}
    cpu_ops: list = []
    cpu_runs: set = set()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            ops, launches = [], 0
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    launches = sum(1 for _ in line.events)
            planes[plane.name] = {"ops": ops, "launches": launches}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    event = (e.name, int(e.start_ns), int(e.duration_ns))
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        cpu_ops.append(event)
                        cpu_runs.add(stats.get("run_id"))
                    elif event[2] > 0:
                        host.setdefault(line.name, []).append(event)
    if not planes and cpu_ops:
        planes["/host:CPU (XLA CPU client, a rehearsal)"] = {
            "ops": cpu_ops, "launches": len(cpu_runs)}
    return planes, host


def main() -> None:
    found = glob.glob(os.path.join(
        sys.argv[1], "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise SystemExit(f"expected one .xplane.pb under {sys.argv[1]}, "
                         f"found {found}")
    planes, host = read_xplane(found[0])
    out = reduce_events(planes, host)
    out["file_bytes"] = os.path.getsize(found[0])
    print(json.dumps(out))


if __name__ == "__main__":
    main()
