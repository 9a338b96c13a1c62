"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace here is plain data, so that the same code reduces a `.xplane.pb` the
profiler just wrote and the small recorded trace `tests/benchmark` keeps:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

What the planes and lines of a TPU v5e trace are (looked at by hand, PR 23):
one plane `/device:TPU:<n>` per chip with the lines `XLA Modules` (one event
per executed program), `XLA Ops` (the TensorCore's instruction stream, one
event per HLO op, never overlapping) and `Async XLA Ops` (spans from an async
op's start to its done: collectives in flight, prefetch copies); one plane
`/host:CPU` whose lines are host threads, present only while the host tracer
is on (the benchmark keeps it off: run.py `trace_epoch`). All planes share one
clock, nanoseconds from the start of the session.
"""

from __future__ import annotations

import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
_COLLECTIVES = (
    "all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    "|collective-broadcast"
)
# the HLO opcode, wherever the instruction's name came from (`%psum.4 =
# f32[8] all-reduce(...)` is a collective called psum)
COLLECTIVE_CALL = re.compile(r"[ )](" + _COLLECTIVES + r")(-start|-done)?\(")
COLLECTIVE_NAME = re.compile(r"^(" + _COLLECTIVES + r")(-start|-done)?")

Interval = tuple[float, float]


def load_xplane(path: str) -> dict:
    """Read a `.xplane.pb` with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events
            ]}
            for line in plane.lines
        ]}
        for plane in data.planes
    ]}


def op_name(event_name: str) -> str:
    """`%all-reduce-start.3 = (f32[...]) all-reduce-start(...)` -> `all-reduce-start`:
    the HLO instruction's own name without its `%`, its operands and its
    numeric suffix, so that one op of every step aggregates under one key."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def collective_phase(event_name: str) -> Optional[str]:
    """None for an op that is no collective; else its phase: `""` for a
    synchronous one, `"-start"` or `"-done"` for the halves of an async one.
    Read from the opcode where the event carries the instruction's text, and
    from the instruction's name where it carries the name alone."""
    if " = " in event_name:
        m = COLLECTIVE_CALL.search(event_name.split(" = ", 1)[1])
    else:
        m = COLLECTIVE_NAME.match(op_name(event_name))
    return None if m is None else (m.group(2) or "")


def is_collective(event_name: str) -> bool:
    return collective_phase(event_name) is not None


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Sorted, disjoint intervals covering the same set of points."""
    out: list[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def overlap(a: list[Interval], b: list[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> list[Interval]:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    """The complement of sorted disjoint `busy` inside [lo, hi]."""
    out, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _spans(events: Iterable) -> list[Interval]:
    return [(start, start + dur) for _, start, dur in events]


def step_starts(trace: dict) -> list[float]:
    """Start times (ns) on the first device of every execution of the program
    that took most device time there: the train step."""
    for plane in sorted(trace["planes"], key=lambda p: p["name"]):
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        by_module: dict[str, list] = {}
        for name, start, dur in _line(plane, MODULES_LINE):
            by_module.setdefault(name, []).append((start, dur))
        if not by_module:
            return []
        main = max(by_module, key=lambda k: sum(d for _, d in by_module[k]))
        return sorted(start for start, _ in by_module[main])
    return []


def reduce_trace(
    trace: dict, window: Optional[Interval] = None, top: int = 10,
) -> dict:
    """Per-device busy, compute and collective time inside `window` (ns on
    the trace's clock; the whole trace when None).

    busy       union of every op on `XLA Ops` and every span on `Async XLA
               Ops`: an instruction runs or a transfer is in flight
    compute    union of the `XLA Ops` events that are not collectives
    collective union of the collectives on either line (a `-done` on `XLA
               Ops` is the core waiting for one)
    exposed    collective time during which no compute op runs
    calls      collectives started: sync ops and `-start`s, never `-done`s
    steps      executions of the program that took most device time
    """
    devices = []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        ops = _line(plane, OPS_LINE)
        asyncs = _line(plane, ASYNC_LINE)
        modules = _line(plane, MODULES_LINE)
        if window is None:
            ends = [s + d for _, s, d in ops + asyncs + modules]
            starts = [s for _, s, _ in ops + asyncs + modules]
            lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
        else:
            lo, hi = window
        inside = [e for e in ops if lo <= e[1] < hi]
        inside_async = [e for e in asyncs if lo <= e[1] < hi]
        compute = union(clip(
            _spans(e for e in inside if not is_collective(e[0])), lo, hi))
        collective = union(clip(_spans(
            e for e in inside + inside_async if is_collective(e[0])), lo, hi))
        busy = union(clip(_spans(inside + inside_async), lo, hi))
        by_op: dict[str, float] = {}
        for name, start, dur in inside:
            by_op[op_name(name)] = by_op.get(op_name(name), 0.0) + dur
        by_module: dict[str, list] = {}
        for name, start, dur in modules:
            if lo <= start < hi:
                by_module.setdefault(name, []).append(dur)
        main = max(by_module, key=lambda k: sum(by_module[k]), default=None)
        devices.append({
            "id": int(m.group(2)),
            "window_ns": hi - lo,
            "busy_ns": total(busy),
            "compute_ns": total(compute),
            "collective_ns": total(collective),
            "exposed_collective_ns": total(collective)
            - overlap(collective, compute),
            "collective_calls": sum(
                1 for e in inside if collective_phase(e[0]) in ("", "-start")),
            "steps": len(by_module.get(main, ())),
            "step_module": main,
            "top_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
            "busy": busy,
            "window": (lo, hi),
        })
    return {"devices": sorted(devices, key=lambda d: d["id"])}


def attribute_gaps(
    busy: list[Interval], window: Interval,
    host: dict[str, list[Interval]], top: int = 10,
) -> list[list]:
    """Idle time of one device inside `window`, by what the host was doing:
    every gap's length is split among the host activities that overlap it
    (`host`: name -> intervals on the trace's clock); what no activity covers
    goes to `other`. Returns [[name, ns], ...], longest first."""
    idle = gaps(busy, *window)
    named = {name: union(spans) for name, spans in host.items()}
    out = {name: overlap(idle, spans) for name, spans in named.items()}
    out["other"] = max(total(idle) - sum(out.values()), 0.0)
    return [
        [name, ns] for name, ns in
        sorted(out.items(), key=lambda kv: -kv[1])[:top] if ns > 0
    ]
