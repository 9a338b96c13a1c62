"""What the program's `step` records say about the parts of a loop iteration.

Since PR 24 a `step` record of the telemetry stream carries, beside the
dispatch (`start_s`, `dur_s`), the spans of its iteration on the same clock
(`mgwfbp_tpu/telemetry/phases.py`):

    "phases": {"wait": [start_s, dur_s], "place": [...], "guard": [...],
               "health": [...], "tail": [...], "log": [...]}

and `restart` on the first step of an epoch, `drain` and `snapshot` on the
last; and the counters `ready` and `lowered`. The readers
under `layer_metrics/` go through this file, so that whether a record is
written at the end of its iteration or its later spans ride on the next
record is decided here alone. A program without the spans (any commit before
PR 24) gives every reader nothing to read: it returns None and the metric is
left out of the line.
"""

from __future__ import annotations

import statistics

# after the dispatch, in loop order
AFTER = ("guard", "health", "tail", "log")


def phases(step: dict) -> dict:
    """name -> [start_s, dur_s] of one step's iteration; {} without spans."""
    return step.get("phases") or {}


def has_spans(run: dict) -> bool:
    return any(phases(e) for e in run["window_steps"])


def durations(run: dict, *names: str) -> list[float]:
    """Per window step that has at least one of `names`, their summed
    durations in seconds."""
    out = []
    for step in run["window_steps"]:
        have = [phases(step)[n][1] for n in names if n in phases(step)]
        if have:
            out.append(sum(have))
    return out


def median_ms(run: dict, *names: str):
    """Median over the steps that have the phase(s); None without any."""
    durs = durations(run, *names)
    return statistics.median(durs) * 1e3 if durs else None


def mean_per_step_ms(run: dict, *names: str):
    """Sum of the phase(s) over the window's steps, per step; None when the
    records carry no spans at all (a phase that never ran reads 0)."""
    if not has_spans(run):
        return None
    return sum(durations(run, *names)) / len(run["window_steps"]) * 1e3


def intervals_inside_epochs(run: dict) -> list[tuple[dict, dict]]:
    """Successive steps of the window that lie in one epoch."""
    steps = run["window_steps"]
    return [(a, b) for a, b in zip(steps, steps[1:])
            if a["epoch"] == b["epoch"] and b["step"] == a["step"] + 1]


def unaccounted_s(a: dict, b: dict) -> float:
    """The interval from `a`'s dispatch to `b`'s less every span in it:
    `a`'s dispatch and what followed it, `b`'s wait and placement."""
    covered = a["dur_s"]
    covered += sum(phases(a)[n][1] for n in AFTER if n in phases(a))
    covered += sum(phases(b)[n][1] for n in ("wait", "place")
                   if n in phases(b))
    return b["start_s"] - a["start_s"] - covered


def idle_under_ms(run: dict, *names: str):
    """Idle time of the chip that idled most in the traced epoch, while the
    host was inside the phase(s) `names`, per traced step. Spans are put on
    the trace's clock as `align` put the dispatches there: stream clock +
    `tel_offset` is the host's, + `shift_ns` the trace's."""
    import trace_reduce

    traced = run.get("traced")
    if not traced or not traced["reduced"]["devices"] \
            or "shift_ns" not in traced:
        return None
    steps = traced.get("dispatches") or []
    if not any(phases(e) for e in steps):
        return None

    def on_trace(t_s: float) -> float:
        return (t_s + run["tel_offset"]) * 1e9 + traced["shift_ns"]

    spans = trace_reduce.union(
        (on_trace(start), on_trace(start + dur))
        for e in steps for name, (start, dur) in phases(e).items()
        if name in names)
    dev = max(traced["reduced"]["devices"],
              key=lambda d: d["window_ns"] - d["busy_ns"])
    idle = trace_reduce.gaps(dev["busy"], *dev["window"])
    return trace_reduce.overlap(idle, spans) / 1e6 / len(steps)
