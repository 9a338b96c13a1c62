"""Median `dur_s` of the window's `step` events: the host's time inside the
jitted step's call (argument handling, enqueue; it blocks when the device's
queue is full)."""

import statistics


def read(run: dict):
    durs = [e["dur_s"] for e in run["window_steps"]]
    return statistics.median(durs) * 1e3 if durs else None
