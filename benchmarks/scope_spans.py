"""The traced epoch's device time split by the program's own map of its step.

Since PR 49 the program keeps what it takes to map the compiled step
(`mgwfbp_tpu/profiling.py`: `step_map()` gives instruction -> its `op_name`,
the whole name stack, and its kind, with the scopes the model and the step
declare, each with its layer) and reduces a device trace by that map
(`split_trace`): milliseconds a step by scope and pass, by merge group, the
exchange's carriers and waits, the longest instructions.

`run` hands the readers neither the map nor the trace's events (`align`
reduces the trace and drops them), so both come from beside it: the map from
the program's `profiling.step_map()` (the harness is one process and builds
one Trainer, whose step is the process's last; built at this first request,
after the window has closed and after `setup_s` was taken), the events from
the `.xplane.pb` that `trace_epoch` has just written under `<out>/trace`,
found from the log directory the map carries (`<out>/logs`), read once a
process, clipped to `run["traced"]["window"]`, over `run["traced"]["steps"]`,
a mean over the chips as `step_device_ms` is. The readers under
`layer_metrics/` go through this file, so that where map and events come from
is decided here alone. A program without the map (any commit before PR 49), a
trace without a device plane (the CPU rehearsal) or no trace file gives every
reader nothing to read: it returns None and the metric is left out of the
line.

The whole split is printed once as `[scopes]` phase lines (every scope,
forward and backward, the 25 longest instructions, and what asking cost), so
that a traced run's output holds what a by-hand split of its trace would.
"""

from __future__ import annotations

import glob
import os
import time

# (key, split) of the one trace a process reads
_read: tuple = (None, None)


def _program():
    """(the program's profiling module, its step map) or (None, None)."""
    try:
        from mgwfbp_tpu import profiling
    except ImportError:
        return None, None
    get = getattr(profiling, "step_map", None)
    step_map = get() if get is not None else None
    return (profiling, step_map) if step_map is not None else (None, None)


def trace_file(logdir):
    """The newest `.xplane.pb` beside the log directory, or None."""
    if not logdir:
        return None
    paths = sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(logdir)), "trace", "plugins",
        "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def device_ops(path: str) -> list[list]:
    """One list of `XLA Ops` events ([name, start_ns, duration_ns]) a chip."""
    import trace_reduce

    return [
        events for plane in trace_reduce.load_xplane(path)["planes"]
        if trace_reduce.DEVICE_PLANE.match(plane["name"])
        for events in [trace_reduce._line(plane, trace_reduce.OPS_LINE)]
        if events
    ]


def _split(run: dict) -> tuple:
    """(the program's profiling module, `split_trace` of the traced epoch),
    or (None, None)."""
    global _read
    traced = run.get("traced")
    if not traced or "window" not in traced or not traced.get("steps"):
        return None, None  # no traced epoch, or no device plane to align on
    profiling, step_map = _program()
    path = trace_file(step_map.logdir) if step_map is not None else None
    if path is None:
        return None, None
    key = (path, tuple(traced["window"]), traced["steps"], id(step_map))
    if _read[0] == key:
        return _read[1]
    t0 = time.perf_counter()
    chips = device_ops(path)
    read_s = time.perf_counter() - t0
    out = None
    if chips:
        out = profiling.split_trace(
            [e for events in chips for e in events], step_map,
            tuple(traced["window"]), traced["steps"] * len(chips))
        print("\n".join("[scopes] " + line for line in (
            f"step map of {len(step_map.instructions)} instructions from "
            f"{step_map.hlo_bytes} bytes of compiled text built in "
            f"{step_map.build_s:.3f} s; {os.path.getsize(path)} bytes of "
            f"trace read again in {read_s:.3f} s ({len(chips)} chip(s), "
            f"{traced['steps']} step(s))",
            *profiling.split_lines(out))), flush=True)
    _read = (key, (profiling if out is not None else None, out))
    return _read[1]


def split(run: dict):
    """`profiling.split_trace` of the traced epoch, or None."""
    return _split(run)[1]


def layer_ms(run: dict, *layers: str):
    """Both passes of the scopes the model declares for `layers`; 0 where it
    declares none. None without a split."""
    profiling, out = _split(run)
    return None if out is None else profiling.layer_ms(out, *layers)


def sum_ms(run: dict, name: str):
    """One of `profiling.split_sums`: `unscoped`, `update`, `forward`,
    `backward`, `no_metadata`."""
    profiling, out = _split(run)
    return None if out is None else profiling.split_sums(out)[name]


def exchange(run: dict, name: str):
    """`device_ms`, `wait_ms` or `calls` of the exchange; 0 where the step
    has no collective."""
    out = split(run)
    return None if out is None else out["exchange"][name]
