"""What the program's `setup` record says set-up was made of.

Since PR 36 the program keeps what precedes its event stream (the process's
start, the imports, the backend, the whole of `Trainer.__init__`) as spans in
memory and writes them with the first step's parts as one `setup` record
(`mgwfbp_tpu/telemetry/phases.py`):

    {"spans": {"before_init": [start_s, dur_s, "setup"], "init": [...],
               "data": [start_s, dur_s, "init"], ...,
               "first_step": [...], "trace": [start_s, dur_s, "first_step"]},
     "counters": {"programs_compiled": 0, ...}, "origin_wall": ...}

`run` hands the readers nothing of set-up, so the record comes from the
program's own `phases.setup_record()`: the harness is one process and builds
one Trainer, whose record is the process's last. The readers under
`layer_metrics/` go through this file, so that where the record comes from is
decided here alone. A program without the record (any commit before PR 36)
gives every reader nothing to read: it returns None and the metric is left
out of the line.
"""

from __future__ import annotations


def record(run: dict):
    """The process's `setup` record, or None: a program that keeps none, or
    whose last one is a rebuild's (no `setup` root)."""
    del run  # nothing of set-up is in it yet
    try:
        from mgwfbp_tpu.telemetry import phases
    except ImportError:
        return None
    get = getattr(phases, "setup_record", None)
    rec = get() if get is not None else None
    return rec if rec and "setup" in rec.get("spans", {}) else None


def seconds(run: dict, *names: str):
    """Summed durations of the spans `names`; a span that was never entered
    reads 0. None without a record."""
    rec = record(run)
    if rec is None:
        return None
    return sum(rec["spans"][n][1] for n in names if n in rec["spans"])


def self_seconds(run: dict, name: str):
    """The span's duration less its children's."""
    rec = record(run)
    if rec is None or name not in rec["spans"]:
        return None
    spans = rec["spans"]
    return spans[name][1] - sum(
        dur_s for _, dur_s, parent in spans.values() if parent == name)


def counter(run: dict, name: str):
    rec = record(run)
    return None if rec is None else rec["counters"].get(name)
