"""Median `health` span: `_note_health_stats`, which stacks the previous
step's statistics on the device and pulls them in one read. Runs only with
telemetry on: it is the instrumentation's own cost."""

import phase_spans


def read(run: dict):
    return phase_spans.median_ms(run, "health")
