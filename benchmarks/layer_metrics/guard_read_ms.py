"""Median `guard` span: `_note_guard_flag`, which reads the previous step's
non-finite flag from the device (`float(flag)`) every step."""

import phase_spans


def read(run: dict):
    return phase_spans.median_ms(run, "guard")
