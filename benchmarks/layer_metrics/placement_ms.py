"""Median `place` span of the window's steps: `_to_model_batch`,
`_stack_micro`, `_globalize` (host to device, and on several chips the split
over the data axis)."""

import phase_spans


def read(run: dict):
    return phase_spans.median_ms(run, "place")
