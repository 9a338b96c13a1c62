"""The first step's `trace` span: the step program's `jaxpr_trace_duration`,
every trace nested in it included."""

import setup_spans


def read(run: dict):
    return setup_spans.seconds(run, "trace")
