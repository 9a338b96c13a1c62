"""`programs_compiled` of the `setup` record: backend compiles during set-up
that the persistent cache did not hold and would. 0 on a warm start."""

import setup_spans


def read(run: dict):
    return setup_spans.counter(run, "programs_compiled")
