"""The constructor's `reducer` span: the backward (and forward) profile, the
merge solver and the bucket plan. About 0 where no reducer is built (one
device, or policy none)."""

import setup_spans


def read(run: dict):
    return setup_spans.seconds(run, "reducer")
