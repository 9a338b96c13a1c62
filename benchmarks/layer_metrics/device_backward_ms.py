"""Device time a step of the model's instructions on the way back, scoped or
not (`transpose(` in the name stack: recomputation with it). Traced epoch,
mean over the chips."""

import scope_spans


def read(run: dict):
    return scope_spans.sum_ms(run, "backward")
