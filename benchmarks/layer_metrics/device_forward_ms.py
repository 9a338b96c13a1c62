"""Device time a step of the model's instructions on the way forward, scoped
or not (no `transpose(` in the name stack). Traced epoch, mean over the chips."""

import scope_spans


def read(run: dict):
    return scope_spans.sum_ms(run, "forward")
