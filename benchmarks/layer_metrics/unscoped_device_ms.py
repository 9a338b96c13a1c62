"""Device time a step of the instructions no declared scope holds:
`(model, no scope)` (under autodiff: norms, residual adds, the embedding; the
whole model where it declares no scope, as the image models) and
`(no metadata)`. Traced epoch, mean over the chips."""

import scope_spans


def read(run: dict):
    return scope_spans.sum_ms(run, "unscoped")
