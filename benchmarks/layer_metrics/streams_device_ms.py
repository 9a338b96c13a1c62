"""Device time a step under the scopes the model declares for its residual
streams (`mhc_*`), both passes: the traced epoch reduced by the program's
step map, mean over the chips."""

import scope_spans


def read(run: dict):
    return scope_spans.layer_ms(run, "residual streams")
