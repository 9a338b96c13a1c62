"""Device time a step under the scope `mlp` (the dense gated MLPs), both
passes: the traced epoch reduced by the program's step map, mean over the
chips."""

import scope_spans


def read(run: dict):
    return scope_spans.layer_ms(run, "mlp")
