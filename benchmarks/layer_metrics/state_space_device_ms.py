"""Device time a step under the scopes the model declares for its state-space
layers (`ssm_*`, `gmu`), both passes: the traced epoch reduced by the
program's step map, mean over the chips; 0 for a model without such a layer."""

import scope_spans


def read(run: dict):
    return scope_spans.layer_ms(run, "state space")
