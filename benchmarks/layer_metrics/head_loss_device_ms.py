"""Device time a step under the scopes `lm_head` and `loss`, both passes: the
traced epoch reduced by the program's step map, mean over the chips; 0 for a
model that declares neither (the image models)."""

import scope_spans


def read(run: dict):
    return scope_spans.layer_ms(run, "head and loss")
