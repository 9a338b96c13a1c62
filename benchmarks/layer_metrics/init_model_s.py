"""The constructor's `model` and `optimizer` spans: the module built, the
optimizer, the train state initialised and placed on the mesh."""

import setup_spans


def read(run: dict):
    return setup_spans.seconds(run, "model", "optimizer")
