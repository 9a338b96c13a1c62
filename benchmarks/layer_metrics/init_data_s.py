"""The constructor's `data` span: `_build_loaders` (the data sets drawn or
read, the loaders, the prefetch pool's start)."""

import setup_spans


def read(run: dict):
    return setup_spans.seconds(run, "data")
