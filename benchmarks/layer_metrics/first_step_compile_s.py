"""The first step's `compile` span: `backend_compile_duration`, which is the
cache key and the load on a hit, the compile and the cache write on a miss."""

import setup_spans


def read(run: dict):
    return setup_spans.seconds(run, "compile")
