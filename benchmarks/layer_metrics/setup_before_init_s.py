"""The `before_init` span of the `setup` record: the process's start (as the
OS knows it) to `Trainer.__init__`'s entry. Interpreter, imports, the
backend's start and the harness's own preparation."""

import setup_spans


def read(run: dict):
    return setup_spans.seconds(run, "before_init")
