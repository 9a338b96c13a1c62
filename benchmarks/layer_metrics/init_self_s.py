"""The constructor's `init` span less its children: what no span inside
`Trainer.__init__` holds."""

import setup_spans


def read(run: dict):
    return setup_spans.self_seconds(run, "init")
