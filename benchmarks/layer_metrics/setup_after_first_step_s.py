"""`setup_s` less the spans `before_init`, `init` and `first_step`: the warm-up
epoch's other steps and the harness's own work between them."""

import setup_spans


def read(run: dict):
    spanned = setup_spans.seconds(run, "before_init", "init", "first_step")
    return None if spanned is None else run["setup_s"] - spanned
