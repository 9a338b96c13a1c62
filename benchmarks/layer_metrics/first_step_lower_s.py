"""The first step's `lower` span: `jaxpr_to_mlir_module_duration`."""

import setup_spans


def read(run: dict):
    return setup_spans.seconds(run, "lower")
