"""Collectives started a step by the compiled text's own kinds: synchronous
ones, `-start`s and the TPU's `async-collective-start` fusions, which carry
no collective opcode. Traced epoch, mean over the chips; 0 on one chip."""

import scope_spans


def read(run: dict):
    return scope_spans.exchange(run, "calls")
