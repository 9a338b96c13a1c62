"""Of `exchange_device_ms`, the `-done` halves of the asynchronous collectives
and the synchronous collectives: the core stands in them. Traced epoch, mean
over the chips; 0 on one chip."""

import scope_spans


def read(run: dict):
    return scope_spans.exchange(run, "wait_ms")
