"""Device time a step under a `mgwfbp_groupNNNN` scope or of collective kind:
packing, carriers and waits of the gradient exchange and the metrics' own
reduction. Traced epoch, mean over the chips; 0 where the step has no
collective (one chip)."""

import scope_spans


def read(run: dict):
    return scope_spans.exchange(run, "device_ms")
