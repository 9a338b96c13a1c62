"""What ending an epoch costs the loop: median, over the epochs that end in
the window, of `drain` (the guard's and the health statistics' last reads)
plus `snapshot` (the `epoch` event and the overlap snapshot)."""

import phase_spans


def read(run: dict):
    return phase_spans.median_ms(run, "drain", "snapshot")
