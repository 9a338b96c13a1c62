"""Device idle time under the `tail` and `log` spans, per traced step, on
the chip that idled most in the traced epoch."""

import phase_spans


def read(run: dict):
    return phase_spans.idle_under_ms(run, "tail", "log")
