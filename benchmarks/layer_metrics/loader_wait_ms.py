"""Mean time per step of the window the loop was blocked in the train
loader's `__next__`: the program's own `wait` span (what `input_wait_ms`
proxies from outside, in the traced run only)."""

import phase_spans


def read(run: dict):
    return phase_spans.mean_per_step_ms(run, "wait")
