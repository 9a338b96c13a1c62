"""Mean per step of `tail` (step checkpoint, async-save poll, fault hooks,
preemption agreement, straggler / drift / profile probes) plus `log` (the
metrics pull every tenth step)."""

import phase_spans


def read(run: dict):
    return phase_spans.mean_per_step_ms(run, "tail", "log")
