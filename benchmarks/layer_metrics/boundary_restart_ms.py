"""What starting an epoch costs the loop: median, over the epochs that start
in the window, of `restart` (train_epoch's entry to the first `next`) plus
that epoch's first `wait` (iterator and pool creation, the first batch with
nothing prefetched), less the median `wait`."""

import statistics

import phase_spans


def read(run: dict):
    starts = [phase_spans.phases(e) for e in run["window_steps"]
              if "restart" in phase_spans.phases(e)]
    if not starts:
        return None
    usual = statistics.median(phase_spans.durations(run, "wait"))
    return (statistics.median(
        p["restart"][1] + p["wait"][1] for p in starts) - usual) * 1e3
