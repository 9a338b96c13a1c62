"""Median, over the iterations inside an epoch, of the interval between two
dispatches less every span in it: what the loop does that no span covers
(fault-plan lookups, watchdog beats, the record's own write, the spans'
clock reads). Small against the interval, or the spans miss something."""

import statistics

import phase_spans


def read(run: dict):
    if not phase_spans.has_spans(run):
        return None
    left = [phase_spans.unaccounted_s(a, b)
            for a, b in phase_spans.intervals_inside_epochs(run)]
    return statistics.median(left) * 1e3 if left else None
