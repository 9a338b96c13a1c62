"""95th percentile of the interval between successive step dispatches in the
window (`start_s` of the program's own `step` events; the guard reads step
i-1's flag at step i, so the dispatch cadence is the completion cadence one
step late). Epoch boundaries are included as they come. Linear interpolation
over all intervals.

Not an end-to-end metric: with epochs of 16 or 32 steps more than a
twentieth of the intervals cross an epoch boundary or refill the prefetch
queue after one, so this reads the boundary's after-effect (PERF.md,
Findings, PR 23), which real ImageNet has once in 5,000 steps."""

import math


def read(run: dict):
    starts = [e["start_s"] for e in run["window_steps"]]
    gaps = sorted(b - a for a, b in zip(starts, starts[1:]))
    if not gaps:
        return None
    pos = 0.95 * (len(gaps) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(gaps) - 1)
    return (gaps[lo] + (gaps[hi] - gaps[lo]) * (pos - lo)) * 1e3
