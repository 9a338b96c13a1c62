"""What one epoch boundary costs the loop: the median interval between two
step dispatches that lie in different epochs (loader restart, guard drain,
overlap snapshot) less the median interval inside an epoch. The synthetic
train set is cut to a few tens of steps, so boundaries come hundreds of times
as often as on ImageNet and take a share of `samples_per_s` that a real run
does not pay: boundary_ms over (steps an epoch x the median interval)."""

import statistics


def read(run: dict):
    steps = run["window_steps"]
    across, inside = [], []
    for a, b in zip(steps, steps[1:]):
        (across if a["epoch"] != b["epoch"] else inside).append(
            b["start_s"] - a["start_s"])
    if not across or not inside:
        return None
    return (statistics.median(across) - statistics.median(inside)) * 1e3
