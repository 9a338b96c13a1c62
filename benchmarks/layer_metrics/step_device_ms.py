"""Device busy time per step: the union of device-op intervals in the traced
epoch over its steps, mean over the chips."""

import statistics


def read(run: dict):
    traced = run["traced"]
    if traced is None or not traced["reduced"]["devices"] or not traced["steps"]:
        return None
    busy = statistics.fmean(
        d["busy_ns"] for d in traced["reduced"]["devices"])
    return busy / traced["steps"] / 1e6
