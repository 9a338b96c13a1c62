"""Assignments to a held expert that were not computed, summed over the
window's steps (`moe_dropped` of the `step` records). The expert layer is
dropless, so anything but 0 is a fault. None where the program has no such
counter."""


def read(run: dict):
    counts = [
        e["moe_dropped"] for e in run["window_steps"] if "moe_dropped" in e]
    return sum(counts) if counts else None
