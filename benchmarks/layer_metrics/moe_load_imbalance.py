"""Tokens on the fullest held expert over tokens on the average held expert
(`moe_load_max` / `moe_load_mean` of the `step` records, both of the layer
whose fullest expert is the fullest), mean over the window's steps. 1 is an
even load; the grouped product's time follows the sum, the four-chip
exchange's will follow the fullest. None where the program has no such
counters."""


def read(run: dict):
    ratios = [
        e["moe_load_max"] / e["moe_load_mean"] for e in run["window_steps"]
        if e.get("moe_load_mean")
    ]
    return sum(ratios) / len(ratios) if ratios else None
