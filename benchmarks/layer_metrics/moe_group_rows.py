"""Rows a group of the experts' grouped product holds: the mean over the
window's steps of the `step` records' counter `moe_load_mean` (tokens a held
expert took, mean over the held experts of the layer whose fullest expert is
fullest; telemetry/phases.py). The grouped product's K and N are the model's
widths; this is its M per group. Where 32 of 256 experts see 8,192 tokens
choosing 8 it starts at about 256 and does not stay there: the router comes
to score the absent experts down, and `laguna-xs2-plain-1chip`'s window
(steps 9 to 136) reads 287, 321, 567 and 962 by epoch, 504 to 534 as its mean
(PERF.md section 6, PR 32); about 2,048 in a cell whose experts carry their
deployed load. None where the program has no such counter (a model without
experts, or a program from before the counter)."""


def read(run: dict):
    rows = [
        e["moe_load_mean"] for e in run["window_steps"]
        if "moe_load_mean" in e]
    return sum(rows) / len(rows) if rows else None
