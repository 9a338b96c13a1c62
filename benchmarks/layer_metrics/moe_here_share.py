"""Share of a step's (token, expert) assignments that landed on experts held
here, in per cent: the window's mean of the `step` records' counter
`moe_here` (mean over the held layers; telemetry/phases.py). With 16 of 64
experts held and a router that spreads its choices evenly it reads 25. None
where the program has no such counter (a model without experts, or a program
from before the counter)."""


def read(run: dict):
    shares = [e["moe_here"] for e in run["window_steps"] if "moe_here" in e]
    return 100.0 * sum(shares) / len(shares) if shares else None
