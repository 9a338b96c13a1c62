"""How far twenty Sinkhorn iterations leave the residual mapping from doubly
stochastic: the largest |row or column sum of H_res - 1| over a step's tokens,
mean over the sub-layers held, mean over the window's steps (`mhc_res_gap` of
the `step` records; models/xing4.py). 0 is a mapping that conserves the
streams' mass exactly; it rises when the exponents spread (a large alpha_res)
and the iteration has not converged. None where the program has no such
counter (a model with one residual stream, or a program from before the
counter)."""


def read(run: dict):
    values = [
        e["mhc_res_gap"] for e in run["window_steps"] if "mhc_res_gap" in e]
    return sum(values) / len(values) if values else None
