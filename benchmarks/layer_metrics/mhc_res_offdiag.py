"""How much the residual streams mix: the mass of a row of H_res off its
diagonal, mean over rows, tokens and the sub-layers held, mean over the
window's steps (`mhc_res_offdiag` of the `step` records; models/xing4.py). 0
is the plain residual (every stream carried to itself), 0.75 four streams
fully mixed. None where the program has no such counter (a model with one
residual stream, or a program from before the counter)."""


def read(run: dict):
    values = [
        e["mhc_res_offdiag"] for e in run["window_steps"]
        if "mhc_res_offdiag" in e]
    return sum(values) / len(values) if values else None
