"""Root mean square of the gated memory m . silu(u W_g) that a gated memory
unit hands its output projection, mean over the GMU layers held and over the
window's steps (`gmu_gate_rms` of the `step` records; telemetry/phases.py).
0 where the last Mamba layer's memory does not reach the unit: the layer then
adds nothing to the residual stream and the loss alone would not say so. None
where the program has no such counter (a model without the unit, or a program
from before the counter)."""


def read(run: dict):
    values = [
        e["gmu_gate_rms"] for e in run["window_steps"] if "gmu_gate_rms" in e]
    return sum(values) / len(values) if values else None
