"""Mean of the attention output gate g = sigmoid(u W_g) over the layers held,
their heads and the step's tokens, mean over the window's steps
(`attn_gate_mean` of the `step` records; telemetry/phases.py). 0.5 at the
seeded start; a gate that closes (towards 0) or saturates (towards 1) shows
here before the loss moves. None where the program has no such counter (a
model without the gate, or a program from before the counter)."""


def read(run: dict):
    values = [
        e["attn_gate_mean"] for e in run["window_steps"]
        if "attn_gate_mean" in e]
    return sum(values) / len(values) if values else None
