"""Mean of the shared expert's gate sigmoid(u . w_s), one scalar a token, over
the step's tokens and the layers held, mean over the window's steps
(`shared_gate_mean` of the `step` records; telemetry/phases.py). A half at the
seeded start; a gate that closes (towards 0) takes the shared expert out of
every token, one that saturates (towards 1) leaves it ungated. None where the
program has no such counter (a model without the gate, or a program from
before the counter)."""


def read(run: dict):
    values = [
        e["shared_gate_mean"] for e in run["window_steps"]
        if "shared_gate_mean" in e]
    return sum(values) / len(values) if values else None
