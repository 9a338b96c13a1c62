"""Mean of the delta rule's write gate beta = sigmoid(b) over the step's
tokens, the value heads and the Gated DeltaNet layers held, mean over the
window's steps (`delta_beta_mean` of the `step` records;
telemetry/phases.py). About a half at the seeded start; 0 or 1 says the gate
is not wired (nothing is written, or nothing of the erasure is scaled). None
where the program has no such counter (a model without a delta rule, or a
program from before the counter)."""


def read(run: dict):
    values = [
        e["delta_beta_mean"] for e in run["window_steps"]
        if "delta_beta_mean" in e]
    return sum(values) / len(values) if values else None
