"""Root mean square of the gated delta rule's state (a value head's keys x
values, 128 x 128) after a sequence's last position, mean over the Gated
DeltaNet layers held and over the window's steps (`delta_state_rms` of the
`step` records; telemetry/phases.py). A state that grows from step to step,
or collapses to 0, shows here before the loss moves. None where the program
has no such counter (a model without a delta rule, or a program from before
the counter)."""


def read(run: dict):
    values = [
        e["delta_state_rms"] for e in run["window_steps"]
        if "delta_state_rms" in e]
    return sum(values) / len(values) if values else None
