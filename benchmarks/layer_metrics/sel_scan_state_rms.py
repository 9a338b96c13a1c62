"""Root mean square of the selective scan's state (channels x states) after a
sequence's last position, mean over the Mamba-1 layers held and over the
window's steps (`sel_scan_state_rms` of the `step` records;
telemetry/phases.py). A state that grows from step to step, or collapses to
0, shows here before the loss moves. None where the program has no such
counter (a model without a selective scan, or a program from before the
counter)."""


def read(run: dict):
    values = [
        e["sel_scan_state_rms"] for e in run["window_steps"]
        if "sel_scan_state_rms" in e]
    return sum(values) / len(values) if values else None
