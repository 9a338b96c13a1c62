"""Share (%) of the held experts' hidden units, over the rows in a group,
that relu left above zero, mean over the `E` layers held and over the
window's steps (`moe_relu2_active` of the `step` records, a share of one
there; models/nemotronh.py). About 50 at seeded weights; units that die pull
it down and with it what the second product of an expert has to multiply,
but a lower share is a sparser expert, not a faster step: no kernel here
skips a zero. None where the program has no such counter (gated experts, or
a program from before the counter)."""


def read(run: dict):
    values = [
        e["moe_relu2_active"] for e in run["window_steps"]
        if "moe_relu2_active" in e]
    return 100.0 * sum(values) / len(values) if values else None
