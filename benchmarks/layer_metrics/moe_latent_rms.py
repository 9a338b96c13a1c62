"""Root mean square of the latent the routed experts read (a token's hidden
state through the down projection, 4,096 -> 1,024), mean over the `E` layers
held and over the window's steps (`moe_latent_rms` of the `step` records;
models/nemotronh.py). A latent that collapses to 0 starves every expert at
once, one that grows squares into relu^2's output, and either shows here
before the loss moves; neither direction means a faster step. None where the
program has no such counter (a model whose experts read the hidden state
itself, or a program from before the counter)."""


def read(run: dict):
    values = [
        e["moe_latent_rms"] for e in run["window_steps"]
        if "moe_latent_rms" in e]
    return sum(values) / len(values) if values else None
