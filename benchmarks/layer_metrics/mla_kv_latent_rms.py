"""Root mean square of latent attention's key-value latent c_kv before its
norm, mean over the layers held, mean over the window's steps
(`mla_kv_latent_rms` of the `step` records; models/xing4.py). The norm hides
its scale from the keys and values; a latent that grows or collapses shows
here first. None where the program has no such counter (a model without
latent attention, or a program from before the counter)."""


def read(run: dict):
    values = [
        e["mla_kv_latent_rms"] for e in run["window_steps"]
        if "mla_kv_latent_rms" in e]
    return sum(values) / len(values) if values else None
