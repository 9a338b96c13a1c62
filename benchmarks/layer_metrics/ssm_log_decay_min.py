"""The most negative sum of log-decays over one chunk of the state-space
scan (any head, any layer held), the least over the window's steps
(`ssm_log_decay_min` of the `step` records; telemetry/phases.py). Nearer 0
is better: at about -87 a float32 exp(.) underflows (the head forgets inside
a chunk, which is only information lost), and a form of the scan that
divides by a decay overflows long before. None where the program has no such
counter."""


def read(run: dict):
    values = [
        e["ssm_log_decay_min"] for e in run["window_steps"]
        if "ssm_log_decay_min" in e]
    return min(values) if values else None
