"""Sum of the sigmoid scores of the experts a token chose, the denominator
of the router's normaliser: mean over tokens and the sparse layers held, mean
over the window's steps (`moe_score_sum` of the `step` records;
telemetry/phases.py). Between 0 and the experts a token (8); near 0 the
weights s_k / sum are a quotient of small numbers, at 8 every chosen score
has saturated and the router no longer ranks. None where the program has no
such counter (a softmax router, a model without experts, or a program from
before the counter)."""


def read(run: dict):
    values = [
        e["moe_score_sum"] for e in run["window_steps"]
        if "moe_score_sum" in e]
    return sum(values) / len(values) if values else None
