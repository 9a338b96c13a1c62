"""Share (%) of the tokens' expert choices that the selection bias made: of
the T x 4 choices by score + bias, those that the score alone would not have
made, mean over the sparse layers held, mean over the window's steps
(`moe_bias_swap_share` of the `step` records, a share of one there;
models/xing4.py). 0 is a bias that changes nothing; every swap sends a token
to an expert the router scored lower. None where the program has no such
counter (a router without a selection bias, or a program from before the
counter)."""


def read(run: dict):
    values = [
        e["moe_bias_swap_share"] for e in run["window_steps"]
        if "moe_bias_swap_share" in e]
    return 100.0 * sum(values) / len(values) if values else None
