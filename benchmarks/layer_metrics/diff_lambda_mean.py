"""Mean of the differential attention's lam = exp(lq1 . lk1) - exp(lq2 . lk2)
+ lam0 over the attention and cross layers held, mean over the window's steps
(`diff_lambda_mean` of the `step` records; telemetry/phases.py). It starts
near the mean of the layers' lam0 (0.796 over layers 15, 17, 19); a lam that
falls to 0 has switched the subtraction off, one that runs past 1 subtracts
more than the first map holds. None where the program has no such counter (a
model without the combine, or a program from before the counter)."""


def read(run: dict):
    values = [
        e["diff_lambda_mean"] for e in run["window_steps"]
        if "diff_lambda_mean" in e]
    return sum(values) / len(values) if values else None
