"""Mean time per step the loop waited in the train loader's `__next__`, over
the steps of the window (the harness's timing proxy, traced run only)."""


def read(run: dict):
    waits = run["waits"][-len(run["window_steps"]):]
    if not waits or not run["window_steps"]:
        return None
    return sum(b - a for a, b in waits) / len(waits) * 1e3
