"""Mean, over the window's steps, of the `ready` counter: batches the
loader's prefetch pool held finished when the loop asked for the next one
(the fewest over a step's micro-batches). Under 1 the pool is behind and the
loop waits for it (`loader_wait_ms`); at `workers + depth` less one it is as
far ahead as it may run. None where no pool runs."""


def read(run: dict):
    counts = [e["ready"] for e in run["window_steps"] if "ready" in e]
    return sum(counts) / len(counts) if counts else None
