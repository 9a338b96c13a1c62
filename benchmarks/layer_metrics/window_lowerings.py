"""Programs lowered while the window's steps ran: the sum of the records'
`lowered` counter (any new program, persistent-cache hit or not). Expected
0: every shape is warmed up before the window."""


def read(run: dict):
    counts = [e["lowered"] for e in run["window_steps"] if "lowered" in e]
    return sum(counts) if counts else None
