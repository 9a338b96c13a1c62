"""`dur_s` of the first `step` event: trace, lower, compile or cache load."""


def read(run: dict):
    return run["first_step_s"]
