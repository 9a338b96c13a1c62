"""Peak device memory on the fullest chip (run.py `device_peak_bytes`: arrays
plus the runtime's reservation for the step's scratch), read after the window
and before the reference runs."""


def read(run: dict):
    return run["peak_bytes"] / 2**30
