"""Process start to the start of the measured window (JAX start-up, data set,
Trainer, first step with its compile or cache load, warm-up epoch), less the
seconds the correctness check spent copying parameters to the host."""


def read(run: dict):
    return run["setup_s"]
