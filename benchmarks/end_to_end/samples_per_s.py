"""Global samples through `Trainer.train_epoch` in the window over the window's
seconds (host clock, closed by block_until_ready; all steps, all the time)."""


def read(run: dict):
    return len(run["window_steps"]) * run["global_batch"] / run["window_s"]
