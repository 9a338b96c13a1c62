"""Host clock around `Trainer(...)`: data set, model init, placement, reducer
(with the backward profile where the policy needs one), loaders."""


def read(run: dict):
    return run["init_s"]
