"""Collective ops started per step on one chip, counted in the traced epoch
(sync collectives and `-start`s of async ones)."""


def read(run: dict):
    traced = run["traced"]
    if traced is None or run["chips"] < 2 or not traced["steps"]:
        return None
    devices = traced["reduced"]["devices"]
    if not devices or not any(d["collective_calls"] for d in devices):
        return None
    return devices[0]["collective_calls"] / traced["steps"]
