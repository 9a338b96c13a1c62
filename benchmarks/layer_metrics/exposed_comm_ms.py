"""Per step, the time in which a collective runs on a device and no compute op
does (traced epoch, worst chip). Nothing to read on one chip."""


def read(run: dict):
    traced = run["traced"]
    if traced is None or run["chips"] < 2 or not traced["steps"]:
        return None
    devices = traced["reduced"]["devices"]
    if not devices or not any(d["collective_calls"] for d in devices):
        return None
    return max(
        d["exposed_collective_ns"] for d in devices) / traced["steps"] / 1e6
