"""1 - busy union over the traced epoch, on the chip that idled most."""


def read(run: dict):
    traced = run["traced"]
    if traced is None or not traced["reduced"]["devices"]:
        return None
    return 100.0 * max(
        1.0 - d["busy_ns"] / d["window_ns"]
        for d in traced["reduced"]["devices"])
