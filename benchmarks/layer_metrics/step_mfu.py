"""Model FLOP/s utilization of the step while the device is busy: the forward
and backward passes' analytic FLOPs of one chip's share of the batch (3 x 2 x
forward MACs, from the reference file's layer shapes; recomputation, BatchNorm
and the optimizer are not counted) over the device-busy time per step, over the
chip's published bf16 peak. Over 105% the count or the time is wrong."""

import statistics

import peaks  # benchmarks/peaks.py: run.py puts its directory on the path


def read(run: dict):
    traced = run["traced"]
    if traced is None or not traced["reduced"]["devices"] or not traced["steps"]:
        return None
    peak = peaks.lookup(run["device_kind"])["bf16_flops_per_s"]
    flops = 3 * 2 * run["forward_macs"] * run["per_device_batch"]
    busy_s = statistics.fmean(
        d["busy_ns"] for d in traced["reduced"]["devices"]
    ) / traced["steps"] / 1e9
    share = 100.0 * flops / busy_s / peak
    if share > 105.0:
        raise RuntimeError(
            f"step_mfu reads {share:.1f}%: the FLOPs are counted too high or "
            "the busy time leaves out part of the step")
    return share
