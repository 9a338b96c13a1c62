"""Measure comm/compute overlap of the production train step from a
jax.profiler trace (VERDICT r2 task #3: prove the overlap).

Runs the jitted MG-WFBP train step under `jax.profiler.trace`, then parses
the captured Chrome-trace JSON (plugins/profile/<run>/*.trace.json.gz) and
reports, per collective op, how much device compute executed concurrently
with it. This is the TPU analogue of the reference's per-merged-tensor
allreduce timers (reference distributed_optimizer.py:374-391,407-425), taken
from the device timeline instead of host timers.

Usage:
    python tools/overlap_report.py [--model resnet20] [--batch 16]
        [--policy mgwfbp] [--nsteps 1] [--out profiles/overlap.json]

Caveats: on a single real chip a cross-device all-reduce compiles away, so
collective rows only appear with >= 2 devices (e.g. the 8-device CPU mesh:
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8). On
CPU the collectives are synchronous thunks — the report then documents the
schedule, while TPU/GPU traces show true async concurrency.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_COLLECTIVE_MARKERS = (
    "all-reduce", "all_reduce", "allreduce",
    "reduce-scatter", "all-gather", "collective-permute",
)
_NON_COMPUTE_MARKERS = _COLLECTIVE_MARKERS + (
    "copy", "infeed", "outfeed", "send", "recv", "tuple", "bitcast",
)


def _load_trace_events(logdir: str) -> list[dict]:
    paths = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.trace.json.gz")
    )
    events: list[dict] = []
    for p in paths:
        with gzip.open(p, "rt") as f:
            data = json.load(f)
        events.extend(data.get("traceEvents", []))
    return events


def _device_lanes(events: list[dict]) -> set[tuple]:
    """(pid) ids of device (non-host) lanes, from process_name metadata."""
    lanes = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            name = e.get("args", {}).get("name", "").lower()
            if any(k in name for k in ("tpu", "device", "xla", "/stream", "gpu")):
                if "host" not in name and "python" not in name:
                    lanes.add(e.get("pid"))
    return lanes


def summarize_overlap(logdir: str) -> dict:
    """Parse a profiler trace dir -> overlap summary dict."""
    events = _load_trace_events(logdir)
    lanes = _device_lanes(events)
    complete = [
        e for e in events
        if e.get("ph") == "X" and (not lanes or e.get("pid") in lanes)
        and "dur" in e and "ts" in e
    ]
    colls = [
        e for e in complete
        if any(m in e.get("name", "").lower() for m in _COLLECTIVE_MARKERS)
    ]
    computes = [
        e for e in complete
        if not any(
            m in e.get("name", "").lower() for m in _NON_COMPUTE_MARKERS
        )
    ]
    rows = []
    for c in colls:
        c0, c1 = c["ts"], c["ts"] + c["dur"]
        concurrent = 0.0
        for k in computes:
            k0, k1 = k["ts"], k["ts"] + k["dur"]
            lo, hi = max(c0, k0), min(c1, k1)
            if hi > lo:
                concurrent += hi - lo
        rows.append(
            {
                "name": c["name"][:120],
                "dur_us": c["dur"],
                "concurrent_compute_us": round(concurrent, 3),
                "overlap_fraction": round(concurrent / max(c["dur"], 1e-9), 4),
            }
        )
    rows.sort(key=lambda r: -r["dur_us"])
    total = sum(r["dur_us"] for r in rows)
    overlapped = sum(r["concurrent_compute_us"] for r in rows)
    return {
        "n_collective_events": len(rows),
        "total_collective_us": round(total, 3),
        "overlapped_us": round(min(overlapped, total), 3),
        "overlap_fraction": round(overlapped / total, 4) if total else None,
        "collectives": rows[:40],
    }


def measure_tb(model, meta, params, batch_stats, batch):
    """One arrival-order backward profile for a model."""
    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu.parallel.allreduce import arrival_order
    from mgwfbp_tpu.profiling import benchmark_trainer_backward

    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    names = [jax.tree_util.keystr(kp) for kp, _ in paths]
    perm = arrival_order(len(names), names=names)
    micro = {
        "x": jnp.zeros((batch,) + tuple(meta.input_shape), meta.input_dtype),
        "y": jnp.zeros((batch,), jnp.int32),
    }
    return benchmark_trainer_backward(
        model, meta, params, batch_stats, micro, perm,
        warmup=1, iters=3, names=names,
    )


def _build_setup(model_name, batch, policy, nsteps, comm_profile=None):
    """Shared setup: model/state/reducer (measured-tb schedule) + step fn;
    tb is measured here for the policies that need it (mgwfbp/auto)."""
    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.optim import make_optimizer
    from mgwfbp_tpu.parallel.allreduce import arrival_order, make_merged_allreduce
    from mgwfbp_tpu.parallel.costmodel import load_profile, lookup_alpha_beta
    from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh
    from mgwfbp_tpu.profiling import benchmark_trainer_backward
    from mgwfbp_tpu.train import create_train_state, make_train_step

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshSpec(data=n_dev))
    model, meta = zoo.create_model(model_name)
    tx, _ = make_optimizer(
        0.1, momentum=0.9, weight_decay=1e-4, lr_schedule="const",
        dataset=meta.dataset, num_batches_per_epoch=1,
    )
    state = create_train_state(
        jax.random.PRNGKey(0), model,
        jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype), tx,
    )
    threshold = 0
    if policy.startswith("threshold:"):
        # "threshold:N" rows reproduce the reference's static-threshold
        # sweep (batch_dist_mpi.sh grid over element-count thresholds)
        policy, threshold = "threshold", int(policy.split(":", 1)[1])
    reducer = None
    if policy not in ("none", "xla"):
        from mgwfbp_tpu.parallel.costmodel import resolve_profile

        cost = (
            resolve_profile(load_profile(comm_profile), max(n_dev, 2))
            if comm_profile
            else lookup_alpha_beta("ici", max(n_dev, 2))
        )
        tb = None
        if policy in ("mgwfbp", "auto"):
            tb = measure_tb(model, meta, state.params, state.batch_stats, batch)
        reducer = make_merged_allreduce(
            state.params, axis_name=DATA_AXIS, policy=policy,
            tb=tb, cost_model=cost, threshold=threshold,
        )
    step = make_train_step(
        model, meta, tx, mesh, reducer, nsteps_update=nsteps, donate=False
    )
    return mesh, model, meta, state, reducer, step, n_dev


def hlo_schedule_report(
    model_name: str, batch: int, policy: str, nsteps: int,
    comm_profile: str | None = None,
) -> dict:
    """Overlap evidence from the compiled module's instruction schedule:
    for each all-reduce in the ENTRY sequence, count the compute ops
    (fusions/convolutions/dots) scheduled BETWEEN it and the previous
    collective. Interleaved compute means each group's collective is issued
    as soon as its members' grads exist — the dataflow freedom the TPU
    latency-hiding scheduler turns into true async overlap — rather than
    all collectives piling up after the full backward (the lax.scan
    barrier failure mode, VERDICT r2 Weak #3)."""
    import re

    import jax
    import jax.numpy as jnp

    mesh, model, meta, state, reducer, step, n_dev = _build_setup(
        model_name, batch, policy, nsteps, comm_profile
    )
    gb = batch * n_dev
    bd = {
        "x": jnp.zeros((nsteps, gb) + tuple(meta.input_shape), meta.input_dtype),
        "y": jnp.zeros((nsteps, gb), jnp.int32),
    }
    text = step.lower(state, bd).compile().as_text()
    entry = text.split("ENTRY")[-1]
    lines = [l.strip() for l in entry.splitlines() if "=" in l]
    compute_pat = re.compile(r"fusion|convolution|dot\(|custom-call")
    rows = []
    since_prev = 0
    compute_after_first_ar = 0
    seen_ar = False
    for ln in lines:
        is_ar = "all-reduce(" in ln or "all-reduce-start(" in ln
        if is_ar:
            name = ln.split("=")[0].strip()[:60]
            rows.append({"collective": name, "compute_ops_since_prev": since_prev})
            since_prev = 0
            seen_ar = True
        elif compute_pat.search(ln):
            since_prev += 1
            if seen_ar:
                compute_after_first_ar += 1
    interleaved = sum(1 for r in rows[1:] if r["compute_ops_since_prev"] > 0)
    return {
        "mode": "hlo_schedule",
        "model": model_name,
        "policy": policy,
        "nsteps_update": nsteps,
        "n_devices": n_dev,
        "device_kind": jax.devices()[0].device_kind,
        "merge_groups": reducer.schedule.num_groups if reducer else 0,
        "n_collectives_in_schedule": len(rows),
        "collectives_with_compute_interleaved_before": interleaved,
        "compute_ops_scheduled_after_first_collective": compute_after_first_ar,
        "collectives": rows[:40],
    }


def capture_and_report(
    model_name: str, batch: int, policy: str, nsteps: int, steps: int = 5,
    comm_profile: str | None = None,
) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    mesh, model, meta, state, reducer, step, n_dev = _build_setup(
        model_name, batch, policy, nsteps, comm_profile
    )
    rs = np.random.RandomState(0)
    gb = batch * n_dev
    shape = (nsteps, gb) + tuple(meta.input_shape)
    bd = {
        "x": jnp.asarray(rs.randn(*shape)).astype(meta.input_dtype),
        "y": jnp.asarray(
            rs.randint(0, meta.num_classes, (nsteps, gb)), jnp.int32
        ),
    }
    state, m = step(state, bd)  # compile + warmup
    jax.block_until_ready(m)
    logdir = tempfile.mkdtemp(prefix="mgwfbp_trace_")
    with jax.profiler.trace(logdir):
        for _ in range(steps):
            state, m = step(state, bd)
        jax.block_until_ready(m)
    out = summarize_overlap(logdir)
    out.update(
        {
            "model": model_name,
            "policy": policy,
            "nsteps_update": nsteps,
            "n_devices": n_dev,
            "device_kind": jax.devices()[0].device_kind,
            "merge_groups": reducer.schedule.num_groups if reducer else 0,
            "trace_dir": logdir,
        }
    )
    if reducer is not None and reducer.schedule.predicted_group_times:
        # predicted-vs-actual per merged collective (reference logs the
        # prediction and times each merged tensor's allreduce in-loop,
        # distributed_optimizer.py:256-259, 374-391, 407-425). Alignment is
        # by rank order of duration/size: the trace does not carry group
        # identity, but the k-th largest collective should correspond to
        # the k-th largest bucket.
        pred = sorted(
            (
                {"bytes": b, "predicted_s": t}
                for b, t in reducer.schedule.predicted_group_times
            ),
            key=lambda r: -r["bytes"],
        )
        # aggregate the per-step events of each collective op (same HLO
        # instruction name recurs once per timed step) into a mean duration
        by_name: dict = {}
        for ev in out.get("collectives", []):
            agg = by_name.setdefault(ev["name"], {"total": 0.0, "n": 0})
            agg["total"] += ev["dur_us"]
            agg["n"] += 1
        actual = sorted(
            (
                {"name": k, "mean_us": v["total"] / v["n"]}
                for k, v in by_name.items()
            ),
            key=lambda r: -r["mean_us"],
        )[: len(pred)]
        rows = []
        for i, p in enumerate(pred):
            row = dict(p)
            if i < len(actual):
                meas = actual[i]["mean_us"] / 1e6
                row["measured_s"] = round(meas, 9)
                row["measured_over_predicted"] = (
                    round(meas / p["predicted_s"], 3)
                    if p["predicted_s"] > 0
                    else None
                )
            rows.append(row)
        out["predicted_vs_actual"] = rows
        out["alignment_caveat"] = (
            "rank-order alignment by duration: the trace's collective list "
            "also contains the metrics and batch_stats pmeans, so rows near "
            "the small-bucket tail may pair a bucket prediction with one of "
            "those; trust the large-bucket rows, and cross-check counts "
            "against merge_groups (+2 for metrics/bstats on BN models)"
        )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet20")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--policy", default="mgwfbp")
    ap.add_argument("--nsteps", type=int, default=1)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--mode", choices=["trace", "hlo"], default="trace",
                    help="trace: profiler-timeline concurrency (needs "
                         "device lanes, i.e. TPU/GPU); hlo: compiled "
                         "schedule interleaving (any backend)")
    ap.add_argument("--comm-profile", dest="comm_profile", default=None,
                    help="calibrated alpha-beta json (profiles/*.json)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from mgwfbp_tpu.utils.platform import apply_platform_overrides

    apply_platform_overrides()
    if args.mode == "hlo":
        report = hlo_schedule_report(
            args.model, args.batch, args.policy, args.nsteps,
            comm_profile=args.comm_profile,
        )
    else:
        report = capture_and_report(
            args.model, args.batch, args.policy, args.nsteps, args.steps,
            comm_profile=args.comm_profile,
        )
    text = json.dumps(report, indent=2)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
