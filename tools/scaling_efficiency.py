"""Weak-scaling efficiency harness (SURVEY.md §7 step 7; BASELINE.md north
star: >= 90% linear scaling efficiency at v5e-64).

Two parts, one committed JSON artifact:

  measured  — sec/iter of the production train step at data extents
              {1, 2, 4, ...} over the AVAILABLE devices (8-device virtual CPU
              mesh, or however many real chips exist), per-device batch held
              constant (weak scaling, reference dl_trainer.py:153-156).
              efficiency(n) = t(1) / t(n): 1.0 is perfect weak scaling.

  predicted — solver-simulated efficiency at TARGET TPU topologies the
              current host cannot provide (v5e-4 / v5e-16 single slice over
              ICI, v5e-64 as 4 slices x 16 chips via the two-level ICI+DCN
              model), per policy: efficiency = t_step(1) / (t_step(1) +
              predicted nonoverlapped comm). Uses the tb profile and
              t_step(1) measured HERE, so run this on the real chip for TPU
              predictions (CPU tb would mis-scale them). The same simulator
              drives the merge solver itself (parallel/solver.py
              simulate_groups), so these numbers are exactly what the
              framework believes — the honest stand-in until multi-chip
              hardware is reachable.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python tools/scaling_efficiency.py --model resnet20 --batch 8 \
      --comm-profile profiles/cpu8_mesh.json --out profiles/scaling_cpu8.json
  python tools/scaling_efficiency.py --model resnet50 --batch 32 \
      --targets v5e-4,v5e-16,v5e-64 --out profiles/scaling_tpu_v5e_pred.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

POLICIES = ("mgwfbp", "auto", "wfbp", "single")


def _measure_step(model, meta, tx, mesh, reducer, batch, compute_dtype,
                  iters, warmup):
    """Best-of-3-window sec/iter of the jitted step (policy-grid protocol)."""
    import jax

    from mgwfbp_tpu.train import create_train_state, make_train_step

    import jax.numpy as jnp

    state = create_train_state(
        jax.random.PRNGKey(0), model,
        jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype), tx,
    )
    step = make_train_step(
        model, meta, tx, mesh, reducer, compute_dtype=compute_dtype,
        donate=True,
    )
    for _ in range(max(warmup, 1)):  # >=1: compile + sync anchor
        state, m = step(state, batch)
    float(m["loss"])
    windows = []
    per_window = max(iters // 3, 1)
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(per_window):
            state, m = step(state, batch)
        float(m["loss"])  # one host pull per window brackets the window
        windows.append((time.perf_counter() - t0) / per_window)
    del state, step
    return min(windows)


def run(model_name, batch, policy, comm_profile, targets, iters, warmup,
        dtype_name):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.optim import make_optimizer
    from mgwfbp_tpu.parallel.allreduce import arrival_order, make_merged_allreduce
    from mgwfbp_tpu.parallel.costmodel import (
        TwoLevelAlphaBeta, load_profile, lookup_alpha_beta,
    )
    from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh
    from mgwfbp_tpu.parallel.solver import LayerSpec, build_schedule
    from mgwfbp_tpu.profiling import benchmark_trainer_backward
    from mgwfbp_tpu.train import create_train_state

    compute_dtype = (
        None if dtype_name in ("float32", "f32") else jnp.dtype(dtype_name)
    )
    model, meta = zoo.create_model(model_name)
    tx, _ = make_optimizer(
        0.01, momentum=0.9, weight_decay=1e-4, lr_schedule="const",
        dataset=meta.dataset, num_batches_per_epoch=1,
    )
    state = create_train_state(
        jax.random.PRNGKey(0), model,
        jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype), tx,
    )
    paths = jax.tree_util.tree_flatten_with_path(state.params)[0]
    names = [jax.tree_util.keystr(kp) for kp, _ in paths]
    leaves = [v for _, v in paths]
    perm = arrival_order(len(names), names=names)

    rs = np.random.RandomState(0)

    def make_batch(n_dev):
        gb = batch * n_dev
        shape = (1, gb) + tuple(meta.input_shape)
        return {
            "x": jnp.asarray(rs.randn(*shape)).astype(meta.input_dtype),
            "y": jnp.asarray(
                rs.randint(0, meta.num_classes, (1, gb)), jnp.int32
            ),
        }

    # tb: measured per-arrival backward profile at the per-device batch
    micro_batch = make_batch(1)
    micro = {k: v[0] for k, v in micro_batch.items()}
    tb = benchmark_trainer_backward(
        model, meta, state.params, state.batch_stats, micro, perm,
        warmup=2, iters=5, names=names, compute_dtype=compute_dtype,
    )

    flat_model = load_profile(comm_profile) if comm_profile else None

    # ---- measured weak scaling over the available devices
    avail = len(jax.devices())
    extents = [n for n in (1, 2, 4, 8, 16, 32) if n <= avail]
    measured = {}
    t1 = None
    for n in extents:
        mesh = make_mesh(MeshSpec(data=n), devices=jax.devices()[:n])
        if n == 1:
            reducer = None  # no communication exists on one device
        else:
            # ADVICE r3: a profile calibrated at ONE world size must not be
            # reused verbatim at every extent. Family profiles resolve per
            # extent (measured trend); a flat profile is resolved as-is and
            # the artifact records that caveat.
            from mgwfbp_tpu.parallel.costmodel import resolve_profile

            cm = (
                resolve_profile(flat_model, n)
                if flat_model is not None
                else lookup_alpha_beta("ici", n)
            )
            reducer = make_merged_allreduce(
                state.params, axis_name=DATA_AXIS, policy=policy, tb=tb,
                cost_model=cm,
            )
        dt = _measure_step(
            model, meta, tx, mesh, reducer, make_batch(n), compute_dtype,
            iters, warmup,
        )
        if n == 1:
            t1 = dt
        measured[str(n)] = {
            "sec_per_iter": round(dt, 6),
            "samples_per_sec": round(batch * n / dt, 2),
            "efficiency": round(t1 / dt, 4),
            "merge_groups": (
                reducer.schedule.num_groups if reducer is not None else 0
            ),
        }

    # ---- predicted efficiency at target TPU topologies (solver simulation)
    def target_cost(tname):
        if tname == "v5e-4":
            return lookup_alpha_beta("ici", 4), 4
        if tname == "v5e-16":
            return lookup_alpha_beta("ici", 16), 16
        if tname == "v5e-64":
            return (
                TwoLevelAlphaBeta(
                    ici=lookup_alpha_beta("ici", 16),
                    dcn=lookup_alpha_beta("dcn", 4),
                    ici_size=16,
                    dcn_size=4,
                ),
                64,
            )
        raise ValueError(f"unknown target {tname!r}")

    itemsize = 2 if compute_dtype == jnp.bfloat16 else 4
    layers = [
        LayerSpec(
            name=names[j], size=int(leaves[j].size), itemsize=itemsize
        )
        for j in perm
    ]
    tb_arrival = list(tb)
    predicted = {}
    for tname in targets:
        cm, nchips = target_cost(tname)
        per_policy = {}
        for pol in POLICIES:
            sched = build_schedule(
                layers, tb_arrival, policy=pol, cost_model=cm,
            )
            nonoverlap = sched.predicted_nonoverlap_time
            per_policy[pol] = {
                "merge_groups": sched.num_groups,
                "predicted_nonoverlap_s": round(nonoverlap, 8),
                "predicted_efficiency": round(t1 / (t1 + nonoverlap), 4),
            }
        predicted[tname] = {"n_chips": nchips, "policies": per_policy}

    return {
        "model": model_name,
        "batch_per_device": batch,
        "policy_measured": policy,
        "compute_dtype": dtype_name,
        "device_kind": jax.devices()[0].device_kind,
        "available_devices": avail,
        "comm_profile": comm_profile,
        "comm_profile_kind": (
            None if flat_model is None else type(flat_model).__name__
        ),
        "comm_profile_note": (
            None
            if flat_model is None
            else (
                "family profile: alpha-beta-gamma resolved per measured "
                "extent (log2 interpolation between calibrated world sizes)"
                if type(flat_model).__name__ == "ProfileFamily"
                else "flat profile calibrated at one world size, applied "
                "AS-IS at every measured extent (no alpha-vs-hops rescale); "
                "prefer a --world-sizes family calibration"
            )
        ),
        "tb_total_s": round(sum(tb), 6),
        "t1_sec_per_iter": round(t1, 6),
        "measured_weak_scaling": measured,
        "predicted_targets": predicted,
        "method": (
            "weak scaling: per-device batch fixed, efficiency = t(1)/t(n); "
            "predictions: efficiency = t1/(t1 + solver-simulated "
            "nonoverlapped comm) per policy, the same simulate_groups the "
            "merge solver optimizes. 'ici'/'dcn' cost models are priors "
            "unless --comm-profile supplies a calibration."
        ),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet20")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--policy", default="mgwfbp")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--comm-profile", dest="comm_profile", default=None)
    ap.add_argument("--targets", default="v5e-4,v5e-16,v5e-64")
    ap.add_argument("--note", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
    )

    apply_platform_overrides()
    enable_compile_cache()
    report = run(
        args.model, args.batch, args.policy, args.comm_profile,
        [t for t in args.targets.split(",") if t], args.iters, args.warmup,
        args.dtype,
    )
    if args.note:
        report["environment_note"] = args.note
    text = json.dumps(report, indent=2)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
