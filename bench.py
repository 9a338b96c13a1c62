"""Benchmark entry point — runs on the attached TPU, never on the CPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}. On an
unrecoverable failure it still prints one JSON line, with an "error" field
and value null, never a raw traceback — and exits non-zero. No chip is such
a failure (a "skipped" record, exit 1): a measurement path that finds no
accelerator does not fall back to the CPU.

Protocol (VERDICT r2 task #2 — a number that survives scrutiny):
  * the full policy grid {mgwfbp, wfbp, single, none} is timed in ONE run —
    the reference's whole experimental method is this A/B grid
    (reference batch_dist_mpi.sh:1-17, settings.py:34 ORIGINAL_HOROVOD);
  * the timed loop is host-synchronized by pulling a scalar computed by
    the LAST chained step: steps chain through donated state, so the device
    runs them strictly in order and the final pull brackets the whole
    region. Intermediate pulls drain the dispatch pipeline, so they are
    avoided (MGWFBP_BENCH_SYNC=iter|window restores per-step/per-10-step
    pulls for harness A/B);
  * >= 50 timed iterations at the model's PRESET per-worker batch
    (resnet50: 128, reference exp_configs/resnet50.conf). A batch that
    does not fit fails the run; nothing is re-run at another size;
  * MFU is computed from XLA's compiled cost analysis, which must succeed;
    a device kind without a known peak reports no MFU, and a physically
    impossible MFU (> 1.0) turns the result into an "error" payload rather
    than reporting garbage.

The mgwfbp policy uses a MEASURED total-backward time to scale its tb
profile (no invented 1e-3 constants).
"""

from __future__ import annotations

import json
import os
import sys
import time

P100_RESNET50_IMG_S = 250.0

_POLICIES = ("mgwfbp", "auto", "wfbp", "single", "none")


def _peak_flops(device_kind: str):
    """Device-kind-keyed peak FLOP/s (shared table in utils.platform)."""
    from mgwfbp_tpu.utils.platform import peak_flops

    return peak_flops(device_kind)


class ChipUnavailable(RuntimeError):
    """There is no accelerator to measure: backend init did not come back
    inside its deadline, or jax found only the CPU. Distinct from a real
    failure so main() can emit a structured "skipped" record — with a
    non-zero exit all the same."""


def _require_chip() -> list:
    """jax.devices() on an accelerator, or ChipUnavailable.

    Init runs under `preflight_backend`'s deadline: a chip held by another
    process can block PJRT init, and a bench that hangs never prints its
    one JSON line.
    """
    from mgwfbp_tpu.utils.faults import FaultPlan
    from mgwfbp_tpu.utils.platform import preflight_backend

    # deterministic fault injection (MGWFBP_FAULT_PLAN=chip_unavailable):
    # exercise the structured record + non-zero exit without a real outage
    if FaultPlan.from_env().chip_unavailable():
        raise ChipUnavailable(
            "chip unavailable (injected by MGWFBP_FAULT_PLAN=chip_unavailable)"
        )
    try:
        devices = preflight_backend()
    except RuntimeError as e:
        raise ChipUnavailable(str(e)) from None
    if devices[0].platform == "cpu":
        raise ChipUnavailable(
            "chip unavailable: jax found only the cpu platform "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    return devices


def _emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def _progress(msg: str) -> None:
    """Phase marker on stderr (stdout carries exactly one JSON line): a
    run that stalls is diagnosable from the stderr tail alone."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _bench_cost_model(n_dev: int):
    """Committed chip calibration profile when one exists
    (profiles/tpu_v5e_family.json; override with MGWFBP_BENCH_PROFILE),
    else the warned uncalibrated prior."""
    from mgwfbp_tpu.parallel.costmodel import committed_profile_or_prior

    path = os.environ.get(
        "MGWFBP_BENCH_PROFILE",
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "profiles", "tpu_v5e_family.json",
        ),
    )
    return committed_profile_or_prior(path, "ici", max(n_dev, 2))


def _bench_policy(
    policy, make_state, model, meta, tx, mesh, batch_dict, tb, iters,
    compute_dtype=None, cost_model=None,
):
    """Build the step for one policy, warm up, time with windowed host sync.

    Returns (sec_per_iter, merge_groups, flops_per_step)."""
    import jax

    from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
    from mgwfbp_tpu.parallel.costmodel import lookup_alpha_beta
    from mgwfbp_tpu.parallel.mesh import DATA_AXIS
    from mgwfbp_tpu.train import make_train_step

    n_dev = mesh.devices.size
    state = make_state()  # fresh per policy: buffers are DONATED below
    if policy == "none":
        reducer = None  # XLA-fused oracle (reference ORIGINAL_HOROVOD)
    else:
        reducer = make_merged_allreduce(
            state.params,
            axis_name=DATA_AXIS,
            policy=policy,
            tb=tb if policy in ("mgwfbp", "auto") else None,
            cost_model=(
                cost_model
                if cost_model is not None
                else lookup_alpha_beta("ici", max(n_dev, 2))
            ),
            comm_op=os.environ.get("MGWFBP_BENCH_COMM_OP", "all_reduce"),
        )
    # donate=True: the state buffers are reused in place across steps —
    # the production configuration (and ~4% faster than copying)
    step = make_train_step(
        model, meta, tx, mesh, reducer, compute_dtype=compute_dtype,
        donate=True,
    )

    # AOT-compile ONCE: the same executable serves cost analysis and the
    # timed loop (lowering twice would double bench startup). A failed
    # compile or cost analysis fails the run: MFU is never quietly dropped
    run = step.lower(state, batch_dict).compile()
    flops = float(run.cost_analysis()["flops"])
    # warmup, synchronized by a host scalar pull
    for _ in range(5):
        state, metrics = run(state, batch_dict)
    float(metrics["loss"])

    # Sync discipline: every step chains through `state` (donated), so the
    # device executes steps strictly in order and pulling a scalar computed
    # by step i forces steps 1..i to have run. ONE pull after the last step
    # therefore brackets the whole timed region exactly. Each extra pull
    # drains the dispatch pipeline, so intermediate pulls would time the
    # host round trip, not the device. MGWFBP_BENCH_SYNC=iter|window
    # restores per-step / per-10-step pulls for A/B-ing the harness.
    sync_mode = os.environ.get("MGWFBP_BENCH_SYNC", "end")
    windows = {"iter": 1, "window": 10, "end": iters}
    if sync_mode not in windows:
        raise ValueError(
            f"MGWFBP_BENCH_SYNC={sync_mode!r}: expected one of "
            f"{sorted(windows)}"
        )
    window = windows[sync_mode]
    loss = None
    t0 = time.perf_counter()
    for i in range(iters):
        state, metrics = run(state, batch_dict)
        if (i + 1) % window == 0 or i == iters - 1:
            loss = float(metrics["loss"])
    dt = (time.perf_counter() - t0) / iters
    del state
    if not (loss == loss):  # NaN guard: timing a diverged program is moot
        raise RuntimeError(f"policy {policy}: non-finite loss in timed loop")
    groups = reducer.schedule.num_groups if reducer is not None else 0
    return dt, groups, flops, reducer


def run_bench() -> dict:
    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
    )

    apply_platform_overrides()
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.config import PRESETS
    from mgwfbp_tpu.optim import make_optimizer
    from mgwfbp_tpu.parallel.allreduce import arrival_order
    from mgwfbp_tpu.parallel.mesh import MeshSpec, make_mesh
    from mgwfbp_tpu.profiling import benchmark_trainer_backward
    from mgwfbp_tpu.train import create_train_state

    model_name = os.environ.get("MGWFBP_BENCH_MODEL", "resnet50")
    preset_bs = PRESETS.get(model_name, {}).get("batch_size", 32)
    batch = int(os.environ.get("MGWFBP_BENCH_BATCH", str(preset_bs)))
    iters = int(os.environ.get("MGWFBP_BENCH_ITERS", "50"))
    # bf16 compute is the native TPU path (master weights stay fp32, the
    # reference's apex-O2 analogue); MGWFBP_BENCH_DTYPE=float32 opts out
    dtype_name = os.environ.get("MGWFBP_BENCH_DTYPE", "bfloat16")
    import jax.numpy as _jnp

    compute_dtype = (
        None if dtype_name in ("float32", "f32") else _jnp.dtype(dtype_name)
    )

    devices = _require_chip()
    _progress(f"backend up: {devices}")
    n_dev = len(devices)
    cost_model, cost_src = _bench_cost_model(n_dev)
    mesh = make_mesh(MeshSpec(data=n_dev))
    model, meta = zoo.create_model(model_name)
    tx, _ = make_optimizer(
        0.01, momentum=0.9, weight_decay=1e-4, lr_schedule="const",
        dataset="imagenet", num_batches_per_epoch=1,
    )
    def make_state():
        return create_train_state(
            jax.random.PRNGKey(0), model,
            jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype), tx,
        )

    state = make_state()  # for the tb measurement only

    def make_batch(per_dev):
        rs = np.random.RandomState(0)
        gb = per_dev * n_dev
        shape = (1, gb) + tuple(meta.input_shape)
        return gb, {
            "x": jnp.asarray(rs.randn(*shape)).astype(meta.input_dtype),
            "y": jnp.asarray(
                rs.randint(0, meta.num_classes, (1, gb)), jnp.int32
            ),
        }

    def run_grid(per_dev):
        """tb measurement + full policy grid at ONE batch size — the A/B
        grid must never mix batch sizes, and the mgwfbp schedule must come
        from a tb profile measured at the batch it is timed at."""
        _progress(f"materializing batch (per-device {per_dev})")
        gb, bd = make_batch(per_dev)
        paths = jax.tree_util.tree_flatten_with_path(state.params)[0]
        names = [jax.tree_util.keystr(kp) for kp, _ in paths]
        perm = arrival_order(len(names), names=names)
        micro = {"x": bd["x"][0, :per_dev], "y": bd["y"][0, :per_dev]}
        # measured tb: real backward wall clock (scale measured, not
        # invented — VERDICT r2 Weak #4); trace-attributed when possible
        _progress(f"tb backward profiling (batch {per_dev})")
        tb_prof = benchmark_trainer_backward(
            model, meta, state.params, state.batch_stats, micro, perm,
            warmup=2, iters=5, names=names, compute_dtype=compute_dtype,
        )
        grid: dict[str, dict] = {}
        reducers: dict[str, object] = {}
        for policy in _POLICIES:
            _progress(f"policy {policy}: build + compile + time")
            dt, groups, flops, reducer = _bench_policy(
                policy, make_state, model, meta, tx, mesh, bd, tb_prof,
                iters, compute_dtype=compute_dtype, cost_model=cost_model,
            )
            grid[policy] = {
                "sec_per_iter": round(dt, 6),
                "images_per_sec": round(gb / dt, 2),
                "merge_groups": groups,
                "flops_per_step": flops,
            }
            reducers[policy] = reducer
        return gb, tb_prof, grid, reducers

    global_batch, tb, results, reducers = run_grid(batch)

    # Headline = the PRODUCTION configuration. On one device the Trainer
    # skips the reducer entirely (reference single-path parity:
    # train_with_single never wraps the optimizer), which is exactly the
    # 'none' row; the instrumented mgwfbp row stays in `policies` so the
    # no-op-dispatch overhead remains visible. Multi-device headline is
    # `auto` — the production default policy (config.py) — matching the
    # reference's ADAPTIVE_MERGE-on default.
    headline_policy = "none" if n_dev == 1 else "auto"
    main = results[headline_policy]
    dt = main["sec_per_iter"]
    img_s = main["images_per_sec"]
    flops = main["flops_per_step"]
    peak = _peak_flops(devices[0].device_kind)
    mfu = flops / dt / (peak * n_dev) if peak else None

    payload = {
        "metric": f"{model_name}_synthetic_{meta.dataset}_train_throughput",
        "value": img_s,
        "unit": "images/s",
        "vs_baseline": round(img_s / P100_RESNET50_IMG_S, 3),
        # the row the headline numbers actually come from; the single-device
        # production rationale lives in "note"
        "policy": headline_policy,
        "n_devices": n_dev,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "batch_per_device": batch,
        "compute_dtype": dtype_name,
        "iters": iters,
        "sec_per_iter": dt,
        "merge_groups": main["merge_groups"],
        "policies": {
            k: {kk: vv for kk, vv in v.items() if kk != "flops_per_step"}
            for k, v in results.items()
        },
        "tb_total_s": round(sum(tb), 6),
        "cost_profile": cost_src or "UNCALIBRATED ici prior",
        "flops_per_step": flops,
    }
    if mfu is not None:
        payload["mfu"] = round(mfu, 4)
    headline_reducer = reducers.get(headline_policy)
    if headline_reducer is not None:
        # overlap-efficiency summary for the headline configuration (the
        # paper's hidden-vs-exposed comm accounting, telemetry/overlap.py)
        # — cost-model-attributed here: the bench loop is not traced
        from mgwfbp_tpu.telemetry import summarize as overlap_summarize

        s = overlap_summarize(headline_reducer, cost_model, list(tb), dt)
        payload["overlap"] = {
            "comm_s": round(s.comm_s, 6),
            "hidden_s": round(s.hidden_s, 6),
            "exposed_s": round(s.exposed_s, 6),
            "efficiency": round(s.efficiency, 4),
            "attribution": s.attribution,
        }
    if n_dev == 1:
        payload["note"] = (
            "single chip: headline is the PRODUCTION configuration — the "
            "Trainer skips the reducer at world size 1 (reference "
            "single-path parity), i.e. the 'none' row. Collectives are "
            "no-ops here, so the XLA-fused oracle "
            "('none'/'single') is the ceiling and merge scheduling can only "
            "add dispatch overhead; MG-WFBP's advantage needs real "
            "inter-chip communication (compare policies on a multi-chip "
            "mesh)."
        )
    if mfu is not None and mfu > 1.0:
        # physically impossible: the measurement layer is broken; refuse to
        # report a throughput number (VERDICT r2 Weak #2)
        payload.update(
            {
                "value": None,
                "vs_baseline": None,
                "error": (
                    f"computed MFU {mfu:.3f} > 1.0 — timing not credible "
                    f"(dt={dt}, flops={flops}, peak={peak})"
                ),
            }
        )
    return payload


def _record_bench_skip(detail: str) -> None:
    """Append a structured bench_skip record to the telemetry stream at
    MGWFBP_TELEMETRY_DIR (when set) — the same typed event family live
    runs write, so outage post-mortems grep one format."""
    d = os.environ.get("MGWFBP_TELEMETRY_DIR")
    if not d:
        return
    try:
        from mgwfbp_tpu.telemetry import EventWriter

        w = EventWriter(
            os.path.join(d, "telemetry.jsonl"), run={"source": "bench"}
        )
        w.emit("bench_skip", detail=detail)
        w.close()
    except Exception:  # noqa: BLE001 — observability must not replace
        # the structured record with a traceback
        pass


def main() -> int:
    try:
        payload = run_bench()
        _emit(payload)
        return 1 if payload.get("error") else 0
    except ChipUnavailable as e:
        # the structured record says "no chip", the exit code still says
        # "nothing was measured"
        _record_bench_skip(f"{type(e).__name__}: {e}")
        _emit(
            {
                "metric": "resnet50_synthetic_imagenet_train_throughput",
                "value": None,
                "unit": "images/s",
                "vs_baseline": None,
                "skipped": "chip unavailable",
                "detail": f"{type(e).__name__}: {e}",
            }
        )
        return 1
    except Exception as e:  # noqa: BLE001 — one JSON line, never a traceback
        _emit(
            {
                "metric": "resnet50_synthetic_imagenet_train_throughput",
                "value": None,
                "unit": "images/s",
                "vs_baseline": None,
                "error": f"{type(e).__name__}: {e}",
            }
        )
        return 1


if __name__ == "__main__":
    sys.exit(main())
