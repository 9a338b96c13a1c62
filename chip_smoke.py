"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              one chip: device, train and kernel phases
    python chip_smoke.py --multichip  four chips: the merged-collective Trainer
                                      against `--policy none`, and nothing else

Drives the main path through the entry points a user calls (`train_cli`'s
flags -> `Trainer` -> `Trainer.fit`: loader, jitted step, guard, health
statistics, end-of-epoch eval) at ResNet-50's full width — the preset of
`mgwfbp_tpu/config.py`: ImageNet shapes, per-device batch 128, bf16 compute,
synthetic data and random weights from a seed — and checks what comes out.

ONE process: nothing here starts a child, because a chip belongs to the
process that first touched JAX. Anything but platform `tpu` is a non-zero
exit, never a CPU run, and a failing phase raises: no `except` lets the run
end in 0. Every phase prints its own lines; the LAST line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Logs and the telemetry stream go under chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
CHIP_COST_PROFILE = os.path.join(ROOT, "profiles", "tpu_v5e_family.json")
SEED = 0
# --multichip bounds. The reduction itself is compared free of training
# dynamics and must agree to f32 rounding. The two K-step runs are two
# DIFFERENT compiled bf16 programs (XLA fuses the backward differently when
# the gradients feed bucket packing), so from step 1 on — same params, same
# batch — they agree only to bf16 noise, which SGD then amplifies: on four
# v5e chips PR 21 saw 1.3e-4 at step 1, at most 5.8e-3 over six steps, and
# final params 1.4e-2 apart (about the distance either run moved).
REDUCE_REL_L2 = 1e-6  # reducer vs lax.pmean on one gradient-shaped tree
FIRST_LOSS_RTOL = 1e-3  # step 1: identical params and batch
LOSS_RTOL = 2e-2  # every step's loss, relative
PARAM_REL_L2 = 5e-2  # ||p_auto - p_none|| / ||p_none|| over all leaves


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require(ok: bool, what: str) -> None:
    """A failed check ends the run (not `assert`: `python -O` drops those)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_phase(want_count: int) -> dict:
    """jax.devices() must be `want_count` TPU chips; prints the versions and
    the compile-cache directory in use. Returns the last line's `device`."""
    from importlib import metadata

    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
    )

    apply_platform_overrides()
    cache_dir = enable_compile_cache()
    import jax
    import jaxlib

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say("device", f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {metadata.version('libtpu')}")
    say("device", f"platform {device['platform']} kind {device['kind']!r} "
        f"count {device['count']}")
    say("device", f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries at start)")
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (jax reports platform "
            f"{device['platform']!r}); this script never runs on the CPU"
        )
    if device["count"] != want_count:
        raise SystemExit(
            f"chip_smoke: this mode needs {want_count} chip(s), jax sees "
            f"{device['count']} (one chip: no arguments; four: --multichip)"
        )
    return device


def native_status() -> str:
    """Whether the loader's C++ kernels were built from augment.cpp (the
    .so is git-ignored, so a fresh checkout has none), found already
    built, or replaced by the NumPy fallback."""
    from mgwfbp_tpu import native

    pattern = os.path.join(os.path.dirname(native.__file__), "*.so")
    before = set(glob.glob(pattern))
    if not native.available():
        return "NumPy fallback (no .so could be built or loaded)"
    built = set(glob.glob(pattern)) - before
    if built:
        return f"built {os.path.basename(built.pop())} from augment.cpp"
    return f"loaded existing {os.path.basename(sorted(before)[-1])}"


def cost_model_source(world: int, phase: str) -> list[str]:
    """Extra train_cli flags selecting the committed chip calibration when
    it exists; says which cost-model source that leaves in use."""
    from mgwfbp_tpu.parallel.costmodel import committed_profile_or_prior

    _, src = committed_profile_or_prior(
        CHIP_COST_PROFILE, "ici", max(world, 2)
    )
    say(phase, "cost model: "
        + (src or "UNCALIBRATED ici alpha-beta prior "
           f"({os.path.relpath(CHIP_COST_PROFILE, ROOT)} does not exist)"))
    return ["--comm-profile", src] if src else []


def train_argv(dnn: str, epochs: int, logdir: str, extra=()) -> list[str]:
    return [
        "--dnn", dnn, "--synthetic", "--dtype", "bfloat16",
        "--epochs", str(epochs), "--seed", str(SEED),
        "--telemetry", "--logdir", logdir, *extra,
    ]


def run_trainer(argv: list[str], phase: str) -> dict:
    """`train_cli`'s flags -> Trainer -> fit, then read back what the run
    itself recorded: the telemetry stream's `health` records carry every
    step's loss, `step` spans the dispatch times, `bad_step` the guard.
    Returns what the phases compare."""
    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu import train_cli
    from mgwfbp_tpu.telemetry import events_of, read_events
    from mgwfbp_tpu.train.trainer import Trainer

    args = train_cli.build_parser().parse_args(argv)
    cfg = train_cli.config_from_args(args)
    shutil.rmtree(args.logdir, ignore_errors=True)  # the stream appends
    t0 = time.perf_counter()
    trainer = Trainer(
        cfg, profile_backward=not args.no_profile_backward,
        synthetic_data=True,
    )
    setup_s = time.perf_counter() - t0
    try:
        reducer = trainer.reducer
        if reducer is not None:
            exchange = (
                f"reducer num_groups {reducer.schedule.num_groups} comm_op "
                f"{reducer.comm_op} detail "
                f"{reducer.schedule.policy_detail or '(direct)'}"
            )
        elif trainer.data_size == 1:
            exchange = "reducer None (dropped at world size 1)"
        else:
            exchange = "reducer None (one XLA-fused pmean per leaf)"
        say(phase, f"flags: {' '.join(argv)}")
        say(phase, f"{cfg.dnn} {trainer.meta.input_shape} per-device batch "
            f"{cfg.batch_size} x {trainer.data_size} device(s), compute "
            f"{cfg.dtype}, policy {cfg.policy}, {exchange}")
        # where the Trainer's own placement puts a training batch
        placed = trainer._stack_micro([trainer._peek_batch()])["x"]
        t0 = time.perf_counter()
        final = trainer.fit(args.epochs)
        fit_s = time.perf_counter() - t0
        if trainer.data_size == 1:
            # world size 1 drops the reducer before the backward benchmark
            # ever runs; take the Trainer's own profile once, so the chip's
            # attribution path is known before a multi-chip run needs it
            t0 = time.perf_counter()
            tb = trainer._profile_backward()
            say(phase, f"backward profile: attribution {tb.source!r}, "
                f"{sum(tb) * 1e3:.1f} ms over {len(tb)} tensors "
                f"({time.perf_counter() - t0:.1f} s to take)")
        elif trainer._tb_cache is not None:
            say(phase, "backward profile: attribution "
                f"{trainer._tb_cache.source!r}")
        if reducer is not None:
            reduce_err = reducer_vs_pmean(trainer)
            say(phase, f"reducer vs lax.pmean on the mesh: relative L2 "
                f"difference {reduce_err:.3e} (bound {REDUCE_REL_L2:g})")
            require(reduce_err <= REDUCE_REL_L2, "merged reduction == pmean")
        params = jax.tree_util.tree_leaves(trainer.state.params)
        want_steps = args.epochs * max(trainer._steps_per_epoch(), 1)
        result = {
            "eval": final["eval"],
            "params": params,
            "reducer": reducer,
            "mesh_devices": [d.id for d in trainer.mesh.devices.flat],
            "batch_devices": [
                s.device.id for s in placed.addressable_shards
            ],
            "param_devices": [
                s.device.id for s in params[0].addressable_shards
            ],
        }
    finally:
        trainer.close()  # also flushes the telemetry stream read below
    tel = read_events(trainer.telemetry.path)
    losses = [e["loss"] for e in events_of(tel, "health")]
    bad_steps = len(events_of(tel, "bad_step"))
    spans = [e["dur_s"] for e in events_of(tel, "step")]
    epochs = [(e["steps"], e["dur_s"]) for e in events_of(tel, "epoch")]
    result["losses"] = losses
    say(phase, f"steps taken {len(spans)} (iteration {trainer.iteration}); "
        f"loss first {losses[0]:.4f} last {losses[-1]:.4f} from "
        f"{len(losses)} health records; bad_step events {bad_steps}")
    say(phase, "eval: " + ", ".join(
        f"{k} {v:.4f}" for k, v in final["eval"].items()))
    per_step = [d / max(n, 1) for n, d in epochs]
    say(phase, f"informational: set-up {setup_s:.1f} s, first step dispatch "
        f"(trace + compile or cache load) {spans[0]:.1f} s, fit "
        f"{fit_s:.1f} s; s/step per epoch (loader + step, synced at epoch "
        "end) " + " ".join(f"{x:.3f}" for x in per_step)
        + f"; steadiest {min(per_step[1:] or per_step):.3f}")
    # checks — what comes out is right
    require(
        len(spans) == len(losses) == want_steps == trainer.iteration,
        f"{len(spans)} steps, {len(losses)} health records, {want_steps} "
        "expected",
    )
    require(all(math.isfinite(x) for x in losses), f"finite losses {losses}")
    chance = math.log(trainer.meta.num_classes)
    require(
        0.5 * chance < losses[0] < 2.0 * chance,
        f"first loss {losses[0]} near ln(classes) = {chance:.2f}",
    )
    require(bad_steps == 0, "the gradient guard never fired")
    require(
        all(math.isfinite(v) for v in final["eval"].values()),
        f"finite eval metrics {final['eval']}",
    )
    require(
        all(bool(jnp.isfinite(p).all()) for p in params), "finite params"
    )
    return result


def reducer_vs_pmean(trainer) -> float:
    """The Trainer's production reducer against one plain `lax.pmean` on the
    live mesh: a gradient-shaped tree whose values differ on every device,
    reduced both ways inside one shard_map program. Returns the relative
    L2 difference (and requires the devices to have held different values,
    so equality is not trivial)."""
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    reducer = trainer.reducer
    leaves, treedef = jax.tree_util.tree_flatten(trainer._params_template)

    def sumsq(tree):
        return sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(tree))

    def minus(a, b):
        return jax.tree_util.tree_map(jnp.subtract, a, b)

    def body(key):
        key = jax.random.fold_in(key, lax.axis_index(reducer.axis_name))
        grads = treedef.unflatten([
            jax.random.normal(k, leaf.shape, leaf.dtype)
            for k, leaf in zip(jax.random.split(key, len(leaves)), leaves)
        ])
        plain = lax.pmean(grads, reducer.axis_name)
        return jnp.stack([
            sumsq(minus(reducer(grads), plain)), sumsq(plain),
            sumsq(minus(grads, plain)),
        ])

    diff, norm, spread = jax.jit(shard_map(
        body, mesh=trainer.mesh, in_specs=P(), out_specs=P(),
        check_vma=False,
    ))(jax.random.PRNGKey(SEED))
    require(float(spread) > 0.5 * float(norm), "per-device gradients differ")
    return math.sqrt(float(diff) / float(norm))


def peak_memory() -> None:
    import jax

    for d in jax.devices():
        stats = d.memory_stats()  # None where the backend keeps none (CPU)
        say("memory", f"device {d.id}: " + (
            f"peak_bytes_in_use {stats['peak_bytes_in_use'] / 2**30:.2f} GiB"
            f" of {stats['bytes_limit'] / 2**30:.2f} GiB"
            if stats else "not reported by this backend"))


def train_phase(dnn: str = "resnet50", epochs: int = 4) -> None:
    """One chip: 4 epochs x 4 steps (512 synthetic images / batch 128)."""
    say("train", f"native loader kernels: {native_status()}")
    extra = cost_model_source(1, "train")
    run_trainer(train_argv(dnn, epochs, OUT_DIR, extra), "train")
    peak_memory()


def kernel_phase(
    b: int = 4, t: int = 2048, h: int = 8, d: int = 64, iters: int = 20,
) -> None:
    """The language cells' attention core (`ops.blockattn`: on the chip its
    fused kernel, elsewhere its plain blocks) against dense attention, on
    the same device: agreement first, both times as information."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mgwfbp_tpu.ops import programs
    from mgwfbp_tpu.ops.blockattn import blockwise_attention
    from mgwfbp_tpu.parallel.ringattn import local_attention
    from mgwfbp_tpu.profiling import measure_step_time

    q, k, v = (
        jax.random.normal(key, (b, t, h, d), jnp.float32).astype(jnp.bfloat16)
        for key in jax.random.split(jax.random.PRNGKey(SEED), 3)
    )
    core = jax.jit(lambda q, k, v: blockwise_attention(q, k, v))
    dense = jax.jit(lambda q, k, v: local_attention(q, k, v, causal=True))
    before = programs.LOWERED.copy()
    got = np.asarray(core(q, k, v), np.float32)
    went = programs.lowered_since(before)["attention"]
    want = np.asarray(dense(q, k, v), np.float32)
    t_core = measure_step_time(core, q, k, v, warmup=3, iters=iters)
    t_dense = measure_step_time(dense, q, k, v, warmup=3, iters=iters)
    say("kernel", f"B{b} T{t} H{h} D{d} bf16 causal forward through "
        f"{'the fused kernel' if went['kernel'] else 'the plain blocks'}: "
        f"core {t_core * 1e3:.3f} ms, dense {t_dense * 1e3:.3f} ms per call "
        f"(host clock, {iters} calls, one sync; informational)")
    say("kernel", f"max abs error core vs dense "
        f"{float(np.abs(got - want).max()):.3e} (bound: rtol 2e-2 atol 2e-2)")
    require(went["kernel"] == int(programs.traced_for_tpu()),
            f"on a TPU the core is the fused kernel ({went})")
    require(got.shape == (b, t, h, d), f"output shape {got.shape}")
    require(bool(np.isfinite(got).all()), "finite kernel output")
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def multichip_phase(dnn: str = "resnet50", epochs: int = 6) -> None:
    """Four chips, one process: K Trainer steps under the production
    `--policy auto` (merged bucket collectives) and the same K steps from
    the same seed under `--policy none` (one XLA-fused pmean per leaf)."""
    import jax
    import numpy as np

    n = jax.device_count()
    extra = cost_model_source(n, "auto")
    auto = run_trainer(train_argv(
        dnn, epochs, os.path.join(OUT_DIR, "auto"),
        ["--policy", "auto", *extra]), "auto")
    none = run_trainer(train_argv(
        dnn, epochs, os.path.join(OUT_DIR, "none"),
        ["--policy", "none"]), "none")
    peak_memory()
    require(auto["reducer"] is not None, "--policy auto built a reducer")
    require(none["reducer"] is None, "--policy none built no reducer")
    for run in (auto, none):
        for what in ("mesh_devices", "batch_devices", "param_devices"):
            require(
                len(set(run[what])) == n,
                f"{what} {run[what]} on {n} distinct devices",
            )
    say("multichip", f"mesh, first batch and params each span devices "
        f"{auto['mesh_devices']}")
    la, ln = np.asarray(auto["losses"]), np.asarray(none["losses"])
    loss_rel = float(np.abs(la - ln).max() / np.abs(ln).max())
    num = sum(
        float(np.sum((np.asarray(a, np.float64) - np.asarray(b)) ** 2))
        for a, b in zip(auto["params"], none["params"])
    )
    den = sum(
        float(np.sum(np.asarray(b, np.float64) ** 2)) for b in none["params"]
    )
    param_rel = math.sqrt(num / den)
    say("multichip", "loss auto " + " ".join(f"{x:.4f}" for x in la))
    say("multichip", "loss none " + " ".join(f"{x:.4f}" for x in ln))
    first_rel = float(abs(la[0] - ln[0]) / abs(ln[0]))
    say("multichip", f"relative loss difference at step 1 {first_rel:.3e} "
        f"(bound {FIRST_LOSS_RTOL:g}), max over steps {loss_rel:.3e} (bound "
        f"{LOSS_RTOL:g}); final params relative L2 difference "
        f"{param_rel:.3e} (bound {PARAM_REL_L2:g})")
    require(first_rel <= FIRST_LOSS_RTOL, "first-step losses agree")
    require(loss_rel <= LOSS_RTOL, "loss trajectories agree")
    require(param_rel <= PARAM_REL_L2, "final params agree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--multichip", action="store_true",
        help="four chips: ONLY the merged-collective Trainer run and the "
             "`--policy none` run it is compared with",
    )
    args = ap.parse_args(argv)
    device = device_phase(4 if args.multichip else 1)
    if args.multichip:
        multichip_phase()
    else:
        train_phase()
        kernel_phase()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
