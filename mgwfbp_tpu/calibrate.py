"""Communication calibration CLI: measure alpha-beta on the live topology.

Parity target: the reference's CommunicationProfiler + LinearRegression fit
(reference profiling.py:150-183, distributed_optimizer.py:105-127) — present
there but dead in the default path, which falls back to hardcoded cluster
tables. Here calibration is a first-class step: run once per topology,
persist the profile, and point training at it with --comm-profile.

Usage:
  python -m mgwfbp_tpu.calibrate --out profiles/v5e8.json
  python -m mgwfbp_tpu.train_cli --dnn resnet50 --comm-profile profiles/v5e8.json
"""

from __future__ import annotations

import argparse
import json
from typing import Optional


def main(argv: Optional[list[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="mgwfbp-calibrate")
    p.add_argument("--out", required=True, help="output profile json path")
    p.add_argument("--min-log2", type=int, default=13,
                   help="smallest payload (log2 elements)")
    p.add_argument("--max-log2", type=int, default=24,
                   help="largest payload (log2 elements)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--no-gamma", action="store_true",
                   help="skip the bucket-path microbenches: the "
                        "per-collective overhead (gamma) fit, the "
                        "per-byte bucketization (pack_beta) fit AND the "
                        "rs_opt_ag update-in-the-middle (update_beta) fit "
                        "— all save as 0.0, reverting the solver to the "
                        "pure alpha-beta objective")
    p.add_argument("--no-overlap", action="store_true",
                   help="skip the comm/compute overlap-capability probe")
    p.add_argument("--allgather", action="store_true",
                   help="also sweep a tiled all-gather at the same payload "
                        "sizes and fit ag_fraction — the measured RS/AG "
                        "phase split the cross-step rs_fwd_ag solver uses "
                        "instead of halving the full-collective predictor "
                        "(persisted in the profile, schema v3; older "
                        "profiles load with the historical 0.5 split)")
    p.add_argument("--gamma-total-log2", type=int, default=22,
                   help="fixed total payload for the gamma fit (log2 elems)")
    p.add_argument("--world-sizes", default=None,
                   help="comma list of data-axis extents to calibrate (e.g. "
                        "2,4,8): produces a 'family' profile whose per-P "
                        "alpha-beta-gamma replace the invented alpha-vs-hops "
                        "prior with measured trend")
    p.add_argument("--prior-extend", default=None, metavar="CONN",
                   help="single-chip mode (VERDICT r4 #5): calibrate the "
                        "chip-measurable constants at the available world "
                        "size (gamma = dispatch per extra collective, "
                        "pack_beta = bucketization copy, overlap — all real "
                        "at world 1, where the collective itself is "
                        "identity) and emit a FAMILY profile whose larger "
                        "extents carry the named alpha-beta prior ('ici' / "
                        "'dcn') combined with the measured "
                        "gamma/pack_beta/overlap. Meta separates "
                        "measured_fields from prior_fields per entry.")
    p.add_argument("--prior-world-sizes", default="2,4,8,16",
                   help="extents for the prior-extended entries")
    p.add_argument("--two-level", dest="two_level", action="store_true",
                   help="per-AXIS calibration of an (ici x dcn) two-axis "
                        "mesh (needs --dcn > 1): sweep a pmean over ONLY "
                        "the inner axis and ONLY the outer axis, fit each "
                        "link's alpha-beta, and persist a schema-stamped "
                        "two-level profile (kind='two_level', SampledCost "
                        "curves per link) — the cost model the two-link "
                        "hier solver schedules against. Combine with "
                        "--allgather to also fit the ICI link's RS/AG "
                        "split. tools/two_level_validation.py consumes "
                        "this calibration and validates the composition "
                        "AND the solved hier schedule against measurement.")
    p.add_argument("--ici", type=int, default=None,
                   help="inner-axis extent for --two-level (default: "
                        "devices / dcn)")
    p.add_argument("--dcn", type=int, default=2,
                   help="outer-axis extent (slices) for --two-level")
    p.add_argument("--forward", action="store_true",
                   help="LAYER-profile mode (needs --model): benchmark the "
                        "model's per-layer backward AND forward durations "
                        "on one device and write a layer profile "
                        "(tb_profile.json format, schema_version=2 with "
                        "tf_s) to --out — the forward timeline the "
                        "cross-step rs_fwd_ag solver prices deferred "
                        "all-gathers against. Unstamped legacy profiles "
                        "without tf_s still load (forward times default "
                        "to 0 with a warning; see "
                        "profiling.load_layer_profile).")
    p.add_argument("--model", default=None,
                   help="model to benchmark in --forward mode (e.g. lenet)")
    p.add_argument("--batch-size", type=int, default=8,
                   help="per-device batch for the --forward benchmark")
    args = p.parse_args(argv)
    if args.prior_extend and args.world_sizes:
        p.error("--prior-extend and --world-sizes are mutually exclusive: "
                "the former measures ONE world size and prior-fills the "
                "rest, the latter measures each listed extent")
    if args.forward and not args.model:
        p.error("--forward needs --model (the layer profile is per-model)")
    if args.two_level and (
        args.world_sizes or args.prior_extend or args.forward
    ):
        p.error("--two-level is its own calibration mode; it does not "
                "combine with --world-sizes/--prior-extend/--forward")
    if args.forward:
        return _forward_main(args)
    if args.two_level:
        return _two_level_main(args)

    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
    )

    apply_platform_overrides()
    enable_compile_cache()
    import dataclasses

    from mgwfbp_tpu.parallel.costmodel import (
        ProfileFamily,
        SampledCost,
        save_profile,
    )
    from mgwfbp_tpu.parallel.mesh import MeshSpec, make_mesh
    from mgwfbp_tpu.profiling import (
        fit_ag_fraction,
        profile_allgather,
        profile_allreduce,
        profile_group_overhead,
        profile_overlap_capability,
        profile_pack_overhead,
        profile_update_beta,
    )

    import jax

    sizes = tuple(2**k for k in range(args.min_log2, args.max_log2 + 1))

    def calibrate_mesh(mesh):
        prof = profile_allreduce(
            mesh, sizes=sizes, warmup=args.warmup, iters=args.iters
        )
        gamma, gsamples = 0.0, None
        if not args.no_gamma:
            gamma, gsamples = profile_group_overhead(
                mesh, alpha=prof.model.alpha,
                total_elems=2**args.gamma_total_log2,
            )
        overlap = 1.0
        if not args.no_overlap:
            # measured the way the train step is compiled, or the solver's
            # `overlap` would describe a chip the step never runs on
            from mgwfbp_tpu.train.step import async_collective_options

            overlap = profile_overlap_capability(
                mesh, compiler_options=async_collective_options(
                    mesh, mesh.axis_names),
            )
        pack_beta = 0.0
        update_beta = 0.0
        if not args.no_gamma:  # same bucket-path microbench family
            pack_beta = profile_pack_overhead(mesh)
            # the rs_opt_ag update-in-the-middle term (ROADMAP PR-2
            # follow-up): rs_ag vs rs_opt_ag on an identical payload
            update_beta = profile_update_beta(mesh)
        ag_fraction = 0.5
        if args.allgather:
            # measured RS/AG phase split (ROADMAP PR-7 follow-up b): a
            # dedicated tiled-all-gather sweep at the SAME payload sizes;
            # the median AG/full ratio replaces the halved-split prior
            ag_prof = profile_allgather(
                mesh, sizes=sizes, warmup=args.warmup, iters=args.iters
            )
            ag_fraction = fit_ag_fraction(prof, ag_prof)
        # the sampled curve (not just the 2-parameter fit) is the persisted
        # predictor: one flat beta cannot describe payload-dependent
        # per-byte cost (cache regimes on CPU, DMA pipelining on TPU)
        model = SampledCost(
            sizes_bytes=tuple(prof.sizes_bytes),
            times_s=tuple(prof.times_s),
            ab=prof.model,
            gamma=gamma,
            overlap=overlap,
            pack_beta=pack_beta,
            update_beta=update_beta,
            ag_fraction=ag_fraction,
        )
        return model, prof, gsamples

    meta = {
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "payload_log2_range": [args.min_log2, args.max_log2],
        "iters": args.iters,
    }
    if args.prior_extend:
        from mgwfbp_tpu.parallel.costmodel import (
            AlphaBeta,
            lookup_alpha_beta,
        )

        avail = len(jax.devices())
        mesh = make_mesh(MeshSpec(data=avail), devices=jax.devices())
        measured, _, gamma_samples = calibrate_mesh(mesh)
        prior_sizes = sorted(
            {int(s) for s in args.prior_world_sizes.split(",")} - {avail}
        )
        entries: dict = {avail: measured}
        for n in prior_sizes:
            ab = lookup_alpha_beta(args.prior_extend, n)
            entries[n] = AlphaBeta(
                alpha=ab.alpha, beta=ab.beta, gamma=measured.gamma,
                overlap=measured.overlap, pack_beta=measured.pack_beta,
                update_beta=measured.update_beta,
                ag_fraction=measured.ag_fraction,
            )
        out_model = ProfileFamily(entries=entries)
        meta["measured_fields"] = {
            str(avail): "all (sampled curve + gamma + pack_beta + overlap)",
            **{
                str(n): "gamma, pack_beta, update_beta, overlap "
                        f"(chip-measured at world={avail})"
                for n in prior_sizes
            },
        }
        meta["prior_fields"] = {
            str(n): f"alpha, beta ({args.prior_extend} prior — no "
                    "multi-chip fabric available to measure)"
            for n in prior_sizes
        }
        if gamma_samples:
            meta["gamma_samples_s"] = [
                [k, round(t, 6)] for k, t in gamma_samples
            ]
        report = {
            "measured_world": avail,
            "alpha_s": measured.alpha,
            "beta_s_per_byte": measured.beta,
            "gamma_s": measured.gamma,
            "overlap": measured.overlap,
            "pack_beta_s_per_byte": measured.pack_beta,
            "update_beta_s_per_byte": measured.update_beta,
            "ag_fraction": measured.ag_fraction,
            "prior_extended": prior_sizes,
            "out": args.out,
        }
    elif args.world_sizes:
        extents = sorted({int(s) for s in args.world_sizes.split(",")})
        avail = len(jax.devices())
        entries = {}
        summary = {}
        for n in extents:
            if n > avail:
                raise SystemExit(
                    f"--world-sizes {n}: only {avail} devices available"
                )
            mesh = make_mesh(MeshSpec(data=n), devices=jax.devices()[:n])
            model, _, _ = calibrate_mesh(mesh)
            entries[n] = model
            summary[str(n)] = {
                "alpha_s": model.alpha,
                "beta_s_per_byte": model.beta,
                "gamma_s": model.gamma,
                "overlap": model.overlap,
                "pack_beta_s_per_byte": model.pack_beta,
                "update_beta_s_per_byte": model.update_beta,
                "ag_fraction": model.ag_fraction,
            }
        out_model = ProfileFamily(entries=entries)
        meta["world_sizes"] = extents
        report = {"family": summary, "out": args.out}
    else:
        mesh = make_mesh(MeshSpec())
        out_model, prof, gamma_samples = calibrate_mesh(mesh)
        if gamma_samples:
            meta["gamma_samples_s"] = [
                [k, round(t, 6)] for k, t in gamma_samples
            ]
        report = {
            "alpha_s": out_model.alpha,
            "beta_s_per_byte": out_model.beta,
            "gamma_s": out_model.gamma,
            "overlap": out_model.overlap,
            "pack_beta_s_per_byte": out_model.pack_beta,
            "update_beta_s_per_byte": out_model.update_beta,
            "ag_fraction": out_model.ag_fraction,
            "samples": len(prof.sizes_bytes),
            "out": args.out,
        }
    import os

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    save_profile(args.out, out_model, meta=meta)
    print(json.dumps(report))
    return 0


def _two_level_main(args) -> int:
    """--two-level: per-axis (ici, dcn) calibration -> two_level profile
    (`profiling.profile_two_level`; schema-stamped via save_profile)."""
    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
    )

    apply_platform_overrides()
    enable_compile_cache()
    import os

    import jax

    from mgwfbp_tpu.parallel.costmodel import save_profile
    from mgwfbp_tpu.profiling import profile_two_level

    dcn = int(args.dcn)
    if dcn <= 1:
        raise SystemExit("--two-level needs --dcn > 1")
    avail = len(jax.devices())
    ici = int(args.ici) if args.ici else avail // dcn
    if ici < 1 or ici * dcn > avail:
        raise SystemExit(
            f"--two-level: {ici} x {dcn} does not fit the {avail} "
            "available device(s)"
        )
    sizes = tuple(2**k for k in range(args.min_log2, args.max_log2 + 1))
    model, raw = profile_two_level(
        ici, dcn, sizes=sizes, warmup=args.warmup, iters=args.iters,
        allgather=args.allgather,
    )
    meta = {
        "device_kind": jax.devices()[0].device_kind,
        "mesh": {"ici": ici, "dcn": dcn},
        "payload_log2_range": [args.min_log2, args.max_log2],
        "iters": args.iters,
        "fit": raw["fit"],
        "ag_fraction": raw["ag_fraction"],
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    save_profile(args.out, model, meta=meta)
    print(json.dumps({
        "ici": {
            "alpha_s": model.ici.alpha, "beta_s_per_byte": model.ici.beta,
            "ag_fraction": raw["ag_fraction"],
        },
        "dcn": {
            "alpha_s": model.dcn.alpha, "beta_s_per_byte": model.dcn.beta,
        },
        "mesh": {"ici": ici, "dcn": dcn},
        "samples": len(raw["sizes_bytes"]),
        "out": args.out,
    }))
    return 0


def _forward_main(args) -> int:
    """--forward: per-layer backward + forward benchmark -> layer profile
    (the tb_profile.json format trainers persist, schema_version=2)."""
    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
    )

    apply_platform_overrides()
    enable_compile_cache()
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.parallel.allreduce import arrival_order
    from mgwfbp_tpu.profiling import (
        LAYER_PROFILE_SCHEMA_VERSION,
        benchmark_trainer_backward,
        benchmark_trainer_forward,
    )
    from mgwfbp_tpu.train.step import create_train_state

    model, meta = zoo.create_model(args.model)
    rng = jax.random.PRNGKey(0)
    import optax

    state = create_train_state(
        rng, model,
        jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype),
        optax.sgd(0.1),
    )
    b = max(args.batch_size, 1)
    rs = np.random.RandomState(0)
    if meta.task == "lm":
        t = int(meta.input_shape[0])
        batch = {
            "x": jnp.asarray(
                rs.randint(0, meta.num_classes, (b, t)), jnp.int32
            ),
            "y": jnp.asarray(
                rs.randint(0, meta.num_classes, (b, t)), jnp.int32
            ),
        }
    elif meta.task == "ctc":
        # speech batch shape: (b, time, feat) float inputs with per-sample
        # lengths, label ids with label lengths (make_loss_fn's ctc branch
        # reads all four keys)
        t = int(meta.input_shape[0])
        label_t = max(t // 8, 4)
        batch = {
            "x": jnp.asarray(rs.randn(b, *meta.input_shape), jnp.float32),
            "input_lengths": jnp.full((b,), t, jnp.int32),
            "y": jnp.asarray(
                rs.randint(1, meta.num_classes, (b, label_t)), jnp.int32
            ),
            "label_lengths": jnp.full((b,), label_t, jnp.int32),
        }
    else:
        batch = {
            "x": jnp.asarray(
                rs.randn(b, *meta.input_shape), jnp.float32
            ),
            "y": jnp.asarray(rs.randint(0, meta.num_classes, (b,)), jnp.int32),
        }
    paths = jax.tree_util.tree_flatten_with_path(state.params)[0]
    names = [jax.tree_util.keystr(kp) for kp, _ in paths]
    perm = arrival_order(len(names), names=names)
    tb = benchmark_trainer_backward(
        model, meta, state.params, state.batch_stats, batch, perm,
        warmup=args.warmup, iters=args.iters, names=names,
    )
    tf = benchmark_trainer_forward(
        model, meta, state.params, state.batch_stats, batch, perm,
        warmup=args.warmup, iters=args.iters, names=names,
    )
    doc = {
        "schema_version": LAYER_PROFILE_SCHEMA_VERSION,
        "tb_s": list(tb),
        "tf_s": list(tf),
        "arrival_names": [names[j] for j in perm],
        "total_s": sum(tb),
        "tf_total_s": sum(tf),
        "source": getattr(tb, "source", "volume-prior"),
        "tf_source": getattr(tf, "source", "volume-prior"),
        "meta": {"model": args.model, "batch_size": b},
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f)
    print(json.dumps({
        "model": args.model,
        "tb_total_s": doc["total_s"],
        "tf_total_s": doc["tf_total_s"],
        "layers": len(doc["tb_s"]),
        "out": args.out,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
