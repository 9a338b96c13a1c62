"""Training CLI — the launcher surface of the framework.

Parity targets (SURVEY.md §2.3, L7/L6): reference dist_trainer.py __main__
(:105-143 argparse: batch-size, nworkers, dnn, dataset, nsteps-update,
compressor/density/threshold) and the exp_configs/*.conf presets sourced by
dist_mpi.sh / single.sh. One CLI serves both the single-host and multi-host
paths (`--coordinator`/`--num-processes`/`--process-id` replace mpirun +
hostfiles; on a TPU pod slice these come from the runtime environment).

Examples:
  python -m mgwfbp_tpu.train_cli --dnn resnet20 --max-epochs 2 --synthetic
  python -m mgwfbp_tpu.train_cli --dnn resnet50 --dataset imagenet \
      --policy mgwfbp --connection ici
  python -m mgwfbp_tpu.train_cli --dnn resnet20 --policy threshold \
      --threshold 524288000   # single-group baseline (batch_dist_mpi.sh grid)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

from mgwfbp_tpu.config import PRESETS, TrainConfig, make_config


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mgwfbp-train",
        description="TPU-native MG-WFBP distributed training",
    )
    p.add_argument("--dnn", default="resnet20", help=f"model: {sorted(PRESETS)}")
    p.add_argument("--dataset", default=None)
    p.add_argument("--data-dir", dest="data_dir", default=None)
    p.add_argument("--batch-size", dest="batch_size", type=int, default=None,
                   help="PER-DEVICE batch (weak scaling, reference semantics)")
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--max-epochs", dest="max_epochs", type=int, default=None)
    p.add_argument("--nsteps-update", dest="nsteps_update", type=int,
                   default=None, help="gradient accumulation micro-steps")
    p.add_argument("--policy", default=None,
                   choices=["mgwfbp", "auto", "threshold", "single", "wfbp",
                            "none"],
                   help="merge policy; 'auto' simulates every candidate "
                        "schedule under the calibrated cost model and picks "
                        "the argmin; 'none' = XLA-fused oracle")
    p.add_argument("--threshold", type=int, default=None,
                   help="elements per group for --policy threshold")
    p.add_argument("--connection", default=None,
                   help="cost-model link class: ici|dcn|56GbIB|10GbE")
    p.add_argument("--comm-profile", dest="comm_profile", default=None,
                   help="path to calibrated alpha-beta json (see calibrate)")
    p.add_argument("--dtype", default=None,
                   help="compute dtype: float32 | bfloat16 (mixed precision;"
                        " master weights stay float32)")
    p.add_argument("--comm-dtype", dest="comm_dtype", default=None,
                   help="wire dtype for collectives, e.g. bfloat16")
    p.add_argument("--norm-clip", dest="norm_clip", type=float, default=None)
    p.add_argument("--lr-schedule", dest="lr_schedule", default=None)
    p.add_argument("--logdir", default=None)
    p.add_argument("--checkpoint-dir", dest="checkpoint_dir", default=None)
    p.add_argument("--ckpt-every-steps", dest="ckpt_every_steps", type=int,
                   default=None,
                   help="mid-epoch step-indexed checkpoint every N optimizer "
                        "steps (preemption-safe resume restarts from the "
                        "exact step; 0 = epoch boundaries only)")
    p.add_argument("--ckpt-format", dest="ckpt_format", default=None,
                   choices=["sharded", "replicated"],
                   help="checkpoint payload format (default sharded): "
                        "'sharded' saves each process's own shard rows + "
                        "a manifest (no world-sized gather; restores "
                        "re-shard onto any world size — the elastic-"
                        "resize path); 'replicated' keeps the legacy "
                        "orbax gathered form for interchange with old "
                        "runs. Restore reads either format transparently")
    p.add_argument("--no-ckpt-async", action="store_true",
                   help="make mid-epoch shard-native checkpoints block "
                        "the step loop (by default the payload write "
                        "runs on a background thread and the commit "
                        "lands at the next agree-interval step)")
    p.add_argument("--no-grad-guard", action="store_true",
                   help="disable the non-finite-gradient guard (by default "
                        "a NaN/inf gradient drops that update, emits a "
                        "bad_step event, and K consecutive bad steps roll "
                        "back to the last checkpoint)")
    p.add_argument("--bad-step-limit", dest="bad_step_limit", type=int,
                   default=None,
                   help="consecutive non-finite steps before rollback to "
                        "the last checkpoint (0 disables rollback)")
    p.add_argument("--no-health-stats", action="store_true",
                   help="disable the in-jit training-health statistics "
                        "(per-group grad norms, update/param ratio riding "
                        "the metrics psum) and with them the online health "
                        "detector + anomaly flight recorder")
    p.add_argument("--pretrain", default=None,
                   help="checkpoint directory to initialize weights from")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--seq-parallel", dest="seq_parallel", type=int, default=None)
    p.add_argument("--num-steps", dest="num_steps", type=int, default=None,
                   help="LM window length (must divide by --seq-parallel)")
    p.add_argument("--layers-held", dest="layers_held", default=None,
                   metavar="N|FIRST:COUNT",
                   help="hold only COUNT layers of a model that can be held "
                        "in part, starting at layer FIRST: one pipeline "
                        "stage's share. A bare N is 0:N, the first N "
                        "(mellum2, granite4h, laguna_xs2, qwen3next take no "
                        "other FIRST; phi4flash's, xing4's and nemotron3s's "
                        "stage may start anywhere and its layers keep their "
                        "published indices)")
    p.add_argument("--experts-held", dest="experts_held", default=None,
                   metavar="FIRST:COUNT",
                   help="hold only COUNT experts of every sparse layer, "
                        "starting at expert FIRST (mellum2, laguna_xs2, "
                        "qwen3next, xing4, nemotron3s): one chip's share of an "
                        "expert-parallel "
                        "group. The router still scores all experts; what "
                        "the absent ones "
                        "would add is left out")
    p.add_argument("--tensor-share", dest="tensor_share", default=None,
                   metavar="INDEX:OF",
                   help="hold what member INDEX of OF chips that share each "
                        "layer's heads holds (nemotron3s): its Mamba heads "
                        "with the B/C groups they read, its query heads with "
                        "their key-value head, its columns of the shared "
                        "expert; router, latent projections and norms whole. "
                        "One chip's share of a tensor-parallel group, run "
                        "without the group's all-reduce: the partial result "
                        "goes on to the next layer")
    p.add_argument("--vocab-size", dest="vocab_size", type=int, default=None,
                   help="--dataset tokens: ids 0..N-1, which are also the "
                        "rows of the embedding and the head held here")
    p.add_argument("--optimizer", default=None, choices=["sgd", "adamw"])
    p.add_argument("--num-batches-per-epoch", dest="num_batches_per_epoch",
                   type=int, default=None,
                   help="cap optimizer steps per epoch (smoke runs)")
    p.add_argument("--synthetic", action="store_true",
                   help="force synthetic data (no dataset files needed)")
    p.add_argument("--no-augment", action="store_true",
                   help="disable training-time data augmentation")
    p.add_argument("--tensorboard", action="store_true",
                   help="stream scalar events to <logdir>/<tag>/events.jsonl "
                        "(mirrors into TensorBoard files if tensorboardX is "
                        "installed)")
    p.add_argument("--telemetry", action="store_true",
                   help="structured run observability: step spans, per-group "
                        "comm spans with exposed/hidden overlap accounting, "
                        "autotune/resize/checkpoint/watchdog events — one "
                        "schema-versioned JSONL per run; render with "
                        "tools/telemetry_report.py (README 'Telemetry')")
    p.add_argument("--telemetry-dir", dest="telemetry_dir", default=None,
                   help="directory for the telemetry event stream (default "
                        "<logdir>/<tag>; implies --telemetry)")
    p.add_argument("--metrics-port", dest="metrics_port", type=int,
                   default=None,
                   help="live observability HTTP port (/metrics Prometheus, "
                        "/healthz watchdog-wired liveness, /status run "
                        "JSON, /profile?steps=N on-demand deep-trace "
                        "window with per-merge-group device attribution); "
                        "0 = ephemeral, multi-host serves "
                        "port+process_index per process (actual bound "
                        "ports persist via MGWFBP_METRICS_PORT_FILE for "
                        "the supervisor's /fleet fan-in); implies "
                        "--telemetry (MGWFBP_METRICS_PORT)")
    p.add_argument("--compressor", default=None,
                   choices=["none", "topk"],
                   help="gradient compressor (reference --compressor)")
    p.add_argument("--density", type=float, default=None,
                   help="kept-fraction for sparsifying compressors; 0 = "
                        "auto (cost-model chooser, may fall back to dense)")
    p.add_argument("--comm-op", dest="comm_op", default=None,
                   choices=["all_reduce", "rs_ag", "hier", "rs_opt_ag",
                            "rs_fwd_ag"],
                   help="bucket collective: monolithic all-reduce, "
                        "reduce-scatter + all-gather (DeAR-style), the "
                        "hierarchical two-level ICI+DCN lowering (requires "
                        "--dcn-slices > 1), reduce-scatter + SHARDED "
                        "optimizer update + param all-gather (ZeRO-1-style "
                        "1/world optimizer state; same wire bytes as "
                        "rs_ag), or rs_fwd_ag — the CROSS-STEP pipeline: "
                        "rs_opt_ag whose param all-gather is deferred into "
                        "the next step's forward, hiding comm behind "
                        "forward compute too (params carried as 1/world "
                        "shards; multi-host capable — checkpoints are "
                        "shard-native)")
    p.add_argument("--dcn-slices", dest="dcn_slices", type=int, default=None,
                   help="slices of a multi-slice pod: adds an outer "
                        "data-parallel mesh axis whose collectives cross "
                        "DCN (two-level cost model)")
    p.add_argument("--autotune", action="store_true",
                   help="closed-loop schedule autotuning: race verified "
                        "candidate schedules for a few real training steps "
                        "each, refit the cost model from the measurements, "
                        "commit the measured argmin and cache it (see "
                        "README 'Autotuning')")
    p.add_argument("--autotune-steps", dest="autotune_steps", type=int,
                   default=None,
                   help="timed steps per raced candidate (plus one "
                        "warmup/compile step each)")
    p.add_argument("--schedule-cache", dest="schedule_cache", default=None,
                   help="directory for committed autotune schedules "
                        "(default profiles/schedule_cache); a second run "
                        "with the same schedule-cache key (see "
                        "parallel/autotune.py cache_key) "
                        "skips the race")
    p.add_argument("--no-profile-backward", action="store_true",
                   help="skip the offline backward benchmark (size prior)")
    p.add_argument("--epochs", type=int, default=None,
                   help="run this many epochs from the resume point "
                        "(default: through --max-epochs, absolute)")
    p.add_argument("--coordinator", default=None,
                   help="multi-host coordinator address host:port")
    p.add_argument("--num-processes", dest="num_processes", type=int, default=None)
    p.add_argument("--process-id", dest="process_id", type=int, default=None)
    p.add_argument("--print-config", action="store_true")
    return p


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    overrides = {
        k: getattr(args, k)
        for k in (
            "dataset", "data_dir", "batch_size", "lr", "max_epochs",
            "nsteps_update", "policy", "threshold", "connection",
            "comm_profile", "dtype", "comm_dtype", "norm_clip", "lr_schedule",
            "logdir", "checkpoint_dir", "pretrain", "seed", "seq_parallel",
            "num_steps", "num_batches_per_epoch", "compressor", "density",
            "comm_op", "dcn_slices", "autotune_steps", "schedule_cache",
            "telemetry_dir", "ckpt_every_steps", "bad_step_limit",
            "metrics_port", "ckpt_format", "layers_held", "experts_held",
            "tensor_share", "vocab_size", "optimizer",
        )
        if getattr(args, k, None) is not None
    }
    if args.no_augment:
        overrides["augment"] = False
    if args.no_grad_guard:
        overrides["grad_guard"] = False
    if args.no_ckpt_async:
        overrides["ckpt_async"] = False
    if args.no_health_stats:
        overrides["health_stats"] = False
    if args.tensorboard:
        overrides["tensorboard"] = True
    if args.telemetry or args.telemetry_dir or args.metrics_port is not None:
        # the live plane's aggregator is fed by the event stream, so
        # --metrics-port implies the stream (same as --telemetry-dir)
        overrides["telemetry"] = True
    if args.autotune:
        overrides["autotune"] = True
    return make_config(args.dnn, **overrides)


_LAUNCH_CHAIN = (
    "resolution chain: --coordinator/--num-processes/--process-id flags "
    "> MGWFBP_COORDINATOR/MGWFBP_NUM_PROCESSES/MGWFBP_PROCESS_ID "
    "> SLURM_NTASKS/SLURM_PROCID > OMPI_COMM_WORLD_SIZE/"
    "OMPI_COMM_WORLD_RANK; `python -m mgwfbp_tpu.runtime.supervise` "
    "exports the full MGWFBP_* contract for local process groups"
)


def resolve_multihost(
    args: argparse.Namespace, environ: Optional[dict] = None,
) -> tuple[Optional[str], Optional[int], Optional[int]]:
    """(coordinator, num_processes, process_id) from the launcher
    fallback chain: explicit flags, then the env chain owned by
    `parallel.mesh.resolve_launch_env` (MGWFBP_* — the supervisor's
    launch contract — then SLURM, then OpenMPI). All-None means a
    single-host launch. A multi-host signal that cannot be completed
    (num_processes > 1 but no coordinator or process id resolvable)
    exits with the recipe instead of handing a half-configured launch to
    jax.distributed (whose failure surfaces as a backend-probe traceback
    or a silent hang)."""
    from mgwfbp_tpu.parallel.mesh import resolve_launch_env

    try:
        env_coord, env_num, env_pid = resolve_launch_env(
            os.environ if environ is None else environ
        )
    except ValueError as e:  # garbage env int -> clean CLI failure
        raise SystemExit(str(e)) from None
    coordinator = args.coordinator or env_coord
    num = (
        args.num_processes
        if args.num_processes is not None
        else env_num
    )
    pid = args.process_id if args.process_id is not None else env_pid
    if coordinator is None and pid is None and (num is None or num <= 1):
        return None, None, None  # single-host
    missing = []
    if num is None:
        missing.append("worker count (--num-processes / "
                       "MGWFBP_NUM_PROCESSES)")
    if num is not None and num > 1:
        if coordinator is None:
            missing.append("coordinator address (--coordinator / "
                           "MGWFBP_COORDINATOR, host:port)")
        if pid is None:
            missing.append("process id (--process-id / MGWFBP_PROCESS_ID "
                           "/ launcher rank env)")
    if missing:
        raise SystemExit(
            "multi-host launch signaled but incomplete — missing "
            + "; ".join(missing) + ". " + _LAUNCH_CHAIN
        )
    return coordinator, num, pid


def main(argv: Optional[list[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    cfg = config_from_args(args)
    if args.print_config:
        print(json.dumps(cfg.__dict__, indent=2, default=str))
        return 0
    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
        preflight_backend,
    )

    apply_platform_overrides()
    enable_compile_cache()
    coordinator, num_processes, process_id = resolve_multihost(args)
    # any explicit distributed signal skips the probe: initialize() must
    # be the first backend touch on every process of a group
    multi_host = bool(
        coordinator is not None
        or process_id is not None
        or (num_processes or 0) > 1
    )
    from mgwfbp_tpu.parallel.mesh import init_distributed
    from mgwfbp_tpu.telemetry.phases import backend_span
    from mgwfbp_tpu.train.trainer import Trainer

    # the backend's start is a span of the set-up record (with telemetry
    # off the Trainer drops the record, and this span with it)
    with backend_span():
        if not multi_host:
            # fail fast instead of hanging in PJRT init while another
            # process holds the chip (MGWFBP_INIT_TIMEOUT_S tunes/disables).
            # Single-process only: jax.distributed.initialize() must run
            # before any backend touch, so a resolved multi-host launch
            # skips the probe — there the coordinator barrier itself
            # surfaces a dead host.
            preflight_backend()
        init_distributed(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    trainer = Trainer(
        cfg,
        profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None,
    )
    from mgwfbp_tpu.runtime.coordination import CoordinationTimeout
    from mgwfbp_tpu.utils.faults import PREEMPT_RC, Preempted

    try:
        metrics = trainer.fit(args.epochs)
    except Preempted as p:
        # graceful drain already checkpointed and emitted the preempt
        # event; EX_TEMPFAIL tells the supervisor "restart me to resume"
        print(json.dumps({
            "preempted": True, "signal": p.signal_name,
            "epoch": p.epoch, "iteration": p.iteration,
        }))
        return PREEMPT_RC
    except CoordinationTimeout as ct:
        # a peer died or wedged mid-collective: the DRAIN-LESS
        # restart-friendly exit (no checkpoint barrier can complete
        # either) — the supervisor's healer resumes the group from the
        # last COMMITTED shard-native step
        print(json.dumps({
            "coordination_timeout": True, "op": ct.op,
            "timeout_s": ct.timeout_s,
            "iteration": trainer.iteration,
        }), flush=True)
        # with a peer dead, the distributed runtime's atexit shutdown
        # barrier can never complete — it waits out the peer's heartbeat
        # timeout and then LOG(FATAL)s (SIGABRT), overriding the rc.
        # Flush our own state and leave without interpreter teardown.
        trainer.close()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(PREEMPT_RC)
    finally:
        trainer.close()
    print(json.dumps(metrics))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
