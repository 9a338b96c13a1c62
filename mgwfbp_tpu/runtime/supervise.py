"""`python -m mgwfbp_tpu.runtime.supervise` — launch a coordinated
multi-process training group under the auto-resubmit supervisor.

    python -m mgwfbp_tpu.runtime.supervise --processes 2 -- \
        --dnn lenet --synthetic --telemetry --logdir logs \
        --checkpoint-dir checkpoints --ckpt-every-steps 25

Everything after ``--`` goes to `mgwfbp_tpu.train_cli` verbatim; the
supervisor exports MGWFBP_COORDINATOR / MGWFBP_NUM_PROCESSES /
MGWFBP_PROCESS_ID per child. Exit-code policy (README "Multi-host
runtime"): rc 75 resubmits the whole group with bounded exponential
backoff, rc 86 (watchdog abort) stops and points at the stack dumps.
Hard failures SELF-HEAL by default (ISSUE 20): crashes relaunch at the
same world, OOM-style SIGKILLs shrink to the survivor count (elastic
resume), wedged children are detected by the liveness monitor and the
group is drained and relaunched — all under per-class budgets;
``--no-heal`` restores the old teardown-and-propagate policy.
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional

from mgwfbp_tpu.runtime.supervisor import Supervisor, default_train_cmd


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mgwfbp-supervise",
        description="multi-process training group supervisor "
                    "(auto-resubmit on rc 75 / EX_TEMPFAIL)",
    )
    p.add_argument("--processes", type=int, required=True,
                   help="process-group size (MGWFBP_NUM_PROCESSES)")
    p.add_argument("--max-restarts", dest="max_restarts", type=int,
                   default=3,
                   help="resubmission budget for preempted (rc 75) groups")
    p.add_argument("--backoff-base", dest="backoff_base", type=float,
                   default=1.0,
                   help="first resubmit delay in seconds (doubles per "
                        "restart, capped by --backoff-max)")
    p.add_argument("--backoff-max", dest="backoff_max", type=float,
                   default=60.0)
    p.add_argument("--grace", type=float, default=10.0,
                   help="seconds between SIGTERM and SIGKILL when tearing "
                        "down stragglers")
    p.add_argument("--drain-grace", dest="drain_grace", type=float,
                   default=120.0,
                   help="seconds peers get to finish their agreed drain "
                        "after the first rc-75 exit")
    p.add_argument("--log-dir", dest="log_dir", default=None,
                   help="capture each child's stdout+stderr to "
                        "<log-dir>/p<idx>.i<incarnation>.log (default: "
                        "inherit this terminal)")
    p.add_argument("--port", type=int, default=None,
                   help="coordinator port (default: pick a free one per "
                        "incarnation)")
    p.add_argument("--fleet-port", dest="fleet_port", type=int,
                   default=None,
                   help="serve the group-level fan-in here "
                        "(/fleet/metrics merges every child's registry "
                        "metrics under a process label, /fleet/status "
                        "the live straggler table + group alarms; 0 = "
                        "ephemeral). Needs MGWFBP_METRICS_PORT exported "
                        "for the children")
    p.add_argument("--fleet-file", dest="fleet_file", default=None,
                   help="persist the children's ACTUAL metrics endpoints "
                        "here in Prometheus http_sd format (default: "
                        "<log-dir>/fleet.json when --log-dir is set)")
    p.add_argument("--resize-to", dest="resize_to", type=int, default=None,
                   help="elastic resize: relaunch the group at this many "
                        "processes at the next drain. With "
                        "MGWFBP_METRICS_PORT set the supervisor initiates "
                        "the drain itself (SIGTERM once a child reports a "
                        "completed step); the relaunched incarnation "
                        "resumes from the exact step — shard-native "
                        "checkpoints re-shard onto the new world size")
    p.add_argument("--no-heal", dest="heal", action="store_false",
                   default=True,
                   help="disable self-healing: any hard child failure "
                        "(crash/OOM/wedge) tears the group down and "
                        "propagates, the pre-ISSUE-20 policy")
    p.add_argument("--heal-max-restarts", dest="heal_max_restarts",
                   type=int, default=2,
                   help="per-failure-class healing budget (crash, "
                        "oom_kill, wedge, ... each get this many "
                        "relaunches before the supervisor gives up)")
    p.add_argument("--liveness-grace", dest="liveness_grace", type=float,
                   default=None,
                   help="seconds a child's /status step may stay frozen "
                        "(or its endpoint unreachable) before it is "
                        "declared wedged and the group is healed "
                        "(default: MGWFBP_LIVENESS_GRACE_S or 120)")
    p.add_argument("train_args", nargs=argparse.REMAINDER,
                   help="arguments for mgwfbp_tpu.train_cli (prefix "
                        "with --)")
    return p


def main(argv: Optional[list[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    train_args = args.train_args
    if train_args and train_args[0] == "--":
        train_args = train_args[1:]
    sup = Supervisor(
        default_train_cmd(train_args),
        args.processes,
        max_restarts=args.max_restarts,
        backoff_base_s=args.backoff_base,
        backoff_max_s=args.backoff_max,
        grace_s=args.grace,
        drain_grace_s=args.drain_grace,
        log_dir=args.log_dir,
        port=args.port,
        fleet_port=args.fleet_port,
        fleet_file=args.fleet_file,
        resize_to=args.resize_to,
        heal=args.heal,
        heal_max_restarts=args.heal_max_restarts,
        liveness_grace_s=args.liveness_grace,
    )
    return sup.run()


if __name__ == "__main__":
    sys.exit(main())
