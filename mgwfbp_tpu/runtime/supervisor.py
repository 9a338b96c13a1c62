"""Process-group supervisor: launch, watch, resubmit.

The resilience layer (PR 5) made a preempted training process exit rc 75
(EX_TEMPFAIL) after draining to a step-indexed checkpoint — but nothing
restarted it, so "preemption-safe" ended at the process boundary. This
module closes the loop for a LOCAL multi-process group (one host driving
N coordinated processes; on a real pod each host runs its own train_cli
under the cluster's scheduler and only the rc contract below applies):

  rc 0   (all)   the run finished; exit 0.
  rc 75  (any)   graceful preemption drain: progress is checkpointed and
                 the whole group agreed to exit (runtime/coordination) —
                 resubmit the ENTIRE group after bounded exponential
                 backoff, until the restart budget is spent.
  rc 86  (any)   watchdog abort: a wedged device/runtime; the aborting
                 process faulthandler-dumped every thread's stack first.
                 Restarting a wedged device loops forever, so STOP and
                 surface where the dumps are.
  other  (any)   a HARD failure. With healing on (the default, ISSUE
                 20): classify it (classify_rc — crash / oom_kill /
                 term), SIGTERM the survivors so they drain through the
                 agreed-preempt path (or their coordination deadline),
                 then relaunch — same world when the slot looks
                 recoverable, SHRUNK to the survivor count for an
                 OOM-style SIGKILL (elastic resume re-shards off the
                 last committed shard-native step) — under per-class
                 restart budgets and a same-step crash-loop detector.
                 With heal=False: tear down the stragglers (SIGTERM,
                 grace, SIGKILL) and exit with the failing rc.

Self-healing also covers failures with NO exit code: a liveness monitor
in the `_watch` poll scrapes each child's /status (hard timeout — a hung
child can never hang the monitor) and declares a child *wedged* when its
step counter freezes past MGWFBP_LIVENESS_GRACE_S (or /healthz goes
503-sticky that long), *unreachable* when a previously-seen endpoint
stops answering; either verdict SIGTERMs the group and heals it the same
way. Every failure/heal decision is appended to the supervisor's own
telemetry stream (`telemetry.supervisor.jsonl`, process_index -1).

Launch contract (what each child sees): MGWFBP_COORDINATOR,
MGWFBP_NUM_PROCESSES, MGWFBP_PROCESS_ID — the env chain train_cli's
`resolve_multihost` reads. Everything else (fault plans, platform
overrides) is inherited, so `MGWFBP_FAULT_PLAN='preempt@step=4,proc=1'`
preempts exactly one process of the group and exercises the agreed
drain end to end.

Live observability plane (ISSUE 9): with MGWFBP_METRICS_PORT set, each
child serves /metrics /healthz /status on port + process_index
(telemetry/serve.py); the supervisor logs each child's port at launch,
and an rc-86 stop (a wedged device the watchdog aborted) includes every
still-reachable child's last /status snapshot in the stop message — the
dead group's final state lands in the supervisor log next to the stack
dumps it points at.

Fleet console (ISSUE 10): the supervisor exports a per-child
MGWFBP_METRICS_PORT_FILE so every child persists its ACTUAL bound port
(covering the MGWFBP_METRICS_PORT=0 ephemeral case, where the
port+process_index convention is simply wrong); the resolved targets are
persisted to a `fleet.json` sidecar in Prometheus http_sd/file_sd format,
and — with ``fleet_port`` set (`supervise --fleet-port`) — served live as
the group-level fan-in: /fleet/metrics merges every child's registry
metrics under a ``process`` label, /fleet/status synthesizes the live
straggler table, slowest-process attribution, and the group's active
alarms (telemetry/fleet.py).

Elastic resize (ISSUE 13): ``--resize-to M`` relaunches the NEXT
incarnation at M processes instead of N. With the live plane configured
the supervisor initiates the drain itself — SIGTERM to the whole group
once a child reports a completed step over /status (the agreed-preempt
path checkpoints shard-native and exits rc 75); without it the resize
applies at the next natural preemption. Children get
MGWFBP_ELASTIC_RESUME=1 so a relaunch at a new size finds the old
world's checkpoints under their sibling tag and re-shards
(train.trainer._resume_cross_world); /fleet/status carries the
transition as a ``resize`` view while it happens.

`python -m mgwfbp_tpu.runtime.supervise --processes 2 -- <train args>`
is the CLI (see runtime/supervise.py).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Callable, Optional, Sequence

from mgwfbp_tpu.utils.faults import PREEMPT_RC
from mgwfbp_tpu.utils.logging import get_logger
from mgwfbp_tpu.utils.platform import env_float

# utils/watchdog.py exits the process with os._exit(86) after dumping all
# thread stacks; keep in sync (the watchdog predates this constant)
WATCHDOG_RC = 86

# self-healing (ISSUE 20): how long a child's /status step may stay
# frozen (or its endpoint unreachable after having been seen) before the
# liveness monitor declares it wedged/unreachable and heals the group
LIVENESS_GRACE_ENV = "MGWFBP_LIVENESS_GRACE_S"
DEFAULT_LIVENESS_GRACE_S = 120.0

# failure classes a child exit decodes to (classify_rc) — the healing
# policy and the `failure` telemetry event share this vocabulary
HEAL_CLASSES = (
    "crash", "oom_kill", "wedge", "unreachable", "term",
)


def classify_rc(rc: int) -> str:
    """Decode one child returncode into the rc-policy vocabulary.

    Popen returncodes are negative for signal deaths (-N = killed by
    signal N); a shell-style 128+N is decoded the same way so the table
    holds for rcs relayed through an intermediate shell. SIGKILL is
    'oom_kill' — on Linux the OOM killer delivers exactly SIGKILL, and a
    sibling that was SIGKILLed by an operator heals identically (the
    slot's memory demand is suspect either way, so the healer SHRINKS
    rather than relaunching the same footprint). SIGTERM is 'term': an
    external/preempt-style stop that never drained — recoverable at the
    same world.
    """
    if rc == 0:
        return "ok"
    if rc == PREEMPT_RC:
        return "preempt"
    if rc == WATCHDOG_RC:
        return "watchdog"
    sig = -rc if rc < 0 else (rc - 128 if 128 < rc < 160 else None)
    if sig == int(signal.SIGKILL):
        return "oom_kill"
    if sig in (int(signal.SIGTERM), int(signal.SIGINT)):
        return "term"
    return "crash"


class _LivenessTracker:
    """Per-child liveness state machine for the `_watch` poll.

    Fed one `/status` scrape (or None) per child per poll; classifies
    each child as 'running', 'wedged' (alive but its step counter froze
    past the grace, or /status reports sticky-unhealthy past the grace),
    'unreachable' (endpoint stopped answering after having been seen),
    or 'unknown' (never seen — still booting/compiling; pre-step hangs
    are the in-process watchdog's domain, not ours). Pure host state
    driven by an injected clock — unit-testable without processes.
    """

    def __init__(self) -> None:
        self._step: dict[int, int] = {}
        self._step_t: dict[int, float] = {}
        self._seen: set[int] = set()
        self._unhealthy_t: dict[int, float] = {}
        self._unreachable_t: dict[int, float] = {}

    def observe(self, idx: int, status, now: float) -> None:
        if status is None:
            # only a child that HAS answered can become unreachable —
            # never-seen children are booting, not lost
            if idx in self._seen:
                self._unreachable_t.setdefault(idx, now)
            return
        self._seen.add(idx)
        self._unreachable_t.pop(idx, None)
        step = int(status.get("step") or 0)
        if step != self._step.get(idx):
            self._step[idx] = step
            self._step_t[idx] = now
        elif idx not in self._step_t:
            self._step_t[idx] = now
        if status.get("healthy") is False:
            self._unhealthy_t.setdefault(idx, now)
        else:
            self._unhealthy_t.pop(idx, None)

    def classify(self, idx: int, now: float, grace_s: float) -> str:
        if idx not in self._seen:
            return "unknown"
        t = self._unreachable_t.get(idx)
        if t is not None and now - t > grace_s:
            return "unreachable"
        t = self._unhealthy_t.get(idx)
        if t is not None and now - t > grace_s:
            return "wedged"
        # a frozen step only counts once the child has EVER stepped:
        # compile/bootstrap legitimately sits at step 0 for a long time
        if (
            self._step.get(idx, 0) >= 1
            and now - self._step_t.get(idx, now) > grace_s
        ):
            return "wedged"
        return "running"

    def max_step(self) -> int:
        """Highest step any child ever reported (crash-loop detection:
        the same max step across consecutive healed incarnations means
        the group is dying at the same point every life)."""
        return max(self._step.values(), default=0)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@dataclasses.dataclass
class GroupResult:
    """Outcome of one incarnation of the process group."""

    incarnation: int
    returncodes: list[int]

    @property
    def ok(self) -> bool:
        return all(rc == 0 for rc in self.returncodes)

    @property
    def preempted(self) -> bool:
        """Restart-friendly: at least one drain, nothing worse."""
        return (
            any(rc == PREEMPT_RC for rc in self.returncodes)
            and all(rc in (0, PREEMPT_RC) for rc in self.returncodes)
        )

    @property
    def watchdog_abort(self) -> bool:
        return any(rc == WATCHDOG_RC for rc in self.returncodes)


class Supervisor:
    """Launch a coordinated N-process group and apply the rc policy.

    `base_cmd` is the per-process command (default: this interpreter's
    train_cli); process index, count, and coordinator land in the child
    ENV, not argv, so the same command line serves every slot and every
    incarnation. Injectable `sleep` keeps the backoff testable.
    """

    def __init__(
        self,
        base_cmd: Sequence[str],
        processes: int,
        *,
        max_restarts: int = 3,
        backoff_base_s: float = 1.0,
        backoff_max_s: float = 60.0,
        grace_s: float = 10.0,
        drain_grace_s: float = 120.0,
        log_dir: Optional[str] = None,
        env: Optional[dict] = None,
        port: Optional[int] = None,
        fleet_port: Optional[int] = None,
        fleet_file: Optional[str] = None,
        resize_to: Optional[int] = None,
        heal: bool = True,
        heal_max_restarts: int = 2,
        heal_same_step_limit: int = 3,
        liveness_grace_s: Optional[float] = None,
        sleep: Callable[[float], None] = time.sleep,
    ):
        if processes < 1:
            raise ValueError(f"processes must be >= 1, got {processes}")
        if resize_to is not None and resize_to < 1:
            raise ValueError(f"resize_to must be >= 1, got {resize_to}")
        self.base_cmd = list(base_cmd)
        self.processes = int(processes)
        self.max_restarts = int(max_restarts)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.grace_s = float(grace_s)
        self.drain_grace_s = float(drain_grace_s)
        self.log_dir = log_dir
        self.env = dict(env if env is not None else os.environ)
        self.port = port
        self.sleep = sleep
        self.log = get_logger("mgwfbp.supervisor")
        self.results: list[GroupResult] = []
        # last /status of each still-alive peer, captured by _watch at
        # the moment an rc-86 exit is first observed (None = no abort
        # seen yet this incarnation)
        self._status_snapshots: Optional[dict] = None
        # fleet console (ISSUE 10): fan-in server port (None = off,
        # 0 = ephemeral), http_sd sidecar path, port-file directory
        self.fleet_port = fleet_port
        self._fleet_file_explicit = fleet_file is not None
        self.fleet_file = fleet_file or (
            os.path.join(log_dir, "fleet.json") if log_dir else None
        )
        self.fleet_server = None
        self._ports_dir: Optional[str] = None
        self._last_fleet_targets: Optional[dict] = None
        # supervisor-driven elastic resize (ISSUE 13): relaunch the next
        # incarnation at `resize_to` processes once the current one
        # drains. With the live plane configured the supervisor TRIGGERS
        # the drain itself (SIGTERM to the whole group as soon as a child
        # reports a completed step — the agreed-preempt path takes it
        # from there); otherwise the resize applies at the next natural
        # preemption.
        self.resize_to = resize_to
        self._initial_processes = int(processes)
        self._resize_signaled = False
        self._resize_poll_t = 0.0
        self._resize_no_metrics_warned = False
        # self-healing (ISSUE 20): hard failures (crash/oom/wedge/
        # unreachable) heal the group instead of tearing it down —
        # relaunch at the same world when the slot looks recoverable,
        # SHRINK to the survivor count (elastic resume) when not, under
        # per-failure-class restart budgets. heal=False keeps the old
        # teardown-and-propagate policy verbatim.
        self.heal = bool(heal)
        self.heal_max_restarts = int(heal_max_restarts)
        self.heal_same_step_limit = int(heal_same_step_limit)
        # garbage in the env knob must fail NOW, naming the variable —
        # not mid-heal (env_float = the MGWFBP_BARRIER_TIMEOUT_S contract)
        self.liveness_grace_s = (
            float(liveness_grace_s)
            if liveness_grace_s is not None
            else env_float(
                LIVENESS_GRACE_ENV, DEFAULT_LIVENESS_GRACE_S,
                environ=self.env,
            )
        )
        self._liveness = _LivenessTracker()
        self._liveness_poll_t = 0.0
        # the failure the current incarnation is dying of: set by the
        # liveness monitor (wedge/unreachable — it SIGTERMs the group,
        # so every child exits 75 and the rc vector alone would look
        # like a plain preempt) or by the hard-exit path in _watch
        self._pending_failure: Optional[dict] = None
        # slot index -> rc for children that exited HARD this
        # incarnation, captured before teardown pollutes the rc vector
        # with its own -15/-9
        self._failed_slots: dict[int, int] = {}
        self._heal_restarts: dict[str, int] = {}
        # max observed step per healed incarnation (crash-loop detection)
        self._crash_steps: list[int] = []
        self._postmortem_paths: list[str] = []
        self._incarnation = 0
        self._events = None  # lazy supervisor-stream EventWriter

    # -- launch ------------------------------------------------------------
    def _metrics_base_port(self) -> Optional[int]:
        """The group's configured metrics base port (child i serves
        base + i — telemetry/serve.resolve_metrics_port), or None when
        the plane is off or the base is ephemeral (0: per-child ports are
        unknowable from outside)."""
        raw = (self.env.get("MGWFBP_METRICS_PORT") or "").strip()
        if not raw:
            return None
        try:
            base = int(raw)
        except ValueError:
            return None
        return base if base > 0 else None

    def _metrics_enabled(self) -> bool:
        """True when the group's live plane is configured at all
        (MGWFBP_METRICS_PORT set to anything, including 0/ephemeral)."""
        raw = (self.env.get("MGWFBP_METRICS_PORT") or "").strip()
        if not raw:
            return False
        try:
            return int(raw) >= 0
        except ValueError:
            return False

    def _port_file(self, idx: int) -> str:
        """Per-child metrics port-file sidecar path (the child's
        telemetry/serve writes its ACTUAL bound port there)."""
        if self._ports_dir is None:
            if self.log_dir:
                self._ports_dir = self.log_dir
                os.makedirs(self._ports_dir, exist_ok=True)
            else:
                import tempfile

                self._ports_dir = tempfile.mkdtemp(
                    prefix="mgwfbp_fleet_ports_"
                )
        return os.path.join(self._ports_dir, f"metrics_port.p{idx}.json")

    def _child_targets(self) -> dict:
        """process index -> (host, port) of every currently-resolvable
        child metrics endpoint: the child-written port file (the ACTUAL
        bound port — authoritative, and the only source in the ephemeral
        base==0 case), falling back to the base+index convention for
        children that have not bound yet."""
        if not self._metrics_enabled():
            return {}
        import json as _json

        base = self._metrics_base_port()
        targets: dict = {}
        for i in range(self.processes):
            path = self._port_file(i)
            try:
                with open(path) as f:
                    doc = _json.load(f)
                targets[i] = (
                    str(doc.get("host") or "127.0.0.1"),
                    int(doc["port"]),
                )
                continue
            except (OSError, ValueError, KeyError, TypeError):
                pass
            if base is not None:
                targets[i] = ("127.0.0.1", base + i)
        return targets

    def _refresh_fleet(self) -> None:
        """Re-resolve the child target map; persist `fleet.json`
        (Prometheus http_sd format) whenever it changes. Called from the
        `_watch` poll loop — targets appear as children bind their
        (possibly ephemeral) ports and write their port files."""
        if not self._metrics_enabled():
            return
        targets = self._child_targets()
        if targets == self._last_fleet_targets:
            return
        if self.fleet_file and targets:
            from mgwfbp_tpu.telemetry.fleet import write_fleet_sd

            try:
                write_fleet_sd(self.fleet_file, targets)
            except OSError as e:
                # do NOT record the targets: the sidecar is stale, and a
                # stable group would otherwise never retry the write
                self.log.warning(
                    "could not write fleet sidecar %s: %s",
                    self.fleet_file, e,
                )
                return
            self.log.info(
                "fleet targets -> %s (%s)", self.fleet_file,
                ", ".join(
                    f"p{i}={h}:{p}" for i, (h, p) in sorted(targets.items())
                ),
            )
        self._last_fleet_targets = dict(targets)

    def _emit(self, event: str, **fields) -> None:
        """Append one record to the supervisor's OWN telemetry stream
        (`telemetry.supervisor.jsonl` — deliberately outside
        find_stream_paths' per-process pattern, so per-run merges only
        see it when asked for explicitly). process_index -1 marks the
        emitter as nobody's training rank. Best-effort: telemetry must
        never be what kills the healer."""
        if not self.log_dir:
            return
        try:
            if self._events is None:
                from mgwfbp_tpu.telemetry.events import EventWriter

                os.makedirs(self.log_dir, exist_ok=True)
                self._events = EventWriter(
                    os.path.join(
                        self.log_dir, "telemetry.supervisor.jsonl"
                    ),
                    run={"process_index": -1, "role": "supervisor"},
                )
            self._events.emit(event, **fields)
        except Exception as e:  # noqa: BLE001 — observability best-effort
            self.log.warning(
                "could not emit %s telemetry event: %s", event, e
            )

    def _fleet_meta(self) -> dict:
        """Supervisor-level fields for /fleet/status."""
        meta = {
            "incarnation": len(self.results),
            "processes_configured": self.processes,
        }
        meta["heal"] = {
            "enabled": self.heal,
            "restarts": dict(self._heal_restarts),
            "budget": self.heal_max_restarts,
            "liveness_grace_s": self.liveness_grace_s,
        }
        if self._pending_failure is not None:
            meta["heal"]["pending_failure"] = dict(self._pending_failure)
        if self.resize_to is not None:
            # the transition is fleet-visible: pending while the group
            # still runs at the old size, done once an incarnation
            # launched at the target
            meta["resize"] = {
                "from": self._initial_processes,
                "to": self.resize_to,
                "state": (
                    "done"
                    if self.processes == self.resize_to
                    else "pending"
                ),
                "triggered": bool(self._resize_signaled),
            }
        return meta

    def _resize_pending(self) -> bool:
        return (
            self.resize_to is not None
            and self.resize_to != self.processes
        )

    def _maybe_trigger_resize(self, procs) -> None:
        """--resize-to with a healthy group: initiate the drain ourselves
        — SIGTERM the whole group once any child reports a COMPLETED step
        over /status (signal handlers are armed by then; an earlier
        signal would kill a child mid-bootstrap instead of draining it).
        Needs the live plane; without it the resize waits for the next
        natural preemption."""
        if not self._resize_pending() or self._resize_signaled:
            return
        if not self._metrics_enabled():
            if not self._resize_no_metrics_warned:
                self._resize_no_metrics_warned = True
                self.log.warning(
                    "--resize-to %d: MGWFBP_METRICS_PORT is not set, so "
                    "the supervisor cannot see training progress to time "
                    "the drain; the resize will apply at the next "
                    "preemption (rc 75) instead", self.resize_to,
                )
            return
        now = time.monotonic()
        if now - self._resize_poll_t < 0.5:  # throttle the /status polls
            return
        self._resize_poll_t = now
        for i in range(self.processes):
            st = self._child_status(i)
            if st and int(st.get("step") or 0) >= 1:
                self.log.warning(
                    "resize %d -> %d: draining the group (SIGTERM; the "
                    "agreed-preempt path checkpoints and exits rc 75)",
                    self.processes, self.resize_to,
                )
                self._resize_signaled = True
                for p in procs:
                    if p.poll() is None:
                        try:
                            p.send_signal(signal.SIGTERM)
                        except OSError:
                            pass
                return

    def _start_fleet_server(self) -> None:
        """One fan-in server for the supervisor's lifetime (targets
        re-resolve per request, so resubmitted incarnations with fresh
        ephemeral ports keep being reachable through the same URL)."""
        if self.fleet_port is None or self.fleet_server is not None:
            return
        if not self._metrics_enabled():
            self.log.warning(
                "fleet fan-in requested but MGWFBP_METRICS_PORT is not "
                "set for the children; /fleet endpoints disabled"
            )
            return
        from mgwfbp_tpu.telemetry.fleet import start_fleet_server

        self.fleet_server = start_fleet_server(
            self._child_targets, self.fleet_port,
            meta_provider=self._fleet_meta,
        )

    def _child_status(self, idx: int, timeout_s: float = 2.0):
        """Last /status snapshot of child `idx`, or None when the plane
        is off / the child is gone. Resolves the child's REAL endpoint
        through the port-file map (ephemeral ports included)."""
        target = self._child_targets().get(idx)
        if target is None:
            return None
        import json as _json
        import urllib.request

        host, port = target
        try:
            with urllib.request.urlopen(
                f"http://{host}:{port}/status", timeout=timeout_s
            ) as resp:
                return _json.loads(resp.read().decode())
        except Exception:  # noqa: BLE001 — a dead child's port refusing
            # is the expected case; the snapshot is best-effort
            return None

    def _child_env(self, idx: int, port: int, incarnation: int = 0) -> dict:
        env = dict(self.env)
        env["MGWFBP_COORDINATOR"] = f"127.0.0.1:{port}"
        env["MGWFBP_NUM_PROCESSES"] = str(self.processes)
        env["MGWFBP_PROCESS_ID"] = str(idx)
        # which life this is: the fault plan's HARD kinds (kill/wedge —
        # drain-less, so a healed relaunch resumes BELOW the fault step)
        # key on this so a chaos fault fires in exactly one incarnation
        # instead of re-firing every life (faults.for_incarnation)
        env["MGWFBP_INCARNATION"] = str(incarnation)
        # supervised groups may resume across world-size changes: a
        # relaunch at a new --processes finds the old world's checkpoints
        # under their sibling tag and re-shards (trainer
        # _resume_cross_world). Explicit operator values win.
        env.setdefault("MGWFBP_ELASTIC_RESUME", "1")
        if self._metrics_enabled():
            # the child persists its ACTUAL bound metrics port here
            # (telemetry/serve.write_port_file) — the fleet fan-in and
            # fleet.json read real ports, never the base+index guess
            env["MGWFBP_METRICS_PORT_FILE"] = self._port_file(idx)
            if self.fleet_port is not None or self._fleet_file_explicit:
                # cross-host seam: with the fleet plane armed (a fan-in
                # server or a fleet.json sidecar for an external
                # Prometheus) the children default to a ROUTABLE bind so
                # off-host consumers can reach them, and the port file
                # advertises the resolved routable address. Scoped to
                # the armed-fleet case deliberately: the endpoints are
                # unauthenticated, so a plain supervised run keeps the
                # loopback default (and explicit operator values always
                # win).
                env.setdefault("MGWFBP_METRICS_HOST", "0.0.0.0")
        return env

    def _spawn(self, idx: int, incarnation: int, port: int):
        stdout = stderr = None
        if self.log_dir:
            os.makedirs(self.log_dir, exist_ok=True)
            path = os.path.join(
                self.log_dir, f"p{idx}.i{incarnation}.log"
            )
            stdout = open(path, "w", buffering=1)
            stderr = subprocess.STDOUT
        return subprocess.Popen(
            self.base_cmd,
            env=self._child_env(idx, port, incarnation),
            stdout=stdout,
            stderr=stderr,
        ), stdout

    def _run_group(self, incarnation: int) -> GroupResult:
        self._status_snapshots = None  # fresh capture per incarnation
        # fresh failure/liveness state per incarnation (the PREVIOUS
        # incarnation's verdicts were consumed by the rc policy already)
        self._failed_slots = {}
        self._pending_failure = None
        self._liveness = _LivenessTracker()
        self._liveness_poll_t = 0.0
        port = self.port if self.port is not None else free_port()
        self.log.info(
            "incarnation %d: launching %d process(es) (coordinator "
            "127.0.0.1:%d)", incarnation, self.processes, port,
        )
        if self._metrics_enabled():
            # stale port files describe the PREVIOUS incarnation's
            # (possibly ephemeral) binds; drop them so the fan-in never
            # scrapes a dead port as live
            for i in range(self.processes):
                try:
                    os.unlink(self._port_file(i))
                except OSError:
                    pass
            self._last_fleet_targets = None
            self._start_fleet_server()
        metrics_base = self._metrics_base_port()
        if metrics_base is not None:
            for i in range(self.processes):
                self.log.info(
                    "incarnation %d: process %d metrics at "
                    "http://127.0.0.1:%d (/metrics /healthz /status)",
                    incarnation, i, metrics_base + i,
                )
        procs, logs = [], []
        for i in range(self.processes):
            p, f = self._spawn(i, incarnation, port)
            procs.append(p)
            logs.append(f)
        try:
            rcs = self._watch(procs)
        finally:
            for f in logs:
                if f is not None:
                    f.close()
        result = GroupResult(incarnation, rcs)
        self.results.append(result)
        self.log.info(
            "incarnation %d: exit codes %s", incarnation, rcs,
        )
        return result

    def _capture_snapshots(self, procs) -> None:
        """Last /status of every still-alive peer, captured the moment a
        hard/watchdog exit is first observed — by the time run() applies
        the rc policy every child is torn down and the ports refuse."""
        if self._status_snapshots is not None:
            return
        self._status_snapshots = {
            i: s for i, p in enumerate(procs)
            if p.poll() is None
            and (s := self._child_status(i)) is not None
        }
        for i, s in sorted(self._status_snapshots.items()):
            for b in (s.get("postmortems") or {}).get("recent", []):
                if b.get("path"):
                    self._postmortem_paths.append(
                        f"p{i}: {b['path']}"
                    )

    def _poll_liveness(self, procs) -> None:
        """The wedge/unreachable detector (ISSUE 20): feed each alive
        child's /status scrape (hard-timeout, same as the fleet fan-in's)
        into the liveness tracker; the first child classified wedged or
        unreachable marks the incarnation's pending failure and SIGTERMs
        the whole group — survivors drain through the agreed-preempt
        path (or their coordination deadline) and the rc policy heals."""
        if (
            not self.heal
            or self._pending_failure is not None
            or self._failed_slots
            or not self._metrics_enabled()
        ):
            return
        now = time.monotonic()
        if now - self._liveness_poll_t < 1.0:  # throttle the scrapes
            return
        self._liveness_poll_t = now
        # sweep EVERY alive child before passing a verdict: a single
        # wedged process freezes its peers at the next merged collective
        # within the same grace window, so the step-freeze signal cannot
        # root-cause which peer wedged first — the honest verdict names
        # the whole frozen set
        culprits: list[tuple[int, str, int]] = []
        for i, p in enumerate(procs):
            if p.poll() is not None:
                continue
            self._liveness.observe(i, self._child_status(i), now)
            verdict = self._liveness.classify(
                i, now, self.liveness_grace_s
            )
            if verdict in ("wedged", "unreachable"):
                culprits.append(
                    (i, verdict, self._liveness._step.get(i, 0))
                )
        if not culprits:
            return
        cls = culprits[0][1]
        target = ",".join(f"p{i}" for i, _, _ in culprits)
        step = max(s for _, _, s in culprits)
        self._pending_failure = {
            "class": cls, "target": target, "step": step,
        }
        self.log.warning(
            "%s is %s (step frozen at %d past %.0fs liveness grace); "
            "SIGTERMing the group to drain and heal",
            target, cls, step, self.liveness_grace_s,
        )
        self._emit(
            "failure", **{"class": cls}, target=target, step=step,
        )
        self._capture_snapshots(procs)
        for q in procs:
            if q.poll() is None:
                try:
                    q.send_signal(signal.SIGTERM)
                except OSError:
                    pass

    def _watch(self, procs) -> list[int]:
        """Poll until every process exits; once ANY process exits,
        stragglers get a bounded window before teardown. A group member
        that outlives its peers is wedged — once a peer is gone its next
        collective can never complete (a clean rc-0 exit takes the
        coordination service down just as surely as a crash) — so
        waiting forever would hang the supervisor exactly the way the
        job hung."""
        deadline = None  # armed on the first exit of any kind
        grace = None
        while True:
            # lazily resolve child metrics endpoints as they bind and
            # keep the fleet.json sidecar current (no-op when the live
            # plane is off or nothing changed)
            self._refresh_fleet()
            # --resize-to: drain a healthy group once it is stepping
            self._maybe_trigger_resize(procs)
            # wedge/unreachable detection (no-op once a failure is known)
            self._poll_liveness(procs)
            pending = [p for p in procs if p.poll() is None]
            done = [p.returncode for p in procs if p.returncode is not None]
            if WATCHDOG_RC in done and self._status_snapshots is None:
                self._capture_snapshots(procs)
            hard = {
                i: int(p.returncode) for i, p in enumerate(procs)
                if p.returncode is not None
                and p.returncode not in (0, PREEMPT_RC, WATCHDOG_RC)
            }
            if (
                self.heal
                and hard
                and not self._failed_slots
                and WATCHDOG_RC not in done
            ):
                # hard exit(s): capture the failed slots NOW (teardown
                # pollutes the rc vector with its own -15/-9 later) and
                # SIGTERM the survivors — blocked in a collective their
                # dead peer will never join, they drain via the agreed
                # preempt path or their coordination deadline (rc 75)
                self._failed_slots = dict(hard)
                self._capture_snapshots(procs)
                for i, rc in sorted(hard.items()):
                    cls = classify_rc(rc)
                    self.log.warning(
                        "process %d exited HARD (rc %d, class %s); "
                        "SIGTERMing survivors to drain for healing",
                        i, rc, cls,
                    )
                    self._emit(
                        "failure", **{"class": cls}, target=f"p{i}",
                        rc=rc, step=self._liveness.max_step(),
                    )
                for p in procs:
                    if p.poll() is None:
                        try:
                            p.send_signal(signal.SIGTERM)
                        except OSError:
                            pass
            if not pending:
                return [int(p.returncode) for p in procs]
            if done and deadline is None:
                # rc 0/75: peers are finishing up or drain-agreeing and
                # checkpointing — give them the drain window. A hard
                # exit under healing gets the SAME window: survivors
                # must ride out their coordination deadline to exit
                # clean. Anything else: broken group, short fuse.
                clean = all(rc in (0, PREEMPT_RC) for rc in done)
                grace = (
                    self.drain_grace_s
                    if clean or (self.heal and self._failed_slots)
                    else self.grace_s
                )
                deadline = time.monotonic() + grace
            if deadline is not None and time.monotonic() > deadline:
                self.log.warning(
                    "tearing down %d straggler(s) %.0fs after first "
                    "failure", len(pending), grace,
                )
                self._teardown(pending)
                return [
                    int(p.returncode) if p.returncode is not None else -9
                    for p in procs
                ]
            time.sleep(0.05)

    def _teardown(self, procs) -> None:
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        t0 = time.monotonic()
        while any(p.poll() is None for p in procs):
            if time.monotonic() - t0 > self.grace_s:
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                for p in procs:
                    p.wait()
                return
            time.sleep(0.05)

    # -- policy ------------------------------------------------------------
    def backoff_s(self, restart: int) -> float:
        """Bounded exponential: base * 2^(restart-1), capped."""
        return min(
            self.backoff_base_s * (2.0 ** max(restart - 1, 0)),
            self.backoff_max_s,
        )

    def run(self) -> int:
        try:
            return self._run_policy()
        finally:
            if self.fleet_server is not None:
                self.fleet_server.close()
                self.fleet_server = None

    def _heal_exit_rc(self) -> int:
        """The rc a give-up heal stop propagates: the failed child's own
        positive rc when it had one, the conventional 128+signal for a
        signal death, 1 for a wedge/unreachable (no child rc to speak
        of — the group was SIGTERMed by the monitor)."""
        rcs = sorted(self._failed_slots.values())
        pos = [rc for rc in rcs if rc > 0]
        if pos:
            return pos[0]
        neg = [rc for rc in rcs if rc < 0]
        if neg:
            return 128 + abs(neg[0])
        return 1

    def _heal_or_stop(self, result: GroupResult) -> Optional[int]:
        """Apply the healing policy to one hard-failed incarnation.

        Returns None when the group was healed (caller relaunches) or
        the final exit rc when the policy gives up. The policy matrix:

          oom_kill     -> SHRINK to the survivor count (the slot's
                          memory footprint is suspect; elastic resume
                          re-shards off the last committed step)
          crash/term   -> relaunch at the SAME world (slot recoverable)
          wedge/
          unreachable  -> relaunch at the SAME world
          any class    -> bounded by its own restart budget
                          (heal_max_restarts per class) and a crash-loop
                          detector (same max step heal_same_step_limit
                          consecutive lives -> stop, postmortems named)
        """
        if self._pending_failure is not None:
            cls = str(self._pending_failure["class"])
            target = str(self._pending_failure["target"])
        else:
            idx = min(self._failed_slots)
            cls = classify_rc(self._failed_slots[idx])
            target = f"p{idx}"
        step = self._liveness.max_step()
        bundles = (
            " Postmortem bundle(s): " + "; ".join(self._postmortem_paths)
            if self._postmortem_paths else ""
        )
        self._crash_steps.append(step)
        tail = self._crash_steps[-self.heal_same_step_limit:]
        if (
            len(tail) >= self.heal_same_step_limit
            and len(set(tail)) == 1
        ):
            self.log.error(
                "crash loop: %d consecutive incarnation(s) died at step "
                "%d (last failure: %s on %s) — the fault is "
                "deterministic, healing cannot fix it; stopping.%s",
                len(tail), step, cls, target, bundles,
            )
            self._emit(
                "heal", action="stop", reason="crash_loop",
                **{"class": cls}, target=target, step=step,
            )
            return self._heal_exit_rc()
        used = self._heal_restarts.get(cls, 0)
        if used >= self.heal_max_restarts:
            self.log.error(
                "%s on %s but the %r heal budget (%d) is spent; "
                "stopping.%s",
                cls, target, cls, self.heal_max_restarts, bundles,
            )
            self._emit(
                "heal", action="stop", reason="budget",
                **{"class": cls}, target=target, restarts=used,
            )
            return self._heal_exit_rc()
        self._heal_restarts[cls] = used + 1
        survivors = self.processes - len(self._failed_slots)
        shrink = cls == "oom_kill" and 1 <= survivors < self.processes
        delay = self.backoff_s(self._heal_restarts[cls])
        if shrink:
            self.log.warning(
                "healing %s on %s: SHRINKING %d -> %d process(es) "
                "(elastic resume off the last committed shard-native "
                "step) in %.1fs (%s heal %d/%d)",
                cls, target, self.processes, survivors, delay, cls,
                self._heal_restarts[cls], self.heal_max_restarts,
            )
            self._emit(
                "heal", action="shrink", **{"class": cls},
                target=target, old_world=self.processes,
                world=survivors, restarts=self._heal_restarts[cls],
            )
            self.processes = survivors
        else:
            self.log.warning(
                "healing %s on %s: relaunching at the same world (%d) "
                "in %.1fs (%s heal %d/%d)",
                cls, target, self.processes, delay, cls,
                self._heal_restarts[cls], self.heal_max_restarts,
            )
            self._emit(
                "heal", action="relaunch", **{"class": cls},
                target=target, world=self.processes,
                restarts=self._heal_restarts[cls],
            )
        self.sleep(delay)
        return None

    def _run_policy(self) -> int:
        restarts = 0
        incarnation = 0
        while True:
            result = self._run_group(incarnation)
            if result.ok:
                if restarts:
                    self.log.info(
                        "group completed after %d resubmission(s)", restarts,
                    )
                return 0
            if result.watchdog_abort:
                where = (
                    f" (per-process logs under {self.log_dir})"
                    if self.log_dir else " (see the group's stderr)"
                )
                # the dead group's final state: _watch captured every
                # still-alive peer's /status at the moment the rc-86
                # exit was observed (the group is fully torn down by
                # now), so the post-mortem starts from the supervisor
                # log, not from N scattered ports that no longer answer
                snapshots = self._status_snapshots or {}
                detail = ""
                if snapshots:
                    import json as _json

                    detail = " Last /status snapshot(s): " + "; ".join(
                        f"p{i}: {_json.dumps(s)}"
                        for i, s in sorted(snapshots.items())
                    )
                    # the flight recorder's evidence (ISSUE 12): any
                    # postmortem bundles the children wrote before the
                    # abort are the post-mortem's starting point — name
                    # them explicitly next to the stack-dump pointer
                    bundles = [
                        f"p{i}: {b.get('path')}"
                        for i, s in sorted(snapshots.items())
                        for b in (s.get("postmortems") or {}).get(
                            "recent", []
                        )
                        if b.get("path")
                    ]
                    if bundles:
                        detail += (
                            " Postmortem bundle(s): " + "; ".join(bundles)
                        )
                self.log.error(
                    "watchdog abort (rc %d): a process dumped all thread "
                    "stacks before exiting%s. A wedged device does "
                    "not heal on restart — NOT resubmitting.%s",
                    WATCHDOG_RC, where, detail,
                )
                return WATCHDOG_RC
            # self-healing (ISSUE 20): a hard failure this incarnation —
            # a slot that exited crash/oom/term, or a wedge/unreachable
            # verdict from the liveness monitor (whose SIGTERM made the
            # rc vector look like a plain preempt) — takes the healing
            # policy, NOT the free preempt resubmit below
            if self.heal and (
                self._pending_failure is not None or self._failed_slots
            ):
                rc = self._heal_or_stop(result)
                if rc is not None:
                    return rc
                incarnation += 1
                continue
            if not result.preempted:
                bad = [
                    rc for rc in result.returncodes
                    if rc not in (0, PREEMPT_RC)
                ]
                self.log.error(
                    "group failed (exit codes %s); stragglers torn down, "
                    "not resubmitting", result.returncodes,
                )
                # prefer a child's real rc over a signal-killed straggler's
                # negative Popen code; a pure-signal group maps to the
                # conventional 128+signal so the shell status stays honest
                pos = [rc for rc in bad if rc > 0]
                if pos:
                    return pos[0]
                return 128 + abs(bad[0]) if bad else 1
            resize_relaunch = self._resize_pending()
            if resize_relaunch:
                # realizing --resize-to is not failure recovery: the
                # relaunch at the new size neither consumes the restart
                # budget nor gets blocked by an already-spent one (the
                # supervisor may itself have SIGTERMed a healthy group to
                # drain it — refusing to relaunch would strand the job)
                self.log.warning(
                    "elastic resize: relaunching the group at %d "
                    "process(es) (was %d); the job continues from the "
                    "drained step", self.resize_to, self.processes,
                )
                self.processes = int(self.resize_to)
                delay = self.backoff_base_s
            else:
                if restarts >= self.max_restarts:
                    self.log.error(
                        "preempted again but the restart budget (%d) is "
                        "spent; progress is checkpointed — resubmit "
                        "manually or raise --max-restarts",
                        self.max_restarts,
                    )
                    return PREEMPT_RC
                restarts += 1
                delay = self.backoff_s(restarts)
            self.log.warning(
                "group preempted (rc %d): resubmitting in %.1fs "
                "(restart %d/%d) — resumed run restores from the drained "
                "checkpoint", PREEMPT_RC, delay, restarts,
                self.max_restarts,
            )
            self.sleep(delay)
            incarnation += 1


def default_train_cmd(train_args: Sequence[str]) -> list[str]:
    """The per-process command for a training group: this interpreter,
    this repo's launcher, the user's args verbatim."""
    return [sys.executable, "-m", "mgwfbp_tpu.train_cli", *train_args]

