"""Training-progress watchdog: detect a hung step loop.

Failure-detection parity (SURVEY.md §5): the reference's failure handling
is passive (MPI aborts the world when a rank dies); a TPU client has a
quieter failure mode — a runtime call that BLOCKS forever when the device
or a peer stops answering (at ~0% CPU, with no error). The
watchdog turns that silence into a signal: a daemon thread checks a
monotonic heartbeat the step loop touches; if no progress lands within
`timeout_s`, it logs CRITICAL with the stalled phase and (optionally,
MGWFBP_WATCHDOG_ABORT=1) hard-exits so a supervisor can restart, instead
of the job hanging until an external kill.

Zero overhead on the hot path: the heartbeat is one time.monotonic()
store per iteration, no locks (a torn read merely delays detection by one
interval).
"""

from __future__ import annotations

import faulthandler
import logging
import os
import sys
import threading
import time
from typing import Optional

from mgwfbp_tpu.utils.logging import get_logger


# Extra deadline for known-long silent phases (overridable; seconds).
# First XLA compile of a step program runs about a minute for ResNet-50
# on a v5e and longer for big models; an orbax save streams the full state
# to disk.
COMPILE_ALLOW_S = float(os.environ.get("MGWFBP_WATCHDOG_COMPILE_S", "600"))
CHECKPOINT_ALLOW_S = float(os.environ.get("MGWFBP_WATCHDOG_CKPT_S", "180"))


class ProgressWatchdog:
    """Arm around a step loop; `beat(phase)` from the loop body."""

    def __init__(
        self,
        timeout_s: Optional[float] = None,
        abort: Optional[bool] = None,
        check_interval_s: float = 10.0,
        on_stall=None,
    ):
        # on_stall(phase=..., idle_s=..., timeout_s=..., abort=...) is
        # called (from the watcher thread) each time the deadline fires —
        # the trainer hooks the telemetry stream here so stalls are
        # greppable from the same file as the step records. It runs BEFORE
        # a configured abort, and its own failure never masks the signal.
        env = os.environ.get("MGWFBP_WATCHDOG_S")
        self.timeout_s = (
            timeout_s
            if timeout_s is not None
            else (float(env) if env else 0.0)
        )
        self.abort = (
            abort
            if abort is not None
            else os.environ.get("MGWFBP_WATCHDOG_ABORT") == "1"
        )
        self.check_interval_s = check_interval_s
        self.on_stall = on_stall
        self.log = get_logger("mgwfbp.watchdog")
        self._last = time.monotonic()
        self._phase = "startup"
        self._allow = 0.0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.fired = False

    @property
    def enabled(self) -> bool:
        return self.timeout_s > 0

    # graft: thread-safe -- lock-free heartbeat by design: stores are
    # GIL-atomic and the watcher tolerates one stale/lenient check (see
    # the _last/_allow ordering comment below); a lock here would let a
    # wedged holder stall the very thread meant to detect wedges
    def beat(self, phase: str = "step", allow_s: float = 0.0) -> None:
        """Record progress. `allow_s` extends the deadline for the phase
        being ENTERED — known-long silent phases (first-step XLA compile,
        about a minute and up; orbax checkpoint save) legitimately
        outlast a per-step timeout, and hard-exiting a healthy run from
        inside its first compile is worse than late detection (ADVICE r4
        #3). The allowance applies until the next beat resets it."""
        self._phase = phase
        # _last strictly before _allow: if the watcher wakes mid-beat it may
        # see the fresh timestamp with the old (larger) allowance — one
        # overly lenient check — instead of a stale timestamp with zero
        # allowance, which would hard-exit a healthy run right as a long
        # compile finishes
        self._last = time.monotonic()
        self._allow = max(float(allow_s), 0.0)

    def _dump_all_stacks(self) -> None:
        """faulthandler dump of EVERY thread to stderr and to any log
        files the framework has open — the escalation step: a stalled run
        (especially one about to abort) must leave the blocked C-call's
        Python frames on disk, or a wedged dispatch is undiagnosable
        post-mortem. faulthandler is async-safe and needs no cooperation
        from the stuck thread."""
        streams = [sys.stderr]
        for name in ("mgwfbp.trainer", "mgwfbp.watchdog"):
            for h in logging.getLogger(name).handlers:
                stream = getattr(h, "stream", None)
                if stream is not None and stream not in streams:
                    streams.append(stream)
        for s in streams:
            try:
                s.write(
                    f"\n== watchdog stall in {self._phase!r}: all-thread "
                    "traceback dump ==\n"
                )
                # flush BEFORE the dump: faulthandler writes straight to
                # the fd, bypassing the Python buffer the banner sits in —
                # without this the banner lands AFTER the tracebacks
                s.flush()
                faulthandler.dump_traceback(file=s, all_threads=True)
                s.flush()
            except Exception:  # noqa: BLE001 — a closed/broken stream
                # must not mask the remaining dump targets or the abort
                continue

    def _watch(self) -> None:
        while not self._stop.wait(min(self.check_interval_s, self.timeout_s)):
            idle = time.monotonic() - self._last
            if idle > self.timeout_s + self._allow:
                self.fired = True
                self.log.critical(
                    "no training progress for %.0f s (stalled in %r; "
                    "timeout %.0f s) — likely a wedged device or "
                    "blocked host call%s",
                    idle, self._phase, self.timeout_s,
                    "; aborting (MGWFBP_WATCHDOG_ABORT=1)"
                    if self.abort
                    else "",
                )
                # escalation BEFORE the optional abort: the stack dump is
                # the post-mortem; os._exit would otherwise take the
                # evidence down with the process
                self._dump_all_stacks()
                if self.on_stall is not None:
                    try:
                        self.on_stall(
                            phase=self._phase, idle_s=float(idle),
                            timeout_s=float(self.timeout_s),
                            abort=bool(self.abort),
                        )
                    except Exception:  # noqa: BLE001 — the stall signal
                        # must never be masked by its own reporting
                        self.log.exception("watchdog on_stall hook failed")
                if self.abort:
                    # os._exit: the stalled runtime call cannot be
                    # interrupted from Python — exiting the process is the
                    # only way to hand control back to a supervisor
                    os._exit(86)
                self.beat(self._phase)  # re-arm so it warns periodically

    def __enter__(self) -> "ProgressWatchdog":
        if self.enabled:
            self.beat("startup")
            self._thread = threading.Thread(target=self._watch, daemon=True)
            self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
