"""Live observability plane: in-memory metric aggregation + HTTP endpoints.

The telemetry stream (telemetry/events.py) was post-hoc only: JSONL on
disk, rendered after the fact by tools/telemetry_report.py. A week-long
supervised multi-host run needs the same answers WHILE it runs — is this
job healthy, what step is it on, is the schedule still right, which host
is slow. This module serves them per process:

  * ``MetricsAggregator`` — an in-memory view fed by the SAME validated
    event stream the JSONL writer appends (the ``EventWriter.observer``
    tee), plus host-side schedule/health facts the trainer pushes. Pure
    host data in, pure host data out: nothing here may ever touch a
    device value (the zero-sync telemetry contract; the emit-site
    JSON-scalar check already rejects device arrays before they reach the
    observer).
  * ``TelemetryServer`` — an opt-in background HTTP server
    (``--metrics-port`` / ``MGWFBP_METRICS_PORT``; a multi-host group
    serves ``port + process_index`` per process) exposing

      /metrics   Prometheus text, rendered live from the aggregator
                 through the SAME registry as the post-hoc file dump
                 (telemetry.export.METRICS / render_metrics — the two
                 surfaces cannot drift);
      /healthz   liveness: 200 while the step loop makes progress, 503
                 once the watchdog reports a stall (sticky when the
                 stall is rc-86-abort-bound — the flip lands BEFORE the
                 process dies, so a prober sees unhealthy, not a reset
                 connection); a later step clears a non-abort stall;
      /status    JSON: run metadata, current step/epoch, the committed
                 merge schedule + comm_op, rolling overlap efficiency,
                 last checkpoint, bad-step/rollback counts, active
                 drift/straggler alarms, profile-window state;
      /profile   on-demand deep profiling (ISSUE 10): ``?steps=N`` arms a
                 bounded ``jax.profiler.trace`` window over the next N
                 live steps — the handler only flips host state; the step
                 loop runs the window at the next (multi-host: group-
                 agreed) boundary, writes a Chrome-trace slice, and posts
                 the per-merge-group device-attributed table back here.

  * The fleet fan-in (`telemetry/fleet.py`, served by the supervisor)
    scrapes these per-process endpoints and merges them under a
    ``process`` label through the SAME metric registry.

The server thread only ever reads the aggregator under its lock — it
issues no device calls, touches no jax state, and a dead server (port
collision, interface gone) degrades to a logged warning, never a failed
training run.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from mgwfbp_tpu.utils.logging import get_logger

METRICS_PORT_ENV = "MGWFBP_METRICS_PORT"
METRICS_HOST_ENV = "MGWFBP_METRICS_HOST"
# where to persist this process's ACTUAL bound port (JSON sidecar): the
# supervisor exports one path per child so the fleet fan-in and fleet.json
# never have to guess ports — the base+index convention cannot cover the
# ephemeral (base == 0) case at all
METRICS_PORT_FILE_ENV = "MGWFBP_METRICS_PORT_FILE"

# hard ceiling on one /profile window: the endpoint is unauthenticated on
# loopback and the window syncs the device, so a request may never arm an
# unbounded trace
PROFILE_MAX_STEPS = 50

# rolling window for the mean-step gauge — matches the historical
# prometheus_text behavior (mean over the last <= 20 step spans)
_STEP_WINDOW = 20


def routable_host() -> str:
    """This machine's best routable address, for ADVERTISING a wildcard
    bind (0.0.0.0) to off-host scrapers: the fleet fan-in and an external
    Prometheus reading fleet.json need an address a peer host can dial,
    and the wildcard is not one. Resolution: the kernel's outbound-route
    pick (a UDP connect sends nothing), then the hostname's address, then
    loopback — each step degrades, never raises."""
    import socket as _socket

    try:
        s = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
        try:
            s.connect(("10.255.255.255", 9))
            host = s.getsockname()[0]
            if host and not host.startswith("0."):
                return host
        finally:
            s.close()
    except OSError:
        pass
    try:
        host = _socket.gethostbyname(_socket.gethostname())
        if host:
            return host
    except OSError:
        pass
    return "127.0.0.1"


def advertised_host(bound_host: str) -> str:
    """The address peers should DIAL for a server bound at `bound_host`:
    wildcard binds advertise the routable address, concrete binds
    advertise themselves."""
    if bound_host in ("", "0.0.0.0", "::"):
        return routable_host()
    return bound_host


def resolve_metrics_port(
    base_port: Optional[int], process_index: int = 0
) -> Optional[int]:
    """Concrete listen port for one process of a run: ``base + index`` so
    a multi-host group's processes serve distinct ports from ONE
    configured value (the supervisor exports a single environment).
    ``base == 0`` asks the OS for an ephemeral port per process (the
    bound port is logged and available as ``TelemetryServer.port``);
    None disables the plane."""
    if base_port is None:
        return None
    base = int(base_port)
    if base < 0:
        raise ValueError(f"metrics port must be >= 0, got {base}")
    port = 0 if base == 0 else base + int(process_index)
    if port > 65535:
        # base + index walked off the end of the port space; an
        # observability knob must degrade (the caller warns), not kill
        # the training process with an OverflowError out of socket.bind
        raise ValueError(
            f"metrics port {base} + process_index {process_index} "
            "exceeds 65535"
        )
    return port


class MetricsAggregator:
    """In-memory metric/health/status state for one process's run.

    Fed two ways, both host-only:
      * ``observe(event, fields)`` — the EventWriter tee (live runs) or
        ``replay(records)`` over an already-written stream (file dump,
        supervisor post-mortems); rotated-segment continuation headers
        and per-process streams replay cleanly (headers only refresh run
        metadata).
      * explicit setters (``set_schedule``) for facts that are not
        events.

    Thread-safe: the step loop, the watchdog thread, and HTTP handler
    threads all touch it.
    """

    def __init__(self, run: Optional[dict] = None):
        self._lock = threading.Lock()
        self._run = dict(run or {})
        self._t0 = time.time()
        self._counts: collections.Counter = collections.Counter()
        self._step_durs: collections.deque = collections.deque(
            maxlen=_STEP_WINDOW
        )
        self._current_step: Optional[int] = None
        self._current_epoch: Optional[int] = None
        self._overlap: Optional[dict] = None
        self._last_checkpoint: Optional[dict] = None
        self._schedule: Optional[dict] = None
        self._last_drift_residual: Optional[float] = None
        self._last_straggler_excess: Optional[float] = None
        # training-health telemetry (ISSUE 12): the latest per-step
        # `health` record, and the flight recorder's recent bundle
        # manifests (fed by `postmortem` events — live tee or replay)
        self._health: Optional[dict] = None
        self._postmortems: collections.deque = collections.deque(maxlen=20)
        # (kind, group/slow_process) -> alarm fields, kept while active
        self._active_alarms: dict = {}
        # health: None = healthy; else the reason string. Sticky once an
        # abort-bound stall landed (the process is about to os._exit(86))
        self._unhealthy: Optional[str] = None
        self._unhealthy_sticky = False
        # on-demand deep profiling (/profile?steps=N): the HTTP handler
        # only ARMS a request here; the trainer's step loop consumes it
        # at the next (group-agreed, on multi-host) step boundary and
        # posts the result back — the handler thread itself never touches
        # jax. `_profile_supported` flips True when a live trainer
        # attaches; a replay-only aggregator rejects arming.
        self._profile_supported = False
        self._profile_state = "idle"  # idle|armed|running|done|failed
        self._profile_steps: Optional[int] = None
        self._profile_result: Optional[dict] = None
        self._profile_error: Optional[str] = None

    # -- feeding -----------------------------------------------------------
    def observe(self, event: str, fields: dict) -> None:
        """One validated telemetry record (the EventWriter tee)."""
        with self._lock:
            self._observe_locked(event, fields)

    def replay(self, records) -> None:
        """Feed an already-written stream (rotated sets and per-process
        streams read by `events.read_event_set` replay as-is)."""
        with self._lock:
            for rec in records:
                ev = rec.get("event")
                if not ev:
                    continue
                self._observe_locked(
                    ev, {k: v for k, v in rec.items() if k != "event"}
                )

    def _observe_locked(self, event: str, fields: dict) -> None:
        from mgwfbp_tpu.telemetry.export import EVENT_COUNTERS

        counter = EVENT_COUNTERS.get(event)
        if counter:
            self._counts[counter] += 1
        if event == "header":
            run = fields.get("run")
            if isinstance(run, dict):
                self._run.update(run)
        elif event == "step":
            self._step_durs.append(float(fields.get("dur_s", 0.0)))
            self._current_step = int(fields.get("step", 0))
            self._current_epoch = int(fields.get("epoch", 0))
            if not self._unhealthy_sticky:
                # progress after a non-abort stall: the step loop moved
                # again, so liveness recovers
                self._unhealthy = None
        elif event == "epoch":
            self._current_epoch = int(fields.get("epoch", 0))
        elif event == "overlap":
            self._overlap = dict(fields)
        elif event == "checkpoint":
            self._last_checkpoint = dict(fields)
        elif event == "watchdog_stall":
            abort = bool(fields.get("abort"))
            self._unhealthy = (
                f"watchdog stall in {fields.get('phase')!r} after "
                f"{float(fields.get('idle_s', 0.0)):.0f}s"
                + (" — aborting (rc 86)" if abort else "")
            )
            if abort:
                self._unhealthy_sticky = True
        elif event == "drift_alarm":
            key = ("drift", fields.get("kind"), fields.get("group", -1))
            if fields.get("active"):
                self._counts["mgwfbp_drift_alarms_total"] += 1
                self._active_alarms[key] = dict(fields, alarm="drift")
            else:
                self._active_alarms.pop(key, None)
            self._last_drift_residual = float(fields.get("residual", 0.0))
        elif event == "straggler":
            key = ("straggler",)
            if fields.get("active"):
                self._counts["mgwfbp_straggler_alarms_total"] += 1
                self._active_alarms[key] = dict(fields, alarm="straggler")
            else:
                self._active_alarms.pop(key, None)
            self._last_straggler_excess = float(
                fields.get("excess_s", 0.0)
            )
        elif event == "health":
            self._health = dict(fields)
        elif event == "health_alarm":
            key = ("health", fields.get("kind"), fields.get("group", -1))
            if fields.get("active"):
                self._counts["mgwfbp_health_alarms_total"] += 1
                self._active_alarms[key] = dict(fields, alarm="health")
            else:
                self._active_alarms.pop(key, None)
        elif event == "postmortem":
            self._postmortems.append(dict(fields))

    def set_schedule(
        self, comm_op: str, num_groups: int, policy_detail: str = "",
        predicted_nonoverlap_s: Optional[float] = None,
    ) -> None:
        """The committed merge schedule (trainer pushes this at build,
        autotune commit, and elastic resize — it is state, not an
        event)."""
        with self._lock:
            self._schedule = {
                "comm_op": str(comm_op),
                "num_groups": int(num_groups),
                "policy_detail": str(policy_detail),
            }
            if predicted_nonoverlap_s is not None:
                self._schedule["predicted_nonoverlap_s"] = float(
                    predicted_nonoverlap_s
                )

    # -- on-demand deep profiling (/profile) -------------------------------
    def enable_profile(self) -> None:
        """A live trainer attached: /profile?steps=N requests now have a
        consumer (the step loop polls `take_profile_request`)."""
        with self._lock:
            self._profile_supported = True

    def arm_profile(self, steps) -> tuple[int, dict]:
        """Arm a bounded trace window for the next `steps` live steps
        (the HTTP handler's side). Returns (http status, response doc)."""
        with self._lock:
            if not self._profile_supported:
                return 409, {
                    "error": "no live trainer attached to this endpoint "
                             "(replay-only aggregator cannot profile)",
                }
            try:
                n = int(steps)
            except (TypeError, ValueError):
                return 400, {"error": f"steps={steps!r} is not an integer"}
            if n < 1:
                return 400, {"error": f"steps must be >= 1, got {n}"}
            if self._profile_state in ("armed", "running"):
                return 409, {
                    "error": f"a profile window is already "
                             f"{self._profile_state}",
                    "state": self._profile_state,
                }
            n = min(n, PROFILE_MAX_STEPS)
            self._profile_state = "armed"
            self._profile_steps = n
            self._profile_error = None
            return 200, {
                "armed": True, "steps": n,
                "max_steps": PROFILE_MAX_STEPS,
            }

    def take_profile_request(self) -> Optional[int]:
        """Consume an armed request (the trainer's step loop; host-only,
        one lock acquire — the disarmed path stays zero-sync)."""
        with self._lock:
            if self._profile_state != "armed":
                return None
            self._profile_state = "running"
            return self._profile_steps

    def set_profile_result(self, result: dict) -> None:
        with self._lock:
            self._profile_state = "done"
            self._profile_result = dict(result)
            self._profile_error = None

    def fail_profile(self, reason: str) -> None:
        with self._lock:
            self._profile_state = "failed"
            self._profile_error = str(reason)

    def profile_status(self) -> dict:
        """The /profile GET document (no query = status/result)."""
        with self._lock:
            return self._profile_status_locked()

    def _profile_status_locked(self) -> dict:
        out: dict = {
            "supported": self._profile_supported,
            "state": self._profile_state,
            "max_steps": PROFILE_MAX_STEPS,
        }
        if self._profile_state in ("armed", "running"):
            out["steps"] = self._profile_steps
        if self._profile_result is not None:
            out["result"] = dict(self._profile_result)
        if self._profile_error is not None:
            out["error"] = self._profile_error
        return out

    # -- reading -----------------------------------------------------------
    def values(self) -> dict:
        """Registry-named metric values (export.render_metrics renders
        them; export.prometheus_text replays a stream into one of these,
        so the file dump equals the live endpoint by construction)."""
        from mgwfbp_tpu.telemetry.export import EVENT_COUNTERS

        with self._lock:
            out: dict = {
                name: 0 for name in EVENT_COUNTERS.values()
            }
            out["mgwfbp_drift_alarms_total"] = 0
            out["mgwfbp_straggler_alarms_total"] = 0
            out["mgwfbp_health_alarms_total"] = 0
            out.update(self._counts)
            if self._step_durs:
                out["mgwfbp_step_seconds"] = (
                    sum(self._step_durs) / len(self._step_durs)
                )
            if self._current_step is not None:
                out["mgwfbp_current_step"] = int(self._current_step)
            if self._current_epoch is not None:
                out["mgwfbp_current_epoch"] = int(self._current_epoch)
            if self._overlap is not None:
                out["mgwfbp_overlap_efficiency"] = float(
                    self._overlap.get("efficiency", 0.0)
                )
                out["mgwfbp_comm_hidden_seconds"] = float(
                    self._overlap.get("hidden_s", 0.0)
                )
                out["mgwfbp_comm_exposed_seconds"] = float(
                    self._overlap.get("exposed_s", 0.0)
                )
            if self._last_checkpoint is not None:
                out["mgwfbp_last_checkpoint_iteration"] = int(
                    self._last_checkpoint.get("iteration", 0)
                )
            if self._last_drift_residual is not None:
                out["mgwfbp_drift_residual"] = float(
                    self._last_drift_residual
                )
            if self._last_straggler_excess is not None:
                out["mgwfbp_straggler_excess_seconds"] = float(
                    self._last_straggler_excess
                )
            if self._health is not None:
                for key, name in (
                    ("loss", "mgwfbp_health_loss"),
                    ("grad_norm", "mgwfbp_health_grad_norm"),
                    ("update_ratio", "mgwfbp_health_update_ratio"),
                ):
                    v = self._health.get(key)
                    if v is not None:
                        out[name] = float(v)
                comp = self._health.get("compression_error") or []
                if comp:
                    out["mgwfbp_health_compression_error"] = max(
                        float(e) for e in comp
                    )
            out["mgwfbp_active_alarms"] = len(self._active_alarms)
            return out

    def health(self) -> tuple[bool, str]:
        """(healthy?, reason) for /healthz."""
        with self._lock:
            if self._unhealthy is None:
                return True, "ok"
            return False, self._unhealthy

    def status(self) -> dict:
        """The /status JSON document."""
        with self._lock:
            healthy = self._unhealthy is None
            return {
                "run": dict(self._run),
                "healthy": healthy,
                "health_reason": "ok" if healthy else self._unhealthy,
                "uptime_s": round(time.time() - self._t0, 3),
                "step": self._current_step,
                "epoch": self._current_epoch,
                "schedule": dict(self._schedule) if self._schedule else None,
                "overlap_efficiency": (
                    float(self._overlap.get("efficiency", 0.0))
                    if self._overlap is not None else None
                ),
                "last_checkpoint": (
                    dict(self._last_checkpoint)
                    if self._last_checkpoint is not None else None
                ),
                "bad_steps": int(
                    self._counts.get("mgwfbp_bad_steps_total", 0)
                ),
                "rollbacks": int(
                    self._counts.get("mgwfbp_rollbacks_total", 0)
                ),
                "drift_alarms": int(
                    self._counts.get("mgwfbp_drift_alarms_total", 0)
                ),
                "straggler_alarms": int(
                    self._counts.get("mgwfbp_straggler_alarms_total", 0)
                ),
                "health_alarms": int(
                    self._counts.get("mgwfbp_health_alarms_total", 0)
                ),
                "health": (
                    dict(self._health) if self._health is not None else None
                ),
                "postmortems": self._postmortems_locked(),
                "active_alarms": [
                    dict(a) for a in self._active_alarms.values()
                ],
                "profile": self._profile_status_locked(),
            }

    def _postmortems_locked(self) -> dict:
        return {
            "total": int(
                self._counts.get("mgwfbp_postmortems_total", 0)
            ),
            "recent": [dict(b) for b in self._postmortems],
        }

    def postmortems(self) -> dict:
        """The /postmortems JSON document: bundle count + the recent
        manifests fed by `postmortem` events (the flight recorder's tee —
        live runs and replayed streams list identically)."""
        with self._lock:
            return self._postmortems_locked()


class _Handler(BaseHTTPRequestHandler):
    # the aggregator is attached to the server instance by TelemetryServer
    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        from urllib.parse import parse_qs, urlsplit

        agg: MetricsAggregator = self.server.aggregator  # type: ignore
        split = urlsplit(self.path)
        path = split.path.rstrip("/") or "/"
        if path == "/metrics":
            from mgwfbp_tpu.telemetry.export import render_metrics

            body = render_metrics(agg.values()).encode()
            ctype = "text/plain; version=0.0.4; charset=utf-8"
            code = 200
        elif path == "/healthz":
            healthy, reason = agg.health()
            body = (reason + "\n").encode()
            ctype = "text/plain; charset=utf-8"
            code = 200 if healthy else 503
        elif path == "/profile":
            # ?steps=N arms a bounded trace window on the live trainer
            # (consumed at the next step boundary — next agree-interval
            # boundary on a multi-host group); no query = status/result
            query = parse_qs(split.query)
            if "steps" in query:
                code, doc = agg.arm_profile(query["steps"][-1])
            else:
                code, doc = 200, agg.profile_status()
            body = (json.dumps(doc, indent=1) + "\n").encode()
            ctype = "application/json"
        elif path == "/postmortems":
            # the flight recorder's bundle index (telemetry/recorder.py):
            # count + recent manifests, live — fed by `postmortem` events
            # through the same validated-stream tee as everything else
            body = (
                json.dumps(agg.postmortems(), indent=1) + "\n"
            ).encode()
            ctype = "application/json"
            code = 200
        elif path in ("/status", "/"):
            body = (json.dumps(agg.status(), indent=1) + "\n").encode()
            ctype = "application/json"
            code = 200
        else:
            body = (
                b"not found: serve /metrics, /healthz, /status, /profile, "
                b"/postmortems\n"
            )
            ctype = "text/plain; charset=utf-8"
            code = 404
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt, *args):  # silence per-request stderr spam
        pass


class TelemetryServer:
    """Background HTTP server over one MetricsAggregator.

    ``port == 0`` binds an ephemeral port (read it back from ``.port``).
    Construction failures (port in use) raise — callers that must not die
    wrap it (`start_metrics_server`). ``close()`` is idempotent."""

    def __init__(
        self,
        aggregator: MetricsAggregator,
        port: int,
        host: Optional[str] = None,
    ):
        # loopback by default: the endpoints are unauthenticated and
        # /status carries run metadata — exposing them on every
        # interface must be an explicit operator choice
        # (MGWFBP_METRICS_HOST=0.0.0.0 for a real Prometheus scrape)
        if host is None:
            host = os.environ.get(METRICS_HOST_ENV) or "127.0.0.1"
        self.aggregator = aggregator
        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.aggregator = aggregator  # type: ignore[attr-defined]
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"mgwfbp-metrics:{self.port}",
            daemon=True,
        )
        self._thread.start()

    def close(self) -> None:
        httpd, self._httpd = self._httpd, None
        if httpd is None:
            return
        try:
            httpd.shutdown()
            httpd.server_close()
        except Exception:  # noqa: BLE001 — teardown must never raise
            pass
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None


def write_port_file(
    path: str, server: TelemetryServer, process_index: int,
) -> None:
    """Persist the ACTUAL bound endpoint (atomic JSON sidecar) so the
    supervisor's fleet fan-in and the `fleet.json` scrape targets read
    real ports instead of assuming the base+index convention — which is
    simply wrong when the base is 0 (per-process ephemeral ports)."""
    doc = {
        "process": int(process_index),
        # a 0.0.0.0 bind advertises the ROUTABLE address (cross-host
        # seam): fleet.json targets must be dialable from other hosts
        "host": advertised_host(server.host),
        "bound_host": server.host,
        "port": int(server.port),
        "pid": os.getpid(),
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def start_metrics_server(
    aggregator: MetricsAggregator,
    base_port: Optional[int],
    process_index: int = 0,
) -> Optional[TelemetryServer]:
    """Start the per-process metrics server, or None when disabled or the
    bind fails (logged — the plane is observability, not a dependency)."""
    log = get_logger("mgwfbp.telemetry.serve")
    try:
        port = resolve_metrics_port(base_port, process_index)
    except ValueError as e:
        log.warning("metrics server disabled: %s", e)
        return None
    if port is None:
        return None
    try:
        server = TelemetryServer(aggregator, port)
    except (OSError, OverflowError) as e:
        log.warning(
            "metrics server failed to bind port %d (%s); live "
            "observability disabled for this process", port, e,
        )
        return None
    port_file = (os.environ.get(METRICS_PORT_FILE_ENV) or "").strip()
    if port_file:
        try:
            write_port_file(port_file, server, process_index)
        except OSError as e:  # the sidecar is a convenience, not a gate
            log.warning("could not write metrics port file %s: %s",
                        port_file, e)
    log.info(
        "metrics server: http://%s:%d "
        "(/metrics /healthz /status /profile /postmortems)",
        server.host, server.port,
    )
    return server
