"""Structured run-observability event stream.

MG-WFBP's whole claim is that the merged schedule *hides* communication
behind the backward pass (arXiv:1811.11141); a production run must be able
to show that it actually does. This module is the spine of the telemetry
subsystem: an append-only, schema-versioned JSONL stream of TYPED records
every layer of the framework feeds — step spans from the trainer's (un-jitted)
step loop, per-merge-group comm spans with exposed/hidden attribution
(`telemetry.overlap`), autotune race rows, elastic resizes, checkpoint
saves, watchdog stalls, bench skips — so a post-mortem, an overlap report
(`tools/telemetry_report.py`), and a Chrome-trace render
(`telemetry.export`) all read from ONE greppable file.

Wire format: line 1 is a ``header`` record carrying ``schema_version``
(validated by the same `check_schema_version` the calibration profiles and
the schedule cache use); every following line is one event object::

    {"event": "step", "wall": 1722760000.1, "step": 12, "epoch": 0,
     "start_s": 3.41, "dur_s": 0.021}

Hot-path discipline: the writer NEVER touches the device. ``emit`` rejects
any field value that is not a plain JSON scalar/list/dict — handing it a
jax array (whose serialization would force a device sync) raises
``TypeError`` instead of silently stalling the step loop. Step spans are
host wall-clock around the *dispatch* of the async jitted step, and no
block_until_ready / device_get is ever issued on their behalf (the zero-sync
guard in tests/test_telemetry.py counts those two calls; lint rule JIT006
covers the jitted side). What telemetry does read from the device is the
health drain (`Trainer._note_health_stats`): each step's statistics are
copied to the host as the step's own output arrays, started at its dispatch
(`copy_to_host_async`) and read one step late (an ``__array__`` read, which
that guard does not count and which waits only for the step that made the
array, never for the one in flight). The drain dispatches no device program
(tests/test_health_drain.py); the `health` phase span times it and the
`stats_ready` counter says whether it had to wait (telemetry/phases.py).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from mgwfbp_tpu.parallel.costmodel import check_schema_version

# Version 1 is the legacy headerless ScalarWriter JSONL
# ({"wall","step","tag","value"} rows, utils/summary.py) — `read_events`
# migrates it to `scalar` records. Version 2 is the typed stream below.
EVENT_SCHEMA_VERSION = 2
_LEGACY_SCALAR_VERSION = 1

# Typed records: event name -> required fields (beyond "event"/"wall").
# Extra fields are allowed — the schema names the invariants a reader may
# rely on, not the exhaustive payload.
EVENT_TYPES: dict[str, tuple[str, ...]] = {
    # run metadata; always the stream's first record
    "header": ("schema_version",),
    # one optimizer step: host wall-clock span around the async dispatch,
    # start_s relative to the stream's epoch (header wall). The Trainer's
    # records also carry `phases` ({name: [start_s, dur_s]} of the rest of
    # the iteration, same clock) and the counters ready / lowered /
    # stats_ready (telemetry/phases.py); such a record is written after the NEXT
    # dispatch, once its iteration is over, not at its own
    "step": ("step", "epoch", "start_s", "dur_s"),
    # one merge group's comm span within the step timeline (model-replayed
    # start, measured or predicted duration; see telemetry.overlap).
    # Hierarchical (hier) regimes additionally carry ici_s/dcn_s — the
    # group's comm split by link — and cross-step regimes ag_start_s/ag_s.
    "comm_group": ("step", "group", "nbytes", "comm_s", "start_s",
                   "hidden_s", "exposed_s", "attribution"),
    # aggregate overlap-efficiency snapshot for the surrounding step
    # regime; hier regimes add ici_s/dcn_s/bottleneck_link (which
    # interconnect carries the larger comm share)
    "overlap": ("step", "epoch", "step_s", "tb_total_s", "comm_s",
                "hidden_s", "exposed_s", "efficiency", "attribution"),
    # ScalarWriter view: the legacy scalar rows, now in the same stream
    "scalar": ("tag", "value", "step"),
    # epoch boundary (throughput trend anchor for the report CLI)
    "epoch": ("epoch", "steps", "dur_s"),
    # one built step program with gradient collectives, read from its
    # compiled text after its first dispatch (Trainer._note_step_program):
    # how many collectives it issues and how many of them the compiler
    # made asynchronous; `compiler_options` (names) rides along
    "step_program": ("step", "collectives", "async_collectives"),
    # a profile window's device trace reduced by the step's map
    # (profiling.split_trace, Trainer._run_profile_window), milliseconds a
    # step over `steps` traced steps, a mean over the local devices:
    # `scopes` as name -> [forward_ms, backward_ms] (the declared scopes,
    # `(model, no scope)`, and in the first place `(no metadata)` and
    # `(outside the model)`); `groups`, by merge group; `exchange`
    # ({device_ms, wait_ms, calls}: under a merge-group scope or of
    # collective kind; of it the `-done` halves and the synchronous
    # collectives; collectives started); `top`, the longest instructions as
    # [ms, instruction, scope]. `layers` (declared scope -> layer of
    # PERF.md's map), `total_ms` and `events` ride along
    "step_scopes": ("step", "steps", "scopes", "groups", "exchange", "top"),
    # one built step program, counted while it was traced (ops/programs.py
    # has the ops and the records' table; Trainer._note_traced_programs
    # writes them): how many of its attention cores
    # went through the fused kernel and how many through the plain blocks
    # (ops/blockattn.py); 0 and 0 for a model without attention
    "attention_program": ("step", "kernel", "blocks"),
    # the same for the experts' grouped products (ops/groupmm.py): how many
    # went through the tiled kernel and how many through `lax.ragged_dot`
    # (3 a sparse layer held), and the distinct kernel programs among the
    # former with their transposes; and the row permutations round them
    # (ops/rowperm.py: 2 a sparse layer held; `rows_held` move only the rows
    # in a group, `rows_all` every assignment's row, `rows_programs` the
    # distinct kernel programs they and their transposes need); and how the
    # expert blocks sized their grouped arrays (models/lm_parts.py: 1 a
    # sparse layer held; `bounded` tokens x experts held rows, where a token
    # routes to more experts than are held, `whole` tokens x k rows); all 0
    # for a model without experts
    "experts_program": (
        "step", "kernel", "ragged", "programs", "rows_held", "rows_all",
        "rows_programs", "bounded", "whole"),
    # the same for the selective scans (ops/selscan.py): how many went
    # through the kernels with the state in VMEM and how many through the
    # plain chunked form (1 a Mamba-1 layer held), and the distinct kernel
    # programs the former need (a forward and a backward one for each
    # shape); all 0 for a model without a selective scan
    "scan_program": ("step", "kernel", "plain", "programs"),
    # the same for the gated delta rules (ops/deltarule.py; 1 a Gated
    # DeltaNet layer held; an inverse, a forward and a backward program for
    # each shape); all 0 for a model without a delta rule
    "delta_program": ("step", "kernel", "plain", "programs"),
    # the same for the short causal convolutions with their SiLU
    # (ops/shortconv.py; 1 a Mamba or Gated DeltaNet layer held; a forward
    # and a backward program for each shape); all 0 for a model without one
    "conv_program": ("step", "kernel", "plain", "programs"),
    # the same for the passes over several residual streams (ops/streams.py;
    # 2 a sub-layer: the mapping with its read, and the write-back; a forward
    # and a backward program for each); all 0 for a model with one stream
    "streams_program": ("step", "kernel", "plain", "programs"),
    # the same for the chunked state-space scans (ops/ssd.py; 1 a Mamba-2
    # layer held; a forward and a backward program for each shape); all 0
    # for a model without one
    "ssd_program": ("step", "kernel", "plain", "programs"),
    # what set-up was made of, once per process start, when the host has
    # read the first step's results, and once more after a rebuild that
    # recompiles the step (telemetry/phases.py): `spans` as
    # {name: [start_s, dur_s, parent]} on the stream's clock (negative for
    # what preceded the writer), `counters` (programs traced, lowered,
    # compiled, loaded from the compile cache; slow_events), `origin_wall`
    # the process's start as the OS knows it
    "setup": ("spans", "counters", "origin_wall"),
    # autotune: one raced candidate / the committed winner
    "autotune_race": ("label", "comm_op", "num_groups", "verified",
                      "measured_step_s"),
    "autotune_commit": ("winner", "comm_op", "num_groups", "source"),
    # elastic resize seam; schedule_source records which path won the
    # post-resize schedule ("schedule-cache" vs "solver" for an in-place
    # update_nworker, "relaunch-reshard" when a supervisor-driven
    # relaunch re-sharded a sibling world's shard-native checkpoint)
    "resize": ("old_world", "new_world", "schedule_source", "num_groups"),
    # a written snapshot; mid_epoch=True rows (the --ckpt-every-steps /
    # preemption-drain path) additionally carry epoch_step. Rows also
    # carry the save cost — duration_s + bytes (this process's payload)
    # + format ("sharded" | "replicated") — so the report tool and
    # flight recorder surface checkpoint-cost regressions
    "checkpoint": ("epoch", "iteration", "mid_epoch"),
    # watchdog stall/abort (also CRITICAL-logged; this makes it greppable
    # from the same file as the step records)
    "watchdog_stall": ("phase", "idle_s", "timeout_s", "abort"),
    # --- resilience layer (ISSUE 5) ------------------------------------
    # graceful preemption drain: the in-flight step finished, a
    # step-indexed checkpoint was written, the process exits rc 75
    "preempt": ("signal", "epoch", "iteration"),
    # non-finite-gradient guard: the jitted step dropped this update
    # (nonfinite = global count of non-finite gradient elements)
    "bad_step": ("step", "epoch", "nonfinite"),
    # K consecutive bad steps -> trainer rolled back to the last checkpoint
    "rollback": ("bad_steps", "restored_iteration", "restored_epoch"),
    # a restart picked up from a saved snapshot (mid_epoch = step-indexed
    # mid-epoch checkpoint, i.e. the preemption-safe resume path)
    "resume": ("epoch", "iteration", "mid_epoch"),
    # --- live observability plane (ISSUE 9) ----------------------------
    # cost-model drift (telemetry/drift.py): `kind` is 'comm_residual'
    # (predicted-vs-measured merge-group comm, `group` = arrival index or
    # -1 for the aggregate) or 'step_trend' (EWMA step time vs the
    # baseline window); `residual` is the ratio/excess that crossed (or
    # re-entered) `band`; active=True raises the alarm, False clears it
    # (hysteresis guarantees no flapping between the two)
    "drift_alarm": ("kind", "step", "residual", "band", "active"),
    # live multi-host straggler probe: per agree-interval the group
    # gathers its window step times (runtime/coordination); the slowest
    # process is named in `slow_process` (NOT 'process' — the merge tool
    # stamps each record with its emitting stream's process index under
    # that key). excess_s = slowest minus fastest window step seconds.
    "straggler": ("step", "slow_process", "excess_s", "step_s_max",
                  "step_s_min", "active"),
    # --- fleet console + deep profiling (ISSUE 10) ----------------------
    # one completed on-demand /profile trace window: `steps` live steps
    # traced, `attribution` 'trace' when per-group device time attributed
    # (device_s rides along per group, layout order) or 'none'
    "profile": ("step", "steps", "attribution"),
    # --- training-health telemetry + flight recorder (ISSUE 12) ---------
    # one optimizer step's model-health statistics, read one step LATE
    # off the jitted step's metrics psum (the PR-5 deque idiom — no
    # device_get on the dispatch path). grad_norm is the global gradient
    # L2 norm (post-reduction on the in-step lowerings; mean of the local
    # pre-reduction norms on the sharded rs_opt_ag/rs_fwd_ag paths),
    # update_ratio the update/param L2-norm ratio. `group_norms` rides
    # along as the per-merge-group grad-norm list (arrival order, [] when
    # no reducer), and `compression_error` as the per-group relative
    # top-k compression error when a sparsifying compressor is live.
    "health": ("step", "epoch", "loss", "grad_norm", "update_ratio"),
    # online health-detector edge (telemetry/health.py): `kind` is
    # 'loss_spike' | 'grad_explosion' | 'plateau' | 'compression_error';
    # `value` the residual that crossed (or re-entered) `band`;
    # active=True raises, False clears (two-edge Hysteresis — no flap)
    "health_alarm": ("kind", "step", "value", "band", "active"),
    # the flight recorder wrote one postmortem bundle (telemetry/
    # recorder.py): `trigger` names the alarm event that tripped it,
    # `step` the trigger's step, `path` the bundle directory
    "postmortem": ("trigger", "step", "path"),
    # --- self-healing supervisor (ISSUE 20) -----------------------------
    # the supervisor (or trainer) observed one HARD failure: `class` is
    # 'crash' | 'oom_kill' | 'wedge' | 'unreachable' | 'coordination',
    # `target` names the failed member ('p1', ...). rc/signal/step ride
    # as extras when known
    "failure": ("class", "target"),
    # the supervisor's healing policy acted on a failure: `action` is
    # 'relaunch' (same world) | 'shrink' (elastic resume at survivor
    # count) | 'stop' (budget exhausted / crash loop).
    # world/incarnation/restarts ride as extras
    "heal": ("action",),
}

_JSON_SCALARS = (str, int, float, bool, type(None))


def stream_filename(process_index: int = 0, process_count: int = 1) -> str:
    """Per-run stream file name. Single-process runs keep the historical
    ``telemetry.jsonl``; a multi-host group writes one stream PER PROCESS
    (``telemetry.pN.jsonl``, process_index/process_count in the header's
    run metadata) — `tools/telemetry_merge.py` reassembles the global
    timeline. One convention, shared by the trainer and the merge tool."""
    if process_count <= 1:
        return "telemetry.jsonl"
    return f"telemetry.p{int(process_index)}.jsonl"


def find_stream_paths(directory: str) -> list[str]:
    """Active stream files under `directory` (single- or multi-process
    naming), process order. Rotated ``.NNNN`` segments are NOT listed —
    `read_event_set` on an active path folds its segments in."""
    out = []
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    for name in names:
        if name == "telemetry.jsonl":
            out.append((-1, name))
        elif name.startswith("telemetry.p") and name.endswith(".jsonl"):
            idx = name[len("telemetry.p"):-len(".jsonl")]
            if idx.isdigit():
                out.append((int(idx), name))
    multi = [e for e in out if e[0] >= 0]
    if multi:
        # a multi-host group never writes the single-process name, so a
        # telemetry.jsonl sitting next to pN streams is a stale earlier
        # single-host run of the same (deterministic) tag — listing it
        # would silently interleave two different runs' timelines in the
        # merge
        out = multi
    return [os.path.join(directory, n) for _, n in sorted(out)]


def _check_jsonable(value, key: str) -> None:
    """Reject anything that is not already host-side JSON data.

    A device array here would force a host transfer during serialization —
    exactly the sync the telemetry contract forbids — so it fails loudly at
    the emit site instead."""
    if isinstance(value, _JSON_SCALARS):
        return
    if isinstance(value, (list, tuple)):
        for i, v in enumerate(value):
            _check_jsonable(v, f"{key}[{i}]")
        return
    if isinstance(value, dict):
        for k, v in value.items():
            _check_jsonable(v, f"{key}.{k}")
        return
    raise TypeError(
        f"telemetry field {key!r} is {type(value).__name__}, not plain JSON "
        "data; convert device values on a cold path first (telemetry must "
        "add zero device syncs to the step loop)"
    )


def _rotated_segments(path: str) -> list[str]:
    """Rotated sibling files of an active stream, oldest first.

    Rotation renames the active file to ``<path>.NNNN`` (zero-padded
    sequence); sort by that integer, NOT lexically, so segment 10 follows
    9 even if a hand-rotated unpadded name slipped in."""
    d = os.path.dirname(path) or "."
    base = os.path.basename(path)
    out = []
    try:
        names = os.listdir(d)
    except OSError:
        return []
    for name in names:
        if not name.startswith(base + "."):
            continue
        suffix = name[len(base) + 1:]
        if suffix.isdigit():
            out.append((int(suffix), os.path.join(d, name)))
    return [p for _, p in sorted(out)]


def _next_segment_index(path: str) -> int:
    """Index the ACTIVE stream at `path` will rotate into next: one past
    the highest existing segment — NOT the segment count, which would
    re-use (and os.replace would silently clobber) the newest surviving
    segment after an operator deletes old ones to reclaim disk."""
    segs = _rotated_segments(path)
    if not segs:
        return 0
    last = os.path.basename(segs[-1])
    return int(last.rsplit(".", 1)[1]) + 1


class EventWriter:
    """Append-only JSONL event stream (one run, process 0).

    Writes the versioned header when it creates (or first appends to an
    empty) file; re-opening an existing stream appends without a second
    header. Thread-safe for concurrent emitters (the watchdog fires from
    its daemon thread) — each record is one line-buffered write.

    Week-long jobs rotate by size (ROADMAP PR-4 follow-up): when the
    active file exceeds ``max_bytes`` (default from
    ``MGWFBP_TELEMETRY_MAX_MB``; unset/0 = never rotate) it is renamed to
    ``<path>.NNNN`` and a fresh segment opens. Every segment starts with
    its own header carrying the SET's original wall anchor and a
    ``segment`` index, so `read_event_set` reassembles one continuous
    timeline and a restart re-anchors correctly off the active segment.
    """

    def __init__(
        self,
        path: str,
        run: Optional[dict] = None,
        max_bytes: Optional[int] = None,
        observer=None,
    ):
        # observer(event, fields) is called for every emitted record AFTER
        # schema validation — the live metrics aggregator
        # (telemetry/serve.py) tees off here so the /metrics endpoint and
        # the JSONL file are fed by the SAME validated stream. A failing
        # observer is detached, never fatal: observability must not kill
        # the run it observes.
        self.observer = observer
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        if max_bytes is None:
            mb = os.environ.get("MGWFBP_TELEMETRY_MAX_MB", "").strip()
            max_bytes = int(float(mb) * 1024 * 1024) if mb else 0
        self.max_bytes = max(int(max_bytes), 0)
        self._run = dict(run or {})
        self._segment = _next_segment_index(path)
        fresh = not (os.path.exists(path) and os.path.getsize(path) > 0)
        header_wall = None
        if not fresh:
            # re-opening (resume under the same tag): span timestamps stay
            # relative to the ORIGINAL header's wall clock, so appended
            # records extend the stream's timeline instead of restarting
            # at zero on top of the first run's spans (rotation headers
            # re-stamp that original anchor into every segment)
            try:
                with open(path) as f:
                    first = json.loads(f.readline())
                if first.get("event") == "header":
                    header_wall = float(first.get("wall", 0.0)) or None
                    self._run = dict(first.get("run", self._run) or {})
            except (OSError, ValueError):
                header_wall = None
        self._f = open(path, "a", buffering=1)  # line-buffered
        self._bytes = 0 if fresh else os.path.getsize(path)
        self._lock = threading.Lock()
        # stream-relative clock for span timestamps: monotonic, immune to
        # wall-clock steps mid-run; anchored at the stream header's wall
        self._t0 = time.perf_counter()
        self._anchor_wall = header_wall if header_wall else time.time()
        if header_wall is not None:
            self._t0 -= max(time.time() - header_wall, 0.0)
        if fresh:
            self._emit_record(
                "header",
                wall=self._anchor_wall,
                schema_version=EVENT_SCHEMA_VERSION,
                run=self._run,
                segment=self._segment,
            )

    def now(self) -> float:
        """Seconds since this writer opened (span-timestamp base)."""
        return time.perf_counter() - self._t0

    def clock_of(self, perf_counter_s: float) -> float:
        """A `time.perf_counter` reading on `now`'s clock: negative for what
        preceded the writer (the `setup` record's spans)."""
        return perf_counter_s - self._t0

    def emit(self, event: str, **fields) -> None:
        """Append one typed record. Unknown event names and missing
        required fields raise — a misspelled emitter must fail its test,
        not write rows no reader understands."""
        required = EVENT_TYPES.get(event)
        if required is None:
            raise ValueError(
                f"unknown telemetry event {event!r}; known: "
                f"{sorted(EVENT_TYPES)}"
            )
        missing = [k for k in required if k not in fields]
        if missing:
            raise ValueError(
                f"telemetry event {event!r} missing required field(s) "
                f"{missing}"
            )
        for k, v in fields.items():
            _check_jsonable(v, k)
        if self.observer is not None:
            try:
                self.observer(event, fields)
            except Exception:  # noqa: BLE001 — a broken aggregator must
                # not take the stream (or the run) down with it; but say
                # so loudly: from here on the live /metrics//status
                # surfaces freeze at their last values while the JSONL
                # keeps advancing
                import logging

                logging.getLogger("mgwfbp.telemetry").exception(
                    "telemetry observer failed on %r; detaching — live "
                    "metrics/health endpoints will no longer update",
                    event,
                )
                self.observer = None
        self._emit_record(event, wall=time.time(), **fields)

    def _emit_record(self, event: str, wall: float, **fields) -> None:
        rec = {"event": event, "wall": round(wall, 3), **fields}
        line = json.dumps(rec) + "\n"
        with self._lock:
            if self._f.closed:
                return
            self._f.write(line)
            self._bytes += len(line)
            if (
                self.max_bytes
                and self._bytes > self.max_bytes
                and event != "header"
            ):
                self._rotate_locked()

    def _rotate_locked(self) -> None:
        """Roll the active file to the next ``<path>.NNNN`` segment and
        start a fresh one (caller holds the lock). A failed rename (e.g.
        read-only sibling dir entries) disables rotation rather than
        killing the run — same contract as every other telemetry failure."""
        self._f.close()
        target = f"{self.path}.{self._segment:04d}"
        try:
            os.replace(self.path, target)
        except OSError:
            self.max_bytes = 0  # rotation unavailable; keep appending
            self._f = open(self.path, "a", buffering=1)
            return
        self._segment += 1
        self._f = open(self.path, "a", buffering=1)
        self._bytes = 0
        # segment header: SAME schema + run + original wall anchor, so a
        # restart re-anchoring off this segment (and any reader of it in
        # isolation) sees the set's single continuous timeline
        rec = {
            "event": "header",
            "wall": round(self._anchor_wall, 3),
            "schema_version": EVENT_SCHEMA_VERSION,
            "run": self._run,
            "segment": self._segment,
        }
        line = json.dumps(rec) + "\n"
        self._f.write(line)
        self._bytes += len(line)

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()


def migrate_legacy_scalars(rows: list[dict]) -> list[dict]:
    """Lift a v1 (headerless ScalarWriter) stream into v2 records."""
    out = []
    for r in rows:
        out.append({
            "event": "scalar",
            "wall": r.get("wall", 0.0),
            "tag": r.get("tag", ""),
            "value": r.get("value"),
            "step": r.get("step", 0),
        })
    return out


def read_events(path: str) -> list[dict]:
    """Load a telemetry stream, validating (and migrating) its schema.

    * v2 stream (leading ``header`` record): version-checked via
      `check_schema_version`; returns all records including the header.
    * v1 legacy stream (headerless ScalarWriter JSONL): each row migrates
      to a ``scalar`` record and a synthesized v2 header is prepended.
    * Anything stamped with a version this build does not read raises
      ValueError — a newer writer's file must fail loudly.
    """
    rows: list[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    if not rows:
        return []
    first = rows[0]
    if first.get("event") == "header" or "schema_version" in first:
        check_schema_version(
            first, path=path, supported=(EVENT_SCHEMA_VERSION,),
            what="telemetry event stream",
        )
        return rows
    # headerless: the legacy scalar layout (or garbage, which json.loads
    # above would already have rejected line-wise)
    migrated = migrate_legacy_scalars(rows)
    header = {
        "event": "header",
        "wall": migrated[0].get("wall", 0.0),
        "schema_version": EVENT_SCHEMA_VERSION,
        "run": {"migrated_from": _LEGACY_SCALAR_VERSION},
    }
    return [header] + migrated


def read_event_set(path: str) -> list[dict]:
    """Load a possibly-rotated stream: every ``<path>.NNNN`` segment in
    sequence order, then the active file. Each segment is schema-validated
    by `read_events`; the first header is kept and the per-segment
    continuation headers dropped, so consumers see ONE stream exactly as
    if rotation had never happened. A bare un-rotated file reads
    identically to `read_events`."""
    parts = _rotated_segments(path)
    if os.path.exists(path):
        parts = parts + [path]
    if not parts:
        raise FileNotFoundError(path)
    out: list[dict] = []
    for p in parts:
        rows = read_events(p)
        for r in rows:
            if r.get("event") == "header" and out:
                continue  # continuation header of a later segment
            out.append(r)
    return out


def events_of(records: list[dict], *names: str) -> list[dict]:
    """Filter records by event type (reader-side convenience)."""
    want = set(names)
    return [r for r in records if r.get("event") in want]
