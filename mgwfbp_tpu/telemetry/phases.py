"""Phase spans of the train loop's iterations, on the event stream's clock.

A `step` record's `start_s` / `dur_s` time the dispatch alone. With
telemetry on, `Trainer.train_epoch` hands every other part of an iteration
to `PhaseRecorder.span`, which reads the stream's clock (`EventWriter.now`)
round it and enters a `jax.profiler.TraceAnnotation` of the same name, so the
stream and a profiler trace taken over the loop show the same spans. The
spans ride on the step's own record:

    {"event": "step", "step": 12, "epoch": 0, "start_s": 3.41, "dur_s": 0.004,
     "phases": {"wait": [3.400, 0.002], "place": [3.402, 0.008],
                "guard": [3.414, 0.041], "health": [3.455, 0.0004],
                "tail": [3.4554, 0.0002], "log": [3.4556, 0.002]},
     "ready": 3, "native": 1, "lowered": 0, "stats_ready": 1}

`phases` maps a name to `[start_s, dur_s]` on the clock `start_s` is on; a
phase entered more than once in an iteration (`wait` and `place` with
`nsteps_update` > 1) keeps its first start and sums its durations. The first
step of an epoch also carries `restart`, the last one `drain` and `snapshot`
(PHASES below). `place` starts at the clock read that ended `wait`: the loop
runs no code of its own between the two, only the rebinding of its batch
variable, and yet 6.8 ms a step passed there on four chips (PERF.md, PR 24).

A record is held back until the NEXT dispatch (or the end of `train_epoch`):
only then has its aftermath run, and only then is the chip busy, so that
serializing the line costs the device nothing. So a step's record still
precedes its `health` record (read one step late), except on an epoch's last
step, whose statistics are drained inside its `drain`; a consumer that
follows the stream live sees step N once step N+1 is on its way; and a
process killed outright (SIGKILL, a crash of the runtime) takes the record of
the step in flight with it. The watchdog's abort writes it first
(`Trainer._on_watchdog_stall`), an exception that unwinds the loop does too.

Counters: `ready`, batches the prefetch pool held finished when the loop
asked for one (the fewest over a step's micro-batches; left out where the
loader cannot tell), read by `benchmarks/layer_metrics/pool_ready.py` and
`tools/telemetry_report.py`; `native`, 1 when every batch of the step came
out of the pool's native transform pass (mgwfbp_tpu/native: one kernel call
over the uint8 source), 0 when one took the NumPy fallback or the loader has
no image transform (asked of the loader after each `next`; left out where no
pool runs), read by `tools/telemetry_report.py` and by no benchmark metric
yet; `lowered`, programs lowered between the previous
step's dispatch and this one's (any new program, cache hit or not; an
epoch's last record also counts what followed it), read by
`window_lowerings`; `stats_ready`, 1 when every statistics array the health
drain was about to read had finished on the device (`is_ready()` of the
replica the read takes), else 0 (left out on a step that drained nothing:
the first of a run or an epoch, and all but every N-th with
`MGWFBP_GUARD_CHECK_INTERVAL=N`; the lesser where an epoch's last step
drains twice), read by `tools/telemetry_report.py`. Where the guard ran
first it has waited for that step and this reads 1; with `--no-grad-guard`
the `health` span is where the host waits for the chip, and it reads 0.
A model that declares statistics of its own (`health_keys` and
`step_counters`) adds counters by the same road and for the same step (the
one BEFORE the record's own). models/mellum.py: `moe_here`, the share of the
step's (token, expert) assignments that landed on experts held here, mean
over the held layers; `moe_load_max` and `moe_load_mean`, the tokens on the
fullest held expert and on the average one, in the layer whose fullest is
the fullest; `moe_dropped`, assignments to a held expert that were not
computed (0: the layer is dropless). Read by `moe_here_share`,
`moe_load_imbalance`, `moe_dropped` under benchmarks/layer_metrics and by
`tools/telemetry_report.py`. models/granite.py: `ssm_state_rms`, the root
mean square of the scan's state after a sequence's last position, mean over
the Mamba layers held, and `ssm_log_decay_min`, the most negative sum of
log-decays over one chunk of the scan (any head, any layer); read by the
reader files of the same names.

The host runs about one step ahead of the chip: no span after the dispatch
of step k needs step k itself. What stops it is the first read of step
k-1's outputs, the `guard` span (or `health`, see above), which is a wait
with the chip busy. `log` reads the step just dispatched, every
`MGWFBP_LOG_INTERVAL`-th step, and the chip idles through the next `place`
and dispatch.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Iterator, Optional

import jax

# loop order; the reader files under benchmarks/layer_metrics and
# tools/telemetry_report.py go by these names
PHASES = (
    "restart",   # train_epoch entry to the first `next`: set_epoch, carry
    "wait",      # blocked in the loader's `__next__`
    "place",     # _to_model_batch, _stack_micro, _globalize
    # (the dispatch itself is the record's start_s / dur_s)
    "guard",     # _note_guard_flag: the previous step's non-finite flag
    "health",    # _note_health_stats: starts this step's statistics on
                 # their way to the host, reads the previous step's there
    "tail",      # step checkpoint, async-save poll, fault hooks, preemption
                 # agreement, straggler / drift / profile probes
    "log",       # the metrics pull every MGWFBP_LOG_INTERVAL-th step
    "drain",     # epoch end: _drain_guard_flags, _drain_health_flags
    "snapshot",  # epoch end: `epoch` event, overlap snapshot
)

_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_lowered = 0


def _on_duration_event(name: str, _secs: float, **_kw) -> None:
    global _lowered
    if name == _LOWERING_EVENT:
        _lowered += 1


# one listener for the process, however many Trainers it builds
jax.monitoring.register_event_duration_secs_listener(_on_duration_event)


def lowered_programs() -> int:
    """Programs lowered in this process since this module was imported."""
    return _lowered


# what the loop enters in a span's place with telemetry off: one shared
# object, no clock read, no annotation
NO_SPAN = contextlib.nullcontext()


def no_span(_name: str) -> contextlib.nullcontext:
    return NO_SPAN


class _Span:
    """One entry of a phase: clock read inside the profiler annotation, so
    the annotation's own cost stays outside the span."""

    __slots__ = ("_rec", "_name", "_annotation", "_t0")

    def __init__(self, rec: "PhaseRecorder", name: str):
        self._rec = rec
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = self._rec.span_start()
        return self

    def __exit__(self, *exc):
        self._rec.add(self._name, self._t0, self._rec.now() - self._t0)
        self._annotation.__exit__(*exc)
        return False


# what precedes a step's dispatch; every other phase is the aftermath of the
# step dispatched last
_BEFORE_DISPATCH = ("restart", "wait", "place")


def _least(have: Optional[int], new: int) -> int:
    """A counter over a step's micro-batches keeps its least reading."""
    return new if have is None else min(have, new)


class PhaseRecorder:
    """Collects each iteration's spans and counters and writes them with the
    step's record. `now` is the stream's clock; `emit(**fields)` writes one
    `step` record.

    A record is written after the NEXT dispatch: between a step's end on the
    device and the next dispatch the chip waits for the host, so nothing is
    serialized or written there; after the dispatch the chip computes."""

    def __init__(self, now: Callable[[], float], emit: Callable[..., None]):
        self.now = now
        self._emit = emit
        # of the step not yet dispatched
        self._ahead: dict[str, list[float]] = {}
        self._ready: Optional[int] = None
        self._native: Optional[int] = None
        # the step dispatched last: its aftermath is running
        self._record: Optional[dict] = None
        self._handed_s: Optional[float] = None
        self._lowered = lowered_programs()

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def span_start(self) -> float:
        """The clock; for the first span after the loader handed out a
        batch, the clock read that ended the `wait`."""
        handed_s, self._handed_s = self._handed_s, None
        return self.now() if handed_s is None else handed_s

    def add(self, name: str, start_s: float, dur_s: float) -> None:
        if name in _BEFORE_DISPATCH or self._record is None:
            phases = self._ahead
        else:
            phases = self._record["phases"]
        have = phases.get(name)
        if have is None:
            phases[name] = [start_s, dur_s]
        else:
            have[1] += dur_s

    def stats_ready(self, ready: bool) -> None:
        """The health drain found every array it is about to read finished
        (or not). Goes on the record in whose aftermath the drain runs; an
        epoch's last step drains twice and keeps the lesser."""
        record = self._record
        if record is not None:
            record["stats_ready"] = min(
                record.get("stats_ready", 1), int(ready))

    def counters(self, fields: dict) -> None:
        """A model's own counters for a step (`step_counters` of
        models/mellum.py, models/granite.py), from the arrays the health
        drain just read. They go on the record in whose aftermath the drain
        runs, as `stats_ready` does (so they describe the step before it); a
        second drain of the same aftermath (an epoch's last step) overwrites
        the first's."""
        record = self._record
        if record is not None:
            record.update(fields)

    def batches(self, loader, entered_s: float) -> Iterator:
        """The loader's batches, each `next` inside a `wait` span; `restart`
        runs from `entered_s` (train_epoch's entry) to the first `next`.
        (The `next` that ends the epoch is the pool's shutdown: no iteration
        owns it.)"""
        ready_batches = getattr(loader, "ready_batches", None)
        native_batch = getattr(loader, "native_batch", None)
        it = iter(loader)
        first = True
        while True:
            ready = None if ready_batches is None else ready_batches()
            with jax.profiler.TraceAnnotation("wait"):
                t0 = self.now()
                try:
                    raw = next(it)
                except StopIteration:
                    return
                self._handed_s = self.now()
            if first:
                self.add("restart", entered_s, t0 - entered_s)
                first = False
            self.add("wait", t0, self._handed_s - t0)
            if ready is not None:
                self._ready = _least(self._ready, ready)
            native = None if native_batch is None else native_batch()
            if native is not None:
                self._native = _least(self._native, native)
            yield raw

    def dispatched(
        self, step: int, epoch: int, start_s: float, dur_s: float,
    ) -> None:
        """The step is on its way: spans from here on are its aftermath, and
        the chip is busy, so the step before it is written now."""
        done = self._record
        self._record = {
            "step": int(step), "epoch": int(epoch),
            "start_s": float(start_s), "dur_s": float(dur_s),
            "phases": self._ahead,
        }
        if self._ready is not None:
            self._record["ready"] = self._ready
        if self._native is not None:
            self._record["native"] = self._native
        self._record["lowered"] = self._lowerings()
        self._ahead, self._handed_s = {}, None
        self._ready = self._native = None
        if done is not None:
            self._write(done)

    def flush(self) -> None:
        """The loop is over (or unwinding, or about to be killed): write the
        step dispatched last. Batches short of a step were handed out after
        it: their `wait` and `place` are added to its own."""
        record, self._record = self._record, None
        if record is None:
            return
        ahead, self._ahead = self._ahead, {}
        for name, (start_s, dur_s) in ahead.items():
            have = record["phases"].setdefault(name, [start_s, 0.0])
            have[1] += dur_s
        record["lowered"] += self._lowerings()
        self._write(record)

    def _lowerings(self) -> int:
        lowered = lowered_programs()
        since, self._lowered = lowered - self._lowered, lowered
        return since

    def _write(self, record: dict) -> None:
        # whole microseconds keep the line short; start and end are rounded
        # (not the duration), so successive spans still do not overlap
        record["phases"] = {
            name: [round(start_s, 6),
                   round(round(start_s + dur_s, 6) - round(start_s, 6), 6)]
            for name, (start_s, dur_s) in record["phases"].items()
        }
        self._emit(**record)
