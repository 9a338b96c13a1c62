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
reader files of the same names. models/phi4flash.py: `sel_scan_state_rms`,
the root mean square of the selective scan's state after a sequence's last
position, mean over the Mamba-1 layers held; `gmu_gate_rms`, that of the
gated memory m . silu(u W_g), mean over the GMU layers held (0: the memory
is not wired); `diff_lambda_mean`, the differential attention's lam, mean
over the attention and cross layers held; read by the reader files of the
same names and by `tools/telemetry_report.py`. models/xing4.py:
`mhc_res_gap`, the largest |row or column sum of H_res - 1| over a step's
tokens (how far twenty Sinkhorn iterations leave the residual mapping from
doubly stochastic), and `mhc_res_offdiag`, the mass of a row of H_res off its
diagonal (0 the plain residual, 0.75 four streams fully mixed), both means
over the sub-layers held; `mla_kv_latent_rms`, the root mean square of latent
attention's c_kv before its norm, mean over the layers held;
`moe_bias_swap_share`, the share of the tokens' expert choices that score +
selection bias made and the score alone would not have, mean over the sparse
layers held; read likewise. models/nemotronh.py:
Granite's two, Mellum 2's four and `moe_bias_swap_share` as above, and
`moe_latent_rms`, the root mean square of the latent its experts read, and
`moe_relu2_active`, the share of the held experts' hidden units, over the
rows in a group, that relu left above zero, both means over the `E` layers
held; read likewise.

The host runs about one step ahead of the chip: no span after the dispatch
of step k needs step k itself. What stops it is the first read of step
k-1's outputs, the `guard` span (or `health`, see above), which is a wait
with the chip busy. `log` reads the step just dispatched, every
`MGWFBP_LOG_INTERVAL`-th step, and the chip idles through the next `place`
and dispatch.

Set-up is spanned by the same means (SetupRecorder below). What a (re)started
job does before it trains again happens mostly before an `EventWriter`
exists: the interpreter's start, the imports, the backend, the whole of
`Trainer.__init__` (the writer is built near its end). So those spans are
kept in memory on `time.perf_counter`, the clock `EventWriter.now` reads, and
written once, as one `setup` record, when the host has read the first step's
results (the `guard` read one step later, else `health`, else the epoch's
`drain`), which is after that step's own `step` record and before the next:

    {"event": "setup", "origin_wall": 1790736012.42,
     "spans": {"setup": [-31.2, 105.8, null],
               "before_init": [-31.2, 21.3, "setup"],
               "import": [-30.9, 4.1, "before_init"],
               "init": [-9.9, 53.2, "setup"], "data": [-9.1, 27.0, "init"],
               "dataset": [-9.1, 26.6, "data"], ...,
               "first_step": [44.1, 19.0, "setup"],
               "trace": [44.2, 4.9, "first_step"], ...},
     "counters": {"programs_traced": 812, "programs_lowered": 97, ...}}

`spans` maps a name to `[start_s, dur_s, parent]`: `start_s` on the stream's
clock (`EventWriter.clock_of`: negative for what preceded the writer, the
clock `benchmarks/run.py align` ties to the device trace), `parent` the span
that caused it, None for a root. A span's self time is its duration less what
its children cover (`self_times`). Every span entered through `setup_span`
also enters a `jax.profiler.TraceAnnotation` of its name. SETUP_SPANS lists
the names; the origin is the process's start as the OS knows it
(`_process_age_s`), so the interpreter's start and every import are inside
`before_init`. `first_step` is step 1's dispatch, the `start_s` and `dur_s`
of its `step` record; its children come from jax's own monitoring events,
which the one listener below keeps while a set-up is open and never after:
`trace` (the longest `jaxpr_trace_duration` inside the dispatch: the step
program's, which holds every trace nested in it), `lower`
(`jaxpr_to_mlir_module_duration`), `compile` (`backend_compile_duration`:
cache key and load on a hit, the compile and the cache write on a miss) and
inside it `cache_load` (`cache_retrieval_time_sec`); what the trace itself
lowered or compiled is inside `trace` and not counted twice. Counters, over
the whole set-up: `programs_traced`, `programs_lowered`, `cache_loads`;
`programs_compiled`, backend compiles that the persistent cache did not hold
and would (at least `jax_persistent_cache_min_compile_time_secs` long: a warm
start reads 0); `small_compiles` and `small_compile_s`, the shorter ones,
which no start finds cached; `kernel_trace_s`, the outermost traces nested in
the step program's, summed; `slow_events`, every monitoring event of 0.1 s or
more as `[event, function, seconds]`, at most 32, longest first. Readers:
`benchmarks/setup_spans.py` (by `setup_record()`), `tools/telemetry_report.py`
and the Trainer's own "set-up:" log line (`setup_line`).

A later Trainer of the same process opens a set-up of its own at its
constructor's entry (no `before_init`). A rebuild that recompiles the step
(`update_nworker`, autotune's swap) writes one more record with `steps` and
`first_step` (and the latter's children) alone, at that step's dispatch. With
telemetry off the constructor enters NO_SPAN, reads no clock and drops the
process's buffer, so the listener is back to its integer add. A name entered
again under the same parent keeps its first start and sums its durations;
under another parent it is kept as `<parent>.<name>`.
"""

from __future__ import annotations

import contextlib
import os
import time
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

# the `setup` record's spans, each under its parent; benchmarks/setup_spans.py
# and tools/telemetry_report.py go by these names
SETUP_SPANS = (
    "setup",             # origin -> the host has read step 1's results
    "before_init",       #   origin -> Trainer.__init__'s entry
    "import",            #     mgwfbp_tpu's import -> train/trainer.py's end
    "backend",           #     the program's own first backend touch
                         #     (train_cli.main; under `mesh` where make_mesh
                         #     is first): absent where the caller made it
    "init",              #   Trainer.__init__
    "mesh",              #     make_mesh
    "model",             #     _create_model, _apply_lm_window (both calls)
    "data",              #     _build_loaders
    "dataset",           #       the data sets' construction (data_prepare)
    "optimizer",         #     _build_optimizer, the state made and placed
    "reducer",           #     _build_reducer
    "profile_backward",  #       _profile_backward
    "profile_forward",   #       _profile_forward
    "solve",             #       make_merged_allreduce: solver, bucket plan
    "steps",             #     _build_steps
    "sinks",             #     _build_run_sinks
    "resume",            #     _maybe_resume
    "first_step",        #   step 1's dispatch (its record's start_s, dur_s)
    "trace",             #     the step program's trace
    "lower",             #     jaxpr -> MLIR module
    "compile",           #     cache key + load, or compile + write
    "cache_load",        #       the persistent cache's read
    "first_result",      #   step 1's dispatch returned -> its results read
    "program_read",      #     _note_step_program, _note_traced_programs
    "step_map",          #       profiling.step_map() where the step has
                         #       gradient collectives to count; a
                         #       one-device run builds it only when a
                         #       profile window or a trace's reader asks
)

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SLOW_EVENT_S = 0.1
_SLOW_EVENTS_KEPT = 32
# a set-up nobody closes (no Trainer is ever built) stops keeping events here
_EVENTS_KEPT = 1 << 16
_lowered = 0
# the set-up that is open (SetupRecorder, below), None once it is written
_setup: Optional["SetupRecorder"] = None


def _on_duration_event(name: str, secs: float, **kw) -> None:
    global _lowered
    if name == _LOWERING_EVENT:
        _lowered += 1
    if _setup is not None:
        _setup.note(name, secs, kw.get("fun_name", ""))


# one listener for the process, however many Trainers it builds
jax.monitoring.register_event_duration_secs_listener(_on_duration_event)


def lowered_programs() -> int:
    """Programs lowered in this process since this module was imported."""
    return _lowered


def _process_age_s() -> Optional[float]:
    """Seconds since the OS started this process: its start time
    (/proc/self/stat, field 22, clock ticks after boot) against the time
    since boot. /proc/stat's `btime` would give the same difference in whole
    seconds only, so the boot clock is read from /proc/uptime (10 ms). None
    where /proc cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # the command (field 2) may hold spaces: count from its ')'
            started = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up_s = float(f.read().split()[0])
        age_s = up_s - started / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return age_s if age_s >= 0.0 else None


def self_times(spans: dict) -> dict:
    """name -> the span's duration less what its children cover."""
    out = {name: span[1] for name, span in spans.items()}
    for _, dur_s, parent in spans.values():
        if parent in out:
            out[parent] -= dur_s
    return out


class _SetupSpan:
    """One entry of a set-up span: as `_Span`, the clock read lies inside
    the profiler annotation; the parent is whatever span is open."""

    __slots__ = ("_rec", "_name", "_annotation", "_t0")

    def __init__(self, rec: "SetupRecorder", name: str):
        self._rec = rec
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        self._name = self._rec.enter(self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._rec.leave(self._name, self._t0, time.perf_counter() - self._t0)
        self._annotation.__exit__(*exc)
        return False


class SetupRecorder:
    """One set-up's spans and monitoring events, on `time.perf_counter`,
    until `finish` puts them on a stream's clock. `begin_setup` makes it."""

    def __init__(self, origin: float, roots: tuple = ("setup",)):
        self.origin = origin
        self.origin_wall = time.time() - (time.perf_counter() - origin)
        # name -> [start, seconds, parent]
        self.spans: dict[str, list] = {}
        self._open = list(roots)
        # a rebuild's record has no root: `steps` and `first_step` alone
        self.rebuild = not roots
        # (clock at the event's end, event, seconds, function)
        self.events: list[tuple[float, str, float, str]] = []
        # the first step dispatched after the build, its dispatch on the
        # STREAM's clock, and when its results were on the host
        self.first_step: Optional[int] = None
        self._dispatch: Optional[tuple[float, float]] = None
        self._read_at: Optional[float] = None

    def span(self, name: str) -> _SetupSpan:
        return _SetupSpan(self, name)

    def enter(self, name: str) -> str:
        """Opens `name` under the span that is open; returns the name it is
        kept under (`<parent>.<name>` where the name has another parent)."""
        parent = self._open[-1] if self._open else None
        have = self.spans.get(name)
        if have is not None and have[2] != parent:
            name = f"{parent}.{name}"
        self._open.append(name)
        return name

    def leave(self, name: str, start: float, seconds: float) -> None:
        self._open.remove(name)
        self.add(name, start, seconds, self._open[-1] if self._open else None)

    def add(self, name: str, start: float, seconds: float, parent) -> None:
        have = self.spans.get(name)
        if have is None:
            self.spans[name] = [start, seconds, parent]
        else:
            have[1] += seconds

    def close_before_init(self, now: float) -> None:
        """A constructor claims the process's set-up: `before_init` ends."""
        self.add("before_init", self.origin, now - self.origin, "setup")
        self._open = ["setup"]

    def note(self, event: str, seconds: float, function: str) -> None:
        if len(self.events) < _EVENTS_KEPT:
            self.events.append(
                (time.perf_counter(), event, seconds, str(function)))

    def dispatched(self, step: int, start_s: float, dur_s: float) -> None:
        """The first step after the build is on its way: `start_s`, `dur_s`
        of its `step` record."""
        self.first_step = int(step)
        self._dispatch = (float(start_s), float(dur_s))

    def results_read(self) -> None:
        """The host holds the first step's results (the first call counts)."""
        if self._read_at is None:
            self._read_at = time.perf_counter()

    def _first_step_spans(self, start: float, end: float) -> tuple:
        """(spans, kernel_trace_s) of the events inside [start, end]: the
        longest trace is the step program's; what it traced, lowered and
        compiled on its own way is inside it."""
        inside = [e for e in self.events if start <= e[0] <= end]
        traces = [e for e in inside if e[1] == _TRACE_EVENT]
        spans: dict[str, list] = {}
        nested_s = 0.0
        t_lo = t_hi = start
        if traces:
            t_hi, _, trace_s, _ = max(traces, key=lambda e: e[2])
            t_lo = t_hi - trace_s
            spans["trace"] = [t_lo, trace_s, "first_step"]
            # outermost first: by start, the longer first among equals
            covered = t_lo
            for at, _, secs, _ in sorted(
                    (e for e in traces if t_lo <= e[0] - e[2] and e[0] < t_hi),
                    key=lambda e: (e[0] - e[2], -e[2])):
                if at > covered:
                    nested_s += secs
                    covered = at
        for name, event, parent in (
                ("lower", _LOWERING_EVENT, "first_step"),
                ("compile", _COMPILE_EVENT, "first_step"),
                ("cache_load", _CACHE_LOAD_EVENT, "compile")):
            for at, what, secs, _ in inside:
                if what == event and not t_lo <= at < t_hi:
                    have = spans.setdefault(name, [at - secs, 0.0, parent])
                    have[1] += secs
        if "compile" not in spans:
            spans.pop("cache_load", None)
        return spans, nested_s

    def _counters(self) -> dict:
        least_s = float(
            jax.config.jax_persistent_cache_min_compile_time_secs)
        counts = dict.fromkeys((
            "programs_traced", "programs_lowered", "programs_compiled",
            "small_compiles", "cache_loads"), 0)
        small_s = 0.0
        loaded = False
        for _, event, secs, _ in self.events:
            if event == _TRACE_EVENT:
                counts["programs_traced"] += 1
            elif event == _LOWERING_EVENT:
                counts["programs_lowered"] += 1
            elif event == _CACHE_LOAD_EVENT:
                counts["cache_loads"] += 1
                loaded = True  # inside the compile event that follows
            elif event == _COMPILE_EVENT:
                if loaded:
                    loaded = False
                elif secs >= least_s:
                    counts["programs_compiled"] += 1
                else:
                    counts["small_compiles"] += 1
                    small_s += secs
        slow = sorted(
            (e for e in self.events if e[2] >= _SLOW_EVENT_S),
            key=lambda e: -e[2])[:_SLOW_EVENTS_KEPT]
        return {
            **counts, "small_compile_s": round(small_s, 6),
            "slow_events": [
                [event.rsplit("/", 1)[-1], function, round(secs, 3)]
                for _, event, secs, function in slow],
        }

    def finish(self, clock_of: Callable[[float], float]) -> dict:
        """The record's fields, spans on the clock `clock_of` maps
        `perf_counter` to; the set-up is over for the listener too."""
        global _setup, _last_record
        if _setup is self:
            _setup = None
        end = self._read_at if self._read_at is not None \
            else time.perf_counter()
        spans = {name: list(span) for name, span in self.spans.items()}
        counters = self._counters()
        root = None if self.rebuild else "setup"
        if self._dispatch is not None:
            # the step record's own numbers, taken back to perf_counter
            start = self._dispatch[0] - clock_of(0.0)
            spans["first_step"] = [start, self._dispatch[1], root]
            parts, nested_s = self._first_step_spans(
                start, start + self._dispatch[1])
            counters["kernel_trace_s"] = round(nested_s, 6)
            spans.update(parts)
            if root is not None:
                done = start + self._dispatch[1]
                spans["first_result"] = [done, max(end - done, 0.0), root]
                if "program_read" in spans:  # entered while this was open
                    spans["program_read"][2] = "first_result"
        if root is not None:
            spans[root] = [self.origin, end - self.origin, None]

        def on_stream(start: float, dur: float, parent) -> list:
            # whole microseconds, start and end rounded as `_write` does
            start_s = round(clock_of(start), 6)
            end_s = round(clock_of(start + dur), 6)
            return [start_s, round(end_s - start_s, 6), parent]

        record = {
            "origin_wall": round(self.origin_wall, 3),
            "spans": {name: on_stream(*span) for name, span in spans.items()},
            "counters": counters,
        }
        if self._dispatch is not None:
            # to the last digit what the step's own record says
            record["spans"]["first_step"][:2] = self._dispatch
        _last_record = record
        return record


def _process_setup() -> SetupRecorder:
    """The set-up this process opened with: origin at the process's start,
    `before_init` open until a Trainer's constructor claims it."""
    now = time.perf_counter()
    import mgwfbp_tpu

    imported = getattr(mgwfbp_tpu, "_IMPORT_T0", now)
    age_s = _process_age_s()
    origin = now - age_s if age_s is not None else imported
    setup = SetupRecorder(min(origin, imported), roots=("before_init",))
    setup.add("import", imported, 0.0, "before_init")
    return setup


_setup = _process_setup()
_claimed = False
_last_record: Optional[dict] = None


def note_imported() -> None:
    """train/trainer.py's last line: the `import` span of the process's
    set-up ends here (at the first call)."""
    setup = _setup
    if setup is not None and not _claimed and not setup.spans["import"][1]:
        setup.spans["import"][1] = \
            time.perf_counter() - setup.spans["import"][0]


def begin_setup(rebuild: bool = False) -> SetupRecorder:
    """A Trainer's constructor (or, `rebuild`, a rebuild of its step) opens
    a set-up: the process's own where no Trainer has claimed it yet, with
    `before_init` closed here; else a new one whose origin is now."""
    global _setup, _claimed
    now = time.perf_counter()
    if rebuild:
        _setup = SetupRecorder(now, roots=())
    elif _setup is not None and not _claimed:
        _setup.close_before_init(now)
    else:
        _setup = SetupRecorder(now)
    _claimed = True
    return _setup


def drop_setup() -> None:
    """Telemetry is off: whatever set-up is open is dropped unwritten, and
    the listener keeps nothing from here on. Reads no clock."""
    global _setup, _claimed
    _setup, _claimed = None, True


def setup_span(name: str):
    """A span of the set-up that is open, for the layers under the Trainer
    (data_prepare, make_mesh, train_cli); NO_SPAN where none is."""
    return NO_SPAN if _setup is None else _setup.span(name)


def backend_span():
    """`backend`, round the program's first touch of the backend
    (`jax.devices()`, `init_distributed`); NO_SPAN where a backend is up
    already (the caller's own touch, as under benchmarks/run.py: the parent
    span's self time holds it)."""
    if _setup is None:
        return NO_SPAN
    from jax._src import xla_bridge

    return NO_SPAN if xla_bridge.backends_are_initialized() \
        else _setup.span("backend")


def setup_record() -> Optional[dict]:
    """The last `setup` record this process finished, or None."""
    return _last_record


def setup_line(record: dict) -> str:
    """The record as one log line, in the constructor's own order."""
    spans = record["spans"]

    def of(parent: str, *extra: str) -> str:
        parts = [f"{name} {span[1]:.1f}" for name, span in spans.items()
                 if span[2] == parent and name not in extra]
        return f" ({', '.join(parts)})" if parts else ""

    def part(label: str, name: str, *extra: str) -> Optional[str]:
        if name not in spans:
            return None
        return f"{label} {spans[name][1]:.1f}{of(name, *extra)}"

    first = part("first step", "first_step")
    if first and "cache_load" in spans:
        first = first[:-1] + f" of which cache load " \
            f"{spans['cache_load'][1]:.1f})"
    parts = [
        part("before the constructor", "before_init"),
        part("constructor", "init"),
        part("steps rebuilt in", "steps") if "setup" not in spans else None,
        first, part("first result", "first_result"),
    ]
    total = f"{spans['setup'][1]:.1f} s to the first result" \
        if "setup" in spans else "the step rebuilt"
    counters = record["counters"]
    return (
        f"set-up: {total}: " + ", ".join(p for p in parts if p)
        + f"; {counters['programs_compiled']} program(s) compiled, "
        f"{counters['cache_loads']} loaded from the compile cache, "
        f"{counters['small_compiles']} too small for it "
        f"({counters['small_compile_s']:.1f} s)")


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

    def dispatch_span(self) -> tuple[float, float]:
        """`start_s`, `dur_s` of the step dispatched last."""
        return self._record["start_s"], self._record["dur_s"]

    def holds(self, step: int) -> bool:
        """The record of `step` is still held back, not yet written."""
        return self._record is not None and self._record["step"] <= step

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
