"""Phase spans of the train loop (telemetry/phases.py): the recorder on an
injected clock, a tiny Trainer's epochs (spans ordered, disjoint, inside
their iteration; boundary spans on the first and last steps; nothing entered
with telemetry off), and the operator's views of them (Chrome trace, report).
Nothing here compares a measured time with a number."""

import os

import jax
import pytest

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.telemetry import events_of, read_events
from mgwfbp_tpu.telemetry import phases as phases_module
from mgwfbp_tpu.telemetry.export import chrome_trace
from mgwfbp_tpu.telemetry.phases import PHASES, PhaseRecorder

AFTER = ("guard", "health", "tail", "log", "drain", "snapshot")


class FakeClock:
    """Every read is one tick later than the last."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


class FakeLoader:
    """`ready` lists what the pool holds finished at each `next`."""

    def __init__(self, n, ready=(3,)):
        self.n = n
        self.ready = list(ready)

    def ready_batches(self):
        return self.ready.pop(0) if len(self.ready) > 1 else self.ready[0]

    def __iter__(self):
        return iter(range(self.n))


def _drive(rec, loader, micro=1, epoch=0, first_step=1):
    """The loop's shape: `micro` batches a step, guard and health after the
    dispatch, drain at the end."""
    step, have = first_step - 1, 0
    for _ in rec.batches(loader, rec.now()):
        with rec.span("place"):
            have += 1
        if have < micro:
            continue
        have = 0
        t0 = rec.now()
        step += 1
        rec.dispatched(step, epoch, t0, rec.now() - t0)
        with rec.span("guard"):
            pass
        with rec.span("health"):
            pass
    with rec.span("drain"):
        pass
    rec.flush()


def test_recorder_writes_each_step_once_its_iteration_is_over():
    out = []
    rec = PhaseRecorder(FakeClock(), lambda **f: out.append(f))
    _drive(rec, FakeLoader(3))
    assert [r["step"] for r in out] == [1, 2, 3]
    assert list(out[0]["phases"]) == [
        "restart", "wait", "place", "guard", "health"]
    assert list(out[1]["phases"]) == ["wait", "place", "guard", "health"]
    assert list(out[2]["phases"]) == [
        "wait", "place", "guard", "health", "drain"]
    for r in out:
        assert r["ready"] == 3 and r["lowered"] == 0
        assert set(r) == {"step", "epoch", "start_s", "dur_s", "phases",
                          "ready", "lowered"}
        assert r["dur_s"] == 1.0  # one tick between the two reads
        spans = sorted(r["phases"].values())
        assert all(a + d <= b for (a, d), (b, _) in zip(spans, spans[1:]))
        before = [v for k, v in r["phases"].items() if k not in AFTER]
        after = [v for k, v in r["phases"].items() if k in AFTER]
        assert all(a + d <= r["start_s"] for a, d in before)
        assert all(a >= r["start_s"] + r["dur_s"] for a, _ in after)


def test_recorder_sums_the_waits_of_one_steps_micro_batches():
    out = []
    rec = PhaseRecorder(FakeClock(), lambda **f: out.append(f))
    _drive(rec, FakeLoader(4), micro=2)
    assert [r["step"] for r in out] == [1, 2]
    for r in out:
        start, dur = r["phases"]["wait"]
        assert dur == 2.0  # two waits of one tick each
        assert start < r["phases"]["place"][0]
        assert r["phases"]["place"][1] == 2.0


def test_record_is_written_after_the_next_dispatch_not_before_it():
    """Between a step's end and the next dispatch the chip waits for the
    host: nothing is written there."""
    out = []
    rec = PhaseRecorder(FakeClock(), lambda **f: out.append(f))
    batches = rec.batches(FakeLoader(2), rec.now())
    next(batches)
    rec.dispatched(1, 0, rec.now(), 1.0)
    next(batches)  # step 1's iteration is over ...
    with rec.span("place"):
        pass
    assert out == []  # ... and not yet written
    rec.dispatched(2, 0, rec.now(), 1.0)
    assert [r["step"] for r in out] == [1]
    assert "place" not in out[0]["phases"]  # step 2's, not step 1's
    rec.flush()
    assert [r["step"] for r in out] == [1, 2]
    assert "place" in out[1]["phases"]


def test_batches_short_of_a_step_are_added_to_the_last_steps_record(
        monkeypatch):
    """`nsteps_update` 2 and three micro-batches: the third makes no step.
    Its wait and placement are summed into the last step's, the epoch's end
    goes there too, and the counters add up instead of starting over."""
    out = []
    rec = PhaseRecorder(FakeClock(), lambda **f: out.append(f))
    lowered = iter([2, 5])  # at the dispatch: 2 new; by the flush: 3 more
    monkeypatch.setattr(
        phases_module, "lowered_programs", lambda: next(lowered))
    rec._lowered = 0
    _drive(rec, FakeLoader(3, ready=[2, 1, 0]), micro=2)
    assert [r["step"] for r in out] == [1]
    (r,) = out
    assert list(r["phases"]) == [
        "restart", "wait", "place", "guard", "health", "drain"]
    assert r["phases"]["wait"][1] == 3.0  # three waits of one tick each
    assert r["phases"]["place"][1] == 3.0
    assert r["ready"] == 1  # the fewest over the step's own two batches
    assert r["lowered"] == 5


def test_recorder_flush_is_idempotent_and_keeps_a_record_after_a_raise():
    out = []
    rec = PhaseRecorder(FakeClock(), lambda **f: out.append(f))
    rec.flush()
    assert out == []
    with pytest.raises(RuntimeError):
        for _ in rec.batches(FakeLoader(2), rec.now()):
            rec.dispatched(1, 0, rec.now(), 0.5)
            with rec.span("guard"):
                raise RuntimeError("rollback")
    rec.flush()
    rec.flush()
    assert [r["step"] for r in out] == [1]
    assert "guard" in out[0]["phases"]  # the span closed on the way out


def test_loader_without_a_pool_leaves_ready_out():
    out = []
    rec = PhaseRecorder(FakeClock(), lambda **f: out.append(f))
    _drive(rec, [0, 1])
    assert all("ready" not in r for r in out)


class NativeLoader(FakeLoader):
    """A pool that also says whether the batch it handed out last came from
    the native pass; `native` lists the answers, None for "no pool"."""

    def __init__(self, n, native):
        super().__init__(n)
        self.native = list(native)
        self.handed = -1

    def native_batch(self):
        return self.native[self.handed]

    def __iter__(self):
        for i in range(self.n):
            self.handed = i
            yield i


class Proxy:
    """benchmarks/run.py's `TimedLoader`: `__getattr__` passes every name
    but the iteration through to the program's loader."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        yield from self._inner


@pytest.mark.parametrize("wrap", [lambda x: x, Proxy], ids=["bare", "proxy"])
@pytest.mark.parametrize("native,micro,want", [
    ([1, 1, 1], 1, [1, 1, 1]),
    ([1, 0, 1], 1, [1, 0, 1]),          # one batch took the fallback
    ([0, 0], 1, [0, 0]),                # a pool with nothing native to run
    ([1, 1, 1, 0], 2, [1, 0]),          # the lesser of a step's batches
    ([None, None], 1, [None, None]),    # a loader whose pool is not running
])
def test_native_counter_rides_on_the_steps_record(native, micro, want, wrap):
    out = []
    rec = PhaseRecorder(FakeClock(), lambda **f: out.append(f))
    _drive(rec, wrap(NativeLoader(len(native), native)), micro=micro)
    assert [r.get("native") for r in out] == want
    for r in out:  # beside `ready`, which the same loader answers
        assert r["ready"] == 3


def test_loader_without_a_pool_leaves_native_out():
    out = []
    rec = PhaseRecorder(FakeClock(), lambda **f: out.append(f))
    _drive(rec, [0, 1])
    _drive(rec, FakeLoader(2))  # answers `ready_batches` alone
    assert len(out) == 4 and all("native" not in r for r in out)


def test_one_lowering_listener_for_the_process():
    from jax._src import monitoring

    PhaseRecorder(FakeClock(), lambda **f: None)
    n = len(monitoring.get_event_duration_listeners())
    before = phases_module.lowered_programs()
    PhaseRecorder(FakeClock(), lambda **f: None)
    assert len(monitoring.get_event_duration_listeners()) == n
    jax.jit(lambda x: x * 3 + 1).lower(1.0)
    assert phases_module.lowered_programs() > before


# --------------------------------------------------------------------------
# a tiny Trainer: two epochs of five steps, a log pull every second step
# --------------------------------------------------------------------------


def _cfg(tmp_path, **kw):
    base = dict(
        lr=0.01, max_epochs=2, logdir=str(tmp_path), checkpoint_dir=None,
        seed=3, batch_size=8, num_batches_per_epoch=5,
    )
    base.update(kw)
    return make_config("lenet", **base)


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    from mgwfbp_tpu.train.trainer import Trainer

    tmp_path = tmp_path_factory.mktemp("phases")
    os.environ["MGWFBP_LOG_INTERVAL"] = "2"
    try:
        cfg = _cfg(tmp_path, telemetry=True)
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        t.train_epoch(0)
        t.train_epoch(1)
        t.close()
    finally:
        del os.environ["MGWFBP_LOG_INTERVAL"]
    return read_events(os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl"))


def _timeline(step):
    """The iteration's spans with the dispatch among them, by start."""
    spans = {**step["phases"], "dispatch": [step["start_s"], step["dur_s"]]}
    return sorted(spans.items(), key=lambda kv: kv[1][0])


def test_every_step_record_carries_its_iterations_spans(stream):
    steps = events_of(stream, "step")
    assert [s["step"] for s in steps] == list(range(1, 11))
    for s in steps:
        assert {"wait", "place", "guard", "health", "tail"} <= set(s["phases"])
        assert set(s["phases"]) <= set(PHASES)
        assert ("log" in s["phases"]) == (
            s["step"] % 2 == 0 and s["step"] % 5 != 0)  # break comes first
        assert s["lowered"] >= 0
        assert not {"pulls", "h2d_bytes"} & set(s)  # read by nothing: gone
    assert steps[-1]["lowered"] == 0


def test_spans_are_ordered_disjoint_and_in_loop_order(stream):
    eps = 1e-9
    order = [*PHASES[:3], "dispatch", *PHASES[3:]]
    end = 0.0
    for s in events_of(stream, "step"):
        line = _timeline(s)
        names = [n for n, _ in line]
        assert names == [n for n in order if n in names]
        for name, (start, dur) in line:
            assert dur >= 0 and start >= end - eps, (s["step"], name)
            end = max(end, start + dur)


def test_spans_cover_the_interval_up_to_the_unaccounted_remainder(stream):
    """Interval between two dispatches of one epoch = the spans in it + what
    no span covers, and that remainder is never negative."""
    steps = events_of(stream, "step")
    for a, b in zip(steps, steps[1:]):
        if a["epoch"] != b["epoch"]:
            continue
        inside = a["dur_s"] + sum(
            a["phases"][n][1] for n in ("guard", "health", "tail", "log")
            if n in a["phases"])
        inside += b["phases"]["wait"][1] + b["phases"]["place"][1]
        assert b["start_s"] - a["start_s"] - inside >= -1e-5


def test_boundary_spans_ride_on_an_epochs_first_and_last_steps(stream):
    for s in events_of(stream, "step"):
        first, last = s["step"] % 5 == 1, s["step"] % 5 == 0
        assert ("restart" in s["phases"]) == first
        assert ("drain" in s["phases"]) == last
        assert ("snapshot" in s["phases"]) == last


def test_the_pool_says_how_many_batches_it_held_ready(stream):
    """An epoch's first `next` builds the pool, so nothing can be asked
    before it; from then on the loader answers, and only when asked."""
    for s in events_of(stream, "step"):
        assert ("ready" in s) == (s["step"] % 5 != 1)
        assert 0 <= s.get("ready", 0) <= 4  # workers + depth


def test_the_pool_says_its_batches_came_from_the_native_pass(stream):
    """LeNet's MNIST batches are normalized by `normalize_u8`: asked after
    each `next`, so an epoch's first step answers too."""
    from mgwfbp_tpu import native

    for s in events_of(stream, "step"):
        assert s["native"] == int(native.available())


def test_stats_ready_is_on_the_records_whose_health_span_drained(stream):
    """An epoch's first step queues its statistics and drains nothing; with
    the guard on, every later drain finds its arrays finished."""
    for s in events_of(stream, "step"):
        assert s.get("stats_ready") == (None if s["step"] % 5 == 1 else 1)


def test_health_records_still_follow_their_steps_record(stream):
    """A step's `health` record is drained one step late, so it follows the
    step's own record; an epoch's last step is drained inside its `drain`
    span, before its record (which carries that span) is written."""
    pos = {(r["event"], r.get("step")): i for i, r in enumerate(stream)}
    for step in range(1, 11):
        if step % 5:
            assert pos[("step", step)] < pos[("health", step)]
            assert pos[("health", step)] < pos[("step", step + 1)]
        else:
            assert pos[("health", step)] < pos[("step", step)]
            assert pos[("step", step - 1)] < pos[("health", step)]


def test_place_starts_where_wait_ended(stream):
    """No code of the loop runs between the two, so no time is left there."""
    for s in events_of(stream, "step"):
        wait, place = s["phases"]["wait"], s["phases"]["place"]
        assert place[0] == pytest.approx(wait[0] + wait[1], abs=2e-6)


def test_chrome_trace_renders_the_phases_on_the_loops_track(stream):
    doc = chrome_trace(stream)
    names = {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert "host loop" in names
    loop = sorted(
        (e for e in doc["traceEvents"] if e["ph"] == "X" and e["tid"] == 4),
        key=lambda e: e["ts"])
    assert {e["name"] for e in loop} == {*PHASES, "dispatch"}
    assert all("step" in e["args"] for e in loop)
    for prev, nxt in zip(loop, loop[1:]):
        assert nxt["ts"] >= prev["ts"] + prev["dur"] - 1e-3, (prev, nxt)


def test_report_prints_one_table_of_the_phases(stream):
    import telemetry_report

    report = telemetry_report.format_report(stream)
    assert "host loop phases (10 steps" in report
    table = report.split("host loop phases")[1].split("\n\n")[0]
    rows = [line.split()[0] for line in table.splitlines()[2:]]
    assert rows == [
        *PHASES[:3], "dispatch", *PHASES[3:], "(no", "prefetch", "native",
        "health"]
    assert "when the loop asked (none on" in table and "of 8 steps)" in table
    # asked after each `next`, so all 10 steps answer (1 where g++ built)
    from mgwfbp_tpu import native

    share = 1.0 if native.available() else 0.0
    assert (f"native pass on {int(10 * share)} of 10 steps "
            f"(share {share:.3f})") in table
    # an epoch's first step queues its statistics and drains nothing
    assert ("health statistics: finished when the drain asked on 8 of 8 "
            "steps (100.0%)") in table


def test_streams_without_phases_render_as_before():
    records = [
        {"event": "header", "schema_version": 2, "wall": 0.0},
        {"event": "step", "step": 1, "epoch": 0, "start_s": 0.0, "dur_s": 0.1},
    ]
    import telemetry_report

    assert "host loop phases" not in telemetry_report.format_report(records)
    doc = chrome_trace(records)
    assert all(e.get("tid") != 4 for e in doc["traceEvents"])


def test_with_telemetry_off_no_span_helper_is_entered(tmp_path, monkeypatch):
    from mgwfbp_tpu.train.trainer import Trainer

    def refuse(*a, **k):
        raise AssertionError("a span helper was entered with telemetry off")

    monkeypatch.setattr(PhaseRecorder, "__init__", refuse)
    monkeypatch.setattr(phases_module._Span, "__init__", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", refuse)
    t = Trainer(_cfg(tmp_path, telemetry=False), synthetic_data=True,
                profile_backward=False)
    metrics = t.train_epoch(0)
    t.close()
    assert "loss" in metrics and t.iteration == 5


class Killed(Exception):
    pass


@pytest.mark.parametrize("how", ["unwound", "watchdog_abort"])
def test_a_loop_stopped_inside_tail_leaves_the_step_in_flight_on_the_stream(
        tmp_path, monkeypatch, how):
    """What a post-mortem finds. A step's record is held back until the next
    dispatch, so a process killed outright inside step 3's `tail` leaves
    steps 1 and 2; an exception that unwinds the loop, and the watchdog's
    abort (rc 86 follows `_on_watchdog_stall`), write step 3's first, with
    the spans the loop got through."""
    from mgwfbp_tpu.train.trainer import Trainer

    t = Trainer(_cfg(tmp_path, telemetry=True), synthetic_data=True,
                profile_backward=False)
    path = t.telemetry.path
    on_disk = []

    def probe():
        if t.iteration != 3:
            return
        on_disk.extend(
            s["step"] for s in events_of(read_events(path), "step"))
        if how == "watchdog_abort":
            t._on_watchdog_stall(
                phase="train", idle_s=9.0, timeout_s=1.0, abort=True)
        raise Killed

    monkeypatch.setattr(t, "_maybe_straggler_probe", probe)
    with pytest.raises(Killed):
        t.train_epoch(0)
    t.close()
    assert on_disk == [1, 2]  # all a SIGKILL at that point leaves behind
    stream = read_events(path)
    steps = events_of(stream, "step")
    assert [s["step"] for s in steps] == [1, 2, 3]  # once each
    got = set(steps[-1]["phases"])
    assert {"wait", "place", "guard", "health"} <= got
    # the abort comes while `tail` is still open; unwinding closes it
    assert ("tail" in got) == (how == "unwound")
    if how == "watchdog_abort":
        order = [r["event"] for r in stream]
        assert order.index("watchdog_stall") > max(
            i for i, r in enumerate(stream)
            if r["event"] == "step" and r["step"] == 3)


def test_routing_counters_ride_the_record_and_the_report_prints_them(stream):
    """PhaseRecorder.counters puts a model's own counters (here the
    sparse-expert model's, from its `step_counters`) on the record in whose
    aftermath the drain ran; a stream without them (every other model's)
    prints no routing line."""
    import numpy as np
    import telemetry_report

    from mgwfbp_tpu.models import mellum
    from mgwfbp_tpu.telemetry.phases import PhaseRecorder

    model = mellum.Mellum2LM(shape=mellum.MELLUM2_TINY)  # top 2 a token

    def routing(tokens, dropped, assignments):
        return model.step_counters(
            {mellum.MOE_TOKENS_KEY: tokens,
             mellum.MOE_DROPPED_KEY: np.asarray(dropped)},
            tokens=assignments // 2)

    assert "expert routing" not in telemetry_report.format_report(stream)
    written = []
    clock = iter(float(i) for i in range(100))
    rec = PhaseRecorder(lambda: next(clock), lambda **f: written.append(f))
    rec.counters(routing(np.ones((2, 2)), 0.0, 8))  # before any dispatch: dropped
    rec.dispatched(1, 0, 0.0, 0.1)
    rec.add("guard", 0.2, 0.1)
    # two layers, four held experts; 16 tokens x top 2 = 32 assignments
    rec.counters(routing(np.array([[2.0, 2, 2, 2], [1.0, 9, 1, 1]]), 0.0, 32))
    rec.dispatched(2, 0, 1.0, 0.1)
    rec.flush()
    first, second = written
    assert first["moe_here"] == pytest.approx((8 + 12) / 2 / 32)
    # the layer whose fullest expert is the fullest: the second
    assert (first["moe_load_max"], first["moe_load_mean"]) == (9.0, 3.0)
    assert first["moe_dropped"] == 0.0 and "moe_here" not in second
    records = [
        {"event": "header", "schema_version": 2, "wall": 0.0},
        *({"event": "step", "epoch": 0, **w} for w in written),
    ]
    report = telemetry_report.format_report(records)
    assert ("expert routing (1 steps): 31.25% of the assignments landed on "
            "experts held here; fullest held expert 3.000x") in report
    assert "0 dropped" in report
