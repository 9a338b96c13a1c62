"""Set-up as spans (telemetry/phases.py): the `setup` record of a tiny
Trainer (one record, after step 1's and before step 2's; every span under a
parent that exists and inside it; the first step's parts from jax's own
monitoring events; counters against a listener of the test's own), the
rebuild's record, nothing opened with telemetry off, the listener at rest
once set-up is closed, the record under `train_cli` itself, and the
operator's views (log line, report table). Nothing here compares a measured
time with a number."""

import json
import os
import subprocess
import sys

import jax
import pytest

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.telemetry import events_of, read_events
from mgwfbp_tpu.telemetry import phases
from mgwfbp_tpu.telemetry.phases import SetupRecorder

TRACE, LOWER = phases._TRACE_EVENT, phases._LOWERING_EVENT
COMPILE, CACHE_LOAD = phases._COMPILE_EVENT, phases._CACHE_LOAD_EVENT
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EPS = 2e-6  # a record's times are whole microseconds


class NoClock:
    """In `phases.time`'s place where no clock may be read."""

    def __getattr__(self, name):
        raise AssertionError(f"time.{name} was read with no set-up open")


def _cfg(tmp_path, **kw):
    base = dict(
        lr=0.01, max_epochs=2, logdir=str(tmp_path), checkpoint_dir=None,
        seed=3, batch_size=8, num_batches_per_epoch=4,
    )
    base.update(kw)
    return make_config("lenet", **base)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One epoch on eight devices, a resize to four, one more epoch; beside
    it a listener of the test's own, counting while a set-up is open."""
    from mgwfbp_tpu.train.trainer import Trainer

    tmp_path = tmp_path_factory.mktemp("setup")
    phases.begin_setup()  # whatever this process opened with is claimed
    seen: list[list] = [[]]

    def listen(name, secs, **kw):
        if phases._setup is not None:
            seen[-1].append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        cfg = _cfg(tmp_path, telemetry=True)
        tags = [None, None]
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        tags[0] = cfg.tag()
        t.train_epoch(0)
        first = phases.setup_record()
        seen.append([])
        t.update_nworker(4)
        tags[1] = cfg.tag()
        t.train_epoch(1)
        t.close()
    finally:
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(listen)
    streams = [
        read_events(os.path.join(str(tmp_path), tag, "telemetry.jsonl"))
        for tag in tags]
    return {"streams": streams, "seen": seen, "first": first,
            "last": phases.setup_record()}


def _setup_of(stream):
    records = events_of(stream, "setup")
    assert len(records) == 1
    return records[0]


def test_one_record_after_step_1s_and_before_step_2s(run):
    order = [(e["event"], e.get("step")) for e in run["streams"][0]
             if e["event"] in ("step", "setup")]
    assert order.index(("step", 1)) < order.index(("setup", None)) \
        < order.index(("step", 2))
    record = _setup_of(run["streams"][0])
    assert {k: record[k] for k in ("spans", "counters", "origin_wall")} \
        == json.loads(json.dumps(run["first"]))


def test_every_span_names_a_parent_that_exists(run):
    spans = _setup_of(run["streams"][0])["spans"]
    assert [n for n, s in spans.items() if s[2] is None] == ["setup"]
    assert all(s[2] in spans for n, s in spans.items() if n != "setup")
    # a later Trainer of the process: its set-up starts at its constructor
    assert "before_init" not in spans
    assert spans["init"][0] == pytest.approx(spans["setup"][0], abs=1e-3)
    wanted = {"mesh", "model", "data", "optimizer", "reducer", "steps",
              "sinks", "resume"}
    assert wanted == {n for n, s in spans.items() if s[2] == "init"}
    assert spans["dataset"][2] == "data"
    assert spans["program_read"][2] == "first_result"
    assert all(n in phases.SETUP_SPANS for n in spans)


def test_children_lie_inside_their_parents(run):
    spans = _setup_of(run["streams"][0])["spans"]
    for name, (start_s, dur_s, parent) in spans.items():
        assert dur_s >= 0.0
        if parent is not None:
            p_start, p_dur, _ = spans[parent]
            assert start_s >= p_start - EPS, (name, parent)
            assert start_s + dur_s <= p_start + p_dur + EPS, (name, parent)
    own = phases.self_times(spans)
    assert own["init"] >= -EPS * len(spans)
    assert sum(s[1] for s in spans.values() if s[2] == "init") \
        <= spans["init"][1] + EPS * len(spans)
    # what preceded the writer is negative on the stream's clock
    assert spans["init"][0] < 0.0 < spans["first_step"][0]


def test_first_step_is_step_1s_own_record(run):
    stream = run["streams"][0]
    spans = _setup_of(stream)["spans"]
    step_1 = next(e for e in events_of(stream, "step") if e["step"] == 1)
    assert spans["first_step"][:2] == [step_1["start_s"], step_1["dur_s"]]
    parts = [spans[n][1] for n in ("trace", "lower", "compile")]
    assert all(p > 0.0 for p in parts)
    assert sum(parts) <= spans["first_step"][1] + 3 * EPS
    assert "cache_load" not in spans  # the tests' compile cache is off
    # the first result is read in step 2's aftermath
    step_2 = next(e for e in events_of(stream, "step") if e["step"] == 2)
    guard_end = sum(step_2["phases"]["guard"])
    assert spans["setup"][0] + spans["setup"][1] <= guard_end + EPS
    assert spans["first_result"][0] + spans["first_result"][1] \
        >= step_2["start_s"] + step_2["dur_s"] - EPS


@pytest.mark.parametrize("which", [0, 1])
def test_counters_equal_a_listener_of_the_tests_own(run, which):
    seen = run["seen"][which]
    counters = _setup_of(run["streams"][which])["counters"]
    assert counters["programs_traced"] == seen.count(TRACE) > 0
    assert counters["programs_lowered"] == seen.count(LOWER) > 0
    assert counters["cache_loads"] == seen.count(CACHE_LOAD)
    assert counters["programs_compiled"] + counters["small_compiles"] \
        + counters["cache_loads"] == seen.count(COMPILE) > 0
    assert counters["kernel_trace_s"] >= 0.0
    slow = counters["slow_events"]
    assert len(slow) <= 32
    assert [s[2] for s in slow] == sorted((s[2] for s in slow), reverse=True)
    assert all(s[2] >= 0.1 for s in slow)


def test_a_rebuild_writes_steps_and_first_step_alone(run):
    stream = run["streams"][1]
    record = _setup_of(stream)
    assert record == {**record, **json.loads(json.dumps(run["last"]))}
    spans = record["spans"]
    assert {n for n, s in spans.items() if s[2] is None} \
        == {"steps", "first_step"}
    assert set(spans) - {"steps", "first_step"} \
        <= {"trace", "lower", "compile", "cache_load"}
    assert spans["trace"][2] == "first_step"
    step = next(e for e in events_of(stream, "step") if e["step"] == 5)
    assert spans["first_step"][:2] == [step["start_s"], step["dur_s"]]
    assert spans["steps"][0] + spans["steps"][1] <= spans["first_step"][0]


def test_the_listener_is_at_rest_once_set_up_is_closed(run, monkeypatch):
    assert phases._setup is None
    monkeypatch.setattr(phases, "time", NoClock())
    before = phases.lowered_programs()
    phases._on_duration_event(TRACE, 0.5, fun_name="step")
    phases._on_duration_event(LOWER, 0.5, fun_name="jit(step)")
    assert phases.lowered_programs() == before + 1
    assert phases.setup_record() == run["last"]
    assert phases.setup_span("data") is phases.NO_SPAN
    assert phases.backend_span() is phases.NO_SPAN


def test_with_telemetry_off_nothing_is_opened_and_no_clock_read(
        tmp_path, monkeypatch):
    from jax._src import monitoring

    from mgwfbp_tpu.train.trainer import Trainer

    def refuse(*a, **k):
        raise AssertionError("set-up was spanned with telemetry off")

    phases.begin_setup()  # an open set-up: the constructor drops it
    assert phases._setup is not None
    last = phases.setup_record()
    listeners = len(monitoring.get_event_duration_listeners())
    monkeypatch.setattr(phases, "time", NoClock())
    monkeypatch.setattr(SetupRecorder, "__init__", refuse)
    monkeypatch.setattr(phases._SetupSpan, "__init__", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    t = Trainer(_cfg(tmp_path, telemetry=False), synthetic_data=True,
                profile_backward=False)
    assert phases._setup is None and t._setup is None
    t.train_epoch(0)
    t.close()
    assert phases.setup_record() is last
    assert len(monitoring.get_event_duration_listeners()) == listeners
    assert not os.path.exists(
        os.path.join(str(tmp_path), t.config.tag(), "telemetry.jsonl"))


def test_a_trainer_that_never_steps_writes_no_record(tmp_path):
    from mgwfbp_tpu.train.trainer import Trainer

    last = phases.setup_record()
    cfg = _cfg(tmp_path, telemetry=True)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert phases._setup is t._setup is not None
    t.close()
    assert phases._setup is None and phases.setup_record() is last
    stream = read_events(
        os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl"))
    assert events_of(stream, "setup") == []


def test_an_epoch_of_one_step_still_writes_step_1_first(tmp_path):
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = _cfg(tmp_path, telemetry=True, num_batches_per_epoch=1)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.train_epoch(0)
    t.close()
    stream = read_events(
        os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl"))
    order = [e["event"] for e in stream if e["event"] in ("step", "setup")]
    assert order == ["step", "setup"]
    spans = _setup_of(stream)["spans"]
    step_1 = events_of(stream, "step")[0]
    # read in the epoch's drain
    assert spans["setup"][0] + spans["setup"][1] \
        <= sum(step_1["phases"]["drain"]) + EPS


# --------------------------------------------------------------------------
# the recorder alone, on hand-made spans and events
# --------------------------------------------------------------------------


def test_a_name_under_another_parent_is_kept_apart():
    rec = SetupRecorder(0.0)
    with rec.span("init"):
        with rec.span("steps"):
            pass
        with rec.span("model"):
            pass
        with rec.span("model"):  # both calls: one span, durations summed
            pass
    with rec.span("steps"):  # a rebuild before the first step
        with rec.span("solve"):
            pass
    assert {n: s[2] for n, s in rec.spans.items()} == {
        "steps": "init", "model": "init", "init": "setup",
        "setup.steps": "setup", "solve": "setup.steps"}
    assert rec._open == ["setup"]


def test_first_step_parts_from_hand_made_events():
    rec = SetupRecorder(0.0)
    rec.events = [
        # (clock at the event's end, event, seconds, function)
        (10.0, TRACE, 0.5, "init"), (10.6, LOWER, 0.6, "jit(init)"),
        (11.0, COMPILE, 0.4, "jit(init)"),
        # the step, dispatched at 20.0: two kernels traced inside its trace,
        # one of them with a trace nested in its own, and a constant the
        # trace compiled on its way
        (20.3, TRACE, 0.2, "gmm"), (20.8, TRACE, 0.1, "inner"),
        (20.9, TRACE, 0.3, "tgmm"), (21.0, LOWER, 0.05, "jit(iota)"),
        (21.1, COMPILE, 0.05, "jit(iota)"),
        (22.0, TRACE, 2.0, "step"), (22.7, LOWER, 0.7, "jit(step)"),
        (23.0, CACHE_LOAD, 0.25, ""), (23.1, COMPILE, 0.4, "jit(step)"),
        # the reference's program afterwards lowers for longer
        (40.0, TRACE, 1.0, "body"), (43.0, LOWER, 3.0, "jit(body)"),
        (43.5, CACHE_LOAD, 0.45, ""), (43.6, COMPILE, 1.6, "jit(body)"),
    ]
    rec.dispatched(1, 20.0, 3.5)
    rec._read_at = 30.0
    record = rec.finish(lambda t: t)
    spans = record["spans"]
    assert spans["first_step"] == [20.0, 3.5, "setup"]
    assert spans["trace"] == [20.0, 2.0, "first_step"]
    assert spans["lower"] == [22.0, 0.7, "first_step"]
    assert spans["compile"] == [22.7, 0.4, "first_step"]
    assert spans["cache_load"] == [22.75, 0.25, "compile"]
    assert spans["first_result"] == [23.5, 6.5, "setup"]
    assert spans["setup"] == [0.0, 30.0, None]
    counters = record["counters"]
    assert counters["kernel_trace_s"] == pytest.approx(0.5)  # gmm + tgmm
    assert (counters["programs_traced"], counters["programs_lowered"]) \
        == (6, 4)
    # two hits; of the two misses one is too short for the cache to keep
    assert (counters["cache_loads"], counters["programs_compiled"],
            counters["small_compiles"]) == (2, 0, 2)
    assert counters["small_compile_s"] == pytest.approx(0.45)
    assert counters["slow_events"][0] == [
        "jaxpr_to_mlir_module_duration", "jit(body)", 3.0]
    assert phases.self_times(spans)["first_step"] == pytest.approx(0.4)


def test_a_compile_the_cache_would_keep_is_counted():
    rec = SetupRecorder(0.0)
    rec.events = [(5.0, COMPILE, 61.0, "jit(step)"),
                  (5.5, COMPILE, 0.01, "jit(add)")]
    counters = rec.finish(lambda t: t)["counters"]
    assert (counters["programs_compiled"], counters["small_compiles"]) \
        == (1, 1)


def test_the_process_age_is_the_operating_systems():
    age_s = phases._process_age_s()
    with open("/proc/uptime") as f:
        up_s = float(f.read().split()[0])
    assert 0.0 <= age_s <= up_s


# --------------------------------------------------------------------------
# the operator's views
# --------------------------------------------------------------------------

RECORD = {
    "origin_wall": 1790736000.0,
    "spans": {
        "setup": [-30.0, 50.0, None], "before_init": [-30.0, 12.0, "setup"],
        "import": [-29.5, 4.0, "before_init"],
        "backend": [-25.0, 6.0, "before_init"],
        "init": [-18.0, 18.0, "setup"], "data": [-17.0, 9.0, "init"],
        "dataset": [-17.0, 8.5, "data"], "optimizer": [-8.0, 6.0, "init"],
        "first_step": [1.0, 15.0, "setup"], "trace": [1.1, 4.0, "first_step"],
        "lower": [5.1, 0.5, "first_step"], "compile": [5.6, 9.0, "first_step"],
        "cache_load": [5.9, 1.5, "compile"],
        "first_result": [16.0, 4.0, "setup"],
    },
    "counters": {
        "programs_traced": 800, "programs_lowered": 90,
        "programs_compiled": 0, "small_compiles": 80, "cache_loads": 10,
        "small_compile_s": 3.25, "kernel_trace_s": 0.22,
        "slow_events": [["backend_compile_duration", "jit(step)", 9.0]],
    },
}


def test_self_times_on_a_hand_made_record():
    own = phases.self_times(RECORD["spans"])
    assert own["setup"] == pytest.approx(50.0 - 12.0 - 18.0 - 15.0 - 4.0)
    assert own["before_init"] == pytest.approx(2.0)
    assert own["init"] == pytest.approx(3.0)
    assert own["data"] == pytest.approx(0.5)
    assert own["first_step"] == pytest.approx(1.5)
    assert own["compile"] == pytest.approx(7.5)
    assert own["dataset"] == 8.5


def test_the_log_line_names_the_parts_in_order():
    line = phases.setup_line(RECORD)
    assert line.startswith(
        "set-up: 50.0 s to the first result: before the constructor 12.0 "
        "(import 4.0, backend 6.0), constructor 18.0 (data 9.0, optimizer "
        "6.0), first step 15.0 (trace 4.0, lower 0.5, compile 9.0 of which "
        "cache load 1.5), first result 4.0; 0 program(s) compiled, 10 loaded")
    rebuilt = {**RECORD, "spans": {
        "steps": [3.0, 0.5, None], "first_step": [4.0, 2.0, None],
        "trace": [4.0, 1.0, "first_step"]}}
    assert phases.setup_line(rebuilt).startswith(
        "set-up: the step rebuilt: steps rebuilt in 0.5, first step 2.0 "
        "(trace 1.0); ")


def test_the_report_prints_the_table_with_self_times():
    import telemetry_report

    records = [
        {"event": "header", "schema_version": 2, "wall": 0.0},
        {"event": "setup", "wall": 1.0, **RECORD},
        {"event": "step", "step": 1, "epoch": 0, "start_s": 1.0,
         "dur_s": 15.0},
    ]
    report = telemetry_report.format_report(records)
    rows = {line.split()[0]: line for line in report.splitlines()
            if line.startswith("  ") and len(line.split()) == 4}
    #            name        start     seconds  self
    assert rows["setup"].split() == ["setup", "-30.000", "50.000", "1.000"]
    assert rows["init"].split() == ["init", "-18.000", "18.000", "3.000"]
    assert rows["compile"].split() == ["compile", "5.600", "9.000", "7.500"]
    assert rows["dataset"].startswith("        dataset ")  # under init, data
    lines = report.splitlines()
    assert lines.index(rows["before_init"]) < lines.index(rows["import"]) \
        < lines.index(rows["init"]) < lines.index(rows["first_step"])
    assert "0 compiled, 10 loaded from the compile cache, 80 too small" \
        in report
    assert "9.000 s  backend_compile_duration  jit(step)" in report


# --------------------------------------------------------------------------
# the real system: train_cli, where the import and the backend are its own
# --------------------------------------------------------------------------


def test_under_train_cli_the_origin_is_the_process_start(tmp_path):
    import time

    launched = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "mgwfbp_tpu.train_cli", "--dnn", "lenet",
         "--synthetic", "--telemetry", "--no-profile-backward",
         "--batch-size", "8", "--epochs", "1", "--num-batches-per-epoch",
         "3", "--logdir", str(tmp_path)],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "HOME": str(tmp_path),
             "MGWFBP_HOST_DEVICES": "2", "XLA_FLAGS": ""},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    (tag,) = [d for d in os.listdir(tmp_path)
              if os.path.exists(os.path.join(tmp_path, d, "telemetry.jsonl"))]
    stream = read_events(os.path.join(str(tmp_path), tag, "telemetry.jsonl"))
    record = _setup_of(stream)
    spans = record["spans"]
    # the OS's start of the process, not the package's import
    assert launched - 1.0 <= record["origin_wall"] <= launched + 5.0
    assert spans["before_init"][0] == spans["setup"][0]
    assert spans["import"][2] == spans["backend"][2] == "before_init"
    assert spans["import"][0] >= spans["setup"][0]
    assert spans["import"][1] > 0.0 and spans["backend"][1] > 0.0
    assert spans["import"][0] + spans["import"][1] \
        <= spans["backend"][0] + EPS
    assert spans["before_init"][0] + spans["before_init"][1] \
        == pytest.approx(spans["init"][0], abs=1e-3)
    header_wall = stream[0]["wall"]
    assert record["origin_wall"] + (-spans["setup"][0]) \
        == pytest.approx(header_wall, abs=0.05)
    assert "set-up: " in proc.stderr + proc.stdout
