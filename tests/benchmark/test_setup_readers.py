"""The per-layer readers of the program's `setup` record (`setup_spans.py` and
the ten files under `layer_metrics/` that go through it), on a hand-made
record (no run, no clock), their None case where the program keeps no such
record, their entries in BENCHMARK.json, and the sums they owe each other in
one rehearsal run on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT, load

RECORD = {
    "origin_wall": 1790736000.0,
    "spans": {
        "setup": [-31.0, 106.0, None],
        "before_init": [-31.0, 21.25, "setup"],
        "import": [-30.5, 4.0, "before_init"],
        "init": [-9.75, 53.25, "setup"],
        "mesh": [-9.75, 0.25, "init"], "model": [-9.5, 0.5, "init"],
        "data": [-9.0, 27.0, "init"], "dataset": [-9.0, 26.5, "data"],
        "optimizer": [18.0, 6.0, "init"], "reducer": [24.0, 16.0, "init"],
        "profile_backward": [24.0, 15.0, "reducer"],
        "steps": [40.0, 0.125, "init"], "sinks": [40.5, 0.125, "init"],
        "first_step": [44.0, 19.0, "setup"],
        "trace": [44.25, 4.75, "first_step"],
        "lower": [49.0, 0.5, "first_step"],
        "compile": [49.5, 11.75, "first_step"],
        "cache_load": [50.0, 1.5, "compile"],
        "first_result": [63.0, 12.0, "setup"],
    },
    "counters": {"programs_traced": 812, "programs_lowered": 97,
                 "programs_compiled": 3, "small_compiles": 80,
                 "cache_loads": 14, "small_compile_s": 3.5,
                 "kernel_trace_s": 0.25, "slow_events": []},
}
SETUP_S = 105.75
EXPECTED = {
    "setup_before_init_s": 21.25,
    "init_data_s": 27.0,
    "init_model_s": 0.5 + 6.0,
    "init_schedule_s": 16.0,
    "init_self_s": 53.25 - (0.25 + 0.5 + 27.0 + 6.0 + 16.0 + 0.125 + 0.125),
    "first_step_trace_s": 4.75,
    "first_step_lower_s": 0.5,
    "first_step_compile_s": 11.75,
    "setup_after_first_step_s": SETUP_S - (21.25 + 53.25 + 19.0),
    "setup_compiles": 3,
}
NAMES = sorted(EXPECTED)


def reader(name):
    return load(f"layer_metrics/{name}.py")


@pytest.fixture
def program_record(monkeypatch):
    """The program's `phases.setup_record()` answers with what is set here."""
    from mgwfbp_tpu.telemetry import phases

    held = {"record": RECORD}
    monkeypatch.setattr(phases, "setup_record", lambda: held["record"])
    return held


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_hand_made_record(name, program_record):
    assert reader(name).read({"setup_s": SETUP_S}) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_a_program_without_the_record(
        name, monkeypatch):
    """Any commit before PR 36 (no `setup_record`), a process that has
    finished no set-up, and one whose last record is a rebuild's: nothing to
    read, nothing raised."""
    from mgwfbp_tpu.telemetry import phases

    run = {"setup_s": SETUP_S}
    monkeypatch.setattr(phases, "setup_record", lambda: None)
    assert reader(name).read(run) is None
    rebuilt = {**RECORD, "spans": {"steps": [3.0, 0.5, None],
                                   "first_step": [4.0, 2.0, None]}}
    monkeypatch.setattr(phases, "setup_record", lambda: rebuilt)
    assert reader(name).read(run) is None
    monkeypatch.delattr(phases, "setup_record")
    assert reader(name).read(run) is None


def test_a_span_that_was_never_entered_reads_zero(program_record):
    spans = {n: s for n, s in RECORD["spans"].items()
             if n not in ("reducer", "profile_backward", "cache_load")}
    program_record["record"] = {**RECORD, "spans": spans}
    assert reader("init_schedule_s").read({}) == 0.0
    assert reader("init_self_s").read({}) == pytest.approx(
        EXPECTED["init_self_s"] + 16.0)


def test_the_parts_add_up_to_what_they_are_parts_of(program_record):
    run = {"setup_s": SETUP_S}
    read = {name: reader(name).read(run) for name in NAMES}
    spans = RECORD["spans"]
    # mesh, steps and sinks are the constructor's too, under no metric
    assert read["init_data_s"] + read["init_model_s"] \
        + read["init_schedule_s"] + read["init_self_s"] == pytest.approx(
            spans["init"][1] - 0.5)
    assert read["setup_before_init_s"] + spans["init"][1] \
        + spans["first_step"][1] + read["setup_after_first_step_s"] \
        == pytest.approx(SETUP_S)


def test_every_new_metric_is_listed_by_name_with_a_file_and_no_workloads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        entry = listed[name]
        assert (entry["layer"], entry["moves"], entry["better"]) \
            == ("entry", "setup_s", "lower")
        assert "workloads" not in entry
        assert entry["unit"] == ("count" if name == "setup_compiles" else "s")
        assert entry["source"] == (
            "program_counter" if name == "setup_compiles" else "program_span")
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    # they time the same layer as the two that were there, from inside
    assert listed["init_s"]["layer"] == listed["first_step_s"]["layer"] \
        == "entry"


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal run in this process: the result line's metrics
    with their values, and the `run` the harness's own readers saw."""
    run_module = load("run.py")
    spec = run_module.load_cell(None)
    seen: dict = {}
    real = run_module.read_metrics

    def read_metrics(spec, run, kind):
        seen.update(run)
        return real(spec, run, kind)

    run_module.read_metrics = read_metrics
    out = tmp_path_factory.mktemp("rehearsal")
    environ = dict(os.environ)  # `apply_env` sets the cell's for good
    try:
        result, _ = run_module.run_once(
            spec, 7, 0.5, True, str(out / "out"), {},
            run_module.CompileCounter(), rehearsal=True)
    finally:
        os.environ.clear()
        os.environ.update(environ)
    assert result["correct"]
    return {k: v["value"] for k, v in result["metrics"].items()}, seen


def test_rehearsal_reports_all_ten_and_the_constructors_parts_add_up(
        rehearsal):
    metrics, run = rehearsal
    assert set(NAMES) <= set(metrics)
    assert metrics["init_data_s"] + metrics["init_model_s"] \
        + metrics["init_schedule_s"] + metrics["init_self_s"] \
        == pytest.approx(run["init_s"], rel=0.02)
    assert metrics["init_s"] == run["init_s"]
    assert all(metrics[n] >= 0.0 for n in NAMES
               if n != "setup_after_first_step_s")


def test_rehearsal_first_steps_parts_fit_inside_the_first_step(rehearsal):
    metrics, run = rehearsal
    parts = [metrics[n] for n in ("first_step_trace_s", "first_step_lower_s",
                                  "first_step_compile_s")]
    assert all(p > 0.0 for p in parts)
    assert sum(parts) <= metrics["first_step_s"] == run["first_step_s"]


def test_rehearsal_lists_the_ten_among_a_traced_runs_metrics(tmp_path):
    """`run.py --rehearse --trace 1` as the driver starts it, one process
    from its start: all ten are among `metric_names`."""
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--rehearse",
         "--trace", "1", "--seconds", "1", "--out", str(tmp_path / "out")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "HOME": str(tmp_path)},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] and set(NAMES) <= set(line["metric_names"])
