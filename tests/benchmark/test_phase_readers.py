"""The per-layer readers of the train loop's phase spans, on hand-made `step`
records and a hand-made busy list (no run, no clock), their None cases, and
the rehearsal's list of the metrics a traced run reports."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import ROOT, load

MS = 1e-3
# one iteration, in loop order: (name, milliseconds); None marks the dispatch
WAIT, FIRST_WAIT, PLACE, DISPATCH = 5, 105, 10, 4
GUARD, HEALTH, TAIL, LOG = 1, 40, 2, 8
RESTART, DRAIN, SNAPSHOT = 20, 30, 7
GAP_BEFORE_DISPATCH, GAP_AFTER = 1, 2
EPOCHS, STEPS = 3, 4


def make_steps(with_phases=True):
    """Three epochs of four steps laid end to end on one clock; a log pull on
    every second step; 1 ms before each dispatch and 2 ms at each iteration's
    end that no span covers."""
    steps, t, n = [], 50.0, 0
    for epoch in range(1, EPOCHS + 1):
        for i in range(STEPS):
            n += 1
            phases = {}

            def span(name, ms):
                nonlocal t
                phases[name] = [t, ms * MS]
                t += ms * MS

            if i == 0:
                span("restart", RESTART)
            span("wait", FIRST_WAIT if i == 0 else WAIT)
            span("place", PLACE)
            t += GAP_BEFORE_DISPATCH * MS
            start_s = t
            t += DISPATCH * MS
            span("guard", GUARD)
            span("health", HEALTH)
            span("tail", TAIL)
            if n % 2 == 0:
                span("log", LOG)
            t += GAP_AFTER * MS
            if i == STEPS - 1:
                span("drain", DRAIN)
                span("snapshot", SNAPSHOT)
            step = {"event": "step", "step": n, "epoch": epoch,
                    "start_s": start_s, "dur_s": DISPATCH * MS}
            if with_phases:
                step.update(phases=phases, lowered=2 if n == 7 else 0)
                if i > 0:  # an epoch's first `next` builds the pool
                    step["ready"] = n % 3
            steps.append(step)
    return steps


TEL_OFFSET, SHIFT_NS = 10.0, 5e9


def on_trace(t_s):
    return (t_s + TEL_OFFSET) * 1e9 + SHIFT_NS


def make_run(with_phases=True, devices=True):
    """The window's steps, and epoch 2 traced: chip 0 never idles; chip 1
    idles through every `guard` span and the second half of every `health`
    span of the traced steps."""
    steps = make_steps(with_phases)
    run = {"window_steps": steps, "tel_offset": TEL_OFFSET, "traced": None}
    traced_steps = [e for e in steps if e["epoch"] == 2]
    first = traced_steps[0]["phases"]["restart"][0] if with_phases \
        else traced_steps[0]["start_s"] - 0.2
    window = (on_trace(first), on_trace(traced_steps[-1]["start_s"] + 0.2))
    idle = []
    for e in traced_steps:
        if not with_phases:
            break
        g0, gd = e["phases"]["guard"]
        h0, hd = e["phases"]["health"]
        idle.append((on_trace(g0), on_trace(g0 + gd)))
        idle.append((on_trace(h0 + hd / 2), on_trace(h0 + hd)))
    busy, cursor = [], window[0]
    for lo, hi in idle:
        busy.append((cursor, lo))
        cursor = hi
    busy.append((cursor, window[1]))
    span_ns = window[1] - window[0]
    idle_ns = sum(hi - lo for lo, hi in idle)
    if devices:
        run["traced"] = {
            "shift_ns": SHIFT_NS, "dispatches": traced_steps,
            "steps": len(traced_steps), "window": window,
            "reduced": {"devices": [
                {"id": 0, "window_ns": span_ns, "busy_ns": span_ns,
                 "busy": [window], "window": window},
                {"id": 1, "window_ns": span_ns, "busy_ns": span_ns - idle_ns,
                 "busy": busy, "window": window},
            ]},
        }
    return run


N = EPOCHS * STEPS
EXPECTED = {
    "loader_wait_ms": ((N - EPOCHS) * WAIT + EPOCHS * FIRST_WAIT) / N,
    "placement_ms": PLACE,
    "guard_read_ms": GUARD,
    "health_drain_ms": HEALTH,
    "loop_tail_ms": (N * TAIL + (N // 2) * LOG) / N,
    "loop_unaccounted_ms": GAP_BEFORE_DISPATCH + GAP_AFTER,
    "idle_guard_ms": GUARD,
    "idle_health_ms": HEALTH / 2,
    "idle_tail_ms": 0.0,
    "boundary_restart_ms": RESTART + FIRST_WAIT - WAIT,
    "boundary_drain_ms": DRAIN + SNAPSHOT,
    "window_lowerings": 2,
    "pool_ready": sum(
        n % 3 for n in range(1, N + 1) if n % STEPS != 1) / (N - EPOCHS),
}
SPANS_AND_COUNTERS = sorted(n for n in EXPECTED if not n.startswith("idle_"))


def reader(name):
    return load(f"layer_metrics/{name}.py")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_step_records(name):
    assert reader(name).read(make_run()) == pytest.approx(
        EXPECTED[name], abs=1e-6)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_records_without_spans(name):
    """The parent's records (dispatch only): nothing to read, nothing raised."""
    assert reader(name).read(make_run(with_phases=False)) is None


@pytest.mark.parametrize("name", ["idle_guard_ms", "idle_health_ms",
                                  "idle_tail_ms"])
def test_idle_readers_need_a_device_plane(name):
    run = make_run(devices=False)
    assert reader(name).read(run) is None  # untraced
    run["traced"] = {"reduced": {"devices": []}, "steps": 4}  # the rehearsal
    assert reader(name).read(run) is None


def test_boundary_readers_with_one_epoch_in_the_window():
    """One epoch still starts and ends in the window: its own restart and
    drain are read; with no span of the kind there is nothing."""
    run = make_run()
    run["window_steps"] = [e for e in run["window_steps"] if e["epoch"] == 2]
    assert reader("boundary_restart_ms").read(run) == pytest.approx(
        EXPECTED["boundary_restart_ms"])
    assert reader("boundary_drain_ms").read(run) == pytest.approx(
        EXPECTED["boundary_drain_ms"])
    middle = {**run, "window_steps": run["window_steps"][1:-1]}
    assert reader("boundary_restart_ms").read(middle) is None
    assert reader("boundary_drain_ms").read(middle) is None
    assert reader("loop_unaccounted_ms").read(
        {**run, "window_steps": run["window_steps"][:1]}) is None


def test_idle_is_read_on_the_chip_that_idled_most():
    run = make_run()
    run["traced"]["reduced"]["devices"].reverse()
    assert reader("idle_health_ms").read(run) == pytest.approx(HEALTH / 2)
    assert reader("idle_guard_ms").read(run) + reader("idle_health_ms").read(
        run) + reader("idle_tail_ms").read(run) == pytest.approx(
            (run["traced"]["reduced"]["devices"][0]["window_ns"]
             - run["traced"]["reduced"]["devices"][0]["busy_ns"]) / 1e6 / 4)


def test_every_new_metric_is_listed_without_a_workloads_key():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        assert listed[name]["moves"] == "samples_per_s"
        assert "workloads" not in listed[name]
    assert [w["name"] for w in bench["workloads"]][-1] == "vgg16-plain-1chip"


def test_rehearsal_lists_the_span_and_counter_metrics(tmp_path):
    """`run.py --rehearse --trace 1` on the CPU: the ten metrics read from
    spans and counters are among those a traced run reports; the three idle
    times need a device plane, which the CPU's trace lacks."""
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--rehearse",
         "--trace", "1", "--seconds", "1", "--out", str(tmp_path / "out")],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "HOME": str(tmp_path)},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(SPANS_AND_COUNTERS) <= set(line["metric_names"])
    assert not {n for n in line["metric_names"] if n.startswith("idle_")}
