"""The reduction from a trace to busy, idle, exposed collective time and
per-step numbers: on hand-made events whose answers can be worked out on
paper, and on a small trace recorded on the chip (data/, see its README)."""

import json
import os

import pytest

from bench_paths import DATA, load

tr = load("trace_reduce.py")

AR = "%all-reduce-start.7 = (f32[8]) all-reduce-start(f32[8] %x), channel_id=1"
AR_DONE = "%all-reduce-done.7 = f32[8] all-reduce-done((f32[8]) %all-reduce-start.7)"
PSUM = ("%psum.414 = f32[4096000]{0:T(1024)S(1)} all-reduce(f32[4096000]{0:T(1024)S(1)} "
        "%reshape.198), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_33.36")
FUSION = "%fusion.12 = bf16[8,8] fusion(bf16[8,8] %p), kind=kOutput"
COPY = "%copy-start.1 = (bf16[8]) copy-start(bf16[8] %w)"


def _trace(ops, asyncs=(), modules=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": list(modules)},
            {"name": "XLA Ops", "events": list(ops)},
            {"name": "Async XLA Ops", "events": list(asyncs)},
        ]},
        {"name": "/host:metadata", "lines": []},
    ]}


def test_op_names_drop_operands_and_numeric_suffixes():
    assert tr.op_name(AR) == "all-reduce-start"
    assert tr.op_name(FUSION) == "fusion"
    assert tr.op_name("convolution_tanh_fusion.3") == "convolution_tanh_fusion"
    assert tr.collective_phase(AR) == "-start" and tr.collective_phase(AR_DONE) == "-done"
    assert tr.collective_phase(PSUM) == "" and tr.collective_phase("all-gather.3") == ""
    assert not tr.is_collective(FUSION) and not tr.is_collective(COPY)
    assert not tr.is_collective("psum.4")  # a name alone says nothing


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert tr.total([(0, 4), (5, 7)]) == 6
    assert tr.overlap([(0, 4), (5, 7)], [(3, 6), (6.5, 10)]) == 1 + 1 + 0.5
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.clip([(0, 4), (5, 7)], 3, 6) == [(3, 4), (5, 6)]


def test_busy_idle_exposed_and_per_step_numbers():
    # two steps of 100 ns in a window of 300 ns. Each step: compute 0-60, a
    # collective in flight 40-90 (async span), the core waiting for it 80-90
    ops, asyncs, modules = [], [], []
    for base in (0.0, 150.0):
        modules.append(["jit_step(1)", base, 100.0])
        ops.append([FUSION, base, 60.0])
        ops.append([AR, base + 40.0, 1.0])
        ops.append([AR_DONE, base + 80.0, 10.0])
        asyncs.append([AR, base + 40.0, 50.0])
        asyncs.append([COPY, base + 5.0, 10.0])
    modules.append(["jit_other(2)", 120.0, 5.0])
    trace = _trace(ops, asyncs, modules)
    assert tr.step_starts(trace) == [0.0, 150.0]
    dev, = tr.reduce_trace(trace, window=(0.0, 300.0))["devices"]
    assert dev["steps"] == 2 and dev["step_module"] == "jit_step(1)"
    assert dev["window_ns"] == 300.0
    assert dev["busy_ns"] == 2 * 90.0  # 0-90: compute, then the collective alone
    assert dev["compute_ns"] == 2 * 60.0  # the -start is a collective, not compute
    assert dev["collective_ns"] == 2 * 50.0
    assert dev["exposed_collective_ns"] == 2 * 30.0  # 60-90 of each step
    assert dev["collective_calls"] == 2  # starts, never dones
    assert dev["top_ops"][0] == ("fusion", 120.0)
    assert 1.0 - dev["busy_ns"] / dev["window_ns"] == pytest.approx(0.4)


def test_window_leaves_out_what_starts_outside_it():
    ops = [[FUSION, 0.0, 10.0], [FUSION, 50.0, 10.0], [FUSION, 95.0, 10.0]]
    dev, = tr.reduce_trace(_trace(ops), window=(40.0, 100.0))["devices"]
    assert dev["busy_ns"] == 10.0 + 5.0  # the last op is cut at the window's end
    whole, = tr.reduce_trace(_trace(ops))["devices"]
    assert whole["window_ns"] == 105.0 and whole["busy_ns"] == 30.0


def test_idle_gaps_go_to_what_the_host_was_doing():
    busy = [(10.0, 20.0), (50.0, 60.0)]
    host = {"input_wait": [(0.0, 8.0), (20.0, 45.0)], "dispatch": [(45.0, 50.0)]}
    out = dict(tr.attribute_gaps(busy, (0.0, 70.0), host))
    assert out == {"input_wait": 33.0, "dispatch": 5.0, "other": 12.0}


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "recorded_trace.json")
    with open(path) as f:
        return json.load(f)


def _sweep_busy(events, lo, hi):
    """Busy time by a sweep over the endpoints: another algorithm than
    trace_reduce.union, for the same quantity."""
    points = []
    for _, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(points):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_trace_reduces_to_its_recorded_numbers(recorded):
    trace, want = recorded["trace"], recorded["expected"]
    window = tuple(recorded["window"])
    reduced = tr.reduce_trace(trace, window=window)
    assert [d["id"] for d in reduced["devices"]] == want["device_ids"]
    for dev, plane in zip(reduced["devices"], (
            p for p in trace["planes"] if tr.DEVICE_PLANE.match(p["name"]))):
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        inside = [e for e in lines["XLA Ops"] + lines.get("Async XLA Ops", [])
                  if window[0] <= e[1] < window[1]]
        assert dev["busy_ns"] == pytest.approx(_sweep_busy(inside, *window))
        assert 0 < dev["busy_ns"] <= dev["window_ns"]
        assert dev["steps"] == want["steps"]
        assert dev["exposed_collective_ns"] <= dev["collective_ns"] + 1e-6
        assert dev["compute_ns"] <= dev["busy_ns"] + 1e-6
    first = reduced["devices"][0]
    assert first["collective_calls"] == want["collective_calls"]
    assert first["busy_ns"] == pytest.approx(want["busy_ns"])
    assert first["exposed_collective_ns"] == pytest.approx(
        want["exposed_collective_ns"])
    assert len(tr.step_starts(trace)) == want["steps"]
