"""The per-layer readers of the traced epoch's split by scope
(`scope_spans.py` and the fourteen files under `layer_metrics/` that go through
it), on a hand-made compiled text and trace (`data/scope_step_hlo.txt`,
`data/scope_trace.json`: no run, no clock, no chip), the sums they owe each
other, their None cases, and their entries in BENCHMARK.json."""

import json
import os

import pytest

from bench_paths import BENCH, DATA, ROOT, load

profiling = pytest.importorskip("mgwfbp_tpu.profiling")

# the scopes of a model with every layer of PERF.md's map, then the step's own
SCOPES = {
    "attn_full": "attention", "moe_experts": "experts",
    "ssm_scan": "state space", "gdn_delta": "linear attention",
    "mhc_mix": "residual streams", "mlp": "mlp", "lm_head": "head and loss",
    "loss": "head and loss", "optimizer": "update",
    "bad_step_guard": "update", "metrics_reduce": "update",
}
# milliseconds a step, mean over the two chips (data/scope_trace.json's
# `about` says how the events were laid out)
EXPECTED = {
    "attention_device_ms": 3 + 5,
    "experts_device_ms": 2 + 4,
    "state_space_device_ms": 1.5 + 1.5,  # the loop's body; not the loop
    "linear_attention_device_ms": 2.5,
    "streams_device_ms": 1,
    "mlp_device_ms": 2,
    "head_loss_device_ms": 1 + 0.5,
    "unscoped_device_ms": 0.75 + 1.25 + 0.5,
    "update_device_ms": 1 + 0.25 + 0.25,
    "device_forward_ms": 3 + 2 + 3 + 1 + 1 + 0.75,
    "device_backward_ms": 5 + 4 + 2.5 + 2 + 0.5 + 1.25,
    # start, packing, the synchronous all-reduce, the wait (4 ms on chip 0,
    # 8 on chip 1), the metrics' own
    "exchange_device_ms": 0.5 + 0.5 + 2 + (4 + 8) / 2 + 0.25,
    "exchange_wait_ms": 2 + (4 + 8) / 2 + 0.25,
    "exchange_calls": 3,
}
NAMES = sorted(EXPECTED)
LAYERS = ("attention_device_ms", "experts_device_ms", "state_space_device_ms",
          "linear_attention_device_ms", "streams_device_ms", "mlp_device_ms",
          "head_loss_device_ms")


def reader(name):
    return load(f"layer_metrics/{name}.py")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "scope_trace.json")) as f:
        trace = json.load(f)
    with open(os.path.join(DATA, "scope_step_hlo.txt")) as f:
        text = f.read()
    return trace, text


@pytest.fixture
def run(recorded, tmp_path, monkeypatch):
    """A traced run's `run` as the readers see it, the program's map and the
    trace file where `scope_spans` looks for them."""
    if not hasattr(profiling, "StepMap"):
        pytest.skip("a program from before PR 49 keeps no map of its step")
    reader(NAMES[0])  # the benchmark's directory on the path
    import scope_spans
    import trace_reduce

    trace, text = recorded
    step_map = profiling.StepMap(
        profiling.hlo_instruction_map(text), SCOPES,
        logdir=str(tmp_path / "logs"), hlo_bytes=len(text), build_s=0.25)
    monkeypatch.setattr(profiling, "step_map", lambda: step_map)
    path = tmp_path / "trace" / "plugins" / "profile" / "2026_10_05" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    loaded = []

    def load_xplane(asked):
        loaded.append(asked)
        return {"planes": trace["planes"]}

    monkeypatch.setattr(trace_reduce, "load_xplane", load_xplane)
    monkeypatch.setattr(scope_spans, "_read", (None, None))
    return {"traced": {"window": tuple(trace["window"]),
                       "steps": trace["steps"]},
            "loaded": loaded, "path": str(path)}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_the_hand_made_trace(run, name):
    assert reader(name).read(run) == pytest.approx(EXPECTED[name])


def test_the_parts_add_up_to_the_events_total(run, recorded):
    got = {name: reader(name).read(run) for name in NAMES}
    window = run["traced"]["window"]
    total = sum(
        dur for plane in recorded[0]["planes"]
        if plane["name"].startswith("/device:")
        for line in plane["lines"] if line["name"] == "XLA Ops"
        for name, start, dur in line["events"]
        if window[0] <= start < window[1] and not name.startswith("while")
    ) / 1e6 / (2 * run["traced"]["steps"])
    layers = sum(got[name] for name in LAYERS)
    assert layers + got["unscoped_device_ms"] + got["update_device_ms"] \
        + got["exchange_device_ms"] == pytest.approx(total)
    import scope_spans

    assert scope_spans.split(run)["total_ms"] == pytest.approx(total)
    no_metadata = scope_spans.sum_ms(run, "no_metadata")
    assert no_metadata == pytest.approx(0.5)
    assert got["device_forward_ms"] + got["device_backward_ms"] \
        == pytest.approx(layers + got["unscoped_device_ms"] - no_metadata)
    assert got["device_forward_ms"] + got["device_backward_ms"] \
        + got["update_device_ms"] + got["exchange_device_ms"] + no_metadata \
        == pytest.approx(total)
    # by merge group: the start and the wait; the packing and the all-reduce
    assert scope_spans.split(run)["groups"] == pytest.approx(
        [0.5 + 6, 0.5 + 2])


def test_the_trace_is_read_once_and_the_split_printed_once(run, capsys):
    for name in NAMES:
        reader(name).read(run)
    assert run["loaded"] == [run["path"]]
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("[scopes] ")]
    assert len(lines) == len(set(lines)) > 25
    assert "built in 0.250 s" in lines[0] and "2 chip(s), 2 step(s)" in lines[0]
    said = "\n".join(lines)
    # every scope with its layer, forward and backward; the longest
    # instructions, the mean wait of the two chips first
    assert "attn_full        attention      3.000      5.000      8.000" in said
    assert "(no metadata)" in said and "bad_step_guard" in said
    assert "6.000 async-collective-done.18 [exchange]" in said
    assert "5.000 splash_mha_dkv.2 [attn_full]" in said


def test_a_model_without_the_layer_reads_nought(run, monkeypatch, recorded):
    """An image model declares no scope: its whole step under autodiff is
    `(model, no scope)`, every layer's metric a true 0."""
    bare = profiling.StepMap(
        profiling.hlo_instruction_map(recorded[1]),
        {k: v for k, v in SCOPES.items() if v == "update"},
        logdir=profiling.step_map().logdir)
    monkeypatch.setattr(profiling, "step_map", lambda: bare)
    for name in LAYERS:
        nought = reader(name).read(run)
        assert nought == 0.0 and isinstance(nought, float)
    assert reader("unscoped_device_ms").read(run) == pytest.approx(24 + 2.5)
    assert reader("update_device_ms").read(run) == pytest.approx(1.5)


def test_one_chip_without_a_collective_reads_nought(run, recorded,
                                                    monkeypatch):
    import trace_reduce

    plane = recorded[0]["planes"][0]
    kept = {"name": plane["name"], "lines": [
        {"name": line["name"], "events": [
            e for e in line["events"]
            if not any(word in e[0] for word in ("collective", "reduce",
                                                 "psum", "fusion.20"))]}
        for line in plane["lines"]]}
    monkeypatch.setattr(
        trace_reduce, "load_xplane", lambda path: {"planes": [kept]})
    for name in ("exchange_device_ms", "exchange_wait_ms", "exchange_calls"):
        assert reader(name).read(run) == 0.0
    assert reader("attention_device_ms").read(run) == pytest.approx(8)


@pytest.mark.parametrize("case", [
    "no traced epoch", "no window", "no record", "no step_map",
    "no device plane", "no trace file"])
def test_none_where_there_is_nothing_to_read(run, monkeypatch, case):
    """An untraced run, a trace `align` found no device plane in (the CPU
    rehearsal), a process that dispatched no step, any commit before PR 49
    (no `step_map`), a trace file without a device plane, no trace file."""
    import trace_reduce

    if case == "no traced epoch":
        run = {"traced": None}
    elif case == "no window":
        run = {"traced": {"steps": 2, "reduced": {"devices": []}}}
    elif case == "no record":
        monkeypatch.setattr(profiling, "step_map", lambda: None)
    elif case == "no step_map":
        monkeypatch.delattr(profiling, "step_map")
    elif case == "no device plane":
        monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: {
            "planes": [{"name": "/host:CPU", "lines": [
                {"name": "python", "events": [["fusion.1", 10e6, 1e6]]}]}]})
    else:
        os.remove(run["path"])
    for name in NAMES:
        assert reader(name).read(run) is None


def test_every_new_entry_resolves_and_lists_only_accepted_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == 11  # no cell, no configuration: entries alone
    listed = [m for m in bench["per_layer"]][-len(NAMES):]
    assert sorted(m["name"] for m in listed) == NAMES
    for entry in listed:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", entry["name"] + ".py")), entry["name"]
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "samples_per_s"
        assert entry["better"] == "lower"
        assert entry["unit"] == (
            "count" if entry["name"] == "exchange_calls" else "ms")
        assert set(entry.get("workloads", cells)) <= set(cells)
        assert set(entry) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    by_name = {m["name"]: m for m in listed}
    assert by_name["linear_attention_device_ms"]["workloads"] == [
        "qwen3next-plain-1chip"]
    assert by_name["streams_device_ms"]["workloads"] == ["xing4-plain-1chip"]
    assert by_name["mlp_device_ms"]["workloads"] == [
        "granite4h-plain-1chip", "laguna-xs2-plain-1chip",
        "phi4flash-plain-1chip", "xing4-plain-1chip"]
    # the others are read in every cell: a model without the layer, a step
    # without a collective read 0
    assert not [n for n, m in by_name.items() if "workloads" in m
                and n not in ("linear_attention_device_ms",
                              "streams_device_ms", "mlp_device_ms")]
    layers = {m["name"]: m["layer"] for m in listed}
    assert layers["attention_device_ms"] == "attention"
    assert layers["state_space_device_ms"] == "state space"
    assert layers["head_loss_device_ms"] == "head and loss"
    assert {layers[n] for n in ("unscoped_device_ms", "update_device_ms",
                                "device_forward_ms", "device_backward_ms")
            } == {"step"}
    assert {layers[n] for n in NAMES if n.startswith("exchange_")} \
        == {"exchange"}
