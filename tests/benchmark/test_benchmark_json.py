"""BENCHMARK.json against the harness: every name resolves to a file, names
and units keep to the contract's characters, one cell asks for four chips."""

import json
import os
import re

import pytest

from bench_paths import BENCH, ROOT, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_command(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lengths(bench):
    named = bench["configs"] + bench["workloads"] + bench["end_to_end"] \
        + bench["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for cell in bench["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert len(config["source"]) <= 200 and len(config["why"]) <= 200
        assert all(NAME.match(k) for k in config["reduced"])
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in bench[kind]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_every_name_resolves_to_a_file(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        entry = configs[cell["config"]]
        assert entry["file"].startswith("benchmarks/configs/")
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == entry["reduced"]
        assert os.path.isfile(os.path.join(
            BENCH, "references", config["reference"] + ".py"))
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", cell["traffic"] + ".json"))
    assert {c["config"] for c in bench["workloads"]} == set(configs)
    for kind, subdir in (("end_to_end", "end_to_end"),
                         ("per_layer", "layer_metrics")):
        for metric in bench[kind]:
            assert os.path.isfile(os.path.join(
                BENCH, subdir, metric["name"] + ".py")), metric["name"]


def test_metrics_cover_the_cells(bench):
    cells = {c["name"] for c in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for metric in bench["end_to_end"]:
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
        assert set(metric.get("workloads", cells)) <= cells
    for metric in bench["per_layer"]:
        assert metric["moves"] in e2e
        assert "bound" not in metric and 1 <= len(metric["layer"]) <= 200
        moved = set(e2e[metric["moves"]].get("workloads", cells))
        assert set(metric.get("workloads", moved)) <= moved
    for cell in cells:
        reported = [m for m in bench["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2


def test_exactly_one_cell_asks_for_four_chips(bench):
    chips = [c["chips"] for c in bench["workloads"]]
    assert set(chips) <= {1, 4} and chips.count(4) == 1
    assert len({(c["config"], c["traffic"]) for c in bench["workloads"]}) \
        == len(chips)


def test_peaks_table_names_the_chip_and_refuses_others():
    peaks = load("peaks.py")
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "ici_bits_per_s": 1600e9}
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup("cpu")


def test_what_each_cell_reports(bench):
    """The rate, the memory and the set-up in every cell; the interval's tail
    and the boundary's cost only where the window holds hundreds of steps and
    several epochs; the exchange only across chips."""
    run = load("run.py")
    per_layer = {}
    for cell in bench["workloads"]:
        spec = run.load_cell(cell["name"])
        assert {m["name"] for m in run.cell_metrics(spec, "end_to_end")} == {
            "samples_per_s", "peak_hbm_gib", "setup_s"}
        per_layer[cell["name"]] = {
            m["name"] for m in run.cell_metrics(spec, "per_layer")}
    plain, augment, four = (per_layer[n] for n in (
        "resnet50-plain-1chip", "resnet50-augment-1chip", "vgg16-plain-4chip"))
    assert plain - augment == {"step_ms_p95", "boundary_ms"}
    assert four - plain == {"exposed_comm_ms", "collective_calls"}
    rehearsal = run.load_cell(None)
    assert rehearsal["cell"]["chips"] == 4
    assert {m["name"] for m in run.cell_metrics(rehearsal, "end_to_end")} == {
        "samples_per_s", "peak_hbm_gib", "setup_s"}


def test_boundary_and_tail_readers_on_hand_made_step_events():
    """Four epochs of four steps, 100 ms apart, 350 ms across a boundary."""
    steps, t = [], 0.0
    for epoch in range(1, 5):
        for i in range(4):
            t += 0.35 if (i == 0 and epoch > 1) else 0.1
            steps.append({"epoch": epoch, "start_s": t, "dur_s": 0.004})
    run = {"window_steps": steps}
    assert load("layer_metrics/boundary_ms.py").read(run) == pytest.approx(250.0)
    assert load("layer_metrics/step_ms_p95.py").read(run) == pytest.approx(350.0)
    one_epoch = {"window_steps": steps[:4]}
    assert load("layer_metrics/boundary_ms.py").read(one_epoch) is None
