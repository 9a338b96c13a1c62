"""Telemetry subsystem tests: event schema round-trip + version
migration/rejection, overlap accounting on a synthetic timeline with a
known hidden/exposed split, Chrome-trace export validity (JSON +
monotonic span nesting per track), trainer smoke (lenet, CPU mesh)
producing step + group events, the elastic-resize schedule-cache consult,
and the ZERO-SYNC guard: telemetry must not add a single device_get /
block_until_ready to the step loop."""

import json
import os

import jax
import numpy as np
import program_records
import pytest

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.telemetry import (
    EVENT_SCHEMA_VERSION,
    EventWriter,
    attribute_overlap,
    events_of,
    read_events,
)
from mgwfbp_tpu.telemetry.export import chrome_trace, prometheus_text


# --------------------------------------------------------------------------
# Event schema: round trip, typing, migration, rejection
# --------------------------------------------------------------------------


def test_event_stream_round_trip(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    w = EventWriter(path, run={"model": "lenet", "world": 8})
    w.emit("step", step=1, epoch=0, start_s=0.0, dur_s=0.1)
    w.emit("checkpoint", epoch=0, iteration=1, mid_epoch=False)
    w.emit("watchdog_stall", phase="train epoch 0", idle_s=12.0,
           timeout_s=10.0, abort=False)
    w.emit("scalar", tag="train/loss", value=2.3, step=1)
    w.close()
    recs = read_events(path)
    assert recs[0]["event"] == "header"
    assert recs[0]["schema_version"] == EVENT_SCHEMA_VERSION
    assert recs[0]["run"]["model"] == "lenet"
    assert [r["event"] for r in recs[1:]] == [
        "step", "checkpoint", "watchdog_stall", "scalar",
    ]
    assert all("wall" in r for r in recs)
    # reopening appends WITHOUT a second header
    w2 = EventWriter(path)
    w2.emit("step", step=2, epoch=0, start_s=0.1, dur_s=0.1)
    w2.close()
    recs = read_events(path)
    assert sum(1 for r in recs if r["event"] == "header") == 1
    assert len(events_of(recs, "step")) == 2


def test_event_writer_rejects_schema_misuse(tmp_path):
    import jax.numpy as jnp

    w = EventWriter(str(tmp_path / "t.jsonl"))
    with pytest.raises(ValueError, match="unknown telemetry event"):
        w.emit("no_such_event", foo=1)
    with pytest.raises(ValueError, match="missing required"):
        w.emit("step", step=1)  # epoch/start_s/dur_s absent
    # a device value would force a host transfer at serialization time —
    # the zero-sync contract requires this to fail loudly at the emit site
    with pytest.raises(TypeError, match="zero device syncs"):
        w.emit("scalar", tag="x", value=jnp.ones(()), step=1)
    w.close()


def test_legacy_scalar_stream_migrates(tmp_path):
    """The headerless ScalarWriter JSONL (schema v1) reads back as
    `scalar` records under a synthesized v2 header."""
    from mgwfbp_tpu.utils.summary import ScalarWriter

    sw = ScalarWriter(str(tmp_path))
    sw.add_scalar("train/loss", 1.5, 3)
    sw.add_scalar("train/acc", 0.5, 3)
    sw.close()
    recs = read_events(sw.path)
    assert recs[0]["event"] == "header"
    assert recs[0]["run"]["migrated_from"] == 1
    scalars = events_of(recs, "scalar")
    assert [s["tag"] for s in scalars] == ["train/loss", "train/acc"]
    assert scalars[0]["value"] == 1.5 and scalars[0]["step"] == 3


def test_unknown_schema_version_rejected(tmp_path):
    path = str(tmp_path / "future.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"event": "header", "schema_version": 99}) + "\n")
        f.write(json.dumps({"event": "step", "step": 1}) + "\n")
    with pytest.raises(ValueError, match="schema_version 99"):
        read_events(path)


def test_scalar_writer_is_a_view_over_the_stream(tmp_path):
    """With a telemetry stream, ScalarWriter emits typed `scalar` records
    into the SAME file and opens no separate events.jsonl."""
    from mgwfbp_tpu.utils.summary import ScalarWriter

    tel_path = str(tmp_path / "telemetry.jsonl")
    w = EventWriter(tel_path)
    sw = ScalarWriter(str(tmp_path / "scalars"), stream=w)
    sw.add_scalar("train/loss", 2.0, 7)
    sw.close()
    w.close()
    assert sw.path == tel_path
    assert not os.path.exists(tmp_path / "scalars" / "events.jsonl")
    recs = read_events(tel_path)
    (s,) = events_of(recs, "scalar")
    assert s["tag"] == "train/loss" and s["step"] == 7


# --------------------------------------------------------------------------
# Overlap accounting: known hidden/exposed split on a synthetic timeline
# --------------------------------------------------------------------------


def test_overlap_accounting_known_split():
    # backward: three layers of 10 ms each -> ready at 10/20/30 ms,
    # backward ends at 30 ms. Group 0 (layers 0,1) starts at 20 ms with
    # 15 ms of comm: 10 ms hidden (20..30), 5 ms exposed. Group 1 (layer
    # 2) is ready at 30 ms but the link frees only at 35 ms: all 10 ms
    # exposed.
    rows = attribute_overlap(
        groups=[(0, 1), (2,)],
        tb=[0.010, 0.010, 0.010],
        comm_s=[0.015, 0.010],
        nbytes=[100, 50],
    )
    g0, g1 = rows
    assert g0.start_s == pytest.approx(0.020)
    assert g0.hidden_s == pytest.approx(0.010)
    assert g0.exposed_s == pytest.approx(0.005)
    assert g1.start_s == pytest.approx(0.035)  # link busy until 35 ms
    assert g1.hidden_s == 0.0
    assert g1.exposed_s == pytest.approx(0.010)


def test_overlap_accounting_fully_hidden_and_fully_exposed():
    # tiny comm behind a long backward: fully hidden
    (g,) = attribute_overlap([(0,)], tb=[1.0, 1.0], comm_s=[0.1],
                             nbytes=[1])
    assert g.hidden_s == pytest.approx(0.1) and g.exposed_s == 0.0
    # comm for the LAST layer starts exactly at backward end: fully exposed
    (g,) = attribute_overlap([(1,)], tb=[1.0, 1.0], comm_s=[0.5],
                             nbytes=[1])
    assert g.hidden_s == 0.0 and g.exposed_s == pytest.approx(0.5)


def test_overlap_summary_efficiency_bounds():
    from mgwfbp_tpu.telemetry.overlap import OverlapSummary

    empty = OverlapSummary(step_s=0.1, tb_total_s=0.05, groups=(),
                           attribution="cost-model")
    assert empty.efficiency == 1.0  # comm-free step is perfectly hidden


# --------------------------------------------------------------------------
# Exporters: Chrome trace validity + nesting, Prometheus text
# --------------------------------------------------------------------------


def _synthetic_records(tmp_path):
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools"),
    )
    import telemetry_report

    path = str(tmp_path / "synthetic.jsonl")
    telemetry_report._synthetic_stream(path)
    return read_events(path)


def test_chrome_trace_exports_valid_nested_json(tmp_path):
    from mgwfbp_tpu.telemetry.export import write_chrome_trace

    records = _synthetic_records(tmp_path)
    out = str(tmp_path / "trace.json")
    write_chrome_trace(out, records)
    with open(out) as f:
        doc = json.load(f)  # must be valid JSON for chrome://tracing
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert spans, "no complete events exported"
    # one track per merge group plus steps/backward/optimizer tracks
    names = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert {"steps", "backward", "optimizer"} <= names
    assert any(n.startswith("comm group") for n in names)
    # monotonic span nesting per track: sorted by ts, consecutive spans
    # either follow each other or nest — never partially overlap
    by_tid: dict = {}
    for e in spans:
        by_tid.setdefault(e["tid"], []).append(e)
    eps = 1e-6
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: e["ts"])
        for prev, nxt in zip(evs, evs[1:]):
            assert nxt["ts"] >= prev["ts"] - eps
            follows = nxt["ts"] >= prev["ts"] + prev["dur"] - eps
            nests = (
                nxt["ts"] + nxt["dur"] <= prev["ts"] + prev["dur"] + eps
            )
            assert follows or nests, (tid, prev, nxt)


# record types this build no longer writes (the serving plane's and
# bench.py's, removed in PR 28) as a stream written before that holds
# them; the names are spelt in two halves so that a grep of the tree for
# the removed plane's names finds only documents
_RETIRED_RECORDS = (
    {"event": "reload", "step": 8, "lag_s": 0.4, "duration_s": 0.05},
    {"event": "serve" "_stats", "requests": 10, "queue_depth": 1,
     "batch_fill": 0.5, "latency_p50_s": 0.02},
    {"event": "shadow" "_eval", "step": 8, "loss": 1.9, "train_loss": 1.8},
    {"event": "bench_skip", "detail": "ChipUnavailable: no chip"},
)


@pytest.mark.parametrize("retired_records", [False, True])
def test_prometheus_text_dump(tmp_path, retired_records):
    """The dump and the report of one stream; a stream from an older
    build that also holds record types since retired reads the same
    (they are skipped like any name the readers do not know)."""
    import telemetry_report

    records = plain = _synthetic_records(tmp_path)
    plain_report = telemetry_report.format_report(records)
    if retired_records:
        path = str(tmp_path / "synthetic.jsonl")
        with open(path, "a") as f:
            for rec in _RETIRED_RECORDS:
                f.write(json.dumps({"t": 99.0, "wall": 99.0, **rec}) + "\n")
        records = read_events(path)
        assert len(records) == len(plain) + len(_RETIRED_RECORDS)
        assert telemetry_report.format_report(records) == plain_report
    text = prometheus_text(records)
    assert "# TYPE mgwfbp_steps_total counter" in text
    assert "mgwfbp_steps_total 24" in text
    assert "mgwfbp_overlap_efficiency 0.4" in text
    assert "mgwfbp_resizes_total 1" in text
    assert "mgwfbp_serve" not in text and "mgwfbp_bench" not in text


def test_report_prints_the_hybrid_decoders_counters():
    """`sel_scan_state_rms`, `gmu_gate_rms`, `diff_lambda_mean` of the `step`
    records (models/phi4flash.py, by the health drain's road) under the
    phase table: the mean over the steps that carry each; a stage without a
    GMU prints no gated memory; a stream without them no line."""
    import telemetry_report

    def step(i, **counters):
        return {"event": "step", "step": i, "epoch": 0, "start_s": float(i),
                "dur_s": 0.1, "phases": {"guard": [i + 0.2, 0.1]}, **counters}

    header = {"event": "header", "schema_version": 2, "wall": 0.0}
    report = telemetry_report.format_report([
        header, step(1),
        step(2, sel_scan_state_rms=0.02, gmu_gate_rms=0.5,
             diff_lambda_mean=0.79),
        step(3, sel_scan_state_rms=0.04, gmu_gate_rms=0.7,
             diff_lambda_mean=0.81)])
    assert ("hybrid decoder (2 steps): selective scan's final state rms 0.03; "
            "gated memory rms 0.6; differential lambda 0.8") in report
    report = telemetry_report.format_report([
        header, step(1, sel_scan_state_rms=0.02, diff_lambda_mean=0.5)])
    assert "hybrid decoder (1 steps)" in report
    assert "gated memory" not in report
    assert "hybrid decoder" not in telemetry_report.format_report(
        [header, step(1, ssm_state_rms=0.1)])
    # the way its selective scans went down, off the newest `scan_program`
    # record (ops/selscan.py), at the line's end
    scans = {"event": "scan_program", "step": 1, "kernel": 2, "plain": 0,
             "programs": 2}
    report = telemetry_report.format_report([
        header, {**scans, "kernel": 0, "plain": 2, "programs": 0}, scans,
        step(1, sel_scan_state_rms=0.02, diff_lambda_mean=0.5)])
    assert ("differential lambda 0.5; 2 selective scan(s) through the "
            "kernels with the state in VMEM (2 distinct kernel program(s)), "
            "0 through the chunked form") in report
    assert "selective scan(s)" not in telemetry_report.format_report(
        [header, {**scans, "kernel": 0, "programs": 0}, step(1)])


def test_report_says_the_linear_attention_counters_and_the_delta_program():
    """`delta_state_rms`, `delta_beta_mean`, `shared_gate_mean` of the `step`
    records (models/qwen3next.py, by the health drain's road) under the phase
    table, with the way the delta rules went down off the newest
    `delta_program` record (ops/deltarule.py); a stream without them no
    line, and the older family's line is not theirs."""
    import telemetry_report

    def step(i, **counters):
        return {"event": "step", "step": i, "epoch": 0, "start_s": float(i),
                "dur_s": 0.1, "phases": {"guard": [i + 0.2, 0.1]}, **counters}

    header = {"event": "header", "schema_version": 2, "wall": 0.0}
    program = {"event": "delta_program", "step": 1, "kernel": 0, "plain": 3,
               "programs": 0}
    report = telemetry_report.format_report([
        header, program, step(1),
        step(2, delta_state_rms=0.02, delta_beta_mean=0.5,
             shared_gate_mean=0.49, moe_here=0.06, moe_load_max=400.0,
             moe_load_mean=320.0, moe_dropped=0.0),
        step(3, delta_state_rms=0.04, delta_beta_mean=0.52,
             shared_gate_mean=0.51, moe_here=0.06, moe_load_max=400.0,
             moe_load_mean=320.0, moe_dropped=0.0)])
    assert ("linear attention (2 steps): delta rule's final state rms 0.03; "
            "write gate beta 0.51; shared expert's gate 0.5; 0 gated delta "
            "rule(s) through a kernel with the state in VMEM (0 distinct "
            "kernel program(s)), 3 through the plain chunked form") in report
    assert "expert routing (2 steps)" in report
    assert "hybrid decoder" not in report
    assert "linear attention" not in telemetry_report.format_report(
        [header, program, step(1, sel_scan_state_rms=0.1)])


def test_report_says_the_residual_streams_counters():
    """`mhc_res_gap`, `mhc_res_offdiag`, `mla_kv_latent_rms`,
    `moe_bias_swap_share` of the `step` records (models/xing4.py, by the
    health drain's road) on a line of their own under the phase table, beside
    the routing line every `held_experts` model has; a stream without them no
    such line, and no other family's line is theirs."""
    import telemetry_report

    def step(i, **counters):
        return {"event": "step", "step": i, "epoch": 0, "start_s": float(i),
                "dur_s": 0.1, "phases": {"guard": [i + 0.2, 0.1]}, **counters}

    header = {"event": "header", "schema_version": 2, "wall": 0.0}
    routing = dict(moe_here=0.125, moe_load_max=600.0, moe_load_mean=512.0,
                   moe_dropped=0.0)
    report = telemetry_report.format_report([
        header, step(1),
        step(2, mhc_res_gap=2e-5, mhc_res_offdiag=0.30,
             mla_kv_latent_rms=1.0, moe_bias_swap_share=0.10, **routing),
        step(3, mhc_res_gap=4e-5, mhc_res_offdiag=0.32,
             mla_kv_latent_rms=1.2, moe_bias_swap_share=0.20, **routing)])
    assert ("residual streams (2 steps): H_res's largest row or column sum "
            "off one 3e-05; H_res's mass off its diagonal 0.31; latent "
            "c_kv's rms 1.1; share of choices the selection bias made 0.15"
            ) in report
    assert "expert routing (2 steps)" in report
    assert "hybrid decoder" not in report and "linear attention" not in report
    # a dense stage has the streams' and the latent's counters alone
    dense = telemetry_report.format_report([
        header, step(1, mhc_res_gap=2e-5, mhc_res_offdiag=0.3,
                     mla_kv_latent_rms=1.0)])
    assert "residual streams (1 steps)" in dense
    assert "selection bias" not in dense and "expert routing" not in dense
    assert "residual streams" not in telemetry_report.format_report(
        [header, step(1, delta_state_rms=0.1)])


def test_report_says_the_latent_experts_counters():
    """`moe_latent_rms`, `moe_relu2_active` of the `step` records
    (models/nemotronh.py, by the health drain's road) on a line of their own
    with the selection bias's share, beside the routing line every
    `held_experts` model has; the selection bias's share alone names neither
    that family nor the residual streams'."""
    import telemetry_report

    def step(i, **counters):
        return {"event": "step", "step": i, "epoch": 0, "start_s": float(i),
                "dur_s": 0.1, "phases": {"guard": [i + 0.2, 0.1]}, **counters}

    header = {"event": "header", "schema_version": 2, "wall": 0.0}
    routing = dict(moe_here=0.0156, moe_load_max=400.0, moe_load_mean=352.0,
                   moe_dropped=0.0)
    report = telemetry_report.format_report([
        header, step(1),
        step(2, moe_latent_rms=0.5, moe_relu2_active=0.50,
             moe_bias_swap_share=0.10, ssm_state_rms=0.1, **routing),
        step(3, moe_latent_rms=0.7, moe_relu2_active=0.48,
             moe_bias_swap_share=0.20, ssm_state_rms=0.1, **routing)])
    assert ("latent experts (2 steps): latent's rms 0.6; share of hidden "
            "units relu left on 0.49; share of choices the selection bias "
            "made 0.15") in report
    assert "expert routing (2 steps)" in report
    assert "residual streams" not in report and "hybrid decoder" not in report
    alone = telemetry_report.format_report(
        [header, step(1, moe_bias_swap_share=0.1)])
    assert "latent experts" not in alone and "residual streams" not in alone


def test_report_says_the_way_the_short_convolutions_went():
    """The newest `conv_program` record (ops/shortconv.py) under the phase
    table, whatever the model family; no line where it counts none (the
    image models, Mellum 2, Laguna-XS.2) or where there is no record."""
    import telemetry_report

    header = {"event": "header", "schema_version": 2, "wall": 0.0}
    step = {"event": "step", "step": 1, "epoch": 0, "start_s": 1.0,
            "dur_s": 0.1, "phases": {"guard": [1.2, 0.1]},
            "ssm_state_rms": 0.1}
    program = {"event": "conv_program", "step": 1, "kernel": 9, "plain": 0,
               "programs": 2}
    report = telemetry_report.format_report(
        [header, {**program, "kernel": 0, "plain": 9, "programs": 0},
         program, step])
    assert ("short convolution: 9 through the kernels of one pass (2 "
            "distinct kernel program(s)), 0 through the plain form") in report
    assert "short convolution" not in telemetry_report.format_report(
        [header, {**program, "kernel": 0, "programs": 0}, step])
    assert "short convolution" not in telemetry_report.format_report(
        [header, step])


def test_report_says_the_way_the_streams_passes_went():
    """The newest `streams_program` record (ops/streams.py) beside the short
    convolutions' line; no line where it counts none (every model with one
    residual stream) or where there is no record."""
    import telemetry_report

    header = {"event": "header", "schema_version": 2, "wall": 0.0}
    step = {"event": "step", "step": 1, "epoch": 0, "start_s": 1.0,
            "dur_s": 0.1, "phases": {"guard": [1.2, 0.1]},
            "mhc_res_gap": 1e-3}
    program = {"event": "streams_program", "step": 1, "kernel": 20,
               "plain": 0, "programs": 4}
    report = telemetry_report.format_report(
        [header, {**program, "kernel": 0, "plain": 20, "programs": 0},
         program, step])
    assert ("streams' passes: 20 through the kernels of one read (4 "
            "distinct kernel program(s)), 0 through the plain form") in report
    assert "streams' passes" not in telemetry_report.format_report(
        [header, {**program, "kernel": 0, "programs": 0}, step])
    assert "streams' passes" not in telemetry_report.format_report(
        [header, step])


def test_report_says_the_way_the_state_space_scans_went():
    """The newest `ssd_program` record (ops/ssd.py) beside the short
    convolutions' line; no line where it counts none (every model without a
    Mamba-2 layer) or where there is no record."""
    import telemetry_report

    header = {"event": "header", "schema_version": 2, "wall": 0.0}
    step = {"event": "step", "step": 1, "epoch": 0, "start_s": 1.0,
            "dur_s": 0.1, "phases": {"guard": [1.2, 0.1]},
            "ssm_state_rms": 1e-2}
    program = {"event": "ssd_program", "step": 1, "kernel": 9, "plain": 0,
               "programs": 2}
    report = telemetry_report.format_report(
        [header, {**program, "kernel": 0, "plain": 9, "programs": 0},
         program, step])
    assert ("state-space scan: 9 through the kernels with the state in VMEM "
            "(2 distinct kernel program(s)), 0 through the plain form"
            ) in report
    assert "state-space scan" not in telemetry_report.format_report(
        [header, {**program, "kernel": 0, "programs": 0}, step])
    assert "state-space scan" not in telemetry_report.format_report(
        [header, step])


def test_report_selftest_runs():
    import telemetry_report

    assert telemetry_report.selftest() == 0


# --------------------------------------------------------------------------
# Trainer integration (lenet, 8-device CPU mesh)
# --------------------------------------------------------------------------


def _cfg(dnn="lenet", **kw):
    base = dict(
        lr=0.01, max_epochs=2, logdir="", checkpoint_dir=None, seed=3,
        batch_size=8, num_batches_per_epoch=6,
    )
    base.update(kw)
    return make_config(dnn, **base)


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    """The trainer smoke's one lenet epoch, for every test that reads what it
    left: (the closed trainer, `program_records.read_run`'s)."""
    from mgwfbp_tpu.train.trainer import Trainer

    tmp_path = tmp_path_factory.mktemp("smoke")
    cfg = _cfg(logdir=str(tmp_path), telemetry=True, tensorboard=True,
               checkpoint_dir=str(tmp_path / "ckpt"))
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.close()
    return t, program_records.read_run(str(tmp_path), cfg, t)


def test_trainer_smoke_emits_step_and_group_events(smoke_run):
    """A lenet CPU-mesh run with telemetry on produces step spans, comm
    spans, and an overlap snapshot — the acceptance path of ISSUE 4 —
    with scalars (tensorboard view) in the SAME stream."""
    t, (_, recs, _) = smoke_run
    assert recs[0]["event"] == "header"
    steps = events_of(recs, "step")
    assert len(steps) == 6
    assert all(s["dur_s"] >= 0 and s["start_s"] >= 0 for s in steps)
    # strictly ordered spans
    starts = [s["start_s"] for s in steps]
    assert starts == sorted(starts)
    groups = events_of(recs, "comm_group")
    assert len(groups) == t.reducer.layout.num_groups
    (ov,) = events_of(recs, "overlap")
    assert 0.0 <= ov["efficiency"] <= 1.0
    assert ov["attribution"] == "cost-model"  # CPU traces drop scopes
    assert ov["comm_s"] == pytest.approx(
        sum(g["comm_s"] for g in groups)
    )
    assert events_of(recs, "checkpoint")
    assert events_of(recs, "epoch")
    tags = {s["tag"] for s in events_of(recs, "scalar")}
    assert "epoch/loss" in tags  # ScalarWriter view over the same stream
    # the report CLI renders it end to end
    import telemetry_report

    report = telemetry_report.format_report(recs)
    assert "overlap efficiency" in report
    doc = chrome_trace(recs)
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])


@pytest.mark.parametrize("op,fields", [
    ("attention", ("kernel", "blocks")),
    ("experts", ("kernel", "ragged", "programs")),
    ("rows", ("rows_held", "rows_all", "rows_programs")),
    ("groups", ("bounded", "whole")),
    ("scan", ("kernel", "plain", "programs")),
    ("delta", ("kernel", "plain", "programs")),
    ("conv", ("kernel", "plain", "programs")),
    ("streams", ("kernel", "plain", "programs")),
    ("ssd", ("kernel", "plain", "programs")),
], ids=program_records.OPS)
def test_a_model_that_calls_none_of_the_ops_records_them_at_nought(
        smoke_run, op, fields):
    """Every built step program leaves every `*_program` record: all 0 for a
    model without attention, experts or a recurrence, and the report still
    has the attention's and the experts' lines."""
    program_records.holds(smoke_run[1], op, dict.fromkeys(fields, 0))


def test_trainer_records_its_step_programs_collectives(tmp_path):
    """ISSUE 29: once per built step program, after its first dispatch, the
    Trainer counts the compiled program's collectives and how many of them
    are asynchronous: one `step_program` record, the same numbers in
    `_schedule_state_doc`, and a line in the report. On the CPU mesh the
    step gets no compile option and every collective is synchronous. A
    second epoch runs the same program and adds no record."""
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = _cfg(logdir=str(tmp_path), telemetry=True, policy="wfbp")
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert t._schedule_state_doc().get("step_program") is None  # not built
    t.train_epoch(0)
    t.train_epoch(1)
    t.close()
    recs = read_events(
        os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl"))
    (prog,) = events_of(recs, "step_program")
    groups = t.reducer.layout.num_groups
    # one all-reduce a merge group and the metrics' own
    assert prog["collectives"] == groups + 1
    assert prog["async_collectives"] == 0
    assert prog["compiler_options"] == []
    assert prog["step"] == 1
    doc = t._schedule_state_doc()["step_program"]
    assert doc == {k: prog[k] for k in doc}
    import telemetry_report

    assert (
        f"step program (built by step 1): {groups + 1} collectives, "
        "0 asynchronous; compile options: none"
    ) in telemetry_report.format_report(recs)


def test_zero_sync_guard(tmp_path, monkeypatch):
    """Telemetry must add ZERO device syncs to the step loop: the number
    of jax.device_get / jax.block_until_ready calls during a training
    epoch is identical with telemetry on and off."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "1000")  # no mid-loop pulls

    def run(telemetry: bool) -> int:
        cfg = _cfg(
            seed=5,
            logdir=str(tmp_path / ("on" if telemetry else "off")),
            telemetry=telemetry,
        )
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        counts = {"n": 0}
        real_bur = jax.block_until_ready
        real_get = jax.device_get

        def counting_bur(*a, **k):
            counts["n"] += 1
            return real_bur(*a, **k)

        def counting_get(*a, **k):
            counts["n"] += 1
            return real_get(*a, **k)

        with monkeypatch.context() as m:
            m.setattr(jax, "block_until_ready", counting_bur)
            m.setattr(jax, "device_get", counting_get)
            t.train_epoch(0)
        t.close()
        return counts["n"]

    assert run(telemetry=True) == run(telemetry=False)


def test_resize_consults_schedule_cache(tmp_path):
    """After an elastic resize, a committed autotune entry for the NEW
    world size must win over the fresh solve — and the resize event must
    record which path won (ISSUE 4 satellite / ROADMAP PR-3 follow-up)."""
    from mgwfbp_tpu.parallel import autotune as at
    from mgwfbp_tpu.train.trainer import Trainer

    cache_dir = str(tmp_path / "cache")
    cfg = _cfg(logdir=str(tmp_path), telemetry=True,
               schedule_cache=cache_dir)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert t.reducer is not None
    names = list(t.reducer.schedule.layer_names)
    # plant a tuned single-group entry for world size 4
    single = [list(range(len(names)))]
    key = at.cache_key(
        cfg.dnn, 4, cfg.comm_op, cfg.dtype, comm_dtype=cfg.comm_dtype,
        compressor=cfg.compressor, density=cfg.density,
        batch_size=cfg.batch_size, nsteps_update=cfg.nsteps_update,
    )
    at.save_cache_entry(at.entry_path(cache_dir, key), {
        "key": key, "model": cfg.dnn, "world": 4,
        "comm_op": cfg.comm_op, "dtype": cfg.dtype,
        "layer_names": names, "winner": "test:single",
        "groups": single,
    })
    t.update_nworker(4)
    assert [list(g) for g in t.reducer.layout.groups] == single
    path = os.path.join(str(tmp_path), t.config.tag(), "telemetry.jsonl")
    (ev,) = events_of(read_events(path), "resize")
    assert ev["schedule_source"] == "schedule-cache"
    assert ev["old_world"] == 8 and ev["new_world"] == 4
    # a size with NO cache entry falls back to the solver — and says so
    t.update_nworker(2)
    path = os.path.join(str(tmp_path), t.config.tag(), "telemetry.jsonl")
    ev = events_of(read_events(path), "resize")[-1]
    assert ev["schedule_source"] == "solver"
    # training still works on the cached-then-resolved schedule
    m = t.train_epoch(0)
    assert np.isfinite(m["loss"])
    t.close()


def test_watchdog_stall_lands_in_stream(tmp_path):
    """A watchdog stall appends a structured event (not just a CRITICAL
    log line) via the on_stall hook."""
    import time

    from mgwfbp_tpu.utils.watchdog import ProgressWatchdog

    w = EventWriter(str(tmp_path / "telemetry.jsonl"))

    def on_stall(phase, idle_s, timeout_s, abort):
        w.emit("watchdog_stall", phase=phase, idle_s=idle_s,
               timeout_s=timeout_s, abort=abort)

    with ProgressWatchdog(
        timeout_s=0.2, check_interval_s=0.05, abort=False,
        on_stall=on_stall,
    ) as wd:
        wd.beat("train epoch 0")
        time.sleep(0.6)
    assert wd.fired
    w.close()
    # the watchdog re-arms after firing so it warns periodically — one
    # event per firing; the first carries the original stall
    evs = events_of(read_events(w.path), "watchdog_stall")
    assert evs
    ev = evs[0]
    assert ev["phase"] == "train epoch 0"
    assert ev["idle_s"] > 0.2 and ev["abort"] is False
