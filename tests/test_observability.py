"""Live observability plane (ISSUE 9): drift-detector units on synthetic
residual streams (band crossing, EWMA trend, alarm hysteresis — no
flapping), the /metrics + /healthz + /status endpoints over a real lenet
CPU-mesh run (including the watchdog-stall unhealthy flip), the
zero-sync pin with the server enabled, rotated multi-segment and
per-process streams replaying into the aggregator, the registry that
keeps the file dump and the live endpoint identical, the measured RS/AG
phase split (calibrate --allgather, profile schema v3), the SUPERVISED
2-process straggler alarm under `stall@` faults on proc=1 — now also
pinning the ISSUE-10 fleet console: /fleet/metrics + /fleet/status
probed mid-run, the alarm fleet-visible, fleet.json persisting the
children's actual ephemeral ports — and the acceptance loop: an
injected 10x calibration error raises a `drift_alarm` that (with
MGWFBP_DRIFT_REAUTOTUNE=1) triggers a re-autotune whose committed
schedule recovers within 5% of the well-calibrated one. The fleet/
profile unit + pinned tests live in tests/test_fleet.py."""

import glob
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.telemetry import (
    DriftConfig,
    DriftDetector,
    EventWriter,
    MetricsAggregator,
    StragglerDetector,
    TelemetryServer,
    events_of,
    read_event_set,
    read_events,
)
from mgwfbp_tpu.telemetry.drift import Hysteresis
from mgwfbp_tpu.telemetry.export import (
    METRICS,
    prometheus_text,
    render_metrics,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get(port: int, path: str):
    """(status, body) — 503 is an answer, not an error."""
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ---------------------------------------------------------------------------
# drift detector units (synthetic residual streams)
# ---------------------------------------------------------------------------


def test_hysteresis_no_flapping():
    """A residual oscillating across the band must not flap the alarm:
    k consecutive exceedances raise, k consecutive normals clear,
    anything shorter holds state."""
    h = Hysteresis(2)
    edges = [h.update(x) for x in
             [True, False, True, False, True, True,   # raise at idx 5
              False, True, False, False]]             # clear at idx 9
    assert edges[5] == "raise" and edges[9] == "clear"
    assert [e for e in edges if e] == ["raise", "clear"]


def test_drift_comm_band_crossing_trace_absolute():
    """Per-group (trace-attributed) residuals: ratio leaving
    [1/band, band] raises after `hysteresis` observations; returning
    clears after the same count. Both sides of the band alarm."""
    det = DriftDetector(DriftConfig(band=2.0, hysteresis=2))
    out = []
    # group 0 over-predicted 3x, group 1 healthy
    for _ in range(2):
        out += det.observe_comm([3.0, 1.0], measured_s=[1.0, 1.0])
    assert [(a.group, a.active) for a in out] == [(0, True)]
    assert det.active
    for _ in range(2):
        out += det.observe_comm([1.0, 1.0], measured_s=[1.0, 1.0])
    assert [(a.group, a.active) for a in out] == [(0, True), (0, False)]
    assert not det.active
    # under-prediction alarms too (hardware slower than the model says)
    out2 = []
    for _ in range(2):
        out2 += det.observe_comm([0.2], measured_s=[1.0])
    assert out2 and out2[0].active and out2[0].residual == pytest.approx(0.2)


def test_drift_comm_aggregate_is_baseline_relative():
    """The aggregate channel (no trace) learns the healthy
    predicted/measured ratio over the baseline window, then alarms on the
    drift FACTOR — unmodeled overhead in the estimator cancels."""
    det = DriftDetector(
        DriftConfig(band=3.0, baseline_window=3, hysteresis=1)
    )
    # healthy phase: prediction is 10% of the (overhead-inflated) estimate
    for _ in range(4):
        assert det.observe_comm([0.1], measured_total_s=1.0) == []
    # model drifts 10x; estimator unchanged -> factor ~10 > band 3
    alarms = det.observe_comm([1.0], measured_total_s=1.0)
    assert len(alarms) == 1 and alarms[0].active
    assert alarms[0].group == -1
    assert alarms[0].residual == pytest.approx(10.0)
    # back in band -> clears
    alarms = det.observe_comm([0.1], measured_total_s=1.0)
    assert len(alarms) == 1 and not alarms[0].active


def test_drift_step_trend_ewma():
    """EWMA step-time trend vs the frozen baseline window."""
    det = DriftDetector(DriftConfig(
        trend_band=0.5, baseline_window=3, hysteresis=2, ewma_alpha=1.0,
    ))
    out = []
    for s in [0.1, 0.1, 0.1]:          # baseline
        out += det.observe_step_window(s)
    for s in [0.11, 0.12, 0.11, 0.12]:  # mild noise: no alarm
        out += det.observe_step_window(s)
    assert out == []
    for s in [0.2, 0.2]:               # 2x: raise after hysteresis
        out += det.observe_step_window(s)
    assert len(out) == 1 and out[0].active and out[0].kind == "step_trend"
    assert out[0].residual == pytest.approx(1.0)
    out2 = []
    for s in [0.1, 0.1]:
        out2 += det.observe_step_window(s)
    assert len(out2) == 1 and not out2[0].active
    det.reset()
    assert not det.active


def test_straggler_detector_hysteresis():
    sd = StragglerDetector(band=0.25, hysteresis=2)
    assert sd.observe([0.1, 0.101]) is None
    assert sd.observe([0.1, 0.2]) is None          # 1st exceedance
    a = sd.observe([0.1, 0.21])                    # 2nd -> raise
    assert a is not None and a.active and a.slow_process == 1
    assert a.excess_s == pytest.approx(0.11)
    assert sd.observe([0.1, 0.1]) is None          # 1st normal
    # the clear edge resolves the RAISED alarm: it must name the process
    # the raise named (p1), even when the healthy probe's argmax lands
    # elsewhere (p0 fractionally slower here)
    a = sd.observe([0.1001, 0.1])                  # 2nd -> clear
    assert a is not None and not a.active
    assert a.slow_process == 1


# ---------------------------------------------------------------------------
# registry + aggregator replay
# ---------------------------------------------------------------------------


def test_render_metrics_rejects_unregistered():
    with pytest.raises(ValueError, match="not in telemetry.export.METRICS"):
        render_metrics({"mgwfbp_not_a_metric": 1})
    assert len({name for name, _, _ in METRICS}) == len(METRICS)


def test_rotated_and_per_process_streams_replay(tmp_path):
    """A size-rotated multi-segment stream and a multi-host group's
    per-process streams both replay into the aggregator exactly as the
    un-rotated single stream would."""
    # rotated: tiny max_bytes forces several segments
    p = str(tmp_path / "telemetry.jsonl")
    w = EventWriter(p, run={"model": "m"}, max_bytes=400)
    for i in range(30):
        w.emit("step", step=i + 1, epoch=0, start_s=i * 0.1, dur_s=0.1)
    w.emit("checkpoint", epoch=0, iteration=30, mid_epoch=False)
    w.close()
    assert glob.glob(p + ".*"), "stream never rotated"
    recs = read_event_set(p)
    agg = MetricsAggregator()
    agg.replay(recs)
    v = agg.values()
    assert v["mgwfbp_steps_total"] == 30
    assert v["mgwfbp_current_step"] == 30
    assert v["mgwfbp_checkpoints_total"] == 1
    # the file dump renders the identical text from the same records
    assert prometheus_text(recs) == render_metrics(v)
    # per-process streams: each replays into its own process's aggregator
    from mgwfbp_tpu.telemetry import find_stream_paths, stream_filename

    d2 = tmp_path / "multi"
    for pi in range(2):
        w = EventWriter(
            str(d2 / stream_filename(pi, 2)),
            run={"process_index": pi, "process_count": 2},
        )
        for i in range(3 + pi):
            w.emit("step", step=i + 1, epoch=0, start_s=0.0, dur_s=0.1)
        w.close()
    paths = find_stream_paths(str(d2))
    assert len(paths) == 2
    for pi, path in enumerate(paths):
        agg = MetricsAggregator()
        agg.replay(read_events(path))
        assert agg.values()["mgwfbp_steps_total"] == 3 + pi
        assert agg.status()["run"]["process_index"] == pi


# ---------------------------------------------------------------------------
# live endpoints over a real lenet CPU-mesh run
# ---------------------------------------------------------------------------


def test_live_endpoints_and_watchdog_flip(tmp_path, monkeypatch):
    """A real lenet run with --metrics-port: /metrics serves the live
    step/overlap/schedule state, /status the run document, and /healthz
    flips 503 on a REAL watchdog stall (injected stall fault + 1 s
    watchdog) then recovers when the loop moves again."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_WATCHDOG_S", "1")
    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "stall@secs=4,step=3")
    cfg = make_config(
        "lenet", lr=0.01, max_epochs=1, logdir=str(tmp_path), seed=3,
        batch_size=8, num_batches_per_epoch=6, metrics_port=0,
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert cfg.telemetry  # metrics_port implies the event stream
    port = t._metrics_server.port
    codes: list[int] = []
    done = threading.Event()

    def poll():
        while not done.is_set():
            code, _ = _get(port, "/healthz")
            if code is not None and (not codes or codes[-1] != code):
                codes.append(code)
            time.sleep(0.05)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        t.fit(1)
    finally:
        done.set()
        poller.join(timeout=5)
    code, body = _get(port, "/metrics")
    assert code == 200
    assert "mgwfbp_steps_total 6" in body, body
    # the watchdog re-fires each interval while the stall lasts
    stalls = int(next(
        line.split()[1] for line in body.splitlines()
        if line.startswith("mgwfbp_watchdog_stalls_total ")
    ))
    assert stalls >= 1, body
    code, status = _get(port, "/status")
    assert code == 200
    st = json.loads(status)
    assert st["step"] == 6 and st["epoch"] == 0, st
    assert st["run"]["model"] == "lenet"
    assert st["schedule"]["num_groups"] >= 1, st
    assert st["overlap_efficiency"] is not None
    assert st["healthy"] and st["health_reason"] == "ok"
    # the stall flipped /healthz unhealthy MID-RUN, then a step recovered
    assert 503 in codes, codes
    assert codes[-1] == 200, codes
    recs = read_event_set(glob.glob(str(tmp_path / "*/telemetry.jsonl"))[0])
    stall_events = events_of(recs, "watchdog_stall")
    assert stall_events and not any(s["abort"] for s in stall_events)
    t.close()
    # the server is down after close()
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=1
        )


def test_abort_bound_stall_sticks_unhealthy():
    """An abort=True stall (the rc-86 path) must flip /healthz sticky —
    the prober sees unhealthy BEFORE the process dies, and no later step
    may clear it."""
    agg = MetricsAggregator()
    agg.observe("step", {"step": 1, "epoch": 0, "start_s": 0, "dur_s": 0.1})
    assert agg.health() == (True, "ok")
    agg.observe("watchdog_stall", {
        "phase": "train", "idle_s": 30.0, "timeout_s": 5.0, "abort": True,
    })
    healthy, reason = agg.health()
    assert not healthy and "rc 86" in reason
    agg.observe("step", {"step": 2, "epoch": 0, "start_s": 0, "dur_s": 0.1})
    assert not agg.health()[0]


# The PR-4/9 zero-sync pin (server + aggregator tee + drift detector add
# zero device syncs) now lives in tests/test_health.py::
# test_zero_sync_guard_with_health_stats_and_recorder, whose on/off
# comparison is a strict superset: the "on" branch runs the same live
# plane PLUS the ISSUE-12 in-jit health statistics, their deque drain,
# the health detector, and the flight recorder tee; the "off" branch
# disables all of it (health_stats=False removes the stats from the
# jitted program entirely). One two-trainer comparison pins both layers.


# ---------------------------------------------------------------------------
# supervisor wiring
# ---------------------------------------------------------------------------


def test_supervisor_reads_child_status():
    """The supervisor resolves per-child metrics ports from the group env
    and pulls a reachable child's /status snapshot (the rc-86 stop path);
    a dead port degrades to None."""
    from mgwfbp_tpu.runtime.supervisor import Supervisor

    agg = MetricsAggregator(run={"model": "x"})
    agg.observe("step", {"step": 7, "epoch": 1, "start_s": 0, "dur_s": 0.1})
    srv = TelemetryServer(agg, 0, host="127.0.0.1")
    try:
        sup = Supervisor(
            ["true"], 2,
            env={"MGWFBP_METRICS_PORT": str(srv.port)},
        )
        assert sup._metrics_base_port() == srv.port
        st = sup._child_status(0)
        assert st is not None and st["step"] == 7, st
        # child 1's port (base+1) has nobody listening
        assert sup._child_status(1) is None
        assert Supervisor(["true"], 1, env={})._metrics_base_port() is None
        assert Supervisor(
            ["true"], 1, env={"MGWFBP_METRICS_PORT": "0"},
        )._metrics_base_port() is None
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# measured RS/AG phase split (calibrate --allgather, schema v3)
# ---------------------------------------------------------------------------


def test_phase_split_measured_and_migrated(tmp_path, mesh8):
    from mgwfbp_tpu.parallel.costmodel import (
        AlphaBeta,
        ProfileFamily,
        SampledCost,
        load_profile,
        refit_from_observations,
        save_profile,
    )
    from mgwfbp_tpu.parallel.solver import (
        cross_step_phase_costs,
        effective_cost_fn,
    )
    from mgwfbp_tpu.profiling import (
        fit_ag_fraction,
        profile_allgather,
        profile_allreduce,
    )

    sizes = (1 << 12, 1 << 14)
    full = profile_allreduce(mesh8, sizes=sizes, warmup=1, iters=2)
    ag = profile_allgather(mesh8, sizes=sizes, warmup=1, iters=2)
    frac = fit_ag_fraction(full, ag)
    assert 0.05 <= frac <= 0.95
    model = SampledCost(
        sizes_bytes=tuple(full.sizes_bytes), times_s=tuple(full.times_s),
        ab=full.model, update_beta=1e-12, ag_fraction=frac,
    )
    # the split must preserve the per-bucket total and realize the
    # measured fraction on the AG leg
    rs_c, ag_c = cross_step_phase_costs(model)
    eff = effective_cost_fn(model, "rs_fwd_ag")
    for n in (1 << 13, 1 << 20):
        assert rs_c(n) + ag_c(n) == pytest.approx(eff(n), rel=1e-12)
        assert ag_c(n) / model.predict(n) == pytest.approx(frac)
    # persisted v3 round trip
    path = str(tmp_path / "p.json")
    save_profile(path, model)
    doc = json.load(open(path))
    assert doc["schema_version"] == 3
    assert load_profile(path).ag_fraction == pytest.approx(frac)
    # v2 (pre-split) file migrates with the historical halved split
    doc.pop("ag_fraction")
    doc["schema_version"] = 2
    json.dump(doc, open(path, "w"))
    old = load_profile(path)
    assert old.ag_fraction == 0.5
    rs_c, ag_c = cross_step_phase_costs(old)
    assert ag_c(1 << 20) == pytest.approx(0.5 * old.predict(1 << 20))
    # unknown future version still rejected
    doc["schema_version"] = 9
    json.dump(doc, open(path, "w"))
    with pytest.raises(ValueError, match="schema_version"):
        load_profile(path)
    # refit keeps the measured split; family interpolation carries it
    refit = refit_from_observations(
        model, [(1e6, 0.01), (2e6, 0.018)], "all_reduce"
    )
    assert refit.ag_fraction == pytest.approx(frac)
    fam = ProfileFamily(entries={
        2: AlphaBeta(1e-5, 1e-10, ag_fraction=0.3),
        8: AlphaBeta(2e-5, 2e-10, ag_fraction=0.7),
    })
    assert fam.at(2).ag_fraction == 0.3
    assert 0.3 < fam.at(4).ag_fraction < 0.7


def test_calibrate_allgather_cli(tmp_path, capsys):
    from mgwfbp_tpu import calibrate

    out = str(tmp_path / "prof.json")
    rc = calibrate.main([
        "--out", out, "--min-log2", "12", "--max-log2", "13",
        "--iters", "2", "--warmup", "1", "--no-gamma", "--no-overlap",
        "--allgather",
    ])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0.05 <= rep["ag_fraction"] <= 0.95
    from mgwfbp_tpu.parallel.costmodel import load_profile

    assert load_profile(out).ag_fraction == pytest.approx(
        rep["ag_fraction"]
    )


# ---------------------------------------------------------------------------
# 2-process straggler alarm (stall@ fault on proc=1)
# ---------------------------------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_STALL_S = 0.8  # one injected stall of the straggler run below
_MIN_EXCESS_S = _STALL_S / 2  # the floor under which nothing may alarm


def test_two_process_straggler_alarm(tmp_path):
    """A SUPERVISED 2-process CPU-mesh group (ephemeral child metrics
    ports) with `stall@` faults on proc=1, pinning the fleet console on
    top of the PR-9 straggler pin (ISSUE 10 acceptance):

      * mid-run, the supervisor's /fleet/metrics merges BOTH children
        under a `process` label and /fleet/status serves the live
        straggler table naming both;
      * the probe-raised straggler alarm is FLEET-VISIBLE (active_alarms
        naming process 1) while the stalls last;
      * fleet.json persists both children's ACTUAL bound (ephemeral)
        ports in Prometheus http_sd format — ports the base+index
        convention could never have guessed;
      * post-hoc, the alarm raised naming process 1 identically in BOTH
        processes' streams and cleared once the stalls passed (the PR-9
        pin, unchanged)."""
    import threading

    from mgwfbp_tpu.runtime.supervisor import Supervisor, default_train_cmd
    from mgwfbp_tpu.telemetry import find_stream_paths

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "MGWFBP_HOST_DEVICES": "4",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        # five consecutive one-shot stalls keep the alarm ACTIVE for 4 s,
        # so the fleet poller below sees it live however slowly a loaded
        # machine lets it poll; the clean steps 8..12 then clear it
        # (hysteresis 1)
        "MGWFBP_FAULT_PLAN": ";".join(
            f"stall@secs={_STALL_S},step={k},proc=1" for k in range(3, 8)
        ),
        "MGWFBP_AGREE_INTERVAL": "1",
        # only the INJECTED excess may raise the alarm: the absolute floor
        # sits at half a stall, far above what a busy neighbour adds to
        # one process's step on a shared CPU (0.05 s was seen to name the
        # healthy process at the default floor of 0.02 s), and the
        # relative band is low enough that a stall still counts when load
        # has made every step slow
        "MGWFBP_STRAGGLER_MIN_EXCESS_S": str(_MIN_EXCESS_S),
        "MGWFBP_STRAGGLER_BAND": "0.1",
        "MGWFBP_DRIFT_HYSTERESIS": "1",
        "MGWFBP_METRICS_PORT": "0",  # ephemeral: port files must resolve
    })
    fleet_port = _free_port()
    sup = Supervisor(
        default_train_cmd([
            "--dnn", "lenet", "--synthetic", "--no-profile-backward",
            "--batch-size", "8", "--num-batches-per-epoch", "12",
            "--max-epochs", "1", "--epochs", "1", "--seed", "7",
            "--logdir", str(tmp_path), "--telemetry",
        ]),
        2,
        env=env,
        log_dir=str(tmp_path / "supervisor"),
        fleet_port=fleet_port,
    )
    rc_box: dict = {}
    runner = threading.Thread(
        target=lambda: rc_box.update(rc=sup.run()), daemon=True
    )
    runner.start()

    def probe(path):
        # the fan-in binds a beat after sup.run() starts; refused
        # connections during that race are "not yet", not failures
        try:
            return _get(fleet_port, path)
        except Exception as e:  # noqa: BLE001 — poll until deadline
            return None, str(e)

    # poll until everything was seen live or the run ends; the deadline
    # only keeps a hung group from hanging the test
    fleet_table = None
    fleet_metrics = None
    fleet_alarm = None
    deadline = time.monotonic() + 290
    while runner.is_alive() and time.monotonic() < deadline and not (
        fleet_table and fleet_metrics and fleet_alarm
    ):
        code, body = probe("/fleet/status")
        if code == 200:
            doc = json.loads(body)
            named = {
                r["process"] for r in doc.get("straggler_table", [])
            }
            if fleet_table is None and named == {0, 1}:
                fleet_table = doc["straggler_table"]
            for a in doc.get("active_alarms", []):
                if a.get("alarm") == "straggler":
                    fleet_alarm = a
        if fleet_metrics is None:
            code, body = probe("/fleet/metrics")
            if code == 200 and all(
                f'mgwfbp_current_step{{process="{i}"}}' in body
                for i in range(2)
            ):
                fleet_metrics = body
        time.sleep(0.05)
    runner.join(timeout=300)
    if runner.is_alive():
        pytest.fail("supervised 2-process straggler run timed out")
    assert rc_box.get("rc") == 0, rc_box
    assert fleet_table is not None, (
        "/fleet/status never served a straggler table naming both "
        "processes"
    )
    assert fleet_metrics is not None, (
        "/fleet/metrics never merged both children under the process "
        "label"
    )
    assert fleet_alarm is not None, (
        "the straggler alarm never became fleet-visible in "
        "/fleet/status active_alarms"
    )
    assert fleet_alarm["slow_process"] == 1, fleet_alarm
    assert fleet_alarm["excess_s"] > _MIN_EXCESS_S, fleet_alarm
    # fleet.json: the children's ACTUAL ephemeral endpoints, http_sd form
    sd = json.load(open(str(tmp_path / "supervisor" / "fleet.json")))
    assert {g["labels"]["process"] for g in sd} == {"0", "1"}
    ports = [int(g["targets"][0].rsplit(":", 1)[1]) for g in sd]
    assert all(p > 0 for p in ports) and len(set(ports)) == 2, sd

    run_dirs = [
        d for d in glob.glob(str(tmp_path / "*"))
        if os.path.isdir(d) and find_stream_paths(d)
    ]
    assert len(run_dirs) == 1
    paths = find_stream_paths(run_dirs[0])
    assert len(paths) == 2
    for path in paths:
        rows = events_of(read_event_set(path), "straggler")
        raised = [r for r in rows if r["active"]]
        assert raised, f"{path}: no straggler alarm raised"
        assert all(r["slow_process"] == 1 for r in raised), raised
        assert raised[0]["excess_s"] > _MIN_EXCESS_S, raised
        assert any(not r["active"] for r in rows), (
            f"{path}: alarm never cleared after the stall passed"
        )
    # both processes agreed on the identical alarm rows
    rows0 = [
        {k: r[k] for k in ("step", "slow_process", "active")}
        for r in events_of(read_event_set(paths[0]), "straggler")
    ]
    rows1 = [
        {k: r[k] for k in ("step", "slow_process", "active")}
        for r in events_of(read_event_set(paths[1]), "straggler")
    ]
    assert rows0 == rows1


# ---------------------------------------------------------------------------
# acceptance: injected calibration error -> drift_alarm -> re-autotune ->
# a measured winner committed and the alarm resolved
# ---------------------------------------------------------------------------

# The run's step times are measured on a CPU mesh that shares its cores
# with whatever else the machine runs, so nothing below compares a measured
# time with a band: the error injected into the cost model (100x) is far
# outside the comm band the test sets (5x either way), which a busy
# neighbour does not reach, and the step-trend channel, which is nothing
# but a band on measured step times, is off.
_INJECTED_ERROR = 100.0
_COMM_BAND = 5.0


def test_drift_alarm_triggers_reautotune_and_recovers(
    tmp_path, monkeypatch,
):
    from mgwfbp_tpu.parallel.costmodel import AlphaBeta, save_profile
    from mgwfbp_tpu.parallel.mesh import MeshSpec, make_mesh
    from mgwfbp_tpu.profiling import profile_allreduce
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "2")
    monkeypatch.setenv("MGWFBP_DRIFT_HYSTERESIS", "1")
    monkeypatch.setenv("MGWFBP_DRIFT_WINDOW", "2")
    monkeypatch.setenv("MGWFBP_DRIFT_REAUTOTUNE", "1")
    monkeypatch.setenv("MGWFBP_DRIFT_BAND", str(_COMM_BAND))
    monkeypatch.setenv("MGWFBP_DRIFT_TREND_BAND", "0")

    mesh = make_mesh(MeshSpec(data=8, seq=1))
    prof = profile_allreduce(
        mesh, sizes=(1 << 12, 1 << 15, 1 << 18), warmup=1, iters=3
    )
    truth = AlphaBeta(
        alpha=prof.model.alpha, beta=prof.model.beta, overlap=0.0
    )
    bad = AlphaBeta(
        alpha=truth.alpha * _INJECTED_ERROR,
        beta=truth.beta * _INJECTED_ERROR, overlap=0.0,
    )
    save_profile(str(tmp_path / "truth.json"), truth)
    cfg = make_config(
        "lenet", lr=0.01, max_epochs=2, logdir=str(tmp_path), seed=3,
        batch_size=8, num_batches_per_epoch=10,
        comm_profile=str(tmp_path / "truth.json"),
        autotune_steps=2, autotune_candidates=4,
        schedule_cache=str(tmp_path / "cache"), telemetry=True,
    )
    # measured tb: both the drift estimator and the step-delta refit are
    # gated on a real backward profile
    t = Trainer(cfg, synthetic_data=True)
    t.train_epoch(0)  # healthy baseline under the truthful model
    assert t._drift_detector is not None
    assert not t._drift_detector.active
    t.cost_model = bad  # inject the calibration error mid-run
    t.train_epoch(1)

    recs = read_event_set(glob.glob(str(tmp_path / "*/telemetry.jsonl"))[0])
    alarms = events_of(recs, "drift_alarm")
    raised = [a for a in alarms if a["active"]]
    assert raised, "the injected calibration error raised no drift_alarm"
    assert {a["kind"] for a in alarms} == {"comm_residual"}, alarms
    # the drift factor is the injected error: beyond the band on the side
    # of a model that predicts too much
    assert raised[0]["band"] == _COMM_BAND
    assert raised[0]["residual"] > _COMM_BAND, raised[0]
    # ... and triggered a re-autotune that committed a measured winner
    commits = events_of(recs, "autotune_commit")
    assert commits and commits[-1]["source"] == "race", commits
    rep = t.autotune_report
    assert rep is not None and rep["source"] == "race"
    # recovery: what was committed is the argmin of the race's own
    # same-phase timings, and the re-autotune resolved the alarm it
    # answered (the clear row follows the raise row in the stream)
    timed = [
        e["measured_step_s"] for e in rep["race"]
        if e["measured_step_s"] is not None
    ]
    assert timed and rep["measured_step_s"] == min(timed), rep["race"]
    first_raise = alarms.index(raised[0])
    assert any(not a["active"] for a in alarms[first_raise + 1:]), alarms
    t.close()
