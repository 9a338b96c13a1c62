"""Fleet console + on-demand deep profiling (ISSUE 10): the registry's
labeled render and its parse inverse, the fleet fan-in (scrape ->
straggler table -> /fleet endpoints, hard-timeout unreachable handling),
the supervisor's port-file/fleet.json resolution (covering the
MGWFBP_METRICS_PORT=0 ephemeral case), MetricsAggregator thread-safety
under concurrent observe/render load, rotated-stream replay equivalence
with the fleet label attached, the HLO-join trace attribution, the
/profile endpoint state machine, and the pinned live /profile window on
a real lenet CPU-mesh run (per-group trace-attributed table + the drift
detector's mid-run switch to the absolute per-group residual channel)."""

import glob
import json
import os
import re
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.telemetry import (
    EventWriter,
    MetricsAggregator,
    TelemetryServer,
    events_of,
    read_event_set,
)
from mgwfbp_tpu.telemetry.export import (
    parse_metrics_text,
    render_labeled_metrics,
    render_metrics,
)
from mgwfbp_tpu.telemetry.fleet import (
    ChildScrape,
    FleetServer,
    fleet_status,
    render_fleet_metrics,
    scrape_fleet,
    straggler_table,
    write_fleet_sd,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get(port: int, path: str, timeout: float = 10.0):
    """(status, body) — non-2xx is an answer, not an error."""
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _child(process, values=None, status=None, reachable=True):
    c = ChildScrape(process=process, host="127.0.0.1", port=1)
    if reachable:
        c.status = status if status is not None else {
            "healthy": True, "active_alarms": [],
        }
        c.values = values or {}
    else:
        c.error = "refused"
    return c


# ---------------------------------------------------------------------------
# registry: labeled render + parse inverse
# ---------------------------------------------------------------------------


def test_parse_metrics_text_inverts_render():
    values = {
        "mgwfbp_steps_total": 12,
        "mgwfbp_step_seconds": 0.0625,
        "mgwfbp_overlap_efficiency": 0.75,
        "mgwfbp_current_step": 12,
    }
    assert parse_metrics_text(render_metrics(values)) == values
    with pytest.raises(ValueError, match="not in telemetry.export"):
        parse_metrics_text("mgwfbp_bogus_metric 1\n")
    with pytest.raises(ValueError, match="unparseable"):
        parse_metrics_text("mgwfbp_steps_total\n")


def test_render_labeled_metrics_merges_under_process_label():
    series = {
        "0": {"mgwfbp_steps_total": 5, "mgwfbp_step_seconds": 0.1},
        "1": {"mgwfbp_steps_total": 7},
    }
    text = render_labeled_metrics(
        series, extra={"mgwfbp_fleet_processes": 2},
    )
    assert 'mgwfbp_steps_total{process="0"} 5' in text
    assert 'mgwfbp_steps_total{process="1"} 7' in text
    assert 'mgwfbp_step_seconds{process="0"} 0.1' in text
    assert 'mgwfbp_step_seconds{process="1"}' not in text
    assert "mgwfbp_fleet_processes 2" in text
    # HELP/TYPE once per metric, not per series
    assert text.count("# HELP mgwfbp_steps_total") == 1
    # one registry: stray names rejected exactly like render_metrics
    with pytest.raises(ValueError, match="not in telemetry.export"):
        render_labeled_metrics({"0": {"mgwfbp_bogus": 1}})
    with pytest.raises(ValueError, match="not in telemetry.export"):
        render_labeled_metrics({}, extra={"mgwfbp_bogus": 1})


def test_rotated_replay_equivalence_with_fleet_label(tmp_path):
    """A size-rotated stream replays into the aggregator exactly like the
    un-rotated one — including when the values are re-rendered under the
    fleet's process label (satellite: the fan-in path reuses the same
    aggregator/registry, so rotation must be invisible there too)."""
    def stream(path, max_bytes):
        w = EventWriter(path, run={"model": "m"}, max_bytes=max_bytes)
        for i in range(40):
            w.emit("step", step=i + 1, epoch=0, start_s=i * 0.1, dur_s=0.1)
        w.emit("checkpoint", epoch=0, iteration=40, mid_epoch=False)
        w.close()
        agg = MetricsAggregator()
        agg.replay(read_event_set(path))
        return agg.values()

    rotated = stream(str(tmp_path / "rot" / "telemetry.jsonl"), 400)
    assert glob.glob(str(tmp_path / "rot" / "telemetry.jsonl.*"))
    plain = stream(str(tmp_path / "plain" / "telemetry.jsonl"), 0)
    assert rotated == plain
    assert render_labeled_metrics(
        {"3": rotated}, extra={"mgwfbp_fleet_processes": 1},
    ) == render_labeled_metrics(
        {"3": plain}, extra={"mgwfbp_fleet_processes": 1},
    )


# ---------------------------------------------------------------------------
# fleet synthesis: straggler table, alarms, status doc, http_sd sidecar
# ---------------------------------------------------------------------------


def test_straggler_table_mean_excess_vs_fastest():
    children = [
        _child(0, {"mgwfbp_step_seconds": 0.10, "mgwfbp_current_step": 9,
                   "mgwfbp_steps_total": 9}),
        _child(1, {"mgwfbp_step_seconds": 0.16, "mgwfbp_current_step": 9,
                   "mgwfbp_steps_total": 9}),
        _child(2, reachable=False),
    ]
    rows = straggler_table(children)
    assert [r["process"] for r in rows] == [0, 1]
    assert rows[0]["excess_s"] == pytest.approx(0.0)
    assert rows[1]["excess_s"] == pytest.approx(0.06)
    assert rows[1]["excess_pct"] == pytest.approx(60.0)
    doc = fleet_status(children, meta={"incarnation": 2})
    assert doc["reachable"] == 2 and doc["incarnation"] == 2
    assert doc["slowest_process"]["process"] == 1
    assert not doc["healthy"]  # an unreachable child is not healthy
    assert doc["unreachable"][0]["process"] == 2


def test_fleet_active_alarms_union_and_dedup():
    alarm = {"alarm": "straggler", "slow_process": 1, "excess_s": 0.5,
             "active": True}
    drift = {"alarm": "drift", "kind": "comm_residual", "group": 0,
             "residual": 5.0, "active": True}
    children = [
        _child(0, status={"healthy": True, "active_alarms": [alarm]}),
        _child(1, status={"healthy": True,
                          "active_alarms": [alarm, drift]}),
    ]
    doc = fleet_status(children)
    alarms = doc["active_alarms"]
    # the group-agreed straggler alarm dedups to ONE row listing both
    # reporting processes; the local drift alarm names its process only
    stragglers = [a for a in alarms if a.get("alarm") == "straggler"]
    drifts = [a for a in alarms if a.get("alarm") == "drift"]
    assert len(stragglers) == 1 and stragglers[0]["processes"] == [0, 1]
    assert stragglers[0]["slow_process"] == 1
    assert len(drifts) == 1 and drifts[0]["processes"] == [1]


def test_write_fleet_sd_http_sd_format(tmp_path):
    path = str(tmp_path / "fleet.json")
    doc = write_fleet_sd(
        path, {0: ("127.0.0.1", 9100), 1: ("127.0.0.1", 45001)},
    )
    assert json.load(open(path)) == doc
    assert doc == [
        {"targets": ["127.0.0.1:9100"],
         "labels": {"job": "mgwfbp", "process": "0"}},
        {"targets": ["127.0.0.1:45001"],
         "labels": {"job": "mgwfbp", "process": "1"}},
    ]


# ---------------------------------------------------------------------------
# fleet fan-in over real child servers (+ the hard-timeout contract)
# ---------------------------------------------------------------------------


def _live_child(step_s: float, steps: int = 5) -> MetricsAggregator:
    agg = MetricsAggregator(run={"model": "lenet"})
    for i in range(steps):
        agg.observe("step", {"step": i + 1, "epoch": 0,
                             "start_s": i * step_s, "dur_s": step_s})
    return agg


def test_fleet_server_fans_in_child_servers():
    a0, a1 = _live_child(0.10), _live_child(0.20)
    s0 = TelemetryServer(a0, 0, host="127.0.0.1")
    s1 = TelemetryServer(a1, 0, host="127.0.0.1")
    fleet = FleetServer(
        lambda: {0: ("127.0.0.1", s0.port), 1: ("127.0.0.1", s1.port)},
        port=0,
        meta_provider=lambda: {"incarnation": 0},
    )
    try:
        code, body = _get(fleet.port, "/fleet/metrics")
        assert code == 200
        assert 'mgwfbp_steps_total{process="0"} 5' in body
        assert 'mgwfbp_steps_total{process="1"} 5' in body
        assert "mgwfbp_fleet_processes 2" in body
        assert "mgwfbp_fleet_straggler_excess_seconds 0.1" in body
        code, body = _get(fleet.port, "/fleet/status")
        assert code == 200
        doc = json.loads(body)
        assert doc["incarnation"] == 0 and doc["healthy"]
        assert doc["slowest_process"]["process"] == 1
        rows = {r["process"]: r for r in doc["straggler_table"]}
        assert rows[1]["excess_s"] == pytest.approx(0.1, rel=1e-6)
        # one child dies -> reported unreachable, fan-in stays up
        s1.close()
        code, body = _get(fleet.port, "/fleet/status")
        doc = json.loads(body)
        assert code == 200 and not doc["healthy"]
        assert [u["process"] for u in doc["unreachable"]] == [1]
        code, body = _get(fleet.port, "/fleet/metrics")
        assert 'mgwfbp_steps_total{process="0"} 5' in body
        assert "mgwfbp_fleet_unreachable 1" in body
    finally:
        fleet.close()
        s0.close()
        s1.close()


def test_fleet_scrape_hard_timeout_on_wedged_child():
    """A child that ACCEPTS but never answers (a wedged process with a
    live listener) must cost one bounded timeout and be reported
    unreachable — a fan-in hang would wedge the check.sh smoke."""
    wedge = socket.socket()
    wedge.bind(("127.0.0.1", 0))
    wedge.listen(1)
    port = wedge.getsockname()[1]
    a0 = _live_child(0.1)
    s0 = TelemetryServer(a0, 0, host="127.0.0.1")
    try:
        t0 = time.monotonic()
        children = scrape_fleet(
            {0: ("127.0.0.1", s0.port), 1: ("127.0.0.1", port)},
            timeout_s=0.5,
        )
        wall = time.monotonic() - t0
        assert wall < 5.0, f"fan-in took {wall:.1f}s against a wedge"
        assert children[0].reachable
        assert not children[1].reachable and children[1].error
        doc = fleet_status(children)
        assert [u["process"] for u in doc["unreachable"]] == [1]
        text = render_fleet_metrics(children)
        assert "mgwfbp_fleet_unreachable 1" in text
    finally:
        s0.close()
        wedge.close()


def test_telemetry_report_live_mode(capsys):
    """`tools/telemetry_report.py --live URL` renders the live report
    from /status + /metrics (per-process URL) or /fleet/status (fan-in
    URL) instead of JSONL files (satellite)."""
    import telemetry_report  # tools/ is on sys.path (conftest)

    agg = _live_child(0.1, steps=7)
    srv = TelemetryServer(agg, 0, host="127.0.0.1")
    fleet = FleetServer(
        lambda: {0: ("127.0.0.1", srv.port)}, port=0,
    )
    try:
        rc = telemetry_report.main(["--live", f"127.0.0.1:{srv.port}"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "steps: 7 recorded" in out, out
        assert "active alarms: none" in out
        rc = telemetry_report.main(
            ["--live", f"http://127.0.0.1:{fleet.port}"]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "live straggler table" in out, out
        # a dead URL is an error, not a traceback
        dead = _free_port()
        assert telemetry_report.main(
            ["--live", f"127.0.0.1:{dead}"]
        ) == 2
    finally:
        fleet.close()
        srv.close()


# ---------------------------------------------------------------------------
# supervisor: port files resolve ACTUAL (ephemeral) ports; fleet.json
# ---------------------------------------------------------------------------


def test_supervisor_resolves_ephemeral_ports_via_port_files(
    tmp_path, monkeypatch,
):
    from mgwfbp_tpu.runtime.supervisor import Supervisor
    from mgwfbp_tpu.telemetry.serve import start_metrics_server

    sup = Supervisor(
        ["true"], 2,
        env={"MGWFBP_METRICS_PORT": "0"},  # ephemeral: base+idx is WRONG
        log_dir=str(tmp_path),
    )
    # base=0 resolves no convention ports at all
    assert sup._metrics_enabled()
    assert sup._metrics_base_port() is None
    assert sup._child_targets() == {}
    # children bind ephemeral ports and persist them through the sidecar
    # env the supervisor exports (the real child path: start_metrics_server)
    servers = []
    for idx in range(2):
        env = sup._child_env(idx, 1234)
        monkeypatch.setenv(
            "MGWFBP_METRICS_PORT_FILE", env["MGWFBP_METRICS_PORT_FILE"]
        )
        agg = _live_child(0.1, steps=idx + 1)
        servers.append(start_metrics_server(agg, 0, idx))
    try:
        targets = sup._child_targets()
        assert targets == {
            i: ("127.0.0.1", servers[i].port) for i in range(2)
        }
        # the resolved (NOT guessed) port answers /status
        st = sup._child_status(1)
        assert st is not None and st["step"] == 2, st
        # fleet.json lands in http_sd format with the ACTUAL ports
        sup._refresh_fleet()
        sd = json.load(open(os.path.join(str(tmp_path), "fleet.json")))
        assert {g["labels"]["process"] for g in sd} == {"0", "1"}
        assert sorted(t for g in sd for t in g["targets"]) == sorted(
            f"127.0.0.1:{s.port}" for s in servers
        )
    finally:
        for s in servers:
            s.close()


def test_supervisor_base_port_fallback_without_port_files(tmp_path):
    from mgwfbp_tpu.runtime.supervisor import Supervisor

    sup = Supervisor(
        ["true"], 2, env={"MGWFBP_METRICS_PORT": "9100"},
        log_dir=str(tmp_path),
    )
    # no port files yet: the base+index convention stands in
    assert sup._child_targets() == {
        0: ("127.0.0.1", 9100), 1: ("127.0.0.1", 9101),
    }
    assert Supervisor(["true"], 1, env={})._child_targets() == {}


@pytest.mark.parametrize("case", ["base_plus_index", "ephemeral", "port_file"])
def test_one_port_rule_for_every_process(case, tmp_path):
    """With one kind of process under the supervisor the listen port is
    `base + process_index` (0 stays 0: ephemeral) and child i's sidecar
    is `metrics_port.p<i>.json` — the path its environment carries."""
    from mgwfbp_tpu.runtime.supervisor import Supervisor
    from mgwfbp_tpu.telemetry.serve import resolve_metrics_port

    if case == "base_plus_index":
        assert resolve_metrics_port(9100, 3) == 9103
    elif case == "ephemeral":
        assert resolve_metrics_port(0, 5) == 0
    else:
        sup = Supervisor(
            ["true"], 4, env={"MGWFBP_METRICS_PORT": "0"},
            log_dir=str(tmp_path),
        )
        path = sup._port_file(3)
        assert path == os.path.join(str(tmp_path), "metrics_port.p3.json")
        assert sup._child_env(3, 1234)["MGWFBP_METRICS_PORT_FILE"] == path


# ---------------------------------------------------------------------------
# MetricsAggregator thread-safety: observe() tee vs render race under load
# ---------------------------------------------------------------------------


def test_aggregator_thread_safety_under_load():
    """Concurrent writers (the EventWriter tee + watchdog threads) racing
    concurrent readers (HTTP handler threads rendering /metrics and
    /status) must neither corrupt counts nor raise — every render along
    the way passes registry validation, and the final counters are
    exact."""
    agg = MetricsAggregator(run={"model": "x"})
    writers, readers = 4, 3
    per_writer = 500
    start = threading.Barrier(writers + readers)
    errors: list = []

    def write(widx: int):
        try:
            start.wait(timeout=10)
            for i in range(per_writer):
                agg.observe("step", {
                    "step": widx * per_writer + i + 1, "epoch": 0,
                    "start_s": 0.0, "dur_s": 0.01,
                })
                agg.observe("drift_alarm", {
                    "kind": "comm_residual", "step": i, "residual": 5.0,
                    "band": 3.0, "active": i % 2 == 0, "group": widx,
                })
        except Exception as e:  # noqa: BLE001 — surfaced via `errors`
            errors.append(e)

    stop = threading.Event()

    def read():
        try:
            start.wait(timeout=10)
            while not stop.is_set():
                text = render_metrics(agg.values())
                assert text.startswith("# HELP")
                st = agg.status()
                json.dumps(st)  # the /status doc must always serialize
                agg.health()
        except Exception as e:  # noqa: BLE001 — surfaced via `errors`
            errors.append(e)

    threads = [
        threading.Thread(target=write, args=(w,)) for w in range(writers)
    ] + [threading.Thread(target=read) for _ in range(readers)]
    for t in threads:
        t.start()
    for t in threads[:writers]:
        t.join(timeout=60)
    stop.set()
    for t in threads[writers:]:
        t.join(timeout=10)
    assert not errors, errors
    v = agg.values()
    assert v["mgwfbp_steps_total"] == writers * per_writer
    assert v["mgwfbp_drift_alarms_total"] == writers * per_writer // 2
    # render and the replay-equivalent file dump still agree
    assert render_metrics(v) == render_metrics(agg.values())


# ---------------------------------------------------------------------------
# HLO-join attribution (the /profile CPU-mesh path) + /profile endpoint
# ---------------------------------------------------------------------------


def test_hlo_collective_scope_map_and_join():
    from mgwfbp_tpu.profiling import (
        _group_times_from_hlo_join,
        hlo_collective_scope_map,
    )

    hlo = "\n".join([
        '%all-reduce.2 = f32[8]{0} all-reduce(%p), metadata='
        '{op_name="jit(f)/jit(main)/mgwfbp_group0000/psum"}',
        '%all-reduce.3 = f32[8]{0} all-reduce(%q), metadata='
        '{op_name="jit(f)/jit(main)/mgwfbp_group0001/psum"}',
        '%fusion.1 = f32[8]{0} fusion(%x), metadata='
        '{op_name="jit(f)/jit(main)/other/add"}',
    ])
    assert hlo_collective_scope_map(hlo) == {
        "all-reduce.2": "mgwfbp_group0000",
        "all-reduce.3": "mgwfbp_group0001",
    }
    # 2 devices x 2 steps per instruction: the MEAN event duration is the
    # per-device per-step time
    rows = (
        [("all-reduce.2", 100.0)] * 4
        + [("all-reduce.3", 50.0)] * 4
        + [("fusion.1", 999.0)] * 4
    )
    out = _group_times_from_hlo_join(rows, 2, hlo)
    assert out == pytest.approx([100e-6, 50e-6])
    # a group with no attributed instruction -> None (partial is worse
    # than none, same contract as the scope path)
    assert _group_times_from_hlo_join(rows[:4], 2, hlo) is None
    assert _group_times_from_hlo_join(rows, 2, "no metadata here") is None


def test_profile_endpoint_state_machine():
    agg = MetricsAggregator()
    srv = TelemetryServer(agg, 0, host="127.0.0.1")
    try:
        # no live trainer attached: arming is refused
        code, body = _get(srv.port, "/profile?steps=3")
        assert code == 409 and "no live trainer" in body
        agg.enable_profile()
        code, body = _get(srv.port, "/profile?steps=abc")
        assert code == 400
        code, body = _get(srv.port, "/profile?steps=3")
        assert code == 200 and json.loads(body)["armed"]
        # double-arm is refused while armed/running
        code, body = _get(srv.port, "/profile?steps=5")
        assert code == 409
        assert agg.take_profile_request() == 3
        assert agg.take_profile_request() is None  # consumed
        agg.set_profile_result({"steps": 3, "attribution": "trace"})
        code, body = _get(srv.port, "/profile")
        doc = json.loads(body)
        assert doc["state"] == "done"
        assert doc["result"]["attribution"] == "trace"
        # /status carries the same state
        code, body = _get(srv.port, "/status")
        assert json.loads(body)["profile"]["state"] == "done"
        # requested steps ride the PROFILE_MAX_STEPS ceiling
        code, body = _get(srv.port, "/profile?steps=10000")
        assert code == 200 and json.loads(body)["steps"] == 50
        agg.fail_profile("boom")
        assert agg.profile_status()["state"] == "failed"
    finally:
        srv.close()


def test_port_file_written_with_actual_bound_port(tmp_path, monkeypatch):
    from mgwfbp_tpu.telemetry.serve import start_metrics_server

    path = str(tmp_path / "metrics_port.p0.json")
    monkeypatch.setenv("MGWFBP_METRICS_PORT_FILE", path)
    agg = MetricsAggregator()
    srv = start_metrics_server(agg, 0, 0)
    try:
        doc = json.load(open(path))
        assert doc["port"] == srv.port and doc["port"] != 0
        assert doc["process"] == 0 and doc["host"] == "127.0.0.1"
    finally:
        srv.close()


def test_port_file_never_observed_truncated(tmp_path):
    """Pin the sidecar's atomicity contract (ISSUE 16 satellite): the
    supervisor's fleet fan-in polls this file while the training process
    (re)writes it, so a reader racing the writer must see either a
    COMPLETE old doc or a COMPLETE new doc — never a truncated or mixed
    one. write_port_file commits via tmp + os.replace; this test hammers
    the write from a thread while reading in a tight loop and fails on
    any unparseable or partial observation (which an in-place open(
    path, 'w') + json.dump would produce within a few hundred rounds)."""
    import threading

    from mgwfbp_tpu.telemetry.serve import write_port_file

    class _Srv:  # the two attributes write_port_file reads
        host = "127.0.0.1"
        port = 0

    path = str(tmp_path / "metrics_port.p0.json")
    stop = threading.Event()

    def hammer():
        srv = _Srv()
        port = 1024
        while not stop.is_set():
            srv.port = port = 1024 + (port - 1023) % 60000
            write_port_file(path, srv, 0)

    w = threading.Thread(target=hammer, daemon=True)
    w.start()
    try:
        seen = 0
        bad = []
        while seen < 2000:
            try:
                with open(path) as f:
                    raw = f.read()
            except FileNotFoundError:  # before the first commit
                continue
            seen += 1
            try:
                doc = json.loads(raw)
            except ValueError:
                bad.append(raw)
                break
            # every committed doc is complete: all keys, coherent values
            missing = {"process", "host", "bound_host", "port",
                       "pid"} - set(doc)
            if missing:
                bad.append(f"missing {missing}: {raw}")
                break
            if not (1024 <= doc["port"] < 61024):
                bad.append(raw)
                break
        assert not bad, f"reader observed a torn sidecar: {bad[0]!r}"
    finally:
        stop.set()
        w.join(timeout=5)
    # the tmp staging names never accumulate (os.replace consumed them)
    leftovers = [p for p in os.listdir(tmp_path) if ".tmp." in p]
    assert leftovers == []


# ---------------------------------------------------------------------------
# pinned: live /profile window on a real lenet CPU-mesh run
# ---------------------------------------------------------------------------


def test_profile_window_live_lenet(tmp_path, monkeypatch):
    """/profile?steps=N on a LIVE lenet CPU-mesh run: the window traces N
    real carried steps, writes the Chrome-trace slice, returns a
    per-merge-group trace-attributed device-time table (via the HLO join
    — CPU traces drop the name stack), and switches the drift detector
    to the ABSOLUTE per-group residual channel mid-run, without
    restarting the job. The zero-sync guard (test_observability) pins
    the disarmed path separately."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "3")
    cfg = make_config(
        "lenet", lr=0.01, max_epochs=1, logdir=str(tmp_path), seed=3,
        batch_size=8, num_batches_per_epoch=6, metrics_port=0,
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    port = t._metrics_server.port
    # the job is live; nothing profiled yet — the drift comm channel has
    # no per-group measurement to go absolute on
    assert t._measured_group_times is None
    code, body = _get(port, "/profile?steps=2")
    assert code == 200 and json.loads(body)["armed"], body
    t.fit(1)

    code, body = _get(port, "/profile")
    assert code == 200
    doc = json.loads(body)
    assert doc["state"] == "done", doc
    res = doc["result"]
    num_groups = t.reducer.layout.num_groups
    assert num_groups >= 2  # lenet under the mgwfbp policy merges
    assert res["attribution"] == "trace", res
    assert len(res["groups"]) == num_groups
    for row in res["groups"]:
        assert row["device_s"] > 0.0
        assert row["nbytes"] > 0
        assert row["predicted_s"] > 0.0
    # the Chrome-trace slice landed next to the run's logs
    assert res["trace_dir"] and os.path.isdir(res["trace_dir"])
    assert glob.glob(
        os.path.join(res["trace_dir"], "plugins", "profile", "*", "*")
    ), "no profiler artifacts in the trace dir"
    # drift detector: the window installed the per-group measurement, so
    # the comm channel now checks each group ABSOLUTELY (measured_s), not
    # the baseline-relative aggregate — mid-run, same process
    assert t._measured_group_times == [
        r["device_s"] for r in res["groups"]
    ]
    calls: list = []
    det = t._drift_detector
    assert det is not None
    real = det.observe_comm

    def spy(predicted_s, measured_s=None, measured_total_s=None):
        calls.append((list(predicted_s), measured_s, measured_total_s))
        return real(
            predicted_s, measured_s=measured_s,
            measured_total_s=measured_total_s,
        )

    monkeypatch.setattr(det, "observe_comm", spy)
    t._observe_drift_window(0.05)
    assert calls, "drift window never consulted the comm channel"
    _, measured_s, measured_total_s = calls[-1]
    assert measured_s is not None and len(measured_s) == num_groups
    assert measured_total_s is None
    # the stream carries the profile event (and the counter ticked)
    recs = read_event_set(
        glob.glob(str(tmp_path / "*/telemetry.jsonl"))[0]
    )
    prof = events_of(recs, "profile")
    assert len(prof) == 1 and prof[0]["attribution"] == "trace"
    assert prof[0]["steps"] == 2
    assert len(prof[0]["device_s"]) == num_groups
    code, body = _get(port, "/metrics")
    assert "mgwfbp_profile_windows_total 1" in body


def test_profile_window_writes_the_step_by_scope(tmp_path, monkeypatch):
    """ISSUE 49: the same window reduces its trace by the step's map
    (`profiling.split_trace`) and writes ONE `step_scopes` record: `groups`
    are what `trace_group_times` returned for the window before (the HLO
    join's mean event of each instruction under the group's scope), the
    exchange holds the groups' all-reduces and the metrics' own, the parts
    add up, and `tools/telemetry_report.py` prints the table."""
    import telemetry_report
    from mgwfbp_tpu import profiling
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "3")
    cfg = make_config(
        "lenet", lr=0.01, max_epochs=1, logdir=str(tmp_path), seed=3,
        batch_size=8, num_batches_per_epoch=6, metrics_port=0,
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    try:
        code, body = _get(t._metrics_server.port, "/profile?steps=2")
        assert code == 200 and json.loads(body)["armed"], body
        t.train_epoch(0)
        res = json.loads(_get(t._metrics_server.port, "/profile")[1])["result"]
    finally:
        t.close()
    num_groups = t.reducer.layout.num_groups
    recs = read_event_set(
        glob.glob(str(tmp_path / "*/telemetry.jsonl"))[0]
    )
    (scopes,) = events_of(recs, "step_scopes")
    (prof,) = events_of(recs, "profile")
    assert scopes["steps"] == 2 and scopes["step"] == prof["step"]
    groups_s = [ms * 1e-3 for ms in scopes["groups"]]
    assert groups_s == pytest.approx(prof["device_s"])
    assert groups_s == pytest.approx(
        [row["device_s"] for row in res["groups"]])
    # ... and what the join of the same trace gave before this record
    step_map = profiling.step_map()
    text = profiling._step["jitted"].lower(
        *profiling._step["args"]).compile().as_text()
    before = profiling._group_times_from_hlo_join(
        profiling._trace_events(res["trace_dir"]), num_groups, text)
    assert groups_s == pytest.approx(before)
    exchange = scopes["exchange"]
    assert exchange["calls"] == num_groups + 1  # and the metrics' own
    assert exchange["calls"] == profiling.collective_counts(
        step_map.instructions)["collectives"]
    assert 0 < exchange["wait_ms"] <= exchange["device_ms"]
    assert exchange["device_ms"] >= sum(scopes["groups"])
    assert scopes["total_ms"] == pytest.approx(
        exchange["device_ms"] + sum(map(sum, scopes["scopes"].values())))
    assert len(scopes["top"]) == 25
    assert scopes["layers"]["optimizer"] == profiling.UPDATE_LAYER
    report = telemetry_report.format_report(recs)
    assert "step by scope (profile window of 2 step(s) to step" in report
    assert f"in {num_groups + 1} collective(s) a step; by group" in report
    assert re.search(r"\(model, no scope\) +- +\d", report)
    t.close()
