"""Training-health telemetry + anomaly-triggered flight recorder
(ISSUE 12): health-detector units on synthetic streams (loss spike /
grad explosion / plateau / compression trend, each raising AND clearing
through the two-edge hysteresis), flight-recorder ring bounds + debounce
+ bundle cap, atomic postmortem-bundle round trips, the aggregator's
health gauges + /postmortems endpoint, the zero-sync pin with health
stats AND the recorder enabled, jaxpr rule SCH010 (stats add no
collectives) with mutation coverage, the per-link refit pin (DCN-only
injected drift refits the DCN leg alone from trace-separated
observations — ROADMAP hier follow-up b), and the pinned end-to-end:
deterministic ``nan@step`` fault -> ``health_alarm`` raised with
hysteresis -> postmortem bundle on disk naming the bad step ->
/postmortems listing it."""

import glob
import json
import os
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.telemetry import (
    EventWriter,
    FlightRecorder,
    HealthConfig,
    HealthDetector,
    MetricsAggregator,
    TelemetryServer,
    events_of,
    list_bundles,
    read_bundle,
    read_event_set,
    tee_observers,
)


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _cfg(**kw) -> HealthConfig:
    """Config with every channel off except what the test enables."""
    base = dict(
        spike_band=0.0, explosion_band=0.0, plateau_window=0,
        compression_band=0.0, baseline_window=2, hysteresis=1,
        ewma_alpha=1.0,
    )
    base.update(kw)
    return HealthConfig(**base)


# ---------------------------------------------------------------------------
# detector units (synthetic streams)
# ---------------------------------------------------------------------------


def test_loss_spike_raises_and_clears_with_hysteresis():
    det = HealthDetector(_cfg(spike_band=2.0, hysteresis=2))
    out = []
    for loss in [1.0, 1.0, 1.0]:
        out += det.observe(loss, 1.0)
    assert out == []
    out += det.observe(5.0, 1.0)  # 1st exceedance: held by hysteresis
    assert out == []
    out += det.observe(5.0, 1.0)  # 2nd: raise edge
    assert [(a.kind, a.active) for a in out] == [("loss_spike", True)]
    assert det.active
    out2 = []
    out2 += det.observe(1.0, 1.0)
    out2 += det.observe(1.0, 1.0)  # 2 in-band: clear edge
    assert [(a.kind, a.active) for a in out2] == [("loss_spike", False)]
    assert not det.active


def test_loss_spike_nonfinite_always_exceeds():
    """NaN > x is False — the detector must special-case non-finite
    losses or the WORST failure mode would never alarm."""
    det = HealthDetector(_cfg(spike_band=2.0, hysteresis=1))
    det.observe(1.0, 1.0)  # seeds the EWMA
    out = det.observe(float("nan"), 1.0)
    assert [(a.kind, a.active) for a in out] == [("loss_spike", True)]
    assert out[0].value == float("inf")


def test_spike_does_not_poison_its_own_baseline():
    """The EWMA tracks the HEALTHY trend: a sustained spike must keep
    alarming, not teach the baseline that spikes are normal."""
    det = HealthDetector(_cfg(spike_band=2.0, hysteresis=1))
    det.observe(1.0, 1.0)
    out = det.observe(10.0, 1.0)
    assert out and out[0].active
    # ewma stayed ~1.0, so a LATER equal spike still measures ~10x
    det2 = HealthDetector(_cfg(spike_band=2.0, hysteresis=1))
    det2.observe(1.0, 1.0)
    det2.observe(10.0, 1.0)
    out2 = det2.observe(10.0, 1.0)
    assert out2 == []  # no new edge — but the ratio is still out of band
    assert det2.active


def test_grad_explosion_band():
    det = HealthDetector(_cfg(explosion_band=3.0, hysteresis=1))
    det.observe(1.0, 1.0)
    det.observe(1.0, 1.1)  # baseline freezes at ~1.05
    out = det.observe(1.0, 5.0)
    assert [(a.kind, a.active) for a in out] == [("grad_explosion", True)]
    assert out[0].value == pytest.approx(5.0 / 1.05, rel=1e-6)
    out = det.observe(1.0, 1.0)
    assert [(a.kind, a.active) for a in out] == [("grad_explosion", False)]


def test_grad_explosion_prebaseline_nan_raises_and_clears():
    """A NaN norm BEFORE the baseline froze still alarms (a NaN-wedged
    run never produces a baseline), and later finite norms clear it."""
    det = HealthDetector(_cfg(explosion_band=3.0, hysteresis=1))
    out = det.observe(1.0, float("nan"))
    assert [(a.kind, a.active) for a in out] == [("grad_explosion", True)]
    out = det.observe(1.0, 1.0)
    assert [(a.kind, a.active) for a in out] == [("grad_explosion", False)]


def test_plateau_window_and_recovery():
    det = HealthDetector(_cfg(plateau_window=3, hysteresis=1))
    out = []
    for loss in [1.0, 0.9, 0.9, 0.9]:
        out += det.observe(loss, 1.0)
    assert out == []  # 0.9 improved once; 2 stagnant observations so far
    out += det.observe(0.9, 1.0)  # 3rd stagnant -> raise
    assert [(a.kind, a.active) for a in out] == [("plateau", True)]
    out2 = det.observe(0.5, 1.0)  # real improvement clears
    assert [(a.kind, a.active) for a in out2] == [("plateau", False)]


def test_compression_error_trend_band():
    det = HealthDetector(_cfg(compression_band=1.5, hysteresis=1))
    assert det.observe(1.0, 1.0, compression_errors=[0.1, 0.05]) == []
    assert det.observe(1.0, 1.0, compression_errors=[0.1]) == []
    out = det.observe(1.0, 1.0, compression_errors=[0.05, 0.3])
    assert [(a.kind, a.active) for a in out] == [
        ("compression_error", True)
    ]
    assert out[0].value == pytest.approx(3.0, rel=1e-6)
    out = det.observe(1.0, 1.0, compression_errors=[0.1])
    assert [(a.kind, a.active) for a in out] == [
        ("compression_error", False)
    ]


def test_clear_alarms_resolves_everything_active():
    det = HealthDetector(_cfg(spike_band=2.0, hysteresis=1))
    det.observe(1.0, 1.0)
    det.observe(9.0, 1.0)
    assert det.active
    clears = det.clear_alarms()
    assert [(a.kind, a.active) for a in clears] == [("loss_spike", False)]
    det.reset()
    assert not det.active and det.clear_alarms() == []


# ---------------------------------------------------------------------------
# flight recorder: ring bounds, debounce, bundle cap, atomic round trip
# ---------------------------------------------------------------------------


def test_ring_is_bounded_and_bundle_round_trips(tmp_path):
    sink_events = []
    rec = FlightRecorder(
        str(tmp_path), ring_size=5, debounce_s=0.0, max_bundles=16,
        status_provider=lambda: {"step": 7, "healthy": False},
        schedule_provider=lambda: {"comm_op": "all_reduce",
                                   "num_groups": 2},
        event_sink=lambda ev, **f: sink_events.append((ev, f)),
    )
    for i in range(20):
        rec.observe("scalar", {"tag": "loss", "value": 1.0, "step": i})
    assert len(rec._ring) == 5  # bounded, oldest dropped
    rec.observe("bad_step", {"step": 20, "epoch": 1, "nonfinite": 3.0})
    bundles = rec.bundles()
    assert len(bundles) == 1 and bundles[0]["trigger"] == "bad_step"
    paths = list_bundles(str(tmp_path))
    assert paths == [bundles[0]["path"]]
    assert not glob.glob(str(tmp_path / "postmortems" / "*.tmp.*"))
    doc = read_bundle(paths[0])
    assert doc["manifest"]["step"] == 20
    assert doc["status"] == {"step": 7, "healthy": False}
    assert doc["schedule"]["num_groups"] == 2
    # the ring dump ends with the trigger itself, preceded by the last
    # pre-trigger records (ring order)
    assert doc["events"][-1]["event"] == "bad_step"
    assert len(doc["events"]) == 5
    # the postmortem record is DEFERRED (emitting inside the trigger's
    # own observe would land it before the trigger's row in the JSONL):
    # nothing in the sink yet, the next observed event flushes it
    assert sink_events == []
    rec.observe("scalar", {"tag": "loss", "value": 1.0, "step": 21})
    assert sink_events and sink_events[0][0] == "postmortem"
    assert sink_events[0][1]["trigger"] == "bad_step"
    assert sink_events[0][1]["step"] == 20
    assert sink_events[0][1]["path"] == paths[0]
    # explicit flush (the trainer's shutdown path) is idempotent
    rec.flush_events()
    assert len(sink_events) == 1


def test_debounce_and_bundle_cap(tmp_path):
    rec = FlightRecorder(
        str(tmp_path), ring_size=8, debounce_s=3600.0, max_bundles=16,
    )
    rec.observe("bad_step", {"step": 1, "epoch": 0, "nonfinite": 1.0})
    # an alarm storm inside the debounce window writes NOTHING further
    for i in range(10):
        rec.observe("health_alarm", {
            "kind": "loss_spike", "step": 2 + i, "value": 9.0,
            "band": 2.0, "active": True,
        })
    assert len(rec.bundles()) == 1
    assert rec.suppressed == 10
    # clear edges never trigger at all
    rec2 = FlightRecorder(
        str(tmp_path / "b"), ring_size=8, debounce_s=0.0, max_bundles=2,
    )
    rec2.observe("drift_alarm", {
        "kind": "step_trend", "step": 1, "residual": 0.0, "band": 0.5,
        "active": False,
    })
    assert rec2.bundles() == []
    # with debounce off, the hard cap still bounds disk usage
    for i in range(5):
        rec2.observe("bad_step", {"step": i, "epoch": 0,
                                  "nonfinite": 1.0})
    assert len(rec2.bundles()) == 2
    assert len(list_bundles(str(tmp_path / "b"))) == 2


def test_abort_bound_stall_flushes_its_postmortem_event(tmp_path):
    """An abort-bound watchdog stall is followed by os._exit(86) — no
    further observe will ever flush the deferred record, so the recorder
    must flush it synchronously (the rc-86 stop message and /status
    snapshot are built FROM that record)."""
    sink = []
    rec = FlightRecorder(
        str(tmp_path), debounce_s=0.0,
        event_sink=lambda ev, **f: sink.append((ev, f)),
    )
    rec.observe("watchdog_stall", {
        "phase": "train", "idle_s": 30.0, "timeout_s": 5.0, "abort": True,
    })
    assert sink and sink[0][0] == "postmortem"
    assert sink[0][1]["trigger"] == "watchdog_stall"
    # a NON-abort stall stays on the deferred path (ordering preserved)
    sink2 = []
    rec2 = FlightRecorder(
        str(tmp_path / "b"), debounce_s=0.0,
        event_sink=lambda ev, **f: sink2.append((ev, f)),
    )
    rec2.observe("watchdog_stall", {
        "phase": "train", "idle_s": 9.0, "timeout_s": 5.0, "abort": False,
    })
    assert sink2 == []


def test_trigger_at_step_zero_keeps_its_step(tmp_path):
    """Step 0 is a legitimate trigger step (NaN on the very first step),
    not the 'no step' sentinel."""
    rec = FlightRecorder(str(tmp_path), debounce_s=0.0)
    rec.observe("bad_step", {"step": 0, "epoch": 0, "nonfinite": 1.0})
    assert rec.bundles()[0]["step"] == 0
    rec.observe("watchdog_stall", {
        "phase": "train", "idle_s": 9.0, "timeout_s": 5.0, "abort": False,
    })  # a step-less trigger still maps to the sentinel
    assert rec.bundles()[1]["step"] == -1


def test_refused_profile_arm_does_not_claim_foreign_window(
    tmp_path, monkeypatch,
):
    """MGWFBP_POSTMORTEM_PROFILE=1 with the aggregator refusing the arm
    (409: someone else's window is running): the recorder must NOT
    attach that foreign window's profile event to its bundle."""
    monkeypatch.setenv("MGWFBP_POSTMORTEM_PROFILE", "1")
    calls = []

    def refuse(steps):
        calls.append(steps)
        return 409, {"error": "busy"}

    rec = FlightRecorder(
        str(tmp_path), debounce_s=0.0, profile_armer=refuse,
    )
    rec.observe("bad_step", {"step": 4, "epoch": 0, "nonfinite": 1.0})
    assert calls == [rec.profile_steps]
    rec.observe("profile", {"step": 6, "steps": 3, "attribution": "trace"})
    doc = read_bundle(rec.bundles()[0]["path"])
    assert "profile" not in doc  # the foreign window stayed foreign
    # an ACCEPTED arm does attach
    rec2 = FlightRecorder(
        str(tmp_path / "ok"), debounce_s=0.0,
        profile_armer=lambda steps: (200, {"armed": True}),
    )
    rec2.observe("bad_step", {"step": 4, "epoch": 0, "nonfinite": 1.0})
    rec2.observe("profile", {"step": 6, "steps": 3,
                             "attribution": "trace"})
    doc2 = read_bundle(rec2.bundles()[0]["path"])
    assert doc2["profile"]["attribution"] == "trace"


def test_bundle_sequence_continues_across_incarnations(tmp_path):
    rec = FlightRecorder(str(tmp_path), debounce_s=0.0)
    rec.observe("bad_step", {"step": 1, "epoch": 0, "nonfinite": 1.0})
    # a resumed run under the same tag extends the sequence — 0000 must
    # not be clobbered
    rec2 = FlightRecorder(str(tmp_path), debounce_s=0.0)
    rec2.observe("bad_step", {"step": 9, "epoch": 0, "nonfinite": 1.0})
    names = [os.path.basename(p) for p in list_bundles(str(tmp_path))]
    assert names == ["0000", "0001"]


def test_tee_observers_detaches_only_the_failing_member(tmp_path):
    seen = []

    def good(ev, fields):
        seen.append(ev)

    def bad(ev, fields):
        raise RuntimeError("boom")

    tee = tee_observers(bad, good, None)
    tee("step", {})
    tee("step", {})
    assert seen == ["step", "step"]  # good kept flowing; bad detached


# ---------------------------------------------------------------------------
# aggregator + endpoints
# ---------------------------------------------------------------------------


def test_aggregator_health_gauges_alarms_and_postmortems(tmp_path):
    agg = MetricsAggregator(run={"model": "x"})
    agg.observe("health", {
        "step": 3, "epoch": 0, "loss": 1.5, "grad_norm": 2.0,
        "update_ratio": 1e-3, "group_norms": [1.0, 1.7],
        "compression_error": [0.1, 0.2],
    })
    agg.observe("health_alarm", {
        "kind": "grad_explosion", "step": 3, "value": 12.0, "band": 10.0,
        "active": True, "group": -1,
    })
    agg.observe("postmortem", {
        "trigger": "health_alarm", "step": 3, "path": "/p/0000",
    })
    v = agg.values()
    assert v["mgwfbp_health_loss"] == 1.5
    assert v["mgwfbp_health_grad_norm"] == 2.0
    assert v["mgwfbp_health_update_ratio"] == 1e-3
    assert v["mgwfbp_health_compression_error"] == 0.2
    assert v["mgwfbp_health_alarms_total"] == 1
    assert v["mgwfbp_postmortems_total"] == 1
    assert v["mgwfbp_active_alarms"] == 1
    st = agg.status()
    assert st["health"]["grad_norm"] == 2.0
    assert st["health_alarms"] == 1
    assert st["postmortems"]["total"] == 1
    assert st["postmortems"]["recent"][0]["path"] == "/p/0000"
    assert any(
        a.get("alarm") == "health" for a in st["active_alarms"]
    )
    # clear edge resolves the active alarm (and the counter stays)
    agg.observe("health_alarm", {
        "kind": "grad_explosion", "step": 5, "value": 1.0, "band": 10.0,
        "active": False, "group": -1,
    })
    st = agg.status()
    assert st["active_alarms"] == [] and st["health_alarms"] == 1
    # /postmortems over HTTP serves the same document
    srv = TelemetryServer(agg, 0, host="127.0.0.1")
    try:
        code, body = _get(srv.port, "/postmortems")
        assert code == 200
        doc = json.loads(body)
        assert doc["total"] == 1 and doc["recent"][0]["step"] == 3
    finally:
        srv.close()


def test_fleet_status_aggregates_postmortems():
    from mgwfbp_tpu.telemetry.fleet import ChildScrape, fleet_status

    children = [
        ChildScrape(0, "h", 1, status={
            "healthy": True,
            "postmortems": {"total": 2, "recent": [{"path": "/a/0001"}]},
        }),
        ChildScrape(1, "h", 2, status={"healthy": True}),
    ]
    doc = fleet_status(children)
    assert doc["postmortems"] == [
        {"process": 0, "total": 2, "recent": [{"path": "/a/0001"}]},
    ]


# ---------------------------------------------------------------------------
# jaxpr rule SCH010: health stats add no collectives / callbacks
# ---------------------------------------------------------------------------


def test_sch010_clean_on_head():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        verify_health_stats_footprint,
    )

    assert verify_health_stats_footprint("lenet", "mgwfbp") == []
    assert verify_health_stats_footprint(
        "lenet", "mgwfbp", comm_op="rs_opt_ag"
    ) == []


def test_sch010_mutation_detects_footprint_change():
    """Feed the comparator two programs whose collective footprints DO
    differ (a per-layer wfbp trace vs a single-group trace) — the rule
    must flag both the added and the removed collectives."""
    from mgwfbp_tpu.analysis.jaxpr_check import (
        collective_footprint,
        compare_collective_footprints,
        trace_train_step,
    )

    single, _, _ = trace_train_step("lenet", "single")
    wfbp, _, _ = trace_train_step("lenet", "wfbp")
    assert collective_footprint(single) != collective_footprint(wfbp)
    findings = compare_collective_footprints(single, wfbp)
    assert findings and all(f.rule_id == "SCH010" for f in findings)
    # ... and the symmetric direction flags a REMOVED collective
    back = compare_collective_footprints(wfbp, single)
    assert back and any("REMOVED" in f.message for f in back)


# ---------------------------------------------------------------------------
# per-link refit pin (ROADMAP hier follow-up b): DCN-only drift refits
# the DCN leg alone, from trace-SEPARATED observations
# ---------------------------------------------------------------------------


def test_trace_scope_split_separates_ici_and_dcn_legs():
    from mgwfbp_tpu.parallel.allreduce import dcn_group_scope_name
    from mgwfbp_tpu.profiling import _group_times_from_scopes

    rows = [
        ("fusion.1 mgwfbp_group0000/psum-scatter", 100.0),
        ("fusion.2 mgwfbp_group0000/all-gather", 50.0),
        ("fusion.3 mgwfbp_group0001/psum-scatter", 200.0),
        ("fusion.4 mgwfbp_group0001/all-gather", 100.0),
        ("ar.1 mgwfbp_dcngroup0000/psum", 4000.0),
        ("ar.2 mgwfbp_dcngroup0001/psum", 8000.0),
    ]
    ici = _group_times_from_scopes(rows, 2, iters=1)
    dcn = _group_times_from_scopes(
        rows, 2, iters=1, scope_name=dcn_group_scope_name
    )
    # each family collects ONLY its own scopes — no cross-contamination
    assert ici == pytest.approx([150e-6, 300e-6])
    assert dcn == pytest.approx([4000e-6, 8000e-6])


def test_dcn_only_drift_refits_dcn_leg_alone():
    """The acceptance pin: synthetic DCN-only drift (the DCN wire is 3x
    slower than the model says, the ICI legs measure exactly on-model)
    fed through the trace-separated per-link path must refit the DCN
    constants by ~3x while the ICI constants stay put — NOT the common
    whole-step drift factor that would smear 3x over both links."""
    from mgwfbp_tpu.parallel.buckets import BucketLayout
    from mgwfbp_tpu.parallel.costmodel import (
        AlphaBeta,
        TwoLevelAlphaBeta,
        refit_two_level_from_observations,
    )
    from mgwfbp_tpu.profiling import dcn_shard_nbytes

    ici = AlphaBeta(1e-5, 2e-10)
    dcn = AlphaBeta(2e-3, 6e-9)
    model = TwoLevelAlphaBeta(ici=ici, dcn=dcn, ici_size=4, dcn_size=2)
    layout = BucketLayout(
        groups=((0,), (1,)),
        offsets=((0,), (0,)),
        group_sizes=(1000, 4000),
        dtypes=(np.dtype(np.float32), np.dtype(np.float32)),
    )
    ici_bytes = [4000.0, 16000.0]  # full bucket payloads (f32)
    # ICI legs measure exactly on-model; the DCN wire is 3x slower
    ici_obs = [(b, ici.alpha + ici.beta * b) for b in ici_bytes]
    dcn_bytes = dcn_shard_nbytes(layout, [[0], [1]], ici_size=4)
    assert dcn_bytes == [1000, 4000]  # padded 1/ici shards on the wire
    dcn_obs = [
        (b, 3.0 * (dcn.alpha + dcn.beta * b)) for b in dcn_bytes
    ]
    new = refit_two_level_from_observations(
        model, [], ici_observations=ici_obs, dcn_observations=dcn_obs,
    )
    assert new.ici.alpha == pytest.approx(ici.alpha, rel=1e-6)
    assert new.ici.beta == pytest.approx(ici.beta, rel=1e-6)
    assert new.dcn.alpha == pytest.approx(3.0 * dcn.alpha, rel=1e-6)
    assert new.dcn.beta == pytest.approx(3.0 * dcn.beta, rel=1e-6)
    # contrast: the whole-step fallback would have moved the ICI link too
    common = refit_two_level_from_observations(
        model, [(b, 3.0 * model.predict(b)) for b in ici_bytes],
    )
    assert common.ici.beta == pytest.approx(3.0 * ici.beta, rel=1e-3)


# ---------------------------------------------------------------------------
# zero-sync pin: health stats + recorder + server all on
# ---------------------------------------------------------------------------


def test_zero_sync_guard_with_health_stats_and_recorder(
    tmp_path, monkeypatch,
):
    """The PR-4/5/9 zero-sync pin, extended to ISSUE 12 (and subsuming
    test_observability's former server-only version): the live plane
    (aggregator tee + HTTP server + drift detector) PLUS the in-jit
    health statistics, their deque drain, the health detector, and the
    flight recorder tee must add ZERO device syncs to the step loop —
    device_get/block_until_ready counts identical with everything on vs
    everything off.

    What this counts: calls of `jax.device_get` and `jax.block_until_ready`,
    nothing else. It does not count `__float__` / `__array__` reads of
    device arrays (the guard, the log line and the health drain make them)
    and it does not count device programs: the drain of PRs 12 to 24
    stacked the statistics with `jnp.stack`, whose programs queued behind
    the step in flight, so it synchronised every step on the chip while
    this pin held (PERF.md, PR 24 and PR 25). That the drain dispatches no
    program is pinned by tests/test_health_drain.py."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "1000")

    def run(on: bool) -> int:
        cfg = make_config(
            "lenet", lr=0.01, max_epochs=1, num_batches_per_epoch=4,
            batch_size=8, seed=5,
            logdir=str(tmp_path / ("on" if on else "off")),
            telemetry=on,
            metrics_port=0 if on else None,
            health_stats=on,
        )
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        if on:
            assert t._metrics_server is not None
            assert t._health_detector is not None
            assert t._recorder is not None
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
        if on:
            code, _ = _get(t._metrics_server.port, "/metrics")
            assert code == 200
        t.close()
        return counts["n"]

    assert run(on=True) == run(on=False)


# ---------------------------------------------------------------------------
# pinned end-to-end: nan@step -> health alarm -> bundle on disk
# ---------------------------------------------------------------------------


def test_nan_fault_raises_health_alarm_and_writes_bundle(
    tmp_path, monkeypatch,
):
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "nan@step=2")
    monkeypatch.setenv("MGWFBP_HEALTH_HYSTERESIS", "1")
    cfg = make_config(
        "lenet", lr=0.01, max_epochs=1, num_batches_per_epoch=6,
        batch_size=8, seed=5, logdir=str(tmp_path),
        telemetry=True, metrics_port=0,
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    port = t._metrics_server.port
    epoch_metrics = t.train_epoch(0)
    # the health/* keys are telemetry plumbing: the log-facing metrics
    # dict train_epoch returns must never carry them
    assert epoch_metrics and not any(
        k.startswith("health/") for k in epoch_metrics
    )
    # /postmortems lists the bundle on the LIVE endpoint
    code, body = _get(port, "/postmortems")
    assert code == 200
    live = json.loads(body)
    t.close()
    assert live["total"] == 1 and live["recent"][0]["step"] == 2, live

    (path,) = glob.glob(str(tmp_path / "*" / "telemetry.jsonl"))
    recs = read_event_set(path)
    tag_dir = os.path.dirname(path)

    # the health stream carries per-group norms every step, NaN at the
    # poisoned one
    health = events_of(recs, "health")
    assert len(health) == 6
    num_groups = len(health[0]["group_norms"])
    assert num_groups >= 2
    bad_rec = [h for h in health if h["step"] == 2]
    assert bad_rec and bad_rec[0]["loss"] != bad_rec[0]["loss"]  # NaN
    good = [h for h in health if h["step"] != 2]
    assert all(
        np.isfinite(h["grad_norm"]) and np.isfinite(h["update_ratio"])
        for h in good
    )

    # the detector raised through hysteresis at the bad step, and the
    # first finite step after it cleared the loss spike
    alarms = events_of(recs, "health_alarm")
    raised = [a for a in alarms if a["active"]]
    assert any(
        a["kind"] == "loss_spike" and a["step"] == 2 for a in raised
    ), alarms
    assert any(
        a["kind"] == "loss_spike" and not a["active"] for a in alarms
    ), alarms

    # exactly one postmortem bundle (debounce folded the concurrent
    # alarms into it), naming the bad step, with the full evidence set
    pms = events_of(recs, "postmortem")
    assert len(pms) == 1 and pms[0]["step"] == 2, pms
    bundles = list_bundles(tag_dir)
    assert len(bundles) == 1
    doc = read_bundle(bundles[0])
    assert doc["manifest"]["step"] == 2
    assert doc["manifest"]["trigger"] in ("bad_step", "health_alarm")
    assert any(r.get("event") == "bad_step" for r in doc["events"])
    assert doc["schedule"]["schedule"]["num_groups"] == num_groups
    assert doc["status"] is not None and "run" in doc["status"]


def test_compression_error_rides_health_stream(tmp_path):
    """With topk compression live, per-group relative compression-error
    scalars stream through the same health records (the ROADMAP
    compression item's convergence guard, landed early)."""
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = make_config(
        "lenet", lr=0.01, max_epochs=1, num_batches_per_epoch=2,
        batch_size=8, seed=3, logdir=str(tmp_path), telemetry=True,
        compressor="topk", density=0.25,
        # wire-dtype path: the error must measure the k-set the bf16
        # wire actually selects, not an f32 re-selection
        comm_dtype="bfloat16",
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    num_groups = t.reducer.layout.num_groups
    t.train_epoch(0)
    t.close()
    (path,) = glob.glob(str(tmp_path / "*" / "telemetry.jsonl"))
    health = events_of(read_event_set(path), "health")
    assert health
    for h in health:
        errs = h.get("compression_error")
        assert errs and len(errs) == num_groups
        # top-k at density 0.25 drops real energy: 0 < err < 1
        assert all(0.0 < e < 1.0 for e in errs), errs
