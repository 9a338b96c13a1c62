"""Self-healing supervisor (ISSUE 20): failure classification, liveness
tracking, the healing policy (relaunch / shrink / budgets / crash-loop),
the chaos fault grammar (kill/wedge + inc), and
the bounded-coordination surface (CoordinationTimeout, env hardening).

Everything here is fast and jax-free on the supervisor side (stub child
commands, fake procs, injected clocks); the end-to-end chaos loop — real
2-process group, SIGKILL mid-epoch, shrink-to-survivor resume — lives in
`tools/fault_smoke.py --chaos` (check.sh chaos stage).
"""

import json
import os
import signal
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stub(script, n=2, **kw):
    from mgwfbp_tpu.runtime.supervisor import Supervisor

    return Supervisor([sys.executable, "-c", script], n, **kw)


# ---------------------------------------------------------------------------
# failure classification (the rc/signal decision table)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rc,cls", [
    (0, "ok"),
    (75, "preempt"),
    (86, "watchdog"),
    (-9, "oom_kill"),            # Popen signal death: SIGKILL
    (137, "oom_kill"),           # shell-relayed 128+9
    (-15, "term"),               # SIGTERM, never drained
    (143, "term"),
    (-2, "term"),                # SIGINT
    (-11, "crash"),              # SIGSEGV
    (139, "crash"),
    (1, "crash"),                # plain nonzero exit
    (3, "crash"),
])
def test_classify_rc_decision_table(rc, cls):
    from mgwfbp_tpu.runtime.supervisor import classify_rc

    assert classify_rc(rc) == cls


# ---------------------------------------------------------------------------
# liveness tracker (injected clock — no processes involved)
# ---------------------------------------------------------------------------

def test_liveness_never_seen_is_unknown():
    from mgwfbp_tpu.runtime.supervisor import _LivenessTracker

    t = _LivenessTracker()
    assert t.classify(0, now=1000.0, grace_s=5.0) == "unknown"
    # a child that NEVER answered cannot become unreachable (it is
    # booting; pre-step hangs are the in-process watchdog's domain)
    t.observe(0, None, now=0.0)
    assert t.classify(0, now=1000.0, grace_s=5.0) == "unknown"


def test_liveness_frozen_step_past_grace_is_wedged():
    from mgwfbp_tpu.runtime.supervisor import _LivenessTracker

    t = _LivenessTracker()
    t.observe(0, {"step": 3, "healthy": True}, now=0.0)
    assert t.classify(0, now=4.0, grace_s=5.0) == "running"
    assert t.classify(0, now=6.0, grace_s=5.0) == "wedged"
    # progress resets the clock
    t.observe(0, {"step": 4, "healthy": True}, now=6.0)
    assert t.classify(0, now=10.0, grace_s=5.0) == "running"


def test_liveness_step_zero_never_wedges():
    """Compile/bootstrap legitimately sits at step 0 arbitrarily long —
    only a child that has EVER stepped can freeze."""
    from mgwfbp_tpu.runtime.supervisor import _LivenessTracker

    t = _LivenessTracker()
    t.observe(0, {"step": 0, "healthy": True}, now=0.0)
    assert t.classify(0, now=1e6, grace_s=5.0) == "running"


def test_liveness_sticky_unhealthy_is_wedged():
    from mgwfbp_tpu.runtime.supervisor import _LivenessTracker

    t = _LivenessTracker()
    t.observe(0, {"step": 0, "healthy": False}, now=0.0)
    assert t.classify(0, now=3.0, grace_s=5.0) == "running"
    t.observe(0, {"step": 0, "healthy": False}, now=6.0)
    assert t.classify(0, now=6.0, grace_s=5.0) == "wedged"
    # recovery clears the sticky clock
    t2 = _LivenessTracker()
    t2.observe(0, {"step": 0, "healthy": False}, now=0.0)
    t2.observe(0, {"step": 1, "healthy": True}, now=2.0)
    assert t2.classify(0, now=6.0, grace_s=5.0) == "running"


def test_liveness_seen_then_silent_is_unreachable():
    from mgwfbp_tpu.runtime.supervisor import _LivenessTracker

    t = _LivenessTracker()
    t.observe(0, {"step": 2, "healthy": True}, now=0.0)
    t.observe(0, None, now=1.0)
    assert t.classify(0, now=3.0, grace_s=5.0) == "running"
    assert t.classify(0, now=7.0, grace_s=5.0) == "unreachable"
    # answering again clears it
    t.observe(0, {"step": 3, "healthy": True}, now=7.5)
    assert t.classify(0, now=8.0, grace_s=5.0) == "running"


def test_liveness_max_step_tracks_group_progress():
    from mgwfbp_tpu.runtime.supervisor import _LivenessTracker

    t = _LivenessTracker()
    assert t.max_step() == 0
    t.observe(0, {"step": 4}, now=0.0)
    t.observe(1, {"step": 7}, now=0.0)
    assert t.max_step() == 7


# ---------------------------------------------------------------------------
# env hardening (fail fast NAMING the variable — the
# MGWFBP_BARRIER_TIMEOUT_S precedent)
# ---------------------------------------------------------------------------

def test_env_float_and_int_name_the_variable():
    from mgwfbp_tpu.utils.platform import env_float, env_int

    assert env_float("X", 2.5, environ={}) == 2.5
    assert env_float("X", 2.5, environ={"X": " 7 "}) == 7.0
    with pytest.raises(ValueError, match="MY_KNOB=.*junk.*not a number"):
        env_float("MY_KNOB", 1.0, environ={"MY_KNOB": "junk"})
    assert env_int("Y", 3, environ={"Y": ""}) == 3
    with pytest.raises(ValueError, match="MY_INT=.*not an integer"):
        env_int("MY_INT", 1, environ={"MY_INT": "1.5"})


def test_supervisor_liveness_grace_garbage_fails_fast():
    with pytest.raises(ValueError, match="MGWFBP_LIVENESS_GRACE_S"):
        _stub("raise SystemExit(0)",
              env={"MGWFBP_LIVENESS_GRACE_S": "soon"})


def test_coord_timeout_env_garbage_fails_fast(monkeypatch):
    from mgwfbp_tpu.runtime import coordination as coord

    monkeypatch.setenv("MGWFBP_COORD_TIMEOUT_S", "whenever")
    with pytest.raises(ValueError, match="MGWFBP_COORD_TIMEOUT_S"):
        coord._coord_timeout_s()
    monkeypatch.setenv("MGWFBP_COORD_TIMEOUT_S", "12")
    assert coord._coord_timeout_s() == 12.0


def test_coordination_timeout_is_structured_runtimeerror():
    from mgwfbp_tpu.runtime.coordination import CoordinationTimeout

    e = CoordinationTimeout("agree_any", 15.0, detail="peer reset")
    assert isinstance(e, RuntimeError)  # existing catchers keep working
    assert e.op == "agree_any" and e.timeout_s == 15.0
    assert "agree_any" in str(e) and "peer reset" in str(e)


# ---------------------------------------------------------------------------
# chaos fault grammar: kill / wedge (+ inc incarnation addressing)
# ---------------------------------------------------------------------------

def test_kill_wedge_parse_and_describe():
    from mgwfbp_tpu.utils.faults import parse_plan

    p = parse_plan("kill@step=4,proc=1;wedge@step=3,secs=300,proc=0,inc=1")
    assert p.describe() == (
        "kill@step=4,proc=1; wedge@step=3,secs=300,proc=0,inc=1"
    )


@pytest.mark.parametrize("plan,msg", [
    ("kill", "missing required key"),
    ("wedge@step=3", "missing required key"),
    ("kill@step=4,secs=2", "takes keys"),
    ("kill@step=4,inc=-1", "inc must be >= 0"),
    ("wedge@step=3,secs=-1", "wedge secs must be >= 0"),
    ("kill@step=4,inc=soonish", "non-numeric"),
])
def test_kill_wedge_grammar_rejects(plan, msg):
    from mgwfbp_tpu.utils.faults import parse_plan

    with pytest.raises(ValueError, match=msg):
        parse_plan(plan)


def test_kill_fires_once_on_live_crossing():
    from mgwfbp_tpu.utils.faults import parse_plan

    p = parse_plan("kill@step=4")
    assert not p.kill_after(3)
    assert p.kill_after(4)
    assert not p.kill_after(4)  # one-shot
    # a resumed counter already past the step consumes it silently
    p2 = parse_plan("kill@step=4")
    assert not p2.kill_after(9)
    assert not p2.kill_after(10)


def test_wedge_fires_only_at_exact_step():
    from mgwfbp_tpu.utils.faults import parse_plan

    p = parse_plan("wedge@step=3,secs=5")
    assert p.wedge_secs(2) == 0.0
    assert p.wedge_secs(3) == 5.0
    assert p.wedge_secs(3) == 0.0  # one-shot


def test_for_incarnation_drops_other_lives_hard_faults():
    """kill/wedge are drain-less: a healed relaunch resumes BELOW the
    fault step, so without incarnation addressing the fault would
    re-fire every life and a chaos run could never complete."""
    from mgwfbp_tpu.utils.faults import parse_plan

    p = parse_plan("kill@step=4,proc=1;nan@step=2")
    inc0 = p.for_incarnation(0)
    assert sorted(s.kind for s in inc0.specs) == ["kill", "nan"]
    inc1 = p.for_incarnation(1)
    # the soft kind passes through; the inc-0 kill is someone else's
    assert [s.kind for s in inc1.specs] == ["nan"]
    p2 = parse_plan("wedge@step=3,secs=9,inc=2")
    assert p2.for_incarnation(2).specs and not p2.for_incarnation(0).specs


def test_supervisor_exports_incarnation_to_children():
    sup = _stub("raise SystemExit(0)", env={})
    env = sup._child_env(0, 12345, incarnation=2)
    assert env["MGWFBP_INCARNATION"] == "2"
    assert env["MGWFBP_PROCESS_ID"] == "0"


# ---------------------------------------------------------------------------
# healing policy (stub child commands — no jax involved)
# ---------------------------------------------------------------------------

def _read_events(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_heal_crash_relaunches_same_world(tmp_path):
    """A crash (rc 3) in the first life heals: survivors are SIGTERMed,
    the group relaunches at the SAME world, the run completes — with the
    failure + heal decisions in the supervisor's own telemetry stream."""
    script = (
        "import os, sys, time\n"
        f"d = {str(tmp_path)!r}\n"
        "inc = os.environ['MGWFBP_INCARNATION']\n"
        "pid = os.environ['MGWFBP_PROCESS_ID']\n"
        "open(os.path.join(d, f'seen_i{inc}_p{pid}'), 'w').close()\n"
        "if inc == '0' and pid == '1':\n"
        "    sys.exit(3)\n"
        "if inc == '0':\n"
        "    time.sleep(120)\n"  # survivor: waits for the heal SIGTERM
        "sys.exit(0)\n"
    )
    sup = _stub(
        script, n=2, sleep=lambda s: None,
        log_dir=str(tmp_path / "logs"), drain_grace_s=10.0,
    )
    t0 = time.monotonic()
    assert sup.run() == 0
    assert time.monotonic() - t0 < 60
    assert len(sup.results) == 2
    assert sup.processes == 2  # crash heals at the SAME world
    rcs = sup.results[0].returncodes
    assert rcs[1] == 3 and rcs[0] != 0  # survivor was torn down, not left
    assert sup.results[1].returncodes == [0, 0]
    assert sup._heal_restarts == {"crash": 1}
    seen = {p for p in os.listdir(str(tmp_path)) if p.startswith("seen_")}
    assert {"seen_i0_p0", "seen_i0_p1",
            "seen_i1_p0", "seen_i1_p1"} <= seen
    events = _read_events(tmp_path / "logs" / "telemetry.supervisor.jsonl")
    assert events[0]["event"] == "header"
    assert events[0]["run"]["process_index"] == -1
    fails = [e for e in events if e["event"] == "failure"]
    heals = [e for e in events if e["event"] == "heal"]
    assert fails and fails[0]["class"] == "crash"
    assert fails[0]["target"] == "p1" and fails[0]["rc"] == 3
    assert len(heals) == 1
    assert heals[0]["action"] == "relaunch" and heals[0]["world"] == 2


def test_heal_sigkill_shrinks_to_survivors(tmp_path):
    """The ISSUE-20 pin in miniature: SIGKILL (OOM-ish) of p1 shrinks
    the group to the survivor count; the relaunch runs at world=1 with
    elastic resume exported."""
    script = (
        "import os, signal, sys, time\n"
        f"d = {str(tmp_path)!r}\n"
        "inc = os.environ['MGWFBP_INCARNATION']\n"
        "n = os.environ['MGWFBP_NUM_PROCESSES']\n"
        "pid = os.environ['MGWFBP_PROCESS_ID']\n"
        "open(os.path.join(d, f'seen_i{inc}_n{n}_p{pid}_'\n"
        "     + os.environ.get('MGWFBP_ELASTIC_RESUME', '0')), 'w')"
        ".close()\n"
        "if inc == '0' and pid == '1':\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"
        "if inc == '0':\n"
        "    time.sleep(120)\n"
        "sys.exit(0)\n"
    )
    sup = _stub(
        script, n=2, sleep=lambda s: None,
        log_dir=str(tmp_path / "logs"), drain_grace_s=10.0,
    )
    assert sup.run() == 0
    assert sup.processes == 1  # shrunk
    assert [len(r.returncodes) for r in sup.results] == [2, 1]
    assert sup.results[0].returncodes[1] == -9
    assert sup._heal_restarts == {"oom_kill": 1}
    seen = {p for p in os.listdir(str(tmp_path)) if p.startswith("seen_")}
    assert "seen_i1_n1_p0_1" in seen  # world=1, elastic resume on
    events = _read_events(tmp_path / "logs" / "telemetry.supervisor.jsonl")
    heal = [e for e in events if e["event"] == "heal"][0]
    assert heal["action"] == "shrink"
    assert heal["old_world"] == 2 and heal["world"] == 1


def test_heal_budget_exhausts_and_propagates_rc(tmp_path):
    sup = _stub(
        "import sys; sys.exit(7)", n=1, sleep=lambda s: None,
        heal_max_restarts=1, heal_same_step_limit=99,
        log_dir=str(tmp_path / "logs"),
    )
    assert sup.run() == 7
    assert len(sup.results) == 2  # initial + one heal, then budget stop
    events = _read_events(tmp_path / "logs" / "telemetry.supervisor.jsonl")
    stops = [e for e in events if e["event"] == "heal"
             and e["action"] == "stop"]
    assert stops and stops[0]["reason"] == "budget"


def test_heal_crash_loop_on_same_step_stops(tmp_path):
    sup = _stub(
        "import sys; sys.exit(9)", n=1, sleep=lambda s: None,
        heal_max_restarts=99, heal_same_step_limit=2,
        log_dir=str(tmp_path / "logs"),
    )
    assert sup.run() == 9
    assert len(sup.results) == 2  # two lives dead at the same step
    events = _read_events(tmp_path / "logs" / "telemetry.supervisor.jsonl")
    stops = [e for e in events if e["event"] == "heal"
             and e["action"] == "stop"]
    assert stops and stops[0]["reason"] == "crash_loop"


def test_no_heal_keeps_legacy_propagation():
    sup = _stub(
        "import sys; sys.exit(7)", n=1, sleep=lambda s: None, heal=False,
    )
    assert sup.run() == 7
    assert len(sup.results) == 1  # no relaunch


class _FakeProc:
    def __init__(self):
        self.signals = []

    def poll(self):
        return None

    def send_signal(self, sig):
        self.signals.append(sig)


def test_wedge_verdict_sigterms_the_group(monkeypatch):
    """The liveness monitor's action path, with the scrape and the
    throttle faked out: a frozen /status step past the grace SIGTERMs
    every member and records the pending wedge failure. With BOTH
    children frozen (a wedged peer freezes the group at the next merged
    collective) the verdict names the whole frozen set."""
    sup = _stub("raise SystemExit(0)", n=2,
                env={"MGWFBP_METRICS_PORT": "9100"},
                liveness_grace_s=0.0)
    frozen = {"step": 5, "healthy": True}
    monkeypatch.setattr(sup, "_child_status", lambda i, timeout_s=2.0: frozen)
    procs = [_FakeProc(), _FakeProc()]
    sup._poll_liveness(procs)  # first observation: running
    assert sup._pending_failure is None
    time.sleep(0.01)
    sup._liveness_poll_t = -1e9  # defeat the 1s scrape throttle
    sup._poll_liveness(procs)  # still step 5 past grace 0 -> wedged
    assert sup._pending_failure is not None
    assert sup._pending_failure["class"] == "wedged"
    assert sup._pending_failure["target"] == "p0,p1"
    assert all(p.signals == [signal.SIGTERM] for p in procs)
    # the verdict is sticky: no double SIGTERM on the next poll
    sup._liveness_poll_t = -1e9
    sup._poll_liveness(procs)
    assert all(len(p.signals) == 1 for p in procs)


def test_wedge_pending_failure_consumes_heal_budget(tmp_path):
    """After a wedge SIGTERM every child exits 75 — the rc vector alone
    looks like a plain preempt. The pending failure must route the
    incarnation through the WEDGE budget, not the free preempt path."""
    sup = _stub(
        "import sys; sys.exit(75)", n=1, sleep=lambda s: None,
        log_dir=str(tmp_path / "logs"),
    )
    real_run_group = sup._run_group

    def run_group(incarnation):
        result = real_run_group(incarnation)
        if incarnation == 0:
            # simulate: the liveness monitor had flagged p0 mid-run
            sup._pending_failure = {
                "class": "wedged", "target": "p0", "step": 3,
            }
        return result

    sup._run_group = run_group
    # incarnation 0: wedge heal (budget). incarnation 1: rc 75 with no
    # pending failure -> plain preempt resubmit. incarnation 2: same ->
    # budget of max_restarts. Cap restarts to keep it short:
    sup.max_restarts = 1
    assert sup.run() == 75
    assert sup._heal_restarts == {"wedged": 1}
    assert len(sup.results) == 3


def test_fleet_meta_reports_heal_state():
    sup = _stub("raise SystemExit(0)", n=2, heal_max_restarts=4)
    sup._heal_restarts["crash"] = 2
    sup._pending_failure = {"class": "wedged", "target": "p1", "step": 6}
    meta = sup._fleet_meta()
    assert meta["heal"]["enabled"] is True
    assert meta["heal"]["restarts"] == {"crash": 2}
    assert meta["heal"]["budget"] == 4
    assert meta["heal"]["pending_failure"]["target"] == "p1"
