"""Multi-host production runtime (ISSUE 6): coordination primitives,
supervisor policy, launcher-env resolution, per-process telemetry merge —
plus real 2-process groups (two OS processes, four virtual CPU devices
each, coordinated by jax.distributed over gloo collectives: the closest
this box gets to the reference's `mpirun -np 2` path, SURVEY.md §4).
The heavyweight end-to-end scenarios (training parity, supervised
preempt -> resubmit -> bitwise resume, 2-process autotune) are
slow-marked; `tools/check.sh` stage 5 keeps a 2-process lifecycle in the
standing gate so the path cannot rot back into dead code."""

import glob
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_pair(cmd_for, timeout=300, env_extra=None):
    """Launch one subprocess per process id and return their stdouts."""
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(env_extra or {})
        env.pop("MGWFBP_NUM_PROCESSES", None)
        procs.append(subprocess.Popen(
            cmd_for(pid), cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("2-process run timed out")
        assert p.returncode == 0, f"rank failed:\n{err[-3000:]}"
        outs.append(out)
    return outs


# ---------------------------------------------------------------------------
# coordination primitives
# ---------------------------------------------------------------------------

def test_coordination_single_process_shortcuts():
    """With one process there is nothing to agree: every primitive is a
    host-side identity and issues zero device work."""
    from mgwfbp_tpu.runtime import coordination as coord

    assert coord.process_count() == 1 and coord.is_primary()
    assert coord.agree_any(True) and not coord.agree_any(False)
    assert coord.agree_all(True) and not coord.agree_all(False)
    assert coord.broadcast_flag(3.25) == 3.25
    assert coord.gather_values(1.5) == [1.5]
    assert coord.gather_vectors([1.0, 2.0]) == [[1.0, 2.0]]
    assert coord.gather_vectors([]) == [[]]
    idx, reduced = coord.all_argmin([2.0, 0.5, None])
    assert idx == 1
    assert reduced == [2.0, 0.5, float("inf")]
    coord.barrier("noop")  # must not touch the (nonexistent) client
    with pytest.raises(ValueError):
        coord.all_argmin([])


def test_coordination_device_reduce_single_process():
    """The jitted psum/pmax transport, exercised directly on the 8-device
    mesh: contributions ride the FIRST local device only, so device
    multiplicity must never inflate a process's value."""
    from mgwfbp_tpu.runtime import coordination as coord

    assert coord._device_reduce([2.0, 5.0], "sum").tolist() == [2.0, 5.0]
    assert coord._device_reduce([2.0, 5.0], "max").tolist() == [2.0, 5.0]


def test_coordination_two_process():
    """Real 2-process agreement over jax.distributed + gloo: both
    processes must compute IDENTICAL results for every primitive."""
    port = _free_port()
    outs = _spawn_pair(
        lambda pid: [sys.executable, WORKER, str(pid), "2", str(port)],
    )
    results = [json.loads(o.strip().splitlines()[-1]) for o in outs]
    for pid, r in enumerate(results):
        assert r["pid"] == pid and r["count"] == 2
        assert r["any"] == [True, False]
        assert r["all"] == [True, False]
        assert r["bcast"] == 41.5  # process 0's value, everywhere
        assert r["argmin"] == [0, [1.5, 3.0, "inf"]]
        assert r["gatherv"] == [[0.0, 10.0], [1.0, 11.0]]
        assert r["barrier"] == "ok"


# ---------------------------------------------------------------------------
# fault-plan proc= addressing
# ---------------------------------------------------------------------------

def test_fault_plan_proc_key():
    from mgwfbp_tpu.utils.faults import parse_plan

    plan = parse_plan("preempt@step=4,proc=1;nan@step=2;stall@secs=1,proc=0")
    assert "proc=1" in plan.describe()
    p0 = plan.for_process(0)
    assert [s.kind for s in p0.specs] == ["nan", "stall"]
    p1 = plan.for_process(1)
    assert [s.kind for s in p1.specs] == ["preempt", "nan"]
    with pytest.raises(ValueError, match="proc"):
        parse_plan("preempt@step=4,proc=-1")
    with pytest.raises(ValueError):
        parse_plan("preempt@step=4,proc=x")


# ---------------------------------------------------------------------------
# train_cli launcher-env resolution
# ---------------------------------------------------------------------------

def _args(argv=()):
    from mgwfbp_tpu.train_cli import build_parser

    return build_parser().parse_args(list(argv))


def test_resolve_multihost_chain():
    from mgwfbp_tpu.train_cli import resolve_multihost

    # nothing signaled -> single host
    assert resolve_multihost(_args(), {}) == (None, None, None)
    # MGWFBP_NUM_PROCESSES=1 is single-host (ADVICE r5 #1 semantics)
    assert resolve_multihost(
        _args(), {"MGWFBP_NUM_PROCESSES": "1"}
    ) == (None, None, None)
    # flags win over envs
    got = resolve_multihost(
        _args(["--coordinator", "h:1", "--num-processes", "2",
               "--process-id", "1"]),
        {"MGWFBP_COORDINATOR": "other:9", "MGWFBP_PROCESS_ID": "0"},
    )
    assert got == ("h:1", 2, 1)
    # the supervisor's env contract
    got = resolve_multihost(_args(), {
        "MGWFBP_COORDINATOR": "127.0.0.1:5", "MGWFBP_NUM_PROCESSES": "2",
        "MGWFBP_PROCESS_ID": "1",
    })
    assert got == ("127.0.0.1:5", 2, 1)
    # SLURM fallback (coordinator still via env)
    got = resolve_multihost(_args(), {
        "SLURM_NTASKS": "4", "SLURM_PROCID": "3",
        "MGWFBP_COORDINATOR": "head:1234",
    })
    assert got == ("head:1234", 4, 3)
    # OpenMPI fallback; a 1-task world stays single-host
    got = resolve_multihost(_args(), {
        "OMPI_COMM_WORLD_SIZE": "2", "OMPI_COMM_WORLD_RANK": "0",
        "MGWFBP_COORDINATOR": "head:1",
    })
    assert got == ("head:1", 2, 0)
    assert resolve_multihost(
        _args(), {"OMPI_COMM_WORLD_SIZE": "1", "OMPI_COMM_WORLD_RANK": "0"}
    ) == (None, None, None)


def test_resolve_multihost_clear_failures():
    from mgwfbp_tpu.train_cli import resolve_multihost

    # multi-host signaled but no coordinator: the satellite's clear
    # message, not a backend-probe traceback
    with pytest.raises(SystemExit, match="coordinator"):
        resolve_multihost(_args(), {"MGWFBP_NUM_PROCESSES": "2",
                                    "MGWFBP_PROCESS_ID": "0"})
    with pytest.raises(SystemExit, match="process id"):
        resolve_multihost(_args(), {"MGWFBP_NUM_PROCESSES": "2",
                                    "MGWFBP_COORDINATOR": "h:1"})
    with pytest.raises(SystemExit, match="worker count"):
        resolve_multihost(_args(["--coordinator", "h:1"]), {})
    with pytest.raises(SystemExit, match="not an integer"):
        resolve_multihost(_args(), {"MGWFBP_NUM_PROCESSES": "nope"})


# ---------------------------------------------------------------------------
# supervisor policy (stub child commands — no jax involved)
# ---------------------------------------------------------------------------

def _stub_supervisor(script, n=2, **kw):
    from mgwfbp_tpu.runtime.supervisor import Supervisor

    return Supervisor([sys.executable, "-c", script], n, **kw)


def test_supervisor_resubmits_preempted_group(tmp_path):
    script = (
        "import os, sys\n"
        f"flag = os.path.join({str(tmp_path)!r}, "
        "'done_' + os.environ['MGWFBP_PROCESS_ID'])\n"
        "if not os.path.exists(flag):\n"
        "    open(flag, 'w').close()\n"
        "    sys.exit(75)\n"
        "sys.exit(0)\n"
    )
    delays = []
    sup = _stub_supervisor(
        script, backoff_base_s=0.5, sleep=delays.append,
        log_dir=str(tmp_path / "logs"),
    )
    assert sup.run() == 0
    assert delays == [0.5]  # one bounded backoff
    assert [r.returncodes for r in sup.results] == [[75, 75], [0, 0]]
    # launch contract: every child saw coordinator + process id envs
    logs = sorted(glob.glob(str(tmp_path / "logs" / "*.log")))
    assert len(logs) == 4  # 2 procs x 2 incarnations


def test_supervisor_resize_policy_relaunches_at_new_size(tmp_path):
    """--resize-to M (ISSUE 13): a drained (rc 75) group relaunches at M
    processes, with MGWFBP_ELASTIC_RESUME exported so the children may
    resume from the old world's sibling tag."""
    script = (
        "import os, sys\n"
        f"d = {str(tmp_path)!r}\n"
        "n = os.environ['MGWFBP_NUM_PROCESSES']\n"
        "pid = os.environ['MGWFBP_PROCESS_ID']\n"
        "open(os.path.join(d, f'seen_n{n}_p{pid}_'\n"
        "     + os.environ.get('MGWFBP_ELASTIC_RESUME', '0')), 'w')"
        ".close()\n"
        "flag = os.path.join(d, 'drained_' + pid)\n"
        "if not os.path.exists(flag):\n"
        "    open(flag, 'w').close()\n"
        "    sys.exit(75)\n"
        "sys.exit(0)\n"
    )
    sup = _stub_supervisor(
        script, n=2, resize_to=1, sleep=lambda s: None,
    )
    assert sup.run() == 0
    assert [r.returncodes for r in sup.results] == [[75, 75], [0]]
    # first incarnation at 2 processes, second at 1, both elastic-enabled
    seen = {os.path.basename(p) for p in glob.glob(str(tmp_path / "seen_*"))}
    assert {"seen_n2_p0_1", "seen_n2_p1_1", "seen_n1_p0_1"} <= seen
    # the fleet view records the completed transition
    meta = sup._fleet_meta()
    assert meta["resize"] == {
        "from": 2, "to": 1, "state": "done", "triggered": False,
    }


def test_supervisor_resize_rejects_bad_target():
    with pytest.raises(ValueError, match="resize_to"):
        _stub_supervisor("raise SystemExit(0)", n=2, resize_to=0)


def test_supervisor_backoff_is_bounded_exponential():
    sup = _stub_supervisor("raise SystemExit(0)", backoff_base_s=1.0,
                           backoff_max_s=5.0)
    assert [sup.backoff_s(r) for r in (1, 2, 3, 4, 5)] == [
        1.0, 2.0, 4.0, 5.0, 5.0,
    ]


def test_supervisor_restart_budget_exhausts_to_75():
    sup = _stub_supervisor(
        "import sys; sys.exit(75)", n=1, max_restarts=2,
        sleep=lambda s: None,
    )
    assert sup.run() == 75
    assert len(sup.results) == 3  # initial + 2 resubmissions


def test_supervisor_stops_on_watchdog_abort():
    sup = _stub_supervisor(
        "import sys; sys.exit(86)", n=1, sleep=lambda s: None,
    )
    assert sup.run() == 86
    assert len(sup.results) == 1  # a wedged device is NOT resubmitted


def test_supervisor_tears_down_stragglers_on_crash():
    """heal=False pins the legacy teardown-and-propagate policy (the
    healing policy has its own suite in test_selfheal.py)."""
    import time

    script = (
        "import os, sys, time\n"
        "if os.environ['MGWFBP_PROCESS_ID'] == '0':\n"
        "    sys.exit(3)\n"
        "time.sleep(300)\n"
    )
    sup = _stub_supervisor(script, grace_s=1.0, heal=False)
    t0 = time.monotonic()
    assert sup.run() == 3
    assert time.monotonic() - t0 < 30  # did not wait out the sleeper
    rcs = sup.results[0].returncodes
    assert rcs[0] == 3 and rcs[1] != 0  # straggler terminated


def test_supervisor_tears_down_peer_wedged_after_clean_exit():
    """A clean rc-0 exit takes the coordination service with it, so a
    peer still blocked in a collective can never finish: the teardown
    deadline must arm on the FIRST exit of any kind, not only on
    failures — otherwise the supervisor hangs exactly like the job."""
    import time

    script = (
        "import os, sys, time\n"
        "if os.environ['MGWFBP_PROCESS_ID'] == '0':\n"
        "    sys.exit(0)\n"
        "time.sleep(300)\n"
    )
    sup = _stub_supervisor(script, grace_s=1.0, drain_grace_s=2.0)
    t0 = time.monotonic()
    rc = sup.run()
    assert time.monotonic() - t0 < 30
    rcs = sup.results[0].returncodes
    assert rcs[0] == 0 and rcs[1] != 0
    assert rc == 128 + 15  # SIGTERM-killed straggler, honest shell status


# ---------------------------------------------------------------------------
# per-process telemetry streams + merge
# ---------------------------------------------------------------------------

def test_stream_filename_convention(tmp_path):
    from mgwfbp_tpu.telemetry import find_stream_paths, stream_filename

    assert stream_filename(0, 1) == "telemetry.jsonl"
    assert stream_filename(1, 2) == "telemetry.p1.jsonl"
    for name in ("telemetry.p1.jsonl", "telemetry.p0.jsonl",
                 "telemetry.pX.jsonl", "unrelated.jsonl"):
        (tmp_path / name).write_text("")
    assert [os.path.basename(p) for p in find_stream_paths(str(tmp_path))] \
        == ["telemetry.p0.jsonl", "telemetry.p1.jsonl"]
    # a stale single-host telemetry.jsonl from an earlier run of the same
    # deterministic tag must NOT leak into the multi-host stream set (the
    # merge would silently interleave two runs) — but alone, it IS the set
    (tmp_path / "telemetry.jsonl").write_text("")
    assert [os.path.basename(p) for p in find_stream_paths(str(tmp_path))] \
        == ["telemetry.p0.jsonl", "telemetry.p1.jsonl"]
    for name in ("telemetry.p0.jsonl", "telemetry.p1.jsonl",
                 "telemetry.pX.jsonl"):
        (tmp_path / name).unlink()
    assert [os.path.basename(p) for p in find_stream_paths(str(tmp_path))] \
        == ["telemetry.jsonl"]


def _write_stream(path, proc, anchor, steps, extra=()):
    rows = [{
        "event": "header", "wall": anchor, "schema_version": 2,
        "run": {"process_index": proc, "process_count": 2},
    }]
    for step, start, dur in steps:
        rows.append({"event": "step", "wall": anchor + start + dur,
                     "step": step, "epoch": 0,
                     "start_s": start, "dur_s": dur})
    rows.extend(extra)
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_telemetry_merge_global_timeline_and_stragglers(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from telemetry_merge import (
        check_monotonic, merge_streams, straggler_table,
    )

    p0 = str(tmp_path / "telemetry.p0.jsonl")
    p1 = str(tmp_path / "telemetry.p1.jsonl")
    # p1's anchor is 0.5s later (its header wall), and its steps are
    # consistently slower: the straggler
    _write_stream(p0, 0, 100.0, [(1, 0.0, 0.10), (2, 0.2, 0.10)])
    _write_stream(p1, 1, 100.5, [(1, 0.0, 0.30), (2, 0.4, 0.30)],
                  extra=[{"event": "overlap", "wall": 101.5, "step": 2,
                          "epoch": 0, "step_s": 0.3, "tb_total_s": 0.1,
                          "comm_s": 0.1, "hidden_s": 0.08,
                          "exposed_s": 0.02, "efficiency": 0.8,
                          "attribution": "model"}])
    merged = merge_streams([p0, p1])
    check_monotonic(merged)
    # span records re-anchor onto their stream's header wall
    first_steps = [r for r in merged if r.get("event") == "step"]
    assert [r["process"] for r in first_steps] == [0, 0, 1, 1]
    assert first_steps[2]["t"] == pytest.approx(100.5)
    rows = straggler_table(merged)
    assert [r["process"] for r in rows] == [0, 1]
    assert rows[0]["mean_excess_s"] == pytest.approx(0.0)
    assert rows[1]["mean_excess_s"] == pytest.approx(0.2)
    assert rows[1]["overlap_efficiency"] == pytest.approx(0.8)
    assert rows[0]["overlap_efficiency"] is None


def test_telemetry_merge_rejects_inconsistent_streams(tmp_path):
    """The 'one monotonic timeline' guarantee must be checked against the
    INPUT streams (the merge sort would hide any corruption): a span that
    starts after its own emit wall means a writer lost the set's anchor;
    a backwards emit wall means mis-ordered segments."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from telemetry_merge import merge_streams

    p = str(tmp_path / "telemetry.p0.jsonl")
    # span re-anchored at "zero": start_s puts t 50s AFTER its emit wall
    _write_stream(p, 0, 100.0, [])
    with open(p, "a") as f:
        f.write(json.dumps({"event": "step", "wall": 101.0, "step": 1,
                            "epoch": 0, "start_s": 51.0,
                            "dur_s": 0.1}) + "\n")
    with pytest.raises(ValueError, match="re-anchored"):
        merge_streams([p])
    # emit wall jumping backwards across records
    _write_stream(p, 0, 100.0, [])
    with open(p, "a") as f:
        f.write(json.dumps({"event": "epoch", "wall": 200.0, "epoch": 0,
                            "steps": 6, "dur_s": 1.0}) + "\n")
        f.write(json.dumps({"event": "epoch", "wall": 150.0, "epoch": 1,
                            "steps": 6, "dur_s": 1.0}) + "\n")
    with pytest.raises(ValueError, match="backwards"):
        merge_streams([p])


def test_telemetry_merge_cli_on_directory(tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import telemetry_merge

    _write_stream(str(tmp_path / "telemetry.p0.jsonl"), 0, 50.0,
                  [(1, 0.0, 0.1)])
    _write_stream(str(tmp_path / "telemetry.p1.jsonl"), 1, 50.0,
                  [(1, 0.0, 0.2)])
    out = str(tmp_path / "merged.jsonl")
    assert telemetry_merge.main([str(tmp_path), "--out", out]) == 0
    assert "2 stream(s), 2 process(es)" in capsys.readouterr().out
    recs = [json.loads(line) for line in open(out)]
    ts = [r["t"] for r in recs]
    assert ts == sorted(ts)
    assert {r["process"] for r in recs} == {0, 1}


# ---------------------------------------------------------------------------
# structured resize error + checkpoint sidecar gating
# ---------------------------------------------------------------------------

def test_multihost_resize_raises_structured_recipe(monkeypatch):
    import jax

    from mgwfbp_tpu.config import make_config
    from mgwfbp_tpu.runtime import ResizeUnsupported
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = make_config("mnistnet", lr=0.01, max_epochs=1, logdir="",
                      batch_size=8, seed=3)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(ResizeUnsupported) as ei:
        t.update_nworker(4)
    msg = str(ei.value)
    assert "mgwfbp_tpu.runtime.supervise" in msg  # the relaunch recipe
    assert ei.value.nworkers == 4


def test_checkpoint_sidecar_written_by_primary_only(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    import optax

    from mgwfbp_tpu.checkpoint import INDEX_FILE, Checkpointer, Snapshot
    from mgwfbp_tpu.runtime import coordination as coord
    from mgwfbp_tpu.train.step import TrainState

    params = {"w": jnp.arange(4, dtype=jnp.float32)}
    tx = optax.sgd(0.1)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
    )
    # posing as a NON-primary process: the orbax payload is written (on a
    # real group orbax itself gates that to the primary), but the sidecar
    # index must not be — process 0 owns the exactly-once commit
    monkeypatch.setattr(coord, "is_primary", lambda: False)
    ck = Checkpointer(str(tmp_path))
    ck.save(Snapshot(state=state, epoch=0, iteration=3, epoch_step=3,
                     mid_epoch=True), wait=True)
    assert not os.path.exists(tmp_path / INDEX_FILE)
    monkeypatch.setattr(coord, "is_primary", lambda: True)
    ck.save(Snapshot(state=state, epoch=0, iteration=6, epoch_step=6,
                     mid_epoch=True), wait=True)
    assert os.path.exists(tmp_path / INDEX_FILE)
    ck.close()
    # the sidecar (written late) still indexes BOTH snapshots: the
    # in-memory index is shared state, only the write is gated
    ck2 = Checkpointer(str(tmp_path))
    snap = ck2.restore(state, step=3)
    assert snap is not None and snap.mid_epoch and snap.epoch_step == 3
    ck2.close()


# ---------------------------------------------------------------------------
# end-to-end 2-process groups (heavyweight; check.sh stage 5 keeps the
# lifecycle in the standing gate)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_two_process_training_losses_agree(tmp_path):
    port = _free_port()

    def cmd(pid):
        return [
            sys.executable, "-m", "mgwfbp_tpu.train_cli",
            "--dnn", "mnistnet", "--batch-size", "4",
            "--epochs", "1", "--synthetic", "--logdir", "",
            "--no-profile-backward",
            "--num-batches-per-epoch", "6",
            "--coordinator", f"127.0.0.1:{port}",
            "--num-processes", "2", "--process-id", str(pid),
        ]

    outs = _spawn_pair(cmd, timeout=540, env_extra={
        "JAX_PLATFORMS": "cpu",
        "MGWFBP_HOST_DEVICES": "4",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": REPO,
    })
    metrics = [json.loads(o.strip().splitlines()[-1]) for o in outs]
    # both ranks trained the SAME global model: losses must agree exactly
    # (metrics are psum'd over the global mesh)
    l0 = metrics[0]["train"]["loss"]
    l1 = metrics[1]["train"]["loss"]
    assert np.isfinite(l0)
    assert l0 == pytest.approx(l1, rel=1e-6)
    assert metrics[0]["eval"]["top1"] == pytest.approx(
        metrics[1]["eval"]["top1"], rel=1e-6
    )


def _train_args(root, extra=(), dnn="lenet", batch="8"):
    return [
        "--dnn", dnn, "--synthetic", "--no-profile-backward",
        "--batch-size", batch, "--num-batches-per-epoch", "6",
        "--max-epochs", "2", "--epochs", "2", "--seed", "7",
        "--logdir", os.path.join(root, "logs"),
        "--checkpoint-dir", os.path.join(root, "ckpt"),
        "--ckpt-every-steps", "2", "--telemetry", *extra,
    ]


def _supervised_run(root, fault_plan, processes=2, extra=(), dnn="lenet",
                    batch="8"):
    from mgwfbp_tpu.runtime.supervisor import Supervisor, default_train_cmd

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu", "MGWFBP_HOST_DEVICES": "4",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "MGWFBP_FAULT_PLAN": fault_plan, "PYTHONPATH": REPO,
    })
    sup = Supervisor(
        default_train_cmd(_train_args(root, extra, dnn=dnn, batch=batch)),
        processes,
        backoff_base_s=0.2, log_dir=os.path.join(root, "sup"), env=env,
    )
    return sup, sup.run()


def _final_snapshot(root):
    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.checkpoint import Checkpointer
    from mgwfbp_tpu.optim import make_optimizer
    from mgwfbp_tpu.train.step import create_train_state

    model, meta = zoo.create_model("lenet")
    tx, _ = make_optimizer(0.01, dataset="mnist", max_epochs=2,
                           num_batches_per_epoch=6)
    template = create_train_state(
        jax.random.PRNGKey(7), model,
        jnp.zeros((1,) + meta.input_shape), tx,
    )
    (ckdir,) = glob.glob(os.path.join(root, "ckpt", "*"))
    ck = Checkpointer(ckdir)
    try:
        return ck.restore(template)
    finally:
        ck.close()


@pytest.mark.slow
def test_two_process_preempt_resume_bitwise_under_supervisor(tmp_path):
    """The ISSUE 6 acceptance scenario: a 2-process CPU-mesh fit under
    the supervisor with MGWFBP_FAULT_PLAN preempting ONE process
    mid-epoch. Both processes drain (agreed), checkpoint once, exit rc
    75; the supervisor resubmits; the resumed run's final params are
    BITWISE identical to an uninterrupted 2-process run; the merged
    per-process telemetry is one monotonic timeline covering both
    incarnations."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from telemetry_merge import check_monotonic, merge_streams

    from mgwfbp_tpu.telemetry import events_of, find_stream_paths

    faulted = str(tmp_path / "faulted")
    sup, rc = _supervised_run(faulted, "preempt@step=4,proc=1")
    assert rc == 0
    assert [r.returncodes for r in sup.results] == [[75, 75], [0, 0]]

    clean = str(tmp_path / "clean")
    sup2, rc2 = _supervised_run(clean, "")
    assert rc2 == 0 and len(sup2.results) == 1

    a, b = _final_snapshot(faulted), _final_snapshot(clean)
    assert a.iteration == b.iteration == 12
    import jax

    for la, lb in zip(
        jax.tree_util.tree_leaves(a.state.params),
        jax.tree_util.tree_leaves(b.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for la, lb in zip(
        jax.tree_util.tree_leaves(a.state.opt_state),
        jax.tree_util.tree_leaves(b.state.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    # one monotonic global timeline across both incarnations
    (tagdir,) = glob.glob(os.path.join(faulted, "logs", "*"))
    paths = find_stream_paths(tagdir)
    assert len(paths) == 2
    merged = merge_streams(paths)
    check_monotonic(merged)
    assert {r["process"] for r in events_of(merged, "preempt")} == {0, 1}
    assert {r["process"] for r in events_of(merged, "resume")} == {0, 1}
    for p in (0, 1):
        steps = [r["step"] for r in events_of(merged, "step")
                 if r["process"] == p]
        assert max(steps) == 12  # both incarnations on one timeline


@pytest.mark.slow
def test_two_process_rs_fwd_ag_preempt_resume_bitwise(tmp_path):
    """The ISSUE 13 acceptance pin for cross-step pipelining at pod
    scale: the rs_fwd_ag multi-host build refusal is GONE, and a
    supervised 2-process rs_fwd_ag run preempted mid-epoch — with the
    param carry living as in-flight 1/world shards — drains to a
    shard-native checkpoint (each process saves only its own shard rows)
    and resumes BITWISE identical to an uninterrupted 2-process run."""
    extra = ("--comm-op", "rs_fwd_ag")
    faulted = str(tmp_path / "faulted")
    sup, rc = _supervised_run(faulted, "preempt@step=4,proc=1", extra=extra)
    assert rc == 0
    assert [r.returncodes for r in sup.results] == [[75, 75], [0, 0]]

    clean = str(tmp_path / "clean")
    sup2, rc2 = _supervised_run(clean, "", extra=extra)
    assert rc2 == 0 and len(sup2.results) == 1

    # the drained checkpoint really is shard-native and per-process
    (tagdir,) = glob.glob(os.path.join(faulted, "ckpt", "*"))
    manifests = glob.glob(
        os.path.join(tagdir, "sharded", "*", "manifest.json")
    )
    assert manifests, "rs_fwd_ag drain did not commit shard-native"
    with open(sorted(manifests)[0]) as f:
        manifest = json.load(f)
    assert manifest["params"]["kind"] == "sharded"  # the in-flight carry
    assert sorted(manifest["processes"]) == ["0", "1"]

    a, b = _final_snapshot(faulted), _final_snapshot(clean)
    assert a.iteration == b.iteration == 12
    import jax

    for la, lb in zip(
        jax.tree_util.tree_leaves(a.state.params),
        jax.tree_util.tree_leaves(b.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for la, lb in zip(
        jax.tree_util.tree_leaves(a.state.opt_state),
        jax.tree_util.tree_leaves(b.state.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _final_carry_snapshot(root, dnn, batch):
    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.checkpoint import Checkpointer
    from mgwfbp_tpu.config import make_config
    from mgwfbp_tpu.optim import make_optimizer
    from mgwfbp_tpu.train.step import create_train_state

    cfg = make_config(dnn, batch_size=int(batch), max_epochs=2, seed=7)
    model, meta = zoo.create_model(dnn, dataset=cfg.dataset)
    tx, _ = make_optimizer(
        cfg.lr, dataset=cfg.dataset, max_epochs=2,
        num_batches_per_epoch=6, lr_schedule=cfg.lr_schedule,
        momentum=cfg.momentum, weight_decay=cfg.weight_decay,
        norm_clip=cfg.norm_clip,
    )
    template = create_train_state(
        jax.random.PRNGKey(7), model,
        jnp.zeros((1,) + meta.input_shape, meta.input_dtype), tx,
    )
    (ckdir,) = glob.glob(os.path.join(root, "ckpt", "*"))
    ck = Checkpointer(ckdir)
    try:
        carry_template = None
        if meta.has_carry:
            # the boundary snapshot carries no mid-epoch carry; a
            # template covering the worst case keeps restore happy
            import numpy as _np

            carry_template = jax.tree_util.tree_map(
                _np.asarray, model.initial_carry(int(batch) * 8)
            )
        return ck.restore(template, carry_template=carry_template)
    finally:
        ck.close()


@pytest.mark.slow
def test_two_process_carry_model_preempt_resume_bitwise(tmp_path):
    """ISSUE 13 closes the multi-host BPTT-carry degrade path: a
    2-process CARRY-MODEL (lstm) run preempted MID-EPOCH checkpoints
    each process's carry batch rows shard-native, and the resumed run's
    final params are BITWISE identical to an uninterrupted 2-process run
    — possible only if the restored hidden state matched exactly (the
    carry feeds every subsequent step)."""
    dnn, batch = "lstm", "4"
    faulted = str(tmp_path / "faulted")
    sup, rc = _supervised_run(
        faulted, "preempt@step=4,proc=1", dnn=dnn, batch=batch,
    )
    assert rc == 0
    assert [r.returncodes for r in sup.results] == [[75, 75], [0, 0]]

    clean = str(tmp_path / "clean")
    sup2, rc2 = _supervised_run(clean, "", dnn=dnn, batch=batch)
    assert rc2 == 0 and len(sup2.results) == 1

    # the drained mid-epoch step really carried per-process carry blocks
    (tagdir,) = glob.glob(os.path.join(faulted, "ckpt", "*"))
    carry_manifests = []
    for m in glob.glob(os.path.join(tagdir, "sharded", "*", "manifest.json")):
        with open(m) as f:
            doc = json.load(f)
        if doc.get("carry"):
            carry_manifests.append(doc)
    assert carry_manifests, "no shard-native step carried the BPTT carry"
    assert any(
        sorted(doc["carry"]["runs"]) == ["0", "1"]
        for doc in carry_manifests
    ), "carry not saved by BOTH processes"

    a = _final_carry_snapshot(faulted, dnn, batch)
    b = _final_carry_snapshot(clean, dnn, batch)
    assert a.iteration == b.iteration == 12
    import jax

    for la, lb in zip(
        jax.tree_util.tree_leaves(a.state.params),
        jax.tree_util.tree_leaves(b.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.slow
def test_two_process_autotune_commits_identical_schedule(tmp_path):
    """2-process autotune race: both processes must survive the race (a
    divergent commit would deadlock in the next collective) and the
    process-0-persisted cache entry must record the agreed winner."""
    port = _free_port()
    cache = str(tmp_path / "cache")

    def cmd(pid):
        return [
            sys.executable, "-m", "mgwfbp_tpu.train_cli",
            "--dnn", "lenet", "--batch-size", "8",
            "--epochs", "1", "--synthetic", "--logdir", "",
            "--no-profile-backward", "--num-batches-per-epoch", "4",
            "--autotune", "--autotune-steps", "1",
            "--schedule-cache", cache,
            "--coordinator", f"127.0.0.1:{port}",
            "--num-processes", "2", "--process-id", str(pid),
        ]

    outs = _spawn_pair(cmd, timeout=540, env_extra={
        "JAX_PLATFORMS": "cpu",
        "MGWFBP_HOST_DEVICES": "4",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": REPO,
    })
    metrics = [json.loads(o.strip().splitlines()[-1]) for o in outs]
    assert metrics[0]["train"]["loss"] == pytest.approx(
        metrics[1]["train"]["loss"], rel=1e-6
    )
    entries = glob.glob(os.path.join(cache, "*.json"))
    assert len(entries) == 1, entries
    entry = json.load(open(entries[0]))
    assert entry["winner"]
    assert entry["world"] == 8
    # the committed grouping is well-formed and raceable by a later run
    assert entry["groups"] and entry["layer_names"]
