"""Fault-injection smoke: the resilience lifecycle, end to end, on the CPU
mesh (tools/check.sh stage).

Single-process (default) drives the REAL launcher twice through
subprocesses:

  1. a lenet run with ``MGWFBP_FAULT_PLAN=
     "nan@step=2;stall@secs=3,step=4;preempt@step=4"`` — must drop the
     NaN step (``bad_step`` event), write a flight-recorder postmortem
     bundle for it (ISSUE 12) that the live ``/postmortems`` endpoint
     serves MID-RUN (the stall before step 4 holds the run open long
     enough to probe), drain the injected SIGTERM gracefully
     (step-indexed checkpoint + ``preempt`` event) and exit rc 75
     (EX_TEMPFAIL, restart-friendly);
  2. the same command with no fault plan — must resume from the exact
     mid-epoch step (``resume`` event with mid_epoch) and finish rc 0.

``--processes 2`` runs the MULTI-HOST lifecycle instead (ISSUE 6): a
2-process CPU-mesh group under the auto-resubmit supervisor with
``preempt@step=4,proc=1`` signaling ONE process — the group must AGREE to
drain (the un-signaled process records signal ``PEER``), checkpoint once,
exit rc 75, get resubmitted, resume mid-epoch on both processes, and
finish; the per-process telemetry streams must merge into one monotonic
global timeline covering both incarnations (tools/telemetry_merge.py).
This stage is what keeps the multi-host path from rotting back into dead
code — the fate of the pre-ISSUE-6 multihost test, slow-marked and never
run while CPU collectives silently stayed unconfigured.

Both modes also smoke the LIVE observability plane (ISSUE 9): the
single-process faulted run is probed mid-run over HTTP (/metrics must
serve the live step counter, /healthz must answer 200), and the
2-process group must serve DISTINCT ports (base + process_index), each
reporting its own process_index in /status.

The 2-process mode additionally smokes the FLEET fan-in (ISSUE 10): the
supervisor's /fleet/status must answer MID-RUN with a live straggler
table naming BOTH processes (a fan-in hang fails check.sh's hard-timeout
stage, exactly like a coordination hang), /fleet/metrics must merge both
children under a `process` label, and the `fleet.json` http_sd sidecar
must persist both children's ACTUAL metrics endpoints.

``--chaos`` runs the SELF-HEALING lifecycle (ISSUE 20) against
drain-less faults the agreed-preempt machinery cannot survive on its
own: a mid-epoch SIGKILL (supervisor classifies the -9, shrinks to the
survivors, elastic-resumes off the last committed shard-native step)
and a 300 s wedge (alive, serving HTTP, not stepping — only the
liveness monitor's frozen-step verdict can see it; the heal must land
in bounded wall-clock time). Both scenarios pin failure/heal events in
the supervisor's own telemetry stream and a monotonic merged timeline
across the heal.

Asserts the telemetry lifecycle after each run. No accelerator, dataset,
or network needed.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from mgwfbp_tpu.runtime.supervisor import free_port as _free_port  # noqa: E402

PREEMPT_RC = 75  # mirrors mgwfbp_tpu.utils.faults.PREEMPT_RC


def _probe(port: int, path: str, timeout_s: float = 1.0):
    """(http status, body) of one endpoint probe, or (None, reason)."""
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout_s
        ) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:  # 503 from /healthz is an answer
        return e.code, e.read().decode()
    except Exception as e:  # noqa: BLE001 — not up yet
        return None, str(e)


def _cli(logdir: str, ckpt: bool = True) -> list[str]:
    cmd = [
        sys.executable, "-m", "mgwfbp_tpu.train_cli",
        "--dnn", "lenet", "--synthetic", "--no-profile-backward",
        "--batch-size", "8", "--num-batches-per-epoch", "6",
        "--max-epochs", "2", "--epochs", "2", "--seed", "7",
        "--logdir", logdir,
    ]
    if ckpt:
        cmd += [
            "--checkpoint-dir", os.path.join(logdir, "ckpt"),
            "--ckpt-every-steps", "2",
        ]
    return cmd + ["--telemetry"]


def _run(
    logdir: str, fault_plan: str, metrics_port: int = 0,
    ckpt: bool = True,
) -> tuple[int, dict]:
    """One real-launcher run; with metrics_port > 0 the live plane is
    probed WHILE the run is up (mid-run, not post-hoc — that is the whole
    point of the plane). Returns (rc, probe results)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MGWFBP_FAULT_PLAN"] = fault_plan
    env.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    if metrics_port:
        env["MGWFBP_METRICS_PORT"] = str(metrics_port)
    # child output goes to FILES, not pipes: this loop does not drain
    # while polling, and a chatty child filling a 64 KiB pipe buffer
    # would block forever (a structural hang the old capture_output
    # call never had)
    out_path = os.path.join(logdir, "fault_smoke_child.log")
    with open(out_path, "w") as sink:
        proc = subprocess.Popen(
            _cli(logdir, ckpt=ckpt), env=env, cwd=_ROOT,
            stdout=sink, stderr=subprocess.STDOUT,
        )
        probes: dict = {}
        deadline = time.monotonic() + 600
        while proc.poll() is None:
            if time.monotonic() > deadline:
                proc.kill()
                proc.wait()
                raise AssertionError("fault-smoke run timed out")
            if metrics_port and "metrics" not in probes:
                code, body = _probe(metrics_port, "/metrics")
                if code == 200 and "mgwfbp_steps_total" in body:
                    probes["metrics"] = body
                    code, body = _probe(metrics_port, "/healthz")
                    assert code == 200, f"/healthz mid-run: {code} {body}"
                    probes["healthz"] = body.strip()
            if metrics_port and "postmortems" not in probes:
                # the injected-NaN bad step must leave a flight-recorder
                # bundle that /postmortems lists WHILE the run is up
                # (the stall@step=4 in the plan holds the window open)
                code, body = _probe(metrics_port, "/postmortems")
                if code == 200:
                    doc = json.loads(body)
                    if doc.get("total", 0) >= 1 and doc.get("recent"):
                        probes["postmortems"] = doc
            time.sleep(0.1)
    with open(out_path) as f:
        tail = f.read()[-4000:]
    if proc.returncode not in (0, PREEMPT_RC):
        sys.stderr.write(tail)
    if metrics_port:
        assert "metrics" in probes, (
            "live /metrics endpoint never answered mid-run "
            f"(port {metrics_port}); child tail:\n" + tail
        )
    return proc.returncode, probes


def _events(logdir: str) -> list[dict]:
    from mgwfbp_tpu.telemetry import read_event_set

    paths = glob.glob(os.path.join(logdir, "*", "telemetry.jsonl"))
    assert len(paths) == 1, f"expected one telemetry stream, got {paths}"
    return read_event_set(paths[0])


def single_process() -> dict:
    from mgwfbp_tpu.telemetry import events_of

    with tempfile.TemporaryDirectory(prefix="mgwfbp_fault_smoke_") as d:
        port = _free_port()
        rc, probes = _run(
            d, "nan@step=2;stall@secs=3,step=4;preempt@step=4",
            metrics_port=port,
        )
        assert rc == PREEMPT_RC, (
            f"faulted run exited rc {rc}, want {PREEMPT_RC} (EX_TEMPFAIL)"
        )
        assert probes.get("healthz") == "ok", probes
        # the live /postmortems probe answered mid-run, naming the bundle
        pm_doc = probes.get("postmortems")
        assert pm_doc is not None, (
            "/postmortems never listed the injected-NaN bundle mid-run; "
            f"probes: {sorted(probes)}"
        )
        assert pm_doc["recent"][0]["trigger"] == "bad_step", pm_doc
        assert pm_doc["recent"][0]["step"] == 2, pm_doc
        recs = _events(d)
        bad = events_of(recs, "bad_step")
        assert bad and bad[0]["step"] == 2, f"bad_step missing/wrong: {bad}"
        assert bad[0]["nonfinite"] > 0
        (pre,) = events_of(recs, "preempt")
        assert pre["signal"] == "SIGTERM" and pre["iteration"] == 4, pre
        ckpts = events_of(recs, "checkpoint")
        assert any(c.get("mid_epoch") for c in ckpts), ckpts
        # ... and the bundle itself is on disk, atomic and complete,
        # naming the bad step (ISSUE 12 flight recorder)
        from mgwfbp_tpu.telemetry.recorder import list_bundles, read_bundle

        (tag_dir,) = [
            p for p in glob.glob(os.path.join(d, "*"))
            if os.path.isdir(os.path.join(p, "postmortems"))
        ]
        bundles = list_bundles(tag_dir)
        assert bundles, f"no postmortem bundle on disk under {d}"
        bundle = read_bundle(bundles[0])
        assert bundle["manifest"]["trigger"] == "bad_step", bundle
        assert bundle["manifest"]["step"] == 2, bundle["manifest"]
        assert any(
            r.get("event") == "bad_step" for r in bundle["events"]
        ), "ring dump lacks the triggering bad_step record"
        assert bundle.get("schedule"), "schedule state missing from bundle"

        rc, _ = _run(d, "")
        assert rc == 0, f"resume run exited rc {rc}"
        recs = _events(d)
        resumes = events_of(recs, "resume")
        assert resumes and resumes[-1]["mid_epoch"], resumes
        assert resumes[-1]["iteration"] == 4, resumes
        steps = events_of(recs, "step")
        assert max(s["step"] for s in steps) == 12, (
            "resumed run did not finish both epochs"
        )
        return {
            "fault_smoke": "ok",
            "bad_steps": len(bad),
            "preempt_iteration": pre["iteration"],
            "resume_iteration": resumes[-1]["iteration"],
            "final_step": max(s["step"] for s in steps),
            "live_metrics_probed": sorted(probes),
            "postmortem_bundle": bundle["manifest"]["path"],
        }


def multi_process(processes: int) -> dict:
    from mgwfbp_tpu.runtime.supervisor import Supervisor, default_train_cmd
    from mgwfbp_tpu.telemetry import events_of, find_stream_paths
    from telemetry_merge import check_monotonic, merge_streams

    with tempfile.TemporaryDirectory(prefix="mgwfbp_mh_smoke_") as d:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # 4 virtual devices per process keeps the group's total world at
        # 8 — the same scale as tier-1 — and the incarnation under ~20 s
        env["MGWFBP_HOST_DEVICES"] = "4"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        # one plan for the whole group: NaN-poison a step on every
        # process, preempt ONLY process 1 — the drain must be agreed
        env["MGWFBP_FAULT_PLAN"] = "nan@step=2;preempt@step=4,proc=1"
        # live plane: one configured base port; child i must serve
        # base + i (telemetry/serve.resolve_metrics_port)
        base_port = _free_port()
        env["MGWFBP_METRICS_PORT"] = str(base_port)
        fleet_port = _free_port()
        sup = Supervisor(
            default_train_cmd(_cli(d)[3:]),  # strip interpreter/-m/module
            processes,
            backoff_base_s=0.2,
            log_dir=os.path.join(d, "supervisor"),
            env=env,
            fleet_port=fleet_port,
        )
        import threading

        rc_box: dict = {}
        runner = threading.Thread(
            target=lambda: rc_box.update(rc=sup.run()), daemon=True
        )
        runner.start()
        # mid-run: every process of the group serves a DISTINCT port,
        # each reporting its own process_index in /status; the
        # supervisor's FLEET fan-in must answer too, with a live
        # straggler table naming BOTH processes (hard-deadline bounded —
        # a fan-in hang must fail this stage, never wedge it)
        served: dict = {}
        fleet_doc = None
        fleet_metrics = None
        deadline = time.monotonic() + 590
        while runner.is_alive() and (
            len(served) < processes or fleet_doc is None
            or fleet_metrics is None
        ):
            if time.monotonic() > deadline:
                break
            for i in range(processes):
                if i in served:
                    continue
                code, body = _probe(base_port + i, "/status")
                if code == 200:
                    served[i] = json.loads(body)
            if fleet_doc is None:
                code, body = _probe(
                    fleet_port, "/fleet/status", timeout_s=10.0
                )
                if code == 200:
                    doc = json.loads(body)
                    named = {
                        r["process"]
                        for r in doc.get("straggler_table", [])
                    }
                    if named == set(range(processes)):
                        fleet_doc = doc
            if fleet_metrics is None:
                code, body = _probe(
                    fleet_port, "/fleet/metrics", timeout_s=10.0
                )
                if code == 200 and all(
                    f'mgwfbp_current_step{{process="{i}"}}' in body
                    for i in range(processes)
                ):
                    fleet_metrics = body
            time.sleep(0.1)
        runner.join(timeout=600)
        assert not runner.is_alive(), "supervised group wedged"
        rc = rc_box.get("rc")
        assert rc == 0, f"supervised group finished rc {rc}, want 0"
        assert set(served) == set(range(processes)), (
            f"live /status never answered on every per-process port "
            f"(base {base_port}): got {sorted(served)}"
        )
        for i, st in served.items():
            assert st["run"]["process_index"] == i, (i, st["run"])
        assert fleet_doc is not None, (
            "/fleet/status never served a live straggler table naming "
            f"every process (fleet port {fleet_port})"
        )
        assert fleet_doc["reachable"] == processes, fleet_doc
        assert fleet_metrics is not None, (
            "/fleet/metrics never merged every child under the process "
            "label"
        )
        # the http_sd sidecar persists the children's ACTUAL endpoints
        fleet_sd_path = os.path.join(d, "supervisor", "fleet.json")
        assert os.path.exists(fleet_sd_path), fleet_sd_path
        with open(fleet_sd_path) as f:
            sd = json.load(f)
        sd_procs = {g["labels"]["process"] for g in sd}
        assert sd_procs == {str(i) for i in range(processes)}, sd
        assert len(sup.results) == 2, (
            f"expected preempt + 1 resubmission, got "
            f"{[r.returncodes for r in sup.results]}"
        )
        assert sup.results[0].preempted, sup.results[0]
        assert sup.results[1].ok, sup.results[1]

        tag_dirs = [
            p for p in glob.glob(os.path.join(d, "*"))
            if os.path.isdir(p) and find_stream_paths(p)
        ]
        assert len(tag_dirs) == 1, f"expected one run dir, got {tag_dirs}"
        paths = find_stream_paths(tag_dirs[0])
        assert len(paths) == processes, (
            f"expected {processes} per-process streams, got {paths}"
        )
        merged = merge_streams(paths)
        check_monotonic(merged)
        pre = events_of(merged, "preempt")
        signals = {r["process"]: r["signal"] for r in pre}
        assert signals.get(1) == "SIGTERM", signals  # the signaled host
        assert signals.get(0) == "PEER", signals     # drained by agreement
        assert all(r["iteration"] == 4 for r in pre), pre
        resumes = events_of(merged, "resume")
        assert {r["process"] for r in resumes} == set(range(processes))
        assert all(
            r["mid_epoch"] and r["iteration"] == 4 for r in resumes
        ), resumes
        bad = events_of(merged, "bad_step")
        assert {r["process"] for r in bad} == set(range(processes))
        assert all(r["step"] == 2 for r in bad), bad
        for p in range(processes):
            last = max(
                r["step"] for r in events_of(merged, "step")
                if r["process"] == p
            )
            assert last == 12, f"process {p} stopped at step {last}"
        return {
            "fault_smoke": "ok",
            "processes": processes,
            "incarnations": [r.returncodes for r in sup.results],
            "merged_records": len(merged),
            "preempt_signals": signals,
            "metrics_ports": [base_port + i for i in range(processes)],
            "fleet_straggler_table": fleet_doc["straggler_table"],
            "fleet_sd_targets": sorted(
                t for g in sd for t in g["targets"]
            ),
        }


def resize_smoke(processes: int = 2, resize_to: int = 1) -> dict:
    """Elastic-resize lifecycle (ISSUE 13): a 2-process supervised group
    with ``--resize-to 1`` — the supervisor must TRIGGER the drain itself
    (SIGTERM once a child reports a completed step over /status), both
    processes must exit rc 75 with shard-native checkpoints committed
    exactly-once, the relaunched 1-process incarnation must find the
    2-process world's checkpoint under its sibling tag, re-shard it onto
    the new layout, emit the ``resize`` telemetry event, resume from the
    exact drained step, and finish — and the merged timeline across BOTH
    world sizes must stay monotonic. A hang anywhere fails check.sh's
    hard-timeout stage."""
    import threading

    from mgwfbp_tpu.runtime.supervisor import Supervisor, default_train_cmd
    from mgwfbp_tpu.telemetry import events_of, find_stream_paths
    from telemetry_merge import check_monotonic, merge_streams

    with tempfile.TemporaryDirectory(prefix="mgwfbp_resize_smoke_") as d:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["MGWFBP_HOST_DEVICES"] = "4"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        # the stall holds the run open so the supervisor's /status poll
        # reliably sees a completed step before the group finishes; the
        # drain itself comes from the supervisor, not the plan
        env["MGWFBP_FAULT_PLAN"] = "stall@secs=4,step=2"
        base_port = _free_port()
        env["MGWFBP_METRICS_PORT"] = str(base_port)
        fleet_port = _free_port()
        # rs_opt_ag: the opt state lives as 1/world shards — exactly the
        # state the shard-native format exists for; the 2-process save
        # must write per-process subtrees and the 1-process restore must
        # re-slice them, never a world-sized gather
        sup = Supervisor(
            default_train_cmd(_cli(d)[3:] + ["--comm-op", "rs_opt_ag"]),
            processes,
            backoff_base_s=0.2,
            log_dir=os.path.join(d, "supervisor"),
            env=env,
            fleet_port=fleet_port,
            resize_to=resize_to,
        )
        rc_box: dict = {}
        runner = threading.Thread(
            target=lambda: rc_box.update(rc=sup.run()), daemon=True
        )
        runner.start()
        # the transition is fleet-visible while it happens
        fleet_resize = None
        deadline = time.monotonic() + 560
        while runner.is_alive() and time.monotonic() < deadline:
            if fleet_resize is None:
                code, body = _probe(
                    fleet_port, "/fleet/status", timeout_s=10.0
                )
                if code == 200:
                    doc = json.loads(body)
                    if doc.get("resize"):
                        fleet_resize = doc["resize"]
            time.sleep(0.1)
        runner.join(timeout=600)
        assert not runner.is_alive(), "supervised resize group wedged"
        rc = rc_box.get("rc")
        assert rc == 0, f"supervised resize finished rc {rc}, want 0"
        assert len(sup.results) == 2, (
            f"expected drain + 1 resized incarnation, got "
            f"{[r.returncodes for r in sup.results]}"
        )
        assert sup.results[0].preempted, sup.results[0]
        assert len(sup.results[0].returncodes) == processes
        assert sup.results[1].ok, sup.results[1]
        assert len(sup.results[1].returncodes) == resize_to, (
            "resized incarnation launched at the wrong size:"
            f" {sup.results[1]}"
        )
        assert fleet_resize is not None, (
            "/fleet/status never surfaced the resize view"
        )
        assert fleet_resize["from"] == processes, fleet_resize
        assert fleet_resize["to"] == resize_to, fleet_resize

        # telemetry: streams from BOTH world sizes merge into one
        # monotonic timeline; the resized run records the transition
        tag_dirs = sorted(
            p for p in glob.glob(os.path.join(d, "*"))
            if os.path.isdir(p) and find_stream_paths(p)
        )
        assert len(tag_dirs) == 2, (
            f"expected one tag dir per world size, got {tag_dirs}"
        )
        paths = [p for t in tag_dirs for p in find_stream_paths(t)]
        assert len(paths) == processes + resize_to, paths
        merged = merge_streams(paths)
        check_monotonic(merged)
        resizes = events_of(merged, "resize")
        assert resizes, "no resize telemetry event recorded"
        rz = resizes[-1]
        assert rz["old_world"] == processes * 4, rz
        assert rz["new_world"] == resize_to * 4, rz
        assert rz["schedule_source"] == "relaunch-reshard", rz
        pre = events_of(merged, "preempt")
        assert len(pre) == processes, pre
        drained_iter = pre[0]["iteration"]
        assert all(r["iteration"] == drained_iter for r in pre), pre
        resumes = events_of(merged, "resume")
        assert resumes and resumes[-1]["iteration"] == drained_iter, (
            f"resumed at {resumes}, drained at {drained_iter}"
        )
        steps = [r["step"] for r in events_of(merged, "step")]
        assert max(steps) == 12, (
            f"resized run stopped at step {max(steps)}, want 12"
        )
        # shard-native payload really is per-process: the 2-process
        # world's committed step holds one subtree PER PROCESS whose
        # files carry exactly that process's shard rows — nothing
        # world-sized anywhere on disk
        n8_tag = [
            t for t in glob.glob(os.path.join(d, "ckpt", "*"))
            if "-n8-" in os.path.basename(t)
        ]
        assert n8_tag, os.listdir(os.path.join(d, "ckpt"))
        shard_steps = glob.glob(
            os.path.join(n8_tag[0], "sharded", "*", "manifest.json")
        )
        assert shard_steps, "2-process run committed no shard-native step"
        import numpy as _np

        with open(shard_steps[-1]) as f:
            manifest = json.load(f)
        rows = {
            p: doc["rows"] for p, doc in manifest["processes"].items()
        }
        assert sorted(r for v in rows.values() for r in v) == list(
            range(manifest["world"])
        ), rows
        step_dir = os.path.dirname(shard_steps[-1])
        for p, prows in rows.items():
            pdir = os.path.join(step_dir, f"p{int(p):05d}")
            for gi, shard in enumerate(manifest["layout"]["shard_sizes"]):
                arr = _np.load(
                    os.path.join(pdir, f"opt.s0.g{gi}.npy"), mmap_mode="r"
                )
                assert arr.shape == (len(prows), shard), (
                    p, gi, arr.shape, (len(prows), shard),
                )
        return {
            "fault_smoke": "ok",
            "mode": "resize",
            "incarnations": [r.returncodes for r in sup.results],
            "drained_iteration": drained_iter,
            "resize_event": {
                k: rz[k] for k in (
                    "old_world", "new_world", "schedule_source",
                )
            },
            "fleet_resize_view": fleet_resize,
            "merged_records": len(merged),
        }


def async_ckpt_smoke() -> dict:
    """ISSUE 16: the async shard writer's cost + event contract, on two
    clean (fault-free) runs. The async run must (a) write every
    mid-epoch --ckpt-every-steps checkpoint through the background
    writer (events carry async:true with the commit iteration), with at
    least one payload write demonstrably overlapping training (commit
    landing at a later iteration than the submit), and (b) keep
    post-warmup step time within noise of a checkpoints-OFF run — the
    step loop pays the shard-row snapshot and the group-agreed
    preamble, never the np.save."""
    from mgwfbp_tpu.telemetry import events_of

    def _post_warmup_median_step_s(d: str) -> float:
        steps = sorted(
            events_of(_events(d), "step"), key=lambda r: r["step"]
        )
        assert len(steps) >= 8, f"run too short: {len(steps)} steps"
        durs = sorted(float(r["dur_s"]) for r in steps[2:])
        return durs[len(durs) // 2]

    with tempfile.TemporaryDirectory(prefix="mgwfbp_async_off_") as d:
        rc, _ = _run(d, "", ckpt=False)
        assert rc == 0, f"ckpt-off run exited rc {rc}"
        off_median = _post_warmup_median_step_s(d)
    with tempfile.TemporaryDirectory(prefix="mgwfbp_async_on_") as d:
        rc, _ = _run(d, "")
        assert rc == 0, f"async-ckpt run exited rc {rc}"
        on_median = _post_warmup_median_step_s(d)
        recs = _events(d)
        mids = [
            c for c in events_of(recs, "checkpoint")
            if c.get("mid_epoch")
        ]
        assert mids, "no mid-epoch checkpoint events"
        assert all(c.get("async") for c in mids), (
            f"mid-epoch saves bypassed the async writer: {mids}"
        )
        assert all(
            int(c["commit_iteration"]) >= int(c["iteration"])
            for c in mids
        ), mids
        overlapped = [
            c for c in mids
            if int(c["commit_iteration"]) > int(c["iteration"])
        ]
        assert overlapped, (
            "every async save committed within its own submit step — "
            f"the payload write never overlapped training: {mids}"
        )
        # durations span submit -> commit, so each overlapping save's
        # duration covers at least the steps it rode over
        assert all(float(c["duration_s"]) > 0 for c in mids), mids
    # "within noise": a generous envelope (CPU CI boxes jitter), but one
    # a synchronous world-blocking save would still trip if the payload
    # write sat on the step path for a multi-ms np.save per 2 steps
    assert on_median <= off_median * 3.0 + 0.05, (
        f"async-ckpt median step {on_median * 1e3:.2f} ms vs ckpt-off "
        f"{off_median * 1e3:.2f} ms — the writer is back on the step "
        "path"
    )
    return {
        "async_ckpt_smoke": "ok",
        "ckpt_off_median_step_ms": round(off_median * 1e3, 3),
        "async_median_step_ms": round(on_median * 1e3, 3),
        "async_saves": len(mids),
        "overlapping_saves": len(overlapped),
        "max_overlap_steps": max(
            int(c["commit_iteration"]) - int(c["iteration"])
            for c in mids
        ),
    }


def chaos_smoke() -> dict:
    """ISSUE 20: the self-healing supervisor, end to end, against DRAIN-
    LESS faults — failures that never say goodbye, which the agreed-
    preempt machinery alone cannot survive.

    Scenario A (kill -> shrink): a 2-process group; ``kill@step=4,
    proc=1`` SIGKILLs process 1 mid-epoch (no drain, no checkpoint, no
    peer agreement). The survivor is left blocked in the merged
    collective; its ``MGWFBP_COORD_TIMEOUT_S`` deadline must convert
    the dead-peer hang into a clean rc-75 exit, the supervisor must
    classify the -9 as oom_kill and SHRINK to the 1 survivor (elastic
    resume off the last COMMITTED shard-native step — the manifest is
    the commit marker, so the resumed iteration is pinned against the
    manifests actually on disk), and the resumed world must finish all
    12 steps. failure/heal events land in the supervisor's own
    telemetry stream and the merged timeline across BOTH world sizes
    plus the supervisor stream stays monotonic.

    Scenario B (wedge -> bounded heal): ``wedge@step=3,secs=300,
    proc=1`` stops process 1 stepping for 300 s while it KEEPS serving
    HTTP — invisible to waitpid, invisible to /healthz. Only the
    liveness monitor (/status step frozen past MGWFBP_LIVENESS_GRACE_S)
    can see it; the group must be SIGTERMed, drain rc 75, relaunch at
    the same world, and finish — in wall-clock time far under both the
    300 s wedge and the 600 s barrier default. A slow detector or a
    barrier-length hang fails the elapsed-time pin (and check.sh's
    hard timeout)."""
    import threading

    from mgwfbp_tpu.runtime.supervisor import Supervisor, default_train_cmd
    from mgwfbp_tpu.telemetry import events_of, find_stream_paths
    from telemetry_merge import check_monotonic, merge_streams

    out: dict = {"fault_smoke": "ok", "mode": "chaos"}

    # ---- Scenario A: SIGKILL mid-epoch -> shrink to survivors --------
    with tempfile.TemporaryDirectory(prefix="mgwfbp_chaos_kill_") as d:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["MGWFBP_HOST_DEVICES"] = "4"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        # drain-less: process 1 dies with SIGKILL the moment it has
        # stepped past 4 — the inc=0 default keeps the fault out of the
        # healed incarnation (a drain-less fault resumes BELOW its own
        # step and would re-fire forever otherwise)
        env["MGWFBP_FAULT_PLAN"] = "kill@step=4,proc=1"
        # the survivor must give up on the dead peer's collective in
        # seconds, not DEFAULT_BARRIER_TIMEOUT_S — the bounded
        # coordination contract is half of what this scenario pins
        env["MGWFBP_COORD_TIMEOUT_S"] = "20"
        env["MGWFBP_METRICS_PORT"] = str(_free_port())
        # rs_opt_ag: sharded opt state, so the shrink really re-shards
        sup = Supervisor(
            default_train_cmd(_cli(d)[3:] + ["--comm-op", "rs_opt_ag"]),
            2,
            backoff_base_s=0.2,
            drain_grace_s=90.0,
            log_dir=os.path.join(d, "supervisor"),
            env=env,
        )
        rc_box: dict = {}
        runner = threading.Thread(
            target=lambda: rc_box.update(rc=sup.run()), daemon=True
        )
        runner.start()
        runner.join(timeout=600)
        assert not runner.is_alive(), "chaos kill group wedged"
        rc = rc_box.get("rc")
        assert rc == 0, f"chaos kill run finished rc {rc}, want 0"
        assert len(sup.results) == 2, (
            f"expected kill + 1 healed incarnation, got "
            f"{[r.returncodes for r in sup.results]}"
        )
        rcs0 = sup.results[0].returncodes
        assert rcs0[1] == -9, f"process 1 did not die by SIGKILL: {rcs0}"
        assert rcs0[0] == PREEMPT_RC, (
            f"survivor exited rc {rcs0[0]}, want {PREEMPT_RC} — the "
            "coordination deadline did not convert the dead-peer hang "
            "into a restart-friendly exit"
        )
        assert sup.processes == 1, (
            f"supervisor did not shrink to the survivor: {sup.processes}"
        )
        r1 = sup.results[1]
        assert r1.ok and len(r1.returncodes) == 1, r1

        # the commit marker is the manifest: resumed iteration must be
        # the LAST committed shard-native step of the 2-process world
        n8_tag = [
            t for t in glob.glob(os.path.join(d, "ckpt", "*"))
            if "-n8-" in os.path.basename(t)
        ]
        assert n8_tag, os.listdir(os.path.join(d, "ckpt"))
        committed = sorted(
            int(json.load(open(m))["step"]) for m in glob.glob(
                os.path.join(n8_tag[0], "sharded", "*", "manifest.json")
            )
        )
        assert committed, "no committed shard-native step survived"

        sup_stream = os.path.join(
            d, "supervisor", "telemetry.supervisor.jsonl"
        )
        assert os.path.exists(sup_stream), (
            "supervisor telemetry stream missing"
        )
        tag_dirs = sorted(
            p for p in glob.glob(os.path.join(d, "*"))
            if os.path.isdir(p) and find_stream_paths(p)
        )
        assert len(tag_dirs) == 2, (
            f"expected one tag dir per world size, got {tag_dirs}"
        )
        paths = [p for t in tag_dirs for p in find_stream_paths(t)]
        assert len(paths) == 3, paths  # 2 streams at n8, 1 at n4
        merged = merge_streams(paths + [sup_stream])
        check_monotonic(merged)
        fails = events_of(merged, "failure")
        oom = [r for r in fails if r["class"] == "oom_kill"]
        assert oom and oom[0]["target"] == "p1", fails
        assert oom[0]["process"] == -1, oom  # the supervisor's verdict
        heals = events_of(merged, "heal")
        shrinks = [r for r in heals if r["action"] == "shrink"]
        assert shrinks, heals
        assert shrinks[0]["old_world"] == 2, shrinks
        assert shrinks[0]["world"] == 1, shrinks
        resumes = events_of(merged, "resize")
        assert resumes and resumes[-1]["old_world"] == 8, resumes
        assert resumes[-1]["new_world"] == 4, resumes
        resumed = events_of(merged, "resume")
        assert resumed, "healed incarnation recorded no resume event"
        assert resumed[-1]["iteration"] == committed[-1], (
            f"resumed at iteration {resumed[-1]['iteration']}, but the "
            f"last committed shard-native step is {committed[-1]}"
        )
        last_step = max(r["step"] for r in events_of(merged, "step"))
        assert last_step == 12, (
            f"shrunk world stopped at step {last_step}, want 12"
        )
        out["kill"] = {
            "incarnations": [r.returncodes for r in sup.results],
            "shrunk_to": sup.processes,
            "committed_steps": committed,
            "resumed_iteration": resumed[-1]["iteration"],
            "merged_records": len(merged),
        }

    # ---- Scenario B: wedge -> liveness verdict -> bounded heal -------
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="mgwfbp_chaos_wedge_") as d:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["MGWFBP_HOST_DEVICES"] = "4"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        # process 1 stops stepping for 300 s at step 3 but keeps serving
        # HTTP — only the liveness monitor's frozen-step verdict can
        # see this failure class
        env["MGWFBP_FAULT_PLAN"] = "wedge@step=3,secs=300,proc=1"
        env["MGWFBP_LIVENESS_GRACE_S"] = "6"
        env["MGWFBP_COORD_TIMEOUT_S"] = "60"
        env["MGWFBP_METRICS_PORT"] = str(_free_port())
        sup = Supervisor(
            default_train_cmd(_cli(d)[3:]),
            2,
            backoff_base_s=0.2,
            drain_grace_s=90.0,
            log_dir=os.path.join(d, "supervisor"),
            env=env,
        )
        rc_box = {}
        runner = threading.Thread(
            target=lambda: rc_box.update(rc=sup.run()), daemon=True
        )
        runner.start()
        runner.join(timeout=600)
        assert not runner.is_alive(), "chaos wedge group wedged for real"
        healed_in = time.monotonic() - t0
        rc = rc_box.get("rc")
        assert rc == 0, f"chaos wedge run finished rc {rc}, want 0"
        # bounded: the heal must land in wall-clock time far under both
        # the 300 s wedge and the 600 s barrier default — this elapsed
        # pin is what makes "detected and healed in bounded time" a
        # checked property instead of a hope
        assert healed_in < 240, (
            f"wedge heal took {healed_in:.0f}s — the liveness monitor "
            "is not bounding detection"
        )
        assert len(sup.results) == 2, (
            f"expected wedge + 1 healed incarnation, got "
            f"{[r.returncodes for r in sup.results]}"
        )
        assert sup.results[0].returncodes == [PREEMPT_RC, PREEMPT_RC], (
            f"SIGTERMed group did not drain restart-friendly: "
            f"{sup.results[0].returncodes}"
        )
        assert sup.processes == 2, "wedge heal must NOT shrink the world"
        r1 = sup.results[1]
        assert r1.ok and len(r1.returncodes) == 2, r1

        sup_stream = os.path.join(
            d, "supervisor", "telemetry.supervisor.jsonl"
        )
        tag_dirs = sorted(
            p for p in glob.glob(os.path.join(d, "*"))
            if os.path.isdir(p) and find_stream_paths(p)
        )
        assert len(tag_dirs) == 1, tag_dirs  # same world both times
        paths = find_stream_paths(tag_dirs[0])
        assert len(paths) == 2, paths
        merged = merge_streams(paths + [sup_stream])
        check_monotonic(merged)
        fails = events_of(merged, "failure")
        wedged = [r for r in fails if r["class"] == "wedged"]
        # the wedged process freezes its peer at the next merged
        # collective inside the same grace window, so the verdict names
        # the frozen SET — the actually-wedged p1 must be in it
        assert wedged and "p1" in wedged[0]["target"].split(","), fails
        assert wedged[0]["process"] == -1, wedged  # the monitor's verdict
        heals = events_of(merged, "heal")
        rel = [r for r in heals if r["action"] == "relaunch"]
        assert rel and rel[0]["world"] == 2, heals
        resumed = events_of(merged, "resume")
        assert {r["process"] for r in resumed} == {0, 1}, resumed
        for p in range(2):
            last = max(
                r["step"] for r in events_of(merged, "step")
                if r["process"] == p
            )
            assert last == 12, f"process {p} stopped at step {last}"
        out["wedge"] = {
            "incarnations": [r.returncodes for r in sup.results],
            "healed_in_s": round(healed_in, 1),
            "wedged_failure_step": wedged[0].get("step"),
            "merged_records": len(merged),
        }
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--processes", type=int, default=1,
                    help="1 = single-process lifecycle (default); >1 = "
                         "supervised multi-host group with an agreed "
                         "drain + auto-resubmit")
    ap.add_argument("--resize", action="store_true",
                    help="elastic-resize lifecycle: 2-process supervised "
                         "group drained by the supervisor's --resize-to "
                         "policy, relaunched at 1 process from the "
                         "shard-native checkpoint, resumed to completion")
    ap.add_argument("--async-ckpt", action="store_true",
                    dest="async_ckpt",
                    help="async shard-writer lifecycle (ISSUE 16): "
                         "checkpoints-off vs async-ckpt step-time "
                         "envelope + async checkpoint event contract")
    ap.add_argument("--chaos", action="store_true",
                    help="self-healing lifecycle (ISSUE 20): SIGKILL a "
                         "process mid-epoch (supervisor shrinks to the "
                         "survivors off the last committed shard-native "
                         "step) and wedge one (liveness monitor heals "
                         "the group in bounded time)")
    args = ap.parse_args()
    if args.chaos:
        out = chaos_smoke()
    elif args.async_ckpt:
        out = async_ckpt_smoke()
    elif args.resize:
        out = resize_smoke(max(args.processes, 2), 1)
    elif args.processes > 1:
        out = multi_process(args.processes)
    else:
        out = single_process()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
