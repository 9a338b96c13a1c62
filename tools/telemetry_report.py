"""Telemetry-stream report: overlap table + run trend from one events file.

Reads a run's telemetry JSONL (written by `mgwfbp_tpu.telemetry`, enabled
with ``--telemetry`` on the train CLI) and prints:

  * the run header (model/world/comm_op/policy);
  * what set-up was made of, from the process's start to the first step's
    results (the `setup` record of telemetry/phases.py): each span under its
    parent with its seconds and its self time, and the compile counters;
  * the step-time trend — span count, mean/min/max seconds per step, first
    vs last 10-span window (throughput drift over the run);
  * the per-merge-group exposed/hidden comm table from the latest overlap
    snapshot, with the attribution source (``trace`` on backends whose op
    metadata keeps the `mgwfbp_groupNNNN` scopes; ``cost-model`` on the
    CPU mesh, whose traces drop the name stack);
  * the aggregate overlap-efficiency number (hidden / total comm — the
    paper's headline metric);
  * the alarms table — cost-model drift rows (kind, merge group, residual
    vs band) and live straggler rows (slow process, excess) from the
    drift detector / multi-host probe (telemetry/drift.py), raise and
    clear edges both;
  * lifecycle events: resizes (and which schedule path won), checkpoints,
    autotune race rows, watchdog stalls, bench skips.

Optionally renders the same stream for external viewers:

  python tools/telemetry_report.py <run>/telemetry.jsonl
  python tools/telemetry_report.py <run>/telemetry.jsonl \
      --chrome-trace trace.json --prometheus metrics.prom
  python tools/telemetry_report.py --live http://host:port   # RUNNING job
  python tools/telemetry_report.py --selftest   # synthetic stream smoke

``--live`` renders the overlap/alarms/lifecycle view from a RUNNING
job's /status + /metrics endpoints (telemetry/serve.py) instead of JSONL
files; pointed at a supervisor's fleet fan-in it renders the group view
(/fleet/status: per-process table, live stragglers, fleet alarms).

``--selftest`` exercises the full pipeline (writer -> reader -> report ->
Chrome trace -> Prometheus) on a synthetic stream in a temp dir — the
standing-gate smoke tools/check.sh runs, no accelerator or dataset needed.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fmt_s(v) -> str:
    return f"{v:.6g}" if isinstance(v, (int, float)) else "n/a"


def _window_mean(spans: list[dict], sl: slice) -> float:
    w = spans[sl]
    return sum(float(s["dur_s"]) for s in w) / max(len(w), 1)


def _phase_section(steps: list[dict], scans: list[dict] = (),
                   deltas: list[dict] = (),
                   convs: list[dict] = (),
                   streams: list[dict] = (),
                   ssds: list[dict] = ()) -> list[str]:
    """One table of the host loop's phases (telemetry/phases.py): median
    milliseconds over the steps that have the phase, and the share of the
    loop's time (first span's start to last span's end) all its entries
    took. The dispatch is the step record's own start_s / dur_s. Under the
    table, the counters: `ready` (was the prefetch pool ahead of the loop),
    `native` (did the pool's batches come from the native transform pass)
    and `stats_ready` (had the health drain's arrays finished when it asked;
    where not, the drain is where the host waited for the chip), and a
    sparse-expert model's routing counters (`moe_here`, `moe_load_max` over
    `moe_load_mean`, `moe_dropped`), and a decoder-hybrid-decoder's
    (`sel_scan_state_rms`, `gmu_gate_rms`, `diff_lambda_mean`) with the way
    its selective scans went down (`scans`: the `scan_program` records, of
    which the newest built step program's is said), and a hybrid
    linear-attention decoder's (`delta_state_rms`, `delta_beta_mean`,
    `shared_gate_mean`) with the way its delta rules did (`deltas`: the
    `delta_program` records), and a decoder's with several residual streams,
    latent attention and a selection bias (`mhc_res_gap`, `mhc_res_offdiag`,
    `mla_kv_latent_rms`, `moe_bias_swap_share`), and a decoder's whose
    experts compute in a latent (`moe_latent_rms`, `moe_relu2_active`, with
    the selection bias's share on its line too), and the way the short
    convolutions of any of
    them did (`convs`: the `conv_program` records; no line where the newest
    counts none), the passes over several residual streams (`streams`:
    the `streams_program` records, likewise) and the chunked state-space
    scans (`ssds`: the `ssd_program` records, likewise)."""
    from mgwfbp_tpu.telemetry.phases import PHASES

    spans: dict[str, list[tuple[float, float]]] = {}
    for s in steps:
        if not s.get("phases"):
            continue
        spans.setdefault("dispatch", []).append(
            (float(s["start_s"]), float(s["dur_s"])))
        for name, (start_s, dur_s) in s["phases"].items():
            spans.setdefault(name, []).append((float(start_s), float(dur_s)))
    if not spans:
        return []
    every = [sp for rows in spans.values() for sp in rows]
    loop_s = max(a + d for a, d in every) - min(a for a, _ in every)
    order = [*PHASES[:3], "dispatch", *PHASES[3:]]
    order += sorted(set(spans) - set(order))
    lines = ["", f"host loop phases ({len(spans['dispatch'])} steps, "
             f"{_fmt_s(loop_s)} s):",
             f"  {'phase':>9} {'steps':>6} {'median_ms':>10} {'share':>7}"]
    covered = 0.0
    for name in order:
        if name not in spans:
            continue
        durs = [d for _, d in spans[name]]
        total = sum(durs)
        covered += total
        lines.append(
            f"  {name:>9} {len(durs):>6} "
            f"{statistics.median(durs) * 1e3:>10.3f} "
            f"{100.0 * total / max(loop_s, 1e-12):>6.1f}%")
    lines.append(
        f"  {'(no span)':>9} {'':>6} {'':>10} "
        f"{100.0 * max(loop_s - covered, 0.0) / max(loop_s, 1e-12):>6.1f}%")
    ready = [int(s["ready"]) for s in steps if "ready" in s]
    if ready:
        lines.append(
            f"  prefetch pool: {sum(ready) / len(ready):.2f} batches ready "
            f"when the loop asked (none on {ready.count(0)} of {len(ready)} "
            "steps)")
    native = [int(s["native"]) for s in steps if "native" in s]
    if native:
        lines.append(
            f"  native transform: the pool's batches came from the native "
            f"pass on {sum(native)} of {len(native)} steps "
            f"(share {sum(native) / len(native):.3f})")
    stats = [int(s["stats_ready"]) for s in steps if "stats_ready" in s]
    if stats:
        lines.append(
            f"  health statistics: finished when the drain asked on "
            f"{sum(stats)} of {len(stats)} steps "
            f"({100.0 * sum(stats) / len(stats):.1f}%)")
    routed = [s for s in steps if "moe_here" in s]
    if routed:
        # a sparse-expert model's routing counters (telemetry/phases.py)
        here = sum(s["moe_here"] for s in routed) / len(routed)
        imbalance = [
            s["moe_load_max"] / s["moe_load_mean"] for s in routed
            if s["moe_load_mean"]]
        lines.append(
            f"  expert routing ({len(routed)} steps): {100.0 * here:.2f}% of "
            "the assignments landed on experts held here; fullest held "
            f"expert {sum(imbalance) / max(len(imbalance), 1):.3f}x the "
            f"average (worst step {max(imbalance, default=0.0):.3f}x); "
            f"{sum(s['moe_dropped'] for s in routed):g} dropped")
    # a model family's own counters, each the mean over the steps that have
    # it, then the way its recurrences went down, off the newest record of
    # the built step program (`scans`, `deltas`): a decoder-hybrid-decoder's
    # (models/phi4flash.py), a hybrid linear-attention decoder's
    # (models/qwen3next.py), a decoder's under several residual streams
    # (models/xing4.py: no recurrence; its passes' record has a line below)
    for title, counters, programs, kernel, plain in (
            ("hybrid decoder", (
                ("sel_scan_state_rms", "selective scan's final state rms"),
                ("gmu_gate_rms", "gated memory rms"),
                ("diff_lambda_mean", "differential lambda")), scans,
             "selective scan(s) through the kernels", "the chunked form"),
            ("linear attention", (
                ("delta_state_rms", "delta rule's final state rms"),
                ("delta_beta_mean", "write gate beta"),
                ("shared_gate_mean", "shared expert's gate")), deltas,
             "gated delta rule(s) through a kernel",
             "the plain chunked form"),
            ("residual streams", (
                ("mhc_res_gap", "H_res's largest row or column sum off one"),
                ("mhc_res_offdiag", "H_res's mass off its diagonal"),
                ("mla_kv_latent_rms", "latent c_kv's rms"),
                ("moe_bias_swap_share",
                 "share of choices the selection bias made")), (), "", ""),
            # models/nemotronh.py: its scans' record has a line below
            ("latent experts", (
                ("moe_latent_rms", "latent's rms"),
                ("moe_relu2_active", "share of hidden units relu left on"),
                ("moe_bias_swap_share",
                 "share of choices the selection bias made")), (), "", "")):
        values = {said: [s[name] for s in steps if name in s]
                  for name, said in counters}
        # the selection bias's share is two families': alone it names none
        if not any(v for (name, _), v in zip(counters, values.values())
                   if name != "moe_bias_swap_share"):
            continue
        says = [f"{said} {sum(v) / len(v):.4g}"
                for said, v in values.items() if v]
        for prog in programs[-1:]:
            says.append(
                f"{prog.get('kernel')} {kernel} with the state in VMEM "
                f"({prog.get('programs')} distinct kernel program(s)), "
                f"{prog.get('plain')} through {plain}")
        lines.append(
            f"  {title} ({max(map(len, values.values()))} steps): "
            + "; ".join(says))
    for title, kernels, programs in (
            ("short convolution", "kernels of one pass", convs),
            ("streams' passes", "kernels of one read", streams),
            ("state-space scan", "kernels with the state in VMEM", ssds)):
        for prog in programs[-1:]:
            if prog.get("kernel") or prog.get("plain"):
                lines.append(
                    f"  {title}: {prog.get('kernel')} through the "
                    f"{kernels} ({prog.get('programs')} distinct kernel "
                    f"program(s)), {prog.get('plain')} through the plain "
                    f"form")
    return lines


def _setup_section(records: list[dict]) -> list[str]:
    """One table a `setup` record (telemetry/phases.py): its spans in the
    order they started, each under the span that caused it, with its seconds
    and its self time (its seconds less what its children cover); under the
    table, the record's counters and its slowest monitoring events."""
    from mgwfbp_tpu.telemetry.phases import self_times

    lines: list[str] = []
    for record in records:
        spans = record["spans"]
        own = self_times(spans)
        children: dict = {}
        for name, (start_s, _, parent) in sorted(
                spans.items(), key=lambda kv: kv[1][0]):
            children.setdefault(parent if parent in spans else None,
                                []).append(name)
        whole = "set-up" if "setup" in spans else "step rebuilt"
        lines += ["", f"{whole} (process started at wall "
                  f"{record['origin_wall']}):",
                  f"  {'span':<28} {'start_s':>10} {'seconds':>9} "
                  f"{'self_s':>9}"]

        def rows(parent, depth: int) -> None:
            for name in children.get(parent, ()):
                start_s, dur_s, _ = spans[name]
                lines.append(
                    f"  {'  ' * depth + name:<28} {start_s:>10.3f} "
                    f"{dur_s:>9.3f} {own[name]:>9.3f}")
                rows(name, depth + 1)

        rows(None, 0)
        c = record["counters"]
        lines.append(
            f"  programs: {c['programs_traced']} traced, "
            f"{c['programs_lowered']} lowered, {c['programs_compiled']} "
            f"compiled, {c['cache_loads']} loaded from the compile cache, "
            f"{c['small_compiles']} too small for it "
            f"({c['small_compile_s']:.3f} s)"
            + (f"; traces nested in the step's {c['kernel_trace_s']:.3f} s"
               if "kernel_trace_s" in c else ""))
        for event, function, secs in c["slow_events"][:8]:
            lines.append(f"    {secs:>8.3f} s  {event}  {function}")
    return lines


def format_report(records: list[dict]) -> str:
    from mgwfbp_tpu.telemetry import events_of

    lines: list[str] = []
    header = next(iter(events_of(records, "header")), {})
    run = header.get("run", {}) or {}
    desc = ", ".join(f"{k}={v}" for k, v in sorted(run.items()))
    lines.append(
        f"telemetry stream: schema v{header.get('schema_version', '?')}"
        + (f" ({desc})" if desc else "")
    )

    steps = events_of(records, "step")
    lines.extend(_setup_section(events_of(records, "setup")))
    for prog in events_of(records, "step_program"):
        lines.append(
            f"step program (built by step {prog.get('step')}): "
            f"{prog.get('collectives')} collectives, "
            f"{prog.get('async_collectives')} asynchronous; compile options: "
            + (", ".join(prog.get("compiler_options") or ()) or "none")
        )
    for rec in events_of(records, "step_scopes"):
        from mgwfbp_tpu.profiling import split_lines

        lines.append(
            f"step by scope (profile window of {rec.get('steps')} step(s) "
            f"to step {rec.get('step')}, ms of device ops a step):")
        lines.extend("  " + line for line in split_lines({
            "layers": {}, "events": 0, **rec,
            "total_ms": rec["exchange"]["device_ms"]
            + sum(map(sum, rec["scopes"].values()))}))
    for prog in events_of(records, "attention_program"):
        lines.append(
            f"attention (step program built by step {prog.get('step')}): "
            f"{prog.get('kernel')} core(s) through the fused kernel, "
            f"{prog.get('blocks')} through the plain blocks"
        )
    for prog in events_of(records, "experts_program"):
        lines.append(
            f"experts (step program built by step {prog.get('step')}): "
            f"{prog.get('kernel')} grouped product(s) through the tiled "
            f"kernel ({prog.get('programs')} distinct kernel program(s)), "
            f"{prog.get('ragged')} through ragged_dot; "
            f"{prog.get('rows_held')} row permutation(s) moving only the "
            f"rows in a group ({prog.get('rows_programs')} distinct kernel "
            f"program(s)), {prog.get('rows_all')} moving every assignment's "
            f"row; {prog.get('bounded')} expert block(s) grouping a token's "
            f"held choices alone (tokens x experts held rows), "
            f"{prog.get('whole')} every choice (tokens x k rows)"
        )
    if steps:
        durs = [float(s["dur_s"]) for s in steps]
        lines.append("")
        lines.append(
            f"steps: {len(steps)} spans, mean {_fmt_s(sum(durs)/len(durs))} "
            f"s/step (min {_fmt_s(min(durs))}, max {_fmt_s(max(durs))})"
        )
        if len(steps) >= 20:
            first = _window_mean(steps, slice(0, 10))
            last = _window_mean(steps, slice(-10, None))
            drift = (last - first) / first * 100.0 if first > 0 else 0.0
            lines.append(
                f"trend: first-10 {_fmt_s(first)} s -> last-10 "
                f"{_fmt_s(last)} s ({drift:+.1f}%)"
            )
        lines.extend(_phase_section(
            steps, events_of(records, "scan_program"),
            events_of(records, "delta_program"),
            events_of(records, "conv_program"),
            events_of(records, "streams_program"),
            events_of(records, "ssd_program")))
    else:
        lines.append("steps: none recorded")

    from mgwfbp_tpu.telemetry.export import latest_snapshot

    snap, rows = latest_snapshot(records)
    if snap is not None:
        lines.append("")
        lines.append(
            f"overlap snapshot (step {snap.get('step')}, attribution="
            f"{snap.get('attribution')}):"
        )
        cross = float(snap.get("tf_total_s", 0.0) or 0.0) > 0.0
        hier = float(snap.get("dcn_s", 0.0) or 0.0) > 0.0
        lines.append(
            f"  {'group':>5} {'bytes':>12} {'comm_s':>10} {'hidden_s':>10} "
            f"{'exposed_s':>10}"
            + (f" {'ag_s':>10}" if cross else "")
            + (f" {'ici_s':>10} {'dcn_s':>10}" if hier else "")
        )
        for r in rows:
            row = (
                f"  {int(r['group']):>5} {int(r['nbytes']):>12} "
                f"{_fmt_s(r['comm_s']):>10} {_fmt_s(r['hidden_s']):>10} "
                f"{_fmt_s(r['exposed_s']):>10}"
            )
            if cross:
                # cross-step regime: ag_s is the deferred all-gather leg
                # riding the NEXT step's forward
                row += f" {_fmt_s(r.get('ag_s', 0.0)):>10}"
            if hier:
                # hierarchical regime: each group's comm split by LINK
                row += (
                    f" {_fmt_s(r.get('ici_s', 0.0)):>10} "
                    f"{_fmt_s(r.get('dcn_s', 0.0)):>10}"
                )
            lines.append(row)
        tail = (
            f"(forward {_fmt_s(snap.get('tf_total_s'))} s, backward "
            if cross
            else "(backward "
        )
        lines.append(
            f"  total comm {_fmt_s(snap.get('comm_s'))} s = hidden "
            f"{_fmt_s(snap.get('hidden_s'))} s + exposed "
            f"{_fmt_s(snap.get('exposed_s'))} s "
            + tail
            + f"{_fmt_s(snap.get('tb_total_s'))} s, step "
            f"{_fmt_s(snap.get('step_s'))} s)"
        )
        if cross:
            lines.append(
                "  cross-step regime (rs_fwd_ag): each group's AG is "
                "deferred into the next step's forward; hidden counts "
                "both forward- and backward-side overlap"
            )
        if hier:
            lines.append(
                "  hierarchical regime (hier): comm split by link — ici "
                f"{_fmt_s(snap.get('ici_s'))} s vs dcn "
                f"{_fmt_s(snap.get('dcn_s'))} s; bottleneck link: "
                f"{snap.get('bottleneck_link')}"
            )
        lines.append(
            f"overlap efficiency: {float(snap.get('efficiency', 0.0)):.4f} "
            "(hidden / total comm; 1.0 = fully hidden)"
        )
    else:
        lines.append("")
        lines.append("overlap: no snapshot recorded (single-device run, "
                     "policy 'none', or telemetry off during fit)")

    lines.extend(_health_section(records))

    alarms = events_of(records, "drift_alarm", "straggler", "health_alarm")
    if alarms:
        lines.append("")
        lines.append("alarms:")
        lines.append(
            f"  {'kind':>17} {'edge':>6} {'group/proc':>10} "
            f"{'residual':>10} {'band':>8} {'step':>8}"
        )
        for r in alarms:
            if r.get("event") == "drift_alarm":
                kind = str(r.get("kind"))
                who = (
                    str(r.get("group"))
                    if int(r.get("group", -1)) >= 0 else "agg"
                )
                residual = _fmt_s(r.get("residual"))
                band = _fmt_s(r.get("band"))
            elif r.get("event") == "health_alarm":
                kind = str(r.get("kind"))
                who = (
                    str(r.get("group"))
                    if int(r.get("group", -1)) >= 0 else "agg"
                )
                residual = _fmt_s(r.get("value"))
                band = _fmt_s(r.get("band"))
            else:
                kind = "straggler"
                who = f"p{r.get('slow_process')}"
                residual = _fmt_s(r.get("excess_s"))
                band = "-"
            lines.append(
                f"  {kind:>17} "
                f"{'RAISE' if r.get('active') else 'clear':>6} "
                f"{who:>10} {residual:>10} {band:>8} "
                f"{str(r.get('step', '-')):>8}"
            )

    lifecycle = []
    for ev, render in (
        ("resize", lambda r: (
            f"resize {r.get('old_world')} -> {r.get('new_world')} "
            f"({r.get('schedule_source')}, {r.get('num_groups')} groups)")),
        ("checkpoint", _ckpt_line),
        ("autotune_race", lambda r: (
            f"autotune race {r.get('label')}: "
            f"{_fmt_s(r.get('measured_step_s'))} s/step "
            f"({'verified' if r.get('verified') else 'rejected'})")),
        ("autotune_commit", lambda r: (
            f"autotune commit {r.get('winner')} "
            f"({r.get('comm_op')}, {r.get('num_groups')} groups, "
            f"source={r.get('source')})")),
        ("watchdog_stall", lambda r: (
            f"WATCHDOG STALL in {r.get('phase')!r} after "
            f"{_fmt_s(r.get('idle_s'))} s"
            + (" (aborted)" if r.get("abort") else ""))),
        ("bad_step", lambda r: (
            f"BAD STEP {r.get('step')} (epoch {r.get('epoch')}): "
            f"{_fmt_s(r.get('nonfinite'))} non-finite gradient element(s), "
            "update dropped")),
        ("rollback", lambda r: (
            f"ROLLBACK after {r.get('bad_steps')} consecutive bad steps "
            f"-> restored iter {r.get('restored_iteration')} "
            f"(epoch {r.get('restored_epoch')})")),
        ("preempt", lambda r: (
            f"PREEMPTED by {r.get('signal')} at epoch {r.get('epoch')} "
            f"iter {r.get('iteration')} (checkpointed, rc 75)")),
        ("resume", lambda r: (
            f"resumed at epoch {r.get('epoch')} iter {r.get('iteration')}"
            + (" (mid-epoch)" if r.get("mid_epoch") else " (boundary)"))),
        ("failure", lambda r: (
            f"FAILURE [{r.get('class')}] on {r.get('target')}"
            + (f" rc {r.get('rc')}" if r.get("rc") is not None else "")
            + (f" at step {r.get('step')}"
               if r.get("step") is not None else "")
            + (f" ({r.get('op')})" if r.get("op") else ""))),
        ("heal", lambda r: (
            f"HEAL {r.get('action')}"
            + (f" [{r.get('class')}]" if r.get("class") else "")
            + (f" {r.get('old_world')} -> {r.get('world')} proc(s)"
               if r.get("action") == "shrink"
               else (f" at world {r.get('world')}"
                     if r.get("world") is not None else ""))
            + (f" (reason: {r.get('reason')})" if r.get("reason") else "")
            + (f" ({r.get('restarts')} restart(s))"
               if r.get("restarts") is not None else ""))),
    ):
        for r in events_of(records, ev):
            lifecycle.append(render(r))
    if lifecycle:
        lines.append("")
        lines.append("lifecycle:")
        lines.extend(f"  {s}" for s in lifecycle)

    # checkpoint save-duration trend (ISSUE 16): creeping save cost is a
    # regression signal (state growth, fs contention), and a save whose
    # async payload write overlapped more than one optimizer step is
    # worth surfacing — that is the writer earning its keep, or, when
    # the overlap keeps growing, the writer falling behind the cadence
    saves = [
        r for r in events_of(records, "checkpoint")
        if r.get("duration_s") is not None
    ]
    if saves:
        durs = [float(r["duration_s"]) for r in saves]
        n_async = sum(1 for r in saves if r.get("async"))
        lines.append("")
        lines.append(
            f"checkpoint saves ({len(saves)}, {n_async} async):"
        )
        half = len(durs) // 2
        trend = ""
        if half >= 1 and len(durs) >= 4:
            early = sum(durs[:half]) / half
            late = sum(durs[half:]) / (len(durs) - half)
            trend = (
                f", trend {_fmt_s(early)} -> {_fmt_s(late)} s"
                + (" [REGRESSING]" if late > 1.5 * early else "")
            )
        lines.append(
            f"  duration mean {_fmt_s(sum(durs) / len(durs))} s, "
            f"max {_fmt_s(max(durs))} s{trend}"
        )
        for r in saves:
            ov = _ckpt_overlap_steps(r)
            if ov > 1:
                lines.append(
                    f"  save at iter {r.get('iteration')} overlapped "
                    f"{ov} steps (committed at iter "
                    f"{r.get('commit_iteration')})"
                )
    return "\n".join(lines)


def _ckpt_overlap_steps(r: dict) -> int:
    """Steps the async payload write spanned: submit iteration to commit
    iteration (0 for synchronous saves, which block the loop)."""
    if not r.get("async") or r.get("commit_iteration") is None:
        return 0
    return int(r["commit_iteration"]) - int(r.get("iteration", 0))


def _ckpt_line(r: dict) -> str:
    s = f"checkpoint epoch {r.get('epoch')} iter {r.get('iteration')}"
    if r.get("duration_s") is not None:
        s += (
            f" [{r.get('format')} {_fmt_s(r.get('duration_s'))} s, "
            f"{int(r.get('bytes', 0)) // 1024} KiB/proc]"
        )
    if r.get("async"):
        ov = _ckpt_overlap_steps(r)
        s += f" [async, +{ov} step(s) to commit]"
    return s


def _ewma(values: list[float], alpha: float = 0.1):
    out = None
    for v in values:
        if v != v:  # NaN — a bad step's loss; skip, don't poison
            continue
        out = v if out is None else alpha * v + (1.0 - alpha) * out
    return out


def _health_section(records: list[dict]) -> list[str]:
    """Training-health section (ISSUE 12): loss trend/EWMA, grad-norm
    trend, update ratio, the per-merge-group grad-norm trend, and the
    postmortem bundle index."""
    from mgwfbp_tpu.telemetry import events_of

    lines: list[str] = []
    health = events_of(records, "health")
    if health:
        losses = [float(h.get("loss", float("nan"))) for h in health]
        norms = [float(h.get("grad_norm", float("nan"))) for h in health]
        ratios = [
            float(h.get("update_ratio", float("nan"))) for h in health
        ]
        finite_n = [v for v in norms if v == v]
        lines.append("")
        lines.append(f"training health ({len(health)} records):")
        lines.append(
            f"  loss: first {_fmt_s(losses[0])} -> last "
            f"{_fmt_s(losses[-1])} (ewma {_fmt_s(_ewma(losses))}); "
            f"update/param ratio last {_fmt_s(ratios[-1])}"
        )
        if finite_n:
            lines.append(
                f"  grad norm: first {_fmt_s(norms[0])} -> last "
                f"{_fmt_s(norms[-1])} (max {_fmt_s(max(finite_n))})"
            )
        bad = sum(1 for v in losses if v != v)
        if bad:
            lines.append(
                f"  non-finite loss records: {bad} (see bad_step rows)"
            )
        per_group = [h.get("group_norms") for h in health]
        per_group = [g for g in per_group if g]
        if per_group and len(per_group[-1]) == len(per_group[0]):
            lines.append(
                f"  {'group':>5} {'gnorm_first':>12} {'gnorm_last':>12}"
            )
            for gi in range(len(per_group[0])):
                lines.append(
                    f"  {gi:>5} {_fmt_s(per_group[0][gi]):>12} "
                    f"{_fmt_s(per_group[-1][gi]):>12}"
                )
        comp = [h.get("compression_error") for h in health]
        comp = [c for c in comp if c]
        if comp:
            lines.append(
                f"  compression error (worst group): first "
                f"{_fmt_s(max(comp[0]))} -> last {_fmt_s(max(comp[-1]))}"
            )
    pms = events_of(records, "postmortem")
    if pms:
        lines.append("")
        lines.append(f"postmortem bundles ({len(pms)}):")
        lines.append(f"  {'trigger':>15} {'step':>8}  path")
        for r in pms:
            lines.append(
                f"  {str(r.get('trigger')):>15} "
                f"{str(r.get('step', '-')):>8}  {r.get('path')}"
            )
    return lines


def _alarm_lines(alarms: list[dict]) -> list[str]:
    """Active-alarm table rows (live /status and /fleet/status share the
    same alarm dicts the aggregator keeps)."""
    lines = [
        f"  {'kind':>14} {'group/proc':>10} {'residual':>10} {'band':>8}"
    ]
    for a in alarms:
        if a.get("alarm") == "straggler" or "slow_process" in a:
            kind = "straggler"
            who = f"p{a.get('slow_process')}"
            residual = _fmt_s(a.get("excess_s"))
            band = "-"
        else:
            # drift alarms report `residual`, health alarms `value`
            kind = str(a.get("kind"))
            who = (
                str(a.get("group"))
                if int(a.get("group", -1)) >= 0 else "agg"
            )
            residual = _fmt_s(a.get("residual", a.get("value")))
            band = _fmt_s(a.get("band"))
        procs = a.get("processes")
        lines.append(
            f"  {kind:>14} {who:>10} {residual:>10} {band:>8}"
            + (f"  reported by {sorted(procs)}" if procs else "")
        )
    return lines


def format_live_report(status: dict, values: dict) -> str:
    """One process's live view, from its /status JSON + parsed /metrics
    (same sections as the post-hoc report, sourced from the running
    job)."""
    lines: list[str] = []
    run = status.get("run", {}) or {}
    desc = ", ".join(f"{k}={v}" for k, v in sorted(run.items()))
    lines.append(f"live /status ({desc})" if desc else "live /status")
    lines.append(
        f"health: {'ok' if status.get('healthy') else 'UNHEALTHY'}"
        + (
            f" — {status.get('health_reason')}"
            if not status.get("healthy") else ""
        )
        + f" (uptime {_fmt_s(status.get('uptime_s'))} s)"
    )
    lines.append("")
    lines.append(
        f"steps: {values.get('mgwfbp_steps_total', 0)} recorded, at step "
        f"{status.get('step')} epoch {status.get('epoch')}, mean "
        f"{_fmt_s(values.get('mgwfbp_step_seconds'))} s/step "
        "(rolling window)"
    )
    sched = status.get("schedule")
    if sched:
        lines.append(
            f"schedule: {sched.get('comm_op')} x "
            f"{sched.get('num_groups')} group(s) "
            f"({sched.get('policy_detail')})"
        )
    eff = status.get("overlap_efficiency")
    if eff is not None:
        lines.append(
            f"overlap efficiency: {float(eff):.4f} (hidden "
            f"{_fmt_s(values.get('mgwfbp_comm_hidden_seconds'))} s + "
            f"exposed {_fmt_s(values.get('mgwfbp_comm_exposed_seconds'))}"
            " s per step)"
        )
    health = status.get("health")
    if health:
        lines.append(
            f"training health (step {health.get('step')}): loss "
            f"{_fmt_s(health.get('loss'))}, grad norm "
            f"{_fmt_s(health.get('grad_norm'))}, update/param ratio "
            f"{_fmt_s(health.get('update_ratio'))}"
        )
        gn = health.get("group_norms") or []
        if gn:
            lines.append(
                "  per-group grad norms: "
                + ", ".join(
                    f"g{gi}={_fmt_s(v)}" for gi, v in enumerate(gn)
                )
            )
        comp = health.get("compression_error") or []
        if comp:
            lines.append(
                f"  compression error (worst group): {_fmt_s(max(comp))}"
            )
    pm = status.get("postmortems") or {}
    if pm.get("total"):
        lines.append(
            f"postmortem bundles: {pm['total']} written"
        )
        for b in pm.get("recent", []):
            lines.append(
                f"  {b.get('trigger')} @ step {b.get('step')}: "
                f"{b.get('path')}"
            )
    alarms = status.get("active_alarms") or []
    lines.append("")
    if alarms:
        lines.append(f"active alarms ({len(alarms)}):")
        lines.extend(_alarm_lines(alarms))
    else:
        lines.append("active alarms: none")
    lines.append("")
    lines.append("lifecycle counters:")
    for key, label in (
        ("mgwfbp_checkpoints_total", "checkpoints"),
        ("mgwfbp_resizes_total", "resizes"),
        ("mgwfbp_bad_steps_total", "bad steps"),
        ("mgwfbp_rollbacks_total", "rollbacks"),
        ("mgwfbp_preempts_total", "preempts"),
        ("mgwfbp_resumes_total", "resumes"),
        ("mgwfbp_failures_total", "hard failures"),
        ("mgwfbp_heals_total", "heals"),
        ("mgwfbp_watchdog_stalls_total", "watchdog stalls"),
        ("mgwfbp_autotune_commits_total", "autotune commits"),
        ("mgwfbp_drift_alarms_total", "drift alarms"),
        ("mgwfbp_straggler_alarms_total", "straggler alarms"),
        ("mgwfbp_health_alarms_total", "health alarms"),
        ("mgwfbp_postmortems_total", "postmortem bundles"),
        ("mgwfbp_profile_windows_total", "profile windows"),
    ):
        v = values.get(key, 0)
        if v:
            lines.append(f"  {label}: {v}")
    prof = status.get("profile") or {}
    if prof.get("state") not in (None, "idle"):
        lines.append("")
        lines.append(f"profile window: {prof.get('state')}")
        res = prof.get("result")
        if res:
            lines.append(
                f"  {res.get('steps')} step(s), attribution="
                f"{res.get('attribution')}"
                + (
                    ", " + ", ".join(
                        f"g{g['group']}={_fmt_s(g.get('device_s'))}s"
                        for g in res.get("groups", [])
                        if "device_s" in g
                    ) if res.get("groups") else ""
                )
            )
    return "\n".join(lines)


def format_fleet_report(doc: dict) -> str:
    """The supervisor fan-in's group view (/fleet/status)."""
    lines = [
        f"fleet /fleet/status: {doc.get('reachable', 0)} process(es) "
        f"reachable, {len(doc.get('unreachable') or [])} unreachable, "
        f"{'healthy' if doc.get('healthy') else 'UNHEALTHY'}"
    ]
    table = doc.get("straggler_table") or []
    if table:
        lines.append("")
        lines.append("live straggler table (mean-excess vs fastest):")
        lines.append(
            f"  {'proc':>5} {'step':>8} {'mean_step_s':>12} "
            f"{'excess_s':>10} {'excess_%':>9}"
        )
        for r in table:
            lines.append(
                f"  {r['process']:>5} {str(r.get('step', '-')):>8} "
                f"{_fmt_s(r['mean_step_s']):>12} "
                f"{_fmt_s(r.get('excess_s')):>10} "
                f"{r.get('excess_pct', 0.0):>8.1f}%"
            )
    slow = doc.get("slowest_process")
    if slow:
        lines.append(
            f"slowest: process {slow['process']} "
            f"(+{_fmt_s(slow['excess_s'])} s/step, "
            f"+{slow['excess_pct']:.1f}%)"
        )
    heal = doc.get("heal")
    if heal:
        lines.append("")
        state = "enabled" if heal.get("enabled") else "DISABLED (--no-heal)"
        lines.append(
            f"self-healing: {state}, liveness grace "
            f"{_fmt_s(heal.get('liveness_grace_s'))} s, budget "
            f"{heal.get('budget')} restart(s)/class"
        )
        restarts = heal.get("restarts") or {}
        if restarts:
            lines.append(
                "  heals so far: " + ", ".join(
                    f"{cls}={n}" for cls, n in sorted(restarts.items())
                )
            )
        pending = heal.get("pending_failure")
        if pending:
            lines.append(
                f"  PENDING FAILURE: {pending.get('class')} on "
                f"{pending.get('target')} (step {pending.get('step')}) "
                "— draining to heal"
            )
    alarms = doc.get("active_alarms") or []
    lines.append("")
    if alarms:
        lines.append(f"fleet active alarms ({len(alarms)}):")
        lines.extend(_alarm_lines(alarms))
    else:
        lines.append("fleet active alarms: none")
    pms = doc.get("postmortems") or []
    if pms:
        lines.append("")
        lines.append("fleet postmortem bundles:")
        for row in pms:
            lines.append(
                f"  p{row.get('process')}: {row.get('total')} bundle(s)"
            )
            for b in row.get("recent", []):
                lines.append(
                    f"    {b.get('trigger')} @ step {b.get('step')}: "
                    f"{b.get('path')}"
                )
    for u in doc.get("unreachable") or []:
        lines.append(
            f"UNREACHABLE: p{u.get('process')} at {u.get('target')} "
            f"({u.get('error')})"
        )
    return "\n".join(lines)


def _fetch(url: str, timeout_s: float = 5.0):
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout_s) as resp:
            return resp.status, resp.read().decode()
    except Exception as e:  # noqa: BLE001 — refused/timeout: try the
        # other endpoint family, then report
        return None, str(e)


def live_report(base: str) -> int:
    """Render from a RUNNING job: per-process /status + /metrics, or a
    supervisor fan-in's /fleet/status."""
    from mgwfbp_tpu.telemetry.export import parse_metrics_text

    base = base.rstrip("/")
    if not base.startswith("http"):
        base = "http://" + base
    code, body = _fetch(base + "/status")
    if code == 200:
        status = json.loads(body)
        mcode, mtext = _fetch(base + "/metrics")
        values = parse_metrics_text(mtext) if mcode == 200 else {}
        print(format_live_report(status, values))
        return 0
    fcode, fbody = _fetch(base + "/fleet/status")
    if fcode == 200:
        print(format_fleet_report(json.loads(fbody)))
        return 0
    print(
        f"telemetry_report: no live endpoint at {base} "
        f"(/status: {code or body}; /fleet/status: {fcode or fbody})",
        file=sys.stderr,
    )
    return 2


def _synthetic_stream(path: str) -> None:
    """Write a small but complete stream: header, steps, an overlap
    snapshot with a known hidden/exposed split, and lifecycle events."""
    from mgwfbp_tpu.telemetry import EventWriter, attribute_overlap

    w = EventWriter(path, run={"model": "selftest", "world": 8})
    tb = [0.010, 0.010, 0.010]
    groups = [(0, 1), (2,)]
    comm = [0.015, 0.010]
    nbytes = [1 << 20, 1 << 19]
    rows = attribute_overlap(groups, tb, comm, nbytes)
    step_s = 0.045
    for i in range(24):
        # phase spans and the loader's counters as telemetry/phases.py
        # writes them: the pool behind on every fourth step, one batch of
        # the 24 through the NumPy fallback
        t = i * step_s
        w.emit("step", step=i, epoch=0, start_s=t, dur_s=0.004,
               phases={"wait": [t - 0.003, 0.002], "place": [t - 0.001, 0.001],
                       "guard": [t + 0.004, 0.040]},
               ready=0 if i % 4 == 0 else 2, native=int(i != 5), lowered=0)
    # the built step program's collectives, as Trainer._note_step_program
    # records them after the first dispatch
    w.emit("step_program", step=1, collectives=33, async_collectives=5,
           compiler_options=["xla_enable_async_all_reduce"])
    # a profile window's trace reduced by the step's map, as
    # Trainer._run_profile_window records it (profiling.split_trace)
    w.emit("step_scopes", step=12, steps=2,
           scopes={"attn_full": [3.0, 6.5], "moe_experts": [4.0, 8.0],
                   "optimizer": [1.5, 0.0], "(model, no scope)": [0.5, 1.0],
                   "(no metadata)": [0.25, 0.0]},
           layers={"attn_full": "attention", "moe_experts": "experts",
                   "optimizer": "update"},
           groups=[0.75, 0.5], exchange={
               "device_ms": 1.25, "wait_ms": 1.0, "calls": 33.0},
           top=[[8.0, "gmm.14", "moe_experts"], [6.5, "fusion.8", "attn_full"]])
    w.emit("attention_program", step=1, kernel=1, blocks=3)
    w.emit("experts_program", step=1, kernel=12, ragged=0, programs=4,
           rows_held=4, rows_all=4, rows_programs=1, bounded=0, whole=4)
    # what set-up was made of, as the Trainer writes it once step 1's
    # results are read (telemetry/phases.py)
    w.emit("setup", origin_wall=1790736000.0, spans={
        "setup": [-30.0, 50.0, None], "before_init": [-30.0, 12.0, "setup"],
        "import": [-29.5, 4.0, "before_init"], "init": [-18.0, 18.0, "setup"],
        "data": [-17.0, 9.0, "init"], "dataset": [-17.0, 8.5, "data"],
        "optimizer": [-8.0, 6.0, "init"],
        "first_step": [1.0, 15.0, "setup"], "trace": [1.1, 4.0, "first_step"],
        "lower": [5.1, 0.5, "first_step"], "compile": [5.6, 9.0, "first_step"],
        "cache_load": [5.9, 1.5, "compile"],
        "first_result": [16.0, 4.0, "setup"],
    }, counters={
        "programs_traced": 800, "programs_lowered": 90,
        "programs_compiled": 0, "small_compiles": 80, "cache_loads": 10,
        "small_compile_s": 3.2, "kernel_trace_s": 0.22,
        "slow_events": [["backend_compile_duration", "jit(step)", 9.0]],
    })
    hidden = sum(r.hidden_s for r in rows)
    total = sum(r.comm_s for r in rows)
    w.emit(
        "overlap", step=23, epoch=0, step_s=step_s,
        tb_total_s=sum(tb), comm_s=total, hidden_s=hidden,
        exposed_s=total - hidden,
        efficiency=hidden / total, attribution="cost-model",
        timeline_end_s=max(sum(tb), max(r.start_s + r.comm_s for r in rows)),
    )
    for r in rows:
        w.emit(
            "comm_group", step=23, group=r.group, nbytes=r.nbytes,
            comm_s=r.comm_s, start_s=r.start_s, hidden_s=r.hidden_s,
            exposed_s=r.exposed_s, attribution="cost-model",
        )
    w.emit("resize", old_world=8, new_world=4,
           schedule_source="schedule-cache", num_groups=2)
    w.emit("checkpoint", epoch=0, iteration=24, mid_epoch=False)
    # async shard-native saves (ISSUE 16): one committed at the next
    # cadence step, one whose payload write overlapped three steps
    w.emit("checkpoint", epoch=0, iteration=8, mid_epoch=True,
           epoch_step=8, duration_s=0.030, bytes=1 << 20,
           format="sharded", commit_iteration=9, **{"async": True})
    w.emit("checkpoint", epoch=0, iteration=16, mid_epoch=True,
           epoch_step=16, duration_s=0.140, bytes=1 << 20,
           format="sharded", commit_iteration=19, **{"async": True})
    w.emit("drift_alarm", kind="comm_residual", step=20, residual=4.5,
           band=3.0, active=True, group=1)
    w.emit("drift_alarm", kind="comm_residual", step=23, residual=1.2,
           band=3.0, active=False, group=1)
    w.emit("straggler", step=22, slow_process=1, excess_s=0.013,
           step_s_max=0.058, step_s_min=0.045, active=True)
    # training-health stream + alarm + postmortem (ISSUE 12)
    for i in range(24):
        w.emit(
            "health", step=i, epoch=0,
            loss=2.0 - 0.05 * i if i != 20 else 9.0,
            grad_norm=1.0 + (8.0 if i == 20 else 0.0),
            update_ratio=1e-3,
            group_norms=[0.8, 0.6],
            compression_error=[0.02, 0.03],
        )
    w.emit("health_alarm", kind="loss_spike", step=20, value=5.2,
           band=2.0, active=True, group=-1)
    w.emit("health_alarm", kind="loss_spike", step=22, value=1.1,
           band=2.0, active=False, group=-1)
    w.emit("postmortem", trigger="health_alarm", step=20,
           path="/tmp/run/postmortems/0000")
    # self-healing supervisor (ISSUE 20): a hard-failure verdict and the
    # healing action taken, as the supervisor's own stream records them
    w.emit("failure", **{"class": "oom_kill"}, target="p1", rc=-9,
           step=20)
    w.emit("heal", action="shrink", **{"class": "oom_kill"}, target="p1",
           old_world=2, world=1, restarts=1)
    w.emit("failure", **{"class": "wedged"}, target="p0,p1", step=21)
    w.emit("heal", action="relaunch", **{"class": "wedged"},
           target="p0,p1", world=2, restarts=1)
    w.close()


def selftest() -> int:
    """Writer -> reader -> report -> exports round trip on synthetic data."""
    from mgwfbp_tpu.telemetry import read_events
    from mgwfbp_tpu.telemetry.export import (
        write_chrome_trace, write_prometheus,
    )

    with tempfile.TemporaryDirectory(prefix="mgwfbp_tel_selftest_") as d:
        path = os.path.join(d, "telemetry.jsonl")
        _synthetic_stream(path)
        records = read_events(path)
        report = format_report(records)
        assert "overlap efficiency" in report, report
        assert "set-up (process started" in report, report
        assert "alarms:" in report and "straggler" in report, report
        # ISSUE 12: training-health section, health alarm row, and the
        # postmortem index table all render from the same stream
        assert "training health (24 records)" in report, report
        assert "loss_spike" in report, report
        assert "postmortem bundles (1):" in report, report
        assert "/tmp/run/postmortems/0000" in report, report
        assert "gnorm_first" in report, report
        # ISSUE 27: the phase table's counter lines, `native` among them
        assert "host loop phases (24 steps" in report, report
        assert "batches ready when the loop asked (none on 6 of 24" in report
        assert (
            "native transform: the pool's batches came from the native "
            "pass on 23 of 24 steps (share 0.958)" in report
        ), report
        # ISSUE 29: how many of the step's collectives are asynchronous
        assert (
            "step program (built by step 1): 33 collectives, 5 asynchronous; "
            "compile options: xla_enable_async_all_reduce" in report
        ), report
        # ISSUE 49: a profile window's device time by scope, as a table
        # with each layer's total
        assert (
            "step by scope (profile window of 2 step(s) to step 12, ms of "
            "device ops a step):" in report
        ), report
        assert (
            "26.000 ms of device ops a step in 0 events: attention 9.500, "
            "experts 12.000, unscoped 1.750, update 1.500, exchange 1.250; "
            "forward 7.500, backward 15.500, no metadata 0.250; the "
            "exchange waits 1.000 ms in 33 collective(s) a step; by group "
            "0.750 0.500" in report
        ), report
        assert "8.000 gmm.14 [moe_experts]" in report, report
        # ISSUE 31: which way the step's attention cores went down
        assert (
            "attention (step program built by step 1): 1 core(s) through "
            "the fused kernel, 3 through the plain blocks" in report
        ), report
        # ISSUE 16: the save-duration trend section renders, async saves
        # are marked in the lifecycle, and the save whose payload write
        # spanned >1 step is flagged with its commit iteration
        assert "checkpoint saves (2, 2 async):" in report, report
        assert "[async, +1 step(s) to commit]" in report, report
        assert (
            "save at iter 16 overlapped 3 steps (committed at iter 19)"
            in report
        ), report
        assert "save at iter 8 overlapped" not in report, report
        # ISSUE 20: failure verdicts and healing actions render in the
        # lifecycle section
        assert "FAILURE [oom_kill] on p1 rc -9 at step 20" in report
        assert (
            "HEAL shrink [oom_kill] 2 -> 1 proc(s) (1 restart(s))"
            in report
        ), report
        assert "FAILURE [wedged] on p0,p1 at step 21" in report, report
        assert (
            "HEAL relaunch [wedged] at world 2 (1 restart(s))" in report
        ), report
        trace_path = os.path.join(d, "trace.json")
        doc = write_chrome_trace(trace_path, records)
        with open(trace_path) as f:
            loaded = json.load(f)
        assert loaded["traceEvents"] and doc["traceEvents"]
        prom = write_prometheus(os.path.join(d, "metrics.prom"), records)
        assert "mgwfbp_steps_total 24" in prom, prom
        assert "mgwfbp_overlap_efficiency" in prom
        # the file dump and the live /metrics endpoint share ONE registry
        # + aggregator; serving the replayed stream must render the very
        # same text (ISSUE 9: the two surfaces cannot diverge)
        from mgwfbp_tpu.telemetry.export import render_metrics
        from mgwfbp_tpu.telemetry.serve import MetricsAggregator

        agg = MetricsAggregator()
        agg.replay(records)
        assert render_metrics(agg.values()) == prom
        assert "mgwfbp_drift_alarms_total 1" in prom, prom
        assert "mgwfbp_health_alarms_total 1" in prom, prom
        assert "mgwfbp_postmortems_total 1" in prom, prom
        assert "mgwfbp_health_grad_norm" in prom, prom
        assert "mgwfbp_failures_total 2" in prom, prom
        assert "mgwfbp_heals_total 2" in prom, prom
        # --live round trip: serve the replayed aggregator over HTTP and
        # render the live report from /status + /metrics; then fan two
        # such children into a fleet view (ISSUE 10) and render that
        from mgwfbp_tpu.telemetry.export import parse_metrics_text
        from mgwfbp_tpu.telemetry.fleet import FleetServer, scrape_fleet
        from mgwfbp_tpu.telemetry.serve import TelemetryServer

        srv = TelemetryServer(agg, 0, host="127.0.0.1")
        fleet = FleetServer(
            lambda: {0: ("127.0.0.1", srv.port),
                     1: ("127.0.0.1", srv.port)},
            port=0,
            # the supervisor's heal state flows through the fan-in meta
            # verbatim (ISSUE 20)
            meta_provider=lambda: {
                "heal": {
                    "enabled": True, "restarts": {"oom_kill": 1},
                    "budget": 2, "liveness_grace_s": 120.0,
                },
            },
        )
        try:
            code, body = _fetch(f"http://127.0.0.1:{srv.port}/status")
            assert code == 200, body
            status = json.loads(body)
            code, mtext = _fetch(f"http://127.0.0.1:{srv.port}/metrics")
            assert code == 200 and parse_metrics_text(mtext), mtext
            live = format_live_report(status, parse_metrics_text(mtext))
            assert "steps: 24 recorded" in live, live
            # ISSUE 20: failure/heal lifecycle counters in the live view
            assert "hard failures: 2" in live, live
            assert "heals: 2" in live, live
            children = scrape_fleet(
                {0: ("127.0.0.1", srv.port), 1: ("127.0.0.1", srv.port)}
            )
            assert all(c.reachable for c in children)
            code, fbody = _fetch(
                f"http://127.0.0.1:{fleet.port}/fleet/status"
            )
            assert code == 200, fbody
            fdoc = json.loads(fbody)
            assert {r["process"] for r in fdoc["straggler_table"]} == {
                0, 1,
            }, fdoc
            code, fmet = _fetch(
                f"http://127.0.0.1:{fleet.port}/fleet/metrics"
            )
            assert 'mgwfbp_steps_total{process="0"} 24' in fmet, fmet
            assert 'mgwfbp_steps_total{process="1"} 24' in fmet, fmet
            # ISSUE 20: the supervisor's heal state renders in the fleet
            # view
            freport = format_fleet_report(fdoc)
            assert "self-healing: enabled" in freport, freport
            assert "heals so far: oom_kill=1" in freport, freport
            print(format_fleet_report(fdoc))
            print()
        finally:
            fleet.close()
            srv.close()
        print(report)
        print()
        print(
            f"telemetry selftest OK: {len(records)} records, "
            f"{len(loaded['traceEvents'])} trace events"
        )
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="telemetry_report",
        description="Render a run's telemetry event stream: overlap table, "
        "step trend, lifecycle; optional Chrome-trace/Prometheus export",
    )
    p.add_argument("events", nargs="?",
                   help="telemetry JSONL path, or a run dir containing "
                   "telemetry.jsonl")
    p.add_argument("--chrome-trace", dest="chrome_trace", default=None,
                   help="write a chrome://tracing / Perfetto JSON here")
    p.add_argument("--prometheus", default=None,
                   help="write a Prometheus text-exposition dump here")
    p.add_argument("--live", default=None, metavar="URL",
                   help="render from a RUNNING job's /status + /metrics "
                        "(or a supervisor fan-in's /fleet/status) "
                        "instead of JSONL files, e.g. "
                        "http://127.0.0.1:9100")
    p.add_argument("--selftest", action="store_true",
                   help="run the synthetic end-to-end smoke and exit")
    args = p.parse_args(argv)

    if args.selftest:
        return selftest()
    if args.live:
        return live_report(args.live)
    if not args.events:
        p.error("events path required (or --selftest, or --live URL)")
    path = args.events
    if os.path.isdir(path):
        path = os.path.join(path, "telemetry.jsonl")

    # read_event_set handles size-rotated streams (telemetry.jsonl.0000,
    # .0001, ... + the active file) as one continuous timeline; a bare
    # un-rotated file reads identically
    from mgwfbp_tpu.telemetry import read_event_set

    try:
        records = read_event_set(path)
    except FileNotFoundError:
        print(f"telemetry_report: no events file at {path}", file=sys.stderr)
        return 2
    print(format_report(records))
    if args.chrome_trace:
        from mgwfbp_tpu.telemetry.export import write_chrome_trace

        doc = write_chrome_trace(args.chrome_trace, records)
        print(f"chrome trace: {args.chrome_trace} "
              f"({len(doc['traceEvents'])} events; open in Perfetto)")
    if args.prometheus:
        from mgwfbp_tpu.telemetry.export import write_prometheus

        write_prometheus(args.prometheus, records)
        print(f"prometheus dump: {args.prometheus}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
