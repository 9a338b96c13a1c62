#!/usr/bin/env python3
"""What a run's first step is made of: tracing, lowering, compile-cache key
and load (or the compile), and the rest (argument checks, the dispatch).

    python tools/first_step_split.py [--out FILE] -- <benchmarks/run.py's arguments>

Runs `benchmarks/run.py` in this process, unchanged, with a listener on jax's
own monitoring events (`jax.monitoring`: the trace of every jitted function,
every jaxpr-to-MLIR conversion, every backend compile or persistent-cache
load), and afterwards prints one JSON object: the run's `first_step_s`,
`init_s` and `setup_s` as the harness computed them, the events of the step
program (`jit(step)`), what is left of the first step
beside them (`rest_s`), and every event of a tenth of a second or more. `setup_s` counts from
`run.py`'s own start, so jax is imported only after `run.py` is loaded, as in
a run without this wrapper. The harness's result line stays the last line of
stdout; the split goes to `--out` (default chiprun_out/first_step_split.jsonl,
one line appended a run) and to stderr.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
STEP_PROGRAMS = ("jit(step)", "jit(step_lm)")  # train/step.py's two


def split(events: list[tuple[float, str, float, str]]) -> dict:
    """events: (clock at the event's end, event name, seconds, function name)
    in order. The step program is the first lowered under `make_train_step`'s
    names; its trace is the longest trace of that name, and the jitted
    functions traced inside that time (the kernels' among them) are reported
    apart; its compile or cache load is the first after its lowering."""
    lowered = [e for e in events if e[1] == LOWER and e[3] in STEP_PROGRAMS]
    if not lowered:
        return {}
    at, _, lower_s, name = lowered[0]
    # a trace is reported under the function's name, its lowering under
    # `jit(name)`
    traces = [e for e in events
              if e[1] == TRACE and f"jit({e[3]})" == name and e[0] <= at]
    if not traces:
        return {"program": name, "lower_s": lower_s}
    traced_at, _, trace_s, _ = max(traces, key=lambda e: e[2])
    nested: dict[str, float] = {}
    count = 0
    for when, event, secs, fun in events:
        if event == TRACE and traced_at - trace_s <= when < traced_at:
            nested[fun] = nested.get(fun, 0.0) + secs
            count += 1
    after = [e for e in events if e[0] >= at]
    compile_s = next((e[2] for e in after if e[1] == COMPILE), 0.0)
    later = next((e[0] for e in after if e[1] == LOWER and e[0] > at), None)
    loads = [e[2] for e in after if e[1] == CACHE_LOAD
             and (later is None or e[0] < later)]
    return {
        "program": name,
        "trace_s": trace_s,
        "nested_traces": count,
        "nested_trace_s": dict(sorted(
            nested.items(), key=lambda kv: -kv[1])[:8]),
        "lower_s": lower_s,
        # cache key + load on a hit; key + compile + write on a miss
        "compile_or_load_s": compile_s,
        "cache_load_s": loads[0] if loads else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "first_step_split.jsonl"))
    ap.add_argument("--tag", default="")
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    run_args = [a for a in args.run_args if a != "--"]
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)  # sets its _PROCESS_T0: before jax, as ever
    import jax.monitoring as monitoring

    events: list[tuple[float, str, float, str]] = []
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events.append(
            (time.time(), name, secs, str(kw.get("fun_name", "")))))
    seen: dict = {}
    real_metrics = run.read_metrics

    def read_metrics(spec, run_doc, kind):
        seen.update(run_doc)
        return real_metrics(spec, run_doc, kind)

    run.read_metrics = read_metrics
    rc = run.main(run_args)
    doc = {"tag": args.tag, "args": run_args, **split(events)}
    for name in ("first_step_s", "init_s", "setup_s"):
        if name in seen:
            doc[name] = seen[name]
    if "first_step_s" in doc and "trace_s" in doc:
        doc["rest_s"] = doc["first_step_s"] - doc["trace_s"] \
            - doc["lower_s"] - doc["compile_or_load_s"]
    doc["long_events"] = [
        [name.rsplit("/", 1)[-1], fun, round(secs, 3)]
        for _, name, secs, fun in events if secs >= 0.1]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(doc) + "\n")
    print("[first-step split] " + json.dumps(doc), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
