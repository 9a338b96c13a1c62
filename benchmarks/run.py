"""One run of one cell of BENCHMARK.json: the Trainer's own loop, on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child. The cell's configuration file and traffic file give
`train_cli`'s argument list and the environment; `--seed` becomes `train_cli`'s
`--seed` (weights, data and its order are made from it). `Trainer` is built as
`train_cli.main` builds it and `Trainer.train_epoch` is driven: one short
warm-up epoch (set-up), then whole epochs until `--seconds` have passed (the
measured window). `correct` is decided outside the window (README.md here).
The last line of stdout is the one JSON object of the contract and carries
nothing else; the numbers `correct` compared are on a phase line before it.

    python3 benchmarks/run.py --workload <cell> --check-seeds 1,2,3 [--control <name>]

reads the numbers `correct` compares on many seeds in one process (no window),
optionally with one of the configuration's lower-precision controls switched
on, to set the limits from.

    python3 benchmarks/run.py --rehearse

drives the same code on the CPU with four virtual devices at the tiny size
`rehearsal.json` names and prints counts and checks, under no device metric's
name.

Nothing in this file names a model, a cell or a metric: a configuration, a
traffic mix, a reference and a per-layer metric are files found by the names
BENCHMARK.json gives.
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK_FILE = os.path.join(ROOT, "BENCHMARK.json")
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def load_module(path: str):
    """A file of the benchmark as a module, whatever characters its name has."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path).replace("-", "_").replace(".py", ""),
        path,
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str | None) -> dict:
    """The cell with its configuration, traffic and benchmark home resolved.
    `None` is the rehearsal's cell, which `rehearsal.json` beside this file
    names: no entry of BENCHMARK.json may name a cell that small.

    The home is the directory that holds `configs/`, `traffic/`,
    `references/`, `layer_metrics/` and `peaks.json`: two levels above the
    configuration's file."""
    bench = load_json(BENCHMARK_FILE)
    if workload is None:
        rehearsal = load_json(os.path.join(HERE, "rehearsal.json"))
        cell = rehearsal["cell"]
        config_file = os.path.join(ROOT, rehearsal["file"])
    else:
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(
                f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}"
            )
        cell = cells[workload]
        entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        config_file = os.path.join(ROOT, entry["file"])
    home = os.path.dirname(os.path.dirname(config_file))
    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(config_file),
        "traffic": load_json(
            os.path.join(home, "traffic", cell["traffic"] + ".json")),
        "home": home,
    }


def cell_metrics(spec: dict, kind: str) -> list[dict]:
    """The metrics of `kind` (`end_to_end`, `per_layer`) this cell reports."""
    name = spec["cell"]["name"]
    return [
        m for m in spec["bench"][kind]
        if "workloads" not in m or name in m["workloads"]
    ]


def train_argv(spec: dict, seed: int, logdir: str, control: dict) -> list[str]:
    return [
        *spec["config"]["train_cli"], *spec["traffic"].get("flags", []),
        *control.get("flags", []), "--seed", str(seed), "--logdir", logdir,
    ]


def apply_env(spec: dict, control: dict, global_batch: int) -> dict:
    """The environment the cell asks for, set before the program reads it.
    The train set is sized here so that an epoch has the traffic's
    `steps_per_epoch` steps of the global batch."""
    env = {
        **spec["config"].get("env", {}), **spec["traffic"].get("env", {}),
        **control.get("env", {}),
    }
    env["MGWFBP_SYNTH_TRAIN_N"] = str(
        spec["traffic"]["steps_per_epoch"] * global_batch)
    os.environ.update(env)
    return env


def require_devices(chips: int, rehearsal: bool) -> dict:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearsal:
        return device
    if device["platform"] != "tpu" or device["count"] != chips:
        raise SystemExit(
            f"benchmark: this cell needs {chips} TPU chip(s); jax reports "
            f"{device['count']} device(s) of platform {device['platform']!r}."
            " No result is printed without them."
        )
    return device


def device_peak_bytes(device) -> int:
    """Peak bytes on one device, read after the window. The allocator's
    `peak_bytes_in_use` counts arrays (state, batches) and not the scratch
    the runtime reserves for programs: on the TPU the step's temporaries (4.5
    GB for ResNet-50 at batch 128) sit in `bytes_reserved`, which free memory
    is short of as well. So the peak is the larger of the arrays' own peak
    and the arrays alive now plus the largest reservation. (The two peaks
    added together can pass the device's memory: set-up's arrays and the
    step's scratch need not coexist.) Arrays in flight inside a step beyond
    those alive at its end are missed."""
    stats = device.memory_stats()  # None where the backend keeps none (CPU)
    if not stats:
        return 0
    say("memory", f"device {device.id}: " + " ".join(
        f"{k} {stats[k]}" for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "bytes_limit") if k in stats))
    return int(max(
        stats["peak_bytes_in_use"],
        stats["bytes_in_use"] + stats.get("peak_bytes_reserved", 0)))


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


class CompileCounter:
    """Counts programs lowered and compiled, from jax's own monitoring
    events: a lowering is any new program (cache hit or not), a backend
    compile one the persistent cache did not hold."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.lowered = 0
        self.compiled = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, _secs: float, **_kw) -> None:
        if name == LOWERING_EVENT:
            self.lowered += 1
        elif name == BACKEND_COMPILE_EVENT:
            self.compiled += 1

    def snapshot(self) -> tuple[int, int]:
        return self.lowered, self.compiled


class TimedLoader:
    """The train loader with every `__next__` timed, for the traced run only:
    `input_wait_ms` and the attribution of idle gaps read it. Everything else
    passes through to the program's loader."""

    def __init__(self, inner):
        self._inner = inner
        self.waits: list[tuple[float, float]] = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __len__(self) -> int:
        return len(self._inner)

    def __iter__(self):
        it = iter(self._inner)
        while True:
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                return
            self.waits.append((t0, time.perf_counter()))
            yield item


def flat_params(tree) -> dict:
    """{"a/b/c": host array} of a parameter tree."""
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): np.asarray(leaf)
        for path, leaf in leaves
    }


def rel_change(before: dict, after: dict) -> float:
    """||after - before|| / ||before|| over all leaves, float64 on the host."""
    import numpy as np

    num = sum(
        float(np.sum((after[k].astype(np.float64) - v) ** 2))
        for k, v in before.items()
    )
    den = sum(float(np.sum(v.astype(np.float64) ** 2)) for v in before.values())
    return math.sqrt(num / den)


def read_stream(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def first_batch(loader):
    """Batch 0 of epoch 0 as the warm-up epoch fed it: the program's loader
    names a batch by (seed, epoch, rank, index) and builds it on demand."""
    return getattr(loader, "inner", loader).load_batch(0, 0)


def judge(checks: dict, limits: dict) -> bool:
    """Print each number compared beside its limit; True when all hold."""
    ok = True
    for name, value in checks.items():
        limit = limits.get(name)
        if limit is None:
            say("correct", f"{name} {value!r} (no limit: informational)")
            continue
        lo, hi = limit.get("min"), limit.get("max")
        holds = (
            isinstance(value, (int, float)) and math.isfinite(value)
            and (lo is None or value >= lo) and (hi is None or value <= hi)
        )
        say("correct", f"{name} {value!r} limit "
            f"[{'' if lo is None else lo}, {'' if hi is None else hi}] "
            f"{'ok' if holds else 'FAILED'}")
        ok = ok and holds
    return ok


def run_once(
    spec: dict, seed: int, seconds: float, trace: bool, out: str,
    control: dict, counter: CompileCounter, rehearsal: bool = False,
) -> tuple[dict, dict]:
    """Build the Trainer from the cell's flags, warm it up, measure, check.
    `seconds` 0 skips the window (the seeds mode). Returns the contract's
    result line and the numbers `correct` compared."""
    import jax
    import numpy as np

    from mgwfbp_tpu import train_cli
    from mgwfbp_tpu.train.trainer import Trainer
    from mgwfbp_tpu.utils.platform import enable_compile_cache

    chips = spec["cell"]["chips"]
    traffic = spec["traffic"]
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    cache_dir = enable_compile_cache()
    entries_start = cache_entries(cache_dir)
    device = require_devices(chips, rehearsal)
    world = device["count"]
    argv = train_argv(spec, seed, os.path.join(out, "logs"), control)
    args = train_cli.build_parser().parse_args(argv)
    cfg = train_cli.config_from_args(args)
    global_batch = cfg.batch_size * world * cfg.nsteps_update
    env = apply_env(spec, control, global_batch)
    say("set-up", f"cell {spec['cell']['name']} seed {seed} on {world} x "
        f"{device['kind']!r}; train_cli {' '.join(argv)}")
    say("set-up", "environment " + " ".join(f"{k}={v}" for k, v in env.items()))
    say("set-up", f"compile cache {cache_dir}: {entries_start} entries at start")

    check_s = 0.0  # the check's own work before the window: not set-up
    t0 = time.perf_counter()
    trainer = Trainer(
        cfg, profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None,
    )
    init_s = time.perf_counter() - t0
    try:
        reducer = trainer.reducer
        say("set-up", f"Trainer built in {init_s:.1f} s: global batch "
            f"{global_batch}, {len(trainer.bundle.train)} steps an epoch")
        leaves = len(jax.tree_util.tree_leaves(trainer.state.params))
        say("exchange", f"--policy {cfg.policy} resolved to "
            f"{reducer.schedule.policy_detail!r}: "
            f"{reducer.schedule.num_groups} group(s) for {leaves} gradient "
            f"leaves, comm_op {reducer.comm_op}"
            if reducer is not None else
            f"--policy {cfg.policy}: no reducer (one device or policy none)")
        t0 = time.perf_counter()
        params_0 = flat_params(trainer.state.params)
        check_s += time.perf_counter() - t0
        loader = None
        if trace:
            loader = trainer.bundle.train = TimedLoader(trainer.bundle.train)
        # warm-up: the first `warmup_steps` steps through the window's own
        # call and feed, epoch end included (guard and health drains)
        warmup = int(traffic["warmup_steps"])
        trainer.config.num_batches_per_epoch = warmup
        t0 = time.perf_counter()
        trainer.train_epoch(0)
        jax.block_until_ready(trainer.state)
        trainer.config.num_batches_per_epoch = args.num_batches_per_epoch
        warmup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        params_k = flat_params(trainer.state.params)
        check_s += time.perf_counter() - t0
        tel_offset = time.perf_counter() - trainer.telemetry.now()
        say("set-up", f"warm-up epoch of {warmup} steps took {warmup_s:.1f} s")

        # ---- the measured window: whole epochs until `seconds` have passed
        lowered_0, compiled_0 = counter.snapshot()
        epochs = 0  # epoch 0 was the warm-up's
        traced = None
        setup_s = time.time() - _PROCESS_T0 - check_s
        w0 = time.perf_counter()
        while seconds > 0:
            epochs += 1
            if trace and epochs == 1:
                traced = trace_epoch(trainer, epochs, os.path.join(out, "trace"))
            else:
                trainer.train_epoch(epochs)
            if time.perf_counter() - w0 >= seconds:
                break
        jax.block_until_ready(trainer.state)
        w1 = time.perf_counter()
        lowered_1, compiled_1 = counter.snapshot()
        peak_bytes = max(
            (device_peak_bytes(d) for d in jax.local_devices()), default=0)

        # ---- what the run itself recorded
        events = read_stream(trainer.telemetry.path)
        steps = [e for e in events if e["event"] == "step"]
        health = {e["step"]: e for e in events if e["event"] == "health"}
        bad_steps = [e for e in events if e["event"] == "bad_step"]
        window_steps = [e for e in steps if e["step"] > warmup]
        window_s = w1 - w0
        if traced is not None:
            align(traced, window_steps, tel_offset)
        if seconds > 0:
            say("window", f"{len(window_steps)} steps in {epochs} epoch(s) "
                f"({epochs} boundaries) over {window_s:.3f} s: closed "
                f"{window_s - seconds:+.3f} s against --seconds {seconds:g}")
            say("window", f"{max(len(window_steps) - 1, 0)} intervals between "
                "successive step dispatches")
            say("window", f"programs lowered inside the window: "
                f"{lowered_1 - lowered_0}, compiled by the backend: "
                f"{compiled_1 - compiled_0}; compile cache entries "
                f"{entries_start} -> {cache_entries(cache_dir)}")

        # ---- correct: outside the window
        t0 = time.perf_counter()
        checks = {}
        x, y = first_batch(getattr(loader, "_inner", trainer.bundle.train))
        if reducer is not None:
            from reduce_check import reducer_vs_pmean

            checks["reduce_rel_l2"] = reducer_vs_pmean(trainer, seed)
        missing = {e["step"] for e in steps if e["step"] not in health}
        nonfinite = {
            s for s, h in health.items() if not math.isfinite(h["loss"])}
        failed = len({e["step"] for e in bad_steps} | missing | nonfinite)
        checks["steps_without_health_record"] = len(missing)
        checks["bad_step_events"] = len(bad_steps)
        checks["nonfinite_losses"] = len(nonfinite)
    finally:
        trainer.close()
    del trainer, reducer
    gc.collect()
    reference = load_module(os.path.join(
        spec["home"], "references", spec["config"]["reference"] + ".py"))
    x, y = np.asarray(x), np.asarray(y)
    want = reference.first_step(params_0, x, y, seed=seed, shards=world)
    nan = {"loss": float("nan"), "grad_norm": float("nan")}
    got = health.get(1, nan)
    loss_k = health.get(warmup, nan)["loss"]

    def against_reference(step_1: dict) -> dict:
        return {
            "first_loss_rel":
                abs(step_1["loss"] - want["loss"]) / abs(want["loss"]),
            "first_grad_norm_rel":
                abs(step_1["grad_norm"] - want["grad_norm"]) / want["grad_norm"],
        }

    checks.update(against_reference(got))
    checks["loss_ratio"] = loss_k / got["loss"]
    checks["update_rel"] = rel_change(params_0, params_k)
    say("correct", f"step 1: program loss {got['loss']!r} gradient norm "
        f"{got['grad_norm']!r}, reference loss {want['loss']!r} gradient "
        f"norm {want['grad_norm']!r}; step {warmup} loss {loss_k!r}; "
        f"reference and checks took {time.perf_counter() - t0:.1f} s")
    limits = spec["config"].get("limits", {})
    correct = sound = judge(checks, limits)
    control_checks = None
    if "reference_dtype" in control:
        # the control: the reference in the program's place, computed in
        # the precision below the one the configuration states
        say("control", f"the reference in {control['reference_dtype']} "
            "in the program's place")
        control_checks = {**checks, **against_reference(reference.first_step(
            params_0, x, y, seed=seed, shards=world,
            dtype=control["reference_dtype"]))}
        correct = judge(control_checks, limits)

    result = {
        "correct": bool(correct),
        "attempted": len(window_steps) if seconds > 0 else len(steps),
        "failed": failed,
        "metrics": {},
        "device": {**device, "memory_peak_bytes": int(peak_bytes)},
    }
    # the numbers compared: on a phase line, not in the contract's line
    compared = {"checks": control_checks or checks}
    if control_checks is not None:
        compared["sound"] = {"correct": sound, "checks": checks}
    say("correct", "compared " + json.dumps(compared))
    if seconds <= 0:
        return result, compared
    run = {
        "chips": chips,
        "device_kind": device["kind"],
        "global_batch": global_batch,
        "per_device_batch": cfg.batch_size * cfg.nsteps_update,
        "window_s": window_s,
        "window_steps": window_steps,
        "setup_s": setup_s,
        "init_s": init_s,
        "first_step_s": steps[0]["dur_s"],
        "peak_bytes": peak_bytes,
        "waits": loader.waits if loader is not None else [],
        "tel_offset": tel_offset,
        "traced": traced,
        "forward_macs": reference.forward_macs(
            tuple(spec["config"]["image_hw"]), spec["config"]["num_classes"]),
    }
    if trace:
        result["metrics"] = read_metrics(spec, run, "per_layer")
        if traced is not None and traced["reduced"]["devices"]:
            devs = traced["reduced"]["devices"]
            result["device"]["busy_s"] = statistics.fmean(
                d["busy_ns"] for d in devs) / 1e9
            result["device"]["window_s"] = devs[0]["window_ns"] / 1e9
            result["breakdown"] = breakdown(run)
    else:
        result["metrics"] = read_metrics(spec, run, "end_to_end")
    return result, compared


def trace_epoch(trainer, epoch: int, trace_dir: str) -> dict:
    """One epoch of the window under the profiler: device tracing only. With
    the host tracer on, the runtime's own threads write 1.4 million events an
    8-step epoch, the loop runs several times slower and the device idles for
    the tracer (my chip run, PR 23); with it off an epoch takes what it takes
    untraced. So the trace carries no host span, and `align` ties its clock
    to the host's through the step dispatches instead."""
    import jax

    import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    first_step = trainer.iteration + 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        t0 = time.perf_counter()
        trainer.train_epoch(epoch)
        jax.block_until_ready(trainer.state)
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    trace = trace_reduce.load_xplane(paths[0])
    say("trace", f"{os.path.getsize(paths[0])} bytes of trace; planes "
        + ", ".join(f"{p['name']} ({len(p['lines'])} lines)"
                    for p in trace["planes"] if p["lines"]))
    return {
        "trace": trace, "t0": t0, "t1": t1,
        "steps": trainer.iteration + 1 - first_step, "first_step": first_step,
    }


def align(traced: dict, window_steps: list, tel_offset: float) -> None:
    """Tie the trace's clock to the host's and reduce the traced epoch.

    A step's program cannot start on the device before the host began to
    dispatch it, and starts at once when the device was waiting for it. So
    the smallest difference between the n-th execution of the step program
    (trace clock) and the n-th dispatch (host clock) is the clocks' shift,
    to within the launch latency."""
    import trace_reduce

    first = traced["first_step"]
    steps = [e for e in window_steps
             if first <= e["step"] < first + traced["steps"]]
    dispatched = [(e["start_s"] + tel_offset) * 1e9 for e in steps]
    started = trace_reduce.step_starts(traced["trace"])
    if not started or not dispatched:  # no device plane (the CPU rehearsal)
        traced.pop("trace")
        traced["reduced"] = {"devices": []}
        return
    if len(started) != len(dispatched):
        say("trace", f"{len(started)} executions of the step program for "
            f"{len(dispatched)} dispatches: aligning on the first of each")
        started, dispatched = started[:1], dispatched[:1]
    shift = min(d - h for d, h in zip(started, dispatched))
    traced["shift_ns"] = shift
    traced["dispatches"] = steps
    traced["window"] = (traced["t0"] * 1e9 + shift, traced["t1"] * 1e9 + shift)
    traced["reduced"] = trace_reduce.reduce_trace(
        traced.pop("trace"), window=traced["window"])


def read_metrics(spec: dict, run: dict, kind: str) -> dict:
    """Each metric of `kind` this cell reports, from its own reader file
    (`end_to_end/<name>.py`, `per_layer` under `layer_metrics/<name>.py`). A
    reader that finds nothing to read returns None and the metric is left
    out of the line."""
    subdir = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[kind]
    out = {}
    for metric in cell_metrics(spec, kind):
        reader = load_module(
            os.path.join(spec["home"], subdir, metric["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def host_activity(run: dict) -> dict:
    """What the host loop was doing during the traced epoch, as intervals on
    the trace's clock: waiting for the loader, placing the batch, dispatching
    the step, and the work after the dispatch (guard and health reads, log
    pull, probes). From the loader proxy's clock readings and the program's
    own `step` events."""
    traced = run["traced"]
    lo, hi = traced["window"]

    def on_trace(t_s: float) -> float:
        return t_s * 1e9 + traced["shift_ns"]

    dispatch = [
        (on_trace(e["start_s"] + run["tel_offset"]),
         on_trace(e["start_s"] + e["dur_s"] + run["tel_offset"]))
        for e in traced["dispatches"]
    ]
    waits = [
        (on_trace(a), on_trace(b)) for a, b in run["waits"]
        if lo <= on_trace(a) < hi
    ]
    placement, after = [], []
    for start, end in dispatch:
        before = [w for w in waits if w[1] <= start]
        if before:
            placement.append((before[-1][1], start))
        later = [w for w in waits if w[0] >= end]
        after.append((end, later[0][0] if later else hi))
    return {
        "input_wait": waits, "placement": placement, "dispatch": dispatch,
        "after_dispatch(guard,health,log)": after,
    }


def breakdown(run: dict) -> dict:
    import trace_reduce

    dev = max(run["traced"]["reduced"]["devices"],
              key=lambda d: d["window_ns"] - d["busy_ns"])
    idle = trace_reduce.attribute_gaps(
        dev["busy"], dev["window"], host_activity(run))
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in dev["top_ops"]],
        "idle_gaps": [[n, ns / 1e9] for n, ns in idle],
    }


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for logs, telemetry stream and trace "
                         "(default bench_out/<workload>/seed<n>-trace<t>)")
    ap.add_argument("--check-seeds", default=None,
                    help="comma-separated seeds: print the compared numbers "
                         "of each, one set-up after another in one process")
    ap.add_argument("--control", default=None,
                    help="switch on one of the configuration's "
                         "lower-precision controls (with --check-seeds)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, four virtual devices, the tiny configuration")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    for path in (HERE, ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    if importlib.util.find_spec("mgwfbp_tpu") is None:
        raise SystemExit(
            "benchmark: the program (mgwfbp_tpu) is not in this checkout; "
            "the benchmark measures it and prints no result without it"
        )
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        spec = load_cell(None)
        seconds = 3.0 if args.seconds is None else args.seconds
    else:
        if not args.workload:
            raise SystemExit("benchmark: --workload is required")
        spec = load_cell(args.workload)
        seconds = (
            float(spec["bench"]["run_seconds"]) if args.seconds is None
            else args.seconds
        )
    control = {}
    if args.control:
        control = spec["config"]["controls"][args.control]
    out = args.out or os.path.join(
        ROOT, "bench_out", spec["cell"]["name"],
        f"seed{args.seed}-trace{args.trace}")
    counter = CompileCounter()
    if args.check_seeds:
        for seed in (int(s) for s in args.check_seeds.split(",")):
            result, compared = run_once(
                spec, seed, 0.0, False, out, control, counter,
                rehearsal=args.rehearse)
            if "sound" in compared:
                print(json.dumps(
                    {"seed": seed, "control": None, **compared["sound"]}),
                    flush=True)
            print(json.dumps({
                "seed": seed, "control": args.control,
                "correct": result["correct"], "checks": compared["checks"],
            }), flush=True)
        return 0
    result, compared = run_once(
        spec, args.seed, seconds, bool(args.trace), out, control, counter,
        rehearsal=args.rehearse)
    if args.rehearse:
        # CPU numbers go under no device metric's name
        result = {
            "rehearsal": "cpu", "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "checks": compared["checks"],
            "metric_names": sorted(result["metrics"]),
        }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
