"""Cost measurement: communication micro-benchmark + layer-wise backward
timing.

Parity targets (SURVEY.md §2.4): reference profiling.py —
`CommunicationProfiler` (:150-183, allreduce sweep over 8K..504K-element
tensors, 5 warmup + N timed each, feeding the sklearn alpha-beta fit at
distributed_optimizer.py:105-127) and `Profiling`/`benchmark` (:13-147,
per-parameter autograd hooks timestamping gradient arrival over 5 warmup +
50 timed full fwd/bwd iterations).

TPU re-design: there are no per-op host hooks under jit (SURVEY.md §7 "hard
parts"), so
  * the comm profiler times REAL `lax.pmean` collectives of each size inside
    a tiny jitted shard_map program (block_until_ready timing), then fits
    alpha-beta with the closed-form least squares from costmodel;
  * layer-wise backward durations are MEASURED by profiler-trace
    attribution (`trace_layerwise_backward`): one `jax.profiler.trace` of
    the jitted backward, device op durations mapped to gradient leaves via
    the jax name-stack scopes XLA preserves in op metadata (the TPU answer
    to the reference's per-parameter hook timestamps, profiling.py:31-48);
    per-scope time splits among a scope's leaves by parameter volume, the
    unattributed residual is spread by the volume prior, and the sum is
    normalized to the measured total backward wall-clock;
  * when tracing yields nothing attributable (exotic backends), the
    fallback distributes the measured TOTAL by the volume prior alone
    (`benchmark_backward`) — measured scale, approximate shape.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import re
import time
from typing import Any, Callable, Iterable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mgwfbp_tpu.parallel.costmodel import AlphaBeta, fit_alpha_beta
from mgwfbp_tpu.parallel.mesh import DATA_AXIS

_log = logging.getLogger("mgwfbp.profiling")
# a child of the Trainer's logger (utils/logging.get_logger: handlers of its
# own, no propagation), so that what building the step's map cost stands in
# the run's own log
_run_log = logging.getLogger("mgwfbp.trainer.profiling")

# Reference sweep: 8K..504K float32 elements in 8K steps (profiling.py:158-160)
# extended upward: TPU interconnects only hit peak bandwidth at MBs.
DEFAULT_SIZES = tuple(int(2**k) for k in range(13, 25))  # 8K .. 16M elements


@dataclasses.dataclass
class CommProfile:
    sizes_bytes: list[float]
    times_s: list[float]
    model: AlphaBeta


def profile_allreduce(
    mesh: Mesh,
    sizes: Sequence[int] = DEFAULT_SIZES,
    warmup: int = 5,
    iters: int = 20,
    axis_name: str = DATA_AXIS,
    dtype=jnp.float32,
) -> CommProfile:
    """Time one pmean per payload size on the real mesh; fit t = a + b*bytes.

    Reference protocol: CommunicationProfiler.benchmark (profiling.py:163-182)
    with synchronize-per-iteration; here each timed call is a jitted psum
    program completed with block_until_ready.
    """
    times, nbytes = [], []
    itemsize = jnp.dtype(dtype).itemsize
    for n in sizes:

        def f(x):
            return lax.pmean(x, axis_name)

        fn = jax.jit(
            shard_map(
                f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
            )
        )
        x = jnp.ones((n,), dtype)
        for _ in range(warmup):
            fn(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(x).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        times.append(dt)
        nbytes.append(n * itemsize)
    return CommProfile(
        sizes_bytes=nbytes, times_s=times, model=fit_alpha_beta(nbytes, times)
    )


def profile_allgather(
    mesh: Mesh,
    sizes: Sequence[int] = DEFAULT_SIZES,
    warmup: int = 5,
    iters: int = 20,
    axis_name: str = DATA_AXIS,
    dtype=jnp.float32,
) -> CommProfile:
    """Time one tiled all-gather per payload size on the real mesh.

    ``sizes`` are FULL-payload element counts (the same axis as
    `profile_allreduce`): each member holds n/P elements and the gather
    reassembles n — exactly the AG leg of an n-element ring all-reduce,
    and exactly what the cross-step rs_fwd_ag lowering defers into the
    next step's forward. The ratio of this sweep to the full-collective
    sweep fits `ag_fraction` (`fit_ag_fraction`), replacing the solver's
    halved-split prior with the link's measured RS/AG asymmetry
    (ROADMAP PR-7 follow-up b)."""
    times, nbytes = [], []
    itemsize = jnp.dtype(dtype).itemsize
    world = int(mesh.shape[axis_name])
    for n in sizes:
        shard = max(n // world, 1)

        def f(x):
            return lax.all_gather(x, axis_name, tiled=True)

        fn = jax.jit(
            shard_map(
                f, mesh=mesh, in_specs=P(axis_name), out_specs=P(),
                check_vma=False,
            )
        )
        x = jnp.ones((shard * world,), dtype)
        for _ in range(warmup):
            fn(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(x).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        times.append(dt)
        nbytes.append(shard * world * itemsize)
    return CommProfile(
        sizes_bytes=nbytes, times_s=times, model=fit_alpha_beta(nbytes, times)
    )


def profile_two_level(
    ici: int,
    dcn: int,
    sizes: Sequence[int] = DEFAULT_SIZES,
    warmup: int = 5,
    iters: int = 20,
    allgather: bool = False,
    noop_baseline: bool = False,
    devices: Optional[Sequence] = None,
    dtype=jnp.float32,
):
    """Per-axis alpha-beta calibration of an (ici x dcn) two-axis mesh —
    the `calibrate --two-level` engine (previously private to
    tools/two_level_validation.py).

    Times a pmean over ONLY the inner (data/ICI) axis and ONLY the outer
    (dcn) axis at every payload size. ``noop_baseline=True`` additionally
    sweeps a no-collective program (each standalone sweep bakes one
    program dispatch into its curve; a fused hierarchical program pays it
    once, so composition consumers subtract it — the validation tool's
    dispatch correction; the calibrate CLI has no consumer for it, so the
    default skips that third of the sweep wall time). With
    ``allgather=True`` a tiled inner-axis AG sweep additionally fits the
    ICI link's ag_fraction (the RS/AG split the two-link solver's leg
    costs use).

    Returns (model, raw): `model` is a TwoLevelAlphaBeta whose members
    are full SampledCost curves (persist with `costmodel.save_profile` —
    schema-stamped, loads anywhere a two-level profile loads), `raw` the
    per-size sweeps keyed by FULL payload bytes plus the mesh/axis names
    for callers that keep measuring on the same mesh (the validation
    tool's hier-vs-flat sweep).

    On a virtual CPU mesh both "axes" share one memory fabric, so the
    constants differ only by group size/contention — fine for validating
    the model's COMPOSITION, meaningless as DCN physics; calibrate on a
    real multi-slice topology for production constants."""
    from mgwfbp_tpu.parallel.costmodel import SampledCost, TwoLevelAlphaBeta
    from mgwfbp_tpu.parallel.mesh import DCN_AXIS, MeshSpec, make_mesh

    if dcn <= 1:
        raise ValueError(f"--two-level needs dcn > 1 (got {dcn})")
    mesh = make_mesh(
        MeshSpec(data=ici, dcn=dcn),
        devices=(
            list(devices)[: ici * dcn]
            if devices is not None
            else jax.devices()[: ici * dcn]
        ),
    )
    itemsize = jnp.dtype(dtype).itemsize

    def sweep(body) -> dict[int, float]:
        out = {}
        for n in sizes:
            fn = jax.jit(shard_map(
                body, mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False,
            ))
            x = jnp.ones((n,), dtype)
            for _ in range(warmup):
                fn(x).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                fn(x).block_until_ready()
            out[n * itemsize] = (time.perf_counter() - t0) / iters
        return out

    t_ici = sweep(lambda x: lax.pmean(x, DATA_AXIS))
    t_dcn = sweep(lambda x: lax.pmean(x, DCN_AXIS))
    t_noop = sweep(lambda x: x * 1.0) if noop_baseline else {}
    nbytes = sorted(t_ici)
    ab_ici = fit_alpha_beta(nbytes, [t_ici[b] for b in nbytes])
    ab_dcn = fit_alpha_beta(nbytes, [t_dcn[b] for b in nbytes])
    ag_fraction = 0.5
    if allgather:
        full = CommProfile(
            sizes_bytes=list(nbytes),
            times_s=[t_ici[b] for b in nbytes],
            model=ab_ici,
        )
        ag_prof = profile_allgather(
            mesh, sizes=sizes, warmup=warmup, iters=iters,
            axis_name=DATA_AXIS, dtype=dtype,
        )
        ag_fraction = fit_ag_fraction(full, ag_prof)
    # sampled curves, not just the 2-parameter fits: one flat beta cannot
    # describe payload-dependent per-byte cost (cache regimes on CPU, DMA
    # pipelining on TPU) — same reason flat calibrations persist curves
    model = TwoLevelAlphaBeta(
        ici=SampledCost(
            sizes_bytes=tuple(nbytes),
            times_s=tuple(t_ici[b] for b in nbytes),
            ab=ab_ici,
            ag_fraction=ag_fraction,
        ),
        dcn=SampledCost(
            sizes_bytes=tuple(nbytes),
            times_s=tuple(t_dcn[b] for b in nbytes),
            ab=ab_dcn,
        ),
        ici_size=int(ici),
        dcn_size=int(dcn),
    )
    raw = {
        "mesh": mesh,
        "inner_axis": DATA_AXIS,
        "outer_axis": DCN_AXIS,
        "sizes_bytes": list(nbytes),
        "ici_s": t_ici,
        "dcn_s": t_dcn,
        "noop_s": t_noop,
        "ag_fraction": ag_fraction,
        "fit": {
            "ici": {"alpha": ab_ici.alpha, "beta": ab_ici.beta},
            "dcn": {"alpha": ab_dcn.alpha, "beta": ab_dcn.beta},
        },
    }
    return model, raw


def fit_ag_fraction(
    full: CommProfile, ag: CommProfile,
    lo: float = 0.05, hi: float = 0.95,
) -> float:
    """ag_fraction from paired sweeps: the median per-size ratio of the
    all-gather time to the full-collective time, clamped to [lo, hi] —
    a degenerate calibration (noise making AG "free" or "everything")
    must not zero out a whole phase of the cross-step timeline. The
    sweeps come from the same `calibrate` invocation over the same size
    list, so samples pair by INDEX (the recorded payload bytes differ
    when world does not divide a sweep size — the AG sweep rounds to
    whole shards). Mismatched sweeps fall back to the 0.5 prior with a
    warning: a silently unmeasured split stamped as measured is exactly
    what this function must not produce."""
    import logging

    ratios = [
        ag_t / full_t
        for full_t, ag_t in zip(full.times_s, ag.times_s)
        if full_t > 0.0
    ]
    if len(full.times_s) != len(ag.times_s) or not ratios:
        logging.getLogger("mgwfbp.profiling").warning(
            "fit_ag_fraction: sweeps do not pair (%d full vs %d ag "
            "samples); keeping the unmeasured 0.5 phase-split prior",
            len(full.times_s), len(ag.times_s),
        )
        return 0.5
    return float(min(max(float(np.median(ratios)), lo), hi))


def profile_group_overhead(
    mesh: Mesh,
    alpha: float,
    total_elems: int = 1 << 22,
    group_counts: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
    warmup: int = 3,
    iters: int = 10,
    axis_name: str = DATA_AXIS,
    dtype=jnp.float32,
) -> tuple[float, list[tuple[int, float]]]:
    """Measure gamma: the fixed per-collective overhead beyond alpha.

    Runs the production bucket path (`merged_psum` with the token chain) over
    a FIXED total payload split into k equal groups, for each k. Pack/unpack
    bytes are constant across k, so the fitted slope of time vs k is the
    marginal cost of one more collective: link startup (alpha) plus the
    pack/dispatch/scheduling overhead the alpha-beta model misses. Returns
    (gamma = max(slope - alpha, 0), [(k, seconds), ...]).

    This is the calibration VERDICT r3 #1 asks for: the reference's model
    (distributed_optimizer.py:166-177) prices a collective as alpha + beta*n
    only, which cannot explain measured multi-group deficits of ~0.5 ms per
    group on the CPU-8 mesh.
    """
    from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce

    times: list[tuple[int, float]] = []
    for k in group_counts:
        per = max(total_elems // k, 1)
        leaves = [jnp.ones((per,), dtype) for _ in range(k)]
        reducer = make_merged_allreduce(
            leaves,
            axis_name=axis_name,
            policy="wfbp",  # one group per leaf = exactly k collectives
            names=[f"g{i:04d}" for i in range(k)],
        )

        def f(tree):
            return reducer(tree)

        fn = jax.jit(
            shard_map(
                f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
            )
        )
        for _ in range(warmup):
            jax.block_until_ready(fn(leaves))
        # min of 3 windows: a single window per k lets one host-load spike
        # bend the fitted slope (gamma varied ~3x across calibration runs);
        # the minimum estimates the undisturbed time
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(leaves)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / iters)
        times.append((k, best))
    ks = np.asarray([k for k, _ in times], np.float64)
    ts = np.asarray([t for _, t in times], np.float64)
    slope = float(((ks - ks.mean()) * (ts - ts.mean())).sum()
                  / max(((ks - ks.mean()) ** 2).sum(), 1e-30))
    return max(slope - alpha, 0.0), times


def profile_pack_overhead(
    mesh: Mesh,
    total_elems: int = 1 << 22,
    members: int = 32,
    warmup: int = 3,
    iters: int = 10,
    axis_name: str = DATA_AXIS,
    dtype=jnp.float32,
) -> float:
    """Measure pack_beta: the per-byte cost of bucketizing a MULTI-member
    group (flatten-concat before the collective + split-unpack after).

    Two programs with identical payload and collective count — one group of
    ONE tensor (reduce in place, no copy) vs one group of `members` tensors
    (real concat + split) — isolate the bucketization copy; the difference
    divided by the payload bytes is pack_beta (costmodel.AlphaBeta.pack_beta).
    """
    from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce

    def timed(leaves):
        reducer = make_merged_allreduce(
            leaves,
            axis_name=axis_name,
            policy="single",
            names=[f"g{i:04d}" for i in range(len(leaves))],
        )
        fn = jax.jit(
            shard_map(
                lambda t: reducer(t), mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False,
            )
        )
        for _ in range(warmup):
            jax.block_until_ready(fn(leaves))
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(leaves)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    per = max(total_elems // members, 1)
    # identical payload in both programs (per*members, not total_elems —
    # a remainder would bill the mono baseline for bytes the packed run
    # never reduces and bias pack_beta low)
    t_mono = timed([jnp.ones((per * members,), dtype)])
    t_packed = timed([jnp.ones((per,), dtype) for _ in range(members)])
    nbytes = float(per * members * jnp.dtype(dtype).itemsize)
    return max((t_packed - t_mono) / nbytes, 0.0)


def profile_overlap_capability(
    mesh: Mesh,
    payload_elems: int = 1 << 22,
    warmup: int = 3,
    iters: int = 10,
    axis_name: str = DATA_AXIS,
    compiler_options: Optional[dict] = None,
) -> float:
    """Measure how much collective time the platform hides behind compute.

    Times three jitted shard_map programs: C (a compute chain), R (one
    all-reduce of `payload_elems`), and T (both, dataflow-independent so
    the compiler MAY run them concurrently). Returns
    clip((C + R - T) / min(C, R), 0, 1): 1.0 when the collective fully
    disappears behind compute (real TPU ICI — async DMA collectives), 0.0
    when they serialize (virtual CPU mesh: collective thunks run on the
    same cores as compute). The solver's simulation blends its overlapped
    and serialized timelines by this factor (simulate_groups); the
    reference assumes 1.0 unconditionally (NCCL streams), which mispredicts
    any platform that cannot overlap. `compiler_options` go to each of the
    three programs' `jax.jit` (train/step.py's `async_collective_options`
    are what the train step is built with: with and without them is how to
    learn what a chip needs before it overlaps).
    """
    w = jnp.ones((512, 512), jnp.float32) * 1e-3
    payload = jnp.ones((payload_elems,), jnp.float32)

    def compute_chain(k):
        def f(x, z):
            y = x
            for _ in range(k):
                y = jnp.tanh(y @ w)
            return y
        return f

    def comm_only(x, z):
        return lax.pmean(z, axis_name)

    def time_fn(body, out_spec):
        fn = jax.jit(
            shard_map(
                body, mesh=mesh, in_specs=(P(), P()), out_specs=out_spec,
                check_vma=False,
            ),
            compiler_options=compiler_options or None,
        )
        x = jnp.ones((512, 512), jnp.float32)
        for _ in range(warmup):
            jax.block_until_ready(fn(x, payload))
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(x, payload)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    r = time_fn(comm_only, P())
    c4 = time_fn(compute_chain(4), P())
    # scale the chain so C is comparable to R (overlap is best measured
    # when neither side trivially dominates)
    k = max(int(round(4 * r / max(c4, 1e-9))), 1)
    k = min(k, 512)
    c = time_fn(compute_chain(k), P())

    def both(x, z):
        return compute_chain(k)(x, z), lax.pmean(z, axis_name)

    t = time_fn(both, (P(), P()))
    denom = min(c, r)
    if denom <= 0:
        return 1.0
    return float(min(max((c + r - t) / denom, 0.0), 1.0))


def backward_cost_weights(params: Any, perm: Sequence[int]) -> np.ndarray:
    """Analytic per-leaf backward-cost weights in arrival order.

    Parameter volume is the per-layer cost proxy: for dense layers backward
    FLOPs ~ 2*numel*batch; for convs ~ 2*numel*output_positions*batch — the
    spatial factor varies, but relative ordering within a model is dominated
    by numel (the reference's measured tb correlates with layer size for the
    same reason its threshold policy packs by element count).
    """
    leaves = jax.tree_util.tree_leaves(params)
    w = np.asarray(
        [float(np.prod(leaves[j].shape)) if leaves[j].shape else 1.0 for j in perm]
    )
    return w / max(w.sum(), 1e-12)


def measure_step_time(
    fn: Callable, *args, warmup: int = 5, iters: int = 50
) -> float:
    """5 warmup + 50 timed iterations (reference benchmark protocol,
    profiling.py:100-101). fn must return a pytree of device arrays."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def time_carried_steps(
    step_once: Callable[[Any], Any],
    state: Any,
    iters: int,
    warmup: int = 1,
) -> tuple[Any, float]:
    """`measure_step_time` for LIVE training: time real steps while
    CARRYING the train state through, so every timed call is a genuine
    optimizer step on a fresh batch and nothing is discarded or replayed
    (the autotuner's race protocol — training never pauses or loses steps;
    `measure_step_time` re-feeds the same args, which donated-buffer steps
    cannot even accept twice).

    step_once(state) -> new_state must consume its own fresh batch per
    call. warmup steps (the first call compiles) run un-timed; the timed
    window is bracketed by one end sync like the bench protocol. Returns
    (final_state, sec_per_step).
    """
    for _ in range(max(warmup, 0)):
        state = step_once(state)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    n = max(iters, 1)
    for _ in range(n):
        state = step_once(state)
    jax.block_until_ready(state)
    return state, (time.perf_counter() - t0) / n


class TbProfile(list):
    """Arrival-ordered per-layer backward seconds, plus provenance.

    `source` records which path produced the numbers: 'trace' (profiler-
    event attribution, truly measured per layer) or 'volume-prior' (the
    measured TOTAL split by analytic numel weights — measured scale,
    approximate shape). A plain list everywhere it is consumed; the tag
    rides along for logs, the persisted tb_profile.json, and the autotune
    cache, so a schedule can always be audited back to how its tb was
    obtained."""

    def __init__(self, values, source: str = "volume-prior"):
        super().__init__(float(v) for v in values)
        self.source = source


def benchmark_backward(
    loss_fn: Callable,
    params: Any,
    loss_args: tuple,
    perm: Sequence[int],
    warmup: int = 5,
    iters: int = 50,
    names: Optional[Sequence[str]] = None,
) -> "TbProfile":
    """Layer-wise backward durations tb (arrival order).

    loss_fn(params, *loss_args) -> scalar. The returned list feeds
    `solver.build_schedule` exactly like the reference's measured
    `layerwise_times` (dist_trainer.py:45-51).

    With `names` (leaf key paths), the per-layer times are MEASURED by
    profiler-trace attribution (`trace_layerwise_backward`) scaled to the
    measured wall-clock total; the analytic numel-weight split of the
    measured total remains the documented fallback when no trace events
    attribute (exotic backends, or names not given). The result's
    `.source` tag records which path produced the numbers.
    """
    grad_fn = jax.jit(jax.grad(lambda p: loss_fn(p, *loss_args)))
    total = measure_step_time(grad_fn, params, warmup=warmup, iters=iters)
    if names is not None:
        tb = trace_layerwise_backward(
            grad_fn, params, names, perm, iters=min(max(iters, 1), 5),
            total_s=total,
        )
        if tb is not None:
            return TbProfile(tb, source="trace")
    weights = backward_cost_weights(params, perm)
    return TbProfile((total * w for w in weights), source="volume-prior")


def _leaf_scopes(names: Sequence[str]) -> list[str]:
    """Leaf key-path -> flax module scope string as it appears in jax name
    stacks: "['Block_1']['Conv_0']['kernel']" -> "Block_1/Conv_0"."""
    import re as _re

    scopes = []
    for nm in names:
        parts = _re.findall(r"\['([^']+)'\]", nm) or [nm]
        scopes.append("/".join(parts[:-1]) if len(parts) > 1 else parts[0])
    return scopes


def _trace_events(logdir: str) -> list[tuple[str, float]]:
    """(identifier, duration_us) of complete events in a jax profiler trace
    dir; identifier concatenates the event name with its args (the full
    jax/XLA metadata lives in either, depending on backend)."""
    import glob
    import gzip
    import json
    import os

    rows: list[tuple[str, float]] = []
    for p in glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.trace.json.gz")
    ):
        with gzip.open(p, "rt") as f:
            data = json.load(f)
        for e in data.get("traceEvents", []):
            if e.get("ph") == "X" and "dur" in e:
                ident = e.get("name", "")
                args = e.get("args")
                if isinstance(args, dict):
                    ident += " " + " ".join(str(v) for v in args.values())
                rows.append((ident, float(e["dur"])))
    return rows


def _with_trace_events(
    run: Callable[[], None],
    logdir: Optional[str] = None,
    prefix: str = "mgwfbp_trace_",
    read: Callable[[str], Any] = _trace_events,
) -> Any:
    """Run `run()` under `jax.profiler.trace` and return what `read` makes
    of the trace dir: the collected (identifier, duration_us) rows. Owns
    (and removes) a temporary logdir when none is given — the shared
    scaffolding of every trace-attribution path
    (`trace_layerwise_backward`, `trace_group_times`, `trace_step_split`)."""
    import shutil
    import tempfile

    own = logdir is None
    logdir = logdir or tempfile.mkdtemp(prefix=prefix)
    try:
        with jax.profiler.trace(logdir):
            run()
        return read(logdir)
    finally:
        if own:
            shutil.rmtree(logdir, ignore_errors=True)


def trace_layerwise_backward(
    grad_fn: Callable,
    params: Any,
    names: Sequence[str],
    perm: Sequence[int],
    iters: int = 5,
    logdir: Optional[str] = None,
    total_s: Optional[float] = None,
    prefer: str = "backward",
) -> Optional[list[float]]:
    """Measure per-leaf backward durations from a profiler trace.

    grad_fn(params) must be the jitted backward (already warmed up). Returns
    tb in ARRIVAL order (perm applied), normalized so sum(tb) equals the
    measured wall-clock total, or None when the trace has no attributable
    events (caller falls back to the volume prior).

    total_s: the wall-clock to normalize against. Pass a measurement taken
    under the PRODUCTION protocol (AOT executable, enough iterations to
    amortize per-call dispatch — `benchmark_trainer_backward` does this);
    the few traced iterations here carry profiler + dispatch overhead that
    inflated tb by >30% vs the measured step (VERDICT r3 Weak #3: the trace
    supplies the per-layer SHAPE, the scale must come from the same regime
    the schedule will run in).

    The reference timestamps each gradient's arrival from an autograd hook
    (reference profiling.py:31-48, 70-89); here the per-layer times come
    from the device timeline instead: every op XLA compiled from a module's
    forward carries that module's name-stack scope in its metadata, and the
    backward ops carry the same scope under `transpose(jvp(...))`.
    """
    total = (
        total_s
        if total_s is not None
        else measure_step_time(grad_fn, params, warmup=0, iters=iters)
    )

    def run():
        out = None
        for _ in range(iters):
            out = grad_fn(params)
        jax.block_until_ready(out)

    rows = _with_trace_events(run, logdir, prefix="mgwfbp_tb_trace_")
    if not rows:
        return None
    scopes = _leaf_scopes(names)
    scope_set = sorted(set(scopes), key=len, reverse=True)  # longest first
    # prefer events from the requested pass (XLA stamps backward ops with
    # `transpose(jvp(...))` in the name stack; forward ops carry the bare
    # module scope); fall back to any scope-tagged event
    if prefer == "forward":
        picked = [r for r in rows if "transpose" not in r[0]]
    else:
        picked = [r for r in rows if "transpose" in r[0]]
    pool = picked if picked else rows
    scope_time: dict[str, float] = {}
    for ident, dur in pool:
        for sc in scope_set:
            if sc and sc in ident:
                scope_time[sc] = scope_time.get(sc, 0.0) + dur
                break
    if not scope_time:
        return None
    leaves = jax.tree_util.tree_leaves(params)
    vol = [float(np.prod(leaves[j].shape)) or 1.0 for j in range(len(leaves))]
    # split each scope's time among its leaves by volume
    per_leaf = np.zeros(len(leaves))
    for sc, t in scope_time.items():
        members = [i for i, s in enumerate(scopes) if s == sc]
        if not members:
            continue
        w = np.asarray([vol[i] for i in members])
        w = w / w.sum()
        for i, wi in zip(members, w):
            per_leaf[i] += t * wi
    attributed = per_leaf.sum()
    if attributed <= 0:
        return None
    # unmatched leaves get the residual of the measured total, spread by
    # volume; then normalize the whole vector to the measured total
    missing = [i for i in range(len(leaves)) if per_leaf[i] == 0.0]
    per_leaf = per_leaf / attributed  # relative shares of traced time
    if missing:
        mvol = np.asarray([vol[i] for i in missing])
        share = float(mvol.sum()) / float(np.sum(vol))
        per_leaf *= 1.0 - share
        for i, w in zip(missing, mvol / mvol.sum()):
            per_leaf[i] = share * w
    tb_fwd = per_leaf * total
    return [float(tb_fwd[j]) for j in perm]


def benchmark_trainer_backward(
    model: Any,
    meta: Any,
    params: Any,
    batch_stats: Any,
    example_batch: dict,
    perm: Sequence[int],
    warmup: int = 5,
    iters: int = 50,
    names: Optional[Sequence[str]] = None,
    compute_dtype: Optional[Any] = None,
) -> list[float]:
    """benchmark(trainer) parity (reference profiling.py:95-147): measure
    the model's backward on one device and return arrival-ordered tb.

    With `names` (leaf key paths) the per-layer times come from profiler-
    trace attribution (`trace_layerwise_backward` — truly measured, like the
    reference's hook timestamps); otherwise, or when the trace yields
    nothing, the measured TOTAL is distributed by the volume prior.

    The TOTAL the per-layer shape is scaled to is measured under the same
    protocol the bench/training step uses — the AOT-compiled executable,
    >= 20 timed iterations, one end sync — so sum(tb) is comparable to (and
    bounded by) the measured step time; timing a freshly-jitted callable for
    a handful of iterations instead over-counts per-call dispatch, which fed
    the solver a >30% overestimate (VERDICT r3 Weak #3)."""
    from mgwfbp_tpu.train.step import make_loss_fn

    loss_fn = make_loss_fn(model, meta, compute_dtype=compute_dtype)
    rng = jax.random.PRNGKey(0)
    carry = None
    if getattr(meta, "has_carry", False):
        carry = model.initial_carry(example_batch["x"].shape[0])

    def scalar_loss(p, batch):
        loss, _ = loss_fn(p, batch_stats, batch, rng, carry)
        return loss

    if names is not None:
        grad_fn = jax.jit(lambda p: jax.grad(scalar_loss)(p, example_batch))
        run = grad_fn
        try:
            run = grad_fn.lower(params).compile()  # the bench protocol
        except Exception:
            pass
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(run(params))
        total = measure_step_time(
            run, params, warmup=0, iters=max(iters, 20)
        )
        tb = trace_layerwise_backward(
            run, params, names, perm, iters=iters, total_s=total
        )
        if tb is not None:
            return TbProfile(tb, source="trace")
    return benchmark_backward(
        scalar_loss, params, (example_batch,), perm, warmup=warmup, iters=iters
    )


def benchmark_trainer_forward(
    model: Any,
    meta: Any,
    params: Any,
    batch_stats: Any,
    example_batch: dict,
    perm: Sequence[int],
    warmup: int = 5,
    iters: int = 50,
    names: Optional[Sequence[str]] = None,
    compute_dtype: Optional[Any] = None,
) -> "TbProfile":
    """`benchmark_trainer_backward`'s twin for the FORWARD pass: measure
    the model's loss forward on one device and return arrival-ordered
    per-layer durations tf.

    This is the forward timeline the cross-step (rs_fwd_ag) solver prices
    deferred all-gathers against: group g's gather must land before the
    forward reaches its first consuming layer, so the solver needs to know
    how much forward compute precedes each layer. Attribution mirrors the
    backward benchmark: profiler-trace events keyed by module name-stack
    scopes where the backend preserves them (prefer='forward' keeps the
    non-`transpose` events), the measured total split by the volume prior
    otherwise; the measured TOTAL always comes from the AOT-compiled
    executable under the bench protocol, like tb.
    """
    from mgwfbp_tpu.train.step import make_loss_fn

    loss_fn = make_loss_fn(model, meta, compute_dtype=compute_dtype)
    rng = jax.random.PRNGKey(0)
    carry = None
    if getattr(meta, "has_carry", False):
        carry = model.initial_carry(example_batch["x"].shape[0])

    def scalar_loss(p, batch):
        loss, _ = loss_fn(p, batch_stats, batch, rng, carry)
        return loss

    fwd_fn = jax.jit(lambda p: scalar_loss(p, example_batch))
    run = fwd_fn
    try:
        run = fwd_fn.lower(params).compile()  # the bench protocol
    except Exception:
        pass
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(run(params))
    total = measure_step_time(run, params, warmup=0, iters=max(iters, 20))
    if names is not None:
        tf = trace_layerwise_backward(
            run, params, names, perm, iters=min(max(iters, 1), 5),
            total_s=total, prefer="forward",
        )
        if tf is not None:
            return TbProfile(tf, source="trace")
    weights = backward_cost_weights(params, perm)
    return TbProfile((total * w for w in weights), source="volume-prior")


# ---------------------------------------------------------------------------
# Layer-profile persistence (tb_profile.json and calibrate --forward's
# output). Version history:
#   1 — unstamped legacy: backward only ({tb_s, arrival_names, total_s,
#       source});
#   2 — adds schema_version and the optional forward timeline (tf_s,
#       tf_total_s, tf_source) the cross-step solver consumes.
# ---------------------------------------------------------------------------

LAYER_PROFILE_SCHEMA_VERSION = 2


def load_layer_profile(path: str) -> dict:
    """Read a persisted layer profile (tb_profile.json format).

    Returns the dict with `tb_s` and `tf_s` both present: a v1/legacy file
    (or a v2 file written before any forward benchmark ran) has no
    forward times, so `tf_s` defaults to ZEROS with a logged warning —
    "forward times defaulted to 0 — rs_fwd_ag disabled" — instead of a
    KeyError; a zero forward timeline makes the cross-step simulate see
    no forward compute to hide gathers behind, so no rs_fwd_ag schedule
    can win on it. Unknown future versions are rejected (the calibration
    profiles' `check_schema_version` convention)."""
    import json
    import logging

    from mgwfbp_tpu.parallel.costmodel import check_schema_version

    with open(path) as f:
        d = json.load(f)
    check_schema_version(
        d, path=path,
        supported=(1, LAYER_PROFILE_SCHEMA_VERSION),
        what="layer profile",
    )
    if not d.get("tf_s"):
        logging.getLogger("mgwfbp.profiling").warning(
            "%s: forward times defaulted to 0 — rs_fwd_ag disabled "
            "(re-profile with `python -m mgwfbp_tpu.calibrate --forward "
            "--model <dnn>` or a fresh training run to measure them)",
            path,
        )
        d["tf_s"] = [0.0] * len(d.get("tb_s", []))
        d.setdefault("tf_source", "absent")
    return d


# ---------------------------------------------------------------------------
# The compiled step as ONE map: instruction -> its `op_name` (the whole name
# stack: scope, pass, merge group) and its kind; a device trace reduced by
# it. The compiled module's per-instruction `op_name` metadata carries the
# name stack (`jit(step)/jvp(Model)/attn_window/...`, `transpose(` on the way
# back, `mgwfbp_groupNNNN` round a merge group) and a trace names each event
# after the instruction it ran, on the TPU (`XLA Ops`) and on the CPU mesh
# (the host threads' events), so the join needs no name stack in the trace.
# ---------------------------------------------------------------------------

_HLO_COLLECTIVES = frozenset((
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
))
# loops and calls span their bodies' events, which a trace lists as well
_HLO_CONTAINERS = frozenset(("while", "conditional", "call"))
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HLO_COMPUTATION = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")
_HLO_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')

_ASYNC_START, _ASYNC_DONE = "async-collective-start", "async-collective-done"
COLLECTIVE_KINDS = ("collective", "collective_start", "collective_done")
# what `classify` calls an instruction that no declared scope holds
MODEL_NO_SCOPE = "(model, no scope)"
NO_METADATA = "(no metadata)"
OUTSIDE_MODEL = "(outside the model)"
# the layer of the step's own scopes (train/step.py) in a declaration
UPDATE_LAYER = "update"


class Instruction(NamedTuple):
    op_name: Optional[str]  # None: the compiler gave it no metadata
    kind: str  # compute | container | collective | collective_start | _done


def _instruction_kind(opcode: str, name: str = "") -> str:
    """`container` for a loop, a branch or a call, whose bodies' events a
    trace lists as well; `collective` for a synchronous one (by OPCODE: a
    `psum` by name is an `all-reduce`); `collective_start` / `collective_done`
    for the halves of an asynchronous one and for a TPU async collective
    fusion (`%async-collective-start.N = ... fusion(...)`: the compiler wraps
    the collective and the compute steps that drive it in one kernel; its
    `-done` twin is where the core waits); else `compute`."""
    if opcode in _HLO_CONTAINERS:
        return "container"
    if opcode in _HLO_COLLECTIVES:
        return "collective"
    for half, fusion in (("start", _ASYNC_START), ("done", _ASYNC_DONE)):
        if name.startswith(fusion) or (
            opcode.endswith("-" + half)
            and opcode[: -len(half) - 1] in _HLO_COLLECTIVES
        ):
            return "collective_" + half
    return "compute"


def _hlo_opcode(rest: str) -> str:
    """The opcode of an instruction's text after its `=`. The result's shape
    comes first: a tuple's stands in parentheses, which layouts nest inside
    (`{1,0:T(8,128)(2,1)}`); any other holds no space."""
    if rest.startswith("("):
        depth = 0
        for at, char in enumerate(rest):
            depth += (char == "(") - (char == ")")
            if depth == 0:
                break
        rest = rest[at + 1:]
    else:
        rest = rest.partition(" ")[2]
    m = _HLO_OPCODE.match(rest)
    return m.group(1) if m is not None else ""


def hlo_instruction_map(hlo_text: str) -> dict[str, Instruction]:
    """{instruction name: (its op_name, its kind)} of a COMPILED
    (post-optimization) module's text, in one pass: THE parser of that text.

    Every computation's instructions but those of a fusion's called
    computation: a fusion runs as one instruction and is counted under the
    scope its own metadata names, its body never a second time. A Pallas
    kernel's custom call is printed over several lines (its
    `kernel_metadata` holds a JSON string with line breaks in it) and its
    `metadata={op_name=...}` stands on the last of them: lines that start
    neither an instruction nor a computation belong to the instruction
    before, and an instruction without metadata of its own inherits none
    (but a TPU `async-collective-start.N` fusion, which takes its
    `async-collective-done.N`'s)."""
    computations: dict[str, dict[str, Instruction]] = {}
    inside = computations.setdefault("", {})
    fused = set()
    waiting = None  # the instruction whose op_name has not been seen yet
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is not None:
            waiting, rest = m.groups()
            opcode = _hlo_opcode(rest)
            if opcode == "fusion":
                fused.update(_HLO_CALLS.findall(rest))
            inside[waiting] = Instruction(
                None, _instruction_kind(opcode, waiting))
        else:
            header = _HLO_COMPUTATION.match(line)
            if header is not None:
                inside = computations.setdefault(header.group(1), {})
                waiting = None
                continue
        if waiting is not None:
            meta = _HLO_OP_NAME.search(line)
            if meta is not None:
                inside[waiting] = inside[waiting]._replace(
                    op_name=meta.group(1))
                waiting = None
    out = {
        name: instruction
        for computation, body in computations.items()
        if computation not in fused
        for name, instruction in body.items()
    }
    # the TPU compiler gives an async collective fusion's start no metadata
    # of its own (asked here for a v5e, PR 49): it is the carrier of the
    # `-done` of its number, whose name stack names the merge group
    for name, instruction in out.items():
        if instruction.op_name is None and name.startswith(_ASYNC_START):
            done = out.get(_ASYNC_DONE + name[len(_ASYNC_START):])
            if done is not None:
                out[name] = instruction._replace(op_name=done.op_name)
    return out


def hlo_collective_scope_map(
    hlo_text: str, tag: str = "mgwfbp_group",
) -> dict[str, str]:
    """HLO instruction name -> merge-group scope: the instructions of
    `hlo_instruction_map` whose name stack passes through a ``<tag>NNNN``
    scope (the scope the jaxpr verifier matches on)."""
    return _scope_map(hlo_instruction_map(hlo_text), tag)


def _scope_map(
    instructions: dict[str, Instruction], tag: str,
) -> dict[str, str]:
    scope = re.compile(rf"{re.escape(tag)}\d+")
    found = ((name, scope.search(instruction.op_name or ""))
             for name, instruction in instructions.items())
    return {name: m.group(0) for name, m in found if m is not None}


def collective_counts(instructions: dict[str, Instruction]) -> dict[str, int]:
    """How many collectives a program issues, and how many of them
    asynchronously: `{"collectives": n, "async_collectives": k}`. The `-done`
    halves are counted with their start; the bare opcode INSIDE a fusion's
    called computation is that fusion's body (`hlo_instruction_map` leaves it
    out)."""
    kinds = collections.Counter(i.kind for i in instructions.values())
    return {
        "collectives": kinds["collective"] + kinds["collective_start"],
        "async_collectives": kinds["collective_start"],
    }


def hlo_collective_counts(hlo_text: str) -> dict[str, int]:
    """`collective_counts` of a COMPILED program's text."""
    return collective_counts(hlo_instruction_map(hlo_text))


def classify(
    op_name: Optional[str], scopes: Sequence[str],
) -> tuple[str, str]:
    """(scope, pass) of one instruction's op_name: the first of the declared
    `scopes` in its name stack, `backward` where the stack holds
    `transpose(` (recomputation with it); else `(model, no scope)` where the
    stack passes through autodiff (`jvp(`: norms, residual adds, the
    embedding), else `(outside the model)`."""
    if op_name is None:
        return NO_METADATA, "-"
    parts = op_name.split("/")
    direction = "backward" if "transpose(" in op_name else "forward"
    for scope in scopes:
        if scope in parts:
            return scope, direction
    if "jvp(" in op_name:
        return MODEL_NO_SCOPE, direction
    return OUTSIDE_MODEL, "-"


@dataclasses.dataclass
class StepMap:
    """The compiled step as a map, with what it takes to reduce a trace of
    it: the instructions, the scopes its model and `train/step.py` declare
    (scope -> layer of PERF.md's map, the model's first), the run's log
    directory, and what building it cost."""

    instructions: dict[str, Instruction]
    scopes: dict[str, str]
    logdir: Optional[str] = None
    hlo_bytes: int = 0
    build_s: float = 0.0


def _event_instruction(event_name: str) -> str:
    """`%fusion.7 = f32[8]{0} fusion(%a)` -> `fusion.7`: a trace event names
    the instruction it ran, with or without its text."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def split_trace(
    events: Iterable[tuple[str, float, float]],
    step_map: StepMap,
    window: Optional[tuple[float, float]] = None,
    steps: int = 1,
    top: int = 25,
) -> dict:
    """THE reduction of a device trace by the step map. `events`: (name,
    start_ns, duration_ns) of the instruction stream (`XLA Ops`), those that
    start inside `window` counted; `steps`: the executions of the step they
    cover (traced steps x the devices whose events are in the list, which
    makes every number a mean over the devices). Milliseconds a step.

    Containers are skipped. An instruction under a merge-group scope or of
    collective kind is the EXCHANGE's (`device_ms`: packing, carriers, waits;
    of it `wait_ms`, the `-done` halves and the synchronous collectives: the
    core stands in them; `calls`, collectives started a step, asynchronous
    ones counted) and, under a group scope, its group's (`groups`, by group
    index, every group of the map). Every other one goes by `classify` to
    `scopes` as name -> [forward_ms, backward_ms] (a name without a pass in
    the first place). `top`: the longest instructions as [ms, instruction,
    scope]. `total_ms` is the sum of all of it."""
    from mgwfbp_tpu.parallel.allreduce import GROUP_SCOPE_PREFIX

    declared = list(step_map.scopes)
    steps = max(int(steps), 1)
    group_of = {
        name: int(scope[len(GROUP_SCOPE_PREFIX):]) for name, scope
        in _scope_map(step_map.instructions, GROUP_SCOPE_PREFIX).items()}
    groups = [0.0] * (max(group_of.values(), default=-1) + 1)
    scopes: dict[str, list[float]] = {}
    exchange = {"device_ms": 0.0, "wait_ms": 0.0, "calls": 0.0}
    by_instruction: collections.Counter = collections.Counter()
    # by the event's own name: (instruction, kind, scope, pass)
    classified: dict[str, tuple[str, str, str, str]] = {}
    counted = 0
    for event, start, dur in events:
        if window is not None and not window[0] <= start < window[1]:
            continue
        if event not in classified:
            name = _event_instruction(event)
            known = step_map.instructions.get(name)
            kind = known.kind if known is not None else _instruction_kind(
                re.sub(r"\.\d+$", "", name), name)
            classified[event] = (name, kind, *classify(
                known.op_name if known is not None else None, declared))
        name, kind, scope, direction = classified[event]
        if kind == "container":
            continue
        counted += 1
        ms = dur * 1e-6 / steps
        if name in group_of or kind in COLLECTIVE_KINDS:
            scope = "exchange"
            exchange["device_ms"] += ms
            if kind in ("collective", "collective_done"):
                exchange["wait_ms"] += ms
            if kind in ("collective", "collective_start"):
                exchange["calls"] += 1.0 / steps
            if name in group_of:
                groups[group_of[name]] += ms
        else:
            scopes.setdefault(scope, [0.0, 0.0])[
                direction == "backward"] += ms
        by_instruction[(name, scope)] += ms
    return {
        "events": counted,
        "total_ms": exchange["device_ms"] + sum(map(sum, scopes.values())),
        "scopes": scopes,
        "layers": dict(step_map.scopes),
        "groups": groups,
        "exchange": exchange,
        "top": [[ms, name, scope] for (name, scope), ms
                in by_instruction.most_common(top)],
    }


def layer_ms(split: dict, *layers: str) -> float:
    """Both passes of the scopes a split's declaration puts in `layers`."""
    return sum(
        (sum(ms) for scope, ms in split["scopes"].items()
         if split["layers"].get(scope) in layers), 0.0)


def split_sums(split: dict) -> dict:
    """A split by what the benchmark's metrics and the report's totals read:
    `unscoped` (`(model, no scope)` + `(no metadata)`: the whole model where
    it declares no scope), `update` (the step's own scopes and whatever else
    is outside the model and not the exchange: optimizer, guard, statistics),
    `forward` / `backward` (the model's instructions by pass, scoped or
    not), `no_metadata`. The model's layers (`layer_ms`), `unscoped`,
    `update` and the exchange add up to `total_ms`; so do `forward`,
    `backward`, `update`, the exchange and `no_metadata`."""
    scopes, layers = split["scopes"], split["layers"]
    zero = [0.0, 0.0]
    model = [ms for scope, ms in scopes.items()
             if scope == MODEL_NO_SCOPE
             or layers.get(scope, UPDATE_LAYER) != UPDATE_LAYER]
    no_metadata = sum(scopes.get(NO_METADATA, zero))
    return {
        "unscoped": sum(scopes.get(MODEL_NO_SCOPE, zero)) + no_metadata,
        "update": sum(scopes.get(OUTSIDE_MODEL, zero))
        + layer_ms(split, UPDATE_LAYER),
        "forward": sum(ms[0] for ms in model),
        "backward": sum(ms[1] for ms in model),
        "no_metadata": no_metadata,
    }


def split_summary(split: dict) -> str:
    """A split in one line: each layer's total, the passes, the exchange."""
    sums = split_sums(split)
    totals = {layer: layer_ms(split, layer)
              for layer in dict.fromkeys(split["layers"].values())
              if layer != UPDATE_LAYER}
    totals.update(
        unscoped=sums["unscoped"], update=sums["update"],
        exchange=split["exchange"]["device_ms"])
    return (
        f"{split['total_ms']:.3f} ms of device ops a step in "
        f"{split['events']} events: "
        + ", ".join(f"{layer} {ms:.3f}" for layer, ms in totals.items() if ms)
        + f"; forward {sums['forward']:.3f}, backward "
        f"{sums['backward']:.3f}, no metadata {sums['no_metadata']:.3f}; "
        f"the exchange waits {split['exchange']['wait_ms']:.3f} ms in "
        f"{split['exchange']['calls']:g} collective(s) a step"
        + ("; by group " + " ".join(f"{ms:.3f}" for ms in split["groups"])
           if split["groups"] else ""))


def split_lines(split: dict) -> list[str]:
    """A split as a table: its summary, every scope with its layer, forward
    and backward, longest first, and the longest instructions. What
    `tools/telemetry_report.py` and the benchmark's `[scopes]` phase lines
    print."""
    whole = split["total_ms"] or 1.0
    rows = [(scope, split["layers"].get(scope, "-"), fwd, bwd)
            for scope, (fwd, bwd) in split["scopes"].items()]
    rows.append(("exchange", "exchange", split["exchange"]["device_ms"], 0.0))
    rows.sort(key=lambda r: -(r[2] + r[3]))
    lines = [
        split_summary(split),
        f"  {'scope':>22} {'layer':>16} {'forward':>10} {'backward':>10} "
        f"{'ms':>10} {'%':>6}",
    ]
    for scope, layer, fwd, bwd in rows:
        lines.append(
            f"  {scope:>22} {layer:>16} {fwd:10.3f} {bwd:10.3f} "
            f"{fwd + bwd:10.3f} {100.0 * (fwd + bwd) / whole:6.1f}")
    lines.append("  longest instructions (ms a step):")
    lines.extend(f"  {ms:10.3f} {name} [{scope}]"
                 for ms, name, scope in split["top"])
    return lines


# What the last step program dispatched in this process takes to be mapped,
# kept by the Trainer after the program's first dispatch (`note_step`): the
# jitted step and its arguments as shapes, dtypes and shardings (no device
# buffer), the declared scopes, the run's log directory. As
# `phases.setup_record()` it outlives the Trainer: the benchmark's readers
# (`benchmarks/scope_spans.py`) ask once the Trainer is closed.
_step: Optional[dict] = None
_step_map: Optional[StepMap] = None


def note_step(
    jitted: Any, args: Any, scopes: dict[str, str],
    logdir: Optional[str] = None,
) -> None:
    """A step program has been dispatched for the first time: keep what it
    takes to map it later. A rebuilt step replaces the one before and drops
    its map. Nothing is lowered, compiled or read here."""
    global _step, _step_map
    _step = {"jitted": jitted, "args": args, "scopes": dict(scopes),
             "logdir": logdir}
    _step_map = None


def step_map() -> Optional[StepMap]:
    """The map of the step program last dispatched in this process, built
    at the FIRST request and held from then on; None where no step was noted
    or its compiled text cannot be had. An untraced run never asks and pays
    nothing. No second compilation: the noted arguments describe the
    dispatch that built the program, so the lowering is jax's cached one and
    the executable the one in memory, or, for a step built with compile
    options (jax keeps no executable in memory for those), a read of the
    persistent compile cache that the dispatch wrote."""
    global _step_map
    if _step_map is not None or _step is None:
        return _step_map
    t0 = time.perf_counter()
    try:
        text = _step["jitted"].lower(*_step["args"]).compile().as_text()
    except Exception as e:  # noqa: BLE001 — a description of the program,
        # never a reason to stop training it
        _run_log.info("step map: compiled text unavailable (%s)", e)
        return None
    _step_map = StepMap(
        hlo_instruction_map(text), _step["scopes"], _step["logdir"],
        len(text))
    _step_map.build_s = time.perf_counter() - t0
    _run_log.info(
        "step map: %d instructions of %d bytes of compiled text in %.3f s",
        len(_step_map.instructions), len(text), _step_map.build_s)
    return _step_map


def trace_op_events(
    trace_dir: str, step_map: StepMap,
) -> tuple[list[tuple[str, float, float]], int]:
    """The instruction stream of the newest trace under `trace_dir` as
    (events, devices): every device plane's `XLA Ops` line, or, on a backend
    whose trace has no device plane (the CPU mesh), the host threads' events
    that are named after an instruction of the map, which are every local
    device's."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return [], 0
    planes = list(ProfileData.from_file(paths[-1]).planes)
    on_device = [p for p in planes
                 if re.match(r"^/device:(TPU|GPU):\d+$", p.name)]
    if on_device:
        return [
            (e.name, e.start_ns, e.duration_ns)
            for plane in on_device for line in plane.lines
            if line.name == "XLA Ops" for e in line.events
        ], len(on_device)
    return [
        (e.name, e.start_ns, e.duration_ns)
        for plane in planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name in step_map.instructions
    ], jax.local_device_count()


def trace_step_split(
    run_steps: Callable[[], None],
    step_map: Optional[StepMap],
    steps: int,
    logdir: Optional[str] = None,
) -> Optional[dict]:
    """`split_trace` of `steps` live steps that `run_steps()` executes (and
    blocks on) under the profiler, a mean over the local devices; None
    without a map or where the trace holds no event of its instructions.
    The trace slice stays in `logdir` where one is given."""

    def read(trace_dir: str) -> Optional[dict]:
        if step_map is None:
            return None
        events, devices = trace_op_events(trace_dir, step_map)
        if not events:
            return None
        return split_trace(events, step_map, steps=steps * devices)

    return _with_trace_events(
        run_steps, logdir, prefix="mgwfbp_profile_", read=read)


def _group_times_from_scopes(
    rows: Sequence[tuple[str, float]], num_groups: int, iters: int,
    scope_name=None,
) -> Optional[list[float]]:
    """The direct name-stack attribution: each group's time is the sum of
    the event durations whose identifier carries its scope, averaged over
    the traced steps (real TPU op metadata keeps the scope).

    ``scope_name`` maps a group index to its scope label; the default is
    the merge-group scope, and the hier lowering's DCN legs attribute by
    passing `allreduce.dcn_group_scope_name` instead (the per-link refit
    path — the two scope families never collide textually)."""
    if scope_name is None:
        from mgwfbp_tpu.parallel.allreduce import group_scope_name

        scope_name = group_scope_name
    out: list[float] = []
    for gi in range(num_groups):
        tag = scope_name(gi)
        dur_us = sum(dur for ident, dur in rows if tag in ident)
        if dur_us <= 0.0:
            return None  # partial attribution is worse than none
        out.append(dur_us * 1e-6 / max(iters, 1))
    return out


def _group_times_from_hlo_join(
    rows: Sequence[tuple[str, float]],
    num_groups: int,
    hlo_text: str,
    tag: str = "mgwfbp_group",
    scope_name=None,
) -> Optional[list[float]]:
    """Attribution fallback via the compiled-HLO join
    (`hlo_collective_scope_map`): trace events are matched by HLO
    instruction NAME, and each instruction's MEAN event duration is its
    per-device per-step time (one event per device per traced step, so
    the mean normalizes over both `iters` and device multiplicity —
    unlike the scope path, whose per-device traces carry only local
    events). A group's time is the sum over its instructions (rs/ag legs
    count once each). Returns None when any group attributes nothing.

    ``tag``/``scope_name`` parameterize the scope family, exactly like
    `_group_times_from_scopes` — the hier DCN legs join on
    ``mgwfbp_dcngroup`` (which ``mgwfbp_group``'s regex cannot match:
    the prefix character before 'group' differs)."""
    if scope_name is None:
        from mgwfbp_tpu.parallel.allreduce import group_scope_name

        scope_name = group_scope_name

    scope_map = hlo_collective_scope_map(hlo_text, tag=tag)
    if not scope_map:
        return None
    per_instr: dict[str, tuple[float, int]] = {}
    for ident, dur in rows:
        name = ident.split(" ", 1)[0]
        if name in scope_map:
            t, c = per_instr.get(name, (0.0, 0))
            per_instr[name] = (t + dur, c + 1)
    out: list[float] = []
    for gi in range(num_groups):
        want = scope_name(gi)
        total_us = 0.0
        found = False
        for name, sc in scope_map.items():
            if sc != want or name not in per_instr:
                continue
            t, c = per_instr[name]
            total_us += t / max(c, 1)
            found = True
        if not found:
            return None
        out.append(total_us * 1e-6)
    return out


def trace_group_times(
    run_steps: Callable[[], None],
    num_groups: int,
    iters: int = 1,
    logdir: Optional[str] = None,
    hlo_text: Optional[str] = None,
) -> Optional[list[float]]:
    """Measured per-merge-group wall-clock from a profiler trace.

    run_steps() must execute `iters` live training steps (carrying state)
    and block until done; every device op a merge group issues carries its
    `mgwfbp_groupNNNN` name scope in the op metadata (the same introspection
    hook the jaxpr verifier matches on), so each group's time is the sum of
    its scoped event durations, averaged over the traced steps.

    With ``hlo_text`` (the COMPILED text of the step being traced), a
    backend whose trace events drop the name stack still attributes: the
    events are named after HLO instructions, and the compiled module's
    per-instruction ``op_name`` metadata recovers each collective's group
    scope (`hlo_collective_scope_map` — the live /profile endpoint's
    CPU-mesh path).

    Returns arrival-order seconds per group per step, or None when the
    trace attributes nothing for some group on EITHER path — the
    autotuner then falls back to step-time deltas
    (`autotune.step_delta_observations`).
    """
    rows = _with_trace_events(
        run_steps, logdir, prefix="mgwfbp_group_trace_"
    )
    if not rows:
        return None
    out = _group_times_from_scopes(rows, num_groups, iters)
    if out is None and hlo_text:
        out = _group_times_from_hlo_join(rows, num_groups, hlo_text)
    return out


def trace_two_level_group_times(
    run_steps: Callable[[], None],
    num_groups: int,
    num_dcn_groups: int,
    iters: int = 1,
    logdir: Optional[str] = None,
    hlo_text: Optional[str] = None,
) -> tuple[Optional[list[float]], Optional[list[float]]]:
    """Per-LINK trace attribution of a hier schedule (ROADMAP hier
    follow-up b): ONE profiler trace, split two ways — the
    ``mgwfbp_groupNNNN`` scopes time each bucket's ICI legs (RS + AG),
    the ``mgwfbp_dcngroupNNNN`` scopes its DCN collective. Returns
    ``(ici_times, dcn_times)`` in arrival / DCN-partition order (seconds
    per step), either side None when its scopes attribute nothing —
    the autotuner then falls back exactly as `trace_group_times` does.

    This is what lets `costmodel.refit_two_level_from_observations`
    refit a drifted DCN link ALONE (its `dcn_observations` input)
    instead of smearing a whole-step drift factor over both links."""
    from mgwfbp_tpu.parallel.allreduce import dcn_group_scope_name

    rows = _with_trace_events(
        run_steps, logdir, prefix="mgwfbp_group_trace_"
    )
    if not rows:
        return None, None
    ici = _group_times_from_scopes(rows, num_groups, iters)
    dcn = _group_times_from_scopes(
        rows, num_dcn_groups, iters, scope_name=dcn_group_scope_name
    )
    if hlo_text:
        if ici is None:
            ici = _group_times_from_hlo_join(rows, num_groups, hlo_text)
        if dcn is None:
            dcn = _group_times_from_hlo_join(
                rows, num_dcn_groups, hlo_text,
                tag="mgwfbp_dcngroup", scope_name=dcn_group_scope_name,
            )
    return ici, dcn


def dcn_shard_nbytes(
    layout: Any,
    dcn_groups: Sequence[Sequence[int]],
    ici_size: int,
    comm_dtype: Optional[Any] = None,
) -> list[int]:
    """Per-DCN-group OUTER-wire payload bytes: the sum of the members'
    padded 1/ici_size bucket shards — exactly the concatenated payload
    the hier lowering's one cross-slice collective moves (and the byte
    convention `refit_two_level_from_observations` expects for its
    `dcn_observations`)."""
    out: list[int] = []
    for members in dcn_groups:
        total = 0
        for gi in members:
            n = int(layout.group_sizes[gi])
            padded = n + ((-n) % max(int(ici_size), 1))
            itemsize = np.dtype(
                comm_dtype if comm_dtype is not None else layout.dtypes[gi]
            ).itemsize
            total += (padded // max(int(ici_size), 1)) * int(itemsize)
        out.append(total)
    return out


def profile_update_beta(
    mesh: Mesh,
    total_elems: int = 1 << 22,
    warmup: int = 3,
    iters: int = 10,
    axis_name: str = DATA_AXIS,
    dtype=jnp.float32,
) -> float:
    """Measure update_beta: the per-BUCKET-byte cost of the fused shard
    optimizer update the rs_opt_ag lowering runs between the reduce-scatter
    and the param all-gather (costmodel.AlphaBeta.update_beta).

    Two single-group programs of identical payload and collective phases —
    the plain rs_ag reduction vs rs_opt_ag with an SGD-momentum shard
    update in the middle — isolate the update's link-timeline occupancy;
    the difference divided by the BUCKET bytes is update_beta. The 1/world
    factor is folded in automatically: the measured update touches only the
    1/world shard while the divisor is the full bucket, exactly the
    convention the solver's `effective_cost_fn` charges.
    """
    from mgwfbp_tpu.optim import OptimSpec
    from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce

    world = mesh.shape[axis_name]
    leaves = [jnp.ones((total_elems,), dtype)]
    names = ["g0000"]

    def timed(fn, *args) -> float:
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(3):  # min-of-3 windows, like profile_group_overhead
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    rs = make_merged_allreduce(
        leaves, axis_name=axis_name, policy="single", names=names,
        comm_op="rs_ag",
    )
    fn_rs = jax.jit(
        shard_map(
            lambda t: rs(t), mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        )
    )
    t_rs = timed(fn_rs, leaves)

    spec = OptimSpec(lr=1e-3, kind="sgd", momentum=0.9)
    opt_red = make_merged_allreduce(
        leaves, axis_name=axis_name, policy="single", names=names,
        comm_op="rs_opt_ag", optim_spec=spec, world_size=world,
    )
    opt_state = opt_red.optim.init()
    state_spec = opt_red.optim.partition_spec()
    fn_opt = jax.jit(
        shard_map(
            lambda g, p, o: opt_red.reduce_and_update(g, p, o),
            mesh=mesh,
            in_specs=(P(), P(), state_spec),
            out_specs=(P(), state_spec),
            check_vma=False,
        )
    )
    t_opt = timed(fn_opt, leaves, leaves, opt_state)
    nbytes = float(total_elems * jnp.dtype(dtype).itemsize)
    return max((t_opt - t_rs) / nbytes, 0.0)
