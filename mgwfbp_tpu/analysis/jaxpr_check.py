"""Jaxpr-level verifier: does the lowered train step realize the schedule?

MG-WFBP's value proposition is that the merge schedule the solver emits is
ACTUALLY issued as N dtype-homogeneous fused collectives overlapping the
backward pass. Nothing at runtime checks that — a refactor of the step, a
jax upgrade, or an overeager XLA pass can silently degrade collective
granularity (the failure mode DeAR, arXiv:2302.12445, documents) while
training still converges. This pass traces the jitted step on ABSTRACT
inputs (`jax.make_jaxpr`; no devices execute anything) and statically
asserts, against the `MergedAllreduce` that built it:

  SCH003  the bucket layout covers every gradient leaf exactly once, with
          dtype-homogeneous groups and consistent offsets
          (`BucketLayout.validate`);
  SCH001  the traced program contains exactly `layout.num_groups` merged
          reduction collectives (matched via the `mgwfbp_groupNNNN` name
          scopes `parallel.allreduce` stamps on them);
  SCH007  each group's collective carries exactly the group's element count;
  SCH002  ... at the layout's bucket dtype (or the comm_dtype wire cast);
  SCH004  no OTHER collective appears outside the declared scopes
          (metrics_reduce / bstats_reduce / flat_grad_reduce) — a stray
          all_gather/all_to_all or an unscoped psum is granularity silently
          leaking away;
  SCH005  no host callbacks / debug prints ride the hot path;
  SCH006  the step donates its input buffers (params/opt-state aliasing —
          without it every step round-trips a full model copy through HBM);
  SCH008  the non-finite-gradient guard (resilience layer) is realized as
          configured: a guard-enabled step must carry the `is_finite`
          reduction feeding the metrics psum (its count rides the EXISTING
          metrics_reduce collective — the guard adds no collective of its
          own, which SCH001/SCH004 already pin), and a guard-disabled step
          must not;
  SCH009  the hierarchical (comm_op='hier') contract: per inner group one
          reduce-scatter then one all-gather over the INNER (ICI) axis
          only, per DCN group exactly one OUTER-axis collective under its
          ``mgwfbp_dcngroupNNNN`` scope moving exactly its members'
          concatenated shards at the wire dtype, the DCN partition
          covering every inner group exactly once, no cross-pod (outer-
          axis) collective anywhere else, and the DCN scope never
          appearing on a non-hier path;
  SCH010  the training-health statistics (ISSUE 12) are FREE at the
          collective layer: tracing the same step with health_stats on
          and off must yield identical collective footprints (same
          collective primitives, same counts — the stats ride the
          EXISTING metrics psum) and zero host callbacks either way. A
          stats build that grows the footprint is a new collective (or a
          host sync) smuggled into the hot path.
"""

from __future__ import annotations

import collections
import re
from typing import Any, Iterator, Optional, Sequence

import numpy as np

from mgwfbp_tpu.analysis.rules import Finding

# --- primitive classes (names as jax 0.9 binds them; matching is by name so
# the verifier needs no private jax imports). `lax.psum_scatter` binds
# `reduce_scatter`. Every shard_map in this repo runs check_vma=False, where
# a psum binds `psum`; under check_vma=True the same call binds
# `psum_invariant` (plus a `pvary` cast) and `all_gather_invariant` exists
# beside `all_gather`, so those are listed too -----------------------------
REDUCTION_PRIMS = frozenset({"psum", "psum_invariant", "reduce_scatter"})
OTHER_COLLECTIVE_PRIMS = frozenset({
    "all_gather", "all_gather_invariant", "all_to_all", "ragged_all_to_all",
    "pmax", "pmin", "ppermute", "pgather",
})
COLLECTIVE_PRIMS = REDUCTION_PRIMS | OTHER_COLLECTIVE_PRIMS
# host round trips: `jax.debug.print` binds `debug_print`, `jax.debug.callback`
# `debug_callback` (the host_callback primitives of jax 0.4 are gone)
CALLBACK_PRIMS = frozenset({
    "debug_callback", "debug_print", "pure_callback", "io_callback",
})

# scopes the train step declares for its OWN auxiliary collectives
# (train/step.py); anything else collective-shaped must be a merge group.
# "sharded_clip_norm" is the rs_opt_ag lowering's one cross-group psum of
# shard squared norms (global-norm clipping while every bucket is
# scattered) — parallel/allreduce.py CLIP_NORM_SCOPE, keep in sync.
# "runtime_coord" is the multi-host runtime's agreement psum/pmax
# (runtime/coordination.py COORD_SCOPE, keep in sync): today those run as
# standalone host-decision programs, but a step that ever traces one in
# stays verifier-clean by declaration instead of tripping SCH004.
DEFAULT_ALLOWED_SCOPES = (
    "metrics_reduce", "bstats_reduce", "flat_grad_reduce",
    "sharded_clip_norm", "runtime_coord",
)


def _group_scope_re() -> "re.Pattern[str]":
    """Regex for the merge-group scope, derived from the prefix constant
    `parallel.allreduce` stamps (import deferred: the lint-only CLI path
    must not pull jax in through this module)."""
    from mgwfbp_tpu.parallel.allreduce import GROUP_SCOPE_PREFIX

    return re.compile(re.escape(GROUP_SCOPE_PREFIX) + r"(\d+)")


def _dcn_scope_re() -> "re.Pattern[str]":
    """Regex for the hier lowering's DCN-group scope
    (`parallel.allreduce.DCN_GROUP_SCOPE_PREFIX`)."""
    from mgwfbp_tpu.parallel.allreduce import DCN_GROUP_SCOPE_PREFIX

    return re.compile(re.escape(DCN_GROUP_SCOPE_PREFIX) + r"(\d+)")


def _eqn_axes(eqn: Any) -> tuple:
    """Named mesh axes a collective eqn reduces/gathers over (psum and
    psum_scatter carry `axes`, all_gather `axis_name`); empty when the
    param shape is unrecognized."""
    for key in ("axes", "axis_name"):
        v = eqn.params.get(key)
        if v is None:
            continue
        if isinstance(v, str):
            return (v,)
        try:
            return tuple(a for a in v if isinstance(a, str))
        except TypeError:
            return ()
    return ()


def _scope_segments(scope: str) -> list[str]:
    """Name-stack entries of a rendered scope string, transformation
    wrappers stripped: 'transpose(jvp(metrics_reduce))/foo' ->
    ['metrics_reduce', 'foo']. Segment-exact matching keeps a scope like
    'extra_metrics_reduce_v2' from whitelisting stray collectives."""
    out = []
    for seg in scope.split("/"):
        while True:
            m = re.fullmatch(r"\w+\((.*)\)", seg)
            if m is None:
                break
            seg = m.group(1)
        out.append(seg)
    return out


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Depth-first walk of a jaxpr's eqns, recursing into every sub-jaxpr
    found in eqn params (pjit/shard_map/scan/cond/custom_* all carry their
    bodies under different param keys; duck-type instead of enumerating)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _sub_jaxprs(v: Any) -> Iterator[Any]:
    if hasattr(v, "eqns"):  # core.Jaxpr
        yield v
    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (tuple, list)):
        for u in v:
            yield from _sub_jaxprs(u)


def _scope_of(eqn: Any) -> str:
    try:
        return str(eqn.source_info.name_stack)
    except Exception:
        return ""


def _numel(aval: Any) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def collect_collectives(closed_jaxpr: Any) -> dict[str, list]:
    """Classify every collective/callback eqn in the traced program.

    Returns {"groups": {gi: [eqn, ...]}, "dcn_groups": {di: [eqn, ...]},
    "allowed": [...], "stray": [...], "callbacks": [...]} where group
    membership comes from the `mgwfbp_groupNNNN` (and, for the hier
    lowering's outer collectives, `mgwfbp_dcngroupNNNN`) name scopes
    stamped by `parallel.allreduce`.
    """
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    group_re = _group_scope_re()
    dcn_re = _dcn_scope_re()
    groups: dict[int, list] = {}
    dcn_groups: dict[int, list] = {}
    allowed: list = []
    stray: list = []
    callbacks: list = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS:
            callbacks.append(eqn)
            continue
        if name not in COLLECTIVE_PRIMS:
            continue
        scope = _scope_of(eqn)
        dm = dcn_re.search(scope)
        m = group_re.search(scope)
        if dm is not None:
            dcn_groups.setdefault(int(dm.group(1)), []).append(eqn)
        elif m is not None:
            groups.setdefault(int(m.group(1)), []).append(eqn)
        elif any(
            seg in DEFAULT_ALLOWED_SCOPES for seg in _scope_segments(scope)
        ):
            allowed.append(eqn)
        else:
            stray.append(eqn)
    return {
        "groups": groups, "dcn_groups": dcn_groups, "allowed": allowed,
        "stray": stray, "callbacks": callbacks,
    }


def find_donated(closed_jaxpr: Any) -> Optional[tuple[bool, ...]]:
    """donated_invars of the outermost jit eqn (primitive `jit` as of jax
    0.9, formerly `pjit`), or None when untraceable."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            d = eqn.params.get("donated_invars")
            if d is not None:
                return tuple(bool(x) for x in d)
    return None


def _check_rs_opt_ag_group(reducer: Any, gi: int, eqns: list, add) -> None:
    """The rs_opt_ag per-group collective contract: exactly ONE
    reduce-scatter (the padded grad bucket, at the wire dtype) and ONE
    all-gather (the UPDATED param shard, 1/world of the padded bucket, at
    the bucket dtype) under the group's scope — nothing else. A second
    reduction, a missing gather, or a full-bucket gather operand all mean
    the sharded-update seam silently degenerated (e.g. back to gathering
    gradients, or to a replicated update)."""
    layout = reducer.layout
    optim = reducer.optim
    comm_dtype = getattr(reducer, "comm_dtype", None)
    reductions = [e for e in eqns if e.primitive.name in REDUCTION_PRIMS]
    gathers = [e for e in eqns if e.primitive.name == "all_gather"]
    extra = [e for e in eqns if e not in reductions and e not in gathers]
    if len(reductions) != 1 or len(gathers) != 1:
        add("SCH001",
            f"rs_opt_ag group {gi}: expected exactly 1 reduce-scatter + 1 "
            f"all-gather under its scope, found {len(reductions)} "
            f"reduction(s) + {len(gathers)} gather(s)")
        return
    for e in extra:
        add("SCH004",
            f"rs_opt_ag group {gi}: unexpected '{e.primitive.name}' in "
            "the group scope")
    padded = optim.padded_size(gi)
    shard = optim.shard_size(gi)
    rs, ag = reductions[0], gathers[0]
    rs_elems = _numel(rs.invars[0].aval)
    if rs_elems != padded:
        add("SCH007",
            f"rs_opt_ag group {gi}: reduce-scatter moves {rs_elems} "
            f"elements, padded bucket is {padded}")
    ag_elems = _numel(ag.invars[0].aval)
    if ag_elems != shard:
        add("SCH007",
            f"rs_opt_ag group {gi}: all-gather operand is {ag_elems} "
            f"elements, the 1/world shard is {shard}")
    want_wire = comm_dtype if comm_dtype is not None else layout.dtypes[gi]
    if np.dtype(rs.invars[0].aval.dtype) != np.dtype(want_wire):
        add("SCH002",
            f"rs_opt_ag group {gi}: reduce-scatter runs at dtype "
            f"{np.dtype(rs.invars[0].aval.dtype).name}, wire dtype is "
            f"{np.dtype(want_wire).name}")
    if np.dtype(ag.invars[0].aval.dtype) != np.dtype(layout.dtypes[gi]):
        add("SCH002",
            f"rs_opt_ag group {gi}: param all-gather runs at dtype "
            f"{np.dtype(ag.invars[0].aval.dtype).name}, bucket dtype is "
            f"{np.dtype(layout.dtypes[gi]).name}")


def _check_rs_fwd_ag_group(reducer: Any, gi: int, eqns: list, add) -> None:
    """The cross-step per-group collective contract, per STEP: exactly ONE
    all-gather (the carried param shard, 1/world of the padded bucket, at
    the bucket dtype — the PREVIOUS step's deferred gather landing in this
    step's forward) followed, later in the program, by exactly ONE
    reduce-scatter (the padded grad bucket, at the wire dtype) whose
    updated shard carries out to the NEXT step. `eqns` preserves program
    order (iter_eqns walks the jaxpr depth-first in sequence), so
    AG-before-RS is exactly 'the gather sits in the forward region, the
    scatter in the backward' — an in-step RS..AG pair (the rs_opt_ag
    shape, i.e. the deferral silently degenerated) fails the order
    check."""
    layout = reducer.layout
    optim = reducer.optim
    comm_dtype = getattr(reducer, "comm_dtype", None)
    reductions = [e for e in eqns if e.primitive.name in REDUCTION_PRIMS]
    gathers = [e for e in eqns if e.primitive.name == "all_gather"]
    extra = [e for e in eqns if e not in reductions and e not in gathers]
    if len(reductions) != 1 or len(gathers) != 1:
        add("SCH001",
            f"rs_fwd_ag group {gi}: expected exactly 1 all-gather + 1 "
            f"reduce-scatter under its scope per step, found "
            f"{len(gathers)} gather(s) + {len(reductions)} reduction(s)")
        return
    for e in extra:
        add("SCH004",
            f"rs_fwd_ag group {gi}: unexpected '{e.primitive.name}' in "
            "the group scope")
    rs, ag = reductions[0], gathers[0]
    if eqns.index(ag) > eqns.index(rs):
        add("SCH004",
            f"rs_fwd_ag group {gi}: the all-gather follows the "
            "reduce-scatter in program order — the gather was NOT "
            "deferred across the step boundary (this is the in-step "
            "rs_opt_ag shape)")
    padded = optim.padded_size(gi)
    shard = optim.shard_size(gi)
    rs_elems = _numel(rs.invars[0].aval)
    if rs_elems != padded:
        add("SCH007",
            f"rs_fwd_ag group {gi}: reduce-scatter moves {rs_elems} "
            f"elements, padded bucket is {padded}")
    ag_elems = _numel(ag.invars[0].aval)
    if ag_elems != shard:
        add("SCH007",
            f"rs_fwd_ag group {gi}: all-gather operand is {ag_elems} "
            f"elements, the carried 1/world shard is {shard}")
    want_wire = comm_dtype if comm_dtype is not None else layout.dtypes[gi]
    if np.dtype(rs.invars[0].aval.dtype) != np.dtype(want_wire):
        add("SCH002",
            f"rs_fwd_ag group {gi}: reduce-scatter runs at dtype "
            f"{np.dtype(rs.invars[0].aval.dtype).name}, wire dtype is "
            f"{np.dtype(want_wire).name}")
    if np.dtype(ag.invars[0].aval.dtype) != np.dtype(layout.dtypes[gi]):
        add("SCH002",
            f"rs_fwd_ag group {gi}: param all-gather runs at dtype "
            f"{np.dtype(ag.invars[0].aval.dtype).name}, bucket dtype is "
            f"{np.dtype(layout.dtypes[gi]).name}")


def _check_hier_group(
    reducer: Any, gi: int, eqns: list, add
) -> Optional[int]:
    """The hier per-inner-group collective contract: exactly ONE
    reduce-scatter (the padded grad bucket at the wire dtype) followed by
    ONE all-gather (the slice shard, post-DCN) under the group's scope —
    both over the INNER (ICI) axis only. A cross-pod (outer-axis)
    collective inside a group scope means the lowering silently routed
    bucket traffic over the slow link the schedule never priced; AG
    before RS means the leg order degenerated. Returns the group's shard
    element count (the DCN contract's payload unit), or None when the
    shape is too broken to measure."""
    layout = reducer.layout
    comm_dtype = getattr(reducer, "comm_dtype", None)
    inner = reducer.axis_name[0]
    outer = reducer.axis_name[1] if len(reducer.axis_name) > 1 else None
    for e in eqns:
        axes = _eqn_axes(e)
        if outer is not None and outer in axes:
            add("SCH009",
                f"hier group {gi}: '{e.primitive.name}' over the OUTER "
                f"(DCN) axis {outer!r} inside an inner-group scope — "
                "cross-pod traffic belongs under mgwfbp_dcngroupNNNN")
    reductions = [e for e in eqns if e.primitive.name in REDUCTION_PRIMS]
    gathers = [e for e in eqns if e.primitive.name == "all_gather"]
    extra = [e for e in eqns if e not in reductions and e not in gathers]
    if len(reductions) != 1 or len(gathers) != 1:
        add("SCH001",
            f"hier group {gi}: expected exactly 1 reduce-scatter + 1 "
            f"all-gather under its scope, found {len(reductions)} "
            f"reduction(s) + {len(gathers)} gather(s)")
        return None
    for e in extra:
        add("SCH004",
            f"hier group {gi}: unexpected '{e.primitive.name}' in the "
            "group scope")
    rs, ag = reductions[0], gathers[0]
    if eqns.index(ag) < eqns.index(rs):
        add("SCH009",
            f"hier group {gi}: the all-gather precedes the reduce-scatter "
            "in program order — the inner RS -> outer AR -> inner AG leg "
            "order degenerated")
    for e, leg in ((rs, "reduce-scatter"), (ag, "all-gather")):
        axes = _eqn_axes(e)
        if axes and tuple(axes) != (inner,):
            add("SCH009",
                f"hier group {gi}: {leg} runs over axes {axes}, the inner "
                f"leg must ride {inner!r} only")
    want_elems = layout.group_sizes[gi]
    rs_elems = _numel(rs.invars[0].aval)
    if rs_elems < want_elems:
        add("SCH007",
            f"hier group {gi}: reduce-scatter moves {rs_elems} elements, "
            f"layout says >= {want_elems}")
    shard_elems = _numel(rs.outvars[0].aval)
    ag_elems = _numel(ag.invars[0].aval)
    if ag_elems != shard_elems:
        add("SCH007",
            f"hier group {gi}: all-gather operand is {ag_elems} elements, "
            f"the inner shard is {shard_elems}")
    want_wire = comm_dtype if comm_dtype is not None else layout.dtypes[gi]
    for e, leg in ((rs, "reduce-scatter"), (ag, "all-gather")):
        if np.dtype(e.invars[0].aval.dtype) != np.dtype(want_wire):
            add("SCH002",
                f"hier group {gi}: {leg} runs at dtype "
                f"{np.dtype(e.invars[0].aval.dtype).name}, wire dtype is "
                f"{np.dtype(want_wire).name}")
    return shard_elems


def _check_hier_dcn(
    reducer: Any, info: dict, shard_elems: dict, add
) -> None:
    """The hier DCN contract (SCH009): the nested partition covers every
    inner group exactly once, each DCN group issues exactly ONE psum over
    the OUTER axis moving exactly its members' concatenated shards at the
    wire dtype — no more DCN collectives than the schedule promised
    (merging on DCN exists to amortize the slow link's startup; a split
    the verifier misses silently doubles it)."""
    from mgwfbp_tpu.parallel.solver import check_dcn_partition

    layout = reducer.layout
    schedule = reducer.schedule
    comm_dtype = getattr(reducer, "comm_dtype", None)
    inner = reducer.axis_name[0]
    outer = reducer.axis_name[1] if len(reducer.axis_name) > 1 else None
    dcn_part = [list(d) for d in schedule.dcn_groups] or [
        [gi] for gi in range(layout.num_groups)
    ]
    try:
        check_dcn_partition(dcn_part, layout.num_groups)
    except ValueError as e:
        add("SCH009", f"hier: {e}")
        return
    observed = info["dcn_groups"]
    if sorted(observed) != list(range(len(dcn_part))):
        add("SCH009",
            f"hier: traced step issues DCN collectives for scopes "
            f"{sorted(observed)}, the nested schedule promises "
            f"{len(dcn_part)} DCN group(s)")
        return
    for di, members in enumerate(dcn_part):
        eqns = observed[di]
        if len(eqns) != 1 or eqns[0].primitive.name != "psum":
            add("SCH009",
                f"hier dcn group {di}: expected exactly 1 outer-axis psum "
                f"under its scope, found "
                f"{[e.primitive.name for e in eqns]}")
            continue
        eqn = eqns[0]
        axes = _eqn_axes(eqn)
        if axes and (
            (outer is not None and tuple(axes) != (outer,))
            or inner in axes
        ):
            add("SCH009",
                f"hier dcn group {di}: psum runs over axes {axes}, the "
                f"cross-slice leg must ride {outer!r} only")
        want = sum(
            shard_elems.get(gi) or 0 for gi in members
        )
        got = _numel(eqn.invars[0].aval)
        if all(shard_elems.get(gi) for gi in members) and got != want:
            add("SCH009",
                f"hier dcn group {di}: outer collective moves {got} "
                f"elements, members {members} shard to {want}")
        dtypes = {layout.dtypes[gi] for gi in members}
        want_wire = comm_dtype if comm_dtype is not None else (
            next(iter(dtypes)) if len(dtypes) == 1 else None
        )
        if want_wire is not None and (
            np.dtype(eqn.invars[0].aval.dtype) != np.dtype(want_wire)
        ):
            add("SCH009",
                f"hier dcn group {di}: outer collective runs at dtype "
                f"{np.dtype(eqn.invars[0].aval.dtype).name}, wire dtype "
                f"is {np.dtype(want_wire).name}")


def verify_jaxpr_against_reducer(
    closed_jaxpr: Any,
    reducer: Any,
    grad_leaves: Sequence[Any],
    *,
    expect_donation: bool = True,
    expect_finite_guard: Optional[bool] = None,
    file: str = "<traced step>",
) -> list[Finding]:
    """Check the MG-WFBP invariants of a traced step against its reducer.

    closed_jaxpr: `jax.make_jaxpr(step)(...)` output for the jitted step.
    reducer: the `MergedAllreduce` the step was built with.
    grad_leaves: gradient-leaf avals in ARRIVAL order (i.e. the layout's
        leaf order — `[leaves[j] for j in reducer.perm]`).
    expect_finite_guard: None skips the SCH008 check; True/False asserts
        the traced program does/does not realize the non-finite-gradient
        guard (matched via the `finite_check`-scoped `is_finite` eqns).
    """
    layout = reducer.layout
    schedule = reducer.schedule
    out: list[Finding] = []

    def add(rule_id: str, msg: str) -> None:
        out.append(Finding(file, 0, rule_id, msg))

    # --- structural pass: layout vs leaves (SCH003) ------------------------
    for problem in layout.validate(grad_leaves):
        add("SCH003", problem)
    if layout.num_groups != schedule.num_groups:
        add("SCH003",
            f"layout has {layout.num_groups} groups but the schedule "
            f"promises {schedule.num_groups}")

    # --- lowered program vs layout -----------------------------------------
    info = collect_collectives(closed_jaxpr)
    groups = info["groups"]
    if len(groups) != layout.num_groups:
        add("SCH001",
            f"traced step issues {len(groups)} merged collectives, "
            f"schedule promises {layout.num_groups}")
    comm_dtype = getattr(reducer, "comm_dtype", None)
    comm_op = getattr(reducer, "comm_op", "all_reduce")
    # the hier/rs_ag lowerings pad buckets to scatter-axis divisibility, so
    # their payload check is >=; the monolithic all-reduce is exact; a
    # sparsifying compressor moves k <= n elements chosen at trace time, so
    # no static payload expectation exists and the size check is skipped
    padded = comm_op != "all_reduce"
    sparsified = getattr(reducer, "compressor", None) is not None
    hier_shards: dict[int, Optional[int]] = {}
    for gi in sorted(groups):
        if gi >= layout.num_groups:
            add("SCH001",
                f"collective scoped to group {gi} but the layout only has "
                f"{layout.num_groups} groups")
            continue
        if comm_op == "rs_opt_ag":
            _check_rs_opt_ag_group(reducer, gi, groups[gi], add)
            continue
        if comm_op == "rs_fwd_ag":
            _check_rs_fwd_ag_group(reducer, gi, groups[gi], add)
            continue
        if comm_op == "hier":
            hier_shards[gi] = _check_hier_group(reducer, gi, groups[gi], add)
            continue
        eqn = groups[gi][0]  # primary reduction (rs_ag/hier add gathers)
        aval = eqn.invars[0].aval
        want_elems = layout.group_sizes[gi]
        got_elems = _numel(aval)
        ok = sparsified or (
            got_elems >= want_elems if padded else got_elems == want_elems
        )
        if not ok:
            add("SCH007",
                f"group {gi} collective moves {got_elems} elements, layout "
                f"says {want_elems}")
        want_dtype = comm_dtype if comm_dtype is not None else (
            layout.dtypes[gi]
        )
        if np.dtype(aval.dtype) != np.dtype(want_dtype):
            add("SCH002",
                f"group {gi} collective runs at dtype "
                f"{np.dtype(aval.dtype).name}, layout bucket is "
                f"{np.dtype(want_dtype).name}")

    # the DCN-group scope is the hier lowering's alone: on any other path
    # a collective hiding under it is scope abuse (SCH009), exactly like
    # the clip-norm scope below — and on the hier path the full nested
    # contract applies (count/payload/dtype per DCN group)
    if comm_op == "hier":
        _check_hier_dcn(reducer, info, hier_shards, add)
    else:
        for di in sorted(info["dcn_groups"]):
            for eqn in info["dcn_groups"][di]:
                add("SCH009",
                    f"'{eqn.primitive.name}' under scope "
                    f"mgwfbp_dcngroup{di:04d} but comm_op is {comm_op!r} "
                    "(scope reserved for the hierarchical lowering)")
    for eqn in info["stray"]:
        add("SCH004",
            f"unexpected '{eqn.primitive.name}' outside declared scopes "
            f"(scope: {_scope_of(eqn) or '<none>'})")
    # the sharded_clip_norm scope is not a blanket whitelist: it exists
    # only for the sharded-update lowerings (rs_opt_ag / rs_fwd_ag), and
    # there its contract is exactly one psum of the shard squared norms —
    # and only when the spec clips
    clip_eqns = [
        e for e in info["allowed"]
        if "sharded_clip_norm" in _scope_segments(_scope_of(e))
    ]
    if comm_op not in ("rs_opt_ag", "rs_fwd_ag"):
        for eqn in clip_eqns:
            add("SCH004",
                f"'{eqn.primitive.name}' under scope sharded_clip_norm "
                f"but comm_op is {comm_op!r} (scope reserved for the "
                "sharded-update lowerings)")
    else:
        clips = getattr(reducer.optim.spec, "norm_clip", None) is not None
        for eqn in clip_eqns:
            if eqn.primitive.name != "psum":
                add("SCH004",
                    f"'{eqn.primitive.name}' under scope sharded_clip_norm "
                    "(only the clip-norm psum belongs there)")
        psums = [e for e in clip_eqns if e.primitive.name == "psum"]
        want = 1 if clips else 0
        if len(psums) != want:
            add("SCH004",
                f"sharded_clip_norm scope carries {len(psums)} psum(s); "
                f"the spec (norm_clip="
                f"{getattr(reducer.optim.spec, 'norm_clip', None)!r}) "
                f"calls for exactly {want}")
    for eqn in info["callbacks"]:
        add("SCH005",
            f"host callback '{eqn.primitive.name}' in the hot path "
            f"(scope: {_scope_of(eqn) or '<none>'})")

    if expect_donation:
        donated = find_donated(closed_jaxpr)
        if donated is None or not any(donated):
            add("SCH006",
                "no donated input buffers on the jitted step "
                "(params/opt-state copy every iteration)")

    if expect_finite_guard is not None:
        jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
        finite_eqns = [
            e for e in iter_eqns(jaxpr)
            if e.primitive.name == "is_finite"
            and "finite_check" in _scope_segments(_scope_of(e))
        ]
        if expect_finite_guard and not finite_eqns:
            add("SCH008",
                "step built with the non-finite-gradient guard but the "
                "traced program carries no finite_check-scoped is_finite "
                "reduction — the guard silently compiled away")
        if not expect_finite_guard and finite_eqns:
            add("SCH008",
                f"guard disabled but {len(finite_eqns)} finite_check-"
                "scoped is_finite eqn(s) remain in the hot path")
    return out


def collective_footprint(closed_jaxpr: Any) -> dict[str, int]:
    """Collective/callback SITES of a traced program per primitive name —
    the SCH010 comparison unit. jax 0.9 binds one psum eqn PER LEAF of a
    `lax.psum(tree)` call (XLA's all-reduce combiner fuses them again), so
    eqns are counted once per (primitive, name scope): a statistic added
    to the metrics dict rides the `metrics_reduce` site, while a
    collective or host callback anywhere else is a new site — exactly what
    the rule forbids."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    sites = {
        (eqn.primitive.name, _scope_of(eqn))
        for eqn in iter_eqns(jaxpr)
        if eqn.primitive.name in COLLECTIVE_PRIMS
        or eqn.primitive.name in CALLBACK_PRIMS
    }
    return dict(collections.Counter(name for name, _ in sites))


def compare_collective_footprints(
    base: Any,
    stats: Any,
    *,
    file: str = "<health-stats trace>",
) -> list[Finding]:
    """SCH010: the stats-on program's collective footprint must equal the
    stats-off program's, and neither may carry a host callback. `base`
    and `stats` are the two traced programs (`jax.make_jaxpr` output)."""
    out: list[Finding] = []

    def add(rule_id: str, msg: str) -> None:
        out.append(Finding(file, 0, rule_id, msg))

    fp_base = collective_footprint(base)
    fp_stats = collective_footprint(stats)
    for prim in sorted(set(fp_base) | set(fp_stats)):
        b, s = fp_base.get(prim, 0), fp_stats.get(prim, 0)
        if prim in CALLBACK_PRIMS:
            if s or b:
                add("SCH005",
                    f"host callback '{prim}' in the hot path "
                    f"(stats-off x{b}, stats-on x{s})")
            continue
        if s > b:
            add("SCH010",
                f"health statistics added {s - b} '{prim}' "
                f"collective(s) ({b} -> {s}) — the stats must ride the "
                "EXISTING metrics psum, not new collectives")
        elif s < b:
            add("SCH010",
                f"health statistics REMOVED {b - s} '{prim}' "
                f"collective(s) ({b} -> {s}) — the stats build no longer "
                "realizes the same schedule as the plain step")
    return out


def verify_health_stats_footprint(
    model_name: str = "lenet",
    policy: str = "mgwfbp",
    *,
    comm_op: str = "all_reduce",
) -> list[Finding]:
    """Trace one representative step with health statistics off and on
    and apply SCH010. The rs_fwd_ag lowering compares its two-step
    programs (the deferred gathers live across the boundary)."""
    kw: dict[str, Any] = dict(comm_op=comm_op)
    if comm_op in ("rs_opt_ag", "rs_fwd_ag"):
        kw["norm_clip"] = 1.0
    if comm_op == "rs_fwd_ag":
        kw["steps"] = 2
    base, _, _ = trace_train_step(model_name, policy, **kw)
    stats, _, _ = trace_train_step(
        model_name, policy, health_stats=True, **kw
    )
    return compare_collective_footprints(
        base, stats,
        file=f"<health-stats {model_name}/{policy}/{comm_op}>",
    )


# ---------------------------------------------------------------------------
# Self-contained verification target: build a representative train step and
# check it. Used by the CLI and by the analyzer's own clean-on-HEAD test.
# ---------------------------------------------------------------------------

def _ensure_cpu_devices(n: int = 8) -> None:
    """Pin an n-device virtual CPU platform (tracing needs a mesh, not real
    hardware). Once a backend is up this changes nothing and the trace
    uses whatever devices exist."""
    from mgwfbp_tpu.utils.platform import apply_platform_overrides

    apply_platform_overrides("cpu", host_device_count=n)


def trace_train_step(
    model_name: str = "lenet",
    policy: str = "mgwfbp",
    *,
    comm_op: str = "all_reduce",
    comm_dtype: Any = None,
    donate: bool = True,
    batch_size: int = 16,
    norm_clip: Optional[float] = None,
    grad_guard: bool = True,
    steps: int = 1,
    dcn_slices: Optional[int] = None,
    dcn_groups: Optional[Any] = None,
    health_stats: bool = False,
) -> tuple[Any, Any, list]:
    """Build and trace a representative jitted MG-WFBP train step.

    health_stats traces the ISSUE-12 training-health-statistics build —
    `verify_health_stats_footprint` compares it against the plain trace
    (rule SCH010: the stats may not change the collective footprint).

    Returns (closed_jaxpr, reducer, grad_leaves_in_arrival_order) — the
    exact inputs `verify_jaxpr_against_reducer` wants. Tracing only: state
    is built with `jax.eval_shape`, the batch is ShapeDtypeStructs, nothing
    executes on any device. Exposed separately from `verify_train_step` so
    the analyzer's mutation tests can verify a REAL traced program against
    a deliberately doctored expectation.

    comm_op='rs_opt_ag' traces the sharded-optimizer path (opt state as
    1/world shard buffers, params gathered post-update); norm_clip then
    additionally exercises the cross-group clip psum. comm_op='rs_fwd_ag'
    carries params as cross-step shards (`params_struct`).

    steps > 1 chains that many consecutive jitted step calls with the
    carried state threaded through — one top-level pjit eqn per call,
    which is what `verify_cross_step_jaxpr` splits on (steps=2 is the
    cross-step two-step contract's program).

    comm_op='hier' traces on an (ici, dcn)-shaped virtual mesh
    (`dcn_slices` outer slices; default 2) under a two-level cost model
    with a deliberately slow DCN link, so the nested-schedule machinery
    is exercised, not just the single-link fallback; `dcn_groups`
    optionally pins an explicit DCN partition (the mutation tests' hook).
    """
    _ensure_cpu_devices()
    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.optim import OptimSpec
    from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
    from mgwfbp_tpu.parallel.costmodel import AlphaBeta, TwoLevelAlphaBeta
    from mgwfbp_tpu.parallel.mesh import (
        DATA_AXIS,
        DCN_AXIS,
        MeshSpec,
        make_mesh,
    )
    from mgwfbp_tpu.train.step import create_train_state, make_train_step

    if comm_op == "hier" and not dcn_slices:
        dcn_slices = 2
    dcn = int(dcn_slices or 1)
    mesh = make_mesh(
        MeshSpec(data=len(jax.devices()) // dcn, seq=1, dcn=dcn)
    )
    axis_name: Any = (
        (DATA_AXIS, DCN_AXIS) if dcn > 1 else DATA_AXIS
    )
    model, meta = zoo.create_model(model_name)
    spec = OptimSpec(lr=0.1, kind="sgd", momentum=0.9, norm_clip=norm_clip)
    tx = spec.make_tx()
    # abstract state: full init math traced, nothing executed
    state = jax.eval_shape(
        lambda: create_train_state(
            jax.random.PRNGKey(0), model,
            jnp.zeros((1,) + meta.input_shape, meta.input_dtype), tx,
        )
    )
    full_params = state.params  # canonical tree (pre any sharded carry)
    kw: dict[str, Any] = {}
    if policy in ("mgwfbp", "auto"):
        if comm_op == "hier":
            # slow-DCN two-level prior: the nested solve must actually
            # price two links here, or the hier contract only ever sees
            # the degenerate one-DCN-collective-per-group shape
            kw = dict(cost_model=TwoLevelAlphaBeta(
                ici=AlphaBeta(1e-5, 2e-11),
                dcn=AlphaBeta(2.5e-3, 6e-10),
                ici_size=len(jax.devices()) // dcn,
                dcn_size=dcn,
            ))
        else:
            kw = dict(cost_model=AlphaBeta(1e-4, 1e-9))
    if comm_op in ("rs_opt_ag", "rs_fwd_ag"):
        kw.update(optim_spec=spec, world_size=len(jax.devices()))
    if dcn_groups is not None:
        kw.update(dcn_groups=dcn_groups)
    reducer = make_merged_allreduce(
        state.params, axis_name=axis_name, policy=policy,
        comm_dtype=comm_dtype, comm_op=comm_op, **kw,
    )
    if comm_op in ("rs_opt_ag", "rs_fwd_ag"):
        state = state.replace(
            opt_state=jax.eval_shape(reducer.optim.init)
        )
    if comm_op == "rs_fwd_ag":
        # params ride as the cross-step sharded carry
        state = state.replace(params=reducer.optim.params_struct())
    step = make_train_step(
        model, meta, tx, mesh, reducer, axis_name=axis_name,
        donate=donate, grad_guard=grad_guard, health_stats=health_stats,
    )
    batch = {
        "x": jax.ShapeDtypeStruct(
            (1, batch_size) + meta.input_shape, meta.input_dtype
        ),
        # a carry-free lm model predicts a token at every position
        "y": jax.ShapeDtypeStruct(
            (1, batch_size)
            + (meta.input_shape if meta.task == "lm" else ()), jnp.int32
        ),
    }
    if steps == 1:
        closed = jax.make_jaxpr(step)(state, batch)
    else:
        def chained(state, *batches):
            metrics = None
            for b in batches:
                state, metrics = step(state, b)
            return state, metrics

        closed = jax.make_jaxpr(chained)(state, *([batch] * steps))
    leaves = jax.tree_util.tree_leaves(full_params)
    arr = [leaves[j] for j in reducer.perm]
    return closed, reducer, arr


def step_subjaxprs(closed_jaxpr: Any) -> list:
    """Top-level pjit eqns of a multi-step trace, program order — one per
    jitted step call (the step boundary marker the cross-step verifier
    splits on; named scopes cannot mark it, because pjit caches the first
    call's trace and would stamp both steps with the first scope)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    return [e for e in jaxpr.eqns if e.primitive.name == "jit"]


def verify_cross_step_jaxpr(
    closed_jaxpr: Any,
    reducer: Any,
    grad_leaves: Sequence[Any],
    *,
    expect_donation: bool = True,
    expect_finite_guard: Optional[bool] = None,
    file: str = "<cross-step trace>",
) -> list[Finding]:
    """The TWO-STEP contract of the rs_fwd_ag lowering (ISSUE 7).

    closed_jaxpr must trace two CONSECUTIVE jitted steps with the carried
    state threaded through (`trace_cross_step`). Each step is verified
    against the reducer independently (SCH001/2/3/7 via the rs_fwd_ag
    group contract, SCH004 strays, SCH005 callbacks, SCH008 finite
    guard), which pins exactly the cross-step shape: within EVERY step,
    each group's all-gather sits in the forward region (before its
    reduce-scatter in program order) and consumes the carried shard the
    PREVIOUS step's reduce-scatter + update produced — the carry is the
    only dataflow path between the two pjit calls, so full per-step
    coverage + in-step ordering IS 'RS in step N, AG in step N+1's
    forward, no strays'. Donation is checked per step call (SCH006)."""
    out: list[Finding] = []
    steps = step_subjaxprs(closed_jaxpr)
    if len(steps) != 2:
        out.append(Finding(
            file, 0, "SCH001",
            f"cross-step trace carries {len(steps)} jitted step call(s); "
            "the two-step contract needs exactly 2",
        ))
        return out
    for si, eqn in enumerate(steps):
        sub = eqn.params.get("jaxpr")
        findings = verify_jaxpr_against_reducer(
            sub, reducer, grad_leaves,
            expect_donation=False,  # donation lives on the pjit eqn here
            expect_finite_guard=expect_finite_guard,
            file=f"{file}#step{si}",
        )
        out.extend(findings)
        if expect_donation:
            donated = eqn.params.get("donated_invars")
            if donated is None or not any(donated):
                out.append(Finding(
                    f"{file}#step{si}", 0, "SCH006",
                    "no donated input buffers on the jitted step "
                    "(params/opt-state copy every iteration)",
                ))
    return out


def trace_cross_step(
    model_name: str = "lenet",
    policy: str = "mgwfbp",
    *,
    comm_dtype: Any = None,
    donate: bool = True,
    batch_size: int = 16,
    norm_clip: Optional[float] = None,
    grad_guard: bool = True,
) -> tuple[Any, Any, list]:
    """Trace TWO consecutive jitted rs_fwd_ag train steps with the carried
    state threaded through — the two-step program `verify_cross_step_jaxpr`
    checks. Thin alias of `trace_train_step(..., comm_op='rs_fwd_ag',
    steps=2)` so the trace protocol has exactly one owner."""
    return trace_train_step(
        model_name, policy, comm_op="rs_fwd_ag", comm_dtype=comm_dtype,
        donate=donate, batch_size=batch_size, norm_clip=norm_clip,
        grad_guard=grad_guard, steps=2,
    )


def verify_cross_step_train_step(
    model_name: str = "lenet",
    policy: str = "mgwfbp",
    *,
    comm_dtype: Any = None,
    donate: bool = True,
    expect_donation: Optional[bool] = None,
    batch_size: int = 16,
    norm_clip: Optional[float] = None,
    grad_guard: bool = True,
    expect_finite_guard: Optional[bool] = None,
) -> list[Finding]:
    """Trace + verify the representative two-step rs_fwd_ag program."""
    closed, reducer, arr = trace_cross_step(
        model_name, policy, comm_dtype=comm_dtype, donate=donate,
        batch_size=batch_size, norm_clip=norm_clip, grad_guard=grad_guard,
    )
    return verify_cross_step_jaxpr(
        closed, reducer, arr,
        expect_donation=donate if expect_donation is None else expect_donation,
        expect_finite_guard=(
            grad_guard if expect_finite_guard is None else expect_finite_guard
        ),
        file=f"<cross-step {model_name}/{policy}/rs_fwd_ag>",
    )


def verify_train_step(
    model_name: str = "lenet",
    policy: str = "mgwfbp",
    *,
    comm_op: str = "all_reduce",
    comm_dtype: Any = None,
    donate: bool = True,
    expect_donation: Optional[bool] = None,
    batch_size: int = 16,
    norm_clip: Optional[float] = None,
    grad_guard: bool = True,
    expect_finite_guard: Optional[bool] = None,
    dcn_slices: Optional[int] = None,
) -> list[Finding]:
    """Trace one representative jitted train step and verify it (the
    finite guard is expected exactly as built unless overridden — the
    override exists for the analyzer's own mutation tests). The cross-step
    rs_fwd_ag lowering dispatches to the TWO-step trace: its contract
    spans a step boundary (RS in step N, AG in step N+1's forward). The
    hier lowering traces on an (ici, dcn) virtual mesh
    (`trace_train_step`'s dcn_slices default)."""
    if comm_op == "rs_fwd_ag":
        return verify_cross_step_train_step(
            model_name, policy, comm_dtype=comm_dtype, donate=donate,
            expect_donation=expect_donation, batch_size=batch_size,
            norm_clip=norm_clip, grad_guard=grad_guard,
            expect_finite_guard=expect_finite_guard,
        )
    closed, reducer, arr = trace_train_step(
        model_name, policy, comm_op=comm_op, comm_dtype=comm_dtype,
        donate=donate, batch_size=batch_size, norm_clip=norm_clip,
        grad_guard=grad_guard, dcn_slices=dcn_slices,
    )
    tag = f"{model_name}/{policy}" + (
        f"/{comm_op}" if comm_op != "all_reduce" else ""
    )
    return verify_jaxpr_against_reducer(
        closed, reducer, arr,
        expect_donation=donate if expect_donation is None else expect_donation,
        expect_finite_guard=(
            grad_guard if expect_finite_guard is None else expect_finite_guard
        ),
        file=f"<train step {tag}>",
    )
