"""Topology-aware hierarchical merge scheduling (ISSUE 11).

The contract under test, end to end: a multi-slice pod has TWO
interconnects (fast ICI inside a slice, slow DCN across), so a hier
schedule is a PAIR of nested partitions — the inner (ICI) grouping of
layers plus an outer (DCN) grouping of those groups, solved PER LINK
(`solver.auto_groups_two_level` / `simulate_groups_two_level`). Covered
here: the two-link timeline simulator, the per-link merge decision (DCN
coarser than ICI on a slow-DCN profile — the win condition's solver
half), the nested lowering's numerics (nesting is bitwise-neutral; hier
vs flat differs only by reduction order), the SCH009 verifier contract +
mutations, per-link cost exposure and refit, the two-level overlap
attribution, the `calibrate --two-level` CLI, the `/fleet/profile`
fan-out, and the PINNED live autotune race on the (ici=4, dcn=2) virtual
CPU mesh — hier candidate wins, commits, and round-trips the schedule
cache.
"""

import dataclasses
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.parallel import autotune as at
from mgwfbp_tpu.parallel import solver as S
from mgwfbp_tpu.parallel.allreduce import (
    dcn_group_scope_name,
    group_scope_name,
    make_merged_allreduce,
)
from mgwfbp_tpu.parallel.costmodel import (
    AlphaBeta,
    SampledCost,
    TwoLevelAlphaBeta,
    load_profile,
    refit_two_level_from_observations,
    save_profile,
)


# the synthetic slow-DCN two-pod profile of the win condition: high DCN
# startup (merging on DCN pays), non-trivial ICI per-byte cost (hiding
# the inner reduce-scatter behind backward pays) — the regime where the
# nested schedule strictly beats every flat single-link candidate
SLOW_DCN = TwoLevelAlphaBeta(
    ici=AlphaBeta(2e-5, 8e-9),
    dcn=AlphaBeta(2e-3, 2e-9),
    ici_size=4,
    dcn_size=2,
)


def _mesh42() -> Mesh:
    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    # (dcn, data): outer slices lead, like parallel.mesh.make_mesh
    return Mesh(devs, ("dcn", "data"))


def _tree(rng, sizes):
    return {
        f"layer{i:02d}": {"w": jnp.asarray(rng.randn(s), jnp.float32)}
        for i, s in enumerate(sizes)
    }


# ---------------------------------------------------------------------------
# solver: per-link cost functions + the two-link timeline
# ---------------------------------------------------------------------------


def test_two_level_leg_costs_sum_to_predict():
    rs, dcn, ag = S.two_level_leg_costs(SLOW_DCN)
    for b in (1.0, 1e4, 1e7):
        assert rs(b) + dcn(b) + ag(b) == pytest.approx(
            SLOW_DCN.predict(b), rel=1e-12
        )
    # the DCN leg moves only the 1/ici_size shard
    assert SLOW_DCN.dcn_shard_predict(4e6) == pytest.approx(
        SLOW_DCN.dcn.predict(1e6)
    )


def test_simulate_two_level_hand_timeline():
    """Hand-checkable two-link replay: 2 groups, one DCN group. ICI RS
    legs queue on one link against grad readiness, the DCN collective
    waits for the LAST member's RS, the AG legs queue after the RS phase
    gated on the DCN landing."""
    groups = [[0], [1]]
    dcn_groups = [[0, 1]]
    nbytes = [100, 100]
    tb = [1.0, 1.0]
    rs = lambda b: 0.5  # noqa: E731
    dcn = lambda b: 2.0  # noqa: E731
    ag = lambda b: 0.25  # noqa: E731
    total, nonoverlap, comm = S.simulate_groups_two_level(
        groups, dcn_groups, nbytes, tb, rs, dcn, ag
    )
    # RS0 [1,1.5], RS1 [2,2.5]; DCN [2.5,4.5]; AG0 [4.5,4.75], AG1
    # [4.75,5.0] -> ici link ends 5.0 > bwd_end 2.0
    assert comm == pytest.approx(0.5 * 2 + 2.0 + 0.25 * 2)
    assert total == pytest.approx(5.0)
    assert nonoverlap == pytest.approx(3.0)
    # serialized regime (overlap=0): everything sums
    t0, _, _ = S.simulate_groups_two_level(
        groups, dcn_groups, nbytes, tb, rs, dcn, ag, overlap=0.0
    )
    assert t0 == pytest.approx(2.0 + comm)
    # the DCN partition must cover every group exactly once
    with pytest.raises(ValueError, match="exactly once"):
        S.simulate_groups_two_level(
            groups, [[0]], nbytes, tb, rs, dcn, ag
        )


def test_dcn_partition_candidates_merge_on_slow_link_only():
    """The per-link merge decision in isolation: with a high DCN alpha the
    outer scan merges the inner groups' cross-slice reductions; with a
    cheap DCN it keeps them split (per-group)."""
    groups = [[0], [1], [2], [3]]
    nbytes = [40_000] * 4
    # arrival gaps: 0/1 close, a long compute stretch, then 2/3 close —
    # the scan on a HIGH-alpha DCN link merges within each close pair but
    # cannot merge across the long gap: a PARTIAL merge neither extreme
    # (per-group / single) produces
    tb = [1e-4, 1e-4, 1e-2, 1e-4]
    rs = lambda b: 1e-5  # noqa: E731 — fast ICI RS legs
    slow_dcn = lambda b: 2.5e-3 + 6e-10 * b  # noqa: E731
    cands = S.dcn_partition_candidates(
        groups, nbytes, tb, rs, slow_dcn, dcn_alpha=2.5e-3
    )
    details = dict((d, p) for d, p in cands)
    assert details["per-group"] == [[0], [1], [2], [3]]
    assert details["single"] == [[0, 1, 2, 3]]
    assert details["scan"] == [[0, 1], [2, 3]]
    # a cheap DCN link never merges: an extra collective costs ~nothing,
    # so the scan degenerates to per-group and dedups away
    fast_dcn = lambda b: 1e-9 + 1e-14 * b  # noqa: E731
    cands2 = S.dcn_partition_candidates(
        groups, nbytes, tb, rs, fast_dcn, dcn_alpha=1e-9
    )
    assert dict(cands2).get("scan", [[0], [1], [2], [3]]) == (
        [[0], [1], [2], [3]]
    )


def test_auto_groups_two_level_wins_and_nests():
    """The win condition's solver half: on the slow-DCN two-pod profile
    the solved nested schedule (a) keeps MORE inner groups than DCN
    groups — the merge decision made per link — and (b) beats the flat
    single-link solve in `simulate_groups_two_level`."""
    sizes = [50_000] * 16
    tb = [3e-4] * 16
    cm = TwoLevelAlphaBeta(
        ici=AlphaBeta(1e-5, 2e-11), dcn=AlphaBeta(2.5e-3, 6e-10),
        ici_size=4, dcn_size=2,
    )
    groups, dcn_part, detail = S.auto_groups_two_level(sizes, tb, cm)
    assert len(dcn_part) < len(groups), (groups, dcn_part, detail)
    rs, dcn_c, ag = S.two_level_leg_costs(cm)
    nbytes = [s * 4 for s in sizes]
    t_nested, _, _ = S.simulate_groups_two_level(
        groups, dcn_part, nbytes, tb, rs, dcn_c, ag
    )
    flat_groups, _ = S.auto_groups(
        sizes, tb, alpha=cm.alpha, cost=cm.predict
    )
    t_flat, _, _ = S.simulate_groups_two_level(
        flat_groups, S.singleton_dcn_groups(len(flat_groups)),
        nbytes, tb, rs, dcn_c, ag,
    )
    assert t_nested < t_flat
    # the frontier agrees with its own argmin and is ranked
    frontier = S.two_level_frontier(sizes, tb, cm, max_candidates=5)
    assert frontier[0][3] == min(f[3] for f in frontier)
    assert frontier[0][1] == groups and frontier[0][2] == dcn_part


def test_build_schedule_hier_nested_and_explicit():
    layers = [S.LayerSpec(f"l{i}", 50_000) for i in range(8)]
    tb = [3e-4] * 8
    cm = TwoLevelAlphaBeta(
        ici=AlphaBeta(1e-5, 2e-11), dcn=AlphaBeta(2.5e-3, 6e-10),
        ici_size=4, dcn_size=2,
    )
    s = S.build_schedule(layers, tb, policy="auto", cost_model=cm,
                         comm_op="hier")
    assert s.dcn_groups  # hier schedules always carry a partition
    assert np.isfinite(s.predicted_total_time)
    # explicit nested partition rides through (cache hits / candidates)
    s2 = S.build_schedule(
        layers, tb, policy="auto", cost_model=cm, comm_op="hier",
        groups=[[0, 1], [2, 3], [4, 5], [6, 7]],
        dcn_groups=[[0, 1], [2, 3]],
    )
    assert s2.dcn_groups == ((0, 1), (2, 3))
    # a flat lowering never carries one
    s3 = S.build_schedule(layers, tb, policy="auto", cost_model=cm)
    assert s3.dcn_groups == ()
    # coverage gaps are rejected at build time
    with pytest.raises(ValueError, match="exactly once"):
        S.build_schedule(
            layers, tb, policy="auto", cost_model=cm, comm_op="hier",
            groups=[[0, 1], [2, 3], [4, 5], [6, 7]],
            dcn_groups=[[0, 1]],
        )


def test_remap_and_align_dcn_groups():
    # refinement: old group 1 split into new groups 1+2
    old = [[0, 1], [2, 3, 4]]
    new = [[0, 1], [2], [3, 4]]
    assert S.remap_dcn_groups(old, new, [[0, 1]]) == [[0, 1, 2]]
    assert S.remap_dcn_groups(old, new, [[0], [1]]) == [[0], [1, 2]]
    # dtype boundaries split DCN groups (one concat buffer per collective)
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert S.align_dcn_groups([[0, 1, 2]], [f32, f32, f32]) == [[0, 1, 2]]
    assert S.align_dcn_groups([[0, 1, 2]], [f32, bf16, bf16]) == (
        [[0], [1, 2]]
    )


# ---------------------------------------------------------------------------
# lowering: nested hier numerics
# ---------------------------------------------------------------------------


def test_hier_nested_lowering_numerics():
    """Nesting is numerics-NEUTRAL: any DCN partition of the same inner
    groups is bitwise-identical (psum is elementwise — reducing
    concatenated shards together or apart cannot change a value). Against
    the flat both-axes pmean the hier family differs by exactly the
    two-stage reduction ORDER (inner sum then outer sum), i.e. ~1 ulp —
    the same property the pre-nesting hier lowering always had."""
    mesh = _mesh42()
    rng = np.random.RandomState(0)
    tree = _tree(rng, [840, 10, 10080, 84, 2400, 16])

    def run(red):
        f = jax.jit(shard_map(
            lambda t: red(t), mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,
        ))
        return jax.tree_util.tree_leaves(f(tree))

    mk = lambda dg: make_merged_allreduce(  # noqa: E731
        tree, axis_name=("data", "dcn"), policy="wfbp", comm_op="hier",
        dcn_groups=dg,
    )
    nested = run(mk([[0, 1, 2], [3, 4, 5]]))
    single = run(mk([[0, 1, 2, 3, 4, 5]]))
    per_group = run(mk(None))
    for a, b in zip(nested, per_group):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(nested, single):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = run(make_merged_allreduce(
        tree, axis_name=("data", "dcn"), policy="wfbp",
        comm_op="all_reduce",
    ))
    for a, b in zip(nested, flat):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        )


def test_hier_dcn_groups_align_at_dtype_boundaries():
    """A solved DCN group spanning bucket dtypes must split before
    lowering (one concatenated shard buffer needs one dtype) — and the
    split partition still reduces correctly."""
    mesh = _mesh42()
    rng = np.random.RandomState(1)
    tree = {
        "a": {"w": jnp.asarray(rng.randn(512), jnp.float32)},
        "b": {"w": jnp.asarray(rng.randn(256), jnp.bfloat16)},
        "c": {"w": jnp.asarray(rng.randn(128), jnp.float32)},
    }
    red = make_merged_allreduce(
        tree, axis_name=("data", "dcn"), policy="wfbp", comm_op="hier",
        dcn_groups=[[0, 1, 2]],
    )
    # the requested single DCN group split at every dtype boundary
    assert len(red.schedule.dcn_groups) >= 2
    # ... but a wire cast unifies the shards, so the same request keeps
    # its single DCN collective (no pointless extra cross-slice alpha)
    red_wire = make_merged_allreduce(
        tree, axis_name=("data", "dcn"), policy="wfbp", comm_op="hier",
        dcn_groups=[[0, 1, 2]], comm_dtype=jnp.bfloat16,
    )
    assert len(red_wire.schedule.dcn_groups) == 1
    dts = [red.layout.dtypes[gi] for d in red.schedule.dcn_groups
           for gi in d]
    for d in red.schedule.dcn_groups:
        assert len({red.layout.dtypes[gi] for gi in d}) == 1, dts
    f = jax.jit(shard_map(
        lambda t: red(t), mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False,
    ))
    out = f(tree)
    ref = jax.jit(shard_map(
        lambda t: jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, ("data", "dcn")), t
        ),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    ))(tree)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-2 if a.dtype == jnp.bfloat16 else 2e-5,
            atol=1e-6,
        )


# ---------------------------------------------------------------------------
# verifier: the SCH009 hier contract + mutations
# ---------------------------------------------------------------------------


def _trace_hier(dcn_groups=None, **kw):
    from mgwfbp_tpu.analysis.jaxpr_check import trace_train_step

    return trace_train_step(
        "lenet", "wfbp", comm_op="hier", dcn_groups=dcn_groups, **kw
    )


def test_hier_trace_verifies_clean_nested():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        verify_jaxpr_against_reducer,
        verify_train_step,
    )

    closed, red, arr = _trace_hier(
        dcn_groups=[[0, 1, 2, 3, 4], [5, 6, 7, 8, 9]]
    )
    assert red.schedule.dcn_groups == ((0, 1, 2, 3, 4), (5, 6, 7, 8, 9))
    assert not verify_jaxpr_against_reducer(
        closed, red, arr, expect_finite_guard=True
    )
    # the CLI sweep's shape: auto policy under the slow-DCN model
    assert not verify_train_step("lenet", "auto", comm_op="hier")


def test_hier_partition_mutations_fail_sch009():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        verify_jaxpr_against_reducer,
    )

    closed, red, arr = _trace_hier(
        dcn_groups=[[0, 1, 2, 3, 4], [5, 6, 7, 8, 9]]
    )
    # promised partition differs from the traced one -> count mismatch
    red2 = dataclasses.replace(red, schedule=dataclasses.replace(
        red.schedule, dcn_groups=tuple((i,) for i in range(10))
    ))
    f = verify_jaxpr_against_reducer(
        closed, red2, arr, expect_finite_guard=True
    )
    assert any(x.rule_id == "SCH009" for x in f), f
    # nested-partition coverage gap
    red3 = dataclasses.replace(red, schedule=dataclasses.replace(
        red.schedule, dcn_groups=((0, 1, 2, 3, 4),)
    ))
    f = verify_jaxpr_against_reducer(
        closed, red3, arr, expect_finite_guard=True
    )
    assert any(
        x.rule_id == "SCH009" and "exactly once" in x.message for x in f
    ), f


def test_dcn_scope_abuse_on_non_hier_path_fails_sch009():
    """A collective hiding under mgwfbp_dcngroupNNNN on a non-hier path
    is scope abuse: verify the hier TRACE against an all_reduce reducer
    (whose declared lowering never issues DCN-scoped collectives)."""
    from mgwfbp_tpu.analysis.jaxpr_check import (
        trace_train_step,
        verify_jaxpr_against_reducer,
    )

    closed, _, arr = _trace_hier()
    _, red_flat, _ = trace_train_step(
        "lenet", "wfbp", comm_op="all_reduce", dcn_slices=2
    )
    f = verify_jaxpr_against_reducer(
        closed, red_flat, arr, expect_finite_guard=True
    )
    assert any(
        x.rule_id == "SCH009" and "reserved" in x.message for x in f
    ), f


def _mutant_program(order="ag_first", stray_outer=False):
    """Handcraft a broken hier lowering for one 64-element group on the
    (4, 2) mesh: wrong leg order (AG before RS) or a stray outer-axis
    collective inside the inner-group scope."""
    from jax import lax

    mesh = _mesh42()
    tree = {"w": jnp.zeros((64,), jnp.float32)}
    red = make_merged_allreduce(
        tree, axis_name=("data", "dcn"), policy="single", comm_op="hier",
    )

    def bad(t):
        buf = t["w"].reshape(-1)
        with jax.named_scope(group_scope_name(0)):
            if stray_outer:
                buf = lax.psum(buf, "dcn")
                shard = lax.psum_scatter(
                    buf, ("data",), scatter_dimension=0, tiled=True
                )
                full = lax.all_gather(shard, ("data",), axis=0, tiled=True)
            elif order == "ag_first":
                fake_shard = buf[: buf.shape[0] // 4]
                full = lax.all_gather(
                    fake_shard, ("data",), axis=0, tiled=True
                )
                shard = lax.psum_scatter(
                    buf, ("data",), scatter_dimension=0, tiled=True
                )
            else:
                shard = lax.psum_scatter(
                    buf, ("data",), scatter_dimension=0, tiled=True
                )
                full = lax.all_gather(shard, ("data",), axis=0, tiled=True)
        with jax.named_scope(dcn_group_scope_name(0)):
            shard = lax.psum(shard, "dcn")
        return {"w": full / 8}

    closed = jax.make_jaxpr(shard_map(
        bad, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    ))(tree)
    return closed, red, [jax.ShapeDtypeStruct((64,), jnp.float32)]


def test_wrong_leg_order_fails_sch009():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        verify_jaxpr_against_reducer,
    )

    closed, red, arr = _mutant_program(order="ag_first")
    f = verify_jaxpr_against_reducer(
        closed, red, arr, expect_donation=False, expect_finite_guard=None
    )
    assert any(
        x.rule_id == "SCH009" and "order" in x.message for x in f
    ), f
    # the well-ordered twin of the same handcrafted program is clean of
    # the order finding (the mutation, not the harness, trips the rule)
    closed2, red2, arr2 = _mutant_program(order="rs_first")
    f2 = verify_jaxpr_against_reducer(
        closed2, red2, arr2, expect_donation=False,
        expect_finite_guard=None,
    )
    assert not any("order" in x.message for x in f2), f2


def test_stray_outer_collective_fails_sch009():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        verify_jaxpr_against_reducer,
    )

    closed, red, arr = _mutant_program(stray_outer=True)
    f = verify_jaxpr_against_reducer(
        closed, red, arr, expect_donation=False, expect_finite_guard=None
    )
    assert any(
        x.rule_id == "SCH009" and "cross-pod" in x.message.lower()
        or x.rule_id == "SCH009" and "OUTER" in x.message
        for x in f
    ), f


# ---------------------------------------------------------------------------
# cost model: per-link refit + sampled two-level persistence
# ---------------------------------------------------------------------------


def test_refit_two_level_per_link_and_common_scale():
    cm = SLOW_DCN
    # per-link observations: ici timed at 3x its model, dcn at 0.5x
    sizes = [1e5, 1e6, 4e6]
    ici_obs = [(b, 3.0 * cm.ici.predict(b)) for b in sizes]
    dcn_obs = [(b / 4, 0.5 * cm.dcn.predict(b / 4)) for b in sizes]
    refit = refit_two_level_from_observations(
        cm, [], ici_observations=ici_obs, dcn_observations=dcn_obs
    )
    assert isinstance(refit, TwoLevelAlphaBeta)
    assert refit.ici.beta == pytest.approx(3.0 * cm.ici.beta, rel=0.05)
    assert refit.dcn.beta == pytest.approx(0.5 * cm.dcn.beta, rel=0.05)
    # whole-collective observations rescale BOTH links by the common
    # drift factor (they cannot separate the wires)
    obs = [(b, 2.0 * cm.predict(b)) for b in sizes]
    scaled = refit_two_level_from_observations(cm, obs)
    assert scaled.ici.alpha == pytest.approx(2.0 * cm.ici.alpha, rel=0.05)
    assert scaled.dcn.alpha == pytest.approx(2.0 * cm.dcn.alpha, rel=0.05)
    for b in sizes:
        assert scaled.predict(b) == pytest.approx(
            2.0 * cm.predict(b), rel=0.05
        )
    with pytest.raises(ValueError, match="observations"):
        refit_two_level_from_observations(cm, [(1e5, 1.0)])
    # a SampledCost link stays a CURVE under the common-factor rescale
    # (collapsing to a line would discard the payload-dependent shape the
    # calibration persisted the curve for)
    curve = SampledCost(
        sizes_bytes=(1e4, 1e5, 1e6), times_s=(1e-4, 3e-4, 1e-3),
        ab=AlphaBeta(1e-4, 1e-9), ag_fraction=0.4,
    )
    cm2 = TwoLevelAlphaBeta(
        ici=curve, dcn=AlphaBeta(2e-3, 2e-9), ici_size=4, dcn_size=2
    )
    obs2 = [(b, 2.0 * cm2.predict(b)) for b in (1e4, 1e5, 1e6)]
    scaled2 = refit_two_level_from_observations(cm2, obs2)
    assert isinstance(scaled2.ici, SampledCost)
    assert scaled2.ici.ag_fraction == pytest.approx(0.4)
    for b in (3e4, 3e5):
        assert scaled2.ici.predict(b) == pytest.approx(
            2.0 * curve.predict(b), rel=0.05
        )


def test_two_level_profile_with_sampled_links_roundtrips(tmp_path):
    sc = SampledCost(
        sizes_bytes=(1e4, 1e5, 1e6),
        times_s=(1e-4, 3e-4, 1e-3),
        ab=AlphaBeta(1e-4, 1e-9),
        ag_fraction=0.4,
    )
    cm = TwoLevelAlphaBeta(
        ici=sc, dcn=AlphaBeta(2e-3, 2e-9), ici_size=4, dcn_size=2
    )
    p = str(tmp_path / "two_level_sampled.json")
    save_profile(p, cm)
    back = load_profile(p)
    assert isinstance(back, TwoLevelAlphaBeta)
    assert isinstance(back.ici, SampledCost)
    assert back.ici.ag_fraction == pytest.approx(0.4)
    for b in (5e4, 5e5):
        assert back.predict(b) == pytest.approx(cm.predict(b))


def test_calibrate_two_level_cli(tmp_path):
    from mgwfbp_tpu.calibrate import main as calibrate_main

    out = str(tmp_path / "tl.json")
    rc = calibrate_main([
        "--out", out, "--two-level", "--dcn", "2",
        "--min-log2", "12", "--max-log2", "14",
        "--iters", "2", "--warmup", "1",
    ])
    assert rc == 0
    m = load_profile(out)
    assert isinstance(m, TwoLevelAlphaBeta)
    assert m.ici_size == 4 and m.dcn_size == 2
    assert isinstance(m.ici, SampledCost)
    meta = json.load(open(out))["meta"]
    assert meta["mesh"] == {"ici": 4, "dcn": 2}
    # its own mode: no combining with the other calibration modes
    with pytest.raises(SystemExit):
        calibrate_main([
            "--out", out, "--two-level", "--world-sizes", "2,4",
        ])


# ---------------------------------------------------------------------------
# telemetry: per-link overlap attribution
# ---------------------------------------------------------------------------


def test_overlap_summarize_splits_hier_links():
    from mgwfbp_tpu.telemetry import overlap as ov

    # DCN-dominated profile: near-free ICI, expensive cross-slice hops —
    # the split must name the DCN link as the bottleneck
    cm = TwoLevelAlphaBeta(
        ici=AlphaBeta(1e-6, 1e-11), dcn=AlphaBeta(5e-3, 1e-8),
        ici_size=4, dcn_size=2,
    )
    tree = {f"l{i}": {"w": jnp.zeros((50_000,), jnp.float32)}
            for i in range(8)}
    red = make_merged_allreduce(
        tree, axis_name=("data", "dcn"), policy="auto", comm_op="hier",
        tb=[3e-4] * 8, cost_model=cm,
    )
    summ = ov.summarize(red, cm, [3e-4] * 8, step_s=5e-3)
    assert summ.dcn_s > 0.0 and summ.ici_s > 0.0
    assert summ.comm_s == pytest.approx(summ.ici_s + summ.dcn_s)
    # a merged DCN group is ONE collective: its cost is priced once on
    # the concatenated payload, never the per-member sum (which would
    # re-charge the DCN alpha the merge exists to amortize)
    _, dcn_c, _ = S.two_level_leg_costs(cm)
    group_b = [
        int(red.layout.group_sizes[gi])
        * np.dtype(red.layout.dtypes[gi]).itemsize
        for gi in range(red.layout.num_groups)
    ]
    want_dcn = sum(
        dcn_c(float(sum(group_b[gi] for gi in d)))
        for d in red.schedule.dcn_groups
    )
    assert summ.dcn_s == pytest.approx(want_dcn)
    # on the slow-DCN profile the bottleneck is, correctly, the DCN link
    assert summ.bottleneck_link == "dcn"
    fields = summ.to_event_fields()
    assert fields["bottleneck_link"] == "dcn"
    assert fields["dcn_s"] == pytest.approx(summ.dcn_s)
    rows = summ.group_event_fields(step=1)
    assert all("dcn_s" in r and "ici_s" in r for r in rows)
    total = sum(r["comm_s"] for r in rows)
    assert total == pytest.approx(summ.comm_s)


# ---------------------------------------------------------------------------
# autotune: hier candidates + the PINNED live race (win condition)
# ---------------------------------------------------------------------------


def test_cache_key_distinguishes_slice_shapes():
    base = at.cache_key("resnet50", 8, "hier", "float32")
    assert at.cache_key(
        "resnet50", 8, "hier", "float32", dcn_slices=2
    ) != base
    # the same world split differently is a different topology
    assert at.cache_key(
        "resnet50", 8, "hier", "float32", dcn_slices=2
    ) != at.cache_key("resnet50", 8, "hier", "float32", dcn_slices=4)
    # single-slice keys stay exactly as before
    assert at.cache_key(
        "resnet50", 8, "all_reduce", "float32", dcn_slices=1
    ) == at.cache_key("resnet50", 8, "all_reduce", "float32")


def test_allowed_comm_ops_multi_slice():
    assert at.allowed_comm_ops("hier") == ("hier",)
    assert at.allowed_comm_ops("hier", multi_slice=True) == (
        "hier", "all_reduce", "rs_ag",
    )
    assert at.allowed_comm_ops("all_reduce", multi_slice=True) == (
        "all_reduce", "rs_ag", "hier",
    )
    # single-slice stays exactly as before
    assert at.allowed_comm_ops("all_reduce") == ("all_reduce", "rs_ag")


def test_build_candidates_hier_nested_ranked_first():
    specs = [S.LayerSpec(f"l{i}", 50_000) for i in range(10)]
    tb = S.size_prior_tb(specs, SLOW_DCN)
    cands = at.build_candidates(
        specs, tb, SLOW_DCN,
        at.allowed_comm_ops("hier", multi_slice=True), max_candidates=6,
    )
    assert cands[0].comm_op == "hier"
    assert cands[0].dcn_groups  # nested partition rides along
    assert any(c.comm_op != "hier" for c in cands)
    # a flat cost model yields no hier candidates (nothing to price)
    flat_cands = at.build_candidates(
        specs, tb, AlphaBeta(1e-4, 1e-9),
        ("hier", "all_reduce"), max_candidates=6,
    )
    assert all(c.comm_op != "hier" for c in flat_cands)


def _slow_dcn_profile(tmp_path) -> str:
    path = str(tmp_path / "slow_dcn.json")
    save_profile(path, SLOW_DCN)
    return path


def _race_cfg(tmp_path, **kw):
    base = dict(
        lr=0.01, max_epochs=1, logdir="", checkpoint_dir=None, seed=3,
        batch_size=8, policy="auto", dcn_slices=2, comm_op="hier",
        comm_profile=_slow_dcn_profile(tmp_path),
        autotune=True, autotune_steps=1, autotune_candidates=4,
        schedule_cache=str(tmp_path / "cache"),
    )
    base.update(kw)
    return make_config("lenet", **base)


def test_pinned_hier_wins_live_race_commits_and_roundtrips(
    tmp_path, monkeypatch
):
    """THE pinned win condition (ISSUE 11 acceptance): on the synthetic
    slow-DCN two-pod profile over the (ici=4, dcn=2) virtual CPU mesh,
    the solved hier schedule beats flat in the simulator (asserted in
    test_auto_groups_two_level_wins_and_nests and re-asserted on the
    race's own predictions here) AND the hier candidate wins the live
    autotune race, commits, and round-trips the schedule cache.

    The race runs REAL carried training steps per candidate — build,
    verifier gate, hot-swap, compile, execute — but the STOPWATCH is the
    deterministic two-link simulator: on a shared-memory CPU mesh both
    'interconnects' are the same fabric, so wall-clock cannot express a
    slow DCN at all (the physics the profile describes does not exist
    here); the simulator under the injected profile is the only honest
    clock for it. Every other part of the loop — candidate construction,
    SCH-verification, swap/commit/cache machinery — is fully live."""
    from mgwfbp_tpu import profiling as prof_mod
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = _race_cfg(tmp_path)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert t.reducer.comm_op == "hier" and t.reducer.schedule.dcn_groups

    real_time_carried = prof_mod.time_carried_steps

    def simulated_clock(step_once, state, iters, warmup=1):
        # one real carried step keeps training/live-state honest; the
        # returned duration is the candidate's two-link simulated total
        # under the injected slow-DCN profile (already computed by
        # build_schedule for the LIVE reducer)
        state, _ = real_time_carried(step_once, state, 1, warmup=0)
        return state, float(t.reducer.schedule.predicted_total_time)

    monkeypatch.setattr(prof_mod, "time_carried_steps", simulated_clock)
    rep = t.autotune()
    assert rep["source"] == "race"
    raced = [e for e in rep["race"] if e["measured_step_s"] is not None]
    assert all(e["verified"] for e in raced)
    labels = [e["label"] for e in raced]
    # hier raced AGAINST the flat lowerings, and won
    assert any(not l.startswith("hier") for l in labels), labels
    assert rep["comm_op"] == "hier", labels
    assert rep["winner"].startswith("hier"), rep["winner"]
    # the winner is a genuinely NESTED schedule: fewer DCN collectives
    # than inner groups — the per-link merge decision, committed live
    assert rep["dcn_groups"], rep
    assert len(rep["dcn_groups"]) < len(rep["groups"]), rep
    # the solved hier schedule beat every flat candidate's prediction too
    hier_best = min(
        e["measured_step_s"] for e in raced if e["label"].startswith("hier")
    )
    flat_best = min(
        e["measured_step_s"] for e in raced
        if not e["label"].startswith("hier")
    )
    assert hier_best < flat_best
    # the live reducer realizes the committed nested schedule
    assert t.reducer.comm_op == "hier"
    assert [list(d) for d in t.reducer.schedule.dcn_groups] == (
        rep["dcn_groups"]
    )
    entry = at.load_cache_entry(rep["cache_path"])
    assert entry["dcn_groups"] == rep["dcn_groups"]
    # the drift detector's comm channel compares group-scope (ICI-only)
    # measurements against scope-COMPARABLE predictions: on hier those
    # must exclude the DCN leg, or a calibrated model alarms forever
    from mgwfbp_tpu.telemetry import group_comm_times

    full, _, _ = group_comm_times(t.reducer, t.cost_model)
    comparable = t._scope_comparable_predictions(t.cost_model)
    assert all(c < f for c, f in zip(comparable, full))
    t.close()

    # round trip: a fresh trainer cache-hits (no race) onto the same
    # nested schedule and still trains
    t2 = Trainer(cfg, synthetic_data=True, profile_backward=False)
    rep2 = t2.autotune()
    assert rep2["source"] == "cache"
    assert t2.reducer.comm_op == "hier"
    assert [list(d) for d in t2.reducer.schedule.dcn_groups] == (
        rep["dcn_groups"]
    )
    m = t2.train_epoch(0)
    assert np.isfinite(m["loss"])
    t2.close()


def test_hier_trainer_steps_match_all_reduce():
    """Numerical acceptance: hier steps vs all_reduce steps on the same
    (ici=4, dcn=2) mesh and seed. The hier family is bitwise-stable
    across DCN nestings (pinned in test_hier_nested_lowering_numerics);
    against the flat all_reduce program the reduction ORDER differs
    (inner-then-outer vs flat — IEEE non-associativity, ~1 ulp/step, a
    property the seed's hier lowering already had), so the cross-program
    comparison uses the repo's established cross-program tolerance."""
    from mgwfbp_tpu.train.trainer import Trainer

    params = {}
    for comm_op in ("hier", "all_reduce"):
        cfg = make_config(
            "lenet", lr=0.01, max_epochs=1, logdir="",
            checkpoint_dir=None, seed=7, batch_size=8,
            num_batches_per_epoch=3, policy="auto", dcn_slices=2,
            comm_op=comm_op,
        )
        tr = Trainer(cfg, synthetic_data=True, profile_backward=False)
        if comm_op == "hier":
            assert tr.reducer.schedule.dcn_groups
        tr.train_epoch(0)
        params[comm_op] = jax.tree_util.tree_leaves(tr.state.params)
        tr.close()
    for a, b in zip(params["hier"], params["all_reduce"]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        )


# ---------------------------------------------------------------------------
# fleet: /fleet/profile fan-out
# ---------------------------------------------------------------------------


def test_fleet_profile_fans_out_to_children():
    from mgwfbp_tpu.telemetry.fleet import FleetServer
    from mgwfbp_tpu.telemetry.serve import (
        MetricsAggregator,
        TelemetryServer,
    )

    aggs = [MetricsAggregator(run={"model": "lenet"}) for _ in range(2)]
    for i, a in enumerate(aggs):
        a.observe("step", {"step": 1, "epoch": 0, "start_s": 0.0,
                           "dur_s": 0.1})
    aggs[0].enable_profile()  # a live trainer attached on child 0 only
    servers = [TelemetryServer(a, 0, host="127.0.0.1") for a in aggs]
    fleet = FleetServer(
        lambda: {
            i: ("127.0.0.1", s.port) for i, s in enumerate(servers)
        },
        port=0,
    )

    def get(path):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{fleet.port}{path}", timeout=5
        ) as r:
            return json.loads(r.read().decode())

    def get_raw(path):
        import urllib.error

        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{fleet.port}{path}", timeout=5
            ) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    try:
        # garbage (or query-smuggling) steps die at the fan-in with 400,
        # never fan out to the children
        assert get_raw("/fleet/profile?steps=abc") == 400
        assert get_raw("/fleet/profile?steps=5%26debug%3D1") == 400
        # one call arms every child; per-child outcome reported
        doc = get("/fleet/profile?steps=3")
        assert doc["armed"] == 1
        assert doc["processes"]["0"]["armed"] is True
        assert doc["processes"]["1"]["armed"] is False  # no live trainer
        assert aggs[0].take_profile_request() == 3  # the arm reached it
        # window table: /fleet/profile without a query + /fleet/status
        aggs[0].set_profile_result({"steps": 3, "attribution": "trace"})
        doc = get("/fleet/profile")
        rows = {r["process"]: r for r in doc["profile_windows"]}
        assert rows[0]["state"] == "done"
        assert rows[0]["result"]["attribution"] == "trace"
        assert rows[1]["state"] == "idle" and not rows[1]["supported"]
        status = get("/fleet/status")
        assert {r["process"] for r in status["profile_windows"]} == {0, 1}
    finally:
        fleet.close()
        for s in servers:
            s.close()
