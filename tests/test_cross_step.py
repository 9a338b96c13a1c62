"""Cross-step communication pipelining (comm_op='rs_fwd_ag', ISSUE 7).

The contract under test, end to end: each merge group's all-reduce splits
into a reduce-scatter issued at backward time (plus the fused shard
optimizer update) and an all-gather DEFERRED into the next step's forward
(DeAR, arXiv:2302.12445) — params ride between steps as per-group 1/world
shards. Covered here: the solver's two-phase timeline (AG deadline before
the first consuming layer), the jaxpr verifier's two-step contract (SCH
mutations), numerical parity with the in-step rs_opt_ag lowering,
checkpoint interchange with all_reduce runs, bitwise preempt/resume with
in-flight shards, the lenet convergence smoke, the autotune cross-step
race + cache round-trip, and the agree-interval / layer-profile
satellites.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.optim import OptimSpec
from mgwfbp_tpu.parallel import solver as S
from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
from mgwfbp_tpu.parallel.costmodel import AlphaBeta
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh
from mgwfbp_tpu.utils.faults import Preempted


WORLD = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(data=WORLD, seq=1))


def _cfg(dnn="lenet", **kw):
    base = dict(
        lr=0.01, max_epochs=2, logdir="", checkpoint_dir=None, seed=11,
        batch_size=8, num_batches_per_epoch=4, comm_op="rs_fwd_ag",
    )
    base.update(kw)
    return make_config(dnn, **base)


def _leaves_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# --------------------------------------------------------------------------
# Solver: the two-phase cross-step timeline
# --------------------------------------------------------------------------


def test_phase_costs_sum_to_effective_cost():
    cm = AlphaBeta(alpha=1e-4, beta=2e-9, update_beta=3e-10)
    rs, ag = S.cross_step_phase_costs(cm)
    eff = S.effective_cost_fn(cm, "rs_fwd_ag")
    for b in (1.0, 1e4, 1e7):
        assert rs(b) + ag(b) == pytest.approx(eff(b), rel=1e-12)


def test_forward_prior_is_half_backward():
    assert S.forward_prior_tf([0.2, 0.4]) == [0.1, 0.2]


def test_ag_deadline_before_first_consuming_layer():
    """Group G-1 holds the FIRST forward layers (arrival order is reverse
    forward), so its gather gates the forward's start: making that one
    gather slow must stall the whole step by the exposed difference, while
    the same cost on group 0 (consumed LAST in the forward) stays hidden
    behind the earlier layers' forward compute."""
    groups = [[0, 1], [2, 3]]
    nbytes = [100, 100, 100, 100]
    tb = [1.0, 1.0, 1.0, 1.0]
    tf = [1.0, 1.0, 1.0, 1.0]
    rs = lambda b: 0.0  # noqa: E731 — isolate the AG phase

    def ag_slow_first_fwd(b):
        # both groups have 200 bytes; charge a flat 3.0 (vs 2.0 of fwd
        # compute before group 0's first use) — the harness varies WHICH
        # group pays by reordering below
        return 3.0

    # slow AG on BOTH groups: group 1 (first forward) stalls the forward
    # start by 3.0; group 0's AG (queued behind, done at 6.0) must beat
    # the forward reaching ITS layers at 3.0 + 2.0 = 5.0 -> 1.0 stall
    total, nonoverlap, comm = S.simulate_cross_step(
        groups, nbytes, tb, tf, rs, ag_slow_first_fwd,
    )
    # forward timeline: g1 AG [0,3], its layers [3,5]; g0 AG [3,6], its
    # layers [6,8] -> fwd_end 8, stall 4 over tf_total 4; backward rides
    # stall + tb. total is backward-anchored: stall + tb_total
    assert total == pytest.approx(4.0 + 4.0)
    assert comm == pytest.approx(6.0)

    # cheap AGs: only the FIRST forward group's gather stays exposed (no
    # forward compute exists before the first layer to hide it behind);
    # group 0's AG [0.5, 1.0) disappears under g1's forward block
    total2, _, _ = S.simulate_cross_step(
        groups, nbytes, tb, tf, rs, lambda b: 0.5,
    )
    assert total2 == pytest.approx(4.0 + 0.5)


def test_serial_regime_sums_everything():
    """overlap=0 (the CPU-mesh regime): nothing hides — total is the
    backward-anchored serialized sum tb + all comm (both legs)."""
    groups = [[0], [1]]
    nbytes = [10, 10]
    tb = [1.0, 1.0]
    tf = [0.5, 0.5]
    rs = lambda b: 0.25  # noqa: E731
    ag = lambda b: 0.75  # noqa: E731
    total, nonoverlap, comm = S.simulate_cross_step(
        groups, nbytes, tb, tf, rs, ag, overlap=0.0,
    )
    assert comm == pytest.approx(2.0)
    assert total == pytest.approx(2.0 + 2.0)
    assert nonoverlap == pytest.approx(2.0)


def test_cross_step_beats_best_in_step_on_slow_link():
    """The win condition: on a comm-bound profile whose collective total
    exceeds what backward alone can hide, deferring each group's AG into
    the next forward hides the overflow — the solved rs_fwd_ag schedule's
    simulated (backward-anchored, comparable) step time beats EVERY
    in-step candidate under every interchangeable lowering."""
    cm = AlphaBeta(alpha=1e-4, beta=5e-9)  # slow interconnect
    specs = [S.LayerSpec(name=f"l{i}", size=200_000) for i in range(8)]
    tb = [2e-4] * 8
    tf = [1e-4] * 8
    sizes = [s.size for s in specs]
    nbytes = [s.nbytes for s in specs]
    best_in = None
    for op in ("all_reduce", "rs_ag"):
        cost = S.effective_cost_fn(cm, op)
        for _, groups in S.candidate_groupings(sizes, tb, cm.alpha, cost):
            t, _, _ = S.simulate_groups(groups, nbytes, tb, cost)
            best_in = t if best_in is None else min(best_in, t)
    sched = S.build_schedule(
        specs, tb, tf=tf, policy="auto", cost_model=cm, comm_op="rs_fwd_ag"
    )
    assert sched.predicted_total_time < best_in


def test_autotune_frontier_prices_cross_step_candidates():
    """build_candidates under a slow link must rank an rs_fwd_ag
    candidate ahead of every in-step one (comparable totals), so the
    race roster leads with the cross-step schedule."""
    from mgwfbp_tpu.parallel import autotune as at

    cm = AlphaBeta(alpha=1e-4, beta=5e-9)
    specs = [S.LayerSpec(name=f"l{i}", size=200_000) for i in range(8)]
    tb = [2e-4] * 8
    tf = [1e-4] * 8
    cands = at.build_candidates(
        specs, tb, cm, ("rs_fwd_ag", "all_reduce", "rs_ag"), tf=tf,
        max_candidates=6,
    )
    assert cands[0].comm_op == "rs_fwd_ag"
    assert any(c.comm_op != "rs_fwd_ag" for c in cands)


# --------------------------------------------------------------------------
# Lowering: numerical parity with the in-step sharded-optimizer path
# --------------------------------------------------------------------------


def _tree(rng):
    return {
        "dense1": {"kernel": jnp.asarray(rng.randn(8, 16), jnp.float32),
                   "bias": jnp.asarray(rng.randn(16), jnp.float32)},
        "dense2": {"kernel": jnp.asarray(rng.randn(16, 4), jnp.float32)},
    }


def test_rs_fwd_ag_matches_rs_opt_ag_bitwise(mesh):
    """Per step the cross-step lowering runs the SAME reduce-scatter,
    clip psum, and fused shard update as rs_opt_ag — only the gather's
    position moves. After k steps the carried shards must hold bitwise
    the params rs_opt_ag gathered in-step, and the in-step gather must
    return the PREVIOUS step's params (the one-step deferral)."""
    rng = np.random.RandomState(0)
    params = _tree(rng)
    spec = OptimSpec(lr=0.1, kind="sgd", momentum=0.9, norm_clip=1.0)
    m_opt = make_merged_allreduce(
        params, axis_name=DATA_AXIS, policy="wfbp", comm_op="rs_opt_ag",
        optim_spec=spec, world_size=WORLD,
    )
    m_fwd = make_merged_allreduce(
        params, axis_name=DATA_AXIS, policy="wfbp", comm_op="rs_fwd_ag",
        optim_spec=spec, world_size=WORLD,
    )

    def stack(x):
        return jnp.stack([x * (i + 1) * 0.01 for i in range(WORLD)])

    gs = jax.tree_util.tree_map(stack, params)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(), m_opt.optim.partition_spec()),
        out_specs=(P(), m_opt.optim.partition_spec()), check_vma=False,
    )
    def step_opt(g, p, o):
        local = jax.tree_util.tree_map(lambda x: x[0], g)
        return m_opt.reduce_and_update(local, p, o)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS), m_fwd.optim.params_partition_spec(),
                  m_fwd.optim.partition_spec()),
        out_specs=(P(), m_fwd.optim.params_partition_spec(),
                   m_fwd.optim.partition_spec()), check_vma=False,
    )
    def step_fwd(g, ps, o):
        local = jax.tree_util.tree_map(lambda x: x[0], g)
        full = m_fwd.gather_params(ps)  # previous step's deferred gather
        new_ps, new_o = m_fwd.reduce_and_defer(local, ps, o)
        return full, new_ps, new_o

    f_opt, f_fwd = jax.jit(step_opt), jax.jit(step_fwd)
    p_opt, o_opt = params, m_opt.optim.init()
    ps = m_fwd.optim.scatter_params(params)
    o_fwd = m_fwd.optim.init()
    prev_opt = params
    for _ in range(3):
        full, ps, o_fwd = f_fwd(gs, ps, o_fwd)
        # the in-step gather returns the params as of the step's START —
        # i.e. what the in-step path held BEFORE its update
        _leaves_equal(full, prev_opt)
        p_opt, o_opt = f_opt(gs, p_opt, o_opt)
        prev_opt = p_opt
    _leaves_equal(m_fwd.optim.gather_params(ps, params), p_opt)
    # opt state slots advanced identically
    _leaves_equal(o_fwd.slots, o_opt.slots)


def test_scatter_gather_params_roundtrip(mesh):
    rng = np.random.RandomState(3)
    params = _tree(rng)
    m = make_merged_allreduce(
        params, axis_name=DATA_AXIS, policy="single", comm_op="rs_fwd_ag",
        optim_spec=OptimSpec(lr=0.1), world_size=WORLD,
    )
    back = m.optim.gather_params(m.optim.scatter_params(params), params)
    _leaves_equal(back, params)


def test_constructor_and_call_contracts():
    rng = np.random.RandomState(4)
    params = _tree(rng)
    with pytest.raises(ValueError, match="requires optim_spec"):
        make_merged_allreduce(
            params, axis_name=DATA_AXIS, policy="wfbp", comm_op="rs_fwd_ag",
        )
    m = make_merged_allreduce(
        params, axis_name=DATA_AXIS, policy="wfbp", comm_op="rs_fwd_ag",
        optim_spec=OptimSpec(lr=0.1), world_size=WORLD,
    )
    with pytest.raises(ValueError, match="reduce_and_defer"):
        m(params)  # grads-only reduction is not this lowering's contract


# --------------------------------------------------------------------------
# Verifier: the two-step contract + SCH mutations
# --------------------------------------------------------------------------


def test_two_step_trace_verifies_clean():
    from mgwfbp_tpu.analysis.jaxpr_check import verify_cross_step_train_step

    assert verify_cross_step_train_step("lenet", "wfbp", norm_clip=1.0) == []


def test_single_step_trace_fails_two_step_contract():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        trace_train_step,
        verify_cross_step_jaxpr,
    )

    closed, reducer, arr = trace_train_step(
        "lenet", "wfbp", comm_op="rs_fwd_ag"
    )
    findings = verify_cross_step_jaxpr(closed, reducer, arr)
    assert any(
        f.rule_id == "SCH001" and "step call" in f.message for f in findings
    )


def test_in_step_shape_flagged_as_not_deferred():
    """The rs_opt_ag program order (RS then AG inside one step) presented
    as a cross-step schedule must trip the deferral check: the gather
    silently degenerating back in-step is exactly the regression SCH004
    exists to catch."""
    import dataclasses

    from mgwfbp_tpu.analysis.jaxpr_check import (
        trace_train_step,
        verify_jaxpr_against_reducer,
    )

    closed, reducer, arr = trace_train_step(
        "lenet", "wfbp", comm_op="rs_opt_ag"
    )
    doctored = dataclasses.replace(reducer, comm_op="rs_fwd_ag")
    findings = verify_jaxpr_against_reducer(closed, doctored, arr)
    assert any(
        f.rule_id == "SCH004" and "NOT deferred" in f.message
        for f in findings
    )


def test_two_step_guard_and_donation_mutations():
    from mgwfbp_tpu.analysis.jaxpr_check import verify_cross_step_train_step

    # SCH008 both directions, per step
    f = verify_cross_step_train_step(
        "lenet", "wfbp", grad_guard=False, expect_finite_guard=True,
    )
    assert sum(1 for x in f if x.rule_id == "SCH008") == 2
    f = verify_cross_step_train_step(
        "lenet", "wfbp", grad_guard=True, expect_finite_guard=False,
    )
    assert sum(1 for x in f if x.rule_id == "SCH008") == 2
    # SCH006: donation checked on each step's pjit eqn
    f = verify_cross_step_train_step(
        "lenet", "wfbp", donate=False, expect_donation=True,
    )
    assert sum(1 for x in f if x.rule_id == "SCH006") == 2


def test_two_step_wrong_layout_mutation():
    """A reducer promising a different grouping than the traced program
    issues must fail SCH001 (group count) in BOTH steps."""
    from mgwfbp_tpu.analysis.jaxpr_check import (
        trace_cross_step,
        verify_cross_step_jaxpr,
    )

    closed, _, arr = trace_cross_step("lenet", "wfbp")
    # re-solve the same layer set as ONE group: the trace has per-layer
    # groups, the doctored reducer promises a single merged one
    single = make_merged_allreduce(
        {"leaves": list(arr)}, axis_name=DATA_AXIS, policy="single",
        comm_op="rs_fwd_ag", optim_spec=OptimSpec(lr=0.1),
        world_size=WORLD, perm=list(range(len(arr))),
    )
    findings = verify_cross_step_jaxpr(closed, single, list(arr))
    assert any(f.rule_id == "SCH001" for f in findings)


# --------------------------------------------------------------------------
# Trainer: convergence, interchange, preempt/resume, autotune
# --------------------------------------------------------------------------


def test_lenet_rs_fwd_ag_trains_and_converges(tmp_path):
    """The staleness-convergence smoke: lenet on the CPU mesh with every
    group's gather one step deferred still learns (loss trend over
    repeated passes of the same synthetic set), and the LIVE jitted step
    passes the verifier's schedule contract."""
    from mgwfbp_tpu.analysis.rules import ERROR
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = _cfg(max_epochs=3, num_batches_per_epoch=6)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert t.reducer.comm_op == "rs_fwd_ag"
    # live single-step verification (the autotune race's gate)
    batch_iter = t._autotune_batches()
    findings = t._verify_live_step(next(batch_iter))
    assert [f for f in findings if f.severity == ERROR] == []
    losses = [t.train_epoch(e)["loss"] for e in range(3)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    ev = t.evaluate()
    assert ev["count"] > 0 and np.isfinite(ev["loss"])
    t.close()


def test_checkpoint_interchange_with_all_reduce(tmp_path):
    """rs_fwd_ag checkpoints store the canonical replicated form: an
    all_reduce run restores them bitwise, and vice versa."""
    from mgwfbp_tpu.train.trainer import Trainer

    ck = str(tmp_path / "ck")
    base = dict(checkpoint_dir=ck, max_epochs=2, num_batches_per_epoch=3)
    t = Trainer(_cfg(**base), synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.checkpointer.wait()
    p_ref = jax.tree_util.tree_map(np.asarray, t._eval_params())
    t.close()

    # the same checkpoint dir read by an all_reduce run (same tag fields)
    t2 = Trainer(
        _cfg(comm_op="all_reduce", **base),
        synthetic_data=True, profile_backward=False,
    )
    assert t2.start_epoch == 1
    _leaves_equal(p_ref, t2.state.params)
    m = t2.train_epoch(1)  # and it trains on from there
    assert np.isfinite(m["loss"])
    t2.close()

    # reverse direction: all_reduce checkpoint into an rs_fwd_ag run
    ck2 = str(tmp_path / "ck2")
    base2 = dict(checkpoint_dir=ck2, max_epochs=2, num_batches_per_epoch=3)
    ta = Trainer(
        _cfg(comm_op="all_reduce", **base2),
        synthetic_data=True, profile_backward=False,
    )
    ta.fit(1)
    ta.checkpointer.wait()
    pa = jax.tree_util.tree_map(np.asarray, ta.state.params)
    ta.close()
    tb = Trainer(_cfg(**base2), synthetic_data=True, profile_backward=False)
    assert tb.start_epoch == 1
    _leaves_equal(pa, tb._eval_params())
    tb.close()


def test_preempt_resume_bitwise_with_inflight_shards(tmp_path, monkeypatch):
    """A SIGTERM drain mid-epoch checkpoints the gathered canonical state
    while params/opt-state live as cross-step shards; the restart
    re-scatters and must replay to BITWISE the uninterrupted run's params
    — the in-flight deferred gathers add no hidden state a resume could
    lose (and a rollback/restore wholesale replaces the carried shards)."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    base = dict(max_epochs=1, num_batches_per_epoch=6, seed=5)
    t_a = Trainer(
        _cfg(logdir=str(tmp_path / "a"), **base),
        synthetic_data=True, profile_backward=False,
    )
    t_a.fit(1)
    p_a = jax.tree_util.tree_map(np.asarray, t_a._eval_params())
    t_a.close()

    cfg_b = _cfg(
        logdir=str(tmp_path / "b"),
        checkpoint_dir=str(tmp_path / "b_ckpt"),
        ckpt_every_steps=2, **base,
    )
    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "preempt@step=3")
    t_b = Trainer(cfg_b, synthetic_data=True, profile_backward=False)
    with pytest.raises(Preempted):
        t_b.fit(1)
    t_b.close()
    monkeypatch.delenv("MGWFBP_FAULT_PLAN")
    t_b2 = Trainer(cfg_b, synthetic_data=True, profile_backward=False)
    assert t_b2.iteration == 3 and t_b2.start_epoch == 0
    t_b2.fit(1)
    assert t_b2.iteration == t_a.iteration == 6
    _leaves_equal(p_a, t_b2._eval_params())
    # opt state interchange form identical too
    _leaves_equal(
        t_a._to_checkpoint_state(t_a.state).opt_state,
        t_b2._to_checkpoint_state(t_b2.state).opt_state,
    )
    t_b2.close()


def test_elastic_resize_rescatters_param_carry():
    """update_nworker on the cross-step path: the carry gathers to the
    canonical form under the OLD (world, schedule), the reducer re-solves
    for the new extent, and the carry re-scatters onto the new layout —
    params bitwise across the resize, and the run keeps training."""
    from mgwfbp_tpu.train.trainer import Trainer

    t = Trainer(_cfg(max_epochs=1), synthetic_data=True,
                profile_backward=False)
    before = jax.tree_util.tree_map(np.asarray, t._eval_params())
    t.update_nworker(4)
    assert t.reducer.comm_op == "rs_fwd_ag" and t.reducer.optim.world == 4
    _leaves_equal(before, t._eval_params())
    m = t.train_epoch(0)
    assert np.isfinite(m["loss"])
    t.close()


def test_nonfinite_guard_keeps_prestep_shards(monkeypatch):
    """A NaN batch on the cross-step path: the in-jit guard must keep the
    ENTIRE pre-step carry — param shards and opt-state shards bitwise
    unchanged (the 'discard in-flight stale shards' half of the rollback
    contract; a checkpoint restore replaces the carry wholesale, which
    the preempt test covers)."""
    from mgwfbp_tpu.train.trainer import Trainer, _poison_batch

    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    t = Trainer(_cfg(max_epochs=1), synthetic_data=True,
                profile_backward=False)
    batch_iter = t._autotune_batches()
    # one clean step so the compile is out of the way
    t.state = t._apply_train_step(t.state, next(batch_iter))
    p0 = jax.tree_util.tree_map(np.asarray, t.state.params)
    o0 = jax.tree_util.tree_map(np.asarray, t.state.opt_state)
    step0 = int(t.state.step)
    bad, poisoned = _poison_batch(next(batch_iter))
    assert poisoned
    state, metrics = t.train_step(t.state, bad)
    assert float(metrics["grads_nonfinite"]) > 0
    assert int(state.step) == step0  # the step never happened
    _leaves_equal(p0, state.params)
    _leaves_equal(o0, state.opt_state)
    t.state = state
    t.close()


def test_autotune_races_and_commits_cross_step(tmp_path):
    """--autotune under comm_op=rs_fwd_ag races cross-step candidates
    AGAINST the in-step lowerings on the live job, commits the measured
    argmin, and a second run cache-hits without re-racing."""
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = _cfg(
        autotune=True, autotune_steps=1, autotune_candidates=3,
        schedule_cache=str(tmp_path / "cache"), max_epochs=1,
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    rep = t.autotune()
    assert rep["source"] == "race"
    labels = [r["label"] for r in rep["race"]]
    assert any(l.startswith("rs_fwd_ag") for l in labels), labels
    assert any(not l.startswith("rs_fwd_ag") for l in labels), labels
    committed_op = rep["comm_op"]
    assert t.reducer.comm_op == committed_op
    t.close()

    t2 = Trainer(cfg, synthetic_data=True, profile_backward=False)
    rep2 = t2.autotune()
    assert rep2["source"] == "cache"
    assert t2.reducer.comm_op == committed_op
    # the committed schedule still drives real steps
    m = t2.train_epoch(0)
    assert np.isfinite(m["loss"])
    t2.close()


# --------------------------------------------------------------------------
# Telemetry: cross-step overlap attribution + deferred-AG render
# --------------------------------------------------------------------------


def test_cross_step_overlap_attribution_split():
    """AG legs hide behind FORWARD compute, RS legs behind backward; the
    totals stay per-group comm = rs + ag and efficiency honest."""
    from mgwfbp_tpu.telemetry.overlap import attribute_overlap_cross_step

    groups = [[0, 1], [2, 3]]
    tb = [1.0] * 4
    tf = [1.0] * 4
    # cheap AGs fully hidden behind forward; big RS on group 1 exposed
    rows, fwd_end = attribute_overlap_cross_step(
        groups, tb, tf, rs_s=[0.5, 6.0], ag_s=[0.5, 0.5],
        nbytes=[10, 10],
    )
    # group 1's AG gates the forward start by 0.5 -> the forward REGION
    # (the render's backward anchor) ends past the pure compute total
    assert fwd_end == pytest.approx(4.5)
    assert rows[0].comm_s == pytest.approx(1.0)
    assert rows[0].ag_s == pytest.approx(0.5)
    # group 0's AG runs [0.5, 1.0) inside the forward window -> hidden;
    # its RS becomes ready last (arrival max=1 -> ready at fwd_end+2)
    assert rows[0].hidden_s >= 0.5
    # group 1's 6.0 s RS cannot hide behind the remaining backward
    assert rows[1].exposed_s > 0.0
    total_comm = sum(r.comm_s for r in rows)
    assert total_comm == pytest.approx(0.5 + 6.0 + 0.5 + 0.5)


def test_chrome_trace_renders_deferred_ag_spans():
    from mgwfbp_tpu.telemetry.export import chrome_trace

    records = [
        {"event": "header", "schema_version": 2, "run": {}},
        {"event": "step", "step": 1, "epoch": 0, "start_s": 0.0,
         "dur_s": 1.0},
        {"event": "overlap", "step": 1, "epoch": 0, "step_s": 1.0,
         "tb_total_s": 0.4, "tf_total_s": 0.2, "fwd_end_s": 0.3,
         "comm_s": 0.2,
         "hidden_s": 0.15, "exposed_s": 0.05, "efficiency": 0.75,
         "attribution": "cost-model", "timeline_end_s": 0.7},
        {"event": "comm_group", "step": 1, "group": 0, "nbytes": 100,
         "comm_s": 0.2, "start_s": 0.5, "hidden_s": 0.15,
         "exposed_s": 0.05, "attribution": "cost-model",
         "ag_start_s": 0.0, "ag_s": 0.08},
    ]
    doc = chrome_trace(records)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = [e["name"] for e in spans]
    assert any("deferred AG" in n for n in names)
    assert "forward" in names  # the forward track renders for cross-step
    # the RS leg renders with the AG's share removed
    rs_spans = [e for e in spans if e["name"].endswith("RS")]
    assert rs_spans and rs_spans[0]["dur"] == pytest.approx(
        (0.2 - 0.08) * 1e6, rel=1e-6
    )
    # the backward anchors at the forward REGION's end (fwd_end_s, which
    # includes AG-deadline stalls), and the forward span covers the region
    fwd = next(e for e in spans if e["name"] == "forward")
    bwd = next(e for e in spans if e["name"] == "backward")
    assert fwd["dur"] == pytest.approx(0.3 * 1e6, rel=1e-6)
    assert bwd["ts"] == pytest.approx(fwd["ts"] + 0.3 * 1e6, rel=1e-6)


# --------------------------------------------------------------------------
# Satellites: agree-interval auto-tuning, layer-profile schema v2
# --------------------------------------------------------------------------


def test_derive_agree_interval_bounds():
    from mgwfbp_tpu.train.trainer import derive_agree_interval

    assert derive_agree_interval(1.0, grace_s=30.0) == 15
    assert derive_agree_interval(0.01, grace_s=30.0) == 1000  # clamp high
    assert derive_agree_interval(100.0, grace_s=30.0) == 1  # clamp low
    assert derive_agree_interval(0.0) == 1  # degenerate measurement


def test_agree_interval_auto_wiring(monkeypatch):
    """Unset MGWFBP_AGREE_INTERVAL -> the first measured step window
    derives the cadence (multi-host only) and broadcasts p0's choice;
    explicit values stay authoritative and skip the derivation."""
    from mgwfbp_tpu.train import trainer as tr

    monkeypatch.delenv("MGWFBP_AGREE_INTERVAL", raising=False)
    monkeypatch.setenv("MGWFBP_PREEMPT_GRACE_S", "10")
    t = tr.Trainer(
        _cfg(comm_op="all_reduce"),
        synthetic_data=True, profile_backward=False,
    )
    assert t._agree_interval_auto and t._agree_interval == 1
    seen = {}
    monkeypatch.setattr(tr.coord, "process_count", lambda: 2)
    monkeypatch.setattr(
        tr.coord, "broadcast_flag",
        lambda v: seen.setdefault("v", v) or v,
    )
    t._maybe_derive_agree_interval(0.5)  # 10 s grace / 2 / 0.5 s = 10
    assert t._agree_interval == 10 and seen["v"] == 10.0
    assert not t._agree_interval_auto  # one-shot
    t.close()

    # explicit value: authoritative, never derived
    monkeypatch.setenv("MGWFBP_AGREE_INTERVAL", "7")
    t2 = tr.Trainer(
        _cfg(comm_op="all_reduce"),
        synthetic_data=True, profile_backward=False,
    )
    assert t2._agree_interval == 7 and not t2._agree_interval_auto
    t2._maybe_derive_agree_interval(0.5)
    assert t2._agree_interval == 7
    t2.close()


def test_layer_profile_v1_migrates_with_warning(tmp_path, caplog):
    from mgwfbp_tpu.profiling import load_layer_profile

    p = tmp_path / "tb_profile.json"
    p.write_text(json.dumps({
        "tb_s": [0.1, 0.2], "arrival_names": ["a", "b"], "total_s": 0.3,
        "source": "trace",
    }))
    import logging

    with caplog.at_level(logging.WARNING, logger="mgwfbp.profiling"):
        d = load_layer_profile(str(p))
    assert d["tf_s"] == [0.0, 0.0] and d["tf_source"] == "absent"
    assert any("rs_fwd_ag disabled" in r.message for r in caplog.records)

    bad = tmp_path / "future.json"
    bad.write_text(json.dumps({"schema_version": 99, "tb_s": []}))
    with pytest.raises(ValueError, match="schema_version"):
        load_layer_profile(str(bad))


def test_trainer_persists_v2_layer_profile_with_forward(tmp_path):
    """A profiled rs_fwd_ag run writes tb_profile.json at schema v2 with
    BOTH timelines, and load_layer_profile round-trips it silently."""
    from mgwfbp_tpu.profiling import load_layer_profile
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = _cfg(logdir=str(tmp_path), max_epochs=1, num_batches_per_epoch=2)
    t = Trainer(cfg, synthetic_data=True, profile_backward=True)
    assert t._tf_cache is not None and len(t._tf_cache) > 0
    path = os.path.join(str(tmp_path), cfg.tag(), "tb_profile.json")
    d = load_layer_profile(path)
    assert d["schema_version"] == 2
    assert len(d["tf_s"]) == len(d["tb_s"]) and sum(d["tf_s"]) > 0
    # the solved schedule used the measured forward timeline
    assert t.reducer.comm_op == "rs_fwd_ag"
    t.close()
