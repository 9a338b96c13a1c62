"""Resilience-layer tests (ISSUE 5) on the 8-device CPU mesh: the fault
plan grammar, the non-finite-gradient guard (skip + rollback), graceful
preemption with bitwise-exact mid-epoch resume, watchdog escalation
(all-thread stack dump before abort), the structured checkpoint-drift
error, and telemetry stream rotation."""

import json
import os
import signal
import subprocess
import sys

import jax
import numpy as np
import pytest

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.telemetry import EventWriter, events_of, read_event_set, \
    read_events
from mgwfbp_tpu.utils.faults import FaultPlan, Preempted, parse_plan


def _cfg(dnn="lenet", **kw):
    base = dict(
        lr=0.01, max_epochs=2, logdir="", checkpoint_dir=None, seed=11,
        batch_size=8, num_batches_per_epoch=6,
    )
    base.update(kw)
    return make_config(dnn, **base)


# --------------------------------------------------------------------------
# Fault-plan grammar
# --------------------------------------------------------------------------


def test_fault_plan_parses_and_queries():
    plan = parse_plan(
        "nan@step=3,count=2; stall@secs=0.5,phase=eval ;"
        "preempt@step=6,signal=SIGINT;kill@step=9"
    )
    assert plan and len(plan.specs) == 4
    assert not plan.nan_at(2)
    assert plan.nan_at(3) and plan.nan_at(4)
    # one-shot per step: a rolled-back REPLAY of step 3 sees clean data
    assert not plan.nan_at(3)
    assert plan.stall_secs("train") == 0.0
    assert plan.stall_secs("eval") == 0.5
    assert plan.stall_secs("eval") == 0.0  # consumed
    assert plan.preempt_signal_after(5) is None
    assert plan.preempt_signal_after(7) == signal.SIGINT  # >= step fires
    assert plan.preempt_signal_after(8) is None  # consumed
    assert not plan.kill_after(8)
    assert plan.kill_after(9)


def test_preempt_spec_consumed_by_resumed_counter():
    """A restarted run (supervisor re-runs the same command, same
    MGWFBP_FAULT_PLAN, on rc 75) resumes with its counter already past
    the planned step: the spec is consumed silently, NOT re-delivered —
    otherwise every restart preempts again and the job never finishes."""
    plan = parse_plan("preempt@step=6")
    assert plan.preempt_signal_after(24) is None  # resumed past 6
    assert plan.preempt_signal_after(25) is None  # stays consumed


def test_fault_plan_rejects_malformed():
    for bad in (
        "explode@step=1",          # unknown kind
        "nan@when=3",              # unknown key
        "nan",                     # missing required step
        "stall@phase=train",       # missing required secs
        "nan@step=three",          # non-numeric
        "preempt@step=1,signal=SIGKILL",  # not drainable
        "nan@step=1,count=0",      # empty range
        "stall@secs=1,phase=evaluation",  # phase the trainer never queries
    ):
        with pytest.raises(ValueError):
            parse_plan(bad)


def test_step_constrained_stall_needs_a_reported_step():
    """stall@...,step=N must fire ONLY at step N — never 'on the first
    call' when the caller reports no step (that would move the wedge)."""
    plan = parse_plan("stall@secs=1.0,phase=eval,step=500")
    assert plan.stall_secs("eval") == 0.0  # caller can't name a step
    assert plan.stall_secs("eval", 3) == 0.0  # wrong step
    assert plan.stall_secs("eval", 500) == 1.0  # the named step
    assert plan.stall_secs("eval", 500) == 0.0  # consumed


def test_fault_plan_from_env(monkeypatch):
    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    assert not FaultPlan.from_env()
    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "nan@step=2")
    assert FaultPlan.from_env().nan_at(2)


# --------------------------------------------------------------------------
# Non-finite guard: skip-step policy, bad_step events, rollback
# --------------------------------------------------------------------------


def test_nan_step_is_skipped_and_training_recovers(tmp_path, monkeypatch):
    """A NaN-injected step must leave params/opt-state/step-counter
    untouched (the in-jit skip), emit a bad_step event, and training must
    keep converging afterwards."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "nan@step=3")
    cfg = _cfg(logdir=str(tmp_path), telemetry=True)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    m = t.train_epoch(0)
    assert np.isfinite(m["loss"])  # the last (clean) step's metrics
    assert "grads_nonfinite" not in m  # plumbing stays out of metrics
    # 6 loader steps, one dropped: the device step counter advanced 5x
    assert int(t.state.step) == 5
    assert t.iteration == 6  # host position still covers the whole epoch
    assert all(
        np.all(np.isfinite(np.asarray(l)))
        for l in jax.tree_util.tree_leaves(t.state.params)
    )
    path = os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl")
    recs = read_events(path)
    (bad,) = events_of(recs, "bad_step")
    assert bad["step"] == 3 and bad["nonfinite"] > 0
    t.close()


def test_consecutive_bad_steps_roll_back_to_checkpoint(
    tmp_path, monkeypatch
):
    """NaN-inject -> skip -> rollback: after bad_step_limit consecutive
    non-finite steps the trainer restores the last step checkpoint and
    finishes the epoch from its exact position."""
    from mgwfbp_tpu.train.trainer import Trainer

    # the stall gives the async writer time to finish step 4's save before
    # step 5's `tail` polls for it: the loop no longer waits a device step
    # out in every health drain (ISSUE 25), and a save still in flight at
    # the rollback is abandoned for the one before it
    monkeypatch.setenv(
        "MGWFBP_FAULT_PLAN", "nan@step=4,count=2;stall@secs=1,step=5")
    cfg = _cfg(
        logdir=str(tmp_path), telemetry=True,
        checkpoint_dir=str(tmp_path / "ckpt"),
        ckpt_every_steps=2, bad_step_limit=2,
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    m = t.fit(1)
    assert np.isfinite(m["train"]["loss"])
    path = os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl")
    recs = read_events(path)
    assert len(events_of(recs, "bad_step")) == 2
    (rb,) = events_of(recs, "rollback")
    assert rb["bad_steps"] == 2
    # rolled back to the step checkpoint written before the fault window
    assert rb["restored_iteration"] == 4
    # a rollback inside one uninterrupted process is NOT a restart: the
    # `rollback` row above is the whole story, no `resume` row rides along
    assert not events_of(recs, "resume")
    # the epoch completed after the rollback replay
    steps = events_of(recs, "step")
    assert max(s["step"] for s in steps) == 6
    t.close()


def test_persistent_nans_abort_instead_of_rollback_livelock(
    tmp_path, monkeypatch
):
    """Two one-shot nan specs at the SAME step model a persistent NaN
    source: the replay after the first rollback goes bad again at the
    same position, and the trainer must ABORT with a diagnosis instead of
    rolling back forever."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "nan@step=5;nan@step=5")
    cfg = _cfg(
        logdir=str(tmp_path), checkpoint_dir=str(tmp_path / "ckpt"),
        ckpt_every_steps=2, bad_step_limit=1,
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    with pytest.raises(RuntimeError, match="persistent non-finite"):
        t.fit(1)
    t.close()


def test_ckpt_gc_keeps_epoch_boundaries_despite_step_bursts(tmp_path):
    """Class-aware retention: mid-epoch step saves must NOT evict the
    epoch-boundary history that evaluate --all-epochs reads."""
    import jax.numpy as jnp
    import optax

    from mgwfbp_tpu.checkpoint import Checkpointer, Snapshot
    from mgwfbp_tpu.train.step import TrainState

    params = {"w": jnp.arange(4, dtype=jnp.float32)}
    tx = optax.sgd(0.1)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
    )
    ck = Checkpointer(str(tmp_path), max_to_keep=2)
    it = 0
    for epoch in range(3):
        for s in range(1, 4):  # 3 mid-epoch saves per epoch
            it += 1
            ck.save(Snapshot(state=state, epoch=epoch, iteration=it,
                             epoch_step=s, mid_epoch=True))
        ck.save(Snapshot(state=state, epoch=epoch, iteration=it))
    ck.wait()
    # the newest 2 BOUNDARIES survived the 9 interleaved step saves...
    assert ck.all_epochs() == [1, 2]
    # ...and at most 2 mid-epoch snapshots are retained alongside them
    # (the last step save of each epoch is PROMOTED to its boundary)
    mids = [
        s for s in ck._mgr.all_steps()
        if ck._index[str(s)].get("mid_epoch")
    ]
    assert 1 <= len(mids) <= 2
    assert ck.restore(state, epoch=1) is not None
    ck.close()


def test_boundary_save_onto_step_checkpoint_promotes_entry(
    tmp_path, monkeypatch
):
    """--ckpt-every-steps dividing the epoch length: the epoch-boundary
    save dedups onto the just-written step checkpoint. The promoted entry
    must resume as a BOUNDARY (next epoch, no skip) and must keep
    describing the payload's carry for stateful models."""
    import jax.numpy as jnp

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.models import ModelMeta
    from mgwfbp_tpu.models.lstm import PTBLSTM
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    # plain model: boundary promotion resumes at the next epoch
    cfg = _cfg(logdir=str(tmp_path / "a"),
               checkpoint_dir=str(tmp_path / "a_ckpt"),
               ckpt_every_steps=3, num_batches_per_epoch=6)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.checkpointer.wait()
    t.close()
    t2 = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert t2.start_epoch == 1 and t2._resume_epoch is None
    t2.close()

    # carry model: the promoted entry still restores the carry payload
    def tiny_lstm(nc):
        nc = nc or 10000
        return (
            PTBLSTM(vocab_size=nc, hidden_size=16, num_layers=1, dropout=0.0),
            ModelMeta(name="lstm", dataset="ptb", num_classes=nc,
                      input_shape=(35,), input_dtype=jnp.int32, task="lm",
                      has_carry=True),
        )

    monkeypatch.setitem(zoo._REGISTRY, "lstm", tiny_lstm)
    cfg_l = _cfg("lstm", logdir=str(tmp_path / "b"),
                 checkpoint_dir=str(tmp_path / "b_ckpt"),
                 batch_size=1, max_epochs=1,
                 ckpt_every_steps=2, num_batches_per_epoch=4)
    tl = Trainer(cfg_l, synthetic_data=True, profile_backward=False)
    tl.fit(1)
    tl.checkpointer.wait()
    tl.close()
    # a fresh trainer must restore cleanly (no spurious drift error from
    # the carry payload) and start the next epoch
    tl2 = Trainer(cfg_l, synthetic_data=True, profile_backward=False)
    assert tl2.start_epoch == 1 and tl2._resume_epoch is None
    tl2.close()


def test_lost_sidecar_index_does_not_misread_new_format(tmp_path):
    """Kill window between the orbax commit and the index write: an
    UNINDEXED new-format step must be probed (not misread as a legacy
    epoch-keyed save, which would turn a mid-epoch snapshot into an epoch
    boundary), and the index healed."""
    import jax.numpy as jnp
    import optax

    from mgwfbp_tpu.checkpoint import Checkpointer, Snapshot
    from mgwfbp_tpu.train.step import TrainState

    params = {"w": jnp.arange(4, dtype=jnp.float32)}
    tx = optax.sgd(0.1)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
    )
    ck = Checkpointer(str(tmp_path))
    ck.save(Snapshot(state=state, epoch=2, iteration=17, epoch_step=5,
                     mid_epoch=True), wait=True)
    ck.close()
    os.remove(os.path.join(str(tmp_path), "steps_index.json"))  # the kill
    ck2 = Checkpointer(str(tmp_path))
    snap = ck2.restore(state)
    assert snap is not None
    assert snap.mid_epoch and snap.epoch == 2 and snap.epoch_step == 5
    # the sidecar was healed from the payload's own bookkeeping
    assert ck2._index["17"]["mid_epoch"] is True
    ck2.close()


def test_lost_sidecar_index_still_reads_a_legacy_payload(tmp_path):
    """The other side of the probe: an unindexed step whose stored meta
    has no `epoch_step` IS the legacy epoch-keyed format (the orbax step
    is the epoch) and restores as an epoch boundary."""
    import jax.numpy as jnp
    import optax
    import orbax.checkpoint as ocp

    from mgwfbp_tpu.checkpoint import Checkpointer
    from mgwfbp_tpu.train.step import TrainState

    params = {"w": jnp.arange(4, dtype=jnp.float32)}
    tx = optax.sgd(0.1)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), rng=jax.random.PRNGKey(0),
    )
    ck = Checkpointer(str(tmp_path))
    ck._mgr.save(3, args=ocp.args.StandardSave(
        {"state": state, "meta": {"epoch": 3, "iteration": 42}}
    ))
    ck._mgr.wait_until_finished()
    ck.close()
    ck2 = Checkpointer(str(tmp_path))
    assert ck2._probe_format(3) is None
    snap = ck2.restore(state, step=3)
    assert snap is not None
    assert (snap.epoch, snap.iteration, snap.mid_epoch) == (3, 42, False)
    ck2.close()


def test_guard_check_interval_batches_reads(tmp_path, monkeypatch):
    """MGWFBP_GUARD_CHECK_INTERVAL=N defers flag reads (one stacked pull
    per N steps); detection still catches the injected NaN by epoch end."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "nan@step=3")
    monkeypatch.setenv("MGWFBP_GUARD_CHECK_INTERVAL", "100")
    cfg = _cfg(logdir=str(tmp_path), telemetry=True)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert t._guard_interval == 100
    t.train_epoch(0)  # all flags drain (one stacked pull) at epoch end
    recs = read_events(
        os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl")
    )
    (bad,) = events_of(recs, "bad_step")
    assert bad["step"] == 3
    t.close()


def test_bad_steps_without_checkpointer_keep_skipping(tmp_path, monkeypatch):
    """No --checkpoint-dir: rollback is impossible — the guard must keep
    dropping updates (params stay finite) instead of crashing."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "nan@step=2,count=3")
    cfg = _cfg(logdir=str(tmp_path), telemetry=True, bad_step_limit=2)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    m = t.train_epoch(0)
    assert np.isfinite(m["loss"])
    assert int(t.state.step) == 3  # 6 steps, 3 dropped
    recs = read_events(
        os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl")
    )
    assert len(events_of(recs, "bad_step")) == 3
    assert not events_of(recs, "rollback")
    t.close()


def test_grad_guard_zero_sync(tmp_path, monkeypatch):
    """The guard must add ZERO device syncs to the step loop: identical
    jax.device_get / jax.block_until_ready counts with the guard on and
    off (the PR-4 zero-sync pattern, pinned for ISSUE 5)."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "1000")
    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)

    def run(guard: bool) -> int:
        cfg = _cfg(
            seed=5, grad_guard=guard,
            logdir=str(tmp_path / ("on" if guard else "off")),
        )
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        counts = {"n": 0}
        real_bur = jax.block_until_ready
        real_get = jax.device_get

        def counting_bur(*a, **k):
            counts["n"] += 1
            return real_bur(*a, **k)

        def counting_get(*a, **k):
            counts["n"] += 1
            return real_get(*a, **k)

        with monkeypatch.context() as m:
            m.setattr(jax, "block_until_ready", counting_bur)
            m.setattr(jax, "device_get", counting_get)
            t.train_epoch(0)
        t.close()
        return counts["n"]

    assert run(guard=True) == run(guard=False)


def test_verifier_pins_finite_guard_both_ways():
    """SCH008: a guard-enabled step must carry the finite_check reduction;
    a guard-disabled step must not (and each passes as itself)."""
    from mgwfbp_tpu.analysis.jaxpr_check import verify_train_step

    assert verify_train_step("lenet", "wfbp", grad_guard=True) == []
    assert verify_train_step("lenet", "wfbp", grad_guard=False) == []
    mutated = verify_train_step(
        "lenet", "wfbp", grad_guard=False, expect_finite_guard=True
    )
    assert [f.rule_id for f in mutated] == ["SCH008"]
    mutated = verify_train_step(
        "lenet", "wfbp", grad_guard=True, expect_finite_guard=False
    )
    assert [f.rule_id for f in mutated] == ["SCH008"]


# --------------------------------------------------------------------------
# Preemption: graceful drain + bitwise-exact mid-epoch resume
# --------------------------------------------------------------------------


def test_preempt_resume_bitwise_equals_uninterrupted(tmp_path, monkeypatch):
    """The acceptance path: a run killed by SIGTERM mid-epoch and
    restarted resumes from the step checkpoint and produces BITWISE
    identical params to an uninterrupted run at the same step."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    # uninterrupted reference
    cfg_a = _cfg(logdir=str(tmp_path / "a"))
    t_a = Trainer(cfg_a, synthetic_data=True, profile_backward=False)
    t_a.fit(1)
    t_a.close()

    # interrupted run: the fault plan delivers a REAL SIGTERM to the
    # armed handler after step 3; the drain checkpoints and raises
    cfg_b = _cfg(
        logdir=str(tmp_path / "b"),
        checkpoint_dir=str(tmp_path / "b_ckpt"),
        ckpt_every_steps=2, telemetry=True,
    )
    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "preempt@step=3")
    t_b = Trainer(cfg_b, synthetic_data=True, profile_backward=False)
    with pytest.raises(Preempted) as exc:
        t_b.fit(1)
    assert exc.value.iteration == 3
    t_b.close()
    recs = read_events(
        os.path.join(str(tmp_path / "b"), cfg_b.tag(), "telemetry.jsonl")
    )
    (pre,) = events_of(recs, "preempt")
    assert pre["signal"] == "SIGTERM" and pre["iteration"] == 3

    # restart: resumes mid-epoch from iter 3 and finishes the epoch
    monkeypatch.delenv("MGWFBP_FAULT_PLAN")
    t_b2 = Trainer(cfg_b, synthetic_data=True, profile_backward=False)
    assert t_b2.iteration == 3 and t_b2.start_epoch == 0
    t_b2.fit(1)
    assert t_b2.iteration == t_a.iteration == 6
    for la, lb in zip(
        jax.tree_util.tree_leaves(t_a.state.params),
        jax.tree_util.tree_leaves(t_b2.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # optimizer state resumed exactly too
    for la, lb in zip(
        jax.tree_util.tree_leaves(t_a.state.opt_state),
        jax.tree_util.tree_leaves(t_b2.state.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    t_b2.close()


def test_carry_model_mid_epoch_resume_bitwise(tmp_path, monkeypatch):
    """Mid-epoch resume for a BPTT carry model: the checkpoint carries the
    hidden state, so the restart is bitwise-identical too."""
    import jax.numpy as jnp

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.models import ModelMeta
    from mgwfbp_tpu.models.lstm import PTBLSTM
    from mgwfbp_tpu.train.trainer import Trainer

    def tiny_lstm(nc):
        nc = nc or 10000
        return (
            PTBLSTM(vocab_size=nc, hidden_size=16, num_layers=1, dropout=0.0),
            ModelMeta(name="lstm", dataset="ptb", num_classes=nc,
                      input_shape=(35,), input_dtype=jnp.int32, task="lm",
                      has_carry=True),
        )

    monkeypatch.setitem(zoo._REGISTRY, "lstm", tiny_lstm)
    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    base = dict(batch_size=1, max_epochs=1, num_batches_per_epoch=4, seed=2)
    cfg_a = _cfg("lstm", logdir=str(tmp_path / "a"), **base)
    t_a = Trainer(cfg_a, synthetic_data=True, profile_backward=False)
    t_a.fit(1)
    t_a.close()

    cfg_b = _cfg("lstm", logdir=str(tmp_path / "b"),
                 checkpoint_dir=str(tmp_path / "b_ckpt"), **base)
    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "preempt@step=2")
    t_b = Trainer(cfg_b, synthetic_data=True, profile_backward=False)
    with pytest.raises(Preempted):
        t_b.fit(1)
    t_b.close()
    monkeypatch.delenv("MGWFBP_FAULT_PLAN")
    t_b2 = Trainer(cfg_b, synthetic_data=True, profile_backward=False)
    assert t_b2.iteration == 2
    t_b2.fit(1)
    for la, lb in zip(
        jax.tree_util.tree_leaves(t_a.state.params),
        jax.tree_util.tree_leaves(t_b2.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    t_b2.close()


def test_preempt_without_checkpoint_dir_still_drains(tmp_path, monkeypatch):
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "preempt@step=2")
    cfg = _cfg(logdir=str(tmp_path), telemetry=True)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    with pytest.raises(Preempted):
        t.fit(1)
    recs = read_events(
        os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl")
    )
    assert events_of(recs, "preempt")
    assert not events_of(recs, "checkpoint")
    t.close()


# --------------------------------------------------------------------------
# Watchdog escalation: all-thread stack dump (and abort) on stall
# --------------------------------------------------------------------------


def test_watchdog_stall_dumps_stacks_to_logfile(tmp_path):
    import logging
    import time

    from mgwfbp_tpu.utils.logging import get_logger
    from mgwfbp_tpu.utils.watchdog import ProgressWatchdog

    logfile = str(tmp_path / "train.log")
    get_logger("mgwfbp.trainer", logfile=logfile)
    try:
        with ProgressWatchdog(
            timeout_s=0.2, check_interval_s=0.05, abort=False
        ) as wd:
            wd.beat("train epoch 0")
            time.sleep(0.6)
        assert wd.fired
    finally:
        get_logger("mgwfbp.trainer", logfile=None)
    content = open(logfile).read()
    assert "all-thread traceback dump" in content
    # faulthandler's per-thread header + this very test frame
    assert "Current thread" in content or "Thread" in content
    assert "test_resilience" in content
    logging.getLogger("mgwfbp.trainer").handlers.clear()
    logging.getLogger("mgwfbp.trainer")._mgwfbp_configured = False


def test_watchdog_abort_exits_86_after_dump(tmp_path):
    """MGWFBP_WATCHDOG_ABORT path in a subprocess: stack dump first, then
    os._exit(86) hands control to the supervisor."""
    script = (
        "import time\n"
        "from mgwfbp_tpu.utils.watchdog import ProgressWatchdog\n"
        "with ProgressWatchdog(timeout_s=0.2, check_interval_s=0.05,\n"
        "                      abort=True) as wd:\n"
        "    wd.beat('train epoch 0')\n"
        "    time.sleep(10)\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", script], cwd=root, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 86
    assert "no training progress" in proc.stderr
    assert "all-thread traceback dump" in proc.stderr
    # the stalled main-thread frame (the sleep on script line 6) is visible
    assert 'File "<string>", line 6' in proc.stderr


def test_injected_stall_fires_watchdog(tmp_path, monkeypatch):
    """stall@... + armed watchdog: the injected wedge is detected and lands
    as a watchdog_stall telemetry event."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "stall@secs=0.8,step=2")
    monkeypatch.setenv("MGWFBP_WATCHDOG_S", "0.2")
    cfg = _cfg(logdir=str(tmp_path), telemetry=True, num_batches_per_epoch=3)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    # pre-compile so the stall lands in the steady state, not the compile
    # allowance window
    t.fit(1)
    recs = read_events(
        os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl")
    )
    stalls = events_of(recs, "watchdog_stall")
    assert stalls and stalls[0]["idle_s"] >= 0.2
    t.close()


# --------------------------------------------------------------------------
# Structured checkpoint-drift error
# --------------------------------------------------------------------------


def test_restore_mismatch_names_offending_leaf(tmp_path, monkeypatch):
    from mgwfbp_tpu.checkpoint import Checkpointer, CheckpointRestoreError
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    cfg = _cfg("mnistnet", checkpoint_dir=str(tmp_path),
               num_batches_per_epoch=2)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.checkpointer.wait()
    run_dir = t.checkpointer._dir
    t.close()

    cfg2 = _cfg("lenet", num_batches_per_epoch=2)
    t2 = Trainer(cfg2, synthetic_data=True, profile_backward=False)
    ck = Checkpointer(run_dir)
    with pytest.raises(CheckpointRestoreError) as exc:
        ck.restore(t2.state)
    msg = str(exc.value)
    assert "config drift" in msg
    assert exc.value.mismatches  # names concrete leaves
    assert "params" in msg
    ck.close()
    t2.close()


# --------------------------------------------------------------------------
# Telemetry stream rotation
# --------------------------------------------------------------------------


def test_event_stream_rotates_by_size_and_reads_as_one(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    w = EventWriter(path, run={"model": "rot"}, max_bytes=2048)
    for i in range(120):
        w.emit("step", step=i, epoch=0, start_s=float(i), dur_s=0.1)
    w.close()
    rotated = sorted(
        f for f in os.listdir(tmp_path) if f.startswith("telemetry.jsonl.")
    )
    assert rotated, "no rotation happened"
    assert os.path.getsize(path) <= 4096  # active segment stays bounded
    recs = read_event_set(path)
    assert sum(1 for r in recs if r["event"] == "header") == 1
    assert recs[0]["run"]["model"] == "rot"
    steps = events_of(recs, "step")
    assert [s["step"] for s in steps] == list(range(120))
    # every segment alone is still a valid, version-checked stream
    seg = read_events(os.path.join(str(tmp_path), rotated[0]))
    assert seg[0]["event"] == "header"
    assert seg[0]["run"]["model"] == "rot"


def test_rotation_gap_never_clobbers_surviving_segment(tmp_path):
    """An operator deleting OLD segments to reclaim disk must not make
    the next rotation overwrite the newest surviving one: the next index
    is max(existing)+1, not the segment count."""
    path = str(tmp_path / "telemetry.jsonl")
    w = EventWriter(path, run={"model": "gap"}, max_bytes=1024)
    i = 0
    while len(_segments(tmp_path)) < 2:
        w.emit("step", step=i, epoch=0, start_s=float(i), dur_s=0.1)
        i += 1
    w.close()
    os.remove(os.path.join(str(tmp_path), "telemetry.jsonl.0000"))
    survivor = os.path.join(str(tmp_path), _segments(tmp_path)[-1])
    before = open(survivor).read()
    w2 = EventWriter(path, max_bytes=1024)
    j = i
    while _segments(tmp_path)[-1] == os.path.basename(survivor):
        w2.emit("step", step=j, epoch=0, start_s=float(j), dur_s=0.1)
        j += 1
    w2.close()
    assert open(survivor).read() == before  # not clobbered
    # and the set still reads end-to-end across the gap
    steps = events_of(read_event_set(path), "step")
    assert steps and steps[-1]["step"] == j - 1


def _segments(d) -> list:
    return sorted(
        f for f in os.listdir(d) if f.startswith("telemetry.jsonl.")
    )


def test_rotation_env_var_and_report(tmp_path, monkeypatch):
    monkeypatch.setenv("MGWFBP_TELEMETRY_MAX_MB", "0.002")  # ~2 KiB
    path = str(tmp_path / "telemetry.jsonl")
    w = EventWriter(path, run={"model": "rot2"})
    assert w.max_bytes == int(0.002 * 1024 * 1024)
    for i in range(80):
        w.emit("step", step=i, epoch=0, start_s=float(i), dur_s=0.1)
    w.close()
    # a restart re-opens the ACTIVE segment and keeps the original anchor
    w2 = EventWriter(path)
    w2.emit("step", step=80, epoch=0, start_s=80.0, dur_s=0.1)
    w2.close()
    import telemetry_report

    recs = read_event_set(path)
    assert len(events_of(recs, "step")) == 81
    report = telemetry_report.format_report(recs)
    assert "81 spans" in report
