"""The health drain of `Trainer.train_epoch` (ISSUE 25): a step's statistics
reach the host as copies of the step's own output arrays and are read one
step late. The drain dispatches no device program (a program would queue
behind the step in flight), and the `health` events carry exactly what the
step computed. CPU mesh; nothing here compares a measured time."""

import os

import jax
import numpy as np
import pytest
from jax._src import core as jax_core

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.telemetry import events_of, read_events
from mgwfbp_tpu.telemetry import phases as phases_module
from mgwfbp_tpu.train.step import HEALTH_PREFIX

STEPS = 6


def _trainer(tmp_path, **kw):
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = make_config(
        "lenet", lr=0.01, max_epochs=1, logdir=str(tmp_path),
        checkpoint_dir=None, seed=11, batch_size=8,
        num_batches_per_epoch=STEPS, telemetry=True, **kw,
    )
    return Trainer(cfg, synthetic_data=True, profile_backward=False), cfg


def _stream(tmp_path, cfg):
    return read_events(
        os.path.join(str(tmp_path), cfg.tag(), "telemetry.jsonl"))


def test_the_drain_lowers_and_dispatches_no_program(tmp_path, monkeypatch):
    """Fails on the parent, whose drain stacked the statistics with
    `jnp.stack` / `jnp.asarray`: programs lowered at the first drain (so
    step 3's record read `lowered` > 0) and dispatched at every one."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "1000")
    inside = {"depth": 0, "binds": 0, "lowered": 0, "calls": 0}
    real_process = jax_core.EvalTrace.process_primitive

    def counting_process(self, primitive, *args, **kw):
        # every eagerly executed primitive (jnp.stack, jnp.asarray(x, dtype),
        # indexing, arithmetic on arrays) passes here on its way to the device
        if inside["depth"]:
            inside["binds"] += 1
        return real_process(self, primitive, *args, **kw)

    def watched(real):
        def call(self, *args, **kw):
            before = phases_module.lowered_programs()
            inside["depth"] += 1
            try:
                return real(self, *args, **kw)
            finally:
                inside["depth"] -= 1
                inside["calls"] += 1
                inside["lowered"] += phases_module.lowered_programs() - before
        return call

    monkeypatch.setattr(
        jax_core.EvalTrace, "process_primitive", counting_process)
    monkeypatch.setattr(
        Trainer, "_note_health_stats", watched(Trainer._note_health_stats))
    monkeypatch.setattr(
        Trainer, "_drain_health_flags", watched(Trainer._drain_health_flags))
    t, cfg = _trainer(tmp_path)
    t.train_epoch(0)
    t.close()
    assert inside["calls"] == STEPS + 1  # every step, and the epoch's drain
    assert inside["binds"] == 0 and inside["lowered"] == 0, inside
    stream = _stream(tmp_path, cfg)
    steps = events_of(stream, "step")
    assert [s["step"] for s in steps] == list(range(1, STEPS + 1))
    # the last record also counts what followed the loop (the closing log
    # line's learning rate), so it is left out
    assert [s["lowered"] for s in steps[2:-1]] == [0] * (STEPS - 3)
    assert len(events_of(stream, "health")) == STEPS


def _expected_event(step, metrics):
    """A `health` event's fields, decoded plainly from one step's outputs."""
    f32 = {k: float(np.float32(np.asarray(v))) for k, v in metrics.items()}
    want = {
        "step": step, "epoch": 0, "loss": f32["loss"],
        "grad_norm": f32[HEALTH_PREFIX + "grad_norm"],
        "update_ratio": f32[HEALTH_PREFIX + "update_ratio"],
    }
    groups = sorted(k for k in f32 if k.startswith(HEALTH_PREFIX + "gnorm_g"))
    comp = sorted(k for k in f32 if k.startswith(HEALTH_PREFIX + "comp_err_g"))
    if groups:
        want["group_norms"] = [f32[k] for k in groups]
    if comp:
        want["compression_error"] = [f32[k] for k in comp]
    return want


@pytest.mark.parametrize("interval", [1, 3])
def test_health_events_equal_the_steps_own_outputs(
    tmp_path, monkeypatch, interval,
):
    """Value for value, one event a step, in step order, whether the queue
    drains one step or three at a time, and across a change of the key set
    between two queued items (an autotune commit or a resize does that):
    steps 1, 2 carry the schedule's groups, steps 3, 4 one group less,
    steps 5, 6 a compression error besides. With an interval of 3 the drains
    take steps 1 to 3 and 4 to 6: each straddles a change."""
    monkeypatch.setenv("MGWFBP_GUARD_CHECK_INTERVAL", str(interval))
    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "1000")
    t, cfg = _trainer(tmp_path)
    assert t._guard_interval == interval
    real_step = t.train_step
    outputs: list[dict] = []

    def recording_step(state, batch):
        state, metrics = real_step(state, batch)
        n = len(outputs) + 1
        groups = sorted(
            k for k in metrics if k.startswith(HEALTH_PREFIX + "gnorm_g"))
        assert len(groups) >= 2, groups
        if n >= 3:
            del metrics[groups[-1]]
        if n >= 5:
            metrics[HEALTH_PREFIX + "comp_err_g0000"] = (
                metrics[HEALTH_PREFIX + "update_ratio"])
        outputs.append({
            k: v for k, v in metrics.items()
            if k == "loss" or k.startswith(HEALTH_PREFIX)
        })
        return state, metrics

    t.train_step = recording_step
    t.train_epoch(0)
    t.close()
    jax.block_until_ready(outputs)
    assert len(outputs) == STEPS
    assert len({frozenset(o) for o in outputs}) == 3  # three key sets
    events = events_of(_stream(tmp_path, cfg), "health")
    got = [
        {k: v for k, v in e.items() if k not in ("event", "wall")}
        for e in events
    ]
    want = [_expected_event(i + 1, o) for i, o in enumerate(outputs)]
    assert got == want  # NaN-free by construction: == compares every float


def test_stats_ready_rides_on_the_records_that_drained(tmp_path, monkeypatch):
    """With the guard on, its read of step k-1's flag has waited for that
    step before the drain asks for step k-1's statistics: 1 on every record
    that drained something, nothing on the first (which queued only)."""
    monkeypatch.setenv("MGWFBP_LOG_INTERVAL", "1000")
    t, cfg = _trainer(tmp_path)
    t.train_epoch(0)
    t.close()
    steps = events_of(_stream(tmp_path, cfg), "step")
    assert "stats_ready" not in steps[0]
    assert [s["stats_ready"] for s in steps[1:]] == [1] * (STEPS - 1)
