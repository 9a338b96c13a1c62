"""Progress watchdog (failure detection, SURVEY §5): a silently blocked
step loop must produce a CRITICAL signal (and optionally an abort) instead
of hanging until an external kill."""

import logging
import time

import pytest

from mgwfbp_tpu.utils.watchdog import ProgressWatchdog


def test_disabled_by_default(monkeypatch):
    monkeypatch.delenv("MGWFBP_WATCHDOG_S", raising=False)
    with ProgressWatchdog() as wd:
        assert not wd.enabled
        assert not wd.fired


def test_fires_on_stall_and_stays_quiet_with_beats():
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    handler = Capture()
    logging.getLogger("mgwfbp.watchdog").addHandler(handler)
    try:
        with ProgressWatchdog(timeout_s=0.3, check_interval_s=0.05) as wd:
            assert wd.enabled
            for _ in range(6):  # active loop: beats keep it quiet
                wd.beat("train epoch 0")
                time.sleep(0.05)
            assert not wd.fired
            time.sleep(0.6)  # stall
        assert wd.fired
    finally:
        logging.getLogger("mgwfbp.watchdog").removeHandler(handler)
    msgs = [r.getMessage() for r in records]
    assert any("no training progress" in m for m in msgs)
    assert any("train epoch 0" in m for m in msgs)


def test_phase_allowance_defers_firing():
    # ADVICE r4 #3: a beat entering a known-long phase (first-step compile,
    # checkpoint save) extends the deadline by allow_s, so a timeout below
    # compile time does not hard-exit a healthy run; the NEXT beat resets
    # the allowance so ordinary steps keep the tight deadline.
    with ProgressWatchdog(timeout_s=0.2, check_interval_s=0.05) as wd:
        wd.beat("compile train step", allow_s=1.0)
        time.sleep(0.5)  # longer than timeout, inside timeout+allowance
        assert not wd.fired
        wd.beat("train epoch 0")  # allowance resets
        time.sleep(0.5)
        assert wd.fired


def test_trainer_arms_watchdog(monkeypatch):
    import numpy as np

    from mgwfbp_tpu.config import make_config
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setenv("MGWFBP_WATCHDOG_S", "60")
    cfg = make_config(
        "mnistnet", batch_size=2, max_epochs=1, num_batches_per_epoch=2,
        logdir=None, augment=False,
    )
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    m = t.fit(1)
    assert np.isfinite(m["train"]["loss"])
    assert t._watchdog is None  # disarmed after fit


def test_preflight_backend_returns_devices_and_times_out(monkeypatch):
    """Failure-detection seam for the launcher: backend init under a
    deadline raises an actionable error instead of blocking forever on a
    chip another process holds."""
    import jax

    from mgwfbp_tpu.utils.platform import preflight_backend

    assert len(preflight_backend(timeout_s=60)) >= 1  # healthy backend
    assert len(preflight_backend(timeout_s=0)) >= 1  # deadline disabled

    def hang():
        time.sleep(30)

    monkeypatch.setattr(jax, "devices", hang)
    with pytest.raises(RuntimeError, match="chip unavailable"):
        preflight_backend(timeout_s=0.2)
