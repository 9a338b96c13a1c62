"""run_with_deadline (failure detection, SURVEY §5: a blocking runtime
call must become a fast, typed error — backend init under it is covered by
test_watchdog's preflight test) and the platform layer's small promises."""

import time

import pytest

from mgwfbp_tpu.utils.platform import DeadlineExceeded, run_with_deadline


def test_returns_value():
    assert run_with_deadline(lambda: 42, 5.0) == 42


def test_deadline_raises_typed_error():
    with pytest.raises(DeadlineExceeded, match="slowop"):
        run_with_deadline(lambda: time.sleep(30), 0.1, what="slowop")


def test_worker_exception_propagates_unchanged():
    with pytest.raises(ZeroDivisionError):
        run_with_deadline(lambda: 1 / 0, 5.0)


def test_explicit_platform_pin_and_no_env_platform_knob(monkeypatch):
    """apply_platform_overrides pins a platform only when told to in code;
    JAX_PLATFORMS is jax's own business and no MGWFBP_* twin of it exists."""
    import jax

    from mgwfbp_tpu.utils import platform as plat

    seen = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: seen.append((k, v))
    )
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("MGWFBP_PLATFORM", "tpu")
    monkeypatch.delenv("MGWFBP_HOST_DEVICES", raising=False)
    plat.apply_platform_overrides()
    assert seen == []  # env alone: nothing is set in code
    plat.apply_platform_overrides("cpu")
    assert seen == [("jax_platforms", "cpu")]


def test_unknown_device_kind_has_no_peak():
    """No invented peak: a device that is not in the table (the CPU
    included) yields None, so MFU is not reported rather than made up."""
    from mgwfbp_tpu.utils.platform import peak_flops

    assert peak_flops("cpu") is None
    assert peak_flops("some future chip") is None
    assert peak_flops("TPU v5 lite") == 197e12
