"""Platform choice, compile cache and process-level deadlines.

JAX picks its backend itself: the attached TPU when there is one, else
the CPU, and `JAX_PLATFORMS=cpu` in the environment pins the CPU (tests,
rehearsals). What is left for an entry point to do before its first
backend touch is small, and lives here: an explicit in-code platform pin
for the few callers that need one, the virtual-CPU-device flag, and the
persistent compile cache (`enable_compile_cache`). One process drives all
chips of a host; a second process that needs the chip fails or hangs, so
nothing here spawns one.
"""

from __future__ import annotations

import os
from typing import Optional

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# Fixed, git-ignored, inside the checkout: the directory is part of the
# cache key, so it must never derive from tempfile, pid or time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def force_host_device_count(n: int) -> None:
    """Request n virtual CPU devices. Must run before jax initializes."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def apply_platform_overrides(
    platform: Optional[str] = None,
    host_device_count: Optional[int] = None,
) -> None:
    """Entry-point set-up that must precede the first backend touch.

    `JAX_PLATFORMS` needs no help (jax reads it itself). `platform` pins
    one in code, for callers that are CPU programs whatever the
    environment says (the analyzer, the driver's multichip dryrun, test
    workers). `host_device_count` (or MGWFBP_HOST_DEVICES) asks the CPU
    backend for that many virtual devices.
    """
    if host_device_count is None:
        host_device_count = env_int("MGWFBP_HOST_DEVICES", 0)
    if host_device_count:
        force_host_device_count(host_device_count)
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache; returns the directory.

    Placeable from outside: when JAX_COMPILATION_CACHE_DIR is set jax
    already uses it and nothing is set in code. Otherwise the cache lives
    at DEFAULT_COMPILE_CACHE_DIR. Called next to every
    `apply_platform_overrides()`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


# Peak dense-matmul FLOP/s per chip by device-kind substring (bf16; for
# fp32 runs an upper bound, making MFU conservative). A device that is not
# listed has no peak: MFU is then not reported, never invented. Read by
# tools/mfu_ablation.py.
PEAK_FLOPS_BY_DEVICE_KIND = [
    ("v5 lite", 197e12),  # TPU v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v6", 918e12),  # Trillium
]


def peak_flops(device_kind: str):
    """Peak FLOP/s for a device kind, or None when unknown."""
    kind = device_kind.lower()
    for sub, peak in PEAK_FLOPS_BY_DEVICE_KIND:
        if sub in kind:
            return peak
    return None


def env_float(
    name: str, default: float, environ=None,
) -> float:
    """Parse a float knob from the environment, failing fast WITH THE
    VARIABLE NAMED on garbage input (the MGWFBP_BARRIER_TIMEOUT_S
    precedent: a typo'd timeout must not surface as a bare float()
    traceback mid-drain, or worse silently fall back to a default that
    changes healing behavior). Unset/empty returns `default`."""
    raw = ((environ if environ is not None else os.environ).get(name)
           or "").strip()
    if not raw:
        return float(default)
    try:
        return float(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not a number") from None


def env_int(name: str, default: int, environ=None) -> int:
    """`env_float`'s integer sibling (same fail-fast naming contract)."""
    raw = ((environ if environ is not None else os.environ).get(name)
           or "").strip()
    if not raw:
        return int(default)
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None


class DeadlineExceeded(RuntimeError):
    """run_with_deadline hit its timeout (the worker thread is abandoned)."""


def run_with_deadline(fn, timeout_s: float, what: str = "operation"):
    """Run fn() on a daemon thread and wait at most timeout_s.

    Returns fn()'s value; raises DeadlineExceeded on timeout, else
    re-raises fn's own exception unchanged. One implementation of the
    spawn/box/join/is_alive watchdog pattern — backend init and the
    multi-host coordination ops both need it (a blocking runtime call has
    no timeout of its own; a deadline turns the hang into a reportable
    error). The abandoned thread is a daemon: it cannot keep the process
    alive, but any C-level lock it holds stays held — callers should
    treat a DeadlineExceeded process as tainted and exit soon.
    """
    import threading

    box: dict = {}

    def work():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=work, daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise DeadlineExceeded(
            f"{what} exceeded {timeout_s:.0f}s deadline"
        )
    if "error" in box:
        raise box["error"]
    return box["value"]


def preflight_backend(timeout_s: Optional[float] = None) -> list:
    """Initialize the JAX backend under a deadline; raise instead of hang.

    A chip belongs to one process at a time: while another process holds
    it, backend init can block instead of failing. A launcher that hangs
    can neither report nor retry, so the wait is bounded and ends in an
    actionable error (failure-detection parity, SURVEY.md §5).

    timeout_s: None reads MGWFBP_INIT_TIMEOUT_S (default 300); <= 0
    disables the deadline. Returns jax.devices() on success.
    """
    if timeout_s is None:
        timeout_s = env_float("MGWFBP_INIT_TIMEOUT_S", 300.0)
    import jax

    if timeout_s <= 0:
        return jax.devices()
    try:
        return run_with_deadline(
            jax.devices, timeout_s, what="JAX backend init"
        )
    except DeadlineExceeded:
        raise RuntimeError(
            f"JAX backend init exceeded {timeout_s:.0f}s — chip unavailable "
            "(is another process on this host holding it?). Probe with "
            "`timeout 60 python -c 'import jax; print(jax.devices())'`, or "
            "raise MGWFBP_INIT_TIMEOUT_S."
        ) from None
