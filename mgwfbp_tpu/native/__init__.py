"""Native (C++) host-side data-path kernels with lazy build + ctypes binding.

The compute path of this framework is JAX/XLA on TPU; the runtime AROUND it
— here, the loader's augmentation/normalization hot loop — is native C++
(SURVEY.md §2.9: the reference's data path rides torch DataLoader's C
workers). The extension is built on first use with the container's g++
(no pip; pybind11 unavailable by design — plain C ABI + ctypes), cached
next to the source, and every caller has a bit-identical NumPy fallback:
`available()` returning False never blocks training.

The library is a `ctypes.CDLL`, so a call releases the GIL for as long as
the kernel runs: `PrefetchLoader`'s workers transform their batches side by
side. `passes()` counts the kernel calls the calling thread has made, which
is how the pool tells a batch that came from the native pass from one that
took the fallback (telemetry's `native` counter).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "augment.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_PASSES = threading.local()


def passes() -> int:
    """Native kernel calls made by the calling thread so far."""
    return getattr(_PASSES, "n", 0)


def _count_pass() -> None:
    _PASSES.n = passes() + 1


def _output(shape: tuple, out: Optional[np.ndarray]) -> np.ndarray:
    """The float32 array a kernel writes: `out` if given (checked: the
    kernel writes shape's worth of float32 where it points), else fresh."""
    if out is None:
        return np.empty(shape, np.float32)
    if (
        out.shape != shape or out.dtype != np.float32
        or not out.flags.c_contiguous or not out.flags.writeable
    ):
        raise ValueError(
            f"out must be a writeable C-contiguous float32 array of shape "
            f"{shape}")
    return out


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libmgwfbp_native_{tag}.so")


def _build(so: str) -> bool:
    import tempfile

    # per-process temp output: concurrent first-use builds (e.g. two ranks
    # of a multi-process run on one box) must not interleave writes into a
    # shared .tmp before the atomic publish
    fd, tmp = tempfile.mkstemp(dir=_DIR, suffix=".so.tmp")
    os.close(fd)
    # no -ffast-math and no FMA contraction: the kernels hold the NumPy
    # fallback's bits
    cmd = ["g++", "-O3", "-ffp-contract=off", "-shared", "-fPIC",
           "-std=c++17", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    """Build (once per source hash) and bind the library; None where that
    fails."""
    so = _so_path()
    if not os.path.exists(so) and not _build(so):
        return None
    try:
        lib = ctypes.CDLL(so)
    except OSError:
        return None
    i64 = ctypes.c_int64
    lib.fused_crop_flip_normalize.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p,
        i64, i64, i64, i64, i64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.fused_crop_flip_normalize.restype = None
    lib.fused_rrc_flip_normalize.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p,
        i64, i64, i64, i64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.fused_rrc_flip_normalize.restype = None
    lib.normalize_u8.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, i64, i64,
        ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.normalize_u8.restype = None
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first call; None when no
    toolchain is available (callers fall back to NumPy). A caller that
    arrives while another thread is still building waits for it: the
    pool's second worker must not take the fallback for its first batch
    because the first worker's `g++` has not finished (it did, until PR 27:
    `_TRIED` was set before the build)."""
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    with _LOCK:
        if not _TRIED:
            _LIB = _load()
            _TRIED = True
        return _LIB


def available() -> bool:
    return get_lib() is not None


def fused_crop_flip_normalize(
    x: np.ndarray,
    oy: np.ndarray,
    ox: np.ndarray,
    flip: np.ndarray,
    mean: np.ndarray,
    std: np.ndarray,
    pad: int,
) -> Optional[np.ndarray]:
    """One-pass crop+flip+normalize of a uint8 (B,H,W,C) batch; None when
    the native library is unavailable or inputs don't qualify."""
    lib = get_lib()
    if lib is None or x.dtype != np.uint8 or x.ndim != 4 or x.shape[3] > 16:
        return None
    x = np.ascontiguousarray(x)
    b, h, w, c = x.shape
    out = np.empty((b, h, w, c), np.float32)
    oy = np.ascontiguousarray(oy, np.int64)
    ox = np.ascontiguousarray(ox, np.int64)
    fl = np.ascontiguousarray(flip, np.uint8)
    m = np.ascontiguousarray(mean, np.float32)
    s = np.ascontiguousarray(std, np.float32)
    lib.fused_crop_flip_normalize(
        x.ctypes.data, out.ctypes.data, b, h, w, c, pad,
        oy.ctypes.data, ox.ctypes.data, fl.ctypes.data,
        m.ctypes.data, s.ctypes.data,
    )
    _count_pass()
    return out


def fused_rrc_flip_normalize(
    x: np.ndarray,
    top: np.ndarray,
    left: np.ndarray,
    ch: np.ndarray,
    cw: np.ndarray,
    flip: np.ndarray,
    mean: np.ndarray,
    std: np.ndarray,
    out: Optional[np.ndarray] = None,
) -> Optional[np.ndarray]:
    """One-pass RandomResizedCrop + flip + normalize of a uint8 (B,H,W,C)
    batch at the given crop rectangles (bilinear, half-pixel centres, back
    to (H, W)), written into `out` where one is given; None when the native
    library is unavailable or inputs don't qualify. Rectangles outside the
    image are an error: the kernel reads where they say."""
    lib = get_lib()
    if lib is None or x.dtype != np.uint8 or x.ndim != 4 or x.shape[3] > 16:
        return None
    x = np.ascontiguousarray(x)
    b, h, w, c = x.shape
    top, left, ch, cw = (
        np.ascontiguousarray(a, np.int64) for a in (top, left, ch, cw))
    fl = np.ascontiguousarray(flip, np.uint8)
    if any(a.shape != (b,) for a in (top, left, ch, cw, fl)):
        raise ValueError("one crop rectangle and one flip per image")
    if (
        (top < 0).any() or (left < 0).any() or (ch < 1).any()
        or (cw < 1).any() or (top + ch > h).any() or (left + cw > w).any()
    ):
        raise ValueError("crop rectangle outside the image")
    out = _output((b, h, w, c), out)
    m = np.ascontiguousarray(mean, np.float32)
    s = np.ascontiguousarray(std, np.float32)
    lib.fused_rrc_flip_normalize(
        x.ctypes.data, out.ctypes.data, b, h, w, c,
        top.ctypes.data, left.ctypes.data, ch.ctypes.data, cw.ctypes.data,
        fl.ctypes.data, m.ctypes.data, s.ctypes.data,
    )
    _count_pass()
    return out


def normalize_u8(
    x: np.ndarray,
    mean: np.ndarray,
    std: np.ndarray,
    out: Optional[np.ndarray] = None,
) -> Optional[np.ndarray]:
    """Fused uint8 -> normalized float32, written into `out` where one is
    given; None when unavailable."""
    lib = get_lib()
    if lib is None or x.dtype != np.uint8 or x.shape[-1] > 16:
        return None
    x = np.ascontiguousarray(x)
    out = _output(x.shape, out)
    m = np.ascontiguousarray(mean, np.float32)
    s = np.ascontiguousarray(std, np.float32)
    lib.normalize_u8(
        x.ctypes.data, out.ctypes.data, x.size, x.shape[-1],
        m.ctypes.data, s.ctypes.data,
    )
    _count_pass()
    return out
