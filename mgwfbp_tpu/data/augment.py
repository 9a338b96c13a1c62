"""Training-time data augmentation (host-side NumPy, seeded).

Parity (VERDICT r2 task #6): the reference trains CIFAR with
RandomCrop(32, padding=4) + RandomHorizontalFlip (reference
dl_trainer.py:381-385) and ImageNet with RandomResizedCrop(224) +
RandomHorizontalFlip (dl_trainer.py:331-336). These run in the loader's
transform slot, TRAIN split only, on (B, H, W, C) batches before
normalization. Randomness comes from a per-batch `np.random.Generator`
handed in by `ShardedLoader` (seeded by (seed, epoch, rank, batch)), so
epochs reshuffle augmentation deterministically and ranks decorrelate.

Everything is vectorized or O(B) NumPy — no PIL/torchvision; the bilinear
resize for RandomResizedCrop is implemented directly. What `data_prepare`
hands the loader for `cifar10` and `imagenet` is the fused form of these
(`FusedCropFlipNormalize`, `FusedResizedCropFlipNormalize`): one native pass
over the uint8 batch (mgwfbp_tpu/native), with the NumPy operations below as
the bit-identical fallback and the tests' plain reference.
"""

from __future__ import annotations

from typing import Callable, Sequence

import numpy as np


def random_hflip(x: np.ndarray, rng: np.random.Generator, p: float = 0.5) -> np.ndarray:
    """Flip each sample left-right with probability p. x: (B, H, W, C)."""
    flip = rng.random(x.shape[0]) < p
    if not flip.any():
        return x
    out = x.copy()
    out[flip] = out[flip, :, ::-1]
    return out


def crop_at_offsets(
    x: np.ndarray, ys: np.ndarray, xs: np.ndarray, pad: int
) -> np.ndarray:
    """Zero-pad by `pad`, crop back to the original size at the given
    per-sample offsets (0..2*pad)."""
    b, h, w, c = x.shape
    padded = np.pad(
        x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="constant"
    )
    out = np.empty_like(x)
    for i in range(b):
        out[i] = padded[i, ys[i] : ys[i] + h, xs[i] : xs[i] + w]
    return out


def random_crop(
    x: np.ndarray, rng: np.random.Generator, pad: int = 4
) -> np.ndarray:
    """Zero-pad by `pad` on each spatial side, crop back to the original
    size at a per-sample random offset (torchvision RandomCrop(size, pad))."""
    b = x.shape[0]
    ys = rng.integers(0, 2 * pad + 1, size=b)
    xs = rng.integers(0, 2 * pad + 1, size=b)
    return crop_at_offsets(x, ys, xs, pad)


def sample_crop_rects(
    rng: np.random.Generator,
    b: int,
    h: int,
    w: int,
    scale: tuple[float, float] = (0.08, 1.0),
    ratio: tuple[float, float] = (3.0 / 4.0, 4.0 / 3.0),
    attempts: int = 10,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """torchvision RandomResizedCrop's rectangles for b images of (h, w):
    (top, left, ch, cw), each (B,) int64, every rectangle inside the image.
    An area fraction and an aspect ratio per sample, `attempts` candidates,
    the first that fits wins; the centre square where none does. The order
    of these draws is part of what a seed names: every caller shares it."""
    area = h * w * rng.uniform(scale[0], scale[1], size=(attempts, b))
    ar = np.exp(
        rng.uniform(np.log(ratio[0]), np.log(ratio[1]), size=(attempts, b))
    )
    tw = np.round(np.sqrt(area * ar)).astype(np.int64)
    th = np.round(np.sqrt(area / ar)).astype(np.int64)
    valid = (tw > 0) & (tw <= w) & (th > 0) & (th <= h)
    first = np.argmax(valid, axis=0)  # index of first valid candidate
    any_valid = valid[first, np.arange(b)]
    cw = np.where(any_valid, tw[first, np.arange(b)], min(w, h))
    ch = np.where(any_valid, th[first, np.arange(b)], min(w, h))
    # per-sample uniform offsets within the valid range
    top = np.floor(rng.random(b) * (h - ch + 1)).astype(np.int64)
    left = np.floor(rng.random(b) * (w - cw + 1)).astype(np.int64)
    # center-crop fallback where nothing was valid (torchvision semantics)
    top = np.where(any_valid, top, (h - ch) // 2)
    left = np.where(any_valid, left, (w - cw) // 2)
    return top, left, ch, cw


def resized_crop_at(
    x: np.ndarray,
    top: np.ndarray,
    left: np.ndarray,
    ch: np.ndarray,
    cw: np.ndarray,
) -> np.ndarray:
    """Crop each sample of x (B, H, W, C) at its rectangle and resize it
    back to (H, W): bilinear, half-pixel centres, indices clipped to the
    rectangle, one batched gather. Output is float32. The native kernel
    (`native.fused_rrc_flip_normalize`) repeats these operations in this
    order, so the two agree to the bit."""
    b, h, w, c = x.shape
    yy = top[:, None] + (np.arange(h)[None, :] + 0.5) * ch[:, None] / h - 0.5
    xx = left[:, None] + (np.arange(w)[None, :] + 0.5) * cw[:, None] / w - 0.5
    y0f = np.floor(yy)
    x0f = np.floor(xx)
    wy = (yy - y0f).astype(np.float32)[:, :, None, None]  # (B, h, 1, 1)
    wx = (xx - x0f).astype(np.float32)[:, None, :, None]  # (B, 1, w, 1)
    ylo = top[:, None]
    yhi = (top + ch - 1)[:, None]
    xlo = left[:, None]
    xhi = (left + cw - 1)[:, None]
    y0 = np.clip(y0f.astype(np.int64), ylo, yhi)
    y1 = np.clip(y0 + 1, ylo, yhi)
    x0 = np.clip(x0f.astype(np.int64), xlo, xhi)
    x1 = np.clip(x0 + 1, xlo, xhi)
    bi = np.arange(b)[:, None, None]
    f = x.astype(np.float32)
    y0e, y1e = y0[:, :, None], y1[:, :, None]  # (B, h, 1)
    x0e, x1e = x0[:, None, :], x1[:, None, :]  # (B, 1, w)
    top_row = f[bi, y0e, x0e] * (1 - wx) + f[bi, y0e, x1e] * wx
    bot_row = f[bi, y1e, x0e] * (1 - wx) + f[bi, y1e, x1e] * wx
    return top_row * (1 - wy) + bot_row * wy


def random_resized_crop(
    x: np.ndarray,
    rng: np.random.Generator,
    scale: tuple[float, float] = (0.08, 1.0),
    ratio: tuple[float, float] = (3.0 / 4.0, 4.0 / 3.0),
    attempts: int = 10,
) -> np.ndarray:
    """torchvision RandomResizedCrop: sample an area fraction and aspect
    ratio per sample, crop, bilinear-resize back to the input size.

    Vectorized over the batch in NumPy: the plain reference of
    `FusedResizedCropFlipNormalize` and its fallback. Output is float32.
    """
    b, h, w, _ = x.shape
    rects = sample_crop_rects(rng, b, h, w, scale, ratio, attempts)
    return resized_crop_at(x, *rects)


class FusedCropFlipNormalize:
    """CIFAR-style crop + flip + normalize as ONE pass over the batch.

    Uses the native C++ kernel (mgwfbp_tpu.native) when available — a single
    read of the uint8 batch producing normalized float32 — with a
    bit-identical NumPy fallback (randomness is drawn host-side with the
    same call order either way, so native and fallback produce the same
    bytes for the same seed)."""

    wants_rng = True

    def __init__(self, mean, std, pad: int = 4, p_flip: float = 0.5):
        self.mean = np.asarray(mean, np.float32)
        self.std = np.asarray(std, np.float32)
        self.pad = pad
        self.p_flip = p_flip

    def __call__(self, x: np.ndarray, rng: np.random.Generator) -> np.ndarray:
        b = x.shape[0]
        ys = rng.integers(0, 2 * self.pad + 1, size=b)
        xs = rng.integers(0, 2 * self.pad + 1, size=b)
        flips = rng.random(b) < self.p_flip
        if x.dtype == np.uint8:
            from mgwfbp_tpu import native

            out = native.fused_crop_flip_normalize(
                x, ys, xs, flips.astype(np.uint8), self.mean, self.std,
                self.pad,
            )
            if out is not None:
                return out
        # fallback: crop_at_offsets returns a fresh array, flip in place;
        # use the SAME affine factorization (px*scale - shift) as the C++
        # kernel so both paths round identically in float32
        x = crop_at_offsets(x, ys, xs, self.pad)
        x[flips] = x[flips, :, ::-1]
        scale = (1.0 / (255.0 * self.std)).astype(np.float32)
        shift = (self.mean / self.std).astype(np.float32)
        return x.astype(np.float32) * scale - shift


class FusedResizedCropFlipNormalize:
    """ImageNet-style RandomResizedCrop + flip + normalize as ONE pass over
    the batch: the twin of `FusedCropFlipNormalize` for
    `chain(train_augment("imagenet"), normalize_images(mean, std))`.

    Rectangles and flips are drawn here, by the calls and in the order
    `random_resized_crop` and `random_hflip` make, so a seed names the same
    crops on every path. The native kernel reads the uint8 batch and writes
    normalized float32 once, without the GIL, into `out` where the caller
    brings one (`PrefetchLoader`'s recycled arrays); the NumPy fallback (no
    library, or a batch that is not uint8) is bit-identical and returns an
    array of its own."""

    wants_rng = True
    takes_out = True

    def __init__(self, mean, std, p_flip: float = 0.5):
        self.mean = np.asarray(mean, np.float32)
        self.std = np.asarray(std, np.float32)
        # the fallback's affine: the SAME factorization (px*scale - shift)
        # as the C++ kernel and `normalize_images`
        self._scale = (1.0 / (255.0 * self.std)).astype(np.float32)
        self._shift = (self.mean / self.std).astype(np.float32)
        self.p_flip = p_flip

    def __call__(
        self, x: np.ndarray, rng: np.random.Generator, out=None
    ) -> np.ndarray:
        b, h, w, _ = x.shape
        rects = sample_crop_rects(rng, b, h, w)
        flips = rng.random(b) < self.p_flip
        if x.dtype == np.uint8:
            from mgwfbp_tpu import native

            out = native.fused_rrc_flip_normalize(
                x, *rects, flips, self.mean, self.std, out=out)
            if out is not None:
                return out
        # fallback: the composition's own operations
        x = resized_crop_at(x, *rects)
        x[flips] = x[flips, :, ::-1]
        return x * self._scale - self._shift


class Augment:
    """Composable seeded augmentation pipeline for the loader's transform
    slot. `wants_rng` tells ShardedLoader to pass its per-batch Generator."""

    wants_rng = True

    def __init__(self, *stages: Callable):
        self.stages = stages

    def __call__(self, x: np.ndarray, rng: np.random.Generator) -> np.ndarray:
        for s in self.stages:
            x = s(x, rng)
        return x


def train_augment(dataset: str) -> Augment | None:
    """Reference training transforms by dataset (dl_trainer.py:331-336,
    381-385); None where the reference doesn't augment (mnist, ptb, an4)."""
    name = dataset.lower()
    if name == "cifar10":
        return Augment(random_crop, random_hflip)
    if name == "imagenet":
        return Augment(random_resized_crop, random_hflip)
    return None


def chain(*transforms) -> Callable:
    """Compose transforms left-to-right; rng-aware stages get the Generator.
    The composite wants an rng iff any member does."""
    members = [t for t in transforms if t is not None]

    class _Chain:
        wants_rng = any(getattr(t, "wants_rng", False) for t in members)

        def __call__(self, x, rng=None):
            for t in members:
                if getattr(t, "wants_rng", False):
                    x = t(x, rng)
                else:
                    x = t(x)
            return x

    return _Chain()
