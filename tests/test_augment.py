"""Augmentation unit tests (VERDICT r2 task #6; reference
dl_trainer.py:331-336 ImageNet RandomResizedCrop+flip, :381-385 CIFAR
RandomCrop(32, pad=4)+flip)."""

import numpy as np
import pytest

from mgwfbp_tpu.data.augment import (
    Augment,
    FusedResizedCropFlipNormalize,
    chain,
    random_crop,
    random_hflip,
    random_resized_crop,
    resized_crop_at,
    sample_crop_rects,
    train_augment,
)
from mgwfbp_tpu.data.loader import ArrayDataset, ShardedLoader


def _rng(seed=0):
    return np.random.default_rng([seed])


def test_random_hflip_flips_some_not_all():
    x = np.arange(8 * 4 * 4 * 1, dtype=np.float32).reshape(8, 4, 4, 1)
    out = random_hflip(x, _rng(0))
    flipped = [
        i for i in range(8) if np.array_equal(out[i], x[i, :, ::-1])
        and not np.array_equal(out[i], x[i])
    ]
    unchanged = [i for i in range(8) if np.array_equal(out[i], x[i])]
    assert flipped and unchanged
    assert len(flipped) + len(unchanged) == 8


def test_random_crop_preserves_shape_and_content_window():
    x = np.random.RandomState(0).rand(4, 32, 32, 3).astype(np.float32)
    out = random_crop(x, _rng(1), pad=4)
    assert out.shape == x.shape
    # every output is a translated copy: its interior must appear in the
    # padded original; cheap check — pixel multiset of the central region
    # intersects heavily (zero padding enters at most 4 rows/cols)
    assert np.isin(
        np.round(out[0, 8:24, 8:24], 5), np.round(x[0], 5)
    ).mean() > 0.9


def test_random_crop_identity_at_zero_offset():
    x = np.ones((2, 8, 8, 1), np.float32)
    out = random_crop(x, _rng(2), pad=2)
    # all-ones image: any crop containing no padding is all ones; padding
    # introduces zeros only at the borders
    assert out.shape == x.shape
    assert set(np.unique(out)).issubset({0.0, 1.0})


def test_random_resized_crop_shape_and_range():
    x = np.random.RandomState(1).rand(3, 32, 32, 3).astype(np.float32)
    out = random_resized_crop(x, _rng(3))
    assert out.shape == x.shape
    assert out.dtype == np.float32
    # bilinear interpolation cannot exceed the input range
    assert out.min() >= x.min() - 1e-5 and out.max() <= x.max() + 1e-5


def test_train_augment_registry():
    assert train_augment("cifar10") is not None
    assert train_augment("imagenet") is not None
    assert train_augment("mnist") is None
    assert train_augment("ptb") is None


def test_loader_augmentation_deterministic_per_epoch():
    rs = np.random.RandomState(0)
    ds = ArrayDataset(
        rs.rand(64, 8, 8, 1).astype(np.float32),
        rs.randint(0, 10, 64),
        10,
    )
    aug = Augment(random_crop, random_hflip)
    loader = ShardedLoader(ds, 16, shuffle=True, seed=7, transform=aug)
    loader.set_epoch(0)
    a0 = [x.copy() for x, _ in loader]
    loader.set_epoch(0)
    a0b = [x.copy() for x, _ in loader]
    loader.set_epoch(1)
    a1 = [x.copy() for x, _ in loader]
    for u, v in zip(a0, a0b):  # same epoch -> identical augmentation
        np.testing.assert_array_equal(u, v)
    assert any(
        not np.array_equal(u, v) for u, v in zip(a0, a1)
    )  # different epoch -> different crops/flips


def test_chain_mixes_rng_and_plain_transforms():
    calls = []

    def plain(x):
        calls.append("plain")
        return x + 1.0

    aug = Augment(random_hflip)
    tf = chain(aug, plain)
    assert tf.wants_rng
    x = np.zeros((2, 4, 4, 1), np.float32)
    out = tf(x, _rng(4))
    assert calls == ["plain"]
    np.testing.assert_array_equal(out, np.ones_like(x))


# --- RandomResizedCrop's two halves and ImageNet's fused transform (ISSUE 27)


@pytest.mark.parametrize("hw", [(224, 224), (299, 299), (17, 31), (31, 17)])
def test_sample_crop_rects_stay_inside_the_image(hw):
    h, w = hw
    top, left, ch, cw = sample_crop_rects(_rng(5), 256, h, w)
    for a in (top, left, ch, cw):
        assert a.shape == (256,) and a.dtype == np.int64
    assert ((ch >= 1) & (cw >= 1) & (top >= 0) & (left >= 0)).all()
    assert ((top + ch <= h) & (left + cw <= w)).all()
    area = ch * cw / (h * w)
    assert area.min() < 0.3 and area.max() > 0.7  # the scale range is used


def test_sample_crop_rects_centre_square_where_no_candidate_fits():
    # every candidate is far wider than the image: torchvision's fallback
    top, left, ch, cw = sample_crop_rects(
        _rng(6), 8, 20, 30, scale=(0.9, 1.0), ratio=(50.0, 60.0))
    np.testing.assert_array_equal(ch, np.full(8, 20))
    np.testing.assert_array_equal(cw, np.full(8, 20))
    np.testing.assert_array_equal(top, np.zeros(8, np.int64))
    np.testing.assert_array_equal(left, np.full(8, 5))


def test_resized_crop_of_the_whole_image_is_the_image():
    x = np.random.RandomState(2).randint(0, 256, (3, 12, 9, 3)).astype(np.uint8)
    whole = [np.full(3, v, np.int64) for v in (0, 0, 12, 9)]
    np.testing.assert_array_equal(
        resized_crop_at(x, *whole), x.astype(np.float32))


def test_random_resized_crop_is_its_two_halves():
    x = np.random.RandomState(3).rand(5, 16, 20, 3).astype(np.float32)
    want = resized_crop_at(x, *sample_crop_rects(_rng(8), 5, 16, 20))
    np.testing.assert_array_equal(random_resized_crop(x, _rng(8)), want)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_fused_imagenet_transform_equals_the_chain(dtype):
    """uint8 batches take the native pass where the library built, anything
    else takes NumPy: either way the chain's bits."""
    from mgwfbp_tpu.data.loader import normalize_images

    mean, std = (0.485, 0.456, 0.406), (0.229, 0.224, 0.225)
    x = np.random.RandomState(4).randint(0, 256, (6, 18, 22, 3)).astype(dtype)
    got = FusedResizedCropFlipNormalize(mean, std)(x, _rng(9))
    want = chain(train_augment("imagenet"), normalize_images(mean, std))(
        x, _rng(9))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_imagenet_loader_augmentation_deterministic_per_epoch():
    rs = np.random.RandomState(0)
    ds = ArrayDataset(
        rs.randint(0, 256, (64, 12, 12, 3)).astype(np.uint8),
        rs.randint(0, 10, 64),
        10,
    )
    tf = FusedResizedCropFlipNormalize((0.5,) * 3, (0.25,) * 3)
    loader = ShardedLoader(ds, 16, shuffle=True, seed=7, transform=tf)
    loader.set_epoch(0)
    a0 = [x.copy() for x, _ in loader]
    loader.set_epoch(0)
    a0b = [x.copy() for x, _ in loader]
    loader.set_epoch(1)
    a1 = [x.copy() for x, _ in loader]
    for u, v in zip(a0, a0b):
        np.testing.assert_array_equal(u, v)
    assert any(not np.array_equal(u, v) for u, v in zip(a0, a1))
    # load_batch names a batch by (seed, epoch, rank, index), in any order
    np.testing.assert_array_equal(loader.load_batch(0, 2)[0], a0[2])
