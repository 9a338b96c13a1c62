"""Native C++ data-path kernels: build, bind, and bit-compare against the
NumPy fallback (mgwfbp_tpu/native)."""

import numpy as np
import pytest

from mgwfbp_tpu import native
from mgwfbp_tpu.data.augment import FusedCropFlipNormalize, crop_at_offsets

MEAN = np.asarray([0.49, 0.48, 0.45], np.float32)
STD = np.asarray([0.2, 0.2, 0.2], np.float32)


def _numpy_reference(x, ys, xs, flips, pad):
    out = crop_at_offsets(x, ys, xs, pad)
    out[flips] = out[flips, :, ::-1]
    scale = (1.0 / (255.0 * STD)).astype(np.float32)
    shift = (MEAN / STD).astype(np.float32)
    return out.astype(np.float32) * scale - shift


def test_native_builds_and_matches_numpy():
    if not native.available():
        pytest.skip("no C++ toolchain in this environment")
    rs = np.random.RandomState(0)
    x = rs.randint(0, 256, size=(6, 32, 32, 3)).astype(np.uint8)
    ys = rs.randint(0, 9, size=6)
    xs = rs.randint(0, 9, size=6)
    flips = rs.rand(6) < 0.5
    got = native.fused_crop_flip_normalize(
        x, ys, xs, flips.astype(np.uint8), MEAN, STD, 4
    )
    want = _numpy_reference(x, ys, xs, flips, 4)
    np.testing.assert_array_equal(got, want)  # same affine -> same bits


def test_native_normalize_matches():
    if not native.available():
        pytest.skip("no C++ toolchain in this environment")
    rs = np.random.RandomState(1)
    x = rs.randint(0, 256, size=(4, 8, 8, 3)).astype(np.uint8)
    got = native.normalize_u8(x, MEAN, STD)
    scale = (1.0 / (255.0 * STD)).astype(np.float32)
    shift = (MEAN / STD).astype(np.float32)
    want = x.astype(np.float32) * scale - shift
    np.testing.assert_array_equal(got, want)


def test_fused_transform_native_equals_fallback(monkeypatch):
    """The loader transform must produce the same bytes whether or not the
    native library loaded (same rng draw order on both paths)."""
    if not native.available():
        pytest.skip("no C++ toolchain in this environment")
    tf = FusedCropFlipNormalize(MEAN, STD, pad=4)
    rs = np.random.RandomState(2)
    x = rs.randint(0, 256, size=(5, 32, 32, 3)).astype(np.uint8)
    a = tf(x, np.random.default_rng([9]))  # native path
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", True)
    b = tf(x, np.random.default_rng([9]))  # numpy fallback, same seed
    np.testing.assert_array_equal(a, b)  # bit-identical paths
    assert a.dtype == np.float32 and a.shape == x.shape


def test_fused_transform_fallback_without_native(monkeypatch):
    import mgwfbp_tpu.native as nat

    monkeypatch.setattr(nat, "_LIB", None)
    monkeypatch.setattr(nat, "_TRIED", True)
    tf = FusedCropFlipNormalize(MEAN, STD, pad=4)
    rs = np.random.RandomState(3)
    x = rs.randint(0, 256, size=(3, 32, 32, 3)).astype(np.uint8)
    out = tf(x, np.random.default_rng([4]))
    assert out.dtype == np.float32 and out.shape == x.shape
    assert np.isfinite(out).all()


# --------------------------------------------------------------------------
# ImageNet's fused transform and the division-free normalize_u8 (ISSUE 27)
# --------------------------------------------------------------------------

from mgwfbp_tpu.data.augment import (  # noqa: E402
    FusedResizedCropFlipNormalize,
    chain,
    random_hflip,
    resized_crop_at,
    sample_crop_rects,
    train_augment,
)
from mgwfbp_tpu.data.loader import (  # noqa: E402
    ArrayDataset,
    PrefetchLoader,
    ShardedLoader,
    normalize_images,
)

needs_native = pytest.mark.skipif(
    not native.available(), reason="no C++ toolchain in this environment")


def _stats(c):
    mean = np.resize(np.asarray([0.485, 0.456, 0.406], np.float32), c)
    std = np.resize(np.asarray([0.229, 0.224, 0.225], np.float32), c)
    return mean, std


def _images(seed, b, h, w, c):
    rs = np.random.RandomState(seed)
    return rs.randint(0, 256, size=(b, h, w, c)).astype(np.uint8)


def _composition(c):
    """The plain reference: NumPy's RandomResizedCrop, then the flip, then
    normalize, each a pass of its own (what the loader ran before the
    fused kernel)."""
    return chain(train_augment("imagenet"), normalize_images(*_stats(c)))


def _rects_reference(x, top, left, ch, cw, flips, c):
    mean, std = _stats(c)
    out = resized_crop_at(x, *(np.asarray(a, np.int64) for a in
                               (top, left, ch, cw)))
    flips = np.asarray(flips, bool)
    out[flips] = out[flips, :, ::-1]
    scale = (1.0 / (255.0 * std)).astype(np.float32)
    shift = (mean / std).astype(np.float32)
    return out * scale - shift


@needs_native
@pytest.mark.parametrize("seed", [0, 1, 2147483659])
@pytest.mark.parametrize("c", [1, 3])
@pytest.mark.parametrize("hw", [(224, 224), (299, 299), (17, 31)])
def test_fused_rrc_equals_the_numpy_composition_bit_for_bit(hw, c, seed):
    x = _images(seed % 1000, 5, *hw, c)
    got = FusedResizedCropFlipNormalize(*_stats(c))(
        x, np.random.default_rng([seed, 3]))
    want = _composition(c)(x, np.random.default_rng([seed, 3]))
    assert got.dtype == want.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def _forced(h, w):
    """(name, top, left, ch, cw): the whole image, crops one pixel wide and
    one pixel high, one pixel in all, and crops touching each border."""
    return [
        ("whole", 0, 0, h, w),
        ("one-wide", 2, w // 2, h - 3, 1),
        ("one-high", h // 2, 1, 1, w - 2),
        ("one-pixel", h - 1, w - 1, 1, 1),
        ("top-left", 0, 0, h // 2, w // 3),
        ("bottom-right", h - h // 3, w - w // 2, h // 3, w // 2),
        ("left-edge-full-height", 0, 0, h, 2),
        ("bottom-edge-full-width", h - 2, 0, 2, w),
    ]


@needs_native
@pytest.mark.parametrize("flip", [0, 1])
@pytest.mark.parametrize("c", [1, 3])
@pytest.mark.parametrize("hw", [(224, 224), (17, 31)])
@pytest.mark.parametrize(
    "rect", range(8), ids=[r[0] for r in _forced(17, 31)])
def test_fused_rrc_at_forced_rectangles(rect, hw, c, flip):
    h, w = hw
    _, top, left, ch, cw = _forced(h, w)[rect]
    x = _images(rect, 2, h, w, c)
    # the second image takes the same rectangle with the other flip
    rects = [np.full(2, v, np.int64) for v in (top, left, ch, cw)]
    flips = np.asarray([flip, 1 - flip], np.uint8)
    got = native.fused_rrc_flip_normalize(x, *rects, flips, *_stats(c))
    want = _rects_reference(x, *rects, flips, c)
    np.testing.assert_array_equal(got, want)
    if (ch, cw) == (h, w):  # the whole image resizes to itself
        plain = normalize_images(*_stats(c))(x)
        kept, flipped = flip, 1 - flip  # flips is [flip, 1 - flip]
        np.testing.assert_array_equal(got[kept], plain[kept])
        np.testing.assert_array_equal(got[flipped], plain[flipped][:, ::-1])


@needs_native
def test_fused_rrc_takes_up_to_16_channels_and_refuses_more():
    x = _images(4, 2, 9, 11, 16)
    rects = sample_crop_rects(np.random.default_rng([5]), 2, 9, 11)
    flips = np.asarray([1, 0], np.uint8)
    got = native.fused_rrc_flip_normalize(x, *rects, flips, *_stats(16))
    np.testing.assert_array_equal(
        got, _rects_reference(x, *rects, flips, 16))
    wide = _images(4, 2, 9, 11, 17)
    assert native.fused_rrc_flip_normalize(
        wide, *rects, flips, *_stats(17)) is None
    assert native.fused_rrc_flip_normalize(
        x.astype(np.float32), *rects, flips, *_stats(16)) is None


@needs_native
@pytest.mark.parametrize("bad", [
    dict(top=-1), dict(left=-1), dict(ch=0), dict(cw=0),
    dict(top=5, ch=5), dict(left=6, cw=6),
])
def test_fused_rrc_refuses_a_rectangle_outside_the_image(bad):
    x = _images(6, 1, 9, 11, 3)
    rect = dict(top=1, left=1, ch=4, cw=4)
    rect.update(bad)
    with pytest.raises(ValueError, match="outside the image"):
        native.fused_rrc_flip_normalize(
            x, *([v] for v in rect.values()), [0], *_stats(3))


@pytest.mark.parametrize("seed", [0, 7, 4294967311])
def test_fused_rrc_draws_the_compositions_rectangles_and_flips(seed):
    """A seed names the same crops as before: the fused transform draws what
    `random_resized_crop` and then `random_hflip` draw from the same
    Generator, and leaves it in the same state."""
    b, h, w = 32, 24, 40
    rng = np.random.default_rng([seed])
    rects = sample_crop_rects(rng, b, h, w)
    flips = rng.random(b) < 0.5
    after = rng.bit_generator.state
    x = _images(1, b, h, w, 3)
    ref_rng = np.random.default_rng([seed])
    cropped = random_hflip(
        train_augment("imagenet").stages[0](x, ref_rng), ref_rng)
    assert ref_rng.bit_generator.state == after
    mine = resized_crop_at(x, *rects)
    mine[flips] = mine[flips, :, ::-1]
    np.testing.assert_array_equal(mine, cropped)
    top, left, ch, cw = rects
    assert ((top >= 0) & (left >= 0) & (ch >= 1) & (cw >= 1)).all()
    assert ((top + ch <= h) & (left + cw <= w)).all()
    assert flips.any() and not flips.all()


@needs_native
@pytest.mark.parametrize("shape", [
    (4, 8, 8, 3),        # 768: whole blocks of 16 pixels
    (3, 7, 5, 3),        # 315: a tail shorter than a block
    (2, 5, 3, 4),
    (5, 28, 28, 1),      # MNIST
    (1, 1, 1, 3),        # less than one block
    (7, 13, 16),         # no batch axis: channel is still the last one
    (0, 4, 4, 3),        # nothing to do
])
def test_normalize_u8_without_its_division_holds_the_bits(shape):
    c = shape[-1]
    rs = np.random.RandomState(sum(shape))
    x = rs.randint(0, 256, size=shape).astype(np.uint8)
    mean, std = _stats(c)
    got = native.normalize_u8(x, mean, std)
    scale = (1.0 / (255.0 * std)).astype(np.float32)
    shift = (mean / std).astype(np.float32)
    assert got.dtype == np.float32 and got.shape == x.shape
    np.testing.assert_array_equal(got, x.astype(np.float32) * scale - shift)


@needs_native
def test_normalize_u8_takes_a_strided_view():
    x = _images(8, 4, 10, 12, 3)[:, ::2, 1:7]
    got = native.normalize_u8(x, *_stats(3))
    np.testing.assert_array_equal(
        got, normalize_images(*_stats(3))(np.ascontiguousarray(x)))


@pytest.mark.parametrize("transform", ["fused_rrc", "normalize"])
def test_imagenet_transforms_fall_back_to_the_same_bits(
        transform, monkeypatch):
    """With `get_lib` answering None the NumPy path runs, counts no native
    pass, and gives what the kernel gives."""
    x = _images(9, 6, 20, 28, 3)
    if transform == "fused_rrc":
        tf = FusedResizedCropFlipNormalize(*_stats(3))
        run = lambda: tf(x, np.random.default_rng([11]))  # noqa: E731
        want = _composition(3)(x, np.random.default_rng([11]))
    else:
        tf = normalize_images(*_stats(3))
        run = lambda: tf(x)  # noqa: E731
        mean, std = _stats(3)
        want = (x.astype(np.float32) * (1.0 / (255.0 * std)).astype(np.float32)
                - (mean / std).astype(np.float32))
    with_lib = run() if native.available() else None
    before = native.passes()
    monkeypatch.setattr(native, "get_lib", lambda: None)
    without = run()
    assert native.passes() == before
    assert without.dtype == np.float32
    np.testing.assert_array_equal(without, want)
    if with_lib is not None:
        np.testing.assert_array_equal(with_lib, without)


def _augmenting_loader(transform=None):
    x = _images(10, 48, 20, 24, 3)
    ds = ArrayDataset(x, np.arange(48) % 10, 10)
    return ShardedLoader(
        ds, 8, shuffle=True, seed=5,
        transform=transform or FusedResizedCropFlipNormalize(*_stats(3)))


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_prefetch_over_the_augmenting_loader_equals_inline(workers):
    inner = _augmenting_loader()
    inner.set_epoch(2)
    inline = [(x.copy(), y.copy()) for x, y in inner]
    pf = PrefetchLoader(_augmenting_loader(), workers=workers)
    pf.set_epoch(2)
    seen = 0
    for (x, y), (xi, yi) in zip(pf, inline):
        np.testing.assert_array_equal(x, xi)
        np.testing.assert_array_equal(y, yi)
        assert pf.native_batch() == int(native.available())
        seen += 1
    assert seen == len(inline) == 6
    assert pf.native_batch() is None  # the pool is gone with the epoch


def test_prefetch_says_which_batches_took_the_fallback(monkeypatch):
    """`native_batch` is per batch and per thread: a transform that is
    plain NumPy reads 0 beside a pool that could have run the kernel."""
    pf = PrefetchLoader(
        _augmenting_loader(lambda x: x.astype(np.float32)), workers=2)
    assert pf.native_batch() is None  # no pool yet
    for _ in pf:
        assert pf.native_batch() == 0
    bare = PrefetchLoader(_augmenting_loader(), workers=0)
    for _ in bare:
        assert bare.native_batch() is None  # no pool at all
    monkeypatch.setattr(native, "get_lib", lambda: None)
    pf = PrefetchLoader(_augmenting_loader(), workers=2)
    for _ in pf:
        assert pf.native_batch() == 0


@needs_native
def test_the_binding_releases_the_gil():
    """`ctypes.CDLL` drops the GIL round a call, `PyDLL` would keep it: the
    pool's workers run their kernels side by side only with the first."""
    import ctypes

    lib = native.get_lib()
    assert isinstance(lib, ctypes.CDLL) and not isinstance(lib, ctypes.PyDLL)
    for name in ("fused_rrc_flip_normalize", "fused_crop_flip_normalize",
                 "normalize_u8"):
        fn = getattr(lib, name)
        assert fn.argtypes and fn.restype is None


def test_data_prepare_hands_imagenet_the_fused_transform():
    from mgwfbp_tpu.data import data_prepare

    bundle = data_prepare(
        "imagenet", batch_size=4, synthetic=True, image_hw=(16, 16))
    inner = getattr(bundle.train, "inner", bundle.train)
    assert isinstance(inner.transform, FusedResizedCropFlipNormalize)
    x, _ = inner.load_batch(0, 0)
    assert x.dtype == np.float32 and x.shape == (4, 16, 16, 3)
    want = _composition(3)(
        inner.dataset.data[inner._epoch_indices(0)[:4]],
        np.random.default_rng([inner.seed, 0, inner.shard.rank, 0]))
    np.testing.assert_array_equal(x, want)
    plain = data_prepare(
        "imagenet", batch_size=4, synthetic=True, image_hw=(16, 16),
        augment=False)
    assert not isinstance(
        getattr(plain.train, "inner", plain.train).transform,
        FusedResizedCropFlipNormalize)


# --------------------------------------------------------------------------
# the pool's recycled output arrays (ISSUE 27, item 3)
# --------------------------------------------------------------------------

from mgwfbp_tpu.data.loader import _OutputRing  # noqa: E402


def _address(a):
    return a.__array_interface__["data"][0]


def _plain_loader(batches=24):
    x = _images(12, 4 * batches, 10, 12, 3)
    ds = ArrayDataset(x, np.arange(len(x)) % 10, 10)
    return ShardedLoader(
        ds, 4, shuffle=True, seed=3, transform=normalize_images(*_stats(3)))


def test_ring_hands_a_block_out_again_only_when_nothing_refers_to_it():
    ring = _OutputRing(2)
    a = ring.take((2, 3))
    b = ring.take((2, 3))
    assert a.dtype == b.dtype == np.float32 and a.shape == (2, 3)
    assert a.flags.writeable and a.flags.c_contiguous
    assert _address(a) != _address(b)
    c = ring.take((2, 3))  # every block is out: a fresh array, not a wait
    assert c.base is None and _address(c) not in (_address(a), _address(b))
    kept, where = a[1:], _address(a)
    del a  # a view still refers to the block
    assert _address(ring.take((2, 3))) not in (where, _address(b))
    del kept
    again = ring.take((2, 3))
    assert _address(again) == where
    # a new batch size replaces the block it finds
    del again, b
    other = ring.take((5, 3))
    assert other.shape == (5, 3) and other.base is not None
    del other
    assert ring.take((5, 3)).base is not None


def test_a_device_put_keeps_its_block_out_of_the_ring():
    """What `place` relies on: the array `jax.device_put` was given stays
    referenced while the copy (on the CPU backend: the alias) needs it."""
    import gc

    import jax

    ring = _OutputRing(1)
    x = ring.take((64, 64))
    x[:] = 7.0
    where = _address(x)
    on_device = jax.device_put(x)
    del x
    gc.collect()
    other = ring.take((64, 64))
    other[:] = -1.0
    np.testing.assert_array_equal(np.asarray(on_device), np.full((64, 64), 7.0))
    on_device.block_until_ready()
    del on_device, other
    gc.collect()
    assert _address(ring.take((64, 64))) == where  # and comes back after


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_a_held_batch_survives_two_turns_of_the_ring(workers):
    inline = [(x.copy(), y.copy()) for x, y in _plain_loader()]
    pf = PrefetchLoader(_plain_loader(), workers=workers)
    turns = 2 * (workers + pf.depth + 2)
    assert len(inline) >= turns + 2
    held, addresses = {}, set()
    for i, (x, y) in enumerate(pf):
        if i == 0:
            held[i] = x           # the array itself
        elif i == 1:
            held[i] = x[1:3]      # a view is enough to keep the block
        addresses.add(_address(x))
        np.testing.assert_array_equal(x, inline[i][0])
        del x
    np.testing.assert_array_equal(held[0], inline[0][0])
    np.testing.assert_array_equal(held[1], inline[1][0][1:3])
    if native.available():
        # the blocks went round (how many fresh arrays the two held blocks
        # forced depends on the threads' timing)
        assert len(addresses) < len(inline)


def test_load_batch_called_directly_returns_an_array_of_its_own():
    inner = _plain_loader()
    x, _ = inner.load_batch(0, 0)
    assert x.base is None and x.flags.owndata
    pf = PrefetchLoader(_plain_loader(), workers=2)
    first = next(iter(pf))[0]
    np.testing.assert_array_equal(first, x)
    assert (first.base is not None) == native.available()


def test_ring_never_lends_one_block_twice_under_contention():
    """More threads than cores, a short switch interval: every thread
    stamps the block it took and must find its stamp when it looks again."""
    import sys
    import threading
    import time

    ring = _OutputRing(4)
    errors, deadline = [], time.monotonic() + 20.0
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def worker(k):
        for i in range(300):
            if time.monotonic() > deadline:
                errors.append("timeout")
                return
            a = ring.take((32, 32))
            stamp = float(k * 1000 + i)
            a[:] = stamp
            time.sleep(0)
            if not (a == stamp).all():
                errors.append((k, i))
            del a

    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert len(ring._free) == 4  # every block came home


@needs_native
@pytest.mark.parametrize("bad", [
    np.empty((4, 10, 12, 3), np.float64),
    np.empty((3, 10, 12, 3), np.float32),
    np.empty((4, 10, 12, 6), np.float32)[..., ::2],
])
def test_kernels_refuse_an_output_they_cannot_fill(bad):
    x = _images(13, 4, 10, 12, 3)
    with pytest.raises(ValueError, match="out must be"):
        native.normalize_u8(x, *_stats(3), out=bad)
    rects = sample_crop_rects(np.random.default_rng([1]), 4, 10, 12)
    with pytest.raises(ValueError, match="out must be"):
        native.fused_rrc_flip_normalize(
            x, *rects, np.zeros(4, np.uint8), *_stats(3), out=bad)


def test_get_lib_makes_late_callers_wait_for_the_build(monkeypatch):
    """The pool's workers all ask at once on a fresh checkout: none may be
    told "no library" while the first is still building it."""
    import threading
    import time

    built = object()
    calls = []

    def slow_load():
        calls.append(1)
        time.sleep(0.3)
        return built

    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.setattr(native, "_load", slow_load)
    got = []
    threads = [threading.Thread(target=lambda: got.append(native.get_lib()))
               for _ in range(4)]
    threads[0].start()
    time.sleep(0.05)  # the build is under way
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
    assert not any(t.is_alive() for t in threads)
    assert got == [built] * 4 and calls == [1]
