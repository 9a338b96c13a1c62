"""ops/selscan.py against the literal recurrence h_t = exp(dt_t (x) A) .
h_{t-1} + (dt_t x_t) (x) B_t, y_t = h_t C_t, one position at a time, down
both of its ways: the plain chunked form (what `selective_scan` is on the
CPU) and the two kernels with the state in VMEM (Pallas `interpret` mode,
called outright). Outputs, final state and every input's gradient ("both
gradients": of y and of the final state); T a multiple of the chunk and not,
one block of positions and several, one strip of channels and several;
bfloat16 inputs computed in float32 inside; a decay so strong that a factor
1 / exp(L_s) would overflow; the states the forward kernel saves for the
backward one; and the test of platform and shape that chooses between the
two, with what each call notes (`ops/programs.py`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.ops import programs, selscan
from mgwfbp_tpu.ops.selscan import selective_scan


def literal(x, dt, a, b, c):
    """(y (B, T, D), final state (B, D, N)) by the recurrence."""
    def step(h, inp):
        xt, dtt, bt, ct = inp  # (B, D), (B, D), (B, N), (B, N)
        h = jnp.exp(dtt[..., None] * a) * h \
            + (dtt * xt)[..., None] * bt[:, None, :]
        return h, jnp.sum(h * ct[:, None, :], axis=-1)

    h0 = jnp.zeros((x.shape[0], x.shape[2], a.shape[1]))
    h, y = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), h


def draws(seed, t, bsz=2, d=6, n=4, decay=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (bsz, t, d))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, t, d)) - 1.0)
    a = -decay * jnp.exp(jax.random.uniform(k[2], (d, n), minval=0.0, maxval=2.0))
    b = jax.random.normal(k[3], (bsz, t, n))
    c = jax.random.normal(k[4], (bsz, t, n))
    return x, dt, a, b, c


def weighted(fn, args, seed=9):
    """Scalar of fn's outputs under fixed random weights, so that one
    gradient exercises y and the final state together."""
    y, h = fn(*args)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jnp.sum(y * jax.random.normal(k1, y.shape))
            + jnp.sum(h * jax.random.normal(k2, h.shape)))


# what `draws` makes for a path: the kernels take whole lane tiles of
# channels and whole sublane tiles of states
WIDTHS = {"plain": dict(d=6, n=4), "kernel": dict(d=256, n=8)}


def scan(path, sizes):
    """`selective_scan`'s signature down one way: the chunked form at
    `sizes` = (chunk, block), or the kernels, interpreted, at (positions a
    block, channels a strip)."""
    if path == "plain":
        return lambda *v: selective_scan(*v, chunk=sizes[0], block=sizes[1])

    def kernels(x, dt, a, b, c):
        y, last = selscan._kernel_scan(
            x, dt, a.T, b, c, selscan.Tiles(*sizes), True)
        return y, jnp.swapaxes(last, 1, 2)

    return kernels


ALL = (0, 1, 2, 3, 4)


@pytest.mark.parametrize("path,t,sizes", [
    ("plain", 32, (8, 2)),    # whole chunks, two blocks of two
    ("plain", 29, (8, 8)),    # a short last chunk, one block
    ("plain", 40, (16, 2)),   # three chunks: the block shrinks to one that divides
    ("plain", 7, (16, 4)),    # shorter than one chunk
    ("kernel", 32, (8, 128)),   # four blocks of positions, two strips
    ("kernel", 24, (8, 256)),   # three blocks, one strip of two lane tiles
    ("kernel", 48, (16, 128)),  # two turns of the loop a block
    ("kernel", 8, (8, 128)),    # one block: the first is the last
])
def test_float32_matches_the_recurrence_forward_and_gradients(path, t, sizes):
    args = draws(t, t, **WIDTHS[path])
    fn = scan(path, sizes)
    y, h = fn(*args)
    want_y, want_h = literal(*args)
    assert y.dtype == h.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *v: weighted(fn, v), argnums=ALL)(*args)
    want = jax.grad(lambda *v: weighted(literal, v), argnums=ALL)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("path,sizes,n", [
    ("plain", (8, 2), 4),
    ("kernel", (8, 128), 16),  # a bfloat16 tile has 16 sublanes
])
def test_bfloat16_inputs_are_computed_in_float32(path, sizes, n):
    """x, B and C in bfloat16 (dt and A float32, as the mixer hands them):
    the result is the float32 scan of the rounded inputs (exactly in the
    chunked form; the interpreted kernels' two programs are compiled apart,
    and differ by float32's last digits, a thousandth of a bfloat16 step),
    and the gradients come back in the inputs' dtypes."""
    x, dt, a, b, c = draws(3, 24, **{**WIDTHS[path], "n": n})
    low = tuple(v.astype(jnp.bfloat16) for v in (x, b, c))
    fn = scan(path, sizes)
    y, h = fn(low[0], dt, a, low[1], low[2])
    want_y, want_h = fn(
        *(v.astype(jnp.float32) for v in (low[0], dt, a, low[1], low[2])))
    assert y.dtype == jnp.float32
    tol = 0.0 if path == "plain" else 4e-6
    np.testing.assert_allclose(y, want_y, rtol=tol, atol=tol)
    np.testing.assert_allclose(h, want_h, rtol=tol, atol=tol)
    grads = jax.grad(lambda *v: weighted(fn, v), argnums=ALL)(
        low[0], dt, a, low[1], low[2])
    assert [g.dtype for g in grads] == [
        jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16]


@pytest.mark.parametrize("path,sizes", [
    ("plain", (16, 2)), ("kernel", (16, 128))])
def test_a_decay_past_float32s_range_stays_finite(path, sizes):
    """dt x A down to -40 a step: over a chunk of 16 the decay passes e^-600,
    whose inverse no float32 holds; every exponent here is <= 0."""
    x, dt, a, b, c = draws(5, 48, decay=40.0, **WIDTHS[path])
    assert float((dt[..., None] * a).min()) * 16 < -200
    fn = scan(path, sizes)
    y, h = fn(x, dt, a, b, c)
    want_y, want_h = literal(x, dt, a, b, c)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
    grads = jax.grad(lambda *v: weighted(fn, v), argnums=ALL)(x, dt, a, b, c)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


@pytest.mark.parametrize("path,sizes", [
    ("plain", (8, 2)), ("kernel", (8, 128))])
def test_a_cotangent_on_the_final_state_alone(path, sizes):
    """The loss reads the state after the last position and nothing of y:
    every gradient then comes from dh entering after the last position."""
    args = draws(11, 24, **WIDTHS[path])
    w = jax.random.normal(
        jax.random.PRNGKey(2), (2, *args[2].shape))

    def of(fn):
        return jax.grad(
            lambda *v: jnp.sum(fn(*v)[1] * w), argnums=ALL)(*args)

    got, want = of(scan(path, sizes)), of(literal)
    assert float(jnp.abs(want[4]).max()) == 0  # C is read by y alone
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    assert all(float(jnp.abs(g).max()) > 0 for g in got[:4])


def test_the_kernel_saves_the_state_each_block_starts_from():
    """Two sequences, four blocks of eight positions, two strips: each
    sequence's state is carried from block to block on its own, and what the
    forward kernel hands the backward one is the literal recurrence's state
    before each block's first position."""
    x, dt, a, b, c = draws(13, 32, **WIDTHS["kernel"])
    y, last, starts = selscan._forward_kernel(
        x, dt, a.T, b, c, tiles=selscan.Tiles(8, 128), interpret=True)
    assert starts.shape == (2, 4, 8, 256)
    np.testing.assert_array_equal(starts[:, 0], 0.0)
    for block in (1, 2, 3):
        _, want = literal(*(
            v[:, :8 * block] for v in (x, dt)), a, b[:, :8 * block],
            c[:, :8 * block])
        np.testing.assert_allclose(
            starts[:, block], jnp.swapaxes(want, 1, 2), rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(starts[0, 3] - starts[1, 3]).max()) > 1e-2
    want_y, want_h = literal(x, dt, a, b, c)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        last, jnp.swapaxes(want_h, 1, 2), rtol=2e-5, atol=2e-5)


def test_the_kernels_tiles_follow_the_shape():
    bf16, f32 = jnp.bfloat16, jnp.float32
    # the Phi-4-mini-flash cell's scan
    tiles = selscan._kernel_tiles(8192, 5120, 16, (bf16, bf16, bf16))
    assert tiles is not None and 8192 % tiles.rows == 0
    assert 5120 % tiles.cols == 0 and tiles.cols % 128 == 0
    assert selscan._kernel_tiles(8192, 384, 8, (f32, f32, f32)).cols == 128
    # T no multiple of the block of positions; channels no whole lane tiles;
    # states no whole sublane tiles, of float32 and of bfloat16; a dtype
    # without a tile
    assert selscan._kernel_tiles(8192 + 8, 5120, 16, (bf16,) * 3) is None
    assert selscan._kernel_tiles(8192, 5120 + 64, 16, (bf16,) * 3) is None
    assert selscan._kernel_tiles(8192, 5120, 4, (f32,) * 3) is None
    assert selscan._kernel_tiles(8192, 5120, 8, (f32, bf16, bf16)) is None
    assert selscan._kernel_tiles(8192, 5120, 16, (jnp.float16,) * 3) is None


def test_a_shape_the_kernels_refuse_falls_to_the_chunked_form(monkeypatch):
    """Traced for a TPU (said so by the test: this process traces for the
    CPU), a T that is no multiple of the block of positions, or channels
    that are no whole lane tiles, go down the chunked form and are counted
    `plain`; a shape that fits is counted `kernel` with its two programs,
    once however many scans of that shape there are."""
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    for t, d, n in ((29, 256, 8), (64, 100, 8), (64, 256, 4)):
        args = draws(t, t, bsz=1, d=d, n=n)
        before = programs.LOWERED.copy()
        y, h = selective_scan(*args, chunk=8, block=2)
        assert programs.lowered_since(before)["scan"] == {
            "kernel": 0, "plain": 1, "programs": 0}
        want_y, want_h = literal(*args)
        np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
    # nothing runs: a kernel traced for a TPU cannot on the CPU
    args = draws(1, 2 * selscan._ROWS, bsz=1, d=256, n=8)
    before = programs.LOWERED.copy()
    (y, h), _ = jax.eval_shape(
        lambda *v: (selective_scan(*v), selective_scan(*v)), *args)
    assert (y.shape, h.shape) == ((1, 2 * selscan._ROWS, 256), (1, 256, 8))
    assert programs.lowered_since(before)["scan"] == {
        "kernel": 2, "plain": 0, "programs": 2}


def test_off_a_tpu_the_scan_is_the_chunked_form():
    args = draws(1, 2 * selscan._ROWS, bsz=1, d=256, n=8)
    before = programs.LOWERED.copy()
    jaxpr = jax.make_jaxpr(lambda *v: selective_scan(*v))(*args)
    assert "pallas_call" not in str(jaxpr)
    assert programs.lowered_since(before)["scan"] == {
        "kernel": 0, "plain": 1, "programs": 0}
