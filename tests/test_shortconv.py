"""ops/shortconv.py: the short causal convolution with its SiLU.

The kernels run here under Pallas `interpret=True` (the CPU), against the
plain form under `jax.jit`, as the models call it: the forward values equal
to the last bit, the gradients against the definition in float64 (autodiff of
the plain form rounds g to x's dtype on its way; the kernel keeps it
float32). The shapes cross every edge the kernels have: two sequences (a
block at a sequence's start reads zeros, not its neighbour), one block of
positions and several, one strip of channels and several with uneven groups
of lane tiles, K 4 and K 2, with and without bias, bfloat16 and float32. That
the kernels compile for a v5e is tests/test_tpu_compile_shortconv.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from mgwfbp_tpu import models as zoo
from mgwfbp_tpu.ops import programs, shortconv
from mgwfbp_tpu.parallel.mesh import DATA_AXIS
from mgwfbp_tpu.train import create_train_state, make_train_step

ROWS = shortconv._ROWS


def _arguments(b, t, c, k, dtype, bias, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (b, t, c), jnp.float32).astype(dtype)
    w = jax.random.uniform(keys[1], (k, c), jnp.float32, -0.5, 0.5)
    bias = jax.random.uniform(
        keys[2], (c,), jnp.float32, -0.5, 0.5) if bias else None
    dy = jax.random.normal(keys[3], (b, t, c), jnp.float32).astype(dtype)
    return x, w, bias, dy


def _gradients_by_definition(x, w, bias, dy):
    """d x, d w, d bias in float64: g = dy . silu'(the pre-activation rounded
    to x's dtype), d x the taps' transpose over g, d w[i] = sum g x[. - (K -
    1) + i], d bias = sum g; zeros before a sequence's first position and
    after its last."""
    k, t = w.shape[0], x.shape[1]
    x64, w64 = np.asarray(x, np.float64), np.asarray(w, np.float64)
    padded = np.pad(x64, ((0, 0), (k - 1, 0), (0, 0)))
    pre = sum(padded[:, i:i + t] * w64[i] for i in range(k))
    if bias is not None:
        pre = pre + np.asarray(bias, np.float64)
    pre = np.asarray(
        jnp.asarray(pre, jnp.float32).astype(x.dtype), np.float64)
    sig = 1.0 / (1.0 + np.exp(-pre))
    g = np.asarray(dy, np.float64) * sig * (1.0 + pre * (1.0 - sig))
    after = np.pad(g, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(after[:, j:j + t] * w64[k - 1 - j] for j in range(k))
    dw = np.stack([np.sum(g * padded[:, i:i + t], axis=(0, 1))
                   for i in range(k)])
    return dx, dw, np.sum(g, axis=(0, 1))


@pytest.mark.parametrize("b,t,c,k,dtype,bias", [
    (2, 2 * ROWS, 256, 4, jnp.bfloat16, True),
    # 21 lane tiles: three strips of seven, each in groups of four and three
    (2, ROWS, 2688, 4, jnp.bfloat16, False),
    (2, 3 * ROWS, 128, 2, jnp.bfloat16, False),
    (2, 2 * ROWS, 128, 2, jnp.float32, True),
    (1, ROWS, 128, 4, jnp.float32, False),
])
def test_the_kernels_are_the_plain_form(b, t, c, k, dtype, bias):
    x, w, bias, dy = _arguments(b, t, c, k, dtype, bias)
    tiles = shortconv._kernel_tiles(t, c, k, jnp.dtype(dtype))
    assert tiles is not None and t % tiles.rows == 0 and c % tiles.cols == 0
    y, pull = jax.vjp(
        lambda *v: shortconv._kernel_conv(*v, tiles, True), x, w, bias)
    want, pull_plain = jax.vjp(jax.jit(shortconv.plain_conv_silu), x, w, bias)
    assert y.dtype == want.dtype == x.dtype
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(want, np.float32))
    dx, dw, db = pull(dy)
    assert dx.dtype == x.dtype and dw.dtype == jnp.float32
    want_dx, want_dw, want_db = _gradients_by_definition(x, w, bias, dy)
    # d x to the rounding of x's dtype, element by element (float32: to the
    # few roundings of its sums)
    eps = max(float(jnp.finfo(dtype).eps), 1e-5)
    np.testing.assert_allclose(
        np.asarray(dx, np.float64), want_dx, rtol=eps,
        atol=eps * float(np.abs(want_dx).max()) * 1e-2)
    assert np.linalg.norm(np.asarray(dw, np.float64) - want_dw) \
        <= 1e-5 * np.linalg.norm(want_dw)
    if bias is None:
        assert db is None
    else:
        assert db.shape == bias.shape
        assert np.linalg.norm(np.asarray(db, np.float64) - want_db) \
            <= 1e-5 * np.linalg.norm(want_db)
    # and the plain form's own autodiff, which rounds g to x's dtype
    for got, plain in zip((dx, dw, db), pull_plain(dy)):
        if got is not None:
            got, plain = (np.asarray(v, np.float64) for v in (got, plain))
            assert np.linalg.norm(got - plain) <= 4 * eps * np.linalg.norm(
                plain)


def test_a_sequence_never_reads_the_one_before_it():
    """The second sequence's first K - 1 outputs, and the first sequence's
    last K - 1 gradients, are what they are with the other sequence gone."""
    x, w, bias, dy = _arguments(2, ROWS, 128, 4, jnp.bfloat16, True, seed=3)
    tiles = shortconv._kernel_tiles(ROWS, 128, 4, jnp.dtype(jnp.bfloat16))

    def both(x, dy):
        y, pull = jax.vjp(
            lambda v: shortconv._kernel_conv(v, w, bias, tiles, True), x)
        return y, pull(dy)[0]

    y, dx = both(x, dy)
    for s in range(2):
        y_s, dx_s = both(x[s:s + 1], dy[s:s + 1])
        np.testing.assert_array_equal(
            np.asarray(y[s], np.float32), np.asarray(y_s[0], np.float32))
        np.testing.assert_array_equal(
            np.asarray(dx[s], np.float32), np.asarray(dx_s[0], np.float32))


def test_the_rule_refuses_what_the_kernels_do_not_take(monkeypatch):
    """C no whole number of lane tiles, a T the block does not divide, more
    taps than a sublane tile, a dtype that is neither: the plain form, and
    the call's note says so; off a TPU the plain form whatever the shape."""
    bf16 = jnp.dtype(jnp.bfloat16)
    assert shortconv._kernel_tiles(8192, 8192, 4, bf16) == (ROWS, 2048)
    assert shortconv._kernel_tiles(8192, 4352, 4, bf16) == (ROWS, 2176)
    assert shortconv._kernel_tiles(8192, 5120, 4, bf16) == (ROWS, 2560)
    assert shortconv._kernel_tiles(ROWS, 128, 1, jnp.dtype(jnp.float32))
    assert shortconv._kernel_tiles(ROWS, 96, 4, bf16) is None
    assert shortconv._kernel_tiles(ROWS - 12, 128, 4, bf16) is None
    assert shortconv._kernel_tiles(ROWS, 128, 9, bf16) is None
    assert shortconv._kernel_tiles(ROWS, 128, 4, jnp.dtype(jnp.float16)) is None

    def went(t, c, k=4):
        x, w, bias, _ = _arguments(2, t, c, k, jnp.bfloat16, True)
        before = programs.LOWERED.copy()
        y = shortconv.causal_conv_silu(x, w, bias)
        np.testing.assert_array_equal(
            np.asarray(y, np.float32),
            np.asarray(shortconv.plain_conv_silu(x, w, bias), np.float32))
        return programs.lowered_since(before)["conv"]

    plain = {"kernel": 0, "plain": 1, "programs": 0}
    assert went(ROWS, 128) == plain  # traced for the CPU
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    assert went(ROWS, 96) == plain
    assert went(ROWS - 12, 128) == plain


@pytest.mark.parametrize("name,share,convolutions", [
    ("granite4h_tiny", {"layers_held": 2}, 2),
    ("phi4flash_tiny", {"layers_held": (0, 3)}, 2),
    ("qwen3next_tiny", {"layers_held": 2, "experts_held": (4, 4)}, 2),
])
def test_a_tiny_preset_counts_its_convolutions(name, share, convolutions):
    """The step of a tiny share traced for the CPU (lowered, not compiled):
    `conv_program` (the step's `traced_programs`) reads 0 + n, a convolution a
    Mamba or Gated DeltaNet layer held. That the losses are the parent
    commit's to the last digit is held where a Trainer runs these presets
    anyway (tests/test_granite_trainer.py, test_phi4flash_trainer.py,
    test_qwen3next_trainer.py)."""
    model, meta = zoo.create_model(name, **share)
    tokens = jnp.zeros((1, 32), jnp.int32)
    tx = optax.sgd(0.1)
    state = jax.eval_shape(lambda: create_train_state(
        jax.random.PRNGKey(0), model, tokens, tx))
    mesh = Mesh(np.asarray(jax.devices()[:1]), (DATA_AXIS,))
    step = make_train_step(model, meta, tx, mesh, None, donate=False)
    step.lower(state, {"x": tokens[None], "y": tokens[None]})
    assert step.traced_programs["conv"] == {
        "kernel": 0, "plain": convolutions, "programs": 0}
