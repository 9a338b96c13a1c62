"""ops/selscan.py against the literal recurrence h_t = exp(dt_t (x) A) .
h_{t-1} + (dt_t x_t) (x) B_t, y_t = h_t C_t, one position at a time: outputs,
final state and every input's gradient ("both gradients": of y and of the
final state); T a multiple of the chunk and not; bfloat16 inputs computed in
float32 inside; and a decay so strong that a factor 1 / exp(L_s) would
overflow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize("t,chunk,block", [
    (32, 8, 2),   # whole chunks, two blocks of two
    (29, 8, 8),   # a short last chunk, one block
    (40, 16, 2),  # three chunks: the block shrinks to one that divides
    (7, 16, 4),   # shorter than one chunk
])
def test_float32_matches_the_recurrence_forward_and_gradients(t, chunk, block):
    args = draws(t, t)
    y, h = selective_scan(*args, chunk=chunk, block=block)
    want_y, want_h = literal(*args)
    assert y.dtype == h.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *v: weighted(
        lambda *w: selective_scan(*w, chunk=chunk, block=block), v),
        argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *v: weighted(literal, v),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_bfloat16_inputs_are_computed_in_float32():
    """x, B and C in bfloat16 (dt and A float32, as the mixer hands them):
    the result is the float32 scan of the rounded inputs, exactly."""
    x, dt, a, b, c = draws(3, 24)
    low = tuple(v.astype(jnp.bfloat16) for v in (x, b, c))
    y, h = selective_scan(low[0], dt, a, low[1], low[2], chunk=8, block=2)
    want_y, want_h = selective_scan(
        *(v.astype(jnp.float32) for v in (low[0], dt, a, low[1], low[2])),
        chunk=8, block=2)
    assert y.dtype == jnp.float32
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(h, want_h)


def test_a_decay_past_float32s_range_stays_finite():
    """dt x A down to -40 a step: over a chunk of 16 the decay passes e^-600,
    whose inverse no float32 holds; every exponent here is <= 0."""
    x, dt, a, b, c = draws(5, 48, decay=40.0)
    assert float((dt[..., None] * a).min()) * 16 < -200
    y, h = selective_scan(x, dt, a, b, c, chunk=16, block=2)
    want_y, want_h = literal(x, dt, a, b, c)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
    grads = jax.grad(lambda *v: weighted(
        lambda *w: selective_scan(*w, chunk=16, block=2), v),
        argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
