"""ops/ssd.py against the literal recurrence S_t = a_t S_{t-1} + dt_t x_t
B_t^T, y_t = S_t C_t, one position at a time: outputs, final state and every
input's gradient; T a multiple of the chunk and not; float32 tight, bfloat16
inputs (float32 decays and state inside) within a stated tolerance; and a
decay so strong that exp(L_t) * exp(-L_s) overflows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.ops.ssd import ssd_scan

HI = jax.lax.Precision.HIGHEST


def literal(x, dt, a, b, c, s0=None):
    """(y (B, T, H, P), final state (B, H, P, N)) by the recurrence."""
    bsz, t, h, p = x.shape
    s0 = jnp.zeros((bsz, h, p, b.shape[-1])) if s0 is None else s0

    def step(s, inp):
        xt, dtt, bt, ct = inp  # (B, H, P), (B, H), (B, N), (B, N)
        s = jnp.exp(dtt * a)[..., None, None] * s + jnp.einsum(
            "bhp,bn->bhpn", xt * dtt[..., None], bt, precision=HI)
        return s, jnp.einsum("bhpn,bn->bhp", s, ct, precision=HI)

    s, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), s


def draws(seed, t, bsz=2, h=3, p=4, n=5, decay=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (bsz, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h)) - 1.0)
    a = -decay * jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.0))
    b = jax.random.normal(k[3], (bsz, t, n))
    c = jax.random.normal(k[4], (bsz, t, n))
    return x, dt, a, b, c


def weighted(fn, args, seed=9):
    """Scalar of fn's outputs under fixed random weights, so that one
    gradient exercises y and the final state together."""
    y, s = fn(*args)[:2]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jnp.sum(y.astype(jnp.float32) * jax.random.normal(k1, y.shape))
            + jnp.sum(s * jax.random.normal(k2, s.shape)))


@pytest.mark.parametrize("t,chunk,block", [
    (32, 8, 2),   # whole chunks, two blocks of two
    (29, 8, 8),   # a short last chunk, one block
    (40, 16, 2),  # three chunks: the block shrinks to one that divides
    (7, 16, 4),   # shorter than one chunk
])
def test_float32_matches_the_recurrence_forward_and_gradients(t, chunk, block):
    args = draws(t, t)
    with jax.default_matmul_precision("highest"):
        y, s, low = ssd_scan(*args, chunk=chunk, block=block)
        want_y, want_s = literal(*args)
        np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(s, want_s, rtol=2e-5, atol=2e-5)
        got = jax.grad(lambda *v: weighted(
            lambda *w: ssd_scan(*w, chunk=chunk, block=block), v),
            argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(lambda *v: weighted(literal, v),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    # the counter: the most negative whole-chunk sum of dt * a
    x, dt, a, _, _ = args
    pad = -t % chunk
    sums = jnp.pad(dt * a, ((0, 0), (0, pad), (0, 0))).reshape(
        2, -1, chunk, 3).sum(axis=2)
    assert float(low) == pytest.approx(float(sums.min()), rel=1e-5)


def test_bfloat16_inputs_keep_float32_decays_and_state():
    """bf16 x, B, C (dt and A stay float32, as the mixer hands them over)
    against the float32 recurrence on the same rounded inputs: the products'
    operands are rounded to bf16 (2^-9 relative each), accumulation and the
    decays are float32, so outputs agree to about 1% of their scale."""
    x, dt, a, b, c = draws(11, 48)
    lo = [v.astype(jnp.bfloat16) for v in (x, b, c)]
    y, s, _ = ssd_scan(lo[0], dt, a, lo[1], lo[2], chunk=16, block=2)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want_y, want_s = literal(
            lo[0].astype(jnp.float32), dt, a, lo[1].astype(jnp.float32),
            lo[2].astype(jnp.float32))
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y.astype(jnp.float32) - want_y).max()) < 0.02 * scale
    assert float(jnp.abs(s - want_s).max()) < 0.02 * float(jnp.abs(want_s).max())
    g = jax.grad(lambda v: jnp.sum(ssd_scan(
        v, dt, a, lo[1], lo[2], chunk=16, block=2)[0].astype(jnp.float32)))(lo[0])
    assert g.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(g)))


def test_a_decay_that_overflows_the_naive_form_stays_finite_and_right():
    """dt * a near -4 a step: over a chunk of 64 the sum passes -250, so
    exp(-L_s) is inf in float32 and exp(L_t) * exp(-L_s) is nan. The
    difference form is exact to rounding, forward and backward."""
    x, dt, a, b, c = draws(5, 128, decay=8.0)
    cum = jnp.cumsum((dt * a).reshape(2, 2, 64, 3), axis=2)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-cum))))  # the naive factor
    with jax.default_matmul_precision("highest"):
        y, s, low = ssd_scan(x, dt, a, b, c, chunk=64, block=1)
        want_y, want_s = literal(x, dt, a, b, c)
        grads = jax.grad(lambda *v: weighted(
            lambda *w: ssd_scan(*w, chunk=64, block=1), v),
            argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
        want = jax.grad(lambda *v: weighted(literal, v),
                        argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    assert float(low) < -88.0
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)
    for g, w in zip(grads, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
