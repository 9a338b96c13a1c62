"""ops/deltarule.py against the literal recurrence S' = exp(g_t) S_{t-1},
S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T, o_t = S_t^T q_t, one position at a
time: outputs, final state and all five inputs' gradients; T a multiple of the
chunk and of the block and not; key heads that serve two value heads; decays
near 0 and near -20 a token; bfloat16 inputs within a stated tolerance; and
each of the rule's parts shown to matter (beta, the erasure)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.ops import deltarule
from mgwfbp_tpu.ops.deltarule import gated_delta_rule

HI = jax.lax.Precision.HIGHEST


def literal(q, k, v, g, beta, erase=True):
    """(o (B, T, H, V), final state (B, H, K, V)) by the recurrence; a key
    head serves H / Hk adjacent value heads."""
    bsz, t, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[3]
    q, k = (jnp.repeat(x, h // hk, axis=2) for x in (q, k))

    def step(s, inp):
        qt, kt, vt, gt, bt = inp  # (B, H, K) x 2, (B, H, V), (B, H) x 2
        s = jnp.exp(gt)[..., None, None] * s
        held = jnp.einsum("bhkv,bhk->bhv", s, kt, precision=HI)
        u = bt[..., None] * (vt - held if erase else vt)
        s = s + jnp.einsum("bhk,bhv->bhkv", kt, u, precision=HI)
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=HI)

    s, o = jax.lax.scan(step, jnp.zeros((bsz, h, dk, dv)), tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def draws(seed, t, bsz=2, hk=2, h=4, dk=6, dv=5, decay=1.0):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(key[0], (bsz, t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(key[1], (bsz, t, hk, dk)))
    v = jax.random.normal(key[2], (bsz, t, h, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(key[3], (bsz, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (bsz, t, h)))
    return q, k, v, g, beta


def weighted(fn, args, seed=9):
    """Scalar of fn's outputs under fixed random weights, so that one
    gradient exercises o and the final state together."""
    o, s = fn(*args)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jnp.sum(o.astype(jnp.float32) * jax.random.normal(k1, o.shape))
            + jnp.sum(s * jax.random.normal(k2, s.shape)))


def both(args, **kw):
    """((o, state, five gradients) of the chunked form, of the recurrence)."""
    with jax.default_matmul_precision("highest"):
        got = (*gated_delta_rule(*args, **kw), *jax.grad(
            lambda *x: weighted(
                lambda *y: gated_delta_rule(*y, **kw), x),
            argnums=(0, 1, 2, 3, 4))(*args))
        want = (*literal(*args), *jax.grad(
            lambda *x: weighted(literal, x), argnums=(0, 1, 2, 3, 4))(*args))
    return got, want


@pytest.mark.parametrize("t,chunk,block", [
    (32, 8, 2),   # whole chunks, two blocks of two
    (29, 8, 8),   # a short last chunk, one block
    (40, 16, 2),  # three chunks: the block shrinks to one that divides
    (7, 16, 4),   # shorter than one chunk
    (48, 8, 4),   # six chunks: blocks of three
])
def test_float32_matches_the_recurrence_forward_state_and_five_gradients(
        t, chunk, block):
    got, want = both(draws(t, t), chunk=chunk, block=block)
    assert got[0].shape == (2, t, 4, 5) and got[1].shape == (2, 4, 6, 5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


def test_one_key_head_for_every_value_head_and_a_group_of_four():
    for hk in (4, 1):
        got, want = both(draws(3, 24, hk=hk), chunk=8, block=2)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)
    with pytest.raises(ValueError, match="do not divide"):
        gated_delta_rule(*draws(3, 8, hk=3), chunk=8)


@pytest.mark.parametrize("decay", [1e-4, 20.0], ids=["near-0", "near-minus-20"])
def test_decays_near_zero_and_near_minus_twenty_a_token(decay):
    """g near 0: nothing is forgotten and the erasure carries the whole
    rule. g near -20 a token: a chunk's sum passes -1,000, exp(-G_j) is inf
    in float32 and exp(G_i) * exp(-G_j) nan; the difference form is exact to
    rounding, forward and backward, with no overflow and no NaN."""
    args = draws(5, 128, decay=decay)
    if decay > 1:
        cum = jnp.cumsum(args[3].reshape(2, 2, 64, 4), axis=2)
        assert float(cum.min()) < -500
        assert not bool(jnp.all(jnp.isfinite(jnp.exp(-cum))))
    got, want = both(args, chunk=64, block=1)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


def test_bfloat16_inputs_keep_float32_decays_solve_and_state():
    """bf16 q, k, v (g and beta stay float32, as the mixer hands them over)
    against the float32 recurrence on the same rounded inputs: the products'
    operands are rounded to bf16 (2^-9 relative each), accumulation, decays,
    solve and state are float32, so outputs agree to a few per cent of their
    scale."""
    q, k, v, g, beta = draws(11, 48)
    lo = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    rule = jax.jit(lambda *x: gated_delta_rule(*x, chunk=16, block=2))
    o, s = rule(*lo, g, beta)
    assert o.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    want_o, want_s = literal(*(x.astype(jnp.float32) for x in lo), g, beta)
    assert float(jnp.abs(o.astype(jnp.float32) - want_o).max()) \
        < 0.03 * float(jnp.abs(want_o).max())
    assert float(jnp.abs(s - want_s).max()) \
        < 0.03 * float(jnp.abs(want_s).max())
    grad = jax.jit(jax.grad(lambda x: jnp.sum(
        rule(lo[0], lo[1], x, g, beta)[0].astype(jnp.float32))))(lo[2])
    assert grad.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(grad)))


def test_the_inverse_by_blocks_against_the_triangular_solve():
    """(I + A)^-1 by blocks against `solve_triangular`, on keys that are all
    but equal with beta one (the entries of A near one, the inverse's terms
    as large as they get) and on random ones, at 8, 16 and 64 rows."""
    from jax.scipy.linalg import solve_triangular

    for c, same in ((8, False), (16, True), (64, False), (64, True)):
        key = jax.random.PRNGKey(c)
        k = jax.random.normal(key, (3, c, 12))
        if same:
            k = k[:, :1] + 0.05 * k
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        a = jnp.tril(jnp.einsum("bik,bjk->bij", k, k), -1)
        with jax.default_matmul_precision("highest"):
            got = deltarule._unit_lower_inverse(a)
            want = solve_triangular(
                jnp.eye(c) + a, jnp.broadcast_to(jnp.eye(c), a.shape),
                lower=True, unit_diagonal=True)
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got - want).max()) < 2e-5 * scale, (c, same)
        assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0
    with pytest.raises(ValueError, match="no power of two"):
        gated_delta_rule(*draws(0, 24), chunk=12)


def test_beta_and_the_erasure_are_the_rule():
    """With beta forced to one, or with the erasure term dropped (plain
    decayed linear attention), the output is another function's."""
    q, k, v, g, beta = draws(2, 32)
    with jax.default_matmul_precision("highest"):
        o, _ = gated_delta_rule(q, k, v, g, beta, chunk=8)
        ones, _ = gated_delta_rule(q, k, v, g, jnp.ones_like(beta), chunk=8)
        plain, _ = literal(q, k, v, g, beta, erase=False)
    scale = float(jnp.abs(o).max())
    assert float(jnp.abs(o - ones).max()) > 0.1 * scale
    assert float(jnp.abs(o - plain).max()) > 0.1 * scale


def test_every_call_is_counted_as_plain():
    before = deltarule.LOWERED.copy()
    jax.eval_shape(lambda *x: gated_delta_rule(*x, chunk=8), *draws(0, 16))
    assert deltarule.lowered_since(before) == {
        "kernel": 0, "plain": 1, "programs": 0}
