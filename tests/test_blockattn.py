"""ops/blockattn.py's `scale`: the default is 1 / sqrt(D), and a model's own
multiplier (granite-4.0-h: 1 / 64 at head size 64, a group of 4 query heads
a key head) replaces it, value and gradients against dense attention."""

import jax
import jax.numpy as jnp
import pytest

from mgwfbp_tpu.ops.blockattn import blockwise_attention


def dense_attention(q, k, v, scale):
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(q.shape[1])[None, :]
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("scale,want_scale", [
    (None, 0.125), (1.0 / 64, 1.0 / 64)], ids=["default", "published-1/64"])
def test_scale_value_and_gradient_against_dense(scale, want_scale):
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    t = 40  # no multiple of the block
    q = 4.0 * jax.random.normal(keys[0], (2, t, 8, 64))
    k = jax.random.normal(keys[1], (2, t, 2, 64))
    v = jax.random.normal(keys[2], (2, t, 2, 64))
    w = jax.random.normal(keys[3], (2, t, 8, 64))

    def through(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)))

    got, got_grads = through(lambda q, k, v: blockwise_attention(
        q, k, v, block=16, scale=scale))(q, k, v)
    want, want_grads = through(
        lambda q, k, v: dense_attention(q, k, v, want_scale))(q, k, v)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for g, wg in zip(got_grads, want_grads):
        assert float(jnp.linalg.norm(g - wg) / jnp.linalg.norm(wg)) < 1e-5
    # the two scales are different functions of these inputs
    other, _ = through(lambda q, k, v: dense_attention(
        q, k, v, 0.125 if scale else 1.0 / 64))(q, k, v)
    assert abs(float(other) - float(want)) > 1e-3 * abs(float(want))
