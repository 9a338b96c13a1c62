"""Chunked selective scan (Mamba-1) with a backward pass.

Per channel d of D and state n of N, with an input x_t in R^D, a time step
dt_t > 0 in R^D, a decay rate A < 0 in R^(D x N), and B_t, C_t in R^N shared
by all channels:

    h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t x_t) (x) B_t       (D x N a token)
    y_t = h_t C_t

The decay differs by channel AND state, so the work inside a chunk has no
matrix-product form (ops/ssd.py's has: one decay a head). What can be shared
is the order: the sequence is cut into chunks of `chunk` positions, and all
the chunks of a block take their steps TOGETHER, each from a zero state:
`chunk` sequential steps over (chunks, N, D) instead of T over (N, D). With
L_t the sum of dt over the chunk up to and including t, the state a chunk
was handed adds

    y_t += sum_n C_t[n] exp(L_t (x) A)[., n] H_in[., n]

and the state it hands on is exp(L_last (x) A) . H_in + its own last state:
one short step a chunk, in order.

**Range.** Every exponent is dt or a sum of dt over a stretch of one chunk,
times A: <= 0, so no factor can overflow and nothing is ever divided by a
decay. Everything here is float32 whatever the inputs' dtype: time steps,
decays, sums, the state and y.

**Memory.** Plain `jax.numpy`; autodiff derives the backward pass. The states
are laid out (chunks, N, D), the channels in the lanes. The blocks of `block`
chunks go through a `lax.scan` whose body is under `jax.checkpoint`: the
states of a block's positions (block x chunk x D x N float32: 336 MB at 16
chunks of 64 and D x N = 5,120 x 16; its backward holds four or five arrays
of that size at once, 1.54 GiB of scratch as the chip's compiler counts it)
live only inside that block's own forward and (recomputed) backward, and what
is saved per block is its inputs and the state carried in. No (T, D, N) array
(2.5 GiB at T 8,192) exists in either pass. No Pallas.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _block(h_in, x, dt, a, b, c):
    """`n` chunks of `q` positions, the state carried through them in order.

    h_in (B, N, D); x, dt (B, n, q, D); a (N, D); b, c (B, n, q, N); all
    float32. Returns (the state after the last chunk, y (B, n, q, D))."""
    n = x.shape[1]
    u = x * dt

    def step(h, at_t):
        dt_t, u_t, b_t, c_t = at_t  # (B, n, D) twice, (B, n, N) twice
        h = jnp.exp(dt_t[:, :, None] * a) * h \
            + b_t[..., None] * u_t[:, :, None]
        return h, jnp.sum(c_t[..., None] * h, axis=2)

    # every chunk from a zero state, a position at a time
    zero = jnp.zeros((*x.shape[:2], *a.shape), jnp.float32)
    own, y = lax.scan(
        step, zero, tuple(jnp.moveaxis(v, 2, 0) for v in (dt, u, b, c)))
    y = jnp.moveaxis(y, 0, 2)
    cum = jnp.cumsum(dt, axis=2)  # L_t, (B, n, q, D)
    whole = jnp.exp(cum[:, :, -1, None] * a)  # (B, n, N, D)
    states = []  # the state each chunk starts from
    h = h_in
    for i in range(n):
        states.append(h)
        h = whole[:, i] * h + own[:, i]
    carried = jnp.stack(states, axis=1)  # (B, n, N, D)
    y = y + jnp.sum(
        c[..., None] * jnp.exp(cum[:, :, :, None] * a) * carried[:, :, None],
        axis=3)
    return h, y


def selective_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    *, chunk: int = 64, block: int = 16,
):
    """The scan over a whole sequence.

    x (B, T, D); dt (B, T, D) positive time steps; a (D, N) negative; b, c
    (B, T, N); the state starts at zero. Any T: the last chunk is padded with
    steps of dt 0, which decay nothing and add nothing. Returns (y (B, T, D)
    float32, the state after position T - 1 (B, D, N) float32). The `D x`
    skip term of the mixer is the caller's."""
    bsz, t, d = x.shape
    x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
    a = a.astype(jnp.float32).T  # (N, D): the channels in the lanes
    pad = -t % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (x, dt, b, c))
    chunks = (t + pad) // chunk
    block = min(block, chunks)
    while chunks % block:
        block -= 1
    blocks = chunks // block

    def cut(v):  # (B, T, W) -> (blocks, B, block, chunk, W)
        v = v.reshape(bsz, blocks, block, chunk, v.shape[-1])
        return jnp.moveaxis(v, 1, 0)

    def body(h, at):
        return jax.checkpoint(_block)(h, at[0], at[1], a, at[2], at[3])

    state = jnp.zeros((bsz, *a.shape), jnp.float32)
    state, y = lax.scan(body, state, (cut(x), cut(dt), cut(b), cut(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, t + pad, d)[:, :t]
    return y, jnp.swapaxes(state, 1, 2)
