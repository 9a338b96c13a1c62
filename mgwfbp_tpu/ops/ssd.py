"""Chunked state-space (SSD, Mamba-2) scan with a backward pass.

Per head, with a state S in R^(P x N), an input x_t in R^P, a time step
dt_t > 0, a decay a_t = exp(dt_t A) (A < 0 per head) and B_t, C_t in R^N
shared by all heads (one group):

    S_t = a_t S_{t-1} + dt_t x_t B_t^T,        y_t = S_t C_t

The literal recurrence is T sequential steps of rank-one updates. Here the
sequence is cut into chunks of `chunk` positions (arXiv 2405.21060, section
6). With l_t = dt_t A and L_t the sum of l over the chunk up to and including
t, a chunk's output is

    y_t = sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s      (within)
        + exp(L_t) S_in C_t                                     (carried in)

and the state it hands on is

    S_out = exp(L_last) S_in + sum_s exp(L_last - L_s) dt_s x_s B_s^T

so the work within a chunk is three dense products and only the chunks'
states are sequential (T / chunk steps).

**Range.** Every exponent above is a sum of l over a stretch of the chunk and
so <= 0: the decays are computed from DIFFERENCES of the cumulative sums,
masked before the exponential, never as exp(L_t) * exp(-L_s) (whose second
factor overflows float32 once a chunk's decay passes e^-88, which a head with
A = -16 and dt = 0.1 does in 55 steps). Log-decays, their cumulative sums,
the decay factors and the carried state are float32 whatever x's dtype; the
products take their operands in x's dtype and accumulate in float32.

**Memory.** Plain `jax.numpy`; autodiff derives the backward pass. The
(chunks, heads, chunk, chunk) decay matrices are the large temporaries (0.54
GB in float32 at 32 chunks of 256 and 64 heads), so the chunks go `block` at
a time through a `lax.scan` whose body is under `jax.checkpoint`: a block's
matrices live only inside its own forward and (recomputed) backward, and
what is saved per block is its inputs and the state carried in. No Pallas.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _block(s_in, x, dt, a, b, c):
    """`n` chunks of `q` positions, the state carried through them in order.

    s_in (B, H, P, N) float32; x (B, n, q, H, P); dt (B, n, q, H) float32;
    a (H,) float32; b, c (B, n, q, N). Returns (state after the last chunk,
    y (B, n, q, H, P) in x's dtype, the most negative whole-chunk sum of
    log-decays)."""
    n, q = x.shape[1], x.shape[2]
    cum = jnp.cumsum(dt * a, axis=2)  # L_t, (B, n, q, H), <= 0
    last = cum[:, :, -1]  # (B, n, H)
    xdt = (x.astype(jnp.float32) * dt[..., None])
    # within a chunk: (C_t . B_s) exp(L_t - L_s) for s <= t
    scores = jnp.einsum(
        "bntk,bnsk->bnts", c, b, preferred_element_type=jnp.float32)
    lt = cum.transpose(0, 1, 3, 2)  # (B, n, H, q)
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        causal, lt[..., :, None] - lt[..., None, :], -jnp.inf))
    mixed = (scores[:, :, None] * decay).astype(x.dtype)  # (B, n, H, q, q)
    y = jnp.einsum(
        "bnhts,bnshp->bnthp", mixed, xdt.astype(x.dtype),
        preferred_element_type=jnp.float32)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(last[:, :, None] - cum)  # (B, n, q, H)
    added = jnp.einsum(
        "bnsk,bnshp->bnhpk", b, (xdt * to_end[..., None]).astype(x.dtype),
        preferred_element_type=jnp.float32)
    whole = jnp.exp(last)  # (B, n, H)
    states = []  # the state each chunk starts from
    s = s_in
    for i in range(n):
        states.append(s)
        s = whole[:, i, :, None, None] * s + added[:, i]
    carried = jnp.einsum(
        "bntk,bnhpk->bnthp", c, jnp.stack(states, axis=1).astype(x.dtype),
        preferred_element_type=jnp.float32)
    y = y + jnp.exp(cum)[..., None] * carried
    return s, y.astype(x.dtype), lax.stop_gradient(jnp.min(last))


def ssd_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    *, chunk: int = 256, block: int = 8,
):
    """The scan over a whole sequence.

    x (B, T, H, P); dt (B, T, H) positive time steps; a (H,) negative; b, c
    (B, T, N), shared by the H heads; the state starts at zero. Any T: the
    last chunk is padded with steps of dt 0, which decay nothing and add
    nothing. Returns (y (B, T, H,
    P) in x's dtype, the state after position T - 1 (B, H, P, N) float32,
    the most negative sum of log-decays over one chunk (a float32 scalar
    without gradient: at about -87 its exponential underflows)). The `D x`
    skip term of the Mamba-2 mixer is the caller's."""
    bsz, t, h, p = x.shape
    n_state = b.shape[-1]
    dt = dt.astype(jnp.float32)
    a = a.astype(jnp.float32)
    pad = -t % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    chunks = (t + pad) // chunk
    block = min(block, chunks)
    while chunks % block:
        block -= 1
    blocks = chunks // block

    def cut(v):  # (B, T, ...) -> (blocks, B, block, chunk, ...)
        v = v.reshape(bsz, blocks, block, chunk, *v.shape[2:])
        return jnp.moveaxis(v, 1, 0)

    state = jnp.zeros((bsz, h, p, n_state), jnp.float32)

    def body(s, xs):
        s, y, low = jax.checkpoint(_block)(s, xs[0], xs[1], a, xs[2], xs[3])
        return s, (y, low)

    state, (y, low) = lax.scan(body, state, (cut(x), cut(dt), cut(b), cut(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, t + pad, h, p)[:, :t]
    return y, state, jnp.min(low)
