"""Chunked gated delta rule (Gated DeltaNet, arXiv 2412.06464) with a backward
pass: the linear attention of Qwen3-Next's three layers in four.

Per value head, with a state S in R^(K x V) (keys x values), a key k_t and a
query q_t in R^K, a value v_t in R^V, a write gate beta_t in (0, 1) and a
log-decay g_t <= 0:

    S' = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T,        o_t = S_t^T q_t

The state decays, then what it holds along k_t is ERASED in part and v_t
written there: a rank-one correction of the state by the state itself, which
neither `ops/ssd.py`'s matrix form nor `ops/selscan.py`'s elementwise
recurrence computes. The literal recurrence is T dependent rank-one updates.
Here the sequence is cut into chunks of `chunk` positions. With G_i the sum of
g over the chunk up to and including i, S_0 the state a chunk starts from and
u_i = beta_i (v_i - S'_i^T k_i) what position i writes,

    S_i = exp(G_i) S_0 + sum_{j <= i} exp(G_i - G_j) k_j u_j^T

so the u of a chunk solve a unit-triangular system,

    (I + A) U = diag(beta) V - diag(beta exp(G)) K S_0,
    A_ij = beta_i exp(G_i - G_j) (k_i . k_j)  for j < i, 0 elsewhere

which is solved ONCE a chunk and head for both right-hand sides, before the
state is known: U = U0 - W S_0 with (I + A) [U0 | W] = [beta V | beta exp(G)
K]. Then

    O   = (exp(G) Q) S_0 + (Q K^T . exp(G_i - G_j), j <= i) U
    S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T U

so the work within a chunk is dense products and one small solve, and only the
chunks' states are sequential: S_C = (exp(G_C) I - K_out^T W) S_0 + K_out^T U0
with K_out = exp(G_C - G) K, whose two products do not wait for S_0, so a
step of the sequence of states is ONE (K, K) x (K, V) product a head (T / chunk
steps), and U and O of all the chunks of a block follow at once.

**Range.** Every exponent above is a sum of g over a stretch of the chunk and
so <= 0: decays come from DIFFERENCES G_i - G_j with i >= j, masked BEFORE the
exponential, never as exp(G_i) * exp(-G_j). Log-decays, their cumulative sums,
the decay factors, beta, the solve and the carried state are float32 whatever
the inputs' dtype. The Gram products K K^T and Q K^T take q and k as they
arrive; the products with the state and with U take float32 quantities rounded
to v's dtype (on the TPU a float32 product at default precision rounds them so
anyway) and accumulate in float32.

**Heads.** q and k may have fewer heads than v (Qwen3-Next: 16 key heads, 32
value heads): key head i serves the value heads i * r .. i * r + r - 1. The
Gram products are taken once a key head.

**The solve.** (I + A)^-1 is formed by blocks (`_unit_lower_inverse`: a
finite product on the diagonal blocks of 8 rows, then pairs of blocks joined
by products), float32 at `highest`, and applied to both right-hand sides by
one product: log2(chunk) batched steps instead of a row-by-row loop.

**Memory.** Plain `jax.numpy`; autodiff derives the backward pass. The chunks
go `block` at a time through a `lax.scan` whose body is under
`jax.checkpoint`: a block's (chunk, chunk) matrices and its chunks' states
live only inside its own forward and (recomputed) backward, and what is saved
per block is its inputs and the state carried in. No (T, heads, K, V) array exists in either pass. No Pallas: the
one path there is, so `LOWERED` counts every call under `plain` (the program
counter `delta_program` of the Trainer, beside `scan_program`).
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax import lax

# calls of `gated_delta_rule` traced so far, by the way they went down
LOWERED: collections.Counter = collections.Counter()
_HI = lax.Precision.HIGHEST
_BASE = 8  # rows of a diagonal block inverted by the finite product


def _unit_lower_inverse(a):
    """(I + a)^-1 for a (..., c, c) strictly lower triangular, c a power of
    two, float32 products at `highest`: forward substitution by BLOCKS, whose
    sequential depth is log2(c) batched products. Row-by-row substitution
    (`solve_triangular`, on the chip a loop of c turns a call) was half of
    the rule's time: 29.7 -> 14.6 ms forward at the cell's size (my chip
    runs, PR 40). The diagonal blocks of `_BASE` rows are inverted by the
    finite product (I - a)(I + a^2)(I + a^4)..., exact because a^_BASE = 0
    there (at 8 rows its terms cannot grow past C(6, 3) = 20 even with every
    key equal and beta one; at 16 rows they reach 3,432 and cost four
    digits); then pairs of inverted blocks are joined, [[T11, 0], [-T22 A21
    T11, T22]], until one block is left."""
    c = a.shape[-1]
    lead = a.shape[:-2]
    a = a.reshape(-1, c, c)  # ONE batch dimension, blocks cut by slices
    size = min(c, _BASE)

    def blocks(rows_from, cols_from, step, size):
        """(N, c / step, size, size): the block of `size` rows from row
        i * step + rows_from and column i * step + cols_from, for every i."""
        return jnp.stack([
            a[:, i + rows_from:i + rows_from + size,
              i + cols_from:i + cols_from + size]
            for i in range(0, c, step)], axis=1)

    diag = blocks(0, 0, size, size)
    eye = jnp.eye(size, dtype=a.dtype)
    t, power = eye - diag, diag
    for _ in range(size.bit_length() - 2):
        power = jnp.matmul(power, power, precision=_HI)
        t = jnp.matmul(t, eye + power, precision=_HI)
    while size < c:
        t11, t22 = t[:, 0::2], t[:, 1::2]
        t21 = -jnp.matmul(t22, jnp.matmul(
            blocks(size, 0, 2 * size, size), t11, precision=_HI),
            precision=_HI)
        t = jnp.concatenate([
            jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
            jnp.concatenate([t21, t22], axis=-1)], axis=-2)
        size *= 2
    return t.reshape(*lead, c, c)


def _block(s_in, q, k, v, g, beta):
    """`n` chunks of `c` positions, the state carried through them in order.

    s_in (B, H, K, V) float32; q, k (B, n, c, Hk, K); v (B, n, c, H, V); g,
    beta (B, n, c, H) float32. Returns (state after the last chunk, o (B, n,
    c, H, V) in v's dtype)."""
    n, c, hk = q.shape[1], q.shape[2], q.shape[3]
    h, dv = v.shape[3], v.shape[4]
    r = h // hk
    dtype = v.dtype
    f32 = jnp.float32
    cum = jnp.cumsum(g, axis=2)  # G_i, (B, n, c, H), <= 0
    last = cum[:, :, -1]  # (B, n, H)
    lt = cum.transpose(0, 1, 3, 2)  # (B, n, H, c)
    causal = jnp.tril(jnp.ones((c, c), bool))
    # exp(G_i - G_j) for j <= i (one on the diagonal), 0 above it
    decay = jnp.exp(jnp.where(
        causal, lt[..., :, None] - lt[..., None, :], -jnp.inf))
    # the Gram products once a key head, then one copy a value head
    kk = jnp.repeat(jnp.einsum(
        "bnihd,bnjhd->bnhij", k, k, preferred_element_type=f32), r, axis=2)
    qk = jnp.repeat(jnp.einsum(
        "bnihd,bnjhd->bnhij", q, k, preferred_element_type=f32), r, axis=2)
    bt = beta.transpose(0, 1, 3, 2)  # (B, n, H, c)
    a = jnp.where(
        jnp.tril(jnp.ones((c, c), bool), -1),
        bt[..., :, None] * kk * decay, 0.0)
    kf = jnp.repeat(k, r, axis=3).astype(f32)  # (B, n, c, H, K)
    qf = jnp.repeat(q, r, axis=3).astype(f32)
    into = jnp.exp(cum)  # exp(G_i): the carried state's share at i
    rhs = jnp.concatenate([
        beta[..., None] * v.astype(f32), (beta * into)[..., None] * kf,
    ], axis=-1).transpose(0, 1, 3, 2, 4)  # (B, n, H, c, V + K)
    solved = jnp.matmul(_unit_lower_inverse(a), rhs, precision=_HI)
    u0, w = solved[..., :dv], solved[..., dv:].astype(dtype)
    within = (qk * decay).astype(dtype)  # (B, n, H, c, c), the diagonal too
    # heads before positions: every product below is batched over (B, n, H)
    q_in = (into[..., None] * qf).astype(dtype).transpose(0, 1, 3, 2, 4)
    k_out = (jnp.exp(last[:, :, None] - cum)[..., None] * kf).astype(
        dtype).transpose(0, 1, 3, 2, 4)  # (B, n, H, c, K)
    # a chunk hands on S_out = exp(G_C) S_in + K_out^T (U0 - W S_in): its
    # two products that do not wait for S_in, for all n chunks at once
    erased = jnp.einsum(
        "bnhck,bnhcj->bnhkj", k_out, w, preferred_element_type=f32
    ).astype(dtype)
    added = jnp.einsum(
        "bnhck,bnhcv->bnhkv", k_out, u0.astype(dtype),
        preferred_element_type=f32)
    whole = jnp.exp(last)[..., None, None]  # (B, n, H, 1, 1)
    # the one sequential part: n steps of one product each
    s, states = s_in, []
    for i in range(n):
        states.append(s)
        s = whole[:, i] * s + added[:, i] - jnp.einsum(
            "bhkj,bhjv->bhkv", erased[:, i], s.astype(dtype),
            preferred_element_type=f32)
    starts = jnp.stack(states, axis=1).astype(dtype)  # (B, n, H, K, V)
    u = (u0 - jnp.einsum(
        "bnhck,bnhkv->bnhcv", w, starts, preferred_element_type=f32)
    ).astype(dtype)
    out = jnp.einsum(
        "bnhck,bnhkv->bnhcv", q_in, starts, preferred_element_type=f32
    ) + jnp.einsum(
        "bnhij,bnhjv->bnhiv", within, u, preferred_element_type=f32)
    return s, out.astype(dtype).transpose(0, 1, 3, 2, 4)


def gated_delta_rule(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    *, chunk: int = 64, block: int = 8,
):
    """The gated delta rule over a whole sequence.

    q, k (B, T, Hk, K), already normalised and scaled as the model wants
    them; v (B, T, H, V) with H a multiple of Hk; g (B, T, H) log-decays
    <= 0; beta (B, T, H) write gates; the state starts at zero. Any T: the
    last chunk is padded with positions of g 0, beta 0 and zero q, k, v,
    which decay nothing, write nothing and read nothing. Returns (o (B, T,
    H, V) in v's dtype, the state after position T - 1 (B, H, K, V)
    float32). `block` chunks are recomputed together in the backward pass."""
    bsz, t, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[3]
    if h % hk:
        raise ValueError(
            f"{h} value heads do not divide over {hk} key heads")
    if chunk & (chunk - 1):
        raise ValueError(f"a chunk of {chunk} positions is no power of two")
    LOWERED["plain"] += 1
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    chunks = (t + pad) // chunk
    block = min(block, chunks)
    while chunks % block:
        block -= 1
    blocks = chunks // block

    def cut(x):  # (B, T, ...) -> (blocks, B, block, chunk, ...)
        x = x.reshape(bsz, blocks, block, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    def body(s, xs):
        return jax.checkpoint(_block)(s, *xs)

    state, o = lax.scan(
        body, jnp.zeros((bsz, h, dk, dv), jnp.float32),
        tuple(cut(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, t + pad, h, dv)[:, :t]
    return o, state


def lowered_since(before: collections.Counter) -> dict:
    """What was traced since `before` (a copy of `LOWERED`), under
    `scan_program`'s names: delta rules through a kernel with the state in
    VMEM (none: there is no kernel yet), through the plain chunked form, and
    the distinct kernel programs among the former."""
    made = LOWERED - before
    return {"kernel": 0, "plain": made["plain"], "programs": 0}
