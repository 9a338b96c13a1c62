"""Chunked gated delta rule (Gated DeltaNet, arXiv 2412.06464) with a backward
pass: the linear attention of Qwen3-Next's three layers in four. One entry
point, `gated_delta_rule`, and two ways down from it.

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

**The solve of the plain form.** (I + A)^-1 is formed by blocks (`_unit_lower_inverse`: a
finite product on the diagonal blocks of 8 rows, then pairs of blocks joined
by products), float32 at `highest`, and applied to both right-hand sides by
one product: log2(chunk) batched steps instead of a row-by-row loop.

**Two ways down from `gated_delta_rule`, chosen by what the code sees.**
Traced for a TPU with keys and values of one lane tile a head, the chunk the
kernels are chosen for (64), one, two or four value heads a key head in
eight pairs a step, q, k and v all bfloat16 or all float32 and T a whole
number of blocks (`_kernel_rows`): three Pallas kernels of this module's own
under a `custom_vjp`. Anything else (the CPU, every tier-1 test, the tiny
preset, a T that is padded): the plain chunked form below, which is also the
kernels' reference. Each call notes the way it went and the kernel programs
it needs (`ops/programs.py`, op `delta`: the Trainer's `delta_program`,
beside `scan_program`).

**The kernels.** q, k, v come in and o goes out as (B, T, heads x 128): a
head is one lane tile of columns that the index maps pick, and nothing is
transposed on either side. g and beta come as (B, Hk, T / 128, r x 8, 128)
float32: for each value head G (the sum of g over a chunk up to a position),
G_C - G, beta and each chunk's G_C along the lanes, made by one small XLA
pass (`_decay_vectors`); d G goes back the same way and its reverse sum over
a chunk is taken outside.

  * `gated_delta_rule_inverse`: (I + A)^-1 of every chunk and value head by
    forward substitution a row at a time, with 128 tiles' systems ALONG THE
    LANES (the vector unit; the MXU, fed whole tiles at `highest`, spent 120
    of a tile's 157 passes on it). Its result, 134 MB a layer at the
    Qwen3-Next cell's size, is read once by the kernel that follows and is no
    residual.
  * `gated_delta_rule_forward`: a grid over (sequence, key head, block of
    512 positions), the blocks innermost and in order; the (128, 128)
    float32 state of each value head of the key head in VMEM scratch from a
    sequence's first block to its last. A tile's K K^T and Q K^T are taken
    once a key head; the decays, the solve [U0 | W] = (I + A)^-1 [beta V |
    beta exp(G) K] (float32 operands as three bfloat16 terms, six passes:
    `_exact`), `within` and K_out for both chunks of a tile at once; then
    chunk by chunk U = U0 - W S, S' = exp(G_C) S + K_out^T U. It writes o,
    the final state and the state each block starts from (64 MiB a layer),
    which is all the backward pass needs beside the inputs.
  * `gated_delta_rule_backward`: the blocks in reverse with d S in VMEM. A
    block's chunks' states and solves are recomputed from its saved start
    into VMEM, then each tile is pulled back: the transposed recurrence
    chunk by chunk (d U, d S), the solve's transpose d[rhs] = (I + A)^-T
    [d U | d W] and d A = - d[rhs] [U0 | W]^T (both `_exact`), and from d A
    and d `within` the keys', queries', values', betas' and decays'
    cotangents; d q and d k are summed over the key head's value heads in
    the kernel.

The roundings are the plain form's: float32 log-decays, decay factors, beta,
inverse, solve and carried state; every other product takes operands
rounded to v's dtype and accumulates in float32. Every call reaches the
kernels through `_inverse_kernel` / `_forward_kernel` / `_backward_kernel`,
each ONE `jax.jit`ted function, so every layer's forward, its recomputation
under the mixer's `jax.checkpoint` and its backward share THREE kernel
programs; the backward's is traced on the way forward (`jax.eval_shape`;
`ops/groupmm.py` has the measurement).

**The plain chunked form.** Plain `jax.numpy`; autodiff derives the backward
pass. The chunks go `block` at a time through a `lax.scan` whose body is
under `jax.checkpoint`: a block's (chunk, chunk) matrices and its chunks'
states live only inside its own forward and (recomputed) backward, and what
is saved per block is its inputs and the state carried in. No (T, heads, K,
V) array exists in either pass, down either way.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs

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



# --- the kernels -----------------------------------------------------------
#
# A TILE is 128 positions: 128 / chunk chunks side by side, whose (chunk,
# chunk) matrices are the diagonal blocks of ONE (128, 128) matrix. Whatever
# couples positions of different chunks is masked away (the decays BEFORE
# their exponential), so the Gram products, A, the solve and `within` are
# products of whole MXU tiles, and a chunk is a slice of aligned rows.

_TILE = 128
_LANES = 128
_VEC = 8  # rows a value head has in the kernels' per-position vectors
# positions a grid step takes (one float32 state a value head is saved for
# each), the first that divides T; chunks the kernels are chosen for
_ROWS = (512, 256, 128)
_CHUNKS = (64,)
_GROUPS = (1, 2, 4)  # value heads a key head may serve in one grid step
_PAIRS = 8  # (key head, value head) pairs a grid step of the inverse takes
_INVERSE_TILES = 16  # tiles of such a step at most: 128 of them side by side
# of the chip's 128 MiB of VMEM: the inverse's three slabs of 4 MiB and its
# blocks, buffered twice, take about 25 MiB
_VMEM_LIMIT = 64 * 2 ** 20


def _kernel_rows(t: int, hk: int, h: int, dk: int, dv: int, chunk: int,
                 dtypes) -> Optional[int]:
    """The positions of a grid step of the kernels for this call, or None
    where the plain form stays: q, k and v not all bfloat16 or all float32,
    keys or values that are not one lane tile a head, a chunk or a group of
    value heads the kernels were not written for, or a T that no block of
    positions divides."""
    if len(set(dtypes)) != 1 or dtypes[0] not in (jnp.bfloat16, jnp.float32):
        return None
    r = h // hk
    if dk != _LANES or dv != _LANES or chunk not in _CHUNKS \
            or r not in _GROUPS or hk % (_PAIRS // r):
        return None
    return next((rows for rows in _ROWS if t % rows == 0), None)


def _pallas():
    """Pallas, imported where a kernel is wanted."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def _mm(a, b, dims=((1,), (0,))):
    return lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


def _nt(a, b):  # a b^T
    return _mm(a, b, ((1,), (1,)))


def _tn(a, b):  # a^T b
    return _mm(a, b, ((0,), (0,)))


def _pieces(x):
    """x float32 as three bfloat16 terms whose sum is x to 24 bits: each
    the rounding of what the ones before it left."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    high = x.astype(bf16)
    left = x - high.astype(f32)
    mid = left.astype(bf16)
    return high, mid, (left - mid.astype(f32)).astype(bf16)


def _exact(a, b, transposed: bool = False):
    """a b (a b^T where `transposed`) of a float32 matrix a and a float32
    or bfloat16 matrix b at the accuracy of `highest` (on the chip XLA's own
    six bfloat16 passes): every pair of their bfloat16 terms down to high x
    low. Each term of b is a weight the MXU loads ONCE, for all the terms
    of a that meet it, stacked along the rows; a bfloat16 b is its own one
    term, and the product three passes. (Mosaic's own float32 product pushes
    8 rows at a time and pops every pass: 7,600 bundles a tile of the first
    forward kernel where this cost 7,000; the chip's compiler, asked here.)"""
    a_terms = _pieces(a)
    b_terms = (b,) if b.dtype == jnp.bfloat16 else _pieces(b)
    product = _nt if transposed else _mm
    rows = a.shape[0]
    out = None
    for j in reversed(range(len(b_terms))):  # the smallest terms first
        with_b = product(jnp.concatenate(a_terms[:3 - j], axis=0), b_terms[j])
        for i in reversed(range(3 - j)):
            term = with_b[i * rows:(i + 1) * rows]
            out = term if out is None else out + term
    return out


class _Masks:
    """The (128, 128) masks of a tile, for chunks of `chunk` positions."""

    def __init__(self, chunk: int):
        shape = (_TILE, _TILE)
        i = lax.broadcasted_iota(jnp.int32, shape, 0)
        j = lax.broadcasted_iota(jnp.int32, shape, 1)
        self.lane = j
        apart = i ^ j  # under c: both in one aligned block of c
        self.causal = (apart < chunk) & (i >= j)
        self.strict = (apart < chunk) & (i > j)
        # the chunk of each lane of a (chunk, 128) matrix of chunks side
        # by side
        self.chunk_of_lane = lax.broadcasted_iota(
            jnp.int32, (chunk, _TILE), 1) // chunk


def _tile_decay(vec, masks: _Masks):
    """vec (8, 128) float32 -> (its rows along the sublanes (128, 8),
    exp(G_i - G_j) for j <= i of one chunk and 0 elsewhere (128, 128))."""
    cols = vec.T
    return cols, jnp.exp(jnp.where(
        masks.causal, cols[:, 0:1] - vec[0:1], -jnp.inf))


def _side_by_side(a, chunk: int):
    """A tile's (128, 128) matrix that is zero outside its chunks'
    diagonal blocks -> those blocks side by side, (chunk, 128)."""
    first, *rest = (a[rows] for rows in _chunks(chunk))
    return sum(rest, first)


def _on_the_diagonal(packed, masks: _Masks, chunk: int):
    """`_side_by_side` back."""
    return jnp.concatenate([
        jnp.where(masks.chunk_of_lane == m, packed, 0.0)
        for m in range(_TILE // chunk)], axis=0)


def _tile_parts(q, k, v, vec, kk, qk, inverse, masks: _Masks, chunk: int,
                solved=None):
    """What a tile of one value head holds before any state is known. q, k
    (128, K) and v (128, V) as they came; vec (8, 128) float32, its rows G,
    G_C - G, beta and each chunk's G_C; kk, qk the key head's Gram
    products; inverse (chunk, 128): the chunks' (I + A)^-1 side by side.
    `solved`, where the caller kept it, spares the solve."""
    f32 = jnp.float32
    dtype = v.dtype
    cols, decay = _tile_decay(vec, masks)
    g, to_end, beta = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
    into, out_of = jnp.exp(g), jnp.exp(to_end)
    kf, qf = k.astype(f32), q.astype(f32)
    inverse = _on_the_diagonal(inverse, masks, chunk)
    if solved is None and dtype == jnp.bfloat16:
        # (T diag(beta)) V: V is its own one term, three passes for six
        by_v = inverse * vec[2:3]
        solved = jnp.concatenate([
            _exact(by_v, v), _exact(by_v * jnp.exp(vec[0:1]), k)], axis=1)
    elif solved is None:
        rhs = jnp.concatenate(
            [beta * v.astype(f32), (beta * into) * kf], axis=1)
        solved = _exact(inverse, rhs)
    dv = v.shape[1]
    return dict(
        beta=beta, into=into, out_of=out_of, decay=decay, kf=kf,
        kk_decay=kk * decay, within=qk * decay, q_in=into * qf,
        k_out=out_of * kf, inverse=inverse, solved=solved,
        u0=solved[:, :dv], w=solved[:, dv:].astype(dtype),
        # exp(G_C) of each chunk, the same along the lanes
        whole=[jnp.exp(vec[3 + m:4 + m]) for m in range(_TILE // chunk)])


def _tile_states(parts, s, chunk: int, dtype, each=None, read=False):
    """A tile's chunks in order from the state s (K, V) float32: (the state
    after the tile, U (128, V) float32, and with `read` what the states the
    chunks start from add to o, Q_in S (128, V)). `each(m, s)` is handed
    the state chunk m starts from."""
    k_out = parts["k_out"].astype(dtype)
    q_in = parts["q_in"].astype(dtype)
    us, reads = [], []
    for m, rows in enumerate(_chunks(chunk)):
        if each is not None:
            each(m, s)
        if read:  # one weight, S, for W's rows and Q_in's
            held = _mm(jnp.concatenate(
                [parts["w"][rows], q_in[rows]], axis=0), s.astype(dtype))
            reads.append(held[chunk:])
            u = parts["u0"][rows] - held[:chunk]
        else:
            u = parts["u0"][rows] - _mm(parts["w"][rows], s.astype(dtype))
        s = parts["whole"][m] * s + _tn(k_out[rows], u.astype(dtype))
        us.append(u)
    return (s, jnp.concatenate(us, axis=0),
            jnp.concatenate(reads, axis=0) if read else None)


def _chunks(chunk: int) -> list[slice]:
    return [slice(m, m + chunk) for m in range(0, _TILE, chunk)]


def _call(kernel, name: str, interpret: bool, **spec):
    """`pl.pallas_call` over a grid of (sequence, key head, block of
    positions), every axis in order: the state is carried in scratch."""
    pl, pltpu = _pallas()
    return pl.pallas_call(
        kernel, **spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


def _vectors(hk: int, *values):
    """(B, T, H) float32 arrays -> (B, Hk, T / 128, r x 8, 128): for each
    value head of a key head eight rows of a tile's positions, the values'
    first and zeros after them."""
    bsz, t, h = values[0].shape
    r = h // hk
    rows = jnp.stack(values, axis=-1)  # (B, T, H, n)
    rows = jnp.pad(rows, ((0, 0),) * 3 + ((0, _VEC - len(values)),))
    rows = rows.reshape(bsz, t // _TILE, _TILE, hk, r * _VEC)
    return rows.transpose(0, 3, 1, 4, 2)


def _unvectors(vec, n: int):
    """`_vectors` back: the first n rows of each value head as n (B, T, H)
    arrays."""
    bsz, hk, tiles, rows, _ = vec.shape
    vec = vec.transpose(0, 2, 4, 1, 3).reshape(
        bsz, tiles * _TILE, hk * rows // _VEC, _VEC)
    return tuple(vec[..., i] for i in range(n))


def _decay_vectors(g, beta, chunk: int, hk: int):
    """The kernels' per-position vectors: G (the sum of g over a chunk up to
    and including a position), G_C - G, beta, and G_C of each chunk of the
    position's tile (rows 3, 4: the same along the lanes)."""
    bsz, t, h = g.shape
    cum = jnp.cumsum(g.reshape(bsz, t // chunk, chunk, h), axis=2)
    to_end = cum[:, :, -1:] - cum
    # (B, T / 128, 128 / chunk, H) -> (B, T, H) x 128 / chunk: chunk m of a
    # position's tile
    ends = cum[:, :, -1].reshape(bsz, t // _TILE, 1, _TILE // chunk, h)
    ends = jnp.broadcast_to(
        ends, (bsz, t // _TILE, _TILE, _TILE // chunk, h)
    ).reshape(bsz, t, _TILE // chunk, h)
    return _vectors(
        hk, cum.reshape(bsz, t, h), to_end.reshape(bsz, t, h), beta,
        *(ends[:, :, m] for m in range(_TILE // chunk)))


def _inverse_tiles(t: int) -> int:
    """Tiles a grid step of the inverse kernel takes: the largest power of
    two up to 16 that divides T / 128."""
    tiles = _INVERSE_TILES
    while (t // _TILE) % tiles:
        tiles //= 2
    return tiles


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _inverse_kernel(k, vec, *, chunk: int, interpret: bool = False):
    """(I + A)^-1 of every chunk and value head by forward substitution, a
    row at a time, float32 throughout: k (B, T, Hk, K); vec as
    `_decay_vectors` makes it -> (B, Hk, r, T / 128, chunk, 128) float32,
    a tile's chunks side by side.

    The MXU is the wrong unit for it: formed by blocks with every product a
    whole tile at the accuracy of `highest`, the inverse was 120 of a
    tile's 157 MXU passes (my chip run, PR 41: 16.8 ms forward where the
    plain form reads 14.4). Here the SYSTEMS lie along the lanes: a grid
    step takes 8 pairs of (key head, value head) over up to 16 tiles, 128
    tiles of 128 / chunk systems each, and holds A as x[i][(m, k), tile] and
    the inverse as t[i][(m, j), tile], a (128, 128) slab a row i. Row i of
    every system at once is then e_i - sum_k A[i, k] t[k]: a row of x[i]
    along the sublanes times a slab, on the vector unit, with no product of
    matrices and no rounding but float32's own."""
    pl, pltpu = _pallas()
    bsz, t, hk, dk = k.shape
    r = vec.shape[3] // _VEC
    heads = _PAIRS // r  # key heads a step
    tiles = _inverse_tiles(t)
    per_tile = _TILE // chunk
    f32 = jnp.float32

    def kernel(k_ref, vec_ref, out_ref, stage_ref, x_ref, t_ref):
        masks = _Masks(chunk)
        if _PAIRS * tiles < _TILE:  # lanes no tile fills: A 0, inverse I
            stage_ref[...] = jnp.zeros(stage_ref.shape, f32)

        def staged(n, pair):  # the rows of pair `pair` of tile n
            return pl.ds(pl.multiple_of(
                (n * _PAIRS + pair) * chunk, chunk), chunk)

        def fill(n, carry):  # A of the tile's 8 pairs, chunks side by side
            at = pl.ds(pl.multiple_of(n * _TILE, _TILE), _TILE)
            for g in range(heads):
                k_t = k_ref[0, at, g * dk:(g + 1) * dk]
                kk = _nt(k_t, k_t)
                for j in range(r):
                    vec = vec_ref[0, g, n, j * _VEC:(j + 1) * _VEC, :]
                    cols, decay = _tile_decay(vec, masks)
                    stage_ref[staged(n, g * r + j), :] = _side_by_side(
                        jnp.where(masks.strict, cols[:, 2:3] * kk * decay,
                                  0.0), chunk)
            return carry

        lax.fori_loop(0, tiles, fill, 0)
        within = lax.broadcasted_iota(
            jnp.int32, (_TILE, _TILE), 0) & (chunk - 1)

        def row_of_all(i):  # row i of every pair and tile
            return pl.ds(i, _TILE, stride=chunk)

        def turn(i, carry):  # (tile, (m, k)) -> x[i] ((m, k), tile)
            x_ref[i] = stage_ref[row_of_all(i), :].T
            return carry

        lax.fori_loop(0, chunk, turn, 0)

        def row(i, carry):
            def less(k, acc):  # acc - A[i, k] t[k]
                weight = jnp.concatenate([
                    jnp.broadcast_to(
                        x_ref[i, pl.ds(m * chunk + k, 1), :],
                        (chunk, _TILE)) for m in range(per_tile)], axis=0)
                return acc - weight * t_ref[k]

            def less4(k4, acc):  # four at a time: loads under products
                for k in range(4):
                    acc = less(k4 * 4 + k, acc)
                return acc

            acc = lax.fori_loop(0, i // 4, less4, (within == i).astype(f32))
            t_ref[i] = lax.fori_loop(i // 4 * 4, i, less, acc)
            return carry

        lax.fori_loop(0, chunk, row, 0)

        def back(i, carry):  # t[i] ((m, j), tile) -> row i of each pair
            stage_ref[row_of_all(i), :] = t_ref[i].T
            return carry

        lax.fori_loop(0, chunk, back, 0)

        def leave(n, carry):
            for pair in range(_PAIRS):
                out_ref[0, pair // r, pair % r, n] = stage_ref[
                    staged(n, pair), :]
            return carry

        lax.fori_loop(0, tiles, leave, 0)

    return _call(
        kernel, "gated_delta_rule_inverse", interpret,
        out_shape=jax.ShapeDtypeStruct(
            (bsz, hk, r, t // _TILE, chunk, _TILE), f32),
        grid=(bsz, t // (tiles * _TILE), hk // heads),
        in_specs=[
            pl.BlockSpec((1, tiles * _TILE, heads * dk),
                         lambda b, i, j: (b, i, j)),
            pl.BlockSpec((1, heads, tiles, r * _VEC, _LANES),
                         lambda b, i, j: (b, j, i, 0, 0))],
        out_specs=pl.BlockSpec(
            (1, heads, r, tiles, chunk, _TILE),
            lambda b, i, j: (b, j, 0, i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_TILE * chunk, _TILE), f32),
            pltpu.VMEM((chunk, _TILE, _TILE), f32),
            pltpu.VMEM((chunk, _TILE, _TILE), f32)],
    )(k.reshape(bsz, t, hk * dk), vec)


@functools.partial(
    jax.jit, static_argnames=("chunk", "rows", "interpret"))
def _forward_kernel(q, k, v, g, beta, *, chunk: int, rows: int,
                    interpret: bool = False):
    """q, k (B, T, Hk, K); v (B, T, H, V); g, beta (B, T, H) float32.
    Returns (o (B, T, H, V) in v's dtype, the state after the last position
    (B, H, K, V) float32, the state each block of `rows` positions starts
    from (B, T / rows, H, K, V) float32)."""
    pl, pltpu = _pallas()
    bsz, t, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[3]
    r, blocks, tiles = h // hk, t // rows, rows // _TILE
    f32 = jnp.float32
    dtype = v.dtype

    def kernel(q_ref, k_ref, v_ref, vec_ref, inverse_ref, o_ref, last_ref,
               starts_ref, s_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            s_ref[...] = jnp.zeros(s_ref.shape, f32)

        starts_ref[0, 0] = s_ref[...]
        masks = _Masks(chunk)

        def tile(n, carry):
            at = pl.ds(pl.multiple_of(n * _TILE, _TILE), _TILE)
            q_t, k_t = q_ref[0, at, :], k_ref[0, at, :]
            kk, qk = _nt(k_t, k_t), _nt(q_t, k_t)
            for j in range(r):
                head = slice(j * dv, (j + 1) * dv)
                parts = _tile_parts(
                    q_t, k_t, v_ref[0, at, head],
                    vec_ref[0, 0, n, j * _VEC:(j + 1) * _VEC, :], kk, qk,
                    inverse_ref[0, 0, j, n], masks, chunk)
                s_ref[j], u, carried = _tile_states(
                    parts, s_ref[j], chunk, dtype, read=True)
                o_ref[0, at, head] = (carried + _mm(
                    parts["within"].astype(dtype), u.astype(dtype))
                ).astype(dtype)
            return carry

        lax.fori_loop(0, tiles, tile, 0)
        last_ref[0] = s_ref[...]

    vec = _decay_vectors(g, beta, chunk, hk)
    o, last, starts = _call(
        kernel, "gated_delta_rule_forward", interpret,
        out_shape=(
            jax.ShapeDtypeStruct((bsz, t, h * dv), dtype),
            jax.ShapeDtypeStruct((bsz, h, dk, dv), f32),
            jax.ShapeDtypeStruct((bsz, blocks, h, dk, dv), f32)),
        grid=(bsz, hk, blocks),
        in_specs=[
            pl.BlockSpec((1, rows, dk), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, rows, dk), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, rows, r * dv), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, 1, tiles, r * _VEC, _LANES),
                         lambda b, j, i: (b, j, i, 0, 0)),
            pl.BlockSpec((1, 1, r, tiles, chunk, _TILE),
                         lambda b, j, i: (b, j, 0, i, 0, 0))],
        out_specs=(
            pl.BlockSpec((1, rows, r * dv), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, r, dk, dv), lambda b, j, i: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, r, dk, dv), lambda b, j, i: (b, i, j, 0, 0))),
        scratch_shapes=[pltpu.VMEM((r, dk, dv), f32)],
    )(q.reshape(bsz, t, hk * dk), k.reshape(bsz, t, hk * dk),
      v.reshape(bsz, t, h * dv), vec,
      _inverse_kernel(k, vec, chunk=chunk, interpret=interpret))
    return o.reshape(bsz, t, h, dv), last, starts


def _tile_pullback(parts, q, k, v, do, starts, ds, masks: _Masks,
                   chunk: int):
    """One value head's tile, backwards. `parts` as `_tile_parts` made them;
    q, k, v, do (128, .) as they came; starts: the state each chunk of the
    tile starts from; ds (K, V) float32: the cotangent of the state after
    the tile. Returns (the cotangent of the state the tile starts from, dq
    and dk (128, K), dv (128, V), float32, (128, 128) float32 whose first
    two COLUMNS are d G and d beta, and (1, 128) that d G still loses: the
    column sums come as a row)."""
    f32 = jnp.float32
    dtype = v.dtype
    beta, into, out_of = parts["beta"], parts["into"], parts["out_of"]
    u0, w, solved = parts["u0"], parts["w"], parts["solved"]
    kf, decay = parts["kf"], parts["decay"]
    q_in, k_out = parts["q_in"].astype(dtype), parts["k_out"].astype(dtype)
    within = parts["within"].astype(dtype)
    dv_ = v.shape[1]
    # what does not wait for d S: O = Q_in S_0 + within U
    du_within = _tn(within, do)
    us, dus, dws, dk_outs, dq_ins, ends = [], [], [], [], [], []
    spans = _chunks(chunk)
    for m in reversed(range(len(spans))):
        rows = spans[m]
        s0 = starts[m]
        s0_, ds_ = s0.astype(dtype), ds.astype(dtype)
        u = u0[rows] - _mm(w[rows], s0_)
        # S_C = exp(G_C) S_0 + K_out^T U;  U = U0 - W S_0
        du = du_within[rows] + _mm(k_out[rows], ds_)
        du_ = du.astype(dtype)
        dk_out = _nt(u.astype(dtype), ds_)
        # d G_C: through exp(G_C) S_0 and through every exp(G_C - G_i)
        ends.append(
            parts["whole"][m][:, :1] * jnp.sum(ds * s0, keepdims=True)
            + jnp.sum(dk_out * parts["k_out"][rows], keepdims=True))
        # Q_in^T d O - W^T d U: one product over the rows of both
        ds = parts["whole"][m] * ds + _tn(
            jnp.concatenate([q_in[rows], -w[rows]], axis=0),
            jnp.concatenate([do[rows], du_], axis=0))
        by_s0 = _nt(jnp.concatenate([du_, do[rows]], axis=0), s0_)
        us.append(u)
        dus.append(du)
        dws.append(-by_s0[:chunk])
        dk_outs.append(dk_out)
        dq_ins.append(by_s0[chunk:])

    def tile(parts_):
        return jnp.concatenate(parts_[::-1], axis=0)

    u, du, dw, dk_out, dq_in = (
        tile(x) for x in (us, dus, dws, dk_outs, dq_ins))
    # [U0 | W] = (I + A)^-1 [beta V | beta exp(G) K]
    drhs = _exact(parts["inverse"].T, jnp.concatenate([du, dw], axis=1))
    da = jnp.where(masks.strict, -_exact(drhs, solved, True), 0.0)
    by_beta = da * parts["kk_decay"]  # d A_ij k_i.k_j exp(G_i - G_j)
    dwithin = jnp.where(masks.causal, _nt(do, u.astype(dtype)), 0.0)
    # both matrices hold exp(G_i - G_j): d G_i gets its row, d G_j loses
    # its column
    through = beta * by_beta + dwithin * parts["within"]
    dkk = beta * da * decay
    dkk = (dkk + dkk.T).astype(dtype)  # K K^T is read on both sides
    dqk = (dwithin * decay).astype(dtype)
    drhs_v, drhs_k = drhs[:, :dv_], drhs[:, dv_:]
    along_k = jnp.sum(drhs_k * kf, axis=1, keepdims=True)
    by_k = _mm(jnp.concatenate([dkk, dqk], axis=0), k)  # one weight, K
    dk = by_k[:_TILE] + _tn(dqk, q) + (beta * into) * drhs_k \
        + out_of * dk_out
    dq = by_k[_TILE:] + into * dq_in
    dbeta = jnp.sum(by_beta, axis=1, keepdims=True) + into * along_k \
        + jnp.sum(drhs_v * v.astype(f32), axis=1, keepdims=True)
    dg = jnp.sum(through, axis=1, keepdims=True) \
        + beta * into * along_k \
        + jnp.sum(dq_in * parts["q_in"], axis=1, keepdims=True) \
        - jnp.sum(dk_out * parts["k_out"], axis=1, keepdims=True)
    position = lax.broadcasted_iota(jnp.int32, (_TILE, 1), 0)
    for m, end in enumerate(ends[::-1]):
        dg = dg + jnp.where(position == spans[m].stop - 1, end, 0.0)
    columns = jnp.where(
        masks.lane == 0, dg, jnp.where(masks.lane == 1, dbeta, 0.0))
    return ds, dq, dk, beta * drhs_v, columns, jnp.sum(
        through, axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("chunk", "rows", "interpret"))
def _backward_kernel(q, k, v, g, beta, starts, do, dlast, *, chunk: int,
                     rows: int, interpret: bool = False):
    """The forward's arguments, the states its blocks started from and the
    cotangents of o (B, T, H, V) and of the final state (B, H, K, V)
    float32. Returns dq, dk, dv in their dtypes and dg, dbeta (B, T, H)
    float32."""
    pl, pltpu = _pallas()
    bsz, t, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[3]
    r, blocks, tiles = h // hk, t // rows, rows // _TILE
    per_tile = _TILE // chunk
    f32 = jnp.float32
    dtype = v.dtype

    def kernel(q_ref, k_ref, v_ref, vec_ref, inverse_ref, starts_ref, do_ref,
               dlast_ref, dq_ref, dk_ref, dv_ref, dvec_ref, ds_ref,
               states_ref, solved_ref):
        @pl.when(pl.program_id(2) == 0)  # a sequence's LAST block
        def _():
            ds_ref[...] = dlast_ref[0]

        masks = _Masks(chunk)
        states_ref[:, 0] = starts_ref[0, 0]

        def operands(n):
            at = pl.ds(pl.multiple_of(n * _TILE, _TILE), _TILE)
            q_t, k_t = q_ref[0, at, :], k_ref[0, at, :]
            return at, q_t, k_t, _nt(k_t, k_t), _nt(q_t, k_t)

        def head(j):
            return slice(j * dv, (j + 1) * dv), slice(
                j * _VEC, (j + 1) * _VEC)

        def forward(n, carry):  # the block's chunks' states again
            at, q_t, k_t, kk, qk = operands(n)
            for j in range(r):
                cols, vec = head(j)
                parts = _tile_parts(
                    q_t, k_t, v_ref[0, at, cols], vec_ref[0, 0, n, vec, :],
                    kk, qk, inverse_ref[0, 0, j, n], masks, chunk)
                solved_ref[j, n] = parts["solved"]

                def keep(m, s):
                    states_ref[j, n * per_tile + m] = s

                # the state after the tile is the next tile's first
                keep(per_tile, _tile_states(
                    parts, states_ref[j, n * per_tile], chunk, dtype,
                    keep)[0])
            return carry

        lax.fori_loop(0, tiles, forward, 0)

        def backward(turn, carry):
            n = tiles - 1 - turn
            at, q_t, k_t, kk, qk = operands(n)
            dq = dk_ = jnp.zeros((_TILE, dk), f32)
            for j in range(r):
                cols, vec = head(j)
                v_t = v_ref[0, at, cols]
                parts = _tile_parts(
                    q_t, k_t, v_t, vec_ref[0, 0, n, vec, :], kk, qk,
                    inverse_ref[0, 0, j, n], masks, chunk, solved_ref[j, n])
                ds, dq_j, dk_j, dv_j, columns, off = _tile_pullback(
                    parts, q_t, k_t, v_t, do_ref[0, at, cols],
                    [states_ref[j, n * per_tile + m]
                     for m in range(per_tile)],
                    ds_ref[j], masks, chunk)
                ds_ref[j] = ds
                dq, dk_ = dq + dq_j, dk_ + dk_j
                dv_ref[0, at, cols] = dv_j.astype(dtype)
                took = jnp.where(
                    lax.broadcasted_iota(jnp.int32, (_VEC, _LANES), 0) == 0,
                    off, 0.0)
                dvec_ref[0, 0, n, vec, :] = columns.T[:_VEC] - took
            dq_ref[0, at, :] = dq.astype(dtype)
            dk_ref[0, at, :] = dk_.astype(dtype)
            return carry

        lax.fori_loop(0, tiles, backward, 0)

    def back(b, j, i):  # the blocks of positions from the last to the first
        return b, blocks - 1 - i, j

    keys = pl.BlockSpec((1, rows, dk), back)
    values = pl.BlockSpec((1, rows, r * dv), back)
    vectors = pl.BlockSpec(
        (1, 1, tiles, r * _VEC, _LANES),
        lambda b, j, i: (b, j, blocks - 1 - i, 0, 0))
    vec = _decay_vectors(g, beta, chunk, hk)
    dq, dk_, dv_, dvec = _call(
        kernel, "gated_delta_rule_backward", interpret,
        out_shape=(
            jax.ShapeDtypeStruct((bsz, t, hk * dk), q.dtype),
            jax.ShapeDtypeStruct((bsz, t, hk * dk), k.dtype),
            jax.ShapeDtypeStruct((bsz, t, h * dv), dtype),
            jax.ShapeDtypeStruct(
                (bsz, hk, t // _TILE, r * _VEC, _LANES), f32)),
        grid=(bsz, hk, blocks),
        in_specs=[
            keys, keys, values, vectors,
            pl.BlockSpec((1, 1, r, tiles, chunk, _TILE),
                         lambda b, j, i: (b, j, 0, blocks - 1 - i, 0, 0)),
            pl.BlockSpec((1, 1, r, dk, dv),
                         lambda b, j, i: (b, blocks - 1 - i, j, 0, 0)),
            values,
            pl.BlockSpec((1, r, dk, dv), lambda b, j, i: (b, j, 0, 0))],
        out_specs=(keys, keys, values, vectors),
        scratch_shapes=[
            pltpu.VMEM((r, dk, dv), f32),
            pltpu.VMEM((r, tiles * per_tile + 1, dk, dv), f32),
            pltpu.VMEM((r, tiles, _TILE, dk + dv), f32)],
    )(q.reshape(bsz, t, hk * dk), k.reshape(bsz, t, hk * dk),
      v.reshape(bsz, t, h * dv), vec,
      _inverse_kernel(k, vec, chunk=chunk, interpret=interpret), starts,
      do.reshape(bsz, t, h * dv), dlast)
    dcum, dbeta = _unvectors(dvec, 2)
    # G is the sum of g over a chunk up to a position: d g_i sums d G from
    # i to the chunk's end
    dg = jnp.flip(jnp.cumsum(jnp.flip(
        dcum.reshape(bsz, t // chunk, chunk, h), axis=2), axis=2), axis=2)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(bsz, t, h), dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kernel_rule(q, k, v, g, beta, chunk: int, rows: int,
                 interpret: bool = False):
    """The two kernels as one differentiable rule: `gated_delta_rule`'s
    arguments with g and beta float32 -> (o, the final state). `interpret`
    runs them without a TPU (the tests' way in)."""
    return _forward_kernel(
        q, k, v, g, beta, chunk=chunk, rows=rows, interpret=interpret)[:2]


def _kernel_rule_fwd(q, k, v, g, beta, chunk, rows, interpret):
    o, last, starts = _forward_kernel(
        q, k, v, g, beta, chunk=chunk, rows=rows, interpret=interpret)
    return (o, last), (q, k, v, g, beta, starts)


def _kernel_rule_bwd(chunk, rows, interpret, res, cotangents):
    return _backward_kernel(
        *res, *cotangents, chunk=chunk, rows=rows, interpret=interpret)


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


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
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    rows = None
    if programs.traced_for_tpu():
        rows = _kernel_rows(
            t, hk, h, dk, dv, chunk, (q.dtype, k.dtype, v.dtype))
    if rows is not None:
        programs.note("delta", "kernel", _programs(q, v, chunk, rows))
        o, state = _kernel_rule(q, k, v, g, beta, chunk, rows, False)
        # the backward program is traced HERE, into jax's cache of traces,
        # and found there by the backward pass (ops/groupmm.py has the
        # measurement)
        jax.eval_shape(
            functools.partial(_backward_kernel, chunk=chunk, rows=rows),
            q, k, v, g, beta, jax.ShapeDtypeStruct(
                (bsz, t // rows, h, dk, dv), jnp.float32), o, state)
        return o, state
    programs.note("delta", "plain")
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


def _programs(q, v, chunk: int, rows: int) -> list[tuple]:
    """The keys of the three kernel programs one rule needs, as jax tells
    programs apart: kernel, shapes, dtypes, chunk, block of positions."""
    shape = (*q.shape, *v.shape[2:], q.dtype.name, chunk)
    return [("inverse", *shape), ("forward", *shape, rows),
            ("backward", *shape, rows)]

