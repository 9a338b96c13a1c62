"""Chunked state-space (SSD, Mamba-2) scan with a backward pass: two entry
points, `ssd_scan` and `ssd_scan_in_place`, and two ways down from them.

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

**Two ways down, chosen by what the code sees** (`_kernel_dims`; no flag).
Traced for a TPU with x, B and C all bfloat16 or all float32, a state of
whole lane tiles, heads of a whole or half a lane tile, chunks of 128 or 256
positions and T a whole number of them: two Pallas kernels of this module's
own under a `custom_vjp`. Anything else (the CPU, every tier-1 test, the
tiny preset at heads of 16 over a state of 8, a T that is padded): the plain
form, which is also the kernels' reference. Each call notes the way it went
and the kernel programs it needs (`ops/programs.py`, op `ssd`: the Trainer's
`ssd_program`). `ssd_scan` takes x, B and C apart; `ssd_scan_in_place` takes
them side by side as ONE array (B, T, H x P + 2 N), the convolution's output
as `models/granite.mamba_mixer` has it, and there the kernels pick a head's
columns, B's and C's by index: no slice, no reshape, no copy on either side
(`ssd_scan` down the kernels' way concatenates first).

**The plain form.** Plain `jax.numpy`; autodiff derives the backward pass.
The (chunks, heads, chunk, chunk) decay matrices are the large temporaries
(0.54 GB in float32 at 32 chunks of 256 and 64 heads), so the chunks go
`block` at a time through a `lax.scan` whose body is under `jax.checkpoint`:
a block's matrices live only inside its own forward and (recomputed)
backward, and what is saved per block is its inputs and the state carried
in.

**The kernels.** A grid over (sequence, chunk), the chunks in order, a grid
step one chunk of ALL the columns; inside it C B^T is taken once, then a
real loop over the lane tiles of x, two heads of 64 (or one of 128) a turn.
A chunk's rows go in blocks of 128 positions and a block reads the columns
up to its own (`_blocks`: the quarter of a chunk of 256 above the diagonal
blocks is never formed). dt and the cumulative log-decay L reach the kernels
as (B, lane tiles, 8, T) float32, positions along the lanes: for each head
of a lane tile L, dt and L at the chunk's end, made by one
small XLA pass over (T, H) (`_vectors`); a turn loads its 8 rows (L_s along
the lanes) and turns them in VMEM (L_t along the sublanes).

  * `ssd_scan_forward`: the float32 state of every head, (H x P, N), in VMEM
    scratch from a sequence's first chunk to its last. A head: decay
    exp(L_t - L_s) from DIFFERENCES masked before the exponential where a
    block meets the diagonal, `mixed = (scores decay)` rounded to x's dtype,
    `y = mixed (dt x) + exp(L) (C S_in)` and `S_out = exp(L_last) S_in +
    (exp(L_last - L) dt x)^T B`: the plain form's four products with its
    operand dtypes, float32 accumulation and roundings, a lane tile's two
    heads side by side wherever the product allows (`C S_in`, the state's
    update). Writes y, the final state and the state each chunk starts from
    (32 x 2 MB a layer at the Granite cell's size), which is all the
    backward pass needs beside the inputs.
  * `ssd_scan_backward`: the chunks in reverse with d S in VMEM; a chunk's
    matrices again from x, dt, B, C and its saved starting state; d x, d B
    and d C leave as ONE array shaped like the input (d B and d C summed
    over the heads in VMEM, float32: no per-head partial reaches HBM); d L
    and d dt through `dt x` leave as per-position vectors, turned back in
    VMEM, and d L's reverse sum over a chunk, d dt and d a are one small XLA
    pass. `ssd_scan_in_place` also takes the mixer's skip, y + d x: forward
    it is plain `jax.numpy` on the kernel's y (it fuses into whatever reads
    it), backward the kernel adds d times the float32 cotangent into d x
    before the one rounding and sums d d over the positions in VMEM: pulled
    back by autodiff the skip was three passes over (T, H x P), a third of
    the kernels' own time.

Every call reaches the kernels through `_forward_kernel` / `_backward_kernel`,
each ONE `jax.jit`ted function, so every layer's forward, its recomputation
under the layer's `jax.checkpoint` and its backward share TWO kernel
programs; the backward's is traced on the way forward (`jax.eval_shape`;
`ops/groupmm.py` has the measurement).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs


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


def _plain_scan(x, dt, a, b, c, chunk: int, block: int):
    """`ssd_scan` down the plain way: dt and a float32 already."""
    bsz, t, h, p = x.shape
    n_state = b.shape[-1]
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


# --- the kernels -----------------------------------------------------------
#
# x, B and C come in as ONE array (B, T, H x P + 2 N), x's heads first, then
# B, then C: the convolution's output as `models/granite.mamba_mixer` has it.
# A grid step takes a chunk of all its columns; the heads are a real loop
# inside it, one LANE TILE of x a turn: two heads of 64 or one of 128.

_LANES = 128
_VEC = 8  # rows a lane tile's heads have in the kernels' per-position vectors
_HEAD = 4  # of them a head's: two heads of half a lane tile fill the eight
# of the chip's 128 MiB of VMEM; the blocks of a grid step, buffered twice,
# may take `_BLOCK_BYTES` of it (the backward's at the Granite cell's size:
# 25 MiB), the rest is for a chunk's (chunk, chunk) matrices
_VMEM_LIMIT = 64 * 2 ** 20
_BLOCK_BYTES = 40 * 2 ** 20
_CHUNKS = (128, 256)  # a chunk's matrices are written out over its registers


class Dims(NamedTuple):
    """What the kernels are built for: heads of `head_dim` channels over a
    state of `state` a channel, chunks of `chunk` positions."""

    heads: int
    head_dim: int
    state: int
    chunk: int


def _kernel_dims(t: int, h: int, p: int, n: int, chunk: int,
                 dtypes) -> Optional[Dims]:
    """The kernels' sizes for this call, or None where the plain form stays:
    x, B and C not all bfloat16 or all float32, a state that is no whole
    number of lane tiles, heads that are neither a whole nor half a lane
    tile (or an odd number of halves), a chunk the kernels were not written
    for, a T that is no whole number of chunks, or blocks that do not fit
    VMEM."""
    if len(set(dtypes)) != 1 or dtypes[0] not in (jnp.bfloat16, jnp.float32):
        return None
    if n % _LANES or p not in (_LANES // 2, _LANES) or (h * p) % _LANES:
        return None
    if chunk not in _CHUNKS or t % chunk:
        return None
    size = jnp.dtype(dtypes[0]).itemsize
    width = h * p + 2 * n
    held = 2 * (2 * chunk * width * size + chunk * h * p * 4
                + 2 * h * p * n * 4) + h * p * n * 4
    return Dims(h, p, n, chunk) if held <= _BLOCK_BYTES else None


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


def _plus(total, more):
    """total + more, where nothing was added yet more."""
    return more if total is None else total + more


def _turned(a):
    """a (rows, cols) float32, both whole lane tiles or one of 8 -> a^T, a
    (128, .) or (., 128) piece at a time."""
    rows, cols = a.shape
    if cols > _LANES:
        return jnp.concatenate([
            a[:, at:at + _LANES].T for at in range(0, cols, _LANES)], axis=0)
    return jnp.concatenate([
        a[at:at + _LANES].T for at in range(0, rows, _LANES)], axis=1)


def _vectors(dt, a, dims: Dims):
    """dt (B, T, H), a (H,) float32 -> (the kernels' per-position vectors
    (B, H / heads a lane tile, 8, T): for each head of a lane tile four
    rows, L (the sum of dt a over a chunk up to and including a position),
    dt, L at the chunk's last position and zeros; the most negative
    whole-chunk sum). One small XLA pass over (T, H)."""
    bsz, t, h = dt.shape
    per = _LANES // dims.head_dim
    cum = jnp.cumsum((dt * a).reshape(bsz, t // dims.chunk, dims.chunk, h),
                     axis=2)
    last = jnp.broadcast_to(cum[:, :, -1:], cum.shape)
    # stacked and turned with H or T minor throughout: three values a
    # position side by side in the lanes cost the chip's compiler tiles of
    # one row
    rows = jnp.stack([cum, dt.reshape(cum.shape), last, jnp.zeros_like(cum)],
                     axis=1).reshape(bsz, _HEAD, t, h).transpose(0, 3, 1, 2)
    rows = rows.reshape(bsz, h // per, per * _HEAD, t)  # (B, H, 4, T) it was
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, _VEC - per * _HEAD), (0, 0)))
    return rows, jnp.min(cum[:, :, -1])


def _by_head(columns, p: int):
    """The (rows, 1) columns of a lane tile's heads -> (rows, 128), each
    along the lanes of its head."""
    rows = columns[0].shape[0]
    if len(columns) == 1:
        return jnp.broadcast_to(columns[0], (rows, _LANES))
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    return jnp.where(lane < p, columns[0], columns[1])


def _of_head(k: int, per: int, p: int, tile, fill=0.0):
    """`tile` (rows, 128) with the lanes of the other head of its lane tile
    at `fill`."""
    if per == 1:
        return tile
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.where((lane < p) == (k == 0), tile, fill)


def _blocks(q: int) -> list[tuple[slice, int]]:
    """A chunk's rows in blocks of 128 positions, each with the columns it
    can see: position t reads s <= t, so block i reads the first 128 (i + 1)
    columns and the rest of its rows is masked away (a quarter of a chunk of
    256 that is never formed)."""
    return [(slice(at, at + _LANES), at + _LANES)
            for at in range(0, q, _LANES)]


def _keep_scores(scores_ref, c, b, q: int) -> None:
    """C B^T by `_blocks` into a (Q, Q) float32 scratch, ONCE for all the
    heads of a chunk."""
    for rows, width in _blocks(q):
        scores_ref[rows, :width] = _nt(c[rows], b[:width])


def _kept_scores(scores_ref, q: int):
    """What `_keep_scores` kept: [(128, 128 (i + 1)) float32]."""
    return [scores_ref[rows, :width] for rows, width in _blocks(q)]


class _Tile:
    """What one lane tile of x (one or two heads) holds over a chunk before
    any cotangent is known. x (Q, 128) as it came; vec (8, Q) float32 as
    `_vectors` makes it; scores: C B^T by `_blocks`; b, c (Q, N) as they
    came; s_in (128, N) float32: the heads' states the chunk starts from,
    head after head along the rows."""

    def __init__(self, x, vec, scores, b, c, s_in, dims: Dims):
        f32 = jnp.float32
        p, q = dims.head_dim, dims.chunk
        per = _LANES // p
        dtype = x.dtype
        cols = _turned(vec)  # (Q, 8): the vectors along the sublanes
        heads = range(per)
        shape = (_LANES, _LANES)
        below = lax.broadcasted_iota(jnp.int32, shape, 0) \
            >= lax.broadcasted_iota(jnp.int32, shape, 1)
        self.per, self.p, self.q, self.dtype = per, p, q, dtype
        self.x32 = x.astype(f32)

        def column(of, m, rows=slice(None)):  # row m of each head's four
            return [of[rows, _HEAD * k + m:_HEAD * k + m + 1]
                    for k in heads]

        self.dt = _by_head(column(cols, 1), p)
        self.u = self.x32 * self.dt  # dt x, (Q, 128)
        self.ub = self.u.astype(dtype)
        self.cols, self.vec, self.below = cols, vec, below
        self.scores = scores
        cum, last = _by_head(column(cols, 0), p), _by_head(column(cols, 2), p)
        self.into = jnp.exp(cum)  # exp(L_t): the carried state's share at t
        self.to_end = jnp.exp(last - cum)
        self.v = (self.u * self.to_end).astype(dtype)
        self.sb = s_in.astype(dtype)
        self.carried = _nt(c, self.sb)  # (Q, 128)
        # exp(L_last) of each head along the rows of its state
        whole = column(cols, 2, slice(_LANES))
        if per == 2:
            at = lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0)
            whole = [jnp.where(at < p, whole[0], whole[1])]
        self.whole = jnp.exp(whole[0])  # (128, 1)

    def head(self, k: int):
        """Head k of the lane tile: (exp(L_t - L_s) for s <= t by `_blocks`,
        masked BEFORE the exponential where a block meets the diagonal (left
        of it t > s); scores times it, rounded to x's dtype)."""
        down = self.cols[:, _HEAD * k:_HEAD * k + 1]
        along = self.vec[_HEAD * k:_HEAD * k + 1]
        decay = []
        for rows, width in _blocks(self.q):
            apart = down[rows] - along[:, :width]
            left = width - _LANES
            met = jnp.where(self.below, apart[:, left:], -jnp.inf)
            decay.append(jnp.exp(met if not left else jnp.concatenate(
                [apart[:, :left], met], axis=1)))
        return decay, [(held * by).astype(self.dtype)
                       for held, by in zip(self.scores, decay)]

    def y(self):
        out = None
        for k in range(self.per):
            _, mixed = self.head(k)
            mine = jnp.concatenate([
                _mm(block, self.ub[:width])
                for block, (_, width) in zip(mixed, _blocks(self.q))], axis=0)
            out = mine if out is None else _of_head(
                k, self.per, self.p, mine, out)
        return out + self.into * self.carried

    def state_out(self, s_in, b):
        return self.whole * s_in + _tn(self.v, b)


def _call(kernel, name: str, interpret: bool, **spec):
    """`pl.pallas_call` over a grid of (sequence, chunk), in order: the
    state is carried in scratch."""
    pl, pltpu = _pallas()
    return pl.pallas_call(
        kernel, **spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _forward_kernel(xbc, dt, a, *, dims: Dims, interpret: bool = False):
    """xbc (B, T, H x P + 2 N); dt (B, T, H), a (H,) float32. Returns (y (B,
    T, H x P) in xbc's dtype, the state after the last position (B, H x P,
    N) float32, the state each chunk starts from (B, T / chunk, H x P, N)
    float32, the most negative whole-chunk sum of log-decays)."""
    pl, pltpu = _pallas()
    bsz, t, width = xbc.shape
    h, p, n, q = dims
    inner, tiles, chunks = h * p, h * p // _LANES, t // q
    f32 = jnp.float32

    def kernel(xbc_ref, vec_ref, y_ref, last_ref, starts_ref, s_ref,
               scores_ref):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _():
            s_ref[...] = jnp.zeros(s_ref.shape, f32)

        b = xbc_ref[0, :, inner:inner + n]
        c = xbc_ref[0, :, inner + n:]
        _keep_scores(scores_ref, c, b, q)

        def tile(j, carry):
            at = pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES)
            s_in = s_ref[at, :]
            starts_ref[0, 0, at, :] = s_in
            held = _Tile(xbc_ref[0, :, at], vec_ref[0, j],
                         _kept_scores(scores_ref, q), b, c, s_in, dims)
            y_ref[0, :, at] = held.y().astype(y_ref.dtype)
            s_ref[at, :] = held.state_out(s_in, b)
            return carry

        lax.fori_loop(0, tiles, tile, 0)

        @pl.when(i == chunks - 1)
        def _():
            last_ref[0] = s_ref[...]

    vec, low = _vectors(dt, a, dims)
    y, last, starts = _call(
        kernel, "ssd_scan_forward", interpret,
        out_shape=(
            jax.ShapeDtypeStruct((bsz, t, inner), xbc.dtype),
            jax.ShapeDtypeStruct((bsz, inner, n), f32),
            jax.ShapeDtypeStruct((bsz, chunks, inner, n), f32)),
        grid=(bsz, chunks),
        in_specs=[
            pl.BlockSpec((1, q, width), lambda s, i: (s, i, 0)),
            pl.BlockSpec((1, tiles, _VEC, q), lambda s, i: (s, 0, 0, i))],
        out_specs=(
            pl.BlockSpec((1, q, inner), lambda s, i: (s, i, 0)),
            pl.BlockSpec((1, inner, n), lambda s, i: (s, 0, 0)),
            pl.BlockSpec((1, 1, inner, n), lambda s, i: (s, i, 0, 0))),
        scratch_shapes=[pltpu.VMEM((inner, n), f32), pltpu.VMEM((q, q), f32)],
    )(xbc, vec)
    return y, last, starts, low


@functools.partial(jax.jit, static_argnames=("dims", "interpret"))
def _backward_kernel(xbc, dt, a, skip, starts, dy, dlast, *, dims: Dims,
                     interpret: bool = False):
    """The forward's arguments, the skip's weight a head (H,) float32, the
    states the forward's chunks started from and the cotangents of y + skip
    x (B, T, H x P) and of the final state (B, H x P, N), float32. Returns
    d xbc in its dtype (the skip's share of d x in it), d dt (B, T, H), d a
    and d skip (H,) float32."""
    pl, pltpu = _pallas()
    bsz, t, width = xbc.shape
    h, p, n, q = dims
    inner, tiles, chunks = h * p, h * p // _LANES, t // q
    per = _LANES // p
    f32 = jnp.float32
    dtype = xbc.dtype

    def kernel(xbc_ref, vec_ref, skip_ref, starts_ref, dy_ref, dlast_ref,
               dxbc_ref, dvec_ref, dskip_ref, ds_ref, scores_ref,
               dscores_ref, db_ref, dc_ref):
        @pl.when(pl.program_id(1) == 0)  # a sequence's LAST chunk
        def _():
            ds_ref[...] = dlast_ref[0]

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            dskip_ref[...] = jnp.zeros(dskip_ref.shape, f32)

        b = xbc_ref[0, :, inner:inner + n]
        c = xbc_ref[0, :, inner + n:]
        _keep_scores(scores_ref, c, b, q)
        dscores_ref[...] = jnp.zeros((q, q), f32)
        db_ref[...] = jnp.zeros((q, n), f32)
        dc_ref[...] = jnp.zeros((q, n), f32)
        lane = lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
        sublane = lax.broadcasted_iota(jnp.int32, (_VEC, q), 0)
        at_end = lax.broadcasted_iota(jnp.int32, (_VEC, q), 1) == q - 1
        state_row = lax.broadcasted_iota(jnp.int32, (_LANES, n), 0)

        def tile(j, carry):
            at = pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES)
            s_in, ds = starts_ref[0, 0, at, :], ds_ref[at, :]
            held = _Tile(xbc_ref[0, :, at], vec_ref[0, j],
                         _kept_scores(scores_ref, q), b, c, s_in, dims)
            # the scan's y was rounded to x's dtype before the skip was
            # added: its cotangent is rounded so, the skip's is not
            dy32 = dy_ref[0, :, at]
            dy_ = dy32.astype(dtype)
            dsb = ds.astype(dtype)
            # y = mixed (dt x) + exp(L) (C S_in);  S_out = exp(L_last) S_in
            # + (exp(L_last - L) dt x)^T B
            dcarried = held.into * dy_.astype(f32)
            dv = _nt(b, dsb)  # (Q, 128)
            dv_out = dv * held.to_end
            du = dv_out
            # what the log-decays collect along a row: L_t through exp(L_t)
            # and exp(L_last - L_t); L_last gets the second's sum back
            through_end = dv_out * held.u
            along = dcarried * held.carried - through_end
            through_whole = held.whole * ds * s_in  # d exp(L_last), by row
            rows, ends, columns = [], [], []
            for k in range(per):
                dy_k = _of_head(k, per, p, dy_)
                decay, mixed = held.head(k)
                # by column tile: what d (dt x) and d L_s collect there
                du_k = [None] * len(_blocks(q))
                lost = [None] * len(_blocks(q))
                folded = []
                for i, (span, width) in enumerate(_blocks(q)):
                    dmixed = _nt(dy_k[span], held.ub[:width])
                    by_decay = dmixed * decay[i]
                    dscores_ref[span, :width] += by_decay
                    # exp(L_t - L_s): d L_t gets its row, d L_s loses its
                    # column
                    through = by_decay * held.scores[i]
                    fold = None
                    for m in range(i + 1):
                        at_m = slice(m * _LANES, (m + 1) * _LANES)
                        more = _tn(mixed[i][:, at_m], dy_k[span])
                        du_k[m] = _plus(du_k[m], more)
                        lost[m] = _plus(lost[m], jnp.sum(
                            through[:, at_m], axis=0, keepdims=True))
                        fold = _plus(fold, through[:, at_m])
                    folded.append(fold)
                du = du + jnp.concatenate(du_k, axis=0)
                rows.append(jnp.concatenate(folded, axis=0)
                            + _of_head(k, per, p, along))
                columns.append(jnp.concatenate(lost, axis=1))
                mine = state_row // p == k
                ends.append(
                    jnp.sum(_of_head(k, per, p, through_end), keepdims=True)
                    + jnp.sum(jnp.where(mine, through_whole, 0.0),
                              keepdims=True))
            dc_ref[...] += _mm(dcarried.astype(dtype), held.sb)
            db_ref[...] += _mm(held.v, dsb)
            ds_ref[at, :] = held.whole * ds + _tn(dcarried.astype(dtype), c)
            dxbc_ref[0, :, at] = (
                du * held.dt + skip_ref[j] * dy32).astype(dtype)
            dskip_ref[j] += jnp.sum(dy32 * held.x32, axis=0, keepdims=True)
            by_dt = du * held.x32
            # the heads' columns (d L, then d dt through dt x) in the first
            # lanes of one tile, turned so that positions lie along the lanes
            placed = jnp.zeros((q, _LANES), f32)
            for k in range(per):
                for m, column in enumerate((
                        jnp.sum(rows[k], axis=1, keepdims=True),
                        jnp.sum(_of_head(k, per, p, by_dt), axis=1,
                                keepdims=True))):
                    placed = jnp.where(lane == _HEAD * k + m, column, placed)
            out = _turned(placed)[:_VEC]  # (8, Q)
            for k in range(per):
                out = out + jnp.where(
                    sublane == _HEAD * k,
                    jnp.where(at_end, ends[k], 0.0) - columns[k], 0.0)
            dvec_ref[0, j] = out
            return carry

        lax.fori_loop(0, tiles, tile, 0)
        dscores = dscores_ref[...].astype(dtype)
        dxbc_ref[0, :, inner:inner + n] = (
            db_ref[...] + _tn(dscores, c)).astype(dtype)
        dxbc_ref[0, :, inner + n:] = (
            dc_ref[...] + _mm(dscores, b)).astype(dtype)

    def back(s, i):  # the chunks from the last to the first
        return s, chunks - 1 - i, 0

    vectors = pl.BlockSpec(
        (1, tiles, _VEC, q), lambda s, i: (s, 0, 0, chunks - 1 - i))
    by_lane = pl.BlockSpec((tiles, 1, _LANES), lambda s, i: (0, 0, 0))
    dxbc, dvec, dskip = _call(
        kernel, "ssd_scan_backward", interpret,
        out_shape=(
            jax.ShapeDtypeStruct(xbc.shape, dtype),
            jax.ShapeDtypeStruct((bsz, tiles, _VEC, t), f32),
            jax.ShapeDtypeStruct((tiles, 1, _LANES), f32)),
        grid=(bsz, chunks),
        in_specs=[
            pl.BlockSpec((1, q, width), back), vectors, by_lane,
            pl.BlockSpec((1, 1, inner, n),
                         lambda s, i: (s, chunks - 1 - i, 0, 0)),
            pl.BlockSpec((1, q, inner), back),
            pl.BlockSpec((1, inner, n), lambda s, i: (s, 0, 0))],
        out_specs=(pl.BlockSpec((1, q, width), back), vectors, by_lane),
        scratch_shapes=[
            pltpu.VMEM((inner, n), f32), pltpu.VMEM((q, q), f32),
            pltpu.VMEM((q, q), f32), pltpu.VMEM((q, n), f32),
            pltpu.VMEM((q, n), f32)],
    )(xbc, _vectors(dt, a, dims)[0],
      jnp.repeat(skip, p).reshape(tiles, 1, _LANES), starts, dy, dlast)
    # (B, tiles, 8, T) -> d L and d dt through dt x, (B, T, H) each
    dvec = dvec[:, :, :_HEAD * per].reshape(bsz, h, _HEAD, t)
    dcum, by_x = (dvec[:, :, m].transpose(0, 2, 1) for m in range(2))
    # L is the sum of dt a over a chunk up to a position: d (dt a) at t sums
    # d L from t to the chunk's end
    dlog = jnp.flip(jnp.cumsum(jnp.flip(
        dcum.reshape(bsz, chunks, q, h), axis=2), axis=2),
        axis=2).reshape(bsz, t, h)
    return (dxbc, by_x + dlog * a, jnp.sum(dlog * dt, axis=(0, 1)),
            jnp.sum(dskip.reshape(h, p), axis=1))


def _with_skip(xbc, y, skip, dims: Dims):
    """y (B, T, H x P) in xbc's dtype -> y + skip x, (B, T, H, P) float32."""
    bsz, t, inner = y.shape
    heads = (bsz, t, dims.heads, dims.head_dim)
    return y.reshape(heads).astype(jnp.float32) + skip[:, None] \
        * xbc[..., :inner].reshape(heads).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _kernel_rule(xbc, dt, a, skip, dims: Dims, interpret: bool = False):
    """The two kernels as one differentiable scan with the mixer's skip on
    it: xbc (B, T, H x P + 2 N), dt (B, T, H), a and skip (H,) float32 ->
    (y + skip x (B, T, H, P) float32, y the scan's result rounded to xbc's
    dtype; the final state (B, H x P, N) float32; the most negative
    whole-chunk sum of log-decays, without gradient). The skip is plain
    `jax.numpy` on the way forward (it fuses into whatever reads it) and the
    backward kernel's on the way back: pulled back by autodiff it is three
    passes over (T, H x P), 1.3 ms a layer at the Granite cell's size, a
    third of them this module's kernels' time (PERF.md section 6, PR 46).
    `interpret` runs the kernels without a TPU (the tests' way in)."""
    y, last, _, low = _forward_kernel(
        xbc, dt, a, dims=dims, interpret=interpret)
    return _with_skip(xbc, y, skip, dims), last, low


def _kernel_rule_fwd(xbc, dt, a, skip, dims, interpret):
    y, last, starts, low = _forward_kernel(
        xbc, dt, a, dims=dims, interpret=interpret)
    return (_with_skip(xbc, y, skip, dims), last, low), (
        xbc, dt, a, skip, starts)


def _kernel_rule_bwd(dims, interpret, res, cotangents):
    dy, dlast, _ = cotangents
    bsz, t = dy.shape[:2]
    return _backward_kernel(
        *res, dy.reshape(bsz, t, -1), dlast, dims=dims, interpret=interpret)


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def _programs(xbc, dims: Dims) -> list[tuple]:
    """The keys of the two kernel programs one scan needs, as jax tells
    programs apart: kernel, shapes, dtype, sizes."""
    shape = (*xbc.shape, xbc.dtype.name, *dims)
    return [("forward", *shape), ("backward", *shape)]


def _kernel_scan(xbc, dt, a, skip, dims: Dims, interpret: bool = False):
    """(y + skip x (B, T, H, P) float32, the final state (B, H, P, N), the
    most negative whole-chunk sum of log-decays) down the kernels' way, from
    x, B and C side by side."""
    bsz, t, _ = xbc.shape
    h, p, n, q = dims
    f32 = jnp.float32
    programs.note("ssd", "kernel", _programs(xbc, dims))
    y, last, low = _kernel_rule(xbc, dt, a, skip, dims, interpret)
    # the backward program is traced HERE, into jax's cache of traces, and
    # found there by the backward pass (ops/groupmm.py has the measurement)
    jax.eval_shape(
        functools.partial(_backward_kernel, dims=dims, interpret=interpret),
        xbc, dt, a, skip,
        jax.ShapeDtypeStruct((bsz, t // q, h * p, n), f32),
        jax.ShapeDtypeStruct((bsz, t, h * p), f32), last)
    return y, last.reshape(bsz, h, p, n), lax.stop_gradient(low)


def _dims_here(t, h, p, n, chunk, dtypes) -> Optional[Dims]:
    """`_kernel_dims` where what is traced now will be lowered for a TPU."""
    if not programs.traced_for_tpu():
        return None
    return _kernel_dims(t, h, p, n, chunk, dtypes)


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
    skip term of the Mamba-2 mixer is the caller's. `block` chunks are
    recomputed together by the plain form; the kernels take no notice of
    it."""
    bsz, t, h, p = x.shape
    dt = dt.astype(jnp.float32)
    a = a.astype(jnp.float32)
    dims = _dims_here(
        t, h, p, b.shape[-1], chunk, (x.dtype, b.dtype, c.dtype))
    if dims is None:
        programs.note("ssd", "plain")
        return _plain_scan(x, dt, a, b, c, chunk, block)
    y, state, low = _kernel_scan(jnp.concatenate(
        [x.reshape(bsz, t, h * p), b, c], axis=-1), dt, a,
        jnp.zeros((h,), jnp.float32), dims)
    return y.astype(x.dtype), state, low


def ssd_scan_in_place(
    xbc: jax.Array, x: jax.Array, dt: jax.Array, a: jax.Array, d: jax.Array,
    *, chunk: int = 256, block: int = 8,
):
    """`ssd_scan` of x, B and C where they lie, with the mixer's skip on its
    result: xbc (B, T, H x P + 2 N) holds x's heads, then B, then C (the
    convolution's output), x (B, T, H, P) is its first H x P columns as the
    caller has cut them out already, d (H,) the skip's weight a head.
    Returns (y + d x (B, T, H, P) float32, with y rounded to x's dtype
    first; the final state; the most negative chunk sum). The kernels read
    xbc and pick the columns by index, so nothing is sliced or copied on
    either side of them, and pull the skip back themselves; the plain form
    takes x, cuts B and C out and adds the skip after."""
    bsz, t, h, p = x.shape
    n = (xbc.shape[-1] - h * p) // 2
    f32 = jnp.float32
    dims = _dims_here(t, h, p, n, chunk, (xbc.dtype, x.dtype))
    if dims is not None:
        return _kernel_scan(
            xbc, dt.astype(f32), a.astype(f32), d.astype(f32), dims)
    y, state, low = ssd_scan(
        x, dt, a, xbc[..., h * p:h * p + n], xbc[..., h * p + n:],
        chunk=chunk, block=block)
    return y.astype(f32) + (d.astype(f32)[:, None] * x.astype(f32)), state, low
