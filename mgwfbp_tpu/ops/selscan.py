"""Selective scan (Mamba-1) with a backward pass. One entry point,
`selective_scan`, and two ways down from it.

Per channel d of D and state n of N, with an input x_t in R^D, a time step
dt_t > 0 in R^D, a decay rate A < 0 in R^(D x N), and B_t, C_t in R^N shared
by all channels:

    h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t x_t) (x) B_t       (D x N a token)
    y_t = h_t C_t

The decay differs by channel AND state, so the work has no matrix-product
form (ops/ssd.py's has: one decay a head).

**The kernel.** Where the step is traced for a TPU and the shape fits
(`_kernel_tiles`: the channels whole lane tiles, the states whole sublane
tiles, T a multiple of the block of positions), the recurrence is taken one
position at a time with the state in VMEM, by two Pallas programs of this
module's own under a `custom_vjp`. Both run a grid over (sequence, block of
`rows` positions, strip of `cols` channels), the strips innermost, so that a
block's B_t and C_t come in once and are used by every strip. A strip's
state, (N, cols) float32 with the states in the sublanes and the channels in
the lanes, stays in VMEM scratch from a sequence's first block to its last;
inside a block it is carried in registers. B_t and C_t are handed over with
each state's value repeated across 128 lanes, (T, N, 128) in the dtype they
came in: the outer product with a channel row is then a plain product of
registers, and no lane broadcast runs in the loop.

  * forward: x, dt, B, C come in once (x, B, C in the dtype they were
    handed over in, converted in VMEM), y goes out once, and beside y and
    the final state the kernel writes the state each block STARTS from (T /
    rows x N x D float32: 10 MB a layer at the Phi-4-mini-flash cell's
    size), which is all the backward pass needs beside the inputs.
  * backward: the blocks in reverse. A block recomputes its positions'
    states from its saved start into VMEM (rows x N x cols float32, 8 MiB),
    then runs the transposed recurrence with dh carried in registers:
    dh_t = C_t (x) dy_t + exp(dt_{t+1} (x) A) . dh_{t+1}, and from it d x,
    d dt (through dt x and through the decay), d A (summed over positions
    in registers, over blocks in VMEM), d B and d C (summed over a strip's
    channels down to 128 lanes position by position, over the strips in
    VMEM, and over the lanes once a block, after its last strip). The final
    state's cotangent enters as dh after the last position.

No (positions, N, D) array reaches HBM in either pass. Every call reaches
the programs through `_forward_kernel` / `_backward_kernel`, each ONE
`jax.jit`ted function, so jax traces and lowers each distinct (shapes,
dtypes, tiles) once per step program: every layer's forward, its
recomputation under the layer's `jax.checkpoint` and its backward share TWO
kernel programs. The backward's is traced on the way forward (`jax.eval_shape`),
where a trace costs a fifth of what it costs inside the backward pass
(PERF.md, PR 35).

**The plain chunked form** (`chunked_scan`). Everywhere else (the CPU, every
tier-1 test, a shape that misfits), and as the kernel's reference: the
sequence is cut into chunks of `chunk` positions, and all the chunks of a
block take their steps TOGETHER, each from a zero state: `chunk` sequential
steps over (chunks, N, D) instead of T over (N, D). With L_t the sum of dt
over the chunk up to and including t, the state a chunk was handed adds

    y_t += sum_n C_t[n] exp(L_t (x) A)[., n] H_in[., n]

and the state it hands on is exp(L_last (x) A) . H_in + its own last state:
one short step a chunk, in order. Plain `jax.numpy`; autodiff derives the
backward pass. The blocks of `block` chunks go through a `lax.scan` whose
body is under `jax.checkpoint`: the states of a block's positions (block x
chunk x D x N float32: 336 MB at 16 chunks of 64 and D x N = 5,120 x 16; its
backward holds four or five arrays of that size at once, 1.54 GiB of scratch
as the chip's compiler counts it) live only inside that block's own forward
and (recomputed) backward. No (T, D, N) array (2.5 GiB at T 8,192) exists in
either pass.

**Range.** Every exponent is dt, or in the chunked form a sum of dt over a
stretch of one chunk, times A: <= 0, so no factor can overflow and nothing
is ever divided by a decay. Everything here is float32 whatever the inputs'
dtype: time steps, decays, sums, the state and y.

Each scan notes the way it went ("kernel", "plain") and the kernel programs
it needs (`ops/programs.py`, op `scan`), for the Trainer's `scan_program`
telemetry record.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs

_LANES = 128
_SUBLANES = 8  # of a float32 tile; a two-byte dtype's tile has twice as many
# positions one turn of a kernel's loop takes, written out: the scheduler
# hides a position's exponentials and outer products behind the two
# dependent operations of the one before (a Pallas `fori_loop` unrolls by 1
# or wholly)
_UNROLL = 8
# of the chip's 128 MiB of VMEM: the backward's blocks, buffered twice, and
# its scratch take about 20 MiB at the tiles below
_VMEM_LIMIT = 64 * 2 ** 20


class Tiles(NamedTuple):
    """A grid step of either kernel: `rows` positions of `cols` channels."""

    rows: int
    cols: int


# Positions a block; channels a strip, the widest that divides D. From a sweep
# of the two kernels alone on a v5e at T 8,192 x 5,120 x 16, batch 1, bf16 x,
# B, C (my chip run, PR 39; ms, host clock, forward / forward + backward,
# where the chunked form reads 13.45 / 59.03): 64 x 512 2.64 / 7.03, 128 x
# 512 2.53 / 6.71, 256 x 512 2.33 / 6.68, 128 x 1,024 2.35 / 6.46, 256 x
# 1,024 2.27 / 6.62, 64 x 256 3.06 / 8.06 (that one with d B and d C summed
# outside). The compiled loops cost the same a lane tile at every width (44
# bundles forward, 21 + 88 backward, for 8 positions of 128 channels), so the
# tiles only set how often a grid step's fixed work is paid: 256 positions of
# 512 channels, because a strip of 1,024 doubles what is traced and compiled
# for 2% of the time.
_ROWS = 256
_COLS = (512, 256, 128)


def _kernel_tiles(t: int, d: int, n: int, dtypes) -> Optional[Tiles]:
    """The kernels' tiles for T positions of D channels and N states, or None
    where the chunked form stays: x, B or C (`dtypes`, in that order) not
    bfloat16 or float32, a D that is no whole number of lane tiles, an N
    that is no whole number of sublane tiles of B's and C's dtype, or a T
    the block of positions does not divide."""
    if any(v not in (jnp.bfloat16, jnp.float32) for v in dtypes):
        return None
    sublanes = max(
        _SUBLANES * 4 // jnp.dtype(v).itemsize for v in dtypes[1:])
    if d % _LANES or n % sublanes or t % _ROWS:
        return None
    return Tiles(_ROWS, next(c for c in _COLS if d % c == 0))


def _pallas():
    """Pallas, imported where a kernel is wanted (the CPU and the models
    without a selective scan never pay for it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def _across_lanes(v):
    """(B, T, N) -> (B, T, N, 128): every state's value across a lane tile."""
    return jnp.broadcast_to(v[..., None], (*v.shape, _LANES))


def _lane_tiles(cols: int) -> list[slice]:
    return [slice(at, at + _LANES) for at in range(0, cols, _LANES)]


def _steps(pl, rows: int, a, h, dt_ref, u_ref, b_ref, each):
    """`rows` positions of the recurrence, in order, on one strip. a, h:
    tuples of (N, 128) float32, one a lane tile; dt_ref, u_ref (rows, cols)
    float32 (dt and dt x); b_ref (rows, N, 128) float32. `each(first, k, i,
    h)` is handed the new state of lane tile i at position first + k, `first`
    a multiple of `_UNROLL` and k a Python int: a row of a (rows, cols)
    block is read and written through the aligned view of `_UNROLL` rows
    that holds it, at k (Mosaic takes no other row index that is not known
    when it compiles). Returns the last state."""
    lanes = _lane_tiles(len(a) * _LANES)

    def turn(q, h):
        first = pl.multiple_of(q * _UNROLL, _UNROLL)
        dt8, u8 = (ref.at[pl.ds(first, _UNROLL)] for ref in (dt_ref, u_ref))
        for k in range(_UNROLL):
            b_t = b_ref[first + k]
            new = []
            for i, at in enumerate(lanes):
                h_i = (jnp.exp(dt8[k:k + 1, at] * a[i]) * h[i]
                       + u8[k:k + 1, at] * b_t)
                each(first, k, i, h_i)
                new.append(h_i)
            h = tuple(new)
        return h

    return lax.fori_loop(0, rows // _UNROLL, turn, h)


def _call(kernel, name: str, interpret: bool, **spec):
    """`pl.pallas_call` over a grid of (sequence, block of positions, strip
    of channels), every axis in order: the state is carried in scratch."""
    pl, pltpu = _pallas()
    return pl.pallas_call(
        kernel, **spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _forward_kernel(x, dt, a, b, c, *, tiles: Tiles, interpret: bool = False):
    """x (B, T, D); dt (B, T, D) float32; a (N, D) float32; b, c (B, T, N).
    Returns (y (B, T, D) float32, the state after the last position (B, N,
    D) float32, the state each block of positions starts from (B, T / rows,
    N, D) float32)."""
    pl, pltpu = _pallas()
    rows, cols = tiles
    (bsz, t, d), n = x.shape, a.shape[0]
    blocks, strips = t // rows, d // cols
    lanes = _lane_tiles(cols)
    f32 = jnp.float32

    def kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, last_ref,
               starts_ref, h_ref, u_ref, bf_ref, cf_ref):
        i, j = pl.program_id(1), pl.program_id(2)

        @pl.when(i == 0)
        def _():
            h_ref[j] = jnp.zeros((n, cols), f32)

        @pl.when(j == 0)
        def _():
            bf_ref[...] = b_ref[0].astype(f32)
            cf_ref[...] = c_ref[0].astype(f32)

        starts_ref[0, 0] = h_ref[j]
        u_ref[...] = x_ref[0].astype(f32) * dt_ref[0]

        def each(first, k, i, h_i):
            y_ref.at[0, pl.ds(first, _UNROLL)][k:k + 1, lanes[i]] = jnp.sum(
                cf_ref[first + k] * h_i, axis=0, keepdims=True)

        h = _steps(
            pl, rows, tuple(a_ref[:, at] for at in lanes),
            tuple(h_ref[j, :, at] for at in lanes), dt_ref.at[0], u_ref,
            bf_ref, each)
        for at, h_i in zip(lanes, h):
            h_ref[j, :, at] = h_i
            last_ref[0, :, at] = h_i

    strip = pl.BlockSpec((1, rows, cols), lambda s, i, j: (s, i, j))
    states = pl.BlockSpec((1, rows, n, _LANES), lambda s, i, j: (s, i, 0, 0))
    return _call(
        kernel, "selective_scan_forward", interpret,
        out_shape=(
            jax.ShapeDtypeStruct((bsz, t, d), f32),
            jax.ShapeDtypeStruct((bsz, n, d), f32),
            jax.ShapeDtypeStruct((bsz, blocks, n, d), f32)),
        grid=(bsz, blocks, strips),
        in_specs=[
            strip, strip, pl.BlockSpec((n, cols), lambda s, i, j: (0, j)),
            states, states],
        out_specs=(
            strip, pl.BlockSpec((1, n, cols), lambda s, i, j: (s, 0, j)),
            pl.BlockSpec((1, 1, n, cols), lambda s, i, j: (s, i, 0, j))),
        scratch_shapes=[
            pltpu.VMEM((strips, n, cols), f32),
            pltpu.VMEM((rows, cols), f32),
            pltpu.VMEM((rows, n, _LANES), f32),
            pltpu.VMEM((rows, n, _LANES), f32)],
    )(x, dt, a, _across_lanes(b), _across_lanes(c))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _backward_kernel(x, dt, a, b, c, starts, dy, dlast, *, tiles: Tiles,
                     interpret: bool = False):
    """The forward's arguments, the states its blocks started from and the
    cotangents of y (B, T, D) and of the final state (B, N, D), float32.
    Returns d x (x's dtype), d dt (B, T, D), d a (N, D), d b and d c (B, T,
    N), float32."""
    pl, pltpu = _pallas()
    rows, cols = tiles
    (bsz, t, d), n = x.shape, a.shape[0]
    blocks, strips = t // rows, d // cols
    lanes = _lane_tiles(cols)
    f32 = jnp.float32

    def kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, starts_ref, dy_ref,
               dlast_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
               dh_ref, hs_ref, u_ref, bf_ref, cf_ref, du_ref, dd_ref,
               dbs_ref, dcs_ref):
        s, i, j = (pl.program_id(axis) for axis in range(3))

        @pl.when(i == 0)  # a sequence's LAST block of positions
        def _():
            dh_ref[j] = dlast_ref[0]

        @pl.when((s == 0) & (i == 0))
        def _():
            da_ref[j] = jnp.zeros((n, cols), f32)

        @pl.when(j == 0)
        def _():
            bf_ref[...] = b_ref[0].astype(f32)
            cf_ref[...] = c_ref[0].astype(f32)
            dbs_ref[...] = jnp.zeros(dbs_ref.shape, f32)
            dcs_ref[...] = jnp.zeros(dcs_ref.shape, f32)

        u_ref[...] = x_ref[0].astype(f32) * dt_ref[0]
        a = tuple(a_ref[:, at] for at in lanes)
        dt_blk = dt_ref.at[0]
        # the block's states again: position t's in hs_ref[t + 1], under
        # the state it started from
        hs_ref[0] = starts_ref[0, 0]

        def each(first, k, i, h_i):
            hs_ref[first + k + 1, :, lanes[i]] = h_i

        _steps(pl, rows, a, tuple(hs_ref[0, :, at] for at in lanes), dt_blk,
               u_ref, bf_ref, each)

        def turn(q, carry):
            dh, da = carry
            first = pl.multiple_of(
                (rows // _UNROLL - 1 - q) * _UNROLL, _UNROLL)
            dt8, dy8, u8, du8, dd8 = (
                ref.at[pl.ds(first, _UNROLL)]
                for ref in (dt_blk, dy_ref.at[0], u_ref, du_ref, dd_ref))
            for k in reversed(range(_UNROLL)):
                t = first + k
                b_t, c_t = bf_ref[t], cf_ref[t]
                db_t = dc_t = jnp.zeros((n, _LANES), f32)
                carried, summed = [], []
                for i, at in enumerate(lanes):
                    row = (slice(k, k + 1), at)
                    dt_t, dy_t = dt8[row], dy8[row]
                    dh_i = dh[i] + dy_t * c_t
                    dc_t = dc_t + dy_t * hs_ref[t + 1, :, at]
                    db_t = db_t + u8[row] * dh_i
                    du8[row] = jnp.sum(b_t * dh_i, axis=0, keepdims=True)
                    # what position t - 1 is handed, and with the state
                    # before it the cotangent of the exponent dt (x) A
                    dh_i = dh_i * jnp.exp(dt_t * a[i])
                    de_i = dh_i * hs_ref[t, :, at]
                    dd8[row] = jnp.sum(a[i] * de_i, axis=0, keepdims=True)
                    carried.append(dh_i)
                    summed.append(da[i] + dt_t * de_i)
                dh, da = tuple(carried), tuple(summed)
                dbs_ref[t] += db_t
                dcs_ref[t] += dc_t
            return dh, da

        dh, da = lax.fori_loop(
            0, rows // _UNROLL, turn,
            (tuple(dh_ref[j, :, at] for at in lanes),
             tuple(da_ref[j, :, at] for at in lanes)))
        for at, dh_i, da_i in zip(lanes, dh, da):
            dh_ref[j, :, at] = dh_i
            da_ref[j, :, at] = da_i

        @pl.when(j == strips - 1)
        def _():
            db_ref[0] = jnp.sum(dbs_ref[...], axis=-1)
            dc_ref[0] = jnp.sum(dcs_ref[...], axis=-1)

        dx_ref[0] = (du_ref[...] * dt_ref[0]).astype(dx_ref.dtype)
        ddt_ref[0] = dd_ref[...] + du_ref[...] * x_ref[0].astype(f32)

    def back(s, i, j):  # the blocks of positions from the last to the first
        return s, blocks - 1 - i, j

    strip = pl.BlockSpec((1, rows, cols), back)
    states = pl.BlockSpec(
        (1, rows, n, _LANES), lambda s, i, j: (s, blocks - 1 - i, 0, 0))
    whole = pl.BlockSpec((strips, n, cols), lambda s, i, j: (0, 0, 0))
    summed = pl.BlockSpec(
        (1, rows, n), lambda s, i, j: (s, blocks - 1 - i, 0))
    dx, ddt, da, db, dc = _call(
        kernel, "selective_scan_backward", interpret,
        out_shape=(
            jax.ShapeDtypeStruct((bsz, t, d), x.dtype),
            jax.ShapeDtypeStruct((bsz, t, d), f32),
            jax.ShapeDtypeStruct((strips, n, cols), f32),
            jax.ShapeDtypeStruct((bsz, t, n), f32),
            jax.ShapeDtypeStruct((bsz, t, n), f32)),
        grid=(bsz, blocks, strips),
        in_specs=[
            strip, strip, pl.BlockSpec((n, cols), lambda s, i, j: (0, j)),
            states, states,
            pl.BlockSpec((1, 1, n, cols),
                         lambda s, i, j: (s, blocks - 1 - i, 0, j)),
            strip, pl.BlockSpec((1, n, cols), lambda s, i, j: (s, 0, j))],
        out_specs=(strip, strip, whole, summed, summed),
        scratch_shapes=[
            pltpu.VMEM((strips, n, cols), f32),
            pltpu.VMEM((rows + 1, n, cols), f32),
            pltpu.VMEM((rows, cols), f32),
            pltpu.VMEM((rows, n, _LANES), f32),
            pltpu.VMEM((rows, n, _LANES), f32),
            pltpu.VMEM((rows, cols), f32),
            pltpu.VMEM((rows, cols), f32),
            pltpu.VMEM((rows, n, _LANES), f32),
            pltpu.VMEM((rows, n, _LANES), f32)],
    )(x, dt, a, _across_lanes(b), _across_lanes(c), starts, dy, dlast)
    return dx, ddt, jnp.moveaxis(da, 0, 1).reshape(n, d), db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_scan(x, dt, a, b, c, tiles: Tiles, interpret: bool = False):
    """The two kernels as one differentiable scan: x (B, T, D), dt (B, T, D)
    float32, a (N, D) float32, b and c (B, T, N) -> (y (B, T, D), the final
    state (B, N, D)), float32. `interpret` runs them without a TPU (the
    tests' way in)."""
    return _forward_kernel(x, dt, a, b, c, tiles=tiles, interpret=interpret)[:2]


def _kernel_scan_fwd(x, dt, a, b, c, tiles, interpret):
    y, last, starts = _forward_kernel(
        x, dt, a, b, c, tiles=tiles, interpret=interpret)
    return (y, last), (x, dt, a, b, c, starts)


def _kernel_scan_bwd(tiles, interpret, res, g):
    x, dt, a, b, c, starts = res
    dx, ddt, da, db, dc = _backward_kernel(
        x, dt, a, b, c, starts, *g, tiles=tiles, interpret=interpret)
    return dx, ddt, da, db.astype(b.dtype), dc.astype(c.dtype)


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


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


def chunked_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    *, chunk: int, block: int,
):
    """The plain chunked form of `selective_scan`, whose arguments and
    results these are. Any T: the last chunk is padded with steps of dt 0,
    which decay nothing and add nothing."""
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


def _programs(x, n: int, b, c, tiles: Tiles) -> list[tuple]:
    """The keys of the two kernel programs one scan needs, as jax tells
    programs apart: kernel, shapes, dtypes, tiles."""
    shape = (*x.shape, n, x.dtype.name, b.dtype.name, c.dtype.name, tiles)
    return [("forward", *shape), ("backward", *shape)]


def selective_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
    *, chunk: int = 64, block: int = 16,
):
    """The scan over a whole sequence.

    x (B, T, D); dt (B, T, D) positive time steps; a (D, N) negative; b, c
    (B, T, N); the state starts at zero. Returns (y (B, T, D) float32, the
    state after position T - 1 (B, D, N) float32). The `D x` skip term of the
    mixer is the caller's. `chunk` and `block` are the chunked form's."""
    (bsz, t, d), n = x.shape, a.shape[1]
    tiles = None
    if programs.traced_for_tpu():
        tiles = _kernel_tiles(t, d, n, (x.dtype, b.dtype, c.dtype))
    if tiles is None:
        programs.note("scan", "plain")
        return chunked_scan(x, dt, a, b, c, chunk=chunk, block=block)
    programs.note("scan", "kernel", _programs(x, n, b, c, tiles))
    # (N, D): the channels in the lanes
    args = (x, dt.astype(jnp.float32), a.astype(jnp.float32).T, b, c)
    y, last = _kernel_scan(*args, tiles, False)
    # the backward program is traced HERE, into jax's cache of traces, and
    # found there by the backward pass (ops/groupmm.py has the measurement)
    jax.eval_shape(
        functools.partial(_backward_kernel, tiles=tiles), *args,
        jax.ShapeDtypeStruct((bsz, t // tiles.rows, n, d), jnp.float32),
        y, last)
    return y, jnp.swapaxes(last, 1, 2)

