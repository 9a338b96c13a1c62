"""The passes over a decoder's n residual streams (hyper-connections): the
mapping's products with the read, and the write-back, each with a backward
pass. Three entry points (`map_streams`, `read_streams`, `write_streams`)
and two ways down from them.

For streams x (n, B, T, C), a sub-layer's phi (n C, m), m = n^2 + 2n, its
alpha_pre, b_pre (n) and, from what the caller makes of a (the rest of the
mapping: its own arithmetic on m floats a token), H_post (n) and H_res
(n, n):

    r = 1 / sqrt(mean over the n C of x^2 + eps)                 (float32)
    a = (sum_i x[i] phi[i]) r       operands as stored, float32 accumulated
    H_pre = sigmoid(alpha_pre a[:n] + b_pre)
    u = round(sum_i H_pre[i] x[i])                        float32 inside
    x'[i] = round(sum_j H_res[i, j] x[j] + H_post[i] y)   float32 inside

**The plain form** (`plain_maps`, `plain_read`, `plain_write`): plain
`jax.numpy`, each pass over the streams under a `jax.checkpoint` of its own
so that autodiff keeps the streams as they are stored and never their float32
copy; autodiff derives the backward passes. The CPU, every tier-1 test, every
shape the kernels do not take, and the kernels' reference. (On the chip its
compiler moves three to five times the bytes these passes need: it splits
each into several fusions and stores a float32 copy of the streams between
them. PERF.md section 6, PR 44.)

**The kernels.** Where the step is traced for a TPU and the shape fits
(`_kernel_rows`: C whole lane tiles, T a multiple of the block of tokens, 2
to `_MAX_STREAMS` streams, x bfloat16 or float32 and phi and y of its dtype,
the blocks within VMEM), four Pallas programs of this module's own under two
`custom_vjp`s, each ONE read of a token's streams from HBM. All run a grid
over (sequence, block of `rows` tokens) with all n streams of a block in
VMEM; inside, a loop takes `_CHUNK` tokens at a time and, within it, a loop
`_UNROLL` lane tiles of their C, widened to float32 in registers (both real
loops: written out over a token's 28 lane tiles the programs trace and lower
two seconds slower a step, PERF.md section 6, PR 45). In a
kernel a token's coefficients lie along the lanes of a (rows, 128) float32
tile, tokens in the sublanes, so that a token's scalar is one column spread
along the lanes of its C; in HBM they are (B, 128, T) float32, the token
LAST as the mapping's 4 x 4 arithmetic has it, and a kernel turns a block of
them in VMEM (a Pallas call pins its operands' layouts, and coefficients
handed over (B, T, 128) made the chip's compiler lay the whole Sinkhorn
chain out with its 16 entries minor: a copy an iteration and 7 ms a step,
PERF.md section 6, PR 45).

  * `streams_map_read`: the sum of squares over the n C, the n products with
    phi's rows on the MXU (phi padded to a lane tile of columns), a, H_pre
    and u from one load of the block. Writes u and a (with r in the row
    after a's m). Bytes: n C + C.
  * `streams_write`: x' from one load of the block of x and y, one rounding,
    one store: 2 n C + C.
  * `streams_write_pull` (given g, the cotangent of x'): d x[j] = sum_i
    H_res[i, j] g[i], d y = sum_i H_post[i] g[i], d H_res[i, j] = sum over C
    of g[i] x[j], d H_post[i] = sum over C of g[i] y: 3 n C + 2 C.
  * `streams_map_read_pull` (given d u, d a and the write-back's d x, which
    is added into in place): d H_pre[i] = sum over C of d u x[i] folded into
    d a; d x[i] += H_pre[i] d u + (d a r) phi[i]^T - x[i] (d a . a) r^2 /
    (n C); d phi[i] = x[i]^T (d a r), summed over the tokens IN the kernel in
    float32 (the block of x is in VMEM already: one XLA product over the
    emitted rows would read the streams once more); d alpha_pre and d b_pre
    summed in the kernel too. d a r is float32 and x, phi bfloat16: it goes
    to the MXU as two bfloat16 terms side by side along the contraction,
    which a lane tile of K has room for. Residuals: x, phi, the gates and a.
    Bytes: 3 n C + C.

The write-back's d x reaches the mapping's backward kernel because
`map_streams` hands the streams on (`Read.x`: the same values) and
`write_streams` is given those: jax then sends that cotangent into the first
`custom_vjp`, where the kernel adds to it, and no pass sums two d x.

Every call reaches the programs through four `jax.jit`ted functions, so all
sub-layers of one shape, their recomputation under the sub-layer's
`jax.checkpoint` and their backward share FOUR kernel programs; the backward
ones are traced on the way forward (`jax.eval_shape`; `ops/groupmm.py` has
the measurement).

`map_streams` and `write_streams` note the way they went ("kernel", "plain")
and the kernel programs they need (`ops/programs.py`, op `streams`), for the
Trainer's `streams_program` telemetry record.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.ops import programs

_LANES = 128
# tokens one turn of a kernel's loop takes: one tile of a two-byte dtype, two
# of float32
_CHUNK = 16
# lane tiles a turn of the loop over a chunk's C takes
_UNROLL = 4
# tokens a block, the largest that divides T and fits (whole lane tiles:
# the tokens are the lanes of the coefficients' blocks)
_ROWS = (256, 128)
_MAX_STREAMS = 8
# of the chip's 128 MiB of VMEM; the blocks of one grid step, buffered twice,
# may take `_BLOCK_BYTES` of it (the write-back's backward holds 3 n + 2
# blocks of rows x C: 49 MiB at n 4, 256 x 3,584, bfloat16)
_VMEM_LIMIT = 100 * 2 ** 20
_BLOCK_BYTES = 56 * 2 ** 20


class Read(NamedTuple):
    """What the mapping's kernel has read already: u, and the streams as the
    write-back is to take them."""

    u: jax.Array
    x: jax.Array


def _kernel_rows(x, *same_dtype) -> Optional[int]:
    """The kernels' block of tokens for streams x (n, B, T, C), or None where
    the plain form stays: x not bfloat16 or float32, another array of the
    call in another dtype, one stream or more than `_MAX_STREAMS`, a C that
    is no whole number of lane tiles, or a T that no block divides within
    VMEM."""
    n, _, t, c = x.shape
    if x.dtype not in (jnp.bfloat16, jnp.float32):
        return None
    if any(v.dtype != x.dtype for v in same_dtype):
        return None
    if c % _LANES or not 2 <= n <= _MAX_STREAMS:
        return None
    for rows in _ROWS:
        held = 2 * (3 * n + 2) * rows * c * x.dtype.itemsize
        if t % rows == 0 and held <= _BLOCK_BYTES:
            return rows
    return None


def _pallas():
    """Pallas, imported where a kernel is wanted (the CPU and the models
    with one residual stream never pay for it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def _lane_tiles(pl, c: int, turn, carry=()):
    """`carry = turn(a lane tile of the C, carry)` for every lane tile, as a
    loop of `_UNROLL` tiles a turn where that divides them (Mosaic unrolls a
    loop whole or not at all): written out over all 28 tiles of the cell's
    C, the four kernels cost a step's first trace 1.2 s and its lowering 0.8
    s more on the chip's host (PERF.md section 6, PR 45)."""
    tiles = c // _LANES
    unroll = max(u for u in range(1, _UNROLL + 1) if tiles % u == 0)

    def body(q, carry):
        for k in range(unroll):
            carry = turn(pl.ds(pl.multiple_of(
                (q * unroll + k) * _LANES, _LANES), _LANES), carry)
        return carry

    return lax.fori_loop(0, tiles // unroll, body, carry)


def _lane(shape):
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _across(tile, k: int):
    """Column k of a (rows, 128) tile along all its lanes: a token's scalar
    as the lanes of its C take it."""
    return jnp.broadcast_to(tile[:, k:k + 1], tile.shape)


def _placed(columns, shape):
    """(rows, 1) sums over the lanes into lanes 0, 1, ... of a (rows, 128)
    tile of zeros."""
    lane = _lane(shape)
    out = jnp.zeros(shape, jnp.float32)
    for k, column in enumerate(columns):
        out = jnp.where(lane == k, column, out)
    return out


def _over_lanes(tile):
    return jnp.sum(tile, axis=1, keepdims=True)


def _chunks(pl, rows: int, turn) -> None:
    """`turn(the chunk's tokens)` for every chunk of a block."""

    def body(q, carry):
        turn(pl.ds(pl.multiple_of(q * _CHUNK, _CHUNK), _CHUNK))
        return carry

    lax.fori_loop(0, rows // _CHUNK, body, 0)


def _call(kernel, name: str, interpret: bool, **spec):
    """`pl.pallas_call` over a grid of (sequence, block of tokens), every
    axis in order: the mapping's backward carries its sums from one grid
    step to the next."""
    pl, pltpu = _pallas()
    return pl.pallas_call(
        kernel, **spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


def _specs(pl, x, rows: int):
    """The windows on the streams (n, B, T, C), on an array of one stream's
    shape (B, T, C) and on the tokens' coefficients (B, 128, T)."""
    n, _, _, c = x.shape
    return (
        pl.BlockSpec((n, 1, rows, c), lambda s, i: (0, s, i, 0)),
        pl.BlockSpec((1, rows, c), lambda s, i: (s, i, 0)),
        pl.BlockSpec((1, _LANES, rows), lambda s, i: (s, 0, i)))


def _whole(pl, shape):
    return pl.BlockSpec(shape, lambda s, i: (0,) * len(shape))


@functools.partial(
    jax.jit, static_argnames=("m", "rows", "eps", "interpret"))
def _map_read(x, phi, gates, *, m: int, rows: int, eps: float,
              interpret: bool = False):
    """x (n, B, T, C); phi (n, C, 128) of x's dtype, zeros from column m on;
    gates (8, 128) float32, row 0 alpha_pre and row 1 b_pre in lanes :n,
    zeros elsewhere. Returns u (B, T, C) in x's dtype and (B, 128, T)
    float32 with a in rows :m and r in row m."""
    pl, pltpu = _pallas()
    n, bsz, t, c = x.shape
    f32 = jnp.float32
    tile = (_CHUNK, _LANES)

    def kernel(x_ref, phi_ref, gate_ref, u_ref, a_ref, sq_ref, h_ref):
        def squares(tokens):
            def turn(lanes, acc):
                for i in range(n):
                    v = x_ref[i, 0, tokens, lanes].astype(f32)
                    acc = acc + v * v
                return acc

            acc = _lane_tiles(pl, c, turn, jnp.zeros(tile, f32))
            sq_ref[tokens, :] = jnp.broadcast_to(_over_lanes(acc), tile)

        _chunks(pl, rows, squares)
        p = jnp.dot(x_ref[0, 0], phi_ref[0], preferred_element_type=f32)
        for i in range(1, n):
            p = p + jnp.dot(
                x_ref[i, 0], phi_ref[i], preferred_element_type=f32)
        inv = lax.rsqrt(sq_ref[...] / (n * c) + eps)
        a = p * inv
        a_ref[0] = jnp.where(_lane(a.shape) == m, inv, a).T
        h_ref[...] = jax.nn.sigmoid(
            gate_ref[0:1, :] * a + gate_ref[1:2, :])

        def read(tokens):
            h = h_ref[tokens, :]
            pre = [_across(h, i) for i in range(n)]

            def turn(lanes, carry):
                u = pre[0] * x_ref[0, 0, tokens, lanes].astype(f32)
                for i in range(1, n):
                    u = u + pre[i] * x_ref[i, 0, tokens, lanes].astype(f32)
                u_ref[0, tokens, lanes] = u.astype(u_ref.dtype)
                return carry

            _lane_tiles(pl, c, turn)

        _chunks(pl, rows, read)

    streams, one, coef = _specs(pl, x, rows)
    return _call(
        kernel, "streams_map_read", interpret,
        out_shape=[jax.ShapeDtypeStruct((bsz, t, c), x.dtype),
                   jax.ShapeDtypeStruct((bsz, _LANES, t), f32)],
        grid=(bsz, t // rows),
        in_specs=[streams, _whole(pl, phi.shape), _whole(pl, gates.shape)],
        out_specs=[one, coef],
        scratch_shapes=[pltpu.VMEM((rows, _LANES), f32)] * 2,
    )(x, phi, gates)


def _two_terms(pltpu, v, m: int, dtype):
    """v (rows, 128) float32 with its values in lanes :m, as the MXU's left
    operand against a right operand of `dtype`: itself where that is
    float32; else two bfloat16 terms whose sum is v to 16 bits, the second
    in the lanes from `_second(m)` on (a tile of its own where one has no
    room for both)."""
    if dtype == jnp.float32:
        return v
    hi = v.astype(dtype)
    lo = (v - hi.astype(jnp.float32)).astype(dtype)
    if _second(m) == m:
        return jnp.where(
            _lane(v.shape) < m, hi.astype(jnp.float32),
            pltpu.roll(lo.astype(jnp.float32), m, 1)).astype(dtype)
    return jnp.concatenate([hi, lo], axis=1)


def _second(m: int) -> int:
    """The lane at which the second term of `_two_terms` starts."""
    return m if 2 * m <= _LANES else _LANES


def _term_rows(m: int, dtype) -> int:
    """Rows of `_two_terms`' transpose that hold anything, in whole sublane
    tiles of `dtype`."""
    used = m if dtype == jnp.float32 else _second(m) + m
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    return -(-used // tile) * tile


@functools.partial(
    jax.jit, static_argnames=("m", "rows", "interpret"))
def _map_read_pull(x, phi_t, gates, a, du, da, dx, *, m: int, rows: int,
                   interpret: bool = False):
    """x, gates and a as `_map_read` took and gave them; phi_t (n, K, C):
    phi[i]^T in rows :m and again from row `_second(m)` on (once where x is
    float32), zeros elsewhere; du (B, T, C) and da (B, 128, T) float32 the
    cotangents of u and a; dx (n, B, T, C) what the write-back's backward
    gave, added into. Returns d x (n, B, T, C), d phi^T (n, rows of
    `_term_rows`, C) float32 as the two terms' parts, and d gates (8, 128)
    float32."""
    pl, pltpu = _pallas()
    n, bsz, t, c = x.shape
    f32 = jnp.float32
    tile = (_CHUNK, _LANES)
    held = _term_rows(m, x.dtype)

    def kernel(x_ref, phit_ref, gate_ref, a_ref, du_ref, da_ref, part_ref,
               dx_ref, dphi_ref, dgate_ref, coef_ref, t_ref):
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            dphi_ref[...] = jnp.zeros(dphi_ref.shape, f32)
            dgate_ref[...] = jnp.zeros(dgate_ref.shape, f32)

        def gate_pull(tokens):  # d H_pre[i] = sum over C of d u x[i]
            def turn(lanes, accs):
                d = du_ref[0, tokens, lanes].astype(f32)
                return tuple(
                    acc + d * x_ref[i, 0, tokens, lanes].astype(f32)
                    for i, acc in enumerate(accs))

            accs = _lane_tiles(pl, c, turn, (jnp.zeros(tile, f32),) * n)
            coef_ref[tokens, :] = _placed(
                [_over_lanes(acc) for acc in accs], tile)

        _chunks(pl, rows, gate_pull)
        lane = _lane((rows, _LANES))
        a = a_ref[0].T  # a token's coefficients along the lanes
        inv = _across(a, m)
        a = jnp.where(lane < m, a, 0.0)
        alpha = gate_ref[0:1, :]
        h = jax.nn.sigmoid(alpha * a + gate_ref[1:2, :])
        dz = coef_ref[...] * h * (1.0 - h)  # zeros from lane n on
        dgate_ref[0:1, :] += jnp.sum(dz * a, axis=0, keepdims=True)
        dgate_ref[1:2, :] += jnp.sum(dz, axis=0, keepdims=True)
        d_a = jnp.where(lane < m, da_ref[0].T, 0.0) + alpha * dz
        # the norm's part: d x[i] gets x[i] times this
        scaled = -_over_lanes(d_a * a) * inv * inv / (n * c)
        coef_ref[...] = jnp.where(
            lane < n, h, jnp.where(lane == n, scaled, 0.0))
        dp = _two_terms(pltpu, d_a * inv, m, x_ref.dtype)
        dp_t = dp.astype(f32).T[:held].astype(dp.dtype)
        for i in range(n):
            t_ref[...] = jnp.dot(
                dp, phit_ref[i], preferred_element_type=f32)
            dphi_ref[i] += jnp.dot(
                dp_t, x_ref[i, 0], preferred_element_type=f32)

            def pull(tokens, i=i):
                coef = coef_ref[tokens, :]
                pre, norm = _across(coef, i), _across(coef, n)

                def turn(lanes, carry):
                    d = (pre * du_ref[0, tokens, lanes].astype(f32)
                         + t_ref[tokens, lanes]
                         + norm * x_ref[i, 0, tokens, lanes].astype(f32)
                         + part_ref[i, 0, tokens, lanes].astype(f32))
                    dx_ref[i, 0, tokens, lanes] = d.astype(dx_ref.dtype)
                    return carry

                _lane_tiles(pl, c, turn)

            _chunks(pl, rows, pull)

    streams, one, coef = _specs(pl, x, rows)
    return _call(
        kernel, "streams_map_read_pull", interpret,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, held, c), f32),
                   jax.ShapeDtypeStruct(gates.shape, f32)],
        grid=(bsz, t // rows),
        in_specs=[streams, _whole(pl, phi_t.shape), _whole(pl, gates.shape),
                  coef, one, coef, streams],
        out_specs=[streams, _whole(pl, (n, held, c)),
                   _whole(pl, gates.shape)],
        scratch_shapes=[pltpu.VMEM((rows, _LANES), f32),
                        pltpu.VMEM((rows, c), f32)],
        input_output_aliases={6: 0},
    )(x, phi_t, gates, a, du, da, dx)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def _write(x, y, coef, *, rows: int, interpret: bool = False):
    """x (n, B, T, C); y (B, T, C); coef (B, 128, T) float32 with H_res[i,
    j] in row i n + j and H_post[i] in row n n + i. Returns x' in x's
    dtype."""
    pl, pltpu = _pallas()
    n, bsz, t, c = x.shape
    f32 = jnp.float32

    def kernel(x_ref, y_ref, coef_ref, out_ref, lanes_ref):
        lanes_ref[...] = coef_ref[0].T  # a token's along the lanes

        def write(tokens):
            coef = lanes_ref[tokens, :]
            res = [[_across(coef, i * n + j) for j in range(n)]
                   for i in range(n)]
            post = [_across(coef, n * n + i) for i in range(n)]

            def turn(lanes, carry):
                xs = [x_ref[j, 0, tokens, lanes].astype(f32)
                      for j in range(n)]
                y32 = y_ref[0, tokens, lanes].astype(f32)
                for i in range(n):
                    mixed = res[i][0] * xs[0]
                    for j in range(1, n):
                        mixed = mixed + res[i][j] * xs[j]
                    out_ref[i, 0, tokens, lanes] = (
                        post[i] * y32 + mixed).astype(out_ref.dtype)
                return carry

            _lane_tiles(pl, c, turn)

        _chunks(pl, rows, write)

    streams, one, coefs = _specs(pl, x, rows)
    return _call(
        kernel, "streams_write", interpret,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(bsz, t // rows),
        in_specs=[streams, one, coefs], out_specs=streams,
        scratch_shapes=[pltpu.VMEM((rows, _LANES), f32)],
    )(x, y, coef)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def _write_pull(x, y, coef, g, *, rows: int, interpret: bool = False):
    """`_write`'s arguments and g (n, B, T, C), the cotangent of x'.
    Returns d x (n, B, T, C) and d y (B, T, C) in x's dtype and d coef (B,
    128, T) float32, laid out as coef."""
    pl, pltpu = _pallas()
    n, bsz, t, c = x.shape
    f32 = jnp.float32
    tile = (_CHUNK, _LANES)

    def kernel(x_ref, y_ref, coef_ref, g_ref, dx_ref, dy_ref, dcoef_ref,
               lanes_ref):
        lanes_ref[...] = coef_ref[0].T  # a token's along the lanes

        def pull(tokens):
            coef = lanes_ref[tokens, :]
            zeros = (jnp.zeros(tile, f32),) * n

            def mixed(weights, with_ref, to_ref):
                """to = sum_i weights[i] g[i] and the n sums over C of g[i]
                times `with`, a lane tile after another."""

                def turn(lanes, accs):
                    gs = [g_ref[i, 0, tokens, lanes].astype(f32)
                          for i in range(n)]
                    other = with_ref[tokens, lanes].astype(f32)
                    d = weights[0] * gs[0]
                    for i in range(1, n):
                        d = d + weights[i] * gs[i]
                    to_ref[tokens, lanes] = d.astype(to_ref.dtype)
                    return tuple(
                        acc + gi * other for acc, gi in zip(accs, gs))

                return _lane_tiles(pl, c, turn, zeros)

            # a stream at a time: its column of H_res and the n sums over C
            # it takes part in are all a turn holds in registers
            sums = [
                mixed([_across(coef, i * n + j) for i in range(n)],
                      x_ref.at[j, 0], dx_ref.at[j, 0])
                for j in range(n)]
            accs = mixed(
                [_across(coef, n * n + i) for i in range(n)],
                y_ref.at[0], dy_ref.at[0])
            lanes_ref[tokens, :] = _placed(
                [_over_lanes(sums[j][i]) for i in range(n) for j in range(n)]
                + [_over_lanes(acc) for acc in accs], tile)

        _chunks(pl, rows, pull)
        dcoef_ref[0] = lanes_ref[...].T

    streams, one, coefs = _specs(pl, x, rows)
    return _call(
        kernel, "streams_write_pull", interpret,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(coef.shape, f32)],
        grid=(bsz, t // rows),
        in_specs=[streams, one, coefs, streams],
        out_specs=[streams, one, coefs],
        scratch_shapes=[pltpu.VMEM((rows, _LANES), f32)],
    )(x, y, coef, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernel_maps(x, phi, gates, rows: int, eps: float,
                 interpret: bool = False):
    """The mapping's two kernels as one differentiable function: x (n, B, T,
    C), phi (n, C, m) of x's dtype, gates (8, 128) float32 (`_gates`) -> (u
    (B, T, C), (B, 128, T) float32 with a in rows :m and r in row m, x
    handed on). `interpret` runs them without a TPU (the tests' way in)."""
    return _kernel_maps_fwd(x, phi, gates, rows, eps, interpret)[0]


def _kernel_maps_fwd(x, phi, gates, rows, eps, interpret):
    m = phi.shape[-1]
    u, a = _map_read(
        x, jnp.pad(phi, ((0, 0), (0, 0), (0, _LANES - m))), gates,
        m=m, rows=rows, eps=eps, interpret=interpret)
    return (u, a, x), (x, phi, gates, a)


def _kernel_maps_bwd(rows, eps, interpret, residuals, cotangents):
    del eps
    x, phi, gates, a = residuals
    du, da, dx = cotangents
    m = phi.shape[-1]
    # phi[i]^T once for each term of `_two_terms`, where that puts them
    starts = (0,) if x.dtype == jnp.float32 else (0, _second(m))
    phi_t = jnp.swapaxes(phi, 1, 2)  # (n, m, C)
    phi_t = jnp.concatenate([
        jnp.pad(phi_t, ((0, 0), (0, start - m), (0, 0)))
        for start in starts[1:]] + [phi_t], axis=1)
    phi_t = jnp.pad(
        phi_t, ((0, 0), (0, -phi_t.shape[1] % _LANES), (0, 0)))
    dx, dphi_t, dgates = _map_read_pull(
        x, phi_t, gates, a, du, da, dx, m=m, rows=rows, interpret=interpret)
    dphi_t = sum(dphi_t[:, start:start + m] for start in starts)
    return dx, jnp.swapaxes(dphi_t, 1, 2).astype(phi.dtype), dgates


_kernel_maps.defvjp(_kernel_maps_fwd, _kernel_maps_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_write(x, y, coef, rows: int, interpret: bool = False):
    """The write-back's two kernels as one differentiable function: x (n, B,
    T, C), y (B, T, C), coef (B, 128, T) float32 (`_coefficients`) -> x'."""
    return _write(x, y, coef, rows=rows, interpret=interpret)


def _kernel_write_fwd(x, y, coef, rows, interpret):
    return (_write(x, y, coef, rows=rows, interpret=interpret),
            (x, y, coef))


def _kernel_write_bwd(rows, interpret, residuals, g):
    return _write_pull(*residuals, g, rows=rows, interpret=interpret)


_kernel_write.defvjp(_kernel_write_fwd, _kernel_write_bwd)


def _gates(alpha, b, n: int):
    """alpha (.,) whose first is alpha_pre and b (m,) whose first n are
    b_pre, as the kernels take them: (8, 128) float32, row 0 alpha_pre and
    row 1 b_pre in lanes :n."""
    rows = jnp.stack([
        jnp.broadcast_to(alpha[0], (n,)), b[:n]]).astype(jnp.float32)
    return jnp.pad(rows, ((0, 6), (0, _LANES - n)))


def _coefficients(res, post):
    """H_res (n, n, B, T) and H_post (n, B, T), token last, as the
    write-back's kernels take them: (B, 128, T) float32, H_res[i, j] in row
    i n + j and H_post[i] in row n n + i."""
    n = post.shape[0]
    rows = jnp.concatenate(
        [res.reshape(n * n, *post.shape[1:]), post]).astype(jnp.float32)
    return jnp.pad(
        jnp.moveaxis(rows, 0, 1), ((0, 0), (0, _LANES - n * n - n), (0, 0)))


@jax.checkpoint
def _inverse_rms(x: jax.Array, eps: float) -> jax.Array:
    """1 / sqrt(mean over the n streams' C of x^2 + eps): (B, T) float32."""
    n, _, _, c = x.shape
    return lax.rsqrt(
        jnp.sum(jnp.square(x.astype(jnp.float32)), axis=(0, 3)) / (n * c)
        + eps)


def plain_maps(phi: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """The plain form of `map_streams`' a: (m, B, T) float32, the token's
    position last. The norm needs no copy of the streams: x~ phi = (x phi)
    over the token's rms, a sum of squares and n products with phi's rows."""
    n, _, _, c = x.shape
    f32 = jnp.float32
    phi = phi.reshape(n, c, phi.shape[-1])
    a = sum(
        jnp.dot(x[i], phi[i], preferred_element_type=f32) for i in range(n))
    return jnp.moveaxis(a * _inverse_rms(x, eps)[..., None], -1, 0)


@jax.checkpoint
def plain_read(x: jax.Array, pre: jax.Array) -> jax.Array:
    """u = sum_i H_pre[i] x[i]: (B, T, C) in x's dtype, float32 inside."""
    return jnp.sum(
        pre[..., None] * x.astype(jnp.float32), axis=0).astype(x.dtype)


@jax.checkpoint
def plain_write(x: jax.Array, res: jax.Array, post: jax.Array,
                y: jax.Array) -> jax.Array:
    """x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y, float32 inside."""
    # a stream at a time from the stored slices, then stacked: of the forms
    # tried on the chip's compiler (one broadcast product summed over `from`;
    # slices of the float32 of all four) this one moves the fewest bytes, 1.5
    # GB forward and 3.4 backward at the cell's size against 2.7 and 3.9 to
    # 5.4 (what it needs: 0.5 and 0.8; PERF.md section 6, PR 44)
    n = x.shape[0]
    y32 = y.astype(jnp.float32)
    return jnp.stack([
        (post[i][..., None] * y32 + sum(
            res[i, j][..., None] * x[j].astype(jnp.float32)
            for j in range(n))).astype(x.dtype)
        for i in range(n)])


def _programs(kernels: tuple[str, str], x, rows: int) -> list[tuple]:
    """The keys of the two kernel programs one call needs, as jax tells
    programs apart: kernel, shapes, dtype, block."""
    return [(kernel, *x.shape, x.dtype.name, rows) for kernel in kernels]


def map_streams(phi: jax.Array, b: jax.Array, alpha: jax.Array,
                x: jax.Array, eps: float):
    """The products of a sub-layer's mapping from the streams x (n, B, T, C):
    phi (n C, m), b (m,) whose first n are b_pre, alpha whose first is
    alpha_pre. Returns (a (m, B, T) float32, the token's position last: the
    normalised products, from which the caller makes H_pre, H_post and
    H_res; what `read_streams` and `write_streams` take: None, or the
    kernel's `Read`, which has made H_pre and the read from the same load)."""
    n, _, _, c = x.shape
    rows = None
    if programs.traced_for_tpu() and phi.shape[0] == n * c:
        rows = _kernel_rows(x, phi)
    if rows is None:
        programs.note("streams", "plain")
        return plain_maps(phi, x, eps), None
    programs.note("streams", "kernel", _programs(
        ("map_read", "map_read_pull"), x, rows))
    return _maps_by_kernels(phi, b, alpha, x, eps, rows)


def _maps_by_kernels(phi, b, alpha, x, eps, rows: int,
                     interpret: bool = False):
    """`map_streams` down the kernels."""
    n, _, _, c = x.shape
    m = phi.shape[-1]
    args = (x, phi.reshape(n, c, m), _gates(alpha, b, n))
    out = _kernel_maps(*args, rows, float(eps), interpret)
    # the backward program is traced HERE, into jax's cache of traces, and
    # found there by the backward pass (ops/groupmm.py has the measurement)
    jax.eval_shape(
        functools.partial(_kernel_maps_bwd, rows, float(eps), interpret),
        (*args, out[1]), out)
    u, a, x = out
    return jnp.moveaxis(a[:, :m], 1, 0), Read(u, x)


def read_streams(x: jax.Array, pre: jax.Array, read: Optional[Read] = None):
    """(u = sum_i H_pre[i] x[i] in x's dtype, float32 inside; the streams
    for `write_streams`): `read` where `map_streams` gave one, whose kernel
    made H_pre itself from what made `pre`."""
    if read is not None:
        return read
    return Read(plain_read(x, pre), x)


def write_streams(x: jax.Array, res: jax.Array, post: jax.Array,
                  y: jax.Array) -> jax.Array:
    """x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y in x's dtype, float32
    inside: x (n, B, T, C), res (n, n, B, T) as [to, from] and post (n, B,
    T), the token's position last, y (B, T, C)."""
    rows = None
    if programs.traced_for_tpu() and y.shape == x.shape[1:]:
        rows = _kernel_rows(x, y)
    if rows is None:
        programs.note("streams", "plain")
        return plain_write(x, res, post, y)
    programs.note("streams", "kernel", _programs(
        ("write", "write_pull"), x, rows))
    return _write_by_kernels(x, res, post, y, rows)


def _write_by_kernels(x, res, post, y, rows: int, interpret: bool = False):
    """`write_streams` down the kernels."""
    args = (x, y, _coefficients(res, post))
    out = _kernel_write(*args, rows, interpret)
    jax.eval_shape(
        functools.partial(_kernel_write_bwd, rows, interpret), args, out)
    return out
